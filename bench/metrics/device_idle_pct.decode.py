"""Share of the traced window in which the device ran nothing, in a cell
driven by ``bench.step`` calls."""


def read(t):
    if not t.named("bench.step") or not any(t.ops.values()):
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)
