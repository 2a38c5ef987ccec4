"""Device time of one compressed-KV decode step: the device time inside
the ``bench.step`` spans (append with its flush, then attend), per step."""


def read(t):
    steps = t.named("bench.step")
    busy = t.busy_s(steps) if steps else 0.0
    if busy <= 0:
        return None
    return 1000.0 * busy / len(steps)
