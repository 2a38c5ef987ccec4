"""The whole MLA decode step's share of the chip's bf16 peak: the FLOPs the
traced steps need in the absorbed form (``drivers/mla_decode.py::mla_flops``:
attention over the valid positions only, plus the projections) over the
device time inside the ``bench.step`` spans.  Whatever implements the
step, the work counted is the same, so this bounds any gain claimed on
the cell's token rate."""


def read(t):
    steps = t.named("bench.step")
    busy = t.busy_s(steps) if steps else 0.0
    if busy <= 0 or "mla_flops" not in t.work:
        return None
    return 100.0 * t.work["mla_flops"] / t.peaks["flops_bf16"] / busy
