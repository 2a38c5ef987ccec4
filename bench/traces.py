"""From a profiler trace to the numbers the per-layer readers take.

The benchmark wraps each call it times in a host span of its own
(``jax.profiler.TraceAnnotation`` named ``bench.<what>``).  Device time is
attributed to those spans by overlap on the trace's clock, never by the
names of kernels or fusions, so a change of implementation is read by the
same yardstick.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import tempfile
from typing import Callable

SPAN_PREFIX = "bench."
WINDOW = "bench.window"


def tpu_ops(plane_name: str, line_name: str) -> bool:
    """The lines that hold a TPU's device operations."""
    return plane_name.startswith("/device:TPU:") and line_name == "XLA Ops"


@dataclasses.dataclass
class Trace:
    """Host spans and device operations of one traced window, in seconds
    on the trace's clock."""
    spans: list[tuple[str, float, float]]                 # bench.* host spans
    host: list[tuple[str, float, float, int]]             # other host events, with depth
    ops: dict[str, list[tuple[str, float, float]]]        # per device plane
    work: dict = dataclasses.field(default_factory=dict)  # counts the run supplies
    peaks: dict = dataclasses.field(default_factory=dict)

    def describe(self) -> str:
        """Each device plane's operation count and extent against the
        window, for the run's log: a trace whose clocks disagree shows here."""
        a, b = self.window
        return "; ".join(f"{d}: {len(ev)} ops over [{min(s for _, s, _ in ev) - a:+.3f}, "
                         f"{max(e for _, _, e in ev) - a:+.3f}] s of a {b - a:.3f} s window"
                         for d, ev in self.ops.items() if ev) or "no device operations"

    def named(self, name: str) -> list[tuple[float, float]]:
        return [(a, b) for n, a, b in self.spans if n == name]

    @property
    def window(self) -> tuple[float, float]:
        w = self.named(WINDOW)
        if len(w) != 1:
            raise ValueError(f"trace holds {len(w)} {WINDOW} spans, expected 1")
        return w[0]

    @property
    def window_s(self) -> float:
        a, b = self.window
        return b - a

    def busy(self, device: str, within: list[tuple[float, float]] | None = None) -> float:
        """Seconds in which an operation ran on ``device``, inside the
        union of ``within`` (default: the window)."""
        cover = _union(within if within is not None else [self.window])
        busy = _union([(a, b) for _, a, b in self.ops.get(device, [])])
        return _overlap(busy, cover)

    def busy_s(self, within: list[tuple[float, float]] | None = None) -> float:
        """Busy seconds averaged over the devices that ran anything."""
        devs = [d for d, ev in self.ops.items() if ev]
        if not devs:
            return 0.0
        return sum(self.busy(d, within) for d in devs) / len(devs)

    def device_ops(self, top: int = 10) -> list[list]:
        """Device operations by total time inside the window (averaged
        over devices), longest first."""
        a, b = self.window
        tot: dict[str, float] = {}
        devs = [d for d, ev in self.ops.items() if ev]
        for d in devs:
            for n, s, e in self.ops[d]:
                dt = min(e, b) - max(s, a)
                if dt > 0:
                    tot[n] = tot.get(n, 0.0) + dt / len(devs)
        return [[n, t] for n, t in sorted(tot.items(), key=lambda kv: -kv[1])[:top]]

    def idle_gaps(self, top: int = 10) -> list[list]:
        """The longest gaps in which the first busy device ran nothing,
        named by the innermost host event (a bench span first) that covers
        the gap's middle."""
        devs = [d for d, ev in self.ops.items() if ev]
        if not devs:
            return []
        a, b = self.window
        busy = _union([(s, e) for _, s, e in self.ops[devs[0]]])
        gaps, t = [], a
        for s, e in busy:
            if s > t:
                gaps.append((t, min(s, b)))
            t = max(t, e)
        if t < b:
            gaps.append((t, b))
        gaps = sorted((g for g in gaps if g[1] > g[0]), key=lambda g: g[0] - g[1])[:top]
        return [[self._host_at((s + e) / 2), e - s] for s, e in gaps]

    def _host_at(self, t: float) -> str:
        span = [(b - a, n) for n, a, b in self.spans if a <= t <= b and n != WINDOW]
        name = min(span)[1] if span else WINDOW
        inner = [(-d, b - a, n) for n, a, b, d in self.host if a <= t <= b]
        return f"{name}/{min(inner)[2]}" if inner else name


def _union(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _overlap(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> float:
    """Total length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def reduce(path: str, is_ops: Callable[[str, str], bool] = tpu_ops) -> Trace:
    """Read an ``.xplane.pb`` file.  ``is_ops(plane, line)`` picks the
    lines whose events are device operations."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, host, ops = [], [], {}
    for plane in pd.planes:
        for line in plane.lines:
            if is_ops(plane.name, line.name):
                ops.setdefault(plane.name, []).extend(
                    (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events if e.duration_ns > 0)
            elif plane.name.startswith("/host:"):
                stack: list[float] = []
                for e in sorted(line.events, key=lambda e: (e.start_ns, -e.duration_ns)):
                    s, t = e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9
                    while stack and stack[-1] <= s:
                        stack.pop()
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, s, t))
                    elif e.duration_ns > 0:
                        host.append((e.name, s, t, len(stack)))
                    stack.append(t)
    if not any(ops.values()):
        seen = sorted({(p.name, ln.name) for p in pd.planes for ln in p.lines})
        raise RuntimeError(f"no device operations in the trace; its lines: {seen}")
    return Trace(spans=spans, host=host, ops=ops)


def traced(fn: Callable[[], object], is_ops: Callable[[str, str], bool] = tpu_ops):
    """Run ``fn`` under the profiler, the trace in a temporary directory
    that is removed before returning.  Returns (fn's result, Trace)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as d:
        with jax.profiler.trace(d, profiler_options=opts):
            result = fn()
        found = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
        if len(found) != 1:
            raise RuntimeError(f"the profiler wrote {len(found)} .xplane.pb files")
        return result, reduce(found[0], is_ops)
