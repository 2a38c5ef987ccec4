"""The benchmark: one cell of ``BENCHMARK.json`` on the chip.

  python3 bench/run.py --workload deepseek-7b-kv.roundtrip --seed 7 --seconds 20 --trace 0

Run from the root of a checkout that holds ``src/repro``.  The cell names
a configuration (``bench/configs/<file>``, whose value family is made by
``bench/values/<family>.py``), a traffic mix (``bench/traffic/<traffic>.json``,
driven by ``bench/drivers/<kind>.py`` for the mix's ``kind``) and its
limits (``bench/limits/<cell>.json``).  Per-layer metrics are read from
the profiler trace of a ``--trace 1`` run by ``bench/metrics/<metric>.py``.

Set-up (inputs made on the chip from ``--seed``, warm-up, compiles) is
``setup_s``; then the window runs for ``--seconds`` (with ``--trace 1``,
for at most the mix's ``trace_seconds``); then what the window produced
is compared with the plain reference.  The last stdout line is the JSON
result; the last stderr lines are the numbers compared, each with its
limit.  Without a TPU, or with fewer chips than the cell asks for, it
exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class Refused(Exception):
    """The run cannot measure this cell here; no result is printed."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_cell(doc: dict, name: str) -> tuple[dict, dict, dict, dict]:
    """(cell, configuration, traffic mix, limits) of the cell ``name``."""
    cells = {c["name"]: c for c in doc["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json; known: {sorted(cells)}")
    cell = cells[name]
    config = next(c for c in doc["configs"] if c["name"] == cell["config"])
    with open(ROOT / config["file"]) as fh:
        config = json.load(fh)
    with open(BENCH / "traffic" / f"{cell['traffic']}.json") as fh:
        mix = json.load(fh)
    with open(BENCH / "limits" / f"{name}.json") as fh:
        limits = json.load(fh)
    return cell, config, mix, limits


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def per_layer_for(doc: dict, cell: str) -> list[dict]:
    """The per-layer metrics this cell reports: those that list it, and
    those without a list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in doc["end_to_end"] if applies(m, cell)}
    return [m for m in doc["per_layer"]
            if cell in m.get("workloads", ()) or ("workloads" not in m and m["moves"] in e2e)]


def reader(name: str):
    import byname

    return byname.load("metrics", name).read


def peaks_of(kind: str) -> dict:
    with open(BENCH / "peaks.json") as fh:
        table = json.load(fh)
    if kind not in table or kind == "source":
        raise Refused(f"no published peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def run_cell(doc: dict, name: str, seed: int, seconds: float, trace: bool, *,
             devices, peaks: dict, t_start: float, is_ops=None,
             parts: tuple[dict, dict, dict, dict] | None = None) -> dict:
    """Set up, measure and check one cell; returns the result object.
    ``parts`` replaces what ``load_cell`` reads (tests use small sizes)."""
    import jax

    import cells
    import traces

    cell, config, mix, limits = parts or load_cell(doc, name)
    compiles = []
    listen = lambda event, duration, **kw: compiles.append(event) \
        if event in COMPILE_EVENTS else None  # noqa: E731
    jax.monitoring.register_event_duration_secs_listener(listen)
    drv = cells.driver(config, mix, seed)
    log(f"[{name}] seed {seed}: set-up")
    drv.setup()
    setup_s = time.perf_counter() - t_start
    log(f"[{name}] set-up {setup_s:.3f} s, {compiles.count(COMPILE_EVENTS[0])} compiles, "
        f"{compiles.count(COMPILE_EVENTS[1])} compile-cache hits; "
        f"window {seconds} s, trace {int(trace)}")
    compiles.clear()
    try:
        if trace:
            length = min(seconds, mix["trace_seconds"])
            w, tr = traces.traced(lambda: drv.window(length), is_ops or traces.tpu_ops)
        else:
            w = drv.window(seconds)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    mem = memory_peak(devices)
    log(f"[{name}] window {w['elapsed']:.3f} s, {w['attempted']} attempted; "
        f"compiles in window: {len(compiles)}")

    out = drv.outputs()
    t0 = time.perf_counter()
    numbers, failed = drv.check(out, limits)
    log(f"[{name}] reference check {time.perf_counter() - t0:.3f} s")
    correct = all(v["value"] <= v["limit"] for v in numbers.values())

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    metrics = {}
    if trace:
        log(f"[{name}] trace: {tr.describe()}")
        tr.work, tr.peaks = w["work"], peaks
        for m in per_layer_for(doc, name):
            v = reader(m["name"])(tr)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
    else:
        for m in doc["end_to_end"]:
            if applies(m, name):
                v = setup_s if m["name"] == "setup_s" else w["metrics"][m["name"]]
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": correct, "attempted": w["attempted"], "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": tr.device_ops(), "idle_gaps": tr.idle_gaps()}
    result["checks"] = numbers
    for k, v in numbers.items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        with open(ROOT / "BENCHMARK.json") as fh:
            doc = json.load(fh)
        cell, *_ = load_cell(doc, args.workload)
        if not (ROOT / "src" / "repro").is_dir():
            raise Refused(f"no program at {ROOT / 'src' / 'repro'}")
        sys.path.insert(0, str(ROOT / "src"))
        devices = on_tpu(cell["chips"])
        peaks = peaks_of(devices[0].device_kind)
    except (Refused, OSError) as e:
        log(f"bench/run.py: {e}")
        return 2
    result = run_cell(doc, args.workload, args.seed, args.seconds, bool(args.trace),
                      devices=devices[:cell["chips"]], peaks=peaks, t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


def on_tpu(chips: int) -> list:
    """JAX's devices, with the program's compile cache on, once JAX finds
    at least ``chips`` TPU chips; ``Refused`` otherwise."""
    import jax

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise Refused(f"needs {chips} TPU chip(s); JAX found {len(devices)} "
                      f"{devices[0].platform!r} device(s) ({devices[0].device_kind})")
    return devices


if __name__ == "__main__":
    sys.exit(main())
