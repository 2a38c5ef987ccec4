"""Benchmark driver: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.  The roofline section reads
the dry-run JSONs if present (run ``python -m repro.launch.dryrun --all``
first for the full table)."""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import (
        bench_compression,
        bench_gradcomp,
        bench_kmeans,
        bench_kvcache,
        bench_throughput,
    )
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    failures = 0
    for mod in (bench_compression, bench_kmeans, bench_throughput,
                bench_gradcomp, bench_kvcache):
        try:
            mod.main()
        except Exception:
            failures += 1
            print(f"{mod.__name__},0,ERROR", file=sys.stderr)
            traceback.print_exc()

    try:
        from pathlib import Path
        if Path("experiments/dryrun").exists():
            from benchmarks import roofline
            cells = [c for c in roofline.load_cells() if c["mesh"] == "pod"]
            for r in roofline.rows(cells):
                print(f"roofline/{r['arch']}__{r['shape']},0,"
                      f"dom={r['dominant']};frac={r['roofline_frac']:.4f};"
                      f"c={r['compute_s']:.4f};m={r['memory_s']:.4f};x={r['collective_s']:.4f}")
    except Exception:
        failures += 1
        traceback.print_exc()

    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
