"""Encode/decode engine throughput (paper §IV: compression/decompression
engines) — the repo's perf baseline generator.

Thin delegate over ``python -m repro.eval.run --throughput`` (one
implementation of the harness, CSV convention, and artifact schema):
warmed, median-of-K encode/decode GiB/s for every codec x workload
family, written to ``experiments/BENCH_throughput.json``.

Codec roles on CPU: ``gbdi``/``bdi`` are the numpy host codecs, ``fr`` is
the vmapped jnp oracle, ``fr_xla`` is the XLA chain (the CPU datapoint,
through :mod:`repro.kernels.ops`), and
``fr_kernel`` interprets the Pallas kernels on a small stream — a
correctness reference whose timing is NOT TPU-representative; its rows
are marked ``truncated`` with the requested size recorded.  Rows carry a
roofline column (``bytes_moved`` vs the modelled HBM ceiling) and the
visible ``devices`` count.  The artifact is written incrementally (one
rewrite per cell); a codec raising mid-sweep marks the failed cell and
exits non-zero instead of silently emitting a partial-but-plausible JSON.

  PYTHONPATH=src python benchmarks/bench_throughput.py            # full baseline
  PYTHONPATH=src python benchmarks/bench_throughput.py --quick    # CI smoke
"""
from __future__ import annotations

import argparse

from repro.eval import run as eval_run


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--bytes", type=int, default=2 << 20, dest="n_bytes")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--codec", default=eval_run.THROUGHPUT_CODECS)
    ap.add_argument("--json", default="experiments/BENCH_throughput.json",
                    help="artifact path ('' to skip writing); paths under "
                         "experiments/ are mirrored to the repo root for "
                         "BENCH_*.json trajectory tracking")
    ap.add_argument("--quick", action="store_true",
                    help="small streams / fewer repeats (CI smoke)")
    args = ap.parse_args(argv)
    if args.quick:
        args.n_bytes, args.repeats = 256 << 10, 2

    cli = ["--throughput", "--csv", "--codec", args.codec,
           "--bytes", str(args.n_bytes), "--repeats", str(args.repeats),
           "--seed", str(args.seed)]
    if args.json:
        cli += ["--json", args.json]
    eval_run.main(cli)


if __name__ == "__main__":
    main()
