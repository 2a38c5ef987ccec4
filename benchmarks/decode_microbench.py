"""Decode steady-state microbench: per-token KV decode-step latency vs
context length, incremental resident region vs full re-decode.

The claim under measurement is the serving half of the tentpole: with
``KVSpec.resident_decode`` every flushed page is decoded once (at flush)
into a resident bf16 region, so a decode step decodes no page and reads
the region and the raw tail as two softmax segments, while the
non-resident path re-runs
``_decompress_all`` over every page slot each step, linear in context
length.  Both paths are bit-identical (property-tested in
``tests/test_kv_compress.py``); this bench records the latency shape.

Per (context, mode) cell the bench builds a fresh single-sequence
``KVSession``, prefills to one token short of ``context``, then times
``step`` (append + attend over everything so far) with the output blocked
each repeat.  Modes: ``resident`` uses the auto backend over a
``resident_decode=True`` cache; ``full`` uses the oracle backend over a
plain cache (decode-all-pages every step).

Artifact schema (``experiments/BENCH_decode_microbench.json``, mirrored
to the repo root like every BENCH_*.json):

  meta:  bench="decode_microbench", contexts, repeats, devices, spec
         fields (n_kv, head_dim, page_tokens, fr page_words)
  rows:  one per (context, mode) cell —
         {context, mode, us_per_token (median), us_best, repeats}
  summary: {mode: {scaling: us(ctx_max)/us(ctx_min), ctx_min, ctx_max}}
         — the flat-vs-linear evidence; resident scaling stays near 1
         while full grows with n_pages.

  PYTHONPATH=src python benchmarks/decode_microbench.py           # full
  PYTHONPATH=src python benchmarks/decode_microbench.py --quick   # CI smoke
"""
from __future__ import annotations

import argparse
import statistics
import time

import numpy as np

MODES = ("resident", "full")


def _time_cell(spec, table, context: int, repeats: int, seed: int,
               backend: str) -> list[float]:
    import jax
    import jax.numpy as jnp

    from repro.serving.engine import KVSession

    rng = np.random.default_rng(seed)
    sess = KVSession(spec, 1, table, backend=backend)
    ch = rng.normal(0, 1, (1, 1, spec.n_kv, spec.head_dim)) * 2

    def mk(n):
        return jnp.asarray(
            (ch + rng.normal(0, 0.1, (1, n, spec.n_kv, spec.head_dim)))
            .astype(np.float32))

    sess.prefill(mk(context - 1), mk(context - 1))
    q = jnp.asarray(
        rng.normal(0, 1, (1, 1, 2 * spec.n_kv, spec.head_dim))
        .astype(np.float32))
    # warm the step compile at this position, then re-enter the timed
    # region from the same position each repeat (steady state: mid-page,
    # no flush) by timing attend-after-append on a frozen cache
    k1, v1 = mk(1), mk(1)
    jax.block_until_ready(sess.step(q, k1, v1))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(sess._attend(q, sess.cache,
                                           jnp.int32(sess.pos - 1)))
        times.append(time.perf_counter() - t0)
    return times


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--contexts", default="128,256,512,1024",
                    help="comma-separated context lengths (tokens)")
    ap.add_argument("--repeats", type=int, default=7)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default="experiments/BENCH_decode_microbench.json",
                    help="artifact path ('' to skip writing); experiments/ "
                         "paths are mirrored to the repo root")
    ap.add_argument("--quick", action="store_true",
                    help="two short contexts, fewer repeats (CI smoke)")
    args = ap.parse_args(argv)
    if args.quick:
        args.contexts, args.repeats = "64,256", 3
    contexts = sorted(int(c) for c in args.contexts.split(","))

    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.core.gbdi_fr import FRConfig, fit_fr_bases
    from repro.eval.run import write_artifact
    from repro.serving import kv_cache as kvc

    fr = FRConfig(word_bits=16, page_words=512, width_set=(4, 8),
                  bucket_caps=(128, 512), num_bases=14, outlier_cap=32)
    n_kv, hd = 4, 32
    rng = np.random.default_rng(args.seed)
    sample = (rng.normal(0, 1, (1, 1, n_kv, hd)) * 2
              + rng.normal(0, 0.1, (1, 1024, n_kv, hd))).astype(np.float32)
    words = jax.lax.bitcast_convert_type(
        jnp.asarray(sample, jnp.bfloat16), jnp.uint16)
    table = fit_fr_bases(words.astype(jnp.int32).reshape(-1), fr)

    rows = []
    for context in contexts:
        for mode in MODES:
            spec = kvc.KVSpec(
                n_kv=n_kv, head_dim=hd, max_len=context, fr=fr,
                resident_decode=(mode == "resident"))
            backend = "auto" if mode == "resident" else "oracle"
            times = _time_cell(spec, table, context, args.repeats,
                               args.seed, backend)
            us_med = statistics.median(times) * 1e6
            us_best = min(times) * 1e6
            rows.append({"context": context, "mode": mode,
                         "n_pages": spec.n_slots,
                         "us_per_token": us_med, "us_best": us_best,
                         "repeats": args.repeats})
            print(f"decode_microbench/ctx{context}_{mode},{us_med:.1f},"
                  f"best={us_best:.1f};n_pages={spec.n_slots}")

    summary = {}
    for mode in MODES:
        us = {r["context"]: r["us_per_token"] for r in rows
              if r["mode"] == mode}
        summary[mode] = {"ctx_min": contexts[0], "ctx_max": contexts[-1],
                         "scaling": us[contexts[-1]] / us[contexts[0]]}
        print(f"decode_microbench/scaling_{mode},0,"
              f"x{summary[mode]['scaling']:.2f} over "
              f"{contexts[0]}->{contexts[-1]} tokens")

    if args.json:
        payload = {
            "bench": "decode_microbench",
            "contexts": contexts,
            "repeats": args.repeats,
            "seed": args.seed,
            "devices": int(jax.local_device_count()),
            "spec": {"n_kv": n_kv, "head_dim": hd,
                     "page_words": fr.page_words,
                     "page_tokens": fr.page_words // (n_kv * hd)},
            "rows": rows,
            "summary": summary,
        }
        for p in write_artifact(args.json, payload):
            print(f"wrote {p}")


if __name__ == "__main__":
    main()
