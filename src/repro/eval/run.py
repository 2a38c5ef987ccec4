"""Run every registered codec over every registered workload.

  PYTHONPATH=src python -m repro.eval.run --suite all --codec gbdi,bdi,fr
  PYTHONPATH=src python -m repro.eval.run --suite ml,column --codec gbdi \
      --bytes 262144 --json experiments/BENCH_eval.json
  PYTHONPATH=src python -m repro.eval.run --sweep --suite ml \
      --json experiments/BENCH_sweep.json
  PYTHONPATH=src python -m repro.eval.run --throughput \
      --json experiments/BENCH_throughput.json
  PYTHONPATH=src python -m repro.eval.run --suite dump --dump-dir d/ \
      # real images ingested with `python -m repro.eval.ingest`

Real memory images (ELF cores, tensor files, live captures) registered by
:mod:`repro.eval.ingest` appear as ``dump:<name>`` families of kind
``Dump`` and run through every mode below exactly like the synthetic
families; ``--dump-dir`` (or ``$REPRO_DUMP_DIR``) says where to scan.

Per cell the runner fits, encodes, decodes, **verifies the roundtrip**
(bit-exact for lossless codecs; for the fixed-rate codec, mismatching
words must not exceed the reported dropped-outlier count), and records
CR / bits-per-word / encode throughput.  Encode/decode timings are warmed
(first call pays jit compilation, untimed) and the median of ``--repeats``
blocked calls.  Output is an aligned stdout table,
``name,us_per_call,derived`` CSV lines matching the ``benchmarks/``
convention, and a ``BENCH_*.json``-style artifact.

``--sweep`` walks a num_bases x width_set/bucket_caps grid of GBDI-FR v2
configs over the selected suite and emits a Pareto table (geomean CR vs
encode MB/s, Pareto-optimal rows marked) plus a ``BENCH_sweep.json``
artifact — replacing the ad-hoc benchmark loops the ROADMAP called out.
``--profile-sets`` adds adaptive per-page bucket-cap profile rows
(``SWEEP_PROFILE_SETS``; see docs/FORMAT.md §5a) next to the static grid.

``BENCH_*.json`` artifacts written under ``experiments/`` are mirrored
to the repo root (trajectory tracking reads root ``BENCH_*.json``).

``--throughput`` is the perf baseline: warmed, median-of-K encode/decode
GiB/s per codec x workload family (no CR columns, no verification), with
a ``BENCH_throughput.json`` artifact.  The compiled ``fr_xla`` backend is
the CPU datapoint (the XLA chain through :mod:`repro.kernels.ops`; rows
record the visible ``devices`` count); interpret-mode ``fr_kernel``
runs on a small stream as a correctness reference, not a throughput
claim — those rows carry ``truncated: true`` plus ``n_bytes_requested``
and are flagged in the table (no silent caps).  Every row is roofline-
attributed: ``bytes_moved`` (stream in + compressed blob out) against
the modelled HBM ceiling ``benchmarks/roofline.py`` quotes, as an
achieved fraction.  With ``--json`` the artifact is rewritten after
every cell (``complete: false`` until the sweep ends) and a codec
raising mid-sweep marks its cell ``failed`` and aborts loudly.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.eval.registry import CodecRegistry, EvalCell, Workload, WorkloadRegistry


def _block(tree):
    """Wait for async (jit-dispatched) results so wall-clock timings are real."""
    import jax

    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()
    return tree


def _timed_median(fn, repeats: int) -> float:
    """Median wall-clock seconds of ``repeats`` calls; caller warms up first
    (``fn`` must block on completion, e.g. via :func:`_block`).  The one
    timing methodology shared by BENCH_eval and BENCH_throughput."""
    times = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def evaluate_cell(
    workload: Workload,
    codec,
    data: np.ndarray,
    *,
    verify: bool = True,
    repeats: int = 3,
) -> EvalCell:
    """Measure one (workload, codec) pair on already-generated ``data``.

    Timing methodology: the first encode/decode call is an untimed warmup
    (it pays jit compilation and device-constant upload for the jitted
    codecs); ``enc_s``/``dec_s`` are the **median of ``repeats`` warmed
    calls**, each blocked on completion — so the throughput columns in
    BENCH_eval.json measure steady state, not compile time or dispatch
    latency.
    """
    from repro.core.gbdi import to_words

    n_bytes = int(np.ascontiguousarray(data).view(np.uint8).size)
    wb = codec.word_bits
    n_words = (n_bytes * 8 + wb - 1) // wb
    repeats = max(1, repeats)

    t0 = time.perf_counter()
    model = codec.fit(data)          # offline background analysis —
    fit_s = time.perf_counter() - t0  # not part of encode throughput

    blob = _block(codec.encode(data, model))      # warmup: jit compile etc.
    size_bits = int(codec.size_bits(blob))
    enc_s = _timed_median(lambda: _block(codec.encode(data, model)), repeats)

    decoded = np.asarray(codec.decode(blob)).reshape(-1)  # warmup + verify data
    dec_s = _timed_median(lambda: np.asarray(codec.decode(blob)), repeats)

    ref = to_words(data, wb)
    got = decoded[: ref.size]
    mism = int(np.count_nonzero(got != ref))
    exact_frac = 1.0 - mism / max(1, ref.size)
    lossless = mism == 0

    verified, error = True, ""
    if verify:
        if codec.lossless and mism:
            verified = False
            error = f"lossless codec mismatched {mism}/{ref.size} words"
        elif not codec.lossless:
            dropped = codec.dropped_words(blob) if hasattr(codec, "dropped_words") else 0
            if mism > dropped:
                verified = False
                error = f"{mism} mismatches > {dropped} dropped outliers"

    return EvalCell(
        workload=workload.name,
        kind=workload.kind,
        codec=codec.name,
        n_bytes=n_bytes,
        word_bits=wb,
        compression_ratio=n_words * wb / max(1, size_bits),
        bits_per_word=size_bits / max(1, n_words),
        fit_s=fit_s,
        encode_s=enc_s,
        decode_s=dec_s,
        encode_mb_s=n_bytes / (1 << 20) / max(enc_s, 1e-9),
        lossless=lossless,
        exact_frac=exact_frac,
        verified=verified,
        error=error,
    )


def evaluate(
    workload_registry: WorkloadRegistry,
    codec_registry: CodecRegistry,
    *,
    suite: str = "all",
    codecs: str = "gbdi,bdi,fr",
    n_bytes: int = 1 << 20,
    seed: int = 0,
    verify: bool = True,
    repeats: int = 3,
) -> list[EvalCell]:
    cells: list[EvalCell] = []
    codec_names = [c.strip() for c in codecs.split(",") if c.strip()]
    for wl in workload_registry.select(suite):
        data = wl.generate(n_bytes, seed)
        for cname in codec_names:
            codec = codec_registry.make(cname, wl.word_bits)
            try:
                cells.append(evaluate_cell(wl, codec, data, verify=verify,
                                           repeats=repeats))
            except Exception as e:  # keep the sweep alive, report the cell red
                cells.append(EvalCell(
                    workload=wl.name, kind=wl.kind, codec=cname,
                    n_bytes=n_bytes, word_bits=wl.word_bits,
                    compression_ratio=0.0, bits_per_word=0.0,
                    fit_s=0.0, encode_s=0.0, decode_s=0.0, encode_mb_s=0.0,
                    lossless=False, exact_frac=0.0, verified=False,
                    error=f"{type(e).__name__}: {e}",
                ))
    return cells


# ---------------------------------------------------------------------------
# config sweep (num_bases x width_set/bucket_caps Pareto)
# ---------------------------------------------------------------------------

#: per-word-size (width_set, bucket_caps) grid points; widths scale with the
#: word so 16- and 32-bit streams sweep comparable shapes
SWEEP_SHAPES = {
    16: [
        ((8,), (2048,)),                       # v1-equivalent single width
        ((4, 8), (192, 1856)),                 # v2 default
        ((4, 8), (128, 1536)),                 # tighter buckets
        ((2, 4, 8), (128, 256, 1664)),         # three classes
    ],
    32: [
        ((16,), (2048,)),
        ((8, 16), (192, 1856)),
        ((8, 16), (128, 1536)),
        ((4, 8, 16), (128, 256, 1664)),
    ],
}
SWEEP_NUM_BASES = (6, 14, 30)

#: named bucket-cap profile tables for the adaptive sweep axis, keyed by
#: word size.  Every table pairs the *default v2 width set* of that word
#: size (``SWEEP_SHAPES[wb][1][0]``): profile 0 is the static default,
#: the rest span narrow-heavy -> wide-heavy -> small (zero/sparse pages).
#: ``"static"`` is the plain ``SWEEP_SHAPES`` bucket-cap grid.
SWEEP_PROFILE_SETS: dict[str, dict[int, tuple[tuple[int, ...], ...]] | None] = {
    "static": None,
    "adaptive4": {
        16: ((192, 1856), (1024, 1024), (64, 1984), (256, 512)),
        32: ((192, 1856), (1024, 1024), (64, 1984), (256, 512)),
    },
    "adaptive2": {
        16: ((192, 1856), (256, 512)),
        32: ((192, 1856), (256, 512)),
    },
}
DEFAULT_PROFILE_SETS = "static,adaptive4"


def _sweep_row(rows, label, cells, backend, **extra):
    rows.append({
        "config": label,
        "backend": backend,
        "geomean_cr": geomean(c.compression_ratio for c in cells),
        "bits_per_word": float(np.mean([c.bits_per_word for c in cells])),
        "encode_mb_s": float(np.mean([c.encode_mb_s for c in cells])),
        "exact_frac": float(np.mean([c.exact_frac for c in cells])),
        "verified": all(c.verified for c in cells),
        "cells": [c.to_json() for c in cells],
        **extra,
    })


def sweep(
    workload_registry: WorkloadRegistry,
    *,
    suite: str = "ml",
    backend: str = "ref",
    n_bytes: int = 1 << 18,
    seed: int = 0,
    verify: bool = True,
    profile_sets: str = DEFAULT_PROFILE_SETS,
) -> list[dict]:
    """Evaluate the FR codec across the config grid; one row per config.

    ``profile_sets`` is a comma list of :data:`SWEEP_PROFILE_SETS` names —
    the adaptive per-page bucket-cap axis.  ``static`` sweeps the plain
    ``num_bases x (width_set, bucket_caps)`` grid; each adaptive set adds
    one row per ``num_bases`` pairing the default v2 width set with its
    cap-profile table.
    """
    from repro.core.gbdi_fr import FRConfig
    from repro.eval.codecs import FRCodec

    set_names = [s.strip() for s in profile_sets.split(",") if s.strip()]
    unknown = sorted(set(set_names) - set(SWEEP_PROFILE_SETS))
    if unknown:
        raise KeyError(f"unknown profile set(s) {unknown}; "
                       f"choose from {sorted(SWEEP_PROFILE_SETS)}")
    workloads = workload_registry.select(suite)
    rows: list[dict] = []

    def run_grid(num_bases, make_cfg, tag):
        cells = []
        width_sets: dict[int, tuple[int, ...]] = {}
        for wl in workloads:
            cfg = make_cfg(wl.word_bits, num_bases)
            width_sets[wl.word_bits] = cfg.width_set
            codec = FRCodec(
                word_bits=wl.word_bits, backend=backend, cfg=cfg,
                name=f"fr[k{num_bases}/w{'-'.join(map(str, cfg.width_set))}"
                     f"{tag}]",
            )
            data = wl.generate(n_bytes, seed)
            # repeats=1: the sweep is a CR Pareto, not a timing harness
            cells.append(evaluate_cell(wl, codec, data, verify=verify,
                                       repeats=1))
        # one label per word size actually evaluated — a mixed suite
        # sweeps paired shapes, e.g. "k14/w4-8|w8-16"
        label = f"k{num_bases}/" + "|".join(
            f"w{'-'.join(map(str, ws))}" for _, ws in sorted(width_sets.items())
        ) + tag
        return label, cells, width_sets

    for num_bases in SWEEP_NUM_BASES:
        if "static" in set_names:
            for shape_idx in range(len(SWEEP_SHAPES[16])):
                def mk(wb, k, idx=shape_idx):
                    width_set, caps = SWEEP_SHAPES[wb][idx]
                    return FRConfig(word_bits=wb, num_bases=k,
                                    width_set=width_set, bucket_caps=caps)
                label, cells, width_sets = run_grid(num_bases, mk, "")
                _sweep_row(
                    rows, label, cells, backend,
                    num_bases=num_bases, shape_idx=shape_idx,
                    profile_set="static",
                    width_sets={str(wb): list(ws)
                                for wb, ws in sorted(width_sets.items())},
                )
        for name in set_names:
            profiles = SWEEP_PROFILE_SETS[name]
            if profiles is None:
                continue

            def mk(wb, k, profs=profiles):
                width_set = SWEEP_SHAPES[wb][1][0]   # default v2 shape
                return FRConfig(word_bits=wb, num_bases=k,
                                width_set=width_set, cap_profiles=profs[wb])
            label, cells, width_sets = run_grid(num_bases, mk, f"+{name}")
            _sweep_row(
                rows, label, cells, backend,
                num_bases=num_bases, shape_idx=None, profile_set=name,
                width_sets={str(wb): list(ws)
                            for wb, ws in sorted(width_sets.items())},
                cap_profiles={str(wb): [list(p) for p in profs]
                              for wb, profs in sorted(profiles.items())},
            )
    # Pareto front on (geomean CR up, encode MB/s up)
    for r in rows:
        r["pareto"] = not any(
            o["geomean_cr"] >= r["geomean_cr"] and o["encode_mb_s"] >= r["encode_mb_s"]
            and (o["geomean_cr"] > r["geomean_cr"] or o["encode_mb_s"] > r["encode_mb_s"])
            for o in rows
        )
    return rows


def format_sweep_table(rows: list[dict]) -> str:
    hdr = f"{'config':<26} {'CR(geo)':>8} {'bits/w':>7} {'enc MB/s':>9} " \
          f"{'exact':>7} {'ok':>3} {'pareto':>6}"
    lines = [hdr, "-" * len(hdr)]
    for r in sorted(rows, key=lambda r: -r["geomean_cr"]):
        lines.append(
            f"{r['config']:<26} {r['geomean_cr']:>8.3f} {r['bits_per_word']:>7.2f} "
            f"{r['encode_mb_s']:>9.1f} {r['exact_frac']:>7.4f} "
            f"{'yes' if r['verified'] else 'NO':>3} {'*' if r['pareto'] else '':>6}"
        )
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# throughput harness (warmed, median-of-K GiB/s per codec x workload family)
# ---------------------------------------------------------------------------

#: one representative stream per workload family, plus both bf16 ML
#: distributions the serving/training paths actually move
THROUGHPUT_WORKLOADS = (
    "605.mcf_s",          # C
    "java_svm",           # Java
    "col_int_keys",       # Column
    "ml_kvcache_bf16",    # ML (serving KV distribution)
    "ml_grads_bf16",      # ML (gradient-transport distribution)
)
THROUGHPUT_CODECS = "gbdi,bdi,fr,fr_xla,fr_kernel"
#: interpret-mode Pallas is a correctness oracle ~10^3x slower than the
#: compiled paths — it gets a smaller stream (GiB/s normalises it away)
KERNEL_N_BYTES = 256 << 10


def roofline_peak_bytes_s(device_kind: str) -> float | None:
    """HBM peak of ``device_kind`` from ``repro.launch.mesh.CHIP_PEAKS``, or
    None for a device with no published peak (the CPU): a host rate is
    never divided by a chip's bandwidth."""
    from repro.launch.mesh import CHIP_PEAKS

    peaks = CHIP_PEAKS.get(device_kind)
    return None if peaks is None else float(peaks.hbm_bytes_s)


def measure_throughput(
    workload: Workload, codec, data: np.ndarray, *, repeats: int = 5,
    n_bytes_requested: int | None = None,
) -> dict:
    """Warmed, blocked, median-of-``repeats`` encode/decode GiB/s.

    Each row carries its roofline attribution: ``bytes_moved`` (stream
    read + compressed blob write, the minimal memory traffic of one
    encode pass), the ``device_kind`` it ran on, that chip's peak
    bandwidth and the achieved fraction of it (``None`` on a device with
    no published peak) — plus the visible device count and, when the harness ran the
    codec on a smaller stream than requested, an explicit ``truncated``
    marker (no silent caps).
    """
    import jax

    n_bytes = int(np.ascontiguousarray(data).view(np.uint8).size)
    requested = n_bytes if n_bytes_requested is None else int(n_bytes_requested)
    model = codec.fit(data)
    blob = _block(codec.encode(data, model))      # warmup: jit + constants
    enc_s = _timed_median(lambda: _block(codec.encode(data, model)), repeats)
    np.asarray(codec.decode(blob))                 # decode warmup
    dec_s = _timed_median(lambda: np.asarray(codec.decode(blob)), repeats)
    gib = n_bytes / (1 << 30)
    comp_bytes = (int(codec.size_bits(blob)) + 7) // 8
    bytes_moved = n_bytes + comp_bytes            # stream in + blob out
    kind = jax.devices()[0].device_kind
    peak = roofline_peak_bytes_s(kind)
    return {
        "workload": workload.name,
        "kind": workload.kind,
        "codec": codec.name,
        "n_bytes": n_bytes,
        "n_bytes_requested": requested,
        "truncated": n_bytes < requested,
        "devices": int(jax.local_device_count()),
        "device_kind": kind,
        "repeats": max(1, repeats),
        "enc_s": enc_s,
        "dec_s": dec_s,
        "enc_gib_s": gib / max(enc_s, 1e-12),
        "dec_gib_s": gib / max(dec_s, 1e-12),
        "comp_bytes": comp_bytes,
        "bytes_moved": bytes_moved,
        "peak_bytes_s": peak,
        "enc_roofline_frac": None if peak is None
        else bytes_moved / max(enc_s, 1e-12) / peak,
        "dec_roofline_frac": None if peak is None
        else bytes_moved / max(dec_s, 1e-12) / peak,
    }


def throughput(
    workload_registry: WorkloadRegistry,
    codec_registry: CodecRegistry,
    *,
    suite: str = "",
    codecs: str = THROUGHPUT_CODECS,
    n_bytes: int = 2 << 20,
    kernel_n_bytes: int = KERNEL_N_BYTES,
    repeats: int = 5,
    seed: int = 0,
    rows: list[dict] | None = None,
    on_row=None,
) -> list[dict]:
    """One row per (workload, codec): warmed median-of-K encode/decode GiB/s.

    ``suite=''`` uses :data:`THROUGHPUT_WORKLOADS` (every family covered);
    any registry suite string narrows/extends the set.

    ``rows``/``on_row`` support incremental artifact writing: every
    completed row is appended to ``rows`` (the same list that is
    returned) and ``on_row(row)`` fires after each append.  A codec
    raising mid-sweep appends a ``failed: True`` cell (workload, codec,
    error), fires ``on_row`` one last time so the partial artifact
    records exactly where the sweep died, then re-raises as
    ``RuntimeError`` — the sweep never silently emits a truncated
    artifact that looks complete.
    """
    if suite:
        workloads = workload_registry.select(suite)
    else:
        workloads = [workload_registry.get(n) for n in THROUGHPUT_WORKLOADS]
    codec_names = [c.strip() for c in codecs.split(",") if c.strip()]
    if rows is None:
        rows = []
    for wl in workloads:
        streams = {nb: wl.generate(nb, seed)
                   for nb in {kernel_n_bytes if c == "fr_kernel" else n_bytes
                              for c in codec_names}}
        for cname in codec_names:
            actual = kernel_n_bytes if cname == "fr_kernel" else n_bytes
            data = streams[actual]
            if actual < n_bytes:
                print(f"note: {cname}/{wl.name} runs on a {actual}-byte "
                      f"stream ({n_bytes} requested) — interpret-mode "
                      f"oracle; row is marked truncated")
            codec = codec_registry.make(cname, wl.word_bits)
            try:
                row = measure_throughput(wl, codec, data, repeats=repeats,
                                         n_bytes_requested=n_bytes)
            except Exception as e:
                row = {"workload": wl.name, "kind": wl.kind, "codec": cname,
                       "n_bytes": actual, "n_bytes_requested": n_bytes,
                       "failed": True, "error": f"{type(e).__name__}: {e}"}
                rows.append(row)
                if on_row is not None:
                    on_row(row)
                raise RuntimeError(
                    f"throughput sweep aborted: codec {cname!r} failed on "
                    f"workload {wl.name!r}: {type(e).__name__}: {e}") from e
            rows.append(row)
            if on_row is not None:
                on_row(row)
    return rows


def throughput_summary(rows: list[dict]) -> list[dict]:
    """Mean GiB/s per codec x workload family (kind); failed cells skipped."""
    groups: dict[tuple[str, str], list[dict]] = {}
    for r in rows:
        if r.get("failed"):
            continue
        groups.setdefault((r["codec"], r["kind"]), []).append(r)
    return [
        {
            "codec": codec,
            "kind": kind,
            "n_workloads": len(g),
            "enc_gib_s": float(np.mean([r["enc_gib_s"] for r in g])),
            "dec_gib_s": float(np.mean([r["dec_gib_s"] for r in g])),
        }
        for (codec, kind), g in sorted(groups.items())
    ]


def format_throughput_table(rows: list[dict]) -> str:
    hdr = f"{'workload':<20} {'kind':<7} {'codec':<10} {'MiB':>6} " \
          f"{'enc GiB/s':>10} {'dec GiB/s':>10} {'enc rf':>9} {'dev':>3}"
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        if r.get("failed"):
            lines.append(
                f"{r['workload']:<20} {r['kind']:<7} {r['codec']:<10} "
                f"{r['n_bytes'] / (1 << 20):>6.2f} FAILED: {r['error']}")
            continue
        trunc = "*" if r.get("truncated") else " "
        rf = r["enc_roofline_frac"]
        lines.append(
            f"{r['workload']:<20} {r['kind']:<7} {r['codec']:<10} "
            f"{r['n_bytes'] / (1 << 20):>5.2f}{trunc} {r['enc_gib_s']:>10.3f} "
            f"{r['dec_gib_s']:>10.3f} {'-' if rf is None else f'{rf:.1e}':>9} "
            f"{r['devices']:>3}"
        )
    if any(r.get("truncated") for r in rows):
        lines.append("* stream truncated vs requested --bytes "
                     "(interpret-mode reference rows)")
    for s in throughput_summary(rows):
        lines.append(f"family {s['kind']:<7} {s['codec']:<10} "
                     f"enc={s['enc_gib_s']:.3f} dec={s['dec_gib_s']:.3f} GiB/s")
    return "\n".join(lines)


def throughput_artifact(rows: list[dict], *, codecs: str, n_bytes: int,
                        kernel_n_bytes: int, repeats: int, seed: int,
                        complete: bool = True) -> dict:
    import jax

    from repro.kernels import ops

    return {
        "bench": "throughput",
        "codecs": codecs,
        "n_bytes": n_bytes,
        "kernel_n_bytes": kernel_n_bytes,
        "repeats": repeats,
        "seed": seed,
        "auto_backend": ops.resolve_backend("auto"),
        "devices": int(jax.local_device_count()),
        "device_kind": jax.devices()[0].device_kind,
        "peak_bytes_s": roofline_peak_bytes_s(jax.devices()[0].device_kind),
        "complete": complete,       # False while rows stream in mid-sweep
        "rows": rows,
        "summary": throughput_summary(rows),
    }


# ---------------------------------------------------------------------------
# reporting
# ---------------------------------------------------------------------------

def write_artifact(path: str, payload: dict) -> list:
    """Write a ``BENCH_*.json`` artifact, mirroring it to the repo root.

    Trajectory tracking reads repo-root ``BENCH_*.json`` files, while the
    curated artifacts live under ``experiments/`` — so when the target sits
    in a directory named ``experiments``, an identical copy lands next to
    that directory (``experiments/BENCH_x.json`` -> ``BENCH_x.json``).
    Returns the list of paths written.
    """
    from pathlib import Path

    p = Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    text = json.dumps(payload, indent=2)
    p.write_text(text)
    written = [p]
    if p.parent.name == "experiments" and p.name.startswith("BENCH_"):
        mirror = p.parent.parent / p.name
        mirror.write_text(text)
        written.append(mirror)
    return written


def geomean(xs) -> float:
    """Geometric mean of CRs (0.0 for an empty set) — the one shared by
    the table, bench_compression and any consumer of BENCH_eval.json."""
    xs = list(xs)
    if not xs:
        return 0.0
    return float(np.exp(np.mean(np.log(np.maximum(xs, 1e-9)))))


def format_table(cells: list[EvalCell]) -> str:
    hdr = f"{'workload':<26} {'kind':<7} {'codec':<10} {'CR':>7} {'bits/w':>7} " \
          f"{'enc MB/s':>9} {'exact':>7} {'ok':>3}"
    lines = [hdr, "-" * len(hdr)]
    for c in cells:
        ok = "yes" if c.verified else "NO"
        lines.append(
            f"{c.workload:<26} {c.kind:<7} {c.codec:<10} {c.compression_ratio:>7.3f} "
            f"{c.bits_per_word:>7.2f} {c.encode_mb_s:>9.1f} {c.exact_frac:>7.4f} {ok:>3}"
        )
    kinds = sorted({c.kind for c in cells})
    for codec in sorted({c.codec for c in cells}):
        sub = [c for c in cells if c.codec == codec and c.compression_ratio > 0]
        if not sub:
            continue
        per_kind = "  ".join(
            f"{k}={geomean(c.compression_ratio for c in sub if c.kind == k):.3f}"
            for k in kinds if any(c.kind == k for c in sub)
        )
        lines.append(f"geomean CR [{codec:<9}] {per_kind}  "
                     f"all={geomean(c.compression_ratio for c in sub):.3f}")
    return "\n".join(lines)


def csv_lines(cells: list[EvalCell]) -> list[str]:
    """``name,us_per_call,derived`` rows, the benchmarks/run.py convention."""
    return [
        f"eval/{c.workload}/{c.codec},{c.encode_s * 1e6:.1f},"
        f"cr={c.compression_ratio:.3f};bpw={c.bits_per_word:.2f};"
        f"exact={c.exact_frac:.4f};kind={c.kind};ok={int(c.verified)}"
        for c in cells
    ]


def to_artifact(cells: list[EvalCell], *, suite: str, codecs: str,
                n_bytes: int, seed: int) -> dict:
    return {
        "bench": "eval",
        "suite": suite,
        "codecs": codecs,
        "n_bytes": n_bytes,
        "seed": seed,
        "rows": [c.to_json() for c in cells],
    }


def main(argv: list[str] | None = None) -> list[EvalCell]:
    from repro.eval.codecs import default_codecs
    from repro.eval.workloads import default_workloads

    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--suite", default="all",
                    help="'all', or comma list of kinds (c,java,column,ml,"
                         "dump) and/or workload names (incl. dump:<name>)")
    ap.add_argument("--dump-dir", default=None,
                    help="directory of ingested dump containers to register "
                         "as dump:<name> families (default: $REPRO_DUMP_DIR "
                         "or experiments/dumps)")
    ap.add_argument("--codec", default=None,
                    help="comma list from: gbdi, bdi, fr, fr_xla, fr_kernel "
                         "(fr_xla is the compiled batched CPU/GPU path; "
                         "fr_kernel interprets the Pallas kernels on CPU). "
                         "Default: all five; for --sweep: fr (jnp oracle)")
    ap.add_argument("--bytes", type=int, default=None, dest="n_bytes",
                    help="stream size per workload (default 1 MiB; "
                         "2 MiB for --throughput)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-verify", action="store_true")
    ap.add_argument("--json", default="", help="write BENCH_*.json artifact here")
    ap.add_argument("--csv", action="store_true",
                    help="also print benchmarks/-style CSV lines")
    ap.add_argument("--sweep", action="store_true",
                    help="sweep num_bases x width_set FR configs; Pareto "
                         "table + BENCH_sweep.json instead of per-codec cells")
    ap.add_argument("--profile-sets", default=DEFAULT_PROFILE_SETS,
                    help="comma list of bucket-cap profile sets for --sweep "
                         f"(from: {','.join(sorted(SWEEP_PROFILE_SETS))}; "
                         "'static' is the plain cap grid, the rest add "
                         "adaptive per-page profile rows)")
    ap.add_argument("--throughput", action="store_true",
                    help="perf baseline: warmed median-of-K GiB/s per codec "
                         "x workload family + BENCH_throughput.json")
    ap.add_argument("--repeats", type=int, default=None,
                    help="timed repeats per measurement (median is reported; "
                         "default 3, 5 for --throughput)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    if args.throughput:
        n_bytes = args.n_bytes if args.n_bytes is not None else 2 << 20
        repeats = args.repeats if args.repeats is not None else 5
        codecs = args.codec or THROUGHPUT_CODECS
        kernel_n_bytes = min(KERNEL_N_BYTES, n_bytes)
        rows: list[dict] = []

        def _partial(_row):
            # incremental artifact: every completed (or failed) cell lands
            # on disk immediately, flagged complete=False until the sweep
            # finishes — a mid-sweep crash leaves an honest partial file
            if args.json:
                write_artifact(args.json, throughput_artifact(
                    rows, codecs=codecs, n_bytes=n_bytes,
                    kernel_n_bytes=kernel_n_bytes, repeats=repeats,
                    seed=args.seed, complete=False))

        try:
            throughput(
                default_workloads(args.dump_dir), default_codecs(),
                suite=args.suite
                if args.suite != "all" else "", codecs=codecs,
                n_bytes=n_bytes, kernel_n_bytes=kernel_n_bytes,
                repeats=repeats, seed=args.seed,
                rows=rows, on_row=_partial if args.json else None,
            )
        except KeyError as e:
            raise SystemExit(f"error: {e.args[0] if e.args else e}")
        except RuntimeError as e:
            print(format_throughput_table(rows))
            raise SystemExit(f"error: {e}")
        print(format_throughput_table(rows))
        if args.csv:
            for r in rows:
                mb = r["n_bytes"] / (1 << 20)
                print(f"throughput/{r['codec']}_encode/{r['workload']},"
                      f"{r['enc_s'] / mb * 1e6:.0f},GiB/s={r['enc_gib_s']:.3f}")
                print(f"throughput/{r['codec']}_decode/{r['workload']},"
                      f"{r['dec_s'] / mb * 1e6:.0f},GiB/s={r['dec_gib_s']:.3f}")
        if args.json:
            for p in write_artifact(args.json, throughput_artifact(
                    rows, codecs=codecs, n_bytes=n_bytes,
                    kernel_n_bytes=kernel_n_bytes, repeats=repeats,
                    seed=args.seed)):
                print(f"wrote {p}")
        return []

    if args.n_bytes is None:
        args.n_bytes = 1 << 20

    if args.sweep:
        # kernel backend only on explicit request: interpret-mode Pallas is
        # orders of magnitude slower and its MB/s is not a CPU datapoint
        backend = "kernel" if args.codec and "fr_kernel" in args.codec else "ref"
        try:
            rows = sweep(default_workloads(args.dump_dir), suite=args.suite,
                         backend=backend,
                         n_bytes=args.n_bytes, seed=args.seed,
                         verify=not args.no_verify,
                         profile_sets=args.profile_sets)
        except KeyError as e:
            raise SystemExit(f"error: {e.args[0] if e.args else e}")
        print(format_sweep_table(rows))
        if args.json:
            for p in write_artifact(args.json, {
                    "bench": "sweep", "suite": args.suite, "backend": backend,
                    "n_bytes": args.n_bytes, "seed": args.seed,
                    "profile_sets": args.profile_sets,
                    "rows": rows,
            }):
                print(f"wrote {p}")
        return []

    try:
        cells = evaluate(
            default_workloads(args.dump_dir), default_codecs(),
            suite=args.suite, codecs=args.codec or "gbdi,bdi,fr,fr_xla,fr_kernel",
            n_bytes=args.n_bytes, seed=args.seed, verify=not args.no_verify,
            repeats=args.repeats if args.repeats is not None else 3,
        )
    except KeyError as e:  # unknown suite/workload/codec: clean CLI error
        raise SystemExit(f"error: {e.args[0] if e.args else e}")
    print(format_table(cells))
    if args.csv:
        for line in csv_lines(cells):
            print(line)
    if args.json:
        for p in write_artifact(args.json, to_artifact(
                cells, suite=args.suite,
                codecs=args.codec or "gbdi,bdi,fr,fr_xla,fr_kernel",
                n_bytes=args.n_bytes, seed=args.seed)):
            print(f"wrote {p}")
    bad = [c for c in cells if not c.verified]
    if bad:
        raise SystemExit(f"{len(bad)} cells failed verification: "
                         + ", ".join(f"{c.workload}/{c.codec}" for c in bad))
    return cells


if __name__ == "__main__":
    main()
