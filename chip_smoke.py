"""Bring-up smoke: the GBDI-FR codec and the serving path on a TPU.

  python chip_smoke.py              # one chip: codec, serving, compressed KV
  python chip_smoke.py --chips 4    # four chips: compressed vs plain pod mean

Run from the root of a checkout; everything is generated from ``--seed``.
One process holds the chip(s) for the whole run.  Phases, each checked
against the repo's own reference:

* codec — 256 MiB per family (``ml_kvcache_bf16`` under the serving
  cache's ``KV_FR``, ``605.mcf_s`` under the eval's 32-bit default) through
  ``kernels.ops`` with ``backend="auto"`` (the Pallas kernels on TPU) and
  with ``backend="xla"`` (the XLA chain serving and collectives call).
  Blobs must be bit-identical to the jnp oracle on a 1024-page sample and
  to each other on the whole stream; every page without a dropped word
  must round-trip exactly, and no page may get more words wrong than it
  dropped.
* serving — deepseek-7b at its published widths, depth cut to 4 layers,
  through the same ``launch.serve.serve`` as ``python -m
  repro.launch.serve``: 12 requests on 8 slots, prompts of 256 and 1024
  tokens, 32 new tokens each.  Every answer has 32 in-vocab tokens, decode
  logits are finite, and a request's first token is the argmax of a solo
  ``model.prefill`` of its prompt.
* compressed KV — a ``KVSession`` over the deepseek-7b KV geometry, filled
  with one answered request's layer-0 K/V from the engine cache, then
  stepped; its attention must equal the same attention over the raw bf16
  K/V wherever the context holds no dropped word, and its decoded pages
  the raw K/V.
* ``--chips 4`` runs only ``compressed_pod_mean`` against
  ``plain_pod_mean`` on a 4-chip ``pod`` mesh, with gradients shaped like
  one deepseek-7b layer, and checks agreement at bf16 transport tolerance.

Any failed check raises (non-zero exit, no result line).  The last line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

STREAM_BYTES = 256 << 20
ORACLE_PAGES = 1024
FIT_PAGES = 32              # fit sample: pages spread evenly over the stream
ARCH, DEPTH = "deepseek-7b", 4
SLOTS, MAX_LEN, N_REQUESTS, PROMPT_LENS, MAX_NEW = 8, 2048, 12, (256, 1024), 32
KV_STEPS = 8
N_PODS = 4


class SmokeFailure(Exception):
    """A phase's output disagreed with its reference."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def log(msg: str) -> None:
    print(msg, flush=True)


def timed(fn, *args, **kw):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


def first_and_steady(name: str, fn, *args, **kw):
    """Run ``fn`` twice, blocked: the first call includes compilation."""
    out, t1 = timed(fn, *args, **kw)
    del out
    out, t2 = timed(fn, *args, **kw)
    log(f"  {name}: first call {t1:.3f} s, steady call {t2:.3f} s "
        f"(compile ~ {max(t1 - t2, 0.0):.3f} s)")
    return out


# ---------------------------------------------------------------------------
# codec
# ---------------------------------------------------------------------------

def codec_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.gbdi_fr import fit_fr_bases, fr_decode
    from repro.eval.codecs import FRCodec
    from repro.eval.workloads import default_workloads
    from repro.kernels import ops
    from repro.kernels.gbdi_encode import DEFAULT_PAGES_PER_TILE
    from repro.kernels.ref import encode_ref
    from repro.serving.kv_cache import KV_FR

    backend = ops.resolve_backend("auto")
    log(f"[codec] ops backend 'auto' -> {backend!r}")
    check(backend == "kernel", "'auto' resolves to the Pallas kernels on TPU")
    registry = default_workloads()
    families = (
        # the serving KV distribution under the serving cache's own format
        ("ml_kvcache_bf16", "KV_FR", KV_FR),
        # the paper's page images under the eval codec's 32-bit format: two
        # width classes, so the spill chain and unaligned sub-streams run
        ("605.mcf_s", "eval 32-bit default", FRCodec(word_bits=32)._config()),
    )
    for fam, cfg_name, cfg in families:
        wl = registry.get(fam)
        t0 = time.perf_counter()
        data = wl.generate(STREAM_BYTES, seed)
        host = data.view(np.uint16 if wl.word_bits == 16 else np.int32)
        n_words = host.size - host.size % cfg.page_words
        pages = jnp.asarray(host[:n_words]).astype(jnp.int32).reshape(-1, cfg.page_words)
        n_pages = pages.shape[0]
        spread = np.linspace(0, n_pages - 1, FIT_PAGES).astype(int)
        table = fit_fr_bases(pages[spread], cfg)
        pages, table = jax.block_until_ready((pages, table))
        log(f"[codec] {fam}: {data.nbytes / 2**20:.1f} MiB, {wl.word_bits}-bit words, "
            f"{n_pages} pages of {cfg.page_words} words; {cfg_name}: width_set="
            f"{cfg.width_set} caps={cfg.bucket_caps} outlier_cap={cfg.outlier_cap}; "
            f"table fitted on {FIT_PAGES} pages spread over the stream; "
            f"set-up (generate + upload + fit) {time.perf_counter() - t0:.2f} s")
        log(f"  batch: the whole stream per call ({n_pages} pages), "
            f"Pallas grid tile {DEFAULT_PAGES_PER_TILE} pages")

        kblob = first_and_steady("encode, ops auto (Pallas)", ops.encode_pages, pages, table, cfg)
        xblob = first_and_steady("encode, ops xla (XLA chain)", ops.encode_pages,
                                 pages, table, cfg, backend="xla")
        rows = np.sort(np.random.default_rng(seed).choice(n_pages, ORACLE_PAGES, replace=False))
        sample = pages[rows]
        rblob = first_and_steady(f"encode, jnp oracle ({ORACLE_PAGES} pages)",
                                 encode_ref, sample, table, cfg)
        for name, blob in (("Pallas", kblob), ("XLA chain", xblob)):
            same = all(bool(jnp.array_equal(blob[f][rows], rblob[f])) for f in rblob)
            check(same, f"{fam}: {name} blobs bit-identical to the oracle on "
                        f"{ORACLE_PAGES} sampled pages")
        check(set(kblob) == set(xblob)
              and all(bool(jnp.array_equal(kblob[f], xblob[f])) for f in kblob),
              f"{fam}: Pallas and XLA-chain blobs identical on all {n_pages} pages")
        n_dropped = int(kblob["n_dropped"].sum())
        n_spilled = int(kblob["n_spilled"].sum())
        lossy = int((kblob["n_dropped"] > 0).sum())
        log(f"  n_dropped={n_dropped} words on {lossy} pages, n_spilled={n_spilled} words")

        kdec = first_and_steady("decode, ops auto (Pallas)", ops.decode_pages, kblob, table, cfg)
        xdec = first_and_steady("decode, ops xla (XLA chain)", ops.decode_pages,
                                xblob, table, cfg, backend="xla")
        check(bool(jnp.array_equal(kdec[rows], fr_decode(rblob, table, cfg))),
              f"{fam}: Pallas decode bit-identical to the oracle decode on the sample")
        exact = int((kblob["n_dropped"] == 0).sum())
        for name, dec in (("Pallas", kdec), ("XLA chain", xdec)):
            wrong = (dec != pages).sum(axis=1)
            check(bool((wrong <= kblob["n_dropped"]).all()),
                  f"{fam}: {name} round trip exact on all {exact} pages with "
                  f"n_dropped == 0, and wrong words <= n_dropped on the rest")
        del data, host, pages, kblob, xblob, rblob, kdec, xdec
        gc.collect()


# ---------------------------------------------------------------------------
# serving + compressed KV
# ---------------------------------------------------------------------------

def run_phase(name: str, fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    log(f"[{name}] phase done in {time.perf_counter() - t0:.1f} s")
    return out


def serving_phase(seed: int):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch.serve import serve
    from repro.serving.engine import Request

    full = get_config(ARCH)
    cfg = dataclasses.replace(full, n_layers=DEPTH)
    log(f"[serving] {ARCH}: d_model={cfg.d_model} heads={cfg.n_heads}x{cfg.head_dim_} "
        f"kv_heads={cfg.n_kv_heads} d_ff={cfg.d_ff} vocab={cfg.vocab_size}")
    log(f"  reduced: n_layers {full.n_layers} -> {cfg.n_layers}")
    rng = np.random.default_rng(seed)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, PROMPT_LENS[i % 2]).astype(np.int32),
                    max_new=MAX_NEW) for i in range(N_REQUESTS)]
    log(f"  traffic: {N_REQUESTS} requests on {SLOTS} slots, prompts "
        f"{'/'.join(map(str, PROMPT_LENS))} tokens, max_new={MAX_NEW}, max_len={MAX_LEN}")
    t0 = time.perf_counter()
    eng = serve(cfg, reqs, slots=SLOTS, max_len=MAX_LEN, seed=seed)
    jax.block_until_ready(eng.cache)
    log(f"  served in {time.perf_counter() - t0:.2f} s (init + compile + prefill + decode)")
    for r in reqs:
        check(len(r.out) == MAX_NEW and all(0 <= t < cfg.vocab_size for t in r.out),
              f"request {r.rid} ({len(r.prompt)}-token prompt): {len(r.out)} in-vocab tokens")
    model, params = eng.model, eng.params
    last = np.zeros((SLOTS, 1), np.int32)
    for s, r in enumerate(eng.slot_req):
        if r is not None:
            last[s, 0] = r.out[-1]
    (logits, _), _ = timed(jax.jit(model.decode_step), params, {"tokens": jnp.asarray(last)},
                           eng.cache, jnp.asarray(eng.slot_pos))
    check(bool(jnp.isfinite(logits).all()),
          f"decode logits finite on all {SLOTS} slots of the final cache")
    prefill = jax.jit(model.prefill)
    for r in reqs[:2]:
        S = len(r.prompt)
        (_, solo), dt = timed(prefill, params, {"tokens": jnp.asarray(r.prompt)[None]},
                              model.init_cache(1, S))
        solo = solo[0, -1].astype(jnp.float32)
        top = int(jnp.argmax(solo))
        tie = top != r.out[0] and bool(solo[r.out[0]] == solo[top])
        log(f"  solo prefill of request {r.rid} ({S} tokens): {dt:.2f} s incl. compile; "
            f"argmax {top}, engine's first token {r.out[0]}{' (exact tie)' if tie else ''}")
        check(bool(jnp.isfinite(solo).all()) and (top == r.out[0] or tie),
              f"request {r.rid}: first token is the argmax of a solo model.prefill")
    return eng


def compressed_kv_phase(eng, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.gbdi_fr import fit_fr_bases
    from repro.kernels import ops
    from repro.serving import kv_cache
    from repro.serving.engine import KVSession

    model = eng.model
    spec = model.kv_cache_spec(MAX_LEN, resident_decode=True)
    slot = next(s for s, r in enumerate(eng.slot_req)
                if r is not None and len(r.prompt) == max(PROMPT_LENS))
    r = eng.slot_req[slot]
    n_tok = len(r.prompt) + len(r.out) - 1      # the last token's KV is never written
    layer = eng.cache["periods"]["slot0"]
    K = layer["k"][0, slot:slot + 1, :n_tok]        # (1, T, Kv, hd) bf16, RoPE applied
    V = layer["v"][0, slot:slot + 1, :n_tok]
    log(f"[compressed KV] request {r.rid} in slot {slot}, layer 0: {n_tok} tokens of "
        f"{spec.n_kv}x{spec.head_dim} bf16; page_words={spec.fr.page_words} -> "
        f"{spec.row_words // spec.fr.page_words} pages per token row, resident decode")

    def words(x):
        return jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.int32)

    # fit sample: K and V rows of tokens spread over the context (fit_fr_bases
    # keeps the first 65536 non-zero words, so a prefix would see only K)
    spread = np.linspace(0, n_tok - 1, 8).astype(int)
    table = fit_fr_bases(jnp.concatenate([words(K[:, spread]).reshape(-1),
                                          words(V[:, spread]).reshape(-1)]), spec.fr)
    drops = {name: np.asarray(ops.encode_pages(
        words(x).reshape(n_tok, -1, spec.fr.page_words), table, spec.fr,
        backend="xla")["n_dropped"].sum(axis=1))
        for name, x in (("K", K), ("V", V))}
    drops_upto = np.cumsum(drops["K"] + drops["V"])
    log(f"  n_dropped over the context: K {int(drops['K'].sum())}, "
        f"V {int(drops['V'].sum())} words")

    sess = KVSession(spec, 1, table)
    t0 = n_tok - KV_STEPS
    _, dt = timed(sess.prefill, K[:, :t0], V[:, :t0])
    log(f"  prefill {t0} tokens: {dt:.2f} s incl. compile")
    attend = jax.jit(functools.partial(kv_cache.attention_decode, spec, backend="resident"))
    q_all = jax.random.normal(jax.random.PRNGKey(seed), (KV_STEPS, 1, 1, model.cfg.n_heads,
                                                          spec.head_dim), jnp.bfloat16)
    for i, t in enumerate(range(t0, n_tok)):
        out, dt = timed(sess.step, q_all[i], K[:, t:t + 1], V[:, t:t + 1])
        raw = {"k_dec": jnp.zeros_like(sess.cache["k_dec"]).at[:, :t + 1].set(K[:, :t + 1]),
               "v_dec": jnp.zeros_like(sess.cache["v_dec"]).at[:, :t + 1].set(V[:, :t + 1]),
               "k_tail": K[:, t:t + 1], "v_tail": V[:, t:t + 1]}
        ref = attend(q_all[i], raw, jnp.int32(t))
        diff = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
        log(f"  step at position {t}: {dt * 1e3:.2f} ms, max |compressed - raw| = {diff}, "
            f"dropped words in context {int(drops_upto[t])}")
        if drops_upto[t] == 0:
            check(bool(jnp.array_equal(out, ref)),
                  f"position {t}: attention over compressed KV equals attention over raw KV")
    for name, x, dec in (("K", K, sess.cache["k_dec"]), ("V", V, sess.cache["v_dec"])):
        wrong = np.asarray((dec[0, :n_tok] != x[0]).reshape(n_tok, -1).sum(axis=1))
        check(bool((wrong <= drops[name]).all()),
              f"decoded {name} pages equal the raw {name} bit for bit on "
              f"{int((drops[name] == 0).sum())} of {n_tok} tokens with no dropped word, "
              f"and wrong words <= n_dropped on the rest")


# ---------------------------------------------------------------------------
# four chips: compressed vs plain cross-pod gradient mean
# ---------------------------------------------------------------------------

def layer_grad_shapes(cfg) -> dict[str, tuple[int, int]]:
    """One dense transformer layer's weight-gradient shapes."""
    d, hd = cfg.d_model, cfg.head_dim_
    return {"wq": (d, cfg.n_heads * hd), "wk": (d, cfg.n_kv_heads * hd),
            "wv": (d, cfg.n_kv_heads * hd), "wo": (cfg.n_heads * hd, d),
            "w_gate": (d, cfg.d_ff), "w_up": (d, cfg.d_ff), "w_down": (cfg.d_ff, d)}


def pod_mean_programs(mesh):
    """(compressed mean, plain mean, per-device dropped words) as jitted
    programs over ``mesh``; gradients arrive stacked on a leading ``pod``
    axis of the row dimension, the table replicated."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.distributed.collectives import (
        compressed_pod_mean, encode_leaf, plain_pod_mean, pod_shard_map,
    )

    def dropped(g, t):
        return jax.tree.map(lambda x: encode_leaf(x, t)["n_dropped"].sum()[None], g)

    comp = pod_shard_map(lambda g, t: compressed_pod_mean(g, t, n_pods=N_PODS), mesh,
                         in_specs=(P("pod"), P()), out_specs=P("pod"))
    plain = pod_shard_map(plain_pod_mean, mesh, in_specs=P("pod"), out_specs=P("pod"))
    drops = pod_shard_map(dropped, mesh, in_specs=(P("pod"), P()), out_specs=P("pod"))
    return jax.jit(comp), jax.jit(plain), jax.jit(drops)


def pod_mean_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.core.gbdi_fr import fit_fr_bases
    from repro.distributed.collectives import GRAD_FR

    devs = jax.devices()
    if len(devs) < N_PODS:
        raise SmokeFailure(f"--chips {N_PODS} needs {N_PODS} devices, JAX sees {len(devs)}")
    mesh = Mesh(np.asarray(devs[:N_PODS]), ("pod",))
    shapes = layer_grad_shapes(get_config(ARCH))
    n_words = sum(a * b for a, b in shapes.values())
    log(f"[pod mean] {N_PODS}-chip pod mesh; one {ARCH} layer of gradients per chip: "
        f"{len(shapes)} leaves, {n_words / 1e6:.1f}M f32 values")
    shard = NamedSharding(mesh, P("pod"))

    @functools.partial(jax.jit, out_shardings=shard)
    def make(key):
        keys = jax.random.split(key, len(shapes))
        return {name: 1e-3 * jax.random.normal(k, (N_PODS * r, c), jnp.float32)
                for k, (name, (r, c)) in zip(keys, shapes.items())}

    grads = jax.block_until_ready(make(jax.random.PRNGKey(seed)))
    sample = grads["w_up"][:256].astype(jnp.bfloat16)      # device 0's rows
    table = fit_fr_bases(jax.lax.bitcast_convert_type(sample, jnp.uint16).astype(jnp.int32),
                         GRAD_FR)
    comp, plain, drops = pod_mean_programs(mesh)
    c, dt = timed(comp, grads, table)
    log(f"  compressed_pod_mean: {dt:.2f} s incl. compile")
    p, dt = timed(plain, grads)
    log(f"  plain_pod_mean: {dt:.2f} s incl. compile")
    dropped = {k: np.asarray(v) for k, v in drops(grads, table).items()}
    total = int(sum(v.sum() for v in dropped.values()))
    log(f"  n_dropped per chip: {[int(sum(v[i] for v in dropped.values())) for i in range(N_PODS)]}"
        f" (total {total})")
    for name in shapes:
        g, cm, pm = grads[name], c[name], p[name]
        # each chip adds 3 peers' values through bf16 transport: per element
        # |compressed - plain| <= 3/4 * 2^-9 * max|g|; the last quarter
        # covers f32 summation order
        tol = 2.0 ** -9 * float(jnp.max(jnp.abs(g)))
        err = jnp.abs(cm.astype(jnp.float32) - pm.astype(jnp.float32))
        over = int((err > tol).sum())
        log(f"  {name} {shapes[name]}: max |compressed - plain| = {float(err.max())}, "
            f"tolerance {tol}, elements over {over}")
        check(over <= 3 * int(dropped[name].sum()),
              f"{name}: compressed mean agrees with plain pmean at bf16 transport "
              f"tolerance (beyond it only where words were dropped)")


# ---------------------------------------------------------------------------

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip compressed-vs-plain pod mean")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: needs a TPU; JAX found {len(devs)} {dev.platform!r} "
              f"device(s) ({dev.device_kind})", file=sys.stderr)
        return 2
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke.py: no src/repro next to {Path(__file__).name}; "
              "run it from the root of a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    from repro.launch.compile_cache import use_compile_cache

    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; jax {jax.__version__}; "
        f"compile cache {use_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_phase("pod mean", pod_mean_phase, args.seed)
    else:
        run_phase("codec", codec_phase, args.seed)
        eng = run_phase("serving", serving_phase, args.seed)
        run_phase("compressed KV", compressed_kv_phase, eng, args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
