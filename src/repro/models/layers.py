"""Shared layer primitives: RMSNorm, RoPE, GQA attention (global / sliding
window / prefix-LM, train+prefill+decode), SwiGLU MLP.

Pure functions over param pytrees (plain dicts) — no framework dependency,
so the same definitions run under jit, vmap, shard_map and the dry-run.
Attention is query-chunked with ``lax.scan`` so the live score tensor is
``(B, q_chunk, S)`` rather than ``(B, S, S)`` — required for the 32k
prefill cells and a §Perf knob everywhere else.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

Params = dict


def constrain_batch(x: jax.Array, cfg: ModelConfig, *extra) -> jax.Array:
    """Pin the leading (batch) dim to the DP mesh axes — without this, XLA's
    sharding propagation can replicate activations across the data axis
    (observed: 148 GB/device temps on the first dry-run)."""
    if not cfg.mesh_axes:
        return x
    from jax.sharding import PartitionSpec as P

    rest = list(extra) + [None] * (x.ndim - 1 - len(extra))
    if x.shape[0] % _axes_size(cfg.mesh_axes):
        return x
    return jax.lax.with_sharding_constraint(x, P(cfg.mesh_axes, *rest))


def _axes_size(axes: tuple) -> int:
    import numpy as _np

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        # a `with mesh:` context sets only the physical mesh
        from jax._src import mesh as _mesh_lib

        mesh = _mesh_lib.thread_resources.env.physical_mesh
    if mesh.empty:
        return 1
    return int(_np.prod([mesh.shape.get(a, 1) for a in axes]))


def dense_init(key, shape, dtype, in_axis: int = 0) -> jax.Array:
    fan_in = shape[in_axis]
    return (jax.random.normal(key, shape) / jnp.sqrt(fan_in)).astype(dtype)


def rmsnorm_init(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype=dtype)}


def rmsnorm(p: Params, x: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * p["scale"]


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, hd); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) * (jnp.log(theta) / half))
    if positions.ndim == 1:
        ang = positions[None, :, None].astype(jnp.float32) * freqs[None, None, :]
    else:
        ang = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig, dtype) -> Params:
    d, hd = cfg.d_model, cfg.head_dim_
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, (d, cfg.n_heads * hd), dtype),
        "wk": dense_init(kk, (d, cfg.n_kv_heads * hd), dtype),
        "wv": dense_init(kv, (d, cfg.n_kv_heads * hd), dtype),
        "wo": dense_init(ko, (cfg.n_heads * hd, d), dtype),
    }


def _mask(q_pos, k_pos, *, window: int, prefix_len: int):
    """(..., Sq, Sk) bool; causal, optionally sliding-window / prefix-LM."""
    causal = k_pos[None, :] <= q_pos[:, None]
    if window:
        causal &= (q_pos[:, None] - k_pos[None, :]) < window
    if prefix_len:
        causal |= k_pos[None, :] < prefix_len
    return causal


def _sdpa(q, k, v, mask, scale):
    """q: (B,Sq,H,hd) k/v: (B,Sk,Kv,hd) mask: (Sq,Sk) or (B,Sq,Sk)."""
    B, Sq, H, hd = q.shape
    Kv = k.shape[2]
    q = q.reshape(B, Sq, Kv, H // Kv, hd)
    logits = jnp.einsum("bskgh,btkh->bkgst", q, k).astype(jnp.float32) * scale
    if mask.ndim == 2:
        mask = mask[None, None, None]
    else:
        mask = mask[:, None, None]
    logits = jnp.where(mask, logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(B, Sq, H * hd)


def attention_apply(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,                   # (B, S, d)
    positions: jax.Array,           # (S,) int32 absolute positions
    *,
    window: int = 0,
    prefix_len: int = 0,
    cache: Params | None = None,    # {"k","v"}: (B, S_cache, Kv, hd)
    cache_pos: jax.Array | None = None,  # int32 next write slot: scalar or (B,)
) -> tuple[jax.Array, Params | None]:
    """Returns (out (B,S,d), updated cache or None).

    Modes: train (no cache), prefill (cache written at [0,S)), decode
    (S==1 appended at cache_pos; sliding-window caches are ring buffers).
    RoPE is applied before caching so cached keys are position-absolute.
    """
    B, S, _ = x.shape
    H, Kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    q = rope(jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(B, S, H, hd), positions, cfg.rope_theta)
    k = rope(jnp.einsum("bsd,dh->bsh", x, p["wk"]).reshape(B, S, Kv, hd), positions, cfg.rope_theta)
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"]).reshape(B, S, Kv, hd)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)

    if cache is None or cache_pos is None:
        # train / stateless forward: q-chunked scan over the sequence
        n_chunks = max(1, S // cfg.q_chunk) if S % cfg.q_chunk == 0 else 1
        if n_chunks > 1:
            qc = q.reshape(B, n_chunks, S // n_chunks, H, hd).transpose(1, 0, 2, 3, 4)
            pc = positions.reshape(n_chunks, -1)

            def body(_, qp):
                qi, pi = qp
                m = _mask(pi, positions, window=window, prefix_len=prefix_len)
                return None, _sdpa(qi, k, v, m, scale)

            if cfg.remat != "none":
                # nested remat: recompute chunk probs in backward instead of
                # stacking (n_chunks, B, H, chunk, S) f32 residuals in HBM
                body = jax.checkpoint(body)
            _, out = jax.lax.scan(body, None, (qc, pc))   # (n, B, chunk, H*hd)
            out = out.transpose(1, 0, 2, 3).reshape(B, S, H * hd)
        else:
            m = _mask(positions, positions, window=window, prefix_len=prefix_len)
            out = _sdpa(q, k, v, m, scale)
        new_cache = None
        if cache is not None:
            W = cache["k"].shape[1]
            if W >= S:
                new_cache = {
                    "k": jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0)),
                    "v": jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0)),
                }
            else:
                # sliding-window ring buffer: position p lives at slot p % W,
                # so the kept tail (positions S-W..S-1) is a cyclic shift
                new_cache = {
                    "k": jnp.roll(k[:, -W:], S % W, axis=1),
                    "v": jnp.roll(v[:, -W:], S % W, axis=1),
                }
        return jnp.einsum("bsh,hd->bsd", out, p["wo"]), new_cache

    # decode: append one step, attend to the cache.  ``cache_pos`` is a
    # scalar (all rows at one shared position) or a (B,) vector of per-slot
    # positions — the continuous-batching engine admits new sequences into
    # free slots while others decode, so every row owns its position.
    W = cache["k"].shape[1]
    slots = jnp.arange(W, dtype=jnp.int32)
    if jnp.ndim(cache_pos) == 0:
        slot = cache_pos % W if window else cache_pos
        ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
        if window:
            key_pos = cache_pos - ((cache_pos - slots) % W)
            valid = key_pos >= 0                                 # (W,)
        else:
            valid = slots <= cache_pos
        vmask = valid[None, None, None, None, :]
    else:
        cp = cache_pos.astype(jnp.int32)                         # (B,)
        slot = cp % W if window else cp
        upd = jax.vmap(lambda c, x1, s: jax.lax.dynamic_update_slice(c, x1, (s, 0, 0)))
        ck = upd(cache["k"], k, slot)
        cv = upd(cache["v"], v, slot)
        if window:
            key_pos = cp[:, None] - ((cp[:, None] - slots[None, :]) % W)
            valid = key_pos >= 0                                 # (B, W)
        else:
            valid = slots[None, :] <= cp[:, None]
        vmask = valid[:, None, None, None, :]
    # explicit f32 casts keep the scan-carried cache bf16: without them the
    # CPU backend's bf16-dot legalisation hoists f32 converts onto the whole
    # stacked cache (observed: 2x566 GB/step phantom traffic in the walker)
    logits = jnp.einsum(
        "bskgh,btkh->bkgst",
        q.reshape(B, S, Kv, H // Kv, hd).astype(jnp.float32),
        ck.astype(jnp.float32),
    ) * scale
    logits = jnp.where(vmask, logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, cv.astype(jnp.float32))
    out = out.reshape(B, S, H * hd).astype(x.dtype)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"]), {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def mlp_init(key, d: int, d_ff: int, dtype) -> Params:
    kg, ku, kd = jax.random.split(key, 3)
    return {
        "wg": dense_init(kg, (d, d_ff), dtype),
        "wu": dense_init(ku, (d, d_ff), dtype),
        "wd": dense_init(kd, (d_ff, d), dtype),
    }


def mlp_apply(p: Params, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["wg"])) * jnp.einsum("bsd,df->bsf", x, p["wu"])
    return jnp.einsum("bsf,fd->bsd", h, p["wd"])
