"""Plain float32 reference of one multi-head latent attention layer, in the
non-absorbed form, written from DeepSeek-V3's description
(``inference/model.py`` of the DeepSeek-V3 repository; the HF
``config.json``) and sharing no code with the program.

For hidden states h at position p: ``c, k_pe = split(kv_a(norm(h)))``, the
latent row is ``[kv_norm(c), rope(k_pe)]``; ``q = q_b(q_norm(q_a(norm(h))))``
splits per head into ``q_nope`` and ``rope(q_pe)``.  Each head's key is
``[c W_UK, k_pe]`` and value ``c W_UV`` (``kv_b``'s per-head blocks of key
then value columns), scores are scaled by ``qk_head_dim ** -0.5 *
mscale ** 2``, causal softmax, then ``o_proj``.  YaRN sets the RoPE
frequencies at every position; pairs ``(x[2i], x[2i+1])`` rotate.

The weights are the program's parameter tree, read by name
(``attn_norm``, ``wq_a``, ``q_norm``, ``wq_b``, ``wkv_a``, ``kv_norm``,
``wkv_b``, ``wo``; matrices are (in, out)).  Attention runs in blocks of
positions with a running softmax, because the expanded keys and values
of a 33k-token context do not fit whole; every matmul is at HIGHEST
precision.  Departures from the published layer: the residual add and the
FFN/MoE after attention are left out.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HP = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    hidden: int
    heads: int
    q_lora: int
    latent: int
    nope: int
    rope: int
    v: int
    eps: float
    inv_freq: tuple
    scale: float

    @classmethod
    def of(cls, c: dict) -> "Dims":
        y = c["rope_scaling"]
        dim, base, factor = c["qk_rope_head_dim"], float(c["rope_theta"]), float(y["factor"])
        orig = y["original_max_position_embeddings"]
        freqs = [base ** (-2.0 * i / dim) for i in range(dim // 2)]

        def corr(rot: float) -> float:
            return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

        lo = max(math.floor(corr(y["beta_fast"])), 0)
        hi = min(math.ceil(corr(y["beta_slow"])), dim - 1)
        inv = []
        for i, f in enumerate(freqs):
            ramp = min(max((i - lo) / max(hi - lo, 0.001), 0.0), 1.0)
            inv.append(f / factor * ramp + f * (1.0 - ramp))
        mscale = 0.1 * float(y["mscale_all_dim"]) * math.log(factor) + 1.0
        return cls(hidden=c["hidden_size"], heads=c["num_attention_heads"],
                   q_lora=c["q_lora_rank"], latent=c["kv_lora_rank"],
                   nope=c["qk_nope_head_dim"], rope=dim,
                   v=c["v_head_dim"], eps=float(c["rms_norm_eps"]), inv_freq=tuple(inv),
                   scale=(c["qk_nope_head_dim"] + dim) ** -0.5 * mscale ** 2)


def _norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _rope(x: jax.Array, pos: jax.Array, d: Dims) -> jax.Array:
    """x (B, T, [H,] rope) float32, pos (T,)."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(np.array(d.inv_freq, np.float32))
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 3), ang.shape[1])
    even, odd = x[..., 0::2], x[..., 1::2]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.stack([even * c - odd * s, even * s + odd * c], axis=-1).reshape(x.shape)


def _mm(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.matmul(x, w.astype(jnp.float32), precision=HP)


def _rounder(low):
    """Rounding through ``low`` (a narrower float dtype) and back to
    float32: the reference computed in that precision.  None: float32."""
    if low is None:
        return lambda a: a.astype(jnp.float32)
    return lambda a: a.astype(low).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(1, 4))
def latent(w: dict, d: Dims, x: jax.Array, pos: jax.Array, low=None) -> jax.Array:
    """Hidden states x (B, T, hidden) at pos (T,) -> float32 latent rows
    (B, T, latent + rope); with ``low``, weights, inputs and every
    intermediate are rounded through that precision."""
    r = _rounder(low)
    w = {k: r(v) for k, v in w.items()}
    kv = r(_mm(r(_norm(r(x), w["attn_norm"], d.eps)), w["wkv_a"]))
    return r(jnp.concatenate([_norm(kv[..., :d.latent], w["kv_norm"], d.eps),
                              _rope(kv[..., d.latent:], pos, d)], axis=-1))


@functools.partial(jax.jit, static_argnums=(1, 5, 6))
def attend(w: dict, d: Dims, x: jax.Array, qpos: jax.Array, rows: jax.Array,
           block: int, low=None) -> jax.Array:
    """Outputs (B, Q, hidden) of queries from x (B, Q, hidden) at positions
    qpos (Q,), each over the latent rows (B, T, R) of positions [0, qpos],
    and the attention's latent outputs (B, Q, H, latent): each head's
    softmax weights over the rows' latent parts, what ``W_UV`` maps to the
    head's value output.  T is a multiple of ``block``.  With ``low``,
    weights, inputs and every intermediate but the softmax's running sums
    are rounded through that precision."""
    B, Q, _ = x.shape
    H = d.heads
    r = _rounder(low)
    w = {k: r(v) for k, v in w.items()}
    h = r(_norm(r(x), w["attn_norm"], d.eps))
    qa = r(_norm(r(_mm(h, w["wq_a"])), w["q_norm"], d.eps))
    q = r(_mm(qa, w["wq_b"])).reshape(B, Q, H, -1)
    q = r(jnp.concatenate([q[..., :d.nope], _rope(q[..., d.nope:], qpos, d)], axis=-1))
    wkv_b = w["wkv_b"]

    def body(carry, j):
        m, l, acc, lat = carry
        blk = r(jax.lax.dynamic_slice_in_dim(rows, j * block, block, axis=1))
        kv = r(jnp.matmul(blk[..., :d.latent], wkv_b, precision=HP)).reshape(B, block, H, -1)
        k = jnp.concatenate([kv[..., :d.nope], jnp.broadcast_to(
            blk[:, :, None, d.latent:], (B, block, H, d.rope))], axis=-1)
        s = jnp.einsum("bqhd,bthd->bhqt", q, k, precision=HP) * d.scale
        tpos = j * block + jnp.arange(block)
        s = jnp.where(tpos[None, :] <= qpos[:, None], s, -jnp.inf)
        m2 = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m2[..., None])
        a = jnp.exp(m - m2)
        acc = acc * a[..., None] + jnp.einsum("bhqt,bthv->bhqv", r(p), kv[..., d.nope:],
                                              precision=HP)
        lat = lat * a[..., None] + jnp.einsum("bhqt,btc->bhqc", r(p), blk[..., :d.latent],
                                              precision=HP)
        return (m2, l * a + p.sum(-1), acc, lat), None

    init = (jnp.full((B, H, Q), -jnp.inf), jnp.zeros((B, H, Q)), jnp.zeros((B, H, Q, d.v)),
            jnp.zeros((B, H, Q, d.latent)))
    (m, l, acc, lat), _ = jax.lax.scan(body, init, jnp.arange(rows.shape[1] // block))
    o = r(jnp.transpose(acc / l[..., None], (0, 2, 1, 3)).reshape(B, Q, H * d.v))
    return r(_mm(o, w["wo"])), r(jnp.transpose(lat / l[..., None], (0, 2, 1, 3)))

