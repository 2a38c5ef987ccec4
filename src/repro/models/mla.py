"""Multi-head latent attention (MLA), DeepSeek-V3's attention layer.

Each token's keys and values are one low-rank latent: ``kv_a`` projects
the normed hidden state to ``c_kv`` (``kv_lora_rank``) and a shared roped
key part ``k_pe`` (``qk_rope_head_dim``).  A decode cache holds one row
per token, ``kv_norm(c_kv)`` followed by the roped ``k_pe`` (576 words at
DeepSeek-V3's widths), and every head reads that same row.

The decode step here is DeepSeek-V3's "absorb" form (``inference/model.py``
of the DeepSeek-V3 repository, ``attn_impl="absorb"``):

* ``q = q_b(q_norm(q_a(h)))`` into ``H x (nope + rope)``; the rope part is
  rotated, the nope part absorbed through ``W_UK`` (the key half of
  ``kv_b``) into the latent, so each head's query is one row-wide vector;
* every head scores the whole latent row and reads its first
  ``kv_lora_rank`` words as the value (:func:`latent_attention`);
* ``W_UV`` (the value half of ``kv_b``), then ``o_proj``.

RoPE rotates interleaved pairs ``(x[2i], x[2i+1])`` as the inference code
does (the HF port's rotate-half form applies one fixed permutation to q
and k, so dot products agree).  YaRN sets the frequencies at every
position, as the config's ``rope_scaling`` says: the linear ramp between
``freq / factor`` and ``freq`` over the correction range that
``beta_fast``/``beta_slow`` give on the original context, and the softmax
scale ``qk_head_dim ** -0.5 * mscale ** 2`` with
``mscale = 0.1 * mscale_all_dim * ln(factor) + 1``.  With ``mscale ==
mscale_all_dim`` (DeepSeek-V3: both 1) the HF port's factor on cos/sin is
1, so none is applied.

:func:`reference_forward` is the plain float32 forward in the
non-absorbed form, the reference the absorbed step is tested against.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models.layers import dense_init

Params = dict[str, jax.Array]


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """One MLA layer's published sizes; the defaults are DeepSeek-V3's
    (huggingface.co/deepseek-ai/DeepSeek-V3, config.json)."""

    hidden_size: int = 7168
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    rope_factor: float = 40.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale_all_dim: float = 1.0

    @classmethod
    def from_hf(cls, c: dict[str, Any]) -> "MLAConfig":
        """From a HF ``config.json`` of the DeepSeek-V2/V3 families."""
        y = c["rope_scaling"]
        return cls(hidden_size=c["hidden_size"], num_heads=c["num_attention_heads"],
                   q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
                   qk_nope_head_dim=c["qk_nope_head_dim"],
                   qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
                   rope_theta=float(c["rope_theta"]), rms_norm_eps=float(c["rms_norm_eps"]),
                   rope_factor=float(y["factor"]),
                   original_max_position=y["original_max_position_embeddings"],
                   beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
                   mscale_all_dim=float(y["mscale_all_dim"]))

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def row_words(self) -> int:
        """Words of one cached latent row."""
        return self.kv_lora_rank + self.qk_rope_head_dim


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(cfg: MLAConfig) -> float:
    return cfg.qk_head_dim ** -0.5 * yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim) ** 2


def yarn_inv_freq(cfg: MLAConfig) -> np.ndarray:
    """(qk_rope_head_dim // 2,) float32 YaRN inverse frequencies."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    freqs = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations: float) -> float:
        return dim * math.log(cfg.original_max_position / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(cfg.beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001), 0, 1)
    smooth = 1 - ramp
    return (freqs / cfg.rope_factor * (1 - smooth) + freqs * smooth).astype(np.float32)


def rope(x: jax.Array, positions: jax.Array, inv_freq: np.ndarray) -> jax.Array:
    """Rotate interleaved pairs of x (B, S, [H,] d) at positions (S,), in
    float32; returns x's dtype."""
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    shape = (ang.shape[0],) + (1,) * (x.ndim - 3) + (ang.shape[1],)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    x0, x1 = xf[..., 0], xf[..., 1]
    out = jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def init(key: jax.Array, cfg: MLAConfig, dtype: Any = jnp.bfloat16) -> Params:
    """Seeded random weights at ``cfg``'s widths; norm weights are 1.
    Matrices are (in, out): ``wkv_b``'s columns are per-head blocks of
    ``qk_nope_head_dim`` key then ``v_head_dim`` value columns."""
    H, d = cfg.num_heads, cfg.hidden_size
    k = jax.random.split(key, 5)
    return {
        "attn_norm": jnp.ones((d,), dtype),
        "wq_a": dense_init(k[0], (d, cfg.q_lora_rank), dtype),
        "q_norm": jnp.ones((cfg.q_lora_rank,), dtype),
        "wq_b": dense_init(k[1], (cfg.q_lora_rank, H * cfg.qk_head_dim), dtype),
        "wkv_a": dense_init(k[2], (d, cfg.row_words), dtype),
        "kv_norm": jnp.ones((cfg.kv_lora_rank,), dtype),
        "wkv_b": dense_init(k[3], (cfg.kv_lora_rank,
                                   H * (cfg.qk_nope_head_dim + cfg.v_head_dim)), dtype),
        "wo": dense_init(k[4], (H * cfg.v_head_dim, d), dtype),
    }


def _kv_b(p: Params, cfg: MLAConfig) -> tuple[jax.Array, jax.Array]:
    """(W_UK (latent, H, nope), W_UV (latent, H, v))."""
    w = p["wkv_b"].reshape(cfg.kv_lora_rank, cfg.num_heads, -1)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def latent_rows(p: Params, cfg: MLAConfig, x: jax.Array, positions: jax.Array) -> jax.Array:
    """Hidden states x (B, S, hidden) at positions (S,) -> the cache's
    latent rows (B, S, kv_lora_rank + qk_rope_head_dim)."""
    with jax.named_scope(obs.MLA_KV_PROJ):
        h = _rmsnorm(x, p["attn_norm"], cfg.rms_norm_eps)
        kv = jnp.einsum("bsd,dr->bsr", h, p["wkv_a"])
        c = _rmsnorm(kv[..., :cfg.kv_lora_rank], p["kv_norm"], cfg.rms_norm_eps)
        k_pe = rope(kv[..., cfg.kv_lora_rank:], positions, yarn_inv_freq(cfg))
        return jnp.concatenate([c, k_pe], axis=-1)


def absorbed_queries(p: Params, cfg: MLAConfig, x: jax.Array,
                     positions: jax.Array) -> jax.Array:
    """Hidden states x (B, S, hidden) -> every head's query absorbed into
    the latent, (B, S, H, kv_lora_rank + qk_rope_head_dim)."""
    B, S, _ = x.shape
    with jax.named_scope(obs.MLA_Q_PROJ):
        h = _rmsnorm(x, p["attn_norm"], cfg.rms_norm_eps)
        qa = _rmsnorm(jnp.einsum("bsd,dr->bsr", h, p["wq_a"]), p["q_norm"], cfg.rms_norm_eps)
        q = jnp.einsum("bsr,rk->bsk", qa, p["wq_b"]).reshape(B, S, cfg.num_heads, -1)
        q_pe = rope(q[..., cfg.qk_nope_head_dim:], positions, yarn_inv_freq(cfg))
    with jax.named_scope(obs.MLA_ABSORB):
        w_uk, _ = _kv_b(p, cfg)
        q_lat = jnp.einsum("bshn,chn->bshc", q[..., :cfg.qk_nope_head_dim], w_uk)
    return jnp.concatenate([q_lat, q_pe], axis=-1)


def latent_attention(q: jax.Array, rows: jax.Array, valid: jax.Array, scale: float,
                     latent_dim: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Absorbed attention over one segment of latent rows: q (B, 1, H, R),
    rows (B, S, R), valid (S,) -> the segment's unnormalised softmax
    partials: the output accumulated in float32 (B, 1, H, latent_dim), the
    row max and the row sum (B, 1, H).  Scores over the whole row, values
    its first ``latent_dim`` words: the weighted sum runs over whole rows
    and keeps its first ``latent_dim`` words, as a slice of the rows would
    be a copy of all of them.  As in the inference code, the products
    come out in the rows' dtype and only the softmax runs in float32.  A
    segment with no valid position gives zero output and sum; segments
    merge with :func:`repro.kernels.gbdi_paged_attn.merge_softmax`, and the
    attention output is the merged output over the merged sum."""
    logits = jnp.einsum("bqhc,btc->bqht", q, rows).astype(jnp.float32) * jnp.float32(scale)
    logits = jnp.where(valid, logits, jnp.float32(-1e30))
    m = logits.max(-1)
    p = jnp.where(valid, jnp.exp(logits - m[..., None]), jnp.float32(0))
    acc = jnp.einsum("bqht,btc->bqhc", p.astype(rows.dtype), rows,
                     preferred_element_type=jnp.float32)
    return acc[..., :latent_dim], m, p.sum(-1)


def decode_in(p: Params, cfg: MLAConfig, x: jax.Array,
              pos: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One decode token x (B, 1, hidden) at position ``pos`` -> (its
    latent row (B, 1, R) for the cache, its absorbed query (B, 1, H, R))."""
    positions = jnp.reshape(pos, (1,))
    return latent_rows(p, cfg, x, positions), absorbed_queries(p, cfg, x, positions)


def decode_out(p: Params, cfg: MLAConfig, o: jax.Array) -> jax.Array:
    """Latent attention output (B, 1, H, kv_lora_rank) -> (B, 1, hidden)."""
    B, S = o.shape[:2]
    with jax.named_scope(obs.MLA_OUT_PROJ):
        _, w_uv = _kv_b(p, cfg)
        v = jnp.einsum("bshc,chv->bshv", o, w_uv).reshape(B, S, -1)
        return jnp.einsum("bsk,kd->bsd", v, p["wo"])


# ---------------------------------------------------------------------------
# plain float32 reference (non-absorbed)
# ---------------------------------------------------------------------------

def reference_latent(p: Params, cfg: MLAConfig, x: jax.Array,
                     positions: jax.Array) -> jax.Array:
    """Float32 latent rows (B, S, R) of hidden states x (B, S, hidden)."""
    R = cfg.kv_lora_rank
    with jax.default_matmul_precision("highest"):
        pf = {k: v.astype(jnp.float32) for k, v in p.items()}
        kv = _rmsnorm(x.astype(jnp.float32), pf["attn_norm"], cfg.rms_norm_eps) @ pf["wkv_a"]
        return jnp.concatenate([_rmsnorm(kv[..., :R], pf["kv_norm"], cfg.rms_norm_eps),
                                rope(kv[..., R:], positions, yarn_inv_freq(cfg))], axis=-1)


def reference_attend(p: Params, cfg: MLAConfig, x: jax.Array, positions: jax.Array,
                     rows: jax.Array) -> jax.Array:
    """Float32 attention outputs (B, S, hidden) of queries from x (B, S,
    hidden) at positions (S,), each over ``rows`` (B, T, R), the cached
    latent rows of positions 0..T-1, causally.  Keys and values are
    expanded per head through ``kv_b``: the non-absorbed form."""
    H, nope = cfg.num_heads, cfg.qk_nope_head_dim
    B, S, _ = x.shape
    with jax.default_matmul_precision("highest"):
        pf = {k: v.astype(jnp.float32) for k, v in p.items()}
        h = _rmsnorm(x.astype(jnp.float32), pf["attn_norm"], cfg.rms_norm_eps)
        qa = _rmsnorm(h @ pf["wq_a"], pf["q_norm"], cfg.rms_norm_eps)
        q = (qa @ pf["wq_b"]).reshape(B, S, H, cfg.qk_head_dim)
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], positions, yarn_inv_freq(cfg))],
                            axis=-1)
        rows = rows.astype(jnp.float32)
        kv = (rows[..., :cfg.kv_lora_rank] @ pf["wkv_b"]).reshape(B, -1, H, nope + cfg.v_head_dim)
        k_pe = jnp.broadcast_to(rows[:, :, None, cfg.kv_lora_rank:],
                                (*kv.shape[:3], cfg.qk_rope_head_dim))
        k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
        s = jnp.einsum("bshd,bthd->bhst", q, k) * softmax_scale(cfg)
        causal = jnp.arange(rows.shape[1])[None, :] <= positions[:, None]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = jnp.einsum("bhst,bthv->bshv", jax.nn.softmax(s, axis=-1), kv[..., nope:])
        return o.reshape(B, S, -1) @ pf["wo"]


def reference_forward(p: Params, cfg: MLAConfig, x: jax.Array,
                      positions: jax.Array | None = None) -> jax.Array:
    """The layer's attention output (B, S, hidden) for hidden states x
    (B, S, hidden), causal, in float32 with every matmul at HIGHEST
    precision; no cache, kernels or batching tricks.

    Departures from the published layer: the residual add and the FFN/MoE
    block that follow attention are left out (this is the attention
    sublayer alone); norm weights are whatever ``p`` holds (``init`` sets
    1); YaRN frequencies apply at every position; RoPE rotates interleaved
    pairs."""
    positions = jnp.arange(x.shape[1]) if positions is None else positions
    return reference_attend(p, cfg, x, positions, reference_latent(p, cfg, x, positions))
