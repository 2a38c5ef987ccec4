"""Fused Pallas kernel: decode attention directly over GBDI-FR pages.

The oracle path (serving/kv_cache.attention_decode) decompresses the cache
to HBM and then attends — paying raw-cache bytes again.  This kernel keeps
the win: compressed pages stream HBM->VMEM, decode happens in-register,
q.K / softmax / .V accumulate in VMEM scratch (flash-decoding style online
softmax across the page grid).  HBM traffic per step = compressed bytes.

Scope: GQA attention layers with row_words = Kv*hd <= page_words (one or
more tokens per page) — llama3/qwen3/gemma3-class decode.  Full pages only;
the caller attends over the raw tail (< page_tokens tokens) and merges the
two streams with the standard (m, l, acc) softmax-merge identity.

Outputs (acc, m, l) per (batch, kv-head, group): the caller normalises.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.format import TableLike, as_base_table
from repro.core.gbdi_fr import FRConfig
from repro.kernels.gbdi_decode import decode_tile
from repro.kernels.gbdi_encode import (
    _LIVE_PLANES,
    VMEM_BUDGET_BYTES,
    smem_table,
)


def attn_vmem_tile_bytes(cfg: FRConfig, *, n_kv: int, hd: int, groups: int) -> int:
    """Conservative per-grid-step VMEM estimate for the fused kernel:
    one K page + one V page decoded in-register next to the q/acc tiles."""
    w = 4
    P = cfg.page_words
    page_blob = (cfg.ptr_lanes + cfg.delta_lanes + 2 * cfg.outlier_cap + 1) * w
    io = (2 * page_blob                      # compressed K + V page tiles
          + 2 * n_kv * groups * hd * w       # q in, acc out
          + 2 * n_kv * groups * w * 2)       # m/l scratch in + out
    # transients of one decode_tile call on a one-page (1, P) plane
    decode = _LIVE_PLANES * P * w
    kv = 2 * P * w                           # decoded K and V words as f32
    return io + 2 * decode + kv


def _check_attn_vmem(cfg: FRConfig, *, n_kv: int, hd: int, groups: int) -> None:
    est = attn_vmem_tile_bytes(cfg, n_kv=n_kv, hd=hd, groups=groups)
    if est > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"paged-attn grid step needs ~{est >> 20} MiB VMEM "
            f"(> {VMEM_BUDGET_BYTES >> 20} MiB); shrink page_words "
            f"(={cfg.page_words}) or the head tile (n_kv={n_kv}, hd={hd})"
        )


def _decode_words(
    ptrs: jax.Array, deltas: jax.Array, ovals: jax.Array, n_out: jax.Array,
    tab_ref: Any, cfg: FRConfig, k: int,
) -> jax.Array:
    """Inline GBDI-FR v2 page decode (1 page) -> (page_words,) int32 words."""
    P = cfg.page_words

    def plane(v: jax.Array) -> jax.Array:
        return jnp.concatenate([v, jnp.zeros((P - v.shape[0],), v.dtype)])[None, :]

    words = decode_tile(plane(ptrs), plane(deltas), plane(ovals),
                        jnp.reshape(n_out, (1, 1)), None,
                        lambda r, j: tab_ref[r * k + j], cfg, k)
    return words[0]


def _kernel(
    pos_ref: Any, q_ref: Any,
    kp_ref: Any, kd_ref: Any, kov_ref: Any, kno_ref: Any,
    vp_ref: Any, vd_ref: Any, vov_ref: Any, vno_ref: Any,
    tab_ref: Any,
    acc_ref: Any, m_ref: Any, l_ref: Any,
    *, cfg: FRConfig, k: int, pt: int, n_kv: int, hd: int, groups: int,
) -> None:
    s = pl.program_id(1)
    pos = pos_ref[0, 0]

    @pl.when(s == 0)
    def _init() -> None:
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    kw = _decode_words(kp_ref[...][0, 0], kd_ref[...][0, 0], kov_ref[...][0, 0],
                       kno_ref[0, 0], tab_ref, cfg, k)
    vw = _decode_words(vp_ref[...][0, 0], vd_ref[...][0, 0], vov_ref[...][0, 0],
                       vno_ref[0, 0], tab_ref, cfg, k)
    K = jax.lax.bitcast_convert_type(kw.astype(jnp.uint16), jnp.bfloat16).reshape(pt, n_kv, hd)
    V = jax.lax.bitcast_convert_type(vw.astype(jnp.uint16), jnp.bfloat16).reshape(pt, n_kv, hd)

    q = q_ref[...].astype(jnp.float32)                        # (1, Kv, G, hd)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    logits = jnp.einsum("bkgh,tkh->bkgt", q, K.astype(jnp.float32)) * scale
    tok = s * pt + jnp.arange(pt, dtype=jnp.int32)
    full_page_limit = (pos // pt) * pt                        # tail handled outside
    valid = tok < full_page_limit
    logits = jnp.where(valid[None, None, None, :], logits, -1e30)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]  # (1,K,G[,hd])
    m_new = jnp.maximum(m_prev, logits.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    # guard the all-masked case: exp(-1e30 - (-1e30)) must be 0, not 1
    p = jnp.where(logits <= -1e29, 0.0, jnp.exp(logits - m_new[..., None]))
    m_ref[...] = m_new
    l_ref[...] = l_prev * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_prev * alpha[..., None] + jnp.einsum(
        "bkgt,tkh->bkgh", p, V.astype(jnp.float32)
    )


@functools.partial(
    jax.jit, static_argnames=("cfg", "n_kv", "hd", "groups", "interpret")
)
def paged_attention_decode(
    q: jax.Array,            # (B, Kv, G, hd) f32/bf16
    pages_k: dict[str, jax.Array], pages_v: dict[str, jax.Array],
    table: TableLike, pos: jax.Array,
    cfg: FRConfig, *, n_kv: int, hd: int, groups: int, interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns un-normalised (acc (B,Kv,G,hd) f32, m (B,Kv,G), l (B,Kv,G))."""
    B, n_slots = pages_k["ptrs"].shape[:2]
    if cfg.page_words % (n_kv * hd):
        raise ValueError(f"a {n_kv}x{hd}-word KV row does not tile a "
                         f"{cfg.page_words}-word page")
    pt = cfg.page_words // (n_kv * hd)
    # the streaming kernel decodes with the static profile-0 layout; the
    # serving KV configs are single-profile (adaptive pages go through
    # kernels.xla.paged_attention_decode, which selects per page)
    assert cfg.num_profiles == 1, "Pallas paged-attn needs a single-profile cfg"
    _check_attn_vmem(cfg, n_kv=n_kv, hd=hd, groups=groups)
    k = cfg.num_bases
    tab = smem_table(as_base_table(table, default_width=cfg.widest_bits), cfg)
    pos_arr = jnp.full((1, 1), pos, jnp.int32)

    def page_specs(lanes: int) -> pl.BlockSpec:
        return pl.BlockSpec((1, 1, lanes), lambda b, s: (b, s, 0))
    kernel = functools.partial(
        _kernel, cfg=cfg, k=k, pt=pt, n_kv=n_kv, hd=hd, groups=groups
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid=(B, n_slots),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, s: (0, 0)),                      # pos
            pl.BlockSpec((1, n_kv, groups, hd), lambda b, s: (b, 0, 0, 0)),  # q
            page_specs(cfg.ptr_lanes), page_specs(cfg.delta_lanes),
            page_specs(cfg.outlier_cap),
            pl.BlockSpec((1, 1), lambda b, s: (b, s)),                       # k n_out
            page_specs(cfg.ptr_lanes), page_specs(cfg.delta_lanes),
            page_specs(cfg.outlier_cap),
            pl.BlockSpec((1, 1), lambda b, s: (b, s)),                       # v n_out
            pl.BlockSpec(memory_space=pltpu.SMEM),                          # base table
        ],
        out_specs=(
            pl.BlockSpec((1, n_kv, groups, hd), lambda b, s: (b, 0, 0, 0)),
            pl.BlockSpec((1, n_kv, groups), lambda b, s: (b, 0, 0)),
            pl.BlockSpec((1, n_kv, groups), lambda b, s: (b, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, n_kv, groups, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, n_kv, groups), jnp.float32),
            jax.ShapeDtypeStruct((B, n_kv, groups), jnp.float32),
        ),
        interpret=interpret,
    )(
        pos_arr, q.astype(jnp.float32),
        pages_k["ptrs"], pages_k["deltas"], pages_k["out_vals"], pages_k["n_out"],
        pages_v["ptrs"], pages_v["deltas"], pages_v["out_vals"], pages_v["n_out"],
        tab,
    )
    return acc, m, l


def merge_softmax(
    acc1: jax.Array, m1: jax.Array, l1: jax.Array,
    acc2: jax.Array, m2: jax.Array, l2: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Streaming-softmax merge of two partial attention streams."""
    m = jnp.maximum(m1, m2)
    a1, a2 = jnp.exp(m1 - m), jnp.exp(m2 - m)
    l = l1 * a1 + l2 * a2
    acc = acc1 * a1[..., None] + acc2 * a2[..., None]
    return acc, m, l
