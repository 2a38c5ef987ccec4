"""Pallas TPU kernel: GBDI-FR v2 page decode.

Decode is the paper's "value reconstruction" engine: global-table lookup +
delta add + outlier scatter-back.  On TPU it runs the encoder's lane moves
(:mod:`repro.kernels.gbdi_encode`) in reverse on ``(pages_per_tile,
page_words)`` tiles: pointer codes and class sub-streams unpack by
spreading packed lanes back over their fields; a word's slot in its class
sub-stream is its page-order rank among same-class words, so each slot
travels right to its word by the inverse of the encoder's compaction, its
unsigned field and distance in one int32 (sign-extended once it lands); the
base value is a select over the (tiny) SMEM base table.  Outliers come
back the same way: both encoders fill the outlier table in page order, so
the j-th outlier-coded word owns slot j (``j < n_out``; a dropped word
keeps the code and decodes to 0).  That makes ``out_idx`` redundant for
encoder-written blobs, and the kernel does not read it.  No dynamic
gather or scatter anywhere.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.core.format import WORD16_MASK, TableLike, as_base_table
from repro.core.gbdi_fr import FRConfig
from repro.kernels.gbdi_encode import (
    DEFAULT_PAGES_PER_TILE,
    _check_vmem,
    compact,
    expand,
    lanes,
    pad_pages,
    prefix_sum,
    smem_table,
    unpack_fields,
)


def _widen(ref: Any, buf: Any) -> jax.Array:
    """Load a ``(T, n)`` block into lanes ``[0, n)`` of a zeroed ``(T, P)``
    VMEM scratch and return the whole plane."""
    buf[...] = jnp.zeros(buf.shape, jnp.int32)
    buf[:, :ref.shape[1]] = ref[...]
    plane: jax.Array = buf[...]
    return plane


def decode_tile(
    ptrs: jax.Array, deltas: jax.Array, out_vals: jax.Array, n_out: jax.Array,
    pid: jax.Array | None, table: Any, cfg: FRConfig, k: int,
) -> jax.Array:
    """Decode ``(T, P)``-widened blob planes -> ``(T, P)`` int32 words.

    ``table(r, j)`` reads row ``r`` (bases, class) of the SMEM table for
    base ``j``; ``n_out`` and ``pid`` are ``(T, 1)``."""
    P = cfg.page_words
    lane = lanes(ptrs.shape)
    with jax.named_scope(obs.DECODE_POINTERS):
        code = unpack_fields(ptrs, cfg.ptr_bits)
        base_val = jnp.zeros_like(code)
        cls_w = jnp.full_like(code, cfg.num_classes)      # non-base codes: no class
        for j in range(k):
            hit = code == j
            base_val = jnp.where(hit, table(0, j), base_val)
            cls_w = jnp.where(hit, table(1, j), cls_w)

    def to_words(sub: jax.Array, member: jax.Array, live: jax.Array, bits: int) -> jax.Array:
        """Slot r of ``sub`` (unsigned, ``bits`` wide) -> the lane of the
        r-th ``member`` word.  The compaction moves the distance alone."""
        rank = prefix_sum(member.astype(jnp.int32)) - 1
        dist = compact(None, member, rank)[1]
        return expand(sub, dist, live, bits)

    def gather_deltas(profile: int) -> jax.Array:
        delta = jnp.zeros_like(code)
        for i, (w, cap, off) in enumerate(
            zip(cfg.width_set, cfg.profiles[profile],
                cfg.class_lane_offsets_for(profile))
        ):
            if cap == 0:
                continue
            packed = pltpu.roll(deltas, P - off, 1) if off else deltas
            sub = unpack_fields(packed, w)      # fields past cap are not live
            inclass = cls_w == i
            moved = to_words(sub, inclass, lane < cap, w)
            moved = (moved << (32 - w)) >> (32 - w)   # sign-extend the w-bit delta
            delta = jnp.where(inclass, moved, delta)
        return delta

    with jax.named_scope(obs.DECODE_BUCKETS):
        if pid is None:
            delta = gather_deltas(0)
        else:   # per-page profile id selects the sub-stream layout
            delta = jnp.zeros_like(code)
            for p in range(cfg.num_profiles):
                delta = jnp.where(pid == p, gather_deltas(p), delta)
        val = base_val + delta
        if cfg.word_bits == 16:
            val = val & WORD16_MASK

    with jax.named_scope(obs.DECODE_OUTLIERS):
        val = jnp.where(code == cfg.zero_code, 0, val)
        is_out = code == cfg.outlier_code
        oval = to_words(out_vals, is_out, lane < n_out, cfg.word_bits)
        return jnp.where(is_out, oval, val)


def _decode_kernel(
    ptr_ref: Any, delta_ref: Any, oval_ref: Any, nout_ref: Any, *refs: Any,
    cfg: FRConfig, k: int,
) -> None:
    prof_ref = refs[0] if cfg.num_profiles > 1 else None
    tab_ref, x_ref, buf = refs[-3:]
    with jax.named_scope(obs.DECODE_WIDEN):
        planes = _widen(ptr_ref, buf), _widen(delta_ref, buf), _widen(oval_ref, buf)
    x_ref[...] = decode_tile(
        *planes, nout_ref[...], None if prof_ref is None else prof_ref[...],
        lambda r, j: tab_ref[r * k + j], cfg, k)


@functools.partial(jax.jit, static_argnames=("cfg", "pages_per_tile", "interpret"))
def gbdi_decode_pallas(
    blob: dict[str, jax.Array],
    table: TableLike,              # BaseTable (or bare bases, v1 compat)
    cfg: FRConfig,
    *,
    pages_per_tile: int = DEFAULT_PAGES_PER_TILE,
    interpret: bool,               # True only off-TPU (correctness oracle)
) -> jax.Array:
    n_pages = blob["ptrs"].shape[0]
    _check_vmem(cfg, pages_per_tile)
    T, P, cap = pages_per_tile, cfg.page_words, cfg.outlier_cap
    k = cfg.num_bases
    tab = smem_table(as_base_table(table, default_width=cfg.widest_bits), cfg)
    kernel = functools.partial(_decode_kernel, cfg=cfg, k=k)
    in_specs = [
        pl.BlockSpec((T, cfg.ptr_lanes), lambda i: (i, 0)),
        pl.BlockSpec((T, cfg.delta_lanes), lambda i: (i, 0)),
        pl.BlockSpec((T, cap), lambda i: (i, 0)),
        pl.BlockSpec((T, 1), lambda i: (i, 0)),
    ]
    args = [blob["ptrs"], blob["deltas"], blob["out_vals"], blob["n_out"][:, None]]
    if cfg.num_profiles > 1:   # adaptive: per-page profile id input
        in_specs.append(pl.BlockSpec((T, 1), lambda i: (i, 0)))
        args.append(blob["profile"][:, None])
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    args = [pad_pages(a, T) for a in args] + [tab]
    n_padded = args[0].shape[0]
    return pl.pallas_call(
        kernel,
        grid=(n_padded // T,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((T, P), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_padded, P), jnp.int32),
        scratch_shapes=[pltpu.VMEM((T, P), jnp.int32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*args)[:n_pages]
