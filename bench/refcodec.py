"""Plain reference of the GBDI-FR page format, written from its
specification and sharing no code with the program.

A page is ``page_words`` words (16-bit words held as 0..65535 in int32,
32-bit words as int32).  Each non-zero word takes the narrowest base whose
width holds its wrapping delta.  Class ``i`` keeps its first
``bucket_caps[i]`` words in page order; a word past the cap moves to the
narrowest fitting base of a wider class, else it becomes an outlier.
Outliers take the first ``outlier_cap`` table slots in page order; the
rest are dropped and decode to 0.  Zero words take the zero code.

``encode`` gives every blob field, ``roundtrip`` the words a decode of
that blob gives (the input with dropped words zeroed) without building
the blob, and ``decode`` reads a blob back.  All are single-profile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from fmt import Format

BLOB_FIELDS = ("ptrs", "deltas", "out_vals", "out_idx", "n_out", "n_spilled", "n_dropped")


def _wrap(d: jax.Array, bits: int) -> jax.Array:
    if bits == 32:
        return d
    half = 1 << (bits - 1)
    return ((d + half) & ((1 << bits) - 1)) - half


def _pack(fields: jax.Array, bits: int) -> jax.Array:
    """(n,) fields < 2**bits -> (n*bits/32,) int32, field j of a lane at
    bit j*bits."""
    per = 32 // bits
    f = fields.astype(jnp.uint32).reshape(-1, per)
    shifts = jnp.arange(per, dtype=jnp.uint32) * bits
    return (f << shifts).sum(axis=1, dtype=jnp.uint32).astype(jnp.int32)


def _unpack(lanes: jax.Array, bits: int) -> jax.Array:
    per = 32 // bits
    shifts = jnp.arange(per, dtype=jnp.uint32) * bits
    f = (lanes.astype(jnp.uint32)[:, None] >> shifts) & jnp.uint32((1 << bits) - 1)
    return f.reshape(-1).astype(jnp.int32)


def _assign(x: jax.Array, bases: jax.Array, widths: jax.Array, f: Format):
    """One page's final base per word after the spill chain, its wrapping
    deltas, the class of each base, the outlier words and the spill count."""
    wb = f.word_bits
    cls = jnp.full(widths.shape, len(f.width_set), jnp.int32)
    for i, w in enumerate(f.width_set):
        cls = jnp.where(widths == w, i, cls)
    alive = cls < len(f.width_set)
    d = _wrap(x[:, None] - bases[None, :], wb)                     # (P, k)
    mag = jnp.maximum(d, -d - 1)
    fits = alive[None, :] & (mag < jnp.left_shift(1, widths - 1)[None, :])
    cost = jnp.where(fits, widths[None, :], wb + 1)
    sel = jnp.argmin(cost, axis=1)
    nonzero = x != 0
    active = fits.any(axis=1) & nonzero
    outlier = ~fits.any(axis=1) & nonzero
    spilled = jnp.int32(0)
    for i, cap in enumerate(f.bucket_caps):
        inclass = active & (cls[sel] == i)
        rank = jnp.cumsum(inclass) - 1
        keep = inclass & (rank < cap)
        over = inclass & ~keep
        wider = jnp.where(cls[None, :] > i, cost, wb + 1)
        alt = jnp.argmin(wider, axis=1)
        can = over & (jnp.min(wider, axis=1) <= wb)
        sel = jnp.where(can, alt, sel)
        spilled = spilled + can.sum(dtype=jnp.int32)
        active = active & ~(over & ~can)
        outlier = outlier | (over & ~can)
    return sel, d, cls, outlier, spilled


def _page_encode(x: jax.Array, bases: jax.Array, widths: jax.Array, f: Format):
    P, cap_out = f.page_words, f.outlier_cap
    sel, d, cls, outlier, spilled = _assign(x, bases, widths, f)
    nonzero = x != 0
    coded = nonzero & ~outlier
    delta = jnp.take_along_axis(d, sel[:, None], axis=1)[:, 0]
    subs = []
    for i, (w, cap) in enumerate(zip(f.width_set, f.bucket_caps)):
        inclass = coded & (cls[sel] == i)
        rank = jnp.cumsum(inclass) - 1
        slot = jnp.where(inclass, rank, cap)
        payload = jnp.where(inclass, delta & ((1 << w) - 1), 0)
        sub = jnp.zeros(cap + 1, jnp.int32).at[slot].set(payload)[:cap]
        subs.append(_pack(sub, w))
    pos = jnp.cumsum(outlier) - 1
    in_table = outlier & (pos < cap_out)
    slot = jnp.where(in_table, pos, cap_out)
    out_vals = jnp.zeros(cap_out + 1, jnp.int32).at[slot].set(jnp.where(in_table, x, 0))
    out_idx = jnp.zeros(cap_out + 1, jnp.int32).at[slot].set(
        jnp.where(in_table, jnp.arange(P, dtype=jnp.int32), 0))
    code = jnp.where(nonzero, sel, f.zero_code)
    code = jnp.where(outlier, f.outlier_code, code)
    return {
        "ptrs": _pack(code, f.ptr_bits),
        "deltas": jnp.concatenate(subs),
        "out_vals": out_vals[:cap_out],
        "out_idx": out_idx[:cap_out],
        "n_out": jnp.minimum(outlier.sum(dtype=jnp.int32), cap_out),
        "n_spilled": spilled,
        "n_dropped": (outlier & ~in_table).sum(dtype=jnp.int32),
    }


def _page_roundtrip(x: jax.Array, bases: jax.Array, widths: jax.Array, f: Format):
    _, _, _, outlier, _ = _assign(x, bases, widths, f)
    dropped = outlier & (jnp.cumsum(outlier) > f.outlier_cap)
    return jnp.where(dropped, 0, x)


def _page_decode(blob: dict, bases: jax.Array, widths: jax.Array, f: Format):
    P, wb = f.page_words, f.word_bits
    code = _unpack(blob["ptrs"], f.ptr_bits)[:P]
    based = code < f.num_bases
    b = jnp.clip(code, 0, f.num_bases - 1)
    w_of = widths[b]
    delta = jnp.zeros(P, jnp.int32)
    off = 0
    for w, cap, lanes in zip(f.width_set, f.bucket_caps, f.class_lanes):
        sub = _unpack(blob["deltas"][off:off + lanes], w)[:cap]
        off += lanes
        if cap == 0:
            continue
        sub = jnp.where(sub >= (1 << (w - 1)), sub - (1 << w), sub)
        inclass = based & (w_of == w)
        rank = jnp.cumsum(inclass) - 1
        delta = jnp.where(inclass, sub[jnp.clip(rank, 0, cap - 1)], delta)
    val = bases[b] + delta
    if wb == 16:
        val = val & 0xFFFF
    val = jnp.where(based, val, 0)
    live = jnp.arange(f.outlier_cap) < blob["n_out"]
    idx = jnp.where(live, blob["out_idx"], P)                      # P: dropped slot
    return val.at[idx].set(blob["out_vals"], mode="drop")


@functools.partial(jax.jit, static_argnums=(3,))
def encode(x: jax.Array, bases: jax.Array, widths: jax.Array, f: Format) -> dict:
    """(n, page_words) words -> blob fields, each (n, ...)."""
    return jax.vmap(lambda p: _page_encode(p, bases, widths, f))(x)


@functools.partial(jax.jit, static_argnums=(3,))
def roundtrip(x: jax.Array, bases: jax.Array, widths: jax.Array, f: Format) -> jax.Array:
    """(n, page_words) words -> the words a decode of their blob gives."""
    return jax.vmap(lambda p: _page_roundtrip(p, bases, widths, f))(x)


@functools.partial(jax.jit, static_argnums=(3,))
def decode(blob: dict, bases: jax.Array, widths: jax.Array, f: Format) -> jax.Array:
    return jax.vmap(lambda b: _page_decode(b, bases, widths, f))(blob)
