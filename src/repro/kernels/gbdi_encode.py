"""Pallas TPU kernel: GBDI-FR v2 page encode.

TPU adaptation of the paper's C/C++ bit-serial encoder: the bit loop
becomes lane-parallel VPU arithmetic on ``(pages_per_tile, page_words)``
tiles — pages on sublanes, words on lanes, every intermediate a 2-D
``(T, P)`` array:

* base selection is a loop over the (tiny, <= 254-entry) base table read
  as scalars from SMEM: each base contributes one elementwise wrapped
  delta, fit test and running minimum, so the narrowest fitting base (and,
  per width class, the narrowest fitting base of a strictly wider class —
  the spill target) falls out of compare/select with first-index
  tie-break, exactly the oracle's ``argmin``;
* page-order ranks are Hillis–Steele prefix sums over lane rotations
  (:func:`prefix_sum`);
* bucket and outlier compaction WITHOUT dynamic scatter (which does not
  lower on TPU): every kept word moves left by its distance to its slot,
  one power-of-two step per distance bit, low bit first (:func:`compact`)
  — collision-free because kept words keep their order.  A step rotates
  one plane: the distance rides in the payload's spare bits (a delta
  field, a 16-bit word: ``bits + log2(page_words) <= 31``), and a lane
  receives a word when the rotated distance there has the step's bit set.
  Only a 32-bit word needs a second plane;
* fixed-width field packing is a shift by lane position, an OR over each
  group of ``32 // bits`` neighbours, and a move of the group heads whose
  distances are fixed by the lane: each step rotates the payload alone,
  under masks computed from the lane index (:func:`pack_fields`).

All of it is bit-identical to the jnp oracle's spill chain.  The decoder
(:mod:`repro.kernels.gbdi_decode`) runs the same moves in reverse.

BlockSpec tiling: ``(pages_per_tile, page_words)`` input tiles in VMEM,
``pages_per_tile`` a multiple of the 8-row int32 sublane tile; the wrapper
pads the page count to whole tiles.  The VMEM budget is asserted in code
(:func:`vmem_tile_bytes`), not prose.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.core.format import (
    WORD16_HALF,
    WORD16_MASK,
    BaseTable,
    TableLike,
    as_base_table,
    class_indices,
    half_span,
)
from repro.core.gbdi_fr import FRConfig

DEFAULT_PAGES_PER_TILE = 8      # one int32 sublane tile
VMEM_BUDGET_BYTES = 16 * 1024 * 1024
#: (T, P) int32 planes live at once in the encode kernel, counted from its
#: body (words, per-base and per-alt running state, masks, rank and the
#: compaction carries); the compiler's own report is the check
#: (``tests/test_tpu_compile.py`` prints ``memory_analysis()``)
_LIVE_PLANES = 32


def vmem_tile_bytes(cfg: FRConfig, pages_per_tile: int) -> int:
    """Conservative per-tile VMEM estimate for the encode/decode kernels:
    double-buffered I/O tiles plus the live ``(T, P)`` int32 planes (one
    extra set per adaptive profile held until the per-page select)."""
    T, P, w = pages_per_tile, cfg.page_words, 4
    blob = T * (cfg.ptr_lanes + cfg.delta_lanes + 2 * cfg.outlier_cap + 4) * w
    io = 2 * (T * P * w + blob)
    planes = (_LIVE_PLANES + 4 * (cfg.num_profiles - 1)) * T * P * w
    return io + planes


def _check_vmem(cfg: FRConfig, pages_per_tile: int) -> None:
    est = vmem_tile_bytes(cfg, pages_per_tile)
    if est > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"encode tile needs ~{est >> 20} MiB VMEM (> {VMEM_BUDGET_BYTES >> 20} MiB); "
            f"lower pages_per_tile (={pages_per_tile}) or page_words (={cfg.page_words})"
        )


def smem_table(table: BaseTable, cfg: FRConfig) -> jax.Array:
    """Flat int32 SMEM table, four rows of ``cfg.num_bases`` entries:
    bases, width-class index (sentinel ``num_classes`` = dead entry),
    width (``word_bits + 1`` when dead) and fit half-span (0 when dead,
    so nothing fits)."""
    wb = cfg.word_bits
    cls = class_indices(table.widths, cfg.width_set)
    width = jnp.full(cls.shape, wb + 1, jnp.int32)
    half = jnp.zeros(cls.shape, jnp.int32)
    for i, w in enumerate(cfg.width_set):
        width = jnp.where(cls == i, jnp.int32(w), width)
        half = jnp.where(cls == i, jnp.int32(half_span(w)), half)
    return jnp.concatenate([table.bases.astype(jnp.int32), cls, width, half])


def pad_pages(x: jax.Array, pages_per_tile: int) -> jax.Array:
    """Zero rows up to a whole number of tiles (stripped by the callers)."""
    pad = (-x.shape[0]) % pages_per_tile
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) if pad else x


# ---------------------------------------------------------------------------
# lane primitives on (T, P) int32 tiles (shared with the decoder)
# ---------------------------------------------------------------------------

def lanes(shape: tuple[int, ...]) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def shift_right(y: jax.Array, s: int) -> jax.Array:
    """``out[:, p] = y[:, p - s]``, zero-filled (a lane rotation + mask)."""
    return jnp.where(lanes(y.shape) >= s, pltpu.roll(y, s, 1), 0)


def prefix_sum(y: jax.Array) -> jax.Array:
    """Hillis–Steele inclusive prefix sum along the lanes."""
    s = 1
    while s < y.shape[1]:
        y = y + shift_right(y, s)
        s *= 2
    return y


def carries(bits: int, n: int) -> bool:
    """Whether a ``bits``-wide payload and a lane distance below ``n`` share
    one int32 lane word, so that one rotation per step moves both."""
    return bits + (n - 1).bit_length() <= 31


def _move(
    val: jax.Array | None, dist: jax.Array, bits: int, left: bool,
) -> tuple[jax.Array, jax.Array]:
    """Move each word ``dist`` lanes, one power-of-two step per distance bit:
    left low bit first (compaction), right high bit first (expansion).

    Empty lanes hold distance 0 and payload 0.  At step ``b`` a lane
    receives a word exactly when the rotated distance there has bit ``b``
    set, so the distance alone says where words go.  When :func:`carries`
    holds, the word travels as ``dist << bits | val`` and each step rotates
    one plane; otherwise the payload rides in a second plane.  No word with
    bit ``b`` set sits within ``b`` lanes of the edge it moves towards, so
    what a rotation wraps round never arrives and needs no zero fill."""
    n = dist.shape[1]
    steps = [1 << i for i in range((n - 1).bit_length())]
    if val is None:
        planes, tag = [dist], 0
    elif carries(bits, n):
        planes, tag = [(dist << bits) | val], bits
    else:
        planes, tag = [dist, val], 0
    for b in steps if left else steps[::-1]:
        moved = [pltpu.roll(p, n - b if left else b, 1) for p in planes]
        arrive = (moved[0] & (b << tag)) != 0
        go = (planes[0] & (b << tag)) != 0
        planes = [jnp.where(arrive, m, jnp.where(go, 0, p)) for m, p in zip(moved, planes)]
    if val is None:
        return planes[0], planes[0]
    if tag:
        return planes[0] & ((1 << bits) - 1), planes[0] >> bits
    return planes[1], planes[0]


def compact(
    val: jax.Array | None, keep: jax.Array, rank: jax.Array, bits: int = 32,
) -> tuple[jax.Array, jax.Array]:
    """Move ``val[:, p]`` where ``keep`` to lane ``rank[:, p]`` (the kept
    words' page-order rank); every other lane ends zero.  Returns the moved
    values and, per lane, how far its word travelled (0 on empty lanes).

    Each kept word travels left by ``p - rank``, one power-of-two step per
    set bit, low bit first (:func:`_move`).  Kept words keep their order,
    so no step ever lands two words on one lane.  A ``bits``-wide unsigned
    payload shares the distance's int32 when :func:`carries` holds: then a
    step rotates one plane, else two.  ``val=None`` moves the distance
    alone, which is then both results."""
    dist = jnp.where(keep, lanes(keep.shape) - rank, 0)
    return _move(None if val is None else jnp.where(keep, val, 0), dist, bits, left=True)


def expand(val: jax.Array, dist: jax.Array, live: jax.Array, bits: int = 32) -> jax.Array:
    """Inverse of :func:`compact`: slot ``r`` (where ``live``) moves right by
    ``dist[:, r]`` to ``r + dist[:, r] < page_words`` — the compaction's
    moves replayed high bit first, one plane per step for a ``bits``-wide
    unsigned payload when :func:`carries` holds; every lane no slot reaches
    ends zero."""
    return _move(jnp.where(live, val, 0), jnp.where(live, dist, 0), bits, left=False)[0]


def _log2(n: int) -> int:
    return n.bit_length() - 1


def _head_steps(shape: tuple[int, ...], per: int) -> list[tuple[int, int, jax.Array]]:
    """The static moves between lane ``per * j`` and lane ``j``: one per bit
    ``k`` of ``j``, which travels between bit ``k + log2(per)`` and bit
    ``k`` of the lane index.  Each is ``(k, shift, field)``: the rotation
    and the index bits ``k .. k + log2(per)`` the masks read."""
    if per == 1:
        return []
    span = (2 << _log2(per)) - 1
    lane = lanes(shape)
    return [(k, (per - 1) << k, lane & (span << k))
            for k in range((shape[1] // per - 1).bit_length())]


def pack_fields(fields: jax.Array, bits: int) -> jax.Array:
    """``pack_lanes`` on a tile: field ``f`` (lane ``f``, ``< 2**bits``)
    lands in lane ``f // per`` at bit ``(f % per) * bits``, ``per = 32 //
    bits``.  Lanes past the packed words are zero.

    The group heads gather their neighbours by an OR tree, then move to
    their packed lanes; the distances are fixed by the lane, so each step
    rotates the payload alone under a mask computed from the lane index."""
    per = 32 // bits
    n = fields.shape[1]
    lane = lanes(fields.shape)
    g = fields << ((lane & (per - 1)) * bits)
    s = 1
    while s < per:                 # a head reads its own group only: no wrap
        g = g | pltpu.roll(g, n - s, 1)
        s *= 2
    for k, shift, field in _head_steps(g.shape, per):   # low bit first
        g = jnp.where(field == (1 << k), pltpu.roll(g, n - shift, 1), g)
    return jnp.where(lane < n // per, g, 0)


def unpack_fields(packed: jax.Array, bits: int) -> jax.Array:
    """Inverse of :func:`pack_fields`: lane ``f`` gets field ``f`` as an
    unsigned value in ``[0, 2**bits)``.  Only the first ``page_words // per``
    lanes of ``packed`` are read."""
    per = 32 // bits
    s = _log2(per)
    lane = lanes(packed.shape)
    heads = packed
    for k, shift, field in _head_steps(packed.shape, per)[::-1]:   # high bit first
        heads = jnp.where(field == (1 << (k + s)), pltpu.roll(heads, shift, 1), heads)
    heads = jnp.where((lane & (per - 1)) == 0, heads, 0)
    s = 1
    while s < per:                 # copy each group head over its group
        heads = heads | pltpu.roll(heads, s, 1)
        s *= 2
    sh = (lane & (per - 1)) * bits
    return jax.lax.shift_right_logical(heads, sh) & ((1 << bits) - 1)


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def _encode_kernel(
    x_ref: Any, tab_ref: Any, *out_refs: Any, cfg: FRConfig, k: int,
) -> None:
    ptr_ref, delta_ref, oval_ref, oidx_ref, nout_ref, nspill_ref, ndrop_ref = out_refs[:7]
    prof_ref = out_refs[7] if cfg.num_profiles > 1 else None
    x = x_ref[...]                                   # (T, P) int32 words
    T, P = x.shape
    wb, nc, cap_out = cfg.word_bits, cfg.num_classes, cfg.outlier_cap
    big = jnp.int32(wb + 1)
    lane = lanes(x.shape)
    zeros = jnp.zeros_like(x)

    with jax.named_scope(obs.ENCODE_CLASSIFY):
        # running minima over the base table: the narrowest fitting base, and
        # per class i < nc-1 the narrowest fitting base of a class > i (the
        # spill target); strict < keeps the first index, like argmin
        best = [jnp.full_like(x, wb + 1), zeros, zeros, zeros]  # cost, idx, cls, delta
        alts = [[jnp.full_like(x, wb + 1), zeros, zeros, zeros] for _ in range(nc - 1)]
        for j in range(k):
            base, cj, width, half = (tab_ref[r * k + j] for r in range(4))
            d = x - base
            if wb == 16:
                d = ((d + WORD16_HALF) & WORD16_MASK) - WORD16_HALF
            cost = jnp.where(jnp.maximum(d, -d - 1) < half, width, big)
            for i, st in [(-1, best)] + list(enumerate(alts)):
                # scalar select, then a vector max: cost where class > i, else big
                c = cost if i < 0 else jnp.maximum(cost, jnp.where(cj > i, 0, big))
                win = c < st[0]
                st[0] = jnp.where(win, c, st[0])
                st[1] = jnp.where(win, j, st[1])
                st[2] = jnp.where(win, cj, st[2])
                st[3] = jnp.where(win, d, st[3])

        found = best[0] <= wb
        is_zero = x == 0
        active0 = found & ~is_zero
        out_cand0 = ~found & ~is_zero

    def run_profile(caps: tuple[int, ...]) -> dict[str, jax.Array]:
        """Bucketing + spill chain under one cap profile (oracle parity)."""
        with jax.named_scope(obs.ENCODE_BUCKETS):
            sel, cls_sel, dsel = best[1], best[2], best[3]
            active, out_cand = active0, out_cand0
            deltas = zeros
            n_spilled = jnp.zeros((T, 1), jnp.int32)
            off = 0
            for i, (w, cap) in enumerate(zip(cfg.width_set, caps)):
                inclass = active & (cls_sel == i)
                rank = prefix_sum(inclass.astype(jnp.int32)) - 1
                keep = inclass & (rank < cap)
                over = inclass & ~keep
                if cap:
                    sub = compact(dsel & ((1 << w) - 1), keep, rank, w)[0]
                    packed = pack_fields(sub, w)
                    deltas = deltas | (pltpu.roll(packed, off, 1) if off else packed)
                    off += cap * w // 32
                if i < nc - 1:
                    a_cost, a_sel, a_cls, a_d = alts[i]
                    spill = over & (a_cost <= wb)
                    sel = jnp.where(spill, a_sel, sel)
                    cls_sel = jnp.where(spill, a_cls, cls_sel)
                    dsel = jnp.where(spill, a_d, dsel)
                    n_spilled = n_spilled + spill.astype(jnp.int32).sum(axis=1, keepdims=True)
                    newly_out = over & ~spill
                else:
                    newly_out = over
                active = active & ~newly_out
                out_cand = out_cand | newly_out

        # outlier compaction in page order; overflow = dropped -> the word
        # keeps the outlier code with no slot (decodes to 0).  The outlier
        # and pointer scopes alternate to keep the statements' order: the
        # TPU compiler's schedule follows it, and reordered, this kernel
        # compiles to more bundles per grid step.
        with jax.named_scope(obs.ENCODE_OUTLIERS):
            pos = prefix_sum(out_cand.astype(jnp.int32)) - 1
            in_table = out_cand & (pos < cap_out)
            out_vals, out_dist = compact(x, in_table, pos, wb)
        with jax.named_scope(obs.ENCODE_POINTERS):
            code = jnp.where(is_zero, cfg.zero_code, sel)
            code = jnp.where(out_cand, cfg.outlier_code, code)
        with jax.named_scope(obs.ENCODE_OUTLIERS):
            n_out = out_cand.astype(jnp.int32).sum(axis=1, keepdims=True)
        with jax.named_scope(obs.ENCODE_POINTERS):
            ptrs = pack_fields(code, cfg.ptr_bits)
        with jax.named_scope(obs.ENCODE_OUTLIERS):
            n_out = jnp.minimum(n_out, cap_out)
            out_idx = jnp.where(lane < n_out, lane + out_dist, 0)
            n_dropped = (out_cand & ~in_table).astype(jnp.int32).sum(axis=1, keepdims=True)
        return {
            "ptrs": ptrs,
            "deltas": deltas,
            "out_vals": out_vals,
            "out_idx": out_idx,
            "n_out": n_out,
            "n_spilled": n_spilled,
            "n_dropped": n_dropped,
        }

    cands = [run_profile(caps) for caps in cfg.profiles]
    if cfg.num_profiles == 1:
        blob, pid = cands[0], None
    else:
        with jax.named_scope(obs.ENCODE_POINTERS):   # the profile select
            # per-page argmin of the effective encoded size, first-wins ties —
            # identical cost + tie-break to cfg.profile_cost_bits (oracle/xla)
            costs = [jnp.int32(cfg.drop_penalty_bits) * b["n_dropped"]
                     + jnp.int32(8 * cfg.compressed_bytes_for_profile(p))
                     for p, b in enumerate(cands)]
            best_cost, pid = costs[0], jnp.zeros((T, 1), jnp.int32)
            for p in range(1, cfg.num_profiles):
                better = costs[p] < best_cost
                best_cost = jnp.where(better, costs[p], best_cost)
                pid = jnp.where(better, jnp.int32(p), pid)

            def select(field: str) -> jax.Array:
                acc = cands[0][field]
                for p in range(1, cfg.num_profiles):
                    acc = jnp.where(pid == p, cands[p][field], acc)
                return acc

            blob = {name: select(name) for name in cands[0]}

    ptr_ref[...] = blob["ptrs"][:, :cfg.ptr_lanes]
    delta_ref[...] = blob["deltas"][:, :cfg.delta_lanes]
    oval_ref[...] = blob["out_vals"][:, :cap_out]
    oidx_ref[...] = blob["out_idx"][:, :cap_out]
    nout_ref[...] = blob["n_out"]
    nspill_ref[...] = blob["n_spilled"]
    ndrop_ref[...] = blob["n_dropped"]
    if prof_ref is not None:
        prof_ref[...] = pid


@functools.partial(
    jax.jit, static_argnames=("cfg", "pages_per_tile", "interpret")
)
def gbdi_encode_pallas(
    x_pages: jax.Array,            # (n_pages, page_words) int32
    table: TableLike,              # BaseTable (or bare bases, v1 compat)
    cfg: FRConfig,
    *,
    pages_per_tile: int = DEFAULT_PAGES_PER_TILE,
    interpret: bool,               # True only off-TPU (correctness oracle)
) -> dict[str, jax.Array]:
    n_pages, P = x_pages.shape
    if P != cfg.page_words:
        raise ValueError(f"pages are {P} words, cfg.page_words={cfg.page_words}")
    if cfg.delta_lanes <= 0:
        raise ValueError("kernel path needs at least one non-empty bucket")
    _check_vmem(cfg, pages_per_tile)
    T, cap = pages_per_tile, cfg.outlier_cap
    k = cfg.num_bases
    tab = smem_table(as_base_table(table, default_width=cfg.widest_bits), cfg)
    x = pad_pages(x_pages, T)
    n_tiles = x.shape[0] // T

    out_shapes = [
        jax.ShapeDtypeStruct((x.shape[0], cfg.ptr_lanes), jnp.int32),
        jax.ShapeDtypeStruct((x.shape[0], cfg.delta_lanes), jnp.int32),
        jax.ShapeDtypeStruct((x.shape[0], cap), jnp.int32),
        jax.ShapeDtypeStruct((x.shape[0], cap), jnp.int32),
        jax.ShapeDtypeStruct((x.shape[0], 1), jnp.int32),
        jax.ShapeDtypeStruct((x.shape[0], 1), jnp.int32),
        jax.ShapeDtypeStruct((x.shape[0], 1), jnp.int32),
    ]
    out_specs = [
        pl.BlockSpec((T, cfg.ptr_lanes), lambda i: (i, 0)),
        pl.BlockSpec((T, cfg.delta_lanes), lambda i: (i, 0)),
        pl.BlockSpec((T, cap), lambda i: (i, 0)),
        pl.BlockSpec((T, cap), lambda i: (i, 0)),
        pl.BlockSpec((T, 1), lambda i: (i, 0)),
        pl.BlockSpec((T, 1), lambda i: (i, 0)),
        pl.BlockSpec((T, 1), lambda i: (i, 0)),
    ]
    if cfg.num_profiles > 1:   # adaptive: per-page profile id rides along
        out_shapes.append(jax.ShapeDtypeStruct((x.shape[0], 1), jnp.int32))
        out_specs.append(pl.BlockSpec((T, 1), lambda i: (i, 0)))
    kernel = functools.partial(_encode_kernel, cfg=cfg, k=k)
    outs = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((T, P), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shapes),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, tab)
    outs = [o[:n_pages] for o in outs]
    ptrs, deltas, out_vals, out_idx, n_out, n_spilled, n_dropped = outs[:7]
    # match the oracle's blob layout
    blob = {
        "ptrs": ptrs,
        "deltas": deltas,
        "out_vals": out_vals,
        "out_idx": out_idx,
        "n_out": n_out[:, 0],
        "n_spilled": n_spilled[:, 0],
        "n_dropped": n_dropped[:, 0],
    }
    if cfg.num_profiles > 1:
        blob["profile"] = outs[7][:, 0]
    return blob
