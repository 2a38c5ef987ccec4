"""The codec kernels' lane moves (``kernels/gbdi_encode.py``) against plain
numpy: compaction, expansion and field packing on random masks, in both
layouts of a move (payload and distance in one int32 lane word, or in two
planes), and the rotations each move and each kernel body compiles to."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro import obs
from repro.core.format import BaseTable
from repro.core.gbdi_fr import FRConfig
from repro.kernels import xla
from repro.kernels.gbdi_decode import gbdi_decode_pallas
from repro.kernels.gbdi_encode import (
    carries,
    compact,
    expand,
    gbdi_encode_pallas,
    pack_fields,
    unpack_fields,
)
from repro.serving.kv_cache import KV_FR

T = 8
# kept share per row: none, sparse, half, dense, all, and (row 5 on) one
# word in the first or the last lane, or both ends: what a rotation wraps
# round sits next to them
DENSITY = (0.0, 0.05, 0.5, 0.95, 1.0)


def _mask(rng, P, pattern):
    keep = np.stack([rng.random(P) < DENSITY[r % len(DENSITY)] for r in range(T)])
    if pattern == "edges":
        keep[5:] = False
        keep[5, P - 1] = True                       # the longest compaction
        keep[6, 0] = True                           # a word that never moves
        keep[7, [0, 1, P - 2, P - 1]] = True
        keep[:5, [0, P - 1]] = True
    return keep


def _payload(rng, P, bits):
    if bits == 32:       # the whole word, sign bit included
        return rng.integers(-(1 << 31), 1 << 31, (T, P), dtype=np.int64).astype(np.int32)
    return rng.integers(0, 1 << bits, (T, P)).astype(np.int32)


def _in_kernel(fn, n_out, *planes):
    """``fn`` on whole ``(T, P)`` int32 planes inside a Pallas kernel
    (interpret mode), which is where ``pltpu.roll`` runs."""
    shape = planes[0].shape

    def kernel(*refs):
        outs = fn(*(r[...] for r in refs[:len(planes)]))
        for ref, out in zip(refs[len(planes):], outs):
            ref[...] = out

    return pl.pallas_call(
        kernel, out_shape=tuple(jax.ShapeDtypeStruct(shape, jnp.int32) for _ in range(n_out)),
        interpret=True)(*(jnp.asarray(p) for p in planes))


def _compact_ref(val, keep):
    out, dist = np.zeros_like(val), np.zeros_like(val)
    for r in range(T):
        src = np.flatnonzero(keep[r])
        out[r, :src.size] = val[r, src]
        dist[r, :src.size] = src - np.arange(src.size)
    return out, dist


def _expand_ref(val, dist, live):
    out = np.zeros_like(val)
    for r in range(T):
        slots = np.flatnonzero(live[r])
        out[r, slots + dist[r, slots]] = val[r, slots]
    return out


def _pack_ref(fields, bits):
    per = 32 // bits
    f = fields.astype(np.uint32).reshape(T, -1, per) << (np.arange(per, dtype=np.uint32) * bits)
    out = np.zeros(fields.shape, np.uint32)
    out[:, :f.shape[1]] = np.bitwise_or.reduce(f, axis=2)
    return out.view(np.int32)


def _unpack_ref(packed, bits):
    per = 32 // bits
    P = packed.shape[1]
    words = packed.view(np.uint32)[:, :P // per]
    sh = np.arange(per, dtype=np.uint32) * bits
    return ((words[:, :, None] >> sh) & ((1 << bits) - 1)).reshape(T, P).astype(np.int32)


# (move, page_words, payload bits, mask pattern, one int32 per step?)
CASES = [
    ("compact", 256, 8, "random", True),
    ("compact", 256, 32, "random", False),
    ("compact", 2048, None, "random", True),      # the distance alone
    ("compact", 2048, 16, "edges", True),
    ("compact", 2048, 32, "edges", False),
    ("compact", 16384, 16, "random", True),       # 14 + 16 = 30 bits: still one
    ("compact", 16384, 16, "edges", True),
    ("expand", 256, 8, "random", True),
    ("expand", 256, 32, "random", False),
    ("expand", 2048, 16, "edges", True),
    ("expand", 2048, 32, "edges", False),
    ("expand", 16384, 16, "random", True),
    ("expand", 16384, 16, "edges", True),
    ("pack", 256, 1, "random", True),
    ("pack", 384, 8, "random", True),             # lane-aligned, not a power of two
    ("pack", 2048, 4, "random", True),
    ("pack", 2048, 16, "random", True),
    ("unpack", 256, 1, "random", True),
    ("unpack", 384, 8, "random", True),
    ("unpack", 2048, 2, "random", True),
    ("unpack", 2048, 4, "random", True),
    ("unpack", 2048, 16, "random", True),
]


@pytest.mark.parametrize("move,P,bits,pattern,one_plane", CASES,
                         ids=[f"{c[0]}-p{c[1]}-b{c[2]}-{c[3]}" for c in CASES])
def test_lane_move_matches_numpy(move, P, bits, pattern, one_plane):
    rng = np.random.default_rng(P * 131 + (bits or 0) * 7 + len(move) + len(pattern))
    if move == "pack":
        fields = _payload(rng, P, bits)
        got, = _in_kernel(lambda f: (pack_fields(f, bits),), 1, fields)
        np.testing.assert_array_equal(np.asarray(got), _pack_ref(fields, bits))
        return
    if move == "unpack":
        packed = _payload(rng, P, 32)    # lanes past the packed words hold junk
        got, = _in_kernel(lambda p: (unpack_fields(p, bits),), 1, packed)
        np.testing.assert_array_equal(np.asarray(got), _unpack_ref(packed, bits))
        return
    if bits is not None:
        assert carries(bits, P) == one_plane
    keep = _mask(rng, P, pattern)
    val = _payload(rng, P, bits or 32)
    rank = (np.cumsum(keep, axis=1) - 1).astype(np.int32)
    want_val, want_dist = _compact_ref(val, keep)
    if move == "compact":
        if bits is None:
            dist, = _in_kernel(lambda k, r: compact(None, k != 0, r)[1:], 1,
                               keep.astype(np.int32), rank)
        else:
            got, dist = _in_kernel(lambda v, k, r: compact(v, k != 0, r, bits), 2,
                                   val, keep.astype(np.int32), rank)
            np.testing.assert_array_equal(np.asarray(got), want_val)
        np.testing.assert_array_equal(np.asarray(dist), want_dist)
        return
    # expand the compacted slots back, the last few slots of a row not
    # live (dropped outliers: their words end zero)
    count = keep.sum(axis=1)
    n_live = np.maximum(count - rng.integers(0, 3, T), 0)
    live = np.arange(P)[None, :] < n_live[:, None]
    got, = _in_kernel(lambda v, d, lv: (expand(v, d, lv != 0, bits),), 1,
                      want_val, want_dist, live.astype(np.int32))
    want = _expand_ref(want_val, want_dist, live)
    np.testing.assert_array_equal(np.asarray(got), want)
    full = live.sum(axis=1) == count
    np.testing.assert_array_equal(np.asarray(got)[full], np.where(keep, val, 0)[full])


# rotations per move at 2048 lanes: one per distance bit (11) in one int32,
# two planes where a 32-bit payload leaves no room; a field pack or unpack
# of per = 32 // bits fields per lane takes log2(per) for the OR tree or
# the copy, and log2(2048 / per) static moves
MOVE_ROLLS = [
    ("compact", 16, 11),
    ("compact", 32, 22),
    ("compact", None, 11),
    ("expand", 16, 11),
    ("expand", 32, 22),
    ("pack", 8, 2 + 9),
    ("pack", 4, 3 + 8),
    ("unpack", 8, 2 + 9),
    ("unpack", 4, 3 + 8),
]


@pytest.mark.parametrize("move,bits,rolls", MOVE_ROLLS,
                         ids=[f"{m}-b{b}" for m, b, _ in MOVE_ROLLS])
def test_rotations_per_move(move, bits, rolls):
    plane = jax.ShapeDtypeStruct((T, 2048), jnp.int32)
    mask = jax.ShapeDtypeStruct((T, 2048), jnp.bool_)
    fn, args = {
        "compact": (lambda v, k, r: compact(None if bits is None else v, k, r, bits or 32),
                    (plane, mask, plane)),
        "expand": (lambda v, d, lv: expand(v, d, lv, bits), (plane, plane, mask)),
        "pack": (lambda f: pack_fields(f, bits), (plane,)),
        "unpack": (lambda p: unpack_fields(p, bits), (plane,)),
    }[move]
    eqns = jax.make_jaxpr(fn)(*args).jaxpr.eqns
    assert sum(e.primitive.name == "roll" for e in eqns) == rolls


W32 = FRConfig(word_bits=32, page_words=2048, num_bases=14, width_set=(8, 16),
               bucket_caps=(512, 1536), outlier_cap=64)
# roll primitives in each kernel body (64 pages): KV_FR's were 170 and 225
# with three planes rotated per step; a 32-bit-word format's outlier moves
# take two planes
KERNEL_ROLLS = {"KV_FR": (KV_FR, 66, 88), "w32": (W32, 111, 144)}


@pytest.mark.parametrize("name", sorted(KERNEL_ROLLS))
def test_kernel_body_rotations(name):
    cfg, enc_rolls, dec_rolls = KERNEL_ROLLS[name]
    k = cfg.num_bases
    table = BaseTable(jnp.arange(k, dtype=jnp.int32), jnp.full((k,), 8, jnp.int32))
    x = jax.ShapeDtypeStruct((64, cfg.page_words), jnp.int32)
    blob = jax.eval_shape(lambda x, t: xla.encode_pages(x, t, cfg), x, table)
    enc = obs.kernel_primitive_counts(jax.make_jaxpr(
        lambda x, t: gbdi_encode_pallas(x, t, cfg, interpret=False))(x, table))
    dec = obs.kernel_primitive_counts(jax.make_jaxpr(
        lambda b, t: gbdi_decode_pallas(b, t, cfg, interpret=False))(blob, table))
    print(f"{name}: encode {enc['roll']} rolls {enc['select_n']} selects, "
          f"decode {dec['roll']} rolls {dec['select_n']} selects")
    assert (enc["roll"], dec["roll"]) == (enc_rolls, dec_rolls)
