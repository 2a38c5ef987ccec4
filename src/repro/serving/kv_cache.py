"""Paged KV cache with GBDI-FR compressed pages.

The decode-time memory wall is KV-cache HBM traffic: every generated token
re-reads the whole cache.  GBDI-FR pages cut those bytes by the fixed rate
(~1.23x for bf16 at ~13 bits/word incl. the outlier table) — the paper's
bandwidth story applied to serving.

Layout per stream of an attention layer (structure-of-arrays, all static
shapes; a K/V layer has streams ``k`` and ``v``, a latent-attention layer
the one stream ``c``):

  pages:   ptrs (B, n_slots, ptr_lanes)  deltas (B, n_slots, delta_lanes)
           out_vals/out_idx (B, n_slots, cap)  n_out (B, n_slots)
  tail:    raw ring (B, group_tokens, *row_shape) — most recent tokens
  dropped: (B,) words the flushes dropped (outliers past a page's table)
  table:   the fitted BaseTable (bases + per-base v2 width classes)
  scalars: handled by the caller (decode position)

The cache is quality-critical, so ``KV_FR`` uses the v2 single-width
special case (one 8-bit class, full-page bucket): bucket overflow cannot
occur and base coverage matches v1 exactly — multi-width fits pair some
bases with the 4-bit class, which shrinks coverage and overflows the
outlier table on realistic KV distributions (words then decode to 0).
Multi-width configs remain available per-spec for workloads whose
measured demand fits (see ``repro.eval.run --sweep``), and adaptive
``cap_profiles`` configs carry their per-page profile id in the cache
tree (the compiled xla attention path selects per page).  The per-page
``n_spilled`` is discarded at flush; ``n_dropped`` is summed into the
``dropped`` counter.

Rows are cut into pages across token boundaries: a flush group of
``group_tokens`` rows fills ``group_pages`` whole pages (see
:class:`_Geometry`).  Appends go to the raw tail; when the tail fills, its
group is compressed into the next page slots (branchless ``lax.cond``).
Reads decompress pages on the fly; K/V decode attention without a resident
region takes the compiled batched paged-attention path
(:mod:`repro.kernels.xla`) with the raw tail softmax-merged in.

Keys/values cache *with RoPE already applied* (like the raw cache), so
page contents are position-final and compress-once.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.format import (
    DEFAULT_NUM_BASES,
    DEFAULT_OUTLIER_CAP,
    DEFAULT_PAGE_WORDS,
    BaseTable,
)
from repro.core.gbdi_fr import FRConfig
from repro.kernels import ops
from repro.kernels import xla as fr_xla

# The per-token flush and the cache's own decodes run the XLA chain on every
# platform; moving them to the Pallas kernels is ROADMAP speed item 7.
_CODEC_BACKEND = "xla"

KV_FR = FRConfig(word_bits=16, page_words=DEFAULT_PAGE_WORDS,
                 num_bases=DEFAULT_NUM_BASES, width_set=(8,),
                 bucket_caps=(DEFAULT_PAGE_WORDS,),
                 outlier_cap=DEFAULT_OUTLIER_CAP)

# the cache tree: array leaves plus the fitted BaseTable pytree
Cache = dict[str, Any]


class _Geometry:
    """Page geometry shared by the cache kinds.

    A stream holds one row of ``row_shape`` words per token.  Rows are cut
    into pages of ``fr.page_words`` words across token boundaries, so a
    flush group is the fewest whole rows that fill whole pages:
    ``group_tokens`` rows make ``group_pages`` pages (the lcm of the two
    widths).  A 4096-word K row is 1 token in 2 pages, a 32-word row 64
    tokens in 1 page, a 576-word latent row 32 tokens in 9 pages.  The raw
    tail ring holds one group; when it fills, the group is compressed
    into its page slots.

    The byte counts are what :func:`init_compressed` allocates for
    ``batch`` sequences (the shared base table aside): every page slot's
    int32 leaves, the tail ring, the per-sequence dropped-word counter
    and, with ``resident_decode``, the decoded region.
    """

    streams: ClassVar[tuple[str, ...]]
    max_len: int
    fr: FRConfig
    resident_decode: bool

    @property
    def row_shape(self) -> tuple[int, ...]:
        raise NotImplementedError

    @property
    def row_words(self) -> int:
        return math.prod(self.row_shape)

    @property
    def group_tokens(self) -> int:
        return math.lcm(self.row_words, self.fr.page_words) // self.row_words

    @property
    def group_pages(self) -> int:
        return math.lcm(self.row_words, self.fr.page_words) // self.fr.page_words

    @property
    def n_groups(self) -> int:
        return math.ceil(self.max_len / self.group_tokens)

    @property
    def n_slots(self) -> int:
        """Page slots per sequence and stream."""
        return self.n_groups * self.group_pages

    @property
    def word_bytes(self) -> int:
        """Bytes per uncompressed memory word (2 for bf16 rows)."""
        return self.fr.word_bits // 8

    @property
    def slot_bytes(self) -> int:
        """Bytes of one page slot's int32 leaves: pointer and delta lanes,
        outlier values and indices, the outlier count (and the profile id
        of an adaptive format)."""
        fr = self.fr
        lanes = fr.ptr_lanes + fr.delta_lanes + 2 * fr.outlier_cap + 1
        return 4 * (lanes + (1 if fr.num_profiles > 1 else 0))

    def _bytes(self, batch: int, groups: int) -> int:
        row = self.row_words * self.word_bytes
        per_stream = groups * self.group_pages * self.slot_bytes \
            + self.group_tokens * row + 4          # tail ring, dropped counter
        if self.resident_decode:                   # decoded copy is resident HBM too
            per_stream += groups * self.group_tokens * row
        return len(self.streams) * batch * per_stream

    def compressed_bytes(self, batch: int) -> int:
        return self._bytes(batch, self.n_groups)

    def raw_bytes(self, batch: int) -> int:
        return len(self.streams) * batch * self.max_len * self.row_words * self.word_bytes

    def compressed_bytes_upto(self, batch: int, n_tokens: int) -> int:
        """Bytes needed to hold just the first ``n_tokens`` of a sequence:
        the page slots of the groups those tokens fill plus the raw tail
        ring (always allocated — unflushed tokens live there).  This is the
        irreducible footprint the serving scheduler charges a prompt when
        deciding whether a request can *ever* fit its byte budget; the
        full static-slot cost is :meth:`compressed_bytes`."""
        return self._bytes(batch, min(self.n_groups, max(0, n_tokens) // self.group_tokens))

    def raw_bytes_upto(self, batch: int, n_tokens: int) -> int:
        """Raw-cache analogue of :meth:`compressed_bytes_upto`."""
        n = min(self.max_len, max(0, n_tokens))
        return len(self.streams) * batch * n * self.row_words * self.word_bytes


@dataclasses.dataclass(frozen=True)
class KVSpec(_Geometry):
    """A K/V pair per layer: streams ``k`` and ``v`` of (n_kv, head_dim)
    rows.  ``resident_decode=True`` adds an incremental decoded-page
    region (``k_dec``/``v_dec`` bf16 leaves) to the cache tree: every
    flushed page is decoded ONCE — at flush, from the same blob that
    landed in the page slots, so capacity drops round-trip identically —
    and reused by every later read.  ``read_full`` then costs O(tail
    overlay) per step instead of O(all pages), at the HBM price of keeping
    the decoded copy resident (the compressed pages remain the
    transport/storage format; ``compressed_bytes`` counts both when the
    region is enabled).  Invariant (property-tested): at every step
    ``k_dec``/``v_dec`` are bit-identical to a from-scratch
    ``_decompress_all`` of the page slots."""

    n_kv: int
    head_dim: int
    max_len: int
    fr: FRConfig = KV_FR
    resident_decode: bool = False

    streams: ClassVar[tuple[str, ...]] = ("k", "v")

    @property
    def row_shape(self) -> tuple[int, ...]:
        return (self.n_kv, self.head_dim)


@dataclasses.dataclass(frozen=True)
class LatentSpec(_Geometry):
    """One latent stream ``c`` per layer, as multi-head latent attention
    caches it: a row per token of the normed ``latent_dim`` latent
    followed by the roped ``rope_dim`` key part, which every head reads
    (keys are the whole row, values its first ``latent_dim`` words).
    Same resident region and invariant as :class:`KVSpec`."""

    latent_dim: int
    rope_dim: int
    max_len: int
    fr: FRConfig = KV_FR
    resident_decode: bool = False

    streams: ClassVar[tuple[str, ...]] = ("c",)

    @property
    def row_shape(self) -> tuple[int, ...]:
        return (self.latent_dim + self.rope_dim,)


Spec = KVSpec | LatentSpec


def _page_zeros(fr: FRConfig, batch: int, n: int) -> dict[str, jax.Array]:
    """A zero blob of ``n`` page slots per sequence."""
    z = {
        "ptrs": jnp.zeros((batch, n, fr.ptr_lanes), jnp.int32),
        "deltas": jnp.zeros((batch, n, fr.delta_lanes), jnp.int32),
        "out_vals": jnp.zeros((batch, n, fr.outlier_cap), jnp.int32),
        "out_idx": jnp.zeros((batch, n, fr.outlier_cap), jnp.int32),
        "n_out": jnp.zeros((batch, n), jnp.int32),
    }
    if fr.num_profiles > 1:   # adaptive cfg: per-page profile ids
        z["profile"] = jnp.zeros((batch, n), jnp.int32)
    return z


def init_compressed(spec: Spec, batch: int, table: BaseTable) -> Cache:
    cache: Cache = {"table": table}
    for s in spec.streams:
        cache[f"{s}_pages"] = _page_zeros(spec.fr, batch, spec.n_slots)
        cache[f"{s}_tail"] = jnp.zeros((batch, spec.group_tokens, *spec.row_shape), jnp.bfloat16)
        cache[f"{s}_dropped"] = jnp.zeros((batch,), jnp.int32)
    if spec.resident_decode:
        # Seed the resident region by decoding zero pages, NOT with plain
        # zeros: a zero blob decodes to bases[0]-derived words, and the
        # invariant is bit-identity with a from-scratch ``_decompress_all``
        # for unflushed pages too.  Every zero group decodes alike, so one
        # group is decoded and tiled.
        one = _decompress_all(spec, _page_zeros(spec.fr, 1, spec.group_pages), table)
        for s in spec.streams:
            cache[f"{s}_dec"] = jnp.tile(one, (batch, spec.n_groups) + (1,) * len(spec.row_shape))
    return cache


def _to_words(x16: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x16.astype(jnp.bfloat16), jnp.uint16).astype(jnp.int32)


def _from_words(w: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(w.astype(jnp.uint16), jnp.bfloat16)


def _split_blob(blob: dict[str, jax.Array]) -> tuple[dict[str, jax.Array], jax.Array]:
    """An encoder's blob -> (the fields a page slot keeps, dropped words
    per leading row).  The per-page spill count is discarded."""
    blob = dict(blob)
    blob.pop("n_spilled", None)
    dropped = blob.pop("n_dropped")
    return blob, dropped.reshape(dropped.shape[0], -1).sum(axis=1, dtype=jnp.int32)


def _compress_rows(spec: Spec, rows: jax.Array,
                   table: BaseTable) -> tuple[dict[str, jax.Array], jax.Array]:
    """rows: (B, group_tokens, *row_shape) -> per-batch page blobs
    (B, group_pages, ...) and the words each sequence's pages dropped.

    All B * group_pages pages go through one batched encode, not a
    vmap-of-vmap over single pages.
    """
    B = rows.shape[0]
    words = _to_words(rows).reshape(B, -1, spec.fr.page_words)
    return _split_blob(ops.encode_pages(words, table, spec.fr, backend=_CODEC_BACKEND))


def _decompress_all(spec: Spec, pages: dict[str, jax.Array], table: BaseTable) -> jax.Array:
    """-> (B, n_groups*group_tokens, *row_shape) bf16; one batched decode."""
    B = pages["ptrs"].shape[0]
    words = ops.decode_pages(pages, table, spec.fr, backend=_CODEC_BACKEND)
    return _from_words(words.reshape(B, -1, *spec.row_shape))


def _put_pages(dst: dict[str, jax.Array], src: dict[str, jax.Array],
               slot: jax.Array) -> dict[str, jax.Array]:
    merged: dict[str, jax.Array] = jax.tree_util.tree_map(
        lambda d, s: jax.lax.dynamic_update_slice(
            d, s.astype(d.dtype), (0, slot) + (0,) * (d.ndim - 2)),
        dst, src)
    return merged


def append(spec: KVSpec, cache: Cache, k: jax.Array, v: jax.Array, pos: jax.Array) -> Cache:
    """Append one token (B, 1, Kv, hd) at absolute position ``pos``."""
    return append_rows(spec, cache, {"k": k, "v": v}, pos)


def append_rows(spec: Spec, cache: Cache, rows: dict[str, jax.Array], pos: jax.Array) -> Cache:
    """Append one token's row of each stream, (B, 1, *row_shape), at
    absolute position ``pos``; when that fills the tail ring, flush its
    group into the page slots (branchless ``lax.cond``)."""
    G = spec.group_tokens
    slot = pos % G
    zeros = (0,) * len(spec.row_shape)
    tails = {f"{s}_tail": jax.lax.dynamic_update_slice(
        cache[f"{s}_tail"], r.astype(jnp.bfloat16), (0, slot, *zeros))
        for s, r in rows.items()}
    group = pos // G

    def flush(c: Cache) -> Cache:
        out = {**c, **tails}
        blobs = {}
        with jax.named_scope(obs.KV_FLUSH_ENCODE):
            for s in rows:
                blobs[s], dropped = _compress_rows(spec, tails[f"{s}_tail"], cache["table"])
                out[f"{s}_dropped"] = c[f"{s}_dropped"] + dropped
        for s in rows:
            out[f"{s}_pages"] = _put_pages(c[f"{s}_pages"], blobs[s], group * spec.group_pages)
        if spec.resident_decode:
            # Incremental decode: decode the just-encoded blob (NOT the raw
            # tail — capacity-dropped outliers must round-trip identically to
            # a from-scratch decode of the page slots) and land it at this
            # group's token offset.  O(one group) per flush; reads reuse it.
            def dec(blob: dict[str, jax.Array]) -> jax.Array:
                w = ops.decode_pages(blob, cache["table"], spec.fr, backend=_CODEC_BACKEND)
                return _from_words(w.reshape(w.shape[0], G, *spec.row_shape))
            with jax.named_scope(obs.KV_FLUSH_DECODE):
                for s in rows:
                    out[f"{s}_dec"] = jax.lax.dynamic_update_slice(
                        c[f"{s}_dec"], dec(blobs[s]), (0, group * G, *zeros))
        return out

    def nop(c: Cache) -> Cache:
        return {**c, **tails}

    out: Cache = jax.lax.cond(slot == G - 1, flush, nop, cache)
    return out


def prefill_groups(spec: Spec, cache: Cache, rows: dict[str, jax.Array],
                   start: jax.Array) -> Cache:
    """Write a context's rows, (B, T, *row_shape) per stream, at positions
    [start, start + T) as whole flush groups: ``start`` and ``T`` are
    multiples of ``group_tokens``.  Every page is encoded (and, with the
    resident region, decoded) in one batched call through
    :mod:`repro.kernels.ops` — the Pallas kernels on TPU.  The result is
    bit-identical to T single-token :func:`append_rows` calls."""
    G, T = spec.group_tokens, next(iter(rows.values())).shape[1]
    if T % G:
        raise ValueError(f"prefill of {T} tokens is not whole groups of {G}")
    zeros = (0,) * len(spec.row_shape)
    out = dict(cache)
    blobs = {}
    with jax.named_scope(obs.KV_FLUSH_ENCODE):
        for s, r in rows.items():
            B = r.shape[0]
            words = _to_words(r).reshape(-1, spec.fr.page_words)
            blob, dropped = _split_blob(ops.encode_pages(words, cache["table"], spec.fr))
            blobs[s] = blob
            out[f"{s}_dropped"] = cache[f"{s}_dropped"] + dropped.reshape(B, -1).sum(axis=1)
            out[f"{s}_pages"] = _put_pages(
                cache[f"{s}_pages"], {k: v.reshape(B, -1, *v.shape[1:]) for k, v in blob.items()},
                start // G * spec.group_pages)
            out[f"{s}_tail"] = r[:, T - G:].astype(jnp.bfloat16)
    if spec.resident_decode:
        with jax.named_scope(obs.KV_FLUSH_DECODE):
            for s, r in rows.items():
                words = ops.decode_pages(blobs[s], cache["table"], spec.fr)
                out[f"{s}_dec"] = jax.lax.dynamic_update_slice(
                    cache[f"{s}_dec"], _from_words(words.reshape(r.shape)), (0, start, *zeros))
    return out


def read_full(spec: Spec, cache: Cache, pos: jax.Array) -> tuple[jax.Array, ...]:
    """-> (one view per stream, valid) covering [0, pos]: decompressed
    pages with the raw tail overlaid for the current (unflushed) group;
    (K, V, valid) for a :class:`KVSpec`, (C, valid) for a
    :class:`LatentSpec`.

    With ``spec.resident_decode`` the pages were already decoded at flush
    time, so this is just the tail overlay — per-step cost stops scaling
    with context length (the decode work moved to one group per flush).
    """
    G = spec.group_tokens
    at = (0, (pos // G) * G) + (0,) * len(spec.row_shape)
    views = []
    for s in spec.streams:
        full = cache[f"{s}_dec"] if f"{s}_dec" in cache else \
            _decompress_all(spec, cache[f"{s}_pages"], cache["table"])
        views.append(jax.lax.dynamic_update_slice(full, cache[f"{s}_tail"], at))
    valid = jnp.arange(views[0].shape[1]) <= pos
    return (*views, valid)


def attention_decode(
    spec: KVSpec, q: jax.Array, cache: Cache, pos: jax.Array,
    backend: str = "auto",
) -> jax.Array:
    """q: (B, 1, H, hd) -> (B, 1, H*hd) over the compressed cache.

    ``backend='oracle'`` attends over the full decompressed view (the
    semantic reference).  ``'resident'`` is the same math but requires the
    ``spec.resident_decode`` incremental region, so no page is decoded on
    this step at all.  ``'xla'`` attends over the compressed pages with
    the compiled paged-attention decode
    (:func:`repro.kernels.xla.paged_attention_decode`) and merges the raw
    tail via the streaming-softmax identity — one batched dispatch, no
    decompressed cache materialised between layers.  ``'auto'`` (default)
    picks the resident region when the cache carries one, else the paged
    path.
    """
    if backend not in ("oracle", "resident", "xla", "auto"):
        raise ValueError(f"unknown backend {backend!r}; "
                         "choose from ('oracle', 'resident', 'xla', 'auto')")
    if backend == "resident" and "k_dec" not in cache:
        raise ValueError("backend='resident' requires a cache built with "
                         "spec.resident_decode=True")
    with jax.named_scope(obs.KV_ATTEND):
        if backend in ("oracle", "resident") or (backend == "auto" and "k_dec" in cache):
            K, V, valid = read_full(spec, cache, pos)
            B, S, Kv, hd = K.shape
            H = q.shape[2]
            scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
            qg = q.reshape(B, 1, Kv, H // Kv, hd)
            logits = jnp.einsum("bskgh,btkh->bkgst", qg, K).astype(jnp.float32) * scale
            logits = jnp.where(valid[None, None, None, None, :], logits, -1e30)
            probs = jax.nn.softmax(logits, axis=-1).astype(V.dtype)
            out = jnp.einsum("bkgst,btkh->bskgh", probs, V)
            return out.reshape(B, 1, H * hd)

        from repro.kernels.gbdi_paged_attn import merge_softmax

        B, _, H, hd = q.shape
        Kv = spec.n_kv
        G = H // Kv
        qg = q.reshape(B, Kv, G, hd).astype(jnp.float32)
        acc, m, l = fr_xla.paged_attention_decode(
            qg, cache["k_pages"], cache["v_pages"], cache["table"], pos, spec.fr,
            n_kv=Kv, hd=hd, groups=G,
        )
        # raw-tail stream (the current partial page), then softmax-merge
        pt = spec.group_tokens
        scale = 1.0 / jnp.sqrt(jnp.float32(hd))
        Kt = cache["k_tail"].astype(jnp.float32)
        Vt = cache["v_tail"].astype(jnp.float32)
        tail_valid = (pos // pt) * pt + jnp.arange(pt) <= pos
        lg = jnp.einsum("bkgh,btkh->bkgt", qg, Kt) * scale
        lg = jnp.where(tail_valid[None, None, None, :], lg, -1e30)
        m2 = lg.max(-1)
        p2 = jnp.where(lg <= -1e29, 0.0, jnp.exp(lg - m2[..., None]))
        acc2 = jnp.einsum("bkgt,btkh->bkgh", p2, Vt)
        accm, _, lm = merge_softmax(acc, m, l, acc2, m2, p2.sum(-1))
        out = accm / lm[..., None]
        return out.reshape(B, 1, H * hd).astype(cache["k_tail"].dtype)


def attention_decode_latent(spec: LatentSpec, q: jax.Array, cache: Cache, pos: jax.Array,
                            scale: float) -> jax.Array:
    """Absorbed latent attention of one decode step over the cache: q
    (B, 1, H, latent_dim + rope_dim), every head's query already absorbed
    into the latent, -> (B, 1, H, latent_dim).  All heads score the whole
    row and read its first ``latent_dim`` words as the value, over the
    resident region when the cache carries one, else over a decode of
    every page."""
    from repro.models import mla

    with jax.named_scope(obs.KV_ATTEND):
        rows, valid = read_full(spec, cache, pos)
        return mla.latent_attention(q, rows, valid, scale, spec.latent_dim)
