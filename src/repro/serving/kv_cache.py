"""Paged KV cache with GBDI-FR compressed pages.

The decode-time memory wall is KV-cache HBM traffic: every generated token
re-reads the whole cache.  GBDI-FR pages cut those bytes by the fixed rate
(~1.23x for bf16 at ~13 bits/word incl. the outlier table) — the paper's
bandwidth story applied to serving.

Layout per attention layer (structure-of-arrays, all static shapes):

  pages:   ptrs (B, n_pages, ptr_lanes)  deltas (B, n_pages, delta_lanes)
           out_vals/out_idx (B, n_pages, cap)  n_out (B, n_pages)
  tail:    k/v raw ring (B, page_tokens, Kv, hd) — most recent tokens
  table:   the fitted BaseTable (bases + per-base v2 width classes)
  scalars: handled by the caller (decode position)

The cache is quality-critical, so ``KV_FR`` uses the v2 single-width
special case (one 8-bit class, full-page bucket): bucket overflow cannot
occur and base coverage matches v1 exactly — multi-width fits pair some
bases with the 4-bit class, which shrinks coverage and overflows the
outlier table on realistic KV distributions (words then decode to 0).
Multi-width configs remain available per-``KVSpec`` for workloads whose
measured demand fits (see ``repro.eval.run --sweep``), and adaptive
``cap_profiles`` configs carry their per-page profile id in the cache
tree (the compiled xla attention path selects per page; the fused Pallas
kernel requires a single-profile cfg).  Note the per-page
``n_spilled``/``n_dropped`` diagnostics are discarded at flush (static
cache tree); measure them offline via ``fr_encode`` if needed.

A page holds ``page_tokens = page_words // (Kv*hd)`` consecutive tokens'
K (or V) values.  Appends go to the raw tail; when the tail fills, it is
compressed into the next page slot (branchless ``lax.cond``).  Reads
decompress pages on the fly; decode attention defaults to the compiled
batched paged-attention path (:mod:`repro.kernels.xla`) with the raw tail
softmax-merged in — or never leaves VMEM at all in the fused Pallas
kernel (:mod:`repro.kernels.gbdi_paged_attn`) on TPU.

Keys/values cache *with RoPE already applied* (like the raw cache), so
page contents are position-final and compress-once.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

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
from repro.kernels import pipeline as fr_pipeline
from repro.kernels import xla as fr_xla

KV_FR = FRConfig(word_bits=16, page_words=DEFAULT_PAGE_WORDS,
                 num_bases=DEFAULT_NUM_BASES, width_set=(8,),
                 bucket_caps=(DEFAULT_PAGE_WORDS,),
                 outlier_cap=DEFAULT_OUTLIER_CAP)

# the cache tree: array leaves plus the fitted BaseTable pytree
Cache = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class KVSpec:
    """Cache geometry.  ``resident_decode=True`` adds an incremental
    decoded-page region (``k_dec``/``v_dec`` bf16 leaves) to the cache
    tree: every flushed page is decoded ONCE — at flush, from the same
    blob that landed in the page slots, so capacity drops round-trip
    identically — and reused by every later read.  ``read_full`` then
    costs O(tail overlay) per step instead of O(all pages), at the HBM
    price of keeping the decoded copy resident (the compressed pages
    remain the transport/storage format; ``compressed_bytes`` counts
    both when the region is enabled).  Invariant (property-tested): at
    every step ``k_dec``/``v_dec`` are bit-identical to a from-scratch
    ``_decompress_all`` of the page slots."""

    n_kv: int
    head_dim: int
    max_len: int
    fr: FRConfig = KV_FR
    resident_decode: bool = False

    @property
    def row_words(self) -> int:
        return self.n_kv * self.head_dim

    @property
    def page_tokens(self) -> int:
        assert self.fr.page_words % self.row_words == 0 or self.row_words % self.fr.page_words == 0
        return max(1, self.fr.page_words // self.row_words)

    @property
    def n_pages(self) -> int:
        return math.ceil(self.max_len / self.page_tokens)

    @property
    def word_bytes(self) -> int:
        """Bytes per uncompressed memory word (2 for bf16 rows)."""
        return self.fr.word_bits // 8

    def compressed_bytes(self, batch: int) -> int:
        per_page = self.fr.compressed_bytes_per_page()
        pages = 2 * batch * self.n_pages * per_page  # k and v
        tail = 2 * batch * self.page_tokens * self.row_words * self.word_bytes
        if self.resident_decode:  # decoded copy is resident HBM too
            pages += 2 * batch * self.n_pages * self.page_tokens \
                * self.row_words * self.word_bytes
        return pages + tail

    def raw_bytes(self, batch: int) -> int:
        return 2 * batch * self.max_len * self.row_words * self.word_bytes  # k and v

    def compressed_bytes_upto(self, batch: int, n_tokens: int) -> int:
        """Bytes needed to hold just the first ``n_tokens`` of a sequence:
        the page slots those tokens flush into plus the raw tail ring
        (always allocated — unflushed tokens live there).  This is the
        irreducible footprint the serving scheduler charges a prompt when
        deciding whether a request can *ever* fit its byte budget; the
        full static-slot cost is :meth:`compressed_bytes`."""
        pages = min(self.n_pages, max(0, n_tokens) // self.page_tokens)
        per_page = self.fr.compressed_bytes_per_page()
        b = 2 * batch * pages * per_page
        b += 2 * batch * self.page_tokens * self.row_words * self.word_bytes
        if self.resident_decode:
            b += 2 * batch * pages * self.page_tokens \
                * self.row_words * self.word_bytes
        return b

    def raw_bytes_upto(self, batch: int, n_tokens: int) -> int:
        """Raw-cache analogue of :meth:`compressed_bytes_upto`."""
        n = min(self.max_len, max(0, n_tokens))
        return 2 * batch * n * self.row_words * self.word_bytes


def init_compressed(spec: KVSpec, batch: int, table: BaseTable) -> Cache:
    fr = spec.fr
    pages_per_row = max(1, spec.row_words // fr.page_words)
    n_slots = spec.n_pages * pages_per_row

    def page_zeros() -> dict[str, jax.Array]:
        z = {
            "ptrs": jnp.zeros((batch, n_slots, fr.ptr_lanes), jnp.int32),
            "deltas": jnp.zeros((batch, n_slots, fr.delta_lanes), jnp.int32),
            "out_vals": jnp.zeros((batch, n_slots, fr.outlier_cap), jnp.int32),
            "out_idx": jnp.zeros((batch, n_slots, fr.outlier_cap), jnp.int32),
            "n_out": jnp.zeros((batch, n_slots), jnp.int32),
        }
        if fr.num_profiles > 1:   # adaptive cfg: per-page profile ids
            z["profile"] = jnp.zeros((batch, n_slots), jnp.int32)
        return z

    tail = jnp.zeros((batch, spec.page_tokens, spec.n_kv, spec.head_dim), jnp.bfloat16)
    cache: Cache = {"k_pages": page_zeros(), "v_pages": page_zeros(),
                    "k_tail": tail, "v_tail": tail, "table": table}
    if spec.resident_decode:
        # Seed the resident region by decoding the zero page tree, NOT with
        # plain zeros: a zero blob decodes to bases[0]-derived words, and the
        # invariant is bit-identity with a from-scratch ``_decompress_all``
        # for unflushed pages too.
        cache["k_dec"] = _decompress_all(spec, cache["k_pages"], table)
        cache["v_dec"] = _decompress_all(spec, cache["v_pages"], table)
    return cache


def _to_words(x16: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x16.astype(jnp.bfloat16), jnp.uint16).astype(jnp.int32)


def _from_words(w: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(w.astype(jnp.uint16), jnp.bfloat16)


def _compress_rows(spec: KVSpec, rows: jax.Array, table: BaseTable) -> dict[str, jax.Array]:
    """rows: (B, page_tokens, Kv, hd) -> per-batch page blobs (B, ppr, ...).

    All B * pages_per_row pages go through ONE batched compiled dispatch
    (:mod:`repro.kernels.xla`), not a vmap-of-vmap over single pages.
    """
    B = rows.shape[0]
    words = _to_words(rows).reshape(B, -1, spec.fr.page_words)
    # pipeline front-end: identical XLA chain under the flush trace, device
    # sharding for eager callers (e.g. offline cache warm-up)
    blob = dict(fr_pipeline.encode_pages(words, table, spec.fr))
    blob.pop("n_dropped", None)
    blob.pop("n_spilled", None)
    return blob


def _decompress_all(spec: KVSpec, pages: dict[str, jax.Array], table: BaseTable) -> jax.Array:
    """-> (B, n_pages*page_tokens, Kv, hd) bf16; one batched dispatch.

    Routed through the pipeline front-end: the fused XLA chain under a
    trace (the jitted serving step), the sharding-aware split for eager
    offline decompression of a big cache.
    """
    B = pages["ptrs"].shape[0]
    words = fr_pipeline.decode_pages(pages, table, spec.fr)
    return _from_words(words.reshape(B, -1, spec.n_kv, spec.head_dim))


def append(spec: KVSpec, cache: Cache, k: jax.Array, v: jax.Array, pos: jax.Array) -> Cache:
    """Append one token (B, 1, Kv, hd) at absolute position ``pos``."""
    pt = spec.page_tokens
    slot = pos % pt
    k_tail = jax.lax.dynamic_update_slice(cache["k_tail"], k.astype(jnp.bfloat16), (0, slot, 0, 0))
    v_tail = jax.lax.dynamic_update_slice(cache["v_tail"], v.astype(jnp.bfloat16), (0, slot, 0, 0))
    page_id = pos // pt
    pages_per_row = max(1, spec.row_words * pt // spec.fr.page_words)

    def flush(c: Cache) -> Cache:
        with jax.named_scope(obs.KV_FLUSH_ENCODE):
            kb = _compress_rows(spec, k_tail, cache["table"])
            vb = _compress_rows(spec, v_tail, cache["table"])
        def put(dst: dict[str, jax.Array], src: dict[str, jax.Array]) -> dict[str, jax.Array]:
            merged: dict[str, jax.Array] = jax.tree_util.tree_map(
                lambda d, s: jax.lax.dynamic_update_slice(
                    d, s.astype(d.dtype),
                    (0, page_id * pages_per_row) + (0,) * (d.ndim - 2),
                ),
                dst, src,
            )
            return merged
        out = {**c, "k_pages": put(c["k_pages"], kb), "v_pages": put(c["v_pages"], vb),
               "k_tail": k_tail, "v_tail": v_tail}
        if "k_dec" in c:
            # Incremental decode: decode the just-encoded blob (NOT the raw
            # tail — capacity-dropped outliers must round-trip identically to
            # a from-scratch decode of the page slots) and land it at this
            # page's token offset.  O(one page) per flush; reads reuse it.
            def dec(blob: dict[str, jax.Array]) -> jax.Array:
                w = fr_pipeline.decode_pages(blob, cache["table"], spec.fr)
                B = w.shape[0]
                return _from_words(w.reshape(B, pt, spec.n_kv, spec.head_dim))
            with jax.named_scope(obs.KV_FLUSH_DECODE):
                out["k_dec"] = jax.lax.dynamic_update_slice(
                    c["k_dec"], dec(kb), (0, page_id * pt, 0, 0))
                out["v_dec"] = jax.lax.dynamic_update_slice(
                    c["v_dec"], dec(vb), (0, page_id * pt, 0, 0))
        return out

    def nop(c: Cache) -> Cache:
        return {**c, "k_tail": k_tail, "v_tail": v_tail}

    out: Cache = jax.lax.cond(slot == pt - 1, flush, nop, cache)
    return out


def read_full(spec: KVSpec, cache: Cache, pos: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """-> (K, V, valid) covering [0, pos]: decompressed pages with the raw
    tail overlaid for the current (unflushed) page.

    With ``spec.resident_decode`` the pages were already decoded at flush
    time, so this is just the tail overlay — per-step cost stops scaling
    with context length (the decode work moved to one page per flush).
    """
    if "k_dec" in cache:
        K, V = cache["k_dec"], cache["v_dec"]
    else:
        K = _decompress_all(spec, cache["k_pages"], cache["table"])
        V = _decompress_all(spec, cache["v_pages"], cache["table"])
    pt = spec.page_tokens
    page_id = pos // pt
    K = jax.lax.dynamic_update_slice(
        K, cache["k_tail"], (0, page_id * pt, 0, 0))
    V = jax.lax.dynamic_update_slice(
        V, cache["v_tail"], (0, page_id * pt, 0, 0))
    S = K.shape[1]
    valid = jnp.arange(S) <= pos
    return K, V, valid


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
        pt = spec.page_tokens
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
