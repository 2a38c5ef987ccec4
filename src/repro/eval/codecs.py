"""``fit/encode/decode/size_bits`` adapters over the repo's codec paths.

The concrete codecs are the paper's GBDI host codec
(:mod:`repro.core.gbdi`), the B∆I baseline (:mod:`repro.core.bdi`), and
the fixed-rate device format GBDI-FR through each backend of
:mod:`repro.kernels.ops` (the oracle, the XLA chain, the Pallas kernels).

The adapter contract (duck-typed, see :class:`repro.eval.registry.CodecRegistry`):

* ``fit(data) -> model`` — offline background analysis (may be ``None``);
* ``encode(data, model) -> blob``;
* ``decode(blob) -> np.ndarray`` of unsigned words (``word_bits`` wide);
* ``size_bits(blob) -> int`` — exact compressed size incl. global tables;
* ``lossless`` — whether bit-exact roundtrip is *guaranteed* (GBDI-FR is
  only capacity-bounded lossless: cells report ``dropped_words`` and the
  verifier checks mismatches are confined to dropped outliers).

This module also owns the dtype -> word-size framing rule
(:func:`word_bits_for_dtype`) shared by the ML families and the
real-dump ingestion path, so a bf16 checkpoint and a bf16 live capture
frame identically.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

from repro.core import bdi, gbdi
from repro.core.gbdi_fr import FRConfig, fit_fr_bases, fr_decode, fr_encode
from repro.eval.registry import CodecRegistry


def word_bits_for_dtype(dtype) -> int:
    """Natural codec word size for a tensor dtype, by bit pattern.

    2-byte dtypes (bf16/fp16/int16) frame as 16-bit words — the serving
    and gradient-transport distributions; everything else frames as the
    paper's 32-bit memory words (8-byte values split into word pairs, the
    same view :func:`repro.core.gbdi.to_words` takes of a raw dump).
    Accepts numpy dtypes, jax dtypes, and ml_dtypes names like
    ``'bfloat16'``.
    """
    return 16 if np.dtype(dtype).itemsize == 2 else 32


@dataclasses.dataclass
class GBDICodec:
    """Paper-faithful host codec: variable-length bit stream, lossless."""

    word_bits: int = 32
    name: str = "gbdi"
    lossless: bool = True

    def _config(self) -> gbdi.GBDIConfig:
        widths = (4, 8) if self.word_bits == 16 else (4, 8, 16, 24)
        return gbdi.GBDIConfig(word_bits=self.word_bits, width_set=widths)

    def fit(self, data: np.ndarray) -> gbdi.GBDIModel:
        return gbdi.fit(data, self._config())

    def encode(self, data: np.ndarray, model: gbdi.GBDIModel) -> dict[str, Any]:
        return gbdi.encode(data, model)

    def decode(self, blob: dict[str, Any]) -> np.ndarray:
        return gbdi.decode(blob)

    def size_bits(self, blob: dict[str, Any]) -> int:
        return gbdi.compressed_size_bits(blob)


@dataclasses.dataclass
class BDICodec:
    """Per-block B∆I baseline (byte blocks; word_bits only names the view)."""

    word_bits: int = 32
    name: str = "bdi"
    lossless: bool = True

    def fit(self, data: np.ndarray) -> None:
        return None  # no global state — that is the contrast with GBDI

    def encode(self, data: np.ndarray, model: None) -> dict[str, Any]:
        blob = bdi.compress(data)
        blob["_word_bits"] = self.word_bits
        return blob

    def decode(self, blob: dict[str, Any]) -> np.ndarray:
        wb = blob["_word_bits"]
        return bdi.decompress(blob).view(np.uint16 if wb == 16 else np.uint32)

    def size_bits(self, blob: dict[str, Any]) -> int:
        return bdi.compressed_size_bits(blob)


@dataclasses.dataclass
class FRCodec:
    """GBDI-FR v2 fixed-rate pages through :mod:`repro.kernels.ops`.

    v2: per-base width classes with bucketed delta sub-streams — zeros and
    outliers consume no payload, which puts the bf16 defaults strictly
    below the v1 single-width 13.02 bits/word.  Capacity-bounded lossless:
    bucket overflow spills to wider classes bit-exactly, outlier-table
    overflow drops words (decode to 0); ``blob['n_dropped']`` counts them
    and the eval verifier bounds mismatches by that count.

    ``cfg`` overrides the per-word-size default — the ``--sweep`` harness
    uses it to walk num_bases / width_set / bucket_caps grids.
    """

    word_bits: int = 16
    backend: str = "ref"          # "ref" | "kernel" | "xla" | "auto" (see kernels.ops)
    name: str = "fr"
    lossless: bool = False
    cfg: FRConfig | None = None

    def _config(self) -> FRConfig:
        if self.cfg is not None:
            return self.cfg
        if self.word_bits == 16:
            return FRConfig(word_bits=16, page_words=2048, num_bases=14,
                            width_set=(4, 8), bucket_caps=(192, 1856),
                            outlier_cap=64)
        return FRConfig(word_bits=32, page_words=2048, num_bases=14,
                        width_set=(8, 16), bucket_caps=(192, 1856),
                        outlier_cap=128)

    def fit(self, data: np.ndarray):
        import jax.numpy as jnp

        cfg = self._config()
        words = gbdi.to_words(data, cfg.word_bits)
        signed = gbdi.words_to_signed(words, cfg.word_bits)
        # fit_fr_bases pre-filters zeros and caps/buckets the sample
        return fit_fr_bases(jnp.asarray(signed, dtype=jnp.int32), cfg)

    def encode(self, data: np.ndarray, table) -> dict[str, Any]:
        import jax.numpy as jnp

        from repro.kernels import ops

        cfg = self._config()
        words = gbdi.to_words(data, cfg.word_bits)
        signed = gbdi.words_to_signed(words, cfg.word_bits)
        n = signed.size
        pad = (-n) % cfg.page_words
        pages = np.pad(signed, (0, pad)).reshape(-1, cfg.page_words)
        blob = dict(ops.encode_pages(jnp.asarray(pages), table, cfg,
                                     backend=self.backend))
        blob.update(_table=table, _cfg=cfg, _n_words=n)
        return blob

    def decode(self, blob: dict[str, Any]):
        from repro.kernels import ops

        cfg: FRConfig = blob["_cfg"]
        inner = {k: v for k, v in blob.items() if not k.startswith("_")}
        pages = ops.decode_pages(inner, blob["_table"], cfg, backend=self.backend)
        signed = np.asarray(pages).reshape(-1)[: blob["_n_words"]]
        return gbdi.signed_to_words(signed, cfg.word_bits)

    def size_bits(self, blob: dict[str, Any]) -> int:
        cfg: FRConfig = blob["_cfg"]
        n_pages = -(-blob["_n_words"] // cfg.page_words)
        # base values + width-class index per base (0 bits if single-class)
        idx_bits = (len(cfg.width_set) - 1).bit_length()
        table_bits = cfg.num_bases * (cfg.word_bits + idx_bits)
        if cfg.num_profiles == 1:
            return n_pages * cfg.compressed_bytes_per_page() * 8 + table_bits
        # adaptive profiles serialize at their own per-page size
        # (profile byte + only the selected profile's delta lanes)
        prof = np.asarray(blob["profile"]).reshape(-1)[:n_pages]
        bytes_per = np.array([cfg.compressed_bytes_for_profile(p)
                              for p in range(cfg.num_profiles)], np.int64)
        return int(bytes_per[prof].sum()) * 8 + table_bits

    def dropped_words(self, blob: dict[str, Any]) -> int:
        return int(np.asarray(blob["n_dropped"]).sum())

    def spilled_words(self, blob: dict[str, Any]) -> int:
        return int(np.asarray(blob["n_spilled"]).sum())

    def profile_histogram(self, blob: dict[str, Any]) -> list[int]:
        """Per-profile page counts of the data pages (``[n_pages]`` for
        single-profile configs) — the per-page selection behind
        :meth:`size_bits`'s adaptive accounting, exposed for analyzing
        which profiles a workload actually exercises."""
        cfg: FRConfig = blob["_cfg"]
        n_pages = -(-blob["_n_words"] // cfg.page_words)
        if cfg.num_profiles == 1:
            return [n_pages]
        prof = np.asarray(blob["profile"]).reshape(-1)[:n_pages]
        return np.bincount(prof, minlength=cfg.num_profiles).tolist()


def default_codecs() -> CodecRegistry:
    reg = CodecRegistry()
    reg.register("gbdi", lambda wb: GBDICodec(word_bits=wb))
    reg.register("bdi", lambda wb: BDICodec(word_bits=wb))
    reg.register("fr", lambda wb: FRCodec(word_bits=wb, backend="ref"))
    reg.register("fr_xla", lambda wb: FRCodec(word_bits=wb, backend="xla",
                                              name="fr_xla"))
    reg.register("fr_kernel", lambda wb: FRCodec(word_bits=wb, backend="kernel",
                                                 name="fr_kernel"))
    return reg
