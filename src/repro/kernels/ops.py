"""The codec's one entry point: every encode and decode outside
``repro.kernels`` goes through :func:`encode_pages`/:func:`decode_pages`
(or their tensor-level wrappers).

Backends, all bit-identical on every blob and word:

* ``'kernel'`` — the Pallas kernels: compiled on TPU, interpret mode
  elsewhere (a correctness check, far slower than compiled code; it runs
  only when a caller asks for ``'kernel'`` off TPU).
* ``'xla'``    — the XLA chain (:mod:`repro.kernels.xla`), compiled on any
  platform, inlined into the caller's program under a trace.
* ``'ref'``    — the pure-jnp oracle (:mod:`repro.kernels.ref`), the
  semantic reference.
* ``'auto'``   — the default: ``'kernel'`` on TPU, ``'xla'`` elsewhere;
  never interpret mode.

The tensor-level helpers bitcast and pad plain fp32/bf16/int32 tensors
into pages.  Tables are a fitted :class:`repro.core.format.BaseTable` (a
bare bases array is taken as all-widest-class).
"""
from __future__ import annotations

from typing import Any

import jax

from repro import obs
from repro.core.format import TableLike
from repro.core.gbdi_fr import (
    FRConfig,
    pages_to_tensor,
    tensor_to_pages,
)
from repro.kernels.gbdi_decode import gbdi_decode_pallas
from repro.kernels.gbdi_encode import gbdi_encode_pallas
from repro.kernels import ref as _ref
from repro.kernels import xla as _xla

BACKENDS = ("ref", "kernel", "xla", "auto")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(backend: str | None = "auto") -> str:
    """Resolve ``'auto'``/``None`` to the compiled backend for this device."""
    if backend in (None, "auto"):
        return "kernel" if _on_tpu() else "xla"
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; choose from {BACKENDS}")
    return backend


def encode_pages(
    x_pages: jax.Array, table: TableLike, cfg: FRConfig, backend: str = "auto"
) -> dict[str, jax.Array]:
    backend = resolve_backend(backend)
    with obs.span("codec.encode"):
        if backend == "kernel":
            return gbdi_encode_pallas(x_pages, table, cfg, interpret=not _on_tpu())
        if backend == "xla":
            return _xla.encode_pages(x_pages, table, cfg)
        return _ref.encode_ref(x_pages, table, cfg)


def decode_pages(
    blob: dict[str, jax.Array], table: TableLike, cfg: FRConfig, backend: str = "auto"
) -> jax.Array:
    backend = resolve_backend(backend)
    with obs.span("codec.decode"):
        if backend == "kernel":
            return gbdi_decode_pallas(blob, table, cfg, interpret=not _on_tpu())
        if backend == "xla":
            return _xla.decode_pages(blob, table, cfg)
        return _ref.decode_ref(blob, table, cfg)


def encode_tensor(
    x: jax.Array, table: TableLike, cfg: FRConfig, backend: str = "auto"
) -> tuple[dict[str, jax.Array], dict[str, Any]]:
    pages, meta = tensor_to_pages(x, cfg)
    meta["n_pages"] = pages.shape[0]
    return encode_pages(pages, table, cfg, backend), meta


def decode_tensor(
    blob: dict[str, jax.Array], meta: dict[str, Any], table: TableLike, cfg: FRConfig,
    backend: str = "auto",
) -> jax.Array:
    pages = decode_pages(blob, table, cfg, backend)
    return pages_to_tensor(pages, meta, cfg)
