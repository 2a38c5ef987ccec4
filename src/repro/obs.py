"""The program's tracing: host spans on the profiler's clock, and the phase
names its codec and KV layers put into device traces and op metadata.

``span(name)`` writes a ``repro.<name>`` host event into whatever
``jax.profiler`` session is running (none: it costs a context manager).
Under a JAX trace it is a null context, so a jitted caller records no
span at trace time.

The phase names label ``jax.named_scope`` regions: inside the Pallas
kernels they lower to Mosaic trace regions; in the XLA chain, the KV
cache and the MLA layer they become HLO op metadata, which costs nothing
on the device.

The TPU compiler drops the kernels' regions unless libtpu runs with
``--xla_enable_custom_call_region_trace=true``; an operator appends it to
``LIBTPU_INIT_ARGS`` before the process starts.  Then a profile holds an
event per region and grid step, on the ``XLA TraceMe`` line of each TPU
plane, inside the kernel's own op.  Off by default, for what the regions
cost on a v5e: with the profiler off, 4.2% (encode) and 1.7% (decode) of
kernel time, 3.1% of the round trip's rate; under the profiler, 8% and 2%,
and a 10 s profile with them loses kernel events (it reads a third of the
window idle where the device ran throughout).
"""
from __future__ import annotations

import collections
import contextlib
from typing import Any, ContextManager

import jax

ENCODE_CLASSIFY = "encode.classify"   # base loop: best base and spill alternates
ENCODE_BUCKETS = "encode.buckets"     # per-class rank, compact, field pack, spill chain
ENCODE_OUTLIERS = "encode.outliers"   # outlier rank, compaction and counts
ENCODE_POINTERS = "encode.pointers"   # pointer codes, their packing, profile select
DECODE_WIDEN = "decode.widen"         # blob planes widened into the scratch tile
DECODE_POINTERS = "decode.pointers"   # pointer unpack and base select
DECODE_BUCKETS = "decode.buckets"     # delta sub-streams back to their words
DECODE_OUTLIERS = "decode.outliers"   # outlier slots back to their words, final select

ENCODE_PHASES = (ENCODE_CLASSIFY, ENCODE_BUCKETS, ENCODE_OUTLIERS, ENCODE_POINTERS)
DECODE_PHASES = (DECODE_WIDEN, DECODE_POINTERS, DECODE_BUCKETS, DECODE_OUTLIERS)

KV_FLUSH_ENCODE = "kv.flush_encode"   # the flush's encode of the full tail page
KV_FLUSH_DECODE = "kv.flush_decode"   # the flush's decode into the resident region
KV_ATTEND = "kv.attend"               # attention over the cache

MLA_Q_PROJ = "mla.q_proj"             # q_a, q_norm, q_b, rope on q_pe
MLA_KV_PROJ = "mla.kv_proj"           # kv_a, kv_norm, rope on k_pe: the latent row
MLA_ABSORB = "mla.absorb"             # q_nope through W_UK into the latent
MLA_OUT_PROJ = "mla.out_proj"         # W_UV, then o_proj


def under_trace(*leaves: Any) -> bool:
    """True inside any active trace (jit, vmap, shard_map, a ``lax.cond``
    branch) or when any of ``leaves`` is a tracer.  Under a trace even ops
    on concrete arrays yield trace-local tracers, so eager-only shortcuts
    (memoized device constants, host spans) must step aside."""
    return (not jax.core.trace_ctx.is_top_level()
            or any(isinstance(leaf, jax.core.Tracer) for leaf in leaves))


def span(name: str) -> ContextManager[object]:
    """A ``repro.<name>`` host span when called eagerly, else a null context."""
    if under_trace():
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(f"repro.{name}")


def kernel_primitive_counts(closed: Any) -> collections.Counter[str]:
    """How often each primitive occurs in the bodies of the ``pallas_call``s
    in a closed jaxpr, nested jaxprs included: a compile-time count, fixed
    per format (the codec kernels' lane moves are their ``roll``s)."""
    counts: collections.Counter[str] = collections.Counter()

    def tally(jaxpr: Any) -> None:
        for eqn in jaxpr.eqns:
            counts[eqn.primitive.name] += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                tally(sub)

    def find(jaxpr: Any) -> None:
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                tally(eqn.params["jaxpr"])
            else:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    find(sub)

    find(closed.jaxpr)
    return counts
