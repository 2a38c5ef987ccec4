"""The program's tracing (``repro.obs``): phase regions in the Pallas
codec kernels, the same phase names on the XLA chain's stages, the KV
cache's scopes, and host spans at the codec's layer boundary."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.format import BaseTable
from repro.core.gbdi_fr import FRConfig
from repro.kernels import ops
from repro.kernels.gbdi_decode import gbdi_decode_pallas
from repro.kernels.gbdi_encode import gbdi_encode_pallas
from repro.serving.kv_cache import KV_FR

CFGS = {
    "kv": KV_FR,
    "two_widths": FRConfig(word_bits=16, page_words=256, width_set=(4, 8),
                           bucket_caps=(64, 224), outlier_cap=16),
    "profiles": FRConfig(word_bits=16, page_words=256, width_set=(4, 8),
                         cap_profiles=((64, 192), (192, 64)), outlier_cap=16),
}
# equations a kernel body may keep outside every phase: its ref loads and
# stores (with the slices that fit a value to its output block), and the
# tile constants it builds once (the lane iota, zeros)
OUTSIDE = {"get", "swap", "slice", "iota", "broadcast_in_dim"}


def _table(cfg):
    k = cfg.num_bases
    return BaseTable(jnp.arange(k, dtype=jnp.int32) * 100, jnp.full((k,), 8, jnp.int32))


def _kernel_body(closed):
    """The inner jaxpr of the one ``pallas_call`` in ``closed``."""
    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn.params["jaxpr"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found = find(sub)
                if found is not None:
                    return found
        return None

    body = find(closed.jaxpr)
    assert body is not None, "no pallas_call in the kernel's jaxpr"
    return body


def _phases_of(body):
    """Top-level equation -> its name-stack scopes."""
    return [(eqn.primitive.name, [s.name for s in eqn.source_info.name_stack.stack])
            for eqn in body.eqns]


@pytest.mark.parametrize("name", sorted(CFGS))
@pytest.mark.parametrize("kernel", ["encode", "decode"])
def test_every_kernel_equation_lies_in_one_phase(kernel, name):
    cfg = CFGS[name]
    table = _table(cfg)
    x = jnp.zeros((16, cfg.page_words), jnp.int32)
    if kernel == "encode":
        closed = jax.make_jaxpr(
            lambda x: gbdi_encode_pallas(x, table, cfg, interpret=False))(x)
        phases = obs.ENCODE_PHASES
    else:
        blob = jax.eval_shape(lambda x: gbdi_encode_pallas(x, table, cfg, interpret=True), x)
        closed = jax.make_jaxpr(
            lambda b: gbdi_decode_pallas(b, table, cfg, interpret=False))(blob)
        phases = obs.DECODE_PHASES
    seen = set()
    for prim, scopes in _phases_of(_kernel_body(closed)):
        mine = [s for s in scopes if s in obs.ENCODE_PHASES + obs.DECODE_PHASES]
        if not mine:
            assert prim in OUTSIDE, f"{prim} lies outside every {kernel} phase"
            continue
        assert len(mine) == 1 and mine[0] in phases, f"{prim} lies in {mine}"
        seen.add(mine[0])
    assert seen == set(phases)


def _host_events(logdir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path)
    return [e.name for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events]


def test_span_is_a_host_event_eagerly_and_absent_under_jit(tmp_path):
    cfg = CFGS["two_widths"]
    table = _table(cfg)
    x = jnp.asarray(np.random.default_rng(0).integers(0, 1 << 16, (8, cfg.page_words)),
                    jnp.int32)
    jitted = jax.jit(lambda x: ops.encode_pages(x, table, cfg))
    jax.block_until_ready(ops.encode_pages(x, table, cfg))   # compile outside the traces
    jax.block_until_ready(jitted(x))

    with jax.profiler.trace(str(tmp_path / "eager")):
        jax.block_until_ready(ops.encode_pages(x, table, cfg))
    assert _host_events(str(tmp_path / "eager")).count("repro.codec.encode") == 1

    with jax.profiler.trace(str(tmp_path / "jit")):
        jax.block_until_ready(jitted(x))
    assert "repro.codec.encode" not in _host_events(str(tmp_path / "jit"))


def test_span_is_null_under_a_trace():
    seen = []

    def f(x):
        seen.append(type(obs.span("codec.encode")).__name__)
        return x + 1

    jax.jit(f)(jnp.int32(1))
    assert seen == ["nullcontext"]
    assert isinstance(obs.span("codec.encode"), jax.profiler.TraceAnnotation)


def _lowered_text(fn, *args):
    """The StableHLO of ``jit(fn)``, with the name-stack locations that
    become each op's metadata."""
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def test_xla_chain_stages_carry_the_phase_names():
    from repro.kernels import xla

    cfg = CFGS["two_widths"]
    table = _table(cfg)
    x = jnp.zeros((8, cfg.page_words), jnp.int32)
    text = _lowered_text(lambda x, t: xla.decode_pages(xla.encode_pages(x, t, cfg), t, cfg),
                         x, table)
    chain = set(obs.ENCODE_PHASES + obs.DECODE_PHASES) - {obs.DECODE_WIDEN}  # kernel-only
    assert {name for name in chain if f"{name}/" in text} == chain


def test_kv_step_carries_the_kv_scopes():
    from repro.serving import kv_cache as kvc

    spec = kvc.KVSpec(n_kv=4, head_dim=32, max_len=16, resident_decode=True,
                      fr=FRConfig(word_bits=16, page_words=128, width_set=(4, 8),
                                  bucket_caps=(32, 128), num_bases=14, outlier_cap=16))
    cache = kvc.init_compressed(spec, 1, _table(spec.fr))
    kv = jnp.zeros((1, 1, 4, 32), jnp.bfloat16)
    append = _lowered_text(lambda c, k, v, p: kvc.append(spec, c, k, v, p),
                           cache, kv, kv, jnp.int32(0))
    attend = _lowered_text(lambda q, c, p: kvc.attention_decode(spec, q, c, p),
                           jnp.zeros((1, 1, 4, 32), jnp.bfloat16), cache, jnp.int32(0))
    assert f"{obs.KV_FLUSH_ENCODE}/" in append and f"{obs.KV_FLUSH_DECODE}/" in append
    assert f"{obs.KV_ATTEND}/" in attend
