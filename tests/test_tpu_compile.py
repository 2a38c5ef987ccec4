"""Compile rehearsal for the TPU v5e: the codec kernels at real widths,
and the decode attention of the compressed caches at the benchmark cells'
shapes.

Interpret mode runs the Pallas kernels as plain jnp and lets through what
the chip's compiler (Mosaic) refuses — block shapes off the (8, 128) int32
tile, unaligned lane slices, more VMEM than a core has.  These tests compile
for a described, not attached, ``v5e:2x2`` topology, so they need only the
installed TPU compiler and run with ``JAX_PLATFORMS=cpu``.  Nothing runs:
a pass says the programs compile, not that they are right or fast.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.format import BaseTable
from repro.core.gbdi_fr import FRConfig
from repro.distributed.collectives import GRAD_FR
from repro.kernels import xla
from repro.kernels.gbdi_decode import gbdi_decode_pallas
from repro.kernels.gbdi_encode import gbdi_encode_pallas
from repro.launch.mesh import CHIP_PEAKS
from repro.models import mla
from repro.serving import kv_cache as kvc
from repro.serving.engine import _mla_step
from repro.serving.kv_cache import KV_FR

N_PAGES = 1024
CONFIGS = {
    "KV_FR": KV_FR,
    "GRAD_FR": GRAD_FR,
    # the eval codec's bf16 default
    "bf16_eval": FRConfig(word_bits=16, page_words=2048, num_bases=14,
                          width_set=(4, 8), bucket_caps=(192, 1856), outlier_cap=64),
    # 32-bit words: the outlier moves carry payload and distance in two planes
    "w32": FRConfig(word_bits=32, page_words=2048, num_bases=14,
                    width_set=(8, 16), bucket_caps=(512, 1536), outlier_cap=64),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed, or it cannot describe v5e
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(one_chip, cfg):
    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    table = BaseTable(s((cfg.num_bases,)), s((cfg.num_bases,)))
    blob = {k: s(v.shape) for k, v in jax.eval_shape(
        lambda x, t: xla.encode_pages(x, t, cfg),
        s((N_PAGES, cfg.page_words)), table).items()}
    return s((N_PAGES, cfg.page_words)), table, blob


def _report(what, compiled):
    mem = compiled.memory_analysis()
    print(f"{what}: {mem}")
    return mem


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_encode_kernel_compiles(one_chip, name):
    cfg = CONFIGS[name]
    x, table, _ = _shapes(one_chip, cfg)
    compiled = jax.jit(lambda x, t: gbdi_encode_pallas(x, t, cfg, interpret=False)) \
        .lower(x, table).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _report(f"encode kernel {name}", compiled)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_decode_kernel_compiles(one_chip, name):
    cfg = CONFIGS[name]
    _, table, blob = _shapes(one_chip, cfg)
    compiled = jax.jit(lambda b, t: gbdi_decode_pallas(b, t, cfg, interpret=False)) \
        .lower(blob, table).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _report(f"decode kernel {name}", compiled)


def test_xla_chain_compiles_under_jit(topo, one_chip):
    """The chain the serving flush and the gradient exchange trace into
    their programs (``KV_FR`` == ``GRAD_FR``), at a 1024-page batch."""
    cfg = KV_FR
    x, table, blob = _shapes(one_chip, cfg)
    enc = jax.jit(lambda x, t: xla.encode_pages(x, t, cfg)).lower(x, table).compile()
    dec = jax.jit(lambda b, t: xla.decode_pages(b, t, cfg)).lower(blob, table).compile()
    for what, compiled in (("xla encode", enc), ("xla decode", dec)):
        mem = _report(f"{what} KV_FR", compiled)
        assert mem.temp_size_in_bytes < CHIP_PEAKS[topo.devices[0].device_kind].hbm_bytes


def test_described_chip_has_published_peaks(topo):
    assert topo.devices[0].device_kind in CHIP_PEAKS


@pytest.mark.parametrize("kernel", ["encode", "decode"])
def test_kernels_compile_keeping_their_regions(one_chip, kernel):
    """The kernels under the flag that keeps their phase regions for a
    profile (``LIBTPU_INIT_ARGS=--xla_enable_custom_call_region_trace=true``
    on the chip), given here per compile: the TPU compiler knows the flag
    and compiles both kernels with it."""
    cfg = KV_FR
    x, table, blob = _shapes(one_chip, cfg)
    fn, arg = (gbdi_encode_pallas, x) if kernel == "encode" else (gbdi_decode_pallas, blob)
    compiled = jax.jit(lambda a, t: fn(a, t, cfg, interpret=False)).lower(arg, table).compile(
        compiler_options={"xla_enable_custom_call_region_trace": True})
    assert "tpu_custom_call" in compiled.as_text()


# the decode cells' caches: the 7B's 32x128 K/V heads over 4096 positions,
# one DeepSeek-V3 MLA layer's 576-word latent rows over 33024, batch 8
ATTENTION = {
    "kv-7b": (kvc.KVSpec(n_kv=32, head_dim=128, max_len=4096, resident_decode=True),
              (8, 1, 32, 128)),
    "mla-v3": (kvc.LatentSpec(latent_dim=512, rope_dim=64, max_len=33024,
                              resident_decode=True), (8, 1, 128, 576)),
}


@pytest.mark.parametrize("name", sorted(ATTENTION))
def test_resident_attention_copies_no_region(one_chip, name):
    """Decode attention over a resident cache reads the region and the raw
    tail as two softmax segments: its temporaries stay below one decoded
    region's bytes, so the region is never copied to write the tail over
    it (a tail overlay compiles to a copy of each region: 536,967,680
    temporary bytes for the K/V pair, 304,445,952 for the latent rows)."""
    spec, q_shape = ATTENTION[name]
    B = q_shape[0]

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    table = BaseTable(s((KV_FR.num_bases,), jnp.int32), s((KV_FR.num_bases,), jnp.int32))
    cache = jax.tree.map(lambda a: s(a.shape, a.dtype),
                         jax.eval_shape(lambda t: kvc.init_compressed(spec, B, t), table))
    q, pos = s(q_shape, jnp.bfloat16), s((), jnp.int32)
    if isinstance(spec, kvc.KVSpec):
        def attend(q, c, p):
            return kvc.attention_decode(spec, q, c, p)
    else:
        scale = mla.softmax_scale(mla.MLAConfig())

        def attend(q, c, p):
            return kvc.attention_decode_latent(spec, q, c, p, scale)
    mem = _report(f"resident attention {name}",
                  jax.jit(attend).lower(q, cache, pos).compile())
    region = B * spec.n_groups * spec.group_tokens * spec.row_words * spec.word_bytes
    assert mem.temp_size_in_bytes < region, (mem.temp_size_in_bytes, region)


def test_mla_step_copies_no_region(one_chip):
    """One MLA layer's whole decode step as the session compiles it (the
    cache donated; append, whose flush may land a group in the region,
    then attention) at the cell's shapes: no copy of the region, and
    temporaries below its bytes.  A region threaded through the flush's
    ``lax.cond``, or a slice of its rows for the values, compiles to
    region-sized copies (372830720 temporary bytes with the tail overlay)."""
    spec, _ = ATTENTION["mla-v3"]
    cfg = mla.MLAConfig()

    def s(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    base = jax.ShapeDtypeStruct((KV_FR.num_bases,), jnp.int32, sharding=one_chip)
    caches = [jax.tree.map(s, jax.eval_shape(
        lambda t: kvc.init_compressed(spec, 8, t), BaseTable(base, base)))]
    params = [jax.tree.map(s, jax.eval_shape(lambda: mla.init(jax.random.PRNGKey(0), cfg)))]
    xs = jax.ShapeDtypeStruct((1, 8, 1, cfg.hidden_size), jnp.bfloat16, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(functools.partial(_mla_step, spec, cfg), donate_argnums=(1,)) \
        .lower(params, caches, xs, pos).compile()
    mem = _report("MLA step, one layer", compiled)
    region = 8 * spec.n_groups * spec.group_tokens * spec.row_words * spec.word_bytes
    assert mem.temp_size_in_bytes < region, (mem.temp_size_in_bytes, region)
    assert not re.search(r"bf16\[8,33024,576\]\{[^}]*\} copy\(", compiled.as_text())
