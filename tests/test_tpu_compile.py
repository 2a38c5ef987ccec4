"""Compile rehearsal for the TPU v5e: the codec kernels at real widths.

Interpret mode runs the Pallas kernels as plain jnp and lets through what
the chip's compiler (Mosaic) refuses — block shapes off the (8, 128) int32
tile, unaligned lane slices, more VMEM than a core has.  These tests compile
for a described, not attached, ``v5e:2x2`` topology, so they need only the
installed TPU compiler and run with ``JAX_PLATFORMS=cpu``.  Nothing runs:
a pass says the programs compile, not that they are right or fast.
"""
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
