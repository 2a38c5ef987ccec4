"""Sharded encode/decode pipeline (repro.kernels.pipeline): blob and word
parity of the auto / explicit-shard / stream / traced paths against the
plain XLA chain in both directions, the multi-device byte-identity
subprocess test (forced host devices), the FRCodec stream/shard knobs,
and the throughput harness's loud-failure + truncation-marking
contract."""
import json
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.gbdi_fr import FRConfig, fit_fr_bases
from repro.kernels import pipeline, xla

CFG = FRConfig(word_bits=16, page_words=256, num_bases=6, width_set=(4, 8),
               cap_profiles=((64, 192), (192, 64)), outlier_cap=16)


def _pages(n_pages: int, seed: int = 0) -> jax.Array:
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(-2000, 2000,
                                    (n_pages, CFG.page_words)).astype(np.int32))


def _assert_blob_equal(got, want, label):
    assert set(got) == set(want), label
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]),
                                      err_msg=f"{label}:{k}")


@pytest.fixture(scope="module")
def fitted():
    x = _pages(37)
    table = fit_fr_bases(x, CFG)
    return x, table, xla.encode_pages(x, table, CFG)


def test_auto_path_matches_xla(fitted):
    x, table, ref = fitted
    _assert_blob_equal(pipeline.encode_pages(x, table, CFG), ref, "auto")


def test_explicit_shards_match_xla(fitted):
    # 37 rows across 4 shards: exercises padding + reassembly + strip
    x, table, ref = fitted
    _assert_blob_equal(pipeline.encode_pages(x, table, CFG, devices=4),
                       ref, "devices=4")
    _assert_blob_equal(
        pipeline.encode_pages_sharded(x, table, CFG, devices=3),
        ref, "sharded3")


def test_encode_stream_double_buffered(fitted):
    x, table, ref = fitted
    parts = np.array_split(np.asarray(x), 5)
    blobs = list(pipeline.encode_stream(parts, table, CFG))
    assert len(blobs) == 5
    cat = {k: jnp.concatenate([b[k] for b in blobs]) for k in blobs[0]}
    _assert_blob_equal(cat, ref, "stream")
    assert list(pipeline.encode_stream([], table, CFG)) == []


def test_traced_caller_falls_through(fitted):
    # under jit the pipeline must be exactly the XLA chain (kv_cache and
    # the gradient ring-exchange both encode inside traced code)
    x, table, ref = fitted

    @jax.jit
    def enc(xs):
        return pipeline.encode_pages(xs, table, CFG)

    _assert_blob_equal(enc(x), ref, "traced")


def test_leading_axes_roundtrip(fitted):
    x, table, ref = fitted
    x3 = x[:36].reshape(4, 9, CFG.page_words)
    blob = pipeline.encode_pages(x3, table, CFG, devices=2)
    assert blob["n_out"].shape == (4, 9)
    flat = {k: v.reshape((-1,) + v.shape[2:]) for k, v in blob.items()}
    _assert_blob_equal(flat, {k: v[:36] for k, v in ref.items()}, "lead")


def test_auto_shards_core_capped():
    assert 1 <= pipeline.auto_shards() <= max(1, os.cpu_count() or 1)
    with pytest.raises(ValueError):
        pipeline.encode_pages(_pages(4), fit_fr_bases(_pages(4), CFG), CFG,
                              devices=0)


def test_frcodec_stream_and_shard_knobs(fitted):
    from repro.eval.codecs import FRCodec

    data = np.asarray(_pages(32)).astype(np.uint16).view(np.uint8).tobytes()
    data = np.frombuffer(data, np.uint8)
    base = FRCodec(word_bits=16, backend="xla", cfg=CFG)
    model = base.fit(data)
    want = base.encode(data, model)
    for codec in (FRCodec(word_bits=16, backend="xla", cfg=CFG, devices=3),
                  FRCodec(word_bits=16, backend="xla", cfg=CFG,
                          stream_batches=4)):
        got = codec.encode(data, model)
        for k in want:
            if k.startswith("_"):
                continue
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(want[k]), err_msg=k)


# ---------------------------------------------------------------------------
# decode front-end: same sharding policy, blobs in -> word pages out
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def decode_ref(fitted):
    x, table, blob = fitted
    return np.asarray(xla.decode_pages(blob, table, CFG))


def test_decode_auto_and_explicit_match_xla(fitted, decode_ref):
    x, table, blob = fitted
    np.testing.assert_array_equal(
        np.asarray(pipeline.decode_pages(blob, table, CFG)), decode_ref)
    # 37 rows across 4 shards: padding rows decode as zero-blob pages and
    # are stripped on reassembly
    np.testing.assert_array_equal(
        np.asarray(pipeline.decode_pages(blob, table, CFG, devices=4)),
        decode_ref)
    np.testing.assert_array_equal(
        np.asarray(pipeline.decode_pages_sharded(blob, table, CFG, devices=3)),
        decode_ref)
    # unsigned output: the fused in-chain cast must equal casting the
    # signed words mod 2**word_bits, on both the plain and split paths
    udt = np.uint16 if CFG.word_bits == 16 else np.uint32
    for kw in ({}, {"devices": 4}):
        uw = np.asarray(pipeline.decode_pages(
            blob, table, CFG, unsigned=True, **kw))
        assert uw.dtype == udt
        np.testing.assert_array_equal(uw, decode_ref.astype(udt))


def test_decode_stream_double_buffered(fitted, decode_ref):
    x, table, blob = fitted
    bounds = np.array_split(np.arange(37), 5)
    parts = [{k: v[idx[0]:idx[-1] + 1] for k, v in blob.items()}
             for idx in bounds]
    words = list(pipeline.decode_stream(parts, table, CFG))
    assert len(words) == 5
    np.testing.assert_array_equal(np.asarray(jnp.concatenate(words)),
                                  decode_ref)
    assert list(pipeline.decode_stream([], table, CFG)) == []


def test_decode_traced_falls_through(fitted, decode_ref):
    # the serving KV cache decompresses inside jit — the front-end must be
    # exactly the XLA chain there
    x, table, blob = fitted

    @jax.jit
    def dec(b):
        return pipeline.decode_pages(b, table, CFG)

    np.testing.assert_array_equal(np.asarray(dec(blob)), decode_ref)


def test_decode_leading_axes(fitted, decode_ref):
    x, table, blob = fitted
    blob36 = {k: v[:36] for k, v in blob.items()}
    blob3 = {k: v.reshape((4, 9) + v.shape[1:]) for k, v in blob36.items()}
    words = pipeline.decode_pages(blob3, table, CFG, devices=2)
    assert words.shape == (4, 9, CFG.page_words)
    np.testing.assert_array_equal(
        np.asarray(words).reshape(36, CFG.page_words), decode_ref[:36])


def test_frcodec_decode_stream_and_shard_knobs(fitted):
    from repro.eval.codecs import FRCodec

    data = np.asarray(_pages(32)).astype(np.uint16).view(np.uint8).tobytes()
    data = np.frombuffer(data, np.uint8)
    base = FRCodec(word_bits=16, backend="xla", cfg=CFG)
    model = base.fit(data)
    blob = base.encode(data, model)
    want = base.decode(blob)
    for codec in (FRCodec(word_bits=16, backend="xla", cfg=CFG, devices=3),
                  FRCodec(word_bits=16, backend="xla", cfg=CFG,
                          stream_batches=4)):
        np.testing.assert_array_equal(codec.decode(blob), want)
    # and the xla path matches the reference backend bit-for-bit
    np.testing.assert_array_equal(
        FRCodec(word_bits=16, backend="ref", cfg=CFG).decode(blob), want)


_SUBPROC = r"""
import hashlib, json, sys
import numpy as np, jax, jax.numpy as jnp
from repro.core import gbdi
from repro.core.gbdi_fr import FRConfig, fit_fr_bases
from repro.eval.workloads import default_workloads
from repro.kernels import pipeline, xla

cfg = FRConfig(word_bits=16, page_words=256, num_bases=6, width_set=(4, 8),
               cap_profiles=((64, 192), (192, 64)), outlier_cap=16)
data = default_workloads().get("ml_grads_bf16").generate(64 << 10, 0)
signed = gbdi.words_to_signed(gbdi.to_words(data, 16), 16)
pages = jnp.asarray(np.pad(signed, (0, (-signed.size) % cfg.page_words))
                    .reshape(-1, cfg.page_words))
table = fit_fr_bases(pages, cfg)

def digest(blob):
    h = hashlib.sha256()
    for k in sorted(blob):
        h.update(k.encode())
        h.update(np.ascontiguousarray(np.asarray(blob[k])).tobytes())
    return h.hexdigest()

single = xla.encode_pages(jax.device_put(pages, jax.devices()[0]), table, cfg)
sharded = pipeline.encode_pages_sharded(pages, table, cfg)

def wdigest(words):
    return hashlib.sha256(
        np.ascontiguousarray(np.asarray(words)).tobytes()).hexdigest()

print(json.dumps({
    "devices": pipeline.device_count(),
    "single": digest(single),
    "sharded": digest(sharded),
    "dec_single": wdigest(xla.decode_pages(single, table, cfg)),
    "dec_sharded": wdigest(pipeline.decode_pages_sharded(sharded, table, cfg)),
    "dec_spmd": wdigest(pipeline.decode_pages_sharded(
        sharded, table, cfg, mode="spmd")),
}))
"""


def test_forced_multi_device_byte_identity():
    """Under XLA_FLAGS=--xla_force_host_platform_device_count=4 the
    sharded pipeline's blobs are byte-identical to the single-device path
    on a bf16 ML stream (sha256 over every blob field), and the sharded
    decode (split AND spmd) of those blobs is byte-identical to the
    single-device decode."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["devices"] == 4
    assert got["single"] == got["sharded"]
    assert got["dec_single"] == got["dec_sharded"]
    assert got["dec_single"] == got["dec_spmd"]


# ---------------------------------------------------------------------------
# throughput harness contract (roofline columns, truncation, loud failure)
# ---------------------------------------------------------------------------

class _BoomCodec:
    name = "boom"
    word_bits = 16
    lossless = True

    def fit(self, data):
        return None

    def encode(self, data, model):
        raise ValueError("kaboom")

    def decode(self, blob):
        return blob

    def size_bits(self, blob):
        return 0


class _BoomRegistry:
    def make(self, name, word_bits):
        return _BoomCodec()


def test_throughput_fails_loudly_and_marks_cell():
    from repro.eval.run import throughput
    from repro.eval.workloads import default_workloads

    rows, seen = [], []
    with pytest.raises(RuntimeError, match="boom.*ml_grads_bf16"):
        throughput(default_workloads(), _BoomRegistry(),
                   suite="ml_grads_bf16", codecs="boom", n_bytes=4096,
                   kernel_n_bytes=4096, repeats=1, rows=rows,
                   on_row=lambda r: seen.append(dict(r)))
    assert rows and rows[-1]["failed"] and "kaboom" in rows[-1]["error"]
    assert len(seen) == len(rows)  # incremental writer saw the failed cell


def test_throughput_row_marks_truncation_and_roofline():
    from repro.eval.run import measure_throughput, roofline_peak_bytes_s
    from repro.eval.codecs import FRCodec
    from repro.eval.workloads import default_workloads

    wl = default_workloads().get("ml_grads_bf16")
    data = wl.generate(16 << 10, 0)
    codec = FRCodec(word_bits=16, backend="xla", cfg=CFG, name="fr_xla")
    row = measure_throughput(wl, codec, data, repeats=1,
                             n_bytes_requested=2 << 20)
    assert row["truncated"] and row["n_bytes_requested"] == 2 << 20
    assert row["devices"] == jax.local_device_count()
    assert row["bytes_moved"] > row["n_bytes"]
    # a host rate never goes under a chip's roofline: no published peak
    # for the CPU, so the roofline columns are null
    assert row["device_kind"] == jax.devices()[0].device_kind
    assert row["peak_bytes_s"] is roofline_peak_bytes_s(row["device_kind"]) is None
    assert row["enc_roofline_frac"] is None and row["dec_roofline_frac"] is None
