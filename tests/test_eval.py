"""Eval subsystem: registries, per-cell roundtrip verification, CLI plumbing,
the cross-process workload-determinism regression (the old generator
seeded with salted ``hash(name)``, so every process saw different data),
and the throughput harness's loud-failure and truncation-marking contract."""
import json
import os
import subprocess
import sys
import zlib

import jax
import numpy as np
import pytest

from repro.core.gbdi_fr import FRConfig
from repro.data import workloads
from repro.eval.codecs import FRCodec, default_codecs
from repro.eval.registry import Workload, WorkloadRegistry
from repro.eval.run import csv_lines, evaluate, evaluate_cell, format_table, to_artifact
from repro.eval.workloads import default_workloads

SMALL = 1 << 16
CFG = FRConfig(word_bits=16, page_words=256, num_bases=6, width_set=(4, 8),
               cap_profiles=((64, 192), (192, 64)), outlier_cap=16)


@pytest.fixture(scope="session")
def registry():
    return default_workloads()


@pytest.fixture(scope="session")
def codecs():
    return default_codecs()


# ---------------------------------------------------------------------------
# workload registry
# ---------------------------------------------------------------------------

def test_registry_has_required_breadth(registry):
    names = registry.names()
    assert len(names) >= 12
    kinds = registry.kinds()
    for kind in ("C", "Java", "Column", "ML"):
        assert kind in kinds, kinds
    # all dump families are wrapped
    for name in workloads.WORKLOADS:
        assert name in names


def test_registry_select_suites(registry):
    assert len(registry.select("all")) == len(registry)
    ml = registry.select("ml")
    assert ml and all(w.kind == "ML" for w in ml)
    mixed = registry.select("column,605.mcf_s")
    assert {w.name for w in mixed} >= {"col_int_keys", "605.mcf_s"}
    with pytest.raises(KeyError):
        registry.select("no_such_suite")


def test_registry_rejects_duplicates():
    reg = WorkloadRegistry()
    w = Workload("x", "C", lambda n, s: np.zeros(n // 4, np.uint32))
    reg.register(w)
    with pytest.raises(ValueError):
        reg.register(w)


def test_column_and_ml_generators_deterministic(registry):
    for name in ("col_int_keys", "col_dict_codes", "col_decimal_prices",
                 "ml_kvcache_bf16"):
        wl = registry.get(name)
        a = wl.generate(SMALL, 3)
        b = wl.generate(SMALL, 3)
        np.testing.assert_array_equal(a, b)
        # dump-style generators are size-approximate (block interleave)
        assert SMALL // 2 <= a.view(np.uint8).size <= 2 * SMALL


# ---------------------------------------------------------------------------
# cross-process determinism regression (the hash(name) seed bug)
# ---------------------------------------------------------------------------

def _subprocess_digests(names):
    script = (
        "import sys, zlib; sys.path.insert(0, 'src')\n"
        "from repro.data import workloads\n"
        "for n in %r:\n"
        "    d = workloads.generate(n, n_bytes=1 << 14, seed=0)\n"
        "    print(n, zlib.crc32(d.tobytes()))\n" % (list(names),)
    )
    r = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=300,
    )
    assert r.returncode == 0, r.stderr[-1500:]
    return dict(line.split() for line in r.stdout.strip().splitlines())


def test_generate_identical_across_processes():
    names = ["605.mcf_s", "java_svm", "col_int_keys"]
    a = _subprocess_digests(names)
    b = _subprocess_digests(names)
    assert a == b and set(a) == set(names)
    # and the parent process agrees (would fail under salted hash())
    for n in names:
        d = workloads.generate(n, n_bytes=1 << 14, seed=0)
        assert int(a[n]) == zlib.crc32(d.tobytes())


def test_generate_seed_and_name_vary_stream():
    a = workloads.generate("605.mcf_s", n_bytes=SMALL, seed=0)
    assert not np.array_equal(a, workloads.generate("605.mcf_s", n_bytes=SMALL, seed=1))
    assert not np.array_equal(a, workloads.generate("620.omnetpp_s", n_bytes=SMALL, seed=0))


# ---------------------------------------------------------------------------
# codec adapters + per-cell verification
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("codec_name", ["gbdi", "bdi", "fr", "fr_xla"])
def test_cell_roundtrip_verifies(registry, codecs, codec_name):
    wl = registry.get("605.mcf_s")
    data = wl.generate(SMALL, 0)
    cell = evaluate_cell(wl, codecs.make(codec_name, wl.word_bits), data)
    assert cell.verified, cell.error
    assert cell.compression_ratio > 0.5
    assert cell.bits_per_word > 0
    if codec_name in ("gbdi", "bdi"):
        assert cell.lossless and cell.exact_frac == 1.0


def test_cell_bf16_workload_uses_16bit_words(registry, codecs):
    wl = registry.get("ml_kvcache_bf16")
    assert wl.word_bits == 16
    data = wl.generate(SMALL, 0)
    cell = evaluate_cell(wl, codecs.make("gbdi", wl.word_bits), data)
    assert cell.verified and cell.lossless
    assert cell.word_bits == 16


def test_fr_verifier_bounds_mismatches_by_dropped(registry, codecs):
    """FR is capacity-bounded: mismatches must be exactly the dropped words."""
    wl = registry.get("631.deepsjeng_s")
    data = wl.generate(SMALL, 0)
    codec = codecs.make("fr", wl.word_bits)
    cell = evaluate_cell(wl, codec, data)
    assert cell.verified, cell.error
    blob = codec.encode(data, codec.fit(data))
    assert isinstance(codec.dropped_words(blob), int)


def test_fr_codec_size_model_is_fixed_rate():
    codec = FRCodec(word_bits=32)
    cfg = codec._config()
    rng = np.random.default_rng(0)
    data = rng.integers(0, 2**32, cfg.page_words * 3, dtype=np.uint32)
    blob = codec.encode(data, codec.fit(data))
    # v2 global table: base value + width-class index per base
    idx_bits = (len(cfg.width_set) - 1).bit_length()
    expect = 3 * cfg.compressed_bytes_per_page() * 8 + cfg.num_bases * (cfg.word_bits + idx_bits)
    assert codec.size_bits(blob) == expect


def test_evaluate_sweep_and_artifacts(registry, codecs, tmp_path):
    cells = evaluate(registry, codecs, suite="column", codecs="gbdi,bdi",
                     n_bytes=SMALL, seed=0)
    assert len(cells) == 3 * 2
    assert all(c.verified for c in cells), [c.error for c in cells]
    table = format_table(cells)
    assert "geomean CR" in table and "col_int_keys" in table
    lines = csv_lines(cells)
    assert len(lines) == len(cells) and all(l.startswith("eval/") for l in lines)
    art = to_artifact(cells, suite="column", codecs="gbdi,bdi",
                      n_bytes=SMALL, seed=0)
    out = tmp_path / "BENCH_eval.json"
    out.write_text(json.dumps(art))
    back = json.loads(out.read_text())
    assert back["bench"] == "eval" and len(back["rows"]) == len(cells)
    assert {"workload", "codec", "compression_ratio", "verified"} <= set(back["rows"][0])


def test_unknown_codec_raises(codecs):
    with pytest.raises(KeyError):
        codecs.make("zstd", 32)


@pytest.mark.slow
def test_ml_model_families_roundtrip(registry, codecs):
    """Model-derived tensors (weights/moments/grads) through the host codec."""
    for name in ("ml_weights_fp32", "ml_weights_bf16", "ml_adamw_moments",
                 "ml_grads_bf16"):
        wl = registry.get(name)
        data = wl.generate(SMALL, 0)
        cell = evaluate_cell(wl, codecs.make("gbdi", wl.word_bits), data)
        assert cell.verified and cell.lossless, (name, cell.error)


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
