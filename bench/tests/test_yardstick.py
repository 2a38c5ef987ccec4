"""The yardstick's arithmetic, reference, table fit, generator and trace
reduction, checked on the CPU."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import gen
import refcodec
import table
import traces
from conftest import BENCH, SEED, cpu_ops
from fmt import Format


def config(name: str) -> dict:
    with open(BENCH / "configs" / f"{name}.json") as fh:
        return json.load(fh)


#: a 32-bit format with two width classes and the spill chain (the
#: repo's eval 32-bit default), for the reference's 32-bit path
WIDE = {"format": {"word_bits": 32, "page_words": 2048, "num_bases": 14, "width_set": [8, 16],
                   "bucket_caps": [192, 1856], "outlier_cap": 128}}


@pytest.mark.parametrize("cfg,blob,raw", [("deepseek-7b-kv", 3332, 4096), (WIDE, 5700, 8192)])
def test_bytes_per_page_match_hand_count(cfg, blob, raw):
    # KV: 256 pointer lanes + 512 delta lanes (x4 B), 64 x 2 B outlier
    # values, 64 x 2 B indices, 4 B header.  32-bit: 256 + 48 + 928 lanes,
    # 128 x 4 B values, 128 x 2 B indices, 4 B header.
    f = Format.from_config(config(cfg) if isinstance(cfg, str) else cfg)
    assert (f.blob_bytes_per_page, f.raw_bytes_per_page) == (blob, raw)
    assert f.page_bytes_moved == blob + raw


def _kv_pages(n: int, seed: int) -> np.ndarray:
    c = config("deepseek-7b-kv")
    return np.asarray(gen.stream(c, {"stream_bytes": n * Format.from_config(c).raw_bytes_per_page},
                                 seed))


def _wide_pages(n: int, seed: int) -> np.ndarray:
    """32-bit heap-like words: structs {ptr lo, ptr hi, int, int}, one
    16-word block in 8 zero; many pages overflow their outlier table."""
    rng = np.random.default_rng(seed)
    field = np.arange(n * 2048) % 4
    ptr = 16 * rng.integers(0, 1 << 26, n * 2048)
    ints = rng.integers(0, 4000, n * 2048)
    w = np.where(field == 0, ptr, np.where(field == 1, 32570, ints)).reshape(-1, 16)
    w[rng.random(w.shape[0]) < 1 / 8] = 0
    return w.reshape(n, 2048).astype(np.int32)


CASES = {"kv": (lambda: config("deepseek-7b-kv"), _kv_pages), "32-bit": (lambda: WIDE, _wide_pages)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_reference_matches_the_program_oracle(case):
    """The reference shares no code with the program; on the same table
    both must give the same blob and words."""
    from repro.core.format import BaseTable
    from repro.core.gbdi_fr import fr_decode, fr_encode

    import cells

    cfg, pages = CASES[case]
    f = Format.from_config(cfg())
    x = pages(16, SEED)
    bases, widths = table.fit(x[:4], f)
    t = BaseTable(jnp.asarray(bases), jnp.asarray(widths))
    prog = fr_encode(jnp.asarray(x), t, cells.fr_config(f))
    ref = refcodec.encode(jnp.asarray(x), jnp.asarray(bases), jnp.asarray(widths), f)
    for k in refcodec.BLOB_FIELDS:
        assert np.array_equal(prog[k], ref[k]), k
    words = refcodec.roundtrip(jnp.asarray(x), jnp.asarray(bases), jnp.asarray(widths), f)
    assert np.array_equal(fr_decode(prog, t, cells.fr_config(f)), words)
    assert np.array_equal(refcodec.decode(ref, jnp.asarray(bases), jnp.asarray(widths), f),
                          words)


def test_reference_drops_only_past_the_outlier_table():
    f = Format.from_config(WIDE)
    x = _wide_pages(8, SEED)
    bases, widths = table.fit(x[:2], f)
    blob = refcodec.encode(jnp.asarray(x), jnp.asarray(bases), jnp.asarray(widths), f)
    words = np.asarray(refcodec.roundtrip(jnp.asarray(x), jnp.asarray(bases),
                                          jnp.asarray(widths), f))
    wrong = (words != x).sum(axis=1)
    assert wrong.any()
    assert np.array_equal(wrong, np.asarray(blob["n_dropped"]))
    assert (np.asarray(blob["n_out"]) == f.outlier_cap)[wrong > 0].all()


def test_table_covers_a_cluster_with_a_centred_base():
    f = Format.from_config(config("deepseek-7b-kv"))
    words = np.arange(1000, 1256)                     # one 8-bit window
    bases, widths = table.fit(words, f)
    assert bases[0] == 1000 + 128 and widths[0] == 8
    assert bases.shape == widths.shape == (f.num_bases,)


def test_generator_is_the_seed_and_only_the_seed():
    import cells

    a = _kv_pages(4, SEED)
    b = _kv_pages(4, SEED)
    c = _kv_pages(4, SEED & 0xFFFFFFFF)                        # same low 32 bits
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    # the layer's table is the configuration's: the same whatever the seed
    cfg = config("deepseek-7b-kv")
    f = Format.from_config(cfg)
    t1, t2 = cells.config_table(cfg, f), cells.config_table(cfg, f)
    assert all(np.array_equal(u, v) for u, v in zip(t1, t2))
    # and it fits the seed's tokens: no page of the stream drops a word
    blob = refcodec.encode(jnp.asarray(a), jnp.asarray(t1[0]), jnp.asarray(t1[1]), f)
    assert not np.asarray(blob["n_dropped"]).any()


def test_kv_stream_rotates_k_and_not_v():
    c = config("deepseek-7b-kv")
    x = _kv_pages(8, SEED).reshape(-1, 2, 4096)                     # token, (K, V), row
    v = np.asarray(gen.bf16_of(jnp.asarray(x[:, 1])).astype(jnp.float32))
    k = np.asarray(gen.bf16_of(jnp.asarray(x[:, 0])).astype(jnp.float32))
    noise = c["values"]["noise_std"]
    assert np.abs(v - v.mean(axis=0)).max() < 8 * noise             # V: means + noise
    assert np.abs(k - k.mean(axis=0)).max() > 8 * noise             # K: rotated by position


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

def test_interval_arithmetic():
    u = traces._union([(3.0, 4.0), (0.0, 1.0), (0.5, 2.0)])
    assert u == [(0.0, 2.0), (3.0, 4.0)]
    assert traces._overlap(u, [(1.0, 3.5)]) == pytest.approx(1.5)


def synthetic() -> traces.Trace:
    spans = [("bench.window", 0.0, 10.0), ("bench.round", 0.0, 4.0),
             ("bench.encode", 0.0, 2.0), ("bench.decode", 2.0, 4.0)]
    ops = {"/device:TPU:0": [("enc", 0.5, 1.5), ("dec", 2.0, 3.0), ("late", 9.0, 11.0)]}
    host = [("wait", 4.0, 9.0, 0)]
    return traces.Trace(spans=spans, host=host, ops=ops,
                        work={"encode_bytes": 100.0, "decode_bytes": 300.0},
                        peaks={"hbm_bytes_s": 200.0})


def test_readers_on_a_synthetic_trace():
    import run

    t = synthetic()
    assert t.busy_s() == pytest.approx(3.0)                       # clipped to the window
    # 100 B in 1 s of device time at 200 B/s: half the roofline
    assert run.reader("encode_roofline")(t) == pytest.approx(50.0)
    assert run.reader("decode_roofline")(t) == pytest.approx(150.0)
    assert run.reader("device_idle_pct.roundtrip")(t) == pytest.approx(70.0)
    assert run.reader("device_idle_pct.decode")(t) is None         # no step spans
    assert run.reader("kv_step_device_ms")(t) is None
    assert t.idle_gaps()[0] == ["bench.window/wait", pytest.approx(6.0)]
    assert dict(t.device_ops()) == pytest.approx({"enc": 1.0, "dec": 1.0, "late": 1.0})


def test_decode_mfu_on_a_synthetic_trace():
    """Three steps of 20 ms device time each inside their spans, a stray op
    outside them, and the FLOPs `attn_flops` counts for three steps at
    positions 100..102 of a 2-sequence, 4-head, 8-wide attention."""
    import byname
    import run

    flops = byname.load("drivers", "decode").attn_flops
    # q.K and p.V: 2 FLOPs per multiply-add each, over (pos + 1) positions
    assert flops(2, 4, 8, 101) == 2 * 2 * (2 * 4 * 8 * 101)
    work = sum(flops(2, 4, 8, p + 1) for p in (100, 101, 102))
    spans = [("bench.window", 0.0, 1.0)] + [("bench.step", 0.1 * i, 0.1 * i + 0.05)
                                             for i in range(1, 4)]
    ops = {"/device:TPU:0": [(f"step{i}", 0.1 * i + 0.01, 0.1 * i + 0.03) for i in range(1, 4)]
           + [("stray", 0.6, 0.9)]}
    t = traces.Trace(spans=spans, host=[], ops=ops, work={"steps": 3, "attn_flops": work},
                     peaks={"flops_bf16": 1e9})
    assert run.reader("decode_mfu")(t) == pytest.approx(100.0 * work / 1e9 / 0.06)
    assert run.reader("kv_step_device_ms")(t) == pytest.approx(20.0)
    assert run.reader("device_idle_pct.decode")(t) == pytest.approx(100.0 * (1 - 0.36))
    # without step spans, or without `attn_flops` in the work, nothing to read
    assert run.reader("decode_mfu")(synthetic()) is None
    t.work = {"steps": 3}
    assert run.reader("decode_mfu")(t) is None


def test_reduction_of_a_cpu_trace():
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()

    def work():
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.step"):
                    f(x).block_until_ready()

    _, t = traces.traced(work, cpu_ops)
    assert len(t.named("bench.step")) == 3 and t.window_s > 0
    inside = t.busy_s(t.named("bench.step"))
    assert 0 < inside <= t.busy_s() <= t.window_s
    t.work = {"steps": 3}
    import run

    assert run.reader("kv_step_device_ms")(t) == pytest.approx(1000 * inside / 3)
    assert any(n.startswith("dot") for n, _ in t.device_ops())
    assert t.describe().startswith("/host:CPU: ")
    with pytest.raises(RuntimeError, match="no device operations"):
        traces.traced(work, lambda plane, line: False)
