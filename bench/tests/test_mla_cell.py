"""Whole runs of the MLA decode cell at a small size on the CPU, past the
harness's look for a chip: a sound run comes out correct, and the float8
control and the program with a planted fault come out not correct."""
from __future__ import annotations

import importlib
import json
import time

import jax
import jax.numpy as jnp
import pytest

import control
import run
from conftest import BENCH, ROOT, SEED, cpu_ops

CELL = "deepseek-v3-mla.decode"
PEAKS = {"hbm_bytes_s": 819e9, "flops_bf16": 197e12}
#: the cell's configuration at small widths: 80-word latent rows on
#: 128-word pages, so a flush group is 8 tokens in 5 pages
SMALL_CONFIG = {"hidden_size": 256, "num_attention_heads": 8, "q_lora_rank": 64,
                "kv_lora_rank": 64, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
                "v_head_dim": 32, "num_hidden_layers": 2,
                "format": {"word_bits": 16, "page_words": 128, "num_bases": 14,
                           "width_set": [8], "bucket_caps": [128], "outlier_cap": 16}}
SMALL_MIX = {"batch": 2, "context": 32, "answer": 16, "max_len": 48, "prefill_chunk": 16,
             "check_steps": 3, "check_rows": 4, "trace_seconds": 0.5}
#: the one limit the small size needs of its own: over 48 positions of 8
#: heads the bf16 program's latent attention outputs read 38-42% of their
#: words more than one bf16 ulp off the reference (over 33k positions of
#: 128 heads on the chip, under 10%); the float8 control and the float8
#: attention-operand fault read 87% and more here
SMALL_LIMITS = {"attn_off_pct": 50.0}


@pytest.fixture(scope="module")
def doc() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.fixture(scope="module")
def parts(doc):
    cell, config, mix, limits = run.load_cell(doc, CELL)
    return cell, {**config, **SMALL_CONFIG}, {**mix, **SMALL_MIX}, {**limits, **SMALL_LIMITS}


def small_run(doc, parts, trace=False, seconds=0.3):
    return run.run_cell(doc, CELL, SEED, seconds, trace, devices=jax.devices(), peaks=PEAKS,
                        t_start=time.perf_counter(), is_ops=cpu_ops, parts=parts)


@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_correct(doc, parts, trace):
    res = small_run(doc, parts, trace)
    assert res["correct"] and res["failed"] == 0, res["checks"]
    assert res["attempted"] > 0
    if trace:
        want = {m["name"] for m in run.per_layer_for(doc, CELL)}
        assert want == {"mla_step_device_ms", "mla_decode_mfu", "device_idle_pct.mla_decode"}
        assert set(res["metrics"]) == want
        assert 0 < res["metrics"]["mla_decode_mfu"]["value"] < 100
        assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    else:
        assert set(res["metrics"]) == {"decode_tokens_per_s", "setup_s"}
        assert all(v["value"] > 0 for v in res["metrics"].values())


def test_control_fails(doc, parts):
    r = control.readings(doc, CELL, SEED, 0.3, parts=parts)
    limits = parts[3]
    assert all(v <= limits[k] for k, v in r["program"].items()), r
    gaps = ("latent_gap", "latent_off_pct", "mla_gap", "mla_off_pct", "attn_gap", "attn_off_pct")
    assert all(r["control"][k] > limits[k] for k in gaps), r


def _last_layer_dropped(f):
    def step(spec, cfg, params, caches, xs, pos):
        out, attn, new = f(spec, cfg, params[:-1], caches[:-1], xs, pos)
        return (jnp.concatenate([out, out[-1:]]), jnp.concatenate([attn, attn[-1:]]),
                new + caches[-1:])
    return step


def _v_from_wrong_slice(f):
    def attention(q, rows, valid, scale, latent_dim):
        return f(q, jnp.roll(rows, -(rows.shape[-1] - latent_dim), axis=-1), valid, scale,
                 latent_dim)
    return attention


def _float8_operands(f):
    def attention(q, rows, valid, scale, latent_dim):
        def low(a):
            return a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        return f(low(q), low(rows), valid, scale, latent_dim)
    return attention


FAULTS = {
    # one layer left out of the step: its cache never grows, its output is another's
    "dropped layer": ("repro.serving.engine", "_mla_step", _last_layer_dropped),
    # a step that leaves the cache as it was
    "stale cache": ("repro.serving.kv_cache", "append_rows",
                    lambda f: lambda spec, cache, rows, pos: cache),
    # values read from the row's last latent_dim words, past the roped part
    "V from the wrong slice": ("repro.models.mla", "latent_attention", _v_from_wrong_slice),
    # the cache stored in bf16, but the attention's products taken on float8 operands
    "float8 attention operands": ("repro.models.mla", "latent_attention", _float8_operands),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_planted_fault_is_not_correct(doc, parts, monkeypatch, fault):
    module, attr, wrap = FAULTS[fault]
    mod = importlib.import_module(module)
    monkeypatch.setattr(mod, attr, wrap(getattr(mod, attr)))
    res = small_run(doc, parts)
    assert not res["correct"] and res["failed"] > 0, res["checks"]


def test_cell_files_exist(doc):
    import byname

    cell, config, mix, limits = run.load_cell(doc, CELL)
    assert (BENCH / "configs" / "deepseek-v3-mla.json").is_file()
    assert callable(byname.load("drivers", mix["kind"]).Driver)
    assert set(limits) == {"kv_pages_off", "kv_words_off", "latent_gap", "latent_off_pct",
                           "mla_gap", "mla_off_pct", "attn_gap", "attn_off_pct"}
