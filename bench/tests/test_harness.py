"""Whole runs of every cell at small sizes on the CPU, past the harness's
look for a chip: sound runs come out correct, and the control and the
program with its timed path broken come out not correct."""
from __future__ import annotations

import json
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import control
import run
from conftest import BENCH, ROOT, SEED, cpu_ops

CELLS = ["deepseek-7b-kv.roundtrip", "deepseek-7b-kv.decode"]
PEAKS = {"hbm_bytes_s": 819e9, "flops_bf16": 197e12}


def small_run(doc, parts, name, trace=False, seconds=0.3):
    return run.run_cell(doc, name, SEED, seconds, trace, devices=jax.devices(), peaks=PEAKS,
                        t_start=time.perf_counter(), is_ops=cpu_ops, parts=parts[name])


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_correct(doc, small_parts, name, trace):
    res = small_run(doc, small_parts, name, trace)
    assert res["correct"] and res["failed"] == 0, res["checks"]
    assert list(res)[-1] == "checks" and res["attempted"] > 0
    cell = small_parts[name][0]
    if trace:
        want = {m["name"] for m in run.per_layer_for(doc, name)}
        assert set(res["metrics"]) == want
        if name == "deepseek-7b-kv.decode":
            assert "decode_mfu" in want and 0 < res["metrics"]["decode_mfu"]["value"] < 100
        assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
        assert res["breakdown"]["device_ops"]
    else:
        want = {m["name"] for m in doc["end_to_end"] if run.applies(m, cell["name"])}
        assert set(res["metrics"]) == want and "setup_s" in want
        assert all(v["value"] > 0 for v in res["metrics"].values())


@pytest.mark.parametrize("name", CELLS)
def test_control_fails(doc, small_parts, name):
    r = control.readings(doc, name, SEED, 0.3, parts=small_parts[name])
    limits = small_parts[name][3]
    assert all(v <= limits[k] for k, v in r["program"].items()), r
    assert any(v > limits[k] for k, v in r["control"].items()), r


def _flip_first(x):
    return x.at[(0,) * x.ndim].add(1)


FAULTS = {
    # an answer altered where it is produced: one decoded word, one blob lane
    "decoded word altered": ("repro.kernels.ops", "decode_pages",
                             lambda f: lambda *a, **k: _flip_first(f(*a, **k))),
    "blob lane altered": ("repro.kernels.ops", "encode_pages",
                          lambda f: lambda *a, **k: {**(b := f(*a, **k)),
                                                     "deltas": _flip_first(b["deltas"])}),
    # half of the batch left out: the second half of the pages never decoded
    "half the pages left out": ("repro.kernels.ops", "decode_pages",
                                lambda f: lambda *a, **k: (lambda d: d.at[d.shape[0] // 2:].set(0))(
                                    f(*a, **k))),
}
DECODE_FAULTS = {
    # a step that returns its state unchanged
    "state unchanged": ("repro.serving.kv_cache", "append",
                        lambda f: lambda spec, cache, k, v, pos: cache),
    # half of the batch left out of attention
    "half the batch left out": ("repro.serving.kv_cache", "attention_decode",
                                lambda f: lambda spec, q, *a, **k: (lambda o: o.at[
                                    o.shape[0] // 2:].set(0))(f(spec, q, *a, **k))),
    # an answer altered where it is produced: one attention output, one K word
    "answer altered": ("repro.serving.kv_cache", "attention_decode",
                       lambda f: lambda *a, **k: (lambda o: o.at[0, 0, 0].add(
                           jnp.asarray(0.5, o.dtype)))(f(*a, **k))),
    "token altered": ("repro.serving.kv_cache", "append",
                      lambda f: lambda spec, cache, k, v, pos: f(spec, cache,
                                                                _flip_word(k), v, pos)),
}


def _flip_word(k):
    w = jax.lax.bitcast_convert_type(k, jnp.uint16)
    return jax.lax.bitcast_convert_type(w.at[0, 0, 0, 0].add(jnp.uint16(1)), jnp.bfloat16)


@pytest.mark.parametrize("cell,fault", [(CELLS[0], f) for f in FAULTS]
                         + [(CELLS[1], f) for f in DECODE_FAULTS])
def test_broken_timed_path_is_not_correct(doc, small_parts, monkeypatch, cell, fault):
    import importlib

    module, attr, wrap = {**FAULTS, **DECODE_FAULTS}[fault]
    mod = importlib.import_module(module)
    monkeypatch.setattr(mod, attr, wrap(getattr(mod, attr)))
    res = small_run(doc, small_parts, cell)
    assert not res["correct"] and res["failed"] > 0, res["checks"]


def test_refuses_without_a_tpu():
    proc = subprocess.run([sys.executable, "bench/run.py", "--workload", CELLS[0],
                           "--seed", "1", "--seconds", "1", "--trace", "0"],
                          cwd=ROOT, capture_output=True, text=True, timeout=300,
                          env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 2 and proc.stdout == ""
    assert "needs 1 TPU chip" in proc.stderr


def test_benchmark_json_names_files_that_exist(doc):
    for c in doc["configs"]:
        assert (ROOT / c["file"]).is_file()
    import byname
    import gen

    for w in doc["workloads"]:
        cell, config, mix, limits = run.load_cell(doc, w["name"])
        assert callable(byname.load("drivers", mix["kind"]).Driver)
        assert callable(gen.family(config).stream)
    for m in doc["per_layer"]:
        assert callable(run.reader(m["name"]))
    from conftest import merged

    pending = json.loads((BENCH / "pending.json").read_text())
    assert merged(json.loads((ROOT / "BENCHMARK.json").read_text()), pending) == doc
    names = [w["name"] for w in doc["workloads"]]
    assert len(set(names)) == len(names) and set(CELLS) == set(names)
