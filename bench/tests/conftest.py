"""Shared set-up for the benchmark's own tests.

  JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They run on the CPU at small sizes; the repo's ``testpaths`` does not
collect them.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: small traffic for each mix kind: same code paths, sizes a test can hold
SMALL = {
    "roundtrip": {"stream_bytes": 48 * 8192, "check_pages": 24, "trace_seconds": 0.5},
    "decode": {"batch": 2, "context": 8, "answer": 4, "max_len": 16, "check_steps": 3,
               "check_rows": 4, "trace_seconds": 0.5},
}
SEED = 2**33 + 12345


def cpu_ops(plane: str, line: str) -> bool:
    """On the CPU, XLA runs its operations on the client's threads."""
    return plane == "/host:CPU" and line.startswith("tf_XLA")


def merged(doc: dict, pending: dict) -> dict:
    """``BENCHMARK.json`` with the entries of ``bench/pending.json``, the
    cells and metrics that wait for their chip runs, so their harness is
    tested alike."""
    return {**doc, **{k: doc[k] + pending[k] for k in ("workloads", "end_to_end", "per_layer")}}


@pytest.fixture(scope="session")
def doc() -> dict:
    with open(ROOT / "BENCHMARK.json") as fh, open(BENCH / "pending.json") as fp:
        return merged(json.load(fh), json.load(fp))


@pytest.fixture(scope="session")
def small_parts(doc):
    """cell name -> (cell, config, mix, limits) with a small mix."""
    import run

    out = {}
    for w in doc["workloads"]:
        cell, config, mix, limits = run.load_cell(doc, w["name"])
        out[w["name"]] = (cell, config, {**mix, **SMALL[mix["kind"]]}, limits)
    return out
