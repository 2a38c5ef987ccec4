"""CPU-only smoke tests for benchmarks/roofline.py.

The roofline table is pure host arithmetic over dry-run JSON cells, so the
whole module is testable with synthetic cells — no compile, no device.
"""
import importlib.util
import json
import math
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _load_roofline():
    spec = importlib.util.spec_from_file_location(
        "_bench_roofline", _ROOT / "benchmarks" / "roofline.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


roofline = _load_roofline()


def _cell(arch="gemma3-12b", shape="decode_32k", mesh="pod", n_chips=16):
    return {
        "ok": True,
        "arch": arch,
        "shape": shape,
        "mesh": mesh,
        "n_chips": n_chips,
        "variant": "baseline",
        "roofline": {
            "compute_s": 0.010,
            "memory_s": 0.025,
            "collective_s": 0.004,
            "dominant": "memory",
            "useful_flops_ratio": 0.82,
        },
    }


def _write_cells(d, cells):
    d.mkdir(parents=True, exist_ok=True)
    for i, c in enumerate(cells):
        (d / f"cell{i}.json").write_text(json.dumps(c))


def test_peak_bytes_per_s_finite():
    peak = roofline.peak_bytes_per_s()
    assert isinstance(peak, float)
    assert math.isfinite(peak)
    assert peak > 0
    # it must be the peak table's entry for the modelled chip, not a
    # re-derived number
    from repro.launch.mesh import DRYRUN_DEVICE_KIND, chip_peaks

    assert peak == chip_peaks(DRYRUN_DEVICE_KIND).hbm_bytes_s


def test_ideal_step_terms_positive_and_finite():
    compute_s, memory_s = roofline.ideal_step_s("gemma3-12b", "decode_32k", 16)
    assert math.isfinite(compute_s) and compute_s > 0
    assert math.isfinite(memory_s) and memory_s > 0
    # train shapes pay the 20-byte/param optimizer traffic; decode does not
    tc, tm = roofline.ideal_step_s("gemma3-12b", "train_4k", 16)
    assert math.isfinite(tc) and math.isfinite(tm) and tm > 0


def test_rows_from_synthetic_cells(tmp_path):
    _write_cells(
        tmp_path,
        [
            _cell(),
            _cell(shape="train_4k"),
            {"ok": False, "arch": "broken"},          # dropped by load_cells
            {"ok": True, "skipped": True, "arch": "x"},  # dropped too
        ],
    )
    cells = roofline.load_cells(str(tmp_path))
    assert len(cells) == 2
    rs = roofline.rows(cells)
    assert len(rs) == 2
    for r in rs:
        assert math.isfinite(r["ideal_s"]) and r["ideal_s"] > 0
        assert math.isfinite(r["roofline_frac"]) and r["roofline_frac"] > 0
        assert r["dominant"] == "memory"


def test_main_smoke(tmp_path, capsys, monkeypatch):
    _write_cells(tmp_path, [_cell(), _cell(mesh="host")])  # host cell filtered
    monkeypatch.setattr(
        sys, "argv", ["roofline.py", "--dir", str(tmp_path), "--mesh", "pod"]
    )
    roofline.main()
    out = capsys.readouterr().out.strip().splitlines()
    assert out[0].startswith("arch,shape,")
    assert len(out) == 2  # header + the one pod cell
    assert out[1].startswith("gemma3-12b,decode_32k,")


def test_main_markdown_smoke(tmp_path, capsys, monkeypatch):
    _write_cells(tmp_path, [_cell()])
    monkeypatch.setattr(
        sys,
        "argv",
        ["roofline.py", "--dir", str(tmp_path), "--mesh", "pod", "--markdown"],
    )
    roofline.main()
    out = capsys.readouterr().out
    assert "| arch |" in out and "| gemma3-12b |" in out
