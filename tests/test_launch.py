"""Launch-path integration: mesh construction, sharding rules on real param
trees, a tiny end-to-end dry-run lower+compile in a 16-device subprocess,
and the train entrypoint."""
import os
import subprocess
import sys

import jax
import pytest

from repro.configs import ARCHS, reduced
from repro.models.api import build_model


def test_sharding_rules_cover_all_archs():
    """Every param leaf of every full config gets a spec that divides."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import jax
from repro.configs import ARCHS
from repro.distributed import sharding as shd
from repro.launch import specs
from repro.launch.mesh import make_production_mesh
from repro.models.api import build_model

mesh = make_production_mesh(multi_pod=True)
for arch, cfg in ARCHS.items():
    model = build_model(cfg)
    p = specs.params_specs(model)
    sh = shd.params_shardings(mesh, p)
    for (path, leaf), (_, s) in zip(
        jax.tree_util.tree_flatten_with_path(p)[0],
        jax.tree_util.tree_flatten_with_path(sh)[0],
    ):
        for dim, name in zip(leaf.shape, tuple(s.spec) + (None,) * 8):
            size = 1
            if name is not None:
                names = name if isinstance(name, tuple) else (name,)
                for n in names:
                    size *= mesh.shape[n]
            assert dim % size == 0, (arch, path, leaf.shape, s.spec)
print("SHARDING_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True,
                       env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       timeout=600)
    assert "SHARDING_OK" in r.stdout, r.stdout[-1500:] + r.stderr[-1500:]


def test_dryrun_tiny_mesh_end_to_end():
    """The real dryrun cell machinery on a 4-device mesh with a reduced
    config: lower + compile + walker stats must succeed."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import jax
from repro.configs import ARCHS, reduced
from repro.launch.dryrun import lower_cell, analyse
from repro.models.config import ShapeConfig

cfg = dataclasses.replace(reduced(ARCHS["gemma3-12b"]), dtype="float32")
sc = ShapeConfig("tiny_train", seq_len=64, global_batch=4, kind="train")
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
lowered = lower_cell(cfg, sc, mesh, n_micro=1)
compiled = lowered.compile()
rec = analyse(cfg, sc, "tiny", lowered, 0.0, compiled, n_chips=4)
assert rec["ok"] and rec["flops_per_chip"] > 0
sc2 = ShapeConfig("tiny_decode", seq_len=64, global_batch=4, kind="decode")
compiled2 = lower_cell(cfg, sc2, mesh).compile()
assert compiled2.cost_analysis() is not None
print("DRYRUN_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True,
                       env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       timeout=600)
    assert "DRYRUN_OK" in r.stdout, r.stdout[-1500:] + r.stderr[-1500:]


@pytest.mark.slow
def test_train_entrypoint_runs(tmp_path):
    """CLI smoke (fresh-process compile + 6 real steps, ~1 min on CPU);
    the Trainer itself stays tier-1 via test_substrate."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "xlstm-1.3b",
         "--reduced", "--steps", "6", "--batch", "2", "--seq", "32",
         "--ckpt-dir", str(tmp_path / "ck"), "--ckpt-every", "5"],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), timeout=600,
    )
    assert "loss" in r.stdout and r.returncode == 0, r.stdout[-800:] + r.stderr[-800:]
