"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

``main`` forces 512 placeholder host devices through ``XLA_FLAGS`` before
jax initialises its backend (the production meshes need them); importing
this module changes nothing.

Per cell this lowers the real step function (train_step / prefill /
decode_step) against ShapeDtypeStruct inputs with full production
shardings, compiles it, and dumps:

  * memory_analysis()  — per-device bytes (proves the cell fits),
  * cost_analysis()    — HLO FLOPs / bytes for the roofline,
  * collective wire bytes parsed from the compiled HLO,
  * the three roofline terms + MODEL_FLOPS (6ND / 6N_aD) ratio,

as JSON under --out (one file per cell, so a crashed cell loses nothing).

Usage:
  python -m repro.launch.dryrun --arch deepseek-7b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
"""
import argparse
import json
import os
import time
import traceback
from pathlib import Path

import jax

from repro.configs import ARCHS, get_config
from repro.distributed import sharding as shd
from repro.launch import hlo_stats, specs
from repro.launch.mesh import DRYRUN_DEVICE_KIND, chip_peaks, make_production_mesh
from repro.models.api import build_model
from repro.models.config import SHAPES, ModelConfig, ShapeConfig
from repro.optim import adamw
from repro.training.train_step import make_train_step


def runs_long_context(cfg: ModelConfig) -> bool:
    """long_500k runs only for sub-quadratic stacks (DESIGN.md §5)."""
    return not all(s.mixer in ("attn", "shared_attn") for s in cfg.pattern)


def cell_skipped(cfg: ModelConfig, sc: ShapeConfig) -> str | None:
    if sc.name == "long_500k" and not runs_long_context(cfg):
        return "pure full-attention arch: long_500k needs sub-quadratic attention"
    return None


def lower_cell(cfg: ModelConfig, sc: ShapeConfig, mesh, *, n_micro: int = 4,
               overrides: dict | None = None):
    import dataclasses

    ba = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp = 1
    for a in ba:
        dp *= mesh.shape[a]
    cfg = dataclasses.replace(cfg, mesh_axes=ba, dp_shards=dp, **(overrides or {}))
    model = build_model(cfg)
    tree = specs.input_specs(cfg, sc)
    p_shard = shd.params_shardings(mesh, tree["params"])

    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    if sc.kind == "train":
        opt_shard = {"m": p_shard, "v": p_shard, "step": rep}
        b_shard = shd.batch_sharding(mesh, tree["batch"])
        # grad-accum microbatching keeps per-device activation memory in
        # HBM budget at global_batch=256 (a production knob, see §Perf)
        step = make_train_step(model, adamw.AdamWConfig(), n_micro=n_micro)
        fn = jax.jit(
            step,
            in_shardings=(p_shard, opt_shard, b_shard),
            out_shardings=(p_shard, opt_shard, rep),
            donate_argnums=(0, 1),
        )
        with mesh:
            lowered = fn.lower(tree["params"], tree["opt_state"], tree["batch"])
    elif sc.kind == "prefill":
        b_shard = shd.batch_sharding(mesh, tree["batch"])
        c_shard = shd.cache_shardings(mesh, tree["cache"])
        fn = jax.jit(
            model.prefill,
            in_shardings=(p_shard, b_shard, c_shard),
            out_shardings=(c_shard, rep),
            donate_argnums=(2,),
        )
        with mesh:
            lowered = fn.lower(tree["params"], tree["batch"], tree["cache"])
    else:  # decode
        s_shard = shd.batch_sharding(mesh, tree["step_in"])
        c_shard = shd.cache_shardings(mesh, tree["cache"])
        fn = jax.jit(
            model.decode_step,
            in_shardings=(p_shard, s_shard, c_shard, rep),
            out_shardings=(rep, c_shard),
            donate_argnums=(2,),
        )
        with mesh:
            lowered = fn.lower(tree["params"], tree["step_in"], tree["cache"], tree["pos"])
    return lowered


def analyse(cfg: ModelConfig, sc: ShapeConfig, mesh_name: str, lowered, compile_s: float,
            compiled, *, n_chips: int | None = None, dtype_scale: float = 1.0) -> dict:
    if n_chips is None:
        n_chips = 512 if mesh_name == "multipod" else 256
    # trip-count-aware walker (XLA's cost_analysis counts loop bodies once)
    stats = hlo_stats.analyze_module(compiled.as_text())
    flops = stats["flops"]
    # dtype_scale=0.5: cell compiled in f32 (clean HLO, no CPU bf16
    # legalisation artifacts); every real tensor is exactly 2x its bf16
    # deployment width, so memory/collective halve (DESIGN.md §8)
    bytes_accessed = stats["hbm_bytes"] * dtype_scale
    coll = {k: v * dtype_scale for k, v in stats["collectives"].items()}
    xla_cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not implement it
        mem_d = {"error": str(e)}

    # tokens per step for MODEL_FLOPS
    toks = sc.global_batch * (sc.seq_len if sc.kind != "decode" else 1)
    n_active = cfg.active_param_count()
    mult = 6 if sc.kind == "train" else 2
    model_flops_global = mult * n_active * toks
    model_flops_per_chip = model_flops_global / n_chips

    peaks = chip_peaks(DRYRUN_DEVICE_KIND)
    compute_s = flops / peaks.flops_bf16
    memory_s = bytes_accessed / peaks.hbm_bytes_s
    collective_s = coll.get("total", 0.0) / peaks.ici_bytes_s_per_link
    dominant = max(
        ("compute", compute_s), ("memory", memory_s), ("collective", collective_s),
        key=lambda kv: kv[1],
    )[0]
    return {
        "arch": cfg.arch_id,
        "shape": sc.name,
        "mesh": mesh_name,
        "n_chips": n_chips,
        "ok": True,
        "compile_seconds": compile_s,
        "flops_per_chip": flops,
        "bytes_per_chip": bytes_accessed,
        "collective_wire_bytes": coll,
        "xla_cost_analysis": {
            "flops_body_once": float(xla_cost.get("flops", 0.0)),
            "bytes_body_once": float(xla_cost.get("bytes accessed", 0.0)),
        },
        "memory_analysis": mem_d,
        "roofline": {
            "compute_s": compute_s,
            "memory_s": memory_s,
            "collective_s": collective_s,
            "dominant": dominant,
            "model_flops_per_chip": model_flops_per_chip,
            "useful_flops_ratio": model_flops_per_chip / flops if flops else None,
        },
        "params_total": cfg.param_count(),
        "params_active": n_active,
    }


def run_cell(
    arch: str, shape: str, mesh_name: str, out_dir: Path, *,
    n_micro: int = 4, variant: str = "", overrides: dict | None = None,
    roofline_dtype: str = "f32x2", mesh_shape: tuple | None = None,
) -> dict:
    cfg = get_config(arch)
    sc = SHAPES[shape]
    suffix = f"__{variant}" if variant else ""
    out_path = out_dir / f"{arch}__{shape}__{mesh_name}{suffix}.json"
    skip = cell_skipped(cfg, sc)
    if skip:
        rec = {"arch": arch, "shape": shape, "mesh": mesh_name, "ok": True, "skipped": skip}
        out_path.write_text(json.dumps(rec, indent=2))
        return rec
    try:
        import dataclasses

        if mesh_shape is not None:
            mesh = jax.make_mesh(
                mesh_shape, ("data", "model") if len(mesh_shape) == 2
                else ("pod", "data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * len(mesh_shape))
            n_chips = 1
            for s in mesh_shape:
                n_chips *= s
        else:
            mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
            n_chips = 512 if mesh_name == "multipod" else 256
        ovr = dict(overrides or {})
        dtype_scale = 1.0
        if roofline_dtype == "f32x2" and cfg.dtype == "bfloat16":
            ovr["dtype"] = "float32"
            dtype_scale = 0.5
        t0 = time.time()
        lowered = lower_cell(cfg, sc, mesh, n_micro=n_micro, overrides=ovr)
        compiled = lowered.compile()
        dt = time.time() - t0
        rec = analyse(cfg, sc, mesh_name, lowered, dt, compiled,
                      n_chips=n_chips, dtype_scale=dtype_scale)
        rec["variant"] = variant or "baseline"
        rec["overrides"] = {k: str(v) for k, v in (overrides or {}).items()}
        rec["roofline_dtype"] = roofline_dtype
        if mesh_shape is not None:
            rec["mesh_shape"] = list(mesh_shape)
        print(compiled.memory_analysis())
        del compiled, lowered
    except Exception:
        rec = {
            "arch": arch, "shape": shape, "mesh": mesh_name, "ok": False,
            "variant": variant or "baseline",
            "error": traceback.format_exc(limit=25),
        }
    out_path.write_text(json.dumps(rec, indent=2))
    status = "OK" if rec.get("ok") else "FAIL"
    extra = f" skip={rec['skipped']}" if rec.get("skipped") else ""
    print(f"[{status}] {arch} x {shape} x {mesh_name}{suffix}"
          f" ({rec.get('compile_seconds', 0):.1f}s){extra}", flush=True)
    return rec


def main() -> None:
    # must precede the first backend initialisation (device count locks)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--n-micro", type=int, default=4)
    ap.add_argument("--variant", default="", help="suffix recorded in the cell JSON")
    ap.add_argument("--set", action="append", default=[],
                    help="ModelConfig override, e.g. --set q_chunk=1024")
    ap.add_argument("--mesh-shape", default=None,
                    help="override mesh, e.g. 4,64 (single-pod hillclimb variants)")
    ap.add_argument("--roofline-dtype", default="f32x2", choices=["f32x2", "native"])
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        overrides[k] = v
    mesh_shape = tuple(int(x) for x in args.mesh_shape.split(",")) if args.mesh_shape else None

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    archs = sorted(ARCHS) if args.all or args.arch is None else [args.arch]
    shapes = list(SHAPES) if args.all or args.shape is None else [args.shape]

    failures = 0
    suffix = f"__{args.variant}" if args.variant else ""
    for mesh_name in meshes:
        for arch in archs:
            for shape in shapes:
                out_path = out_dir / f"{arch}__{shape}__{mesh_name}{suffix}.json"
                if args.skip_existing and out_path.exists():
                    prev = json.loads(out_path.read_text())
                    if prev.get("ok"):
                        continue
                rec = run_cell(
                    arch, shape, mesh_name, out_dir, n_micro=args.n_micro,
                    variant=args.variant, overrides=overrides,
                    roofline_dtype=args.roofline_dtype, mesh_shape=mesh_shape,
                )
                failures += 0 if rec.get("ok") else 1
    print(f"done; failures={failures}", flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
