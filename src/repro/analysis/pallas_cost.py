"""Static Pallas VMEM cost model + the ``vmem-over-budget`` checker.

The kernels assert their own tile budgets at trace time
(:func:`repro.kernels.gbdi_encode.vmem_tile_bytes`), but only for code
paths a test actually traces, and only for modules that remembered to
call the check at all — ``gbdi_paged_attn.py`` shipped without one.
This module makes the budget a static gate:

* every ``pl.BlockSpec`` tile shape in the kernel modules is evaluated
  against representative configs (the default :class:`FRConfig` for the
  encode/decode pair, the serving ``KV_FR`` + a llama3-class GQA shape
  for paged attention) — pure AST work, no JAX import;
* each kernel module's own transient estimate (``vmem_tile_bytes`` /
  ``attn_vmem_tile_bytes``) is added on top, lazily imported and gated
  so the checker degrades to the AST-only part when JAX is absent;
* both must fit ``VMEM_BUDGET_BYTES`` — the single budget constant the
  whole repo shares.

The per-kernel byte report (:func:`cost_report`) is what CI uploads via
``python -m repro.analysis --vmem-report``.
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Iterator

from repro.analysis import _ast_util as U
from repro.analysis.base import register
from repro.analysis.finding import Finding
from repro.analysis.project import Project, SourceFile

#: dtype width assumed for every tile (int32/f32 lanes throughout)
_WORD = 4

#: kernel modules the cost model knows how to parameterise
_KERNEL_MODULES = (
    "src/repro/kernels/gbdi_encode.py",
    "src/repro/kernels/gbdi_decode.py",
    "src/repro/kernels/gbdi_paged_attn.py",
)

#: presence of any of these names ties a module to the shared budget
_BUDGET_NAMES = {"VMEM_BUDGET_BYTES", "_check_vmem", "_check_attn_vmem"}


@dataclasses.dataclass
class KernelCost:
    """Per-kernel VMEM bytes, static (BlockSpec) + module transient model."""

    module: str                    # repo-relative path
    kernel: str                    # pallas entry function name
    config: str                    # label of the representative config
    blockspec_bytes: int           # sum of evaluated BlockSpec tiles
    model_bytes: int | None        # module's own transient estimate
    budget_bytes: int
    error: str | None = None

    @property
    def total_bytes(self) -> int:
        return self.blockspec_bytes + (self.model_bytes or 0)

    @property
    def ok(self) -> bool:
        return self.error is None and self.total_bytes <= self.budget_bytes

    def to_json(self) -> dict[str, object]:
        return {
            "module": self.module, "kernel": self.kernel,
            "config": self.config, "blockspec_bytes": self.blockspec_bytes,
            "model_bytes": self.model_bytes, "total_bytes": self.total_bytes,
            "budget_bytes": self.budget_bytes, "ok": self.ok,
            "error": self.error,
        }


class _ShapeEnvError(Exception):
    pass


def _eval_dim(node: ast.expr, env: dict[str, int]) -> int:
    """Evaluate one BlockSpec dimension: ints, env names (possibly dotted),
    and integer arithmetic."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return node.value
    name = U.dotted_name(node)
    if name:
        if name in env:
            return env[name]
        raise _ShapeEnvError(f"unknown dimension name `{name}`")
    if isinstance(node, ast.BinOp):
        lhs, rhs = _eval_dim(node.left, env), _eval_dim(node.right, env)
        if isinstance(node.op, ast.Add):
            return lhs + rhs
        if isinstance(node.op, ast.Sub):
            return lhs - rhs
        if isinstance(node.op, ast.Mult):
            return lhs * rhs
        if isinstance(node.op, (ast.FloorDiv, ast.Div)):
            return lhs // rhs
        raise _ShapeEnvError(f"unsupported operator {ast.dump(node.op)}")
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        return -_eval_dim(node.operand, env)
    raise _ShapeEnvError(f"unsupported dimension expr {ast.dump(node)}")


def _blockspec_shape(call: ast.Call) -> ast.expr | None:
    """The shape tuple of a ``pl.BlockSpec((dims...), index_map)`` call."""
    if U.dotted_name(call.func).rsplit(".", 1)[-1] != "BlockSpec":
        return None
    return call.args[0] if call.args else None


def _spec_helpers(tree: ast.Module) -> dict[str, tuple[list[str], ast.expr]]:
    """Functions whose body is ``return pl.BlockSpec((...), ...)`` — e.g.
    ``page_specs(lanes)`` in the paged-attention kernel.  Maps name ->
    (positional params, shape tuple AST) so call sites can be inlined."""
    out: dict[str, tuple[list[str], ast.expr]] = {}
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        body = [s for s in node.body if not isinstance(s, ast.Expr)
                or not isinstance(s.value, ast.Constant)]
        if len(body) != 1 or not isinstance(body[0], ast.Return):
            continue
        ret = body[0].value
        if isinstance(ret, ast.Call):
            shape = _blockspec_shape(ret)
            if shape is not None:
                out[node.name] = (U.positional_param_names(node), shape)
    return out


def pallas_entries(tree: ast.Module) -> list[ast.FunctionDef | ast.AsyncFunctionDef]:
    """Functions that issue a ``pl.pallas_call`` (the kernel entries)."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for sub in ast.walk(node):
            if (isinstance(sub, ast.Call)
                    and U.dotted_name(sub.func).rsplit(".", 1)[-1] == "pallas_call"):
                out.append(node)
                break
    # keep outermost only: a nested helper never owns the entry
    names = {n.name for n in out}
    return [n for n in out if not any(
        n is not m and n in ast.walk(m) for m in out if m.name in names)]


def blockspec_bytes(
    fn: ast.FunctionDef | ast.AsyncFunctionDef,
    helpers: dict[str, tuple[list[str], ast.expr]],
    env: dict[str, int],
) -> int:
    """Sum of all BlockSpec tile footprints in one kernel entry.

    Conditional specs (adaptive-profile branches) are counted
    unconditionally — a small conservative overestimate.
    """
    total = 0

    def visit(node: ast.AST) -> None:
        nonlocal total
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)) and child is not fn:
                continue                       # helper defs handled via calls
            if isinstance(child, ast.Call):
                shape = _blockspec_shape(child)
                if shape is not None and isinstance(shape, (ast.Tuple, ast.List)):
                    dims = [_eval_dim(d, env) for d in shape.elts]
                    tile = _WORD
                    for d in dims:
                        tile *= d
                    total += tile
                elif (isinstance(child.func, ast.Name)
                        and child.func.id in helpers):
                    params, shape = helpers[child.func.id]
                    bound = dict(env)
                    for p, a in zip(params, child.args):
                        bound[p] = _eval_dim(a, env)
                    assert isinstance(shape, (ast.Tuple, ast.List))
                    tile = _WORD
                    for d in shape.elts:
                        tile *= _eval_dim(d, bound)
                    total += tile
            visit(child)

    visit(fn)
    return total


def _runtime_models() -> dict[str, tuple[str, dict[str, int], int | None, int]] | None:
    """Import the kernel modules and build (config label, shape env,
    transient-model bytes, budget) per known module.  None when the
    kernel stack cannot import (no JAX in the venv) — the checker then
    runs its AST-only part."""
    try:
        from repro.core.gbdi_fr import FRConfig
        from repro.kernels import gbdi_encode as enc
        from repro.serving.kv_cache import KV_FR
    except Exception:                          # pragma: no cover - no-JAX envs
        return None
    cfg = FRConfig()
    tile_env = {
        "T": enc.DEFAULT_PAGES_PER_TILE, "P": cfg.page_words,
        "cap": cfg.outlier_cap,
        "cfg.ptr_lanes": cfg.ptr_lanes, "cfg.delta_lanes": cfg.delta_lanes,
        "cfg.outlier_cap": cfg.outlier_cap, "cfg.page_words": cfg.page_words,
    }
    tile_model = enc.vmem_tile_bytes(cfg, enc.DEFAULT_PAGES_PER_TILE)
    # representative GQA decode shape: llama3-8B-class heads over KV_FR
    hd = 128
    n_kv = max(1, min(8, KV_FR.page_words // hd))
    while KV_FR.page_words % (n_kv * hd):
        n_kv -= 1
    groups = 4
    attn_env = {
        "n_kv": n_kv, "hd": hd, "groups": groups,
        "cfg.ptr_lanes": KV_FR.ptr_lanes, "cfg.delta_lanes": KV_FR.delta_lanes,
        "cfg.outlier_cap": KV_FR.outlier_cap, "cfg.page_words": KV_FR.page_words,
    }
    attn_model: int | None = None
    try:
        from repro.kernels import gbdi_paged_attn as attn
        attn_model = attn.attn_vmem_tile_bytes(KV_FR, n_kv=n_kv, hd=hd,
                                               groups=groups)
    except (ImportError, AttributeError):
        attn_model = None                      # flagged as missing budget tie
    return {
        "src/repro/kernels/gbdi_encode.py": (
            f"FRConfig() x pages_per_tile={enc.DEFAULT_PAGES_PER_TILE}", tile_env, tile_model,
            enc.VMEM_BUDGET_BYTES),
        "src/repro/kernels/gbdi_decode.py": (
            f"FRConfig() x pages_per_tile={enc.DEFAULT_PAGES_PER_TILE}", tile_env, tile_model,
            enc.VMEM_BUDGET_BYTES),
        "src/repro/kernels/gbdi_paged_attn.py": (
            f"KV_FR x (n_kv={n_kv}, hd={hd}, groups={groups})", attn_env,
            attn_model, enc.VMEM_BUDGET_BYTES),
    }


def cost_report(project: Project) -> list[KernelCost] | None:
    """Evaluate every known kernel module; None when JAX is unavailable."""
    models = _runtime_models()
    if models is None:
        return None
    out: list[KernelCost] = []
    for rel in _KERNEL_MODULES:
        src = project.by_rel.get(rel)
        if src is None:
            continue
        label, env, model_bytes, budget = models[rel]
        helpers = _spec_helpers(src.tree)
        for fn in pallas_entries(src.tree):
            try:
                static = blockspec_bytes(fn, helpers, env)
                err = None
            except _ShapeEnvError as exc:
                static, err = 0, str(exc)
            out.append(KernelCost(
                module=rel, kernel=fn.name, config=label,
                blockspec_bytes=static, model_bytes=model_bytes,
                budget_bytes=budget, error=err))
    return out


def _module_budget_tied(tree: ast.Module) -> bool:
    names = {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            names |= {a.asname or a.name for a in node.names}
    return bool(names & _BUDGET_NAMES)


@register(
    "vmem-over-budget",
    "Pallas kernel tile footprint exceeds (or is not tied to) the shared "
    "VMEM_BUDGET_BYTES budget",
    scope="project",
)
def check_vmem_budget(project: Project) -> Iterator[Finding]:
    pallas_files = []
    for src in project.glob("src/repro/kernels/"):
        has_pallas = any(
            isinstance(n, ast.Call)
            and U.dotted_name(n.func).rsplit(".", 1)[-1] == "pallas_call"
            for n in ast.walk(src.tree))
        if has_pallas:
            pallas_files.append(src)

    for src in pallas_files:
        entries = pallas_entries(src.tree)
        line = entries[0].lineno if entries else 1
        if not _module_budget_tied(src.tree):
            yield Finding(
                "vmem-over-budget", src.rel, line, 0,
                "Pallas kernel module never references the shared VMEM "
                "budget (VMEM_BUDGET_BYTES / _check_vmem); add a trace-time "
                "tile-size assertion so oversized configs fail loudly",
                src.anchor(line))
        if src.rel not in _KERNEL_MODULES:
            yield Finding(
                "vmem-over-budget", src.rel, line, 0,
                "Pallas kernel module is not registered in "
                "analysis/pallas_cost.py — add a representative config so "
                "the static VMEM report covers it",
                src.anchor(line))

    report = cost_report(project)
    if report is None:                         # pragma: no cover - no-JAX envs
        return
    for cost in report:
        if cost.ok:
            continue
        src = project.by_rel[cost.module]
        entries = [f for f in pallas_entries(src.tree) if f.name == cost.kernel]
        line = entries[0].lineno if entries else 1
        detail = (cost.error if cost.error is not None else
                  f"~{cost.total_bytes >> 10} KiB tile footprint under "
                  f"{cost.config} exceeds the {cost.budget_bytes >> 20} MiB "
                  "budget")
        yield Finding(
            "vmem-over-budget", src.rel, line, 0,
            f"`{cost.kernel}`: {detail}; shrink pages_per_tile/page_words "
            "or raise VMEM_BUDGET_BYTES deliberately",
            src.anchor(line))
