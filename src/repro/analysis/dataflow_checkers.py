"""Layer-3 dataflow checkers: hazards that live across statements.

PR 7 made the encode hot path *donate* its state buffers
(``donate_argnums`` on the stage jits): the old device buffer is freed
the moment the call is dispatched, so any later read of the Python name
still bound to it aliases freed memory — JAX raises, but only at
runtime, and only on paths that actually execute.  ``use-after-donate``
finds those reads statically by tracking names through each function
body in execution order.

A module-level memo keyed by content grows without limit in a long sweep
unless it evicts (``_PREP_CACHE`` in ``kernels/xla.py`` is keyed by table
digest x config, and every new table would pin its device arrays).
``unbounded-module-cache`` makes that class of leak a gate:
a module-level dict that function bodies insert into must also have an
eviction path (``popitem``/``pop``/``del``/``clear``) or an explicit
baseline entry saying why it is bounded.
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Iterator

from repro.analysis import _ast_util as U
from repro.analysis.base import register
from repro.analysis.finding import Finding
from repro.analysis.project import SourceFile

# --------------------------------------------------------------------------
# use-after-donate
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _Donor:
    """One module-local callable that donates argument buffers."""

    name: str
    positions: frozenset[int]      # donated positional indices
    params: frozenset[str]         # donated parameter names (kwarg calls)


def _module_donors(tree: ast.Module) -> dict[str, _Donor]:
    """Callables in this module whose call sites donate arguments:
    jit-decorated defs with ``donate_arg*`` and ``g = jax.jit(f,
    donate_argnums=...)`` call-form bindings."""
    donors: dict[str, _Donor] = {}
    defs = {
        n.name: n for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    }
    for fn in defs.values():
        for dec in fn.decorator_list:
            site = U.parse_jit_decorator(dec)
            if site is None or not site.has_donate:
                continue
            pos_names = U.positional_param_names(fn)
            donated = site.donated_params(fn)
            positions = set(site.donate_argnums)
            positions |= {pos_names.index(p) for p in donated if p in pos_names}
            donors[fn.name] = _Donor(fn.name, frozenset(positions),
                                     frozenset(donated))
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign) or not isinstance(node.value, ast.Call):
            continue
        site = U.parse_jit_decorator(node.value)
        if site is None or not site.has_donate:
            continue
        inner = node.value.args[0] if node.value.args else None
        fn = defs.get(inner.id) if isinstance(inner, ast.Name) else None
        positions = set(site.donate_argnums)
        params = set(site.donate_argnames)
        if fn is not None:
            pos_names = U.positional_param_names(fn)
            positions |= {pos_names.index(p) for p in site.donate_argnames
                          if p in pos_names}
            params |= {pos_names[i] for i in site.donate_argnums
                       if i < len(pos_names)}
        for tgt in node.targets:
            if isinstance(tgt, ast.Name):
                donors[tgt.id] = _Donor(tgt.id, frozenset(positions),
                                        frozenset(params))
    return donors


def _stmt_reads(stmt: ast.stmt) -> Iterator[ast.Name]:
    """Name loads in one statement, not descending into nested defs."""
    for node in ast.walk(stmt):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef, ast.Lambda)) and node is not stmt:
            continue
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            yield node


def _expr_kills(stmt: ast.stmt, donors: dict[str, _Donor]) -> dict[str, tuple[str, int]]:
    """Names donated by calls in this statement -> (callee, lineno)."""
    kills: dict[str, tuple[str, int]] = {}
    for node in ast.walk(stmt):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        if not isinstance(node, ast.Call):
            continue
        callee = None
        if isinstance(node.func, ast.Name):
            callee = donors.get(node.func.id)
        if callee is None:
            continue
        for i, arg in enumerate(node.args):
            if i in callee.positions and isinstance(arg, ast.Name):
                kills[arg.id] = (callee.name, node.lineno)
        for kw in node.keywords:
            if kw.arg in callee.params and isinstance(kw.value, ast.Name):
                kills[kw.value.id] = (callee.name, node.lineno)
    return kills


def _binding_targets(stmt: ast.stmt) -> set[str]:
    """Names this statement (re)binds at its own level."""
    out: set[str] = set()

    def add(t: ast.expr) -> None:
        if isinstance(t, ast.Name):
            out.add(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for el in t.elts:
                add(el)
        elif isinstance(t, ast.Starred):
            add(t.value)

    if isinstance(stmt, ast.Assign):
        for t in stmt.targets:
            add(t)
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        add(stmt.target)
    elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
        out.add(stmt.name)
    elif isinstance(stmt, (ast.Import, ast.ImportFrom)):
        for alias in stmt.names:
            out.add((alias.asname or alias.name).split(".")[0])
    return out


def _merge(
    a: dict[str, tuple[str, int]], b: dict[str, tuple[str, int]]
) -> dict[str, tuple[str, int]]:
    """Join two branch outcomes conservatively: a name is dead after the
    join only if BOTH branches left it dead (no false positives from
    branches that rebind)."""
    return {k: v for k, v in a.items() if k in b}


class _DonateScan:
    """Forward scan of one function body tracking donated-dead names."""

    def __init__(self, src: SourceFile, donors: dict[str, _Donor]) -> None:
        self.src = src
        self.donors = donors
        self.findings: list[Finding] = []
        self._seen: set[tuple[str, int]] = set()

    def _flag(self, name: ast.Name, origin: tuple[str, int]) -> None:
        key = (name.id, name.lineno)
        if key in self._seen:
            return
        self._seen.add(key)
        callee, dline = origin
        self.findings.append(Finding(
            "use-after-donate", self.src.rel, name.lineno, name.col_offset,
            f"`{name.id}` was donated to `{callee}` (line {dline}, "
            "donate_argnums) and is read again here; the buffer is freed at "
            "dispatch — rebind the name to the call's result or drop the read",
            self.src.anchor(name.lineno)))

    def scan(self, body: list[ast.stmt],
             dead: dict[str, tuple[str, int]]) -> dict[str, tuple[str, int]]:
        for stmt in body:
            dead = self._scan_stmt(stmt, dead)
        return dead

    def _scan_stmt(self, stmt: ast.stmt,
                   dead: dict[str, tuple[str, int]]) -> dict[str, tuple[str, int]]:
        # compound statements: reads in the header, then branch bodies
        if isinstance(stmt, (ast.If, ast.While)):
            for name in _stmt_reads_expr(stmt.test):
                if name.id in dead:
                    self._flag(name, dead[name.id])
            a = self.scan(list(stmt.body), dict(dead))
            if isinstance(stmt, ast.While):
                self._rescan_loop(stmt.body, a, dead)
            b = self.scan(list(stmt.orelse), dict(dead))
            return _merge(a, b) if isinstance(stmt, ast.If) else _merge(dead, _merge(a, b))
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            for name in _stmt_reads_expr(stmt.iter):
                if name.id in dead:
                    self._flag(name, dead[name.id])
            entry = dict(dead)
            for t in _binding_targets_expr(stmt.target):
                entry.pop(t, None)
            after = self.scan(list(stmt.body), dict(entry))
            self._rescan_loop(stmt.body, after, entry)
            b = self.scan(list(stmt.orelse), dict(dead))
            return _merge(dead, _merge(after, b))
        if isinstance(stmt, ast.Try):
            a = self.scan(list(stmt.body), dict(dead))
            merged = a
            for h in stmt.handlers:
                merged = _merge(merged, self.scan(list(h.body), dict(dead)))
            merged = _merge(merged, self.scan(list(stmt.orelse), dict(a)))
            return self.scan(list(stmt.finalbody), merged)
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                for name in _stmt_reads_expr(item.context_expr):
                    if name.id in dead:
                        self._flag(name, dead[name.id])
                if item.optional_vars is not None:
                    for t in _binding_targets_expr(item.optional_vars):
                        dead.pop(t, None)
            return self.scan(list(stmt.body), dead)
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            dead.pop(stmt.name, None)
            return dead
        # simple statement: reads -> donate kills -> binding un-kills
        for name in _stmt_reads(stmt):
            if name.id in dead:
                self._flag(name, dead[name.id])
        for name_id, origin in _expr_kills(stmt, self.donors).items():
            dead[name_id] = origin
        for t in _binding_targets(stmt):
            dead.pop(t, None)
        if isinstance(stmt, ast.Delete):
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    dead.pop(t.id, None)
        return dead

    def _rescan_loop(self, body: list[ast.stmt],
                     after: dict[str, tuple[str, int]],
                     entry: dict[str, tuple[str, int]]) -> None:
        """Names dead at the end of a loop body flow back to its top: one
        extra pass catches cross-iteration use-after-donate (a name
        donated late in the body and read early next iteration)."""
        carried = {k: v for k, v in after.items() if k not in entry}
        if carried:
            self.scan(list(body), carried)


def _stmt_reads_expr(expr: ast.expr) -> Iterator[ast.Name]:
    for node in ast.walk(expr):
        if isinstance(node, ast.Lambda):
            continue
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            yield node


def _binding_targets_expr(target: ast.expr) -> set[str]:
    out: set[str] = set()
    for node in ast.walk(target):
        if isinstance(node, ast.Name):
            out.add(node.id)
    return out


@register(
    "use-after-donate",
    "name bound to a donate_argnums argument read again after the jitted "
    "call (the device buffer is freed at dispatch)",
)
def check_use_after_donate(src: SourceFile) -> Iterator[Finding]:
    if src.is_test:
        return
    donors = _module_donors(src.tree)
    if not donors:
        return
    for ctx in U.walk_functions(src.tree):
        scan = _DonateScan(src, donors)
        scan.scan(list(ctx.node.body), {})
        yield from scan.findings


# --------------------------------------------------------------------------
# unbounded-module-cache
# --------------------------------------------------------------------------

#: container constructors that build a memo-shaped module global
_DICT_CTORS = {"dict", "OrderedDict", "defaultdict", "WeakValueDictionary"}
#: insertion mutations (growth); eviction ops are the bound evidence
_INSERTERS = {"setdefault", "update", "__setitem__"}
_EVICTORS = {"popitem", "pop", "clear", "__delitem__"}


def _module_dict_globals(tree: ast.Module) -> dict[str, int]:
    """Module-level names bound to dict-like containers -> def lineno."""
    out: dict[str, int] = {}
    for node in tree.body:
        targets: list[ast.expr] = []
        value: ast.expr | None = None
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        if value is None:
            continue
        is_dict = isinstance(value, (ast.Dict, ast.DictComp))
        if isinstance(value, ast.Call):
            head = U.dotted_name(value.func).rsplit(".", 1)[-1]
            is_dict = head in _DICT_CTORS
        if not is_dict:
            continue
        for t in targets:
            if isinstance(t, ast.Name):
                out[t.id] = node.lineno


    return out


def _cache_ops(tree: ast.Module, names: set[str]) -> tuple[dict[str, int], set[str]]:
    """(first in-function insertion lineno per name, names with eviction)."""
    inserts: dict[str, int] = {}
    evicts: set[str] = set()

    def in_function(node: ast.AST, parents: dict[ast.AST, ast.AST]) -> bool:
        cur = node
        while cur in parents:
            cur = parents[cur]
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return True
        return False

    parents = U.build_parents(tree)
    for node in ast.walk(tree):
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            tgts = node.targets if isinstance(node, ast.Assign) else [node.target]
            for t in tgts:
                # constant-key stores are a fixed-schema record (counter
                # dicts like {"hits": 0}), not unbounded memo growth —
                # the statically-spelled key set bounds the dict itself
                if (isinstance(t, ast.Subscript) and isinstance(t.value, ast.Name)
                        and t.value.id in names
                        and not isinstance(t.slice, ast.Constant)
                        and in_function(node, parents)):
                    inserts.setdefault(t.value.id, node.lineno)
        elif isinstance(node, ast.Call):
            fn = node.func
            if (isinstance(fn, ast.Attribute) and isinstance(fn.value, ast.Name)
                    and fn.value.id in names):
                if (fn.attr in _INSERTERS and in_function(node, parents)
                        and not (fn.attr == "setdefault" and node.args
                                 and isinstance(node.args[0], ast.Constant))):
                    inserts.setdefault(fn.value.id, node.lineno)
                elif fn.attr in _EVICTORS:
                    evicts.add(fn.value.id)
        elif isinstance(node, ast.Delete):
            for t in node.targets:
                if (isinstance(t, ast.Subscript)
                        and isinstance(t.value, ast.Name)
                        and t.value.id in names):
                    evicts.add(t.value.id)
    return inserts, evicts


def _unbounded_lru_decorators(tree: ast.Module) -> Iterator[tuple[str, int]]:
    """``@functools.cache`` / ``@lru_cache(maxsize=None)`` decorators —
    memo containers with no eviction bound, same hazard as a bare dict."""
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for dec in node.decorator_list:
            head = U.dotted_name(dec.func if isinstance(dec, ast.Call) else dec)
            tail = head.rsplit(".", 1)[-1]
            if tail == "cache" and head in ("functools.cache", "cache"):
                yield node.name, dec.lineno
            elif tail == "lru_cache" and isinstance(dec, ast.Call):
                sized = [a for a in (dec.args + [k.value for k in dec.keywords
                                                 if k.arg == "maxsize"])]
                for a in sized:
                    if isinstance(a, ast.Constant) and a.value is None:
                        yield node.name, dec.lineno


@register(
    "unbounded-module-cache",
    "module-level memo with no eviction bound: a dict grown from function "
    "bodies with no popitem/pop/del/clear, or lru_cache(maxsize=None)/"
    "functools.cache — leaks across long sweeps",
)
def check_unbounded_module_cache(src: SourceFile) -> Iterator[Finding]:
    if src.is_test:
        return
    for fn_name, lineno in _unbounded_lru_decorators(src.tree):
        yield Finding(
            "unbounded-module-cache", src.rel, lineno, 0,
            f"`{fn_name}` memoizes with no eviction bound "
            "(lru_cache(maxsize=None) / functools.cache); every distinct "
            "key pins its value — jitted closures especially — forever; "
            "give it a maxsize",
            src.anchor(lineno))
    containers = _module_dict_globals(src.tree)
    if not containers:
        return
    inserts, evicts = _cache_ops(src.tree, set(containers))
    for name, lineno in sorted(inserts.items(), key=lambda kv: kv[1]):
        if name in evicts:
            continue
        yield Finding(
            "unbounded-module-cache", src.rel, lineno, 0,
            f"module-level dict `{name}` (defined line {containers[name]}) "
            "grows here with no eviction path anywhere in the module; bound "
            "it (`while len(c) > CAP: c.popitem(last=False)`), use "
            "functools.lru_cache(maxsize=...), or baseline with the reason "
            "it cannot grow unboundedly",
            src.anchor(lineno))
