"""Intraprocedural (same-module) call graph with device-context propagation.

PR 6's checkers classified *device context* — code that executes under a
JAX trace — purely decorator-adjacent: a function is device code only if
it carries a jit decorator, looks like a Pallas kernel, or is lexically
nested in one.  Real kernel code factors helpers out to module level
(``_decode_words`` in ``gbdi_paged_attn.py``, the ``_class_update_impl``
stage bodies in ``kernels/xla.py``), and a ``.item()`` inside such a
helper serialises the program exactly as hard as one written inline.

This module closes that gap without whole-program analysis: it builds the
module-local call graph (who calls whom, among functions *defined in the
same file*) and propagates device context along call edges — a function
is *trace-reachable* when any caller chain from a jit/kernel entry
reaches it.  Checkers ask :func:`device_contexts` for the resulting
classification and get the lexical :class:`~repro.analysis._ast_util.
FnContext` walk plus the propagated bit.

The propagation is deliberately one-module-deep (imports are opaque):
cross-module helpers stay host-classified, which errs on silence — the
analysis pass never guesses a hazard it cannot see the trace context of.

A second escape hatch keeps the pass quiet on deliberate host/device
dispatchers: a function that is *not* lexically device but tests
``isinstance(..., Tracer)`` in its body (``fit_fr_bases`` in
``core/gbdi_fr.py`` filters a concrete sample on the host with numpy and
takes a traced one as it is) is a *trace boundary* — it is
still checked itself, but it does not transmit device context to its
callees, because the calls on its concrete path run at trace time only
when the guard has already proven the inputs are host values.
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Iterator

from repro.analysis import _ast_util as U


@dataclasses.dataclass
class FnNode:
    """One function definition in the module call graph.

    ``qualname`` is the lexical dotted path (``outer.inner``); top-level
    functions are addressable by bare name, which is how call sites
    resolve (a call to ``helper(...)`` can only mean the module-level
    ``helper`` — Python name resolution inside another function cannot
    see a sibling's nested defs).
    """

    qualname: str
    node: ast.FunctionDef | ast.AsyncFunctionDef
    ctx: U.FnContext                       # lexical device classification
    callees: set[str] = dataclasses.field(default_factory=set)
    boundary: bool = False                 # host/device dispatcher (Tracer guard)


@dataclasses.dataclass
class CallGraph:
    """Module-local call graph + the trace-reachable closure."""

    nodes: dict[str, FnNode]               # qualname -> node
    device: set[str]                       # trace-reachable qualnames

    def is_device(self, qualname: str) -> bool:
        return qualname in self.device


def _qualnames(tree: ast.Module) -> Iterator[tuple[str, U.FnContext]]:
    """Pair every function of the lexical walk with its dotted qualname.

    ``walk_functions`` yields in document order with nested functions
    after their parent, so a parent stack keyed on AST containment
    reconstructs the lexical path.
    """
    # parent chain via a fresh containment walk (cheap: one pass)
    parents = U.build_parents(tree)
    for ctx in U.walk_functions(tree):
        parts = [ctx.node.name]
        cur: ast.AST = ctx.node
        while cur in parents:
            cur = parents[cur]
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
                parts.append(cur.name)
        yield ".".join(reversed(parts)), ctx


def _callee_names(fn: ast.FunctionDef | ast.AsyncFunctionDef) -> set[str]:
    """Bare names this function calls or passes to a jit/vmap-style
    wrapper (``jax.jit(helper)`` and ``jax.lax.fori_loop(0, n, body, c)``
    execute ``helper``/``body`` under the caller's trace)."""
    out: set[str] = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        if isinstance(node.func, ast.Name):
            out.add(node.func.id)
        # higher-order: function-valued arguments run in the callee's
        # context too (cond/fori/scan/jit all trace their fn args)
        for arg in node.args:
            if isinstance(arg, ast.Name):
                out.add(arg.id)
    return out


def _has_tracer_guard(fn: ast.FunctionDef | ast.AsyncFunctionDef) -> bool:
    """True when the body mentions a ``Tracer`` type — the idiomatic
    ``isinstance(x, jax.core.Tracer)`` host/device dispatch guard."""
    for node in ast.walk(fn):
        if isinstance(node, ast.Attribute) and node.attr == "Tracer":
            return True
        if isinstance(node, ast.Name) and node.id == "Tracer":
            return True
    return False


def build_callgraph(tree: ast.Module) -> CallGraph:
    """Build the module call graph and propagate device context.

    Seeds are the lexically-classified device functions (jit decorator,
    kernel heuristic, nesting); propagation follows call edges from any
    device function to same-module callees until a fixed point.  A
    nested function's calls count as its enclosing top-level function's
    calls for resolution purposes (both can only reach module-level
    names).
    """
    nodes: dict[str, FnNode] = {}
    for qualname, ctx in _qualnames(tree):
        nodes[qualname] = FnNode(qualname=qualname, node=ctx.node, ctx=ctx,
                                 boundary=_has_tracer_guard(ctx.node))

    # resolve: bare name -> module-level qualname (top-level defs only;
    # shadowed/duplicate names resolve to the last def, like runtime)
    toplevel = {q: n for q, n in nodes.items() if "." not in q}
    for node in nodes.values():
        for name in _callee_names(node.node):
            if name in toplevel and name != node.qualname:
                node.callees.add(name)

    device = {q for q, n in nodes.items() if n.ctx.device}
    # call-form entries: `g = jax.jit(f)` anywhere in the module makes a
    # top-level `f` a trace entry even though it carries no decorator
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and U.parse_jit_decorator(node) is not None):
            for arg in node.args:
                if isinstance(arg, ast.Name) and arg.id in toplevel:
                    device.add(arg.id)
    frontier = list(device)
    while frontier:
        q = frontier.pop()
        if nodes[q].boundary and not nodes[q].ctx.device:
            continue  # trace-aware dispatcher: checked itself, not a conduit
        for callee in nodes[q].callees:
            if callee not in device:
                device.add(callee)
                # nested defs of a newly-device function inherit context
                for sub in nodes:
                    if sub.startswith(callee + ".") and sub not in device:
                        device.add(sub)
                        frontier.append(sub)
                frontier.append(callee)
    return CallGraph(nodes=nodes, device=device)


def device_contexts(tree: ast.Module) -> Iterator[tuple[U.FnContext, bool]]:
    """The lexical function walk, augmented with the propagated bit.

    Yields ``(ctx, propagated)`` where ``propagated`` is True when the
    function is trace-reachable through the call graph but *not* device
    by the lexical rules alone — checkers phrase their message
    differently for those ("called from jitted `f`" vs "jitted").
    """
    graph = build_callgraph(tree)
    for qualname, ctx in _qualnames(tree):
        reachable = graph.is_device(qualname)
        yield ctx, reachable and not ctx.device


def device_callers(tree: ast.Module, qualname: str) -> list[str]:
    """Device-context functions that (transitively) call ``qualname`` —
    used to name the trace entry in propagated findings."""
    graph = build_callgraph(tree)
    out = []
    for q, n in graph.nodes.items():
        if n.ctx.device and _reaches(graph, q, qualname):
            out.append(q)
    return sorted(out)


def _reaches(graph: CallGraph, src: str, dst: str) -> bool:
    seen: set[str] = set()
    stack = [src]
    while stack:
        q = stack.pop()
        if q == dst:
            return True
        if q in seen or q not in graph.nodes:
            continue
        seen.add(q)
        stack.extend(graph.nodes[q].callees)
    return False
