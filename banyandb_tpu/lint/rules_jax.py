"""Hot-path rules: host syncs, recompile churn, precision drift.

These encode the project's JAX performance contract (ROADMAP north
star): device work in query/, ops/, parallel/ and index/ must
not round-trip to the host per column, must not rebuild jit wrappers per
call, and must not silently promote kernel inputs to float64.

Device-value taint is deliberately convention-driven: a call to any
callable whose final name segment is ``kernel``, ``jitted`` or ``step``
(or a name bound from ``jax.jit(...)`` / a ``@jax.jit`` function in the
same module) is treated as producing device arrays.  The codebase names
its compiled entry points exactly this way (fused_exec/stream_exec
``kernel``, dist_exec ``step``/``jitted``), which keeps the analysis
local and false-positive-light; cross-module device returns are covered
by the always-flagged explicit sync APIs (device_get/block_until_ready).
"""

from __future__ import annotations

import ast
import re
from typing import Iterable, Iterator

from banyandb_tpu.lint.core import FileContext, Finding, dotted_name

HOT_SCOPE = ("query/", "ops/", "parallel/", "index/")

_DEVICE_CALLEE_RE = re.compile(r"^_?([a-z0-9]+_)*(kernel|jitted|step)$")
_DEVICE_MODULES = ("jnp.", "jax.numpy.", "jax.lax.", "jax.ops.")
_CLOCK_CALLS = {
    "time.time",
    "time.time_ns",
    "time.monotonic",
    "time.perf_counter",
    "datetime.datetime.now",
    "datetime.datetime.utcnow",
}
_SYNC_CASTS = {"float", "int", "bool"}
_SYNC_NP = {
    "np.asarray",
    "np.array",
    "numpy.asarray",
    "numpy.array",
}


def _is_jax_jit(node: ast.AST) -> bool:
    d = dotted_name(node)
    if d in ("jax.jit", "jit"):
        return True
    # functools.partial(jax.jit, ...) decorator form
    if isinstance(node, ast.Call) and dotted_name(node.func) in (
        "partial",
        "functools.partial",
    ):
        return bool(node.args) and dotted_name(node.args[0]) in (
            "jax.jit",
            "jit",
        )
    return False


class ModuleJaxFacts:
    """Module-level jit analysis shared by host-sync / recompile-hazard.

    - ``jitted_names``: names bound to jit-compiled callables
      (``x = jax.jit(f)``, ``@jax.jit def f``)
    - ``traced_fns``: FunctionDef nodes whose BODIES run under trace
      (decorated with jax.jit, or whose name is passed to jax.jit
      anywhere in the module — the nested-``kernel`` build pattern)
    """

    def __init__(self, tree: ast.Module):
        self.jitted_names: set[str] = set()
        traced_names: set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if any(_is_jax_jit(d) for d in node.decorator_list):
                    self.jitted_names.add(node.name)
                    traced_names.add(node.name)
            elif isinstance(node, ast.Call) and _is_jax_jit(node.func):
                if node.args and isinstance(node.args[0], ast.Name):
                    traced_names.add(node.args[0].id)
            elif isinstance(node, ast.Assign):
                if isinstance(node.value, ast.Call) and _is_jax_jit(
                    node.value.func
                ):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            self.jitted_names.add(t.id)
        self.traced_fns = [
            n
            for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
            and n.name in traced_names
        ]


def _walk_own(fn: ast.AST) -> Iterator[ast.AST]:
    """Walk a function's subtree WITHOUT descending into nested function
    defs — each nested def is visited as its own function by the caller,
    so descending here would report its findings once per enclosing
    scope."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _assign_targets(node: ast.AST) -> Iterator[str]:
    if isinstance(node, ast.Assign):
        for t in node.targets:
            if isinstance(t, ast.Name):
                yield t.id
            elif isinstance(t, ast.Tuple):
                for e in t.elts:
                    if isinstance(e, ast.Name):
                        yield e.id
    elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
        if isinstance(node.target, ast.Name):
            yield node.target.id


class _FnTaint:
    """Single-pass device-value taint over one function body."""

    def __init__(self, fn: ast.AST, facts: ModuleJaxFacts):
        self.facts = facts
        self.tainted: set[str] = set()
        self.jitted_locals: set[str] = set(facts.jitted_names)
        for node in ast.walk(fn):
            if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                value = node.value
                if value is None:
                    continue
                if isinstance(value, ast.Call) and _is_jax_jit(value.func):
                    self.jitted_locals.update(_assign_targets(node))
                elif self.expr_tainted(value):
                    self.tainted.update(_assign_targets(node))

    def callee_is_device(self, func: ast.AST) -> bool:
        d = dotted_name(func)
        last = d.rsplit(".", 1)[-1] if d else ""
        if last in self.jitted_locals:
            return True
        return bool(_DEVICE_CALLEE_RE.match(last))

    def expr_tainted(self, e: ast.AST) -> bool:
        if isinstance(e, ast.Name):
            return e.id in self.tainted
        if isinstance(e, (ast.Subscript, ast.Attribute)):
            return self.expr_tainted(e.value)
        if isinstance(e, ast.Call):
            d = dotted_name(e.func)
            if d.startswith(_DEVICE_MODULES) or d == "jax.device_put":
                return True
            return self.callee_is_device(e.func)
        if isinstance(e, ast.BinOp):
            return self.expr_tainted(e.left) or self.expr_tainted(e.right)
        if isinstance(e, ast.UnaryOp):
            return self.expr_tainted(e.operand)
        if isinstance(e, ast.IfExp):
            return self.expr_tainted(e.body) or self.expr_tainted(e.orelse)
        return False


class HostSyncRule:
    """host-sync: device->host round-trips in hot modules.

    Flags block_until_ready / jax.device_get anywhere in scope (the
    legitimate result-boundary transfer carries a suppression with a
    reason — that is the point: boundaries become greppable decisions),
    np.asarray/np.array/float/int/bool applied to device-tainted values
    (each one is a separate blocking transfer; batch them into ONE
    device_get at the boundary), and wall-clock reads inside traced
    functions (they freeze at trace time)."""

    name = "host-sync"
    summary = "device->host sync (transfer/cast/clock) in a hot module"
    scope = HOT_SCOPE

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        facts = ctx.jax_facts
        fns = [
            n
            for n in ast.walk(ctx.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        traced = set(map(id, facts.traced_fns))
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            d = dotted_name(node.func)
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "block_until_ready"
            ):
                yield ctx.finding(
                    node,
                    self.name,
                    "block_until_ready stalls the dispatch pipeline; "
                    "batch at the result boundary",
                )
            elif d == "jax.device_get":
                yield ctx.finding(
                    node,
                    self.name,
                    "explicit device->host transfer; if this is the "
                    "result boundary, suppress with a reason",
                )
        for fn in fns:
            taint = _FnTaint(fn, facts)
            for node in _walk_own(fn):
                if not isinstance(node, ast.Call) or not node.args:
                    continue
                d = dotted_name(node.func)
                is_cast = d in _SYNC_CASTS and len(node.args) == 1
                if (d in _SYNC_NP or is_cast) and taint.expr_tainted(
                    node.args[0]
                ):
                    yield ctx.finding(
                        node,
                        self.name,
                        f"{d}() on a device value forces a blocking "
                        "transfer; use one jax.device_get at the boundary",
                    )
            if id(fn) in traced:
                for node in _walk_own(fn):
                    if (
                        isinstance(node, ast.Call)
                        and dotted_name(node.func) in _CLOCK_CALLS
                    ):
                        yield ctx.finding(
                            node,
                            self.name,
                            "wall-clock read inside a traced function "
                            "freezes at trace time; pass time in as an "
                            "argument",
                        )


class RecompileHazardRule:
    """recompile-hazard: jit wrapper churn and trace-time formatting.

    ``jax.jit(lambda ...)`` and ``jax.jit(f)(...)`` build a fresh
    wrapper (and compile cache entry) per evaluation; a jit call inside
    a loop does so per iteration.  The blessed pattern is the module
    cache keyed by a static FusedSpec (fused_exec._KERNEL_CACHE).
    F-strings over traced parameters concretize under trace."""

    name = "recompile-hazard"
    summary = "per-call jit wrapper / trace-time string formatting"
    scope = HOT_SCOPE

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call) and _is_jax_jit(node.func)):
                continue
            if node.args and isinstance(node.args[0], ast.Lambda):
                yield ctx.finding(
                    node,
                    self.name,
                    "jax.jit(lambda): a fresh lambda never hits the jit "
                    "cache; jit a named function once",
                )
            parent = ctx.parents.get(node)
            if isinstance(parent, ast.Call) and parent.func is node:
                yield ctx.finding(
                    node,
                    self.name,
                    "jax.jit(f)(...) compiles per call; bind the jitted "
                    "callable once and reuse it",
                )
            anc = parent
            while anc is not None:
                if isinstance(anc, (ast.For, ast.While)):
                    yield ctx.finding(
                        node,
                        self.name,
                        "jax.jit inside a loop rebuilds the wrapper per "
                        "iteration; hoist it (or cache by plan spec)",
                    )
                    break
                if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    break  # enclosing fn may itself be cached: loop scan ends
                anc = ctx.parents.get(anc)
        for fn in ctx.jax_facts.traced_fns:
            params = {
                a.arg
                for a in list(fn.args.args)
                + list(fn.args.posonlyargs)
                + list(fn.args.kwonlyargs)
            }
            for node in ast.walk(fn):
                if isinstance(node, ast.JoinedStr):
                    used = {
                        n.id
                        for v in node.values
                        if isinstance(v, ast.FormattedValue)
                        for n in ast.walk(v.value)
                        if isinstance(n, ast.Name)
                    }
                    if used & params:
                        yield ctx.finding(
                            node,
                            self.name,
                            "f-string over a traced argument concretizes "
                            "at trace time",
                        )


class PrecisionDriftRule:
    """precision-drift: dtype-less float64-defaulting constructors.

    ``np.zeros/ones/empty/full/arange`` default to float64; in kernel
    paths that either doubles HBM traffic when the array crosses to the
    device, or silently widens a host accumulator.  Both are real
    decisions (the f32-device/f64-host-merge precision contract,
    docs/soak_r05.json) — make them explicit with a dtype."""

    name = "precision-drift"
    summary = "numpy constructor without explicit dtype in a kernel path"
    scope = ("query/", "ops/", "parallel/")

    _CTORS = {
        "np.zeros": 1,
        "np.ones": 1,
        "np.empty": 1,
        "np.full": 2,
        "np.arange": None,  # dtype is keyword-only in practice
        "numpy.zeros": 1,
        "numpy.ones": 1,
        "numpy.empty": 1,
        "numpy.full": 2,
        "numpy.arange": None,
    }

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            d = dotted_name(node.func)
            if d not in self._CTORS:
                continue
            if any(k.arg == "dtype" for k in node.keywords):
                continue
            pos = self._CTORS[d]
            if pos is not None and len(node.args) > pos:
                continue  # positional dtype present
            yield ctx.finding(
                node,
                self.name,
                f"{d}() defaults to float64; state the dtype the "
                "precision contract intends",
            )


RULES = (HostSyncRule(), RecompileHazardRule(), PrecisionDriftRule())
