"""TM201 — use-after-donate lint for jitted call sites.

``jax.jit(f, donate_argnums=(0,))`` hands argument 0's buffers to XLA:
after the call, reading that array from Python is undefined behavior
(on TPU it is a crash or garbage; on CPU it often *silently works*,
which is why this bug class survives tier-1 — the exact class the
``donate_batch=False`` opt-out exists to dodge).

The pass has two phases:

1. **Registry** — scan the whole package for donating callables:

   * ``@partial(jax.jit, donate_argnums=(...))`` decorated defs
     (the exchanger's merge fns);
   * ``name = jax.jit(fn, donate_argnums=(...))`` assignments,
     including ``self.attr = jax.jit(...)`` (wgan, InferenceSession);
   * factory functions whose ``return jax.jit(..., donate_argnums=...)``
     makes every ``step = build_train_step(...)`` call site a donating
     callable too (the parallel/ step builders).

   ``donate_argnums=(0,) if donate else ()`` counts as donating — the
   lint must assume donation CAN happen.

2. **Dataflow** — per function body, in statement order: a call to a
   registered callable marks each *simple path* argument
   (``x``, ``model.state.params``) in a donated position as dead; any
   later read of the dead path (or an extension of it) is flagged;
   any store to the path or a prefix of it (``model.state = ...``)
   revives it.  Reads inside the donating statement itself are not
   flagged (Python evaluates them before the call).  ``if`` branches
   are treated as mutually exclusive (each analyzed on a copy of the
   incoming state; the fall-through state is the union), so the zoo's
   ``k>1 / a>1 / else`` step-dispatch pattern does not cross-poison.

Known limits (documented in docs/ANALYSIS.md): loop bodies are walked
once in place, so a loop that donates at the bottom and reads at the
top is only caught when the read follows the donate in source order;
donated arguments that are expressions (``f(g(x))``) are not tracked.
"""

from __future__ import annotations

import ast

from theanompi_tpu.analysis.common import (
    Finding,
    SourceFile,
    dotted_name,
    int_tuple,
    make_key,
)

CHECK_ID = "TM201"

_JIT_NAMES = {"jax.jit", "jit"}
_DONATE_KWARGS = ("donate_argnums", "static_argnums_donate")


# ---------------------------------------------------------------------------
# Phase 1: the donating-callable registry
# ---------------------------------------------------------------------------


def _kw_positions(kw: ast.keyword) -> tuple[int, ...] | None:
    """Donated positions from one ``donate_argnums=`` keyword — the
    ONE evaluation rule both the decorator and assignment paths share.
    Literal specs evaluate exactly (``()`` -> None: the explicit
    no-donate spec must not register); IfExp takes the union of its
    branches; a dynamic spec (a helper like ``_donate_argnums(...)``)
    falls back to ``(0, 1)`` — the canonical state+staged-batch
    donation of the bsp/zero/fsdp step builders, erring toward
    tracking."""
    pos = int_tuple(kw.value)
    if pos is not None:
        return pos or None
    return (0, 1)


def _donated_positions(call: ast.Call) -> tuple[int, ...] | None:
    """Donated positions of a ``jax.jit(...)`` call; None when the
    call does not donate (or we cannot tell it does)."""
    if (dotted_name(call.func) or "") not in _JIT_NAMES:
        return None
    for kw in call.keywords:
        if kw.arg in _DONATE_KWARGS:
            return _kw_positions(kw)
    return None


def _decorator_positions(fn: ast.FunctionDef) -> tuple[int, ...] | None:
    for dec in fn.decorator_list:
        if not isinstance(dec, ast.Call):
            continue
        callee = dotted_name(dec.func) or ""
        if callee.split(".")[-1] == "partial" and dec.args:
            if (dotted_name(dec.args[0]) or "") in _JIT_NAMES:
                for kw in dec.keywords:
                    if kw.arg in _DONATE_KWARGS:
                        return _kw_positions(kw)
        p = _donated_positions(dec)
        if p:
            return p
    return None


def build_registry(files: list[SourceFile]) -> dict[str, tuple[int, ...]]:
    """callable name (simple or ``self.attr``) -> donated positions.

    Keys are intentionally unqualified: the package imports these
    functions by name (``from ...exchanger import easgd_apply_delta``),
    and a same-name collision between a donating and non-donating
    callable is itself worth flagging loudly rather than missing.
    """
    registry: dict[str, tuple[int, ...]] = {}
    factories: dict[str, tuple[int, ...]] = {}
    for src in files:
        for node in ast.walk(src.tree):
            if isinstance(node, ast.FunctionDef):
                pos = _decorator_positions(node)
                if pos:
                    registry[node.name] = pos
                # factory: returns a donating jax.jit wrapper
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Return) \
                            and isinstance(sub.value, ast.Call):
                        rpos = _donated_positions(sub.value)
                        if rpos:
                            factories[node.name] = rpos
            elif isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call):
                pos = _donated_positions(node.value)
                if pos:
                    for tgt in node.targets:
                        d = dotted_name(tgt)
                        if d:
                            registry[d] = pos
    # second pass: assignments calling a factory
    for src in files:
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call):
                callee = (dotted_name(node.value.func) or "").split(".")[-1]
                if callee in factories:
                    for tgt in node.targets:
                        d = dotted_name(tgt)
                        if d:
                            registry[d] = factories[callee]
    return registry


# ---------------------------------------------------------------------------
# Phase 2: per-function linear dataflow
# ---------------------------------------------------------------------------


_SCOPE_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef,
                ast.Lambda)


#: statements with nested statement lists — their HEADER expressions
#: are analyzed standalone and their bodies recursed, so no expression
#: is ever walked twice
_COMPOUND = (ast.If, ast.While, ast.For, ast.AsyncFor, ast.With,
             ast.AsyncWith, ast.Try)


def _walk_scope(node: ast.AST):
    """ast.walk pruned at nested scope boundaries."""
    for child in ast.iter_child_nodes(node):
        if isinstance(child, _SCOPE_NODES):
            continue
        yield child
        yield from _walk_scope(child)


def _loads_and_stores(stmt: ast.AST):
    loads: list[tuple[str, int]] = []
    stores: list[str] = []
    calls: list[ast.Call] = []
    nodes = [stmt] if isinstance(stmt, (ast.Name, ast.Attribute,
                                        ast.Call)) else []
    for node in nodes + list(_walk_scope(stmt)):
        if isinstance(node, ast.Call):
            calls.append(node)
        d = dotted_name(node) if isinstance(
            node, (ast.Name, ast.Attribute)) else None
        if d is None:
            continue
        ctx = getattr(node, "ctx", None)
        if isinstance(ctx, (ast.Store, ast.Del)):
            stores.append(d)
        elif isinstance(ctx, ast.Load):
            loads.append((d, node.lineno))
    return loads, stores, calls


def _covers(dead: str, path: str) -> bool:
    """True when a read of ``path`` touches the donated tree ``dead``
    (the path itself or anything under it)."""
    return path == dead or path.startswith(dead + ".")


def _revives(store: str, dead: str) -> bool:
    """A store to the path, a prefix, or a sub-path replaces the
    binding (or the container holding it) — the old buffers are no
    longer reachable through it."""
    return (store == dead or dead.startswith(store + ".")
            or store.startswith(dead + "."))


class _Flow:
    """Per-function dataflow state + the unit step shared by every
    block walk: ``dead`` maps a donated path to (callee, line)."""

    def __init__(self, src: SourceFile,
                 registry: dict[str, tuple[int, ...]], qual: str,
                 findings: list[Finding]):
        self.src = src
        self.registry = registry
        self.qual = qual
        self.findings = findings
        self.reported: set[str] = set()

    def unit(self, node: ast.AST, dead: dict) -> None:
        loads, stores, calls = _loads_and_stores(node)
        # 1. reads of already-dead paths (donations from PRIOR units
        # only — same-statement reads precede the call)
        for path, lineno in loads:
            for dpath, (callee, dline) in dead.items():
                if _covers(dpath, path) \
                        and not self.src.suppressed(lineno, CHECK_ID):
                    key = make_key(CHECK_ID, self.src.relpath,
                                   self.qual, dpath)
                    if key not in self.reported:
                        self.reported.add(key)
                        self.findings.append(Finding(
                            CHECK_ID, self.src.relpath, lineno,
                            f"'{path}' used after being donated to "
                            f"{callee}() at line {dline} "
                            f"(donate_argnums)", key))
        # 2. new donations (the call executes before any assignment of
        # its result, so donations register BEFORE stores revive —
        # ``x = f(x)`` with donated arg 0 leaves x alive)
        for call in calls:
            name = dotted_name(call.func)
            if name is None:
                continue
            pos = self.registry.get(name) \
                or self.registry.get(name.split(".")[-1])
            if not pos:
                continue
            for i in pos:
                if i < len(call.args):
                    d = dotted_name(call.args[i])
                    if d is not None:
                        dead[d] = (name, call.lineno)
        # 3. stores revive (a rebound name no longer reaches the
        # donated buffers)
        for store in stores:
            for dpath in [d for d in dead if _revives(store, d)]:
                del dead[dpath]

    def block(self, stmts: list[ast.stmt], dead: dict) -> None:
        """Walk one statement list, mutating ``dead`` in place.  If
        branches are MUTUALLY EXCLUSIVE: each runs on its own copy of
        the incoming state (a donation in one branch cannot kill a
        read in the other), and the fall-through state is the union of
        the branches' dead sets (the donation may have happened).
        Loop/with/try bodies stay linear, visited once in place."""
        for stmt in stmts:
            if isinstance(stmt, _SCOPE_NODES[:3]):
                continue  # nested scope: checked on its own walk
            if isinstance(stmt, ast.If):
                self.unit(stmt.test, dead)
                d_then = dict(dead)
                d_else = dict(dead)
                self.block(stmt.body, d_then)
                self.block(stmt.orelse, d_else)
                dead.clear()
                dead.update(d_else)
                dead.update(d_then)
            elif isinstance(stmt, (ast.For, ast.AsyncFor)):
                self.unit(stmt.iter, dead)
                self.unit(stmt.target, dead)
                self.block(stmt.body, dead)
                self.block(stmt.orelse, dead)
            elif isinstance(stmt, ast.While):
                self.unit(stmt.test, dead)
                self.block(stmt.body, dead)
                self.block(stmt.orelse, dead)
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    self.unit(item.context_expr, dead)
                self.block(stmt.body, dead)
            elif isinstance(stmt, ast.Try):
                self.block(stmt.body, dead)
                for handler in stmt.handlers:
                    self.block(handler.body, dead)
                self.block(stmt.orelse, dead)
                self.block(stmt.finalbody, dead)
            else:
                self.unit(stmt, dead)


def check_function(src: SourceFile, fn: ast.FunctionDef,
                   registry: dict[str, tuple[int, ...]],
                   qual: str) -> list[Finding]:
    findings: list[Finding] = []
    flow = _Flow(src, registry, qual, findings)
    flow.block(fn.body, {})
    return findings


def run(files: list[SourceFile],
        registry: dict[str, tuple[int, ...]] | None = None
        ) -> list[Finding]:
    registry = registry if registry is not None else build_registry(files)
    out: list[Finding] = []
    for src in files:
        # walk every function (methods included), each as its own scope
        stack: list[tuple[ast.AST, str]] = [(src.tree, "")]
        while stack:
            node, prefix = stack.pop()
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.ClassDef):
                    stack.append((child, f"{prefix}{child.name}."))
                elif isinstance(child, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                    qual = f"{prefix}{child.name}"
                    out.extend(check_function(src, child, registry, qual))
                    stack.append((child, f"{qual}."))
                else:
                    stack.append((child, prefix))
    return out
