"""Which part of the step each device op belongs to.

A profile names a device op by its HLO instruction (``fusion.765``,
``while.288``): XLA's numbering of one compilation, which says nothing
of the mechanism that ran and cannot be compared across two builds.
The PROGRAM knows more: it traced the step under ``jax.named_scope``s
(``nemotron_h/mamba/ssd``, ``bsp/update``), flax module names
(``Block_3/mlp_up``) and JAX's transforms (``jvp``, ``transpose``,
``checkpoint/rematted_computation``), and XLA keeps all of that as
``metadata={op_name="..."}`` on every instruction of the optimized
module (``compiled.as_text()``).  This module reads it back:

* ``parse(op_name) -> (phase, scope)``: ``phase`` is ``forward``,
  ``backward`` or ``recompute``; ``scope`` the ``/``-joined user scopes
  and module names, with everything that is JAX's own dropped;
* ``scope_map(hlo_text) -> {instruction name: (phase, scope)}`` over
  every instruction of every computation of the module.  A fusion
  carries ONE ``op_name``, its root's: an op fused into another scope's
  fusion is counted there (a profile's ``tf_op`` field shows the same);
* ``step_scopes()``: the map of the step program this process last
  dispatched, built on demand and cached: one lowering and one compile
  under a cache key that includes the metadata (the persistent cache's
  own key is blind to it, so the executable a dispatch LOADED can carry
  an older build's ``op_name``s).  ``TpuModel`` only
  NOTES what it dispatched (``note_step``: the function and its
  arguments' shapes, dtypes and shardings, once); nothing is lowered,
  compiled or parsed unless somebody asks;
* ``python -m theanompi_tpu.monitor.scopes <profile dir>``: device
  milliseconds a step by scope and phase, from the directory's
  ``.xplane.pb`` (read with ``jax.profiler.ProfileData``: JAX alone)
  and the ``step_scopes.json`` that ``utils/profiling.py StepProfiler``
  leaves beside it.

docs/OBSERVABILITY.md "Device-trace names" lists the scopes, the kernel
names and the benchmark metric that reads each.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
import weakref
from typing import Callable

PHASES = ("forward", "backward", "recompute")
SCOPES_FILE = "step_scopes.json"

#: wrappers whose argument is a function's name, not a scope: dropped
#: whole (``jit(shard_step)``, ``jit(relu)``, ``pjit(...)``)
FUNCTION_WRAPPERS = frozenset({"jit", "pjit", "shard_map"})
#: JAX's transforms: the wrapper goes, the scopes inside it stay
#: (``jvp(m/loss)`` -> ``m/loss``); ``transpose`` marks the backward pass
TRANSFORM_WRAPPERS = frozenset({"jvp", "transpose", "vmap"})
#: JAX's own tokens inside a name stack: control flow, closed calls,
#: rematerialisation, custom derivatives, a bare ``shard_map``.
#: ``rematted_computation`` marks recomputation before it is dropped.
#: What the five benchmark configurations' step programs hold, on the
#: CPU and on the chip; tests/test_step_scopes.py pins the list and
#: holds every scope token of those programs against it
DROPPED_TOKENS = frozenset({
    "while", "body", "cond", "closed_call", "checkpoint",
    "rematted_computation", "custom_vjp_call", "custom_jvp_call",
    "shard_map",
})
#: a ``lax.switch`` / ``cond`` branch, and the subscripts ``jnp.einsum``
#: names its product by (``bqhd,bkhd->bhqk``): JAX's own as well
BRANCH_TOKEN = re.compile(r"^branch_\d+_fun$")
EINSUM_TOKEN = re.compile(r"->")
REMAT_TOKEN = "rematted_computation"

_WRAPPED = re.compile(r"^([A-Za-z_][\w.]*)\((.*)\)$", re.S)
#: ``  ROOT %name.3 = f32[...] opcode(...), ..., metadata={... op_name="..."``
_INSTRUCTION = re.compile(
    r"^[ \t]*(?:ROOT[ \t]+)?%?([\w.\-]+)[ \t]*=[ \t]"
    r".*?\bop_name=\"((?:[^\"\\]|\\.)*)\"", re.M)


def _split(text: str) -> list[str]:
    """``text`` cut at the ``/`` that stand outside every parenthesis."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(text):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            parts.append(text[start:i])
            start = i + 1
    parts.append(text[start:])
    return parts


def _walk(tokens: list[str], scope: list[str], flags: set) -> None:
    for token in tokens:
        wrapped = _WRAPPED.match(token)
        if wrapped and wrapped.group(1) in FUNCTION_WRAPPERS:
            continue
        if wrapped and wrapped.group(1) in TRANSFORM_WRAPPERS:
            flags.add(wrapped.group(1))
            inner: list[str] = []
            _walk(_split(wrapped.group(2)), inner, flags)
            # a checkpointed layer's backward, and a ``jax.vjp`` inside
            # a custom backward, re-enter the transform they are under
            # and say the op's own stack again from its root
            # (``transpose(jvp(Net))/jvp(Net)/checkpoint/Layer_3/...``):
            # the op's own stack stands, said once
            if inner and scope[:len(inner)] == inner:
                del scope[len(inner):]
            else:
                scope.extend(inner)
            continue
        if token == REMAT_TOKEN:
            flags.add(REMAT_TOKEN)
        if (not token or token in DROPPED_TOKENS
                or BRANCH_TOKEN.match(token) or EINSUM_TOKEN.search(token)):
            continue
        scope.append(token)


def parse(op_name: str) -> tuple[str, str]:
    """``(phase, scope)`` of one instruction's ``op_name``.

    Anything under ``rematted_computation`` is ``recompute``; else
    anything under ``transpose(`` is ``backward``; else ``forward``.
    The last token is the primitive's name and is dropped with JAX's
    own; what is left, in order, is the scope ('' where there is none).
    Of several names joined by ``;`` (XLA merged two instructions) the
    first stands."""
    first = op_name.split(";", 1)[0]
    tokens = _split(first)[:-1]  # the trailing primitive
    scope: list[str] = []
    flags: set = set()
    _walk(tokens, scope, flags)
    if REMAT_TOKEN in flags:
        phase = "recompute"
    elif "transpose" in flags:
        phase = "backward"
    else:
        phase = "forward"
    return phase, "/".join(scope)


def scope_map(hlo_text: str) -> dict[str, tuple[str, str]]:
    """``{instruction name: (phase, scope)}`` of an optimized HLO
    module's text, for every instruction that carries an ``op_name``
    (fusions and the instructions inside them, ``while`` bodies,
    branches: every computation of the module)."""
    out: dict[str, tuple[str, str]] = {}
    parsed: dict[str, tuple[str, str]] = {}
    for m in _INSTRUCTION.finditer(hlo_text):
        op_name = m.group(2)
        if op_name not in parsed:
            parsed[op_name] = parse(op_name)
        out[m.group(1)] = parsed[op_name]
    return out


# -- what the process last dispatched --------------------------------------


class _Noted:
    """One noted dispatch: the jitted function (weakly: it dies with the
    model that built it), its abstract arguments, and the map once
    somebody asked for it."""

    __slots__ = ("fn", "args", "map")

    def __init__(self, fn, args):
        self.fn = weakref.ref(fn)
        self.args = args
        self.map: dict | None = None


_last: _Noted | None = None


def _abstract(x):
    """Shape, dtype and, where the array is committed to one, sharding:
    what ``jit`` keys its lowering on.  An uncommitted array (a fresh
    ``jax.random`` key) goes where the computation goes, and says so by
    carrying no sharding; so does a NumPy leaf (a state restored from a
    checkpoint or handed over by an asynchronous rule holds some), and a
    key array has no ``weak_type``."""
    import jax

    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, weak_type=getattr(x, "weak_type", False),
        sharding=x.sharding if getattr(x, "committed", False) else None)


def note_step(fn: Callable, args: tuple) -> Callable:
    """Remember that the jitted ``fn`` was dispatched on ``args``
    (shapes, dtypes and shardings only: no array is kept alive, and the
    function only weakly).  Returns ``fn``, which the caller keeps to
    know it has been noted.  Costs one tree map; lowers, compiles and
    parses nothing."""
    import jax

    global _last
    _last = _Noted(fn, jax.tree.map(_abstract, args))
    return fn


#: ``Lowered.compile`` hands back the executable the dispatch made, and
#: that one may have come from the persistent cache, whose key is blind
#: to metadata: the instructions of THIS program under the ``op_name``s
#: of whichever build compiled them first.  A compiler option that is
#: its own default changes nothing the compiler does and makes JAX
#: compile (or load) anew; ``METADATA_IN_KEY`` makes that compile's
#: cache entry this source's own
FRESH_COMPILE = {"xla_dump_disable_metadata": False}
METADATA_IN_KEY = "jax_compilation_cache_include_metadata_in_key"


def compile_with_own_metadata(fn: Callable, args: tuple):
    """``fn`` lowered at ``args`` and compiled so that the result's
    ``op_name``s are the ones THIS source traced: not the executable in
    JAX's memory, under a cache key that includes the metadata.  The same
    HLO and the same options as the dispatch but for a no-op, so the same
    instructions under the same names.  One real compile the first time,
    a load from the persistent cache afterwards."""
    import jax

    before = getattr(jax.config, METADATA_IN_KEY)
    jax.config.update(METADATA_IN_KEY, True)
    try:
        return fn.lower(*args).compile(compiler_options=FRESH_COMPILE)
    finally:
        jax.config.update(METADATA_IN_KEY, before)


def step_scopes() -> dict[str, tuple[str, str]] | None:
    """The map of the step program this process last dispatched; None
    where no model has stepped (or the one that did is gone).  The first
    call lowers the noted function at the noted abstract arguments,
    compiles it (``compile_with_own_metadata``) and parses the text; the
    map is kept."""
    noted = _last
    fn = noted.fn() if noted is not None else None
    if not hasattr(fn, "lower"):  # gone, or no jitted function
        return None
    if noted.map is None:
        noted.map = scope_map(
            compile_with_own_metadata(fn, noted.args).as_text())
    return noted.map


def forget_for_tests() -> None:
    global _last
    _last = None


# -- the operator's account -------------------------------------------------


def write_step_scopes(directory: str) -> str | None:
    """``step_scopes()`` as ``<directory>/step_scopes.json``, for the
    account below; None (and no file) where there is no map.  Scopes are
    written once and ops point at them: ``{"scopes": [[phase, scope],
    ...], "ops": {instruction name: index}}``."""
    mapped = step_scopes()
    if mapped is None:
        return None
    index: dict[tuple[str, str], int] = {}
    ops = {name: index.setdefault(entry, len(index))
           for name, entry in mapped.items()}
    path = os.path.join(directory, SCOPES_FILE)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump({"scopes": [list(entry) for entry in index],
                   "ops": ops}, f)
    os.replace(tmp, path)
    return path


def read_step_scopes(path: str) -> dict[str, tuple[str, str]]:
    with open(path) as f:
        stored = json.load(f)
    entries = [tuple(entry) for entry in stored["scopes"]]
    return {name: entries[i] for name, i in stored["ops"].items()}


#: ops that hold other ops: their time is their contents', counted there
CONTAINER_OPCODES = frozenset({"while", "conditional", "call"})
_OPCODE = re.compile(r"\s([a-z][\w-]*)\(")
_INDEX = re.compile(r"_\d+(?=/|$)")


def _name_and_opcode(text: str) -> tuple[str, str]:
    """A device event is named by its whole HLO text (``%fusion.12 =
    (shapes) fusion(operands), ...``): the instruction's name and its
    opcode ('' where the event is a bare name)."""
    name, _, rest = text.partition(" = ")
    opcode = _OPCODE.search(" " + rest) if rest else None
    return name.lstrip("%"), opcode.group(1) if opcode else ""


def account(xplane_path: str, mapped: dict) -> dict:
    """Device milliseconds a step by (scope, phase) on the lowest-
    numbered chip of a profile: ``{"steps", "program", "busy_ms",
    "rows": {(scope, phase): ms}}``.  Layer indices are folded
    (``Layer_3`` -> ``Layer_*``); an op the map does not know is under
    the scope ``(not in the map)``, a container op under none.  The
    steps are the executions of the longest-running program on the
    plane's "XLA Modules" line."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    planes = sorted((p for p in data.planes
                     if re.match(r"^/device:TPU:\d+$", p.name)),
                    key=lambda p: int(p.name.rsplit(":", 1)[1]))
    if not planes:
        raise SystemExit(f"{xplane_path}: no /device:TPU plane (a profile "
                         "taken without a TPU holds no device ops)")
    rows: dict[tuple[str, str], float] = {}
    modules: dict[str, list] = {}
    busy = 0.0
    for line in planes[0].lines:
        if line.name == "XLA Modules":
            for ev in line.events:
                entry = modules.setdefault(ev.name, [0, 0.0])
                entry[0] += 1
                entry[1] += ev.duration_ns
        elif line.name == "XLA Ops":
            for ev in line.events:
                name, opcode = _name_and_opcode(ev.name)
                if opcode in CONTAINER_OPCODES:
                    continue
                phase, scope = mapped.get(name, ("", "(not in the map)"))
                key = (_INDEX.sub("_*", scope), phase)
                rows[key] = rows.get(key, 0.0) + ev.duration_ns
                busy += ev.duration_ns
    program = max(modules, key=lambda m: modules[m][1], default=None)
    steps = modules[program][0] if program else 1
    return {"steps": steps, "program": program,
            "busy_ms": busy / steps / 1e6,
            "rows": {key: ns / steps / 1e6 for key, ns in rows.items()}}


def format_account(acc: dict, scope: str | None = None) -> str:
    """The table of ``account``: one row a scope, a column a phase,
    milliseconds a step and the share of the device's busy time; with
    ``scope`` (a regular expression, searched) only the rows that match
    and their sum, which is what a benchmark metric of that pattern
    reads."""
    rx = re.compile(scope) if scope else None
    by_scope: dict[str, dict[str, float]] = {}
    for (name, phase), ms in acc["rows"].items():
        if rx is None or rx.search(name):
            by_scope.setdefault(name, {})[phase] = ms
    busy = acc["busy_ms"] or 1.0
    out = [f"# {acc['program']}: {acc['steps']} step(s), "
           f"{acc['busy_ms']:.3f} ms of leaf ops a step",
           f"{'forward':>10} {'backward':>10} {'recompute':>10} "
           f"{'ms/step':>10} {'%':>6}  scope"]

    def row(label: str, phases: dict[str, float]) -> str:
        all_ms = sum(phases.values())
        cells = " ".join(f"{phases.get(p, 0.0):10.3f}" for p in PHASES)
        return f"{cells} {all_ms:10.3f} {100 * all_ms / busy:6.2f}  {label}"

    ranked = sorted(by_scope.items(), key=lambda kv: -sum(kv[1].values()))
    sums: dict[str, float] = {}
    for _, phases in ranked:
        for phase, ms in phases.items():
            sums[phase] = sums.get(phase, 0.0) + ms
    out.append(row(f"TOTAL of {len(ranked)} scope(s)"
                   + (f" matching {scope!r}" if scope else ""), sums))
    out.extend(row(name or "(no scope)", phases) for name, phases in ranked)
    return "\n".join(out)


def main(argv: list[str] | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m theanompi_tpu.monitor.scopes",
        description="Device ms a step by scope and phase, from a profile "
                    "directory's .xplane.pb and step_scopes.json.")
    ap.add_argument("profile_dir")
    ap.add_argument("--scope", help="regular expression: only the scopes "
                    "it matches, and their sum")
    args = ap.parse_args(argv)
    found = sorted(glob.glob(os.path.join(args.profile_dir, "**",
                                          "*.xplane.pb"), recursive=True))
    scopes_path = os.path.join(args.profile_dir, SCOPES_FILE)
    if not found or not os.path.exists(scopes_path):
        print(f"{args.profile_dir}: needs an .xplane.pb (found "
              f"{len(found)}) and {SCOPES_FILE} (THEANOMPI_TPU_PROFILE "
              "writes both)", file=sys.stderr)
        return 1
    acc = account(found[-1], read_step_scopes(scopes_path))
    print(format_account(acc, args.scope))
    return 0


if __name__ == "__main__":
    sys.exit(main())
