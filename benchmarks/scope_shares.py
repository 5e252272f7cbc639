"""Shares of device-busy time by the part of the step an op belongs to:
the helper behind the ``step program`` layer's scope and phase metrics
(``layer_metrics/{scope_coverage,backward_share,update_share,
recompute_share,loss_share,ssd_share,expert_layer_share}.py``).

The trace names an op by its HLO instruction (``fusion.765``); which
scope and phase that instruction was traced under is the PROGRAM's to
say: ``theanompi_tpu/monitor/scopes.py step_scopes()`` gives ``{name:
(phase, scope)}`` for the step program this process dispatched, from
the ``op_name`` metadata of its own compiled HLO (taken the way
``expert_matmul_roofline_share.py`` takes ``routing_log``: in-process,
after the window).  It lowers and compiles the step once, here, after
the window and outside every traced segment; the seconds it took go to
the earlier line as ``phases.step_scopes_s``.

A fusion carries one ``op_name``, its root's, so an op fused across a
scope's border is counted on the root's side.  Container ops (``while``,
``conditional``, ``call``) are never counted: their time is the time of
the ops inside them, which the trace lists too, and a backward ``while``
holds recomputed forwards.
"""

import importlib
import re
import time

#: the opcodes (first word of a trace category) of ops that hold others
CONTAINERS = frozenset({"while", "conditional", "call"})


def step_map(run):
    """The program's map of its step, or None: a program without the
    module (the parent of the PR that brought it), or no step noted."""
    try:
        scopes = importlib.import_module("theanompi_tpu.monitor.scopes")
    except ImportError:
        return None
    t0 = time.perf_counter()
    mapped = scopes.step_scopes()
    # the first reader pays the lowering and the compile; the rest read
    # the program's cached map in microseconds
    run.phases.setdefault("step_scopes_s", time.perf_counter() - t0)
    return mapped


def share(run, phase=None, scope=None):
    """Percent of device-busy time (``trace_lib.busy_ns``'s union, the
    denominator of every ``*_share``) in LEAF ops of the traced window
    whose mapped phase equals ``phase`` and whose mapped scope the
    regular expression ``scope`` finds (None: any); mean over the chips.
    An op the map does not hold is no hit.  None where there is no
    trace, no device or no map (a dry run asks the program for nothing)."""
    if run.trace is None or not run.on_device:
        return None
    mapped = step_map(run)
    if mapped is None:
        return None
    lib, window = run.trace_lib, run.trace.window
    rx = re.compile(scope) if scope is not None else None
    shares = []
    for ops in run.trace.device_ops.values():
        hit = []
        for name, category, start, end in ops:
            if category.split(" ", 1)[0] in CONTAINERS:
                continue
            op_phase, op_scope = mapped.get(name, (None, None))
            if op_phase is None or (phase is not None and op_phase != phase):
                continue
            if rx is None or rx.search(op_scope):
                hit.append((start, end))
        busy = lib.total(lib.union(lib.clip(
            [(start, end) for _, _, start, end in ops], window)))
        shares.append(lib.total(lib.union(lib.clip(hit, window))) / busy
                      if busy else 0.0)
    return 100.0 * sum(shares) / len(shares)
