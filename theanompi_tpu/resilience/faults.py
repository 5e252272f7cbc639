"""Deterministic fault injection.

A *fault plan* is a JSON list of fault specs; each spec names a
``site`` (where in the code the fault fires), coordinate matchers
(which event at that site), and an ``action``:

    [{"site": "worker_step", "worker": 1, "step": 3, "action": "raise"},
     {"site": "service_call", "op": "easgd_exchange", "nth": 3,
      "action": "drop"},
     {"site": "service_call", "op": "asgd_push_pull", "action": "delay",
      "delay_s": 0.2, "times": 2},
     {"site": "checkpoint", "epoch": 1, "action": "truncate"},
     {"site": "exchange", "kind": "easgd", "action": "raise"}]

Spec fields:

``site``
    required — matched literally against the call site's name.  The
    wired sites are ``worker_step`` (async-rule worker loops; coords
    ``rule``, ``worker``, ``step``), ``service_call``
    (``ServiceClient.call``; coord ``op``), ``checkpoint``
    (``Checkpointer`` manifest sync; coord ``epoch``),
    ``exchange`` (the in-process parameter stores; coord ``kind``),
    and the serving pair (docs/SERVING.md): ``serve_step`` (one
    replica batch execution; coords ``replica``, ``step`` — ``raise``
    fails the batch and exercises restart-from-export, ``delay``
    slows a replica so admission control trips) and ``serve_rpc``
    (the inference server's per-request handler; coord ``op``).
    Distributed ingest (docs/DESIGN.md "Distributed ingest") adds
    ``ingest_batch`` (reader-side batch assembly; coords ``reader``,
    ``epoch``, ``index`` — ``delay`` makes a reader a straggler;
    ``raise`` surfaces a typed server error that FAILS the trainer's
    stream fast — the client only retries typed ``Overloaded`` and
    only fails over on transport errors, so reader-death drills use a
    real kill, e.g. ``IngestProcessGroup.kill_reader``) and
    ``ingest_pull`` (trainer-side fetch; coords ``index``, ``rank`` —
    ``raise`` injects a trainer-side stream failure).  Disaggregated serving (docs/SERVING.md "Disaggregated
    serving") adds ``router_route`` (the front-door router's
    per-request handler; coord ``op`` — ``raise`` fails a client
    stream at the router before any backend is touched) and
    ``page_migrate`` (the KV-page migration legs; coords ``side`` =
    ``export``/``adopt`` and, on the adopt side, ``replica`` —
    ``raise`` on ``export`` sheds the prefill, on ``adopt`` it fails
    the decode leg and exercises router failover).
``action``
    ``raise`` (default) raises :class:`FaultInjected` at the site;
    ``delay`` sleeps ``delay_s`` seconds (default 0.1) then lets the
    call proceed; any other string (``drop``, ``truncate``) is
    returned to the call site, which implements the effect —
    ``ServiceClient`` turns ``drop`` into a synthesized transport
    error (exercising the reconnect path), the checkpointer turns
    ``truncate`` into a half-truncated file in the just-written epoch
    dir.
``nth``
    1-based: fire on the nth *matching* event (default 1 — the first).
``times``
    how many consecutive matching events fire from ``nth`` on
    (default 1); ``-1`` = every matching event forever.

Any other key is a coordinate matcher: the spec matches only events
whose ``fire(site, key=value, ...)`` call carries an equal value
(compared as strings, so ``"worker": 1`` and ``"worker": "1"`` are the
same).  A coordinate the call site doesn't pass never matches.

Activation: ``THEANOMPI_TPU_FAULTS`` (inline JSON or a path to a JSON
file) is read once at import, so every process of a run — launcher,
workers, a tmserver — picks the plan up from its environment; the
launcher's ``--fault-plan`` flag re-reads it after setting the env
var.  Tests use :func:`install` / :func:`clear` directly.

No-op discipline (the contract every hot loop relies on): with no
plan installed, :func:`fire` returns after ONE ``is None`` check and
allocates nothing.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Any

from theanompi_tpu import monitor

ENV_VAR = "THEANOMPI_TPU_FAULTS"

#: spec keys that are control fields, not coordinate matchers
_CONTROL_KEYS = frozenset({"site", "action", "nth", "times", "delay_s"})


class FaultInjected(RuntimeError):
    """Raised by a ``raise``-action fault.  A plain RuntimeError
    subclass so the supervised-recovery path treats it exactly like a
    real worker crash — the point of injecting it."""


class _Spec:
    """One compiled fault spec with its private match counter."""

    def __init__(self, raw: dict):
        if not isinstance(raw, dict) or "site" not in raw:
            raise ValueError(f"fault spec needs a 'site' key: {raw!r}")
        self.site = str(raw["site"])
        self.action = str(raw.get("action", "raise"))
        self.nth = int(raw.get("nth", 1))
        self.times = int(raw.get("times", 1))
        self.delay_s = float(raw.get("delay_s", 0.1))
        self.coords = {k: str(v) for k, v in raw.items()
                       if k not in _CONTROL_KEYS}
        if self.nth < 1:
            raise ValueError(f"fault spec nth must be >= 1: {raw!r}")
        self._matched = 0

    def matches(self, site: str, coords: dict[str, Any]) -> bool:
        if site != self.site:
            return False
        for k, want in self.coords.items():
            if k not in coords or str(coords[k]) != want:
                return False
        return True

    def should_fire(self) -> bool:
        """Count a matching event; True while inside [nth, nth+times)."""
        self._matched += 1
        if self._matched < self.nth:
            return False
        return self.times < 0 or self._matched < self.nth + self.times


class FaultPlan:
    """A compiled, thread-safe fault plan (see module docstring)."""

    def __init__(self, specs: list[dict]):
        self._specs = [_Spec(s) for s in specs]
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._specs)

    def fire(self, site: str, **coords) -> str | None:
        """Match + perform the first firing spec; None when nothing
        fires.  ``raise`` raises here; ``delay`` sleeps here; other
        actions are returned for the call site to implement."""
        with self._lock:
            action = None
            for spec in self._specs:
                if spec.matches(site, coords) and spec.should_fire():
                    action = spec.action
                    break
        if action is None:
            return None
        monitor.inc("resilience/faults_injected_total",
                    site=site, action=action)
        print(f"[resilience] FAULT {action} at {site} "
              f"{coords}", file=sys.stderr, flush=True)
        if action == "raise":
            raise FaultInjected(f"injected fault at {site} {coords}")
        if action == "delay":
            time.sleep(spec.delay_s)
        return action


#: the active plan — None is the strict no-op state
_plan: FaultPlan | None = None


def enabled() -> bool:
    return _plan is not None


def fire(site: str, **coords) -> str | None:
    """The instrumented-site entry point.  With no plan installed this
    is ONE attribute read + ``is None`` check — the zero-cost path."""
    plan = _plan
    if plan is None:
        return None
    return plan.fire(site, **coords)


def load(text_or_path: str) -> FaultPlan:
    """Parse a plan from inline JSON or a path to a JSON file."""
    text = text_or_path.strip()
    if not text.startswith(("[", "{")):
        with open(text_or_path) as f:
            text = f.read()
    specs = json.loads(text)
    if isinstance(specs, dict):
        specs = [specs]
    return FaultPlan(specs)


def install(plan_or_specs: FaultPlan | list[dict] | str) -> FaultPlan:
    """Activate a plan (replacing any previous one); returns it."""
    global _plan
    if isinstance(plan_or_specs, FaultPlan):
        plan = plan_or_specs
    elif isinstance(plan_or_specs, str):
        plan = load(plan_or_specs)
    else:
        plan = FaultPlan(plan_or_specs)
    _plan = plan
    return plan


def clear() -> None:
    """Deactivate fault injection (back to the strict no-op state)."""
    global _plan
    _plan = None


def install_from_env() -> FaultPlan | None:
    """(Re)read ``THEANOMPI_TPU_FAULTS``; None + cleared when unset.
    Called once at import and again by the launcher after it exports
    ``--fault-plan`` (the package may already be imported by then)."""
    raw = os.environ.get(ENV_VAR)
    if not raw:
        clear()
        return None
    plan = install(raw)
    print(f"[resilience] fault plan active: {len(plan)} spec(s) "
          f"from ${ENV_VAR}", file=sys.stderr, flush=True)
    return plan


install_from_env()
