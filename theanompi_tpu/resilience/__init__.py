"""theanompi_tpu.resilience — fault injection, retry/backoff,
supervised recovery, and checkpoint integrity.

The monitor subsystem (PR 1) *detects* stalls, stragglers, and crashes;
this subsystem *acts* on them (docs/RESILIENCE.md is the operator's
reference).  Four modules, one discipline:

* **faults** (``faults.py``) — a deterministic, config/env-driven
  fault-injection plane: kill worker rank R at step N, drop/delay the
  Kth ServiceClient RPC, truncate a just-written checkpoint, raise in
  a server exchange hook.  Activated by ``THEANOMPI_TPU_FAULTS`` (a
  JSON fault plan, inline or a file path) or ``faults.install(...)``;
  a strict zero-cost no-op when disabled — every instrumented site
  pays ONE ``is None`` check and allocates nothing (tested:
  ``tests/test_resilience.py::test_faults_disabled_is_noop``, the same
  discipline as the monitor's zero-write guarantee).
* **retry** (``retry.py``) — a reusable retry/backoff policy
  (exponential + jitter, deadline, retryable-exception classifier)
  adopted by ``ServiceClient.call`` (reconnect-with-backoff through a
  parameter-service restart) and ``Checkpointer.restore`` (transient
  read I/O; the write *fence* deliberately stays retry-free — orbax
  clears its stored async-write error after raising it once, so a
  retried fence would mask data loss).
* **supervisor** (``supervisor.py``) — bounded restart-from-center
  supervision for the async rules' worker threads, consuming the
  monitor's StragglerDetector signal; aborts when the worker quorum is
  lost.  GOSGD workers are not restartable (no center to restart
  from) and fall back to the hub's existing ``deactivate`` path.
* **recovery** (``recovery.py``) — checkpoint integrity (a manifest +
  per-file sha256 digest written alongside every completed Orbax save)
  and verified restore: a corrupt latest checkpoint falls back to the
  previous kept epoch instead of killing the resume.

Enablement contract: fault injection is OFF unless a plan is
installed; retry/recovery are *always-on behaviors of their host
components* (a reconnect only happens on a transport error, a manifest
only costs I/O at checkpoint-fence time) and add nothing to the BSP
hot path.  Supervision is OFF unless a rule is given
``max_restarts > 0`` (the default preserves the reference's fail-fast
a-dead-worker-kills-the-job semantics, SURVEY.md §5.3).
"""

from __future__ import annotations

from theanompi_tpu.resilience import faults, recovery, retry, supervisor
from theanompi_tpu.resilience.faults import ENV_VAR, FaultInjected, FaultPlan
from theanompi_tpu.resilience.retry import RetryPolicy
from theanompi_tpu.resilience.supervisor import WorkerSupervisor

__all__ = [
    "ENV_VAR", "FaultInjected", "FaultPlan", "RetryPolicy",
    "WorkerSupervisor", "faults", "recovery", "retry", "supervisor",
]
