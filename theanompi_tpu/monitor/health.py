"""Health reporting — heartbeat files, a stall watchdog, straggler
detection.

The r04 bench spent 240 s wedged in device init with zero structured
signal about where; its only output was silence.  The heartbeat closes
that class of blind spot: a reporter thread writes a small per-rank
JSON file every few seconds carrying (phase, step, seconds since last
progress), so any outside observer — an operator, a cluster
babysitter — can distinguish "slow" from "stuck" without
attaching a debugger.  The same thread runs the watchdog: when no
progress has been reported for ``stall_after`` seconds it names the
stuck phase on stderr (once per stall episode, not every tick) and
counts it in the registry.

``StragglerDetector`` is the multi-worker counterpart: the async rules
feed it per-worker step durations; a worker whose recent median step
time exceeds ``factor`` x the cross-worker rolling median is flagged.
Flags are edge-triggered (counted and logged on transition, cleared on
recovery) so a persistently slow worker doesn't spam.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import threading
import time
from collections import deque

from theanompi_tpu.monitor.registry import MetricsRegistry, atomic_write_text


class HeartbeatReporter:
    """Background thread: heartbeat file + stall watchdog + periodic
    metrics-snapshot flush.

    The heartbeat file ``heartbeat_rank{rank}.json`` is rewritten
    atomically every ``interval`` seconds:

        {"rank": 0, "pid": 1234, "phase": "train", "step": 812,
         "progress_age_s": 0.4, "stalled": false, "uptime_s": 93.1,
         "written": 1754200000.0, "workers": {"1": {...}}}

    Freshness IS the health signal: a reader that finds ``written``
    older than ~3 intervals knows the process is gone or the GIL is
    held; ``progress_age_s``/``stalled`` separate alive-but-stuck from
    making-progress.  ``progress()`` is the hot-path call (a few plain
    attribute writes under a lock held for nanoseconds) — rules call it
    once per step."""

    def __init__(self, run_dir: str, rank: int = 0,
                 registry: MetricsRegistry | None = None,
                 interval: float = 5.0, stall_after: float = 60.0,
                 snapshot_path: str | None = None,
                 suffix: str | None = None):
        self.run_dir = run_dir
        self.rank = rank
        self.registry = registry
        self.interval = interval
        self.stall_after = stall_after
        self.snapshot_path = snapshot_path
        # ``suffix`` distinguishes co-located processes that are NOT
        # ranks of one training session (a tmserver next to a trainer
        # would otherwise both write heartbeat_rank0.json)
        self.path = os.path.join(
            run_dir, f"heartbeat_{suffix or f'rank{rank}'}.json")
        self._lock = threading.Lock()
        self._t_start = time.monotonic()
        self._phase = "startup"
        self._step: int | None = None
        self._last_progress = time.monotonic()
        self._workers: dict[str, dict] = {}
        self._stalled = False
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    # -- hot path ------------------------------------------------------

    def progress(self, phase: str | None = None, step: int | None = None,
                 worker: int | None = None) -> None:
        """Record that work advanced.  ``worker`` scopes the update to
        one async-rule worker thread; rank-level phase/step otherwise."""
        now = time.monotonic()
        with self._lock:
            self._last_progress = now
            if phase is not None:
                # rank-level phase updates even for worker-scoped
                # progress: async-rule workers are the ONLY progress
                # source there, and a heartbeat stuck on 'startup'
                # after hours of training would misname every stall
                self._phase = phase
            if worker is None:
                if step is not None:
                    self._step = step
            else:
                w = self._workers.setdefault(str(worker), {})
                if phase is not None:
                    w["phase"] = phase
                if step is not None:
                    w["step"] = step
                w["progress_age_s"] = 0.0
                w["_last"] = now
            if self._stalled:
                self._stalled = False
                if self.registry is not None:
                    self.registry.inc("health/stall_recoveries_total")

    # -- lifecycle -----------------------------------------------------

    def start(self) -> "HeartbeatReporter":
        os.makedirs(self.run_dir, exist_ok=True)
        self.write_once()  # a file exists from t=0, not t=interval
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=f"monitor-heartbeat-r{self.rank}")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2 * self.interval)
            self._thread = None
        self.write_once()  # final state on disk

    def _run(self) -> None:
        while not self._stop.wait(self.interval):
            self._check_stall()
            self.write_once()
            if self.registry is not None and self.snapshot_path:
                try:
                    self.registry.write_jsonl(self.snapshot_path)
                except OSError:
                    pass  # a full disk must not kill the training loop

    # -- watchdog ------------------------------------------------------

    def _check_stall(self) -> None:
        with self._lock:
            age = time.monotonic() - self._last_progress
            phase, step, was = self._phase, self._step, self._stalled
            if age > self.stall_after:
                self._stalled = True
        if age > self.stall_after and not was:
            # edge-triggered: name the stuck phase ONCE per episode
            print(f"[monitor] WATCHDOG rank {self.rank}: no progress for "
                  f"{age:.0f}s (phase={phase!r}, step={step}) — "
                  f"stall threshold {self.stall_after:.0f}s", file=sys.stderr,
                  flush=True)
            if self.registry is not None:
                self.registry.inc("health/stalls_total", phase=phase)

    # -- the file ------------------------------------------------------

    def state(self) -> dict:
        now = time.monotonic()
        with self._lock:
            workers = {
                k: {kk: vv for kk, vv in w.items() if kk != "_last"}
                | {"progress_age_s": round(now - w.get("_last", now), 3)}
                for k, w in self._workers.items()
            }
            return {
                "rank": self.rank,
                "pid": os.getpid(),
                "phase": self._phase,
                "step": self._step,
                "progress_age_s": round(now - self._last_progress, 3),
                "stalled": self._stalled,
                "uptime_s": round(now - self._t_start, 3),
                "written": time.time(),
                "workers": workers,
            }

    def write_once(self) -> str:
        try:
            atomic_write_text(self.path, json.dumps(self.state()))
        except OSError:
            pass
        return self.path


class StragglerDetector:
    """Rolling-median straggler detection over per-worker step times.

    ``observe(rank, seconds)`` returns True while ``rank`` is flagged:
    its own recent median exceeds ``factor`` x the median of the OTHER
    workers' recent steps.  The fleet median must exclude the
    candidate's own window — a pooled median would be dragged up by
    the straggler itself (with 2 equal windows a worker can never
    exceed ``factor`` x the pooled median, however slow it is).
    Needs ``min_samples`` observations from the flagged worker and at
    least 2 active workers before flagging (a solo worker has no peers
    to lag behind)."""

    def __init__(self, factor: float = 2.0, window: int = 32,
                 min_samples: int = 8,
                 registry: MetricsRegistry | None = None):
        self.factor = factor
        self.min_samples = min_samples
        self.registry = registry
        self._lock = threading.Lock()
        self._window = window
        self._times: dict[int, deque[float]] = {}
        self._flagged: set[int] = set()

    def observe(self, rank: int, seconds: float) -> bool:
        with self._lock:
            dq = self._times.setdefault(
                rank, deque(maxlen=self._window))
            dq.append(float(seconds))
            if len(self._times) < 2 or len(dq) < self.min_samples:
                return rank in self._flagged
            own = statistics.median(dq)
            others = [t for r, d in self._times.items()
                      if r != rank for t in d]
            peer_med = statistics.median(others)
            is_straggler = (peer_med > 0
                            and own > self.factor * peer_med)
            was = rank in self._flagged
            if is_straggler and not was:
                self._flagged.add(rank)
                if self.registry is not None:
                    self.registry.inc("health/straggler_flags_total",
                                      worker=rank)
                print(f"[monitor] STRAGGLER worker {rank}: median step "
                      f"{own * 1e3:.1f}ms vs peer median "
                      f"{peer_med * 1e3:.1f}ms "
                      f"(threshold {self.factor:g}x)",
                      file=sys.stderr, flush=True)
            elif not is_straggler and was:
                self._flagged.discard(rank)
            return is_straggler

    def stragglers(self) -> list[int]:
        with self._lock:
            return sorted(self._flagged)
