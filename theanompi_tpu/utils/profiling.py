"""Profiler integration — jax.profiler traces around the training loop.

The reference leaned on Theano's profiler plus the Recorder's wall
timers (SURVEY.md §5.1 — mount empty, no file:line).  The TPU
equivalent is XLA's own tracer: ``StepProfiler`` captures the first N
steps of a session into a TensorBoard-loadable trace (HLO timelines,
ICI collectives, host/device overlap), and per-step
``jax.profiler.StepTraceAnnotation`` markers (emitted by
``TpuModel.train_iter``) label each iteration in the timeline.

Enable by env (``THEANOMPI_TPU_PROFILE=/dir`` plus optional
``THEANOMPI_TPU_PROFILE_STEPS``, default 20) or by passing ``log_dir``
to ``run_bsp_session``.  View with TensorBoard's profile plugin or
``xprof``; ``python -m theanompi_tpu.monitor.scopes <dir>`` prints the
device's milliseconds a step by scope and phase (forward, backward,
recompute) from the capture and the ``step_scopes.json`` that
``StepProfiler.stop`` leaves beside it.
"""

from __future__ import annotations

import logging
import os

import jax

from theanompi_tpu.monitor import scopes

_log = logging.getLogger(__name__)


def trace_running() -> bool:
    """Whether a ``jax.profiler`` trace is being captured in this
    process, whoever started it (JAX keeps one session a process).
    JAX 0.9.0 has no public accessor: this reads the state
    ``jax.profiler.start_trace`` sets."""
    from jax._src import profiler

    return profiler._profile_state.profile_session is not None


class StepProfiler:
    """Trace the first ``n_steps`` training iterations, then stop.

    No-op unless a log dir is configured, so the session loop can call
    it unconditionally.

    Also a context manager: ``with StepProfiler(dir):`` starts the
    capture on entry and guarantees ``stop()`` on exit — a crash
    mid-capture still flushes a loadable trace instead of losing the
    whole capture (``jax.profiler.stop_trace`` is what writes the
    files)."""

    def __init__(self, log_dir: str | None = None,
                 n_steps: int | None = None):
        self.log_dir = log_dir or os.environ.get("THEANOMPI_TPU_PROFILE")
        self.n_steps = (n_steps if n_steps is not None else
                        int(os.environ.get("THEANOMPI_TPU_PROFILE_STEPS",
                                           "20")))
        self._active = False
        self._done = False
        self._count = 0

    @property
    def enabled(self) -> bool:
        return bool(self.log_dir)

    def __enter__(self) -> "StepProfiler":
        self.maybe_start()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.stop()

    def maybe_start(self) -> None:
        if self.log_dir and not self._active and not self._done:
            jax.profiler.start_trace(self.log_dir)
            self._active = True

    def step(self, fence=None) -> None:
        """Call once per training iteration.  ``fence`` (an array or
        pytree of the iteration's results) is waited for before the
        capture closes: dispatch is asynchronous, and a capture closed
        when the HOST has dispatched ``n_steps`` holds the device half
        way through the first (five ResNet-50 steps: 26.8 of 5 x 47.6
        ms; my chip run, PR 36)."""
        if self._active:
            self._count += 1
            if self._count >= self.n_steps:
                if fence is not None:
                    jax.block_until_ready(fence)
                self.stop()

    def stop(self) -> None:
        """Close the capture, then leave ``step_scopes.json`` beside it
        (monitor/scopes.py: which scope and phase each instruction of
        the step program belongs to, for ``python -m
        theanompi_tpu.monitor.scopes <log_dir>``).  The map costs one
        lowering and one compile of the step (a load from the
        persistent cache after the first): AFTER ``stop_trace``, so its
        seconds are in no traced step."""
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
            self._done = True
            try:
                scopes.write_step_scopes(self.log_dir)
            except Exception:  # the map is an aid: never the run's end
                _log.exception("no %s written to %s", scopes.SCOPES_FILE,
                               self.log_dir)
