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
``xprof``.
"""

from __future__ import annotations

import os

import jax


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

    def step(self) -> None:
        """Call once per training iteration."""
        if self._active:
            self._count += 1
            if self._count >= self.n_steps:
                self.stop()

    def stop(self) -> None:
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
            self._done = True
