"""Span tracing — nested wall-clock spans that line up with XLA traces.

A span is a named wall-clock interval around a phase of work
(``with span("comm/psum"): ...``).  Three things happen per span:

1. **Honest timing.**  Under jit the step call returns before the
   device finishes (async dispatch), so a naive wall timer measures
   dispatch, not compute.  A span can *fence* on a device array or
   pytree at exit (``fence=...``) with ``jax.block_until_ready``,
   like the Recorder (utils/recorder.py).
2. **XLA alignment.**  Each span enters a
   ``jax.profiler.TraceAnnotation``, so when a StepProfiler capture is
   active the span shows up as a named region in the TensorBoard/xprof
   timeline — host spans and HLO ops on one ruler.
3. **Registry feed.**  On exit the duration lands in the registry
   histogram ``span_ms{name=...}`` (count + sum there give per-section
   totals; p50/p95/p99 give the distribution).

Nesting is tracked per-thread; the full name of a nested span is
``parent/child`` so ``with span("epoch"): with span("val")`` emits
``epoch/val``.  Open spans are globally visible (`open_spans()`) so
the postmortem dump can say exactly which phase a crash or hang was
inside.
"""

from __future__ import annotations

import threading
import time
from typing import Any

from theanompi_tpu.monitor import trace as _trace

_local = threading.local()

#: all currently-open spans across threads: id(span) -> Span.  The
#: postmortem hook reads this; entries are tiny and removed on exit.
_open: dict[int, "Span"] = {}
_open_lock = threading.Lock()


def _stack() -> list["Span"]:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class Span:
    """One timed interval.  Use via ``monitor.span(...)`` (the facade
    returns a no-op when monitoring is disabled) or directly in tests.

    ``registry=None`` times, nests and annotates the profiler's trace
    but records nowhere."""

    __slots__ = ("name", "full_name", "labels", "fence_on", "registry",
                 "t0", "t_wall", "thread", "_annotation",
                 "trace_id", "span_id", "parent_id", "sampled")

    def __init__(self, name: str, registry=None, fence: Any = None,
                 **labels):
        self.name = name
        self.full_name = name  # finalized on __enter__ from the stack
        self.labels = labels
        self.fence_on = fence
        self.registry = registry
        self.t0 = 0.0
        self.t_wall = 0.0
        self.thread = threading.current_thread().name
        self._annotation = None
        # trace linkage — ids stay None unless tracing is enabled at
        # __enter__, so the disabled path allocates nothing
        self.trace_id: str | None = None
        self.span_id: str | None = None
        self.parent_id: str | None = None
        self.sampled = False

    def __enter__(self) -> "Span":
        # t0 must be set before the span becomes globally visible, or
        # a concurrent open_spans()/postmortem snapshot would compute
        # age from 0.0 (host-uptime-sized garbage)
        self.t0 = time.monotonic()
        st = _stack()
        if st:
            self.full_name = f"{st[-1].full_name}/{self.name}"
        if _trace.enabled():
            (self.trace_id, self.span_id,
             self.parent_id, self.sampled) = _trace.begin(
                st[-1] if st else None)
        st.append(self)
        with _open_lock:
            _open[id(self)] = self
        try:
            import jax

            self._annotation = jax.profiler.TraceAnnotation(self.full_name)
            self._annotation.__enter__()
        except Exception:
            # annotation is best-effort alignment; a failure here must
            # not abort __enter__ AFTER the span registered itself in
            # _open/_stack (the with-statement would never run
            # __exit__, leaking a ghost open span)
            self._annotation = None
        # re-stamp after annotation setup so its cost (first jax
        # import can be slow) isn't charged to the timed block; the
        # wall stamp pairs with the SAME instant so merged timelines
        # and in-process interval math describe one interval
        self.t0 = time.monotonic()
        self.t_wall = time.time()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        try:
            if self.fence_on is not None and exc_type is None:
                import jax  # lazy, like the annotation above

                jax.block_until_ready(self.fence_on)
        finally:
            dt = time.monotonic() - self.t0
            if self._annotation is not None:
                try:
                    self._annotation.__exit__(exc_type, exc, tb)
                except Exception:
                    # profiler teardown racing an open span (e.g.
                    # StepProfiler.stop() on the crash path) must not
                    # skip the stack/_open cleanup below or mask the
                    # body's exception
                    pass
                self._annotation = None
            st = _stack()
            if st and st[-1] is self:
                st.pop()
            else:  # exited out of order (shouldn't happen) — scrub
                try:
                    st.remove(self)
                except ValueError:
                    pass
            with _open_lock:
                _open.pop(id(self), None)
            if self.registry is not None:
                self.registry.observe("span_ms", dt * 1e3,
                                      name=self.full_name, **self.labels)
                if exc_type is not None:
                    self.registry.inc("span_errors_total",
                                      name=self.full_name)
            if self.trace_id is not None:
                _trace.record_span(self, dt, exc_type is not None)

    @property
    def age_s(self) -> float:
        return time.monotonic() - self.t0


class _NullSpan:
    """The disabled fast path: a shared, reentrant, stateless no-op."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


NULL_SPAN = _NullSpan()


def current_span() -> Span | None:
    st = getattr(_local, "stack", None)
    return st[-1] if st else None


def open_spans() -> list[dict]:
    """Snapshot of every open span in the process (all threads),
    oldest first — the postmortem's "where was everyone" view."""
    with _open_lock:
        spans = list(_open.values())
    spans.sort(key=lambda s: s.t0)
    out = []
    for s in spans:
        d = {"name": s.full_name, "thread": s.thread,
             "age_s": round(s.age_s, 3), "labels": s.labels}
        if s.trace_id is not None:  # only under tracing — the
            # disabled-mode snapshot stays byte-identical to pre-trace
            d["trace"] = s.trace_id
            d["span"] = s.span_id
        out.append(d)
    return out
