"""Metrics registry — counters, gauges, streaming histograms.

The reference framework's only instrumentation was the Recorder's wall
timers and printed epoch lines (Theano-MPI §4 measured its calc/comm
breakdowns exactly that way); everything else was ``print(...,
flush=True)``.  This registry is the structured replacement: a
process-wide, thread-safe store of labeled series that every layer
(rule loops, the parameter service, the exchanger, bench probes) writes
into, snapshot-able as JSONL and as a Prometheus-style text dump.

Design constraints, in order:

1. **Strict no-op when disabled.**  The hot path (one observation per
   training step) must cost a single attribute check when monitoring is
   off.  That gate lives in the facade (``theanompi_tpu/monitor``);
   the registry itself always works — tests and the postmortem hook use
   a bare registry directly.
2. **Thread-safe.**  The async rules run one worker thread per device
   and the service runs one handler thread per connection; all of them
   share one registry.  One lock per registry, held only for O(1)
   dict/deque work — never around I/O.
3. **Bounded memory.**  Histograms are streaming: exact count/sum/
   min/max plus a fixed-size ring of recent observations for the
   p50/p95/p99 estimates.  A week-long run holds the same few KB per
   series as a 5-step smoke.

Series are keyed by ``(name, sorted(labels))`` so ``rpc_ms{op=a}`` and
``rpc_ms{op=b}`` are isolated series under one logical name.
"""

from __future__ import annotations

import json
import math
import os
import threading
import time
from collections import deque
from typing import Any, Iterable

#: ring size for histogram percentile estimation — large enough that
#: p99 over a training epoch is meaningful, small enough to be noise
#: in memory (8 KB of floats per series)
HISTOGRAM_RING = 1024

LabelKey = tuple[tuple[str, str], ...]


def _label_key(labels: dict[str, Any]) -> LabelKey:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def atomic_write_text(path: str, text: str) -> None:
    """Write-then-rename publication, shared by every monitor file
    writer (snapshot, heartbeat, postmortem).  The tmp name carries
    pid AND thread id: the heartbeat thread and a same-process caller
    (flush(), stop(), finalize) must never truncate each other's
    half-written tmp file."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
    with open(tmp, "w") as f:
        f.write(text)
    os.replace(tmp, path)


class Counter:
    """Monotonic counter (events, bytes, errors)."""

    kind = "counter"

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def inc(self, amount: float = 1.0) -> None:
        self.value += amount

    def state(self) -> dict:
        return {"value": self.value}


class Gauge:
    """Last-write-wins instantaneous value (connected clients, bytes
    per exchange, current LR)."""

    kind = "gauge"

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def set(self, value: float) -> None:
        self.value = float(value)

    def add(self, delta: float) -> None:
        self.value += delta

    def state(self) -> dict:
        return {"value": self.value}


class Histogram:
    """Streaming histogram: exact count/sum/min/max, percentile
    estimates (p50/p95/p99) from a ring of the most recent
    ``HISTOGRAM_RING`` observations.

    Percentile edges: an empty histogram reports ``None`` percentiles;
    a single observation reports that value for every percentile
    (nearest-rank on a 1-element sample)."""

    kind = "histogram"

    __slots__ = ("count", "sum", "min", "max", "_ring")

    PERCENTILES = (50.0, 95.0, 99.0)

    def __init__(self, ring: int = HISTOGRAM_RING):
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf
        self._ring: deque[float] = deque(maxlen=ring)

    def observe(self, value: float) -> None:
        v = float(value)
        self.count += 1
        self.sum += v
        if v < self.min:
            self.min = v
        if v > self.max:
            self.max = v
        self._ring.append(v)

    def percentile(self, q: float) -> float | None:
        """Nearest-rank percentile over the recent-observation ring.
        ``q`` in [0, 100].  None when empty."""
        if not self._ring:
            return None
        data = sorted(self._ring)
        # nearest-rank: ceil(q/100 * n), 1-indexed, clamped to [1, n]
        rank = max(1, min(len(data), math.ceil(q / 100.0 * len(data))))
        return data[rank - 1]

    def state(self) -> dict:
        out = {
            "count": self.count,
            "sum": round(self.sum, 6),
            "min": None if self.count == 0 else self.min,
            "max": None if self.count == 0 else self.max,
            "mean": None if self.count == 0 else self.sum / self.count,
        }
        for q in self.PERCENTILES:
            out[f"p{q:g}"] = self.percentile(q)
        return out


_KINDS = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}


class MetricsRegistry:
    """Process-wide store of labeled metric series.

    ``write_count`` counts every mutation — the no-op contract of the
    disabled facade is tested as "a full rule session leaves the global
    registry's write_count at zero"."""

    def __init__(self):
        self._lock = threading.Lock()
        self._series: dict[tuple[str, LabelKey], Any] = {}  # guarded_by: self._lock
        self._kinds: dict[str, str] = {}                    # guarded_by: self._lock
        self.write_count = 0                                # guarded_by: self._lock
        self.created_at = time.time()

    # -- series access -------------------------------------------------

    def _get(self, kind: str, name: str, labels: dict[str, Any]):  # requires_lock: self._lock
        declared = self._kinds.setdefault(name, kind)
        if declared != kind:
            raise TypeError(
                f"metric {name!r} already registered as {declared}, "
                f"cannot use as {kind}")
        key = (name, _label_key(labels))
        series = self._series.get(key)
        if series is None:
            series = self._series[key] = _KINDS[kind]()
        return series

    def inc(self, name: str, amount: float = 1.0, /, **labels) -> None:
        with self._lock:
            self._get("counter", name, labels).inc(amount)
            self.write_count += 1

    def set_gauge(self, name: str, value: float, /, **labels) -> None:
        with self._lock:
            self._get("gauge", name, labels).set(value)
            self.write_count += 1

    def add_gauge(self, name: str, delta: float, /, **labels) -> None:
        with self._lock:
            self._get("gauge", name, labels).add(delta)
            self.write_count += 1

    def observe(self, name: str, value: float, /, **labels) -> None:
        with self._lock:
            self._get("histogram", name, labels).observe(value)
            self.write_count += 1

    # -- reads ---------------------------------------------------------

    def get(self, name: str, /, **labels):
        """The raw series object (None if absent) — for tests and the
        watchdog's own reads; mutating it bypasses write_count."""
        with self._lock:
            return self._series.get((name, _label_key(labels)))

    def value(self, name: str, /, **labels) -> float | None:
        s = self.get(name, **labels)
        return None if s is None or not hasattr(s, "value") else s.value

    def series_names(self) -> set[str]:
        with self._lock:
            return {name for name, _ in self._series}

    # -- snapshots -----------------------------------------------------

    def snapshot(self) -> list[dict]:
        """One dict per series: name, kind, labels, state.  Taken under
        the lock (consistent point-in-time view), JSON-ready."""
        now = time.time()
        with self._lock:
            items = sorted(self._series.items(),
                           key=lambda kv: (kv[0][0], kv[0][1]))
            return [
                {"ts": now, "name": name, "kind": series.kind,
                 "labels": dict(lk), **series.state()}
                for (name, lk), series in items
            ]

    def write_jsonl(self, path: str) -> str:
        """Atomically (re)write the snapshot as JSONL — one series per
        line.  Overwrites: the file is the LATEST state, not an append
        log (watchdogs read it whole; history lives in the Recorder's
        per-epoch JSONL)."""
        snap = self.snapshot()
        atomic_write_text(path, "".join(json.dumps(rec) + "\n"
                                        for rec in snap))
        return path

    def to_prometheus(self) -> str:
        """Prometheus text exposition (counters/gauges as-is;
        histograms as summary-style quantile lines + _count/_sum)."""
        lines: list[str] = []
        seen_types: set[str] = set()
        for rec in self.snapshot():
            pname = _prom_name(rec["name"])
            if pname not in seen_types:
                ptype = {"counter": "counter", "gauge": "gauge",
                         "histogram": "summary"}[rec["kind"]]
                lines.append(f"# TYPE {pname} {ptype}")
                seen_types.add(pname)
            labels = rec["labels"]
            if rec["kind"] == "histogram":
                lines.append(f"{pname}_count{_prom_labels(labels)} "
                             f"{rec['count']}")
                lines.append(f"{pname}_sum{_prom_labels(labels)} "
                             f"{rec['sum']}")
                for q in (50, 95, 99):
                    v = rec[f"p{q}"]
                    if v is not None:
                        ql = dict(labels, quantile=f"0.{q}")
                        lines.append(f"{pname}{_prom_labels(ql)} {v}")
            else:
                lines.append(f"{pname}{_prom_labels(labels)} "
                             f"{rec['value']}")
        return "\n".join(lines) + ("\n" if lines else "")


def _prom_name(name: str) -> str:
    """``service/rpc_ms`` -> ``theanompi_service_rpc_ms`` (slashes and
    dots are series namespacing here, underscores on the wire)."""
    safe = "".join(c if c.isalnum() or c == "_" else "_" for c in name)
    return f"theanompi_{safe}"


def _prom_labels(labels: dict[str, str]) -> str:
    if not labels:
        return ""

    def esc(v: str) -> str:
        # exposition-format escaping: one unescaped quote in a label
        # value (e.g. a client-supplied op name) would invalidate the
        # whole dump for a Prometheus parser
        return (str(v).replace("\\", "\\\\").replace('"', '\\"')
                .replace("\n", "\\n"))

    inner = ",".join(f'{k}="{esc(v)}"' for k, v in sorted(labels.items()))
    return "{" + inner + "}"


def tree_bytes(tree: Any) -> int:
    """Total byte size of a pytree of arrays (numpy, jax, or abstract
    tracers — anything exposing ``.size``/``.dtype``).  Used by the
    exchanger's bytes counters and the service client's wire
    accounting; non-array leaves count 0."""
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree.leaves(tree):
        size = getattr(leaf, "size", None)
        dtype = getattr(leaf, "dtype", None)
        if size is not None and dtype is not None:
            total += int(size) * np.dtype(dtype).itemsize
        elif isinstance(leaf, (bytes, bytearray)):
            total += len(leaf)
    return total


def tree_dtypes(tree: Any) -> str:
    """Sorted comma-joined dtype set of a pytree — the ``dtype`` label
    for exchange counters (one label value per exchange call, not one
    series per leaf)."""
    import jax

    names: set[str] = set()
    for leaf in jax.tree.leaves(tree):
        dt = getattr(leaf, "dtype", None)
        if dt is not None:
            names.add(str(dt))
    return ",".join(sorted(names)) or "none"
