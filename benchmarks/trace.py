"""Reduction from a profiler trace to numbers: the benchmark's own.

A trace is reduced to plain tuples first (``load``), so that every
function below works alike on a recorded ``.xplane.pb`` and on a
hand-made list of events (``benchmarks/fixtures/``), and so that
``benchmarks/selfcheck.py`` can check each one by hand.

* ``Trace.device_ops``: per chip, ``(name, category, start_ns, end_ns)``
  of every event on the device plane's "XLA Ops" line.
* ``Trace.host_spans``: ``(name, start_ns, end_ns)`` of the harness's
  own spans (``bench/...``), taken on the host's clock and moved onto
  the trace's (``load``).
* ``Trace.window``: from the first ``bench/segment`` span's start to
  the last one's end - the traced part of the measured window.

Reads with ``jax.profiler.ProfileData``, which needs only JAX.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
#: the opcode in an op's HLO text: the first word before a "(" that
#: follows a space (layout tokens such as T(8,128) follow no space)
OPCODE = re.compile(r"\s([a-z][\w-]*)\(")
DETAIL = re.compile(r"kind=(k\w+)|custom_call_target=\\?\"(\w+)")
OPS_LINE = "XLA Ops"
ENVIRONMENT_PLANE = "Task Environment"
PROFILE_START = "profile_start_time"
SEGMENT_SPAN = "bench/segment"


@dataclasses.dataclass
class Trace:
    device_ops: dict          # chip -> [(name, category, start_ns, end_ns)]
    host_spans: list          # [(name, start_ns, end_ns)]
    window: tuple             # (start_ns, end_ns)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def load(path: str, host_spans: list, chips: int | None = None
         ) -> Trace | None:
    """Plain tuples from an ``.xplane.pb`` and the harness's own spans.

    ``host_spans`` are ``(name, start, end)`` in ``time.time_ns()``.  An
    event's ``start_ns`` counts from the profile's start, which the
    "Task Environment" plane gives in the same clock
    (``profile_start_time``), so the spans move onto the trace's clock
    by subtraction (checked against a ``TraceAnnotation`` of the same
    interval: 10 us apart).  None where the trace holds no device plane
    or no ``bench/segment`` span came with it (nothing to read)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    device_ops: dict = {}
    origin = None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops = device_ops.setdefault(chip, [])
                for ev in line.events:
                    name, category = name_and_category(ev.name)
                    ops.append((name, category, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
        elif plane.name == ENVIRONMENT_PLANE:
            origin = int(dict(plane.stats)[PROFILE_START])
    if origin is None:
        return None
    if chips is not None:
        device_ops = {c: device_ops[c] for c in sorted(device_ops)[:chips]}
    return from_events(device_ops, [(name, start - origin, end - origin)
                                    for name, start, end in host_spans])


def name_and_category(text: str) -> tuple:
    """An op event is named by its whole HLO text (``%fusion.12 = (shapes)
    fusion(operands), kind=kOutput, calls=...``).  The name is what stands
    before `` = ``; the category is the opcode plus, for a fusion, its
    kind and, for a custom call, its target: ``fusion kOutput``,
    ``custom-call tpu_custom_call``, ``all-reduce``.  Operand names never
    enter either, so a fusion that reads ``%all-reduce.1`` is no
    all-reduce."""
    name, _, rest = text.partition(" = ")
    if not rest:
        return text.lstrip("%"), ""
    opcode = OPCODE.search(" " + rest)
    words = [opcode.group(1)] if opcode else []
    detail = DETAIL.search(rest)
    if detail:
        words.append(detail.group(1) or detail.group(2))
    return name.lstrip("%"), " ".join(words)


def from_events(device_ops: dict, host_spans: list) -> Trace | None:
    segments = [s for s in host_spans if s[0] == SEGMENT_SPAN]
    if not device_ops or not segments:
        return None
    window = (min(s[1] for s in segments), max(s[2] for s in segments))
    for ops in device_ops.values():
        ops.sort(key=lambda op: op[2])
    return Trace(device_ops, sorted(host_spans, key=lambda s: s[1]), window)


# -- interval arithmetic ---------------------------------------------------


def union(intervals) -> list:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    merged: list = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def clip(intervals, window) -> list:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(intervals, holes) -> list:
    """``union(intervals)`` minus ``union(holes)``."""
    out = []
    holes = union(holes)
    for start, end in union(intervals):
        cursor = start
        for h_start, h_end in holes:
            if h_end <= cursor:
                continue
            if h_start >= end:
                break
            if h_start > cursor:
                out.append((cursor, h_start))
            cursor = max(cursor, h_end)
            if cursor >= end:
                break
        if cursor < end:
            out.append((cursor, end))
    return out


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def _matching(ops, pattern: str, window):
    rx = re.compile(pattern)
    hit, miss = [], []
    for name, category, start, end in ops:
        (hit if rx.search(f"{name} {category}") else miss).append(
            (start, end))
    return clip(hit, window), clip(miss, window)


# -- the reductions ---------------------------------------------------------


def busy_ns(trace: Trace) -> float:
    """Nanoseconds in which an operation ran on the device inside the
    traced window: the union of the op intervals, averaged over the
    chips."""
    per_chip = [total(union(clip([(s, e) for _, _, s, e in ops],
                                 trace.window)))
                for ops in trace.device_ops.values()]
    return sum(per_chip) / len(per_chip)


def idle_share(trace: Trace) -> float:
    """1 - busy over the traced window, in percent."""
    return 100.0 * (1.0 - busy_ns(trace) / trace.window_ns)


def device_ms_per_step(trace: Trace, steps: int) -> float:
    """Union of device-op intervals on ONE chip (the lowest-numbered)
    over the steps traced, in milliseconds a step."""
    ops = trace.device_ops[min(trace.device_ops)]
    busy = total(union(clip([(s, e) for _, _, s, e in ops], trace.window)))
    return busy / steps / 1e6


def class_share(trace: Trace, pattern: str) -> float:
    """Share of device-busy time, in percent, during which an op whose
    ``"<name> <category>"`` matches ``pattern`` ran; mean over the
    chips."""
    shares = []
    for ops in trace.device_ops.values():
        hit, miss = _matching(ops, pattern, trace.window)
        busy = total(union(hit + miss))
        shares.append(total(union(hit)) / busy if busy else 0.0)
    return 100.0 * sum(shares) / len(shares)


def exposed_ms_per_step(trace: Trace, pattern: str, steps: int) -> float:
    """Milliseconds a step during which an op matching ``pattern`` ran
    and NO other op ran on that chip; mean over the chips."""
    exposed = []
    for ops in trace.device_ops.values():
        hit, miss = _matching(ops, pattern, trace.window)
        exposed.append(total(subtract(hit, miss)))
    return sum(exposed) / len(exposed) / steps / 1e6


def top_ops(trace: Trace, n: int = 10) -> list:
    """The ``n`` device ops with most time inside the window on the
    lowest-numbered chip: ``[["name [category]", seconds], ...]``."""
    sums: dict = {}
    for name, category, start, end in trace.device_ops[
            min(trace.device_ops)]:
        for s, e in clip([(start, end)], trace.window):
            key = f"{name} [{category}]" if category else name
            sums[key] = sums.get(key, 0.0) + (e - s)
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(trace: Trace, n: int = 5) -> list:
    """The ``n`` longest idle gaps of the lowest-numbered chip inside
    the window, each named by the harness span that covers most of it
    (of equal covers the shortest span, which is the innermost):
    ``[["bench/wait", seconds], ...]``."""
    ops = trace.device_ops[min(trace.device_ops)]
    busy = union(clip([(s, e) for _, _, s, e in ops], trace.window))
    gaps = subtract([trace.window], busy)
    spans = [s for s in trace.host_spans if s[0] != SEGMENT_SPAN]
    named = []
    for g_start, g_end in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, best_key = "no span", (0.0, 0.0)
        for name, s_start, s_end in spans:
            cover = min(g_end, s_end) - max(g_start, s_start)
            if cover <= 0:
                continue
            key = (round(cover / (g_end - g_start), 2), -(s_end - s_start))
            if key > best_key:
                best, best_key = name, key
        named.append([best, (g_end - g_start) / 1e9])
    return named
