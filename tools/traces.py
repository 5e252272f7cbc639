#!/usr/bin/env python
"""Assemble distributed traces from a merged fleet JSONL.

Consumes the collector's ``fleet.jsonl`` (or a run dir of per-process
``events_*.jsonl`` files when no collector ran) and prints, per trace:
the span tree, the process fan-out, orphan count, and the **critical
path** — the chain of spans that bounds the trace's wall time, which
is where an exchange period or a GENERATE request actually spent its
time.  Also runs **idle-all-workers gap detection** (ROADMAP item 2's
acceptance metric): intervals inside the observation window where NO
process had any span open — the keep-the-device-busy discipline of
the source paper, made checkable.

Wall timestamps are mapped onto the collector's clock before any
cross-process comparison: each record carries the sender's estimated
``offset_s`` (sampled from the export handshake round trip — see
docs/OBSERVABILITY.md "Distributed tracing").

Usage:
    python tools/traces.py RUNDIR_OR_FLEET_JSONL [--gap-ms 50]
        [--trace ID] [--min-spans 2] [--require-procs N]
        [--require-zero-orphans]

Exit status: 0, or 1 when a ``--require-*`` assertion fails.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys


# ---------------------------------------------------------------------------
# Loading
# ---------------------------------------------------------------------------


def _read_jsonl(path: str) -> list[dict]:
    out: list[dict] = []
    try:
        with open(path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # torn tail line mid-write
                if isinstance(rec, dict):
                    out.append(rec)
    except OSError:
        pass
    return out


def _with_rotations(path: str) -> list[str]:
    rotated, i = [], 1
    while os.path.exists(f"{path}.{i}"):
        rotated.append(f"{path}.{i}")
        i += 1
    return [*reversed(rotated), path]


def load_events(target: str) -> list[dict]:
    """Records from a fleet JSONL, or from every event file under a
    run dir (fleet.jsonl preferred; falls back to the per-process
    local files so traces assemble even with no collector)."""
    if os.path.isdir(target):
        fleet = os.path.join(target, "fleet.jsonl")
        paths: list[str] = []
        if os.path.exists(fleet):
            paths = _with_rotations(fleet)
        else:
            for p in sorted(glob.glob(
                    os.path.join(target, "events_*.jsonl"))):
                if not p.rsplit(".", 1)[-1].isdigit():
                    paths.extend(_with_rotations(p))
        out: list[dict] = []
        for p in paths:
            out.extend(_read_jsonl(p))
        return out
    out = []
    for p in _with_rotations(target):
        out.extend(_read_jsonl(p))
    return out


# ---------------------------------------------------------------------------
# Trace assembly
# ---------------------------------------------------------------------------


def spans_of(records: list[dict]) -> list[dict]:
    """Span records with collector-clock times attached: ``t0`` /
    ``t1`` are offset-corrected wall seconds."""
    out = []
    for r in records:
        if r.get("event") != "span" or not r.get("trace"):
            continue
        try:
            off = float(r.get("offset_s") or 0.0)
            dur = float(r.get("dur_s") or 0.0)
            t0 = float(r["t_wall"]) + off
        except (KeyError, TypeError, ValueError):
            continue
        s = dict(r)
        s["t0"], s["t1"] = t0, t0 + dur
        out.append(s)
    return out


def assemble(records: list[dict]) -> dict[str, list[dict]]:
    """trace_id -> spans, each trace sorted by corrected start."""
    traces: dict[str, list[dict]] = {}
    for s in spans_of(records):
        traces.setdefault(s["trace"], []).append(s)
    for spans in traces.values():
        spans.sort(key=lambda s: s["t0"])
    return traces


def orphans(spans: list[dict]) -> list[dict]:
    """Spans whose declared parent is missing from the trace — a
    broken stitch (dropped span, or a propagation hole)."""
    ids = {s["span"] for s in spans}
    return [s for s in spans
            if s.get("parent") is not None and s["parent"] not in ids]


def processes_of(spans: list[dict]) -> set:
    return {(s.get("pid"), s.get("role")) for s in spans}


def critical_path(spans: list[dict]) -> list[dict]:
    """Root-to-leaf chain that bounds the trace's wall time: from each
    node, descend into the child whose (corrected) end time is
    latest.  Roots are parentless spans (plus orphans, so a damaged
    trace still yields a path); among roots the latest-ending wins."""
    if not spans:
        return []
    ids = {s["span"] for s in spans}
    children: dict[str, list[dict]] = {}
    roots: list[dict] = []
    for s in spans:
        p = s.get("parent")
        if p is not None and p in ids:
            children.setdefault(p, []).append(s)
        else:
            roots.append(s)
    path: list[dict] = []
    node = max(roots, key=lambda s: s["t1"])
    seen = set()
    while node is not None and node["span"] not in seen:
        seen.add(node["span"])
        path.append(node)
        kids = children.get(node["span"], [])
        node = max(kids, key=lambda s: s["t1"]) if kids else None
    return path


# ---------------------------------------------------------------------------
# Idle-all-workers gaps
# ---------------------------------------------------------------------------


def idle_gaps(spans: list[dict], threshold_s: float = 0.05
              ) -> list[tuple[float, float]]:
    """Intervals of the observation window (first span start to last
    span end, collector clock) longer than ``threshold_s`` during
    which NO span was open in ANY process.  Zero gaps is the
    keep-the-device-busy acceptance condition; each gap is dead fleet
    time nothing was attributed to."""
    ivals = sorted((s["t0"], s["t1"]) for s in spans)
    if not ivals:
        return []
    gaps: list[tuple[float, float]] = []
    cover_end = ivals[0][1]
    for t0, t1 in ivals[1:]:
        if t0 > cover_end and t0 - cover_end >= threshold_s:
            gaps.append((cover_end, t0))
        cover_end = max(cover_end, t1)
    return gaps


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _fmt_span(s: dict) -> str:
    labels = s.get("labels") or {}
    lab = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
    who = f"{s.get('role')}/pid{s.get('pid')}"
    return (f"{s.get('name')}{'{' + lab + '}' if lab else ''} "
            f"[{who}] {float(s.get('dur_s') or 0.0) * 1e3:.2f}ms")


def print_trace(tid: str, spans: list[dict], file=None) -> None:
    file = file if file is not None else sys.stdout
    orph = orphans(spans)
    procs = processes_of(spans)
    t0 = min(s["t0"] for s in spans)
    t1 = max(s["t1"] for s in spans)
    print(f"trace {tid}: {len(spans)} spans, {len(procs)} processes, "
          f"{(t1 - t0) * 1e3:.2f}ms wall, {len(orph)} orphans",
          file=file)
    path = critical_path(spans)
    path_ids = {s["span"] for s in path}
    print("  critical path:", file=file)
    for depth, s in enumerate(path):
        print(f"    {'  ' * depth}{_fmt_span(s)}", file=file)
    rest = [s for s in spans if s["span"] not in path_ids]
    if rest:
        print(f"  off-path spans ({len(rest)}):", file=file)
        for s in rest:
            print(f"    {_fmt_span(s)}", file=file)
    for s in orph:
        print(f"  ORPHAN {_fmt_span(s)} "
              f"(parent {s.get('parent')} missing)", file=file)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="assemble distributed traces from a fleet JSONL "
                    "(docs/OBSERVABILITY.md 'Distributed tracing')")
    ap.add_argument("target",
                    help="fleet.jsonl (or a run dir containing it / "
                         "per-process events_*.jsonl files)")
    ap.add_argument("--trace", default=None,
                    help="print only this trace id")
    ap.add_argument("--min-spans", type=int, default=2,
                    help="hide traces smaller than this (default 2; "
                         "single-span traces are usually untraced "
                         "background noise)")
    ap.add_argument("--gap-ms", type=float, default=50.0,
                    help="idle-all-workers gap threshold (default 50)")
    ap.add_argument("--require-procs", type=int, default=0,
                    help="exit 1 unless some trace spans >= N "
                         "processes with zero orphans")
    ap.add_argument("--require-zero-orphans", action="store_true",
                    help="exit 1 if any printed trace has orphans")
    args = ap.parse_args(argv)

    records = load_events(args.target)
    traces = assemble(records)
    if args.trace:
        traces = {k: v for k, v in traces.items() if k == args.trace}
    shown = {tid: spans for tid, spans in traces.items()
             if len(spans) >= args.min_spans}
    all_spans = [s for spans in traces.values() for s in spans]
    if not shown:
        print(f"no traces with >= {args.min_spans} spans "
              f"({len(all_spans)} span records total)")
    for tid, spans in sorted(shown.items(),
                             key=lambda kv: kv[1][0]["t0"]):
        print_trace(tid, spans)

    gaps = idle_gaps(all_spans, args.gap_ms / 1e3)
    if gaps:
        print(f"idle-all-workers gaps (> {args.gap_ms:.0f}ms): "
              f"{len(gaps)}")
        for g0, g1 in gaps:
            print(f"  {(g1 - g0) * 1e3:.1f}ms dead at +"
                  f"{(g0 - all_spans[0]['t0']):.3f}s")
    else:
        print(f"idle-all-workers gaps (> {args.gap_ms:.0f}ms): none")

    rc = 0
    if args.require_zero_orphans and any(
            orphans(spans) for spans in shown.values()):
        print("FAIL: orphan spans present", file=sys.stderr)
        rc = 1
    if args.require_procs:
        ok = any(len(processes_of(spans)) >= args.require_procs
                 and not orphans(spans) for spans in shown.values())
        if not ok:
            print(f"FAIL: no complete trace spanning >= "
                  f"{args.require_procs} processes", file=sys.stderr)
            rc = 1
    return rc


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:  # `traces.py ... | head` is a normal use
        sys.exit(0)
