"""Op-level device-time account from a JAX profiler xplane proto.

The Perfetto ``trace.json.gz`` export of the committed r3 capture
carries host threads but NO device timeline.  The ``vm.xplane.pb``
beside it does hold the device planes: ``/device:TPU:0`` with an
"XLA Ops" line (17 790 events for 5 ResNet steps), each event carrying
``hlo_category``, ``flops``, ``bytes_accessed``, and the HLO text with
shapes.  This tool turns that into the per-op MFU account (SURVEY §6 /
§7 hard-part 2): where every slice of the step goes, at what measured
TF/s and GB/s, and how close each slice sits to its own roofline.  The
benchmark's own reading of a step's classes is ``python3
benchmarks/run.py --workload <cell> --seed <n> --seconds 25 --trace 1``
(``benchmarks/trace.py``); this tool is the per-op view beneath it.

Needs the TF tsl xplane proto bindings
(``tensorflow.tsl.profiler.protobuf.xplane_pb2`` — present in this
image's tensorflow); the aggregation itself is pure Python over plain
dicts so it unit-tests without tensorflow.

Usage:
    python tools/analyze_xplane.py artifacts/tpu_trace [--out report.json]

The positional argument is a profile dir (searched recursively for
``*.xplane.pb``) or a single ``.xplane.pb`` file.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from collections import defaultdict

# -- pure aggregation core (unit-testable without tensorflow) -------------

_SHAPE_RE = re.compile(r"\[(\d+),(\d+),(\d+),(\d+)\]")

#: Rows whose total duration per step is below this are too short for the
#: flops/bytes counters to produce meaningful rates (the r4 account printed
#: 5.77e6 GB/s for async-start); their rates are suppressed and flagged.
SUB_RESOLUTION_MS = 0.05


def hlo_output_part(hlo_text: str) -> str:
    """The output-shape side of ``%name = <shapes> op(operands…)`` —
    text before the operand list (shared with tools/fusion_deepdive.py
    so the two tools can't silently diverge on output parsing)."""
    return hlo_text.split(" fusion(")[0] if " fusion(" in hlo_text \
        else hlo_text.split("(")[0]


_COPY_SHAPE = re.compile(r"copy-done\(\((\w+)\[([\d,]*)\]")
_DTYPE_BYTES = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "f16": 2,
                "s8": 1, "u8": 1, "pred": 1}
#: one full shape token inside an HLO tuple: dtype[dims]{layout}
_SHAPE_TOK = re.compile(r"(\w+)\[([\d,]*)\]\{([^}]*)\}")


def _size_class(nbytes: int) -> str:
    """'param_vec' (<=64 KiB — BN scales, biases, optimizer scalars),
    'kernel' (<=4 MiB), 'activation' (larger) — THE size thresholds,
    shared by copy_size_class and attribute_copies so the two views
    cannot classify one event differently."""
    if nbytes <= 64 * 1024:
        return "param_vec"
    if nbytes <= 4 * 1024 * 1024:
        return "kernel"
    return "activation"


def _shape_nbytes(dtype: str, dims: str) -> int:
    """Bytes of one ``dtype[dims]`` shape token — THE byte math for
    every copy view in this file."""
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def copy_size_class(name: str) -> str:
    """Size class of the tensor a copy-done materialises, parsed from
    the copy's tuple-shape text; 'unknown' when no copy tuple is
    present.  (Shared with tools/fusion_deepdive.py.)"""
    m = _COPY_SHAPE.search(name)
    if not m:
        return "unknown"
    return _size_class(_shape_nbytes(m.group(1), m.group(2)))


def shrink_tf_op(tf_op: str) -> str:
    """'jit(shard_step)/jvp(ResNet)/BottleneckBlock_1/add:' ->
    'fwd/BottleneckBlock_1/add' (strip jit wrapper, fold jvp/transpose
    into fwd/bwd, drop trailing colon).  Empty in -> empty out, so
    callers' ``or``-fallbacks to the display name still fire.
    (Shared with tools/fusion_deepdive.py.)"""
    if not tf_op:
        return ""
    s = tf_op.rstrip(":")
    direction = "bwd" if "transpose(" in s else "fwd"
    s = re.sub(r"jit\([^)]*\)/", "", s)
    s = re.sub(r"(transpose\(|jvp\(|\))", "", s)
    return f"{direction}/{s}"


def copy_endpoints(name: str) -> tuple[str, str, str, int]:
    """(direction, shape, dest_layout, nbytes) of one copy-done event.

    The r3 capture's copy events carry NO tf_op (the source-op stat is
    empty on every one of the 6 670), so attribution has to come from
    the HLO text itself: a copy-start's operand tuple is ``(dest, src,
    context)`` and the memory-space suffix on the layouts says which
    way the bytes flow — ``S(1)`` is the compiler-managed alternate
    memory (MSA/VMEM prefetch space):

    - dest in S(1): ``prefetch`` — HBM -> on-chip staging of a buffer
      the scheduler wants resident before use (the 1 146 tiny
      param-vector copies of the account);
    - src in S(1): ``writeback`` — staged/produced on-chip, copied out
      to a fresh HBM buffer.  A big batch-led shape here is the smoking
      gun for a live input buffer XLA could not alias (donation gap);
    - neither: ``move`` — an HBM->HBM copy (layout change or alias
      materialization).
    """
    m = re.search(r"copy-done\(\((.*)", name)
    toks = _SHAPE_TOK.findall(m.group(1)) if m else []
    if len(toks) < 2:
        return "unknown", "?", "", 0
    (d_dt, d_dims, d_lay), (_s_dt, _s_dims, s_lay) = toks[0], toks[1]
    nbytes = _shape_nbytes(d_dt, d_dims)
    if "S(1)" in d_lay:
        direction = "prefetch"
    elif "S(1)" in s_lay:
        direction = "writeback"
    else:
        direction = "move"
    return direction, f"{d_dt}[{d_dims}]", d_lay, nbytes


def attribute_copies(events: list[dict], n_steps: int) -> dict:
    """The copy-done account: every copy event attributed to what it
    copies (direction x size-class x shape), sorted by time.

    The r4 account flags 2.37 ms/step across 1 334 copy-done events as
    near-zero-FLOP residue; this names each slice so the fix (buffer
    donation, layout pinning) can be targeted and the after-capture
    diffed per row (tools/xla_sweep.py consumes two of these).
    """
    rows = defaultdict(lambda: [0, 0, 0])      # dur_ps, bytes, n
    done_dur = done_n = start_dur = start_n = 0
    for e in events:
        if e["category"] == "copy-start":
            start_dur += e["dur_ps"]
            start_n += 1
            continue
        if e["category"] != "copy-done":
            continue
        done_dur += e["dur_ps"]
        done_n += 1
        direction, shape, _lay, nbytes = copy_endpoints(e["name"])
        cls = _size_class(nbytes) if direction != "unknown" \
            else "unknown"
        a = rows[(direction, cls, shape)]
        a[0] += e["dur_ps"]
        a[1] += nbytes
        a[2] += 1

    out_rows = []
    for (direction, cls, shape), (dur, nbytes, n) in sorted(
            rows.items(), key=lambda kv: -kv[1][0]):
        ms = dur / 1e9 / n_steps
        out_rows.append({
            "producer": f"{direction}:{cls}:{shape}",
            "ms_per_step": round(ms, 3),
            "events_per_step": n // n_steps,
            "us_per_event": round(dur / 1e6 / n, 2) if n else 0.0,
            "mbytes_per_step": round(nbytes / 1e6 / n_steps, 2),
            "pct_of_copy_done": round(100 * dur / done_dur, 1)
            if done_dur else 0.0,
        })
    return {
        "copy_done_ms_per_step": round(done_dur / 1e9 / n_steps, 3),
        "copy_done_events_per_step": done_n // n_steps,
        "copy_start_ms_per_step": round(start_dur / 1e9 / n_steps, 3),
        "copy_start_events_per_step": start_n // n_steps,
        "rows": out_rows,
    }


def conv_spatial_bucket(hlo_text: str, tf_op: str = "") -> str:
    """Bucket a conv fusion by its ACTIVATION shape + pass kind.

    The round-4 account used the first 4-D shape in the HLO text, which
    for weight-gradient convs is the *kernel* (e.g. ``[1,1,64,256]``) —
    ~8%% of the step was mis-attributed to kernel-shaped "activation"
    buckets (round-4 verdict, weak #3).  This version:

    - finds every 4-D shape in the text, takes the batch dim as the
      leading dim of the largest shape by element count (the streamed
      activation; a modal-leading-dim rule fails on wgrad fusions that
      fold the optimizer update and so mention the kernel shape 4x),
    - buckets by the batch-led shape with the largest spatial extent
      (the activation actually streamed from HBM), labelled HxWxC,
    - classifies the pass: ``wgrad`` when the op's *output* contains a
      4-D shape that is NOT batch-led (the kernel gradient), ``dgrad``
      when the JAX source path shows ``transpose(`` (reverse-mode),
      else ``fprop``.

    Returns ``"HxWxC:kind"`` so the bucket table still sums to the conv
    category total, or ``"other"`` when no 4-D shape appears.
    """
    shapes = [tuple(int(g) for g in m.groups())
              for m in _SHAPE_RE.finditer(hlo_text)]
    if not shapes:
        return "other"
    batch = max(shapes, key=lambda s: s[0] * s[1] * s[2] * s[3])[0]
    acts = [s for s in shapes if s[0] == batch]
    if acts:
        n, h, w, c = max(acts, key=lambda s: (s[1] * s[2], s[3]))
    else:
        n, h, w, c = shapes[0]
    out_part = hlo_output_part(hlo_text)
    out_shapes = [tuple(int(g) for g in m.groups())
                  for m in _SHAPE_RE.finditer(out_part)]
    if out_shapes and all(s[0] != batch for s in out_shapes):
        kind = "wgrad"
    elif "transpose(" in tf_op:
        kind = "dgrad"
    else:
        kind = "fprop"
    return f"{h}x{w}x{c}:{kind}"


def aggregate(events: list[dict], n_steps: int) -> dict:
    """events: [{name, display, category, dur_ps, flops, bytes,
    tf_op?}] over ``n_steps`` captured steps.  Returns {categories,
    conv_buckets, top_ops, totals} with per-STEP ms and measured rates.
    Rows shorter than ``SUB_RESOLUTION_MS`` per step carry
    ``rates_unreliable: true`` and suppressed (0.0) rates."""
    cats = defaultdict(lambda: [0, 0, 0, 0])       # dur, flops, bytes, n
    convs = defaultdict(lambda: [0, 0, 0, 0])
    ops = defaultdict(lambda: [0, 0, 0, 0, ""])
    for e in events:
        for table, key in ((cats, e["category"]),
                           (ops, e["display"])):
            a = table[key]
            a[0] += e["dur_ps"]
            a[1] += e["flops"]
            a[2] += e["bytes"]
            a[3] += 1
            if table is ops:
                a[4] = e["category"]
        if e["category"] == "convolution fusion":
            a = convs[conv_spatial_bucket(e["name"], e.get("tf_op", ""))]
            a[0] += e["dur_ps"]
            a[1] += e["flops"]
            a[2] += e["bytes"]
            a[3] += 1

    def row(d, f, b, n, *extra):
        ms = d / 1e9 / n_steps
        sec = d / 1e12
        unreliable = ms < SUB_RESOLUTION_MS
        return {
            "ms_per_step": round(ms, 3),
            "tflops_per_s": (round(f / sec / 1e12, 1)
                             if d and not unreliable else 0.0),
            "gbytes_per_s": (round(b / sec / 1e9, 1)
                             if d and not unreliable else 0.0),
            "events_per_step": n // n_steps,
            **({"rates_unreliable": True} if unreliable else {}),
            **({"category": extra[0]} if extra else {}),
        }

    total_dur = sum(v[0] for v in cats.values())
    total_flops = sum(v[1] for v in cats.values())
    return {
        "totals": {
            "device_busy_ms_per_step": round(total_dur / 1e9 / n_steps, 3),
            "achieved_tflops_per_s": round(
                total_flops / (total_dur / 1e12) / 1e12, 1)
            if total_dur else 0.0,
            "n_steps": n_steps,
        },
        "categories": {
            k: {**row(*v), "pct": round(100 * v[0] / total_dur, 1)}
            for k, v in sorted(cats.items(), key=lambda kv: -kv[1][0])
        },
        "conv_buckets": {
            k: {**row(*v), "pct": round(100 * v[0] / total_dur, 1)}
            for k, v in sorted(convs.items(), key=lambda kv: -kv[1][0])
        },
        "top_ops": [
            {"op": k, **row(*v[:4], v[4]),
             "pct": round(100 * v[0] / total_dur, 1)}
            for k, v in sorted(ops.items(), key=lambda kv: -kv[1][0])[:25]
        ],
    }


def roofline(report: dict, peak_tflops: float, peak_hbm_gbps: float) -> dict:
    """Per-slice roofline adjudication: a slice running at X TF/s while
    streaming Y GB/s has an HBM-implied ceiling of
    X * (peak_hbm / Y) — if that ceiling is close to X, the slice is
    bandwidth-bound and X is ~its achievable rate at this arithmetic
    intensity."""
    out = {}
    for k, c in report["categories"].items():
        if c.get("rates_unreliable"):
            out[k] = {"hbm_fraction": None, "mxu_fraction": None,
                      "hbm_implied_tflops_ceiling": None,
                      "rates_unreliable": True}
            continue
        gbs, tfs = c["gbytes_per_s"], c["tflops_per_s"]
        hbm_frac = gbs / peak_hbm_gbps if peak_hbm_gbps else 0.0
        implied = tfs / hbm_frac if hbm_frac > 0 else float("inf")
        entry = {
            "hbm_fraction": round(hbm_frac, 3),
            "mxu_fraction": round(tfs / peak_tflops, 3)
            if peak_tflops else 0.0,
            "hbm_implied_tflops_ceiling": (round(implied, 1)
                                           if implied != float("inf")
                                           else None),
        }
        # bytes_accessed counts every operand touch, including
        # VMEM-resident re-reads and async waits charged against tiny
        # on-stream durations — a "fraction" well past peak is an
        # accounting artifact, not a measurement of HBM streaming.
        if hbm_frac > 1.25:
            entry["accounting_artifact"] = True
            entry["hbm_implied_tflops_ceiling"] = None
        out[k] = entry
    return out


def pick_n_steps(line_event_counts: dict) -> int:
    """Number of captured steps from a {line_name: n_events} map.

    Prefers the 'XLA Modules' line (one event per module execution);
    falls back to 'Steps'; with neither, warns and returns 1 so the
    per-step columns are at least labelled honestly as per-capture.
    (Round-4 advisor: the old truthiness one-liner silently collapsed
    both absent and empty to 1 with no warning.)
    """
    n = line_event_counts.get("XLA Modules", 0)
    if n:
        return n
    n = line_event_counts.get("Steps", 0)
    if n:
        return n
    print("WARNING: no 'XLA Modules'/'Steps' line in this capture — "
          "treating the whole capture as ONE step; per-step columns "
          "are really per-capture", file=sys.stderr)
    return 1


# -- proto extraction -----------------------------------------------------

def _load_xspace(path: str):
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError as e:  # pragma: no cover
        raise SystemExit(
            "needs tensorflow's tsl xplane proto bindings "
            f"(import failed: {e}); on a box without tensorflow, copy "
            "the .xplane.pb to one that has it") from e
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def extract_device_events(space) -> tuple[list[dict], int, dict]:
    """(events, n_steps, device_info) from the first TPU/GPU device
    plane.  Events come from the 'XLA Ops' line; n_steps from the
    'XLA Modules' line (module executions captured)."""
    plane = None
    for p in space.planes:
        if "/device:" in p.name and "CUSTOM" not in p.name and any(
                ln.events for ln in p.lines):
            plane = p
            break
    if plane is None:
        raise SystemExit(
            "no device plane with events in this xplane — the capture "
            "has host threads only (the round-3 failure mode); re-trace "
            "with the step running on the device backend")
    sm, em = plane.stat_metadata, plane.event_metadata

    def stat_val(s):
        return (s.str_value or s.int64_value or s.uint64_value
                or s.double_value)

    info = {"plane": plane.name}
    for s in plane.stats:
        n = sm[s.metadata_id].name
        if n in ("device_type_string", "peak_teraflops_per_second",
                 "peak_hbm_bw_gigabytes_per_second"):
            info[n] = stat_val(s)

    lines = {ln.name: ln for ln in plane.lines}
    n_steps = pick_n_steps({ln.name: len(ln.events) for ln in plane.lines})
    events = []
    for e in lines["XLA Ops"].events:
        md = em[e.metadata_id]
        st = {sm[s.metadata_id].name: stat_val(s) for s in md.stats}
        events.append({
            "name": md.name,
            "display": md.display_name,
            "category": st.get("hlo_category", "?"),
            "dur_ps": e.duration_ps,
            "flops": st.get("flops", 0) or 0,
            "bytes": st.get("bytes_accessed", 0) or 0,
            "tf_op": st.get("tf_op", "") or "",
        })
    return events, n_steps, info


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise SystemExit(f"no *.xplane.pb under {path}")
    return hits[-1]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("path", help="profile dir or .xplane.pb file")
    ap.add_argument("--out", default=None, help="write full JSON here")
    ap.add_argument("--copies", action="store_true",
                    help="attribute every copy-start/done event to its "
                         "producer (direction x size-class x shape)")
    args = ap.parse_args()

    pb = find_xplane(args.path)
    events, n_steps, info = extract_device_events(_load_xspace(pb))
    report = aggregate(events, n_steps)
    peak_tf = float(info.get("peak_teraflops_per_second", 0) or 0)
    peak_bw = float(info.get("peak_hbm_bw_gigabytes_per_second", 0) or 0)
    rl = roofline(report, peak_tf, peak_bw)

    t = report["totals"]
    print(f"# {info.get('device_type_string', '?')} — peak "
          f"{peak_tf:.0f} TF/s, HBM {peak_bw:.0f} GB/s ({info['plane']})")
    print(f"# {t['n_steps']} steps captured, device-busy "
          f"{t['device_busy_ms_per_step']} ms/step, achieved "
          f"{t['achieved_tflops_per_s']} TF/s over device-busy time")
    print(f"{'category':<26}{'ms/step':>9}{'%':>7}{'TF/s':>8}{'GB/s':>8}"
          f"{'%HBM':>7}{'ceilTF/s':>10}")
    for k, c in report["categories"].items():
        r = rl[k]
        ceil = r["hbm_implied_tflops_ceiling"]
        if c.get("rates_unreliable"):
            print(f"{k[:25]:<26}{c['ms_per_step']:9.3f}{c['pct']:7.1f}"
                  f"{'(sub-resolution: rates suppressed)':>40}")
            continue
        frac = r["hbm_fraction"] or 0.0
        art = "*" if r.get("accounting_artifact") else ""
        print(f"{k[:25]:<26}{c['ms_per_step']:9.3f}{c['pct']:7.1f}"
              f"{c['tflops_per_s']:8.1f}{c['gbytes_per_s']:8.0f}"
              f"{100 * frac:6.1f}{art:1}"
              f"{(f'{ceil:10.1f}' if ceil else '         -')}")
    print(f"\n{'conv bucket (HxWxC:kind)':<26}{'ms/step':>9}{'%':>7}"
          f"{'TF/s':>8}{'GB/s':>8}")
    for k, c in report["conv_buckets"].items():
        print(f"{k:<26}{c['ms_per_step']:9.3f}{c['pct']:7.1f}"
              f"{c['tflops_per_s']:8.1f}{c['gbytes_per_s']:8.0f}")
    copies = None
    if args.copies:
        copies = attribute_copies(events, n_steps)
        print(f"\n== copy attribution: copy-done "
              f"{copies['copy_done_ms_per_step']} ms/step over "
              f"{copies['copy_done_events_per_step']} events (+ "
              f"copy-start {copies['copy_start_ms_per_step']} ms) ==")
        print(f"{'ms/step':>8}{'n':>6}{'us/ea':>7}{'MB/step':>9}"
              f"{'%copy':>7}  producer")
        for r in copies["rows"][:20]:
            print(f"{r['ms_per_step']:8.3f}{r['events_per_step']:6d}"
                  f"{r['us_per_event']:7.2f}{r['mbytes_per_step']:9.1f}"
                  f"{r['pct_of_copy_done']:7.1f}  {r['producer']}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"device": info, "report": report, "roofline": rl,
                       **({"copy_attribution": copies} if copies
                          else {}),
                       "source": pb}, f, indent=1)
        print(f"\nwrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
