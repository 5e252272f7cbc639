"""A/B attribution reports for the ResNet-50 step's levers.

Two subcommands:

``report BEFORE.json AFTER.json``
    Diff two ``analyze_xplane.py --out`` accounts: per-category
    ms/step deltas, totals, and (when both captured with ``--copies``)
    per-producer copy-done deltas.  This is the before/after evidence
    format every optimization in this repo must ship with.

``expected``
    Write the committed expected-delta table for the ResNet-50 levers
    (artifacts/xla_sweep_expected.md) — the prediction is on record
    BEFORE the chip run that grades it, so the after-capture grades
    the model of the step, not just the step.

``ab_report`` is unit-tested in tests/test_xplane_tool.py without
tensorflow or a chip.

Usage:
    python tools/xla_sweep.py report before.json after.json [--out ab.json]
    python tools/xla_sweep.py expected --out artifacts/xla_sweep_expected.md
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _get_report(account: dict) -> dict:
    """Accept a full ``analyze_xplane --out`` dict or a bare report."""
    return account.get("report", account)


def ab_report(before: dict, after: dict) -> dict:
    """Per-category (and per-copy-producer) delta of two accounts."""
    rb, ra = _get_report(before), _get_report(after)
    cats = {}
    for k in {**rb["categories"], **ra["categories"]}:
        b = rb["categories"].get(k, {})
        a = ra["categories"].get(k, {})
        bm = b.get("ms_per_step", 0.0)
        am = a.get("ms_per_step", 0.0)
        cats[k] = {
            "before_ms": bm, "after_ms": am,
            "delta_ms": round(am - bm, 3),
            "before_events": b.get("events_per_step", 0),
            "after_events": a.get("events_per_step", 0),
        }
    tb = rb["totals"]["device_busy_ms_per_step"]
    ta = ra["totals"]["device_busy_ms_per_step"]
    out = {
        "totals": {
            "before_ms": tb, "after_ms": ta,
            "delta_ms": round(ta - tb, 3),
            "delta_pct": round(100 * (ta - tb) / tb, 1) if tb else 0.0,
        },
        "categories": dict(sorted(cats.items(),
                                  key=lambda kv: kv[1]["delta_ms"])),
    }
    cb = before.get("copy_attribution")
    ca = after.get("copy_attribution")
    if cb and ca:
        rows_b = {r["producer"]: r for r in cb["rows"]}
        rows_a = {r["producer"]: r for r in ca["rows"]}
        copies = {}
        for k in {**rows_b, **rows_a}:
            bm = rows_b.get(k, {}).get("ms_per_step", 0.0)
            am = rows_a.get(k, {}).get("ms_per_step", 0.0)
            copies[k] = {"before_ms": bm, "after_ms": am,
                         "delta_ms": round(am - bm, 3)}
        out["copy_producers"] = dict(
            sorted(copies.items(), key=lambda kv: kv[1]["delta_ms"]))
        out["copy_totals"] = {
            "before_ms": cb["copy_done_ms_per_step"],
            "after_ms": ca["copy_done_ms_per_step"],
            "delta_ms": round(ca["copy_done_ms_per_step"]
                              - cb["copy_done_ms_per_step"], 3),
        }
    return out


def print_report(rep: dict) -> None:
    t = rep["totals"]
    print(f"# device-busy {t['before_ms']} -> {t['after_ms']} ms/step "
          f"({t['delta_pct']:+.1f}%)")
    print(f"{'category':<26}{'before':>9}{'after':>9}{'delta':>9}"
          f"{'ev b/a':>12}")
    for k, c in rep["categories"].items():
        print(f"{k[:25]:<26}{c['before_ms']:9.3f}{c['after_ms']:9.3f}"
              f"{c['delta_ms']:+9.3f}"
              f"{c['before_events']:>6}/{c['after_events']:<5}")
    if "copy_producers" in rep:
        ct = rep["copy_totals"]
        print(f"\n# copy-done {ct['before_ms']} -> {ct['after_ms']} "
              f"ms/step ({ct['delta_ms']:+.3f})")
        for k, c in list(rep["copy_producers"].items())[:15]:
            print(f"{c['before_ms']:9.3f}{c['after_ms']:9.3f}"
                  f"{c['delta_ms']:+9.3f}  {k}")


EXPECTED_MD = """\
# Expected deltas for the ResNet-50 step's levers

Committed BEFORE the chip run that grades them.  `python3
benchmarks/run.py --workload resnet50_b128_x1 --seed N --seconds 25
--trace 1` reads the step's classes (`conv_share`, `breakdown`) and the
end-to-end rate for either leg.  For the per-op account capture a
before/after pair of profiles with `THEANOMPI_TPU_PROFILE=DIR python -m
theanompi_tpu.launcher BSP -m resnet50 --epochs 1` (the `after` leg
adds the lever: `--set bn_act_impl=pallas`, or `XLA_FLAGS=...` in the
environment), then grade this table with

    python tools/analyze_xplane.py DIR/before --copies --out /tmp/b.json
    python tools/analyze_xplane.py DIR/after  --copies --out /tmp/a.json
    python tools/xla_sweep.py report /tmp/b.json /tmp/a.json

Baseline: the r3 capture's 46.90 ms device-busy step
(`artifacts/mfu_account.json`, `artifacts/copy_attribution_r03.json`;
older stack, JAX 0.4.x).  None of these has been graded yet.

| lever | slice attacked (r3 measured) | expected after | basis |
|---|---|---|---|
| fused scale-bias-relu epilogue (`bn_act_impl='pallas'`, ops/fused_bn.py) | loop fusion 5.81 ms / 269 ev (adds+relu 678-992 GB/s) | 4.3-5.0 ms | the 3 stage-1 `BottleneckBlock_*/add` exit epilogues alone are 2.7 ms at 83% HBM; fusing BN-apply+add+relu into one stream removes one full read+write of each exit activation (~1/3 of those bytes). Fwd-only win — bwd mask recompute streams the same bytes XLA's does |
| `--xla_tpu_enable_latency_hiding_scheduler=true` | 1 146 param-vec MSA copies 1.42 ms (latency-bound, ~1-7 us each) + async-done 0.62 ms | 0.7-1.2 ms combined | scheduler overlaps the tiny prefetches under the conv stream; per-copy latency doesn't shrink, exposure does |
| `--xla_tpu_scoped_vmem_limit_kib=65536` | activation spill prefetch/writeback ~0.9 ms | 0.5-0.8 ms | bigger scoped VMEM keeps stage-exit activations resident. May TRADE against conv rate (less pipelining headroom) — that is why every flag point re-measures throughput, not just the account |

The fourth lever this table once held — an argmax-saving Pallas
max-pool backward against the 0.761 ms select-and-scatter — is gone:
Mosaic (JAX 0.9.0 / libtpu 0.0.34) refuses the kernel's stride-2
slices on tiled dimensions, the repair was not local, and PR 21
removed it with its knob (docs/KERNELS.md).

**Not graded by a single-step pair — staged-batch donation
(`donate_batch`, parallel/bsp.py).** It only changes the stacked
(k>1 / grad-accum) programs, and a harness that replays one staged
batch necessarily opts out with `donate_batch=False` — a replayed
batch cannot be donated.  Grade that lever from a prefetcher-fed k>1
`run_bsp_session` run — e.g. `THEANOMPI_TPU_PROFILE=dir python -m
theanompi_tpu.launcher BSP -m cifar10 --epochs 1 --set
steps_per_call=4`.  Until then the donation is asserted
structurally by the lowering tests
(tests/test_multi_step.py::TestStagedBatchDonation).

Net expectation for the fused-epilogue pair (donation excluded):
device-busy 46.9 -> 45.4-46.1 ms/step, convs unchanged at ~93% of
their HBM-implied ceiling.  Anything outside these ranges means the
model of the step is wrong somewhere — find where before believing
the number.
"""


def main() -> int:
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="cmd", required=True)
    r = sub.add_parser("report")
    r.add_argument("before")
    r.add_argument("after")
    r.add_argument("--out", default=None)
    x = sub.add_parser("expected")
    x.add_argument("--out",
                   default=os.path.join(REPO, "artifacts",
                                        "xla_sweep_expected.md"))
    args = ap.parse_args()

    if args.cmd == "report":
        with open(args.before) as fh:
            before = json.load(fh)
        with open(args.after) as fh:
            after = json.load(fh)
        rep = ab_report(before, after)
        print_report(rep)
        if args.out:
            with open(args.out, "w") as fh:
                json.dump(rep, fh, indent=1)
            print(f"\nwrote {args.out}")
        return 0
    with open(args.out, "w") as fh:
        fh.write(EXPECTED_MD)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
