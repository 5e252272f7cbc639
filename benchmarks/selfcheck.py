"""CPU self-check of the benchmark: run it by hand after changing it.

    JAX_PLATFORMS=cpu python3 benchmarks/selfcheck.py [--reducers-only]

1. Every trace reducer on a hand-made list of events
   (``benchmarks/fixtures/handmade_events.json``) whose answers were
   worked out by hand and are written in that file, and on a slice of a
   trace recorded on the chip (``benchmarks/fixtures/*_chip_events.json``)
   whose answers are pinned there.
2. The harness end to end at the ``dry_run`` sizes, on one and on four
   virtual CPU devices: the last line has the contract's keys, and no
   metric but a program counter carries a number.

It is not a tier-1 test (this PR may add none): a later PR may move it
under ``tests/``.  Exit code 0 means every check held.
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def check(condition: bool, what: str) -> None:
    if not condition:
        raise SystemExit(f"selfcheck FAILED: {what}")
    print(f"ok: {what}")


def close(got: float, want: float, what: str, rel: float = 1e-9) -> None:
    check(abs(got - want) <= rel * max(1.0, abs(want)),
          f"{what}: got {got!r}, want {want!r}")


def check_reducers() -> None:
    from benchmarks import trace as trace_lib

    for path in sorted(glob.glob(os.path.join(HERE, "fixtures",
                                              "*_events.json"))):
        with open(path) as f:
            fixture = json.load(f)
        trace = trace_lib.from_events(
            {int(chip): [tuple(op) for op in ops]
             for chip, ops in fixture["device_ops"].items()},
            [tuple(span) for span in fixture["host_spans"]])
        name = os.path.basename(path)
        want = fixture["expected"]
        steps = fixture["steps"]
        close(trace.window_ns, want["window_ns"], f"{name} window_ns")
        close(trace_lib.busy_ns(trace), want["busy_ns"], f"{name} busy_ns")
        close(trace_lib.idle_share(trace), want["idle_share"],
              f"{name} idle_share")
        close(trace_lib.device_ms_per_step(trace, steps),
              want["device_ms_per_step"], f"{name} device_ms_per_step")
        for pattern, share in want["class_share"].items():
            close(trace_lib.class_share(trace, pattern), share,
                  f"{name} class_share {pattern!r}")
        for pattern, ms in want.get("exposed_ms_per_step", {}).items():
            close(trace_lib.exposed_ms_per_step(trace, pattern, steps), ms,
                  f"{name} exposed_ms_per_step {pattern!r}")
        if "idle_gaps" in want:
            got = trace_lib.idle_gaps(trace, len(want["idle_gaps"]))
            check([g[0] for g in got] == [g[0] for g in want["idle_gaps"]],
                  f"{name} idle gaps named {[g[0] for g in got]}")
            for g, w in zip(got, want["idle_gaps"]):
                close(g[1], w[1], f"{name} idle gap {g[0]}")
        if "top_op" in want:
            top = trace_lib.top_ops(trace, 1)[0]
            check(top[0] == want["top_op"][0], f"{name} top op {top[0]}")
            close(top[1], want["top_op"][1], f"{name} top op seconds")


def check_dry_run(workload: str, devices: int, trace: int) -> None:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         workload, "--seed", "2147483659", "--seconds", "2", "--trace",
         str(trace)],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    what = f"{workload} on {devices} CPU device(s), --trace {trace}"
    check(done.returncode == 0, f"{what}: exit code {done.returncode}\n"
          + done.stderr[-2000:])
    result = json.loads(done.stdout.strip().splitlines()[-1])
    check(RESULT_KEYS <= set(result), f"{what}: keys {sorted(result)}")
    check(DEVICE_KEYS <= set(result["device"]), f"{what}: device keys")
    check(result["device"]["platform"] == "cpu"
          and result["device"]["count"] == devices, f"{what}: device named")
    check(result["correct"] is True and result["failed"] == 0
          and result["attempted"] > 0, f"{what}: correct, nothing failed")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    sources = {m["name"]: m["source"]
               for m in bench["end_to_end"] + bench["per_layer"]}
    check(bool(result["metrics"]), f"{what}: some metric on the line")
    for name, metric in result["metrics"].items():
        if sources[name] == "program_counter":
            continue
        check(metric["value"] is None,
              f"{what}: {name} carries no CPU number")
    declared = {m["name"] for m in
                (bench["per_layer"] if trace else bench["end_to_end"])
                if workload in m.get("workloads", [workload])}
    # a traced dry run has no device to read: those readers return
    # nothing and are left off the line
    on_line = set(result["metrics"])
    check(on_line == declared if not trace else on_line <= declared,
          f"{what}: metrics on the line {sorted(on_line)}, declared for "
          f"the cell {sorted(declared)}")


def main() -> int:
    check_reducers()
    if "--reducers-only" not in sys.argv:
        check_dry_run("resnet50_b128_x1", 1, 0)
        check_dry_run("resnet50_b128_x4", 4, 1)
        check_dry_run("gpt2m_s1024_x1", 1, 1)
        check_dry_run("gpt2m_s1024_x1", 1, 0)
    print("selfcheck: all held")
    return 0


if __name__ == "__main__":
    sys.exit(main())
