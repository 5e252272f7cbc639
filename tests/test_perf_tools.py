"""CLI smoke tests for the perf tooling: the probes a chip run
depends on must not rot between rounds (each runs as a REAL
subprocess, synthetic data, tiny shapes)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_tool(args, timeout=540):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, timeout=timeout, env=env,
                          cwd=REPO_ROOT)


def test_host_pipeline_probe_smoke():
    r = _run_tool([os.path.join(REPO_ROOT, "tools/host_pipeline_probe.py"),
                   "--batch", "16", "--batches", "4", "--store", "40",
                   "--crop", "32"])
    assert r.returncode == 0, r.stdout + r.stderr
    recs = [json.loads(line) for line in r.stdout.splitlines() if line]
    assert [rec["mode"] for rec in recs] == ["device", "host"]
    assert all(rec["img_per_sec"] > 0 and rec["synthetic"] for rec in recs)
    assert recs[0]["dtype"] == "uint8" and recs[1]["dtype"] == "float32"


@pytest.mark.slow
def test_bench_lm_smoke():
    r = _run_tool([os.path.join(REPO_ROOT, "tools/bench_lm.py"),
                   "--batch", "2", "--seq", "32", "--layers", "1",
                   "--d-model", "32", "--heads", "2", "--steps", "2",
                   "--dtype", "float32"])
    assert r.returncode == 0, r.stdout + r.stderr
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "transformer_lm_tokens_per_sec_per_chip"
    # the 1-layer d=32 smoke model's GF/seq rounds to 0.00 at 2dp —
    # assert shape/liveness, not magnitude
    assert rec["value"] > 0 and rec["detail"]["step_ms"] > 0
    assert rec["detail"]["train_gflops_per_seq"] >= 0


@pytest.mark.slow
def test_conv_ladder_smoke():
    r = _run_tool([os.path.join(REPO_ROOT, "tools/conv_ladder.py"),
                   "--batch", "1", "--iters", "1", "--dtype", "float32"],
                  timeout=560)
    assert r.returncode == 0, r.stdout + r.stderr
    lines = [json.loads(line) for line in r.stdout.splitlines() if line]
    summary = lines[-1]
    assert summary["event"] == "ladder_summary"
    # canonical ResNet-50: 8.18 GF/img fwd in 2xMAC units
    assert abs(summary["sum_gflops_fwd"] - 8.18) < 0.2


def test_bench_exchange_buckets_shards_conflict():
    """ISSUE 13 satellite: --buckets with --shards must fail FAST with
    the typed FlagConflict (exit 2) instead of silently ignoring one
    flag, both in-process and as a subprocess."""
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    try:
        import bench_exchange
    finally:
        sys.path.pop(0)
    with pytest.raises(bench_exchange.FlagConflict) as ei:
        bench_exchange.main(["--buckets", "4", "--shards", "2"])
    assert ei.value.code == 2
    r = _run_tool([os.path.join(REPO_ROOT, "tools/bench_exchange.py"),
                   "--buckets", "4", "--shards", "2"], timeout=120)
    assert r.returncode == 2
    assert "mutually exclusive" in r.stderr
