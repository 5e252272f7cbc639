"""CLI smoke test for the per-op deep-dive tooling that stays beside
the benchmark (a REAL subprocess, synthetic data, tiny shapes)."""

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
