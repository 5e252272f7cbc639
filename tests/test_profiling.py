"""Profiler hook (SURVEY.md §5.1) + show_record output tool."""

import json
import os
import subprocess
import sys

import numpy as np


def test_step_profiler_writes_trace(tmp_path, mesh8):
    from theanompi_tpu.models.base import ModelConfig
    from theanompi_tpu.models.cifar10 import Cifar10_model
    from theanompi_tpu.rules.bsp import run_bsp_session
    from theanompi_tpu.data.cifar10 import Cifar10_data

    class Tiny(Cifar10_model):
        def build_data(self):
            return Cifar10_data(synthetic_n=256)

    cfg = ModelConfig(batch_size=2, n_epochs=1, print_freq=10**9,
                      compute_dtype="float32")
    m = Tiny(config=cfg, mesh=mesh8)
    trace_dir = str(tmp_path / "trace")
    run_bsp_session(m, max_epochs=1, checkpoint=False,
                    profile_dir=trace_dir)
    # jax.profiler writes plugins/profile/<ts>/*; just require non-empty
    found = [os.path.join(dp, f) for dp, _, fs in os.walk(trace_dir)
             for f in fs]
    assert found, f"no trace files under {trace_dir}"


def test_step_profiler_noop_without_dir(monkeypatch):
    from theanompi_tpu.utils.profiling import StepProfiler

    monkeypatch.delenv("THEANOMPI_TPU_PROFILE", raising=False)
    p = StepProfiler()
    assert not p.enabled
    p.maybe_start(); p.step(); p.stop()  # all no-ops


def test_show_record_tool(tmp_path):
    recs = [
        {"epoch": i, "wall_time_s": 10.0, "images_per_sec": 100.0 + i,
         "train_loss": 2.0 - 0.1 * i, "train_error": 0.5,
         "val_loss": 1.9 - 0.1 * i, "val_error": 0.4 - 0.02 * i,
         "time": {"calc": 8.0, "comm": 0.0, "wait": 0.5, "load": 0.2}}
        for i in range(5)
    ]
    with open(tmp_path / "record_rank0.jsonl", "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    out = subprocess.run(
        [sys.executable, os.path.join("tools", "show_record.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr
    assert "images/sec" in out.stdout and "train_loss" in out.stdout
    assert "4" in out.stdout  # last epoch row present


def test_step_profiler_context_manager_flushes_on_crash(tmp_path,
                                                        monkeypatch):
    # a crash mid-capture must still stop the trace (stop_trace is what
    # flushes the files) — the context manager guarantees it
    from theanompi_tpu.utils.profiling import StepProfiler

    calls = []
    monkeypatch.setattr("jax.profiler.start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr("jax.profiler.stop_trace",
                        lambda: calls.append(("stop",)))
    try:
        with StepProfiler(str(tmp_path), n_steps=100) as p:
            p.step()
            raise RuntimeError("mid-capture crash")
    except RuntimeError:
        pass
    assert calls == [("start", str(tmp_path)), ("stop",)]

    # and a no-dir profiler stays a no-op as a context manager too
    monkeypatch.delenv("THEANOMPI_TPU_PROFILE", raising=False)
    with StepProfiler() as p:
        p.step()
    assert not any(c[0] == "start" for c in calls[2:])


def test_step_profiler_spans_epochs(tmp_path, monkeypatch):
    # n_steps larger than one epoch: the trace must keep running into
    # the next epoch instead of silently truncating at the boundary
    from theanompi_tpu.utils.profiling import StepProfiler

    calls = []
    monkeypatch.setattr("jax.profiler.start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr("jax.profiler.stop_trace",
                        lambda: calls.append(("stop",)))
    p = StepProfiler(str(tmp_path), n_steps=5)
    p.maybe_start()
    for _ in range(3):   # epoch 0: 3 iters — must NOT stop
        p.step()
    assert calls == [("start", str(tmp_path))]
    for _ in range(2):   # epoch 1 continues the same trace
        p.step()
    assert calls[-1] == ("stop",)
    p.maybe_start()      # done: no restart
    assert sum(c[0] == "start" for c in calls) == 1
