"""ssd_roofline_share (%, device trace): the least time the chip could
take for the Mamba-2 scan's kernel calls of the traced steps (the larger
of their FLOPs over the bf16 peak and their bytes over the HBM peak;
flops/nemotron_h_ssd.py, peaks.py) over the time those calls took in
the trace.  The calls are the custom calls NAMED ``nemotron_h_ssd_fwd``
and ``nemotron_h_ssd_bwd`` (theanompi_tpu/ops/ssd.py under
models/nemotron_h.py Mamba2Mixer), COUNTED FROM THE TRACE: a forward
recomputed under ``remat`` is a call like any other.  Work a call: the
products its body runs, the forward's four and the backward's as
written (its in-chunk matrices remade); bytes: each HBM operand and
result once, the forward's saved states included.

The shapes of a call are the one cell's that lists this metric in
BENCHMARK.json (its configuration's and its traffic's files, below).
Returns None wherever there is nothing to read: no trace, no device, a
trace without such a call (a program whose scan is not the kernels).
"""

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)

#: matched against an op's name; group 1 says which pass
PATTERN = r"nemotron_h_ssd_(fwd|bwd)"
#: where a call's shapes are stated
CONFIG = os.path.join(BENCH, "configs", "nemotron_twotower_30b.json")
TRAFFIC = os.path.join(BENCH, "traffic", "lm_s2048_seg4_x1.json")


def calls_in(trace):
    """``{"fwd": [calls, ns], "bwd": [calls, ns]}`` of the lowest chip's
    named calls inside the traced window."""
    rx = re.compile(PATTERN)
    lo, hi = trace.window
    found = {"fwd": [0, 0.0], "bwd": [0, 0.0]}
    for name, _category, start, end in trace.device_ops[
            min(trace.device_ops)]:
        hit = rx.search(name)
        inside = min(end, hi) - max(start, lo)
        if hit and inside > 0:
            found[hit.group(1)][0] += 1
            found[hit.group(1)][1] += inside
    return found


def call_shape():
    with open(CONFIG) as f:
        model = json.load(f)["model"]["kwargs"]
    with open(TRAFFIC) as f:
        traffic = json.load(f)
    return dict(batch=traffic["batch_per_chip"],
                seq_len=traffic["model_kwargs"]["seq_len"],
                heads=model["mamba_heads"], head_dim=model["mamba_head_dim"],
                groups=model["n_groups"], state=model["state"],
                chunk=model["chunk"])


def read(run):
    if run.trace is None or not run.on_device:
        return None
    calls = calls_in(run.trace)
    kernel_s = sum(ns for _, ns in calls.values()) / 1e9
    if kernel_s <= 0:
        return None
    spec = importlib.util.spec_from_file_location(
        "bench_flops_nemotron_h_ssd",
        os.path.join(BENCH, "flops", "nemotron_h_ssd.py"))
    flops_lib = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(flops_lib)
    shape = call_shape()
    flops = sum(n * flops_lib.ssd_kernel_flops(which=which, **shape)
                for which, (n, _) in calls.items())
    moved = sum(n * flops_lib.ssd_kernel_bytes(which=which, **shape)
                for which, (n, _) in calls.items())
    least_s = max(flops / (run.peak["bf16_tflops"] * 1e12),
                  moved / (run.peak["hbm_gb_per_s"] * 1e9))
    return 100.0 * least_s / kernel_s
