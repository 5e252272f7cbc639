"""qwen3_next_attention_roofline_share (%, device trace): the least time
the chip could take for the head-256 attention kernel's calls of the
traced steps (the larger of their FLOPs over the bf16 peak and their
bytes over the HBM peak; flops/qwen3_next.py, peaks.py) over the time
those calls took in the trace.  The calls are the custom calls NAMED
``qwen3_next_attention_fwd`` and ``qwen3_next_attention_bwd``
(ops/attention.py under models/qwen3_next.py GatedAttentionMixer), and
they are COUNTED FROM THE TRACE: a forward recomputed under ``remat`` is
a call like any other.  Work a call: the products the causal mask
leaves, 2 forward and 5 backward (the backward's score recomputation is
the algorithm's own); bytes: q and o at 16 query heads, k and v at 2.

The shapes of a call are the one cell's that lists this metric in
BENCHMARK.json (its configuration's and its traffic's files, below).
Returns None wherever there is nothing to read: no trace, no device, a
trace without such a call (a program that lacks the model).
"""

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)

#: matched against an op's name; group 1 says which pass
PATTERN = r"qwen3_next_attention_(fwd|bwd)"
#: where a call's shapes are stated
CONFIG = os.path.join(BENCH, "configs", "qwen3_next_80b.json")
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
    return dict(batch=traffic["batch_per_chip"], heads=model["n_heads"],
                kv_heads=model["n_kv_heads"], head_dim=model["head_dim"],
                seq_len=traffic["model_kwargs"]["seq_len"])


def read(run):
    if run.trace is None or not run.on_device:
        return None
    calls = calls_in(run.trace)
    kernel_s = sum(ns for _, ns in calls.values()) / 1e9
    if kernel_s <= 0:
        return None
    spec = importlib.util.spec_from_file_location(
        "bench_flops_qwen3_next", os.path.join(BENCH, "flops",
                                               "qwen3_next.py"))
    flops_lib = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(flops_lib)
    shape = call_shape()
    flops = sum(n * flops_lib.attention_flops(which=which, **shape)
                for which, (n, _) in calls.items())
    moved = sum(n * flops_lib.attention_bytes(which=which, **shape)
                for which, (n, _) in calls.items())
    least_s = max(flops / (run.peak["bf16_tflops"] * 1e12),
                  moved / (run.peak["hbm_gb_per_s"] * 1e9))
    return 100.0 * least_s / kernel_s
