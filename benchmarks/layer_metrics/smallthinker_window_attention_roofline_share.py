"""smallthinker_window_attention_roofline_share (%, device trace): the
least time the chip could take for the window attention kernels' calls
of the traced steps (the larger of their FLOPs over the bf16 peak and
their bytes over the HBM peak; flops/smallthinker.py, peaks.py) over the
time those calls took in the trace.  The calls are the custom calls
NAMED ``smallthinker_window_attention_fwd``, ``..._bwd_kv`` and
``..._bwd_q`` (ops/attention.py's streamed kernels under
models/smallthinker.py ``Attention``), and they are COUNTED FROM THE
TRACE: a forward recomputed under ``remat`` is a call like any other,
and a backward is one ``bwd_kv`` and one ``bwd_q`` kernel.  Work a
call: the products over the pairs the window leaves, 2 forward and 5
backward (the backward's score recomputation is the algorithm's own;
its second recomputation in the dQ kernel is not counted); bytes: q and
o at 28 query heads, k and v at 4.

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

#: matched against an op's name; {kind} is ``window`` or ``global``,
#: group 1 says which kernel
PATTERN = r"smallthinker_{kind}_attention_(fwd|bwd_kv|bwd_q)"
#: where a call's shapes are stated
CONFIG = os.path.join(BENCH, "configs", "smallthinker_21b.json")
TRAFFIC = os.path.join(BENCH, "traffic", "lm_s16384_seg2_x1.json")


def calls_in(trace, kind: str):
    """``{"fwd" | "bwd_kv" | "bwd_q": [calls, ns]}`` of the lowest
    chip's named calls inside the traced window."""
    rx = re.compile(PATTERN.format(kind=kind))
    lo, hi = trace.window
    found = {"fwd": [0, 0.0], "bwd_kv": [0, 0.0], "bwd_q": [0, 0.0]}
    for name, _category, start, end in trace.device_ops[
            min(trace.device_ops)]:
        hit = rx.search(name)
        inside = min(end, hi) - max(start, lo)
        if hit and inside > 0:
            found[hit.group(1)][0] += 1
            found[hit.group(1)][1] += inside
    return found


def call_shape(kind: str):
    with open(CONFIG) as f:
        model = json.load(f)["model"]["kwargs"]
    with open(TRAFFIC) as f:
        traffic = json.load(f)
    return dict(batch=traffic["batch_per_chip"], heads=model["n_heads"],
                kv_heads=model["n_kv_heads"], head_dim=model["head_dim"],
                seq_len=traffic["model_kwargs"]["seq_len"],
                window=model["window"] if kind == "window" else None)


def share(run, kind: str):
    """The roofline share of the ``kind`` attention's calls."""
    if run.trace is None or not run.on_device:
        return None
    calls = calls_in(run.trace, kind)
    kernel_s = sum(ns for _, ns in calls.values()) / 1e9
    if kernel_s <= 0:
        return None
    spec = importlib.util.spec_from_file_location(
        "bench_flops_smallthinker", os.path.join(BENCH, "flops",
                                                 "smallthinker.py"))
    flops_lib = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(flops_lib)
    shape = call_shape(kind)
    passes = {"fwd": calls["fwd"][0], "bwd": calls["bwd_q"][0]}
    flops = sum(n * flops_lib.attention_flops(which=which, **shape)
                for which, n in passes.items())
    moved = sum(n * flops_lib.attention_bytes(which=which, **shape)
                for which, n in passes.items())
    least_s = max(flops / (run.peak["bf16_tflops"] * 1e12),
                  moved / (run.peak["hbm_gb_per_s"] * 1e9))
    return 100.0 * least_s / kernel_s


def read(run):
    return share(run, "window")
