"""qwen3_next_delta_rule_roofline_share (%, device trace): the least
time the chip could take for the chunked gated delta rule's work in the
traced steps (the larger of its FLOPs over the bf16 peak and its bytes
over the HBM peak; flops/qwen3_next_delta_rule.py, peaks.py) over the
device time of the leaf ops under the scope
``qwen3_next/linear_attention/delta_rule`` in the trace
(models/qwen3_next.py GatedDeltaNetMixer; the step program's own map of
its ops, as ``qwen3_next_delta_rule_share`` reads it).

The work is COUNTED FROM THE CONFIGURATION, not from the trace: the
rule has no kernel to count calls of.  A traced step runs every Gated
DeltaNet layer's rule forward, once more recomputed where the
configuration sets ``remat``, and backward; the traced steps are the
segment the harness traced.  The count is of the work whatever
implements it, so a later kernel under the same scope is judged by the
same yardstick.

The shapes are the one cell's that lists this metric in BENCHMARK.json
(its configuration's and its traffic's files, below).  Returns None
wherever there is nothing to read: no trace, no device, no map of the
step, no op under the scope (a program that lacks the model).
"""

import importlib.util
import json
import os
import re

from benchmarks import scope_shares

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)

#: searched in the op's scope
SCOPE = r"(^|/)qwen3_next/linear_attention/delta_rule(/|$)"
#: where the shapes are stated
CONFIG = os.path.join(BENCH, "configs", "qwen3_next_80b.json")
TRAFFIC = os.path.join(BENCH, "traffic", "lm_s2048_seg4_x1.json")


def scope_ns(run, mapped):
    """Nanoseconds of the lowest chip's window in leaf ops whose mapped
    scope matches ``SCOPE`` (their union: overlapping ops once)."""
    lib, rx = run.trace_lib, re.compile(SCOPE)
    hit = []
    for name, category, start, end in run.trace.device_ops[
            min(run.trace.device_ops)]:
        if category.split(" ", 1)[0] in scope_shares.CONTAINERS:
            continue
        phase, scope = mapped.get(name, (None, None))
        if phase is not None and rx.search(scope):
            hit.append((start, end))
    return lib.total(lib.union(lib.clip(hit, run.trace.window)))


def passes():
    """``(the shape of one pass, {pass: passes a step})``."""
    with open(CONFIG) as f:
        config = json.load(f)
    with open(TRAFFIC) as f:
        traffic = json.load(f)
    model = config["model"]["kwargs"]
    interval = model["full_attention_interval"]
    linear = sum(1 for i in range(model["n_layers"]) if (i + 1) % interval)
    shape = dict(batch=traffic["batch_per_chip"],
                 seq_len=traffic["model_kwargs"]["seq_len"],
                 heads=model["linear_value_heads"],
                 key_dim=model["linear_key_dim"],
                 value_dim=model["linear_value_dim"], chunk=model["chunk"])
    remat = bool(config["model_config"].get("remat"))
    return shape, {"fwd": linear * (2 if remat else 1), "bwd": linear}


def read(run):
    if run.trace is None or not run.on_device:
        return None
    mapped = scope_shares.step_map(run)
    if mapped is None:
        return None
    spent_s = scope_ns(run, mapped) / 1e9
    if spent_s <= 0:
        return None
    spec = importlib.util.spec_from_file_location(
        "bench_flops_qwen3_next_delta_rule",
        os.path.join(BENCH, "flops", "qwen3_next_delta_rule.py"))
    flops_lib = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(flops_lib)
    shape, per_step = passes()
    steps = run.traced_steps
    flops = steps * sum(n * flops_lib.delta_rule_flops(which=which, **shape)
                        for which, n in per_step.items())
    moved = steps * sum(n * flops_lib.delta_rule_bytes(which=which, **shape)
                        for which, n in per_step.items())
    least_s = max(flops / (run.peak["bf16_tflops"] * 1e12),
                  moved / (run.peak["hbm_gb_per_s"] * 1e9))
    return 100.0 * least_s / spent_s
