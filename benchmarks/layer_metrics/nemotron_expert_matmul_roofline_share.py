"""nemotron_expert_matmul_roofline_share (%, device trace): the least
time the chip could take for the grouped expert products of the traced
steps (the larger of their FLOPs over the bf16 peak and their bytes over
the HBM peak; flops/nemotron_h.py: TWO products a row forward, peaks.py)
over the time the kernels took in the trace.  The rows are the ones the
kernels REALLY multiplied in the traced steps: the program counts them
each step and keeps its last flushes in-process
(theanompi_tpu/models/nemotron_h.py ``routing_log``), each stamped with
whether a profiler trace was being captured at the flush (the harness
traces one whole segment, its flush included).  This reader, which runs
in the harness's process after the window, takes the one entry flushed
under the trace.  It returns None wherever it cannot (no trace, a
program without the model or its log, no stamped entry or several, an
entry of another number of steps than the trace holds, no kernel time),
never an expected share: uneven routing moves the real one.
"""

import importlib
import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_file(*parts):
    """A benchmark file as a module, by path."""
    path = os.path.join(os.path.dirname(HERE), *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + parts[-1].replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def traced_entry(run):
    """The ``routing_log`` entry of the traced segment, or None."""
    try:
        log = importlib.import_module(
            "theanompi_tpu.models.nemotron_h").routing_log
    except (ImportError, AttributeError):
        return None
    traced = [entry for entry in log if entry.get("profiled")]
    if len(traced) != 1 or len(traced[0]["held_rows"]) != run.traced_steps:
        return None
    return traced[0]


def read(run):
    if run.trace is None or not run.on_device:
        return None
    entry = traced_entry(run)
    if entry is None:
        return None
    flops_lib = load_file("flops", "nemotron_h.py")
    pattern = load_file("layer_metrics",
                        "nemotron_expert_matmul_share.py").PATTERN
    held_count, d_model, expert_width = entry["expert_shape"]
    # held_rows: one number a step, summed over the expert layers, whose
    # matrices are all of one shape and moved once a layer and step
    rows = sum(entry["held_rows"])
    flops = flops_lib.expert_matmul_flops(
        rows=rows, d_model=d_model, expert_width=expert_width)
    moved = flops_lib.expert_matmul_bytes(
        rows=rows, layer_steps=entry["n_layers"] * len(entry["held_rows"]),
        held_count=held_count, d_model=d_model, expert_width=expert_width)
    least_s = max(flops / (run.peak["bf16_tflops"] * 1e12),
                  moved / (run.peak["hbm_gb_per_s"] * 1e9))
    kernel_s = (run.trace_lib.class_share(run.trace, pattern) / 100.0
                * run.trace_lib.busy_ns(run.trace) / 1e9)
    if kernel_s <= 0:
        return None
    return 100.0 * least_s / kernel_s
