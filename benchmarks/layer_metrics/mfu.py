"""mfu.* (%, host clock): the traced run's own whole-window throughput
(all its samples over all its seconds, less what the profiler took to
start and stop) x FLOPs per sample (benchmarks/flops/) over the chip's
published bf16 peak (benchmarks/peaks.py).  Throughput is per chip
already.  It decides nothing: the end-to-end metric does.
"""


def read(run):
    if not run.on_device:
        return None
    samples_per_s = run.throughput / run.units_per_sample
    return (100.0 * samples_per_s * run.flops_per_sample
            / (run.peak["bf16_tflops"] * 1e12))
