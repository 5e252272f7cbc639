"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which is also the one that holds the chips.  It builds the
cell's model through the program's own constructor, drives the loop a
user of ``tmlocal BSP`` gets (``begin_epoch`` -> ``train_iter`` ->
``_flush_metrics``, the calls ``rules/bsp.py`` makes) for ``--seconds``
and prints, as the last line of its standard output, one JSON object
with ``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``.

Everything that belongs to one cell is data, found by the names in
``BENCHMARK.json``:

* ``benchmarks/configs/<config>.json``: the model by module, class and
  constructor arguments, its data source, its ``ModelConfig`` fields;
* ``benchmarks/traffic/<traffic>.json``: batch a chip, sequence length,
  steps to a segment;
* ``benchmarks/reference/<config>.py``: the plain reference behind
  ``correct``;
* ``benchmarks/layer_metrics/<quantity>.py``: one reader per per-layer
  quantity, ``read(run) -> number | None``.  A metric's name is the
  quantity and, after a dot, which end-to-end metric it moves
  (``device_idle_share.img``, ``.tok``): the suffix is not part of the
  file's name, so one quantity under another end-to-end metric is an
  entry in ``BENCHMARK.json`` and no file;
* ``benchmarks/flops/<family>.py``: the operations a trained sample
  needs, named by the configuration's ``flops.file``.

So a later PR adds a cell, a model or a metric by adding files and
entries and edits none.

**The reading.**  After warm-up the window is cut into segments of a
fixed number of steps (the traffic file's ``segment_steps``, about one
second).  Each segment ends in a fence (the metric flush reads back the
segment's losses; the prefetcher thread keeps filling meanwhile) and a
timestamp.  The end-to-end throughput is ALL the window's samples over
ALL its seconds, per chip: a stall a user pays for is in it.  The rate
of the median segment stands beside it as a per-layer metric
(``median_segment_rate.*``).  The line before the result says of every
segment how long it took and where the host was meanwhile (dispatch,
input wait, fence, CPU seconds of the thread and the process, garbage
collection), so that an odd run can be told apart as "a few slow
segments" or "slow throughout", and a slow segment as the host's work
or the host's waiting.

Without a TPU the command prints one line to stderr and exits non-zero,
unless the caller set ``JAX_PLATFORMS=cpu``: that asks for a dry run at
the tiny sizes of the files' ``dry_run`` keys, in which the whole path
executes and every metric but a program counter reads null (no CPU
number under a device metric's name).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, to the nearest import

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from unittest import mock  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: a ``--trace 1`` run traces ONE segment, this one: long enough after the
#: window's start to be steady state, and about a second of steps, which
#: is what a trace of some hundred megabytes can hold
TRACED_SEGMENT = 2
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_file_module(path: str) -> types.ModuleType:
    """Import one file by path (metric files carry dots in their names)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path).replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def merged(base: dict, override: dict | None) -> dict:
    out = dict(base)
    for key, value in (override or {}).items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = merged(out[key], value)
        else:
            out[key] = value
    return out


class CompileCounter:
    """Compilations as JAX's own monitoring reports them: every
    ``backend_compile`` (a real compile or a load from the persistent
    cache) and every persistent-cache miss."""

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += seconds

    def _event(self, event: str, **_kw) -> None:
        if event == CACHE_MISS_EVENT:
            self.cache_misses += 1


def build_model(config: dict, traffic: dict, seed: int, devices):
    """The cell's model through the program's own constructor; returns
    ``(model, phases)``."""
    from theanompi_tpu.parallel.mesh import data_mesh

    phases = {}
    mesh = data_mesh(len(devices), devices)
    global_batch = traffic["batch_per_chip"] * len(devices)

    t0 = time.perf_counter()
    data_spec = config["data"]
    data_cls = getattr(importlib.import_module(data_spec["module"]),
                       data_spec["class"])
    data_kwargs = merged(data_spec["kwargs"], traffic.get("data_kwargs"))
    data_kwargs[data_spec["size_kwarg"]] = (global_batch
                                            * traffic["epoch_steps"])
    data = data_cls(seed=seed, **data_kwargs)
    phases["data_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    model_spec = config["model"]
    model_cls = getattr(importlib.import_module(model_spec["module"]),
                        model_spec["class"])
    overrides = {k: (tuple(v) if isinstance(v, list) else v)
                 for k, v in config["model_config"].items()}
    model_config = dataclasses.replace(
        model_cls.default_config(), seed=seed,
        batch_size=traffic["batch_per_chip"], **overrides)
    model_kwargs = merged(model_spec["kwargs"], traffic.get("model_kwargs"))
    model = model_cls(config=model_config, mesh=mesh, verbose=False,
                      data=data, **model_kwargs)
    phases["build_s"] = time.perf_counter() - t0
    return model, phases


class Loop:
    """The user's loop, in segments, watched from outside."""

    #: what ``segments`` holds of each segment, in this order: its wall
    #: seconds; of those, in the dispatch loop (input wait included), in
    #: ``DevicePrefetcher.__next__``, in the fence; CPU seconds of this
    #: thread and of the whole process; seconds in Python's garbage
    #: collector.  (Not what the hypervisor stole: /proc/stat reads all
    #: zeros on the machines with the chip.)
    SEGMENT_FIELDS = ("s", "dispatch_s", "wait_s", "flush_s", "thread_cpu_s",
                      "process_cpu_s", "gc_s")

    def __init__(self, model, segment_steps: int):
        from theanompi_tpu.utils.recorder import Recorder

        if segment_steps > 50:
            raise ValueError("segment_steps over 50 would let train_iter's "
                             "own flush window fence inside a segment")
        self.model = model
        self.segment_steps = segment_steps
        self.recorder = Recorder(rank=0, size=model.n_workers, print_freq=0)
        self.it = 0
        self.wait_s = 0.0
        self.gc_s = 0.0
        self._gc_t0 = 0.0
        gc.callbacks.append(self._on_gc)
        self.segments: list[tuple] = []
        #: ``(name, start, end)`` in ``time.time_ns()`` of the harness's
        #: spans while a trace runs (a list then), else None.  Kept here
        #: and not as profiler annotations: see ``measure_window``
        self.spans: list | None = None

    def _on_gc(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self.gc_s += time.perf_counter() - self._gc_t0

    @contextlib.contextmanager
    def span(self, name: str):
        if self.spans is None:
            yield
            return
        t0 = time.time_ns()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.time_ns()))

    def timed_next(self, original):
        loop = self

        def __next__(prefetcher):
            t0 = time.perf_counter()
            with loop.span("bench/wait"):
                batch = original(prefetcher)
            loop.wait_s += time.perf_counter() - t0
            return batch

        return __next__

    def _host(self) -> tuple:
        return (self.wait_s, time.thread_time(), time.process_time(),
                self.gc_s)

    def segment(self) -> float:
        """``segment_steps`` dispatches and a fence; returns its seconds
        and appends its readings to ``segments``."""
        before = self._host()
        t0 = time.perf_counter()
        with self.span("bench/segment"):
            for _ in range(self.segment_steps):
                with self.span("bench/dispatch"):
                    self.it += self.model.train_iter(self.it, self.recorder)
            t1 = time.perf_counter()
            with self.span("bench/flush"):
                self.model._flush_metrics(self.recorder)
        t2 = time.perf_counter()
        wait, thread, process, gc_s = (
            b - a for a, b in zip(before, self._host()))
        self.segments.append((t2 - t0, t1 - t0, wait, t2 - t1, thread,
                              process, gc_s))
        return t2 - t0


def device_peak_bytes(device) -> int | None:
    """The peak a chip's runtime reports: the arrays the process held at
    once plus what it reserved for running programs.  A program's
    temporaries live in the reservation and are NOT in
    ``peak_bytes_in_use`` (ResNet-50 at b=128: 0.70 GB in use, 4.51 GB
    reserved, and XLA's own ``memory_analysis()`` of the step gives 4.55
    GB of temporaries).  The two peaks need not fall together, so the sum
    can overstate; in a training loop both are held all the time."""
    stats = device.memory_stats()
    if not stats:
        return None
    return stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)


def check_against_reference(model, config: dict, seed: int) -> dict:
    """The system's loss, and its gradient in the parameter leaves the
    configuration names (``reference.grad_rel_l2_tol``: leaf path ->
    tolerance), against the plain reference's, on a seeded sample the
    reference can hold.  Outside the measured window."""
    import jax
    import numpy as np

    reference = load_file_module(
        os.path.join(HERE, "reference", config["name"] + ".py"))
    spec = config["reference"]
    n = spec["samples"]
    tolerances = spec["grad_rel_l2_tol"]
    batch = next(model.data.train_batches(10**6, n))
    rng = jax.random.key(seed + 17)
    params, model_state = model.state.params, model.state.model_state

    def chosen(tree):
        by_path = {"/".join(str(k.key) for k in path): leaf for path, leaf
                   in jax.tree_util.tree_leaves_with_path(tree)}
        return [by_path[path] for path in tolerances]

    @jax.jit
    def system(params, model_state, batch, rng):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss_fn(p, model_state, batch, rng)[0])(params)
        return loss, chosen(grads)

    inputs = reference.inputs(model, batch, rng)

    @jax.jit
    def plain(params, inputs):
        loss, grads = jax.value_and_grad(
            lambda p: reference.loss(p, *inputs, **spec.get("kwargs", {}))
        )(params)
        return loss, chosen(grads)

    sys_loss, sys_grads = system(params, model_state, batch, rng)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = plain(params, inputs)
    sys_loss, ref_loss = float(sys_loss), float(ref_loss)
    loss_err = abs(sys_loss - ref_loss) / abs(ref_loss)
    grad_errs = {}
    for path, got, want in zip(tolerances, sys_grads, ref_grads):
        got = np.asarray(got, np.float64).ravel()
        want = np.asarray(want, np.float64).ravel()
        grad_errs[path] = float(np.linalg.norm(got - want)
                                / np.linalg.norm(want))
    ok = (math.isfinite(loss_err) and loss_err <= spec["loss_rel_tol"]
          and all(math.isfinite(e) and e <= tolerances[path]
                  for path, e in grad_errs.items()))
    return {"ok": ok, "samples": n, "system_loss": sys_loss,
            "reference_loss": ref_loss, "loss_rel_err": loss_err,
            "grad_rel_l2_err": grad_errs,
            "loss_rel_tol": spec["loss_rel_tol"],
            "grad_rel_l2_tol": tolerances}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"run.py: no workload {args.workload!r} in BENCHMARK.json "
              f"(known: {sorted(cells)})", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    config = load_json(HERE, "configs", cell["config"] + ".json")
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    dry_run = platform != "tpu"
    if dry_run and os.environ.get("JAX_PLATFORMS") != "cpu":
        print(f"run.py: no accelerator (jax reports platform {platform!r}); "
              "set JAX_PLATFORMS=cpu for a dry run that measures nothing",
              file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"run.py: workload {cell['name']} needs {cell['chips']} "
              f"chip(s), jax reports {len(devices)}", file=sys.stderr)
        return 1
    devices = devices[:cell["chips"]]
    if dry_run:
        config = merged(config, config.get("dry_run"))
        traffic = merged(traffic, traffic.get("dry_run"))

    return run_cell(args, bench, cell, config, traffic, devices, dry_run)


def traced_segment(loop, phases: dict) -> tuple[str, list]:
    """One segment under the profiler, device events only; returns the
    profile's directory and the harness's spans of the segment.  The
    seconds the profiler takes to start and stop are no part of the
    loop: they are kept in ``phases["profiler_s"]`` and taken out of
    the traced run's window."""
    import jax

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    t0 = time.perf_counter()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    t1 = time.perf_counter()
    loop.spans = []
    loop.segment()
    spans, loop.spans = loop.spans, None
    t2 = time.perf_counter()
    jax.profiler.stop_trace()
    phases["profiler_s"] = (t1 - t0) + (time.perf_counter() - t2)
    return trace_dir, spans


def load_trace(trace_dir: str, spans: list, chips: int, phases: dict):
    """The reduced trace of a profile's directory, None where it holds
    no device plane; the directory is removed."""
    from benchmarks import trace as trace_lib

    try:
        xplane = trace_lib.find_xplane(trace_dir)
        if xplane is None:
            return None
        t0 = time.perf_counter()
        trace = trace_lib.load(xplane, spans, chips)
        phases["trace_load_s"] = time.perf_counter() - t0
        phases["trace_bytes"] = os.path.getsize(xplane)
        return trace
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def measure_window(args, loop, n_iters: int, phases: dict) -> dict:
    """The measured window: segments until ``--seconds`` have passed,
    under the wrapper round ``DevicePrefetcher.__next__``.

    With ``--trace 1`` segment ``TRACED_SEGMENT`` runs under the
    profiler, device events only.  With the profiler's host tracer on,
    at any level, the runtime logs every chunk of the host-side relayout
    of a staged uint8 batch: 1.2 million events a thread in a 20-step
    ResNet segment, a trace of 0.8-0.9 GB, and the traced segment took
    6.5 s instead of 0.96 on one chip, 13 s instead of 0.98 on four
    (PR 24).  So the harness keeps its own spans on the host's clock
    (``Loop.span``) and ``trace.load`` puts them on the trace's.  A
    profile that holds no device plane gives no trace, and the metrics
    that read one are left off the line."""
    from theanompi_tpu.data.prefetch import DevicePrefetcher

    traced = None
    first = len(loop.segments)
    with mock.patch.object(DevicePrefetcher, "__next__",
                           loop.timed_next(DevicePrefetcher.__next__)):
        t_window = time.perf_counter()
        while time.perf_counter() - t_window < args.seconds:
            if loop.it + 2 * loop.segment_steps > n_iters:
                raise RuntimeError(
                    f"the epoch's {n_iters} steps ran out inside the "
                    "window; raise epoch_steps in the traffic file")
            if args.trace and len(loop.segments) - first == TRACED_SEGMENT:
                traced = traced_segment(loop, phases)
            else:
                loop.segment()
        window_s = time.perf_counter() - t_window
    return {"segments": loop.segments[first:],
            "window_s": window_s - phases.get("profiler_s", 0.0),
            "traced": traced}


def run_cell(args, bench, cell, config, traffic, devices, dry_run) -> int:
    """Set-up, window, reading and the result line of one cell."""
    from theanompi_tpu.utils.helper_funcs import enable_compilation_cache

    cache_dir = enable_compilation_cache()  # the program's own placement
    compiles = CompileCounter()
    phases = {"reach_chip_s": time.perf_counter() - T_START}

    # ---- set-up: data, model, step program, first steps ----
    model, built = build_model(config, traffic, args.seed, devices)
    phases.update(built)
    loop = Loop(model, traffic["segment_steps"])
    t0 = time.perf_counter()
    model.compile_iter_fns("avg")
    phases["compile_iter_fns_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    n_iters = model.begin_epoch(0)
    phases["begin_epoch_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    loop.it += model.train_iter(loop.it, loop.recorder)
    model._flush_metrics(loop.recorder)  # fence: the first step is done
    phases["first_step_s"] = time.perf_counter() - t0
    phases["warm_segment_s"] = loop.segment()
    setup_s = time.perf_counter() - T_START  # what a user's restart pays

    # ---- the reference check: the benchmark's own, in neither set-up
    # nor window; then one more segment, so that the window opens on a
    # loop in its stride ----
    t0 = time.perf_counter()
    reference = check_against_reference(model, config, args.seed)
    phases["reference_s"] = time.perf_counter() - t0
    phases["second_warm_segment_s"] = loop.segment()
    warm_losses = len(loop.recorder.train_losses)
    phases["compile_s"] = compiles.compile_s
    phases["cache_misses"] = compiles.cache_misses
    compiles_before = compiles.compiles

    window = measure_window(args, loop, n_iters, phases)
    recompiles = compiles.compiles - compiles_before
    prefetcher = dict(model._train_prefetcher.stats)  # since begin_epoch
    model.cleanup()
    chips = len(devices)
    trace = None
    if window["traced"] is not None:
        trace = load_trace(*window["traced"], chips, phases)

    # ---- the reading ----
    segments, window_s = window["segments"], window["window_s"]
    units_per_sample = traffic.get("units_per_sample", 1)
    units_per_step = model.global_batch * units_per_sample
    steps = len(segments) * loop.segment_steps
    throughput = units_per_step * steps / window_s / chips
    median_s = statistics.median(seg[0] for seg in segments)
    median_rate = units_per_step * loop.segment_steps / median_s / chips
    losses = loop.recorder.train_losses[warm_losses:]
    failed = sum(1 for x in losses if not math.isfinite(x))
    peak_bytes = max(filter(None, map(device_peak_bytes, devices)),
                     default=None)
    info = {"workload": cell["name"], "seed": args.seed, "phases": phases,
            "setup_s": setup_s, "cache_dir": cache_dir,
            "reference": reference, "segment_steps": loop.segment_steps,
            "segment_fields": Loop.SEGMENT_FIELDS, "segments": segments,
            "median_segment_s": median_s, "window_s": window_s,
            "steps": steps,
            "whole_window_rate_per_chip": None if dry_run else throughput,
            "median_segment_rate_per_chip":
                None if dry_run else median_rate,
            "input_wait_s": loop.wait_s, "prefetcher": prefetcher,
            "recompiles_in_window": recompiles,
            "last_loss": losses[-1] if losses else None,
            "memory_stats": devices[0].memory_stats()}

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": chips,
              "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(reference["ok"] and failed == 0
                              and len(losses) == steps),
              "attempted": steps, "failed": failed, "metrics": {},
              "device": device}
    declared = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}

    def report(name: str, value: float) -> None:
        # a dry run executes the path and measures nothing: no CPU number
        # under a device metric's name, only what the program counted
        if dry_run and declared[name]["source"] != "program_counter":
            value = None
        result["metrics"][name] = {
            "value": None if value is None else float(value),
            "unit": declared[name]["unit"]}

    def of_this_cell(metric: dict) -> bool:
        return cell["name"] in metric.get("workloads", [cell["name"]])

    if not args.trace:
        throughput_metric, = (m["name"] for m in bench["end_to_end"]
                              if m["name"] != "setup_s" and of_this_cell(m))
        report(throughput_metric, throughput)
        report("setup_s", setup_s)
    else:
        from benchmarks import peaks as peak_table
        from benchmarks import trace as trace_lib

        flops = config["flops"]
        flops_per_sample = load_file_module(os.path.join(
            HERE, "flops", flops["file"] + ".py")).train_flops_per_sample(
            **merged(flops.get("kwargs", {}), traffic.get("model_kwargs")))
        run = types.SimpleNamespace(
            on_device=not dry_run, phases=phases, wait_s=loop.wait_s,
            window_s=window_s, chips=chips, throughput=throughput,
            median_rate=median_rate, units_per_sample=units_per_sample,
            flops_per_sample=flops_per_sample,
            peak=None if dry_run else peak_table.peak(devices[0].device_kind),
            peak_bytes=peak_bytes, recompiles=recompiles, trace=trace,
            traced_steps=loop.segment_steps, trace_lib=trace_lib)
        for metric in filter(of_this_cell, bench["per_layer"]):
            # the reader is the quantity's: the name up to its first dot
            value = load_file_module(os.path.join(
                HERE, "layer_metrics",
                metric["name"].split(".")[0] + ".py")).read(run)
            if value is not None:  # nothing to read: left out of the line
                report(metric["name"], value)
        if trace is not None and not dry_run:
            device["busy_s"] = trace_lib.busy_ns(trace) / 1e9
            device["window_s"] = trace.window_ns / 1e9
            result["breakdown"] = {
                "device_ops": trace_lib.top_ops(trace, 10),
                "idle_gaps": trace_lib.idle_gaps(trace, 5)}
    print(json.dumps(info), flush=True)  # the earlier line
    print(json.dumps(result), flush=True)  # the result: the LAST line
    return 0


if __name__ == "__main__":
    sys.exit(main())
