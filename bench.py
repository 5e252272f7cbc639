"""Benchmark: ResNet-50 ImageNet BSP training throughput (the driver's
primary metric — BASELINE.json: images/sec/chip, north-star ≥2500
img/s on a v5e-16 ⇒ 156.25 img/s/chip).

Two legs, one compile:

* **device-step**: the flagship BSP training step (on-device
  crop/flip/normalize + fwd + bwd + psum exchange + SGD update, bf16
  compute) over pre-staged uint8 batches — the images/sec/chip
  headline.
* **e2e**: the same step driven through the real pipeline
  (``train_iter``: synthetic-pool host batches → DevicePrefetcher →
  sharded device_put → step), wall-clock — proves the host can feed
  the chip.  The TPU-native data path ships raw uint8 and augments on
  device (ops/augment.py), so the one-core host only assembles
  batches.

Prints ONE JSON line ``{"metric": ..., "value": N, "unit":
"images/sec/chip", "vs_baseline": N, "platform": ..., "device_kind":
..., "n_devices": N, "detail": {...}}`` where detail carries the e2e
leg and the recorder cross-check.

One process, which is also the one that holds the chip.  Without a
TPU it prints one line to stderr and exits non-zero — unless the
caller set ``JAX_PLATFORMS=cpu``, which asks for a dry run: both legs
execute and the line carries ``"value": null``.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import numpy as np

from theanompi_tpu import monitor

BASELINE_PER_CHIP = 2500.0 / 16.0  # north-star v5e-16 target, per chip
E2E_STEPS = int(os.environ.get("THEANOMPI_TPU_BENCH_E2E_STEPS", "64"))
BATCH_PER_CHIP = int(os.environ.get("THEANOMPI_TPU_BENCH_BATCH", "128"))
N_STEPS = int(os.environ.get("THEANOMPI_TPU_BENCH_STEPS", "30"))
# scanned multi-step cadence (ModelConfig.steps_per_call): k>1 runs k
# training iterations per device dispatch — bit-identical trajectory,
# amortizes the per-dispatch overhead.  The per-batch default (4 at
# b<=128, 1 above) was chosen from a k x batch ladder on the older
# stack (JAX 0.4.x; BASELINE.md) and has not been re-measured on the
# installed one.  It applies on the TPU backend ONLY: the scanned
# ResNet body ran 13x slower per step on the CPU backend (a backend
# de-optimization, not a trajectory change), so CPU dry runs keep k=1
# unless THEANOMPI_TPU_BENCH_K is set explicitly.
_BENCH_K_ENV = os.environ.get("THEANOMPI_TPU_BENCH_K")
STEPS_PER_CALL = (int(_BENCH_K_ENV) if _BENCH_K_ENV is not None
                  else (4 if BATCH_PER_CHIP <= 128 else 1))
if STEPS_PER_CALL < 1:
    raise SystemExit(f"THEANOMPI_TPU_BENCH_K must be >= 1, "
                     f"got {STEPS_PER_CALL}")
if STEPS_PER_CALL > E2E_STEPS:
    if _BENCH_K_ENV is not None:
        raise SystemExit(f"THEANOMPI_TPU_BENCH_K ({STEPS_PER_CALL}) must "
                         f"not exceed THEANOMPI_TPU_BENCH_E2E_STEPS "
                         f"({E2E_STEPS}) or the e2e leg would run zero "
                         "iterations")
    # defaulted k: clamp instead of aborting, so a lowered E2E_STEPS
    # smoke run (e.g. CI with E2E_STEPS=2) still works out of the box
    STEPS_PER_CALL = E2E_STEPS


def fenced_loss(metrics) -> float:
    """Host value of the last loss — waits for the whole dispatch
    chain.  Multi-step metrics come back stacked (k,)."""
    return float(np.asarray(metrics["loss"]).ravel()[-1])


def result_line(devices, step_per_chip: float, detail: dict) -> dict:
    """The one JSON line, stamped with the device it belongs to.  Off
    the TPU (a JAX_PLATFORMS=cpu dry run) both legs ran but nothing
    was measured: a CPU's rates and times never appear under a device
    metric's name — ``value`` is null and only the counts stay."""
    platform = devices[0].platform
    value = round(step_per_chip, 2)
    vs_baseline = round(step_per_chip / BASELINE_PER_CHIP, 4)
    if platform != "tpu":
        value = vs_baseline = None
        counts = ("n_chips", "global_batch", "steps_per_call",
                  "e2e_steps", "augment", "backend")
        detail = {key: detail[key] for key in counts}
        detail["note"] = (f"JAX_PLATFORMS={platform} dry run: both legs "
                          "executed, no device number was taken")
    return {
        "metric": "resnet50_imagenet_bsp_images_per_sec_per_chip",
        "value": value,
        "unit": "images/sec/chip",
        "vs_baseline": vs_baseline,
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "n_devices": len(devices),
        "detail": detail,
    }


def main() -> int:
    # telemetry session (no-op unless $THEANOMPI_TPU_MONITOR is set):
    # the legs become spans and the heartbeat file names the live phase
    with monitor.session():
        return _main()


def _main() -> int:
    devices = jax.devices()
    platform = devices[0].platform
    # a rate is a device metric: it comes from a chip or it is not
    # printed.  JAX_PLATFORMS=cpu is the caller asking for a dry run —
    # both legs execute (debug the path here, measure there) and the
    # line carries "value": null (result_line)
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        print(f"bench.py: no accelerator (jax reports platform "
              f"{platform!r}); set JAX_PLATFORMS=cpu for a dry run "
              "that measures nothing", file=sys.stderr)
        return 1
    from theanompi_tpu.utils.helper_funcs import enable_compilation_cache

    enable_compilation_cache()
    monitor.progress(phase=f"measure ({platform})")

    from theanompi_tpu.models.base import ModelConfig
    from theanompi_tpu.models.resnet50 import ResNet50
    from theanompi_tpu.data.imagenet import ImageNet_data
    from theanompi_tpu.parallel.mesh import data_mesh, shard_batch
    from theanompi_tpu.utils.recorder import Recorder

    n_chips = len(devices)
    mesh = data_mesh(n_chips, devices)

    batch_per_chip = BATCH_PER_CHIP
    global_batch = batch_per_chip * n_chips

    class BenchResNet50(ResNet50):
        def build_data(self):
            return ImageNet_data(crop=224,
                                 synthetic_n=global_batch * (E2E_STEPS + 2),
                                 synthetic_pool=64, synthetic_store=256,
                                 augment_on_device=True)

    k = STEPS_PER_CALL
    if _BENCH_K_ENV is None and platform == "cpu":
        k = 1   # scanned bodies are ~13x slower on the CPU backend
    cfg = ModelConfig(batch_size=batch_per_chip, n_epochs=1,
                      compute_dtype="bfloat16", track_top5=False,
                      steps_per_call=k, print_freq=10**9,
                      # the device-step leg replays 2 pre-staged
                      # batches round-robin; donation would delete
                      # them after the first pass
                      donate_batch=False)
    model = BenchResNet50(config=cfg, mesh=mesh, verbose=False)
    model.compile_iter_fns("avg")

    # ---- leg 1: device step over pre-staged uint8 batches ----
    host_it = model.data.train_batches(0, global_batch)
    if k > 1:
        from theanompi_tpu.models.base import _stack_host_batches

        stacked_it = _stack_host_batches(host_it, k)
        staged = [shard_batch(next(stacked_it), mesh,
                              spec=model.stacked_batch_spec())
                  for _ in range(2)]
        step_fn = model.train_step_multi
    else:
        staged = [shard_batch(next(host_it), mesh) for _ in range(4)]
        step_fn = model.train_step

    rng = jax.random.key(0)
    state = model.state
    monitor.progress(phase="compile+warmup")
    with monitor.span("bench/compile_warmup"):
        for i in range(3):  # warmup: compile + steady state
            state, metrics = step_fn(state, staged[i % len(staged)], rng)
        fenced_loss(metrics)

    monitor.progress(phase="device-step leg")
    n_steps = max(1, N_STEPS // k)  # dispatches; each covers k iters
    with monitor.span("bench/device_step"):
        t0 = time.perf_counter()
        for i in range(n_steps):
            state, metrics = step_fn(state, staged[i % len(staged)], rng)
        loss = fenced_loss(metrics)  # fences the whole chain
        dt = time.perf_counter() - t0
    assert np.isfinite(loss), f"non-finite loss {loss}"
    model.state = state  # keep the warm state for the e2e leg

    step_total = n_steps * k * global_batch / dt
    step_per_chip = step_total / n_chips
    del staged, host_it  # free leg-1 device buffers before the e2e leg

    # ---- H2D ceiling: what the host→device link allows ----
    # the explicit ceiling keeps the e2e fraction honest: an e2e leg
    # below the device step is a slow link, not a pipeline bug, when
    # it sits at this ceiling
    monitor.progress(phase="h2d probe")
    probe = next(model.data.train_batches(0, global_batch))
    probe_bytes = sum(np.asarray(a).nbytes for a in jax.tree.leaves(probe))
    # compile the slice kernels outside the timer
    jax.block_until_ready(shard_batch(probe, mesh))
    t0 = time.perf_counter()
    jax.block_until_ready(shard_batch(probe, mesh))
    h2d_s = time.perf_counter() - t0
    h2d_gbps = probe_bytes / h2d_s / 1e9
    h2d_ceiling_total = global_batch / h2d_s  # img/s if H2D-serial
    del probe

    # ---- leg 2: end-to-end through the real pipeline ----
    # train_iter covers k iterations per dispatch when steps_per_call
    # is on, so drive by consumed count like rules/bsp.py does
    monitor.progress(phase="e2e leg")
    recorder = Recorder(rank=0, size=n_chips, print_freq=0)
    n_iters = min(model.begin_epoch(0), E2E_STEPS)
    n_iters -= n_iters % k
    with monitor.span("bench/e2e"):
        t0 = time.perf_counter()
        it = 0
        while it < n_iters:
            it += model.train_iter(it, recorder)
        model._flush_metrics(recorder)  # blocks on the last metrics
        e2e_dt = time.perf_counter() - t0
    model.cleanup()
    assert np.isfinite(recorder.train_losses).all()

    e2e_total = it * global_batch / e2e_dt
    e2e_per_chip = e2e_total / n_chips
    # recorder cross-check: its calc+wait seconds should explain the
    # fenced wall-clock within a few percent
    rec_accounted = sum(recorder.epoch_time[k] for k in recorder.SECTIONS)

    detail = {
        "n_chips": n_chips,
        "global_batch": global_batch,
        "steps_per_call": k,
        "images_per_sec_total": round(step_total, 2),
        "step_ms": round(dt / (n_steps * k) * 1e3, 2),
        "dispatch_ms": round(dt / n_steps * 1e3, 2),
        "e2e_images_per_sec_per_chip": round(e2e_per_chip, 2),
        "e2e_fraction_of_device_step": round(e2e_per_chip
                                             / step_per_chip, 4),
        "h2d_gbps": round(h2d_gbps, 4),
        "h2d_ceiling_images_per_sec_per_chip": round(
            h2d_ceiling_total / n_chips, 2),
        "e2e_fraction_of_h2d_ceiling": round(
            e2e_total / h2d_ceiling_total, 4),
        "e2e_bound": ("h2d" if h2d_ceiling_total < step_total
                      else "compute"),
        "e2e_steps": it,
        "recorder_accounted_s": round(rec_accounted, 3),
        "recorder_wall_s": round(e2e_dt, 3),
        "augment": "device",
        "backend": jax.default_backend(),
    }
    print(json.dumps(result_line(devices, step_per_chip, detail)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
