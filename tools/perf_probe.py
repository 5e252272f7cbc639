"""Single-chip perf probe for the ResNet-50 BSP step (VERDICT r1 #2).

Times the jitted train step under controlled variations (batch size,
compute dtype, stem layout, metrics on/off).  Optionally dumps a
``jax.profiler`` trace for offline analysis.  Every result line names
the platform it ran on; the utilization column exists only for a
device whose published peak is in tools/flop_constants.py.

Usage:
    python tools/perf_probe.py --batch 128 256 --steps 30
    python tools/perf_probe.py --batch 256 --trace /tmp/trace
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from flop_constants import TRAIN_GFLOP_PER_IMAGE, peak_bf16_tflops  # noqa: E402


def time_step(step, state, batch, rng, n_steps: int, warmup: int = 3):
    for _ in range(warmup):
        state, metrics = step(state, batch, rng)
    jax.block_until_ready(metrics)
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, metrics = step(state, batch, rng)
    loss = float(metrics["loss"])
    dt = time.perf_counter() - t0
    assert np.isfinite(loss)
    return dt / n_steps, state


def build(batch: int, dtype: str, variant: str,
          bn_act_impl: str = "xla"):
    from theanompi_tpu.models.base import ModelConfig
    from theanompi_tpu.models.resnet50 import ResNet50
    from theanompi_tpu.data.imagenet import ImageNet_data
    from theanompi_tpu.parallel.mesh import data_mesh, shard_batch

    devices = jax.devices()
    mesh = data_mesh(len(devices), devices)
    global_batch = batch * len(devices)

    uint8_feed = variant == "uint8"

    class ProbeResNet50(ResNet50):
        def build_data(self):
            if uint8_feed:
                # the FLAGSHIP staging (bench.py device-step leg): raw
                # uint8 store images, crop/flip/normalize traced into
                # the step (ops/augment.py).  The f32 'base' variant
                # stages pre-normalized floats — its trace carries an
                # input f32->bf16 convert + 38 MB copy the flagship
                # step doesn't have (seen in the r3/r4 account), and
                # misses the device augment slice the flagship does.
                return ImageNet_data(crop=224, synthetic_n=global_batch,
                                     synthetic_pool=1,
                                     synthetic_store=256,
                                     augment_on_device=True)
            return ImageNet_data(crop=224, synthetic_n=global_batch,
                                 synthetic_pool=1, synthetic_store=32)

    cfg = ModelConfig(batch_size=batch, compute_dtype=dtype,
                      track_top5=False, print_freq=10**9,
                      bn_act_impl=bn_act_impl)
    model = ProbeResNet50(config=cfg, mesh=mesh, verbose=False)
    if variant not in ("base", "uint8"):
        raise ValueError(variant)
    model.compile_iter_fns("avg")

    if uint8_feed:
        x = np.random.default_rng(0).integers(
            0, 256, size=(global_batch, 256, 256, 3), dtype=np.uint8)
    else:
        x = np.random.default_rng(0).standard_normal(
            (global_batch, 224, 224, 3)).astype(np.float32)
    y = np.random.default_rng(1).integers(0, 1000, global_batch)
    staged = shard_batch((x, y), mesh)
    return model, staged, mesh, global_batch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, nargs="+", default=[128])
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--variant", default="base")
    ap.add_argument("--trace", default=None,
                    help="dump a jax.profiler trace to this dir")
    ap.add_argument("--xla-flags", default=None,
                    help="appended to XLA_FLAGS before first backend use "
                    "(capture the profile under a flag set from "
                    "tools/xla_sweep.py)")
    ap.add_argument("--bn-act-impl", default="xla",
                    choices=("xla", "pallas"),
                    help="BN/activation epilogue kernel "
                    "(ops/fused_bn.py) — the A/B lever of the "
                    "xla_sweep fused-epilogue entries")
    args = ap.parse_args()
    if args.xla_flags:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " " + args.xla_flags)

    device = jax.devices()[0]
    # unknown device_kind -> error before anything is timed
    peak = (peak_bf16_tflops(device.device_kind)
            if device.platform != "cpu" else None)
    for b in args.batch:
        model, staged, mesh, global_batch = build(
            b, args.dtype, args.variant, args.bn_act_impl)
        rng = jax.random.key(0)
        step_s, state = time_step(model.train_step, model.state, staged, rng,
                                  args.steps)
        img_s = global_batch / step_s
        per_chip = img_s / len(jax.devices())
        tflops = per_chip * TRAIN_GFLOP_PER_IMAGE / 1000.0
        print(json.dumps({
            "batch_per_chip": b, "dtype": args.dtype, "variant": args.variant,
            "bn_act_impl": args.bn_act_impl,
            "platform": device.platform,
            "device_kind": device.device_kind,
            "n_devices": len(jax.devices()),
            "step_ms": round(step_s * 1e3, 2),
            "images_per_sec_per_chip": round(per_chip, 1),
            "tflops_per_chip": round(tflops, 1),
            "mfu_pct": (round(100 * tflops / peak, 1)
                        if peak is not None else None),
        }))
        if args.trace:
            with jax.profiler.trace(args.trace):
                for _ in range(5):
                    state, metrics = model.train_step(state, staged,
                                                      rng)
                jax.block_until_ready(metrics)
            print(f"trace written to {args.trace}")


if __name__ == "__main__":
    main()
