"""chip_smoke.py — the quickest proof that the system still starts on
the chip.

Two legs, one process (the one that holds the chip):

1. **The main path.**  ``tmlocal BSP -m resnet50 --epochs 1`` through
   ``theanompi_tpu.launcher.tmlocal`` in-process: the zoo-default
   ResNet-50 ((3,4,6,3), 224 crop, bf16, per-chip batch 128) over all
   local devices, fed by the synthetic ImageNet loader (8 192 train
   images) through ``DevicePrefetcher``, then ``val_epoch`` and the
   Orbax checkpoint.  The leg only WATCHES the trainer (step clock,
   per-step losses, where the first staged batch landed) — it adds one
   fence after the first step and otherwise nothing the trainer would
   not do.
2. **The kernels.**  The ResNet default path uses no Pallas kernel, so
   each kernel in ``theanompi_tpu/ops`` is jitted fwd+bwd with
   ``impl='pallas'`` at the shape the zoo uses, must lower to a Mosaic
   call, and must agree with its XLA form.

Usage: ``python chip_smoke.py [OUT_DIR]`` (default ``./chip_smoke_out``;
everything it writes lands there).  Exits non-zero in one line unless
JAX's default platform is ``tpu``; the full-size path never runs on a
CPU.  The checks are plain functions so tests/test_chip_smoke.py can
drive them at a tiny size on the CPU mesh.  The last stdout line is
``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import sys
import time
from typing import Callable
from unittest import mock

#: the synthetic ImageNet train set the trainer falls back to without
#: a data dir (data/imagenet.py ``synthetic_n``)
SYNTHETIC_TRAIN_IMAGES = 8192
#: how many of a tensor's elements may sit outside the tolerance: the
#: fused epilogue recomputes its relu mask, so a |z| < 1 ulp element
#: may land on the other side of 0 than in XLA's fusion, and a bf16
#: attention gradient at T=1024 has a tail just past the tier-1
#: tolerance (worst element 0.60x of it on a one-chip v5e, 1.08x on a
#: four-chip host, PR 21).  A wrong tile or mask moves far more.
MAX_BAD_FRACTION = 1e-5


class SmokeFailure(Exception):
    """A check did not hold."""


# ---------------------------------------------------------------------------
# Leg 1: the trainer, watched
# ---------------------------------------------------------------------------


class TrainerProbe:
    """What the smoke observes while ``tmlocal`` runs, through three
    seams the trainer already has: ``TpuModel.train_iter`` (step count
    and clock), ``Recorder.train_metrics`` (per-step losses, as the
    trainer itself records them) and ``DevicePrefetcher.__next__``
    (the first staged batch's devices)."""

    def __init__(self):
        self.steps = 0
        self.losses: list[float] = []
        self.first_step_s: float | None = None
        #: per leaf of the first staged batch: the sorted device ids
        #: its addressable shards sit on
        self.batch_devices: list[list[int]] | None = None
        self.t0 = 0.0  # when watching began

    @contextlib.contextmanager
    def watching(self):
        import jax

        from theanompi_tpu.data.prefetch import DevicePrefetcher
        from theanompi_tpu.models.base import TpuModel
        from theanompi_tpu.utils.recorder import Recorder

        probe = self
        orig_iter = TpuModel.train_iter
        orig_metrics = Recorder.train_metrics
        orig_next = DevicePrefetcher.__next__

        def train_iter(model, count, recorder):
            consumed = orig_iter(model, count, recorder)
            if probe.steps == 0:
                # "completed", not "dispatched": the step counter is an
                # output of the first program
                jax.block_until_ready(model.state.step)
                probe.first_step_s = time.monotonic() - probe.t0
            probe.steps += consumed
            return consumed

        def train_metrics(recorder, loss, error, n_images):
            probe.losses.append(float(loss))
            return orig_metrics(recorder, loss, error, n_images)

        def staged_next(prefetcher):
            batch = orig_next(prefetcher)
            if probe.batch_devices is None:
                probe.batch_devices = [
                    sorted(s.device.id for s in leaf.addressable_shards)
                    for leaf in jax.tree.leaves(batch)]
            return batch

        with mock.patch.object(TpuModel, "train_iter", train_iter), \
                mock.patch.object(Recorder, "train_metrics",
                                  train_metrics), \
                mock.patch.object(DevicePrefetcher, "__next__",
                                  staged_next):
            self.t0 = time.monotonic()
            yield self


def run_trainer(argv: list[str]) -> tuple[TrainerProbe, float]:
    """Run ``tmlocal(argv)`` in this process under a probe; returns the
    probe and the wall seconds of the whole call."""
    from theanompi_tpu.launcher import tmlocal

    with TrainerProbe().watching() as probe:
        rc = tmlocal(argv)
        wall = time.monotonic() - probe.t0
    if rc != 0:
        raise SmokeFailure(f"tmlocal {' '.join(argv)} returned {rc}")
    return probe, wall


def check_losses(train_losses: list[float], val_loss) -> None:
    import math

    if not train_losses:
        raise SmokeFailure("the trainer recorded no train loss")
    bad = [i for i, l in enumerate(train_losses) if not math.isfinite(l)]
    if bad:
        raise SmokeFailure(f"non-finite train loss at step(s) {bad[:8]}")
    if val_loss is None or not math.isfinite(val_loss):
        raise SmokeFailure(f"non-finite val loss {val_loss!r}")


def check_step_count(steps: int, n_devices: int, n_images: int,
                     batch_per_device: int) -> None:
    want = n_images // (batch_per_device * n_devices)
    if steps != want:
        raise SmokeFailure(
            f"ran {steps} steps, expected {n_images} // "
            f"({batch_per_device} x {n_devices}) = {want}")


def check_batch_placement(batch_devices, n_devices: int) -> None:
    if not batch_devices:
        raise SmokeFailure("no staged batch was observed")
    for i, ids in enumerate(batch_devices):
        if len(set(ids)) != n_devices:
            raise SmokeFailure(
                f"staged batch leaf {i} sits on device(s) "
                f"{sorted(set(ids))}, expected {n_devices} distinct")


def check_peak_memory(peaks: dict[str, int | None]) -> None:
    """``peaks``: device -> ``memory_stats()['peak_bytes_in_use']``
    (None where the backend reports no stats)."""
    idle = [d for d, p in peaks.items() if not p]
    if idle:
        raise SmokeFailure(f"no device memory was used on {idle}")


def device_peaks(devices) -> dict[str, int | None]:
    return {str(d): (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices}


def check_checkpoint_restores(snapshot_dir: str, model_name: str,
                              steps: int) -> None:
    """The checkpoint the session wrote verifies against its manifest,
    restores in a fresh (read-only) Checkpointer, holds the step count
    the run reached, and carries finite params."""
    import jax
    import numpy as np

    from theanompi_tpu.utils.checkpoint import Checkpointer

    directory = os.path.join(snapshot_dir, model_name)
    if not os.path.isdir(directory):
        raise SmokeFailure(f"the session wrote no checkpoint dir "
                           f"{directory}")
    ckpt = Checkpointer(directory, read_only=True)
    try:
        epoch, payload = ckpt.restore_latest_verified()
    finally:
        ckpt.close()
    if payload is None:
        raise SmokeFailure(f"no restorable checkpoint under {directory}")
    got = int(np.asarray(payload["state"]["step"]))
    if got != steps:
        raise SmokeFailure(f"checkpoint (epoch {epoch}) holds step "
                           f"{got}, the run reached {steps}")
    for leaf in jax.tree.leaves(payload["state"]["params"]):
        if not np.isfinite(np.asarray(leaf, np.float32)).all():
            raise SmokeFailure("restored params are not finite")


def trainer_leg(out_dir: str, model: str, model_name: str, n_images: int,
                batch_per_device: int, extra_argv=()) -> dict:
    """Leg 1 end to end; returns its report or raises SmokeFailure."""
    import jax

    snapshots = os.path.join(out_dir, "snapshots")
    result_json = os.path.join(out_dir, "result.json")
    argv = ["BSP", "-m", model, "--epochs", "1",
            "--snapshot-dir", snapshots, "--result-json", result_json,
            *extra_argv]
    probe, wall = run_trainer(argv)
    with open(result_json) as f:
        val = json.load(f).get("val", {})
    devices = jax.local_devices()
    n = len(devices)
    report = {
        "argv": argv, "steps": probe.steps,
        "first_step_s": round(probe.first_step_s or 0.0, 2),
        "rest_s": round(wall - (probe.first_step_s or 0.0), 2),
        "train_loss_first": probe.losses[0] if probe.losses else None,
        "train_loss_last": probe.losses[-1] if probe.losses else None,
        "val_loss": val.get("loss"),
        "batch_devices": probe.batch_devices,
        "peak_bytes_in_use": device_peaks(devices),
    }
    check_losses(probe.losses, val.get("loss"))
    check_step_count(probe.steps, n, n_images, batch_per_device)
    check_batch_placement(probe.batch_devices, n)
    if devices[0].platform != "cpu":  # the CPU client keeps no stats
        check_peak_memory(report["peak_bytes_in_use"])
    check_checkpoint_restores(snapshots, model_name, probe.steps)
    return report


# ---------------------------------------------------------------------------
# Leg 2: every Pallas kernel, compiled
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One kernel at one shape: ``fn(impl)`` maps the arrays
    ``make_args()`` builds to an array; fwd+bwd (w.r.t. every
    argument) of the 'pallas' form must match the 'xla' form within
    ``(rtol, atol)`` — the tolerances of the kernel's own tier-1 test."""

    name: str
    fn: Callable[[str], Callable]
    #: built when the case runs, so only one case's arrays are alive
    make_args: Callable[[], tuple]
    rtol: float
    atol: float


def _normal(*specs):
    """Thunk building one seeded normal array per (shape, dtype)."""
    def make():
        import jax

        keys = jax.random.split(jax.random.key(0), len(specs))
        return tuple(jax.random.normal(k, shape, dtype)
                     for k, (shape, dtype) in zip(keys, specs))
    return make


def kernel_cases(full: bool = True) -> list[KernelCase]:
    """The zoo's shapes (``full``) or the same cases a few tiles big
    for the interpret-mode run on the CPU mesh."""
    import jax
    import jax.numpy as jnp

    from theanompi_tpu.ops.attention import fused_attention, rotary_table
    from theanompi_tpu.ops.fused_bn import scale_bias_act
    from theanompi_tpu.ops.lrn import lrn

    f32, bf16 = jnp.float32, jnp.bfloat16
    cases = []
    # AlexNet's two LRN sites at the zoo batch: f32, and the bf16 the
    # default recipe (compute_dtype='bfloat16') actually feeds them
    for shape in ([(128, 55, 55, 96), (128, 27, 27, 256)] if full
                  else [(2, 5, 5, 96)]):
        for dtype, rtol, atol in ((f32, 1e-4, 1e-5), (bf16, 2e-2, 1e-2)):
            cases.append(KernelCase(
                f"lrn{shape}{jnp.dtype(dtype).name}",
                lambda impl: lambda x: lrn(x, impl=impl),
                _normal((shape, dtype)), rtol=rtol, atol=atol))
    # TransformerLM's local attention block
    shape = (8, 1024, 12, 64) if full else (1, 16, 2, 8)
    cases.append(KernelCase(
        f"attention{shape}",
        lambda impl: lambda q, k, v: fused_attention(
            q, k, v, causal=True, impl=impl),
        _normal(*[(shape, bf16)] * 3), rtol=2e-2, atol=2e-2))
    # ZayaLM's: 8 query heads over 2 key/value heads of 128 (10 of 16
    # tiles of 512 x 512 at this length), and the grouped expert matmul
    # under the dropless expert layer, 8 held of 16 experts
    q_shape, kv_shape = (((4, 2048, 8, 128), (4, 2048, 2, 128)) if full
                         else ((1, 16, 4, 8), (1, 16, 2, 8)))
    cases.append(KernelCase(
        f"attention_gqa{q_shape}",
        lambda impl: lambda q, k, v: fused_attention(
            q, k, v, causal=True, impl=impl),
        _normal((q_shape, bf16), (kv_shape, bf16), (kv_shape, bf16)),
        rtol=2e-2, atol=2e-2))
    # OuroLM's: 16 over 16 heads of 128, picked by index map from the
    # projections' own layout, q and k rotated inside the kernels
    shape = (4, 2048, 16, 128) if full else (1, 128, 2, 128)
    cases.append(KernelCase(
        f"attention_rotary{shape}",
        lambda impl: lambda q, k, v: fused_attention(
            q, k, v, causal=True, impl=impl, rotary=rotary_table(
                jnp.arange(q.shape[1]), q.shape[3], 1e6)),
        _normal(*[(shape, bf16)] * 3), rtol=2e-2, atol=2e-2))
    # SmallThinkerLM's window layers: 28 over 4 heads of 128 under a
    # sliding window, K/V streamed from HBM a key tile at a time (at the
    # cell's 16 384 tokens the XLA form would hold 30 GB of scores)
    q_shape, kv_shape = (((1, 4096, 28, 128), (1, 4096, 4, 128)) if full
                         else ((1, 64, 4, 128), (1, 64, 2, 128)))
    window = 1024 if full else 16
    cases.append(KernelCase(
        f"attention_window{q_shape}",
        lambda impl: lambda q, k, v: fused_attention(
            q, k, v, causal=True, impl=impl, window=window),
        _normal((q_shape, bf16), (kv_shape, bf16), (kv_shape, bf16)),
        rtol=2e-2, atol=2e-2))
    def experts_case(kernel, n, d, held, routed, **kw):
        def experts_layer(impl):
            from theanompi_tpu.parallel.expert import routed_experts

            def layer(x, logits, gate, up, down):
                out, _ = routed_experts(
                    x, jax.nn.softmax(logits, -1),
                    {"gate": gate * d ** -0.5, "up": up * d ** -0.5,
                     "down": down * d ** -0.5}, (0, held),
                    impl="pallas" if impl == "pallas" else "ragged_dot",
                    **kw)
                return out
            return layer

        return KernelCase(
            f"{kernel}({n}, {d})x{held}of{routed}", experts_layer,
            _normal(((n, d), bf16), ((n, routed), f32),
                    *[((held, d, d), bf16)] * 3), rtol=2e-2, atol=2e-2)

    cases.append(experts_case(
        "grouped_matmul",
        *((8192, 2048, 8, 16) if full else (200, 16, 2, 4))))
    # a held quarter, top-6: the ladder's lower rung sums the buffer
    # into the tokens by the row kernel (against XLA's scatter-add)
    cases.append(experts_case(
        "expert_rows", *((8192, 2048, 8, 32) if full else (400, 16, 4, 16)),
        top_k=6, normalize=True))
    # NemotronHLM's Mamba-2 scan: 64 heads of 64 in 8 groups, state and
    # chunk 128; time steps made positive, decays negative, and B and C
    # scaled so that C . B is of order 1, as a layer's are
    b, t, h, g = (4, 2048, 64, 8) if full else (1, 256, 2, 1)

    def scan(impl):
        from theanompi_tpu.ops import ssd

        def layer(x, dt, a, b_in, c_in, d):
            args = (x, jax.nn.softplus(dt - 2.0), -jnp.exp(a),
                    b_in * 128 ** -0.5, c_in * 128 ** -0.5, d)
            if impl == "pallas":
                return ssd.ssd_chunked(*args, chunk=128, name="smoke_ssd")
            return ssd._ssd_jnp(*args, 128)
        return layer

    cases.append(KernelCase(
        f"ssd{(b, t, h, 64)}x{g}", scan,
        _normal(((b, t, h, 64), bf16), ((b, t, h), f32), ((h,), f32),
                *[((b, t, g, 128), bf16)] * 2, ((h,), f32)),
        rtol=2e-2, atol=2e-2))
    # Qwen3-Next's gated delta rule: 32 value heads of 128, chunks of
    # 64; keys and queries L2-normed (queries scaled), log decays
    # negative and write strengths in (0, 1), as the layer hands them
    shape = (4, 2048, 32, 128) if full else (1, 128, 2, 128)

    def delta_rule(impl):
        from theanompi_tpu.ops import gated_delta

        def layer(q, k, v, g, beta):
            def unit(x):
                x = x.astype(f32)
                return (x / jnp.linalg.norm(x, axis=-1, keepdims=True)
                        ).astype(bf16)

            args = (unit(q) * shape[3] ** -0.5, unit(k), v,
                    -jax.nn.softplus(g), jax.nn.sigmoid(beta))
            if impl == "pallas":
                return gated_delta.gated_delta_chunked(
                    *args, chunk=64, name="smoke_delta_rule")
            return gated_delta._delta_jnp(*args, 64)
        return layer

    cases.append(KernelCase(
        f"gated_delta{shape}", delta_rule,
        _normal(*[(shape, bf16)] * 3, *[(shape[:3], f32)] * 2),
        rtol=2e-2, atol=2e-2))
    # ResNet-50 stage-1 epilogues: conv3 (C=256, +residual) and
    # conv1/conv2 (C=64), each with and without the residual stream
    for c in (256, 64) if full else (32,):
        shape = (128, 56, 56, c) if full else (2, 5, 3, c)
        for with_res in (False, True):
            specs = [(shape, bf16), ((c,), f32), ((c,), f32)]
            if with_res:
                specs.append((shape, bf16))
            cases.append(KernelCase(
                f"fused_bn{shape}{'+res' if with_res else ''}",
                lambda impl: lambda x, s, b, r=None: scale_bias_act(
                    x, s, b, r, act="relu", impl=impl),
                _normal(*specs), rtol=2e-2, atol=1e-2))
    return cases


def run_kernel_case(case: KernelCase, require_mosaic: bool) -> dict:
    """jit fwd+bwd of both forms, compare on the device; returns the
    case's report or raises SmokeFailure."""
    import jax
    import jax.numpy as jnp

    def fwd_bwd(impl):
        def run(args, ct):
            y, vjp = jax.vjp(case.fn(impl), *args)
            return y, vjp(ct)

        return jax.jit(run)

    args = case.make_args()
    y_shape = jax.eval_shape(case.fn("xla"), *args)
    ct = jax.random.normal(jax.random.key(1), y_shape.shape, y_shape.dtype)
    pallas = fwd_bwd("pallas")
    mosaic_calls = pallas.lower(args, ct).as_text().count("tpu_custom_call")
    if require_mosaic and mosaic_calls < 2:
        raise SmokeFailure(
            f"{case.name}: {mosaic_calls} Mosaic call(s) in the lowered "
            "fwd+bwd, expected one each way")
    t0 = time.monotonic()
    got = jax.block_until_ready(pallas(args, ct))
    compile_run_s = time.monotonic() - t0
    want = fwd_bwd("xla")(args, ct)
    worst, n_bad = 0.0, 0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        # reduced on the device: only three scalars per tensor come back
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        if not bool(jnp.isfinite(g).all()):
            raise SmokeFailure(f"{case.name}: non-finite kernel output")
        excess = jnp.abs(g - w) / (case.atol + case.rtol * jnp.abs(w))
        worst = max(worst, float(excess.max()))
        bad = int((excess > 1.0).sum())
        n_bad += bad
        if bad > MAX_BAD_FRACTION * excess.size:
            raise SmokeFailure(
                f"{case.name}: {bad} of {excess.size} elements differ "
                f"from the XLA form beyond rtol={case.rtol} "
                f"atol={case.atol} (worst {worst:.3g}x the tolerance)")
    return {"name": case.name, "mosaic_calls": mosaic_calls,
            "worst_error_over_tolerance": round(worst, 4),
            "elements_over_tolerance": n_bad,
            "compile_run_s": round(compile_run_s, 2)}


def kernel_leg(full: bool, require_mosaic: bool) -> list[dict]:
    """Every case runs even after one fails, so one chip run names
    every kernel that needs work."""
    reports, failures = [], []
    for case in kernel_cases(full):
        try:
            reports.append(run_kernel_case(case, require_mosaic))
        except SmokeFailure as e:
            failures.append(str(e))
        except Exception as e:  # a compiler refusal: name the kernel
            failures.append(f"{case.name}: {type(e).__name__}: "
                            f"{str(e)[:2000]}")
    if failures:
        raise SmokeFailure("kernel leg: " + " | ".join(failures))
    return reports


# ---------------------------------------------------------------------------


def main(argv: list[str]) -> int:
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: no accelerator (jax reports {device}); the "
              "smoke runs on a TPU only", file=sys.stderr)
        return 2
    out_dir = os.path.abspath(argv[1] if len(argv) > 1
                              else "chip_smoke_out")
    os.makedirs(out_dir, exist_ok=True)

    from theanompi_tpu.utils.helper_funcs import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    print(f"chip_smoke: {device}, compile cache {cache_dir}", flush=True)
    report = {"device": device, "compile_cache_dir": cache_dir}
    try:
        report["trainer"] = trainer_leg(
            out_dir, "resnet50", "resnet50", SYNTHETIC_TRAIN_IMAGES, 128)
        print("chip_smoke: trainer", json.dumps(report["trainer"]),
              flush=True)
        report["kernels"] = kernel_leg(full=True, require_mosaic=True)
        print("chip_smoke: kernels", json.dumps(report["kernels"]),
              flush=True)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
            json.dump(report, f, indent=1)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
