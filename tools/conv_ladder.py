"""Per-op MFU ladder for the ResNet-50 BSP step (VERDICT r2 #2).

The committed performance model (docs/DESIGN.md) bounds the
*environment* (size-dependent matmul rates, dispatch floor, H2D);
this tool bounds the *model step*: it enumerates every distinct conv
shape in ResNet-50 (geometry mirrored from
``theanompi_tpu/models/resnet50.py`` — BottleneckBlock 1x1/3x3/1x1,
projection on the first block of each stage, conv7 or s2d stem), times
each shape's forward and forward+backward on the current backend, and
reconciles the weighted sum against the measured full-step time.  The
residual (full step − Σ convs) is the BN/elementwise/optimizer/psum
slice XLA fuses around the convs.

Run on the chip (via the TPU queue) for real numbers; runs on CPU for
tool validation at small batch.  Emits one JSON line per shape plus a
summary line:

    python tools/conv_ladder.py --batch 128 --out ladder.jsonl
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def resnet50_convs(batch: int, stem: str = "conv7",
                   stage_sizes=(3, 4, 6, 3), width: int = 64):
    """(name, b, h_in, cin, cout, k, stride, count) for every distinct
    conv in one fwd pass, with multiplicity.  h_in is the INPUT spatial
    size; output spatial = h_in // stride (SAME padding throughout)."""
    convs = []
    if stem == "s2d":
        convs.append(("stem_s2d4x4", batch, 112, 12, width, 4, 1, 1))
    else:
        convs.append(("stem_conv7", batch, 224, 3, width, 7, 2, 1))

    cin = width                       # after the 3x3/2 maxpool: 56x56x64
    spatial = 56
    for s, n_blocks in enumerate(stage_sizes):
        feat, out = width * (2 ** s), 4 * width * (2 ** s)
        stride = 2 if s > 0 else 1
        # first block (projection + possible stride)
        convs += [
            (f"s{s}b0_proj1x1", batch, spatial, cin, out, 1, stride, 1),
            (f"s{s}b0_red1x1", batch, spatial, cin, feat, 1, 1, 1),
            (f"s{s}b0_mid3x3", batch, spatial, feat, feat, 3, stride, 1),
            (f"s{s}b0_exp1x1", batch, spatial // stride, feat, out, 1, 1, 1),
        ]
        spatial //= stride
        # remaining identical blocks
        if n_blocks > 1:
            m = n_blocks - 1
            convs += [
                (f"s{s}bN_red1x1", batch, spatial, out, feat, 1, 1, m),
                (f"s{s}bN_mid3x3", batch, spatial, feat, feat, 3, 1, m),
                (f"s{s}bN_exp1x1", batch, spatial, feat, out, 1, 1, m),
            ]
        cin = out
    return convs


def conv_gflops(b, h, cin, cout, k, stride) -> float:
    h_out = h // stride
    return 2.0 * b * h_out * h_out * k * k * cin * cout / 1e9


def time_shape(b, h, cin, cout, k, stride, dtype, n_iters, fence):
    import jax
    import jax.numpy as jnp
    from jax import lax

    pad = "SAME"
    x = jax.random.normal(jax.random.key(0), (b, h, h, cin), dtype)
    w = jax.random.normal(jax.random.key(1), (k, k, cin, cout), dtype)

    def conv(x, w):
        # output dtype == operand dtype, mirroring flax nn.Conv as the
        # models use it (models/resnet50.py dtype=compute_dtype, no
        # preferred_element_type); a f32 output here would also make
        # the VJP's transpose conv see a f32 cotangent against bf16
        # operands, which lax.conv_general_dilated rejects
        return lax.conv_general_dilated(
            x, w, (stride, stride), pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=dtype)

    fwd = jax.jit(conv)
    # fwd+bwd wrt both operands — primal + dgrad + wgrad, like
    # training.  value_and_grad, NOT grad: conv is linear, so under
    # plain grad the primal is dead code (the sum's cotangent is
    # constant ones and neither VJP reads the output) and only 2 of
    # the 3 GEMMs would be timed.  The sum accumulates in f32 so the
    # scalar stays finite at b=128 sizes.
    fb = jax.jit(jax.value_and_grad(
        lambda x, w: conv(x, w).astype(jnp.float32).sum(),
        argnums=(0, 1)))

    def bench(fn):
        out = fn(x, w)
        fence(out)                      # compile + settle
        t0 = time.perf_counter()
        for _ in range(n_iters):
            out = fn(x, w)
        fence(out)
        return (time.perf_counter() - t0) / n_iters * 1e3

    return bench(fwd), bench(fb)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--stem", default="conv7", choices=("conv7", "s2d"))
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--out", default=None, help="also append JSONL here")
    ap.add_argument("--step-ms", type=float, default=None,
                    help="measured full-step ms to reconcile against")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    def fence(tree):
        for leaf in jax.tree.leaves(tree):
            np.asarray(leaf.ravel()[:1])

    dtype = jnp.dtype(args.dtype)
    sink = open(args.out, "a", buffering=1) if args.out else None

    def emit(obj):
        line = json.dumps(obj)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")

    # out_dtype tags every row: v1 of this tool emitted f32 conv
    # outputs (+cast), v2 emits operand-dtype outputs — rows from the
    # two generations in one JSONL are not directly comparable, so
    # each row says which regime produced it (ADVICE r3 #3)
    emit({"event": "ladder_start", "backend": jax.default_backend(),
          "batch": args.batch, "stem": args.stem, "dtype": args.dtype,
          "out_dtype": args.dtype, "tool_version": 2})
    total_fwd = total_fb = total_gflops = 0.0
    for (name, b, h, cin, cout, k, stride, count) in resnet50_convs(
            args.batch, args.stem):
        g = conv_gflops(b, h, cin, cout, k, stride)
        fwd_ms, fb_ms = time_shape(b, h, cin, cout, k, stride, dtype,
                                   args.iters, fence)
        total_fwd += count * fwd_ms
        total_fb += count * fb_ms
        total_gflops += count * g
        emit({"conv": name, "h_in": h, "cin": cin, "cout": cout,
              "k": k, "stride": stride, "count": count,
              "out_dtype": args.dtype,
              "gflops_fwd": round(g, 2),
              "fwd_ms": round(fwd_ms, 3), "fwdbwd_ms": round(fb_ms, 3),
              "tflops_fwd": round(g / fwd_ms, 2),
              "tflops_fwdbwd": round(3 * g / fb_ms, 2),
              "total_ms": round(count * fb_ms, 2)})
    summary = {
        "event": "ladder_summary",
        "sum_fwd_ms": round(total_fwd, 2),
        "sum_fwdbwd_ms": round(total_fb, 2),
        "sum_gflops_fwd": round(total_gflops, 1),
        "tflops_fwdbwd": round(3 * total_gflops / total_fb, 2),
    }
    if args.step_ms:
        summary["measured_step_ms"] = args.step_ms
        summary["conv_fraction"] = round(total_fb / args.step_ms, 3)
        summary["residual_ms"] = round(args.step_ms - total_fb, 2)
    emit(summary)
    if sink:
        sink.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
