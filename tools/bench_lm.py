"""Transformer-LM training throughput — tokens/sec/chip + TFLOP/s.

The CNN flagship has bench.py; this gives the transformer family the
same on-chip measurement surface (the LM family declares its trained
FLOPs from the real param count, models/transformer.py), so a chip
window can quantify the fused-attention + remat stack, not just
ResNet.  One JSON line, bench.py conventions (pre-staged batches,
value-readback fencing).

    python tools/bench_lm.py --batch 8 --seq 1024 --layers 12 \
        --d-model 768 --steps 20
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import fenced_loss  # noqa: E402  (shared fence)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8,
                    help="sequences per data shard")
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--d-model", type=int, default=768)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--attn", default=None,
                    choices=("auto", "pallas", "xla"),
                    help="force the attention impl (the r3 'fused' "
                    "points exported THEANOMPI_TPU_ATTN_IMPL by hand; "
                    "a flag makes the queue JSON self-contained)")
    args = ap.parse_args()
    if args.attn:
        os.environ["THEANOMPI_TPU_ATTN_IMPL"] = args.attn

    from theanompi_tpu.models.base import ModelConfig
    from theanompi_tpu.models.transformer import TransformerLM
    from theanompi_tpu.parallel.mesh import data_mesh, shard_batch

    devices = jax.devices()
    mesh = data_mesh(len(devices), devices)
    cfg = ModelConfig(batch_size=args.batch, n_epochs=1,
                      optimizer="adamw", learning_rate=1e-3,
                      weight_decay=0.01, lr_schedule="constant",
                      compute_dtype=args.dtype, remat=args.remat,
                      print_freq=10**9)
    model = TransformerLM(config=cfg, mesh=mesh, vocab=args.vocab,
                          seq_len=args.seq, n_layers=args.layers,
                          d_model=args.d_model, n_heads=args.heads,
                          verbose=False)
    model.compile_iter_fns("avg")
    global_batch = model.global_batch
    # stage with the MODEL's partition (P('data','seq') for the LM) so
    # jit never reshards inside the timed loop
    staged = [shard_batch(b, mesh, spec=model.batch_partition)
              for _, b in zip(
                  range(2), model.data.train_batches(0, global_batch))]

    rng = jax.random.key(0)
    state = model.state
    for i in range(2):  # compile + settle
        state, metrics = model.train_step(state, staged[i % 2], rng)
    fenced_loss(metrics)
    t0 = time.perf_counter()
    for i in range(args.steps):
        state, metrics = model.train_step(state, staged[i % 2], rng)
    loss = fenced_loss(metrics)
    dt = time.perf_counter() - t0
    assert np.isfinite(loss), loss
    model.cleanup()

    # one shared definition with bench_serving's decode mode
    # (utils/token_accounting.py): training tokens are every position
    # of every sequence, over the timed window, per chip
    from theanompi_tpu.utils.token_accounting import token_throughput

    rate = token_throughput(args.steps * global_batch * args.seq, dt,
                            len(devices))
    tflops = (args.steps * global_batch * model.train_flops_per_sample
              / dt / 1e12)
    print(json.dumps({
        "metric": "transformer_lm_tokens_per_sec_per_chip",
        "value": round(rate["tokens_per_sec_per_chip"], 1),
        "unit": "tokens/sec/chip",
        "detail": {
            "n_chips": len(devices),
            "tokens": rate["tokens"],
            "global_batch": global_batch,
            "seq_len": args.seq,
            "layers": args.layers, "d_model": args.d_model,
            "remat": args.remat, "dtype": args.dtype,
            "attn": args.attn or os.environ.get(
                "THEANOMPI_TPU_ATTN_IMPL", "auto"),
            "step_ms": round(dt / args.steps * 1e3, 2),
            "tflops_per_chip": round(tflops / len(devices), 2),
            "train_gflops_per_seq": round(
                model.train_flops_per_sample / 1e9, 2),
            "final_loss": round(loss, 4),
            "backend": jax.default_backend(),
        },
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
