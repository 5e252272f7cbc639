"""Microbench: XLA-composed vs Pallas fused attention on the chip.

Decides (and re-validates) ops/attention.py's 'auto' = Pallas-on-TPU
default.  Benches the causal fwd and fwd+bwd at transformer-shaped
sizes; the first line names the platform the numbers belong to.

Usage: python tools/bench_attention.py [batch] [seqlen]
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from theanompi_tpu.ops.attention import fused_attention


def bench(fn, args, n_iters=30):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n_iters):
        y = fn(*args)
    jax.block_until_ready(y)
    return (time.perf_counter() - t0) / n_iters * 1e3


def main():
    b = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    t = int(sys.argv[2]) if len(sys.argv) > 2 else 1024
    h, d = 8, 64
    dev = jax.devices()[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"shape=({b},{t},{h},{d}) bf16")
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, t, h, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, t, h, d), jnp.bfloat16)

    for impl in ("xla", "pallas"):
        fwd = jax.jit(lambda q, k, v, impl=impl: fused_attention(
            q, k, v, causal=True, impl=impl))
        ms = bench(fwd, (q, k, v))
        print(f"{impl:7s} fwd     {ms:8.3f} ms")

        grad = jax.jit(jax.grad(lambda q, k, v, impl=impl: fused_attention(
            q, k, v, causal=True, impl=impl).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))
        ms = bench(grad, (q, k, v))
        print(f"{impl:7s} fwd+bwd {ms:8.3f} ms")


if __name__ == "__main__":
    main()
