"""Microbench: XLA-composed vs Pallas LRN on the attached chip.

This measurement is why 'auto' in ops/lrn.py resolves to the Pallas
kernel on TPU (older stack, JAX 0.4.x, batch 64: fwd+bwd 4.35->2.94 ms
at (55,55,96), 2.41->1.96 ms at (27,27,256); not re-timed on the
installed stack); re-run it if either impl changes.  The first line
names the platform the numbers belong to.

Usage: python tools/bench_lrn.py [batch]
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from theanompi_tpu.ops import lrn


def bench(fn, x, n_iters=50):
    jax.block_until_ready(fn(x))
    t0 = time.perf_counter()
    for _ in range(n_iters):
        y = fn(x)
    jax.block_until_ready(y)
    return (time.perf_counter() - t0) / n_iters * 1e3


def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 128
    dev = jax.devices()[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind}")
    # AlexNet's two LRN sites
    for shape in ((batch, 55, 55, 96), (batch, 27, 27, 256)):
        x = jax.random.normal(jax.random.key(0), shape, jnp.float32)
        for impl in ("xla", "pallas"):
            fwd = jax.jit(lambda v, i=impl: lrn(v, impl=i))
            grad = jax.jit(jax.grad(lambda v, i=impl: lrn(v, impl=i).sum()))
            t_f = bench(fwd, x)
            t_g = bench(grad, x)
            print(f"{shape} {impl:6s}: fwd {t_f:7.3f} ms  fwd+bwd {t_g:7.3f} ms")


if __name__ == "__main__":
    main()
