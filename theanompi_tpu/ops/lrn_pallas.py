"""Pallas TPU kernel for cross-channel LRN (forward + custom VJP).

This is the TPU default for ``ops.lrn`` (see ops/lrn.py for its
on-chip record).  It tiles the flattened (N*H*W, C) view into VMEM
blocks, computes the windowed squared-sum on the VPU in one pass, and
backs it with an analytic VJP so the backward pass reuses the same
kernel shape instead of differentiating through the shift-and-add
chain (W^T is the adjoint window — equal to W for odd n):

    y  = x * s^{-beta},            s = k + a * W(x^2)
    dx = g * s^{-beta} - 2*a*beta * x * W^T(g * x * s^{-beta-1})

Runs in interpret mode on the CPU platform (ops/pallas_mode.py) so the
numerics are unit-testable on the CPU mesh.  Select explicitly with
``ops.lrn(..., impl=...)``.
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from theanompi_tpu.ops.lrn import window_sum as _window_sum
from theanompi_tpu.ops.pallas_mode import interpret

# rows of the flattened (pixels, channels) view per VMEM block; with
# C<=512 fp32 this stays well under the ~16MB VMEM budget
TILE_M = 1024


def _fwd_kernel(x_ref, y_ref, *, n, k, a, beta):
    x = x_ref[:]
    s = k + a * _window_sum(x * x, n)
    y_ref[:] = x * s ** (-beta)


def _bwd_kernel(x_ref, g_ref, dx_ref, *, n, k, a, beta):
    x = x_ref[:]
    g = g_ref[:]
    s = k + a * _window_sum(x * x, n)
    s_mb1 = s ** (-beta - 1.0)
    dx_ref[:] = g * s_mb1 * s - 2.0 * a * beta * x * _window_sum(
        g * x * s_mb1, n, adjoint=True)


def _blocked_call(kernel, n_in: int, m: int, c: int, dtype):
    tile = min(TILE_M, m)
    grid = (pl.cdiv(m, tile),)
    spec = pl.BlockSpec((tile, c), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec] * n_in,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((m, c), dtype),
        interpret=interpret(),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def lrn_pallas(x: jax.Array, n: int = 5, k: float = 2.0,
               alpha: float = 1e-4, beta: float = 0.75,
               alpha_scaled_by_n: bool = True) -> jax.Array:
    """Cross-channel LRN for NHWC input — Pallas TPU kernel."""
    y, _ = _lrn_fwd(x, n, k, alpha, beta, alpha_scaled_by_n)
    return y


def _lrn_fwd(x, n, k, alpha, beta, alpha_scaled_by_n):
    if x.ndim != 4:
        raise ValueError(f"lrn expects NHWC, got shape {x.shape}")
    a = alpha / n if alpha_scaled_by_n else alpha
    b, h, w, c = x.shape
    m = b * h * w
    flat = x.reshape(m, c)
    kern = functools.partial(_fwd_kernel, n=n, k=k, a=a, beta=beta)
    y = _blocked_call(kern, 1, m, c, x.dtype)(flat)
    return y.reshape(x.shape), x


def _lrn_bwd(n, k, alpha, beta, alpha_scaled_by_n, x, g):
    a = alpha / n if alpha_scaled_by_n else alpha
    b, h, w, c = x.shape
    m = b * h * w
    kern = functools.partial(_bwd_kernel, n=n, k=k, a=a, beta=beta)
    dx = _blocked_call(kern, 2, m, c, x.dtype)(
        x.reshape(m, c), g.reshape(m, c))
    return (dx.reshape(x.shape),)


lrn_pallas.defvjp(_lrn_fwd, _lrn_bwd)
