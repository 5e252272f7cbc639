"""Pallas TPU kernel for the fused scale-bias(-residual)-ReLU epilogue
(forward + custom VJP), with a plain-XLA fallback.

Why this exists: the round-4/5 op-level account of the real v5e step
(artifacts/mfu_account.json, artifacts/fusion_deepdive.json) charges
**5.81 ms/step — 12.4% of device time at ~1% of the FLOPs — to 269
"loop fusion" events**, dominated by the BatchNorm normalize/affine
passes and the residual add+relu epilogues of the bottleneck blocks,
all running at 678–992 GB/s of pure HBM streaming.  XLA fuses each of
them locally but still materializes the BN output before the residual
add and the add before the relu in several block shapes.  This kernel
collapses the whole epilogue into ONE pass over the activation:

    y = act(x * scale + bias [+ residual])

where ``scale``/``bias`` are the folded BN affine
(``gamma*rsqrt(var+eps)`` and ``beta - mean*scale``: the batch-stat
reductions stay XLA — they are genuine reductions, not streaming
waste) or a plain conv-bias (``scale=1``).  The backward recomputes
the relu mask from the saved input instead of storing it and emits
``dx``/``dresidual`` plus the folded-parameter cotangents in the same
single stream, so fwd+bwd touch x, residual and g once each.

Like ops/lrn_pallas.py this tiles the flattened ``(N*H*W, C)`` view
into VMEM row-blocks and runs in interpret mode on the CPU platform
only (ops/pallas_mode.py), so the numerics are unit-tested on the CPU
mesh (tests/test_fused_bn.py pins forward AND gradient against the
unfused XLA reference).

On-chip status (PR 21, TPU v5 lite, JAX 0.9.0 / libtpu 0.0.34):
compiles and matches the XLA form fwd+bwd at the ResNet-50 stage-1
shapes (128*56*56 rows x 256 and x 64 channels, bf16, with and
without residual) — chip_smoke.py repeats that check.  As first
written the backward was refused: its per-block partial sums used a
``(1, C)`` block over an ``(n_blocks, C)`` array, which breaks the
(8, 128) block rule; they now accumulate into one resident ``(1, C)``
block (``_accumulate``).  Its SPEED against the XLA form has not been
measured, so it stays opt-in via ``ModelConfig.bn_act_impl='pallas'``
(ROADMAP A2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from theanompi_tpu.ops.pallas_mode import interpret

#: per-operand VMEM block budget; with 4 streamed operands (x, g, dx,
#: res) in the widest backward this keeps the working set ~2 MB
_TILE_BYTES = 1 << 19


def _tile_rows(m: int, c: int, itemsize: int) -> int:
    # 32 rows is the sublane tile of the narrowest dtype (int8/fp8;
    # bf16 packs 16, f32 8), so one rounding serves every operand
    rows = _TILE_BYTES // max(c * itemsize, 1)
    rows = max(32, (rows // 32) * 32)
    return min(rows, m)


def _row_mask(shape, m_rows: int, tile: int):
    """True for rows that exist in the un-padded (m, c) view — the last
    grid block may be padded and OOB reads are NOT guaranteed zero, so
    every reduction masks by absolute row index."""
    rows = pl.program_id(0) * tile + jax.lax.broadcasted_iota(
        jnp.int32, shape, 0)
    return rows < m_rows


# -- kernels over the flattened (rows, C) view ----------------------------

def _fwd_kernel(x_ref, s_ref, b_ref, y_ref, *, relu):
    z = x_ref[:].astype(jnp.float32) * s_ref[0] + b_ref[0]
    if relu:
        z = jnp.maximum(z, 0.0)
    y_ref[:] = z.astype(y_ref.dtype)


def _fwd_res_kernel(x_ref, s_ref, b_ref, r_ref, y_ref, *, relu):
    z = (x_ref[:].astype(jnp.float32) * s_ref[0] + b_ref[0]
         + r_ref[:].astype(jnp.float32))
    if relu:
        z = jnp.maximum(z, 0.0)
    y_ref[:] = z.astype(y_ref.dtype)


def _accumulate(ds_ref, db_ref, gx, g):
    """Add this row-block's column sums into the (1, C) cotangents.
    Their out-spec maps every grid step to the same block, so it stays
    resident in VMEM across the (sequential) row grid and is written
    back once.  (A per-block (n_blocks, C) partial-sum output needs a
    (1, C) block on a dimension that is not 1, which Mosaic's (8, 128)
    block rule refuses.)"""
    @pl.when(pl.program_id(0) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    ds_ref[...] += jnp.sum(gx, axis=0, keepdims=True)
    db_ref[...] += jnp.sum(g, axis=0, keepdims=True)


def _bwd_kernel(x_ref, s_ref, b_ref, g_ref, dx_ref, ds_ref, db_ref,
                *, relu, m_rows, tile):
    x = x_ref[:].astype(jnp.float32)
    s = s_ref[0]
    g = g_ref[:].astype(jnp.float32)
    if relu:
        g = jnp.where(x * s + b_ref[0] > 0, g, 0.0)
    g = jnp.where(_row_mask(x.shape, m_rows, tile), g, 0.0)
    dx_ref[:] = (g * s).astype(dx_ref.dtype)
    _accumulate(ds_ref, db_ref, g * x, g)


def _bwd_res_kernel(x_ref, s_ref, b_ref, r_ref, g_ref,
                    dx_ref, dr_ref, ds_ref, db_ref,
                    *, relu, m_rows, tile):
    x = x_ref[:].astype(jnp.float32)
    s = s_ref[0]
    g = g_ref[:].astype(jnp.float32)
    if relu:
        z = x * s + b_ref[0] + r_ref[:].astype(jnp.float32)
        g = jnp.where(z > 0, g, 0.0)
    g = jnp.where(_row_mask(x.shape, m_rows, tile), g, 0.0)
    dx_ref[:] = (g * s).astype(dx_ref.dtype)
    dr_ref[:] = g.astype(dr_ref.dtype)
    _accumulate(ds_ref, db_ref, g * x, g)


def _specs(m: int, c: int, itemsize: int):
    """(grid, row-block spec, whole-(1,C)-vector spec, tile) shared by
    the forward and backward pallas_calls."""
    tile = _tile_rows(m, c, itemsize)
    grid = (pl.cdiv(m, tile),)
    row = pl.BlockSpec((tile, c), lambda i: (i, 0),
                       memory_space=pltpu.VMEM)
    vec = pl.BlockSpec((1, c), lambda i: (0, 0), memory_space=pltpu.VMEM)
    return grid, row, vec, tile


# -- custom_vjp wrappers (2-D view; reshape happens in scale_bias_act) ----

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused(x, scale, bias, relu, out_dtype):
    y, _ = _fused_fwd(x, scale, bias, relu, out_dtype)
    return y


def _fused_fwd(x, scale, bias, relu, out_dtype):
    m, c = x.shape
    grid, row, vec, _tile = _specs(m, c, x.dtype.itemsize)
    out_row = pl.BlockSpec(row.block_shape, lambda i: (i, 0),
                           memory_space=pltpu.VMEM)
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, relu=relu),
        grid=grid,
        in_specs=[row, vec, vec],
        out_specs=out_row,
        out_shape=jax.ShapeDtypeStruct((m, c), out_dtype),
        interpret=interpret(),
    )(x, scale.reshape(1, c), bias.reshape(1, c))
    return y, (x, scale, bias)


def _fused_bwd(relu, out_dtype, saved, g):
    x, scale, bias = saved
    m, c = x.shape
    grid, row, vec, tile = _specs(m, c, x.dtype.itemsize)
    dx, ds, db = pl.pallas_call(
        functools.partial(_bwd_kernel, relu=relu, m_rows=m, tile=tile),
        grid=grid,
        in_specs=[row, vec, vec, row],
        out_specs=[row, vec, vec],
        out_shape=[
            jax.ShapeDtypeStruct((m, c), x.dtype),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
        ],
        interpret=interpret(),
    )(x, scale.reshape(1, c), bias.reshape(1, c), g)
    return (dx, ds.reshape(c).astype(scale.dtype),
            db.reshape(c).astype(bias.dtype))


_fused.defvjp(_fused_fwd, _fused_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused_res(x, scale, bias, res, relu, out_dtype):
    y, _ = _fused_res_fwd(x, scale, bias, res, relu, out_dtype)
    return y


def _fused_res_fwd(x, scale, bias, res, relu, out_dtype):
    m, c = x.shape
    grid, row, vec, _tile = _specs(m, c, x.dtype.itemsize)
    y = pl.pallas_call(
        functools.partial(_fwd_res_kernel, relu=relu),
        grid=grid,
        in_specs=[row, vec, vec, row],
        out_specs=pl.BlockSpec(row.block_shape, lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, c), out_dtype),
        interpret=interpret(),
    )(x, scale.reshape(1, c), bias.reshape(1, c), res)
    return y, (x, scale, bias, res)


def _fused_res_bwd(relu, out_dtype, saved, g):
    x, scale, bias, res = saved
    m, c = x.shape
    grid, row, vec, tile = _specs(m, c, x.dtype.itemsize)
    dx, dr, ds, db = pl.pallas_call(
        functools.partial(_bwd_res_kernel, relu=relu, m_rows=m,
                          tile=tile),
        grid=grid,
        in_specs=[row, vec, vec, row, row],
        out_specs=[row, row, vec, vec],
        out_shape=[
            jax.ShapeDtypeStruct((m, c), x.dtype),
            jax.ShapeDtypeStruct((m, c), res.dtype),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
        ],
        interpret=interpret(),
    )(x, scale.reshape(1, c), bias.reshape(1, c), res, g)
    return (dx, ds.reshape(c).astype(scale.dtype),
            db.reshape(c).astype(bias.dtype), dr)


_fused_res.defvjp(_fused_res_fwd, _fused_res_bwd)


# -- public API -----------------------------------------------------------

def scale_bias_act(x: jax.Array, scale: jax.Array, bias: jax.Array,
                   residual: jax.Array | None = None,
                   act: str | None = "relu", impl: str = "xla",
                   out_dtype=None) -> jax.Array:
    """``act(x * scale + bias [+ residual])`` over channel-last input.

    ``scale``/``bias`` are per-channel vectors (the folded BN affine or
    a conv bias with ``scale=ones``); ``residual`` must match ``x``'s
    shape.  ``impl='pallas'`` runs the fused single-stream kernel
    (interpret mode on the CPU platform); ``impl='xla'`` is the plain jnp form
    the kernel is oracle-tested against.  Math is f32 either way; the
    result is cast to ``out_dtype`` (default: ``x.dtype``).
    """
    if act not in (None, "relu"):
        raise ValueError(f"unknown act {act!r} (want None|'relu')")
    c = x.shape[-1]
    if scale.shape != (c,) or bias.shape != (c,):
        raise ValueError(
            f"scale/bias must be ({c},) channel vectors, got "
            f"{scale.shape}/{bias.shape} for x {x.shape}")
    if residual is not None and residual.shape != x.shape:
        raise ValueError(f"residual {residual.shape} != x {x.shape}")
    out_dtype = jnp.dtype(out_dtype if out_dtype is not None else x.dtype)
    if x.size == 0 and impl == "pallas":
        # zero-size activations (e.g. a VALID pool collapsing a tiny
        # test shape) have no rows to tile; the jnp path is exact
        impl = "xla"
    if impl == "xla":
        z = (x.astype(jnp.float32) * scale.astype(jnp.float32)
             + bias.astype(jnp.float32))
        if residual is not None:
            z = z + residual.astype(jnp.float32)
        if act == "relu":
            z = jnp.maximum(z, 0.0)
        return z.astype(out_dtype)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r} (want 'xla'|'pallas')")
    shape = x.shape
    m = 1
    for d in shape[:-1]:
        m *= d
    x2 = x.reshape(m, c)
    if residual is None:
        y = _fused(x2, scale, bias, act == "relu", out_dtype)
    else:
        y = _fused_res(x2, scale, bias, residual.reshape(m, c),
                       act == "relu", out_dtype)
    return y.reshape(shape)
