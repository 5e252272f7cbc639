"""Grouped matrix product over row tiles, as Pallas TPU kernels.

The expert layer's hot op (parallel/expert.py ``routed_experts``): the
rows of ``lhs`` are tokens laid out expert by expert, and each expert
multiplies its own rows by its own matrix.

    out[rows of tile t] = lhs[rows of tile t] @ rhs[tile_group[t]]

The layout is the caller's: every group starts on a tile boundary (a
group's rows are padded up to whole tiles of ``TILE_M`` rows, the
padding rows are zero), so a tile belongs to exactly one group and the
kernels need no row masks and revisit no output tile.  ``tile_group``
(int32, one entry a tile, scalar-prefetched so the index maps read it)
names each tile's group, ``n_tiles`` (a traced scalar: the grid's
extent) says how many leading tiles are in use.  **Rows of tiles past
``n_tiles`` are never read and never written**: what the output holds
there is undefined, and the caller must not read it (``routed_experts``
gathers, or masks before it sums, only rows it placed).

Three kernels, each a ``pallas_call`` with a ``name=`` so that a trace
reducer can find them (``<name>_gmm``, ``<name>_gmm_t``,
``<name>_tgmm``):

* ``gmm``: ``lhs (M, K) @ rhs[g] (K, N)``.  Grid ``(N tiles, row
  tiles)``: the whole contraction in one step, and a group's ``rhs``
  block stays in VMEM while the group's row tiles pass (Pallas skips
  the copy when the block index repeats), so each weight is read once.
* ``gmm`` with ``transpose_rhs``: ``lhs (M, N) @ rhs[g] (K, N)^T``, the
  gradient with respect to ``gmm``'s ``lhs``.
* ``tgmm``: ``out[g] = sum over g's tiles of lhs_tile^T @ rhs_tile``,
  the gradient with respect to ``gmm``'s ``rhs``; accumulates in fp32
  VMEM scratch while consecutive tiles name the same group.  Every
  group must own at least one tile (an empty group owns one tile of
  zero rows), so every output block is written.

``grouped_matmul`` ties them into one differentiable function.  The
design follows ``jax.experimental.pallas.ops.tpu.megablox`` (dynamic
grid extent, group ids by scalar prefetch); the tile-aligned layout is
what lets these be short.  ``jax.lax.ragged_dot`` over the same layout
is the oracle and the path off the TPU.

On-chip status (PR 27, TPU v5 lite, JAX 0.9.0 / libtpu 0.0.34): the
three kernels compile and run at (9216, 2048) x (8, 2048, 2048) bf16,
row tile 128; the zaya1_8b cell's reference check holds the gradient
inside an expert against float32.  PR 34: at (50176, 2688) x (8, 2688,
1856) and (50176, 1856) x (8, 1856, 2688), widths no power-of-two tile
divides (``_column_tile``): tiles of 640 over 1856 columns, the last 576
wide, and whole tiles of 896 and 384 over 2688; since PR 35 mostly at
7168 rows, inside the branches of ``routed_experts``' buffer ladder.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows of one tile: the MXU's height on v5e, and what a group's rows
#: are padded up to (so at most ``TILE_M - 1`` idle rows a group)
TILE_M = 128
#: a block of ``rhs`` (double-buffered by the pipeline) may take this
#: much VMEM; the column tile is the largest that keeps it
_RHS_BLOCK_BYTES = 4 * 1024 * 1024
_VMEM_LIMIT_BYTES = 48 * 1024 * 1024


#: what one grid step costs beside its product, in columns of a step's
#: product (about 0.35 us, a 128-column step at the widths met so far)
_STEP_COLUMNS = 128


def _column_tile(n: int, k: int, itemsize: int,
                 whole_tiles: bool = False) -> int:
    """The column tile of an ``n``-column product whose block of the
    other operand is ``(k, tile)`` of ``itemsize`` bytes: of the
    multiples of 128 up to 1024 inside ``_RHS_BLOCK_BYTES``, the one
    whose tiles cost least, columns multiplied plus ``_STEP_COLUMNS`` a
    grid step, the wider of two that cost the same.  Where none divides
    ``n`` (1856 = 14.5 x 128) the last tile is partly empty: Pallas pads
    what a block reads past the array's edge and drops what it writes
    there, and a column of any of the three products depends on no
    other column.  ``whole_tiles`` takes only tiles that divide ``n``.
    ``n`` itself up to 128 columns (small test shapes) and where no
    tile qualifies."""
    if n <= 128:
        return n
    fit = [tile for tile in range(1024, 0, -128)
           if tile <= n and k * tile * itemsize <= _RHS_BLOCK_BYTES
           and not (whole_tiles and n % tile)]
    if not fit:
        return n if whole_tiles else 128
    return min(fit, key=lambda tile: -(-n // tile) * (tile + _STEP_COLUMNS))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def gmm(lhs, rhs, tile_group, n_tiles, *, transpose_rhs: bool = False,
        name: str = "grouped_matmul", interpret: bool = False):
    """``lhs (M, K) @ rhs[tile_group[t]]`` for each of the first
    ``n_tiles`` row tiles; ``rhs`` is ``(G, K, N)``, or read as
    ``(G, N, K)^T`` with ``transpose_rhs``.  Returns ``(M, N)`` in
    ``lhs.dtype``, fp32 accumulation."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    if m % TILE_M:
        raise ValueError(f"{m} rows are no whole number of {TILE_M}-row "
                         "tiles")
    # transposed, a partly empty last tile over a contraction that is no
    # multiple of 128 hung the chip (PR 34: 2688 rows of (8, 2688, 1856)
    # in tiles of 1024; in whole tiles of 896 it ran): whole tiles there
    tile_n = _column_tile(n, k, jnp.dtype(rhs.dtype).itemsize,
                          whole_tiles=transpose_rhs and k % 128 != 0)
    contract = (((1,), (1,)), ((), ())) if transpose_rhs \
        else (((1,), (0,)), ((), ()))

    def kernel(tile_group_ref, lhs_ref, rhs_ref, out_ref):
        del tile_group_ref  # the index maps read it
        out_ref[...] = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], contract,
            preferred_element_type=jnp.float32).astype(out_ref.dtype)

    if transpose_rhs:
        rhs_spec = pl.BlockSpec((None, tile_n, k),
                                lambda j, t, group: (group[t], j, 0))
    else:
        rhs_spec = pl.BlockSpec((None, k, tile_n),
                                lambda j, t, group: (group[t], 0, j))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[
                pl.BlockSpec((TILE_M, k), lambda j, t, group: (t, 0)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec((TILE_M, tile_n),
                                   lambda j, t, group: (t, j)),
            grid=(pl.cdiv(n, tile_n), n_tiles),
        ),
        compiler_params=_params(),
        interpret=interpret,
        name=name + ("_gmm_t" if transpose_rhs else "_gmm"),
    )(tile_group, lhs, rhs)


def tgmm(lhs, rhs, tile_group, n_tiles, n_groups: int, *,
         name: str = "grouped_matmul", interpret: bool = False):
    """``out[g] = sum over the tiles t of group g of lhs_t^T @ rhs_t``:
    ``lhs (M, K)``, ``rhs (M, N)`` -> ``(n_groups, K, N)`` in
    ``rhs.dtype``, fp32 accumulation.  Tiles of one group are
    consecutive and every group owns at least one."""
    m, k = lhs.shape
    n = rhs.shape[1]
    # the fp32 accumulator is a (k, tile_n) block: same budget
    tile_n = _column_tile(n, k, 4)

    def kernel(tile_group_ref, lhs_ref, rhs_ref, out_ref, acc_ref):
        t = pl.program_id(1)
        last = pl.num_programs(1) - 1
        group = tile_group_ref[t]
        first_of_group = jnp.logical_or(
            t == 0, tile_group_ref[jnp.maximum(t - 1, 0)] != group)
        last_of_group = jnp.logical_or(
            t == last, tile_group_ref[jnp.minimum(t + 1, last)] != group)

        @pl.when(first_of_group)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(last_of_group)
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n_groups, k, n), rhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[
                pl.BlockSpec((TILE_M, k), lambda j, t, group: (t, 0)),
                pl.BlockSpec((TILE_M, tile_n), lambda j, t, group: (t, j)),
            ],
            out_specs=pl.BlockSpec((None, k, tile_n),
                                   lambda j, t, group: (group[t], 0, j)),
            grid=(pl.cdiv(n, tile_n), n_tiles),
            scratch_shapes=[pltpu.VMEM((k, tile_n), jnp.float32)],
        ),
        compiler_params=_params(),
        interpret=interpret,
        name=name + "_tgmm",
    )(tile_group, lhs, rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def grouped_matmul(lhs, rhs, tile_group, n_tiles,
                   name: str = "grouped_matmul", interpret: bool = False):
    """Differentiable ``gmm``: ``lhs (M, K)``, ``rhs (G, K, N)`` ->
    ``(M, N)``; see the module docstring for the layout."""
    return gmm(lhs, rhs, tile_group, n_tiles, name=name, interpret=interpret)


def _grouped_matmul_fwd(lhs, rhs, tile_group, n_tiles, name, interpret):
    out = gmm(lhs, rhs, tile_group, n_tiles, name=name, interpret=interpret)
    return out, (lhs, rhs, tile_group, n_tiles)


def _grouped_matmul_bwd(name, interpret, res, g):
    lhs, rhs, tile_group, n_tiles = res
    kw = dict(name=name, interpret=interpret)
    d_lhs = gmm(g, rhs, tile_group, n_tiles, transpose_rhs=True, **kw)
    d_rhs = tgmm(lhs, g, tile_group, n_tiles, rhs.shape[0], **kw)
    return d_lhs, d_rhs.astype(rhs.dtype), None, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
