"""Sum an expert buffer's placed rows into their tokens, as a Pallas TPU
kernel.

``routed_experts`` (parallel/expert.py) lays the rows of a chip's held
experts out in a buffer, expert by expert, each expert's rows padded to
whole tiles of ``TILE_M``.  On a rung of its ladder with fewer rows than
there are assignments the layer's output is summed from the buffer's
side: each placed row ``r`` belongs to token ``token[r]``, and

    out[t] = sum over the placed rows r with token[r] == t of rows[r]

(times the row's weight ``weight[r]`` in the forward), in float32, cast
to ``rows.dtype`` at the end.  Its transpose is the gather that fills
the buffer, left to XLA; the sum is in turn that gather's transpose.

**The invariant.**  Each assignment ``a = t * top_k + j`` is ranked
within its expert by a cumulative sum in assignment order, so inside a
held expert's part of the buffer the rows come in strictly increasing
token order.  For a block of consecutive tokens each held expert's rows
are then one contiguous range ``[lo, hi)`` of the buffer, whose bounds
are the expert's first row plus the running count of its assignments
before the block (``block_ranges``).  A token picks an expert once, so
no token appears twice in one range.

**The kernel** (``sum_rows``, ``pallas_call`` named ``<name>_rows``).
One output block is ``block`` consecutive tokens, accumulated in a
float32 VMEM scratch.  The buffer is read in windows of ``window`` rows
(a window lies inside one expert's tiles); a *visit* is one window of
one range, and the grid walks the visits block by block, expert by
expert (``visits``: a block with no held row gets one empty visit, so
every output block is written).  The tables — each visit's block,
window and rows, and each row's token and weight — are scalar-prefetched
into SMEM, and the windows come through the pipeline's own
double-buffered DMA.  Mosaic loads and stores only whole sublane tiles
at a dynamic row, so a grid step takes its window's rows 16 at a time
and adds each, as a select on the VPU, into the 8-row tile of the
accumulator that holds its token's row, ``f32(row) * weight`` where
weighted: the scatter-add's arithmetic, only the order of the float32
additions may differ.  A row of the group outside the visit adds zero
(a select, so the undefined rows of a tile's padding and past the
tiles in use reach nothing).  There is no slow path: a range longer
than a window takes one visit a window, on the same path, and
``max_visits`` bounds the visits statically.

**Where it runs** (``row_plan``): on the kernels' path (``impl ==
"pallas"``) wherever the scalar tables fit ``_SMEM_BUDGET_BYTES``; the
plan is said once a shape in the log beside the buffer's ladder, e.g.
"smallthinker_experts: rung 51200 summed into 16384 tokens by
smallthinker_experts_rows (blocks of 1024 tokens, windows of 128 rows,
at most 928 visits)".

**Measured** on a TPU v5 lite (JAX 0.9.0), at the three lower rungs
that sum from the buffer's side, uniform routing, the walk's tables
included; ms a call, weighted / not, and the visits:

===================  ============  ============  ============
tiling               51 200 rows   14 336 rows   7 168 rows
===================  ============  ============  ============
(n, d)               16 384, 2560  8 192, 2048   8 192, 2688
held rows            24 542        5 184         3 042
XLA scatter-add      7.88 / 7.93   1.65 / 1.66   1.17 / 1.18
256 x 128, branch    1.75 / 1.72   0.63 / 0.63   0.47 / 0.47
512 x 64, branch     1.45 / 1.44   0.61 / 0.60   0.42 / 0.43
512 x 128, branch    1.44 / 1.43   0.68 / 0.68   0.42 / 0.42
1024 x 128, branch   1.27 / 1.27   0.46 / 0.47   0.41 / 0.42
512 x 128            1.23 / 1.22   0.69 / 0.70   0.42 / 0.41
1024 x 128           1.01 / 0.99   0.44 / 0.45   0.40 / 0.42
visits, 1024 x 128   438           288           84
===================  ============  ============  ============

(tokens a block x rows a window; "branch": a row outside the visit
skipped by a branch in place of the select.)  The kernel is faster at
all three, and blocks of 1024 tokens in windows of 128 rows without a
branch are the fastest at the two larger rungs and within 0.02 ms of
the fastest at the third.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: tokens of one output block, and rows of one window of the buffer
BLOCK = 1024
WINDOW = 128
#: what the scalar tables (a token, and a weight, a buffer row; four
#: words a visit) may take of the 1 MiB of SMEM
_SMEM_BUDGET_BYTES = 512 * 1024
_VMEM_LIMIT_BYTES = 48 * 1024 * 1024
#: rows of the float32 accumulator's sublane tile, and of a window's
#: rows loaded at once (a bfloat16 tile)
_SUBLANES = 8
_GROUP = 16


@dataclasses.dataclass(frozen=True)
class RowPlan:
    """How ``sum_rows`` walks a call's shape (static: part of a jit
    key); one log line a shape."""

    name: str
    rows: int
    n: int
    count: int
    block: int
    window: int
    pallas: bool = True

    @property
    def blocks(self) -> int:
        return -(-self.n // self.block)

    @property
    def max_visits(self) -> int:
        """A bound on the visits: every window of the buffer once, one
        more at each end of each (block, expert) range, and an empty
        visit for a block with no row."""
        return (self.rows // self.window
                + self.blocks * (2 * self.count + 1))

    def __str__(self):
        head = (f"{self.name}: rung {self.rows} summed into {self.n} tokens "
                "by ")
        if not self.pallas:
            return head + "an XLA scatter-add"
        return head + (f"{self.name}_rows (blocks of {self.block} tokens, "
                       f"windows of {self.window} rows, at most "
                       f"{self.max_visits} visits)")


def row_plan(n: int, rows: int, count: int, name: str = "expert_rows",
             pallas: bool = True) -> RowPlan:
    """The kernel where its scalar tables (a token and a weight a buffer
    row, four words a visit) fit SMEM, else the scatter-add."""
    plan = RowPlan(name, rows, n, count, min(BLOCK, -(-n // 8) * 8), WINDOW)
    table_bytes = 4 * (2 * rows + 4 * plan.max_visits)
    return dataclasses.replace(
        plan, pallas=pallas and table_bytes <= _SMEM_BUDGET_BYTES)


def block_ranges(onehot, starts, top_k: int, plan: RowPlan):
    """``(lo, hi)``, each ``(blocks, count)``: the buffer rows of held
    expert ``e`` whose tokens lie in block ``b`` are ``lo[b, e] ..
    hi[b, e] - 1``.  ``onehot (n * top_k, count)`` says which held
    expert each assignment went to (none where it went elsewhere);
    ``starts (count,)`` each expert's first row."""
    pad = (plan.blocks * plan.block - plan.n) * top_k
    in_block = jnp.pad(onehot, ((0, pad), (0, 0))).reshape(
        plan.blocks, plan.block * top_k, -1).sum(1, dtype=jnp.int32)
    lo = starts[None, :] + jnp.cumsum(in_block, 0) - in_block
    return lo, lo + in_block


def visits(lo, hi, plan: RowPlan):
    """The grid's walk: ``(block, window, first, end)`` of each visit,
    ``(plan.max_visits,)`` each (the buffer rows ``first .. end - 1`` of
    window ``window`` added into output block ``block``), block by
    block, expert by expert, and last the number of visits."""
    blocks, count = lo.shape
    window = plan.window
    spans = jnp.where(hi > lo, (hi - 1) // window - lo // window + 1, 0)
    # a block with no row: one empty visit, so that it is written
    spans = spans.at[:, 0].max((spans.sum(1) == 0).astype(jnp.int32))
    spans = spans.reshape(-1)
    ends = jnp.cumsum(spans)
    v = jnp.arange(plan.max_visits, dtype=jnp.int32)
    pair = jnp.minimum(jnp.searchsorted(ends, v, side="right"),
                       blocks * count - 1).astype(jnp.int32)
    lo, hi = lo.reshape(-1)[pair], hi.reshape(-1)[pair]
    chunk = jnp.minimum(lo // window + v - (ends - spans)[pair],
                        plan.rows // window - 1)
    first = jnp.clip(lo, chunk * window, (chunk + 1) * window)
    end = jnp.clip(hi, first, (chunk + 1) * window)
    return pair // count, chunk, first, end, ends[-1]


def sum_rows(rows, token, weight, walk, plan: RowPlan,
             interpret: bool = False):
    """``out (n, d)`` in ``rows.dtype``: the sum in float32 of the
    buffer rows each visit of ``walk`` (``visits``) names, into their
    tokens ``token (R,)``, each times ``weight (R,)`` (float32) where
    given.  See the module docstring."""
    _, d = rows.shape
    block, window = plan.block, plan.window
    *walk, n_visits = walk
    weighted = weight is not None

    def kernel(v_block, v_chunk, v_first, v_end, token_ref, *refs):
        if weighted:
            weight_ref, *refs = refs
        rows_ref, out_ref, acc_ref = refs
        v = pl.program_id(0)
        last = pl.num_programs(0) - 1
        b = v_block[v]

        @pl.when(jnp.logical_or(v == 0, v_block[jnp.maximum(v - 1, 0)] != b))
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        row0 = v_chunk[v] * window
        token0 = b * block
        first, end = v_first[v], v_end[v]
        sublane = lax.broadcasted_iota(jnp.int32, (_SUBLANES, d), 0)

        def add_group(g, carry):
            # Mosaic loads and stores whole sublane tiles at a dynamic
            # row: a group of rows at once, each added into the 8-row
            # tile of the accumulator that holds its token's row; a row
            # outside the visit adds zero to the first tile (a select
            # and no branch: faster on the chip, and its NaN goes nowhere)
            at = pl.multiple_of(g * _GROUP, _GROUP)
            group = rows_ref[pl.ds(at, _GROUP), :].astype(jnp.float32)
            for s in range(_GROUP):
                r = row0 + at + s
                inside = jnp.logical_and(r >= first, r < end)
                row = group[s:s + 1]
                if weighted:
                    row = row * weight_ref[r]
                t = jnp.where(inside, token_ref[r] - token0, 0)
                tile = pl.multiple_of(t // _SUBLANES * _SUBLANES, _SUBLANES)
                acc_ref[pl.ds(tile, _SUBLANES), :] += jnp.where(
                    jnp.logical_and(sublane == t % _SUBLANES, inside), row,
                    0.0)
            return carry

        lax.fori_loop((first - row0) // _GROUP,
                      (end - row0 + _GROUP - 1) // _GROUP, add_group, 0)

        @pl.when(jnp.logical_or(
            v == last, v_block[jnp.minimum(v + 1, last)] != b))
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    tables = (token, weight.astype(jnp.float32)) if weighted else (token,)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((plan.n, d), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4 + len(tables),
            in_specs=[pl.BlockSpec(
                (window, d), lambda v, v_block, v_chunk, *_: (v_chunk[v], 0))],
            out_specs=pl.BlockSpec(
                (block, d), lambda v, v_block, *_: (v_block[v], 0)),
            grid=(n_visits,),
            scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=plan.name + "_rows",
    )(*walk, *tables, rows)
