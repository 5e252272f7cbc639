"""Fused softmax attention as a Pallas TPU kernel.

The transformer family's hot op (beyond-parity surface — the reference
predates attention; its analogue is routing conv/LRN to cuDNN,
SURVEY.md §2.12).  One program owns one Q block of one (batch, head)
and walks that head's K/V, resident in VMEM, in key TILES: scores,
causal/position mask, an online softmax and the PV matmul a tile at a
time, so the (Tq, Tk) score matrix never round-trips HBM the way the
composed XLA form's does, and never exists whole in VMEM either.
Softmax statistics are computed in fp32 regardless of the compute
dtype.

Scope notes:

* Under a causal mask over the DEFAULT positions (``q_pos`` and
  ``k_pos`` both None: local aranges, what every model passes) both
  passes visit only the tiles that hold an unmasked score: q block j
  walks keys [0, (j+1) * block), masks only the tiles the diagonal
  crosses, and never touches the rest.  The walk is two rolled
  ``fori_loop``s inside the kernel (unmasked tiles, then masked ones)
  whose trip counts depend on the block's index; each body is lowered
  once whatever the number of tiles.  ``tile_plan`` is the mechanism's
  counter, a pure function of the shape: "n of m tiles" (3 of 4 at
  (1024, 1024), 10 of 16 at (2048, 2048), 1 of 1 at 128).
* EXPLICIT positions (``parallel/sequence.py``'s all-gather strategy
  passes traced ones) visit EVERY tile and mask each score by score,
  exactly as the one-pass kernel did: the skip is static and engages
  only where ``fused_attention`` sees that both are None.  A fully
  masked row comes out uniform over all keys, forward and backward, as
  before.  ``causal=False`` visits m of m through the same code.
* Blocks are held TRANSPOSED, (key tile, q block): a softmax row runs
  down the sublanes, so its max, sum, lse and rescale are lane-dense
  (1, q block) vectors.  As (q block, 1) columns they cost a tile's
  worth of vector work a visit and 512 bytes a row in VMEM and HBM.
  The forward contracts a V tile over its rows as it lies, and ``out``
  leaves it as (D, q block), turned by XLA as the operand of the next
  matmul (turned in the kernel it cost the forward 13%, 40% at one
  tile).
* The kernels above hold K/V for one (batch, head) RESIDENT in VMEM,
  up to a few thousand keys (2 560 at head 128 for both passes).  Over
  the default positions a shape that either resident pass cannot hold,
  and every call with a sliding ``window=``, takes the STREAMED kernels
  instead (``TilePlan.stream``; the resident plans of every other shape
  are as they were, pinned by tests/test_attention_tiles.py): a grid
  step is one (q block, key tile) pair the mask leaves
  (``_stream_visits``), listed in scalar-prefetched tables that the
  index maps read, so a key tile outside the causal triangle or the
  window is neither computed nor fetched from HBM, and consecutive
  visits of one block fetch it once; the running max, sum and
  accumulator live in VMEM scratch across a q block's visits.  The
  backward streams too: a dK/dV pass key tile by key tile (the group's
  query heads innermost) and a dQ pass q block by q block, each over
  the same pairs, storing nothing but the forward's ``out`` and
  ``lse``.  A window of W keys is ``(i - W, i]``: the query's own key
  and the W - 1 before it; at (16 384, 16 384), blocks and tiles of
  512, a window of 4 096 visits 252 of 1 024 tiles, the causal mask
  528.  The streamed kernels rotate nothing: a caller's ``rotary=``
  table is applied by ``rotary_xla`` before them.  Explicit positions
  keep the resident kernels or, past them, the XLA path (a window over
  explicit positions too): GLOBAL long context over devices is the
  ring/Ulysses layer's job (parallel/sequence.py).
* Backward is ALSO fused (flash-style): the fwd emits the per-row
  logsumexp, and the bwd kernel recomputes p from (q, k, lse) tile by
  tile, accumulating dq in the walk's carry and dk/dv in fp32 VMEM
  scratch — the (Tq, Tk) matrix never exists in either direction.  A
  row's sum of dp * p over all its keys, which no tile sees, is
  g . out (so ``out`` is a residual; the next layer's matmul keeps it
  anyway).  Its five products take float32 operands, which on the
  chip cost nothing to speak of: over the tiles visited a backward
  call runs at 84.7% of the bf16 peak at head 128 ((4, 2048, 16|16,
  128): 1.287 ms for 0.2147 TFLOP) and at 40% at head 64, where a
  contraction of 64 half-fills the 128-wide array and the shape allows
  ~50% (ledger, PR 32; PERF.md section 7).  Ragged q-blocks or
  oversize shapes fall back to the composed-XLA VJP.
* HOW A HEAD IS REACHED follows from what the caller hands over (no
  option; ``TilePlan.rotates``).  A caller that passes ``rotary=``
  hands q, k, v as its projections leave them; where a head fills
  whole lanes (``head_dim % 128 == 0``) both passes then take them
  (and g) as (B, T, H * D), a free reshape of (B, T, H, D), and pick a
  head's lanes in the ``BlockSpec``'s index map; the backward writes
  dq, dk, dv the same way: no XLA transpose on either side of either
  kernel (ten of 33.5 MB a layer at (4, 2048, 16, 128) before).  The
  forward's ``out`` stays (B * H, D, T), ``lse`` and ``delta`` keep
  their rows.  These kernels are handed the positions only where a
  mask has to read them (explicit ones under ``causal``): over the
  defaults a masked tile's mask comes from the block's and the tile's
  indices, and the key-position column (512 bytes a key in VMEM) is
  not held.  Every other call keeps the folded kernels, (B * H, T, D)
  through ``_fold`` / ``unfold``, to the parent's jaxpr
  (tests/test_attention_contract.py): a head of 64 (the ``gpt2m``
  cells) because a 64-lane block of a 1 024-lane row is not a block
  Mosaic takes (two heads of 64 in one 128-lane block is the next
  step there; it needs lane slices inside the kernel bodies); and a
  head of 128 whose caller rotates in XLA (``ZayaLM``: partial rotary
  behind a unit norm and per-head convolutions) because there the
  head-major layout is what XLA's own passes before the kernel write
  anyway: by index map that cell's step read 1.4% LONGER on the chip
  (copies and the rotation's fusions +2.3 ms, the kernels +3.5% on
  rows of 256 bytes at a stride; PERF.md section 6, PR 33).
* ROTARY EMBEDDING: ``fused_attention(..., rotary=rotary_table(...))``
  rotates q and k before the score product (halves paired, float32,
  rounded once to the input dtype).  Where a head fills whole lanes
  the KERNELS do it: ``x * cos + roll(x, D / 2 lanes) * (-sin | sin)``,
  one lane roll where XLA splits a head at lane 64, relays out and
  concatenates (four to six passes over q and k; with the head folds
  1.58 ms of XLA passes a layer forward and 1.36 backward at (4,
  2048, 16, 128), beside kernels of 0.83 and 1.29: PERF.md section
  5.3c).  The q block is rotated by its program, K once a (batch,
  key/value head) into a VMEM scratch the later programs read (the
  grid runs in order on one core); the backward rotates both
  again for its recomputed scores and turns dq and dk back in float32
  before their one rounding (one rounding fewer than ``rope``'s VJP
  behind the kernel).  The table is ONE (T, D) float32 array, cos |
  sin, held whole in VMEM (1 MiB at (2048, 128), twice for the
  pipeline: what the key-position column freed); a caller makes it
  once a step.  Elsewhere (a head under 128, a ragged q tail, the
  composed form) ``rotary_xla`` rotates, to the same numbers.
* Each pass is a module-level ``jax.jit`` with the plan among its
  static arguments: the 24 call sites of a step program share one
  traced jaxpr and one lowered function a pass, and a model built
  eagerly compiles the forward once a process, not once a layer.  (A
  bare ``pallas_call`` is traced and lowered to its Mosaic module
  again at every call site, on every start, before any cache key
  exists: PERF.md §6, PR 28.)
* ``impl=None`` or ``'auto'``: Pallas on TPU, XLA elsewhere; a caller
  forces one with ``impl='pallas'|'xla'`` (interpret mode, CPU platform
  only, makes the Pallas path unit-testable — tests/test_ops.py;
  ``TransformerLM_TP`` passes ``'xla'`` under GSPMD).
  Every choice made from a shape is logged once per shape at trace
  time (logger ``theanompi_tpu.ops.attention``; a warning when a TPU
  run takes the XLA form), so no path is taken quietly: ``pallas
  (fits, q block 512, key tile 512, 3 of 4 tiles)``, and at head 128
  ``pallas (fits, q block 512, key tile 512, 10 of 16 tiles, heads by
  index map, rotary in kernel)``.
* Grouped-query heads: k/v may carry FEWER heads than q (Hq a
  multiple of Hkv; query head h reads key/value head h // (Hq/Hkv)).
  The kernels pick the shared head by index map — k/v are never
  repeated in HBM; the fused bwd walks the group's query heads in
  its innermost grid axis and accumulates their dk/dv in the same
  VMEM scratch.  The XLA fallback repeats k/v (it is the fallback).
* The q block and the key tile are chosen per shape (``_q_block``,
  ``_key_tile``): ``_Q_BLOCK`` (512), else its halves down to 128, the
  largest that divides the length (and, for the q block, keeps BOTH
  passes inside the VMEM budget); a length shorter than the block is
  one block.  A visit costs ~0.35 us
  whatever its size (two dependent matmuls and a softmax between them,
  nothing to overlap in a rolled loop), so on the chip 512 x 512 beats
  every smaller plan at both benchmark shapes although 256 x 256 skips
  more (PERF.md §6, PR 29).
* ``name=`` labels the ``pallas_call``s (``<name>_fwd`` /
  ``<name>_bwd``; the streamed backward's two ``<name>_bwd_kv`` and
  ``<name>_bwd_q``) so a trace reducer can tell one model's attention
  from another custom call; None keeps Pallas's default.
* On-chip status (TPU v5 lite, JAX 0.9.0 / libtpu 0.0.34, PR 33): fwd
  and the fused bwd compile and match the XLA form at (8, 1024, 12,
  64), with 8 query over 2 key/value heads of 128 at (4, 2048, 8|2,
  128), and by index map with the rotation inside at (4, 2048, 16|16,
  128), bf16 causal, 3 of 4 and 10 of 16 tiles of 512 x 512
  (chip_smoke.py's three checks); the four LM cells' reference checks
  run both passes against float32 at (8, 1024, 16, 64), (64, 128, 16,
  64), (4, 2048, 8|2, 128) and (4, 2048, 16|16, 128) rotating.
  Since PR 38 a head of 256 runs both passes on the chip too, (4, 2048,
  16|2, 256) bf16 causal in the Qwen3-Next cell (its reference check
  runs them against float32), under a VMEM budget of its own
  (``_vmem_budget``: a head of 256 holds twice a head of 128's K and V,
  and its fused backward 24.5 MiB at a q block of 512; every smaller
  head keeps the 16 MiB it was graded at, pinned by
  tests/test_attention_tiles.py).  Explicit positions,
  ``causal=False``, the rotation with grouped heads or a head of 256,
  and lengths that take a q block under the key tile are compiled for
  the chip (tests/test_attention_tiles.py) and have not run on it; the
  ragged-q-tail path has not been compiled.
"""

from __future__ import annotations

import functools
import logging
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from theanompi_tpu.ops import pallas_mode

_log = logging.getLogger(__name__)

# large-negative mask value: finite so softmax/online-softmax
# accumulators never produce inf-inf=nan; exp(-1e30 - m) underflows to
# exactly 0 once any real score is seen, wiping masked contributions.
# The single source — parallel/sequence.py imports it.
_MASK_NEG = -1e30
#: what one program of either pass may hold in VMEM: the compiler's
#: own scoped limit on a v5e (16 MiB), against estimates that count
#: what the pipeline really holds (``_fits_vmem*``).
_VMEM_BUDGET_BYTES = 16 * 1024 * 1024
#: the budget of a head of 256 lanes or more (``_vmem_budget``): its
#: fused backward holds a head's Q, G, dq, K, V, dk and dv whole, twice,
#: 24.5 MiB at (2048, 2048, 256) bf16 with a q block of 512, and a v5e
#: core has 128 MiB
_WIDE_HEAD_BUDGET_BYTES = 32 * 1024 * 1024
#: the largest q block and key tile a plan takes.  Graded on the chip
#: (PERF.md §6, PR 29; fwd+bwd of one layer): at (8, 1024, 16, 64)
#: 512 x 512 reads 1.660 ms against 2.311 (256 x 256), 2.044
#: (256 x 512), 1.916 (512 x 256) and 1.821 (1024 x 1024); at
#: (4, 2048, 8|2, 128) 1.320 against 2.051 (256 x 256) and 1.443
#: (1024 x 1024).
_Q_BLOCK = 512
#: the streamed kernels' largest q block or key tile: a length that no
#: configured size divides would be one block of all its rows
_STREAM_BLOCK_LIMIT = 2048


def block_scores(q, k, scale):
    """q (B,Tq,H,D) x k (B,Tk,H,D) -> (B,H,Tq,Tk); fp32 accumulation.
    Shared with parallel/sequence.py's ring/oracle forms."""
    return jnp.einsum("bqhd,bkhd->bhqk", q, k,
                      preferred_element_type=jnp.float32) * scale


def causal_mask(q_pos, k_pos, window: int | None = None):
    """(Tq, Tk): key j is seen by query i iff ``k_pos[j] <= q_pos[i]``
    and, under a ``window`` of W keys, ``q_pos[i] - k_pos[j] < W``."""
    ahead = q_pos[:, None] - k_pos[None, :]
    seen = ahead >= 0
    if window is not None:
        seen &= ahead < window
    return seen


class TilePlan(NamedTuple):
    """How both passes tile one (Tq, Tk) score square: the q block, the
    key tile, whether tiles above the diagonal are left out (``skip``:
    a causal mask over the default positions), and the mechanism's
    counter, ``visited`` of ``total`` tiles; then the kernels' contract
    with their caller: where the rotary embedding a caller asked for
    runs (``rotary``: ``'kernel'``, ``'XLA'`` or None = none was asked
    for; the kernels that rotate also pick a head by index map from
    (B, T, H * D), the others are handed (B * H, T, D)), and whether
    they are handed the positions (``positions``: always to the folded
    kernels; to the others only where a mask has to read them).  A
    ``window`` of W keys (query i sees keys ``(i - W, i]``) and
    ``stream`` (K/V fetched from HBM a key tile at a time, the streamed
    kernels) come last."""

    q_block: int
    key_tile: int
    skip: bool
    visited: int
    total: int
    positions: bool = True
    rotary: str | None = None
    window: int | None = None
    stream: bool = False

    @property
    def rotates(self) -> bool:
        """Whether the kernels are handed the projections' outputs and
        the table: they pick heads by index map and rotate."""
        return self.rotary == "kernel"

    def __str__(self):
        return (f"q block {self.q_block}, key tile {self.key_tile}, "
                f"{self.visited} of {self.total} tiles"
                + (f", window {self.window}" if self.window else "")
                + (", K/V streamed" if self.stream else "")
                + (", heads by index map" if self.rotates else "")
                + (f", rotary in {self.rotary}" if self.rotary else ""))


def _walk_bounds(j, q_block: int, key_tile: int, n_tiles: int,
                 causal: bool, skip: bool):
    """Key tiles q block ``j`` walks: ``[0, first)`` hold no masked
    score, ``[first, end)`` are masked score by score, the rest are
    never visited.  ``j`` is a Python int (the counter) or a traced
    index (the kernels): one rule for both."""
    if not causal:
        return n_tiles, n_tiles
    if not skip:          # explicit positions: every tile, masked
        return 0, n_tiles
    # default positions: row r sees keys [0, r]
    first = (j * q_block + 1) // key_tile
    end = ((j + 1) * q_block + key_tile - 1) // key_tile
    least = min if isinstance(j, int) else jnp.minimum
    return least(first, n_tiles), least(end, n_tiles)


def _key_tile(tk: int) -> int:
    """The configured block or its halves down to 128, the largest
    that divides ``tk``; where none does, all of ``tk`` as one tile."""
    tile = min(_Q_BLOCK, tk)
    while tk % tile and tile >= 256 and tile % 16 == 0:
        tile //= 2
    return tk if tk % tile else tile


def tile_plan(tq: int, tk: int, d: int, dtype, causal: bool,
              default_positions: bool = True,
              rotary: bool = False, window: int | None = None) -> TilePlan:
    """The plan of a shape: a pure function of shape, dtype, mask,
    whether the caller passed a rotary table, and the module's
    configured sizes, which the kernels, the log line, the tests and
    PERF.md all read.  Over the default positions the streamed kernels
    take a ``window`` and every shape whose K/V the resident forward or
    fused backward cannot hold whole; every other shape keeps the plan
    it had.  A window the
    streamed kernels cannot take (explicit positions, a length no block
    divides) stays on the plan, for ``_resolve_impl`` to route."""
    skip = causal and default_positions
    # a head of whole 128-lane rows is a block of (B, T, H * D) that
    # Mosaic takes; a 64-lane block of a 1 024-lane row is not
    where = ("kernel" if d % 128 == 0 else "XLA") if rotary else None
    positions = where != "kernel" or (causal and not skip)
    q_block = _q_block(tq, tk, d, dtype, positions, where == "kernel")
    if where == "kernel" and tq % q_block:
        # a ragged tail's q block would read past the table's rows
        where = "XLA"
        positions, q_block = True, _q_block(tq, tk, d, dtype)
    key_tile = _key_tile(tk)
    n_blocks, n_tiles = pl.cdiv(tq, q_block), tk // key_tile
    visited = sum(_walk_bounds(j, q_block, key_tile, n_tiles, causal,
                               skip)[1] for j in range(n_blocks))
    plan = TilePlan(q_block, key_tile, skip, visited, n_blocks * n_tiles,
                    positions, where, window)
    rotates = where == "kernel"
    if default_positions and (window is not None or not (
            _fits_vmem(tk, d, dtype, q_block, positions, rotates)
            and _fits_vmem_bwd(tq, tk, d, dtype, q_block, positions,
                               rotates))):
        return _stream_plan(tq, tk, causal, rotary, window) or plan
    return plan


def _stream_plan(tq: int, tk: int, causal: bool, rotary: bool,
                 window: int | None) -> TilePlan | None:
    """The streamed kernels' plan: the configured q block and key tile
    or their halves down to 128, the largest that divide the lengths
    (a length shorter than the block is one block); the rotation, where
    asked for, in XLA.  None where a length takes a block over
    ``_STREAM_BLOCK_LIMIT`` rows (no configured size divides it)."""
    q_block = min(_Q_BLOCK, tq)
    while tq % q_block and q_block >= 256 and q_block % 16 == 0:
        q_block //= 2
    key_tile = _key_tile(tk)
    if tq % q_block or max(q_block, key_tile) > _STREAM_BLOCK_LIMIT:
        return None
    plan = TilePlan(q_block, key_tile, causal, 0,
                    (tq // q_block) * (tk // key_tile), False,
                    "XLA" if rotary else None, window, True)
    return plan._replace(visited=len(_stream_visits(plan, tq, tk)))


def _stream_visits(plan: TilePlan, tq: int, tk: int) -> list:
    """``(q block, key tile, masked)`` of every tile the streamed
    kernels visit, q block by q block: over the default positions query
    i sees key j iff ``0 <= i - j`` (causal) and ``i - j < W`` (a
    window of W), so a tile is visited where some pair of it is seen,
    and masked where some pair is not.  A pure function of the plan and
    the lengths."""
    qb, kt, w = plan.q_block, plan.key_tile, plan.window
    visits = []
    for j in range(tq // qb):
        for t in range(tk // kt):
            if not plan.skip:                   # not causal: every tile
                visits.append((j, t, False))
                continue
            least = j * qb - ((t + 1) * kt - 1)     # least i - j in the tile
            most = (j + 1) * qb - 1 - t * kt        # and the most
            if most < 0 or (w is not None and least >= w):
                continue
            visits.append((j, t, least < 0 or (w is not None
                                               and most >= w)))
    return visits


def _walk(j, body, carry, plan: TilePlan, n_tiles: int, causal: bool):
    """Run ``body(masked)(t, carry)`` over the key tiles q block ``j``
    visits: two rolled loops (unmasked tiles, then masked ones), each
    body lowered once whatever the number of tiles."""
    first, end = _walk_bounds(j, plan.q_block, plan.key_tile, n_tiles,
                              causal, plan.skip)
    if plan.skip or not causal:   # else no tile is known to be unmasked
        carry = jax.lax.fori_loop(0, first, body(False), carry)
    if causal:
        carry = jax.lax.fori_loop(first, end, body(True), carry)
    return carry


def _tile(t, size: int, extent: int):
    """Rows ``[t * size, (t + 1) * size)`` of ``extent``; all of them,
    statically, where one tile is the whole (a length that is no
    multiple of 8 has no aligned dynamic slice)."""
    if size == extent:
        return slice(None)
    return pl.ds(pl.multiple_of(t * size, size), size)


def _operands(refs, plan: TilePlan):
    """A kernel's refs by name: ``(q, k, v, table, qpos, kpos, the
    rest)``, None for what the plan does not hand it."""
    q, k, v, *rest = refs
    table = rest.pop(0) if plan.rotates else None
    qpos, kpos = ((rest.pop(0), rest.pop(0)) if plan.positions
                  else (None, None))
    return q, k, v, table, qpos, kpos, rest


def _default_mask(j, t, plan: TilePlan):
    """The (key tile, q block) causal mask of q block ``j`` against
    key tile ``t`` over the default positions, from their indices."""
    shape = (plan.key_tile, plan.q_block)
    ahead = (jax.lax.broadcasted_iota(jnp.int32, shape, 1)
             - jax.lax.broadcasted_iota(jnp.int32, shape, 0))
    return ahead >= t * plan.key_tile - j * plan.q_block


def _rotate(x, table, inverse: bool = False):
    """The rotary embedding of ``x (rows, D)`` float32 by ``table
    (rows, D)`` (``rotary_table``: cos | sin), halves paired: ``x * cos
    + roll(x, D / 2) * (-sin | sin)``, one lane roll where XLA splits,
    relays out and concatenates.  ``inverse``: by the negative angle,
    the rotation's transpose, for a gradient."""
    half = x.shape[1] // 2
    swapped = pltpu.roll(table, half, 1)              # sin | cos
    first = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) < half
    cos = jnp.where(first, table, swapped)
    sin = (jnp.where(first, swapped, -table) if inverse
           else jnp.where(first, -swapped, table))
    return x * cos + pltpu.roll(x, half, 1) * sin


def _rotate_keys(k_ref, table_ref, rotated, plan: TilePlan):
    """A head's K, rotated a key tile at a time and rounded once to its
    own dtype (where ``rotary_xla`` rounds), into the scratch both
    passes read their key tiles from."""
    tk = k_ref.shape[1]

    def one(t, _):
        ks = _tile(t, plan.key_tile, tk)
        rotated[ks] = _rotate(k_ref[0, ks].astype(jnp.float32),
                              table_ref[ks]).astype(rotated.dtype)
        return 0

    jax.lax.fori_loop(0, tk // plan.key_tile, one, 0)


def _kernel(*refs, scale, causal, plan, group):
    """One q block of one (batch, head) against that head's resident
    K/V, walked in key tiles with an online softmax.  Scores are held
    TRANSPOSED, (key tile, q block): a row of the softmax runs down the
    sublanes, so its max, sum and rescale are lane-dense (1, q block)
    vectors (as (q block, 1) columns they cost a tile's worth of work a
    visit), and they and the fp32 (D, q block) accumulator are the
    loop's carry.  With a rotary table the q block is rotated here and
    K once a (batch, key/value head), at the first q block of the
    group's first query head, into a scratch the later programs read."""
    q_ref, k_ref, v_ref, table_ref, qpos_ref, kpos_ref, rest = _operands(
        refs, plan)
    o_ref, lse_ref, *scratch = rest
    d, tq_blk = o_ref.shape[1:]
    tile = plan.key_tile
    q = q_ref[0]                                      # (TQB, D)
    j = pl.program_id(1)
    keys = lambda ks: k_ref[0, ks]  # noqa: E731
    if table_ref is not None:
        rotated, = scratch
        keys = lambda ks: rotated[ks]  # noqa: E731

        @pl.when(jnp.logical_and(j == 0, pl.program_id(0) % group == 0))
        def _():
            _rotate_keys(k_ref, table_ref, rotated, plan)

        q = _rotate(q.astype(jnp.float32),
                    table_ref[_tile(j, tq_blk, table_ref.shape[0])]
                    ).astype(q.dtype)

    def body(masked):
        def step(t, carry):
            m, l, acc = carry
            ks = _tile(t, tile, k_ref.shape[1])
            s = jax.lax.dot_general(
                keys(ks), q, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (TILE, TQB)
            if masked:
                mask = (qpos_ref[:] >= kpos_ref[ks]   # (1,TQB)>=(TILE,1)
                        if plan.positions else _default_mask(j, t, plan))
                s = jnp.where(mask, s, _MASK_NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + jnp.sum(p, axis=0, keepdims=True)
            acc = alpha * acc + jax.lax.dot_general(
                v_ref[0, ks], p.astype(v_ref.dtype),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)   # v^T p (D, TQB)
            return m_new, l, acc
        return step

    # a finite floor, not -inf: a fully masked row keeps m at the floor
    # and comes out uniform over every key visited, as the one-pass
    # softmax gave it
    m, l, acc = _walk(
        j, body,
        (jnp.full((1, tq_blk), _MASK_NEG, jnp.float32),
         jnp.zeros((1, tq_blk), jnp.float32),
         jnp.zeros((d, tq_blk), jnp.float32)),
        plan, k_ref.shape[1] // tile, causal)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)                       # (1, TQB) fp32


def _vmem_budget(d: int) -> int:
    """What a program may hold at head size ``d``: the compiler's
    scoped default up to a head of 128 (every plan the LM cells run was
    graded under it), twice that from a head of 256 on, whose K and V
    alone are twice a head of 128's."""
    return _VMEM_BUDGET_BYTES if d < 256 else _WIDE_HEAD_BUDGET_BYTES


def _compiler_params(d: int):
    """The compiler may take twice what the estimates admit: its own
    count of the fused backward grows with batch x heads in a way they
    do not model ((2, 4096, 16, 64) bf16 under 16 MiB, (8, 4096, 16,
    64) 18.0, (16, 3584, 16, 64) 17.5), and a v5e core has 128 MiB."""
    return pltpu.CompilerParams(vmem_limit_bytes=2 * _vmem_budget(d))


def _fold(x):                                # (B,T,H,D) -> (B*H,T,D)
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _heads(x, plan: TilePlan):
    """``x (B, T, H, D)`` as the kernels index it: (B, T, H * D), which
    is ``x`` as a projection leaves it, where they rotate and pick a
    head's lanes by index map; else (B * H, T, D), a transpose in HBM."""
    if plan.rotates:
        return x.reshape(*x.shape[:2], -1)
    return _fold(x)


def _table_spec(table):
    """The rotary table whole, at one block index for every program:
    fetched once a call."""
    return pl.BlockSpec(table.shape, lambda i, j: (0, 0),
                        memory_space=pltpu.VMEM)


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "interpret", "name", "plan"))
def _pallas_attention(q, k, v, q_pos, k_pos, table=None, *, scale, causal,
                      interpret, plan: TilePlan, name: str | None = None):
    """The forward pass -> (out (B,Tq,H,D), lse (B*H,1,Tq) fp32: a
    row a head, which HBM holds as it is; a (Tq, 1) column there pads
    every row to 128 lanes, 64 MB a layer at (8, 1024, 16, 64)).
    Jitted at module level with the plan static: every call site of a
    shape shares one traced jaxpr and one lowered function, and an
    eager caller compiles it once a process.  ``q_pos``/``k_pos`` are
    None where the plan hands the kernel no positions, ``table`` is the
    rotary table where it rotates."""
    b, tq, h, d = q.shape
    tk, h_kv = k.shape[1:3]
    bh = b * h
    group = h // h_kv          # query heads per key/value head
    tq_blk = plan.q_block

    operands = [_heads(q, plan), _heads(k, plan), _heads(v, plan)]
    if plan.rotates:
        # program i is query head i % H of batch i // H, in the lanes
        # [head * D, (head + 1) * D) of its row
        query = lambda i, j: (i // h, j, i % h)  # noqa: E731
        shared = lambda i, j: (i // h, 0, i % h // group)  # noqa: E731
    else:
        # folded query row b*Hq + h reads folded key/value row
        # b*Hkv + h // group, which is (b*Hq + h) // group
        query = lambda i, j: (i, j, 0)  # noqa: E731
        shared = lambda i, j: (i // group, 0, 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec((1, tq_blk, d), query, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, tk, d), shared, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, tk, d), shared, memory_space=pltpu.VMEM),
    ]
    scratch = []
    if plan.rotates:
        operands.append(table)
        in_specs.append(_table_spec(table))
        scratch.append(pltpu.VMEM((tk, d), k.dtype))
    if plan.positions:
        operands += [q_pos.astype(jnp.int32).reshape(1, tq),
                     k_pos.astype(jnp.int32).reshape(tk, 1)]
        in_specs += [
            pl.BlockSpec((1, tq_blk), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tk, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
        ]

    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             plan=plan, group=group)
    out, lse = pl.pallas_call(
        kern,
        grid=(bh, pl.cdiv(tq, tq_blk)),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, d, tq_blk), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, tq_blk), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, d, tq), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, tq), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(d),
        interpret=interpret,
        name=name and name + "_fwd",
    )(*operands)
    return out.reshape(b, h, d, tq).transpose(0, 3, 1, 2), lse


def _repeat_kv(q, k, v):
    """k/v with q's head count (the XLA forms only)."""
    group = q.shape[2] // k.shape[2]
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)


def rotary_table(positions, dim: int, theta: float):
    """The rotary embedding's table for ``fused_attention(...,
    rotary=)``: ``(T, dim)`` float32, a row a position, its first half
    the cosines and its second the sines of ``position * theta **
    (-i / (dim / 2))``, i = 0 .. dim / 2 - 1.  Made once a step: every
    layer and pass of a model rotates by the same angles."""
    half = dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.concatenate([jnp.cos(angle), jnp.sin(angle)], -1)


def rotary_xla(x, table):
    """``x (B, T, H, D)`` rotated by ``table (T, D)`` (``rotary_table``)
    in plain XLA, halves paired (i with i + D / 2), in float32, rounded
    once to ``x``'s dtype: what the kernels do to q and k themselves
    where a head fills whole lanes, and the form for every other
    shape."""
    half = x.shape[-1] // 2
    cos, sin = (t[None, :, None, :] for t in (table[:, :half],
                                              table[:, half:]))
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _xla_attention(q, k, v, q_pos, k_pos, scale, causal, window=None):
    """The composed-XLA fallback (same primitives as the oracle)."""
    k, v = _repeat_kv(q, k, v)
    s = block_scores(q, k, scale)
    if causal:
        s = jnp.where(causal_mask(q_pos, k_pos, window)[None, None], s,
                      _MASK_NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def _fits_vmem(tk, d, dtype, tq_blk: int, positions: bool = True,
               rotary: bool = False) -> bool:
    """The forward holds K and V whole, one q block and its output,
    each TWICE (the pipeline's two buffers), and two (key tile, q
    block) fp32 blocks of scores beside the fp32 accumulator; where it
    is handed the positions (``TilePlan.positions``), the keys' as a
    column (512 bytes a key in VMEM), twice; where it rotates, the
    float32 table whole, twice, and the rotated K once (the rotation's
    own float32 blocks live before the walk's and are smaller)."""
    itemsize, tile = jnp.dtype(dtype).itemsize, _key_tile(tk)
    need = (2 * (2 * tk * d * itemsize         # K, V
                 + positions * tk * 512        # key positions
                 + rotary * tk * d * 4         # rotary table
                 + 2 * tq_blk * d * itemsize)  # Q block, out
            + rotary * tk * d * itemsize       # rotated K
            + 2 * tile * tq_blk * 4            # fp32 scores, exp
            + 2 * tq_blk * d * 4)              # accumulator, v p
    return need <= _vmem_budget(d)


def _fits_vmem_bwd(tq, tk, d, dtype, tq_blk: int, positions: bool = True,
                   rotary: bool = False) -> bool:
    """The fused bwd holds whole Q/G/dq plus K/V/dk/dv per (b*h), each
    TWICE (the pipeline's two buffers), fp32 dk/dv scratch and, per
    (key tile, q block), fp32 casts of the operands and two score
    blocks; the key positions, the rotary table and the rotated K as
    the forward does.  At few heads this is
    the v5e compiler's own count (under its default 16 MiB it refused
    (2, 5120, 16, 64) bf16 at 17.50 MiB; this says 17.6); with more
    batch x heads the compiler asks more, which ``_compiler_params``
    leaves room for, so every shape admitted here compiles
    (tests/test_attention_tiles.py).  At (2048, 2048, 128) bf16, q
    block 512: 14.25 MiB with the positions, 12.25 without, 14.75 with
    the rotation and without the positions; with both, 16.75, a plan
    takes a q block of 256."""
    itemsize, tile = jnp.dtype(dtype).itemsize, _key_tile(tk)
    need = (2 * (3 * tq * d * itemsize         # Q, G, dq
                 + 4 * tk * d * itemsize       # K, V, dk, dv
                 + positions * tk * 512        # key positions
                 + rotary * tk * d * 4)        # rotary table
            + rotary * tk * d * itemsize       # rotated K
            + 2 * tk * d * 4                   # fp32 dk/dv scratch
            + 3 * tq_blk * d * 4               # q/g casts, dq carry
            + 2 * tile * d * 4                 # k/v tile casts
            + 2 * tile * tq_blk * 4)           # s/p and dp/ds blocks
    return need <= _vmem_budget(d)


def _q_block(tq, tk, d, dtype, positions: bool = True,
             rotary: bool = False) -> int:
    """The q block of a shape: the configured block, else its halves
    down to 128, the largest that divides ``tq`` and keeps the forward
    AND the fused backward inside the VMEM budget.  Where none does,
    the configured block (one block when ``tq`` is shorter): the
    callers' own checks then route what does not fit or divide."""
    top = min(_Q_BLOCK, tq)
    blk = top
    while blk >= 128 and blk % 8 == 0:
        if (tq % blk == 0
                and _fits_vmem(tk, d, dtype, blk, positions, rotary)
                and _fits_vmem_bwd(tq, tk, d, dtype, blk, positions,
                                   rotary)):
            return blk
        blk //= 2
    return top


@functools.lru_cache(maxsize=None)
def _log_choice(what: str, shape: tuple, dtype: str, choice: str,
                why: str) -> None:
    """One line per (shape, choice): the cache is the once-per-shape
    memory.  Runs at trace time only."""
    level = (logging.WARNING
             if choice == "xla" and jax.default_backend() == "tpu"
             else logging.INFO)
    _log.log(level, "%s q=%s %s -> %s (%s)", what, shape, dtype, choice,
             why)


def _resolve_impl(impl: str | None, q, k,
                  plan: TilePlan | None = None) -> str:
    """``plan``: the call's own; None = that of a causal mask over the
    default positions."""
    impl = impl or "auto"
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown attention impl {impl!r}")
    b, tq, h, d = q.shape
    plan = plan or tile_plan(tq, k.shape[1], d, q.dtype, causal=True)
    if plan.window is not None and not plan.stream:
        # only the streamed kernels mask a window
        impl = "auto" if impl == "pallas" else impl
    if impl != "auto":
        return impl
    if jax.default_backend() != "tpu":
        choice, why = "xla", "not a TPU"
    elif plan.window is not None and not plan.stream:
        choice, why = "xla", ("a window over explicit positions or a "
                              "length no block divides")
    elif plan.stream:
        choice, why = "pallas", f"streams, {plan}"
    elif not _fits_vmem(k.shape[1], d, q.dtype, plan.q_block,
                        plan.positions, plan.rotates):
        choice, why = "xla", "K/V + score block exceed the VMEM budget"
    elif tq % plan.q_block != 0:
        # ragged q-tails rely on Pallas out-of-range block padding,
        # which has only ever run interpreted; impl='pallas' still
        # forces the kernel (how tests cover it)
        choice, why = "xla", f"ragged q-tail (Tq % {_Q_BLOCK} != 0)"
    else:
        choice, why = "pallas", f"fits, {plan}"
    _log_choice("attention fwd", q.shape + k.shape[1:3], str(q.dtype),
                choice, why)
    return choice


def _bwd_kernel(*refs, scale, causal, plan):
    """Flash-style backward for one (batch * key/value head, query head
    of its group): loop q-blocks and, inside, the key tiles each one
    visits; recompute p from (q, k, lse) — no stored score matrix
    anywhere, blocks held transposed as in the forward — accumulating
    dq in the inner loop's carry and dk/dv in fp32 VMEM scratch over
    the q-blocks AND over the group's query heads (the innermost grid
    axis; one head when q and k/v have the same count).  With a rotary
    table q and K are rotated as the forward rotated them (K once, at
    the group's first head), and dq and dk, which are gradients of the
    ROTATED q and k, are turned back in float32 before their one
    rounding."""
    q_ref, k_ref, v_ref, table_ref, qpos_ref, kpos_ref, rest = _operands(
        refs, plan)
    (g_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, dk_s, dv_s,
     *scratch) = rest
    member = pl.program_id(1)
    tq_blk, tile = plan.q_block, plan.key_tile
    tq, d = q_ref.shape[1:]
    tk = k_ref.shape[1]
    keys = lambda ks: k_ref[0, ks]  # noqa: E731
    if table_ref is not None:
        rotated, = scratch
        keys = lambda ks: rotated[ks]  # noqa: E731

    @pl.when(member == 0)
    def _():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)
        if table_ref is not None:
            _rotate_keys(k_ref, table_ref, rotated, plan)

    def q_block(i, _):
        sl = _tile(i, tq_blk, tq)
        q = q_ref[0, sl].astype(jnp.float32)          # (TQB, D)
        if table_ref is not None:   # rounded where the forward rounds
            q = _rotate(q, table_ref[sl]).astype(q_ref.dtype).astype(
                jnp.float32)
        g = g_ref[0, sl].astype(jnp.float32)
        lse, delta = lse_ref[0, i], delta_ref[0, i]   # (1, TQB)
        # a FULLY-masked row (explicit positions alone can make one)
        # saturates lse to _MASK_NEG in fp32 and exp(s-lse)=1
        # everywhere: 1/Tk restores the uniform distribution the
        # forward actually produced there
        share = (jnp.where(lse <= 0.5 * _MASK_NEG, 1.0 / tk, 1.0)
                 if causal and not plan.skip else None)

        def body(masked):
            def step(t, dq):
                ks = _tile(t, tile, tk)
                kmat = keys(ks).astype(jnp.float32)       # (TILE, D)
                vmat = v_ref[0, ks].astype(jnp.float32)
                s = jax.lax.dot_general(
                    kmat, q, (((1,), (1,)), ((), ()))) * scale
                if masked:
                    mask = (qpos_ref[i] >= kpos_ref[ks]   # (1,TQB)>=(TILE,1)
                            if plan.positions
                            else _default_mask(i, t, plan))
                    s = jnp.where(mask, s, _MASK_NEG)
                p = jnp.exp(s - lse)                  # (TILE, TQB)
                if share is not None:
                    p = p * share
                dv_s[ks] += jax.lax.dot_general(
                    p, g, (((1,), (0,)), ((), ())))   # p^T g (TILE, D)
                dp = jax.lax.dot_general(
                    vmat, g, (((1,), (1,)), ((), ())))  # (g v^T)^T
                ds = p * (dp - delta)
                dk_s[ks] += jax.lax.dot_general(
                    ds, q, (((1,), (0,)), ((), ()))) * scale  # ds^T q
                return dq + jax.lax.dot_general(
                    ds, kmat, (((0,), (0,)), ((), ())))   # ds k (TQB, D)
            return step

        dq = _walk(i, body, jnp.zeros((tq_blk, d), jnp.float32), plan,
                   tk // tile, causal)
        if table_ref is not None:
            dq = _rotate(dq, table_ref[sl], inverse=True)
        dq_ref[0, sl] = (dq * scale).astype(dq_ref.dtype)
        return 0

    jax.lax.fori_loop(0, tq // tq_blk, q_block, 0)

    @pl.when(member == pl.num_programs(1) - 1)
    def _():
        if table_ref is None:
            dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        else:
            def one(t, _):
                ks = _tile(t, tile, tk)
                dk_ref[0, ks] = _rotate(dk_s[ks], table_ref[ks],
                                        inverse=True).astype(dk_ref.dtype)
                return 0

            jax.lax.fori_loop(0, tk // tile, one, 0)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "interpret", "name", "plan"))
def _pallas_attention_bwd(q, k, v, q_pos, k_pos, out, lse, g, table=None, *,
                          scale, causal, interpret, plan: TilePlan,
                          name: str | None = None):
    """The fused backward, jitted once a shape like the forward.
    ``delta``, a row's sum of dp * p over ALL its keys, which no one
    tile sees, is g . out, taken here in fp32."""
    b, tq, h, d = q.shape
    tk, h_kv = k.shape[1:3]
    group = h // h_kv
    tq_blk = plan.q_block

    operands = [_heads(q, plan), _heads(k, plan), _heads(v, plan)]
    gf = _heads(g, plan)
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    # what a q block reads whole lies along the lanes, one row a block
    # (the kernel picks the row by its leading index)
    rows = (tq // tq_blk, 1, tq_blk)
    delta = delta.transpose(0, 2, 1).reshape(b * h, *rows)

    # grid: (batch * key/value heads, query heads of a group); the
    # key/value blocks stay put while the group's query heads pass
    if plan.rotates:
        query = lambda i, m: (  # noqa: E731
            i // h_kv, 0, i % h_kv * group + m)
        shared = lambda i, m: (i // h_kv, 0, i % h_kv)  # noqa: E731
        heads = lambda n, t: (b, t, n * d)  # noqa: E731
    else:
        query = lambda i, m: (i * group + m, 0, 0)  # noqa: E731
        shared = lambda i, m: (i, 0, 0)  # noqa: E731
        heads = lambda n, t: (b * n, t, d)  # noqa: E731
    query_rows = lambda i, m: (i * group + m, 0, 0, 0)  # noqa: E731
    row_block = (1,) + rows
    in_specs = [
        pl.BlockSpec((1, tq, d), query, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, tk, d), shared, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, tk, d), shared, memory_space=pltpu.VMEM),
    ]
    scratch = [pltpu.VMEM((tk, d), jnp.float32),
               pltpu.VMEM((tk, d), jnp.float32)]
    if plan.rotates:
        operands.append(table)
        in_specs.append(_table_spec(table))
        scratch.append(pltpu.VMEM((tk, d), k.dtype))
    if plan.positions:
        operands += [q_pos.astype(jnp.int32).reshape(rows),
                     k_pos.astype(jnp.int32).reshape(tk, 1)]
        in_specs += [
            pl.BlockSpec(rows, lambda i, m: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tk, 1), lambda i, m: (0, 0),
                         memory_space=pltpu.VMEM),
        ]
    in_specs += [
        pl.BlockSpec((1, tq, d), query, memory_space=pltpu.VMEM),
        pl.BlockSpec(row_block, query_rows, memory_space=pltpu.VMEM),
        pl.BlockSpec(row_block, query_rows, memory_space=pltpu.VMEM),
    ]
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, causal=causal,
                          plan=plan),
        grid=(b * h_kv, group),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, tq, d), query, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tk, d), shared, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tk, d), shared, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(heads(h, tq), q.dtype),
            jax.ShapeDtypeStruct(heads(h_kv, tk), k.dtype),
            jax.ShapeDtypeStruct(heads(h_kv, tk), v.dtype),
        ],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(d),
        interpret=interpret,
        name=name and name + "_bwd",
    )(*operands, gf, lse.reshape(b * h, *rows), delta)

    def unfold(x, t, heads):
        if plan.rotates:
            return x.reshape(b, t, heads, d)
        return x.reshape(b, heads, t, d).transpose(0, 2, 1, 3)

    return unfold(dq, tq, h), unfold(dk, tk, h_kv), unfold(dv, tk, h_kv)


# ---- the streamed kernels: K/V a key tile at a time from HBM ----
#
# A grid step is one VISIT, a (q block, key tile) pair the mask leaves,
# listed by ``_stream_visits`` and handed to the kernels as
# scalar-prefetched tables: the index maps read which block and tile a
# step fetches, so a tile the mask leaves out is neither computed nor
# fetched, and consecutive visits of one block or tile fetch it once.
# The running max, sum and accumulator live in VMEM scratch across a
# block's visits.  Scores are held transposed, (key tile, q block), as
# in the resident kernels; their products and float32 casts too.

#: a visit's flags: the first and last visit of its block (or tile),
#: whether its scores are masked one by one, and (the dK/dV pass alone)
#: a key tile no query sees, whose gradients are written as zeros
_FIRST, _LAST, _MASKED, _EMPTY = 1, 2, 4, 8


def _stream_tables(visits: list, key_tiles: int | None = None):
    """Three int32 tables over the visits, in the order a pass walks
    them: q block, key tile, flags.  ``key_tiles``: key tile by key tile
    over that many (the dK/dV pass), a tile that no query sees given one
    empty visit; None: q block by q block."""
    rows = []
    if key_tiles is not None:
        seen = {t for _, t, _ in visits}
        visits = sorted(visits + [(0, t, None) for t in range(key_tiles)
                                  if t not in seen],
                        key=lambda v: (v[1], v[0]))
    owner = 0 if key_tiles is None else 1   # a visit's q block, or tile
    for n, visit in enumerate(visits):
        j, t, masked = visit
        first = n == 0 or visits[n - 1][owner] != visit[owner]
        last = n == len(visits) - 1 or visits[n + 1][owner] != visit[owner]
        rows.append((j, t, first * _FIRST + last * _LAST
                     + (masked is True) * _MASKED
                     + (masked is None) * _EMPTY))
    return tuple(jnp.asarray([r[i] for r in rows], jnp.int32)
                 for i in range(3))


def _stream_mask(j, t, plan: TilePlan):
    """The (key tile, q block) mask of q block ``j`` against key tile
    ``t`` (traced indices): query - key = ``ahead - offset``."""
    shape = (plan.key_tile, plan.q_block)
    ahead = (jax.lax.broadcasted_iota(jnp.int32, shape, 1)
             - jax.lax.broadcasted_iota(jnp.int32, shape, 0))
    offset = t * plan.key_tile - j * plan.q_block
    seen = ahead >= offset
    if plan.window is not None:
        seen = jnp.logical_and(seen, ahead < offset + plan.window)
    return seen


def _on_visit(flag, visit):
    """``visit(masked)`` under the flag's mask bit: two bodies, each
    lowered once."""
    pl.when((flag & _MASKED) != 0)(lambda: visit(True))
    pl.when((flag & (_MASKED | _EMPTY)) == 0)(lambda: visit(False))


def _stream_kernel(q_of, k_of, flags, q_ref, k_ref, v_ref, o_ref, lse_ref,
                   m_s, l_s, acc_s, *, scale, plan):
    """One visit of the streamed forward: the online softmax of the
    resident kernel, its carry in scratch across a q block's visits."""
    s = pl.program_id(1)
    flag, j, t = flags[s], q_of[s], k_of[s]

    @pl.when((flag & _FIRST) != 0)
    def _():
        m_s[...] = jnp.full(m_s.shape, _MASK_NEG, jnp.float32)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    def visit(masked):
        sc = jax.lax.dot_general(
            k_ref[0], q_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (TILE, TQB)
        if masked:
            sc = jnp.where(_stream_mask(j, t, plan), sc, _MASK_NEG)
        m = m_s[...]
        m_new = jnp.maximum(m, jnp.max(sc, axis=0, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=0, keepdims=True)
        acc_s[...] = alpha * acc_s[...] + jax.lax.dot_general(
            v_ref[0], p.astype(v_ref.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # v^T p (D, TQB)
        m_s[...] = m_new

    _on_visit(flag, visit)

    @pl.when((flag & _LAST) != 0)
    def _():
        o_ref[0] = (acc_s[...] / l_s[...]).astype(o_ref.dtype)
        lse_ref[0] = m_s[...] + jnp.log(l_s[...])


def _stream_layout(b: int, h: int, h_kv: int, d: int):
    """How the streamed kernels reach a head: by index map in (B, T, H *
    D) where a head fills whole lanes, else folded (B * H, T, D).
    Returns ``(reshape, query, shared)``: ``query(row, block)`` and
    ``shared(row, tile)`` are the block indices of folded query row
    ``row = b * H + head`` and of the key/value head it reads."""
    group = h // h_kv
    if d % 128 == 0:
        return (lambda x: x.reshape(*x.shape[:2], -1),
                lambda r, j: (r // h, j, r % h),
                lambda r, t: (r // h, t, r % h // group))
    return (_fold, lambda r, j: (r, j, 0),
            lambda r, t: (r // group, t, 0))


def _stream_params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics)


@functools.partial(jax.jit, static_argnames=(
    "scale", "interpret", "name", "plan"))
def _stream_attention(q, k, v, *, scale, interpret, plan: TilePlan,
                      name: str | None = None):
    """The streamed forward -> (out (B, Tq, H, D), lse (B*H, 1, Tq)),
    as ``_pallas_attention`` returns them."""
    b, tq, h, d = q.shape
    tk, h_kv = k.shape[1:3]
    qb, kt = plan.q_block, plan.key_tile
    tables = _stream_tables(_stream_visits(plan, tq, tk))
    heads, query, shared = _stream_layout(b, h, h_kv, d)
    out, lse = pl.pallas_call(
        functools.partial(_stream_kernel, scale=scale, plan=plan),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b * h, len(tables[0])),
            in_specs=[
                pl.BlockSpec((1, qb, d),
                             lambda i, s, qo, ko, f: query(i, qo[s])),
                pl.BlockSpec((1, kt, d),
                             lambda i, s, qo, ko, f: shared(i, ko[s])),
                pl.BlockSpec((1, kt, d),
                             lambda i, s, qo, ko, f: shared(i, ko[s])),
            ],
            out_specs=[
                pl.BlockSpec((1, d, qb),
                             lambda i, s, qo, ko, f: (i, 0, qo[s])),
                pl.BlockSpec((1, 1, qb),
                             lambda i, s, qo, ko, f: (i, 0, qo[s])),
            ],
            scratch_shapes=[pltpu.VMEM((1, qb), jnp.float32),
                            pltpu.VMEM((1, qb), jnp.float32),
                            pltpu.VMEM((d, qb), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b * h, d, tq), q.dtype),
                   jax.ShapeDtypeStruct((b * h, 1, tq), jnp.float32)],
        compiler_params=_stream_params("parallel", "arbitrary"),
        interpret=interpret,
        name=name and name + "_fwd",
    )(*tables, heads(q), heads(k), heads(v))
    return out.reshape(b, h, d, tq).transpose(0, 3, 1, 2), lse


def _stream_scores(q_ref, k_ref, lse_ref, j, t, masked, scale, plan):
    """A visit's probabilities, (key tile, q block) float32, recomputed
    from (q, k, lse), and its q and k as float32."""
    q = q_ref[0].astype(jnp.float32)                  # (TQB, D)
    kmat = k_ref[0].astype(jnp.float32)               # (TILE, D)
    sc = jax.lax.dot_general(kmat, q, (((1,), (1,)), ((), ()))) * scale
    if masked:
        sc = jnp.where(_stream_mask(j, t, plan), sc, _MASK_NEG)
    return jnp.exp(sc - lse_ref[0]), q, kmat


def _stream_kv_kernel(q_of, k_of, flags, q_ref, k_ref, v_ref, g_ref,
                      lse_ref, delta_ref, dk_ref, dv_ref, dk_s, dv_s, *,
                      scale, plan):
    """One visit of the dK/dV pass: key tile by key tile, over the q
    blocks that see it and the group's query heads (the innermost grid
    axis), dk and dv summed in float32 scratch."""
    s, member = pl.program_id(1), pl.program_id(2)
    flag, j, t = flags[s], q_of[s], k_of[s]
    group = pl.num_programs(2)

    @pl.when(jnp.logical_and((flag & _FIRST) != 0, member == 0))
    def _():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    def visit(masked):
        p, q, _ = _stream_scores(q_ref, k_ref, lse_ref, j, t, masked,
                                 scale, plan)
        g = g_ref[0].astype(jnp.float32)
        dv_s[...] += jax.lax.dot_general(
            p, g, (((1,), (0,)), ((), ())))           # p^T g (TILE, D)
        dp = jax.lax.dot_general(
            v_ref[0].astype(jnp.float32), g, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta_ref[0])
        dk_s[...] += jax.lax.dot_general(
            ds, q, (((1,), (0,)), ((), ()))) * scale  # ds^T q

    _on_visit(flag, visit)

    @pl.when(jnp.logical_and((flag & _LAST) != 0, member == group - 1))
    def _():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _stream_q_kernel(q_of, k_of, flags, q_ref, k_ref, v_ref, g_ref,
                     lse_ref, delta_ref, dq_ref, dq_s, *, scale, plan):
    """One visit of the dQ pass: q block by q block over the key tiles
    it sees, dq summed in float32 scratch."""
    s = pl.program_id(1)
    flag, j, t = flags[s], q_of[s], k_of[s]

    @pl.when((flag & _FIRST) != 0)
    def _():
        dq_s[...] = jnp.zeros_like(dq_s)

    def visit(masked):
        p, _, kmat = _stream_scores(q_ref, k_ref, lse_ref, j, t, masked,
                                    scale, plan)
        g = g_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(
            v_ref[0].astype(jnp.float32), g, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta_ref[0])
        dq_s[...] += jax.lax.dot_general(
            ds, kmat, (((0,), (0,)), ((), ())))       # ds k (TQB, D)

    _on_visit(flag, visit)

    @pl.when((flag & _LAST) != 0)
    def _():
        dq_ref[0] = (dq_s[...] * scale).astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "interpret", "name", "plan"))
def _stream_attention_bwd(q, k, v, out, lse, g, *, scale, interpret,
                          plan: TilePlan, name: str | None = None):
    """The streamed backward: a dK/dV pass over the key tiles and a dQ
    pass over the q blocks, each walking only the visits the mask
    leaves; ``delta = g . out`` a row, as the resident backward takes
    it."""
    b, tq, h, d = q.shape
    tk, h_kv = k.shape[1:3]
    group = h // h_kv
    qb, kt = plan.q_block, plan.key_tile
    visits = _stream_visits(plan, tq, tk)
    heads, query, shared = _stream_layout(b, h, h_kv, d)
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    delta = delta.transpose(0, 2, 1).reshape(b * h, 1, tq)
    operands = (heads(q), heads(k), heads(v), heads(g), lse, delta)

    # the dK/dV pass: grid (batch * key/value head, visit, group member);
    # query row of (i, member) is i * group + member
    def q_row(i, m):
        return i * group + m

    kv_tables = _stream_tables(visits, tk // kt)
    kv_in = [
        pl.BlockSpec((1, qb, d), lambda i, s, m, qo, ko, f: query(
            q_row(i, m), qo[s])),
        pl.BlockSpec((1, kt, d), lambda i, s, m, qo, ko, f: shared(
            q_row(i, m), ko[s])),
        pl.BlockSpec((1, kt, d), lambda i, s, m, qo, ko, f: shared(
            q_row(i, m), ko[s])),
        pl.BlockSpec((1, qb, d), lambda i, s, m, qo, ko, f: query(
            q_row(i, m), qo[s])),
        pl.BlockSpec((1, 1, qb), lambda i, s, m, qo, ko, f: (
            q_row(i, m), 0, qo[s])),
        pl.BlockSpec((1, 1, qb), lambda i, s, m, qo, ko, f: (
            q_row(i, m), 0, qo[s])),
    ]
    kv_out = [pl.BlockSpec((1, kt, d), lambda i, s, m, qo, ko, f: shared(
        i * group, ko[s]))] * 2
    kv_shape = ((b, tk, h_kv * d) if d % 128 == 0 else (b * h_kv, tk, d))
    dk, dv = pl.pallas_call(
        functools.partial(_stream_kv_kernel, scale=scale, plan=plan),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b * h_kv, len(kv_tables[0]), group),
            in_specs=kv_in, out_specs=kv_out,
            scratch_shapes=[pltpu.VMEM((kt, d), jnp.float32),
                            pltpu.VMEM((kt, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(kv_shape, k.dtype),
                   jax.ShapeDtypeStruct(kv_shape, v.dtype)],
        compiler_params=_stream_params("parallel", "arbitrary",
                                       "arbitrary"),
        interpret=interpret,
        name=name and name + "_bwd_kv",
    )(*kv_tables, *operands)

    # the dQ pass: grid (batch * query head, visit), the forward's walk
    q_tables = _stream_tables(visits)
    q_in = [
        pl.BlockSpec((1, qb, d), lambda i, s, qo, ko, f: query(i, qo[s])),
        pl.BlockSpec((1, kt, d), lambda i, s, qo, ko, f: shared(i, ko[s])),
        pl.BlockSpec((1, kt, d), lambda i, s, qo, ko, f: shared(i, ko[s])),
        pl.BlockSpec((1, qb, d), lambda i, s, qo, ko, f: query(i, qo[s])),
        pl.BlockSpec((1, 1, qb), lambda i, s, qo, ko, f: (i, 0, qo[s])),
        pl.BlockSpec((1, 1, qb), lambda i, s, qo, ko, f: (i, 0, qo[s])),
    ]
    dq = pl.pallas_call(
        functools.partial(_stream_q_kernel, scale=scale, plan=plan),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b * h, len(q_tables[0])),
            in_specs=q_in,
            out_specs=pl.BlockSpec((1, qb, d), lambda i, s, qo, ko, f:
                                   query(i, qo[s])),
            scratch_shapes=[pltpu.VMEM((qb, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(heads(q).shape, q.dtype),
        compiler_params=_stream_params("parallel", "arbitrary"),
        interpret=interpret,
        name=name and name + "_bwd_q",
    )(*q_tables, *operands)

    def unfold(x, t, n):
        if d % 128 == 0:
            return x.reshape(b, t, n, d)
        return x.reshape(b, n, t, d).transpose(0, 2, 1, 3)

    return unfold(dq, tq, h), unfold(dk, tk, h_kv), unfold(dv, tk, h_kv)


def _xla_bwd(q, k, v, q_pos, k_pos, scale, causal, g, window=None):
    """Composed-XLA VJP (recompute p from inputs): dv = p^T g;
    ds = p * (dp - rowsum(dp*p)), dp = g v^T; dq = ds k * scale;
    dk = ds^T q * scale.  Fallback when the Pallas bwd's VMEM/blocking
    premises don't hold."""
    group, kv_shape = q.shape[2] // k.shape[2], k.shape
    k, v = _repeat_kv(q, k, v)
    s = block_scores(q, k, scale)
    if causal:
        s = jnp.where(causal_mask(q_pos, k_pos, window)[None, None], s,
                      _MASK_NEG)
    p = jax.nn.softmax(s, axis=-1)                       # fp32
    g32 = g.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bqhd->bkhd", p, g32)
    dp = jnp.einsum("bqhd,bkhd->bhqk", g32, v.astype(jnp.float32))
    ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
    dq = (jnp.einsum("bhqk,bkhd->bqhd", ds, k.astype(jnp.float32))
          * scale).astype(q.dtype)
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, q.astype(jnp.float32)) * scale

    def shared(x):     # a key/value head's gradient sums over its group
        b, tk, h_kv, d = kv_shape
        return x.reshape(b, tk, h_kv, group, d).sum(3)

    return dq, shared(dk).astype(k.dtype), shared(dv).astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _fused(q, k, v, q_pos, k_pos, table, scale, causal, interpret, name,
           plan):
    return _fused_fwd(q, k, v, q_pos, k_pos, table, scale, causal,
                      interpret, name, plan)[0]


def _fused_fwd(q, k, v, q_pos, k_pos, table, scale, causal, interpret,
               name, plan):
    if plan.stream:
        out, lse = _stream_attention(q, k, v, scale=scale,
                                     interpret=interpret, name=name,
                                     plan=plan)
    else:
        out, lse = _pallas_attention(q, k, v, q_pos, k_pos, table,
                                     scale=scale, causal=causal,
                                     interpret=interpret, name=name,
                                     plan=plan)
    return out, (q, k, v, q_pos, k_pos, table, out, lse)


def _fused_bwd(scale, causal, interpret, name, plan, res, g):
    q, k, v, q_pos, k_pos, table, out, lse = res
    tq, tk, d = q.shape[1], k.shape[1], q.shape[-1]
    # the fused bwd loops exact q-blocks; ragged tails and oversize VMEM
    # needs that the plan could not stream take the composed-XLA path
    fused = not plan.stream and tq % plan.q_block == 0 and _fits_vmem_bwd(
        tq, tk, d, q.dtype, plan.q_block, plan.positions, plan.rotates)
    _log_choice("attention bwd", q.shape + k.shape[1:3], str(q.dtype),
                "pallas" if fused or plan.stream else "xla",
                f"fits, {plan}" if fused else f"streams, {plan}"
                if plan.stream else "ragged q-tail or over the VMEM budget")
    if fused:
        dq, dk, dv = _pallas_attention_bwd(
            q, k, v, q_pos, k_pos, out, lse, g, table, scale=scale,
            causal=causal, interpret=interpret, name=name, plan=plan)
    elif plan.stream:
        dq, dk, dv = _stream_attention_bwd(
            q, k, v, out, lse, g, scale=scale, interpret=interpret,
            name=name, plan=plan)
    else:
        if not plan.positions:
            q_pos, k_pos = jnp.arange(tq), jnp.arange(tk)
        unrotate = None
        if table is not None:
            (q, k), unrotate = jax.vjp(
                lambda q, k: (rotary_xla(q, table), rotary_xla(k, table)),
                q, k)
        dq, dk, dv = _xla_bwd(q, k, v, q_pos, k_pos, scale, causal, g,
                              plan.window)
        if unrotate:
            dq, dk = unrotate((dq, dk))
    return dq, dk, dv, None, None, None


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_attention(q, k, v, q_pos=None, k_pos=None,
                    causal: bool = False, scale: float | None = None,
                    impl: str | None = None, name: str | None = None,
                    rotary=None, window: int | None = None):
    """Softmax attention, fused on TPU.

    q: (B, Tq, H, D); k/v: (B, Tk, Hkv, D) with H a multiple of Hkv
    (query head h reads key/value head h // (H / Hkv)); optional global
    positions (Tq,)/(Tk,) for the causal mask (default: local aranges).
    ``rotary``: a ``rotary_table`` over the rows of q AND k (so Tq ==
    Tk; (T, D) float32), by which q and k are rotated before the score
    product: inside the kernels where a head fills whole lanes, by
    ``rotary_xla`` elsewhere, the same numbers either way.
    ``window``: a causal sliding window of W keys, query i seeing keys
    ``(i - W, i]`` (its own included): the streamed kernels, which
    neither compute nor fetch a tile outside it.  ``name`` labels the
    kernels in a trace.  Returns (B, Tq, H, D) in q.dtype.
    """
    if q.shape[2] % k.shape[2] or k.shape[2] != v.shape[2]:
        raise ValueError(f"{q.shape[2]} query heads over {k.shape[2]} key "
                         f"and {v.shape[2]} value heads: the query count "
                         "must be a multiple of one shared count")
    if rotary is not None and not (
            rotary.shape == q.shape[1::2] == k.shape[1::2]):
        raise ValueError(
            f"a rotary table of {rotary.shape} for q {q.shape} and k "
            f"{k.shape}: one row a position of q AND k, (T, D)")
    if window is not None and not (causal and window >= 1
                                   and q.shape[1] == k.shape[1]):
        raise ValueError(
            f"a window of {window} keys needs a causal mask and q and k "
            f"of one length (q {q.shape}, k {k.shape})")
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    # tiles above the diagonal are skipped only where the kernel KNOWS
    # the positions: the defaults.  Explicit (traced) positions visit
    # every tile and mask it score by score, exactly as before
    plan = tile_plan(q.shape[1], k.shape[1], q.shape[-1], q.dtype, causal,
                     default_positions=q_pos is None and k_pos is None,
                     rotary=rotary is not None, window=window)
    if q_pos is None:
        q_pos = jnp.arange(q.shape[1])
    if k_pos is None:
        k_pos = jnp.arange(k.shape[1])
    resolved = _resolve_impl(impl, q, k, plan)
    if rotary is not None and (resolved == "xla" or not plan.rotates):
        q, k, rotary = rotary_xla(q, rotary), rotary_xla(k, rotary), None
    if resolved == "xla":
        return _xla_attention(q, k, v, q_pos, k_pos, scale, causal, window)
    if not plan.positions:
        q_pos = k_pos = None
    return _fused(q, k, v, q_pos, k_pos, rotary, scale, causal,
                  pallas_mode.interpret(), name, plan)
