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
  leaves it as (D, q block), turned by XLA beside the head fold (turned
  in the kernel it cost the forward 13%, 40% at one tile).
* K/V for one (batch, head) must fit VMEM (checked; oversize shapes
  fall back to the XLA path) — local shard lengths up to a few
  thousand, which is the regime this framework runs attention at:
  GLOBAL long context is the ring/Ulysses layer's job
  (parallel/sequence.py), and what each device sees locally is exactly
  this kernel's shape.
* Backward is ALSO fused (flash-style): the fwd emits the per-row
  logsumexp, and the bwd kernel recomputes p from (q, k, lse) tile by
  tile, accumulating dq in the walk's carry and dk/dv in fp32 VMEM
  scratch — the (Tq, Tk) matrix never exists in either direction.  A
  row's sum of dp * p over all its keys, which no tile sees, is
  g . out (so ``out`` is a residual; the next layer's matmul keeps it
  anyway).  Its products take float32 operands (the next kernel
  lever, ROADMAP A3).  Ragged q-blocks or oversize shapes fall back
  to the composed-XLA VJP.
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
  (fits, q block 512, key tile 512, 3 of 4 tiles)``.
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
* ``name=`` labels the two ``pallas_call``s (``<name>_fwd`` /
  ``<name>_bwd``) so a trace reducer can tell one model's attention
  from another custom call; None keeps Pallas's default.
* On-chip status (TPU v5 lite, JAX 0.9.0 / libtpu 0.0.34, PR 29): fwd
  and the fused bwd compile and match the XLA form at (8, 1024, 12,
  64) and, with 8 query over 2 key/value heads of 128, at (4, 2048,
  8|2, 128), bf16 causal, 3 of 4 and 10 of 16 tiles of 512 x 512
  (chip_smoke.py's two checks); the three LM cells' reference checks
  run both passes against float32 at (8, 1024, 16, 64), (64, 128, 16,
  64) and (4, 2048, 8|2, 128).  Explicit positions, ``causal=False``
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
#: the largest q block and key tile a plan takes.  Graded on the chip
#: (PERF.md §6, PR 29; fwd+bwd of one layer): at (8, 1024, 16, 64)
#: 512 x 512 reads 1.660 ms against 2.311 (256 x 256), 2.044
#: (256 x 512), 1.916 (512 x 256) and 1.821 (1024 x 1024); at
#: (4, 2048, 8|2, 128) 1.320 against 2.051 (256 x 256) and 1.443
#: (1024 x 1024).
_Q_BLOCK = 512


def block_scores(q, k, scale):
    """q (B,Tq,H,D) x k (B,Tk,H,D) -> (B,H,Tq,Tk); fp32 accumulation.
    Shared with parallel/sequence.py's ring/oracle forms."""
    return jnp.einsum("bqhd,bkhd->bhqk", q, k,
                      preferred_element_type=jnp.float32) * scale


def causal_mask(q_pos, k_pos):
    return q_pos[:, None] >= k_pos[None, :]          # (Tq, Tk)


class TilePlan(NamedTuple):
    """How both passes tile one (Tq, Tk) score square: the q block, the
    key tile, whether tiles above the diagonal are left out (``skip``:
    a causal mask over the default positions), and the mechanism's
    counter, ``visited`` of ``total`` tiles."""

    q_block: int
    key_tile: int
    skip: bool
    visited: int
    total: int

    def __str__(self):
        return (f"q block {self.q_block}, key tile {self.key_tile}, "
                f"{self.visited} of {self.total} tiles")


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
              default_positions: bool = True) -> TilePlan:
    """The plan of a shape: a pure function of shape, dtype, mask and
    the module's configured sizes, which the kernels, the log line,
    the tests and PERF.md all read."""
    q_block, key_tile = _q_block(tq, tk, d, dtype), _key_tile(tk)
    skip = causal and default_positions
    n_blocks, n_tiles = pl.cdiv(tq, q_block), tk // key_tile
    visited = sum(_walk_bounds(j, q_block, key_tile, n_tiles, causal,
                               skip)[1] for j in range(n_blocks))
    return TilePlan(q_block, key_tile, skip, visited, n_blocks * n_tiles)


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


def _kernel(q_ref, k_ref, v_ref, qpos_ref, kpos_ref, o_ref, lse_ref, *,
            scale, causal, plan):
    """One q block of one (batch, head) against that head's resident
    K/V, walked in key tiles with an online softmax.  Scores are held
    TRANSPOSED, (key tile, q block): a row of the softmax runs down the
    sublanes, so its max, sum and rescale are lane-dense (1, q block)
    vectors (as (q block, 1) columns they cost a tile's worth of work a
    visit), and they and the fp32 (D, q block) accumulator are the
    loop's carry."""
    d, tq_blk = o_ref.shape[1:]
    tile = plan.key_tile
    q = q_ref[0]                                      # (TQB, D)

    def body(masked):
        def step(t, carry):
            m, l, acc = carry
            ks = _tile(t, tile, k_ref.shape[1])
            s = jax.lax.dot_general(
                k_ref[0, ks], q, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (TILE, TQB)
            if masked:
                mask = qpos_ref[:] >= kpos_ref[ks]    # (1,TQB)>=(TILE,1)
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
        pl.program_id(1), body,
        (jnp.full((1, tq_blk), _MASK_NEG, jnp.float32),
         jnp.zeros((1, tq_blk), jnp.float32),
         jnp.zeros((d, tq_blk), jnp.float32)),
        plan, k_ref.shape[1] // tile, causal)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)                       # (1, TQB) fp32


def _compiler_params():
    """The compiler may take twice what the estimates admit: its own
    count of the fused backward grows with batch x heads in a way they
    do not model ((2, 4096, 16, 64) bf16 under 16 MiB, (8, 4096, 16,
    64) 18.0, (16, 3584, 16, 64) 17.5), and a v5e core has 128 MiB."""
    return pltpu.CompilerParams(vmem_limit_bytes=2 * _VMEM_BUDGET_BYTES)


def _fold(x):                                # (B,T,H,D) -> (B*H,T,D)
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "interpret", "name", "plan"))
def _pallas_attention(q, k, v, q_pos, k_pos, *, scale, causal, interpret,
                      plan: TilePlan, name: str | None = None):
    """The forward pass -> (out (B,Tq,H,D), lse (B*H,1,Tq) fp32: a
    row a head, which HBM holds as it is; a (Tq, 1) column there pads
    every row to 128 lanes, 64 MB a layer at (8, 1024, 16, 64)).
    Jitted at module level with the plan static: every call site of a
    shape shares one traced jaxpr and one lowered function, and an
    eager caller compiles it once a process."""
    b, tq, h, d = q.shape
    tk, h_kv = k.shape[1:3]
    bh = b * h
    group = h // h_kv          # query heads per key/value head
    tq_blk = plan.q_block

    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    qp = q_pos.astype(jnp.int32).reshape(1, tq)
    kp = k_pos.astype(jnp.int32).reshape(tk, 1)

    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             plan=plan)
    # folded query row b*Hq + h reads folded key/value row
    # b*Hkv + h // group, which is (b*Hq + h) // group
    shared = lambda i, j: (i // group, 0, 0)  # noqa: E731
    out, lse = pl.pallas_call(
        kern,
        grid=(bh, pl.cdiv(tq, tq_blk)),
        in_specs=[
            pl.BlockSpec((1, tq_blk, d), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tk, d), shared, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tk, d), shared, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tq_blk), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tk, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
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
        compiler_params=_compiler_params(),
        interpret=interpret,
        name=name and name + "_fwd",
    )(qf, kf, vf, qp, kp)
    return out.reshape(b, h, d, tq).transpose(0, 3, 1, 2), lse


def _repeat_kv(q, k, v):
    """k/v with q's head count (the XLA forms only)."""
    group = q.shape[2] // k.shape[2]
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)


def _xla_attention(q, k, v, q_pos, k_pos, scale, causal):
    """The composed-XLA fallback (same primitives as the oracle)."""
    k, v = _repeat_kv(q, k, v)
    s = block_scores(q, k, scale)
    if causal:
        s = jnp.where(causal_mask(q_pos, k_pos)[None, None], s, _MASK_NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def _fits_vmem(tk, d, dtype, tq_blk: int) -> bool:
    """The forward holds K and V whole, the key positions as a column
    (512 bytes a key in VMEM), one q block and its output, each TWICE
    (the pipeline's two buffers), and two (key tile, q block) fp32
    blocks of scores beside the fp32 accumulator."""
    itemsize, tile = jnp.dtype(dtype).itemsize, _key_tile(tk)
    need = (2 * (2 * tk * d * itemsize         # K, V
                 + tk * 512                    # key positions
                 + 2 * tq_blk * d * itemsize)  # Q block, out
            + 2 * tile * tq_blk * 4            # fp32 scores, exp
            + 2 * tq_blk * d * 4)              # accumulator, v p
    return need <= _VMEM_BUDGET_BYTES


def _fits_vmem_bwd(tq, tk, d, dtype, tq_blk: int) -> bool:
    """The fused bwd holds whole Q/G/dq plus K/V/dk/dv per (b*h) and
    the key positions as a column, each TWICE (the pipeline's two
    buffers), fp32 dk/dv scratch and, per (key tile, q block), fp32
    casts of the operands and two score blocks.  At few heads this is
    the v5e compiler's own count (under its default 16 MiB it refused
    (2, 5120, 16, 64) bf16 at 17.50 MiB; this says 17.6); with more
    batch x heads the compiler asks more, which ``_compiler_params``
    leaves room for, so every shape admitted here compiles
    (tests/test_attention_tiles.py)."""
    itemsize, tile = jnp.dtype(dtype).itemsize, _key_tile(tk)
    need = (2 * (3 * tq * d * itemsize         # Q, G, dq
                 + 4 * tk * d * itemsize       # K, V, dk, dv
                 + tk * 512)                   # key positions
            + 2 * tk * d * 4                   # fp32 dk/dv scratch
            + 3 * tq_blk * d * 4               # q/g casts, dq carry
            + 2 * tile * d * 4                 # k/v tile casts
            + 2 * tile * tq_blk * 4)           # s/p and dp/ds blocks
    return need <= _VMEM_BUDGET_BYTES


def _q_block(tq, tk, d, dtype) -> int:
    """The q block of a shape: the configured block, else its halves
    down to 128, the largest that divides ``tq`` and keeps the forward
    AND the fused backward inside the VMEM budget.  Where none does,
    the configured block (one block when ``tq`` is shorter): the
    callers' own checks then route what does not fit or divide."""
    top = min(_Q_BLOCK, tq)
    blk = top
    while blk >= 128 and blk % 8 == 0:
        if (tq % blk == 0 and _fits_vmem(tk, d, dtype, blk)
                and _fits_vmem_bwd(tq, tk, d, dtype, blk)):
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
    if impl != "auto":
        return impl
    b, tq, h, d = q.shape
    plan = plan or tile_plan(tq, k.shape[1], d, q.dtype, causal=True)
    if jax.default_backend() != "tpu":
        choice, why = "xla", "not a TPU"
    elif not _fits_vmem(k.shape[1], d, q.dtype, plan.q_block):
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


def _bwd_kernel(q_ref, k_ref, v_ref, qpos_ref, kpos_ref, g_ref, lse_ref,
                delta_ref, dq_ref, dk_ref, dv_ref, dk_s, dv_s, *, scale,
                causal, plan):
    """Flash-style backward for one (batch * key/value head, query head
    of its group): loop q-blocks and, inside, the key tiles each one
    visits; recompute p from (q, k, lse) — no stored score matrix
    anywhere, blocks held transposed as in the forward — accumulating
    dq in the inner loop's carry and dk/dv in fp32 VMEM scratch over
    the q-blocks AND over the group's query heads (the innermost grid
    axis; one head when q and k/v have the same count)."""
    member = pl.program_id(1)
    tq_blk, tile = plan.q_block, plan.key_tile
    tq, d = q_ref.shape[1:]
    tk = k_ref.shape[1]

    @pl.when(member == 0)
    def _():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    def q_block(i, _):
        sl = _tile(i, tq_blk, tq)
        q = q_ref[0, sl].astype(jnp.float32)          # (TQB, D)
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
                kmat = k_ref[0, ks].astype(jnp.float32)   # (TILE, D)
                vmat = v_ref[0, ks].astype(jnp.float32)
                s = jax.lax.dot_general(
                    kmat, q, (((1,), (1,)), ((), ()))) * scale
                if masked:
                    mask = qpos_ref[i] >= kpos_ref[ks]  # (1,TQB)>=(TILE,1)
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
        dq_ref[0, sl] = (dq * scale).astype(dq_ref.dtype)
        return 0

    jax.lax.fori_loop(0, tq // tq_blk, q_block, 0)

    @pl.when(member == pl.num_programs(1) - 1)
    def _():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "interpret", "name", "plan"))
def _pallas_attention_bwd(q, k, v, q_pos, k_pos, out, lse, g, *, scale,
                          causal, interpret, plan: TilePlan,
                          name: str | None = None):
    """The fused backward, jitted once a shape like the forward.
    ``delta``, a row's sum of dp * p over ALL its keys, which no one
    tile sees, is g . out, taken here in fp32."""
    b, tq, h, d = q.shape
    tk, h_kv = k.shape[1:3]
    group = h // h_kv
    tq_blk = plan.q_block

    qf, kf, vf, gf = _fold(q), _fold(k), _fold(v), _fold(g)
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    # what a q block reads whole lies along the lanes, one row a block
    # (the kernel picks the row by its leading index)
    rows = (tq // tq_blk, 1, tq_blk)
    delta = delta.transpose(0, 2, 1).reshape(b * h, *rows)
    qp = q_pos.astype(jnp.int32).reshape(rows)
    kp = k_pos.astype(jnp.int32).reshape(tk, 1)

    # grid: (batch * key/value heads, query heads of a group); the
    # key/value blocks stay put while the group's query heads pass
    query = lambda i, m: (i * group + m, 0, 0)  # noqa: E731
    query_rows = lambda i, m: (i * group + m, 0, 0, 0)  # noqa: E731
    shared = lambda i, m: (i, 0, 0)  # noqa: E731
    row_block = (1,) + rows
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, causal=causal,
                          plan=plan),
        grid=(b * h_kv, group),
        in_specs=[
            pl.BlockSpec((1, tq, d), query, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tk, d), shared, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tk, d), shared, memory_space=pltpu.VMEM),
            pl.BlockSpec(rows, lambda i, m: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tk, 1), lambda i, m: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tq, d), query, memory_space=pltpu.VMEM),
            pl.BlockSpec(row_block, query_rows, memory_space=pltpu.VMEM),
            pl.BlockSpec(row_block, query_rows, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, tq, d), query, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tk, d), shared, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tk, d), shared, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h_kv, tk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h_kv, tk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((tk, d), jnp.float32),
            pltpu.VMEM((tk, d), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name=name and name + "_bwd",
    )(qf, kf, vf, qp, kp, gf, lse.reshape(b * h, *rows), delta)

    def unfold(x, t, heads):
        return x.reshape(b, heads, t, d).transpose(0, 2, 1, 3)

    return unfold(dq, tq, h), unfold(dk, tk, h_kv), unfold(dv, tk, h_kv)


def _xla_bwd(q, k, v, q_pos, k_pos, scale, causal, g):
    """Composed-XLA VJP (recompute p from inputs): dv = p^T g;
    ds = p * (dp - rowsum(dp*p)), dp = g v^T; dq = ds k * scale;
    dk = ds^T q * scale.  Fallback when the Pallas bwd's VMEM/blocking
    premises don't hold."""
    group, kv_shape = q.shape[2] // k.shape[2], k.shape
    k, v = _repeat_kv(q, k, v)
    s = block_scores(q, k, scale)
    if causal:
        s = jnp.where(causal_mask(q_pos, k_pos)[None, None], s, _MASK_NEG)
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _fused(q, k, v, q_pos, k_pos, scale, causal, interpret, name, plan):
    return _fused_fwd(q, k, v, q_pos, k_pos, scale, causal, interpret,
                      name, plan)[0]


def _fused_fwd(q, k, v, q_pos, k_pos, scale, causal, interpret, name,
               plan):
    out, lse = _pallas_attention(q, k, v, q_pos, k_pos, scale=scale,
                                 causal=causal, interpret=interpret,
                                 name=name, plan=plan)
    return out, (q, k, v, q_pos, k_pos, out, lse)


def _fused_bwd(scale, causal, interpret, name, plan, res, g):
    q, k, v, q_pos, k_pos, out, lse = res
    tq, tk, d = q.shape[1], k.shape[1], q.shape[-1]
    # the fused bwd loops exact q-blocks; ragged tails or oversize
    # VMEM needs take the composed-XLA path instead
    fused = tq % plan.q_block == 0 and _fits_vmem_bwd(
        tq, tk, d, q.dtype, plan.q_block)
    _log_choice("attention bwd", q.shape + k.shape[1:3], str(q.dtype),
                "pallas" if fused else "xla",
                f"fits, {plan}" if fused else "ragged q-tail "
                "or over the VMEM budget")
    if fused:
        dq, dk, dv = _pallas_attention_bwd(
            q, k, v, q_pos, k_pos, out, lse, g, scale=scale,
            causal=causal, interpret=interpret, name=name, plan=plan)
    else:
        dq, dk, dv = _xla_bwd(q, k, v, q_pos, k_pos, scale, causal, g)
    return dq, dk, dv, None, None


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_attention(q, k, v, q_pos=None, k_pos=None,
                    causal: bool = False, scale: float | None = None,
                    impl: str | None = None, name: str | None = None):
    """Softmax attention, fused on TPU.

    q: (B, Tq, H, D); k/v: (B, Tk, Hkv, D) with H a multiple of Hkv
    (query head h reads key/value head h // (H / Hkv)); optional global
    positions (Tq,)/(Tk,) for the causal mask (default: local aranges).
    ``name`` labels the kernels in a trace.  Returns (B, Tq, H, D) in
    q.dtype.
    """
    if q.shape[2] % k.shape[2] or k.shape[2] != v.shape[2]:
        raise ValueError(f"{q.shape[2]} query heads over {k.shape[2]} key "
                         f"and {v.shape[2]} value heads: the query count "
                         "must be a multiple of one shared count")
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    # tiles above the diagonal are skipped only where the kernel KNOWS
    # the positions: the defaults.  Explicit (traced) positions visit
    # every tile and mask it score by score, exactly as before
    plan = tile_plan(q.shape[1], k.shape[1], q.shape[-1], q.dtype, causal,
                     default_positions=q_pos is None and k_pos is None)
    if q_pos is None:
        q_pos = jnp.arange(q.shape[1])
    if k_pos is None:
        k_pos = jnp.arange(k.shape[1])
    resolved = _resolve_impl(impl, q, k, plan)
    if resolved == "xla":
        return _xla_attention(q, k, v, q_pos, k_pos, scale, causal)
    return _fused(q, k, v, q_pos, k_pos, scale, causal,
                  pallas_mode.interpret(), name, plan)
