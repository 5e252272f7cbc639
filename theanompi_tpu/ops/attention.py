"""Fused softmax attention as a Pallas TPU kernel.

The transformer family's hot op (beyond-parity surface — the reference
predates attention; its analogue is routing conv/LRN to cuDNN,
SURVEY.md §2.12).  The kernel computes one Q block's full attention in
VMEM — scores, causal/position mask, row softmax, and the PV matmul —
in a single pass per (batch*head, q-block) grid cell, so the (Tq, Tk)
score matrix never round-trips HBM the way the composed XLA form's
does.  Softmax statistics are computed in fp32 regardless of the
compute dtype.

Scope notes:

* K/V for one (batch, head) must fit VMEM alongside one fp32 score
  block (checked; oversize shapes fall back to the XLA path) — local
  shard lengths up to a few thousand, which is the regime this
  framework runs attention at: GLOBAL long context is the ring/
  Ulysses layer's job (parallel/sequence.py), and what each device
  sees locally is exactly this kernel's shape.
* Backward is ALSO fused (flash-style): the fwd emits the per-row
  logsumexp, and the bwd kernel recomputes p from (q, k, lse) block
  by block, accumulating dk/dv in fp32 VMEM scratch — the (Tq, Tk)
  matrix never exists outside VMEM in either direction.  Ragged
  q-blocks or oversize shapes fall back to the composed-XLA VJP.
* ``impl='auto'``: Pallas on TPU, XLA elsewhere; force with
  ``THEANOMPI_TPU_ATTN_IMPL=pallas|xla`` (interpret mode, CPU platform
  only, makes the Pallas path unit-testable — tests/test_ops.py).
  Every choice made from a shape is logged once per shape at trace
  time (logger ``theanompi_tpu.ops.attention``; a warning when a TPU
  run takes the XLA form), so no path is taken quietly.
* Grouped-query heads: k/v may carry FEWER heads than q (Hq a
  multiple of Hkv; query head h reads key/value head h // (Hq/Hkv)).
  The kernels pick the shared head by index map — k/v are never
  repeated in HBM; the fused bwd walks the group's query heads in
  its innermost grid axis and accumulates their dk/dv in the same
  VMEM scratch.  The XLA fallback repeats k/v (it is the fallback).
* The q block is chosen per shape (``_q_block``): the configured
  ``THEANOMPI_TPU_ATTN_QBLOCK`` (256), else its halves down to 128,
  the largest that divides Tq and keeps BOTH passes inside the VMEM
  budget.  (8, 1024, 16, 64) bf16 stays on 256; (4, 2048, 8|2, 128)
  bf16 takes 128, where the fused bwd needs 10.6 MiB (13.75 at 256,
  over the 12 MiB budget).
* ``name=`` labels the two ``pallas_call``s (``<name>_fwd`` /
  ``<name>_bwd``) so a trace reducer can tell one model's attention
  from another custom call; None keeps Pallas's default.
* On-chip status (TPU v5 lite, JAX 0.9.0 / libtpu 0.0.34): fwd and
  the fused bwd compile and match the XLA form at (8, 1024, 12, 64)
  bf16 causal (PR 21; chip_smoke.py repeats that check) and, since
  PR 27, at head size 128 with 8 query over 2 key/value heads,
  (4, 2048, 8|2, 128) bf16 causal at q block 128 (the zaya1_8b
  cell's reference check runs both passes against float32).  The
  ragged-q-tail path has not been compiled.
"""

from __future__ import annotations

import functools
import logging
import os

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
#: per-(batch*head) VMEM budget for K + V + one fp32 score block.
#: Env-tunable (THEANOMPI_TPU_ATTN_VMEM_MB / _ATTN_QBLOCK) so on-chip
#: block-size sweeps need no code edits.
_VMEM_BUDGET_BYTES = int(float(os.environ.get(
    "THEANOMPI_TPU_ATTN_VMEM_MB", "12")) * 1024 * 1024)
if _VMEM_BUDGET_BYTES <= 0:
    raise ValueError("THEANOMPI_TPU_ATTN_VMEM_MB must be positive — 0 "
                     "would silently route every shape to the XLA path")
_Q_BLOCK = int(os.environ.get("THEANOMPI_TPU_ATTN_QBLOCK", "256"))
if _Q_BLOCK < 8 or _Q_BLOCK % 8:
    raise ValueError(f"THEANOMPI_TPU_ATTN_QBLOCK must be a positive "
                     f"multiple of 8 (sublane tiling), got {_Q_BLOCK}")


def block_scores(q, k, scale):
    """q (B,Tq,H,D) x k (B,Tk,H,D) -> (B,H,Tq,Tk); fp32 accumulation.
    Shared with parallel/sequence.py's ring/oracle forms."""
    return jnp.einsum("bqhd,bkhd->bhqk", q, k,
                      preferred_element_type=jnp.float32) * scale


def causal_mask(q_pos, k_pos):
    return q_pos[:, None] >= k_pos[None, :]          # (Tq, Tk)


def _kernel(q_ref, k_ref, v_ref, qpos_ref, kpos_ref, o_ref, lse_ref, *,
            scale, causal):
    q = q_ref[0]                                      # (TQ, D)
    k = k_ref[0]                                      # (TK, D)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (TQ, TK)
    if causal:
        mask = qpos_ref[:] >= kpos_ref[:]             # (TQ,1)>=(1,TK)
        s = jnp.where(mask, s, _MASK_NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[0] = (o / l).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)                       # (TQ, 1) fp32


def _fold(x):                                # (B,T,H,D) -> (B*H,T,D)
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _pallas_attention(q, k, v, q_pos, k_pos, scale, causal,
                      interpret: bool, name: str | None = None):
    b, tq, h, d = q.shape
    tk = k.shape[1]
    bh = b * h
    group = h // k.shape[2]    # query heads per key/value head

    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    qp = q_pos.astype(jnp.int32).reshape(tq, 1)
    kp = k_pos.astype(jnp.int32).reshape(1, tk)

    tq_blk = _q_block(tq, tk, d, q.dtype)
    grid = (bh, pl.cdiv(tq, tq_blk))
    kern = functools.partial(_kernel, scale=scale, causal=causal)
    # folded query row b*Hq + h reads folded key/value row
    # b*Hkv + h // group, which is (b*Hq + h) // group
    shared = lambda i, j: (i // group, 0, 0)  # noqa: E731
    out, lse = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tq_blk, d), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tk, d), shared, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tk, d), shared, memory_space=pltpu.VMEM),
            pl.BlockSpec((tq_blk, 1), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tk), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, tq_blk, d), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tq_blk, 1), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32),
        ],
        interpret=interpret,
        name=name and name + "_fwd",
    )(qf, kf, vf, qp, kp)
    return (out.reshape(b, h, tq, d).transpose(0, 2, 1, 3),
            lse.reshape(bh, tq, 1))


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
    itemsize = jnp.dtype(dtype).itemsize
    need = (2 * tk * d * itemsize          # K + V
            + tq_blk * d * itemsize        # Q block
            + 2 * tq_blk * tk * 4)         # fp32 scores + exp
    return need <= _VMEM_BUDGET_BYTES


def _fits_vmem_bwd(tq, tk, d, dtype, tq_blk: int) -> bool:
    """The fused bwd holds whole Q/G/dq plus K/V/dk/dv per (b*h),
    fp32 copies of K/V (kmat/vmat), fp32 dk/dv scratch, and per-block
    fp32 casts of q/g."""
    itemsize = jnp.dtype(dtype).itemsize
    need = (3 * tq * d * itemsize          # Q, G, dq
            + 4 * tk * d * itemsize        # K, V, dk, dv
            + 2 * tk * d * 4               # kmat/vmat fp32 copies
            + 2 * tk * d * 4               # fp32 dk/dv scratch
            + 2 * tq_blk * d * 4           # q/g block fp32 casts
            + 3 * tq_blk * tk * 4)         # s/p + dp/ds blocks
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


def _resolve_impl(impl: str | None, q, k) -> str:
    impl = impl or os.environ.get("THEANOMPI_TPU_ATTN_IMPL", "auto")
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown attention impl {impl!r}")
    if impl != "auto":
        return impl
    b, tq, h, d = q.shape
    tq_blk = _q_block(tq, k.shape[1], d, q.dtype)
    if jax.default_backend() != "tpu":
        choice, why = "xla", "not a TPU"
    elif not _fits_vmem(k.shape[1], d, q.dtype, tq_blk):
        choice, why = "xla", "K/V + score block exceed the VMEM budget"
    elif tq % tq_blk != 0:
        # ragged q-tails rely on Pallas out-of-range block padding,
        # which has only ever run interpreted; impl='pallas' still
        # forces the kernel (how tests cover it)
        choice, why = "xla", f"ragged q-tail (Tq % {_Q_BLOCK} != 0)"
    else:
        choice, why = "pallas", f"fits, q block {tq_blk}"
    _log_choice("attention fwd", q.shape + k.shape[1:3], str(q.dtype),
                choice, why)
    return choice


def _bwd_kernel(q_ref, k_ref, v_ref, qpos_ref, kpos_ref, g_ref, lse_ref,
                dq_ref, dk_ref, dv_ref, dk_s, dv_s, *, scale, causal,
                tq_blk):
    """Flash-style backward for one (batch * key/value head, query head
    of its group): loop q-blocks, recompute p from (q, k, lse) — no
    stored score matrix anywhere — accumulating dk/dv in fp32 VMEM
    scratch over the q-blocks AND over the group's query heads (the
    innermost grid axis; one head when q and k/v have the same
    count)."""
    member = pl.program_id(1)
    kmat = k_ref[0].astype(jnp.float32)               # (TK, D)
    vmat = v_ref[0].astype(jnp.float32)

    @pl.when(member == 0)
    def _():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    n_blocks = q_ref.shape[1] // tq_blk

    def body(i, _):
        sl = pl.ds(i * tq_blk, tq_blk)
        q = q_ref[0, sl].astype(jnp.float32)          # (TQB, D)
        g = g_ref[0, sl].astype(jnp.float32)
        lse = lse_ref[0, sl]                          # (TQB, 1)
        s = jax.lax.dot_general(
            q, kmat, (((1,), (1,)), ((), ()))) * scale
        if causal:
            mask = qpos_ref[sl] >= kpos_ref[:]        # (TQB,1)>=(1,TK)
            s = jnp.where(mask, s, _MASK_NEG)
        p = jnp.exp(s - lse)
        # re-normalize: a no-op (sum==1) for ordinary rows, but a
        # FULLY-masked row saturates lse to _MASK_NEG in fp32 and
        # exp(s-lse)=1 everywhere — the divide restores the uniform
        # 1/Tk distribution the forward actually produced there
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        dv_s[...] += jax.lax.dot_general(
            p, g, (((0,), (0,)), ((), ())))           # p^T g (TK, D)
        dp = jax.lax.dot_general(
            g, vmat, (((1,), (1,)), ((), ())))        # g v^T (TQB, TK)
        ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
        dq_ref[0, sl] = (jax.lax.dot_general(
            ds, kmat, (((1,), (0,)), ((), ()))) * scale
        ).astype(dq_ref.dtype)
        dk_s[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ()))) * scale  # ds^T q (TK, D)
        return 0

    jax.lax.fori_loop(0, n_blocks, body, 0)

    @pl.when(member == pl.num_programs(1) - 1)
    def _():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _pallas_attention_bwd(q, k, v, q_pos, k_pos, lse, g, scale, causal,
                          interpret, name: str | None = None):
    b, tq, h, d = q.shape
    tk, h_kv = k.shape[1:3]
    group = h // h_kv

    qf, kf, vf, gf = _fold(q), _fold(k), _fold(v), _fold(g)
    qp = q_pos.astype(jnp.int32).reshape(tq, 1)
    kp = k_pos.astype(jnp.int32).reshape(1, tk)
    tq_blk = _q_block(tq, tk, d, q.dtype)

    # grid: (batch * key/value heads, query heads of a group); the
    # key/value blocks stay put while the group's query heads pass
    query = lambda i, m: (i * group + m, 0, 0)  # noqa: E731
    shared = lambda i, m: (i, 0, 0)  # noqa: E731
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, causal=causal,
                          tq_blk=tq_blk),
        grid=(b * h_kv, group),
        in_specs=[
            pl.BlockSpec((1, tq, d), query, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tk, d), shared, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tk, d), shared, memory_space=pltpu.VMEM),
            pl.BlockSpec((tq, 1), lambda i, m: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tk), lambda i, m: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tq, d), query, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tq, 1), query, memory_space=pltpu.VMEM),
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
        interpret=interpret,
        name=name and name + "_bwd",
    )(qf, kf, vf, qp, kp, gf, lse)

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


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _fused(q, k, v, q_pos, k_pos, scale, causal, interpret, name):
    out, _ = _pallas_attention(q, k, v, q_pos, k_pos, scale, causal,
                               interpret, name)
    return out


def _fused_fwd(q, k, v, q_pos, k_pos, scale, causal, interpret, name):
    out, lse = _pallas_attention(q, k, v, q_pos, k_pos, scale, causal,
                                 interpret, name)
    return out, (q, k, v, q_pos, k_pos, lse)


def _fused_bwd(scale, causal, interpret, name, res, g):
    q, k, v, q_pos, k_pos, lse = res
    tq, tk, d = q.shape[1], k.shape[1], q.shape[-1]
    tq_blk = _q_block(tq, tk, d, q.dtype)
    # the fused bwd loops exact q-blocks; ragged tails or oversize
    # VMEM needs take the composed-XLA path instead
    fused = tq % tq_blk == 0 and _fits_vmem_bwd(tq, tk, d, q.dtype,
                                                tq_blk)
    _log_choice("attention bwd", q.shape + k.shape[1:3], str(q.dtype),
                "pallas" if fused else "xla",
                f"fits, q block {tq_blk}" if fused else "ragged q-tail "
                "or over the VMEM budget")
    if fused:
        dq, dk, dv = _pallas_attention_bwd(q, k, v, q_pos, k_pos, lse,
                                           g, scale, causal, interpret,
                                           name)
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
    if q_pos is None:
        q_pos = jnp.arange(q.shape[1])
    if k_pos is None:
        k_pos = jnp.arange(k.shape[1])
    resolved = _resolve_impl(impl, q, k)
    if resolved == "xla":
        return _xla_attention(q, k, v, q_pos, k_pos, scale, causal)
    return _fused(q, k, v, q_pos, k_pos, scale, causal,
                  pallas_mode.interpret(), name)
