"""The gated delta rule of a Gated DeltaNet layer, in its chunked form.

Per head, with a state ``S (dk, dv)`` that starts at zero::

    S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - exp(g_t) S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

``q``, ``k (B, T, H, dk)`` (already normed, scaled and repeated to the
value heads by the caller); ``v (B, T, H, dv)``; ``g (B, T, H)`` the
log decays (``<= 0``) and ``beta (B, T, H)`` the write strengths, both
float32.  The rule subtracts what the state already predicts for
``k_t`` before it writes, so a chunk of ``C`` steps is no longer a sum
of decayed outer products (``ops/ssd.py``'s form): inside a chunk the
writes depend on one another through a unit-lower-triangular system,
the WY / UT transform of Yang et al., "Gated Delta Networks"
(arXiv:2412.06464).  With ``gamma`` the running sum of ``g`` inside a
chunk, ``Gamma_ij = exp(gamma_i - gamma_j)`` for ``j <= i``:

* ``A = strict_lower(diag(beta) K K^T * Gamma)``; ``(I + A) W =
  diag(beta) (K * exp(gamma))`` and ``(I + A) U = diag(beta) V``;
* with ``S`` the entering state: ``U' = U - W S``;
* ``O = (Q * exp(gamma)) S + (Q K^T * Gamma * lower_incl) U'``;
* ``S_next = exp(gamma_C) S + (K * exp(gamma_C - gamma))^T U'``.

The decays, the running sums, the triangular system and the carried
state are float32; the products take the inputs' dtype with float32
accumulation.  A length that is no multiple of what a path walks at a
time is padded with steps that neither decay nor write (``g = beta =
0``), whose outputs are dropped.  Two paths, chosen by ``delta_plan``
from the shape alone:

* **Pallas** (where ``dk`` and ``dv`` are whole lane tiles, the chunk a
  whole number of the dtype's sublane tiles that divides 128 or is 128
  or 256, and a grid step fits the VMEM budget): a kernel pair under a
  ``jax.custom_vjp``.  A grid step covers a *span* of
  ``lcm(C, 128)`` steps (two chunks at ``C = 64``, so that the decays
  fill a 128-lane row) of several value heads; the forward's grid is
  ``(batch, head block, span)``, the spans in order, and each head's
  carried state ``S`` lives in VMEM from span to span, float32.  Per
  span and head, all in VMEM: the running sums of ``g`` along the
  lanes of each chunk, the masked decays ``Gamma`` of all the span's
  chunks as one block-diagonal matrix, ``A``, the inverse ``T = (I +
  A)^-1`` by block doubling (``T_2s = T_s - T_s A_s T_s``, ``A_s`` the
  blocks between the halves of each ``2s`` block: ten float32 products
  at ``C = 64``, each level's blocks multiplied side by side so that a
  product pushes ``max(16, 2s)`` rows, not the span's), ``[W | U] = T
  [diag(beta) K e^gamma | diag(beta) V]`` in float32, then chunk by
  chunk ``U'``, the output and the next state.  The step's heads go
  through each of these phases side by side, so that their chains of
  dependent products overlap (on a v5e that halved both kernels' time
  against a head at a time).  Only ``o`` leaves VMEM and, where a
  gradient will be asked for, each chunk's float32 entering state, the
  backward's residual.
  The backward walks the spans in reverse carrying ``dS`` in VMEM,
  rebuilds ``Gamma``, ``A``, ``T``, ``W`` and ``U`` from the inputs,
  takes the inverse's gradient without a second solve (``dA =
  -strict_lower((T^T dW) W^T + (T^T dU) U^T)``) and emits ``dq``,
  ``dk``, ``dv``, ``d beta`` and ``dg`` (``d gamma`` summed from the
  right inside each chunk).  ``q``, ``k``, ``v``, ``o`` and their
  gradients are read and written in their ``(B, T, H d)`` view, a head
  a lane block; ``g`` and ``beta`` as rows ``(B, H / heads a step,
  heads a step, T)``.  The ``pallas_call``s are named ``<name>_fwd``
  and ``<name>_bwd``.
* **``jax.numpy``** (every other shape, e.g. the dry run's state of 8,
  chunk 8): the same algebra for all chunks at once with a triangular
  solve a chunk and a ``lax.scan`` over the chunks carrying ``S`` and
  keeping each chunk's entering state; JAX differentiates it (the
  caller recomputes it under ``remat``).  It is the kernels' oracle,
  with ``gated_delta_recurrent``, the rule stepped one token at a time.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from theanompi_tpu.ops import pallas_mode

_log = logging.getLogger(__name__)
_F32 = jnp.float32
LANES = 128
#: what a grid step may hold in VMEM (``_vmem_bytes``) and the limit
#: the compiler is given, as ``ops/ssd.py``'s
_VMEM_BUDGET_BYTES = 24 * 1024 * 1024
_VMEM_LIMIT_BYTES = 32 * 1024 * 1024
#: value heads a grid step may take, most first: it walks them in
#: Python loops
_HEADS_A_STEP = (8, 4, 2, 1)
_PLAIN = (((1,), (0,)), ((), ()))     # a @ b
_ROWS = (((1,), (1,)), ((), ()))      # a @ b^T
_COLS = (((0,), (0,)), ((), ()))      # a^T @ b


@dataclasses.dataclass(frozen=True)
class DeltaPlan:
    """Which path ``gated_delta_chunked`` takes at a shape and how
    (static: part of a jit key); one log line a shape."""

    batch: int
    chunks: int
    chunk: int
    heads: int
    key_dim: int
    value_dim: int
    pad: int = 0
    name: str = "gated_delta"
    pallas: bool = False
    heads_per_step: int = 1
    interpret: bool = False

    @property
    def span(self) -> int:
        """Steps a kernel grid step covers: whole chunks, whole lane
        tiles."""
        return _span(self.chunk)

    @property
    def per_span(self) -> int:
        return self.span // self.chunk

    @property
    def spans(self) -> int:
        return self.chunks // self.per_span

    def __str__(self):
        head = (f"{self.name}: {self.chunks} chunks of {self.chunk}"
                + (f" ({self.pad} steps padded)" if self.pad else "")
                + f", {self.heads} heads, state {self.key_dim} x "
                f"{self.value_dim}")
        if not self.pallas:
            return head + (", jax.numpy (WY triangular solve a chunk, "
                           "scan over the chunks)")
        return head + (
            f", pallas (grid {self.batch} x "
            f"{self.heads // self.heads_per_step} x {self.spans}, "
            f"{self.per_span} chunks of {self.heads_per_step} heads a step, "
            "inverse and state in VMEM)")


def _span(chunk: int) -> int:
    return chunk * LANES // math.gcd(chunk, LANES)


def _vmem_bytes(chunk: int, key_dim: int, value_dim: int, heads: int,
                itemsize: int) -> int:
    """The backward's grid step, the larger of the two: blocks twice
    (pipelined), scratch once, and what every head holds at once, ``22 x
    itemsize`` bytes a ``span x max(span, dk + dv)`` element (the
    compiler's own count for a v5e: 16.95 MiB at 8 heads of 128 in
    bfloat16, 33.04 MiB in float32, chunks of 64)."""
    span = _span(chunk)
    lanes_k, lanes_v = heads * key_dim, heads * value_dim
    blocks = (span * (4 * lanes_k + 3 * lanes_v) * itemsize
              + span // chunk * key_dim * lanes_v * 4
              + 4 * max(heads, 8) * span * 4)
    scratch = key_dim * lanes_v * 4
    return (2 * blocks + scratch
            + heads * 22 * itemsize * span * max(span, key_dim + value_dim))


def delta_plan(batch: int, t: int, heads: int, key_dim: int,
               value_dim: int, chunk: int, name: str | None = None,
               itemsize: int = 2) -> DeltaPlan:
    """The path of ``gated_delta_chunked`` at this shape: the kernels
    where ``dk`` and ``dv`` are whole lane tiles, the chunk a whole
    number of sublane tiles of the dtype that divides a lane tile or is
    one or two of them (so that a span is at most 256 steps), and a grid
    step of some number of heads fits the VMEM budget; ``jax.numpy``
    elsewhere."""
    span = _span(chunk)
    kernels = (key_dim % LANES == 0 and value_dim % LANES == 0
               and chunk % (32 // itemsize) == 0 and span <= 2 * LANES)
    per_step = next((n for n in _HEADS_A_STEP if heads % n == 0
                     and _vmem_bytes(chunk, key_dim, value_dim, n, itemsize)
                     <= _VMEM_BUDGET_BYTES), 0) if kernels else 0
    walk = span if per_step else chunk
    chunks = -(-t // walk) * walk // chunk
    return DeltaPlan(batch, chunks, chunk, heads, key_dim, value_dim,
                     chunks * chunk - t, name or "gated_delta",
                     pallas=bool(per_step), heads_per_step=per_step or 1,
                     interpret=pallas_mode.interpret())


@functools.lru_cache(maxsize=None)
def _log_plan(plan: DeltaPlan) -> None:
    """One line a shape (trace time only), as ``ssd``'s plan says its
    own."""
    _log.info("%s", plan)


def _mm(a, b, spec):
    return jnp.einsum(spec, a, b, preferred_element_type=_F32)


def gated_delta_chunked(q, k, v, g, beta, *, chunk: int = 64,
                        name: str | None = None):
    """``o (B, T, H, dv)`` in ``v.dtype``; see the module docstring.
    ``name`` labels the plan's log line and the kernels in a trace."""
    batch, t, h, dk = k.shape
    dv = v.shape[-1]
    plan = delta_plan(batch, t, h, dk, dv, chunk, name,
                      jnp.dtype(v.dtype).itemsize)
    _log_plan(plan)
    g, beta = g.astype(_F32), beta.astype(_F32)
    if plan.pad:
        pad = ((0, 0), (0, plan.pad), (0, 0))
        q, k, v = (jnp.pad(x, pad + ((0, 0),)) for x in (q, k, v))
        g, beta = jnp.pad(g, pad), jnp.pad(beta, pad)
    if plan.pallas:
        return _delta_pallas(q, k, v, g, beta, plan)[:, :t]
    return _delta_jnp(q, k, v, g, beta, chunk)[:, :t]


def _delta_jnp(q, k, v, g, beta, chunk: int):
    """The WY form for all chunks at once, a triangular solve a chunk
    and a scan over the chunks; JAX differentiates it.  ``T`` is a whole
    number of chunks."""
    batch, t, h, dk = k.shape
    dv, dtype = v.shape[-1], v.dtype
    n, c = t // chunk, chunk

    def chunks(x):              # (B, T, H, ...) -> (B, N, H, C, ...)
        x = x.reshape((batch, n, c, h) + x.shape[3:])
        return jnp.moveaxis(x, 3, 2)

    q, k, v = chunks(q), chunks(k), chunks(v)
    g, beta = chunks(g), chunks(beta)
    gamma = jnp.cumsum(g, axis=-1)                       # (B, N, H, C)
    rows = jnp.arange(c)
    lower = rows[:, None] >= rows[None, :]
    # exp only where j <= i: above the diagonal the difference is >= 0
    # and could overflow
    gap = jnp.where(lower, gamma[..., :, None] - gamma[..., None, :],
                    -jnp.inf)
    decay = jnp.exp(gap)                                 # Gamma, (.., C, C)
    strict = rows[:, None] > rows[None, :]
    a = jnp.where(strict, beta[..., None] * _mm(k, k, "...id,...jd->...ij")
                  * decay, 0.0)
    system = a + jnp.eye(c, dtype=_F32)
    rhs = jnp.concatenate(
        [beta[..., None] * k.astype(_F32) * jnp.exp(gamma)[..., None],
         beta[..., None] * v.astype(_F32)], axis=-1)
    wu = jax.lax.linalg.triangular_solve(system, rhs, left_side=True,
                                         lower=True, unit_diagonal=True)
    w, u = wu[..., :dk].astype(dtype), wu[..., dk:]
    last = gamma[..., -1]                                # (B, N, H)
    k_out = (k.astype(_F32) * jnp.exp(last[..., None] - gamma)[..., None]
             ).astype(dtype)

    def step(state, inputs):
        w_c, u_c, k_c, last_c = inputs
        fresh = u_c - _mm(w_c, state.astype(dtype), "bhcd,bhde->bhce")
        state_next = (jnp.exp(last_c)[..., None, None] * state
                      + _mm(k_c, fresh.astype(dtype), "bhcd,bhce->bhde"))
        return state_next, (state, fresh.astype(dtype))

    scanned = tuple(jnp.moveaxis(x, 1, 0) for x in (w, u, k_out, last))
    _, (entering, fresh) = jax.lax.scan(
        step, jnp.zeros((batch, h, dk, dv), _F32), scanned)
    entering, fresh = (jnp.moveaxis(x, 0, 1) for x in (entering, fresh))
    scores = (_mm(q, k, "...id,...jd->...ij") * decay).astype(dtype)
    out = (_mm((q.astype(_F32) * jnp.exp(gamma)[..., None]).astype(dtype),
               entering.astype(dtype), "bnhcd,bnhde->bnhce")
           + _mm(scores, fresh, "bnhij,bnhje->bnhie"))
    out = jnp.moveaxis(out, 2, 3).reshape(batch, n * c, h, dv)
    return out.astype(dtype)


def gated_delta_recurrent(q, k, v, g, beta):
    """The rule one token at a time, float32 throughout; ``o (B, T, H,
    dv)`` float32."""
    q, k, v, g, beta = (x.astype(_F32) for x in (q, k, v, g, beta))
    batch, _, h, dk = k.shape

    def step(state, inputs):
        q_t, k_t, v_t, g_t, b_t = inputs
        state = jnp.exp(g_t)[..., None, None] * state
        predicted = jnp.einsum("bhde,bhd->bhe", state, k_t)
        state = state + jnp.einsum("bhd,bhe->bhde", k_t,
                                   b_t[..., None] * (v_t - predicted))
        return state, jnp.einsum("bhde,bhd->bhe", state, q_t)

    inputs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, out = jax.lax.scan(
        step, jnp.zeros((batch, h, dk, v.shape[-1]), _F32), inputs)
    return jnp.moveaxis(out, 0, 1)


# -- the kernels ----------------------------------------------------------
#
# A grid step walks its heads in Python loops (``_HEADS_A_STEP``) so
# that every head's lanes and its column of the span's running sums are
# static slices, and the span's chunks in another, carrying the state
# from one to the next.  The heads go side by side through each phase
# (their inverses level by level, the chunks' chain chunk by chunk),
# and the carried states are read from scratch before any is written,
# so that the heads' dependent products overlap.  Every span x span
# matrix is block-diagonal by chunk: what lies between two chunks is
# masked to 0 (``Gamma`` and the masks below), so the span's chunks
# never mix but through the carried state.


def _dot(a, b, dims=_PLAIN):
    """``a`` times ``b`` into float32; float32 operands at full
    precision."""
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=_F32,
        precision=(jax.lax.Precision.HIGHEST if a.dtype == _F32 else None))


def _rowsum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _chunk_sums(v, chunk: int, reverse: bool = False):
    """Inclusive running sums along the lanes of ``(rows, span)``
    float32 inside each chunk of ``chunk`` lanes, from the right with
    ``reverse``: log2(chunk) shifted adds (``ops/ssd.py``'s
    ``_running_sums``, chunk by chunk)."""
    span = v.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    at = lane - lane // chunk * chunk
    shift = 1
    while shift < chunk:
        if reverse:      # lane i adds lane i + shift
            v = v + jnp.where(at < chunk - shift,
                              pltpu.roll(v, span - shift, 1), 0.0)
        else:            # lane i adds lane i - shift
            v = v + jnp.where(at >= shift, pltpu.roll(v, shift, 1), 0.0)
        shift *= 2
    return v


def _inverses(systems, chunk: int):
    """``(I + A)^-1`` of each ``A (span, span)``, strictly lower and
    block-diagonal by chunk, float32, by block doubling: ``T_1 = I``,
    ``T_2s = T_s - T_s A_s T_s`` with ``A_s`` the part of ``A`` between
    the two halves of each ``2s`` block (the inverse of ``[[L1, 0], [M,
    L2]]`` is ``[[T1, 0], [-T2 M T1, T2]]``).  ``T_2 = I - A_1`` needs
    no product; the other levels take two each.  A level's blocks of
    ``b = max(16, 2s)`` are multiplied side by side, ``[X_0 | X_1 |
    ...] (b, span)`` times the block-diagonal ``Y``, so that a product
    pushes ``b`` rows through the MXU and not ``span``; and the systems
    (the step's heads) go level by level together, so that their chains
    of products overlap."""
    n = systems[0].shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)

    def packing(b):
        lanes = jax.lax.broadcasted_iota(jnp.int32, (b, n), 1)

        def pack(x):            # (span, span) -> (b, span)
            return sum(x[k * b:(k + 1) * b] for k in range(n // b))

        def unpack(x):          # (b, span) -> (span, span)
            if b == n:
                return x
            return jnp.concatenate([jnp.where(lanes // b == k, x, 0.0)
                                    for k in range(n // b)], axis=0)

        i = jax.lax.broadcasted_iota(jnp.int32, (b, n), 0)
        return pack, unpack, i, lanes - lanes // b * b

    b = min(16, chunk)
    pack, unpack, i, j = packing(b)
    a = [pack(jnp.where(rows // b == cols // b, x, 0.0)) for x in systems]
    t = [jnp.where(i == j, 1.0, 0.0) - jnp.where(i // 2 == j // 2, x, 0.0)
         for x in a]
    s = 2
    while s < chunk:
        if 2 * s > b:           # the blocks outgrow the packing
            full = [unpack(x) for x in t]
            b = 2 * s
            pack, unpack, i, j = packing(b)
            t = [pack(x) for x in full]
            a = [pack(jnp.where(rows // b == cols // b, x, 0.0))
                 for x in systems]
        between = (i // (2 * s) == j // (2 * s)) & (i // s != j // s)
        ys = [unpack(_dot(jnp.where(between, x, 0.0), unpack(y)))
              for x, y in zip(a, t)]
        t = [y - _dot(y, z) for y, z in zip(t, ys)]
        s *= 2
    return [unpack(x) for x in t]


class _Span:
    """A grid step's decays and write strengths, every head at once:
    ``gamma`` (the running sums of ``g`` inside each chunk) as rows
    ``(heads, span)`` and as columns ``(span, heads)``, ``beta`` as
    columns, ``exp(gamma)``, ``exp(gamma_C - gamma)`` to each row's
    chunk end, ``gamma_C`` of each chunk (``(1, heads)``), and the
    block-diagonal masks."""

    def __init__(self, g_rows, beta_rows, plan: DeltaPlan):
        n, c = plan.span, plan.chunk
        self.rows = _chunk_sums(g_rows, c)
        self.cols = jnp.transpose(self.rows)
        self.beta = jnp.transpose(beta_rows)
        i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        same = i // c == j // c
        self.lower, self.strict = same & (i >= j), same & (i > j)
        self.row = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
        self.ends = [self.cols[m * c + c - 1:(m + 1) * c, :]
                     for m in range(plan.per_span)]
        last = self.ends[0]
        for m in range(1, plan.per_span):
            last = jnp.where(self.row >= m * c, self.ends[m], last)
        self.exp_g = jnp.exp(self.cols)
        self.to_end = jnp.exp(last - self.cols)

    def through_of(self, m: int, j: int, width: int):
        """``exp(gamma_C)`` of chunk ``m`` of head ``j`` as a row of
        ``width``: a lone value is broadcast across the lanes before
        ``exp`` and down the sublanes after it (Mosaic does not take
        both at once)."""
        return jnp.exp(jnp.broadcast_to(self.ends[m][:, j:j + 1],
                                        (1, width)))

    def decay(self, j: int):
        """``Gamma`` of head ``j``: ``exp(gamma_i - gamma_j)`` where
        ``j <= i`` in one chunk, else 0."""
        return jnp.exp(jnp.where(
            self.lower, self.cols[:, j:j + 1] - self.rows[j:j + 1, :],
            -jnp.inf))


class _Head:
    """What both kernels make of head ``j``'s span before the chunks'
    sequential part: ``Gamma``, ``K K^T``, ``Q K^T`` and ``A`` here;
    ``T``, ``[W | U]`` (float32), the scaled keys and queries and the
    masked scores in ``solve``."""

    def __init__(self, s: _Span, j: int, q, k, v):
        self.q, self.k, self.v = q, k, v
        self.kf = k.astype(_F32)
        self.beta = s.beta[:, j:j + 1]
        self.exp_g, self.to_end = s.exp_g[:, j:j + 1], s.to_end[:, j:j + 1]
        self.decay = s.decay(j)
        self.kk = _dot(k, k, _ROWS)
        self.qk = _dot(q, k, _ROWS)
        self.a = jnp.where(s.strict, self.beta * self.kk * self.decay, 0.0)

    def solve(self, t):
        dk, dtype = self.k.shape[1], self.v.dtype
        self.t = t
        self.rw = self.beta * self.exp_g * self.kf
        self.wu = _dot(t, jnp.concatenate(
            [self.rw, self.beta * self.v.astype(_F32)], axis=1))
        self.w, self.u = self.wu[:, :dk].astype(dtype), self.wu[:, dk:]
        self.kd_f = self.kf * self.to_end
        self.kd = self.kd_f.astype(dtype)
        self.qe_f = self.q.astype(_F32) * self.exp_g
        self.qe = self.qe_f.astype(dtype)
        self.scores = (self.qk * self.decay).astype(dtype)


def _heads(s: _Span, q_ref, k_ref, v_ref, plan: DeltaPlan):
    """The step's heads, their inverses made together, and each head's
    lanes of the keys and of the values."""
    dk, dv = plan.key_dim, plan.value_dim
    lanes = [(slice(j * dk, (j + 1) * dk), slice(j * dv, (j + 1) * dv))
             for j in range(plan.heads_per_step)]
    hds = [_Head(s, j, q_ref[:, keys], k_ref[:, keys], v_ref[:, values])
           for j, (keys, values) in enumerate(lanes)]
    for hd, t in zip(hds, _inverses([hd.a for hd in hds], plan.chunk)):
        hd.solve(t)
    return hds, lanes


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest,
                plan: DeltaPlan, save: bool):
    states_ref, state = (rest[0], rest[1]) if save else (None, rest[0])
    c, dv = plan.chunk, plan.value_dim

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    s = _Span(g_ref[...], b_ref[...], plan)
    hds, lanes = _heads(s, q_ref, k_ref, v_ref, plan)
    dtype = v_ref.dtype
    # every head's state is read before any is written: the heads'
    # chains do not wait on one another through the scratch
    entering = [state[:, values] for _, values in lanes]
    fresh = [[] for _ in hds]
    inter = [[] for _ in hds]
    for m in range(plan.per_span):
        rows = slice(m * c, (m + 1) * c)
        for j, hd in enumerate(hds):
            if save:
                states_ref[m, :, lanes[j][1]] = entering[j]
            low = entering[j].astype(dtype)
            fresh[j].append((hd.u[rows] - _dot(hd.w[rows], low)).astype(
                dtype))
            inter[j].append(_dot(hd.qe[rows], low))
            entering[j] = (s.through_of(m, j, dv) * entering[j]
                           + _dot(hd.kd[rows], fresh[j][-1], _COLS))
    for j, (hd, (_, values)) in enumerate(zip(hds, lanes)):
        state[:, values] = entering[j]
        o = (jnp.concatenate(inter[j], axis=0)
             + _dot(hd.scores, jnp.concatenate(fresh[j], axis=0)))
        o_ref[:, values] = o.astype(o_ref.dtype)


def _specs(plan: DeltaPlan, reverse: bool):
    """BlockSpecs of the operands: span ``c`` (or, in reverse, the
    ``c``-th from the end) of head block ``h`` of sequence ``i``."""
    n, heads = plan.span, plan.heads_per_step
    last = plan.spans - 1
    at = (lambda c: last - c) if reverse else (lambda c: c)  # noqa: E731
    return dict(
        keys=pl.BlockSpec((None, n, heads * plan.key_dim),
                          lambda i, h, c: (i, at(c), h)),
        values=pl.BlockSpec((None, n, heads * plan.value_dim),
                            lambda i, h, c: (i, at(c), h)),
        rows=pl.BlockSpec((None, None, heads, n),
                          lambda i, h, c: (i, h, 0, at(c))),
        states=pl.BlockSpec((None, plan.per_span, plan.key_dim,
                             heads * plan.value_dim),
                            lambda i, h, c: (i, at(c), 0, h)))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _grid(plan: DeltaPlan):
    return plan.batch, plan.heads // plan.heads_per_step, plan.spans


@functools.partial(jax.jit, static_argnames=("plan", "save"))
def _forward(q, k, v, g, beta, *, plan: DeltaPlan, save: bool):
    """``o (B, T, H dv)`` and, with ``save``, each chunk's entering
    state ``(B, chunks, dk, H dv)`` float32."""
    s = _specs(plan, reverse=False)
    out_specs = [s["values"]]
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype)]
    if save:
        out_specs.append(s["states"])
        out_shape.append(jax.ShapeDtypeStruct(
            (plan.batch, plan.chunks, plan.key_dim, v.shape[2]), _F32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, plan=plan, save=save),
        grid=_grid(plan),
        in_specs=[s["keys"], s["keys"], s["values"], s["rows"], s["rows"]],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(                        # S, carried
            (plan.key_dim, plan.heads_per_step * plan.value_dim), _F32)],
        compiler_params=_params(),
        interpret=plan.interpret,
        name=plan.name + "_fwd",
    )(q, k, v, g, beta)
    return tuple(out) if save else out[0]


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dstate, *,
                plan: DeltaPlan):
    c, dk, dv = plan.chunk, plan.key_dim, plan.value_dim
    heads, chunks = plan.heads_per_step, range(plan.per_span)

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    s = _Span(g_ref[...], b_ref[...], plan)
    hds, lanes = _heads(s, q_ref, k_ref, v_ref, plan)
    dtype = v_ref.dtype
    do = [do_ref[:, values] for _, values in lanes]
    entering = [[s_ref[m, :, values] for m in chunks] for _, values in lanes]
    ds = [dstate[:, values] for _, values in lanes]  # d(the span's exit)
    fresh = [jnp.concatenate(
        [hd.u[m * c:(m + 1) * c]
         - _dot(hd.w[m * c:(m + 1) * c], entering[j][m].astype(dtype))
         for m in chunks], axis=0).astype(dtype) for j, hd in enumerate(hds)]
    # P^T dO: the in-chunk outputs' share of dU'
    dfresh_in = [_dot(hd.scores, do[j], _COLS) for j, hd in enumerate(hds)]
    dfresh, dkd, dqe, dw = ([[None] * plan.per_span for _ in hds]
                            for _ in range(4))
    at_end = [jnp.zeros((plan.span, 1), _F32) for _ in hds]  # d gamma_C
    for m in reversed(chunks):
        rows = slice(m * c, (m + 1) * c)
        for j, hd in enumerate(hds):
            ds_low, s_low = ds[j].astype(dtype), entering[j][m].astype(dtype)
            df = dfresh_in[j][rows] + _dot(hd.kd[rows], ds_low)
            dkd[j][m] = _dot(fresh[j][rows], ds_low, _ROWS)
            dqe[j][m] = _dot(do[j][rows], s_low, _ROWS)
            end = (jnp.exp(s.ends[m][:, j:j + 1])
                   * jnp.sum(_rowsum(ds[j] * entering[j][m]), axis=0,
                             keepdims=True)
                   + jnp.sum(_rowsum(dkd[j][m] * hd.kd_f[rows]), axis=0,
                             keepdims=True))
            at_end[j] = jnp.where(s.row == m * c + c - 1, end, at_end[j])
            df_low = df.astype(dtype)
            dw[j][m] = -_dot(df_low, s_low, _ROWS)
            dfresh[j][m] = df
            ds[j] = (_dot(hd.qe[rows], do[j][rows], _COLS)
                     + s.through_of(m, j, dv) * ds[j]
                     - _dot(hd.w[rows], df_low, _COLS))
    for j, (_, values) in enumerate(lanes):
        dstate[:, values] = ds[j]

    head_lane = jax.lax.broadcasted_iota(jnp.int32, (1, heads), 1)
    head_row = jax.lax.broadcasted_iota(jnp.int32, (heads, 1), 0)
    dgamma_cols = dbeta_cols = jnp.zeros((plan.span, heads), _F32)
    dgamma_rows = jnp.zeros((heads, plan.span), _F32)
    for j, (hd, (keys, values)) in enumerate(zip(hds, lanes)):
        dfresh_j, dkd_j, dqe_j, dw_j = (jnp.concatenate(x[j], axis=0)
                                        for x in (dfresh, dkd, dqe, dw))
        # T^T [dW | dU], then dA without a second solve
        dr = _dot(hd.t, jnp.concatenate([dw_j, dfresh_j], axis=1), _COLS)
        drw, dru = dr[:, :dk], dr[:, dk:]
        da = jnp.where(s.strict, -_dot(dr, hd.wu, _ROWS), 0.0)
        dp = jnp.where(s.lower, _dot(do[j], fresh[j], _ROWS), 0.0)
        dkk = (da * hd.beta * hd.decay).astype(dtype)
        dqk = (dp * hd.decay).astype(dtype)
        # d Gamma times Gamma: into gamma_i by rows, out of gamma_j by
        # columns
        z = (da * hd.beta * hd.kk + dp * hd.qk) * hd.decay
        dgamma = (_rowsum(z) + _rowsum(dqe_j * hd.qe_f)
                  - _rowsum(dkd_j * hd.kd_f) + _rowsum(drw * hd.rw)
                  + at_end[j])
        dgamma_cols = jnp.where(head_lane == j, dgamma, dgamma_cols)
        dgamma_rows = jnp.where(head_row == j,
                                -jnp.sum(z, axis=0, keepdims=True),
                                dgamma_rows)
        dbeta = (_rowsum(da * hd.kk * hd.decay)
                 + _rowsum(drw * hd.kf * hd.exp_g)
                 + _rowsum(dru * hd.v.astype(_F32)))
        dbeta_cols = jnp.where(head_lane == j, dbeta, dbeta_cols)
        dq_ref[:, keys] = (_dot(dqk, hd.k) + dqe_j * hd.exp_g).astype(
            dq_ref.dtype)
        dk_ref[:, keys] = (
            _dot(dkk, hd.k) + _dot(dkk, hd.k, _COLS) + _dot(dqk, hd.q, _COLS)
            + dkd_j * hd.to_end + drw * hd.beta * hd.exp_g
        ).astype(dk_ref.dtype)
        dv_ref[:, values] = (dru * hd.beta).astype(dv_ref.dtype)
    dg_ref[...] = _chunk_sums(jnp.transpose(dgamma_cols) + dgamma_rows, c,
                              reverse=True)
    db_ref[...] = jnp.transpose(dbeta_cols)


@functools.partial(jax.jit, static_argnames=("plan",))
def _backward(q, k, v, g, beta, states, do, *, plan: DeltaPlan):
    """``(dq, dk, dv, dg rows, d beta rows)``."""
    s = _specs(plan, reverse=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, plan=plan),
        grid=_grid(plan),
        in_specs=[s["keys"], s["keys"], s["values"], s["rows"], s["rows"],
                  s["states"], s["values"]],
        out_specs=[s["keys"], s["keys"], s["values"], s["rows"], s["rows"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, _F32),
                   jax.ShapeDtypeStruct(beta.shape, _F32)],
        scratch_shapes=[pltpu.VMEM(                        # dS, carried
            (plan.key_dim, plan.heads_per_step * plan.value_dim), _F32)],
        compiler_params=_params(),
        interpret=plan.interpret,
        name=plan.name + "_bwd",
    )(q, k, v, g, beta, states, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _delta(q, k, v, g, beta, plan):
    return _forward(q, k, v, g, beta, plan=plan, save=False)


def _delta_fwd(q, k, v, g, beta, plan):
    o, states = _forward(q, k, v, g, beta, plan=plan, save=True)
    return o, (q, k, v, g, beta, states)


def _delta_bwd(plan, res, do):
    return _backward(*res, do, plan=plan)


_delta.defvjp(_delta_fwd, _delta_bwd)


def _delta_pallas(q, k, v, g, beta, plan: DeltaPlan):
    """The kernels' layouts round ``_delta``: ``q``, ``k``, ``v`` as
    ``(B, T, H d)``; ``g`` and ``beta`` as rows, a head a row."""
    batch, t, h, dk = k.shape
    dv, per = v.shape[-1], plan.heads_per_step

    def rows(x):                # (B, T, H) -> (B, H / per, per, T)
        return jnp.moveaxis(x, 1, 2).reshape(batch, h // per, per, t)

    o = _delta(q.reshape(batch, t, h * dk), k.reshape(batch, t, h * dk),
               v.reshape(batch, t, h * dv), rows(g), rows(beta), plan)
    return o.reshape(batch, t, h, dv)
