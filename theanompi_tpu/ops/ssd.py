"""The selective state-space recurrence of a Mamba-2 layer, in its
chunked matrix ("SSD") form.

Per head, with a state ``S (P, N)`` that starts at zero::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;    y_t = S_t C_t + D x_t

``x (B, T, H, P)``; ``dt (B, T, H)`` the positive time steps; ``A (H,)``
negative, one a head; ``B`` and ``C`` ``(B, T, G, N)``, shared by the
``r = H / G`` heads of a group (head h reads group ``h // r``); ``D
(H,)`` the skip.

The recurrence is linear in ``S``, so a chunk of ``Q`` steps is four
matrix products and the sequential dependence is left between chunks
only ("Transformers are SSMs", arXiv:2405.21060, section 6).  With
``L_t`` the running sum of ``dt A`` inside a chunk:

* in the chunk: ``y_t += sum_{s <= t} exp(L_t - L_s) (C_t . B_s) dt_s
  x_s``, the decay-masked ``C B^T`` product (``Q x Q`` a group) applied
  to ``x``;
* each chunk's end state, from zero: ``sum_s exp(L_Q - L_s) dt_s x_s
  B_s^T``;
* the states carried across the chunks: ``S_in[c + 1] = exp(L_Q of c)
  S_in[c] + end state of c``;
* their contribution to the chunk's outputs: ``y_t += exp(L_t) S_in
  C_t``.

Decays, running sums and carried states are float32; the four products
run in ``x``'s dtype with float32 accumulation.  Two paths, chosen by
``ssd_plan`` from the shape alone:

* **Pallas** (where ``Q``, ``N`` and ``r P`` are whole lane tiles, a
  head fills or divides one and a group has at most 16 heads): a kernel
  pair under a ``jax.custom_vjp``.
  The forward's grid is ``(batch, group, chunk)``, the chunks in order;
  the group's ``r`` carried states ``(N, r P)`` float32 live in VMEM
  from chunk to chunk, and every ``Q x Q`` matrix is made and used in
  VMEM: only ``y`` leaves it (and, where a gradient will be asked for,
  each chunk's entering states, the backward's residual).  The backward
  walks the chunks in reverse, carrying the states' gradient in VMEM,
  remakes the chunk's matrices from the inputs and emits ``dx``,
  ``dB``, ``dC``, ``dD``, the time steps' gradient through ``x dt``
  and that of ``dt A``.  Both kernels make ``L``, the running sums
  of ``dt A`` along a chunk's lanes (``_running_sums``; the backward
  turns ``dL`` into ``d(dt A)`` by the sums from the right), and XLA's
  transpose of ``dt A`` finishes ``ddt`` and ``dA``.  A head of ``P <
  128`` shares a 128-lane tile with its neighbours: its products run
  over the tile and keep the head's own lanes.  The ``pallas_call``s
  are named ``<name>_fwd`` and ``<name>_bwd``.
* **``jax.numpy``** (every other shape, e.g. the dry run's chunk 8,
  state 8, head 8): the same four products, JAX differentiates them.
  It is also the tests' oracle for the kernels.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from theanompi_tpu.ops import pallas_mode

LANES = 128
#: what a grid step may hold in VMEM, blocks double-buffered, scratch
#: and the body's temporaries (``_vmem_bytes``)
_VMEM_BUDGET_BYTES = 24 * 1024 * 1024
_VMEM_LIMIT_BYTES = 32 * 1024 * 1024
_F32 = jnp.float32
#: heads a group may have on the kernels' path: a grid step walks them
#: in a Python loop (see the kernels' section)
_MAX_HEADS = 16
_ROWS = (((1,), (1,)), ((), ()))      # a @ b^T
_COLS = (((0,), (0,)), ((), ()))      # a^T @ b


@dataclasses.dataclass(frozen=True)
class SsdPlan:
    """Which path a shape takes and how (static: part of a jit key)."""

    batch: int
    chunks: int
    chunk: int
    heads: int
    groups: int
    head_dim: int
    state: int
    pallas: bool
    name: str = "ssd"
    interpret: bool = False

    @property
    def per_group(self) -> int:
        return self.heads // self.groups

    @property
    def width(self) -> int:
        """Lanes a head's products run over: its own, or the 128-lane
        tile it shares."""
        return max(self.head_dim, LANES)

    @property
    def per_tile(self) -> int:
        """Heads that share a tile of ``width`` lanes."""
        return self.width // self.head_dim

    @property
    def tiles(self) -> int:
        return self.per_group * self.head_dim // self.width

    def __str__(self):
        head = (f"ssd: {self.chunks} chunks of {self.chunk}, {self.heads} "
                f"heads, state {self.head_dim} x {self.state}")
        if not self.pallas:
            return head + ", jax.numpy"
        return (head + f", pallas (grid {self.batch} x {self.groups} x "
                f"{self.chunks}, state in VMEM)")


def _vmem_bytes(q: int, n: int, r: int, p: int, itemsize: int) -> int:
    """The backward's grid step, the larger of the two: blocks twice
    (pipelined), scratch once, and eight ``Q x max(Q, rP)`` float32
    temporaries."""
    rp, rows = r * p, max(r, 8)
    blocks = (3 * q * rp * itemsize + n * rp * 4 + 4 * q * n * itemsize
              + 4 * rows * q * 4 + 2 * 8 * rp * 4)
    scratch = n * rp * 4 + 2 * q * rp * itemsize
    return 2 * blocks + scratch + 8 * q * max(q, rp) * 4


def ssd_plan(batch: int, t: int, heads: int, head_dim: int, groups: int,
             state: int, chunk: int, itemsize: int = 2,
             name: str | None = None) -> SsdPlan:
    """The path of ``ssd_chunked`` at this shape: the kernels where the
    chunk, the state and a group's ``r P`` lanes are whole lane tiles, a
    head fills or divides one, and a grid step fits the VMEM budget;
    ``jax.numpy`` elsewhere."""
    r = heads // groups
    fits = (chunk % LANES == 0 and state % LANES == 0
            and (r * head_dim) % LANES == 0 and r <= _MAX_HEADS
            and (head_dim % LANES == 0 or LANES % head_dim == 0)
            and _vmem_bytes(chunk, state, r, head_dim, itemsize)
            <= _VMEM_BUDGET_BYTES)
    return SsdPlan(batch, t // chunk, chunk, heads, groups, head_dim, state,
                   pallas=fits, name=name or "ssd",
                   interpret=pallas_mode.interpret())


def ssd_chunked(x, dt, a, b, c, d, *, chunk: int, name: str | None = None):
    """``y (B, T, H, P)`` in ``x.dtype``; see the module docstring.
    ``T`` is a whole number of chunks.  ``name`` labels the kernels in
    a trace."""
    batch, t, h, p = x.shape
    g, n = b.shape[2:]
    if t % chunk or h % g:
        raise ValueError(
            f"{t} steps in chunks of {chunk}, {h} heads over {g} groups: "
            "the sequence is a whole number of chunks and a group a whole "
            "number of heads")
    plan = ssd_plan(batch, t, h, p, g, n, chunk,
                    jnp.dtype(x.dtype).itemsize, name)
    if plan.pallas:
        return _ssd_pallas(x, dt, a, b, c, d, plan)
    return _ssd_jnp(x, dt, a, b, c, d, chunk)


def _ssd_jnp(x, dt, a, b, c, d, chunk: int):
    """The four products in ``jax.numpy``; JAX differentiates them."""
    batch, t, h, p = x.shape
    g, n = b.shape[2:]
    nc, r, dtype, f32 = t // chunk, h // g, x.dtype, _F32
    x32 = x.astype(f32).reshape(batch, nc, chunk, g, r, p)
    dt = dt.astype(f32).reshape(batch, nc, chunk, g, r)
    b = b.reshape(batch, nc, chunk, g, n)
    c = c.reshape(batch, nc, chunk, g, n)
    # L (b, c, g, r, q): the log decay from the chunk's start through t
    run = jnp.cumsum(jnp.moveaxis(dt * a.astype(f32).reshape(g, r), 2, -1),
                     axis=-1)

    def product(spec, lhs, rhs):
        return jnp.einsum(spec, lhs, rhs, preferred_element_type=f32)

    # in the chunk: the decay-masked C B^T, applied to dt x
    scores = product("bcqgn,bcsgn->bcgqs", c, b)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(
        causal, run[..., :, None] - run[..., None, :], -jnp.inf))
    xdt = x32 * dt[..., None]                          # (b, c, s, g, r, p)
    y = product("bcgrqs,bcsgrp->bcqgrp",
                (scores[:, :, :, None] * decay).astype(dtype),
                xdt.astype(dtype))
    # each chunk's end state from zero, then the states carried across
    to_end = jnp.moveaxis(jnp.exp(run[..., -1:] - run), -1, 2)
    ends = product("bcsgrp,bcsgn->bcgrpn",
                   (xdt * to_end[..., None]).astype(dtype), b)
    through = jnp.exp(run[..., -1])                    # (b, c, g, r)

    def carry(state, chunk_c):
        end, factor = chunk_c
        return state * factor[..., None, None] + end, state

    _, entering = jax.lax.scan(
        carry, jnp.zeros_like(ends[:, 0]),
        (jnp.moveaxis(ends, 1, 0), jnp.moveaxis(through, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)            # (b, c, g, r, p, n)
    y = y + (product("bcqgn,bcgrpn->bcqgrp", c, entering.astype(dtype))
             * jnp.moveaxis(jnp.exp(run), -1, 2)[..., None])
    y = y + x32 * d.astype(f32).reshape(g, r, 1)
    return y.reshape(batch, t, h, p).astype(dtype)


# -- the kernels ----------------------------------------------------------
#
# Layouts (free reshapes of the caller's arrays but two small
# transposes): x, y, dy, dx as (B, T, H P), a group's r P lanes a block;
# B and C as (B, T, G N); dt and dt A as ROWS (B, G, r, T), a head a
# row; D spread over its head's lanes (G, 1, r P); the carried states
# transposed, (N, r P) a group: state[n, head j's lanes] = S_j[:, n].
#
# A grid step walks its group's heads in Python loops (r of them, at
# most 16, ``ssd_plan``), so that every head's lanes and its column of
# the chunk's running sums are static slices: Mosaic refuses a
# one-lane slice at a traced offset.  Elementwise work runs once a
# 128-lane tile, whatever heads share it; the Q x Q work once a head.


def _running_sums(v, reverse: bool = False):
    """Inclusive running sums along the lanes of ``(rows, Q)`` float32,
    from the right with ``reverse``: log2(Q) shifted adds, exact in
    float32 up to the order of the additions."""
    q = v.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    shift = 1
    while shift < q:
        if reverse:      # lane i adds lane i + shift
            v = v + jnp.where(lane < q - shift,
                              pltpu.roll(v, q - shift, 1), 0.0)
        else:            # lane i adds lane i - shift
            v = v + jnp.where(lane >= shift, pltpu.roll(v, shift, 1), 0.0)
        shift *= 2
    return v


class _Chunk:
    """A chunk's decay terms, every head of the group at once: ``L``
    as rows ``(r, Q)`` and as columns ``(Q, r)``, and from the columns
    ``exp(L_t)``, ``exp(L_Q - L_t)``, ``exp(L_Q)`` (``(1, r)``); the
    time steps as rows and columns, and ``dt_t exp(L_Q - L_t)``."""

    def __init__(self, dt_rows, dta_rows, plan: SsdPlan):
        q = plan.chunk
        self.plan = plan
        self.rows = _running_sums(dta_rows)
        self.cols = jnp.transpose(self.rows)
        self.dt_rows = dt_rows
        self.dt = jnp.transpose(dt_rows)
        last = self.cols[q - 1:, :]
        self.exp_l = jnp.exp(self.cols)
        self.to_end = jnp.exp(last - self.cols)
        self.dt_to_end = self.dt * self.to_end
        self.through = jnp.exp(last)
        self.causal = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
                       >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
        self.which = (jax.lax.broadcasted_iota(jnp.int32, (1, plan.width), 1)
                      // plan.head_dim)

    def decay(self, j: int):
        """``exp(L_t - L_s)`` of head ``j`` where ``s <= t``, else 0
        (``Q x Q``)."""
        return jnp.exp(jnp.where(
            self.causal, self.cols[:, j:j + 1] - self.rows[j:j + 1, :],
            -jnp.inf))

    def spread(self, cols, tile: int):
        """Column ``j`` of ``cols`` over head ``j``'s lanes of ``tile``."""
        k = self.plan.per_tile
        out = cols[:, tile * k:tile * k + 1]
        for i in range(1, k):
            out = jnp.where(self.which == i,
                            cols[:, tile * k + i:tile * k + i + 1], out)
        return out

    def keep(self, v, i: int):
        """``v`` on the lanes of the tile's ``i``-th head, 0 elsewhere."""
        return v if self.plan.per_tile == 1 else jnp.where(
            self.which == i, v, 0.0)


def _fwd_kernel(x_ref, dt_ref, dta_ref, b_ref, c_ref, d_ref, y_ref, *rest,
                plan: SsdPlan, save: bool):
    states_ref, (state, xw) = (rest[0], rest[1:]) if save \
        else (None, rest)
    b, c = b_ref[...], c_ref[...]
    dtype, w, per_tile = b.dtype, plan.width, plan.per_tile

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    entering = state[...]
    if save:
        states_ref[...] = entering
    k = _Chunk(dt_ref[...], dta_ref[...], plan)
    scores = jax.lax.dot_general(c, b, _ROWS, preferred_element_type=_F32)
    inter = jnp.dot(c, entering.astype(dtype), preferred_element_type=_F32)
    for tile in range(plan.tiles):
        lanes = slice(tile * w, (tile + 1) * w)
        x_low = x_ref[:, lanes]
        xt = x_low.astype(_F32)
        y = k.spread(k.exp_l, tile) * inter[:, lanes] + d_ref[:, lanes] * xt
        for i in range(per_tile):
            j = tile * per_tile + i
            # (C_t . B_s) exp(L_t - L_s) dt_s, applied to x_s
            m = scores * k.decay(j) * k.dt_rows[j:j + 1, :]
            y = y + k.keep(jnp.dot(m.astype(dtype), x_low,
                                   preferred_element_type=_F32), i)
        y_ref[:, lanes] = y.astype(y_ref.dtype)
        xw[:, lanes] = (xt * k.spread(k.dt_to_end, tile)).astype(dtype)
        state[:, lanes] = entering[:, lanes] * k.spread(k.through, tile)
    state[...] += jax.lax.dot_general(b, xw[...], _COLS,
                                      preferred_element_type=_F32)


def _specs(plan: SsdPlan, reverse: bool):
    """BlockSpecs of the operands, chunk ``c`` (or, in reverse, the
    ``c``-th from the end) of group ``g`` of sequence ``i``."""
    q, n, r = plan.chunk, plan.state, plan.per_group
    rp, last = r * plan.head_dim, plan.chunks - 1
    at = (lambda c: last - c) if reverse else (lambda c: c)  # noqa: E731
    return dict(
        lanes=pl.BlockSpec((None, q, rp), lambda i, g, c: (i, at(c), g)),
        group=pl.BlockSpec((None, q, n), lambda i, g, c: (i, at(c), g)),
        rows=pl.BlockSpec((None, None, r, q),
                          lambda i, g, c: (i, g, 0, at(c))),
        skip=pl.BlockSpec((None, 1, rp), lambda i, g, c: (g, 0, 0)),
        states=pl.BlockSpec((None, None, n, rp),
                            lambda i, g, c: (i, at(c), 0, g)))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)


@functools.partial(jax.jit, static_argnames=("plan", "save"))
def _forward(x, dtr, dtar, b, c, drow, *, plan: SsdPlan, save: bool):
    """``y (B, T, H P)`` and, with ``save``, each chunk's entering
    states ``(B, chunks, N, H P)`` float32."""
    batch, t, hp = x.shape
    rp, n = plan.per_group * plan.head_dim, plan.state
    s = _specs(plan, reverse=False)
    out_specs = [s["lanes"]]
    out_shape = [jax.ShapeDtypeStruct((batch, t, hp), x.dtype)]
    if save:
        out_specs.append(s["states"])
        out_shape.append(jax.ShapeDtypeStruct(
            (batch, plan.chunks, n, hp), _F32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, plan=plan, save=save),
        grid=(batch, plan.groups, plan.chunks),
        in_specs=[s["lanes"], s["rows"], s["rows"], s["group"], s["group"],
                  s["skip"]],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, rp), _F32),           # S, carried
                        pltpu.VMEM((plan.chunk, rp), x.dtype)],
        compiler_params=_params(),
        interpret=plan.interpret,
        name=plan.name + "_fwd",
    )(x, dtr, dtar, b, c, drow)
    return tuple(out) if save else out[0]


def _bwd_kernel(x_ref, dt_ref, dta_ref, b_ref, c_ref, d_ref, s_ref, dy_ref,
                dx_ref, ddt_ref, ddta_ref, db_ref, dc_ref, dd_ref,
                dstate, dye, xw, *, plan: SsdPlan):
    b, c = b_ref[...], c_ref[...]
    dtype, w, per_tile = b.dtype, plan.width, plan.per_tile
    q, r = plan.chunk, plan.per_group

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    k = _Chunk(dt_ref[...], dta_ref[...], plan)
    s_in, ds_out = s_ref[...], dstate[...]
    scores = jax.lax.dot_general(c, b, _ROWS, preferred_element_type=_F32)
    cs = jnp.dot(c, s_in.astype(dtype), preferred_element_type=_F32)
    g2 = jnp.dot(b, ds_out.astype(dtype), preferred_element_type=_F32)
    # <dS_out, S_in> of every lane, summed over the state
    kept = jnp.sum(ds_out * s_in, axis=0, keepdims=True)
    dscores = jnp.zeros((q, q), _F32)
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (1, r), 1)
    head_row = jax.lax.broadcasted_iota(jnp.int32, (r, 1), 0)
    ddt_cols = dl_cols = jnp.zeros((q, r), _F32)
    dl_rows = jnp.zeros((r, q), _F32)
    dl_last = jnp.zeros((r, 1), _F32)

    def lane_sum(v):
        return jnp.sum(v, axis=1, keepdims=True)

    for tile in range(plan.tiles):
        lanes = slice(tile * w, (tile + 1) * w)
        x_low, dy_low = x_ref[:, lanes], dy_ref[:, lanes]
        xt, dyt = x_low.astype(_F32), dy_low.astype(_F32)
        dts, to_end = k.spread(k.dt, tile), k.spread(k.to_end, tile)
        g2t = g2[:, lanes]
        dxd = to_end * g2t
        z_sums = []
        for i in range(per_tile):
            j = tile * per_tile + i
            decay = k.decay(j)
            m = scores * decay
            dyj = k.keep(dy_low, i)
            # dy_t . (dt_s x_s) over the head's lanes: its share of
            # d(C B^T), and through the decays of dL
            dm = jax.lax.dot_general(dyj, x_low, _ROWS,
                                     preferred_element_type=_F32
                                     ) * k.dt_rows[j:j + 1, :]
            dscores = dscores + dm * decay
            z = dm * m
            z_sums.append((lane_sum(z), jnp.sum(z, axis=0, keepdims=True)))
            # nonzero on the head's own lanes only
            dxd = dxd + jax.lax.dot_general(m.astype(dtype), dyj, _COLS,
                                            preferred_element_type=_F32)
        dx_ref[:, lanes] = (dxd * dts + d_ref[:, lanes] * dyt).astype(
            dx_ref.dtype)
        x_end = xt * dts * to_end
        dy_e = dyt * k.spread(k.exp_l, tile)
        # what the states' decays did with L: into the chunk's outputs
        # (u), and from each step to the chunk's end (v)
        u, v = dy_e * cs[:, lanes], x_end * g2t
        e_dt = dxd * xt
        kept_t = kept[:, lanes]
        for i, (row_z, col_z) in enumerate(z_sums):
            j = tile * per_tile + i
            v_col = lane_sum(k.keep(v, i))
            ddt_cols = jnp.where(head_lane == j, lane_sum(k.keep(e_dt, i)),
                                 ddt_cols)
            dl_cols = jnp.where(
                head_lane == j, row_z + lane_sum(k.keep(u, i)) - v_col,
                dl_cols)
            dl_rows = jnp.where(head_row == j, -col_z, dl_rows)
            dl_last = jnp.where(
                head_row == j, k.through[:, j:j + 1] * lane_sum(
                    k.keep(kept_t, i)) + jnp.sum(v_col, axis=0,
                                                 keepdims=True), dl_last)
        dye[:, lanes] = dy_e.astype(dtype)
        xw[:, lanes] = x_end.astype(dtype)
        dstate[:, lanes] = ds_out[:, lanes] * k.spread(k.through, tile)
    at_end = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    dl = jnp.transpose(dl_cols) + dl_rows + jnp.where(at_end, dl_last, 0.0)
    ddta_ref[...] = _running_sums(dl, reverse=True)
    ddt_ref[...] = jnp.transpose(ddt_cols)
    dscores = dscores.astype(dtype)
    dy_e, x_w = dye[...], xw[...]
    dc_ref[...] = (
        jnp.dot(dscores, b, preferred_element_type=_F32)
        + jax.lax.dot_general(dy_e, s_in.astype(dtype), _ROWS,
                              preferred_element_type=_F32)
    ).astype(dc_ref.dtype)
    db_ref[...] = (
        jax.lax.dot_general(dscores, c, _COLS, preferred_element_type=_F32)
        + jax.lax.dot_general(x_w, ds_out.astype(dtype), _ROWS,
                              preferred_element_type=_F32)
    ).astype(db_ref.dtype)
    dstate[...] += jax.lax.dot_general(c, dy_e, _COLS,
                                       preferred_element_type=_F32)
    dd_ref[...] += jnp.sum(dy_ref[...].astype(_F32)
                           * x_ref[...].astype(_F32), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("plan",))
def _backward(x, dtr, dtar, b, c, drow, states, dy, *, plan: SsdPlan):
    """``(dx, d dt rows, d(dt A) rows, dB, dC, dD spread (B, G, 1,
    r P))``; ``dt``'s is the part through ``x dt`` alone."""
    batch = x.shape[0]
    q, n, rp = plan.chunk, plan.state, plan.per_group * plan.head_dim
    s = _specs(plan, reverse=True)
    dd = pl.BlockSpec((None, None, 1, rp), lambda i, g, c: (i, g, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, plan=plan),
        grid=(batch, plan.groups, plan.chunks),
        in_specs=[s["lanes"], s["rows"], s["rows"], s["group"], s["group"],
                  s["skip"], s["states"], s["lanes"]],
        out_specs=[s["lanes"], s["rows"], s["rows"], s["group"], s["group"],
                   dd],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(dtr.shape, _F32),
                   jax.ShapeDtypeStruct(dtar.shape, _F32),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype),
                   jax.ShapeDtypeStruct((batch, plan.groups, 1, rp), _F32)],
        scratch_shapes=[pltpu.VMEM((n, rp), _F32),       # dS, carried
                        pltpu.VMEM((q, rp), x.dtype),    # dy exp(L)
                        pltpu.VMEM((q, rp), x.dtype)],   # xd exp(L_Q - L)
        compiler_params=_params(),
        interpret=plan.interpret,
        name=plan.name + "_bwd",
    )(x, dtr, dtar, b, c, drow, states, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd(x, dtr, dtar, b, c, drow, plan):
    return _forward(x, dtr, dtar, b, c, drow, plan=plan, save=False)


def _ssd_fwd(x, dtr, dtar, b, c, drow, plan):
    y, states = _forward(x, dtr, dtar, b, c, drow, plan=plan, save=True)
    return y, (x, dtr, dtar, b, c, drow, states)


def _ssd_bwd(plan, res, dy):
    dx, ddt, ddta, db, dc, dd = _backward(*res, dy, plan=plan)
    return dx, ddt, ddta, db, dc, dd.sum(0)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def _ssd_pallas(x, dt, a, b, c, d, plan: SsdPlan):
    """The kernels' layouts round ``_ssd``: ``dt`` and ``dt A`` as rows,
    ``D`` over its head's lanes; XLA's transposes of these finish
    ``ddt``, ``dA`` and ``dD``."""
    batch, t, h, p = x.shape
    g, n, r = plan.groups, plan.state, plan.per_group
    dtr = jnp.moveaxis(dt.astype(_F32), 1, 2).reshape(batch, g, r, t)
    dtar = dtr * a.astype(_F32).reshape(g, r, 1)
    drow = jnp.repeat(d.astype(_F32), p).reshape(g, 1, r * p)
    y = _ssd(x.reshape(batch, t, h * p), dtr, dtar,
             b.reshape(batch, t, g * n), c.reshape(batch, t, g * n), drow,
             plan)
    return y.reshape(batch, t, h, p)
