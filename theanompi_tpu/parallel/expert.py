"""Expert parallelism over the mesh's ``expert`` axis (switch-style MoE).

Beyond reference parity (the reference is data-parallel only,
SURVEY.md §2.11) — the fifth and last reserved mesh axis becomes real.
The canonical TPU pattern: experts are sharded over ``expert`` (each
shard owns ``E / ep`` expert FFNs, params stacked on a leading expert
axis ``P('expert')``), tokens are batch-sharded over data axes, and a
pair of ``lax.all_to_all`` collectives regroups tokens by expert and
back inside the jitted step.

Routing is top-1 (switch) with a fixed capacity per expert — static
shapes, as XLA requires: each token picks its argmax expert, tokens
beyond an expert's capacity are dropped (their combine weight is
zero), and the router is trained with the standard load-balancing
auxiliary loss (mean fraction routed x mean router probability, scaled
by E).

``routed_experts`` (PR 27) is the other expert layer: dropless, top-k,
and TOLD WHICH EXPERTS IT HOLDS.  It routes over every expert the
router knows, lays the tokens routed to its own experts out expert by
expert, multiplies each expert's rows by that expert's matrices in one
grouped product a projection (ops/grouped_matmul.py), and returns the
part of the layer's output that its own experts give; tokens routed
elsewhere get zero from it.  No capacity, no dropped token, no dense
one-hot, and nothing stands in for the chips that hold the other
experts: across an expert-parallel group the shares add up to the
whole layer (tests/test_routed_experts.py).  The capacity path above
stays for ``TransformerLM_MoE`` until that model moves over.

Layout of ``routed_experts``.  The ``n * top_k`` assignments are counted
per held expert (a cumulative sum, no sort), each expert's rows are
padded up to whole tiles of ``TILE_M`` and placed expert by expert in a
buffer; an empty expert keeps one tile of zero rows.  The buffer's rows
are copies of their tokens' rows of ``x``, pass through the expert's
grouped products (three, or two), and are summed back into the tokens.

* **The ladder** (PR 35).  A dropless layer on static shapes must be
  ready for every assignment: ``ceil(n * top_k / TILE_M) * TILE_M +
  count * TILE_M`` rows, 50 176 where a chip that holds 8 of 128
  experts sees about 1 700, and every XLA pass round the kernels walks
  the buffer's static size.  So the size is chosen on the chip, each
  call, from ``buffer_ladder``: the top rung is that worst case, a
  lower rung of twice the expected load exists where it is at most
  three quarters of it (7 168 / 50 176 there; 51 200 / 100 352 where
  16 of 64 experts are held, top-6 of 16 384 tokens; one rung, and no
  ``switch`` at all, where half of the experts are held).
  ``lax.switch`` on the tiles in use runs the smallest rung that holds
  them, from the placement map to the ``(n, d)`` output (``_rung``): no
  host round trip, no recompile, and never a dropped row.
* **The sum from the buffer's side.**  On a rung with fewer rows than
  there are assignments, a placed row knows its token and its weight,
  and the output is the sum of the rung's weighted rows into the
  tokens, in float32: on the kernels' path the Pallas kernel
  ``<name>_rows`` (ops/expert_rows.py) with the weight applied inside
  it (``_sum_weighted``), which is also the transpose of the gather
  that fills the buffer (``_move_rows``); off it (``ragged_dot``, the
  oracle) XLA's scatter-add, both ways.  On the top rung the buffer is the
  longer side, and each token gathers its ``top_k`` rows (``_take_rows``,
  whose transpose is a gather too).  Read on a TPU v5 lite at ``(n, d)``
  = (8 192, 2 688) bf16 (PR 35): summing 7 168 rows into the tokens takes
  1.09 ms as a scatter-add, 1.71 ms as a one-hot product on the MXU and
  2.53 ms as the gather of ``(n, 6, d)`` picks with its weighted sum;
  a scatter-add of 50 176 rows 6.0 ms.  A gather of 7 168 rows 0.27 ms,
  of 50 176 rows 1.3 ms.  On the same chip a whole ReGLU layer at
  (16 384, 2 560), top-6, on a rung of 51 200 rows takes 40.0 ms forward
  and backward summed from the buffer's side, 51.7 ms from the tokens'.
  The row kernel against the scatter-add there, weighted / not, the
  walk's tables included, under uniform routing: 51 200 rows of which
  24 542 held, d 2 560: 1.01 / 0.99 ms against 7.88 / 7.93; 14 336
  rows, 5 184 held, d 2 048: 0.44 / 0.45 against 1.65 / 1.66; 7 168
  rows, 3 042 held, d 2 688: 0.40 / 0.42 against 1.17 / 1.18.  It is
  faster at all three lower rungs that sum from the buffer's side, so
  it runs wherever its tables fit SMEM.
* **The ladder's own VJP** (``_ladder``).  JAX differentiates a
  conditional by making every branch return every branch's residuals,
  zero-filled where not taken: the top rung's buffers would be written
  as zeros each step.  The ladder keeps its operands alone and its
  backward pass is a second ``switch`` over ``jax.vjp`` of each rung.
"""

from __future__ import annotations

import functools
import logging
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from theanompi_tpu.ops import expert_rows, pallas_mode
from theanompi_tpu.ops.grouped_matmul import TILE_M, grouped_matmul
from theanompi_tpu.parallel.mesh import AXIS_EXPERT

PyTree = Any
_log = logging.getLogger(__name__)


@jax.custom_vjp
def _take_rows(x, idx, mask, inv_idx, inv_mask):
    """``where(mask, x[idx], 0)``: rows of ``x (R, d)`` picked by an
    index array of any shape.  The picks are a partial permutation
    whose inverse the caller knows, so the transpose is a gather too:
    row ``r`` of ``x`` receives the cotangent rows ``inv_idx[r, :]`` of
    the flattened output where ``inv_mask[r, :]``.  The form of a
    buffer with more rows than there are assignments: a scatter-add of
    50 176 rows of 2 688 takes 6.0 ms on a TPU v5 lite, of 7 168 rows
    1.09 ms (PR 35), which is why the lower rungs sum from the buffer's
    side (``_move_rows``) and the top one does not."""
    return jnp.where(mask[..., None], x[idx], 0).astype(x.dtype)


def _take_rows_fwd(x, idx, mask, inv_idx, inv_mask):
    return _take_rows(x, idx, mask, inv_idx, inv_mask), (inv_idx, inv_mask)


def _take_rows_bwd(res, g):
    inv_idx, inv_mask = res
    rows = g.reshape(-1, g.shape[-1])[inv_idx]         # (R, j, d)
    dx = jnp.where(inv_mask[..., None], rows, 0).sum(-2).astype(g.dtype)
    return dx, None, None, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _move_rows(a, token, placed, walk, n: int, to_buffer: bool, plan):
    """Rows between the tokens ``(n, d)`` and a buffer ``(R, d)`` whose
    placed row ``r`` belongs to token ``token[r]``, in ``a.dtype``.
    ``to_buffer``: ``where(placed, a[token], 0)``, a gather.  Else its
    transpose, ``out[t] = sum of the placed rows r with token[r] == t``,
    summed in float32: the row kernel where ``plan`` runs it (over the
    visits ``walk``), else a scatter-add.  The lower rungs' form; each
    direction is the other's VJP."""
    if to_buffer:
        return jnp.where(placed[:, None], a[token], 0).astype(a.dtype)
    if plan.pallas:
        return expert_rows.sum_rows(a, token, None, walk, plan,
                                    pallas_mode.interpret())
    out = jnp.zeros((n, a.shape[-1]), jnp.float32).at[
        jnp.where(placed, token, n)].add(a.astype(jnp.float32), mode="drop")
    return out.astype(a.dtype)


def _move_rows_fwd(a, token, placed, walk, n, to_buffer, plan):
    return (_move_rows(a, token, placed, walk, n, to_buffer, plan),
            (token, placed, walk))


def _move_rows_bwd(n, to_buffer, plan, res, g):
    token, placed, walk = res
    return (_move_rows(g, token, placed, walk, n, not to_buffer, plan),
            None, None, None)


_move_rows.defvjp(_move_rows_fwd, _move_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _sum_weighted(rows, weight, token, placed, walk, plan):
    """``out[t] = sum over the placed rows r with token[r] == t of
    f32(rows[r]) * weight[r]``, in float32 and then ``rows.dtype``: the
    row kernel with the weight applied inside it, so that the float32
    ``(R, d)`` weighted rows are never written.  Its VJP is the XLA
    program the unfused form differentiates to: the gather of the
    float32 cotangent, then the product's transpose."""
    return expert_rows.sum_rows(rows, token, weight, walk, plan,
                                pallas_mode.interpret())


def _sum_weighted_fwd(rows, weight, token, placed, walk, plan):
    return (_sum_weighted(rows, weight, token, placed, walk, plan),
            (rows, weight, token, placed))


def _sum_weighted_bwd(plan, res, g):
    rows, weight, token, placed = res
    mask = placed[:, None]
    picked = jnp.where(mask, g.astype(jnp.float32)[token], 0)
    d_rows = jnp.where(mask, (picked * weight[:, None].astype(jnp.float32))
                       .astype(rows.dtype), 0)
    d_weight = (picked * jnp.where(mask, rows, 0).astype(jnp.float32)).sum(-1)
    return d_rows, d_weight.astype(weight.dtype), None, None, None


_sum_weighted.defvjp(_sum_weighted_fwd, _sum_weighted_bwd)


def buffer_ladder(n_assign: int, count: int, n_experts: int) -> tuple:
    """The static sizes, in rows, a chip's buffer may take for
    ``n_assign`` assignments over ``n_experts`` experts of which it
    holds ``count``, smallest first.  The top rung holds every
    assignment (each expert's rows padded to whole tiles), so the layer
    is dropless whatever the router does; a lower rung of twice the
    expected load exists only where it is at most three quarters of the
    top one (at a held quarter the slack of a tile an expert puts it
    just past half)."""
    slack = count * TILE_M
    top = -(-n_assign // TILE_M) * TILE_M + slack
    twice = -(-2 * n_assign * count // n_experts)
    low = -(-twice // TILE_M) * TILE_M + slack
    return (low, top) if 4 * low <= 3 * top else (top,)


@functools.lru_cache(maxsize=None)
def _log_buffer_plan(name: str, rungs: tuple, plan) -> None:
    """The ladder is a pure function of the call's shape, so its plan
    is said once a shape (trace time only), as ``tile_plan`` says its
    own, and the row kernel's ``plan`` where a rung sums from the
    buffer's side; ``stats["buffer_rows"]`` counts which rung a step
    took."""
    _log.info("%s: expert buffer: rungs %s of %d-row tiles", name,
              " / ".join(str(r) for r in rungs), TILE_M)
    if plan:
        _log.info("%s", plan)


#: the gated form's activations, by ``routed_experts``' ``activation``
_GATE_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _rung(rows: int, top_k: int, impl: str, name: str, activation: str,
          plan, x, weights, expert_params, dest, here, tiles, tile_ends,
          *walk):
    """The layer from the placement map to the ``(n, d)`` output in a
    buffer of ``rows`` rows; ``dest (n * top_k,)`` is each assignment's
    buffer row where ``here``, ``tiles`` and ``tile_ends (count,)`` each
    held expert's tiles and their running sum.  ``plan``: the row
    kernel's plan where this rung sums from the buffer's side (else
    None), and ``walk`` its visits."""
    n, _ = x.shape
    count = tiles.shape[0]
    n_assign = n * top_k
    # the buffer row -> assignment map: a 1-D integer scatter
    src = jnp.full((rows,), n_assign, jnp.int32).at[
        jnp.where(here, dest, rows)].set(
        jnp.arange(n_assign, dtype=jnp.int32), mode="drop")
    placed = src < n_assign
    src = jnp.where(placed, src, 0)
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_ends, jnp.arange(rows // TILE_M),
                         side="right"), count - 1).astype(jnp.int32)
    n_tiles = tile_ends[-1]
    from_buffer = rows < n_assign      # sum over the shorter side

    if from_buffer:
        token = src // top_k
        buf = _move_rows(x, token, placed, walk, n, True, plan)
    else:
        dest_nk = dest.reshape(n, top_k)
        here_nk = here.reshape(n, top_k)
        buf = _take_rows(x, src // top_k, placed, dest_nk, here_nk)

    if impl == "pallas":
        def matmul(lhs, rhs, which):
            return grouped_matmul(lhs, rhs.astype(lhs.dtype), tile_group,
                                  n_tiles, f"{name}_{which}",
                                  pallas_mode.interpret())
    else:
        padded_sizes = tiles * TILE_M

        def matmul(lhs, rhs, which):
            del which
            return lax.ragged_dot(lhs, rhs.astype(lhs.dtype), padded_sizes)

    if "gate" in expert_params:
        gate = matmul(buf, expert_params["gate"], "gate")
        hidden = _GATE_ACTIVATIONS[activation](gate) * matmul(
            buf, expert_params["up"], "up")
    else:
        hidden = jnp.square(jax.nn.relu(
            matmul(buf, expert_params["up"], "up")))
    out_rows = matmul(hidden, expert_params["down"], "down")
    if from_buffer:
        # a placed row knows its token and its weight; rows past
        # ``n_tiles`` are undefined: masked before anything is scaled
        weight = weights.reshape(-1)[src]
        if plan.pallas:
            return _sum_weighted(out_rows, weight, token, placed, walk,
                                 plan).astype(x.dtype)
        weighted = (jnp.where(placed[:, None], out_rows, 0)
                    .astype(jnp.float32) * weight[:, None])
        return _move_rows(weighted, token, placed, walk, n, False,
                          plan).astype(x.dtype)
    picked = _take_rows(out_rows, dest_nk, here_nk, src[:, None],
                        placed[:, None])                       # (n, k, d)
    return (picked * weights[..., None].astype(picked.dtype)).sum(1)


def _ladder(rungs, index, operands):
    """``rungs[index](*operands)``, differentiated by hand: ``operands``
    is ``(x, weights, expert_params, *integer maps)`` and only the
    first three carry gradients.  JAX's own rule for a conditional
    makes every branch return every branch's residuals, zero-filled for
    the branches not taken, which would write the top rung's buffers
    (over 1 GB a layer at 50 176 rows) as zeros each step.  Here the
    residuals are the operands themselves, which depend on no rung, and
    the backward pass is a second ``switch`` whose branch runs
    ``jax.vjp`` of that rung's body (its forward again, at the rung's
    size).  Under ``nn.remat`` the recomputed forward is then dead code,
    so the expert products run as often as before."""
    @jax.custom_vjp
    def run(index, *operands):
        return lax.switch(index, rungs, *operands)

    def forward(index, *operands):
        return run(index, *operands), (index, operands)

    def backward(res, g):
        index, operands = res
        carried, maps = operands[:3], operands[3:]

        def pull(rung):
            def branch(g, carried, maps):
                def body(*carried):
                    # JAX renames the first scope under a transformation
                    # to ``jvp(...)``: this one, and not a kernel's
                    # ``name=``, by which the trace's readers find it
                    with jax.named_scope("rung"):
                        return rung(*carried, *maps)
                return jax.vjp(body, *carried)[1](g)
            return branch

        grads = lax.switch(index, [pull(rung) for rung in rungs], g,
                           carried, maps)
        return (None, *grads, *(None for _ in maps))

    run.defvjp(forward, backward)
    return run(index, *operands)


def routed_experts(x: jax.Array, probs: jax.Array, expert_params: PyTree,
                   held: tuple[int, int], top_k: int = 1,
                   select_by: jax.Array | None = None,
                   impl: str | None = None, name: str = "routed_experts",
                   normalize: bool = False, scale: float = 1.0,
                   activation: str = "silu"):
    """This chip's part of a dropless top-k expert layer.

    ``x (n, d)`` tokens; ``probs (n, E)`` the router's scores (softmax
    probabilities, sigmoids) over ALL ``E`` experts; ``held = (first,
    count)``: this chip holds experts ``first .. first + count - 1``,
    and ``expert_params`` are theirs alone.  Their keys say what an
    expert is: ``gate`` and ``up`` ``(count, d, f)`` with ``down``
    ``(count, f, d)`` a gated MLP, ``(act(x gate) * (x up)) down`` with
    ``act`` the ``activation`` named (``"silu"``, the default, or
    ``"relu"``: ReGLU); ``up`` and ``down`` alone a two-matrix MLP with
    a squared ReLU between them, ``relu(x up)^2 down``.  Each token goes to its
    ``top_k`` experts weighted by their scores: as they are, or with
    ``normalize`` divided by their sum over ALL the token's chosen
    experts, held here or not (``w = p / (sum of the k chosen p +
    1e-20)``), and in either case times ``scale``.  ``select_by (n,
    E)``, where given, is what the top-k is taken over in place of
    ``probs`` (a balancing bias moves the choice and not the weight).
    Returns ``(out, stats)``: ``out (n, d)`` is
    ``sum over a token's chosen experts HELD HERE of w_e * expert_e(x)``
    and zero for a token none of whose experts is held; ``stats`` counts
    the rows this chip multiplied (``held_rows``), the assignments that
    went elsewhere (``rows_elsewhere``), the fullest held expert's
    rows (``max_expert_rows``) and the rows of the buffer they were
    laid out in (``buffer_rows``: the rung taken), float32 scalars, and
    gives every expert's assignments, held or not (``expert_load
    (E,)``: what a balancing controller steers by).

    Layout: see the module docstring.

    ``impl``: ``'pallas'`` (the kernels; default on a TPU, interpreted
    on the CPU platform when forced) or ``'ragged_dot'``
    (``jax.lax.ragged_dot`` over the same layout: the oracle, default
    elsewhere).
    """
    n, d = x.shape
    first, count = held
    n_experts = probs.shape[-1]
    if not 0 <= first <= first + count <= n_experts:
        raise ValueError(f"held experts {first}..{first + count - 1} are "
                         f"not among the router's {n_experts}")
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "ragged_dot"
    if impl not in ("pallas", "ragged_dot"):
        raise ValueError(f"unknown expert matmul impl {impl!r}")
    if activation not in _GATE_ACTIVATIONS or (
            activation != "silu" and "gate" not in expert_params):
        raise ValueError(f"activation {activation!r}: the gated form takes "
                         f"one of {sorted(_GATE_ACTIVATIONS)}")

    if select_by is None:
        weights, chosen = lax.top_k(probs, top_k)              # (n, k)
    else:
        chosen = lax.top_k(select_by, top_k)[1]
        weights = jnp.take_along_axis(probs, chosen, axis=-1)
    if normalize:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    if scale != 1.0:
        weights = weights * scale
    local = chosen.reshape(-1) - first                         # (n*k,)
    here = (local >= 0) & (local < count)
    # one-hot over the HELD experts only: (n*k, count), all-false rows
    # for assignments that went elsewhere
    onehot = (local[:, None] == jnp.arange(count)[None, :])
    sizes = onehot.sum(0, dtype=jnp.int32)                     # (count,)
    rank = ((jnp.cumsum(onehot, axis=0, dtype=jnp.int32) - 1)
            * onehot).sum(-1)                    # place within its expert
    tiles = jnp.maximum(-(-sizes // TILE_M), 1)    # an empty expert: one
    tile_ends = jnp.cumsum(tiles)
    starts = (tile_ends - tiles) * TILE_M          # each expert's first row
    n_assign = n * top_k
    rungs = buffer_ladder(n_assign, count, n_experts)
    # the lower rung, where it has fewer rows than there are assignments
    # (the top rung holds them all), sums from the buffer's side
    low = rungs[0] if rungs[0] < n_assign else None
    plan = (expert_rows.row_plan(n, low, count, name, impl == "pallas")
            if low else None)
    _log_buffer_plan(name, rungs, plan)
    dest = jnp.where(here, starts[jnp.clip(local, 0, count - 1)] + rank, 0)
    walk = ()
    if plan and plan.pallas:
        walk = expert_rows.visits(*expert_rows.block_ranges(
            onehot, starts, top_k, plan), plan)
    operands = (x, weights, expert_params, dest, here, tiles, tile_ends,
                *walk)
    bodies = [functools.partial(_rung, rows, top_k, impl, name, activation,
                                plan if rows == low else None)
              for rows in rungs]
    if len(rungs) == 1:
        out = bodies[0](*operands)
        buffer_rows = jnp.float32(rungs[0])
    else:
        # the smallest rung that holds the tiles in use
        index = (tile_ends[-1] * TILE_M
                 > jnp.asarray(rungs[:-1], jnp.int32)).sum()
        out = _ladder(bodies, index, operands)
        buffer_rows = jnp.asarray(rungs, jnp.float32)[index]
    held_rows = sizes.sum()
    stats = {"held_rows": held_rows.astype(jnp.float32),
             "rows_elsewhere": (n_assign - held_rows).astype(jnp.float32),
             "max_expert_rows": sizes.max().astype(jnp.float32),
             "buffer_rows": buffer_rows,
             "expert_load": (chosen.reshape(-1, 1) == jnp.arange(n_experts)
                             ).sum(0, dtype=jnp.float32)}
    return out.astype(x.dtype), stats


def top1_dispatch(router_logits: jax.Array, capacity: int):
    """Build switch-routing dispatch/combine tensors for one shard.

    ``router_logits``: (n_tokens, E).  Returns
    ``dispatch`` (E, capacity, n_tokens) one-hot — token t is slot s of
    expert e; ``combine`` (n_tokens, E, capacity) — router-prob weights
    (zero for dropped tokens); and the load-balancing aux loss.
    """
    n, e = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)            # (n,)
    expert_prob = jnp.max(probs, axis=-1)              # (n,)

    # position of each token within its expert's queue
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.int32)   # (n, E)
    position = jnp.cumsum(onehot, axis=0) * onehot - 1        # (n, E)
    pos_in_expert = position.max(axis=-1)                     # (n,)
    keep = pos_in_expert < capacity

    # aux loss (Switch Transformer eq. 4): E * mean(frac_tokens) . mean(prob)
    frac_tokens = onehot.astype(jnp.float32).mean(axis=0)
    frac_probs = probs.mean(axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)

    slot = jnp.where(keep, pos_in_expert, 0)
    dispatch = (
        jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)[:, :, None]
        * jax.nn.one_hot(slot, capacity, dtype=jnp.float32)[:, None, :]
        * keep[:, None, None]
    )                                                   # (n, E, capacity)
    combine = dispatch * expert_prob[:, None, None]
    return jnp.moveaxis(dispatch, 0, -1), combine, aux  # (E, cap, n), ...


def moe_ffn(x: jax.Array, router_kernel: jax.Array, expert_params: PyTree,
            apply_expert, capacity_factor: float = 1.25,
            axis_name: str | None = AXIS_EXPERT):
    """Switch-MoE FFN over tokens ``x`` (n_tokens, d).

    ``expert_params`` leaves carry a leading LOCAL-expert axis (E/ep
    per shard when ``axis_name`` is a real mesh axis; E when None or
    inside a size-1 axis).  ``apply_expert(params_e, tokens) -> out``
    applies one expert FFN; it is vmapped over local experts.

    With expert parallelism the dispatched tokens cross shards via
    ``all_to_all`` (tokens -> owning expert's shard) and return the
    same way; XLA schedules both on ICI.  Returns (out, aux_loss).
    """
    n, d = x.shape
    ep = lax.axis_size(axis_name) if axis_name is not None else 1
    e_local = jax.tree.leaves(expert_params)[0].shape[0]
    e = e_local * ep
    capacity = max(1, int(capacity_factor * n / e))

    router_logits = x.astype(jnp.float32) @ router_kernel  # (n, E)
    dispatch, combine, aux = top1_dispatch(router_logits, capacity)

    # tokens for every expert, gathered from this shard: (E, cap, d)
    expert_in = jnp.einsum("ecn,nd->ecd", dispatch, x.astype(jnp.float32))

    if ep > 1:
        # outbound: shard j receives, from every source shard s, the
        # (e_local, cap, d) block of tokens routed to ITS experts —
        # result (ep[source], e_local, cap, d) -> (e_local, ep*cap, d)
        expert_in = expert_in.reshape(ep, e_local, capacity, d)
        expert_in = lax.all_to_all(expert_in, axis_name, split_axis=0,
                                   concat_axis=0, tiled=False)
        expert_in = jnp.moveaxis(expert_in, 0, 1)  # (E_local, ep, cap, d)
        expert_in = expert_in.reshape(e_local, ep * capacity, d)
    # apply this shard's experts
    expert_out = jax.vmap(apply_expert)(expert_params, expert_in)
    if ep > 1:
        # return trip (exact mirror): send each source shard its token
        # slots back; dim0 of the result indexes the expert-owner
        # shard, so reshaping restores the global (E, cap, d) layout
        expert_out = expert_out.reshape(e_local, ep, capacity, d)
        expert_out = jnp.moveaxis(expert_out, 1, 0)  # (ep, E_local, cap, d)
        expert_out = lax.all_to_all(expert_out, axis_name, split_axis=0,
                                    concat_axis=0, tiled=False)
        expert_out = expert_out.reshape(e, capacity, d)
    out = jnp.einsum("nec,ecd->nd", combine, expert_out)
    return out.astype(x.dtype), aux


def make_moe_train_step(
    loss_fn,
    tx,
    mesh,
    state_specs: PyTree,
    expert_mask: PyTree,
    batch_partition=None,
    data_axis: str = "data",
    expert_axis: str = AXIS_EXPERT,
    donate: bool = True,
    grad_scale: float = 1.0,
):
    """shard_map training step for an expert-parallel model.

    The batch is sharded over BOTH ``(data, expert)`` — for non-MoE
    layers the expert axis is just more data parallelism — so grads of
    replicated params are pmean-ed over both axes, while leaves where
    ``expert_mask`` is True (the expert FFN stacks, sharded
    ``P('expert')``) already saw every token routed to them via the
    all_to_all and are pmean-ed over ``data`` only.
    """
    from jax.sharding import PartitionSpec as P

    from theanompi_tpu.parallel.bsp import apply_update, grad_and_metrics

    if batch_partition is None:
        batch_partition = P((data_axis, expert_axis))

    def shard_step(state, batch, rng):
        for ax in (data_axis, expert_axis):
            rng = jax.random.fold_in(rng, lax.axis_index(ax))
        grads, new_ms, metrics = grad_and_metrics(
            loss_fn, state.params, state.model_state, batch, rng)
        # expert leaves: the all_to_all TRANSPOSE already accumulated
        # every expert-axis shard's cotangent onto the owning shard (a
        # SUM over the axis, where replicated params get a per-shard
        # local grad) — divide by ep so expert grads live on the same
        # global-mean-loss scale as everything else, then average the
        # data replicas.  Non-expert leaves: plain mean over both axes.
        ep = lax.axis_size(expert_axis)
        grads = jax.tree.map(
            lambda g, is_exp: (
                lax.pmean(g, data_axis) / ep if is_exp
                else lax.pmean(g, (data_axis, expert_axis))),
            grads, expert_mask)
        if grad_scale != 1.0:  # reference 'cdd' sum-mode exchange
            grads = jax.tree.map(lambda g: g * grad_scale, grads)
        metrics = jax.tree.map(
            lambda x: lax.pmean(x, (data_axis, expert_axis)), metrics)
        return apply_update(tx, state, grads, new_ms), metrics

    sharded = jax.shard_map(
        shard_step,
        mesh=mesh,
        in_specs=(state_specs, batch_partition, P()),
        out_specs=(state_specs, P()),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def make_moe_eval_step(
    eval_fn,
    mesh,
    state_specs: PyTree,
    batch_partition=None,
    data_axis: str = "data",
    expert_axis: str = AXIS_EXPERT,
):
    from jax.sharding import PartitionSpec as P

    if batch_partition is None:
        batch_partition = P((data_axis, expert_axis))

    def shard_step(state, batch):
        metrics = eval_fn(state.params, state.model_state, batch)
        return jax.tree.map(
            lambda x: lax.pmean(x, (data_axis, expert_axis)), metrics)

    sharded = jax.shard_map(
        shard_step,
        mesh=mesh,
        in_specs=(state_specs, batch_partition),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(sharded)
