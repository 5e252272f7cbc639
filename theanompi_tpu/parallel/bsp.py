"""BSP training as a single SPMD program.

In the reference, BSP was a subsystem: each rank ran ``train_iter()``
then called ``BSP_Exchanger.exchange()`` to allreduce gradients over
MPI/NCCL (reference layout ``theanompi/lib/exchanger.py`` + the BSP
worker module; SURVEY.md §2.3–§2.4, §3.2 — mount empty, no file:line).

On TPU, BSP is a compiler annotation: one jitted step, ``shard_map``-ped
over the ``data`` axis of a mesh, with the exchange traced inside it as
``psum``.  XLA schedules the ICI collectives and overlaps them with the
backward pass — the calc/comm overlap the reference could only
approximate with multi-stream tricks falls out of the compiler.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import PartitionSpec as P

from theanompi_tpu.parallel.exchanger import SCOPE_EXCHANGE, BSP_Exchanger
from theanompi_tpu.parallel.mesh import AXIS_DATA

PyTree = Any

# loss_fn(params, model_state, batch, rng) -> (loss, (new_model_state, metrics))
LossFn = Callable[[PyTree, PyTree, PyTree, jax.Array], tuple[jax.Array, tuple]]


@struct.dataclass
class TrainState:
    """Replicated training state (params + optimizer + mutable model
    collections such as BN batch_stats).

    ``exchange_residual`` is the bf16-exchange error-feedback buffer
    (``BSP_Exchanger.exchange_with_residual``): a per-shard f32 tree
    carried with a LEADING data-shard axis — leaf shape
    ``(n_data, *param_shape)`` globally, sharded ``P('data')``, seen
    as ``(1, *param_shape)`` inside the shard body.  It is per-shard
    state (each shard's quantization error differs), which is why it
    cannot ride the replicated part of the tree; ``None`` (the
    default, an empty subtree) keeps the pytree leaf set — and
    therefore every existing checkpoint — unchanged when the feature
    is off."""

    step: jax.Array
    params: PyTree
    opt_state: PyTree
    model_state: PyTree
    exchange_residual: PyTree = None

    @classmethod
    def create(cls, params, tx: optax.GradientTransformation, model_state=None):
        return cls(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=tx.init(params),
            model_state={} if model_state is None else model_state,
        )


#: the step's own tail in a device trace (docs/OBSERVABILITY.md
#: "Device-trace names"): the optimizer update here, the gradient
#: exchange with the cross-replica means under ``SCOPE_EXCHANGE``.
#: Forward and backward need no scope: JAX's transforms say which is
#: which (monitor/scopes.py ``parse``)
SCOPE_UPDATE = "bsp/update"


def _pmean(tree: PyTree, axes=(AXIS_DATA,)) -> PyTree:
    with jax.named_scope(SCOPE_EXCHANGE):
        return jax.tree.map(lambda x: jax.lax.pmean(x, axes), tree)


def grad_and_metrics(loss_fn: LossFn, params, model_state, batch, rng):
    """Shared step-front: value_and_grad + metrics normalization.
    Used by every step builder (bsp/tensor/pipeline) so the core stays
    in one place; the builders differ only in which collectives wrap
    the results."""
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    (loss, (new_ms, metrics)), grads = grad_fn(params, model_state, batch,
                                               rng)
    metrics = dict(metrics)
    metrics.setdefault("loss", loss)
    return grads, new_ms, metrics


def apply_update(tx: optax.GradientTransformation, state: "TrainState",
                 grads, new_ms) -> "TrainState":
    """Shared step-tail: optimizer update + TrainState rebuild.  Its
    device ops carry the scope ``bsp/update`` (monitor/scopes.py)."""
    with jax.named_scope(SCOPE_UPDATE):
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
    return TrainState(step=state.step + 1, params=new_params,
                      opt_state=new_opt, model_state=new_ms,
                      exchange_residual=state.exchange_residual)


def _default_exchanger(exchanger: BSP_Exchanger | None,
                       reduce_axes: tuple[str, ...]) -> BSP_Exchanger:
    return exchanger or BSP_Exchanger(
        axis=reduce_axes if len(reduce_axes) > 1 else reduce_axes[0])


def _fold_axis_rng(rng, reduce_axes: tuple[str, ...]):
    """Decorrelate per-shard randomness (dropout, augment draws)."""
    for ax in reduce_axes:
        rng = jax.random.fold_in(rng, jax.lax.axis_index(ax))
    return rng


def _donate_argnums(donate: bool, donate_batch: bool) -> tuple[int, ...]:
    """argnums for the stacked-cadence steps: state (0) and optionally
    the staged batch (1).  The r3/r4 copy account charges 2.37 ms/step
    to 1 334 copy-done events; keeping a multi-megabyte staged batch
    alive across the whole scanned program forces XLA to copy around
    it, so the cadences donate it by default — the prefetcher stages a
    fresh batch per dispatch and never touches one after yielding it.
    ``donate_batch`` exists for callers that deliberately replay one
    staged batch (the equivalence tests that re-feed a stacked batch to
    a second step builder; a device-resident replay cell, PERF.md §7
    row c)."""
    if not donate:
        return ()
    return (0, 1) if donate_batch else (0,)


def state_partition_spec(residual_axis: str = AXIS_DATA) -> "TrainState":
    """TrainState-shaped PartitionSpec tree for the shard_map step
    builders: everything replicated EXCEPT the error-feedback residual,
    whose leading axis is sharded over ``residual_axis``.  Each field's
    spec is a pytree PREFIX, so this one tree covers both the
    residual-off case (``None`` — empty subtree under the prefix) and
    the residual-on case (every leaf split on its shard axis)."""
    return TrainState(step=P(), params=P(), opt_state=P(),
                      model_state=P(),
                      exchange_residual=P(residual_axis))


def init_exchange_residual(params: PyTree, n_shards: int) -> PyTree:
    """Zero residual with the leading shard axis, host-side; the caller
    places it (``P('data')`` on the leading axis)."""
    import numpy as np

    return jax.tree.map(
        lambda p: np.zeros((n_shards,) + tuple(p.shape), np.float32),
        params)


def _exchange_grads_and_update(exchanger: BSP_Exchanger,
                               tx: optax.GradientTransformation,
                               state: "TrainState", grads, new_ms,
                               reduce_axes) -> "TrainState":
    """Shared grads-mode tail: BN-stat pmean + exchange + update.
    Used by the single/multi-step grads branch AND the accum step so
    exchange semantics live in one place."""
    new_ms = _pmean(new_ms, reduce_axes)
    if exchanger.error_feedback:
        if state.exchange_residual is None:
            raise ValueError(
                "error_feedback needs state.exchange_residual "
                "(init_exchange_residual; models/base.py builds it from "
                "ModelConfig.exchange_error_feedback)")
        # residual leaves arrive per-shard as (1, *shape) — the leading
        # axis is the data-shard axis the spec splits
        with jax.named_scope(SCOPE_EXCHANGE):
            res = jax.tree.map(lambda r: r[0], state.exchange_residual)
            grads, new_res = exchanger.exchange_with_residual(grads, res)
        new_state = apply_update(tx, state, grads, new_ms)
        return new_state.replace(
            exchange_residual=jax.tree.map(lambda r: r[None], new_res))
    with jax.named_scope(SCOPE_EXCHANGE):
        grads = exchanger.exchange(grads)
    return apply_update(tx, state, grads, new_ms)


def _make_shard_step(
    loss_fn: LossFn,
    tx: optax.GradientTransformation,
    exchanger: BSP_Exchanger | None,
    reduce_axes: tuple[str, ...],
):
    """The per-shard training step body (one iteration): fwd + bwd +
    exchange + update + cross-replica syncs.  Shared by the single-step
    and the scanned multi-step builders."""
    exchanger = _default_exchanger(exchanger, reduce_axes)

    def bucketed_step(state: TrainState, batch, rng):
        # exchange_buckets > 1 grads path: the per-bucket collectives
        # are embedded in the backward DAG (exchanger.backward_exchange
        # boundary tags), so grads come back ALREADY exchanged — the
        # step tail is just BN-stat pmean + optimizer update
        res = None
        if exchanger.error_feedback:
            if state.exchange_residual is None:
                raise ValueError(
                    "error_feedback needs state.exchange_residual "
                    "(init_exchange_residual; models/base.py builds it "
                    "from ModelConfig.exchange_error_feedback)")
            res = jax.tree.map(lambda r: r[0], state.exchange_residual)
        loss, (new_ms, metrics), grads, new_res = (
            exchanger.backward_exchange(loss_fn, state.params,
                                        state.model_state, batch, rng,
                                        residual=res))
        metrics = dict(metrics)
        metrics.setdefault("loss", loss)
        new_ms = _pmean(new_ms, reduce_axes)
        new_state = apply_update(tx, state, grads, new_ms)
        if new_res is not None:
            new_state = new_state.replace(
                exchange_residual=jax.tree.map(lambda r: r[None],
                                               new_res))
        return new_state, _pmean(metrics, reduce_axes)

    def shard_step(state: TrainState, batch, rng):
        rng = _fold_axis_rng(rng, reduce_axes)
        if (exchanger.exchange_what == "grads"
                and exchanger.exchange_buckets > 1):
            return bucketed_step(state, batch, rng)
        grads, new_ms, metrics = grad_and_metrics(
            loss_fn, state.params, state.model_state, batch, rng)

        if exchanger.exchange_what == "grads":
            new_state = _exchange_grads_and_update(
                exchanger, tx, state, grads, new_ms, reduce_axes)
        else:  # 'params': local update, then allreduce parameters
            # Cross-replica sync of mutable collections (BN stats):
            # each shard saw a different micro-batch; average them.
            new_ms = _pmean(new_ms, reduce_axes)
            new_state = apply_update(tx, state, grads, new_ms)
            avg_exch = (
                exchanger if exchanger.avg
                else dataclasses.replace(exchanger, avg=True)
            )
            with jax.named_scope(SCOPE_EXCHANGE):
                new_params = avg_exch.exchange(new_state.params)
            new_state = new_state.replace(
                params=new_params,
                # Momentum buffers live per-shard in 'params' mode;
                # average them too so state stays replicated (matches
                # the reference's param-averaging BSP semantics closely
                # enough, and keeps the SPMD invariant that state is
                # identical on every shard).
                opt_state=_pmean(new_state.opt_state, reduce_axes),
            )

        return new_state, _pmean(metrics, reduce_axes)

    return shard_step


def make_bsp_train_step(
    loss_fn: LossFn,
    tx: optax.GradientTransformation,
    mesh: jax.sharding.Mesh,
    exchanger: BSP_Exchanger | None = None,
    donate: bool = True,
    batch_partition: P = P(AXIS_DATA),
    reduce_axes: tuple[str, ...] = (AXIS_DATA,),
):
    """Build the jitted SPMD training step.

    Returns ``step(state, batch, rng) -> (state, metrics)`` where
    ``state`` is replicated over the mesh, ``batch`` is a pytree whose
    arrays are sharded by ``batch_partition`` (default: leading dim
    over the ``data`` axis; a sequence-parallel step passes
    ``P('data', 'seq')`` with ``reduce_axes=('data', 'seq')``), and
    ``rng`` is a replicated key (folded per-shard inside for dropout
    decorrelation).
    """
    shard_step = _make_shard_step(loss_fn, tx, exchanger, reduce_axes)
    st = state_partition_spec()
    sharded = jax.shard_map(
        shard_step,
        mesh=mesh,
        in_specs=(st, batch_partition, P()),
        out_specs=(st, P()),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def make_bsp_multi_step(
    loss_fn: LossFn,
    tx: optax.GradientTransformation,
    mesh: jax.sharding.Mesh,
    exchanger: BSP_Exchanger | None = None,
    donate: bool = True,
    donate_batch: bool = True,
    batch_partition: P = P(AXIS_DATA),
    reduce_axes: tuple[str, ...] = (AXIS_DATA,),
):
    """``lax.scan`` several training iterations into ONE device program.

    Returns ``multi_step(state, stacked_batch, rng) -> (state, metrics)``
    where ``stacked_batch`` arrays carry a leading steps axis ``k`` (the
    per-step batch axis behind it, sharded by ``batch_partition``) and
    ``metrics`` leaves come back stacked ``(k,)``.

    Why: each jitted execution pays a host dispatch; one program per
    k batches amortizes it k-fold.  Inside the scan each
    sub-step is the SAME program as ``make_bsp_train_step`` builds —
    grads psum-ed per sub-step, optimizer applied per sub-step — so the
    training trajectory is identical to k separate calls with rngs
    ``fold_in(rng, i)``.
    """
    single = _make_shard_step(loss_fn, tx, exchanger, reduce_axes)

    def shard_multi(state: TrainState, stacked, rng):
        def body(carry, xs):
            i, batch = xs
            new_state, metrics = single(carry, batch,
                                        jax.random.fold_in(rng, i))
            return new_state, metrics

        k = jax.tree.leaves(stacked)[0].shape[0]
        state, metrics = jax.lax.scan(
            body, state, (jnp.arange(k), stacked))
        return state, metrics

    stacked_partition = P(None, *batch_partition)
    st = state_partition_spec()
    sharded = jax.shard_map(
        shard_multi,
        mesh=mesh,
        in_specs=(st, stacked_partition, P()),
        out_specs=(st, P()),
        check_vma=False,
    )
    return jax.jit(sharded,
                   donate_argnums=_donate_argnums(donate, donate_batch))


def accumulate_microbatch_grads(loss_fn: LossFn, params, model_state,
                                stacked, rng, init_gsum, add_grads):
    """Shared accumulation scan for the grad-accum cadences (plain
    and ZeRO): threads model_state through ``a`` microbatches with
    per-microbatch rng folds, combining grads via ``add_grads(gsum,
    grads_tree)``.  Returns (new_model_state, gsum, metrics_mean, a) —
    the cadence semantics live HERE so the two step builders cannot
    diverge."""
    a = jax.tree.leaves(stacked)[0].shape[0]

    def body(carry, xs):
        ms, gsum = carry
        i, mb = xs
        grads, ms, metrics = grad_and_metrics(
            loss_fn, params, ms, mb, jax.random.fold_in(rng, i))
        return (ms, add_grads(gsum, grads)), metrics

    (ms, gsum), metrics = jax.lax.scan(
        body, (model_state, init_gsum), (jnp.arange(a), stacked))
    metrics = jax.tree.map(lambda m: m.mean(axis=0), metrics)
    return ms, gsum, metrics, a


def make_bsp_accum_step(
    loss_fn: LossFn,
    tx: optax.GradientTransformation,
    mesh: jax.sharding.Mesh,
    exchanger: BSP_Exchanger | None = None,
    donate: bool = True,
    donate_batch: bool = True,
    batch_partition: P = P(AXIS_DATA),
    reduce_axes: tuple[str, ...] = (AXIS_DATA,),
):
    """Gradient accumulation: ``a`` microbatches → ONE optimizer update.

    Returns ``accum_step(state, stacked_batch, rng) -> (state, metrics)``
    where ``stacked_batch`` arrays carry a leading microbatch axis ``a``
    (per-microbatch batch axis behind it, sharded by
    ``batch_partition``) and metrics come back averaged over the ``a``
    microbatches.  Grads are averaged across microbatches locally, then
    exchanged ONCE — so the effective global batch is
    ``a * global_batch`` at the HBM footprint of one microbatch, and
    the per-update ICI traffic of plain BSP.  Mean-of-means equals the
    big-batch gradient exactly for equal microbatch sizes (tested).

    Mutable model collections (BN batch_stats) thread through the scan
    per-microbatch, matching what a sequential big-batch pass would do
    step-wise.  ``exchange_what='params'`` has no well-defined
    accumulation semantics and is rejected.
    """
    exchanger = _default_exchanger(exchanger, reduce_axes)
    if exchanger.exchange_what != "grads":
        raise ValueError("gradient accumulation requires "
                         "exchange_what='grads' (param-averaging per "
                         "microbatch has no accumulation semantics)")

    def shard_accum(state: TrainState, stacked, rng):
        rng = _fold_axis_rng(rng, reduce_axes)
        gz = jax.tree.map(jnp.zeros_like, state.params)
        new_ms, gsum, metrics, a = accumulate_microbatch_grads(
            loss_fn, state.params, state.model_state, stacked, rng,
            gz, lambda gsum, g: jax.tree.map(jnp.add, gsum, g))
        grads = jax.tree.map(lambda g: g / a, gsum)

        new_state = _exchange_grads_and_update(
            exchanger, tx, state, grads, new_ms, reduce_axes)
        return new_state, _pmean(metrics, reduce_axes)

    stacked_partition = P(None, *batch_partition)
    st = state_partition_spec()
    sharded = jax.shard_map(
        shard_accum,
        mesh=mesh,
        in_specs=(st, stacked_partition, P()),
        out_specs=(st, P()),
        check_vma=False,
    )
    return jax.jit(sharded,
                   donate_argnums=_donate_argnums(donate, donate_batch))


def make_bsp_eval_step(
    eval_fn: Callable[[PyTree, PyTree, PyTree], dict],
    mesh: jax.sharding.Mesh,
    batch_partition: P = P(AXIS_DATA),
    reduce_axes: tuple[str, ...] = (AXIS_DATA,),
):
    """Build the jitted SPMD eval step.

    ``eval_fn(params, model_state, batch) -> metrics`` runs per shard;
    metrics are pmean-ed over the reduce axes (the reference allreduced
    val metrics the same way, SURVEY.md §3.5).
    """

    def shard_step(state: TrainState, batch):
        metrics = eval_fn(state.params, state.model_state, batch)
        return _pmean(metrics, reduce_axes)

    sharded = jax.shard_map(
        shard_step,
        mesh=mesh,
        in_specs=(P(), batch_partition),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(sharded)
