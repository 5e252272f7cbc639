"""ZeRO-1 data parallelism: optimizer state sharded over ``data``.

Plain BSP replicates the optimizer state (momentum, adam moments) on
every data shard — for a model with P parameters and an optimizer with
m state slots, each chip holds m*P floats it only ever reads 1/N of
usefully.  ZeRO-1 shards that state over the data axis:

    grads  --psum_scatter-->  1/N grad shard        (reduce_scatter)
    update on the 1/N param/opt shard               (compute saved too)
    params --all_gather-->    full replicated tree

Same collective volume as one psum (reduce_scatter + all_gather IS the
ring allreduce, just with the update between the halves), identical
update math for elementwise optimizers (sgd/momentum/adam/adamw/
rmsprop — proven step-equal to plain BSP in tests), and m*P/N
optimizer memory per chip.  LARS is layerwise, not elementwise, so it
is rejected (a flat shard has no layer boundaries) — enforced at the
config layer (models/base.py compile_iter_fns); direct callers of this
module must likewise pass an elementwise optimizer.

**Bucketed exchange (ISSUE 13).**  ``exchange_buckets=B`` cuts the
flatten-order leaves into B layer-ordered, byte-balanced buckets
(``parallel/exchanger.bucket_ranges`` — the same pure plan every rank
derives) and the flat gradient vector becomes B per-bucket segments,
each padded to a multiple of N and scattered by its OWN collective.
On the single/multi step the segment collectives are embedded in the
backward DAG via custom_vjp boundary tags (each bucket's reduce-
scatter/all_to_all fires as soon as its layers' cotangents are
complete — the backward emits its result through the cotangent of a
dummy ``(segment/N,)`` slot input, the only side channel a custom
backward has for a shape-changing output), so XLA's latency-hiding
scheduler overlaps bucket i's collective with bucket i+1's gradient
compute.  The grad-accum cadence accumulates locally first (one
exchange per update is the whole point of accumulation), then runs
the SAME per-segment collectives post-backward.

Layout contract: with B>1 the per-shard flat vector is the
concatenation of per-bucket shard pieces — same trajectory for every
REAL parameter element (elementwise update; pad elements stay zero),
but the element ORDER inside the shard (and therefore inside the
sharded optimizer state and the flat error-feedback residual) depends
on B.  A checkpoint written under one ``exchange_buckets`` must be
resumed under the same value — ENFORCED by shape: the last bucket
carries an n*B^2-element encoding pad that makes the per-shard length
strictly increasing in the bucket count, so a mismatched resume fails
loudly in the structural restore instead of silently applying
momentum to the wrong parameters (natural per-bucket pads alone can
coincide across bucket counts).

The reference has no analogue (its exchanger zoo allreduced grads or
params, SURVEY.md §2.4); this is the TPU-era completion of that zoo —
selected as ``ModelConfig.zero_sharding=True``, BSP only (composes
with the ``seq`` axis — extra reduce axes psum the gradient shard —
and with ``grad_accum_steps`` via the shared cadence scan).  The pattern is the cross-replica
weight-update sharding of arXiv:2004.13336 (retrieved in PAPERS.md) /
ZeRO stage 1.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from theanompi_tpu.parallel.bsp import (
    SCOPE_UPDATE,
    TrainState,
    _donate_argnums,
    _fold_axis_rng,
    _pmean,
    accumulate_microbatch_grads,
    grad_and_metrics,
)
from theanompi_tpu.parallel.bsp import state_partition_spec  # noqa: F401
from theanompi_tpu.parallel.exchanger import (
    SCOPE_EXCHANGE,
    bucket_ranges,
    emit_bucket_gauges,
    validate_bucket_count,
)
from theanompi_tpu.parallel.mesh import AXIS_DATA

PyTree = Any


def _flat_info(params: PyTree, n_shards: int) -> tuple[int, int, int]:
    """(total, pad, per_shard) for the flattened param vector."""
    total = sum(int(np.prod(l.shape)) if hasattr(l, "shape") else 1
                for l in jax.tree.leaves(params))
    pad = (-total) % n_shards
    return total, pad, (total + pad) // n_shards


@dataclasses.dataclass(frozen=True)
class _ZeroLayout:
    """The bucketed flat layout — a pure function of (leaf shapes,
    n_shards, exchange_buckets), derived identically on every rank.
    Bucket b owns leaves ``ranges[b]``, i.e. ``m[b]`` elements padded
    by ``pad[b]`` to segment ``seg[b]`` (a multiple of n_shards);
    its per-shard piece is ``pb[b] = seg[b]//n`` at offset
    ``shard_off[b]`` in the shard vector and ``flat_off[b]`` in the
    bucketed flat vector.  B=1 degenerates to the historical global
    layout exactly."""

    ranges: tuple          # ((lo, hi) leaf index ranges)
    leaf_elems: tuple      # element count per leaf, flatten order
    m: tuple               # real elements per bucket
    pad: tuple             # pad elements per bucket
    seg: tuple             # m + pad (multiple of n)
    pb: tuple              # per-shard piece per bucket
    flat_off: tuple        # bucket offset in the bucketed flat vector
    shard_off: tuple       # bucket offset in the per-shard vector
    per_shard: int         # sum(pb)
    total_flat: int        # sum(seg)


def _zero_layout(params: PyTree, n_shards: int,
                 exchange_buckets: int = 1) -> _ZeroLayout:
    leaves = jax.tree.leaves(params)
    elems = tuple(int(np.prod(l.shape)) if hasattr(l, "shape") else 1
                  for l in leaves)
    ranges = tuple(bucket_ranges(elems, exchange_buckets))
    m = tuple(sum(elems[lo:hi]) for lo, hi in ranges)
    pad = tuple((-mb) % n_shards for mb in m)
    if len(ranges) > 1:
        # B-ENCODING pad: the last bucket carries n*B^2 extra zero
        # elements, which makes per_shard strictly increasing in the
        # bucket count (natural pads sum to < n*B, and n*(B'^2-B^2)
        # exceeds that for every B' > B >= 1) — so resuming a
        # checkpoint under a different exchange_buckets REALLY fails
        # on shape instead of silently misaligning the momentum/
        # residual layout when the natural pads happen to coincide.
        # Pad elements are trajectory-neutral: zero params, zero
        # grads, zero momentum, dropped at the gather.  Cost: B^2*n
        # f32 elements (2 KB at B=8, n=8).
        pad = pad[:-1] + (pad[-1] + n_shards * len(ranges) ** 2,)
    seg = tuple(mb + pb for mb, pb in zip(m, pad))
    pb = tuple(s // n_shards for s in seg)
    flat_off = tuple(int(x) for x in np.cumsum((0,) + seg[:-1]))
    shard_off = tuple(int(x) for x in np.cumsum((0,) + pb[:-1]))
    return _ZeroLayout(ranges=ranges, leaf_elems=elems, m=m, pad=pad,
                       seg=seg, pb=pb, flat_off=flat_off,
                       shard_off=shard_off, per_shard=sum(pb),
                       total_flat=sum(seg))


def _ravel_bucket(leaves, lo: int, hi: int, pad: int):
    """One bucket's leaves as a padded f32 segment (flatten order —
    identical element order to ``ravel_pytree`` over the same
    leaves)."""
    parts = [leaves[i].reshape(-1).astype(jnp.float32)
             for i in range(lo, hi)]
    flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return jnp.pad(flat, (0, pad)) if pad else flat


def _ravel_bucketed(tree: PyTree, layout: _ZeroLayout):
    leaves = jax.tree.leaves(tree)
    segs = [_ravel_bucket(leaves, lo, hi, pad)
            for (lo, hi), pad in zip(layout.ranges, layout.pad)]
    return segs[0] if len(segs) == 1 else jnp.concatenate(segs)


def _unravel_bucketed(flat, tree_template: PyTree, layout: _ZeroLayout):
    """Rebuild the param tree from the bucketed flat vector (inverse
    of ``_ravel_bucketed``; pad elements dropped, per-leaf dtypes
    restored)."""
    t_leaves, treedef = jax.tree.flatten(tree_template)
    out = []
    for (lo, hi), off in zip(layout.ranges, layout.flat_off):
        pos = off
        for i in range(lo, hi):
            n = layout.leaf_elems[i]
            out.append(flat[pos:pos + n]
                       .reshape(t_leaves[i].shape)
                       .astype(t_leaves[i].dtype))
            pos += n
    return jax.tree.unflatten(treedef, out)


def _opt_specs(tx: optax.GradientTransformation, per_shard: int):
    """Per-leaf PartitionSpecs for the sharded optimizer state, derived
    STRUCTURALLY (ADVICE r2): ``optax.tree_map_params`` knows exactly
    which state leaves mirror the params (momentum/moments — sharded
    over 'data'); everything else (inject_hyperparams' learning_rate,
    counts) replicates.  Shape matching alone would silently mis-shard
    a replicated vector whose length happens to equal per_shard.

    A param-SHAPED leaf that tree_map_params does NOT register (a
    custom transform keeping unregistered per-param state) would be
    replicated yet updated with shard-local values — silent divergence
    under check_vma=False — so it is rejected instead."""
    template = jax.eval_shape(tx.init, jnp.zeros((per_shard,), jnp.float32))
    marked = optax.tree_map_params(tx, lambda _: True, template,
                                   transform_non_params=lambda _: False)
    specs = jax.tree.map(lambda m: P(AXIS_DATA) if m else P(), marked)
    suspect = [
        leaf for m, leaf in zip(jax.tree.leaves(marked),
                                jax.tree.leaves(template))
        if not m and getattr(leaf, "ndim", 0) == 1
        and leaf.shape[0] == per_shard
    ]
    if suspect:
        raise ValueError(
            f"optimizer state holds {len(suspect)} param-shaped leaf/leaves "
            "not registered as params with optax.tree_map_params; ZeRO "
            "cannot tell whether to shard them — use an optimizer whose "
            "per-param state is registered (sgd/adam/adamw/rmsprop are)")
    return template, specs


def _shard_slice(pflat, layout: _ZeroLayout, idx):
    """This shard's slice of the bucketed flat vector: the
    concatenation of its per-bucket pieces."""
    pieces = [lax.dynamic_slice(pflat, (off + idx * pb,), (pb,))
              for off, pb in zip(layout.flat_off, layout.pb)]
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)


def init_zero_opt_state(tx: optax.GradientTransformation, params: PyTree,
                        mesh: jax.sharding.Mesh,
                        exchange_buckets: int = 1):
    """Build the optimizer state directly SHARDED over 'data' (never
    materializing the full-size state on any device).
    ``exchange_buckets`` must match the step's — it fixes the shard
    layout (see the module docstring's layout contract)."""
    n = mesh.shape[AXIS_DATA]
    layout = _zero_layout(params, n, exchange_buckets)
    _, specs = _opt_specs(tx, layout.per_shard)

    def shard_init(params):
        idx = lax.axis_index(AXIS_DATA)
        pshard = _shard_slice(_ravel_bucketed(params, layout), layout,
                              idx)
        return tx.init(pshard)

    sharded = jax.shard_map(shard_init, mesh=mesh, in_specs=(P(),),
                            out_specs=specs, check_vma=False)
    return jax.jit(sharded)(params), specs


def init_zero_exchange_residual(params_template: PyTree,
                                mesh: jax.sharding.Mesh,
                                exchange_buckets: int = 1) -> np.ndarray:
    """Zero error-feedback residual for the ZeRO step: the bucketed
    flat gradient vector per data shard, host-side
    ``(n_data, total_flat)`` f32 — the caller places it sharded
    ``P('data')`` on the leading axis (models/base.py
    ``_create_state``).  ``exchange_buckets`` fixes the flat layout
    the residual lives in."""
    n = mesh.shape[AXIS_DATA]
    layout = _zero_layout(params_template, n, exchange_buckets)
    return np.zeros((n, layout.total_flat), np.float32)


def make_bsp_zero_step(
    loss_fn,
    tx: optax.GradientTransformation,
    mesh: jax.sharding.Mesh,
    params_template: PyTree,
    avg: bool = True,
    donate: bool = True,
    donate_batch: bool = True,
    batch_partition: P = P(AXIS_DATA),
    reduce_axes: tuple[str, ...] = (AXIS_DATA,),
    accum: bool = False,
    multi: bool = False,
    exchange_dtype: str = "f32",
    error_feedback: bool = False,
    exchange_buckets: int = 1,
):
    """Build the ZeRO-1 training step.

    ``exchange_dtype='bf16'`` quantizes the flat gradient vector to
    bfloat16 before the data-axis ``psum_scatter`` — the ring
    reduce-scatter (and therefore the pod's ICI gradient bytes) moves
    2 bytes/element — and upcasts the received shard to f32 BEFORE the
    extra-axis psum, the average, and the optimizer update, so
    accumulation on the shard stays f32.  ``error_feedback=True``
    additionally carries each shard's f32 quantization error in
    ``state.exchange_residual`` (flat, ``(n_data, total_flat)`` global,
    sharded over 'data') and re-injects it into the next exchange —
    the cumulative applied gradient then tracks the cumulative true
    gradient to one quantization step (same scheme as the unflattened
    path in parallel/bsp.py).

    ``exchange_buckets=B`` splits the flat vector into B layer-ordered
    segments with one collective each; on the single/multi step the
    segment collectives are embedded in the backward DAG (module
    docstring).  ``init_zero_opt_state`` / the residual init must be
    built with the SAME bucket count — the plan fixes the shard
    layout.

    ``accum=True`` builds the grad-accumulation variant instead:
    ``step(state, stacked_batch, rng)`` with a leading microbatch axis
    — grads accumulate locally as the padded flat vector, then ONE
    sharded (per-bucket) exchange/update (ZeRO x grad-accum
    composition).

    ``multi=True`` builds the ``steps_per_call`` variant (ZeRO x
    multi-step): ``lax.scan`` of the FULL sharded step —
    reduce_scatter + shard update + all_gather per sub-step, so the
    trajectory is identical to k separate calls with rngs
    ``fold_in(rng, i)`` — amortizing the per-dispatch floor k-fold
    exactly like parallel/bsp.py's make_bsp_multi_step.  Mutually
    exclusive with ``accum`` (the two stacked cadences always are).

    ``step(state, batch, rng) -> (state, metrics)`` with ``state.params``
    replicated and ``state.opt_state`` sharded over 'data' (the specs
    come from ``init_zero_opt_state``).  ``reduce_axes`` must include
    'data'; any OTHER reduce axis (e.g. 'seq' for the long-context
    family) is psum-ed plainly before the data-axis reduce_scatter —
    the optimizer shard stays a pure data-axis concept.
    """
    if AXIS_DATA not in reduce_axes:
        raise ValueError(f"zero needs the '{AXIS_DATA}' axis in "
                         f"reduce_axes, got {reduce_axes}")
    if accum and multi:
        raise ValueError("accum and multi are mutually exclusive "
                         "stacked cadences")
    if exchange_dtype not in ("f32", "bf16"):
        raise ValueError(f"exchange_dtype must be 'f32' or 'bf16', "
                         f"got {exchange_dtype!r}")
    if error_feedback and exchange_dtype != "bf16":
        raise ValueError("error_feedback compensates bf16 quantization; "
                         "it needs exchange_dtype='bf16'")
    validate_bucket_count(exchange_buckets)
    extra_axes = tuple(a for a in reduce_axes if a != AXIS_DATA)
    n = mesh.shape[AXIS_DATA]
    n_total = n * int(np.prod([mesh.shape[a] for a in extra_axes] or [1]))
    layout = _zero_layout(params_template, n, exchange_buckets)
    n_buckets = len(layout.ranges)
    _, opt_specs = _opt_specs(tx, layout.per_shard)
    state_in_specs = TrainState(step=P(), params=P(), opt_state=opt_specs,
                                model_state=P(),
                                exchange_residual=P(AXIS_DATA))
    wire = "bf16" if exchange_dtype == "bf16" else "f32"

    @jax.named_scope(SCOPE_EXCHANGE)
    def scatter_segment(seg, res_seg):
        """One bucket's collective, from its local padded f32 segment:
        reduce_scatter (f32) or quantize + all_to_all + f32 local
        accumulation (bf16, optionally error-fed).  Returns
        (per-shard piece, new residual segment | None).

        Why all_to_all for bf16: a bf16 psum_scatter would round every
        partial sum to 8 mantissa bits and (at N shards) swallow
        quantization-step-sized corrections — the same failure the
        exchanger's _bf16_sum documents.  all_to_all moves exactly the
        ring reduce-scatter's (N-1)/N x bytes, but every add happens
        locally in f32."""
        if exchange_dtype == "bf16":
            if error_feedback:
                comp = seg + res_seg
                q = comp.astype(jnp.bfloat16)
                new_r = comp - q.astype(jnp.float32)
            else:
                q = seg.astype(jnp.bfloat16)
                new_r = None
            recv = lax.all_to_all(q.reshape(n, -1), AXIS_DATA,
                                  split_axis=0, concat_axis=0,
                                  tiled=True)
            return jnp.sum(recv.astype(jnp.float32), axis=0), new_r
        piece = lax.psum_scatter(seg, AXIS_DATA,
                                 scatter_dimension=0, tiled=True)
        return piece, None

    def scatter_flat(gflat, residual_flat):
        """All buckets' collectives from the local bucketed flat
        gradient (the post-backward path: B=1 single step and the
        accum tail).  Returns (gshard, new bucketed residual | None)."""
        pieces, res_segs = [], []
        for b in range(n_buckets):
            off, sg = layout.flat_off[b], layout.seg[b]
            seg = lax.dynamic_slice(gflat, (off,), (sg,))
            res_seg = (lax.dynamic_slice(residual_flat, (off,), (sg,))
                       if error_feedback else None)
            piece, new_r = scatter_segment(seg, res_seg)
            pieces.append(piece)
            res_segs.append(new_r)
        gshard = (pieces[0] if n_buckets == 1
                  else jnp.concatenate(pieces))
        if error_feedback:
            new_res = (res_segs[0] if n_buckets == 1
                       else jnp.concatenate(res_segs))
            return gshard, new_res
        return gshard, None

    def update_and_gather(state, gshard, new_res, new_ms):
        """The ZeRO tail from the per-shard gradient: extra-axis psum
        (the sums commute, and psum-ing only the 1/N shard moves
        data-axis-size times less traffic than the full vector would),
        average, update the shard, gather the params back per
        bucket."""
        if extra_axes:
            with jax.named_scope(SCOPE_EXCHANGE):
                gshard = lax.psum(gshard, extra_axes)
        if avg:
            gshard = gshard / n_total

        idx = lax.axis_index(AXIS_DATA)
        pflat = _ravel_bucketed(state.params, layout)
        pshard = _shard_slice(pflat, layout, idx)

        with jax.named_scope(SCOPE_UPDATE):  # as bsp.apply_update's
            updates, new_opt = tx.update(gshard, state.opt_state, pshard)
            new_pshard = optax.apply_updates(pshard, updates)
        with jax.named_scope(SCOPE_EXCHANGE):
            # (n, per_shard)
            gathered = lax.all_gather(new_pshard, AXIS_DATA)
        segs = [gathered[:, so:so + pb].reshape(-1)
                for so, pb in zip(layout.shard_off, layout.pb)]
        new_flat = segs[0] if n_buckets == 1 else jnp.concatenate(segs)
        new_params = _unravel_bucketed(new_flat, state.params, layout)
        if new_res is not None:
            new_res = new_res[None]  # leading shard axis back on
        else:
            new_res = state.exchange_residual
        return TrainState(step=state.step + 1, params=new_params,
                          opt_state=new_opt, model_state=new_ms,
                          exchange_residual=new_res)

    # -- backward-embedded bucketed scatter (exchange_buckets > 1) ------

    def _zero_tag(b: int):
        """Boundary tag for bucket ``b``: identity on its param leaves;
        the backward ravels the bucket's cotangents and fires its
        scatter collective immediately.  The per-shard piece (and the
        new residual segment) leave the backward through the
        cotangents of dummy slot inputs — a custom_vjp backward's only
        outputs are cotangents, and the scatter result's shape
        (1/N of the segment) matches no real input, so a
        ``(seg/N,)``-shaped slot exists to carry it."""
        lo, hi = layout.ranges[b]
        pad = layout.pad[b]

        if error_feedback:
            @jax.custom_vjp
            def tag(leaves, slot, res_seg):
                return leaves

            def fwd(leaves, slot, res_seg):
                return leaves, res_seg

            def bwd(res_seg, cts):
                seg = _ravel_bucket(cts, 0, len(cts), pad)
                piece, new_r = scatter_segment(seg, res_seg)
                zeros = tuple(jnp.zeros_like(c) for c in cts)
                return zeros, piece, new_r
        else:
            @jax.custom_vjp
            def tag(leaves, slot):
                return leaves

            def fwd(leaves, slot):
                return leaves, None

            def bwd(_, cts):
                seg = _ravel_bucket(cts, 0, len(cts), pad)
                piece, _ = scatter_segment(seg, None)
                zeros = tuple(jnp.zeros_like(c) for c in cts)
                return zeros, piece

        tag.defvjp(fwd, bwd)
        return tag

    def backward_scatter(state, batch, rng):
        """Gradient computation with per-bucket scatters embedded in
        the backward (the exchange_buckets>1 sibling of
        exchanger.backward_exchange).  Returns (gshard, new_res | None,
        new_ms, metrics)."""
        leaves0, treedef0 = jax.tree.flatten(state.params)
        emit_bucket_gauges("zero", layout.ranges, leaves0, wire)
        slots = tuple(jnp.zeros((pb,), jnp.float32) for pb in layout.pb)
        if error_feedback:
            res_full = state.exchange_residual[0]
            res_slots = tuple(
                lax.dynamic_slice(res_full, (off,), (sg,))
                for off, sg in zip(layout.flat_off, layout.seg))
            diff_arg = (slots, res_slots)
        else:
            diff_arg = slots

        def tagged_loss(diff_arg, model_state, batch, rng):
            slots_, res_ = (diff_arg if error_feedback
                            else (diff_arg, None))
            new_leaves = []
            for b, (lo, hi) in enumerate(layout.ranges):
                bucket = tuple(leaves0[lo:hi])
                if error_feedback:
                    new_leaves.extend(
                        _zero_tag(b)(bucket, slots_[b], res_[b]))
                else:
                    new_leaves.extend(_zero_tag(b)(bucket, slots_[b]))
            return loss_fn(jax.tree.unflatten(treedef0, new_leaves),
                           model_state, batch, rng)

        grad_fn = jax.value_and_grad(tagged_loss, has_aux=True)
        (loss, (new_ms, metrics)), g = grad_fn(
            diff_arg, state.model_state, batch, rng)
        metrics = dict(metrics)
        metrics.setdefault("loss", loss)
        if error_feedback:
            pieces, res_segs = g
            new_res = (res_segs[0] if n_buckets == 1
                       else jnp.concatenate(res_segs))
        else:
            pieces, new_res = g, None
        gshard = (pieces[0] if n_buckets == 1
                  else jnp.concatenate(pieces))
        return gshard, new_res, new_ms, metrics

    def shard_step(state: TrainState, batch, rng):
        rng = _fold_axis_rng(rng, reduce_axes)
        if n_buckets > 1:
            gshard, new_res, new_ms, metrics = backward_scatter(
                state, batch, rng)
        else:
            grads, new_ms, metrics = grad_and_metrics(
                loss_fn, state.params, state.model_state, batch, rng)
            gflat = _ravel_bucketed(grads, layout)
            res_flat = (state.exchange_residual[0] if error_feedback
                        else None)
            gshard, new_res = scatter_flat(gflat, res_flat)
        new_ms = _pmean(new_ms, reduce_axes)
        new_state = update_and_gather(state, gshard, new_res, new_ms)
        return new_state, _pmean(metrics, reduce_axes)

    def shard_accum(state: TrainState, stacked, rng):
        # a microbatches -> ONE sharded update (ZeRO x grad-accum):
        # grads accumulate locally as the bucketed flat vector (the
        # shared cadence scan in parallel/bsp.py), then the same
        # post-backward per-bucket scatter tail as the B=1 step —
        # accumulation's whole point is ONE exchange per update, so
        # the bucket collectives stay after the (scanned) backward
        rng = _fold_axis_rng(rng, reduce_axes)

        def add_flat(gsum, grads):
            return gsum + _ravel_bucketed(grads, layout)

        gz = jnp.zeros((layout.total_flat,), jnp.float32)
        new_ms, gsum, metrics, a = accumulate_microbatch_grads(
            loss_fn, state.params, state.model_state, stacked, rng,
            gz, add_flat)
        if n_buckets > 1:
            leaves0 = jax.tree.leaves(state.params)
            emit_bucket_gauges("zero", layout.ranges, leaves0, wire)
        new_ms = _pmean(new_ms, reduce_axes)
        res_flat = (state.exchange_residual[0] if error_feedback
                    else None)
        gshard, new_res = scatter_flat(gsum / a, res_flat)
        new_state = update_and_gather(state, gshard, new_res, new_ms)
        return new_state, _pmean(metrics, reduce_axes)

    def shard_multi(state: TrainState, stacked, rng):
        def body(carry, xs):
            i, batch = xs
            return shard_step(carry, batch, jax.random.fold_in(rng, i))

        k = jax.tree.leaves(stacked)[0].shape[0]
        return lax.scan(body, state, (jnp.arange(k), stacked))

    fn = shard_accum if accum else (shard_multi if multi else shard_step)
    partition = (P(None, *batch_partition) if (accum or multi)
                 else batch_partition)
    sharded = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(state_in_specs, partition, P()),
        out_specs=(state_in_specs, P()),
        check_vma=False,
    )
    # the stacked cadences donate the staged batch like parallel/bsp.py
    # (same copy-done rationale + the same opt-out for batch replayers)
    dn = _donate_argnums(donate, donate_batch and (accum or multi))
    return jax.jit(sharded, donate_argnums=dn)
