"""Parameter/gradient exchange — the heart of the framework.

TPU-native rebuild of the reference's exchanger layer (reference layout
``theanompi/lib/exchanger.py`` + ``lib/exchanger_strategy.py``,
SURVEY.md §2.4–§2.5; the reference mount was empty this round so
citations are to SURVEY.md sections, not file:line).

The reference flattened Theano shared variables into GPU buffers and
dispatched to one of six transport strategies (``ar``, ``asa32``,
``asa16``, ``copper``, ``nccl32``, ``nccl16``) for an MPI- or
NCCL-backed allreduce after each iteration.  On TPU the transport zoo
collapses: XLA emits ICI collectives for ``jax.lax.psum`` inside the
jitted SPMD step, and the compiler — not the framework — schedules and
overlaps them.  What survives of the reference's strategy seam is the
*numeric* choice the strategies encoded:

* fp32 exchange (``ar``/``asa32``/``copper``/``nccl32``) -> ``psum``
  on the native dtype;
* fp16-compressed exchange (``asa16``/``nccl16``) -> cast to bfloat16,
  ``psum``, cast back.  bf16 keeps fp32's exponent range, so the
  reference's fp16 loss-scale knob is unnecessary on TPU (kept as a
  config field for API parity; default 1.0).
* sum vs average (the reference's ``avg`` flag).

This module also carries the async rules' merge arithmetic (EASGD
elastic update, ASGD server update, GOSGD weighted merge — SURVEY.md
§2.3/§2.5) as small pure jitted functions; the rules in
``theanompi_tpu/rules`` own the process topology around them.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from theanompi_tpu import monitor
from theanompi_tpu.parallel.mesh import AXIS_DATA
from theanompi_tpu.parallel.partition import balanced_ranges

PyTree = Any

#: the scope of the gradient exchange and the cross-replica means in a
#: device trace (docs/OBSERVABILITY.md "Device-trace names")
SCOPE_EXCHANGE = "bsp/exchange"


def bucket_ranges(sizes, n_buckets: int) -> list[tuple[int, int]]:
    """Layer-ordered, byte-balanced bucket plan over flatten-order
    leaves: contiguous ``(lo, hi)`` leaf ranges, a pure function of
    (leaf byte sizes, bucket count) — every rank derives the identical
    plan from its own model tree, exactly like the shard fleet's
    ``partition_ranges`` (same greedy walk, ``parallel/partition.py``).
    Unlike the shard plan, a bucket count beyond the leaf count CLAMPS
    to per-leaf buckets instead of raising: the bucket plan is a
    scheduling hint, not an ownership contract."""
    sizes = list(sizes)
    return balanced_ranges(sizes, min(int(n_buckets), len(sizes)))


def validate_bucket_count(exchange_buckets) -> int:
    """The ONE contract check for the ``exchange_buckets`` knob (the
    exchanger and the zero/fsdp step builders all accept it — one
    validator keeps the three planes' accepted values and error text
    identical)."""
    b = exchange_buckets
    if isinstance(b, bool) or not isinstance(b, int) or b < 1:
        raise ValueError(
            f"exchange_buckets must be an int >= 1, got {b!r}")
    return b


def _leaf_nbytes(leaf) -> int:
    import numpy as np

    size = getattr(leaf, "size", None)
    if size is None:
        size = int(np.prod(getattr(leaf, "shape", ())))
    return int(size) * np.dtype(leaf.dtype).itemsize


def emit_bucket_gauges(plane: str, ranges, leaves, wire_dtype: str) -> None:
    """Trace-time bucket telemetry (same contract as the exchange
    gauges below: recorded once per compile, bytes/step = gauge x
    steps): the live bucket count and each bucket's wire bytes."""
    if not monitor.enabled():
        return
    monitor.set_gauge("bsp/exchange_buckets", len(ranges), plane=plane,
                      dtype=wire_dtype)
    for i, (lo, hi) in enumerate(ranges):
        if wire_dtype == "bf16":
            nbytes = 2 * sum(int(getattr(l, "size", 0))
                             for l in leaves[lo:hi])
        else:
            nbytes = sum(_leaf_nbytes(l) for l in leaves[lo:hi])
        monitor.set_gauge("bsp/exchange_bucket_bytes", nbytes,
                          plane=plane, bucket=str(i), dtype=wire_dtype)

# Reference strategy names -> TPU numeric strategy.
_STRATEGY_ALIASES = {
    "ar": "psum",
    "asa32": "psum",
    "copper": "psum",
    "nccl32": "psum",
    "psum": "psum",
    "asa16": "psum_bf16",
    "nccl16": "psum_bf16",
    "psum_bf16": "psum_bf16",
}


def resolve_strategy(name: str) -> str:
    """Map a reference-era strategy name to its TPU numeric strategy
    ('psum' | 'psum_bf16'); raises on unknown names."""
    try:
        return _STRATEGY_ALIASES[name]
    except KeyError:
        raise ValueError(
            f"unknown exchange strategy {name!r}; "
            f"expected one of {sorted(_STRATEGY_ALIASES)}") from None


@dataclasses.dataclass(frozen=True)
class BSP_Exchanger:
    """BSP exchange semantics, applied *inside* the SPMD training step.

    Name kept for API parity with the reference's ``BSP_Exchanger``
    (SURVEY.md §2.4).  Unlike the reference this is not a stateful
    buffer manager: it is a pure ``tree -> tree`` transform traced into
    the jitted step, so exchange overlaps backprop wherever XLA can
    schedule it.

    Args:
      strategy: one of the reference names (``ar``/``asa32``/``asa16``/
        ``copper``/``nccl32``/``nccl16``) or the native names
        (``psum``/``psum_bf16``).
      avg: True -> average over the data axis (the reference's ``avg``
        sync type); False -> plain sum (``cdd``-style; caller is then
        expected to have pre-scaled its learning rate, cf. the
        reference's ``scale_lr``).
      exchange_what: ``'grads'`` (allreduce gradients each iteration,
        the reference BSP default) or ``'params'`` (average parameters,
        the reference's alternative BSP mode).
      fp16_scale: kept for parity with the reference's fp16 strategies;
        bf16 needs no scaling, default 1.0.
      axis: mesh axis name (or tuple of names) to reduce over — a
        data x seq training step exchanges over both axes.
      exchange_dtype: ``None`` (derive from ``strategy``) | ``'f32'`` |
        ``'bf16'`` — the ICI wire dtype of the exchange.  ``'bf16'``
        quantizes each leaf to bfloat16 before the psum (half the
        gradient bytes on the pod interconnect) and restores float32
        BEFORE the average, so the mean and the optimizer update
        accumulate in f32.  The ``ModelConfig.exchange_dtype`` knob
        lands here; the reference-era ``nccl16``-family strategy names
        remain the parity spelling of the same choice.
      error_feedback: carry the per-shard bf16 quantization error into
        the next step's gradient (1-bit-SGD-style residual, SURVEY.md
        compression lineage): ``exchange_with_residual`` adds the
        stored residual before quantizing and returns the new one.
        The residual rides ``TrainState.exchange_residual`` with a
        leading shard axis (parallel/bsp.py threads it).  Requires the
        bf16 wire dtype and ``exchange_what='grads'``.
      exchange_buckets: partition the flatten-order gradient leaves
        into this many layer-ordered, byte-balanced buckets
        (``bucket_ranges``) and issue ONE collective per bucket
        instead of per-leaf ops the compiler must re-combine.  On the
        training step's grads path the collectives are embedded INTO
        the backward DAG (``backward_exchange``: custom_vjp boundary
        tags fire each bucket's psum the moment its layers' cotangents
        are complete), so XLA's latency-hiding scheduler overlaps
        bucket i's collective with bucket i+1's gradient compute — the
        layer-ordered bucketing of arXiv:1802.06949 expressed in the
        compiler's DAG.  ``1`` (default) keeps today's whole-tree
        post-backward exchange byte-identical.  Numerics are identical
        under any bucket count (pinned): bucketing regroups elementwise
        collectives, it never reorders a per-element sum.
    """

    strategy: str = "psum"
    avg: bool = True
    exchange_what: str = "grads"
    fp16_scale: float = 1.0
    axis: str | tuple[str, ...] = AXIS_DATA
    exchange_dtype: str | None = None
    error_feedback: bool = False
    exchange_buckets: int = 1

    def __post_init__(self):
        validate_bucket_count(self.exchange_buckets)
        if self.strategy not in _STRATEGY_ALIASES:
            raise ValueError(
                f"unknown exchange strategy {self.strategy!r}; "
                f"expected one of {sorted(_STRATEGY_ALIASES)}"
            )
        if self.exchange_what not in ("grads", "params"):
            raise ValueError("exchange_what must be 'grads' or 'params'")
        if self.exchange_dtype not in (None, "f32", "bf16"):
            raise ValueError(
                f"exchange_dtype must be 'f32' or 'bf16', "
                f"got {self.exchange_dtype!r}")
        if self.error_feedback:
            if self.wire_dtype != "bf16":
                raise ValueError(
                    "error_feedback compensates bf16 quantization; it "
                    "needs exchange_dtype='bf16' (or a bf16 strategy)")
            if self.exchange_what != "grads":
                raise ValueError(
                    "error_feedback is a gradient-compression technique; "
                    "exchange_what='params' has no residual semantics")

    @property
    def resolved(self) -> str:
        if self.exchange_dtype == "bf16":
            return "psum_bf16"
        if self.exchange_dtype == "f32":
            return "psum"
        return _STRATEGY_ALIASES[self.strategy]

    @property
    def wire_dtype(self) -> str:
        """'bf16' | 'f32' — what actually moves over ICI."""
        return "bf16" if self.resolved == "psum_bf16" else "f32"

    # -- the exchange itself (must run inside shard_map over self.axis) --

    def exchange(self, tree: PyTree) -> PyTree:
        """Allreduce a pytree over the data axis. Traced into the step."""
        axis = self.axis

        # Telemetry: this body executes at TRACE time (the exchange is
        # compiled into the step), so per-call counting is impossible
        # from here — what IS knowable here, exactly once per compile,
        # is the exchange's shape: bytes moved per call and the wire
        # dtype.  Per-step totals = bytes_per_call x the step counter.
        if monitor.enabled():
            if self.resolved == "psum_bf16":
                # the compressed strategy ships 2 bytes/element
                # regardless of the storage dtype
                wire_dtype = "bfloat16"
                nbytes = 2 * sum(
                    int(getattr(l, "size", 0))
                    for l in jax.tree.leaves(tree))
            else:
                wire_dtype = monitor.tree_dtypes(tree)
                nbytes = monitor.tree_bytes(tree)
            monitor.set_gauge("exchange/bytes_per_call", nbytes,
                              strategy=self.resolved, dtype=wire_dtype,
                              what=self.exchange_what)
            monitor.inc("exchange/traces_total", strategy=self.resolved)

        if self.exchange_buckets > 1:
            # post-backward bucketed exchange (the grad-accum tail and
            # the 'params' averaging mode; the single/multi grads path
            # embeds the buckets into the backward via
            # ``backward_exchange`` instead): one collective per
            # byte-balanced leaf bucket
            leaves, treedef = jax.tree.flatten(tree)
            ranges = bucket_ranges([_leaf_nbytes(l) for l in leaves],
                                   self.exchange_buckets)
            emit_bucket_gauges("bsp", ranges, leaves, self.wire_dtype)
            out = []
            for lo, hi in ranges:
                out.extend(self._reduce_bucket(tuple(leaves[lo:hi])))
            return jax.tree.unflatten(treedef, out)

        if self.resolved == "psum_bf16":
            def reduce_leaf(x):
                orig = x.dtype
                y = (x * self.fp16_scale).astype(jnp.bfloat16)
                y = self._bf16_sum(y, axis)
                return (y / self.fp16_scale).astype(orig)
        else:
            def reduce_leaf(x):
                return jax.lax.psum(x, axis)

        out = jax.tree.map(reduce_leaf, tree)
        if self.avg:
            n = self._axis_size()
            out = jax.tree.map(lambda x: x / n, out)
        return out

    def _axis_size(self):
        axes = ((self.axis,) if isinstance(self.axis, str)
                else tuple(self.axis))
        n = 1
        for a in axes:
            n *= jax.lax.axis_size(a)
        return n

    @staticmethod
    def _bf16_sum(y, axis):
        """Sum bf16-quantized leaves over ``axis`` with a bf16 WIRE and
        f32 ACCUMULATION: all_gather the quantized values (bf16 on the
        interconnect — (N-1)/N x 2 bytes/element, half a bf16 ring
        all-reduce's traffic and a quarter of the f32 one) and reduce
        locally in float32.

        Why not ``psum(bf16)``: the psum accumulates IN bf16, and at N
        shards the partial sums sit N x above the payload — each add
        can then swallow an entire quantization step of the increment
        (at N=8 a 2^-8 correction on a ~1.0 payload vanishes into the
        ~8.0 partial sum's 2^-5 spacing).  Measured on the 8-dev CPU
        mesh, that rounding defeats error feedback almost entirely;
        the local f32 reduce is what makes the residual pin
        (tests/test_exchanger.py long-run gradient-sum) hold."""
        g = jax.lax.all_gather(y, axis)
        return jnp.sum(g.astype(jnp.float32), axis=0)

    # -- bucketed exchange (ISSUE 13) -----------------------------------

    @staticmethod
    def _bucket_flat(cts: tuple):
        """Concatenate a bucket's leaves into ONE vector when their
        dtypes agree (one collective per bucket in the lowered
        program — the reference's bucket flattening); ``None`` for a
        mixed-dtype bucket (the per-leaf fallback keeps numerics
        exact instead of forcing a cast)."""
        if len({jnp.result_type(c) for c in cts}) != 1:
            return None
        if len(cts) == 1:
            return cts[0].reshape(-1)
        return jnp.concatenate([c.reshape(-1) for c in cts])

    @staticmethod
    def _split_like(flat, refs: tuple) -> tuple:
        out, off = [], 0
        for r in refs:
            n = int(r.size)
            out.append(flat[off:off + n].reshape(r.shape))
            off += n
        return tuple(out)

    def _reduce_bucket(self, cts: tuple) -> tuple:
        """Exchange one bucket of gradient leaves: elementwise-identical
        to the per-leaf ``exchange`` (psum and the bf16 quantize/sum
        are elementwise across shards — regrouping leaves cannot move
        a single per-element sum), but issued as ONE collective."""
        axis = self.axis
        flat = self._bucket_flat(cts)
        if flat is None:  # mixed dtypes: per-leaf ops, same boundary
            if self.resolved == "psum_bf16":
                red = tuple(
                    (self._bf16_sum((c * self.fp16_scale)
                                    .astype(jnp.bfloat16), axis)
                     / self.fp16_scale).astype(c.dtype) for c in cts)
            else:
                red = jax.lax.psum(cts, axis)
            if self.avg:
                n = self._axis_size()
                red = tuple(x / n for x in red)
            return tuple(red)
        if self.resolved == "psum_bf16":
            y = (flat * self.fp16_scale).astype(jnp.bfloat16)
            red = (self._bf16_sum(y, axis)
                   / self.fp16_scale).astype(flat.dtype)
        else:
            red = jax.lax.psum(flat, axis)
        if self.avg:
            red = red / self._axis_size()
        return self._split_like(red, cts)

    def _reduce_bucket_ef(self, cts: tuple, res: tuple
                          ) -> tuple[tuple, tuple]:
        """Error-feedback variant of ``_reduce_bucket``: quantize
        ``ct + residual`` to bf16, one all-gather + f32 sum for the
        bucket, return (exchanged, new per-shard residual slice) —
        the per-leaf ``exchange_with_residual`` math on one flat
        bucket vector."""
        axis = self.axis
        flat = self._bucket_flat(cts)
        if flat is None:
            comp = tuple(c.astype(jnp.float32) + r
                         for c, r in zip(cts, res))
            q = tuple(c.astype(jnp.bfloat16) for c in comp)
            new_r = tuple(c - qq.astype(jnp.float32)
                          for c, qq in zip(comp, q))
            out = tuple(self._bf16_sum(qq, axis).astype(c.dtype)
                        for qq, c in zip(q, cts))
            if self.avg:
                n = self._axis_size()
                out = tuple(x / n for x in out)
            return out, new_r
        rflat = self._bucket_flat(res)
        comp = flat.astype(jnp.float32) + rflat
        q = comp.astype(jnp.bfloat16)
        new_r = comp - q.astype(jnp.float32)
        out = self._bf16_sum(q, axis).astype(flat.dtype)
        if self.avg:
            out = out / self._axis_size()
        return (self._split_like(out, cts),
                self._split_like(new_r, res))

    def _grad_tag(self):
        """custom_vjp boundary marker for one bucket: identity forward;
        the backward fires the bucket's collective the moment its
        leaves' cotangents are complete, embedding the exchange into
        the backward DAG for the latency-hiding scheduler to overlap
        with the remaining segments' gradient compute."""

        @jax.custom_vjp
        def tag(leaves):
            return leaves

        def fwd(leaves):
            return leaves, None

        def bwd(_, cts):
            with jax.named_scope(SCOPE_EXCHANGE):
                return (self._reduce_bucket(cts),)

        tag.defvjp(fwd, bwd)
        return tag

    def _ef_tag(self):
        """Error-feedback boundary marker.  The residual slice is a
        *differentiated* input whose "cotangent" we define to be the
        NEW residual — the only side channel a backward segment has
        for emitting state (a custom_vjp bwd returns exactly one
        cotangent per input)."""

        @jax.custom_vjp
        def tag(leaves, res):
            return leaves

        def fwd(leaves, res):
            return leaves, res

        def bwd(res, cts):
            with jax.named_scope(SCOPE_EXCHANGE):
                out, new_r = self._reduce_bucket_ef(cts, res)
            return out, new_r

        tag.defvjp(fwd, bwd)
        return tag

    def backward_exchange(self, loss_fn, params: PyTree,
                          model_state: PyTree, batch, rng,
                          residual: PyTree | None = None):
        """value_and_grad with the bucketed exchange embedded in the
        backward DAG (the ``exchange_buckets > 1`` grads path).

        The flatten-order leaves are cut into layer-ordered buckets
        (``bucket_ranges``); each bucket's leaves pass through a
        boundary tag whose custom backward issues that bucket's
        collective as soon as all its cotangents exist.  Autodiff
        runs the backward segment for the deepest layers first, so
        the last bucket's psum is already on the interconnect while
        earlier layers' cotangents are still being computed — the
        lowered program carries B collectives interleaved with the
        backward fusions instead of one trailing exchange block
        (pinned structurally in tests/test_exchanger.py).

        Returns ``(loss, (new_model_state, metrics), grads,
        new_residual)`` where ``grads`` is ALREADY exchanged (and
        averaged when ``avg``) and ``new_residual`` is ``None``
        unless ``error_feedback``.
        """
        if self.exchange_what != "grads":
            raise ValueError("backward_exchange embeds the GRADIENT "
                             "exchange; exchange_what='params' has no "
                             "backward to interleave with")
        leaves, treedef = jax.tree.flatten(params)
        ranges = bucket_ranges([_leaf_nbytes(l) for l in leaves],
                               self.exchange_buckets)
        emit_bucket_gauges("bsp", ranges, leaves, self.wire_dtype)
        ef = self.error_feedback
        if ef:
            if residual is None:
                raise ValueError("error_feedback needs the residual "
                                 "tree (TrainState.exchange_residual)")
            rleaves = jax.tree.flatten(residual)[0]

        def tagged_loss(diff_arg, model_state, batch, rng):
            buckets, rbuckets = (diff_arg if ef else (diff_arg, None))
            new_leaves = []
            for b in range(len(ranges)):
                if ef:
                    new_leaves.extend(
                        self._ef_tag()(buckets[b], rbuckets[b]))
                else:
                    new_leaves.extend(self._grad_tag()(buckets[b]))
            return loss_fn(jax.tree.unflatten(treedef, new_leaves),
                           model_state, batch, rng)

        buckets = tuple(tuple(leaves[lo:hi]) for lo, hi in ranges)
        if ef:
            rbuckets = tuple(tuple(rleaves[lo:hi]) for lo, hi in ranges)
            diff_arg = (buckets, rbuckets)
        else:
            diff_arg = buckets
        grad_fn = jax.value_and_grad(tagged_loss, has_aux=True)
        (loss, (new_ms, metrics)), g = grad_fn(diff_arg, model_state,
                                               batch, rng)
        if ef:
            gb, rb = g
            new_residual = jax.tree.unflatten(
                treedef, [r for rt in rb for r in rt])
        else:
            gb, new_residual = g, None
        grads = jax.tree.unflatten(treedef,
                                   [x for bt in gb for x in bt])
        return loss, (new_ms, metrics), grads, new_residual

    def exchange_with_residual(self, tree: PyTree,
                               residual: PyTree) -> tuple[PyTree, PyTree]:
        """bf16 exchange with error feedback: quantize ``tree +
        residual`` to bfloat16, sum the quantized values over the axis
        with ``_bf16_sum`` (bf16 on the wire — 2 bytes/element — f32
        accumulation locally), average in f32, and
        return the NEW per-shard residual — the f32 difference between
        what this shard wanted to send and what the quantizer let
        through.  Over a run the residual re-injects every bit the
        wire dropped, so the cumulative applied gradient tracks the
        cumulative true gradient to within one quantization step
        (pinned by test)."""
        if not self.error_feedback:
            raise ValueError("exchange_with_residual needs "
                             "error_feedback=True")

        if self.exchange_buckets > 1:
            # post-backward bucketed EF exchange (the grad-accum tail;
            # per-bucket residual slices are the same leaves, just
            # grouped): one all-gather per bucket
            leaves, treedef = jax.tree.flatten(tree)
            rleaves = jax.tree.flatten(residual)[0]
            ranges = bucket_ranges([_leaf_nbytes(l) for l in leaves],
                                   self.exchange_buckets)
            emit_bucket_gauges("bsp", ranges, leaves, self.wire_dtype)
            out, new_res = [], []
            for lo, hi in ranges:
                o, r = self._reduce_bucket_ef(
                    tuple(leaves[lo:hi]), tuple(rleaves[lo:hi]))
                out.extend(o)
                new_res.extend(r)
            return (jax.tree.unflatten(treedef, out),
                    jax.tree.unflatten(treedef, new_res))

        # comp appears in both maps; XLA CSEs the duplicate add
        q_tree = jax.tree.map(
            lambda x, r: (x.astype(jnp.float32) + r).astype(jnp.bfloat16),
            tree, residual)
        new_residual = jax.tree.map(
            lambda x, r, q: (x.astype(jnp.float32) + r)
            - q.astype(jnp.float32),
            tree, residual, q_tree)
        axis = self.axis
        out = jax.tree.map(
            lambda q, x: self._bf16_sum(q, axis).astype(x.dtype),
            q_tree, tree)
        if self.avg:
            n = self._axis_size()
            out = jax.tree.map(lambda x: x / n, out)
        return out, new_residual


# ---------------------------------------------------------------------------
# Async-rule merge arithmetic (EASGD / ASGD / GOSGD)
#
# In the reference these were tiny Theano functions compiled on the
# worker/server GPUs and driven by MPI Sendrecv of GPU buffers
# (SURVEY.md §2.5, §3.3).  Here they are pure jitted pytree ops; the
# host-side rule actors in theanompi_tpu/rules move the data.
# ---------------------------------------------------------------------------


@partial(jax.jit, donate_argnums=(0,))
def easgd_worker_update(worker: PyTree, center: PyTree, alpha) -> PyTree:
    """worker <- worker - alpha * (worker - center)  (SURVEY.md §2.3)."""
    return jax.tree.map(lambda w, c: w - alpha * (w - c), worker, center)


@partial(jax.jit, donate_argnums=(0,))
def easgd_center_update(center: PyTree, worker: PyTree, alpha) -> PyTree:
    """center <- center + alpha * (worker - center)  (SURVEY.md §2.3)."""
    return jax.tree.map(lambda c, w: c + alpha * (w - c), center, worker)


@jax.jit
def easgd_both_updates(worker: PyTree, center: PyTree, alpha):
    """One fused elastic exchange: returns (new_worker, new_center).

    The reference did this as one MPI Sendrecv + two GPU kernels; fusing
    both sides into one jitted call halves the host round-trips.
    """
    new_w = jax.tree.map(lambda w, c: w - alpha * (w - c), worker, center)
    new_c = jax.tree.map(lambda c, w: c + alpha * (w - c), center, worker)
    return new_w, new_c


@jax.jit
def easgd_center_update_n(center: PyTree, worker_mean: PyTree,
                          alpha_eff) -> PyTree:
    """Aggregated center move (hierarchical exchange,
    ``parallel/aggregate.py``): ``center + alpha_eff*(mean - center)``
    with ``alpha_eff = n*alpha`` — the closed-form composition of n
    same-version elastic exchanges.  Deliberately NON-donating: the
    caller returns the pre-update ``center`` to the aggregator, which
    computes each worker's own elastic pull against it."""
    return jax.tree.map(lambda c, m: c + alpha_eff * (m - c),
                        center, worker_mean)


@partial(jax.jit, donate_argnums=(0,))
def easgd_apply_delta(current: PyTree, snapshot: PyTree,
                      returned: PyTree) -> PyTree:
    """Overlapped-EASGD correction (rules/async_rules.py overlap mode).

    The exchange thread shipped ``snapshot`` (the params at submit
    time) and got back ``returned = snapshot - alpha*(snapshot -
    center)``; meanwhile the worker trained on.  The elastic force the
    server computed is ``delta = snapshot - returned = alpha*(snapshot
    - center)`` — apply it to the params the worker has NOW:
    ``current - delta``.  This is the classic staleness-1 elastic
    update: same force, applied one exchange period late, bounded by
    the pipe's max-1-outstanding barrier."""
    return jax.tree.map(lambda c, s, r: c - (s - r),
                        current, snapshot, returned)


@partial(jax.jit, donate_argnums=(0,))
def asgd_apply_grads(center: PyTree, grads: PyTree, lr) -> PyTree:
    """Parameter-server SGD step: center <- center - lr * grads."""
    return jax.tree.map(lambda c, g: c - lr * g, center, grads)


@jax.jit
def gosgd_merge(own: PyTree, own_w, recv: PyTree, recv_w):
    """Gossip merge (Blot et al., SURVEY.md §2.3):

    receiver params <- weighted average of (own, received) by their
    scalar weights; receiver weight <- own_w + recv_w.
    """
    total = own_w + recv_w
    merged = jax.tree.map(
        lambda a, b: (own_w * a + recv_w * b) / total, own, recv
    )
    return merged, total


#: optimizer-state fields that hold FIRST-moment information (gradient
#: direction memory) — the slots a gossip merge must scale.  Second
#: moments (adam/rmsprop ``nu``) are deliberately NOT here: shrinking a
#: curvature estimate toward zero while its bias-correction ``count``
#: stays put would make the next preconditioned step
#: mu_hat/sqrt(nu_hat) BLOW UP at exactly the teleported point —
#: the opposite of the stabilization this exists for.
_FIRST_MOMENT_FIELDS = frozenset({"trace", "mu", "mean", "momentum"})


def gosgd_scale_momentum(opt_state: PyTree, frac: float) -> PyTree:
    """Scale the optimizer's first-moment slots by the receiver's
    share of a gossip merge.

    The merge teleports params toward the sender when recv_w >> own_w,
    but the local momentum buffer was accumulated along the OLD
    trajectory — applying it unscaled at the new point is the measured
    divergence mode of gossip over slow links (docs/SCALING.md: loss
    5-9 vs the 2.3 random floor at momentum 0.9, stable at 0).
    Treating momentum like params in the weighted average — with the
    sender's (unshipped) momentum taken as zero — scales it by
    own_w/total: a small merge barely touches it, a dominating push
    resets it.

    Slots are matched by state-field NAME (optax state namedtuples:
    sgd/momentum ``trace``, adam/adamw ``mu``, adabelief-style
    ``mean``); everything else — second moments, counts, injected
    hyperparams — is kept, which is the conservative direction (``keep``
    was the reference's raw behavior).  A cheap path-walk per message,
    no optimizer re-initialization."""
    from jax import tree_util as jtu

    def scale(path, leaf):
        names = {p.name for p in path if isinstance(p, jtu.GetAttrKey)}
        if names & _FIRST_MOMENT_FIELDS:
            return leaf * frac
        return leaf

    return jtu.tree_map_with_path(scale, opt_state)
