"""Shared layer library for the model zoo.

Parity rebuild of the reference's ``theanompi/models/layers2.py``
(SURVEY.md §2.8 — mount empty, no file:line): Conv (with channel
grouping), pooling, LRN, BatchNorm, Dropout, FC, softmax head, plus
the era-appropriate weight initializers.  Built on flax.linen; the
grouped convolution that the reference routed to cuDNN groups maps to
XLA's ``feature_group_count``, and LRN is composed from XLA ops
(theanompi_tpu.ops.lrn).

Everything is NHWC and defaults to float32 params with configurable
compute dtype — pass ``dtype=jnp.bfloat16`` to run the matmul/conv
FLOPs on the MXU in bf16 while keeping fp32 master params.
"""

from __future__ import annotations

import functools
import logging
from typing import Any, Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen import dtypes as _flax_dtypes
from jax import lax

from theanompi_tpu.ops.fused_bn import scale_bias_act
from theanompi_tpu.ops.lrn import lrn

Dtype = Any

_log = logging.getLogger(__name__)

# -- reference-era initializers (gaussian std + constant bias) --


def gaussian_init(std: float = 0.01):
    def init(key, shape, dtype=jnp.float32):
        return std * jax.random.normal(key, shape, dtype)
    return init


def constant_init(v: float = 0.0):
    def init(key, shape, dtype=jnp.float32):
        return jnp.full(shape, v, dtype)
    return init


he_init = nn.initializers.he_normal
xavier_init = nn.initializers.xavier_uniform


class Conv(nn.Module):
    """Convolution with optional channel grouping + LRN + pooling —
    mirroring the reference's fused ConvPoolLRN layer blocks."""

    features: int
    kernel: tuple[int, int]
    strides: tuple[int, int] = (1, 1)
    padding: str | Sequence[tuple[int, int]] = "SAME"
    groups: int = 1
    use_bias: bool = True
    kernel_init: Callable = nn.initializers.he_normal()
    bias_init: Callable = constant_init(0.0)
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        return nn.Conv(
            features=self.features,
            kernel_size=self.kernel,
            strides=self.strides,
            padding=self.padding,
            feature_group_count=self.groups,
            use_bias=self.use_bias,
            kernel_init=self.kernel_init,
            bias_init=self.bias_init,
            dtype=self.dtype,
        )(x)


def max_pool(x, window: int = 3, stride: int = 2, padding="VALID"):
    return nn.max_pool(x, (window, window), (stride, stride), padding)


def avg_pool(x, window: int = 3, stride: int = 2, padding="VALID"):
    return nn.avg_pool(x, (window, window), (stride, stride), padding)


def global_avg_pool(x):
    return jnp.mean(x, axis=(1, 2))


class LRN(nn.Module):
    """Cross-channel local response normalization (AlexNet/GoogLeNet)."""

    n: int = 5
    k: float = 2.0
    alpha: float = 1e-4
    beta: float = 0.75

    @nn.compact
    def __call__(self, x):
        return lrn(x, self.n, self.k, self.alpha, self.beta)


class BatchNormAct(nn.Module):
    """BatchNorm with a fusable activation/residual epilogue.

    Drop-in for ``nn.BatchNorm`` (+ a following relu / residual add):
    the variable layout is IDENTICAL to flax's — params ``scale``/
    ``bias``, batch_stats ``mean``/``var`` — so a module that pins the
    instance name (``name='BatchNorm_0'``) swaps implementations
    without moving a single leaf of the param tree, and checkpoints
    stay loadable across the ``impl`` knob.

    ``impl='xla'`` (default) reproduces today's unfused composition
    bit-for-bit: flax-style normalize (f32 stats, fast variance,
    ``maximum(0, E[x^2]-E[x]^2)``), cast to the compute dtype, then
    ``+ residual`` and relu as separate ops for XLA to fuse as it sees
    fit.  ``impl='pallas'`` folds the affine
    (``scale*rsqrt(var+eps)``, ``bias - mean*scale_eff``) and runs the
    whole epilogue as ONE Pallas stream over the activation
    (ops/fused_bn.py) — the batch-stat reductions stay XLA either way.
    This is the seam the MFU account's 5.81 ms of loop-fusion HBM
    traffic funnels through (artifacts/fusion_deepdive.json).

    ``act`` is ``None`` or ``'relu'``; ``residual`` (same shape as x)
    is added before the activation — the bottleneck-exit
    ``relu(bn(y) + shortcut)`` pattern.
    """

    use_running_average: bool = False
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: Dtype | None = None
    param_dtype: Dtype = jnp.float32
    axis_name: str | None = None
    act: str | None = None
    impl: str = "xla"            # 'xla' | 'pallas' (ModelConfig.bn_act_impl)
    scale_init: Callable = nn.initializers.ones
    bias_init: Callable = nn.initializers.zeros

    @nn.compact
    def __call__(self, x, residual=None):
        features = x.shape[-1]
        scale = self.param("scale", self.scale_init, (features,),
                           self.param_dtype)
        bias = self.param("bias", self.bias_init, (features,),
                          self.param_dtype)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda s: jnp.zeros(s, jnp.float32),
                                (features,))
        ra_var = self.variable("batch_stats", "var",
                               lambda s: jnp.ones(s, jnp.float32),
                               (features,))
        if self.use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            # flax _compute_stats semantics: f32 reductions, fast
            # variance clipped at zero, mean+mean2 stacked into ONE
            # pmean when cross-replica (sync_bn)
            xf = x.astype(jnp.float32)
            axes = tuple(range(x.ndim - 1))
            mean = xf.mean(axes)
            mean2 = (xf * xf).mean(axes)
            if self.axis_name is not None and not self.is_initializing():
                mean, mean2 = lax.pmean(jnp.stack([mean, mean2]),
                                        self.axis_name)
            var = jnp.maximum(0.0, mean2 - mean * mean)
            if not self.is_initializing():
                ra_mean.value = (self.momentum * ra_mean.value
                                 + (1 - self.momentum) * mean)
                ra_var.value = (self.momentum * ra_var.value
                                + (1 - self.momentum) * var)
        out_dtype = _flax_dtypes.canonicalize_dtype(x, scale, bias,
                                                    dtype=self.dtype)
        if self.impl == "xla":
            # exactly flax _normalize + the models' epilogue ops, so
            # the default path is numerically unchanged
            mul = lax.rsqrt(var + self.epsilon) * scale
            y = (x - mean) * mul + bias
            y = jnp.asarray(y, out_dtype)
            if residual is not None:
                y = y + residual
            if self.act == "relu":
                y = nn.relu(y)
            return y
        scale_eff = scale * lax.rsqrt(var + self.epsilon)
        bias_eff = bias - mean * scale_eff
        return scale_bias_act(x, scale_eff, bias_eff, residual=residual,
                              act=self.act, impl=self.impl,
                              out_dtype=out_dtype)


class BiasAct(nn.Module):
    """Per-channel bias + activation — the conv epilogue of the BN-free
    zoo members (VGG, GoogLeNet).  With ``impl='pallas'`` the bias add
    and relu run as one fused stream (``scale=1`` through
    ops/fused_bn.py); ``impl='xla'`` matches ``nn.Conv``'s own bias-add
    (compute-dtype add) followed by relu.  NOTE: fusing moves the bias
    param from ``Conv_*/bias`` to this module's ``bias`` — the param
    TREE differs between a model built with fusion on vs off (unlike
    BatchNormAct, whose layout is pinned), so flip the knob at model
    build, not mid-run.
    """

    features: int
    bias_init: Callable = nn.initializers.zeros
    act: str | None = "relu"
    impl: str = "xla"

    @nn.compact
    def __call__(self, x):
        bias = self.param("bias", self.bias_init, (self.features,),
                          jnp.float32)
        if self.impl == "xla":
            y = x + bias.astype(x.dtype)
            return nn.relu(y) if self.act == "relu" else y
        return scale_bias_act(x, jnp.ones_like(bias), bias, act=self.act,
                              impl=self.impl, out_dtype=x.dtype)


class BatchNorm(nn.Module):
    """BN with the running stats in the 'batch_stats' collection.

    Cross-replica note: per-shard batch stats are averaged over the
    data axis by the BSP step (parallel/bsp.py pmean of model_state),
    which matches the reference's per-worker BN closely enough while
    keeping state replicated.  ``axis_name`` switches to TRUE
    cross-replica stats (pmean of mean/var inside the BN), mirroring
    the knob ResNet wires from ModelConfig.sync_bn (resnet50.py uses
    flax nn.BatchNorm directly; this wrapper exposes the same choice
    to zoo models built from the layer toolkit): required when the
    per-shard batch is too small for its statistics to serve eval.

    WIRING OBLIGATION (ADVICE r4): ``ModelConfig.sync_bn`` does NOT
    reach this wrapper automatically — a ``build_module()`` that uses
    it must pass ``axis_name=self._bn_axis()`` (models/base.py), or
    ``sync_bn=True`` silently keeps per-shard stats.  The ResNet
    family and the BN-variant toolkit zoo (``ModelConfig.batch_norm``:
    VGG16/VGG19, GoogLeNet, AlexNet) all thread the knob — any NEW
    zoo model using this wrapper inherits the obligation.  ``TpuModel``
    warns at compile when a ``uses_batchnorm`` model has a small
    per-shard batch and ``sync_bn`` off.  Regression:
    tests/test_model_zoo.py::TestLayersBatchNormSyncWiring and
    ::TestZooBatchNormVariants (per-model bn_axis threading)."""

    use_running_average: bool = False
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: Dtype = jnp.float32
    axis_name: str | None = None
    #: optional fused epilogue (BatchNormAct): act None|'relu', impl
    #: 'xla'|'pallas'.  The inner module is pinned to the name flax
    #: auto-assigned before this seam existed ('BatchNorm_0'), so the
    #: param tree is byte-identical to the old nn.BatchNorm wrapper.
    act: str | None = None
    impl: str = "xla"

    @nn.compact
    def __call__(self, x, residual=None):
        return BatchNormAct(
            use_running_average=self.use_running_average,
            momentum=self.momentum,
            epsilon=self.epsilon,
            dtype=self.dtype,
            axis_name=self.axis_name,
            act=self.act,
            impl=self.impl,
            name="BatchNorm_0",
        )(x, residual=residual)


class Dense(nn.Module):
    features: int
    kernel_init: Callable = gaussian_init(0.005)
    bias_init: Callable = constant_init(0.0)
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        return nn.Dense(
            self.features,
            kernel_init=self.kernel_init,
            bias_init=self.bias_init,
            dtype=self.dtype,
        )(x)


class Dropout(nn.Module):
    rate: float = 0.5

    @nn.compact
    def __call__(self, x, train: bool):
        return nn.Dropout(self.rate, deterministic=not train)(x)


# -- loss / metric heads (the reference's softmax layer + error calc) --


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array,
                          label_smoothing: float = 0.0) -> jax.Array:
    """Mean CE over the batch; labels are integer class ids.

    ``label_smoothing=eps`` mixes the one-hot target with uniform:
    target = (1-eps)*onehot + eps/K — the standard regularizer of the
    modern 90-epoch ResNet recipes (0.1)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    ll = jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=1)
    nll = -jnp.mean(ll)
    if label_smoothing:
        eps = label_smoothing
        # -mean over batch of [ (1-eps)*logp_y + eps * mean_k logp_k ]
        return (1.0 - eps) * nll - eps * jnp.mean(logp)
    return nll


def _token_block(n: int, block: int) -> int:
    """The largest divisor of ``n`` that is at most ``block``."""
    block = min(block, n)
    while n % block:
        block -= 1
    return block


@functools.lru_cache(maxsize=None)
def _log_block_plan(tokens: int, block: int, vocab: int) -> None:
    """The blocked loss always engages, so its counter is its plan: one
    line a shape (the cache is the once-a-shape memory; trace time
    only), as ``ops/attention.py`` says "n of m tiles"."""
    _log.info("loss in %d blocks of %d tokens x %d", tokens // block, block,
              vocab)


def _xent_blocks(h, weight, bias, labels, weights, block: int,
                 vocab_axis: int, smoothing: float, with_grad: bool):
    """The one scan over token blocks behind
    ``blocked_softmax_cross_entropy``: a block's float32 logits, its
    summed loss and top-1 misses and, ``with_grad``, its gradients in
    the same pass (``softmax - target`` gives the block's ``d_h`` and
    its part of the weight's and the bias's gradient, accumulated in
    float32 across blocks), so that no block's logits outlive it.

    ``weights (tokens,)`` or None: with them a block's loss is ``sum_i
    w_i l_i``, its ``d_logits`` is ``w_i (softmax - target)_i``, and
    each token's own loss and miss come back beside the block's sums
    (the losses are the weights' cotangent).  None is a static branch
    that leaves the unweighted program as it was.

    The weight is contracted as it lies: ``vocab_axis`` only picks the
    dimension numbers of the three products, so neither layout pays a
    transpose of the weight or of its float32 gradient."""
    d = h.shape[-1]
    vocab = weight.shape[vocab_axis]
    w_c = weight.astype(h.dtype)
    over_d = (((1,), (1 - vocab_axis,)), ((), ()))
    over_vocab = (((1,), (vocab_axis,)), ((), ()))
    over_tokens = (((0,), (0,)), ((), ()))
    dot = functools.partial(lax.dot_general,
                            preferred_element_type=jnp.float32)

    def one(carry, args):
        hb, yb, *wb = args                  # wb: [] or the block's weights
        logits = dot(hb, w_c, over_d)
        if bias is not None:
            logits = logits + bias.astype(jnp.float32)
        top = jnp.max(logits, axis=-1, keepdims=True)
        exp = jnp.exp(logits - top)
        total = jnp.sum(exp, axis=-1, keepdims=True)
        column = lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        hit = column == yb[:, None]
        picked = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
        lse = jnp.log(total[:, 0]) + top[:, 0]
        token_loss = lse - picked
        if smoothing:
            # (1 - eps) * nll - eps * mean_k logp_k, logp = logits - lse
            token_loss = ((1.0 - smoothing) * token_loss
                          + smoothing * (lse - jnp.mean(logits, axis=-1)))
        loss = jnp.sum(wb[0] * token_loss if wb else token_loss)
        # argmax's answer (the FIRST index of the maximum) as a plain
        # float32 min-reduce, which XLA fuses with the row's other
        # reductions; ``jnp.argmax`` is a variadic reduce and kept a
        # pass over the block's logits to itself, as an int32 min did
        # (2.19 ms a step at GPT-2-medium's 412 M logits, PERF.md
        # section 6 PR 31).  A row with a NaN counts as a miss; its
        # loss is NaN anyway
        first = jnp.min(jnp.where(logits == top, column.astype(jnp.float32),
                                  float(vocab)), axis=-1)
        token_miss = (first != yb.astype(jnp.float32)).astype(jnp.float32)
        # per token only where the caller weighs tokens
        sums = (loss, jnp.sum(token_miss), *((token_loss, token_miss)
                                             if wb else ()))
        if not with_grad:
            return carry, sums
        d_weight, d_bias = carry
        target = hit.astype(jnp.float32)
        if smoothing:
            target = (1.0 - smoothing) * target + smoothing / vocab
        d_logits32 = exp / total - target                      # (blk, V)
        if wb:
            d_logits32 = wb[0][:, None] * d_logits32
        d_logits = d_logits32.astype(h.dtype)
        d_hb = dot(d_logits, w_c, over_vocab).astype(h.dtype)
        # the operands' order gives the gradient the weight's layout
        pair = (d_logits, hb) if vocab_axis == 0 else (hb, d_logits)
        d_weight = d_weight + dot(*pair, over_tokens)
        if bias is not None:
            d_bias = d_bias + jnp.sum(d_logits32, axis=0)
        return (d_weight, d_bias), (*sums, d_hb)

    carry = None
    if with_grad:
        carry = (jnp.zeros(weight.shape, jnp.float32),
                 None if bias is None else jnp.zeros(bias.shape, jnp.float32))
    blocks = (h.reshape(-1, block, d), labels.reshape(-1, block))
    if weights is not None:
        blocks += (weights.astype(jnp.float32).reshape(-1, block),)
    return lax.scan(one, carry, blocks)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _xent_sums(h, weight, bias, labels, weights, block: int, vocab_axis: int,
               smoothing: float):
    """Summed token cross-entropy and summed top-1 misses; with
    ``weights`` the sum is weighted and each token's loss and miss
    follow, ``(tokens,)`` each."""
    _, (loss, miss, *per_token) = _xent_blocks(
        h, weight, bias, labels, weights, block, vocab_axis, smoothing,
        with_grad=False)
    return (loss.sum(), miss.sum(), *(x.reshape(-1) for x in per_token))


def _xent_sums_fwd(h, weight, bias, labels, weights, block: int,
                   vocab_axis: int, smoothing: float):
    """The loss is the end of the program (or, weighted, a term of its
    last sum), so its gradient is taken in the forward's pass over the
    blocks; the backward only scales by the incoming cotangent.  The
    tokens' own losses are the weights' gradient."""
    (d_weight, d_bias), (loss, miss, *per_token, d_h) = _xent_blocks(
        h, weight, bias, labels, weights, block, vocab_axis, smoothing,
        with_grad=True)
    per_token = tuple(x.reshape(-1) for x in per_token)
    return ((loss.sum(), miss.sum(), *per_token),
            (d_h.reshape(h.shape), d_weight.astype(weight.dtype),
             None if bias is None else d_bias.astype(bias.dtype),
             per_token[0].astype(weights.dtype) if per_token else None))


def _xent_sums_bwd(block: int, vocab_axis: int, smoothing: float, res,
                   cotangents):
    del block, vocab_axis, smoothing
    d_h, d_weight, d_bias, d_weights = res
    g = cotangents[0]     # misses and the tokens' own values carry none
    return ((g * d_h).astype(d_h.dtype), (g * d_weight).astype(d_weight.dtype),
            None if d_bias is None else (g * d_bias).astype(d_bias.dtype),
            None,
            None if d_weights is None else (g * d_weights).astype(
                d_weights.dtype))


_xent_sums.defvjp(_xent_sums_fwd, _xent_sums_bwd)

#: tokens a block of the blocked loss (the largest divisor of the token
#: count at or under it); chip readings in PERF.md §6 PR 31
_LOSS_BLOCK_TOKENS = 2048


def blocked_softmax_cross_entropy(h: jax.Array, weight: jax.Array,
                                  bias: jax.Array | None, labels: jax.Array,
                                  *, vocab_axis: int,
                                  weights: jax.Array | None = None,
                                  label_smoothing: float = 0.0,
                                  block_tokens: int = _LOSS_BLOCK_TOKENS):
    """Mean token cross-entropy and top-1 error of an output head,
    ``logits = h @ W (+ bias)``, without ever holding the whole
    ``(tokens, vocab)`` logits: the tokens pass in blocks (the largest
    divisor of their count up to ``block_tokens``), forward and
    gradient in one pass under a ``custom_vjp`` (``_xent_blocks``).

    * ``h (tokens, d)`` in the compute dtype; its gradient comes back
      in that dtype.
    * ``weight``: the head's MASTER weights, cast to ``h.dtype`` for the
      products, in the layout the caller's tree has: ``vocab_axis=0``
      for a ``(vocab, d)`` table (a head tied to the embedding,
      ``ZayaLM``), ``vocab_axis=1`` for a ``(d, vocab)`` kernel
      (``nn.Dense``, ``TransformerLM``).  That is a fact of the tree,
      not a setting: neither layout is transposed.  ``bias (vocab,)``
      or None, added to the float32 logits.  Both gradients are
      accumulated in float32 and come back in the masters' dtypes.
    * ``labels (tokens,)`` integer ids.
    * ``label_smoothing=eps`` (a static Python float; no work at 0) is
      ``softmax_cross_entropy``'s: target ``(1-eps) * onehot + eps/V``.

    * ``weights (tokens,)`` or None (a static choice; None is the plain
      mean): each token's loss weighed by a number the caller
      differentiates too, as a looped model's exit distribution over
      its passes' stacked states (``OuroLM``).  ``softmax - target`` is
      scaled by it in the same pass, and the tokens' own losses are its
      gradient.

    A block's logits leave the MXU's accumulator in float32 and stay so
    through the softmax; ``softmax - target`` is cast to ``h.dtype``
    for the two gradient products.  Returns ``(loss, error)``, float32
    scalars; ``error`` is ``error_rate``'s (``argmax != label``, first
    index on ties) and carries no gradient.  With ``weights`` it
    returns ``(sum_i w_i l_i, token losses, token misses)``: the sum as
    it is (the weights carry the caller's normalisation) and, float32
    ``(tokens,)`` each and without a gradient of their own, every
    token's ``l_i`` and whether its argmax missed."""
    if vocab_axis not in (0, 1) or weight.shape[1 - vocab_axis] != h.shape[-1]:
        raise ValueError(f"weight {weight.shape} with vocab_axis={vocab_axis} "
                         f"does not contract with h {h.shape}")
    n = h.shape[0]
    if weights is not None and weights.shape != (n,):
        raise ValueError(f"weights {weights.shape} for {n} tokens: one "
                         "weight a token")
    block = _token_block(n, block_tokens)
    _log_block_plan(n, block, weight.shape[vocab_axis])
    loss, miss, *per_token = _xent_sums(
        h, weight, bias, labels.astype(jnp.int32), weights, block, vocab_axis,
        float(label_smoothing))
    if weights is None:
        return loss / n, miss / n
    return (loss, *per_token)


def error_rate(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Top-1 error (the reference's per-iteration 'error')."""
    return jnp.mean((jnp.argmax(logits, axis=-1) != labels).astype(jnp.float32))


def topk_error(logits: jax.Array, labels: jax.Array, k: int = 5) -> jax.Array:
    """Top-k error (the reference tracked top-5 for ImageNet).

    k is clamped to the class count: a top5-tracking recipe pointed at
    a <5-class dataset (e.g. a tiny smoke config inheriting the
    ResNet-50 recipe's ``track_top5=True``) must degrade to top-K over
    all classes, not crash in ``lax.top_k`` (round-3 verdict weak #3).
    The clamp is static — ``logits.shape[-1]`` is a trace-time
    constant — so it costs nothing under jit."""
    k = min(k, logits.shape[-1])
    topk = jax.lax.top_k(logits, k)[1]
    hit = jnp.any(topk == labels[:, None], axis=-1)
    return 1.0 - jnp.mean(hit.astype(jnp.float32))
