"""Qwen3-Next-style hybrid causal LM: Gated DeltaNet linear attention
three layers in four, gated softmax attention at a head of 256 in the
fourth, and in every layer a softmax top-k expert layer beside a gated
shared expert.

The language model of ``Qwen/Qwen3-Next-80B-A3B-Instruct``
(``model_type`` ``qwen3_next``) as ``benchmarks/configs/
qwen3_next_80b.json`` states it, on the same spine as the rest of the
zoo (``TpuModel``: ``begin_epoch`` / ``train_iter`` / ``_flush_metrics``,
AdamW, the BSP step).  Layer ``i`` is full attention where ``(i + 1) %
full_attention_interval == 0`` and Gated DeltaNet elsewhere; every
layer is two pre-norm residual blocks::

    h = x + mixer(norm_in(x));    out = h + moe(norm_post(h))

Every RMSNorm but the gated one is ZERO-CENTRED: ``x_hat * (1 + w)``
with ``w`` starting at 0, float32 inside.  With ``u (B, T, d)`` the
normed input:

* **Gated DeltaNet** (``GatedDeltaNetMixer``): ``[q k v z] = u W_qkvz``
  laid out by key head, ``[q (dk) | k (dk) | v (r dv) | z (r dv)]`` for
  each of the ``nk`` key heads with ``r = nv / nk``, and ``[b a] = u
  W_ba`` the same way (``r`` each); ``qkv = silu(causal depthwise
  conv(qkv))`` without bias; ``q``, ``k`` L2-normalised per head (eps
  1e-6), ``q`` scaled by ``dk^-1/2``, both repeated to the value heads
  (key head ``j`` serves value heads ``j r .. j r + r - 1``); ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``, float32;
  the gated delta rule (``ops/gated_delta.py``, chunked); ``y =
  RMSNorm_dv(o) w * silu(z)``, a norm per head, then the gate; ``out =
  y W_out``.
* **Gated attention** (``GatedAttentionMixer``): ``q_proj`` gives each
  of ``H`` heads ``[q (D) | gate (D)]``; ``q`` and ``k`` zero-centred
  RMSNorm over the head; RoPE (rotate-half, ``rope_theta``) on the first
  ``partial_rotary_factor D`` of each head, in XLA; causal grouped-query
  attention (``ops/attention.py``'s kernels, named
  ``qwen3_next_attention``); ``o * sigmoid(gate)``, then ``o_proj``.
* **Experts** (``SparseMoe``): ``p = softmax(float32(u) W_r)`` over ALL
  ``n_experts``; a token's ``top_k`` experts, chosen over ``u W_r +
  bias``, weighted by ``p`` renormalised over the ``top_k``; an expert
  ``(silu(u W_g) * u W_u) W_d`` (``parallel/expert.py routed_experts``,
  told which experts THIS chip holds; tokens routed elsewhere get
  nothing from the routed part).  Beside it a shared expert of the same
  form, scaled by ``sigmoid(u w_sg)``.  The correction ``bias`` is no
  parameter: a controller moves it after every step against each
  expert's excess load (``router_state``, as ``ZayaLayer``'s); the
  published model balances by the loss alone, but the cut needs both
  (``BALANCE_GAIN``).
* **Head and loss**: a final zero-centred norm, the untied ``(d,
  vocab)`` head through ``layers.blocked_softmax_cross_entropy``, plus
  ``aux_loss_coef`` times the Switch balancing loss of all the layers'
  routers taken together, ``E sum_e f_e P_e``: ``f_e`` the share of all
  the layers' assignments that chose expert ``e``, ``P_e`` its mean
  probability over all the layers' tokens (the family's
  ``load_balancing_loss_func`` over the concatenated router logits).

``ModelConfig.remat`` recomputes each layer in the backward pass.  What
the published ``config.json`` leaves open is listed under ``assumed`` in
the configuration file; ``benchmarks/reference/qwen3_next_80b.py`` is
the same function in plain ``jax.numpy``, its rule stepped a token at a
time.

Tracing: ``jax.named_scope``s ``qwen3_next/linear_attention`` (with
``qwen3_next/linear_attention/delta_rule`` round the rule),
``qwen3_next/attention``, ``qwen3_next/router``,
``qwen3_next/experts``, ``qwen3_next/shared_expert`` and
``lm/loss`` (the head and its loss, as ``TransformerLM`` names them);
the kernels are ``qwen3_next_attention_{fwd,bwd}``
and ``qwen3_next_experts_{gate,up,down}_{gmm,gmm_t,tgmm}``; the delta
rule's plan is one log line a shape.  Each step's metrics carry the rows
this chip's experts multiplied, the rows of their buffers and the
balancing loss; ``_flush_metrics`` feeds them to ``monitor``
(``moe/held_rows``, ``moe/rows_elsewhere``, ``moe/max_expert_rows``,
``moe/held_share``, ``moe/buffer_rows``, ``moe/buffer_fill``,
``moe/aux_loss``) and appends them to this module's ``routing_log``
(docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import collections

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from theanompi_tpu.data.lm import SeqLM_data
from theanompi_tpu.models import layers as L
from theanompi_tpu.models.base import ModelConfig, TpuModel
from theanompi_tpu.models.nemotron_h import (NemotronHHead,
                                             causal_depthwise_conv)
from theanompi_tpu.models.zaya import rope
from theanompi_tpu.ops.attention import fused_attention
from theanompi_tpu.ops.gated_delta import gated_delta_chunked
from theanompi_tpu.parallel.expert import routed_experts
from theanompi_tpu.parallel.mesh import AXIS_DATA
from theanompi_tpu.utils.profiling import trace_running

#: what the held experts multiplied in this process's last flushes, one
#: entry a flush, newest last, as ``nemotron_h.routing_log`` has it
#: (``held_rows``, ``rows_elsewhere``, ``max_expert_rows``,
#: ``buffer_rows`` a flushed step each, ``n_layers``, ``top_k``,
#: ``expert_shape``, ``profiled``), and ``aux_loss`` a step
routing_log: collections.deque = collections.deque(maxlen=256)

_ROUTING_KEYS = ("moe_held_rows", "moe_rows_elsewhere",
                 "moe_max_expert_rows", "moe_buffer_rows", "moe_aux_loss")
#: the balancing controller's gain: after a step an expert's correction
#: bias moves by ``-BALANCE_GAIN * (its load / the mean load - 1)`` and
#: stays inside ``+-BIAS_LIMIT``, in log-probability units
#: (``ZayaLayer``'s constants).  The cut needs it: the 480 absent
#: experts add nothing to a token, so training moves the router onto the
#: 32 held ones (without it their share of the assignments went from 1/16
#: to 0.33-0.42 in 57 steps on the chip, PERF.md section 4)
BALANCE_GAIN = 0.5
BIAS_LIMIT = 30.0


def _dense(features: int, name: str, dtype, std: float = 0.02):
    return nn.Dense(features, use_bias=False,
                    kernel_init=L.gaussian_init(std), dtype=dtype, name=name)


def layer_kinds(n_layers: int, full_attention_interval: int) -> str:
    """``"L"`` (Gated DeltaNet) or ``"F"`` (full attention) a layer:
    layer ``i`` is full where ``(i + 1) % interval == 0``."""
    return "".join("F" if (i + 1) % full_attention_interval == 0 else "L"
                   for i in range(n_layers))


class ZeroCentredRMSNorm(nn.Module):
    """``x / rms(x) * (1 + w)`` over the last axis, float32 inside,
    ``w`` starting at 0."""

    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.zeros, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, -1, keepdims=True) + self.eps)
        return (normed * (1.0 + w)).astype(x.dtype)


def l2_normalise(x, eps: float = 1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, in float32."""
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, -1, keepdims=True) + eps)


def gated_rms_norm(y, z, weight, eps: float):
    """``RMSNorm(y) * w * silu(z)`` over the last axis: the norm first,
    then the gate; float32 inside, ``y.dtype`` out."""
    y32 = y.astype(jnp.float32)
    normed = y32 * jax.lax.rsqrt(jnp.mean(y32 * y32, -1, keepdims=True)
                                 + eps)
    return (normed * weight * jax.nn.silu(z.astype(jnp.float32))
            ).astype(y.dtype)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log A`` with ``A`` uniform in (0, 16]."""
    return jnp.log(16.0 * (1.0 - jax.random.uniform(key, shape, jnp.float32))
                   ).astype(dtype)


class GatedDeltaNetMixer(nn.Module):
    """The Gated DeltaNet mixer; see the module docstring."""

    d_model: int
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv_kernel: int = 4
    chunk: int = 64
    rms_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        b, t, _ = u.shape
        nk, nv, dk, dv = (self.key_heads, self.value_heads, self.key_dim,
                          self.value_dim)
        r = nv // nk
        with jax.named_scope("qwen3_next/linear_attention"):
            qkvz = _dense(2 * nk * dk + 2 * nv * dv, "in_proj_qkvz",
                          self.dtype)(u).reshape(b, t, nk, -1)
            q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + r * dv],
                                   axis=-1)
            ba = _dense(2 * nv, "in_proj_ba", self.dtype)(u).reshape(
                b, t, nk, 2 * r)
            beta_in, a = ba[..., :r].reshape(b, t, nv), \
                ba[..., r:].reshape(b, t, nv)
            qkv = jnp.concatenate([q.reshape(b, t, nk * dk),
                                   k.reshape(b, t, nk * dk),
                                   v.reshape(b, t, nv * dv)], axis=-1)
            kernel = self.param("conv_kernel", L.gaussian_init(
                self.conv_kernel ** -0.5), (self.conv_kernel, qkv.shape[-1]))
            qkv = nn.silu(causal_depthwise_conv(
                qkv, kernel.astype(self.dtype), 0.0))
            q, k, v = jnp.split(qkv, [nk * dk, 2 * nk * dk], axis=-1)
            q = jnp.repeat((l2_normalise(q.reshape(b, t, nk, dk))
                            * dk ** -0.5).astype(self.dtype), r, axis=2)
            k = jnp.repeat(l2_normalise(k.reshape(b, t, nk, dk)).astype(
                self.dtype), r, axis=2)
            a_log = self.param("A_log", _a_log_init, (nv,))
            dt_bias = self.param("dt_bias", nn.initializers.ones, (nv,))
            beta = jax.nn.sigmoid(beta_in.astype(jnp.float32))
            g = -jnp.exp(a_log) * jax.nn.softplus(
                a.astype(jnp.float32) + dt_bias)
            with jax.named_scope("delta_rule"):
                o = gated_delta_chunked(q, k, v.reshape(b, t, nv, dv), g,
                                        beta, chunk=self.chunk,
                                        name="qwen3_next_delta_rule")
            weight = self.param("norm_weight", nn.initializers.ones, (dv,))
            y = gated_rms_norm(o, z.reshape(b, t, nv, dv), weight,
                               self.rms_eps)
            return _dense(self.d_model, "out_proj", self.dtype)(
                y.reshape(b, t, nv * dv))


class GatedAttentionMixer(nn.Module):
    """Gated grouped-query attention with partial RoPE; see the module
    docstring."""

    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float
    rms_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        b, t, _ = u.shape
        hq, hk, dh, rot = (self.n_heads, self.n_kv_heads, self.head_dim,
                           self.rotary_dim)
        with jax.named_scope("qwen3_next/attention"):
            q, gate = jnp.split(_dense(2 * hq * dh, "q_proj", self.dtype)(
                u).reshape(b, t, hq, 2 * dh), 2, axis=-1)
            k = _dense(hk * dh, "k_proj", self.dtype)(u).reshape(b, t, hk, dh)
            v = _dense(hk * dh, "v_proj", self.dtype)(u).reshape(b, t, hk, dh)
            q = ZeroCentredRMSNorm(self.rms_eps, name="q_norm")(q)
            k = ZeroCentredRMSNorm(self.rms_eps, name="k_norm")(k)
            positions = jnp.arange(t)
            q = rope(q, positions, rot, self.rope_theta)
            k = rope(k, positions, rot, self.rope_theta)
            o = fused_attention(q, k, v, causal=True, scale=dh ** -0.5,
                                name="qwen3_next_attention")
            o = gated_output(o, gate)
            return _dense(self.d_model, "o_proj", self.dtype)(
                o.reshape(b, t, hq * dh))


def gated_output(o, gate):
    """Attention's output ``o * sigmoid(gate)``, the gate in float32."""
    return o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)


class SparseMoe(nn.Module):
    """Softmax top-k routed experts told their share, beside a gated
    shared expert; returns ``(out, stats)``, the stats with the layer's
    expert loads and summed probabilities for the balancing loss.  The
    choice is the top-k of ``logits + bias``, a correction bias that is
    state and no parameter (``router_state``); the weights are the
    unbiased probabilities.  A training pass moves the bias against each
    expert's excess load, as ``ZayaLayer``'s controller does."""

    n_experts: int
    top_k: int
    expert_width: int
    shared_width: int
    held_experts: tuple
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        b, t, d = u.shape
        count, f = self.held_experts[1], self.expert_width
        rows = u.reshape(b * t, d)
        with jax.named_scope("qwen3_next/router"):
            # float32 in earnest: on a TPU a float32 product runs in
            # bfloat16 passes unless told otherwise
            logits = nn.Dense(
                self.n_experts, use_bias=False, dtype=jnp.float32,
                kernel_init=L.gaussian_init(0.02),
                precision=jax.lax.Precision.HIGHEST, name="router")(rows)
            probs = jax.nn.softmax(logits, axis=-1)
        bias = self.variable("router_state", "bias", jnp.zeros,
                             (self.n_experts,), jnp.float32)
        experts = {name: self.param(f"experts_{name}", L.gaussian_init(0.02),
                                    shape)
                   for name, shape in (("gate", (count, d, f)),
                                       ("up", (count, d, f)),
                                       ("down", (count, f, d)))}
        with jax.named_scope("qwen3_next/experts"):
            out, stats = routed_experts(
                rows, probs, experts, self.held_experts, top_k=self.top_k,
                select_by=logits + bias.value, normalize=True,
                name="qwen3_next_experts")
        if (self.is_mutable_collection("router_state")
                and not self.is_initializing()):
            load = stats["expert_load"]
            bias.value = jnp.clip(
                bias.value - BALANCE_GAIN * (load / load.mean() - 1.0),
                -BIAS_LIMIT, BIAS_LIMIT)
        stats["prob_sum"] = probs.sum(0)
        with jax.named_scope("qwen3_next/shared_expert"):
            shared = GatedMLP(self.shared_width, self.dtype,
                              name="shared_expert")(rows)
            gate = _dense(1, "shared_expert_gate", self.dtype)(rows)
            out = out + jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
                shared.dtype) * shared
        return out.reshape(b, t, d), stats


class GatedMLP(nn.Module):
    """``(silu(x W_gate) * x W_up) W_down``."""

    width: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        hidden = nn.silu(_dense(self.width, "gate", self.dtype)(x)) * _dense(
            self.width, "up", self.dtype)(x)
        return _dense(x.shape[-1], "down", self.dtype)(hidden)


class Qwen3NextLayer(nn.Module):
    """``h = x + mixer(norm_in(x)); h + moe(norm_post(h))`` for the
    mixer ``kind`` names (``L`` / ``F``); returns ``(h, routing
    stats)``."""

    kind: str
    mixer: dict          # the mixer's fields
    moe: dict            # the expert layer's
    rms_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        u = ZeroCentredRMSNorm(self.rms_eps, name="input_norm")(x)
        if self.kind == "L":
            mixed = GatedDeltaNetMixer(**self.mixer, rms_eps=self.rms_eps,
                                       dtype=self.dtype,
                                       name="linear_attention")(u)
        else:
            mixed = GatedAttentionMixer(**self.mixer, rms_eps=self.rms_eps,
                                        dtype=self.dtype,
                                        name="attention")(u)
        h = x + mixed
        out, stats = SparseMoe(**self.moe, dtype=self.dtype, name="moe")(
            ZeroCentredRMSNorm(self.rms_eps, name="post_norm")(h))
        return h + out, stats


class Qwen3NextLMNet(nn.Module):
    """Token ids ``(B, T)`` -> ``(hidden (B, T, d) after the final norm,
    routing stats over the layers)``; the head is only declared.  The
    stats are summed over the layers, the fullest expert's rows are the
    largest, and ``aux_loss`` is the balancing loss of all the layers'
    routers together."""

    vocab: int
    d_model: int
    kinds: str
    mixers: dict         # kind -> that mixer's fields
    moe: dict
    rms_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    remat: bool = False

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        del train  # no dropout, no batch statistics
        x = nn.Embed(self.vocab, self.d_model,
                     embedding_init=L.gaussian_init(0.02),
                     name="embed")(tokens).astype(self.dtype)
        NemotronHHead(self.d_model, self.vocab, name="head")()
        # explicit names pin the tree to the layout without remat
        layer_cls = nn.remat(Qwen3NextLayer) if self.remat else Qwen3NextLayer
        held = elsewhere = fullest = buffer = jnp.zeros((), jnp.float32)
        load = prob_sum = jnp.zeros((self.moe["n_experts"],), jnp.float32)
        for i, kind in enumerate(self.kinds):
            x, stats = layer_cls(kind, self.mixers[kind], self.moe,
                                 self.rms_eps, self.dtype,
                                 name=f"Layer_{i}")(x)
            held += stats["held_rows"]
            elsewhere += stats["rows_elsewhere"]
            fullest = jnp.maximum(fullest, stats["max_expert_rows"])
            buffer += stats["buffer_rows"]
            load += stats["expert_load"]
            prob_sum += stats["prob_sum"]
        x = ZeroCentredRMSNorm(self.rms_eps, name="final_norm")(x)
        # Switch form over every layer's tokens at once: f_e the share
        # of the assignments, P_e the mean probability
        tokens_seen = len(self.kinds) * tokens.size
        aux = self.moe["n_experts"] * jnp.sum(
            jax.lax.stop_gradient(load) / tokens_seen
            * prob_sum / tokens_seen)
        return x, {"moe_held_rows": held, "moe_rows_elsewhere": elsewhere,
                   "moe_max_expert_rows": fullest,
                   "moe_buffer_rows": buffer, "moe_aux_loss": aux}


def delta_rule_macs(*, chunk: int, key_dim: int, value_dim: int) -> float:
    """Multiply-adds a token and a value head of the chunked rule's
    forward, as ``ops/gated_delta.py`` runs it: in the chunk the key
    Gram matrix ``K K^T`` and the scores ``Q K^T`` (``C dk`` each), the
    triangular solve for ``W`` and ``U`` (``(C - 1) (dk + dv) / 2``) and
    the scores applied to ``U'`` (``C dv``); against the state ``W S``,
    ``Q S`` and ``K^T U'`` (``dk dv`` each).  The benchmark's
    ``flops/qwen3_next_delta_rule.py`` counts the same work a chunk."""
    return (2 * chunk * key_dim + chunk * value_dim
            + (chunk - 1) * (key_dim + value_dim) / 2
            + 3 * key_dim * value_dim)


def qwen3_next_train_flops(*, d_model: int, vocab: int, seq_len: int,
                           n_layers: int, full_attention_interval: int,
                           linear_key_heads: int, linear_value_heads: int,
                           linear_key_dim: int, linear_value_dim: int,
                           chunk: int, n_experts: int, top_k: int,
                           expert_width: int, shared_width: int,
                           held_count: int, n_heads: int, n_kv_heads: int,
                           head_dim: int) -> float:
    """Trained FLOPs per SEQUENCE, 2xMAC units: 6 for every parameter
    applied to a token (forward 2, backward 4), by layer kind.  The one
    count: the benchmark's ``flops/qwen3_next.py`` hands out this
    function.

    * Gated DeltaNet: the three projections and the chunked rule's
      products (``delta_rule_macs``, at the chunk it runs); the
      convolution, the norms and the gates do no matmul work.
    * attention: the four projections (``q_proj`` twice as wide: the
      output gate), the score and value products counted CAUSALLY,
      ``6 H D s (s + 1)`` a layer.
    * every layer's expert part: the router, the shared expert and its
      gate, and the HELD experts at their EXPECTED share of the
      assignments, ``top_k x held_count / n_experts`` of a gated MLP.
    * the untied head (``d_model x vocab``).

    The recomputed forwards of ``remat`` are not counted."""
    kinds = layer_kinds(n_layers, full_attention_interval)
    nk, nv, dk, dv = (linear_key_heads, linear_value_heads, linear_key_dim,
                      linear_value_dim)
    linear = (d_model * (2 * nk * dk + 2 * nv * dv + 2 * nv)
              + nv * dv * d_model
              + nv * delta_rule_macs(chunk=min(chunk, seq_len), key_dim=dk,
                                     value_dim=dv))
    attention = (d_model * (2 * n_heads + 2 * n_kv_heads) * head_dim
                 + n_heads * head_dim * d_model)
    moe = (d_model * n_experts + 3 * d_model * shared_width + d_model
           + 3 * d_model * expert_width * top_k * held_count / n_experts)
    per_token = (kinds.count("L") * linear + kinds.count("F") * attention
                 + n_layers * moe + d_model * vocab)
    scores = (6.0 * kinds.count("F") * n_heads * head_dim
              * seq_len * (seq_len + 1))
    return 6.0 * per_token * seq_len + scores


class Qwen3NextLM(TpuModel):
    """Hybrid Gated DeltaNet / gated attention / expert LM over
    data-sharded batches; reference contract."""

    name = "qwen3_next_lm"
    batch_partition = P(AXIS_DATA)
    #: ``decode/kvcache.py`` holds keys and values only: a Gated DeltaNet
    #: layer decodes from a recurrent state and a convolution window
    decode_capable = False

    @classmethod
    def default_config(cls) -> ModelConfig:
        return ModelConfig(batch_size=8, n_epochs=5, optimizer="adamw",
                           learning_rate=3e-4, weight_decay=0.01,
                           lr_schedule="constant", print_freq=20)

    def __init__(self, *args, vocab: int = 256, seq_len: int = 128,
                 d_model: int = 64, n_layers: int = 4,
                 full_attention_interval: int = 4,
                 linear_key_heads: int = 2, linear_value_heads: int = 4,
                 linear_key_dim: int = 16, linear_value_dim: int = 16,
                 conv_kernel: int = 4, chunk: int = 64, n_experts: int = 16,
                 top_k: int = 4, expert_width: int = 32,
                 shared_width: int = 32, held_experts=None,
                 n_heads: int = 4, n_kv_heads: int = 2, head_dim: int = 32,
                 partial_rotary_factor: float = 0.25,
                 rope_theta: float = 1e7, rms_norm_eps: float = 1e-6,
                 aux_loss_coef: float = 1e-3, **kwargs):
        held = tuple(held_experts) if held_experts is not None \
            else (0, n_experts)
        if n_heads % n_kv_heads or linear_value_heads % linear_key_heads:
            raise ValueError(
                f"{n_heads} query heads over {n_kv_heads} key/value heads, "
                f"{linear_value_heads} value heads over {linear_key_heads} "
                "key heads: each shared head serves a whole number of heads")
        if top_k > n_experts:
            raise ValueError(f"top_k={top_k} of {n_experts} experts")
        rotary_dim = int(head_dim * partial_rotary_factor)
        self.aux_loss_coef = aux_loss_coef
        self._net_cfg = dict(
            vocab=vocab, seq_len=seq_len, d_model=d_model,
            kinds=layer_kinds(n_layers, full_attention_interval),
            rms_eps=rms_norm_eps,
            mixers={
                "L": dict(d_model=d_model, key_heads=linear_key_heads,
                          value_heads=linear_value_heads,
                          key_dim=linear_key_dim,
                          value_dim=linear_value_dim,
                          conv_kernel=conv_kernel, chunk=chunk),
                "F": dict(d_model=d_model, n_heads=n_heads,
                          n_kv_heads=n_kv_heads, head_dim=head_dim,
                          rotary_dim=rotary_dim, rope_theta=rope_theta)},
            moe=dict(n_experts=n_experts, top_k=top_k,
                     expert_width=expert_width, shared_width=shared_width,
                     held_experts=held))
        super().__init__(*args, **kwargs)
        self.train_flops_per_sample = qwen3_next_train_flops(
            d_model=d_model, vocab=vocab, seq_len=seq_len, n_layers=n_layers,
            full_attention_interval=full_attention_interval,
            linear_key_heads=linear_key_heads,
            linear_value_heads=linear_value_heads,
            linear_key_dim=linear_key_dim, linear_value_dim=linear_value_dim,
            chunk=chunk, n_experts=n_experts, top_k=top_k,
            expert_width=expert_width, shared_width=shared_width,
            held_count=held[1], n_heads=n_heads, n_kv_heads=n_kv_heads,
            head_dim=head_dim)

    def _input_dtype(self):
        return jnp.int32

    def build_data(self):
        c = self._net_cfg
        return SeqLM_data(vocab=c["vocab"], seq_len=c["seq_len"],
                          seed=self.config.seed)

    def build_module(self) -> nn.Module:
        c = dict(self._net_cfg)
        del c["seq_len"]
        return Qwen3NextLMNet(**c, dtype=self._compute_dtype(),
                              remat=self.config.remat)

    def _loss_and_error(self, params, model_state, batch, train: bool):
        """``(loss, error, routing counts, new model state)``: the loss is
        the cross-entropy plus ``aux_loss_coef`` times the balancing
        loss; a training pass lets the controller move its biases."""
        tokens, targets = batch
        variables = {"params": params, **model_state}
        if train:
            (h, routing), moved = self.module.apply(
                variables, tokens, mutable=["router_state"])
            model_state = {**model_state, **moved}
        else:
            h, routing = self.module.apply(variables, tokens)
        # the LM family's loss scope, which ``loss_share.tok`` reads
        with jax.named_scope("lm/loss"):
            loss, err = L.blocked_softmax_cross_entropy(
                h.reshape(-1, h.shape[-1]), params["head"]["kernel"],
                None, targets.reshape(-1), vocab_axis=1,
                label_smoothing=(self.config.label_smoothing if train
                                 else 0.0))
        return (loss + self.aux_loss_coef * routing["moe_aux_loss"], err,
                routing, model_state)

    def loss_fn(self, params, model_state, batch, rng):
        del rng  # no dropout
        loss, err, routing, model_state = self._loss_and_error(
            params, model_state, batch, train=True)
        return loss, (model_state, {"loss": loss, "error": err, **routing})

    def eval_fn(self, params, model_state, batch):
        loss, err, _, _ = self._loss_and_error(params, model_state, batch,
                                               train=False)
        return {"loss": loss, "error": err}

    def _flush_metrics(self, recorder) -> None:
        """The base flush, and the pending steps' routing counts to
        ``monitor`` and ``routing_log`` (they are device scalars until
        here; the flush is the fence anyway)."""
        from theanompi_tpu import monitor

        if self._pending:
            held, elsewhere, fullest, buffer, aux = (
                np.concatenate([np.atleast_1d(np.asarray(m[key]))
                                for _, m in self._pending])
                for key in _ROUTING_KEYS)
            c = self._net_cfg
            moe = c["moe"]
            routing_log.append({
                "held_rows": [float(x) for x in held],
                "rows_elsewhere": [float(x) for x in elsewhere],
                "max_expert_rows": [float(x) for x in fullest],
                "buffer_rows": [float(x) for x in buffer],
                "aux_loss": [float(x) for x in aux],
                "n_layers": len(c["kinds"]),
                "top_k": moe["top_k"],
                "expert_shape": (moe["held_experts"][1], c["d_model"],
                                 moe["expert_width"]),
                "profiled": trace_running()})
            monitor.inc("moe/held_rows", float(held.sum()))
            monitor.inc("moe/rows_elsewhere", float(elsewhere.sum()))
            monitor.set_gauge("moe/max_expert_rows", float(fullest.max()))
            monitor.inc("moe/buffer_rows", float(buffer.sum()))
            monitor.set_gauge("moe/buffer_fill",
                              float(held.sum() / buffer.sum()))
            monitor.set_gauge("moe/held_share", float(
                held.sum() / max(held.sum() + elsewhere.sum(), 1.0)))
            monitor.set_gauge("moe/aux_loss", float(aux[-1]))
        super()._flush_metrics(recorder)
