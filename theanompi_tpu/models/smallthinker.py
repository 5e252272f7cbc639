"""SmallThinker-style causal LM: global attention without positions
one layer in four beside sliding-window attention with RoPE, a router
that reads the layer's input before attention, and in every layer a
softmax top-k layer of ReGLU experts.

The language model of ``PowerInfer/SmallThinker-21BA3B-Instruct`` as
``benchmarks/configs/smallthinker_21b.json`` states it, on the same
spine as the rest of the zoo (``TpuModel``: ``begin_epoch`` /
``train_iter`` / ``_flush_metrics``, AdamW, the BSP step).  Layer ``i``
rotates q and k where ``rope_layout[i]`` is 1 and attends over a window
of ``window`` keys where ``sliding_window_layout[i]`` is 1 (the
published layouts: ``[0, 1, 1, 1]`` a period, so layer ``4j`` is global
causal attention with no position signal and the three after it
windowed with RoPE).  With ``x`` the layer's input and ``rms(x, w) = x
/ sqrt(mean(x^2) + eps) * w`` (float32 inside, ``w`` from 1)::

    u = rms(x, w_in)
    p = softmax(u W_r)                      over ALL the experts, float32
    q, k, v = u W_q, u W_k, u W_v           GQA, no bias, no q/k norm
    q, k = rope(q), rope(k)                 rotate-half, theta; where rope_layout
    a = causal softmax(q k^T / sqrt(D)) v   query i sees keys (i - W, i] where
                                            sliding_window_layout, else [0, i]
    h = x + a W_o
    v = rms(h, w_post)
    out = h + sum over the top_k e of p (renormalised over them) of
          (relu(v G_e) * v U_e) D_e         ReGLU; those HELD here only

The router reads ``u``, attention's input, as the published model
places it (so that experts can be fetched while attention runs); its
scores are carried past attention to the experts.  A token's ``top_k``
experts are chosen over ``u W_r + bias``, where ``bias`` is no
parameter: a controller moves it after every step against each
expert's excess load (``router_state``, ``BALANCE_GAIN``), as
``Qwen3NextLM``'s; the weights are the unbiased ``p``.  An expert is
applied by ``parallel/expert.py routed_experts`` (``activation="relu"``),
told which experts THIS chip holds; tokens routed elsewhere get nothing
from it.  After the last layer a final RMSNorm and the untied ``(d,
vocab)`` head through ``layers.blocked_softmax_cross_entropy``.

Attention is ``ops/attention.py``'s ``fused_attention``: a windowed
layer passes ``window=`` and takes the streamed kernels, which neither
compute nor fetch a key tile outside the window; the global layer at a
length whose K/V the resident kernels cannot hold streams too.  The
rotation runs in XLA (``rotary_xla``) before them, from one table a step.

``ModelConfig.remat`` recomputes each layer in the backward pass.  What
the published ``config.json`` leaves open is listed under ``assumed`` in
the configuration file; ``benchmarks/reference/smallthinker_21b.py`` is
the same function in plain ``jax.numpy``.

Tracing: ``jax.named_scope``s ``smallthinker/router``,
``smallthinker/window_attention``, ``smallthinker/global_attention``,
``smallthinker/experts`` and ``lm/loss`` (the head and its loss); the
kernels are ``smallthinker_{window,global}_attention_{fwd,bwd_kv,
bwd_q}`` and ``smallthinker_experts_{gate,up,down}_{gmm,gmm_t,tgmm}``.
Each step's metrics carry the rows this chip's experts multiplied and
the rows of their buffers; ``_flush_metrics`` feeds them to ``monitor``
(``moe/held_rows``, ``moe/rows_elsewhere``, ``moe/max_expert_rows``,
``moe/held_share``, ``moe/buffer_rows``, ``moe/buffer_fill``) and
appends them to this module's ``routing_log``.
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
from theanompi_tpu.models.nemotron_h import NemotronHHead
from theanompi_tpu.ops.attention import (fused_attention, rotary_table,
                                         rotary_xla)
from theanompi_tpu.parallel.expert import routed_experts
from theanompi_tpu.parallel.mesh import AXIS_DATA
from theanompi_tpu.utils.profiling import trace_running

#: what the held experts multiplied in this process's last flushes, one
#: entry a flush, newest last, as ``qwen3_next.routing_log`` has it
#: (``held_rows``, ``rows_elsewhere``, ``max_expert_rows``,
#: ``buffer_rows`` a flushed step each, ``n_layers``, ``top_k``,
#: ``expert_shape``, ``profiled``)
routing_log: collections.deque = collections.deque(maxlen=256)

_ROUTING_KEYS = ("moe_held_rows", "moe_rows_elsewhere",
                 "moe_max_expert_rows", "moe_buffer_rows")
#: the balancing controller's gain and bound (``Qwen3NextLM``'s): after a
#: step an expert's correction bias moves by ``-BALANCE_GAIN * (its load
#: / the mean load - 1)`` and stays inside ``+-BIAS_LIMIT``
BALANCE_GAIN = 0.5
BIAS_LIMIT = 30.0


def _dense(features: int, name: str, dtype):
    return nn.Dense(features, use_bias=False,
                    kernel_init=L.gaussian_init(0.02), dtype=dtype, name=name)


def layer_kinds(rope_layout, sliding_window_layout) -> str:
    """``"W"`` (a window, RoPE), ``"G"`` (global, no positions) a layer,
    from the published layouts; the other two pairings are ``"w"`` (a
    window without positions) and ``"g"`` (global with RoPE)."""
    return "".join({(1, 1): "W", (0, 0): "G", (0, 1): "w", (1, 0): "g"}[
        (int(r), int(s))] for r, s in zip(rope_layout, sliding_window_layout))


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * w`` over the last axis, float32
    inside, ``w`` from 1."""

    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True)
                                    + self.eps) * w).astype(x.dtype)


class Attention(nn.Module):
    """Grouped-query causal attention, global or over a window, with or
    without RoPE (the table is handed in: one a step)."""

    n_heads: int
    n_kv_heads: int
    head_dim: int
    window: int | None
    rope: bool
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u, table):
        b, t, d = u.shape
        hq, hk, dh = self.n_heads, self.n_kv_heads, self.head_dim
        kind = "global" if self.window is None else "window"
        with jax.named_scope(f"smallthinker/{kind}_attention"):
            q = _dense(hq * dh, "q_proj", self.dtype)(u).reshape(b, t, hq, dh)
            k = _dense(hk * dh, "k_proj", self.dtype)(u).reshape(b, t, hk, dh)
            v = _dense(hk * dh, "v_proj", self.dtype)(u).reshape(b, t, hk, dh)
            if self.rope:
                q, k = rotary_xla(q, table), rotary_xla(k, table)
            o = fused_attention(q, k, v, causal=True, scale=dh ** -0.5,
                                window=self.window,
                                name=f"smallthinker_{kind}_attention")
            return _dense(d, "o_proj", self.dtype)(o.reshape(b, t, hq * dh))


class Experts(nn.Module):
    """The held ReGLU experts of a softmax top-k layer whose scores were
    taken before attention; returns ``(out, stats)``.  The choice is the
    top-k of ``logits + bias`` (``router_state``, no parameter), moved
    by a training pass against each expert's excess load."""

    n_experts: int
    top_k: int
    expert_width: int
    held_experts: tuple
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, v, logits, probs):
        b, t, d = v.shape
        count, f = self.held_experts[1], self.expert_width
        bias = self.variable("router_state", "bias", jnp.zeros,
                             (self.n_experts,), jnp.float32)
        experts = {name: self.param(f"experts_{name}", L.gaussian_init(0.02),
                                    shape)
                   for name, shape in (("gate", (count, d, f)),
                                       ("up", (count, d, f)),
                                       ("down", (count, f, d)))}
        with jax.named_scope("smallthinker/experts"):
            out, stats = routed_experts(
                v.reshape(b * t, d), probs, experts, self.held_experts,
                top_k=self.top_k, select_by=logits + bias.value,
                normalize=True, activation="relu",
                name="smallthinker_experts")
        if (self.is_mutable_collection("router_state")
                and not self.is_initializing()):
            load = stats["expert_load"]
            bias.value = jnp.clip(
                bias.value - BALANCE_GAIN * (load / load.mean() - 1.0),
                -BIAS_LIMIT, BIAS_LIMIT)
        return out.reshape(b, t, d), stats


class SmallThinkerLayer(nn.Module):
    """``u = norm_in(x)``; the router's scores of ``u``; ``h = x +
    attention(u)``; ``h + experts(norm_post(h))`` by those scores.
    Returns ``(out, routing stats)``."""

    kind: str            # layer_kinds' letter
    attention: dict      # Attention's fields but window and rope
    moe: dict            # Experts' fields
    window: int
    rms_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, table):
        b, t, d = x.shape
        u = RMSNorm(self.rms_eps, name="input_norm")(x)
        with jax.named_scope("smallthinker/router"):
            # float32 in earnest: on a TPU a float32 product runs in
            # bfloat16 passes unless told otherwise
            logits = nn.Dense(
                self.moe["n_experts"], use_bias=False, dtype=jnp.float32,
                kernel_init=L.gaussian_init(0.02),
                precision=jax.lax.Precision.HIGHEST,
                name="router")(u.reshape(b * t, d))
            probs = jax.nn.softmax(logits, axis=-1)
        h = x + Attention(**self.attention,
                          window=self.window if self.kind in "Ww" else None,
                          rope=self.kind in "Wg", dtype=self.dtype,
                          name="attention")(u, table)
        out, stats = Experts(**self.moe, dtype=self.dtype, name="moe")(
            RMSNorm(self.rms_eps, name="post_norm")(h), logits, probs)
        return h + out, stats


class SmallThinkerLMNet(nn.Module):
    """Token ids ``(B, T)`` -> ``(hidden (B, T, d) after the final norm,
    routing stats summed over the layers)``; the head is only declared."""

    vocab: int
    d_model: int
    kinds: str
    attention: dict
    moe: dict
    window: int
    rope_theta: float
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
        table = rotary_table(jnp.arange(tokens.shape[1]),
                             self.attention["head_dim"], self.rope_theta)
        # explicit names pin the tree to the layout without remat
        layer_cls = (nn.remat(SmallThinkerLayer) if self.remat
                     else SmallThinkerLayer)
        held = elsewhere = fullest = buffer = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(self.kinds):
            x, stats = layer_cls(kind, self.attention, self.moe, self.window,
                                 self.rms_eps, self.dtype,
                                 name=f"Layer_{i}")(x, table)
            held += stats["held_rows"]
            elsewhere += stats["rows_elsewhere"]
            fullest = jnp.maximum(fullest, stats["max_expert_rows"])
            buffer += stats["buffer_rows"]
        x = RMSNorm(self.rms_eps, name="final_norm")(x)
        return x, {"moe_held_rows": held, "moe_rows_elsewhere": elsewhere,
                   "moe_max_expert_rows": fullest, "moe_buffer_rows": buffer}


def window_pairs(seq_len: int, window: int | None) -> float:
    """(query, key) pairs a head and sequence the causal mask leaves:
    ``s (s + 1) / 2`` globally; under a window of ``W`` keys, each
    query's own included, ``W (W + 1) / 2 + (s - W) W`` from ``s = W``
    on."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) / 2
    return window * (window + 1) / 2 + (seq_len - window) * window


def smallthinker_train_flops(*, d_model: int, vocab: int, seq_len: int,
                             n_layers: int, rope_layout,
                             sliding_window_layout, window: int,
                             n_heads: int, n_kv_heads: int, head_dim: int,
                             n_experts: int, top_k: int, expert_width: int,
                             held_count: int) -> float:
    """Trained FLOPs per SEQUENCE, 2xMAC units: 6 for every parameter
    applied to a token (forward 2, backward 4).  The one count: the
    benchmark's ``flops/smallthinker.py`` hands out this function.

    * every layer: the four attention projections, the router, and the
      HELD ReGLU experts at their EXPECTED share of the assignments,
      ``top_k x held_count / n_experts`` of three matrices;
    * attention's score and value products over the pairs each layer's
      mask leaves (``window_pairs``), ``6 x 2 H D`` a pair;
    * the untied head (``d_model x vocab``).

    The recomputed forwards of ``remat`` are not counted."""
    kinds = layer_kinds(rope_layout[:n_layers],
                        sliding_window_layout[:n_layers])
    per_token = n_layers * (
        d_model * (2 * n_heads + 2 * n_kv_heads) * head_dim
        + d_model * n_experts
        + 3 * d_model * expert_width * top_k * held_count / n_experts
    ) + d_model * vocab
    scores = sum(12.0 * n_heads * head_dim
                 * window_pairs(seq_len, window if kind in "Ww" else None)
                 for kind in kinds)
    return 6.0 * per_token * seq_len + scores


class SmallThinkerLM(TpuModel):
    """Window / global attention LM with a pre-attention router and ReGLU
    experts over data-sharded batches; reference contract."""

    name = "smallthinker_lm"
    batch_partition = P(AXIS_DATA)
    #: ``decode/kvcache.py`` holds one geometry for every layer: a window
    #: layer and a global one would need a cache each
    decode_capable = False

    @classmethod
    def default_config(cls) -> ModelConfig:
        return ModelConfig(batch_size=8, n_epochs=5, optimizer="adamw",
                           learning_rate=3e-4, weight_decay=0.01,
                           lr_schedule="constant", print_freq=20)

    def __init__(self, *args, vocab: int = 256, seq_len: int = 128,
                 d_model: int = 64, n_layers: int = 4,
                 rope_layout=(0, 1, 1, 1), sliding_window_layout=(0, 1, 1, 1),
                 window: int = 32, n_heads: int = 4, n_kv_heads: int = 2,
                 head_dim: int = 16, n_experts: int = 16, top_k: int = 4,
                 expert_width: int = 32, held_experts=None,
                 rope_theta: float = 1.5e6, rms_norm_eps: float = 1e-6,
                 **kwargs):
        held = tuple(held_experts) if held_experts is not None \
            else (0, n_experts)
        if n_heads % n_kv_heads:
            raise ValueError(f"{n_heads} query heads over {n_kv_heads} "
                             "key/value heads: each shared head serves a "
                             "whole number of heads")
        if top_k > n_experts:
            raise ValueError(f"top_k={top_k} of {n_experts} experts")
        if min(len(rope_layout), len(sliding_window_layout)) < n_layers:
            raise ValueError(f"layouts of {len(rope_layout)} and "
                             f"{len(sliding_window_layout)} layers for "
                             f"{n_layers} layers")
        rope_layout = tuple(rope_layout[:n_layers])
        sliding_window_layout = tuple(sliding_window_layout[:n_layers])
        self._net_cfg = dict(
            vocab=vocab, seq_len=seq_len, d_model=d_model,
            kinds=layer_kinds(rope_layout, sliding_window_layout),
            window=window, rope_theta=rope_theta, rms_eps=rms_norm_eps,
            attention=dict(n_heads=n_heads, n_kv_heads=n_kv_heads,
                           head_dim=head_dim),
            moe=dict(n_experts=n_experts, top_k=top_k,
                     expert_width=expert_width, held_experts=held))
        super().__init__(*args, **kwargs)
        self.train_flops_per_sample = smallthinker_train_flops(
            d_model=d_model, vocab=vocab, seq_len=seq_len, n_layers=n_layers,
            rope_layout=rope_layout,
            sliding_window_layout=sliding_window_layout, window=window,
            n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
            n_experts=n_experts, top_k=top_k, expert_width=expert_width,
            held_count=held[1])

    def _input_dtype(self):
        return jnp.int32

    def build_data(self):
        c = self._net_cfg
        return SeqLM_data(vocab=c["vocab"], seq_len=c["seq_len"],
                          seed=self.config.seed)

    def build_module(self) -> nn.Module:
        c = dict(self._net_cfg)
        del c["seq_len"]
        return SmallThinkerLMNet(**c, dtype=self._compute_dtype(),
                                 remat=self.config.remat)

    def _loss_and_error(self, params, model_state, batch, train: bool):
        """``(loss, error, routing counts, new model state)``; a training
        pass lets the controller move its biases."""
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
        return loss, err, routing, model_state

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
            held, elsewhere, fullest, buffer = (
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
        super()._flush_metrics(recorder)
