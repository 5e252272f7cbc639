"""Nemotron-H-style hybrid causal LM: a stack built from a PATTERN
STRING of three kinds of layer, Mamba-2 state-space mixers, sigmoid
top-k expert layers beside a shared expert, and grouped-query attention.

The language model of
``nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16`` (``model_type``
``nemotron_h``) as ``benchmarks/configs/nemotron_twotower_30b.json``
states it, on the same spine as the rest of the zoo (``TpuModel``:
``begin_epoch`` / ``train_iter`` / ``_flush_metrics``, AdamW, the BSP
step).  A layer is one pre-norm residual block round ONE mixer, ``x = x
+ mixer(RMSNorm(x))``, and the pattern names each layer's mixer
(``MEMEM*EME``): the parameter tree, the recomputation and the FLOP
count differ by layer kind.  After the last layer a final RMSNorm and
an untied head.  With ``u (B, T, d)`` the normed input:

* **``M``, Mamba-2** (``Mamba2Mixer``): ``[z | xBC | dt] = u W_in``
  (widths ``H P | H P + 2 G N | H``); ``xBC = silu(conv(xBC) + b)``, a
  causal depthwise convolution over time; split into ``x (T, H, P)``,
  ``B`` and ``C (T, G, N)``; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; the selective recurrence ``S_t = exp(dt_t A) S_{t-1} +
  dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t`` in its chunked form
  (``ops/ssd.py``); ``y = RMSNorm_groups(y * silu(z)) * w``, the mean
  square over each of the ``G`` groups of channels, gate before norm;
  ``out = y W_out``.
* **``E``, experts** (``ExpertMixer``): ``s = sigmoid(float32(u)
  W_r)`` over ALL ``n_experts``; a token's ``top_k`` experts are chosen
  over ``s + bias`` and weighted by ``s / (sum of the chosen s) x
  routed_scaling_factor``; an expert is ``relu(u W_up)^2 W_down``
  (``parallel/expert.py routed_experts``, told which experts THIS chip
  holds: ``held_experts = (first, count)``; tokens routed elsewhere get
  nothing from the routed part).  Beside it a shared expert of the same
  form that every token passes, whole on every chip.  The correction
  ``bias`` is no parameter: a controller moves it after every step
  against each expert's excess load (it lives in ``model_state`` under
  ``router_state``, as ``ZayaLayer``'s does).
* **``*``, attention** (``AttentionMixer``): grouped-query causal
  softmax attention, no rotary embedding and no other position signal
  (the state-space layers carry the order); ``ops/attention.py``'s
  fused kernel.
* **Head and loss**: ``layers.blocked_softmax_cross_entropy`` over the
  untied ``(d, vocab)`` kernel, a block of tokens at a time.

``ModelConfig.remat`` recomputes each layer in the backward pass (a
Mamba-2 layer's chunk matrices are ``B x T / Q x H x Q x Q`` numbers
each).  What the published ``config.json`` does not pin down (the
initialisation, the controller) is listed under ``assumed`` in the
configuration file; ``benchmarks/reference/nemotron_twotower_30b.py``
is the same function in plain ``jax.numpy``, its recurrence stepped a
token at a time.

Tracing: ``jax.named_scope``s ``nemotron_h/mamba/in_proj``, ``/conv``,
``/ssd``, ``/gate_norm``, ``/out_proj``, ``nemotron_h/router``,
``nemotron_h/experts``, ``nemotron_h/shared_expert``,
``nemotron_h/attention`` and ``nemotron_h/loss``; the kernels are named
``nemotron_h_experts_{up,down}_{gmm,gmm_t,tgmm}``,
``nemotron_h_attention_{fwd,bwd}`` and, where the scan's shape meets
the kernels' tiling, ``nemotron_h_ssd_{fwd,bwd}``; the scan's plan
(which path ran and how) is one log line a shape, the expert buffer's
ladder another.  Each step's metrics carry
the rows this chip's experts multiplied and the rows of the buffers
they lay in; ``_flush_metrics`` feeds them to ``monitor``
(``moe/held_rows``, ``moe/rows_elsewhere``, ``moe/max_expert_rows``,
``moe/held_share``, ``moe/buffer_rows``, ``moe/buffer_fill``) and
appends them to this module's ``routing_log`` (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import collections
import functools
import logging
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from theanompi_tpu.data.lm import SeqLM_data
from theanompi_tpu.models import layers as L
from theanompi_tpu.models.base import ModelConfig, TpuModel
from theanompi_tpu.ops.attention import fused_attention
from theanompi_tpu.ops.ssd import ssd_chunked, ssd_plan
from theanompi_tpu.parallel.expert import routed_experts
from theanompi_tpu.parallel.mesh import AXIS_DATA
from theanompi_tpu.utils.profiling import trace_running

_log = logging.getLogger(__name__)

#: what the held experts multiplied in this process's last flushes,
#: whether or not a ``monitor`` session is on; one entry a flush, newest
#: last: ``{"held_rows": [rows of each flushed step, summed over the
#: expert layers], "rows_elsewhere": [...], "max_expert_rows": [...],
#: "buffer_rows": [the rows of the buffers they were laid out in],
#: "n_layers": expert layers, "top_k": ..., "expert_shape": (held
#: experts, d_model, expert_width), "profiled": whether a
#: ``jax.profiler`` trace was being captured at the flush}``.
#: ``profiled`` is how a reader of a device trace finds the steps its
#: trace holds.
routing_log: collections.deque = collections.deque(maxlen=256)

_ROUTING_KEYS = ("moe_held_rows", "moe_rows_elsewhere",
                 "moe_max_expert_rows", "moe_buffer_rows")
#: the layer kinds a pattern may name
KINDS = "ME*"
#: the balancing controller's gain: after a step an expert's correction
#: bias moves by ``-BALANCE_GAIN * (its load / the mean load - 1)`` and
#: stays inside ``+-BIAS_LIMIT``, in units of the sigmoid scores (which
#: span 1: a gain of ``ZayaLayer``'s size, made for log-probabilities,
#: would leave the choice to the bias alone)
BALANCE_GAIN = 0.05
BIAS_LIMIT = 1.0
#: the scan's kernels are ``<SSD_NAME>_fwd`` and ``<SSD_NAME>_bwd``
SSD_NAME = "nemotron_h_ssd"


def _dense(features: int, name: str, dtype, std: float = 0.02):
    return nn.Dense(features, use_bias=False,
                    kernel_init=L.gaussian_init(std), dtype=dtype, name=name)


def causal_depthwise_conv(x, kernel, bias):
    """``y[:, t] = sum_j kernel[j] * x[:, t - (k - 1 - j)] + bias`` on
    ``x (B, T, C)``, ``kernel (k, C)``: every channel its own ``k``
    taps, zeros before the sequence's start."""
    taps, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * kernel[j] for j in range(taps)) + bias


def gated_group_norm(y, z, scale, n_groups: int, eps: float):
    """``RMSNorm_groups(y * silu(z)) * scale`` on ``(..., C)``: the gate
    first, then the mean square over each of ``n_groups`` groups of
    ``C / n_groups`` channels; float32 inside, ``y.dtype`` out."""
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    groups = gated.reshape(gated.shape[:-1] + (n_groups, -1))
    normed = groups * jax.lax.rsqrt(
        jnp.mean(groups * groups, -1, keepdims=True) + eps)
    return (normed.reshape(gated.shape) * scale).astype(y.dtype)


def _dt_bias_init(low: float, high: float, floor: float):
    """The inverse softplus of a log-uniform draw in ``[low, high]``
    floored at ``floor``: ``softplus(dt_bias)`` starts in the range."""
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(high) - math.log(low)) + math.log(low))
        dt = jnp.maximum(dt, floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


@functools.lru_cache(maxsize=None)
def _log_ssd_plan(plan) -> None:
    """The chunked scan always engages, so its counter is its plan: one
    line a shape (trace time only), which path ran and how, as
    ``tile_plan`` and the blocked loss say theirs."""
    _log.info("%s", plan)


class Mamba2Mixer(nn.Module):
    """The Mamba-2 mixer; see the module docstring."""

    d_model: int
    n_heads: int
    head_dim: int
    n_groups: int
    state: int
    conv_kernel: int = 4
    chunk: int = 128
    time_step_min: float = 1e-3
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    rms_eps: float = 1e-5
    out_std: float = 0.02
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        b, t, _ = u.shape
        h, p, g, n = self.n_heads, self.head_dim, self.n_groups, self.state
        inner, bc = h * p, g * n
        with jax.named_scope("nemotron_h/mamba/in_proj"):
            z, xbc, dt = jnp.split(
                _dense(2 * inner + 2 * bc + h, "in_proj", self.dtype)(u),
                [inner, 2 * inner + 2 * bc], axis=-1)
        with jax.named_scope("nemotron_h/mamba/conv"):
            kernel = self.param("conv_kernel", L.gaussian_init(
                self.conv_kernel ** -0.5), (self.conv_kernel, inner + 2 * bc))
            bias = self.param("conv_bias", nn.initializers.zeros,
                              (inner + 2 * bc,))
            xbc = nn.silu(causal_depthwise_conv(
                xbc, kernel.astype(self.dtype), bias.astype(self.dtype)))
        x, b_in, c_in = jnp.split(xbc, [inner, inner + bc], axis=-1)
        a_log = self.param("A_log", _a_log_init, (h,))
        dt_bias = self.param("dt_bias", _dt_bias_init(
            self.time_step_min, self.time_step_max, self.time_step_floor),
            (h,))
        skip = self.param("D", nn.initializers.ones, (h,))
        chunk = min(self.chunk, t)
        _log_ssd_plan(ssd_plan(b, t, h, p, g, n, chunk,
                               jnp.dtype(self.dtype).itemsize, SSD_NAME))
        with jax.named_scope("nemotron_h/mamba/ssd"):
            y = ssd_chunked(
                x.reshape(b, t, h, p),
                jax.nn.softplus(dt.astype(jnp.float32) + dt_bias),
                -jnp.exp(a_log), b_in.reshape(b, t, g, n),
                c_in.reshape(b, t, g, n), skip, chunk=chunk, name=SSD_NAME)
        with jax.named_scope("nemotron_h/mamba/gate_norm"):
            scale = self.param("norm_scale", nn.initializers.ones, (inner,))
            y = gated_group_norm(y.reshape(b, t, inner), z, scale, g,
                                 self.rms_eps)
        with jax.named_scope("nemotron_h/mamba/out_proj"):
            return _dense(self.d_model, "out_proj", self.dtype,
                          self.out_std)(y)


class ExpertMixer(nn.Module):
    """Sigmoid top-k routed experts told their share, beside a shared
    expert; returns ``(out, routing stats)``.  See the module
    docstring."""

    d_model: int
    n_experts: int
    top_k: int
    expert_width: int
    shared_width: int
    held_experts: tuple
    routed_scaling_factor: float = 1.0
    out_std: float = 0.02
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        b, t, d = u.shape
        count, f = self.held_experts[1], self.expert_width
        rows = u.reshape(b * t, d)
        with jax.named_scope("nemotron_h/router"):
            # float32 in earnest: on a TPU a float32 product runs in
            # bfloat16 passes unless told otherwise
            scores = jax.nn.sigmoid(nn.Dense(
                self.n_experts, use_bias=False, dtype=jnp.float32,
                kernel_init=L.gaussian_init(0.02),
                precision=jax.lax.Precision.HIGHEST, name="router")(rows))
        bias = self.variable("router_state", "bias", jnp.zeros,
                             (self.n_experts,), jnp.float32)
        experts = {
            "up": self.param("experts_up", L.gaussian_init(0.02),
                             (count, d, f)),
            "down": self.param("experts_down",
                               L.gaussian_init(self.out_std), (count, f, d)),
        }
        with jax.named_scope("nemotron_h/experts"):
            out, stats = routed_experts(
                rows, scores, experts, self.held_experts, top_k=self.top_k,
                select_by=scores + bias.value, normalize=True,
                scale=self.routed_scaling_factor, name="nemotron_h_experts")
        load = stats.pop("expert_load")
        if (self.is_mutable_collection("router_state")
                and not self.is_initializing()):
            bias.value = jnp.clip(
                bias.value - BALANCE_GAIN * (load / load.mean() - 1.0),
                -BIAS_LIMIT, BIAS_LIMIT)
        with jax.named_scope("nemotron_h/shared_expert"):
            hidden = _dense(self.shared_width, "shared_up", self.dtype)(rows)
            out = out + _dense(d, "shared_down", self.dtype, self.out_std)(
                jnp.square(nn.relu(hidden)))
        return out.reshape(b, t, d), stats


class AttentionMixer(nn.Module):
    """Grouped-query causal attention with no position signal."""

    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    out_std: float = 0.02
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        b, t, _ = u.shape
        hq, hk, dh = self.n_heads, self.n_kv_heads, self.head_dim
        with jax.named_scope("nemotron_h/attention"):
            q = _dense(hq * dh, "q_proj", self.dtype)(u).reshape(b, t, hq, dh)
            k = _dense(hk * dh, "k_proj", self.dtype)(u).reshape(b, t, hk, dh)
            v = _dense(hk * dh, "v_proj", self.dtype)(u).reshape(b, t, hk, dh)
            o = fused_attention(q, k, v, causal=True, scale=dh ** -0.5,
                                name="nemotron_h_attention")
            return _dense(self.d_model, "o_proj", self.dtype, self.out_std)(
                o.reshape(b, t, hq * dh))


class NemotronHLayer(nn.Module):
    """``x + mixer(RMSNorm(x))`` for the mixer ``kind`` names; returns
    ``(x, routing stats)``, the stats empty but for an ``E`` layer."""

    kind: str
    mixer: dict          # the mixer's fields
    rms_eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        u = nn.RMSNorm(epsilon=self.rms_eps, dtype=self.dtype,
                       name="norm")(x)
        if self.kind == "M":
            out, stats = Mamba2Mixer(**self.mixer, rms_eps=self.rms_eps,
                                     dtype=self.dtype, name="mamba")(u), {}
        elif self.kind == "E":
            out, stats = ExpertMixer(**self.mixer, dtype=self.dtype,
                                     name="moe")(u)
        else:
            out, stats = AttentionMixer(**self.mixer, dtype=self.dtype,
                                        name="attention")(u), {}
        return x + out, stats


class NemotronHHead(nn.Module):
    """The untied head's kernel ``(d, vocab)``: declared here, applied
    by ``NemotronHLM`` a block of tokens at a time."""

    d_model: int
    vocab: int

    @nn.compact
    def __call__(self):
        return self.param("kernel", L.gaussian_init(0.02),
                          (self.d_model, self.vocab))


class NemotronHLMNet(nn.Module):
    """Token ids ``(B, T)`` -> ``(hidden (B, T, d) after the final norm,
    routing stats summed over the expert layers)``; the head is only
    declared."""

    vocab: int
    d_model: int
    pattern: str
    mixers: dict         # kind -> that mixer's fields
    rms_eps: float = 1e-5
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
        layer_cls = nn.remat(NemotronHLayer) if self.remat else NemotronHLayer
        held = elsewhere = fullest = buffer = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(self.pattern):
            x, stats = layer_cls(kind, self.mixers[kind], self.rms_eps,
                                 self.dtype, name=f"Layer_{i}")(x)
            if stats:
                held += stats["held_rows"]
                elsewhere += stats["rows_elsewhere"]
                fullest = jnp.maximum(fullest, stats["max_expert_rows"])
                buffer += stats["buffer_rows"]
        x = nn.RMSNorm(epsilon=self.rms_eps, dtype=self.dtype,
                       name="final_norm")(x)
        return x, {"moe_held_rows": held, "moe_rows_elsewhere": elsewhere,
                   "moe_max_expert_rows": fullest,
                   "moe_buffer_rows": buffer}


def nemotron_h_train_flops(*, pattern: str, d_model: int, vocab: int,
                           seq_len: int, mamba_heads: int,
                           mamba_head_dim: int, n_groups: int,
                           state: int, chunk: int, n_experts: int,
                           top_k: int, expert_width: int,
                           shared_width: int, held_count: int,
                           n_heads: int, n_kv_heads: int,
                           head_dim: int) -> float:
    """Trained FLOPs per SEQUENCE, 2xMAC units: 6 for every parameter
    applied to a token (forward 2, backward 4), by layer kind.  The one
    count: the benchmark's ``flops/nemotron_h.py`` hands out this
    function.

    * ``M``: the input and output projections, and the chunked scan's
      four products as the algorithm at this chunk size runs them (``C
      B^T`` over a chunk's ``Q x Q`` pairs a group, its application to
      ``x``, the chunk states in and out); the depthwise convolution,
      the gate, the norms and the carry between chunks do no matmul
      work.
    * ``E``: the router, the shared expert, and the HELD experts at
      their EXPECTED share of the assignments, ``top_k x held_count /
      n_experts`` of a two-matrix MLP (routing decides the real share).
    * ``*``: the four projections, and the score and value products
      counted CAUSALLY: ``6 H D s (s + 1)`` a layer.
    * the untied head (``d_model x vocab``).

    The recomputed forwards of ``remat`` are not counted."""
    inner = mamba_heads * mamba_head_dim
    chunk = min(chunk, seq_len)
    mamba = (d_model * (2 * inner + 2 * n_groups * state + mamba_heads)
             + inner * d_model
             + chunk * n_groups * state          # C B^T
             + chunk * inner                     # its application to x
             + 2 * inner * state)                # chunk states in and out
    experts = (d_model * n_experts + 2 * d_model * shared_width
               + 2 * d_model * expert_width * top_k * held_count / n_experts)
    attention = (d_model * (n_heads + 2 * n_kv_heads) * head_dim
                 + n_heads * head_dim * d_model)
    per_token = (pattern.count("M") * mamba + pattern.count("E") * experts
                 + pattern.count("*") * attention + d_model * vocab)
    scores = (6.0 * pattern.count("*") * n_heads * head_dim
              * seq_len * (seq_len + 1))
    return 6.0 * per_token * seq_len + scores


class NemotronHLM(TpuModel):
    """Hybrid state-space / expert / attention LM over data-sharded
    batches; reference contract."""

    name = "nemotron_h_lm"
    batch_partition = P(AXIS_DATA)
    #: ``decode/kvcache.py`` holds keys and values only: a state-space
    #: layer decodes from a recurrent state and a convolution window
    decode_capable = False

    @classmethod
    def default_config(cls) -> ModelConfig:
        return ModelConfig(batch_size=8, n_epochs=5, optimizer="adamw",
                           learning_rate=3e-4, weight_decay=0.01,
                           lr_schedule="constant", print_freq=20)

    def __init__(self, *args, vocab: int = 256, seq_len: int = 128,
                 pattern: str = "ME*E", d_model: int = 64,
                 mamba_heads: int = 4, mamba_head_dim: int = 32,
                 n_groups: int = 2, state: int = 16, conv_kernel: int = 4,
                 chunk: int = 32, time_step_min: float = 1e-3,
                 time_step_max: float = 0.1, time_step_floor: float = 1e-4,
                 n_experts: int = 8, top_k: int = 2, expert_width: int = 48,
                 shared_width: int = 96, held_experts=None,
                 routed_scaling_factor: float = 2.5, n_heads: int = 4,
                 n_kv_heads: int = 2, head_dim: int = 16,
                 rms_norm_eps: float = 1e-5, **kwargs):
        held = tuple(held_experts) if held_experts is not None \
            else (0, n_experts)
        if not pattern or set(pattern) - set(KINDS):
            raise ValueError(
                f"pattern {pattern!r}: one of {KINDS!r} a layer (M Mamba-2, "
                "E experts, * attention)")
        if n_heads % n_kv_heads or mamba_heads % n_groups:
            raise ValueError(
                f"{n_heads} query heads over {n_kv_heads} key/value heads, "
                f"{mamba_heads} state-space heads over {n_groups} groups: "
                "each key/value head and each group serves a whole number "
                "of heads")
        if top_k > n_experts:
            raise ValueError(f"top_k={top_k} of {n_experts} experts")
        # rescale_prenorm_residual: what writes to the residual stream
        # starts smaller by the root of the depth
        out_std = 0.02 / math.sqrt(len(pattern))
        self._net_cfg = dict(
            vocab=vocab, seq_len=seq_len, pattern=pattern, d_model=d_model,
            rms_eps=rms_norm_eps,
            mixers={
                "M": dict(d_model=d_model, n_heads=mamba_heads,
                          head_dim=mamba_head_dim, n_groups=n_groups,
                          state=state, conv_kernel=conv_kernel, chunk=chunk,
                          time_step_min=time_step_min,
                          time_step_max=time_step_max,
                          time_step_floor=time_step_floor, out_std=out_std),
                "E": dict(d_model=d_model, n_experts=n_experts, top_k=top_k,
                          expert_width=expert_width,
                          shared_width=shared_width, held_experts=held,
                          routed_scaling_factor=routed_scaling_factor,
                          out_std=out_std),
                "*": dict(d_model=d_model, n_heads=n_heads,
                          n_kv_heads=n_kv_heads, head_dim=head_dim,
                          out_std=out_std)})
        super().__init__(*args, **kwargs)
        self.train_flops_per_sample = nemotron_h_train_flops(
            pattern=pattern, d_model=d_model, vocab=vocab, seq_len=seq_len,
            mamba_heads=mamba_heads, mamba_head_dim=mamba_head_dim,
            n_groups=n_groups, state=state, chunk=chunk,
            n_experts=n_experts, top_k=top_k, expert_width=expert_width,
            shared_width=shared_width, held_count=held[1], n_heads=n_heads,
            n_kv_heads=n_kv_heads, head_dim=head_dim)

    def _input_dtype(self):
        return jnp.int32

    def build_data(self):
        c = self._net_cfg
        return SeqLM_data(vocab=c["vocab"], seq_len=c["seq_len"],
                          seed=self.config.seed)

    def build_module(self) -> nn.Module:
        c = dict(self._net_cfg)
        del c["seq_len"]
        return NemotronHLMNet(**c, dtype=self._compute_dtype(),
                              remat=self.config.remat)

    def _loss_and_error(self, params, model_state, batch, train: bool):
        """``(loss, error, routing counts, new model state)``; a
        training pass lets the balancing controller move its biases."""
        tokens, targets = batch
        variables = {"params": params, **model_state}
        if train:
            (h, routing), moved = self.module.apply(
                variables, tokens, mutable=["router_state"])
            model_state = {**model_state, **moved}
        else:
            h, routing = self.module.apply(variables, tokens)
        with jax.named_scope("nemotron_h/loss"):
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

        if self._pending and "E" in self._net_cfg["pattern"]:
            held, elsewhere, fullest, buffer = (
                np.concatenate([np.atleast_1d(np.asarray(m[key]))
                                for _, m in self._pending])
                for key in _ROUTING_KEYS)
            c = self._net_cfg
            experts = c["mixers"]["E"]
            routing_log.append({
                "held_rows": [float(x) for x in held],
                "rows_elsewhere": [float(x) for x in elsewhere],
                "max_expert_rows": [float(x) for x in fullest],
                "buffer_rows": [float(x) for x in buffer],
                "n_layers": c["pattern"].count("E"),
                "top_k": experts["top_k"],
                "expert_shape": (experts["held_experts"][1], c["d_model"],
                                 experts["expert_width"]),
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
