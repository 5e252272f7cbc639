"""ZAYA1-style causal LM: compressed convolutional attention, a router
MLP and a dropless expert layer that is told which experts it holds.

The block of ``Zyphra/ZAYA1-8B`` (``model_type`` ``zaya``) as
``benchmarks/configs/zaya1_8b.json`` states it, on the same spine as the
rest of the zoo (``TpuModel``: ``begin_epoch`` / ``train_iter`` /
``_flush_metrics``, AdamW, the BSP step).  Per layer, with ``x (B, S,
d)``::

    h = x + CCA(RMSNorm(x));   y = h + MoE(RMSNorm(h))

* **CCA** (``CCA``): queries and keys are projected into a compressed
  latent (``n_heads`` and ``n_kv_heads`` heads of ``head_dim``), mixed
  by two causal convolutions over time (depthwise, then dense within
  each head), joined with the mean of the un-mixed q and k latents,
  L2-normalised per head (keys carry a learned per-head temperature),
  rotated (RoPE on the leading ``partial_rotary_factor`` of each head)
  and attended causally with grouped-query heads, all in the latent;
  values are two halves, one from the current and one from the previous
  position.  The attention is ``ops/attention.py``'s fused kernel.
* **MoE** (``ZayaRouter`` + ``parallel/expert.routed_experts``): a
  small MLP over a down-projection gives the router's logits over ALL
  ``n_experts``; each token goes to its top-1 expert, a gated SiLU MLP,
  weighted by the router's probability.  The choice (not the weight)
  is taken over the logits plus a per-expert BALANCING BIAS, which is
  no parameter: a controller moves it after every step against each
  expert's excess load (``ZayaLayer``; it lives in ``model_state``
  under ``router_state``), so the experts' loads stay even without an
  auxiliary loss.  ``held_experts = (first,
  count)`` says which experts THIS chip holds (expert parallelism's
  share): only their matrices exist here, only their rows are
  multiplied, and tokens routed elsewhere get zero from this layer,
  which is what goes on to the next.  No capacity, nothing dropped.
* **Head**: tied to the embedding; the loss passes the tokens in blocks
  so that the ``(tokens, vocab)`` logits never exist whole
  (``layers.blocked_softmax_cross_entropy`` over the ``(vocab, d)``
  table as it lies, the scan ``TransformerLM`` runs over its kernel).

What the published ``config.json`` does not pin down (the value shift,
the q-k mean, the temperature, the router MLP's depth and activation)
is listed under ``assumed`` in the configuration file, and
``benchmarks/reference/zaya1_8b.py`` is the same function in plain
``jax.numpy``.

Tracing: the step carries ``jax.named_scope``s ``zaya/cca``,
``zaya/router``, ``zaya/experts`` and ``zaya/loss``; the kernels are
named ``zaya_cca_attention_{fwd,bwd}`` and
``zaya_experts_{gate,up,down}_{gmm,gmm_t,tgmm}``.  Each step's metrics
carry the rows this chip's experts multiplied and the rows of the
buffers they lay in; ``_flush_metrics`` feeds them to ``monitor``
(``moe/held_rows``, ``moe/rows_elsewhere``, ``moe/max_expert_rows``,
``moe/buffer_rows``, ``moe/buffer_fill``) and appends them, step by
step, to this module's ``routing_log`` (docs/OBSERVABILITY.md).
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
from theanompi_tpu.ops.attention import fused_attention
from theanompi_tpu.parallel.expert import routed_experts
from theanompi_tpu.parallel.mesh import AXIS_DATA
from theanompi_tpu.utils.profiling import trace_running

#: what the held experts multiplied in this process's last flushes,
#: whether or not a ``monitor`` session is on; one entry a flush,
#: newest last: ``{"held_rows": [rows of each flushed step, summed
#: over the layers], "buffer_rows": [the rows of the buffers they were
#: laid out in], "n_layers": ..., "expert_shape": (held experts,
#: d_model, expert_width), "profiled": whether a ``jax.profiler``
#: trace was being captured at the flush}``.  ``profiled`` is how a
#: reader of a device trace finds the steps its trace holds.
routing_log: collections.deque = collections.deque(maxlen=256)

_ROUTING_KEYS = ("moe_held_rows", "moe_rows_elsewhere",
                 "moe_max_expert_rows", "moe_buffer_rows")
#: the balancing controller's gain: after a step an expert's bias moves
#: by ``-BALANCE_GAIN * (its load / the mean load - 1)``, and stays
#: inside ``+-BIAS_LIMIT`` (log-probability units)
BALANCE_GAIN = 0.5
BIAS_LIMIT = 30.0


def shift_time(x, steps: int = 1):
    """``x[:, t - steps]`` with zeros before the sequence's start;
    time is axis 1."""
    if steps == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (steps, 0)
    return jnp.pad(x, pad)[:, :x.shape[1]]


def rope(x, positions, rotary_dim: int, theta: float):
    """Rotary embedding on the first ``rotary_dim`` of each head of
    ``x (B, T, H, D)``, halves paired (i with i + rotary_dim / 2);
    computed in float32."""
    half = rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2, rest = (x32[..., :half], x32[..., half:rotary_dim],
                    x32[..., rotary_dim:])
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1).astype(x.dtype)


class CCA(nn.Module):
    """Compressed convolutional attention; see the module docstring."""

    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    time0: int = 2
    time1: int = 2
    rotary_dim: int = 64
    rope_theta: float = 5e6
    dtype: jnp.dtype = jnp.float32

    def _causal_convs(self, x, heads: int, which: str):
        """``conv1(conv0(x))`` on ``x (B, T, heads, head_dim)``: conv0
        depthwise over time, conv1 dense within each head."""
        dh = self.head_dim
        w0 = self.param(f"conv0_{which}", L.gaussian_init(self.time0 ** -0.5),
                        (self.time0, heads, dh)).astype(self.dtype)
        w1 = self.param(f"conv1_{which}",
                        L.gaussian_init((self.time1 * dh) ** -0.5),
                        (self.time1, heads, dh, dh)).astype(self.dtype)
        # tap j reads the position (taps - 1 - j) steps back
        y = sum(shift_time(x, self.time0 - 1 - j) * w0[j]
                for j in range(self.time0))
        return sum(jnp.einsum("bthc,hcd->bthd",
                              shift_time(y, self.time1 - 1 - j), w1[j])
                   for j in range(self.time1))

    @nn.compact
    def __call__(self, u):
        b, t, _ = u.shape
        hq, hk, dh = self.n_heads, self.n_kv_heads, self.head_dim
        group = hq // hk

        def dense(features, name):
            return nn.Dense(features, use_bias=False,
                            kernel_init=L.xavier_init(), dtype=self.dtype,
                            name=name)

        q_lat = dense(hq * dh, "q_proj")(u).reshape(b, t, hq, dh)
        k_lat = dense(hk * dh, "k_proj")(u).reshape(b, t, hk, dh)
        # value shift: the first half of the value heads from this
        # position, the second from the one before
        half = hk // 2 * dh
        v = jnp.concatenate(
            [dense(half, "v_proj_now")(u),
             dense(hk * dh - half, "v_proj_prev")(shift_time(u))],
            -1).reshape(b, t, hk, dh)

        q_hat = self._causal_convs(q_lat, hq, "q")
        k_hat = self._causal_convs(k_lat, hk, "k")
        mean = (q_lat + jnp.repeat(k_lat, group, axis=2)) / 2
        q = q_hat + mean
        k = k_hat + mean.reshape(b, t, hk, group, dh).mean(3)

        temp = self.param("temp", nn.initializers.ones, (hk,))

        def unit(x):   # L2 norm 1 per head, times sqrt(head_dim)
            x32 = x.astype(jnp.float32)
            return x32 * jax.lax.rsqrt(
                jnp.sum(x32 * x32, -1, keepdims=True) + 1e-12) * dh ** 0.5

        q = unit(q).astype(self.dtype)
        k = (unit(k) * temp[:, None]).astype(self.dtype)
        positions = jnp.arange(t)
        q = rope(q, positions, self.rotary_dim, self.rope_theta)
        k = rope(k, positions, self.rotary_dim, self.rope_theta)
        o = fused_attention(q, k, v, causal=True, scale=dh ** -0.5,
                            name="zaya_cca_attention")
        return dense(self.d_model, "o_proj")(o.reshape(b, t, hq * dh))


class ZayaRouter(nn.Module):
    """Router MLP: down-projection, two hidden layers (GELU), logits
    over all experts; returns float32 probabilities."""

    n_experts: int
    hidden: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        r = nn.Dense(self.hidden, use_bias=False,
                     kernel_init=L.xavier_init(), dtype=self.dtype,
                     name="down")(u)
        for name in ("fc1", "fc2"):
            r = nn.gelu(nn.Dense(self.hidden, kernel_init=L.xavier_init(),
                                 dtype=self.dtype, name=name)(r))
        logits = nn.Dense(self.n_experts, kernel_init=L.xavier_init(),
                          dtype=self.dtype, name="fc3")(r)
        return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)


class ZayaLayer(nn.Module):
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    n_experts: int
    expert_width: int
    router_hidden: int
    held_experts: tuple
    time0: int = 2
    time1: int = 2
    rotary_dim: int = 64
    rope_theta: float = 5e6
    rms_eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        norm = lambda name: nn.RMSNorm(  # noqa: E731
            epsilon=self.rms_eps, dtype=self.dtype, name=name)
        with jax.named_scope("zaya/cca"):
            x = x + CCA(self.d_model, self.n_heads, self.n_kv_heads,
                        self.head_dim, self.time0, self.time1,
                        self.rotary_dim, self.rope_theta, self.dtype,
                        name="cca")(norm("attn_norm")(x))
        u = norm("moe_norm")(x)
        with jax.named_scope("zaya/router"):
            probs = ZayaRouter(self.n_experts, self.router_hidden,
                               self.dtype, name="router")(u)
        count = self.held_experts[1]
        f = self.expert_width
        experts = {
            "gate": self.param("experts_gate", L.gaussian_init(d ** -0.5),
                               (count, d, f)),
            "up": self.param("experts_up", L.gaussian_init(d ** -0.5),
                             (count, d, f)),
            "down": self.param("experts_down", L.gaussian_init(f ** -0.5),
                               (count, f, d)),
        }
        bias = self.variable("router_state", "bias", jnp.zeros,
                             (self.n_experts,), jnp.float32)
        probs = probs.reshape(b * t, -1)
        with jax.named_scope("zaya/experts"):
            out, stats = routed_experts(
                u.reshape(b * t, d), probs, experts, self.held_experts,
                top_k=1, select_by=jnp.log(probs + 1e-30) + bias.value,
                name="zaya_experts")
        load = stats.pop("expert_load")
        if (self.is_mutable_collection("router_state")
                and not self.is_initializing()):
            bias.value = jnp.clip(
                bias.value - BALANCE_GAIN * (load / load.mean() - 1.0),
                -BIAS_LIMIT, BIAS_LIMIT)
        return x + out.reshape(b, t, d), stats


class ZayaLMNet(nn.Module):
    """Token ids ``(B, T)`` -> ``(hidden (B, T, d) after the final
    norm, routing stats)``; the head is the embedding, applied by the
    caller (``ZayaLM._loss_and_error``) a block of tokens at a time."""

    vocab: int
    n_layers: int
    layer: dict          # ZayaLayer's fields
    remat: bool = False

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        del train  # no dropout, no batch statistics
        dtype = self.layer["dtype"]
        x = nn.Embed(self.vocab, self.layer["d_model"],
                     embedding_init=L.gaussian_init(0.02),
                     name="embed")(tokens).astype(dtype)
        layer_cls = nn.remat(ZayaLayer) if self.remat else ZayaLayer
        held = elsewhere = fullest = buffer = 0.0
        for i in range(self.n_layers):
            x, stats = layer_cls(**self.layer, name=f"Layer_{i}")(x)
            held += stats["held_rows"]
            elsewhere += stats["rows_elsewhere"]
            fullest = jnp.maximum(fullest, stats["max_expert_rows"])
            buffer += stats["buffer_rows"]
        x = nn.RMSNorm(epsilon=self.layer["rms_eps"], dtype=dtype,
                       name="final_norm")(x)
        return x, {"moe_held_rows": held, "moe_rows_elsewhere": elsewhere,
                   "moe_max_expert_rows": fullest,
                   "moe_buffer_rows": buffer}


def zaya_train_flops(*, n_layers: int, d_model: int, n_heads: int,
                     n_kv_heads: int, head_dim: int, n_experts: int,
                     expert_width: int, router_hidden: int, held_count: int,
                     vocab: int, seq_len: int, cca_time1: int = 2,
                     top_k: int = 1) -> float:
    """Trained FLOPs per SEQUENCE, 2xMAC units: 6 for every parameter
    applied to a token (forward 2, backward 4).  The one count: the
    benchmark's ``flops/zaya1.py`` hands out this function.

    Per layer and token: the q, k, v and o projections of the latent,
    the dense-within-head convolution (``cca_time1`` taps of head_dim x
    head_dim for each of the query and key heads; the depthwise one,
    RoPE, the norms and the embedding gather do no matmul work), the
    router MLP, and the HELD experts at their EXPECTED share of the
    tokens, ``held_count / n_experts`` of a gated MLP of 3 matrices
    (routing decides the real share).  Plus the tied head (d_model x
    vocab).  Attention's score and value products are counted
    CAUSALLY: 6 H D s (s + 1) per layer."""
    latent = (n_heads + n_kv_heads) * head_dim
    per_token = (
        d_model * (latent + n_kv_heads * head_dim)     # q, k, v
        + n_heads * head_dim * d_model                 # o
        + cca_time1 * latent * head_dim                # conv1
        + d_model * router_hidden + 2 * router_hidden ** 2
        + router_hidden * n_experts
        + 3 * d_model * expert_width * top_k * held_count / n_experts)
    dense = 6.0 * (n_layers * per_token + d_model * vocab) * seq_len
    attention = 6.0 * n_layers * n_heads * head_dim * seq_len * (seq_len + 1)
    return dense + attention


class ZayaLM(TpuModel):
    """ZAYA1-style LM over data-sharded batches; reference contract."""

    name = "zaya_lm"
    batch_partition = P(AXIS_DATA)
    #: the decode runtime serves ``TransformerLMNet`` trees only
    decode_capable = False

    @classmethod
    def default_config(cls) -> ModelConfig:
        return ModelConfig(batch_size=8, n_epochs=5, optimizer="adamw",
                           learning_rate=3e-4, weight_decay=0.01,
                           lr_schedule="constant", print_freq=20)

    def __init__(self, *args, vocab: int = 256, seq_len: int = 128,
                 n_layers: int = 2, d_model: int = 128, n_heads: int = 4,
                 n_kv_heads: int = 2, head_dim: int = 32,
                 n_experts: int = 4, expert_width: int = 128,
                 router_hidden: int = 32, held_experts=None,
                 cca_time0: int = 2, cca_time1: int = 2,
                 partial_rotary_factor: float = 0.5,
                 rope_theta: float = 5e6, rms_norm_eps: float = 1e-5,
                 **kwargs):
        held = tuple(held_experts) if held_experts is not None \
            else (0, n_experts)
        if n_heads % n_kv_heads or n_kv_heads % 2:
            raise ValueError(
                f"{n_heads} query heads over {n_kv_heads} key/value heads: "
                "the value shift splits an even number of key/value heads, "
                "and each serves a whole group of query heads")
        self._net_cfg = dict(
            vocab=vocab, seq_len=seq_len, n_layers=n_layers,
            d_model=d_model, n_heads=n_heads, n_kv_heads=n_kv_heads,
            head_dim=head_dim, n_experts=n_experts,
            expert_width=expert_width, router_hidden=router_hidden,
            held_experts=held, time0=cca_time0, time1=cca_time1,
            rotary_dim=int(head_dim * partial_rotary_factor),
            rope_theta=rope_theta, rms_eps=rms_norm_eps)
        super().__init__(*args, **kwargs)
        self.train_flops_per_sample = zaya_train_flops(
            n_layers=n_layers, d_model=d_model, n_heads=n_heads,
            n_kv_heads=n_kv_heads, head_dim=head_dim, n_experts=n_experts,
            expert_width=expert_width, router_hidden=router_hidden,
            held_count=held[1], vocab=vocab, seq_len=seq_len,
            cca_time1=cca_time1)

    def _input_dtype(self):
        return jnp.int32

    def build_data(self):
        c = self._net_cfg
        return SeqLM_data(vocab=c["vocab"], seq_len=c["seq_len"],
                          seed=self.config.seed)

    def build_module(self) -> nn.Module:
        c = dict(self._net_cfg)
        vocab, n_layers = c.pop("vocab"), c.pop("n_layers")
        del c["seq_len"]
        return ZayaLMNet(
            vocab=vocab, n_layers=n_layers, remat=self.config.remat,
            layer=dict(c, dtype=self._compute_dtype()))

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
        with jax.named_scope("zaya/loss"):
            loss, err = L.blocked_softmax_cross_entropy(
                h.reshape(-1, h.shape[-1]), params["embed"]["embedding"],
                None, targets.reshape(-1), vocab_axis=0,
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
            routing_log.append({
                "held_rows": [float(x) for x in held],
                "buffer_rows": [float(x) for x in buffer],
                "n_layers": c["n_layers"],
                "expert_shape": (c["held_experts"][1], c["d_model"],
                                 c["expert_width"]),
                "profiled": trace_running()})
            monitor.inc("moe/held_rows", float(held.sum()))
            monitor.inc("moe/rows_elsewhere", float(elsewhere.sum()))
            monitor.set_gauge("moe/max_expert_rows", float(fullest.max()))
            monitor.inc("moe/buffer_rows", float(buffer.sum()))
            monitor.set_gauge("moe/buffer_fill",
                              float(held.sum() / buffer.sum()))
        super()._flush_metrics(recorder)
