"""Ouro-style looped causal LM: one stack of layers run several times
over shared weights, an exit gate and the output head after every pass,
the loss weighted by the exit distribution.

The model of ``ByteDance/Ouro-2.6B`` (``model_type`` ``ouro``; "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741) as
``benchmarks/configs/ouro_2_6b.json`` states it, on the same spine as
the rest of the zoo (``TpuModel``: ``begin_epoch`` / ``train_iter`` /
``_flush_metrics``, AdamW, the BSP step).  With tokens ``x``, targets
``y``, ``T = total_ut_steps`` passes over ``L`` layers::

    h(0) = E[x]
    for t = 1..T:
        u = h(t-1)
        for l = 1..L:                               the SAME L layers every pass
            a = RMSNorm_l1(u);  q, k, v = a Wq, a Wk, a Wv
            q, k = RoPE(q), RoPE(k)                 whole head, positions 0..S-1
            o = causal_softmax(q k^T / sqrt(D)) v   (the rotation inside the kernel)
            u = u + RMSNorm_l2(o Wo)                norms on both sides
            m = RMSNorm_l3(u);  f = (silu(m Wgate) * (m Wup)) Wdown
            u = u + RMSNorm_l4(f)
        h(t) = RMSNorm_f(u)                         what pass t+1 starts from
        l(t)_i = CE(h(t)_i W_o, y_i);   lam(t)_i = sigmoid(h(t)_i . w_g + b_g)
    p(t)_i = lam(t)_i prod_{j<t} (1 - lam(j)_i)  (t < T);  p(T)_i = prod_{j<T} (1 - lam(j)_i)
    loss = mean_i [ sum_t p(t)_i l(t)_i - beta H(p_i) ],  H(p) = - sum_t p(t) log p(t)

* **One set of layer modules, applied T times** (``OuroStack``: flax
  shares a submodule's parameters across calls), so a shared weight's
  gradient is the sum over its T uses, accumulated in float32 (each use
  casts the float32 master to the compute dtype on its own).  The T
  passes are ONE scanned body (``nn.scan`` with the parameters
  broadcast): a start traces and lowers L layers, not T x L, and on the
  chip the step is 2.8% shorter than with the passes unrolled and the
  cold first step 79 s shorter (PERF.md section 6, PR 32), so there is
  no unrolled form.  ``ModelConfig.remat``
  recomputes each layer application in the backward pass (at 32
  applications of 8 192 tokens the stored activations would not fit).
* **Attention** is ``ops/attention.py``'s fused kernel under the name
  ``ouro_attention`` (``ouro_attention_fwd`` / ``ouro_attention_bwd``
  in a trace), equal query and key/value head counts.  The kernels
  take q, k and v as the projections leave them and rotate q and k
  themselves (``rotary=``: ONE table a step, made outside the scanned
  body), so no XLA pass stands between a projection and the kernel
  where a head fills whole lanes (the published 128); smaller heads
  are rotated by XLA, to the same numbers.
* **Head and loss**: the T passes' states stacked to ``(T x tokens,
  d)`` and ONE ``layers.blocked_softmax_cross_entropy`` over the untied
  ``(d, vocab)`` kernel with the exit distribution as the tokens'
  weights, so the ``(tokens, vocab)`` logits never exist whole and the
  tokens' losses come back as the weights' gradient.  The exit
  distribution and its entropy are plain ``jnp`` on ``(T, tokens)``.
* ``error`` and the validation loss are pass T's: with the published
  ``early_exit_threshold`` of 1 the model never leaves early.  Every
  pass is always computed, so a step's work does not depend on the data.

What the published ``config.json`` does not pin down (the four norms a
layer, the gate's form, the objective and its beta, the init) is listed
under ``assumed`` in the configuration file, and
``benchmarks/reference/ouro_2_6b.py`` is the same function in plain
``jax.numpy``.

Tracing: ``jax.named_scope``s ``ouro/pass`` (the scanned body),
``ouro/exit_gate`` and ``ouro/loss``; one log
line a shape with the plan.  Each step's metrics carry the mean exit
mass and the mean loss of every pass and the mean exit entropy;
``_flush_metrics`` hands them to ``monitor`` (``ouro/exit_mass_<t>``,
``ouro/loss_pass_<t>``, ``ouro/exit_entropy``): they say that a run
trained its gates at all (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import functools
import logging

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from theanompi_tpu.data.lm import SeqLM_data
from theanompi_tpu.models import layers as L
from theanompi_tpu.models.base import ModelConfig, TpuModel
from theanompi_tpu.ops.attention import fused_attention, rotary_table
from theanompi_tpu.parallel.mesh import AXIS_DATA

_log = logging.getLogger(__name__)


class OuroLayer(nn.Module):
    """One decoder layer, norms on both sides of each sublayer; see the
    module docstring."""

    d_model: int
    n_heads: int
    head_dim: int
    d_ff: int
    rms_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u, rotary):
        b, t, d = u.shape
        heads = (b, t, self.n_heads, self.head_dim)

        def norm(name):
            return nn.RMSNorm(epsilon=self.rms_eps, dtype=self.dtype,
                              name=name)

        def dense(features, name):
            return nn.Dense(features, use_bias=False,
                            kernel_init=L.gaussian_init(0.02),
                            dtype=self.dtype, name=name)

        a = norm("attn_norm")(u)
        q, k, v = (dense(d, name)(a).reshape(heads)
                   for name in ("q_proj", "k_proj", "v_proj"))
        # the kernels rotate q and k themselves: nothing stands between
        # a projection and the kernel
        o = fused_attention(q, k, v, causal=True, scale=self.head_dim ** -0.5,
                            name="ouro_attention", rotary=rotary)
        u = u + norm("attn_out_norm")(dense(d, "o_proj")(o.reshape(b, t, d)))
        m = norm("mlp_norm")(u)
        f = dense(d, "down_proj")(nn.silu(dense(self.d_ff, "gate_proj")(m))
                                  * dense(self.d_ff, "up_proj")(m))
        return u + norm("mlp_out_norm")(f)


class OuroStack(nn.Module):
    """One pass: the L layers, then the final norm.  Scan-shaped
    (``carry, rotary table -> (carry, out)``): ``OuroLMNet`` runs it as
    the body of ``nn.scan`` over the T passes, on one set of parameters
    and one table."""

    n_layers: int
    layer: dict          # OuroLayer's fields
    remat: bool = False

    @nn.compact
    def __call__(self, u, rotary):
        # explicit names pin the tree to the layout without remat
        layer_cls = nn.remat(OuroLayer) if self.remat else OuroLayer
        for i in range(self.n_layers):
            u = layer_cls(**self.layer, name=f"Layer_{i}")(u, rotary)
        h = nn.RMSNorm(epsilon=self.layer["rms_eps"],
                       dtype=self.layer["dtype"], name="final_norm")(u)
        return h, h


class OuroHead(nn.Module):
    """The untied head's kernel ``(d, vocab)``: declared here, applied
    by ``OuroLM`` a block of tokens at a time."""

    d_model: int
    vocab: int

    @nn.compact
    def __call__(self):
        return self.param("kernel", L.gaussian_init(0.02),
                          (self.d_model, self.vocab))


class OuroLMNet(nn.Module):
    """Token ids ``(B, S)`` -> ``(states (T, B, S, d): the normed state
    after every pass, exit-gate logits (T - 1, B, S) in float32)``; the
    head (``OuroHead``) is only declared."""

    vocab: int
    n_layers: int
    total_ut_steps: int
    layer: dict          # OuroLayer's fields
    rope_theta: float = 1e6
    remat: bool = False

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        del train  # no dropout, no batch statistics
        d, dtype = self.layer["d_model"], self.layer["dtype"]
        steps = self.total_ut_steps
        # every layer of every pass rotates by the same angles: one
        # table a step, outside the scanned body
        rotary = rotary_table(jnp.arange(tokens.shape[1]),
                              self.layer["head_dim"], self.rope_theta)
        x = nn.Embed(self.vocab, d, embedding_init=L.gaussian_init(0.02),
                     name="embed")(tokens).astype(dtype)
        OuroHead(d, self.vocab, name="head")()
        stack = dict(n_layers=self.n_layers, layer=self.layer,
                     remat=self.remat, name="stack")
        if self.is_initializing():
            # one pass makes every parameter
            states = OuroStack(**stack)(x, rotary)[1][None]
        else:
            with jax.named_scope("ouro/pass"):
                _, states = nn.scan(
                    OuroStack, variable_broadcast="params",
                    split_rngs={"params": False}, in_axes=nn.broadcast,
                    length=steps)(**stack)(x, rotary)
        with jax.named_scope("ouro/exit_gate"):
            # zeros: the exit distribution starts at 1/2, 1/4, ... and
            # the last pass takes what is left
            gate = nn.Dense(1, kernel_init=nn.initializers.zeros,
                            dtype=dtype, name="exit_gate")(states[:steps - 1])
        return states, gate[..., 0].astype(jnp.float32)


def exit_distribution(gate_logits):
    """``(log p, p)`` of the exit distribution, ``(T, tokens)`` each,
    from the gate's logits ``(T - 1, tokens)``: pass t < T takes
    ``lam(t)`` of what the passes before it left, pass T all that is
    left.  In logs, so that a saturated gate gives 0 and no NaN."""
    row = jnp.zeros((1,) + gate_logits.shape[1:], gate_logits.dtype)
    log_stop = -jax.nn.softplus(-gate_logits)       # log lam(t)
    log_go = -jax.nn.softplus(gate_logits)          # log (1 - lam(t))
    # what the passes before t left: log prod_{j<t} (1 - lam(j))
    left = jnp.concatenate([row, jnp.cumsum(log_go, axis=0)])
    log_p = left + jnp.concatenate([log_stop, row])
    return log_p, jnp.exp(log_p)


def ouro_train_flops(*, n_layers: int, d_model: int, d_ff: int, vocab: int,
                     seq_len: int, total_ut_steps: int) -> float:
    """Trained FLOPs per SEQUENCE, 2xMAC units: 6 for every parameter
    applied to a token (forward 2, backward 4), every pass: a layer's
    four attention projections and three MLP matrices, and the head,
    which runs after every pass; plus attention's score and value
    products counted CAUSALLY (6 d s (s + 1) a layer and pass).  What is
    RUN and useful: the loop is counted (6 x parameters would be short
    by T), the recomputed forwards of ``remat`` are not.  The one count:
    the benchmark's ``flops/ouro.py`` hands out this function."""
    per_token = n_layers * (4 * d_model ** 2 + 3 * d_model * d_ff) \
        + d_model * vocab
    dense = 6.0 * seq_len * total_ut_steps * per_token
    attention = (6.0 * total_ut_steps * n_layers * d_model
                 * seq_len * (seq_len + 1))
    return dense + attention


@functools.lru_cache(maxsize=None)
def _log_plan(steps: int, n_layers: int, remat: bool, tokens: int) -> None:
    """The loop always engages, so its counter is its plan: one line a
    shape (trace time only), as ``tile_plan`` and the blocked loss say
    theirs."""
    _log.info("%d passes x %d layers (one scanned body), %s; head and loss "
              "over %d x %d tokens", steps, n_layers,
              "each layer recomputed" if remat else "activations stored",
              steps, tokens)


class OuroLM(TpuModel):
    """Looped LM over data-sharded batches; reference contract."""

    name = "ouro_lm"
    batch_partition = P(AXIS_DATA)
    #: the decode runtime serves ``TransformerLMNet`` trees only (a
    #: looped stack needs a cache row per pass and layer)
    decode_capable = False

    @classmethod
    def default_config(cls) -> ModelConfig:
        return ModelConfig(batch_size=8, n_epochs=5, optimizer="adamw",
                           learning_rate=3e-4, weight_decay=0.01,
                           lr_schedule="constant", print_freq=20)

    def __init__(self, *args, vocab: int = 256, seq_len: int = 128,
                 n_layers: int = 2, d_model: int = 128, n_heads: int = 4,
                 head_dim: int = 32, d_ff: int = 352,
                 total_ut_steps: int = 4, exit_entropy_beta: float = 0.1,
                 rope_theta: float = 1e6, rms_norm_eps: float = 1e-6,
                 **kwargs):
        if total_ut_steps < 1:
            raise ValueError(f"total_ut_steps={total_ut_steps}: the stack "
                             "runs at least once")
        if n_heads * head_dim != d_model or head_dim % 2:
            raise ValueError(
                f"{n_heads} heads of {head_dim} over d_model={d_model}: the "
                "heads must multiply out to the hidden size (the projections "
                "are square), and RoPE pairs the halves of an even head")
        self._net_cfg = dict(
            vocab=vocab, seq_len=seq_len, n_layers=n_layers,
            d_model=d_model, n_heads=n_heads, head_dim=head_dim, d_ff=d_ff,
            total_ut_steps=total_ut_steps, rope_theta=rope_theta,
            rms_eps=rms_norm_eps)
        self.exit_entropy_beta = exit_entropy_beta
        super().__init__(*args, **kwargs)
        self.train_flops_per_sample = ouro_train_flops(
            n_layers=n_layers, d_model=d_model, d_ff=d_ff, vocab=vocab,
            seq_len=seq_len, total_ut_steps=total_ut_steps)

    def _input_dtype(self):
        return jnp.int32

    def build_data(self):
        c = self._net_cfg
        return SeqLM_data(vocab=c["vocab"], seq_len=c["seq_len"],
                          seed=self.config.seed)

    def build_module(self) -> nn.Module:
        c = dict(self._net_cfg)
        del c["seq_len"]
        return OuroLMNet(
            vocab=c.pop("vocab"), n_layers=c.pop("n_layers"),
            total_ut_steps=c.pop("total_ut_steps"),
            rope_theta=c.pop("rope_theta"), remat=self.config.remat,
            layer=dict(c, dtype=self._compute_dtype()))

    def _states(self, params, tokens):
        c = self._net_cfg
        _log_plan(c["total_ut_steps"], c["n_layers"], self.config.remat,
                  tokens.size)
        return self.module.apply({"params": params}, tokens)

    def loss_fn(self, params, model_state, batch, rng):
        """The exit-weighted objective; ``metrics`` carry it as ``loss``,
        pass T's top-1 error as ``error``, and each pass's mean exit
        mass and mean loss and the mean exit entropy."""
        del rng  # no dropout
        tokens, targets = batch
        states, gate = self._states(params, tokens)
        steps, n = states.shape[0], targets.size
        log_p, p = exit_distribution(gate.reshape(steps - 1, n))
        entropy = -jnp.sum(p * log_p, axis=0)                 # (tokens,)
        with jax.named_scope("ouro/loss"):
            weighted, token_loss, token_miss = \
                L.blocked_softmax_cross_entropy(
                    states.reshape(steps * n, -1), params["head"]["kernel"],
                    None, jnp.tile(targets.reshape(-1), steps),
                    vocab_axis=1, weights=p.reshape(-1) / n,
                    label_smoothing=self.config.label_smoothing)
        loss = weighted - self.exit_entropy_beta * jnp.mean(entropy)
        metrics = {"loss": loss,
                   "error": jnp.mean(token_miss.reshape(steps, n)[-1]),
                   "ouro_exit_mass": jnp.mean(p, axis=1),
                   "ouro_loss_pass": jnp.mean(token_loss.reshape(steps, n),
                                              axis=1),
                   "ouro_exit_entropy": jnp.mean(entropy)}
        return loss, (model_state, metrics)

    def eval_fn(self, params, model_state, batch):
        """Pass T's loss and error: what the model answers with."""
        tokens, targets = batch
        states, _ = self._states(params, tokens)
        with jax.named_scope("ouro/loss"):
            loss, err = L.blocked_softmax_cross_entropy(
                states[-1].reshape(targets.size, -1),
                params["head"]["kernel"], None, targets.reshape(-1),
                vocab_axis=1)
        return {"loss": loss, "error": err}

    def _flush_metrics(self, recorder) -> None:
        """The base flush, and the pending steps' exit masses, pass
        losses and exit entropy to ``monitor``, as gauges of the newest
        step (device arrays until here; the flush is the fence
        anyway)."""
        from theanompi_tpu import monitor

        if self._pending:
            newest = self._pending[-1][1]
            mass, losses = (np.asarray(newest[key]).reshape(
                -1, self._net_cfg["total_ut_steps"])[-1]
                for key in ("ouro_exit_mass", "ouro_loss_pass"))
            for t, (m, l) in enumerate(zip(mass, losses), start=1):
                monitor.set_gauge(f"ouro/exit_mass_{t}", float(m))
                monitor.set_gauge(f"ouro/loss_pass_{t}", float(l))
            monitor.set_gauge(
                "ouro/exit_entropy",
                float(np.asarray(newest["ouro_exit_entropy"]).ravel()[-1]))
        super()._flush_metrics(recorder)
