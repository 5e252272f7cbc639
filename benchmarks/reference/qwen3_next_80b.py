"""Plain reference for the ``qwen3_next_80b`` configuration.

The language model of ``Qwen/Qwen3-Next-80B-A3B-Instruct`` as
``benchmarks/configs/qwen3_next_80b.json`` states it (``published`` for
the sizes, ``assumed`` for what the published ``config.json`` leaves
open, ``departures`` for what is left out), in straight ``jax.numpy``:
float32, ``jax.default_matmul_precision("highest")`` (set by the
caller), no flax module, no kernel, no chunked form.  Layer ``i`` is
``F`` (full attention) where ``(i + 1) % full_attention_interval == 0``
and ``L`` (Gated DeltaNet) elsewhere; with ``zrms(x, w) = x / rms(x) *
(1 + w)``, eps 1e-6::

    layer(x):  h = x + mixer(zrms(x, w_in));  h + moe(zrms(h, w_post))
    L(u):  per key head j of nk: [q_j (dk) | k_j (dk) | v_j (r dv) | z_j (r dv)] = u W_qkvz
           per key head j: [b_j (r) | a_j (r)] = u W_ba;   r = nv / nk
           [q k v] = silu(conv([q k v]))               causal, depthwise, 4 taps, no bias
           q = l2norm(q) / sqrt(dk);  k = l2norm(k);  value head h reads key head h // r
           beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
           S_0 = 0;  S_t = e^g_t S_{t-1} + beta_t k_t (v_t - e^g_t S_{t-1}^T k_t)^T;  o_t = S_t^T q_t
                 ONE TOKEN AT A TIME, per value head, S (dk, dv)
           y = o / rms(o) * w * silu(z)                 over each head's dv
           return y W_out
    F(u):  [q_h | gate_h] = (u W_q)_h                   D each, per query head
           q = zrms(q, w_q);  k = zrms(u W_k, w_k);  v = u W_v   over each head's D
           rotate-half RoPE (theta) on the first rot dims of q and k
           o = causal softmax(q k^T / sqrt(D)) v, grouped-query;  return (o * sigmoid(gate)) W_o
    moe(u):  p = softmax(u W_r)                         over ALL experts
             chosen = top-k of (u W_r + bias);  w_e = p_e / sum of the chosen p
                                                       the bias is state, not a parameter (given)
             return sum over the chosen e HELD here of w_e (silu(u G_e) * u U_e) D_e
                    + sigmoid(u w_sg) (silu(u G_s) * u U_s) D_s
    logits = zrms(h_L, w_f) W_head;  mean token cross-entropy
             + aux_coef * E sum_e f_e P_e              f_e: share of ALL layers' assignments
                                                       (the chosen) on e; P_e: mean p_e over all
                                                       layers' tokens

Every expert held here is applied to EVERY token and masked by the
routing (no gather, no grouped product).  ``held = (first, count)`` is
this chip's share; the absent experts' part is left out here exactly as
in the program, and with ``held = (0, n_experts)`` this is the whole
layer (the tier-1 test adds the sixteen shares up).

So that its gradient fits beside the resident training state, the
recurrence is stepped in blocks of ``TIME_BLOCK`` tokens, each block
wrapped in ``jax.checkpoint``, attention goes by blocks of queries,
the experts one at a time and the loss by blocks of tokens, and each
layer is checkpointed; none of that changes a value.  It reads the
system's own parameter tree by its pinned names (``embed``,
``Layer_{i}``: ``input_norm``, ``post_norm`` and one of
``linear_attention`` (``in_proj_qkvz``, ``in_proj_ba``,
``conv_kernel``, ``A_log``, ``dt_bias``, ``norm_weight``,
``out_proj``) or ``attention`` (``q_proj``, ``k_proj``, ``v_proj``,
``o_proj``, ``q_norm``, ``k_norm``), and ``moe`` (``router``,
``experts_gate``, ``experts_up``, ``experts_down``, ``shared_expert``,
``shared_expert_gate``); ``final_norm``, ``head``) and imports nothing of
the program's models or ops.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

TIME_BLOCK = 64
QUERY_BLOCK = 512
TOKEN_BLOCK = 1024


def _zrms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _conv(x, kernel):
    """Causal depthwise convolution over time (axis 1): tap j of
    ``kernel (k, C)`` reads the position ``k - 1 - j`` steps back,
    zeros before the start."""
    taps, t = kernel.shape[0], x.shape[1]
    out = 0.0
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :t - back]], axis=1)
        out = out + shifted * kernel[j]
    return out


def _recurrence(q, k, v, g, beta):
    """``o (B, T, H, dv)`` of the gated delta rule, one token at a time."""
    batch, t, h, dk = k.shape

    def step(state, inputs):
        q_t, k_t, v_t, g_t, b_t = inputs                # (B, H, ...)
        state = jnp.exp(g_t)[..., None, None] * state
        predicted = jnp.einsum("bhde,bhd->bhe", state, k_t)
        state = state + k_t[..., :, None] * (
            b_t[..., None] * (v_t - predicted))[..., None, :]
        return state, jnp.einsum("bhde,bhd->bhe", state, q_t)

    @jax.checkpoint
    def block(state, inputs):
        return jax.lax.scan(step, state, inputs)

    size = TIME_BLOCK if t % TIME_BLOCK == 0 else t
    blocks = tuple(jnp.moveaxis(x, 1, 0).reshape(
        (t // size, size) + x.shape[:1] + x.shape[2:])
        for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(
        block, jnp.zeros((batch, h, dk, v.shape[-1]), q.dtype), blocks)
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)


def _linear(u, p, cfg):
    batch, t, _ = u.shape
    nk, nv, dk = cfg["linear_key_heads"], cfg["linear_value_heads"], \
        cfg["linear_key_dim"]
    r = nv // nk
    dv = p["norm_weight"].shape[0]
    qkvz = (u @ p["in_proj_qkvz"]["kernel"]).reshape(batch, t, nk, -1)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv]
    z = qkvz[..., 2 * dk + r * dv:].reshape(batch, t, nv, dv)
    ba = (u @ p["in_proj_ba"]["kernel"]).reshape(batch, t, nk, 2 * r)
    b, a = ba[..., :r].reshape(batch, t, nv), ba[..., r:].reshape(batch, t, nv)
    mixed = jax.nn.silu(_conv(jnp.concatenate(
        [q.reshape(batch, t, -1), k.reshape(batch, t, -1),
         v.reshape(batch, t, -1)], -1), p["conv_kernel"]))
    q = mixed[..., :nk * dk].reshape(batch, t, nk, dk)
    k = mixed[..., nk * dk:2 * nk * dk].reshape(batch, t, nk, dk)
    v = mixed[..., 2 * nk * dk:].reshape(batch, t, nv, dv)
    l2 = lambda x: x * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    q = jnp.repeat(l2(q) * dk ** -0.5, r, axis=2)
    k = jnp.repeat(l2(k), r, axis=2)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    o = _recurrence(q, k, v, g, beta)
    y = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg["rms_norm_eps"]) * p["norm_weight"] \
        * jax.nn.silu(z)
    return y.reshape(batch, t, nv * dv) @ p["out_proj"]["kernel"]


def _rope(x, rot, theta):
    """Rotate-half on the first ``rot`` dims of each head of ``x (B, T,
    H, D)``; frequencies ``theta ** (-i / (rot / 2))``."""
    half = rot // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], -1)


def _attention(u, p, cfg):
    """Gated causal softmax attention, query head h over key/value head
    ``h // (Hq / Hkv)``, by blocks of queries, each against the whole
    masked score rows."""
    batch, t, _ = u.shape
    hq, hk, eps = cfg["n_heads"], cfg["n_kv_heads"], cfg["rms_norm_eps"]
    qg = (u @ p["q_proj"]["kernel"]).reshape(batch, t, hq, -1)
    d = qg.shape[-1] // 2
    q, gate = qg[..., :d], qg[..., d:]
    q = _zrms(q, p["q_norm"]["weight"], eps)
    k = _zrms((u @ p["k_proj"]["kernel"]).reshape(batch, t, hk, d),
              p["k_norm"]["weight"], eps)
    v = (u @ p["v_proj"]["kernel"]).reshape(batch, t, hk, d)
    rot = int(d * cfg["partial_rotary_factor"])
    q, k = _rope(q, rot, cfg["rope_theta"]), _rope(k, rot, cfg["rope_theta"])
    k, v = jnp.repeat(k, hq // hk, axis=2), jnp.repeat(v, hq // hk, axis=2)
    outs = []
    for start in range(0, t, QUERY_BLOCK):
        qb = q[:, start:start + QUERY_BLOCK]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * d ** -0.5
        q_pos = start + jnp.arange(qb.shape[1])
        scores = jnp.where(q_pos[:, None] >= jnp.arange(t)[None, :],
                           scores, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd",
                               jax.nn.softmax(scores, -1), v))
    o = jnp.concatenate(outs, axis=1) * jax.nn.sigmoid(gate)
    return o.reshape(batch, t, hq * d) @ p["o_proj"]["kernel"]


def _gated_mlp(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def _moe(u, p, bias, cfg):
    """The layer's output, and its loads and summed probabilities over
    all the experts (for the balancing loss)."""
    first, count = cfg["held"]
    n_experts = p["router"]["kernel"].shape[-1]
    logits = u @ p["router"]["kernel"]
    probs = jax.nn.softmax(logits, -1)
    chosen = jax.lax.top_k(logits + bias, cfg["top_k"])[1]      # (..., k)
    picked = jnp.take_along_axis(probs, chosen, -1)
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-20)

    def one(out, expert):           # every held expert on every token
        gate, up, down, local = expert
        weight = jnp.sum(jnp.where(chosen == first + local, weights, 0.0), -1)
        return out + weight[..., None] * _gated_mlp(u, gate, up, down), None

    # a scan and no Python loop: one expert's program, compiled once
    routed, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        p["experts_gate"][:count], p["experts_up"][:count],
        p["experts_down"][:count], jnp.arange(count)))
    s = p["shared_expert"]
    shared = _gated_mlp(u, s["gate"]["kernel"], s["up"]["kernel"],
                        s["down"]["kernel"])
    out = routed + jax.nn.sigmoid(u @ p["shared_expert_gate"]["kernel"]) \
        * shared
    load = jnp.sum(chosen[..., None] == jnp.arange(n_experts),
                   axis=tuple(range(chosen.ndim)), dtype=jnp.float32)
    return out, load, probs.reshape(-1, n_experts).sum(0)


def _layer(x, p, bias, kind, cfg):
    eps = cfg["rms_norm_eps"]
    u = _zrms(x, p["input_norm"]["weight"], eps)
    h = x + (_linear(u, p["linear_attention"], cfg) if kind == "L"
             else _attention(u, p["attention"], cfg))
    out, load, prob_sum = _moe(_zrms(h, p["post_norm"]["weight"], eps),
                               p["moe"], bias, cfg)
    return h + out, load, prob_sum


def _block_loss(x, kernel, targets):
    logp = jax.nn.log_softmax(x @ kernel)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def loss(params, tokens, targets, router_bias, *, n_layers: int,
         full_attention_interval: int, linear_key_heads: int,
         linear_value_heads: int, linear_key_dim: int, top_k: int,
         held_experts, n_heads: int, n_kv_heads: int,
         partial_rotary_factor: float, rope_theta: float,
         aux_loss_coef: float, rms_norm_eps: float = 1e-6):
    """Mean next-token cross-entropy over every position of every
    sequence, plus ``aux_loss_coef`` times the balancing loss.
    ``tokens``/``targets`` are int32 (B, T); ``router_bias`` maps a
    layer's index to its correction bias ``(n_experts,)`` as the program
    holds it; ``held_experts = (first, count)``."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    cfg = dict(linear_key_heads=linear_key_heads,
               linear_value_heads=linear_value_heads,
               linear_key_dim=linear_key_dim, top_k=top_k,
               held=tuple(held_experts), n_heads=n_heads,
               n_kv_heads=n_kv_heads,
               partial_rotary_factor=partial_rotary_factor,
               rope_theta=rope_theta, rms_norm_eps=rms_norm_eps)
    x = params["embed"]["embedding"][tokens]
    load = prob_sum = 0.0
    for i in range(n_layers):
        kind = "F" if (i + 1) % full_attention_interval == 0 else "L"
        layer = jax.checkpoint(functools.partial(_layer, kind=kind, cfg=cfg))
        x, layer_load, layer_probs = layer(x, params[f"Layer_{i}"],
                                           router_bias.get(i, 0.0))
        load, prob_sum = load + layer_load, prob_sum + layer_probs
    x = _zrms(x, params["final_norm"]["weight"], rms_norm_eps)
    x, targets = x.reshape(-1, x.shape[-1]), targets.reshape(-1)
    block_loss = jax.checkpoint(_block_loss)
    total = sum(block_loss(x[i:i + TOKEN_BLOCK], params["head"]["kernel"],
                           targets[i:i + TOKEN_BLOCK])
                for i in range(0, x.shape[0], TOKEN_BLOCK))
    seen = n_layers * x.shape[0]
    aux = prob_sum.shape[0] * jnp.sum(load / seen * prob_sum / seen)
    return total / x.shape[0] + aux_loss_coef * aux


def inputs(model, batch, rng):
    """The reference's inputs: the batch as it is, and the correction
    biases the program's controller has reached (state, no parameter:
    the reference is given them as it is given the weights), by the
    index of their layer."""
    del rng
    tokens, targets = batch
    state = model.state.model_state.get("router_state", {})
    return (jnp.asarray(tokens), jnp.asarray(targets),
            {int(name.split("_")[1]): layer["moe"]["bias"]
             for name, layer in state.items()})
