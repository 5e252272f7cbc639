"""Plain reference for the ``nemotron_twotower_30b`` configuration.

The language model of
``nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16`` as
``benchmarks/configs/nemotron_twotower_30b.json`` states it
(``published`` for the sizes, ``assumed`` for what the published
``config.json`` leaves open, ``departures`` for what is left out), in
straight ``jax.numpy``: float32,
``jax.default_matmul_precision("highest")`` (set by the caller), no flax
module, no kernel, no chunked scan.  The pattern names each layer's
mixer; with ``h (B, T, d)``::

    layer(h):  h + mixer(rms(h, g))
    M(u):  [z | xBC | dt] = u W_in                      H P | H P + 2 G N | H
           xBC = silu(conv(xBC) + b)                    causal, depthwise, k taps
           x (T, H, P), B (T, G, N), C (T, G, N) = split(xBC);  head h reads group h // (H / G)
           dt = softplus(dt + dt_bias);  A = -exp(A_log)
           S_{-1} = 0;  S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
                 ONE TOKEN AT A TIME, per head, S (P, N)
           y = rms over each of the G groups of channels of (y * silu(z)), times w
           return y W_out
    E(u):  s = sigmoid(u W_r)                           over ALL experts
           chosen = top-k of (s + bias)                 the bias is state, not a parameter (given)
           w_e = s_e / (sum of the chosen s + 1e-20) * routed_scaling_factor
           return sum over the chosen e HELD here of w_e relu(u W_up,e)^2 W_down,e
                  + relu(u W_sup)^2 W_sdown             the shared expert, every token
    *(u):  q, k, v = u Wq, u Wk, u Wv;  causal softmax(q k^T / sqrt(D)) v, grouped-query
           heads, NO position signal;  return o Wo
    logits = rms(h_L, g_f) W_head;  mean token cross-entropy

Every expert held here is applied to EVERY token and masked by the
routing (no gather, no grouped product).  ``held = (first, count)`` is
this chip's share; the absent experts' part is left out here exactly as
in the program, and with ``held = (0, n_experts)`` this is the whole
layer (the tier-1 test adds the sixteen shares up).

So that its gradient fits beside the resident training state, the
recurrence is stepped in blocks of ``TIME_BLOCK`` tokens, each block
wrapped in ``jax.checkpoint`` (2 048 kept states of 64 x 64 x 128
float32 would be 4.3 GB a layer), attention goes by blocks of queries
and the loss by blocks of tokens, and each layer is checkpointed; none
of that changes a value.  It reads the system's own parameter tree by
its pinned names (``embed``, ``Layer_{i}``: ``norm`` and one of
``mamba`` (``in_proj``, ``conv_kernel``, ``conv_bias``, ``A_log``,
``dt_bias``, ``D``, ``norm_scale``, ``out_proj``), ``moe`` (``router``,
``experts_up``, ``experts_down``, ``shared_up``, ``shared_down``),
``attention`` (``q_proj`` ... ``o_proj``); ``final_norm``, ``head``)
and imports nothing of the program's models or ops.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

TIME_BLOCK = 64
QUERY_BLOCK = 512
TOKEN_BLOCK = 1024


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _conv(x, kernel, bias):
    """Causal depthwise convolution over time (axis 1): tap j of
    ``kernel (k, C)`` reads the position ``k - 1 - j`` steps back,
    zeros before the start."""
    taps, t = kernel.shape[0], x.shape[1]
    out = bias
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :t - back]], axis=1)
        out = out + shifted * kernel[j]
    return out


def _recurrence(x, dt, a, b, c, d):
    """``y (B, T, H, P)`` of the selective recurrence, one token at a
    time; ``b`` and ``c`` already one a head, ``(B, T, H, N)``."""
    batch, t, h, p = x.shape
    n = b.shape[-1]

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs                    # (B, H, ...)
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        y_t = jnp.sum(state * c_t[..., None, :], -1) + d[:, None] * x_t
        return state, y_t

    @jax.checkpoint
    def block(state, inputs):
        return jax.lax.scan(step, state, inputs)

    size = TIME_BLOCK if t % TIME_BLOCK == 0 else t
    blocks = tuple(jnp.moveaxis(v, 1, 0).reshape(
        (t // size, size) + v.shape[:1] + v.shape[2:])
        for v in (x, dt, b, c))
    _, y = jax.lax.scan(block, jnp.zeros((batch, h, p, n), x.dtype), blocks)
    return jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1)


def _mamba(u, p, cfg):
    batch, t, _ = u.shape
    h, g, n = cfg["mamba_heads"], cfg["n_groups"], cfg["state"]
    inner = p["out_proj"]["kernel"].shape[0]
    z, xbc, dt = jnp.split(u @ p["in_proj"]["kernel"],
                           [inner, 2 * inner + 2 * g * n], axis=-1)
    xbc = jax.nn.silu(_conv(xbc, p["conv_kernel"], p["conv_bias"]))
    x, b, c = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    per_head = lambda m: jnp.repeat(  # noqa: E731
        m.reshape(batch, t, g, n), h // g, axis=2)
    y = _recurrence(x.reshape(batch, t, h, -1),
                    jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
                    per_head(b), per_head(c), p["D"])
    gated = (y.reshape(batch, t, inner) * jax.nn.silu(z)).reshape(
        batch, t, g, -1)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, -1, keepdims=True) + cfg["rms_norm_eps"])
    return (normed.reshape(batch, t, inner) * p["norm_scale"]
            ) @ p["out_proj"]["kernel"]


def _relu2_mlp(u, up, down):
    return jnp.square(jax.nn.relu(u @ up)) @ down


def _moe(u, p, bias, cfg):
    first, count = cfg["held"]
    scores = jax.nn.sigmoid(u @ p["router"]["kernel"])
    chosen = jax.lax.top_k(scores + bias, cfg["top_k"])[1]       # (..., k)
    picked = jnp.take_along_axis(scores, chosen, -1)
    weights = (picked / (picked.sum(-1, keepdims=True) + 1e-20)
               * cfg["routed_scaling_factor"])

    def one(out, expert):           # every held expert on every token
        up, down, local = expert
        weight = jnp.sum(jnp.where(chosen == first + local, weights, 0.0), -1)
        return out + weight[..., None] * _relu2_mlp(u, up, down), None

    # a scan and no Python loop: one expert's program, compiled once
    routed, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        p["experts_up"][:count], p["experts_down"][:count],
        jnp.arange(count)))
    return routed + _relu2_mlp(u, p["shared_up"]["kernel"],
                               p["shared_down"]["kernel"])


def _attention(u, p, cfg):
    """Causal softmax attention, query head h over key/value head
    ``h // (Hq / Hkv)``, by blocks of queries, each against the whole
    masked score rows."""
    batch, t, _ = u.shape
    hq, hk = cfg["n_heads"], cfg["n_kv_heads"]
    q = (u @ p["q_proj"]["kernel"]).reshape(batch, t, hq, -1)
    k = (u @ p["k_proj"]["kernel"]).reshape(batch, t, hk, -1)
    v = (u @ p["v_proj"]["kernel"]).reshape(batch, t, hk, -1)
    d = q.shape[-1]
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
    return jnp.concatenate(outs, axis=1).reshape(batch, t, hq * d) \
        @ p["o_proj"]["kernel"]


def _layer(h, p, bias, kind, cfg):
    u = _rms(h, p["norm"]["scale"], cfg["rms_norm_eps"])
    if kind == "M":
        return h + _mamba(u, p["mamba"], cfg)
    if kind == "E":
        return h + _moe(u, p["moe"], bias, cfg)
    return h + _attention(u, p["attention"], cfg)


def _block_loss(x, kernel, targets):
    logp = jax.nn.log_softmax(x @ kernel)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def loss(params, tokens, targets, router_bias, *, pattern: str,
         mamba_heads: int, n_groups: int, state: int, top_k: int,
         held_experts, routed_scaling_factor: float, n_heads: int,
         n_kv_heads: int, rms_norm_eps: float = 1e-5):
    """Mean next-token cross-entropy over every position of every
    sequence.  ``tokens``/``targets`` are int32 (B, T); ``router_bias``
    maps an ``E`` layer's index to its correction bias ``(n_experts,)``
    as the program holds it; ``held_experts = (first, count)``."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    cfg = dict(mamba_heads=mamba_heads, n_groups=n_groups, state=state,
               top_k=top_k, held=tuple(held_experts),
               routed_scaling_factor=routed_scaling_factor, n_heads=n_heads,
               n_kv_heads=n_kv_heads, rms_norm_eps=rms_norm_eps)
    x = params["embed"]["embedding"][tokens]
    for i, kind in enumerate(pattern):
        layer = jax.checkpoint(functools.partial(_layer, kind=kind, cfg=cfg))
        x = layer(x, params[f"Layer_{i}"], router_bias.get(i, 0.0))
    x = _rms(x, params["final_norm"]["scale"], rms_norm_eps)
    x, targets = x.reshape(-1, x.shape[-1]), targets.reshape(-1)
    block_loss = jax.checkpoint(_block_loss)
    total = sum(block_loss(x[i:i + TOKEN_BLOCK], params["head"]["kernel"],
                           targets[i:i + TOKEN_BLOCK])
                for i in range(0, x.shape[0], TOKEN_BLOCK))
    return total / x.shape[0]


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
