"""Plain reference for the ``ouro_2_6b`` configuration.

The looped model of ``ByteDance/Ouro-2.6B`` as
``benchmarks/configs/ouro_2_6b.json`` states it (``published`` for the
sizes, ``assumed`` for what the published ``config.json`` leaves open,
``departures`` for what is left out), in straight ``jax.numpy``:
float32, ``jax.default_matmul_precision("highest")`` (set by the
caller), no flax module, no kernel, no scan: a Python loop over the
passes and, inside it, over the layers, the SAME layers every pass.
With tokens ``x (B, S)``, targets ``y``, ``T`` passes, ``L`` layers::

    h(0) = E[x]
    for t = 1..T:
        u = h(t-1)
        for l = 1..L:
            a = rms(u, g_l1);  q, k, v = a Wq, a Wk, a Wv       heads of D
            q, k = rope(q), rope(k)          all D dims, rotate-half, positions 0..S-1
            o = softmax(mask(q k^T / sqrt(D))) v                 the whole (S, S) scores
            u = u + rms(o Wo, g_l2)
            m = rms(u, g_l3);  f = (silu(m Wgate) * (m Wup)) Wdown
            u = u + rms(f, g_l4)
        h(t) = rms(u, g_f)
        l(t)_i = -log softmax(h(t)_i W_o)[y_i];   lam(t)_i = sigmoid(h(t)_i . w_g + b_g)
    p(t)_i = lam(t)_i prod_{j<t} (1 - lam(j)_i)   for t < T;   p(T)_i = prod_{j<T} (1 - lam(j)_i)
    loss = mean_i [ sum_t p(t)_i l(t)_i  -  beta * H(p_i) ],   H(p) = - sum_t p(t) log p(t)

One sequence of 2 048 tokens at the published widths keeps about 0.6 GB
a layer application in float32 (the (16, S, S) scores alone are 0.27),
and there are T x L = 32 of them beside the resident training state: so
each layer application is wrapped in ``jax.checkpoint``, and so is each
pass's head with its cross-entropy (a pass's logits are 0.4 GB; the four
are never held together).  Neither changes a value.  It reads the
system's own parameter tree by its pinned names (``embed``, ``stack``:
``Layer_{i}`` (``attn_norm``, ``q_proj`` ... ``mlp_out_norm``) and
``final_norm``, ``head``, ``exit_gate``) and imports nothing of the
program's models.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (B, S, H, D): dim i rotates with dim i + D/2 by the angle
    position * theta^(-2i/D)."""
    s, half = x.shape[1], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def _layer(u, p, *, n_heads, rope_theta, eps):
    b, s, d = u.shape
    heads = (b, s, n_heads, d // n_heads)
    a = _rms(u, p["attn_norm"]["scale"], eps)
    q = _rope((a @ p["q_proj"]["kernel"]).reshape(heads), rope_theta)
    k = _rope((a @ p["k_proj"]["kernel"]).reshape(heads), rope_theta)
    v = (a @ p["v_proj"]["kernel"]).reshape(heads)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(heads[-1]))
    visible = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, d)
    u = u + _rms(o @ p["o_proj"]["kernel"], p["attn_out_norm"]["scale"], eps)
    m = _rms(u, p["mlp_norm"]["scale"], eps)
    f = (jax.nn.silu(m @ p["gate_proj"]["kernel"])
         * (m @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]
    return u + _rms(f, p["mlp_out_norm"]["scale"], eps)


def _token_losses(h, kernel, targets):
    """-log softmax(h W_o)[y] of every token, (B, S)."""
    logp = jax.nn.log_softmax(h @ kernel)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def loss(params, tokens, targets, *, n_heads: int, total_ut_steps: int,
         exit_entropy_beta: float = 0.1, rope_theta: float = 1e6,
         rms_norm_eps: float = 1e-6):
    """The exit-weighted objective over every position of every
    sequence; ``tokens`` / ``targets`` are int32 (B, S)."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    stack = params["stack"]
    n_layers = sum(1 for name in stack if name.startswith("Layer_"))
    layer = jax.checkpoint(functools.partial(
        _layer, n_heads=n_heads, rope_theta=rope_theta, eps=rms_norm_eps))
    token_losses = jax.checkpoint(_token_losses)

    h = params["embed"]["embedding"][tokens]
    losses, stops = [], []
    for _ in range(total_ut_steps):
        for i in range(n_layers):
            h = layer(h, stack[f"Layer_{i}"])
        h = _rms(h, stack["final_norm"]["scale"], rms_norm_eps)
        losses.append(token_losses(h, params["head"]["kernel"], targets))
        stops.append(jax.nn.sigmoid(
            (h @ params["exit_gate"]["kernel"])[..., 0]
            + params["exit_gate"]["bias"][0]))

    left = jnp.ones_like(losses[0])     # prod_{j<t} (1 - lam(j))
    exits = []
    for lam in stops[:-1]:
        exits.append(lam * left)
        left = left * (1.0 - lam)
    exits.append(left)                  # the last pass takes what is left
    expected = sum(p * l for p, l in zip(exits, losses))
    entropy = -sum(p * jnp.log(jnp.maximum(p, 1e-37)) for p in exits)
    return jnp.mean(expected - exit_entropy_beta * entropy)


def inputs(model, batch, rng):
    """The reference's inputs: the batch as it is (the model keeps no
    state beside its parameters)."""
    del model, rng
    tokens, targets = batch
    return jnp.asarray(tokens), jnp.asarray(targets)
