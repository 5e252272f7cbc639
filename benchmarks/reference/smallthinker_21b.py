"""Plain reference for the ``smallthinker_21b`` configuration.

The language model of ``PowerInfer/SmallThinker-21BA3B-Instruct`` as
``benchmarks/configs/smallthinker_21b.json`` states it (``published``
for the sizes, ``assumed`` for what the published ``config.json`` leaves
open, ``departures`` for what is left out), in straight ``jax.numpy``:
float32, ``jax.default_matmul_precision("highest")`` (set by the
caller), no flax module, no kernel.  Layer ``i`` rotates q and k where
``rope_layout[i]`` is 1 and attends over a window of ``window`` keys
where ``sliding_window_layout[i]`` is 1; with ``rms(x, w) = x /
sqrt(mean(x^2) + eps) * w``::

    layer(x):  u = rms(x, w_in)
               p = softmax(u W_r)                  over ALL experts
               q, k, v = u W_q, u W_k, u W_v       query head h reads key/value head h // (Hq / Hkv)
               rotate-half RoPE (theta) on all of each head of q and k, where rope_layout
               a = softmax(q k^T / sqrt(D) masked) v
                   query i sees key j iff j <= i, and i - j < window where sliding_window_layout
               h = x + a W_o;  v = rms(h, w_post)
               chosen = top-k of (u W_r + bias);  w_e = p_e / sum of the chosen p
                                                   the bias is state, not a parameter (given)
               return h + sum over the chosen e HELD here of w_e (relu(v G_e) * v U_e) D_e
    logits = rms(h_L, w_f) W_head;  mean token cross-entropy

Every expert held here is applied to EVERY token and masked by the
routing (no gather, no grouped product).  ``held = (first, count)`` is
this chip's share; the absent experts' part is left out here exactly as
in the program, and with ``held = (0, n_experts)`` this is the whole
layer (the tier-1 test adds the four shares up).

So that its gradient fits beside the resident training state at 16 384
tokens, attention goes by blocks of ``QUERY_BLOCK`` queries, each
wrapped in ``jax.checkpoint`` (the global layer's probabilities stored
whole would be 28 x 16 384^2 / 2 float32, 15 GB): a window block reads
only the ``window + QUERY_BLOCK`` keys before its end, a global block
every key; the experts go one at a time and the loss by blocks of
tokens, each checkpointed, and each layer is checkpointed; none of that
changes a value.  It reads the system's own parameter tree by its
pinned names (``embed``, ``Layer_{i}``: ``input_norm``, ``router``,
``attention`` (``q_proj``, ``k_proj``, ``v_proj``, ``o_proj``),
``post_norm``, ``moe`` (``experts_gate``, ``experts_up``,
``experts_down``); ``final_norm``, ``head``) and imports nothing of the
program's models or ops.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256
TOKEN_BLOCK = 1024


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotate-half over all ``D`` dims of each head of ``x (B, T, H,
    D)``; frequencies ``theta ** (-i / (D / 2))``."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(u, p, cfg, rope, window):
    """Causal softmax attention, global or over ``window`` keys, by
    blocks of queries, each against the keys it can see; query head ``h``
    reads key/value head ``h // group``."""
    batch, t, _ = u.shape
    hq, hk = cfg["n_heads"], cfg["n_kv_heads"]
    q = (u @ p["q_proj"]["kernel"]).reshape(batch, t, hq, -1)
    d = q.shape[-1]
    k = (u @ p["k_proj"]["kernel"]).reshape(batch, t, hk, d)
    v = (u @ p["v_proj"]["kernel"]).reshape(batch, t, hk, d)
    if rope:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    q = q.reshape(batch, t, hk, hq // hk, d)
    size = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    # a window block reads the `reach` keys before its end: keys padded
    # in front so that every block's slice has one length
    reach = t if window is None else min(t, window + size)
    pad = reach - size
    k = jnp.concatenate([jnp.zeros_like(k[:, :pad]), k], axis=1)
    v = jnp.concatenate([jnp.zeros_like(v[:, :pad]), v], axis=1)

    @jax.checkpoint
    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, size, axis=1)
        kb = jax.lax.dynamic_slice_in_dim(k, start, reach, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, start, reach, axis=1)
        q_pos = start + jnp.arange(size)
        k_pos = start - pad + jnp.arange(reach)
        ahead = q_pos[:, None] - k_pos[None, :]
        seen = (ahead >= 0) & (k_pos[None, :] >= 0)
        if window is not None:
            seen &= ahead < window
        scores = jnp.where(seen, jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb)
                           * d ** -0.5, -jnp.inf)
        return jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(scores, -1),
                          vb)

    _, outs = jax.lax.scan(lambda c, start: (c, block(start)), None,
                           jnp.arange(0, t, size))
    o = jnp.moveaxis(outs, 0, 1).reshape(batch, t, hq * d)
    return o @ p["o_proj"]["kernel"]


def _moe(u, v, p_router, p, bias, cfg):
    """The held experts' part of the layer: the router reads ``u``,
    the experts ``v``; one held expert after another, each on every
    token."""
    first, count = cfg["held"]
    logits = u @ p_router["kernel"]
    probs = jax.nn.softmax(logits, -1)
    chosen = jax.lax.top_k(logits + bias, cfg["top_k"])[1]      # (..., k)
    picked = jnp.take_along_axis(probs, chosen, -1)
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-20)

    @jax.checkpoint
    def expert(out, held):
        e, gate, up, down = held
        weight = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
        return out + weight[..., None] * (
            (jax.nn.relu(v @ gate) * (v @ up)) @ down), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(v), (
        first + jnp.arange(count), p["experts_gate"][:count],
        p["experts_up"][:count], p["experts_down"][:count]))
    return out


def _layer(x, p, bias, rope, window, cfg):
    eps = cfg["rms_norm_eps"]
    u = _rms(x, p["input_norm"]["weight"], eps)
    h = x + _attention(u, p["attention"], cfg, rope, window)
    v = _rms(h, p["post_norm"]["weight"], eps)
    return h + _moe(u, v, p["router"], p["moe"], bias, cfg)


def _block_loss(kernel, total, block):
    x, targets = block
    logp = jax.nn.log_softmax(x @ kernel)
    return total - jnp.sum(jnp.take_along_axis(logp, targets[:, None],
                                               axis=-1)), None


def loss(params, tokens, targets, router_bias, *, n_layers: int,
         rope_layout, sliding_window_layout, window: int, top_k: int,
         held_experts, n_heads: int, n_kv_heads: int, rope_theta: float,
         rms_norm_eps: float = 1e-6):
    """Mean next-token cross-entropy over every position of every
    sequence.  ``tokens``/``targets`` are int32 (B, T); ``router_bias``
    maps a layer's index to its correction bias ``(n_experts,)`` as the
    program holds it; ``held_experts = (first, count)``."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    cfg = dict(top_k=top_k, held=tuple(held_experts), n_heads=n_heads,
               n_kv_heads=n_kv_heads, rope_theta=rope_theta,
               rms_norm_eps=rms_norm_eps)
    x = params["embed"]["embedding"][tokens]
    for i in range(n_layers):
        layer = jax.checkpoint(functools.partial(
            _layer, rope=bool(rope_layout[i]),
            window=window if sliding_window_layout[i] else None, cfg=cfg))
        x = layer(x, params[f"Layer_{i}"], router_bias.get(i, 0.0))
    x = _rms(x, params["final_norm"]["weight"], rms_norm_eps)
    n = targets.size
    size = TOKEN_BLOCK if n % TOKEN_BLOCK == 0 else n
    total, _ = jax.lax.scan(
        jax.checkpoint(functools.partial(_block_loss,
                                         params["head"]["kernel"])),
        jnp.zeros((), jnp.float32),
        (x.reshape(n // size, size, -1), targets.reshape(n // size, size)))
    return total / n


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
