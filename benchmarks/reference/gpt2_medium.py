"""Plain reference for the ``gpt2_medium`` configuration.

A pre-LN decoder as GPT-2 (Radford et al. 2019) describes it, at the
sizes of ``openai-community/gpt2-medium``'s ``config.json``, in straight
``jax.numpy``: float32, ``jax.default_matmul_precision("highest")`` (set
by the caller), no flax module, no kernel, the whole s x s score matrix
with a causal mask.  Each block is wrapped in ``jax.checkpoint`` so that
the reference's backward pass stays below the training step's own peak
memory (it would otherwise raise the process's peak reading); that
changes no value.  It reads the system's own parameter tree by its
pinned names (``Embed_0``, ``pos_emb``, ``Block_{i}``, ``q_proj`` ...,
``LayerNorm_0``, ``Dense_0``).

Departures from the published model, all the program's and followed
here so that the two compute the same function (they are listed in
``benchmarks/configs/gpt2_medium.json``): no bias on q, k, v and the
attention output projection; an output head of its own, with a bias,
not tied to the embedding; LayerNorm epsilon 1e-6; no dropout; tanh
GELU (GPT-2's ``gelu_new``, so no departure there).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-6


def _layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + EPS) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, n_heads: int):
    b, t, d = x.shape
    d_head = d // n_heads
    h = _layer_norm(x, p["LayerNorm_0"])
    split = lambda a: a.reshape(b, t, n_heads, d_head)  # noqa: E731
    q = split(h @ p["q_proj"]["kernel"])
    k = split(h @ p["k_proj"]["kernel"])
    v = split(h @ p["v_proj"]["kernel"])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d_head ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    x = x + attn.reshape(b, t, d) @ p["o_proj"]["kernel"]
    h = _layer_norm(x, p["LayerNorm_1"])
    h = _gelu_tanh(h @ p["mlp_up"]["kernel"] + p["mlp_up"]["bias"])
    return x + h @ p["mlp_down"]["kernel"] + p["mlp_down"]["bias"]


def loss(params, tokens, targets, *, n_heads: int):
    """Mean next-token cross-entropy over every position of every
    sequence.  ``tokens``/``targets`` are int32 (B, T)."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    t = tokens.shape[1]
    x = params["Embed_0"]["embedding"][tokens] + params["pos_emb"][:t][None]
    block = jax.checkpoint(_block, static_argnums=(2,))
    n_layers = sum(1 for name in params if name.startswith("Block_"))
    for i in range(n_layers):
        x = block(x, params[f"Block_{i}"], n_heads)
    x = _layer_norm(x, params["LayerNorm_0"])
    head = params["Dense_0"]
    logits = x @ head["kernel"] + head["bias"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def inputs(model, batch, rng):
    """The reference's inputs: the batch as it is."""
    tokens, targets = batch
    return jnp.asarray(tokens), jnp.asarray(targets)
