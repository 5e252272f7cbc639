"""Plain reference for the ``zaya1_8b`` configuration.

The layer of ``Zyphra/ZAYA1-8B`` as ``benchmarks/configs/zaya1_8b.json``
states it (``published`` for the sizes, ``assumed`` for what the
published ``config.json`` leaves open, ``departures`` for what is left
out), in straight ``jax.numpy``: float32,
``jax.default_matmul_precision("highest")`` (set by the caller), no flax
module, no kernel, no sort.  With ``x (B, S, d)``::

    layer(x):  h = x + CCA(RMSNorm(x));   y = h + MoE(RMSNorm(h))
    CCA(u):    q~ = u Wq;  k~ = u Wk                    the compressed latent
               v  = [ u_t Wv_now ; u_{t-1} Wv_prev ]    value shift (u_{-1} = 0)
               q^ = conv1(conv0(q~)),  k^ = conv1(conv0(k~))
                     conv0: causal, depthwise over time; conv1: causal over
                     time, dense within each head
               m  = (q~ + rep(k~)) / 2                  before the convolutions
               q  = q^ + m;   k = k^ + mean over each group of m
               q  = unit(q) sqrt(D);  k = unit(k) sqrt(D) temp_h
               q, k = rope(q, k) on the first rotary_dim of each head
               o  = causal softmax attention, grouped-query heads, 1/sqrt(D)
               return o Wo
    MoE(u):    r = u Wd;  z = W3 gelu(W2 gelu(W1 r + b1) + b2) + b3
               p = softmax(z);  e = argmax (log p + bias)   top-1 of all experts;
                     the balancing bias is state, not a parameter (given)
               return p_e expert_e(u) if e is held here, else 0
               expert_e(u) = (silu(u Wg_e) * (u Wu_e)) Wdn_e
    logits = RMSNorm(y_L) E^T, E the tied embedding;  mean token cross-entropy

Every expert held here is applied to EVERY token and masked by the
routing (no gather, no grouped product): the independent form of what
``parallel/expert.routed_experts`` computes.  ``held = (first, count)``
is this chip's share; the absent experts' part is left out here exactly
as in the program, and with ``held = (0, n_experts)`` this is the whole
layer (the tier-1 test adds the shares up).

So that it fits beside the resident training state, attention goes by
blocks of queries and the loss by blocks of tokens, and each layer and
each loss block is wrapped in ``jax.checkpoint``; none of that changes
a value.  It reads the system's own parameter tree by its pinned names
(``embed``, ``Layer_{i}``: ``attn_norm``, ``cca`` (``q_proj`` ...),
``moe_norm``, ``router`` (``down``, ``fc1`` ...), ``experts_gate`` ...,
``final_norm``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
TOKEN_BLOCK = 1024


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _shift(x, steps=1):
    """x[:, t - steps], zeros before the start (time is axis 1)."""
    if steps == 0:
        return x
    return jnp.concatenate(
        [jnp.zeros_like(x[:, :steps]), x[:, :x.shape[1] - steps]], axis=1)


def _convs(x, w0, w1):
    """conv1(conv0(x)) on x (B, T, H, D): w0 (taps, H, D) depthwise,
    w1 (taps, H, D, D) dense within a head; tap j reads taps-1-j back."""
    y = sum(_shift(x, w0.shape[0] - 1 - j) * w0[j]
            for j in range(w0.shape[0]))
    return sum(jnp.einsum("bthc,hcd->bthd", _shift(y, w1.shape[0] - 1 - j),
                          w1[j]) for j in range(w1.shape[0]))


def _rope(x, rotary_dim, theta):
    t = x.shape[1]
    half = rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary_dim:]], -1)


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-12)


def _attention(q, k, v):
    """Causal softmax attention, q (B, T, Hq, D) over k/v (B, T, Hkv, D):
    query head h reads key/value head h // (Hq / Hkv).  By blocks of
    queries, each against the whole masked score rows."""
    b, t, hq, d = q.shape
    group = hq // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    outs = []
    for start in range(0, t, QUERY_BLOCK):
        qb = q[:, start:start + QUERY_BLOCK]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * d ** -0.5
        q_pos = start + jnp.arange(qb.shape[1])
        scores = jnp.where(q_pos[:, None] >= jnp.arange(t)[None, :],
                           scores, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd",
                               jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(outs, axis=1)


def _cca(u, p, *, n_heads, n_kv_heads, rotary_dim, rope_theta):
    b, t, _ = u.shape
    hq, hk = n_heads, n_kv_heads
    q_lat = (u @ p["q_proj"]["kernel"]).reshape(b, t, hq, -1)
    k_lat = (u @ p["k_proj"]["kernel"]).reshape(b, t, hk, -1)
    d = q_lat.shape[-1]
    v = jnp.concatenate([u @ p["v_proj_now"]["kernel"],
                         _shift(u) @ p["v_proj_prev"]["kernel"]],
                        -1).reshape(b, t, hk, d)
    q_hat = _convs(q_lat, p["conv0_q"], p["conv1_q"])
    k_hat = _convs(k_lat, p["conv0_k"], p["conv1_k"])
    m = (q_lat + jnp.repeat(k_lat, hq // hk, axis=2)) / 2
    q = q_hat + m
    k = k_hat + m.reshape(b, t, hk, hq // hk, d).mean(3)
    q = _unit(q) * d ** 0.5
    k = _unit(k) * d ** 0.5 * p["temp"][:, None]
    q, k = _rope(q, rotary_dim, rope_theta), _rope(k, rotary_dim, rope_theta)
    o = _attention(q, k, v)
    return o.reshape(b, t, hq * d) @ p["o_proj"]["kernel"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _moe(u, p, *, held, bias=0.0):
    first, count = held
    r = u @ p["router"]["down"]["kernel"]
    for name in ("fc1", "fc2"):
        r = _gelu_tanh(r @ p["router"][name]["kernel"]
                       + p["router"][name]["bias"])
    z = r @ p["router"]["fc3"]["kernel"] + p["router"]["fc3"]["bias"]
    probs = jax.nn.softmax(z, -1)
    chosen = jnp.argmax(jnp.log(probs + 1e-30) + bias, -1)
    p_chosen = jnp.take_along_axis(probs, chosen[..., None], -1)[..., 0]

    def one(out, expert):           # every held expert on every token
        gate, up, down, local = expert
        y = (jax.nn.silu(u @ gate) * (u @ up)) @ down
        weight = jnp.where(chosen == first + local, p_chosen, 0.0)
        return out + weight[..., None] * y, None

    # a scan and no Python loop: one expert's program, compiled once
    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        p["experts_gate"][:count], p["experts_up"][:count],
        p["experts_down"][:count], jnp.arange(count)))
    return out


def _layer(x, p, bias, cfg):
    cca_cfg = {k: cfg[k] for k in ("n_heads", "n_kv_heads", "rotary_dim",
                                   "rope_theta")}
    eps = cfg["rms_norm_eps"]
    h = x + _cca(_rms_norm(x, p["attn_norm"]["scale"], eps), p["cca"],
                 **cca_cfg)
    return h + _moe(_rms_norm(h, p["moe_norm"]["scale"], eps), p,
                    held=cfg["held"], bias=bias)


def _block_loss(x, table, targets):
    logp = jax.nn.log_softmax(x @ table.T)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def loss(params, tokens, targets, router_bias, *, n_heads: int,
         n_kv_heads: int,
         held_experts, partial_rotary_factor: float = 0.5,
         rope_theta: float = 5e6, rms_norm_eps: float = 1e-5):
    """Mean next-token cross-entropy over every position of every
    sequence, the head tied to the embedding.  ``tokens``/``targets``
    are int32 (B, T); ``router_bias`` is each layer's balancing bias
    ``(n_experts,)`` as the program holds it; ``held_experts = (first,
    count)``."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    table = params["embed"]["embedding"]
    head_dim = (params["Layer_0"]["cca"]["q_proj"]["kernel"].shape[1]
                // n_heads)
    cfg = dict(n_heads=n_heads, n_kv_heads=n_kv_heads,
               rotary_dim=int(head_dim * partial_rotary_factor),
               rope_theta=rope_theta, rms_norm_eps=rms_norm_eps,
               held=tuple(held_experts))
    x = table[tokens]
    layer = jax.checkpoint(functools.partial(_layer, cfg=cfg))
    n_layers = sum(1 for name in params if name.startswith("Layer_"))
    for i in range(n_layers):
        x = layer(x, params[f"Layer_{i}"], router_bias[i])
    x = _rms_norm(x, params["final_norm"]["scale"], rms_norm_eps)
    x, targets = x.reshape(-1, x.shape[-1]), targets.reshape(-1)
    block_loss = jax.checkpoint(_block_loss)
    total = sum(block_loss(x[i:i + TOKEN_BLOCK], table,
                           targets[i:i + TOKEN_BLOCK])
                for i in range(0, x.shape[0], TOKEN_BLOCK))
    return total / x.shape[0]


def inputs(model, batch, rng):
    """The reference's inputs: the batch as it is, and the balancing
    biases the program's controller has reached (state, no parameter:
    the reference is given them as it is given the weights)."""
    tokens, targets = batch
    state = model.state.model_state["router_state"]
    n_layers = sum(1 for name in state if name.startswith("Layer_"))
    return (jnp.asarray(tokens), jnp.asarray(targets),
            [state[f"Layer_{i}"]["bias"] for i in range(n_layers)])
