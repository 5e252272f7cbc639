"""The selective state-space recurrence of a Mamba-2 layer, in its
chunked matrix ("SSD") form.

Per head, with a state ``S (P, N)`` that starts at zero::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;    y_t = S_t C_t + D x_t

``x (B, T, H, P)``; ``dt (B, T, H)`` the positive time steps; ``A (H,)``
negative, one a head; ``B`` and ``C`` ``(B, T, G, N)``, shared by the
``H / G`` heads of a group (head h reads group ``h // (H / G)``); ``D
(H,)`` the skip.

The recurrence is linear in ``S``, so a chunk of ``Q`` steps is four
matrix products and the sequential dependence is left between chunks
only ("Transformers are SSMs", arXiv:2405.21060, section 6).  With
``L_t`` the running sum of ``dt A`` inside a chunk:

* in the chunk: ``y_t += sum_{s <= t} exp(L_t - L_s) (C_t . B_s) dt_s
  x_s``, the decay-masked ``C B^T`` product (``Q x Q`` a group) applied
  to ``x``;
* each chunk's end state, from zero: ``sum_s exp(L_Q - L_s) dt_s x_s
  B_s^T``;
* the states carried across the chunks: ``S_in[c + 1] = exp(L_Q of c)
  S_in[c] + end state of c`` (a scan over the ``T / Q`` chunks,
  elementwise);
* their contribution to the next chunk's outputs: ``y_t += exp(L_t)
  S_in C_t``.

Decays and running sums are float32 and so are the carried states; the
four products run in ``x``'s dtype with float32 accumulation.  JAX
differentiates it.  One implementation, plain ``jax.numpy``: XLA's
fusions carry no name a trace reducer could find, so a profile reads it
by the caller's scope (``nemotron_h/mamba/ssd``); the kernel that
replaces it brings its own name.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_chunked(x, dt, a, b, c, d, *, chunk: int):
    """``y (B, T, H, P)`` in ``x.dtype``; see the module docstring.
    ``T`` is a whole number of chunks."""
    batch, t, h, p = x.shape
    g, n = b.shape[2:]
    if t % chunk or h % g:
        raise ValueError(
            f"{t} steps in chunks of {chunk}, {h} heads over {g} groups: "
            "the sequence is a whole number of chunks and a group a whole "
            "number of heads")
    nc, r, dtype, f32 = t // chunk, h // g, x.dtype, jnp.float32
    x32 = x.astype(f32).reshape(batch, nc, chunk, g, r, p)
    dt = dt.astype(f32).reshape(batch, nc, chunk, g, r)
    b = b.reshape(batch, nc, chunk, g, n)
    c = c.reshape(batch, nc, chunk, g, n)
    # L (b, c, g, r, q): the log decay from the chunk's start through t
    run = jnp.cumsum(jnp.moveaxis(dt * a.astype(f32).reshape(g, r), 2, -1),
                     axis=-1)

    def product(spec, lhs, rhs):
        return jnp.einsum(spec, lhs, rhs, preferred_element_type=f32)

    # in the chunk: the decay-masked C B^T, applied to dt x
    scores = product("bcqgn,bcsgn->bcgqs", c, b)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(
        causal, run[..., :, None] - run[..., None, :], -jnp.inf))
    xdt = x32 * dt[..., None]                          # (b, c, s, g, r, p)
    y = product("bcgrqs,bcsgrp->bcqgrp",
                (scores[:, :, :, None] * decay).astype(dtype),
                xdt.astype(dtype))
    # each chunk's end state from zero, then the states carried across
    to_end = jnp.moveaxis(jnp.exp(run[..., -1:] - run), -1, 2)
    ends = product("bcsgrp,bcsgn->bcgrpn",
                   (xdt * to_end[..., None]).astype(dtype), b)
    through = jnp.exp(run[..., -1])                    # (b, c, g, r)

    def carry(state, chunk_c):
        end, factor = chunk_c
        return state * factor[..., None, None] + end, state

    _, entering = jax.lax.scan(
        carry, jnp.zeros_like(ends[:, 0]),
        (jnp.moveaxis(ends, 1, 0), jnp.moveaxis(through, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)            # (b, c, g, r, p, n)
    y = y + (product("bcqgn,bcgrpn->bcqgrp", c, entering.astype(dtype))
             * jnp.moveaxis(jnp.exp(run), -1, 2)[..., None])
    y = y + x32 * d.astype(f32).reshape(g, r, 1)
    return y.reshape(batch, t, h, p).astype(dtype)
