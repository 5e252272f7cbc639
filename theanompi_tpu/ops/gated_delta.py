"""The gated delta rule of a Gated DeltaNet layer, in its chunked form.

Per head, with a state ``S (dk, dv)`` that starts at zero::

    S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - exp(g_t) S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

``q``, ``k (B, T, H, dk)`` (already normed, scaled and repeated to the
value heads by the caller); ``v (B, T, H, dv)``; ``g (B, T, H)`` the
log decays (``<= 0``) and ``beta (B, T, H)`` the write strengths, both
float32.  The rule subtracts what the state already predicts for
``k_t`` before it writes, so a chunk of ``C`` steps is no longer a sum
of decayed outer products (``ops/ssd.py``'s form): inside a chunk the
writes depend on one another through a unit-lower-triangular system,
the WY / UT transform of Yang et al., "Gated Delta Networks"
(arXiv:2412.06464).  With ``gamma`` the running sum of ``g`` inside a
chunk, ``Gamma_ij = exp(gamma_i - gamma_j)`` for ``j <= i``:

* ``A = strict_lower(diag(beta) K K^T * Gamma)``; ``(I + A) W =
  diag(beta) (K * exp(gamma))`` and ``(I + A) U = diag(beta) V``, by a
  triangular solve (``T = (I + A)^-1`` never formed);
* with ``S`` the entering state: ``U' = U - W S``;
* ``O = (Q * exp(gamma)) S + (Q K^T * Gamma * lower_incl) U'``;
* ``S_next = exp(gamma_C) S + (K * exp(gamma_C - gamma))^T U'``.

Everything but ``U' = U - W S`` and ``S_next`` is independent from
chunk to chunk and is made for all chunks at once; a ``lax.scan`` over
the chunks carries ``S`` (float32) and keeps each chunk's entering
state, and the outputs are made from those afterwards, again for all
chunks at once.  The decays, the running sums, the triangular system
and the carried state are float32; the products take the inputs'
dtype with float32 accumulation.  JAX differentiates the whole (the
caller recomputes it under ``remat``).  A length that is no multiple
of ``C`` is padded with steps that neither decay nor write
(``g = beta = 0``), whose outputs are dropped.

``gated_delta_recurrent`` is the rule stepped one token at a time: the
ground truth the tests hold the chunked form to.
"""

from __future__ import annotations

import dataclasses
import functools
import logging

import jax
import jax.numpy as jnp

_log = logging.getLogger(__name__)
_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class DeltaPlan:
    """How ``gated_delta_chunked`` runs a shape: one log line a shape."""

    batch: int
    chunks: int
    chunk: int
    heads: int
    key_dim: int
    value_dim: int
    pad: int = 0
    name: str = "gated_delta"

    def __str__(self):
        return (f"{self.name}: {self.chunks} chunks of {self.chunk}"
                + (f" ({self.pad} steps padded)" if self.pad else "")
                + f", {self.heads} heads, state {self.key_dim} x "
                f"{self.value_dim}, jax.numpy (WY triangular solve a chunk, "
                "scan over the chunks)")


def delta_plan(batch: int, t: int, heads: int, key_dim: int,
               value_dim: int, chunk: int,
               name: str | None = None) -> DeltaPlan:
    chunks = -(-t // chunk)
    return DeltaPlan(batch, chunks, chunk, heads, key_dim, value_dim,
                     chunks * chunk - t, name or "gated_delta")


@functools.lru_cache(maxsize=None)
def _log_plan(plan: DeltaPlan) -> None:
    """One line a shape (trace time only), as ``ssd``'s plan says its
    own."""
    _log.info("%s", plan)


def _mm(a, b, spec):
    return jnp.einsum(spec, a, b, preferred_element_type=_F32)


def gated_delta_chunked(q, k, v, g, beta, *, chunk: int = 64,
                        name: str | None = None):
    """``o (B, T, H, dv)`` in ``v.dtype``; see the module docstring.
    ``name`` labels the plan's log line."""
    batch, t, h, dk = k.shape
    dv = v.shape[-1]
    plan = delta_plan(batch, t, h, dk, dv, chunk, name)
    _log_plan(plan)
    dtype = v.dtype
    if plan.pad:
        pad = ((0, 0), (0, plan.pad), (0, 0))
        q, k, v = (jnp.pad(x, pad + ((0, 0),)) for x in (q, k, v))
        g, beta = jnp.pad(g, pad), jnp.pad(beta, pad)
    n, c = plan.chunks, chunk

    def chunks(x):              # (B, T, H, ...) -> (B, N, H, C, ...)
        x = x.reshape((batch, n, c, h) + x.shape[3:])
        return jnp.moveaxis(x, 3, 2)

    q, k, v = chunks(q), chunks(k), chunks(v)
    g, beta = chunks(g.astype(_F32)), chunks(beta.astype(_F32))
    gamma = jnp.cumsum(g, axis=-1)                       # (B, N, H, C)
    rows = jnp.arange(c)
    lower = rows[:, None] >= rows[None, :]
    # exp only where j <= i: above the diagonal the difference is >= 0
    # and could overflow
    gap = jnp.where(lower, gamma[..., :, None] - gamma[..., None, :],
                    -jnp.inf)
    decay = jnp.exp(gap)                                 # Gamma, (.., C, C)
    strict = rows[:, None] > rows[None, :]
    a = jnp.where(strict, beta[..., None] * _mm(k, k, "...id,...jd->...ij")
                  * decay, 0.0)
    system = a + jnp.eye(c, dtype=_F32)
    rhs = jnp.concatenate(
        [beta[..., None] * k.astype(_F32) * jnp.exp(gamma)[..., None],
         beta[..., None] * v.astype(_F32)], axis=-1)
    wu = jax.lax.linalg.triangular_solve(system, rhs, left_side=True,
                                         lower=True, unit_diagonal=True)
    w, u = wu[..., :dk].astype(dtype), wu[..., dk:]
    last = gamma[..., -1]                                # (B, N, H)
    k_out = (k.astype(_F32) * jnp.exp(last[..., None] - gamma)[..., None]
             ).astype(dtype)

    def step(state, inputs):
        w_c, u_c, k_c, last_c = inputs
        fresh = u_c - _mm(w_c, state.astype(dtype), "bhcd,bhde->bhce")
        state_next = (jnp.exp(last_c)[..., None, None] * state
                      + _mm(k_c, fresh.astype(dtype), "bhcd,bhce->bhde"))
        return state_next, (state, fresh.astype(dtype))

    scanned = tuple(jnp.moveaxis(x, 1, 0) for x in (w, u, k_out, last))
    _, (entering, fresh) = jax.lax.scan(
        step, jnp.zeros((batch, h, dk, dv), _F32), scanned)
    entering, fresh = (jnp.moveaxis(x, 0, 1) for x in (entering, fresh))
    scores = (_mm(q, k, "...id,...jd->...ij") * decay).astype(dtype)
    out = (_mm((q.astype(_F32) * jnp.exp(gamma)[..., None]).astype(dtype),
               entering.astype(dtype), "bnhcd,bnhde->bnhce")
           + _mm(scores, fresh, "bnhij,bnhje->bnhie"))
    out = jnp.moveaxis(out, 2, 3).reshape(batch, n * c, h, dv)
    return out[:, :t].astype(dtype)


def gated_delta_recurrent(q, k, v, g, beta):
    """The rule one token at a time, float32 throughout; ``o (B, T, H,
    dv)`` float32."""
    q, k, v, g, beta = (x.astype(_F32) for x in (q, k, v, g, beta))
    batch, _, h, dk = k.shape

    def step(state, inputs):
        q_t, k_t, v_t, g_t, b_t = inputs
        state = jnp.exp(g_t)[..., None, None] * state
        predicted = jnp.einsum("bhde,bhd->bhe", state, k_t)
        state = state + jnp.einsum("bhd,bhe->bhde", k_t,
                                   b_t[..., None] * (v_t - predicted))
        return state, jnp.einsum("bhde,bhd->bhe", state, q_t)

    inputs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, out = jax.lax.scan(
        step, jnp.zeros((batch, h, dk, v.shape[-1]), _F32), inputs)
    return jnp.moveaxis(out, 0, 1)
