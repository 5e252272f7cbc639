"""ops/gated_delta.py: the chunked gated delta rule against the rule
stepped one token at a time, forward and gradients, in float32 on the
CPU: its ``jax.numpy`` form (the WY triangular solve a chunk, a scan
over the chunks) at small sizes, and its kernel pair (interpret mode)
at the smallest shape the kernels take, also against the ``jax.numpy``
form; and what the rule must do whatever its form."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.ops import gated_delta as G
from theanompi_tpu.ops.gated_delta import (delta_plan, gated_delta_chunked,
                                           gated_delta_recurrent)

#: the smallest shape the kernels take: one sequence, two value heads,
#: key and value heads of one lane tile, chunks of 64 (two a grid step)
KERNELS = dict(batch=1, heads=2, dk=128, dv=128)


def _inputs(t, batch=2, heads=3, dk=8, dv=6, seed=0):
    """Keys and queries L2-normalised (queries scaled) as the layer
    hands them over, log decays <= 0 and strengths in (0, 1)."""
    keys = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(keys[0], (batch, t, heads, dk))
    k = jax.random.normal(keys[1], (batch, t, heads, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (batch, t, heads, dv))
    g = -jax.nn.softplus(jax.random.normal(keys[3], (batch, t, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (batch, t, heads)))
    return q, k, v, g, beta


def _jnp_form(monkeypatch):
    """``gated_delta_chunked`` with the ``jax.numpy`` form wherever the
    plan picks the kernels (same padding, same chunks)."""
    monkeypatch.setattr(G, "_delta_pallas", lambda q, k, v, g, beta, plan:
                        G._delta_jnp(q, k, v, g, beta, plan.chunk))


@pytest.mark.parametrize("t,chunk,shape", [
    (8, 8, {}), (16, 8, {}), (24, 8, {}), (21, 8, {}), (5, 8, {}),
    # the kernels: one grid step of two chunks, two steps (the second
    # chunk of the second step padded), a ragged length, and eight
    # chunks of 16 in one grid step
    (128, 64, KERNELS), (192, 64, KERNELS), (150, 64, KERNELS),
    (128, 16, KERNELS)],
    ids=["1_chunk", "2_chunks", "3_chunks", "ragged_21_of_8",
         "shorter_than_a_chunk", "kernels_2_chunks", "kernels_3_chunks",
         "kernels_ragged_150_of_64", "kernels_8_chunks_of_16"])
def test_the_chunked_rule_is_the_recurrence(t, chunk, shape, monkeypatch):
    """Outputs and the gradients of q, k, v, g and beta, for 1, 2 and 3
    chunks and for lengths that are no multiple of the chunk; the
    kernels are held to the recurrence and to the ``jax.numpy`` form."""
    args = _inputs(t, **shape)
    plan = delta_plan(*args[2].shape[:3], args[1].shape[-1],
                      args[2].shape[-1], chunk, itemsize=4)
    assert plan.pallas == bool(shape), str(plan)

    def loss(fn):
        return lambda *x: jnp.sum(jnp.sin(fn(*x)))

    def rule(*x):
        return gated_delta_chunked(*x, chunk=chunk)

    got = [rule(*args), jax.grad(loss(rule), argnums=range(5))(*args)]
    wants = [[gated_delta_recurrent(*args), jax.grad(
        loss(gated_delta_recurrent), argnums=range(5))(*args)]]
    if plan.pallas:
        _jnp_form(monkeypatch)
        wants.append([rule(*args),
                      jax.grad(loss(rule), argnums=range(5))(*args)])
    for want in wants:
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-6)
        for name, a, b in zip("q k v g beta".split(), got[1], want[1]):
            np.testing.assert_allclose(a, b, rtol=1e-4,
                                       atol=1e-5 * float(jnp.abs(b).max()),
                                       err_msg=name)


def test_no_write_strength_leaves_the_state_as_it_was():
    """beta = 0 writes nothing: from the zero state every output is 0;
    after a first half that writes, a second half with beta = 0 and no
    decay reads ONE state, across two chunks, whatever its keys and
    values."""
    q, k, v, g, beta = _inputs(32)
    silent = gated_delta_chunked(q, k, v, g, jnp.zeros_like(beta), chunk=8)
    assert float(jnp.abs(silent).max()) == 0.0
    second = jnp.arange(32) >= 12
    beta = jnp.where(second[None, :, None], 0.0, beta)
    g = jnp.where(second[None, :, None], 0.0, g)
    q = jnp.where(second[None, :, None, None], q[:, 12:13], q)
    out = gated_delta_chunked(q, k, v, g, beta, chunk=8)
    np.testing.assert_allclose(out[:, 12:], jnp.broadcast_to(
        out[:, 12:13], out[:, 12:].shape), rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(out[:, 12]).max()) > 0.01


@pytest.mark.parametrize("read", ["each_its_own", "the_first_across_chunks",
                                  "kernels_each_its_own",
                                  "kernels_the_first_across_chunks"])
def test_orthonormal_keys_without_decay_write_each_value_exactly(read):
    """g = 0 and beta = 1 on orthonormal keys: nothing the state holds is
    predicted for a new key, so each write is the value itself; reading
    key t back gives v_t, and reading the first key at every step gives
    v_0 at every step, the carried state crossing the chunks (on the
    kernels: four chunks of 64 over keys of 256, so the state crosses a
    chunk inside a grid step and from one grid step to the next)."""
    kernels = read.startswith("kernels_")
    t, dk, dv, chunk = (256, 256, 128, 64) if kernels else (16, 16, 5, 4)
    assert delta_plan(1, t, 2, dk, dv, chunk, itemsize=4).pallas == kernels
    keys = jnp.broadcast_to(jnp.eye(dk)[None, :t, None, :], (1, t, 2, dk))
    v = jax.random.normal(jax.random.key(3), (1, t, 2, dv))
    q = keys if read.endswith("each_its_own") else jnp.broadcast_to(
        keys[:, :1], keys.shape)
    out = gated_delta_chunked(q, keys, v, jnp.zeros((1, t, 2)),
                              jnp.ones((1, t, 2)), chunk=chunk)
    want = v if read.endswith("each_its_own") else jnp.broadcast_to(
        v[:, :1], v.shape)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)


def test_in_bfloat16_the_rule_keeps_its_state_in_float32():
    """bfloat16 products over a float32 state and float32 decays: the
    output is bfloat16 and within bfloat16's reach of the float32 rule
    (a state carried in bfloat16 over 64 steps would not be), in the
    ``jax.numpy`` form and on the kernels."""
    for t, chunk, shape in ((64, 16, dict(heads=2, dk=16, dv=16)),
                            (256, 64, KERNELS)):
        q, k, v, g, beta = _inputs(t, **shape)
        assert delta_plan(q.shape[0], t, q.shape[2], q.shape[3],
                          v.shape[3], chunk).pallas == (shape is KERNELS)
        low = gated_delta_chunked(
            *(x.astype(jnp.bfloat16) for x in (q, k, v)), g, beta,
            chunk=chunk)
        assert low.dtype == jnp.bfloat16
        want = gated_delta_recurrent(q, k, v, g, beta)
        err = float(jnp.linalg.norm(low.astype(jnp.float32) - want)
                    / jnp.linalg.norm(want))
        assert err < 2e-2, (t, err)


def test_the_plan_is_said_once_a_shape(caplog):
    """One log line a shape: chunks, their size, any padding, and which
    path: the kernels at the Qwen3-Next cell's shape (and the smallest
    they take), ``jax.numpy`` at the dry run's (state 8, chunk 8)."""
    plan = delta_plan(4, 2048, 32, 128, 128, 64, "qwen3_next_delta_rule")
    assert str(plan) == (
        "qwen3_next_delta_rule: 32 chunks of 64, 32 heads, state 128 x 128, "
        "pallas (grid 4 x 4 x 16, 2 chunks of 8 heads a step, inverse and "
        "state in VMEM)")
    assert str(delta_plan(1, 150, 2, 128, 128, 64)) == (
        "gated_delta: 4 chunks of 64 (106 steps padded), 2 heads, state "
        "128 x 128, pallas (grid 1 x 1 x 2, 2 chunks of 2 heads a step, "
        "inverse and state in VMEM)")
    assert "(3 steps padded)" in str(delta_plan(1, 21, 2, 8, 8, 8))
    assert not delta_plan(2, 16, 4, 8, 8, 8).pallas
    # a chunk of 8 in bfloat16 is half a sublane tile
    assert not delta_plan(1, 128, 2, 128, 128, 8).pallas
    assert delta_plan(1, 128, 2, 128, 128, 8, itemsize=4).pallas
    args = _inputs(13, heads=1)
    with caplog.at_level(logging.INFO, logger="theanompi_tpu.ops.gated_delta"):
        for _ in range(2):
            jax.jit(lambda *x: gated_delta_chunked(*x, chunk=4,
                                                   name="once"))(*args)
    said = [r.getMessage() for r in caplog.records if "once" in r.getMessage()]
    assert said == ["once: 4 chunks of 4 (3 steps padded), 1 heads, "
                    "state 8 x 6, jax.numpy (WY triangular solve a chunk, "
                    "scan over the chunks)"]
