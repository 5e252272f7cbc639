"""ops/attention.py's contract with its caller since PR 33: handed a
rotary table, and where a head fills whole lanes, the kernels pick a
head by index map from (B, T, H * D), as a projection leaves it, and
rotate q and k themselves; every other call keeps the folded kernels,
to the parent's jaxpr.  Interpret mode on the CPU; the real shapes are
compiled for the chip in tests/test_attention_tiles.py."""

import hashlib
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import theanompi_tpu.ops.attention as A
from theanompi_tpu.models.zaya import rope
from theanompi_tpu.ops import pallas_mode

THETA = 1e4


def _qkv(b, t, hq, hkv, d, dtype):
    key = jax.random.key(3)
    shapes = ((b, t, hq, d), (b, t, hkv, d), (b, t, hkv, d))
    return tuple(jax.random.normal(jax.random.fold_in(key, i), s).astype(
        dtype) for i, s in enumerate(shapes))


def _folded(monkeypatch):
    """The kernels as every other call gets them: heads folded in HBM,
    the positions passed, any rotation left to XLA."""
    real = A.tile_plan

    def plan(*args, rotary=False, **kw):
        return real(*args, **kw)._replace(
            rotary="XLA" if rotary else None)
    monkeypatch.setattr(A, "tile_plan", plan)


def _out_and_grads(fn, q, k, v):
    out, vjp = jax.vjp(fn, q, k, v)
    g = jax.random.normal(jax.random.key(7), out.shape).astype(out.dtype)
    return (out,) + vjp(g)


#: heads: (query, key/value); tiles: (length, _Q_BLOCK): one tile, and
#: 10 of 16
HEADS = {"equal_heads": (2, 2), "8_over_2": (8, 2)}
TILES = {"one_tile": (64, 64), "several_tiles": (256, 64)}
#: float32 to 1e-5; bf16 as tests/test_ops.py holds the kernel to XLA
TOLERANCE = {"float32": dict(rtol=1e-5, atol=1e-5),
             "bfloat16": dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("dtype", TOLERANCE)
@pytest.mark.parametrize("tiles", TILES)
@pytest.mark.parametrize("heads", HEADS)
def test_heads_by_index_map_equal_the_folded_heads(monkeypatch, heads, tiles,
                                                   dtype):
    """Forward and dq, dk, dv of the kernels that pick heads by index
    map and rotate, against the folded kernels behind ``rotary_xla``,
    on the same values and the same table."""
    (hq, hkv), (t, q_block) = HEADS[heads], TILES[tiles]
    monkeypatch.setattr(A, "_Q_BLOCK", q_block)
    q, k, v = _qkv(1, t, hq, hkv, 128, dtype)
    table = A.rotary_table(jnp.arange(t), 128, THETA)
    attend = lambda q, k, v: A.fused_attention(  # noqa: E731
        q, k, v, causal=True, impl="pallas", rotary=table)
    plan = A.tile_plan(t, t, 128, q.dtype, True, rotary=True)
    assert plan.rotates and not plan.positions
    got = _out_and_grads(attend, q, k, v)
    _folded(monkeypatch)
    plan = A.tile_plan(t, t, 128, q.dtype, True, rotary=True)
    assert not plan.rotates and plan.positions
    want = _out_and_grads(attend, q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a.astype(jnp.float32),
                                   b.astype(jnp.float32), **TOLERANCE[dtype])


@pytest.mark.parametrize("dtype", TOLERANCE)
@pytest.mark.parametrize("tiles", TILES)
@pytest.mark.parametrize("heads", HEADS)
def test_the_kernels_rotation_equals_rope_before_the_kernel(
        monkeypatch, heads, tiles, dtype):
    """``fused_attention(..., rotary=table)`` against ``rope`` on q and
    k and then ``fused_attention``: forward and dq, dk, dv (the kernel
    turns dq and dk back before their one rounding)."""
    (hq, hkv), (t, q_block) = HEADS[heads], TILES[tiles]
    monkeypatch.setattr(A, "_Q_BLOCK", q_block)
    q, k, v = _qkv(1, t, hq, hkv, 128, dtype)
    positions = jnp.arange(t)
    table = A.rotary_table(positions, 128, THETA)
    assert A.tile_plan(t, t, 128, q.dtype, True, rotary=True).rotates
    got = _out_and_grads(
        lambda q, k, v: A.fused_attention(q, k, v, causal=True,
                                          impl="pallas", rotary=table),
        q, k, v)
    want = _out_and_grads(
        lambda q, k, v: A.fused_attention(
            rope(q, positions, 128, THETA), rope(k, positions, 128, THETA),
            v, causal=True, impl="pallas"), q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a.astype(jnp.float32),
                                   b.astype(jnp.float32), **TOLERANCE[dtype])


@pytest.mark.parametrize("case", ["head_32", "ragged_q_tail", "xla"])
def test_where_the_kernel_does_not_rotate_xla_does(monkeypatch, case):
    """A head that does not fill the lanes, a ragged q tail (whose last
    q block would read past the table) and the composed form all rotate
    by ``rotary_xla``, which is ``rope`` to the bit."""
    monkeypatch.setattr(A, "_Q_BLOCK", 32)
    t, d = (80, 128) if case == "ragged_q_tail" else (64, 32)
    q, k, v = _qkv(1, t, 2, 2, d, jnp.float32)
    positions = jnp.arange(t)
    table = A.rotary_table(positions, d, THETA)
    np.testing.assert_array_equal(A.rotary_xla(q, table),
                                  rope(q, positions, d, THETA))
    assert A.tile_plan(t, t, d, q.dtype, True, rotary=True).rotary == "XLA"
    impl = "xla" if case == "xla" else "pallas"
    got = _out_and_grads(
        lambda q, k, v: A.fused_attention(q, k, v, causal=True, impl=impl,
                                          rotary=table), q, k, v)
    want = _out_and_grads(
        lambda q, k, v: A.fused_attention(
            rope(q, positions, d, THETA), rope(k, positions, d, THETA), v,
            causal=True, impl="xla"), q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_a_backward_over_the_budget_turns_the_gradients_back(monkeypatch):
    """The kernel rotated in the forward and the fused backward does
    not fit: the composed backward runs on the rotated q and k, and dq
    and dk come back through the rotation's transpose.  Over explicit
    positions: over the default ones such a shape streams both passes
    (tests/test_attention_window.py)."""
    monkeypatch.setattr(A, "_Q_BLOCK", 64)
    q, k, v = _qkv(1, 128, 2, 2, 128, jnp.float32)
    positions = jnp.arange(128)
    table = A.rotary_table(positions, 128, THETA)
    monkeypatch.setattr(A, "_fits_vmem_bwd", lambda *a, **kw: False)
    ran = []
    real = A._xla_bwd
    monkeypatch.setattr(A, "_xla_bwd",
                        lambda *a: (ran.append(1), real(*a))[1])
    got = _out_and_grads(
        lambda q, k, v: A.fused_attention(q, k, v, positions, positions,
                                          causal=True, impl="pallas",
                                          rotary=table),
        q, k, v)
    assert ran
    want = _out_and_grads(
        lambda q, k, v: A.fused_attention(
            rope(q, positions, 128, THETA), rope(k, positions, 128, THETA),
            v, causal=True, impl="xla"), q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_explicit_positions_reach_the_index_map_kernels():
    """Explicit positions at a head of 128 with the rotation inside:
    the kernels are handed them and mask score by score, every tile
    visited."""
    q, k, v = _qkv(1, 32, 2, 2, 128, jnp.float32)
    q_pos, k_pos = 5 + jnp.arange(32), jnp.arange(32)
    table = A.rotary_table(k_pos, 128, THETA)
    plan = A.tile_plan(32, 32, 128, q.dtype, True, default_positions=False,
                       rotary=True)
    assert plan.rotates and plan.positions and not plan.skip
    got = _out_and_grads(
        lambda q, k, v: A.fused_attention(q, k, v, q_pos, k_pos, causal=True,
                                          impl="pallas", rotary=table),
        q, k, v)
    want = _out_and_grads(
        lambda q, k, v: A.fused_attention(q, k, v, q_pos, k_pos, causal=True,
                                          impl="xla", rotary=table), q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_a_head_of_128_without_a_table_keeps_the_folded_kernels():
    """``ZayaLM``'s call (8 over 2 heads of 128, its own partial rotary
    in XLA): the folded kernels with the positions, as before PR 33."""
    plan = A.tile_plan(2048, 2048, 128, jnp.bfloat16, True)
    assert not plan.rotates and plan.positions
    assert str(plan) == "q block 512, key tile 512, 10 of 16 tiles"


def test_a_table_of_other_rows_is_refused():
    q, k, v = _qkv(1, 16, 2, 2, 128, jnp.float32)
    with pytest.raises(ValueError, match="one row a position"):
        A.fused_attention(q, k, v, causal=True,
                          rotary=A.rotary_table(jnp.arange(8), 128, THETA))


@pytest.mark.parametrize("q_shape,kv_heads,rotary,said", [
    ((4, 2048, 16, 128), 16, True,
     "pallas (fits, q block 512, key tile 512, 10 of 16 tiles, "
     "heads by index map, rotary in kernel)"),
    ((4, 2048, 8, 128), 2, False,
     "pallas (fits, q block 512, key tile 512, 10 of 16 tiles)"),
    ((8, 1024, 16, 64), 16, False,
     "pallas (fits, q block 512, key tile 512, 3 of 4 tiles)"),
    ((8, 1024, 16, 64), 16, True,
     "pallas (fits, q block 512, key tile 512, 3 of 4 tiles, "
     "rotary in XLA)"),
])
def test_the_log_says_how_heads_are_reached_and_where_the_rotation_runs(
        monkeypatch, caplog, q_shape, kv_heads, rotary, said):
    """Both passes' one line a shape, a pure function of shape and
    arguments, at the two cells' shapes of head 128 and at head 64."""
    A._log_choice.cache_clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(A, "_pallas_attention_bwd",
                        lambda q, k, v, *a, **kw: (q, k, v))
    b, t, h, d = q_shape
    q = jnp.zeros(q_shape, jnp.bfloat16)
    k = jnp.zeros((b, t, kv_heads, d), jnp.bfloat16)
    plan = A.tile_plan(t, t, d, q.dtype, True, rotary=rotary)
    pos = jnp.arange(t) if plan.positions else None
    with caplog.at_level(logging.INFO, logger=A.__name__):
        assert A._resolve_impl(None, q, k, plan) == "pallas"
        A._fused_bwd(d ** -0.5, True, False, None, plan,
                     (q, k, k, pos, pos, None, q, None), q)
    A._log_choice.cache_clear()
    fwd, bwd = (r.getMessage() for r in caplog.records)
    assert fwd.startswith("attention fwd") and fwd.endswith(said)
    assert bwd.startswith("attention bwd") and bwd.endswith(said)


#: (q shape, key/value heads) -> sha256 of ``str(jax.make_jaxpr(...))`` of
#: the loss and its three gradients, bf16, causal, taken on PR 33's
#: parent (commit cd65843) in this installation (jax 0.9.0): the two
#: ``gpt2m`` cells' shapes and ``zaya1_8b_s2048_x1``'s
PARENT_JAXPR = {
    ((8, 1024, 16, 64), 16): "39824586224fde1f",
    ((64, 128, 16, 64), 16): "84dfcc633af85942",
    ((4, 2048, 8, 128), 2): "30147e188a48dfc8",
}


@pytest.mark.parametrize("shape,kv_heads", PARENT_JAXPR)
def test_a_call_without_a_table_traces_to_the_parents_jaxpr(
        monkeypatch, shape, kv_heads):
    """The accepted cells' guard: nothing of the index map or of the
    rotation reaches the folded kernels, to the letter."""
    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    k = jax.ShapeDtypeStruct(shape[:2] + (kv_heads, shape[3]), jnp.bfloat16)
    loss = lambda q, k, v: A.fused_attention(  # noqa: E731
        q, k, v, causal=True, impl="pallas",
        name="guard").astype(jnp.float32).sum()
    text = str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        q, k, k))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_JAXPR[shape, kv_heads]
