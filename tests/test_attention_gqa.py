"""ops/attention.py since PR 27: key/value heads fewer than query
heads, head size 128, the q block chosen per shape, named kernels.
Interpret mode on the CPU against the composed XLA form."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import theanompi_tpu.ops.attention as A


def _qkv(b, t, hq, hkv, d, dtype=jnp.float32, seed=0):
    key = jax.random.key(seed)
    shape = lambda h: (b, t, h, d)  # noqa: E731
    return tuple(jax.random.normal(jax.random.fold_in(key, i), shape(h),
                                   dtype)
                 for i, h in enumerate((hq, hkv, hkv)))


def _oracle(q, k, v):
    """Causal attention with the key/value heads repeated by hand."""
    group = q.shape[2] // k.shape[2]
    pos = jnp.arange(q.shape[1])
    return A._xla_attention(q, jnp.repeat(k, group, 2),
                            jnp.repeat(v, group, 2), pos, pos,
                            q.shape[-1] ** -0.5, True)


@pytest.mark.parametrize("q_block", [256, 128])
def test_grouped_query_heads_of_128_match_the_xla_form(monkeypatch, q_block):
    """8 query heads over 2 key/value heads of 128, forward and the
    fused backward, in one and in two q blocks."""
    monkeypatch.setattr(A, "_Q_BLOCK", q_block)
    q, k, v = _qkv(1, 256, 8, 2, 128)
    loss = lambda fn: lambda *a: (fn(*a) ** 2).sum()  # noqa: E731
    kernel = lambda q, k, v: A.fused_attention(  # noqa: E731
        q, k, v, causal=True, impl="pallas", name="test_attention")
    np.testing.assert_allclose(kernel(q, k, v), _oracle(q, k, v),
                               rtol=2e-5, atol=2e-5)
    got = jax.grad(loss(kernel), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(_oracle), argnums=(0, 1, 2))(q, k, v)
    assert got[1].shape == k.shape and got[2].shape == v.shape
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_the_xla_forms_take_grouped_heads_too():
    q, k, v = _qkv(2, 24, 4, 2, 8, seed=3)
    loss = lambda fn: lambda *a: (fn(*a) ** 2).sum()  # noqa: E731
    xla = lambda q, k, v: A.fused_attention(  # noqa: E731
        q, k, v, causal=True, impl="xla")
    np.testing.assert_allclose(xla(q, k, v), _oracle(q, k, v),
                               rtol=1e-6, atol=1e-6)
    # the hand-written composed backward (the fused path's fallback)
    pos = jnp.arange(24)
    g = jax.random.normal(jax.random.key(4), q.shape)
    got = A._xla_bwd(q, k, v, pos, pos, 8 ** -0.5, True, g)
    want = jax.vjp(_oracle, q, k, v)[1](g)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_head_counts_must_divide():
    q, k, v = _qkv(1, 8, 4, 3, 8)
    with pytest.raises(ValueError, match="multiple of one shared count"):
        A.fused_attention(q, k, v, causal=True)


def test_the_q_block_is_chosen_from_the_shape():
    bf16 = jnp.bfloat16
    # both benchmark shapes take the configured block: since the passes
    # walk K in tiles (PR 29) a score block is (key tile, q block), not
    # (q block, Tk), and head 128 at 2 048 no longer has to halve it
    assert A._q_block(1024, 1024, 64, bf16) == 512
    assert A._fits_vmem_bwd(2048, 2048, 128, bf16, 512)
    assert A._fits_vmem(2048, 128, bf16, 512)
    assert A._q_block(2048, 2048, 128, bf16) == 512
    # what no longer fits is a head's whole Q/G/dq and K/V/dk/dv, twice:
    # under its default 16 MiB the v5e compiler takes the fused backward
    # of (2, 4096, 16, 64) and refuses (2, 5120, 16, 64) at 17.50 MiB
    assert A._fits_vmem_bwd(4096, 4096, 64, bf16, 512)
    assert not A._fits_vmem_bwd(5120, 5120, 64, bf16, 128)
    assert not A._fits_vmem_bwd(3072, 3072, 128, bf16, 128)
    # a length the configured block does not divide takes a half
    assert A._q_block(768, 768, 64, bf16) == 256
    assert A._key_tile(768) == 256
    # shorter than a block: one block, as before
    assert A._q_block(20, 20, 16, jnp.float32) == 20
    assert A._key_tile(20) == 20
    # nothing divides: the configured block, and the caller sees a
    # ragged tail
    assert A._q_block(600, 600, 8, jnp.float32) == 512
    assert A._key_tile(600) == 600


def test_both_passes_take_the_kernel_at_the_zaya_shape(monkeypatch, caplog):
    """On a TPU (4, 2048, 8|2, 128) bf16 resolves to the kernel forward
    and backward, and the log names the plan."""
    A._log_choice.cache_clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jnp.zeros((4, 2048, 8, 128), jnp.bfloat16)
    k = jnp.zeros((4, 2048, 2, 128), jnp.bfloat16)
    lse = jnp.zeros((32, 1, 2048), jnp.float32)
    pos = jnp.arange(2048)
    plan = A.tile_plan(2048, 2048, 128, jnp.bfloat16, True)
    seen = {}
    monkeypatch.setattr(A, "_pallas_attention_bwd",
                        lambda *a, **kw: seen.setdefault("bwd", (q, k, k)))
    with caplog.at_level(logging.INFO, logger=A.__name__):
        assert A._resolve_impl(None, q, k, plan) == "pallas"
        A._fused_bwd(128 ** -0.5, True, False, "n", plan,
                     (q, k, k, pos, pos, None, q, lse), q)
    A._log_choice.cache_clear()
    assert "bwd" in seen
    said = [r.getMessage() for r in caplog.records]
    named = "q block 512, key tile 512, 10 of 16 tiles)"
    assert any("attention fwd" in m and "pallas" in m and named in m
               for m in said)
    assert any("attention bwd" in m and "pallas" in m and named in m
               for m in said)
    assert all(r.levelno == logging.INFO for r in caplog.records)


def test_the_kernels_carry_their_name():
    q, k, v = _qkv(1, 16, 2, 2, 8)
    text = str(jax.make_jaxpr(jax.grad(lambda q: A.fused_attention(
        q, k, v, causal=True, impl="pallas",
        name="zaya_cca_attention").sum()))(q))
    assert "zaya_cca_attention_fwd" in text
    assert "zaya_cca_attention_bwd" in text
