import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.ops import lrn


def _lrn_ref(x, n, k, alpha, beta, scaled=True):
    """Straightforward numpy LRN for cross-checking."""
    N, H, W, C = x.shape
    out = np.zeros_like(x)
    a = alpha / n if scaled else alpha
    for c in range(C):
        lo = max(0, c - (n - 1) // 2)
        hi = min(C, c + (n - 1 - (n - 1) // 2) + 1)
        s = (x[..., lo:hi] ** 2).sum(axis=-1)
        out[..., c] = x[..., c] / (k + a * s) ** beta
    return out


def test_lrn_matches_reference_formula():
    rng = np.random.RandomState(0)
    x = rng.randn(2, 4, 4, 8).astype(np.float32)
    got = np.asarray(lrn(jnp.asarray(x), n=5, k=2.0, alpha=1e-4, beta=0.75))
    want = _lrn_ref(x, 5, 2.0, 1e-4, 0.75, scaled=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_lrn_unscaled_variant():
    rng = np.random.RandomState(1)
    x = rng.randn(1, 2, 2, 6).astype(np.float32)
    got = np.asarray(lrn(jnp.asarray(x), n=3, k=1.0, alpha=1e-3, beta=0.5,
                         alpha_scaled_by_n=False))
    want = _lrn_ref(x, 3, 1.0, 1e-3, 0.5, scaled=False)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_lrn_differentiable():
    x = jnp.ones((1, 2, 2, 4))
    g = jax.grad(lambda y: lrn(y).sum())(x)
    assert np.isfinite(np.asarray(g)).all()


class TestLRNPallas:
    """lrn_pallas runs in interpret mode off-TPU (conftest pins cpu),
    so numerics and the analytic VJP are testable on the CPU mesh."""

    def test_matches_xla_impl(self):
        rng = np.random.RandomState(2)
        x = rng.randn(2, 3, 5, 96).astype(np.float32)
        got = np.asarray(lrn(jnp.asarray(x), impl="pallas"))
        want = np.asarray(lrn(jnp.asarray(x), impl="xla"))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_analytic_vjp_matches_autodiff(self):
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(2, 2, 3, 16).astype(np.float32))
        ct = jnp.asarray(rng.randn(2, 2, 3, 16).astype(np.float32))
        g_pallas = jax.grad(lambda v: (lrn(v, impl="pallas") * ct).sum())(x)
        g_xla = jax.grad(lambda v: (lrn(v, impl="xla") * ct).sum())(x)
        np.testing.assert_allclose(np.asarray(g_pallas), np.asarray(g_xla),
                                   rtol=1e-4, atol=1e-5)

    def test_non_tile_aligned_rows(self, monkeypatch):
        # force a genuinely ragged grid: TILE_M=8 with m=N*H*W=10 →
        # 2 blocks, last one masked; results must still be exact
        from theanompi_tpu.ops import lrn_pallas as lp
        monkeypatch.setattr(lp, "TILE_M", 8)
        rng = np.random.RandomState(4)
        x = rng.randn(1, 2, 5, 8).astype(np.float32)
        got = np.asarray(lrn(jnp.asarray(x), impl="pallas"))
        want = np.asarray(lrn(jnp.asarray(x), impl="xla"))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_even_window_gradient(self):
        # even n: the window is asymmetric, so the VJP must use the
        # adjoint window — compare against autodiff of the XLA form
        rng = np.random.RandomState(5)
        x = jnp.asarray(rng.randn(1, 2, 3, 12).astype(np.float32))
        ct = jnp.asarray(rng.randn(1, 2, 3, 12).astype(np.float32))
        g_pallas = jax.grad(
            lambda v: (lrn(v, n=4, impl="pallas") * ct).sum())(x)
        g_xla = jax.grad(
            lambda v: (lrn(v, n=4, impl="xla") * ct).sum())(x)
        np.testing.assert_allclose(np.asarray(g_pallas), np.asarray(g_xla),
                                   rtol=1e-4, atol=1e-5)

    def test_bad_impl_rejected(self):
        import pytest
        with pytest.raises(ValueError):
            lrn(jnp.ones((1, 1, 1, 4)), impl="cuda")


class TestFusedAttention:
    """ops/attention.py Pallas kernel (interpret mode on CPU) vs the
    parallel/sequence.py oracle."""

    def _rand(self, b=2, tq=16, tk=16, h=2, d=8, seed=0):
        import jax

        ks = jax.random.split(jax.random.key(seed), 3)
        q = jax.random.normal(ks[0], (b, tq, h, d))
        k = jax.random.normal(ks[1], (b, tk, h, d))
        v = jax.random.normal(ks[2], (b, tk, h, d))
        return q, k, v

    @staticmethod
    def _fused_and_xla_bwd(A, q, k, v, q_pos, k_pos, g):
        """Both passes of the kernel under explicit positions (every
        tile visited and masked) beside the composed-XLA VJP."""
        tq, tk, d = q.shape[1], k.shape[1], q.shape[-1]
        kw = dict(scale=d ** -0.5, causal=True, interpret=True,
                  plan=A.tile_plan(tq, tk, d, q.dtype, True,
                                   default_positions=False))
        out, lse = A._pallas_attention(q, k, v, q_pos, k_pos, **kw)
        got = A._pallas_attention_bwd(q, k, v, q_pos, k_pos, out, lse, g,
                                      **kw)
        return got, A._xla_bwd(q, k, v, q_pos, k_pos, kw["scale"], True, g)

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_oracle(self, causal):
        from theanompi_tpu.ops.attention import fused_attention
        from theanompi_tpu.parallel.sequence import attention_reference

        q, k, v = self._rand()
        got = fused_attention(q, k, v, causal=causal, impl="pallas")
        want = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_global_positions_match_oracle(self):
        import jax.numpy as jnp

        from theanompi_tpu.ops.attention import fused_attention
        from theanompi_tpu.parallel.sequence import _attention_positions

        q, k, v = self._rand(tq=8, tk=24)
        q_pos = 16 + jnp.arange(8)       # a later shard attends back
        k_pos = jnp.arange(24)
        got = fused_attention(q, k, v, q_pos=q_pos, k_pos=k_pos,
                              causal=True, impl="pallas")
        want = _attention_positions(q, k, v, q_pos, k_pos,
                                    q.shape[-1] ** -0.5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_gradients_match_oracle(self):
        import jax

        from theanompi_tpu.ops.attention import fused_attention
        from theanompi_tpu.parallel.sequence import attention_reference

        q, k, v = self._rand(tq=12, tk=12)

        def loss(fn, q, k, v):
            return (fn(q, k, v) ** 2).sum()

        g_got = jax.grad(lambda *a: loss(
            lambda q, k, v: fused_attention(q, k, v, causal=True,
                                            impl="pallas"), *a),
            argnums=(0, 1, 2))(q, k, v)
        g_want = jax.grad(lambda *a: loss(
            lambda q, k, v: attention_reference(q, k, v, causal=True),
            *a), argnums=(0, 1, 2))(q, k, v)
        for got, want in zip(g_got, g_want):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=5e-5, atol=5e-5)

    def test_q_blocking_non_divisible(self, monkeypatch):
        import theanompi_tpu.ops.attention as A

        monkeypatch.setattr(A, "_Q_BLOCK", 8)
        q, k, v = self._rand(tq=20, tk=20)   # 20 = 2 full blocks + 4
        got = A.fused_attention(q, k, v, causal=True, impl="pallas")
        from theanompi_tpu.parallel.sequence import attention_reference

        want = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_auto_falls_back_off_tpu_and_on_oversize(self):
        import jax.numpy as jnp

        import theanompi_tpu.ops.attention as A

        q, k, v = self._rand(tq=4, tk=4)
        assert A._resolve_impl("auto", q, k) == "xla"  # cpu backend
        # oversize K/V: auto must refuse pallas even on TPU
        big = jnp.zeros((1, 200_000, 1, 64))
        assert A._resolve_impl("auto", big, big) == "xla"
        with pytest.raises(ValueError, match="unknown attention impl"):
            A._resolve_impl("flash", q, k)

    def test_auto_routes_ragged_tq_to_xla_on_tpu(self, monkeypatch):
        """Ragged q-tails in the Pallas FORWARD rely on out-of-range
        block padding only ever exercised in interpret mode (ADVICE
        r2) — on real silicon 'auto' must route them to XLA exactly
        like the backward already does; impl='pallas' still forces
        the kernel so interpret-mode tests keep their coverage."""
        import jax.numpy as jnp

        import theanompi_tpu.ops.attention as A

        monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
        ragged = jnp.zeros((1, A._Q_BLOCK + 4, 2, 16))
        assert A._resolve_impl("auto", ragged, ragged) == "xla"
        exact = jnp.zeros((1, 2 * A._Q_BLOCK, 2, 16))
        assert A._resolve_impl("auto", exact, exact) == "pallas"
        small = jnp.zeros((1, 20, 2, 16))  # tq < _Q_BLOCK: one block
        assert A._resolve_impl("auto", small, small) == "pallas"

    def test_bf16_inputs(self):
        import jax.numpy as jnp

        from theanompi_tpu.ops.attention import fused_attention
        from theanompi_tpu.parallel.sequence import attention_reference

        q, k, v = self._rand()
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
        got = fused_attention(qb, kb, vb, causal=True, impl="pallas")
        assert got.dtype == jnp.bfloat16
        want = attention_reference(qb, kb, vb, causal=True)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=2e-2, atol=2e-2)

    def test_fused_bwd_kernel_matches_xla_bwd(self):
        """The flash-style Pallas bwd (recompute-from-lse, fp32
        accumulation) == the composed-XLA VJP, incl. global positions."""
        import jax.numpy as jnp

        import theanompi_tpu.ops.attention as A

        q, k, v = self._rand(tq=16, tk=48)
        q_pos = 32 + jnp.arange(16)
        k_pos = jnp.arange(48)
        g = jax.random.normal(jax.random.key(9), q.shape)
        got, want = self._fused_and_xla_bwd(A, q, k, v, q_pos, k_pos, g)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-5, atol=5e-5)

    def test_fused_bwd_ragged_falls_back(self, monkeypatch):
        """tq not divisible by the q-block -> the VJP routes to the
        XLA bwd and grads still match the oracle."""
        import theanompi_tpu.ops.attention as A
        from theanompi_tpu.parallel.sequence import attention_reference

        monkeypatch.setattr(A, "_Q_BLOCK", 8)
        q, k, v = self._rand(tq=20, tk=20)  # 20 % 8 != 0

        g_got = jax.grad(lambda q: (A.fused_attention(
            q, k, v, causal=True, impl="pallas") ** 2).sum())(q)
        g_want = jax.grad(lambda q: (attention_reference(
            q, k, v, causal=True) ** 2).sum())(q)
        np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want),
                                   rtol=5e-5, atol=5e-5)

    def test_fused_bwd_multiblock_accumulation(self, monkeypatch):
        """Several q-blocks per (b*h): dk/dv accumulate across the
        fori_loop correctly."""
        import theanompi_tpu.ops.attention as A
        from theanompi_tpu.parallel.sequence import attention_reference

        monkeypatch.setattr(A, "_Q_BLOCK", 8)
        q, k, v = self._rand(tq=24, tk=24)  # 3 blocks of 8

        def loss(fn, *a):
            return (fn(*a) ** 2).sum()

        g_got = jax.grad(lambda q, k, v: loss(
            lambda q, k, v: A.fused_attention(q, k, v, causal=True,
                                              impl="pallas"), q, k, v),
            argnums=(0, 1, 2))(q, k, v)
        g_want = jax.grad(lambda q, k, v: loss(
            lambda q, k, v: attention_reference(q, k, v, causal=True),
            q, k, v), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_got, g_want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-5, atol=5e-5)

    def test_fused_bwd_fully_masked_rows(self):
        """A q row preceding every k position (fully masked): lse
        saturates in fp32, and the bwd's re-normalization must still
        reproduce the XLA VJP's uniform-row gradients."""
        import jax.numpy as jnp

        import theanompi_tpu.ops.attention as A

        q, k, v = self._rand(tq=8, tk=16)
        q_pos = jnp.arange(8)          # rows 0.. precede k_pos 8..
        k_pos = 8 + jnp.arange(16)     # -> ALL rows fully masked
        g = jax.random.normal(jax.random.key(3), q.shape)
        got, want = self._fused_and_xla_bwd(A, q, k, v, q_pos, k_pos, g)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-5, atol=5e-5)
