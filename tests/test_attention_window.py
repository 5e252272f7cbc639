"""ops/attention.py's streamed kernels: K/V fetched from HBM a key tile
at a time, a causal mask or a sliding window whose tiles outside it are
neither computed nor fetched, the backward as a dK/dV pass and a dQ
pass.  Interpret mode on the CPU against the composed XLA forms with
the same mask (the real shapes are compiled for a v5e in
tests/test_attention_tiles.py, beside the resident kernels')."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import theanompi_tpu.ops.attention as A


def _qkv(b, t, hq, hkv, d, seed=0):
    key = jax.random.key(seed)
    shapes = ((b, t, hq, d), (b, t, hkv, d), (b, t, hkv, d))
    return tuple(jax.random.normal(jax.random.fold_in(key, i), s)
                 for i, s in enumerate(shapes))


def _mask_visits(tq, tk, q_block, key_tile, window):
    """The visits a plan should make, read off the mask itself."""
    mask = np.asarray(A.causal_mask(jnp.arange(tq), jnp.arange(tk), window))
    visits = []
    for j in range(tq // q_block):
        for t in range(tk // key_tile):
            tile = mask[j * q_block:(j + 1) * q_block,
                        t * key_tile:(t + 1) * key_tile]
            if tile.any():
                visits.append((j, t, not tile.all()))
    return visits


#: name: (b, t, hq, hkv, d), q block (= _Q_BLOCK), key tile (None = the
#: rule's), window, (visited, total)
CASES = {
    "window_16_tiles_of_8": ((1, 64, 2, 2, 8), 8, None, 16, (21, 64)),
    "window_17_straddles_a_tile": ((1, 64, 2, 2, 8), 8, None, 17, (21, 64)),
    "window_of_the_whole_length": ((1, 64, 2, 2, 8), 8, None, 64, (36, 64)),
    "causal_without_a_window": ((2, 64, 2, 2, 8), 8, None, None, (36, 64)),
    "grouped_4_over_2_heads": ((1, 64, 4, 2, 8), 8, None, 16, (21, 64)),
    "q_block_over_key_tile": ((1, 64, 2, 1, 8), 16, 8, 17, (14, 32)),
    "q_block_under_key_tile": ((1, 64, 2, 2, 8), 8, 16, 17, (14, 32)),
    # heads of whole lanes, reached by index map in (B, T, H * D)
    "heads_of_128_by_index_map": ((1, 64, 4, 2, 128), 8, None, 16,
                                  (21, 64)),
}


@pytest.mark.parametrize("case", CASES)
def test_streamed_passes_match_the_xla_forms(monkeypatch, case):
    """Forward, lse and the gradients of q, k and v of the streamed
    kernels against the composed XLA forms with the same mask; the
    visits are the tiles the mask touches, masked where it cuts them."""
    shape, q_block, key_tile, window, tiles = CASES[case]
    monkeypatch.setattr(A, "_Q_BLOCK", q_block)
    if key_tile is not None:
        monkeypatch.setattr(A, "_key_tile", lambda tk: key_tile)
    q, k, v = _qkv(*shape)
    t, d = q.shape[1], q.shape[-1]
    plan = A.tile_plan(t, t, d, q.dtype, True, window=window)
    if window is None:       # a shape that fits streams only when forced
        assert not plan.stream
        plan = A._stream_plan(t, t, True, False, None)
    assert plan.stream and plan.window == window
    assert (plan.visited, plan.total) == tiles
    assert A._stream_visits(plan, t, t) == _mask_visits(
        t, t, plan.q_block, plan.key_tile, window)
    scale = d ** -0.5
    pos = jnp.arange(t)

    out, lse = A._stream_attention(q, k, v, scale=scale, interpret=True,
                                   plan=plan)
    np.testing.assert_allclose(
        out, A._xla_attention(q, k, v, pos, pos, scale, True, window),
        rtol=2e-5, atol=2e-5)
    ks, _ = A._repeat_kv(q, k, v)
    s = jnp.where(A.causal_mask(pos, pos, window)[None, None],
                  A.block_scores(q, ks, scale), A._MASK_NEG)
    np.testing.assert_allclose(
        lse, jax.nn.logsumexp(s, axis=-1).reshape(-1, 1, t),
        rtol=1e-5, atol=1e-5)
    g = jax.random.normal(jax.random.key(7), q.shape)
    got = A._stream_attention_bwd(q, k, v, out, lse, g, scale=scale,
                                  interpret=True, plan=plan)
    want = A._xla_bwd(q, k, v, pos, pos, scale, True, g, window)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("window", [16, 17, 64])
def test_fused_attention_with_a_window_differentiates_through_the_kernels(
        monkeypatch, window):
    """The public entry: ``window=`` takes the streamed pair forward and
    backward, and agrees with the XLA form; rotated q and k too."""
    monkeypatch.setattr(A, "_Q_BLOCK", 8)
    q, k, v = _qkv(1, 64, 4, 2, 128)
    table = A.rotary_table(jnp.arange(64), 128, 1.5e6)
    ran = []
    real = A._stream_attention_bwd
    monkeypatch.setattr(A, "_stream_attention_bwd", lambda *a, **kw: (
        ran.append(1), real(*a, **kw))[1])

    def run(impl):
        return jax.vjp(lambda q, k, v: A.fused_attention(
            q, k, v, causal=True, impl=impl, window=window, rotary=table,
            name="windowed"), q, k, v)

    out, vjp = run("pallas")
    g = jax.random.normal(jax.random.key(3), q.shape)
    got = vjp(g)
    assert ran == [1]
    want_out, want_vjp = run("xla")
    np.testing.assert_allclose(out, want_out, rtol=2e-5, atol=2e-5)
    for a, b in zip(got, want_vjp(g)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
    text = str(jax.make_jaxpr(jax.grad(lambda q: A.fused_attention(
        q, k, v, causal=True, impl="pallas", window=window,
        name="windowed").sum()))(q))
    for kernel in ("windowed_fwd", "windowed_bwd_kv", "windowed_bwd_q"):
        assert kernel in text


def test_keys_no_query_sees_get_zero_gradients(monkeypatch):
    """A dK/dV tile no query reaches (keys past the last query) is given
    one empty visit that writes its zeros."""
    monkeypatch.setattr(A, "_Q_BLOCK", 8)
    key = jax.random.key(0)
    q = jax.random.normal(key, (1, 16, 2, 8))
    k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, 32, 2, 8))
            for i in (1, 2))
    plan = A._stream_plan(16, 32, True, False, None)
    assert (plan.visited, plan.total) == (3, 8)
    flags = A._stream_tables(A._stream_visits(plan, 16, 32), 4)[2]
    assert int((np.asarray(flags) & A._EMPTY != 0).sum()) == 2
    pos_q, pos_k = jnp.arange(16), jnp.arange(32)
    out, lse = A._stream_attention(q, k, v, scale=0.5, interpret=True,
                                   plan=plan)
    np.testing.assert_allclose(
        out, A._xla_attention(q, k, v, pos_q, pos_k, 0.5, True),
        rtol=2e-5, atol=2e-5)
    g = jax.random.normal(jax.random.key(5), q.shape)
    got = A._stream_attention_bwd(q, k, v, out, lse, g, scale=0.5,
                                  interpret=True, plan=plan)
    want = A._xla_bwd(q, k, v, pos_q, pos_k, 0.5, True, g)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
    assert float(jnp.abs(got[1][:, 16:]).max()) == 0.0


def test_without_a_mask_every_tile_is_visited_unmasked(monkeypatch):
    """``causal=False`` over K/V the resident kernels cannot hold (what
    parallel/sequence.py's all-gather strategy hands a chip): every tile,
    none masked, the XLA form's numbers."""
    monkeypatch.setattr(A, "_Q_BLOCK", 8)
    q, k, v = _qkv(1, 32, 4, 2, 8)
    plan = A._stream_plan(32, 32, False, False, None)
    assert (plan.visited, plan.total, plan.skip) == (16, 16, False)
    assert all(not masked for _, _, masked in A._stream_visits(plan, 32, 32))
    pos = jnp.arange(32)
    out, lse = A._stream_attention(q, k, v, scale=0.3, interpret=True,
                                   plan=plan)
    np.testing.assert_allclose(
        out, A._xla_attention(q, k, v, pos, pos, 0.3, False),
        rtol=2e-5, atol=2e-5)
    g = jax.random.normal(jax.random.key(4), q.shape)
    got = A._stream_attention_bwd(q, k, v, out, lse, g, scale=0.3,
                                  interpret=True, plan=plan)
    for a, b in zip(got, A._xla_bwd(q, k, v, pos, pos, 0.3, False, g)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_a_backward_over_the_budget_streams_where_it_can(monkeypatch):
    """A shape whose forward the resident kernel holds but whose fused
    backward does not fit, over the default positions: both passes
    stream, with the XLA form's numbers; over explicit positions the
    resident forward stays and the backward takes the XLA form."""
    monkeypatch.setattr(A, "_Q_BLOCK", 8)
    monkeypatch.setattr(A, "_fits_vmem_bwd", lambda *a, **kw: False)
    q, k, v = _qkv(1, 32, 2, 2, 8)
    assert A.tile_plan(32, 32, 8, q.dtype, True).stream
    assert not A.tile_plan(32, 32, 8, q.dtype, True,
                           default_positions=False).stream
    ran = []
    for name in ("_stream_attention_bwd", "_xla_bwd"):
        real = getattr(A, name)
        monkeypatch.setattr(A, name, lambda *a, _real=real, _name=name,
                            **kw: (ran.append(_name), _real(*a, **kw))[1])
    out, vjp = jax.vjp(lambda q, k, v: A.fused_attention(
        q, k, v, causal=True, impl="pallas"), q, k, v)
    g = jax.random.normal(jax.random.key(7), q.shape)
    got = vjp(g)
    assert ran == ["_stream_attention_bwd"]
    pos = jnp.arange(32)
    for a, b in zip(got, A._xla_bwd(q, k, v, pos, pos, 8 ** -0.5, True, g)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_the_cells_plans_at_16384_tokens():
    """SmallThinker's two attention calls at (1, 16384, 28 over 4, 128)
    bf16: the window layer visits 252 of 1 024 tiles of 512 x 512, the
    global layer 528; both stream, the rotation in XLA; and a shape the
    resident kernels hold keeps its plan."""
    window = A.tile_plan(16384, 16384, 128, jnp.bfloat16, True,
                         rotary=True, window=4096)
    assert str(window) == ("q block 512, key tile 512, 252 of 1024 tiles, "
                           "window 4096, K/V streamed, rotary in XLA")
    whole = A.tile_plan(16384, 16384, 128, jnp.bfloat16, True)
    assert str(whole) == ("q block 512, key tile 512, 528 of 1024 tiles, "
                          "K/V streamed")
    assert not A._fits_vmem(16384, 128, jnp.bfloat16, 512)
    assert A.tile_plan(2048, 2048, 128, jnp.bfloat16, True) == A.TilePlan(
        512, 512, True, 10, 16, True, None)


def test_the_window_is_refused_where_it_means_nothing():
    q, k, v = _qkv(1, 16, 2, 2, 8)
    with pytest.raises(ValueError, match="causal mask"):
        A.fused_attention(q, k, v, causal=False, window=4)
    with pytest.raises(ValueError, match="one length"):
        A.fused_attention(q, k[:, :8], v[:, :8], causal=True, window=4)


def test_a_window_over_explicit_positions_takes_the_xla_form(monkeypatch,
                                                             caplog):
    """Only the streamed kernels mask a window, and they read the
    default positions: explicit ones go to XLA, with the positions'
    mask, even where the kernel is asked for."""
    A._log_choice.cache_clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jnp.zeros((1, 64, 2, 128), jnp.bfloat16)
    plan = A.tile_plan(64, 64, 128, q.dtype, True, default_positions=False,
                       window=16)
    assert plan.window == 16 and not plan.stream
    with caplog.at_level(logging.INFO, logger=A.__name__):
        assert A._resolve_impl("pallas", q, q, plan) == "xla"
    A._log_choice.cache_clear()
    assert any("a window over explicit positions" in r.getMessage()
               for r in caplog.records)


def test_the_streamed_plan_is_logged_once_a_shape(monkeypatch, caplog):
    A._log_choice.cache_clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jnp.zeros((1, 16384, 28, 128), jnp.bfloat16)
    k = jnp.zeros((1, 16384, 4, 128), jnp.bfloat16)
    plan = A.tile_plan(16384, 16384, 128, q.dtype, True, window=4096)
    with caplog.at_level(logging.INFO, logger=A.__name__):
        for _ in range(3):
            assert A._resolve_impl(None, q, k, plan) == "pallas"
    A._log_choice.cache_clear()
    said = [r.getMessage() for r in caplog.records]
    assert said == [
        "attention fwd q=(1, 16384, 28, 128, 16384, 4) bfloat16 -> pallas "
        "(streams, q block 512, key tile 512, 252 of 1024 tiles, "
        "window 4096, K/V streamed)"]
