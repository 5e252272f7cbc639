"""ops/attention.py since PR 29: both passes walk K/V in tiles and a
causal mask over the default positions leaves out the tiles above the
diagonal; each pass is traced and lowered once a shape.  Interpret mode
on the CPU against the composed XLA forms."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import theanompi_tpu.ops.attention as A


def _qkv(b, tq, tk, hq, hkv, d, seed=0):
    key = jax.random.key(seed)
    shapes = ((b, tq, hq, d), (b, tk, hkv, d), (b, tk, hkv, d))
    return tuple(jax.random.normal(jax.random.fold_in(key, i), s)
                 for i, s in enumerate(shapes))


def _reference_lse(q, k, v, q_pos, k_pos, scale, causal):
    k, _ = A._repeat_kv(q, k, v)
    s = A.block_scores(q, k, scale)
    if causal:
        s = jnp.where(A.causal_mask(q_pos, k_pos)[None, None], s,
                      A._MASK_NEG)
    return jax.nn.logsumexp(s, axis=-1).reshape(-1, 1, q.shape[1])


def _allgather_positions(shard, t_local, n):
    """What parallel/sequence.py's all-gather strategy passes."""
    return shard * t_local + jnp.arange(t_local), jnp.arange(n * t_local)


#: name: (b, tq, tk, hq, hkv, d), _Q_BLOCK, key tile (None = the rule's),
#: causal, explicit (q_pos, k_pos) or None, (visited, total), the
#: backward that runs
CASES = {
    "one_tile_s128": ((2, 128, 128, 2, 2, 16), 512, None, True, None,
                      (1, 1), "pallas"),
    "q_block_equals_key_tile": ((2, 32, 32, 2, 2, 8), 8, None, True, None,
                                (10, 16), "pallas"),
    "q_block_under_key_tile": ((1, 32, 32, 2, 2, 8), 8, 16, True, None,
                               (6, 8), "pallas"),
    "q_block_over_key_tile": ((1, 32, 32, 2, 2, 8), 16, 8, True, None,
                              (6, 8), "pallas"),
    "keys_longer_than_queries": ((1, 16, 48, 2, 2, 8), 8, None, True, None,
                                 (3, 12), "pallas"),
    "grouped_8_over_2_heads_of_128": ((1, 256, 256, 8, 2, 128), 64, None,
                                      True, None, (10, 16), "pallas"),
    # Qwen3-Next's head of 256 (two lane tiles), 8 query heads a
    # key/value head as its 16 over 2
    "grouped_8_over_1_heads_of_256": ((1, 256, 256, 8, 1, 256), 64, None,
                                      True, None, (10, 16), "pallas"),
    "not_causal_visits_every_tile": ((2, 24, 24, 2, 2, 8), 8, None, False,
                                     None, (9, 9), "pallas"),
    "allgather_positions_last_shard": (
        (1, 16, 48, 2, 2, 8), 8, None, True, _allgather_positions(2, 16, 3),
        (12, 12), "pallas"),
    "allgather_positions_first_shard": (
        (1, 16, 48, 4, 2, 8), 8, 16, True, _allgather_positions(0, 16, 3),
        (6, 6), "pallas"),
    # rows 0..7, the first q block, precede every key: fully masked
    "a_q_block_that_sees_no_key": (
        (1, 16, 16, 2, 2, 8), 8, None, True,
        (jnp.arange(16), 8 + jnp.arange(16)), (4, 4), "pallas"),
    "ragged_q_tail_takes_the_xla_bwd": ((1, 20, 24, 2, 2, 8), 8, None, True,
                                        None, (6, 9), "xla"),
}


@pytest.mark.parametrize("case", CASES)
def test_tiled_passes_match_the_xla_forms(monkeypatch, case):
    """Forward, lse and the gradients of q, k and v, for each way the
    walk can go: one tile, square and oblong tiles, grouped heads, no
    mask, explicit positions (every tile visited and masked, a fully
    masked q block's uniform rows among them), and a shape whose
    backward is the composed-XLA one."""
    shape, q_block, key_tile, causal, positions, tiles, bwd = CASES[case]
    monkeypatch.setattr(A, "_Q_BLOCK", q_block)
    if key_tile is not None:
        monkeypatch.setattr(A, "_key_tile", lambda tk: key_tile)
    q, k, v = _qkv(*shape)
    tq, tk, d = q.shape[1], k.shape[1], q.shape[-1]
    scale = d ** -0.5
    plan = A.tile_plan(tq, tk, d, q.dtype, causal,
                       default_positions=positions is None)
    assert (plan.visited, plan.total) == tiles
    q_pos, k_pos = positions or (jnp.arange(tq), jnp.arange(tk))

    ran = []
    for name in ("_pallas_attention_bwd", "_xla_bwd"):
        real = getattr(A, name)
        monkeypatch.setattr(A, name, lambda *a, _real=real, _name=name, **kw:
                            (ran.append(_name), _real(*a, **kw))[1])
    kernel = lambda q, k, v: A.fused_attention(  # noqa: E731
        q, k, v, *(positions or ()), causal=causal, impl="pallas")
    g = jax.random.normal(jax.random.key(7), q.shape)
    out, vjp = jax.vjp(kernel, q, k, v)
    got = vjp(g)
    assert ran == ["_pallas_attention_bwd" if bwd == "pallas" else "_xla_bwd"]

    np.testing.assert_allclose(
        out, A._xla_attention(q, k, v, q_pos, k_pos, scale, causal),
        rtol=2e-5, atol=2e-5)
    _, lse = A._pallas_attention(q, k, v, q_pos, k_pos, scale=scale,
                                 causal=causal, interpret=True, plan=plan)
    np.testing.assert_allclose(
        lse, _reference_lse(q, k, v, q_pos, k_pos, scale, causal),
        rtol=1e-5, atol=1e-5)
    want = A._xla_bwd(q, k, v, q_pos, k_pos, scale, causal, g)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_a_fully_masked_q_block_comes_out_uniform():
    """The rows that see no key average V, as the one-pass softmax gave
    them, and their lse saturates to the mask value."""
    q, k, v = _qkv(1, 16, 16, 2, 2, 8)
    q_pos, k_pos = jnp.arange(16), 8 + jnp.arange(16)
    plan = A.tile_plan(16, 16, 8, q.dtype, True, default_positions=False)
    out, lse = A._pallas_attention(q, k, v, q_pos, k_pos, scale=8 ** -0.5,
                                   causal=True, interpret=True, plan=plan)
    np.testing.assert_allclose(
        out[:, :8], jnp.broadcast_to(v.mean(1, keepdims=True),
                                     (1, 8, 2, 8)), rtol=1e-5, atol=1e-6)
    assert (lse[:, 0, :8] == jnp.float32(A._MASK_NEG)).all()


@pytest.mark.parametrize("tq,tk,q_block,causal,default,want", [
    (1024, 1024, 256, True, True, (256, 256, 10, 16)),
    (2048, 2048, 128, True, True, (128, 128, 136, 256)),
    (2048, 2048, 256, True, True, (256, 256, 36, 64)),
    (128, 128, 512, True, True, (128, 128, 1, 1)),
    (1024, 1024, 512, True, True, (512, 512, 3, 4)),
    (2048, 2048, 512, True, True, (512, 512, 10, 16)),
    (1024, 1024, 256, False, True, (256, 256, 16, 16)),
    (1024, 1024, 256, True, False, (256, 256, 16, 16)),
    (256, 768, 256, True, True, (256, 256, 1, 3)),
    (384, 384, 256, True, True, (128, 128, 6, 9)),
])
def test_the_counter_is_a_function_of_the_shape(monkeypatch, tq, tk, q_block,
                                                causal, default, want):
    """``tile_plan``: n of m tiles, the number PERF.md and the log line
    quote."""
    monkeypatch.setattr(A, "_Q_BLOCK", q_block)
    plan = A.tile_plan(tq, tk, 64, jnp.bfloat16, causal,
                       default_positions=default)
    assert (plan.q_block, plan.key_tile, plan.visited, plan.total) == want
    assert plan.skip == (causal and default)
    assert str(plan) == (f"q block {want[0]}, key tile {want[1]}, "
                         f"{want[2]} of {want[3]} tiles")


def test_walk_bounds_agree_with_the_mask():
    """A tile is left out only where the mask removes its every score,
    and is left unmasked only where the mask removes none."""
    for q_block, key_tile, tq, tk in ((8, 8, 32, 32), (8, 16, 32, 32),
                                      (16, 8, 32, 48), (8, 8, 24, 16)):
        mask = np.asarray(A.causal_mask(jnp.arange(tq), jnp.arange(tk)))
        n_tiles = tk // key_tile
        for j in range(tq // q_block):
            first, end = A._walk_bounds(j, q_block, key_tile, n_tiles,
                                        True, True)
            rows = mask[j * q_block:(j + 1) * q_block]
            for t in range(n_tiles):
                tile = rows[:, t * key_tile:(t + 1) * key_tile]
                assert (t < first) == tile.all()
                assert (t >= end) == (not tile.any())


def test_the_plan_is_logged_once_a_shape(monkeypatch, caplog):
    A._log_choice.cache_clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(A, "_Q_BLOCK", 256)
    q = jnp.zeros((8, 1024, 16, 64), jnp.bfloat16)
    plans = [A.tile_plan(1024, 1024, 64, q.dtype, True, default_positions=d)
             for d in (True, True, False)]
    with caplog.at_level(logging.INFO, logger=A.__name__):
        for plan in plans:
            assert A._resolve_impl(None, q, q, plan) == "pallas"
    A._log_choice.cache_clear()
    said = [r.getMessage() for r in caplog.records]
    assert len(said) == 2     # one a (shape, plan), not one a call
    assert "pallas (fits, q block 256, key tile 256, 10 of 16 tiles)" \
        in said[0]
    assert "16 of 16 tiles" in said[1]


def _net(n_layers):
    from theanompi_tpu.models.transformer import TransformerLMNet

    return TransformerLMNet(vocab=32, n_layers=n_layers, d_model=24,
                            n_heads=2, d_ff=48, max_len=16,
                            attn_impl="pallas")


@pytest.fixture
def kernel_traces(monkeypatch):
    """Counts of kernel-body traces, with both passes' jit caches (and
    so their traced jaxprs) emptied first."""
    A._pallas_attention.clear_cache()
    A._pallas_attention_bwd.clear_cache()
    monkeypatch.setattr(A, "_Q_BLOCK", 8)
    counts = {"_kernel": 0, "_bwd_kernel": 0}
    for name in counts:
        real = getattr(A, name)

        def counted(*a, _real=real, _name=name, **kw):
            counts[_name] += 1
            return _real(*a, **kw)
        monkeypatch.setattr(A, name, counted)
    return counts


def test_each_pass_is_traced_and_lowered_once_a_shape(kernel_traces):
    """24 layers or 2: one kernel trace and one lowered function a pass.
    (A bare ``pallas_call`` under the ``custom_vjp`` was traced and
    lowered again at every call site, on every start.)"""
    tokens = jnp.zeros((2, 16), jnp.int32)
    seen = {}
    for n_layers in (2, 6):
        net = _net(n_layers)
        before = dict(kernel_traces)
        params = jax.eval_shape(net.init, jax.random.key(0), tokens)
        text = jax.jit(jax.grad(
            lambda p: net.apply(p, tokens).sum())).lower(params).as_text()
        seen[n_layers] = (
            {k: kernel_traces[k] - before[k] for k in before},
            text.count("func.func private @_pallas_attention("),
            text.count("func.func private @_pallas_attention_bwd("),
            text.count("call @_pallas_attention("),
            text.count("call @_pallas_attention_bwd("))
    # the first depth traces each kernel body once (the jaxpr of the
    # jitted pass is kept), the second not at all; each program holds
    # one function a pass, called once a layer
    assert seen[2] == ({"_kernel": 1, "_bwd_kernel": 1}, 1, 1, 2, 2)
    assert seen[6] == ({"_kernel": 0, "_bwd_kernel": 0}, 1, 1, 6, 6)


def test_the_eager_constructor_compiles_the_forward_once(kernel_traces):
    """``module.init`` run primitive by primitive, as models/base.py
    runs it: six layers, one compiled forward."""
    tokens = jnp.zeros((2, 16), jnp.int32)
    _net(6).init(jax.random.key(0), tokens)
    assert kernel_traces["_kernel"] == 1
    assert A._pallas_attention._cache_size() == 1


# ---- the real shapes, compiled for the chip that is described here and
# not attached (no chip time; what interpret mode cannot refuse) ----

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("q_shape,kv_heads,rotary,plan", [
    # the four LM cells' shapes (the last is the looped model's: twice
    # ZAYA1's batch x heads at head 128, equal head counts, and the
    # kernels rotate q and k)
    ((8, 1024, 16, 64), 16, False,
     "q block 512, key tile 512, 3 of 4 tiles"),
    ((4, 2048, 8, 128), 2, False,
     "q block 512, key tile 512, 10 of 16 tiles"),
    ((64, 128, 16, 64), 16, False,
     "q block 128, key tile 128, 1 of 1 tiles"),
    ((4, 2048, 16, 128), 16, True,
     "q block 512, key tile 512, 10 of 16 tiles, heads by index map, "
     "rotary in kernel"),
    # long lengths the budget admits, at a batch x heads where the
    # compiler asks more than the estimate (18.0 MiB at the first), and
    # one that takes a smaller q block than key tile
    ((8, 4096, 16, 64), 16, False,
     "q block 512, key tile 512, 36 of 64 tiles"),
    ((16, 2560, 8, 128), 2, False,
     "q block 256, key tile 512, 30 of 50 tiles"),
    # the rotation with grouped heads (K rotated once a group), and
    # with a head of two lane tiles
    ((4, 2048, 8, 128), 2, True,
     "q block 512, key tile 512, 10 of 16 tiles, heads by index map, "
     "rotary in kernel"),
    ((2, 1024, 4, 256), 4, True,
     "q block 512, key tile 512, 3 of 4 tiles, heads by index map, "
     "rotary in kernel"),
    # the Qwen3-Next cell's: a head of 256, 16 query over 2 key/value
    # heads, under the wide-head budget (its fused backward holds 24.5 MiB)
    ((4, 2048, 16, 256), 2, False,
     "q block 512, key tile 512, 10 of 16 tiles"),
])
def test_both_passes_compile_for_a_v5e(monkeypatch, one_chip, q_shape,
                                       kv_heads, rotary, plan):
    """Mosaic takes both kernels at the plans the budget functions
    admit, bf16, causal."""
    from jax.experimental.compilation_cache import compilation_cache

    from theanompi_tpu.ops import pallas_mode

    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)
    b, t, h, d = q_shape
    chosen = A.tile_plan(t, t, d, jnp.bfloat16, True, rotary=rotary)
    assert str(chosen) == plan
    assert A._fits_vmem_bwd(t, t, d, jnp.bfloat16, chosen.q_block,
                            chosen.positions, chosen.rotates)
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, t, kv_heads, d), jnp.bfloat16,
                             sharding=one_chip)
    table = (jax.ShapeDtypeStruct((t, d), jnp.float32, sharding=one_chip)
             if rotary else None)
    loss = lambda q, k, v, table: A.fused_attention(  # noqa: E731
        q, k, v, causal=True, impl="pallas", name="test_attention",
        rotary=table).astype(jnp.float32).sum()
    # a program compiled for a described chip cannot be read back from
    # the persistent cache without one: keep it out
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
            q, k, k, table).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    assert "test_attention_fwd" in text and "test_attention_bwd" in text


@pytest.mark.parametrize("window", [4096, None])
def test_the_streamed_pair_compiles_for_a_v5e(monkeypatch, one_chip, window):
    """Mosaic takes the streamed forward and both backward passes at
    (1, 16384, 28 over 4, 128) bf16, windowed and global."""
    from jax.experimental.compilation_cache import compilation_cache

    from theanompi_tpu.ops import pallas_mode

    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)
    q = jax.ShapeDtypeStruct((1, 16384, 28, 128), jnp.bfloat16,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16,
                             sharding=one_chip)
    loss = lambda q, k, v: A.fused_attention(  # noqa: E731
        q, k, v, causal=True, impl="pallas", name="test_attention",
        window=window).astype(jnp.float32).sum()
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
            q, k, k).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    for kernel in ("test_attention_fwd", "test_attention_bwd_kv",
                   "test_attention_bwd_q"):
        assert kernel in text


def test_the_scan_kernels_compile_for_a_v5e_under_their_scope(monkeypatch,
                                                              one_chip):
    """The Mamba-2 scan's kernel pair at the Nemotron cell's scan shape
    (4 x 2048 tokens, 64 heads of 64 in 8 groups, state 128, chunks of
    128) inside a recomputed mixer, compiled for the chip: Mosaic takes
    both, and the step's map places the forward (run and recomputed) and
    the backward under the scope ``ssd_share`` reads."""
    import re

    import flax.linen as nn
    from jax.experimental.compilation_cache import compilation_cache

    from theanompi_tpu.models import nemotron_h
    from theanompi_tpu.monitor import scopes
    from theanompi_tpu.ops import pallas_mode

    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)

    class Net(nn.Module):
        @nn.compact
        def __call__(self, u):
            return nn.remat(nemotron_h.Mamba2Mixer)(
                d_model=256, n_heads=64, head_dim=64, n_groups=8, state=128,
                chunk=128, dtype=jnp.bfloat16, name="mamba")(u)

    net = Net()
    u = jax.ShapeDtypeStruct((4, 2048, 256), jnp.bfloat16, sharding=one_chip)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(net.init, jax.random.key(0), u))
    loss = lambda p, u: net.apply(p, u).astype(  # noqa: E731
        jnp.float32).sum()
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(jax.value_and_grad(loss)).lower(
            params, u).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    scope = r"(^|/)nemotron_h/mamba/ssd(/|$)"
    kernels = {(name.rsplit(".", 1)[0], phase)
               for name, (phase, where) in scopes.scope_map(text).items()
               if name.startswith("nemotron_h_ssd_")
               and re.search(scope, where)}
    assert kernels == {("nemotron_h_ssd_fwd", "forward"),
                       ("nemotron_h_ssd_fwd", "recompute"),
                       ("nemotron_h_ssd_bwd", "backward")}


def test_the_delta_rule_kernels_compile_for_a_v5e_under_their_scope(
        monkeypatch, one_chip):
    """The gated delta rule's kernel pair at the Qwen3-Next cell's shape
    (4 x 2048 tokens, 16 key heads repeated to 32 value heads of 128,
    chunks of 64) inside a recomputed Gated DeltaNet mixer, compiled for
    the chip: Mosaic takes both, XLA's triangular solve is gone, and the
    step's map places the forward (run and recomputed) and the backward
    under the scope both delta-rule metrics read."""
    import re

    import flax.linen as nn
    from jax.experimental.compilation_cache import compilation_cache

    from theanompi_tpu.models import qwen3_next
    from theanompi_tpu.monitor import scopes
    from theanompi_tpu.ops import pallas_mode

    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)

    class Net(nn.Module):
        @nn.compact
        def __call__(self, u):
            return nn.remat(qwen3_next.GatedDeltaNetMixer)(
                d_model=256, key_heads=16, value_heads=32, key_dim=128,
                value_dim=128, chunk=64, dtype=jnp.bfloat16,
                name="linear")(u)

    net = Net()
    u = jax.ShapeDtypeStruct((4, 2048, 256), jnp.bfloat16, sharding=one_chip)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(net.init, jax.random.key(0), u))
    loss = lambda p, u: net.apply(p, u).astype(  # noqa: E731
        jnp.float32).sum()
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(jax.value_and_grad(loss)).lower(
            params, u).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    assert "InvertDiagBlocksLowerTriangular" not in text
    scope = r"(^|/)qwen3_next/linear_attention/delta_rule(/|$)"
    kernels = {(name.rsplit(".", 1)[0], phase)
               for name, (phase, where) in scopes.scope_map(text).items()
               if name.startswith("qwen3_next_delta_rule_")
               and re.search(scope, where)}
    assert kernels == {("qwen3_next_delta_rule_fwd", "forward"),
                       ("qwen3_next_delta_rule_fwd", "recompute"),
                       ("qwen3_next_delta_rule_bwd", "backward")}


@pytest.mark.parametrize("n, top_k, count, rows, d", [
    (16384, 6, 16, 51200, 2560),     # SmallThinker's lower rung
    (8192, 10, 32, 14336, 2048),     # Qwen3-Next's
    (8192, 6, 8, 7168, 2688),        # Nemotron's
])
def test_the_row_kernel_compiles_for_a_v5e(one_chip, n, top_k, count, rows,
                                           d):
    """The expert buffer's sum into the tokens (ops/expert_rows.py) at
    the three lower rungs that sum from the buffer's side, bf16, with
    the weight fused and without, its tables built in the same
    program: Mosaic takes it, and the scalar tables fit SMEM."""
    from jax.experimental.compilation_cache import compilation_cache

    from theanompi_tpu.ops import expert_rows

    plan = expert_rows.row_plan(n, rows, count, "test")
    assert plan.pallas

    def layer(values, token, weight, onehot, starts):
        walk = expert_rows.visits(*expert_rows.block_ranges(
            onehot, starts, top_k, plan), plan)
        return (expert_rows.sum_rows(values, token, weight, walk, plan),
                expert_rows.sum_rows(values, token, None, walk, plan))

    shapes = [((rows, d), jnp.bfloat16), ((rows,), jnp.int32),
              ((rows,), jnp.float32), ((n * top_k, count), jnp.bool_),
              ((count,), jnp.int32)]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(layer).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    assert "test_rows" in text


#: every attention shape the LM cells call (q, key/value heads, whether
#: the kernels rotate) and its plan: the four cells' from before PR 38,
#: pinned so that the wide-head budget moves none of them, and the
#: Qwen3-Next cell's head of 256
CELL_PLANS = {
    "gpt2m_s1024_x1": ((8, 1024, 16, 64), 16, False,
                       A.TilePlan(512, 512, True, 3, 4, True, None)),
    "gpt2m_s128_x1": ((64, 128, 16, 64), 16, False,
                      A.TilePlan(128, 128, True, 1, 1, True, None)),
    "zaya1_8b_s2048_x1": ((4, 2048, 8, 128), 2, False,
                          A.TilePlan(512, 512, True, 10, 16, True, None)),
    "ouro_2_6b_s2048_x1": ((4, 2048, 16, 128), 16, True,
                           A.TilePlan(512, 512, True, 10, 16, False,
                                      "kernel")),
    "nemotron_twotower_30b_s2048_x1": (
        (4, 2048, 32, 128), 2, False,
        A.TilePlan(512, 512, True, 10, 16, True, None)),
    "qwen3_next_80b_s2048_x1": ((4, 2048, 16, 256), 2, False,
                                A.TilePlan(512, 512, True, 10, 16, True,
                                           None)),
}


@pytest.mark.parametrize("cell", CELL_PLANS)
def test_each_cells_plan_and_budget_are_pinned(cell):
    """The plan (q block, key tile, tiles visited, what the kernels are
    handed) of each cell's attention shape, bf16, causal; both passes
    fused, the head of 256 under its own budget and every other head
    under the 16 MiB it was graded at."""
    (b, t, h, d), kv_heads, rotary, plan = CELL_PLANS[cell]
    got = A.tile_plan(t, t, d, jnp.bfloat16, True, rotary=rotary)
    assert got == plan
    assert A._vmem_budget(d) == (32 if d >= 256 else 16) * 2 ** 20
    assert A._fits_vmem(t, d, jnp.bfloat16, got.q_block, got.positions,
                        got.rotates)
    assert A._fits_vmem_bwd(t, t, d, jnp.bfloat16, got.q_block,
                            got.positions, got.rotates)


def test_a_head_of_256_needs_its_own_budget(monkeypatch):
    """At 16 MiB no q block holds the Qwen3-Next cell's fused backward
    (whole Q, G, dq, K, V, dk, dv of a head of 256, twice): its backward
    would fall back to the composed XLA form and its (4, 16, 2048,
    2048) float32 scores."""
    monkeypatch.setattr(A, "_WIDE_HEAD_BUDGET_BYTES", A._VMEM_BUDGET_BYTES)
    assert not any(A._fits_vmem_bwd(2048, 2048, 256, jnp.bfloat16, blk)
                   for blk in (512, 256, 128))
