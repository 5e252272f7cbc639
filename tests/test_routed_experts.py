"""The expert layer that is told which experts it holds
(parallel/expert.py ``routed_experts``) and its grouped matrix product
(ops/grouped_matmul.py), at small sizes on the CPU: the kernels run
interpreted, ``jax.lax.ragged_dot`` is the oracle, and the benchmark's
plain reference (benchmarks/reference/zaya1_8b.py) is the uncut layer."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.models.zaya import ZayaRouter
from theanompi_tpu.ops import grouped_matmul as G
from theanompi_tpu.parallel.expert import routed_experts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
IMPLS = ("ragged_dot", "pallas")


def _reference():
    spec = importlib.util.spec_from_file_location(
        "zaya_reference",
        os.path.join(ROOT, "benchmarks", "reference", "zaya1_8b.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _layer_params(n_experts=16, d=16, f=24, hidden=8, seed=0):
    """A router + experts tree under the names the reference reads."""
    key = jax.random.key(seed)
    u = jax.random.normal(jax.random.fold_in(key, 0), (300, d))
    router = ZayaRouter(n_experts, hidden).init(
        jax.random.fold_in(key, 1), u)["params"]
    # spread the logits so that every expert gets tokens
    router["fc3"]["kernel"] = router["fc3"]["kernel"] * 8.0
    normal = lambda i, shape: 0.3 * jax.random.normal(  # noqa: E731
        jax.random.fold_in(key, i), shape)
    return u, {"router": router,
               "experts_gate": normal(2, (n_experts, d, f)),
               "experts_up": normal(3, (n_experts, d, f)),
               "experts_down": normal(4, (n_experts, f, d))}


def _share(u, p, held, impl, top_k=1, **kw):
    first, count = held
    probs = ZayaRouter(p["router"]["fc3"]["bias"].shape[0],
                       p["router"]["fc1"]["bias"].shape[0]).apply(
        {"params": p["router"]}, u)
    experts = {k: p["experts_" + k][first:first + count]
               for k in ("gate", "up", "down")}
    return routed_experts(u, probs, experts, held, top_k=top_k, impl=impl,
                          **kw)


@pytest.mark.parametrize("impl", IMPLS)
def test_the_two_shares_add_up_to_the_uncut_layer(impl):
    """16 experts held as 2 x 8: what the two chips' layers give, each
    for its own experts, sums to the reference's whole layer."""
    u, p = _layer_params()
    whole = _reference()._moe(u[None], p, held=(0, 16))[0]
    (low, low_stats), (high, high_stats) = (
        jax.jit(lambda u, p, held=held: _share(u, p, held, impl))(u, p)
        for held in ((0, 8), (8, 8)))
    np.testing.assert_allclose(low + high, whole, rtol=1e-5, atol=1e-5)
    # both shares do work, and every token is somebody's
    assert low_stats["held_rows"] > 0 and high_stats["held_rows"] > 0
    assert low_stats["held_rows"] + high_stats["held_rows"] == 300
    assert low_stats["rows_elsewhere"] == high_stats["held_rows"]
    # and each share alone is the reference's share
    np.testing.assert_allclose(
        low, _reference()._moe(u[None], p, held=(0, 8))[0],
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", IMPLS)
def test_the_shares_gradients_add_up_too(impl):
    u, p = _layer_params(n_experts=4, seed=3)

    def both(u, p):
        return sum((_share(u, p, held, impl)[0] ** 2).sum()
                   for held in ((0, 2), (2, 2)))

    def whole(u, p):
        # the shares' squares add up only where the shares do not
        # overlap: top-1, so each token is in exactly one
        return (_reference()._moe(u[None], p, held=(0, 4)) ** 2).sum()

    got = jax.jit(jax.grad(both, argnums=(0, 1)))(u, p)
    want = jax.grad(whole, argnums=(0, 1))(u, p)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("impl", IMPLS)
def test_nothing_is_dropped_when_every_token_picks_one_expert(impl):
    """A router forced onto expert 3: all 300 tokens reach it (the
    capacity path would drop all but 1.25 * 300 / 16), and the chip
    that does not hold it returns zeros."""
    u, p = _layer_params()
    probs = jnp.full((300, 16), 0.01).at[:, 3].set(0.85)
    experts = {k: p["experts_" + k] for k in ("gate", "up", "down")}
    held = {k: v[:8] for k, v in experts.items()}
    out, stats = routed_experts(u, probs, held, (0, 8), impl=impl)
    want = 0.85 * (jax.nn.silu(u @ experts["gate"][3])
                   * (u @ experts["up"][3])) @ experts["down"][3]
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    assert (stats["held_rows"], stats["rows_elsewhere"],
            stats["max_expert_rows"]) == (300, 0, 300)
    away = {k: v[8:] for k, v in experts.items()}
    out, stats = routed_experts(u, probs, away, (8, 8), impl=impl)
    assert not np.asarray(out).any()
    assert (stats["held_rows"], stats["rows_elsewhere"],
            stats["max_expert_rows"]) == (0, 300, 0)


@pytest.mark.parametrize("impl", IMPLS)
def test_top_2_weights_each_choice_by_its_probability(impl):
    u, p = _layer_params(n_experts=4, seed=5)
    probs = jax.nn.softmax(
        jax.random.normal(jax.random.key(8), (300, 4)) * 2)
    experts = {k: p["experts_" + k] for k in ("gate", "up", "down")}
    out, stats = routed_experts(u, probs, experts, (0, 4), top_k=2,
                                impl=impl)
    weights, chosen = jax.lax.top_k(probs, 2)
    want = 0
    for e in range(4):
        y = (jax.nn.silu(u @ experts["gate"][e])
             * (u @ experts["up"][e])) @ experts["down"][e]
        want = want + ((chosen == e) * weights).sum(-1)[:, None] * y
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    assert stats["held_rows"] == 600


@pytest.mark.parametrize("impl", IMPLS)
def test_a_balancing_bias_moves_the_choice_and_not_the_weight(impl):
    """``select_by``: the top-1 is taken over biased scores, the output
    is still weighted by the unbiased probability, and ``expert_load``
    counts every expert's assignments, held or not."""
    u, p = _layer_params(n_experts=4, seed=7)
    bias = jnp.array([0.0, 3.0, -3.0, 0.5])
    probs = ZayaRouter(4, 8).apply({"params": p["router"]}, u)
    out, stats = _share(u, p, (0, 2), impl,
                        select_by=jnp.log(probs) + bias)
    want = _reference()._moe(u[None], p, held=(0, 2), bias=bias)[0]
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    plain = _share(u, p, (0, 2), impl)[1]["expert_load"]
    load = stats["expert_load"]
    assert load.sum() == plain.sum() == 300
    assert load[1] > plain[1] and load[2] < plain[2]
    assert stats["held_rows"] == load[:2].sum()


def test_held_experts_must_be_among_the_routers():
    u, p = _layer_params(n_experts=4)
    with pytest.raises(ValueError, match="not among the router's 4"):
        _share(u, p, (2, 4), "ragged_dot")
    with pytest.raises(ValueError, match="unknown expert matmul impl"):
        _share(u, p, (0, 4), "dense")


def _tiled_layout(sizes, tile=G.TILE_M):
    """Rows laid out group by group on tile boundaries, as
    routed_experts lays them; an empty group keeps one tile."""
    tiles = np.maximum(-(-np.asarray(sizes) // tile), 1)
    tile_group = np.repeat(np.arange(len(sizes)), tiles)
    mask = np.concatenate([
        np.arange(t * tile) < s for s, t in zip(sizes, tiles)])
    spare = 2   # tiles past n_tiles: never read, never written
    return (jnp.asarray(np.concatenate([tile_group, [len(sizes) - 1] * spare]),
                        jnp.int32),
            jnp.asarray(tiles.sum(), jnp.int32), jnp.asarray(mask),
            jnp.asarray(tiles * tile, jnp.int32), spare * tile)


def test_the_kernels_agree_with_ragged_dot():
    """Values and both gradients of the interpreted Pallas kernels
    against ``jax.lax.ragged_dot`` over the same tile-aligned layout:
    uneven groups, one of them empty."""
    tile_group, n_tiles, mask, padded, spare = _tiled_layout([200, 0, 37, 128])
    rows = mask.shape[0]
    key = jax.random.key(2)
    lhs = jnp.where(mask[:, None], jax.random.normal(
        jax.random.fold_in(key, 0), (rows, 24)), 0)
    lhs = jnp.concatenate([lhs, jnp.zeros((spare, 24))])
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (4, 24, 40))
    live = jnp.concatenate([mask, jnp.zeros(spare, bool)])[:, None]

    def kernel(lhs, rhs):
        out = G.grouped_matmul(lhs, rhs, tile_group, n_tiles, "test", True)
        return jnp.where(live, out, 0)   # rows past n_tiles: undefined

    def oracle(lhs, rhs):
        return jnp.where(live, jax.lax.ragged_dot(lhs, rhs, padded), 0)

    np.testing.assert_allclose(kernel(lhs, rhs), oracle(lhs, rhs),
                               rtol=1e-5, atol=1e-5)
    loss = lambda fn: lambda a, b: (fn(a, b) ** 2).sum()  # noqa: E731
    got = jax.grad(loss(kernel), argnums=(0, 1))(lhs, rhs)
    want = jax.grad(loss(oracle), argnums=(0, 1))(lhs, rhs)
    # the rows past n_tiles are undefined in the gradient as well (the
    # interpreter fills them with NaN, which is how this test knows
    # that nothing reads them)
    np.testing.assert_allclose(jnp.where(live, got[0], 0), want[0],
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-4)
    assert not np.asarray(got[1][1]).any()   # the empty group's matrix


def test_the_kernels_carry_their_names():
    """A trace reducer finds the kernels by name: the forward product,
    its transpose and the weight gradient each say which they are."""
    tile_group, n_tiles, mask, _, _ = _tiled_layout([128, 128])
    lhs = jnp.ones((256, 8))
    rhs = jnp.ones((2, 8, 8))
    text = str(jax.make_jaxpr(jax.grad(
        lambda a, b: G.grouped_matmul(a, b, tile_group[:2], n_tiles,
                                      "zaya_experts_gate", True).sum(),
        argnums=(0, 1)))(lhs, rhs))
    for name in ("zaya_experts_gate_gmm", "zaya_experts_gate_gmm_t",
                 "zaya_experts_gate_tgmm"):
        assert name in text


def test_rows_must_be_whole_tiles():
    with pytest.raises(ValueError, match="whole number"):
        G.gmm(jnp.ones((100, 8)), jnp.ones((1, 8, 8)),
              jnp.zeros(1, jnp.int32), jnp.asarray(1, jnp.int32),
              interpret=True)
