"""The expert layer that is told which experts it holds
(parallel/expert.py ``routed_experts``) and its grouped matrix product
(ops/grouped_matmul.py), at small sizes on the CPU: the kernels run
interpreted, ``jax.lax.ragged_dot`` is the oracle, and the benchmark's
plain reference (benchmarks/reference/zaya1_8b.py) is the uncut layer."""

import dataclasses
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


def _relu2_layer(n=300, d=16, f=24, n_experts=16, seed=11):
    key = jax.random.key(seed)
    u = jax.random.normal(key, (n, d))
    scores = jax.nn.sigmoid(
        2.0 * jax.random.normal(jax.random.fold_in(key, 1), (n, n_experts)))
    experts = {
        "up": 0.3 * jax.random.normal(jax.random.fold_in(key, 2),
                                      (n_experts, d, f)),
        "down": 0.3 * jax.random.normal(jax.random.fold_in(key, 3),
                                        (n_experts, f, d))}
    return u, scores, experts


@pytest.mark.parametrize("impl", IMPLS)
def test_two_matrix_experts_top_6_normalised_and_scaled(impl):
    """``up`` and ``down`` alone are a relu^2 expert (the keys say so,
    no flag); each of a token's 6 choices is weighted by its score over
    the sum of ALL 6, held here or not, times the scale."""
    u, scores, experts = _relu2_layer()
    held = {k: v[4:12] for k, v in experts.items()}
    out, stats = jax.jit(lambda u, s, p: routed_experts(
        u, s, p, (4, 8), top_k=6, normalize=True, scale=2.5, impl=impl,
        name="nemotron_h_experts"))(u, scores, held)
    picked, chosen = jax.lax.top_k(scores, 6)
    weights = 2.5 * picked / picked.sum(-1, keepdims=True)
    want = 0
    for e in range(4, 12):
        y = jnp.square(jax.nn.relu(u @ experts["up"][e])) @ experts["down"][e]
        want = want + ((chosen == e) * weights).sum(-1)[:, None] * y
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    assert stats["held_rows"] == ((chosen >= 4) & (chosen < 12)).sum()
    assert stats["held_rows"] + stats["rows_elsewhere"] == 6 * 300
    assert stats["expert_load"].sum() == 6 * 300
    # without the normalisation the weights are the scores as they are
    plain = routed_experts(u, scores, held, (4, 8), top_k=6, impl=impl)[0]
    assert float(jnp.abs(plain - out).max()) > 1e-3


def test_two_matrix_experts_interpreted_kernels_against_ragged_dot():
    """Values and every gradient (tokens, scores, both matrices) of the
    interpreted Pallas kernels against ``jax.lax.ragged_dot``, top-6,
    at a width the column tiles do not divide (f = 200: a last tile of
    72 columns in the up product, and the same along the down product's
    contraction)."""
    u, scores, experts = _relu2_layer(n=150, d=136, f=200, seed=13)
    held = {k: v[:8] for k, v in experts.items()}

    def loss(impl):
        def fn(u, scores, held):
            out, _ = routed_experts(u, scores, held, (0, 8), top_k=6,
                                    normalize=True, scale=2.5, impl=impl)
            return (out ** 2).sum(), out
        return jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2),
                                          has_aux=True))

    ((_, got_out), got), ((_, want_out), want) = (
        loss(impl)(u, scores, held) for impl in ("pallas", "ragged_dot"))
    np.testing.assert_allclose(got_out, want_out, rtol=1e-4, atol=1e-4)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-3,
                                   atol=1e-4 * float(jnp.abs(b).max()))


def test_two_matrix_experts_carry_their_names():
    u, scores, experts = _relu2_layer(n=20)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: routed_experts(u, scores, p, (0, 16), top_k=6,
                                 impl="pallas",
                                 name="nemotron_h_experts")[0].sum()))(
        experts))
    for which in ("up", "down"):
        for kernel in ("gmm", "gmm_t", "tgmm"):
            assert f"nemotron_h_experts_{which}_{kernel}" in text
    assert "nemotron_h_experts_gate" not in text


@pytest.mark.parametrize("n, k, itemsize, tile", [
    (2048, 2048, 2, 1024),    # ZayaLM's products: as before
    (2048, 2048, 4, 512),     # ... and its weight gradient's accumulator
    (1856, 2688, 2, 640),     # NemotronHLM up: 3 tiles, the last 576 wide
    (2688, 1856, 2, 896),     # its down product: 3 whole tiles
    (1856, 2688, 4, 384),     # the accumulators of the weight gradients:
    (2688, 1856, 4, 384),     # 5 tiles, the last 320 wide; 7 whole tiles
    (40, 24, 4, 40),          # small test shapes: the whole width
    (200, 136, 2, 128),       # no tile wider than the product
])
def test_the_column_tile_at_the_widths_met(n, k, itemsize, tile):
    """1856 = 14.5 x 128 has no dividing tile and 2688 = 21 x 128 no
    power of two above 128: the tile is the multiple of 128 that costs
    least within the block budget, and the last one may be partly
    empty."""
    assert G._column_tile(n, k, itemsize) == tile
    assert n <= 128 or k * tile * itemsize <= G._RHS_BLOCK_BYTES


def test_the_transposed_product_takes_whole_tiles_over_a_ragged_contraction():
    """On the chip the transposed product hung with a partly empty last
    tile where its contraction (1856) was no multiple of 128 (PR 34):
    there only tiles that divide the width, or the whole width."""
    assert G._column_tile(2688, 1856, 2, whole_tiles=True) == 896
    assert G._column_tile(200, 136, 2, whole_tiles=True) == 200
    assert G._column_tile(1856, 2688, 2, whole_tiles=True) == 1856


def test_a_partly_empty_last_column_tile_in_all_three_kernels():
    """(rows, 136) x (3, 136, 200): two column tiles of 128, the second
    72 wide, forward and in both gradients."""
    tile_group, n_tiles, mask, padded, spare = _tiled_layout([130, 0, 90])
    rows = mask.shape[0]
    key = jax.random.key(5)
    lhs = jnp.where(mask[:, None], jax.random.normal(
        jax.random.fold_in(key, 0), (rows, 136)), 0)
    lhs = jnp.concatenate([lhs, jnp.zeros((spare, 136))])
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (3, 136, 200))
    live = jnp.concatenate([mask, jnp.zeros(spare, bool)])[:, None]

    def kernel(lhs, rhs):
        out = G.grouped_matmul(lhs, rhs, tile_group, n_tiles, "test", True)
        return jnp.where(live, out, 0)

    def oracle(lhs, rhs):
        return jnp.where(live, jax.lax.ragged_dot(lhs, rhs, padded), 0)

    assert kernel(lhs, rhs).shape == (rows + spare, 200)
    np.testing.assert_allclose(kernel(lhs, rhs), oracle(lhs, rhs),
                               rtol=1e-5, atol=1e-4)
    loss = lambda fn: lambda a, b: (fn(a, b) ** 2).sum()  # noqa: E731
    got = jax.grad(loss(kernel), argnums=(0, 1))(lhs, rhs)
    want = jax.grad(loss(oracle), argnums=(0, 1))(lhs, rhs)
    np.testing.assert_allclose(jnp.where(live, got[0], 0), want[0],
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-3)


# --- the buffer's ladder (PR 35) --------------------------------------

@pytest.mark.parametrize("n_assign, count, n_experts, rungs", [
    (8192 * 6, 8, 128, (7168, 50176)),   # NemotronHLM's cell: two rungs
    (8192, 8, 16, (9216,)),              # ZayaLM's: the top one alone
    (8192 * 10, 32, 512, (14336, 86016)),  # Qwen3NextLM's
    (16384 * 6, 16, 64, (51200, 100352)),  # SmallThinkerLM's: a quarter held
    (300 * 6, 8, 128, (1280, 2944)),     # the small shape forced below
    (400 * 6, 4, 16, (1792, 2944)),      # a held quarter, forced below
    (300 * 6, 4, 16, (1536, 2432)),      # a held quarter: past half
    (300 * 6, 8, 16, (2944,)),           # half the experts held: no room
    (300, 8, 16, (1408,)),               # top-1 (384 + 1024)
    (100, 2, 64, (384,)),                # the slack of a tile an expert
    (4096 * 8, 4, 256, (1536, 33280)),   # top-8 of 256, 4 held
])
def test_the_ladder_is_a_function_of_the_calls_shape(n_assign, count,
                                                     n_experts, rungs):
    """The top rung is the dropless buffer; a lower rung of twice the
    expected load exists only where it is at most three quarters of
    that: at a held quarter the slack of a tile an expert takes the
    lower rung just past half of the top one."""
    from theanompi_tpu.parallel.expert import buffer_ladder
    got = buffer_ladder(n_assign, count, n_experts)
    assert got == rungs
    assert all(r % G.TILE_M == 0 for r in got)
    assert got[-1] >= n_assign + count * G.TILE_M
    assert all(4 * low <= 3 * got[-1] for low in got[:-1])


#: the two calls whose ladders the routings below force: name ->
#: (tokens, experts, held), top-6
_SHAPES = {
    # an eighth of 128 held: the (1280, 2944) ladder
    "eighth": (300, 128, 8),
    # a held quarter, as SmallThinkerLM's chip holds 16 of 64: the
    # (1792, 2944) ladder, the lower rung just past half of the top one
    "quarter": (400, 16, 4),
}

#: routings that force a rung of a ladder: name -> (the shape, expert ->
#: the tokens that pick it, the rung's rows, the held rows)
_ROUTINGS = {
    # a deployment's share: 8 tiles, the lower rung with room
    "few": ("eighth", {e: range(20 * e, 20 * e + 12) for e in range(8)},
            1280, 96),
    # 3 + 7 tiles: exactly the lower rung's 10
    "full": ("eighth", {0: range(300), **{e: range(35 * e, 35 * e + 30)
                                          for e in range(1, 8)}}, 1280, 510),
    # 3 + 2 + 6 tiles: one more than it holds
    "one_more": ("eighth", {0: range(300), 1: range(40, 170),
                            **{e: range(35 * e, 35 * e + 30)
                               for e in range(2, 8)}}, 2944, 610),
    # every token's six choices held here: the worst case
    "all": ("eighth", {e: range(300) for e in range(6)}, 2944, 1800),
    # a held quarter's share: 4 x 2 tiles on the lower rung
    "quarter_few": ("quarter", {e: range(60 * e, 60 * e + 150)
                                for e in range(4)}, 1792, 600),
    # 4 + 4 + 4 + 2 tiles: exactly the lower rung's 14
    "quarter_full": ("quarter", {**{e: range(400) for e in range(3)},
                                 3: range(200)}, 1792, 1400),
    # 4 + 4 + 4 + 3 tiles: one more than it holds
    "quarter_one_more": ("quarter", {**{e: range(400) for e in range(3)},
                                     3: range(300)}, 2944, 1500),
}


def _forced_layer(routing, form, seed=17):
    """Tokens, scores and the held experts' matrices: the planned
    assignments score 0.5-0.9, a held expert scores 0 elsewhere, and a
    token's other choices fall on the experts not held."""
    shape, plan = _ROUTINGS[routing][:2]
    n, n_experts, count = _SHAPES[shape]
    key = jax.random.key(seed)
    u, _, experts = _relu2_layer(n=n, n_experts=count, seed=seed)
    if form in ("gated", "reglu"):
        experts["gate"] = 0.3 * jax.random.normal(
            jax.random.fold_in(key, 5), experts["up"].shape)
    scores = np.array(0.01 + 0.09 * jax.random.uniform(
        jax.random.fold_in(key, 6), (n, n_experts)))
    scores[:, :count] = 0.0
    high = np.array(0.5 + 0.4 * jax.random.uniform(
        jax.random.fold_in(key, 7), (n, count)))
    for e, tokens in plan.items():
        scores[list(tokens), e] = high[list(tokens), e]
    return u, jnp.asarray(scores), experts


#: the gated form's activation in each form of expert
_ACTIVATIONS = {"relu2": "silu", "gated": "silu", "reglu": "relu"}


def _uncut(u, scores, experts, activation="silu"):
    """The layer with no buffer at all: every held expert applied to
    every token, weighted where the token chose it."""
    picked, chosen = jax.lax.top_k(scores, 6)
    weights = 2.5 * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    act = {"silu": jax.nn.silu, "relu": jax.nn.relu}[activation]
    out = 0
    for e in range(experts["up"].shape[0]):
        up = u @ experts["up"][e]
        hidden = (act(u @ experts["gate"][e]) * up
                  if "gate" in experts else jnp.square(jax.nn.relu(up)))
        out = out + (((chosen == e) * weights).sum(-1)[:, None]
                     * (hidden @ experts["down"][e]))
    return out


def _loss(fn):
    """``fn``'s output, its counters where it has them, and the
    gradients of its sum of squares by tokens, scores and matrices."""
    def scalar(u, scores, experts):
        out = fn(u, scores, experts)
        out, stats = out if isinstance(out, tuple) else (out, None)
        return (out ** 2).sum(), (out, stats)
    return jax.jit(jax.value_and_grad(scalar, argnums=(0, 1, 2),
                                      has_aux=True))


@pytest.mark.parametrize("form", ["relu2", "gated", "reglu"])
@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("routing", list(_ROUTINGS))
def test_every_rung_is_the_uncut_layer(routing, impl, form):
    """Output, every gradient (tokens, scores, each matrix) and the
    counters on each rung of the ladder, top-6 normalised and scaled:
    the lower rung sums from the buffer's side, the top one gathers as
    before, and both are differentiated through the ladder's own VJP;
    for the squared ReLU, the gated SiLU and the gated ReLU (ReGLU),
    with an eighth of the experts held and with a quarter."""
    u, scores, experts = _forced_layer(routing, form)
    shape, _, rows, held_rows = _ROUTINGS[routing]
    n, _, count = _SHAPES[shape]
    activation = _ACTIVATIONS[form]

    def layer(u, scores, experts):
        return routed_experts(u, scores, experts, (0, count), top_k=6,
                              normalize=True, scale=2.5, impl=impl,
                              activation=activation)

    def uncut(u, scores, experts):
        return _uncut(u, scores, experts, activation)

    (_, (got_out, stats)), got = _loss(layer)(u, scores, experts)
    (_, (want_out, _)), want = _loss(uncut)(u, scores, experts)
    assert (stats["buffer_rows"], stats["held_rows"]) == (rows, held_rows)
    assert stats["held_rows"] + stats["rows_elsewhere"] == 6 * n
    np.testing.assert_allclose(got_out, want_out, rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(b).max() + 1))


@pytest.mark.parametrize("impl", IMPLS)
def test_a_held_quarter_is_the_same_layer_on_either_rung(impl,
                                                         monkeypatch):
    """The routing of a held quarter's share, on the lower rung (summed
    from the buffer's side) and forced onto the top rung alone (summed
    from the tokens' side): the same output and gradients as each other
    and as ``ragged_dot`` on the lower rung, in ReGLU as
    ``SmallThinkerLM`` runs it."""
    from theanompi_tpu.parallel import expert
    u, scores, experts = _forced_layer("quarter_few", "reglu")

    def run(impl):
        return _loss(lambda u, s, p: routed_experts(
            u, s, p, (0, 4), top_k=6, normalize=True, scale=2.5, impl=impl,
            activation="relu"))(u, scores, experts)

    (_, (low, low_stats)), low_grads = run(impl)
    (_, (oracle, _)), oracle_grads = run("ragged_dot")
    ladder = expert.buffer_ladder
    monkeypatch.setattr(expert, "buffer_ladder",
                        lambda *shape: ladder(*shape)[-1:])
    (_, (top, top_stats)), top_grads = run(impl)
    assert (low_stats["buffer_rows"], top_stats["buffer_rows"]) == (
        1792, 2944)
    for want, want_grads in ((top, top_grads), (oracle, oracle_grads)):
        np.testing.assert_allclose(low, want, rtol=1e-5, atol=1e-5)
        for a, b in zip(jax.tree.leaves(low_grads),
                        jax.tree.leaves(want_grads)):
            np.testing.assert_allclose(
                a, b, rtol=1e-4, atol=1e-5 * float(jnp.abs(b).max() + 1))


def test_the_ladder_is_recomputed_under_remat_like_the_uncut_layer():
    """``jax.checkpoint`` round the layer, as ``nn.remat`` puts it in
    the models: the ladder's VJP keeps only its operands."""
    u, scores, experts = _forced_layer("few", "relu2")

    def loss(fn):
        return jax.jit(jax.grad(jax.checkpoint(
            lambda *a: (fn(*a) ** 2).sum()), argnums=(0, 1, 2)))

    got = loss(lambda u, s, p: routed_experts(
        u, s, p, (0, 8), top_k=6, normalize=True, scale=2.5)[0])(
            u, scores, experts)
    want = loss(_uncut)(u, scores, experts)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(b).max() + 1))


def test_one_rung_is_the_body_alone(monkeypatch):
    """At ``ZayaLM``'s call shape (8 192 tokens, top-1, 8 of 16 held)
    the ladder has one rung: no ``cond`` in the program, the ladder's
    VJP is not entered, and the one body is traced once at 9 216 rows,
    differentiated by JAX as before (``buffer_rows`` at one rung:
    tests/test_zaya.py, tests/test_nemotron_h.py)."""
    from theanompi_tpu.parallel import expert

    def no_ladder(*args):
        raise AssertionError("one rung needs no switch")

    traced = []
    rung = expert._rung
    monkeypatch.setattr(expert, "_ladder", no_ladder)
    monkeypatch.setattr(expert, "_rung", lambda rows, *a: (
        traced.append(rows), rung(rows, *a))[1])
    x = jax.ShapeDtypeStruct((8192, 32), jnp.bfloat16)
    probs = jax.ShapeDtypeStruct((8192, 16), jnp.float32)
    experts = {k: jax.ShapeDtypeStruct(
        (8, 48, 32) if k == "down" else (8, 32, 48), jnp.float32)
        for k in ("gate", "up", "down")}

    def fn(x, probs, experts):
        out, stats = routed_experts(x, probs, experts, (0, 8),
                                    select_by=jnp.log(probs),
                                    impl="ragged_dot", name="zaya_experts")
        return out.astype(jnp.float32).sum(), stats

    text = str(jax.make_jaxpr(jax.value_and_grad(
        fn, argnums=(0, 1, 2), has_aux=True))(x, probs, experts))
    assert traced == [9216]
    assert "cond[" not in text and "9216" in text


def test_the_plan_is_said_once_a_shape(caplog):
    from theanompi_tpu.parallel import expert
    expert._log_buffer_plan.cache_clear()
    u, scores, experts = _forced_layer("few", "relu2")
    with caplog.at_level("INFO", logger=expert.__name__):
        for _ in range(2):
            routed_experts(u, scores, experts, (0, 8), top_k=6,
                           name="nemotron_h_experts")
    said = [r.getMessage() for r in caplog.records]
    assert said == ["nemotron_h_experts: expert buffer: rungs 1280 / 2944 "
                    "of 128-row tiles",
                    "nemotron_h_experts: rung 1280 summed into 300 tokens "
                    "by an XLA scatter-add"]


def test_the_kernels_keep_their_names_inside_the_ladders_backward():
    """The ladder's backward pass runs ``jax.vjp`` of a rung, and JAX
    renames the first scope under a transformation (``jvp(...)``): it
    must not be a kernel's, whose HLO instruction the trace's readers
    find by its plain name (``..._gmm``, ``..._gmm_t``, ``..._tgmm``)."""
    u, scores, experts = _forced_layer("few", "relu2")
    text = jax.jit(jax.value_and_grad(
        lambda u, s, p: routed_experts(u, s, p, (0, 8), top_k=6,
                                       impl="pallas",
                                       name="nemotron_h_experts")[0].sum(),
        argnums=(0, 1, 2))).lower(u, scores, experts).as_text(
            debug_info=True)
    for which in ("up", "down"):
        for kernel in ("gmm", "gmm_t", "tgmm"):
            assert f'/nemotron_h_experts_{which}_{kernel}/' in text
    assert "(nemotron_h_experts" not in text


@pytest.mark.parametrize("routing", ["few", "all"])
def test_the_default_activation_is_the_gated_silu_bit_for_bit(routing):
    """``activation`` left out is the program it was before the choice
    existed: the same jaxpr as ``"silu"`` named, the same bits, and
    ReGLU another layer; a two-matrix expert takes no gate activation."""
    u, scores, experts = _forced_layer(routing, "gated")

    def layer(**kw):
        return jax.jit(lambda u, s, p: routed_experts(
            u, s, p, (0, 8), top_k=6, normalize=True, scale=2.5, **kw)[0])

    default = layer()(u, scores, experts)
    silu = layer(activation="silu")(u, scores, experts)
    assert np.array_equal(np.asarray(default), np.asarray(silu))
    assert str(jax.make_jaxpr(layer())(u, scores, experts)) == str(
        jax.make_jaxpr(layer(activation="silu"))(u, scores, experts))
    reglu = layer(activation="relu")(u, scores, experts)
    assert not np.allclose(np.asarray(reglu), np.asarray(default))
    with pytest.raises(ValueError, match="gated form"):
        layer(activation="gelu")(u, scores, experts)
    two = {k: experts[k] for k in ("up", "down")}
    with pytest.raises(ValueError, match="gated form"):
        layer(activation="relu")(u, scores, two)


# --- the row kernel: the buffer summed into the tokens ----------------

def _placed_buffer(chosen, count, seed, dtype, spare_tiles=2):
    """A buffer laid out as ``routed_experts`` lays it, built here from
    the picks alone: experts ``0 .. count - 1`` are held, each one's
    rows in assignment order from the first row of its tiles.  Padding
    rows and the ``spare_tiles`` past the tiles in use hold NaN, which
    must not reach the output."""
    n, top_k = chosen.shape
    local = chosen.reshape(-1)
    onehot = local[:, None] == np.arange(count)
    sizes = onehot.sum(0)
    tiles = np.maximum(-(-sizes // G.TILE_M), 1)
    starts = (np.cumsum(tiles) - tiles) * G.TILE_M
    rows = int(tiles.sum() + spare_tiles) * G.TILE_M
    src = np.full(rows, n * top_k)
    for e in range(count):
        picks = np.flatnonzero(local == e)
        src[starts[e]:starts[e] + len(picks)] = picks
    placed = src < n * top_k
    key = jax.random.key(seed)
    values = jax.random.normal(key, (rows, 24), jnp.float32).astype(dtype)
    values = jnp.where(jnp.asarray(placed)[:, None], values, jnp.nan)
    weight = jax.random.uniform(jax.random.fold_in(key, 1), (rows,),
                                minval=0.1, maxval=0.9)
    return (values, weight, jnp.asarray(np.where(placed, src, 0) // top_k,
                                        jnp.int32),
            jnp.asarray(placed), jnp.asarray(onehot),
            jnp.asarray(starts, jnp.int32))


def _picks(n, top_k, n_experts, seed, held_by=None, none_held=()):
    """Each token's ``top_k`` distinct experts at random; ``held_by``
    pins the first picks of some tokens (``token -> experts``), and the
    tokens in ``none_held`` pick none of the held experts (those below
    ``n_experts // 2`` here)."""
    rng = np.random.default_rng(seed)
    chosen = np.stack([rng.permutation(n_experts)[:top_k] for _ in range(n)])
    for t, experts in (held_by or {}).items():
        rest = [e for e in chosen[t] if e not in experts]
        chosen[t] = (list(experts) + rest)[:top_k]
    for t in none_held:
        chosen[t] = rng.permutation(np.arange(n_experts // 2, n_experts))[
            :top_k]
    return chosen


#: name -> (picks, held experts, tokens a block, rows a window)
_ROW_CASES = {
    # tokens 32..63 pick no held expert: a block with no row (its one
    # empty visit), and empty (block, expert) ranges all round
    "empty_ranges_and_an_empty_block": (
        _picks(96, 2, 8, 1, none_held=range(32, 64)), 4, 32, 16),
    # every token picks held expert 0: a range of 64 rows in one block,
    # four windows of 16
    "a_range_longer_than_a_window": (
        _picks(64, 2, 4, 2, held_by={t: (0,) for t in range(64)}), 2, 64,
        16),
    # all of a token's picks held (and every token's: all experts held)
    "every_pick_held": (_picks(80, 3, 4, 3), 4, 16, 16),
    # a held quarter, top-6, uneven blocks (200 = 3 x 64 + 8)
    "a_held_quarter_top_6": (_picks(200, 6, 16, 4), 4, 64, 32),
}


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", list(_ROW_CASES))
def test_the_row_kernel_is_the_scatter_add(case, dtype, weighted):
    """``sum_rows`` interpreted against the XLA scatter-add it replaces,
    with and without the weight fused, forward and both gradients
    (through ``_move_rows`` and ``_sum_weighted``, whose VJPs are XLA's
    gathers): empty ranges and an empty block, a range over several
    windows, tokens with every pick held and with none, and NaN in the
    rows no range covers."""
    from theanompi_tpu.ops import expert_rows
    from theanompi_tpu.parallel import expert

    chosen, count, block, window = _ROW_CASES[case]
    n = chosen.shape[0]
    rows, weight, token, placed, onehot, starts = _placed_buffer(
        chosen, count, 5, dtype)
    plan = expert_rows.RowPlan("test", rows.shape[0], n, count, block,
                               window)
    walk = expert_rows.visits(*expert_rows.block_ranges(
        onehot, starts, chosen.shape[1], plan), plan)
    xla = dataclasses.replace(plan, pallas=False)

    def kernel(rows, weight):
        if weighted:
            return expert._sum_weighted(rows, weight, token, placed, walk,
                                        plan)
        return expert._move_rows(rows, token, placed, walk, n, False, plan)

    def scatter_add(rows, weight):
        if weighted:
            rows = (jnp.where(placed[:, None], rows, 0).astype(jnp.float32)
                    * weight[:, None])
        return expert._move_rows(rows, token, placed, walk, n, False,
                                 xla).astype(dtype)

    cotangent = jax.random.normal(jax.random.key(9), (n, 24), dtype)
    for fn in (kernel, scatter_add):
        assert fn(rows, weight).dtype == dtype
    got, want = (jax.jit(fn)(rows, weight) for fn in (kernel, scatter_add))
    assert np.isfinite(np.asarray(got, np.float32)).all()
    tol = 1e-6 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    grads = [jax.jit(jax.grad(
        lambda r, w, fn=fn: (fn(r, w).astype(jnp.float32)
                             * cotangent.astype(jnp.float32)).sum(),
        argnums=(0, 1)))(rows, weight) for fn in (kernel, scatter_add)]
    for a, b in zip(*grads):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol)
    # the walk: visits in order of block, at least one a block
    v_block, _, first, end, n_visits = (np.asarray(a) for a in walk)
    assert set(v_block[:n_visits]) == set(range(plan.blocks))
    assert (np.diff(v_block[:n_visits]) >= 0).all()
    assert (end - first)[:n_visits].sum() == int(placed.sum())
    assert n_visits <= plan.max_visits


@pytest.mark.parametrize("block, window", [(None, None), (64, 16)])
@pytest.mark.parametrize("form", ["reglu", "gated", "relu2"])
def test_the_lower_rung_sums_by_the_row_kernel(form, block, window,
                                               monkeypatch):
    """The whole layer on a held quarter's lower rung with the kernels
    forced (interpreted), against ``ragged_dot`` and its scatter-add:
    the row kernel runs in the program, and output and every gradient
    agree, at the planned blocks (one block here) and at blocks of 64
    tokens in windows of 16 rows."""
    from theanompi_tpu.ops import expert_rows

    if block:
        monkeypatch.setattr(expert_rows, "BLOCK", block)
        monkeypatch.setattr(expert_rows, "WINDOW", window)
    u, scores, experts = _forced_layer("quarter_few", form)

    def run(impl):
        return _loss(lambda u, s, p: routed_experts(
            u, s, p, (0, 4), top_k=6, normalize=True, scale=2.5, impl=impl,
            activation=_ACTIVATIONS[form], name="smallthinker_experts"))

    text = str(jax.make_jaxpr(run("pallas"))(u, scores, experts))
    assert "smallthinker_experts_rows" in text
    assert "smallthinker_experts_rows" not in str(
        jax.make_jaxpr(run("ragged_dot"))(u, scores, experts))
    (_, (got, stats)), got_grads = run("pallas")(u, scores, experts)
    (_, (want, _)), want_grads = run("ragged_dot")(u, scores, experts)
    assert stats["buffer_rows"] == 1792
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(b).max() + 1))


@pytest.mark.parametrize("n, rows, count, plan", [
    # the three cells whose lower rung sums from the buffer's side
    (16384, 51200, 16, "blocks of 1024 tokens, windows of 128 rows, at "
                       "most 928 visits"),
    (8192, 14336, 32, "blocks of 1024 tokens, windows of 128 rows, at "
                      "most 632 visits"),
    (8192, 7168, 8, "blocks of 1024 tokens, windows of 128 rows, at "
                    "most 192 visits"),
])
def test_the_row_plan_is_a_function_of_the_calls_shape(n, rows, count,
                                                        plan):
    from theanompi_tpu.ops import expert_rows
    got = expert_rows.row_plan(n, rows, count, "x")
    assert str(got) == (f"x: rung {rows} summed into {n} tokens by x_rows "
                        f"({plan})")
    assert not expert_rows.row_plan(n, rows, count, "x", False).pallas
    # a buffer whose tables would not fit SMEM keeps the scatter-add
    assert not expert_rows.row_plan(n, 65536 + rows, count, "x").pallas


def test_the_row_plan_is_said_beside_the_ladder(caplog):
    from theanompi_tpu.parallel import expert
    expert._log_buffer_plan.cache_clear()
    u, scores, experts = _forced_layer("few", "relu2")
    with caplog.at_level("INFO", logger=expert.__name__):
        for _ in range(2):
            routed_experts(u, scores, experts, (0, 8), top_k=6,
                           impl="pallas", name="nemotron_h_experts")
    said = [r.getMessage() for r in caplog.records]
    assert said == ["nemotron_h_experts: expert buffer: rungs 1280 / 2944 "
                    "of 128-row tiles",
                    "nemotron_h_experts: rung 1280 summed into 300 tokens "
                    "by nemotron_h_experts_rows (blocks of 304 tokens, "
                    "windows of 128 rows, at most 27 visits)"]
