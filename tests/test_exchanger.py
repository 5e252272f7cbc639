"""Exchange semantics: psum-of-grads equals sum of per-shard grads,
avg flag, bf16 strategy, async merge arithmetic closed forms."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from theanompi_tpu.parallel import (
    AXIS_DATA,
    BSP_Exchanger,
    asgd_apply_grads,
    easgd_both_updates,
    easgd_center_update,
    easgd_worker_update,
    gosgd_merge,
)

from tests._hlo_dataflow import ancestors


def _run_exchange(mesh, exchanger, tree):
    f = jax.jit(jax.shard_map(
        exchanger.exchange,
        mesh=mesh,
        in_specs=P(AXIS_DATA),
        out_specs=P(AXIS_DATA),
        check_vma=False,
    ))
    return f(tree)


def test_psum_sum_of_shards(mesh8):
    x = np.arange(8 * 3, dtype=np.float32).reshape(8, 3)
    ex = BSP_Exchanger(strategy="ar", avg=False)
    out = np.asarray(_run_exchange(mesh8, ex, x))
    expected = np.tile(x.sum(axis=0), (8, 1))
    np.testing.assert_allclose(out, expected, rtol=1e-6)


def test_psum_avg(mesh8):
    x = np.arange(8 * 4, dtype=np.float32).reshape(8, 4)
    ex = BSP_Exchanger(strategy="nccl32", avg=True)
    out = np.asarray(_run_exchange(mesh8, ex, x))
    expected = np.tile(x.mean(axis=0), (8, 1))
    np.testing.assert_allclose(out, expected, rtol=1e-6)


def test_bf16_strategy_close_to_fp32(mesh8):
    rng = np.random.RandomState(0)
    x = rng.randn(8, 16).astype(np.float32)
    ex16 = BSP_Exchanger(strategy="nccl16", avg=True)
    out = np.asarray(_run_exchange(mesh8, ex16, x))
    expected = np.tile(x.mean(axis=0), (8, 1))
    # bf16 mantissa is 8 bits -> ~1e-2 relative tolerance
    np.testing.assert_allclose(out, expected, rtol=0.05, atol=0.05)
    assert out.dtype == np.float32  # cast back to original dtype


def test_exchange_dtype_bf16_matches_f32_within_tolerance(mesh8):
    """ISSUE 5 equivalence pin: the modern ``exchange_dtype='bf16'``
    spelling quantizes to bfloat16 for the psum and restores f32 for
    the average — the result must match the f32 exchange within bf16's
    8-bit mantissa (documented tolerance: rel 2^-7 after the
    sum-of-8)."""
    rng = np.random.RandomState(3)
    x = rng.randn(8, 64).astype(np.float32)
    ex_bf = BSP_Exchanger(exchange_dtype="bf16", avg=True)
    ex_f32 = BSP_Exchanger(exchange_dtype="f32", avg=True)
    assert ex_bf.wire_dtype == "bf16" and ex_bf.resolved == "psum_bf16"
    assert ex_f32.wire_dtype == "f32" and ex_f32.resolved == "psum"
    out_bf = np.asarray(_run_exchange(mesh8, ex_bf, x))
    out_f = np.asarray(_run_exchange(mesh8, ex_f32, x))
    assert out_bf.dtype == np.float32  # f32 accumulation downstream
    np.testing.assert_allclose(out_bf, out_f, rtol=2 ** -7, atol=2 ** -7)


def test_exchange_dtype_and_error_feedback_validation():
    with pytest.raises(ValueError, match="exchange_dtype"):
        BSP_Exchanger(exchange_dtype="f16")
    # error feedback compensates bf16 quantization — f32 has none
    with pytest.raises(ValueError, match="bf16"):
        BSP_Exchanger(error_feedback=True)
    with pytest.raises(ValueError, match="params"):
        BSP_Exchanger(exchange_dtype="bf16", error_feedback=True,
                      exchange_what="params")
    # the reference-era strategy spelling counts as the bf16 wire
    BSP_Exchanger(strategy="nccl16", error_feedback=True)
    ex = BSP_Exchanger(exchange_dtype="bf16")
    with pytest.raises(ValueError, match="error_feedback"):
        ex.exchange_with_residual({}, {})


def test_error_feedback_long_run_gradient_sum(mesh8):
    """The ISSUE 5 acceptance pin: with error feedback, the CUMULATIVE
    applied gradient tracks the cumulative true f32 mean to within one
    bf16 quantization step — the error does NOT grow with the number
    of exchanges — while plain bf16 quantization drifts O(K) on
    below-resolution gradient components."""
    from jax.sharding import PartitionSpec

    K = 200
    # per-shard gradient with a component bf16 cannot resolve: 1.0 +
    # eps where eps << 2^-9 never survives Q(1+eps) -> 1.0, so the
    # naive wire silently drops K*eps; the residual must carry it
    eps = np.arange(1, 9, dtype=np.float32)[:, None] * 2e-4
    g = np.ones((8, 16), np.float32) + eps
    true_mean = g.mean(axis=0)

    ex = BSP_Exchanger(exchange_dtype="bf16", error_feedback=True,
                       avg=True)
    step = jax.jit(jax.shard_map(
        ex.exchange_with_residual, mesh=mesh8,
        in_specs=(PartitionSpec(AXIS_DATA), PartitionSpec(AXIS_DATA)),
        out_specs=(PartitionSpec(AXIS_DATA), PartitionSpec(AXIS_DATA)),
        check_vma=False))

    residual = np.zeros_like(g)
    applied = np.zeros((16,), np.float64)
    naive = np.zeros((16,), np.float64)
    for _ in range(K):
        out, residual = step(g, residual)
        applied += np.asarray(out)[0]
        naive += np.asarray(
            jnp.mean(g.astype(jnp.bfloat16).astype(jnp.float32), axis=0))
    target = true_mean.astype(np.float64) * K
    ef_err = np.abs(applied - target).max()
    naive_err = np.abs(naive - target).max()
    # cumulative applied = K*true - mean(r_K) exactly (telescoping sum
    # with f32 accumulation via _bf16_sum), so the error is bounded by
    # ONE bf16 quantization step of the ~1.0 payload (2^-8 ~ 0.004),
    # independent of K (measured 0.0013 at K=200)
    assert ef_err < 4e-3, ef_err
    # the naive wire silently dropped ~K*eps — two orders worse
    assert naive_err > 0.1 and naive_err > 50 * ef_err, (naive_err, ef_err)
    # the residual is live state, not zeros: it holds what the wire
    # hasn't emitted yet
    assert np.abs(np.asarray(residual)).max() > 0


def test_bsp_train_step_bf16_exchange_matches_f32(mesh8):
    """Full BSP train-step equivalence (acceptance criterion): 3 steps
    with the bf16 gradient exchange land within documented tolerance
    of 3 f32 steps, and the error-feedback variant threads its
    residual through ``TrainState.exchange_residual``."""
    import optax

    from theanompi_tpu.parallel.bsp import (
        TrainState,
        init_exchange_residual,
        make_bsp_train_step,
    )

    def loss(params, model_state, batch, rng):
        x, y = batch
        pred = jnp.tanh(x @ params["w1"]) @ params["w2"]
        l = jnp.mean((pred - y) ** 2)
        return l, (model_state, {"loss": l, "error": l})

    k1, k2 = jax.random.split(jax.random.key(0))
    params = {"w1": jax.random.normal(k1, (6, 9)),
              "w2": jax.random.normal(k2, (9, 2))}
    tx = optax.sgd(0.05, momentum=0.9)
    rng_np = np.random.default_rng(5)
    x = rng_np.standard_normal((32, 6)).astype(np.float32)
    y = rng_np.standard_normal((32, 2)).astype(np.float32)
    rng = jax.random.key(1)

    from theanompi_tpu.parallel.mesh import shard_batch
    batch = shard_batch((x, y), mesh8)

    def run(exchanger, residual=None):
        step = make_bsp_train_step(loss, tx, mesh8, exchanger,
                                   donate=False)
        s = TrainState.create(params, tx)
        if residual is not None:
            s = s.replace(exchange_residual=residual)
        for _ in range(3):
            s, m = step(s, batch, rng)
        return s, m

    s_f32, m_f32 = run(BSP_Exchanger(avg=True))
    s_bf16, m_bf16 = run(BSP_Exchanger(exchange_dtype="bf16", avg=True))
    s_ef, _ = run(BSP_Exchanger(exchange_dtype="bf16",
                                error_feedback=True, avg=True),
                  residual=init_exchange_residual(params, 8))
    for name, s_q in (("bf16", s_bf16), ("bf16+ef", s_ef)):
        for a, b in zip(jax.tree.leaves(s_f32.params),
                        jax.tree.leaves(s_q.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0.02, atol=2e-3,
                                       err_msg=name)
    assert float(m_bf16["loss"]) == pytest.approx(float(m_f32["loss"]),
                                                  rel=0.02)
    # the EF run's residual came back per-shard and non-degenerate
    res_leaves = jax.tree.leaves(s_ef.exchange_residual)
    assert res_leaves and all(l.shape[0] == 8 for l in res_leaves)
    # missing residual state fails loudly, not silently uncompensated
    with pytest.raises(ValueError, match="exchange_residual"):
        run(BSP_Exchanger(exchange_dtype="bf16", error_feedback=True,
                          avg=True))


def test_pytree_exchange(mesh8):
    tree = {
        "w": np.ones((8, 2, 2), np.float32),
        "b": np.full((8, 5), 2.0, np.float32),
    }
    ex = BSP_Exchanger(avg=True)
    out = _run_exchange(mesh8, ex, tree)
    np.testing.assert_allclose(np.asarray(out["w"]), 1.0)
    np.testing.assert_allclose(np.asarray(out["b"]), 2.0)


def test_strategy_aliases():
    for name in ("ar", "asa32", "asa16", "copper", "nccl32", "nccl16"):
        BSP_Exchanger(strategy=name)
    with pytest.raises(ValueError):
        BSP_Exchanger(strategy="bogus")


def test_easgd_closed_form():
    alpha = 0.5
    # note: first args of the update fns are donated — use fresh trees
    new_w = easgd_worker_update({"a": jnp.array([1.0, 2.0])},
                                {"a": jnp.array([0.0, 0.0])}, alpha)
    new_c = easgd_center_update({"a": jnp.array([0.0, 0.0])},
                                {"a": jnp.array([1.0, 2.0])}, alpha)
    np.testing.assert_allclose(np.asarray(new_w["a"]), [0.5, 1.0])
    np.testing.assert_allclose(np.asarray(new_c["a"]), [0.5, 1.0])
    # fused variant matches the two-call form
    w2, c2 = easgd_both_updates({"a": jnp.array([1.0, 2.0])},
                                {"a": jnp.array([0.0, 0.0])}, alpha)
    np.testing.assert_allclose(np.asarray(w2["a"]), [0.5, 1.0])
    np.testing.assert_allclose(np.asarray(c2["a"]), [0.5, 1.0])


def test_asgd_apply():
    c = {"a": jnp.array([1.0])}
    g = {"a": jnp.array([2.0])}
    out = asgd_apply_grads(c, g, 0.1)
    np.testing.assert_allclose(np.asarray(out["a"]), [0.8])


def test_gosgd_merge_weighted_avg():
    own = {"a": jnp.array([0.0])}
    recv = {"a": jnp.array([1.0])}
    merged, w = gosgd_merge(own, 1.0, recv, 3.0)
    np.testing.assert_allclose(np.asarray(merged["a"]), [0.75])
    assert float(w) == 4.0


def _named_leaves(state):
    from jax import tree_util as jtu

    return {jtu.keystr(path): leaf
            for path, leaf in jtu.tree_flatten_with_path(state)[0]}


def _has_field(key: str, name: str) -> bool:
    import re

    return re.search(rf"(?<![A-Za-z_]){name}(?![A-Za-z_])", key) is not None


def test_gosgd_scale_momentum_first_moments_only():
    """Merge-time momentum scaling (the measured stale-momentum
    divergence fix, docs/SCALING.md): FIRST-moment slots (adam mu)
    scale by the receiver's share; second moments (nu), counts and
    hyperparams are kept — shrinking nu with a stale bias-correction
    count would inflate the next preconditioned step."""
    import optax

    from theanompi_tpu.parallel import gosgd_scale_momentum

    params = {"w": jnp.ones(4), "b": jnp.ones(2)}
    tx = optax.adamw(1e-3)
    state = tx.init(params)
    g = jax.tree.map(jnp.ones_like, params)
    _, state = tx.update(g, state, params)

    before = _named_leaves(state)
    after = _named_leaves(gosgd_scale_momentum(state, 0.25))
    assert before.keys() == after.keys()
    n_mu = n_kept = 0
    for key, v in before.items():
        if _has_field(key, "mu"):
            np.testing.assert_allclose(np.asarray(after[key]),
                                       0.25 * np.asarray(v), rtol=1e-6)
            n_mu += 1
        else:  # nu, count
            np.testing.assert_allclose(np.asarray(after[key]),
                                       np.asarray(v))
            n_kept += 1
    assert n_mu >= 2 and n_kept >= 3  # mu x2 leaves; nu x2 + count


def test_gosgd_scale_momentum_through_build_optimizer():
    """The PRODUCTION optimizer shape — inject_hyperparams(chain(...))
    from build_optimizer — must scale its trace/mu and keep nu, count,
    and the injected learning_rate."""
    from theanompi_tpu.parallel import gosgd_scale_momentum
    from theanompi_tpu.utils.helper_funcs import build_optimizer

    params = {"w": jnp.ones(3)}
    for opt, first, kept in [
        ("sgd", "trace", "learning_rate"),
        ("adamw", "mu", "nu"),
    ]:
        tx = build_optimizer(0.1, optimizer=opt, momentum=0.9,
                             weight_decay=1e-4)
        state = tx.init(params)
        _, state = tx.update({"w": jnp.ones(3)}, state, params)
        before = _named_leaves(state)
        after = _named_leaves(gosgd_scale_momentum(state, 0.5))
        f_keys = [k for k in before if _has_field(k, first)]
        k_keys = [k for k in before if _has_field(k, kept)]
        assert f_keys and k_keys, (opt, sorted(before))
        for k in f_keys:
            np.testing.assert_allclose(np.asarray(after[k]),
                                       0.5 * np.asarray(before[k]),
                                       rtol=1e-6)
        for k in k_keys:
            np.testing.assert_allclose(np.asarray(after[k]),
                                       np.asarray(before[k]))


def test_gosgd_dominant_push_resets_momentum():
    """A push whose weight dwarfs the receiver's must effectively reset
    the receiver's momentum (share -> 0), so the next SGD step is a
    plain gradient at the teleported point rather than a stale kick."""
    import optax

    from theanompi_tpu.parallel import gosgd_merge, gosgd_scale_momentum

    tx = optax.sgd(0.1, momentum=0.9)
    params = {"w": jnp.zeros(3)}
    state = tx.init(params)
    _, state = tx.update({"w": jnp.ones(3)}, state, params)

    own_w, recv_w = 1e-6, 0.5
    _, new_w = gosgd_merge(params, own_w, {"w": jnp.ones(3)}, recv_w)
    scaled = gosgd_scale_momentum(state, own_w / float(new_w))
    mom = [v for k, v in _named_leaves(scaled).items()
           if _has_field(k, "trace")]
    assert mom and float(jnp.abs(mom[0]).max()) < 1e-5


# ---------------------------------------------------------------------------
# Bucketed exchange (ISSUE 13): layer-ordered byte-balanced buckets,
# collectives embedded in the backward DAG, B-count equivalence pins.
# ---------------------------------------------------------------------------


class TestBucketPlan:
    def test_plan_pure_balanced_contiguous(self):
        from theanompi_tpu.parallel.exchanger import bucket_ranges

        sizes = [4 * n for n in (7, 7, 3, 64, 64, 64, 64, 1, 4096, 10)]
        for B in (1, 2, 4, 8):
            plan = bucket_ranges(sizes, B)
            # purity: identical on a second derivation (every rank
            # computes its own plan — no plan ever travels on a wire)
            assert plan == bucket_ranges(list(sizes), B)
            # contiguity + full cover, in order (layer order IS
            # flatten order)
            assert plan[0][0] == 0 and plan[-1][1] == len(sizes)
            for (_, hi), (lo2, _) in zip(plan, plan[1:]):
                assert hi == lo2
            assert all(hi > lo for lo, hi in plan)
            # byte balance: the greedy quantile walk never exceeds a
            # quantile target by more than one leaf
            per = [sum(sizes[lo:hi]) for lo, hi in plan]
            assert max(per) <= sum(sizes) / len(plan) + max(sizes)

    def test_plan_clamps_beyond_leaf_count(self):
        from theanompi_tpu.parallel.exchanger import bucket_ranges

        # a bucket plan is a scheduling hint: B > n_leaves degrades to
        # per-leaf buckets instead of raising like the shard plan
        assert bucket_ranges([8, 8, 8], 64) == [(0, 1), (1, 2), (2, 3)]

    def test_plan_shares_the_shard_partition_walk(self):
        from theanompi_tpu.parallel.exchanger import bucket_ranges
        from theanompi_tpu.parallel.shards import partition_ranges

        sizes = [3, 100, 7, 42, 42, 9, 512, 1]
        for k in (1, 2, 4):
            assert bucket_ranges(sizes, k) == partition_ranges(sizes, k)

    def test_exchanger_validates_bucket_count(self):
        for bad in (0, -1, 1.5, True):
            with pytest.raises(ValueError, match="exchange_buckets"):
                BSP_Exchanger(exchange_buckets=bad)


class TestBucketedPostHocExchange:
    """exchange()/exchange_with_residual() with B>1 regroup the
    per-leaf collectives into per-bucket flat ones — elementwise
    identical (no per-element sum moves)."""

    def test_exchange_bit_identical_across_bucket_counts(self, mesh8):
        rng = np.random.RandomState(7)
        tree = {f"l{i:02d}": rng.randn(8, 3 + i).astype(np.float32)
                for i in range(6)}
        for dtype in (None, "bf16"):
            ref = _run_exchange(mesh8,
                                BSP_Exchanger(exchange_dtype=dtype,
                                              avg=True), tree)
            for B in (2, 4, 8):
                out = _run_exchange(
                    mesh8, BSP_Exchanger(exchange_dtype=dtype, avg=True,
                                         exchange_buckets=B), tree)
                for a, b in zip(jax.tree.leaves(ref),
                                jax.tree.leaves(out)):
                    np.testing.assert_array_equal(np.asarray(a),
                                                  np.asarray(b))

    def test_exchange_with_residual_bucketed_identical(self, mesh8):
        from jax.sharding import PartitionSpec

        rng = np.random.RandomState(9)
        tree = {f"l{i}": rng.randn(8, 16).astype(np.float32)
                for i in range(4)}
        res = jax.tree.map(lambda x: np.zeros_like(x), tree)

        def run(B):
            ex = BSP_Exchanger(exchange_dtype="bf16",
                               error_feedback=True, avg=True,
                               exchange_buckets=B)
            f = jax.jit(jax.shard_map(
                ex.exchange_with_residual, mesh=mesh8,
                in_specs=(PartitionSpec(AXIS_DATA),) * 2,
                out_specs=(PartitionSpec(AXIS_DATA),) * 2,
                check_vma=False))
            return f(tree, res)

        out1, res1 = run(1)
        for B in (2, 4):
            outB, resB = run(B)
            for a, b in zip(jax.tree.leaves(out1), jax.tree.leaves(outB)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree.leaves(res1), jax.tree.leaves(resB)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _bucket_loss(params, model_state, batch, rng):
    x, y = batch
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    pred = h @ params["w2"] + params["b2"]
    loss = jnp.mean((pred - y) ** 2)
    return loss, (model_state, {"loss": loss, "error": loss})


def _bucket_params():
    k = jax.random.split(jax.random.key(3), 2)
    return {"w1": jax.random.normal(k[0], (6, 9)) * 0.3,
            "b1": jnp.zeros(9),
            "w2": jax.random.normal(k[1], (9, 2)) * 0.3,
            "b2": jnp.zeros(2)}


def _bucket_batch(mesh8):
    from theanompi_tpu.parallel.mesh import shard_batch

    rng_np = np.random.default_rng(5)
    x = rng_np.standard_normal((32, 6)).astype(np.float32)
    y = rng_np.standard_normal((32, 2)).astype(np.float32)
    return shard_batch((x, y), mesh8)


class TestBucketedTrainStep:
    """The acceptance pins: B>1 equal to B=1 at EVERY step, plain and
    error-feedback variants, with the collectives embedded in the
    backward (HLO pin below).

    The bits are pinned under PLAIN SGD.  What the bucket count changes
    is the exchange, and that is bit-identical: with `p - lr*g` every
    step of every variant has the same bits at every bucket count.
    With momentum the B=1 and B>1 programs part by one unit in the last
    place from the first step whose trace is non-zero (never at step 1,
    where `0.9*0 + g` is exact): XLA's CPU backend (JAX 0.9.0) fuses
    the optimizer's `0.9*m + g` into different loops in the two
    programs and contracts it to a fused multiply-add in one of them.
    That rounding is the compiler's, not the exchange's, so momentum is
    held to a tolerance (measured: at most 6 ulp, 6.1e-7 relative, over
    3 steps) and to the same bits run to run at one bucket count."""

    def _run(self, mesh8, B, dtype=None, ef=False, steps=3,
             momentum=None):
        import optax

        from theanompi_tpu.parallel.bsp import (
            TrainState,
            init_exchange_residual,
            make_bsp_train_step,
        )

        params = _bucket_params()
        tx = optax.sgd(0.05, momentum=momentum)
        ex = BSP_Exchanger(exchange_dtype=dtype, error_feedback=ef,
                           exchange_buckets=B, avg=True)
        step = make_bsp_train_step(_bucket_loss, tx, mesh8, ex,
                                   donate=False)
        s = TrainState.create(params, tx)
        if ef:
            s = s.replace(
                exchange_residual=init_exchange_residual(params, 8))
        batch = _bucket_batch(mesh8)
        rng = jax.random.key(1)
        traj = []
        for _ in range(steps):
            s, m = step(s, batch, rng)
            traj.append(jax.tree.map(np.asarray, s.params))
        return s, m, traj

    @pytest.mark.parametrize("dtype,ef", [(None, False), ("bf16", False),
                                          ("bf16", True)])
    def test_bucketed_step_bit_identical_per_step(self, mesh8, dtype, ef):
        s1, m1, traj1 = self._run(mesh8, 1, dtype, ef)
        for B in (2, 4, 8):
            sB, mB, trajB = self._run(mesh8, B, dtype, ef)
            for t1, tB in zip(traj1, trajB):  # EVERY step, not just last
                for a, b in zip(jax.tree.leaves(t1), jax.tree.leaves(tB)):
                    np.testing.assert_array_equal(a, b, err_msg=f"B={B}")
            assert float(m1["loss"]) == float(mB["loss"])
            if ef:
                for a, b in zip(jax.tree.leaves(s1.exchange_residual),
                                jax.tree.leaves(sB.exchange_residual)):
                    np.testing.assert_array_equal(np.asarray(a),
                                                  np.asarray(b))
        # momentum: the same bits run to run, a few ulp across B
        _, _, mom4 = self._run(mesh8, 4, dtype, ef, momentum=0.9)
        _, _, again = self._run(mesh8, 4, dtype, ef, momentum=0.9)
        _, _, mom1 = self._run(mesh8, 1, dtype, ef, momentum=0.9)
        for t4, ta, t1 in zip(mom4, again, mom1):
            for a, b, c in zip(*map(jax.tree.leaves, (t4, ta, t1))):
                np.testing.assert_array_equal(a, b)
                np.testing.assert_allclose(a, c, rtol=5e-6, atol=1e-7)

    def test_bucketed_cadences_bit_identical(self, mesh8):
        import optax
        from jax.sharding import PartitionSpec as P

        from theanompi_tpu.parallel.bsp import (
            TrainState,
            make_bsp_accum_step,
            make_bsp_multi_step,
        )
        from theanompi_tpu.parallel.mesh import shard_batch

        params = _bucket_params()
        tx = optax.sgd(0.05)  # plain: see the class docstring
        rng_np = np.random.default_rng(6)
        xs = rng_np.standard_normal((2, 32, 6)).astype(np.float32)
        ys = rng_np.standard_normal((2, 32, 2)).astype(np.float32)
        stacked = shard_batch((xs, ys), mesh8, spec=P(None, "data"))
        for maker in (make_bsp_multi_step, make_bsp_accum_step):
            outs = {}
            for B in (1, 4):
                ex = BSP_Exchanger(exchange_buckets=B, avg=True)
                step = maker(_bucket_loss, tx, mesh8, ex, donate=False)
                s = TrainState.create(params, tx)
                s, _ = step(s, stacked, jax.random.key(2))
                outs[B] = s
            for a, b in zip(jax.tree.leaves(outs[1].params),
                            jax.tree.leaves(outs[4].params)):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b),
                                              err_msg=maker.__name__)

    def test_backward_exchange_rejects_params_mode(self):
        ex = BSP_Exchanger(exchange_what="params", exchange_buckets=2)
        with pytest.raises(ValueError, match="backward"):
            ex.backward_exchange(_bucket_loss, {}, {}, None, None)

    def test_bucketed_ef_requires_residual_state(self, mesh8):
        import optax

        from theanompi_tpu.parallel.bsp import (
            TrainState,
            make_bsp_train_step,
        )

        ex = BSP_Exchanger(exchange_dtype="bf16", error_feedback=True,
                           exchange_buckets=4, avg=True)
        step = make_bsp_train_step(_bucket_loss, optax.sgd(0.05), mesh8,
                                   ex, donate=False)
        s = TrainState.create(_bucket_params(), optax.sgd(0.05))
        with pytest.raises(ValueError, match="exchange_residual"):
            step(s, _bucket_batch(mesh8), jax.random.key(0))


class TestBucketedHloInterleaving:
    """The structural acceptance pin: the bucketed program carries B
    bucket all-reduces, and the first of them depends on its own
    bucket's cotangents only, so it can run while the rest of the
    backward is still computing; the B=1 program keeps one trailing
    collective block after every backward dot.  (Until PR 25 the pin
    read the ORDER OF LINES in the lowering, which under JAX 0.9.0 puts
    every bucket's collective after the last backward dot:
    tests/_hlo_dataflow.py.)"""

    def _lowered(self, mesh8, B):
        import optax

        from theanompi_tpu.parallel.bsp import (
            TrainState,
            make_bsp_train_step,
        )

        params = _bucket_params()
        tx = optax.sgd(0.05, momentum=0.9)
        ex = BSP_Exchanger(exchange_buckets=B, avg=True)
        step = make_bsp_train_step(_bucket_loss, tx, mesh8, ex,
                                   donate=False)
        s = TrainState.create(params, tx)
        return step.lower(s, _bucket_batch(mesh8),
                          jax.random.key(0)).as_text()

    @staticmethod
    def _layout(txt):
        lines = txt.splitlines()
        ar = [i for i, l in enumerate(lines)
              if "stablehlo.all_reduce" in l]
        dots = [i for i, l in enumerate(lines)
                if "stablehlo.dot_general" in l]
        return ar, dots

    def test_bucket_collective_count_and_interleave(self, mesh8):
        n_leaves = len(jax.tree.leaves(_bucket_params()))
        ar1, dots1 = self._layout(self._lowered(mesh8, 1))
        # B=1: one psum per leaf (+ the metric pmeans) — ALL of them
        # after the last backward dot: one trailing collective block
        metric_ars = len(ar1) - n_leaves
        assert metric_ars >= 0
        assert not [d for d in dots1 if d > ar1[0]], \
            "B=1 lowering has backward compute after a collective"
        for B in (2, 4):
            txt = self._lowered(mesh8, B)
            arB, dotsB = self._layout(txt)
            # exactly B bucket collectives (each bucket's leaves are
            # flattened into ONE all-reduce) + the metric pmeans
            assert len(arB) == B + metric_ars, (B, len(arB), metric_ars)
            # interleaving: some backward dot is NOT an input of the
            # first bucket collective — the exchange may overlap the
            # remaining backward
            waits_for = ancestors(txt, arB[0])
            assert [d for d in dotsB if d not in waits_for], \
                f"B={B}: the first bucket collective depends on every " \
                "backward dot"

    def test_bucket_gauges_emitted_at_trace_time(self, mesh8, tmp_path):
        import json

        import optax

        from theanompi_tpu import monitor
        from theanompi_tpu.parallel.bsp import (
            TrainState,
            make_bsp_train_step,
        )

        with monitor.session(run_dir=str(tmp_path)):
            ex = BSP_Exchanger(exchange_buckets=4, avg=True)
            step = make_bsp_train_step(_bucket_loss,
                                       optax.sgd(0.05, momentum=0.9),
                                       mesh8, ex, donate=False)
            s = TrainState.create(_bucket_params(),
                                  optax.sgd(0.05, momentum=0.9))
            s, _ = step(s, _bucket_batch(mesh8), jax.random.key(0))
            monitor.flush()
        recs = [json.loads(l) for l in
                open(tmp_path / "metrics_rank0.jsonl")]
        by = {}
        for r in recs:
            by.setdefault(r["name"], []).append(r)
        (bk,) = [r for r in by["bsp/exchange_buckets"]
                 if r["labels"].get("plane") == "bsp"]
        assert bk["value"] == 4
        buckets = {r["labels"]["bucket"]
                   for r in by["bsp/exchange_bucket_bytes"]}
        assert buckets == {"0", "1", "2", "3"}
        total = sum(r["value"] for r in by["bsp/exchange_bucket_bytes"])
        n_param_bytes = sum(l.size * 4 for l in
                            jax.tree.leaves(_bucket_params()))
        assert total == n_param_bytes
