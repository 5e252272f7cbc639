"""ZeRO-1 optimizer-state sharding (parallel/zero.py +
ModelConfig.zero_sharding): reduce_scatter/update-shard/all_gather,
step-equal to plain BSP, state physically sharded."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.models.base import ModelConfig
from theanompi_tpu.parallel.bsp import TrainState, make_bsp_train_step
from theanompi_tpu.parallel.mesh import AXIS_DATA, data_mesh, shard_batch
from theanompi_tpu.parallel.zero import (
    init_zero_opt_state,
    make_bsp_zero_step,
)
from theanompi_tpu.utils.helper_funcs import (
    build_optimizer,
    get_learning_rate,
    set_learning_rate,
)
from theanompi_tpu.utils.recorder import Recorder


def _loss(params, model_state, batch, rng):
    x, y = batch
    pred = jnp.tanh(x @ params["w1"]) @ params["w2"] + params["b"]
    loss = jnp.mean((pred - y) ** 2)
    return loss, (model_state, {"loss": loss, "error": loss})


def _params():
    k = jax.random.key(0)
    k1, k2 = jax.random.split(k)
    # deliberately not divisible by 8 so the pad path is exercised
    return {"w1": jax.random.normal(k1, (5, 7)),
            "w2": jax.random.normal(k2, (7, 3)),
            "b": jnp.zeros((3,))}


@pytest.mark.parametrize("opt", ["sgd", "adamw"])
def test_zero_step_equals_plain_bsp(mesh8, opt):
    """N steps of ZeRO == N steps of plain BSP (elementwise update is
    sharding-transparent), while opt state lives 1/8 per device."""
    tx = build_optimizer(0.05, optimizer=opt, momentum=0.9,
                         weight_decay=1e-4)
    params = _params()
    rng_np = np.random.default_rng(1)
    x = rng_np.standard_normal((32, 5)).astype(np.float32)
    y = rng_np.standard_normal((32, 3)).astype(np.float32)
    rng = jax.random.key(2)

    plain = make_bsp_train_step(_loss, tx, mesh8, donate=False)
    s_p = TrainState.create(params, tx)

    zero = make_bsp_zero_step(_loss, tx, mesh8, params, donate=False)
    opt0, specs = init_zero_opt_state(tx, params, mesh8)
    s_z = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                     opt_state=opt0, model_state={})

    batch = shard_batch((x, y), mesh8)
    for _ in range(3):
        s_p, m_p = plain(s_p, batch, rng)
        s_z, m_z = zero(s_z, batch, rng)
    for a, b in zip(jax.tree.leaves(s_p.params),
                    jax.tree.leaves(s_z.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)
    assert float(m_z["loss"]) == pytest.approx(float(m_p["loss"]),
                                               rel=1e-5)


def test_opt_state_physically_sharded(mesh8):
    tx = build_optimizer(0.1, optimizer="sgd", momentum=0.9)
    params = _params()
    opt0, specs = init_zero_opt_state(tx, params, mesh8)
    vec_leaves = [l for l in jax.tree.leaves(opt0)
                  if getattr(l, "ndim", 0) == 1 and l.size >= 8]
    assert vec_leaves, "expected momentum vector slots"
    for leaf in vec_leaves:
        # each device holds 1/8 of the padded flat vector
        shard_shapes = {s.data.shape for s in leaf.addressable_shards}
        assert shard_shapes == {(leaf.shape[0] // 8,)}, leaf.sharding
    # lr stays mutable through the sharded state (adjust_hyperp path)
    opt1 = set_learning_rate(opt0, 0.01)
    assert get_learning_rate(opt1) == pytest.approx(0.01)


def test_model_trains_with_zero_and_lr_schedule(mesh8, tmp_path):
    from tests._tiny_models import TinyCifar128

    cfg = ModelConfig(batch_size=4, n_epochs=1, learning_rate=0.02,
                      print_freq=0, zero_sharding=True,
                      lr_schedule="step", lr_decay_epochs=(1,),
                      snapshot_dir=str(tmp_path))
    m = TinyCifar128(config=cfg, mesh=mesh8, verbose=False)
    m.compile_iter_fns("avg")
    rec = Recorder(rank=0, size=8, print_freq=0)
    m.begin_epoch(0)
    for i in range(3):
        m.train_iter(i, rec)
    m._flush_metrics(rec)
    assert np.isfinite(rec.train_losses).all()
    assert m.adjust_hyperp(1) == pytest.approx(0.002)
    # the schedule's new lr feeds back through the sharded state
    m.train_iter(3, rec)
    m._flush_metrics(rec)
    assert np.isfinite(rec.train_losses).all()
    m.cleanup()


def test_zero_rejects_unsupported(mesh8):
    from tests._tiny_models import TinyCifar

    for bad, msg in [
        (dict(optimizer="lars"), "ELEMENTWISE"),
        (dict(exchange_what="params"), "IS the gradient exchange"),
    ]:
        cfg = ModelConfig(batch_size=4, print_freq=0, zero_sharding=True,
                          **bad)
        with pytest.raises(ValueError, match=msg):
            TinyCifar(config=cfg, mesh=mesh8, verbose=False)
    # the two stacked cadences never nest (same rule as plain BSP)
    cfg = ModelConfig(batch_size=4, print_freq=0, zero_sharding=True,
                      steps_per_call=2, grad_accum_steps=2)
    m = TinyCifar(config=cfg, mesh=mesh8, verbose=False)
    with pytest.raises(ValueError, match="stacked-batch cadences"):
        m.compile_iter_fns("avg")


def test_zero_multi_step_equals_singles(mesh8):
    """ZeRO x steps_per_call (round-3 completion of the cadence
    matrix): the scanned multi-step runs the FULL sharded step —
    reduce_scatter + shard update + all_gather — per sub-step, so its
    trajectory equals k single zero steps with rngs fold_in(rng, i)."""
    from jax.sharding import PartitionSpec as P

    tx = build_optimizer(0.05, optimizer="adamw", momentum=0.9,
                         weight_decay=1e-4)
    params = _params()
    rng = jax.random.key(7)
    k = 3
    rng_np = np.random.default_rng(3)
    xs = rng_np.standard_normal((k, 32, 5)).astype(np.float32)
    ys = rng_np.standard_normal((k, 32, 3)).astype(np.float32)

    multi = make_bsp_zero_step(_loss, tx, mesh8, params, donate=False,
                               multi=True)
    opt0, _ = init_zero_opt_state(tx, params, mesh8)
    s_m = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                     opt_state=opt0, model_state={})
    stacked = shard_batch((xs, ys), mesh8, spec=P(None, AXIS_DATA))
    s_m, metrics = multi(s_m, stacked, rng)
    assert np.asarray(metrics["loss"]).shape == (k,)

    single = make_bsp_zero_step(_loss, tx, mesh8, params, donate=False)
    opt0b, _ = init_zero_opt_state(tx, params, mesh8)
    s_s = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                     opt_state=opt0b, model_state={})
    losses = []
    for i in range(k):
        batch = shard_batch((xs[i], ys[i]), mesh8)
        s_s, m = single(s_s, batch, jax.random.fold_in(rng, i))
        losses.append(float(m["loss"]))
    np.testing.assert_allclose(np.asarray(metrics["loss"]), losses,
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s_m.params),
                    jax.tree.leaves(s_s.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)
    assert int(s_m.step) == k


def test_zero_stacked_cadence_donates_staged_batch(mesh8):
    """ISSUE 3 copy-done fix reaches the ZeRO cadences too: the
    multi-step lowering donates the two batch leaves on top of the
    state, and donate_batch=False withholds exactly those two."""
    from jax.sharding import PartitionSpec as P

    from tests.test_multi_step import _donated_inputs

    tx = build_optimizer(0.05, optimizer="sgd", momentum=0.9)
    params = _params()
    rng_np = np.random.default_rng(9)
    x = rng_np.standard_normal((2, 16, 5)).astype(np.float32)
    y = rng_np.standard_normal((2, 16, 3)).astype(np.float32)

    def donors(**kw):
        zm = make_bsp_zero_step(_loss, tx, mesh8, params, multi=True,
                                **kw)
        opt0, _ = init_zero_opt_state(tx, params, mesh8)
        s = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=opt0, model_state={})
        stacked = shard_batch((x, y), mesh8, spec=P(None, AXIS_DATA))
        return _donated_inputs(
            zm.lower(s, stacked, jax.random.key(0)).as_text())

    assert donors() == donors(donate_batch=False) + 2
    assert donors(donate=False) == 0


def test_zero_steps_per_call_model_glue(mesh8):
    """The model path (stacked host batches -> train_step_multi) works
    with a SHARDED optimizer state."""
    from tests._tiny_models import TinyCifar128
    from theanompi_tpu.utils.recorder import Recorder

    cfg = ModelConfig(batch_size=4, print_freq=0, zero_sharding=True,
                      steps_per_call=2, n_epochs=1)
    m = TinyCifar128(config=cfg, mesh=mesh8, verbose=False)
    m.compile_iter_fns("avg")
    rec = Recorder(rank=0, size=8, print_freq=0)
    n = m.begin_epoch(0)
    it = 0
    while it < n:
        it += m.train_iter(it, rec)
    m._flush_metrics(rec)
    assert it == n
    assert len(rec.train_losses) == n  # every sub-step recorded
    assert np.isfinite(rec.train_losses).all()
    m.cleanup()


def test_zero_rejects_bf16_strategy_and_variant_models(mesh8):
    from tests._tiny_models import TinyCifar
    from theanompi_tpu.models.transformer import TransformerLM_TP
    from theanompi_tpu.parallel.mesh import MeshSpec, make_training_mesh

    cfg = ModelConfig(batch_size=4, print_freq=0, zero_sharding=True,
                      exchange_strategy="nccl16")
    with pytest.raises(ValueError, match="exchange_dtype"):
        TinyCifar(config=cfg, mesh=mesh8, verbose=False)
    # ... and the modern spelling IS accepted: the reduce_scatter has a
    # quantization seam (see test_zero_bf16_* for the numerics)
    cfg_ok = ModelConfig(batch_size=4, print_freq=0, zero_sharding=True,
                         exchange_dtype="bf16")
    TinyCifar(config=cfg_ok, mesh=mesh8, verbose=False)

    mesh = make_training_mesh(MeshSpec(data=2, model=4),
                              jax.devices()[:8])
    cfg = ModelConfig(batch_size=4, print_freq=0, zero_sharding=True,
                      weight_decay=0.0)
    m = TransformerLM_TP(config=cfg, mesh=mesh, verbose=False,
                         n_layers=1, d_model=32, n_heads=4, seq_len=16)
    with pytest.raises(ValueError, match="zero_sharding is not"):
        m.compile_iter_fns("avg")


def _zero_state(params, tx, mesh, residual=None):
    opt0, _ = init_zero_opt_state(tx, params, mesh)
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      opt_state=opt0, model_state={},
                      exchange_residual=residual)


def test_zero_bf16_step_close_to_f32(mesh8):
    """ISSUE 5 equivalence pin, ZeRO flavor: the bf16-wire
    reduce-scatter (all_to_all of the quantized flat vector + local
    f32 accumulation) lands within bf16 tolerance of the f32 ZeRO
    step, for both the plain and the error-feedback variant."""
    from theanompi_tpu.parallel.zero import init_zero_exchange_residual

    tx = build_optimizer(0.05, optimizer="sgd", momentum=0.9)
    params = _params()
    rng_np = np.random.default_rng(11)
    x = rng_np.standard_normal((32, 5)).astype(np.float32)
    y = rng_np.standard_normal((32, 3)).astype(np.float32)
    batch = shard_batch((x, y), mesh8)
    rng = jax.random.key(3)

    def run(state, **kw):
        step = make_bsp_zero_step(_loss, tx, mesh8, params,
                                  donate=False, **kw)
        for _ in range(3):
            state, m = step(state, batch, rng)
        return state, m

    s_f, m_f = run(_zero_state(params, tx, mesh8))
    s_b, m_b = run(_zero_state(params, tx, mesh8),
                   exchange_dtype="bf16")
    s_e, _ = run(_zero_state(params, tx, mesh8,
                             init_zero_exchange_residual(params, mesh8)),
                 exchange_dtype="bf16", error_feedback=True)
    for name, s_q in (("bf16", s_b), ("bf16+ef", s_e)):
        for a, b in zip(jax.tree.leaves(s_f.params),
                        jax.tree.leaves(s_q.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0.02, atol=2e-3,
                                       err_msg=name)
    assert float(m_b["loss"]) == pytest.approx(float(m_f["loss"]),
                                               rel=0.02)
    # EF residual: per-data-shard rows of the padded flat vector, live
    res = s_e.exchange_residual
    assert res.shape[0] == 8 and np.abs(np.asarray(res)).max() > 0


def test_zero_bf16_validation(mesh8):
    tx = build_optimizer(0.05)
    with pytest.raises(ValueError, match="exchange_dtype"):
        make_bsp_zero_step(_loss, tx, mesh8, _params(),
                           exchange_dtype="f16")
    with pytest.raises(ValueError, match="bf16"):
        make_bsp_zero_step(_loss, tx, mesh8, _params(),
                           error_feedback=True)


def test_zero_bf16_model_glue(mesh8):
    """ModelConfig threading: zero_sharding + exchange_dtype='bf16' +
    error feedback builds, creates the sharded flat residual in
    TrainState, and trains finite."""
    from tests._tiny_models import TinyCifar128

    cfg = ModelConfig(batch_size=4, n_epochs=1, learning_rate=0.02,
                      print_freq=0, zero_sharding=True,
                      exchange_dtype="bf16",
                      exchange_error_feedback=True)
    m = TinyCifar128(config=cfg, mesh=mesh8, verbose=False)
    m.compile_iter_fns("avg")
    res = m.state.exchange_residual
    assert res is not None and res.ndim == 2 and res.shape[0] == 8
    from theanompi_tpu.utils.recorder import Recorder

    rec = Recorder(rank=0, size=8, print_freq=0)
    m.begin_epoch(0)
    for i in range(2):
        m.train_iter(i, rec)
    m._flush_metrics(rec)
    assert np.isfinite(rec.train_losses).all()
    # the residual is trained state now — it must have moved
    assert np.abs(np.asarray(m.state.exchange_residual)).max() > 0
    m.cleanup()


def test_zero_composes_with_sequence_parallel():
    """ZeRO over (data x seq): extra axes psum plainly, the data axis
    reduce_scatters — one step equals the plain SP step, with the
    optimizer state sharded over 'data' only."""
    from theanompi_tpu.models.transformer import TransformerLM
    from theanompi_tpu.parallel.mesh import MeshSpec, make_training_mesh
    from theanompi_tpu.utils.recorder import Recorder

    mesh = make_training_mesh(MeshSpec(data=2, seq=4), jax.devices()[:8])

    def make(zero):
        cfg = ModelConfig(batch_size=4, n_epochs=1, learning_rate=0.05,
                          print_freq=0, weight_decay=0.0, seed=7,
                          zero_sharding=zero)
        return TransformerLM(config=cfg, mesh=mesh, verbose=False,
                             n_layers=1, d_model=32, n_heads=4,
                             seq_len=32)

    losses = {}
    for zero in (False, True):
        m = make(zero)
        m.compile_iter_fns("avg")
        rec = Recorder(rank=0, size=8, print_freq=0)
        m.begin_epoch(0)
        for i in range(2):
            m.train_iter(i, rec)
        m._flush_metrics(rec)
        losses[zero] = list(np.asarray(rec.train_losses))
        if zero:
            vec = [l for l in jax.tree.leaves(m.state.opt_state)
                   if getattr(l, "ndim", 0) == 1 and l.size >= 8]
            assert vec, "momentum vector slots expected"
            # sharded over 'data' (2-way), replicated over 'seq'
            assert {s.data.shape for s in vec[0].addressable_shards} \
                == {(vec[0].shape[0] // 2,)}
        m.cleanup()
    np.testing.assert_allclose(losses[True], losses[False], rtol=2e-5,
                               atol=1e-6)


def test_zero_composes_with_grad_accum(mesh8, tmp_path):
    """ZeRO x grad-accum: a microbatches, one sharded update — equals
    the plain grad-accum step (which itself equals the big batch)."""
    from jax.sharding import PartitionSpec as P

    from theanompi_tpu.parallel.bsp import make_bsp_accum_step

    tx = build_optimizer(0.05, optimizer="sgd", momentum=0.9)
    params = _params()
    rng_np = np.random.default_rng(5)
    x = rng_np.standard_normal((64, 5)).astype(np.float32)
    y = rng_np.standard_normal((64, 3)).astype(np.float32)
    rng = jax.random.key(1)
    stacked = shard_batch((x.reshape(4, 16, 5), y.reshape(4, 16, 3)),
                          mesh8, spec=P(None, AXIS_DATA))

    plain = make_bsp_accum_step(_loss, tx, mesh8, donate=False)
    s_p, m_p = plain(TrainState.create(params, tx), stacked, rng)

    za = make_bsp_zero_step(_loss, tx, mesh8, params, donate=False,
                            accum=True)
    opt0, _ = init_zero_opt_state(tx, params, mesh8)
    s_z = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                     opt_state=opt0, model_state={})
    s_z, m_z = za(s_z, stacked, rng)

    for a, b in zip(jax.tree.leaves(s_p.params),
                    jax.tree.leaves(s_z.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)
    assert float(m_z["loss"]) == pytest.approx(float(m_p["loss"]),
                                               rel=1e-5)
    assert int(s_z.step) == 1

    # model plumbing: both knobs on -> accum dispatches, counts hold
    from tests._tiny_models import TinyCifar128
    from theanompi_tpu.utils.recorder import Recorder

    cfg = ModelConfig(batch_size=4, n_epochs=1, learning_rate=0.02,
                      print_freq=0, zero_sharding=True,
                      grad_accum_steps=4, snapshot_dir=str(tmp_path))
    m = TinyCifar128(config=cfg, mesh=mesh8, verbose=False)
    m.compile_iter_fns("avg")
    rec = Recorder(rank=0, size=8, print_freq=0)
    n_iters = m.begin_epoch(0)
    it = 0
    while it < n_iters:
        assert m.train_iter(it, rec) == 4
        it += 4
    m._flush_metrics(rec)
    assert int(m.state.step) == n_iters // 4
    assert np.isfinite(rec.train_losses).all()
    m.cleanup()


# ---------------------------------------------------------------------------
# Bucketed exchange (ISSUE 13): per-bucket reduce_scatter/all_to_all,
# embedded in the backward on the single/multi step, layout contract.
# ---------------------------------------------------------------------------


def _zero_bucket_state(tx, params, mesh8, B, ef=False):
    from theanompi_tpu.parallel.zero import init_zero_exchange_residual

    opt0, _ = init_zero_opt_state(tx, params, mesh8, exchange_buckets=B)
    res = (init_zero_exchange_residual(params, mesh8, exchange_buckets=B)
           if ef else None)
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      opt_state=opt0, model_state={},
                      exchange_residual=res)


def _run_zero_bucketed(mesh8, B, dtype="f32", ef=False, cadence=None,
                       steps=3):
    from jax.sharding import PartitionSpec as P

    from theanompi_tpu.parallel.mesh import shard_batch
    from theanompi_tpu.utils.helper_funcs import build_optimizer

    tx = build_optimizer(0.05, optimizer="sgd", momentum=0.9,
                         weight_decay=1e-4)
    params = _params()
    s = _zero_bucket_state(tx, params, mesh8, B, ef)
    kw = dict(exchange_dtype=dtype, error_feedback=ef,
              exchange_buckets=B, donate=False)
    if cadence:
        kw[cadence] = True
    step = make_bsp_zero_step(_loss, tx, mesh8, params, **kw)
    rng_np = np.random.default_rng(1)
    if cadence:
        xs = rng_np.standard_normal((2, 32, 5)).astype(np.float32)
        ys = rng_np.standard_normal((2, 32, 3)).astype(np.float32)
        batch = shard_batch((xs, ys), mesh8, spec=P(None, "data"))
        steps = 1
    else:
        x = rng_np.standard_normal((32, 5)).astype(np.float32)
        y = rng_np.standard_normal((32, 3)).astype(np.float32)
        batch = shard_batch((x, y), mesh8)
    rng = jax.random.key(2)
    traj = []
    for _ in range(steps):
        s, m = step(s, batch, rng)
        traj.append(jax.tree.map(np.asarray, s.params))
    return s, m, traj


@pytest.mark.parametrize("dtype,ef", [("f32", False), ("bf16", False),
                                      ("bf16", True)])
def test_zero_bucketed_identical_to_b1(mesh8, dtype, ef):
    """The acceptance pin on the ZeRO plane: B>1 equals B=1 at every
    step.  f32 is bit-identical; the bf16 variants sit within one f32
    ulp (the per-segment all_to_all programs fuse the quantize/sum
    chain differently from the whole-vector one — reassociation noise,
    not drift; pinned tight so real drift still fails)."""
    exact = dtype == "f32"
    _, m1, traj1 = _run_zero_bucketed(mesh8, 1, dtype, ef)
    for B in (2, 4, 8):
        _, mB, trajB = _run_zero_bucketed(mesh8, B, dtype, ef)
        for t1, tB in zip(traj1, trajB):
            for a, b in zip(jax.tree.leaves(t1), jax.tree.leaves(tB)):
                if exact:
                    np.testing.assert_array_equal(a, b, err_msg=f"B={B}")
                else:
                    np.testing.assert_allclose(a, b, rtol=2e-6,
                                               atol=1e-8,
                                               err_msg=f"B={B}")
        close = (float(m1["loss"]) == float(mB["loss"]) if exact else
                 float(m1["loss"]) == pytest.approx(float(mB["loss"]),
                                                    rel=1e-6))
        assert close


@pytest.mark.parametrize("cadence", ["multi", "accum"])
def test_zero_bucketed_cadences_identical(mesh8, cadence):
    """multi scans the tagged backward-embedded step; accum keeps ONE
    post-accumulation exchange split per bucket — both must equal
    their B=1 twins."""
    _, _, traj1 = _run_zero_bucketed(mesh8, 1, cadence=cadence)
    _, _, traj4 = _run_zero_bucketed(mesh8, 4, cadence=cadence)
    for a, b in zip(jax.tree.leaves(traj1[-1]),
                    jax.tree.leaves(traj4[-1])):
        np.testing.assert_array_equal(a, b, err_msg=cadence)


def test_zero_bucket_layout_properties(mesh8):
    """The layout is a pure function of (leaf shapes, N, B): segments
    are N-divisible, offsets consistent, and B=1 degenerates to the
    historical global flat layout exactly."""
    from theanompi_tpu.parallel.zero import _flat_info, _zero_layout

    params = _params()
    total, pad, per_shard = _flat_info(params, 8)
    l1 = _zero_layout(params, 8, 1)
    assert l1.per_shard == per_shard and l1.total_flat == total + pad
    # the layout-contract enforcement: per-shard length is strictly
    # increasing in the (clamped) bucket count, so resuming a
    # checkpoint under a different exchange_buckets ALWAYS fails on
    # shape — natural pads alone can coincide across bucket counts
    lengths = [_zero_layout(params, 8, B).per_shard
               for B in (1, 2, 3)]  # 3 leaves: clamp caps at 3
    assert lengths == sorted(set(lengths)), lengths
    many = {f"l{i}": np.zeros((8, 4)) for i in range(16)}  # all pads 0
    many_lengths = [_zero_layout(many, 8, B).per_shard
                    for B in (1, 2, 4, 8, 16)]
    assert many_lengths == sorted(set(many_lengths)), many_lengths
    for B in (2, 3):
        lB = _zero_layout(params, 8, B)
        assert lB == _zero_layout(params, 8, B)  # pure
        assert all(s % 8 == 0 for s in lB.seg)
        assert sum(lB.m) == total
        assert lB.per_shard == sum(lB.pb)
        assert lB.total_flat == sum(lB.seg)
        # opt-state shard length is a LAYOUT property: resuming a
        # checkpoint under a different B must fail on shape, not
        # silently misalign (the docstring's layout contract)
        opt0, _ = init_zero_opt_state(
            optax_sgd_momentum(), params, mesh8, exchange_buckets=B)
        vec = [l for l in jax.tree.leaves(opt0)
               if getattr(l, "ndim", 0) == 1 and l.size >= 8]
        assert vec and all(v.shape[0] == 8 * lB.per_shard for v in vec)


def optax_sgd_momentum():
    from theanompi_tpu.utils.helper_funcs import build_optimizer

    return build_optimizer(0.05, optimizer="sgd", momentum=0.9,
                           weight_decay=1e-4)


def test_zero_bucketed_collectives_in_lowering(mesh8):
    """Structural pin: the f32 bucketed step lowers to exactly B
    reduce-scatters (one per bucket), the first of which does not
    depend on the whole backward — not one whole-vector scatter that
    every backward dot feeds.  (Dependence, not the order of lines:
    tests/_hlo_dataflow.py.)"""
    from tests._hlo_dataflow import ancestors
    from theanompi_tpu.utils.helper_funcs import build_optimizer

    tx = build_optimizer(0.05, optimizer="sgd", momentum=0.9,
                         weight_decay=1e-4)
    params = _params()

    def lowered(B):
        s = _zero_bucket_state(tx, params, mesh8, B)
        step = make_bsp_zero_step(_loss, tx, mesh8, params,
                                  exchange_buckets=B, donate=False)
        rng_np = np.random.default_rng(1)
        batch = shard_batch(
            (rng_np.standard_normal((32, 5)).astype(np.float32),
             rng_np.standard_normal((32, 3)).astype(np.float32)), mesh8)
        return step.lower(s, batch, jax.random.key(0)).as_text()

    def layout(txt):
        lines = txt.splitlines()
        rs = [i for i, l in enumerate(lines)
              if "stablehlo.reduce_scatter" in l]
        dots = [i for i, l in enumerate(lines)
                if "stablehlo.dot_general" in l]
        return rs, dots

    txt1 = lowered(1)
    rs1, dots1 = layout(txt1)
    assert len(rs1) == 1
    assert not [d for d in dots1 if d > rs1[0]], \
        "B=1 has backward compute after the scatter"
    assert set(dots1) <= ancestors(txt1, rs1[0]), \
        "B=1: a backward dot does not feed the one scatter"
    # _params() has 3 leaves, so B=4 clamps to 3 per-leaf buckets —
    # assert against the plan's own bucket count
    from theanompi_tpu.parallel.zero import _zero_layout

    for B in (2, 4):
        n_buckets = len(_zero_layout(params, 8, B).ranges)
        txtB = lowered(B)
        rsB, dotsB = layout(txtB)
        assert len(rsB) == n_buckets, (B, n_buckets, len(rsB))
        waits_for = ancestors(txtB, rsB[0])
        assert [d for d in dotsB if d not in waits_for], \
            f"B={B}: the first scatter depends on every backward dot"


def test_zero_bucketed_donation_unchanged(mesh8):
    """Bucketing must not change what the stacked cadence donates
    (aliasing/buffer-donor count identical to B=1)."""
    from jax.sharding import PartitionSpec as P

    from theanompi_tpu.utils.helper_funcs import build_optimizer

    tx = build_optimizer(0.05, optimizer="sgd", momentum=0.9)
    params = _params()

    def donors(B):
        s = _zero_bucket_state(tx, params, mesh8, B)
        step = make_bsp_zero_step(_loss, tx, mesh8, params, multi=True,
                                  exchange_buckets=B)
        rng_np = np.random.default_rng(1)
        xs = rng_np.standard_normal((2, 32, 5)).astype(np.float32)
        ys = rng_np.standard_normal((2, 32, 3)).astype(np.float32)
        stacked = shard_batch((xs, ys), mesh8, spec=P(None, "data"))
        txt = step.lower(s, stacked, jax.random.key(0)).as_text()
        return (txt.count("tf.aliasing_output")
                + txt.count("jax.buffer_donor"))

    assert donors(4) == donors(1) > 0


def test_zero_bucketed_model_glue(mesh8):
    """ModelConfig.exchange_buckets reaches the ZeRO stack end to end:
    the sharded opt state and the residual are built on the SAME
    layout the step uses, and a few iterations train finite."""
    from tests._tiny_models import TinyCifar128

    from theanompi_tpu.utils.recorder import Recorder

    cfg = ModelConfig(batch_size=4, n_epochs=1, learning_rate=0.02,
                      print_freq=0, zero_sharding=True,
                      exchange_buckets=4, exchange_dtype="bf16",
                      exchange_error_feedback=True)
    m = TinyCifar128(config=cfg, mesh=mesh8, verbose=False)
    m.compile_iter_fns("avg")
    rec = Recorder(rank=0, size=8, print_freq=0)
    m.begin_epoch(0)
    for i in range(2):
        m.train_iter(i, rec)
    m._flush_metrics(rec)
    assert np.isfinite(rec.train_losses).all()
    # the residual rides the bucketed layout
    from theanompi_tpu.parallel.zero import _zero_layout

    layout = _zero_layout(m.state.params, 8, 4)
    res = m.state.exchange_residual
    assert res is not None and res.shape == (8, layout.total_flat)
    m.cleanup()
