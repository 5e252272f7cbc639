"""ZayaLM (models/zaya.py): the ZAYA1 block on the normal training
path, at small sizes on the CPU, against the benchmark's plain
reference (benchmarks/reference/zaya1_8b.py)."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu import monitor
from theanompi_tpu.models import layers as L
from theanompi_tpu.models import zaya
from theanompi_tpu.models.base import ModelConfig
from theanompi_tpu.parallel.mesh import data_mesh
from theanompi_tpu.utils.recorder import Recorder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(vocab=64, seq_len=24, n_layers=2, d_model=32, n_heads=4,
            n_kv_heads=2, head_dim=16, n_experts=4, expert_width=48,
            router_hidden=16)
REFERENCE_KWARGS = dict(n_heads=4, n_kv_heads=2)


def _load(*parts):
    spec = importlib.util.spec_from_file_location(
        "bench_" + parts[-1][:-3], os.path.join(ROOT, "benchmarks", *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _model(devices=1, batch_size=2, held=(0, 2), dtype="float32",
           remat=False, label_smoothing=0.0, **overrides):
    config = ModelConfig(batch_size=batch_size, optimizer="adamw",
                         learning_rate=3e-3, weight_decay=0.01,
                         lr_schedule="constant", compute_dtype=dtype,
                         remat=remat, label_smoothing=label_smoothing)
    return zaya.ZayaLM(config=config,
                       mesh=data_mesh(devices, jax.devices()[:devices]),
                       verbose=False, held_experts=list(held),
                       **dict(TINY, **overrides))


@pytest.mark.parametrize("held", [(0, 2), (2, 2), (0, 4)])
def test_system_and_reference_agree_in_float32(held):
    """Loss and every leaf's gradient to 1e-5, for a share of the
    experts (either half) and for all of them."""
    model = _model(held=held)
    reference = _load("reference", "zaya1_8b.py")
    batch = next(model.data.train_batches(0, 2))
    params = model.state.params
    # biases as a controller would have left them, not zeros
    state = jax.tree.map(
        lambda b: b + jnp.array([0.4, -0.3, 0.0, 0.2]),
        model.state.model_state)
    model.state = model.state.replace(model_state=state)
    got_loss, got = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, state, batch, None)[0]))(params)
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.jit(jax.value_and_grad(
            lambda p: reference.loss(
                p, *reference.inputs(model, batch, None),
                held_experts=held, **REFERENCE_KWARGS)))(params)
    assert abs(float(got_loss) - float(want_loss)) <= 1e-5 * float(want_loss)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        err = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert err <= 1e-5, (jax.tree_util.keystr(path), err)


def test_trains_through_the_base_loop_and_counts_its_rows(tmp_path):
    """begin_epoch -> train_iter -> _flush_metrics on the BSP step over
    two devices: the loss falls, and each flush hands the rows the
    held experts multiplied to ``monitor`` and to ``zaya.routing_log``,
    stamped with whether a profiler trace was running at the flush."""
    zaya.routing_log.clear()
    model = _model(devices=2, batch_size=2)
    model.compile_iter_fns("avg")
    recorder = Recorder(rank=0, size=2, print_freq=0)
    with monitor.session(str(tmp_path)):
        model.begin_epoch(0)
        it = 0
        for flush in range(3):
            if flush == 1:
                jax.profiler.start_trace(str(tmp_path / "trace"))
            for _ in range(10):
                it += model.train_iter(it, recorder)
            model._flush_metrics(recorder)
            if flush == 1:
                jax.profiler.stop_trace()
        registry = monitor.registry()
        held = registry.value("moe/held_rows")
        elsewhere = registry.value("moe/rows_elsewhere")
        fullest = registry.value("moe/max_expert_rows")
        buffers = registry.value("moe/buffer_rows")
        fill = registry.value("moe/buffer_fill")
    model.cleanup()
    losses = recorder.train_losses
    assert len(losses) == 30 and losses[-1] < losses[0] - 0.3
    assert [e["profiled"] for e in zaya.routing_log] == [False, True, False]
    entry = zaya.routing_log[-1]
    assert len(entry["held_rows"]) == 10
    assert entry["n_layers"] == 2 and entry["expert_shape"] == (2, 32, 48)
    # a shard has 2 x 24 tokens in each of 2 layers; the counts are the
    # shards' mean, and every assignment is here or elsewhere
    assert held + elsewhere == 30 * 2 * 48
    assert held == sum(sum(e["held_rows"]) for e in zaya.routing_log)
    assert 0 < fullest <= 48
    # the buffer they lay in: one rung at this shape (48 assignments
    # padded to a tile, and a tile of slack for each of 2 held experts),
    # in each of 2 layers; the gauge is the newest flush's fill
    assert entry["buffer_rows"] == [2 * 384.0] * 10
    assert buffers == 30 * 2 * 384
    assert fill == pytest.approx(sum(entry["held_rows"]) / (10 * 2 * 384))
    # what the roofline reader under benchmarks/layer_metrics/ takes
    assert set(entry) == {"held_rows", "buffer_rows", "n_layers",
                          "expert_shape", "profiled"}


def test_the_balancing_controller_evens_the_experts_loads():
    """Under a router that prefers one expert, the biases move against
    the excess load step by step (they are state, not parameters: no
    gradient, no optimizer) until the loads are even; an evaluation
    pass moves nothing."""
    model = _model(held=(0, 4), batch_size=8, n_layers=1)
    params = jax.tree.map(lambda a: a, model.state.params)
    skew = jnp.array([2.0, 0.0, 0.0, -2.0])
    fc3 = params["Layer_0"]["router"]["fc3"]
    params["Layer_0"]["router"]["fc3"] = dict(fc3, bias=fc3["bias"] + skew)
    batch = next(model.data.train_batches(0, 8))
    state = model.state.model_state
    assert not np.asarray(state["router_state"]["Layer_0"]["bias"]).any()

    @jax.jit
    def step(state):
        _, (new_state, metrics) = model.loss_fn(params, state, batch, None)
        return new_state, metrics["moe_max_expert_rows"]

    fullest = []
    for _ in range(30):
        state, rows = step(state)
        fullest.append(float(rows))
    bias = np.asarray(state["router_state"]["Layer_0"]["bias"])
    assert bias[0] < -1.0 and bias[3] > 1.0
    assert fullest[0] > 0.6 * 192 and fullest[-1] < 0.4 * 192
    assert model.eval_fn(params, state, batch)["loss"].shape == ()
    grads = jax.grad(lambda p: model.loss_fn(p, state, batch, None)[0])(
        params)
    assert "router_state" not in grads


def test_bfloat16_compute_keeps_float32_state_and_a_finite_loss():
    model = _model(dtype="bfloat16")
    batch = next(model.data.train_batches(0, 2))
    loss, (_, metrics) = jax.jit(
        lambda p: model.loss_fn(p, model.state.model_state, batch, None))(model.state.params)
    assert np.isfinite(float(loss)) and loss.dtype == jnp.float32
    assert all(leaf.dtype == jnp.float32
               for leaf in jax.tree.leaves(model.state.params))
    assert set(metrics) == {"loss", "error", "moe_held_rows",
                            "moe_rows_elsewhere", "moe_max_expert_rows",
                            "moe_buffer_rows"}


def test_remat_changes_no_value():
    plain, remat = _model(), _model(remat=True)
    batch = next(plain.data.train_batches(0, 2))
    grads = [jax.jit(jax.grad(lambda p, m=m: m.loss_fn(
            p, plain.state.model_state, batch, None)[0]))(
        plain.state.params) for m in (plain, remat)]
    for a, b in zip(*map(jax.tree.leaves, grads)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_eval_reports_the_training_loss():
    model = _model()
    batch = next(model.data.train_batches(0, 2))
    params = model.state.params
    state = model.state.model_state
    loss = model.loss_fn(params, state, batch, None)[0]
    metrics = model.eval_fn(params, state, batch)
    np.testing.assert_allclose(metrics["loss"], loss, rtol=1e-6)
    assert 0.0 <= float(metrics["error"]) <= 1.0


def test_label_smoothing_is_train_time_only():
    """``config.label_smoothing`` reaches the blocked loss in training
    (``softmax_cross_entropy``'s smoothing over the tied head's logits)
    and not in validation."""
    plain, smoothed = _model(), _model(label_smoothing=0.1)
    batch = next(plain.data.train_batches(0, 2))
    params, state = plain.state.params, plain.state.model_state
    h, _ = plain.module.apply({"params": params, **state}, batch[0])
    logits = h.reshape(-1, h.shape[-1]) @ params["embed"]["embedding"].T
    np.testing.assert_allclose(
        smoothed.loss_fn(params, state, batch, None)[0],
        L.softmax_cross_entropy(logits, batch[1].reshape(-1), 0.1),
        rtol=1e-5)
    np.testing.assert_allclose(
        smoothed.eval_fn(params, state, batch)["loss"],
        plain.loss_fn(params, state, batch, None)[0], rtol=1e-6)


def test_what_the_class_refuses():
    with pytest.raises(ValueError, match="even number of key/value heads"):
        _model(n_kv_heads=1)
    with pytest.raises(ValueError, match="not among the router's 4"):
        _model(held=(3, 2))


def test_the_models_flop_count_is_the_benchmarks():
    """benchmarks/flops/zaya1.py hands out the model's own count, the
    model sets it from its sizes, and at the published sizes it is the
    issue's arithmetic: 0.93 GFLOP a token (0.40 the head, 0.53 six
    layers)."""
    model = _model()
    flops = _load("flops", "zaya1.py")
    assert flops.train_flops_per_sample is zaya.zaya_train_flops
    # by hand: q, k, v, o, conv1, router, half of 3 expert matrices
    per_token = (32 * (96 + 32) + 64 * 32 + 2 * 96 * 16
                 + 32 * 16 + 2 * 16 * 16 + 16 * 4 + 3 * 32 * 48 / 2)
    assert model.train_flops_per_sample == pytest.approx(
        6.0 * (2 * per_token + 32 * 64) * 24 + 6.0 * 2 * 4 * 16 * 24 * 25)
    published = dict(n_layers=6, d_model=2048, n_heads=8, n_kv_heads=2,
                     head_dim=128, n_experts=16, expert_width=2048,
                     router_hidden=256, held_count=8, vocab=32784,
                     seq_len=2048)
    per_token = flops.train_flops_per_sample(**published) / 2048
    head = 6 * 2048 * 32784
    assert 0.92e9 < per_token < 0.94e9 and 0.42 < head / per_token < 0.44
    # the expert kernels' own count: 9 products a layer
    assert flops.expert_matmul_flops(
        rows=4096, d_model=2048, expert_width=2048) == 18 * 4096 * 2048 ** 2
    assert flops.expert_matmul_bytes(
        rows=4096, layer_steps=1, held_count=8, d_model=2048,
        expert_width=2048) == 9 * 2 * (4096 * 4096 + 8 * 2048 * 2048)


@pytest.mark.parametrize("block", [32, 40, 96])
def test_the_tied_loss_in_blocks_is_the_whole_loss(block):
    """Values, error rate and both gradients against the loss over
    whole logits; 40 does not divide 96 tokens (the largest divisor
    below it is taken)."""
    key = jax.random.key(0)
    h = jax.random.normal(jax.random.fold_in(key, 1), (96, 16))
    table = jax.random.normal(jax.random.fold_in(key, 2), (50, 16))
    labels = jnp.argmax(h @ table.T, -1).at[::3].set(7)
    blocked = lambda h, t: L.blocked_softmax_cross_entropy(  # noqa: E731
        h, t, None, labels, vocab_axis=0, block_tokens=block)
    whole = lambda h, t: L.softmax_cross_entropy(h @ t.T, labels)  # noqa: E731
    loss, err = blocked(h, table)
    np.testing.assert_allclose(loss, whole(h, table), rtol=1e-6)
    np.testing.assert_allclose(err, L.error_rate(h @ table.T, labels))
    got = jax.grad(lambda *a: 3.0 * blocked(*a)[0], argnums=(0, 1))(h, table)
    want = jax.grad(lambda *a: 3.0 * whole(*a), argnums=(0, 1))(h, table)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_the_tied_loss_keeps_no_whole_logits():
    """The jaxpr of loss and gradient holds no (tokens, vocab) array."""
    h = jnp.zeros((96, 16), jnp.bfloat16)
    table = jnp.zeros((50, 16))
    labels = jnp.zeros((96,), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda h, t: L.blocked_softmax_cross_entropy(
            h, t, None, labels, vocab_axis=0, block_tokens=32)[0],
        argnums=(0, 1)))(h, table)

    def shapes(jaxpr):
        for eqn in jaxpr.eqns:
            for var in eqn.outvars:
                yield getattr(var.aval, "shape", ())
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    seen = set(shapes(jaxpr.jaxpr))
    assert (32, 50) in seen and (96, 50) not in seen
    # the table's gradient comes back in the table's dtype
    grads = jax.grad(
        lambda h, t: L.blocked_softmax_cross_entropy(
            h, t, None, labels, vocab_axis=0, block_tokens=32)[0],
        argnums=(0, 1))(h, table)
    assert grads[0].dtype == jnp.bfloat16 and grads[1].dtype == jnp.float32
