"""OuroLM (models/ouro.py): a stack of layers run several times over
shared weights, an exit gate and the head after every pass, the loss
weighted by the exit distribution; on the normal training path, at
small sizes on the CPU, against the benchmark's plain reference
(benchmarks/reference/ouro_2_6b.py)."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu import monitor
from theanompi_tpu.models import layers as L
from theanompi_tpu.models import ouro
from theanompi_tpu.models.base import ModelConfig
from theanompi_tpu.ops.attention import rotary_table
from theanompi_tpu.parallel.mesh import data_mesh
from theanompi_tpu.utils.recorder import Recorder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(vocab=64, seq_len=24, n_layers=2, d_model=32, n_heads=2,
            head_dim=16, d_ff=48)


def _load(*parts):
    spec = importlib.util.spec_from_file_location(
        "bench_" + parts[-1][:-3], os.path.join(ROOT, "benchmarks", *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _model(devices=1, batch_size=2, dtype="float32", remat=False, **overrides):
    config = ModelConfig(batch_size=batch_size, optimizer="adamw",
                         learning_rate=3e-3, weight_decay=0.01,
                         lr_schedule="constant", compute_dtype=dtype,
                         remat=remat)
    return ouro.OuroLM(config=config,
                       mesh=data_mesh(devices, jax.devices()[:devices]),
                       verbose=False, **dict(TINY, **overrides))


def _trained_gate(params, key=7):
    """The parameters with a gate that is no longer zeros, as training
    would have left it: the exit distribution then differs by token."""
    gate = params["exit_gate"]
    kernel = 0.5 * jax.random.normal(jax.random.key(key),
                                     gate["kernel"].shape)
    return dict(params, exit_gate=dict(gate, kernel=kernel,
                                       bias=gate["bias"] + 0.3))


def _loss_and_grads(model, params, batch):
    return jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, {}, batch, None)[0]))(params)


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_system_and_reference_agree_in_float32(steps):
    """Loss and every leaf's gradient to 1e-5, for one pass, two and
    the published four."""
    model = _model(total_ut_steps=steps)
    reference = _load("reference", "ouro_2_6b.py")
    batch = next(model.data.train_batches(0, 2))
    params = _trained_gate(model.state.params)
    got_loss, got = _loss_and_grads(model, params, batch)
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.jit(jax.value_and_grad(
            lambda p: reference.loss(p, *reference.inputs(model, batch, None),
                                     n_heads=2, total_ut_steps=steps)))(params)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got))
    for path, leaf in jax.tree_util.tree_leaves_with_path(want):
        scale = max(float(jnp.abs(leaf).max()), 1e-6)
        np.testing.assert_allclose(flat_got[path], leaf, rtol=1e-4,
                                   atol=1e-5 * scale, err_msg=str(path))
    if steps == 1:   # one pass takes all the mass: the gate is not read
        assert not np.asarray(got["exit_gate"]["kernel"]).any()
    else:
        assert np.asarray(got["exit_gate"]["kernel"]).any()


def test_one_pass_is_a_plain_dense_decoders_cross_entropy():
    """T = 1: the last (only) pass takes the whole exit mass, the
    entropy term is zero, and the objective is the mean token
    cross-entropy of the one pass's logits."""
    model = _model(total_ut_steps=1)
    batch = next(model.data.train_batches(0, 2))
    params = _trained_gate(model.state.params)
    states, gate = model.module.apply({"params": params}, batch[0])
    assert states.shape == (1, 2, 24, 32) and gate.shape == (0, 2, 24)
    logits = states[0].reshape(-1, 32) @ params["head"]["kernel"]
    loss, (_, metrics) = model.loss_fn(params, {}, batch, None)
    np.testing.assert_allclose(
        loss, L.softmax_cross_entropy(logits, batch[1].reshape(-1)),
        rtol=1e-6)
    np.testing.assert_allclose(metrics["ouro_exit_mass"], [1.0])
    np.testing.assert_allclose(metrics["ouro_exit_entropy"], 0.0, atol=1e-7)
    np.testing.assert_allclose(
        metrics["error"], L.error_rate(logits, batch[1].reshape(-1)))


def test_the_exit_distribution():
    """Zeros give 1/2, 1/4, 1/8, 1/8; any logits give a distribution;
    a saturated gate gives zeros and no NaN."""
    log_p, p = ouro.exit_distribution(jnp.zeros((3, 5)))
    np.testing.assert_allclose(p[:, 0], [0.5, 0.25, 0.125, 0.125])
    logits = jax.random.normal(jax.random.key(0), (3, 7)) * 3
    log_p, p = ouro.exit_distribution(logits)
    np.testing.assert_allclose(p.sum(0), 1.0, rtol=1e-6)
    lam = jax.nn.sigmoid(logits)
    np.testing.assert_allclose(p[2], lam[2] * (1 - lam[0]) * (1 - lam[1]),
                               rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(p[3], np.prod(1 - lam, axis=0), rtol=1e-4,
                               atol=1e-7)
    log_p, p = ouro.exit_distribution(jnp.full((3, 2), 200.0))
    assert np.isfinite(np.asarray(p * log_p)).all()
    np.testing.assert_allclose(p[:, 0], [1.0, 0.0, 0.0, 0.0], atol=1e-30)


def test_a_shared_layers_gradient_is_the_sum_over_its_uses():
    """T passes over one set of layers against T untied copies of the
    stack run one after the other (the reference's layer, a copy a
    pass): the shared gradient is the sum of the copies' gradients."""
    steps = 3
    model = _model(total_ut_steps=steps)
    reference = _load("reference", "ouro_2_6b.py")
    batch = next(model.data.train_batches(0, 2))
    params = _trained_gate(model.state.params)
    shared = _loss_and_grads(model, params, batch)[1]["stack"]

    def untied(stacks):
        # the reference's own loop, each pass reading its own copy
        h = params["embed"]["embedding"][batch[0]]
        losses, stops = [], []
        for stack in stacks:
            for i in range(TINY["n_layers"]):
                h = reference._layer(h, stack[f"Layer_{i}"], n_heads=2,
                                     rope_theta=1e6, eps=1e-6)
            h = reference._rms(h, stack["final_norm"]["scale"], 1e-6)
            losses.append(reference._token_losses(
                h, params["head"]["kernel"], batch[1]))
            stops.append((h @ params["exit_gate"]["kernel"])[..., 0]
                         + params["exit_gate"]["bias"][0])
        log_p, p = ouro.exit_distribution(jnp.stack(stops[:-1]))
        return jnp.mean(jnp.sum(p * jnp.stack(losses), 0)
                        + 0.1 * jnp.sum(p * log_p, 0))

    copies = jax.jit(jax.grad(untied))([params["stack"]] * steps)
    summed = jax.tree.map(lambda *g: sum(g), *copies)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(shared),
                            jax.tree.leaves(summed)):
        scale = max(float(jnp.abs(b).max()), 1e-6)
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5 * scale,
                                   err_msg=str(path))
    # and no copy's gradient alone is the shared one
    first = jax.tree.leaves(copies[0])[0]
    assert not np.allclose(first, jax.tree.leaves(shared)[0], rtol=1e-2)


def test_remat_changes_no_value():
    plain, remat = _model(), _model(remat=True)
    assert (jax.tree.structure(plain.state.params)
            == jax.tree.structure(remat.state.params))
    batch = next(plain.data.train_batches(0, 2))
    params = _trained_gate(plain.state.params)
    results = [jax.jit(jax.value_and_grad(
        lambda p, m=m: m.loss_fn(p, {}, batch, None), has_aux=True))(params)
        for m in (plain, remat)]
    ((loss_a, (_, met_a)), grad_a), ((loss_b, (_, met_b)), grad_b) = results
    np.testing.assert_allclose(loss_a, loss_b, rtol=1e-6)
    for key in met_a:
        np.testing.assert_allclose(met_a[key], met_b[key], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(grad_a), jax.tree.leaves(grad_b)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("remat", [False, True])
def test_the_scanned_body_is_the_stack_applied_pass_after_pass(remat):
    """The T passes as one scanned body against the same ``OuroStack``
    applied T times in a Python loop on the same parameters (the form
    the chip read 2.8% slower, PERF.md section 6 PR 32): the states of
    every pass, and their gradient through all the passes."""
    model = _model(remat=remat)
    batch = next(model.data.train_batches(0, 2))
    params = _trained_gate(model.state.params)
    net = model.module
    stack = ouro.OuroStack(net.n_layers, net.layer, remat)
    rotary = rotary_table(jnp.arange(batch[0].shape[1]),
                          net.layer["head_dim"], net.rope_theta)

    def scanned(params):
        return net.apply({"params": params}, batch[0])[0]

    def unrolled(params):
        h = params["embed"]["embedding"][batch[0]]
        states = []
        for _ in range(net.total_ut_steps):
            h, out = stack.apply({"params": params["stack"]}, h, rotary)
            states.append(out)
        return jnp.stack(states)

    weigh = jax.random.normal(jax.random.key(3), (4, 2, 24, 32))
    got, want = (jax.jit(jax.value_and_grad(
        lambda p, f=f: jnp.sum(f(p) * weigh), has_aux=False))(params)
        for f in (scanned, unrolled))
    np.testing.assert_allclose(scanned(params), unrolled(params), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for key in ("embed", "stack"):
        for a, b in zip(jax.tree.leaves(got[1][key]),
                        jax.tree.leaves(want[1][key])):
            np.testing.assert_allclose(
                a, b, rtol=1e-4, atol=1e-5 * float(jnp.abs(b).max()))


@pytest.mark.parametrize("remat", [False, True])
def test_the_layer_with_the_kernels_rotation_is_the_layer_with_rope(
        monkeypatch, remat):
    """``OuroLayer`` at the published head of 128 with both kernels
    (interpret mode: heads by index map, q and k rotated inside)
    against the same layer on the composed form behind ``zaya.rope``,
    the way the layer was written before the kernels rotated: the
    output and the gradient of the input and of every weight."""
    import flax.linen as nn

    import theanompi_tpu.ops.attention as A
    from theanompi_tpu.models.zaya import rope

    monkeypatch.setattr(A, "_Q_BLOCK", 32)
    theta, t = 1e6, 64
    cls = nn.remat(ouro.OuroLayer) if remat else ouro.OuroLayer
    layer = cls(d_model=256, n_heads=2, head_dim=128, d_ff=64)
    u = jax.random.normal(jax.random.key(1), (2, t, 256))
    table = rotary_table(jnp.arange(t), 128, theta)
    params = layer.init(jax.random.key(0), u, table)
    weigh = jax.random.normal(jax.random.key(2), u.shape)
    run = lambda p, u: jnp.sum(layer.apply(p, u, table) * weigh)  # noqa: E731

    plans = []
    real_plan = A.tile_plan
    monkeypatch.setattr(A, "tile_plan", lambda *a, **kw: (
        plans.append(real_plan(*a, **kw)), plans[-1])[1])
    monkeypatch.setattr(A, "_resolve_impl", lambda *a, **kw: "pallas")
    got = jax.value_and_grad(run, argnums=(0, 1))(params, u)
    assert str(plans[-1]) == ("q block 32, key tile 32, 3 of 4 tiles, "
                              "heads by index map, rotary in kernel")

    def composed(q, k, v, causal, scale, name, rotary):
        positions = jnp.arange(q.shape[1])
        return A.fused_attention(rope(q, positions, 128, theta),
                                 rope(k, positions, 128, theta), v,
                                 causal=causal, scale=scale, impl="xla")
    monkeypatch.setattr(ouro, "fused_attention", composed)
    want = jax.value_and_grad(run, argnums=(0, 1))(params, u)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-5 * max(float(jnp.abs(b).max()), 1.0))


def test_a_start_traces_one_stack_whatever_the_passes():
    """The scanned body holds the L layers once: the step's jaxpr has
    as many products at 4 passes as at 2."""
    counts = []
    for steps in (2, 4):
        model = _model(total_ut_steps=steps)
        batch = next(model.data.train_batches(0, 2))
        text = str(jax.make_jaxpr(
            lambda p: model.loss_fn(p, {}, batch, None)[0])(
            model.state.params))
        counts.append(text.count("dot_general"))
    assert counts[0] == counts[1] > 2 * 9


def test_bfloat16_compute_keeps_float32_state_and_a_finite_loss():
    model = _model(dtype="bfloat16", remat=True)
    batch = next(model.data.train_batches(0, 2))
    (loss, (_, metrics)), grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, {}, batch, None), has_aux=True))(
        model.state.params)
    assert np.isfinite(float(loss)) and loss.dtype == jnp.float32
    assert all(leaf.dtype == jnp.float32 for leaf in
               jax.tree.leaves((model.state.params, grads)))
    assert set(metrics) == {"loss", "error", "ouro_exit_mass",
                            "ouro_loss_pass", "ouro_exit_entropy"}
    states, gate = model.module.apply({"params": model.state.params},
                                      batch[0])
    assert states.dtype == jnp.bfloat16 and gate.dtype == jnp.float32


def test_trains_through_the_base_loop_and_publishes_its_counters(tmp_path):
    """begin_epoch -> train_iter -> _flush_metrics on the BSP step over
    two devices: the loss falls, the gates move, and the flush hands
    every pass's exit mass and loss and the exit entropy to
    ``monitor``."""
    model = _model(devices=2, batch_size=2)
    model.compile_iter_fns("avg")
    recorder = Recorder(rank=0, size=2, print_freq=0)
    gate_before = np.asarray(model.state.params["exit_gate"]["kernel"])
    with monitor.session(str(tmp_path)):
        model.begin_epoch(0)
        it = 0
        for _ in range(3):
            for _ in range(10):
                it += model.train_iter(it, recorder)
            model._flush_metrics(recorder)
        registry = monitor.registry()
        mass = [registry.value(f"ouro/exit_mass_{t}") for t in range(1, 5)]
        losses = [registry.value(f"ouro/loss_pass_{t}") for t in range(1, 5)]
        entropy = registry.value("ouro/exit_entropy")
    model.cleanup()
    assert len(recorder.train_losses) == 30
    assert recorder.train_losses[-1] < recorder.train_losses[0] - 0.1
    assert sum(mass) == pytest.approx(1.0, rel=1e-5)
    assert all(0 < m < 1 for m in mass) and mass != [0.5, 0.25, 0.125, 0.125]
    assert all(np.isfinite(l) and 0 < l < 5 for l in losses)
    assert 0 < entropy <= np.log(4) + 1e-6
    gate_after = np.asarray(model.state.params["exit_gate"]["kernel"])
    assert not np.array_equal(gate_before, gate_after)
    assert not gate_before.any()


def test_eval_reports_the_last_passs_loss():
    model = _model()
    batch = next(model.data.train_batches(0, 2))
    params = _trained_gate(model.state.params)
    _, (_, train) = model.loss_fn(params, {}, batch, None)
    metrics = model.eval_fn(params, {}, batch)
    np.testing.assert_allclose(metrics["loss"], train["ouro_loss_pass"][-1],
                               rtol=1e-6)
    np.testing.assert_allclose(metrics["error"], train["error"])
    assert float(metrics["loss"]) != pytest.approx(float(train["loss"]),
                                                   rel=1e-3)


def test_what_the_class_refuses():
    with pytest.raises(ValueError, match="runs at least once"):
        _model(total_ut_steps=0)
    with pytest.raises(ValueError, match="multiply out to the hidden size"):
        _model(n_heads=3)
    with pytest.raises(ValueError, match="even head"):
        _model(d_model=30, n_heads=2, head_dim=15)
    assert ouro.OuroLM.decode_capable is False


def test_the_models_flop_count_is_the_benchmarks():
    """benchmarks/flops/ouro.py hands out the model's own count, which
    knows the loop; at the published sizes it is the issue's
    arithmetic: 13.09 GFLOP a token, 18% of it the head."""
    model = _model()
    flops = _load("flops", "ouro.py")
    assert flops.train_flops_per_sample is ouro.ouro_train_flops
    per_token = 2 * (4 * 32 * 32 + 3 * 32 * 48) + 32 * 64
    assert model.train_flops_per_sample == pytest.approx(
        6.0 * 24 * 4 * per_token + 6.0 * 4 * 2 * 32 * 24 * 25)
    published = dict(n_layers=8, d_model=2048, d_ff=5632, vocab=49152,
                     seq_len=2048, total_ut_steps=4)
    per_token = flops.train_flops_per_sample(**published) / 2048
    head = 6 * 4 * 2048 * 49152
    assert 13.0e9 < per_token < 13.2e9 and 0.18 < head / per_token < 0.19
    whole = flops.train_flops_per_sample(**dict(published, n_layers=48))
    assert 0.035 < head * 2048 / whole < 0.037
    # one pass of the loop is a quarter of it
    assert flops.train_flops_per_sample(
        **dict(published, total_ut_steps=1)) * 4 == pytest.approx(
        per_token * 2048)
    # one call of the kernel at the cell's shape
    shape = dict(batch=4, heads=16, head_dim=128, seq_len=2048)
    assert flops.attention_flops(which="fwd", **shape) == (
        2 * 2 * 4 * 16 * 128 * 2048 * 2049 / 2)
    assert flops.attention_flops(which="bwd", **shape) == (
        2.5 * flops.attention_flops(which="fwd", **shape))
    assert flops.attention_bytes(which="fwd", **shape) == 4 * 4 * 2048 * 2048 * 2
    assert flops.attention_bytes(which="bwd", **shape) == 8 * 4 * 2048 * 2048 * 2
