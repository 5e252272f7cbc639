"""NemotronHLM (models/nemotron_h.py): a stack built from a pattern
string of Mamba-2, expert and attention layers on the normal training
path, at small sizes on the CPU, against the benchmark's plain
reference (benchmarks/reference/nemotron_twotower_30b.py), whose
recurrence is stepped one token at a time."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu import monitor
from theanompi_tpu.models import nemotron_h as N
from theanompi_tpu.models.base import ModelConfig, TpuModel
from theanompi_tpu.ops import ssd
from theanompi_tpu.ops.ssd import ssd_chunked
from theanompi_tpu.parallel.expert import routed_experts
from theanompi_tpu.parallel.mesh import data_mesh
from theanompi_tpu.utils.recorder import Recorder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PATTERN = "MEMEM*EME"
TINY = dict(vocab=64, seq_len=16, pattern=PATTERN, d_model=32,
            mamba_heads=4, mamba_head_dim=8, n_groups=2, state=8, chunk=8,
            n_experts=16, top_k=3, expert_width=24, shared_width=48,
            n_heads=4, n_kv_heads=2, head_dim=8)
REFERENCE_KWARGS = dict(pattern=PATTERN, mamba_heads=4, n_groups=2, state=8,
                        top_k=3, routed_scaling_factor=2.5, n_heads=4,
                        n_kv_heads=2)


def _load(*parts):
    spec = importlib.util.spec_from_file_location(
        "bench_" + parts[-1][:-3], os.path.join(ROOT, "benchmarks", *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REFERENCE = _load("reference", "nemotron_twotower_30b.py")


def _model(devices=1, batch_size=2, held=(0, 4), dtype="float32",
           remat=False, **overrides):
    config = ModelConfig(batch_size=batch_size, optimizer="adamw",
                         learning_rate=3e-3, weight_decay=0.01,
                         lr_schedule="constant", compute_dtype=dtype,
                         remat=remat)
    return N.NemotronHLM(config=config,
                         mesh=data_mesh(devices, jax.devices()[:devices]),
                         verbose=False, held_experts=list(held),
                         **dict(TINY, **overrides))


def _scan_inputs(t, heads=4, groups=2, seed=0, batch=2, head_dim=3,
                 state=5):
    """A group shared by ``heads / groups`` heads; time steps and decay
    rates in the ranges a layer starts from."""
    k = jax.random.split(jax.random.key(seed), 6)
    b, p, n = batch, head_dim, state
    return (jax.random.normal(k[0], (b, t, heads, p)),
            jax.nn.softplus(jax.random.normal(k[1], (b, t, heads)) - 1.0),
            -jnp.exp(jax.random.normal(k[2], (heads,))),
            jax.random.normal(k[3], (b, t, groups, n)),
            jax.random.normal(k[4], (b, t, groups, n)),
            jax.random.normal(k[5], (heads,)))


#: the smallest shape the kernels take: one sequence, two groups of two
#: heads of 64 (a 128-lane tile a group), state 128, chunks of 128
ALIGNED = dict(batch=1, head_dim=64, state=128)


def _stepped(x, dt, a, b, c, d):
    """The reference's recurrence, one token at a time."""
    rep = x.shape[2] // b.shape[2]
    return REFERENCE._recurrence(x, dt, a, jnp.repeat(b, rep, axis=2),
                                 jnp.repeat(c, rep, axis=2), d)


def _scan(path, chunk):
    """The scan by ``path``: ``ssd_chunked``'s own choice, which the
    shape makes (``"pallas"`` or ``"jax.numpy"``, checked), or the
    ``jax.numpy`` body called by name at any shape (``"body"``)."""
    def run(x, dt, a, b, c, d):
        if path == "body":
            return ssd._ssd_jnp(x, dt, a, b, c, d, chunk)
        plan = ssd.ssd_plan(x.shape[0], x.shape[1], x.shape[2], x.shape[3],
                            b.shape[2], b.shape[3], chunk)
        assert plan.pallas == (path == "pallas"), str(plan)
        return ssd_chunked(x, dt, a, b, c, d, chunk=chunk)
    return run


@pytest.mark.parametrize("path, t, chunk, shape", [
    ("jax.numpy", 24, 24, {}), ("jax.numpy", 24, 8, {}),
    ("jax.numpy", 24, 4, {}), ("jax.numpy", 128, 64, {}),
    # the kernels (interpret mode), over one chunk and two
    ("pallas", 128, 128, ALIGNED), ("pallas", 256, 128, ALIGNED),
    # the jax.numpy body at the kernels' shape
    ("body", 256, 128, ALIGNED)])
def test_the_chunked_scan_is_the_recurrence(path, t, chunk, shape):
    """Outputs and the gradient with respect to EVERY input (x, dt, A,
    B, C, D), over one chunk and over several, so that the state carried
    between chunks is exercised; two heads share each group.  1e-5 of
    the largest entry: both sides are float32 and differ by the order of
    their sums alone."""
    inputs = _scan_inputs(t, **shape)
    scan = _scan(path, chunk)
    got = scan(*inputs)
    want = _stepped(*inputs)
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * float(jnp.abs(want).max()))
    weigh = jax.random.normal(jax.random.key(9), want.shape)
    grads = [jax.grad(lambda *v: (fn(*v) * weigh).sum(), argnums=range(6))(
        *inputs) for fn in (scan, _stepped)]
    for g, w in zip(*grads):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=2e-5 * float(jnp.abs(w).max()))


def test_the_scan_refuses_a_ragged_sequence():
    with pytest.raises(ValueError, match="whole number of chunks"):
        ssd_chunked(*_scan_inputs(24), chunk=16)
    with pytest.raises(ValueError, match="whole number of chunks"):
        ssd_chunked(*_scan_inputs(24, heads=3), chunk=8)


@pytest.mark.parametrize("path, t, chunk, shape", [
    ("jax.numpy", 64, 16, {}), ("pallas", 256, 128, ALIGNED)])
def test_the_scan_in_bfloat16_keeps_its_decays_in_float32(path, t, chunk,
                                                          shape):
    """bfloat16 products, float32 decays and carried states: the output
    comes back in bfloat16 within its own rounding of the float32 one."""
    inputs = _scan_inputs(t, **shape)
    scan = _scan(path, chunk)
    want = scan(*inputs)
    x, dt, a, b, c, d = inputs
    got = scan(x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16),
               c.astype(jnp.bfloat16), d)
    assert got.dtype == jnp.bfloat16
    err = jnp.abs(got.astype(jnp.float32) - want).max() / jnp.abs(want).max()
    assert err < 0.03


@pytest.mark.parametrize("shape, line", [
    # the cell's: 4 x 2048 tokens, 64 heads of 64 in 8 groups, state 128
    (dict(batch=4, t=2048, d_model=2688, n_heads=64, head_dim=64,
          n_groups=8, state=128, chunk=128, dtype=jnp.bfloat16),
     "ssd: 16 chunks of 128, 64 heads, state 64 x 128, pallas (grid 4 x 8 "
     "x 16, state in VMEM)"),
    # the dry run's: chunk 8, state 8, head 8
    (dict(batch=2, t=16, d_model=32, n_heads=4, head_dim=8, n_groups=2,
          state=8, chunk=8, dtype=jnp.float32),
     "ssd: 2 chunks of 8, 4 heads, state 8 x 8, jax.numpy")])
def test_the_plan_line_says_which_path_the_shape_takes(shape, line, caplog):
    """One ``ssd_chunked``: the kernels at the cell's shape, the
    ``jax.numpy`` body at the dry run's, read from the layer's own plan
    line (traced, not run)."""
    batch, t = shape.pop("batch"), shape.pop("t")
    mixer = N.Mamba2Mixer(**shape)
    u = jnp.zeros((batch, t, shape["d_model"]), shape["dtype"])
    N._log_ssd_plan.cache_clear()
    with caplog.at_level("INFO", logger=N.__name__):
        jax.eval_shape(lambda u: mixer.init_with_output(
            jax.random.key(0), u), u)
    said = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("ssd:")]
    assert said == [line]


def test_each_kernel_is_traced_and_lowered_once_a_shape(monkeypatch):
    """Two recomputed Mamba layers or four: one trace of each kernel
    body (the forward's twice: ``init``'s primal writes no states, the
    gradient's forward does), and one lowered function a pass that
    every layer calls."""
    import flax.linen as nn

    counts = {"_fwd_kernel": 0, "_bwd_kernel": 0}
    for name in counts:
        real = getattr(ssd, name)

        def counted(*a, _real=real, _name=name, **kw):
            counts[_name] += 1
            return _real(*a, **kw)
        monkeypatch.setattr(ssd, name, counted)
    ssd._forward.clear_cache()
    ssd._backward.clear_cache()

    class Stack(nn.Module):
        depth: int

        @nn.compact
        def __call__(self, u):
            for _ in range(self.depth):
                u = u + nn.remat(N.Mamba2Mixer)(
                    d_model=16, n_heads=2, head_dim=64, n_groups=1,
                    state=128, chunk=128)(u)
            return u

    u = jnp.zeros((1, 128, 16))
    seen = {}
    for depth in (2, 4):
        net, before = Stack(depth), dict(counts)
        params = jax.eval_shape(net.init, jax.random.key(0), u)
        text = jax.jit(jax.grad(lambda p: net.apply(p, u).sum())).lower(
            params).as_text()
        seen[depth] = ({k: counts[k] - before[k] for k in counts},
                       text.count("func.func private @_forward("),
                       text.count("func.func private @_backward("),
                       text.count("call @_backward("))
    assert seen[2] == ({"_fwd_kernel": 2, "_bwd_kernel": 1}, 1, 1, 2)
    assert seen[4] == ({"_fwd_kernel": 0, "_bwd_kernel": 0}, 1, 1, 4)


def test_the_convolution_is_causal_and_depthwise():
    """A change at position t moves nothing before t and only its own
    channel; the values are the reference's."""
    key = jax.random.key(1)
    x = jax.random.normal(key, (2, 12, 6))
    kernel = jax.random.normal(jax.random.fold_in(key, 1), (4, 6))
    bias = jax.random.normal(jax.random.fold_in(key, 2), (6,))
    y = N.causal_depthwise_conv(x, kernel, bias)
    np.testing.assert_allclose(y, REFERENCE._conv(x, kernel, bias),
                               rtol=1e-6, atol=1e-6)
    moved = N.causal_depthwise_conv(x.at[:, 7, 2].add(1.0), kernel, bias) - y
    assert not np.asarray(moved[:, :7]).any()
    assert not np.asarray(moved[:, :, [0, 1, 3, 4, 5]]).any()
    # taps 3, 2, 1, 0 read positions 7, 8, 9, 10: the change reaches 3 on
    np.testing.assert_allclose(moved[0, 7:11, 2], kernel[::-1, 2], rtol=1e-5)
    assert not np.asarray(moved[:, 11:]).any()
    np.testing.assert_allclose(y[:, 0], x[:, 0] * kernel[3] + bias,
                               rtol=1e-5, atol=1e-6)


def test_the_gated_group_norm_gates_first_and_norms_each_group():
    key = jax.random.key(2)
    y = jax.random.normal(key, (3, 5, 12))
    z = jax.random.normal(jax.random.fold_in(key, 1), (3, 5, 12))
    scale = jax.random.normal(jax.random.fold_in(key, 2), (12,))
    got = N.gated_group_norm(y, z, scale, n_groups=3, eps=1e-5)
    gated = np.asarray(y * jax.nn.silu(z), np.float64).reshape(3, 5, 3, 4)
    want = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(got, want.reshape(3, 5, 12) * scale,
                               rtol=1e-5, atol=1e-6)
    # a group's scale does not reach its neighbours
    louder = N.gated_group_norm(y.at[..., :4].multiply(7.0), z, scale, 3,
                                1e-5)
    np.testing.assert_allclose(louder[..., 4:], got[..., 4:], rtol=1e-6)


def test_the_router_chooses_by_biased_scores_and_weighs_by_unbiased():
    """Top-3 of ``s + bias``; the weights are the chosen experts'
    UNBIASED scores over their sum over all three, times 2.5, whether
    or not an expert is held here."""
    key = jax.random.key(3)
    n, d, f, e = 40, 8, 6, 8
    u = jax.random.normal(key, (n, d))
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 1),
                                              (n, e)))
    bias = jnp.array([0.0, 0.9, -0.9, 0.0, 0.3, 0.0, -0.2, 0.0])
    ups = 0.3 * jax.random.normal(jax.random.fold_in(key, 2), (e, d, f))
    downs = 0.3 * jax.random.normal(jax.random.fold_in(key, 3), (e, f, d))
    chosen = np.argsort(-np.asarray(scores + bias), axis=-1)[:, :3]
    assert (chosen != np.argsort(-np.asarray(scores), axis=-1)[:, :3]).any()
    want = np.zeros((n, d))
    for i in range(n):
        total = sum(float(scores[i, j]) for j in chosen[i])
        for j in chosen[i]:
            if 2 <= j < 6:            # the held ones
                want[i] += (2.5 * float(scores[i, j]) / total
                            * np.asarray(REFERENCE._relu2_mlp(u[i], ups[j], downs[j])))
    out, stats = routed_experts(
        u, scores, {"up": ups[2:6], "down": downs[2:6]}, (2, 4), top_k=3,
        select_by=scores + bias, normalize=True, scale=2.5,
        impl="ragged_dot")
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    assert stats["held_rows"] == ((chosen >= 2) & (chosen < 6)).sum()
    assert stats["expert_load"].sum() == 3 * n
    assert stats["expert_load"][1] > stats["expert_load"][2]


def _expert_layer(n_experts=32, held=(0, 32), seed=4):
    layer = N.ExpertMixer(d_model=16, n_experts=n_experts, top_k=6,
                          expert_width=12, shared_width=20,
                          held_experts=held, routed_scaling_factor=2.5)
    u = jax.random.normal(jax.random.key(seed), (2, 25, 16))
    variables = layer.init(jax.random.key(seed + 1), u)
    # an init of 0.02 leaves every score at 1/2: spread them
    params = jax.tree.map(lambda a: a * 20.0, variables["params"])
    bias = 0.05 * jax.random.normal(jax.random.key(seed + 2), (n_experts,))
    return layer, u, params, bias


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """32 experts held as 16 x 2, top-6: the routed parts of the sixteen
    chips' layers summed, with the shared expert counted ONCE, are the
    reference's whole layer; each share alone is the reference's share,
    and every assignment is some chip's."""
    whole_layer, u, params, bias = _expert_layer()
    cfg = dict(top_k=6, routed_scaling_factor=2.5)
    whole = REFERENCE._moe(u, params, bias, dict(cfg, held=(0, 32)))
    shared = REFERENCE._relu2_mlp(u, params["shared_up"]["kernel"],
                                  params["shared_down"]["kernel"])
    routed, rows = 0, 0
    for chip in range(16):
        held = (2 * chip, 2)
        layer = whole_layer.clone(held_experts=held)
        share = dict(params,
                     experts_up=params["experts_up"][2 * chip:2 * chip + 2],
                     experts_down=params["experts_down"][
                         2 * chip:2 * chip + 2])
        out, stats = layer.apply(
            {"params": share, "router_state": {"bias": bias}}, u)
        np.testing.assert_allclose(
            out, REFERENCE._moe(u, share, bias, dict(cfg, held=held)),
            rtol=1e-4, atol=1e-5)
        routed = routed + (out - shared)
        rows += stats["held_rows"]
    np.testing.assert_allclose(routed + shared, whole, rtol=1e-4, atol=1e-5)
    assert rows == 6 * 50
    assert float(jnp.abs(whole - shared).max()) > 0.1   # the routed part


def test_the_correction_bias_is_state_and_evens_the_loads():
    """Only a pass that may write ``router_state`` moves the bias, by
    the controller's rule, and it is no parameter."""
    layer, u, params, _ = _expert_layer()
    variables = {"params": params,
                 "router_state": {"bias": jnp.zeros(32)}}
    assert "bias" not in params
    (_, _), frozen = layer.apply(variables, u, mutable=[])
    assert not frozen
    (_, stats), moved = layer.apply(variables, u, mutable=["router_state"])
    scores = jax.nn.sigmoid(u.reshape(-1, 16) @ params["router"]["kernel"])
    load = np.bincount(np.argsort(-np.asarray(scores), -1)[:, :6].ravel(),
                       minlength=32)
    np.testing.assert_allclose(
        moved["router_state"]["bias"],
        -N.BALANCE_GAIN * (load / load.mean() - 1.0), rtol=1e-5, atol=1e-7)
    assert "expert_load" not in stats


@pytest.mark.parametrize("held", [(0, 4), (12, 4), (0, 16)])
def test_system_and_reference_agree_in_float32(held):
    """Loss and EVERY leaf's gradient of the nine-layer pattern to 1e-5,
    for a share of the experts (either end) and for all of them, with
    the correction biases where a controller would have left them."""
    model = _model(held=held)
    batch = next(model.data.train_batches(0, 2))
    # an init of 0.02 leaves every score near 1/2 and the choice to the
    # bias alone: spread the routers so that the tokens differ
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a * 30.0 if "router" in jax.tree_util.keystr(path)
        else a, model.state.params)
    state = jax.tree.map(
        lambda b: b + 0.05 * jax.random.normal(jax.random.key(1), b.shape),
        model.state.model_state)
    model.state = model.state.replace(model_state=state)
    got_loss, got = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, state, batch, None)[0]))(params)
    inputs = REFERENCE.inputs(model, batch, None)
    assert sorted(inputs[2]) == [1, 3, 6, 8]
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: REFERENCE.loss(p, *inputs, held_experts=held,
                                 **REFERENCE_KWARGS)))(params)
    model.cleanup()
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(want)
    assert len(flat) == len(jax.tree.leaves(got))
    for (path, w), g in zip(flat, jax.tree.leaves(got)):
        scale = float(jnp.abs(w).max())
        assert scale > 0, path
        np.testing.assert_allclose(g, w, rtol=1e-3, atol=2e-5 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_tree_differs_by_layer_kind():
    model = _model()
    params = model.state.params
    kinds = {"M": "mamba", "E": "moe", "*": "attention"}
    for i, kind in enumerate(PATTERN):
        assert sorted(params[f"Layer_{i}"]) == sorted([kinds[kind], "norm"])
    assert sorted(params["Layer_0"]["mamba"]) == [
        "A_log", "D", "conv_bias", "conv_kernel", "dt_bias", "in_proj",
        "norm_scale", "out_proj"]
    assert sorted(params["Layer_1"]["moe"]) == [
        "experts_down", "experts_up", "router", "shared_down", "shared_up"]
    assert params["head"]["kernel"].shape == (32, 64)
    assert sorted(model.state.model_state["router_state"]) == [
        "Layer_1", "Layer_3", "Layer_6", "Layer_8"]
    # the time steps start inside the published range
    dt = jax.nn.softplus(params["Layer_0"]["mamba"]["dt_bias"])
    assert float(dt.min()) >= 1e-3 * 0.999 and float(dt.max()) <= 0.1 * 1.001
    a = jnp.exp(params["Layer_0"]["mamba"]["A_log"])
    assert float(a.min()) >= 1 and float(a.max()) <= 16
    model.cleanup()


def test_remat_changes_no_value():
    losses = []
    for remat in (False, True):
        model = _model(remat=remat)
        batch = next(model.data.train_batches(0, 2))
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: model.loss_fn(p, model.state.model_state, batch,
                                    None)[0]))(model.state.params)
        losses.append((loss, grads))
        model.cleanup()
    assert float(losses[0][0]) == pytest.approx(float(losses[1][0]), rel=1e-6)
    for a, b in zip(*(jax.tree.leaves(g) for _, g in losses)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


def test_trains_through_the_base_loop_and_counts_its_rows(tmp_path):
    """begin_epoch -> train_iter -> _flush_metrics on the BSP step over
    two devices, each layer recomputed: the loss falls, and each flush
    hands the rows the held experts multiplied to ``monitor`` and to
    ``nemotron_h.routing_log``, stamped with whether a profiler trace
    was running at the flush."""
    N.routing_log.clear()
    model = _model(devices=2, batch_size=2, remat=True)
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
        share = registry.value("moe/held_share")
        buffers = registry.value("moe/buffer_rows")
        fill = registry.value("moe/buffer_fill")
    bias = model.state.model_state["router_state"]["Layer_1"]["moe"]["bias"]
    model.cleanup()
    losses = recorder.train_losses
    assert len(losses) == 30 and losses[-1] < losses[0] - 0.1
    assert [e["profiled"] for e in N.routing_log] == [False, True, False]
    entry = N.routing_log[-1]
    assert len(entry["held_rows"]) == len(entry["rows_elsewhere"]) == 10
    assert (entry["n_layers"], entry["top_k"], entry["expert_shape"]) == (
        4, 3, (4, 32, 24))
    # a shard has 2 x 16 tokens, 3 assignments each, in each of 4 expert
    # layers; the counts are the shards' mean, and every assignment is
    # here or elsewhere
    assert held + elsewhere == 30 * 4 * 3 * 32
    assert held == sum(sum(e["held_rows"]) for e in N.routing_log)
    assert 0 < fullest <= 32
    last = N.routing_log[-1]
    assert share == pytest.approx(sum(last["held_rows"]) / (10 * 4 * 3 * 32))
    # the buffers they lay in: one rung at this shape (96 assignments
    # padded to a tile, and a tile of slack for each of 4 held experts),
    # in each of 4 expert layers; the gauge is the newest flush's fill
    assert last["buffer_rows"] == [4 * 640.0] * 10
    assert buffers == 30 * 4 * 640
    assert fill == pytest.approx(sum(last["held_rows"]) / (10 * 4 * 640))
    # the older keys are what the roofline reader under
    # benchmarks/layer_metrics/ takes
    assert set(last) == {"held_rows", "rows_elsewhere", "max_expert_rows",
                         "buffer_rows", "n_layers", "top_k", "expert_shape",
                         "profiled"}
    assert float(jnp.abs(bias).max()) > 0     # the controller moved it


def test_bfloat16_compute_keeps_float32_state_and_a_finite_loss():
    model = _model(dtype="bfloat16", remat=True)
    batch = next(model.data.train_batches(0, 2))
    loss, (state, _) = jax.jit(model.loss_fn)(
        model.state.params, model.state.model_state, batch, None)
    assert all(leaf.dtype == jnp.float32
               for leaf in jax.tree.leaves(model.state.params))
    assert np.isfinite(float(loss))
    assert all(leaf.dtype == jnp.float32 for leaf in jax.tree.leaves(state))
    model.cleanup()


def test_eval_reports_the_training_loss_and_moves_no_bias():
    model = _model()
    batch = next(model.data.train_batches(0, 2))
    state = model.state.model_state
    train = model.loss_fn(model.state.params, state, batch, None)
    val = model.eval_fn(model.state.params, state, batch)
    assert float(val["loss"]) == pytest.approx(float(train[0]), rel=1e-6)
    assert float(jnp.abs(train[1][0]["router_state"]["Layer_1"]["moe"]
                         ["bias"]).max()) > 0
    model.cleanup()


def test_a_pattern_without_experts_has_no_router_state():
    model = _model(pattern="M*M")
    assert "router_state" not in model.state.model_state
    recorder = Recorder(rank=0, size=1, print_freq=0)
    model.compile_iter_fns("avg")
    model.begin_epoch(0)
    before = len(N.routing_log)
    model.train_iter(0, recorder)
    model._flush_metrics(recorder)
    assert len(N.routing_log) == before
    assert np.isfinite(recorder.train_losses[-1])
    model.cleanup()


def test_what_the_class_refuses():
    for bad, said in ((dict(pattern="MXE"), "one of"),
                      (dict(pattern=""), "one of"),
                      (dict(n_kv_heads=3), "whole number"),
                      (dict(n_groups=3), "whole number"),
                      (dict(top_k=17), "top_k=17 of 16")):
        with pytest.raises(ValueError, match=said):
            _model(**bad)
    assert N.NemotronHLM.decode_capable is False


def test_the_zoo_builds_it_by_name():
    from theanompi_tpu.models import MODEL_ZOO
    from theanompi_tpu.rules import resolve_model_class

    assert resolve_model_class(*MODEL_ZOO["nemotron_h_lm"]) is N.NemotronHLM
    assert N.NemotronHLM.name == "nemotron_h_lm"
    assert N.NemotronHLM.default_config().optimizer == "adamw"


def _configuration():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron_twotower_30b.json")) as f:
        return json.load(f)


def test_the_configurations_model_has_667_million_parameters(monkeypatch):
    """``jax.eval_shape`` of the model at the configuration file's
    arguments, nothing materialised: 666 962 944 parameters by layer
    kind as the file's ``deployment.parameters`` counts them."""
    config = _configuration()

    def shapes_only(self, config=None, **_kw):
        self.config = config or self.default_config()

    monkeypatch.setattr(TpuModel, "__init__", shapes_only)
    kwargs = {k: (tuple(v) if isinstance(v, list) else v)
              for k, v in config["model"]["kwargs"].items()}
    model = N.NemotronHLM(seq_len=2048, **kwargs)
    tree = jax.eval_shape(model.build_module().init, jax.random.key(0),
                          jax.ShapeDtypeStruct((1, 2048), jnp.int32))
    count = lambda t: sum(int(np.prod(a.shape))  # noqa: E731
                          for a in jax.tree.leaves(t))
    params = tree["params"]
    assert count(params["Layer_0"]) == 38_744_896
    assert count(params["Layer_1"]) == 100_125_312
    assert count(params["Layer_5"]) == 23_399_040
    assert count(params["embed"]) == count(params["head"]) == 16384 * 2688
    assert count(params) == 666_962_944
    assert count(params) * 16 / 1e9 == pytest.approx(10.67, abs=0.005)
    assert count(tree["router_state"]) == 4 * 128
    assert "666 962 944" in config["deployment"]["parameters"]


def test_the_models_flop_count_is_the_benchmarks_and_the_issues():
    """One function behind the program's MFU and the benchmark's; at the
    published sizes 2.0 GFLOP a trained token, of which the Mamba-2
    layers are 48%, the expert layers 29%, the head 13%, attention
    10%."""
    flops_lib = _load("flops", "nemotron_h.py")
    assert flops_lib.train_flops_per_sample is N.nemotron_h_train_flops
    kwargs = dict(_configuration()["flops"]["kwargs"], seq_len=2048)
    per_token = N.nemotron_h_train_flops(**kwargs) / 2048
    assert per_token == pytest.approx(2.0e9, rel=0.005)

    def without(kind):
        return N.nemotron_h_train_flops(**dict(
            kwargs, pattern=PATTERN.replace(kind, ""))) / 2048

    shares = {kind: (per_token - without(kind)) / per_token
              for kind in "ME*"}
    head = 6 * 2688 * 16384 / per_token
    assert shares["M"] == pytest.approx(0.48, abs=0.01)
    assert shares["E"] == pytest.approx(0.29, abs=0.01)
    assert shares["*"] == pytest.approx(0.10, abs=0.01)
    assert head == pytest.approx(0.13, abs=0.01)
    assert sum(shares.values()) + head == pytest.approx(1.0)
    # the forward MACs of one Mamba-2 layer, by hand
    one = N.nemotron_h_train_flops(**dict(kwargs, pattern="M", vocab=0))
    assert one / 2048 / 6 == pytest.approx(
        2688 * 10304 + 4096 * 2688 + 128 * 8 * 128 + 128 * 4096
        + 2 * 4096 * 128)
    # the grouped products' own count: two products a row, six in all
    assert flops_lib.expert_matmul_flops(
        rows=100, d_model=2688, expert_width=1856) == 12 * 100 * 2688 * 1856
    assert flops_lib.expert_matmul_bytes(
        rows=100, layer_steps=4, held_count=8, d_model=2688,
        expert_width=1856) == 6 * 2 * (100 * (2688 + 1856)
                                       + 4 * 8 * 2688 * 1856)
    model = _model()
    assert model.train_flops_per_sample == N.nemotron_h_train_flops(
        **{k: TINY[k] for k in TINY if k != "conv_kernel"}, held_count=4)
    model.cleanup()
