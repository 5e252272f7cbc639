"""SmallThinkerLM (models/smallthinker.py): global attention without
positions one layer in four beside sliding-window attention with RoPE, a
router read before attention, softmax top-k ReGLU experts, on the normal
training path, at small sizes on the CPU, against the benchmark's plain
reference (benchmarks/reference/smallthinker_21b.py)."""

import importlib.util
import json
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu import monitor
from theanompi_tpu.models import smallthinker as S
from theanompi_tpu.models.base import ModelConfig, TpuModel
from theanompi_tpu.parallel.mesh import data_mesh
from theanompi_tpu.utils.recorder import Recorder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYOUT = [0, 1, 1, 1]
TINY = dict(vocab=64, seq_len=16, d_model=32, n_layers=4,
            rope_layout=LAYOUT, sliding_window_layout=LAYOUT, window=5,
            n_heads=4, n_kv_heads=2, head_dim=8, n_experts=16, top_k=3,
            expert_width=12)
REFERENCE_KWARGS = dict(n_layers=4, rope_layout=LAYOUT,
                        sliding_window_layout=LAYOUT, window=5, top_k=3,
                        n_heads=4, n_kv_heads=2, rope_theta=1.5e6)


def _load(*parts):
    spec = importlib.util.spec_from_file_location(
        "bench_" + parts[-1][:-3], os.path.join(ROOT, "benchmarks", *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REFERENCE = _load("reference", "smallthinker_21b.py")


def _configuration():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "smallthinker_21b.json")) as f:
        return json.load(f)


def _model(devices=1, batch_size=2, held=(0, 4), dtype="float32",
           remat=False, **overrides):
    config = ModelConfig(batch_size=batch_size, optimizer="adamw",
                         learning_rate=3e-3, weight_decay=0.01,
                         lr_schedule="constant", compute_dtype=dtype,
                         remat=remat)
    return S.SmallThinkerLM(config=config,
                            mesh=data_mesh(devices, jax.devices()[:devices]),
                            verbose=False, held_experts=list(held),
                            **dict(TINY, **overrides))


def _spread(params, seed=1):
    """The seeded weights made to matter: the routers spread (an init of
    0.02 leaves every probability near 1/E), every norm weight moved off
    its init, the experts made large enough to move the loss."""
    def one(path, a):
        name = jax.tree_util.keystr(path)
        key = jax.random.fold_in(jax.random.key(seed), zlib.crc32(
            name.encode()) % 2**31)
        if "router" in name:
            return a * 30.0
        if "weight" in name:
            return a + 0.3 * jax.random.normal(key, a.shape)
        if "experts" in name:
            return a * 10.0
        return a
    return jax.tree_util.tree_map_with_path(one, params)


def _rel(got, want):
    got, want = (np.asarray(x, np.float64).ravel() for x in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def test_the_layer_kinds_follow_the_published_layouts():
    published = _configuration()["published"]
    kinds = S.layer_kinds(published["rope_layout"],
                          published["sliding_window_layout"])
    assert kinds == "GWWW" * 13
    assert S.layer_kinds([1, 0], [0, 1]) == "gw"


@pytest.mark.parametrize("held,kernels", [((0, 4), False), ((12, 4), False),
                                          ((0, 16), False), ((4, 4), True)])
def test_system_and_reference_agree_in_float32(monkeypatch, held, kernels):
    """Loss and EVERY leaf's gradient of the four-layer period to 1e-5
    (relative L2), for a share of the experts (either end) and for all of
    them, with the correction biases moved off zero; and once through
    the streamed attention kernels (interpreted, q blocks and key tiles
    of 8, so that the window of 5 cuts tiles)."""
    if kernels:
        from theanompi_tpu.ops import attention as A

        monkeypatch.setattr(A, "_Q_BLOCK", 8)
        real = S.fused_attention
        monkeypatch.setattr(S, "fused_attention", lambda *a, **kw: real(
            *a, impl="pallas", **kw))
    model = _model(held=held)
    batch = next(model.data.train_batches(0, 2))
    params = _spread(model.state.params)
    # the correction biases where a controller would have left them
    state = jax.tree.map(
        lambda b: b + 0.5 * jax.random.normal(jax.random.key(2), b.shape),
        model.state.model_state)
    model.state = model.state.replace(model_state=state)
    got_loss, got = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, state, batch, None)[0]))(params)
    inputs = REFERENCE.inputs(model, batch, None)
    assert sorted(inputs[2]) == [0, 1, 2, 3]
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: REFERENCE.loss(p, *inputs, held_experts=held,
                                 **REFERENCE_KWARGS)))(params)
    model.cleanup()
    assert _rel(got_loss, want_loss) < 1e-5
    flat = jax.tree_util.tree_leaves_with_path(want)
    assert len(flat) == len(jax.tree.leaves(got))
    for (path, w), g in zip(flat, jax.tree.leaves(got)):
        assert float(jnp.abs(w).max()) > 0, path
        assert _rel(g, w) < 1e-5, jax.tree_util.keystr(path)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """16 experts held as 4 x 4, top-3 of a softmax read before
    attention: the four chips' layers summed, with attention and the
    residual (what every chip computes alike) counted ONCE, are the
    reference's whole layer; each share alone is the reference's share,
    and every assignment is some chip's."""
    model = _model(held=(0, 16))
    params = _spread(model.state.params)["Layer_1"]
    model.cleanup()
    x = jax.random.normal(jax.random.key(3), (2, 16, 32))
    bias = 0.3 * jax.random.normal(jax.random.key(7), (16,))
    cfg = dict(top_k=3, n_heads=4, n_kv_heads=2, rope_theta=1.5e6,
               rms_norm_eps=1e-6)

    def reference(share, held):
        return REFERENCE._layer(x, share, bias, True, 5,
                                dict(cfg, held=held))

    whole = reference(params, (0, 16))
    common = reference(params, (0, 0))       # attention and the residual
    layer = S.SmallThinkerLayer(
        "W", dict(n_heads=4, n_kv_heads=2, head_dim=8),
        dict(n_experts=16, top_k=3, expert_width=12, held_experts=(0, 4)),
        window=5)
    table = S.rotary_table(jnp.arange(16), 8, 1.5e6)
    total, rows = 0, 0
    for chip in range(4):
        held = (4 * chip, 4)
        moe = {name: params["moe"][name][4 * chip:4 * chip + 4]
               for name in ("experts_gate", "experts_up", "experts_down")}
        share = dict(params, moe=moe)
        out, stats = layer.clone(moe=dict(layer.moe, held_experts=held)).apply(
            {"params": share, "router_state": {"moe": {"bias": bias}}},
            x, table)
        np.testing.assert_allclose(out, reference(share, held), rtol=1e-5,
                                   atol=1e-5)
        total = total + (out - common)
        rows += stats["held_rows"]
    np.testing.assert_allclose(total + common, whole, rtol=1e-5, atol=1e-5)
    assert rows == 3 * 32
    assert float(jnp.abs(whole - common).max()) > 0.01   # the experts' part


def test_the_router_reads_the_input_before_attention():
    """The router's scores are of ``norm_in(x)``, attention's input: the
    experts a token gets do not move when attention's output does (the
    first layer's ``o_proj`` zeroed), and the expert layer's input
    does."""
    model = _model(held=(0, 16))
    batch = next(model.data.train_batches(0, 2))
    params = _spread(model.state.params)
    silent = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a)
        if "'Layer_0'" in jax.tree_util.keystr(path)
        and "o_proj" in jax.tree_util.keystr(path) else a, params)
    seen = []
    for p in (params, silent):
        _, captured = model.module.apply(
            {"params": p, **model.state.model_state}, batch[0],
            mutable=["intermediates"], capture_intermediates=lambda m, _:
            m.name in ("router", "post_norm"))
        layer = captured["intermediates"]["Layer_0"]
        seen.append((layer["router"]["__call__"][0],
                     layer["post_norm"]["__call__"][0]))
    model.cleanup()
    np.testing.assert_array_equal(seen[0][0], seen[1][0])
    assert float(jnp.abs(seen[0][1] - seen[1][1]).max()) > 1e-3


def test_remat_changes_no_value():
    losses = []
    for remat in (False, True):
        model = _model(remat=remat)
        batch = next(model.data.train_batches(0, 2))
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: model.loss_fn(p, model.state.model_state, batch,
                                    None)[0]))(_spread(model.state.params))
        losses.append((loss, grads))
        model.cleanup()
    assert float(losses[0][0]) == pytest.approx(float(losses[1][0]), rel=1e-6)
    for a, b in zip(*(jax.tree.leaves(g) for _, g in losses)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


def test_trains_through_the_base_loop_and_counts_its_rows(tmp_path):
    """begin_epoch -> train_iter -> _flush_metrics on the BSP step over
    two devices, each layer recomputed: the loss falls, the controller
    moves its biases, and each flush hands the rows the held experts
    multiplied to ``monitor`` and to ``smallthinker.routing_log``."""
    S.routing_log.clear()
    model = _model(devices=2, batch_size=2, remat=True)
    model.compile_iter_fns("avg")
    recorder = Recorder(rank=0, size=2, print_freq=0)
    with monitor.session(str(tmp_path)):
        model.begin_epoch(0)
        it = 0
        for flush in range(3):
            for _ in range(10):
                it += model.train_iter(it, recorder)
            model._flush_metrics(recorder)
        registry = monitor.registry()
        held = registry.value("moe/held_rows")
        elsewhere = registry.value("moe/rows_elsewhere")
        share = registry.value("moe/held_share")
    biases = jax.tree.leaves(model.state.model_state)
    model.cleanup()
    losses = recorder.train_losses
    assert len(losses) == 30 and losses[-1] < losses[0] - 0.1
    assert all(float(jnp.abs(b).max()) > 0 for b in biases)
    entry = S.routing_log[-1]
    assert len(entry["held_rows"]) == 10
    assert (entry["n_layers"], entry["top_k"], entry["expert_shape"]) == (
        4, 3, (4, 32, 12))
    # a shard has 2 x 16 tokens, 3 assignments each, in each of 4 layers;
    # the counts are the shards' mean, every assignment here or elsewhere
    assert held + elsewhere == 30 * 4 * 3 * 32
    assert share == pytest.approx(sum(entry["held_rows"]) / (10 * 4 * 3 * 32))


def test_bfloat16_compute_keeps_float32_state_and_a_finite_loss():
    model = _model(dtype="bfloat16", remat=True)
    batch = next(model.data.train_batches(0, 2))
    loss, (state, _) = jax.jit(model.loss_fn)(
        model.state.params, model.state.model_state, batch, None)
    assert all(leaf.dtype == jnp.float32 for leaf in jax.tree.leaves(state))
    assert all(leaf.dtype == jnp.float32
               for leaf in jax.tree.leaves(model.state.params))
    assert np.isfinite(float(loss))
    model.cleanup()


def test_what_the_class_refuses():
    for bad, said in ((dict(n_kv_heads=3), "whole number"),
                      (dict(top_k=17), "top_k=17 of 16"),
                      (dict(rope_layout=[0, 1]), "layouts of 2")):
        with pytest.raises(ValueError, match=said):
            _model(**bad)
    assert S.SmallThinkerLM.decode_capable is False


def test_the_zoo_builds_it_by_name():
    from theanompi_tpu.models import MODEL_ZOO
    from theanompi_tpu.rules import resolve_model_class

    assert resolve_model_class(*MODEL_ZOO["smallthinker_lm"]) \
        is S.SmallThinkerLM
    assert S.SmallThinkerLM.default_config().optimizer == "adamw"


def test_the_configurations_model_has_657_million_parameters(monkeypatch):
    """``jax.eval_shape`` of the model at the configuration file's
    arguments, nothing materialised: 656 529 920 parameters as the
    file's ``deployment.parameters`` counts them."""
    config = _configuration()

    def shapes_only(self, config=None, **_kw):
        self.config = config or self.default_config()

    monkeypatch.setattr(TpuModel, "__init__", shapes_only)
    kwargs = {k: (tuple(v) if isinstance(v, list) else v)
              for k, v in config["model"]["kwargs"].items()}
    model = S.SmallThinkerLM(seq_len=16384, **kwargs)
    tree = jax.eval_shape(model.build_module().init, jax.random.key(0),
                          jax.ShapeDtypeStruct((1, 16384), jnp.int32))
    count = lambda t: sum(int(np.prod(a.shape))  # noqa: E731
                          for a in jax.tree.leaves(t))
    params = tree["params"]
    assert count(params["Layer_0"]["attention"]) == 20_971_520
    assert count(params["Layer_0"]["router"]) == 163_840
    assert count(params["Layer_0"]["moe"]) == 94_371_840
    assert count(params["Layer_0"]) == count(params["Layer_1"]) \
        == 115_512_320
    assert count(params["embed"]) == count(params["head"]) == 37984 * 2560
    assert count(params) == 656_529_920
    assert count(params) * 16 / 1e9 == pytest.approx(10.50, abs=0.005)
    assert "656 529 920" in config["deployment"]["parameters"]


def test_the_models_flop_count_is_the_benchmarks_and_the_hand_count():
    """One function behind the program's MFU and the benchmark's; at the
    published sizes 705.93 M forward FLOPs a token (2.118 GFLOP trained),
    of which window attention's products are 22%, global attention's
    17%, the projections 24%, the expert layers 10%, the head 28%."""
    flops_lib = _load("flops", "smallthinker.py")
    assert flops_lib.train_flops_per_sample is S.smallthinker_train_flops
    kwargs = dict(_configuration()["flops"]["kwargs"], seq_len=16384)
    per_token = S.smallthinker_train_flops(**kwargs) / 16384 / 3
    assert per_token == pytest.approx(705.93e6, rel=1e-5)
    s, w = 16384, 4096
    window = 3 * 4 * 128 * 28 * (w * (w + 1) / 2 + (s - w) * w) / s
    assert (w * (w + 1) / 2 + (s - w) * w) / s == 3584.125
    glob = 4 * 128 * 28 * (s + 1) / 2
    projections = 4 * 2 * 20_971_520
    experts = 4 * 2 * (163_840 + 3 * 2560 * 768 * 6 * 16 / 64)
    head = 2 * 2560 * 37984
    assert window + glob + projections + experts + head == pytest.approx(
        per_token)
    for part, share in ((window, 0.22), (glob, 0.17), (projections, 0.24),
                        (experts, 0.10), (head, 0.28)):
        assert part / per_token == pytest.approx(share, abs=0.006)
    model = _model()
    assert model.train_flops_per_sample == S.smallthinker_train_flops(
        **{k: TINY[k] for k in TINY}, held_count=4)
    model.cleanup()
