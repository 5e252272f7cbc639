"""Qwen3NextLM (models/qwen3_next.py): Gated DeltaNet layers three in
four, gated attention in the fourth, a softmax top-k expert layer
beside a gated shared expert in each, on the normal training path, at
small sizes on the CPU, against the benchmark's plain reference
(benchmarks/reference/qwen3_next_80b.py), whose delta rule is stepped
one token at a time."""

import importlib.util
import json
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu import monitor
from theanompi_tpu.models import qwen3_next as Q
from theanompi_tpu.models.base import ModelConfig, TpuModel
from theanompi_tpu.parallel.mesh import data_mesh
from theanompi_tpu.utils.recorder import Recorder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(vocab=64, seq_len=16, d_model=32, n_layers=4,
            full_attention_interval=4, linear_key_heads=2,
            linear_value_heads=4, linear_key_dim=8, linear_value_dim=8,
            chunk=8, n_experts=16, top_k=3, expert_width=12, shared_width=12,
            n_heads=4, n_kv_heads=2, head_dim=16)
REFERENCE_KWARGS = dict(n_layers=4, full_attention_interval=4,
                        linear_key_heads=2, linear_value_heads=4,
                        linear_key_dim=8, top_k=3, n_heads=4, n_kv_heads=2,
                        partial_rotary_factor=0.25, rope_theta=1e7,
                        aux_loss_coef=1e-3)


def _load(*parts):
    spec = importlib.util.spec_from_file_location(
        "bench_" + parts[-1][:-3], os.path.join(ROOT, "benchmarks", *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REFERENCE = _load("reference", "qwen3_next_80b.py")


def _configuration():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "qwen3_next_80b.json")) as f:
        return json.load(f)


def _model(devices=1, batch_size=2, held=(0, 4), dtype="float32",
           remat=False, **overrides):
    config = ModelConfig(batch_size=batch_size, optimizer="adamw",
                         learning_rate=3e-3, weight_decay=0.01,
                         lr_schedule="constant", compute_dtype=dtype,
                         remat=remat)
    return Q.Qwen3NextLM(config=config,
                         mesh=data_mesh(devices, jax.devices()[:devices]),
                         verbose=False, held_experts=list(held),
                         **dict(TINY, **overrides))


def _spread(params, seed=1):
    """The seeded weights made to matter: the routers spread (an init of
    0.02 leaves every probability near 1/E), every zero-centred norm
    weight and the decays' parameters moved off their inits."""
    def one(path, a):
        name = jax.tree_util.keystr(path)
        key = jax.random.fold_in(jax.random.key(seed), zlib.crc32(
            name.encode()) % 2**31)
        if "router" in name:
            return a * 30.0
        if "weight" in name or "A_log" in name or "dt_bias" in name:
            return a + 0.3 * jax.random.normal(key, a.shape)
        return a
    return jax.tree_util.tree_map_with_path(one, params)


def test_the_layer_kinds_follow_the_interval():
    assert Q.layer_kinds(4, 4) == "LLLF"
    assert Q.layer_kinds(8, 4) == "LLLFLLLF"
    assert Q.layer_kinds(3, 2) == "LFL"


@pytest.mark.parametrize("held", [(0, 4), (12, 4), (0, 16)])
def test_system_and_reference_agree_in_float32(held):
    """Loss and EVERY leaf's gradient of the four-layer period, for a
    share of the experts (either end) and for all of them, with the
    correction biases moved off zero."""
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
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(want)
    assert len(flat) == len(jax.tree.leaves(got))
    for (path, w), g in zip(flat, jax.tree.leaves(got)):
        scale = float(jnp.abs(w).max())
        assert scale > 0, path
        # a floor of 1e-8: the decays' gradients are ~1e-6, and the two
        # forms' float32 sums differ there by ~1e-9
        np.testing.assert_allclose(g, w, rtol=1e-3,
                                   atol=max(1e-4 * scale, 1e-8),
                                   err_msg=jax.tree_util.keystr(path))


def test_the_zero_centred_norm_at_zero_is_the_plain_normalisation():
    x = jax.random.normal(jax.random.key(0), (3, 5, 24)) * 3.0 + 1.0
    norm = Q.ZeroCentredRMSNorm(1e-6)
    variables = norm.init(jax.random.key(1), x)
    assert float(jnp.abs(variables["params"]["weight"]).max()) == 0.0
    plain = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(norm.apply(variables, x), plain, rtol=1e-5)
    w = jnp.linspace(-0.5, 0.5, 24)
    np.testing.assert_allclose(
        norm.apply({"params": {"weight": w}}, x), plain * (1.0 + w),
        rtol=1e-5)


def _expert_layer(n_experts=32, held=(0, 32), seed=0):
    layer = Q.SparseMoe(n_experts=n_experts, top_k=6,
                        expert_width=8, shared_width=8, held_experts=held)
    u = jax.random.normal(jax.random.key(seed), (2, 25, 16))
    params = layer.init(jax.random.key(seed + 1), u)["params"]
    # an init of 0.02 leaves every probability near 1/E and every
    # expert's output near 0: spread them
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a * (30.0 if "router" in jax.tree_util.keystr(path)
                             else 10.0), params)
    return layer, u, params


@pytest.mark.parametrize("gate", [-30.0, 0.0, 30.0])
def test_the_shared_expert_is_scaled_by_its_sigmoid_gate(gate):
    """The layer is its routed part plus ``sigmoid(u w_sg)`` times the
    shared expert, a gate a token: along ``w_sg`` a scale of 0 lets half
    through, and +-30 shuts it for the tokens on one side and opens it
    for those on the other."""
    layer, u, params = _expert_layer()
    rows = u.reshape(-1, 16)
    direction = jnp.ones((16, 1)) / jnp.sqrt(16.0)
    params = dict(params, shared_expert_gate={"kernel": direction * gate})
    zero = {"bias": jnp.zeros(32)}
    out, _ = layer.apply({"params": params, "router_state": zero}, u)
    s = params["shared_expert"]
    silent = dict(params, shared_expert=dict(
        s, down={"kernel": jnp.zeros_like(s["down"]["kernel"])}))
    routed, _ = layer.apply({"params": silent, "router_state": zero}, u)
    shared = (jax.nn.silu(rows @ s["gate"]["kernel"])
              * (rows @ s["up"]["kernel"])) @ s["down"]["kernel"]
    opening = jax.nn.sigmoid(rows @ direction * gate)
    np.testing.assert_allclose(out.reshape(-1, 16),
                               routed.reshape(-1, 16) + opening * shared,
                               rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(shared).max()) > 1e-3


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """32 experts held as 16 x 2, top-6 of a softmax: the routed parts of
    the sixteen chips' layers summed, with the gated shared expert
    counted ONCE, are the reference's whole layer; each share alone is
    the reference's share, and every assignment is some chip's."""
    whole_layer, u, params = _expert_layer()
    cfg = dict(top_k=6)
    bias = 0.3 * jax.random.normal(jax.random.key(7), (32,))
    whole, _, _ = REFERENCE._moe(u, params, bias, dict(cfg, held=(0, 32)))
    none_held, _, _ = REFERENCE._moe(u, params, bias, dict(cfg, held=(0, 0)))
    shared = none_held              # the gated shared expert alone
    routed, rows = 0, 0
    for chip in range(16):
        held = (2 * chip, 2)
        layer = whole_layer.clone(held_experts=held)
        share = dict(params, **{
            name: params[name][2 * chip:2 * chip + 2]
            for name in ("experts_gate", "experts_up", "experts_down")})
        out, stats = layer.apply(
            {"params": share, "router_state": {"bias": bias}}, u)
        np.testing.assert_allclose(
            out, REFERENCE._moe(u, share, bias, dict(cfg, held=held))[0],
            rtol=1e-4, atol=1e-5)
        routed = routed + (out - shared)
        rows += stats["held_rows"]
    np.testing.assert_allclose(routed + shared, whole, rtol=1e-4, atol=1e-5)
    assert rows == 6 * 50
    assert float(jnp.abs(whole - shared).max()) > 0.01   # the routed part


def test_the_balancing_loss_is_the_switch_form_over_all_layers():
    """``E sum_e f_e P_e`` over the layers' tokens together, by hand from
    the routers; no gradient through the counts; the loss the model
    trains on is the cross-entropy plus 0.001 of it."""
    model = _model()
    batch = next(model.data.train_batches(0, 2))
    params = _spread(model.state.params)
    tokens = batch[0]
    # what each layer's expert part was handed: its post-norm's output
    (_, routing), seen_by = model.module.apply(
        {"params": params, **model.state.model_state}, tokens,
        mutable=["intermediates"],
        capture_intermediates=lambda module, _: module.name == "post_norm")
    captured = [seen_by["intermediates"][f"Layer_{i}"]["post_norm"][
        "__call__"][0] for i in range(4)]
    load, probs = 0.0, 0.0
    for i, u in enumerate(captured):
        p = jax.nn.softmax(u.reshape(-1, 32) @ params[f"Layer_{i}"]["moe"][
            "router"]["kernel"], -1)
        chosen = jax.lax.top_k(p, 3)[1]
        load = load + jnp.bincount(chosen.ravel(), length=16)
        probs = probs + p.sum(0)
    seen = 4 * tokens.size
    want = 16 * jnp.sum(load / seen * probs / seen)
    assert float(routing["moe_aux_loss"]) == pytest.approx(float(want),
                                                           rel=1e-5)
    loss, (_, metrics) = model.loss_fn(params, model.state.model_state,
                                       batch, None)
    ce = float(loss) - 1e-3 * float(routing["moe_aux_loss"])
    assert float(metrics["moe_aux_loss"]) == pytest.approx(float(want),
                                                           rel=1e-5)
    assert 0 < ce < float(loss)
    model.cleanup()


def test_the_tree_differs_by_layer_kind():
    model = _model()
    params = model.state.params
    for i, kind in enumerate("LLLF"):
        mixer = "linear_attention" if kind == "L" else "attention"
        assert sorted(params[f"Layer_{i}"]) == sorted(
            [mixer, "input_norm", "post_norm", "moe"])
    assert sorted(params["Layer_0"]["linear_attention"]) == [
        "A_log", "conv_kernel", "dt_bias", "in_proj_ba", "in_proj_qkvz",
        "norm_weight", "out_proj"]
    assert sorted(params["Layer_3"]["attention"]) == [
        "k_norm", "k_proj", "o_proj", "q_norm", "q_proj", "v_proj"]
    assert params["Layer_3"]["attention"]["q_proj"]["kernel"].shape == (
        32, 2 * 4 * 16)                      # each head's q and its gate
    assert sorted(params["Layer_0"]["moe"]) == [
        "experts_down", "experts_gate", "experts_up", "router",
        "shared_expert", "shared_expert_gate"]
    assert params["head"]["kernel"].shape == (32, 64)
    assert sorted(model.state.model_state["router_state"]) == [
        "Layer_0", "Layer_1", "Layer_2", "Layer_3"]
    a = jnp.exp(params["Layer_0"]["linear_attention"]["A_log"])
    assert float(a.min()) > 0 and float(a.max()) <= 16
    assert float(jnp.abs(params["Layer_0"]["linear_attention"]["dt_bias"]
                         - 1.0).max()) == 0.0
    model.cleanup()


def test_remat_changes_no_value():
    losses = []
    for remat in (False, True):
        model = _model(remat=remat)
        batch = next(model.data.train_batches(0, 2))
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: model.loss_fn(p, model.state.model_state, batch,
                                    None)[0]))(
            _spread(model.state.params))
        losses.append((loss, grads))
        model.cleanup()
    assert float(losses[0][0]) == pytest.approx(float(losses[1][0]), rel=1e-6)
    for a, b in zip(*(jax.tree.leaves(g) for _, g in losses)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


def test_trains_through_the_base_loop_and_counts_its_rows(tmp_path):
    """begin_epoch -> train_iter -> _flush_metrics on the BSP step over
    two devices, each layer recomputed: the loss falls, and each flush
    hands the rows the held experts multiplied and the balancing loss to
    ``monitor`` and to ``qwen3_next.routing_log``."""
    Q.routing_log.clear()
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
        aux = registry.value("moe/aux_loss")
    model.cleanup()
    losses = recorder.train_losses
    assert len(losses) == 30 and losses[-1] < losses[0] - 0.1
    entry = Q.routing_log[-1]
    assert len(entry["held_rows"]) == len(entry["aux_loss"]) == 10
    assert (entry["n_layers"], entry["top_k"], entry["expert_shape"]) == (
        4, 3, (4, 32, 12))
    # a shard has 2 x 16 tokens, 3 assignments each, in each of 4 layers;
    # the counts are the shards' mean, every assignment here or elsewhere
    assert held + elsewhere == 30 * 4 * 3 * 32
    assert share == pytest.approx(sum(entry["held_rows"]) / (10 * 4 * 3 * 32))
    # at uniform routing the Switch form reads top_k
    assert aux == pytest.approx(entry["aux_loss"][-1]) and 2.5 < aux < 6


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
                      (dict(linear_key_heads=3), "whole number"),
                      (dict(top_k=17), "top_k=17 of 16")):
        with pytest.raises(ValueError, match=said):
            _model(**bad)
    assert Q.Qwen3NextLM.decode_capable is False


def test_the_zoo_builds_it_by_name():
    from theanompi_tpu.models import MODEL_ZOO
    from theanompi_tpu.rules import resolve_model_class

    assert resolve_model_class(*MODEL_ZOO["qwen3_next_lm"]) is Q.Qwen3NextLM
    assert Q.Qwen3NextLM.default_config().optimizer == "adamw"


def test_the_configurations_model_has_626_million_parameters(monkeypatch):
    """``jax.eval_shape`` of the model at the configuration file's
    arguments, nothing materialised: 625 667 136 parameters by layer
    kind as the file's ``deployment.parameters`` counts them."""
    config = _configuration()

    def shapes_only(self, config=None, **_kw):
        self.config = config or self.default_config()

    monkeypatch.setattr(TpuModel, "__init__", shapes_only)
    kwargs = {k: (tuple(v) if isinstance(v, list) else v)
              for k, v in config["model"]["kwargs"].items()}
    model = Q.Qwen3NextLM(seq_len=2048, **kwargs)
    tree = jax.eval_shape(model.build_module().init, jax.random.key(0),
                          jax.ShapeDtypeStruct((1, 2048), jnp.int32))
    count = lambda t: sum(int(np.prod(a.shape))  # noqa: E731
                          for a in jax.tree.leaves(t))
    params = tree["params"]
    assert count(params["Layer_0"]["linear_attention"]) == 33_718_464
    assert count(params["Layer_0"]) == 138_582_208
    assert count(params["Layer_3"]) == 132_127_232
    assert count(params["embed"]) == count(params["head"]) == 18992 * 2048
    assert count(params) == 625_667_136
    assert count(params) * 16 / 1e9 == pytest.approx(10.01, abs=0.005)
    assert "625 667 136" in config["deployment"]["parameters"]


def test_the_models_flop_count_is_the_benchmarks_and_the_issues():
    """One function behind the program's MFU and the benchmark's; at the
    published sizes 1.249 GFLOP a trained token (ISSUE 38: 418 M forward),
    of which the Gated DeltaNet mixers are 52%, attention 17%, the
    expert layers 12%, the head 19%; the rule's work a chunk is the
    benchmark's own count."""
    flops_lib = _load("flops", "qwen3_next.py")
    rule_lib = _load("flops", "qwen3_next_delta_rule.py")
    assert flops_lib.train_flops_per_sample is Q.qwen3_next_train_flops
    kwargs = dict(_configuration()["flops"]["kwargs"], seq_len=2048)
    per_token = Q.qwen3_next_train_flops(**kwargs) / 2048
    assert per_token / 3 == pytest.approx(416.2e6, rel=0.001)

    def without(**zero):
        return Q.qwen3_next_train_flops(**dict(kwargs, **zero)) / 2048

    head = 6 * 2048 * 18992
    experts = 4 * 6 * (2048 * 512 + 3 * 2048 * 512 + 2048
                       + 3 * 2048 * 512 * 10 * 32 / 512)
    attention = (6 * (2048 * (2 * 16 + 2 * 2) * 256 + 16 * 256 * 2048)
                 + 6 * 16 * 256 * 2049)
    linear = per_token - head - experts - attention
    assert without(vocab=0) == pytest.approx(per_token - head)
    shares = [x / per_token for x in (linear, attention, experts, head)]
    for got, want in zip(shares, (0.52, 0.17, 0.12, 0.19)):
        assert got == pytest.approx(want, abs=0.01)
    # the rule's forward work a token and value head, and the
    # benchmark's count a chunk
    macs = Q.delta_rule_macs(chunk=64, key_dim=128, value_dim=128)
    assert macs * 64 == rule_lib._chunk_macs(64, 128, 128)
    assert rule_lib.delta_rule_flops(
        which="fwd", batch=4, seq_len=2048, heads=32, key_dim=128,
        value_dim=128, chunk=64) == 2 * macs * 4 * 2048 * 32
    assert rule_lib.delta_rule_flops(
        which="bwd", batch=1, seq_len=64, heads=1, key_dim=8, value_dim=8,
        chunk=64) == 2 * rule_lib.delta_rule_flops(
        which="fwd", batch=1, seq_len=64, heads=1, key_dim=8, value_dim=8,
        chunk=64)
    model = _model()
    assert model.train_flops_per_sample == Q.qwen3_next_train_flops(
        **{k: TINY[k] for k in TINY}, held_count=4)
    model.cleanup()
