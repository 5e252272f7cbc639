"""The SmallThinker cell under tier-1: its dry runs (the whole harness
path at the files' ``dry_run`` sizes on one virtual CPU device), what
``BENCHMARK.json`` and the configuration declare for it, the four new
per-layer readers on hand-made events and on a step recorded on the chip
(``benchmarks/fixtures/smallthinker_21b_s16384_chip_events.json``), and
the reference check against planted faults."""

import importlib.util
import json
import os
import types

import pytest

from benchmarks import scope_shares, selfcheck
from benchmarks import trace as trace_lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(ROOT, "benchmarks", "fixtures",
                       "smallthinker_21b_s16384_chip_events.json")
CELL = "smallthinker_21b_s16384_x1"
CONFIG = "smallthinker_21b"
TRAFFIC = "lm_s16384_seg2_x1"
#: the accepted lists the cell joins, and the new metrics
LISTS = ("tokens_per_s_per_chip", "median_segment_rate.tok",
         "input_wait_share.tok", "device_ms_per_step.tok", "mfu.tok",
         "device_idle_share.tok", "peak_hbm_gb.tok",
         "recompiles_in_window.tok", "scope_coverage.tok",
         "backward_share.tok", "update_share.tok", "recompute_share.tok",
         "loss_share.tok")
NEW = {"smallthinker_window_attention_roofline_share": ("higher", "kernels"),
       "smallthinker_global_attention_roofline_share": ("higher", "kernels"),
       "smallthinker_attention_share": ("lower", "step program"),
       "smallthinker_expert_layer_share": ("lower", "step program")}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "bench_reader_" + name,
        os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _flops():
    spec = importlib.util.spec_from_file_location(
        "bench_flops_smallthinker",
        os.path.join(ROOT, "benchmarks", "flops", "smallthinker.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _configuration():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [0, 1])
def test_dry_run_of_the_cell(trace, capsys):
    """The cell's metrics on the line, and no CPU number under a device
    metric's name."""
    try:
        selfcheck.check_dry_run(CELL, 1, trace)
    except SystemExit as miss:
        pytest.fail(str(miss))
    said = capsys.readouterr().out
    assert "correct, nothing failed" in said
    assert "carries no CPU number" in said


def test_the_cell_and_its_metrics_are_declared():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, = (w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    for said in ("16384", "22%", "17%", "10%", "1536", "6144", "28%"):
        assert said in cell["why"]
    assert len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config, = (c for c in bench["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["num_hidden_layers",
                                 "moe_num_primary_experts", "vocab_size"]
    assert config["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
        "blob/main/config.json")
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    listed = {m["name"]: m.get("workloads") for m in
              bench["end_to_end"] + bench["per_layer"]}
    assert {name for name, cells in listed.items()
            if cells and CELL in cells} == set(LISTS) | set(NEW)
    for name, (better, layer) in NEW.items():
        metric, = (m for m in bench["per_layer"] if m["name"] == name)
        assert metric == {
            "name": name, "unit": "%", "source": "device_trace",
            "better": better, "layer": layer,
            "moves": "tokens_per_s_per_chip", "workloads": [CELL]}
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
    # appended together, in this order, after what was there
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(next(iter(NEW)))
    assert names[first:first + len(NEW)] == list(NEW)
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           TRAFFIC + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["batch_per_chip"], traffic["units_per_sample"],
            traffic["model_kwargs"]["seq_len"], traffic["segment_steps"]) \
        == (1, 16384, 16384, 2)


def test_the_configuration_keeps_every_published_width():
    """Only the depth, the experts held and the vocabulary differ from
    the source's config, which is copied whole; the model, the FLOP
    count and the reference are built from those same numbers."""
    config = _configuration()
    published = config["published"]
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == set(config["reduced"]) == set(config["reduced_why"]) \
        == {"num_hidden_layers", "moe_num_primary_experts", "vocab_size"}
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"],
            config["vocab_size"]) == (4, 16, 151936 // 4)
    assert config["layer_kinds_run"] == "GWWW"
    kwargs = config["model"]["kwargs"]
    assert (kwargs["d_model"], kwargs["n_layers"], kwargs["rope_layout"],
            kwargs["sliding_window_layout"], kwargs["window"],
            kwargs["n_heads"], kwargs["n_kv_heads"], kwargs["head_dim"],
            kwargs["n_experts"], kwargs["top_k"], kwargs["expert_width"],
            kwargs["rope_theta"], kwargs["rms_norm_eps"],
            kwargs["vocab"]) == (
        published["hidden_size"], config["num_hidden_layers"],
        published["rope_layout"], published["sliding_window_layout"],
        published["sliding_window_size"], published["num_attention_heads"],
        published["num_key_value_heads"], published["head_dim"],
        published["moe_num_primary_experts"],
        published["moe_num_active_primary_experts"],
        published["moe_ffn_hidden_size"], published["rope_theta"],
        published["rms_norm_eps"], config["vocab_size"])
    assert kwargs["held_experts"] == [0, config["moe_num_primary_experts"]]
    assert published["norm_topk_prob"] is True
    assert published["moe_primary_router_apply_softmax"] is True
    assert published["tie_word_embeddings"] is False
    assert published["max_position_embeddings"] == 16384
    flops = config["flops"]["kwargs"]
    assert all(flops[k] == kwargs[k] for k in flops if k != "held_count")
    assert flops["held_count"] == kwargs["held_experts"][1]
    reference = config["reference"]
    assert all(reference["kwargs"][k] == kwargs[k][:4]
               if k.endswith("layout") else reference["kwargs"][k] == kwargs[k]
               for k in reference["kwargs"])
    # unrouted leaves at both ends and of both attention kinds, a router
    assert set(reference["grad_rel_l2_tol"]) == {
        "embed/embedding", "final_norm/weight", "head/kernel",
        "Layer_0/attention/q_proj/kernel", "Layer_0/attention/o_proj/kernel",
        "Layer_1/attention/k_proj/kernel", "Layer_1/attention/o_proj/kernel",
        "Layer_0/router/kernel"}
    assert config["model_config"]["remat"] is True
    for key in ("router_input", "window_edge", "qk_norm_and_bias", "router",
                "router_balance", "init", "rope", "norms", "optimizer",
                "compute_dtype", "data", "sequence_length"):
        assert config["assumed"][key]
    assert "secondary experts" in config["departures"][0]
    assert config["deployment"]["expert_parallel_chips"] == 4
    assert "1 536" in config["deployment"]["tokens"]


def _handmade_trace():
    """One chip, a window of 1 000 ns, 850 of them busy: the window
    kernels (100 + 40 + 60), a global forward (50), a router fusion
    (100) that an expert fusion overlaps by 50, and XLA's own work; the
    step map's scopes by op."""
    ops = [
        ("smallthinker_window_attention_fwd.1",
         "custom-call tpu_custom_call", 0, 100),
        ("smallthinker_window_attention_bwd_kv",
         "custom-call tpu_custom_call", 100, 140),
        ("smallthinker_window_attention_bwd_q",
         "custom-call tpu_custom_call", 140, 200),
        ("smallthinker_global_attention_fwd",
         "custom-call tpu_custom_call", 200, 250),
        ("fusion.1", "fusion kLoop", 250, 350),
        ("fusion.2", "fusion kLoop", 300, 400),
        ("while.3", "while", 400, 800),
        ("fusion.4", "fusion kOutput", 400, 800),
        ("fusion.5", "fusion kLoop", 850, 900),
    ]
    mapped = {
        "smallthinker_window_attention_fwd.1": (
            "forward", "Net/Layer_1/attention/smallthinker/window_attention"),
        "smallthinker_window_attention_bwd_kv": (
            "backward", "Net/Layer_1/attention/smallthinker/window_attention"),
        "smallthinker_window_attention_bwd_q": (
            "backward", "Net/Layer_1/attention/smallthinker/window_attention"),
        "smallthinker_global_attention_fwd": (
            "recompute", "Net/Layer_0/attention/smallthinker/global_attention"),
        "fusion.1": ("forward", "Net/Layer_0/smallthinker/router"),
        "fusion.2": ("backward", "Net/Layer_0/moe/smallthinker/experts/rung"),
        "while.3": ("backward", "Net/Layer_1/moe/smallthinker/experts"),
        "fusion.4": ("backward", "Net/lm/loss"),
        "fusion.5": ("forward", "Net/Layer_2/input_norm"),
    }
    return trace_lib.from_events({0: ops}, [("bench/segment", 0, 1000)]), \
        mapped


def _run(trace, mapped, monkeypatch, steps=2):
    from benchmarks import peaks

    monkeypatch.setattr(scope_shares, "step_map", lambda run: mapped)
    return types.SimpleNamespace(
        trace=trace, trace_lib=trace_lib, on_device=True, traced_steps=steps,
        peak=peaks.peak("TPU v5 lite"), phases={})


def test_the_readers_on_a_handmade_list_of_events(monkeypatch):
    """Each reader's pattern and arithmetic on events worked out by hand:
    busy 850 ns; the attention scopes 250 ns; the router's and the
    experts' 100 + 100 - their overlap (a ``while`` holds others and is
    never counted); the window calls 200 ns by name, one forward and one
    backward; None with nothing to read."""
    trace, mapped = _handmade_trace()
    run = _run(trace, mapped, monkeypatch)
    assert trace_lib.busy_ns(trace) == 850
    attention = _reader("smallthinker_attention_share")
    experts = _reader("smallthinker_expert_layer_share")
    assert attention.read(run) == pytest.approx(100 * 250 / 850)
    assert experts.read(run) == pytest.approx(100 * 150 / 850)
    window = _reader("smallthinker_window_attention_roofline_share")
    glob = _reader("smallthinker_global_attention_roofline_share")
    assert window.calls_in(trace, "window") == {
        "fwd": [1, 100.0], "bwd_kv": [1, 40.0], "bwd_q": [1, 60.0]}
    assert window.calls_in(trace, "global") == {
        "fwd": [1, 50.0], "bwd_kv": [0, 0.0], "bwd_q": [0, 0.0]}
    lib = _flops()
    for kind, reader, ns, passes in (("window", window, 200e-9,
                                      ("fwd", "bwd")),
                                     ("global", glob, 50e-9, ("fwd",))):
        shape = window.call_shape(kind)
        assert shape == dict(batch=1, heads=28, kv_heads=4, head_dim=128,
                             seq_len=16384,
                             window=4096 if kind == "window" else None)
        flops = sum(lib.attention_flops(which=w, **shape) for w in passes)
        moved = sum(lib.attention_bytes(which=w, **shape) for w in passes)
        assert flops / 197e12 > moved / 819e9      # compute bounds it
        assert reader.read(run) == pytest.approx(100 * (flops / 197e12) / ns)
    readers = (attention, experts, window, glob)
    for reader in readers:
        assert reader.read(types.SimpleNamespace(trace=None)) is None
    run.on_device = False
    for reader in readers:
        assert reader.read(run) is None
    run.on_device = True
    monkeypatch.setattr(scope_shares, "step_map", lambda run: None)
    for reader in (attention, experts):
        assert reader.read(run) is None
    empty = trace_lib.from_events({0: [("fusion.9", "fusion kLoop", 0, 10)]},
                                  [("bench/segment", 0, 100)])
    assert window.read(_run(empty, {}, monkeypatch)) is None


def test_the_attention_counts_by_hand():
    """One call of each pass at the cell's shape: the products over the
    pairs each mask leaves, 28 query heads of 128; the arrays at their
    own head counts."""
    lib = _flops()
    shape = dict(batch=1, heads=28, kv_heads=4, head_dim=128, seq_len=16384)
    windowed = 4096 * 4097 / 2 + (16384 - 4096) * 4096
    causal = 16384 * 16385 / 2
    for which, products in (("fwd", 2), ("bwd", 5)):
        assert lib.attention_flops(which=which, window=4096, **shape) == \
            products * 2.0 * 28 * 128 * windowed
        assert lib.attention_flops(which=which, **shape) == \
            products * 2.0 * 28 * 128 * causal
    assert lib.attention_bytes(which="fwd", window=4096, **shape) == \
        (2 * 28 + 2 * 4) * 16384 * 128 * 2
    assert lib.attention_bytes(which="bwd", **shape) == \
        (4 * 28 + 4 * 4) * 16384 * 128 * 2


def test_the_readers_on_a_step_recorded_on_the_chip(monkeypatch):
    """Steps of the cell recorded on the chip, with the step map of
    their ops: each reader gives what it gave there, each attention
    kind's calls come 2 forwards (run and recomputed) and 1 backward
    (its two kernels) a layer and step, and both roofline shares lie
    under 100%."""
    with open(FIXTURE) as f:
        fixture = json.load(f)
    trace = trace_lib.from_events(
        {int(chip): [tuple(op) for op in ops]
         for chip, ops in fixture["device_ops"].items()},
        [tuple(span) for span in fixture["host_spans"]])
    mapped = {name: tuple(where) for name, where in fixture["scopes"].items()}
    run = _run(trace, mapped, monkeypatch, steps=fixture["steps"])
    window = _reader("smallthinker_window_attention_roofline_share")
    steps = fixture["steps"]
    for kind, layers in (("window", 3), ("global", 1)):
        calls = window.calls_in(trace, kind)
        assert (calls["fwd"][0], calls["bwd_kv"][0], calls["bwd_q"][0]) == (
            2 * layers * steps, layers * steps, layers * steps)
    for name, want in fixture["expected"]["readers"].items():
        got = _reader(name).read(run)
        assert got == pytest.approx(want), name
        assert 0 < got < 100, name


@pytest.fixture(scope="module")
def dry_run_model():
    """The cell's model at the files' dry-run sizes, built and warmed
    as ``run.py`` does before its reference check."""
    from benchmarks import run

    config = run.load_json(run.HERE, "configs", CONFIG + ".json")
    traffic = run.load_json(run.HERE, "traffic", TRAFFIC + ".json")
    config = run.merged(config, config["dry_run"])
    traffic = run.merged(traffic, traffic["dry_run"])
    import jax

    model, _ = run.build_model(config, traffic, 5, jax.devices()[:1])
    loop = run.Loop(model, traffic["segment_steps"])
    model.compile_iter_fns("avg")
    model.begin_epoch(0)
    for _ in range(3):
        loop.it += model.train_iter(loop.it, loop.recorder)
    model._flush_metrics(loop.recorder)
    yield model, config
    model.cleanup()


def _router_after_attention():
    """``SmallThinkerLayer`` with its router reading ``norm_post(h)``,
    the expert layer's input, in place of attention's: the same tree."""
    import flax.linen as nn
    import jax

    from theanompi_tpu.models import smallthinker as S

    class Late(S.SmallThinkerLayer):
        @nn.compact
        def __call__(self, x, table):
            b, t, d = x.shape
            u = S.RMSNorm(self.rms_eps, name="input_norm")(x)
            h = x + S.Attention(
                **self.attention,
                window=self.window if self.kind in "Ww" else None,
                rope=self.kind in "Wg", dtype=self.dtype,
                name="attention")(u, table)
            v = S.RMSNorm(self.rms_eps, name="post_norm")(h)
            logits = nn.Dense(self.moe["n_experts"], use_bias=False,
                              dtype=jax.numpy.float32,
                              name="router")(v.reshape(b * t, d))
            out, stats = S.Experts(**self.moe, dtype=self.dtype, name="moe")(
                v, logits, jax.nn.softmax(logits, axis=-1))
            return h + out, stats
    return Late


@pytest.mark.parametrize("fault", [None, "e4m3", "window_off_by_one",
                                   "rope_on_the_global_layer",
                                   "router_after_attention",
                                   "silu_in_place_of_relu"])
def test_the_reference_check_tells_a_planted_fault(dry_run_model, fault,
                                                   monkeypatch):
    """``run.py``'s own comparison under the configuration's limits:
    the healthy system is ``ok``; every matrix rounded to 8 bits in the
    system alone, a window of one key more, RoPE on the global layer,
    the router reading the expert layer's input, and SiLU in the
    experts' gate are not."""
    import jax
    import jax.numpy as jnp

    from benchmarks import run
    from theanompi_tpu.models import smallthinker as S

    model, config = dry_run_model
    healthy_loss = model.loss_fn
    real_attention, real_experts = S.fused_attention, S.routed_experts

    def rounded(params, *rest):
        return healthy_loss(jax.tree.map(
            lambda a: a + jax.lax.stop_gradient(
                a.astype(jnp.float8_e4m3fn).astype(a.dtype) - a)
            if a.ndim >= 2 else a, params), *rest)

    def wider(*args, window=None, **kwargs):
        return real_attention(*args, window=window and window + 1, **kwargs)

    def silu(*args, **kwargs):
        return real_experts(*args, **dict(kwargs, activation="silu"))

    if fault == "e4m3":
        monkeypatch.setattr(model, "loss_fn", rounded)
    elif fault == "window_off_by_one":
        monkeypatch.setattr(S, "fused_attention", wider)
    elif fault == "rope_on_the_global_layer":
        assert model.module.kinds == "GWWW"
        monkeypatch.setattr(model, "module",
                            model.module.clone(kinds="gWWW"))
    elif fault == "router_after_attention":
        monkeypatch.setattr(S, "SmallThinkerLayer", _router_after_attention())
    elif fault == "silu_in_place_of_relu":
        monkeypatch.setattr(S, "routed_experts", silu)
    result = run.check_against_reference(model, config, 5)
    assert result["ok"] == (fault is None), result
    if fault not in (None, "e4m3"):
        over = [leaf for leaf, err in result["grad_rel_l2_err"].items()
                if err > result["grad_rel_l2_tol"][leaf]]
        assert over, result
