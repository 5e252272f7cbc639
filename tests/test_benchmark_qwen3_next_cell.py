"""The cell PR 38 added, under tier-1: its dry runs (the whole harness
path at the files' ``dry_run`` sizes on one virtual CPU device), what
``BENCHMARK.json`` and the configuration declare for it, the four new
per-layer readers on hand-made events and on a step recorded on the chip
(``benchmarks/fixtures/qwen3_next_80b_s2048_chip_events.json``), and the
reference check against planted faults."""

import importlib.util
import json
import math
import os
import types

import pytest

from benchmarks import scope_shares, selfcheck
from benchmarks import trace as trace_lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(ROOT, "benchmarks", "fixtures",
                       "qwen3_next_80b_s2048_chip_events.json")
CELL = "qwen3_next_80b_s2048_x1"
CONFIG = "qwen3_next_80b"
#: the accepted lists the cell joins, and the new metrics
LISTS = ("tokens_per_s_per_chip", "median_segment_rate.tok",
         "input_wait_share.tok", "device_ms_per_step.tok", "mfu.tok",
         "device_idle_share.tok", "peak_hbm_gb.tok",
         "recompiles_in_window.tok", "scope_coverage.tok",
         "backward_share.tok", "update_share.tok", "recompute_share.tok",
         "loss_share.tok")
NEW = {"qwen3_next_delta_rule_share": ("lower", "step program"),
       "qwen3_next_delta_rule_roofline_share": ("higher", "kernels"),
       "qwen3_next_attention_roofline_share": ("higher", "kernels"),
       "qwen3_next_expert_layer_share": ("lower", "step program")}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "bench_reader_" + name,
        os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _load_flops(name):
    spec = importlib.util.spec_from_file_location(
        "bench_flops_" + name[:-3],
        os.path.join(ROOT, "benchmarks", "flops", name))
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


def test_the_cell_is_declared_where_the_issue_says():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, = (w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "lm_s2048_seg4_x1", 1)
    for said in ("53%", "17%", "12%", "160", "2 560", "19%"):
        assert said in cell["why"]
    assert len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config, = (c for c in bench["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/"
        "config.json")
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


def test_the_configuration_keeps_every_published_width():
    """Only the depth, the experts held and the vocabulary differ from
    the source's config, which is copied whole; the model, the FLOP
    count and the reference are built from those same numbers."""
    config = _configuration()
    published = config["published"]
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == set(config["reduced"]) == set(config["reduced_why"]) \
        == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 32, 151936 // 8)
    assert config["layer_kinds_run"] == "LLLF"
    kwargs = config["model"]["kwargs"]
    assert (kwargs["d_model"], kwargs["n_layers"],
            kwargs["full_attention_interval"], kwargs["linear_key_heads"],
            kwargs["linear_value_heads"], kwargs["linear_key_dim"],
            kwargs["linear_value_dim"], kwargs["conv_kernel"],
            kwargs["n_experts"], kwargs["top_k"], kwargs["expert_width"],
            kwargs["shared_width"], kwargs["n_heads"], kwargs["n_kv_heads"],
            kwargs["head_dim"], kwargs["partial_rotary_factor"],
            kwargs["rope_theta"], kwargs["rms_norm_eps"],
            kwargs["vocab"]) == (
        published["hidden_size"], config["num_hidden_layers"],
        published["full_attention_interval"],
        published["linear_num_key_heads"],
        published["linear_num_value_heads"],
        published["linear_key_head_dim"], published["linear_value_head_dim"],
        published["linear_conv_kernel_dim"], published["num_experts"],
        published["num_experts_per_tok"], published["moe_intermediate_size"],
        published["shared_expert_intermediate_size"],
        published["num_attention_heads"], published["num_key_value_heads"],
        published["head_dim"], published["partial_rotary_factor"],
        published["rope_theta"], published["rms_norm_eps"],
        config["vocab_size"])
    assert kwargs["held_experts"] == [0, config["num_experts"]]
    assert published["norm_topk_prob"] is True
    assert published["tie_word_embeddings"] is False
    assert (published["decoder_sparse_step"], published["mlp_only_layers"]) \
        == (1, [])
    flops = config["flops"]["kwargs"]
    assert all(flops[k] == kwargs[k] for k in flops if k != "held_count")
    assert flops["held_count"] == kwargs["held_experts"][1]
    reference = config["reference"]
    assert all(reference["kwargs"][k] == kwargs[k]
               for k in reference["kwargs"])
    # unrouted leaves at both ends and of both layer kinds, and a router
    assert set(reference["grad_rel_l2_tol"]) == {
        "embed/embedding", "final_norm/weight", "head/kernel",
        "Layer_0/linear_attention/in_proj_qkvz/kernel",
        "Layer_0/linear_attention/A_log", "Layer_3/attention/q_proj/kernel",
        "Layer_3/attention/o_proj/kernel",
        "Layer_3/moe/shared_expert/down/kernel",
        "Layer_3/moe/shared_expert_gate/kernel", "Layer_0/moe/router/kernel"}
    assert config["model_config"]["remat"] is True
    for key in ("qkvz_layout", "linear_init", "chunk", "init", "aux_loss",
                "router", "router_balance", "norms", "optimizer",
                "compute_dtype", "data", "sequence_length"):
        assert config["assumed"][key]
    assert "MTP" in config["departures"][0]
    assert config["deployment"]["expert_parallel_chips"] == 16
    assert "2 560" in config["deployment"]["tokens"]


def _handmade_trace():
    """One chip, a window of 1 000 ns, 800 of them busy: two attention
    calls (100 + 60), a delta-rule fusion (200) that a router fusion
    overlaps by 50, and XLA's own work; the step map's scopes by op."""
    ops = [
        ("qwen3_next_attention_fwd.1", "custom-call tpu_custom_call",
         0, 100),
        ("qwen3_next_attention_bwd", "custom-call tpu_custom_call",
         100, 160),
        ("fusion.1", "fusion kLoop", 160, 360),
        ("fusion.2", "fusion kLoop", 310, 400),
        ("while.3", "while", 400, 800),
        ("fusion.4", "fusion kOutput", 400, 800),
        ("nemotron_h_attention_fwd", "custom-call tpu_custom_call",
         850, 900),
    ]
    mapped = {
        "qwen3_next_attention_fwd.1": (
            "forward", "Net/Layer_3/attention/qwen3_next/attention"),
        "qwen3_next_attention_bwd": (
            "backward", "Net/Layer_3/attention/qwen3_next/attention"),
        "fusion.1": ("recompute", "Net/Layer_0/linear_attention/qwen3_next/"
                     "linear_attention/delta_rule"),
        "fusion.2": ("backward", "Net/Layer_0/moe/qwen3_next/router"),
        "while.3": ("backward", "Net/Layer_0/linear_attention/qwen3_next/"
                    "linear_attention/delta_rule"),
        "fusion.4": ("backward", "Net/Layer_1/moe/qwen3_next/experts/rung"),
    }
    return trace_lib.from_events({0: ops}, [("bench/segment", 0, 1000)]), \
        mapped


def _run(trace, mapped, monkeypatch, steps=4):
    from benchmarks import peaks

    monkeypatch.setattr(scope_shares, "step_map", lambda run: mapped)
    return types.SimpleNamespace(
        trace=trace, trace_lib=trace_lib, on_device=True, traced_steps=steps,
        peak=peaks.peak("TPU v5 lite"), phases={})


def test_the_readers_on_a_handmade_list_of_events(monkeypatch):
    """Each reader's pattern and arithmetic on events worked out by hand:
    busy 850 ns; the delta rule's leaf op 200 ns (a ``while`` holds
    others and is never counted), the expert layer's 90 + 400 - its
    overlap; the attention calls 160 ns by name; None with nothing to
    read."""
    trace, mapped = _handmade_trace()
    run = _run(trace, mapped, monkeypatch)
    assert trace_lib.busy_ns(trace) == 850
    share = _reader("qwen3_next_delta_rule_share")
    experts = _reader("qwen3_next_expert_layer_share")
    assert share.read(run) == pytest.approx(100 * 200 / 850)
    assert experts.read(run) == pytest.approx(100 * (90 + 400) / 850)
    rule = _reader("qwen3_next_delta_rule_roofline_share")
    assert rule.scope_ns(run, mapped) == 200
    shape, per_step = rule.passes()
    assert shape == dict(batch=4, seq_len=2048, heads=32, key_dim=128,
                         value_dim=128, chunk=64)
    assert per_step == {"fwd": 6, "bwd": 3}     # 3 layers, remat
    rule_lib = _load_flops("qwen3_next_delta_rule.py")
    moved = 4 * sum(n * rule_lib.delta_rule_bytes(which=w, **shape)
                    for w, n in per_step.items())
    flops = 4 * sum(n * rule_lib.delta_rule_flops(which=w, **shape)
                    for w, n in per_step.items())
    assert moved / 819e9 > flops / 197e12      # the states bound it
    assert rule.read(run) == pytest.approx(100 * (moved / 819e9) / 200e-9)
    attention = _reader("qwen3_next_attention_roofline_share")
    assert attention.calls_in(trace) == {"fwd": [1, 100.0],
                                         "bwd": [1, 60.0]}
    shape = attention.call_shape()
    assert shape == dict(batch=4, heads=16, kv_heads=2, head_dim=256,
                         seq_len=2048)
    lib = _load_flops("qwen3_next.py")
    flops = sum(lib.attention_flops(which=w, **shape) for w in ("fwd", "bwd"))
    moved = sum(lib.attention_bytes(which=w, **shape) for w in ("fwd", "bwd"))
    assert flops / 197e12 > moved / 819e9      # a head of 256: compute
    assert attention.read(run) == pytest.approx(
        100 * (flops / 197e12) / 160e-9)
    # nothing to read
    for reader in (share, experts, rule, attention):
        assert reader.read(types.SimpleNamespace(trace=None)) is None
    run.on_device = False
    for reader in (share, experts, rule, attention):
        assert reader.read(run) is None
    run.on_device = True
    monkeypatch.setattr(scope_shares, "step_map", lambda run: None)
    for reader in (share, experts, rule):
        assert reader.read(run) is None
    monkeypatch.setattr(scope_shares, "step_map", lambda run: {})
    assert rule.read(run) is None


def test_the_attention_counts_by_hand():
    """One call of each pass at the cell's shape: the causal products
    over 16 query heads of 256, the arrays at their own head counts."""
    lib = _load_flops("qwen3_next.py")
    shape = dict(batch=4, heads=16, kv_heads=2, head_dim=256, seq_len=2048)
    causal = 2048 * 2049 / 2
    assert lib.attention_flops(which="fwd", **shape) == \
        2 * 2.0 * 4 * 16 * 256 * causal
    assert lib.attention_flops(which="bwd", **shape) == \
        5 * 2.0 * 4 * 16 * 256 * causal
    assert lib.attention_bytes(which="fwd", **shape) == \
        (2 * 16 + 2 * 2) * 4 * 2048 * 256 * 2
    assert lib.attention_bytes(which="bwd", **shape) == \
        (4 * 16 + 4 * 2) * 4 * 2048 * 256 * 2


def test_the_readers_on_a_step_recorded_on_the_chip(monkeypatch):
    """One step of the cell recorded on the chip, with the step map of
    its ops: each reader gives what it gave there, the attention calls
    come 3 a step (forward, recomputed forward, backward), and both
    roofline shares lie under 100%."""
    with open(FIXTURE) as f:
        fixture = json.load(f)
    trace = trace_lib.from_events(
        {int(chip): [tuple(op) for op in ops]
         for chip, ops in fixture["device_ops"].items()},
        [tuple(span) for span in fixture["host_spans"]])
    mapped = {name: tuple(where) for name, where in fixture["scopes"].items()}
    run = _run(trace, mapped, monkeypatch, steps=fixture["steps"])
    attention = _reader("qwen3_next_attention_roofline_share")
    calls = attention.calls_in(trace)
    assert (calls["fwd"][0], calls["bwd"][0]) == (2, 1)
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
    traffic = run.load_json(run.HERE, "traffic", "lm_s2048_seg4_x1.json")
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


@pytest.mark.parametrize("fault", [None, "e4m3", "weights_not_normalised",
                                   "delta_rule_without_beta",
                                   "attention_without_its_gate"])
def test_the_reference_check_tells_a_planted_fault(dry_run_model, fault,
                                                   monkeypatch):
    """``run.py``'s own comparison under the configuration's limits:
    the healthy system is ``ok``; every matrix rounded to 8 bits in the
    system alone, the top-10 weights left unnormalised, the delta rule
    written without its strength ``beta`` and attention without its
    output gate are not.  (The issue's "top-k taken before the softmax"
    is no fault under ``norm_topk_prob``: a softmax over the chosen
    logits IS the softmax over all of them renormalised over the
    chosen, so the weights and the choice are the published ones; the
    router's planted fault is the normalisation dropped.)"""
    import jax
    import jax.numpy as jnp

    from benchmarks import run
    from theanompi_tpu.models import qwen3_next
    from theanompi_tpu.parallel import expert

    model, config = dry_run_model
    healthy_loss, rule = model.loss_fn, qwen3_next.gated_delta_chunked

    def rounded(params, *rest):
        return healthy_loss(jax.tree.map(
            lambda a: a + jax.lax.stop_gradient(
                a.astype(jnp.float8_e4m3fn).astype(a.dtype) - a)
            if a.ndim >= 2 else a, params), *rest)

    def unnormalised(*args, **kwargs):
        return expert.routed_experts(*args, **dict(kwargs, normalize=False))

    def without_beta(q, k, v, g, beta, **kwargs):
        return rule(q, k, v, g, jnp.ones_like(beta), **kwargs)

    if fault == "e4m3":
        monkeypatch.setattr(model, "loss_fn", rounded)
    elif fault == "weights_not_normalised":
        monkeypatch.setattr(qwen3_next, "routed_experts", unnormalised)
    elif fault == "delta_rule_without_beta":
        monkeypatch.setattr(qwen3_next, "gated_delta_chunked", without_beta)
    elif fault == "attention_without_its_gate":
        monkeypatch.setattr(qwen3_next, "gated_output", lambda o, gate: o)
    result = run.check_against_reference(model, config, 5)
    assert result["ok"] == (fault is None), result
    if fault not in (None, "e4m3"):
        over = [leaf for leaf, err in result["grad_rel_l2_err"].items()
                if err > result["grad_rel_l2_tol"][leaf]]
        assert over, result


def test_the_rule_count_is_worked_by_hand():
    """One forward pass of one layer at the cell's shape (4 x 2048
    tokens, 32 value heads, 128 x 128 state, chunks of 64), from the
    module's list of products; the backward twice its work."""
    lib = _load_flops("qwen3_next_delta_rule.py")
    shape = dict(batch=4, seq_len=2048, heads=32, key_dim=128,
                 value_dim=128, chunk=64)
    chunk_macs = (64 * 64 * 128          # K K^T
                  + 64 * 63 / 2 * 256    # the solve for W and U
                  + 2 * 64 * 128 * 128   # W S, Q S
                  + 64 * 64 * 128        # Q K^T
                  + 64 * 64 * 128        # applied to U'
                  + 64 * 128 * 128)      # K^T U'
    fwd = 2.0 * chunk_macs * 4 * 32 * 32
    assert lib.delta_rule_flops(which="fwd", **shape) == fwd
    assert lib.delta_rule_flops(which="bwd", **shape) == 2 * fwd
    tokens = 4 * 2048 * 32
    states = 4 * 32 * 32 * 128 * 128 * 4
    assert lib.delta_rule_bytes(which="fwd", **shape) == (
        tokens * 512 * 2 + 2 * tokens * 4 + states)
    assert lib.delta_rule_bytes(which="bwd", **shape) == (
        tokens * 512 * 2 + tokens * 384 * 2 + 4 * tokens * 4 + states)
    assert math.isclose(fwd / 1e9, 42.88, rel_tol=1e-3)
