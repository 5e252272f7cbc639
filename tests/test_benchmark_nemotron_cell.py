"""The cell PR 34 added, under tier-1: its dry runs (the whole harness
path at the files' ``dry_run`` sizes on one virtual CPU device), what
``BENCHMARK.json`` and the configuration declare for it, the three new
per-layer readers on a hand-made list of events and on a step recorded
on the chip
(``benchmarks/fixtures/nemotron_twotower_30b_s2048_chip_events.json``),
and the reference check against planted faults."""

import importlib.util
import json
import math
import os
import re
import types

import pytest

from benchmarks import selfcheck
from benchmarks import trace as trace_lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(ROOT, "benchmarks", "fixtures",
                       "nemotron_twotower_30b_s2048_chip_events.json")
CELL = "nemotron_twotower_30b_s2048_x1"
CONFIG = "nemotron_twotower_30b"
#: the accepted lists the issue names for the cell, and the new metrics
LISTS = ("tokens_per_s_per_chip", "median_segment_rate.tok",
         "input_wait_share.tok", "device_ms_per_step.tok", "mfu.tok",
         "device_idle_share.tok", "peak_hbm_gb.tok",
         "recompiles_in_window.tok",
         # PR 36: the step program by scope and phase
         "scope_coverage.tok", "backward_share.tok", "update_share.tok",
         "recompute_share.tok", "loss_share.tok", "ssd_share",
         "expert_layer_share")
NEW = ("nemotron_expert_matmul_share",
       "nemotron_expert_matmul_roofline_share", "nemotron_attention_share",
       # PR 37: the scan's kernels
       "ssd_roofline_share")


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


def _fixture_trace():
    with open(FIXTURE) as f:
        fixture = json.load(f)
    trace = trace_lib.from_events(
        {int(chip): [tuple(op) for op in ops]
         for chip, ops in fixture["device_ops"].items()},
        [tuple(span) for span in fixture["host_spans"]])
    return fixture, trace


def _handmade_trace():
    """One chip, a window of 1 000 ns, 800 of them busy: two expert
    kernels (100 + 60), one attention kernel (80), a fusion that
    overlaps the first expert kernel by half, and XLA's own work."""
    ops = [
        ("nemotron_h_experts_up_gmm.1", "custom-call tpu_custom_call",
         0, 100),
        ("fusion.7", "fusion kLoop", 50, 150),
        ("nemotron_h_experts_down_tgmm", "custom-call tpu_custom_call",
         150, 210),
        ("nemotron_h_attention_fwd.3", "custom-call tpu_custom_call",
         210, 290),
        ("nemotron_h_experts_gate_gmm", "custom-call tpu_custom_call",
         290, 300),          # no such product in a relu^2 expert: not read
        ("zaya_experts_up_gmm", "custom-call tpu_custom_call", 300, 310),
        ("fusion.9", "fusion kOutput", 310, 800),
    ]
    return trace_lib.from_events({0: ops}, [("bench/segment", 0, 1000)])


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
    for said in ("77%", "384", "6 144", "13%"):
        assert said in cell["why"]
    assert len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config, = (c for c in bench["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["source"] == (
        "https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-"
        "Base-BF16/blob/main/config.json")
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    listed = {m["name"]: m.get("workloads") for m in
              bench["end_to_end"] + bench["per_layer"]}
    assert {name for name, cells in listed.items()
            if cells and CELL in cells} == set(LISTS) | set(NEW)
    for name in NEW:
        metric, = (m for m in bench["per_layer"] if m["name"] == name)
        assert metric == {
            "name": name, "unit": "%", "source": "device_trace",
            "better": "higher" if "roofline" in name else "lower",
            "layer": "kernels", "moves": "tokens_per_s_per_chip",
            "workloads": [CELL]}
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "lm_s2048_seg4_x1.json")) as f:
        traffic = json.load(f)
    assert (traffic["batch_per_chip"], traffic["units_per_sample"],
            traffic["model_kwargs"], traffic["segment_steps"],
            traffic["epoch_steps"]) == (4, 2048, {"seq_len": 2048}, 4, 1000)


def test_the_configuration_keeps_every_published_width():
    """Only the depth, the experts held and the vocabulary differ from
    the source's config; the model, the FLOP count and the reference are
    built from those same numbers; the first departure is the tower left
    out."""
    config = _configuration()
    published = config["published"]
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert set(config["reduced_why"]) == differs
    pattern = config["hybrid_override_pattern_run"]
    assert pattern == published["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert config["num_hidden_layers"] == len(pattern) == 9
    assert len(published["hybrid_override_pattern"]) == 52
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    kwargs = config["model"]["kwargs"]
    assert (kwargs["pattern"], kwargs["d_model"], kwargs["mamba_heads"],
            kwargs["mamba_head_dim"], kwargs["n_groups"], kwargs["state"],
            kwargs["conv_kernel"], kwargs["chunk"], kwargs["n_experts"],
            kwargs["top_k"], kwargs["expert_width"], kwargs["shared_width"],
            kwargs["routed_scaling_factor"], kwargs["n_heads"],
            kwargs["n_kv_heads"], kwargs["head_dim"],
            kwargs["rms_norm_eps"], kwargs["vocab"]) == (
        pattern, published["hidden_size"], published["mamba_num_heads"],
        published["mamba_head_dim"], published["n_groups"],
        published["ssm_state_size"], published["conv_kernel"],
        published["chunk_size"], published["n_routed_experts"],
        published["num_experts_per_tok"], published["moe_intermediate_size"],
        published["moe_shared_expert_intermediate_size"],
        published["routed_scaling_factor"], published["num_attention_heads"],
        published["num_key_value_heads"], published["head_dim"],
        published["layer_norm_epsilon"], config["vocab_size"])
    assert kwargs["held_experts"] == [0, config["n_routed_experts"]]
    assert (kwargs["time_step_min"], kwargs["time_step_max"],
            kwargs["time_step_floor"]) == (
        published["time_step_min"], published["time_step_max"],
        published["time_step_floor"])
    assert published["tie_word_embeddings"] is False
    assert published["norm_topk_prob"] is True
    # the inner width is heads x head size, not expand x hidden
    assert published["mamba_num_heads"] * published["mamba_head_dim"] == 4096
    assert "expand" in config["assumed"]["mamba_layout"]
    flops = config["flops"]["kwargs"]
    assert all(flops[k] == kwargs[k] for k in flops if k != "held_count")
    assert flops["held_count"] == kwargs["held_experts"][1]
    reference = config["reference"]
    assert all(reference["kwargs"][k] == kwargs[k]
               for k in reference["kwargs"])
    # unrouted leaves at both ends and of every layer kind, and a router
    assert set(reference["grad_rel_l2_tol"]) == {
        "embed/embedding", "final_norm/scale", "head/kernel",
        "Layer_0/mamba/A_log", "Layer_0/mamba/in_proj/kernel",
        "Layer_5/attention/o_proj/kernel", "Layer_8/moe/shared_down/kernel",
        "Layer_1/moe/router/kernel"}
    assert config["model_config"]["remat"] is True
    assert config["dry_run"]["model"]["kwargs"]["pattern"] == pattern
    for key in ("no_position_signal", "mamba_init", "mamba_layout", "init",
                "router", "router_balance", "norms", "optimizer",
                "compute_dtype", "data"):
        assert config["assumed"][key]
    assert "needs it" in config["assumed"]["router_balance"]
    first = config["departures"][0]
    assert "DENOISING tower" in first and "diffusion" in first
    assert "config.json" in first and "none is guessed" in first
    assert config["deployment"]["expert_parallel_chips"] == 16
    assert "6 144" in config["deployment"]["tokens"]


def test_the_share_readers_on_a_handmade_list_of_events():
    """Each reader's pattern, as its file has it, on events whose
    answers are worked out by hand: busy 800 ns; the relu^2 expert
    kernels 100 + 60 ns (a ``gate`` product and another model's kernels
    are not theirs), attention 80 ns."""
    trace = _handmade_trace()
    run = types.SimpleNamespace(trace=trace, trace_lib=trace_lib)
    assert trace_lib.busy_ns(trace) == 800
    experts = _reader("nemotron_expert_matmul_share")
    attention = _reader("nemotron_attention_share")
    assert experts.read(run) == pytest.approx(100 * 160 / 800)
    assert attention.read(run) == pytest.approx(100 * 80 / 800)
    none = types.SimpleNamespace(trace=None, trace_lib=trace_lib)
    assert experts.read(none) is None and attention.read(none) is None


def test_the_roofline_reader_on_a_handmade_list_of_events(monkeypatch):
    """The rows are the program's (the one ``routing_log`` entry flushed
    under the trace), TWO products a row; None wherever it cannot be
    read, never an expected share."""
    from benchmarks import peaks
    from theanompi_tpu.models import nemotron_h

    reader = _reader("nemotron_expert_matmul_roofline_share")
    run = types.SimpleNamespace(
        trace=_handmade_trace(), trace_lib=trace_lib, on_device=True,
        traced_steps=2, peak=peaks.peak("TPU v5 lite"))
    entry = {"held_rows": [1000.0, 3000.0], "n_layers": 4, "top_k": 6,
             "expert_shape": (8, 2688, 1856), "profiled": True}
    other = dict(entry, held_rows=[9.0, 9.0], profiled=False)
    monkeypatch.setattr(nemotron_h, "routing_log", [other, entry, other])
    flops = 6 * 2.0 * 4000 * 2688 * 1856
    moved = 6 * 2 * (4000 * (2688 + 1856) + 4 * 2 * 8 * 2688 * 1856)
    assert moved / 819e9 > flops / 197e12       # so few rows: the weights
    assert reader.read(run) == pytest.approx(
        100 * (moved / 819e9) / 160e-9)
    many = dict(entry, held_rows=[4e5, 4e5])
    monkeypatch.setattr(nemotron_h, "routing_log", [many])
    assert reader.read(run) == pytest.approx(
        100 * (6 * 2.0 * 8e5 * 2688 * 1856 / 197e12) / 160e-9)
    # nothing to read: no trace, no device, no entry flushed under a
    # trace or two of them, an entry of another number of steps than
    # were traced, no kernel time, a program without the log
    assert reader.read(types.SimpleNamespace(trace=None)) is None
    run.on_device = False
    assert reader.read(run) is None
    run.on_device = True
    for log in ([other, other], [entry, other, entry],
                [dict(entry, held_rows=[1.0])], []):
        monkeypatch.setattr(nemotron_h, "routing_log", log)
        assert reader.read(run) is None
    monkeypatch.setattr(nemotron_h, "routing_log", [entry])
    with open(os.path.join(ROOT, "benchmarks", "fixtures",
                           "gpt2m_s1024_chip_events.json")) as f:
        other_cell = json.load(f)
    run.trace = trace_lib.from_events(
        {int(chip): [tuple(op) for op in ops]
         for chip, ops in other_cell["device_ops"].items()},
        [tuple(span) for span in other_cell["host_spans"]])
    assert reader.read(run) is None
    run.trace = _handmade_trace()
    monkeypatch.delattr(nemotron_h, "routing_log")
    assert reader.read(run) is None


#: one call of each of the scan's kernels at the cell's shape (4 x 2048
#: tokens, 64 heads of 64 in 8 groups, state 128, chunks of 128), worked
#: from the kernels' bodies: 4 x 8 x 16 = 512 grid steps; a head of 64
#: runs its products over its 128-lane tile
SSD_STEPS = 4 * 8 * 16
SSD_FWD_FLOPS = 2.0 * SSD_STEPS * (
    128 * 128 * 128             # C B^T
    + 128 * 128 * 512           # C S_in, the group's 8 heads at once
    + 8 * 128 * 128 * 128       # a head's masked scores applied to x
    + 128 * 128 * 512)          # B^T applied to x: the state
SSD_BWD_FLOPS = 2.0 * SSD_STEPS * (
    3 * 128 * 128 * 128         # C B^T; d(C B^T) B; d(C B^T)^T C
    + 5 * 128 * 128 * 512       # C S_in; B dS; dy S^T; x dS^T; C^T dy
    + 2 * 8 * 128 * 128 * 128)  # a head's dy x^T and M^T dy
_X = 4 * 2048 * 64 * 64 * 2               # x, y, dy or dx in bf16
_BC = 4 * 2048 * 8 * 128 * 2              # B, C or a gradient
_ROWS = 4 * 2048 * 64 * 4                 # dt or dt A rows, float32
_STATES = 4 * 16 * 128 * 64 * 64 * 4      # the chunks' entering states
SSD_FWD_BYTES = 2 * _X + 2 * _BC + 2 * _ROWS + 64 * 64 * 4 + _STATES
SSD_BWD_BYTES = (3 * _X + 4 * _BC + 4 * _ROWS + 64 * 64 * 4
                 + 4 * 64 * 64 * 4 + _STATES)


def _ssd_trace(fwd_ns, bwd_ns):
    """A window of 10 ms: two forward calls and a backward one inside,
    a forward call before it, and another model's kernel."""
    ops = [("nemotron_h_ssd_fwd.2", "custom-call tpu_custom_call",
            -2_000_000, -1_000_000),
           ("nemotron_h_ssd_fwd.1", "custom-call tpu_custom_call",
            0, fwd_ns),
           ("nemotron_h_attention_fwd", "custom-call tpu_custom_call",
            fwd_ns, fwd_ns + 500),
           ("nemotron_h_ssd_fwd.3", "custom-call tpu_custom_call",
            fwd_ns + 500, 2 * fwd_ns + 500),
           ("nemotron_h_ssd_bwd.1", "custom-call tpu_custom_call",
            2 * fwd_ns + 500, 2 * fwd_ns + 500 + bwd_ns)]
    return trace_lib.from_events({0: ops},
                                 [("bench/segment", 0, 10_000_000)])


def test_the_scan_roofline_reader_on_a_handmade_list_of_events():
    """Calls counted by name inside the window only; each call's work is
    the hand count above; None where no such call was traced; the
    calls' least time itself reads (just) under 100%."""
    from benchmarks import peaks

    reader = _reader("ssd_roofline_share")
    flops_lib = _load_flops("nemotron_h_ssd.py")
    shape = reader.call_shape()
    assert shape == dict(batch=4, seq_len=2048, heads=64, head_dim=64,
                         groups=8, state=128, chunk=128)
    assert flops_lib.ssd_kernel_flops(which="fwd", **shape) == SSD_FWD_FLOPS
    assert flops_lib.ssd_kernel_flops(which="bwd", **shape) == SSD_BWD_FLOPS
    assert flops_lib.ssd_kernel_bytes(which="fwd", **shape) == SSD_FWD_BYTES
    assert flops_lib.ssd_kernel_bytes(which="bwd", **shape) == SSD_BWD_BYTES
    # 36.5 and 83.8 GFLOP, 306 and 411 MB: both bound by the bytes
    assert SSD_FWD_BYTES / 819e9 > SSD_FWD_FLOPS / 197e12
    assert SSD_BWD_BYTES / 819e9 > SSD_BWD_FLOPS / 197e12
    run = types.SimpleNamespace(
        trace=_ssd_trace(1_000_000, 2_500_000), trace_lib=trace_lib,
        on_device=True, peak=peaks.peak("TPU v5 lite"))
    assert reader.calls_in(run.trace) == {"fwd": [2, 2e6], "bwd": [1, 2.5e6]}
    least = (2 * SSD_FWD_BYTES + SSD_BWD_BYTES) / 819e9
    assert reader.read(run) == pytest.approx(100 * least / 4.5e-3)
    # the least time itself, to the nanosecond above
    fwd_ns = math.ceil(SSD_FWD_BYTES / 819e9 * 1e9)
    bwd_ns = math.ceil(SSD_BWD_BYTES / 819e9 * 1e9)
    run.trace = _ssd_trace(fwd_ns, bwd_ns)
    assert 99.999 < reader.read(run) <= 100
    # nothing to read
    run.trace = _handmade_trace()
    assert reader.read(run) is None
    _, run.trace = _fixture_trace()      # the parent's step: no such call
    assert reader.read(run) is None
    run.on_device = False
    assert reader.read(run) is None
    assert reader.read(types.SimpleNamespace(trace=None)) is None


def test_the_readers_on_a_step_recorded_on_the_chip(monkeypatch):
    """One step of the cell recorded on the chip: each pattern finds
    its kernels there, the two classes are disjoint and together are the
    step's custom calls, the calls come in the numbers the layers give
    (4 expert layers x 2 products x (forward, recomputed forward, 2
    gradients); 1 attention layer x (forward, recomputed, backward)),
    and the roofline share is under 100%."""
    from benchmarks import peaks
    from theanompi_tpu.models import nemotron_h

    fixture, trace = _fixture_trace()
    experts = _reader("nemotron_expert_matmul_share")
    attention = _reader("nemotron_attention_share")
    roofline = _reader("nemotron_expert_matmul_roofline_share")
    run = types.SimpleNamespace(
        trace=trace, trace_lib=trace_lib, on_device=True, traced_steps=1,
        peak=peaks.peak("TPU v5 lite"))
    want = fixture["expected"]
    assert experts.read(run) == pytest.approx(
        want["class_share"][experts.PATTERN])
    assert attention.read(run) == pytest.approx(
        want["class_share"][attention.PATTERN])
    both = trace_lib.class_share(
        trace, f"{experts.PATTERN}|{attention.PATTERN}")
    assert both == pytest.approx(experts.read(run) + attention.read(run))
    assert both == pytest.approx(want["class_share"]["tpu_custom_call"])
    names = [op[0] for op in trace.device_ops[0]]
    assert sum(bool(re.search(experts.PATTERN, n)) for n in names) == 4 * 2 * 4
    assert sum(bool(re.search(attention.PATTERN, n)) for n in names) == 3
    rows = fixture["held_rows_of_the_step"]
    monkeypatch.setattr(nemotron_h, "routing_log", [
        {"held_rows": [rows], "n_layers": 4, "top_k": 6,
         "expert_shape": (8, 2688, 1856), "profiled": True}])
    share = roofline.read(run)
    assert share == pytest.approx(want["roofline_share"])
    assert 1 < share < 100


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
                                   "gate_after_norm", "decay_dropped"])
def test_the_reference_check_tells_a_planted_fault(dry_run_model, fault,
                                                   monkeypatch):
    """``run.py``'s own comparison under the configuration's limits:
    the healthy system is ``ok``; every matrix rounded to 8 bits in the
    system alone, the chosen experts' weights left unnormalised, the
    gate applied after the group norm and the state's decay left out of
    the scan are not."""
    import jax
    import jax.numpy as jnp

    from benchmarks import run
    from theanompi_tpu.models import nemotron_h
    from theanompi_tpu.parallel import expert

    model, config = dry_run_model
    healthy_loss, ssd = model.loss_fn, nemotron_h.ssd_chunked

    def rounded(params, *rest):
        return healthy_loss(jax.tree.map(
            lambda a: a + jax.lax.stop_gradient(
                a.astype(jnp.float8_e4m3fn).astype(a.dtype) - a)
            if a.ndim >= 2 else a, params), *rest)

    def unnormalised(*args, **kwargs):
        return expert.routed_experts(*args, **dict(kwargs, normalize=False))

    def gate_after_norm(y, z, scale, n_groups, eps):
        groups = y.astype(jnp.float32).reshape(y.shape[:-1] + (n_groups, -1))
        normed = groups * jax.lax.rsqrt(
            jnp.mean(groups * groups, -1, keepdims=True) + eps)
        return (normed.reshape(y.shape) * scale
                * jax.nn.silu(z.astype(jnp.float32))).astype(y.dtype)

    def no_decay(x, dt, a, b, c, d, **kwargs):
        return ssd(x, dt, jnp.zeros_like(a), b, c, d, **kwargs)

    if fault == "e4m3":
        monkeypatch.setattr(model, "loss_fn", rounded)
    elif fault == "weights_not_normalised":
        monkeypatch.setattr(nemotron_h, "routed_experts", unnormalised)
    elif fault == "gate_after_norm":
        monkeypatch.setattr(nemotron_h, "gated_group_norm", gate_after_norm)
    elif fault == "decay_dropped":
        monkeypatch.setattr(nemotron_h, "ssd_chunked", no_decay)
    result = run.check_against_reference(model, config, 5)
    assert result["ok"] == (fault is None), result
    if fault not in (None, "e4m3"):
        over = [leaf for leaf, err in result["grad_rel_l2_err"].items()
                if err > result["grad_rel_l2_tol"][leaf]]
        # at 16 tokens a sequence and three steps from the init the
        # faults are small (the decays near 1, the routed part an
        # eighth of the shared one): one leaf past its limit is enough
        assert over, result
