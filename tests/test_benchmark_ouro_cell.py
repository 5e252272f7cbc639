"""The cell PR 32 added, under tier-1: its dry runs (the whole harness
path at the files' ``dry_run`` sizes on one virtual CPU device), what
``BENCHMARK.json`` and the configuration declare for it, the new
per-layer reader on a step recorded on the chip
(``benchmarks/fixtures/ouro_2_6b_s2048_chip_events.json``), and the
reference check against planted faults."""

import importlib.util
import json
import os
import re
import types

import pytest

from benchmarks import selfcheck
from benchmarks import trace as trace_lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(ROOT, "benchmarks", "fixtures",
                       "ouro_2_6b_s2048_chip_events.json")
CELL = "ouro_2_6b_s2048_x1"
#: the accepted lists the issue names for the cell, and the new metric
LISTS = ("tokens_per_s_per_chip", "median_segment_rate.tok",
         "input_wait_share.tok", "device_ms_per_step.tok", "mfu.tok",
         "device_idle_share.tok", "peak_hbm_gb.tok",
         "recompiles_in_window.tok", "attention_share",
         "attention_roofline_share",
         # PR 36: the step program by scope and phase
         "scope_coverage.tok", "backward_share.tok", "update_share.tok",
         "recompute_share.tok", "loss_share.tok")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "bench_reader_" + name,
        os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _fixture_trace():
    with open(FIXTURE) as f:
        fixture = json.load(f)
    trace = trace_lib.from_events(
        {int(chip): [tuple(op) for op in ops]
         for chip, ops in fixture["device_ops"].items()},
        [tuple(span) for span in fixture["host_spans"]])
    return fixture, trace


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
        "ouro_2_6b", "lm_s2048_seg1_x1", 1)
    assert "18%" in cell["why"] and "recomputed" in cell["why"]
    assert bench["workloads"][5] is cell and len(bench["workloads"]) >= 6
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config, = (c for c in bench["configs"] if c["name"] == "ouro_2_6b")
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["source"] == (
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json")
    assert config["file"] == "benchmarks/configs/ouro_2_6b.json"
    listed = {m["name"]: m.get("workloads") for m in
              bench["end_to_end"] + bench["per_layer"]}
    assert {name for name, cells in listed.items()
            if cells and CELL in cells} == set(LISTS)
    assert all(CELL in listed[name] for name in LISTS)
    roofline, = (m for m in bench["per_layer"]
                 if m["name"] == "attention_roofline_share")
    assert roofline == {
        "name": "attention_roofline_share", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "tokens_per_s_per_chip", "workloads": [CELL]}
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "lm_s2048_seg1_x1.json")) as f:
        traffic = json.load(f)
    assert (traffic["batch_per_chip"], traffic["units_per_sample"],
            traffic["model_kwargs"], traffic["segment_steps"],
            traffic["epoch_steps"]) == (4, 2048, {"seq_len": 2048}, 1, 1000)


def test_the_configuration_keeps_every_published_width():
    """Only the depth differs from the source's config; the model, the
    FLOP count and the reference are built from those same numbers, and
    the cell recomputes."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ouro_2_6b.json")) as f:
        config = json.load(f)
    published = config["published"]
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == set(config["reduced"]) == {"num_hidden_layers"}
    assert 4 <= config["num_hidden_layers"] <= published["num_hidden_layers"]
    kwargs = config["model"]["kwargs"]
    assert (kwargs["n_layers"], kwargs["d_model"], kwargs["n_heads"],
            kwargs["head_dim"], kwargs["d_ff"], kwargs["vocab"],
            kwargs["total_ut_steps"], kwargs["rms_norm_eps"],
            kwargs["rope_theta"]) == (
        config["num_hidden_layers"], published["hidden_size"],
        published["num_attention_heads"], published["head_dim"],
        published["intermediate_size"], published["vocab_size"],
        published["total_ut_steps"], published["rms_norm_eps"],
        published["rope_theta"])
    assert published["num_key_value_heads"] == published["num_attention_heads"]
    assert published["tie_word_embeddings"] is False
    flops = config["flops"]["kwargs"]
    assert all(flops[k] == kwargs[k] for k in flops)
    reference = config["reference"]
    assert reference["kwargs"]["total_ut_steps"] == kwargs["total_ut_steps"]
    assert reference["kwargs"]["n_heads"] == kwargs["n_heads"]
    assert (reference["kwargs"]["exit_entropy_beta"]
            == kwargs["exit_entropy_beta"] == 0.1)
    last = f"stack/Layer_{kwargs['n_layers'] - 1}/o_proj/kernel"
    assert set(reference["grad_rel_l2_tol"]) == {
        "embed/embedding", "stack/Layer_0/attn_norm/scale", last,
        "stack/final_norm/scale", "head/kernel", "exit_gate/kernel"}
    assert config["model_config"]["remat"] is True
    assert config["dry_run"]["model"]["kwargs"]["n_layers"] == 8
    for key in ("norms", "final_norm_every_pass", "bias", "rope",
                "exit_gate", "objective", "init", "optimizer",
                "compute_dtype", "data"):
        assert config["assumed"][key]
    assert len(config["departures"]) >= 3 and config["deployment"]["what"]


def test_the_roofline_reader_counts_the_calls_of_a_recorded_step():
    """One step of ouro_2_6b_s2048_x1 recorded on the chip: the reader
    finds the named calls, counts them (4 passes x 8 layers: 32 forward,
    32 recomputed, 32 backward), and holds their time against the larger
    of FLOPs and bytes over the peaks; None where there is nothing to
    read."""
    from benchmarks import peaks

    fixture, trace = _fixture_trace()
    reader = _reader("attention_roofline_share")
    calls = reader.calls_in(trace)
    assert (calls["fwd"][0], calls["bwd"][0]) == (64, 32)
    want = fixture["expected"]
    assert [calls["fwd"][0], calls["bwd"][0]] == want["attention_calls"]
    run = types.SimpleNamespace(
        trace=trace, trace_lib=trace_lib, on_device=True,
        peak=peaks.peak("TPU v5 lite"))
    share = reader.read(run)
    kernel_s = (calls["fwd"][1] + calls["bwd"][1]) / 1e9
    one = 2.0 * 4 * 16 * 128 * 2048 * 2049 / 2      # one product, one call
    flops = (64 * 2 + 32 * 5) * one
    moved = (64 * 4 + 32 * 8) * 4 * 2048 * 16 * 128 * 2
    assert share == pytest.approx(
        100 * max(flops / 197e12, moved / 819e9) / kernel_s)
    assert share == pytest.approx(want["attention_roofline_share"])
    assert flops / 197e12 > moved / 819e9      # the compute-bound side
    assert 5 < share < 100
    # the kernels are the step's only custom calls, so the accepted
    # attention_share reads the same time
    names = [op[0] for op in trace.device_ops[0]
             if re.search(r"tpu_custom_call", f"{op[0]} {op[1]}")]
    assert names and all(re.search(reader.PATTERN, n) for n in names)
    assert trace_lib.class_share(trace, reader.PATTERN) == pytest.approx(
        _reader("attention_share").read(run))
    # nothing to read: no trace, no device, a trace of another program
    assert reader.read(types.SimpleNamespace(trace=None)) is None
    run.on_device = False
    assert reader.read(run) is None
    run.on_device = True
    with open(os.path.join(ROOT, "benchmarks", "fixtures",
                           "gpt2m_s1024_chip_events.json")) as f:
        other = json.load(f)
    run.trace = trace_lib.from_events(
        {int(chip): [tuple(op) for op in ops]
         for chip, ops in other["device_ops"].items()},
        [tuple(span) for span in other["host_spans"]])
    assert reader.read(run) is None


@pytest.fixture(scope="module")
def dry_run_model():
    """The cell's model at the files' dry-run sizes, built and warmed
    as ``run.py`` does before its reference check."""
    from benchmarks import run

    config = run.load_json(run.HERE, "configs", "ouro_2_6b.json")
    traffic = run.load_json(run.HERE, "traffic", "lm_s2048_seg1_x1.json")
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


@pytest.mark.parametrize("fault", [None, "e4m3", "one_pass_short",
                                   "exit_weights_off", "no_norm_between"])
def test_the_reference_check_tells_a_planted_fault(dry_run_model, fault,
                                                   monkeypatch):
    """``run.py``'s own comparison under the configuration's limits:
    the healthy system is ``ok``; every matrix rounded to 8 bits in the
    system alone, T - 1 passes, the exit weights left off (the plain
    mean of the four passes' losses) and the final norm not applied
    between passes are not."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from benchmarks import run
    from theanompi_tpu.models import ouro

    model, config = dry_run_model
    healthy_loss = model.loss_fn

    def rounded(params, *rest):
        return healthy_loss(jax.tree.map(
            lambda a: a + jax.lax.stop_gradient(
                a.astype(jnp.float8_e4m3fn).astype(a.dtype) - a)
            if a.ndim >= 2 else a, params), *rest)

    def uniform(gate_logits):
        steps = gate_logits.shape[0] + 1
        p = jnp.full((steps,) + gate_logits.shape[1:], 1.0 / steps)
        return jnp.log(p), p

    class NoNormBetween(ouro.OuroStack):
        @nn.compact
        def __call__(self, u, rotary):
            for i in range(self.n_layers):
                u = ouro.OuroLayer(**self.layer, name=f"Layer_{i}")(u, rotary)
            h = nn.RMSNorm(epsilon=self.layer["rms_eps"],
                           dtype=self.layer["dtype"], name="final_norm")(u)
            return u, h          # the next pass starts from the un-normed u

    if fault == "e4m3":
        monkeypatch.setattr(model, "loss_fn", rounded)
    elif fault == "one_pass_short":
        monkeypatch.setattr(model, "module", model.module.clone(
            total_ut_steps=model.module.total_ut_steps - 1))
    elif fault == "exit_weights_off":
        monkeypatch.setattr(ouro, "exit_distribution", uniform)
    elif fault == "no_norm_between":
        monkeypatch.setattr(ouro, "OuroStack", NoNormBetween)
    result = run.check_against_reference(model, config, 5)
    assert result["ok"] == (fault is None), result
    if fault in ("one_pass_short", "exit_weights_off", "no_norm_between"):
        over = [leaf for leaf, err in result["grad_rel_l2_err"].items()
                if err > result["grad_rel_l2_tol"][leaf]]
        assert len(over) >= 4, result
