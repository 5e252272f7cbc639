"""The cells PR 27 added, under tier-1: their dry runs (the whole
harness path at the files' ``dry_run`` sizes on one virtual CPU device),
the three new per-layer readers on a step recorded on the chip
(``benchmarks/fixtures/zaya1_8b_s2048_chip_events.json``), and what
``BENCHMARK.json`` declares for them."""

import importlib.util
import json
import os
import types

import pytest

from benchmarks import selfcheck
from benchmarks import trace as trace_lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(ROOT, "benchmarks", "fixtures",
                       "zaya1_8b_s2048_chip_events.json")
NEW_CELLS = ("zaya1_8b_s2048_x1", "gpt2m_s128_x1")


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


@pytest.mark.parametrize("workload,trace", [
    ("zaya1_8b_s2048_x1", 0), ("zaya1_8b_s2048_x1", 1),
    ("gpt2m_s128_x1", 1)])
def test_dry_run_of_a_new_cell(workload, trace, capsys):
    try:
        selfcheck.check_dry_run(workload, 1, trace)
    except SystemExit as miss:
        pytest.fail(str(miss))
    assert "correct, nothing failed" in capsys.readouterr().out


def test_the_new_cells_are_declared_where_the_issue_says():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells["zaya1_8b_s2048_x1"]["config"] == "zaya1_8b"
    assert cells["zaya1_8b_s2048_x1"]["traffic"] == "lm_s2048_x1"
    assert cells["gpt2m_s128_x1"]["config"] == "gpt2_medium"
    assert cells["gpt2m_s128_x1"]["traffic"] == "lm_s128_x1"
    assert all(cells[name]["chips"] == 1 for name in NEW_CELLS)
    listed = {m["name"]: m.get("workloads", []) for m in
              bench["end_to_end"] + bench["per_layer"]}
    for metric, cells_of in listed.items():
        if metric == "recompute_share.tok":  # PR 36: the remat cells' own
            assert not set(NEW_CELLS) & set(cells_of)
        elif metric == "tokens_per_s_per_chip" or metric.endswith(".tok"):
            assert set(NEW_CELLS) <= set(cells_of), metric
    # the accepted pattern of attention_share would count the expert
    # kernels as attention: the zaya cell has a reader of its own
    assert "gpt2m_s128_x1" in listed["attention_share"]
    assert "zaya1_8b_s2048_x1" not in listed["attention_share"]
    for metric in ("expert_matmul_share", "cca_attention_share",
                   "expert_matmul_roofline_share"):
        assert listed[metric] == ["zaya1_8b_s2048_x1"]
    config, = (c for c in bench["configs"] if c["name"] == "zaya1_8b")
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]


def test_the_configuration_keeps_every_published_width():
    """Only depth, experts held and vocabulary differ from the source's
    config; the model is built from those same numbers."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "zaya1_8b.json")) as f:
        config = json.load(f)
    published = config["published"]
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == set(config["reduced"])
    kwargs = config["model"]["kwargs"]
    assert (kwargs["n_layers"], kwargs["held_experts"], kwargs["vocab"]) == (
        config["num_hidden_layers"], [0, config["num_experts"]],
        config["vocab_size"])
    assert (kwargs["d_model"], kwargs["n_heads"], kwargs["n_kv_heads"],
            kwargs["head_dim"], kwargs["n_experts"], kwargs["expert_width"],
            kwargs["router_hidden"]) == (
        published["hidden_size"], published["num_attention_heads"],
        published["num_key_value_heads"], published["head_dim"],
        published["num_experts"], published["moe_intermediate_size"],
        published["router_hidden_size"])
    assert config["num_experts"] >= 8 and config["num_hidden_layers"] >= 4
    assert config["vocab_size"] * 8 >= published["vocab_size"]


def test_the_readers_patterns_find_the_kernels_of_a_recorded_step():
    """One step of zaya1_8b_s2048_x1 recorded on the chip: each
    reader's pattern, as its file has it, matches kernels there, the
    two classes are disjoint, and both are custom calls."""
    fixture, trace = _fixture_trace()
    experts = _reader("expert_matmul_share")
    attention = _reader("cca_attention_share")
    run = types.SimpleNamespace(trace=trace, trace_lib=trace_lib)
    want = fixture["expected"]["class_share"]
    assert experts.read(run) == pytest.approx(want[experts.PATTERN])
    assert attention.read(run) == pytest.approx(want[attention.PATTERN])
    assert experts.read(run) > 5 and attention.read(run) > 5
    both = trace_lib.class_share(
        trace, f"{experts.PATTERN}|{attention.PATTERN}")
    assert both == pytest.approx(experts.read(run) + attention.read(run))
    assert both == pytest.approx(want["tpu_custom_call"])
    # per layer and step: 3 products forward, 6 backward; 2 attention
    names = [op[0] for op in trace.device_ops[0]]
    import re
    assert sum(bool(re.search(experts.PATTERN, n)) for n in names) % 9 == 0
    assert sum(bool(re.search(attention.PATTERN, n)) for n in names) % 2 == 0
    no_trace = types.SimpleNamespace(trace=None, trace_lib=trace_lib)
    assert experts.read(no_trace) is None
    assert attention.read(no_trace) is None


def test_the_roofline_reader_takes_its_rows_from_the_program(monkeypatch):
    """It reads the entry of the program's routing log that was flushed
    under the profiler's trace, holds the kernels' time against the
    larger of FLOPs and bytes over the peaks, and returns None wherever
    it cannot."""
    from benchmarks import peaks
    from theanompi_tpu.models import zaya

    fixture, trace = _fixture_trace()
    reader = _reader("expert_matmul_roofline_share")
    run = types.SimpleNamespace(
        trace=trace, trace_lib=trace_lib, on_device=True, traced_steps=1,
        peak=peaks.peak("TPU v5 lite"))
    rows = fixture["held_rows_of_the_step"]
    entry = {"held_rows": [rows], "n_layers": 6,
             "expert_shape": (8, 2048, 2048), "profiled": True}
    other = dict(entry, held_rows=[rows / 2], profiled=False)
    monkeypatch.setattr(zaya, "routing_log", [other, other, entry, other])
    share = reader.read(run)
    kernel_s = (trace_lib.class_share(
        trace, _reader("expert_matmul_share").PATTERN) / 100
        * trace_lib.busy_ns(trace) / 1e9)
    flops = 18.0 * rows * 2048 * 2048
    moved = 9 * 2 * (rows * 4096 + 6 * 8 * 2048 * 2048)
    assert share == pytest.approx(
        100 * max(flops / 197e12, moved / 819e9) / kernel_s)
    assert share == pytest.approx(fixture["expected"]["roofline_share"])
    assert 5 < share < 100
    # nothing to read: no trace, no device, no entry flushed under a
    # trace or two of them, an entry of another number of steps than
    # were traced, a program without the log
    assert reader.read(types.SimpleNamespace(trace=None)) is None
    run.on_device = False
    assert reader.read(run) is None
    run.on_device = True
    for log in ([other, other], [entry, other, entry],
                [dict(entry, held_rows=[rows, rows])], []):
        monkeypatch.setattr(zaya, "routing_log", log)
        assert reader.read(run) is None
    monkeypatch.delattr(zaya, "routing_log")
    assert reader.read(run) is None


@pytest.fixture(scope="module")
def dry_run_model():
    """The cell's model at the files' dry-run sizes, built and warmed
    as ``run.py`` does before its reference check."""
    from benchmarks import run

    config = run.load_json(run.HERE, "configs", "zaya1_8b.json")
    traffic = run.load_json(run.HERE, "traffic", "lm_s2048_x1.json")
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


@pytest.mark.parametrize("fault", [None, "e4m3", "swapped_expert",
                                   "dropped_weight"])
def test_the_reference_check_tells_a_planted_fault(dry_run_model, fault,
                                                   monkeypatch):
    """``run.py``'s own comparison under the configuration's limits:
    the healthy system is ``ok``; every matrix rounded to 8 bits in the
    system alone, each expert's rows multiplied by its neighbour's
    matrices, and the router's weight left off the expert's output are
    not, and the two indexing faults read past every limit (the
    readings at the cell's size are in PERF.md section 2)."""
    import jax
    import jax.numpy as jnp

    from benchmarks import run
    from theanompi_tpu.models import zaya

    model, config = dry_run_model
    healthy_loss, healthy_experts = model.loss_fn, zaya.routed_experts

    def rounded(params, *rest):
        return healthy_loss(jax.tree.map(
            lambda a: a + jax.lax.stop_gradient(
                a.astype(jnp.float8_e4m3fn).astype(a.dtype) - a)
            if a.ndim >= 2 else a, params), *rest)

    def swapped(x, probs, expert_params, held, **kw):
        return healthy_experts(x, probs, jax.tree.map(
            lambda w: jnp.roll(w, 1, axis=0), expert_params), held, **kw)

    def dropped(x, probs, expert_params, held, **kw):
        # the choice stays (select_by); the weight becomes 1
        return healthy_experts(x, jnp.ones_like(probs), expert_params,
                               held, **kw)

    if fault == "e4m3":
        monkeypatch.setattr(model, "loss_fn", rounded)
    elif fault == "swapped_expert":
        monkeypatch.setattr(zaya, "routed_experts", swapped)
    elif fault == "dropped_weight":
        monkeypatch.setattr(zaya, "routed_experts", dropped)
    result = run.check_against_reference(model, config, 5)
    assert result["ok"] == (fault is None), result
    if fault in ("swapped_expert", "dropped_weight"):
        assert all(err > result["grad_rel_l2_tol"][leaf] for leaf, err
                   in result["grad_rel_l2_err"].items()), result
