"""One FLOP count a model family: what ``tmlocal`` prints as
``tflops_per_shard`` (``model.train_flops_per_sample``) and what the
benchmark's ``mfu.*`` divides by (``benchmarks/flops/<file>.py``) agree
at the published shapes of every configuration the benchmark has.

Each model goes through its class's OWN constructor at full width, with
``TpuModel.__init__`` stood in for by a shapes-only one: no data, no
mesh, no optimizer, and the parameter tree as ``jax.eval_shape`` of the
module's init, so nothing of a 350 M or 700 M parameter model is ever
materialised on the CPU."""

from __future__ import annotations

import dataclasses
import functools
import importlib
import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.run import (  # noqa: E402
    load_file_module, load_json, merged)
from theanompi_tpu.models.base import TpuModel  # noqa: E402

BENCH = os.path.join(REPO, "benchmarks")


class _ShapesOnlyState:
    """``state.params`` as shapes.  Only a class that counts from its
    real tree reads it (the ``TransformerLM`` family), and those take
    token batches of ``(batch, seq_len)``."""

    def __init__(self, model):
        self._model = model

    @functools.cached_property
    def params(self):
        model = self._model
        tokens = jax.ShapeDtypeStruct((2, model._net_cfg["seq_len"]),
                                      model._input_dtype())
        key = jax.random.key(0)
        module = model.build_module()
        return jax.eval_shape(
            lambda: module.init({"params": key, "dropout": key}, tokens,
                                train=True))["params"]


def _shapes_only_init(self, config=None, mesh=None, verbose=True,
                      shard_rank=0, shard_size=1, data=None):
    self.config = config or self.default_config()
    self.mesh = mesh
    self.state = _ShapesOnlyState(self)


@pytest.mark.parametrize("config_name, traffic_name", [
    ("resnet50", "imagenet_b128_x1"),
    ("gpt2_medium", "lm_s1024_x1"),
    ("gpt2_medium", "lm_s128_x1"),
    ("zaya1_8b", "lm_s2048_x1"),
    ("ouro_2_6b", "lm_s2048_seg1_x1"),
    ("nemotron_twotower_30b", "lm_s2048_seg4_x1"),
    ("smallthinker_21b", "lm_s16384_seg2_x1"),
])
def test_program_and_benchmark_count_the_same_flops(
        monkeypatch, config_name, traffic_name):
    config = load_json(BENCH, "configs", config_name + ".json")
    traffic = load_json(BENCH, "traffic", traffic_name + ".json")
    shapes = traffic.get("model_kwargs")

    flops = config["flops"]
    benchmark_count = load_file_module(os.path.join(
        BENCH, "flops", flops["file"] + ".py")).train_flops_per_sample(
        **merged(flops.get("kwargs", {}), shapes))

    spec = config["model"]
    model_cls = getattr(importlib.import_module(spec["module"]),
                        spec["class"])
    overrides = {k: (tuple(v) if isinstance(v, list) else v)
                 for k, v in config["model_config"].items()}
    monkeypatch.setattr(TpuModel, "__init__", _shapes_only_init)
    model = model_cls(
        config=dataclasses.replace(model_cls.default_config(), **overrides),
        verbose=False, **merged(spec["kwargs"], shapes))

    # the program counts 6 N from its real tree, so biases and norm
    # scales (under 0.1% of GPT-2-medium) ride along; nothing else may
    assert model.train_flops_per_sample == pytest.approx(
        benchmark_count, rel=2e-3)


@pytest.mark.parametrize("seq_len, window", [
    (16, None), (16, 5), (16, 16), (16, 40), (33, 8), (64, 17)])
def test_the_window_count_is_the_masks(seq_len, window):
    """SmallThinker's pairs a head and sequence, the count behind its
    FLOPs and its attention roofline shares, against a brute-force count
    of the mask the kernels and the reference apply."""
    import numpy as np

    from theanompi_tpu.models.smallthinker import window_pairs
    from theanompi_tpu.ops.attention import causal_mask

    pos = np.arange(seq_len)
    mask = np.asarray(causal_mask(pos, pos, window))
    assert window_pairs(seq_len, window) == mask.sum()
    lib = load_file_module(os.path.join(BENCH, "flops", "smallthinker.py"))
    assert lib.attention_flops(which="fwd", batch=2, heads=3, head_dim=4,
                               seq_len=seq_len, window=window) == (
        2 * 2.0 * 2 * 3 * 4 * mask.sum())
