"""What PR 21 (bring-up on the v5e, JAX 0.9.0) established, pinned on
the CPU: the compile cache is placed from outside or at one fixed
path; the entry points neither fall back to a CPU nor pick a
platform; host-only children are pinned to the CPU; a kernel
the compiler refuses is loud."""

from __future__ import annotations

import logging
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _tracked_sources() -> list[str]:
    out = []
    for root in ("theanompi_tpu", "tools"):
        for dirpath, _dirs, files in os.walk(os.path.join(REPO, root)):
            out += [os.path.join(dirpath, f) for f in files
                    if f.endswith(".py")]
    return out + [os.path.join(REPO, f) for f in
                  ("chip_smoke.py", "__graft_entry__.py")]


# -- compile cache ----------------------------------------------------------

_CACHE_CHILD = (
    "from theanompi_tpu.utils.helper_funcs import enable_compilation_cache\n"
    "import jax\n"
    "print(enable_compilation_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n")


def _cache_child(env_dir: str | None) -> list[str]:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run([sys.executable, "-c", _CACHE_CHILD], env=env,
                       capture_output=True, text=True, timeout=120,
                       cwd="/")
    assert r.returncode == 0, r.stderr
    return r.stdout.split()


class TestCompileCachePlacement:
    def test_variable_places_the_cache(self, tmp_path):
        want = str(tmp_path / "placed_from_outside")
        assert _cache_child(want) == [want, want]

    def test_default_is_the_fixed_checkout_path(self):
        want = os.path.join(REPO, "artifacts", "jax_cache")
        assert _cache_child(None) == [want, want]

    def test_one_update_site_and_no_third_path(self):
        sites = []
        for path in _tracked_sources():
            with open(path) as f:
                src = f.read()
            assert "THEANOMPI_TPU_COMPILATION_CACHE" not in src, path
            assert "compilation-cache-dir" not in src, path
            sites += [path] * len(re.findall(
                r"config\.update\(\s*[\"']jax_compilation_cache_dir", src))
        assert [os.path.relpath(p, REPO) for p in sites] == [
            "theanompi_tpu/utils/helper_funcs.py"]

    def test_launchers_have_no_cache_flag(self):
        from theanompi_tpu.launcher import _build_parser

        with pytest.raises(SystemExit):
            _build_parser(False).parse_args(
                ["BSP", "--compilation-cache-dir", "/tmp/x"])


# -- entry points -------------------------------------------------------------


class TestDryrunMultichip:
    def test_source_sets_no_platform(self):
        with open(os.path.join(REPO, "__graft_entry__.py")) as f:
            src = f.read()
        assert "jax_platforms" not in src and "JAX_PLATFORMS" not in src
        assert "THEANOMPI_TPU_" not in src  # both switches are gone

    def test_too_few_devices_is_an_error_not_a_fallback(self):
        from __graft_entry__ import dryrun_multichip

        before = jax.config.jax_platforms, jax.default_backend()
        with pytest.raises(RuntimeError, match="has 8 device"):
            dryrun_multichip(9)
        assert (jax.config.jax_platforms, jax.default_backend()) == before
        assert len(jax.devices()) == 8


# -- one process per chip: host-only children run on the CPU ------------------


class _FakePopen:
    calls: list = []

    def __init__(self, cmd, env=None, **kw):
        type(self).calls.append((cmd, env))


@pytest.fixture
def fake_popen(monkeypatch):
    _FakePopen.calls = []
    monkeypatch.setattr(subprocess, "Popen", _FakePopen)
    # the parent holds a chip: children must not inherit that
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    return _FakePopen.calls


class TestChildrenArePinnedToCpu:
    def test_shard_children(self, fake_popen):
        import inspect

        from theanompi_tpu.parallel.shards import ShardProcessGroup

        group = object.__new__(ShardProcessGroup)
        group.host, group._socks = "127.0.0.1", []
        # what the launcher's --shards gets: the constructor default
        group.platform = inspect.signature(
            ShardProcessGroup.__init__).parameters["platform"].default
        group._spawn(0, 4242)
        (_, env), = fake_popen
        assert env["JAX_PLATFORMS"] == "cpu"

    def test_ingest_readers_and_coordinator(self, fake_popen):
        from theanompi_tpu.ingest.fleet import IngestProcessGroup

        group = object.__new__(IngestProcessGroup)
        group.host, group.data_dir, group.seed = "127.0.0.1", "/d", 0
        group.max_inflight, group._ports = None, [4243]
        group._spawn_reader(0, 4243)
        group._spawn_coordinator(4244)
        assert [env["JAX_PLATFORMS"] for _, env in fake_popen] == [
            "cpu", "cpu"]

    def test_collector_child(self, fake_popen):
        from theanompi_tpu.monitor.collector import CollectorProcess

        proc = object.__new__(CollectorProcess)
        proc.host, proc.port, proc.run_dir = "127.0.0.1", 4245, "/m"
        proc._spawn()
        (_, env), = fake_popen
        assert env["JAX_PLATFORMS"] == "cpu"


# -- peaks --------------------------------------------------------------------


class TestPeakTable:
    def test_unknown_device_kind_is_an_error(self):
        from benchmarks.peaks import peak

        assert peak("TPU v5 lite")["bf16_tflops"] == 197.0
        with pytest.raises(KeyError, match="TPU v9"):
            peak("TPU v9")
        with pytest.raises(KeyError):
            peak("cpu")


# -- kernels: no quiet way out -------------------------------------------------


class TestNoQuietFallback:
    def test_interpret_only_on_the_cpu_platform(self, monkeypatch):
        from theanompi_tpu.ops import pallas_mode

        assert pallas_mode.interpret() is True
        for backend in ("tpu", "gpu"):
            monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
            assert pallas_mode.interpret() is False

    def test_lrn_auto_does_not_swallow_a_compile_error(self, monkeypatch):
        import theanompi_tpu.ops.lrn_pallas as lp
        from theanompi_tpu.ops import lrn

        def refused(*a, **k):
            raise NotImplementedError("Mosaic refuses this kernel")

        monkeypatch.setattr(lp, "lrn_pallas", refused)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(NotImplementedError, match="Mosaic refuses"):
            lrn(jnp.ones((1, 2, 2, 8)))  # impl='auto' -> pallas on tpu

    def test_attention_choice_is_logged_once_per_shape(self, monkeypatch,
                                                       caplog):
        from theanompi_tpu.ops import attention as A

        A._log_choice.cache_clear()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        ragged = jnp.zeros((1, 600, 2, 8))   # 600 % 512 != 0
        fits = jnp.zeros((1, 256, 2, 8))
        with caplog.at_level(logging.INFO, logger=A.__name__):
            assert A._resolve_impl(None, ragged, ragged) == "xla"
            assert A._resolve_impl(None, ragged, ragged) == "xla"
            assert A._resolve_impl(None, fits, fits) == "pallas"
        A._log_choice.cache_clear()
        msgs = [(r.levelno, r.getMessage()) for r in caplog.records]
        assert len(msgs) == 2  # once per shape, not per call
        # leaving the kernel on a TPU is a warning, taking it is info
        assert msgs[0][0] == logging.WARNING and "ragged" in msgs[0][1]
        assert msgs[1][0] == logging.INFO and "pallas" in msgs[1][1]

    def test_gspmd_step_takes_the_xla_attention(self):
        """GSPMD cannot partition a Mosaic kernel (met on four chips):
        the tensor-parallel model's plain-jit step passes impl='xla',
        the shard_map models keep the kernel's own choice."""
        from theanompi_tpu.models.base import ModelConfig
        from theanompi_tpu.models.transformer import (
            TransformerLM,
            TransformerLM_TP,
        )
        from theanompi_tpu.parallel.mesh import MeshSpec, make_training_mesh

        net = dict(vocab=16, seq_len=8, n_layers=1, d_model=8, n_heads=2,
                   verbose=False,
                   config=ModelConfig(batch_size=2,
                                      compute_dtype="float32"))
        devs = jax.devices()[:2]
        dp = TransformerLM(mesh=make_training_mesh(MeshSpec(data=2), devs),
                           **net)
        tp = TransformerLM_TP(
            mesh=make_training_mesh(MeshSpec(data=1, model=2), devs), **net)
        assert dp.module.attn_impl is None
        assert tp.module.attn_impl == "xla"
