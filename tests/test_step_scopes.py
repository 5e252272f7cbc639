"""The step program by scope and phase (PR 36): ``monitor/scopes.py``'s
reading of ``op_name``, its map of a compiled module, what ``TpuModel``
notes and when anything is lowered, the benchmark's helper on hand-made
events, and the scopes the benchmark's metrics name in the five
configurations' own step programs."""

import importlib.util
import json
import os
import re
import types

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from benchmarks import run as bench_run
from benchmarks import scope_shares
from benchmarks import trace as trace_lib
from theanompi_tpu.monitor import scopes
from theanompi_tpu.parallel.bsp import SCOPE_UPDATE
from theanompi_tpu.parallel.exchanger import SCOPE_EXCHANGE

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- (a), (b): a small step with every construct the parser must see ------


@jax.custom_vjp
def _sine(x):
    return jnp.sin(x)


_sine.defvjp(lambda x: (jnp.sin(x), x), lambda x, g: (g * jnp.cos(x),))


class _Layer(nn.Module):
    @nn.compact
    def __call__(self, x):
        with jax.named_scope("m/ssd"):
            return jnp.tanh(nn.Dense(8, name="proj")(x))


class _Net(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.remat(_Layer)(name="layer0")(x)
        with jax.named_scope("m/scanbody"):
            ws = self.param("ws", nn.initializers.normal(), (3, 8, 8))
            x, _ = jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), x, ws)
        with jax.named_scope("m/cv"):
            x = _sine(x)
        with jax.named_scope("m/switch"):
            x = jax.lax.switch(jnp.int32(x.sum() > 0),
                               [lambda y: y * 2, jnp.exp], x)
        with jax.named_scope("m/einsum"):
            x = jnp.einsum("bd,bd->bd", x, x)
        with jax.named_scope("m/loss"):
            return (x ** 2).mean()


@pytest.fixture(scope="module")
def small_step_text(devices8):
    net = _Net()
    x = jnp.ones((4, 8))
    params = net.init(jax.random.key(0), x)
    mesh = jax.make_mesh((2,), ("data",), devices=devices8[:2])

    def shard_step(params, x):
        loss, grads = jax.value_and_grad(lambda p: net.apply(p, x))(params)
        with jax.named_scope(SCOPE_EXCHANGE):
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, "data"), grads)
        with jax.named_scope(SCOPE_UPDATE):
            return jax.tree.map(lambda p, g: p - 0.1 * g, params, grads), loss

    step = jax.jit(jax.shard_map(shard_step, mesh=mesh,
                                 in_specs=(P(), P("data")),
                                 out_specs=(P(), P()),
                                 check_vma=False))
    return step.lower(params, x).compile().as_text()


def _op_names(hlo_text):
    return {m.group(2) for m in scopes._INSTRUCTION.finditer(hlo_text)}


def test_parse_gives_every_phase_and_scope_of_a_small_step(small_step_text):
    got = {scopes.parse(op) for op in _op_names(small_step_text)}
    assert got == {
        ("forward", ""),  # parameters, shard_map's own glue
        ("forward", "_Net/layer0/m/ssd"),
        ("forward", "_Net/layer0/m/ssd/proj"),
        ("recompute", "_Net/layer0/m/ssd"),
        ("recompute", "_Net/layer0/m/ssd/proj"),
        ("backward", "_Net/layer0/m/ssd"),
        ("backward", "_Net/layer0/m/ssd/proj"),
        ("forward", "_Net/m/scanbody"),
        ("backward", "_Net/m/scanbody"),
        ("forward", "_Net/m/cv"),
        ("backward", "_Net/m/cv"),
        ("forward", "_Net/m/switch"),
        ("backward", "_Net/m/switch"),
        ("forward", "_Net/m/einsum"),
        ("backward", "_Net/m/einsum"),
        ("forward", "_Net/m/loss"),
        ("backward", "_Net/m/loss"),
        ("forward", "bsp/exchange"),
        ("forward", "bsp/update"),
    }
    # the program really held what the parser dropped
    raw = " ".join(_op_names(small_step_text))
    for token in ("shard_map", "while/body", "closed_call", "cond/branch_1",
                  "checkpoint/rematted_computation", "transpose(jvp(_Net))",
                  "bd,bd->bd"):
        assert token in raw, token


@pytest.mark.parametrize("op_name, want", [
    ("jit(step)/jvp(m/ssd)/tanh", ("forward", "m/ssd")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/m/ssd/add_any",
     ("backward", "m/ssd")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "m/ssd/tanh", ("recompute", "m/ssd")),
    ("jit(step)/transpose(jvp())/while/body/closed_call/m/scanbody/"
     "dot_general", ("backward", "m/scanbody")),
    ("jit(step)/bsp/update/sub", ("forward", "bsp/update")),
    # a checkpointed layer's backward re-enters the transform it is under
    ("jit(shard_step)/transpose(jvp(Net))/jvp(Net)/checkpoint/"
     "rematted_computation/Layer_3/mamba/nemotron_h/mamba/ssd/"
     "bcqgn,bcgrpn->bcqgrp/dot_general",
     ("recompute", "Net/Layer_3/mamba/nemotron_h/mamba/ssd")),
    # a jax.vjp inside a custom backward says the stack again from its root
    ("jit(s)/transpose(jvp(Net))/jvp(Net)/checkpoint/Layer_1/moe/"
     "nemotron_h/experts/rung/cond/branch_0_fun/jvp(Net)/Layer_1/moe/"
     "nemotron_h/experts/rung/transpose(jvp(Net))/Layer_1/moe/nemotron_h/"
     "experts/rung/mul",
     ("backward", "Net/Layer_1/moe/nemotron_h/experts/rung")),
    ("jit(f)/Layer_0/moe/nemotron_h/experts/jit(take_along_axis)/gather",
     ("forward", "Layer_0/moe/nemotron_h/experts")),
    ("jit(f)/jvp(Net)/m/switch/cond/branch_12_fun/custom_vjp_call/mul",
     ("forward", "Net/m/switch")),
    ("a/b/mul;transpose(jvp(Net))/m/loss/broadcast_in_dim",
     ("forward", "a/b")),  # XLA merged two instructions: the first stands
    ("state.params['Layer_0']['norm']['scale']", ("forward", "")),
    ("x", ("forward", "")),
    ("", ("forward", "")),
])
def test_parse_of_one_op_name(op_name, want):
    assert scopes.parse(op_name) == want


def test_the_dropped_tokens_are_pinned():
    """What counts as JAX's own in a name stack is this list, letter
    for letter: a change here changes every scope metric's reading."""
    assert scopes.PHASES == ("forward", "backward", "recompute")
    assert scopes.FUNCTION_WRAPPERS == {"jit", "pjit", "shard_map"}
    assert scopes.TRANSFORM_WRAPPERS == {"jvp", "transpose", "vmap"}
    assert scopes.DROPPED_TOKENS == {
        "while", "body", "cond", "closed_call", "checkpoint",
        "rematted_computation", "custom_vjp_call", "custom_jvp_call",
        "shard_map"}
    assert scopes.BRANCH_TOKEN.pattern == r"^branch_\d+_fun$"
    assert scopes.EINSUM_TOKEN.pattern == r"->"


def test_scope_map_names_every_instruction_that_has_an_op_name(
        small_step_text):
    mapped = scopes.scope_map(small_step_text)
    with_name = [line for line in small_step_text.splitlines()
                 if "op_name=" in line and " = " in line]
    assert len(with_name) > 100
    for line in with_name:
        name = line.split(" = ", 1)[0].replace("ROOT", "").strip().lstrip("%")
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        assert mapped[name] == scopes.parse(op_name), line
    assert len(mapped) == len(with_name)  # names are a module's own
    # fusions AND the instructions inside them, in every computation
    assert any(name.startswith("fusion") or "_fusion" in name
               for name in mapped)
    assert {"forward", "backward", "recompute"} == {
        phase for phase, _ in mapped.values()}
    assert scopes.scope_map("HloModule m\n\nENTRY %e () -> f32[] {\n"
                            "  ROOT %c = f32[] constant(0)\n}\n") == {}


# -- (c): the benchmark's helper on hand-made events ------------------------


def _run(ops, window=(0, 1000), on_device=True):
    trace = trace_lib.from_events(
        {chip: list(chip_ops) for chip, chip_ops in ops.items()},
        [("bench/segment",) + tuple(window)])
    return types.SimpleNamespace(trace=trace, on_device=on_device,
                                 trace_lib=trace_lib, phases={})


def _reader(metric):
    spec = importlib.util.spec_from_file_location(
        "bench_reader_" + metric,
        os.path.join(ROOT, "benchmarks", "layer_metrics", metric + ".py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    return reader


@pytest.fixture
def handmade_map(monkeypatch):
    """The program's map, hand-made."""
    monkeypatch.setattr(scope_shares, "step_map", lambda run: HANDMADE_MAP)


HANDMADE_OPS = {0: [
    # a backward while of 400 ns holds a recomputed forward (100), a
    # backward fusion (200) and an op the map does not know (100)
    ("while.1", "while", 0, 400),
    ("fusion.1", "fusion kLoop", 0, 100),
    ("fusion.2", "fusion kOutput", 100, 300),
    ("copy.9", "copy", 300, 400),
    ("update_fusion", "fusion kLoop", 400, 500),
    ("ssd_kernel", "custom-call tpu_custom_call", 500, 700),
    ("conditional.3", "conditional", 700, 800),   # a container, empty
    ("fusion.7", "fusion kLoop", 900, 1100),      # half outside the window
]}
HANDMADE_MAP = {
    "while.1": ("backward", "Net/Layer_0/nemotron_h/mamba/ssd"),
    "fusion.1": ("recompute", "Net/Layer_0/nemotron_h/mamba/ssd"),
    "fusion.2": ("backward", "Net/Layer_0/nemotron_h/mamba/ssd"),
    "update_fusion": ("forward", "bsp/update"),
    "ssd_kernel": ("forward", "Net/Layer_1/nemotron_h/mamba/ssd/chunk"),
    "conditional.3": ("forward", "Net/lm/loss"),
    "fusion.7": ("forward", ""),
}


@pytest.mark.parametrize("phase, scope, want_ns", [
    # busy: 0-800 and 900-1000 = 900 ns; every share is of THAT
    (None, r".", 100 + 200 + 100 + 200),   # coverage: copy.9 and the
                                           # unscoped fusion.7 lower it
    ("backward", None, 200),               # the while itself is no hit
    ("recompute", None, 100),
    ("forward", None, 100 + 200 + 100),    # fusion.7 clipped to 100
    (None, r"^bsp/update", 100),
    (None, r"(^|/)nemotron_h/mamba/ssd(/|$)", 100 + 200 + 200),
    ("backward", r"mamba/ssd", 200),
    (None, r"lm/loss", 0),                 # only a container had it
    (None, None, 700),                     # every mapped leaf
])
def test_share_is_of_device_busy_time_in_mapped_leaf_ops(phase, scope,
                                                         want_ns,
                                                         handmade_map):
    run = _run(HANDMADE_OPS)
    assert trace_lib.busy_ns(run.trace) == 900
    got = scope_shares.share(run, phase=phase, scope=scope)
    assert got == pytest.approx(100.0 * want_ns / 900)


def test_share_is_the_mean_over_chips_and_none_without_a_reading(
        monkeypatch):
    with monkeypatch.context() as patch:
        patch.setattr(scope_shares, "step_map", lambda run: HANDMADE_MAP)
        ops = dict(HANDMADE_OPS)
        ops[1] = [("update_fusion", "fusion kLoop", 0, 500)]
        got = scope_shares.share(_run(ops), scope=r"^bsp/update")
        assert got == pytest.approx(100.0 * (100 / 900 + 1.0) / 2)
        dry = _run(HANDMADE_OPS, on_device=False)
        assert scope_shares.share(dry) is None
        no_trace = types.SimpleNamespace(trace=None, on_device=True,
                                         phases={})
        assert scope_shares.share(no_trace) is None
    # no map: nobody has stepped, so the program has nothing to say
    scopes.forget_for_tests()
    run = _run(HANDMADE_OPS)
    assert scope_shares.share(run, scope=r".") is None
    assert run.phases["step_scopes_s"] < 1.0


@pytest.mark.parametrize("metric", [
    "scope_coverage", "backward_share", "update_share", "recompute_share",
    "loss_share", "ssd_share", "expert_layer_share"])
def test_each_reader_is_its_pattern_and_the_helper(metric, monkeypatch):
    reader = _reader(metric)
    asked = []
    monkeypatch.setattr(
        scope_shares, "share",
        lambda run, phase=None, scope=None: asked.append((phase, scope))
        or 12.5)
    assert reader.read(object()) == 12.5
    (phase, scope), = asked
    assert phase == getattr(reader, "PHASE", None)
    assert scope == getattr(reader, "SCOPE", None)
    assert (phase is None) != (scope is None)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = [m for m in json.load(f)["per_layer"]
                    if m["name"].split(".")[0] == metric]
    assert declared and all(
        (m["unit"], m["source"], m["layer"], bool(m["workloads"]))
        == ("%", "device_trace", "step program", True) for m in declared)


@pytest.mark.parametrize("metric, scope, hits", [
    ("loss_share", "TransformerLMNet/lm/loss", True),
    ("loss_share", "lm/loss", True),
    ("loss_share", "ZayaLMNet/zaya/loss", True),
    ("loss_share", "OuroLMNet/ouro/loss", True),
    ("loss_share", "NemotronHLMNet/nemotron_h/loss", True),
    ("loss_share", "Net/film/loss", False),
    ("ssd_share", "N/Layer_0/mamba/nemotron_h/mamba/ssd/ssd_chunked", True),
    ("ssd_share", "N/Layer_0/mamba/nemotron_h/mamba/ssd_prep", False),
    ("expert_layer_share", "Z/Layer_1/moe/zaya/experts", True),
    ("expert_layer_share", "N/Layer_1/moe/nemotron_h/experts/rung", True),
    ("expert_layer_share", "N/Layer_1/moe/nemotron_h/shared_expert", False),
    ("update_share", "bsp/update", True),
    ("update_share", "Net/bsp/update", False),
    ("scope_coverage", "x", True),
    ("scope_coverage", "", False),
])
def test_the_patterns_find_their_scopes_and_no_neighbour(metric, scope,
                                                         hits):
    reader = _reader(metric)
    assert bool(re.search(reader.SCOPE, scope)) is hits


# -- (d): nothing is lowered until somebody asks ----------------------------


class _Counting:
    """A jitted step that counts what is asked of it."""

    def __init__(self, fn):
        self.fn, self.calls, self.lowers = fn, 0, 0

    def __call__(self, *args):
        self.calls += 1
        return self.fn(*args)

    def lower(self, *args):
        self.lowers += 1
        return self.fn.lower(*args)


@pytest.fixture
def tiny_model(mesh8):
    from theanompi_tpu.models.base import ModelConfig
    from _tiny_models import TinyCifar

    model = TinyCifar(config=ModelConfig(
        batch_size=2, n_epochs=1, print_freq=10**9,
        compute_dtype="float32"), mesh=mesh8, verbose=False)
    model.compile_iter_fns("avg")
    model.begin_epoch(0)
    yield model
    model.cleanup()


def test_nothing_is_lowered_until_the_map_is_asked_for(tiny_model,
                                                       monkeypatch):
    from theanompi_tpu.utils.recorder import Recorder

    scopes.forget_for_tests()
    assert scopes.step_scopes() is None  # nobody has stepped
    noted = []
    note_step = scopes.note_step
    monkeypatch.setattr(
        scopes, "note_step",
        lambda fn, args: noted.append(fn) or note_step(fn, args))
    step = tiny_model.train_step = _Counting(tiny_model.train_step)
    recorder = Recorder(rank=0, size=tiny_model.n_workers, print_freq=0)
    for it in range(3):
        tiny_model.train_iter(it, recorder)
    tiny_model._flush_metrics(recorder)
    assert (step.calls, step.lowers) == (3, 0)
    assert noted == [step]            # the first dispatch, and only it
    assert tiny_model._noted_step is step
    assert scopes._last.map is None   # nothing parsed either
    # what was noted keeps no array alive: shapes, dtypes, shardings
    assert all(isinstance(leaf, jax.ShapeDtypeStruct)
               for leaf in jax.tree.leaves(scopes._last.args))

    mapped = scopes.step_scopes()
    assert step.lowers == 1
    assert scopes.step_scopes() is mapped and step.lowers == 1  # cached
    found = {entry for entry in mapped.values()}
    assert ("forward", "bsp/update") in found
    assert ("forward", "bsp/exchange") in found
    assert any(phase == "backward" for phase, _ in found)
    # another function dispatched is another note
    tiny_model.train_step = _Counting(step.fn)
    tiny_model.train_iter(3, recorder)
    tiny_model._flush_metrics(recorder)
    assert noted == [step, tiny_model.train_step]


def test_the_map_dies_with_the_step_that_was_noted():
    scopes.forget_for_tests()
    step = jax.jit(lambda x: x + 1)
    assert scopes.note_step(step, (jnp.ones(3),)) is step
    assert scopes.step_scopes() is not None
    del step
    assert scopes.step_scopes() is None
    # a state restored from a checkpoint holds NumPy leaves: noted alike
    import numpy as np

    step = jax.jit(lambda x, y: x + y)
    scopes.note_step(step, (np.ones(3, np.float32), jnp.ones(3)))
    assert scopes.step_scopes() is not None
    # a step that is no jitted function has no program to map
    plain = lambda x: x  # noqa: E731
    scopes.note_step(plain, (jnp.ones(3),))
    assert scopes.step_scopes() is None
    scopes.forget_for_tests()


STALE_CACHE_SCRIPT = """
import sys
import jax, jax.numpy as jnp
from theanompi_tpu.monitor import scopes
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
def loss(x, w):
    with jax.named_scope(sys.argv[1]):
        return (jnp.tanh(x @ w) ** 2).sum()
step = jax.jit(jax.grad(loss))
args = (jnp.ones((64, 64)), jnp.ones((64, 64)))
step(*args).block_until_ready()
in_memory = step.lower(*args).compile().as_text()
own = scopes.compile_with_own_metadata(step, args).as_text()
print(sys.argv[1] in in_memory, sys.argv[1] in own,
      jax.config.jax_compilation_cache_include_metadata_in_key)
"""


def test_a_cache_that_served_an_older_builds_executable_does_not_fool_it(
        tmp_path):
    """JAX's persistent cache keys a program without its metadata, so a
    build that only renamed a scope LOADS the older build's executable,
    ``op_name``s and all.  The map must be this source's."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_ENABLE_COMPILATION_CACHE="true",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=ROOT)
    said = []
    for scope in ("older/build", "this/build", "this/build"):
        done = subprocess.run(
            [sys.executable, "-c", STALE_CACHE_SCRIPT, scope], env=env,
            cwd=ROOT, capture_output=True, text=True, timeout=240)
        assert done.returncode == 0, done.stderr[-2000:]
        said.append(done.stdout.split())
    # (the scope is in the dispatch's executable, in the map's, the flag
    # is back to its default)
    assert said[0] == ["True", "True", "False"]
    assert said[1] == ["False", "True", "False"]  # the hazard, and the cure
    assert said[2] == ["False", "True", "False"]


def test_the_profiler_leaves_the_map_beside_its_capture(tiny_model,
                                                        tmp_path,
                                                        monkeypatch):
    from theanompi_tpu.utils.profiling import StepProfiler
    from theanompi_tpu.utils.recorder import Recorder

    scopes.forget_for_tests()
    recorder = Recorder(rank=0, size=tiny_model.n_workers, print_freq=0)
    order = []
    real_stop = jax.profiler.stop_trace
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda tree: order.append("fence"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: order.append("stop") or real_stop())
    with StepProfiler(str(tmp_path), n_steps=2) as profiler:
        for it in range(2):
            tiny_model.train_iter(it, recorder)
            profiler.step(fence=tiny_model.state.step)
    monkeypatch.undo()
    # the device has run what the host dispatched before the capture closes
    assert order == ["fence", "stop"]
    tiny_model._flush_metrics(recorder)
    path = tmp_path / scopes.SCOPES_FILE
    assert path.exists()
    assert scopes.read_step_scopes(str(path)) == scopes.step_scopes()
    assert not [p for p in os.listdir(tmp_path) if p.endswith(".tmp")]
    # a CPU capture holds no device plane: the account says so, and the
    # command fails without a traceback
    with pytest.raises(SystemExit, match="no /device:TPU plane"):
        scopes.main([str(tmp_path)])
    assert scopes.main([str(tmp_path / "nowhere")]) == 1


def test_the_account_is_a_table_by_scope_and_phase():
    ssd = "Net/Layer_*/mamba/nemotron_h/mamba/ssd"
    acc = {"steps": 4, "program": "jit_shard_step(1)", "busy_ms": 10.0,
           "rows": {(ssd, "forward"): 1.0, (ssd, "backward"): 2.0,
                    (ssd, "recompute"): 1.0,
                    ("bsp/update", "forward"): 2.5,
                    ("", "forward"): 0.5,
                    ("(not in the map)", ""): 3.0}}
    table = scopes.format_account(acc).splitlines()
    assert "4 step(s)" in table[0] and "10.000 ms" in table[0]
    assert table[2].split()[:5] == ["4.000", "2.000", "1.000", "10.000",
                                    "100.00"]
    assert table[3].split() == ["1.000", "2.000", "1.000", "4.000", "40.00",
                                "Net/Layer_*/mamba/nemotron_h/mamba/ssd"]
    assert table[4].endswith("(not in the map)")
    assert table[-1].endswith("(no scope)")
    only = scopes.format_account(acc, scope=r"mamba/ssd").splitlines()
    assert len(only) == 4 and "matching 'mamba/ssd'" in only[2]
    assert only[2].split()[3:5] == ["4.000", "40.00"]


# -- (e): the five configurations' own step programs ------------------------

CELLS = {
    "resnet50_b128_x1": {"scopes": [r"^bsp/update", r"BottleneckBlock_\d+",
                                    r"stem_conv"], "recompute": False},
    "gpt2m_s1024_x1": {"scopes": [r"^bsp/update", r"(^|/)lm/loss(/|$)",
                                  r"Block_\d+/mlp_up"], "recompute": False},
    "zaya1_8b_s2048_x1": {"scopes": [r"^bsp/update", r"(^|/)zaya/loss(/|$)",
                                     r"(^|/)zaya/experts(/|$)",
                                     r"zaya/cca", r"zaya/router"],
                          "recompute": False},
    "ouro_2_6b_s2048_x1": {"scopes": [r"^bsp/update", r"(^|/)ouro/loss(/|$)",
                                      r"ouro/pass", r"ouro/exit_gate"],
                           "recompute": True},
    "nemotron_twotower_30b_s2048_x1": {
        "scopes": [r"^bsp/update", r"(^|/)nemotron_h/loss(/|$)",
                   r"(^|/)nemotron_h/mamba/ssd(/|$)",
                   r"(^|/)nemotron_h/experts(/|$)",
                   r"nemotron_h/mamba/(in_proj|conv|gate_norm|out_proj)",
                   r"nemotron_h/(router|shared_expert|attention)"],
        "recompute": True},
}
#: a scope token is a scope's or a module's or a function's name: no
#: wrapper's parenthesis, no einsum's arrow, none of JAX's own
TOKEN = re.compile(r"^[A-Za-z_][\w.<>]*$")


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_configurations_step_program_holds_its_metrics_scopes(cell,
                                                                devices8):
    from theanompi_tpu.utils.recorder import Recorder

    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    declared, = (w for w in bench["workloads"] if w["name"] == cell)
    config = bench_run.load_json(bench_run.HERE, "configs",
                                 declared["config"] + ".json")
    traffic = bench_run.load_json(bench_run.HERE, "traffic",
                                  declared["traffic"] + ".json")
    config = bench_run.merged(config, config["dry_run"])
    traffic = bench_run.merged(traffic, traffic["dry_run"])
    scopes.forget_for_tests()
    model, _ = bench_run.build_model(config, traffic, 5, devices8[:1])
    try:
        model.compile_iter_fns("avg")
        model.begin_epoch(0)
        recorder = Recorder(rank=0, size=model.n_workers, print_freq=0)
        model.train_iter(0, recorder)
        model._flush_metrics(recorder)
        mapped = scopes.step_scopes()
    finally:
        model.cleanup()
    found = set(mapped.values())
    for pattern in CELLS[cell]["scopes"]:
        assert any(re.search(pattern, scope) for _, scope in found), pattern
    phases = {phase for phase, _ in found}
    assert {"forward", "backward"} <= phases
    assert ("recompute" in phases) is CELLS[cell]["recompute"]
    own = (scopes.DROPPED_TOKENS | scopes.FUNCTION_WRAPPERS
           | scopes.TRANSFORM_WRAPPERS)
    for _, scope in found:
        for token in filter(None, scope.split("/")):
            assert TOKEN.match(token) and token not in own, (token, scope)
    # the update's ops are the optimizer's: a share of the map that no
    # model scope claims
    assert not any(re.search(r"bsp/update", scope) and "/bsp/" in scope
                   for _, scope in found)
    # the cells the metrics list are the cells whose programs hold them
    listed = {m["name"]: m["workloads"] for m in bench["per_layer"]
              if m["layer"] == "step program" and "workloads" in m}
    assert (cell in listed["recompute_share.tok"]) is CELLS[cell]["recompute"]
    assert (cell in listed["ssd_share"]) is any(
        re.search(r"nemotron_h/mamba/ssd", scope) for _, scope in found)
    assert (cell in listed["expert_layer_share"]) is any(
        re.search(r"(zaya|nemotron_h)/experts", scope) for _, scope in found)


def test_benchmark_json_gained_the_ten_entries_of_pr_36_in_order():
    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    lm = ["gpt2m_s1024_x1", "zaya1_8b_s2048_x1", "gpt2m_s128_x1",
          "ouro_2_6b_s2048_x1", "nemotron_twotower_30b_s2048_x1"]
    img = ["resnet50_b128_x1"]
    tok, ips = "tokens_per_s_per_chip", "images_per_s_per_chip"
    want = [("scope_coverage.img", "higher", ips, img),
            ("scope_coverage.tok", "higher", tok, lm),
            ("backward_share.img", "lower", ips, img),
            ("backward_share.tok", "lower", tok, lm),
            ("update_share.img", "lower", ips, img),
            ("update_share.tok", "lower", tok, lm),
            ("recompute_share.tok", "lower", tok, lm[3:]),
            ("loss_share.tok", "lower", tok, lm),
            ("ssd_share", "lower", tok, lm[4:]),
            ("expert_layer_share", "lower", tok, [lm[1], lm[4]])]
    first = [m["name"] for m in bench["per_layer"]].index(
        "scope_coverage.img")
    got = bench["per_layer"][first:first + 10]
    # a later PR's cells may join a list, at its end (PR 38's did)
    assert [dict(m, workloads=m["workloads"][:len(cells)]) for m, (
        _, _, _, cells) in zip(got, want)] == [
        {"name": name, "unit": "%", "better": better,
         "source": "device_trace", "layer": "step program", "moves": moves,
         "workloads": cells} for name, better, moves, cells in want]
