"""Telemetry subsystem (theanompi_tpu/monitor): registry math, span
nesting + device fence, heartbeat freshness, straggler detection,
postmortem dump, and the strict disabled no-op contract."""

import json
import os
import threading
import time

import numpy as np
import pytest

from theanompi_tpu import monitor
from theanompi_tpu.monitor.health import HeartbeatReporter, StragglerDetector
from theanompi_tpu.monitor.registry import (
    Histogram,
    MetricsRegistry,
    tree_bytes,
    tree_dtypes,
)
from theanompi_tpu.monitor.spans import Span, open_spans


@pytest.fixture(autouse=True)
def fresh_monitor():
    monitor.reset_for_tests()
    yield
    monitor.reset_for_tests()


# ---------------------------------------------------------------------------
# registry math
# ---------------------------------------------------------------------------


def test_counter_and_gauge():
    r = MetricsRegistry()
    r.inc("req")
    r.inc("req", 4)
    assert r.value("req") == 5
    r.set_gauge("clients", 3)
    r.add_gauge("clients", -1)
    assert r.value("clients") == 2


def test_label_isolation():
    r = MetricsRegistry()
    r.inc("rpc", 1, op="a")
    r.inc("rpc", 10, op="b")
    r.inc("rpc", 100, op="a")
    assert r.value("rpc", op="a") == 101
    assert r.value("rpc", op="b") == 10
    # label ORDER must not split series
    r.inc("multi", 1, x="1", y="2")
    r.inc("multi", 1, y="2", x="1")
    assert r.value("multi", x="1", y="2") == 2


def test_kind_conflict_raises():
    r = MetricsRegistry()
    r.inc("metric")
    with pytest.raises(TypeError):
        r.observe("metric", 1.0)


def test_histogram_math_and_percentiles():
    h = Histogram()
    for v in range(1, 101):  # 1..100
        h.observe(float(v))
    assert h.count == 100
    assert h.sum == pytest.approx(5050.0)
    assert h.min == 1.0 and h.max == 100.0
    assert h.percentile(50) == 50.0
    assert h.percentile(95) == 95.0
    assert h.percentile(99) == 99.0


def test_histogram_percentile_edges():
    h = Histogram()
    # empty: no percentile, None min/max in state
    assert h.percentile(50) is None
    st = h.state()
    assert st["count"] == 0 and st["p50"] is None and st["min"] is None
    # single observation: every percentile IS that value
    h.observe(7.5)
    assert h.percentile(50) == 7.5
    assert h.percentile(99) == 7.5
    assert h.state()["mean"] == 7.5


def test_histogram_ring_bounds_memory():
    h = Histogram(ring=8)
    for v in range(1000):
        h.observe(float(v))
    assert h.count == 1000          # exact count survives
    assert h.sum == pytest.approx(sum(range(1000)))
    assert h.percentile(50) >= 992.0  # ring only holds the newest 8


def test_registry_thread_safety():
    r = MetricsRegistry()

    def work():
        for _ in range(1000):
            r.inc("n")

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert r.value("n") == 8000
    assert r.write_count == 8000


def test_snapshot_jsonl_and_prometheus(tmp_path):
    r = MetricsRegistry()
    r.inc("service/requests_total", 3, op="ping")
    r.observe("rpc_ms", 1.5, op="ping")
    path = r.write_jsonl(str(tmp_path / "m.jsonl"))
    recs = [json.loads(l) for l in open(path)]
    by_name = {rec["name"]: rec for rec in recs}
    assert by_name["service/requests_total"]["value"] == 3
    assert by_name["rpc_ms"]["count"] == 1
    prom = r.to_prometheus()
    assert 'theanompi_service_requests_total{op="ping"} 3' in prom
    assert "# TYPE theanompi_rpc_ms summary" in prom


def test_prometheus_escapes_label_values():
    # a client-supplied label value (service op names come off the
    # wire) must not be able to corrupt the exposition format
    r = MetricsRegistry()
    r.inc("errs", 1, op='get"x\\y\nz')
    prom = r.to_prometheus()
    assert 'op="get\\"x\\\\y\\nz"' in prom
    assert "\nz\"" not in prom  # no raw newline inside a label value


def test_tree_bytes_and_dtypes():
    tree = {"a": np.zeros((4, 4), np.float32), "b": np.zeros(3, np.uint8)}
    assert tree_bytes(tree) == 4 * 4 * 4 + 3
    assert tree_dtypes(tree) == "float32,uint8"
    assert tree_bytes({"s": "not-an-array"}) == 0


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_span_nesting_and_registry_feed():
    r = MetricsRegistry()
    with Span("outer", registry=r):
        with Span("inner", registry=r):
            time.sleep(0.01)
    snap = {(s["name"], s["labels"].get("name")): s
            for s in r.snapshot()}
    assert ("span_ms", "outer") in snap
    assert ("span_ms", "outer/inner") in snap
    inner = snap[("span_ms", "outer/inner")]
    assert inner["count"] == 1 and inner["sum"] >= 10.0
    # outer covers inner
    assert snap[("span_ms", "outer")]["sum"] >= inner["sum"]


def test_span_fence_on_cpu_arrays():
    import jax.numpy as jnp

    r = MetricsRegistry()
    with Span("fenced", registry=r, fence={"x": jnp.ones((32,)),
                                           "y": jnp.zeros((4, 4))}):
        pass
    assert r.get("span_ms", name="fenced").count == 1


def test_open_spans_visible_across_threads():
    release = threading.Event()
    started = threading.Event()

    def worker():
        with Span("worker-phase"):
            started.set()
            release.wait(timeout=5)

    t = threading.Thread(target=worker, name="spanthread")
    t.start()
    try:
        assert started.wait(timeout=5)
        names = [s["name"] for s in open_spans()]
        assert "worker-phase" in names
    finally:
        release.set()
        t.join(30)
    assert "worker-phase" not in [s["name"] for s in open_spans()]


def test_span_records_on_exception():
    r = MetricsRegistry()
    with pytest.raises(ValueError):
        with Span("dies", registry=r):
            raise ValueError("boom")
    assert r.get("span_ms", name="dies").count == 1
    assert r.value("span_errors_total", name="dies") == 1
    assert open_spans() == []  # cleaned up despite the exception


# ---------------------------------------------------------------------------
# heartbeat / watchdog / straggler
# ---------------------------------------------------------------------------


def test_heartbeat_file_freshness(tmp_path):
    hb = HeartbeatReporter(str(tmp_path), rank=3, interval=0.05,
                           stall_after=60)
    hb.start()
    try:
        hb.progress(phase="train", step=12)
        time.sleep(0.15)  # at least one reporter tick
        rec = json.load(open(tmp_path / "heartbeat_rank3.json"))
    finally:
        hb.stop()
    assert rec["rank"] == 3
    assert rec["phase"] == "train" and rec["step"] == 12
    assert rec["stalled"] is False
    assert time.time() - rec["written"] < 5.0  # fresh
    assert rec["progress_age_s"] < 5.0


def test_watchdog_flags_stall(tmp_path, capsys):
    r = MetricsRegistry()
    hb = HeartbeatReporter(str(tmp_path), rank=0, registry=r,
                           interval=0.05, stall_after=0.15)
    hb.start()
    try:
        hb.progress(phase="device_init")
        time.sleep(0.4)  # exceed stall_after with no progress
        rec = json.load(open(tmp_path / "heartbeat_rank0.json"))
        assert rec["stalled"] is True
        assert r.value("health/stalls_total",
                       phase="device_init") >= 1
        # progress clears the flag (read state() directly: immediate,
        # no reporter-tick race)
        hb.progress(phase="train", step=1)
        assert hb.state()["stalled"] is False
        assert r.value("health/stall_recoveries_total") >= 1
    finally:
        hb.stop()
    assert "WATCHDOG" in capsys.readouterr().err


def test_heartbeat_tracks_workers(tmp_path):
    hb = HeartbeatReporter(str(tmp_path), rank=0, interval=5)
    hb.progress(phase="train", step=4, worker=1)
    hb.progress(phase="train", step=9, worker=2)
    state = hb.state()
    assert state["workers"]["1"]["step"] == 4
    assert state["workers"]["2"]["step"] == 9


def test_straggler_detection_flags_slow_worker():
    r = MetricsRegistry()
    det = StragglerDetector(factor=2.0, window=16, min_samples=4,
                            registry=r)
    # two healthy workers at ~10ms, one at 100ms
    for _ in range(8):
        det.observe(0, 0.010)
        det.observe(1, 0.011)
    flagged = [det.observe(2, 0.100) for _ in range(8)]
    assert flagged[-1] is True
    assert det.stragglers() == [2]
    assert r.value("health/straggler_flags_total", worker="2") == 1
    # recovery un-flags
    for _ in range(16):
        det.observe(2, 0.010)
    assert det.stragglers() == []


def test_straggler_needs_two_workers():
    det = StragglerDetector(min_samples=2)
    for _ in range(10):
        assert det.observe(0, 1.0) is False  # solo: never a straggler


def test_straggler_persistent_two_worker_case():
    # the fleet median must EXCLUDE the candidate: with a pooled median
    # a 2-worker straggler whose window is as full as its peer's could
    # never exceed factor x the median, however slow it is
    det = StragglerDetector(factor=2.0, window=8, min_samples=4)
    for _ in range(16):  # both windows saturated
        det.observe(0, 0.010)
        det.observe(1, 0.100)
    assert det.observe(1, 0.100) is True
    assert det.stragglers() == [1]


# ---------------------------------------------------------------------------
# facade: sessions, the no-op contract, postmortem
# ---------------------------------------------------------------------------


def test_disabled_is_noop(monkeypatch):
    """The acceptance contract: with monitoring off, instrumented code
    paths produce ZERO registry writes."""
    monkeypatch.delenv(monitor.ENV_VAR, raising=False)
    with monitor.session():  # no dir anywhere -> disabled
        monitor.inc("a")
        monitor.set_gauge("b", 1)
        monitor.observe("c", 2.0)
        monitor.observe_step(0.01, phase="train", step=1, worker=0)
        monitor.progress(phase="x")
        with monitor.span("s", fence=np.ones(3)):
            pass
        assert monitor.flush() is None
        assert monitor.dump_postmortem(RuntimeError("x")) is None
    assert monitor.registry().write_count == 0
    assert monitor.registry().series_names() == set()


def test_env_var_enables(tmp_path, monkeypatch):
    monkeypatch.setenv(monitor.ENV_VAR, str(tmp_path))
    with monitor.session() as live:
        assert live and monitor.enabled()
        monitor.inc("via_env")
    assert not monitor.enabled()
    recs = [json.loads(l)
            for l in open(tmp_path / "metrics_rank0.jsonl")]
    assert any(r["name"] == "via_env" for r in recs)
    assert (tmp_path / "metrics_rank0.prom").exists()
    assert (tmp_path / "heartbeat_rank0.json").exists()


def test_consecutive_sessions_get_fresh_registries(tmp_path):
    # a sweep running two monitored sessions in one process: run 2's
    # snapshot must not merge run 1's series
    with monitor.session(run_dir=str(tmp_path / "run1")):
        monitor.inc("steps", 5)
    with monitor.session(run_dir=str(tmp_path / "run2")):
        monitor.inc("steps", 2)
    r2 = [json.loads(l)
          for l in open(tmp_path / "run2" / "metrics_rank0.jsonl")]
    assert next(r for r in r2 if r["name"] == "steps")["value"] == 2


def test_session_activation_failure_does_not_leak_depth(tmp_path,
                                                        monkeypatch):
    # a bad knob (or unwritable dir) must fail THAT session, not poison
    # every later one into a silent it-looks-live-but-records-nothing
    # state
    monkeypatch.setenv("THEANOMPI_TPU_MONITOR_INTERVAL", "5s")  # bad
    with pytest.raises(ValueError):
        with monitor.session(run_dir=str(tmp_path)):
            pass
    monkeypatch.delenv("THEANOMPI_TPU_MONITOR_INTERVAL")
    with monitor.session(run_dir=str(tmp_path)) as live:
        assert live and monitor.enabled()
        monitor.inc("recovered")
    assert monitor.registry().value("recovered") == 1


def test_nested_sessions_share_state(tmp_path):
    with monitor.session(run_dir=str(tmp_path)):
        with monitor.session(run_dir=str(tmp_path / "ignored")):
            monitor.inc("n")
        assert monitor.enabled()  # inner exit must not tear down
        monitor.inc("n")
    assert not monitor.enabled()
    recs = [json.loads(l)
            for l in open(tmp_path / "metrics_rank0.jsonl")]
    assert next(r for r in recs if r["name"] == "n")["value"] == 2
    assert not (tmp_path / "ignored").exists()


def test_postmortem_on_injected_exception(tmp_path):
    # a worker thread sits inside a span during the crash — its span
    # must appear in the dump's open-spans section (the crashing
    # thread's own spans unwind with the exception, by design: their
    # durations + error counts are already in the registry)
    release = threading.Event()
    started = threading.Event()

    def worker():
        with Span("worker/exchange"):
            started.set()
            release.wait(timeout=10)

    t = threading.Thread(target=worker)
    t.start()
    try:
        assert started.wait(timeout=5)
        with pytest.raises(RuntimeError, match="injected"):
            with monitor.session(run_dir=str(tmp_path)):
                monitor.observe_step(0.020, phase="train", step=1)
                monitor.observe_step(0.021, phase="train", step=2)
                with monitor.span("train/epoch0"):
                    raise RuntimeError("injected failure")
    finally:
        release.set()
        t.join(30)
    pm = json.load(open(tmp_path / "postmortem_rank0.json"))
    assert pm["exception"]["type"] == "RuntimeError"
    assert "injected failure" in pm["exception"]["message"]
    assert "RuntimeError" in pm["exception"]["traceback"]
    assert "worker/exchange" in [s["name"] for s in pm["open_spans"]]
    assert pm["recent_step_ms"] == [20.0, 21.0]
    assert any(m["name"] == "step_ms" for m in pm["metrics"])
    # the crashed span's timing + error count made it into the dump
    span_recs = [m for m in pm["metrics"] if m["name"] == "span_errors_total"]
    assert any(m["labels"]["name"] == "train/epoch0" for m in span_recs)


# ---------------------------------------------------------------------------
# rule-loop integration (the acceptance contract)
# ---------------------------------------------------------------------------


def _tiny_bsp_model(mesh8):
    from theanompi_tpu.data.cifar10 import Cifar10_data
    from theanompi_tpu.models.base import ModelConfig
    from theanompi_tpu.models.cifar10 import Cifar10_model

    class Tiny(Cifar10_model):
        def build_data(self):
            return Cifar10_data(synthetic_n=80)  # 5 iters at batch 2x8

    cfg = ModelConfig(batch_size=2, n_epochs=1, print_freq=10**9,
                      compute_dtype="float32")
    return Tiny(config=cfg, mesh=mesh8)


def test_bsp_session_emits_telemetry(tmp_path, mesh8):
    """5-step CPU BSP run with monitoring on: parseable snapshot with
    the step-time histogram + section span totals, fresh heartbeat."""
    from theanompi_tpu.rules.bsp import run_bsp_session

    run_bsp_session(_tiny_bsp_model(mesh8), max_epochs=1,
                    checkpoint=False, monitor_dir=str(tmp_path))
    recs = [json.loads(l)
            for l in open(tmp_path / "metrics_rank0.jsonl")]
    by = {}
    for r in recs:
        by.setdefault(r["name"], []).append(r)
    # step-time histogram: 5 steps observed
    (steps,) = by["step_ms"]
    assert steps["kind"] == "histogram" and steps["count"] == 5
    assert steps["p50"] is not None and steps["sum"] > 0
    # section span totals (recorder as registry client + phase spans)
    sections = {r["labels"]["section"] for r in by["recorder/section_ms"]}
    assert {"calc", "wait"} <= sections
    span_names = {r["labels"]["name"] for r in by["span_ms"]}
    assert "bsp/compile" in span_names and "bsp/epoch" in span_names
    # exchange shape counters (traced once per compile)
    assert by["exchange/bytes_per_call"][0]["value"] > 0
    # fresh heartbeat that reached the end of the epoch
    hb = json.load(open(tmp_path / "heartbeat_rank0.json"))
    assert time.time() - hb["written"] < 60
    assert hb["stalled"] is False and hb["phase"] == "epoch_end"
    # prometheus dump parses to the same series
    prom = open(tmp_path / "metrics_rank0.prom").read()
    assert "theanompi_step_ms_count" in prom


def test_bsp_session_disabled_zero_writes(monkeypatch, mesh8):
    """With monitoring disabled the instrumented rule loop performs
    ZERO registry writes — the no-op fast path."""
    from theanompi_tpu.rules.bsp import run_bsp_session

    monkeypatch.delenv(monitor.ENV_VAR, raising=False)
    run_bsp_session(_tiny_bsp_model(mesh8), max_epochs=1,
                    checkpoint=False)
    assert monitor.registry().write_count == 0
    assert monitor.registry().series_names() == set()


def test_bsp_crash_writes_postmortem(tmp_path, mesh8):
    from theanompi_tpu.rules.bsp import run_bsp_session

    model = _tiny_bsp_model(mesh8)
    calls = {"n": 0}
    orig = model.train_iter

    def dying_train_iter(it, recorder):
        calls["n"] += 1
        if calls["n"] >= 3:
            raise RuntimeError("injected step crash")
        return orig(it, recorder)

    model.train_iter = dying_train_iter
    with pytest.raises(RuntimeError, match="injected step crash"):
        run_bsp_session(model, max_epochs=1, checkpoint=False,
                        monitor_dir=str(tmp_path))
    pm = json.load(open(tmp_path / "postmortem_rank0.json"))
    assert pm["exception"]["type"] == "RuntimeError"
    assert len(pm["recent_step_ms"]) == 2  # the steps that completed
    assert any(m["name"] == "step_ms" for m in pm["metrics"])


def test_observe_step_feeds_histogram_and_straggler(tmp_path):
    with monitor.session(run_dir=str(tmp_path)):
        for _ in range(8):
            monitor.observe_step(0.010, worker=0)
            monitor.observe_step(0.010, worker=1)
        flagged = False
        for _ in range(8):
            flagged = monitor.observe_step(0.100, worker=2)
        assert flagged is True
        reg = monitor.registry()
        assert reg.get("step_ms", worker="0").count == 8
        assert reg.get("step_ms", worker="2").count == 8
