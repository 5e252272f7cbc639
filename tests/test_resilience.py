"""Resilience subsystem (theanompi_tpu/resilience): retry-policy math,
fault-plan matching, supervisor restart/quorum semantics, checkpoint
integrity + corrupt-latest fallback, ServiceClient reconnect through a
server restart, and the fault-matrix e2e (EASGD worker killed mid-run
recovers from center) — plus the strict faults-disabled no-op
contract, the same discipline test_monitor.py pins for telemetry."""

from __future__ import annotations

import json
import os
import socket
import threading
import time

import numpy as np
import pytest

from theanompi_tpu import monitor
from theanompi_tpu.resilience import faults, recovery
from theanompi_tpu.resilience.faults import FaultInjected, FaultPlan
from theanompi_tpu.resilience.retry import RetryPolicy
from theanompi_tpu.resilience.supervisor import WorkerSupervisor


@pytest.fixture(autouse=True)
def fresh_resilience():
    faults.clear()
    monitor.reset_for_tests()
    yield
    faults.clear()
    monitor.reset_for_tests()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# retry policy
# ---------------------------------------------------------------------------


class TestRetryPolicy:
    def test_delay_growth_and_cap(self):
        p = RetryPolicy(base_delay=0.1, max_delay=1.0, multiplier=2.0,
                        jitter=0.0)
        assert p.delay(0) == pytest.approx(0.1)
        assert p.delay(1) == pytest.approx(0.2)
        assert p.delay(2) == pytest.approx(0.4)
        assert p.delay(10) == pytest.approx(1.0)  # capped

    def test_jitter_bounds(self):
        p = RetryPolicy(base_delay=1.0, max_delay=1.0, jitter=0.5)
        for _ in range(100):
            assert 0.5 <= p.delay(0) <= 1.0

    def test_call_retries_transient_then_succeeds(self):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise ConnectionResetError("transient")
            return "ok"

        p = RetryPolicy(max_attempts=5, base_delay=0.001, jitter=0.0)
        assert p.call(flaky) == "ok"
        assert len(calls) == 3

    def test_call_does_not_retry_unretryable(self):
        calls = []

        def bad():
            calls.append(1)
            raise ValueError("logic bug")

        p = RetryPolicy(max_attempts=5, base_delay=0.001)
        with pytest.raises(ValueError):
            p.call(bad)
        assert len(calls) == 1

    def test_call_exhausts_attempts(self):
        calls = []

        def down():
            calls.append(1)
            raise ConnectionRefusedError("down")

        p = RetryPolicy(max_attempts=3, base_delay=0.001, jitter=0.0)
        with pytest.raises(ConnectionRefusedError):
            p.call(down)
        assert len(calls) == 3

    def test_deadline_stops_early(self):
        def down():
            raise ConnectionRefusedError("down")

        p = RetryPolicy(max_attempts=100, base_delay=0.2, jitter=0.0,
                        deadline_s=0.05)
        t0 = time.monotonic()
        with pytest.raises(ConnectionRefusedError):
            p.call(down)
        assert time.monotonic() - t0 < 1.0

    def test_classifier_wins_over_types(self):
        p = RetryPolicy(max_attempts=3, base_delay=0.001,
                        classify=lambda e: "retry me" in str(e))
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 2:
                raise ValueError("please retry me")
            return 7

        assert p.call(flaky) == 7
        assert len(calls) == 2


# ---------------------------------------------------------------------------
# fault plan
# ---------------------------------------------------------------------------


class TestFaultPlan:
    def test_disabled_is_strict_noop(self):
        """The acceptance contract (same pattern as the monitor's
        zero-write guarantee): with no plan installed, every fire()
        site returns None after one is-None check, no wrapper objects
        exist, and the registry sees ZERO writes."""
        assert faults.enabled() is False
        assert faults._plan is None  # no lurking plan object
        for _ in range(100):
            assert faults.fire("worker_step", rule="easgd", worker=0,
                               step=1) is None
            assert faults.fire("service_call", op="easgd_exchange") is None
            assert faults.fire("checkpoint", epoch=0) is None
            assert faults.fire("exchange", kind="gosgd") is None
        assert monitor.registry().write_count == 0
        assert monitor.registry().series_names() == set()

    def test_raise_action_with_coordinates(self):
        faults.install([{"site": "worker_step", "worker": 1, "step": 3}])
        # wrong worker / wrong step: no fire
        assert faults.fire("worker_step", worker=0, step=3) is None
        assert faults.fire("worker_step", worker=1, step=2) is None
        with pytest.raises(FaultInjected, match="worker_step"):
            faults.fire("worker_step", worker=1, step=3)
        # times=1 default: consumed
        assert faults.fire("worker_step", worker=1, step=3) is None

    def test_int_vs_str_coordinates_equal(self):
        faults.install([{"site": "worker_step", "worker": "1"}])
        with pytest.raises(FaultInjected):
            faults.fire("worker_step", worker=1, step=0)

    def test_nth_and_times(self):
        faults.install([{"site": "service_call", "op": "x",
                         "action": "drop", "nth": 2, "times": 2}])
        assert faults.fire("service_call", op="x") is None      # 1st
        assert faults.fire("service_call", op="x") == "drop"    # 2nd
        assert faults.fire("service_call", op="x") == "drop"    # 3rd
        assert faults.fire("service_call", op="x") is None      # 4th

    def test_times_minus_one_fires_forever(self):
        faults.install([{"site": "exchange", "action": "drop",
                         "times": -1}])
        for _ in range(10):
            assert faults.fire("exchange", kind="easgd") == "drop"

    def test_delay_action_sleeps(self):
        faults.install([{"site": "service_call", "action": "delay",
                         "delay_s": 0.05}])
        t0 = time.monotonic()
        assert faults.fire("service_call", op="y") == "delay"
        assert time.monotonic() - t0 >= 0.04

    def test_load_inline_and_file(self, tmp_path):
        plan = faults.load('[{"site": "a"}]')
        assert isinstance(plan, FaultPlan) and len(plan) == 1
        path = tmp_path / "plan.json"
        path.write_text(json.dumps(
            [{"site": "b"}, {"site": "c", "action": "drop"}]))
        assert len(faults.load(str(path))) == 2

    def test_env_install(self, monkeypatch):
        monkeypatch.setenv(faults.ENV_VAR, '[{"site": "z"}]')
        faults.install_from_env()
        assert faults.enabled()
        with pytest.raises(FaultInjected):
            faults.fire("z")
        monkeypatch.delenv(faults.ENV_VAR)
        faults.install_from_env()
        assert not faults.enabled()

    def test_bad_spec_rejected(self):
        with pytest.raises(ValueError, match="site"):
            FaultPlan([{"action": "raise"}])
        with pytest.raises(ValueError, match="nth"):
            FaultPlan([{"site": "a", "nth": 0}])


# ---------------------------------------------------------------------------
# supervisor
# ---------------------------------------------------------------------------


class TestWorkerSupervisor:
    def test_restart_within_budget_completes(self):
        died = {"n": 0}
        restarted = []

        def worker(abort):
            if died["n"] < 2:
                died["n"] += 1
                raise FaultInjected("boom")

        sup = WorkerSupervisor(n_workers=1, max_restarts=2,
                               restart_from=restarted.append)
        sup.run([worker])
        assert restarted == [0, 0]
        assert sup.restart_counts() == {0: 2}
        assert sup.lost_workers() == []

    def test_budget_exhausted_quorum_lost_aborts(self):
        def worker(abort):
            raise FaultInjected("always dies")

        sup = WorkerSupervisor(n_workers=1, max_restarts=1,
                               restart_from=lambda r: None)
        with pytest.raises(FaultInjected):
            sup.run([worker])
        assert sup.lost_workers() == [0]

    def test_lost_worker_with_quorum_continues(self):
        lost_hook = []
        finished = []

        def dying(abort):
            raise FaultInjected("dead on arrival")

        def healthy(abort):
            finished.append(True)

        sup = WorkerSupervisor(n_workers=2, max_restarts=1,
                               min_workers=1, restart_from=None,
                               on_lost=lost_hook.append)
        sup.run([dying, healthy])  # must NOT raise
        assert lost_hook == [0]
        assert finished == [True]
        assert sup.lost_workers() == [0]

    def test_quorum_loss_aborts_peers(self):
        def dying(abort):
            raise FaultInjected("dead")

        def patient(abort):
            # cooperative loop: exits promptly on abort
            for _ in range(500):
                if abort.is_set():
                    return
                time.sleep(0.01)

        sup = WorkerSupervisor(n_workers=2, max_restarts=0,
                               min_workers=2, restart_from=None)
        t0 = time.monotonic()
        with pytest.raises(FaultInjected):
            sup.run([dying, patient])
        assert time.monotonic() - t0 < 4.0  # peers aborted, not run out

    def test_base_exception_is_fatal_despite_budget(self):
        def worker(abort):
            raise KeyboardInterrupt()

        sup = WorkerSupervisor(n_workers=1, max_restarts=5,
                               restart_from=lambda r: None)
        with pytest.raises(KeyboardInterrupt):
            sup.run([worker])
        assert sup.restart_counts() == {}

    def test_failing_restart_hook_aborts(self):
        def worker(abort):
            raise FaultInjected("boom")

        def bad_restart(rank):
            raise ConnectionError("center unreachable")

        sup = WorkerSupervisor(n_workers=1, max_restarts=3,
                               restart_from=bad_restart)
        with pytest.raises(ConnectionError):
            sup.run([worker])

    def test_extra_target_failure_aborts(self):
        def worker(abort):
            for _ in range(500):
                if abort.is_set():
                    return
                time.sleep(0.01)

        def orchestrator(abort):
            raise RuntimeError("validation exploded")

        sup = WorkerSupervisor(n_workers=1, max_restarts=2,
                               restart_from=lambda r: None)
        with pytest.raises(RuntimeError, match="validation exploded"):
            sup.run([worker], extra=[orchestrator])

    def test_restart_resumes_worker_closure_state(self):
        """The rules' restart pattern (code-review finding): worker
        closures carry a mutable ``progress`` dict OUTSIDE the target
        fn, so a supervised re-invocation resumes at the epoch the
        worker died in — NOT at the start epoch (which would retrain
        redundantly and, for ASGD rank 0, re-push the early-schedule
        LR to the server)."""
        seen = []
        progress = {"epoch": 0}

        def worker(abort):
            for epoch in range(progress["epoch"], 3):
                progress["epoch"] = epoch
                seen.append(epoch)
                if epoch == 1 and seen.count(1) == 1:
                    raise FaultInjected("die mid-epoch 1")

        sup = WorkerSupervisor(n_workers=1, max_restarts=1,
                               restart_from=lambda r: None)
        sup.run([worker])
        assert seen == [0, 1, 1, 2]  # epoch 0 NOT re-run

    def test_note_straggler_edges(self, tmp_path):
        sup = WorkerSupervisor(n_workers=2, max_restarts=1,
                               restart_from=lambda r: None)
        with monitor.session(run_dir=str(tmp_path)):
            sup.note_straggler(1, True)
            sup.note_straggler(1, True)   # no double count
            assert sup.stragglers() == [1]
            sup.note_straggler(1, False)  # recovery clears
            assert sup.stragglers() == []
            sup.note_straggler(1, True)
            assert monitor.registry().value(
                "resilience/straggler_handoffs_total", worker="1") == 2


# ---------------------------------------------------------------------------
# checkpoint integrity + recovery
# ---------------------------------------------------------------------------


def _payload(v: float):
    return {"state": {"w": np.full((4, 3), v, np.float32)}, "epoch": 0}


class TestCheckpointIntegrity:
    def test_manifest_written_and_verifies(self, tmp_path):
        from theanompi_tpu.utils.checkpoint import Checkpointer

        ckpt = Checkpointer(str(tmp_path), async_save=False)
        ckpt.save(0, _payload(1.0))
        ckpt.close()
        assert os.path.exists(recovery.manifest_path(str(tmp_path), 0))
        ok, detail = recovery.verify_checkpoint(str(tmp_path), 0)
        assert ok is True, detail

    def test_truncation_detected(self, tmp_path):
        from theanompi_tpu.utils.checkpoint import Checkpointer
        from theanompi_tpu.utils.checkpoint import _truncate_largest_file

        ckpt = Checkpointer(str(tmp_path), async_save=False)
        ckpt.save(0, _payload(1.0))
        ckpt.close()
        _truncate_largest_file(recovery.find_step_dir(str(tmp_path), 0))
        ok, detail = recovery.verify_checkpoint(str(tmp_path), 0)
        assert ok is False
        assert "mismatch" in detail or "missing" in detail

    def test_corrupt_latest_falls_back_to_previous(self, tmp_path):
        """The acceptance-criteria case: truncated-latest restore
        falls back to the previous kept epoch.  The corrupt step dir
        is QUARANTINED so the resumed run's save of that epoch really
        writes (orbax silently skips saves to an existing step) and
        no later manifest pass re-blesses the corrupt files
        (code-review finding)."""
        from theanompi_tpu.utils.checkpoint import Checkpointer
        from theanompi_tpu.utils.checkpoint import _truncate_largest_file

        ckpt = Checkpointer(str(tmp_path), async_save=False)
        ckpt.save(0, _payload(1.0))
        ckpt.save(1, _payload(2.0))
        ckpt.close()
        _truncate_largest_file(recovery.find_step_dir(str(tmp_path), 1))

        ckpt2 = Checkpointer(str(tmp_path), async_save=False)
        epoch, payload = ckpt2.restore_latest_verified(like=_payload(0.0))
        assert epoch == 0
        np.testing.assert_allclose(payload["state"]["w"], 1.0)
        # corrupt epoch 1 was quarantined: step dir gone, manifest
        # gone, corpse preserved for forensics
        assert recovery.find_step_dir(str(tmp_path), 1) is None
        assert not os.path.exists(recovery.manifest_path(str(tmp_path), 1))
        assert os.path.isdir(tmp_path / "quarantine" / "1")
        # ...so re-saving epoch 1 actually persists and verifies
        ckpt2.save(1, _payload(5.0))
        ckpt2.close()
        ok, detail = recovery.verify_checkpoint(str(tmp_path), 1)
        assert ok is True, detail
        ckpt3 = Checkpointer(str(tmp_path))
        epoch, payload = ckpt3.restore_latest_verified(like=_payload(0.0))
        ckpt3.close()
        assert epoch == 1
        np.testing.assert_allclose(payload["state"]["w"], 5.0)

    def test_intact_latest_restores_latest(self, tmp_path):
        from theanompi_tpu.utils.checkpoint import Checkpointer

        ckpt = Checkpointer(str(tmp_path), async_save=False)
        ckpt.save(0, _payload(1.0))
        ckpt.save(1, _payload(2.0))
        epoch, payload = ckpt.restore_latest_verified(like=_payload(0.0))
        ckpt.close()
        assert epoch == 1
        np.testing.assert_allclose(payload["state"]["w"], 2.0)

    def test_empty_dir_returns_none(self, tmp_path):
        from theanompi_tpu.utils.checkpoint import Checkpointer

        ckpt = Checkpointer(str(tmp_path))
        epoch, payload = ckpt.restore_latest_verified()
        ckpt.close()
        assert epoch is None and payload is None

    def test_legacy_checkpoint_without_manifest_still_restores(
            self, tmp_path):
        from theanompi_tpu.utils.checkpoint import Checkpointer

        ckpt = Checkpointer(str(tmp_path), async_save=False,
                            integrity=False)  # pre-resilience writer
        ckpt.save(0, _payload(3.0))
        ckpt.close()
        assert not os.path.exists(recovery.manifest_path(str(tmp_path), 0))
        ckpt2 = Checkpointer(str(tmp_path))
        epoch, payload = ckpt2.restore_latest_verified(like=_payload(0.0))
        ckpt2.close()
        assert epoch == 0
        np.testing.assert_allclose(payload["state"]["w"], 3.0)

    def test_manifests_pruned_with_max_to_keep(self, tmp_path):
        from theanompi_tpu.utils.checkpoint import Checkpointer

        ckpt = Checkpointer(str(tmp_path), max_to_keep=2,
                            async_save=False)
        for e in range(4):
            ckpt.save(e, _payload(float(e)))
        ckpt.close()
        manifests = sorted(p for p in os.listdir(tmp_path)
                           if p.startswith("manifest_"))
        assert manifests == ["manifest_2.json", "manifest_3.json"]

    def test_fault_plan_truncate_action(self, tmp_path):
        """The 'checkpoint write landed corrupt' fault: the plan
        truncates epoch 1 AFTER its manifest is written, so the next
        verified restore falls back to epoch 0."""
        from theanompi_tpu.utils.checkpoint import Checkpointer

        faults.install([{"site": "checkpoint", "epoch": 1,
                         "action": "truncate"}])
        ckpt = Checkpointer(str(tmp_path), async_save=False)
        ckpt.save(0, _payload(1.0))
        ckpt.save(1, _payload(2.0))
        ckpt.close()
        faults.clear()
        ckpt2 = Checkpointer(str(tmp_path))
        epoch, payload = ckpt2.restore_latest_verified(like=_payload(0.0))
        ckpt2.close()
        assert epoch == 0
        np.testing.assert_allclose(payload["state"]["w"], 1.0)


# ---------------------------------------------------------------------------
# service: reconnect through faults and a full server restart
# ---------------------------------------------------------------------------


def _start_service(port):
    from theanompi_tpu.parallel.service import serve

    ready, stop = threading.Event(), threading.Event()
    t = threading.Thread(target=serve,
                         args=("127.0.0.1", port, ready, stop),
                         daemon=True)
    t.start()
    assert ready.wait(10)
    return t, stop


@pytest.fixture()
def service_env(monkeypatch):
    monkeypatch.setenv("THEANOMPI_TPU_SERVICE_KEY", "resilience-test")
    # fast client retry so failure paths stay test-speed
    monkeypatch.setenv("THEANOMPI_TPU_SERVICE_RETRIES", "6")
    monkeypatch.setenv("THEANOMPI_TPU_SERVICE_RETRY_DEADLINE_S", "20")


class TestServiceResilience:
    def test_call_survives_injected_drop(self, service_env):
        from theanompi_tpu.parallel.service import RemoteEASGD

        port = _free_port()
        t, stop = _start_service(port)
        try:
            faults.install([{"site": "service_call",
                             "op": "easgd_exchange", "action": "drop"}])
            params = {"w": np.ones((3,), np.float32)}
            srv = RemoteEASGD(f"127.0.0.1:{port}", params, alpha=0.5,
                              session_id="drop-test")
            # the dropped RPC reconnects, rejoins, re-sends — the
            # caller never sees the transport failure
            out = srv.exchange({"w": np.full((3,), 3.0, np.float32)})
            np.testing.assert_allclose(out["w"], 2.0)  # 3 - 0.5*(3-1)
            srv.close()
        finally:
            stop.set()
            _shutdown_service(port)
            t.join(timeout=5)

    def test_dropped_init_of_a_new_session_displaces_the_old(
            self, service_env):
        """A session-creating init whose first send is lost is SENT
        AGAIN, not preceded by a rejoin: the service still holds the
        session this init displaces, and a rejoin under the new id was
        refused as `SessionDisplaced ... by <the old session>` (PR 25:
        how test_shards' sharded ASGD resume failed whenever another
        xdist worker's leak fence unlinked the shm segment its init
        rode on)."""
        from theanompi_tpu.parallel.service import RemoteASGD

        port = _free_port()
        t, stop = _start_service(port)
        try:
            addr = f"127.0.0.1:{port}"
            params = {"w": np.ones((3,), np.float32)}
            opt = dict(learning_rate=0.1, optimizer="sgd")
            old = RemoteASGD(addr, params, opt, session_id="old")
            faults.install([{"site": "service_call", "op": "asgd_init",
                             "action": "drop"}])
            new = RemoteASGD(addr, params, opt, session_id="new")
            np.testing.assert_allclose(new.get_center()["w"], 1.0)
            with pytest.raises(Exception, match="displaced"):
                old.get_center()
            old.close()
            new.close()
        finally:
            stop.set()
            _shutdown_service(port)
            t.join(timeout=5)

    def test_client_survives_server_restart(self, service_env, rpc_loop):
        """Acceptance-criteria case: a ServiceClient reconnects
        through a full parameter-service restart (new process-worth of
        state: the store is GONE) without losing session state — the
        rejoin rebuilds the center from the client's last good
        params."""
        from theanompi_tpu.parallel.service import RemoteEASGD

        port = _free_port()
        t1, stop1 = _start_service(port)
        params = {"w": np.zeros((3,), np.float32)}
        srv = RemoteEASGD(f"127.0.0.1:{port}", params, alpha=0.5,
                          session_id="restart-test")
        out1 = srv.exchange({"w": np.full((3,), 2.0, np.float32)})
        np.testing.assert_allclose(out1["w"], 1.0)  # 2 - 0.5*(2-0)

        # hard server restart on the same port: all stores lost
        stop1.set()
        _shutdown_service(port)
        t1.join(timeout=5)
        t2, stop2 = _start_service(port)
        try:
            # next exchange: transport error -> reconnect -> rejoin
            # rebuilds the center from the last exchange result (1.0)
            out2 = srv.exchange({"w": np.full((3,), 5.0, np.float32)})
            np.testing.assert_allclose(out2["w"], 3.0)  # 5 - 0.5*(5-1)
            srv.close()
        finally:
            stop2.set()
            _shutdown_service(port)
            t2.join(timeout=5)

    def test_joiner_rejoins_once_peer_rebuilds(self, service_env):
        """A join-only client (no rebuild payload) must keep RETRYING
        its rejoin across attempts until a payload-bearing peer has
        rebuilt the store — not die on the first op the restarted
        server rejects (code-review finding)."""
        from theanompi_tpu.parallel.service import RemoteEASGD

        port = _free_port()
        t1, stop1 = _start_service(port)
        params = {"w": np.zeros((2,), np.float32)}
        creator = RemoteEASGD(f"127.0.0.1:{port}", params, alpha=0.5,
                              session_id="joiner-test")
        creator.exchange({"w": np.full((2,), 2.0, np.float32)})
        joiner = RemoteEASGD(f"127.0.0.1:{port}", None, alpha=0.5,
                             session_id="joiner-test")
        # joiner has NO payload yet (never exchanged) when the service
        # restarts
        stop1.set()
        _shutdown_service(port)
        t1.join(timeout=5)
        t2, stop2 = _start_service(port)
        try:
            # the creator rebuilds the store shortly AFTER the joiner
            # starts retrying
            def rebuild_later():
                time.sleep(0.8)
                creator.exchange({"w": np.full((2,), 3.0, np.float32)})

            helper = threading.Thread(target=rebuild_later, daemon=True)
            helper.start()
            out = joiner.exchange({"w": np.full((2,), 5.0, np.float32)})
            helper.join(timeout=10)
            assert np.all(np.isfinite(out["w"]))
            creator.close()
            joiner.close()
        finally:
            stop2.set()
            _shutdown_service(port)
            t2.join(timeout=5)

    def test_lost_reply_retries_idempotent_tolerant_op(self, service_env, rpc_loop):
        """easgd_exchange tolerates at-least-once: a reply lost after
        the server applied it is re-sent (one extra elastic pull)."""
        from theanompi_tpu.parallel.service import RemoteEASGD

        port = _free_port()
        t, stop = _start_service(port)
        try:
            srv = RemoteEASGD(f"127.0.0.1:{port}",
                              {"w": np.zeros(2, np.float32)}, alpha=0.5,
                              session_id="alo-test")
            # stub BOTH read primitives: v1 pickle replies arrive via
            # conn.recv(), v2 framed replies via conn.recv_bytes()
            # (wire.recv_msg) — the negotiated protocol decides which
            # one the lost-reply simulation must intercept
            real_recv = srv._conn.recv
            real_recv_bytes = srv._conn.recv_bytes
            calls = {"n": 0}

            def _flaky(real):
                def flaky(*a, **kw):
                    if calls["n"] == 0:
                        calls["n"] += 1
                        raise ConnectionResetError("reply lost")
                    return real(*a, **kw)
                return flaky

            srv._conn.recv = _flaky(real_recv)
            srv._conn.recv_bytes = _flaky(real_recv_bytes)
            out = srv.exchange({"w": np.full(2, 2.0, np.float32)})
            assert np.all(np.isfinite(out["w"]))
            srv.close()
        finally:
            stop.set()
            _shutdown_service(port)
            t.join(timeout=5)

    def test_lost_reply_does_not_resend_gossip_ops(self, service_env, rpc_loop):
        """AT-MOST-ONCE for gossip push/drain (code-review finding):
        once the request is on the wire, a lost reply must RAISE, not
        re-send — a re-applied push double-delivers gossip weight and
        a re-sent drain silently discards the popped payload."""
        from theanompi_tpu.parallel.service import RemoteGossipHub

        port = _free_port()
        t, stop = _start_service(port)
        try:
            hub = RemoteGossipHub(f"127.0.0.1:{port}", 2,
                                  session_id="amo-test")

            def dead_recv(*a, **kw):
                raise ConnectionResetError("reply lost after send")

            # kill both read primitives — see the at-least-once test
            # above for why v1 and v2 read through different ones
            hub._conn.recv = dead_recv
            hub._conn.recv_bytes = dead_recv
            with pytest.raises(ConnectionError, match="not\\s+re-sending"):
                hub.push(1, {"w": np.ones(2, np.float32)}, 0.25)
            # no reconnect happened (the client raised instead of
            # retrying), so the patched connection is still in place
            with pytest.raises(ConnectionError, match="not\\s+re-sending"):
                hub.drain(0)
        finally:
            stop.set()
            _shutdown_service(port)
            t.join(timeout=5)

    def test_displaced_session_rejoin_refused(self, service_env):
        from theanompi_tpu.parallel.service import (
            RemoteEASGD,
            ServiceError,
        )

        port = _free_port()
        t, stop = _start_service(port)
        try:
            params = {"w": np.zeros((2,), np.float32)}
            old = RemoteEASGD(f"127.0.0.1:{port}", params, alpha=0.5,
                              session_id="old")
            old.exchange({"w": np.ones((2,), np.float32)})
            RemoteEASGD(f"127.0.0.1:{port}", params, alpha=0.5,
                        session_id="new")  # displaces 'old'
            with pytest.raises(ServiceError, match="displaced"):
                old._rejoin()
            old.close()
        finally:
            stop.set()
            _shutdown_service(port)
            t.join(timeout=5)


def _shutdown_service(port):
    from theanompi_tpu.parallel.service import ServiceClient

    try:
        ServiceClient(f"127.0.0.1:{port}").call("shutdown")
    except Exception:
        pass


# ---------------------------------------------------------------------------
# fault matrix e2e: the rules under injected faults
# ---------------------------------------------------------------------------


def tiny_cfg(tmp_path, **kw):
    from theanompi_tpu.models.base import ModelConfig

    base = dict(batch_size=8, n_epochs=1, learning_rate=0.01,
                snapshot_dir=str(tmp_path), print_freq=0)
    base.update(kw)
    return ModelConfig(**base)


def test_easgd_worker_killed_recovers(tmp_path):
    """Acceptance-criteria case: an EASGD worker killed mid-run is
    restarted from center params and the session completes."""
    from theanompi_tpu import EASGD

    faults.install([{"site": "worker_step", "rule": "easgd",
                     "worker": 1, "step": 3}])
    rule = EASGD()
    rule.init(devices=2, modelfile="tests._tiny_models",
              modelclass="TinyCifar", config=tiny_cfg(tmp_path),
              tau=4, alpha=0.5, checkpoint=False, max_restarts=1)
    res = rule.wait()
    assert res["restarts"] == {1: 1}
    assert res["lost_workers"] == []
    assert res["n_exchanges"] > 0
    assert np.isfinite(res["val"]["loss"])


def test_easgd_fault_without_supervision_still_fails_fast(tmp_path):
    """Control: max_restarts=0 (the default) keeps the reference's
    fail-fast semantics even with a fault plan installed."""
    from theanompi_tpu import EASGD

    faults.install([{"site": "worker_step", "rule": "easgd",
                     "worker": 1, "step": 3}])
    rule = EASGD()
    rule.init(devices=2, modelfile="tests._tiny_models",
              modelclass="TinyCifar", config=tiny_cfg(tmp_path),
              tau=4, alpha=0.5, checkpoint=False)
    with pytest.raises(FaultInjected):
        rule.wait()


@pytest.mark.slow
def test_easgd_killed_matches_no_fault_run(tmp_path):
    """Tolerance leg of the acceptance criteria: the recovered run's
    final loss matches a no-fault run within tolerance (the restarted
    worker re-seeds from center, so both trainings see ~the same
    trajectory length on a converged tiny problem)."""
    from theanompi_tpu import EASGD

    def run(fault: bool, sub: str):
        faults.clear()
        if fault:
            faults.install([{"site": "worker_step", "rule": "easgd",
                             "worker": 1, "step": 5}])
        rule = EASGD()
        rule.init(devices=2, modelfile="tests._tiny_models",
                  modelclass="TinyCifar",
                  config=tiny_cfg(tmp_path / sub, n_epochs=2),
                  tau=4, alpha=0.5, checkpoint=False,
                  max_restarts=1)
        return rule.wait()

    base = run(False, "nofault")
    faulted = run(True, "fault")
    assert faulted["restarts"] == {1: 1}
    assert abs(faulted["val"]["loss"] - base["val"]["loss"]) < 0.35, \
        (faulted["val"], base["val"])


@pytest.mark.slow
def test_gosgd_lost_worker_deactivates_and_completes(tmp_path):
    """GOSGD fallback path: no center to restart from — the killed
    worker is deactivated (peers stop pushing at it) and the session
    completes on the surviving quorum."""
    from theanompi_tpu import GOSGD

    faults.install([{"site": "worker_step", "rule": "gosgd",
                     "worker": 1, "step": 2}])
    rule = GOSGD()
    rule.init(devices=3, modelfile="tests._tiny_models",
              modelclass="TinyCifar", config=tiny_cfg(tmp_path),
              p_push=0.3, checkpoint=False, max_restarts=1)
    res = rule.wait()
    assert res["lost_workers"] == [1]
    assert np.isfinite(res["val"]["loss"])


def test_rule_resume_falls_back_past_corrupt_latest(tmp_path):
    """End-to-end recovery wiring: an EASGD run checkpoints per epoch;
    the LATEST checkpoint is then truncated; a resumed session must
    fall back to the previous epoch instead of dying."""
    from theanompi_tpu import EASGD
    from theanompi_tpu.models.base import ModelConfig

    cfg = tiny_cfg(tmp_path, n_epochs=2)
    rule = EASGD()
    rule.init(devices=2, modelfile="tests._tiny_models",
              modelclass="TinyCifar", config=cfg, tau=4,
              checkpoint=True)
    rule.wait()

    ckpt_dir = os.path.join(str(tmp_path), rule.model.name)
    epochs = sorted(int(n) for n in os.listdir(ckpt_dir) if n.isdigit())
    assert len(epochs) >= 2, epochs
    from theanompi_tpu.utils.checkpoint import _truncate_largest_file

    _truncate_largest_file(recovery.find_step_dir(ckpt_dir, epochs[-1]))

    cfg2 = tiny_cfg(tmp_path, n_epochs=3)
    rule2 = EASGD()
    rule2.init(devices=2, modelfile="tests._tiny_models",
               modelclass="TinyCifar", config=cfg2, tau=4,
               checkpoint=True, resume=True)
    res = rule2.wait()
    assert np.isfinite(res["val"]["loss"])
    # the corrupt epoch was quarantined at resume and RE-SAVED by the
    # resumed run — on disk again and verifying (code-review finding:
    # without quarantine orbax silently skips the re-save and the
    # corrupt files get re-blessed)
    ok, detail = recovery.verify_checkpoint(ckpt_dir, epochs[-1])
    assert ok is True, detail


def test_crash_marker_written_with_monitoring(tmp_path, monkeypatch):
    """rules/base.py postmortem hook: a crashed session leaves a
    machine-readable resilience crash marker in the monitor dir."""
    from theanompi_tpu import EASGD

    mondir = tmp_path / "mon"
    monkeypatch.setenv(monitor.ENV_VAR, str(mondir))
    faults.install([{"site": "worker_step", "rule": "easgd",
                     "worker": 0, "step": 1}])
    rule = EASGD()
    rule.init(devices=2, modelfile="tests._tiny_models",
              modelclass="TinyCifar",
              config=tiny_cfg(tmp_path / "snap"),
              tau=4, checkpoint=False)
    with pytest.raises(FaultInjected):
        rule.wait()
    markers = [p for p in os.listdir(mondir)
               if p.startswith("resilience_crash_")]
    assert markers, os.listdir(mondir)
    marker = json.load(open(mondir / markers[0]))
    assert marker["rule"] == "EASGD"
    assert "FaultInjected" in marker["error"]
