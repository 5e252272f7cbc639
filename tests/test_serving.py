"""serving/: export → verified read-only load → dynamic batching →
multi-replica server, on the CPU mesh (ISSUE 4 acceptance):

* N concurrent clients get BIT-identical answers to single-request
  serving (same bucket shape → same compiled program; pad rows are
  row-independent in eval mode);
* dynamic batches with occupancy > 1 actually form;
* queue-depth overload returns ``Overloaded`` instead of queueing
  unboundedly;
* a hot reload to a newer export completes with zero failed in-flight
  requests;
plus replica restart-from-export under an injected ``serve_step``
fault, the wire protocol, and the launcher's SERVE surface.
"""

from __future__ import annotations

import os
import socket
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu import monitor
from theanompi_tpu.models.base import ModelConfig
from theanompi_tpu.resilience import faults
from theanompi_tpu.serving import (
    BatchPolicy,
    DynamicBatcher,
    InferenceClient,
    InferenceServer,
    InferenceSession,
    Overloaded,
    default_buckets,
    export_model,
    latest_export_version,
    load_export,
    pick_bucket,
    serve,
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture(scope="module")
def tiny_export(tmp_path_factory):
    """One untrained TinyCifar export (v0) shared by the module: the
    (model, export_dir, request rows) triple every test builds on."""
    from tests._tiny_models import TinyCifar

    model = TinyCifar(config=ModelConfig(batch_size=8, n_epochs=1,
                                         print_freq=0), verbose=False)
    export_dir = str(tmp_path_factory.mktemp("serving") / "export")
    export_model(model, export_dir, version=0)
    x = np.asarray(model.data.x_val[:8])
    return model, export_dir, x


@pytest.fixture()
def wire_server(tiny_export):
    """A 2-replica server on a real socket; yields (client-factory,
    server).  Buckets pinned to (4,): every batch — single-request or
    coalesced — runs the SAME compiled program, the bit-identity
    precondition."""
    model, export_dir, _ = tiny_export
    key_before = os.environ.get("THEANOMPI_TPU_SERVICE_KEY")
    policy = BatchPolicy(max_batch=4, max_delay_ms=30.0, buckets=(4,),
                         max_queue=16)
    server = InferenceServer(export_dir, replicas=2, policy=policy,
                             reload_poll_s=0, model=model).start()
    port = _free_port()
    ready, stop = threading.Event(), threading.Event()
    t = threading.Thread(target=serve,
                         args=(server, "127.0.0.1", port, ready, stop),
                         daemon=True)
    t.start()
    assert ready.wait(30)
    addr = f"127.0.0.1:{port}"
    clients: list[InferenceClient] = []

    def make_client() -> InferenceClient:
        c = InferenceClient(addr)
        clients.append(c)
        return c

    yield make_client, server
    try:
        InferenceClient(addr).shutdown()
    except Exception:
        stop.set()
    for c in clients:
        c.close()
    t.join(timeout=5)
    server.stop()
    faults.clear()
    if key_before is None:
        os.environ.pop("THEANOMPI_TPU_SERVICE_KEY", None)
    else:
        os.environ["THEANOMPI_TPU_SERVICE_KEY"] = key_before


# ---------------------------------------------------------------------------
# export.py
# ---------------------------------------------------------------------------


class TestExport:
    def test_versioned_verified_export_round_trips(self, tiny_export):
        model, export_dir, _ = tiny_export
        assert latest_export_version(export_dir) == 0
        assert os.path.exists(os.path.join(export_dir,
                                           "manifest_0.json"))
        loaded = load_export(export_dir)
        assert loaded.version == 0
        assert loaded.meta["modelclass"] == "TinyCifar"
        for a, b in zip(jax.tree.leaves(loaded.params),
                        jax.tree.leaves(jax.device_get(
                            model.state.params))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_reexporting_a_version_refuses(self, tiny_export, tmp_path):
        model, _, _ = tiny_export
        d = str(tmp_path / "exp")
        export_model(model, d, version=3)
        with pytest.raises(ValueError, match="immutable"):
            export_model(model, d, version=3)

    def test_serving_load_leaves_export_dir_byte_identical(
            self, tiny_export):
        """The read-only reader contract end to end: a full verified
        serving load (manifest digests + Orbax restore) mutates
        NOTHING — sizes, hashes, mtimes-of-content, file set all
        unchanged (the satellite's pin lives in test_checkpoint.py at
        the Checkpointer layer; this is the serving-path version)."""
        import hashlib

        _, export_dir, _ = tiny_export

        def digest_tree(root):
            out = {}
            for r, dirs, files in os.walk(root):
                for name in files:
                    full = os.path.join(r, name)
                    with open(full, "rb") as f:
                        out[os.path.relpath(full, root)] = (
                            hashlib.sha256(f.read()).hexdigest())
            return out

        before = digest_tree(export_dir)
        InferenceSession.from_export(export_dir)
        assert digest_tree(export_dir) == before

    def test_half_published_version_falls_back_to_meta(
            self, tiny_export, tmp_path):
        """Exporter killed between the checkpoint publish and the meta
        sidecar write: that version must cost a FALLBACK (and not be
        offered to the reload watcher), never a server that crashes on
        meta={} at every (re)start."""
        model, _, _ = tiny_export
        d = str(tmp_path / "exp")
        export_model(model, d, version=0)
        export_model(model, d, version=1)
        os.unlink(os.path.join(d, "export_meta_1.json"))  # the kill
        # publish marker is the meta (written last): v1 isn't offered
        assert latest_export_version(d) == 0
        loaded = load_export(d)
        assert loaded.version == 0
        assert loaded.meta["modelclass"] == "TinyCifar"

    def test_swap_is_monotonic(self, tiny_export):
        """A replica restart that loaded the export while a concurrent
        hot reload published a newer version must not roll the session
        back; same-version swaps (the restart itself) are allowed."""
        model, export_dir, x = tiny_export
        loaded = load_export(export_dir)
        s = InferenceSession(model, params=loaded.params,
                             model_state=loaded.model_state,
                             version=5, donate=False)
        assert not s.swap(3, loaded.params, loaded.model_state)
        assert s.version == 5
        assert s.swap(5, loaded.params, loaded.model_state)
        assert s.swap(6, loaded.params, loaded.model_state)
        assert s.version == 6

    def test_session_matches_model_eval_path(self, tiny_export):
        """The frozen inference fn IS the model's eval path: same
        module, eval transform, train=False running-stat BN."""
        model, export_dir, x = tiny_export
        sess = InferenceSession(model)
        got = sess.infer(x)
        transform = getattr(model.data, "device_transform", None)
        xe = (transform(jnp.asarray(x), None, train=False)
              if transform is not None else jnp.asarray(x))
        # one compiled program, as the session's is: run primitive by
        # primitive the same path rounds 3 ulp apart (JAX 0.9.0, CPU)
        want = jax.jit(lambda v, xe: model.module.apply(
            v, xe, train=False))(
            {"params": model.state.params,
             **jax.device_get(model.state.model_state)}, xe)
        np.testing.assert_allclose(got, np.asarray(want, np.float32),
                                   rtol=1e-6, atol=1e-6)

    def test_infer_input_is_donated(self, tiny_export):
        """The export contract says donation ON: the request batch
        buffer is handed to XLA for reuse (aliased when shapes allow,
        else at least marked ``jax.buffer_donor``)."""
        model, _, x = tiny_export
        sess = InferenceSession(model)
        _, params, ms = sess._live
        text = sess._jit.lower(params, ms, jnp.asarray(x)).as_text()
        assert (text.count("tf.aliasing_output")
                + text.count("jax.buffer_donor")) >= 1

    def test_swap_changes_output_without_recompile(self, tiny_export):
        model, _, x = tiny_export
        sess = InferenceSession(model)
        y0 = sess.infer(x)
        zeroed = jax.tree.map(np.zeros_like,
                              jax.device_get(model.state.params))
        sess.swap(1, zeroed, jax.device_get(model.state.model_state))
        y1 = sess.infer(x)
        assert sess.version == 1
        assert not np.allclose(y0, y1)
        # zero params → identical logits per class for every row
        np.testing.assert_allclose(y1, y1[:1].repeat(len(x), 0),
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# batcher.py (no wire, no model — a row-wise fake)
# ---------------------------------------------------------------------------


def _row_fn(delay_s: float = 0.0):
    """Row-independent fake inference recording each padded shape."""
    shapes: list[tuple] = []

    def run(x):
        shapes.append(x.shape)
        if delay_s:
            time.sleep(delay_s)
        return x * 2.0
    run.shapes = shapes
    return run


class TestBatcher:
    def test_default_buckets_and_pick(self):
        assert default_buckets(8) == (1, 2, 4, 8)
        assert default_buckets(6) == (1, 2, 4, 6)
        assert pick_bucket(3, (1, 2, 4, 8)) == 4
        with pytest.raises(ValueError, match="exceed"):
            pick_bucket(9, (1, 2, 4, 8))

    def test_bucket_must_cover_max_batch(self):
        with pytest.raises(ValueError, match="max_batch"):
            BatchPolicy(max_batch=8, buckets=(1, 4)).resolved_buckets()

    def test_concurrent_requests_coalesce_and_split(self):
        run = _row_fn(delay_s=0.01)
        b = DynamicBatcher(run, BatchPolicy(max_batch=8,
                                            max_delay_ms=50.0)).start()
        try:
            xs = [np.full((1, 3), i, np.float32) for i in range(6)]
            outs = [None] * 6
            ths = [threading.Thread(
                target=lambda i=i: outs.__setitem__(i, b.submit(xs[i])))
                for i in range(6)]
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            for i in range(6):
                np.testing.assert_array_equal(outs[i], xs[i] * 2.0)
            assert b.max_occupancy > 1
            # every dispatched shape was a bucket shape
            assert {s[0] for s in run.shapes} <= set(b.buckets)
        finally:
            b.stop()

    def test_overload_rejects_fast_and_bounded(self):
        run = _row_fn(delay_s=0.2)  # slow replica
        b = DynamicBatcher(run, BatchPolicy(
            max_batch=1, max_delay_ms=0.0, buckets=(1,),
            max_queue=2)).start()
        try:
            results = []
            lock = threading.Lock()

            def go(i):
                t0 = time.monotonic()
                try:
                    b.submit(np.ones((1, 2), np.float32))
                    out = "ok"
                except Overloaded:
                    out = "overloaded"
                with lock:
                    results.append((out, time.monotonic() - t0))

            ths = [threading.Thread(target=go, args=(i,))
                   for i in range(10)]
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            kinds = [r[0] for r in results]
            assert "overloaded" in kinds and "ok" in kinds
            # rejections are O(1), not queue-the-world: far faster
            # than serving the whole flood serially (10 x 0.2s)
            rejected = [dt for k, dt in results if k == "overloaded"]
            assert max(rejected) < 0.5
            assert b.alive
        finally:
            b.stop()

    def test_oversize_request_rejected(self):
        b = DynamicBatcher(_row_fn(), BatchPolicy(max_batch=4))
        with pytest.raises(ValueError, match="split"):
            b.submit(np.ones((5, 2), np.float32))

    def test_timeout_reclaims_admission_slot(self):
        """A submit() timeout must pull the abandoned request back out
        of the queue: zombie entries must not hold max_queue slots
        (starving live requests) nor burn device batches nobody
        awaits."""
        gate = threading.Event()

        def wedged(x):
            gate.wait(10)  # first batch wedges the collector
            return x * 2.0
        b = DynamicBatcher(wedged, BatchPolicy(
            max_batch=1, max_delay_ms=0.0, buckets=(1,), max_queue=1,
            submit_timeout_s=0.3)).start()
        try:
            x = np.ones((1, 2), np.float32)
            t1 = threading.Thread(
                target=lambda: pytest.raises(TimeoutError,
                                             b.submit, x))
            t1.start()
            time.sleep(0.05)  # t1's request is now IN-FLIGHT (wedged)
            # this one stays QUEUED behind it and times out
            with pytest.raises(TimeoutError, match="timed out"):
                b.submit(x)
            # the slot came back: a fresh request is ADMITTED (queued),
            # not rejected with Overloaded
            assert b.queue_depth() == 0
            t2 = threading.Thread(target=lambda: b.submit(x))
            t2.start()
            time.sleep(0.05)
            assert b.queue_depth() == 1  # admitted, no Overloaded
            gate.set()
            t1.join(timeout=5)
            t2.join(timeout=5)
        finally:
            gate.set()
            b.stop()

    def test_batch_error_fails_batch_and_hook_decides(self):
        calls = {"n": 0}

        def boom(x):
            raise RuntimeError("bad batch")

        def on_err(e):
            calls["n"] += 1
            return False  # lose the replica

        b = DynamicBatcher(boom, BatchPolicy(max_batch=2,
                                             max_delay_ms=0.0),
                           on_batch_error=on_err).start()
        try:
            with pytest.raises(RuntimeError, match="bad batch"):
                b.submit(np.ones((1, 2), np.float32))
            assert calls["n"] == 1
            assert not b.alive
            with pytest.raises(Overloaded):
                b.submit(np.ones((1, 2), np.float32))
        finally:
            b.stop()


# ---------------------------------------------------------------------------
# server.py — the CPU integration acceptance tests
# ---------------------------------------------------------------------------


class TestServerIntegration:
    def test_concurrent_bit_identical_with_occupancy(self, wire_server,
                                                     tiny_export):
        """Acceptance #1 + #2: concurrent answers are BIT-identical to
        single-request serving, and multi-request batches form."""
        _, _, x = tiny_export
        make_client, server = wire_server
        client = make_client()
        # single-request serving, one at a time (occupancy 1)
        singles = [client.infer(x[i:i + 1]) for i in range(8)]
        # the same 8 rows from 8 concurrent clients
        outs = [None] * 8
        clients = [make_client() for _ in range(8)]
        ths = [threading.Thread(
            target=lambda i=i: outs.__setitem__(
                i, clients[i].infer(x[i:i + 1])))
            for i in range(8)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        for i in range(8):
            np.testing.assert_array_equal(outs[i], singles[i])
        st = client.stats()
        assert st["max_occupancy"] > 1
        assert st["version"] == 0
        assert st["live_replicas"] == 2

    def test_overload_returns_typed_rejection(self, tiny_export):
        """Acceptance #3: with every live replica's queue full the
        server answers ``Overloaded`` — fast — instead of queueing
        unboundedly; accepted requests still complete."""
        model, export_dir, x = tiny_export
        key_before = os.environ.get("THEANOMPI_TPU_SERVICE_KEY")
        policy = BatchPolicy(max_batch=1, max_delay_ms=0.0,
                             buckets=(1,), max_queue=1)
        server = InferenceServer(export_dir, replicas=1, policy=policy,
                                 reload_poll_s=0, model=model).start()
        port = _free_port()
        ready, stop = threading.Event(), threading.Event()
        t = threading.Thread(
            target=serve, args=(server, "127.0.0.1", port, ready, stop),
            daemon=True)
        t.start()
        assert ready.wait(30)
        faults.install([{"site": "serve_step", "action": "delay",
                         "delay_s": 0.15, "times": -1}])
        try:
            addr = f"127.0.0.1:{port}"
            results = []
            lock = threading.Lock()
            # pre-connect so the flood's ARRIVALS are tight — the HMAC
            # handshake must not spread them past the service rate
            pool = [InferenceClient(addr) for _ in range(10)]

            def go(c):
                try:
                    c.infer(x[:1])
                    r = "ok"
                except Overloaded:
                    r = "overloaded"
                finally:
                    c.close()
                with lock:
                    results.append(r)

            ths = [threading.Thread(target=go, args=(c,))
                   for c in pool]
            for th in ths:
                th.start()
            for th in ths:
                th.join()
            # the flood was SPLIT: the bounded queue accepted some and
            # typed-rejected the rest (nothing hung, nothing errored —
            # every client got an answer).  The O(1)-rejection LATENCY
            # bound is pinned socket-free in
            # TestBatcher::test_overload_rejects_fast_and_bounded;
            # wall-clock asserts on the 1-core CI box are noise.
            assert len(results) == 10
            assert "overloaded" in results and "ok" in results
            # the server is still healthy after the flood
            c = InferenceClient(addr)
            np.testing.assert_array_equal(
                c.infer(x[:1]).shape, (1, 10))
            c.close()
        finally:
            faults.clear()
            try:
                InferenceClient(f"127.0.0.1:{port}").shutdown()
            except Exception:
                stop.set()
            t.join(timeout=5)
            server.stop()
            if key_before is None:
                os.environ.pop("THEANOMPI_TPU_SERVICE_KEY", None)
            else:
                os.environ["THEANOMPI_TPU_SERVICE_KEY"] = key_before

    def test_hot_reload_zero_failed_inflight(self, tiny_export,
                                             tmp_path):
        """Acceptance #4: publish v1 while a request storm is in
        flight, force the reload, and finish the storm — zero failed
        requests, the server ends up serving v1's numbers.  Runs on a
        COPY of the module export so the shared fixture's version
        history stays pristine under randomized test order."""
        import shutil

        from tests._tiny_models import TinyCifar

        model, export_dir0, x = tiny_export
        export_dir = str(tmp_path / "export")
        shutil.copytree(export_dir0, export_dir)
        key_before = os.environ.get("THEANOMPI_TPU_SERVICE_KEY")
        policy = BatchPolicy(max_batch=4, max_delay_ms=30.0,
                             buckets=(4,), max_queue=16)
        server = InferenceServer(export_dir, replicas=2, policy=policy,
                                 reload_poll_s=0, model=model).start()
        port = _free_port()
        ready, stop = threading.Event(), threading.Event()
        srv_t = threading.Thread(
            target=serve, args=(server, "127.0.0.1", port, ready, stop),
            daemon=True)
        srv_t.start()
        assert ready.wait(30)
        addr = f"127.0.0.1:{port}"
        made: list[InferenceClient] = []

        def make_client() -> InferenceClient:
            c = InferenceClient(addr)
            made.append(c)
            return c

        client = make_client()
        before = client.infer(x[:1])

        errors: list[BaseException] = []
        n_done = [0]
        stop_storm = threading.Event()
        lock = threading.Lock()

        def storm():
            c = make_client()
            while not stop_storm.is_set():
                try:
                    c.infer(x[:2])
                except BaseException as e:  # noqa: BLE001
                    with lock:
                        errors.append(e)
                    return
                with lock:
                    n_done[0] += 1

        try:
            ths = [threading.Thread(target=storm) for _ in range(4)]
            for t in ths:
                t.start()
            time.sleep(0.1)  # storm established
            # v1: same architecture, different params (fresh seed)
            model2 = TinyCifar(config=ModelConfig(
                batch_size=8, n_epochs=1, print_freq=0, seed=77),
                verbose=False)
            export_model(model2, export_dir, version=1)
            assert client.reload() == 1
            time.sleep(0.2)  # storm keeps running THROUGH the swap
            stop_storm.set()
            for t in ths:
                t.join(timeout=30)
            assert errors == []
            assert n_done[0] > 8
            st = client.stats()
            assert st["version"] == 1
            assert all(r["version"] == 1 for r in st["replicas"])
            after = client.infer(x[:1])
            assert not np.allclose(before, after)
            want = InferenceSession(model2).infer(x[:1])
            np.testing.assert_allclose(after, want, rtol=1e-5,
                                       atol=1e-5)
        finally:
            stop_storm.set()
            try:
                client.shutdown()
            except Exception:
                stop.set()
            for c in made:
                c.close()
            srv_t.join(timeout=5)
            server.stop()
            if key_before is None:
                os.environ.pop("THEANOMPI_TPU_SERVICE_KEY", None)
            else:
                os.environ["THEANOMPI_TPU_SERVICE_KEY"] = key_before

    def test_replica_restarts_from_export_on_fault(self, tiny_export, rpc_loop):
        """resilience wiring: an injected ``serve_step`` crash fails
        that batch (surfaced to its client), the replica reloads the
        verified export, and serving continues."""
        from theanompi_tpu.parallel.service import ServiceError

        model, export_dir, x = tiny_export
        key_before = os.environ.get("THEANOMPI_TPU_SERVICE_KEY")
        policy = BatchPolicy(max_batch=4, max_delay_ms=0.0,
                             buckets=(4,), max_queue=8)
        server = InferenceServer(export_dir, replicas=1, policy=policy,
                                 reload_poll_s=0, max_restarts=1,
                                 model=model).start()
        port = _free_port()
        ready, stop = threading.Event(), threading.Event()
        t = threading.Thread(
            target=serve, args=(server, "127.0.0.1", port, ready, stop),
            daemon=True)
        t.start()
        assert ready.wait(30)
        client = InferenceClient(f"127.0.0.1:{port}")
        try:
            ok = client.infer(x[:1])
            faults.install([{"site": "serve_step", "action": "raise"}])
            with pytest.raises(ServiceError, match="FaultInjected"):
                client.infer(x[:1])
            faults.clear()
            # restarted from export: serving continues, same numbers
            np.testing.assert_array_equal(client.infer(x[:1]), ok)
            st = client.stats()
            assert st["replicas"][0]["restarts"] == 1
            assert st["live_replicas"] == 1
        finally:
            faults.clear()
            try:
                client.shutdown()
            except Exception:
                stop.set()
            client.close()
            t.join(timeout=5)
            server.stop()
            if key_before is None:
                os.environ.pop("THEANOMPI_TPU_SERVICE_KEY", None)
            else:
                os.environ["THEANOMPI_TPU_SERVICE_KEY"] = key_before

    def test_fault_plan_does_not_crash_warmup(self, tiny_export):
        """A ``serve_step`` raise plan must take down SERVED batches
        (supervised restart), not server construction: warmup bypasses
        the fault site (batcher.warmup(fn=session.infer))."""
        model, export_dir, x = tiny_export
        policy = BatchPolicy(max_batch=4, max_delay_ms=0.0,
                             buckets=(4,), max_queue=8)
        faults.install([{"site": "serve_step", "action": "raise"}])
        try:
            server = InferenceServer(
                export_dir, replicas=1, policy=policy, reload_poll_s=0,
                max_restarts=1, model=model, warmup=True).start()
        finally:
            faults.clear()
        try:
            assert server.submit(x[:1]).shape == (1, 10)
            # warmup fired no fault, so no restart was consumed
            assert server.stats()["replicas"][0]["restarts"] == 0
        finally:
            server.stop()

    def test_corrupt_newer_export_skipped_until_superseded(
            self, tiny_export, tmp_path, monkeypatch):
        """A published-but-corrupt newest version must cost ONE
        verified-load attempt, not one per poll: the watcher remembers
        the bad version and waits for a strictly newer manifest."""
        import theanompi_tpu.serving.server as srv
        from theanompi_tpu.resilience.recovery import find_step_dir
        from theanompi_tpu.utils.checkpoint import _truncate_largest_file

        model, _, x = tiny_export
        d = str(tmp_path / "exp")
        export_model(model, d, version=0)
        server = InferenceServer(d, replicas=1, reload_poll_s=0,
                                 model=model, warmup=False)
        try:
            export_model(model, d, version=1)
            _truncate_largest_file(find_step_dir(d, 1))
            calls = {"n": 0}
            orig = srv.load_export

            def counting(path):
                calls["n"] += 1
                return orig(path)

            monkeypatch.setattr(srv, "load_export", counting)
            assert server.check_reload() == 0  # v1 fell back -> skip
            assert calls["n"] == 1
            for _ in range(3):  # further polls never re-load v1
                assert server.check_reload() == 0
            assert calls["n"] == 1
            # a strictly newer GOOD version resets the skip
            export_model(model, d, version=2)
            assert server.check_reload() == 2
            assert server.stats()["replicas"][0]["version"] == 2
        finally:
            server.stop()

    def test_serving_metrics_reach_the_monitor(self, tiny_export,
                                               tmp_path):
        """The monitor wiring end to end (in-process, no wire): the
        request-latency histogram, batch formation series, and
        per-replica heartbeat land in the registry snapshot."""
        import json

        model, export_dir, x = tiny_export
        monitor.reset_for_tests()
        run_dir = str(tmp_path / "mon")
        with monitor.session(run_dir=run_dir):
            policy = BatchPolicy(max_batch=4, max_delay_ms=20.0,
                                 buckets=(4,), max_queue=8)
            server = InferenceServer(export_dir, replicas=1,
                                     policy=policy, reload_poll_s=0,
                                     model=model).start()
            try:
                ths = [threading.Thread(
                    target=lambda i=i: server.submit(x[i:i + 1]))
                    for i in range(4)]
                for t in ths:
                    t.start()
                for t in ths:
                    t.join()
            finally:
                server.stop()
        recs = [json.loads(l) for l in
                open(os.path.join(run_dir, "metrics_rank0.jsonl"))]
        names = {r["name"] for r in recs}
        for want in ("serving/request_ms", "serving/batch_occupancy",
                     "serving/batches_total",
                     "serving/replica_heartbeat",
                     "serving/model_version"):
            assert want in names, f"missing {want}: {sorted(names)}"
        lat = next(r for r in recs if r["name"] == "serving/request_ms")
        assert lat["count"] == 4 and "p99" in lat
        monitor.reset_for_tests()
