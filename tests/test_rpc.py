"""The selector-driven RPC substrate (parallel/rpc.py, ISSUE 11).

Every plane's own suite already exercises the substrate end to end
(the selector loop is the default); this file pins the substrate's NEW
contracts — handshake deadline, abrupt-disconnect accounting,
backpressure, stream multiplexing, per-stream FIFO — on BOTH loops
where the contract is loop-agnostic.
"""

from __future__ import annotations

import os
import pickle
import socket
import struct
import threading
import time

import numpy as np
import pytest

from theanompi_tpu import monitor
from theanompi_tpu.parallel import rpc, wire
from theanompi_tpu.parallel.service import (
    ParamService,
    ServiceClient,
    ServiceError,
    serve,
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class EchoService:
    """Minimal service: ops that echo, sleep, fail, or record
    concurrency — enough to probe the loop without jax stores."""

    RPC_CONTROL_OPS = frozenset({"ctl"})

    def __init__(self):
        self._lock = threading.Lock()
        self.active = 0
        self.max_active = 0
        self.per_stream_active: dict = {}

    def handle(self, op, *args):
        if op == "echo":
            return args[0] if args else None
        if op == "ctl":
            return "ctl-ok"
        if op == "boom":
            raise ValueError("boom goes the service")
        if op == "sleep":
            time.sleep(float(args[0]))
            return "slept"
        if op == "big":
            return np.zeros(int(args[0]), np.uint8)
        if op == "track":
            key = args[0]
            with self._lock:
                self.active += 1
                self.max_active = max(self.max_active, self.active)
                n = self.per_stream_active.get(key, 0) + 1
                self.per_stream_active[key] = n
                assert n == 1, f"stream {key} ran concurrently"
            time.sleep(0.02)
            with self._lock:
                self.active -= 1
                self.per_stream_active[key] -= 1
            return key
        if op == "ping":
            return "pong"
        raise ValueError(f"unknown op {op!r}")


@pytest.fixture()
def echo_server(rpc_loop, monkeypatch):  # rpc_loop: tests/conftest.py
    monkeypatch.setenv("THEANOMPI_TPU_SERVICE_KEY", "rpc-test-key")
    svc = EchoService()
    port = _free_port()
    ready, stop = threading.Event(), threading.Event()
    t = threading.Thread(
        target=serve, args=("127.0.0.1", port, ready, stop),
        kwargs=dict(service=svc), daemon=True)
    t.start()
    assert ready.wait(10)
    yield f"127.0.0.1:{port}", svc, rpc_loop
    stop.set()
    try:
        ServiceClient(f"127.0.0.1:{port}").call("shutdown")
    except Exception:
        pass
    t.join(timeout=10)
    assert not t.is_alive(), f"{rpc_loop} serve loop did not exit"


class TestBothLoops:
    def test_round_trip_and_typed_errors(self, echo_server):
        addr, _, _ = echo_server
        c = ServiceClient(addr)
        try:
            assert c.call("echo", {"x": np.arange(5)})["x"].tolist() \
                == list(range(5))
            with pytest.raises(ServiceError, match="ValueError"):
                c.call("boom")
            # the connection survives a server-side error
            assert c.call("ping") == "pong"
        finally:
            c.close()

    def test_v1_round_trip(self, echo_server, monkeypatch):
        monkeypatch.setenv("THEANOMPI_TPU_WIRE_PROTOCOL", "v1")
        addr, _, _ = echo_server
        c = ServiceClient(addr)
        try:
            assert c.wire_protocol == "v1"
            out = c.call("echo", np.arange(7, dtype=np.float32))
            assert out.tobytes() == np.arange(
                7, dtype=np.float32).tobytes()
        finally:
            c.close()

    def test_handshake_deadline_reaps_silent_connect(
            self, echo_server, monkeypatch):
        """ISSUE 11 satellite: a client that connects and never sends
        the HMAC challenge reply is reaped after the deadline — it
        must neither wedge the accept path nor leak a handler until
        shutdown, on either loop."""
        addr, _, _ = echo_server
        host, _, port = addr.rpartition(":")
        monkeypatch.setenv("THEANOMPI_TPU_RPC_HANDSHAKE_TIMEOUT_S",
                           "0.5")
        silent = socket.create_connection((host, int(port)))
        try:
            # while the silent connect is parked, real clients work
            c = ServiceClient(addr)
            assert c.call("ping") == "pong"
            c.close()
            # ...and the server closes the silent peer at the deadline
            silent.settimeout(10)
            data = silent.recv(4096)  # the challenge arrives first
            assert data, "server never sent its challenge"
            assert silent.recv(4096) == b"", \
                "silent connection was not reaped at the deadline"
        finally:
            silent.close()

    def test_client_handshake_has_the_deadline_too(self, monkeypatch):
        """A connect the kernel completed but nobody greets (a listener
        closing during the three-way handshake leaves exactly that: the
        client ESTABLISHED, no peer, no RST) fails at the deadline as a
        ``ConnectionError``, so a retry loop sees it like any refused
        connect.  (PR 25: the stdlib client waited for ever, and a
        test's `shutdown` poke after `stop.set()` hung a tier-1 worker
        about once in 40 000 connects.)"""
        monkeypatch.setenv("THEANOMPI_TPU_RPC_HANDSHAKE_TIMEOUT_S",
                           "0.5")
        with socket.socket() as mute:  # listens, never accepts
            mute.bind(("127.0.0.1", 0))
            mute.listen(1)
            t0 = time.monotonic()
            with pytest.raises(rpc.HandshakeTimeout):
                rpc.connect(mute.getsockname(), b"k")
            assert time.monotonic() - t0 < 5

    def test_abrupt_disconnect_sweeps_clients_gauge(
            self, echo_server, tmp_path):
        """ISSUE 11 satellite: an RST mid-frame must run the same
        close sweep as a polite close — the ``service/clients`` gauge
        returns to its baseline on both loops."""
        addr, _, _ = echo_server
        host, _, port = addr.rpartition(":")

        def gauge():
            for e in monitor.registry().snapshot():
                if e["name"] == "service/clients":
                    return e["value"]
            return 0.0

        with monitor.session(str(tmp_path / "mon"),
                             stall_after=float("inf")):
            base = gauge()
            c = ServiceClient(addr)
            assert c.call("ping") == "pong"
            deadline = time.monotonic() + 5
            while gauge() < base + 1 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert gauge() == base + 1
            # abrupt kill: RST instead of FIN, mid-frame — send a
            # partial length prefix, then hard-reset the socket
            raw = c._conn if not isinstance(c._conn, rpc.MuxStream) \
                else None
            if raw is not None:
                s = socket.socket(fileno=os.dup(raw.fileno()))
                s.send(struct.pack("!i", 1 << 20))  # header, no body
                s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                             struct.pack("ii", 1, 0))
                s.close()
            raw.close() if raw is not None else c.close()
            deadline = time.monotonic() + 5
            while gauge() > base and time.monotonic() < deadline:
                time.sleep(0.01)
            assert gauge() == base, \
                "clients gauge leaked after an abrupt disconnect"

    def test_large_zero_copy_frames(self, echo_server):
        addr, _, _ = echo_server
        c = ServiceClient(addr)
        try:
            out = c.call("big", 3_000_000)
            assert out.shape == (3_000_000,) and out.dtype == np.uint8
        finally:
            c.close()

    def test_concurrent_clients_all_answered(self, echo_server):
        addr, svc, _ = echo_server
        clients = [ServiceClient(addr) for _ in range(8)]
        outs = [None] * 8

        def run(i):
            outs[i] = clients[i].call("track", f"conn{i}")

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        for c in clients:
            c.close()
        assert outs == [f"conn{i}" for i in range(8)]
        # handlers genuinely overlapped (the track op sleeps)
        assert svc.max_active > 1


class TestSelectorOnly:
    """Contracts only the event plane has: mux, control-pool routing,
    write-queue backpressure."""

    @pytest.fixture()
    def server(self, monkeypatch):
        monkeypatch.setenv("THEANOMPI_TPU_RPC_LOOP", "selector")
        monkeypatch.setenv("THEANOMPI_TPU_SERVICE_KEY", "rpc-test-key")
        svc = EchoService()
        port = _free_port()
        ready, stop = threading.Event(), threading.Event()
        t = threading.Thread(
            target=serve, args=("127.0.0.1", port, ready, stop),
            kwargs=dict(service=svc), daemon=True)
        t.start()
        assert ready.wait(10)
        yield f"127.0.0.1:{port}", svc
        stop.set()
        try:
            ServiceClient(f"127.0.0.1:{port}").call("shutdown")
        except Exception:
            pass
        t.join(timeout=10)
        assert not t.is_alive()

    def test_peer_handshaken_during_shutdown_sees_eof(self):
        """A peer whose handshake ends after the loop's last turn is
        CLOSED by the shutdown, as is one that ends later still: left
        open and unregistered, it waits for ever for the reply to its
        hello (PR 25: a test's own `shutdown` client, connecting just
        after `stop.set()`, hung a tier-1 worker this way)."""
        srv = rpc._SelectorServer(EchoService(), "127.0.0.1", 0,
                                  threading.Event(), b"k",
                                  rpc.RpcHooks(), 2)
        ours, theirs = zip(socket.socketpair(), socket.socketpair())
        srv.register_ready(ours[0])  # handshaken, never registered
        srv._shutdown()
        srv.register_ready(ours[1])  # outlived the pools' join
        for peer in theirs:
            with peer:
                peer.settimeout(5)
                assert peer.recv(1) == b""

    def test_mux_streams_share_one_socket(self, server):
        addr, svc = server
        with rpc.MuxConnection(addr) as mc:
            assert mc.mux, "selector server must grant mux"
            clients = [ServiceClient(addr, transport=mc)
                       for i in range(6)]
            outs = [None] * 6

            def run(i):
                outs[i] = clients[i].call("track", f"stream{i}")

            threads = [threading.Thread(target=run, args=(i,))
                       for i in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(30)
            assert outs == [f"stream{i}" for i in range(6)]
            # streams of ONE socket ran concurrently server-side
            assert svc.max_active > 1
            for c in clients:
                c.close()

    def test_mux_interleaved_large_frames_byte_exact(self, server):
        addr, _ = server
        with rpc.MuxConnection(addr) as mc:
            clients = [ServiceClient(addr, transport=mc)
                       for _ in range(4)]
            payloads = [np.random.default_rng(i).integers(
                0, 255, 1 << 20).astype(np.uint8) for i in range(4)]
            outs = [None] * 4

            def run(i):
                acc = []
                for _ in range(5):
                    acc.append(clients[i].call("echo", payloads[i]))
                outs[i] = acc

            threads = [threading.Thread(target=run, args=(i,))
                       for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            for i in range(4):
                for got in outs[i]:
                    assert got.tobytes() == payloads[i].tobytes()
            for c in clients:
                c.close()

    def test_per_stream_requests_stay_fifo(self, server):
        """Pipelined requests on one stream are answered in order —
        the contract the ingest fetch loop's FIFO matching rides."""
        addr, _ = server
        with rpc.MuxConnection(addr) as mc:
            stream, opts = mc.connect_stream()
            assert opts is not None
            try:
                for i in range(20):
                    wire.send_msg(stream, ("echo", i), opts)
                for i in range(20):
                    status, payload = wire.recv_msg(stream, opts)
                    assert status == "ok" and payload == i
            finally:
                stream.close()

    def test_control_ops_dodge_a_saturated_pool(self, server,
                                                monkeypatch):
        """Ops in RPC_CONTROL_OPS answer while the default pool is
        parked — the starvation seam the shard fence rides."""
        addr, _ = server
        blockers = [ServiceClient(addr) for _ in range(20)]
        done = []

        def park(c):
            done.append(c.call("sleep", 1.0))

        threads = [threading.Thread(target=park, args=(c,))
                   for c in blockers]
        for t in threads:
            t.start()
        time.sleep(0.2)  # let the sleepers saturate the default pool
        c = ServiceClient(addr)
        t0 = time.monotonic()
        assert c.call("ctl") == "ctl-ok"
        elapsed = time.monotonic() - t0
        c.close()
        for t in threads:
            t.join(30)
        for b in blockers:
            b.close()
        assert elapsed < 0.9, \
            f"control op waited {elapsed:.2f}s behind parked workers"

    def test_backpressure_bounds_write_queue(self, server,
                                             monkeypatch, tmp_path):
        """A client that stops reading cannot balloon server memory:
        replies block at the write-queue budget and the connection is
        dropped at the deadline — the stall is counted, the close
        sweep runs, and the server stays healthy.  (The dropped
        client's own sends may keep succeeding for a while — the
        kernel lingers an orphaned socket while queued replies drain —
        so the assertions are server-side.)"""
        addr, _ = server
        import theanompi_tpu.parallel.rpc as rpc_mod

        monkeypatch.setattr(rpc_mod, "_WRITEQ_BYTES", 1 << 20)
        monkeypatch.setattr(rpc_mod, "_WRITEQ_TIMEOUT_S", 1.0)
        # a RAW pipelined connection that never reads (a mux transport
        # would not do: its reader thread always drains)
        from multiprocessing.connection import Client as MpClient

        def series(name):
            for e in monitor.registry().snapshot():
                if e["name"] == name:
                    return e["value"]
            return 0.0

        host, _, port = addr.rpartition(":")
        with monitor.session(str(tmp_path / "mon"),
                             stall_after=float("inf")):
            base_gauge = series("service/clients")
            base_stalls = series("rpc/backpressure_stalls_total")
            conn = MpClient((host, int(port)), authkey=b"rpc-test-key")
            try:
                want = wire.WireOptions()
                conn.send((wire.HELLO_OP, wire.hello_payload(want)))
                status, _ = conn.recv()
                assert status == "ok"
                opts = wire.WireOptions(allow_pickle=True)
                # pipeline many 4 MB replies and read NOTHING
                for _ in range(32):
                    wire.send_msg(conn, ("big", 4 << 20), opts)
                deadline = time.monotonic() + 20
                while time.monotonic() < deadline and not (
                        series("rpc/backpressure_stalls_total")
                        > base_stalls
                        and series("service/clients") <= base_gauge):
                    time.sleep(0.05)
                assert series("rpc/backpressure_stalls_total") \
                    > base_stalls, "write queue never stalled"
                assert series("service/clients") <= base_gauge, \
                    "stalled connection was not swept"
            finally:
                try:
                    conn.close()
                except OSError:
                    pass
            # and the server still serves others
            c = ServiceClient(addr)
            assert c.call("ping") == "pong"
            c.close()

    def test_mux_falls_back_on_threaded_server(self, monkeypatch):
        monkeypatch.setenv("THEANOMPI_TPU_SERVICE_KEY", "rpc-test-key")
        port = _free_port()
        ready, stop = threading.Event(), threading.Event()
        t = threading.Thread(
            target=serve, args=("127.0.0.1", port, ready, stop),
            kwargs=dict(service=EchoService(), loop="threaded"),
            daemon=True)
        t.start()
        assert ready.wait(10)
        try:
            with rpc.MuxConnection(f"127.0.0.1:{port}") as mc:
                assert not mc.mux
                c = ServiceClient(f"127.0.0.1:{port}", transport=mc)
                assert c.call("ping") == "pong"
                assert c.wire_protocol == "v2"
                c.close()
        finally:
            stop.set()
            try:
                ServiceClient(f"127.0.0.1:{port}").call("shutdown")
            except Exception:
                pass
            t.join(timeout=10)
            assert not t.is_alive()

    def test_wait_readable_mixes_streams_and_conns(self, server):
        addr, _ = server
        with rpc.MuxConnection(addr) as mc:
            s1, opts = mc.connect_stream()
            s2, _ = mc.connect_stream()
            try:
                assert rpc.wait_readable([s1, s2], timeout=0.05) == []
                wire.send_msg(s2, ("echo", "hi"), opts)
                deadline = time.monotonic() + 5
                ready = []
                while not ready and time.monotonic() < deadline:
                    ready = rpc.wait_readable([s1, s2], timeout=0.2)
                assert ready == [s2]
                status, payload = wire.recv_msg(s2, opts)
                assert (status, payload) == ("ok", "hi")
            finally:
                s1.close()
                s2.close()

    def test_malformed_pipelined_reply_stays_fifo(self, server):
        """Review regression: a malformed request's err reply must
        queue BEHIND the in-flight request's reply on its stream — an
        IO-thread shortcut would mispair a FIFO-matched client."""
        addr, _ = server
        with rpc.MuxConnection(addr) as mc:
            stream, opts = mc.connect_stream()
            try:
                wire.send_msg(stream, ("sleep", 0.3), opts)
                wire.send_msg(stream, "not-a-tuple", opts)
                wire.send_msg(stream, ("echo", "after"), opts)
                assert wire.recv_msg(stream, opts) == ("ok", "slept")
                status, diag = wire.recv_msg(stream, opts)
                assert status == "err" and "malformed" in diag
                assert wire.recv_msg(stream, opts) == ("ok", "after")
            finally:
                stream.close()

    def test_mux_grant_does_not_leak_open_streams_gauge(
            self, server, tmp_path):
        """Review regression: granting mux retires the pre-mux stream
        0 — its rpc/open_streams count must go with it."""
        addr, _ = server

        def gauge():
            for e in monitor.registry().snapshot():
                if e["name"] == "rpc/open_streams":
                    return e["value"]
            return 0.0

        with monitor.session(str(tmp_path / "mon"),
                             stall_after=float("inf")):
            base = gauge()
            with rpc.MuxConnection(addr) as mc:
                stream, opts = mc.connect_stream()
                wire.send_msg(stream, ("ping",), opts)
                assert wire.recv_msg(stream, opts) == ("ok", "pong")
                stream.close()
            deadline = time.monotonic() + 5
            while gauge() != base and time.monotonic() < deadline:
                time.sleep(0.02)
            assert gauge() == base, \
                "rpc/open_streams drifted across a mux connection"

    def test_corrupt_v2_frame_gets_typed_err_and_survives(
            self, server):
        """Selector-loop twin of the threaded loop's drained-frame
        discipline: a corrupt-but-aligned frame yields a typed err and
        the connection keeps working."""
        addr, _ = server
        with rpc.MuxConnection(addr) as mc:
            stream, opts = mc.connect_stream()
            try:
                # a header+skeleton chunk declaring 0 buffers with
                # garbage JSON: aligned (no buffers follow), corrupt
                head = wire._HEADER.pack(wire.MAGIC, wire.WIRE_VERSION,
                                         0, 0, 9)
                stream.send_bytes(head + b"not json!")
                status, payload = wire.recv_msg(stream, opts)
                assert status == "err"
                assert "WireDecodeError" in payload
                wire.send_msg(stream, ("ping",), opts)
                assert wire.recv_msg(stream, opts) == ("ok", "pong")
            finally:
                stream.close()


class TestParamServiceOnSubstrate:
    """The real ParamService riding each loop (store arithmetic is
    pinned elsewhere; this pins the serve() plumbing)."""

    def test_param_service_both_loops(self, rpc_loop, monkeypatch):
        monkeypatch.setenv("THEANOMPI_TPU_SERVICE_KEY", "rpc-test-key")
        port = _free_port()
        ready, stop = threading.Event(), threading.Event()
        t = threading.Thread(
            target=serve, args=("127.0.0.1", port, ready, stop),
            daemon=True)
        t.start()
        assert ready.wait(10)
        try:
            from theanompi_tpu.parallel.service import RemoteEASGD

            tree = {"w": np.arange(6, dtype=np.float32)}
            srv = RemoteEASGD(f"127.0.0.1:{port}", tree, alpha=0.5,
                              session_id=f"sub-{rpc_loop}")
            back = srv.get_center()
            assert np.asarray(back["w"]).tobytes() == tree["w"].tobytes()
            srv.close()
        finally:
            stop.set()
            try:
                ServiceClient(f"127.0.0.1:{port}").call("shutdown")
            except Exception:
                pass
            t.join(timeout=10)
            assert not t.is_alive()
