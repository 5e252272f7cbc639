"""Shared-memory wire lane (parallel/shm.py + wire v2, ISSUE 20).

The acceptance pins:

* **byte identity** — trees, RawArrays batches, ingest streams, and
  prefill→decode KV pages delivered over the shm lane are EXACTLY the
  in-band bytes (the lane ships leaves at their original dtype — no
  bf16 re-encode, no compression);
* **negotiation / silent fallback** — a remote peer, a legacy server,
  a disabled knob, and a grant whose arena then fails to allocate all
  degrade to plain in-band v2 with no caller-visible difference;
* **lease refusal matrix** — stale generation, double decref, foreign
  segment, and expired lease are TYPED refusals that ride the wire's
  ``("err", "ClassName: ...")`` discipline; the connection survives
  and the client disables its lane and retries in-band;
* **no leaked segments** — lease expiry sweeps, channel close, and
  the dead-owner orphan probe each reclaim everything (the conftest
  ``shm_segment_leak_guard`` enforces this for every test here);
* **AF_UNIX** — ``unix:/path`` addresses serve and connect on both
  RPC loops.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from theanompi_tpu import monitor
from theanompi_tpu.parallel import rpc, shm, wire
from theanompi_tpu.parallel.service import (
    RemoteEASGD,
    ServiceClient,
    serve,
)
from theanompi_tpu.parallel.server import EASGDServer
from theanompi_tpu.parallel.shards import ShardedEASGD, serve_shard

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _assert_bytes_equal(a, b, msg=""):
    fa, ta = jax.tree.flatten(a)
    fb, tb = jax.tree.flatten(b)
    assert ta == tb, f"treedef mismatch {msg}"
    for x, y in zip(fa, fb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape, msg
        assert x.tobytes() == y.tobytes(), msg


@pytest.fixture()
def shm_env(monkeypatch):
    """v2 wire + a low out-of-band threshold so the small test trees
    actually take the lane."""
    monkeypatch.setenv("THEANOMPI_TPU_SERVICE_KEY", "shm-test")
    monkeypatch.setenv("THEANOMPI_TPU_WIRE_PROTOCOL", "v2")
    monkeypatch.setenv("THEANOMPI_TPU_WIRE_SHM", "1")
    monkeypatch.setenv("THEANOMPI_TPU_SHM_MIN_BYTES", "1024")


def _big_tree(seed: int = 0) -> dict:
    """Leaves straddling the 1024-byte lane threshold: f32/f64/u8
    above it (out-of-band), an i32 and an empty leaf below (in-band)."""
    rng = np.random.default_rng(seed)
    return {"w": rng.standard_normal((64, 16)).astype(np.float32),
            "f64": rng.standard_normal((300,)),
            "px": rng.integers(0, 255, (40, 40), dtype=np.uint8),
            "step": np.arange(8, dtype=np.int32),
            "empty": np.zeros((0, 3), np.float32)}


# ---------------------------------------------------------------------------
# Arena + map_payload units (no sockets)
# ---------------------------------------------------------------------------


class TestArena:
    def test_alloc_put_map_decref_roundtrip(self, shm_env):
        a = shm.arena()
        payload = os.urandom(5000)
        lease = a.alloc(len(payload))
        assert lease is not None
        off = lease.put(payload)
        assert off is not None and off % 64 == 0
        m = shm.map_payload(lease.name, lease.generation)
        try:
            assert bytes(m[off:off + len(payload)]) == payload
        finally:
            m.close()
        a.decref(lease.name, lease.generation)
        assert a.outstanding() == 0
        # the ack proves the receiver is done -> the segment PARKS for
        # reuse instead of unlinking ...
        assert lease.name in shm.segment_names()
        # ... and the next same-size frame recycles it under a bumped
        # generation (steady state: one warm memcpy, no create cycle)
        lease2 = a.alloc(len(payload))
        assert lease2.name == lease.name
        assert lease2.generation > lease.generation
        # a reader holding the OLD generation's descriptor is refused
        with pytest.raises(shm.StaleGeneration):
            shm.map_payload(lease.name, lease.generation)
        a.decref(lease2.name, lease2.generation)
        # release_all unlinks parked segments too (test-fence path)
        a.release_all()
        assert lease.name not in shm.segment_names()

    def test_decref_refusal_matrix(self, shm_env):
        a = shm.arena()
        with pytest.raises(shm.ForeignSegment):
            a.decref(f"{shm.SEG_PREFIX}_999999_dead_1", 1)
        lease = a.alloc(100)
        with pytest.raises(shm.StaleGeneration):
            a.decref(lease.name, lease.generation + 7)
        a.decref(lease.name, lease.generation)
        with pytest.raises(shm.DoubleDecref):
            a.decref(lease.name, lease.generation)

    def test_map_refusal_matrix(self, shm_env):
        with pytest.raises(shm.ForeignSegment):
            shm.map_payload("not_a_lane_segment", 1)
        with pytest.raises(shm.LeaseExpired):
            shm.map_payload(f"{shm.SEG_PREFIX}_1_nothere_1", 1)
        # a lane-named file with no lane header: refused, not mapped
        bogus = f"{shm.SEG_PREFIX}_{os.getpid()}_bogus_1"
        path = os.path.join("/dev/shm", bogus)
        with open(path, "wb") as f:
            f.write(b"\0" * 128)
        try:
            with pytest.raises(shm.ForeignSegment, match="no lane header"):
                shm.map_payload(bogus, 1)
        finally:
            os.unlink(path)
        # wrong generation against a real segment
        lease = shm.arena().alloc(100)
        try:
            with pytest.raises(shm.StaleGeneration):
                shm.map_payload(lease.name, lease.generation + 1)
        finally:
            shm.arena().decref(lease.name, lease.generation)

    def test_lease_expiry_swept(self, shm_env, monkeypatch):
        monkeypatch.setenv("THEANOMPI_TPU_SHM_LEASE_S", "0.05")
        a = shm.arena()
        lease = a.alloc(100)
        name = lease.name
        time.sleep(0.1)
        assert a.sweep() >= 1
        assert a.outstanding() == 0
        assert name not in shm.segment_names()
        # the receiver-side read of the swept lease is the typed expiry
        with pytest.raises(shm.LeaseExpired):
            shm.map_payload(name, lease.generation)

    def test_alloc_cap_degrades_not_raises(self, shm_env, monkeypatch):
        monkeypatch.setenv("THEANOMPI_TPU_SHM_MAX_BYTES", "4096")
        assert shm.arena().alloc(1 << 20) is None

    def test_orphans_of_dead_owner_swept(self, shm_env):
        """The kill leg's cleanup: a subprocess leases a segment and is
        SIGKILLed mid-lease; the survivor's orphan probe reclaims it."""
        code = ("import os, sys, time\n"
                "sys.path.insert(0, %r)\n"
                "from theanompi_tpu.parallel import shm\n"
                "lease = shm.arena().alloc(4096)\n"
                "print(lease.name, flush=True)\n"
                "time.sleep(60)\n" % REPO_ROOT)
        p = subprocess.Popen([sys.executable, "-c", code],
                             stdout=subprocess.PIPE, text=True)
        try:
            name = p.stdout.readline().strip()
            assert name in shm.segment_names()
            p.kill()
            p.wait(timeout=10)
            deadline = time.monotonic() + 10
            while name in shm.segment_names():
                shm.sweep_orphans()
                assert time.monotonic() < deadline, \
                    f"orphan {name} survived the sweep"
                time.sleep(0.05)
        finally:
            p.kill()
            p.wait(timeout=10)


# ---------------------------------------------------------------------------
# Codec: out-of-band frames without sockets
# ---------------------------------------------------------------------------


def _lane_pair():
    """A negotiated connection's two endpoints, in-process: the hello
    really runs, so this covers offer → grant → channel construction."""
    offer = shm.client_offer()
    assert offer is not None
    server_ch, reply_grant = shm.server_grant(offer)
    assert server_ch is not None
    client_ch = shm.client_channel(offer, {"shm": reply_grant})
    assert client_ch is not None
    return (wire.WireOptions(allow_pickle=False, shm=client_ch),
            wire.WireOptions(allow_pickle=False, shm=server_ch))


class TestCodec:
    def test_roundtrip_byte_identical_and_acked(self, shm_env):
        send_opts, recv_opts = _lane_pair()
        tree = _big_tree()
        head, bufs, stats = wire.encode_frame(tree, send_opts)
        # the three >=1KiB leaves left the band; small ones stayed in
        assert stats._shm_oob == sum(
            tree[k].nbytes for k in ("w", "f64", "px"))
        assert len(bufs) == 2  # step + empty ship in-band
        back = wire.decode_frame(head, [bytes(b) for b in bufs],
                                 recv_opts)
        _assert_bytes_equal(back, tree)
        assert not back["w"].flags.writeable  # PROT_READ view
        # while the decoded views LIVE, no ack is queued: the sender
        # must not recycle the segment under them
        assert shm.arena().outstanding() == 1
        h_live, b_live, _ = wire.encode_frame(("ok", None), recv_opts)
        assert wire.decode_frame(h_live, b_live, send_opts) \
            == ("ok", None)
        assert shm.arena().outstanding() == 1
        # dropping the last view fires the decref; the ack piggybacks
        # on the receiver's next frame and the segment parks for reuse
        del back
        h2, b2, _ = wire.encode_frame(("ok", None), recv_opts)
        assert wire.decode_frame(h2, b2, send_opts) == ("ok", None)
        assert shm.arena().outstanding() == 0
        send_opts.shm.close()
        recv_opts.shm.close()

    def test_rawarrays_ride_the_lane(self, shm_env):
        send_opts, recv_opts = _lane_pair()
        x = np.arange(4096, dtype=np.uint8).reshape(64, 64) % 251
        y = np.arange(64, dtype=np.int64)
        head, bufs, stats = wire.encode_frame(
            ("batch", 3, wire.RawArrays(x, y)), send_opts)
        assert stats._shm_oob == x.nbytes  # y is under the threshold
        op, idx, (bx, by) = wire.decode_frame(head, bufs, recv_opts)
        assert (op, idx) == ("batch", 3)
        assert bx.tobytes() == x.tobytes() and bx.dtype == x.dtype
        assert by.tobytes() == y.tobytes() and by.dtype == y.dtype
        del bx, by  # release views; close() reclaims the lease
        send_opts.shm.close()
        recv_opts.shm.close()

    def test_oob_leaves_skip_bf16_rewrite(self, shm_env):
        """The lane ships ORIGINAL dtypes: under the bf16 wire dtype a
        lane-eligible f32 leaf still arrives byte-exact, while a small
        in-band f32 leaf pays the usual bf16 round trip."""
        offer = shm.client_offer()
        ch_s, grant = shm.server_grant(offer)
        ch_c = shm.client_channel(offer, {"shm": grant})
        send = wire.WireOptions(dtype="bf16", allow_pickle=False,
                                shm=ch_c)
        recv = wire.WireOptions(dtype="bf16", allow_pickle=False,
                                shm=ch_s)
        rng = np.random.default_rng(5)
        tree = {"big": rng.standard_normal(1000).astype(np.float32),
                "small": rng.standard_normal(17).astype(np.float32)}
        head, bufs, _ = wire.encode_frame(tree, send)
        back = wire.decode_frame(head, bufs, recv)
        assert back["big"].tobytes() == tree["big"].tobytes()
        assert back["small"].dtype == np.float32
        assert back["small"].tobytes() != tree["small"].tobytes()
        np.testing.assert_allclose(back["small"], tree["small"],
                                   rtol=2 ** -8)
        del back  # release views; close() reclaims the lease
        ch_c.close()
        ch_s.close()

    def test_refusals_without_negotiated_lane(self, shm_env):
        send_opts, _ = _lane_pair()
        head, bufs, _ = wire.encode_frame(_big_tree(), send_opts)
        plain = wire.WireOptions(allow_pickle=False)
        with pytest.raises(wire.ShmRefusal, match="no shm lane"):
            wire.decode_frame(head, bufs, plain)
        send_opts.shm.close()

    def test_descriptor_for_expired_lease_is_typed(self, shm_env,
                                                   monkeypatch):
        send_opts, recv_opts = _lane_pair()
        head, bufs, _ = wire.encode_frame(_big_tree(), send_opts)
        shm.release_all()  # the owner swept before the receiver mapped
        with pytest.raises(wire.ShmRefusal, match="LeaseExpired"):
            wire.decode_frame(head, bufs, recv_opts)
        send_opts.shm.close()
        recv_opts.shm.close()

    def test_foreign_and_double_acks_are_typed(self, shm_env):
        send_opts, recv_opts = _lane_pair()
        tree = _big_tree()
        head, bufs, _ = wire.encode_frame(tree, send_opts)
        back = wire.decode_frame(head, bufs, recv_opts)
        del back  # release the views -> the decref ack queues
        # replaying the SAME piggybacked ack is a DoubleDecref; an ack
        # for a segment this arena never leased is ForeignSegment
        with recv_opts.shm._lock:
            acks = [list(a) for a in recv_opts.shm._acks]
        assert acks, "view release queued no ack"
        h2, b2, _ = wire.encode_frame(("ok",), recv_opts)
        wire.decode_frame(h2, b2, send_opts)
        with recv_opts.shm._lock:
            recv_opts.shm._acks = list(acks)
        h3, b3, _ = wire.encode_frame(("ok",), recv_opts)
        with pytest.raises(wire.ShmRefusal, match="DoubleDecref"):
            wire.decode_frame(h3, b3, send_opts)
        with recv_opts.shm._lock:
            recv_opts.shm._acks = [[f"{shm.SEG_PREFIX}_1_x_1", 1]]
        h4, b4, _ = wire.encode_frame(("ok",), recv_opts)
        with pytest.raises(wire.ShmRefusal, match="ForeignSegment"):
            wire.decode_frame(h4, b4, send_opts)
        send_opts.shm.close()
        recv_opts.shm.close()

    def test_grant_then_alloc_failure_ships_in_band(self, shm_env,
                                                    monkeypatch):
        """The negotiated-but-broken case: the grant landed, then the
        arena cannot create a segment — every frame silently ships
        in-band, byte-identical."""
        send_opts, recv_opts = _lane_pair()
        monkeypatch.setattr(shm.Arena, "alloc",
                            lambda self, n: None)
        tree = _big_tree()
        head, bufs, stats = wire.encode_frame(tree, send_opts)
        assert getattr(stats, "_shm_oob", 0) == 0
        assert len(bufs) == len(jax.tree.flatten(tree)[0])
        _assert_bytes_equal(
            wire.decode_frame(head, bufs, recv_opts), tree)
        send_opts.shm.close()
        recv_opts.shm.close()

    def test_channel_close_releases_unacked_leases(self, shm_env):
        send_opts, recv_opts = _lane_pair()
        wire.encode_frame(_big_tree(), send_opts)  # never delivered
        assert shm.arena().outstanding() == 1
        send_opts.shm.close()
        assert shm.arena().outstanding() == 0
        recv_opts.shm.close()


# ---------------------------------------------------------------------------
# Negotiation matrix (hello level)
# ---------------------------------------------------------------------------


class TestNegotiation:
    def test_happy_path_grants_both_ends(self, shm_env):
        offer = shm.client_offer()
        payload = wire.hello_payload(wire.WireOptions(), shm_offer=offer)
        opts, reply, _ = wire.accept_hello(payload, allow_shm=True)
        assert opts.shm is not None and opts.shm.role == "server"
        assert reply["shm"]["granted"] is True
        ch = shm.client_channel(offer, reply)
        assert ch is not None and ch.role == "client"
        opts.shm.close()
        ch.close()

    def test_remote_peer_refused(self, shm_env):
        offer = dict(shm.client_offer(), boot_id="some-other-host")
        opts, reply, _ = wire.accept_hello(
            wire.hello_payload(wire.WireOptions(), shm_offer=offer),
            allow_shm=True)
        assert opts.shm is None and "shm" not in reply
        assert shm.client_channel(offer, reply) is None
        offer = dict(shm.client_offer(), uid=-1)
        opts, reply, _ = wire.accept_hello(
            wire.hello_payload(wire.WireOptions(), shm_offer=offer),
            allow_shm=True)
        assert opts.shm is None and "shm" not in reply

    def test_legacy_server_ignores_offer(self, shm_env):
        """allow_shm=False is the pre-lane accept path (and the
        per-connection threaded v1 fallback): the reply simply has no
        grant and the client stays in-band."""
        offer = shm.client_offer()
        opts, reply, _ = wire.accept_hello(
            wire.hello_payload(wire.WireOptions(), shm_offer=offer),
            allow_shm=False)
        assert opts.shm is None and "shm" not in reply
        assert shm.client_channel(offer, reply) is None

    def test_nonce_mismatch_refused_client_side(self, shm_env):
        offer = shm.client_offer()
        _, grant = shm.server_grant(dict(offer, nonce="replayed"))
        assert shm.client_channel(offer, {"shm": grant}) is None

    def test_disabled_knob_never_offers_or_grants(self, shm_env,
                                                  monkeypatch):
        monkeypatch.setenv("THEANOMPI_TPU_WIRE_SHM", "0")
        assert shm.client_offer() is None
        assert shm.server_grant({"boot_id": shm.boot_id(),
                                 "uid": os.getuid(),
                                 "nonce": "n"}) == (None, None)


# ---------------------------------------------------------------------------
# Service end-to-end (real sockets, both loops)
# ---------------------------------------------------------------------------


@pytest.fixture()
def local_service(shm_env, rpc_loop):
    port = _free_port()
    ready, stop = threading.Event(), threading.Event()
    t = threading.Thread(target=serve,
                         args=("127.0.0.1", port, ready, stop),
                         daemon=True)
    t.start()
    assert ready.wait(10)
    yield f"127.0.0.1:{port}"
    stop.set()
    try:
        ServiceClient(f"127.0.0.1:{port}").call("shutdown")
    except Exception:
        pass
    t.join(timeout=5)


class TestServiceE2E:
    def test_exchange_byte_identical_with_grant(self, local_service,
                                                tmp_path):
        """The headline pin: an EASGD exchange sequence over a granted
        lane is byte-identical to the in-process oracle, and the
        monitor proves the frames actually left the band."""
        with monitor.session(str(tmp_path)):
            tree = _big_tree(1)
            oracle = EASGDServer(tree, alpha=0.5)
            srv = RemoteEASGD(local_service, tree, alpha=0.5,
                              session_id="shm-e2e")
            try:
                assert srv.wire_protocol == "v2"
                for n in range(1, 4):
                    w = jax.tree.map(
                        lambda x: x + x.dtype.type(1) * n, tree)
                    _assert_bytes_equal(
                        srv.exchange(w),
                        jax.tree.map(np.asarray,
                                     jax.device_get(oracle.exchange(w))),
                        f"exchange {n}")
                _assert_bytes_equal(
                    srv.get_center(),
                    jax.tree.map(np.asarray,
                                 jax.device_get(oracle.get_center())),
                    "center")
            finally:
                srv.close()
            reg = monitor.registry()
            assert (reg.value("shm/grants_total", role="server")
                    or 0) >= 1
            assert (reg.value("shm/oob_bytes_total", dir="send")
                    or 0) > 0
            assert (reg.value("shm/oob_bytes_total", dir="recv")
                    or 0) > 0

    def test_refusal_disables_lane_and_call_survives(self,
                                                     local_service):
        """A typed ShmRefusal from the server (here: a poisoned
        piggybacked ack) must never surface to the caller — the client
        disables its lane, reconnects, and the SAME call succeeds
        in-band."""
        c = ServiceClient(local_service)
        try:
            c.call("ping")
            ch = c._wire.shm
            assert ch is not None  # the grant landed
            with ch._lock:
                ch._acks.append([f"{shm.SEG_PREFIX}_1_poison_1", 3])
            assert c.call("ping") == "pong"
            assert c._shm_on is False
            assert c._wire is None or c._wire.shm is None
            assert c.call("ping") == "pong"  # still in-band, still up
        finally:
            c.close()

    def test_forced_off_client_runs_in_band(self, local_service,
                                            monkeypatch):
        monkeypatch.setenv("THEANOMPI_TPU_WIRE_SHM", "0")
        tree = _big_tree(2)
        srv = RemoteEASGD(local_service, tree, alpha=0.5,
                          session_id="inband")
        try:
            assert srv.wire_protocol == "v2"
            _assert_bytes_equal(srv.get_center(), tree, "center")
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# Sharded K=2 + AF_UNIX + ingest + KV migration over the lane
# ---------------------------------------------------------------------------


def test_sharded_k2_byte_identical_over_lane(shm_env, rpc_loop):
    tree = _big_tree(3)
    oracle = EASGDServer(tree, alpha=0.5)
    fleet = []
    for i in range(2):
        port = _free_port()
        ready, stop = threading.Event(), threading.Event()
        t = threading.Thread(target=serve_shard,
                             args=("127.0.0.1", port, i, ready, stop),
                             daemon=True)
        t.start()
        assert ready.wait(10)
        fleet.append((f"127.0.0.1:{port}", stop, t))
    try:
        srv = ShardedEASGD([a for a, _, _ in fleet], tree, alpha=0.5,
                           session_id="shm-k2")
        try:
            for n in range(1, 4):
                w = jax.tree.map(lambda x: x + x.dtype.type(n), tree)
                _assert_bytes_equal(
                    srv.exchange(w),
                    jax.tree.map(np.asarray,
                                 jax.device_get(oracle.exchange(w))),
                    f"exchange {n} (K=2, shm)")
        finally:
            srv.close()
    finally:
        for addr, stop, t in fleet:
            stop.set()
            try:
                ServiceClient(addr).call("shutdown")
            except Exception:
                pass
            t.join(timeout=5)


@pytest.mark.parametrize("loop", ["threaded", "selector"])
def test_unix_address_serves_both_loops(shm_env, monkeypatch, tmp_path,
                                        loop):
    """``unix:/path`` through serve() and every client path: the
    listener binds the socket file, clients round-trip, and shutdown
    unlinks it."""
    if not rpc.have_af_unix():  # pragma: no cover - linux CI has it
        pytest.skip("no AF_UNIX on this platform")
    monkeypatch.setenv("THEANOMPI_TPU_RPC_LOOP", loop)
    path = str(tmp_path / "svc.sock")
    addr = f"{rpc.UNIX_PREFIX}{path}"
    ready, stop = threading.Event(), threading.Event()
    t = threading.Thread(target=serve, args=(addr, 0, ready, stop),
                         daemon=True)
    t.start()
    assert ready.wait(10)
    assert os.path.exists(path)
    tree = _big_tree(4)
    srv = RemoteEASGD(addr, tree, alpha=0.5, session_id="unix")
    try:
        assert srv.wire_protocol == "v2"
        _assert_bytes_equal(srv.get_center(), tree, "center over unix")
    finally:
        srv.close()
        stop.set()
        try:
            ServiceClient(addr).call("shutdown")
        except Exception:
            pass
        t.join(timeout=5)
    deadline = time.monotonic() + 5
    while os.path.exists(path) and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not os.path.exists(path), "shutdown left the socket file"


def test_ingest_stream_byte_identical_over_lane(shm_env, rpc_loop,
                                                tmp_path):
    """The ingest plane: a remote stream whose pixel batches ride the
    lane equals the in-process loader batch for batch."""
    from theanompi_tpu.data.imagenet import (
        ImageNet_data,
        prepare_imagenet_shards,
    )
    from theanompi_tpu.ingest.client import RemoteBatchSource
    from theanompi_tpu.ingest.reader import IngestReader, serve_reader

    d = str(tmp_path / "shards")
    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 255, size=(200, 8, 8, 3), dtype=np.uint8)
    labels = rng.integers(0, 10, size=200).astype(np.int64)
    prepare_imagenet_shards(imgs, labels, d, shard_size=100)
    dataset = ImageNet_data(data_dir=d, crop=8, seed=7,
                            augment_on_device=True)
    port = _free_port()
    reader = IngestReader(d, seed=7, reader_id=0)
    ready = threading.Event()
    t = threading.Thread(target=serve_reader,
                         args=("127.0.0.1", port, reader, ready),
                         daemon=True)
    t.start()
    assert ready.wait(30)
    addr = f"127.0.0.1:{port}"
    try:
        with monitor.session(str(tmp_path / "mon")):
            with RemoteBatchSource([addr], data=dataset, epoch=1,
                                   global_batch=32) as src:
                remote = list(src)
            local = list(dataset.train_batches(1, 32, 0, 1))
            assert len(remote) == len(local)
            for i, ((rx, ry), (lx, ly)) in enumerate(zip(remote, local)):
                assert rx.dtype == lx.dtype and np.array_equal(rx, lx), i
                assert ry.dtype == ly.dtype and np.array_equal(ry, ly), i
            reg = monitor.registry()
            assert (reg.value("shm/oob_bytes_total", dir="recv")
                    or 0) > 0
    finally:
        c = ServiceClient(addr)
        try:
            c.call("shutdown")
        except Exception:
            pass
        c.close()
        t.join(timeout=10)


@pytest.mark.slow
def test_prefill_to_decode_pages_over_lane(shm_env, tmp_path,
                                           monkeypatch):
    """The KV plane: prefill exports pages, the client receives them
    over the lane BYTE-identically, and the decode server adopts them
    into a stream equal to the uncached full-forward oracle."""
    from tests._decode_helpers import flax_greedy
    from theanompi_tpu.frontdoor import PrefillClient, PrefillServer
    from theanompi_tpu.frontdoor import prefill as prefill_mod
    from theanompi_tpu.models.base import ModelConfig
    from theanompi_tpu.models.transformer import TransformerLM
    from theanompi_tpu.serving import (
        InferenceClient,
        InferenceServer,
        export_model,
    )
    from theanompi_tpu.serving import serve as serve_inference

    monkeypatch.setenv("THEANOMPI_TPU_SHM_MIN_BYTES", "256")
    cfg = ModelConfig(batch_size=4, n_epochs=1, print_freq=0,
                      compute_dtype="float32", optimizer="adamw",
                      learning_rate=1e-3, weight_decay=0.0,
                      lr_schedule="constant")
    model = TransformerLM(config=cfg, vocab=32, seq_len=16, n_layers=2,
                          d_model=16, n_heads=2, verbose=False)
    params = jax.device_get(model.state.params)
    export_dir = str(tmp_path / "export")
    export_model(model, export_dir, version=0)
    geo = dict(page_size=4, pages_per_seq=8, max_seqs=4,
               prefill_buckets=(8,))
    pre = PrefillServer(export_dir, model=model, max_pending=8, **geo)
    dec = InferenceServer(export_dir, replicas=1, reload_poll_s=0,
                          model=model, decode=True,
                          decode_opts=geo).start()
    sent = {}
    orig = pre.prefill

    def spy(prompt):
        man, raw = orig(prompt)
        sent["k"], sent["v"] = raw
        return man, raw

    pre.prefill = spy
    threads, stops, addrs = [], [], {}
    for name, target, obj in (("prefill", prefill_mod.serve, pre),
                              ("decode", serve_inference, dec)):
        port = _free_port()
        ready, stop = threading.Event(), threading.Event()
        t = threading.Thread(target=target,
                             args=(obj, "127.0.0.1", port, ready, stop),
                             daemon=True)
        t.start()
        assert ready.wait(30)
        threads.append(t)
        stops.append(stop)
        addrs[name] = f"127.0.0.1:{port}"
    try:
        with monitor.session(str(tmp_path / "mon")):
            prompt = np.asarray([3, 1, 4, 1, 5], np.int32)
            pc = PrefillClient(addrs["prefill"])
            try:
                man, k, v = pc.prefill(prompt)
            finally:
                pc.close()
            assert k.tobytes() == sent["k"].tobytes()
            assert v.tobytes() == sent["v"].tobytes()
            dc = InferenceClient(addrs["decode"])
            try:
                toks = dc.adopt(man, k, v, 6)
            finally:
                dc.close()
            assert list(toks) == flax_greedy(model, params, prompt, 6)
            reg = monitor.registry()
            assert (reg.value("shm/oob_bytes_total", dir="recv")
                    or 0) > 0
    finally:
        for stop in stops:
            stop.set()
        for name in ("prefill", "decode"):
            try:
                ServiceClient(addrs[name]).call("shutdown")
            except Exception:
                pass
        for t in threads:
            t.join(timeout=10)
        dec.stop()
