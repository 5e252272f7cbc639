"""Distributed ingest (theanompi_tpu/ingest, ISSUE 9): byte-identical
remote streams, shuffle-epoch determinism across fleet sizes,
backpressure via typed Overloaded, and reader-death reassignment —
over REAL sockets (thread-hosted readers, the same wire loop the
standalone processes run)."""

from __future__ import annotations

import os
import socket
import threading
import time

import numpy as np
import pytest

os.environ.setdefault("THEANOMPI_TPU_SERVICE_KEY", "test-ingest")

from theanompi_tpu.data.imagenet import (
    ImageNet_data,
    prepare_imagenet_shards,
)
from theanompi_tpu.ingest import protocol
from theanompi_tpu.ingest.client import RemoteBatchSource
from theanompi_tpu.ingest.coordinator import (
    IngestCoordinator,
    serve_coordinator,
)
from theanompi_tpu.ingest.order import EpochOrder
from theanompi_tpu.ingest.reader import IngestReader, serve_reader
from theanompi_tpu.parallel.service import ServiceClient, ServiceError

SEED = 3
BATCH = 32


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture(scope="module")
def shard_tree(tmp_path_factory):
    """A real mmap shard tree: 700 samples in 7 files of 100 (batches
    straddle file boundaries at global batch 32)."""
    d = str(tmp_path_factory.mktemp("ingest_shards"))
    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 255, size=(700, 8, 8, 3), dtype=np.uint8)
    labels = rng.integers(0, 10, size=700).astype(np.int64)
    prepare_imagenet_shards(imgs, labels, d, shard_size=100)
    return d


@pytest.fixture()
def dataset(shard_tree):
    return ImageNet_data(data_dir=shard_tree, crop=8, seed=SEED,
                         augment_on_device=True)


class _Fleet:
    """Thread-hosted readers (+ optional coordinator) on real ports."""

    def __init__(self, data_dir: str, n: int, seed: int = SEED,
                 coordinator: bool = False, max_inflight: int = 8,
                 probe_interval_s: float = 0.3):
        self.readers: list[IngestReader] = []
        self.threads: list[threading.Thread] = []
        self.addrs: list[str] = []
        for i in range(n):
            port = _free_port()
            reader = IngestReader(data_dir, seed=seed, reader_id=i,
                                  max_inflight=max_inflight)
            ready = threading.Event()
            t = threading.Thread(
                target=serve_reader,
                args=("127.0.0.1", port, reader, ready),
                daemon=True)
            t.start()
            assert ready.wait(30)
            self.readers.append(reader)
            self.threads.append(t)
            self.addrs.append(f"127.0.0.1:{port}")
        self.coordinator = None
        self.coordinator_addr = None
        if coordinator:
            self.coordinator = IngestCoordinator(
                list(self.addrs), probe_interval_s=probe_interval_s)
            port = _free_port()
            ready = threading.Event()
            t = threading.Thread(
                target=serve_coordinator,
                args=("127.0.0.1", port, self.coordinator, ready),
                daemon=True)
            t.start()
            assert ready.wait(30)
            self.threads.append(t)
            self.coordinator_addr = f"127.0.0.1:{port}"

    @property
    def ingest_addrs(self) -> list[str]:
        return ([self.coordinator_addr] if self.coordinator_addr
                else list(self.addrs))

    def kill(self, addr: str) -> None:
        """Shut one server loop down (its conns close, like a process
        death from the clients' point of view)."""
        c = ServiceClient(addr)
        try:
            c.call("shutdown")
        except Exception:
            pass
        c.close()

    def stop(self) -> None:
        for addr in ([self.coordinator_addr] if self.coordinator_addr
                     else []) + list(self.addrs):
            self.kill(addr)
        for t in self.threads:
            t.join(timeout=10)
            assert not t.is_alive(), "server thread did not exit"


@pytest.fixture()
def fleet2(shard_tree):
    f = _Fleet(shard_tree, 2)
    yield f
    f.stop()


def _local_stream(dataset, epoch, rank=0, size=1):
    return list(dataset.train_batches(epoch, BATCH, rank, size))


def _assert_streams_equal(remote, local):
    assert len(remote) == len(local)
    for i, ((rx, ry), (lx, ly)) in enumerate(zip(remote, local)):
        assert rx.dtype == lx.dtype and np.array_equal(rx, lx), i
        assert ry.dtype == ly.dtype and np.array_equal(ry, ly), i


# ---------------------------------------------------------------------------
# Pure plan / order math
# ---------------------------------------------------------------------------


class TestPartition:
    def test_covers_contiguously(self):
        owners = protocol.partition_batches(10, ["a", "b", "c"])
        assert owners == [(0, 4, "a"), (4, 7, "b"), (7, 10, "c")]
        assert [protocol.owner_of(owners, i) for i in range(10)] == \
            ["a"] * 4 + ["b"] * 3 + ["c"] * 3

    def test_rotation_spreads_concurrent_ranks(self):
        """Rank-rotated plans start concurrent trainers on DIFFERENT
        readers (same ranges, rotated owner order) so a same-phase
        fleet serves in parallel instead of one reader at a time."""
        r0 = protocol.partition_batches(10, ["a", "b"], rotation=0)
        r1 = protocol.partition_batches(10, ["a", "b"], rotation=1)
        assert [(lo, hi) for lo, hi, _ in r0] == \
            [(lo, hi) for lo, hi, _ in r1]
        assert [a for _, _, a in r0] == ["a", "b"]
        assert [a for _, _, a in r1] == ["b", "a"]
        assert protocol.partition_batches(10, ["a", "b"], rotation=2) \
            == r0

    def test_more_readers_than_batches(self):
        owners = protocol.partition_batches(2, ["a", "b", "c"])
        assert owners == [(0, 1, "a"), (1, 2, "b"), (2, 2, "c")]

    def test_out_of_range(self):
        with pytest.raises(IndexError):
            protocol.owner_of(protocol.partition_batches(4, ["a"]), 4)

    def test_validation(self):
        with pytest.raises(ValueError):
            protocol.partition_batches(4, [])
        with pytest.raises(ValueError):
            protocol.partition_batches(-1, ["a"])

    def test_addresses_parse(self, monkeypatch):
        assert protocol.ingest_addresses("h:1, g:2,") == ["h:1", "g:2"]
        assert protocol.ingest_addresses("") is None
        monkeypatch.delenv(protocol.ENV_VAR, raising=False)
        assert protocol.ingest_addresses() is None
        monkeypatch.setenv(protocol.ENV_VAR, "x:9")
        assert protocol.ingest_addresses() == ["x:9"]
        with pytest.raises(ValueError):
            protocol.ingest_addresses("no-port")


class TestEpochOrder:
    @pytest.mark.parametrize("rank,size", [(0, 1), (0, 2), (1, 2)])
    def test_byte_identical_to_streaming_loader(self, dataset, rank,
                                                size):
        for epoch in (0, 2):
            local = _local_stream(dataset, epoch, rank, size)
            order = EpochOrder(dataset.train_files, dataset._file_sizes,
                               SEED, epoch, rank, size)
            assert order.n_batches(BATCH) == len(local) \
                == dataset.n_train_batches_for(epoch, BATCH, rank, size)
            remote = [order.assemble(i, BATCH)
                      for i in range(order.n_batches(BATCH))]
            _assert_streams_equal(remote, local)

    def test_out_of_range(self, dataset):
        order = EpochOrder(dataset.train_files, dataset._file_sizes,
                           SEED, 0)
        with pytest.raises(IndexError):
            order.assemble(order.n_batches(BATCH), BATCH)

    def test_files_for_batches(self, dataset):
        order = EpochOrder(dataset.train_files, dataset._file_sizes,
                           SEED, 0)
        n = order.n_batches(BATCH)
        everything = order.files_for_batches(0, n, BATCH)
        assert everything == list(range(len(order.files)))
        head = order.files_for_batches(0, 2, BATCH)
        # 2 batches of 32 touch only the first shard file (100 rows)
        assert head == [0]
        assert order.files_for_batches(3, 3, BATCH) == []


# ---------------------------------------------------------------------------
# Reader + client over real sockets
# ---------------------------------------------------------------------------


class TestRemoteStream:
    @pytest.mark.parametrize("n_readers", [1, 2, 3])
    def test_byte_identical_across_fleet_sizes(self, rpc_loop, shard_tree,
                                               dataset, n_readers):
        """The acceptance pin: every fleet size N yields EXACTLY the
        in-process loader's stream — same seed, one permutation per
        epoch, reassembled in epoch order."""
        fleet = _Fleet(shard_tree, n_readers)
        try:
            with RemoteBatchSource(fleet.ingest_addrs, data=dataset,
                                   epoch=1, global_batch=BATCH) as src:
                remote = list(src)
            _assert_streams_equal(remote, _local_stream(dataset, 1))
            if n_readers > 1:
                served = [r.stats()["served"] for r in fleet.readers]
                assert all(s > 0 for s in served), served
        finally:
            fleet.stop()

    def test_mux_pipes_byte_identical(self, fleet2, dataset,
                                      shard_tree, monkeypatch):
        """ISSUE 11: with mux on, the control clients and the pull
        pipeline to each reader share one multiplexed socket — and
        the stream stays byte-identical to the in-process loader."""
        monkeypatch.setenv("THEANOMPI_TPU_RPC_LOOP", "selector")
        with RemoteBatchSource(fleet2.ingest_addrs, data=dataset,
                               epoch=1, global_batch=BATCH,
                               mux=True) as src:
            remote = list(src)
            # one shared transport per reader peer, all mux-granted
            assert src._transports and all(
                t.mux for t in src._transports.values())
        _assert_streams_equal(remote, _local_stream(dataset, 1))

    def test_sharded_trainer_streams(self, fleet2, dataset, shard_tree):
        """Async-rule trainers (rank r of s) each see their own
        byte-identical stream from ONE fleet."""
        for rank in (0, 1):
            with RemoteBatchSource(fleet2.ingest_addrs, data=dataset,
                                   epoch=0, global_batch=BATCH,
                                   rank=rank, size=2) as src:
                remote = list(src)
            _assert_streams_equal(remote,
                                  _local_stream(dataset, 0, rank, 2))

    def test_meta_mismatch_refused(self, fleet2, shard_tree):
        """A trainer whose dataset seed differs from the fleet's must
        be refused at construction — not fed a silently different
        permutation."""
        other = ImageNet_data(data_dir=shard_tree, crop=8, seed=SEED + 1,
                              augment_on_device=True)
        with pytest.raises(ValueError, match="different dataset"):
            RemoteBatchSource(fleet2.ingest_addrs, data=other, epoch=0,
                              global_batch=BATCH)

    def test_host_augmented_dataset_refused(self, fleet2, shard_tree):
        ds = ImageNet_data(data_dir=shard_tree, crop=8, seed=SEED,
                           augment_on_device=False)
        with pytest.raises(ValueError, match="augment"):
            RemoteBatchSource(fleet2.ingest_addrs, data=ds, epoch=0,
                              global_batch=BATCH)

    def test_synthetic_dataset_refused(self, fleet2):
        ds = ImageNet_data(crop=8, seed=SEED, augment_on_device=True)
        assert ds.synthetic
        with pytest.raises(RuntimeError, match="synthetic"):
            RemoteBatchSource(fleet2.ingest_addrs, data=ds, epoch=0,
                              global_batch=BATCH)


class TestBackpressure:
    def test_overload_is_typed_and_bounded(self, shard_tree, dataset):
        """Admission past max_inflight rejects in O(1) with the typed
        Overloaded riding the err-reply prefix — the serving
        discipline on the reader."""
        fleet = _Fleet(shard_tree, 1, max_inflight=1)
        try:
            reader = fleet.readers[0]
            # hold the only admission slot: the next pull must be
            # rejected, not queued
            assert reader._admission.acquire(blocking=False)
            c = ServiceClient(fleet.addrs[0])
            try:
                with pytest.raises(ServiceError, match="Overloaded"):
                    c.call(protocol.OP_BATCH, 0, 0, 1, BATCH, 0)
                reader._admission.release()
                x, y = c.call(protocol.OP_BATCH, 0, 0, 1, BATCH, 0)
                assert x.shape == (BATCH, 8, 8, 3)
            finally:
                c.close()
        finally:
            fleet.stop()

    def test_client_backs_off_and_retries(self, shard_tree, dataset):
        """An overloaded reader sheds load; the client treats it as
        backpressure (retry with backoff), not failure."""
        fleet = _Fleet(shard_tree, 1, max_inflight=1)
        try:
            reader = fleet.readers[0]
            assert reader._admission.acquire(blocking=False)
            src = RemoteBatchSource(fleet.ingest_addrs, data=dataset,
                                    epoch=0, global_batch=BATCH,
                                    depth=2)
            try:
                time.sleep(0.3)  # fetchers are hitting Overloaded now
                assert reader.stats()["served"] == 0
                reader._admission.release()
                _assert_streams_equal(list(src),
                                      _local_stream(dataset, 0))
            finally:
                src.close()
        finally:
            fleet.stop()

    def test_slow_trainer_bounds_reader_memory(self, shard_tree,
                                               dataset):
        """A slow consumer stops the pipelined pulls at the reorder
        window — readers never run ahead unboundedly (no unbounded
        queue anywhere)."""
        fleet = _Fleet(shard_tree, 2)
        try:
            depth = 3
            src = RemoteBatchSource(fleet.ingest_addrs, data=dataset,
                                    epoch=0, global_batch=BATCH,
                                    depth=depth)
            try:
                next(iter(src))  # consume ONE batch, then stall
                time.sleep(0.5)
                served = sum(r.stats()["served"]
                             for r in fleet.readers)
                # 1 consumed + at most `depth` in the window
                assert served <= 1 + depth, served
                before = served
                time.sleep(0.3)
                assert sum(r.stats()["served"]
                           for r in fleet.readers) == before
            finally:
                src.close()
        finally:
            fleet.stop()


class TestReaderDeath:
    def test_static_failover_byte_identical(self, shard_tree, dataset):
        """Kill a reader mid-epoch with NO coordinator: the client
        re-partitions over the survivors and the stream stays
        byte-identical."""
        fleet = _Fleet(shard_tree, 2)
        killed = False
        try:
            local = _local_stream(dataset, 1)
            src = RemoteBatchSource(fleet.ingest_addrs, data=dataset,
                                    epoch=1, global_batch=BATCH,
                                    depth=2)
            remote = []
            try:
                it = iter(src)
                for _ in range(3):
                    remote.append(next(it))
                # the tail range's owner dies mid-epoch
                fleet.kill(fleet.addrs[1])
                killed = True
                for b in it:
                    remote.append(b)
            finally:
                src.close()
            _assert_streams_equal(remote, local)
        finally:
            if killed:
                fleet.addrs.pop(1)  # already shut down
                fleet.threads.pop(1).join(timeout=10)
            fleet.stop()

    def test_coordinator_reassigns_mid_epoch(self, shard_tree, dataset):
        """The coordinator verifies the report, reassigns the dead
        reader's ranges, and the stream stays byte-identical — the
        acceptance kill/reassign pin."""
        fleet = _Fleet(shard_tree, 2, coordinator=True)
        killed = False
        try:
            local = _local_stream(dataset, 1)
            src = RemoteBatchSource(fleet.ingest_addrs, data=dataset,
                                    epoch=1, global_batch=BATCH,
                                    depth=2)
            remote = []
            try:
                it = iter(src)
                for _ in range(3):
                    remote.append(next(it))
                fleet.kill(fleet.addrs[1])
                killed = True
                for b in it:
                    remote.append(b)
            finally:
                src.close()
            _assert_streams_equal(remote, local)
            stats = fleet.coordinator.stats()
            assert stats["reassignments"] >= 1
            assert stats["readers"][fleet.addrs[1]] is False
        finally:
            if killed:
                fleet.addrs.pop(1)
                fleet.threads.pop(1).join(timeout=10)
            fleet.stop()

    def test_report_dead_verifies_first(self, shard_tree):
        """A flaky trainer reporting a HEALTHY reader must not evict
        it."""
        fleet = _Fleet(shard_tree, 2, coordinator=True)
        try:
            c = ServiceClient(fleet.coordinator_addr)
            try:
                out = c.call(protocol.OP_REPORT_DEAD, fleet.addrs[0])
                assert out["dead"] is False
                assert fleet.coordinator.stats()["readers"][
                    fleet.addrs[0]] is True
            finally:
                c.close()
        finally:
            fleet.stop()

    def test_plan_pinned_until_membership_changes(self, shard_tree):
        fleet = _Fleet(shard_tree, 2, coordinator=True)
        try:
            c = ServiceClient(fleet.coordinator_addr)
            try:
                p1 = c.call(protocol.OP_PLAN, 0, 0, 1, BATCH, 10)
                p2 = c.call(protocol.OP_PLAN, 0, 0, 1, BATCH, 10)
                assert p1 == p2
                owners = [tuple(o) for o in p1["owners"]]
                assert owners == protocol.partition_batches(
                    10, fleet.addrs)
            finally:
                c.close()
        finally:
            fleet.stop()


class TestAssignRace:
    def test_concurrent_assigns_never_join_unstarted_thread(
            self, shard_tree):
        """T trainers hitting one epoch boundary push concurrent
        ingest_assign ops; replacement must never observe (and join) a
        stored-but-unstarted prefetch thread."""
        reader = IngestReader(shard_tree, seed=SEED, reader_id=0)
        errs: list = []

        def assign(i):
            try:
                for k in range(5):
                    reader._assign(0, i % 2, 2, BATCH, 0, 3)
            except BaseException as e:
                errs.append(e)

        threads = [threading.Thread(target=assign, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads), \
            "an assignment never returned"
        reader.shutdown()
        assert not errs, errs


class TestLauncherFlag:
    def test_serve_refuses_ingest(self):
        from theanompi_tpu.launcher import tmlocal

        with pytest.raises(SystemExit, match="TRAINING"):
            tmlocal(["SERVE", "--export-dir", "/tmp/x",
                     "--ingest", "h:1"])

    def test_bad_spec_fails_fast(self):
        from theanompi_tpu.launcher import tmlocal

        with pytest.raises(SystemExit, match="--ingest"):
            tmlocal(["BSP", "--ingest", "not-an-address"])


class TestEndToEnd:
    def test_begin_epoch_switches_on_env(self, shard_tree, monkeypatch):
        """The rules-facing contract: with THEANOMPI_TPU_INGEST set
        (launcher --ingest), begin_epoch stages the SAME device
        batches through DevicePrefetcher as the local loader —
        nothing above the data layer changes."""
        import jax

        from tests._tiny_models import TinyRecipeResNet
        from theanompi_tpu.models.base import ModelConfig
        from theanompi_tpu.parallel import data_mesh

        rng = np.random.default_rng(1)
        imgs = rng.integers(0, 255, size=(256, 40, 40, 3),
                            dtype=np.uint8)
        labels = rng.integers(0, 1000, size=256).astype(np.int64)
        d = os.path.join(shard_tree, "..", "e2e_shards")
        prepare_imagenet_shards(imgs, labels, d, shard_size=64)
        ds = ImageNet_data(data_dir=d, crop=32, seed=0,
                           augment_on_device=True)
        cfg = ModelConfig(batch_size=2, n_epochs=1, print_freq=0)
        model = TinyRecipeResNet(config=cfg, mesh=data_mesh(8),
                                 data=ds, verbose=False)

        monkeypatch.delenv(protocol.ENV_VAR, raising=False)
        n_local = model.begin_epoch(0)
        local = [jax.device_get(next(model._train_iter))
                 for _ in range(n_local)]
        model.cleanup_iter()

        fleet = _Fleet(d, 2, seed=0)
        try:
            monkeypatch.setenv(protocol.ENV_VAR,
                               ",".join(fleet.addrs))
            n_remote = model.begin_epoch(0)
            assert n_remote == n_local
            remote = [jax.device_get(next(model._train_iter))
                      for _ in range(n_remote)]
            assert model._ingest_source is not None
            model.cleanup_iter()
            assert model._ingest_source is None
            _assert_streams_equal(remote, local)
        finally:
            fleet.stop()
        model.cleanup()
