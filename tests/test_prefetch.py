"""DevicePrefetcher: staging, exhaustion, error propagation, and the
round-5 ``stats`` hook (the in-session ingest measurement: ``stats``
separates the loader's critical path from consumer compute that shares
the host core).

PR 26: a batch whose rows are named but not copied (``RowGather``) is
staged per device slice when its sharding has several addressable
shards; everything else goes whole.  The per-shard form is held to
``shard_batch(next(train_batches))`` bit for bit."""

from __future__ import annotations

import threading
import time

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from theanompi_tpu import monitor
from theanompi_tpu.data.base import RowGather
from theanompi_tpu.data.imagenet import ImageNet_data, prepare_imagenet_shards
from theanompi_tpu.data.prefetch import DevicePrefetcher
from theanompi_tpu.models.base import _stack_host_batches
from theanompi_tpu.parallel.mesh import (
    MeshSpec,
    data_mesh,
    make_training_mesh,
    shard_batch,
)


@pytest.fixture(scope="module")
def mesh():
    return data_mesh(8)


def _batches(n, global_batch=16):
    for i in range(n):
        yield (np.full((global_batch, 4), i, np.float32),
               np.arange(global_batch, dtype=np.int32))


class TestDevicePrefetcher:
    def test_yields_all_batches_sharded(self, mesh):
        pf = DevicePrefetcher(_batches(5), mesh)
        got = list(pf)
        assert len(got) == 5
        x0, y0 = got[0]
        assert x0.shape == (16, 4) and y0.shape == (16,)
        assert float(np.asarray(x0)[0, 0]) == 0.0
        assert float(np.asarray(got[4][0])[0, 0]) == 4.0
        # sharded over the data axis, not replicated
        assert len(x0.sharding.device_set) == 8

    def test_stats_account_batches_and_images(self, mesh):
        pf = DevicePrefetcher(_batches(3), mesh)
        list(pf)
        assert pf.stats["batches"] == 3
        assert pf.stats["images"] == 3 * 16
        assert pf.stats["busy_s"] > 0.0

    def test_error_propagates_to_consumer(self, mesh):
        def bad():
            yield from _batches(1)
            raise RuntimeError("loader exploded")

        pf = DevicePrefetcher(bad(), mesh)
        it = iter(pf)
        next(it)
        with pytest.raises(RuntimeError, match="loader exploded"):
            while True:
                next(it)

    def test_close_stops_early(self, mesh):
        pf = DevicePrefetcher(_batches(100), mesh)
        next(iter(pf))
        pf.close()  # must not hang or raise


# -- per-shard staging (PR 26) ----------------------------------------------

GLOBAL_BATCH = 24


@pytest.fixture(scope="module")
def mesh4():
    return data_mesh(4)


@pytest.fixture(scope="module")
def shard_dir(tmp_path_factory):
    """A shard tree whose files (37 rows) never line up with a batch
    (24) or a device slice (6): most batches gather from two shards and
    the seam falls inside a slice."""
    d = tmp_path_factory.mktemp("shards")
    rng = np.random.default_rng(7)
    for prefix, n in (("train", 200), ("val", 160)):
        prepare_imagenet_shards(
            rng.integers(0, 256, (n, 12, 12, 3), dtype=np.uint8),
            rng.integers(0, 1000, n).astype(np.int32), str(d),
            prefix=prefix, shard_size=37)
    return str(d)


def _imagenet(source, shard_dir, **kw):
    kw = {"crop": 8, "seed": 5, "augment_on_device": True, **kw}
    if source == "file":
        return ImageNet_data(data_dir=shard_dir, **kw)
    return ImageNet_data(synthetic_n=GLOBAL_BATCH * 8, synthetic_pool=16,
                         synthetic_store=12, **kw)


def _streams(data, split):
    """(the row stream the model hands the prefetcher, the plain stream
    everyone else reads) of one split."""
    if split == "train":
        return (data.train_batch_rows(1, GLOBAL_BATCH),
                data.train_batches(1, GLOBAL_BATCH))
    return data.val_batch_rows(GLOBAL_BATCH), data.val_batches(GLOBAL_BATCH)


def _shard_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("prefetch-shard")]


class TestRowGather:
    @pytest.mark.parametrize("lo,hi", [(0, 11), (0, 4), (3, 9), (5, 11),
                                       (4, 5), (7, 7)])
    def test_rows_are_the_slice_of_the_whole(self, lo, hi):
        rng = np.random.default_rng(0)
        a, b = (rng.integers(0, 256, (9, 3, 2), dtype=np.uint8)
                for _ in range(2))
        sel_a, sel_b, sel_c = (np.array([8, 0, 3, 3]), np.array([1]),
                               np.array([2, 2, 7, 0, 5, 4]))
        leaf = RowGather([(a, sel_a), (b, sel_b), (a, sel_c)])
        whole = np.concatenate([a[sel_a], b[sel_b], a[sel_c]])
        assert leaf.shape == whole.shape and leaf.dtype == whole.dtype
        assert len(leaf) == 11
        np.testing.assert_array_equal(np.asarray(leaf), whole)
        got = leaf.rows(lo, hi)
        np.testing.assert_array_equal(got, whole[lo:hi])
        assert got.flags.owndata  # a buffer of its own, never a view


class TestPerShardStaging:
    @pytest.mark.parametrize("source,split", [
        ("synthetic", "train"), ("synthetic", "val"),
        ("file", "train"), ("file", "val")])
    def test_equals_whole_batch_staging_bit_for_bit(
            self, mesh4, shard_dir, source, split):
        data = _imagenet(source, shard_dir)
        rows, plain = _streams(data, split)
        n = 0
        with DevicePrefetcher(rows, mesh4) as pf:
            for (x, y), host in zip(pf, plain):
                assert type(host[0]) is np.ndarray  # plain, as ever
                rx, ry = shard_batch(host, mesh4)
                assert x.sharding == rx.sharding
                assert y.sharding == ry.sharding
                np.testing.assert_array_equal(np.asarray(y), np.asarray(ry))
                for got, want in zip(x.addressable_shards,
                                     rx.addressable_shards):
                    assert got.device == want.device
                    assert got.index == want.index
                    np.testing.assert_array_equal(np.asarray(got.data),
                                                  np.asarray(want.data))
                n += 1
            stats = dict(pf.stats)
        assert n >= 6 and next(plain, None) is None  # the whole stream
        assert stats["batches"] == n
        assert stats["shards"] == 4 * n
        assert stats["images"] == GLOBAL_BATCH * n
        assert stats["assemble_s"] > 0.0 and stats["stage_s"] > 0.0
        assert not _shard_threads()  # exhausted: the pool is gone

    @pytest.mark.parametrize("case", [
        "one_shard", "plain_arrays", "host_augment", "stacked",
        "spec_override"])
    def test_everything_else_goes_whole(self, mesh4, shard_dir, case,
                                        tmp_path):
        mesh, spec = mesh4, None
        data = _imagenet("synthetic", shard_dir)
        rows, plain = _streams(data, "train")
        if case == "one_shard":
            mesh = data_mesh(1)
        elif case == "plain_arrays":
            rows = _streams(data, "train")[1]
        elif case == "host_augment":
            data = _imagenet("synthetic", shard_dir, augment_on_device=False)
            rows, plain = _streams(data, "train")
        elif case == "stacked":
            rows, plain = (_stack_host_batches(it, 2)
                           for it in (rows, plain))
            spec = P(None, "data")
        elif case == "spec_override":
            # rows over 'data' AND image rows over 'seq': more than the
            # batch axis is split, so no slice is a row range
            mesh = make_training_mesh(MeshSpec(data=2, seq=2),
                                      jax.devices()[:4])
            rows, plain = (((x,) for x, _ in it) for it in (rows, plain))
            spec = P("data", "seq")
        with monitor.session(str(tmp_path)):
            with DevicePrefetcher(rows, mesh, spec=spec) as pf:
                staged = list(pf)
                stats = dict(pf.stats)
            counted = {e["labels"]["path"]: e["value"]
                       for e in monitor.registry().snapshot()
                       if e["name"] == "ingest/loader_shards_total"}
        assert len(staged) >= 4
        assert stats["shards"] == 0
        assert set(counted) == {"whole"}
        assert counted["whole"] == len(staged) * len(mesh.devices.flat)
        for got, host in zip(staged, plain):
            want = shard_batch(host, mesh, spec)
            for g, w in zip(got, want):
                assert g.sharding == w.sharding
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_monitor_counts_per_shard_slices(self, mesh4, shard_dir,
                                             tmp_path):
        rows, _ = _streams(_imagenet("synthetic", shard_dir), "train")
        with monitor.session(str(tmp_path)):
            with DevicePrefetcher(rows, mesh4, source="local") as pf:
                n = len(list(pf))
            counted = {(e["labels"]["source"], e["labels"]["path"]):
                       e["value"]
                       for e in monitor.registry().snapshot()
                       if e["name"] == "ingest/loader_shards_total"}
        assert counted == {("local", "per_shard"): 4 * n}

    def test_slice_worker_error_reaches_the_consumer(self, mesh4):
        class Exploding(RowGather):
            def rows(self, lo, hi):
                if lo:
                    raise RuntimeError("slice exploded")
                return super().rows(lo, hi)

        pool = np.zeros((4, 2, 2, 3), np.uint8)

        def batches():
            for cls in (RowGather, Exploding, RowGather):
                yield (cls([(pool, np.arange(8) % 4)]),
                       np.arange(8, dtype=np.int32))

        pf = DevicePrefetcher(batches(), mesh4)
        next(pf)
        with pytest.raises(RuntimeError, match="slice exploded"):
            next(pf)
        assert not pf._thread.is_alive() and not _shard_threads()

    def test_close_mid_epoch_leaves_no_worker(self, mesh4, shard_dir):
        rows, _ = _streams(_imagenet("file", shard_dir), "train")
        pf = DevicePrefetcher(rows, mesh4)
        next(pf)
        assert _shard_threads()  # the pool is up, one thread a shard
        pf.close()
        assert not pf._thread.is_alive()
        assert not _shard_threads()

    def test_busy_s_is_not_time_blocked_on_a_full_queue(self, mesh4,
                                                        shard_dir):
        rows, _ = _streams(_imagenet("synthetic", shard_dir), "train")
        with DevicePrefetcher(rows, mesh4, depth=2) as pf:
            next(pf)
            time.sleep(1.0)  # the worker stages two ahead, then blocks
            n = 1 + len(list(pf))
            stats = dict(pf.stats)
        assert n == 8 and stats["shards"] == 4 * n
        assert stats["busy_s"] < 0.5
