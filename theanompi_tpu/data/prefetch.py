"""Double-buffered host->device prefetch.

TPU-native rebuild of the reference's parallel loader (a separate OS
process per worker decoding the next hkl file into a shared buffer
while the GPU trains — SURVEY.md §2.9/§3.4; mount empty, no file:line).

Here one background thread draws the host batches in order and the
staged result is already a *sharded device array*, so the H2D copy for
batch t+1 overlaps the device step for batch t — the same software
double-buffering, minus the process boundary and shared-memory plumbing
(numpy releases the GIL for the copy, and jax dispatch is async anyway).

How a batch is staged follows from what it is.  A leaf that names its
rows without having copied them (``data/base.py RowGather``: the
ImageNet sources) and whose sharding splits those rows over several
addressable devices is staged PER DEVICE: a small pool, one thread a
shard, has each device's slice gathered into a host buffer of its own
and put on its device, all at once, and the global array is
``jax.make_array_from_single_device_arrays`` — the global host batch
(100 MB at 4 x 128 store images) is never materialised, and four chips
no longer wait for one thread (PERF.md §6, PR 26: 124 ms a batch in one
thread against a 50 ms device step).  Everything else — one shard, plain
arrays (LM, CIFAR, remote ingest, stacked cadences), a spec that splits
more than rows, a mesh that spans processes — is copied whole and takes
``shard_batch``'s single ``device_put``: with one shard that IS the
per-device form.
"""

from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Iterable, Iterator

import jax
import numpy as np
from jax.sharding import NamedSharding

from theanompi_tpu import monitor
from theanompi_tpu.data.base import RowGather
from theanompi_tpu.parallel.mesh import (
    batch_spec,
    is_multiprocess,
    shard_batch,
)


class DevicePrefetcher:
    """Wrap a host batch iterator; yield mesh-sharded device batches.

    ``depth`` is the number of batches staged ahead (2 = classic double
    buffering).  The background thread dies with the iterator; call
    ``close()`` (or exhaust it) to stop early.

    ``stats`` exposes the loader's own critical path, measured inside
    the worker thread: ``busy_s`` is time spent assembling host
    batches + staging them to devices (NOT time blocked on a full
    queue), so ``images / busy_s`` is the sustained rate the loader
    could deliver if the consumer never ran — the in-session ingest
    number the round-4 verdict asked for, cleanly separated from
    device compute that shares the host core on CPU meshes.
    ``assemble_s`` and ``stage_s`` are the seconds spent making host
    rows and in ``device_put``, summed over whoever spent them: they
    add up to ``busy_s`` where one thread stages whole batches and
    exceed it by the overlap where slices are staged in parallel;
    ``shards`` counts the device slices staged that way (0: every
    batch went whole).

    The same numbers are exported as ``ingest/loader_*`` monitor
    series (labelled ``source='local'|'remote'``), so a run fed by the
    in-process loader and one fed by a remote reader fleet
    (theanompi_tpu/ingest) are graphed on the same dashboard rows —
    docs/OBSERVABILITY.md.
    """

    _SENTINEL = object()

    def __init__(self, host_batches: Iterable, mesh, depth: int = 2,
                 spec=None, images_per_batch: int | None = None,
                 source: str = "local"):
        self.mesh = mesh
        self.spec = spec  # PartitionSpec override (default: data axis)
        self._source = source  # 'local' | 'remote' monitor label
        # stacked cadences (steps_per_call / grad_accum) stage
        # (k, global_batch, ...) leaves, where leaves[0].shape[0] is k,
        # not an image count — callers that stack must say how many
        # images one staged batch carries (models/base.py does)
        self._images_per_batch = images_per_batch
        self.stats = {"busy_s": 0.0, "batches": 0, "images": 0,
                      "shards": 0, "assemble_s": 0.0, "stage_s": 0.0}
        self._sharding = NamedSharding(
            mesh, spec if spec is not None else batch_spec(mesh))
        self._whole_only = is_multiprocess(mesh)
        self._pool: ThreadPoolExecutor | None = None  # one thread a shard
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._err: BaseException | None = None
        self._thread = threading.Thread(
            target=self._worker, args=(iter(host_batches),), daemon=True
        )
        self._thread.start()

    def _row_slices(self, leaf) -> dict | None:
        """``{device: (lo, hi)}`` if ``leaf`` is to be staged per device:
        its rows are not copied yet and the sharding splits them, and
        nothing else, over more than one addressable device."""
        if self._whole_only or not isinstance(leaf, RowGather):
            return None
        index_map = self._sharding.addressable_devices_indices_map(
            leaf.shape)
        if len(index_map) < 2:
            return None
        slices = {}
        for device, (rows, *rest) in index_map.items():
            if any(r != slice(None) for r in rest):
                return None
            slices[device] = rows.indices(len(leaf))[:2]
        return slices

    def _stage_slice(self, leaf: RowGather, device, lo: int, hi: int):
        t0 = time.perf_counter()
        rows = leaf.rows(lo, hi)
        t1 = time.perf_counter()
        # fenced: the slice's transfer is this worker's time, not the
        # consumer's, and ``stage_s`` is the transfer, not its enqueue
        staged = jax.block_until_ready(jax.device_put(rows, device))
        return staged, t1 - t0, time.perf_counter() - t1

    def _stage(self, batch):
        """The batch as mesh-sharded device arrays, by the module
        docstring's rule; returns ``(staged, device slices staged)``."""
        s = self.stats
        leaves, treedef = jax.tree.flatten(batch)
        pending = {}  # leaf index -> a future a device slice
        for i, leaf in enumerate(leaves):
            slices = self._row_slices(leaf)
            if slices is None:
                continue
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    len(slices), thread_name_prefix="prefetch-shard")
            pending[i] = [
                self._pool.submit(self._stage_slice, leaf, d, lo, hi)
                for d, (lo, hi) in slices.items()]
        # the other leaves, as ever (labels; every leaf of a plain batch)
        t0 = time.perf_counter()
        whole = [None if i in pending
                 else (np.asarray(leaf) if isinstance(leaf, RowGather)
                       else leaf)
                 for i, leaf in enumerate(leaves)]
        t1 = time.perf_counter()
        staged = shard_batch(whole, self.mesh, self.spec)
        s["assemble_s"] += t1 - t0
        s["stage_s"] += time.perf_counter() - t1
        n_slices = 0
        for i, futures in pending.items():
            arrays = []
            for f in futures:
                array, assemble_s, stage_s = f.result()
                arrays.append(array)
                s["assemble_s"] += assemble_s
                s["stage_s"] += stage_s
            staged[i] = jax.make_array_from_single_device_arrays(
                leaves[i].shape, self._sharding, arrays)
            n_slices += len(arrays)
        s["shards"] += n_slices
        return jax.tree.unflatten(treedef, staged), n_slices

    def _worker(self, it: Iterator) -> None:
        try:
            while not self._stop.is_set():
                t0 = time.perf_counter()
                try:
                    batch = next(it)
                except StopIteration:
                    break
                s = self.stats
                s["assemble_s"] += time.perf_counter() - t0
                staged, n_slices = self._stage(batch)
                s["busy_s"] += time.perf_counter() - t0
                s["batches"] += 1
                if self._images_per_batch is not None:
                    s["images"] += self._images_per_batch
                else:
                    leaves = jax.tree.leaves(staged)
                    if leaves:
                        s["images"] += leaves[0].shape[0]
                if monitor.enabled():
                    # the loader-rate series local and remote ingest
                    # share (class docstring); strictly gated — the
                    # monitor-off hot path pays one branch
                    monitor.set_gauge("ingest/loader_img_s",
                                      s["images"] / s["busy_s"]
                                      if s["busy_s"] else 0.0,
                                      source=self._source)
                    monitor.set_gauge("ingest/loader_queue_depth",
                                      self._q.qsize(),
                                      source=self._source)
                    monitor.inc("ingest/loader_batches_total",
                                source=self._source)
                    monitor.inc(
                        "ingest/loader_shards_total",
                        n_slices or len(
                            self._sharding.addressable_devices),
                        source=self._source,
                        path="per_shard" if n_slices else "whole")
                while not self._stop.is_set():
                    try:
                        self._q.put(staged, timeout=0.1)
                        break
                    except queue.Full:
                        continue
        except BaseException as e:  # surfaced to the consumer thread
            self._err = e
        finally:
            if self._pool is not None:
                self._pool.shutdown(wait=True, cancel_futures=True)
            while not self._stop.is_set():
                try:
                    self._q.put(self._SENTINEL, timeout=0.1)
                    return
                except queue.Full:
                    continue

    def __iter__(self):
        return self

    def __next__(self):
        item = self._q.get()
        if item is self._SENTINEL:
            self._thread.join()
            if self._err is not None:
                raise self._err
            raise StopIteration
        return item

    def close(self) -> None:
        self._stop.set()
        # drain so the worker unblocks
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
