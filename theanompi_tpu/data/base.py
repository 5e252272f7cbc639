"""Dataset contract.

Parity counterpart of the reference's data objects
(``theanompi/models/data/`` — per-rank shard lists, shuffled epoch
order broadcast from rank 0, train/val iterators; SURVEY.md §2.9 —
mount empty, no file:line).

TPU-native inversion: the reference gave each of N processes its own
shard and its own iterator.  Here one controller process yields
*global* batches (size ``batch_size * data_axis_size``) which
``shard_batch`` splits across the mesh in a single ``device_put`` —
the per-worker shard view becomes a sharding annotation (and, for a
``RowGather`` batch on several chips, each chip's slice is copied and
put by a worker of its own: ``data/prefetch.py``).  The
``rank``/``size`` arguments survive for multi-host mode, where each
host process loads only its slice of the global batch.
"""

from __future__ import annotations

import abc
from typing import Iterator

import numpy as np

Batch = tuple[np.ndarray, np.ndarray]  # (images NHWC, integer labels)


class RowGather:
    """A batch leaf whose rows are drawn but not yet copied: the
    concatenation of ``src[sel]`` over ``parts`` (``(src, sel)`` pairs,
    ``src`` any row-addressable array — a pool, a shard's mmap).

    The source decides WHICH rows, in its one sequential thread (rng
    draws, file order); WHO copies them is the consumer's choice:
    ``np.asarray(leaf)`` gives the whole batch, ``rows(lo, hi)`` one
    device's slice of it, so ``DevicePrefetcher`` can have each device's
    slice gathered by a worker of its own and never materialise the
    global batch.  Either way the bytes are the same."""

    def __init__(self, parts: list[tuple[np.ndarray, np.ndarray]]):
        self.parts = parts
        src = parts[0][0]
        self.dtype = src.dtype
        self.shape = (sum(len(sel) for _, sel in parts),) + src.shape[1:]

    def __len__(self) -> int:
        return self.shape[0]

    def rows(self, lo: int, hi: int) -> np.ndarray:
        """Rows ``[lo, hi)`` in a fresh array: one gather per part that
        holds some of them, the only host copy a row takes."""
        out = np.empty((hi - lo,) + self.shape[1:], self.dtype)
        at = 0
        for src, sel in self.parts:
            a, b = max(lo, at), min(hi, at + len(sel))
            if a < b:
                # the indices are the source's own draws, all in range;
                # under the default mode='raise' np.take fills a
                # temporary and copies it to ``out``: twice the traffic
                np.take(src, sel[a - at:b - at], axis=0,
                        out=out[a - lo:b - lo], mode="clip")
            at += len(sel)
        return out

    def __array__(self, dtype=None, copy=None) -> np.ndarray:
        x = self.rows(0, len(self))
        return x if dtype is None else x.astype(dtype, copy=False)


class Dataset(abc.ABC):
    """Iterable source of global batches for one (model, run) pair."""

    #: per-shard sample shape, e.g. (32, 32, 3) — NHWC like XLA prefers
    sample_shape: tuple[int, ...]
    n_classes: int
    n_train: int
    n_val: int

    #: optional jittable ``transform(x, rng, train) -> fp32`` applied to
    #: each batch INSIDE the step (ops/augment.py).  When set, the host
    #: iterators yield raw (e.g. uint8 store-size) images and the device
    #: does crop/flip/normalize — honored by the default
    #: ``TpuModel.loss_fn``/``eval_fn``.
    device_transform = None

    @abc.abstractmethod
    def train_batches(
        self, epoch: int, global_batch: int, rank: int = 0, size: int = 1
    ) -> Iterator[Batch]:
        """Yield shuffled, augmented global train batches for ``epoch``.

        Shuffle order must be a pure function of ``epoch`` (the
        reference broadcast the epoch's shuffled file order from rank 0
        — deriving it from the epoch number gives every host the same
        order with no broadcast at all).
        """

    @abc.abstractmethod
    def val_batches(
        self, global_batch: int, rank: int = 0, size: int = 1
    ) -> Iterator[Batch]:
        """Yield validation batches in fixed order, no augmentation."""

    # -- rows named, not yet copied (DevicePrefetcher's input) -----------

    def train_batch_rows(self, epoch: int, global_batch: int,
                         rank: int = 0, size: int = 1) -> Iterator[Batch]:
        """The stream of ``train_batches``, except that a leaf MAY be a
        ``RowGather``: what ``models/base.py`` hands the prefetcher.  A
        source whose batches are row gathers overrides this and derives
        ``train_batches`` from it; the default has nothing to defer."""
        return self.train_batches(epoch, global_batch, rank, size)

    def val_batch_rows(self, global_batch: int,
                       rank: int = 0, size: int = 1) -> Iterator[Batch]:
        """``val_batches``, in the same form."""
        return self.val_batches(global_batch, rank, size)

    # -- multi-host (one controller process per host) -------------------

    @staticmethod
    def _block_slice(batch: Batch, host_rank: int, host_count: int) -> Batch:
        x, y = batch
        if len(x) % host_count != 0:
            raise ValueError(
                f"global batch {len(x)} not divisible by {host_count} hosts")
        chunk = len(x) // host_count
        sl = slice(host_rank * chunk, (host_rank + 1) * chunk)
        return x[sl], y[sl]

    def host_train_batches(self, epoch: int, global_batch: int,
                           host_rank: int, host_count: int) -> Iterator[Batch]:
        """This host's contiguous block of each *global* train batch.

        Multi-host BSP: ``jax.devices()`` orders devices by process, so
        host p's addressable shards cover rows
        ``[p*B/P, (p+1)*B/P)`` of every global batch;
        ``shard_batch`` reassembles the global array from these slices
        (``jax.make_array_from_process_local_data``).  Shuffle and
        augmentation order are pure functions of ``epoch`` (class
        docstring), so every host derives the identical global batch and
        the multi-host run is bit-equivalent to the single-process run.

        Default: build the global batch and slice — correct everywhere;
        datasets whose storage is row-addressable should override to
        read only their rows.
        """
        for batch in self.train_batches(epoch, global_batch):
            yield self._block_slice(batch, host_rank, host_count)

    def host_val_batches(self, global_batch: int, host_rank: int,
                         host_count: int) -> Iterator[Batch]:
        for batch in self.val_batches(global_batch):
            yield self._block_slice(batch, host_rank, host_count)

    def n_train_batches(self, global_batch: int) -> int:
        from theanompi_tpu.utils.helper_funcs import divide_batches

        return divide_batches(self.n_train, global_batch)

    def n_train_batches_for(self, epoch: int, global_batch: int,
                            rank: int = 0, size: int = 1) -> int:
        """EXACT number of batches ``train_batches(epoch, global_batch,
        rank, size)`` will yield.  Ranks' shards need not be equal
        (file-list sharding gives unequal sample counts), so training
        loops must size their iteration count with this, not with a
        global ``n_train / size`` estimate."""
        # default matches the index-sharding scheme (order[rank::size])
        n_mine = (self.n_train - rank + size - 1) // size
        return n_mine // global_batch

    def n_val_batches(self, global_batch: int) -> int:
        from theanompi_tpu.utils.helper_funcs import divide_batches

        return divide_batches(self.n_val, global_batch)
