"""ImageNet data object — sharded batch files + parallel loading.

Parity counterpart of the reference's ImageNet pipeline
(``theanompi/models/data/imagenet.py`` + its parallel hkl loader,
SURVEY.md §2.9/§3.4 — mount empty, no file:line).  The reference
pre-processed ImageNet into hickle (HDF5) batch files, sharded the
file list per rank, broadcast the epoch's shuffled order from rank 0,
and ran a separate loader process per worker that decoded the next
file into a shared buffer while the GPU trained.

TPU-native inversion of each piece:

* **hkl batch files → shard files**: mmap-able ``train_*.x.npy`` /
  ``*.y.npy`` pairs (uint8 ``x`` (N,H,W,3), int ``y``) — the round-3
  default: zero decode at training time, the read-ahead thread just
  pages rows in (measured 1.8x the npz ingest rate on one CPU core,
  round 3) — with ``train_*.npz`` (round 1/2) still read.  Same
  pre-decoded design either way: decode cost is paid once at
  preparation time.
* **rank-0 broadcast of the shuffle → seeded permutation.**  The epoch
  order is a pure function of (seed, epoch), so every host computes
  the identical order with zero communication.
* **loader process + shared buffer → read-ahead thread feeding
  ``DevicePrefetcher``.**  File t+1 is decoded while file t's batches
  are consumed, and the prefetcher overlaps the sharded ``device_put``
  with the device step — the same double buffering without the process
  boundary (numpy releases the GIL for decode/copy).
* **no data present → deterministic synthetic mode** (this environment
  has no network egress): a small pool of class-conditional patterned
  images is generated once and sampled per batch, so benches and tests
  run the full pipeline (crop/flip/normalize/shard) with realistic
  shapes and clearly-labelled synthetic content.
"""

from __future__ import annotations

import glob
import json
import os
import queue
import threading
from typing import Callable, Iterator, Sequence

import numpy as np

from theanompi_tpu.data.base import Batch, Dataset, RowGather
from theanompi_tpu.data.utils import augment_normalize, center_normalize

IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)


def readahead(items: Sequence, load: Callable, depth: int = 2) -> Iterator:
    """Yield ``load(item)`` for each item, decoding ``depth`` ahead in a
    background thread — the reference's parallel-loader overlap.

    Abandoning the generator (GC / ``close()``) stops the producer:
    its puts are timed and poll a stop event, so no thread or decoded
    shard is leaked when a consumer takes fewer batches than the files
    hold."""
    q: queue.Queue = queue.Queue(maxsize=depth)
    sentinel = object()
    stop = threading.Event()
    err: list[BaseException] = []

    def put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def worker():
        try:
            for it in items:
                if stop.is_set() or not put(load(it)):
                    return
        except BaseException as e:  # re-raised on the consumer side
            err.append(e)
        finally:
            put(sentinel)

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    try:
        while True:
            out = q.get()
            if out is sentinel:
                if err:
                    raise err[0]
                return
            yield out
    finally:
        stop.set()
        t.join(timeout=5)


# shard-size lookups are cached in-process and via an optional
# manifest.json so a real-ImageNet directory (~1000+ shard files) is
# not re-scanned per dataset instance (reference: per-rank loaders each
# enumerated the batch-file list once at startup too)
_SIZE_CACHE: dict[str, int] = {}


def _file_size_map(data_dir: str, files: list[str]) -> dict[str, int]:
    missing = [f for f in files if f not in _SIZE_CACHE]
    if missing:
        manifest = os.path.join(data_dir, "manifest.json")
        if os.path.exists(manifest):
            import json
            with open(manifest) as fh:
                m = json.load(fh)
            for f in missing:
                n = m.get(os.path.basename(f))
                if n is not None:
                    _SIZE_CACHE[f] = int(n)
            missing = [f for f in missing if f not in _SIZE_CACHE]
        for f in missing:
            _SIZE_CACHE[f] = len(_load_shard(f)[1])
    return {f: _SIZE_CACHE[f] for f in files}


def _load_shard(path: str):
    """Decode one shard file.  ``*.x.npy`` pairs are the mmap-able
    format: ``np.load(mmap_mode='r')`` costs no decode and no copy —
    the OS pages image rows in as the gather touches them — which is
    what lets ONE host core assemble uint8 batches at device rate.
    ``.npz`` (zip container, member copy per load) remains supported.

    Cold-read strategy (round 5): ``posix_fadvise(WILLNEED)`` first —
    the kernel then streams the whole file at device speed (measured
    6 GB/s buffered on this box) instead of serving one page fault at
    a time (the bare strided touch measured 0.365 GB/s cold: QD-1
    faults, 16x under the device).  The strided touch AFTER the hint
    still (a) forces residency so the consumer's gather never blocks
    on I/O and (b) paces this read-ahead thread so ``readahead_depth``
    bounds memory, but it now walks pages the fadvise already landed."""
    if path.endswith(".x.npy"):
        x = np.load(path, mmap_mode="r")
        try:
            with open(path, "rb") as fh:
                os.posix_fadvise(fh.fileno(), 0, 0,
                                 os.POSIX_FADV_WILLNEED)
        except (AttributeError, OSError):  # pragma: no cover
            pass  # non-POSIX or odd fs: fall back to fault-driven I/O
        x.reshape(-1)[:: 4096].sum()  # one byte per page: residency
        return x, np.load(path[: -len(".x.npy")] + ".y.npy"
                          ).astype(np.int32)
    with np.load(path) as z:
        return z["x"], z["y"].astype(np.int32)


def _shard_glob(data_dir: str, prefix: str) -> list[str]:
    return sorted(
        glob.glob(os.path.join(data_dir, f"{prefix}_*.npz"))
        + glob.glob(os.path.join(data_dir, f"{prefix}_*.x.npy")))


# -- pure epoch-order derivation (shared with the ingest readers) -----------
#
# The reference broadcast each epoch's shuffled order from rank 0; here
# the order is a pure function of (seed, epoch, rank, size), so the
# in-process loader AND a standalone ingest reader fleet
# (theanompi_tpu/ingest) derive the identical stream with zero
# coordination — which is what makes the remote path byte-identical to
# the local one (pinned by tests/test_ingest.py).  These three helpers
# are THE single source of that derivation; ImageNet_data delegates.


def epoch_file_order(files: Sequence[str], seed: int, epoch: int | None,
                     rank: int = 0, size: int = 1) -> list[str]:
    """The epoch's sharded file list: seeded permutation of the full
    list (``epoch=None`` keeps sorted order — the val path), then this
    rank's ``[rank::size]`` slice."""
    files = list(files)
    if epoch is not None:
        order = np.random.default_rng(seed + 1000 + epoch)
        files = [files[i] for i in order.permutation(len(files))]
    if size > 1:
        files = files[rank::size]
    return files


def shuffle_rng(seed: int, epoch: int, rank: int) -> np.random.Generator:
    """The in-file shuffle stream: one per-file permutation is drawn
    from it per shard file, in epoch file order."""
    return np.random.default_rng(seed + 9000 + 7919 * epoch + rank)


def augment_rng(seed: int, epoch: int, rank: int) -> np.random.Generator:
    """The host-augmentation stream (unused — but still constructed —
    when augmentation runs on device)."""
    return np.random.default_rng(seed + 5000 + 7919 * epoch + rank)


def shard_tree_signature(train_files: Sequence[str],
                         sizes: dict[str, int], seed: int) -> dict:
    """Identity of a (shard set, seed) pair — what trainer and ingest
    reader must agree on for their streams to be byte-identical."""
    import hashlib

    sig = hashlib.sha256()
    for f in train_files:
        sig.update(f"{os.path.basename(f)}:{sizes[f]};".encode())
    return {"seed": int(seed),
            "n_train": int(sum(sizes[f] for f in train_files)),
            "n_files": len(train_files),
            "files_sha256": sig.hexdigest()}


def _synthetic_pool(n_images: int, n_classes: int, hw: int, seed: int):
    """Pool of distinct patterned images (uint8) + labels.  Classes get
    distinct low-frequency signatures so models can actually fit them."""
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:hw, 0:hw].astype(np.float32) / hw
    labels = (np.arange(n_images) * max(n_classes // max(n_images, 1), 1)
              ) % n_classes
    imgs = np.empty((n_images, hw, hw, 3), np.uint8)
    for i, c in enumerate(labels):
        fx, fy = 1 + c % 5, 1 + (c // 5) % 5
        phase = 2 * np.pi * (c % 97) / 97.0
        base = np.sin(2 * np.pi * fx * xx + phase) * np.cos(2 * np.pi * fy * yy)
        img = np.stack(
            [base * (0.5 + 0.5 * np.sin(phase + k)) for k in range(3)], -1
        )
        img = img + 0.3 * rng.standard_normal((hw, hw, 3), dtype=np.float32)
        imgs[i] = ((img - img.min()) / (img.max() - img.min() + 1e-8) * 255
                   ).astype(np.uint8)
    return imgs, labels.astype(np.int32)


def _copied(batches: Iterator[Batch]) -> Iterator[Batch]:
    """Plain ``(x, y)`` arrays of a ``*_batch_rows`` stream."""
    for x, y in batches:
        yield np.asarray(x), y


class ImageNet_data(Dataset):
    """ImageNet batches from shard files, or synthetic.

    ``data_dir`` layout: ``train_*`` and ``val_*`` shards — mmap-able
    ``.x.npy``/``.y.npy`` pairs (the prep default) and/or ``.npz`` —
    with ``x`` uint8 (N, store, store, 3) and ``y`` int labels.  Train
    images are randomly cropped ``store → crop`` + mirrored; val images
    are center-cropped.  File-list sharding over ``rank``/``size``
    reproduces the reference's per-rank shard lists for async rules and
    multi-host loading.
    """

    n_classes = 1000

    def __init__(self, data_dir: str | None = None, crop: int = 224,
                 seed: int = 0, synthetic_n: int = 8192,
                 synthetic_pool: int = 256, synthetic_store: int = 256,
                 readahead_depth: int = 2,
                 augment_on_device: bool = False,
                 label_noise: float = 0.0):
        self.crop = crop
        self.seed = seed
        self.sample_shape = (crop, crop, 3)
        self.readahead_depth = readahead_depth
        # device-side crop/flip/normalize (ops/augment.py): the host
        # ships raw uint8 store images — 4x fewer H2D bytes, and the one
        # host core here cannot augment at device rate (~1600 img/s
        # native fused vs 2600+ img/s device step, measured round 2)
        self.augment_on_device = augment_on_device
        if augment_on_device:
            from theanompi_tpu.ops.augment import make_device_augment

            self.device_transform = make_device_augment(
                crop, mean=IMAGENET_MEAN, std=IMAGENET_STD)
        self.synthetic = False
        self.train_files: list[str] = []
        self.val_files: list[str] = []

        data_dir = data_dir or os.environ.get("THEANOMPI_TPU_IMAGENET")
        if data_dir and os.path.isdir(data_dir):
            self.train_files = _shard_glob(data_dir, "train")
            self.val_files = _shard_glob(data_dir, "val")

        if self.train_files:
            self._file_sizes = _file_size_map(
                data_dir, self.train_files + self.val_files)
            self.n_train = sum(self._file_sizes[f] for f in self.train_files)
            self.n_val = sum(self._file_sizes[f] for f in self.val_files)
            # prepared trees carry their label space (classes.json from
            # prepare_imagenet_from_images); without it keep the
            # ImageNet default of 1000 rather than guessing from labels
            # seen in shards (a subset scan could undercount)
            cj = os.path.join(data_dir, "classes.json")
            if os.path.exists(cj):
                with open(cj) as fh:
                    self.n_classes = len(json.load(fh))
        else:
            self.synthetic = True
            self.n_train = synthetic_n
            self.n_val = max(synthetic_n // 16, 256)
            self._pool_x, self._pool_y = _synthetic_pool(
                synthetic_pool, self.n_classes, synthetic_store, seed
            )
        # falsifiable-oracle knob (VERDICT r2 #5): synthetic labels are
        # re-flipped PER DRAW (pool images recur, so a fixed flip would
        # be memorizable); Bayes val-error floor is ρ·(C-1)/C in
        # expectation on every evaluation
        self.label_noise = float(label_noise)
        if label_noise > 0.0 and not self.synthetic:
            raise ValueError("label_noise is a synthetic-oracle knob; "
                             "real ImageNet shards were found and loaded")

    # -- shared prep ---------------------------------------------------------

    def _prep_train(self, x: RowGather, rng: np.random.Generator):
        if self.augment_on_device:
            # raw uint8 store images, device crops/normalizes: the rows
            # stay uncopied, for whoever stages them (RowGather)
            return x
        return augment_normalize(np.asarray(x), self.crop, self.crop, rng,
                                 mean=IMAGENET_MEAN, std=IMAGENET_STD)

    def _prep_val(self, x: RowGather):
        if self.augment_on_device:
            return x
        return center_normalize(np.asarray(x), self.crop, self.crop,
                                mean=IMAGENET_MEAN, std=IMAGENET_STD)

    # -- synthetic path ------------------------------------------------------

    def _synthetic_batches(self, n_batches: int, global_batch: int,
                           rng: np.random.Generator, train: bool
                           ) -> Iterator[Batch]:
        pool = len(self._pool_x)
        for _ in range(n_batches):
            idx = rng.integers(0, pool, size=global_batch)
            x, y = RowGather([(self._pool_x, idx)]), self._pool_y[idx]
            if self.label_noise > 0.0:
                flip = rng.random(global_batch) < self.label_noise
                y = y.copy()
                y[flip] = rng.integers(0, self.n_classes,
                                       size=int(flip.sum()),
                                       dtype=np.int64).astype(y.dtype)
            if train:
                x = self._prep_train(x, rng)
            else:
                x = self._prep_val(x)
            yield x, y

    # -- file path -----------------------------------------------------------

    def _sharded_files(self, files: list[str], epoch: int | None,
                       rank: int, size: int) -> list[str]:
        return epoch_file_order(files, self.seed, epoch, rank, size)

    def _file_batches(self, files: list[str], global_batch: int,
                      aug_rng: np.random.Generator | None,
                      shuffle_rng: np.random.Generator | None
                      ) -> Iterator[Batch]:
        """Stream batches across shard files with read-ahead decode.
        Leftover tail samples of each file carry into the next batch.

        Each batch is ONE gather per contributing shard, straight
        from the mmap — the only host copy an image takes before
        ``device_put``.  This loop only names the rows (``RowGather``:
        the per-shard permutation slices, in order); the copy runs
        where the batch is staged, per device slice when there are
        several.  (A round-5 in-session probe found the previous shape of
        this loop — materialize ``x[perm]`` for the whole shard, then
        np.concatenate carried tails — cost ~3 memcpy passes per image
        and capped a one-core host at ~1.4k img/s warm; the gather form
        is bit-identical in output: the same per-shard permutation
        sliced in the same order.)"""

        # pending: [x, y, perm, pos] — shard arrays (x usually a
        # mmap), its draw order, and how much of it is consumed.
        # (A reusable gather buffer was tried and rejected: on a
        # single-device CPU mesh jax.device_put may zero-copy ALIAS
        # host numpy memory, so reusing the buffer could corrupt an
        # in-flight staged batch — and the isolated profile showed
        # allocation is not the bottleneck.)
        pending: list[list] = []
        buffered = 0

        def assemble() -> tuple[RowGather, np.ndarray]:
            parts: list[tuple[np.ndarray, np.ndarray]] = []
            parts_y: list[np.ndarray] = []
            need = global_batch
            while need:
                x, y, perm, pos = pending[0]
                take = min(need, len(perm) - pos)
                sel = perm[pos:pos + take]
                parts.append((x, sel))
                parts_y.append(y[sel])
                need -= take
                if pos + take == len(perm):
                    pending.pop(0)
                else:
                    pending[0][3] = pos + take
            yb = parts_y[0] if len(parts_y) == 1 \
                else np.concatenate(parts_y)
            return RowGather(parts), yb

        for x, y in readahead(files, _load_shard, self.readahead_depth):
            perm = (shuffle_rng.permutation(len(y))
                    if shuffle_rng is not None else np.arange(len(y)))
            pending.append([x, y, perm, 0])
            buffered += len(y)
            while buffered >= global_batch:
                xb, yb = assemble()
                buffered -= global_batch
                if aug_rng is not None:
                    xb = self._prep_train(xb, aug_rng)
                else:
                    xb = self._prep_val(xb)
                yield xb, yb

    # -- Dataset interface ---------------------------------------------------

    def train_batch_rows(self, epoch: int, global_batch: int,
                         rank: int = 0, size: int = 1) -> Iterator[Batch]:
        if self.synthetic:
            rng = np.random.default_rng(
                self.seed + 5000 + 7919 * epoch + 104729 * rank)
            n = (self.n_train // size) // global_batch
            yield from self._synthetic_batches(n, global_batch, rng, True)
            return
        files = self._sharded_files(self.train_files, epoch, rank, size)
        aug = augment_rng(self.seed, epoch, rank)
        shuf = shuffle_rng(self.seed, epoch, rank)
        yield from self._file_batches(files, global_batch, aug, shuf)

    def val_batch_rows(self, global_batch: int,
                       rank: int = 0, size: int = 1) -> Iterator[Batch]:
        if self.synthetic:
            rng = np.random.default_rng(self.seed + 31337 + rank)
            n = (self.n_val // size) // global_batch
            yield from self._synthetic_batches(n, global_batch, rng, False)
            return
        files = self._sharded_files(self.val_files, None, rank, size)
        yield from self._file_batches(files, global_batch, None, None)

    def train_batches(self, epoch: int, global_batch: int,
                      rank: int = 0, size: int = 1) -> Iterator[Batch]:
        yield from _copied(
            self.train_batch_rows(epoch, global_batch, rank, size))

    def val_batches(self, global_batch: int,
                    rank: int = 0, size: int = 1) -> Iterator[Batch]:
        yield from _copied(self.val_batch_rows(global_batch, rank, size))

    def n_train_batches(self, global_batch: int) -> int:
        return self.n_train // global_batch

    def n_train_batches_for(self, epoch: int, global_batch: int,
                            rank: int = 0, size: int = 1) -> int:
        if self.synthetic:
            return (self.n_train // size) // global_batch
        files = self._sharded_files(self.train_files, epoch, rank, size)
        n_mine = sum(self._file_sizes[f] for f in files)
        return n_mine // global_batch

    def ingest_signature(self) -> dict:
        """What a remote ingest reader must agree on for its stream to
        be byte-identical to this dataset's (theanompi_tpu/ingest):
        the seed (every rng above derives from it) and the exact shard
        set.  Compared against the reader's ``ingest_meta`` at
        RemoteBatchSource construction — a silent mismatch would train
        on a different permutation (or different data) while looking
        healthy."""
        if self.synthetic:
            raise RuntimeError(
                "synthetic datasets have no shard tree to serve "
                "remotely; distributed ingest needs a prepared "
                "data_dir (docs/DESIGN.md 'Distributed ingest')")
        return shard_tree_signature(self.train_files, self._file_sizes,
                                    self.seed)


def _update_manifest(out_dir: str, entries: dict[str, int]) -> None:
    """manifest.json maps shard basename -> sample count so
    training-time init never re-scans shard files."""
    import json

    manifest_path = os.path.join(out_dir, "manifest.json")
    manifest = {}
    if os.path.exists(manifest_path):
        with open(manifest_path) as fh:
            manifest = json.load(fh)
    manifest.update(entries)
    with open(manifest_path, "w") as fh:
        json.dump(manifest, fh)


def _write_shard(out_dir: str, prefix: str, index: int,
                 x: np.ndarray, y: np.ndarray, shard_format: str) -> str:
    """One shard in the chosen format; returns the path training
    discovers (for npy pairs, the ``.x.npy`` member)."""
    base = os.path.join(out_dir, f"{prefix}_{index:04d}")
    if shard_format == "npy":
        np.save(base + ".x.npy", x)
        np.save(base + ".y.npy", y)
        return base + ".x.npy"
    if shard_format == "npz":
        np.savez(base + ".npz", x=x, y=y)
        return base + ".npz"
    raise ValueError(f"unknown shard_format {shard_format!r} "
                     "(expected 'npy' or 'npz')")


def _unlink_shard(path: str) -> None:
    os.unlink(path)
    if path.endswith(".x.npy"):
        sibling = path[: -len(".x.npy")] + ".y.npy"
        if os.path.exists(sibling):
            os.unlink(sibling)


def _remove_shards(out_dir: str, paths, manifest: bool = True) -> None:
    """Delete shard files (incl. npy pair siblings); optionally prune
    their manifest entries."""
    paths = sorted(paths)
    if not paths:
        return
    if manifest:
        import json

        manifest_path = os.path.join(out_dir, "manifest.json")
        if os.path.exists(manifest_path):
            with open(manifest_path) as fh:
                m = json.load(fh)
            for p in paths:
                m.pop(os.path.basename(p), None)
            with open(manifest_path, "w") as fh:
                json.dump(m, fh)
    for p in paths:
        if os.path.exists(p):
            _unlink_shard(p)


def prepare_imagenet_shards(src_images: np.ndarray, src_labels: np.ndarray,
                            out_dir: str, prefix: str = "train",
                            shard_size: int = 1024,
                            shard_format: str = "npy") -> list[str]:
    """Offline prep: pack (N,H,W,3) uint8 images + labels into shard
    files — the rebuild's analogue of the reference's hickle
    pre-processing scripts (SURVEY.md §2.9).  Default format is the
    mmap-able ``.x.npy``/``.y.npy`` pair (see ``_load_shard``: training
    reads page in lazily with zero decode); ``shard_format='npz'``
    keeps the round-1/2 container.  A rerun replaces the prefix's
    previous shard set in EITHER format — training globs both, so a
    leftover would silently inflate the dataset."""
    os.makedirs(out_dir, exist_ok=True)
    preexisting = set(_shard_glob(out_dir, prefix))
    paths: list[str] = []
    try:
        for i in range(0, len(src_labels), shard_size):
            paths.append(_write_shard(out_dir, prefix, i // shard_size,
                                      src_images[i:i + shard_size],
                                      src_labels[i:i + shard_size],
                                      shard_format))
    except BaseException:
        _remove_shards(out_dir, set(paths) - preexisting, manifest=False)
        raise
    _remove_shards(out_dir, preexisting - set(paths))
    _update_manifest(out_dir, {
        os.path.basename(p): int(min(shard_size, len(src_labels) - k * shard_size))
        for k, p in enumerate(paths)})
    return paths


IMAGE_EXTENSIONS = (".jpeg", ".jpg", ".png", ".bmp", ".webp")


def list_image_dir(src_dir: str,
                   class_to_idx: dict[str, int] | None = None,
                   extensions: Sequence[str] = IMAGE_EXTENSIONS,
                   ) -> tuple[list[tuple[str, int]], dict[str, int]]:
    """Enumerate an ImageNet-style directory (one subdirectory per
    class, e.g. wnids) into (path, label) pairs.  Labels come from
    ``class_to_idx`` or the sorted subdirectory names — the same
    convention as the standard ImageFolder layout, so a real ImageNet
    train/ tree works unchanged."""
    classes = sorted(d for d in os.listdir(src_dir)
                     if os.path.isdir(os.path.join(src_dir, d)))
    if not classes:
        raise FileNotFoundError(
            f"{src_dir!r} has no class subdirectories (expected "
            "<src_dir>/<class>/<image>.jpeg, the ImageFolder layout)")
    if class_to_idx is None:
        class_to_idx = {c: i for i, c in enumerate(classes)}
    pairs = []
    for c in classes:
        if c not in class_to_idx:
            raise KeyError(f"directory {c!r} missing from class_to_idx")
        cdir = os.path.join(src_dir, c)
        for f in sorted(os.listdir(cdir)):
            if f.lower().endswith(tuple(extensions)):
                pairs.append((os.path.join(cdir, f), class_to_idx[c]))
    return pairs, class_to_idx


def decode_image(path: str, store: int) -> np.ndarray:
    """JPEG/PNG -> uint8 (store, store, 3): RGB, shorter side resized
    to ``store``, center crop — the reference's hickle prep stored
    256x256 center crops of the shorter-side-256 resize the same way
    (SURVEY.md §2.9)."""
    from PIL import Image

    with Image.open(path) as im:
        im = im.convert("RGB")
        w, h = im.size
        scale = store / min(w, h)
        im = im.resize((max(store, round(w * scale)),
                        max(store, round(h * scale))), Image.BILINEAR)
        left = (im.width - store) // 2
        top = (im.height - store) // 2
        im = im.crop((left, top, left + store, top + store))
        return np.asarray(im, np.uint8)


def _bounded_thread_map(fn: Callable, items: Sequence, workers: int,
                        window: int) -> Iterator:
    """``ThreadPoolExecutor.map`` with BACKPRESSURE: at most ``window``
    decode results in flight, so a slow consumer (shard writes to a
    network fs) cannot make 1.28M decoded images pile up in RAM
    (``Executor.map`` submits everything eagerly; its ``chunksize`` is
    process-pool-only)."""
    from collections import deque
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=workers) as pool:
        pending: deque = deque()
        for item in items:
            pending.append(pool.submit(fn, item))
            if len(pending) >= window:
                yield pending.popleft().result()
        while pending:
            yield pending.popleft().result()


def prepare_imagenet_from_images(src_dir: str, out_dir: str,
                                 prefix: str = "train", store: int = 256,
                                 shard_size: int = 1024,
                                 class_to_idx: dict[str, int] | None = None,
                                 workers: int = 8,
                                 shuffle_seed: int | None = 0,
                                 shard_format: str = "npy") -> list[str]:
    """Raw image directory -> resized npz shards + manifest (VERDICT r1
    next-round #8): the full analogue of the reference's raw-JPEG hickle
    preparation.  Decodes in a thread pool (PIL releases the GIL in
    libjpeg), streams into fixed-size shards so ImageNet never has to
    fit in RAM, and records the class mapping in ``classes.json``.

    ``shuffle_seed`` shuffles the global file order once at prep time
    (class subdirectories are otherwise contiguous, which would make
    early training batches single-class even after training-time
    file-order shuffling); None keeps directory order.
    """
    import json

    try:
        import PIL  # noqa: F401
    except ImportError as e:  # pragma: no cover - PIL is in this env
        raise RuntimeError(
            "raw-image preparation needs Pillow; pre-decode with "
            "prepare_imagenet_shards(images, labels, ...) instead") from e

    pairs, class_to_idx = list_image_dir(src_dir, class_to_idx)
    if shuffle_seed is not None:
        order = np.random.default_rng(shuffle_seed).permutation(len(pairs))
        pairs = [pairs[i] for i in order]
    os.makedirs(out_dir, exist_ok=True)
    # note the previous run's shards now, remove the leftovers only
    # AFTER the new set is complete: a mid-run failure (one corrupt
    # JPEG) must not destroy an existing good dataset
    preexisting = set(_shard_glob(out_dir, prefix))
    with open(os.path.join(out_dir, "classes.json"), "w") as fh:
        json.dump(class_to_idx, fh)

    paths: list[str] = []
    counts: dict[str, int] = {}
    buf_x = np.empty((shard_size, store, store, 3), np.uint8)
    buf_y = np.empty(shard_size, np.int32)
    fill = 0

    def flush():
        nonlocal fill
        p = _write_shard(out_dir, prefix, len(paths), buf_x[:fill],
                         buf_y[:fill], shard_format)
        paths.append(p)
        counts[os.path.basename(p)] = fill
        fill = 0

    decoded = _bounded_thread_map(
        lambda pl: (decode_image(pl[0], store), pl[1]), pairs,
        workers=workers, window=workers * 4)
    try:
        for img, label in decoded:
            buf_x[fill] = img
            buf_y[fill] = label
            fill += 1
            if fill == shard_size:
                flush()
        if fill:
            flush()
    except BaseException:
        # mid-run failure (one corrupt JPEG): remove THIS run's new
        # shards so the directory still holds exactly the pre-run set —
        # without this, a cross-format rerun would leave a partial new
        # set beside the complete old one and training (which globs
        # both formats) would silently train on the union
        _remove_shards(out_dir, set(paths) - preexisting, manifest=False)
        raise
    # success: drop the previous run's leftover shards IN EITHER FORMAT
    # and prune their manifest entries
    _remove_shards(out_dir, preexisting - set(paths))
    _update_manifest(out_dir, counts)
    return paths
