"""Host-side ingest rate, measured WITHOUT a chip (VERDICT r2 weak #3).

bench.py's e2e leg mixes the host pipeline with the host-to-device
link and the step.  This probe times exactly what the host does per
batch in each mode, on the real data path (`ImageNet_data`):

* ``device`` mode (the default economics): gather + stack raw uint8
  store images — the host's only job when augmentation runs on-device
  (`ops/augment.py`).
* ``host`` mode (reference loader semantics): the same plus host-side
  crop/flip/normalize to float32.

Run with synthetic pools (no data needed) or ``--data-dir`` npz shards
(the real decode/stream path).  One JSON line per mode:

    python tools/host_pipeline_probe.py --batch 128 --batches 40
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128,
                    help="global batch (one chip's worth = 128)")
    ap.add_argument("--batches", type=int, default=40)
    ap.add_argument("--store", type=int, default=256)
    ap.add_argument("--crop", type=int, default=224)
    ap.add_argument("--data-dir", default=None,
                    help="shard dir — .x.npy pairs and/or .npz "
                         "(default: synthetic pool)")
    args = ap.parse_args()

    from theanompi_tpu.data.imagenet import ImageNet_data

    out = []
    for mode, on_device in (("device", True), ("host", False)):
        ds = ImageNet_data(
            data_dir=args.data_dir, crop=args.crop,
            synthetic_n=args.batch * (args.batches + 2),
            synthetic_pool=256, synthetic_store=args.store,
            augment_on_device=on_device)
        def stream():
            epoch = 0
            while True:  # cross epochs: reshuffle + file reopen included
                yield from ds.train_batches(epoch, args.batch)
                epoch += 1

        it = stream()
        x, y = next(it)  # warm the pool/file cache outside the timer
        t0 = time.perf_counter()
        n = 0
        for _ in range(args.batches):
            x, y = next(it)
            n += len(y)
        dt = time.perf_counter() - t0
        rec = {
            "mode": mode,
            "synthetic": ds.synthetic,
            "batch": args.batch,
            "img_per_sec": round(n / dt, 1),
            "ms_per_batch": round(dt / args.batches * 1e3, 2),
            "batch_mb": round(
                sum(a.nbytes for a in (x, y)) / 1e6, 1),
            "dtype": str(x.dtype),
        }
        out.append(rec)
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
