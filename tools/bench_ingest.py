"""Distributed-ingest benchmark — a REAL N-reader fleet over sockets
(ISSUE 9 measurement leg).

Drives T trainer streams (``RemoteBatchSource``, the exact client the
rules use) against an :class:`IngestProcessGroup` of N real reader
processes serving a real mmap shard tree, and reports the aggregate
delivered rate per fleet size.  The N=1 vs N=2 comparison consumes the
IDENTICAL batch set (same dataset, same epoch permutation, same
trainer count — the streams are byte-identical by construction, and
the bench cross-checks the consumed byte totals), so the ratio
isolates what the fleet adds: assembly + framing CPU moving out of one
process into N.

``--smoke`` is the preflight gate (exit 1 on any miss):

* N=2 aggregate img/s >= ``--scale-bar`` (default 1.7) x N=1 at
  identical total bytes;
* the kill leg — one reader is SIGKILLed mid-epoch; the client fails
  over (stream completes, byte-identical count), the fleet watcher
  relaunches the corpse — and the recovery counters
  (``ingest/reader_failovers_total``, ``ingest/reader_restarts_total``)
  land in the monitor JSONL;
* every reader actually served traffic (per-reader ``ingest_pull``
  spans in the monitor JSONL).

Usage:
    python tools/bench_ingest.py                    # full, ~16k samples
    python tools/bench_ingest.py --smoke            # preflight gate
    python tools/bench_ingest.py --readers 4 --trainers 4
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def build_tree(n_samples: int, store: int, shard_size: int,
               seed: int = 0) -> str:
    """A real shard tree of random uint8 images in a temp dir."""
    from theanompi_tpu.data.imagenet import prepare_imagenet_shards

    d = tempfile.mkdtemp(prefix="bench_ingest_")
    rng = np.random.default_rng(seed)
    # write in slabs so the bench never holds the whole set in RAM
    slab = max(shard_size, 2048)
    offset = 0
    while offset < n_samples:
        n = min(slab, n_samples - offset)
        imgs = rng.integers(0, 255, size=(n, store, store, 3),
                            dtype=np.uint8)
        labels = rng.integers(0, 1000, size=n).astype(np.int64)
        prepare_imagenet_shards(
            imgs, labels, d, prefix=f"train_{offset:07d}",
            shard_size=shard_size)
        offset += n
    return d


def trainer_worker(args) -> int:
    """``--worker`` mode: ONE trainer process driving one epoch
    stream — real trainers are separate processes (each owns its GIL
    and its pipelined fetch loop), so the parent measures the fleet,
    not a single client process's ceiling.  Protocol: warm pass,
    print READY, wait for GO on stdin (so all workers' timed windows
    overlap), timed pass, print one JSON line."""
    from theanompi_tpu.data.imagenet import ImageNet_data
    from theanompi_tpu.ingest.client import RemoteBatchSource

    if os.environ.get("THEANOMPI_TPU_INGEST_DEBUG_DUMP"):
        import faulthandler

        faulthandler.dump_traceback_later(
            float(os.environ["THEANOMPI_TPU_INGEST_DEBUG_DUMP"]),
            exit=True)
    ds = ImageNet_data(data_dir=args.data_dir, crop=args.store, seed=0,
                       augment_on_device=True)
    addrs = args.worker_addrs.split(",")

    def one_pass():
        n = imgs = nbytes = 0
        t0 = time.monotonic()
        with RemoteBatchSource(addrs, data=ds, epoch=0,
                               global_batch=args.batch,
                               rank=args.worker_rank,
                               size=args.worker_size,
                               depth=args.depth) as src:
            for x, y in src:
                n += 1
                imgs += len(y)
                nbytes += x.nbytes + y.nbytes
        return {"batches": n, "images": imgs, "bytes": nbytes,
                "wall_s": time.monotonic() - t0}

    one_pass()  # warm: page cache + codepaths
    print("READY", flush=True)
    if sys.stdin.readline().strip() != "GO":
        return 1
    print(json.dumps(one_pass()), flush=True)
    return 0


def drive_trainers(addrs: list[str], data_dir: str, t_count: int,
                   batch: int, store: int, depth: int) -> dict:
    """T trainer PROCESSES consuming their epoch streams concurrently
    (ready/go barrier so the timed windows overlap); aggregate img/s
    = total images / the longest worker wall.  The per-stream byte
    totals double as the identical-bytes cross-check between fleet
    sizes."""
    import subprocess

    procs = []
    for t in range(t_count):
        cmd = [sys.executable, os.path.abspath(__file__),
               "--worker-rank", str(t), "--worker-size", str(t_count),
               "--worker-addrs", ",".join(addrs),
               "--data-dir", data_dir, "--batch", str(batch),
               "--store", str(store), "--depth", str(depth)]
        procs.append(subprocess.Popen(
            cmd, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True, env=dict(os.environ)))
    try:
        for p in procs:
            line = p.stdout.readline().strip()
            if line != "READY":
                raise RuntimeError(
                    f"trainer worker failed before READY: {line!r} "
                    f"(rc={p.poll()})")
        for p in procs:
            p.stdin.write("GO\n")
            p.stdin.flush()
        outs = []
        for p in procs:
            outs.append(json.loads(p.stdout.readline()))
            p.stdin.close()
        for p in procs:
            p.wait(timeout=60)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    wall = max(o["wall_s"] for o in outs)
    return {"wall_s": round(wall, 3),
            "batches": sum(o["batches"] for o in outs),
            "images": sum(o["images"] for o in outs),
            "bytes": sum(o["bytes"] for o in outs),
            "agg_img_s": round(sum(o["images"] for o in outs) / wall,
                               1)}


def shm_compare_leg(samples: int = 8192, store: int = 96,
                    shard_size: int = 512, batch: int = 256,
                    depth: int = 4) -> dict:
    """Ingest plane of the shared-memory-lane comparison (ISSUE 20):
    in-band wire v2 vs the shm lane over the SAME committed workload —
    identical shard tree, epoch permutation and batch schedule, so the
    delivered streams are sha256-checked byte-identical across legs.
    Each leg gets a FRESH reader process (no negotiated lane state
    leaks between legs); the parent consumes the stream directly so
    the client-side lane counters land in the caller's monitor
    session, which the caller owns (``monitor.registry()`` is
    process-global).  Returns the ingest plane doc for
    ``BENCH_shm_smoke.json``."""
    import hashlib

    from theanompi_tpu import monitor
    from theanompi_tpu.data.imagenet import ImageNet_data
    from theanompi_tpu.ingest.client import RemoteBatchSource
    from theanompi_tpu.ingest.fleet import IngestProcessGroup
    from theanompi_tpu.parallel import shm

    data_dir = build_tree(samples, store, shard_size)
    pre_segments = set(shm.segment_names())
    prior = os.environ.get("THEANOMPI_TPU_WIRE_SHM")
    reg = monitor.registry()
    val = lambda name, **lb: reg.value(name, **lb) or 0.0
    legs: dict[str, dict] = {}
    try:
        dataset = ImageNet_data(data_dir=data_dir, crop=store, seed=0,
                                augment_on_device=True)

        def hash_pass(addrs: list[str]) -> str:
            """Warm pass doubling as the identity proof: sha256 over
            every delivered byte — the same epoch-1 stream the timed
            pass re-consumes (identical permutation + schedule)."""
            digest = hashlib.sha256()
            with RemoteBatchSource(addrs, data=dataset, epoch=1,
                                   global_batch=batch,
                                   depth=depth) as src:
                for x, y in src:
                    digest.update(x.tobytes())
                    digest.update(y.tobytes())
            return digest.hexdigest()

        def timed_pass(addrs: list[str]) -> dict:
            """Throughput pass: every byte is still READ (a training
            step consumes the whole batch) via a cheap reduction, but
            no cryptographic hash dilutes the transport difference —
            the sums double as a secondary cross-leg identity check."""
            images = nbytes = batches = 0
            checksum = 0
            t0 = time.monotonic()
            with RemoteBatchSource(addrs, data=dataset, epoch=1,
                                   global_batch=batch,
                                   depth=depth) as src:
                for x, y in src:
                    checksum += int(x.sum(dtype=np.int64))
                    checksum += int(y.sum(dtype=np.int64))
                    batches += 1
                    images += len(y)
                    nbytes += x.nbytes + y.nbytes
            wall = time.monotonic() - t0
            return {"wall_s": round(wall, 3), "batches": batches,
                    "images": images, "bytes": nbytes,
                    "img_s": round(images / wall, 1),
                    "checksum": checksum}

        for name, lane in (("in_band", "0"), ("shm", "1")):
            # the reader subprocess inherits the toggle; the parent
            # client reads it at hello time — both sides of the leg
            # negotiate (or never offer) the lane consistently
            os.environ["THEANOMPI_TPU_WIRE_SHM"] = lane
            oob0 = val("shm/oob_bytes_total", dir="recv")
            grants0 = val("shm/grants_total", role="client")
            group = IngestProcessGroup(1, data_dir, seed=0,
                                       coordinator=False,
                                       max_restarts=1)
            try:
                addrs = group.reader_addresses
                sha = hash_pass(addrs)  # warm + identity evidence
                r = timed_pass(addrs)
                r["sha256"] = sha
            finally:
                group.stop()
            r["oob_bytes_recv"] = int(
                val("shm/oob_bytes_total", dir="recv") - oob0)
            r["shm_grants"] = int(
                val("shm/grants_total", role="client") - grants0)
            legs[name] = r
            print(f"[bench_ingest] shm-compare {name}: "
                  f"{r['img_s']:.0f} img/s, "
                  f"{r['oob_bytes_recv']/1e6:.1f} MB out-of-band",
                  flush=True)
    finally:
        if prior is None:
            os.environ.pop("THEANOMPI_TPU_WIRE_SHM", None)
        else:
            os.environ["THEANOMPI_TPU_WIRE_SHM"] = prior
        shutil.rmtree(data_dir, ignore_errors=True)
    shm.sweep_orphans()
    leaked = [n for n in shm.segment_names() if n not in pre_segments]
    ratio = legs["shm"]["img_s"] / legs["in_band"]["img_s"]
    return {
        "plane": "ingest",
        "samples": samples, "store_px": store, "batch": batch,
        "depth": depth,
        "legs": legs,
        "img_s_ratio_shm_over_in_band": round(ratio, 3),
        "byte_identical": (legs["shm"]["sha256"]
                           == legs["in_band"]["sha256"]
                           and legs["shm"]["checksum"]
                           == legs["in_band"]["checksum"]),
        # payload bytes that left the socket path entirely (the
        # receiver maps them instead of copying them off the wire)
        "socket_bytes_saved": legs["shm"]["oob_bytes_recv"],
        "leaked_segments": len(leaked),
    }


def shm_evidence(monitor_dir: str | None, since: float = 0.0) -> dict:
    """Scan every metrics JSONL in ``monitor_dir`` written after
    ``since`` for shared-memory-lane evidence.  Subprocess roles
    (readers, shards, prefill/decode replicas) run their OWN monitor
    sessions writing sibling ``metrics_*.jsonl`` files into the shared
    dir, so the parent's snapshot alone never shows the server side of
    the lane — this aggregates both sides.  Counter snapshots are
    cumulative, so per (file, name, labels) the LAST value wins."""
    grants = 0.0
    oob = 0.0
    if not monitor_dir or not os.path.isdir(monitor_dir):
        return {"grants": 0, "oob_bytes": 0}
    for fn in sorted(os.listdir(monitor_dir)):
        path = os.path.join(monitor_dir, fn)
        if not fn.endswith(".jsonl"):
            continue
        try:
            if os.path.getmtime(path) < since:
                continue
            last: dict[str, float] = {}
            with open(path) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    name = rec.get("name")
                    if name in ("shm/grants_total",
                                "shm/oob_bytes_total"):
                        key = f"{name}|{sorted((rec.get('labels') or {}).items())}"
                        last[key] = float(rec.get("value") or 0.0)
            for key, v in last.items():
                if key.startswith("shm/grants_total"):
                    grants += v
                else:
                    oob += v
        except OSError:
            continue
    return {"grants": int(grants), "oob_bytes": int(oob)}


def run_shm_compare(args) -> int:
    """``--shm-compare`` mode: the standalone ingest shm leg —
    in-band vs lane over the identical stream, fresh reader process
    per leg; with ``--smoke`` asserts the >= ``--shm-bar`` img/s
    lift, byte identity, lane evidence, and zero leaked segments."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    os.environ.setdefault("THEANOMPI_TPU_SERVICE_KEY", "bench-ingest")
    os.environ.setdefault(
        "THEANOMPI_TPU_MONITOR",
        os.path.join(REPO, "artifacts", "bench_ingest_monitor"))

    from theanompi_tpu import monitor

    n_samples = args.samples or (8192 if args.smoke else 16384)
    # the lane targets payload-dominated batches (pixels >> skeleton);
    # the default 64-image batch is a latency workload, not this one
    batch = max(args.batch, 256)
    with monitor.session():
        doc = shm_compare_leg(n_samples, args.store, args.shard_size,
                              batch, args.depth)
    out_doc = {"bench": "ingest_shm_lane",
               "backend": jax.default_backend(), **doc}
    tag = args.tag or "ingest_shm"
    path = args.out or os.path.join(REPO, "artifacts",
                                    f"BENCH_{tag}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out_doc, f, indent=1)
    print(f"[bench_ingest] wrote {path} (shm "
          f"{doc['img_s_ratio_shm_over_in_band']:.2f}x in-band img/s)",
          flush=True)
    if not args.smoke:
        return 0
    ok = True
    if not doc["byte_identical"]:
        print("[bench_ingest] FAIL: shm leg delivered different bytes "
              "than the in-band leg", file=sys.stderr)
        ok = False
    if doc["img_s_ratio_shm_over_in_band"] < args.shm_bar:
        print(f"[bench_ingest] FAIL: shm img/s "
              f"{doc['img_s_ratio_shm_over_in_band']:.2f}x in-band < "
              f"{args.shm_bar}x bar", file=sys.stderr)
        ok = False
    if doc["legs"]["shm"]["oob_bytes_recv"] <= 0 \
            or doc["legs"]["shm"]["shm_grants"] < 1:
        print("[bench_ingest] FAIL: shm leg shows no lane traffic "
              f"({doc['legs']['shm']})", file=sys.stderr)
        ok = False
    if doc["legs"]["in_band"]["oob_bytes_recv"] != 0:
        print("[bench_ingest] FAIL: in-band leg leaked lane traffic "
              f"({doc['legs']['in_band']})", file=sys.stderr)
        ok = False
    if doc["leaked_segments"]:
        print(f"[bench_ingest] FAIL: {doc['leaked_segments']} shm "
              "segment(s) leaked after the legs", file=sys.stderr)
        ok = False
    print(f"[bench_ingest] shm-compare {'PASS' if ok else 'FAIL'}",
          flush=True)
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--readers", type=int, default=2, metavar="N")
    ap.add_argument("--trainers", type=int, default=4, metavar="T",
                    help="trainer PROCESSES; demand must exceed one "
                         "reader's capacity or N=1 vs N=2 compares "
                         "two idle fleets")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--store", type=int, default=None,
                    help="stored image side (uint8 HxWx3); default 64, "
                         "96 under --shm-compare (payload-dominated "
                         "batches are the lane's target workload)")
    ap.add_argument("--samples", type=int, default=None,
                    help="dataset size (default 65536; 32768 in "
                         "--smoke)")
    ap.add_argument("--shard-size", type=int, default=512)
    ap.add_argument("--depth", type=int, default=6,
                    help="per-trainer pipelined pulls")
    ap.add_argument("--data-dir", default=None,
                    help="existing shard tree (default: build a "
                         "synthetic one in a temp dir)")
    ap.add_argument("--scale-bar", type=float, default=1.7,
                    help="--smoke: required N=2/N=1 aggregate ratio")
    ap.add_argument("--out", default=None)
    ap.add_argument("--tag", default=None)
    ap.add_argument("--shm-compare", action="store_true",
                    help="shared-memory-lane leg (ISSUE 20): in-band "
                         "vs shm over the identical stream, one fresh "
                         "reader process per leg, sha256 byte-identity "
                         "checked; with --smoke asserts the --shm-bar "
                         "img/s lift + lane evidence + zero leaked "
                         "segments")
    ap.add_argument("--shm-bar", type=float, default=1.3,
                    help="--shm-compare --smoke: required shm/in-band "
                         "aggregate img/s ratio")
    ap.add_argument("--smoke", action="store_true",
                    help="preflight gate: assert the scaling bar, the "
                         "kill-recovery leg, and the monitor evidence; "
                         "exit 1 on any miss")
    # internal: one trainer process of drive_trainers' barrier fleet
    ap.add_argument("--worker-rank", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--worker-size", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--worker-addrs", default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.store is None:
        args.store = 96 if args.shm_compare else 64
    if args.worker_rank is not None:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        return trainer_worker(args)
    if args.shm_compare:
        return run_shm_compare(args)

    # ingest is a host-plane bench: numpy + sockets; keep jax off any
    # real accelerator in every process of the fleet
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    os.environ.setdefault("THEANOMPI_TPU_SERVICE_KEY", "bench-ingest")
    os.environ.setdefault(
        "THEANOMPI_TPU_MONITOR",
        os.path.join(REPO, "artifacts", "bench_ingest_monitor"))

    from theanompi_tpu import monitor
    from theanompi_tpu.data.imagenet import ImageNet_data
    from theanompi_tpu.ingest.fleet import IngestProcessGroup

    n_samples = args.samples or (32768 if args.smoke else 65536)
    own_tree = args.data_dir is None
    data_dir = args.data_dir or build_tree(n_samples, args.store,
                                           args.shard_size)
    dataset = ImageNet_data(data_dir=data_dir, crop=args.store,
                            seed=0, augment_on_device=True)
    print(f"[bench_ingest] tree: {dataset.n_train} samples x "
          f"{args.store}px uint8, {len(dataset.train_files)} "
          f"files; {args.trainers} trainer process(es), batch "
          f"{args.batch}, depth {args.depth}", flush=True)

    modes = []
    kill = None
    t_start = time.time()
    try:
        with monitor.session():
            for n_readers in ([1, args.readers]
                              if args.readers > 1 else [1]):
                group = IngestProcessGroup(
                    n_readers, data_dir, seed=0, coordinator=False,
                    max_restarts=2)
                try:
                    addrs = group.reader_addresses
                    # workers warm their own pass before the barrier,
                    # so both fleet sizes measure warm page cache
                    r = drive_trainers(addrs, data_dir, args.trainers,
                                       args.batch, args.store,
                                       args.depth)
                    r["readers"] = n_readers
                    r["served_per_reader"] = reader_served(addrs)
                    modes.append(r)
                    print(f"[bench_ingest] N={n_readers}: "
                          f"{r['agg_img_s']:.0f} img/s aggregate, "
                          f"{r['bytes']/1e6:.1f} MB in "
                          f"{r['wall_s']:.2f}s", flush=True)
                    if args.smoke and n_readers > 1:
                        kill = kill_leg(group, dataset, args)
                finally:
                    group.stop()
            snapshot_path = monitor.flush()
    finally:
        if own_tree:
            shutil.rmtree(data_dir, ignore_errors=True)

    n1 = next(m for m in modes if m["readers"] == 1)
    nk = modes[-1]
    scaling = (nk["agg_img_s"] / n1["agg_img_s"]
               if nk is not n1 else 1.0)
    out_doc = {
        "bench": "ingest_fleet",
        "backend": jax.default_backend(),
        "n_samples": dataset.n_train,
        "store_px": args.store,
        "batch": args.batch,
        "trainers": args.trainers,
        "depth": args.depth,
        "modes": modes,
        "aggregate_scaling_vs_n1": round(scaling, 3),
        "identical_total_bytes": n1["bytes"] == nk["bytes"],
        "kill_leg": kill,
    }
    tag = args.tag or ("smoke" if args.smoke
                       else f"n{args.readers}t{args.trainers}")
    path = args.out or os.path.join(REPO, "artifacts",
                                    f"BENCH_ingest_{tag}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out_doc, f, indent=1)
    print(f"[bench_ingest] wrote {path} (N={nk['readers']} aggregate "
          f"{scaling:.2f}x N=1)", flush=True)

    if not args.smoke:
        return 0
    return smoke_verdict(out_doc, args, snapshot_path, since=t_start)


def reader_served(addrs: list[str]) -> list[int]:
    """Per-reader served-batch counters (the 'every reader actually
    served its range' evidence, straight from the reader processes)."""
    from theanompi_tpu.parallel.service import ServiceClient

    out = []
    for addr in addrs:
        c = ServiceClient(addr)
        try:
            out.append(int(c.call("stats")["served"]))
        finally:
            c.close()
    return out


def kill_leg(group, ds, args) -> dict:
    """Mid-epoch reader death: SIGKILL reader 0, the client stream
    must complete byte-identically over the survivor while the
    watcher relaunches the corpse."""
    from theanompi_tpu.ingest.client import RemoteBatchSource
    expected = ds.n_train_batches_for(1, args.batch, 0, 1)
    got = 0
    with RemoteBatchSource(group.reader_addresses, data=ds, epoch=1,
                           global_batch=args.batch, depth=args.depth
                           ) as src:
        it = iter(src)
        for _ in range(3):
            next(it)
            got += 1
        group.kill_reader(0)
        print("[bench_ingest] kill leg: reader 0 SIGKILLed mid-epoch",
              flush=True)
        for _ in it:
            got += 1
    group.wait_restarted(0)
    restarts = group.restart_counts()
    out = {"expected_batches": expected, "completed_batches": got,
           "reader0_restarts": restarts.get(0, 0),
           "recovered": got == expected and restarts.get(0, 0) >= 1}
    print(f"[bench_ingest] kill leg: {out}", flush=True)
    return out


def smoke_verdict(doc: dict, args, snapshot_path: str | None,
                  since: float = 0.0) -> int:
    ok = True
    if args.readers < 2:
        print("[bench_ingest] FAIL: smoke needs --readers >= 2",
              file=sys.stderr)
        ok = False
    if not doc["identical_total_bytes"]:
        print("[bench_ingest] FAIL: fleet sizes consumed different "
              "byte totals — the comparison is not like-for-like",
              file=sys.stderr)
        ok = False
    if doc["aggregate_scaling_vs_n1"] < args.scale_bar:
        print(f"[bench_ingest] FAIL: N={args.readers} aggregate "
              f"{doc['aggregate_scaling_vs_n1']:.2f}x N=1 < "
              f"{args.scale_bar}x bar", file=sys.stderr)
        ok = False
    if not (doc["kill_leg"] or {}).get("recovered"):
        print("[bench_ingest] FAIL: the kill-one-reader leg did not "
              "recover", file=sys.stderr)
        ok = False
    nk = doc["modes"][-1]
    if not all(s > 0 for s in nk.get("served_per_reader", [])):
        print(f"[bench_ingest] FAIL: a reader of the N="
              f"{nk['readers']} fleet served nothing "
              f"({nk.get('served_per_reader')})", file=sys.stderr)
        ok = False
    # monitor JSONL evidence: per-reader serving spans + the recovery
    # counters (the operator-facing proof, like the shard smoke's)
    served, names = set(), set()
    if snapshot_path and os.path.exists(snapshot_path):
        with open(snapshot_path) as f:
            for line in f:
                rec = json.loads(line)
                names.add(rec.get("name"))
                if (rec.get("name") == "span_ms"
                        and rec.get("labels", {}).get("name")
                        == "ingest_pull" and rec.get("count", 0) > 0):
                    served.add(rec["labels"].get("reader"))
    if len(served) < args.readers:
        print(f"[bench_ingest] FAIL: ingest_pull spans name only "
              f"{len(served)} reader(s) ({sorted(served)}) in the "
              f"monitor JSONL ({snapshot_path}); expected "
              f"{args.readers}", file=sys.stderr)
        ok = False
    for needed in ("ingest/reader_failovers_total",
                   "ingest/reader_restarts_total"):
        if needed not in names:
            print(f"[bench_ingest] FAIL: {needed} missing from the "
                  f"monitor JSONL ({snapshot_path})", file=sys.stderr)
            ok = False
    # shm-lane evidence (ISSUE 20): same-host readers must have
    # granted the lane and shipped batch pixels out-of-band.  The
    # trainer workers run no monitor session, so the proof lives in
    # the READER processes' sibling metrics files — scan the dir.
    from theanompi_tpu.parallel import shm

    if shm.enabled() and shm.available():
        mon_dir = os.path.dirname(snapshot_path) if snapshot_path \
            else os.environ.get("THEANOMPI_TPU_MONITOR")
        ev = shm_evidence(mon_dir, since=since)
        if ev["grants"] < 1 or ev["oob_bytes"] <= 0:
            print(f"[bench_ingest] FAIL: no shm-lane evidence in the "
                  f"monitor dir ({mon_dir}): {ev} — same-host readers "
                  "should have granted the lane and shipped batches "
                  "out-of-band", file=sys.stderr)
            ok = False
    print(f"[bench_ingest] smoke {'PASS' if ok else 'FAIL'}",
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
