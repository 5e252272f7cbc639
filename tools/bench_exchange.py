"""Wire-protocol exchange benchmark — v1 pickle vs v2 framed, over
REAL sockets (ISSUE 5 measurement leg).

Drives a ResNet-50-sized (~25.5M param) parameter tree through the
param service's EASGD exchange in every (protocol, compression, dtype)
mode and reports, per mode:

* **bytes/exchange** — exact serialized request + reply bytes.  v2
  modes are measured by encoding the same frames the client sends
  (``wire.encode_frame`` is deterministic); v1 is measured by running
  the SAME reduction ``multiprocessing.connection.Connection.send``
  uses (``ForkingPickler.dumps``) on the request/reply tuples.
* **wall ms/exchange** — client-observed round-trip over a localhost
  TCP socket (serialize + socket + server elastic merge + reply).
  Localhost removes network bandwidth from the picture, so this is
  the floor the serialization layer itself sets; on a real DCN link
  the byte cut converts to time at the link's rate.

Emits ``artifacts/BENCH_wire_<tag>.json``.  ``--smoke`` is the
preflight gate: asserts v2-framed beats v1-pickle on bytes/exchange
and that the wire compression-ratio gauge landed in the monitor
JSONL (exit 1 otherwise).

Usage:
    python tools/bench_exchange.py                  # full, ~25M params
    python tools/bench_exchange.py --smoke          # preflight gate
    python tools/bench_exchange.py --params 1e6 --exchanges 5
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: (protocol, compression, dtype) — v1 has no negotiated options
MODES = (
    ("v1", "none", "f32"),
    ("v2", "none", "f32"),
    ("v2", "zlib", "f32"),
    ("v2", "none", "bf16"),
    ("v2", "zlib", "bf16"),
)


class FlagConflict(SystemExit):
    """Typed refusal for mutually exclusive bench legs (``--buckets``
    vs ``--shards``): the bucket leg drives the SPMD in-step exchange
    on a device mesh, the shard leg drives the wire exchange against
    real shard processes — silently ignoring one flag would report a
    number the caller did not ask for.  Exits 2 like an argparse
    usage error."""

    def __init__(self, msg: str):
        print(f"[bench_exchange] ERROR: {msg}", file=sys.stderr)
        super().__init__(2)


def resnet50_like_tree(target_params: int, seed: int = 0) -> dict:
    """A parameter tree with ResNet-50's leaf-size distribution
    (conv kernels from (7,7,3,64) up to (1,1,1024,2048), BN vectors,
    one big FC) scaled to ~``target_params`` total — the leaf-count /
    leaf-size mix is what exercises the per-buffer framing overhead
    realistically, not just one flat 100 MB blob."""
    rng = np.random.default_rng(seed)
    shapes: list[tuple[int, ...]] = [(7, 7, 3, 64)]
    stages = ((64, 64, 3), (256, 128, 4), (512, 256, 6), (1024, 512, 3))
    for c_in, c_mid, reps in stages:
        for r in range(reps):
            cin = c_in if r == 0 else c_mid * 4
            shapes += [(1, 1, cin, c_mid), (3, 3, c_mid, c_mid),
                       (1, 1, c_mid, c_mid * 4)]
            for width in (c_mid, c_mid, c_mid * 4):
                shapes += [(width,)] * 4      # BN scale/bias/mean/var
    shapes.append((2048, 1000))
    shapes.append((1000,))
    base_total = sum(int(np.prod(s)) for s in shapes)
    scale = max(1, round(target_params / base_total))
    tree = {}
    for i, s in enumerate(shapes):
        # scale by repeating leaves, preserving the size distribution
        for k in range(scale if len(s) > 1 else 1):
            tree[f"leaf_{i:03d}_{k}"] = rng.standard_normal(
                s).astype(np.float32) * 0.05
    return tree


def tree_params(tree: dict) -> int:
    return sum(int(v.size) for v in tree.values())


def tree_nbytes(tree: dict) -> int:
    return sum(int(v.nbytes) for v in tree.values())


def _pickle_len(obj) -> int:
    """Bytes ``Connection.send`` would write for ``obj`` (v1 wire)."""
    import io
    from multiprocessing.reduction import ForkingPickler

    buf = io.BytesIO()
    ForkingPickler(buf, -1).dump(obj)
    return buf.getbuffer().nbytes


def measure_mode(addr: str, protocol: str, compression: str, dtype: str,
                 tree: dict, n_exchanges: int) -> dict:
    from theanompi_tpu.parallel import wire
    from theanompi_tpu.parallel.service import RemoteEASGD

    opts = wire.WireOptions(compression=compression, dtype=dtype)
    sid = f"bench-{protocol}-{compression}-{dtype}"
    srv = RemoteEASGD.__new__(RemoteEASGD)
    # RemoteEASGD.__init__ ships the init tree too; time only the
    # steady-state exchanges, so construct with the real init path
    t0 = time.monotonic()
    RemoteEASGD.__init__(srv, addr, tree, alpha=0.5, session_id=sid)
    # force the requested protocol AFTER construction knobs: the env
    # route would leak across modes
    if protocol == "v1" and srv.wire_protocol != "v1":
        srv.close()
        from theanompi_tpu.parallel.service import RemoteEASGD as _R

        os.environ["THEANOMPI_TPU_WIRE_PROTOCOL"] = "v1"
        try:
            srv = _R(addr, tree, alpha=0.5, session_id=sid + "1")
        finally:
            os.environ.pop("THEANOMPI_TPU_WIRE_PROTOCOL", None)
    init_s = time.monotonic() - t0
    assert srv.wire_protocol == protocol, (srv.wire_protocol, protocol)

    # exact per-exchange wire bytes (request and reply carry the same
    # tree shape for the elastic exchange)
    request = ("easgd_exchange", sid, tree)
    reply = ("ok", tree)
    if protocol == "v2":
        head, bufs, st_req = wire.encode_frame(request, opts)
        _, _, st_rep = wire.encode_frame(reply, opts)
        bytes_sent, bytes_recv = st_req.post_bytes, st_rep.post_bytes
        pre_bytes = st_req.pre_bytes
    else:
        bytes_sent = _pickle_len(request)
        bytes_recv = _pickle_len(reply)
        pre_bytes = bytes_sent

    walls = []
    for i in range(n_exchanges):
        t0 = time.monotonic()
        out = srv.exchange(tree)
        walls.append((time.monotonic() - t0) * 1e3)
    # sanity: the arithmetic survived the transport
    k = next(iter(tree))
    assert np.isfinite(out[k]).all()
    srv.close()
    total = bytes_sent + bytes_recv
    return {
        "protocol": protocol, "compression": compression, "dtype": dtype,
        "bytes_sent_per_exchange": bytes_sent,
        "bytes_recv_per_exchange": bytes_recv,
        "bytes_per_exchange": total,
        "pre_bytes": pre_bytes,
        "wire_ratio": round(total / (2 * pre_bytes), 4),
        "n_exchanges": n_exchanges,
        "wall_ms_mean": round(float(np.mean(walls)), 2),
        "wall_ms_min": round(float(np.min(walls)), 2),
        "init_s": round(init_s, 3),
    }


def run_sharded(args) -> int:
    """``--shards K`` mode (ISSUE 8): drive the same parameter tree
    against K REAL shard processes via the shard router and compare
    per-shard and aggregate bytes/wall against K=1.  The aggregate
    exchange scatters K concurrent sub-exchanges (each shard process
    serializes + merges its leaf range in parallel), so aggregate wall
    should beat the single-center round trip on a multi-core box.

    ``--smoke`` additionally kills shard 0 mid-run, waits for the
    supervised relaunch, and asserts (a) both shards served traffic,
    (b) the kill recovered (exchange succeeds, reconnect + restart
    events land in the monitor JSONL) — the preflight 2-shard gate."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    os.environ.setdefault("THEANOMPI_TPU_SERVICE_KEY", "bench-exchange")
    os.environ.setdefault(
        "THEANOMPI_TPU_MONITOR",
        os.path.join(REPO, "artifacts", "bench_exchange_monitor"))

    from theanompi_tpu import monitor
    from theanompi_tpu.parallel import wire
    from theanompi_tpu.parallel.shards import (
        ShardProcessGroup,
        ShardedEASGD,
    )

    k = int(args.shards)
    n_exchanges = max(3, args.exchanges)
    tree = resnet50_like_tree(int(args.params))
    n_params = tree_params(tree)
    print(f"[bench_exchange] shard mode: {n_params/1e6:.1f}M params, "
          f"{len(tree)} leaves, {tree_nbytes(tree)/1e6:.1f} MB f32, "
          f"K in (1, {k})", flush=True)
    opts = wire.WireOptions.from_env()

    modes = []
    kill_recovered = None
    with monitor.session():
        for n_shards in ([1, k] if k > 1 else [1]):
            group = ShardProcessGroup(n_shards, max_restarts=2)
            try:
                sid = f"bench-shards-{n_shards}"
                srv = ShardedEASGD(group.addresses, tree, alpha=0.5,
                                   session_id=sid)
                # exact per-shard wire bytes: encode the same frames
                # the router's sub-exchanges send/receive
                per_shard = []
                flat, _ = jax.tree.flatten(tree)
                for i, (lo, hi) in enumerate(srv._plan.ranges):
                    sub = flat[lo:hi]
                    _, _, st_req = wire.encode_frame(
                        ("shard_exchange", sid, sub, "cid", 1), opts)
                    _, _, st_rep = wire.encode_frame(("ok", sub), opts)
                    per_shard.append({
                        "shard": i, "n_leaves": hi - lo,
                        "bytes_sent_per_exchange": st_req.post_bytes,
                        "bytes_recv_per_exchange": st_rep.post_bytes,
                    })
                # probe rounds: each shard timed alone (sequential) so
                # the wall is attributable to THAT shard; repeated so
                # the per-shard tail (p50/p99) is reported alongside
                # the aggregate concurrent wall — a single probe hid a
                # slow shard entirely (ISSUE 13 satellite fix)
                probe_rounds = max(5, n_exchanges)
                probes = [[] for _ in srv._plan.ranges]
                # one untimed warmup round first: the session's first
                # tagged exchange pays one-off jit/session costs that
                # would otherwise masquerade as the p99 tail
                for r in range(probe_rounds + 1):
                    seq = srv._next_seq()
                    for i, (lo, hi) in enumerate(srv._plan.ranges):
                        t0 = time.monotonic()
                        srv._shard_clients[i].exchange_tagged(
                            flat[lo:hi], srv._client_id, seq)
                        if r > 0:
                            probes[i].append(
                                (time.monotonic() - t0) * 1e3)
                for i, ws in enumerate(probes):
                    per_shard[i]["probe_wall_ms"] = round(ws[0], 2)
                    per_shard[i]["probe_wall_p50_ms"] = round(
                        float(np.percentile(ws, 50)), 2)
                    per_shard[i]["probe_wall_p99_ms"] = round(
                        float(np.percentile(ws, 99)), 2)
                    per_shard[i]["probe_rounds"] = probe_rounds
                walls = []
                for _ in range(n_exchanges):
                    t0 = time.monotonic()
                    out = srv.exchange(tree)
                    walls.append((time.monotonic() - t0) * 1e3)
                assert np.isfinite(out[next(iter(tree))]).all()
                if args.smoke and n_shards > 1:
                    # fault leg: hard-kill shard 0, let the group
                    # relaunch it, prove the router recovers (the
                    # per-shard rejoin re-seeds only shard 0's range)
                    group.kill_shard(0)
                    group.wait_restarted(0)
                    out = srv.exchange(tree)
                    kill_recovered = bool(
                        np.isfinite(out[next(iter(tree))]).all()
                        and group.restart_counts().get(0) == 1)
                    print(f"[bench_exchange] shard-0 kill recovered: "
                          f"{kill_recovered}", flush=True)
                srv.close()
                modes.append({
                    "shards": n_shards,
                    "n_exchanges": n_exchanges,
                    "wall_ms_mean": round(float(np.mean(walls)), 2),
                    "wall_ms_min": round(float(np.min(walls)), 2),
                    "bytes_per_exchange": sum(
                        p["bytes_sent_per_exchange"]
                        + p["bytes_recv_per_exchange"]
                        for p in per_shard),
                    "per_shard": per_shard,
                })
                print(f"[bench_exchange] K={n_shards}: "
                      f"{modes[-1]['wall_ms_mean']:.0f} ms mean, "
                      f"{modes[-1]['bytes_per_exchange']/1e6:.1f} "
                      "MB/exchange", flush=True)
            finally:
                group.stop()
        snapshot_path = monitor.flush()

    k1 = next(m for m in modes if m["shards"] == 1)
    kk = next(m for m in modes if m["shards"] == k)
    improvement = 1.0 - kk["wall_ms_mean"] / k1["wall_ms_mean"]
    out_doc = {
        "bench": "shard_exchange",
        "backend": jax.default_backend(),
        "n_params": n_params,
        "n_leaves": len(tree),
        "tree_mb_f32": round(tree_nbytes(tree) / 1e6, 2),
        "wire": {"compression": opts.compression, "dtype": opts.dtype},
        "modes": modes,
        "aggregate_wall_improvement_vs_k1": round(improvement, 4),
        "kill_recovered": kill_recovered,
    }
    tag = args.tag or ("shard_smoke" if args.smoke else f"shard_k{k}")
    path = args.out or os.path.join(REPO, "artifacts",
                                    f"BENCH_{tag}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out_doc, f, indent=1)
    print(f"[bench_exchange] wrote {path} (K={k} aggregate wall "
          f"{improvement:+.1%} vs K=1)", flush=True)

    if not args.smoke:
        return 0
    ok = True
    if k < 2:
        print("[bench_exchange] FAIL: shard smoke needs --shards >= 2",
              file=sys.stderr)
        ok = False
    if improvement <= 0:
        print(f"[bench_exchange] FAIL: K={k} aggregate wall "
              f"({kk['wall_ms_mean']} ms) does not improve on K=1 "
              f"({k1['wall_ms_mean']} ms)", file=sys.stderr)
        ok = False
    if kill_recovered is not True:
        print("[bench_exchange] FAIL: shard-0 kill did not recover",
              file=sys.stderr)
        ok = False
    # monitor JSONL: per-shard traffic (shard_exchange spans for every
    # shard) + the recovery events (client reconnect, shard relaunch)
    served, names = set(), set()
    shm_oob = 0.0
    if snapshot_path and os.path.exists(snapshot_path):
        with open(snapshot_path) as f:
            for line in f:
                rec = json.loads(line)
                names.add(rec.get("name"))
                if rec.get("name") == "shm/oob_bytes_total":
                    shm_oob = max(shm_oob,
                                  float(rec.get("value") or 0.0))
                if (rec.get("name") == "span_ms"
                        and rec.get("labels", {}).get("name")
                        == "shard_exchange" and rec.get("count", 0) > 0):
                    served.add(rec["labels"].get("worker"))
    missing_shards = {str(i) for i in range(k)} - served
    if missing_shards:
        print(f"[bench_exchange] FAIL: no shard_exchange spans for "
              f"shard(s) {sorted(missing_shards)} in the monitor JSONL "
              f"({snapshot_path})", file=sys.stderr)
        ok = False
    for needed in ("service/client_reconnects_total",
                   "service/shard_restarts_total"):
        if needed not in names:
            print(f"[bench_exchange] FAIL: {needed} missing from the "
                  f"monitor JSONL ({snapshot_path})", file=sys.stderr)
            ok = False
    # shm-lane evidence (ISSUE 20): a same-host shard fleet must have
    # granted the lane and shipped the big leaves out-of-band — the
    # client side of both counters lands in THIS process's snapshot
    from theanompi_tpu.parallel import shm

    if shm.enabled() and shm.available():
        if "shm/grants_total" not in names or shm_oob <= 0:
            print(f"[bench_exchange] FAIL: no shm-lane evidence in "
                  f"the monitor JSONL ({snapshot_path}): grants "
                  f"{'present' if 'shm/grants_total' in names else 'missing'}, "
                  f"oob_bytes {shm_oob:.0f} — same-host shards should "
                  "have granted the lane", file=sys.stderr)
            ok = False
    print(f"[bench_exchange] shard smoke {'PASS' if ok else 'FAIL'}",
          flush=True)
    return 0 if ok else 1


def run_shm_compare(args) -> int:
    """``--shm-compare`` (ISSUE 20): the shared-memory-lane
    comparison across the three same-host planes, one committed
    artifact (``artifacts/BENCH_shm_smoke.json``).

    Exchange plane: the full parameter tree against ONE real shard
    process — in-band wire v2 vs the negotiated shm lane, identical
    exchange schedule, every round's merged tree sha256-checked
    across legs, each leg against a FRESH server process.  The shm
    leg ends with the lane FORCE-DISABLED mid-run on the live
    client (the refusal recovery path: drop the lane, reconnect
    without an offer): the tail exchanges must stay byte-identical
    with ZERO out-of-band growth — the silent-fallback proof.  A
    separate kill leg SIGKILLs the server between an exchange and
    its piggybacked ack (so its reply segments are still leased),
    then asserts the dead peer's segments sweep to zero.

    Ingest and serving planes ride the sibling tools' legs
    (``bench_ingest.shm_compare_leg`` /
    ``bench_serving.shm_compare_leg``) so each plane's measurement
    lives next to its own bench.

    ``--smoke`` enforces the acceptance bars: >= 25% exchange wall
    cut, >= 1.3x ingest img/s, byte identity on every plane, lane
    evidence in the monitor registry, zero leaked segments after
    every leg including the kill leg."""
    import hashlib

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    os.environ.setdefault("THEANOMPI_TPU_SERVICE_KEY", "bench-exchange")
    os.environ.setdefault(
        "THEANOMPI_TPU_MONITOR",
        os.path.join(REPO, "artifacts", "bench_exchange_monitor"))

    from theanompi_tpu import monitor
    from theanompi_tpu.parallel import shm, wire
    from theanompi_tpu.parallel.shards import (
        ShardProcessGroup,
        ShardedEASGD,
    )

    if not (shm.enabled() and shm.available()):
        print("[bench_exchange] FAIL: the shm lane is disabled or "
              "/dev/shm is unavailable on this host", file=sys.stderr)
        return 1

    tree = resnet50_like_tree(int(args.params))
    n_params = tree_params(tree)
    n_exchanges = max(3, args.exchanges)
    tail = 2  # post-force-disable exchanges (the fallback proof)
    print(f"[bench_exchange] shm-compare: {n_params/1e6:.1f}M params, "
          f"{len(tree)} leaves, {tree_nbytes(tree)/1e6:.1f} MB f32, "
          f"{n_exchanges} timed + {tail} fallback exchanges/leg",
          flush=True)

    # exact in-band wire bytes (the copied-bytes ledger baseline):
    # the same frames the K=1 router sends/receives, no lane attached
    opts = wire.WireOptions.from_env()
    flat, _ = jax.tree.flatten(tree)
    _, _, st_req = wire.encode_frame(
        ("shard_exchange", "bench-shm", flat, "cid", 1), opts)
    _, _, st_rep = wire.encode_frame(("ok", flat), opts)
    wire_bytes = st_req.post_bytes + st_rep.post_bytes

    keys = sorted(tree)

    def tree_digest(t: dict) -> str:
        h = hashlib.sha256()
        for k in keys:
            h.update(np.asarray(t[k]).tobytes())
        return h.hexdigest()

    # lazy registry lookup: monitor.session() swaps in a FRESH
    # registry on activation, so a handle captured here would read
    # the stale pre-session one (and count nothing)
    val = lambda name, **lb: (
        monitor.registry().value(name, **lb) or 0.0)
    oob_total = lambda: (val("shm/oob_bytes_total", dir="send")
                         + val("shm/oob_bytes_total", dir="recv"))
    pre_segments = set(shm.segment_names())
    prior_lane = os.environ.get("THEANOMPI_TPU_WIRE_SHM")

    def exchange_leg(lane: str) -> dict:
        """One fresh-server leg: warm + timed + tail exchanges, every
        merged tree digested.  ``lane`` toggles the hello offer for
        BOTH sides (the shard subprocess inherits the environment)."""
        os.environ["THEANOMPI_TPU_WIRE_SHM"] = lane
        grants0 = val("shm/grants_total", role="client")
        oob0 = oob_total()
        digests: list[str] = []
        walls: list[float] = []
        group = ShardProcessGroup(1, max_restarts=1)
        try:
            srv = ShardedEASGD(group.addresses, tree, alpha=0.5,
                               session_id=f"bench-shm-{lane}")
            try:
                out = srv.exchange(tree)  # warm: jit + session setup
                digests.append(tree_digest(out))
                for _ in range(n_exchanges):
                    t0 = time.monotonic()
                    out = srv.exchange(tree)
                    walls.append((time.monotonic() - t0) * 1e3)
                    digests.append(tree_digest(out))
                oob_tail0 = oob_total()
                if lane == "1":
                    # force-disable mid-run on the LIVE client: the
                    # same degrade path a typed refusal takes — drop
                    # the lane, reconnect without an offer
                    for c in srv._shard_clients:
                        c._disable_shm()
                        if getattr(c, "_transport", None) is None:
                            try:
                                c._conn.close()
                            except OSError:
                                pass
                for _ in range(tail):
                    out = srv.exchange(tree)
                    digests.append(tree_digest(out))
                oob_tail_growth = oob_total() - oob_tail0
            finally:
                srv.close()
        finally:
            group.stop()
        oob = oob_total() - oob0
        leg = {
            "wall_ms_mean": round(float(np.mean(walls)), 2),
            "wall_ms_min": round(float(np.min(walls)), 2),
            "n_exchanges": n_exchanges,
            "digests": digests,
            "shm_grants": int(val("shm/grants_total", role="client")
                              - grants0),
            "oob_bytes": int(oob),
            "oob_bytes_per_exchange": int(oob / (n_exchanges + 1)),
            "oob_tail_growth": int(oob_tail_growth),
        }
        print(f"[bench_exchange] shm-compare "
              f"{'shm' if lane == '1' else 'in_band'}: "
              f"{leg['wall_ms_mean']:.0f} ms/exchange mean, "
              f"{leg['oob_bytes']/1e6:.1f} MB out-of-band", flush=True)
        return leg

    def kill_leg() -> dict:
        """SIGKILL the server while its reply segments are still
        leased (the ack rides the client's NEXT frame, which never
        comes), then prove the dead peer's segments sweep to zero."""
        os.environ["THEANOMPI_TPU_WIRE_SHM"] = "1"
        group = ShardProcessGroup(1, max_restarts=0)
        try:
            srv = ShardedEASGD(group.addresses, tree, alpha=0.5,
                               session_id="bench-shm-kill")
            try:
                srv.exchange(tree)
                srv.exchange(tree)
                orphans_before = len(
                    [n for n in shm.segment_names()
                     if n not in pre_segments])
                group.kill_shard(0)
            finally:
                try:
                    srv.close()
                except Exception:
                    pass
        finally:
            group.stop()
        shm.release_all()
        swept = shm.sweep_orphans()
        leaked = [n for n in shm.segment_names()
                  if n not in pre_segments]
        out = {"leased_at_kill": orphans_before,
               "swept": int(swept or 0),
               "leaked_after_sweep": len(leaked)}
        print(f"[bench_exchange] shm-compare kill leg: {out}",
              flush=True)
        return out

    planes: dict[str, dict] = {}
    with monitor.session():
        try:
            in_band = exchange_leg("0")
            lane = exchange_leg("1")
            kill = kill_leg()
        finally:
            if prior_lane is None:
                os.environ.pop("THEANOMPI_TPU_WIRE_SHM", None)
            else:
                os.environ["THEANOMPI_TPU_WIRE_SHM"] = prior_lane
        wall_cut = 1.0 - lane["wall_ms_mean"] / in_band["wall_ms_mean"]
        planes["exchange"] = {
            "plane": "exchange",
            "n_params": n_params,
            "wire_bytes_per_exchange_in_band": wire_bytes,
            "legs": {"in_band": in_band, "shm": lane},
            "wall_cut_shm_vs_in_band": round(wall_cut, 4),
            "byte_identical": in_band["digests"] == lane["digests"],
            # payload bytes that left the socket path entirely per
            # exchange (receiver maps instead of copying off the wire)
            "socket_bytes_saved_per_exchange":
                lane["oob_bytes_per_exchange"],
            "kill_leg": kill,
        }
        print(f"[bench_exchange] exchange plane: shm cuts "
              f"{wall_cut:.1%} of the in-band wall", flush=True)

        # sibling planes: same artifact, each leg owned by its bench
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import bench_ingest
        import bench_serving

        planes["ingest"] = bench_ingest.shm_compare_leg(
            samples=4096 if args.smoke else 8192)
        print(f"[bench_exchange] ingest plane: shm "
              f"{planes['ingest']['img_s_ratio_shm_over_in_band']:.2f}"
              "x in-band img/s", flush=True)
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            planes["serving"] = bench_serving.shm_compare_leg(td)
        print(f"[bench_exchange] serving plane: shm wall delta "
              f"{planes['serving']['wall_delta_pct']:+.1f}%",
              flush=True)

    leaked_final = [n for n in shm.segment_names()
                    if n not in pre_segments]
    # digests are leg-internal evidence; keep the artifact readable
    for leg in planes["exchange"]["legs"].values():
        leg.pop("digests", None)
    out_doc = {
        "bench": "shm_lane",
        "backend": jax.default_backend(),
        "n_params": n_params,
        "n_leaves": len(tree),
        "tree_mb_f32": round(tree_nbytes(tree) / 1e6, 2),
        "planes": planes,
        "leaked_segments_final": len(leaked_final),
    }
    tag = args.tag or "shm_smoke"
    path = args.out or os.path.join(REPO, "artifacts",
                                    f"BENCH_{tag}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out_doc, f, indent=1)
    print(f"[bench_exchange] wrote {path}", flush=True)

    if not args.smoke:
        return 0
    ok = True
    ex = planes["exchange"]
    if not ex["byte_identical"]:
        print("[bench_exchange] FAIL: shm exchange leg diverged from "
              "the in-band leg (byte identity)", file=sys.stderr)
        ok = False
    if ex["wall_cut_shm_vs_in_band"] < 0.25:
        print(f"[bench_exchange] FAIL: shm wall cut "
              f"{ex['wall_cut_shm_vs_in_band']:.1%} < 25%",
              file=sys.stderr)
        ok = False
    legs = ex["legs"]
    if legs["shm"]["shm_grants"] < 1 or legs["shm"]["oob_bytes"] <= 0:
        print("[bench_exchange] FAIL: shm leg shows no lane traffic "
              f"({legs['shm']})", file=sys.stderr)
        ok = False
    if legs["in_band"]["oob_bytes"] != 0 \
            or legs["in_band"]["shm_grants"] != 0:
        print("[bench_exchange] FAIL: in-band leg negotiated the lane "
              f"({legs['in_band']})", file=sys.stderr)
        ok = False
    if legs["shm"]["oob_tail_growth"] != 0:
        print("[bench_exchange] FAIL: out-of-band bytes grew after "
              "the mid-run force-disable — the fallback is not "
              "in-band", file=sys.stderr)
        ok = False
    if ex["kill_leg"]["leased_at_kill"] < 1:
        print("[bench_exchange] FAIL: kill leg found no leased "
              "segment at SIGKILL time — the leg proved nothing",
              file=sys.stderr)
        ok = False
    if ex["kill_leg"]["leaked_after_sweep"] != 0:
        print(f"[bench_exchange] FAIL: {ex['kill_leg']} — dead peer's "
              "segments survived the sweep", file=sys.stderr)
        ok = False
    ing = planes["ingest"]
    if not ing["byte_identical"]:
        print("[bench_exchange] FAIL: ingest shm leg delivered "
              "different bytes", file=sys.stderr)
        ok = False
    if ing["img_s_ratio_shm_over_in_band"] < 1.3:
        print(f"[bench_exchange] FAIL: ingest shm img/s "
              f"{ing['img_s_ratio_shm_over_in_band']:.2f}x < 1.3x",
              file=sys.stderr)
        ok = False
    srv_plane = planes["serving"]
    if not srv_plane["byte_identical"]:
        print("[bench_exchange] FAIL: serving shm leg delivered "
              "different page bytes", file=sys.stderr)
        ok = False
    if srv_plane["legs"]["shm"]["oob_bytes_recv"] <= 0:
        print("[bench_exchange] FAIL: serving shm leg shows no lane "
              "traffic", file=sys.stderr)
        ok = False
    if leaked_final:
        print(f"[bench_exchange] FAIL: {len(leaked_final)} shm "
              f"segment(s) leaked after all legs ({leaked_final})",
              file=sys.stderr)
        ok = False
    print(f"[bench_exchange] shm-compare smoke "
          f"{'PASS' if ok else 'FAIL'}", flush=True)
    return 0 if ok else 1


def lattice_tree(target_params: int, seed: int = 0,
                 grid_bits: int = 10) -> dict:
    """``resnet50_like_tree`` snapped to the exact-arithmetic f32
    lattice (integer multiples of 2**-grid_bits, magnitudes << 2**10):
    every sum/mean/elastic-pull the hierarchical plane computes stays
    exactly representable, so the trajectory pins compare BITWISE
    instead of hiding behind a tolerance — f32 associativity cannot
    blur what the aggregation math actually did.

    ``+ 0.0`` flushes the ``-0.0`` entries ``np.round`` mints for
    small negatives: IEEE cancellation yields ``+0.0`` while a
    summed-then-applied ``-0.0`` delta preserves the sign, so signed
    zeros would flip BYTES between the direct and aggregated paths at
    exactly-zero positions — numerically equal, bitwise noise."""
    grid = float(1 << grid_bits)
    return {k: (np.round(v * grid) / grid + 0.0).astype(np.float32)
            for k, v in resnet50_like_tree(target_params, seed).items()}


def run_hierarchy(args) -> int:
    """``--local-workers N`` mode (ISSUE 14): hierarchical intra-host
    aggregation (``parallel/aggregate.py``) against K REAL shard
    processes, vs N direct per-worker exchanges — per-period wire-byte
    accounting plus trajectory pins:

    * **EASGD** — the aggregated center must equal the closed-form
      composition of N same-version exchanges (exact on the
      lattice-valued tree; f32-tolerance in general —
      docs/DESIGN.md "Hierarchical exchange").  The direct-vs-
      aggregated center delta is reported too: a direct chain applies
      the exchanges sequentially, an O(alpha^2) order effect the doc
      quantifies.
    * **ASGD** — the aggregated delta-sum must match N direct
      same-version pushes BYTE-identically (plain-SGD pushes commute
      exactly on the lattice), pinning that hierarchy changes where
      bytes travel, never what the center computes.

    ``--smoke`` is the preflight gate: asserts the N=4 wire-byte
    reduction (>= 3.9x of the direct baseline — the aggregate frame's
    multiplier arg costs a few skeleton bytes of the exact 4x), both
    pins, and the fan-in gauge + ``local_aggregate`` spans in the
    monitor JSONL; exit 1 otherwise."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    os.environ.setdefault("THEANOMPI_TPU_SERVICE_KEY", "bench-exchange")
    os.environ.setdefault(
        "THEANOMPI_TPU_MONITOR",
        os.path.join(REPO, "artifacts", "bench_exchange_monitor"))

    from theanompi_tpu import monitor
    from theanompi_tpu.parallel import wire
    from theanompi_tpu.parallel.aggregate import (
        AggregatedExchange,
        LocalAggregator,
    )
    from theanompi_tpu.parallel.shards import (
        ShardProcessGroup,
        ShardedASGD,
        ShardedEASGD,
    )

    n_workers = int(args.local_workers)
    k = int(args.shards or 1)
    # --smoke is a GATE, not the artifact: only the asserted (K, N)
    # combo runs, at 2 periods (the pins need >= 2 to compose) —
    # the full K x N matrix with wall statistics is the committed-
    # artifact (non-smoke) run, like every other bench mode's split
    periods = 2 if args.smoke else max(3, args.exchanges)
    alpha = 0.25  # N*alpha <= 1 at N=4 (docs/DESIGN.md stability note)
    base = lattice_tree(int(args.params))
    n_params = tree_params(base)
    rng = np.random.default_rng(3)
    drifts = [
        {kk: (rng.integers(-64, 65, v.shape) * 2.0**-10)
         .astype(np.float32) for kk, v in base.items()}
        for _ in range(n_workers)]
    print(f"[bench_exchange] hierarchy mode: {n_params/1e6:.1f}M "
          f"params, {len(base)} leaves, "
          f"{tree_nbytes(base)/1e6:.1f} MB f32, N in (1, {n_workers}), "
          f"K in (1, {k})", flush=True)
    opts = wire.WireOptions.from_env()

    def frame_bytes(op_tuple) -> int:
        _, _, st = wire.encode_frame(op_tuple, opts)
        return st.post_bytes

    def shard_subs(client, tree):
        flat, _ = jax.tree.flatten(tree)
        flat = [np.asarray(a) for a in flat]
        return [flat[lo:hi] for lo, hi in client._plan.ranges]

    def worker_start(i):
        return {kk: base[kk] + drifts[i][kk] for kk in base}

    def run_leg(n_shards, n_local, hierarchical):
        """One (K, N, mode) leg on a fresh fleet; returns the measured
        row + the final center (for the trajectory pins)."""
        group = ShardProcessGroup(n_shards, max_restarts=1)
        sid = (f"hier-{n_shards}-{n_local}"
               if hierarchical else f"direct-{n_shards}-{n_local}")
        srv = ShardedEASGD(group.addresses, base, alpha=alpha,
                           session_id=sid)
        try:
            workers = [worker_start(i) for i in range(n_local)]
            walls = []
            if hierarchical:
                agg = LocalAggregator("easgd", srv, alpha=alpha)
                ports = [AggregatedExchange(
                    agg, i, lambda: ShardedEASGD(
                        group.addresses, None, alpha=alpha,
                        session_id=sid)) for i in range(n_local)]
                for _ in range(periods):
                    outs = [None] * n_local
                    ths = [threading.Thread(
                        target=lambda i=i: outs.__setitem__(
                            i, ports[i].exchange(workers[i])))
                        for i in range(n_local)]
                    t0 = time.monotonic()
                    for t in ths:
                        t.start()
                    for t in ths:
                        t.join()
                    walls.append((time.monotonic() - t0) * 1e3)
                    workers = [
                        {kk: outs[i][kk] + drifts[i][kk] for kk in base}
                        for i in range(n_local)]
                for p in ports:
                    p.close()
                # wire bytes/period: ONE tagged aggregate sub-exchange
                # per shard (mean tree out, pre-update center back)
                per_period = sum(
                    frame_bytes(("shard_exchange", sid, sub, "cid", 1,
                                 n_local)) + frame_bytes(("ok", sub))
                    for sub in shard_subs(srv, base))
            else:
                clients = [srv] + [
                    ShardedEASGD(group.addresses, None, alpha=alpha,
                                 session_id=sid)
                    for _ in range(n_local - 1)]
                for _ in range(periods):
                    outs = [None] * n_local
                    ths = [threading.Thread(
                        target=lambda i=i: outs.__setitem__(
                            i, clients[i].exchange(workers[i])))
                        for i in range(n_local)]
                    t0 = time.monotonic()
                    for t in ths:
                        t.start()
                    for t in ths:
                        t.join()
                    walls.append((time.monotonic() - t0) * 1e3)
                    workers = [
                        {kk: np.asarray(outs[i][kk]) + drifts[i][kk]
                         for kk in base} for i in range(n_local)]
                for c in clients[1:]:
                    c.close()
                # wire bytes/period: N full scatters (worker tree out,
                # new worker tree back, per shard, per worker)
                per_period = n_local * sum(
                    frame_bytes(("shard_exchange", sid, sub, "cid", 1))
                    + frame_bytes(("ok", sub))
                    for sub in shard_subs(srv, base))
            center = srv.get_center()
            return {
                "wall_ms_mean": round(float(np.mean(walls)), 2),
                "wall_ms_min": round(float(np.min(walls)), 2),
                "wire_bytes_per_period": per_period,
            }, center
        finally:
            srv.close()
            group.stop()

    def easgd_closed_form():
        """N same-version exchanges per period, composed on host —
        the reference the aggregated leg is pinned against."""
        c = {kk: v.copy() for kk, v in base.items()}
        workers = [worker_start(i) for i in range(n_workers)]
        a = np.float32(alpha)
        for _ in range(periods):
            new_c = {kk: c[kk] + a * sum(w[kk] - c[kk] for w in workers)
                     for kk in base}
            workers = [
                {kk: (w[kk] - a * (w[kk] - c[kk])) + drifts[i][kk]
                 for kk in base} for i, w in enumerate(workers)]
            c = new_c
        return c

    def max_abs_diff(t1, t2) -> float:
        return max(float(np.max(np.abs(np.asarray(t1[kk])
                                       - np.asarray(t2[kk]))))
                   for kk in base)

    def asgd_pin(n_shards) -> bool:
        """Direct N same-version plain-SGD pushes vs ONE aggregated
        delta-sum push, on the lattice: byte-identical centers."""
        small = lattice_tree(int(min(args.params, 2e5)), seed=5)
        grads = [
            {kk: (np.random.default_rng(50 + i)
                  .integers(-8, 9, v.shape) * 2.0**-10)
             .astype(np.float32) for kk, v in small.items()}
            for i in range(n_workers)]
        opt_cfg = dict(learning_rate=0.125, optimizer="sgd")
        finals = []
        for mode in ("direct", "hier"):
            group = ShardProcessGroup(n_shards, max_restarts=1)
            sid = f"asgd-pin-{mode}-{n_shards}"
            srv = ShardedASGD(group.addresses, small, opt_cfg,
                              session_id=sid)
            try:
                for _ in range(periods):
                    if mode == "direct":
                        for g in grads:
                            srv.push_pull(g)
                    else:
                        gsum = {kk: np.sum([g[kk] for g in grads],
                                           axis=0, dtype=np.float32)
                                for kk in small}
                        srv.push_pull_n(gsum, n_workers)
                # the pin compares MATH: an at-least-once transport
                # duplicate (reconnect + re-send under load) would
                # legitimately shift the center — detect and report it
                # as transport noise, not a math miss
                n_updates = srv.n_updates
                finals.append((srv.get_center(), n_updates))
            finally:
                srv.close()
                group.stop()
        (c_direct, n_direct), (c_hier, n_hier) = finals
        expect = periods * n_workers
        if n_direct != expect or n_hier != expect:
            print(f"[bench_exchange] asgd pin saw a transport re-send "
                  f"(updates direct={n_direct} hier={n_hier}, expected "
                  f"{expect}) — at-least-once duplicate, not a math "
                  "miss; pin inconclusive this run", file=sys.stderr)
            return None
        bad = [kk for kk in small
               if np.asarray(c_direct[kk]).tobytes()
               != np.asarray(c_hier[kk]).tobytes()]
        if bad:
            worst = max(float(np.max(np.abs(np.asarray(c_direct[kk])
                                            - np.asarray(c_hier[kk]))))
                        for kk in bad)
            print(f"[bench_exchange] asgd pin mismatch on "
                  f"{len(bad)}/{len(small)} leaves "
                  f"(max abs diff {worst})", file=sys.stderr)
        return not bad

    combos = ([(k, n_workers)] if args.smoke else
              [(s, n) for s in sorted({1, k})
               for n in sorted({1, n_workers})])
    modes = []
    with monitor.session():
        for n_shards, n_local in combos:
            direct, d_center = run_leg(n_shards, n_local, False)
            hier, h_center = run_leg(n_shards, n_local, True)
            row = {
                "shards": n_shards, "local_workers": n_local,
                "periods": periods,
                "direct": direct, "hierarchical": hier,
                "wire_byte_reduction_x": round(
                    direct["wire_bytes_per_period"]
                    / hier["wire_bytes_per_period"], 4),
                "wall_delta_vs_direct": round(
                    1.0 - hier["wall_ms_mean"]
                    / direct["wall_ms_mean"], 4),
                "easgd_direct_vs_hier_center_max_abs_diff":
                    max_abs_diff(d_center, h_center),
            }
            if n_local == n_workers:
                row["easgd_closed_form_max_abs_diff"] = \
                    max_abs_diff(h_center, easgd_closed_form())
            modes.append(row)
            print(f"[bench_exchange] K={n_shards} N={n_local}: "
                  f"{row['wire_byte_reduction_x']}x fewer wire "
                  f"bytes/period "
                  f"({direct['wire_bytes_per_period']/1e6:.1f} -> "
                  f"{hier['wire_bytes_per_period']/1e6:.1f} MB), "
                  f"wall {direct['wall_ms_mean']:.0f} -> "
                  f"{hier['wall_ms_mean']:.0f} ms", flush=True)
        asgd_identical = asgd_pin(k)
        if asgd_identical is None:  # transport re-send: one more try
            asgd_identical = asgd_pin(k)
        snapshot_path = monitor.flush()

    top = next(m for m in modes
               if m["shards"] == k and m["local_workers"] == n_workers)
    out_doc = {
        "bench": "hierarchical_exchange",
        "backend": jax.default_backend(),
        "n_params": n_params,
        "n_leaves": len(base),
        "tree_mb_f32": round(tree_nbytes(base) / 1e6, 2),
        "alpha": alpha,
        "wire": {"compression": opts.compression, "dtype": opts.dtype},
        "modes": modes,
        "asgd_delta_sum_byte_identical": asgd_identical,
        "note": ("trajectory pins on the exact f32 lattice: ASGD "
                 "byte-identical to N direct same-version pushes; "
                 "EASGD equal to the closed-form same-version "
                 "composition (the direct-vs-hier delta is the "
                 "documented O(alpha^2) sequential-order effect)"),
    }
    tag = args.tag or "hierarchy_smoke"
    path = args.out or os.path.join(REPO, "artifacts",
                                    f"BENCH_{tag}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out_doc, f, indent=1)
    print(f"[bench_exchange] wrote {path} "
          f"(N={n_workers} K={k}: {top['wire_byte_reduction_x']}x "
          "fewer wire bytes/period)", flush=True)

    if not args.smoke:
        return 0
    ok = True
    if top["wire_byte_reduction_x"] < 3.9 and n_workers >= 4:
        print(f"[bench_exchange] FAIL: wire-byte reduction "
              f"{top['wire_byte_reduction_x']}x < 3.9x at "
              f"N={n_workers}", file=sys.stderr)
        ok = False
    if top["hierarchical"]["wire_bytes_per_period"] >= \
            top["direct"]["wire_bytes_per_period"]:
        print("[bench_exchange] FAIL: hierarchical wire bytes/period "
              "not below the direct baseline", file=sys.stderr)
        ok = False
    if top.get("easgd_closed_form_max_abs_diff", 1.0) != 0.0:
        print(f"[bench_exchange] FAIL: EASGD aggregate deviates from "
              f"the closed form on the exact lattice "
              f"(max abs diff "
              f"{top.get('easgd_closed_form_max_abs_diff')})",
              file=sys.stderr)
        ok = False
    if asgd_identical is not True:
        print("[bench_exchange] FAIL: ASGD delta-sum not "
              "byte-identical to N direct same-version pushes",
              file=sys.stderr)
        ok = False
    # monitor JSONL: the fan-in gauge + local_aggregate spans are the
    # operator-facing proof the aggregation plane actually served
    fan_in, agg_spans = None, 0
    if snapshot_path and os.path.exists(snapshot_path):
        with open(snapshot_path) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("name") == "aggregate/fan_in":
                    fan_in = rec.get("value")
                if (rec.get("name") == "span_ms"
                        and rec.get("labels", {}).get("name")
                        == "local_aggregate"):
                    agg_spans = rec.get("count", 0)
    if fan_in != float(n_workers):
        print(f"[bench_exchange] FAIL: aggregate/fan_in gauge is "
              f"{fan_in}, expected {n_workers} (monitor JSONL "
              f"{snapshot_path})", file=sys.stderr)
        ok = False
    if agg_spans <= 0:
        print("[bench_exchange] FAIL: no local_aggregate spans in the "
              f"monitor JSONL ({snapshot_path})", file=sys.stderr)
        ok = False
    print(f"[bench_exchange] hierarchy smoke {'PASS' if ok else 'FAIL'}",
          flush=True)
    return 0 if ok else 1


def _bucket_step_equivalence(mesh, B: int) -> bool:
    """Build a real bucketed TRAIN step (collectives embedded in the
    backward via the exchanger's boundary tags) and check it equals
    the B=1 step bit-for-bit over 3 iterations — the preflight-grade
    proof that bucketing changes scheduling, never numerics."""
    import jax
    import jax.numpy as jnp
    import optax

    from theanompi_tpu.parallel.bsp import TrainState, make_bsp_train_step
    from theanompi_tpu.parallel.exchanger import BSP_Exchanger
    from theanompi_tpu.parallel.mesh import shard_batch

    def loss(params, ms, batch, rng):
        x, y = batch
        h = jnp.tanh(x @ params["w1"] + params["b1"])
        pred = h @ params["w2"] + params["b2"]
        l = jnp.mean((pred - y) ** 2)
        return l, (ms, {"loss": l})

    k = jax.random.split(jax.random.key(0), 2)
    params = {"w1": jax.random.normal(k[0], (6, 9)) * 0.3,
              "b1": jnp.zeros(9),
              "w2": jax.random.normal(k[1], (9, 2)) * 0.3,
              "b2": jnp.zeros(2)}
    tx = optax.sgd(0.05, momentum=0.9)
    rng_np = np.random.default_rng(5)
    batch = shard_batch(
        (rng_np.standard_normal((32, 6)).astype(np.float32),
         rng_np.standard_normal((32, 2)).astype(np.float32)), mesh)
    rng = jax.random.key(1)

    def run(buckets):
        ex = BSP_Exchanger(exchange_buckets=buckets, avg=True)
        step = make_bsp_train_step(loss, tx, mesh, ex, donate=False)
        s = TrainState.create(params, tx)
        for _ in range(3):
            s, _ = step(s, batch, rng)
        return [np.asarray(x) for x in jax.tree.leaves(s.params)]

    ref, out = run(1), run(B)
    return all(np.array_equal(a, b) for a, b in zip(ref, out))


def run_buckets(args) -> int:
    """``--buckets`` mode (ISSUE 13): drive the ~22.8M-param tree's
    IN-STEP bucketed exchange on the 8-device CPU mesh across bucket
    counts x wire dtypes.  Reports, per (dtype, B): the lowered
    program's collective count (B bucket collectives, by
    construction), per-bucket frame accounting (leaves + wire bytes
    from the shared plan every rank derives), and wall/exchange; plus
    the aggregate wall delta vs B=1 per dtype.  CPU walls bound the
    host-visible overhead of splitting the exchange, NOT the ICI
    overlap win — that needs a profile pair on a four-chip host
    (ROADMAP A3).

    ``--smoke`` is the preflight gate: sweeps only {1, B}, asserts the
    B=4-vs-B=1 train-step bit-identity and the bucket-count gauge in
    the monitor JSONL, exit 1 otherwise."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    os.environ.setdefault(
        "THEANOMPI_TPU_MONITOR",
        os.path.join(REPO, "artifacts", "bench_exchange_monitor"))

    from jax.sharding import PartitionSpec as P

    from theanompi_tpu import monitor
    from theanompi_tpu.parallel.exchanger import (
        BSP_Exchanger,
        _leaf_nbytes,
        bucket_ranges,
    )
    from theanompi_tpu.parallel.mesh import data_mesh

    bucket_list = sorted({int(b) for b in str(args.buckets).split(",")})
    if 1 not in bucket_list:
        bucket_list = [1] + bucket_list  # always carry the baseline
    smoke_b = max(bucket_list)
    n_exchanges = max(3, args.exchanges)
    tree = resnet50_like_tree(int(args.params))
    n_params = tree_params(tree)
    mesh = data_mesh(8)
    print(f"[bench_exchange] bucket mode: {n_params/1e6:.1f}M params, "
          f"{len(tree)} leaves, {tree_nbytes(tree)/1e6:.1f} MB f32, "
          f"B in {bucket_list}, 8-dev CPU mesh", flush=True)

    leaves = jax.tree.leaves(tree)
    sizes = [_leaf_nbytes(l) for l in leaves]
    modes = []
    dtypes = ("f32",) if args.smoke else ("f32", "bf16")
    with monitor.session():
        for dtype in dtypes:
            for B in bucket_list:
                ex = BSP_Exchanger(
                    exchange_dtype=None if dtype == "f32" else "bf16",
                    exchange_buckets=B, avg=True)
                fn = jax.jit(jax.shard_map(
                    ex.exchange, mesh=mesh, in_specs=P(),
                    out_specs=P(), check_vma=False))
                # one trace+lower serves both the collective count and
                # the executable (lower().compile() — calling fn()
                # after lower() would trace the whole program twice)
                t0 = time.monotonic()
                lowered = fn.lower(tree)
                txt = lowered.as_text()
                n_coll = (txt.count("stablehlo.all_reduce")
                          + txt.count("stablehlo.all_gather"))
                run = lowered.compile()
                out = run(tree)
                np.asarray(jax.tree.leaves(out)[0])  # fence
                compile_s = time.monotonic() - t0
                walls = []
                for _ in range(n_exchanges):
                    t0 = time.monotonic()
                    out = run(tree)
                    np.asarray(jax.tree.leaves(out)[0])
                    walls.append((time.monotonic() - t0) * 1e3)
                plan = bucket_ranges(sizes, B)
                wire_per_elem = 2 if dtype == "bf16" else 4
                per_bucket = [{
                    "bucket": i, "n_leaves": hi - lo,
                    "wire_bytes": wire_per_elem * sum(
                        int(l.size) for l in leaves[lo:hi]),
                } for i, (lo, hi) in enumerate(plan)]
                modes.append({
                    "dtype": dtype, "buckets": B,
                    "plan_buckets": len(plan),
                    "n_collectives_lowered": n_coll,
                    "n_exchanges": n_exchanges,
                    "wall_ms_mean": round(float(np.mean(walls)), 2),
                    "wall_ms_min": round(float(np.min(walls)), 2),
                    "compile_s": round(compile_s, 2),
                    "wire_bytes_total": sum(p["wire_bytes"]
                                            for p in per_bucket),
                    "per_bucket": per_bucket,
                })
                print(f"[bench_exchange] {dtype} B={B}: "
                      f"{modes[-1]['wall_ms_mean']:.0f} ms mean, "
                      f"{n_coll} collectives lowered", flush=True)
        equiv = _bucket_step_equivalence(mesh, smoke_b)
        snapshot_path = monitor.flush()

    delta = {}
    for dtype in dtypes:
        base = next(m for m in modes
                    if m["dtype"] == dtype and m["buckets"] == 1)
        delta[dtype] = {
            str(m["buckets"]):
                round(1.0 - m["wall_ms_mean"] / base["wall_ms_mean"], 4)
            for m in modes
            if m["dtype"] == dtype and m["buckets"] != 1}
    out_doc = {
        "bench": "bucketed_exchange",
        "backend": jax.default_backend(),
        "mesh_devices": 8,
        "n_params": n_params,
        "n_leaves": len(tree),
        "tree_mb_f32": round(tree_nbytes(tree) / 1e6, 2),
        "modes": modes,
        "aggregate_wall_delta_vs_b1": delta,
        "step_equivalence": {"buckets": smoke_b, "bit_identical": equiv},
        "note": ("CPU walls bound host-visible bucketing overhead only; "
                 "the ICI overlap win is graded by the queued on-chip "
                 "profile pair (xla_sweep_expected.md)"),
    }
    tag = args.tag or ("bucketed_smoke" if args.smoke
                       else f"bucketed_b{smoke_b}")
    path = args.out or os.path.join(REPO, "artifacts",
                                    f"BENCH_{tag}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out_doc, f, indent=1)
    print(f"[bench_exchange] wrote {path}", flush=True)

    if not args.smoke:
        return 0
    ok = True
    if not equiv:
        print(f"[bench_exchange] FAIL: B={smoke_b} train step is not "
              "bit-identical to B=1", file=sys.stderr)
        ok = False
    # the bucket-count gauge must have landed in the monitor JSONL
    # (operator-facing proof the bucket telemetry is live)
    found = False
    if snapshot_path and os.path.exists(snapshot_path):
        with open(snapshot_path) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("name") == "bsp/exchange_buckets":
                    found = True
    if not found:
        print("[bench_exchange] FAIL: bsp/exchange_buckets gauge "
              f"missing from monitor JSONL ({snapshot_path})",
              file=sys.stderr)
        ok = False
    print(f"[bench_exchange] bucket smoke {'PASS' if ok else 'FAIL'}",
          flush=True)
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--params", type=float, default=25.5e6,
                    help="target parameter count (~ResNet-50)")
    ap.add_argument("--exchanges", type=int, default=3,
                    help="timed exchanges per mode")
    ap.add_argument("--out", default=None,
                    help="output JSON (default artifacts/"
                         "BENCH_wire_<tag>.json)")
    ap.add_argument("--tag", default=None,
                    help="artifact tag (default: jax backend name)")
    ap.add_argument("--buckets", default=None, metavar="B[,B...]",
                    help="bucket mode (ISSUE 13): drive the in-step "
                         "bucketed gradient exchange on the 8-dev CPU "
                         "mesh across the given bucket counts (the "
                         "B=1 baseline is always added) x {f32,bf16}, "
                         "with per-bucket frame accounting and the "
                         "aggregate wall delta vs B=1; with --smoke "
                         "asserts the B-vs-1 step bit-identity + the "
                         "bucket gauge (the preflight bucketed gate). "
                         "Mutually exclusive with --shards")
    ap.add_argument("--shards", type=int, default=None, metavar="K",
                    help="shard mode: drive the tree against K real "
                         "shard processes (parallel/shards.py) and "
                         "report per-shard + aggregate bytes/wall vs "
                         "K=1; with --smoke also kills+recovers a "
                         "shard (the preflight 2-shard gate)")
    ap.add_argument("--local-workers", type=int, default=None,
                    metavar="N",
                    help="hierarchy mode (ISSUE 14): N co-located "
                         "workers behind one intra-host aggregator "
                         "(parallel/aggregate.py) vs N direct "
                         "exchanges, against --shards K real shard "
                         "processes (default 1) — per-period wire-byte "
                         "accounting + the ASGD byte-identity / EASGD "
                         "closed-form trajectory pins; with --smoke "
                         "asserts the >=3.9x byte reduction and the "
                         "fan-in gauge + local_aggregate spans (the "
                         "preflight hierarchy gate).  Mutually "
                         "exclusive with --buckets (hierarchical "
                         "aggregation is an async-rules plane; BSP's "
                         "in-step bucketed exchange refuses it — the "
                         "same matrix as the GOSGD/BSP launcher "
                         "refusals)")
    ap.add_argument("--shm-compare", action="store_true",
                    help="shared-memory-lane mode (ISSUE 20): in-band "
                         "vs shm legs across the exchange, ingest and "
                         "KV-page planes — identical workloads, fresh "
                         "server processes, sha256 byte-identity, a "
                         "mid-run force-disable fallback tail and a "
                         "SIGKILL-mid-lease sweep leg; writes "
                         "artifacts/BENCH_shm_smoke.json; with --smoke "
                         "asserts the >=25% exchange wall cut, the "
                         ">=1.3x ingest img/s lift, and zero leaked "
                         "segments.  Mutually exclusive with the other "
                         "legs")
    ap.add_argument("--smoke", action="store_true",
                    help="preflight gate: 1 exchange/mode, assert the "
                         "v2 byte win + the monitor gauge, exit 1 on "
                         "failure")
    args = ap.parse_args(argv)
    if args.shm_compare and (args.buckets is not None
                             or args.shards is not None
                             or args.local_workers is not None):
        raise FlagConflict(
            "--shm-compare is its own multi-plane leg (exchange + "
            "ingest + KV pages vs the shm lane) and drives its own "
            "fleet sizes — run --buckets/--shards/--local-workers "
            "separately")
    if args.buckets is not None and args.shards is not None:
        raise FlagConflict(
            "--buckets and --shards are mutually exclusive legs: the "
            "bucket leg measures the in-step SPMD exchange on a device "
            "mesh, the shard leg measures the wire exchange against "
            "real shard processes — run them separately")
    if args.local_workers is not None and args.buckets is not None:
        # the sibling of the --buckets/--shards conflict: hierarchical
        # aggregation applies to the async rules' WIRE exchange; BSP's
        # bucketed exchange runs inside the step program and refuses
        # it — exactly the GOSGD/BSP refusal matrix the launcher's
        # --local-aggregation enforces
        raise FlagConflict(
            "--local-workers and --buckets are mutually exclusive: "
            "hierarchical aggregation is an async-rules (EASGD/ASGD) "
            "wire plane, while the bucket leg measures BSP's in-step "
            "SPMD exchange — BSP (like GOSGD) refuses hierarchical "
            "aggregation (docs/DESIGN.md 'Hierarchical exchange')")
    if args.local_workers is not None and args.local_workers < 1:
        raise FlagConflict(
            f"--local-workers must be >= 1, got {args.local_workers}")
    if args.shm_compare:
        return run_shm_compare(args)
    if args.local_workers is not None:
        return run_hierarchy(args)
    if args.buckets is not None:
        return run_buckets(args)
    if args.shards is not None:
        return run_sharded(args)
    if args.smoke:
        args.exchanges = 1

    # the exchange service does its merge arithmetic in jax — keep it
    # off any real accelerator, this benchmarks the WIRE
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    os.environ.setdefault("THEANOMPI_TPU_SERVICE_KEY", "bench-exchange")
    mon_dir = os.environ.setdefault(
        "THEANOMPI_TPU_MONITOR",
        os.path.join(REPO, "artifacts", "bench_exchange_monitor"))

    from theanompi_tpu import monitor
    from theanompi_tpu.parallel.service import serve

    tree = resnet50_like_tree(int(args.params))
    n_params = tree_params(tree)
    print(f"[bench_exchange] tree: {n_params/1e6:.1f}M params, "
          f"{len(tree)} leaves, {tree_nbytes(tree)/1e6:.1f} MB f32",
          flush=True)

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    ready, stop = threading.Event(), threading.Event()
    threading.Thread(target=serve,
                     args=("127.0.0.1", port, ready, stop),
                     daemon=True).start()
    if not ready.wait(30):
        print("[bench_exchange] service never came up", file=sys.stderr)
        return 1
    addr = f"127.0.0.1:{port}"

    results = []
    with monitor.session():
        for protocol, compression, dtype in MODES:
            os.environ["THEANOMPI_TPU_WIRE_COMPRESSION"] = compression
            os.environ["THEANOMPI_TPU_WIRE_DTYPE"] = dtype
            os.environ["THEANOMPI_TPU_WIRE_PROTOCOL"] = protocol
            try:
                r = measure_mode(addr, protocol, compression, dtype,
                                 tree, args.exchanges)
            finally:
                for k in ("THEANOMPI_TPU_WIRE_COMPRESSION",
                          "THEANOMPI_TPU_WIRE_DTYPE",
                          "THEANOMPI_TPU_WIRE_PROTOCOL"):
                    os.environ.pop(k, None)
            print(f"[bench_exchange] {protocol}/{compression}/{dtype}: "
                  f"{r['bytes_per_exchange']/1e6:.1f} MB/exchange, "
                  f"{r['wall_ms_mean']:.0f} ms mean", flush=True)
            results.append(r)
        snapshot_path = monitor.flush()
        stop.set()

    v1 = next(r for r in results if r["protocol"] == "v1")
    v2_bf16 = next(r for r in results if r["protocol"] == "v2"
                   and r["dtype"] == "bf16" and r["compression"] == "none")
    v2_f32 = next(r for r in results if r["protocol"] == "v2"
                  and r["dtype"] == "f32" and r["compression"] == "none")
    byte_cut = 1.0 - v2_bf16["bytes_per_exchange"] / v1["bytes_per_exchange"]
    out = {
        "bench": "wire_exchange",
        "backend": jax.default_backend(),
        "n_params": n_params,
        "n_leaves": len(tree),
        "tree_mb_f32": round(tree_nbytes(tree) / 1e6, 2),
        "modes": results,
        "v2_bf16_vs_v1_byte_cut": round(byte_cut, 4),
        "v2_f32_vs_v1_byte_overhead": round(
            v2_f32["bytes_per_exchange"] / v1["bytes_per_exchange"] - 1.0,
            4),
    }
    tag = args.tag or ("smoke" if args.smoke else jax.default_backend())
    path = args.out or os.path.join(REPO, "artifacts",
                                    f"BENCH_wire_{tag}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"[bench_exchange] wrote {path} "
          f"(v2+bf16 cuts {byte_cut:.1%} of v1 bytes)", flush=True)

    if args.smoke:
        ok = True
        # v2's raw f32 framing is byte-equal to pickle (both ship raw
        # buffers; v2 trades pickle's memo for a JSON skeleton) — the
        # byte win lives in the negotiated modes, so the gate checks
        # the LOSSLESS one (zlib/f32 must beat v1 with zero numeric
        # change) and the headline bf16 cut below
        v2_zlib = next(r for r in results if r["protocol"] == "v2"
                       and r["dtype"] == "f32"
                       and r["compression"] == "zlib")
        if v2_zlib["bytes_per_exchange"] >= v1["bytes_per_exchange"]:
            print("[bench_exchange] FAIL: v2-framed (zlib/f32, lossless) "
                  "does not beat v1-pickle on bytes/exchange",
                  file=sys.stderr)
            ok = False
        if byte_cut < 0.45:
            print(f"[bench_exchange] FAIL: v2+bf16 byte cut {byte_cut:.1%}"
                  " < 45%", file=sys.stderr)
            ok = False
        # the compression-ratio gauge must have landed in the monitor
        # JSONL (the operator-facing proof the wire accounting is live)
        found = False
        if snapshot_path and os.path.exists(snapshot_path):
            with open(snapshot_path) as f:
                for line in f:
                    rec = json.loads(line)
                    if rec.get("name") == "service/wire_compression_ratio":
                        found = True
        if not found:
            print("[bench_exchange] FAIL: service/wire_compression_ratio "
                  f"gauge missing from monitor JSONL ({snapshot_path})",
                  file=sys.stderr)
            ok = False
        print(f"[bench_exchange] smoke {'PASS' if ok else 'FAIL'}",
              flush=True)
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
