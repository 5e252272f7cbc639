"""Connection-scaling benchmark for the RPC substrate (ISSUE 11).

Two legs, both against a REAL server process
(``python -m theanompi_tpu.parallel.service``) pinned to one core —
the honest front-door accounting: a serving host's event plane must be
cheap enough to leave the cores to the work.

* **connscale** — P client worker processes, each pipelining one
  in-flight pull on each of its C connections from a single thread
  (total concurrent authenticated connections 1→1000, every one with
  a request in flight), against the legacy thread-per-connection loop
  AND the selector event plane.  Reports aggregate pulls/s + p50/p99
  per point.  This is where thread-per-connection dies: at 600+
  in-flight connections the old loop is ~600 GIL-fighting server
  threads, while the event plane is one IO thread + a small executor
  pool.
* **convoy** — the PR 9 client-side collapse shape: N logical
  concurrent pullers in ONE client process pinned to ONE core with a
  GIL-holding compute thread (the trainer stand-in), comparing N
  dedicated sockets + N blocking recv threads (the old client) against
  ONE multiplexed socket + ONE pipelined thread
  (``rpc.MuxConnection``).  The committed bar is the PR 9 measured
  baseline — ~40 pulls/s at 12 recv threads on the one-core driver box
  (docs/DESIGN.md "Distributed ingest", measured pitfalls) — which the
  substrate must beat ≥10× at identical payload sizes.

``--smoke`` is the preflight gate (exit 1 on any miss):

* the selector loop sustains ≥1000 concurrent authenticated
  connections, every one with an in-flight request, at ≥1000 aggregate
  pulls/s with FLAT per-connection p99 (p99/conns at 1000 within 3× of
  the 8-connection point — i.e. pure fair-share queueing, no
  convoy-shaped blowup);
* at the 12-client convoy point the new substrate clears ≥10× the
  committed 40 pulls/s PR 9 baseline;
* the server's monitor JSONL carries the evidence
  (``rpc/connections_total`` ≥ the connection count,
  ``service/requests_total``, ``service/rpc_ms``).

Usage:
    python tools/bench_rpc.py                   # full sweep
    python tools/bench_rpc.py --smoke           # preflight gate
    python tools/bench_rpc.py --conns 8,200,1000 --loops selector
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the PR 9 measured collapse: ~1000→40 pulls/s at 12 recv threads in
#: one process on the one-core driver box (GIL convoy, 5 ms switch
#: interval per IO wake) — the committed baseline the ISSUE-11
#: acceptance bar is written against
PR9_CONVOY_BASELINE_PULLS_S = 40.0

SESSION = "bench-rpc"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _pin(pid: int, cores: set[int] | None) -> None:
    if cores:
        try:
            os.sched_setaffinity(pid, cores)
        except (AttributeError, OSError):
            pass


# ---------------------------------------------------------------------------
# Server lifecycle
# ---------------------------------------------------------------------------


def start_server(loop: str, payload_floats: int,
                 server_cores: set[int] | None,
                 monitor_dir: str | None):
    """One real service process on ``loop``, seeded with the payload
    tree; returns (port, Popen, init_client)."""
    from theanompi_tpu.parallel.service import RemoteEASGD, _authkey

    _authkey(generate=True)  # one key for server + all workers
    port = _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               THEANOMPI_TPU_RPC_LOOP=loop,
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    if monitor_dir:
        env["THEANOMPI_TPU_MONITOR"] = monitor_dir
    srv = subprocess.Popen(
        [sys.executable, "-m", "theanompi_tpu.parallel.service",
         "--port", str(port), "--platform", "cpu"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    # readiness: the HMAC handshake answering is the signal
    deadline = time.monotonic() + 60
    init = None
    while init is None:
        try:
            tree = {"w": np.random.default_rng(0)
                    .random(payload_floats).astype(np.float32)}
            init = RemoteEASGD(f"127.0.0.1:{port}", tree, alpha=0.5,
                               session_id=SESSION)
        except Exception:
            if time.monotonic() > deadline:
                srv.terminate()
                raise RuntimeError(f"server ({loop}) never came up")
            time.sleep(0.2)
    _pin(srv.pid, server_cores)
    return port, srv, init


def stop_server(port: int, srv, init) -> None:
    from theanompi_tpu.parallel.service import ServiceClient

    init.close()
    try:
        c = ServiceClient(f"127.0.0.1:{port}")
        c.call("shutdown")
        c.close()
    except Exception:
        srv.terminate()
    srv.wait(timeout=30)


# ---------------------------------------------------------------------------
# connscale leg — worker subprocess protocol
# ---------------------------------------------------------------------------


def worker_main(args) -> int:
    """One client process: C authenticated connections, one in-flight
    pull pipelined on each, collected from a SINGLE thread via the
    select-style wait (no client-side thread convoy — the client must
    measure the server)."""
    from multiprocessing.connection import Client as MpClient
    from multiprocessing.connection import wait as conn_wait

    from theanompi_tpu.parallel import wire
    from theanompi_tpu.parallel.service import _authkey

    opts = wire.WireOptions()
    conns = []
    for _ in range(args.worker_conns):
        c = MpClient(("127.0.0.1", args.worker_port),
                     authkey=_authkey())
        c.send((wire.HELLO_OP, wire.hello_payload(opts)))
        status, _ = c.recv()
        assert status == "ok", "wire negotiation failed"
        conns.append(c)
    sys.stdout.write("READY\n")
    sys.stdout.flush()
    sys.stdin.readline()  # the go barrier
    req = ("easgd_get_center", SESSION)
    count, lat, sent = 0, [], {}
    stop = time.monotonic() + args.worker_dur
    for c in conns:
        wire.send_msg(c, req, opts)
        sent[c] = time.monotonic()
    while time.monotonic() < stop:
        for c in conn_wait(list(sent), timeout=0.2):
            status, _ = wire.recv_msg(c, opts)
            assert status == "ok"
            lat.append(time.monotonic() - sent.pop(c))
            count += 1
            wire.send_msg(c, req, opts)
            sent[c] = time.monotonic()
    lat.sort()
    out = {"count": count,
           "p50_ms": lat[len(lat) // 2] * 1e3 if lat else 0.0,
           "p99_ms": lat[int(len(lat) * 0.99)] * 1e3 if lat else 0.0}
    for c in conns:
        c.close()
    print("RESULT " + json.dumps(out))
    sys.stdout.flush()
    return 0


def connscale_point(loop: str, total_conns: int, procs: int,
                    dur_s: float, payload_floats: int,
                    server_cores: set[int] | None,
                    monitor_dir: str | None = None) -> dict:
    procs = min(procs, total_conns)
    port, srv, init = start_server(loop, payload_floats, server_cores,
                                   monitor_dir)
    try:
        per = total_conns // procs
        extra = total_conns - per * procs
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        ps = []
        for i in range(procs):
            n = per + (1 if i < extra else 0)
            ps.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--worker-port", str(port), "--worker-conns", str(n),
                 "--worker-dur", str(dur_s)],
                env=env, stdin=subprocess.PIPE,
                stdout=subprocess.PIPE, text=True))
        for p in ps:
            line = p.stdout.readline().strip()
            assert line == "READY", f"worker said {line!r}"
        t0 = time.monotonic()
        for p in ps:  # the go barrier: all conns exist before any pull
            p.stdin.write("go\n")
            p.stdin.flush()
        results = []
        for p in ps:
            for line in p.stdout:
                if line.startswith("RESULT "):
                    results.append(json.loads(line[7:]))
                    break
            p.wait(timeout=60)
        wall = time.monotonic() - t0
    finally:
        stop_server(port, srv, init)
    return {
        "loop": loop, "conns": total_conns, "procs": procs,
        "pulls_s": round(sum(r["count"] for r in results) / wall, 1),
        "p50_ms": round(max(r["p50_ms"] for r in results), 2),
        "p99_ms": round(max(r["p99_ms"] for r in results), 2),
    }


# ---------------------------------------------------------------------------
# convoy leg — the PR 9 client shape, in this process
# ---------------------------------------------------------------------------


def convoy_point(port: int, n: int, dur_s: float,
                 client_core: set[int] | None) -> dict:
    """Old client (N sockets, N blocking recv threads) vs new client
    (ONE mux socket, ONE pipelined thread) with a GIL-holding compute
    thread running — all in this process, optionally pinned to one
    core (the PR 9 driver-box conditions)."""
    from theanompi_tpu.parallel import rpc, wire
    from theanompi_tpu.parallel.service import ServiceClient

    before = (os.sched_getaffinity(0)
              if hasattr(os, "sched_getaffinity") else None)
    _pin(0, client_core)
    stop_compute = threading.Event()

    def compute():
        x = np.random.rand(64, 64)
        while not stop_compute.is_set():
            for _ in range(50):
                (x @ x).sum()
            sum(i * i for i in range(2000))

    ct = threading.Thread(target=compute, daemon=True,
                          name="bench-rpc-compute")
    ct.start()
    req = ("easgd_get_center", SESSION)

    def drive_threads() -> dict:
        clients = [ServiceClient(f"127.0.0.1:{port}")
                   for _ in range(n)]
        counts = [0] * n
        lat: list[float] = []
        llock = threading.Lock()
        stop_t = time.monotonic() + dur_s

        def run(i):
            c = clients[i]
            while time.monotonic() < stop_t:
                t0 = time.monotonic()
                c.call(*req)
                with llock:
                    lat.append(time.monotonic() - t0)
                counts[i] += 1

        ths = [threading.Thread(target=run, args=(i,))
               for i in range(n)]
        t0 = time.monotonic()
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        wall = time.monotonic() - t0
        for c in clients:
            c.close()
        lat.sort()
        return {"pulls_s": round(sum(counts) / wall, 1),
                "p50_ms": round(lat[len(lat) // 2] * 1e3, 2),
                "p99_ms": round(lat[int(len(lat) * 0.99)] * 1e3, 2)}

    def drive_mux() -> dict:
        mc = rpc.MuxConnection(f"127.0.0.1:{port}")
        streams = [mc.connect_stream() for _ in range(n)]
        omap = dict(streams)
        count, lat, inflight = 0, [], {}
        stop_t = time.monotonic() + dur_s
        t0 = time.monotonic()
        for s, o in streams:
            wire.send_msg(s, req, o)
            inflight[s] = time.monotonic()
        while time.monotonic() < stop_t:
            for s in rpc.wait_readable(list(inflight), 0.05):
                wire.recv_msg(s, omap[s])
                lat.append(time.monotonic() - inflight.pop(s))
                count += 1
                wire.send_msg(s, req, omap[s])
                inflight[s] = time.monotonic()
        wall = time.monotonic() - t0
        for s, _ in streams:
            s.close()
        mc.close()
        lat.sort()
        return {"pulls_s": round(count / wall, 1),
                "p50_ms": round(lat[len(lat) // 2] * 1e3, 2),
                "p99_ms": round(lat[int(len(lat) * 0.99)] * 1e3, 2)}

    try:
        old = drive_threads()
        new = drive_mux()
    finally:
        stop_compute.set()
        ct.join(timeout=5)
        if before is not None:
            _pin(0, before)
    return {"n_clients": n,
            "old_threads_per_conn": old,
            "new_mux_pipelined": new,
            "committed_pr9_baseline_pulls_s":
                PR9_CONVOY_BASELINE_PULLS_S,
            "recovery_vs_pr9_baseline": round(
                new["pulls_s"] / PR9_CONVOY_BASELINE_PULLS_S, 1)}


# ---------------------------------------------------------------------------
# soak leg — mux byte-identity under sustained load
# ---------------------------------------------------------------------------


def run_soak(args) -> int:
    """``--soak``: the mux byte-identity pins under sustained load —
    the CPU gate behind flipping ``THEANOMPI_TPU_SHARD_MUX`` /
    ``THEANOMPI_TPU_INGEST_MUX`` defaults to ON (ROADMAP item 6
    leftover).

    Per loop (selector AND threaded): a real server seeded with a
    known tree; several ``rpc.MuxConnection`` transports, each shared
    by multiple ``ServiceClient`` streams (the shard-router shape —
    data + control streams on one socket); reader threads hammer
    ``easgd_get_center`` for ``--dur`` seconds comparing EVERY reply
    bitwise to the seeded tree, while writer threads interleave large
    gossip push/drain frames on the SAME transports.  The threaded
    loop grants no mux, so the identical client code must silently
    fall back to dedicated sockets and still hold identity — that
    fallback is what makes the ON default safe against old servers.
    Exit 1 on any byte mismatch or transport error."""
    from theanompi_tpu.parallel import rpc
    from theanompi_tpu.parallel.service import (
        RemoteGossipHub,
        ServiceClient,
    )

    payload_floats = args.payload_kb * 256
    ref = np.random.default_rng(0).random(payload_floats) \
        .astype(np.float32)
    ref_bytes = ref.tobytes()
    n_transports, streams_per = 3, 4
    results = {}
    for loop in args.loops.split(","):
        port, srv, init = start_server(loop, payload_floats, None, None)
        stop_t = time.monotonic() + args.dur
        counts = {"reads": 0, "writes": 0}
        errors: list[str] = []
        mismatches = [0]
        lock = threading.Lock()
        try:
            transports = [rpc.MuxConnection(f"127.0.0.1:{port}")
                          for _ in range(n_transports)]
            readers = [ServiceClient(f"127.0.0.1:{port}", transport=t)
                       for t in transports for _ in range(streams_per)]
            # one writer hub PER mux transport: the large gossip
            # frames must chunk-interleave with the identity-checked
            # reads on the SAME sockets — that interleaving is exactly
            # the hazard the mux-ON default flip is gated on
            hubs = [RemoteGossipHub(f"127.0.0.1:{port}", 2,
                                    session_id=SESSION + "-soak",
                                    transport=t) for t in transports]

            def read_loop(c):
                n = 0
                try:
                    while time.monotonic() < stop_t:
                        out = c.call("easgd_get_center", SESSION)
                        if np.asarray(out["w"]).tobytes() != ref_bytes:
                            with lock:
                                mismatches[0] += 1
                        n += 1
                except Exception as e:
                    with lock:
                        errors.append(f"reader: {type(e).__name__}: {e}")
                with lock:
                    counts["reads"] += n

            def write_loop(hub):
                # big frames both directions on the shared sockets:
                # gossip push/drain rides its OWN store kind, so the
                # easgd center the readers pin stays untouched
                n = 0
                tree = {"g": ref[: payload_floats // 4]}
                try:
                    while time.monotonic() < stop_t:
                        hub.push(1, tree, 0.01)
                        hub.drain(1)
                        n += 1
                except Exception as e:
                    with lock:
                        errors.append(f"writer: {type(e).__name__}: {e}")
                with lock:
                    counts["writes"] += n

            ths = [threading.Thread(target=read_loop, args=(c,),
                                    daemon=True) for c in readers] \
                + [threading.Thread(target=write_loop, args=(h,),
                                    daemon=True) for h in hubs]
            t0 = time.monotonic()
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            wall = time.monotonic() - t0
            muxed = any(getattr(t, "_mux", False) for t in transports)
            for c in readers:
                c.close()
            for h in hubs:
                h.close()
            for t in transports:
                t.close()
        finally:
            stop_server(port, srv, init)
        results[loop] = {
            "reads": counts["reads"], "writes": counts["writes"],
            "reads_per_s": round(counts["reads"] / wall, 1),
            "byte_mismatches": mismatches[0],
            "errors": errors[:5],
            "mux_granted": muxed,
            "streams": n_transports * (streams_per + 1),
            "dur_s": round(wall, 1),
        }
        print(f"[soak] loop={loop:8s} {counts['reads']} identity-"
              f"checked reads ({results[loop]['reads_per_s']}/s), "
              f"{counts['writes']} interleaved push/drain rounds, "
              f"mux_granted={muxed}, mismatches={mismatches[0]}, "
              f"errors={len(errors)}", flush=True)

    failures = []
    for loop, r in results.items():
        if r["byte_mismatches"]:
            failures.append(f"{loop}: {r['byte_mismatches']} byte "
                            "mismatches")
        if r["errors"]:
            failures.append(f"{loop}: transport errors {r['errors']}")
        if not r["reads"] or not r["writes"]:
            failures.append(f"{loop}: no sustained load "
                            f"(reads={r['reads']}, "
                            f"writes={r['writes']})")
    if "selector" in results and not results["selector"]["mux_granted"]:
        failures.append("selector loop did not grant mux — the soak "
                        "never exercised stream multiplexing")
    if "threaded" in results and results["threaded"]["mux_granted"]:
        failures.append("threaded loop granted mux?! the dedicated-"
                        "socket fallback went unexercised")
    out_doc = {"bench": "rpc_soak", "payload_kb": args.payload_kb,
               "loops": results,
               "failures": failures, "ok": not failures}
    out = args.out or os.path.join(REPO, "artifacts",
                                   "BENCH_rpc_soak.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(out_doc, f, indent=1)
    for fmsg in failures:
        print(f"[soak] FAIL: {fmsg}", file=sys.stderr)
    print(f"[soak] {'PASS' if not failures else 'FAIL'} -> {out}",
          flush=True)
    return 0 if not failures else 1


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="preflight gate: 1000-conn flat-p99 + convoy "
                         "recovery assertions, exit 1 on any miss")
    ap.add_argument("--soak", action="store_true",
                    help="mux byte-identity soak (the gate behind the "
                         "SHARD_MUX/INGEST_MUX ON defaults): muxed "
                         "streams hammer identity-checked reads with "
                         "interleaved large frames for --dur seconds "
                         "on BOTH loops (threaded = the dedicated-"
                         "socket fallback), exit 1 on any mismatch")
    ap.add_argument("--conns", default=None,
                    help="comma-separated connscale points "
                         "(default smoke: 8,1000; full: "
                         "1,8,48,200,600,1000)")
    ap.add_argument("--loops", default="threaded,selector")
    ap.add_argument("--procs", type=int, default=4,
                    help="client worker processes per point")
    ap.add_argument("--dur", type=float, default=5.0,
                    help="seconds per measured point")
    ap.add_argument("--payload-kb", type=int, default=256,
                    help="pull payload (f32 tree) for connscale; the "
                         "convoy leg always uses 1024 (the PR 9 "
                         "~1 MB batch-pull shape)")
    ap.add_argument("--convoy-clients", type=int, default=12,
                    help="the PR 9 measured collapse point")
    ap.add_argument("--server-core", type=int, default=None,
                    help="pin the server to ONE core (default: the "
                         "highest available; -1 disables pinning)")
    ap.add_argument("--out", default=None,
                    help="result JSON path (default "
                         "artifacts/BENCH_rpc_smoke.json with --smoke)")
    # worker mode (internal)
    ap.add_argument("--worker-port", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--worker-conns", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--worker-dur", type=float, default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.worker_port is not None:
        return worker_main(args)
    if args.soak:
        return run_soak(args)

    ncpu = os.cpu_count() or 1
    if args.server_core == -1:
        server_cores = None
        client_core = None
    else:
        core = (args.server_core if args.server_core is not None
                else ncpu - 1)
        server_cores = {core}
        client_core = {0} if ncpu > 1 else None
    points = [int(x) for x in (args.conns or (
        "8,1000" if args.smoke else "1,8,48,200,600,1000")).split(",")]
    loops = args.loops.split(",")
    payload_floats = args.payload_kb * 256  # f32 per KB

    result = {
        "host": {"cpus": ncpu, "server_cores": sorted(server_cores)
                 if server_cores else "unpinned"},
        "payload_kb": args.payload_kb,
        "connscale": [],
        "convoy": None,
        "committed_pr9_baseline_pulls_s": PR9_CONVOY_BASELINE_PULLS_S,
    }

    mon_dir = tempfile.mkdtemp(prefix="bench_rpc_mon_")
    try:
        for conns in points:
            for loop in loops:
                use_mon = (mon_dir if loop == "selector"
                           and conns == max(points) else None)
                r = connscale_point(loop, conns, args.procs, args.dur,
                                    payload_floats, server_cores,
                                    monitor_dir=use_mon)
                result["connscale"].append(r)
                print(f"[connscale] conns={conns:5d} loop={loop:8s} "
                      f"{r['pulls_s']:9.1f} pulls/s "
                      f"p50={r['p50_ms']:8.1f}ms "
                      f"p99={r['p99_ms']:8.1f}ms", flush=True)

        # convoy leg: selector server (unpinned interference is the
        # point on the CLIENT side; server stays pinned), 1 MB pulls
        port, srv, init = start_server("selector", 262144,
                                       server_cores, None)
        try:
            result["convoy"] = convoy_point(port, args.convoy_clients,
                                            args.dur, client_core)
        finally:
            stop_server(port, srv, init)
        cv = result["convoy"]
        print(f"[convoy] n={cv['n_clients']} old(threads/conn): "
              f"{cv['old_threads_per_conn']['pulls_s']} pulls/s | "
              f"new(mux 1-thread): "
              f"{cv['new_mux_pipelined']['pulls_s']} pulls/s | "
              f"{cv['recovery_vs_pr9_baseline']}x the committed "
              f"{PR9_CONVOY_BASELINE_PULLS_S:.0f} pulls/s PR9 "
              "baseline", flush=True)

        # monitor JSONL evidence from the biggest selector point
        evidence = {}
        for fn in os.listdir(mon_dir):
            if fn.startswith("metrics_") and fn.endswith(".jsonl"):
                recs = [json.loads(l)
                        for l in open(os.path.join(mon_dir, fn))]
                for r in recs:
                    if r["name"] == "rpc/connections_total":
                        evidence["rpc_connections_total"] = \
                            evidence.get("rpc_connections_total", 0) \
                            + r["value"]
                    if (r["name"] == "service/requests_total"
                            and r["labels"].get("op")
                            == "easgd_get_center"):
                        evidence["requests_total"] = \
                            evidence.get("requests_total", 0) \
                            + r["value"]
                    if (r["name"] == "service/rpc_ms"
                            and r["labels"].get("op")
                            == "easgd_get_center"):
                        evidence["server_rpc_p99_ms"] = r.get("p99")
        result["monitor_evidence"] = evidence

        if args.smoke:
            failures = []
            sel = {r["conns"]: r for r in result["connscale"]
                   if r["loop"] == "selector"}
            top = max(sel)
            # the committed artifact must carry the full 1000; an
            # explicit --conns (preflight's quicker >=200 leg) lowers
            # the floor, not the flatness/recovery bars
            min_top = 1000 if args.conns is None else 200
            if top < min_top:
                failures.append(f"top selector point is {top} conns; "
                                f"the smoke bar is {min_top}")
            if sel[top]["pulls_s"] < 1000:
                failures.append(
                    f"selector at {top} conns: "
                    f"{sel[top]['pulls_s']} pulls/s < 1000")
            lo = min(sel)
            flat = ((sel[top]["p99_ms"] / top)
                    / max(sel[lo]["p99_ms"] / lo, 1e-9))
            if flat > 3.0:
                failures.append(
                    f"p99-per-connection not flat: {top}-conn point "
                    f"is {flat:.1f}x the {lo}-conn point (bar 3x)")
            result["p99_per_conn_flatness"] = round(flat, 2)
            new = cv["new_mux_pipelined"]["pulls_s"]
            if new < 10 * PR9_CONVOY_BASELINE_PULLS_S:
                failures.append(
                    f"convoy recovery {new} pulls/s < 10x the "
                    f"committed {PR9_CONVOY_BASELINE_PULLS_S} "
                    "baseline")
            if evidence.get("rpc_connections_total", 0) < top:
                failures.append(
                    "monitor evidence missing: rpc/connections_total "
                    f"= {evidence.get('rpc_connections_total')} < "
                    f"{top}")
            if not evidence.get("requests_total"):
                failures.append("monitor evidence missing: "
                                "service/requests_total")
            result["smoke"] = {"failures": failures,
                               "ok": not failures}
            for f in failures:
                print(f"[smoke] FAIL: {f}", flush=True)
    finally:
        shutil.rmtree(mon_dir, ignore_errors=True)

    out = args.out or (os.path.join(REPO, "artifacts",
                                    "BENCH_rpc_smoke.json")
                       if args.smoke else None)
    if out:
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            json.dump(result, f, indent=2)
        print(f"[bench_rpc] wrote {out}", flush=True)
    else:
        print(json.dumps(result, indent=2))
    if args.smoke and result["smoke"]["failures"]:
        print("BENCH_RPC SMOKE: FAIL", flush=True)
        return 1
    if args.smoke:
        print("BENCH_RPC SMOKE: GREEN", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
