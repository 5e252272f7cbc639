#!/bin/bash
# Snapshot gate (round-4 verdict #6 / round-3 #4b): the FULL suite —
# slow tests included — plus the driver entry points must be green
# before any end-of-round snapshot.  Round 3 committed a slow e2e test
# that had never been run (it failed); nothing structural prevented a
# repeat until this script.
#
# Usage:  bash tools/preflight.sh [artifacts/preflight_rNN.log]
# Exit 0 = safe to snapshot.  Writes the full output to the log path
# (default artifacts/preflight.log) so the round log can cite it.
set -u
LOG="${1:-artifacts/preflight.log}"
cd "$(dirname "$0")/.."
# shm-lane evidence scan (ISSUE 20): the same-host smokes must show
# the shared-memory lane actually carried payload — a grant landed
# AND out-of-band bytes flowed — in the monitor JSONL the smoke just
# wrote.  Returns 1 (and prints what's missing) if the lane silently
# fell back everywhere, which would mean the negotiation or adopter
# wiring regressed while the in-band fallback kept the smoke green.
shm_lane_evidence() {  # $1 = monitor dir, $2 = plane label
  python - "$1" "$2" <<'PYEOF'
import glob, json, os, sys
mondir, label = sys.argv[1], sys.argv[2]
grants = oob = 0.0
for path in glob.glob(os.path.join(mondir, "**", "*.jsonl"),
                      recursive=True):
    for line in open(path):
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        # role exporters write flat per-series records; the collector
        # wraps a snapshot list inside event=metrics records
        series = [rec] if "name" in rec else rec.get("snapshot") or []
        for s in series:
            if s.get("name") == "shm/grants_total":
                grants = max(grants, s.get("value") or 0.0)
            elif s.get("name") == "shm/oob_bytes_total":
                oob = max(oob, s.get("value") or 0.0)
if grants < 1 or oob <= 0:
    print(f"shm lane evidence MISSING for {label}: "
          f"grants={grants:.0f} oob_bytes={oob:.0f}")
    sys.exit(1)
print(f"shm lane evidence ({label}): grants>={grants:.0f}, "
      f"{oob/1e6:.3f} MB out-of-band")
PYEOF
}
{
  echo "# preflight $(date -u +%Y-%m-%dT%H:%M:%SZ) HEAD=$(git rev-parse --short HEAD)"
  echo "## tmlint --gate (static checker suite, docs/ANALYSIS.md)"
  # zero NEW findings vs analysis/baseline.json; pure-ast, seconds on
  # CPU — runs FIRST so a locking/donation/doc-drift regression fails
  # before the expensive suites even start
  python tools/tmlint.py --gate
  TMLINT_RC=$?
  echo "tmlint rc=$TMLINT_RC"
  echo "## pytest slow-subset gate (-m gate)"
  # The tagged MUST-PASS slow subset (pyproject markers: 'gate') runs
  # as its OWN step so an environmental failure elsewhere in the full
  # --runslow set (e.g. this jax's multihost-on-CPU limitation) can
  # never mask a broken gate test — the round-3 failure mode was a
  # committed-but-never-run slow e2e, and a habitually-red full suite
  # recreates exactly that blind spot.  Currently gated: the jpeg-tree
  # end-to-end training oracle (tests/test_oracle.py).
  python -m pytest tests/ --runslow -q -m gate
  GATE_RC=$?
  echo "gate subset rc=$GATE_RC"
  echo "## pytest --runslow (-m 'not gate' — the gate subset just ran)"
  python -m pytest tests/ --runslow -q -m 'not gate'
  PYTEST_RC=$?
  echo "pytest rc=$PYTEST_RC"
  echo "## __graft_entry__ (entry + dryrun_multichip on the virtual mesh)"
  # the gate runs on the CPU platform (chip_smoke.py is the chip's
  # check); the variable alone selects it
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python __graft_entry__.py
  ENTRY_RC=$?
  echo "graft_entry rc=$ENTRY_RC"
  echo "## monitor smoke (5-step CPU BSP with THEANOMPI_TPU_MONITOR)"
  # telemetry end-to-end: the snapshot JSONL must parse and carry the
  # core series, and the heartbeat must be fresh (docs/OBSERVABILITY.md)
  MONDIR="$(mktemp -d)"
  JAX_PLATFORMS=cpu THEANOMPI_TPU_MONITOR="$MONDIR" python - <<'PYEOF'
import json, os, sys, time
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import jax
jax.config.update("jax_platforms", "cpu")
from theanompi_tpu.data.cifar10 import Cifar10_data
from theanompi_tpu.models.base import ModelConfig
from theanompi_tpu.models.cifar10 import Cifar10_model
from theanompi_tpu.parallel import data_mesh
from theanompi_tpu.rules.bsp import run_bsp_session

class Tiny(Cifar10_model):
    def build_data(self):
        return Cifar10_data(synthetic_n=80)  # 5 iters at batch 2 x 8

cfg = ModelConfig(batch_size=2, n_epochs=1, print_freq=10**9,
                  compute_dtype="float32")
run_bsp_session(Tiny(config=cfg, mesh=data_mesh(8)), max_epochs=1,
                checkpoint=False)
mondir = os.environ["THEANOMPI_TPU_MONITOR"]
recs = [json.loads(l)
        for l in open(os.path.join(mondir, "metrics_rank0.jsonl"))]
names = {r["name"] for r in recs}
missing = {"step_ms", "span_ms", "recorder/section_ms"} - names
assert not missing, f"snapshot missing core series: {missing}"
steps = next(r for r in recs if r["name"] == "step_ms")
assert steps["count"] == 5, f"expected 5 step observations: {steps}"
hb = json.load(open(os.path.join(mondir, "heartbeat_rank0.json")))
assert time.time() - hb["written"] < 120, f"stale heartbeat: {hb}"
assert hb["stalled"] is False
print(f"monitor smoke OK: {len(names)} series, "
      f"step p50 {steps['p50']:.1f}ms, heartbeat fresh")
PYEOF
  MONITOR_RC=$?
  rm -rf "$MONDIR"
  echo "monitor smoke rc=$MONITOR_RC"
  echo "## collector smoke (distributed tracing: trainer -> 2 real shard processes + concurrent decode GENERATE -> one collector, docs/OBSERVABILITY.md 'Distributed tracing')"
  # the ISSUE 16 vertical end-to-end: a supervised collector process, a
  # REAL 2-shard EASGD fleet, and a concurrent decode GENERATE, all
  # shipping span/metric events to ONE merged fleet.jsonl.  The gate
  # asserts (a) the exchange reconstructs as a single trace spanning
  # >= 3 PROCESSES with zero orphans, (b) the GENERATE reconstructs as
  # a single client->rpc_handle->decode_generate trace, (c)
  # tools/traces.py prints the critical path and runs the
  # idle-all-workers gap detector on the merged stream, and (d)
  # tools/tmtop.py renders a fleet frame from the shipped metrics
  COLDIR="$(mktemp -d)"
  JAX_PLATFORMS=cpu THEANOMPI_TPU_MONITOR="$COLDIR" python - <<'PYEOF'
import os, socket, sys, threading, time
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
os.environ["THEANOMPI_TPU_TRACE"] = "1"  # before any child spawns
from theanompi_tpu import monitor
from theanompi_tpu.models.base import ModelConfig
from theanompi_tpu.models.transformer import TransformerLM
from theanompi_tpu.monitor.collector import CollectorProcess
from theanompi_tpu.parallel.shards import (ShardProcessGroup,
                                           ShardedEASGD,
                                           shard_addresses)
from theanompi_tpu.serving import (InferenceClient, InferenceServer,
                                   export_model, serve)

mondir = os.environ["THEANOMPI_TPU_MONITOR"]
col = CollectorProcess(mondir)  # exports THEANOMPI_TPU_COLLECTOR
group = ShardProcessGroup(2, max_restarts=1)  # inherits trace+collector
try:
    cfg = ModelConfig(batch_size=4, n_epochs=1, print_freq=0,
                      compute_dtype="float32", optimizer="adamw",
                      learning_rate=1e-3, weight_decay=0.0,
                      lr_schedule="constant")
    lm = TransformerLM(config=cfg, vocab=32, seq_len=16, n_layers=1,
                       d_model=16, n_heads=2, verbose=False)
    export_dir = os.path.join(mondir, "export")
    export_model(lm, export_dir, version=0)
    rng = np.random.default_rng(0)
    tree = {"a": rng.standard_normal((64, 8)).astype(np.float32),
            "b": rng.standard_normal((33,)).astype(np.float32)}
    with monitor.session(run_dir=mondir, stall_after=float("inf")):
        server = InferenceServer(
            export_dir, replicas=1, reload_poll_s=0, model=lm,
            decode=True,
            decode_opts=dict(page_size=4, pages_per_seq=8, max_seqs=4,
                             prefill_buckets=(8,))).start()
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        ready = threading.Event()
        t = threading.Thread(target=serve,
                             args=(server, "127.0.0.1", port, ready),
                             daemon=True)
        t.start()
        assert ready.wait(30)
        c = InferenceClient(f"127.0.0.1:{port}")
        gen_out = {}

        def gen():
            with monitor.span("client_generate"):
                gen_out["toks"] = c.generate(
                    np.asarray([1, 2, 3], np.int32), 6)

        gt = threading.Thread(target=gen)
        gt.start()  # concurrent with the exchange leg, per the gate
        srv = ShardedEASGD(shard_addresses(group.server_addr), tree,
                           alpha=0.5, session_id="preflight-trace")
        for n in range(3):
            w = jax.tree.map(lambda x: x + np.float32(0.05 * (n + 1)),
                             tree)
            with monitor.span("exchange_period"):
                srv.exchange(w)
        srv.close()
        gt.join(120)
        assert gen_out.get("toks") is not None \
            and len(gen_out["toks"]) == 6
        c.shutdown()
        c.close()
        t.join(timeout=5)
        server.stop()
        time.sleep(1.5)  # let the shard exporters flush their tails
    # session exit flushed the trainer's exporter; the fleet file now
    # carries >= 3 processes (trainer + 2 shards) + the collector meta
    st = col.stats()
    assert st and st["events"] > 0 and st["senders"] >= 3, st
    sys.path.insert(0, os.path.join(os.getcwd(), "tools"))
    import traces as traces_tool
    records = traces_tool.load_events(os.path.join(mondir,
                                                   "fleet.jsonl"))
    tr = traces_tool.assemble(records)
    ex = [s for s in tr.values()
          if any(x["name"] == "exchange_period" for x in s)]
    assert ex, "no exchange trace reached the collector"
    stitched = [s for s in ex
                if len(traces_tool.processes_of(s)) >= 3
                and not traces_tool.orphans(s)]
    assert stitched, [
        (len(s), sorted(traces_tool.processes_of(s)),
         len(traces_tool.orphans(s))) for s in ex]
    gen_tr = [s for s in tr.values()
              if any(x["name"] == "client_generate" for x in s)]
    assert len(gen_tr) == 1 and not traces_tool.orphans(gen_tr[0]), \
        "GENERATE must reconstruct as ONE trace with zero orphans"
    names = [x["name"] for x in gen_tr[0]]
    assert any("rpc_handle" in n for n in names), names
    assert any("decode_generate" in n for n in names), names
    print(f"collector smoke OK: {st['events']} events from "
          f"{st['senders']} senders, exchange trace spans "
          f"{len(traces_tool.processes_of(stitched[0]))} processes "
          f"({len(stitched[0])} spans, 0 orphans), GENERATE stitched "
          f"({len(gen_tr[0])} spans)")
finally:
    group.stop()
    col.stop()
PYEOF
  COLLECTOR_RC=$?
  if [ "$COLLECTOR_RC" -eq 0 ]; then
    # the consumer tools over the SAME merged file: traces.py must
    # confirm a >=3-process orphan-free trace, print its critical
    # path, and run the idle-gap detector; tmtop must render a frame
    python tools/traces.py "$COLDIR" --require-procs 3 --gap-ms 5000 \
      > "$COLDIR/traces.out" 2>&1
    TRACES_RC=$?
    grep -q "critical path" "$COLDIR/traces.out" || TRACES_RC=1
    grep -q "idle-all-workers gaps" "$COLDIR/traces.out" || TRACES_RC=1
    sed -n '1,12p' "$COLDIR/traces.out"
    python tools/tmtop.py "$COLDIR" --once || TRACES_RC=1
    COLLECTOR_RC=$TRACES_RC
  fi
  rm -rf "$COLDIR"
  echo "collector smoke rc=$COLLECTOR_RC"
  echo "## resilience smoke (EASGD kill-and-recover via THEANOMPI_TPU_FAULTS)"
  # fault-injection end-to-end (docs/RESILIENCE.md): kill worker 1 at
  # step 3 of a tiny EASGD session; supervised recovery must restart
  # it from center, the run must exit 0, and the recovery event must
  # land in the monitor JSONL
  FAULTDIR="$(mktemp -d)"
  JAX_PLATFORMS=cpu THEANOMPI_TPU_MONITOR="$FAULTDIR" \
    THEANOMPI_TPU_FAULTS='[{"site": "worker_step", "rule": "easgd", "worker": 1, "step": 3}]' \
    python - <<'PYEOF'
import json, os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from theanompi_tpu import EASGD
from theanompi_tpu.models.base import ModelConfig

cfg = ModelConfig(batch_size=8, n_epochs=1, learning_rate=0.01,
                  snapshot_dir=os.environ["THEANOMPI_TPU_MONITOR"],
                  print_freq=0)
rule = EASGD()
rule.init(devices=2, modelfile="tests._tiny_models",
          modelclass="TinyCifar", config=cfg, tau=4, alpha=0.5,
          checkpoint=False, max_restarts=1)
res = rule.wait()
assert res["restarts"] == {1: 1}, res.get("restarts")
assert res["lost_workers"] == [], res.get("lost_workers")
assert np.isfinite(res["val"]["loss"])
mondir = os.environ["THEANOMPI_TPU_MONITOR"]
recs = [json.loads(l)
        for l in open(os.path.join(mondir, "metrics_rank0.jsonl"))]
by_name = {r["name"]: r for r in recs}
assert "resilience/worker_restarts_total" in by_name, sorted(by_name)
assert "resilience/faults_injected_total" in by_name
print("resilience smoke OK: worker 1 killed at step 3, restarted "
      "from center, recovery event in monitor JSONL")
PYEOF
  RESILIENCE_RC=$?
  rm -rf "$FAULTDIR"
  echo "resilience smoke rc=$RESILIENCE_RC"
  echo "## serving smoke (export -> server -> concurrent clients, docs/SERVING.md)"
  # the serving vertical end-to-end on CPU: export an untrained tiny
  # model, serve it on a real socket, fire concurrent clients; at
  # least one multi-request batch must form and the request-latency
  # histogram must land in the monitor JSONL
  SERVEDIR="$(mktemp -d)"
  JAX_PLATFORMS=cpu THEANOMPI_TPU_MONITOR="$SERVEDIR" python - <<'PYEOF'
import glob, json, os, socket, threading
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from tests._tiny_models import TinyCifar
from theanompi_tpu import monitor
from theanompi_tpu.models.base import ModelConfig
from theanompi_tpu.serving import (BatchPolicy, InferenceClient,
                                   InferenceServer, export_model, serve)

mondir = os.environ["THEANOMPI_TPU_MONITOR"]
model = TinyCifar(config=ModelConfig(batch_size=8, n_epochs=1,
                                     print_freq=0), verbose=False)
export_dir = os.path.join(mondir, "export")
export_model(model, export_dir, version=0)
with monitor.session(run_dir=mondir, stall_after=float("inf")):
    server = InferenceServer(
        export_dir, replicas=1, reload_poll_s=0, model=model,
        policy=BatchPolicy(max_batch=4, max_delay_ms=50.0,
                           buckets=(4,), max_queue=16)).start()
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    ready = threading.Event()
    t = threading.Thread(target=serve,
                         args=(server, "127.0.0.1", port, ready),
                         daemon=True)
    t.start()
    assert ready.wait(30)
    x = np.asarray(model.data.x_val[:8])
    outs = [None] * 8
    clients = [InferenceClient(f"127.0.0.1:{port}") for _ in range(8)]
    ths = [threading.Thread(
        target=lambda i=i: outs.__setitem__(
            i, clients[i].infer(x[i:i + 1]))) for i in range(8)]
    for th in ths:
        th.start()
    for th in ths:
        th.join()
    st = clients[0].stats()
    assert st["max_occupancy"] > 1, f"no dynamic batch formed: {st}"
    assert all(o is not None and o.shape == (1, 10) for o in outs)
    clients[0].shutdown()
    for c in clients:
        c.close()
    t.join(timeout=5)
    server.stop()
snap = [p for p in glob.glob(os.path.join(mondir, "metrics_rank0.jsonl"))]
recs = [json.loads(l) for l in open(snap[0])]
names = {r["name"] for r in recs}
missing = {"serving/request_ms", "serving/batch_occupancy",
           "serving/requests_total"} - names
assert not missing, f"snapshot missing serving series: {missing}"
lat = next(r for r in recs if r["name"] == "serving/request_ms")
assert lat["count"] == 8 and "p99" in lat, lat
print(f"serving smoke OK: occupancy_max={st['max_occupancy']}, "
      f"{st['batches']} batches / {st['rows']} rows, "
      f"request p99 {lat['p99']:.1f}ms in monitor JSONL")
PYEOF
  SERVING_RC=$?
  rm -rf "$SERVEDIR"
  echo "serving smoke rc=$SERVING_RC"
  echo "## decode smoke (LM+draft exports -> speculative decode server -> shared-prefix streams, docs/SERVING.md 'Decode'/'Speculative decode'/'Prefix cache')"
  # the autoregressive vertical end-to-end on CPU: export a tiny
  # TransformerLM AND a bf16 self-draft, serve in decode mode with
  # speculation + prefix cache on a real socket, drive a warm stream
  # then two concurrent streams sharing its page-aligned prompt
  # prefix; at least one decode step must batch rows from BOTH
  # sequences (iteration-level sharing), every stream must match the
  # uncached full-forward argmax oracle, speculation must accept at
  # least one draft (accept-rate > 0), the prefix-cache hit counter
  # must land in the monitor JSONL, and the inter-token histogram too
  DECODEDIR="$(mktemp -d)"
  JAX_PLATFORMS=cpu THEANOMPI_TPU_MONITOR="$DECODEDIR" python - <<'PYEOF'
import json, os, socket, threading
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
from theanompi_tpu import monitor
from theanompi_tpu.models.base import ModelConfig
from theanompi_tpu.models.transformer import TransformerLM
from theanompi_tpu.serving import (InferenceClient, InferenceServer,
                                   export_model, serve)

mondir = os.environ["THEANOMPI_TPU_MONITOR"]
cfg = ModelConfig(batch_size=4, n_epochs=1, print_freq=0,
                  compute_dtype="float32", optimizer="adamw",
                  learning_rate=1e-3, weight_decay=0.0,
                  lr_schedule="constant")
model = TransformerLM(config=cfg, vocab=32, seq_len=16, n_layers=2,
                      d_model=16, n_heads=2, verbose=False)
params = jax.device_get(model.state.params)
export_dir = os.path.join(mondir, "export")
draft_dir = os.path.join(mondir, "draft")
export_model(model, export_dir, version=0)
# bf16 self-draft: same net quantized — near-total greedy agreement,
# so the accept machinery is exercised without a training run
export_model(model, draft_dir, version=0, weight_dtype="bf16")
with monitor.session(run_dir=mondir, stall_after=float("inf")):
    server = InferenceServer(
        export_dir, replicas=1, reload_poll_s=0, model=model,
        decode=True,
        decode_opts=dict(page_size=4, pages_per_seq=8, max_seqs=4,
                         prefill_buckets=(8,),
                         draft_export_dir=draft_dir,
                         speculate_k=3)).start()
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    ready = threading.Event()
    t = threading.Thread(target=serve,
                         args=(server, "127.0.0.1", port, ready),
                         daemon=True)
    t.start()
    assert ready.wait(30)
    rng = np.random.default_rng(0)
    base = rng.integers(0, 32, 4).astype(np.int32)   # shared page
    warm_prompt = np.concatenate(
        [base, rng.integers(0, 32, 1).astype(np.int32)])
    prompts = [np.concatenate(
        [base, rng.integers(0, 32, n).astype(np.int32)])
        for n in (2, 3)]
    def oracle(p, n):
        cur = [int(x) for x in p]
        out = []
        for _ in range(n):
            lg = np.asarray(model.module.apply(
                {"params": params}, jnp.asarray([cur], jnp.int32),
                train=False, seq_axis=None))
            tok = int(np.argmax(lg[0, -1])); out.append(tok)
            cur.append(tok)
        return out
    clients = [InferenceClient(f"127.0.0.1:{port}") for _ in range(2)]
    # warm stream completes first: registers the shared prefix so the
    # concurrent pair deterministically hits it
    warm_out = clients[0].generate(warm_prompt, 10)
    assert list(warm_out) == oracle(warm_prompt, 10)
    outs = [None, None]
    ths = [threading.Thread(
        target=lambda i=i: outs.__setitem__(
            i, clients[i].generate(prompts[i], 10))) for i in range(2)]
    for th in ths:
        th.start()
    for th in ths:
        th.join(120)
    # every stream token-identical to the uncached flax oracle
    for p, o in zip(prompts, outs):
        assert o is not None and list(o) == oracle(p, 10), (o, p)
    st = clients[0].stats()
    assert st["decode"] is True
    assert st["shared_steps"] >= 1, f"no shared decode step: {st}"
    assert st["accept_rate"] and st["accept_rate"] > 0, \
        f"speculation accepted nothing: {st}"
    assert st["prefix_cache_hits"] >= 1, f"no prefix hit: {st}"
    clients[0].shutdown()
    for c in clients:
        c.close()
    t.join(timeout=5)
    server.stop()
recs = [json.loads(l)
        for l in open(os.path.join(mondir, "metrics_rank0.jsonl"))]
names = {r["name"] for r in recs}
missing = {"decode/intertoken_ms", "decode/tokens_total",
           "decode/steps_total", "decode/accept_rate",
           "decode/draft_tokens_total",
           "decode/prefix_cache_hits_total"} - names
assert not missing, f"snapshot missing decode series: {missing}"
itl = next(r for r in recs if r["name"] == "decode/intertoken_ms")
# 3 streams x 10 tokens, minus each stream's FIRST token (prefill's
# output: queue+prefill latency, excluded from the inter-token SLO);
# rejected draft tokens never enter the histogram either
assert itl["count"] == 27 and "p99" in itl, itl
hits = next(r for r in recs
            if r["name"] == "decode/prefix_cache_hits_total")
assert hits["value"] >= 1, hits
print(f"decode smoke OK: shared_steps={st['shared_steps']}, "
      f"{st['tokens']} tokens / {st['steps']} steps, "
      f"accept_rate {st['accept_rate']:.2f}, "
      f"prefix hits {st['prefix_cache_hits']}, "
      f"intertoken p99 {itl['p99']:.1f}ms in monitor JSONL")
PYEOF
  DECODE_RC=$?
  rm -rf "$DECODEDIR"
  echo "decode smoke rc=$DECODE_RC"
  echo "## frontdoor smoke (disaggregated fleet: router + 2 prefill + 1 decode REAL processes, docs/SERVING.md 'Disaggregated serving')"
  # the ISSUE 17 vertical end-to-end: DisaggregatedFleet spawns real
  # prefill subprocesses and a real decode subprocess, router in the
  # parent; three CONCURRENT client streams generate through the
  # front door (prompt phase on a prefill replica, pages migrated
  # over wire v2, token phase on the decode replica).  The gate
  # asserts greedy determinism across identical prompts, zero sheds,
  # and — via the collector file — that ONE client_generate trace
  # stitches >= 3 PROCESSES with zero orphans and carries the
  # page_migrate span; tools/traces.py --require-procs 3 then
  # confirms the same from the merged stream and prints the critical
  # path.  The batched-prefill additions (docs/SERVING.md "Batched
  # prefill" / "Fleet prefix cache"): concurrent streams must COALESCE
  # into a multi-sequence prefill batch (occupancy > 1 in the monitor
  # JSONL), and a prompt prefilled on the cache authority must FLEET-
  # HIT from the peer replica — shipped pages, byte-identical output,
  # zero leaked leases — instead of recomputing the prefix
  # the toy model's KV pages are ~KB-scale — far under the 64KB
  # default lane floor — so drop the floor for this smoke to prove
  # the disagg page-migration path inherits the lane end-to-end
  FRONTDIR="$(mktemp -d)"
  JAX_PLATFORMS=cpu THEANOMPI_TPU_MONITOR="$FRONTDIR" \
    THEANOMPI_TPU_SHM_MIN_BYTES=256 python - <<'PYEOF'
import os, sys, threading, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
os.environ["THEANOMPI_TPU_TRACE"] = "1"  # before any child spawns
from theanompi_tpu import monitor
from theanompi_tpu.frontdoor.fleet import DisaggregatedFleet
from theanompi_tpu.frontdoor.prefill import PrefillClient
from theanompi_tpu.frontdoor.router import RouterClient
from theanompi_tpu.models.base import ModelConfig
from theanompi_tpu.models.transformer import TransformerLM
from theanompi_tpu.monitor.collector import CollectorProcess
from theanompi_tpu.serving import export_model

mondir = os.environ["THEANOMPI_TPU_MONITOR"]
cfg = ModelConfig(batch_size=4, n_epochs=1, print_freq=0,
                  compute_dtype="float32", optimizer="adamw",
                  learning_rate=1e-3, weight_decay=0.0,
                  lr_schedule="constant")
lm = TransformerLM(config=cfg, vocab=32, seq_len=32, n_layers=2,
                   d_model=16, n_heads=2, verbose=False)
export_dir = os.path.join(mondir, "export")
export_model(lm, export_dir, version=0)
col = CollectorProcess(mondir)  # exports THEANOMPI_TPU_COLLECTOR
try:
    with monitor.session(run_dir=mondir, stall_after=float("inf")), \
         DisaggregatedFleet(export_dir, prefill=2, decode=1,
                            router_host="127.0.0.1", page_size=4,
                            pages_per_seq=8, max_seqs=4,
                            prefill_buckets=(8,),
                            prefill_delay_ms=250.0) as fleet:
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 32, 5).astype(np.int32)
                   for _ in range(2)]
        prompts.append(prompts[0].copy())  # greedy-determinism pair
        outs = [None] * 3

        def gen(i):
            c = RouterClient(fleet.router_addr)
            try:
                with monitor.span("client_generate"):
                    outs[i] = c.generate(prompts[i], 6)
            finally:
                c.close()

        ths = [threading.Thread(target=gen, args=(i,))
               for i in range(3)]
        for th in ths:
            th.start()
        for th in ths:
            th.join(180)
        assert all(o is not None and len(o) == 6 for o in outs), outs
        assert list(outs[0]) == list(outs[2]), (outs[0], outs[2])
        c = RouterClient(fleet.router_addr)
        st = c.stats()
        c.close()
        assert st["streams"] >= 3 and st["shed"] == 0, st
        # batched prefill: 3 concurrent streams round-robin over 2
        # replicas, so ONE replica saw 2 inside the 250ms coalescing
        # window — a multi-sequence batch (fewer batches than prompts)
        addrs = fleet.prefill_group.addresses()
        pstats = []
        for a in addrs:
            pc = PrefillClient(a)
            pstats.append(pc.stats())
            pc.close()
        assert sum(s["prefills"] for s in pstats) >= 3, pstats
        assert any(s["prefills"] > s["prefill_batches"]
                   for s in pstats), \
            f"no multi-sequence prefill batch formed: {pstats}"
        # fleet prefix cache: prefill a FRESH prompt on the authority
        # (replica 0), then the SAME prompt on the peer — the peer has
        # never seen it, so its local prefix hit can only come from
        # pages the authority shipped over the wire; byte-identical
        # pages, and the lease is released (never leaked)
        auth = fleet._authority_addr
        peer = next(a for a in addrs if a != auth)
        pnew = rng.integers(0, 32, 8).astype(np.int32)
        c0, c1 = PrefillClient(auth), PrefillClient(peer)
        try:
            hits0 = c1.stats()["prefix_cache"]["hits"]
            man0, k0, v0 = c0.prefill(pnew)
            man1, k1, v1 = c1.prefill(pnew)
            assert man0["first_token"] == man1["first_token"], \
                (man0, man1)
            # the shipped PREFIX page (pages axis 1) is byte-verbatim
            # on the peer — shipped, not recomputed; suffix pages are
            # extend-computed and only token-identity is pinned
            assert np.array_equal(np.asarray(k0)[:, 0],
                                  np.asarray(k1)[:, 0])
            assert np.array_equal(np.asarray(v0)[:, 0],
                                  np.asarray(v1)[:, 0])
            st1 = c1.stats()
            assert st1["prefix_cache"]["hits"] >= hits0 + 1, \
                f"peer never fleet-hit the authority's prefix: {st1}"
            st0 = c0.stats()
            assert st0["fleet_cache_leases"] == 0, \
                f"authority leaked a fleet-cache lease: {st0}"
        finally:
            c0.close()
            c1.close()
        time.sleep(3.0)  # let the role exporters flush their tails
                         # (metric snapshots ship every ~2s)
    # the fleet file now carries client+router / prefill / decode
    cst = col.stats()
    assert cst and cst["events"] > 0 and cst["senders"] >= 3, cst
    # monitor JSONL: the batched-prefill occupancy histogram and the
    # fleet-cache hit/ship counters crossed the collector (snapshots
    # are cumulative — take the max each series ever reported)
    import json
    occ = 0.0
    fleet_hits = 0.0
    ship_bytes = 0.0
    for line in open(os.path.join(mondir, "fleet.jsonl")):
        rec = json.loads(line)
        if rec.get("event") != "metrics":
            continue
        for s in rec.get("snapshot", []):
            if s["name"] == "frontdoor/prefill_batch_occupancy":
                occ = max(occ, s.get("max") or 0.0)
            elif (s["name"] == "frontdoor/fleet_cache_lookups_total"
                  and s.get("labels", {}).get("result") == "hit"):
                fleet_hits = max(fleet_hits, s["value"])
            elif s["name"] == "decode/fleet_cache_ship_bytes_total":
                ship_bytes = max(ship_bytes, s["value"])
    assert occ > 1, \
        f"prefill_batch_occupancy max {occ} <= 1 in monitor JSONL"
    assert fleet_hits >= 1, \
        "no fleet-cache hit reached the monitor JSONL"
    assert ship_bytes > 0, \
        "fleet-cache hit shipped zero page bytes"
    sys.path.insert(0, os.path.join(os.getcwd(), "tools"))
    import traces as traces_tool
    records = traces_tool.load_events(os.path.join(mondir,
                                                   "fleet.jsonl"))
    tr = traces_tool.assemble(records)
    gen_tr = [s for s in tr.values()
              if any(x["name"] == "client_generate" for x in s)]
    assert gen_tr, "no client_generate trace reached the collector"
    full = [s for s in gen_tr
            if len(traces_tool.processes_of(s)) >= 3
            and not traces_tool.orphans(s)]
    assert full, [(len(s), sorted(traces_tool.processes_of(s)),
                   len(traces_tool.orphans(s))) for s in gen_tr]
    names = [x["name"] for x in full[0]]
    assert any("page_migrate" in n for n in names), names
    print(f"frontdoor smoke OK: {st['streams']} streams through "
          f"router+prefill+decode, stitched trace spans "
          f"{len(traces_tool.processes_of(full[0]))} processes "
          f"({len(full[0])} spans, 0 orphans, page_migrate present), "
          f"prefill batch occupancy max {occ:.0f}, "
          f"{fleet_hits:.0f} fleet-cache hit(s) shipped "
          f"{ship_bytes:.0f} page bytes")
finally:
    col.stop()
PYEOF
  FRONTDOOR_RC=$?
  if [ "$FRONTDOOR_RC" -eq 0 ]; then
    # the consumer tool over the SAME merged file: traces.py must
    # confirm the >=3-process orphan-free trace and print its
    # critical path
    python tools/traces.py "$FRONTDIR" --require-procs 3 \
      > "$FRONTDIR/traces.out" 2>&1
    FTRACES_RC=$?
    grep -q "critical path" "$FRONTDIR/traces.out" || FTRACES_RC=1
    sed -n '1,8p' "$FRONTDIR/traces.out"
    FRONTDOOR_RC=$FTRACES_RC
  fi
  # page migration + fleet-cache ship between same-host replicas must
  # have granted the lane and moved KV pages out-of-band
  if [ "$FRONTDOOR_RC" -eq 0 ]; then
    shm_lane_evidence "$FRONTDIR" "disagg kv pages" || FRONTDOOR_RC=1
  fi
  rm -rf "$FRONTDIR"
  echo "frontdoor smoke rc=$FRONTDOOR_RC"
  echo "## exchange-bench smoke (wire v1 vs v2 over real sockets, docs/DESIGN.md 'Wire protocol v2')"
  # the comms vertical end-to-end: drive the ~25M-param ResNet-50-sized
  # tree through the param service in every protocol x compression x
  # dtype mode; the gate asserts v2-framed beats v1-pickle on
  # bytes/exchange (lossless zlib/f32 AND the >=45% bf16 headline cut)
  # and that the wire compression-ratio gauge landed in the monitor
  # JSONL (tools/bench_exchange.py --smoke, exit 1 on any miss)
  EXCHDIR="$(mktemp -d)"
  JAX_PLATFORMS=cpu THEANOMPI_TPU_MONITOR="$EXCHDIR" \
    python tools/bench_exchange.py --smoke \
      --out "$EXCHDIR/BENCH_wire_smoke.json"
  EXCHANGE_RC=$?
  rm -rf "$EXCHDIR"
  echo "exchange smoke rc=$EXCHANGE_RC"
  echo "## bucketed-exchange smoke (B=4 in-step bucketing on the 8-dev CPU mesh, docs/DESIGN.md 'Bucketed exchange')"
  # the ISSUE 13 vertical: bucketed exchange programs over the
  # ResNet-50-sized tree on the 8-device CPU mesh.  The gate asserts
  # (a) a real B=4 train step is BIT-identical to B=1 over 3
  # iterations (bucketing is scheduling, never numerics) and (b) the
  # bsp/exchange_buckets gauge landed in the monitor JSONL
  # (tools/bench_exchange.py --buckets 4 --smoke, exit 1 on any miss)
  BUCKETDIR="$(mktemp -d)"
  JAX_PLATFORMS=cpu THEANOMPI_TPU_MONITOR="$BUCKETDIR" \
    python tools/bench_exchange.py --buckets 4 --smoke \
      --out "$BUCKETDIR/BENCH_bucketed_smoke.json"
  BUCKET_RC=$?
  rm -rf "$BUCKETDIR"
  echo "bucketed-exchange smoke rc=$BUCKET_RC"
  echo "## shard smoke (2-shard EASGD over real sockets + kill-recovery, docs/DESIGN.md 'Sharded parameter service')"
  # the sharded-center vertical end-to-end: two REAL shard processes,
  # the router's concurrent leaf-range exchanges, and the fault leg —
  # shard 0 is hard-killed, the process group relaunches it, and the
  # per-shard session rejoin re-seeds only its leaf range.  The gate
  # asserts the K=2 aggregate wall beats K=1, BOTH shards served
  # traffic (per-shard shard_exchange spans in the monitor JSONL), and
  # the recovery events (client reconnect + shard relaunch) landed
  SHARDDIR="$(mktemp -d)"
  JAX_PLATFORMS=cpu THEANOMPI_TPU_MONITOR="$SHARDDIR" \
    python tools/bench_exchange.py --smoke --shards 2 \
      --out "$SHARDDIR/BENCH_shard_smoke.json"
  SHARD_RC=$?
  # same-host shards: the shm lane must have granted and carried the
  # exchange payload out-of-band (docs/DESIGN.md 'Shared-memory lane')
  if [ "$SHARD_RC" -eq 0 ]; then
    shm_lane_evidence "$SHARDDIR" "shard exchange" || SHARD_RC=1
  fi
  rm -rf "$SHARDDIR"
  echo "shard smoke rc=$SHARD_RC"
  echo "## hierarchy smoke (4 local workers -> 1 aggregator -> 2 real shard processes, docs/DESIGN.md 'Hierarchical exchange')"
  # the ISSUE 14 vertical: intra-host aggregation in front of a real
  # 2-shard fleet.  The gate asserts wire bytes/period land FLAT in N
  # (>= 3.9x below the 4-worker direct-exchange baseline), the ASGD
  # delta-sum byte-identity + EASGD closed-form trajectory pins, and
  # the monitor evidence — aggregate/fan_in gauge at 4 and
  # local_aggregate spans in the JSONL
  # (tools/bench_exchange.py --local-workers 4 --shards 2 --smoke)
  HIERDIR="$(mktemp -d)"
  JAX_PLATFORMS=cpu THEANOMPI_TPU_MONITOR="$HIERDIR" \
    python tools/bench_exchange.py --local-workers 4 --shards 2 \
      --smoke --out "$HIERDIR/BENCH_hierarchy_smoke.json"
  HIER_RC=$?
  rm -rf "$HIERDIR"
  echo "hierarchy smoke rc=$HIER_RC"
  echo "## rpc soak (mux byte-identity under sustained load, docs/DESIGN.md 'RPC substrate')"
  # the gate behind the SHARD_MUX/INGEST_MUX ON defaults: muxed
  # streams hammer identity-checked center reads with interleaved
  # large gossip frames on BOTH loops; the threaded loop doubles as
  # the dedicated-socket fallback proof (tools/bench_rpc.py --soak)
  SOAKDIR="$(mktemp -d)"
  JAX_PLATFORMS=cpu \
    python tools/bench_rpc.py --soak --dur 4 --payload-kb 64 \
      --out "$SOAKDIR/BENCH_rpc_soak.json"
  SOAK_RC=$?
  rm -rf "$SOAKDIR"
  echo "rpc soak rc=$SOAK_RC"
  echo "## ingest smoke (2-reader fleet over real sockets + kill-recovery, docs/DESIGN.md 'Distributed ingest')"
  # the distributed-ingest vertical end-to-end: two REAL reader
  # processes serving a real mmap shard tree to trainer worker
  # processes over pipelined wire-v2 raw batch frames.  The gate
  # asserts N=2 aggregate img/s >= 1.7x N=1 at identical total bytes,
  # BOTH readers served their ranges (per-reader ingest_pull spans +
  # served counters), and the kill leg recovered — reader 0 SIGKILLed
  # mid-epoch, the client fails over (stream completes), the fleet
  # watcher relaunches it, and the recovery counters land in the
  # monitor JSONL (tools/bench_ingest.py --smoke, exit 1 on any miss)
  INGESTDIR="$(mktemp -d)"
  JAX_PLATFORMS=cpu THEANOMPI_TPU_MONITOR="$INGESTDIR" \
    python tools/bench_ingest.py --smoke \
      --out "$INGESTDIR/BENCH_ingest_smoke.json"
  INGEST_RC=$?
  # same-host readers: batch frames must have ridden the shm lane
  if [ "$INGEST_RC" -eq 0 ]; then
    shm_lane_evidence "$INGESTDIR" "ingest batches" || INGEST_RC=1
  fi
  rm -rf "$INGESTDIR"
  echo "ingest smoke rc=$INGEST_RC"
  echo "## rpc smoke (concurrent-connection scaling on the selector event plane, docs/DESIGN.md 'RPC substrate')"
  # the event-plane vertical end-to-end: a REAL service process
  # (selector loop, pinned to one core) fronting hundreds of
  # concurrent authenticated connections, every one with a pull in
  # flight.  The gate asserts flat per-connection p99 across the
  # scaling points, the >=10x recovery of the committed PR-9
  # GIL-convoy baseline at the 12-client point, and the monitor JSONL
  # evidence (rpc/connections_total + service/requests_total from the
  # server process) — tools/bench_rpc.py --smoke, exit 1 on any miss.
  # 200-conn top point here (preflight's >=200-client bar); the
  # committed artifacts/BENCH_rpc_smoke.json carries the full
  # 1000-connection run.
  RPCDIR="$(mktemp -d)"
  JAX_PLATFORMS=cpu \
    python tools/bench_rpc.py --smoke --conns 8,200 --dur 3 \
      --out "$RPCDIR/BENCH_rpc_smoke.json"
  RPC_RC=$?
  rm -rf "$RPCDIR"
  echo "rpc smoke rc=$RPC_RC"
  if [ "$TMLINT_RC" -ne 0 ] || [ "$GATE_RC" -ne 0 ] || [ "$PYTEST_RC" -ne 0 ] || [ "$ENTRY_RC" -ne 0 ] || [ "$MONITOR_RC" -ne 0 ] || [ "$COLLECTOR_RC" -ne 0 ] || [ "$RESILIENCE_RC" -ne 0 ] || [ "$SERVING_RC" -ne 0 ] || [ "$DECODE_RC" -ne 0 ] || [ "$FRONTDOOR_RC" -ne 0 ] || [ "$EXCHANGE_RC" -ne 0 ] || [ "$BUCKET_RC" -ne 0 ] || [ "$SHARD_RC" -ne 0 ] || [ "$HIER_RC" -ne 0 ] || [ "$SOAK_RC" -ne 0 ] || [ "$INGEST_RC" -ne 0 ] || [ "$RPC_RC" -ne 0 ]; then
    echo "PREFLIGHT: FAIL"
    [ "$TMLINT_RC" -ne 0 ] && echo "PREFLIGHT: tmlint --gate found NEW findings — fix or baseline with a reason (docs/ANALYSIS.md)"
    [ "$GATE_RC" -ne 0 ] && echo "PREFLIGHT: the -m gate subset itself failed — do NOT snapshot"
    exit 1
  fi
  echo "PREFLIGHT: GREEN"
} 2>&1 | tee "$LOG"
exit "${PIPESTATUS[0]}"
