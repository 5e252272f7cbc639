"""Launchers — ``tmlocal`` (single host) and ``tmlauncher`` (multi-host).

Parity surface of the reference's console entry points (SURVEY.md
§2.1 — mount empty, no file:line): ``tmlauncher <rule> ...`` composed
an ``mpirun`` command with one rank per GPU; ``tmlocal`` was the
single-node variant.

TPU-native inversion (deliberate divergence, SURVEY.md §7.6): there is
no process-per-device.  ``tmlocal`` runs the rule in-process over all
(or the requested) local chips — BSP is one SPMD program, async rules
are worker threads.  ``tmlauncher`` is the multi-host form: run the
SAME command on every host with ``--coordinator host:port --nhosts N
--host-id i``; it calls ``jax.distributed.initialize`` so the hosts
form one global mesh over DCN, then runs the rule across
``jax.devices()`` (one process per HOST, not per chip).

Usage (matches the reference's shape):
    tmlocal BSP -D 8 -m theanompi_tpu.models.cifar10 -c Cifar10_model
    tmlauncher BSP --coordinator host0:1234 --nhosts 2 --host-id 0 \
        -m theanompi_tpu.models.resnet50 -c ResNet50
"""

from __future__ import annotations

import argparse
import sys

from theanompi_tpu.models import MODEL_ZOO

#: SERVE is the inference mode (theanompi_tpu/serving, docs/SERVING.md)
#: — same entry point so one operator surface covers train AND serve
RULES = ("BSP", "EASGD", "ASGD", "GOSGD", "SERVE")


def _build_parser(multihost: bool) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tmlauncher" if multihost else "tmlocal",
        description=__doc__.split("\n")[0],
    )
    p.add_argument("rule", choices=RULES, help="parallel training rule")
    p.add_argument("-m", "--modelfile",
                   default="theanompi_tpu.models.cifar10",
                   help="model module path, or a zoo shortname "
                        f"({', '.join(MODEL_ZOO)})")
    p.add_argument("-c", "--modelclass", default=None,
                   help="model class name (inferred for zoo shortnames)")
    p.add_argument("-D", "--devices", type=int, default=None,
                   help="number of local devices (default: all)")
    p.add_argument("--epochs", type=int, default=None,
                   help="cap the number of epochs (for smoke runs)")
    p.add_argument("--resume", action="store_true")
    p.add_argument("--sync-type", default="avg", choices=("avg", "cdd"))
    p.add_argument("--model-parallel", type=int, default=1,
                   help="BSP: tensor-parallel degree (devices on the "
                        "'model' mesh axis; use with transformer_lm_tp)")
    p.add_argument("--seq-parallel", type=int, default=1,
                   help="BSP: sequence-parallel degree (devices on the "
                        "'seq' axis; ring attention for transformer_lm)")
    p.add_argument("--pipe-parallel", type=int, default=1,
                   help="BSP: pipeline-parallel degree (devices on the "
                        "'pipe' axis; use with transformer_lm_pp)")
    p.add_argument("--expert-parallel", type=int, default=1,
                   help="BSP: expert-parallel degree (devices on the "
                        "'expert' axis; use with transformer_lm_moe)")
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--lr", type=float, default=None)
    p.add_argument("--snapshot-dir", default=None)
    p.add_argument("--set", action="append", default=[], metavar="K=V",
                   dest="config_sets",
                   help="override any ModelConfig field, repeatable "
                        "(e.g. --set optimizer=lars --set "
                        "warmup_epochs=5 --set lr_schedule=cosine); "
                        "values are parsed by the field's declared type")
    p.add_argument("--tau", type=int, default=10, help="EASGD sync period")
    p.add_argument("--alpha", type=float, default=0.5,
                   help="EASGD elastic coefficient")
    p.add_argument("--p-push", type=float, default=0.1,
                   help="GOSGD per-iteration push probability")
    p.add_argument("--merge-momentum", default="scale",
                   choices=("scale", "keep"),
                   help="GOSGD: scale momentum by the receiver's share "
                        "on each merge (default — prevents the measured "
                        "stale-momentum divergence over slow links, see "
                        "docs/SCALING.md) or keep it untouched")
    p.add_argument("--server-addr", default=None,
                   help="host:port of a tmserver parameter service — runs "
                        "the async rule's server over DCN instead of "
                        "in-process (parallel/service.py).  A "
                        "comma-separated list names a SHARD FLEET: the "
                        "center is leaf-range-partitioned across the "
                        "listed shard services (parallel/shards.py; "
                        "EASGD/ASGD only)")
    p.add_argument("--shards", type=int, default=None, metavar="K",
                   help="EASGD/ASGD, single-host: spawn and supervise K "
                        "shard service processes and partition the "
                        "center across them (docs/DESIGN.md 'Sharded "
                        "parameter service').  A crashed shard is "
                        "relaunched (budget --max-restarts, default 1) "
                        "and the workers' per-shard session rejoin "
                        "re-seeds only its leaf range.  Multi-host runs "
                        "point every host at one fleet via a "
                        "comma-separated --server-addr instead")
    p.add_argument("--ingest", default=None, metavar="ADDR[,ADDR...]",
                   help="distributed ingest (theanompi_tpu/ingest, "
                        "docs/DESIGN.md 'Distributed ingest'): pull "
                        "train batches from a standalone reader fleet "
                        "instead of the in-process loader.  ONE "
                        "address names the fleet's coordinator; a "
                        "comma-separated list names the readers "
                        "directly (static fleet, plan derived "
                        "client-side).  The stream is byte-identical "
                        "to the local loader for the same dataset "
                        "seed; exported as THEANOMPI_TPU_INGEST so "
                        "every epoch's loader (and any subprocess) "
                        "picks it up.  Start a fleet with tmingest or "
                        "python -m theanompi_tpu.ingest.fleet")
    p.add_argument("--overlap-exchange", action="store_true",
                   help="EASGD/ASGD: run each worker's parameter "
                        "exchange on a dedicated thread so compute "
                        "overlaps the RPC (bounded staleness 1; "
                        "docs/DESIGN.md 'Overlapped exchange')")
    p.add_argument("--local-aggregation", action="store_true",
                   help="EASGD/ASGD: aggregate this host's worker "
                        "exchanges in-process so N local workers cost "
                        "ONE wire exchange per shard per period — ASGD "
                        "delta-sums the gradient pushes, EASGD "
                        "composes the elastic displacements against "
                        "one center version (docs/DESIGN.md "
                        "'Hierarchical exchange').  Workers fall back "
                        "to direct exchange if the aggregation plane "
                        "goes down; composes with --overlap-exchange "
                        "(the aggregate rides the exchange threads) "
                        "and --shards/--server-addr fleets")
    p.add_argument("--wire-protocol", default=None,
                   choices=("v1", "v2"),
                   help="param-service transport: v2 framed zero-copy "
                        "(default) or v1 pickle (legacy); exported as "
                        "THEANOMPI_TPU_WIRE_PROTOCOL so every client "
                        "this run spawns inherits it")
    p.add_argument("--wire-compression", default=None,
                   choices=("none", "zlib"),
                   help="v2 wire payload compression "
                        "(THEANOMPI_TPU_WIRE_COMPRESSION)")
    p.add_argument("--wire-dtype", default=None, choices=("f32", "bf16"),
                   help="v2 wire dtype: bf16 halves param/grad bytes on "
                        "the wire; f32 accumulation at the service is "
                        "preserved (THEANOMPI_TPU_WIRE_DTYPE)")
    p.add_argument("--n-total-workers", type=int, default=None,
                   help="GOSGD: global worker count when several hosts "
                        "share one --server-addr hub")
    p.add_argument("--rank-offset", type=int, default=0,
                   help="GOSGD: this host's first global worker rank")
    p.add_argument("--session-id", default=None,
                   help="shared id scoping the --server-addr service "
                        "store; hosts of ONE training session must pass "
                        "the same id (default: a fresh uuid per session)")
    p.add_argument("--platform", default=None,
                   help="force a jax platform (e.g. 'cpu' with "
                        "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
                        "for the virtual test mesh)")
    p.add_argument("--result-json", default=None, metavar="PATH",
                   help="write the session result (val metrics + scalar "
                        "rule stats, e.g. GOSGD gossip weights, EASGD "
                        "n_exchanges) as JSON — param trees are omitted")
    # default None: training resolves to 0 (the reference's fail-fast
    # behavior), SERVE to 2 (serving defaults to supervised recovery —
    # serve_main and `python -m ...serving.server` already do; the
    # launcher must not silently disable it)
    p.add_argument("--max-restarts", type=int, default=None, metavar="N",
                   help="resilience (docs/RESILIENCE.md): async rules "
                        "restart a crashed worker thread from the center "
                        "params up to N times (quorum-bounded); under "
                        "tmlocal any rule additionally auto-resumes a "
                        "crashed session from its latest verified "
                        "checkpoint up to N times (requires "
                        "checkpointing, the default).  Session "
                        "auto-resume is single-host only — one host of "
                        "a tmlauncher SPMD program cannot rejoin the "
                        "collectives its peers are mid-flight in. "
                        "0 = the reference's fail-fast behavior.  "
                        "SERVE: per-replica restart-from-export budget "
                        "(docs/SERVING.md)")
    p.add_argument("--fault-plan", default=None, metavar="PATH|JSON",
                   help="activate the deterministic fault-injection "
                        "plane with this plan (a JSON file path or "
                        "inline JSON; docs/RESILIENCE.md); equivalent "
                        "to setting THEANOMPI_TPU_FAULTS — exported so "
                        "subprocesses inherit it")
    p.add_argument("--export-dir", default=None, metavar="DIR",
                   help="SERVE: versioned model-export directory "
                        "(serving/export.py export_model writes it; "
                        "required for the SERVE rule, which watches it "
                        "for new versions to hot-reload)")
    p.add_argument("--port", type=int, default=None,
                   help="SERVE: listen port (default 45900)")
    p.add_argument("--serve-host", default="0.0.0.0",
                   help="SERVE: listen address")
    p.add_argument("--serve-replicas", type=int, default=1,
                   help="SERVE: inference replica count (each with its "
                        "own queue + batcher)")
    p.add_argument("--max-batch", type=int, default=8,
                   help="SERVE: max rows per coalesced batch")
    p.add_argument("--max-delay-ms", type=float, default=5.0,
                   help="SERVE: max wait for a batch to fill before it "
                        "dispatches anyway")
    p.add_argument("--serve-buckets", default=None, metavar="N,N,...",
                   help="SERVE: padded batch sizes (pre-compiled "
                        "shapes; default powers of two up to "
                        "--max-batch)")
    p.add_argument("--max-queue", type=int, default=32,
                   help="SERVE: admission bound — pending requests "
                        "beyond this are rejected with Overloaded "
                        "instead of queued (docs/SERVING.md)")
    p.add_argument("--reload-poll-s", type=float, default=1.0,
                   help="SERVE: export-dir poll interval for hot "
                        "reload (0 disables the watcher)")
    p.add_argument("--decode", action="store_true",
                   help="SERVE: autoregressive decode mode "
                        "(theanompi_tpu/decode, docs/SERVING.md): "
                        "paged KV-cache + continuous batching over a "
                        "TransformerLM export; clients use the "
                        "GENERATE wire op (InferenceClient.generate)")
    p.add_argument("--decode-page-size", type=int, default=16,
                   help="SERVE --decode: tokens per KV-cache page")
    p.add_argument("--decode-pages-per-seq", type=int, default=8,
                   help="SERVE --decode: pages per live sequence — "
                        "page_size x pages_per_seq is the attention "
                        "window; older tokens ring-evict")
    p.add_argument("--decode-max-seqs", type=int, default=8,
                   help="SERVE --decode: max concurrently-decoding "
                        "sequences per replica")
    p.add_argument("--decode-max-pending", type=int, default=32,
                   help="SERVE --decode: admission bound — pending "
                        "prompts beyond this are rejected with "
                        "Overloaded")
    p.add_argument("--decode-prefill-buckets", default=None,
                   metavar="N,N,...",
                   help="SERVE --decode: padded prompt-length buckets "
                        "(default powers of two up to min(512, "
                        "max_len))")
    p.add_argument("--decode-draft-export-dir", default=None,
                   metavar="DIR",
                   help="SERVE --decode: speculative decoding — a "
                        "small decode-capable export proposing tokens "
                        "the target verifies k-at-a-time in one "
                        "bucketed step (docs/SERVING.md 'Speculative "
                        "decode'); dims may differ, vocab must match")
    p.add_argument("--decode-speculate-k", type=int, default=4,
                   help="SERVE --decode: draft tokens per speculative "
                        "round (needs --decode-draft-export-dir)")
    p.add_argument("--decode-no-prefix-cache", action="store_true",
                   help="SERVE --decode: disable the cross-request "
                        "prefix cache (copy-on-write KV page sharing "
                        "is on by default — docs/SERVING.md 'Prefix "
                        "cache')")
    p.add_argument("--decode-prefill-batch", type=int, default=8,
                   help="SERVE --decode: max prompts coalesced into "
                        "ONE batched prefill program call per "
                        "admission round (1 = serial prefill — "
                        "docs/SERVING.md 'Batched prefill')")
    p.add_argument("--decode-prefill-delay-ms", type=float,
                   default=2.0,
                   help="SERVE --decode: how long the oldest pending "
                        "prompt may wait for batch company before its "
                        "prefill launches anyway")
    p.add_argument("--decode-fleet-cache", default=None,
                   metavar="HOST:PORT",
                   help="SERVE --decode: fleet-wide prefix-cache "
                        "authority (a prefill server) consulted on "
                        "local prefix-cache misses — docs/SERVING.md "
                        "'Fleet prefix cache'")
    p.add_argument("--disaggregate", action="store_true",
                   help="SERVE --decode: split the deployment into a "
                        "prefill fleet + decode fleet behind the "
                        "front-door router (theanompi_tpu/frontdoor, "
                        "docs/SERVING.md 'Disaggregated serving'); "
                        "--serve-replicas sizes the decode fleet")
    p.add_argument("--prefill-replicas", type=int, default=1,
                   help="SERVE --disaggregate: initial prefill "
                        "replica count")
    p.add_argument("--autoscale", action="store_true",
                   help="SERVE --disaggregate: grow/shrink both roles "
                        "from load signals (frontdoor/autoscale.py)")
    p.add_argument("--scale-max", type=int, default=4,
                   help="SERVE --disaggregate --autoscale: max "
                        "replicas per role (the fleet budget)")
    p.add_argument("--slo-p99-ms", type=float, default=None,
                   help="SERVE --disaggregate --autoscale: intertoken "
                        "p99 target feeding the decode scale signal")
    p.add_argument("--monitor-dir", default=None, metavar="DIR",
                   help="enable the telemetry subsystem and write its "
                        "artifacts (metrics snapshot JSONL + Prometheus "
                        "dump, per-rank heartbeat, crash postmortem) "
                        "under DIR; equivalent to setting "
                        "THEANOMPI_TPU_MONITOR=DIR "
                        "(docs/OBSERVABILITY.md)")
    p.add_argument("--collector", action="store_true",
                   help="spawn + supervise a telemetry collector for "
                        "this run (monitor/collector.py): every process "
                        "ships span/metric events to ONE merged "
                        "fleet.jsonl under --monitor-dir (required); "
                        "enables distributed tracing "
                        "(THEANOMPI_TPU_TRACE=1, unless already set) "
                        "and exports THEANOMPI_TPU_COLLECTOR so shard/"
                        "reader/serve subprocesses ship too.  Inspect "
                        "with tools/traces.py and tools/tmtop.py "
                        "(docs/OBSERVABILITY.md 'Distributed tracing')")
    if multihost:
        p.add_argument("--coordinator", required=True,
                       help="host:port of host 0 (jax.distributed)")
        p.add_argument("--nhosts", type=int, required=True)
        p.add_argument("--host-id", type=int, required=True)
    return p


def _parse_config_sets(pairs: list[str]) -> dict:
    """``--set k=v`` strings → typed ModelConfig overrides (the typed
    escape hatch so every new config field doesn't need its own flag)."""
    import dataclasses

    from theanompi_tpu.models.base import ModelConfig

    fields = {f.name: f for f in dataclasses.fields(ModelConfig)}
    out: dict = {}
    for pair in pairs:
        key, sep, raw = pair.partition("=")
        if not sep:
            raise SystemExit(f"--set expects K=V, got {pair!r}")
        if key not in fields:
            raise SystemExit(f"--set: unknown ModelConfig field {key!r}; "
                             f"valid: {', '.join(sorted(fields))}")
        default = fields[key].default
        low = raw.lower()
        if low in ("none", "null") and default is None:
            # only nullable fields (declared default None) accept it
            out[key] = None
        elif isinstance(default, bool):
            if low not in ("true", "false", "1", "0"):
                raise SystemExit(f"--set {key}: expected a bool, got {raw!r}")
            out[key] = low in ("true", "1")
        else:
            try:
                if isinstance(default, int):
                    out[key] = int(raw)
                elif isinstance(default, float):
                    out[key] = float(raw)
                elif isinstance(default, tuple):
                    out[key] = tuple(
                        float(x) if "." in x else int(x)
                        for x in raw.split(",") if x != "")
                else:
                    out[key] = raw
            except ValueError:
                raise SystemExit(
                    f"--set {key}: expected a "
                    f"{type(default).__name__}, got {raw!r}") from None
    return out


def _resolve_model(args) -> tuple[str, str]:
    if args.modelfile in MODEL_ZOO:
        mod, cls = MODEL_ZOO[args.modelfile]
        return mod, args.modelclass or cls
    if args.modelclass is None:
        raise SystemExit("--modelclass is required for a custom --modelfile")
    return args.modelfile, args.modelclass


def _run(args, multihost: bool) -> int:
    """Collector seam around the session: the collector must be up
    (and ``THEANOMPI_TPU_COLLECTOR`` exported) BEFORE any monitor
    session activates — the exporter reads the address once at session
    start — and must outlive the session's final flush."""
    collector = None
    if getattr(args, "collector", False):
        if multihost:
            # one collector per RUN, not per host: start it once
            # (python -m theanompi_tpu.monitor.collector) and export
            # THEANOMPI_TPU_COLLECTOR on every host instead
            raise SystemExit(
                "--collector is single-host (tmlocal spawns the "
                "collector process); multi-host runs start one "
                "collector and export THEANOMPI_TPU_COLLECTOR=host:port "
                "on every host")
        if not args.monitor_dir:
            raise SystemExit("--collector requires --monitor-dir (the "
                             "merged fleet.jsonl lands there)")
        import os

        # export before spawning so the collector's own artifacts land
        # under the run dir too
        os.environ["THEANOMPI_TPU_MONITOR"] = args.monitor_dir
        from theanompi_tpu.monitor.collector import CollectorProcess

        collector = CollectorProcess(args.monitor_dir)
        # a collector without tracing still merges fleet metrics, but
        # the flag's point is the one-timeline view — turn tracing on
        # unless the operator pinned it (e.g. =0 to sample metrics only)
        os.environ.setdefault("THEANOMPI_TPU_TRACE", "1")
    try:
        return _run_session(args, multihost)
    finally:
        if collector is not None:
            collector.stop()


def _run_session(args, multihost: bool) -> int:
    if args.monitor_dir:
        # the env var is THE activation channel: the rule session, the
        # recorder, the service clients, and any subprocess this run
        # spawns all read it (theanompi_tpu/monitor)
        import os

        os.environ["THEANOMPI_TPU_MONITOR"] = args.monitor_dir
    for flag, env in (("wire_protocol", "THEANOMPI_TPU_WIRE_PROTOCOL"),
                      ("wire_compression",
                       "THEANOMPI_TPU_WIRE_COMPRESSION"),
                      ("wire_dtype", "THEANOMPI_TPU_WIRE_DTYPE")):
        value = getattr(args, flag, None)
        if value:
            # env is the channel: ServiceClient reads it at connect,
            # and subprocesses this run spawns inherit it
            import os

            os.environ[env] = value
    if args.fault_plan:
        import os

        os.environ["THEANOMPI_TPU_FAULTS"] = args.fault_plan
        # the package may already be imported (env read at import
        # happened before argv parsing) — re-read explicitly
        from theanompi_tpu.resilience import faults

        faults.install_from_env()
    if args.ingest:
        if args.rule == "SERVE":
            raise SystemExit("--ingest feeds TRAINING batches; the "
                             "SERVE rule has no train loader")
        if multihost:
            # a multi-host SPMD program slices each global batch per
            # host locally; silently ignoring the flag would let the
            # user believe the fleet is feeding the run when it is not
            raise SystemExit(
                "--ingest is single-host for now (each host of a "
                "tmlauncher program feeds its own slice); run the "
                "readers co-located with each host instead")
        import os

        from theanompi_tpu.ingest.protocol import ingest_addresses

        try:
            ingest_addresses(args.ingest)  # fail fast on a bad spec
        except ValueError as e:
            raise SystemExit(f"--ingest: {e}") from None
        # env is the channel: models/base.py begin_epoch reads it each
        # epoch, and subprocesses this run spawns inherit it
        os.environ["THEANOMPI_TPU_INGEST"] = args.ingest
    if args.platform:
        import jax

        # must land before the first backend touch
        jax.config.update("jax_platforms", args.platform)
    from theanompi_tpu.utils.helper_funcs import enable_compilation_cache

    enable_compilation_cache()
    if args.decode and args.rule != "SERVE":
        # silently ignoring the flag would let the user believe the
        # decode plane is live when it is not
        raise SystemExit("--decode is a SERVE option "
                         "(tmlocal SERVE --decode ...)")
    if args.rule == "SERVE":
        # inference mode (theanompi_tpu/serving): no rule session, no
        # model resolution — the export's metadata names the model
        if multihost:
            raise SystemExit("SERVE is single-host (run one server per "
                             "host behind your load balancer)")
        if not args.export_dir:
            raise SystemExit("SERVE requires --export-dir (see "
                             "serving/export.py export_model)")
        from theanompi_tpu.serving.server import (
            DEFAULT_PORT,
            decode_opts_from_args,
            serve_main,
        )

        buckets = (tuple(int(b) for b in args.serve_buckets.split(","))
                   if args.serve_buckets else None)
        if args.disaggregate:
            if not args.decode:
                # prefill/decode disaggregation only exists on the
                # decode plane — the eval server has no KV pages
                raise SystemExit("--disaggregate requires --decode "
                                 "(tmlocal SERVE --decode "
                                 "--disaggregate ...)")
            from theanompi_tpu.frontdoor import fleet as frontdoor_fleet
            from theanompi_tpu.frontdoor.router import (
                DEFAULT_PORT as ROUTER_PORT,
            )

            pb = (tuple(int(b)
                        for b in args.decode_prefill_buckets.split(","))
                  if args.decode_prefill_buckets else None)
            return frontdoor_fleet.run_foreground(
                export_dir=args.export_dir,
                prefill=args.prefill_replicas,
                decode=args.serve_replicas,
                router_host=args.serve_host,
                router_port=(args.port if args.port is not None
                             else ROUTER_PORT),
                page_size=args.decode_page_size,
                pages_per_seq=args.decode_pages_per_seq,
                max_seqs=args.decode_max_seqs,
                prefill_buckets=pb,
                decode_max_pending=args.decode_max_pending,
                prefix_cache=not args.decode_no_prefix_cache,
                prefill_batch=args.decode_prefill_batch,
                prefill_delay_ms=args.decode_prefill_delay_ms,
                draft_export_dir=args.decode_draft_export_dir,
                speculate_k=args.decode_speculate_k,
                autoscale=args.autoscale, scale_max=args.scale_max,
                slo_p99_ms=args.slo_p99_ms,
                max_restarts=(1 if args.max_restarts is None
                              else args.max_restarts))
        decode_opts = decode_opts_from_args(args)
        return serve_main(
            args.export_dir, host=args.serve_host,
            port=args.port if args.port is not None else DEFAULT_PORT,
            replicas=args.serve_replicas, max_batch=args.max_batch,
            max_delay_ms=args.max_delay_ms, buckets=buckets,
            max_queue=args.max_queue,
            max_restarts=(2 if args.max_restarts is None
                          else args.max_restarts),
            reload_poll_s=args.reload_poll_s,
            decode=args.decode, decode_opts=decode_opts)
    if multihost:
        import jax

        jax.distributed.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.nhosts,
            process_id=args.host_id,
        )

    import theanompi_tpu as tm

    modelfile, modelclass = _resolve_model(args)
    rule_cls = getattr(tm, args.rule)
    rule = rule_cls()

    config = None
    overrides = {k: v for k, v in (("batch_size", args.batch_size),
                                   ("learning_rate", args.lr),
                                   ("snapshot_dir", args.snapshot_dir))
                 if v is not None}
    overrides.update(_parse_config_sets(args.config_sets))
    if overrides:
        from theanompi_tpu.rules import resolve_model_class
        import dataclasses

        cls = resolve_model_class(modelfile, modelclass)
        config = dataclasses.replace(cls.default_config(), **overrides)

    kwargs = dict(devices=args.devices, modelfile=modelfile,
                  modelclass=modelclass, config=config, resume=args.resume,
                  sync_type=args.sync_type, max_epochs=args.epochs)
    if args.rule == "BSP":
        kwargs.update(model_parallel=args.model_parallel,
                      seq_parallel=args.seq_parallel,
                      pipe_parallel=args.pipe_parallel,
                      expert_parallel=args.expert_parallel)
    elif (args.model_parallel > 1 or args.seq_parallel > 1
          or args.pipe_parallel > 1 or args.expert_parallel > 1):
        raise SystemExit("--model-parallel/--seq-parallel/--pipe-parallel/"
                         "--expert-parallel are BSP options (async rules "
                         "are data-parallel per worker)")
    if args.overlap_exchange and args.rule not in ("EASGD", "ASGD"):
        # BSP overlaps via XLA; GOSGD pushes are already fire-and-forget
        # — silently ignoring the flag would let the user believe the
        # exchange is overlapped when it is not
        raise SystemExit("--overlap-exchange applies to EASGD/ASGD only")
    if args.local_aggregation and args.rule not in ("EASGD", "ASGD"):
        # same refusal matrix as --shards: GOSGD ships whole trees to
        # random peers (nothing to delta-sum) and BSP exchanges inside
        # the step program — silently ignoring the flag would let the
        # user believe the wire cost dropped when it did not
        raise SystemExit(
            "--local-aggregation applies to EASGD/ASGD only: GOSGD "
            "gossip pushes whole (params, weight) trees to random "
            "peers and BSP exchanges in-step via XLA collectives "
            "(docs/DESIGN.md 'Hierarchical exchange')")
    shard_group = None
    if args.shards is not None:
        if args.rule not in ("EASGD", "ASGD"):
            raise SystemExit(
                "--shards applies to EASGD/ASGD only: the GOSGD gossip "
                "hub is unsharded (it rendezvouses whole param trees, "
                "not an accumulating center) and BSP has no parameter "
                "service (docs/DESIGN.md 'Sharded parameter service')")
        if multihost:
            raise SystemExit(
                "--shards is single-host (tmlocal spawns the shard "
                "processes); multi-host runs start the fleet once and "
                "point every host at it with a comma-separated "
                "--server-addr")
        if args.server_addr:
            raise SystemExit(
                "pass either --shards K (spawn a local shard fleet) or "
                "a comma-separated --server-addr (an existing fleet), "
                "not both")
        if args.shards < 1:
            raise SystemExit("--shards must be >= 1")
        from theanompi_tpu.parallel.shards import ShardProcessGroup

        shard_group = ShardProcessGroup(
            args.shards,
            max_restarts=(1 if args.max_restarts is None
                          else args.max_restarts))
        args.server_addr = shard_group.server_addr
    if args.rule == "EASGD":
        kwargs.update(tau=args.tau, alpha=args.alpha)
    elif args.rule == "GOSGD":
        kwargs.update(p_push=args.p_push,
                      n_total_workers=args.n_total_workers,
                      rank_offset=args.rank_offset,
                      merge_momentum=args.merge_momentum)
    if args.rule != "BSP":
        if args.server_addr:
            kwargs.update(server_addr=args.server_addr)
            if args.session_id:
                kwargs.update(session_id=args.session_id)
        if args.overlap_exchange:
            kwargs.update(overlap=True)
        if args.local_aggregation:
            kwargs.update(local_aggregation=True)
        if args.max_restarts:
            # worker-thread supervision (resilience.supervisor) — the
            # first line of defense; the session-level auto-resume
            # below catches what it can't
            kwargs.update(max_restarts=args.max_restarts)
    # session-level auto-resume (docs/RESILIENCE.md): a crashed
    # session restarts from its latest VERIFIED checkpoint — corrupt
    # latest falls back to the previous kept epoch (rules' resume
    # paths go through resilience.recovery).  Single-host only: one
    # host of a multi-host SPMD program resuming alone would issue
    # collectives its peers (blocked mid-all-reduce at a different
    # step) can never match — fail fast on every host instead.
    session_restarts = (0 if multihost
                        else (args.max_restarts or 0))
    attempts = 0
    try:
        while True:
            rule.init(**kwargs)
            try:
                result = rule.wait()
                break
            except Exception as e:
                attempts += 1
                if attempts > session_restarts:
                    raise
                import sys as _sys

                if (args.rule == "GOSGD" and args.server_addr
                        and args.session_id):
                    # a pinned-session-id gossip hub survives the crash
                    # WITH its deactivated ranks and stale in-flight
                    # payloads — resuming into it would refuse gossip to
                    # restarted ranks and merge pre-crash params; the
                    # operator must restart every host with a fresh id
                    print("[resilience] NOT auto-resuming GOSGD: the "
                          f"pinned --session-id {args.session_id!r} hub "
                          "keeps deactivated ranks and stale in-flight "
                          "gossip across a resume; restart all hosts "
                          "with a fresh --session-id", file=_sys.stderr,
                          flush=True)
                    raise
                print(f"[resilience] {args.rule} session died "
                      f"({type(e).__name__}: {e}); auto-resume "
                      f"{attempts}/{session_restarts} from the latest "
                      "verified checkpoint", file=_sys.stderr, flush=True)
                from theanompi_tpu import monitor

                monitor.inc("resilience/session_autoresumes_total")
                kwargs.update(resume=True)
                rule = rule_cls()
    finally:
        if shard_group is not None:
            shard_group.stop()
    val = result.get("val", {})
    if val:
        print("final val:", {k: round(float(v), 4) for k, v in val.items()})
    if args.result_json:
        # tmlauncher runs the SAME command on every host: gate like the
        # recorder's JSONL (rules/bsp.py) so N hosts sharing a
        # filesystem don't clobber one path with nondeterministic data
        if multihost:
            import jax

            write = jax.process_index() == 0
        else:
            write = True
        if write:
            import json

            with open(args.result_json, "w") as f:
                json.dump(_jsonable(result), f)
    return 0


def _jsonable(value):
    """Scalar-only view of a rule result: val metrics, counters, gossip
    weights survive; param/center pytrees (device or numpy arrays) are
    dropped — the snapshot dir is the artifact channel for those."""
    import numpy as np

    if value is None or isinstance(value, (bool, int, float, str)):
        return value
    if isinstance(value, dict):
        kept = {k: v for k, v in ((k, _jsonable(v))
                                  for k, v in value.items())
                if v is not None}
        # a param tree filters down to nested empty dicts — drop it
        # entirely rather than emitting structural noise
        return kept or None
    if isinstance(value, (list, tuple)):
        kept = [_jsonable(v) for v in value]
        return kept if all(v is not None for v in kept) else None
    if np.isscalar(value) or (hasattr(value, "shape")
                              and getattr(value, "shape") == ()):
        try:
            return float(value)
        except (TypeError, ValueError):
            return None
    return None


def tmlocal(argv=None) -> int:
    return _run(_build_parser(False).parse_args(argv), multihost=False)


def tmlauncher(argv=None) -> int:
    return _run(_build_parser(True).parse_args(argv), multihost=True)


def main(argv=None) -> int:  # python -m theanompi_tpu.launcher
    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0] == "--multihost":
        return tmlauncher(argv[1:])
    return tmlocal(argv)


if __name__ == "__main__":
    sys.exit(main())
