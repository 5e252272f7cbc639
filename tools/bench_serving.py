"""Serving load generator — closed- and open-loop, against a live
server or a self-contained in-process one.

Closed loop (``--mode closed``): N client threads each send
back-to-back requests for ``--duration`` seconds — measures the
server's saturated throughput and the latency it buys (more clients →
bigger coalesced batches → higher throughput per accelerator step).

Open loop (``--mode open``): requests arrive on a Poisson clock at
``--rate`` req/s regardless of completions — the honest
heavy-traffic model (arrivals don't wait for the server), so latency
includes queueing and the admission controller's ``Overloaded``
rejections are counted instead of letting the queue grow without
bound.

Decode (``--decode``): requests are token-generation streams against
a ``tmlocal SERVE --decode`` server (theanompi_tpu/decode).  The
headline numbers change axis: **tokens/s/chip** (accounted by
utils/token_accounting.py) and **inter-token
latency p50/p99** from the server's own per-token histogram, measured
under overload when the open-loop rate exceeds capacity.  The smoke
artifact lives at ``artifacts/BENCH_decode_smoke.json``.

Prompt-heavy trace (``--decode --mode trace``): S streams whose
prompts share a ``--shared-prefix``-token system prefix and append
long-tail suffixes (``--tail-lengths``), each generating
``--gen-tokens`` — the workload the two token-throughput multipliers
exist for.  Reports **per-stream tok/s** (tokens / that stream's own
wall, queue included) and the server's accept-rate / prefix-cache
counters.  ``--spec-compare`` runs the SAME trace twice on fresh
in-process servers — baseline (no draft, prefix cache off) vs
optimized (speculative decoding + prefix cache) — verifies the two
legs' outputs are byte-identical, and emits one JSON with both legs
plus the per-stream speedup (committed:
``artifacts/BENCH_decode_spec.json``).  The demo draft is the target
re-exported at bf16 (self-speculation: same argmax almost always, so
it measures the accept machinery honestly; a real deployment exports
a separately trained smaller draft).

Mixed trace (``--decode --mode mixed-trace``): the disaggregation
workload — open-loop SHORT chat streams (Poisson at ``--rate``) with
periodic LONG-prompt arrivals (``--long-every-s``) whose prefill is
compute-bound.  Four legs on fresh in-process servers with IDENTICAL
decode capacity: single-role short-only (its baseline), single-role
mixed (the long prefills run between decode steps of the one shared
loop and stall every live stream), disaggregated short-only and
disaggregated mixed (prefill fleet + router + decode fleet — the
decode replica only ever executes cheap adopt scatters).  Headline:
short-stream **inter-token p99** per leg, from the decode server's
own histogram (reset after the warm pass), plus the two ratios the
acceptance pins — single-role mixed blows its baseline up, the
disaggregated fleet holds ~1x.  ``--scale-drill`` appends a REAL
``DisaggregatedFleet`` (subprocess roles, autoscaler on) driven past
the prefill admission bound until scale-up fires, and records the
executed scale events + zero dropped streams; the run's monitor JSONL
lands in ``--monitor-dir``.  The smoke artifact lives at
``artifacts/BENCH_disagg_smoke.json``.

Emits one ``BENCH_serving`` JSON (throughput, latency p50/p95/p99,
batch occupancy / decode sharing from the server's own stats, overload
counts) to ``--out`` and prints it — same artifact discipline as the
other bench tools.

Usage:
    # against a running server (tmlocal SERVE ...):
    python tools/bench_serving.py --addr host:45900 --mode open --rate 200

    # self-contained (exports a tiny model, serves in-process, drives it):
    JAX_PLATFORMS=cpu python tools/bench_serving.py --demo --mode closed

    # token-throughput mode against a decode server (or --demo):
    JAX_PLATFORMS=cpu python tools/bench_serving.py --demo --decode \
        --mode open --rate 20 --gen-tokens 16
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import socket
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _percentiles(ms: list[float]) -> dict:
    if not ms:
        return {}
    a = np.sort(np.asarray(ms))
    pick = lambda q: float(a[min(len(a) - 1, int(q * len(a)))])
    return {"mean": float(a.mean()), "p50": pick(0.50),
            "p95": pick(0.95), "p99": pick(0.99), "max": float(a[-1])}


def _demo_export(tmp_dir: str, decode: bool = False,
                 d_model: int = 32, n_layers: int = 2,
                 n_heads: int = 2, vocab: int = 64,
                 seq_len: int = 32, draft: str | None = None):
    """Export an untrained tiny model so the tool runs anywhere:
    TinyCifar for eval mode, a small TransformerLM for --decode
    (dims CLI-sized so the trace mode can make prefill compute-bound
    on the CPU box).  ``draft='bf16'`` additionally exports the same
    net quantized as the speculative draft (self-speculation) and
    returns (export_dir, draft_dir)."""
    from theanompi_tpu.models.base import ModelConfig
    from theanompi_tpu.serving import export_model

    if decode:
        from theanompi_tpu.models.transformer import TransformerLM

        cfg = ModelConfig(batch_size=4, n_epochs=1, print_freq=0,
                          compute_dtype="float32", optimizer="adamw",
                          learning_rate=1e-3, weight_decay=0.0,
                          lr_schedule="constant")
        model = TransformerLM(config=cfg, vocab=vocab, seq_len=seq_len,
                              n_layers=n_layers, d_model=d_model,
                              n_heads=n_heads, verbose=False)
    else:
        from tests._tiny_models import TinyCifar

        model = TinyCifar(config=ModelConfig(batch_size=8, n_epochs=1,
                                             print_freq=0),
                          verbose=False)
    export_dir = os.path.join(tmp_dir, "export")
    export_model(model, export_dir, version=0)
    if not draft:
        return export_dir
    draft_dir = os.path.join(tmp_dir, "draft")
    export_model(model, draft_dir, version=0, weight_dtype="bf16")
    return export_dir, draft_dir


def _demo_trained_exports(tmp_dir: str, args):
    """Target + genuinely-smaller-draft demo exports for the trace
    mode's honest configuration: BOTH nets train
    ``--demo-train-epochs`` epochs on the synthetic successor-table
    LM task (data/lm.py, noise=0.15 so each learns a Markov rule
    robust to off-chain context) — after which the small draft agrees
    with the target on greedy rollouts because both learned the same
    table, which is exactly the regime speculative decoding is for.
    Returns (export_dir, draft_dir)."""
    from theanompi_tpu.data.lm import SeqLM_data
    from theanompi_tpu.models.base import ModelConfig
    from theanompi_tpu.models.transformer import TransformerLM
    from theanompi_tpu.rules.bsp import run_bsp_session
    from theanompi_tpu.serving import export_model

    def build(d_model, n_layers, n_heads):
        cfg = ModelConfig(batch_size=16,
                          n_epochs=args.demo_train_epochs,
                          print_freq=0, compute_dtype="float32",
                          optimizer="adamw", learning_rate=3e-3,
                          weight_decay=0.0, lr_schedule="constant")
        data = SeqLM_data(vocab=args.demo_vocab,
                          seq_len=args.demo_seq_len, n_train=512,
                          n_val=64, seed=0, noise=0.15)
        return TransformerLM(config=cfg, vocab=args.demo_vocab,
                             seq_len=args.demo_seq_len,
                             n_layers=n_layers, d_model=d_model,
                             n_heads=n_heads, verbose=False, data=data)

    target = build(args.demo_d_model, args.demo_layers,
                   args.demo_heads)
    run_bsp_session(target, checkpoint=False)
    draft = build(args.demo_draft_d_model, args.demo_draft_layers,
                  args.demo_draft_heads)
    run_bsp_session(draft, checkpoint=False)
    export_dir = os.path.join(tmp_dir, "export")
    draft_dir = os.path.join(tmp_dir, "draft")
    export_model(target, export_dir, version=0)
    export_model(draft, draft_dir, version=0)
    return export_dir, draft_dir


def make_trace(shared_prefix: int, tail_lengths: list[int],
               streams: int, vocab: int, seed: int = 0) -> list:
    """The prompt-heavy trace: every stream's prompt = one shared
    system prefix + its own long-tail suffix (lengths cycled from
    ``tail_lengths``).  Deterministic, so compare legs replay
    byte-identical prompts."""
    rng = np.random.default_rng(seed)
    top = max(2, vocab - 1)
    prefix = (rng.integers(0, top, shared_prefix).astype(np.int32) + 1
              if shared_prefix else np.zeros((0,), np.int32))
    prompts = []
    for i in range(streams):
        tail = rng.integers(0, top,
                            tail_lengths[i % len(tail_lengths)])
        prompts.append(np.concatenate(
            [prefix, tail.astype(np.int32) + 1]))
    return prompts


def run_trace(addr: str, prompts: list, gen_tokens: int,
              concurrency: int) -> dict:
    """Drive one stream per prompt (own connection each — the server's
    admission bound, not a client pool, is what saturates), at most
    ``concurrency`` in flight.  Per-stream wall includes queueing —
    the number a user's stream actually experiences."""
    from theanompi_tpu.serving import InferenceClient, Overloaded

    sem = threading.Semaphore(concurrency)
    lock = threading.Lock()
    streams: list[dict | None] = [None] * len(prompts)
    counts = {"ok": 0, "overloaded": 0, "errors": 0}

    def one(i: int) -> None:
        with sem:
            t0 = time.monotonic()
            client = InferenceClient(addr)
            try:
                out = client.generate(prompts[i], gen_tokens)
            except Overloaded:
                with lock:
                    counts["overloaded"] += 1
                return
            except Exception:
                with lock:
                    counts["errors"] += 1
                return
            finally:
                client.close()
            wall = time.monotonic() - t0
            with lock:
                counts["ok"] += 1
                streams[i] = {"wall_s": wall, "tokens": len(out),
                              "prompt_tokens": int(prompts[i].shape[0]),
                              "out": [int(t) for t in out]}

    t_start = time.monotonic()
    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t_start
    done = [s for s in streams if s is not None]
    per_stream = [s["tokens"] / s["wall_s"] for s in done
                  if s["wall_s"] > 0]
    return {
        "wall_s": wall,
        "streams": streams,
        "tokens": sum(s["tokens"] for s in done),
        "tok_s_per_stream": {
            "mean": float(np.mean(per_stream)) if per_stream else 0.0,
            "p50": float(np.median(per_stream)) if per_stream else 0.0,
            "min": float(np.min(per_stream)) if per_stream else 0.0,
            "max": float(np.max(per_stream)) if per_stream else 0.0,
        },
        **counts,
    }


def run_load(addr: str, sample: np.ndarray, mode: str, clients: int,
             rate: float, duration: float, decode: bool = False,
             gen_tokens: int = 16) -> dict:
    from theanompi_tpu.serving import InferenceClient, Overloaded

    lock = threading.Lock()
    lat_ms: list[float] = []
    counts = {"ok": 0, "overloaded": 0, "errors": 0, "tokens": 0}

    def one(client) -> None:
        t0 = time.monotonic()
        try:
            if decode:
                out = client.generate(sample, gen_tokens)
            else:
                client.infer(sample)
                out = None
        except Overloaded:
            with lock:
                counts["overloaded"] += 1
            return
        except Exception:
            with lock:
                counts["errors"] += 1
            return
        dt = (time.monotonic() - t0) * 1e3
        with lock:
            counts["ok"] += 1
            if out is not None:
                counts["tokens"] += len(out)
            lat_ms.append(dt)

    t_start = time.monotonic()
    if mode == "closed":
        def worker():
            client = InferenceClient(addr)
            while time.monotonic() - t_start < duration:
                one(client)
            client.close()

        threads = [threading.Thread(target=worker)
                   for _ in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    else:  # open loop: Poisson arrivals, one short-lived thread each
        rng = np.random.default_rng(0)
        # eval requests are ~ms, so a small shared client pool
        # approximates open-loop; a decode STREAM holds its connection
        # for the whole generation (ServiceClient serializes per
        # connection), so every in-flight stream needs its OWN
        # connection or the pool lock — not the server — caps
        # concurrency and the bench measures client queueing
        pool = ([] if decode
                else [InferenceClient(addr) for _ in range(clients)])

        def one_arrival(i: int) -> None:
            if decode:
                c = InferenceClient(addr)
                try:
                    one(c)
                finally:
                    c.close()
            else:
                one(pool[i % clients])

        inflight: list[threading.Thread] = []
        i = 0
        next_t = t_start
        while time.monotonic() - t_start < duration:
            next_t += float(rng.exponential(1.0 / rate))
            delay = next_t - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            t = threading.Thread(target=one_arrival, args=(i,))
            t.start()
            inflight.append(t)
            i += 1
        for t in inflight:
            t.join()
        for c in pool:
            c.close()
    wall = time.monotonic() - t_start
    return {"wall_s": wall, "latency_ms": _percentiles(lat_ms),
            **counts,
            "throughput_rps": counts["ok"] / wall if wall else 0.0}


def _start_decode_server(export_dir: str, args, draft_dir: str | None,
                         prefix_cache: bool,
                         prefill_batch: int | None = None,
                         prefill_delay_ms: float | None = None):
    from theanompi_tpu.serving import InferenceServer, serve

    decode_opts = dict(
        max_seqs=args.decode_max_seqs,
        max_pending=args.decode_max_pending,
        page_size=args.decode_page_size,
        pages_per_seq=args.decode_pages_per_seq,
        prefix_cache=prefix_cache)
    if prefill_batch is not None:
        decode_opts["prefill_batch"] = int(prefill_batch)
    if prefill_delay_ms is not None:
        decode_opts["prefill_delay_ms"] = float(prefill_delay_ms)
    if args.decode_prefill_buckets:
        decode_opts["prefill_buckets"] = tuple(
            int(b) for b in args.decode_prefill_buckets.split(","))
    if draft_dir:
        decode_opts["draft_export_dir"] = draft_dir
        decode_opts["speculate_k"] = args.speculate_k
    server = InferenceServer(export_dir, replicas=args.replicas,
                             decode=True, decode_opts=decode_opts,
                             reload_poll_s=0).start()
    port = _free_port()
    ready = threading.Event()
    thread = threading.Thread(
        target=serve, args=(server, "127.0.0.1", port, ready),
        daemon=True)
    thread.start()
    assert ready.wait(60), "server never came up"
    return server, thread, f"127.0.0.1:{port}"


def trace_main(args, tmp_dir: str) -> dict:
    """The prompt-heavy trace: one leg honoring the flags, or — with
    ``--spec-compare`` — baseline vs optimized legs on fresh
    in-process servers, byte-identity-checked (module docstring)."""
    from theanompi_tpu.serving import InferenceClient, load_export
    from theanompi_tpu.utils.token_accounting import token_throughput

    export_dir = args.export_dir
    draft_dir = args.draft_export_dir
    if export_dir is None:
        if not args.demo:
            raise SystemExit(
                "--mode trace needs --export-dir or --demo (it "
                "starts its own in-process servers)")
        if args.demo_train_epochs > 0:
            export_dir, draft_dir = _demo_trained_exports(tmp_dir,
                                                          args)
        else:
            export_dir, draft_dir = _demo_export(
                tmp_dir, decode=True, d_model=args.demo_d_model,
                n_layers=args.demo_layers, n_heads=args.demo_heads,
                vocab=args.demo_vocab, seq_len=args.demo_seq_len,
                draft="bf16")
    meta = load_export(export_dir).meta
    vocab = int((meta.get("net") or {}).get("vocab", 64))
    tails = [int(x) for x in args.tail_lengths.split(",")]
    prompts = make_trace(args.shared_prefix, tails, args.streams,
                         vocab)
    if args.spec_compare:
        if draft_dir is None:
            raise SystemExit(
                "--spec-compare needs a draft: pass "
                "--draft-export-dir with --export-dir, or use --demo "
                "(which exports one) — otherwise the 'optimized' leg "
                "would silently run without speculation")
        plan = (("baseline", False, False), ("optimized", True, True))
    else:
        plan = (("trace", bool(draft_dir),
                 not args.no_prefix_cache),)
    legs = {}
    for name, use_draft, use_prefix in plan:
        server, thread, addr = _start_decode_server(
            export_dir, args, draft_dir if use_draft else None,
            use_prefix)
        try:
            probe = InferenceClient(addr)
            # warm pass: compiles every (bucket, family) the trace
            # touches and seeds the prefix cache — the measured pass
            # is the steady state users live in
            run_trace(addr, prompts, args.gen_tokens,
                      args.concurrency)
            warm_compiles = [
                {"target": r.get("compiles"),
                 "draft": r.get("draft_compiles")}
                for r in probe.stats()["replicas"]]
            res = run_trace(addr, prompts, args.gen_tokens,
                            args.concurrency)
            st = probe.stats()
            probe.shutdown()
            probe.close()
        finally:
            server.stop()
            thread.join(timeout=10)
        measured_compiles = [
            {"target": r.get("compiles"),
             "draft": r.get("draft_compiles")}
            for r in st["replicas"]]
        legs[name] = {
            "speculative": use_draft,
            "prefix_cache": use_prefix,
            "tok_s_per_stream": res["tok_s_per_stream"],
            "throughput": token_throughput(res["tokens"],
                                           res["wall_s"]),
            "wall_s": res["wall_s"],
            "ok": res["ok"], "overloaded": res["overloaded"],
            "errors": res["errors"],
            "outputs": [s["out"] if s else None
                        for s in res["streams"]],
            "server": {
                "tokens": st.get("tokens"),
                "steps": st.get("steps"),
                "mean_tokens_per_step": (st["tokens"] / st["steps"]
                                         if st.get("steps") else None),
                "accept_rate": st.get("accept_rate"),
                "prefix_cache_hits": st.get("prefix_cache_hits"),
                "intertoken_ms": (st["replicas"][0] or {}).get(
                    "intertoken_ms"),
            },
            # steady-state pin: the measured pass may not compile
            # anything the warm pass did not
            "zero_steady_state_recompiles":
                warm_compiles == measured_compiles,
            "compiles": measured_compiles,
        }
    out = {
        "bench": "serving",
        "mode": "trace",
        "decode": True,
        "argv": sys.argv[1:],
        "trace": {
            "streams": args.streams,
            "shared_prefix_tokens": args.shared_prefix,
            "tail_lengths": tails,
            "gen_tokens_per_stream": args.gen_tokens,
            "concurrency": args.concurrency,
            "speculate_k": args.speculate_k,
        },
        "model": {"net": meta.get("net"),
                  "weight_dtype": meta.get("weight_dtype")},
        "legs": {name: {k: v for k, v in leg.items()
                        if k != "outputs"}
                 for name, leg in legs.items()},
    }
    if args.spec_compare:
        base, opt = legs["baseline"], legs["optimized"]
        out["byte_identical_output"] = (base["outputs"]
                                        == opt["outputs"])
        b = base["tok_s_per_stream"]["mean"]
        o = opt["tok_s_per_stream"]["mean"]
        out["per_stream_speedup"] = o / b if b else None
    return out


def prefill_compare_main(args, tmp_dir: str) -> dict:
    """``--prefill-compare``: the SAME concurrent prompt trace twice
    on fresh in-process decode servers — serial admission
    (``prefill_batch=1``, byte-for-byte the pre-batching path) vs
    batched admission (``--decode-prefill-batch`` prompts per program
    launch).  Headline: **aggregate prefill tok/s** (prompt tokens /
    prefill program wall, the batcher's own counters) and **TTFT
    p50/p99** from the per-stream time-to-first-token ring, measured
    on a warm second pass.  Verifies both legs' outputs are
    byte-identical and neither compiles anything in the measured pass
    (committed: ``artifacts/BENCH_prefill_batch.json``)."""
    from theanompi_tpu.serving import InferenceClient, load_export

    export_dir = args.export_dir
    if export_dir is None:
        if not args.demo:
            raise SystemExit(
                "--prefill-compare needs --export-dir or --demo (it "
                "starts its own in-process servers)")
        export_dir = _demo_export(
            tmp_dir, decode=True, d_model=args.demo_d_model,
            n_layers=args.demo_layers, n_heads=args.demo_heads,
            vocab=args.demo_vocab, seq_len=args.demo_seq_len)
    meta = load_export(export_dir).meta
    vocab = int((meta.get("net") or {}).get("vocab", 64))
    tails = [int(x) for x in args.tail_lengths.split(",")]
    # DISTINCT prompts (no shared prefix): every admission is a cold
    # prefill, so the measured axis is the program-launch economics of
    # batching itself, not prefix-cache sharing
    prompts = make_trace(0, tails, args.streams, vocab)
    legs = {}
    for name, pb in (("serial", 1),
                     ("batched", args.decode_prefill_batch)):
        print(f"[prefill-compare] leg {name} (prefill_batch={pb}) ...",
              flush=True)
        server, thread, addr = _start_decode_server(
            export_dir, args, None, prefix_cache=True,
            prefill_batch=pb,
            prefill_delay_ms=args.decode_prefill_delay_ms)
        try:
            probe = InferenceClient(addr)
            # warm pass compiles every (n_seqs, token) bucket pair the
            # trace touches; the measured pass is the steady state
            run_trace(addr, prompts, args.gen_tokens,
                      args.concurrency)
            warm_compiles = [r.get("compiles")
                             for r in probe.stats()["replicas"]]
            st0 = probe.stats()
            for r in server.replicas:
                r.batcher.reset_intertoken()
            res = run_trace(addr, prompts, args.gen_tokens,
                            args.concurrency)
            st = probe.stats()
            probe.shutdown()
            probe.close()
        finally:
            server.stop()
            thread.join(timeout=10)
        measured_compiles = [r.get("compiles")
                            for r in st["replicas"]]
        rep, rep0 = st["replicas"][0], st0["replicas"][0]
        pf_tokens = rep["prefill_tokens"] - rep0["prefill_tokens"]
        pf_s = rep["prefill_s"] - rep0["prefill_s"]
        batches = rep["prefill_batches"] - rep0["prefill_batches"]
        legs[name] = {
            "prefill_batch": pb,
            "prefill_delay_ms": args.decode_prefill_delay_ms,
            "ok": res["ok"], "overloaded": res["overloaded"],
            "errors": res["errors"],
            "wall_s": res["wall_s"],
            "outputs": [s["out"] if s else None
                        for s in res["streams"]],
            "prefill": {
                "prompt_tokens": pf_tokens,
                "program_wall_s": pf_s,
                "batches": batches,
                "mean_occupancy": (res["ok"] / batches
                                   if batches else None),
                "max_occupancy": rep["max_prefill_batch"],
                "aggregate_tok_s": pf_tokens / pf_s if pf_s else None,
            },
            "ttft_ms": rep["ttft_ms"],
            "zero_steady_state_recompiles":
                warm_compiles == measured_compiles,
            "compiles": measured_compiles,
        }
    serial, batched = legs["serial"], legs["batched"]
    sp, bp = (serial["prefill"]["aggregate_tok_s"],
              batched["prefill"]["aggregate_tok_s"])
    speedup = bp / sp if sp and bp else None
    s99, b99 = serial["ttft_ms"]["p99"], batched["ttft_ms"]["p99"]
    return {
        "bench": "serving",
        "mode": "prefill-compare",
        "decode": True,
        "argv": sys.argv[1:],
        "trace": {
            "streams": args.streams,
            "tail_lengths": tails,
            "gen_tokens_per_stream": args.gen_tokens,
            "concurrency": args.concurrency,
        },
        "model": {"net": meta.get("net"),
                  "weight_dtype": meta.get("weight_dtype")},
        "legs": {name: {k: v for k, v in leg.items()
                        if k != "outputs"}
                 for name, leg in legs.items()},
        "byte_identical_output": (serial["outputs"]
                                  == batched["outputs"]),
        "aggregate_prefill_speedup": speedup,
        "ttft_p99_ms": {"serial": s99, "batched": b99},
        "acceptance": {
            "aggregate_prefill_2x": (speedup is not None
                                     and speedup >= 2.0),
            "ttft_p99_not_worse": (s99 is not None and b99 is not None
                                   and b99 <= s99),
            "byte_identical_output": (serial["outputs"]
                                      == batched["outputs"]),
            "zero_steady_state_recompiles": (
                serial["zero_steady_state_recompiles"]
                and batched["zero_steady_state_recompiles"]),
        },
    }


def make_mixed_workload(vocab: int, n_short: int, short_tokens: int,
                        long_tokens: int, rate: float,
                        long_every_s: float, seed: int = 0):
    """Deterministic open-loop schedule: ``n_short`` short-chat
    arrivals on a pre-drawn Poisson clock at ``rate`` req/s, plus one
    long-prompt arrival every ``long_every_s`` inside that horizon.
    Every prompt is DISTINCT random tokens (no page-aligned shared
    prefixes → no prefix-cache hits), so the same schedule replays
    byte-comparable prompts across all legs."""
    rng = np.random.default_rng(seed)
    top = max(2, vocab - 1)
    t_short = np.cumsum(rng.exponential(1.0 / rate, n_short))
    shorts = [(float(t_short[i]),
               rng.integers(0, top, short_tokens).astype(np.int32) + 1)
              for i in range(n_short)]
    longs = []
    t = long_every_s
    while t < float(t_short[-1]):
        longs.append((float(t),
                      rng.integers(0, top,
                                   long_tokens).astype(np.int32) + 1))
        t += long_every_s
    return shorts, longs


def run_mixed(make_client, shorts, longs, gen_short: int,
              gen_long: int) -> dict:
    """Replay one mixed schedule open-loop: each arrival gets its own
    thread + connection (streams hold their connection, so the server's
    admission bound — not a client pool — is what saturates).  Returns
    per-class counts and per-arrival outputs (index-aligned with the
    schedule, so legs compare byte-for-byte)."""
    from theanompi_tpu.serving import Overloaded

    lock = threading.Lock()
    out_short: list[dict | None] = [None] * len(shorts)
    out_long: list[dict | None] = [None] * len(longs)
    counts = {"short": {"ok": 0, "overloaded": 0, "errors": 0},
              "long": {"ok": 0, "overloaded": 0, "errors": 0}}

    def one(cls, idx, prompt, gen, sink):
        t0 = time.monotonic()
        client = None
        try:
            client = make_client()
            out = client.generate(prompt, gen)
        except Overloaded:
            with lock:
                counts[cls]["overloaded"] += 1
            return
        except Exception:
            with lock:
                counts[cls]["errors"] += 1
            return
        finally:
            if client is not None:
                try:
                    client.close()
                except Exception:
                    pass
        with lock:
            counts[cls]["ok"] += 1
            sink[idx] = {"wall_s": time.monotonic() - t0,
                         "out": [int(t) for t in out]}

    arrivals = ([("short", i, at, p, gen_short, out_short)
                 for i, (at, p) in enumerate(shorts)]
                + [("long", i, at, p, gen_long, out_long)
                   for i, (at, p) in enumerate(longs)])
    arrivals.sort(key=lambda a: a[2])
    t_start = time.monotonic()
    threads = []
    for cls, idx, at, prompt, gen, sink in arrivals:
        delay = at - (time.monotonic() - t_start)
        if delay > 0:
            time.sleep(delay)
        th = threading.Thread(target=one,
                              args=(cls, idx, prompt, gen, sink))
        th.start()
        threads.append(th)
    for th in threads:
        th.join()
    return {
        "wall_s": time.monotonic() - t_start,
        "counts": counts,
        "short_outputs": [s["out"] if s else None for s in out_short],
        "long_outputs": [s["out"] if s else None for s in out_long],
    }


def _measure_mixed_leg(make_client, server, warm_long, warm_shorts,
                       shorts, longs, args) -> dict:
    """Warm pass → drop the decode server's inter-token ring →
    measured replay.  The warm pass must compile every program the
    measured pass can touch: both prompt buckets, AND the decode
    BATCH buckets — those only compile at the concurrency that
    reaches them, so the short warms run ``max_seqs`` wide with
    decaying generation lengths (the active set drains 8→4→2→1
    through every power-of-two bucket)."""
    c = make_client()
    try:
        c.generate(warm_long, args.long_gen_tokens)
    finally:
        c.close()

    def one(prompt, gen):
        cc = make_client()
        try:
            cc.generate(prompt, gen)
        finally:
            cc.close()

    threads = [threading.Thread(target=one, args=(p, g))
               for p, g in warm_shorts]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    warm_compiles = [dict(r.batcher.stats()["compiles"])
                     for r in server.replicas]
    for r in server.replicas:
        r.batcher.reset_intertoken()
    res = run_mixed(make_client, shorts, longs, args.gen_tokens,
                    args.long_gen_tokens)
    measured_compiles = [dict(r.batcher.stats()["compiles"])
                        for r in server.replicas]
    # steady-state pin (same contract as --mode trace): a compile gap
    # in the measured pass would sit in the p99 and lie about physics
    res["zero_steady_state_recompiles"] = (warm_compiles
                                           == measured_compiles)
    return res


def _mixed_leg_summary(res: dict, st: dict) -> dict:
    rep = (st.get("replicas") or [{}])[0] or {}
    return {
        "wall_s": res["wall_s"],
        "counts": res["counts"],
        "zero_steady_state_recompiles":
            res.get("zero_steady_state_recompiles"),
        "intertoken_ms": rep.get("intertoken_ms"),
        "server": {"tokens": st.get("tokens"), "steps": st.get("steps"),
                   "adopted": rep.get("adopted"),
                   "adopt_refused": rep.get("adopt_refused")},
    }


def _outputs_identical(a: list, b: list) -> dict:
    """Index-aligned byte-identity over arrivals that completed in
    BOTH legs (an Overloaded shed in one leg just shrinks the set)."""
    both = [(x, y) for x, y in zip(a, b)
            if x is not None and y is not None]
    return {"identical": bool(both) and all(x == y for x, y in both),
            "compared": len(both)}


def _scale_drill(export_dir: str, args, monitor_dir: str) -> dict:
    """The autoscaler leg, on a REAL subprocess fleet: a tiny prefill
    admission bound (max_pending=2) gets hammered with concurrent
    long-prompt streams until the overload signal trips the
    hysteresis controller and a scale-up executes; then a fresh wave
    must land entirely on the grown fleet — zero errors, zero sheds.
    The whole drill runs under a monitor session rooted at
    ``monitor_dir`` with ``$THEANOMPI_TPU_MONITOR`` exported, so every
    role process ships its metrics JSONL there — the committed
    evidence."""
    from theanompi_tpu import monitor
    from theanompi_tpu.frontdoor.fleet import DisaggregatedFleet
    from theanompi_tpu.frontdoor.router import RouterClient
    from theanompi_tpu.serving import Overloaded

    monitor_dir = os.path.abspath(monitor_dir)
    os.makedirs(monitor_dir, exist_ok=True)
    rng = np.random.default_rng(7)
    top = 63
    long_prompt = lambda: (rng.integers(0, top,
                           args.long_prompt_tokens).astype(np.int32) + 1)
    short_prompt = lambda: (rng.integers(0, top,
                            args.prompt_tokens).astype(np.int32) + 1)
    buckets = (tuple(int(b) for b in
                     args.decode_prefill_buckets.split(","))
               if args.decode_prefill_buckets else None)
    prev_env = os.environ.get(monitor.ENV_VAR)
    os.environ[monitor.ENV_VAR] = monitor_dir  # fan out to children
    try:
        with monitor.session(run_dir=monitor_dir,
                             stall_after=float("inf"),
                             name="bench_frontdoor"):
            monitor.progress(phase="frontdoor")
            with DisaggregatedFleet(
                    export_dir, prefill=1, decode=1,
                    router_host="127.0.0.1",
                    page_size=args.decode_page_size,
                    pages_per_seq=args.decode_pages_per_seq,
                    max_seqs=args.decode_max_seqs,
                    prefill_buckets=buckets,
                    prefill_max_pending=2,
                    decode_max_pending=args.decode_max_pending,
                    autoscale=True, scale_max=2,
                    scale_poll_s=0.5) as fleet:
                addr = fleet.router_addr
                lock = threading.Lock()
                hammer = {"ok": 0, "overloaded": 0, "errors": 0}
                stop = threading.Event()

                def drive():
                    while not stop.is_set():
                        c = None
                        try:
                            c = RouterClient(addr)
                            c.generate(long_prompt(),
                                       args.long_gen_tokens)
                            with lock:
                                hammer["ok"] += 1
                        except Overloaded:
                            with lock:
                                hammer["overloaded"] += 1
                        except Exception:
                            with lock:
                                hammer["errors"] += 1
                        finally:
                            if c is not None:
                                try:
                                    c.close()
                                except Exception:
                                    pass

                drivers = [threading.Thread(target=drive)
                           for _ in range(6)]
                for d in drivers:
                    d.start()
                # wait for the executed scale-up (grow() blocks the
                # autoscaler tick until the new replica answers, so
                # this also covers the replica's JAX warmup)
                deadline = time.monotonic() + 240
                while time.monotonic() < deadline:
                    if fleet.autoscaler.events:
                        break
                    time.sleep(0.25)
                stop.set()
                for d in drivers:
                    d.join()
                events = list(fleet.autoscaler.events)
                # new traffic onto the grown fleet: nothing may drop
                post = {"ok": 0, "overloaded": 0, "errors": 0}

                def wave():
                    c = None
                    try:
                        c = RouterClient(addr)
                        c.generate(long_prompt(), args.long_gen_tokens)
                        c.generate(short_prompt(), args.gen_tokens)
                        with lock:
                            post["ok"] += 1
                    except Overloaded:
                        with lock:
                            post["overloaded"] += 1
                    except Exception:
                        with lock:
                            post["errors"] += 1
                    finally:
                        if c is not None:
                            try:
                                c.close()
                            except Exception:
                                pass

                waves = [threading.Thread(target=wave)
                         for _ in range(4)]
                for w in waves:
                    w.start()
                for w in waves:
                    w.join()
                router_stats = RouterClient(addr).stats()
    finally:
        if prev_env is None:
            os.environ.pop(monitor.ENV_VAR, None)
        else:
            os.environ[monitor.ENV_VAR] = prev_env
    return {
        "monitor_dir": monitor_dir,
        "monitor_files": sorted(os.listdir(monitor_dir)),
        "scale_events": [{"role": r, "direction": d, "addr": a}
                         for r, d, a in events],
        "hammer": hammer,
        "post_scale_wave": post,
        "router": {k: router_stats.get(k)
                   for k in ("streams", "shed", "failovers")},
        "acceptance": {
            "scale_up_executed": any(d == "up" for _, d, _ in events),
            "zero_dropped_streams": (hammer["errors"] == 0
                                     and post["errors"] == 0),
            "post_scale_wave_fully_admitted": (
                post["ok"] == 4 and post["overloaded"] == 0),
        },
    }


def mixed_main(args, tmp_dir: str) -> dict:
    """The disaggregation workload (module docstring): four legs with
    identical decode capacity, short-stream inter-token p99 headline,
    byte-identity across topologies, optional autoscale drill."""
    from theanompi_tpu.frontdoor import router as router_mod
    from theanompi_tpu.frontdoor.autoscale import RoleGroup
    from theanompi_tpu.frontdoor.router import Router, RouterClient
    from theanompi_tpu.serving import InferenceClient, load_export

    export_dir = args.export_dir
    if export_dir is None:
        if not args.demo:
            raise SystemExit(
                "--mode mixed-trace needs --export-dir or --demo (it "
                "starts its own servers and fleets)")
        export_dir = _demo_export(
            tmp_dir, decode=True, d_model=args.demo_d_model,
            n_layers=args.demo_layers, n_heads=args.demo_heads,
            vocab=args.demo_vocab, seq_len=args.demo_seq_len)
    export_dir = os.path.abspath(export_dir)
    meta = load_export(export_dir).meta
    vocab = int((meta.get("net") or {}).get("vocab", 64))
    shorts, longs = make_mixed_workload(
        vocab, args.short_streams, args.prompt_tokens,
        args.long_prompt_tokens, args.rate, args.long_every_s)
    wrng = np.random.default_rng(1234)
    top = max(2, vocab - 1)
    warm_long = (wrng.integers(0, top, args.long_prompt_tokens)
                 .astype(np.int32) + 1)
    warm_shorts = [
        (wrng.integers(0, top, args.prompt_tokens)
         .astype(np.int32) + 1, max(2, 2 * (i + 1)))
        for i in range(args.decode_max_seqs)]

    legs: dict[str, dict] = {}
    outputs: dict[str, dict] = {}

    # -- single-role pair: one decode server does both phases ----------
    for name, leg_longs in (("single_short", []),
                            ("single_mixed", longs)):
        print(f"[mixed-trace] leg {name} ...", flush=True)
        server, sthread, addr = _start_decode_server(
            export_dir, args, None, prefix_cache=True)
        try:
            res = _measure_mixed_leg(
                lambda: InferenceClient(addr), server, warm_long,
                warm_shorts, shorts, leg_longs, args)
            probe = InferenceClient(addr)
            st = probe.stats()
            probe.shutdown()
            probe.close()
        finally:
            server.stop()
            sthread.join(timeout=10)
        legs[name] = _mixed_leg_summary(res, st)
        outputs[name] = {"short": res["short_outputs"],
                         "long": res["long_outputs"]}

    # -- disaggregated pair: the SAME decode server config, prefill
    # offloaded to its own replica process, router in front ------------
    def prefill_argv(port: int) -> list[str]:
        cmd = [sys.executable, "-m", "theanompi_tpu.frontdoor.prefill",
               "--export-dir", export_dir, "--host", "127.0.0.1",
               "--port", str(port),
               "--page-size", str(args.decode_page_size),
               "--pages-per-seq", str(args.decode_pages_per_seq),
               "--max-seqs", str(args.decode_max_seqs),
               "--max-pending", str(args.prefill_max_pending)]
        if args.decode_prefill_buckets:
            cmd += ["--prefill-buckets", args.decode_prefill_buckets]
        if args.prefill_nice and shutil.which("nice"):
            # in production the roles sit on SEPARATE hosts; on a
            # shared CI box the OS timeslices them over the same
            # cores, so a prefill burst would steal cycles from
            # mid-flight decode steps — the exact coupling
            # disaggregation removes.  Deprioritizing the prefill
            # fleet restores the isolation: decode preempts promptly
            # and prefill runs in the gaps (long TTFT pays, short
            # intertoken doesn't — the disaggregation trade, made
            # explicit).  The single-role legs can't be helped this
            # way: their prefill runs INSIDE the decode loop.
            cmd = ["nice", "-n", str(args.prefill_nice)] + cmd
        return cmd

    print("[mixed-trace] booting the prefill replica (subprocess) ...",
          flush=True)
    prefill_group = RoleGroup("prefill", prefill_argv, initial=1)
    try:
        for name, leg_longs in (("disagg_short", []),
                                ("disagg_mixed", longs)):
            print(f"[mixed-trace] leg {name} ...", flush=True)
            server, sthread, decode_addr = _start_decode_server(
                export_dir, args, None, prefix_cache=True)
            router = Router(prefill=prefill_group.addresses(),
                            decode=[decode_addr])
            rport = _free_port()
            ready, rstop = threading.Event(), threading.Event()
            rthread = threading.Thread(
                target=router_mod.serve, daemon=True,
                kwargs=dict(router=router, host="127.0.0.1",
                            port=rport, ready_event=ready,
                            stop_event=rstop))
            rthread.start()
            assert ready.wait(30), "router never came up"
            raddr = f"127.0.0.1:{rport}"
            try:
                res = _measure_mixed_leg(
                    lambda: RouterClient(raddr), server, warm_long,
                    warm_shorts, shorts, leg_longs, args)
                rst = router.stats()
                probe = InferenceClient(decode_addr)
                st = probe.stats()
                probe.shutdown()
                probe.close()
            finally:
                rstop.set()
                rthread.join(timeout=10)
                router.close()
                server.stop()
                sthread.join(timeout=10)
            legs[name] = _mixed_leg_summary(res, st)
            legs[name]["router"] = {k: rst.get(k) for k in
                                    ("streams", "shed", "failovers")}
            outputs[name] = {"short": res["short_outputs"],
                             "long": res["long_outputs"]}
    finally:
        prefill_group.stop()

    p99 = {name: (leg.get("intertoken_ms") or {}).get("p99")
           for name, leg in legs.items()}
    ratio = lambda a, b: (p99[a] / p99[b]
                          if p99.get(a) and p99.get(b) else None)
    ratios = {
        "single_mixed_over_short": ratio("single_mixed",
                                         "single_short"),
        "disagg_mixed_over_short": ratio("disagg_mixed",
                                         "disagg_short"),
    }
    byte_identity = {
        # migration alone (no long-prompt interference) ...
        "disagg_short_vs_single_short": _outputs_identical(
            outputs["disagg_short"]["short"],
            outputs["single_short"]["short"]),
        # ... and under the mixed load, short and long streams both
        "disagg_mixed_vs_single_short": _outputs_identical(
            outputs["disagg_mixed"]["short"],
            outputs["single_short"]["short"]),
        "disagg_mixed_long_vs_single_mixed": _outputs_identical(
            outputs["disagg_mixed"]["long"],
            outputs["single_mixed"]["long"]),
    }
    out = {
        "bench": "serving",
        "mode": "mixed-trace",
        "decode": True,
        "argv": sys.argv[1:],
        "workload": {
            "short_streams": len(shorts),
            "short_prompt_tokens": args.prompt_tokens,
            "short_gen_tokens": args.gen_tokens,
            "long_arrivals": len(longs),
            "long_prompt_tokens": args.long_prompt_tokens,
            "long_gen_tokens": args.long_gen_tokens,
            "rate_rps": args.rate,
            "long_every_s": args.long_every_s,
        },
        "model": {"net": meta.get("net"),
                  "weight_dtype": meta.get("weight_dtype")},
        "legs": legs,
        "intertoken_p99_ms": p99,
        "ratios": ratios,
        "byte_identity": byte_identity,
        "acceptance": {
            "single_role_degrades_3x": (
                ratios["single_mixed_over_short"] is not None
                and ratios["single_mixed_over_short"] >= 3.0),
            "disagg_holds_1p3x": (
                ratios["disagg_mixed_over_short"] is not None
                and ratios["disagg_mixed_over_short"] <= 1.3),
            "byte_identical_migrated_output": all(
                v["identical"] for v in byte_identity.values()),
        },
    }
    if args.scale_drill:
        print("[mixed-trace] scale drill (subprocess fleet, "
              "autoscaler on) ...", flush=True)
        monitor_dir = args.monitor_dir or os.path.join(
            tmp_dir, "monitor")
        out["scale_drill"] = _scale_drill(export_dir, args,
                                          monitor_dir)
    return out


def shm_compare_leg(tmp_dir: str, rounds: int = 10) -> dict:
    """KV-page plane of the shared-memory-lane comparison (ISSUE 20):
    a prefill replica (FRESH subprocess per leg) ships KV pages to the
    client over wire v2 — in-band vs the shm lane — for the SAME
    prompt set, with the k/v page bytes sha256-checked byte-identical
    across legs.  The caller owns the enclosing monitor session (the
    client-side lane counters are registry-global)."""
    import hashlib
    import subprocess

    from theanompi_tpu import monitor
    from theanompi_tpu.frontdoor.prefill import PrefillClient
    from theanompi_tpu.parallel import shm

    export_dir = _demo_export(tmp_dir, decode=True, d_model=64,
                              n_layers=2, n_heads=4, vocab=64,
                              seq_len=64)
    rng = np.random.default_rng(20)
    prompts = [(rng.integers(0, 62, 24).astype(np.int32) + 1)
               for _ in range(4)]
    pre_segments = set(shm.segment_names())
    reg = monitor.registry()
    val = lambda name, **lb: reg.value(name, **lb) or 0.0
    prior = {k: os.environ.get(k) for k in
             ("THEANOMPI_TPU_WIRE_SHM", "THEANOMPI_TPU_SHM_MIN_BYTES")}
    legs: dict[str, dict] = {}
    try:
        # the tiny demo net's KV pages are tens of KB — under the
        # default 64 KiB lane floor; BOTH legs run the same lowered
        # floor so the comparison stays like-for-like
        os.environ["THEANOMPI_TPU_SHM_MIN_BYTES"] = "1024"
        for name, lane in (("in_band", "0"), ("shm", "1")):
            os.environ["THEANOMPI_TPU_WIRE_SHM"] = lane
            port = _free_port()
            proc = subprocess.Popen(
                [sys.executable, "-m",
                 "theanompi_tpu.frontdoor.prefill",
                 "--export-dir", export_dir, "--host", "127.0.0.1",
                 "--port", str(port), "--page-size", "16",
                 "--pages-per-seq", "4", "--max-seqs", "8",
                 "--max-pending", "8", "--prefill-batch", "1",
                 "--prefill-delay-ms", "0", "--platform", "cpu"],
                env=dict(os.environ))
            c = None
            deadline = time.monotonic() + 180
            while c is None:
                try:
                    c = PrefillClient(f"127.0.0.1:{port}")
                    c.ping()
                except Exception:
                    if c is not None:
                        c.close()
                    c = None
                    if proc.poll() is not None:
                        raise RuntimeError(
                            f"prefill replica died (rc={proc.poll()})")
                    if time.monotonic() > deadline:
                        proc.kill()
                        raise RuntimeError(
                            "prefill replica never came up")
                    time.sleep(0.3)
            oob0 = val("shm/oob_bytes_total", dir="recv")
            grants0 = val("shm/grants_total", role="client")
            digest = hashlib.sha256()
            page_bytes = 0
            try:
                for p in prompts:  # warm: prefill program compile
                    c.prefill(p)
                t0 = time.monotonic()
                for _ in range(rounds):
                    for p in prompts:
                        _, k, v = c.prefill(p)
                        digest.update(k.tobytes())
                        digest.update(v.tobytes())
                        page_bytes += k.nbytes + v.nbytes
                wall = time.monotonic() - t0
            finally:
                try:
                    c.shutdown()
                except Exception:
                    pass
                c.close()
                try:
                    proc.wait(timeout=20)
                except Exception:
                    proc.kill()
                    proc.wait(timeout=10)
            n = rounds * len(prompts)
            legs[name] = {
                "prefills": n,
                "wall_s": round(wall, 3),
                "prefill_ms_mean": round(wall / n * 1e3, 2),
                "page_bytes": page_bytes,
                "sha256": digest.hexdigest(),
                "oob_bytes_recv": int(
                    val("shm/oob_bytes_total", dir="recv") - oob0),
                "shm_grants": int(
                    val("shm/grants_total", role="client") - grants0),
            }
            print(f"[bench_serving] shm-compare {name}: "
                  f"{legs[name]['prefill_ms_mean']:.1f} ms/prefill, "
                  f"{legs[name]['oob_bytes_recv']/1e6:.1f} MB "
                  "out-of-band", flush=True)
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    shm.sweep_orphans()
    leaked = [n for n in shm.segment_names() if n not in pre_segments]
    return {
        "plane": "serving_kv",
        "rounds": rounds, "prompts": len(prompts),
        "legs": legs,
        "byte_identical": (legs["shm"]["sha256"]
                           == legs["in_band"]["sha256"]),
        "wall_delta_pct": round(
            100.0 * (1.0 - legs["shm"]["wall_s"]
                     / legs["in_band"]["wall_s"]), 1),
        # page bytes that left the socket path entirely (the client
        # maps them instead of copying them off the wire)
        "socket_bytes_saved": legs["shm"]["oob_bytes_recv"],
        "leaked_segments": len(leaked),
    }


def shm_compare_main(args) -> int:
    """``--shm-compare``: the standalone KV-page shm leg.  Always a
    gate — exits 1 unless the lane carried the pages, the delivered
    bytes are identical to the in-band leg, and nothing leaked."""
    import tempfile

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("THEANOMPI_TPU_SERVICE_KEY", "bench-serving")
    from theanompi_tpu import monitor

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with tempfile.TemporaryDirectory() as td:
        with monitor.session(os.path.join(td, "monitor")):
            doc = shm_compare_leg(td)
    import jax

    out_doc = {"bench": "serving_shm_lane",
               "backend": jax.default_backend(), **doc}
    path = (args.out if args.out != "BENCH_serving.json"
            else os.path.join(repo, "artifacts",
                              "BENCH_serving_shm.json"))
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(out_doc, f, indent=1)
    print(f"[bench_serving] wrote {path} (shm wall delta "
          f"{doc['wall_delta_pct']:+.1f}%)", flush=True)
    ok = True
    if not doc["byte_identical"]:
        print("[bench_serving] FAIL: shm leg delivered different page "
              "bytes than the in-band leg", file=sys.stderr)
        ok = False
    if doc["legs"]["shm"]["oob_bytes_recv"] <= 0 \
            or doc["legs"]["shm"]["shm_grants"] < 1:
        print("[bench_serving] FAIL: shm leg shows no lane traffic "
              f"({doc['legs']['shm']})", file=sys.stderr)
        ok = False
    if doc["legs"]["in_band"]["oob_bytes_recv"] != 0:
        print("[bench_serving] FAIL: in-band leg leaked lane traffic",
              file=sys.stderr)
        ok = False
    if doc["leaked_segments"]:
        print(f"[bench_serving] FAIL: {doc['leaked_segments']} shm "
              "segment(s) leaked", file=sys.stderr)
        ok = False
    print(f"[bench_serving] shm-compare {'PASS' if ok else 'FAIL'}",
          flush=True)
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--addr", default=None,
                    help="host:port of a running server; omitted = "
                         "serve --export-dir in-process")
    ap.add_argument("--export-dir", default=None)
    ap.add_argument("--demo", action="store_true",
                    help="export an untrained TinyCifar to a temp dir "
                         "first (self-contained CPU run)")
    ap.add_argument("--mode",
                    choices=("closed", "open", "trace", "mixed-trace"),
                    default="closed",
                    help="closed/open loop, 'trace' — the decode "
                         "prompt-heavy trace (shared prefix x many "
                         "streams, per-stream tok/s) — or "
                         "'mixed-trace' — the disaggregation workload "
                         "(open-loop short chat + periodic long "
                         "prompts; single-role vs disaggregated "
                         "inter-token p99)")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--rate", type=float, default=100.0,
                    help="open-loop arrival rate, req/s")
    ap.add_argument("--duration", type=float, default=5.0)
    ap.add_argument("--rows", type=int, default=1,
                    help="rows per request")
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-delay-ms", type=float, default=5.0)
    ap.add_argument("--max-queue", type=int, default=32)
    ap.add_argument("--decode", action="store_true",
                    help="token-throughput mode: drive 'generate' "
                         "streams against a decode server (tokens/s/"
                         "chip + inter-token p50/p99 headline)")
    ap.add_argument("--prompt-tokens", type=int, default=8,
                    help="--decode: prompt length per stream")
    ap.add_argument("--gen-tokens", type=int, default=16,
                    help="--decode: tokens generated per stream")
    ap.add_argument("--decode-max-seqs", type=int, default=8,
                    help="--decode in-process server: max concurrent "
                         "sequences per replica")
    ap.add_argument("--decode-max-pending", type=int, default=32,
                    help="--decode in-process server: admission bound "
                         "(prompts beyond it get Overloaded)")
    ap.add_argument("--decode-page-size", type=int, default=16,
                    help="--decode in-process trace server: tokens "
                         "per KV page")
    ap.add_argument("--decode-pages-per-seq", type=int, default=8,
                    help="--decode in-process trace server: pages per "
                         "sequence (window = page_size x pages)")
    ap.add_argument("--decode-prefill-buckets", default=None,
                    metavar="N,N,...",
                    help="--decode in-process trace server: padded "
                         "prompt-length buckets")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="--mode trace: shared system-prefix tokens "
                         "prepended to every stream's prompt")
    ap.add_argument("--tail-lengths", default="1,2,4,8,16",
                    help="--mode trace: long-tail per-stream prompt "
                         "suffix lengths, cycled")
    ap.add_argument("--streams", type=int, default=16,
                    help="--mode trace: generation streams in the "
                         "trace")
    ap.add_argument("--concurrency", type=int, default=8,
                    help="--mode trace: max streams in flight")
    ap.add_argument("--short-streams", type=int, default=40,
                    help="--mode mixed-trace: short-chat arrivals in "
                         "the schedule (prompts = --prompt-tokens, "
                         "generation = --gen-tokens, Poisson at "
                         "--rate)")
    ap.add_argument("--long-prompt-tokens", type=int, default=224,
                    help="--mode mixed-trace: prompt length of the "
                         "periodic long arrivals (the compute-bound "
                         "prefill)")
    ap.add_argument("--long-gen-tokens", type=int, default=2,
                    help="--mode mixed-trace: tokens generated per "
                         "long stream")
    ap.add_argument("--long-every-s", type=float, default=0.5,
                    help="--mode mixed-trace: long-arrival period")
    ap.add_argument("--prefill-max-pending", type=int, default=8,
                    help="--mode mixed-trace: the prefill replica's "
                         "admission bound")
    ap.add_argument("--prefill-nice", type=int, default=5,
                    help="--mode mixed-trace: CPU niceness for the "
                         "prefill subprocess — emulates the separate "
                         "host the prefill role gets in production, "
                         "so a shared CI box's timeslicing doesn't "
                         "charge prefill bursts to decode intertoken "
                         "(0 = share the cores as-is)")
    ap.add_argument("--scale-drill", action="store_true",
                    help="--mode mixed-trace: append the autoscaler "
                         "leg — a real subprocess fleet hammered past "
                         "its prefill admission bound until scale-up "
                         "executes (monitor JSONL lands in "
                         "--monitor-dir)")
    ap.add_argument("--monitor-dir", default=None,
                    help="--scale-drill: directory for the drill's "
                         "monitor metrics JSONL (default: a temp dir, "
                         "i.e. discarded)")
    ap.add_argument("--speculate-k", type=int, default=4,
                    help="draft tokens per speculative round")
    ap.add_argument("--draft-export-dir", default=None,
                    help="speculative draft export for the in-process "
                         "server (--demo exports a bf16 self-draft)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="--mode trace single leg: disable the prefix "
                         "cache")
    ap.add_argument("--prefill-compare", action="store_true",
                    help="--decode: run the SAME concurrent prompt "
                         "trace on a serial-admission server "
                         "(prefill_batch=1) and a batched one "
                         "(--decode-prefill-batch), verify "
                         "byte-identical outputs, report aggregate "
                         "prefill tok/s + TTFT p50/p99 per leg")
    ap.add_argument("--decode-prefill-batch", type=int, default=8,
                    help="--decode in-process server: prompts "
                         "coalesced into one batched prefill program "
                         "(1 = serial admission)")
    ap.add_argument("--decode-prefill-delay-ms", type=float,
                    default=2.0,
                    help="--decode in-process server: how long the "
                         "oldest pending prompt waits for company "
                         "before a partial batch launches")
    ap.add_argument("--spec-compare", action="store_true",
                    help="--mode trace: run baseline (no draft, no "
                         "prefix cache) and optimized (both on) legs "
                         "over the SAME trace, verify byte-identical "
                         "outputs, report the per-stream speedup")
    ap.add_argument("--demo-d-model", type=int, default=32)
    ap.add_argument("--demo-layers", type=int, default=2)
    ap.add_argument("--demo-heads", type=int, default=2)
    ap.add_argument("--demo-vocab", type=int, default=64)
    ap.add_argument("--demo-seq-len", type=int, default=32)
    ap.add_argument("--demo-train-epochs", type=int, default=0,
                    help="--mode trace --demo: train target AND a "
                         "smaller draft net this many epochs on the "
                         "synthetic successor-table LM task before "
                         "exporting (0 = untrained target with a bf16 "
                         "self-draft)")
    ap.add_argument("--demo-draft-d-model", type=int, default=64)
    ap.add_argument("--demo-draft-layers", type=int, default=1)
    ap.add_argument("--demo-draft-heads", type=int, default=2)
    ap.add_argument("--shm-compare", action="store_true",
                    help="shared-memory-lane leg (ISSUE 20): ship the "
                         "SAME KV pages from a fresh prefill "
                         "subprocess in-band vs over the shm lane, "
                         "byte-identity-checked; exits 1 unless the "
                         "lane carried the pages with zero leaked "
                         "segments")
    ap.add_argument("--out", default="BENCH_serving.json")
    args = ap.parse_args(argv)

    if args.shm_compare:
        return shm_compare_main(args)
    if args.prefill_compare or args.mode in ("trace", "mixed-trace"):
        if not args.decode:
            ap.error("--prefill-compare is a --decode mode"
                     if args.prefill_compare
                     else f"--mode {args.mode} is a --decode mode")
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            out = (prefill_compare_main(args, td)
                   if args.prefill_compare
                   else trace_main(args, td) if args.mode == "trace"
                   else mixed_main(args, td))
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
        print(json.dumps(out, indent=1))
        print(f"BENCH_serving written to {args.out}")
        return 0

    import tempfile

    from theanompi_tpu.serving import (
        BatchPolicy,
        InferenceClient,
        InferenceServer,
        load_export,
        serve,
    )

    tmp_ctx = tempfile.TemporaryDirectory()
    server = thread = None
    try:
        if args.addr is None:
            export_dir = args.export_dir
            if export_dir is None:
                if not args.demo:
                    ap.error("need --addr, --export-dir, or --demo")
                export_dir = _demo_export(tmp_ctx.name,
                                          decode=args.decode)
            policy = BatchPolicy(max_batch=args.max_batch,
                                 max_delay_ms=args.max_delay_ms,
                                 max_queue=args.max_queue)
            decode_opts = (dict(max_seqs=args.decode_max_seqs,
                                max_pending=args.decode_max_pending)
                           if args.decode else None)
            server = InferenceServer(export_dir,
                                     replicas=args.replicas,
                                     policy=policy,
                                     decode=args.decode,
                                     decode_opts=decode_opts).start()
            port = _free_port()
            ready = threading.Event()
            thread = threading.Thread(
                target=serve, args=(server, "127.0.0.1", port, ready),
                daemon=True)
            thread.start()
            assert ready.wait(30), "server never came up"
            addr = f"127.0.0.1:{port}"
            meta = load_export(export_dir).meta
        else:
            addr = args.addr
            if args.export_dir:
                meta = load_export(args.export_dir).meta
            else:
                meta = {}
        if args.decode:
            vocab = int((meta.get("net") or {}).get("vocab", 64))
            sample = (np.arange(args.prompt_tokens, dtype=np.int32)
                      % max(2, vocab - 1)) + 1
        else:
            shape = tuple(meta.get("sample_shape") or (32, 32, 3))
            dtype = np.dtype(meta.get("sample_dtype") or "uint8")
            sample = np.zeros((args.rows, *shape), dtype)

        probe = InferenceClient(addr)
        if args.decode:  # one warm stream outside the window
            probe.generate(sample, args.gen_tokens)
        else:
            probe.infer(sample)
        result = run_load(addr, sample, args.mode, args.clients,
                          args.rate, args.duration,
                          decode=args.decode,
                          gen_tokens=args.gen_tokens)
        stats = probe.stats()
        if server is not None:
            probe.shutdown()
        probe.close()
        out = {
            "bench": "serving",
            "mode": args.mode,
            "decode": args.decode,
            "clients": args.clients,
            "rate_rps": args.rate if args.mode == "open" else None,
            "server": {
                "addr": addr,
                "version": stats.get("version"),
                "replicas": stats.get("live_replicas"),
                "overloaded": stats.get("overloaded"),
            },
            **result,
        }
        if args.decode:
            # the repository's one tokens/s arithmetic
            from theanompi_tpu.utils.token_accounting import (
                token_throughput,
            )

            n_chips = 1
            if server is not None:
                import jax

                n_chips = len(jax.devices())
            reps = stats.get("replicas") or [{}]
            out.update(
                prompt_tokens=args.prompt_tokens,
                gen_tokens_per_stream=args.gen_tokens,
                throughput=token_throughput(result["tokens"],
                                            result["wall_s"], n_chips),
                intertoken_ms=reps[0].get("intertoken_ms"),
                server_decode={
                    "tokens": stats.get("tokens"),
                    "steps": stats.get("steps"),
                    "shared_steps": stats.get("shared_steps"),
                    "max_concurrent": stats.get("max_concurrent"),
                    "mean_tokens_per_step": (
                        stats["tokens"] / stats["steps"]
                        if stats.get("steps") else None),
                },
            )
        else:
            out.update(
                rows_per_request=args.rows,
                server_batching={
                    "batches": stats.get("batches"),
                    "batch_rows": stats.get("rows"),
                    "max_occupancy": stats.get("max_occupancy"),
                    "mean_occupancy": (stats["rows"] / stats["batches"]
                                       if stats.get("batches")
                                       else None),
                },
            )
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
        print(json.dumps(out, indent=1))
        print(f"BENCH_serving written to {args.out}")
        return 0
    finally:
        if server is not None:
            server.stop()
        tmp_ctx.cleanup()


if __name__ == "__main__":
    raise SystemExit(main())
