"""Multi-replica inference server + wire client.

The transport is the shared RPC substrate (``parallel/rpc.py``) —
selector event loop, HMAC auth with a handshake deadline (NO default
key; ``THEANOMPI_TPU_SERVICE_KEY`` gates both ends), negotiated
wire-v2 framing, typed error names riding the ``err`` reply prefix —
so everything learned on the param service (reconnect-with-backoff
clients, fast-failing server errors) carries over to serving.

Topology: one :class:`InferenceServer` owns N :class:`Replica`\\ s.
Each replica is an :class:`~theanompi_tpu.serving.export.InferenceSession`
(its own jitted eval fn — on real hardware each would pin its own
device) behind its own :class:`~theanompi_tpu.serving.batcher.DynamicBatcher`
queue.  Requests round-robin over live replicas with overflow
failover; when EVERY live replica's queue is full the request is
rejected with :class:`Overloaded` — bounded queues, bounded latency
(docs/SERVING.md).

Resilience wiring: ``serve_rpc`` (per-request, in the connection
handler) and ``serve_step`` (per-batch, in the replica) are fault
sites (resilience.faults).  A batch-execution failure fails that
batch's requests, then the replica is RESTARTED FROM THE EXPORT — a
fresh verified load of the current version — up to ``max_restarts``
times, after which the replica is lost and traffic routes around it
(the quorum analogue: a server with zero live replicas rejects, it
does not crash).

Hot reload: a watcher polls the export directory for a newer version
(meta-sidecar presence = completed publish); a new one is VERIFIED-loaded
once and swapped into every replica atomically — in-flight batches
finish on the old arrays, zero requests dropped
(tests/test_serving.py pins this).
"""

from __future__ import annotations

import argparse
import os
import threading
import time
from typing import Any

import numpy as np

from theanompi_tpu import monitor
from theanompi_tpu.analysis.lockgraph import make_lock
from theanompi_tpu.decode.migrate import IncompatiblePages
from theanompi_tpu.parallel import rpc, wire
from theanompi_tpu.resilience import faults
from theanompi_tpu.serving.batcher import (
    BatchPolicy,
    DynamicBatcher,
    Overloaded,
)
from theanompi_tpu.serving.export import (
    IncompatibleExport,
    InferenceSession,
    build_model_from_meta,
    draft_incompatibility,
    export_incompatibility,
    latest_export_version,
    load_export,
)

PyTree = Any

#: default port one above the param service's 45800 block
DEFAULT_PORT = 45900


class Replica:
    """One inference session + batcher under restart supervision."""

    def __init__(self, idx: int, export_dir: str, policy: BatchPolicy,
                 loaded, model, max_restarts: int = 2,
                 donate: bool = True):
        self.idx = int(idx)
        self.export_dir = export_dir
        self.max_restarts = int(max_restarts)
        self.restarts = 0
        self._steps = 0
        self.session = InferenceSession(
            model, params=loaded.params, model_state=loaded.model_state,
            version=loaded.version, donate=donate)
        self.batcher = DynamicBatcher(
            self._run_batch, policy, replica=self.idx,
            on_batch_error=self._on_batch_error)

    @property
    def alive(self) -> bool:
        return self.batcher.alive

    def submit(self, x: np.ndarray) -> np.ndarray:
        return self.batcher.submit(x)

    def _run_batch(self, x: np.ndarray) -> np.ndarray:
        self._steps += 1
        faults.fire("serve_step", replica=self.idx, step=self._steps)
        return self.session.infer(x)

    def _on_batch_error(self, exc: BaseException) -> bool:
        """Supervised recovery (resilience, docs/SERVING.md): reload
        this replica's arrays from the export — a fresh read of THE
        VERSION BEING SERVED, so a batch failure caused by in-memory
        corruption starts over from known-good bytes.  Pinning the
        version matters: loading "newest" here would silently swap in
        a just-published export the reload watcher may have REFUSED as
        incompatible (weight dtype / net dims) — upgrades go through
        `check_reload`'s compatibility gate, never through a crash.
        Returns False (replica lost) once the budget is spent."""
        self.restarts += 1
        monitor.inc("serving/replica_restarts_total", replica=self.idx)
        if self.restarts > self.max_restarts:
            print(f"[serving] replica {self.idx} exhausted "
                  f"{self.max_restarts} restarts "
                  f"({type(exc).__name__}: {exc}); marking it lost",
                  flush=True)
            return False
        try:
            loaded = load_export(self.export_dir,
                                 version=self.session.version)
        except Exception as e:
            print(f"[serving] replica {self.idx} restart-from-export "
                  f"failed ({type(e).__name__}: {e}); marking it lost",
                  flush=True)
            return False
        swapped = self.session.swap(loaded.version, loaded.params,
                                    loaded.model_state)
        print(f"[serving] replica {self.idx} restarted "
              + (f"from export v{loaded.version}" if swapped else
                 f"on v{self.session.version} (a concurrent hot "
                 f"reload superseded the v{loaded.version} load)")
              + f" after {type(exc).__name__} "
              f"(restart {self.restarts}/{self.max_restarts})",
              flush=True)
        return True

    def swap(self, version: int, params, model_state) -> None:
        self.session.swap(version, params, model_state)


class InferenceServer:
    """Replica pool + admission + hot reload (module docstring)."""

    def __init__(self, export_dir: str, replicas: int = 1,
                 policy: BatchPolicy | None = None,
                 max_restarts: int = 2, reload_poll_s: float = 1.0,
                 warmup: bool = True, mesh=None, donate: bool = True,
                 model=None, decode: bool = False,
                 decode_opts: dict | None = None):
        if replicas < 1:
            raise ValueError(f"need >= 1 replica, got {replicas}")
        self.export_dir = os.path.abspath(export_dir)
        self.policy = policy or BatchPolicy()
        self.reload_poll_s = float(reload_poll_s)
        self.decode = bool(decode)
        loaded = load_export(self.export_dir)
        # ONE model rebuild (module + config threading) shared by all
        # replicas; each replica jits its own fn over the shared
        # module.  ``model=`` skips the rebuild when the caller (a
        # test, an embedded exporter-server) already holds the
        # instance — the ARRAYS still come from the verified export.
        self.model = (model if model is not None
                      else build_model_from_meta(loaded.meta, mesh=mesh))
        self.version = loaded.version        # guarded_by: self._reload_lock
        #: meta of the version being served — the hot-reload
        #: compatibility anchor (export_incompatibility)
        self._meta = loaded.meta             # guarded_by: self._reload_lock
        self.draft_export_dir = None
        self.draft_version = None            # guarded_by: self._reload_lock
        self._draft_meta = None              # guarded_by: self._reload_lock
        if self.decode:
            # autoregressive mode (theanompi_tpu/decode): replicas are
            # DecodeReplicas (paged KV-cache + continuous batcher) and
            # the wire surface is the 'generate' op
            if not loaded.meta.get("decode"):
                raise ValueError(
                    "decode mode needs a decode-capable export "
                    "(TransformerLM family; export_meta 'decode' is "
                    f"false/absent in {self.export_dir})")
            from theanompi_tpu.decode import DecodePolicy, DecodeReplica

            opts = dict(decode_opts or {})
            pol_kw = {k: opts.pop(k)
                      for k in ("max_pending", "max_new_cap",
                                "submit_timeout_s", "eos_token",
                                "speculate_k", "prefill_batch",
                                "prefill_delay_ms")
                      if k in opts}
            self.replicas = [
                DecodeReplica(i, self.export_dir, self.model, loaded,
                              policy=DecodePolicy(**pol_kw),
                              max_restarts=max_restarts, donate=donate,
                              **opts)
                for i in range(int(replicas))
            ]
            #: draft-export watcher state (speculative decoding): the
            #: replicas validated + loaded the draft at construction;
            #: the watcher polls its dir like the target's
            self.draft_export_dir = (
                os.path.abspath(opts["draft_export_dir"])
                if opts.get("draft_export_dir") else None)
            r0 = self.replicas[0]
            self.draft_version = (            # guarded_by: self._reload_lock
                r0.draft_session.version
                if r0.draft_session is not None else None)
            self._draft_meta = r0.draft_meta  # guarded_by: self._reload_lock
            if warmup:
                for r in self.replicas:
                    r.warmup()
        else:
            self.replicas = [
                Replica(i, self.export_dir, self.policy, loaded,
                        self.model, max_restarts=max_restarts,
                        donate=donate)
                for i in range(int(replicas))
            ]
            if warmup:
                shape = tuple(loaded.meta.get("sample_shape")
                              or self.model.data.sample_shape)
                dtype = np.dtype(loaded.meta.get("sample_dtype") or
                                 np.float32)
                for r in self.replicas:
                    # fn=session.infer: warmup compiles the same jitted
                    # fn but skips the serve_step fault site — a fault
                    # plan must take down served batches (supervised
                    # restart), not construction before the port is
                    # bound
                    r.batcher.warmup(shape, dtype, fn=r.session.infer)
        self._rr_lock = make_lock("InferenceServer._rr_lock")
        self._rr = 0                          # guarded_by: self._rr_lock
        self._stop = threading.Event()
        self._watcher: threading.Thread | None = None
        self._reload_lock = make_lock("InferenceServer._reload_lock")
        #: newest published version that failed verification or was
        #: refused as incompatible — not re-LOADED by the reload poll
        #: until a strictly newer one appears
        self._bad_newest: int | None = None  # guarded_by: self._reload_lock
        #: refusal reason when _bad_newest was an IncompatibleExport:
        #: re-raised (from memory, no disk load) on every further
        #: reload of that version, so a client's reload() RPC gets the
        #: typed error regardless of whether the background watcher
        #: observed the publish first
        self._bad_reason: str | None = None  # guarded_by: self._reload_lock
        #: same memory for the DRAFT export's poll (speculative
        #: decoding): a published draft whose dims/vocab are
        #: incompatible with the live target is refused once, loudly,
        #: and remembered until a strictly newer draft publish
        self._bad_draft_newest: int | None = None  # guarded_by: self._reload_lock
        self._bad_draft_reason: str | None = None  # guarded_by: self._reload_lock
        monitor.set_gauge("serving/model_version", self.version)
        monitor.set_gauge("serving/replicas", len(self.replicas))

    # -- lifecycle -----------------------------------------------------

    def start(self) -> "InferenceServer":
        for r in self.replicas:
            r.batcher.start()
        if self.reload_poll_s > 0:
            self._watcher = threading.Thread(
                target=self._watch_reload, daemon=True,
                name="serving-reload-watcher")
            self._watcher.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        for r in self.replicas:
            r.batcher.stop()
        if self._watcher is not None:
            self._watcher.join(timeout=5)

    # -- request path --------------------------------------------------

    def _route(self, fn_name: str, *args):
        """Round-robin one request over live replicas with overflow
        failover; Overloaded only when EVERY live replica rejects."""
        n = len(self.replicas)
        with self._rr_lock:
            start = self._rr
            self._rr = (self._rr + 1) % n
        last: Overloaded | None = None
        any_alive = False
        for k in range(n):
            r = self.replicas[(start + k) % n]
            if not r.alive:
                continue
            any_alive = True
            try:
                return getattr(r, fn_name)(*args)
            except Overloaded as e:
                last = e
        if not any_alive:
            raise Overloaded("no live replicas (all lost); the server "
                             "needs a restart or a good export")
        raise last if last is not None else Overloaded("rejected")

    def submit(self, x: np.ndarray) -> np.ndarray:
        """Route one eval request to a live replica."""
        if self.decode:
            raise ValueError("this server runs decode mode; use the "
                             "'generate' op (InferenceClient.generate)")
        return self._route("submit", x)

    def generate(self, prompt: np.ndarray,
                 max_new: int | None = None):
        """Route one token-generation request to a live decode
        replica; returns the generated token ids (int32) — or a
        :class:`~theanompi_tpu.decode.scheduler.MigratedStream` when
        the replica drained mid-stream (scale-down)."""
        if not self.decode:
            raise ValueError("this server runs eval mode; start it "
                             "with decode=True (tmlocal SERVE "
                             "--decode) for the generate op")
        out = self._route("generate", prompt, max_new)
        if not isinstance(out, (list, np.ndarray)):
            return out  # MigratedStream
        return np.asarray(out, np.int32)

    def generate_adopted(self, manifest: dict, k, v,
                         max_new: int | None = None):
        """Route one MIGRATED stream (decode/migrate.py: a prefill
        replica's pages + manifest) to a live decode replica, which
        adopts the pages and decodes from there.  A geometry mismatch
        raises the typed :class:`IncompatiblePages` straight through
        ``_route`` — a per-stream refusal, never a replica failure."""
        if not self.decode:
            raise ValueError("this server runs eval mode; start it "
                             "with decode=True (tmlocal SERVE "
                             "--decode) for the adopt op")
        out = self._route("generate_adopted", manifest,
                          np.asarray(k), np.asarray(v), max_new)
        if not isinstance(out, (list, np.ndarray)):
            return out  # MigratedStream
        return np.asarray(out, np.int32)

    def drain_migrate(self) -> int:
        """Scale-down hand-off: every decode replica stops admitting
        (Overloaded) and exports its live streams as MigratedStream
        payloads at the next step boundary (the autoscaler's decode
        scale-down path — docs/SERVING.md).  Returns the replica
        count told to drain."""
        if not self.decode:
            raise ValueError("drain_migrate is a decode-mode op")
        for r in self.replicas:
            r.drain_migrate()
        return len(self.replicas)

    # -- hot reload ----------------------------------------------------

    def check_reload(self) -> int:
        """One poll: load + swap if a newer version is published;
        returns the serving version either way.  Safe to call
        concurrently (watcher + the ``reload`` RPC)."""
        with self._reload_lock:
            newest = latest_export_version(self.export_dir)
            if newest is None or newest <= self.version:
                return self.version
            if newest == self._bad_newest:
                if self._bad_reason is not None:
                    # a REFUSED (not corrupt) publish: every reload of
                    # it re-raises the typed error from memory, so the
                    # refusal is observable however the poll race with
                    # the watcher went
                    raise IncompatibleExport(self._bad_reason)
                return self.version
            loaded = load_export(self.export_dir)
            if loaded.version <= self.version:
                # the newest manifest is on disk but its files did not
                # verify (restore_latest_verified fell back, possibly
                # to what we already serve).  Versions are immutable
                # (export_model refuses re-export), so retrying the
                # same corrupt version every poll is pure disk/CPU
                # churn — remember it and wait for a strictly newer
                # manifest to reset the skip.
                self._bad_newest = newest
                self._bad_reason = None
                return self.version
            reason = export_incompatibility(self._meta, loaded.meta)
            if reason is not None:
                # refusal, not a crash: the export verified but must
                # not be swapped into live replicas (different model /
                # sample shape / net dims / weight dtype / decode
                # capability).  Remember it like a corrupt newest so
                # the poll loop does not re-LOAD it every interval —
                # but keep the reason, so every reload of this version
                # still surfaces the typed error; a strictly newer
                # publish resets the skip.
                self._bad_newest = newest
                self._bad_reason = (f"refusing hot reload "
                                    f"v{self.version} -> "
                                    f"v{loaded.version}: {reason}")
                monitor.inc("serving/reload_refused_total")
                print(f"[serving] {self._bad_reason}", flush=True)
                raise IncompatibleExport(self._bad_reason)
            self._bad_newest = None
            self._bad_reason = None
            for r in self.replicas:
                r.swap(loaded.version, loaded.params,
                       loaded.model_state)
            self._meta = loaded.meta
            old, self.version = self.version, loaded.version
            monitor.set_gauge("serving/model_version", self.version)
            monitor.inc("serving/reloads_total")
            print(f"[serving] hot reload v{old} -> v{self.version} "
                  f"({len(self.replicas)} replicas, in-flight "
                  "requests kept)", flush=True)
            return self.version

    def check_draft_reload(self) -> int | None:
        """One poll of the DRAFT export dir (speculative decoding):
        load + swap a newer compatible draft into every replica;
        returns the serving draft version (None when speculation is
        off).  A draft whose dims/vocab no longer fit the live target
        raises the typed :class:`IncompatibleExport` — refused and
        REMEMBERED exactly like a refused target publish (no re-load
        churn, every reload re-raises from memory, the server keeps
        serving and keeps speculating on the old draft) until a
        strictly newer draft version supersedes it."""
        if not self.decode or self.draft_export_dir is None:
            return None
        with self._reload_lock:
            newest = latest_export_version(self.draft_export_dir)
            if newest is None or newest <= self.draft_version:
                return self.draft_version
            if newest == self._bad_draft_newest:
                if self._bad_draft_reason is not None:
                    raise IncompatibleExport(self._bad_draft_reason)
                return self.draft_version
            loaded = load_export(self.draft_export_dir)
            if loaded.version <= self.draft_version:
                # newest manifest failed verification; fell back —
                # remember like the target poll does
                self._bad_draft_newest = newest
                self._bad_draft_reason = None
                return self.draft_version
            # two anchors: the live TARGET (vocab/positional range —
            # the accept comparison) and the live DRAFT session (net
            # dims etc. — the new arrays must adopt into the compiled
            # draft programs, the same reason target hot reload
            # refuses a resized net; restart to change draft dims)
            reason = (draft_incompatibility(self._meta, loaded.meta)
                      or export_incompatibility(self._draft_meta,
                                                loaded.meta))
            if reason is not None:
                self._bad_draft_newest = newest
                self._bad_draft_reason = (
                    f"refusing draft hot reload v{self.draft_version} "
                    f"-> v{loaded.version}: {reason}")
                monitor.inc("serving/reload_refused_total")
                print(f"[serving] {self._bad_draft_reason}", flush=True)
                raise IncompatibleExport(self._bad_draft_reason)
            self._bad_draft_newest = None
            self._bad_draft_reason = None
            swapped = sum(1 for r in self.replicas
                          if r.swap_draft(loaded.version,
                                          loaded.params))
            if swapped == 0:
                # every replica downgraded to plain decode (failed
                # draft restarts): there is no draft session to swap
                # into, and claiming a reload would advertise a draft
                # version nobody serves — restart to re-enable
                print(f"[serving] draft v{loaded.version} published "
                      "but speculation is disabled on every replica "
                      "(failed draft restarts); not swapped — restart "
                      "the server to re-enable speculation",
                      flush=True)
                return self.draft_version
            self._draft_meta = loaded.meta
            old, self.draft_version = self.draft_version, loaded.version
            monitor.inc("serving/reloads_total")
            print(f"[serving] draft hot reload v{old} -> "
                  f"v{self.draft_version} ({swapped}/"
                  f"{len(self.replicas)} replicas speculating, "
                  "in-flight streams kept)", flush=True)
            return self.draft_version

    def _watch_reload(self) -> None:
        while not self._stop.wait(self.reload_poll_s):
            for check in (self.check_reload, self.check_draft_reload):
                try:
                    check()
                except IncompatibleExport:
                    # already printed once at refusal time; the
                    # remembered refusal re-raises every poll until
                    # superseded, and re-printing it each second is
                    # pure log spam
                    pass
                except Exception as e:
                    # a broken half-published export must not kill the
                    # watcher; next poll retries
                    print(f"[serving] reload check failed: "
                          f"{type(e).__name__}: {e}", flush=True)

    # -- introspection -------------------------------------------------

    def stats(self) -> dict:
        # TM101 regression: the serving version is hot-reload state —
        # replica stats AND the version are read under the reload lock
        # so a concurrent swap cannot pair a new version with stats
        # from the other side of it.  Cost: a stats() issued DURING a
        # reload blocks until the verified load finishes — truthful,
        # and only as long as the reload itself.
        with self._reload_lock:
            reps = [dict(r.batcher.stats(), restarts=r.restarts,
                         version=r.session.version)
                    for r in self.replicas]
            version = self.version
            draft_version = self.draft_version
        out = {
            "version": version,
            "decode": self.decode,
            "replicas": reps,
            "overloaded": sum(r.get("overloaded", 0) for r in reps),
            "live_replicas": sum(1 for r in self.replicas if r.alive),
        }
        if self.decode:
            # decode replicas account tokens/steps, not batches/rows
            drafted = sum((r.get("speculation") or {})
                          .get("draft_tokens", 0) for r in reps)
            accepted = sum((r.get("speculation") or {})
                           .get("accepted_draft_tokens", 0)
                           for r in reps)
            out.update(
                tokens=sum(r.get("tokens", 0) for r in reps),
                steps=sum(r.get("steps", 0) for r in reps),
                shared_steps=sum(r.get("shared_steps", 0)
                                 for r in reps),
                max_concurrent=max((r.get("max_concurrent", 0)
                                    for r in reps), default=0),
                draft_version=draft_version,
                draft_tokens=drafted,
                accepted_draft_tokens=accepted,
                accept_rate=accepted / drafted if drafted else None,
                prefix_cache_hits=sum(
                    (r.get("prefix_cache") or {}).get("hits", 0)
                    for r in reps),
            )
        else:
            out.update(
                batches=sum(r.get("batches", 0) for r in reps),
                rows=sum(r.get("rows", 0) for r in reps),
                max_occupancy=max((r.get("max_occupancy", 0)
                                   for r in reps), default=0),
            )
        return out

    # -- wire dispatch ---------------------------------------------------

    def rpc_max_workers(self) -> int:
        """Executor width for the RPC substrate: enough workers that
        every admissible request (the batchers' bounded queues + one
        executing batch per replica) can block in a handler
        concurrently, plus slack so O(1) ``Overloaded`` rejections
        never queue behind parked handlers."""
        n = len(self.replicas)
        if self.decode:
            per = max((getattr(r.batcher.policy, "max_pending", 32)
                       + getattr(r.session.cfg, "max_seqs", 8))
                      for r in self.replicas)
        else:
            per = self.policy.max_queue + self.policy.max_batch
        return n * per + 8

    @staticmethod
    def _wire_tokens(out):
        """Wire encoding for a generate/adopt result: a token array,
        or a drained stream's pages as a tagged tuple (the token ids
        can never collide with the tag — normal results are arrays)."""
        if isinstance(out, np.ndarray):
            return out
        # MigratedStream: partial tokens + manifest + pages
        return ("migrated", [int(t) for t in out.tokens], out.manifest,
                wire.RawArrays(np.asarray(out.k), np.asarray(out.v)))

    def handle(self, op: str, *args):
        if op == "infer":
            (x,) = args
            return self.submit(np.asarray(x))
        if op == "generate":
            prompt, max_new = args
            return self._wire_tokens(
                self.generate(np.asarray(prompt, np.int32),
                              None if max_new is None
                              else int(max_new)))
        if op == "adopt":
            # pages arrive as one RawArrays frame pair (decoded to a
            # plain (k, v) tuple by the wire) + the page manifest
            manifest, pages, max_new = args
            k, v = pages
            return self._wire_tokens(
                self.generate_adopted(manifest, k, v,
                                      None if max_new is None
                                      else int(max_new)))
        if op == "drain":
            return self.drain_migrate()
        if op == "stats":
            return self.stats()
        if op == "reload":
            # target first, then the draft poll — either refusal
            # surfaces as the typed IncompatibleExport (a successful
            # target swap is already committed when a draft refusal
            # raises; the next reload returns the new version)
            version = self.check_reload()
            self.check_draft_reload()
            return version
        if op == "ping":
            return "pong"
        raise ValueError(f"unknown op {op!r}")


class _ServingRpcHooks(rpc.RpcHooks):
    """The inference plane's seams into the shared RPC substrate
    (``parallel/rpc.py``): literal ``serving/*`` series names (the
    TM403/404 docs-coverage contract) and the ``serve_rpc`` fault
    site.  Migrating onto the substrate also bought this plane wire-v2
    framing — request/reply arrays now travel as zero-copy buffers
    instead of pickles — with clients unchanged
    (:class:`InferenceClient` always negotiated; the old loop just
    answered "unknown op")."""

    plane = "serving"

    def on_connect(self) -> None:
        monitor.add_gauge("serving/clients", 1.0)

    def on_disconnect(self) -> None:
        monitor.add_gauge("serving/clients", -1.0)

    def on_request(self, op: str, ms: float) -> None:
        monitor.inc("serving/requests_total", op=op)
        monitor.observe("serving/rpc_ms", ms, op=op)
        monitor.progress(phase="serving")

    def on_error(self, op: str) -> None:
        monitor.inc("serving/errors_total", op=op)

    def on_negotiate(self, opts) -> None:
        monitor.inc("serving/wire_negotiations_total",
                    compression=opts.compression, dtype=opts.dtype)

    def fire(self, op: str) -> None:
        # fault plane: 'raise' rejects this RPC (the client sees the
        # typed err), 'delay' adds latency — both exercised with the
        # server LIVE, which is the point
        faults.fire("serve_rpc", op=op)


def serve(server: InferenceServer, host: str = "0.0.0.0",
          port: int = DEFAULT_PORT,
          ready_event: threading.Event | None = None,
          stop_event: threading.Event | None = None,
          authkey: bytes | None = None,
          loop: str | None = None) -> None:
    """The shared RPC substrate over an :class:`InferenceServer` until
    a ``shutdown`` op or ``stop_event`` (``parallel/rpc.py``; same
    loops/knobs as every other plane).  The executor pool is sized by
    the plane's own admission bound — an ``infer``/``generate``
    handler legitimately blocks until its batch completes, and the
    batchers' bounded queues already cap how many can be in flight;
    past that bound requests get their O(1) typed ``Overloaded``."""
    from theanompi_tpu.parallel.service import _authkey

    if authkey is None:
        authkey = _authkey(generate=True)
    rpc.serve(server, host, port, ready_event=ready_event,
              stop_event=stop_event, authkey=authkey,
              hooks=_ServingRpcHooks(), loop=loop,
              max_workers=server.rpc_max_workers())


# ---------------------------------------------------------------------------
# Client
# ---------------------------------------------------------------------------


from theanompi_tpu.parallel.service import ServiceClient, ServiceError


class InferenceClient(ServiceClient):
    """Wire client: transport failures reconnect-with-backoff
    (``infer`` is pure, so at-least-once is safe); server-side errors
    fail fast, with :class:`Overloaded` re-raised as its own type off
    the typed err-prefix (never retried by the transport — backoff
    or shed ABOVE the wire)."""

    def infer(self, x) -> np.ndarray:
        try:
            return self.call("infer", np.asarray(x))
        except ServiceError as e:
            if Overloaded.__name__ in str(e):
                raise Overloaded(str(e)) from None
            raise

    @staticmethod
    def _unwire_tokens(out):
        """Inverse of ``InferenceServer._wire_tokens``: token ids, or
        a drained stream's ``MigratedStream`` for the router to
        re-dispatch (frontdoor/router.py stitches the halves)."""
        if (isinstance(out, tuple) and len(out) == 4
                and out[0] == "migrated"):
            from theanompi_tpu.decode.scheduler import MigratedStream

            _, tokens, manifest, pages = out
            k, v = pages
            return MigratedStream([int(t) for t in tokens],
                                  manifest, k, v)
        return np.asarray(out, np.int32)

    def generate(self, prompt, max_new: int | None = None):
        """Greedy-decode up to ``max_new`` tokens after ``prompt`` on
        a decode-mode server; returns the generated token ids (int32),
        or a ``MigratedStream`` when the serving replica drained
        mid-stream (scale-down — the caller re-dispatches).
        At-least-once safe like ``infer``: generation is deterministic
        (greedy) given the export version, and a redelivered request
        only costs duplicate work, never duplicate side effects."""
        try:
            return self._unwire_tokens(
                self.call("generate",
                          np.asarray(prompt, np.int32),
                          None if max_new is None else int(max_new)))
        except ServiceError as e:
            if Overloaded.__name__ in str(e):
                raise Overloaded(str(e)) from None
            raise

    def adopt(self, manifest: dict, k, v,
              max_new: int | None = None) -> np.ndarray:
        """Ship one migrated stream (page manifest + KV pages) to a
        decode-mode server; returns its generated token ids, first
        token included.  The pages travel as one ``RawArrays`` frame
        pair — the raw uint8 path, no compression and no wire-dtype
        re-encode, because KV bytes must arrive EXACTLY as prefilled
        (byte-identity is pinned at the bench level).  Geometry
        mismatches re-raise the server's typed
        :class:`~theanompi_tpu.decode.migrate.IncompatiblePages`;
        admission rejections re-raise :class:`Overloaded` — the
        connection survives both."""
        try:
            return self._unwire_tokens(
                self.call("adopt", manifest, wire.RawArrays(k, v),
                          None if max_new is None else int(max_new)))
        except ServiceError as e:
            if Overloaded.__name__ in str(e):
                raise Overloaded(str(e)) from None
            if IncompatiblePages.__name__ in str(e):
                raise IncompatiblePages(str(e)) from None
            raise

    def drain_migrate(self) -> int:
        """Tell a decode server to drain: stop admitting, export live
        streams as MigratedStream payloads (scale-down hand-off)."""
        return int(self.call("drain"))

    def stats(self) -> dict:
        return self.call("stats")

    def reload(self) -> int:
        """Force an immediate export-dir poll; returns the serving
        version after it.  An incompatible published export re-raises
        the server's typed :class:`IncompatibleExport` refusal."""
        try:
            return int(self.call("reload"))
        except ServiceError as e:
            if IncompatibleExport.__name__ in str(e):
                raise IncompatibleExport(str(e)) from None
            raise

    def shutdown(self) -> None:
        self.call("shutdown")


# ---------------------------------------------------------------------------
# Entry point (the launcher's SERVE mode lands here)
# ---------------------------------------------------------------------------


def decode_opts_from_args(args) -> dict | None:
    """The ``--decode-*`` flags → ``InferenceServer(decode_opts=...)``
    dict — ONE translation shared by the launcher's SERVE rule and
    this module's CLI (identically-named flags in both parsers), so a
    new decode knob cannot silently exist in one entry point only."""
    if not args.decode:
        return None
    opts = {
        "page_size": args.decode_page_size,
        "pages_per_seq": args.decode_pages_per_seq,
        "max_seqs": args.decode_max_seqs,
        "max_pending": args.decode_max_pending,
        "prefix_cache": not args.decode_no_prefix_cache,
        "prefill_batch": args.decode_prefill_batch,
        "prefill_delay_ms": args.decode_prefill_delay_ms,
    }
    if args.decode_fleet_cache:
        opts["fleet_cache"] = args.decode_fleet_cache
    if args.decode_prefill_buckets:
        opts["prefill_buckets"] = tuple(
            int(b) for b in args.decode_prefill_buckets.split(","))
    if args.decode_draft_export_dir:
        opts["draft_export_dir"] = args.decode_draft_export_dir
        opts["speculate_k"] = args.decode_speculate_k
    return opts


def serve_main(export_dir: str, host: str = "0.0.0.0",
               port: int = DEFAULT_PORT, replicas: int = 1,
               max_batch: int = 8, max_delay_ms: float = 5.0,
               buckets: tuple[int, ...] | None = None,
               max_queue: int = 32, max_restarts: int = 2,
               reload_poll_s: float = 1.0, decode: bool = False,
               decode_opts: dict | None = None) -> int:
    # persistent compilation cache before any replica warms up: the
    # per-bucket eval programs compile once per (shape, flags) EVER,
    # not once per server restart — a hot-standby restart re-serves in
    # deserialization time
    from theanompi_tpu.utils.helper_funcs import enable_compilation_cache

    enable_compilation_cache()
    policy = BatchPolicy(max_batch=max_batch, max_delay_ms=max_delay_ms,
                         buckets=buckets, max_queue=max_queue)
    # serving telemetry mirrors the param service's: request-driven
    # progress, so the stall watchdog is off; name-suffixed files so a
    # co-located trainer's rank0 files survive
    with monitor.session(stall_after=float("inf"),
                         name=f"serve{os.getpid()}"):
        monitor.progress(phase="serving")
        server = InferenceServer(
            export_dir, replicas=replicas, policy=policy,
            max_restarts=max_restarts, reload_poll_s=reload_poll_s,
            decode=decode, decode_opts=decode_opts)
        server.start()
        if decode:
            r0 = server.replicas[0]
            s0 = r0.session
            spec = ("off" if r0.draft_session is None else
                    f"k={r0.batcher.policy.speculate_k} "
                    f"draft=v{r0.draft_session.version}")
            print(f"[serving] DECODE v{server.version} x{replicas} "
                  f"replicas on {host}:{port} "
                  f"(window={s0.window}, page_size={s0.cfg.page_size}, "
                  f"max_seqs={s0.cfg.max_seqs}, "
                  f"prefill_buckets={s0.prefill_buckets}, "
                  f"speculation={spec}, prefix_cache="
                  f"{'on' if s0.prefix_cache is not None else 'off'})",
                  flush=True)
        else:
            print(f"[serving] v{server.version} x{replicas} replicas "
                  f"on {host}:{port} (max_batch={max_batch}, "
                  f"max_delay={max_delay_ms}ms, "
                  f"buckets={server.policy.resolved_buckets()}, "
                  f"max_queue={max_queue})", flush=True)
        try:
            serve(server, host, port)
        finally:
            server.stop()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="theanompi-tpu dynamic-batching inference server")
    ap.add_argument("--export-dir", required=True)
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=DEFAULT_PORT)
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-delay-ms", type=float, default=5.0)
    ap.add_argument("--buckets", default=None,
                    help="comma-separated padded batch sizes "
                         "(default: powers of two up to max-batch)")
    ap.add_argument("--max-queue", type=int, default=32)
    ap.add_argument("--max-restarts", type=int, default=2)
    ap.add_argument("--reload-poll-s", type=float, default=1.0)
    ap.add_argument("--decode", action="store_true",
                    help="autoregressive mode (theanompi_tpu/decode): "
                         "paged KV-cache + continuous batching; serves "
                         "the 'generate' op for TransformerLM exports")
    ap.add_argument("--decode-page-size", type=int, default=16)
    ap.add_argument("--decode-pages-per-seq", type=int, default=8)
    ap.add_argument("--decode-max-seqs", type=int, default=8)
    ap.add_argument("--decode-max-pending", type=int, default=32)
    ap.add_argument("--decode-prefill-buckets", default=None,
                    metavar="N,N,...",
                    help="padded prompt-length buckets (default powers "
                         "of two up to min(512, max_len))")
    ap.add_argument("--decode-draft-export-dir", default=None,
                    metavar="DIR",
                    help="speculative decoding: a small decode-capable "
                         "export that proposes tokens the target "
                         "verifies k-at-a-time in one bucketed step "
                         "(docs/SERVING.md 'Speculative decode'); "
                         "dims may differ, vocab must match")
    ap.add_argument("--decode-speculate-k", type=int, default=4,
                    help="draft tokens per speculative round (needs "
                         "--decode-draft-export-dir)")
    ap.add_argument("--decode-no-prefix-cache", action="store_true",
                    help="disable the cross-request prefix cache "
                         "(copy-on-write KV page sharing; on by "
                         "default — docs/SERVING.md 'Prefix cache')")
    ap.add_argument("--decode-prefill-batch", type=int, default=8,
                    help="max prompts coalesced into ONE batched "
                         "prefill program call per admission round "
                         "(1 = serial prefill, the pre-batching path "
                         "— docs/SERVING.md 'Batched prefill')")
    ap.add_argument("--decode-prefill-delay-ms", type=float,
                    default=2.0,
                    help="how long the oldest pending prompt may wait "
                         "for batch company before its prefill "
                         "launches regardless of occupancy")
    ap.add_argument("--decode-fleet-cache", default=None,
                    metavar="HOST:PORT",
                    help="fleet-wide prefix cache authority (a "
                         "prefill server's port): local prefix-cache "
                         "misses consult it, cold prefills register "
                         "their page-aligned prefixes — docs/"
                         "SERVING.md 'Fleet prefix cache'")
    ap.add_argument("--platform", default=None,
                    help="jax platform (e.g. 'cpu')")
    args = ap.parse_args(argv)
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    buckets = (tuple(int(b) for b in args.buckets.split(","))
               if args.buckets else None)
    decode_opts = decode_opts_from_args(args)
    return serve_main(args.export_dir, args.host, args.port,
                      replicas=args.replicas, max_batch=args.max_batch,
                      max_delay_ms=args.max_delay_ms, buckets=buckets,
                      max_queue=args.max_queue,
                      max_restarts=args.max_restarts,
                      reload_poll_s=args.reload_poll_s,
                      decode=args.decode, decode_opts=decode_opts)


if __name__ == "__main__":
    raise SystemExit(main())
