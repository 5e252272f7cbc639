"""frontdoor/: disaggregated prefill/decode serving (ISSUE 17).

The acceptance pins:

* **byte identity** — a stream routed prefill → (pages over the wire)
  → decode is token-identical to the single-role decode server and to
  the uncached full-forward oracle; the migrated page BYTES round-trip
  the wire exactly (raw frames, no re-encode);
* **typed refusals** — a geometry-mismatched adopt is refused with the
  typed ``IncompatiblePages`` over the wire and the CONNECTION (and
  the replica) keep serving; the whole manifest/pages refusal matrix
  is covered in-process;
* **failover** — a decode backend lost mid-stream makes the router
  re-prefill from the prompt and adopt onto a survivor; the retried
  stream is byte-identical (the adopt RPC returns whole streams, so
  nothing was delivered before the loss);
* **load shedding** — admission bounds anywhere (router, prefill
  fleet, decode fleet) surface as the typed ``Overloaded`` end to end,
  never a destructive retry;
* **scale events drop nothing** — adding a backend admits new traffic
  with zero dropped streams; removing one DRAINS (no new routes,
  in-flight streams finish, closed only at zero streams);
* **autoscaler units** — hysteresis/hold/cooldown against an injected
  clock; the signal fold (queue depth, occupancy, p99 vs SLO,
  overload-delta saturation); scale-down drains before release.

The real-subprocess fleet (``DisaggregatedFleet``) is exercised in the
slow set (``--runslow``); everything above runs
in-process over real sockets, the ``tests/test_decode.py`` pattern.
"""

from __future__ import annotations

import os
import threading

import numpy as np
import pytest

from theanompi_tpu.decode.migrate import (
    GEOMETRY_FIELDS,
    IncompatiblePages,
    manifest_incompatibility,
    page_manifest,
    pages_incompatibility,
)
from theanompi_tpu.frontdoor import (
    Autoscaler,
    HysteresisController,
    PrefillClient,
    PrefillServer,
    Router,
    RouterClient,
)
from theanompi_tpu.frontdoor import prefill as prefill_mod
from theanompi_tpu.frontdoor import router as router_mod
from theanompi_tpu.serving import (
    InferenceClient,
    InferenceServer,
    Overloaded,
    serve,
)

from tests._decode_helpers import VOCAB, build_tiny_lm
from tests._decode_helpers import flax_greedy as _flax_greedy
from tests._decode_helpers import free_port as _free_port

GEO = dict(page_size=4, pages_per_seq=8, max_seqs=4,
           prefill_buckets=(8,))


@pytest.fixture(scope="module")
def tiny_lm(tmp_path_factory):
    return build_tiny_lm(
        str(tmp_path_factory.mktemp("frontdoor") / "export"))


def _serve_thread(target_serve, obj, port):
    """Start ``target_serve(obj, ...)`` on 127.0.0.1:port in a daemon
    thread; returns (addr, stop_event, thread)."""
    ready, stop = threading.Event(), threading.Event()
    t = threading.Thread(target=target_serve,
                         args=(obj, "127.0.0.1", port, ready, stop),
                         daemon=True)
    t.start()
    assert ready.wait(30)
    return f"127.0.0.1:{port}", stop, t


@pytest.fixture(scope="module")
def servers(tiny_lm):
    """The expensive half of the stack, built once per module: one
    PrefillServer session, two geometry-matched decode servers (A, B)
    and one geometry-MISMATCHED one (C, page_size 2 vs 4) — batchers
    running, NO sockets (the wire is function-scoped so each test's
    RPC worker threads die with the test)."""
    model, params, export_dir = tiny_lm
    key_before = os.environ.get("THEANOMPI_TPU_SERVICE_KEY")
    pre = PrefillServer(export_dir, model=model, max_pending=8, **GEO)

    def decode_server(**over):
        opts = dict(GEO)
        opts.update(over)
        return InferenceServer(export_dir, replicas=1, reload_poll_s=0,
                               model=model, decode=True,
                               decode_opts=opts).start()

    srv_a = decode_server()
    srv_b = decode_server()
    srv_c = decode_server(page_size=2)  # window still 16 >= bucket 8
    yield dict(model=model, params=params, export_dir=export_dir,
               prefill_server=pre, srv_a=srv_a, srv_b=srv_b,
               srv_c=srv_c)
    for srv in (srv_a, srv_b, srv_c):
        srv.stop()
    if key_before is None:
        os.environ.pop("THEANOMPI_TPU_SERVICE_KEY", None)
    else:
        os.environ["THEANOMPI_TPU_SERVICE_KEY"] = key_before


@pytest.fixture()
def stack(servers):
    """Function-scoped wire over the module-scoped servers: serve
    loops (and their spawn-on-demand RPC pools) start and stop inside
    each test, so the thread-leak fence stays exact."""
    stops, threads = [], []

    def up(target_serve, obj):
        addr, stop, t = _serve_thread(target_serve, obj, _free_port())
        stops.append(stop)
        threads.append(t)
        return addr

    yield dict(servers,
               prefill=up(prefill_mod.serve,
                          servers["prefill_server"]),
               decode_a=up(serve, servers["srv_a"]),
               decode_b=up(serve, servers["srv_b"]),
               mismatch=up(serve, servers["srv_c"]))
    for stop in stops:
        stop.set()
    for t in threads:
        t.join(timeout=5)


class _served_router:
    """Context manager: serve ``router`` on a free port, yield a
    :class:`RouterClient` factory, tear down router + clients."""

    def __init__(self, router: Router):
        self.router = router
        self.clients: list[RouterClient] = []

    def __enter__(self):
        self.addr, self._stop, self._t = _serve_thread(
            router_mod.serve, self.router, _free_port())
        return self

    def client(self) -> RouterClient:
        c = RouterClient(self.addr)
        self.clients.append(c)
        return c

    def __exit__(self, *exc):
        for c in self.clients:
            c.close()
        self._stop.set()
        self._t.join(timeout=5)
        self.router.close()


# ---------------------------------------------------------------------------
# migrate.py — the manifest/pages refusal matrix (in-process)
# ---------------------------------------------------------------------------


class TestRefusalMatrix:
    def _cfg_and_pages(self, stack):
        sess = stack["prefill_server"].session
        prompt = np.arange(1, 6, dtype=np.int32)
        with stack["prefill_server"]._lock:
            seq, logits = sess.admit(prompt)
            k, v = sess.export_pages(seq)
            man = page_manifest(sess.cfg, prompt, seq.length,
                                int(np.argmax(logits)))
            sess.release(seq)
        return sess.cfg, man, k, v

    def test_compatible_passes(self, stack):
        cfg, man, k, v = self._cfg_and_pages(stack)
        assert manifest_incompatibility(man, cfg) is None
        assert pages_incompatibility(man, k, v, cfg) is None

    def test_every_geometry_field_refused(self, stack):
        cfg, man, k, v = self._cfg_and_pages(stack)
        for f in GEOMETRY_FIELDS:
            bad = dict(man)
            bad[f] = "float64" if f == "dtype" else int(man[f]) + 1
            reason = manifest_incompatibility(bad, cfg)
            assert reason is not None and f in reason, (f, reason)

    def test_missing_fields_and_lies_refused(self, stack):
        cfg, man, k, v = self._cfg_and_pages(stack)
        for f in (*GEOMETRY_FIELDS, "length", "prompt", "first_token"):
            bad = {x: y for x, y in man.items() if x != f}
            assert f in (manifest_incompatibility(bad, cfg) or "")
        bad = dict(man, length=0)
        assert "length" in manifest_incompatibility(bad, cfg)
        bad = dict(man, prompt=man["prompt"] + [1])
        assert "prompt" in manifest_incompatibility(bad, cfg)
        # the manifest can lie about the arrays: shape and dtype
        assert "shaped" in pages_incompatibility(man, k[:, :1], v, cfg)
        assert "dtype" in pages_incompatibility(
            man, k, v.astype(np.float64), cfg)

    def test_mismatch_refused_over_wire_connection_survives(
            self, stack, tiny_lm):
        """Ship geometry-correct pages to the page_size-2 server: the
        typed ``IncompatiblePages`` rides the wire and the SAME client
        connection (and the replica) keep serving."""
        model, params, _ = tiny_lm
        cfg, man, k, v = self._cfg_and_pages(stack)
        c = InferenceClient(stack["mismatch"])
        try:
            with pytest.raises(IncompatiblePages,
                               match="page geometry mismatch"):
                c.adopt(man, k, v, 4)
            # same connection, same replica: native streams unaffected
            out = c.generate(np.asarray(man["prompt"], np.int32), 4)
            assert list(out) == _flax_greedy(model, params,
                                             man["prompt"], 4)
            assert sum(r.get("adopt_refused", 0)
                       for r in c.stats()["replicas"]) >= 1
        finally:
            c.close()


# ---------------------------------------------------------------------------
# prefill.py — page export byte identity + shedding
# ---------------------------------------------------------------------------


class TestPrefill:
    def test_pages_byte_identical_over_wire(self, stack):
        """The raw-frame transport pin: the page bytes the CLIENT
        receives are exactly the bytes the server handler returned —
        no bf16 re-dtype, no lossy step anywhere on the wire.  Spies
        on the served object, so prefill numerics (prefix-cache hits
        take the extend program) can't blur the comparison."""
        server = stack["prefill_server"]
        sent = {}
        orig = server.prefill

        def spy(prompt):
            man, raw = orig(prompt)
            sent["k"], sent["v"] = raw  # RawArrays IS a tuple
            return man, raw

        server.prefill = spy
        prompt = np.asarray([3, 1, 4, 1, 5], np.int32)
        c = PrefillClient(stack["prefill"])
        try:
            man, k, v = c.prefill(prompt)
        finally:
            c.close()
            del server.prefill  # un-shadow the method
        assert man["prompt"] == [int(t) for t in prompt]
        assert man["length"] == len(prompt)
        assert k.dtype == sent["k"].dtype
        assert v.dtype == sent["v"].dtype
        assert k.tobytes() == sent["k"].tobytes()
        assert v.tobytes() == sent["v"].tobytes()

    def test_admission_shed_is_typed(self, tiny_lm, stack):
        model, _, export_dir = tiny_lm
        server = PrefillServer(export_dir, model=model, max_pending=0,
                               warmup=False, **GEO)
        with pytest.raises(Overloaded, match="max_pending"):
            server.prefill(np.asarray([1, 2, 3], np.int32))
        assert server.stats()["overloaded"] == 1


# ---------------------------------------------------------------------------
# router.py — byte identity, failover, shedding, drain (real sockets)
# ---------------------------------------------------------------------------


class TestRouter:
    def test_stream_byte_identical_to_single_role(self, stack):
        """The headline pin: router(prefill → migrate → adopt) equals
        the single-role decode server equals the uncached oracle."""
        model, params = stack["model"], stack["params"]
        router = Router(prefill=[stack["prefill"]],
                        decode=[stack["decode_a"]])
        with _served_router(router) as sr:
            rng = np.random.default_rng(17)
            prompts = [rng.integers(0, VOCAB, n).astype(np.int32)
                       for n in (5, 7, 8)]
            single = InferenceClient(stack["decode_b"])
            try:
                for p in prompts:
                    got = sr.client().generate(p, 10)
                    assert list(got) == list(single.generate(p, 10))
                    assert list(got) == _flax_greedy(model, params,
                                                     p, 10)
            finally:
                single.close()
            st = sr.client().stats()
            assert st["streams"] == len(prompts)
            assert st["shed"] == 0 and st["failovers"] == 0

    def test_concurrent_streams_all_correct(self, stack):
        model, params = stack["model"], stack["params"]
        router = Router(prefill=[stack["prefill"]],
                        decode=[stack["decode_a"], stack["decode_b"]])
        with _served_router(router) as sr:
            rng = np.random.default_rng(23)
            prompts = [rng.integers(0, VOCAB, 5 + i % 4)
                          .astype(np.int32) for i in range(6)]
            outs = [None] * len(prompts)

            def run(i, c):
                outs[i] = c.generate(prompts[i], 8)

            ths = [threading.Thread(target=run,
                                    args=(i, sr.client()))
                   for i in range(len(prompts))]
            for t in ths:
                t.start()
            for t in ths:
                t.join(60)
            for p, o in zip(prompts, outs):
                assert o is not None
                assert list(o) == _flax_greedy(model, params, p, 8)

    def test_dead_decode_backend_fails_over_byte_identical(
            self, stack):
        """A decode backend lost on the token leg: the router
        re-prefills from the prompt and adopts onto the survivor —
        stream output byte-identical, failover counted."""
        model, params = stack["model"], stack["params"]
        dead = f"127.0.0.1:{_free_port()}"  # nobody listening
        router = Router(prefill=[stack["prefill"]],
                        decode=[dead, stack["decode_a"]])
        # pin round-robin so the DEAD backend is tried first
        router._rr["decode"] = 0
        prompt = np.asarray([2, 7, 1, 8], np.int32)
        out = router.generate(prompt, 8)
        assert list(out) == _flax_greedy(model, params, prompt, 8)
        st = router.stats()
        assert st["failovers"] == 1
        assert st["shed"] == 0
        router.close()

    def test_failover_budget_exhausts_to_connection_error(self, stack):
        dead = f"127.0.0.1:{_free_port()}"
        router = Router(prefill=[stack["prefill"]], decode=[dead],
                        failover_attempts=1)
        with pytest.raises(ConnectionError):
            router.generate(np.asarray([1, 2, 3], np.int32), 4)
        assert router.stats()["failovers"] == 1
        router.close()

    def test_overload_sheds_typed_end_to_end(self, stack):
        """Admission bounds surface as typed ``Overloaded`` over the
        wire — router admission and an empty decode role both."""
        router = Router(prefill=[stack["prefill"]],
                        decode=[stack["decode_a"]], max_streams=0)
        with _served_router(router) as sr:
            with pytest.raises(Overloaded, match="max_streams"):
                sr.client().generate(np.asarray([1, 2], np.int32), 4)
        router = Router(prefill=[stack["prefill"]], decode=[])
        with _served_router(router) as sr:
            c = sr.client()
            with pytest.raises(Overloaded, match="decode"):
                c.generate(np.asarray([1, 2], np.int32), 4)
            # typed shed: the connection survives
            assert c.stats()["shed"] >= 1

    def test_incompatible_backend_propagates_typed(self, stack):
        """A geometry-mismatched decode fleet is a deployment error:
        the typed refusal reaches the client, the router keeps
        serving."""
        router = Router(prefill=[stack["prefill"]],
                        decode=[stack["mismatch"]])
        with _served_router(router) as sr:
            c = sr.client()
            with pytest.raises(IncompatiblePages,
                               match="page geometry mismatch"):
                c.generate(np.asarray([1, 2, 3], np.int32), 4)
            assert c.stats()["active_streams"] == 0

    def test_scale_up_admits_with_zero_dropped_streams(self, stack):
        """Adding a backend mid-traffic: every stream before, during
        and after the add completes; the new backend takes work."""
        model, params = stack["model"], stack["params"]
        router = Router(prefill=[stack["prefill"]],
                        decode=[stack["decode_a"]])

        def adopted_on_b() -> int:
            c = InferenceClient(stack["decode_b"])
            try:
                return sum(r.get("adopted", 0)
                           for r in c.stats()["replicas"])
            finally:
                c.close()

        adopted_b0 = adopted_on_b()
        with _served_router(router) as sr:
            prompt = np.asarray([4, 4, 2], np.int32)
            want = _flax_greedy(model, params, prompt, 6)
            assert list(sr.client().generate(prompt, 6)) == want
            router.add_backend("decode", stack["decode_b"])
            outs = [None] * 4

            def run(i, c):
                outs[i] = c.generate(prompt, 6)

            ths = [threading.Thread(target=run, args=(i, sr.client()))
                   for i in range(4)]
            for t in ths:
                t.start()
            for t in ths:
                t.join(60)
            assert all(o is not None and list(o) == want for o in outs)
            st = sr.client().stats()
            assert st["shed"] == 0
        # the added backend took streams: zero dropped, real traffic
        assert adopted_on_b() > adopted_b0

    def test_scale_down_drains_before_close(self, stack):
        """The drain protocol: a removed backend takes no NEW streams,
        reports its in-flight count until the last stream releases,
        and only then leaves the router."""
        model, params = stack["model"], stack["params"]
        router = Router(prefill=[stack["prefill"]],
                        decode=[stack["decode_a"], stack["decode_b"]])
        with router._lock:
            b = next(x for x in router._backends["decode"]
                     if x.addr == stack["decode_b"])
        inflight = b.acquire()  # one stream parked on B
        router.remove_backend("decode", stack["decode_b"])
        assert router.backend_streams("decode", stack["decode_b"]) == 1
        # no new streams route to the draining backend
        assert all(x.addr != stack["decode_b"]
                   for x in router._candidates("decode"))
        prompt = np.asarray([6, 1, 6], np.int32)
        assert list(router.generate(prompt, 5)) == \
            _flax_greedy(model, params, prompt, 5)
        # last stream out closes the backend
        assert b.release(inflight, ok=True) is True
        router._drop_if_drained(b)
        assert router.backend_streams("decode", stack["decode_b"]) == 0
        assert all(s["addr"] != stack["decode_b"]
                   for s in router.stats()["backends"]["decode"])
        router.close()


# ---------------------------------------------------------------------------
# autoscale.py — controller units + the scaler loop (no subprocesses)
# ---------------------------------------------------------------------------


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


class TestHysteresis:
    def test_validation(self):
        with pytest.raises(ValueError, match="down < up"):
            HysteresisController(up=0.2, down=0.8)
        with pytest.raises(ValueError, match="min_size"):
            HysteresisController(min_size=3, max_size=2)

    def test_hold_then_up_then_cooldown(self):
        clk = _Clock()
        c = HysteresisController(up=0.8, down=0.2, hold=2,
                                 cooldown_s=10.0, max_size=4,
                                 clock=clk)
        assert c.decide(0.9, 1) == 0   # first breach holds
        assert c.decide(0.9, 1) == 1   # second scales
        assert c.decide(0.9, 2) == 0   # cooldown gates
        assert c.decide(0.9, 2) == 0
        clk.t = 11.0
        assert c.decide(0.9, 2) == 1   # breaches counted through it

    def test_dead_band_resets_breaches(self):
        c = HysteresisController(up=0.8, down=0.2, hold=2,
                                 cooldown_s=0.0, clock=_Clock())
        assert c.decide(0.9, 1) == 0
        assert c.decide(0.5, 1) == 0   # dead band: counter resets
        assert c.decide(0.9, 1) == 0   # back to one breach
        assert c.decide(0.9, 1) == 1

    def test_down_and_size_clamps(self):
        clk = _Clock()
        c = HysteresisController(up=0.8, down=0.2, hold=2,
                                 cooldown_s=0.0, min_size=1,
                                 max_size=2, clock=clk)
        assert c.decide(0.1, 2) == 0
        assert c.decide(0.1, 2) == -1
        assert c.decide(0.1, 1) == 0   # hold restarts after event
        assert c.decide(0.1, 1) == 0   # min_size clamps
        assert c.decide(0.9, 2) == 0
        assert c.decide(0.9, 2) == 0   # max_size clamps


class _FakeGroup:
    def __init__(self, addrs):
        self._addrs = list(addrs)
        self.grown = 0
        self.released: list[str] = []

    def addresses(self):
        return list(self._addrs)

    def __len__(self):
        return len(self._addrs)

    def grow(self):
        self.grown += 1
        addr = f"127.0.0.1:{9000 + self.grown}"
        self._addrs.append(addr)
        return addr

    def release(self, addr):
        self._addrs.remove(addr)
        self.released.append(addr)


class _FakeRouter:
    def __init__(self):
        self.log: list[tuple] = []
        self.streams: dict[str, int] = {}

    def add_backend(self, role, addr):
        self.log.append(("add", role, addr))

    def remove_backend(self, role, addr):
        self.log.append(("remove", role, addr))

    def backend_streams(self, role, addr):
        return self.streams.get(addr, 0)


class TestAutoscaler:
    def _scaler(self, stats_map, **ctl):
        group = _FakeGroup(list(stats_map))
        router = _FakeRouter()
        ctl.setdefault("hold", 1)
        ctl.setdefault("cooldown_s", 0.0)
        ctl.setdefault("clock", _Clock())
        scaler = Autoscaler(router, {"decode": group},
                            {"decode": HysteresisController(**ctl)},
                            drain_timeout_s=0.2)
        scaler._stats = lambda addr: stats_map.get(addr)
        return scaler, group, router

    def test_replica_load_fold(self):
        scaler, _, _ = self._scaler({})
        scaler.slo_p99_ms = 10.0
        # prefill: queue depth
        assert scaler._replica_load("a", {
            "role": "prefill", "inflight": 4, "max_pending": 8,
            "overloaded": 0}) == pytest.approx(0.5)
        # decode: max over pending depth / occupancy / p99-vs-SLO
        load = scaler._replica_load("b", {
            "overloaded": 0,
            "replicas": [{"pending": 2, "active": 3, "free_pages": 8,
                          "intertoken_ms": {"p99": 25.0}}]})
        assert load == pytest.approx(2.5)  # p99 dominates: 25/10
        # an overload DELTA saturates the signal to 1.0 — but the
        # first observation only primes the baseline
        assert scaler._replica_load("c", {
            "role": "prefill", "inflight": 0, "max_pending": 8,
            "overloaded": 5}) == 0.0
        assert scaler._replica_load("c", {
            "role": "prefill", "inflight": 0, "max_pending": 8,
            "overloaded": 6}) == 1.0

    def test_tick_scales_up_on_load(self):
        stats_map = {"127.0.0.1:8001": {
            "role": "prefill", "inflight": 8, "max_pending": 8,
            "overloaded": 0}}
        scaler, group, router = self._scaler(stats_map)
        scaler.tick()
        assert group.grown == 1
        assert router.log == [("add", "decode", "127.0.0.1:9001")]
        assert scaler.events == [("decode", "up", "127.0.0.1:9001")]

    def test_tick_drains_then_releases_on_idle(self):
        stats_map = {
            "127.0.0.1:8001": {"role": "prefill", "inflight": 0,
                               "max_pending": 8, "overloaded": 0},
            "127.0.0.1:8002": {"role": "prefill", "inflight": 0,
                               "max_pending": 8, "overloaded": 0},
        }
        scaler, group, router = self._scaler(stats_map)
        scaler.tick()
        # newest replica drained: router removal BEFORE process release
        assert router.log == [("remove", "decode", "127.0.0.1:8002")]
        assert group.released == ["127.0.0.1:8002"]
        assert scaler.events == [("decode", "down", "127.0.0.1:8002")]
        # at min_size the controller stops shrinking
        scaler.tick()
        assert group.released == ["127.0.0.1:8002"]

    def test_dead_replica_does_not_kill_the_loop(self):
        stats_map = {"127.0.0.1:8001": None}  # stats unreachable
        scaler, group, router = self._scaler(stats_map)
        scaler.tick()  # load 0.0 from nothing; size 1 = min: no event
        assert router.log == [] and group.released == []


# ---------------------------------------------------------------------------
# the real-subprocess fleet (slow set)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_disaggregated_fleet_subprocess_roundtrip(tiny_lm):
    """DisaggregatedFleet end to end: real prefill + decode children,
    the in-process router, one client stream oracle-equal."""
    from theanompi_tpu.frontdoor.fleet import DisaggregatedFleet

    model, params, export_dir = tiny_lm
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    with DisaggregatedFleet(export_dir, prefill=1, decode=1,
                            page_size=4, pages_per_seq=8, max_seqs=4,
                            prefill_buckets=(8,)) as fleet:
        c = RouterClient(fleet.router_addr)
        try:
            prompt = np.asarray([3, 1, 4, 1, 5], np.int32)
            out = c.generate(prompt, 8)
            assert list(out) == _flax_greedy(model, params, prompt, 8)
            st = c.stats()
            assert st["streams"] == 1 and st["shed"] == 0
        finally:
            c.close()


# ---------------------------------------------------------------------------
# fleet prefix cache (ISSUE 18): authority ops + lease refusal matrix
# ---------------------------------------------------------------------------


class TestFleetCache:
    def test_register_then_lookup_ships_identical_bytes(self, stack,
                                                        tiny_lm):
        """A peer registers a page-aligned prefix over the wire; the
        next lookup ships back byte-identical pages under a lease."""
        from theanompi_tpu.decode import DecodeSession, fleetcache

        model, params, _ = tiny_lm
        sess = DecodeSession(model, params=params, **GEO)
        c = fleetcache.FleetCacheClient(stack["prefill"])
        try:
            rng = np.random.default_rng(41)
            prompt = rng.integers(0, VOCAB, 8).astype(np.int32)
            assert c.lookup(prompt) is None          # cold fleet
            seq, _ = sess.admit(prompt)
            k, v = sess.export_page_ids([int(seq.page_row[0])])
            man = fleetcache.prefix_manifest(sess.cfg, prompt[:4])
            assert c.register_prefix(man, k, v)["added"] is True
            got = c.lookup(prompt)
            assert got is not None
            m2, k2, v2, lease = got
            assert m2["n_tokens"] == 4
            assert m2["prefix"] == [int(t) for t in prompt[:4]]
            np.testing.assert_array_equal(k2, k)
            np.testing.assert_array_equal(v2, v)
            c.decref(lease)
            sess.release(seq)
        finally:
            c.close()

    def test_lease_refusal_matrix_over_wire(self, stack):
        """Foreign lease and double decref raise the typed LeaseError;
        a geometry-lying register raises IncompatiblePages; the same
        client connection (and the authority) keep serving."""
        from theanompi_tpu.decode import fleetcache

        pre = stack["prefill_server"]
        c = fleetcache.FleetCacheClient(stack["prefill"])
        try:
            with pytest.raises(fleetcache.LeaseError, match="lease"):
                c.decref("lease-0-999999")           # foreign
            rng = np.random.default_rng(42)
            prompt = rng.integers(0, VOCAB, 8).astype(np.int32)
            pre.prefill(prompt)       # cold prefill seeds the cache
            man, k, v, lease = c.lookup(prompt)
            c.decref(lease)
            with pytest.raises(fleetcache.LeaseError, match="lease"):
                c.decref(lease)                      # double decref
            bad = dict(man, page_size=8)
            with pytest.raises(IncompatiblePages, match="page_size"):
                c.register_prefix(bad, np.asarray(k), np.asarray(v))
            # same connection: the authority still answers
            got = c.lookup(prompt)
            assert got is not None
            c.decref(got[3])
        finally:
            c.close()

    def test_evict_while_leased_pages_survive(self, stack):
        """Remote eviction can never free a shipped page mid-flight:
        the lease's reference keeps it allocated until decref."""
        pre = stack["prefill_server"]
        sess = pre.session
        rng = np.random.default_rng(43)
        prompt = rng.integers(0, VOCAB, 8).astype(np.int32)
        pre.prefill(prompt)
        got = pre.cache_lookup(prompt)
        assert got is not None
        _, _, lease = got
        page_ids = list(pre._leases[lease])
        with pre._lock:
            sess.prefix_cache.evict_all()    # cache refs dropped
        assert all(sess.pool.refcount(p) >= 1 for p in page_ids)
        pre.cache_decref(lease)
        assert all(sess.pool.refcount(p) == 0 for p in page_ids)

    def test_cross_replica_fleet_hit_end_to_end(self, stack, tiny_lm):
        """A session that attaches the authority as its fleet cache
        turns a local miss into an adopted local hit (and registers
        its own cold prefixes back): both directions, with the decoded
        stream token-identical to the oracle and no leaked lease."""
        from theanompi_tpu.decode import DecodeSession, fleetcache

        model, params, _ = tiny_lm
        pre = stack["prefill_server"]
        rng = np.random.default_rng(44)
        prompt = rng.integers(0, VOCAB, 8).astype(np.int32)
        pre.prefill(prompt)          # authority caches prompt[:4]
        sess = DecodeSession(model, params=params, **GEO)
        sess.fleet = fleetcache.FleetCacheClient(stack["prefill"])
        try:
            leases0 = len(pre._leases)
            seq, lg = sess.admit(prompt)   # miss -> fetch -> local hit
            assert sess.prefix_cache.hits == 1
            assert len(pre._leases) == leases0     # fetch decrefs
            out = [int(np.argmax(lg))]
            for _ in range(5):
                l2 = sess.decode([seq],
                                 np.asarray([out[-1]], np.int32))
                out.append(int(np.argmax(l2[0])))
            assert out == _flax_greedy(model, params, prompt, 6)
            # reverse direction: a cold admit registers its prefix
            p2 = rng.integers(0, VOCAB, 8).astype(np.int32)
            sess.admit(p2)
            got = pre.cache_lookup(p2)
            assert got is not None and got[0]["n_tokens"] == 4
            pre.cache_decref(got[2])
        finally:
            sess.fleet.close()


class TestPrefillCoalescing:
    def test_concurrent_prefills_coalesce_into_one_batch(self,
                                                         tiny_lm):
        """4 concurrent prefill() calls ride ONE batched program (the
        leader waits out the oldest prompt's deadline) and each caller
        gets the manifest of the serial cap-1 path and its pages to
        float32 rounding.  Not byte for byte: the 4-row and the 1-row
        prefill are two compiled programs, and XLA's CPU backend rounds
        their matmuls apart (1 % of the elements, at most 2.4e-7
        absolute, 6.8e-7 relative; JAX 0.9.0).  Bytes are pinned where
        one program serves both sides: the tokens decoded from batched
        pages (test_decode.py TestBatchedPrefill) and the pages over
        the wire (TestPrefill, TestFleetCache)."""
        model, params, export_dir = tiny_lm
        pre = PrefillServer(export_dir, model=model, max_pending=8,
                            warmup=False, prefill_delay_ms=250.0,
                            **GEO)
        rng = np.random.default_rng(45)
        prompts = [rng.integers(0, VOCAB, 6 + i % 3).astype(np.int32)
                   for i in range(4)]
        results = [None] * 4

        def run(i):
            results[i] = pre.prefill(prompts[i])

        ts = [threading.Thread(target=run, args=(i,))
              for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(120)
        assert pre.n_batches == 1 and pre.n_prefills == 4
        serial = PrefillServer(export_dir, model=model, max_pending=8,
                               warmup=False, prefill_batch=1, **GEO)
        for p, (man, pages) in zip(prompts, results):
            rman, rpages = serial.prefill(p)
            assert man == rman
            np.testing.assert_allclose(pages[0], rpages[0],
                                       rtol=5e-6, atol=1e-6)
            np.testing.assert_allclose(pages[1], rpages[1],
                                       rtol=5e-6, atol=1e-6)
