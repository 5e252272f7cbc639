"""Hierarchical intra-host aggregation (parallel/aggregate.py,
ISSUE 14).

The acceptance bar is EXACTNESS plus the fault matrix: the aggregated
center math must equal N independent exchanges at the same center
version — BITWISE on the exact-arithmetic f32 lattice (ASGD's
delta-sum; EASGD's closed-form elastic composition) — and a killed
aggregator must fail its workers over to direct exchange within the
same period (no idle-all-workers gap), with a relaunch rejoining the
periods that follow.
"""

from __future__ import annotations

import socket
import threading
import time

import jax
import numpy as np
import pytest

from theanompi_tpu.parallel.aggregate import (
    AggregatedExchange,
    AggregatorDown,
    LocalAggregator,
)
from theanompi_tpu.parallel.server import ASGDServer, EASGDServer
from theanompi_tpu.utils.helper_funcs import build_optimizer

ALPHA = 0.25  # N*ALPHA <= 1 at N=4 (docs/DESIGN.md stability note)


def lattice(shape, rng, lo=-2**12, hi=2**12):
    """Exact-arithmetic f32 values: integer multiples of 2**-10 with
    |x| <= 4 — every sum/mean/elastic-pull below stays exactly
    representable, so equality asserts are bitwise, not tolerances.
    ``+ 0.0`` flushes signed zeros (cancellation yields +0.0 while a
    propagated -0.0 keeps its sign — numerically equal, bitwise
    noise)."""
    return (rng.integers(lo, hi, shape) * 2.0**-10 + 0.0) \
        .astype(np.float32)


def tree(seed: int) -> dict:
    rng = np.random.default_rng(seed)
    return {"a": lattice((8, 4), rng),
            "b": {"c": lattice((33,), rng)},
            "d": lattice((2, 2, 2), rng)}


def grad_tree(seed: int) -> dict:
    rng = np.random.default_rng(seed)
    return {"a": lattice((8, 4), rng, -8, 9),
            "b": {"c": lattice((33,), rng, -8, 9)},
            "d": lattice((2, 2, 2), rng, -8, 9)}


def assert_tree_bytes_equal(t1, t2, msg=""):
    f1, d1 = jax.tree.flatten(t1)
    f2, d2 = jax.tree.flatten(t2)
    assert d1 == d2, f"treedef mismatch {msg}"
    for x, y in zip(f1, f2):
        x, y = np.asarray(x), np.asarray(y)
        assert x.tobytes() == y.tobytes(), msg


def closed_form_easgd(center, workers, alpha):
    """N independent exchanges at ONE center version: the reference
    the aggregate is pinned against."""
    a = np.float32(alpha)
    new_c = jax.tree.map(
        lambda c, *ws: c + a * sum(w - c for w in ws), center, *workers)
    new_ws = [jax.tree.map(lambda w, c: w - a * (w - c), w, center)
              for w in workers]
    return new_c, new_ws


# ---------------------------------------------------------------------------
# Store-level aggregate math
# ---------------------------------------------------------------------------


class TestAggregateStoreMath:
    def test_easgd_exchange_n_is_closed_form(self):
        c0 = tree(0)
        ws = [tree(10 + i) for i in range(4)]
        srv = EASGDServer(c0, alpha=ALPHA)
        mean = jax.tree.map(
            lambda *xs: sum(xs[1:], xs[0]) / np.float32(4), *ws)
        pre = srv.exchange_n(mean, 4)
        ref_c, _ = closed_form_easgd(c0, ws, ALPHA)
        assert_tree_bytes_equal(pre, c0, "pre-update center")
        assert_tree_bytes_equal(jax.device_get(srv.get_center()), ref_c,
                                "aggregated center vs closed form")
        assert srv.n_exchanges == 4  # n logical exchanges

    def test_easgd_n1_matches_direct_exchange(self):
        c0, w = tree(1), tree(2)
        direct = EASGDServer(c0, alpha=ALPHA)
        agg = EASGDServer(c0, alpha=ALPHA)
        new_w = direct.exchange(w)
        pre = agg.exchange_n(w, 1)
        # the aggregator-side worker pull against the pre-update center
        ported = jax.tree.map(
            lambda x, c: x - np.float32(ALPHA) * (x - c), w, pre)
        assert_tree_bytes_equal(jax.device_get(new_w), ported,
                                "n=1 worker pull")
        assert_tree_bytes_equal(jax.device_get(direct.get_center()),
                                jax.device_get(agg.get_center()),
                                "n=1 center")

    def test_asgd_push_pull_n_delta_sums_exactly(self):
        c0 = tree(3)
        gs = [grad_tree(20 + i) for i in range(4)]
        tx = build_optimizer(learning_rate=0.125, optimizer="sgd")
        direct = ASGDServer({k: v for k, v in c0.items()}, tx)
        agg = ASGDServer({k: v for k, v in c0.items()}, tx)
        for _ in range(3):
            for g in gs:
                direct.push_pull(g)
            gsum = jax.tree.map(lambda *xs: sum(xs[1:], xs[0]), *gs)
            agg.push_pull_n(gsum, 4)
        assert_tree_bytes_equal(jax.device_get(direct.get_center()),
                                jax.device_get(agg.get_center()),
                                "delta-sum vs sequential pushes")
        assert direct.n_updates == agg.n_updates == 12

    def test_n_below_one_refused(self):
        srv = EASGDServer(tree(0), alpha=ALPHA)
        with pytest.raises(ValueError, match="n >= 1"):
            srv.exchange_n(tree(1), 0)
        asrv = ASGDServer(tree(0),
                          build_optimizer(learning_rate=0.1))
        with pytest.raises(ValueError, match="n >= 1"):
            asrv.push_pull_n(grad_tree(1), 0)


# ---------------------------------------------------------------------------
# LocalAggregator periods
# ---------------------------------------------------------------------------


def _run_period(ports, payloads):
    """All workers exchange concurrently; returns their results."""
    outs = [None] * len(ports)
    errs = [None] * len(ports)

    def run(i):
        try:
            outs[i] = ports[i].exchange(payloads[i])
        except BaseException as e:  # pragma: no cover - surfaced below
            errs[i] = e

    ths = [threading.Thread(target=run, args=(i,))
           for i in range(len(ports))]
    for t in ths:
        t.start()
    for t in ths:
        t.join(120)
    assert not any(t.is_alive() for t in ths), "an exchange never returned"
    assert all(e is None for e in errs), errs
    return outs


class TestLocalAggregator:
    def test_periods_match_closed_form(self):
        c0 = tree(0)
        srv = EASGDServer(c0, alpha=ALPHA)
        agg = LocalAggregator("easgd", srv, alpha=ALPHA)
        ports = [AggregatedExchange(agg, i, lambda: srv)
                 for i in range(4)]
        workers = [tree(10 + i) for i in range(4)]
        ref_c, ref_ws = c0, workers
        for _ in range(3):
            outs = _run_period(ports, workers)
            ref_c, ref_ws = closed_form_easgd(ref_c, ref_ws, ALPHA)
            for out, ref in zip(outs, ref_ws):
                assert_tree_bytes_equal(out, ref, "worker pull")
            workers = outs
        assert_tree_bytes_equal(jax.device_get(srv.get_center()), ref_c,
                                "3-period center vs closed form")
        assert srv.n_exchanges == 12
        for p in ports:
            p.close()

    def test_asgd_fan_out_shares_fresh_center(self):
        tx = build_optimizer(learning_rate=0.125, optimizer="sgd")
        srv = ASGDServer(tree(0), tx)
        agg = LocalAggregator("asgd", srv)
        ports = [AggregatedExchange(agg, i, lambda: srv)
                 for i in range(3)]
        gs = [grad_tree(30 + i) for i in range(3)]
        outs = [None] * 3
        ths = [threading.Thread(
            target=lambda i=i: outs.__setitem__(
                i, ports[i].push_pull(gs[i]))) for i in range(3)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(120)
        assert not any(t.is_alive() for t in ths), \
            "a push_pull never returned"
        center = jax.device_get(srv.get_center())
        for out in outs:
            assert_tree_bytes_equal(out, center, "fanned-out center")
        assert srv.n_updates == 3
        for p in ports:
            p.close()

    def test_leave_shrinks_period_quorum(self):
        srv = EASGDServer(tree(0), alpha=ALPHA)
        agg = LocalAggregator("easgd", srv, alpha=ALPHA)
        ports = [AggregatedExchange(agg, i, lambda: srv)
                 for i in range(4)]
        ports[3].close()  # worker 3 is gone before the period
        outs = _run_period(ports[:3], [tree(10 + i) for i in range(3)])
        assert all(o is not None for o in outs)
        assert srv.n_exchanges == 3
        for p in ports[:3]:
            p.close()

    def test_timeout_withdraws_and_falls_back(self):
        srv = EASGDServer(tree(0), alpha=ALPHA)
        agg = LocalAggregator("easgd", srv, alpha=ALPHA,
                              wait_timeout_s=0.3)
        agg.register(0)
        agg.register(1)  # never submits -> period can't complete
        port = AggregatedExchange(agg, 0, lambda: srv)
        out = port.exchange(tree(5))  # falls back direct after timeout
        assert out is not None
        assert srv.n_exchanges == 1  # the DIRECT exchange, not a flight
        port.close()

    def test_gosgd_kind_refused(self):
        with pytest.raises(ValueError, match="easgd/asgd only"):
            LocalAggregator("gosgd", object())

    def test_easgd_requires_alpha(self):
        with pytest.raises(ValueError, match="alpha"):
            LocalAggregator("easgd", object())


# ---------------------------------------------------------------------------
# Fault matrix: kill -> direct fallback within one period -> rejoin
# ---------------------------------------------------------------------------


class TestAggregatorFaultMatrix:
    def test_kill_mid_wait_falls_back_within_period_then_rejoins(self):
        """Workers parked on the period barrier when the aggregator
        dies must complete THAT period via direct exchange (no
        idle-all-workers gap), and a restarted aggregator serves the
        periods that follow."""
        srv = EASGDServer(tree(0), alpha=ALPHA)
        agg = LocalAggregator("easgd", srv, alpha=ALPHA)
        ports = [AggregatedExchange(agg, i, lambda: srv)
                 for i in range(4)]
        workers = [tree(10 + i) for i in range(4)]

        # period 1: aggregated (sanity)
        workers = _run_period(ports, workers)
        assert srv.n_exchanges == 4

        # period 2: three workers park on the barrier, then the kill
        # lands before the fourth ever submits
        outs = [None] * 4
        started = threading.Barrier(4)

        def run(i):
            started.wait(60)
            outs[i] = ports[i].exchange(workers[i])

        ths = [threading.Thread(target=run, args=(i,)) for i in range(3)]
        for t in ths:
            t.start()
        started.wait(60)  # all three are inside exchange (or about to be)
        agg.kill("fault-matrix kill")
        for t in ths:
            t.join(timeout=30)
        assert all(t.is_alive() is False for t in ths)
        # worker 3 exchanges AFTER the kill: immediate direct fallback
        outs[3] = ports[3].exchange(workers[3])
        assert all(o is not None for o in outs)
        # every worker's period completed via the direct path
        assert srv.n_exchanges == 8

        # relaunch rejoins: the next period aggregates again
        agg.restart()
        workers = [jax.tree.map(np.asarray, o) for o in outs]
        outs = _run_period(ports, workers)
        assert all(o is not None for o in outs)
        # ONE aggregate flight = 4 logical exchanges (not 4 directs —
        # proves the ports rejoined the plane rather than staying on
        # their fallback clients)
        assert srv.n_exchanges == 12
        assert agg.alive()
        for p in ports:
            p.close()

    def test_wire_failure_fails_over_that_period(self):
        """An aggregate wire op that raises must surface as
        AggregatorDown to EVERY submitted worker of that period (the
        port then goes direct); the plane itself stays usable."""

        class FlakyStore:
            def __init__(self, inner):
                self.inner = inner
                self.fail_next = False

            def exchange_n(self, mean, n):
                if self.fail_next:
                    self.fail_next = False
                    raise ConnectionError("injected wire failure")
                return self.inner.exchange_n(mean, n)

            def exchange(self, w):
                return self.inner.exchange(w)

        srv = EASGDServer(tree(0), alpha=ALPHA)
        flaky = FlakyStore(srv)
        agg = LocalAggregator("easgd", flaky, alpha=ALPHA)
        agg.register(0)
        agg.register(1)
        flaky.fail_next = True
        errs = []

        def direct_exchange(rank, payload):
            try:
                return agg.exchange(rank, payload)
            except AggregatorDown as e:
                errs.append(e)
                return srv.exchange(payload)

        outs = [None, None]
        ths = [threading.Thread(
            target=lambda i=i: outs.__setitem__(
                i, direct_exchange(i, tree(10 + i)))) for i in range(2)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(120)
        assert not any(t.is_alive() for t in ths), \
            "a failed-over exchange never returned"
        assert len(errs) == 2  # both workers of the period failed over
        assert all(o is not None for o in outs)
        # next period succeeds (the failure was one period's, not a
        # permanent down-state)
        outs = [None, None]
        ths = [threading.Thread(
            target=lambda i=i: outs.__setitem__(
                i, agg.exchange(i, tree(20 + i)))) for i in range(2)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(120)
        assert all(o is not None for o in outs)

    def test_kill_restart_racing_inflight_aggregate_never_wedges(self):
        """kill() + immediate restart() landing while the aggregate
        wire op is IN FLIGHT: the kill watermark stops the stale
        flight publishing, so a waiter that slept through the brief
        down window must still get a typed AggregatorDown (its
        generation's result will never arrive) — not re-extend its
        deadline forever.  The documented at-least-once window: the
        in-flight aggregate may still apply, exactly like a re-sent
        exchange after a lost reply."""

        class SlowStore:
            def __init__(self, inner):
                self.inner = inner
                self.flying = threading.Event()
                self.release = threading.Event()

            def exchange_n(self, mean, n):
                self.flying.set()
                assert self.release.wait(10)
                return self.inner.exchange_n(mean, n)

        srv = EASGDServer(tree(0), alpha=ALPHA)
        slow = SlowStore(srv)
        agg = LocalAggregator("easgd", slow, alpha=ALPHA,
                              wait_timeout_s=2.0)
        agg.register(0)
        agg.register(1)
        res = {}

        def worker(i):
            try:
                res[i] = ("ok", agg.exchange(i, tree(10 + i)))
            except AggregatorDown as e:
                res[i] = ("down", e)

        ths = [threading.Thread(target=worker, args=(i,))
               for i in range(2)]
        for t in ths:
            t.start()
        assert slow.flying.wait(10)  # the flyer is inside the wire op
        agg.kill("restart drill")
        agg.restart()  # faster than the waiter's 50 ms cv poll
        slow.release.set()  # the stale flight lands post-restart
        for t in ths:
            t.join(timeout=8)
        assert not any(t.is_alive() for t in ths), \
            "a worker wedged waiting on the killed flight's result"
        # the flyer keeps its own (applied) result; the waiter got the
        # typed failover signal
        kinds = sorted(k for k, _ in res.values())
        assert kinds == ["down", "ok"], kinds
        # the plane aggregates again after the drill
        outs = [None, None]
        ths = [threading.Thread(
            target=lambda i=i: outs.__setitem__(
                i, agg.exchange(i, tree(20 + i)))) for i in range(2)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=10)
        assert all(o is not None for o in outs)

    def test_kill_restart_racing_parked_waiter_never_wedges(self):
        """kill() + immediate restart() landing BEFORE any flyer takes
        off (quorum not yet met): the kill discards the parked
        waiter's pending payload, so the waiter must get a typed
        AggregatorDown on its next wakeup (payload never applied —
        safe direct fallback) even though it never observed the down
        window — not wait out the full quorum timeout."""
        srv = EASGDServer(tree(0), alpha=ALPHA)
        agg = LocalAggregator("easgd", srv, alpha=ALPHA,
                              wait_timeout_s=60.0)
        agg.register(0)
        agg.register(1)  # never submits: quorum stays unmet
        res = {}

        def worker():
            try:
                res[0] = ("ok", agg.exchange(0, tree(10)))
            except AggregatorDown as e:
                res[0] = ("down", e)

        t = threading.Thread(target=worker)
        t.start()
        for _ in range(200):  # wait until the payload is parked
            if 0 in agg._pending:
                break
            time.sleep(0.01)
        agg.kill("restart drill")
        agg.restart()
        t.join(timeout=5)  # well below the 60 s quorum timeout
        assert not t.is_alive(), \
            "parked waiter wedged after kill+restart discarded its " \
            "payload"
        assert res[0][0] == "down"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture()
def shard_env(monkeypatch):
    monkeypatch.setenv("THEANOMPI_TPU_SERVICE_KEY", "aggregate-test")


def _start_fleet(k: int):
    from theanompi_tpu.parallel.service import ServiceClient
    from theanompi_tpu.parallel.shards import serve_shard

    fleet = []
    for i in range(k):
        port = _free_port()
        ready, stop = threading.Event(), threading.Event()
        t = threading.Thread(target=serve_shard,
                             args=("127.0.0.1", port, i, ready, stop),
                             daemon=True)
        t.start()
        assert ready.wait(10)
        fleet.append({"addr": f"127.0.0.1:{port}", "stop": stop,
                      "thread": t})

    def teardown():
        for s in fleet:
            s["stop"].set()
            try:
                ServiceClient(s["addr"]).call("shutdown")
            except Exception:
                pass
            s["thread"].join(timeout=5)

    return [s["addr"] for s in fleet], teardown


class TestShardedAggregate:
    def test_sharded_exchange_n_byte_identical_to_inprocess(
            self, shard_env):
        from theanompi_tpu.parallel.shards import ShardedEASGD

        addrs, teardown = _start_fleet(2)
        try:
            c0 = tree(0)
            ws = [tree(10 + i) for i in range(4)]
            mean = jax.tree.map(
                lambda *xs: sum(xs[1:], xs[0]) / np.float32(4), *ws)
            ref = EASGDServer(c0, alpha=ALPHA)
            ref_pre = ref.exchange_n(mean, 4)
            srv = ShardedEASGD(addrs, c0, alpha=ALPHA,
                               session_id="agg-bytes")
            pre = srv.exchange_n(mean, 4)
            assert_tree_bytes_equal(pre, jax.device_get(ref_pre),
                                    "sharded pre-update center")
            assert_tree_bytes_equal(srv.get_center(),
                                    jax.device_get(ref.get_center()),
                                    "sharded aggregated center")
            srv.close()
        finally:
            teardown()

    def test_fence_counts_aggregate_as_n_exchanges(self, shard_env):
        """The version fence's applied counter must advance by n for
        one aggregate op — byte-identical accounting to n independent
        exchanges at the same version."""
        from theanompi_tpu.parallel.service import ServiceClient
        from theanompi_tpu.parallel.shards import ShardedEASGD

        addrs, teardown = _start_fleet(1)
        try:
            c0 = tree(0)
            srv = ShardedEASGD(addrs, c0, alpha=ALPHA,
                               session_id="agg-fence")
            srv.exchange_n(tree(1), 4)
            c = ServiceClient(addrs[0])
            info = c.call("shard_freeze", "easgd", "agg-fence", "tkn")
            c.call("shard_release", "easgd", "agg-fence", "tkn")
            assert info["applied"] == 4, info
            # ONE seq in the vector clock: one full-tree op
            assert list(info["vclock"].values()) == [1], info
            c.close()
            srv.close()
        finally:
            teardown()


# ---------------------------------------------------------------------------
# Rules integration
# ---------------------------------------------------------------------------


def tiny_cfg(tmp_path, **kw):
    from theanompi_tpu.models.base import ModelConfig

    base = dict(batch_size=8, n_epochs=1, learning_rate=0.01,
                snapshot_dir=str(tmp_path), print_freq=0)
    base.update(kw)
    return ModelConfig(**base)


def test_easgd_session_with_local_aggregation(tmp_path):
    """The rules-level wiring: a short aggregated EASGD session runs,
    its ONE aggregate flight per period still counts every worker's
    logical exchange, and validation is finite."""
    from theanompi_tpu import EASGD

    rule = EASGD()
    rule.init(devices=2, modelfile="tests._tiny_models",
              modelclass="TinyCifar", config=tiny_cfg(tmp_path),
              tau=4, alpha=0.25, checkpoint=False,
              local_aggregation=True)
    res = rule.wait()
    assert res["n_exchanges"] > 0
    assert np.isfinite(res["val"]["loss"])


@pytest.mark.slow
def test_asgd_session_with_local_aggregation(tmp_path):
    from theanompi_tpu import ASGD

    rule = ASGD()
    rule.init(devices=2, modelfile="tests._tiny_models",
              modelclass="TinyCifar", config=tiny_cfg(tmp_path),
              checkpoint=False, local_aggregation=True)
    res = rule.wait()
    assert res["n_updates"] > 0
    assert np.isfinite(res["val"]["loss"])


def test_easgd_aggregation_refuses_unstable_alpha(tmp_path):
    """n*alpha > 1 makes the composed center move overshoot the worker
    mean every period — the rule refuses at wiring time (the repo's
    refusal-over-silent-divergence policy) instead of training a run
    that oscillates: default alpha=0.5 with 4 local workers is the
    trap this guards."""
    from theanompi_tpu import EASGD

    rule = EASGD()
    rule.init(devices=4, modelfile="tests._tiny_models",
              modelclass="TinyCifar", config=tiny_cfg(tmp_path),
              tau=4, alpha=0.5, checkpoint=False,
              local_aggregation=True)
    with pytest.raises(ValueError, match=r"n\*alpha"):
        rule.wait()


def test_gosgd_refuses_local_aggregation(tmp_path):
    from theanompi_tpu import GOSGD

    rule = GOSGD()
    rule.init(devices=2, modelfile="tests._tiny_models",
              modelclass="TinyCifar", config=tiny_cfg(tmp_path),
              checkpoint=False, local_aggregation=True)
    with pytest.raises(ValueError, match="refuses hierarchical"):
        rule.wait()
