"""decode/: paged KV-cache autoregressive serving (ISSUE 10).

The acceptance pins:

* greedy decode through the paged cache is TOKEN-IDENTICAL to the
  uncached full-forward argmax oracle — per prefill bucket, across a
  ring-eviction boundary (oracle = the same model under a
  sliding-window mask), and after a mid-stream admit;
* steady-state decode triggers ZERO recompiles (trace counters);
* a sequence admitted mid-stream shares a decode step with an
  in-flight one (iteration-level batching, `shared_steps`);
* bf16/int8 quantized exports hold their error bounds, and the
  hot-reload watcher REFUSES an incompatible export with the typed
  `IncompatibleExport` instead of swapping or crashing;
* the GENERATE wire op serves concurrent streams over a real socket.

The speculative-decode and prefix-cache pins of ISSUE 12 live in
test_decode_spec.py (one file was a sixth of tier-1's time on one
worker under `--dist loadfile`); batched prefill (ISSUE 18) and drain
migration follow below.
"""

from __future__ import annotations

import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.decode import (
    CacheConfig,
    ContinuousBatcher,
    DecodePolicy,
    DecodeSession,
    PagePool,
    full_forward,
)
from theanompi_tpu.decode import kvcache
from theanompi_tpu.models.base import ModelConfig
from theanompi_tpu.serving import (
    IncompatibleExport,
    InferenceClient,
    InferenceServer,
    Overloaded,
    dequantize_tree,
    export_model,
    load_export,
    quantize_tree,
    serve,
)
from theanompi_tpu.serving.server import ServiceError

from tests._decode_helpers import (
    D_MODEL,
    N_HEADS,
    N_LAYERS,
    VOCAB,
    build_tiny_lm,
)
from tests._decode_helpers import flax_greedy as _flax_greedy
from tests._decode_helpers import free_port as _free_port
from tests._decode_helpers import hot as _hot
from tests._decode_helpers import session_greedy as _session_greedy
from tests._decode_helpers import windowed_greedy as _windowed_greedy


@pytest.fixture(scope="module")
def tiny_lm(tmp_path_factory):
    return build_tiny_lm(str(tmp_path_factory.mktemp("decode") / "export"))



# ---------------------------------------------------------------------------
# kvcache.py — ring math and the page pool
# ---------------------------------------------------------------------------


class TestKVCache:
    def test_stored_positions_and_mask(self):
        w = 4
        # length 0: nothing stored, nothing attendable
        pos = np.asarray(kvcache.stored_positions(jnp.asarray([0]), w))
        assert (pos < 0).all()
        assert not np.asarray(kvcache.cache_mask(jnp.asarray([0]), w)).any()
        # length 3 < window: slots 0..2 hold 0..2, slot 3 unwritten
        pos = np.asarray(kvcache.stored_positions(jnp.asarray([3]), w))[0]
        assert pos.tolist() == [0, 1, 2, -1]
        mask = np.asarray(kvcache.cache_mask(jnp.asarray([3]), w))[0]
        assert mask.tolist() == [True, True, True, False]
        # length 6 > window: ring wrapped — slots hold 4, 5, 2, 3; the
        # next token (position 6) may attend 3, 4, 5 only (window 4
        # including itself), so slot holding 2 (== 6-4) is masked
        pos = np.asarray(kvcache.stored_positions(jnp.asarray([6]), w))[0]
        assert pos.tolist() == [4, 5, 2, 3]
        mask = np.asarray(kvcache.cache_mask(jnp.asarray([6]), w))[0]
        assert mask.tolist() == [True, True, False, True]

    def test_ring_from_prompt_wraps_and_drops_pad(self):
        w = 4
        kv = jnp.arange(6, dtype=jnp.float32).reshape(6, 1, 1) + 1.0
        # length 6 through a window of 4: positions 2..5 survive in
        # slots 2,3,0,1; the padded tail (rows >= length) is dropped
        ring = np.asarray(kvcache.ring_from_prompt(kv, 6, w))[:, 0, 0]
        assert ring.tolist() == [5.0, 6.0, 3.0, 4.0]
        # length 2: slots 0,1 filled, rest stay zero
        ring = np.asarray(kvcache.ring_from_prompt(kv, 2, w))[:, 0, 0]
        assert ring.tolist() == [1.0, 2.0, 0.0, 0.0]

    def test_page_pool_alloc_free(self):
        cfg = CacheConfig(n_layers=1, n_heads=1, d_head=4, page_size=2,
                          pages_per_seq=2, max_seqs=2)
        pool = PagePool(cfg)
        assert pool.free_pages == 4
        a = pool.alloc_seq()
        b = pool.alloc_seq()
        assert pool.alloc_seq() is None and pool.free_pages == 0
        assert pool.used_fraction == 1.0
        pool.free_seq(a)
        assert pool.free_pages == 2
        with pytest.raises(ValueError):
            pool.free_seq(a)  # double free
        pool.free_seq(b)
        assert sorted(np.concatenate([a, b]).tolist()) == [0, 1, 2, 3]


# ---------------------------------------------------------------------------
# model.py — the shared-weights applier vs the training module
# ---------------------------------------------------------------------------


class TestFullForward:
    def test_matches_training_module(self, tiny_lm):
        model, params, _ = tiny_lm
        toks = np.random.default_rng(0).integers(
            0, VOCAB, (2, 10)).astype(np.int32)
        want = np.asarray(model.module.apply(
            {"params": params}, jnp.asarray(toks), train=False,
            seq_axis=None))
        got, ks, vs = full_forward(params, jnp.asarray(toks), N_LAYERS,
                                   N_HEADS, jnp.float32)
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
        assert len(ks) == N_LAYERS
        assert ks[0].shape == (2, 10, N_HEADS, D_MODEL // N_HEADS)

    def test_window_geq_len_is_plain_causal(self, tiny_lm):
        _, params, _ = tiny_lm
        toks = np.random.default_rng(1).integers(
            0, VOCAB, (1, 6)).astype(np.int32)
        a, _, _ = full_forward(params, jnp.asarray(toks), N_LAYERS,
                               N_HEADS, jnp.float32, window=None)
        b, _, _ = full_forward(params, jnp.asarray(toks), N_LAYERS,
                               N_HEADS, jnp.float32, window=6)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# session.py — greedy token identity + the compile-counter pin
# ---------------------------------------------------------------------------


class TestGreedyIdentity:
    def test_token_identical_per_prefill_bucket(self, tiny_lm):
        """Prompts landing in DIFFERENT prefill buckets (8 and 16)
        decode token-identically to the uncached flax oracle."""
        model, params, _ = tiny_lm
        sess = DecodeSession(model, params=params, page_size=4,
                             pages_per_seq=8, max_seqs=2,
                             prefill_buckets=(8, 16))
        rng = np.random.default_rng(2)
        for plen in (5, 12):  # buckets 8 and 16
            prompt = rng.integers(0, VOCAB, plen).astype(np.int32)
            got = _session_greedy(sess, prompt, 6)
            assert got == _flax_greedy(model, params, prompt, 6)
        assert _hot(sess.compiles) == {"prefill": 2, "decode": 1}

    def test_token_identical_across_eviction_boundary(self, tiny_lm):
        """window = 8 (page_size 4 x 2 pages); 5-token prompt + 10
        generated crosses the ring boundary at position 8 — identical
        to the sliding-window full-forward oracle, including a prompt
        that ALONE overflows the window (prefill-side eviction)."""
        model, params, _ = tiny_lm
        rng = np.random.default_rng(3)
        for plen in (5, 12):
            sess = DecodeSession(model, params=params, page_size=4,
                                 pages_per_seq=2, max_seqs=2,
                                 prefill_buckets=(8, 16))
            assert sess.window == 8
            prompt = rng.integers(0, VOCAB, plen).astype(np.int32)
            got = _session_greedy(sess, prompt, 10)
            assert got == _windowed_greedy(params, prompt, 10, 8)

    def test_batched_decode_matches_sequential(self, tiny_lm):
        """Two sequences decoded in ONE shared step each produce the
        same tokens as the unbatched oracle (pad rows and the second
        sequence cannot perturb the first)."""
        model, params, _ = tiny_lm
        sess = DecodeSession(model, params=params, page_size=4,
                             pages_per_seq=8, max_seqs=4,
                             prefill_buckets=(8,))
        rng = np.random.default_rng(4)
        pa = rng.integers(0, VOCAB, 4).astype(np.int32)
        pb = rng.integers(0, VOCAB, 7).astype(np.int32)
        sa, la = sess.admit(pa)
        sb, lb = sess.admit(pb)
        oa, ob = [int(np.argmax(la))], [int(np.argmax(lb))]
        for _ in range(5):
            lg = sess.decode([sa, sb],
                             np.asarray([oa[-1], ob[-1]], np.int32))
            oa.append(int(np.argmax(lg[0])))
            ob.append(int(np.argmax(lg[1])))
        assert oa == _flax_greedy(model, params, pa, 6)
        assert ob == _flax_greedy(model, params, pb, 6)


class TestCompileCounter:
    def test_steady_state_zero_recompiles(self, tiny_lm):
        """After one admit/decode/evict cycle has touched a (prefill
        bucket, decode bucket) pair, further traffic through the same
        buckets — different prompts, lengths, page assignments, admit
        order — compiles NOTHING new."""
        model, params, _ = tiny_lm
        sess = DecodeSession(model, params=params, page_size=4,
                             pages_per_seq=2, max_seqs=2,
                             prefill_buckets=(8,))
        rng = np.random.default_rng(5)

        def cycle():
            a, la = sess.admit(rng.integers(0, VOCAB, 3).astype(np.int32))
            ta = int(np.argmax(la))
            lg = sess.decode([a], np.asarray([ta], np.int32))
            b, lb = sess.admit(rng.integers(0, VOCAB, 6).astype(np.int32))
            tb = int(np.argmax(lb))
            for _ in range(6):  # crosses the window-8 boundary
                lg = sess.decode([a, b], np.asarray([ta, tb], np.int32))
                ta, tb = int(np.argmax(lg[0])), int(np.argmax(lg[1]))
            sess.release(a)
            lg = sess.decode([b], np.asarray([tb], np.int32))
            sess.release(b)

        cycle()  # warm: compiles prefill x1, decode buckets 1 and 2
        warm = dict(sess.compiles)
        assert _hot(warm) == {"prefill": 1, "decode": 2}
        for _ in range(3):
            cycle()
        assert sess.compiles == warm, (
            f"steady-state decode recompiled: {warm} -> {sess.compiles}")


# ---------------------------------------------------------------------------
# scheduler.py — continuous batching
# ---------------------------------------------------------------------------


class TestContinuousBatcher:
    def test_mid_stream_admit_shares_step_and_stays_correct(self, tiny_lm):
        """Stream B submitted while A is mid-generation: at least one
        decode step batches BOTH (iteration-level sharing), and both
        streams stay token-identical to the uncached oracle."""
        model, params, _ = tiny_lm
        sess = DecodeSession(model, params=params, page_size=4,
                             pages_per_seq=8, max_seqs=4,
                             prefill_buckets=(8,))
        batcher = ContinuousBatcher(sess, DecodePolicy(max_pending=8),
                                    replica=0).start()
        try:
            rng = np.random.default_rng(6)
            pa = rng.integers(0, VOCAB, 4).astype(np.int32)
            pb = rng.integers(0, VOCAB, 6).astype(np.int32)
            results = {}

            def run(name, prompt, n):
                results[name] = batcher.generate(prompt, n)

            ta = threading.Thread(target=run, args=("a", pa, 24))
            tb = threading.Thread(target=run, args=("b", pb, 12))
            ta.start()
            tb.start()  # lands while A is in flight
            ta.join(60)
            tb.join(60)
            assert results["a"] == _flax_greedy(model, params, pa, 24)
            assert results["b"] == _flax_greedy(model, params, pb, 12)
            st = batcher.stats()
            assert st["shared_steps"] >= 1, st
            assert st["evicted"] == 2 and st["active"] == 0
            # every page is either free or retained by the prefix
            # cache for the NEXT stream — none leaked to dead seqs
            assert sess.pool.free_pages \
                + sess.prefix_cache.cached_pages == sess.cfg.n_pages
        finally:
            batcher.stop()

    def test_admission_overload_is_typed_and_o1(self, tiny_lm):
        """A full pending queue rejects with the SAME typed Overloaded
        the eval path uses — immediately, without waiting on the
        scheduler."""
        model, params, _ = tiny_lm
        sess = DecodeSession(model, params=params, page_size=4,
                             pages_per_seq=2, max_seqs=2,
                             prefill_buckets=(8,))
        # NOT started: pending can only grow, so the bound is exact
        batcher = ContinuousBatcher(sess, DecodePolicy(max_pending=1),
                                    replica=0)
        errs = []

        def bg():
            try:
                batcher.generate(np.asarray([1, 2, 3], np.int32), 4)
            except Exception as e:
                errs.append(e)

        t = threading.Thread(target=bg)
        t.start()
        deadline = 50
        while batcher.stats()["pending"] < 1 and deadline:
            deadline -= 1
            threading.Event().wait(0.02)
        with pytest.raises(Overloaded):
            batcher.generate(np.asarray([1, 2, 3], np.int32), 4)
        batcher.stop()  # fails the queued request with Overloaded
        t.join(10)
        assert len(errs) == 1 and isinstance(errs[0], Overloaded)

    def test_decode_step_fault_restarts_from_export(self, tiny_lm,
                                                    tmp_path):
        """An injected decode_step fault fails THAT step's streams,
        then the replica restarts from a fresh export load of THE
        VERSION IT SERVES on a zeroed page pool (same budgeted
        supervision as eval replicas) and serves the next stream
        correctly.  A newer INCOMPATIBLE publish sitting in the dir
        must not ride in through the restart — that would be a side
        door past the reload watcher's IncompatibleExport refusal."""
        from theanompi_tpu.decode import DecodeReplica
        from theanompi_tpu.resilience import faults

        model, params, _ = tiny_lm
        export_dir = str(tmp_path / "export")
        export_model(model, export_dir, version=0)
        # newer, incompatible (weight dtype) publish: newest-verified,
        # but NOT what this replica serves
        export_model(model, export_dir, version=1, weight_dtype="int8")
        loaded = load_export(export_dir, version=0)
        rep = DecodeReplica(0, export_dir, model, loaded,
                            DecodePolicy(max_pending=4),
                            max_restarts=1, page_size=4,
                            pages_per_seq=8, max_seqs=4,
                            prefill_buckets=(8,))
        rep.batcher.start()
        faults.install([{"site": "decode_step", "replica": 0,
                         "step": 2}])
        try:
            rng = np.random.default_rng(9)
            prompt = rng.integers(0, VOCAB, 5).astype(np.int32)
            with pytest.raises(faults.FaultInjected):
                rep.generate(prompt, 8)
            assert rep.restarts == 1 and rep.alive
            # restarted on the SERVED version, not the newer publish
            assert rep.session.version == 0
            # the restarted replica serves, token-identically
            out = rep.generate(prompt, 6)
            assert out == _flax_greedy(model, params, prompt, 6)
            assert rep.session.pool.free_pages \
                + rep.session.prefix_cache.cached_pages == \
                rep.session.cfg.n_pages
        finally:
            faults.clear()
            rep.batcher.stop()

    def test_request_validation(self, tiny_lm):
        model, params, _ = tiny_lm
        sess = DecodeSession(model, params=params, page_size=4,
                             pages_per_seq=2, max_seqs=2,
                             prefill_buckets=(8,))
        # max_new_cap above max_len so the positional-table check is
        # reachable (the cap otherwise clamps the request first)
        batcher = ContinuousBatcher(
            sess, DecodePolicy(max_new_cap=sess.max_len + 8,
                               submit_timeout_s=5.0), replica=0)
        with pytest.raises(ValueError):
            batcher.generate(np.zeros((0,), np.int32), 4)
        with pytest.raises(ValueError):  # prompt > largest bucket
            batcher.generate(np.zeros((9,), np.int32), 4)
        with pytest.raises(ValueError):  # past the positional table
            batcher.generate(np.asarray([1], np.int32),
                             sess.max_len + 1)
        batcher.stop()


# ---------------------------------------------------------------------------
# Quantized exports
# ---------------------------------------------------------------------------


class TestQuantizedExports:
    def test_bf16_round_trip_error_bound(self, tiny_lm):
        _, params, _ = tiny_lm
        deq = dequantize_tree(quantize_tree(params, "bf16"),
                              upcast_bf16=True)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(deq)):
            a, b = np.asarray(a), np.asarray(b)
            assert b.dtype == np.float32
            # bf16 keeps 8 significant bits: elementwise relative
            # error bounded by 2^-8 (plus an absolute floor near 0)
            assert np.all(np.abs(a - b)
                          <= np.abs(a) * 2.0 ** -8 + 1e-12)

    def test_int8_round_trip_error_bound(self, tiny_lm):
        _, params, _ = tiny_lm
        q = quantize_tree(params, "int8")
        deq = dequantize_tree(q, upcast_bf16=True)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(deq)):
            a, b = np.asarray(a), np.asarray(b)
            if a.ndim < 2:
                np.testing.assert_array_equal(a, b)  # kept f32
                continue
            # symmetric per-output-channel scale: |err| <= scale/2
            amax = np.max(np.abs(a), axis=tuple(range(a.ndim - 1)),
                          keepdims=True)
            bound = np.where(amax > 0, amax, 1.0) / 127.0 / 2.0
            assert np.all(np.abs(a - b) <= bound + 1e-7)

    def test_quantized_export_load_and_meta(self, tiny_lm, tmp_path):
        model, params, _ = tiny_lm
        for wd in ("bf16", "int8"):
            d = str(tmp_path / f"export_{wd}")
            export_model(model, d, version=0, weight_dtype=wd)
            loaded = load_export(d)  # dequantize-on-load default
            assert loaded.meta["weight_dtype"] == wd
            assert loaded.meta["decode"] is True
            assert loaded.meta["net"]["vocab"] == VOCAB
            for leaf in jax.tree.leaves(loaded.params):
                assert np.asarray(leaf).dtype == np.float32
            raw = load_export(d, dequantize=False)
            kinds = {np.asarray(leaf).dtype.name
                     for leaf in jax.tree.leaves(raw.params)}
            assert ("int8" in kinds) if wd == "int8" \
                else ("bfloat16" in kinds)

    def test_on_the_fly_matches_dequantize_on_load(self, tiny_lm,
                                                   tmp_path):
        """int8 weights kept quantized on device (dequantize_tree runs
        inside the jitted step) decode the same tokens as the
        collapsed-at-load tree — the two dequant paths are one
        arithmetic."""
        model, params, _ = tiny_lm
        d = str(tmp_path / "export_fly")
        export_model(model, d, version=0, weight_dtype="int8")
        rng = np.random.default_rng(7)
        prompt = rng.integers(0, VOCAB, 5).astype(np.int32)
        outs = []
        for dequantize in (True, False):
            loaded = load_export(d, dequantize=dequantize)
            sess = DecodeSession(model, params=loaded.params,
                                 page_size=4, pages_per_seq=8,
                                 max_seqs=2, prefill_buckets=(8,))
            outs.append(_session_greedy(sess, prompt, 8))
        assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# Hot-reload refusal + the GENERATE wire op
# ---------------------------------------------------------------------------


class TestDecodeServing:
    @pytest.fixture()
    def decode_server(self, tiny_lm):
        model, params, export_dir = tiny_lm
        key_before = os.environ.get("THEANOMPI_TPU_SERVICE_KEY")
        server = InferenceServer(
            export_dir, replicas=1, reload_poll_s=0, model=model,
            decode=True,
            decode_opts=dict(page_size=4, pages_per_seq=8, max_seqs=4,
                             prefill_buckets=(8,))).start()
        port = _free_port()
        ready, stop = threading.Event(), threading.Event()
        t = threading.Thread(
            target=serve, args=(server, "127.0.0.1", port, ready, stop),
            daemon=True)
        t.start()
        assert ready.wait(30)
        addr = f"127.0.0.1:{port}"
        clients: list[InferenceClient] = []

        def make_client() -> InferenceClient:
            c = InferenceClient(addr)
            clients.append(c)
            return c

        yield make_client, server
        try:
            InferenceClient(addr).shutdown()
        except Exception:
            stop.set()
        for c in clients:
            c.close()
        t.join(timeout=5)
        server.stop()
        if key_before is None:
            os.environ.pop("THEANOMPI_TPU_SERVICE_KEY", None)
        else:
            os.environ["THEANOMPI_TPU_SERVICE_KEY"] = key_before

    def test_generate_over_wire_two_streams(self, tiny_lm,
                                            decode_server):
        model, params, _ = tiny_lm
        make_client, server = decode_server
        rng = np.random.default_rng(8)
        prompts = [rng.integers(0, VOCAB, 5).astype(np.int32),
                   rng.integers(0, VOCAB, 7).astype(np.int32)]
        outs = [None, None]
        cs = [make_client(), make_client()]

        def run(i):
            outs[i] = cs[i].generate(prompts[i], 10)

        ths = [threading.Thread(target=run, args=(i,))
               for i in range(2)]
        for th in ths:
            th.start()
        for th in ths:
            th.join(60)
        for p, o in zip(prompts, outs):
            assert o is not None and o.dtype == np.int32
            assert list(o) == _flax_greedy(model, params, p, 10)
        st = cs[0].stats()
        assert st["decode"] is True
        assert st["shared_steps"] >= 1, st
        assert st["tokens"] >= 20

    def test_infer_op_rejected_in_decode_mode(self, decode_server):
        make_client, _ = decode_server
        with pytest.raises(ServiceError, match="generate"):
            make_client().infer(np.zeros((1, 16), np.int32))

    def test_reload_refuses_incompatible_then_accepts(
            self, tiny_lm, decode_server):
        """Publish v1 with a DIFFERENT weight dtype: the watcher must
        refuse with the typed IncompatibleExport, keep serving v0, and
        skip the bad version until v2 (compatible) supersedes it."""
        model, params, export_dir = tiny_lm
        make_client, server = decode_server
        c = make_client()
        export_model(model, export_dir, version=1, weight_dtype="int8")
        with pytest.raises(IncompatibleExport, match="weight_dtype"):
            c.reload()
        assert server.version == 0
        # the refusal is remembered (no re-LOAD) but EVERY reload of
        # the refused version re-raises the typed error from memory —
        # a client polling after the background watcher saw the
        # publish first still observes the refusal, not a silent
        # old-version return
        with pytest.raises(IncompatibleExport, match="weight_dtype"):
            c.reload()
        # the server still serves
        out = c.generate(np.asarray([1, 2, 3], np.int32), 4)
        assert len(out) == 4
        # a compatible v2 goes through and supersedes the skip
        export_model(model, export_dir, version=2)
        assert c.reload() == 2
        assert server.version == 2

    def test_export_incompatibility_covers_net_dims(self):
        """A resized transformer (same class, same sample_shape, same
        dtype) must be refused: its arrays cannot adopt into sessions
        built around the live module's dims."""
        from theanompi_tpu.serving import export_incompatibility

        live = {"modelfile": "m", "modelclass": "C",
                "sample_shape": [16], "weight_dtype": "f32",
                "decode": True,
                "net": {"vocab": 32, "d_model": 16, "n_layers": 2}}
        assert export_incompatibility(live, dict(live)) is None
        resized = dict(live,
                       net={"vocab": 32, "d_model": 32, "n_layers": 2})
        assert "net dims" in export_incompatibility(live, resized)

    def test_decode_mode_requires_capable_export(self, tmp_path):
        from tests._tiny_models import TinyCifar

        model = TinyCifar(config=ModelConfig(batch_size=8, n_epochs=1,
                                             print_freq=0),
                          verbose=False)
        d = str(tmp_path / "cnn_export")
        export_model(model, d, version=0)
        with pytest.raises(ValueError, match="decode-capable"):
            InferenceServer(d, replicas=1, reload_poll_s=0,
                            model=model, decode=True)


# ---------------------------------------------------------------------------
# Batched prefill (ISSUE 18)
# ---------------------------------------------------------------------------


class TestBatchedPrefill:
    def _session(self, tiny_lm, **over):
        model, params, _ = tiny_lm
        opts = dict(page_size=4, pages_per_seq=4, max_seqs=4,
                    prefill_buckets=(4, 8))
        opts.update(over)
        return model, params, DecodeSession(model, params=params,
                                            **opts)

    def test_batch_identity_across_buckets_zero_recompiles(
            self, tiny_lm):
        """Every (n_seqs, token) bucket pair: a batched admission's
        rows decode token-identically to the uncached oracle (= the
        serial admit path's own identity anchor), and after
        ``warmup_prefill_batch`` no batch shape compiles anything."""
        model, params, sess = self._session(tiny_lm,
                                            prefix_cache=False)
        sess.warmup()
        sess.warmup_prefill_batch()
        warm = dict(sess.compiles)
        rng = np.random.default_rng(30)
        # n straddles the n_seqs buckets (1, 2, 4); lengths straddle
        # the token buckets (4, 8) inside one batch
        for lens in ((3,), (4, 5), (3, 4, 8), (2, 4, 5, 8)):
            prompts = [rng.integers(0, VOCAB, t).astype(np.int32)
                       for t in lens]
            admitted = sess.admit_batch(prompts)
            seqs = [s for s, _ in admitted]
            outs = [[int(np.argmax(lg))] for _, lg in admitted]
            for _ in range(3):
                lg = sess.decode(seqs, np.asarray(
                    [o[-1] for o in outs], np.int32))
                for i, o in enumerate(outs):
                    o.append(int(np.argmax(lg[i])))
            for p, o in zip(prompts, outs):
                assert o == _flax_greedy(model, params, p, 4)
            for s in seqs:
                sess.release(s)
        # the decode calls above touch their own (unwarmed) n-seq
        # buckets; the batched-prefill pin is the prefill families
        for fam in ("prefill", "prefill_batch", "extend"):
            assert sess.compiles[fam] == warm[fam], (
                f"{fam} recompiled: {warm} -> {sess.compiles}")

    def test_mixed_cold_and_hit_rows_share_pages(self, tiny_lm):
        """One batch carries a prefix-cache HIT row (extend from a
        start offset) and a COLD row (start 0): the hit aliases the
        cached page, the cold row fills fresh pages, both rows decode
        token-identically."""
        model, params, sess = self._session(tiny_lm)
        rng = np.random.default_rng(31)
        base = rng.integers(0, VOCAB, 4).astype(np.int32)
        seed, _ = sess.admit(np.concatenate(
            [base, rng.integers(0, VOCAB, 1).astype(np.int32)]))
        ph = np.concatenate(
            [base, rng.integers(0, VOCAB, 2).astype(np.int32)])
        pcold = rng.integers(0, VOCAB, 6).astype(np.int32)
        hits0 = sess.prefix_cache.hits
        (sh, lh), (sc, lc) = sess.admit_batch([ph, pcold])
        assert sess.prefix_cache.hits == hits0 + 1
        assert int(sh.page_row[0]) == int(seed.page_row[0])  # aliased
        assert int(sc.page_row[0]) != int(seed.page_row[0])
        oh, oc = [int(np.argmax(lh))], [int(np.argmax(lc))]
        for _ in range(5):
            lg = sess.decode([sh, sc],
                             np.asarray([oh[-1], oc[-1]], np.int32))
            oh.append(int(np.argmax(lg[0])))
            oc.append(int(np.argmax(lg[1])))
        assert oh == _flax_greedy(model, params, ph, 6)
        assert oc == _flax_greedy(model, params, pcold, 6)

    def test_cow_when_two_batch_rows_share_a_page(self, tiny_lm):
        """Two rows of ONE batch alias the same cached prefix page;
        decoding past the ring window writes into it -> COW un-shares
        each row privately, both match the sliding-window oracle."""
        model, params, sess = self._session(tiny_lm, pages_per_seq=2,
                                            prefill_buckets=(8,))
        rng = np.random.default_rng(32)
        base = rng.integers(0, VOCAB, 5).astype(np.int32)
        seed, _ = sess.admit(base)        # registers base[:4]
        sess.release(seed)
        pa = np.concatenate(
            [base[:4], rng.integers(0, VOCAB, 1).astype(np.int32)])
        pb = np.concatenate(
            [base[:4], rng.integers(0, VOCAB, 2).astype(np.int32)])
        (sa, la), (sb, lb) = sess.admit_batch([pa, pb])
        shared = int(sa.page_row[0])
        assert shared == int(sb.page_row[0])
        assert sess.pool.refcount(shared) == 3   # cache + both rows
        oa, ob = [int(np.argmax(la))], [int(np.argmax(lb))]
        for _ in range(11):               # crosses the window-8 wrap
            lg = sess.decode([sa, sb],
                             np.asarray([oa[-1], ob[-1]], np.int32))
            oa.append(int(np.argmax(lg[0])))
            ob.append(int(np.argmax(lg[1])))
        assert oa == _windowed_greedy(params, pa, 12, 8)
        assert ob == _windowed_greedy(params, pb, 12, 8)
        assert sess.cow_copies >= 2
        assert int(sa.page_row[0]) != int(sb.page_row[0])  # diverged

    def test_allocation_pressure_evicts_mid_batch(self, tiny_lm):
        """A batch whose rows outnumber the free pages evicts LRU
        prefix entries row by row instead of failing — and the
        admitted rows still decode correctly."""
        model, params, sess = self._session(tiny_lm, pages_per_seq=2,
                                            prefill_buckets=(8,))
        rng = np.random.default_rng(33)
        for _ in range(4):                # 4 one-page orphan entries
            s, _ = sess.admit(rng.integers(0, VOCAB, 5)
                              .astype(np.int32))
            sess.release(s)
        assert len(sess.prefix_cache) == 4
        assert sess.pool.free_pages == 4  # of n_pages=8
        prompts = [rng.integers(0, VOCAB, 5).astype(np.int32)
                   for _ in range(3)]
        admitted = sess.admit_batch(prompts)    # needs 6 pages
        assert sess.prefix_cache.evictions >= 1
        seqs = [s for s, _ in admitted]
        outs = [[int(np.argmax(lg))] for _, lg in admitted]
        for _ in range(2):                # stays inside window 8
            lg = sess.decode(seqs, np.asarray(
                [o[-1] for o in outs], np.int32))
            for i, o in enumerate(outs):
                o.append(int(np.argmax(lg[i])))
        for p, o in zip(prompts, outs):
            assert o == _flax_greedy(model, params, p, 3)
        # nothing leaked: once the rows release and the cache drops
        # its refs, every page is free again
        for s in seqs:
            sess.release(s)
        sess.prefix_cache.evict_all()
        assert sess.pool.free_pages == sess.cfg.n_pages

    def test_failed_batch_leaks_no_pages(self, tiny_lm):
        """A batch refused mid-validation (one over-long prompt)
        unwinds every already-taken page reference."""
        model, params, sess = self._session(tiny_lm)
        rng = np.random.default_rng(35)
        free0 = sess.pool.free_pages
        good = rng.integers(0, VOCAB, 5).astype(np.int32)
        bad = rng.integers(0, VOCAB, 9).astype(np.int32)  # > bucket 8
        with pytest.raises(ValueError, match="prompt length"):
            sess.admit_batch([good, bad])
        assert sess.pool.free_pages == free0


class TestDrainMigration:
    def test_drained_stream_resumes_byte_identical(self, tiny_lm):
        """Scale-down drain: a mid-flight stream leaves the batcher as
        a MigratedStream (emitted tokens + resume manifest + pages); a
        survivor batcher adopts it and the stitched output is
        byte-identical to one uninterrupted stream.  The draining
        batcher refuses new work with the typed Overloaded."""
        from theanompi_tpu.decode.scheduler import MigratedStream

        model, params, _ = tiny_lm

        def mk():
            return DecodeSession(model, params=params, page_size=4,
                                 pages_per_seq=4, max_seqs=2,
                                 prefill_buckets=(8,))

        rng = np.random.default_rng(34)
        prompt = rng.integers(0, VOCAB, 3).astype(np.int32)
        ref = _flax_greedy(model, params, prompt, 13)

        # no scheduler thread: pump by hand so the drain lands at a
        # deterministic point (4 emitted, the stream mid-flight)
        b = ContinuousBatcher(mk(), DecodePolicy(max_pending=4,
                                                 prefill_delay_ms=0.0))
        res = {}
        t = threading.Thread(
            target=lambda: res.setdefault("out",
                                          b.generate(prompt, 13)))
        t.start()
        import time
        for _ in range(2000):
            if b._pending:
                break
            time.sleep(0.002)
        b._admit()
        for _ in range(3):
            b._step()
        b._draining = True
        b._migrate_out()
        t.join(30)
        out = res["out"]
        assert isinstance(out, MigratedStream)
        # the pending (un-resumed) token rides the manifest, not the
        # emitted list
        assert out.tokens == ref[:3]
        assert out.manifest["first_token"] == ref[3]
        with pytest.raises(Overloaded, match="draining"):
            b.generate(prompt, 2)
        st = b.stats()
        assert st["drain_migrated"] == 1 and st["draining"]

        survivor = ContinuousBatcher(
            mk(), DecodePolicy(max_pending=4)).start()
        try:
            rest = survivor.generate_adopted(
                out.manifest, out.k, out.v, 13 - len(out.tokens))
            assert out.tokens + [int(x) for x in rest] == ref
        finally:
            survivor.stop()
