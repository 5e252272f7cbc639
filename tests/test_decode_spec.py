"""decode/: the two token-throughput multipliers of ISSUE 12 (split
from test_decode.py, whose helpers it shares: tests/_decode_helpers.py).

* speculative decoding is byte-identical to the non-speculative
  oracle across every accept/reject boundary (self-draft = full
  accepts, a random small draft = rejects at every depth) and across
  ring eviction, with zero steady-state recompiles (accept counts are
  data, not shapes);
* copy-on-write page sharing: a prefix-cache hit aliases pages and
  stays token-identical, the first wrapping write diverges via COW, a
  shared page outlives its first owner (refcounted eviction), and
  allocation pressure evicts LRU cache entries;
* the draft hot-reload refusal matrix (wrong vocab / resized net ->
  typed `IncompatibleExport`, remembered, server keeps serving).
"""

from __future__ import annotations

import threading

import jax
import numpy as np
import pytest

from theanompi_tpu.decode import (
    CacheConfig,
    ContinuousBatcher,
    DecodePolicy,
    DecodeSession,
    PagePool,
)
from theanompi_tpu.models.transformer import TransformerLM
from theanompi_tpu.serving import (
    IncompatibleExport,
    InferenceServer,
    export_model,
)

from tests._decode_helpers import (
    VOCAB,
    build_tiny_lm,
    tiny_config,
)
from tests._decode_helpers import flax_greedy as _flax_greedy
from tests._decode_helpers import spec_greedy as _spec_greedy
from tests._decode_helpers import windowed_greedy as _windowed_greedy


@pytest.fixture(scope="module")
def tiny_lm(tmp_path_factory):
    return build_tiny_lm(str(tmp_path_factory.mktemp("decode") / "export"))


# ---------------------------------------------------------------------------
# Refcounted pool + cross-request prefix cache (ISSUE 12)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_draft():
    """A genuinely smaller net over the SAME vocab — random weights,
    so its proposals force real accept/reject boundaries."""
    model = TransformerLM(config=tiny_config(), vocab=VOCAB, seq_len=16,
                          n_layers=1, d_model=8, n_heads=1,
                          verbose=False)
    return model, jax.device_get(model.state.params)


@pytest.fixture(scope="module")
def wrong_vocab_draft():
    """tiny_draft's net over HALF the vocab: what a draft export must
    be refused for."""
    return TransformerLM(config=tiny_config(), vocab=16, seq_len=16,
                         n_layers=1, d_model=8, n_heads=1, verbose=False)


class TestRefcountedPagePool:
    def test_incref_decref_and_free_list(self):
        cfg = CacheConfig(n_layers=1, n_heads=1, d_head=4, page_size=2,
                          pages_per_seq=2, max_seqs=2)
        pool = PagePool(cfg)
        row = pool.alloc_seq()
        pool.incref(row)                      # a second owner
        assert all(pool.refcount(int(p)) == 2 for p in row)
        pool.free_seq(row)                    # first owner gone
        assert pool.free_pages == 2           # still held
        assert pool.decref(row) == 2          # last ref frees
        assert pool.free_pages == 4
        with pytest.raises(ValueError):
            pool.decref(row)                  # double free
        with pytest.raises(ValueError):
            pool.incref([int(row[0])])        # incref of a free page
        with pytest.raises(ValueError):
            pool.incref([cfg.n_pages + 1])    # foreign id

    def test_prefix_cache_longest_match_and_lru(self, tiny_lm):
        model, params, _ = tiny_lm
        sess = DecodeSession(model, params=params, page_size=4,
                             pages_per_seq=2, max_seqs=2,
                             prefill_buckets=(8,))
        pc = sess.prefix_cache
        rng = np.random.default_rng(11)
        p = rng.integers(0, VOCAB, 8).astype(np.int32)
        seq, _ = sess.admit(p)                # registers 4-token entry
        assert len(pc) == 1 and pc.misses == 1
        # longest-match: same first page hits; a different page misses
        hit = pc.lookup(np.concatenate([p[:4], p[:1]]))
        assert hit is not None and hit.n_tokens == 4
        assert pc.lookup(rng.integers(0, VOCAB, 8).astype(np.int32)) \
            is None
        # prompts longer than the window are never matched or cached
        assert pc.lookup(np.tile(p, 2)) is None
        sess.release(seq)
        # eviction returns the cache's refs; pool drains to fully free
        assert pc.evict_lru() >= 1
        assert sess.pool.free_pages == sess.cfg.n_pages


class TestPrefixSharing:
    def test_hit_aliases_pages_and_stays_token_identical(self, tiny_lm):
        """Stream B starting with A's page-aligned prefix prefills
        only its suffix against A's shared pages — and still decodes
        token-identically to the uncached oracle."""
        model, params, _ = tiny_lm
        sess = DecodeSession(model, params=params, page_size=4,
                             pages_per_seq=8, max_seqs=4,
                             prefill_buckets=(8, 16))
        rng = np.random.default_rng(12)
        base = rng.integers(0, VOCAB, 4).astype(np.int32)
        pa = np.concatenate([base,
                             rng.integers(0, VOCAB, 2).astype(np.int32)])
        pb = np.concatenate([base,
                             rng.integers(0, VOCAB, 3).astype(np.int32)])
        sa, la = sess.admit(pa)
        sb, lb = sess.admit(pb)
        assert sess.prefix_cache.hits == 1
        assert int(sa.page_row[0]) == int(sb.page_row[0])  # aliased
        oa, ob = [int(np.argmax(la))], [int(np.argmax(lb))]
        for _ in range(7):
            lg = sess.decode([sa, sb],
                             np.asarray([oa[-1], ob[-1]], np.int32))
            oa.append(int(np.argmax(lg[0])))
            ob.append(int(np.argmax(lg[1])))
        assert oa == _flax_greedy(model, params, pa, 8)
        assert ob == _flax_greedy(model, params, pb, 8)

    def test_cow_divergence_across_ring_wrap(self, tiny_lm):
        """window=8: decoding past the window writes into the shared
        prefix page -> host copy-on-write gives each stream a private
        copy; both stay identical to the sliding-window oracle and
        their tables diverge."""
        model, params, _ = tiny_lm
        sess = DecodeSession(model, params=params, page_size=4,
                             pages_per_seq=2, max_seqs=4,
                             prefill_buckets=(8,))
        rng = np.random.default_rng(13)
        base = rng.integers(0, VOCAB, 5).astype(np.int32)
        pa = base
        pb = np.concatenate([base[:4],
                             rng.integers(0, VOCAB, 2).astype(np.int32)])
        sa, la = sess.admit(pa)
        sb, lb = sess.admit(pb)
        assert int(sa.page_row[0]) == int(sb.page_row[0])
        oa, ob = [int(np.argmax(la))], [int(np.argmax(lb))]
        for _ in range(11):   # crosses the window-8 boundary
            lg = sess.decode([sa, sb],
                             np.asarray([oa[-1], ob[-1]], np.int32))
            oa.append(int(np.argmax(lg[0])))
            ob.append(int(np.argmax(lg[1])))
        assert oa == _windowed_greedy(params, pa, 12, 8)
        assert ob == _windowed_greedy(params, pb, 12, 8)
        assert sess.cow_copies >= 2
        assert int(sa.page_row[0]) != int(sb.page_row[0])  # diverged

    def test_shared_page_outlives_first_owner(self, tiny_lm):
        """Refcounted eviction: the prefilling stream releases, a
        later stream still hits its cached prefix and decodes
        correctly; pages only truly free once cache AND users let go."""
        model, params, _ = tiny_lm
        sess = DecodeSession(model, params=params, page_size=4,
                             pages_per_seq=8, max_seqs=2,
                             prefill_buckets=(8, 16))
        rng = np.random.default_rng(14)
        base = rng.integers(0, VOCAB, 4).astype(np.int32)
        pa = np.concatenate([base,
                             rng.integers(0, VOCAB, 1).astype(np.int32)])
        sa, _ = sess.admit(pa)
        sess.release(sa)      # owner gone; the cache keeps the page
        assert sess.pool.free_pages < sess.cfg.n_pages
        pb = np.concatenate([base,
                             rng.integers(0, VOCAB, 2).astype(np.int32)])
        sb, lb = sess.admit(pb)             # hits the orphaned prefix
        assert sess.prefix_cache.hits == 1
        out = [int(np.argmax(lb))]
        for _ in range(5):
            lg = sess.decode([sb], np.asarray([out[-1]], np.int32))
            out.append(int(np.argmax(lg[0])))
        assert out == _flax_greedy(model, params, pb, 6)
        sess.release(sb)
        sess.prefix_cache.evict_all()
        assert sess.pool.free_pages == sess.cfg.n_pages

    def test_allocation_pressure_evicts_lru_entries(self, tiny_lm):
        """Each released stream leaves one cached prefix page behind;
        once orphaned pages fill the pool, the next admission evicts
        LRU entries (the free-list discipline extended to shared
        pages) instead of rejecting."""
        model, params, _ = tiny_lm
        sess = DecodeSession(model, params=params, page_size=4,
                             pages_per_seq=2, max_seqs=4,
                             prefill_buckets=(8,))
        rng = np.random.default_rng(15)
        for _ in range(12):   # > n_pages=8 one-page entries
            p = rng.integers(0, VOCAB, 6).astype(np.int32)
            s, _ = sess.admit(p)
            sess.release(s)
            assert sess.can_admit()
            # nothing leaks: every page is free or cache-held
            assert sess.pool.free_pages \
                + sess.prefix_cache.cached_pages == sess.cfg.n_pages
        assert sess.prefix_cache.evictions >= 1

    def test_zero_recompiles_with_sharing(self, tiny_lm):
        """Hit/miss/COW cycles through warmed buckets compile nothing
        new: extend + cow_copy are program families like any other."""
        model, params, _ = tiny_lm
        sess = DecodeSession(model, params=params, page_size=4,
                             pages_per_seq=2, max_seqs=4,
                             prefill_buckets=(8,))
        rng = np.random.default_rng(16)

        def cycle():
            base = rng.integers(0, VOCAB, 5).astype(np.int32)
            pb = np.concatenate(
                [base[:4], rng.integers(0, VOCAB, 2).astype(np.int32)])
            sa, la = sess.admit(base)
            sb, lb = sess.admit(pb)
            ta, tb = int(np.argmax(la)), int(np.argmax(lb))
            for _ in range(10):  # wraps window 8 -> COW
                lg = sess.decode([sa, sb],
                                 np.asarray([ta, tb], np.int32))
                ta, tb = (int(np.argmax(lg[0])),
                          int(np.argmax(lg[1])))
            sess.release(sa)
            sess.release(sb)

        cycle()
        warm = dict(sess.compiles)
        assert warm["extend"] == 1 and warm["cow_copy"] == 1
        for _ in range(2):
            cycle()
        assert sess.compiles == warm, (
            f"sharing recompiled: {warm} -> {sess.compiles}")


# ---------------------------------------------------------------------------
# Speculative decoding (ISSUE 12)
# ---------------------------------------------------------------------------


class TestSpeculative:
    def test_full_accept_token_identity(self, tiny_lm):
        """Draft == target (self-speculation): every draft accepted,
        output still byte-identical to the uncached oracle, and the
        bonus token makes rounds emit k+1 tokens."""
        model, params, _ = tiny_lm
        sess = DecodeSession(model, params=params, page_size=4,
                             pages_per_seq=8, max_seqs=2,
                             prefill_buckets=(8,))
        draft = DecodeSession(model, params=params, page_size=4,
                              pages_per_seq=8, max_seqs=2,
                              prefill_buckets=(8,))
        rng = np.random.default_rng(17)
        prompt = rng.integers(0, VOCAB, 5).astype(np.int32)
        out = _spec_greedy(sess, draft, prompt, 12, k=3)
        assert out == _flax_greedy(model, params, prompt, 12)

    def test_accept_reject_boundaries_token_identity(self, tiny_lm,
                                                     tiny_draft):
        """A random SMALL draft proposes mostly-wrong tokens: rounds
        reject at every possible boundary and the output is STILL
        byte-identical to the oracle — rejected drafts were never
        written (count-masked scatter), so no rollback can corrupt."""
        model, params, _ = tiny_lm
        dmodel, dparams = tiny_draft
        sess = DecodeSession(model, params=params, page_size=4,
                             pages_per_seq=8, max_seqs=2,
                             prefill_buckets=(8,))
        draft = DecodeSession(dmodel, params=dparams, page_size=4,
                              pages_per_seq=8, max_seqs=2,
                              prefill_buckets=(8,))
        rng = np.random.default_rng(18)
        for plen in (3, 7):
            prompt = rng.integers(0, VOCAB, plen).astype(np.int32)
            out = _spec_greedy(sess, draft, prompt, 10, k=3)
            assert out == _flax_greedy(model, params, prompt, 10)

    def test_identity_across_eviction_boundary(self, tiny_lm):
        """Speculative rounds crossing the ring-wrap boundary match
        the sliding-window oracle (count-masked writes + the chunk
        mask agree with the ring's eviction semantics)."""
        model, params, _ = tiny_lm
        sess = DecodeSession(model, params=params, page_size=4,
                             pages_per_seq=2, max_seqs=2,
                             prefill_buckets=(8,))
        draft = DecodeSession(model, params=params, page_size=4,
                              pages_per_seq=2, max_seqs=2,
                              prefill_buckets=(8,))
        rng = np.random.default_rng(19)
        prompt = rng.integers(0, VOCAB, 5).astype(np.int32)
        out = _spec_greedy(sess, draft, prompt, 14, k=3)
        assert out == _windowed_greedy(params, prompt, 14, 8)

    def test_zero_recompiles_across_accept_reject(self, tiny_lm,
                                                  tiny_draft):
        """Accept counts are DATA: rounds with full accepts, partial
        accepts and total rejects all run the same three programs."""
        model, params, _ = tiny_lm
        dmodel, dparams = tiny_draft
        sess = DecodeSession(model, params=params, page_size=4,
                             pages_per_seq=8, max_seqs=2,
                             prefill_buckets=(8,))
        draft = DecodeSession(dmodel, params=dparams, page_size=4,
                              pages_per_seq=8, max_seqs=2,
                              prefill_buckets=(8,))
        rng = np.random.default_rng(20)
        prompt = rng.integers(0, VOCAB, 5).astype(np.int32)
        _spec_greedy(sess, draft, prompt, 8, k=3)
        warm_t, warm_d = dict(sess.compiles), dict(draft.compiles)
        assert warm_t["verify"] == 1
        assert warm_d["propose"] == 1 and warm_d["commit"] == 1
        for seed in (21, 22):
            p = np.random.default_rng(seed).integers(
                0, VOCAB, 6).astype(np.int32)
            _spec_greedy(sess, draft, p, 8, k=3)
        assert sess.compiles == warm_t
        assert draft.compiles == warm_d

    def test_batcher_speculates_with_shared_prefix(self, tiny_lm):
        """End to end through the ContinuousBatcher: two concurrent
        streams sharing a prefix, speculation on — both match the
        oracle, at least one step batches both, accept rate lands in
        stats with the shared token-accounting shape."""
        model, params, _ = tiny_lm
        sess = DecodeSession(model, params=params, page_size=4,
                             pages_per_seq=8, max_seqs=4,
                             prefill_buckets=(8,))
        draft = DecodeSession(model, params=params, page_size=4,
                              pages_per_seq=8, max_seqs=4,
                              prefill_buckets=(8,))
        batcher = ContinuousBatcher(
            sess, DecodePolicy(max_pending=8, speculate_k=3),
            replica=0, draft_session=draft).start()
        try:
            rng = np.random.default_rng(23)
            base = rng.integers(0, VOCAB, 4).astype(np.int32)
            pa = np.concatenate(
                [base, rng.integers(0, VOCAB, 1).astype(np.int32)])
            pb = np.concatenate(
                [base, rng.integers(0, VOCAB, 2).astype(np.int32)])
            results = {}

            def run(name, prompt, n):
                results[name] = batcher.generate(prompt, n)

            ta = threading.Thread(target=run, args=("a", pa, 17))
            tb = threading.Thread(target=run, args=("b", pb, 9))
            ta.start()
            tb.start()
            ta.join(60)
            tb.join(60)
            assert results["a"] == _flax_greedy(model, params, pa, 17)
            assert results["b"] == _flax_greedy(model, params, pb, 9)
            st = batcher.stats()
            assert st["shared_steps"] >= 1
            spec = st["speculation"]
            assert spec["draft_tokens"] > 0
            assert spec["accept_rate"] is not None \
                and spec["accept_rate"] > 0
            assert st["prefix_cache"]["hits"] >= 1
            # emitted tokens, NOT drafted, are the throughput axis:
            # exactly max_new per stream despite multi-token rounds
            # (the emission trim), far fewer steps than tokens
            assert st["tokens"] == 17 + 9
            assert st["steps"] < st["tokens"]
            assert st["evicted"] == 2 and st["active"] == 0
            assert sess.pool.free_pages \
                + sess.prefix_cache.cached_pages == sess.cfg.n_pages
        finally:
            batcher.stop()

    def test_speculate_k_validation(self, tiny_lm):
        model, params, _ = tiny_lm
        sess = DecodeSession(model, params=params, page_size=4,
                             pages_per_seq=2, max_seqs=2,
                             prefill_buckets=(8,))
        draft = DecodeSession(model, params=params, page_size=4,
                              pages_per_seq=2, max_seqs=2,
                              prefill_buckets=(8,))
        with pytest.raises(ValueError, match="speculate_k"):
            ContinuousBatcher(sess, DecodePolicy(speculate_k=8),
                              replica=0, draft_session=draft)

    def test_speculative_accounting_shape(self):
        from theanompi_tpu.utils.token_accounting import (
            speculative_accounting,
        )

        none_yet = speculative_accounting(0, 0, 0)
        assert none_yet["accept_rate"] is None
        rec = speculative_accounting(26, 18, 12)
        assert rec == {"emitted_tokens": 26, "draft_tokens": 18,
                       "accepted_draft_tokens": 12,
                       "accept_rate": 12 / 18}


class TestDraftServing:
    def test_draft_incompatibility_matrix(self):
        from theanompi_tpu.serving import draft_incompatibility

        target = {"decode": True,
                  "net": {"vocab": 32, "seq_len": 16, "d_model": 16,
                          "n_layers": 2, "n_heads": 2}}
        ok = {"decode": True,
              "net": {"vocab": 32, "seq_len": 16, "d_model": 8,
                      "n_layers": 1, "n_heads": 1}}
        assert draft_incompatibility(target, ok) is None
        assert "decode-capable" in draft_incompatibility(
            target, dict(ok, decode=False))
        assert "vocab" in draft_incompatibility(
            target, dict(ok, net=dict(ok["net"], vocab=16)))
        big = dict(target, net=dict(target["net"], seq_len=4096))
        assert "positional" in draft_incompatibility(big, ok)

    def test_draft_reload_refusal_matrix_over_wire(
            self, tiny_lm, tiny_draft, wrong_vocab_draft, tmp_path):
        """The PR-10 refusal matrix extended to the draft poll: a
        published draft with the wrong vocab (target anchor) or
        resized net (draft-session anchor) raises the typed
        IncompatibleExport, is REMEMBERED (no reload churn), the
        server keeps serving AND speculating; a compatible newer
        draft supersedes the skip."""
        model, params, _ = tiny_lm
        export_dir = str(tmp_path / "export")
        draft_dir = str(tmp_path / "draft")
        export_model(model, export_dir, version=0)
        export_model(model, draft_dir, version=0, weight_dtype="bf16")
        server = InferenceServer(
            export_dir, replicas=1, reload_poll_s=0, model=model,
            decode=True,
            decode_opts=dict(page_size=4, pages_per_seq=8, max_seqs=4,
                             prefill_buckets=(8,),
                             draft_export_dir=draft_dir,
                             speculate_k=3)).start()
        try:
            rng = np.random.default_rng(24)
            prompt = rng.integers(0, VOCAB, 5).astype(np.int32)
            oracle = _flax_greedy(model, params, prompt, 6)
            assert server.generate(prompt, 6).tolist() == oracle
            export_model(wrong_vocab_draft, draft_dir, version=1)
            with pytest.raises(IncompatibleExport, match="vocab"):
                server.check_draft_reload()
            resized, _ = tiny_draft  # same vocab, a smaller net
            export_model(resized, draft_dir, version=2)
            with pytest.raises(IncompatibleExport, match="net dims"):
                server.check_draft_reload()
            # remembered: re-raises from memory, still serving v0
            with pytest.raises(IncompatibleExport):
                server.check_draft_reload()
            assert server.draft_version == 0
            assert server.generate(prompt, 4).tolist() == oracle[:4]
            # a compatible newer draft goes through
            export_model(model, draft_dir, version=3,
                         weight_dtype="bf16")
            assert server.check_draft_reload() == 3
            assert server.generate(prompt, 6).tolist() == oracle
            st = server.stats()
            assert st["draft_version"] == 3
            assert st["accept_rate"] is not None
        finally:
            server.stop()

    def test_incompatible_draft_refused_at_construction(
            self, tiny_lm, wrong_vocab_draft, tmp_path):
        model, params, _ = tiny_lm
        export_dir = str(tmp_path / "export")
        draft_dir = str(tmp_path / "draft")
        export_model(model, export_dir, version=0)
        export_model(wrong_vocab_draft, draft_dir, version=0)
        with pytest.raises(IncompatibleExport, match="vocab"):
            InferenceServer(
                export_dir, replicas=1, reload_poll_s=0, model=model,
                decode=True,
                decode_opts=dict(page_size=4, pages_per_seq=8,
                                 max_seqs=4, prefill_buckets=(8,),
                                 draft_export_dir=draft_dir))
