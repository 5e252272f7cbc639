"""Sequence-parallel transformer LM: trains end-to-end over a
(data x seq) mesh through the standard rule spine, and the (data x seq)
factorization is numerically equivalent to plain data parallelism."""

import jax
import numpy as np
import pytest

from theanompi_tpu.models.base import ModelConfig
from theanompi_tpu.models.transformer import TransformerLM
from theanompi_tpu.parallel.mesh import MeshSpec, make_training_mesh
from theanompi_tpu.utils.recorder import Recorder


def make_lm(mesh, seq_len=32, batch=4, seed=42):
    cfg = ModelConfig(batch_size=batch, n_epochs=1, learning_rate=0.5,
                      momentum=0.9, weight_decay=0.0, lr_schedule="constant",
                      print_freq=1000, seed=seed)
    return TransformerLM(config=cfg, mesh=mesh, vocab=32, seq_len=seq_len,
                         n_layers=2, d_model=32, n_heads=4)


@pytest.fixture(scope="module")
def dp_sp_mesh():
    return make_training_mesh(MeshSpec(data=2, seq=4), jax.devices()[:8])


class TestTransformerSP:
    @pytest.mark.slow  # convergence proof; the numeric contract is
    # test_dp_sp_equivalent_to_pure_dp below
    def test_learns_synthetic_grammar(self, dp_sp_mesh):
        m = make_lm(dp_sp_mesh)
        m.compile_iter_fns("avg")
        rec = Recorder(rank=1, size=8, print_freq=1000)
        m.begin_epoch(0)
        first = None
        for i in range(60):
            m.train_iter(i, rec)
            if i == 4:
                m._flush_metrics(rec)
                first = m.current_info["loss"]
        m._flush_metrics(rec)
        last = m.current_info["loss"]
        # ln(32) ≈ 3.47 at init; the 0.9-deterministic successor table
        # drives CE down fast once the table is learned
        assert first is not None and last < first - 0.5, (first, last)
        val = m.val_epoch(rec)
        assert val["error"] < 0.6
        m.cleanup()

    def test_dp_sp_equivalent_to_pure_dp(self):
        # same init, same global batch, no dropout: one train step over
        # (data=2, seq=4) must equal one over (data=8, seq=1)
        devs = jax.devices()[:8]
        mesh_sp = make_training_mesh(MeshSpec(data=2, seq=4), devs)
        mesh_dp = make_training_mesh(MeshSpec(data=8, seq=1), devs)

        results = []
        for mesh, batch in ((mesh_sp, 16), (mesh_dp, 4)):
            # per-shard batch sizes differ so the GLOBAL batch matches:
            # 16*2 == 4*8 == 32 sequences
            m = make_lm(mesh, batch=batch, seed=7)
            m.compile_iter_fns("avg")
            rec = Recorder(rank=1, size=8, print_freq=1000)
            m.begin_epoch(0)
            m.train_iter(0, rec)
            m._flush_metrics(rec)
            results.append(
                (jax.tree.map(np.asarray, m.state.params),
                 m.current_info["loss"]))
            m.cleanup()

        (p_sp, l_sp), (p_dp, l_dp) = results
        assert np.isclose(l_sp, l_dp, rtol=1e-4), (l_sp, l_dp)
        for a, b in zip(jax.tree.leaves(p_sp), jax.tree.leaves(p_dp)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

    @pytest.mark.slow
    def test_zoo_entry_and_session(self, dp_sp_mesh, tmp_path):
        from theanompi_tpu.rules.bsp import run_bsp_session

        m = make_lm(dp_sp_mesh)
        m.config.snapshot_dir = str(tmp_path)
        out = run_bsp_session(m, max_epochs=1, checkpoint=True)
        assert out["epochs_run"] == 1
        assert np.isfinite(out["val"]["loss"])


def test_remat_identical_params_and_grads():
    """ModelConfig.remat: same param tree, same loss, same grads —
    only the backward's memory/recompute schedule changes."""
    from theanompi_tpu.models.transformer import TransformerLMNet

    kw = dict(vocab=16, n_layers=2, d_model=8, n_heads=2, d_ff=16,
              max_len=32)
    plain = TransformerLMNet(**kw, remat=False)
    remat = TransformerLMNet(**kw, remat=True)
    tokens = jax.random.randint(jax.random.key(0), (1, 8), 0, 16)
    def init(net):
        return jax.jit(lambda: net.init(jax.random.key(1), tokens,
                                        train=True))

    vp = init(plain)()
    assert jax.tree.structure(vp) == jax.tree.structure(
        jax.eval_shape(init(remat)))

    def loss(net, v):
        logits = net.apply(v, tokens, train=True)
        return (logits ** 2).mean()

    lp, gp = jax.jit(jax.value_and_grad(
        lambda v: loss(plain, v)))(vp)
    lr, gr = jax.jit(jax.value_and_grad(
        lambda v: loss(remat, v)))(vp)
    assert lp == pytest.approx(lr, rel=1e-6)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.slow  # integration compose; the remat contract itself is
# test_remat_identical_params_and_grads (fast)
def test_remat_trains_through_sp_spine(dp_sp_mesh):
    """remat composes with the (data x seq) ring-attention step."""
    cfg = ModelConfig(batch_size=4, n_epochs=1, learning_rate=0.05,
                      print_freq=0, weight_decay=0.0, remat=True)
    m = TransformerLM(config=cfg, mesh=dp_sp_mesh, verbose=False,
                      n_layers=2, d_model=32, n_heads=4, seq_len=32)
    m.compile_iter_fns("avg")
    rec = Recorder(rank=0, size=8, print_freq=0)
    m.begin_epoch(0)
    for i in range(2):
        m.train_iter(i, rec)
    m._flush_metrics(rec)
    assert np.isfinite(rec.train_losses).all()
    m.cleanup()


def test_lm_declares_trained_flops(dp_sp_mesh):
    """The LM family reports achieved TFLOP/s like the CNN zoo: FLOPs
    per sequence = 6·n_active·L (2xMAC, fwd+bwd; embedding/positional
    tables excluded — gather + add, ~0 FLOPs) + the attention score/PV
    term counted causally, 6·n_layers·d·L(L+1), computed from the REAL
    param count so resized/TP models stay honest."""
    from jax import tree_util as jtu

    m = make_lm(dp_sp_mesh)
    flat = jtu.tree_flatten_with_path(m.state.params)[0]

    def is_table(path):
        keys = ({getattr(k, "key", None) for k in path}
                | {getattr(k, "name", None) for k in path})
        return bool(keys & {"embedding", "pos_emb"})

    active = sum(int(leaf.size) for p, leaf in flat if not is_table(p))
    total = sum(int(leaf.size) for _, leaf in flat)
    assert 0 < active < total  # the tables exist AND are excluded
    want = 6 * active * 32 + 6 * 2 * 32 * 32 * 33
    assert m.train_flops_per_sample == float(want)
    m.cleanup()


def test_lm_train_flops_discounts_experts():
    import jax.numpy as jnp

    from theanompi_tpu.models.transformer import _lm_train_flops

    params = {"dense": jnp.zeros((10,)), "experts": jnp.zeros((4, 5))}
    mask = {"dense": False, "experts": True}
    got = _lm_train_flops(params, n_layers=1, seq_len=2, d_model=3,
                          expert_mask=mask, n_experts=4)
    # top-1 routing: 20 expert weights count as 20/4 active per token
    want = 6 * (10 + 20 // 4) * 2 + 6 * 1 * 3 * 2 * 3
    assert got == float(want)


class TestSeqAxisRouting:
    """A size-1 seq axis must route attention through the fused local
    path, not a 1-hop ring that materializes the full (B,H,T,T) score
    matrix (the round-3 on-chip lm_b16_s2048 HBM OOM)."""

    def test_pure_dp_mesh_resolves_to_none(self):
        mesh = make_training_mesh(MeshSpec(data=8), jax.devices()[:8])
        m = make_lm(mesh)
        assert m._resolved_seq_axis() is None

    def test_sp_mesh_keeps_seq_axis(self, dp_sp_mesh):
        m = make_lm(dp_sp_mesh)
        assert m._resolved_seq_axis() == "seq"

    def test_pure_dp_never_calls_sequence_attention(self, monkeypatch):
        import theanompi_tpu.models.transformer as tr

        def boom(*a, **k):
            raise AssertionError("sequence_attention called on a "
                                 "size-1 seq axis")

        monkeypatch.setattr(tr, "sequence_attention", boom)
        mesh = make_training_mesh(MeshSpec(data=8), jax.devices()[:8])
        m = make_lm(mesh)
        m.compile_iter_fns("avg")
        rec = Recorder(rank=0, size=8, print_freq=1000)
        try:
            m.begin_epoch(0)
            m.train_iter(0, rec)   # would raise through trace if routed
        finally:
            m.cleanup()
