"""Scanned multi-step training (parallel/bsp.py make_bsp_multi_step):
k iterations in one device program must produce the exact trajectory
of k single-step calls, and the model/epoch plumbing must account
iterations correctly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from theanompi_tpu.models.base import ModelConfig
from theanompi_tpu.parallel.bsp import (
    TrainState,
    make_bsp_multi_step,
    make_bsp_train_step,
)
from theanompi_tpu.parallel.mesh import shard_batch
from theanompi_tpu.utils.helper_funcs import build_sgd_optimizer


def linear_loss(params, model_state, batch, rng):
    x, y = batch
    pred = x @ params["w"] + params["b"]
    loss = jnp.mean((pred - y) ** 2)
    return loss, (model_state, {"loss": loss, "error": loss})


class TestMultiStepEquivalence:
    def test_matches_k_single_steps(self, mesh8):
        k = 3
        tx = build_sgd_optimizer(0.05, momentum=0.9)
        params = {"w": jnp.zeros((4, 1)), "b": jnp.zeros(1)}
        single = make_bsp_train_step(linear_loss, tx, mesh8, donate=False)
        multi = make_bsp_multi_step(linear_loss, tx, mesh8, donate=False)

        rng = np.random.default_rng(0)
        xs = rng.standard_normal((k, 16, 4)).astype(np.float32)
        ys = (xs @ np.array([[1.0], [2.0], [-1.0], [0.5]])).astype(np.float32)
        key = jax.random.key(7)

        # trajectory A: k single steps, rng folded per step
        state_a = TrainState.create(params, tx)
        losses_a = []
        for i in range(k):
            batch = shard_batch((xs[i], ys[i][:, 0]), mesh8)
            state_a, m = single(state_a, batch, jax.random.fold_in(key, i))
            losses_a.append(float(m["loss"]))

        # trajectory B: one scanned program over the stacked batches
        state_b = TrainState.create(params, tx)
        stacked = shard_batch((xs, ys[:, :, 0]), mesh8, spec=P(None, "data"))
        state_b, metrics = multi(state_b, stacked, key)
        losses_b = np.asarray(metrics["loss"])

        np.testing.assert_allclose(losses_b, losses_a, rtol=1e-6)
        for la, lb in zip(jax.tree.leaves(state_a.params),
                          jax.tree.leaves(state_b.params)):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       rtol=1e-6)
        assert int(state_b.step) == k


def _donated_inputs(lowered_text: str) -> int:
    """Inputs the lowering marks for donation — either already aliased
    to an output (``tf.aliasing_output``) or handed to XLA as a
    reusable buffer (``jax.buffer_donor``; the compiler decides the
    alias at HLO level)."""
    return (lowered_text.count("tf.aliasing_output")
            + lowered_text.count("jax.buffer_donor"))


class TestStagedBatchDonation:
    """ISSUE 3 copy-done fix: the stacked cadence must DONATE the
    staged batch (donate_argnums covers arg 1, not just the state) so
    XLA can reuse its HBM instead of copying around a live input —
    the r3 account charges 2.37 ms/step to 1 334 copy events."""

    def _donors(self, mesh8, **kw):
        tx = build_sgd_optimizer(0.05, momentum=0.9)
        params = {"w": jnp.zeros((4, 1)), "b": jnp.zeros(1)}
        multi = make_bsp_multi_step(linear_loss, tx, mesh8, **kw)
        rng = np.random.default_rng(1)
        xs = rng.standard_normal((2, 16, 4)).astype(np.float32)
        ys = (xs @ np.ones((4, 1)))[:, :, 0].astype(np.float32)
        stacked = shard_batch((xs, ys), mesh8, spec=P(None, "data"))
        state = TrainState.create(params, tx)
        lowered = multi.lower(state, stacked, jax.random.key(0))
        return _donated_inputs(lowered.as_text()), len(
            jax.tree.leaves(state))

    def test_batch_buffers_donated_by_default(self, mesh8):
        donors, n_state = self._donors(mesh8)
        # every state leaf plus BOTH batch leaves (x and y)
        assert donors == n_state + 2

    def test_donate_batch_false_keeps_buffers(self, mesh8):
        # a caller that replays pre-staged batches opts out; the
        # opt-out must really withhold the batch from donation
        donors, n_state = self._donors(mesh8, donate_batch=False)
        assert donors == n_state

    def test_donate_false_overrides_batch_donation(self, mesh8):
        donors, _ = self._donors(mesh8, donate=False)
        assert donors == 0

    def test_bucketed_exchange_keeps_donation(self, mesh8):
        """ISSUE 13: embedding the bucketed collectives in the backward
        (custom_vjp boundary tags) must not change what the cadence
        donates — state leaves AND both batch leaves, same as B=1."""
        from theanompi_tpu.parallel.exchanger import BSP_Exchanger

        base, n_state = self._donors(mesh8)
        bucketed, _ = self._donors(
            mesh8, exchanger=BSP_Exchanger(exchange_buckets=4, avg=True))
        assert bucketed == base == n_state + 2

    def test_model_config_threads_donate_batch(self, mesh8):
        """ModelConfig.donate_batch reaches the compiled cadence."""
        from tests._tiny_models import TinyCifar128

        def donors(**cfg_kw):
            cfg = ModelConfig(batch_size=4, n_epochs=1, print_freq=0,
                              steps_per_call=2, **cfg_kw)
            m = TinyCifar128(config=cfg, mesh=mesh8, verbose=False)
            m.compile_iter_fns("avg")
            x = np.zeros((2, 32, 32, 32, 3), np.float32)
            y = np.zeros((2, 32), np.int64)
            lowered = m.train_step_multi.lower(
                m.state, (x, y), jax.random.key(0))
            n = _donated_inputs(lowered.as_text())
            m.cleanup()
            return n

        assert donors() == donors(donate_batch=False) + 2


class TestModelPlumbing:
    def test_cifar_trains_with_steps_per_call(self, mesh8, tmp_path):
        """The contract path: begin_epoch stacks host batches, train_iter
        reports k consumed, the recorder sees every sub-step's metrics."""
        from tests._tiny_models import TinyCifar128
        from theanompi_tpu.utils.recorder import Recorder

        cfg = ModelConfig(batch_size=4, n_epochs=1, learning_rate=0.02,
                          print_freq=0, steps_per_call=4,
                          snapshot_dir=str(tmp_path))
        m = TinyCifar128(config=cfg, mesh=mesh8, verbose=False)
        m.compile_iter_fns("avg")
        rec = Recorder(rank=0, size=8, print_freq=0)
        n_iters = m.begin_epoch(0)
        assert n_iters % 4 == 0 and n_iters > 0
        it = 0
        while it < n_iters:
            consumed = m.train_iter(it, rec)
            assert consumed == 4
            it += consumed
        m._flush_metrics(rec)
        # every sub-step produced a metric entry
        assert len(rec.train_losses) == n_iters
        assert np.isfinite(rec.train_losses).all()
        m.cleanup()

    def test_async_rules_reject_steps_per_call(self, tmp_path):
        """Multi-step scanning would skip the async rules' between-
        iteration exchange points — they must refuse it loudly."""
        from theanompi_tpu import EASGD

        cfg = ModelConfig(batch_size=4, n_epochs=1, steps_per_call=2,
                          snapshot_dir=str(tmp_path))
        rule = EASGD()
        with pytest.raises(ValueError, match="steps_per_call"):
            rule.init(devices=2, modelfile="tests._tiny_models",
                      modelclass="TinyCifar", config=cfg, checkpoint=False)
            rule.wait()

    @pytest.mark.slow
    def test_run_bsp_session_with_multi_step(self, mesh8, tmp_path):
        from tests._tiny_models import TinyCifar
        from theanompi_tpu.rules.bsp import run_bsp_session

        cfg = ModelConfig(batch_size=4, n_epochs=1, learning_rate=0.02,
                          print_freq=0, steps_per_call=2,
                          snapshot_dir=str(tmp_path))
        m = TinyCifar(config=cfg, mesh=mesh8, verbose=False)
        res = run_bsp_session(m, checkpoint=False)
        assert np.isfinite(res["val"]["loss"])
