"""The output head and its loss over token blocks
(``layers.blocked_softmax_cross_entropy``): values and hand-written
gradients against ``jax.grad`` of the composed form, for both weight
layouts, and ``TransformerLM``'s training and validation steps on it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.models import layers as L
from theanompi_tpu.models.base import ModelConfig
from theanompi_tpu.models.transformer import TransformerLM
from theanompi_tpu.parallel.mesh import data_mesh

TOKENS, D, VOCAB = 90, 16, 50     # 50 is no multiple of 128; 90 = 2*3*3*5


def _problem(dtype=jnp.float32):
    """``(h, W (d, vocab), bias, labels)``: a third of the labels wrong,
    so that the error rate is neither 0 nor 1."""
    key = jax.random.key(0)
    h = jax.random.normal(jax.random.fold_in(key, 1), (TOKENS, D))
    w = jax.random.normal(jax.random.fold_in(key, 2), (D, VOCAB)) / 4
    b = jax.random.normal(jax.random.fold_in(key, 3), (VOCAB,))
    labels = jnp.argmax(h @ w + b, -1).at[::3].set(7)
    return h.astype(dtype), w, b, labels


def _as_laid_out(w, vocab_axis):
    return w.T if vocab_axis == 0 else w


def _whole(h, w, b, labels, vocab_axis, smoothing):
    """The composed form over whole logits, float32."""
    w = _as_laid_out(w, vocab_axis)      # back to (d, vocab)
    logits = h.astype(jnp.float32) @ w + (0.0 if b is None else b)
    return (L.softmax_cross_entropy(logits, labels, smoothing),
            L.error_rate(logits, labels))


def _both(h, w, b, labels, vocab_axis, smoothing, block):
    """``(values, gradients)`` of the blocked and of the whole form; the
    loss is scaled so that the backward's cotangent is not 1."""
    blocked = lambda h, w, b: L.blocked_softmax_cross_entropy(  # noqa: E731
        h, w, b, labels, vocab_axis=vocab_axis, label_smoothing=smoothing,
        block_tokens=block)
    whole = lambda h, w, b: _whole(h, w, b, labels, vocab_axis,  # noqa: E731
                                   smoothing)
    out = []
    for fn in (blocked, whole):
        values = fn(h, w, b)
        grads = jax.grad(lambda *a: 3.0 * fn(*a)[0], argnums=(0, 1, 2))(
            h, w, b)
        out.append((values, grads))
    return out


# 90 tokens: one block; several (the largest divisor of 90 under 40 is
# 30); 7 is no divisor, so blocks of 6 (prime-ish: the search steps down)
@pytest.mark.parametrize("block", [2048, 40, 7])
@pytest.mark.parametrize("vocab_axis", [0, 1])
@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_blocked_loss_is_the_whole_loss(block, vocab_axis, smoothing):
    """(a), (b): loss, error and the gradients of ``h``, the weight and
    the bias equal ``jax.grad`` of ``softmax_cross_entropy(h @ W + b)``
    and ``error_rate`` to 1e-5, float32."""
    h, w, b, labels = _problem()
    w = _as_laid_out(w, vocab_axis)
    (got, got_grads), (want, want_grads) = _both(
        h, w, b, labels, vocab_axis, smoothing, block)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    np.testing.assert_allclose(got[1], want[1])
    assert 0.2 < float(got[1]) < 0.5
    for a, e in zip(got_grads, want_grads):
        assert a.shape == e.shape and a.dtype == e.dtype
        np.testing.assert_allclose(a, e, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("vocab_axis", [0, 1])
def test_blocked_loss_without_a_bias(vocab_axis):
    """The tied head's case: no bias, no bias gradient."""
    h, w, _, labels = _problem()
    w = _as_laid_out(w, vocab_axis)
    blocked = lambda h, w: L.blocked_softmax_cross_entropy(  # noqa: E731
        h, w, None, labels, vocab_axis=vocab_axis, block_tokens=30)
    loss, err = blocked(h, w)
    want_loss, want_err = _whole(h, w, None, labels, vocab_axis, 0.0)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    np.testing.assert_allclose(err, want_err)
    got = jax.grad(lambda *a: blocked(*a)[0], argnums=(0, 1))(h, w)
    want = jax.grad(lambda *a: _whole(*a, None, labels, vocab_axis, 0.0)[0],
                    argnums=(0, 1))(h, w)
    for a, e in zip(got, want):
        np.testing.assert_allclose(a, e, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("vocab_axis", [0, 1])
@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_bf16_hidden_state_float32_masters(vocab_axis, smoothing):
    """(c): a bf16 ``h`` against float32 masters: ``d_h`` comes back in
    bf16, the weight's and the bias's gradients in float32, each within
    bf16's error (2**-8 a value, relative L2) of the float32 form on the
    same values."""
    h, w, b, labels = _problem(jnp.bfloat16)
    w = _as_laid_out(w, vocab_axis)
    (got, got_grads), (want, want_grads) = _both(
        h, w, b, labels, vocab_axis, smoothing, 30)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-2)
    assert [g.dtype for g in got_grads] == [jnp.bfloat16, jnp.float32,
                                            jnp.float32]
    # the composed form differentiates float32 copies of the same values
    want_grads = jax.grad(
        lambda *a: 3.0 * _whole(*a, labels, vocab_axis, smoothing)[0],
        argnums=(0, 1, 2))(h.astype(jnp.float32), w, b)
    for a, e in zip(got_grads, want_grads):
        a, e = np.asarray(a, np.float32), np.asarray(e, np.float32)
        assert np.linalg.norm(a - e) / np.linalg.norm(e) < 2e-2


@pytest.mark.parametrize("with_grad", [False, True])
def test_the_error_takes_the_first_index_on_ties(with_grad):
    """``error_rate``'s meaning: argmax picks the first of equal maxima,
    so a label on a later one is a miss; in the scan's forward-only and
    forward-and-gradient bodies alike."""
    h = jnp.zeros((6, D))
    w = jnp.zeros((D, VOCAB))
    b = jnp.zeros((VOCAB,)).at[jnp.array([3, 9])].set(2.0)
    labels = jnp.array([3, 9, 3, 9, 0, 3])
    fn = lambda h: L.blocked_softmax_cross_entropy(  # noqa: E731
        h, w, b, labels, vocab_axis=1, block_tokens=3)
    err = jax.value_and_grad(fn, has_aux=True)(h)[0][1] if with_grad \
        else fn(h)[1]
    np.testing.assert_allclose(err, L.error_rate(h @ w + b, labels))
    np.testing.assert_allclose(err, 0.5)


def test_a_weight_that_does_not_contract_is_refused():
    h, w, b, labels = _problem()
    with pytest.raises(ValueError, match="does not contract"):
        L.blocked_softmax_cross_entropy(h, w, b, labels, vocab_axis=0)


def test_the_plan_is_said_once_a_shape(caplog):
    """The mechanism's counter is its plan, in the log, once a shape."""
    h, w, b, labels = _problem()
    L._log_block_plan.cache_clear()
    with caplog.at_level("INFO", logger=L.__name__):
        for _ in range(2):
            L.blocked_softmax_cross_entropy(h, w, b, labels, vocab_axis=1,
                                            block_tokens=40)
    said = [r.getMessage() for r in caplog.records]
    assert said == ["loss in 3 blocks of 30 tokens x 50"]


# -- TransformerLM on it -----------------------------------------------

LM = dict(vocab=37, seq_len=24, n_layers=2, d_model=32, n_heads=4)


def _lm(**config):
    cfg = ModelConfig(batch_size=4, n_epochs=1, print_freq=1000, seed=3,
                      **config)
    return TransformerLM(config=cfg, mesh=data_mesh(1, jax.devices()[:1]),
                         verbose=False, **LM)


def _batch():
    key = jax.random.key(5)
    tokens = jax.random.randint(key, (4, LM["seq_len"]), 0, LM["vocab"])
    return tokens, jnp.roll(tokens, -1, axis=1)


def _composed_loss_fn(model, params, batch, smoothing):
    """``TransformerLM.loss_fn`` as it was before the blocked loss: the
    module's own float32 logits, then loss and error over all of them."""
    tokens, targets = batch
    logits = model.module.apply({"params": params}, tokens, train=True,
                                seq_axis=None)
    v = logits.shape[-1]
    return (L.softmax_cross_entropy(logits.reshape(-1, v),
                                    targets.reshape(-1), smoothing),
            L.error_rate(logits.reshape(-1, v), targets.reshape(-1)))


@pytest.fixture(scope="module")
def lm():
    return _lm()


@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_transformer_lm_loss_fn_is_the_composed_form(lm, smoothing):
    """(d): loss, error and the gradient of EVERY leaf equal the old
    ``loss_fn`` on the module's own logits, float32 to 1e-5."""
    model = lm if not smoothing else _lm(label_smoothing=smoothing)
    params, batch = model.state.params, _batch()
    rng = jax.random.key(0)

    def new(p):
        loss, (_, metrics) = model.loss_fn(p, {}, batch, rng)
        return loss, metrics["error"]

    old = lambda p: _composed_loss_fn(model, p, batch, smoothing)  # noqa: E731
    (loss, err), grads = jax.jit(jax.value_and_grad(new, has_aux=True))(params)
    (want, want_err), want_grads = jax.jit(
        jax.value_and_grad(old, has_aux=True))(params)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    np.testing.assert_allclose(err, want_err)
    flat, want_flat = (jax.tree_util.tree_leaves_with_path(g)
                       for g in (grads, want_grads))
    assert len(flat) == len(want_flat) > 20
    for (path, a), (_, e) in zip(flat, want_flat):
        np.testing.assert_allclose(a, e, rtol=1e-5, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))


def test_transformer_lm_eval_reports_the_training_loss(lm):
    """(d): ``eval_fn``'s loss is ``loss_fn``'s (no smoothing), and a
    smoothed model's validation loss is the unsmoothed one."""
    params, batch = lm.state.params, _batch()
    loss = lm.loss_fn(params, {}, batch, jax.random.key(0))[0]
    metrics = lm.eval_fn(params, {}, batch)
    np.testing.assert_allclose(metrics["loss"], loss, rtol=1e-6)
    assert 0.0 <= float(metrics["error"]) <= 1.0
    smoothed = _lm(label_smoothing=0.1)
    np.testing.assert_allclose(
        smoothed.eval_fn(params, {}, batch)["loss"], loss, rtol=1e-6)
    assert float(smoothed.loss_fn(params, {}, batch,
                                  jax.random.key(0))[0]) != float(loss)


def _shapes(jaxpr):
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield getattr(var.aval, "shape", ())
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _shapes(sub)


@pytest.mark.parametrize("step", ["train", "eval"])
def test_transformer_lm_steps_hold_no_whole_logits(lm, step):
    """(e): no array of tokens x vocab elements anywhere in the training
    step's (loss and every gradient) or the validation step's program;
    a block's logits are there.  3 x 1024 tokens pass as 2 blocks of
    1536 (traced only; the position table has 2048 rows)."""
    params, vocab = lm.state.params, LM["vocab"]
    batch = (jnp.zeros((3, 1024), jnp.int32),) * 2
    if step == "train":
        fn = jax.grad(lambda p: lm.loss_fn(p, {}, batch, jax.random.key(0))[0])
    else:
        fn = lambda p: lm.eval_fn(p, {}, batch)  # noqa: E731
    seen = set(_shapes(jax.make_jaxpr(fn)(params).jaxpr))
    whole = {s for s in seen if vocab in s
             and np.prod(s, dtype=np.int64) >= 3072 * vocab}
    assert (1536, vocab) in seen and not whole, whole


def test_transformer_lm_tree_and_default_exit_are_the_parents(lm):
    """(f): the parameter tree name for name, float32 logits by default,
    and the hidden exit's ``h`` is what the head is applied to."""
    params = lm.state.params
    assert sorted(params) == ["Block_0", "Block_1", "Dense_0", "Embed_0",
                              "LayerNorm_0", "pos_emb"]
    assert sorted(params["Dense_0"]) == ["bias", "kernel"]
    assert params["Dense_0"]["kernel"].shape == (LM["d_model"], LM["vocab"])
    assert sorted(params["Block_0"]) == [
        "LayerNorm_0", "LayerNorm_1", "k_proj", "mlp_down", "mlp_up",
        "o_proj", "q_proj", "v_proj"]
    tokens, _ = _batch()
    logits = lm.module.apply({"params": params}, tokens)
    assert logits.dtype == jnp.float32
    assert logits.shape == (4, LM["seq_len"], LM["vocab"])
    h = lm.module.apply({"params": params}, tokens, hidden=True)
    assert h.shape == (4, LM["seq_len"], LM["d_model"])
    head = params["Dense_0"]
    np.testing.assert_allclose(h @ head["kernel"] + head["bias"], logits,
                               rtol=1e-5, atol=1e-6)


# -- per-token weights (a looped model's exit distribution) ----------------


def _weights():
    """Positive, summing to 1 over the tokens, far from uniform."""
    raw = jax.random.uniform(jax.random.key(9), (TOKENS,)) ** 3
    return raw / raw.sum()


def _weighted_whole(h, w, b, labels, weights, vocab_axis, smoothing):
    """``sum_i w_i l_i`` over whole logits, float32, by autodiff."""
    w = _as_laid_out(w, vocab_axis)
    logits = h.astype(jnp.float32) @ w + (0.0 if b is None else b)
    logp = jax.nn.log_softmax(logits)
    token = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    if smoothing:
        token = (1 - smoothing) * token - smoothing * jnp.mean(logp, axis=-1)
    return jnp.sum(weights * token), token


@pytest.mark.parametrize("block", [2048, 40])
@pytest.mark.parametrize("vocab_axis", [0, 1])
@pytest.mark.parametrize("with_bias", [False, True])
@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_weighted_blocked_loss_is_the_whole_weighted_loss(
        block, vocab_axis, with_bias, smoothing):
    """With ``weights``: the weighted sum, every token's own loss and
    miss, and the gradients of ``h``, the weight, the bias and the
    WEIGHTS (the tokens' losses) equal the composed form's to 1e-5."""
    h, w, b, labels = _problem()
    w = _as_laid_out(w, vocab_axis)
    b = b if with_bias else None
    weights = _weights()
    blocked = lambda h, w, b, q: L.blocked_softmax_cross_entropy(  # noqa: E731
        h, w, b, labels, vocab_axis=vocab_axis, weights=q,
        label_smoothing=smoothing, block_tokens=block)
    whole = lambda h, w, b, q: _weighted_whole(  # noqa: E731
        h, w, b, labels, q, vocab_axis, smoothing)
    loss, token_loss, token_miss = blocked(h, w, b, weights)
    want_loss, want_token = whole(h, w, b, weights)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    np.testing.assert_allclose(token_loss, want_token, rtol=1e-5, atol=1e-6)
    logits = h @ _as_laid_out(w, vocab_axis) + (0.0 if b is None else b)
    np.testing.assert_array_equal(
        token_miss, (jnp.argmax(logits, -1) != labels).astype(jnp.float32))
    argnums = (0, 1, 2, 3) if with_bias else (0, 1, 3)
    got = jax.grad(lambda *a: 3.0 * blocked(*a)[0], argnums=argnums)(
        h, w, b, weights)
    want = jax.grad(lambda *a: 3.0 * whole(*a)[0], argnums=argnums)(
        h, w, b, weights)
    for a, e in zip(got, want):
        assert a.shape == e.shape and a.dtype == e.dtype
        np.testing.assert_allclose(a, e, rtol=1e-5, atol=1e-6)
    # the weights' gradient IS the tokens' losses (times the cotangent)
    np.testing.assert_allclose(got[-1], 3.0 * token_loss, rtol=1e-6)


def test_uniform_weights_give_the_plain_mean():
    """``weights = 1 / n`` is the unweighted loss, and the tokens' own
    values carry no gradient of their own."""
    h, w, b, labels = _problem()
    uniform = jnp.full((TOKENS,), 1.0 / TOKENS)
    plain = L.blocked_softmax_cross_entropy(h, w, b, labels, vocab_axis=1,
                                            block_tokens=30)
    loss, token_loss, token_miss = L.blocked_softmax_cross_entropy(
        h, w, b, labels, vocab_axis=1, weights=uniform, block_tokens=30)
    np.testing.assert_allclose(loss, plain[0], rtol=1e-6)
    np.testing.assert_allclose(token_miss.mean(), plain[1])
    through_tokens = jax.grad(lambda h: L.blocked_softmax_cross_entropy(
        h, w, b, labels, vocab_axis=1, weights=uniform,
        block_tokens=30)[1].sum())(h)
    assert not np.asarray(through_tokens).any()
    with pytest.raises(ValueError, match="one weight a token"):
        L.blocked_softmax_cross_entropy(h, w, b, labels, vocab_axis=1,
                                        weights=uniform[:-1])


def test_weighted_loss_in_bf16_keeps_float32_weights_and_masters():
    h, w, b, labels = _problem(jnp.bfloat16)
    weights = _weights()
    grads = jax.grad(lambda *a: L.blocked_softmax_cross_entropy(
        a[0], a[1], None, labels, vocab_axis=1, weights=a[2],
        block_tokens=30)[0], argnums=(0, 1, 2))(h, w, weights)
    assert [g.dtype for g in grads] == [jnp.bfloat16, jnp.float32,
                                        jnp.float32]
    want = jax.grad(lambda *a: _weighted_whole(
        a[0], a[1], None, labels, a[2], 1, 0.0)[0], argnums=(0, 1, 2))(
        h.astype(jnp.float32), w, weights)
    for a, e in zip(grads, want):
        err = (jnp.linalg.norm(a.astype(jnp.float32) - e)
               / jnp.linalg.norm(e))
        assert float(err) < 2e-2


#: sha256 (first 16 hex digits) of ``str(jax.make_jaxpr(...))`` of loss
#: and gradients WITHOUT weights, taken at PR 31's commit (ddd7777, the
#: parent of the PR that brought ``weights``) by the function below,
#: under the installation the verify skill states: the three accepted LM
#: cells run this program, and an optional argument of another model
#: must not move it.  A change of JAX re-takes them from that commit.
_UNWEIGHTED_PROGRAM = {
    (0, False): "fdd19bec0a76bcf3", (0, True): "b8e095232e8a2878",
    (1, False): "ee83c1b596e18ad2", (1, True): "1a561a2617c2d9b6"}


@pytest.mark.parametrize("vocab_axis,with_bias", sorted(_UNWEIGHTED_PROGRAM))
def test_without_weights_the_program_is_the_parents(vocab_axis, with_bias):
    """``weights=None`` is a static branch: the jaxpr of the loss and
    its gradients (bf16 ``h``, float32 masters, four blocks) is, to the
    letter, what PR 31 traced for ``ZayaLM``'s tied table (axis 0, no
    bias) and ``TransformerLM``'s kernel and bias (axis 1)."""
    import hashlib

    n, d, v = 64, 16, 40
    h = jnp.zeros((n, d), jnp.bfloat16)
    w = jnp.zeros((v, d) if vocab_axis == 0 else (d, v), jnp.float32)
    b = jnp.zeros((v,), jnp.float32) if with_bias else None
    y = jnp.zeros((n,), jnp.int32)

    def f(h, w, b):
        return L.blocked_softmax_cross_entropy(
            h, w, b, y, vocab_axis=vocab_axis, label_smoothing=0.0,
            block_tokens=16)

    text = str(jax.make_jaxpr(jax.value_and_grad(
        f, argnums=(0, 1, 2) if with_bias else (0, 1), has_aux=True))(
        h, w, b))
    assert (hashlib.sha256(text.encode()).hexdigest()[:16]
            == _UNWEIGHTED_PROGRAM[vocab_axis, with_bias])
