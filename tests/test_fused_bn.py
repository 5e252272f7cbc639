"""ops/fused_bn + layers.BatchNormAct/BiasAct: the fused
scale-bias(-residual)-ReLU epilogue (ISSUE 3 tentpole), oracle-tested
in interpret mode against the unfused XLA reference path — forward AND
gradient — so correctness is provable without a chip.

Three layers of contract:
- kernel vs jnp fallback (scale_bias_act impl='pallas' vs 'xla');
- BatchNormAct impl='xla' BIT-IDENTICAL to flax nn.BatchNorm (+relu /
  +residual-add) including running-stat updates — the default path is
  numerically unchanged by this refactor;
- the model seam: ResNet/VGG/GoogLeNet built with
  ModelConfig.bn_act_impl='pallas' match their 'xla' builds end to end
  (same params, tolerance for the folded-affine association).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.models import layers as L
from theanompi_tpu.ops.fused_bn import scale_bias_act


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(key), shape, dtype)


class TestScaleBiasActKernel:
    @pytest.mark.parametrize("shape,dtype", [
        ((2, 7, 5, 16), jnp.float32),       # ragged rows vs tile
        ((3, 4, 4, 130), jnp.float32),      # C not lane-aligned
        ((2, 8, 8, 32), jnp.bfloat16),      # compute dtype of the zoo
    ])
    @pytest.mark.parametrize("with_res", [False, True])
    def test_fwd_and_grad_match_xla(self, shape, dtype, with_res):
        c = shape[-1]
        x = _rand(0, shape, dtype)
        s = _rand(1, (c,))
        b = _rand(2, (c,))
        res = _rand(3, shape, dtype) if with_res else None
        bf16 = dtype == jnp.bfloat16
        ref = scale_bias_act(x, s, b, res, act="relu", impl="xla")
        got = scale_bias_act(x, s, b, res, act="relu", impl="pallas")
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            rtol=2e-2 if bf16 else 1e-6, atol=1e-6)

        def loss(impl):
            def f(*args):
                y = scale_bias_act(args[0], args[1], args[2],
                                   args[3] if with_res else None,
                                   act="relu", impl=impl)
                return (y.astype(jnp.float32) ** 2).sum()
            return f

        args = (x, s, b) + ((res,) if with_res else ())
        nums = tuple(range(len(args)))
        gr = jax.grad(loss("xla"), argnums=nums)(*args)
        gp = jax.grad(loss("pallas"), argnums=nums)(*args)
        for a, g in zip(gr, gp):
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(a, np.float32),
                rtol=2e-2 if bf16 else 1e-5,
                atol=1e-3 if bf16 else 1e-5)

    def test_act_none_is_affine(self):
        x = _rand(5, (2, 6, 6, 24))
        y = scale_bias_act(x, jnp.ones(24), jnp.zeros(24), act=None,
                           impl="pallas")
        np.testing.assert_allclose(np.asarray(y), np.asarray(x),
                                   rtol=1e-6)

    def test_relu_grad_zero_at_negative(self):
        # the mask must be computed from z = x*s+b, not from x
        x = jnp.full((1, 1, 1, 8), 2.0)
        s = jnp.full((8,), -1.0)
        b = jnp.zeros(8)
        for impl in ("xla", "pallas"):
            g = jax.grad(lambda x: scale_bias_act(
                x, s, b, act="relu", impl=impl).sum())(x)
            np.testing.assert_array_equal(np.asarray(g),
                                          np.zeros_like(np.asarray(g)))

    def test_jit_composes(self):
        x = _rand(6, (2, 8, 8, 16))
        s, b = _rand(7, (16,)), _rand(8, (16,))
        np.testing.assert_allclose(
            np.asarray(jax.jit(lambda x: scale_bias_act(
                x, s, b, act="relu", impl="pallas"))(x)),
            np.asarray(scale_bias_act(x, s, b, act="relu", impl="xla")),
            rtol=1e-6, atol=1e-6)

    def test_validation(self):
        x = _rand(9, (2, 4, 4, 8))
        with pytest.raises(ValueError, match="unknown act"):
            scale_bias_act(x, jnp.ones(8), jnp.zeros(8), act="gelu")
        with pytest.raises(ValueError, match="channel vectors"):
            scale_bias_act(x, jnp.ones(4), jnp.zeros(8))
        with pytest.raises(ValueError, match="residual"):
            scale_bias_act(x, jnp.ones(8), jnp.zeros(8),
                           residual=jnp.zeros((2, 4, 4, 4)))
        with pytest.raises(ValueError, match="unknown impl"):
            scale_bias_act(x, jnp.ones(8), jnp.zeros(8), impl="cudnn")


class _FlaxRef(nn.Module):
    """The pre-seam composition: nn.BatchNorm -> (+res) -> relu."""

    dtype: jnp.dtype = jnp.float32
    act: bool = True

    @nn.compact
    def __call__(self, x, residual=None, train=True):
        y = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                         epsilon=1e-5, dtype=self.dtype)(x)
        if residual is not None:
            y = y + residual
        return nn.relu(y) if self.act else y


class _ActMod(nn.Module):
    dtype: jnp.dtype = jnp.float32
    act: str | None = "relu"
    impl: str = "xla"

    @nn.compact
    def __call__(self, x, residual=None, train=True):
        # name pinned exactly like the models do, so variables from
        # the _FlaxRef module load unchanged
        return L.BatchNormAct(use_running_average=not train,
                              momentum=0.9, epsilon=1e-5,
                              dtype=self.dtype, act=self.act,
                              impl=self.impl,
                              name="BatchNorm_0")(x, residual=residual)


class TestBatchNormAct:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("with_res", [False, True])
    def test_xla_impl_bit_identical_to_flax(self, dtype, with_res):
        """The refactor's default path must not move a single bit:
        same variables, same output, same running-stat update."""
        x = _rand(0, (4, 6, 6, 32), dtype)
        res = _rand(1, (4, 6, 6, 32), dtype) if with_res else None
        ref = _FlaxRef(dtype=dtype)
        v = ref.init({"params": jax.random.key(1)}, x, res)
        got_m = _ActMod(dtype=dtype, impl="xla")
        yr, sr = ref.apply(v, x, res, mutable=["batch_stats"])
        yg, sg = got_m.apply(v, x, res, mutable=["batch_stats"])
        np.testing.assert_array_equal(np.asarray(yr, np.float32),
                                      np.asarray(yg, np.float32))
        for a, b in zip(jax.tree.leaves(sr), jax.tree.leaves(sg)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # eval path (running averages) too
        ye = ref.apply(v, x, res, False)
        ge = got_m.apply(v, x, res, False)
        np.testing.assert_array_equal(np.asarray(ye, np.float32),
                                      np.asarray(ge, np.float32))

    @pytest.mark.parametrize("with_res", [False, True])
    def test_pallas_impl_matches_flax_fwd_and_grad(self, with_res):
        """Folded-affine kernel vs the full unfused BN — through the
        batch statistics, so the custom_vjp's dscale/dbias cotangents
        chain into the TRUE BN gradient (incl. d/dmean, d/dvar)."""
        x = _rand(2, (4, 6, 6, 32))
        res = _rand(3, (4, 6, 6, 32)) if with_res else None
        ref = _FlaxRef()
        v = ref.init({"params": jax.random.key(2)}, x, res)
        pal = _ActMod(impl="pallas")
        yr, sr = ref.apply(v, x, res, mutable=["batch_stats"])
        yp, sp = pal.apply(v, x, res, mutable=["batch_stats"])
        np.testing.assert_allclose(np.asarray(yp), np.asarray(yr),
                                   rtol=1e-5, atol=1e-5)
        for a, b in zip(jax.tree.leaves(sr), jax.tree.leaves(sp)):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=1e-6, atol=1e-6)

        def loss(mod):
            def f(params, x, res):
                y, _ = mod.apply(
                    {"params": params,
                     "batch_stats": v["batch_stats"]}, x, res,
                    mutable=["batch_stats"])
                return (y.astype(jnp.float32) ** 2).sum()
            return f

        gr = jax.grad(loss(ref), argnums=(0, 1, 2) if with_res
                      else (0, 1))(v["params"], x, res)
        gp = jax.grad(loss(pal), argnums=(0, 1, 2) if with_res
                      else (0, 1))(v["params"], x, res)
        for a, b in zip(jax.tree.leaves(gr), jax.tree.leaves(gp)):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=1e-4, atol=1e-4)

    def test_layers_batchnorm_wrapper_keeps_tree(self):
        """layers.BatchNorm (now BatchNormAct-backed) still stores its
        variables where the old nn.BatchNorm wrapper did."""
        x = _rand(4, (2, 4, 4, 8))
        v = L.BatchNorm().init({"params": jax.random.key(3)}, x)
        assert set(v["params"]["BatchNorm_0"]) == {"scale", "bias"}
        assert set(v["batch_stats"]["BatchNorm_0"]) == {"mean", "var"}


class TestBiasAct:
    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_matches_conv_bias_relu(self, impl):
        """conv(use_bias) + relu == conv(no bias) -> BiasAct, given
        the same kernel/bias values (the VGG/GoogLeNet seam)."""
        x = _rand(5, (2, 8, 8, 3))
        ref = nn.Sequential([nn.Conv(16, (3, 3)), nn.relu])
        vr = ref.init(jax.random.key(4), x)
        kernel = vr["params"]["layers_0"]["kernel"]
        bias = vr["params"]["layers_0"]["bias"]

        conv = nn.Conv(16, (3, 3), use_bias=False)
        ba = L.BiasAct(16, act="relu", impl=impl)
        vb = ba.init(jax.random.key(5), jnp.zeros((1, 1, 1, 16)))
        y_ref = ref.apply(vr, x)
        y_got = ba.apply(
            {"params": {"bias": bias}},
            conv.apply({"params": {"kernel": kernel}}, x))
        np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-6)
        assert set(vb["params"]) == {"bias"}


class TestEvalModeParity:
    """Serving (theanompi_tpu/serving) runs the EVAL path exclusively —
    ``use_running_average=True``, stats frozen at whatever training
    left them — which PR 3's oracles only pinned for the xla impl.
    These pin pallas == xla on that path, with NON-TRIVIAL running
    stats (the init zeros/ones would let a mean/var mix-up pass)."""

    def _stats_vars(self, c=32, key=20):
        return {
            "params": {"scale": _rand(key, (c,)),
                       "bias": _rand(key + 1, (c,))},
            "batch_stats": {"mean": _rand(key + 2, (c,)),
                            "var": jnp.abs(_rand(key + 3, (c,))) + 0.3},
        }

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("with_res", [False, True])
    def test_batchnormact_eval_pallas_matches_xla(self, dtype, with_res):
        x = _rand(21, (4, 6, 6, 32), dtype)
        res = _rand(22, (4, 6, 6, 32), dtype) if with_res else None
        v = self._stats_vars()
        outs = {}
        for impl in ("xla", "pallas"):
            mod = L.BatchNormAct(use_running_average=True, act="relu",
                                 impl=impl, dtype=dtype)
            # NOT mutable: the eval path must never touch the stats
            outs[impl] = mod.apply(v, x, residual=res)
        bf16 = dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(outs["pallas"], np.float32),
            np.asarray(outs["xla"], np.float32),
            # the folded affine (scale*rsqrt(var+eps) precomputed)
            # reassociates the bf16 rounding vs normalize-then-scale;
            # atol covers near-zero outputs at the relu knee, where
            # one bf16 ulp (~8e-3 at |y|~1) dwarfs any rtol
            rtol=2e-2 if bf16 else 1e-5, atol=1e-2 if bf16 else 1e-5)

    def test_batchnormact_eval_leaves_stats_untouched(self):
        """Both impls: applying with use_running_average=True and the
        stats collection MUTABLE still writes back the input values —
        a serving step can never drift the frozen statistics."""
        x = _rand(23, (4, 6, 6, 32))
        v = self._stats_vars()
        for impl in ("xla", "pallas"):
            mod = L.BatchNormAct(use_running_average=True, act="relu",
                                 impl=impl)
            _, upd = mod.apply(v, x, mutable=["batch_stats"])
            for key in ("mean", "var"):
                np.testing.assert_array_equal(
                    np.asarray(upd["batch_stats"][key]),
                    np.asarray(v["batch_stats"][key]))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_biasact_pallas_matches_xla_eval(self, dtype):
        """BiasAct has no train/eval split of its own, but serving
        runs it at the zoo's bf16 compute dtype — pin the impls
        against each other there too."""
        x = _rand(24, (2, 8, 8, 16), dtype)
        b = _rand(25, (16,))
        y_x = L.BiasAct(16, act="relu", impl="xla").apply(
            {"params": {"bias": b}}, x)
        y_p = L.BiasAct(16, act="relu", impl="pallas").apply(
            {"params": {"bias": b}}, x)
        bf16 = dtype == jnp.bfloat16
        # bf16 atol: the xla path adds in bf16, the kernel in f32
        # before the final cast — near-zero relu outputs differ by up
        # to one bf16 ulp
        np.testing.assert_allclose(
            np.asarray(y_p, np.float32), np.asarray(y_x, np.float32),
            rtol=2e-2 if bf16 else 1e-6, atol=1e-2 if bf16 else 1e-6)


class TestModelSeam:
    def test_resnet_pallas_equals_xla_fwd_and_grad(self):
        """ResNet built with bn_act_impl='pallas' matches the 'xla'
        build on the SAME params — the integration contract behind
        ModelConfig.bn_act_impl."""
        from theanompi_tpu.models.resnet50 import ResNet

        kw = dict(stage_sizes=(1, 1), width=8, n_classes=4,
                  dtype=jnp.float32)
        mx = ResNet(**kw, bn_act_impl="xla")
        mp = ResNet(**kw, bn_act_impl="pallas")
        x = _rand(6, (2, 16, 16, 3))

        def init(m):
            return jax.jit(lambda: m.init(
                {"params": jax.random.key(6)}, x, train=True))

        v = init(mx)()
        # identical variable trees: the impl knob moves no leaves
        assert (jax.tree_util.tree_structure(v) ==
                jax.tree_util.tree_structure(
                    jax.eval_shape(init(mp))))
        np.testing.assert_allclose(
            np.asarray(jax.jit(
                lambda v: mp.apply(v, x, train=False))(v)),
            np.asarray(jax.jit(
                lambda v: mx.apply(v, x, train=False))(v)),
            rtol=1e-5, atol=1e-5)

        def loss(m):
            def f(params):
                y, _ = m.apply(
                    {"params": params,
                     "batch_stats": v["batch_stats"]},
                    x, train=True, mutable=["batch_stats"])
                return (y.astype(jnp.float32) ** 2).sum()
            return f

        gx = jax.jit(jax.grad(loss(mx)))(v["params"])
        gp = jax.jit(jax.grad(loss(mp)))(v["params"])
        for a, b in zip(jax.tree.leaves(gx), jax.tree.leaves(gp)):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=5e-4, atol=5e-4)

    def test_vgg_googlenet_pallas_seam_builds_and_runs(self):
        """The BN-free zoo members accept the knob: a tiny VGG/
        GoogLeNet built with the fused bias-act epilogue runs fwd+bwd
        and produces finite values (their param tree legitimately
        differs between impls — layers.BiasAct docstring)."""
        from theanompi_tpu.models.googlenet import GoogLeNetCNN
        from theanompi_tpu.models.vgg16 import VGGCNN

        x = _rand(7, (2, 32, 32, 3))
        for mod in (VGGCNN(blocks=((1, 8), (1, 8)), n_classes=4,
                           act_impl="pallas"),
                    GoogLeNetCNN(n_classes=4, width_mult=0.05,
                                 act_impl="pallas")):
            # rbg keys: the values drawn are not what is asserted, and
            # 128 threefry draws compile four times as long on the CPU
            v = jax.jit(lambda: mod.init(
                {"params": jax.random.key(8, impl="rbg"),
                 "dropout": jax.random.key(9, impl="rbg")}, x,
                train=True))()

            def f(params):
                y = mod.apply({"params": params}, x, train=True,
                              rngs={"dropout": jax.random.key(0)})
                if isinstance(y, (tuple, list)):
                    y = y[0]
                return (y.astype(jnp.float32) ** 2).sum()

            val, grads = jax.jit(jax.value_and_grad(f))(v["params"])
            assert np.isfinite(float(val))
            assert all(np.isfinite(np.asarray(g)).all()
                       for g in jax.tree.leaves(grads))
            # the fused seam actually engaged: a BiasAct scope exists
            flat = jax.tree_util.tree_flatten_with_path(v["params"])[0]
            assert any("BiasAct" in jax.tree_util.keystr(p)
                       for p, _ in flat)

    def test_config_threads_bn_act_impl(self):
        """ModelConfig.bn_act_impl reaches every zoo builder."""
        from theanompi_tpu.data.cifar10 import Cifar10_data
        from theanompi_tpu.models.base import ModelConfig
        from theanompi_tpu.models.resnet50 import ResNet50

        class TinyResNet(ResNet50):
            stage_sizes = (1,)

            def build_data(self):
                return Cifar10_data(synthetic_n=16)

        cfg = ModelConfig(batch_size=2, bn_act_impl="pallas",
                          compute_dtype="float32")
        m = TinyResNet(config=cfg, verbose=False)
        assert m.module.bn_act_impl == "pallas"
