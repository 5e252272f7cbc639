"""ResNet-50 — the north-star recipe (bundled recipe #4: 8-worker BSP
ImageNet; BASELINE.json configs[3], ≥2500 img/s on v5e-16).

Parity counterpart of the reference's ``theanompi/models/resnet50.py``
(SURVEY.md §2.8 — mount empty, no file:line): bottleneck ResNet-50
with batch norm, SGD+momentum, step LR decay.  TPU-native choices:
NHWC layout, bf16 compute on the MXU with fp32 master params
(``compute_dtype='bfloat16'``), BN statistics pmean-ed across the data
axis by the BSP step (parallel/bsp.py), and the whole fwd+bwd+psum+
update fused into one jitted SPMD program.
"""

from __future__ import annotations

from typing import Sequence

import flax.linen as nn
import jax.numpy as jnp

from theanompi_tpu.data.imagenet import ImageNet_data
from theanompi_tpu.models import layers as L
from theanompi_tpu.models.base import ModelConfig, TpuModel


class BottleneckBlock(nn.Module):
    """1x1 -> 3x3 -> 1x1 bottleneck with projection shortcut on
    stride/width change.  The final BN's scale is init to zero
    (standard residual-friendly init; keeps early training stable at
    large global batch).

    Every BN carries its epilogue (relu; the exit BN also the shortcut
    add) through ``layers.BatchNormAct`` so ``bn_act_impl='pallas'``
    runs each one as a single fused HBM stream — the loop-fusion slice
    of the MFU account.  Instance names pin flax's old auto-numbering
    (``BatchNorm_{i}`` in creation order), so the param tree is
    identical to the pre-seam module and independent of the impl knob.
    """

    features: int            # bottleneck width; output is 4x this
    strides: tuple[int, int] = (1, 1)
    dtype: jnp.dtype = jnp.float32
    #: named mesh axis to pmean BN stats over (cross-replica BN);
    #: None = per-shard stats (the reference's per-worker semantics)
    bn_axis: str | None = None
    #: BN+act epilogue impl (ModelConfig.bn_act_impl): 'xla' | 'pallas'
    bn_act_impl: str = "xla"

    @nn.compact
    def __call__(self, x, train: bool):
        bn_i = iter(range(4))
        norm = lambda act=None, scale_init=nn.initializers.ones: (  # noqa: E731
            L.BatchNormAct(
                use_running_average=not train, momentum=0.9, epsilon=1e-5,
                dtype=self.dtype, scale_init=scale_init,
                axis_name=self.bn_axis, act=act, impl=self.bn_act_impl,
                name=f"BatchNorm_{next(bn_i)}"))
        out_features = self.features * 4

        residual = x
        if residual.shape[-1] != out_features or self.strides != (1, 1):
            residual = L.Conv(out_features, (1, 1), strides=self.strides,
                              use_bias=False, dtype=self.dtype,
                              name="proj_conv")(residual)
            residual = norm()(residual)

        y = L.Conv(self.features, (1, 1), use_bias=False, dtype=self.dtype)(x)
        y = norm(act="relu")(y)
        y = L.Conv(self.features, (3, 3), strides=self.strides,
                   use_bias=False, dtype=self.dtype)(y)
        y = norm(act="relu")(y)
        y = L.Conv(out_features, (1, 1), use_bias=False, dtype=self.dtype)(y)
        # exit epilogue: relu(bn(y) + shortcut) in one fused stream
        return norm(act="relu", scale_init=nn.initializers.zeros)(
            y, residual=residual)


def space_to_depth(x, block: int = 2):
    """(N, H, W, C) -> (N, H/b, W/b, b*b*C) — pixel-block channels in
    (row-offset, col-offset, channel) order, matching
    ``s2d_stem_kernel_from_conv7``."""
    n, h, w, c = x.shape
    x = x.reshape(n, h // block, block, w // block, block, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(
        n, h // block, w // block, block * block * c)


def s2d_stem_kernel_from_conv7(w7):
    """Exact re-parameterization of a 7x7/stride-2 stem kernel as the
    4x4/stride-1 kernel over the 2x2 space-to-depth input: zero-pad
    the taps 7->8 at the leading edge (tap index p = original + 1, so
    p = 2q + a with block tap q and within-block offset a), then fold
    the offsets into the input-channel dim.  Used by the equivalence
    test; training from scratch just initializes the 4x4 kernel."""
    kh, kw, c, o = w7.shape
    assert (kh, kw) == (7, 7)
    w8 = jnp.zeros((8, 8, c, o), w7.dtype).at[1:, 1:].set(w7)
    w8 = w8.reshape(4, 2, 4, 2, c, o)           # (q, a, p, b, c, o)
    return w8.transpose(0, 2, 1, 3, 4, 5).reshape(4, 4, 4 * c, o)


class ResNet(nn.Module):
    """Generic bottleneck ResNet (50 = (3,4,6,3)).

    ``stem='s2d'`` replaces the 7x7/stride-2 stem conv with the exact
    4x4/stride-1 conv over a 2x2 space-to-depth input (12 channels
    instead of 3): the C=3 conv is the one shape in the network the
    MXU cannot pack lanes for, and this is the standard TPU fix for
    it.  Identical function class (see s2d_stem_kernel_from_conv7 +
    tests); opt-in until on-chip profiling decides the default.
    """

    stage_sizes: Sequence[int] = (3, 4, 6, 3)
    width: int = 64
    n_classes: int = 1000
    dtype: jnp.dtype = jnp.float32
    stem: str = "conv7"          # 'conv7' | 's2d'
    #: cross-replica BN axis (ModelConfig.sync_bn); None = per-shard
    bn_axis: str | None = None
    #: BN+activation epilogue impl (ModelConfig.bn_act_impl): 'xla'
    #: (unfused reference path) or 'pallas' (ops/fused_bn.py)
    bn_act_impl: str = "xla"

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.astype(self.dtype)
        if self.stem == "s2d":
            if x.shape[1] % 2 or x.shape[2] % 2:
                raise ValueError("stem='s2d' needs even spatial dims, "
                                 f"got {x.shape}")
            x = space_to_depth(x, 2)
            # block rows i-2..i+1 of the s2d image -> pad (2, 1)
            x = L.Conv(self.width, (4, 4), strides=(1, 1),
                       padding=[(2, 1), (2, 1)], use_bias=False,
                       dtype=self.dtype, name="stem_conv")(x)
        elif self.stem == "conv7":
            x = L.Conv(self.width, (7, 7), strides=(2, 2),
                       padding=[(3, 3), (3, 3)], use_bias=False,
                       dtype=self.dtype, name="stem_conv")(x)
        else:
            raise ValueError(f"unknown stem {self.stem!r}")
        x = L.BatchNormAct(use_running_average=not train, momentum=0.9,
                           epsilon=1e-5, dtype=self.dtype, name="stem_bn",
                           axis_name=self.bn_axis,
                           impl=self.bn_act_impl)(x)
        # relu AFTER the pool: max-pooling commutes with relu (max of
        # relu == relu of max, -inf pool padding never wins, and the
        # backward argmax selection is identical), so this is
        # bit-identical to the textbook relu-then-pool stem while
        # running the relu on the 4x smaller pooled tensor.  The r3
        # on-chip xplane account charged 0.62 ms/step — 1.3% of the
        # step — to the pre-pool relu on [b,112,112,64] as a separate
        # HBM-bound loop fusion (artifacts/fusion_deepdive.json
        # 'fwd/ResNet/max'); post-pool it fuses into the maxpool
        # output fusion's quarter-size stream.
        x = nn.max_pool(x, (3, 3), (2, 2), padding=[(1, 1), (1, 1)])
        x = nn.relu(x)
        for stage, n_blocks in enumerate(self.stage_sizes):
            for block in range(n_blocks):
                strides = (2, 2) if stage > 0 and block == 0 else (1, 1)
                x = BottleneckBlock(self.width * (2 ** stage), strides,
                                    self.dtype, self.bn_axis,
                                    self.bn_act_impl)(x, train)
        x = L.global_avg_pool(x)
        x = L.Dense(self.n_classes, kernel_init=L.xavier_init())(x)
        return x.astype(jnp.float32)


class ResNet50(TpuModel):
    name = "resnet50"
    uses_batchnorm = True        # enables the small-shard BN warning
    stage_sizes = (3, 4, 6, 3)   # zoo variants (101/152) override this
    #: 2xMAC FLOPs — ~4.1 GMAC fwd @224 = 8.2 GF (tools/conv_ladder.py
    #: enumerates it), x ~3 for fwd+bwd.  Round-2 used the MAC count
    #: (12.3e9) here while the chip's nominal 197 TF/s and the measured
    #: matmul rates are true FLOPs, understating every MFU figure 2x.
    train_flops_per_sample = 24.6e9

    @classmethod
    def default_config(cls) -> ModelConfig:
        # The reference-era 90-epoch step recipe (SURVEY.md §5.6), with
        # linear LR scaling over workers for the 8-worker BSP config.
        return ModelConfig(
            batch_size=128,
            n_epochs=90,
            learning_rate=0.05,     # per 128-batch; scaled by n_workers
            momentum=0.9,
            weight_decay=1e-4,
            lr_schedule="step",
            lr_decay_epochs=(30, 60, 80),
            lr_decay_factor=0.1,
            lr_scale_with_workers="linear",
            compute_dtype="bfloat16",
            track_top5=True,
            print_freq=20,
        )

    def build_module(self) -> nn.Module:
        return ResNet(stage_sizes=self.stage_sizes,
                      n_classes=self.data.n_classes,
                      dtype=self._compute_dtype(),
                      stem=self.config.resnet_stem,
                      bn_axis=self._bn_axis(),
                      bn_act_impl=self.config.bn_act_impl)

    def build_data(self):
        return ImageNet_data(data_dir=self.config.data_dir,
                             seed=self.config.seed,
                             augment_on_device=self.config.augment_on_device)


# reference-style alias (upstream files exposed Model-suffixed names too)
ResNet50_model = ResNet50
