"""Extra model-zoo variants — parity counterpart of the reference's
``theanompi/models/lasagne_model_zoo/`` (SURVEY.md §2.8 — mount empty,
no file:line), which carried Lasagne-based VGG and ResNet variants
alongside the first-class models.

Here the variants are thin reconfigurations of the first-class flax
networks (the TPU-native analogue of "another model-zoo frontend over
the same layers"): VGG19 (configuration E) and deeper bottleneck
ResNets (101/152).  Each keeps the full model contract, so every rule
and launcher drives them like any zoo member.
"""

from __future__ import annotations

from theanompi_tpu.models.resnet50 import ResNet50
from theanompi_tpu.models.vgg16 import VGG16

# configuration E: (n_convs, features) per block — 16 convs + 3 FC
VGG19_BLOCKS = ((2, 64), (2, 128), (4, 256), (4, 512), (4, 512))


class VGG19(VGG16):
    name = "vgg19"
    blocks = VGG19_BLOCKS
    train_flops_per_sample = 117.6e9  # 2xMAC: 19.6 GMAC fwd @224 x2 x ~3


class ResNet101(ResNet50):
    name = "resnet101"
    stage_sizes = (3, 4, 23, 3)
    train_flops_per_sample = 46.8e9   # 2xMAC: 7.8 GMAC fwd @224 x2 x ~3


class ResNet152(ResNet101):
    name = "resnet152"
    stage_sizes = (3, 8, 36, 3)
    train_flops_per_sample = 69.0e9   # 2xMAC: 11.5 GMAC fwd @224 x2 x ~3


class ResNet50_LargeBatch(ResNet50):
    """The modern large-batch TPU recipe over the same network: LARS +
    linear warmup + cosine decay (Goyal-style ramp, You-style layerwise
    trust ratios), per-chip batch 128 (measured optimum — the round-3
    on-chip ladder ran b/chip {128,256} x k {1,4,8} and 256 lost at
    every k; see default_config below), bf16 compute, space-to-depth
    stem.  The reference era scaled its SGD LR linearly with workers
    (SURVEY.md §2.7 scale_lr); this is the recipe that replaced it when
    global batches outgrew plain momentum."""

    name = "resnet50_large"

    @classmethod
    def default_config(cls):
        from theanompi_tpu.models.base import ModelConfig

        return ModelConfig(
            # per-chip batch 128, measured: the round-3 on-chip ladder
            # (older stack, JAX 0.4.x — BASELINE.md table) ran
            # b/chip in {128,256} x k in {1,4,8} and b=256 LOST at
            # every k (-2.45% to -5.08% img/s/chip) — N<=256 lane-bound
            # conv GEMMs don't gain from doubling M while the 2x
            # activations pressure HBM.  The published LARS recipes'
            # 8k-32k GLOBAL batch comes from the shard count (128/chip
            # x 64+ chips), not from a big per-chip batch, so the
            # large-batch geometry is preserved where it matters.
            batch_size=128,
            # per-shard master LR; sqrt scaling with the data-shard
            # count keeps the LARS LR in its working range at every
            # mesh size (0.7 on 1 chip -> ~5.6 at 64 shards / 8k
            # global batch, the regime the published LARS recipes
            # tune for)
            learning_rate=0.7,
            lr_scale_with_workers="sqrt",
            n_epochs=90,
            optimizer="lars",
            momentum=0.9,
            weight_decay=1e-4,
            lr_schedule="cosine",
            warmup_epochs=5,
            label_smoothing=0.1,
            compute_dtype="bfloat16",
            resnet_stem="s2d",
            track_top5=True,
            print_freq=20,
        )
