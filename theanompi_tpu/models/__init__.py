"""Model zoo registry (reference ``theanompi/models/`` — SURVEY.md §2.8).

Models import lazily by (modulepath, classname) through
``theanompi_tpu.rules.resolve_model_class``; this table is the
discovery surface for launchers and docs.
"""

MODEL_ZOO = {
    "cifar10": ("theanompi_tpu.models.cifar10", "Cifar10_model"),
    "alexnet": ("theanompi_tpu.models.alex_net", "AlexNet"),
    "googlenet": ("theanompi_tpu.models.googlenet", "GoogLeNet"),
    "vgg16": ("theanompi_tpu.models.vgg16", "VGG16"),
    "resnet50": ("theanompi_tpu.models.resnet50", "ResNet50"),
    "wgan": ("theanompi_tpu.models.wasserstein_gan", "Wasserstein_GAN"),
    # beyond reference parity: long-context sequence-parallel LM
    "transformer_lm": ("theanompi_tpu.models.transformer", "TransformerLM"),
    "transformer_lm_tp": ("theanompi_tpu.models.transformer",
                          "TransformerLM_TP"),
    "transformer_lm_pp": ("theanompi_tpu.models.transformer",
                          "TransformerLM_PP"),
    "transformer_lm_moe": ("theanompi_tpu.models.transformer",
                           "TransformerLM_MoE"),
    # a current block: compressed convolutional attention + a dropless
    # expert layer that is told which experts it holds (ZAYA1 family)
    "zaya_lm": ("theanompi_tpu.models.zaya", "ZayaLM"),
    # a looped LM: one stack of layers run several times over shared
    # weights, an exit gate and the head after every pass (Ouro family)
    "ouro_lm": ("theanompi_tpu.models.ouro", "OuroLM"),
    # a hybrid stack built from a pattern string: Mamba-2 state-space
    # layers, sigmoid top-k experts beside a shared expert, one GQA
    # layer in several (Nemotron-H family)
    "nemotron_h_lm": ("theanompi_tpu.models.nemotron_h", "NemotronHLM"),
    # Gated DeltaNet linear attention three layers in four, gated
    # attention at head 256, softmax top-10 of 512 experts beside a gated
    # shared expert (Qwen3-Next family)
    "qwen3_next_lm": ("theanompi_tpu.models.qwen3_next", "Qwen3NextLM"),
    # global attention without positions one layer in four beside RoPE
    # sliding-window attention, a router read before attention, softmax
    # top-6 of 64 ReGLU experts (SmallThinker family)
    "smallthinker_lm": ("theanompi_tpu.models.smallthinker",
                        "SmallThinkerLM"),
    # zoo variants (reference lasagne_model_zoo equivalents)
    "vgg19": ("theanompi_tpu.models.model_zoo", "VGG19"),
    "resnet101": ("theanompi_tpu.models.model_zoo", "ResNet101"),
    "resnet152": ("theanompi_tpu.models.model_zoo", "ResNet152"),
    # the modern large-batch recipe (LARS + warmup/cosine + s2d stem)
    "resnet50_large": ("theanompi_tpu.models.model_zoo",
                       "ResNet50_LargeBatch"),
}

__all__ = ["MODEL_ZOO"]
