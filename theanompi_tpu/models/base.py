"""Model contract + generic training machinery.

The reference consumed a duck-typed model contract from every rule
(``self.params``, ``self.data``, ``batch_size``, ``n_epochs``;
``compile_iter_fns(sync_type)``, ``train_iter(count, recorder)``,
``val_iter(count, recorder)``, ``adjust_hyperp(epoch)``,
``save``/``load``, ``cleanup`` — reference ``theanompi/models/*.py``,
SURVEY.md §2.8; mount empty, no file:line).  This module keeps that
contract — it is the API-parity surface the rules and launchers see —
but implements it once, TPU-natively:

* ``compile_iter_fns`` builds ONE jitted SPMD step (forward + backward
  + psum exchange + update fused; XLA overlaps the ICI collectives
  with backprop) instead of compiling per-worker Theano functions and
  pairing them with a post-hoc exchanger.
* ``train_iter`` consumes mesh-sharded device batches from a
  double-buffered prefetcher and dispatches asynchronously; metrics
  are fetched in windows (every ``print_freq`` iters) so the host
  never serializes the device pipeline.
* The reference's 'comm' recorder section is structurally zero here —
  exchange is fused into 'calc' by design; the recorder keeps the
  column for output parity.

Subclasses define the network (a flax module taking ``(x, train)``),
the dataset, and a config; everything else is inherited.
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Any, Iterator

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from theanompi_tpu.data.base import Dataset
from theanompi_tpu.data.prefetch import DevicePrefetcher
from theanompi_tpu.monitor import scopes
from theanompi_tpu.models.layers import (
    error_rate,
    softmax_cross_entropy,
    topk_error,
)
from theanompi_tpu.parallel.bsp import (
    TrainState,
    make_bsp_eval_step,
    make_bsp_train_step,
)
from theanompi_tpu.parallel.exchanger import BSP_Exchanger
from theanompi_tpu.parallel.mesh import (
    data_axis_size,
    data_mesh,
    host_count,
    host_rank,
    is_multiprocess,
    replicate,
)
from theanompi_tpu.utils.helper_funcs import (
    build_optimizer,
    load_params_npz,
    save_params_npz,
    scale_lr,
    set_learning_rate,
)
from theanompi_tpu.utils.recorder import Recorder

PyTree = Any


def _stack_host_batches(host_iter: Iterator, k: int) -> Iterator:
    """Group k host batches into one stacked pytree with a leading
    steps axis (the multi-step program's scan axis); drops a ragged
    tail group."""
    group = []
    for batch in host_iter:
        group.append(batch)
        if len(group) == k:
            yield jax.tree.map(lambda *xs: np.stack(xs), *group)
            group = []


@dataclasses.dataclass
class ModelConfig:
    """One config dataclass per (model, rule) pair — SURVEY.md §5.6.

    ``batch_size`` is PER data-shard (reference semantics: per-worker);
    the global batch is ``batch_size * data_axis_size(mesh)``.
    """

    batch_size: int = 128
    n_epochs: int = 70
    learning_rate: float = 0.01
    #: optimizer family (utils.helper_funcs.OPTIMIZERS): 'sgd' is the
    #: reference recipe; 'lars' is the large-batch ResNet choice,
    #: 'adamw' the transformer one
    optimizer: str = "sgd"
    momentum: float = 0.9
    nesterov: bool = False
    weight_decay: float = 1e-4
    adam_beta1: float = 0.9
    adam_beta2: float = 0.999
    adam_eps: float = 1e-8
    rmsprop_decay: float = 0.9
    lars_trust_coefficient: float = 0.001
    lr_schedule: str = "step"       # 'step' | 'constant' | 'poly' | 'cosine'
    lr_decay_epochs: tuple = (40, 60)
    lr_decay_factor: float = 0.1
    lr_poly_power: float = 1.0
    #: linear warmup over the first N epochs (0 = off), applied before
    #: the schedule proper — the standard large-batch ramp
    warmup_epochs: int = 0
    #: label smoothing eps for the classification CE (train loss only;
    #: eval reports plain CE).  0.1 in modern 90-epoch ResNet recipes
    label_smoothing: float = 0.0
    lr_scale_with_workers: str | None = None   # None | 'linear' | 'sqrt'
    exchange_strategy: str = "psum"        # reference names accepted (nccl16...)
    exchange_what: str = "grads"
    #: ICI wire dtype of the gradient exchange: 'f32' (full precision,
    #: default) or 'bf16' — gradients are quantized to bfloat16 for the
    #: psum/reduce_scatter (HALF the per-step interconnect bytes on the
    #: pod) and restored to f32 before the average and the optimizer
    #: update, so accumulation stays f32.  The modern spelling of the
    #: reference's nccl16/asa16 strategies; works for plain BSP and
    #: zero_sharding (fsdp_sharding rejects it — its collectives are
    #: compiler-inserted with no quantization seam).  Step-vs-f32
    #: deviation is bounded by bf16's 8-bit mantissa (tolerance-pinned
    #: in tests/test_exchanger.py)
    exchange_dtype: str = "f32"
    #: carry the bf16 quantization error of each shard into its next
    #: exchange (error feedback): the residual rides
    #: ``TrainState.exchange_residual`` (per-shard, f32, checkpointed)
    #: and re-injects every bit the wire dropped, so the long-run
    #: applied-gradient sum tracks the true sum to one quantization
    #: step.  Requires exchange_dtype='bf16', exchange_what='grads',
    #: and a pure-'data' reduce axis (the residual is per-DATA-shard
    #: state); costs one extra f32 param-sized buffer per device
    exchange_error_feedback: bool = False
    #: partition the gradient exchange into this many layer-ordered,
    #: byte-balanced buckets (parallel/exchanger.bucket_ranges — a pure
    #: plan every rank derives identically) and embed each bucket's
    #: collective INTO the backward DAG, so early backward segments'
    #: psums overlap the remaining segments' gradient compute
    #: (arXiv:1802.06949's bucketed collectives, expressed as
    #: custom_vjp boundary tags for XLA's latency-hiding scheduler).
    #: 1 (default) keeps the whole-tree post-backward exchange
    #: byte-identical.  Works for plain BSP (f32/bf16/error-feedback),
    #: zero_sharding (per-bucket reduce_scatter/all_to_all — NOTE the
    #: sharded opt-state/residual layout depends on the bucket count,
    #: so resume a checkpoint under the SAME value), and fsdp_sharding
    #: (scheduling fences only; GSPMD owns the collectives).  The
    #: grad-accum cadence keeps its single post-accumulation exchange,
    #: split per bucket.  B>1 is pinned step-identical to B=1 on all
    #: three planes (tests/test_exchanger.py, test_zero.py,
    #: test_fsdp.py)
    exchange_buckets: int = 1
    compute_dtype: str = "float32"         # 'bfloat16' -> MXU-friendly compute
    #: crop/flip/normalize on DEVICE (ops/augment.py) — the host ships
    #: raw uint8 and the step augments; False = host-side augmentation
    #: (the reference's loader semantics).  Honored by the ImageNet
    #: model family's build_data.
    augment_on_device: bool = True
    #: ResNet stem flavor: 'conv7' (reference geometry) or 's2d'
    #: (exact space-to-depth re-parameterization — the TPU-friendly
    #: shape for the C=3 stem conv; models/resnet50.py)
    resnet_stem: str = "conv7"
    #: BN/activation epilogue impl: 'xla' (today's unfused composition,
    #: default) or 'pallas' (ops/fused_bn.py — ONE stream for the BN
    #: affine + residual add + relu, targeting the account's 5.81 ms of
    #: loop-fusion HBM traffic).  ResNet family: fuses every
    #: BN(+add)+relu with the param tree unchanged.  BN-free models
    #: (VGG/GoogLeNet) route their conv bias+relu epilogues through
    #: layers.BiasAct instead — NOTE that moves the bias param out of
    #: the conv scope, so their param tree depends on this knob (pick
    #: it at build time, not mid-run).  Default-off until the queued
    #: A/B account pair (tools/xla_sweep.py) confirms on chip.
    bn_act_impl: str = "xla"
    #: donate the STAGED BATCH buffers to the stacked-cadence steps
    #: (steps_per_call / grad_accum_steps programs) so XLA reuses their
    #: HBM for outputs instead of copying around live input buffers —
    #: part of the copy-done attack (the r3 account counts 1 334
    #: copy events/step).  The prefetcher stages a fresh batch per
    #: dispatch, so donation is safe on the training path; turn off
    #: when replaying the SAME staged batch through a step twice
    #: (the equivalence tests do; PERF.md §7 row c would)
    donate_batch: bool = True
    #: cross-replica BatchNorm: compute BN batch statistics over the
    #: whole DATA axis (lax.pmean inside the BN, flax ``axis_name``)
    #: instead of per-shard.  The standard TPU-pod choice when the
    #: per-core batch is small (running stats from a 4-8 image shard
    #: are too noisy to serve eval — observed as chance-level val error
    #: with converged train loss).  Per-shard BN (False) matches the
    #: reference's per-worker semantics.  Requires a shard_map step
    #: with a live 'data' axis — incompatible with fsdp_sharding
    #: (GSPMD jit has no named axes; compile_iter_fns rejects the
    #: combination).  Honored by models whose build_module() threads
    #: ``_bn_axis()`` into their BN layers: the ResNet family
    #: (resnet50.py) and — with ``batch_norm=True`` — the whole
    #: layer-toolkit zoo (VGG16/VGG19, GoogLeNet, AlexNet), which
    #: closes the round-4 advisor's wiring obligation.  A NEW zoo
    #: model using ``layers.BatchNorm`` must still pass
    #: ``self._bn_axis()`` itself.  Models that declare
    #: ``uses_batchnorm`` warn at compile when the per-shard batch is
    #: small and this is left False.
    sync_bn: bool = False
    #: build the BatchNorm variant of the layer-toolkit CNNs (the
    #: classic vgg16_bn-style configuration): every conv's bias+relu
    #: epilogue becomes ``layers.BatchNorm`` (+relu, conv bias
    #: dropped), with ``_bn_axis()`` threaded so ``sync_bn`` is
    #: honored — the ADVICE r4 wiring obligation now holds for the
    #: whole zoo (VGG16/VGG19, GoogLeNet, AlexNet), not just ResNet.
    #: The param tree changes (BatchNorm_* scale/bias + batch_stats
    #: instead of conv bias), so flip at model build, not mid-run.
    #: No-op for models that always carry BN (ResNet) or none (LM).
    batch_norm: bool = False
    #: rematerialize transformer blocks in the backward pass
    #: (jax.checkpoint): activations are recomputed instead of stored,
    #: trading ~1/3 more FLOPs for O(n_layers) less activation HBM —
    #: the knob that lets long-context training fit
    remat: bool = False
    #: scan this many training iterations into one device program
    #: (parallel/bsp.py make_bsp_multi_step) — amortizes per-dispatch
    #: overhead; 1 = one program per batch (reference cadence)
    steps_per_call: int = 1
    #: accumulate gradients over this many microbatches before ONE
    #: optimizer update (parallel/bsp.py make_bsp_accum_step): the
    #: effective global batch is grad_accum_steps * batch_size * shards
    #: at the HBM footprint of one microbatch.  Mutually exclusive with
    #: steps_per_call > 1; BSP only
    grad_accum_steps: int = 1
    #: ZeRO-1: shard the optimizer state over the data axis
    #: (parallel/zero.py — reduce_scatter grads, update the 1/N shard,
    #: all_gather params).  Step-equal to plain BSP for elementwise
    #: optimizers; BSP only, composes with the seq axis, with
    #: grad_accum_steps, and with steps_per_call (the two stacked
    #: cadences stay mutually exclusive with each other)
    zero_sharding: bool = False
    #: FSDP (ZeRO-3 class): params AND optimizer state live 1/N per
    #: device over the data axis; the step is plain global math under
    #: GSPMD — XLA inserts per-layer all-gathers before each weight's
    #: use and reduce-scatters for its grads (parallel/fsdp.py).
    #: Trajectory equals unsharded BSP exactly.  BSP only; composes
    #: with steps_per_call OR grad_accum_steps; mutually exclusive
    #: with zero_sharding (FSDP already shards strictly more)
    fsdp_sharding: bool = False
    seed: int = 42
    data_dir: str | None = None
    snapshot_dir: str = "./snapshots"
    print_freq: int = 40
    track_top5: bool = False


class TpuModel:
    """Base model implementing the reference contract over the BSP spine."""

    name = "model"
    #: how batches land on the mesh; None = leading dim over 'data'.
    #: Sequence-parallel models override (e.g. P('data', 'seq')).
    batch_partition = None
    #: trained FLOPs per sample (fwd+bwd, ~3x fwd) — models that know
    #: theirs set it so the recorder's epoch records carry achieved
    #: TFLOP/s (utils/recorder.py); None = column omitted
    train_flops_per_sample: float | None = None

    def __init__(self, config: ModelConfig | None = None, mesh=None,
                 verbose: bool = True, shard_rank: int = 0,
                 shard_size: int = 1, data: Dataset | None = None):
        self._init_scaffold(config, mesh, verbose, shard_rank, shard_size,
                            data)
        self.module: nn.Module = self.build_module()

        rng = jax.random.key(self.config.seed)
        dummy = jnp.zeros((2, *self.data.sample_shape), self._input_dtype())
        # init traces the TRAINING path so train-only parameters (e.g.
        # GoogLeNet's aux heads) are created; flax skips running-stat
        # writes while initializing, so BN state stays at its init values
        variables = self.module.init({"params": rng, "dropout": rng}, dummy,
                                     train=True)
        variables = dict(variables)
        params = variables.pop("params")
        model_state = variables  # e.g. {'batch_stats': ...} or {}

        self.tx = self._build_optimizer(self._base_lr)
        self.state = self._create_state(params, model_state)

    def _create_state(self, params, model_state) -> "TrainState":
        """Build + place the initial training state.  Default: create
        (optimizer init included) then replicate over the mesh — pure
        DP.  Parameter-sharded models (TP) override so the optimizer
        state is built directly from SHARDED params and never
        materializes full-size on any device.  ZeRO-1
        (``zero_sharding``) replicates params but builds the optimizer
        state sharded over 'data'."""
        if self.config.fsdp_sharding:
            from theanompi_tpu.parallel.fsdp import (fsdp_specs,
                                                     init_fsdp_state)

            self._check_fsdp_supported()
            # param_specs doubles as the checkpoint-resume placement
            # contract (adopt_restored_state re-places params AND the
            # optimizer's param-like buffers per these specs)
            self.param_specs = fsdp_specs(params, self.mesh)
            return init_fsdp_state(params, self.tx, model_state,
                                   self.mesh, self.param_specs)
        if self.config.zero_sharding:
            from theanompi_tpu.parallel.zero import init_zero_opt_state

            self._check_zero_supported()
            opt_state, _ = init_zero_opt_state(
                self.tx, params, self.mesh,
                exchange_buckets=self.config.exchange_buckets)
            params_r, ms_r, step_r = replicate(
                (params, model_state, jnp.zeros((), jnp.int32)), self.mesh)
            return TrainState(step=step_r, params=params_r,
                              opt_state=opt_state, model_state=ms_r,
                              exchange_residual=self._init_residual(params))
        state = replicate(TrainState.create(params, self.tx, model_state),
                          self.mesh)
        return state.replace(exchange_residual=self._init_residual(params))

    def _init_residual(self, params) -> PyTree | None:
        """Error-feedback residual for the bf16 gradient exchange
        (``ModelConfig.exchange_error_feedback``): zeros with a leading
        data-shard axis, placed sharded ``P('data')`` so each shard
        owns exactly its own quantization error
        (parallel/bsp.py ``TrainState.exchange_residual``).  ``None``
        (the default) leaves the state's pytree unchanged."""
        cfg = self.config
        if not cfg.exchange_error_feedback:
            return None
        if cfg.exchange_dtype != "bf16":
            raise ValueError("exchange_error_feedback compensates bf16 "
                             "quantization; set exchange_dtype='bf16'")
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from theanompi_tpu.parallel.mesh import AXIS_DATA

        part, axes = self._batch_axes()
        if axes != (AXIS_DATA,):
            raise ValueError(
                "exchange_error_feedback keeps one residual per DATA "
                f"shard; this model reduces over {axes} — per-shard "
                "error state is only defined for the pure-data mesh")
        n = self.mesh.shape[AXIS_DATA]
        if cfg.zero_sharding:
            from theanompi_tpu.parallel.zero import (
                init_zero_exchange_residual,
            )

            res = init_zero_exchange_residual(
                params, self.mesh,
                exchange_buckets=cfg.exchange_buckets)
        else:
            from theanompi_tpu.parallel.bsp import init_exchange_residual

            res = init_exchange_residual(params, n)
        sh = NamedSharding(self.mesh, P(AXIS_DATA))
        return jax.tree.map(lambda x: jax.device_put(x, sh), res)

    def _check_psum_grads_only(self, feature: str, how: str,
                               allow_bf16_wire: bool = False) -> None:
        """Shared guard for the sharding features that ARE the gradient
        exchange (zero/fsdp): exchange_what/strategy knobs don't apply.
        ``allow_bf16_wire=True`` (ZeRO) accepts the ``exchange_dtype``
        compression knob — its reduce_scatter has a quantization seam —
        while still rejecting the legacy strategy spelling."""
        cfg = self.config
        if cfg.exchange_what != "grads":
            raise ValueError(f"{feature} IS the gradient exchange; "
                             "exchange_what='params' does not apply")
        from theanompi_tpu.parallel.exchanger import resolve_strategy

        if resolve_strategy(cfg.exchange_strategy) != "psum":
            raise ValueError(
                f"{feature}'s {how}; the bf16-compressed strategy "
                f"{cfg.exchange_strategy!r} does not apply")
        if not allow_bf16_wire and (cfg.exchange_dtype != "f32"
                                    or cfg.exchange_error_feedback):
            raise ValueError(
                f"{feature}'s {how}; exchange_dtype="
                f"{cfg.exchange_dtype!r}/exchange_error_feedback do not "
                "apply")

    def _check_zero_supported(self) -> None:
        from theanompi_tpu.parallel.mesh import AXIS_DATA

        cfg = self.config
        part, axes = self._batch_axes()
        if AXIS_DATA not in axes:
            raise ValueError("zero_sharding shards the optimizer over "
                             f"the '{AXIS_DATA}' axis, which is not "
                             f"among this model's reduce axes {axes}")
        if cfg.optimizer == "lars":
            raise ValueError("zero_sharding needs an ELEMENTWISE "
                             "optimizer; lars computes layerwise trust "
                             "ratios which a flat shard cannot see")
        self._check_psum_grads_only(
            "zero_sharding",
            "reduce_scatter owns the wire dtype (use exchange_dtype)",
            allow_bf16_wire=True)

    def _reject_zero_sharding(self, model_kind: str) -> None:
        """Compile-time guard mirroring _reject_grad_accum for models
        with their own state/step builders."""
        if self.config.zero_sharding:
            raise ValueError(f"zero_sharding is not implemented for "
                             f"the {model_kind}")
        if self.config.fsdp_sharding:
            raise ValueError(f"fsdp_sharding is not implemented for "
                             f"the {model_kind}")
        if self.config.exchange_error_feedback:
            # the residual is TrainState plumbing these custom stacks
            # don't thread; silently ignoring new state would be worse
            # than refusing
            raise ValueError(f"exchange_error_feedback is not "
                             f"implemented for the {model_kind}")
        if self.config.exchange_buckets != 1:
            # custom step builders don't route through the exchanger's
            # backward tags; a silently-ignored knob would fake the win
            raise ValueError(f"exchange_buckets is not implemented for "
                             f"the {model_kind}")

    def _check_fsdp_supported(self) -> None:
        from theanompi_tpu.parallel.mesh import AXIS_DATA

        cfg = self.config
        if cfg.zero_sharding:
            raise ValueError("fsdp_sharding already shards params AND "
                             "optimizer state; combining it with "
                             "zero_sharding is meaningless")
        part, axes = self._batch_axes()
        if axes != (AXIS_DATA,):
            raise ValueError(
                f"fsdp_sharding is the pure-DP parameter-sharding path "
                f"(GSPMD over '{AXIS_DATA}'); this model reduces over "
                f"{axes} — use the family's own sharded step instead")
        self._check_psum_grads_only(
            "fsdp_sharding",
            "collectives are compiler-inserted at full precision")

    def adopt_restored_state(self, state: "TrainState") -> "TrainState":
        """Hook for checkpoint resume: re-establish this model's device
        placement on a restored (host-side) state.  Replicated models:
        as-is (the shard_map step's in_specs place state on entry).
        Parameter-sharded models (``param_specs`` set): params AND the
        optimizer's param-like buffers are re-placed per their specs —
        essential for the TP path, whose plain-jit step infers
        shardings from the committed arrays."""
        if self.param_specs is None:
            return state
        import optax
        from jax.sharding import NamedSharding

        def put(leaf, spec):
            return jax.device_put(jnp.asarray(leaf),
                                  NamedSharding(self.mesh, spec))

        return state.replace(
            params=jax.tree.map(put, state.params, self.param_specs),
            opt_state=optax.tree_map_params(
                self.tx, put, state.opt_state, self.param_specs),
        )

    def _init_scaffold(self, config, mesh, verbose, shard_rank, shard_size,
                       data) -> None:
        """The contract scaffolding shared by every model — including
        ones (WGAN) whose network/optimizer state diverges from the
        single-module TrainState path: mesh/shard bookkeeping, dataset,
        worker-scaled LR, rng, and the train-loop fields that
        ``begin_epoch``/``train_iter``/``_flush_metrics`` rely on."""
        self.config = config or self.default_config()
        self.verbose = verbose
        self.mesh = mesh if mesh is not None else data_mesh()
        self.n_workers = data_axis_size(self.mesh)
        # async-rule data sharding: this model instance sees shard
        # shard_rank of shard_size (BSP leaves these 0/1 — the mesh
        # shards the global batch instead)
        self.shard_rank = shard_rank
        self.shard_size = shard_size
        # multi-host: this controller feeds only its host's slice of
        # every global batch (data/base.py host_train_batches)
        self.multiprocess = is_multiprocess(self.mesh)
        self.host_rank = host_rank() if self.multiprocess else 0
        self.host_count = host_count() if self.multiprocess else 1
        if self.multiprocess and shard_size > 1:
            raise ValueError(
                "per-worker data sharding (shard_size>1, async rules) and a "
                "multi-host mesh cannot be combined in one model instance")
        self.batch_size = self.config.batch_size
        self.global_batch = self.batch_size * self.n_workers
        self.n_epochs = self.config.n_epochs
        self.current_epoch = 0
        self.current_info: dict = {}

        # ``data`` lets N worker models in one process (async rules)
        # share one Dataset instead of loading N copies
        self.data: Dataset = data if data is not None else self.build_data()

        base_lr = self.config.learning_rate
        if self.config.lr_scale_with_workers:
            base_lr = scale_lr(base_lr, self.n_workers,
                               self.config.lr_scale_with_workers)
        self._base_lr = base_lr

        self._rng = self._epoch_rng(0)
        self.train_step = None
        self.train_step_multi = None
        self.train_step_accum = None
        #: the train step last noted with monitor/scopes.py
        self._noted_step = None
        self.eval_step = None
        self._train_prefetcher: DevicePrefetcher | None = None
        self._train_iter: Iterator | None = None
        self._ingest_source = None  # RemoteBatchSource when --ingest
        self._pending: list[tuple[int, dict]] = []

    # -- hooks for subclasses ------------------------------------------------

    @classmethod
    def default_config(cls) -> ModelConfig:
        return ModelConfig()

    def build_module(self) -> nn.Module:
        raise NotImplementedError

    def build_data(self) -> Dataset:
        raise NotImplementedError

    def _input_dtype(self):
        return jnp.float32

    def _compute_dtype(self):
        """MXU compute dtype from config (params stay fp32 masters)."""
        return (jnp.bfloat16 if self.config.compute_dtype == "bfloat16"
                else jnp.float32)

    def _bn_axis(self) -> str | None:
        """Named axis for cross-replica BN stats (ModelConfig.sync_bn);
        None keeps per-shard stats.  BN-using build_module()s pass this
        to their module so one config knob covers the family."""
        if not self.config.sync_bn:
            return None
        from theanompi_tpu.parallel.mesh import AXIS_DATA

        return AXIS_DATA

    # -- optimizer / loss ----------------------------------------------------

    def _build_optimizer(self, lr: float) -> optax.GradientTransformation:
        return build_optimizer(lr, **self._optimizer_kwargs())

    def _optimizer_kwargs(self) -> dict:
        cfg = self.config
        return {"optimizer": cfg.optimizer, "momentum": cfg.momentum,
                "nesterov": cfg.nesterov, "weight_decay": cfg.weight_decay,
                "beta1": cfg.adam_beta1, "beta2": cfg.adam_beta2,
                "eps": cfg.adam_eps, "rmsprop_decay": cfg.rmsprop_decay,
                "lars_trust_coefficient": cfg.lars_trust_coefficient}

    def optimizer_hyperparams(self) -> dict:
        """The plain-value description of this model's optimizer — what
        a remote ASGD service needs to rebuild it (parallel/service.py;
        the keys are ``build_optimizer``'s kwargs)."""
        return {"learning_rate": self._base_lr, **self._optimizer_kwargs()}

    def loss_fn(self, params, model_state, batch, rng):
        """Default: softmax CE + top-1 error.  Override for GANs etc.

        Honors the dataset's ``device_transform`` (ops/augment.py):
        raw uint8 batches are cropped/flipped/normalized on device as
        part of this same jitted step."""
        x, y = batch
        transform = getattr(self.data, "device_transform", None)
        if transform is not None:
            rng, aug_rng = jax.random.split(rng)
            x = transform(x, aug_rng, train=True)
        variables = {"params": params, **model_state}
        mutable = [k for k in model_state if k == "batch_stats"]
        if mutable:
            logits, updates = self.module.apply(
                variables, x, train=True, mutable=mutable,
                rngs={"dropout": rng},
            )
            new_ms = {**model_state, **updates}
        else:
            logits = self.module.apply(variables, x, train=True,
                                       rngs={"dropout": rng})
            new_ms = model_state
        smooth = self.config.label_smoothing  # train-time only; eval
        if isinstance(logits, (tuple, list)):  # aux heads (GoogLeNet)
            main, *aux = logits                 # reports plain CE
            loss = softmax_cross_entropy(main, y, smooth)
            for a_logits, a_w in aux:
                loss = loss + a_w * softmax_cross_entropy(a_logits, y,
                                                          smooth)
            logits = main
        else:
            loss = softmax_cross_entropy(logits, y, smooth)
        metrics = {"loss": loss, "error": error_rate(logits, y)}
        if self.config.track_top5:
            metrics["top5_error"] = topk_error(logits, y, 5)
        return loss, (new_ms, metrics)

    def eval_fn(self, params, model_state, batch):
        x, y = batch
        transform = getattr(self.data, "device_transform", None)
        if transform is not None:
            x = transform(x, None, train=False)  # center crop, no mirror
        variables = {"params": params, **model_state}
        logits = self.module.apply(variables, x, train=False)
        if isinstance(logits, (tuple, list)):
            logits = logits[0]
        metrics = {"loss": softmax_cross_entropy(logits, y),
                   "error": error_rate(logits, y)}
        if self.config.track_top5:
            metrics["top5_error"] = topk_error(logits, y, 5)
        return metrics

    # -- reference contract --------------------------------------------------

    @property
    def params(self) -> PyTree:
        return self.state.params

    def _batch_axes(self) -> tuple:
        """(partition, reduce_axes) derived from ``batch_partition`` —
        every mesh axis the batch is sharded over is also a gradient/
        metric reduce axis, so a subclass setting the attribute gets a
        consistent step with no extra plumbing."""
        from jax.sharding import PartitionSpec as P

        from theanompi_tpu.parallel.mesh import AXIS_DATA

        part = (self.batch_partition if self.batch_partition is not None
                else P(AXIS_DATA))
        axes = []
        for entry in part:
            if entry is None:
                continue
            for a in (entry,) if isinstance(entry, str) else entry:
                axes.append(a)
        return part, tuple(axes)

    #: models whose network contains BatchNorm set this True so the
    #: small-shard warning below can fire (only they are exposed to
    #: the noisy-per-shard-stats failure)
    uses_batchnorm: bool = False

    def compile_iter_fns(self, sync_type: str = "avg") -> None:
        """Build the jitted SPMD steps (the reference's Theano-function
        compile; ``sync_type`` 'avg' vs 'cdd' maps to exchange avg/sum)."""
        part, axes = self._batch_axes()
        if (self.uses_batchnorm and not self.config.sync_bn
                and self.batch_size < 16):
            import warnings

            warnings.warn(
                f"{type(self).__name__}: per-shard batch "
                f"{self.batch_size} with sync_bn=False — BatchNorm "
                "running statistics from so few images are too noisy "
                "to serve eval (observed as chance-level val error at "
                "converged train loss, round-4 jpeg e2e).  Set "
                "ModelConfig.sync_bn=True (cross-replica stats) or "
                "raise batch_size.", stacklevel=2)
        if (self.config.steps_per_call > 1
                and self.config.grad_accum_steps > 1):
            raise ValueError(
                "steps_per_call and grad_accum_steps are both stacked-"
                "batch cadences; combining them by nesting is not "
                "supported — set one of them to 1")
        if self.config.fsdp_sharding:
            from theanompi_tpu.parallel.fsdp import make_bsp_fsdp_step

            self._check_fsdp_supported()
            if self.config.sync_bn:
                raise ValueError(
                    "sync_bn needs a shard_map step with a named 'data' "
                    "axis; the FSDP step is GSPMD-jitted with no named "
                    "axes — use per-shard BN (sync_bn=False) with FSDP")
            # param_specs was derived at state build; passing it keeps
            # the step's shardings and the resume placement identical
            fsdp_kw = dict(avg=(sync_type != "cdd"), batch_partition=part,
                           donate_batch=self.config.donate_batch,
                           specs=self.param_specs,
                           exchange_buckets=self.config.exchange_buckets)
            self.train_step = make_bsp_fsdp_step(
                self.loss_fn, self.tx, self.mesh,
                params_template=self.state.params, **fsdp_kw)
            if self.config.steps_per_call > 1:
                self.train_step_multi = make_bsp_fsdp_step(
                    self.loss_fn, self.tx, self.mesh,
                    params_template=self.state.params, multi=True,
                    **fsdp_kw)
            if self.config.grad_accum_steps > 1:
                self.train_step_accum = make_bsp_fsdp_step(
                    self.loss_fn, self.tx, self.mesh,
                    params_template=self.state.params, accum=True,
                    **fsdp_kw)
            # eval reuses the shard_map step: its replicated in_spec
            # makes jit insert one params all-gather per eval batch
            self.eval_step = make_bsp_eval_step(self.eval_fn, self.mesh,
                                                batch_partition=part,
                                                reduce_axes=axes)
            return
        if self.config.zero_sharding:
            from theanompi_tpu.parallel.zero import make_bsp_zero_step

            self._check_zero_supported()
            zero_kw = dict(avg=(sync_type != "cdd"),
                           donate_batch=self.config.donate_batch,
                           batch_partition=part, reduce_axes=axes,
                           exchange_dtype=self.config.exchange_dtype,
                           error_feedback=self.config
                           .exchange_error_feedback,
                           exchange_buckets=self.config.exchange_buckets)
            self.train_step = make_bsp_zero_step(
                self.loss_fn, self.tx, self.mesh,
                params_template=self.state.params,  # shapes only
                **zero_kw)
            if self.config.steps_per_call > 1:
                self.train_step_multi = make_bsp_zero_step(
                    self.loss_fn, self.tx, self.mesh,
                    params_template=self.state.params, multi=True,
                    **zero_kw)
            if self.config.grad_accum_steps > 1:
                self.train_step_accum = make_bsp_zero_step(
                    self.loss_fn, self.tx, self.mesh,
                    params_template=self.state.params, accum=True,
                    **zero_kw)
            self.eval_step = make_bsp_eval_step(self.eval_fn, self.mesh,
                                                batch_partition=part,
                                                reduce_axes=axes)
            return
        exchanger = BSP_Exchanger(
            strategy=self.config.exchange_strategy,
            avg=(sync_type != "cdd"),
            exchange_what=self.config.exchange_what,
            axis=axes if len(axes) > 1 else axes[0],
            exchange_dtype=(None if self.config.exchange_dtype == "f32"
                            else self.config.exchange_dtype),
            error_feedback=self.config.exchange_error_feedback,
            exchange_buckets=self.config.exchange_buckets,
        )
        self.train_step = make_bsp_train_step(self.loss_fn, self.tx,
                                              self.mesh, exchanger,
                                              batch_partition=part,
                                              reduce_axes=axes)
        if self.config.steps_per_call > 1:
            from theanompi_tpu.parallel.bsp import make_bsp_multi_step

            self.train_step_multi = make_bsp_multi_step(
                self.loss_fn, self.tx, self.mesh, exchanger,
                donate_batch=self.config.donate_batch,
                batch_partition=part, reduce_axes=axes)
        if self.config.grad_accum_steps > 1:
            from theanompi_tpu.parallel.bsp import make_bsp_accum_step

            self.train_step_accum = make_bsp_accum_step(
                self.loss_fn, self.tx, self.mesh, exchanger,
                donate_batch=self.config.donate_batch,
                batch_partition=part, reduce_axes=axes)
        self.eval_step = make_bsp_eval_step(self.eval_fn, self.mesh,
                                            batch_partition=part,
                                            reduce_axes=axes)

    def _reject_grad_accum(self, model_kind: str) -> None:
        """Compile-time guard for models whose custom step builders
        do not implement accumulation (call from compile_iter_fns
        overrides, mirroring their steps_per_call guards)."""
        if self.config.grad_accum_steps > 1:
            raise ValueError(f"grad_accum_steps>1 is not implemented "
                             f"for the {model_kind}")

    def compile_grad_fn(self):
        """Jitted gradient-only step for parameter-server rules (ASGD):
        returns ``fn(state, batch, rng) -> (grads, new_model_state,
        metrics)`` with no optimizer update — the server applies it."""

        from theanompi_tpu.parallel.bsp import grad_and_metrics

        def gstep(state: TrainState, batch, rng):
            return grad_and_metrics(self.loss_fn, state.params,
                                    state.model_state, batch, rng)

        return jax.jit(gstep)

    def begin_epoch(self, epoch: int) -> int:
        """Stage the epoch's prefetched train iterator; returns n_iters
        (rounded down to a multiple of ``steps_per_call``)."""
        self.cleanup_iter()
        self.current_epoch = epoch
        # re-derive the step rng as a pure function of (seed, epoch):
        # dropout/augment draws become epoch-deterministic, so a resume
        # at an epoch boundary replays EXACTLY the continuous run's
        # draws (not merely statistically equivalent ones)
        self._rng = self._epoch_rng(epoch)
        # distributed ingest (theanompi_tpu/ingest): with
        # THEANOMPI_TPU_INGEST set (launcher --ingest), the epoch's
        # host batches come from the remote reader fleet instead of
        # this process's loader thread — byte-identical stream, same
        # DevicePrefetcher downstream, rules untouched.  Multi-host
        # SPMD programs keep the local per-host slicing path (each
        # host feeds only its slice of every global batch).
        ingest = None
        if not self.multiprocess:
            from theanompi_tpu.ingest.client import ingest_addresses

            ingest = ingest_addresses()
        if ingest:
            from theanompi_tpu.ingest.client import RemoteBatchSource

            self._ingest_source = RemoteBatchSource(
                ingest, data=self.data, epoch=epoch,
                global_batch=self.global_batch,
                rank=self.shard_rank, size=self.shard_size)
            host_iter = self._ingest_source
            n_iters = self._ingest_source.n_batches
        elif self.multiprocess:
            host_iter = self.data.host_train_batches(
                epoch, self.global_batch, self.host_rank, self.host_count)
            n_iters = self.data.n_train_batches_for(epoch, self.global_batch)
        else:
            host_iter = self.data.train_batch_rows(
                epoch, self.global_batch, self.shard_rank, self.shard_size)
            n_iters = self.data.n_train_batches_for(
                epoch, self.global_batch, self.shard_rank, self.shard_size)
        spec = self.batch_partition
        # both cadences stage a stacked batch; compile_iter_fns rejects
        # setting both, so at most one of k/a exceeds 1
        stack = max(self.config.steps_per_call,
                    self.config.grad_accum_steps)
        if stack > 1:
            host_iter = _stack_host_batches(host_iter, stack)
            n_iters -= n_iters % stack
            if n_iters == 0:
                raise ValueError(
                    f"the epoch has fewer iterations than the stacked "
                    f"cadence ({stack} = max(steps_per_call, "
                    f"grad_accum_steps)) — every epoch would train "
                    f"NOTHING; shrink the stack or grow the dataset/"
                    f"batch ratio")
            spec = self.stacked_batch_spec()
        # per staged batch this PROCESS assembles: multi-host iterators
        # yield only this host's slice of each global batch
        host_rows = self.global_batch // (self.host_count
                                          if self.multiprocess else 1)
        self._train_prefetcher = DevicePrefetcher(
            host_iter, self.mesh, spec=spec,
            images_per_batch=host_rows * stack,
            source="remote" if ingest else "local")
        self._train_iter = iter(self._train_prefetcher)
        return n_iters

    def stacked_batch_spec(self):
        """PartitionSpec of a stacked batch (leading steps/microbatch
        axis unsharded, per-step axes per ``batch_partition``) — the
        single source ``begin_epoch`` and the tests stage with, for BOTH
        stacked cadences (``train_step_multi`` and
        ``train_step_accum``)."""
        from jax.sharding import PartitionSpec as P

        from theanompi_tpu.parallel.mesh import AXIS_DATA

        per_step = (self.batch_partition if self.batch_partition
                    is not None else P(AXIS_DATA))
        return P(None, *per_step)

    def _epoch_rng(self, epoch: int):
        """The step-rng stream for an epoch — THE single derivation
        (init uses epoch 0, so pre-training draws match epoch 0's
        stream)."""
        return jax.random.fold_in(jax.random.key(self.config.seed + 1),
                                  epoch)

    def _next_rng(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def train_iter(self, count: int, recorder: Recorder) -> int:
        """One training dispatch; returns the number of iterations it
        covered (``steps_per_call`` for the scanned multi-step,
        ``grad_accum_steps`` for accumulation, else 1) so epoch drivers
        can advance their counters."""
        if self.train_step is None:
            raise RuntimeError("call compile_iter_fns() first")
        k = self.config.steps_per_call
        a = self.config.grad_accum_steps
        recorder.start()
        batch = next(self._train_iter)
        recorder.end("wait")  # time blocked on the loader = reference 'wait'
        recorder.start()
        if k > 1:
            step = self.train_step_multi
        elif a > 1:
            step = self.train_step_accum
            if step is None:
                raise ValueError(
                    f"{type(self).__name__}'s compile_iter_fns does "
                    "not build an accumulation step; grad_accum_steps"
                    ">1 is unsupported for this model")
        else:
            step = self.train_step
        rng = self._next_rng()
        if self._noted_step is not step:
            # the first dispatch of this function: say what ran, so that
            # monitor/scopes.py can map its device ops when asked
            self._noted_step = scopes.note_step(
                step, (self.state, batch, rng))
        # the annotation labels this iteration in jax.profiler traces
        # (utils/profiling.py); free when no trace is active
        with jax.profiler.StepTraceAnnotation("train", step_num=count):
            self.state, metrics = step(self.state, batch, rng)
        recorder.end("calc")  # async dispatch; device time lands on flush
        self._pending.append((count, metrics))
        # flush window: print_freq when printing, else a fixed window so
        # quiet runs (print_freq<=0) still batch device syncs
        window = recorder.print_freq if recorder.print_freq > 0 else 50
        consumed = max(k, a)
        if len(self._pending) * consumed >= window:
            self._flush_metrics(recorder)
            recorder.print_train_info(count)
        return consumed

    def _flush_metrics(self, recorder: Recorder) -> None:
        """Convert pending device metrics (blocks until the device has
        caught up — charged to 'calc').  Multi-step entries carry
        ``(k,)``-stacked metric leaves; each sub-step is recorded."""
        if not self._pending:
            return
        recorder.start()
        # a scalar entry covers grad_accum_steps microbatches' images
        # (metrics came back averaged over them); stacked entries carry
        # one sub-step per leaf row
        per_scalar = self.global_batch * self.config.grad_accum_steps
        for _, m in self._pending:
            loss = np.asarray(m["loss"])
            err = np.asarray(m["error"])
            if loss.ndim == 0:
                recorder.train_metrics(float(loss), float(err),
                                       per_scalar)
            else:
                for l, e in zip(loss, err):
                    recorder.train_metrics(float(l), float(e),
                                           self.global_batch)
        recorder.end("calc", block_on=self._pending[-1][1])
        self._pending.clear()
        self.current_info = {
            "epoch": self.current_epoch,
            "loss": recorder.train_losses[-1] if recorder.train_losses else None,
        }

    def val_iter(self, count: int, recorder: Recorder,
                 batch=None) -> dict:
        """One async eval dispatch, timed like the train path (the
        returned metrics are device scalars; the caller fetches them in
        bulk so the device pipeline never serializes per batch)."""
        recorder.start()
        metrics = self.eval_step(self.state, batch)
        recorder.end("calc")
        return metrics

    #: max un-synced validation dispatches: bounds how many in-flight
    #: batches' device buffers the runtime must pin (a full ImageNet val
    #: epoch left unfenced would queue gigabytes of inputs)
    VAL_SYNC_WINDOW = 8

    def val_epoch(self, recorder: Recorder) -> dict[str, float]:
        """Full validation pass; returns averaged metrics.  Dispatches
        eval steps asynchronously and syncs once per ``VAL_SYNC_WINDOW``
        batches — the device pipeline stays busy without per-batch
        serialization or unbounded buffer retention."""
        pending: list[dict] = []
        if self.multiprocess:
            host_iter = self.data.host_val_batches(
                self.global_batch, self.host_rank, self.host_count)
        else:
            host_iter = self.data.val_batch_rows(self.global_batch)
        from theanompi_tpu import monitor

        with DevicePrefetcher(host_iter, self.mesh,
                              spec=self.batch_partition) as pf:
            for n, batch in enumerate(pf):
                pending.append(self.val_iter(n, recorder, batch))
                # per-batch heartbeat: a long val epoch is progress,
                # not a stall — only a WEDGED one should trip the
                # watchdog
                monitor.progress(phase="validate", step=n)
                if (n + 1) % self.VAL_SYNC_WINDOW == 0:
                    recorder.start()
                    recorder.end("calc", block_on=pending[-1])
        if not pending:
            return {}
        recorder.start()
        sums: dict[str, float] = {}
        for m in pending:
            for k, v in m.items():
                sums[k] = sums.get(k, 0.0) + float(v)
        recorder.end("calc", block_on=pending[-1])
        return {k: v / len(pending) for k, v in sums.items()}

    def adjust_hyperp(self, epoch: int) -> float:
        """Per-epoch LR schedule (the reference's step/poly decay, plus
        cosine and the large-batch linear warmup ramp)."""
        cfg = self.config
        if cfg.warmup_epochs and epoch < cfg.warmup_epochs:
            lr = self._base_lr * (epoch + 1) / cfg.warmup_epochs
        elif cfg.lr_schedule == "constant":
            lr = self._base_lr
        elif cfg.lr_schedule == "step":
            k = sum(1 for e in cfg.lr_decay_epochs if epoch >= e)
            lr = self._base_lr * (cfg.lr_decay_factor ** k)
        elif cfg.lr_schedule in ("poly", "cosine"):
            # decay spans the post-warmup epochs
            span = max(cfg.n_epochs - cfg.warmup_epochs, 1)
            frac = min((epoch - cfg.warmup_epochs) / span, 1.0)
            if cfg.lr_schedule == "poly":
                lr = self._base_lr * (1.0 - frac) ** cfg.lr_poly_power
            else:
                lr = self._base_lr * 0.5 * (1.0 + math.cos(math.pi * frac))
        else:
            raise ValueError(f"unknown lr_schedule {cfg.lr_schedule!r}")
        self.state = self.state.replace(
            opt_state=set_learning_rate(self.state.opt_state, lr)
        )
        return lr

    # -- persistence (npz param snapshots; full-state resume is Orbax in
    #    the rules layer) ----------------------------------------------------

    def save(self, path: str | None = None) -> str:
        path = path or os.path.join(self.config.snapshot_dir,
                                    f"{self.name}_params.npz")
        save_params_npz(path, self.state.params)
        return path

    #: per-leaf PartitionSpecs for parameter-sharded models (TP/PP/MoE
    #: set this); None = fully replicated params (the DP default)
    param_specs = None

    def _place_params(self, params: PyTree) -> PyTree:
        """Put a host-side param tree back on the mesh the way this
        model shards it (per ``param_specs``, else replicated)."""
        if self.param_specs is None:
            return replicate(jax.tree.map(jnp.asarray, params), self.mesh)
        from jax.sharding import NamedSharding

        return jax.tree.map(
            lambda x, spec: jax.device_put(
                jnp.asarray(x), NamedSharding(self.mesh, spec)),
            params, self.param_specs)

    def load(self, path: str) -> None:
        """Contract ``load`` — PRESERVES the model's param sharding
        (a replicated load of a pipe/expert/model-sharded stack would
        materialize it full-size on every device).  The template is
        shape/dtype-only: no cross-device gather of sharded weights."""
        template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            self.state.params)
        params = load_params_npz(path, template)
        self.state = self.state.replace(params=self._place_params(params))

    def cleanup_iter(self) -> None:
        if self._train_prefetcher is not None:
            self._train_prefetcher.close()
            self._train_prefetcher = None
            self._train_iter = None
        if self._ingest_source is not None:
            # the prefetcher abandons its host iterator; the remote
            # source's fetcher threads + connections need an explicit
            # close (thread-leak fence, tests/conftest.py)
            self._ingest_source.close()
            self._ingest_source = None

    def cleanup(self) -> None:
        self.cleanup_iter()
