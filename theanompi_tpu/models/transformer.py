"""Causal transformer LM — the long-context / sequence-parallel
flagship.

Not a reference-parity model (the reference predates attention,
SURVEY.md §2.11/§5.7); this is the model family that exercises the
framework's first-class long-context path: the TIME dimension is
sharded over the mesh's ``seq`` axis and attention runs via
``parallel.sequence`` (ring / all-gather / ulysses), so context length
scales with chips.  Everything else rides the same spine as the CNN
zoo — the model keeps the full reference contract and trains through
``run_bsp_session`` with the batch sharded ``P('data', 'seq')`` and
gradients exchanged over BOTH axes.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from theanompi_tpu.data.lm import SeqLM_data
from theanompi_tpu.models import layers as L
from theanompi_tpu.models.base import ModelConfig, TpuModel
from theanompi_tpu.parallel.mesh import (
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_PIPE,
    AXIS_SEQ,
)
from theanompi_tpu.ops.attention import fused_attention
from theanompi_tpu.parallel.sequence import (
    sequence_attention,
)

#: param-tree keys whose tensors are NOT applied as per-token matmuls —
#: the embedding gather and the positional add contribute ~0 FLOPs, and
#: the standard 6N convention drops them
_NON_MATMUL_KEYS = frozenset({"embedding", "pos_emb"})


def _lm_train_flops(params, n_layers: int, seq_len: int, d_model: int,
                    expert_mask=None, n_experts: int = 1) -> float:
    """Trained FLOPs per SAMPLE (= per sequence) in the 2xMAC units the
    CNN zoo shares: the standard 6·n_active per trained token (fwd 2 +
    bwd 4) over matmul-applied params — embedding/positional tables are
    excluded (gather + add, ~0 FLOPs) — plus the attention score/PV
    term the param-proportional term misses, counted CAUSALLY:
    position t attends to t+1 keys, so QK^T and PV cost
    2·2·d·s(s+1)/2 forward a layer, x3 with the backward =
    6·n_layers·d·s(s+1).  That is the work the mask leaves and, since
    the kernel visits only the tiles the mask leaves (ops/attention.py),
    close to the work done; a whole s x s product would count twice
    this, the masked half of it useless.  The benchmark's
    ``benchmarks/flops/lm_decoder.py`` counts the same way, so
    ``tflops_per_shard`` and ``mfu.tok`` rest on one count
    (tests/test_flops_agree.py).  Computed from the REAL param count
    (biases and norm scales ride along, under 0.1% at GPT-2-medium) so
    CLI-resized and sharded variants stay honest; with top-1 routing
    only 1/n_experts of each expert tensor is active per token (pass
    the MoE's ``expert_mask``)."""
    from jax import tree_util as jtu

    flat = jtu.tree_flatten_with_path(params)[0]
    flags = (jax.tree.leaves(expert_mask) if expert_mask is not None
             else [False] * len(flat))
    active = 0
    for (path, leaf), is_exp in zip(flat, flags):
        keys = {getattr(k, "key", None) for k in path} | \
               {getattr(k, "name", None) for k in path}
        if keys & _NON_MATMUL_KEYS:
            continue
        active += int(leaf.size) // (n_experts if is_exp else 1)
    return float(6 * active * seq_len
                 + 6 * n_layers * d_model * seq_len * (seq_len + 1))


class Block(nn.Module):
    """Pre-LN transformer block with sequence-parallel attention.

    Round-2 note: the attention projections are three named Dense
    modules (``q_proj``/``k_proj``/``v_proj``), not one fused qkv —
    required for clean tensor-parallel column sharding.  This changed
    the param tree (old ``Dense_N`` snapshots no longer load) and the
    per-projection xavier fan differs from the fused kernel's, so
    pre-change training curves are not bit-reproducible."""

    d_model: int
    n_heads: int
    d_ff: int
    sp_strategy: str = "ring"
    dtype: jnp.dtype = jnp.float32
    #: ops.attention impl for the full local path; None = its own
    #: choice (Pallas on TPU).  GSPMD-jitted steps pass 'xla'
    #: (TransformerLM_TP.attn_impl)
    attn_impl: str | None = None

    @nn.compact
    def __call__(self, x, seq_axis: str | None = None):
        b, t, _ = x.shape
        d_head = self.d_model // self.n_heads
        h = nn.LayerNorm(dtype=self.dtype)(x)
        # separate (named) Q/K/V projections: under tensor parallelism
        # each is column-sharded over 'model' so every head's Q, K and
        # V live on ONE shard — a fused qkv kernel sharded in
        # contiguous chunks would straddle the split points and force
        # an all-to-all per block (parallel/tensor.py rules)
        proj = lambda name: nn.Dense(  # noqa: E731
            self.d_model, use_bias=False, kernel_init=L.xavier_init(),
            dtype=self.dtype, name=name)(h)
        shape = (b, t, self.n_heads, d_head)
        q = proj("q_proj").reshape(shape)
        k = proj("k_proj").reshape(shape)
        v = proj("v_proj").reshape(shape)
        if seq_axis is not None:
            o = sequence_attention(q, k, v, axis_name=seq_axis, causal=True,
                                   strategy=self.sp_strategy)
        else:
            # full local attention: the fused Pallas kernel on TPU
            # (ops/attention.py; XLA oracle elsewhere/oversize)
            o = fused_attention(q, k, v, causal=True,
                                impl=self.attn_impl, name="lm_attention")
        o = o.reshape((b, t, self.d_model))
        x = x + nn.Dense(self.d_model, use_bias=False,
                         kernel_init=L.xavier_init(), dtype=self.dtype,
                         name="o_proj")(o)
        h = nn.LayerNorm(dtype=self.dtype)(x)
        h = nn.Dense(self.d_ff, kernel_init=L.he_init(), dtype=self.dtype,
                     name="mlp_up")(h)
        h = nn.gelu(h)
        x = x + nn.Dense(self.d_model, kernel_init=L.xavier_init(),
                         dtype=self.dtype, name="mlp_down")(h)
        return x


class TransformerLMNet(nn.Module):
    """Token ids (B, T_local) -> logits (B, T_local, vocab).

    ``seq_axis`` is a CALL-time argument (not a module field) so the
    same parameters serve both the sharded training path (inside
    shard_map, where positions offset by the shard index) and
    unsharded init/inference.

    Two exits, one parameter tree.  By default the head (``Dense_0``)
    is applied and float32 logits come back: init, decode
    (``decode/model.py``), the eval exports (``serving/export.py``) and
    whoever wants a distribution over the vocabulary.  With
    ``hidden=True`` the call stops after the final LayerNorm and hands
    back ``h (B, T_local, d)`` in the compute dtype: ``TransformerLM``'s
    training and validation steps, which give ``h`` and ``Dense_0``'s
    kernel and bias to ``layers.blocked_softmax_cross_entropy`` so that
    the ``(tokens, vocab)`` logits never exist whole.
    """

    vocab: int = 256
    n_layers: int = 2
    d_model: int = 128
    n_heads: int = 4
    d_ff: int = 512
    max_len: int = 2048
    sp_strategy: str = "ring"
    dtype: jnp.dtype = jnp.float32
    #: jax.checkpoint each block: recompute activations in the
    #: backward instead of storing them (ModelConfig.remat)
    remat: bool = False
    #: forwarded to every Block (see Block.attn_impl)
    attn_impl: str | None = None

    @nn.compact
    def __call__(self, tokens, train: bool = False,
                 seq_axis: str | None = None, hidden: bool = False):
        t_local = tokens.shape[1]
        offset = (lax.axis_index(seq_axis) * t_local
                  if seq_axis is not None else 0)
        x = nn.Embed(self.vocab, self.d_model,
                     embedding_init=L.gaussian_init(0.02))(tokens)
        pos_emb = self.param("pos_emb", L.gaussian_init(0.02),
                             (self.max_len, self.d_model))
        x = x + lax.dynamic_slice_in_dim(pos_emb, offset, t_local)[None]
        x = x.astype(self.dtype)
        # static_argnums counts the bound method's args with the module
        # at 0, so seq_axis (a mesh-axis NAME, not data) is arg 2.
        # Explicit names pin the param tree to the non-remat layout
        # (nn.remat's class rename would otherwise key params under
        # CheckpointBlock_i, breaking snapshots and the TP specs).
        block_cls = (nn.remat(Block, static_argnums=(2,))
                     if self.remat else Block)
        for i in range(self.n_layers):
            x = block_cls(self.d_model, self.n_heads, self.d_ff,
                          self.sp_strategy, self.dtype, self.attn_impl,
                          name=f"Block_{i}")(x, seq_axis)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        if hidden:
            return x
        logits = nn.Dense(self.vocab, kernel_init=L.xavier_init(),
                          dtype=self.dtype)(x)
        return logits.astype(jnp.float32)


class TransformerLM(TpuModel):
    """LM over (data x seq)-sharded batches; reference model contract."""

    name = "transformer_lm"
    sp_strategy = "ring"
    batch_partition = P(AXIS_DATA, AXIS_SEQ)   # (B, T) over (data, seq)
    #: mesh axis the TIME dimension is sharded over inside the step
    #: (None = full attention; the TP variant sets None)
    seq_axis: str | None = AXIS_SEQ
    #: ops.attention impl of the full local path; None = its own choice
    #: (Pallas on TPU).  The tensor-parallel variant overrides it
    attn_impl: str | None = None
    #: exports of this family may serve the autoregressive decode path
    #: (theanompi_tpu/decode — single-flax-module param tree; the
    #: PP/MoE variants assemble diverging trees and stay eval-only)
    decode_capable = True

    @classmethod
    def default_config(cls) -> ModelConfig:
        return ModelConfig(
            batch_size=16,
            n_epochs=5,
            learning_rate=0.1,
            momentum=0.9,
            weight_decay=0.0,
            lr_schedule="constant",
            print_freq=20,
        )

    def __init__(self, *args, vocab: int = 256, seq_len: int = 128,
                 n_layers: int = 2, d_model: int = 128, n_heads: int = 4,
                 **kwargs):
        self._net_cfg = dict(vocab=vocab, seq_len=seq_len, n_layers=n_layers,
                             d_model=d_model, n_heads=n_heads)
        super().__init__(*args, **kwargs)
        self.train_flops_per_sample = _lm_train_flops(
            self.state.params, n_layers, seq_len, d_model)

    def _input_dtype(self):
        return jnp.int32

    def _resolved_seq_axis(self) -> str | None:
        """The seq axis the step should ACTUALLY shard time over.

        A size-1 ``seq`` axis (any pure-DP mesh — ``data_mesh`` always
        carries all five named axes) must degrade to ``None`` so
        attention takes the fused local path (ops/attention.py Pallas
        kernel) instead of ``ring_attention`` with a 1-hop ring, which
        materializes the FULL (B, H, T, T) score matrix per block: at
        b=16 t=2048 that was 768 MB of HLO temp PER BLOCK — the
        round-3 on-chip lm_b16_s2048 OOM — and a throughput hit at
        every size.  Ring-with-n=1 and full attention are the same
        math, so this is a routing fix, not a semantics change
        (equivalence covered by tests/test_transformer_sp.py).
        """
        ax = self.seq_axis
        if ax is None or self.mesh is None:
            return ax
        return ax if dict(self.mesh.shape).get(ax, 1) > 1 else None

    def build_data(self):
        c = self._net_cfg
        return SeqLM_data(vocab=c["vocab"], seq_len=c["seq_len"],
                          seed=self.config.seed)

    def build_module(self) -> nn.Module:
        c = self._net_cfg
        return TransformerLMNet(
            vocab=c["vocab"], n_layers=c["n_layers"], d_model=c["d_model"],
            n_heads=c["n_heads"], d_ff=4 * c["d_model"],
            max_len=max(2048, c["seq_len"]), sp_strategy=self.sp_strategy,
            dtype=self._compute_dtype(), remat=self.config.remat,
            attn_impl=self.attn_impl)

    # -- (data x seq) SPMD wiring -------------------------------------------

    def _loss_and_error(self, params, batch, train: bool, rng=None):
        """Mean token loss and top-1 error over this shard's tokens: the
        module's hidden exit, then the head and its loss a block of
        tokens at a time (no ``(tokens, vocab)`` logits on this path)."""
        tokens, targets = batch
        h = self.module.apply({"params": params}, tokens, train=train,
                              seq_axis=self._resolved_seq_axis(), hidden=True,
                              rngs={"dropout": rng} if train else None)
        head = params["Dense_0"]
        with jax.named_scope("lm/loss"):
            return L.blocked_softmax_cross_entropy(
                h.reshape(-1, h.shape[-1]), head["kernel"], head["bias"],
                targets.reshape(-1), vocab_axis=1,
                label_smoothing=(self.config.label_smoothing if train
                                 else 0.0))

    def loss_fn(self, params, model_state, batch, rng):
        loss, err = self._loss_and_error(params, batch, True, rng)
        return loss, (model_state, {"loss": loss, "error": err})

    def eval_fn(self, params, model_state, batch):
        loss, err = self._loss_and_error(params, batch, False)
        return {"loss": loss, "error": err}


class TransformerLM_TP(TransformerLM):
    """Tensor-parallel LM over a (data x model) mesh.

    Megatron-style TP the GSPMD way (parallel/tensor.py): block
    weights are sharded over ``model`` (Q/K/V/MLP-up column-wise,
    attn-out/MLP-down row-wise), the step is ONE plain jit and the
    compiler inserts every collective — both the TP all-reduces and
    the data-axis gradient all-reduce.  Attention runs unsharded in
    time (``seq_axis=None``); heads are what ``model`` splits, so this
    composes with DP, not SP.
    """

    name = "transformer_lm_tp"
    batch_partition = P(AXIS_DATA)   # tokens (B, T): batch over 'data'
    seq_axis = None                  # full attention; 'model' splits heads

    #: GSPMD cannot partition a Mosaic kernel: on four chips this
    #: model's plain-jit step died with "Mosaic kernels cannot be
    #: automatically partitioned. Please wrap the call in a shard_map"
    #: (PR 21), so it takes the XLA attention.  Giving it the kernel
    #: means wrapping the call in a shard_map over (data, model) — open
    attn_impl = "xla"

    def _create_state(self, params, model_state):
        """Shard params per the Megatron specs and build the optimizer
        state FROM the sharded tree — full-size momentum buffers never
        exist on any device."""
        from theanompi_tpu.parallel.mesh import AXIS_MODEL
        from theanompi_tpu.parallel.tensor import (
            shard_train_state,
            transformer_tp_specs,
        )

        tp = self.mesh.shape[AXIS_MODEL]
        c = self._net_cfg
        d_ff = 4 * c["d_model"]
        if c["n_heads"] % tp or d_ff % tp:
            raise ValueError(
                f"tensor parallelism {tp} must divide n_heads="
                f"{c['n_heads']} and d_ff={d_ff}: otherwise heads/hidden "
                "straddle shards and GSPMD silently inserts per-block "
                "reshards instead of the Megatron pattern")
        self.param_specs = transformer_tp_specs(params)
        return shard_train_state(params, model_state, self.mesh,
                                 self.param_specs, self.tx)

    # load()/adopt_restored_state(): the base implementations re-place
    # per self.param_specs (models/base.py) — nothing TP-specific left

    def compile_iter_fns(self, sync_type: str = "avg") -> None:
        """TP path: plain jit, shardings from the committed arrays.
        The global-batch mean gradient IS the averaged (``avg``)
        exchange; ``cdd`` (the reference's summed exchange, used with a
        pre-scaled LR) is realized by scaling grads by the data-axis
        size."""
        from theanompi_tpu.parallel.mesh import data_axis_size
        from theanompi_tpu.parallel.tensor import (
            make_gspmd_eval_step,
            make_gspmd_multi_step,
            make_gspmd_train_step,
        )

        self._reject_grad_accum("GSPMD tensor-parallel step")
        self._reject_zero_sharding("GSPMD tensor-parallel step (its "
                                   "optimizer state is already sharded "
                                   "like the params)")
        scale = float(data_axis_size(self.mesh)) if sync_type == "cdd" \
            else 1.0
        self.train_step = make_gspmd_train_step(self.loss_fn, self.tx,
                                                grad_scale=scale)
        if self.config.steps_per_call > 1:
            self.train_step_multi = make_gspmd_multi_step(
                self.loss_fn, self.tx, grad_scale=scale)
        self.eval_step = make_gspmd_eval_step(self.eval_fn)



class TransformerLM_PP(TpuModel):
    """Pipeline-parallel LM over a (data x pipe) mesh (GPipe-style).

    The blocks live STACKED on a leading layer axis sharded
    ``P('pipe')`` — each stage owns ``n_layers / pipe`` blocks — and
    microbatches flow stage-to-stage via ``ppermute`` inside the
    jitted step (parallel/pipeline.py); jax transposes the schedule
    for the backward pass.  Embedding/positional tables are replicated
    and their gradients psum-ed over ``pipe`` (only stage 0's compute
    path touches them); the final norm + LM head run identically on
    every stage from the broadcast pipeline output.

    Like the WGAN, this model diverges from the single-flax-module
    TrainState path, so it assembles its pieces on the shared
    ``_init_scaffold`` (models/base.py).
    """

    name = "transformer_lm_pp"
    batch_partition = P(AXIS_DATA)

    @classmethod
    def default_config(cls) -> ModelConfig:
        return TransformerLM.default_config()

    def __init__(self, config: ModelConfig | None = None, mesh=None,
                 verbose: bool = True, shard_rank: int = 0,
                 shard_size: int = 1, data=None, vocab: int = 256,
                 seq_len: int = 128, n_layers: int = 4, d_model: int = 128,
                 n_heads: int = 4, n_microbatches: int = 4):
        self._net_cfg = dict(vocab=vocab, seq_len=seq_len,
                             n_layers=n_layers, d_model=d_model,
                             n_heads=n_heads)
        self.n_microbatches = n_microbatches
        self._init_scaffold(config, mesh, verbose, shard_rank, shard_size,
                            data)
        n_stages = self.mesh.shape[AXIS_PIPE]
        if n_layers % n_stages != 0:
            raise ValueError(f"n_layers={n_layers} not divisible by "
                             f"pipe={n_stages} stages")
        local_batch = self.global_batch // self.mesh.shape[AXIS_DATA]
        if local_batch % n_microbatches != 0:
            raise ValueError(
                f"per-data-shard batch {local_batch} not divisible by "
                f"{n_microbatches} microbatches")

        from theanompi_tpu.parallel.pipeline import stack_stages
        from theanompi_tpu.parallel.tensor import shard_train_state

        dtype = self._compute_dtype()
        d = d_model
        self.embed_mod = nn.Embed(vocab, d,
                                  embedding_init=L.gaussian_init(0.02))
        self.block_mod = Block(d, n_heads, 4 * d, dtype=dtype)
        self.ln_mod = nn.LayerNorm(dtype=dtype)
        self.head_mod = nn.Dense(vocab, kernel_init=L.xavier_init(),
                                 dtype=dtype)

        rng = jax.random.key(self.config.seed)
        tok = jnp.zeros((2, seq_len), jnp.int32)
        x = jnp.zeros((2, seq_len, d), jnp.float32)
        params = {
            "embed": self.embed_mod.init(rng, tok)["params"],
            "pos_emb": L.gaussian_init(0.02)(
                jax.random.fold_in(rng, 1), (seq_len, d)),
            "blocks": stack_stages([
                self.block_mod.init(jax.random.fold_in(rng, 10 + i),
                                    x)["params"]
                for i in range(n_layers)]),
            "ln_f": self.ln_mod.init(rng, x)["params"],
            "head": self.head_mod.init(jax.random.fold_in(rng, 2),
                                       x)["params"],
        }
        self.tx = self._build_optimizer(self._base_lr)
        self.param_specs = jax.tree_util.tree_map_with_path(
            lambda path, leaf: (P(AXIS_PIPE)
                                if getattr(path[0], "key", None) == "blocks"
                                else P()),
            params)
        # stage params sharded over 'pipe' from the start; optimizer
        # state built from the sharded tree (parallel/tensor.py)
        self.state = shard_train_state(params, {}, self.mesh,
                                       self.param_specs, self.tx)
        self.train_flops_per_sample = _lm_train_flops(
            params, n_layers, seq_len, d_model)
        # masked-loss convention: every param NOT owned per-stage has
        # real grads on exactly one stage (embeddings on stage 0 via
        # the inject path, head/ln_f on the last via the masked loss)
        # and zeros elsewhere -> psum over 'pipe' syncs the replicas
        self.pipe_psum_mask = jax.tree_util.tree_map_with_path(
            lambda path, leaf: getattr(path[0], "key", None) != "blocks",
            params)

    def _input_dtype(self):
        return jnp.int32

    def build_data(self):
        c = self._net_cfg
        return SeqLM_data(vocab=c["vocab"], seq_len=c["seq_len"],
                          seed=self.config.seed)

    # -- forward through the pipeline (runs inside shard_map) ---------------

    def _forward(self, params, tokens):
        from theanompi_tpu.parallel.pipeline import pipeline_apply

        b, t = tokens.shape
        d = self._net_cfg["d_model"]
        x = self.embed_mod.apply({"params": params["embed"]}, tokens)
        x = x + params["pos_emb"][None, :t]
        x = x.astype(self._compute_dtype())
        m = self.n_microbatches
        xm = x.reshape(m, b // m, t, d)

        def stage_fn(stage_params, h):
            def body(carry, layer_params):
                out = self.block_mod.apply({"params": layer_params}, carry,
                                           seq_axis=None)
                return out, None

            h, _ = lax.scan(body, h, stage_params)
            return h

        outs = pipeline_apply(stage_fn, params["blocks"], xm,
                              axis_name=AXIS_PIPE)
        h = outs.reshape(b, t, d)
        h = self.ln_mod.apply({"params": params["ln_f"]}, h)
        logits = self.head_mod.apply({"params": params["head"]}, h)
        return logits.astype(jnp.float32)

    def loss_fn(self, params, model_state, batch, rng):
        from theanompi_tpu.parallel.pipeline import last_stage_mask

        del rng  # no dropout in the block
        tokens, targets = batch
        logits = self._forward(params, tokens)
        v = logits.shape[-1]
        # masked-loss convention (parallel/pipeline.py): seed the
        # backward on the last stage only; the step psums metrics and
        # the single-stage params' grads over 'pipe'
        mask = last_stage_mask()
        loss = mask * L.softmax_cross_entropy(
            logits.reshape(-1, v), targets.reshape(-1),
            self.config.label_smoothing)
        err = mask * L.error_rate(logits.reshape(-1, v),
                                  targets.reshape(-1))
        return loss, (model_state, {"loss": loss, "error": err})

    def eval_fn(self, params, model_state, batch):
        from theanompi_tpu.parallel.pipeline import last_stage_mask

        tokens, targets = batch
        logits = self._forward(params, tokens)
        v = logits.shape[-1]
        mask = last_stage_mask()
        return {"loss": mask * L.softmax_cross_entropy(
                    logits.reshape(-1, v), targets.reshape(-1)),
                "error": mask * L.error_rate(logits.reshape(-1, v),
                                             targets.reshape(-1))}

    def compile_iter_fns(self, sync_type: str = "avg") -> None:
        from theanompi_tpu.parallel.bsp import TrainState
        from theanompi_tpu.parallel.mesh import data_axis_size
        from theanompi_tpu.parallel.pipeline import (
            make_pp_eval_step,
            make_pp_train_step,
        )
        from theanompi_tpu.parallel.tensor import opt_state_specs

        self._reject_grad_accum("pipeline/expert step")
        self._reject_zero_sharding("pipeline/expert step")
        if self.config.steps_per_call > 1:
            raise ValueError("steps_per_call>1 is not implemented for the "
                             "pipeline-parallel path")
        state_specs = TrainState(
            step=P(),
            params=self.param_specs,
            opt_state=opt_state_specs(self.tx, self.state.opt_state,
                                      self.param_specs),
            model_state={},
        )
        scale = float(data_axis_size(self.mesh)) if sync_type == "cdd" \
            else 1.0
        self.train_step = make_pp_train_step(
            self.loss_fn, self.tx, self.mesh, state_specs,
            self.pipe_psum_mask, batch_partition=self.batch_partition,
            grad_scale=scale)
        self.eval_step = make_pp_eval_step(
            self.eval_fn, self.mesh, state_specs,
            batch_partition=self.batch_partition)


class AttnBlock(nn.Module):
    """Pre-LN attention sublayer (LN + q/k/v/o + residual) — the
    attention half of ``Block``, reused by the MoE variant whose FFN
    half is the expert-parallel switch layer."""

    d_model: int
    n_heads: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, t, _ = x.shape
        d_head = self.d_model // self.n_heads
        h = nn.LayerNorm(dtype=self.dtype)(x)
        proj = lambda name: nn.Dense(  # noqa: E731
            self.d_model, use_bias=False, kernel_init=L.xavier_init(),
            dtype=self.dtype, name=name)(h)
        shape = (b, t, self.n_heads, d_head)
        o = fused_attention(proj("q_proj").reshape(shape),
                            proj("k_proj").reshape(shape),
                            proj("v_proj").reshape(shape), causal=True,
                            name="lm_attention")
        o = o.reshape((b, t, self.d_model))
        return x + nn.Dense(self.d_model, use_bias=False,
                            kernel_init=L.xavier_init(), dtype=self.dtype,
                            name="o_proj")(o)


class TransformerLM_MoE(TpuModel):
    """Switch-MoE LM over a (data x expert) mesh.

    Every layer's FFN is a top-1-routed mixture of ``n_experts``
    expert MLPs, sharded over the ``expert`` axis (each shard owns
    ``n_experts / ep``); tokens reach their expert and return via
    ``lax.all_to_all`` inside the jitted step (parallel/expert.py).
    The batch is sharded over BOTH (data, expert) — the expert axis
    doubles as data parallelism outside the MoE layers, the standard
    TPU MoE topology.  Router load balancing uses the switch aux loss.

    Like the WGAN/PP models, diverges from the single-flax-module
    state path and assembles on ``_init_scaffold``.
    """

    name = "transformer_lm_moe"
    batch_partition = P((AXIS_DATA, AXIS_EXPERT))

    @classmethod
    def default_config(cls) -> ModelConfig:
        return TransformerLM.default_config()

    def __init__(self, config: ModelConfig | None = None, mesh=None,
                 verbose: bool = True, shard_rank: int = 0,
                 shard_size: int = 1, data=None, vocab: int = 256,
                 seq_len: int = 128, n_layers: int = 2, d_model: int = 128,
                 n_heads: int = 4, n_experts: int = 8,
                 capacity_factor: float = 1.25, aux_weight: float = 0.01):
        from theanompi_tpu.parallel.mesh import AXIS_EXPERT as AE

        self._net_cfg = dict(vocab=vocab, seq_len=seq_len,
                             n_layers=n_layers, d_model=d_model,
                             n_heads=n_heads)
        self.n_experts = n_experts
        self.capacity_factor = capacity_factor
        self.aux_weight = aux_weight
        self._init_scaffold(config, mesh, verbose, shard_rank, shard_size,
                            data)
        ep = self.mesh.shape[AE]
        if n_experts % ep != 0:
            raise ValueError(f"n_experts={n_experts} not divisible by "
                             f"expert-parallel degree {ep}")
        # tokens ride BOTH axes; recompute the data-parallel width AND
        # everything derived from it — notably the worker-scaled LR,
        # which _init_scaffold computed from the data axis alone
        self.n_workers = self.mesh.shape[AXIS_DATA] * ep
        self.global_batch = self.batch_size * self.n_workers
        if self.config.lr_scale_with_workers:
            from theanompi_tpu.utils.helper_funcs import scale_lr

            self._base_lr = scale_lr(self.config.learning_rate,
                                     self.n_workers,
                                     self.config.lr_scale_with_workers)

        from theanompi_tpu.parallel.tensor import shard_train_state

        dtype = self._compute_dtype()
        d, ff = d_model, 4 * d_model
        self.attn_mod = AttnBlock(d, n_heads, dtype=dtype)
        self.ln_mod = nn.LayerNorm(dtype=dtype)
        self.head_mod = nn.Dense(vocab, kernel_init=L.xavier_init(),
                                 dtype=dtype)
        self.embed_mod = nn.Embed(vocab, d,
                                  embedding_init=L.gaussian_init(0.02))

        rng = jax.random.key(self.config.seed)
        tok = jnp.zeros((2, seq_len), jnp.int32)
        x = jnp.zeros((2, seq_len, d), jnp.float32)

        def expert_init(key, layer):
            k1, k2 = jax.random.split(jax.random.fold_in(key, layer))
            he = (2.0 / d) ** 0.5
            xa = (6.0 / (ff + d)) ** 0.5
            return {
                "up_kernel": he * jax.random.normal(
                    k1, (n_experts, d, ff), jnp.float32),
                "up_bias": jnp.zeros((n_experts, ff), jnp.float32),
                "down_kernel": jax.random.uniform(
                    k2, (n_experts, ff, d), jnp.float32, -xa, xa),
                "down_bias": jnp.zeros((n_experts, d), jnp.float32),
            }

        params = {
            "embed": self.embed_mod.init(rng, tok)["params"],
            "pos_emb": L.gaussian_init(0.02)(
                jax.random.fold_in(rng, 1), (seq_len, d)),
            "attn": [self.attn_mod.init(jax.random.fold_in(rng, 10 + i),
                                        x)["params"]
                     for i in range(n_layers)],
            "moe_ln": [self.ln_mod.init(rng, x)["params"]
                       for _ in range(n_layers)],
            "router": [L.gaussian_init(0.02)(
                jax.random.fold_in(rng, 100 + i), (d, n_experts))
                for i in range(n_layers)],
            "experts": [expert_init(jax.random.fold_in(rng, 200), i)
                        for i in range(n_layers)],
            "ln_f": self.ln_mod.init(rng, x)["params"],
            "head": self.head_mod.init(jax.random.fold_in(rng, 2),
                                       x)["params"],
        }
        self.tx = self._build_optimizer(self._base_lr)

        def leaf_spec(path, leaf):
            in_experts = any(getattr(k, "key", None) == "experts"
                             for k in path)
            return P(AE) if in_experts else P()

        self.param_specs = jax.tree_util.tree_map_with_path(leaf_spec,
                                                            params)
        self.expert_mask = jax.tree_util.tree_map_with_path(
            lambda path, leaf: any(getattr(k, "key", None) == "experts"
                                   for k in path), params)
        self.state = shard_train_state(params, {}, self.mesh,
                                       self.param_specs, self.tx)
        self.train_flops_per_sample = _lm_train_flops(
            params, n_layers, seq_len, d_model,
            expert_mask=self.expert_mask, n_experts=n_experts)

    def _input_dtype(self):
        return jnp.int32

    def build_data(self):
        c = self._net_cfg
        return SeqLM_data(vocab=c["vocab"], seq_len=c["seq_len"],
                          seed=self.config.seed)

    # -- forward (runs inside shard_map over the (data, expert) axes) -------

    def _forward(self, params, tokens):
        from theanompi_tpu.parallel.expert import moe_ffn
        from theanompi_tpu.parallel.mesh import AXIS_EXPERT as AE

        b, t = tokens.shape
        d = self._net_cfg["d_model"]
        x = self.embed_mod.apply({"params": params["embed"]}, tokens)
        x = (x + params["pos_emb"][None, :t]).astype(self._compute_dtype())

        def apply_expert(p, tok):
            h = jnp.maximum(tok @ p["up_kernel"] + p["up_bias"], 0.0)
            return h @ p["down_kernel"] + p["down_bias"]

        aux_total = 0.0
        for layer in range(self._net_cfg["n_layers"]):
            x = self.attn_mod.apply({"params": params["attn"][layer]}, x)
            h = self.ln_mod.apply({"params": params["moe_ln"][layer]}, x)
            out, aux = moe_ffn(h.reshape(b * t, d), params["router"][layer],
                               params["experts"][layer], apply_expert,
                               capacity_factor=self.capacity_factor,
                               axis_name=AE)
            x = x + out.reshape(b, t, d)
            aux_total = aux_total + aux
        h = self.ln_mod.apply({"params": params["ln_f"]}, x)
        logits = self.head_mod.apply({"params": params["head"]}, h)
        return logits.astype(jnp.float32), aux_total

    def loss_fn(self, params, model_state, batch, rng):
        del rng
        tokens, targets = batch
        logits, aux = self._forward(params, tokens)
        v = logits.shape[-1]
        ce = L.softmax_cross_entropy(logits.reshape(-1, v),
                                     targets.reshape(-1),
                                     self.config.label_smoothing)
        err = L.error_rate(logits.reshape(-1, v), targets.reshape(-1))
        loss = ce + self.aux_weight * aux / self._net_cfg["n_layers"]
        return loss, (model_state, {"loss": ce, "error": err,
                                    "aux": aux})

    def eval_fn(self, params, model_state, batch):
        tokens, targets = batch
        logits, _ = self._forward(params, tokens)
        v = logits.shape[-1]
        return {"loss": L.softmax_cross_entropy(logits.reshape(-1, v),
                                                targets.reshape(-1)),
                "error": L.error_rate(logits.reshape(-1, v),
                                      targets.reshape(-1))}

    def compile_iter_fns(self, sync_type: str = "avg") -> None:
        from theanompi_tpu.parallel.bsp import TrainState
        from theanompi_tpu.parallel.expert import (
            make_moe_eval_step,
            make_moe_train_step,
        )
        from theanompi_tpu.parallel.tensor import opt_state_specs

        self._reject_grad_accum("pipeline/expert step")
        self._reject_zero_sharding("pipeline/expert step")
        if self.config.steps_per_call > 1:
            raise ValueError("steps_per_call>1 is not implemented for the "
                             "expert-parallel path")
        state_specs = TrainState(
            step=P(),
            params=self.param_specs,
            opt_state=opt_state_specs(self.tx, self.state.opt_state,
                                      self.param_specs),
            model_state={},
        )
        expert_mask_state = self.expert_mask
        scale = (float(self.n_workers) if sync_type == "cdd" else 1.0)
        self.train_step = make_moe_train_step(
            self.loss_fn, self.tx, self.mesh, state_specs,
            expert_mask_state, batch_partition=self.batch_partition,
            grad_scale=scale)
        self.eval_step = make_moe_eval_step(
            self.eval_fn, self.mesh, state_specs,
            batch_partition=self.batch_partition)
