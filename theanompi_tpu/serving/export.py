"""Model export: freeze a trained zoo state into a versioned, verified,
eval-mode inference artifact.

Training-side state (``TrainState``: params + optimizer state + batch
stats) is NOT what serving loads — the optimizer state is dead weight
and the module must run its EVAL path (``train=False``:
``BatchNormAct``/``BatchNorm`` switch to running statistics, dropout
off), with the model's ``bn_act_impl`` threading intact
so a recipe benched with the fused epilogue serves with it too.

An export is a directory of numbered versions written through the same
:class:`~theanompi_tpu.utils.checkpoint.Checkpointer` machinery the
training checkpoints use — synchronous save, per-file sha256 manifest
(resilience.recovery) — plus one ``export_meta_{v}.json`` sidecar
carrying what the loader needs to REBUILD the model around the arrays:
modelfile/modelclass (the reference's resolution convention) and the
full ``ModelConfig``.  Serving readers open the directory with
``Checkpointer(read_only=True)`` — no write fence, no manifest writes,
no quarantine moves — and load via ``restore_latest_verified``, so a
half-written or bit-rotted newest version costs a fallback, never the
server.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from theanompi_tpu.parallel.wire import BF16
from theanompi_tpu.utils.checkpoint import Checkpointer

PyTree = Any

#: export weight storage dtypes (docs/SERVING.md "Quantized exports"):
#: 'bf16' halves the artifact/device bytes (the wire-v2 dtype reused at
#: rest), 'int8' quarters them with a per-output-channel scale
WEIGHT_DTYPES = ("f32", "bf16", "int8")


class IncompatibleExport(RuntimeError):
    """A published export the live server must NOT hot-swap in:
    different model, sample shape, weight dtype, or decode capability
    than what is serving.  Typed (rides the wire ``err`` prefix like
    :class:`~theanompi_tpu.serving.batcher.Overloaded`) so the reload
    watcher refuses and keeps serving instead of crashing a replica
    mid-swap."""


def meta_path(export_dir: str, version: int) -> str:
    return os.path.join(export_dir, f"export_meta_{int(version)}.json")


# ---------------------------------------------------------------------------
# Weight quantization (bf16 / int8 weight-only)
# ---------------------------------------------------------------------------

#: structural marker of one int8-quantized leaf: a dict holding exactly
#: the quantized bytes and their per-output-channel f32 scale
_INT8_KEYS = frozenset({"int8_data", "int8_scale"})


def is_quantized_leaf(node: Any) -> bool:
    return isinstance(node, dict) and set(node.keys()) == _INT8_KEYS


def quantize_tree(params: PyTree, weight_dtype: str) -> PyTree:
    """Quantize a HOST param tree for storage (export side).

    Weight-only, matmul-applied tensors only: float32 leaves of
    ndim >= 2 (kernels, embeddings).  Biases, norms and other 1-D
    state stay f32 — their bytes are noise and their precision is not.

    * ``bf16``: the wire-v2 discipline at rest — bfloat16 keeps f32's
      exponent range, costs 16 of 24 mantissa bits (error-bound pinned
      in tests/test_decode.py).
    * ``int8``: symmetric per-output-channel scale (amax over all axes
      but the last / 127); dequantized as ``data * scale`` either at
      load or inside the jitted step (``dequantize_tree``).
    """
    if weight_dtype not in WEIGHT_DTYPES:
        raise ValueError(f"weight_dtype must be one of {WEIGHT_DTYPES}, "
                         f"got {weight_dtype!r}")
    if weight_dtype == "f32":
        return params
    if BF16 is None:  # pragma: no cover - ml_dtypes ships with jax
        raise RuntimeError("quantized exports need ml_dtypes")

    def q(leaf):
        a = np.asarray(leaf)
        if a.dtype != np.float32 or a.ndim < 2:
            return a
        if weight_dtype == "bf16":
            return a.astype(BF16)
        amax = np.max(np.abs(a), axis=tuple(range(a.ndim - 1)),
                      keepdims=True)
        scale = (np.where(amax > 0, amax, 1.0) / 127.0).astype(
            np.float32)
        data = np.clip(np.rint(a / scale), -127, 127).astype(np.int8)
        return {"int8_data": data, "int8_scale": scale}

    return jax.tree.map(q, params)


def dequantize_tree(tree: PyTree, upcast_bf16: bool = False) -> PyTree:
    """Collapse quantized nodes back to float arrays.

    jit-safe (pure ``astype``/multiply — the decode session calls it
    INSIDE the traced step so int8 weights stay int8 on device,
    docs/SERVING.md).  ``upcast_bf16=True`` additionally converts
    bf16-stored leaves to f32 — the dequantize-ON-LOAD path
    (``load_export`` default), restoring exactly what a non-quantized
    session expects.
    """
    if is_quantized_leaf(tree):
        return tree["int8_data"].astype("float32") * tree["int8_scale"]
    if isinstance(tree, dict):
        return {k: dequantize_tree(v, upcast_bf16)
                for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(dequantize_tree(v, upcast_bf16)
                          for v in tree)
    if upcast_bf16 and BF16 is not None \
            and getattr(tree, "dtype", None) == BF16:
        return np.asarray(tree, np.float32)
    return tree


def export_incompatibility(live_meta: dict, new_meta: dict) -> str | None:
    """Why a newly published export must NOT be hot-swapped into a
    server currently serving ``live_meta`` — None when compatible.
    The refusal contract the reload watcher enforces (typed
    :class:`IncompatibleExport`, docs/SERVING.md)."""
    for key in ("modelfile", "modelclass"):
        if live_meta.get(key) != new_meta.get(key):
            return (f"{key} changed "
                    f"{live_meta.get(key)!r} -> {new_meta.get(key)!r}")
    if list(live_meta.get("sample_shape") or []) != \
            list(new_meta.get("sample_shape") or []):
        return (f"sample_shape changed "
                f"{live_meta.get('sample_shape')} -> "
                f"{new_meta.get('sample_shape')}")
    if (live_meta.get("net") or {}) != (new_meta.get("net") or {}):
        # constructor dims (the transformer family's vocab/layers/
        # d_model/heads): a resized export's arrays cannot adopt into
        # sessions built around the live module — swapping it in would
        # crash-loop every replica, the exact failure refusal exists
        # to prevent
        return (f"net dims changed {live_meta.get('net')} -> "
                f"{new_meta.get('net')}")
    live_wd = live_meta.get("weight_dtype") or "f32"
    new_wd = new_meta.get("weight_dtype") or "f32"
    if live_wd != new_wd:
        return (f"weight_dtype changed {live_wd!r} -> {new_wd!r} "
                "(a live replica's compiled programs and memory plan "
                "assume the serving dtype; restart the server to "
                "change it)")
    if bool(live_meta.get("decode")) != bool(new_meta.get("decode")):
        return ("decode capability changed "
                f"{bool(live_meta.get('decode'))} -> "
                f"{bool(new_meta.get('decode'))}")
    return None


def draft_incompatibility(target_meta: dict,
                          draft_meta: dict) -> str | None:
    """Why a draft export must NOT speculate for a live target — None
    when compatible.  The draft's DIMS are free (a smaller net is the
    whole point); what must agree is the token space and the
    positional range, because the target verifies draft TOKENS, not
    draft activations:

    * ``decode`` capability — the draft runs the same decode plane;
    * ``vocab`` — a draft emitting ids the target never trained on
      (or missing ids it would propose) breaks the accept comparison;
    * the positional table must cover the target's — a draft that
      clamps positions earlier than the target silently degrades
      accept rate deep into long streams, so it is refused loudly.

    Enforced at replica construction AND by the reload watcher's
    draft poll (typed :class:`IncompatibleExport`, remembered like
    every refused publish — server keeps serving)."""
    if not draft_meta.get("decode"):
        return "draft export is not decode-capable"
    t_net = target_meta.get("net") or {}
    d_net = draft_meta.get("net") or {}
    if t_net.get("vocab") != d_net.get("vocab"):
        return (f"draft vocab {d_net.get('vocab')} != target vocab "
                f"{t_net.get('vocab')}")
    # TransformerLM's positional table: max(2048, seq_len)
    t_max = max(2048, int(t_net.get("seq_len") or 0))
    d_max = max(2048, int(d_net.get("seq_len") or 0))
    if d_max < t_max:
        return (f"draft positional table {d_max} shorter than the "
                f"target's {t_max}")
    return None


def _host(tree: PyTree) -> PyTree:
    return jax.tree.map(np.asarray, jax.device_get(tree))


def _sample_dtype(model) -> str:
    """The dtype requests arrive in — the dataset's raw row dtype when
    it ships one (uint8 under device-side augment), else the model's
    declared input dtype."""
    xv = getattr(model.data, "x_val", None)
    if xv is not None:
        return str(np.asarray(xv[:0]).dtype)
    return str(np.dtype(model._input_dtype()))


def export_model(model, export_dir: str, version: int | None = None,
                 max_to_keep: int = 5, weight_dtype: str = "f32") -> int:
    """Write one export version from a live model; returns the version.

    ``version`` defaults to the model's current epoch.  Re-exporting
    an existing version is refused (Orbax would silently skip the
    write, blessing stale files under a new manifest) — bump the
    version instead; the serving reload protocol is strictly
    monotonic.

    ``weight_dtype`` selects the stored precision of matmul-applied
    weights (``quantize_tree``): 'bf16' halves and 'int8' quarters the
    artifact and (with on-the-fly dequant) device bytes — the
    replicas-per-chip lever.  The dtype is recorded in the meta
    sidecar; a live server refuses to hot-swap across a dtype change
    (``export_incompatibility``)."""
    if version is None:
        version = int(model.current_epoch)
    version = int(version)
    payload = {"params": quantize_tree(_host(model.state.params),
                                       weight_dtype),
               "model_state": _host(model.state.model_state)}
    # sync save: when export_model returns, files AND manifest are on
    # disk — the atomic publish a watching server's poll keys off
    ckpt = Checkpointer(export_dir, max_to_keep=max_to_keep,
                        async_save=False)
    try:
        if version in ckpt.kept_epochs():
            raise ValueError(
                f"export version {version} already exists in "
                f"{export_dir}; versions are immutable — export the "
                "next one")
        ckpt.save(version, payload)
        kept = ckpt.kept_epochs()
    finally:
        ckpt.close()
    meta = {
        "version": version,
        "name": model.name,
        "modelfile": type(model).__module__,
        "modelclass": type(model).__qualname__,
        "config": dataclasses.asdict(model.config),
        "sample_shape": list(model.data.sample_shape),
        "sample_dtype": _sample_dtype(model),
        "n_classes": getattr(model.data, "n_classes", None),
        # constructor kwargs beyond ModelConfig (the transformer
        # family's vocab/seq_len/layers/dims) — without these a
        # CLI-resized export would rebuild at DEFAULT dims and fail to
        # adopt the restored arrays
        "net": getattr(model, "_net_cfg", None),
        "weight_dtype": weight_dtype,
        # decode capability: may this export serve the autoregressive
        # path (theanompi_tpu/decode)?  The hot-reload watcher refuses
        # to swap a capability change into a live replica
        "decode": bool(getattr(model, "decode_capable", False)),
        "created": time.time(),
    }
    path = meta_path(export_dir, version)
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(meta, f)
    os.replace(tmp, path)
    # prune metas of versions max_to_keep dropped (mirrors
    # recovery.prune_manifests)
    import glob
    import re

    for p in glob.glob(os.path.join(export_dir, "export_meta_*.json")):
        m = re.search(r"export_meta_(\d+)\.json$", p)
        if m and int(m.group(1)) not in kept:
            try:
                os.unlink(p)
            except OSError:
                pass
    return version


def latest_export_version(export_dir: str) -> int | None:
    """Digest-free poll hint for the reload watcher: the newest version
    whose MANIFEST and META sidecar are BOTH on disk.  export_model
    writes checkpoint files, then manifest, then meta — so the meta is
    the completed-publish marker; a manifest alone means the exporter
    died (or is still) mid-publish and the version must not be
    offered to the reload watcher yet.  Full verification happens at
    actual load."""
    import glob
    import re

    from theanompi_tpu.resilience.recovery import manifest_path

    if not os.path.isdir(export_dir):
        return None
    best = None
    for p in glob.glob(os.path.join(export_dir, "export_meta_*.json")):
        m = re.search(r"export_meta_(\d+)\.json$", p)
        if not m:
            continue
        v = int(m.group(1))
        if os.path.exists(manifest_path(export_dir, v)):
            best = v if best is None else max(best, v)
    return best


@dataclasses.dataclass
class LoadedExport:
    version: int
    params: PyTree
    model_state: dict
    meta: dict


def load_export(export_dir: str, version: int | None = None,
                dequantize: bool = True) -> LoadedExport:
    """Read-only verified load (newest verified version by default).

    ``dequantize=True`` (default) collapses any stored bf16/int8
    weights back to f32 — callers see the same tree regardless of the
    export's ``weight_dtype``.  Pass ``False`` to keep the quantized
    leaves (``{int8_data, int8_scale}`` nodes / bf16 arrays) for
    on-the-fly dequantization inside a jitted step
    (``dequantize_tree``), which keeps device memory at the quantized
    footprint."""
    from theanompi_tpu.resilience.recovery import verify_checkpoint

    ckpt = Checkpointer(export_dir, read_only=True)
    try:
        if version is None:
            v, payload = ckpt.restore_latest_verified()
            if v is None:
                raise FileNotFoundError(
                    f"no restorable export in {export_dir}")
            if not os.path.exists(meta_path(export_dir, v)):
                # the exporter died between the checkpoint publish and
                # the meta-sidecar write: the arrays restore but the
                # loader cannot rebuild a model around them.  The
                # directory contract says a half-published newest
                # version costs a fallback, never the server — walk
                # the older versions that DID finish publishing.
                for e in sorted(ckpt.kept_epochs(), reverse=True):
                    if (e >= v or not
                            os.path.exists(meta_path(export_dir, e))):
                        continue
                    if verify_checkpoint(export_dir, e)[0] is False:
                        continue
                    try:
                        v, payload = e, ckpt.restore(e)
                        break
                    except Exception:
                        continue
                else:
                    raise FileNotFoundError(
                        f"newest restorable export v{v} in "
                        f"{export_dir} has no meta sidecar and no "
                        "older fully-published version exists")
        else:
            v, payload = int(version), ckpt.restore(int(version))
    finally:
        ckpt.close()
    meta = {}
    mp = meta_path(export_dir, v)
    if os.path.exists(mp):
        with open(mp) as f:
            meta = json.load(f)
    params = payload["params"]
    if dequantize:
        params = dequantize_tree(params, upcast_bf16=True)
    return LoadedExport(int(v), params,
                        payload.get("model_state") or {}, meta)


def build_model_from_meta(meta: dict, mesh=None):
    """Reconstruct the exported model (module + config threading —
    ``bn_act_impl``, dtypes) around restored arrays.
    JSON round-trips ModelConfig's tuple fields as lists; they are
    re-tupled here so the rebuilt config equals the exporter's."""
    from theanompi_tpu.models.base import ModelConfig
    from theanompi_tpu.rules.base import resolve_model_class

    cls = resolve_model_class(meta["modelfile"], meta["modelclass"])
    fields = {f.name: f for f in dataclasses.fields(ModelConfig)}
    kw = {}
    for k, v in (meta.get("config") or {}).items():
        if k not in fields:
            continue  # a field a newer exporter knew and we don't
        kw[k] = tuple(v) if isinstance(v, list) else v
    # net kwargs: the transformer family's constructor dims (vocab,
    # seq_len, n_layers, ...) — absent for the CNN zoo
    net = meta.get("net") or {}
    return cls(config=ModelConfig(**kw), mesh=mesh, verbose=False,
               **net)


class InferenceSession:
    """One jitted eval-mode inference fn over swappable arrays.

    The compiled fn takes ``(params, model_state, x)`` — params and
    stats as ARGUMENTS, not captured constants, so a hot reload swaps
    arrays without recompiling (shapes are fixed by the export).  The
    input ``x`` is DONATED: the batcher stages a fresh padded batch
    per call, so XLA may reuse its buffer for the logits
    (tests/test_serving.py pins the aliasing in the lowering).

    ``swap``/``infer`` synchronize by publishing one tuple attribute:
    readers snapshot ``(version, params, model_state)`` in a single
    reference read, so an in-flight batch finishes entirely on the
    arrays it started with while the next batch picks up the new ones
    — the zero-dropped-requests half of the reload protocol
    (docs/SERVING.md)."""

    def __init__(self, model, params: PyTree | None = None,
                 model_state: dict | None = None, version: int = 0,
                 donate: bool = True):
        self.model = model
        self.module = model.module
        self._transform = getattr(model.data, "device_transform", None)
        params = params if params is not None else model.state.params
        ms = (model_state if model_state is not None
              else model.state.model_state)
        self._live = (int(version), self._place(params), self._place(ms))
        self._swap_lock = threading.Lock()
        self._jit = jax.jit(
            self._infer_fn, donate_argnums=(2,) if donate else ())

    @staticmethod
    def _place(tree: PyTree) -> PyTree:
        return jax.tree.map(jnp.asarray, tree)

    @property
    def version(self) -> int:
        return self._live[0]

    def _infer_fn(self, params, model_state, x):
        if self._transform is not None:
            # the dataset's EVAL transform (center crop / normalize) —
            # requests ship rows exactly as val batches do
            x = self._transform(x, None, train=False)
        variables = {"params": params, **model_state}
        logits = self.module.apply(variables, x, train=False)
        if isinstance(logits, (tuple, list)):  # aux heads (GoogLeNet)
            logits = logits[0]
        return logits.astype(jnp.float32)

    def infer(self, x) -> np.ndarray:
        version, params, ms = self._live  # one-read snapshot
        out = self._jit(params, ms, jnp.asarray(x))
        return np.asarray(jax.device_get(out))

    def swap(self, version: int, params: PyTree,
             model_state: dict) -> bool:
        """Publish a new model version (host or device trees); the
        next ``infer`` snapshot picks it up, in-flight calls finish on
        the old one.  MONOTONIC: a swap to an OLDER version than the
        live one is refused (returns False) — a replica restart that
        loaded the export while a concurrent hot reload published a
        newer version must not roll the replica back; the reload's
        arrays are themselves a fresh verified load, so the restart's
        known-good-bytes goal is already met.  Same-version swaps are
        allowed (that IS the restart: fresh bytes of what we serve)."""
        with self._swap_lock:
            if int(version) < self._live[0]:
                return False
            self._live = (int(version), self._place(params),
                          self._place(model_state))
            return True

    @classmethod
    def from_export(cls, export_dir: str, version: int | None = None,
                    mesh=None, donate: bool = True) -> "InferenceSession":
        loaded = load_export(export_dir, version)
        model = build_model_from_meta(loaded.meta, mesh=mesh)
        return cls(model, params=loaded.params,
                   model_state=loaded.model_state,
                   version=loaded.version, donate=donate)
