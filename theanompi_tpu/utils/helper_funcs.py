"""Small shared helpers.

Parity counterpart of the reference's ``theanompi/lib/helper_funcs.py``
(SURVEY.md §2.7 — mount empty, no file:line).  The reference's helpers
were MPI-buffer plumbing (``bufint``, ``dtype_to_mpi``) plus batch
division, learning-rate scaling and npz param save/load.  The MPI
plumbing has no TPU analogue (XLA owns the buffers); what survives is
the arithmetic and the npz format.
"""

from __future__ import annotations

import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax

PyTree = Any


#: where the persistent XLA compilation cache lives when nobody placed
#: it from outside: a FIXED path in the checkout (the path is part of
#: the cache key, so a directory that moves from run to run never hits)
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "artifacts", "jax_cache")


def enable_compilation_cache() -> str:
    """Place JAX's persistent compilation cache and return the
    directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    into ``jax_compilation_cache_dir`` at import and this sets nothing;
    otherwise the cache goes to ``<checkout>/artifacts/jax_cache``.
    Call before the first compile.  The cache key includes the XLA
    flags and the jax version, so flag sweeps (tools/xla_sweep.py)
    never cross-contaminate, and child processes find the same
    directory the same way (inherited variable, or the same checkout).
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir


def divide_batches(n_samples: int, batch_size: int, drop_remainder: bool = True) -> int:
    """Number of batches per epoch (reference dropped ragged tails)."""
    if drop_remainder:
        return n_samples // batch_size
    return -(-n_samples // batch_size)


def scale_lr(lr: float, size: int, mode: str = "linear") -> float:
    """Linear LR scaling with worker count (the reference's ``scale_lr``)."""
    if mode == "linear":
        return lr * size
    if mode == "sqrt":
        return lr * (size ** 0.5)
    raise ValueError(f"unknown lr scaling mode {mode!r}")


#: optimizer families ``build_optimizer`` knows how to assemble.  The
#: reference era was SGD+momentum only (its layers lib built momentum
#: update rules by hand); the zoo adds the families large-batch TPU
#: recipes actually use (LARS for big-batch ResNet, AdamW for
#: transformers) — all lr-mutable via inject_hyperparams so
#: ``adjust_hyperp``/``set_learning_rate`` work uniformly.
OPTIMIZERS = ("sgd", "adam", "adamw", "rmsprop", "lars")


def build_optimizer(learning_rate: float, optimizer: str = "sgd",
                    momentum: float = 0.0, nesterov: bool = False,
                    weight_decay: float = 0.0, beta1: float = 0.9,
                    beta2: float = 0.999, eps: float = 1e-8,
                    rmsprop_decay: float = 0.9,
                    lars_trust_coefficient: float = 0.001):
    """Build the framework's optimizer chain from plain hyperparams —
    shared by TpuModel and the remote parameter service, which must
    rebuild a worker's optimizer from an init message (optax transforms
    hold closures and do not pickle, so the wire format is this kwargs
    dict; see ``TpuModel.optimizer_hyperparams``).

    Weight decay for sgd / adam / rmsprop is classic L2 added to the
    grads pre-update (coupled — for adaptive optimizers it rides
    through the normalization); adamw and lars apply their own
    *decoupled* decay directly to the params.
    """
    if optimizer not in OPTIMIZERS:
        raise ValueError(f"unknown optimizer {optimizer!r}; "
                         f"choose from {OPTIMIZERS}")

    def make(learning_rate):
        parts = []
        if weight_decay and optimizer in ("sgd", "adam", "rmsprop"):
            parts.append(optax.add_decayed_weights(weight_decay))
        if optimizer == "sgd":
            parts.append(optax.sgd(learning_rate, momentum=momentum or None,
                                   nesterov=nesterov))
        elif optimizer == "adam":
            parts.append(optax.adam(learning_rate, b1=beta1, b2=beta2,
                                    eps=eps))
        elif optimizer == "adamw":
            parts.append(optax.adamw(learning_rate, b1=beta1, b2=beta2,
                                     eps=eps, weight_decay=weight_decay))
        elif optimizer == "rmsprop":
            parts.append(optax.rmsprop(learning_rate, decay=rmsprop_decay,
                                       eps=eps, momentum=momentum or None))
        elif optimizer == "lars":
            parts.append(optax.lars(
                learning_rate, weight_decay=weight_decay,
                trust_coefficient=lars_trust_coefficient,
                momentum=momentum, nesterov=nesterov))
        return optax.chain(*parts)

    return optax.inject_hyperparams(make)(learning_rate=learning_rate)


def build_sgd_optimizer(learning_rate: float, momentum: float = 0.0,
                        nesterov: bool = False, weight_decay: float = 0.0):
    """Back-compat alias: the original SGD-only builder."""
    return build_optimizer(learning_rate, optimizer="sgd",
                           momentum=momentum, nesterov=nesterov,
                           weight_decay=weight_decay)


def set_learning_rate(opt_state: PyTree, lr: float) -> PyTree:
    """Return a copy of an ``optax.inject_hyperparams`` optimizer state
    with its learning rate rewritten — pure and structure-preserving, so
    feeding it back into the jitted step does not retrace (the TPU
    analogue of the reference mutating its shared ``lr`` variable in
    ``adjust_hyperp``)."""
    old = optax.tree_utils.tree_get(opt_state, "learning_rate")
    if old is None:
        raise ValueError(
            "opt_state has no 'learning_rate' hyperparam; wrap the "
            "optimizer in optax.inject_hyperparams to make lr mutable"
        )
    return optax.tree_utils.tree_set(
        opt_state, learning_rate=jnp.asarray(lr, dtype=jnp.asarray(old).dtype)
    )


def get_learning_rate(opt_state: PyTree) -> float | None:
    lr = optax.tree_utils.tree_get(opt_state, "learning_rate")
    return None if lr is None else float(lr)


# -- flat-vector view of a param pytree (the async rules ship params as
#    one contiguous buffer, like the reference's flattened GPU buffers) --


def tree_to_vector(tree: PyTree) -> tuple[np.ndarray, Any]:
    """Flatten a pytree into one contiguous uint8 byte vector.

    Byte-exact per leaf (no dtype upcast), so mixed fp32/bf16/int trees
    round-trip losslessly and the wire size is exactly the payload size.
    """
    leaves, treedef = jax.tree.flatten(tree)
    arrs = [np.asarray(l) for l in leaves]
    if arrs:
        flat = np.concatenate([a.ravel().view(np.uint8) if a.dtype == np.uint8
                               else np.frombuffer(a.tobytes(), np.uint8)
                               for a in arrs])
    else:
        flat = np.zeros(0, np.uint8)
    meta = (treedef, [(a.shape, a.dtype) for a in arrs])
    return flat, meta


def vector_to_tree(vec: np.ndarray, meta: Any) -> PyTree:
    treedef, shapes = meta
    leaves, off = [], 0
    for shape, dtype in shapes:
        nbytes = int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
        leaves.append(
            np.frombuffer(bytes(vec[off:off + nbytes]), dtype=dtype).reshape(shape)
        )
        off += nbytes
    return jax.tree.unflatten(treedef, leaves)


def tree_size(tree: PyTree) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))


# -- npz param save/load (reference parity format, SURVEY.md §2.7) --


def _keypath_str(keypath) -> str:
    """Stable string key for one tree path (dict keys, sequence indices
    and attribute nodes — NamedTuples / flax.struct dataclasses)."""
    parts = []
    for k in keypath:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def save_params_npz(path: str, params: PyTree) -> None:
    flat = {
        _keypath_str(keypath): np.asarray(leaf)
        for keypath, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
    }
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    np.savez(path, **flat)


def load_params_npz(path: str, like: PyTree) -> PyTree:
    with np.load(path) as data:
        flat_paths = jax.tree_util.tree_flatten_with_path(like)
        leaves = []
        for keypath, leaf in flat_paths[0]:
            key = _keypath_str(keypath)
            arr = data[key]
            if arr.shape != leaf.shape:
                raise ValueError(f"shape mismatch for {key}: {arr.shape} vs {leaf.shape}")
            leaves.append(arr.astype(leaf.dtype))
    return jax.tree.unflatten(flat_paths[1], leaves)
