"""Local Response Normalization (AlexNet-era, across channels).

The reference got LRN from cuDNN via Theano's dnn ops (layer library
``theanompi/models/layers2.py``, SURVEY.md §2.8 — mount empty, no
file:line).  On TPU there is no library kernel to call; two impls:
a composed-XLA form (shift-and-add over the channel axis, fused by
the compiler) and a Pallas VMEM-tiled kernel with an analytic VJP
(ops/lrn_pallas.py), which is the TPU default: it microbenchmarked
~1.2-1.5x faster fwd+bwd on a v5e under the older stack (JAX 0.4.x;
not re-timed on the installed one), and compiles and matches the XLA
form at AlexNet's two LRN shapes under JAX 0.9.0 / libtpu 0.0.34
(PR 21; chip_smoke.py repeats that check).

y = x / (k + alpha/n * sum_{j in window(n)} x_j^2)^beta
(matching cuDNN/Caffe LRN, where alpha is divided by the window size;
set ``alpha_scaled_by_n=False`` for the raw AlexNet-paper variant that
uses alpha directly).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def window_sum(v: jax.Array, n: int, adjoint: bool = False) -> jax.Array:
    """Windowed sum over the last (channel) axis, same-padded — static
    shift-and-add (n is tiny, 3-5, so this beats reduce_window and is
    trivially differentiable).  The single source of truth for the
    window convention, shared by the XLA and Pallas impls: centered
    low for even n (lo=(n-1)//2); ``adjoint=True`` swaps the padding
    (the transpose the Pallas VJP needs; identical for odd n)."""
    lo = (n - 1) // 2
    hi = n - 1 - lo
    if adjoint:
        lo, hi = hi, lo
    c = v.shape[-1]
    pad = [(0, 0)] * (v.ndim - 1) + [(lo, hi)]
    padded = jnp.pad(v, pad)
    win = padded[..., 0:c]
    for d in range(1, n):
        win = win + padded[..., d:d + c]
    return win


def lrn(
    x: jax.Array,
    n: int = 5,
    k: float = 2.0,
    alpha: float = 1e-4,
    beta: float = 0.75,
    *,
    alpha_scaled_by_n: bool = True,
    impl: str | None = None,
) -> jax.Array:
    """Cross-channel LRN for NHWC input.

    ``impl``: 'auto' (None, the default), 'xla' (composed ops, fused by
    the compiler) or 'pallas' (VMEM-tiled kernel with analytic VJP,
    ops/lrn_pallas.py).  'auto' picks pallas on TPU and xla elsewhere
    (interpret-mode pallas is for tests on the CPU platform).  There
    is no compile probe and no fallback: a refused kernel raises.
    """
    if x.ndim != 4:
        raise ValueError(f"lrn expects NHWC, got shape {x.shape}")
    impl = impl or "auto"
    if impl == "auto":
        # no probe, no fallback: a kernel the compiler refuses is a
        # loud error on the chip (chip_smoke.py compiles it at the
        # zoo's shapes), never a silent switch to the other form
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas":
        from theanompi_tpu.ops.lrn_pallas import lrn_pallas

        return lrn_pallas(x, n, k, alpha, beta, alpha_scaled_by_n)
    if impl != "xla":
        raise ValueError(f"unknown lrn impl {impl!r} (want 'xla'|'pallas')")
    a = alpha / n if alpha_scaled_by_n else alpha
    return x * (k + a * window_sum(x * x, n)) ** (-beta)
