"""Operations and bytes of the SmallThinker family (global attention
without positions one layer in four beside sliding-window attention
with RoPE, a softmax top-k layer of ReGLU experts of which this chip
holds a share in every layer); named by a configuration's
``flops.file``.  ``train_flops_per_sample`` is the whole step's count
behind ``mfu.tok``: the program's own (``models/smallthinker.py
smallthinker_train_flops``: held experts at their EXPECTED share,
attention over the pairs each layer's mask leaves), so that there is
one.  ``attention_flops`` and ``attention_bytes`` are one call's of the
attention kernels at grouped heads, under a window or globally, behind
``smallthinker_{window,global}_attention_roofline_share``, which count
the calls from the trace (a recomputed forward is a call; a backward is
its dK/dV and dQ kernels together).  The count is of the work, whatever
implements it."""

from __future__ import annotations

from theanompi_tpu.models.smallthinker import (  # noqa: F401
    smallthinker_train_flops as train_flops_per_sample, window_pairs)

#: matrix products a call, each over the pairs the mask leaves: the
#: forward's q k^T and p v; the backward's recomputed q k^T (the
#: algorithm's own), dp = g v^T, dv = p^T g, dq = ds k, dk = ds^T q
PRODUCTS = {"fwd": 2, "bwd": 5}
#: (tokens, query heads, head_dim) arrays a call moves once at the
#: least, and (tokens, key/value heads, head_dim) ones: the forward q, o
#: and k, v; the backward q, o, g, dq and k, v, dk, dv
QUERY_ARRAYS = {"fwd": 2, "bwd": 4}
SHARED_ARRAYS = {"fwd": 2, "bwd": 4}


def attention_flops(*, which: str, batch: int, heads: int, head_dim: int,
                    seq_len: int, window: int | None = None,
                    **_shared) -> float:
    """FLOPs of ONE call (``which``: ``fwd`` or ``bwd``): each product
    is 2 x head_dim for every pair the mask leaves (``window_pairs``) a
    query head and sequence."""
    return (PRODUCTS[which] * 2.0 * batch * heads * head_dim
            * window_pairs(seq_len, window))


def attention_bytes(*, which: str, batch: int, heads: int, kv_heads: int,
                    head_dim: int, seq_len: int, itemsize: int = 2,
                    **_window) -> float:
    """Bytes ONE call must move at the least: its arrays once, the
    key/value ones at their own head count (the row statistics, 4 bytes
    a row and head, are left out)."""
    return (QUERY_ARRAYS[which] * heads + SHARED_ARRAYS[which] * kv_heads
            ) * float(batch * seq_len * head_dim) * itemsize
