"""Operations and bytes of the Qwen3-Next family (Gated DeltaNet layers
three in four, gated grouped-query attention in the fourth, a softmax
top-k expert layer of which this chip holds a share beside a gated
shared expert in every layer); named by a configuration's
``flops.file``.  ``train_flops_per_sample`` is the whole step's count
behind ``mfu.tok``: the program's own (``models/qwen3_next.py
qwen3_next_train_flops``: the chunked rule's products as run, held
experts at their EXPECTED share, attention causally; uneven routing
makes the real share another, ``mfu.tok`` decides nothing), so that
there is one.  ``attention_flops`` and ``attention_bytes`` are one call's
of the attention kernel at grouped heads, behind
``qwen3_next_attention_roofline_share``, which counts the calls from the
trace (a recomputed forward is a call)."""

from __future__ import annotations

from theanompi_tpu.models.qwen3_next import (  # noqa: F401
    qwen3_next_train_flops as train_flops_per_sample)

#: matrix products a call, each over the scores the causal mask leaves:
#: the forward's q k^T and p v; the backward's recomputed q k^T (the
#: algorithm's own), dp = g v^T, dv = p^T g, dq = ds k, dk = ds^T q
PRODUCTS = {"fwd": 2, "bwd": 5}
#: (tokens, query heads, head_dim) arrays a call moves once at the
#: least, and (tokens, key/value heads, head_dim) ones: the forward q, o
#: and k, v; the backward q, o, g, dq and k, v, dk, dv
QUERY_ARRAYS = {"fwd": 2, "bwd": 4}
SHARED_ARRAYS = {"fwd": 2, "bwd": 4}


def attention_flops(*, which: str, batch: int, heads: int, head_dim: int,
                    seq_len: int, **_shared) -> float:
    """FLOPs of ONE call (``which``: ``fwd`` or ``bwd``): each product
    is 2 x head_dim for every score the mask leaves, s (s + 1) / 2 a
    query head and sequence."""
    return (PRODUCTS[which] * 2.0 * batch * heads * head_dim
            * seq_len * (seq_len + 1) / 2)


def attention_bytes(*, which: str, batch: int, heads: int, kv_heads: int,
                    head_dim: int, seq_len: int, itemsize: int = 2) -> float:
    """Bytes ONE call must move at the least: its arrays once, the
    key/value ones at their own head count (the row statistics, 4 bytes
    a row and head, are left out)."""
    return (QUERY_ARRAYS[which] * heads + SHARED_ARRAYS[which] * kv_heads
            ) * float(batch * seq_len * head_dim) * itemsize
