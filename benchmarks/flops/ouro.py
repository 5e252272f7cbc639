"""Operations and bytes of the Ouro family (one stack of layers run
``total_ut_steps`` times over shared weights, the head after every
pass); named by a configuration's ``flops.file``.
``train_flops_per_sample`` is the whole step's count behind ``mfu.tok``:
the program's own (``models/ouro.py ouro_train_flops``: every pass of
every layer and of the head, attention causally, recomputed forwards
NOT counted), so that there is one.  ``attention_flops`` and
``attention_bytes`` are one call's of the attention kernel, behind
``attention_roofline_share``, which counts the calls from the trace (a
recomputed forward is a call)."""

from __future__ import annotations

from theanompi_tpu.models.ouro import (  # noqa: F401
    ouro_train_flops as train_flops_per_sample)

#: matrix products a call, each over the scores the causal mask leaves:
#: the forward's q k^T and p v; the backward's recomputed q k^T (the
#: algorithm's own), dp = g v^T, dv = p^T g, dq = ds k, dk = ds^T q
PRODUCTS = {"fwd": 2, "bwd": 5}
#: (tokens, heads, head_dim) arrays a call moves once at the least:
#: q, k, v in and o out; the backward q, k, v, o, g in and dq, dk, dv out
ARRAYS = {"fwd": 4, "bwd": 8}


def attention_flops(*, which: str, batch: int, heads: int, head_dim: int,
                    seq_len: int) -> float:
    """FLOPs of ONE call (``which``: ``fwd`` or ``bwd``): each product
    is 2 x head_dim for every score the mask leaves, s (s + 1) / 2 a
    head and sequence."""
    return (PRODUCTS[which] * 2.0 * batch * heads * head_dim
            * seq_len * (seq_len + 1) / 2)


def attention_bytes(*, which: str, batch: int, heads: int, head_dim: int,
                    seq_len: int, itemsize: int = 2) -> float:
    """Bytes ONE call must move at the least: its arrays once (the
    row statistics, 4 bytes a row and head, are left out)."""
    return ARRAYS[which] * float(batch * seq_len * heads * head_dim) * itemsize
