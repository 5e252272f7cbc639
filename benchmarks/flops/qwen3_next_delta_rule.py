"""Operations and bytes of the gated delta rule in its chunked form
(``theanompi_tpu/ops/gated_delta.py`` under models/qwen3_next.py
``GatedDeltaNetMixer``, scope ``qwen3_next/linear_attention/delta_rule``),
behind ``qwen3_next_delta_rule_roofline_share``: the WORK of one pass of
one layer, whatever implements it, so that a later kernel is judged by
the same yardstick.

A chunk of ``C`` steps of one value head, ``dk`` x ``dv`` state, takes
in its forward:

* the decay-weighted key Gram matrix ``K K^T`` (``C x C x dk``);
* the unit-lower-triangular solve for ``W`` and ``U`` (``C (C - 1) / 2 x
  (dk + dv)``);
* against the entering state ``W S`` and ``Q S`` (``C x dk x dv`` each);
* the in-chunk scores ``Q K^T`` (``C x C x dk``) applied to ``U'``
  (``C x C x dv``);
* the state's update ``K^T U'`` (``dk x C x dv``).

The backward takes each product's two gradients: twice the forward's
work.  Bytes are each HBM operand and result of a pass once: ``q``,
``k``, ``v`` and ``o`` (or ``do``) in the compute type, the decays and
write strengths float32, and the chunks' entering states ``(B, chunks,
H, dk, dv)`` float32, which a training step's forward writes as the
backward's residual and the backward reads; the backward writes
``dq``, ``dk``, ``dv``, and the decays' and strengths' gradients.
"""

from __future__ import annotations


def _chunk_macs(chunk, key_dim, value_dim):
    c, dk, dv = chunk, key_dim, value_dim
    return (2 * c * c * dk + c * c * dv + c * (c - 1) / 2 * (dk + dv)
            + 3 * c * dk * dv)


def delta_rule_flops(*, which: str, batch: int, seq_len: int, heads: int,
                     key_dim: int, value_dim: int, chunk: int) -> float:
    """FLOPs (2 x MAC) of one ``which`` (``fwd`` / ``bwd``) pass of one
    layer over ``batch`` sequences."""
    chunks = -(-seq_len // chunk)
    forward = 2.0 * _chunk_macs(chunk, key_dim, value_dim) * (
        batch * chunks * heads)
    return forward if which == "fwd" else 2 * forward


def delta_rule_bytes(*, which: str, batch: int, seq_len: int, heads: int,
                     key_dim: int, value_dim: int, chunk: int,
                     itemsize: int = 2) -> float:
    """Bytes of one pass's HBM operands and results, each once."""
    tokens = batch * seq_len * heads
    arrays = tokens * (2 * key_dim + 2 * value_dim) * itemsize  # q k v o
    rows = 2 * tokens * 4                                       # g, beta
    states = batch * (-(-seq_len // chunk)) * heads * key_dim * value_dim * 4
    if which == "fwd":
        return arrays + rows + states
    grads = tokens * (2 * key_dim + value_dim) * itemsize       # dq dk dv
    return arrays + grads + 2 * rows + states
