"""Operations a trained sequence of a pre-LN decoder LM needs; named by a
configuration's ``flops.file``.  Copied in spirit from
``models/transformer.py _lm_train_flops``, which counts attention's
whole s x s product; this one counts it causally and says so."""

from __future__ import annotations


def train_flops_per_sample(*, n_layers: int, d_model: int, d_ff: int,
                          vocab: int, seq_len: int, **_unused) -> float:
    """Trained FLOPs per SEQUENCE of a pre-LN decoder with an untied
    output head, 2xMAC units.

    Matmul-applied parameters per layer: q, k, v, o projections
    (4 d^2) and the MLP (2 d d_ff); plus the output head (d vocab).
    The embedding gather and the positional add do no matmul work.
    Each such parameter costs 6 FLOPs per trained token (forward 2,
    backward 4).

    Attention's score and value products are counted CAUSALLY: position
    t attends to t+1 keys, so QK^T and PV cost 2 * 2 * d * s(s+1)/2
    forward per layer, x3 with the backward = 6 d s (s+1).  A kernel
    that computes the whole s x s product and masks does twice this
    work; the surplus is not useful work and is not counted, so the MFU
    read here is the conservative one.  (``_lm_train_flops`` in the
    program counts the full product, 12 L s^2 d.)
    """
    matmul_params = n_layers * (4 * d_model * d_model
                                + 2 * d_model * d_ff) + d_model * vocab
    dense = 6.0 * matmul_params * seq_len
    attention = 6.0 * n_layers * d_model * seq_len * (seq_len + 1)
    return dense + attention
