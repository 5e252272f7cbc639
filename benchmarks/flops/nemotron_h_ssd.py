"""Operations and bytes of the Mamba-2 scan's kernel pair
(``theanompi_tpu/ops/ssd.py``, ``nemotron_h_ssd_fwd`` /
``nemotron_h_ssd_bwd``), behind ``ssd_roofline_share``: one CALL's,
counted from the kernels' bodies as they run.

A grid step is one chunk of ``Q`` steps of one group of ``r = H / G``
heads of one sequence: ``batch x groups x chunks`` steps a call.  With
``R = r P`` a group's lanes and ``W = max(P, 128)`` the lanes a head's
products run over (a head of 64 shares a 128-lane tile with its
neighbour and its products run over the tile, half of them on lanes it
then drops), a step's products are:

* forward (4): ``C B^T`` (Q x Q x N), ``C S_in`` (Q x N x R), a head's
  masked scores applied to ``x`` (r of Q x Q x W), ``B^T`` applied to
  ``x`` for the state (N x Q x R);
* backward (as written, the forward's in-chunk matrices remade): ``C
  B^T`` again, ``C S_in`` and ``B dS_out`` (2 of Q x N x R), per head
  ``dy x^T`` and ``M^T dy`` (2 r of Q x Q x W), then ``dC`` (Q x Q x N
  and Q x R x N), ``dB`` (the same two) and ``dS_in`` (N x Q x R).

Bytes are each HBM operand and result of the call once: ``x``, ``y``,
``dy``, ``dx`` in the compute type; ``B``, ``C`` and their gradients in
the compute type; ``dt`` and ``dt A`` as float32 rows and their
gradients; ``D`` spread over its lanes (float32, and ``dD`` a sequence
and group); the chunks' entering states ``(B, chunks, N, H P)`` float32,
which a training step's forward writes as the backward's residual and
the backward reads.  A call under a gradient, as every one of the
training cell's is, writes them.
"""

from __future__ import annotations


def _steps(batch, seq_len, groups, chunk):
    return batch * groups * (seq_len // chunk)


def ssd_kernel_flops(*, which: str, batch: int, seq_len: int, heads: int,
                     head_dim: int, groups: int, state: int,
                     chunk: int) -> float:
    """FLOPs (2 x MAC) of one ``which`` (``fwd`` / ``bwd``) call."""
    q, n, r = chunk, state, heads // groups
    lanes, width = r * head_dim, max(head_dim, 128)
    if which == "fwd":
        macs = q * q * n + 2 * q * n * lanes + r * q * q * width
    else:
        macs = 3 * q * q * n + 5 * q * n * lanes + 2 * r * q * q * width
    return 2.0 * macs * _steps(batch, seq_len, groups, chunk)


def ssd_kernel_bytes(*, which: str, batch: int, seq_len: int, heads: int,
                     head_dim: int, groups: int, state: int, chunk: int,
                     itemsize: int = 2, states: bool = True) -> float:
    """Bytes of one call's HBM operands and results, each once; the
    forward's entering states where ``states`` (a call under a
    gradient)."""
    tokens = batch * seq_len
    lanes = tokens * heads * head_dim * itemsize      # x, y, dy or dx
    groups_bc = tokens * groups * state * itemsize    # B or C
    rows = tokens * heads * 4                          # dt or dt A, f32
    skip = heads * head_dim * 4
    saved = batch * (seq_len // chunk) * state * heads * head_dim * 4
    if which == "fwd":
        return 2 * lanes + 2 * groups_bc + 2 * rows + skip \
            + (saved if states else 0)
    return (3 * lanes + 4 * groups_bc + 4 * rows + skip
            + batch * groups * (heads // groups) * head_dim * 4 + saved)
