"""Operations and bytes of the Nemotron-H family (a stack of Mamba-2
state-space layers, sigmoid top-k expert layers of which this chip holds
a share beside a shared expert, and grouped-query attention layers, in
the order a pattern string gives); named by a configuration's
``flops.file``.  ``train_flops_per_sample`` is the whole step's count
behind ``mfu.tok``: the program's own (``models/nemotron_h.py
nemotron_h_train_flops``: three kinds of layer, the chunked scan's four
products as run, held experts at their EXPECTED share, attention
causally; uneven routing makes the real share another, ``mfu.tok``
decides nothing), so that there is one.  ``expert_matmul_flops`` and
``expert_matmul_bytes`` are the grouped expert product's, behind
``nemotron_expert_matmul_roofline_share``, which counts the rows really
multiplied."""

from __future__ import annotations

from theanompi_tpu.models.nemotron_h import (  # noqa: F401
    nemotron_h_train_flops as train_flops_per_sample)


def expert_matmul_flops(*, rows: float, d_model: int,
                        expert_width: int) -> float:
    """FLOPs of the grouped expert products of training over ``rows``
    token rows (summed over layers and steps): TWO products forward (up,
    down: a relu^2 expert has no gate), and for each its two gradients
    (by the rows, by the weights): 6 products of 2 x rows x d_model x
    expert_width."""
    return 6 * 2.0 * rows * d_model * expert_width


def expert_matmul_bytes(*, rows: float, layer_steps: int, held_count: int,
                        d_model: int, expert_width: int,
                        itemsize: int = 2) -> float:
    """Bytes those 6 products must move at the least, each call counted
    alone: its row operand and its row result (``rows`` x d_model or
    expert_width each), and, once for each layer of each step
    (``layer_steps``), the held experts' matrices (read by the 4 row
    products, written by the 2 weight gradients)."""
    row_bytes = rows * (d_model + expert_width) * itemsize
    weight_bytes = layer_steps * held_count * d_model * expert_width * itemsize
    return 6 * (row_bytes + weight_bytes)
