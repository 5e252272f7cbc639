"""Operations and bytes of the ZAYA1 family (compressed convolutional
attention + a top-1 expert layer of which this chip holds a share);
named by a configuration's ``flops.file``.  ``train_flops_per_sample``
is the whole step's count behind ``mfu.tok``: the program's own
(``models/zaya.py zaya_train_flops``: held experts at their EXPECTED
half, attention causally; uneven routing makes the real share another,
``mfu.tok`` decides nothing), so that there is one.
``expert_matmul_flops`` and ``expert_matmul_bytes`` are the grouped
expert product's, behind ``expert_matmul_roofline_share``, which counts
the rows really multiplied."""

from __future__ import annotations

from theanompi_tpu.models.zaya import (  # noqa: F401
    zaya_train_flops as train_flops_per_sample)


def expert_matmul_flops(*, rows: float, d_model: int,
                        expert_width: int) -> float:
    """FLOPs of the grouped expert products of training over ``rows``
    token rows (summed over layers and steps): three products forward
    (gate, up, down), and for each its two gradients (by the rows, by
    the weights): 9 products of 2 x rows x d_model x expert_width."""
    return 9 * 2.0 * rows * d_model * expert_width


def expert_matmul_bytes(*, rows: float, layer_steps: int, held_count: int,
                        d_model: int, expert_width: int,
                        itemsize: int = 2) -> float:
    """Bytes those 9 products must move at the least, each call
    counted alone: its row operand and its row result (``rows`` x
    d_model or expert_width each), and, once for each layer of each
    step (``layer_steps``), the held experts' matrices (read by the 6
    row products, written by the 3 weight gradients)."""
    row_bytes = rows * (d_model + expert_width) * itemsize
    weight_bytes = layer_steps * held_count * d_model * expert_width * itemsize
    return 9 * (row_bytes + weight_bytes)
