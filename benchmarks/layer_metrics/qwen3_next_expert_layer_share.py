"""qwen3_next_expert_layer_share (%, device trace): share of
device-busy time in leaf ops under ``qwen3_next/router``,
``qwen3_next/experts`` or ``qwen3_next/shared_expert``
(theanompi_tpu/models/qwen3_next.py SparseMoe): the softmax router over
512 experts, the grouped matmul kernels and the XLA passes round them
(placement, gather, scatter, combine), and the gated shared expert, in
every phase.  The pattern is data, below.
"""

from benchmarks import scope_shares

#: searched in the op's scope
SCOPE = r"(^|/)qwen3_next/(router|experts|shared_expert)(/|$)"


def read(run):
    return scope_shares.share(run, scope=SCOPE)
