"""smallthinker_expert_layer_share (%, device trace): share of
device-busy time in leaf ops under ``smallthinker/router`` or
``smallthinker/experts`` (theanompi_tpu/models/smallthinker.py): the
softmax router over 64 experts, read before attention, the grouped
matmul kernels of the held ReGLU experts and the XLA passes round them
(placement, gather, scatter, combine), in every phase.  The pattern is
data, below.
"""

from benchmarks import scope_shares

#: searched in the op's scope
SCOPE = r"(^|/)smallthinker/(router|experts)(/|$)"


def read(run):
    return scope_shares.share(run, scope=SCOPE)
