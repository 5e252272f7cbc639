"""expert_layer_share (%, device trace): share of device-busy time in
leaf ops under ``zaya/experts`` or ``nemotron_h/experts``: the grouped
matmul kernels AND the XLA passes round them (sort, gather, scatter,
combine), in every phase.  ``*expert_matmul_share`` reads the kernels
alone.  The pattern is data, below.
"""

from benchmarks import scope_shares

#: searched in the op's scope
SCOPE = r"(^|/)(zaya|nemotron_h)/experts(/|$)"


def read(run):
    return scope_shares.share(run, scope=SCOPE)
