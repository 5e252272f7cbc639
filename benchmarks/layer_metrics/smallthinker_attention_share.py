"""smallthinker_attention_share (%, device trace): share of device-busy
time in leaf ops under ``smallthinker/window_attention`` or
``smallthinker/global_attention`` (theanompi_tpu/models/smallthinker.py
``Attention``): the four projections, the rotation in XLA, the streamed
kernels and the layout passes round them, in every phase.  The pattern
is data, below.
"""

from benchmarks import scope_shares

#: searched in the op's scope
SCOPE = r"(^|/)smallthinker/(window|global)_attention(/|$)"


def read(run):
    return scope_shares.share(run, scope=SCOPE)
