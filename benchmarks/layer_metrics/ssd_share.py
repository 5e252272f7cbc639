"""ssd_share (%, device trace): share of device-busy time in leaf ops
under ``nemotron_h/mamba/ssd`` (theanompi_tpu/models/nemotron_h.py: the
Mamba-2 chunked scan), forward, backward and recomputed.  It reads the
scope, whatever implements the scan beneath it.  The pattern is data,
below.
"""

from benchmarks import scope_shares

#: searched in the op's scope
SCOPE = r"(^|/)nemotron_h/mamba/ssd(/|$)"


def read(run):
    return scope_shares.share(run, scope=SCOPE)
