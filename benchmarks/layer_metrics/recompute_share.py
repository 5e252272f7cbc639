"""recompute_share (%, device trace): share of device-busy time in leaf
ops traced under ``checkpoint/rematted_computation``: the forwards a
``remat``ted layer runs again inside the backward pass
(theanompi_tpu/monitor/scopes.py ``parse``).  The phase is data, below.
"""

from benchmarks import scope_shares

PHASE = "recompute"


def read(run):
    return scope_shares.share(run, phase=PHASE)
