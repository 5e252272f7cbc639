"""backward_share (%, device trace): share of device-busy time in leaf
ops traced under ``transpose(`` and not under ``rematted_computation``
(theanompi_tpu/monitor/scopes.py ``parse``): the backward pass without
the forwards it recomputes.  The phase is data, below.
"""

from benchmarks import scope_shares

PHASE = "backward"


def read(run):
    return scope_shares.share(run, phase=PHASE)
