"""qwen3_next_delta_rule_share (%, device trace): share of device-busy
time in leaf ops under ``qwen3_next/linear_attention/delta_rule``
(theanompi_tpu/models/qwen3_next.py: the chunked gated delta rule of the
Gated DeltaNet layers), forward, backward and recomputed.  It reads the
scope, whatever implements the rule beneath it.  The pattern is data,
below.
"""

from benchmarks import scope_shares

#: searched in the op's scope
SCOPE = r"(^|/)qwen3_next/linear_attention/delta_rule(/|$)"


def read(run):
    return scope_shares.share(run, scope=SCOPE)
