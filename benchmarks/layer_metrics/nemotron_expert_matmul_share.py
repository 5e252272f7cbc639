"""nemotron_expert_matmul_share (%, device trace): share of device-busy
time in which the grouped expert matmul kernels of NemotronHLM ran
(ops/grouped_matmul.py: the two forward products of a relu^2 expert and
their four gradients, issued by parallel/expert.py routed_experts under
the name ``nemotron_h_experts``).  The pattern is data, below, taken
from a trace of nemotron_twotower_30b_s2048_x1
(fixtures/nemotron_twotower_30b_s2048_chip_events.json).
"""

#: matched against "<op name> <category>"
PATTERN = r"nemotron_h_experts_(up|down)_(gmm|gmm_t|tgmm)\b"


def read(run):
    if run.trace is None:
        return None
    return run.trace_lib.class_share(run.trace, PATTERN)
