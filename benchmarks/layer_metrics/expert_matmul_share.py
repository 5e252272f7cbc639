"""expert_matmul_share (%, device trace): share of device-busy time in
which the grouped expert matmul kernels ran (ops/grouped_matmul.py: the
three forward products and their six gradients, issued by
parallel/expert.py routed_experts under ZayaLM's name ``zaya_experts``).
The pattern is data, below, taken from a trace of zaya1_8b_s2048_x1
(fixtures/zaya1_8b_s2048_chip_events.json).
"""

#: matched against "<op name> <category>"
PATTERN = r"zaya_experts_(gate|up|down)_(gmm|gmm_t|tgmm)\b"


def read(run):
    if run.trace is None:
        return None
    return run.trace_lib.class_share(run.trace, PATTERN)
