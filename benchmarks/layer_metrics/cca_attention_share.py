"""cca_attention_share (%, device trace): share of device-busy time in
which ZayaLM's attention kernels ran (ops/attention.py's forward and
fused backward, issued by models/zaya.py CCA under the name
``zaya_cca_attention``).  The pattern is data, below, taken from a trace
of zaya1_8b_s2048_x1 (fixtures/zaya1_8b_s2048_chip_events.json).
"""

#: matched against "<op name> <category>"
PATTERN = r"zaya_cca_attention_(fwd|bwd)"


def read(run):
    if run.trace is None:
        return None
    return run.trace_lib.class_share(run.trace, PATTERN)
