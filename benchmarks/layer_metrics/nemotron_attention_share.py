"""nemotron_attention_share (%, device trace): share of device-busy time
in which NemotronHLM's attention kernels ran (ops/attention.py's forward
and fused backward at 32 query over 2 key-value heads of 128, issued by
models/nemotron_h.py AttentionMixer under the name
``nemotron_h_attention``).  The pattern is data, below, taken from a
trace of nemotron_twotower_30b_s2048_x1
(fixtures/nemotron_twotower_30b_s2048_chip_events.json).
"""

#: matched against "<op name> <category>"
PATTERN = r"nemotron_h_attention_(fwd|bwd)"


def read(run):
    if run.trace is None:
        return None
    return run.trace_lib.class_share(run.trace, PATTERN)
