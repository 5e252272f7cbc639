"""attention_share (%, device trace): share of device-busy time in which
the attention kernel (ops/attention.py, a Mosaic custom call) ran.  The
class pattern is data, below.
"""

#: matched against "<op name> <category>"
PATTERN = r"tpu_custom_call"


def read(run):
    if run.trace is None:
        return None
    return run.trace_lib.class_share(run.trace, PATTERN)
