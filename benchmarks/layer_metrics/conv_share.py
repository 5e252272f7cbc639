"""conv_share (%, device trace): share of device-busy time in which a
convolution (or a fusion built round one) ran.  The class pattern is
data, below.
"""

#: matched against "<op name> <category>"
PATTERN = r"convolution|kOutput"


def read(run):
    if run.trace is None:
        return None
    return run.trace_lib.class_share(run.trace, PATTERN)
