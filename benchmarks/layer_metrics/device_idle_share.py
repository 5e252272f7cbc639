"""device_idle_share.* (%, device trace): 1 - busy over the traced
segment, mean over the chips.
"""


def read(run):
    if run.trace is None:
        return None
    return run.trace_lib.idle_share(run.trace)
