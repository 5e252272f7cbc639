"""allreduce_exposed_ms_per_step (ms, device trace): time a step during
which an all-reduce ran on a chip and no other op did, mean over the
chips.  The class pattern is data, below.
"""

#: matched against "<op name> <category>"
PATTERN = r"all-reduce"


def read(run):
    if run.trace is None:
        return None
    return run.trace_lib.exposed_ms_per_step(run.trace, PATTERN,
                                             run.traced_steps)
