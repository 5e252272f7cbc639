"""device_ms_per_step.* (ms, device trace): union of the device-op
intervals on one chip over the steps traced, mean over the chips.
"""


def read(run):
    if run.trace is None:
        return None
    return run.trace_lib.device_ms_per_step(run.trace, run.traced_steps)
