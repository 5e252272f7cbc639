"""peak_hbm_gb.* (GB, program counter): memory_stats() of the fullest
chip after the window, peak_bytes_in_use + peak_bytes_reserved (a
program's temporaries live in the reservation), in 1e9 bytes.
"""


def read(run):
    if not run.on_device or run.peak_bytes is None:
        return None
    return run.peak_bytes / 1e9
