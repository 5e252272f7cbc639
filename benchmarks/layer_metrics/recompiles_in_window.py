"""recompiles_in_window.* (count, program counter): compilations (real or
loaded from the cache) that JAX's monitoring reported between the end of
warm-up and the end of the window.  Must read 0.
"""


def read(run):
    return run.recompiles
