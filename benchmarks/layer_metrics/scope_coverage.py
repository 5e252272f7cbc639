"""scope_coverage (%, device trace): share of device-busy time in leaf
ops that the program's map of its step (theanompi_tpu/monitor/scopes.py)
places under some scope: a ``jax.named_scope`` or a flax module's name.
The witness of every other scope metric: what it leaves out is ops of
other programs, ops XLA made without metadata and the step's unscoped
glue.  The pattern is data, below.
"""

from benchmarks import scope_shares

#: searched in the op's scope: any scope but the empty one
SCOPE = r"."


def read(run):
    return scope_shares.share(run, scope=SCOPE)
