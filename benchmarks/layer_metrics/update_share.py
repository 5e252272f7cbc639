"""update_share (%, device trace): share of device-busy time in leaf ops
under the scope ``bsp/update`` (theanompi_tpu/parallel/bsp.py
``apply_update``: the optimizer's update and its application to the
parameters).  The pattern is data, below.
"""

from benchmarks import scope_shares

#: searched in the op's scope
SCOPE = r"^bsp/update"


def read(run):
    return scope_shares.share(run, scope=SCOPE)
