"""loss_share (%, device trace): share of device-busy time in leaf ops
under a model's loss scope (``lm/loss``, ``zaya/loss``, ``ouro/loss``,
``nemotron_h/loss``: head, softmax and cross-entropy in token blocks), in
every phase: the blocked loss computes its gradient in the forward pass.
The head's optimizer update is under ``bsp/update``, not here.  The
pattern is data, below.
"""

from benchmarks import scope_shares

#: searched in the op's scope
SCOPE = r"(^|/)(lm|zaya|ouro|nemotron_h)/loss(/|$)"


def read(run):
    return scope_shares.share(run, scope=SCOPE)
