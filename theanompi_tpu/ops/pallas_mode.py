"""Where Pallas kernels run interpreted."""

from __future__ import annotations

import jax


def interpret() -> bool:
    """Interpret mode is for the CPU platform only (the unit tests on
    the CPU mesh).  Anywhere else a kernel compiles for the device or
    fails loudly — there is no quiet interpreted run on an
    accelerator."""
    return jax.default_backend() == "cpu"
