#!/usr/bin/env python
"""Repo entry point for the static checker suite (docs/ANALYSIS.md).

Loads ``theanompi_tpu.analysis`` WITHOUT executing the package
``__init__`` (whatever it grows to import): a stub parent module with
``__path__`` pointing at the real package directory is installed
first, so the subpackage resolves from the filesystem while the
parent's body never runs.  The gate is therefore pure stdlib end to
end — it runs on a cold box with a broken or absent jax install and
can never touch (or be wedged by) a device runtime.  (The installed
``tmlint`` console script imports the real package instead — same
checkers, but it needs a working environment.)

    python tools/tmlint.py --gate
"""

from __future__ import annotations

import os
import sys
import types

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

if "theanompi_tpu" not in sys.modules:
    _stub = types.ModuleType("theanompi_tpu")
    _stub.__path__ = [os.path.join(_REPO, "theanompi_tpu")]
    sys.modules["theanompi_tpu"] = _stub

from theanompi_tpu.analysis.cli import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main())
