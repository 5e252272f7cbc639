"""The documents describe the tree that is there: every repository path
a document names exists.

Checked: ``tools/...``, ``benchmarks/...``, ``theanompi_tpu/...``,
``tests/...``, ``artifacts/...``, ``docs/...`` and bare ``name.py``
(found at the root, or by its name anywhere in those directories).  A
``file.py:123`` or ``test_x.py::TestY`` suffix is dropped; a path with
``<...>`` or ``*`` in it is a pattern and must match something.  What a
run writes, and what a sentence names as removed, is listed in
``EXCUSED`` with that reason; nothing else is excused."""

from __future__ import annotations

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOTS = ("tools", "benchmarks", "theanompi_tpu", "tests", "artifacts",
         "docs")

DOCUMENTS = (["README.md", "artifacts/README.md",
              ".claude/skills/verify/SKILL.md"]
             + sorted(os.path.relpath(p, REPO) for p in
                      glob.glob(os.path.join(REPO, "docs", "*.md"))))

#: path -> why a document may name it though it is not in the checkout
EXCUSED = {
    "artifacts/jax_cache": "a run writes it (git-ignored compile cache)",
    "bench.py": "named as removed (PR 30), with what replaced it",
    "tools/preflight.sh": "named as removed (PR 30), with what replaced it",
    "tools/bench_maxpool.py": "named as removed (PR 21) with its kernel",
    "layers2.py": "the reference project's file, named as what "
                  "models/layers.py stands in for",
}

_SPAN = re.compile(r"`([^`\n]+)`")
_ROOTED = re.compile(
    r"(?<![\w./-])(?:%s)/[\w./<>*-]+" % "|".join(ROOTS))
_BARE_PY = re.compile(r"(?<![\w./<>*-])\w+\.py\b")


def _named_paths(text: str) -> set[str]:
    """Rooted paths wherever they stand (inline code may run over a line
    end, and a fenced block has no backticks of its own); bare
    ``name.py`` only inside inline code."""
    # `file.py:123`, `file.py::Test`, a sentence's full stop
    found = {re.sub(r"(\.\w+)[:.].*$", r"\1", path).rstrip("./")
             for path in _ROOTED.findall(text)}
    for span in _SPAN.findall(text):
        if not _ROOTED.search(span):
            found.update(_BARE_PY.findall(span))
    return found


@pytest.fixture(scope="module")
def basenames() -> set[str]:
    names = {f for f in os.listdir(REPO) if f.endswith(".py")}
    for root in ROOTS:
        for _dir, _subdirs, files in os.walk(os.path.join(REPO, root)):
            names.update(f for f in files if f.endswith(".py"))
    return names


def _exists(path: str, basenames: set[str]) -> bool:
    if "/" not in path:
        return path in basenames
    if "<" in path or "*" in path:
        return bool(glob.glob(os.path.join(
            REPO, re.sub(r"<[^>]*>", "*", path))))
    return os.path.exists(os.path.join(REPO, path))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_named_path_exists(document, basenames):
    with open(os.path.join(REPO, document)) as f:
        named = _named_paths(f.read())
    assert named, f"{document} names no path: the extraction is broken"
    missing = sorted(p for p in named - set(EXCUSED)
                     if not _exists(p, basenames))
    assert not missing, (
        f"{document} names paths that are not in the tree: {missing}")
