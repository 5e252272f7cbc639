"""Data dependence between the ops of a lowered StableHLO text.

The order of the lines in a lowering is the order of the jaxpr's
equations, which JAX is free to choose (0.9.0 puts every bucket's
collective after the whole backward, because the bucket tags are the
forward's FIRST equations and the backward pass walks them last).  What
a scheduler may overlap is decided by what an op depends on, so the
structural pins of the bucketed exchange ask that."""

import re

_NAME = re.compile(r"%[\w.]+")


def ancestors(txt: str, line: int) -> set[int]:
    """Indices of the lines whose results the op printed on line
    ``line`` of ``txt`` depends on, directly or through other ops."""
    lines = txt.splitlines()
    # value names start again in every function: stay inside the op's
    funcs = [i for i, text in enumerate(lines) if "func.func" in text]
    start = max(i for i in funcs if i <= line)
    end = min([i for i in funcs if i > line], default=len(lines))
    defined_on: dict[str, int] = {}
    uses: dict[int, list[str]] = {}
    for i in range(start + 1, end):
        lhs, eq, rhs = lines[i].partition(" = ")
        if not eq or not lhs.strip().startswith("%"):
            continue
        for name in _NAME.findall(lhs):  # "%7:2" defines %7
            defined_on[name] = i
        uses[i] = _NAME.findall(rhs)     # "%7#0" names %7
    seen: set[int] = set()
    todo = [line]
    while todo:
        for name in uses.get(todo.pop(), ()):
            i = defined_on.get(name)
            if i is not None and i not in seen:
                seen.add(i)
                todo.append(i)
    return seen
