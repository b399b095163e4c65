"""Reading a compiled module's text: its computations, and the
instructions that each conditional's branches run."""

from __future__ import annotations

import re

_HEAD = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_CALL = re.compile(r"\b(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_CALLS = re.compile(r"\b(?:branch_computations|called_computations)="
                    r"\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def computations(text: str) -> dict:
    """{computation name: the lines of its instructions}."""
    out, cur = {}, None
    for line in text.splitlines():
        if not line.startswith(" "):
            head = _HEAD.match(line)
            if head:
                cur = out.setdefault(head.group(1), [])
                continue
            if line == "}":
                cur = None
                continue
        if cur is not None:
            cur.append(line)
    return out


def _callees(lines) -> set:
    out = set()
    for line in lines:
        out.update(_CALL.findall(line))
        for group in _CALLS.findall(line):
            out.update(c.strip().lstrip("%") for c in group.split(","))
    return out


def _reached(comps: dict, root: str) -> list:
    seen, todo, lines = set(), [root], []
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        lines += comps[name]
        todo += _callees(comps[name])
    return lines


def op_name(line: str) -> str:
    hit = _OP_NAME.search(line)
    return hit.group(1) if hit else ""


def conditionals(text: str) -> list:
    """[(the conditional's line, [the lines each branch runs, the
    computations it calls included])], branches in index order."""
    comps = computations(text)
    out = []
    for lines in comps.values():
        for line in lines:
            if " conditional(" in line:
                names = _CALLS.search(line).group(1).split(",")
                out.append((line, [_reached(comps, n.strip().lstrip("%"))
                                   for n in names]))
    return out
