"""Reading a compiled HLO module's text: its computations, the
instructions of each, and what the body of a while loop reaches."""
import re

_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.-]+) \(.*\{\s*$")
_NAME = re.compile(r"^\s*(?:ROOT )?%?[\w.-]+ = ")
_OPCODE = re.compile(r" ([\w-]+)\(")
_CALLS = re.compile(
    r"\b(?:condition|body|calls|to_apply|branch_computations|"
    r"called_computations|true_computation|false_computation)="
    r"\{?(%?[\w.-]+(?:, %?[\w.-]+)*)\}?")


def _opcode(line: str) -> str | None:
    """The opcode of an instruction line, after its shape: a tuple shape
    nests parentheses (``{1,0:T(8,128)}`` layouts), so it is skipped by
    counting them."""
    mt = _NAME.match(line)
    if not mt:
        return None
    i = mt.end()
    if line[i:i + 1] == "(":
        depth = 0
        for j in range(i, len(line)):
            depth += {"(": 1, ")": -1}.get(line[j], 0)
            if depth == 0:
                break
        i = j + 1
    else:
        i = line.find(" ", i)
    op = _OPCODE.match(line, i)
    return op.group(1) if op else None


def computations(hlo: str) -> dict[str, list[tuple[str, str]]]:
    """name -> [(opcode, line)] of every computation of the module."""
    out, current = {}, None
    for line in hlo.splitlines():
        head = _HEADER.match(line)
        if head:
            current = out.setdefault(head.group(1), [])
            continue
        op = _opcode(line)
        if op and current is not None:
            current.append((op, line))
    return out


def _reach(comps: dict, root: str) -> set[str]:
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for _, line in comps[name]:
            for group in _CALLS.findall(line):
                todo += [c.strip().lstrip("%") for c in group.split(",")]
    return seen


def loop_instructions(hlo: str, marker: str) -> list[tuple[str, str]]:
    """[(opcode, line)] of every instruction the body of a while loop
    reaches, nested computations included, for the one loop whose body
    reaches a line holding ``marker``."""
    comps = computations(hlo)
    loops = []
    for body in re.findall(r" while\(.*?\bbody=%?([\w.-]+)", hlo):
        reach = _reach(comps, body)
        ins = [i for name in reach for i in comps[name]]
        if any(marker in line for _, line in ins):
            loops.append(ins)
    assert len(loops) == 1, f"{len(loops)} loops reach {marker!r}"
    return loops[0]
