"""Which of the program's layer scopes each instruction of a compiled job
belongs to.

The program names its layers with ``jax.named_scope("malstone.<layer>")``;
a scope lands in the JAX op path (``op_name`` metadata) of every
instruction traced under it. An instruction belongs to the innermost such
scope of its own path (``""`` when the path names none); a fusion carries
its root instruction's metadata, so it goes to its root's scope whatever
XLA fused into it. Instructions that XLA's passes make carry no metadata at
all (copies, relayouts, loops it builds); each goes to the scope of the
nearest instruction with a path that reads its result, a computation's
result being read by the instruction that calls it.
"""

from __future__ import annotations

import collections
import re

import trace_reduce

PREFIX = "malstone."
_OPERANDS = re.compile(r"%([\w.\-]+)")


def scope_of(op_name: str) -> str:
    """The innermost ``malstone.*`` component of a JAX op path, or ``""``."""
    for part in reversed(op_name.split("/")):
        if part.startswith(PREFIX):
            return part
    return ""


def _operands(rest: str) -> list:
    """Names of an instruction's operands, given the text after ``=``: the
    ``%`` names inside the parentheses that follow its opcode."""
    m = re.search(r"(?:^|[\s)])[a-z][\w\-]*\(", rest)
    if not m:
        return []
    depth, end = 1, m.end()
    while depth and end < len(rest):
        depth += (rest[end] == "(") - (rest[end] == ")")
        end += 1
    return _OPERANDS.findall(rest[m.end():end])


def instruction_scopes(hlo_text: str) -> dict:
    """Instruction name -> its scope (``""`` for none) for every instruction
    of an HLO module's text."""
    op_name, roots = {}, {}
    users = collections.defaultdict(list)     # instruction -> its readers
    callers = collections.defaultdict(list)   # computation -> its callers
    comp = None
    for line in hlo_text.splitlines():
        m = trace_reduce._INSTR.match(line)
        if m and comp is not None:
            name, rest = m.groups()
            path = trace_reduce._OP_NAME.search(rest)
            op_name[name] = path.group(1) if path else ""
            for o in _operands(rest):
                users[o].append(name)
            for c in trace_reduce._CALLS.findall(rest):
                callers[c].append(name)
            if line.lstrip().startswith("ROOT"):
                roots[comp] = name
            continue
        m = trace_reduce._COMP.match(line)
        if m:
            comp = m.group(1)
    for c, root in roots.items():
        users[root].extend(callers.get(c, ()))

    def scope(name):
        seen, frontier = {name}, [name]
        while frontier:
            for n in frontier:
                if op_name[n]:
                    return scope_of(op_name[n])
            readers = []
            for n in frontier:
                for u in users.get(n, ()):
                    if u not in seen:
                        seen.add(u)
                        readers.append(u)
            frontier = readers
        return ""

    return {name: scope(name) for name in op_name}
