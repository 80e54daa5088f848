"""Reading the layer and the stencil node of each instruction of a compiled
dycore step from its ``op_name`` metadata (shared by the scope tests)."""

from __future__ import annotations

import collections
import re

from repro.fv3.dyncore import HALO_SCOPE, STEP_SCOPES

PROGRAM_SCOPES = tuple(s for s in STEP_SCOPES if s != HALO_SCOPE)
#: the step's own jit, the root of every op_name inside it
STEP = "jit(_inner)"
#: components of an op_name that only the step's own loops add
LOOP = {"_inner", "while", "body", "cond", "closed_call", "scan"}
#: instructions that move no data of their own
NO_WORK = {"parameter", "get-tuple-element", "tuple", "constant", "bitcast",
           "while", "call", "conditional", "after-all", "opt-barrier"}

# names carry a "%" in a compiled module's text, none before compiling
_COMPUTATION = re.compile(r"^(ENTRY )?%?(\S+) .*\{\s*$")
#: an instruction's opcode is the first lower-case word before a "(" after
#: its type (tuple types hold spaces; layouts' tags are upper-case)
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?(\S+) = .*? ([a-z][\w\-]*)\((.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_LABEL = re.compile(r"\S+#f?\d+")


def instructions(hlo: str) -> list[tuple]:
    """(name, opcode, op_name or None, rest of line) of every instruction
    in a computation that runs as a sequence (the entry, loop bodies and
    conditions, called computations), not inside a fusion or a reducer.
    Before compiling, a called computation's op_names start afresh at the
    call (the compiler's inliner prefixes them with the call's); they are
    given the call's op_name as a prefix here."""
    comps: dict[str, list] = {}
    entry, cur = None, None
    for line in hlo.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            entry = entry or (cur if m.group(1) else None)
            continue
        m = _INSTRUCTION.match(line)
        if m and cur:
            on = _OP_NAME.search(m.group(3))
            comps[cur].append((m.group(1), m.group(2), on and on.group(1),
                               m.group(3)))
    out, seen, todo = [], set(), [(entry, "")]
    while todo:
        c, prefix = todo.pop()
        if (c, prefix) in seen:
            continue
        seen.add((c, prefix))
        for name, op, on, rest in comps[c]:
            if on and prefix and not on.startswith(STEP):
                on = f"{prefix}/{on}"
            out.append((name, op, on, rest))
            callees = re.findall(
                r"(?:body|condition|to_apply|branch_computations)="
                r"\{?%?([\w.\-]+)", rest) + re.findall(
                r"(?:true|false)_computation=%?([\w.\-]+)", rest)
            if op == "call":
                todo += [(k, on or prefix) for k in callees]
            elif op in ("while", "conditional"):
                todo += [(k, prefix) for k in callees]
    return out


def parts(op_name: str) -> list[str]:
    """The op_name's path, each transform wrapper (``vmap(...)``) removed:
    ``d_sw/vmap(al_x#3)/jit(al_x)`` -> ``["d_sw", "al_x#3", "al_x"]``."""
    out = []
    for p in op_name.split("/"):
        while (m := re.fullmatch(r"\w+\((.*)\)", p)):
            p = m.group(1)
        out.append(p)
    return out


def check(hlo: str) -> tuple[collections.Counter, dict]:
    """Assert that every work instruction of the step falls under exactly
    one layer scope, apart from the loops' own bookkeeping, and that each
    under a program scope carries one stencil node's label, with the
    kernel's jit named after the node's stencil next to it.  Returns the
    instruction count of each layer and {instruction name: node label}."""
    per_layer: collections.Counter = collections.Counter()
    nodes, unscoped = {}, []
    for name, op, op_name, _ in instructions(hlo):
        # no op_name: an instruction the compiler put in (a copy or a
        # layout change of a loop carry); an op_name outside the step's
        # trace: the step's arguments
        if op in NO_WORK or op_name is None \
                or not op_name.startswith(STEP):
            continue
        path = parts(op_name)
        layers = [p for p in path if p in STEP_SCOPES]
        if not layers:
            # the loops' own counters and carries
            if not set(path[:-1]) <= LOOP:
                unscoped.append(op_name)
            continue
        assert len(layers) == 1, op_name
        per_layer[layers[0]] += 1
        if layers[0] not in PROGRAM_SCOPES:
            continue
        after = path[path.index(layers[0]) + 1:]
        labels = [p for p in after if _LABEL.fullmatch(p)]
        if not labels and after == ["", "transpose"]:
            # the tile vmap moving the axes of a member-batched program's
            # arguments (``vmap()/transpose``)
            continue
        assert len(labels) == 1, op_name
        i = after.index(labels[0])
        assert after[i + 1:i + 2] in ([], [labels[0].rsplit("#", 1)[0]]), \
            op_name
        nodes[name] = labels[0]
    assert not unscoped, unscoped[:5]
    return per_layer, nodes
