"""Which layer each device operation belongs to.

The device trace names every operation by its HLO instruction text
(`%fusion.14 = s32[...] fusion(...)`), without the JAX name stack. So the
classes come from the compiled program's own HLO text, by data flow: the
benchmark names the program's arguments (`params['tables']`, `ids`,
`params['fc']`), and an instruction carries the tags of every argument it
reads, through bitcasts, tuples and loop bodies. Then:

- an HLO collective (collective-permute, all-reduce, ...) is `collective`;
- an instruction that reads an FC weight is `fc` (FC1 reads the lookup's
  output too; the matmul is the FC's work);
- one that reads only the tables or the ids is `lookup`;
- anything else is `other`.

Named scopes inside the program would make this exact; until the program
has them, the engine's combine arithmetic on the lookup's allreduce counts
as lookup, and on FC1's allreduce as fc.
"""
from __future__ import annotations

import re

COLLECTIVE_OPCODES = frozenset({
    "collective-permute", "collective-permute-start",
    "collective-permute-done", "all-reduce", "all-reduce-start",
    "all-reduce-done", "all-gather", "all-gather-start", "all-gather-done",
    "all-to-all", "reduce-scatter", "collective-broadcast",
    "ragged-all-to-all", "send", "send-done", "recv", "recv-done",
})

_COMP = re.compile(r"^(ENTRY\s+)?%(\S+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%(\S+)\s+=\s+(.*)$")
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLEES = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|"
    r"false_computation)=%([\w.\-]+)|branch_computations=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _skip_shape(s: str) -> str:
    """`s` past its leading result shape (a tuple may nest parens)."""
    if s.startswith("("):
        depth = 0
        for i, ch in enumerate(s):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                return s[i + 1:].lstrip()
    return s.split(" ", 1)[1] if " " in s else ""


def _split_call(rest: str):
    """'opcode(a, b), attrs' -> (opcode, 'a, b', attrs)."""
    op, _, tail = rest.partition("(")
    depth, i = 1, 0
    for i, ch in enumerate(tail):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            break
    return op.strip(), tail[:i], tail[i + 1:]


def parse_hlo(text: str) -> dict:
    """HLO text -> {"module": name, "entry": comp, "comps": {comp: [instr]}}
    with instr = {name, opcode, operands, callees, op_name}."""
    comps, entry, cur, module = {}, None, None, ""
    for line in text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        m = _COMP.match(line)
        if m and not _INSTR.match(line):
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                entry = cur
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR.match(line)
        if m is None or cur is None:
            continue
        name, rest = m.group(1), _skip_shape(m.group(2))
        opcode, args, attrs = _split_call(rest)
        callees = []
        for single, multi in _CALLEES.findall(attrs):
            callees += [single] if single else _OPERAND.findall(multi)
        on = _OP_NAME.search(attrs)
        comps[cur].append({"name": name, "opcode": opcode,
                           "operands": _OPERAND.findall(args),
                           "callees": callees,
                           "op_name": on.group(1) if on else ""})
    return {"module": module, "entry": entry, "comps": comps}


def classify(text: str, seed_tags) -> dict:
    """HLO text -> {instruction name: class}. `seed_tags(instr)` gives the
    tags ({"lookup", "fc"}) of an entry parameter from its name and
    op_name."""
    hlo = parse_hlo(text)
    comps = hlo["comps"]
    tags: dict = {}
    param_tags = {hlo["entry"]: None}
    order = [hlo["entry"]]
    seen = set(order)
    for comp in order:
        inherited = param_tags.get(comp)
        for ins in comps.get(comp, ()):
            if ins["opcode"] == "parameter":
                t = set(seed_tags(ins)) if inherited is None \
                    else set(inherited)
            else:
                t = set()
                for o in ins["operands"]:
                    t |= tags.get(o, set())
            tags[ins["name"]] = t
            for c in ins["callees"]:
                param_tags[c] = param_tags.get(c) or set()
                param_tags[c] |= t
                if c not in seen:
                    seen.add(c)
                    order.append(c)
    out = {}
    for comp in order:
        for ins in comps.get(comp, ()):
            t = tags[ins["name"]]
            if ins["opcode"] in COLLECTIVE_OPCODES:
                out[ins["name"]] = "collective"
            elif "fc" in t:
                out[ins["name"]] = "fc"
            elif "lookup" in t:
                out[ins["name"]] = "lookup"
            else:
                out[ins["name"]] = "other"
    return {"module": hlo["module"], "classes": out}


def dlrm_seed_tags(ins: dict) -> set:
    """Entry-parameter tags for the DLRM serving step, whose arguments the
    benchmark names `params` (a dict with `tables` and `fc`) and `ids`."""
    if "tables" in ins["op_name"] + ins["name"] or ins["op_name"] == "ids" \
            or ins["name"].startswith("ids"):
        return {"lookup"}
    if "fc" in ins["op_name"] + ins["name"]:
        return {"fc"}
    return set()


def instr_name(event_name: str) -> str:
    """'%fusion.14 = s32[...] ...' -> 'fusion.14' (a trace event's name)."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name.split(" ", 1)[0].lstrip("%")
