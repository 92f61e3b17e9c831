"""Classify the instructions of a compiled program's HLO text by what they
compute, never by names the program chose.

* ``a_ops``: instructions that take the data matrix A (any buffer of it)
  as an operand: the local products, which every iteration has to run.
  A is followed from the entry parameters through tuples, loop carries,
  called computations and views (bitcast, reshape, slices).
* ``collectives``: all-reduce, all-gather, reduce-scatter, all-to-all and
  collective-permute, their async start/done halves, and fusions or async
  wrappers whose called computation holds one.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

COLLECTIVE_OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
                      "all-to-all", "collective-permute")
#: Opcodes whose result is A or a part of it (followed: an instruction
#: that reads them reads A).  Those that move no data run no kernel.
_VIEWS = ("bitcast", "reshape", "copy", "slice", "dynamic-slice",
          "transpose", "optimization-barrier", "copy-start", "copy-done")
_ALIASES = ("bitcast", "reshape", "get-tuple-element", "tuple", "while",
            "parameter", "optimization-barrier", "call", "conditional")

_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->\s*(.*)\{\s*$")
_SHAPE = re.compile(r"^([a-z]+\d*)\[([\d,]*)\]")


@dataclass
class Instr:
    name: str
    shape: str
    opcode: str
    operands: list
    attrs: str
    comp: str
    param: int = -1       # a parameter's index


@dataclass
class Module:
    name: str
    comps: dict = field(default_factory=dict)      # name -> [Instr]
    entry: str = ""


def _match_paren(s: str, i: int) -> int:
    depth = 0
    for j in range(i, len(s)):
        if s[j] in "({[":
            depth += 1
        elif s[j] in ")}]":
            depth -= 1
            if depth == 0:
                return j
    return len(s) - 1


def _parse_instr(line: str, comp: str) -> Instr | None:
    line = line.strip()
    if line.startswith("ROOT "):
        line = line[5:]
    if " = " not in line:
        return None
    name, rest = line.split(" = ", 1)
    name = name.lstrip("%")
    if rest.startswith("("):
        end = _match_paren(rest, 0)
        shape, rest = rest[:end + 1], rest[end + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    m = re.match(r"([\w\-]+)\(", rest)
    if not m:
        return None
    opcode = m.group(1)
    end = _match_paren(rest, m.end() - 1)
    inner = rest[m.end():end]
    operands = re.findall(r"%([\w.\-]+)", inner)
    param = int(inner) if opcode == "parameter" and inner.isdigit() else -1
    return Instr(name=name, shape=shape, opcode=opcode, operands=operands,
                 attrs=rest[end + 1:], comp=comp, param=param)


def parse(text: str) -> Module:
    mod = Module(name="")
    comp = None
    for line in text.splitlines():
        if line.startswith("HloModule "):
            mod.name = line.split()[1].rstrip(",")
            continue
        h = _HEADER.match(line)
        if h:
            comp = h.group(2)
            mod.comps[comp] = []
            if h.group(1):
                mod.entry = comp
            continue
        if line.strip() == "}":
            comp = None
            continue
        if comp is not None:
            ins = _parse_instr(line, comp)
            if ins is not None:
                mod.comps[comp].append(ins)
    return mod


def shape_key(shape: str):
    """(dtype, dims) of an array shape string such as f32[8,128]{1,0}."""
    m = _SHAPE.match(shape)
    if not m:
        return None
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    return m.group(1), dims


def _called(ins: Instr) -> list:
    """Names of the computations ``ins`` calls."""
    out = re.findall(r"\b(?:calls|body|condition|to_apply)=%([\w.\-]+)",
                     ins.attrs)
    for group in re.findall(r"\b(?:branch_computations|called_computations)"
                            r"=\{([^}]*)\}", ins.attrs):
        out += re.findall(r"%([\w.\-]+)", group)
    return out


#: Opcodes whose events enclose the events of the ops they run.
CONTAINERS = ("while", "conditional", "call")


@dataclass
class Classes:
    module: str
    a_ops: dict            # name -> opcode
    collectives: dict      # name -> opcode
    containers: set        # control flow whose events enclose others


def classify(text: str, a_param_shapes: list) -> Classes:
    """``a_param_shapes``: the (dtype, dims) of A's buffers as the entry
    computation sees them (a device's shard on a mesh), in argument order;
    the entry parameters among the first ``len(a_param_shapes)`` whose shape
    is one of them are A."""
    mod = parse(text)
    want = set(a_param_shapes)
    # kind: True for A (or a part of it); a dict {index: kind} for tuples.
    kinds: dict = {}
    params: dict = {}          # comp -> {parameter index: kind}
    params[mod.entry] = {
        ins.param: True for ins in mod.comps.get(mod.entry, ())
        if ins.opcode == "parameter" and ins.param < len(a_param_shapes)
        and shape_key(ins.shape) in want}

    def kind_of(comp, name):
        return kinds.get((comp, name))

    a_ops, colls, containers = {}, {}, set()
    changed = True
    while changed:
        changed = False
        for comp, instrs in mod.comps.items():
            for ins in instrs:
                new = _transfer(ins, comp, kinds, params)
                if new is not None and kinds.get((comp, ins.name)) != new:
                    kinds[(comp, ins.name)] = new
                    changed = True
                # Pass operand kinds into called computations' parameters.
                calls = _called(ins)
                if ins.opcode == "while":
                    src = kind_of(comp, ins.operands[0]) if ins.operands \
                        else None
                    if src is not None:
                        for c in calls:
                            if params.setdefault(c, {}).get(0) != src:
                                params[c][0] = src
                                changed = True
                elif calls and ins.opcode in ("call", "conditional",
                                              "async-start"):
                    for c in calls:
                        for i, op in enumerate(ins.operands):
                            k = kind_of(comp, op)
                            if k is not None and \
                                    params.setdefault(c, {}).get(i) != k:
                                params[c][i] = k
                                changed = True
    fused = _fused_comps(mod)
    for comp, instrs in mod.comps.items():
        if comp in fused:
            continue
        for ins in instrs:
            reads_a = any(kind_of(comp, o) is True for o in ins.operands)
            if reads_a and ins.opcode not in _ALIASES:
                a_ops[ins.name] = ins.opcode
            if _is_collective(mod, ins):
                colls[ins.name] = ins.opcode
            if ins.opcode in CONTAINERS:
                containers.add(ins.name)
    return Classes(module=mod.name, a_ops=a_ops, collectives=colls,
                   containers=containers)


def _fused_comps(mod: Module) -> set:
    """Computations that are the bodies of fusions: their instructions run
    inside the fusion's one kernel and have no events of their own."""
    return {c for instrs in mod.comps.values() for ins in instrs
            if ins.opcode == "fusion" for c in _called(ins)}


def _is_collective(mod: Module, ins: Instr) -> bool:
    base = ins.opcode
    for suffix in ("-start", "-done"):
        if base.endswith(suffix):
            base = base[:-len(suffix)]
    if base in COLLECTIVE_OPCODES:
        return True
    if ins.opcode in ("fusion", "async-start", "async-done", "async-update"):
        for c in _called(ins):
            for inner in mod.comps.get(c, ()):
                if _is_collective(mod, inner):
                    return True
    return False


def _transfer(ins: Instr, comp: str, kinds: dict, params: dict):
    """The kind of ``ins``'s result from its operands' kinds."""
    def k(name):
        return kinds.get((comp, name))
    op = ins.opcode
    if op == "parameter":
        return params.get(comp, {}).get(ins.param)
    if op in _VIEWS:
        return k(ins.operands[0]) if ins.operands else None
    if op == "get-tuple-element":
        src = k(ins.operands[0]) if ins.operands else None
        m = re.search(r"index=(\d+)", ins.attrs)
        if isinstance(src, dict) and m:
            return src.get(int(m.group(1)))
        return None
    if op == "tuple":
        out = {i: k(o) for i, o in enumerate(ins.operands)
               if k(o) is not None}
        return out or None
    if op == "while":
        return k(ins.operands[0]) if ins.operands else None
    return None
