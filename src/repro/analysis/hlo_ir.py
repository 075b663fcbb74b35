"""Module IR over XLA's HLO text dump — the shared parsing layer.

``launch/hlo_cost.py`` (trip-count-aware cost model) and
``launch/hlo_analysis.py`` (collective-byte accounting) grew the same
primitives independently: the dtype-width table, the shape regex, the
depth-aware operand splitter, the collective-op classifier. This module
is the single copy both build on, and the substrate the ``analysis``
rule engine (DESIGN.md §12) walks.

The IR is deliberately textual: ``parse_module`` turns one per-device
HLO module dump into ``{name: Computation}`` where each ``Computation``
holds its instruction list plus per-value size/type tables. That is
enough structure for byte accounting, FLOP models, async-pair windows
and the lint rules, while staying independent of jaxlib internals
(the text format is the one XLA artifact stable enough to pin in
hand-written regression tests — see tests/test_hlo_cost.py).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}
SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

COMP_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?(%?[\w.\-]+)\s*(\(.*)?\{\s*$")
# the last type alternative takes an array whose layout holds parentheses,
# as TPU tiled layouts do: u8[4,1,N]{2,1,0:T(4,128)(4,1)}
INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(%?[\w.\-]+) = "
    r"((?:\([^=]*?\)|[^(=]*?|\w+\[[\d,]*\]\{[^}]*\})) ([\w\-]+)\((.*)$")
PARAM_RE = re.compile(r"(%?[\w.\-]+):\s*((?:\w+\[[\d,]*\][^,)]*|\([^)]*\)))")
CALLED_RE = re.compile(r"(?:calls|to_apply|body)=(%?[\w.\-]+)")
COND_RE = re.compile(r"condition=(%?[\w.\-]+)")


def shape_elems_bytes(type_str: str) -> tuple[int, int]:
    """(element count, byte size) of an HLO type string (sums tuples)."""
    elems = tot = 0
    for dt, dims in SHAPE_RE.findall(type_str):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems += n
        tot += n * DTYPE_BYTES[dt]
    return elems, tot


def type_bytes(type_str: str) -> int:
    """Byte size of an HLO type string (handles tuples)."""
    return shape_elems_bytes(type_str)[1]


def first_shape_dims(type_str: str) -> list[int]:
    """Dims of the first array shape in a type string."""
    m = SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


def base_op(op: str) -> str:
    """Opcode with the SSA-uniquifying digit suffix stripped
    (``all-gather-start.42`` -> ``all-gather-start``)."""
    return op.rstrip(".0123456789")


def collective_kind(op: str) -> tuple[str | None, str]:
    """(collective kind, phase) of an opcode: phase is ``"start"`` /
    ``"done"`` for async halves, ``""`` for sync collectives; kind is
    None for non-collectives."""
    base = base_op(op)
    for kind in COLLECTIVES:
        if base.startswith(kind):
            if base == kind + "-start":
                return kind, "start"
            if base == kind + "-done":
                return kind, "done"
            if base == kind:
                return kind, ""
    return None, ""


def operand_name(o: str) -> str:
    """Reference name of one operand. Depending on XLA version the text
    form is either bare (``%foo.1``) or typed
    (``f32[1,2]{1,0} %foo.1``); take the trailing %-token."""
    toks = o.split()
    for t in reversed(toks):
        if t.startswith("%"):
            return t.lstrip("%")
    return toks[-1].lstrip("%") if toks else o


def split_top(s: str) -> list[str]:
    """Split an operand list at depth 0 commas."""
    out, depth, cur = [], 0, []
    for ch in s:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    if cur:
        out.append("".join(cur).strip())
    return out


def operand_span(rest: str) -> tuple[str, str]:
    """Split the text after an instruction's opening paren into
    (operand list, trailing attributes) at the matching close paren."""
    depth = 0
    end = len(rest)
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            if depth == 0:
                end = i
                break
            depth -= 1
    return rest[:end], rest[end + 1:]


@dataclass
class Instr:
    name: str
    type_str: str
    op: str
    operands: list[str]
    attrs: str
    line: str


@dataclass
class Computation:
    name: str
    instrs: list[Instr] = field(default_factory=list)
    sizes: dict = field(default_factory=dict)     # name -> bytes
    elems: dict = field(default_factory=dict)     # name -> element count
    types: dict = field(default_factory=dict)     # name -> type str


def parse_module(text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for raw in text.splitlines():
        # strip /*index=N*/ comments: they contain '=' and '(' characters
        # that break type/operand parsing of long tuple-typed instructions
        line = re.sub(r"/\*.*?\*/", "", raw.rstrip())
        if cur is None:
            m = COMP_HEADER_RE.match(line.strip())
            head = line.split("{")[0]
            if m and " = " not in head:
                cur = Computation(m.group(1).lstrip("%"))
                # header params carry types
                for pname, ptype in PARAM_RE.findall(line):
                    n = pname.lstrip("%")
                    e, b = shape_elems_bytes(ptype)
                    cur.sizes[n] = b
                    cur.elems[n] = e
                    cur.types[n] = ptype
            continue
        if line.strip() == "}":
            comps[cur.name] = cur
            cur = None
            continue
        m = INSTR_RE.match(line)
        if not m:
            continue
        name = m.group(1).lstrip("%")
        type_str = m.group(2).strip()
        op = m.group(3)
        span, attrs = operand_span(m.group(4))
        ops = [operand_name(o.strip()) for o in split_top(span)
               if o.strip()]
        e, b = shape_elems_bytes(type_str)
        cur.sizes[name] = b
        cur.elems[name] = e
        cur.types[name] = type_str
        cur.instrs.append(Instr(name, type_str, op, ops, attrs, line))
    return comps


def entry_name(comps: dict[str, Computation]) -> str | None:
    """The entry computation: the ``main``-named one when present (the
    jit entry), else the first parsed."""
    entry = None
    for name in comps:
        if entry is None or name.startswith("main"):
            entry = name
    return entry
