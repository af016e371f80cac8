"""Minimal HLO-text parser.

Used by (1) core.payload — counting surviving noise ops (the paper's §2.3
static payload/overhead verification), and (2) roofline — summing collective
operand bytes and dot FLOPs with while-loop trip-count multipliers (XLA's
HloCostAnalysis counts loop bodies once; scanned-layer models need the
multiplier to report honest roofline terms).

The parser is deliberately text-based: it works on both ``lowered.as_text()``
(stable HLO -> HLO) and ``compiled.as_text()`` (optimized HLO), needs no XLA
internals, and is trivially portable across jax versions.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

# Dims may be static (`128`), bounded-dynamic (`<=128`), or unbounded-
# dynamic (`?`) — all three print in XLA shape strings.
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,?<=]*)\]")
# `  %name = SHAPE opcode(...)` where SHAPE is a token or a (tuple, ...)
# possibly containing /*index=N*/ comments; lazy-match up to ` opcode(`.
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"
    r"(.+?)\s+"                        # shape (token or tuple, incl. comments)
    r"([a-z][\w\-]*)\(")               # opcode
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\([^)]*\))?.*\{\s*$")
_ASSIGN_RE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=")
# op_name extraction: scoped to the metadata={...} block when one is present
# (newer XLA emits multi-attribute blocks whose other values may themselves
# contain quoted strings), with escaped-quote tolerance in the value.
_METADATA_BLOCK_RE = re.compile(r"metadata=\{([^}]*)\}")
_METADATA_RE = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def _dim_count(d: str) -> int:
    """One dim token -> element count: `<=N` uses the bound, `?` counts 1."""
    if d.startswith("<="):
        d = d[2:]
    return 1 if d == "?" else int(d)


def shape_bytes(shape: str) -> int:
    """Total bytes of an HLO shape string (tuples summed; bounded-dynamic
    dims ``<=N`` count their bound, unbounded ``?`` dims count 1)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= _dim_count(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def shape_dims(shape: str) -> list[tuple[str, tuple[int, ...]]]:
    """[(dtype, dims), ...] for each array in the shape string (dynamic
    dims resolved as in ``shape_bytes``)."""
    out = []
    for dtype, dims in _SHAPE_RE.findall(shape):
        if dtype in _DTYPE_BYTES:
            out.append((dtype,
                        tuple(_dim_count(d) for d in dims.split(",") if d)
                        if dims else ()))
    return out


def extract_op_name(line: str) -> str:
    """The metadata op_name of one instruction line ("" when absent).

    Searches inside the ``metadata={...}`` block when the line has one —
    multi-attribute blocks (``op_type=... op_name=... source_file=...``)
    from newer XLA otherwise risk matching an op_name-shaped substring in
    another attribute's value."""
    m = _METADATA_BLOCK_RE.search(line)
    md = _METADATA_RE.search(m.group(1) if m else line)
    return md.group(1) if md else ""


_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")


@dataclasses.dataclass
class Instr:
    name: str
    opcode: str
    shape: str                # result shape string
    line: str                 # raw line (operands, attrs, metadata)
    op_name: str = ""         # metadata op_name (named_scope path)
    shape_map: Optional[dict] = None   # module-wide name -> shape (shared)

    @property
    def result_bytes(self) -> int:
        return shape_bytes(self.shape)

    def _operand_span(self) -> str:
        """Text between the opcode's '(' and its matching ')'."""
        key = self.opcode + "("
        i = self.line.find(key)
        if i < 0:
            return ""
        j = i + len(key)
        depth = 1
        k = j
        while k < len(self.line) and depth:
            c = self.line[k]
            depth += (c == "(") - (c == ")")
            k += 1
        return self.line[j:k - 1]

    def operand_names(self) -> list[str]:
        return _OPERAND_NAME_RE.findall(self._operand_span())

    def operand_shapes(self) -> list[str]:
        """Operand shape strings. Optimized dumps print bare names
        (``dot(%a, %b)``) — resolved through the module shape map; lowered
        dumps print shapes inline — parsed directly."""
        span = self._operand_span()
        inline = [f"{d}[{dims}]" for d, dims in _SHAPE_RE.findall(span)]
        if inline:
            return inline
        if self.shape_map:
            return [self.shape_map[n] for n in self.operand_names()
                    if n in self.shape_map]
        return []


def parse_module(text: str) -> dict[str, list[Instr]]:
    """Split an HLO module dump into {computation_name: [Instr, ...]}."""
    comps: dict[str, list[Instr]] = {}
    cur: Optional[str] = None
    for line in text.splitlines():
        if cur is None:
            m = _COMP_RE.match(line)
            # a header is `name (sig) -> ... {` and NOT an assignment — the
            # sig may contain `=` inside /*index=N*/ comments, so test for
            # the assignment form rather than for a bare `=`.
            if m and not _ASSIGN_RE.match(line):
                cur = m.group(1)
                comps[cur] = []
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR_RE.match(line)
        if m:
            name, shape, opcode = m.groups()
            comps[cur].append(Instr(name=name, opcode=opcode, shape=shape,
                                    line=line, op_name=extract_op_name(line)))
    # module-wide name -> result shape map (operands print without shapes in
    # optimized dumps); parameters keep their declared shapes via their defs.
    shape_map: dict[str, str] = {}
    for instrs in comps.values():
        for ins in instrs:
            shape_map[ins.name] = ins.shape
    for instrs in comps.values():
        for ins in instrs:
            ins.shape_map = shape_map
    return comps


# ---------------------------------------------------------------------------
# While-loop trip counts
# ---------------------------------------------------------------------------

_CONST_RE = re.compile(r"constant\((\-?\d+)\)")
_TRIP_RE = re.compile(r'known_trip_count[^0-9]*"?n"?[^0-9]*(\d+)')


def called_comp(instr: Instr, key: str) -> Optional[str]:
    m = re.search(key + r"=%?([\w.\-]+)", instr.line)
    return m.group(1) if m else None


def while_trip_counts(comps: dict[str, list[Instr]]) -> dict[str, int]:
    """Trip count per `while` instruction name.

    Primary source: XLA's own ``backend_config={"known_trip_count":{"n":N}}``
    (present on optimized scan/fori loops). Fallback: the canonical jax
    pattern — condition ``compare(iv, limit), direction=LT`` with a constant
    limit. Unrecognized loops map to 1 (conservative).
    """
    out: dict[str, int] = {}
    for cname, instrs in comps.items():
        for ins in instrs:
            if ins.opcode != "while":
                continue
            trip = 1
            m = _TRIP_RE.search(ins.line)
            if m:
                trip = int(m.group(1))
            else:
                cond = called_comp(ins, "condition")
                if cond and cond in comps:
                    consts = [int(x) for i in comps[cond]
                              for x in _CONST_RE.findall(i.line)]
                    cmp_ok = any(i.opcode == "compare" and "LT" in i.line
                                 for i in comps[cond])
                    if consts and cmp_ok:
                        trip = max(consts)
            out[ins.name] = max(trip, 1)
    return out


def nesting_multipliers(comps: dict[str, list[Instr]],
                        entry: str) -> dict[str, int]:
    """Execution-count multiplier for every computation, walking calls from
    ``entry``: while bodies multiply by trip count, fusions/calls by 1.
    """
    trips = while_trip_counts(comps)
    mult: dict[str, int] = {}

    def visit(cname: str, m: int):
        if cname not in comps:
            return
        mult[cname] = mult.get(cname, 0) + m
        for ins in comps[cname]:
            if ins.opcode == "while":
                t = trips.get(ins.name, 1)
                body = called_comp(ins, "body")
                cond = called_comp(ins, "condition")
                if body:
                    visit(body, m * t)
                if cond:
                    visit(cond, m * (t + 1))
            elif ins.opcode in ("fusion", "call", "map", "reduce",
                                "reduce-window", "scatter", "sort",
                                "conditional", "custom-call", "all-reduce",
                                "reduce-scatter", "select-and-scatter"):
                for key in ("calls", "to_apply", "body", "branch_computations",
                            "called_computations"):
                    sub = called_comp(ins, key)
                    if sub:
                        visit(sub, m)
                # conditional: parse brace list {%a, %b}
                if ins.opcode == "conditional":
                    for mm in re.finditer(r"branch_computations=\{([^}]*)\}",
                                          ins.line):
                        for name in re.findall(r"%?([\w.\-]+)", mm.group(1)):
                            visit(name, m)

    visit(entry, 1)
    return mult


def find_entry(comps: dict[str, list[Instr]], text: str) -> str:
    m = re.search(r"^ENTRY\s+%?([\w.\-]+)", text, re.MULTILINE)
    if m:
        return m.group(1)
    # fall back: computation that is not called anywhere
    called = set()
    for instrs in comps.values():
        for ins in instrs:
            for key in ("calls", "to_apply", "body", "condition"):
                c = called_comp(ins, key)
                if c:
                    called.add(c)
    for name in comps:
        if name not in called:
            return name
    return next(iter(comps))
