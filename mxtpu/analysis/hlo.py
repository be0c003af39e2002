"""Structural parser for XLA HLO text.

The ONE HLO parser in the tree (ISSUE 6): ``tests/test_zero.py``'s
regex helpers and every future compiled-artifact check go through
this module instead of re-growing ad-hoc ``re.findall`` over
``hlo_text()``.  Scope is deliberately the two dump formats this
repo's jaxlib emits: ``compiled.as_text()`` (post-optimization) and
``lowered.as_text(dialect="hlo", debug_info=True)`` (pre-optimization)
— instruction lines of the form::

    [ROOT ][%]name = <shape> opcode(operands), attr=..., metadata={...}

grouped into computations (``ENTRY`` marks the entry one).  Both
dumps put source locations in ``FileNames``/``FileLocations``/
``StackFrames`` tables ahead of the computations and tag instructions
with ``stack_frame_id=N``; :func:`inline_source_sites` rewrites that
into per-instruction ``source_file="..." source_line=N``.
Unknown lines are skipped, not errors: the parser must survive
dialect drift across jaxlib upgrades and report *less*, never crash.

Pure stdlib — importable without jax so ``tools/hlocheck`` can check
saved dumps and mxlint-adjacent tooling can reuse it.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

# bytes per element for HLO primitive types (token/opaque count as 0)
DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "f8e4m3fnuz": 1, "f8e5m2fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_FLOAT_WIDTH = {"f8e4m3fn": 1, "f8e5m2": 1, "f16": 2, "bf16": 2,
                "f32": 4, "f64": 8}

_NAME_RE = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*")
_SIMPLE_SHAPE_RE = re.compile(
    r"[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?")
_SHAPE_TOKEN_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_OPCODE_RE = re.compile(r"\s*([\w\-]+)\s*\(")
_NAME_TOKEN_RE = re.compile(r"%?([A-Za-z_][\w.\-]*)$")
_COMMENT_RE = re.compile(r"/\*.*?\*/")
# attributes whose value names computation(s): `calls=%f`,
# `to_apply=region_0.1`, `branch_computations={%a, %b}`
_CALL_ATTR_RE = re.compile(
    r"\b(?:calls|to_apply|body|condition|select|scatter|comparator|"
    r"true_computation|false_computation|branch_computations|"
    r"called_computations)=(\{[^}]*\}|%?[\w.\-]+)")
_FRAME_ID_RE = re.compile(r"stack_frame_id=(\d+)")
_FRAME_LOC_RE = re.compile(r"file_location_id=(\d+)")
_LOC_FILE_RE = re.compile(r"file_name_id=(\d+)")
_LOC_LINE_RE = re.compile(r"\bline=(\d+)")
_TABLE_ROW_RE = re.compile(r"(\d+)\s+(.*)$")
_TABLE_NAMES = ("FileNames", "FunctionNames", "FileLocations",
                "StackFrames")
# opcodes whose parenthesised text is a literal, not operand names
_LITERAL_OPS = ("parameter", "constant", "iota")
_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')
_STRING_RE = re.compile(r'"[^"]*"')


def shape_elems(dims: Tuple[int, ...]) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


class Instruction:
    """One HLO instruction: result shape(s), opcode, operand names,
    raw attribute text."""

    __slots__ = ("name", "opcode", "root", "shapes", "operands",
                 "attrs", "target", "calls")

    def __init__(self, name: str, opcode: str, root: bool,
                 shapes: List[Tuple[str, Tuple[int, ...]]],
                 operands: List[str], attrs: str):
        self.name = name
        self.opcode = opcode
        self.root = root
        self.shapes = shapes          # [(dtype, dims), ...]
        self.operands = operands      # names used inside the parens
        self.attrs = attrs            # raw text after the operand list
        m = _TARGET_RE.search(attrs)
        self.target: Optional[str] = m.group(1) if m else None
        # computations referenced from attributes (calls=, to_apply=,
        # body=/condition=, branch_computations={...}); attribute
        # strings are stripped first so quoted text can't alias a name
        self.calls: List[str] = [
            n for value in _CALL_ATTR_RE.findall(
                _STRING_RE.sub('""', attrs))
            for n in _names_in(value.strip("{}"))]

    def result_bytes(self) -> int:
        return sum(DTYPE_BYTES.get(dt, 0) * shape_elems(dims)
                   for dt, dims in self.shapes)

    def result_elems(self) -> int:
        return sum(shape_elems(dims) for dt, dims in self.shapes
                   if dt in DTYPE_BYTES)

    def dtypes(self) -> List[str]:
        return [dt for dt, _ in self.shapes]


class Computation:
    __slots__ = ("name", "is_entry", "instructions", "by_name",
                 "_consumers")

    def __init__(self, name: str, is_entry: bool):
        self.name = name
        self.is_entry = is_entry
        self.instructions: List[Instruction] = []
        self.by_name: Dict[str, Instruction] = {}
        self._consumers: Optional[Dict[str, List[Instruction]]] = None

    def add(self, instr: Instruction) -> None:
        self.instructions.append(instr)
        self.by_name[instr.name] = instr

    def consumers(self, name: str) -> List[Instruction]:
        if self._consumers is None:
            cons: Dict[str, List[Instruction]] = {}
            for i in self.instructions:
                for op in i.operands:
                    cons.setdefault(op, []).append(i)
            self._consumers = cons
        return self._consumers.get(name, [])


class HloProgram:
    """All computations of one HLO module, entry marked."""

    def __init__(self, computations: Dict[str, Computation],
                 entry: Optional[str]):
        self.computations = computations
        self.entry_name = entry

    @property
    def entry(self) -> Optional[Computation]:
        return self.computations.get(self.entry_name) \
            if self.entry_name else None

    def all_instructions(self) -> Iterable[Instruction]:
        for comp in self.computations.values():
            for instr in comp.instructions:
                yield instr

    def instruction_count(self) -> int:
        return sum(len(c.instructions)
                   for c in self.computations.values())

    def count_opcode(self, opcode: str) -> int:
        return sum(1 for i in self.all_instructions()
                   if i.opcode == opcode)


def _names_in(text: str) -> List[str]:
    """Instruction/computation names in a comma-separated list: each
    item's last token (``f32[4]{0} %x``, ``%x`` and ``x`` all name
    ``x``); literals (``0``, ``{1,2}``) name nothing."""
    names = []
    depth = 0
    item = []
    for ch in _COMMENT_RE.sub("", text) + ",":
        depth += (ch in "({[") - (ch in ")}]")
        if ch == "," and depth == 0:
            tokens = "".join(item).split()
            m = _NAME_TOKEN_RE.match(tokens[-1]) if tokens else None
            if m:
                names.append(m.group(1))
            item = []
        else:
            item.append(ch)
    return names


def _parse_instruction(line: str) -> Optional[Instruction]:
    m = _NAME_RE.match(line)
    if not m:
        return None
    root, name = bool(m.group(1)), m.group(2)
    rest = line[m.end():]
    # result shape: either a (possibly nested) tuple or a simple
    # array/token shape with optional layout braces
    if rest.startswith("("):
        depth = 0
        end = -1
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                end = i
                break
        if end < 0:
            return None
        shape_text, rest = rest[:end + 1], rest[end + 1:]
    else:
        sm = _SIMPLE_SHAPE_RE.match(rest)
        if not sm:
            return None
        shape_text, rest = sm.group(0), rest[sm.end():]
    om = _OPCODE_RE.match(rest)
    if not om:
        return None
    opcode = om.group(1)
    # operand list: balanced parens starting at the opcode's "("
    start = om.end() - 1
    depth = 0
    end = -1
    for i in range(start, len(rest)):
        depth += (rest[i] == "(") - (rest[i] == ")")
        if depth == 0:
            end = i
            break
    if end < 0:
        return None
    operand_text = rest[start + 1:end]
    attrs = rest[end + 1:]
    shapes = [(dt, tuple(int(x) for x in dims.split(",") if x))
              for dt, dims in _SHAPE_TOKEN_RE.findall(shape_text)]
    operands = [] if opcode in _LITERAL_OPS else _names_in(operand_text)
    return Instruction(name, opcode, root, shapes, operands, attrs)


def inline_source_sites(text: str) -> str:
    """Rewrite each instruction's ``stack_frame_id=N`` as the
    innermost frame's ``source_file="..." source_line=L`` and drop
    the location tables.  The tables also hold every OUTER frame of
    the trace (the caller of ``lower()`` included), so without this
    the same program lowered from two call sites differs textually."""
    table: Optional[str] = None
    tables: Dict[str, Dict[str, str]] = {t: {} for t in _TABLE_NAMES}

    def resolve(m: "re.Match") -> str:
        frame = tables["StackFrames"].get(m.group(1), "")
        loc = _FRAME_LOC_RE.search(frame)
        loc = tables["FileLocations"].get(loc.group(1), "") \
            if loc else ""
        fid = _LOC_FILE_RE.search(loc)
        lineno = _LOC_LINE_RE.search(loc)
        fname = tables["FileNames"].get(fid.group(1)) if fid else None
        if fname is None or lineno is None:
            return ""
        return f"source_file={fname} source_line={lineno.group(1)}"

    out = []
    for line in text.splitlines():
        stripped = line.strip()
        if stripped in _TABLE_NAMES:
            table = stripped
            continue
        if table is not None:
            rm = _TABLE_ROW_RE.match(stripped)
            if rm:
                tables[table][rm.group(1)] = rm.group(2)
                continue
            if not stripped:
                continue
            table = None
        out.append(_FRAME_ID_RE.sub(resolve, line))
    return "\n".join(out) + "\n"


def parse_hlo(text: str) -> HloProgram:
    """Parse ``compiled.as_text()`` or pre-optimization
    ``lowered.as_text(dialect="hlo")`` output.  Lines that are neither
    a computation header, an instruction, nor a closing brace are
    ignored."""
    computations: Dict[str, Computation] = {}
    entry: Optional[str] = None
    current: Optional[Computation] = None
    for line in text.splitlines():
        stripped = line.strip()
        if not stripped or stripped.startswith("//"):
            continue
        # computation header: `[ENTRY ]%name (params) -> type {` or
        # the pre-opt dump's bare `name {` — instruction lines always
        # contain " = " before any brace
        if stripped.endswith("{") and " = " not in stripped:
            hm = re.match(r"(ENTRY\s+)?%?([\w.\-]+)\s*[({]", stripped)
            if hm:
                current = Computation(hm.group(2), bool(hm.group(1)))
                computations[current.name] = current
                if current.is_entry:
                    entry = current.name
            continue
        if stripped.startswith("}"):
            current = None
            continue
        if current is None:
            continue
        instr = _parse_instruction(line)
        if instr is not None:
            current.add(instr)
    return HloProgram(computations, entry)
