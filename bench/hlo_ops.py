"""Which instructions of a compiled program are GEMMs or collectives.

The names XLA gives fused instructions (``fusion.23``,
``convolution_add_fusion.7``) change with any edit to the program, so the
sets are built anew in every run from that run's own ``compiled.as_text()``:
an instruction is a GEMM when it is a ``dot`` or ``convolution``, a Pallas
``tpu_custom_call``, or a fusion (or other call) whose called computations
contain one; a collective likewise.  The trace's op events carry these
instruction names.
"""
from __future__ import annotations

import re
from typing import Dict, List, Set, Tuple

GEMM_OPCODES = {"dot", "convolution"}
COLLECTIVE_OPCODES = {
    "all-reduce", "all-reduce-start", "all-reduce-done", "reduce-scatter",
    "all-gather", "all-gather-start", "all-gather-done", "all-to-all",
    "collective-permute", "collective-permute-start",
    "collective-permute-done"}

_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition|branch_computations"
                    r"|called_computations)=\{?([%\w.\-, ]+)\}?")


def _split_instruction(line: str) -> Tuple[str, str] | None:
    """(name, opcode) of one instruction line, or None."""
    s = line.strip()
    if s.startswith("ROOT "):
        s = s[5:]
    eq = s.find(" = ")
    if eq < 0:
        return None
    name = s[:eq].lstrip("%")
    rest = s[eq + 3:]
    if rest.startswith("("):                     # tuple type: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:].lstrip()
                break
    else:
        sp = rest.find(" ")
        rest = rest[sp + 1:] if sp >= 0 else ""
    m = re.match(r"([\w\-]+)\(", rest)
    return (name, m.group(1)) if m else None


def parse(text: str) -> Dict[str, List[Tuple[str, str, str]]]:
    """computation name -> [(instruction, opcode, line)], plus '__entry__'."""
    comps: Dict[str, List[Tuple[str, str, str]]] = {}
    current = None
    for line in text.splitlines():
        if current is None:
            m = _HEADER.match(line)
            if m and "=" not in line.split("(")[0]:
                current = m.group(2)
                comps[current] = []
                if m.group(1):
                    comps["__entry__"] = comps[current]
            continue
        if line.strip() == "}":
            current = None
            continue
        parsed = _split_instruction(line)
        if parsed:
            comps[current].append((parsed[0], parsed[1], line))
    return comps


def _called(line: str) -> List[str]:
    out: List[str] = []
    for group in _CALLS.findall(line):
        out += [c.strip().lstrip("%") for c in group.split(",") if c.strip()]
    return out


def _is_kind(opcode: str, line: str, kind: str) -> bool:
    if kind == "gemm":
        return opcode in GEMM_OPCODES or (
            opcode == "custom-call" and "tpu_custom_call" in line)
    return opcode in COLLECTIVE_OPCODES


def select(text: str, kind: str) -> Set[str]:
    """Names of the instructions of ``kind`` ('gemm' or 'collective') that
    run as ops of their own: those outside fusion bodies, whose own opcode
    or called computations make them one."""
    comps = parse(text)
    memo: Dict[str, bool] = {}

    def contains(comp: str, stack: Tuple[str, ...] = ()) -> bool:
        if comp in memo:
            return memo[comp]
        if comp in stack or comp not in comps:
            return False
        hit = any(_is_kind(op, line, kind)
                  or any(contains(c, stack + (comp,)) for c in _called(line))
                  for _, op, line in comps[comp])
        memo[comp] = hit
        return hit

    fused = {c for instrs in comps.values() for _, op, line in instrs
             if op == "fusion" for c in _called(line)}
    names: Set[str] = set()
    for comp, instrs in comps.items():
        if comp in fused or comp == "__entry__":
            continue
        for name, op, line in instrs:
            if _is_kind(op, line, kind) or (
                    op in ("fusion", "custom-call", "async-start", "call")
                    and any(contains(c) for c in _called(line))):
                names.add(name)
    return names
