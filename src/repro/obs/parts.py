"""Named parts of a compiled program, read from its optimized HLO text.

A device trace names each operation by its HLO instruction and carries no
scope.  The compiled program's text (``jax.stages.Compiled.as_text()``)
keeps each instruction's ``jax.named_scope`` path in its ``op_name``
metadata, so a program can publish a map from instruction name to part
that a reader joins with the device's operations.

The rule, on the scope path (the ``/``-separated components of
``op_name``):

* an instruction whose path holds ``cast`` anywhere is ``cast``;
* otherwise it belongs to the first part in its path;
* otherwise to ``other``.

A fusion is one operation on the device.  It takes the part of its own
metadata (XLA gives a fusion its root's), so a convert fused into its
consumer counts with the consumer.  A fusion whose own path names no part
takes the part of the last instruction of its fused computation that has
one.

Some operations have no scope of their own: the copies XLA adds (no
metadata), and ``lax.scan``'s slicing of its inputs and stacking of its
outputs (a path of nothing but ``while``/``body``/``cond``).  Such an
operation takes the part of the data it moves: that of the nearest
operand with a part, else of the nearest user with one, looking through
other such operations in its computation (never through a ``while``).
One exception: XLA moves the converts of the scanned weight stacks out of
the loop and writes the moved convert with no metadata, so a convert
outside fused computations with no metadata is ``cast``.

The computations an instruction applies (a reduction's ``to_apply``, a
sort's ``comparator``) run inside that instruction and hold no operation
of their own, as fused computations do not.

Stdlib only, like the rest of ``repro.obs``.
"""

from __future__ import annotations

import re
from collections import deque
from typing import Dict, Optional, Sequence, Tuple

__all__ = ["DECODE_PARTS", "part_of", "program_parts"]

#: the named scopes of the serving engine's decode program
DECODE_PARTS = ("cast", "attn", "kv", "mlp", "moe", "head", "sample")

_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s+=\s(.*)$")
_OPCODE = re.compile(r"\s([a-z][\w-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([^\s,}]+)")
_APPLIES = re.compile(r"\b(?:to_apply|comparator)=%?([^\s,}]+)")
_REF = re.compile(r"%([^\s,(){}]+)")
_SCAFFOLD = ("while", "body", "cond")


def part_of(op_name: str, parts: Sequence[str] = DECODE_PARTS) -> str:
    """The part of one scope path, by the rule in the module docstring."""
    path = op_name.split("/")
    if "cast" in parts and "cast" in path:
        return "cast"
    for comp in path:
        if comp in parts:
            return comp
    return "other"


class _Instr:
    __slots__ = ("opcode", "op_name", "part", "calls", "operands", "users")

    def __init__(self, opcode, op_name, part, calls, operands):
        self.opcode, self.op_name, self.part = opcode, op_name, part
        self.calls, self.operands, self.users = calls, operands, []

    def unscoped(self) -> bool:
        """No scope of its own: no metadata, or only scan's scaffolding
        between the program's name and the primitive's."""
        if self.opcode == "while":
            return False
        return self.op_name is None or all(
            c in _SCAFFOLD for c in self.op_name.split("/")[1:-1])


def _parse(hlo_text: str, parts: Sequence[str]):
    module = None
    comps: Dict[str, Dict[str, _Instr]] = {}
    cur: Optional[Dict[str, _Instr]] = None
    for line in hlo_text.splitlines():
        if module is None:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), {})
            continue
        m = _INSTRUCTION.match(line) if cur is not None else None
        if not m:
            continue
        rhs = m.group(2)
        op = _OPCODE.search(rhs)
        opcode = op.group(1) if op else ""
        on = _OP_NAME.search(rhs)
        part = part_of(on.group(1), parts) if on else "other"
        calls = _CALLS.search(rhs)
        args = rhs.split(", metadata=", 1)[0]
        cur[m.group(1)] = _Instr(
            opcode, on.group(1) if on else None,
            None if part == "other" else part,
            calls.group(1) if calls else None,
            [r for r in _REF.findall(args) if r in cur])
    for instrs in comps.values():
        for name, ins in instrs.items():
            for o in ins.operands:
                instrs[o].users.append(name)
    return module, comps


def _nearest(instrs: Dict[str, _Instr], start: str, edge: str
             ) -> Optional[str]:
    """Breadth-first along ``edge`` (operands or users) from ``start``:
    the part of the nearest instruction that has one, passing only
    through unscoped instructions."""
    seen, todo = {start}, deque(getattr(instrs[start], edge))
    while todo:
        name = todo.popleft()
        if name in seen:
            continue
        seen.add(name)
        ins = instrs[name]
        if ins.part:
            return ins.part
        if ins.unscoped():
            todo.extend(getattr(ins, edge))
    return None


def program_parts(hlo_text: str, parts: Sequence[str] = DECODE_PARTS
                  ) -> Tuple[Optional[str], Dict[str, str]]:
    """``(module name, {instruction name: part})`` of an optimized HLO
    module.  The map holds the instructions that can run as operations
    of their own (those outside fused and applied computations) and have
    a part; an instruction it does not hold is ``other``."""
    module, comps = _parse(hlo_text, parts)
    fused = {i.calls for instrs in comps.values() for i in instrs.values()
             if i.calls} | set(_APPLIES.findall(hlo_text))
    memo: Dict[str, Optional[str]] = {}

    def inner(comp: str) -> Optional[str]:
        if comp not in memo:
            memo[comp] = None
            for ins in reversed(list(comps.get(comp, {}).values())):
                part = ins.part or (inner(ins.calls) if ins.calls else None)
                if part:
                    memo[comp] = part
                    break
        return memo[comp]

    for comp, instrs in comps.items():
        for ins in instrs.values():
            if not ins.part and ins.calls:
                ins.part = inner(ins.calls)
            elif (comp not in fused and ins.op_name is None
                  and ins.opcode == "convert" and "cast" in parts):
                ins.part = "cast"         # moved out of the scan by XLA
    out: Dict[str, str] = {}
    for comp, instrs in comps.items():
        if comp in fused:
            continue
        for name, ins in instrs.items():
            part = ins.part
            if not part and ins.unscoped():
                part = (_nearest(instrs, name, "operands")
                        or _nearest(instrs, name, "users"))
            if part:
                out[name] = part
    return module, out
