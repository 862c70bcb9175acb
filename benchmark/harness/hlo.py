"""Reads a compiled program's text: which instruction is what.

A device trace names an event after the HLO instruction that ran
(`fusion.12`, `all-reduce-start.3`, `jvp__.1`), and those names say little:
the flash kernels are custom calls named after the transformation that made
them. The compiled program's own text says what each name is: its opcode,
its result, its operands and, for a custom call, its target. The benchmark
classifies trace events by this table and never by a pattern on the name.
"""

from __future__ import annotations

import re
from dataclasses import dataclass

#: opcodes that move data between chips; `-start` is where an asynchronous
#: one is issued and `-done` where its result is awaited
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")

#: instructions whose trace event spans the events of the computation they
#: run, which are on the same line
CONTAINERS = ("while", "conditional", "call")

MOSAIC_TARGET = "tpu_custom_call"   # a Pallas kernel compiled for the TPU

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
               "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1}

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<result>.*?)\s"
    r"(?P<opcode>[a-z][a-z0-9\-]*)\((?P<rest>.*)$")
_ARRAY = re.compile(r"\b([a-z]+[0-9]*[a-z0-9]*)\[([0-9,]*)\]")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


@dataclass(frozen=True)
class Instruction:
    name: str
    opcode: str
    results: tuple      # ((dtype, (dims...)), ...) of the result, flattened
    n_operands: int
    target: str | None  # custom_call_target

    @property
    def result_bytes(self) -> int:
        total = 0
        for dtype, dims in self.results:
            n = DTYPE_BYTES[dtype]
            for d in dims:
                n *= d
            total += n
        return total

    @property
    def collective(self) -> str | None:
        """The kind of collective this instruction belongs to, if any."""
        for kind in COLLECTIVES:
            if self.opcode in (kind, kind + "-start", kind + "-done"):
                return kind
        return None

    @property
    def is_mosaic_kernel(self) -> bool:
        return self.opcode == "custom-call" and self.target == MOSAIC_TARGET


def _arrays(text: str) -> tuple:
    return tuple((dtype, tuple(int(d) for d in dims.split(",") if d))
                 for dtype, dims in _ARRAY.findall(text)
                 if dtype in DTYPE_BYTES)


def _operand_list(rest: str) -> str:
    """The text between an instruction's opening parenthesis and the one
    that closes it."""
    depth = 1
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return rest[:i]
    return rest


def _count_operands(operands: str) -> int:
    """Operands are separated by commas outside any bracket."""
    if not operands.strip():
        return 0
    depth = n = 0
    for ch in operands:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == "," and depth == 0:
            n += 1
    return n + 1


def op_name(event_name: str) -> str:
    """A trace event's name as the program's text has it: `%fusion.1 = ...`
    and `fusion.1` are the same instruction."""
    return event_name.split(" = ")[0].strip().lstrip("%")


def opcode_of(event_name: str) -> str | None:
    """The opcode, where the trace names an event by its instruction's whole
    text (`%fusion.1 = bf16[8]{0} fusion(%a), kind=kLoop`) and not by its
    name alone."""
    m = _INSTRUCTION.match(event_name)
    return m["opcode"] if m else None


def index(text: str) -> dict:
    """name -> Instruction for every instruction of every computation of a
    compiled program's text (`compiled.as_text()`)."""
    table = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        operands = _operand_list(m["rest"])
        target = _TARGET.search(m["rest"])
        table[m["name"]] = Instruction(
            name=m["name"], opcode=m["opcode"], results=_arrays(m["result"]),
            n_operands=_count_operands(operands),
            target=target.group(1) if target else None)
    return table


def allreduce_bytes(table: dict) -> int:
    """Bytes the program all-reduces per execution, from its instructions'
    result shapes. An instruction inside a loop body counts once."""
    return sum(i.result_bytes for i in table.values()
               if i.opcode in ("all-reduce", "all-reduce-start"))
