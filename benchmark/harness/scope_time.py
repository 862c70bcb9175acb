"""Device time of the instructions of a compiled step that lie under a
`jax.named_scope`, for scopes `harness/scopes.py` does not know.

A scope reaches the compiled text as a component of an instruction's
`op_name` metadata and survives `jit`, remat, the layer scan and
differentiation (`harness/scopes.py` says what a fusion can misplace). A
program without the scope has no such instruction: every function here then
returns nothing and raises nothing, which is what a reader of a new metric
owes a program that lacks what the metric reads.
"""

from __future__ import annotations

from benchmark.harness import hlo, xplane
from benchmark.harness.scopes import _OP_NAME   # an instruction's op_name


def names_under(text: str, table: dict, prefix: str) -> set:
    """Names of the instructions of the compiled program `text` (indexed as
    `table` by `hlo.index`) whose `op_name` has a component that starts with
    `prefix`. A loop or call is left out: its event spans its body's, which
    are there themselves."""
    names = set()
    for line in text.splitlines():
        m = _OP_NAME.match(line)
        if not m or not any(part.startswith(prefix)
                            for part in m["op"].split("/")):
            continue
        instruction = table.get(m["name"])
        if instruction and instruction.opcode not in hlo.CONTAINERS:
            names.add(m["name"])
    return names


def traced(run) -> bool:
    """Whether `run` holds a device trace and the program it is of."""
    return bool(run.trace is not None and run.trace.devices
                and run.program is not None)


def ms_per_step(run, names):
    """Device time per traced step, on the first chip, of the instructions
    `names`, in ms; None where there are none or no whole step."""
    if not names:
        return None
    seconds = xplane.op_seconds_per_step(run.trace.devices[0],
                                         names.__contains__)
    return None if seconds is None else seconds * 1e3
