"""Which part of the expert layer an instruction of the compiled step belongs
to, from the program's own text.

`horovod_tpu/parallel/moe.py` puts `jax.named_scope`s around its four parts
(`moe.route`, `moe.dispatch`, `moe.experts`, `moe.combine`). A scope reaches
the compiled text as a component of an instruction's `op_name` metadata
(`jit(step)/.../checkpoint/moe.route/dot_general`) and survives `jit`, remat,
the layer scan and differentiation, so the backward pass and the remat
repeat of a part carry its scope too. Two things the metadata cannot say:

* A fusion carries the metadata of its root instruction alone. Where the
  compiler fuses work of two parts, or work of a part with the block's
  (the residual add after `moe.combine`, the RMSNorm before `moe.route`),
  the whole fusion's time goes to the root's scope, or to none.
* The TPU compiler turns `lax.ragged_dot` into Mosaic kernels of its own and
  names them itself (`ragged-dot-none`, `ragged-dot-metadata`): the scope is
  gone. They are told by signature, as the flash kernels are
  (`layer_metrics/flash_roofline.py`): the grouped matmul takes seven
  operands (five of metadata, the rows, the weights) and returns one array;
  the kernel that makes the metadata from the group sizes takes one and
  returns four. Both count as `experts`.
"""

from __future__ import annotations

import re

from benchmark.harness import hlo, xplane

SCOPE_PREFIX = "moe."
PARTS = ("route", "dispatch", "experts", "combine")

# (operands, results) of a Mosaic custom call -> what it is
GROUPED_MATMUL = (7, 1)
GROUPED_METADATA = (1, 4)

_OP_NAME = re.compile(
    r'^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=.*\bop_name="(?P<op>[^"]*)"')


def part_of(op_name: str):
    """The part of the expert layer an `op_name` lies in: its outermost
    component `moe.<part>`, or None."""
    for component in op_name.split("/"):
        if component.startswith(SCOPE_PREFIX) and \
                component[len(SCOPE_PREFIX):] in PARTS:
            return component[len(SCOPE_PREFIX):]
    return None


def grouped_kernels(table: dict) -> dict:
    """name -> GROUPED_MATMUL or GROUPED_METADATA for the Mosaic kernels the
    compiler made of the program's ragged dots."""
    found = {}
    for name, i in table.items():
        signature = (i.n_operands, len(i.results))
        if i.is_mosaic_kernel and signature in (GROUPED_MATMUL,
                                                GROUPED_METADATA):
            found[name] = signature
    return found


def moe_parts(text: str, table: dict) -> dict:
    """instruction name -> one of PARTS, for the instructions of the compiled
    program `text` (indexed as `table` by `hlo.index`) that belong to the
    expert layer. A loop or call is left out: its event spans its body's,
    which are there themselves."""
    parts = {}
    for line in text.splitlines():
        m = _OP_NAME.match(line)
        part = m and part_of(m["op"])
        instruction = m and table.get(m["name"])
        if part and instruction and \
                instruction.opcode not in hlo.CONTAINERS:
            parts[m["name"]] = part
    parts.update(dict.fromkeys(grouped_kernels(table), "experts"))
    return parts


def traced_parts(run):
    """`moe_parts` of a traced run's step program; None where there is no
    device trace or the program has no expert layer. A program with `moe.*`
    scopes and no kernel of a grouped matmul's signature is an error: the
    compiler then builds or names them otherwise than GROUPED_MATMUL and
    GROUPED_METADATA say, and every reading would silently leave the
    experts' products out."""
    if run.trace is None or not run.trace.devices or run.program is None:
        return None
    parts = moe_parts(run.program.as_text(), run.instructions)
    if parts and not grouped_kernels(run.instructions):
        raise RuntimeError(
            "the step program has moe.* scopes but no Mosaic kernel with a "
            "grouped matmul's signature: benchmark/harness/scopes.py tells "
            f"them by (operands, results) = {GROUPED_MATMUL} and "
            f"{GROUPED_METADATA}")
    return parts or None


def part_ms_per_step(run, wanted):
    """Device time per traced step, on the first chip, of the instructions
    whose part of the expert layer `wanted` accepts, in ms; None where there
    is no trace or the program has no such instruction."""
    parts = traced_parts(run)
    names = {name for name, part in (parts or {}).items() if wanted(part)}
    if not names:
        return None
    seconds = xplane.op_seconds_per_step(run.trace.devices[0],
                                         names.__contains__)
    return None if seconds is None else seconds * 1e3
