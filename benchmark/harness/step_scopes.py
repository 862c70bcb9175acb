"""The device time of a traced step, partitioned by the scopes the program
gave its layers.

`horovod_tpu/models/transformer.py` runs every part of the train step under
a `jax.named_scope` (`STEP_SCOPES` there, and its three mixers' `moe.*`,
`mla.*`, `gdn.*`). `scope_time.py` says how a scope reaches the compiled
text. Two things it does not meet, which this reader does:

* A scope entered outside the layer scan is wrapped by the transformation
  that was applied around it: `jit(step)/transpose(jvp(vocab.head))/mul`,
  where the scan's body has `.../checkpoint/attn.project/dot_general`. A
  component is read with its wrappers taken off.
* A Mosaic kernel takes the name of the scope it was called under
  (`attn.attend.10`), and the kernels the backward pass makes of it keep the
  name and lose the metadata. A kernel without a scope in its `op_name` goes
  to the scope its own name carries.

Every instruction of the step program that is no loop or call goes to ONE
part: the outermost component of its `op_name` that starts with a known
prefix, else `OTHER`. So the parts are disjoint and sum to the time of the
program's instructions in the trace. What a fusion can misplace is in
`harness/scopes.py`: it carries its root's scope alone.

A part no instruction of the program lies in reads None, and so does `OTHER`
for a program without any known scope (one compiled before the scopes): a
reader owes that to a program that lacks what it reads.
"""

from __future__ import annotations

import re

from benchmark.harness import hlo, scope_time
from benchmark.harness.scope_time import _OP_NAME   # an instruction's op_name

#: the first letters of a scope, per layer of the step; the first five are
#: the prefixes of `transformer.STEP_SCOPES`
PREFIXES = ("attn.", "mlp.", "vocab.", "grad.", "opt.",
            "moe.", "mla.", "gdn.")
OTHER = "other"

_WRAPPERS = re.compile(r"^(?:[\w\-]+\()+")   # `transpose(jvp(` of a component
_NUMBER = re.compile(r"\.\d+$")              # `.10` of an instruction's name


def scope_of(op_name: str):
    """The outermost component of `op_name` that starts with a known prefix,
    without the transformations' wrappers; None where there is none."""
    for component in op_name.split("/"):
        bare = _WRAPPERS.sub("", component).rstrip(")")
        if bare.startswith(PREFIXES):
            return bare
    return None


def partition(text: str, table: dict) -> dict:
    """instruction name -> its scope (`attn.attend`, `moe.shared`, ...) or
    `OTHER`, for every instruction of the compiled program `text` (indexed
    as `table` by `hlo.index`) that is no loop or call: their events span
    their bodies', which are there themselves."""
    scopes = {}
    for line in text.splitlines():
        m = _OP_NAME.match(line)
        if m and m["name"] in table:
            scopes[m["name"]] = scope_of(m["op"])
    parts = {}
    for name, instruction in table.items():
        if instruction.opcode in hlo.CONTAINERS:
            continue
        scope = scopes.get(name)
        if scope is None and instruction.is_mosaic_kernel and \
                name.startswith(PREFIXES):
            scope = _NUMBER.sub("", name)
        parts[name] = scope or OTHER
    return parts


def traced_partition(run):
    """`partition` of a traced run's step program; None where there is no
    device trace, no program, or no known scope in the program."""
    if not scope_time.traced(run):
        return None
    parts = partition(run.program.as_text(), run.instructions)
    return parts if any(p != OTHER for p in parts.values()) else None


def ms_per_step(run, wanted):
    """Device time per traced step, on the first chip, of the instructions
    whose part `wanted` accepts, in ms; None where `traced_partition` is or
    the program has no such instruction."""
    parts = traced_partition(run)
    if parts is None:
        return None
    return scope_time.ms_per_step(
        run, {name for name, part in parts.items() if wanted(part)})


def ms_under(run, prefix: str):
    """`ms_per_step` of the parts that start with `prefix`."""
    return ms_per_step(run, lambda part: part.startswith(prefix))
