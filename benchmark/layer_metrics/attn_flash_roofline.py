"""Pallas kernels: the flash-attention kernels of a patterned model's
full-attention layers, their share of their roofline, in percent: the least
time the chip could take for the executions traced (per execution the larger
of FLOPs over the bf16 peak and bytes over the HBM peak, from the call's
shapes) over the time they took.

The kernels are told by signature (`flash_roofline.SIGNATURES`: the forward
takes q, k, v and returns (o, lse); the backward kernels take q, k, v, o,
do, lse, and dk/dv returns two arrays, dq one) AND by shape: the first
array a kernel returns (o, dk or dq) is the family's `flash_kernel_shape`
(batch, heads, seq, head_dim), as the kernels hold it, (batch x heads, seq,
head_dim). Any other Mosaic kernel of the step, such as one a linear layer's
scan may get, is ignored. None for a program without an `attn.*` scope."""

import math

from benchmark.harness import scope_time, xplane
from benchmark.layer_metrics.flash_roofline import (kernel_kind,
                                                    least_seconds)

SCOPE_PREFIX = "attn."


def flash_kernels(table: dict, shape) -> dict:
    """name -> "forward", "dkdv" or "dq" for the Mosaic kernels of a flash
    kernel's signature whose first result has `shape`'s rows and width."""
    batch, heads, seq, width = shape
    found = {}
    for name, i in table.items():
        kind = kernel_kind(i) if i.is_mosaic_kernel else None
        dims = i.results[0][1] if kind and i.results else ()
        if len(dims) >= 2 and dims[-2:] == (seq, width) \
                and math.prod(dims[:-2]) == batch * heads:
            found[name] = kind
    return found


def traced_kernels(run):
    """`flash_kernels` of a traced run whose program has an `attn.*` scope
    and whose family gives one head width; else None."""
    if not scope_time.traced(run) or \
            not hasattr(run.family, "flash_kernel_shape"):
        return None
    shape = run.family.flash_kernel_shape(run.cell.config, run.cell.traffic)
    if len(shape) != 4 or not scope_time.names_under(
            run.program.as_text(), run.instructions, SCOPE_PREFIX):
        return None
    return flash_kernels(run.instructions, shape) or None


def read(run):
    kernels = traced_kernels(run)
    if not kernels or run.peaks is None:
        return None
    shape = run.family.flash_kernel_shape(run.cell.config, run.cell.traffic)
    dev = run.trace.devices[0]
    least = took = 0.0
    for name, kind in kernels.items():
        runs = xplane.op_counts_per_step(dev, name.__eq__)
        if not runs:
            continue
        least += runs * least_seconds(kind, shape, run.peaks)[0]
        took += xplane.op_seconds_per_step(dev, name.__eq__)
    return 100.0 * least / took if took > 0.0 else None
