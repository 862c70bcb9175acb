"""Pallas kernels: the flash-attention kernels of differential attention over
grouped keys and values, their share of their roofline, in percent: the
least time the chip could take for the executions traced (per execution the
larger of FLOPs over the bf16 peak and bytes over the HBM peak) over the time
they took.

The kernels are told by signature (`flash_roofline.SIGNATURES`) AND by
shape: the first array a kernel returns (o, dk or dq) has the rows and the
width the family's `flash_kernel_shapes` gives it: o is (batch x query
heads, seq, values' width), dq (batch x query heads, seq, keys' width), dk
(batch x K/V heads, seq, keys' width). A windowed layer's kernels have a
full layer's shapes; they are the ones under the `attn.window` scope of
`models/transformer.py` (in the instruction's `op_name`, or in its name).
Their work is the band's: score entries a query's last `window` keys hold,
q.k at the keys' width and p.v at the values'; the others' the causal
half's. None for a program without an `attn.*` scope or a family without
`flash_kernel_shapes`."""

import math

from benchmark.harness import scope_time, xplane
from benchmark.layer_metrics.flash_roofline import kernel_kind

SCOPE_PREFIX = "attn."
WINDOWED = "attn.window"


def work(kind: str, shape, keys_seen: float, elem_bytes: int = 2):
    """(FLOPs, bytes) one execution of a flash kernel needs on (batch, query
    heads, K/V heads, seq, keys' width, values' width) where a query sees
    `keys_seen` keys on average: 2 FLOPs a multiply-add of the products over
    the score entries the mask holds, each operand and result moved once
    (lse is one float32 a row)."""
    batch, heads, kv_heads, seq, dk, dv = shape
    entries = batch * heads * seq * keys_seen
    q, o = (batch * heads * seq * w * elem_bytes for w in (dk, dv))
    k, v = (batch * kv_heads * seq * w * elem_bytes for w in (dk, dv))
    lse = batch * heads * seq * 4
    if kind == "forward":    # q.k, p.v ; reads q k v, writes o lse
        return 2 * entries * (dk + dv), q + k + v + o + lse
    if kind == "dkdv":       # q.k, do.v, p.do, ds.q ; writes dk dv
        return 2 * entries * 2 * (dk + dv), q + k + v + 2 * o + lse + k + v
    if kind == "dq":         # q.k, do.v, ds.k ; writes dq
        return 2 * entries * (2 * dk + dv), q + k + v + 2 * o + lse + q
    raise ValueError(kind)


def least_seconds(kind: str, shape, keys_seen: float, peaks):
    """(seconds, which bound holds) for one execution."""
    flops, moved = work(kind, shape, keys_seen)
    compute, memory = flops / peaks.bf16_flops, moved / peaks.hbm_bytes_per_s
    return max(compute, memory), "compute" if compute >= memory else "memory"


def flash_kernels(table: dict, shape) -> dict:
    """name -> "forward", "dkdv" or "dq" for the Mosaic kernels of a flash
    kernel's signature whose first result has the rows and the width `shape`
    gives that kind."""
    batch, heads, kv_heads, seq, dk, dv = shape
    first = {"forward": (batch * heads, dv), "dq": (batch * heads, dk),
             "dkdv": (batch * kv_heads, dk)}
    found = {}
    for name, i in table.items():
        kind = kernel_kind(i) if i.is_mosaic_kernel else None
        dims = i.results[0][1] if kind and i.results else ()
        if len(dims) >= 2 and dims[-2:] == (seq, first[kind][1]) \
                and math.prod(dims[:-2]) == first[kind][0]:
            found[name] = kind
    return found


def traced_kernels(run):
    """(`flash_kernels` of a traced run, the names among them that lie under
    `attn.window`), for a program with an `attn.*` scope and a family with
    `flash_kernel_shapes`; else None."""
    if not scope_time.traced(run) or \
            not hasattr(run.family, "flash_kernel_shapes"):
        return None
    text = run.program.as_text()
    if not scope_time.names_under(text, run.instructions, SCOPE_PREFIX):
        return None
    shapes = run.family.flash_kernel_shapes(run.cell.config,
                                            run.cell.traffic)
    kernels = flash_kernels(run.instructions, shapes["shape"])
    if not kernels:
        return None
    windowed = scope_time.names_under(text, run.instructions, WINDOWED) | {
        name for name in kernels if name.startswith(WINDOWED)}
    return kernels, windowed & set(kernels)


def parts(run):
    """{"window" | "full": (ms a step the kind's kernels took, the least
    they could take)} of a traced run; None where `traced_kernels` is."""
    found = traced_kernels(run)
    if found is None:
        return None
    kernels, windowed = found
    shapes = run.family.flash_kernel_shapes(run.cell.config,
                                            run.cell.traffic)
    dev = run.trace.devices[0]
    out = {}
    for part, (_, keys_seen) in shapes["layers"].items():
        took = least = 0.0
        for name, kind in kernels.items():
            if (name in windowed) != (part == "window"):
                continue
            runs = xplane.op_counts_per_step(dev, name.__eq__)
            if not runs:
                continue
            took += xplane.op_seconds_per_step(dev, name.__eq__)
            if run.peaks is not None:
                least += runs * least_seconds(kind, shapes["shape"],
                                              keys_seen, run.peaks)[0]
        out[part] = (took * 1e3, least * 1e3)
    return out


def read(run):
    found = parts(run)
    if not found or run.peaks is None:
        return None
    took = sum(t for t, _ in found.values())
    return 100.0 * sum(l for _, l in found.values()) / took \
        if took > 0.0 else None
