"""Latent attention: the flash-attention kernels' share of their roofline at
unequal widths, in percent: the least time the chip could take for the
executions traced (per execution the larger of FLOPs over the bf16 peak and
bytes over the HBM peak, from the call's shapes) over the time they took.

The kernels are told by signature alone, so that the compiler's other Mosaic
kernels in the step (the grouped matmuls) are not taken for them: the
forward takes q, k, v and returns (o, lse); the backward kernels take q, k,
v, o, do, lse, and dk/dv returns two arrays, dq one. Queries and keys are
`dqk` wide, values `dv`: o, do and dv follow v, dq and dk follow q. None for
a family whose `flash_kernel_shape` gives one width."""

from benchmark.harness import scope_time, xplane
from benchmark.layer_metrics.flash_roofline import kernel_kind


def flash_kernels(table: dict) -> dict:
    """name -> "forward", "dkdv" or "dq" for the Mosaic kernels of a flash
    kernel's signature (`flash_roofline.SIGNATURES`)."""
    return {name: kernel_kind(i) for name, i in table.items()
            if i.is_mosaic_kernel and kernel_kind(i)}


def causal_work(kind: str, shape, elem_bytes: int = 2):
    """(FLOPs, bytes) one execution of a causal flash kernel needs on
    (batch, heads, seq, dqk, dv): matrix products over the causal half of
    the score matrix, 2 FLOPs per multiply-add, a product with q or k
    `dqk` deep and one with v or do `dv` deep; each operand and result
    moved once (lse is one float32 per row)."""
    b, h, s, dqk, dv = shape
    half = b * h * s * s / 2              # score entries the causal half has
    qk, vo = 2 * half * dqk, 2 * half * dv      # one product of each depth
    wide, narrow = b * h * s * dqk * elem_bytes, b * h * s * dv * elem_bytes
    lse = b * h * s * 4
    if kind == "forward":    # q.k | p.v ; reads q k | v, writes o, lse
        return qk + vo, 2 * wide + 2 * narrow + lse
    if kind == "dkdv":       # q.k, ds.q | do.v, p.do ; reads q k | v o do,
        return 2 * qk + 2 * vo, 3 * wide + 4 * narrow + lse  # writes dk | dv
    if kind == "dq":         # q.k, ds.k | do.v ; reads q k | v o do,
        return 2 * qk + vo, 3 * wide + 3 * narrow + lse      # writes dq
    raise ValueError(kind)


def least_seconds(kind: str, shape, peaks):
    """(seconds, which bound holds) for one execution."""
    flops, moved = causal_work(kind, shape)
    compute, memory = flops / peaks.bf16_flops, moved / peaks.hbm_bytes_per_s
    return max(compute, memory), "compute" if compute >= memory else "memory"


def read(run):
    if not scope_time.traced(run) or run.peaks is None \
            or not hasattr(run.family, "flash_kernel_shape"):
        return None
    shape = run.family.flash_kernel_shape(run.cell.config, run.cell.traffic)
    if len(shape) != 5:
        return None
    dev = run.trace.devices[0]
    least = took = 0.0
    for name, kind in flash_kernels(run.instructions).items():
        runs = xplane.op_counts_per_step(dev, name.__eq__)
        if not runs:
            continue
        least += runs * least_seconds(kind, shape, run.peaks)[0]
        took += xplane.op_seconds_per_step(dev, name.__eq__)
    return 100.0 * least / took if took > 0.0 else None
