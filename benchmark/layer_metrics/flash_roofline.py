"""Pallas kernels: the flash-attention kernels' share of their roofline, in
percent: the least time the chip could take for the executions traced (per
execution the larger of FLOPs over the bf16 peak and bytes over the HBM
peak, from the call's shapes) over the time they took.

The program gives its kernels no names, so each Mosaic custom call of the
step program is told by its signature: the forward takes q, k, v and returns
(o, lse); the backward kernels take q, k, v, o, do, lse, and dk/dv returns
two arrays, dq one."""

from benchmark.harness import xplane

# (operands, results) of the custom call -> which kernel it is
SIGNATURES = {(3, 2): "forward", (6, 2): "dkdv", (6, 1): "dq"}


def kernel_kind(instruction):
    return SIGNATURES.get((instruction.n_operands, len(instruction.results)))


def causal_work(kind: str, shape, elem_bytes: int = 2):
    """(FLOPs, bytes) one execution of a causal flash kernel needs on
    (batch, heads, seq, head_dim): matrix products over the causal half of
    the score matrix, 2 FLOPs per multiply-add, and each operand and result
    moved once (lse is one float32 per row)."""
    b, h, s, d = shape
    product = 2 * b * h * s * s * d / 2     # one (s, d) x (d, s)-sized matmul
    array = b * h * s * d * elem_bytes
    lse = b * h * s * 4
    if kind == "forward":    # q.k, p.v ; reads q k v, writes o lse
        return 2 * product, 4 * array + lse
    if kind == "dkdv":       # q.k, do.v, p.do, ds.q ; reads 5 + lse, writes 2
        return 4 * product, 7 * array + lse
    if kind == "dq":         # q.k, do.v, ds.k ; reads 5 + lse, writes 1
        return 3 * product, 6 * array + lse
    raise ValueError(kind)


def least_seconds(kind: str, shape, peaks):
    """(seconds, which bound holds) for one execution."""
    flops, moved = causal_work(kind, shape)
    compute, memory = flops / peaks.bf16_flops, moved / peaks.hbm_bytes_per_s
    return max(compute, memory), "compute" if compute >= memory else "memory"


def read(run):
    if run.trace is None or not run.trace.devices or run.peaks is None \
            or not hasattr(run.family, "flash_kernel_shape"):
        return None
    shape = run.family.flash_kernel_shape(run.cell.config, run.cell.traffic)
    dev = run.trace.devices[0]
    least = took = 0.0
    for name, instruction in run.instructions.items():
        if not instruction.is_mosaic_kernel:
            continue
        kind = kernel_kind(instruction)
        if kind is None:
            return None   # a kernel this reader does not know
        runs = xplane.op_counts_per_step(dev, name.__eq__)
        seconds = xplane.op_seconds_per_step(dev, name.__eq__)
        if not runs:
            continue
        least += runs * least_seconds(kind, shape, run.peaks)[0]
        took += seconds
    return 100.0 * least / took if took > 0.0 else None
