"""Expert layer: summed device time, per traced step on the first chip, of
the step program's instructions under a `moe.*` scope and of the grouped
matmul kernels: router, sort and gather, experts, combine, forward, remat
repeat and backward (`harness/scopes.py` says how each is told, and what a
fusion can misplace)."""

from benchmark.harness import scopes


def read(run):
    return scopes.part_ms_per_step(run, lambda part: True)
