"""Expert layer: device time per traced step of the experts themselves: the
grouped matmul kernels the compiler makes of `lax.ragged_dot`, the kernels
that make their metadata, and the instructions under `moe.experts` (the gate:
silu(gate) * up, and its backward)."""

from benchmark.harness import scopes


def read(run):
    return scopes.part_ms_per_step(run, "experts".__eq__)
