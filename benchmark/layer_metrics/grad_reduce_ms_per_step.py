"""Jitted SPMD step: device time per traced step of the gradient reduction:
the step program's instructions under the scope `grad.reduce`
(`transformer.build_loss_and_grads`: the recursive halving's slices, adds
and collective-permutes inside the backward loop, the all-gathers that
complete the layers' gradients with what the compiler fuses beside them,
the all-reduces of the rest). Summed durations on the first chip: where
they run beside other work the sum is more than what the step waits for.
`collective_ms_per_step` times the collectives alone, by opcode. None for a
program without the scope, which a program for one rank is."""

from benchmark.harness import step_scopes


def read(run):
    return step_scopes.ms_under(run, "grad.")
