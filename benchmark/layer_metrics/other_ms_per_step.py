"""Jitted SPMD step: device time per traced step of the step program's
instructions under no scope `harness/step_scopes.py` knows: the layer
scan's slices of the stacked weights and updates of the stacked gradients
and residuals, norms and residual adds the compiler does not fuse into a
named neighbour, casts and copies. With the parts by scope it sums to the
time of the program's instructions. None for a program without any known
scope."""

from benchmark.harness import step_scopes


def read(run):
    return step_scopes.ms_per_step(run, step_scopes.OTHER.__eq__)
