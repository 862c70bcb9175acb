"""Jitted SPMD step: device time per traced step of the step program's
instructions under an `attn.*` scope (`attn.project`, `attn.attend`,
`attn.out` of `models/transformer.py`: the three projections, QK-norm and
the rotation, the flash kernels, the output product; forward, remat repeat
and backward). By scope (`harness/step_scopes.py`), where
`flash_ms_per_step` tells the kernels by their kind. None for a program
without `attn.*` scopes."""

from benchmark.harness import step_scopes


def read(run):
    return step_scopes.ms_under(run, "attn.")
