"""Jitted SPMD step: device time per traced step of the step program's
instructions under the scope `mlp.dense` (`models/transformer.py`: a dense
layer's MLP, gated or GELU, with its bias adds; forward, remat repeat and
backward). The shared experts are `moe.shared`'s and no part of it. None for
a program without the scope."""

from benchmark.harness import step_scopes


def read(run):
    return step_scopes.ms_under(run, "mlp.")
