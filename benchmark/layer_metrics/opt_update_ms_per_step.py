"""Jitted SPMD step: device time per traced step of the optimizer's update:
the step program's instructions under the scope `opt.update`
(`optimizer.update` and `optax.apply_updates` in
`transformer.build_train_step`). A fusion carries its root's scope alone:
a gradient product the compiler fuses with its update is here or with its
layer, whole. None for a program without the scope."""

from benchmark.harness import step_scopes


def read(run):
    return step_scopes.ms_under(run, "opt.")
