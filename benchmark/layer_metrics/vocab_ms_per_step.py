"""Jitted SPMD step: device time per traced step of the vocabulary's work:
the step program's instructions under `vocab.embed` (the lookup, learned
positions, and the scatter-add that is its gradient), `vocab.head` (the
final norm and the product with `unembed`) and `vocab.loss` (log-softmax,
the targets' gather, the sums, the experts' auxiliary terms) of
`models/transformer.py`, forward and backward. None for a program without
`vocab.*` scopes."""

from benchmark.harness import step_scopes


def read(run):
    return step_scopes.ms_under(run, "vocab.")
