"""Eager optimizer path: median length, in the traced steps, of the span
`hvd.opt.reduce` that `DistributedOptimizer.step` records around its
gradient reduction (`optim/optimizer.py`). Host time, on the profiler's
clock; a part of `opt_step_host_ms`. None where the program records no such
span."""

from benchmark.harness import host_spans


def read(run):
    return host_spans.median_ms(run, "hvd.opt.reduce")
