"""Eager optimizer path: median length of the benchmark's span around
`DistributedOptimizer.step`, in the traced steps. Host time: how long the
call kept the loop, not how long the device worked."""

import statistics


def read(run):
    if run.trace is None:
        return None
    spans = [s.dur for s in run.trace.spans if s.name == "bench.opt_step"]
    return statistics.median(spans) * 1e3 if spans else None
