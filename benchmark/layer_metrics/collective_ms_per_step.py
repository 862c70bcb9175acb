"""Collectives across chips: summed device time, per traced step on the
first chip, of the step program's collective instructions (all-reduce,
reduce-scatter, all-gather, ...; for an asynchronous one its `-start` and
`-done`). Total time, hidden or not: what compute does not hide is an open
question (PERF.md). Zero on one chip, where the program holds none."""

from benchmark.harness import xplane


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    collectives = {name for name, i in run.instructions.items()
                   if i.collective}
    seconds = xplane.op_seconds_per_step(run.trace.devices[0],
                                         collectives.__contains__)
    return None if seconds is None else seconds * 1e3
