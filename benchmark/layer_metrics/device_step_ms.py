"""Jitted step: per traced step, the time in which some operation ran on
the chip (union of the device-op intervals); median over steps, mean over
chips."""

import statistics

from benchmark.harness import xplane


def read(run):
    if run.trace is None:
        return None
    per_chip = [statistics.median(steps) for steps in
                map(xplane.device_step_seconds, run.trace.devices) if steps]
    return statistics.fmean(per_chip) * 1e3 if per_chip else None
