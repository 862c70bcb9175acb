"""Pallas kernels: summed device time, per traced step on the first chip, of
the step program's Mosaic custom calls (the flash-attention forward, dk/dv
and dq kernels; the forward runs twice a layer under remat)."""

from benchmark.harness import xplane


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    kernels = {name for name, i in run.instructions.items()
               if i.is_mosaic_kernel}
    if not kernels:
        return None
    seconds = xplane.op_seconds_per_step(run.trace.devices[0],
                                         kernels.__contains__)
    return None if seconds is None else seconds * 1e3
