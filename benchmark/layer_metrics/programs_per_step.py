"""Eager optimizer path: programs executed on the first chip per traced step
(events of the device plane's `XLA Modules` line). One for a jitted step."""

from benchmark.harness import xplane


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    return xplane.modules_per_step(run.trace.devices[0])
