"""Device: the share of the traced window in which no operation ran on the
chip, in percent; mean over chips. A collective counts as busy."""

from benchmark.harness import xplane


def read(run):
    if run.trace is None:
        return None
    totals = xplane.busy_and_window_seconds(run.trace)
    if not totals or totals[1] <= 0.0:
        return None
    return 100.0 * (1.0 - totals[0] / totals[1])
