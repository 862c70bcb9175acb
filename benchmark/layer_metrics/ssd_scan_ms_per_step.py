"""State-space dual layers: device time per traced step of the step
program's instructions under the `ssd.scan` scope of `models/mixers.py`:
from the step's projection to the scan's output y (the softplus, the log
decays and their running sums in both of the forms the kernels read, the
kernels of `ops/ssd_scan.py`, and the sums of the gradients the backward
kernel writes a block of heads at a time; forward, remat repeat and
backward). By scope alone. None for a program without the scope."""

from benchmark.layer_metrics.gdn_scan_ms_per_step import ms_under

SCOPE = "ssd.scan"


def read(run):
    return ms_under(run, SCOPE)
