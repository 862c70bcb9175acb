"""State-space layers: device time per traced step of the step program's
instructions under the `ssm.scan` scope of `models/transformer.py`: from the
step's projection to the scan's output y (the softplus, the decay rates and
the selective scan of `ops/selective_scan.py` with what its wrapper adds:
B and C replicated along the lanes, the partial sums of their gradients
added up; forward, remat repeat and backward). By scope alone. None for a
program without the scope."""

from benchmark.layer_metrics.gdn_scan_ms_per_step import ms_under

SCOPE = "ssm.scan"


def read(run):
    return ms_under(run, SCOPE)
