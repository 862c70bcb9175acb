"""State-space layers: device time per traced step of the step program's
instructions under an `ssm.*` scope (`ssm.project`, `ssm.conv`, `ssm.scan`,
`ssm.gate`, `ssm.out` of `models/transformer.py`: the three projections, the
convolution, the step's softplus with the selective scan of
`ops/selective_scan.py`, the gate, the output product; forward, remat repeat
and backward). By scope alone, so a kernel that later runs under one of them
is counted without an edit. None for a program without `ssm.*` scopes."""

from benchmark.layer_metrics.gdn_scan_ms_per_step import ms_under

SCOPE_PREFIX = "ssm."


def read(run):
    return ms_under(run, SCOPE_PREFIX)
