"""Linear attention: device time per traced step of the step program's
instructions under a `gdn.*` scope (`gdn.project`, `gdn.conv`, `gdn.scan`,
`gdn.gate`, `gdn.out` of `models/transformer.py`: the six projections, the
convolutions, the normalisation and the gated delta rule, the gated norm,
the output product; forward, remat repeat and backward). By scope alone, so
a kernel that later runs under one of them is counted without an edit. None
for a program without `gdn.*` scopes."""

from benchmark.layer_metrics.gdn_scan_ms_per_step import ms_under

SCOPE_PREFIX = "gdn."


def read(run):
    return ms_under(run, SCOPE_PREFIX)
