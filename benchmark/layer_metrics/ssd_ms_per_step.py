"""State-space dual layers: device time per traced step of the step
program's instructions under an `ssd.*` scope (`ssd.project`, `ssd.conv`,
`ssd.scan`, `ssd.gate`, `ssd.out` of `models/mixers.py`'s Mamba-2 mixer: the
input projection's two products, the convolution over x, B and C, the step's
softplus with the scan of `ops/ssd_scan.py`, the gated norm, the output
product; forward, remat repeat and backward). By scope alone, so a kernel
that later runs under one of them is counted without an edit. None for a
program without `ssd.*` scopes."""

from benchmark.layer_metrics.gdn_scan_ms_per_step import ms_under

SCOPE_PREFIX = "ssd."


def read(run):
    return ms_under(run, SCOPE_PREFIX)
