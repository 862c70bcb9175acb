"""Gated short-convolution layers: device time per traced step of the step
program's instructions under a `shortconv.*` scope (`shortconv.project`,
`shortconv.mix`, `shortconv.out` of `models/mixers.py`'s "shortconv" mixer:
the 3 D-wide input product, everything between the two products, the output
product; forward, remat repeat and backward). By scope alone, so a kernel
that later runs under one of them is counted without an edit. None for a
program without `shortconv.*` scopes."""

from benchmark.layer_metrics.gdn_scan_ms_per_step import ms_under

SCOPE_PREFIX = "shortconv."


def read(run):
    return ms_under(run, SCOPE_PREFIX)
