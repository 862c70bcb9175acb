"""Kimi Delta Attention layers: device time per traced step of the step
program's instructions under a `kda.*` scope (`kda.project`, `kda.conv`,
`kda.scan`, `kda.gate`, `kda.out` of `models/mixers.py`'s "kda" mixer: the
projections with the decay's and the gate's low-rank pairs, the three
convolutions, the decay with the rule of `ops/gated_delta.py`, the gated
norm, the output product; forward, remat repeat and backward). By scope
alone, so a kernel that later runs under one of them is counted without an
edit. None for a program without `kda.*` scopes."""

from benchmark.layer_metrics.gdn_scan_ms_per_step import ms_under

SCOPE_PREFIX = "kda."


def read(run):
    return ms_under(run, SCOPE_PREFIX)
