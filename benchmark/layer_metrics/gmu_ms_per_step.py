"""State-space layers: device time per traced step of the step program's
instructions under a `gmu.*` scope (`gmu.project`, `gmu.gate`, `gmu.out` of
`models/transformer.py`: a Gated Memory Unit's two products and the gate on
the memory a state-space layer handed on; forward, remat repeat and
backward). By scope alone. None for a program without `gmu.*` scopes."""

from benchmark.layer_metrics.gdn_scan_ms_per_step import ms_under

SCOPE_PREFIX = "gmu."


def read(run):
    return ms_under(run, SCOPE_PREFIX)
