"""Kimi Delta Attention layers: device time per traced step of the step
program's instructions under the `kda.scan` scope of `models/mixers.py`:
from the decay's projected logits to the rule's output o (the softplus and
the rate, beta's sigmoid, g's running sums inside the chunks and their
reverse for the gradient, and the per-channel kernels of
`ops/gated_delta.py`; forward, remat repeat and backward). By scope alone.
None for a program without the scope."""

from benchmark.layer_metrics.gdn_scan_ms_per_step import ms_under

SCOPE = "kda.scan"


def read(run):
    return ms_under(run, SCOPE)
