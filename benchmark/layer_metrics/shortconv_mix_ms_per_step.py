"""Gated short-convolution layers: device time per traced step of the step
program's instructions under the `shortconv.mix` scope of
`models/mixers.py`: everything between the layer's two products (the gate
B * X, the depthwise causal convolution's shifted multiply-adds, the gate C;
forward, remat repeat and backward with the taps' gradient). By scope alone.
None for a program without the scope."""

from benchmark.layer_metrics.gdn_scan_ms_per_step import ms_under

SCOPE = "shortconv.mix"


def read(run):
    return ms_under(run, SCOPE)
