"""Gated short-convolution layers: the share of its roofline of what lies
between a layer's two products, in percent: the least time the chip could
take for a step's passes over the time the instructions under
`shortconv.mix` took (`shortconv_mix_ms_per_step`).

The least time is counted from the family's `shortconv_mix_work`: per layer
the forward passes a step runs (two under remat) and one backward pass, each
the larger of its FLOPs over the bf16 peak and its bytes over the HBM peak
(the bytes, by two orders: the pass is memory-bound). The least work is one
pass that reads B, C and X and writes the gated convolution, and one that
reads them and the cotangent and writes three cotangents, whatever
implements it: float32 intermediates that reach memory, a padded copy or a
second pass count against the share, not into it. None for a program without
the scope or a family without `shortconv_mix_work`."""

from benchmark.layer_metrics import shortconv_mix_ms_per_step
from benchmark.layer_metrics.gdn_scan_roofline import least_seconds


def read(run):
    if run.peaks is None or not hasattr(run.family, "shortconv_mix_work"):
        return None
    took = shortconv_mix_ms_per_step.read(run)
    if not took:
        return None
    least = sum(least_seconds(work, run.peaks)[0] for work in
                run.family.shortconv_mix_work(run.cell.config,
                                              run.cell.traffic))
    return 100.0 * least * 1e3 / took
