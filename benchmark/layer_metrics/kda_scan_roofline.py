"""Kimi Delta Attention layers: the per-channel delta rule's share of its
roofline, in percent: the least time the chip could take for a step's scans
over the time the instructions under `kda.scan` took
(`kda_scan_ms_per_step`).

The least time is counted from the family's `kda_scan_work`: per KDA layer
the forward passes a step runs (two under remat) and one backward pass, each
the larger of its FLOPs over the bf16 peak and its bytes over the HBM peak.
The rule's least work is the recurrent form's (3 dk dv multiply-adds a token
a head, each operand and result moved once, g as dk float32 numbers a token
a head): what the chunked form adds to it (the decayed products block by
block, the explicit differences on the diagonal blocks, the inverse), the
residuals it writes and the decay's softplus under the same scope count
against the share, not into it. None for a program without the scope or a
family without `kda_scan_work`."""

from benchmark.layer_metrics import kda_scan_ms_per_step
from benchmark.layer_metrics.gdn_scan_roofline import least_seconds


def read(run):
    if run.peaks is None or not hasattr(run.family, "kda_scan_work"):
        return None
    took = kda_scan_ms_per_step.read(run)
    if not took:
        return None
    least = sum(least_seconds(work, run.peaks)[0] for work in
                run.family.kda_scan_work(run.cell.config, run.cell.traffic))
    return 100.0 * least * 1e3 / took
