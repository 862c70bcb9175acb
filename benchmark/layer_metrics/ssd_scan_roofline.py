"""State-space dual layers: the chunked scan's share of its roofline, in
percent: the least time the chip could take for a step's scans over the time
the instructions under `ssd.scan` took (`ssd_scan_ms_per_step`).

The least time is counted from the family's `ssd_scan_work`: per Mamba-2
layer the forward passes a step runs (two under remat) and one backward pass
at twice the forward's operations, each the larger of its FLOPs over the
bf16 peak and its bytes over the HBM peak, every operand and result moved
once. The FLOPs are the chunked form's at the published chunk, of the
(Q x Q) scores only what the causal mask holds: what the kernels compute
above the diagonal, their vector-unit work on the decays and the entry
states they write and read count against the share, not into it. None for a
program without the scope or a family without `ssd_scan_work`."""

from benchmark.layer_metrics import ssd_scan_ms_per_step
from benchmark.layer_metrics.gdn_scan_roofline import least_seconds


def read(run):
    if run.peaks is None or not hasattr(run.family, "ssd_scan_work"):
        return None
    took = ssd_scan_ms_per_step.read(run)
    if not took:
        return None
    least = sum(least_seconds(work, run.peaks)[0] for work in
                run.family.ssd_scan_work(run.cell.config, run.cell.traffic))
    return 100.0 * least * 1e3 / took
