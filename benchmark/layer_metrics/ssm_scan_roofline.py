"""State-space layers: the selective scan's share of its roofline, in
percent: the least time the chip could take for a step's scans over the time
the instructions under `ssm.scan` took (`ssm_scan_ms_per_step`).

The least time is counted from the family's `ssm_scan_work`: per
state-space layer the forward passes a step runs (two under remat) and one
backward pass, each the larger of its FLOPs over the bf16 peak and its bytes
over the HBM peak, every operand and result moved once. The recurrence is
elementwise, so against the matrix unit's peak its FLOPs are nothing and
the bound is the bytes': what the vector unit's work costs beyond moving the
operands counts against the share, not into it. None for a program without
the scope or a family without `ssm_scan_work`."""

from benchmark.layer_metrics import ssm_scan_ms_per_step
from benchmark.layer_metrics.gdn_scan_roofline import least_seconds


def read(run):
    if run.peaks is None or not hasattr(run.family, "ssm_scan_work"):
        return None
    took = ssm_scan_ms_per_step.read(run)
    if not took:
        return None
    least = sum(least_seconds(work, run.peaks)[0] for work in
                run.family.ssm_scan_work(run.cell.config, run.cell.traffic))
    return 100.0 * least * 1e3 / took
