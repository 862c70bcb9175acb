"""Linear attention: the gated delta rule's share of its roofline, in
percent: the least time the chip could take for a step's scans over the time
the instructions under `gdn.scan` took (`gdn_scan_ms_per_step`).

The least time is counted from the family's `gdn_scan_work`: per linear
layer the forward passes a step runs (two under remat) and one backward
pass, each the larger of its FLOPs over the bf16 peak and its bytes over
the HBM peak. The rule's least work is the recurrent form's (3 dk dv
multiply-adds a token a head, each operand and result moved once): what the
chunked form adds to it, and the normalisations under the same scope, count
against the share, not into it. None for a program without the scope or a
family without `gdn_scan_work`."""

from benchmark.layer_metrics import gdn_scan_ms_per_step


def least_seconds(work, peaks):
    """(seconds, which bound holds) for the executions `work` counts:
    (executions, FLOPs, bytes)."""
    executions, flops, moved = work
    compute, memory = flops / peaks.bf16_flops, moved / peaks.hbm_bytes_per_s
    return (executions * max(compute, memory),
            "compute" if compute >= memory else "memory")


def read(run):
    if run.peaks is None or not hasattr(run.family, "gdn_scan_work"):
        return None
    took = gdn_scan_ms_per_step.read(run)
    if not took:
        return None
    least = sum(least_seconds(work, run.peaks)[0] for work in
                run.family.gdn_scan_work(run.cell.config, run.cell.traffic))
    return 100.0 * least * 1e3 / took
