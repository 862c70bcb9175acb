"""Jitted step: model FLOP/s utilisation, in percent. The family's model
FLOPs per sample (forward and backward, 2 per multiply-add, no recomputation)
times the rate of the untraced stretch, over chips times the published bf16
peak. An end-to-end utilisation: not a kernel's roofline share, and it says
nothing about idle time."""


def read(run):
    if run.peaks is None:
        return None
    flops = run.family.flops_per_sample(run.cell.config, run.cell.traffic)
    rate = run.window.steps_per_second() * run.samples_per_step
    return 100.0 * flops * rate / (run.chips * run.peaks.bf16_flops)
