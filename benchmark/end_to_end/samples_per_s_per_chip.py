"""Samples (tokens of an LM, images of a vision model) completed per second
per chip: the samples of one step over the median time between two steps'
completions in the window, on the host's clock, each stamp taken when the
host had that step's loss (`harness/loop.py` says why the median)."""


def read(run):
    return run.window.steps_per_second() * run.samples_per_step / run.chips
