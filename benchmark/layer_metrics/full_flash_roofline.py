"""Pallas kernels: the full-attention layers' flash kernels' share of their
roofline, in percent, as `swa_flash_roofline` computes the windowed layers':
the work is the causal half's (a query sees (seq + 1) / 2 keys on
average)."""

from benchmark.layer_metrics import swa_flash_roofline


def read(run):
    return swa_flash_roofline.read_share(run, "full")
