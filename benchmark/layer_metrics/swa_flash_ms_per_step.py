"""Pallas kernels: device time per traced step of the flash-attention kernels
of the sliding-window layers (under `attn.window`): a layer's forward, its
remat repeat, dk/dv and dq, told as `swa_flash_roofline.part` tells them.
None for a program without an `attn.*` scope."""

from benchmark.layer_metrics import swa_flash_roofline


def read(run):
    return swa_flash_roofline.read_ms(run, "window", "swa_flash_ms_per_step")
