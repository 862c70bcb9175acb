"""Pallas kernels: device time per traced step of the flash-attention kernels
of the full-attention layers of a model that also has windowed ones (under
`attn.attend` and NOT under `attn.window`: SmallThinker's NoPE layer over the
whole causal half), told as `swa_flash_roofline.part` tells them. None for a
program without an `attn.*` scope."""

from benchmark.layer_metrics import swa_flash_roofline


def read(run):
    return swa_flash_roofline.read_ms(run, "full", "full_flash_ms_per_step")
