"""Pallas kernels: device time per traced step of the flash-attention
kernels of a patterned model's full-attention layers (forward, its remat
repeat, dk/dv and dq), told by signature and shape as
`attn_flash_roofline.flash_kernels` tells them. None for a program without
an `attn.*` scope."""

from benchmark.harness import scope_time
from benchmark.layer_metrics import attn_flash_roofline


def read(run):
    kernels = attn_flash_roofline.traced_kernels(run)
    return scope_time.ms_per_step(run, set(kernels)) if kernels else None
