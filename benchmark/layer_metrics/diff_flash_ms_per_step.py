"""Pallas kernels: device time per traced step of the flash-attention
kernels of differential attention (forward, its remat repeat, dk/dv and dq
of both softmaxes of every windowed, full and cross layer), told by
signature and shape as `diff_flash_roofline.flash_kernels` tells them. Its
two parts, the windowed layers' kernels and the others', go to the run's
log with the time a layer of each kind takes. None for a program without an
`attn.*` scope."""

from benchmark.harness.runner import say
from benchmark.layer_metrics import diff_flash_roofline


def read(run):
    found = diff_flash_roofline.parts(run)
    if not found:
        return None
    layers = run.family.flash_kernel_shapes(run.cell.config,
                                            run.cell.traffic)["layers"]
    say(f"{run.cell.name}: diff_flash_ms_per_step by part: " + "; ".join(
        f"{part} {took:.3f} ms over {layers[part][0]} layer(s), "
        f"{took / max(layers[part][0], 1):.3f} a layer (least {least:.3f})"
        for part, (took, least) in found.items()))
    return sum(took for took, _ in found.values())
