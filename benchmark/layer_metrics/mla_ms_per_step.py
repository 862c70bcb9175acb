"""Latent attention: device time per traced step of the step program's
instructions under a `mla.*` scope (`mla.project`, `mla.rope`, `mla.attend`,
`mla.out` of `models/transformer.py`: projections, the latent's norm, the
rotation, the concatenations, the output product; forward, remat repeat and
backward) and of the flash-attention kernels, which are told by signature
alone (`mla_flash_roofline.flash_kernels`), whatever scope their metadata
keeps. None for a program without `mla.*` scopes."""

from benchmark.harness import scope_time
from benchmark.layer_metrics import mla_flash_roofline

SCOPE_PREFIX = "mla."


def read(run):
    if not scope_time.traced(run):
        return None
    scoped = scope_time.names_under(run.program.as_text(), run.instructions,
                                    SCOPE_PREFIX)
    if not scoped:
        return None
    kernels = set(mla_flash_roofline.flash_kernels(run.instructions))
    return scope_time.ms_per_step(run, scoped | kernels)
