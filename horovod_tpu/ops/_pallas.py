"""The one place the Pallas kernels decide how they run.

Every kernel family in `ops/` (flash attention, the fused conv+BN
kernels) builds its `pallas_call` through `pallas_call` below, so the
"compiled for the chip or interpreted" decision is made — and can be
asserted on, as `chip_smoke.py` does — in exactly one function.
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def interpret() -> bool:
    """True when the kernels run through the Pallas interpreter: on every
    backend but the TPU (the CPU test suite, tests/conftest.py). On the
    TPU they are compiled by Mosaic."""
    return jax.default_backend() != "tpu"


def pallas_call(kernel, **kwargs):
    """`pl.pallas_call` with the interpret decision made here.

    A kernel compiled for the chip is traced with 64-bit mode off: under
    `jax_enable_x64` the index maps return i64 and Python float constants
    become f64, which Mosaic refuses ("failed to legalize func.return" /
    "tpu.truncf"). The kernels take and return 32-bit-or-narrower arrays,
    so tracing them in 32-bit mode changes no result and makes x64 a
    non-issue for their callers. The interpreter keeps the caller's mode.
    """
    if interpret():
        return pl.pallas_call(kernel, interpret=True, **kwargs)
    call = pl.pallas_call(kernel, **kwargs)

    def call_32bit(*operands):
        with jax.enable_x64(False):
            return call(*operands)

    return call_32bit
