"""The chip's peak FLOP/s and the FLOPs of a compiled program.

One convention: a multiply-add is **2 FLOPs**, as XLA's HloCostAnalysis,
the chip's published peak and `benchmark/harness/peaks.py` count it. The
module keeps no per-model constant: the FLOPs of a step come from the
compiler's own cost analysis of the program that ran
(`compiled_cost_flops`, remat recomputation included), and that is the
number `perfscope.set_model_flops` is meant to be fed.

MFU is defined as in the PaLM paper (Chowdhery et al., 2022, appendix
B): observed throughput x model FLOPs per sample, divided by the chip's
peak FLOP/s.
"""

from __future__ import annotations

import os
from typing import Optional

from horovod_tpu.common.exceptions import HorovodTpuError

# Peak dense bf16 TFLOP/s per chip by device kind (public specs).
PEAK_TFLOPS = {
    "TPU v4": 275.0, "TPU v5 lite": 197.0, "TPU v5litepod": 197.0,
    "TPU v5": 459.0, "TPU v5p": 459.0, "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
}


def peak_flops_per_chip(device_kind: Optional[str] = None
                        ) -> Optional[float]:
    """Peak dense bf16 FLOP/s for this chip. None off the TPU (CPU runs
    compute no MFU); a TPU kind missing from PEAK_TFLOPS is an error —
    an MFU against a guessed or absent peak would be silently wrong.

    HOROVOD_BENCH_PEAK_TFLOPS overrides (measured-peak MFU runs)."""
    env = os.environ.get("HOROVOD_BENCH_PEAK_TFLOPS")
    if env:
        # Loud on garbage: silently falling back to the spec table
        # would skew every MFU in exactly the runs that set this knob.
        try:
            return float(env) * 1e12
        except ValueError:
            raise ValueError(
                f"HOROVOD_BENCH_PEAK_TFLOPS={env!r} is not a number")
    if device_kind is None:
        try:
            import jax
            device_kind = jax.devices()[0].device_kind
        except Exception:
            return None
    for name, tf in PEAK_TFLOPS.items():
        if device_kind.startswith(name):
            return tf * 1e12
    if device_kind.startswith("TPU"):
        raise HorovodTpuError(
            f"no peak FLOP/s known for device kind {device_kind!r}: add "
            "it to PEAK_TFLOPS (profiler/flops.py)")
    return None


def compiled_cost_flops(compiled) -> Optional[float]:
    """Total FLOPs of a compiled XLA program, from the compiler's own
    HloCostAnalysis — the source of a step's model FLOPs.

    `compiled` is what `jax.jit(f).lower(*args).compile()` returns.
    `cost_analysis()` yields a dict (newer JAX) or a per-device list of
    dicts; under SPMD partitioning the module is per-device code, so
    the number is per-participating-device. Returns None when the
    backend exposes no cost model (some CPU builds) or the FLOPs entry
    is missing/zero."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        # Per-device list (older JAX): under SPMD every device runs the
        # same module, so take the first entry with a POSITIVE NUMERIC
        # flops count — device 0's dict can be empty, and some builds
        # report -1 or a non-numeric placeholder for "unknown", which
        # must not shadow a populated later entry.
        def _usable(d):
            try:
                return float(d.get("flops")) > 0.0
            except (TypeError, ValueError):
                return False
        dicts = [d for d in ca if isinstance(d, dict)]
        ca = next((d for d in dicts if _usable(d)),
                  dicts[0] if dicts else {})
    if not isinstance(ca, dict):
        return None
    f = ca.get("flops")
    try:
        f = float(f)
    except (TypeError, ValueError):
        return None
    return f if f > 0.0 else None


def jit_cost_flops(fn, *args, **kwargs) -> Optional[float]:
    """FLOPs of `jax.jit`-wrapped `fn` at these args via AOT
    lower+compile. Pays a compile — prefer `compiled_cost_flops` on an
    executable you are about to run anyway."""
    try:
        return compiled_cost_flops(fn.lower(*args, **kwargs).compile())
    except Exception:
        return None
