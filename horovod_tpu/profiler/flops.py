"""Model-FLOPs accounting: one home for every FLOPs/peak constant.

Before this module, the peak-TFLOPs table and per-model FLOPs constants
(197e12, 12.3e9, 4.1e9, ...) were hand-maintained in `bench.py` and
three profiling scripts, and could silently drift apart. They now live
here, demoted to *documented fallbacks*: the primary FLOPs
source is XLA's own cost analysis of the compiled step
(`compiled_cost_flops`), which counts exactly the program that ran,
remat recomputation included.

Conventions (they differ, and the delta matters — see docs/perf.md):

* The conv-model constants (ResNet/Inception/VGG) follow the
  torchvision **multiply-add (MAC)** convention: one MAC = 1 "FLOP".
  That is the convention every BENCH round so far used, so the headline
  `mfu` fields keep it for round-over-round comparability.
* XLA's HloCostAnalysis (and chip spec peaks) count a fused
  multiply-add as **2 FLOPs**, so for conv models the XLA-derived
  number is ~2x the MAC constant. `train_flops_per_image(...,
  convention="flops")` returns the 2x variant for like-for-like
  comparison with XLA.
* The transformer analytic formula (the standard 6N accounting, PaLM
  appendix B / Chowdhery et al., 2022) already counts mul+add
  separately, so it is directly comparable with XLA.

MFU itself is defined as in the PaLM paper: observed throughput x model
FLOPs per sample, divided by the chip's peak FLOP/s.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

from horovod_tpu.common.config import _env_on
from horovod_tpu.common.exceptions import HorovodTpuError

# Peak dense bf16 TFLOP/s per chip by device kind (public specs).
PEAK_TFLOPS = {
    "TPU v4": 275.0, "TPU v5 lite": 197.0, "TPU v5litepod": 197.0,
    "TPU v5": 459.0, "TPU v5p": 459.0, "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
}

#: HBM GiB per chip by device kind (public specs) — the budget the
#: static per-device peak-HBM estimate (analysis/shard.py, bench.py
#: `memory` stamp, scripts/perf_gate.py) is judged against.
HBM_GIB = {
    "TPU v4": 32.0, "TPU v5 lite": 16.0, "TPU v5litepod": 16.0,
    "TPU v5": 95.0, "TPU v5p": 95.0, "TPU v6 lite": 32.0,
    "TPU v6e": 32.0,
}

#: Forward GMACs per image @224 (torchvision multiply-add convention —
#: see module docstring; the roofline doc's 4.1 GFLOP ResNet-50 number).
RESNET_FWD_GMACS = {50: 4.1, 101: 7.8, 152: 11.5}
#: Inception V3 fwd @299, same convention.
INCEPTION_V3_FWD_GMACS = 5.73
#: VGG-16 fwd @224, same convention.
VGG16_FWD_GMACS = 15.5

#: Training step ~= forward + 2x backward.
TRAIN_STEP_MULTIPLIER = 3.0


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


def hbm_bytes_per_chip(device_kind: Optional[str] = None
                       ) -> Optional[int]:
    """HBM bytes per chip (None on unknown chip/CPU).

    HOROVOD_BENCH_HBM_GB overrides (non-standard boards, or arming the
    memory gate on CPU hosts)."""
    env = os.environ.get("HOROVOD_BENCH_HBM_GB")
    if env:
        # Loud on garbage: a silent fallback would skew the memory
        # gate in exactly the runs that set this knob.
        try:
            return int(float(env) * (1 << 30))
        except ValueError:
            raise ValueError(
                f"HOROVOD_BENCH_HBM_GB={env!r} is not a number")
    if device_kind is None:
        try:
            import jax
            device_kind = jax.devices()[0].device_kind
        except Exception:
            return None
    for name, gib in HBM_GIB.items():
        if device_kind.startswith(name):
            return int(gib * (1 << 30))
    return None


def _per_image(gmacs: float, convention: str) -> float:
    if convention == "macs":
        return gmacs * 1e9 * TRAIN_STEP_MULTIPLIER
    if convention == "flops":
        # mul+add counted separately — XLA / spec-peak convention.
        return 2.0 * gmacs * 1e9 * TRAIN_STEP_MULTIPLIER
    raise ValueError(f"unknown FLOPs convention {convention!r}")


def resnet_train_flops_per_image(depth: int = 50,
                                 convention: str = "macs") -> float:
    """Fallback training FLOPs/image for ResNet @224."""
    return _per_image(RESNET_FWD_GMACS[depth], convention)


def inception_v3_train_flops_per_image(convention: str = "macs") -> float:
    return _per_image(INCEPTION_V3_FWD_GMACS, convention)


def vgg16_train_flops_per_image(convention: str = "macs") -> float:
    return _per_image(VGG16_FWD_GMACS, convention)


def transformer_train_flops_per_token(d_model: int, d_ff: int,
                                      n_layers: int, vocab: int,
                                      seq: int) -> float:
    """Analytical decoder-LM training FLOPs per token (6N + attention).

    The standard accounting (PaLM appendix B): matmul params
    (non-embedding) N ~= layers*(4*D^2 attn + 2*D*F ffn), fwd+bwd ~= 6*N
    per token; attention scores+values fwd+bwd ~= 12*L*S*D per token
    (causal halves it -> 6*L*S*D); + 6*D*V for the unembedding matmul.
    Counts mul+add separately, so directly comparable with XLA."""
    n_matmul = n_layers * (4 * d_model * d_model + 2 * d_model * d_ff)
    return float(6 * n_matmul + 6 * n_layers * seq * d_model
                 + 6 * d_model * vocab)


def transformer_matmul_params(d_model: int, d_ff: int, n_layers: int,
                              vocab: int) -> int:
    """Non-embedding matmul params + embedding/unembedding (for the
    params_m bench field)."""
    n_matmul = n_layers * (4 * d_model * d_model + 2 * d_model * d_ff)
    return n_matmul + 2 * d_model * vocab


# ---------------------------------------------------------------- XLA

def xla_flops_enabled() -> bool:
    """HOROVOD_PERFSCOPE_XLA_FLOPS gate (default on): `0` makes every
    consumer (bench sections) skip the cost-analysis derivation and use
    the hand-constant fallbacks."""
    return _env_on("HOROVOD_PERFSCOPE_XLA_FLOPS", True)


def compiled_cost_flops(compiled) -> Optional[float]:
    """Total FLOPs of a compiled XLA program, from the compiler's own
    HloCostAnalysis — the primary MFU source (hand constants above are
    the fallback).

    `compiled` is what `jax.jit(f).lower(*args).compile()` returns.
    `cost_analysis()` yields a dict (newer JAX) or a per-device list of
    dicts; under SPMD partitioning the module is per-device code, so
    the number is per-participating-device. Returns None when the
    backend exposes no cost model (some CPU builds) or the FLOPs entry
    is missing/zero — callers must fall back."""
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
    executable you are about to run anyway (bench._scan_timed does)."""
    try:
        return compiled_cost_flops(fn.lower(*args, **kwargs).compile())
    except Exception:
        return None


def pick_flops(xla_flops: Optional[float], fallback: Optional[float]
               ) -> Tuple[Optional[float], str]:
    """(flops, source): XLA wins when present, else the hand constant,
    else (None, "none")."""
    if xla_flops:
        return xla_flops, "xla"
    if fallback:
        return fallback, "fallback"
    return None, "none"
