#!/usr/bin/env python3
"""chip_smoke.py — does the training path start, compile and step on the TPU?

The quickest standing proof that the system still runs on the chip, through
the entry points a user calls (`hvd.init`, the eager collectives,
`tfm.build_train_step` on a `MeshSpec`, `hvd.DistributedOptimizer.step`, the
launcher), at the full width of the models the repo is measured on:

    python chip_smoke.py             one chip, one process
    python chip_smoke.py --chips 4   only what exists across chips: the
                                     launcher's one process per chip, then
                                     one process over four chips

It is a check, not a benchmark: the step times, rates and peak memory it
prints are one run's information. It fails — non-zero exit, no `ok` line —
when JAX finds no TPU, when x64 or rank emulation is on, or when any phase
raises; nothing here catches a phase's exception. Each phase is a plain
function of its sizes, so tests/test_chip_smoke.py can rehearse it on the CPU
at a tiny size. The last line of stdout is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import signal
import statistics
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from benchmark.families import (granite_hybrid, kimi_linear, olmo_hybrid,
                                phi4_flash)
from benchmark.harness import peaks
from benchmark.layer_metrics import gdn_scan_roofline
from horovod_tpu import native
from horovod_tpu.core import topology
from horovod_tpu.models import resnet, transformer as tfm
from horovod_tpu.ops import _pallas
from horovod_tpu.ops import causal_conv as cc
from horovod_tpu.ops import gated_delta as gd
from horovod_tpu.ops import grouped_matmul as gm
from horovod_tpu.ops import row_gather as rg
from horovod_tpu.ops import selective_scan as ss
from horovod_tpu.ops import ssd_scan as ssd
from horovod_tpu.ops.flash_attention import (backward_products,
                                             causal_tile_share,
                                             flash_attention,
                                             grid_step_share,
                                             masked_attention_reference,
                                             row_strip_share,
                                             window_tile_share)
from horovod_tpu.parallel.mesh import MeshSpec, build_mesh
from horovod_tpu.parallel.moe import held_rows
from horovod_tpu.parallel.ring_attention import blockwise_attention_reference

REPO = os.path.dirname(os.path.abspath(__file__))

#: The flagship LM: L12 D2048 F8192 H16 V32768,
#: bf16, flash attention, remat — run at B=12 S=1024.
FLAGSHIP = tfm.TransformerConfig(
    vocab=32768, d_model=2048, n_heads=16, d_ff=8192, n_layers=12,
    max_seq=1024, attn="flash", dtype=jnp.bfloat16, remat=True)

#: bf16 carries 8 bits of mantissa: two runs of the same step that differ
#: only in reduction order agree to a few units of it.
BF16_RTOL = 4 * 2.0 ** -8


def say(msg: str) -> None:
    print(msg, flush=True)


class CompileLog:
    """Counts what JAX compiles, from its own monitoring events: every
    compile request (a new trace reaching the backend, cached or not) and
    the persistent cache's hits and misses."""

    def __init__(self) -> None:
        self.requests = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def peak_hbm_gib() -> float | None:
    stats = jax.local_devices()[0].memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return None  # the CPU backend reports none
    return stats["peak_bytes_in_use"] / 2**30


def check_losses(name: str, losses: list) -> None:
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"{name}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(
            f"{name}: loss did not fall on a fixed batch: {losses}")


def timed_steps(name: str, one_step, steps: int, log: CompileLog):
    """Run `steps` steps and return (losses, seconds). Each step is timed
    on the host clock behind `block_until_ready` (inside `one_step`, which
    returns the loss). Step 1 may compile; any compile request after it
    is a failure, and so is a loss that is not finite and falling."""
    losses, secs = [], []
    after_first = None
    for _ in range(steps):
        t0 = time.perf_counter()
        losses.append(float(one_step()))
        secs.append(time.perf_counter() - t0)
        if after_first is None:
            after_first = log.requests
    if log.requests != after_first:
        raise AssertionError(
            f"{name}: {log.requests - after_first} recompile(s) after "
            "step 1")
    check_losses(name, losses)
    say(f"[{name}] {steps} steps, loss {losses[0]:.4f} -> {losses[-1]:.4f}, "
        f"first step {secs[0]:.2f} s, then median "
        f"{statistics.median(secs[1:]) * 1e3:.1f} ms/step, "
        "0 recompiles after step 1")
    return losses, secs


def information(name: str, rate: str) -> None:
    peak = peak_hbm_gib()
    say(f"[{name}] information only (one run, not a benchmark): {rate}, "
        "peak HBM of the process so far "
        f"{'not reported' if peak is None else f'{peak:.2f} GiB'}")


# ----------------------------------------------------------------- preflight

def preflight(chips: int) -> None:
    """Refuse anything that is not the real thing, then say what runs."""
    if os.environ.get("HOROVOD_TPU_EMULATE_RANKS"):
        raise SystemExit("chip_smoke: HOROVOD_TPU_EMULATE_RANKS is set — "
                         "the smoke never emulates")
    if jax.config.jax_enable_x64:
        raise SystemExit("chip_smoke: jax_enable_x64 is on — the training "
                         "path is a 32-bit path")
    hvd.init()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX came up on '{devs[0].platform}', "
                         "not on a TPU — nothing to check")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, {len(devs)} visible")
    import jaxlib
    import libtpu
    say(f"[versions] jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {libtpu.__version__}")
    say(f"[device] kind={devs[0].device_kind!r} count={len(devs)} "
        f"hvd.size={hvd.size()}")
    cache, set_here = topology.compile_cache_dir(topology.state().config,
                                                 devs[0].platform)
    say(f"[compile cache] {cache} "
        f"({'set by hvd.init' if set_here else 'JAX_COMPILATION_CACHE_DIR'})")
    say(f"[native control plane] {native.status()}")


# ----------------------------------------------------------- one-chip phases

def eager_api(log: CompileLog, n: int = 1 << 20) -> None:
    """The eager collectives against numpy, on whatever `hvd.init()` set
    up. Rank r's tensor is row r of a stacked array (a plain array where
    this process owns one rank)."""
    k = hvd.size()
    rng = np.random.default_rng(0)
    rows = rng.standard_normal((k, n)).astype(np.float32)

    def mine(a):  # what this process passes for its rank(s)
        return a if k > 1 else a[0]

    def ranks(out):  # -> one row per rank
        out = np.asarray(out)
        return out if k > 1 else out[None]

    for r in ranks(hvd.allreduce(mine(rows), op=hvd.Sum)):
        np.testing.assert_allclose(r, rows.sum(0), rtol=1e-5, atol=1e-5)
    small = rows[:, :1024].reshape(k, 32, 32)
    g = hvd.grouped_allreduce([mine(rows), mine(small)], op=hvd.Average)
    np.testing.assert_allclose(ranks(g[0])[0], rows.mean(0), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(ranks(g[1])[0], small.mean(0), rtol=1e-5,
                               atol=1e-6)
    for r in ranks(hvd.allgather(mine(small))):
        np.testing.assert_array_equal(r, small.reshape(k * 32, 32))
    for r in ranks(hvd.broadcast(mine(rows), root_rank=k - 1)):
        np.testing.assert_array_equal(r, rows[k - 1])
    per = n // k
    rs = ranks(hvd.reducescatter(mine(rows), op=hvd.Sum))
    for i, r in enumerate(rs):
        np.testing.assert_allclose(r, rows.sum(0)[i * per:(i + 1) * per],
                                   rtol=1e-5, atol=1e-5)
    a2a = hvd.alltoall(mine(rows[:, :k * 4].reshape(k, k * 2, 2)))
    for dst, (out, splits) in enumerate(a2a if k > 1 else [a2a]):
        want = np.concatenate(
            [rows[src, :k * 4].reshape(k * 2, 2)[dst * 2:(dst + 1) * 2]
             for src in range(k)])
        np.testing.assert_array_equal(np.asarray(out), want)
        np.testing.assert_array_equal(np.asarray(splits), np.full(k, 2))
    hvd.barrier()
    say(f"[eager api] allreduce grouped_allreduce allgather broadcast "
        f"reducescatter alltoall barrier agree with numpy "
        f"({k} rank(s), {n} elements)")


def _rms_off(got, want) -> float:
    """The rms of got - want as a share of want's rms, in float32."""
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def _best_ms(runs: dict, repeats: int = 10) -> dict:
    """name -> milliseconds an execution of `fn(*args)` takes, the best of
    three batches of `repeats`, for `runs` name -> (fn, args)."""
    best = {}
    for name, (fn, args) in runs.items():
        jax.block_until_ready(fn(*args))
        batches = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(repeats):
                out = fn(*args)
            jax.block_until_ready(out)
            batches.append((time.perf_counter() - t0) / repeats)
        best[name] = min(batches) * 1e3
    return best


def _kernels_alone_ms(q, k, v, repeats: int = 10) -> dict:
    """Milliseconds an execution of each flash kernel takes alone (best of
    three batches of `repeats`; the backward is one kernel where its
    accumulators fit VMEM, `backward_products`, else dq and dk/dv together),
    causal, at q, k: (B, H, S, dqk) and v: (B, H, S, dv). Information only:
    alone the kernels read ~10% over their time inside a train step
    (PERF.md, PR 27)."""
    from horovod_tpu.ops import flash_attention as fa
    seq = q.shape[2]
    block = fa._auto_block(seq)
    flat = [x.reshape(-1, seq, x.shape[-1]) for x in (q, k, v)]
    scale = float(q.shape[-1]) ** -0.5
    fwd = jax.jit(lambda q, k, v: fa._fwd(q, k, v, True, scale, block,
                                          block))

    def bwd(q, k, v, o, lse, do):
        return fa._bwd(q, k, v, o, lse, do, None, True, scale, block, block)

    o, lse = fwd(*flat)
    runs = {"forward": (fwd, flat),
            "backward": (jax.jit(bwd), (*flat, o, lse, o))}
    return _best_ms(runs, repeats)


def flash_kernel(log: CompileLog, shape=(12, 16, 1024, 128),
                 wide=(2, 16, 4096, 192, 128)) -> None:
    """Flash attention forward and gradients against the plain reference,
    at the flagship's (B, H, S, dh) and at `wide` (B, H, S, dqk, dv): keys
    wider than values, the latent attention's shape (`dsv2lite-1chip`). On
    the TPU the kernel must be compiled by Mosaic, not interpreted, and the
    program must hold it. Then the forward and the backward alone, at both."""
    if _pallas.interpret() is on_tpu():
        raise AssertionError(
            f"Pallas interpret={_pallas.interpret()} on platform "
            f"{jax.devices()[0].platform}: the kernel would not run as "
            "compiled for this device")
    for widths in (shape + shape[-1:], tuple(wide)):
        _flash_against_reference(widths)


def _flash_against_reference(widths) -> None:
    *lead, dqk, dv = widths
    shape = (*lead, dqk) if dqk == dv else widths
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = (jax.random.normal(kk, (*lead, dqk), jnp.bfloat16)
            for kk in ks[:2])
    v, w = (jax.random.normal(kk, (*lead, dv), jnp.bfloat16)
            for kk in ks[2:])

    # Everything big is an argument: an array a jitted function closes
    # over is baked into the program as a constant (50 MB here), and into
    # every persistent-cache entry made of it.
    def loss(attn, q, k, v, w):
        o = attn(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o

    flash = jax.jit(jax.value_and_grad(
        lambda q, k, v, w: loss(flash_attention, q, k, v, w),
        argnums=(0, 1, 2), has_aux=True))
    compiled = flash.lower(q, k, v, w).compile()
    n_kernels = compiled.as_text().count("tpu_custom_call")
    products, resident = backward_products(shape[2], dqk, dv)
    want = 2 if products == 5 else 3    # forward; backward, or dk/dv and dq
    if on_tpu() and n_kernels != want:
        raise AssertionError(
            f"compiled flash program holds {n_kernels} Mosaic custom "
            f"calls, expected the forward and {want - 1} of the backward "
            f"pass ({products} products a block pair)")
    (_, o), grads = flash(q, k, v, w)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    with jax.default_matmul_precision("highest"):
        (_, o_ref), g_ref = jax.jit(jax.value_and_grad(
            lambda q, k, v, w: loss(blockwise_attention_reference,
                                    q, k, v, w),
            argnums=(0, 1, 2), has_aux=True))(*f32, w)
    worst = 0.0
    for name, got, want, like in (
            ("o", o, o_ref, v), ("dq", grads[0], g_ref[0], q),
            ("dk", grads[1], g_ref[1], k), ("dv", grads[2], g_ref[2], v)):
        got = np.asarray(got.astype(jnp.float32))
        want = np.asarray(want)
        if got.shape != like.shape or not np.all(np.isfinite(got)):
            raise AssertionError(f"flash {name}: bad shape or non-finite")
        err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        worst = max(worst, err)
        if err > BF16_RTOL:
            raise AssertionError(
                f"flash {name} differs from the reference by {err:.3g} of "
                f"its range (tolerance {BF16_RTOL:.3g})")
    alone = _kernels_alone_ms(q, k, v)
    say(f"[flash attention] shape {shape} bf16: fwd and dq/dk/dv agree "
        f"with the reference (worst {worst:.2e} of range); "
        f"causal_tile_share {causal_tile_share(shape[2]):.4f} (score "
        "entries computed over the causal half's), grid_step_share "
        f"{grid_step_share(shape[2]):.4f} (grid steps a head runs over the "
        "steps that compute), row_strip_share "
        f"{row_strip_share(shape[2]):.4f} (the forward's score entries in "
        f"strips of 256 rows or fewer), backward_products {products} "
        "(score-sized products a block pair of the backward pass: 5 in one "
        f"kernel, {resident / 2 ** 20:.1f} MiB of accumulators and output "
        "blocks resident; 7 in two); "
        f"interpret={_pallas.interpret()}, {n_kernels} tpu_custom_call "
        "in the compiled program; the kernels alone (information only), "
        "ms an execution: "
        + ", ".join(f"{name} {ms:.3f}" for name, ms in alone.items()))


def grouped_kernel(log: CompileLog,
                   shapes=((65536, 2048, 1024, 64, 65536),
                           (12288, 2048, 1408, 8, 6144))) -> None:
    """The grouped matmul (ops/grouped_matmul.py) against `lax.ragged_dot`
    and its `jax.vjp`, then its three products alone, at the expert cells'
    (rows, D, F, experts, rows routed): `olmoe-1chip`'s 64 experts over
    every pair, `dsv2lite-1chip`'s held eighth in its row buffer, the free
    rows zero and in the last group. Uneven seeded group sizes."""
    for n_rows, d, f, experts, routed in shapes:
        rng = np.random.default_rng(0)
        sizes = rng.multinomial(routed, rng.dirichlet(np.full(experts, 8.0)))
        sizes[-1] += n_rows - routed
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        rows = jax.random.normal(ks[0], (n_rows, d), jnp.bfloat16)
        rows = rows * (jnp.arange(n_rows) < routed)[:, None].astype(rows.dtype)
        weights = jax.random.normal(ks[1], (experts, d, f),
                                    jnp.bfloat16) * d ** -0.5
        cot = jax.random.normal(ks[2], (n_rows, f), jnp.bfloat16)
        group_sizes = jnp.asarray(sizes, jnp.int32)

        def three(product):
            def run(rows, weights, cot, group_sizes):
                out, vjp = jax.vjp(
                    lambda r, w: product(r, w, group_sizes), rows, weights)
                return (out,) + vjp(cot)
            return jax.jit(run)

        mine = three(gm.grouped_matmul)
        n_kernels = mine.lower(rows, weights, cot, group_sizes).compile(
            ).as_text().count("tpu_custom_call")
        if on_tpu() and n_kernels != 3:
            raise AssertionError(
                f"compiled grouped matmul and its backward pass hold "
                f"{n_kernels} Mosaic custom calls, expected three")
        got = mine(rows, weights, cot, group_sizes)
        want = three(jax.lax.ragged_dot)(rows, weights, cot, group_sizes)
        worst = 0.0
        for name, g, w in zip(("rows x weights", "towards the rows",
                               "towards the weights"), got, want):
            g, w = (np.asarray(x.astype(jnp.float32)) for x in (g, w))
            if g.shape != w.shape or not np.all(np.isfinite(g)):
                raise AssertionError(
                    f"grouped matmul {name}: bad shape or non-finite")
            err = float(np.max(np.abs(g - w)) / np.max(np.abs(w)))
            worst = max(worst, err)
            if err > BF16_RTOL:
                raise AssertionError(
                    f"grouped matmul {name} differs from lax.ragged_dot by "
                    f"{err:.3g} of its range (tolerance {BF16_RTOL:.3g})")
        plan = gm.visits(group_sizes, n_rows)
        alone = _best_ms({
            "rows x weights": (jax.jit(
                lambda r, w, c, p: gm._rows_product(r, w, p, False)),
                (rows, weights, cot, plan)),
            "towards the rows": (jax.jit(
                lambda r, w, c, p: gm._rows_product(c, w, p, True)),
                (rows, weights, cot, plan)),
            "towards the weights": (jax.jit(
                lambda r, w, c, p: gm._weights_product(
                    r, c, p, w.shape[0])),
                (rows, weights, cot, plan))})
        # the share is of the benchmark's table of peaks: none off the TPU
        least_ms = 2e3 * n_rows * d * f / peaks.for_kind(
            jax.devices()[0].device_kind).bf16_flops if on_tpu() else None
        say(f"[grouped matmul] {n_rows} rows x ({experts}, {d}, {f}) bf16, "
            f"{routed} rows routed: the three products agree with "
            f"lax.ragged_dot and its vjp (worst {worst:.2e} of range); "
            f"visit_share {gm.visit_share(sizes.tolist()):.4f} (tile visits "
            "over row tiles), strip_share "
            f"{gm.strip_share(sizes.tolist()):.4f} (strips multiplied over "
            f"the rows' strips); interpret={_pallas.interpret()}, "
            f"{n_kernels} tpu_custom_call in the compiled program; the "
            "kernels alone (information only), ms an execution and share "
            "of the bf16 peak for all the rows: "
            + ", ".join(
                f"{name} {ms:.3f}" + (f" ({100 * least_ms / ms:.1f}%)"
                                      if least_ms else "")
                for name, ms in alone.items()))


def _counted(runs: dict, want: dict, what: str) -> dict:
    """The Mosaic custom calls each compiled program of `runs` holds; on the
    TPU they must be `want`."""
    kernels = {name: fn.as_text().count(
        'custom_call_target="tpu_custom_call"')
        for name, (fn, _) in runs.items()}
    if on_tpu() and kernels != want:
        raise AssertionError(
            f"the compiled {what} holds {kernels} Mosaic custom calls, "
            f"expected {want}")
    return kernels


def _timed_without_recompiles(log: CompileLog, runs: dict, what: str,
                              repeats: int) -> dict:
    before = log.requests
    ms = _best_ms(runs, repeats=repeats)
    if log.requests != before:
        raise AssertionError(f"{what}: {log.requests - before} recompile(s) "
                             "after a first call")
    return ms


def gated_delta_scan(log: CompileLog, shape=(1, 30, 8192, 96, 192),
                     checked=256, per_channel=False) -> None:
    """The gated delta rule's kernels (ops/gated_delta.py) alone at
    `olmohybrid-1chip`'s (batch, heads, tokens, key width, value width):
    its first `checked` tokens against the token-by-token recurrence, the
    Mosaic kernels the compiled forward and forward + backward hold (on the
    TPU: one, and two), then their times against the least time the
    benchmark's `gdn_scan_roofline` counts (the family's `rule_work`). With
    `per_channel` the form with a decay a key channel (`kda_scan`), which
    also says what its decayed products cost (`channel_gram_work`) and what
    g's running sums inside its kernels do (`running_sum_work`); both say
    what the solve costs (`solve_work`)."""
    batch, heads, seq, dk, dv = shape
    tag = "delta rule, a decay a channel" if per_channel \
        else "gated delta rule"
    family = kimi_linear if per_channel else olmo_hybrid
    wide = (dk,) if per_channel else ()
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    bf16 = jnp.bfloat16

    def unit(x):
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                 + 1e-6)

    q = (unit(jax.random.normal(ks[0], (batch, heads, seq, dk)))
         * dk ** -0.5).astype(bf16)
    k = unit(jax.random.normal(ks[1], (batch, heads, seq, dk))).astype(bf16)
    v = jax.random.normal(ks[2], (batch, heads, seq, dv), bf16)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[3], (batch, heads, seq)))
    # decays as the model's initialisation draws them: A ~ U(0, 16) a head,
    # a step of 0.001 to 0.1 a token
    rate = jax.random.uniform(ks[4], (1, heads, 1) + (1,) * len(wide),
                              minval=1e-3, maxval=16.0)
    g = -rate * jnp.exp(jax.random.uniform(
        ks[5], (batch, heads, seq) + wide, minval=np.log(1e-3),
        maxval=np.log(0.1)))
    args = (q, k, v, g, beta)
    head = tuple(x[:, :, :checked] for x in args)
    got = np.asarray(jax.jit(gd.gated_delta_rule)(*head).astype(jnp.float32))
    want = np.asarray(jax.jit(gd.recurrent_gated_delta_rule)(
        *(x.astype(jnp.float32) for x in head)))
    if not np.all(np.isfinite(got)):
        raise AssertionError(f"{tag}: non-finite output")
    err = _rms_off(got, want)
    if err > BF16_RTOL:
        raise AssertionError(
            f"the chunked {tag} is {err:.3g} of its rms from "
            f"the recurrence over {checked} tokens (tolerance "
            f"{BF16_RTOL:.3g})")
    cot = jax.random.normal(ks[0], v.shape, bf16)

    def both(*args):
        out, vjp = jax.vjp(gd.gated_delta_rule, *args)
        return (out,) + vjp(cot)

    runs = {"forward": (jax.jit(gd.gated_delta_rule).lower(*args).compile(),
                        args),
            "forward + backward": (jax.jit(both).lower(*args).compile(),
                                   args)}
    kernels = _counted(runs, {"forward": 1, "forward + backward": 2}, tag)
    ms = _best_ms(runs, repeats=5)
    shares = ""
    if on_tpu():   # the shares are of the benchmark's table of peaks
        p = peaks.for_kind(jax.devices()[0].device_kind)
        fwd, bwd = (gdn_scan_roofline.least_seconds((1, *work), p)[0] * 1e3
                    for work in family.rule_work(batch * seq * heads, dk,
                                                 dv))
        shares = (f"; least time forward {fwd:.3f} ms "
                  f"({100 * fwd / ms['forward']:.2f}% of it), forward + "
                  f"backward {fwd + bwd:.3f} ms "
                  f"({100 * (fwd + bwd) / ms['forward + backward']:.2f}%)")
    a_step = gd.heads_a_step(heads, dk, dv, per_channel=per_channel)
    halving = ""
    if per_channel:    # the counter of a form that engages in every chunk
        work = gd.channel_gram_work(gd.CHUNK, dk)
        halving = (f"; its decayed products by {work['levels']} levels of a "
                   "halving, forward / backward a chunk and head: "
                   + ", ".join(f"{work[name][0]} / {work[name][1]} "
                               + name.replace("_", " ") for name in (
                                   "products", "exp_registers",
                                   "lane_reductions", "lane_broadcasts")))
        sums = gd.running_sum_work(gd.CHUNK, dk)
        halving += ("; g's running sums inside the kernels, "
                    f"{sums['steps'][0]} / {sums['steps'][1]} doubling steps, "
                    f"{sums['rolled_registers'][0]} / "
                    f"{sums['rolled_registers'][1]} rolled registers, "
                    f"{sums['products'][0]} / {sums['products'][1]} products")
    solve = gd.solve_work(gd.CHUNK)   # engages in every chunk, both forms
    say(f"[{tag}] {batch} x {seq} tokens x {heads} heads, "
        f"{dk} | {dv}, bf16: chunk {gd.CHUNK}, "
        f"{gd.chunks_of(seq)} chunks a sequence, the chunked form's "
        "multiply-adds "
        f"{gd.chunked_over_recurrent_macs(dk, dv):.2f} x the recurrent "
        f"form's{halving}; the solve by {solve['panels']} panels, a chunk "
        f"and head: {solve['products']} exact products "
        f"({solve['bf16_passes']} bf16 passes), {solve['lane_broadcasts']} "
        f"lane broadcasts, {solve['steps']} steps; {a_step} heads a grid step "
        f"({a_step * gd.step_bytes(dk, dv, per_channel=per_channel) / 2 ** 20:.2f} "
        "MiB of VMEM asked "
        "for its blocks and state); interpret="
        f"{_pallas.interpret()}, tpu_custom_call in the compiled "
        + ", ".join(f"{name} {n}" for name, n in kernels.items())
        + f"; the first {checked} tokens are {err:.2e} of their rms "
        "from the token-by-token recurrence; alone (information only), ms "
        "an execution: "
        + ", ".join(f"{name} {t:.3f}" for name, t in ms.items()) + shares)


def kda_scan(log: CompileLog, shape=(1, 32, 16384, 128, 128),
             checked=256) -> None:
    """`gated_delta_scan` with a decay per key channel, at
    `kimilinear-1chip`'s shape: the kernels of Kimi Delta Attention's rule
    against the recurrence, and their times against the least time
    `kda_scan_roofline` counts."""
    gated_delta_scan(log, shape, checked, per_channel=True)


def causal_conv_pass(log: CompileLog, shape=(1, 30, 8192),
                     widths=((96, 96 ** -0.5), (192, None))) -> None:
    """The convolution's kernels (ops/causal_conv.py) alone at
    `olmohybrid-1chip`'s (batch, heads, tokens) and (width, L2 scale) of its
    queries and of its values: y, du and dw against the `jnp` form, the
    Mosaic kernels the compiled forward and forward + backward hold (on the
    TPU: one, and two), no compile request after a first call, then their
    times beside the bytes they must move over the HBM rate. The arrays are
    laid out tokens-major, width-minor, as the kernels and the rule take
    them, so the times hold no copy of the phase's own."""
    from jax.experimental.layout import Format, Layout
    batch, heads, seq = shape
    rows_major = Format(Layout(major_to_minor=(0, 1, 2, 3)),
                        jax.sharding.SingleDeviceSharding(jax.devices()[0]))
    told = []
    for width, l2_scale in widths:
        ks = jax.random.split(jax.random.PRNGKey(width), 3)
        u, cot = (jax.device_put(jax.random.normal(
            k, (batch, heads, seq, width), jnp.bfloat16), rows_major)
            for k in ks[:2])
        w = (0.5 * jax.random.normal(ks[2], (heads, width, 4))).astype(
            jnp.bfloat16)

        def both(fn, u, w, cot):
            out, vjp = jax.vjp(lambda u, w: fn(u, w, l2_scale=l2_scale), u, w)
            return (out,) + vjp(cot.astype(out.dtype))

        f32 = [x.astype(jnp.float32) for x in (u, w, cot)]
        want = jax.jit(functools.partial(
            both, cc.reference_causal_conv_silu))(*f32)
        runs = {"forward": (jax.jit(
            lambda u, w: cc.causal_conv_silu(u, w, l2_scale=l2_scale),
            out_shardings=rows_major).lower(u, w).compile(), (u, w)),
            "forward + backward": (jax.jit(
                functools.partial(both, cc.causal_conv_silu),
                out_shardings=(rows_major, rows_major, None)).lower(
                    u, w, cot).compile(), (u, w, cot))}
        got = runs["forward + backward"][0](u, w, cot)
        errs = {}
        for name, g, r in zip(("y", "du", "dw"), got, want):
            errs[name] = _rms_off(g, r)
            if not errs[name] <= BF16_RTOL:       # a NaN fails too
                raise AssertionError(
                    f"causal conv, width {width}: {name} is {errs[name]:.3g} "
                    f"of its rms from the jnp form (tolerance "
                    f"{BF16_RTOL:.3g})")
        kernels = _counted(runs, {"forward": 1, "forward + backward": 2},
                           "causal conv")
        ms = _timed_without_recompiles(log, runs, "causal conv", 10)
        least = ""
        if on_tpu():   # a share of the benchmark's table of peaks
            rate = peaks.for_kind(jax.devices()[0].device_kind).hbm_bytes_per_s
            fwd, bwd = (1e3 * b * batch * heads * seq / rate
                        for b in cc.least_bytes(width))
            least = (f"; its bytes at the HBM rate forward {fwd:.3f} ms "
                     f"({100 * fwd / ms['forward']:.1f}% of it), forward + "
                     f"backward {fwd + bwd:.3f} ms "
                     f"({100 * (fwd + bwd) / ms['forward + backward']:.1f}%)")
        tile = cc.tile_of(seq)
        told.append(
            f"width {width}" + (" normed" if l2_scale else "")
            + f": tiles of {tile} tokens, "
            f"{cc.heads_a_step(heads, width, tile)} heads a grid step, "
            "tpu_custom_call in the compiled "
            + ", ".join(f"{name} {n}" for name, n in kernels.items())
            + ", from the jnp form "
            + " ".join(f"{name} {e:.2e}" for name, e in errs.items())
            + "; alone (information only), ms an execution: "
            + ", ".join(f"{name} {t:.3f}" for name, t in ms.items()) + least)
    say(f"[causal conv] {batch} x {seq} tokens x {heads} heads, bf16, "
        f"interpret={_pallas.interpret()}, 0 recompiles after a first call; "
        + "; ".join(told))


def row_sum_pass(log: CompileLog,
                 shapes=((16384, 2560, 6, 64, 16), (8192, 2048, 6, 64, 8),
                         (8192, 2048, 8, 64, 64))) -> None:
    """The expert layer's sum of rows (ops/row_gather.py) alone at the three
    expert cells' (tokens, width, k, experts, experts held): `back` and
    `n_valid` of a seeded routing to k distinct experts a token, the row
    buffer `parallel/moe.py` sizes for it. The kernel against the `jnp` form,
    bit for bit; the Mosaic kernels the compiled sum holds (on the TPU two:
    the row form and the sum); no compile request after a first call; then
    both forms' times, the kernel's per row that counts, `moved_share`, and
    the bytes that must move over the HBM rate. Where every expert is held
    (`olmoe-1chip`) the program keeps the `jnp` sum: the kernel is timed at
    that shape with the whole buffer as the limit, which is what keeps it
    out."""
    told = []
    for n_tokens, width, k, n_experts, n_local in shapes:
        rng = np.random.default_rng(0)
        experts = np.argsort(rng.random((n_tokens, n_experts)),
                             axis=1)[:, :k].reshape(-1)
        key = np.where(experts < n_local, experts, n_local)
        back = np.argsort(np.argsort(key, kind="stable")).astype(np.int32)
        room = held_rows(n_tokens * k, n_local, n_experts)
        n_valid = min(int((key < n_local).sum()), room)
        ys = jax.random.normal(jax.random.PRNGKey(width), (room, width),
                               jnp.bfloat16)
        operands = (ys, jnp.asarray(back), jnp.int32(n_valid))
        runs = {"kernel": jax.jit(
                    lambda ys, back, n: rg._kernel_sum(ys, back, k, n)),
                "jnp": jax.jit(
                    lambda ys, back, n: rg.reference_sum(ys, back, k, n))}
        runs = {name: (fn.lower(*operands).compile(), operands)
                for name, fn in runs.items()}
        got, want = (fn(*args) for fn, args in runs.values())
        if not bool(jnp.array_equal(got.astype(jnp.float32),
                                    want.astype(jnp.float32))):
            raise AssertionError(
                f"row sum, {n_tokens} tokens x {width}, k {k}: the kernel's "
                "sum is not the jnp form's, bit for bit")
        kernels = _counted(runs, {"kernel": 2, "jnp": 0}, "row sum")
        ms = _timed_without_recompiles(log, runs, "row sum", 10)
        least = ""
        if on_tpu():   # a share of the benchmark's table of peaks
            rate = peaks.for_kind(jax.devices()[0].device_kind).hbm_bytes_per_s
            bound = 1e3 * 2 * width * (n_valid + n_tokens) / rate
            least = (f"; the counted rows read and the tokens written at the "
                     f"HBM rate {bound:.3f} ms "
                     f"({100 * bound / ms['kernel']:.1f}% of the kernel's)")
        told.append(
            f"{n_tokens} tokens x {width}, k {k}, {n_local} of {n_experts} "
            f"experts held: {n_valid} of {n_tokens * k} entries count into "
            f"a buffer of {room} rows, moved_share "
            f"{rg.moved_share(back.tolist(), n_valid):.4f}, "
            f"tpu_custom_call in the compiled kernel {kernels['kernel']}, "
            "the same bits as the jnp form; alone (information only), ms an "
            f"execution: kernel {ms['kernel']:.3f} "
            f"({1e6 * ms['kernel'] / max(n_valid, 1):.1f} ns a counted row), "
            f"jnp {ms['jnp']:.3f}" + least)
    say(f"[row sum] bf16, interpret={_pallas.interpret()}, 0 recompiles "
        "after a first call; " + "; ".join(told))


def embed_grad_pass(log: CompileLog,
                    shapes=((16384, 2560, 37984), (8192, 3840, 12544))
                    ) -> None:
    """The embedding lookup's backward pass (ops/row_gather.py `lookup_rows`)
    alone at `smallthinker-1chip`'s and `olmohybrid-1chip`'s (tokens, width,
    rows of the table), ids drawn uniformly as the benchmark draws them: the
    table's gradient against the float32 scatter-add rounded once, bit for
    bit; the static counters of a mechanism that always engages, `scatters`
    (scatter instructions in the compiled backward: 0) and `slot_share`
    (slots of the sorted chunks that hold a sum over the tokens); no compile
    request after a first call; then its time beside the compiler's
    scatter-add of the same rows (plain indexing's backward) and the bytes
    that must move over the HBM rate."""
    told = []
    for n_tokens, width, n_rows in shapes:
        ids = jax.random.randint(jax.random.PRNGKey(n_rows), (1, n_tokens), 0,
                                 n_rows, jnp.int32)
        cot = jax.random.normal(jax.random.PRNGKey(width),
                                (1, n_tokens, width), jnp.bfloat16)
        table = jnp.zeros((n_rows, width), jnp.bfloat16)

        def grad_of(lookup, table, ids, cot):
            return jax.vjp(lambda t: lookup(t, ids)[0], table)[1](cot)[0]

        operands = (table, ids, cot)
        runs = {"lookup_rows": jax.jit(functools.partial(grad_of,
                                                         rg.lookup_rows)),
                "scatter-add": jax.jit(functools.partial(
                    grad_of, lambda t, i: (t[i], t)))}
        runs = {name: (fn.lower(*operands).compile(), operands)
                for name, fn in runs.items()}
        want = jax.jit(lambda ids, cot: jnp.zeros(
            (n_rows, width), jnp.float32).at[ids[0]].add(
                cot[0].astype(jnp.float32)).astype(jnp.bfloat16))(ids, cot)
        got = runs["lookup_rows"][0](*operands)
        if not bool(jnp.array_equal(got, want)):
            raise AssertionError(
                f"embed grad, {n_tokens} tokens x {width} into {n_rows}: not "
                "the float32 scatter-add rounded once, bit for bit")
        scatters = {name: fn.as_text().count(" scatter(")
                    for name, (fn, _) in runs.items()}
        if scatters["lookup_rows"] or not scatters["scatter-add"]:
            raise AssertionError(
                f"embed grad: scatter instructions in the compiled backward "
                f"{scatters}, expected none in lookup_rows' and one in plain "
                "indexing's")
        _counted(runs, {"lookup_rows": 0, "scatter-add": 0}, "embed grad")
        ms = _timed_without_recompiles(log, runs, "embed grad", 10)
        least = ""
        if on_tpu():   # a share of the benchmark's table of peaks
            rate = peaks.for_kind(jax.devices()[0].device_kind).hbm_bytes_per_s
            bound = 1e3 * 2 * width * (n_tokens + n_rows) / rate
            least = (f"; the cotangent read and the gradient written at the "
                     f"HBM rate {bound:.3f} ms "
                     f"({100 * bound / ms['lookup_rows']:.1f}% of it)")
        told.append(
            f"{n_tokens} tokens x {width} into {n_rows} rows: chunks of "
            f"{rg.LOOKUP_CHUNK}, scatters {scatters['lookup_rows']} (plain "
            f"indexing's backward: {scatters['scatter-add']}), slot_share "
            f"{rg.slot_share(np.asarray(ids[0]).tolist()):.4f}, the float32 "
            "scatter-add rounded once, bit for bit; alone (information "
            f"only), ms an execution: lookup_rows {ms['lookup_rows']:.3f} "
            f"({1e6 * ms['lookup_rows'] / n_tokens:.1f} ns a token), "
            f"scatter-add {ms['scatter-add']:.3f} "
            f"({1e6 * ms['scatter-add'] / n_tokens:.1f})" + least)
    say(f"[embed grad] bf16, 0 recompiles after a first call; "
        + "; ".join(told))


def _out_and_grads(fn, cot, *args):
    """fn's output and, for the cotangent `cot`, every argument's gradient."""
    out, vjp = jax.vjp(fn, *args)
    return (out,) + vjp(cot.astype(out.dtype))


def selective_scan_pass(log: CompileLog, shape=(1, 8192, 5120, 16),
                        checked=512) -> None:
    """The selective scan's kernels (ops/selective_scan.py) alone at
    `phi4flash-1chip`'s (batch, tokens, channels, states): the output and
    the six gradients over the first `checked` tokens against the
    token-by-token `jnp` form, the Mosaic kernels the compiled forward and
    forward + backward hold (on the TPU: one, and two), no compile request
    after a first call, then their times against the least time the
    benchmark's `ssm_scan_roofline` counts (the family's `scan_work`)."""
    batch, seq, channels, states = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    bf16 = jnp.bfloat16
    c, cot = (jax.random.normal(k, (batch, seq, channels), bf16)
              for k in ks[:2])
    # steps and rates as the model's initialisation draws them
    delta = jnp.exp(jax.random.uniform(
        ks[2], (batch, seq, channels), minval=np.log(1e-3),
        maxval=np.log(0.1)))
    rates = -jnp.broadcast_to(jnp.arange(1, states + 1, dtype=jnp.float32),
                              (channels, states))
    b_in, c_out = (jax.random.normal(k, (batch, seq, states), bf16)
                   for k in ks[3:])
    args = (c, delta, rates, b_in, c_out, jnp.ones((channels,), jnp.float32))

    def head(x):
        return x[:, :checked] if x.ndim == 3 else x

    short = tuple(head(x) for x in args)
    got = jax.jit(functools.partial(_out_and_grads, ss.selective_scan))(
        head(cot), *short)
    want = jax.jit(functools.partial(_out_and_grads, ss.reference_selective_scan))(
        head(cot), *(x.astype(jnp.float32) for x in short))
    errs = {}
    for name, g, w in zip(("y", "dc", "ddelta", "dA", "dB", "dC", "dD"),
                          got, want):
        errs[name] = _rms_off(g, w)
        if not errs[name] <= BF16_RTOL:           # a NaN fails too
            raise AssertionError(
                f"selective scan: {name} is {errs[name]:.3g} of its rms "
                f"from the token-by-token form over {checked} tokens "
                f"(tolerance {BF16_RTOL:.3g})")
    runs = {"forward": (jax.jit(ss.selective_scan).lower(*args).compile(),
                        args),
            "forward + backward": (jax.jit(functools.partial(
                _out_and_grads, ss.selective_scan)).lower(
                    cot, *args).compile(),
                (cot, *args))}
    kernels = _counted(runs, {"forward": 1, "forward + backward": 2},
                       "selective scan")
    ms = _timed_without_recompiles(log, runs, "selective scan", 5)
    shares = ""
    if on_tpu():   # the shares are of the benchmark's table of peaks
        p = peaks.for_kind(jax.devices()[0].device_kind)
        fwd, bwd = (gdn_scan_roofline.least_seconds((1, *work), p)[0] * 1e3
                    for work in phi4_flash.scan_work(batch * seq, channels,
                                                     states))
        shares = (f"; least time forward {fwd:.3f} ms "
                  f"({100 * fwd / ms['forward']:.2f}% of it), forward + "
                  f"backward {fwd + bwd:.3f} ms "
                  f"({100 * (fwd + bwd) / ms['forward + backward']:.2f}%)")
    chunk = ss.chunk_of(seq)
    say(f"[selective scan] {batch} x {seq} tokens x {channels} channels x "
        f"{states} states, bf16 (delta float32): tiles of {chunk} tokens, "
        f"{seq // chunk} a sequence; interpret={_pallas.interpret()}, 0 "
        "recompiles after a first call, tpu_custom_call in the compiled "
        + ", ".join(f"{name} {n}" for name, n in kernels.items())
        + f"; the first {checked} tokens from the token-by-token form "
        + " ".join(f"{name} {e:.2e}" for name, e in errs.items())
        + "; alone (information only), ms an execution: "
        + ", ".join(f"{name} {t:.3f}" for name, t in ms.items()) + shares)


def ssd_scan_pass(log: CompileLog, shape=(1, 4096, 64, 64, 128),
                  checked=512) -> None:
    """The state-space dual scan's kernels (ops/ssd_scan.py) alone at
    `granite4h-1chip`'s (batch, tokens, heads held, head width, states): the
    output and the six gradients over the first `checked` tokens against the
    token-by-token `jnp` form, the Mosaic kernels the compiled forward and
    forward + backward hold (on the TPU: one, and two), no compile request
    after a first call, then their times against the least time the
    benchmark's `ssd_scan_roofline` counts (the family's `scan_work`)."""
    batch, seq, heads, width, states = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    bf16 = jnp.bfloat16
    x, cot = (jax.random.normal(k, (batch, seq, heads * width), bf16)
              for k in ks[:2])
    # steps and rates as the model's initialisation draws them
    dt = jnp.exp(jax.random.uniform(ks[2], (batch, seq, heads),
                                    minval=np.log(1e-3), maxval=np.log(0.1)))
    a_log = jnp.log(jax.random.uniform(ks[3], (heads,), minval=1.0,
                                       maxval=16.0))
    b_in, c_out = (jax.random.normal(k, (batch, seq, states), bf16)
                   for k in ks[4:])
    args = (x, dt, a_log, b_in, c_out, jnp.ones((heads,), jnp.float32))

    def head(v):
        return v[:, :checked] if v.ndim == 3 else v

    short = tuple(head(v) for v in args)
    got = jax.jit(functools.partial(_out_and_grads, ssd.ssd_scan))(head(cot), *short)
    want = jax.jit(functools.partial(_out_and_grads, ssd.recurrent_ssd_scan))(
        head(cot), *(v.astype(jnp.float32) for v in short))
    errs = {}
    for name, g, w in zip(("y", "dx", "ddt", "da_log", "dB", "dC", "dD"),
                          got, want):
        errs[name] = _rms_off(g, w)
        if not errs[name] <= BF16_RTOL:           # a NaN fails too
            raise AssertionError(
                f"ssd scan: {name} is {errs[name]:.3g} of its rms from the "
                f"token-by-token form over {checked} tokens (tolerance "
                f"{BF16_RTOL:.3g})")
    runs = {"forward": (jax.jit(ssd.ssd_scan).lower(*args).compile(), args),
            "forward + backward": (jax.jit(functools.partial(
                _out_and_grads, ssd.ssd_scan)).lower(cot, *args).compile(),
                (cot, *args))}
    kernels = _counted(runs, {"forward": 1, "forward + backward": 2},
                       "ssd scan")
    ms = _timed_without_recompiles(log, runs, "ssd scan", 5)
    shares = ""
    if on_tpu():   # the shares are of the benchmark's table of peaks
        p = peaks.for_kind(jax.devices()[0].device_kind)
        config = {"mamba_n_heads": heads, "mamba_d_head": width,
                  "mamba_d_state": states, "mamba_chunk_size": ssd.CHUNK}
        fwd, bwd = (gdn_scan_roofline.least_seconds((1, *work), p)[0] * 1e3
                    for work in granite_hybrid.scan_work(batch * seq,
                                                         config))
        shares = (f"; least time forward {fwd:.3f} ms "
                  f"({100 * fwd / ms['forward']:.2f}% of it), forward + "
                  f"backward {fwd + bwd:.3f} ms "
                  f"({100 * (fwd + bwd) / ms['forward + backward']:.2f}%)")
    chunk = min(ssd.CHUNK, seq)
    say(f"[ssd scan] {batch} x {seq} tokens x {heads} heads of {width} x "
        f"{states} states, bf16 (dt float32): chunk {chunk}, "
        f"{ssd.chunks_of(seq, chunk)} chunks a sequence, "
        f"{ssd.heads_a_step(heads, width)} heads a grid step, the chunked "
        "form's multiply-adds "
        f"{ssd.chunked_over_recurrent_macs(heads, width, states, chunk):.2f}"
        f" x the recurrent form's; interpret={_pallas.interpret()}, 0 "
        "recompiles after a first call, tpu_custom_call in the compiled "
        + ", ".join(f"{name} {n}" for name, n in kernels.items())
        + f"; the first {checked} tokens from the token-by-token form "
        + " ".join(f"{name} {e:.2e}" for name, e in errs.items())
        + "; alone (information only), ms an execution: "
        + ", ".join(f"{name} {t:.3f}" for name, t in ms.items()) + shares)


def windowed_grouped_flash(log: CompileLog, shape=(1, 20, 10, 8192, 64, 128),
                           window=512, checked=2048) -> None:
    """One softmax of `phi4flash-1chip`'s differential attention alone:
    (batch, query heads, K/V heads, tokens, keys' width, values' width) with
    and without the window. Output and the three gradients over the first
    `checked` tokens against the mask written out, the Mosaic kernels the
    compiled programs hold (forward one, with the gradients two: the
    backward pass is one kernel, `backward_products`), no
    compile request after a first call, and the times of the windowed call
    beside the full one's."""
    batch, heads, kv_heads, seq, dk, dv = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    bf16 = jnp.bfloat16
    q = jax.random.normal(ks[0], (batch, heads, seq, dk), bf16)
    k = jax.random.normal(ks[1], (batch, kv_heads, seq, dk), bf16)
    v, cot = (jax.random.normal(kk, (batch, n, seq, dv), bf16)
              for kk, n in ((ks[2], kv_heads), (ks[3], heads)))

    def both(attn, banded, cot, q, k, v):
        out, vjp = jax.vjp(lambda q, k, v: attn(q, k, v, banded), q, k, v)
        return (out,) + vjp(cot.astype(out.dtype))

    def flash(q, k, v, banded):
        return flash_attention(q, k, v, causal=True, window=banded)

    def plain(q, k, v, banded):
        return masked_attention_reference(q, k, v, True, None, banded)

    told = []
    for banded in (window, None):
        short = tuple(x[:, :, :checked] for x in (cot, q, k, v))
        got = jax.jit(functools.partial(both, flash, banded))(*short)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(functools.partial(both, plain, banded))(
                *(x.astype(jnp.float32) for x in short))
        errs = {}
        for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
            errs[name] = _rms_off(g, w)
            if g.shape != w.shape or not errs[name] <= BF16_RTOL:
                raise AssertionError(
                    f"flash, window {banded}, {heads} heads over "
                    f"{kv_heads}: {name} is {errs[name]:.3g} of its rms "
                    f"from the mask written out (tolerance {BF16_RTOL:.3g})")
        runs = {"forward": (jax.jit(functools.partial(
            flash, banded=banded)).lower(q, k, v).compile(), (q, k, v)),
            "forward + backward": (jax.jit(functools.partial(
                both, flash, banded)).lower(cot, q, k, v).compile(),
                (cot, q, k, v))}
        fused = backward_products(seq, dk, dv, heads // kv_heads)[0] == 5
        kernels = _counted(
            runs, {"forward": 1, "forward + backward": 2 if fused else 3},
            "grouped flash attention")
        ms = _timed_without_recompiles(log, runs, "grouped flash", 10)
        share = window_tile_share(seq, banded) if banded and banded < seq \
            else causal_tile_share(seq)
        told.append(
            (f"window {banded}: window_tile_share {share:.4f}" if banded else
             f"no window: causal_tile_share {share:.4f}")
            + f", grid_step_share {grid_step_share(seq, banded):.4f}"
            + f", row_strip_share {row_strip_share(seq, banded):.4f}"
            + ", tpu_custom_call in the compiled "
            + ", ".join(f"{name} {n}" for name, n in kernels.items())
            + f", the first {checked} tokens from the mask written out "
            + " ".join(f"{name} {e:.2e}" for name, e in errs.items())
            + "; alone (information only), ms an execution: "
            + ", ".join(f"{name} {t:.3f}" for name, t in ms.items()))
    say(f"[grouped flash] {batch} x {seq} tokens, {heads} query heads over "
        f"{kv_heads} K/V heads, {dk} | {dv}, bf16, "
        f"interpret={_pallas.interpret()}, 0 recompiles after a first call; "
        + "; ".join(told))


def lm_steps(log: CompileLog, name: str, cfg, batch: int, seq: int,
             steps: int, spec: MeshSpec, devices) -> dict:
    """`steps` train steps of the LM over `spec` on `devices`, through
    tfm.init / shard_params / init_opt_state / build_train_step, on a
    fixed batch placed the way the step shards it. Returns losses, step
    seconds, the compiled program's text, and the devices params and
    batch sit on."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = build_mesh(spec, devices=devices)
    tfm.validate_cfg_for_mesh(cfg, mesh)
    params = tfm.shard_params(tfm.init(jax.random.PRNGKey(0), cfg), cfg,
                              mesh)
    opt = optax.adam(1e-3)
    opt_state = tfm.init_opt_state(opt, params, mesh)
    step = tfm.build_train_step(cfg, mesh, opt)
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                           cfg.vocab),
        NamedSharding(mesh, P(("dp", "ep"), "sp")))
    targets = jnp.roll(tokens, -1, axis=1)
    placed = {"params": {s.device for leaf in jax.tree_util.tree_leaves(
                  params) for s in leaf.addressable_shards},
              "batch": {s.device for s in tokens.addressable_shards}}
    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, tokens, targets).compile()
    compile_s = time.perf_counter() - t0
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    say(f"[{name}] compiled in {compile_s:.1f} s (persistent cache so far: "
        f"{log.hits} hit(s), {log.misses} miss(es)); program needs "
        f"{(mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 2**30:.2f}"
        f" GiB per device; {text.count('tpu_custom_call')} tpu_custom_call")
    state = [params, opt_state]

    def one_step():
        state[0], state[1], loss = step(state[0], state[1], tokens, targets)
        return jax.block_until_ready(loss)

    losses, secs = timed_steps(name, one_step, steps, log)
    return {"losses": losses, "secs": secs, "text": text, "placed": placed}


def flagship_lm(log: CompileLog, cfg=FLAGSHIP, batch: int = 12,
                seq: int = 1024, steps: int = 6) -> None:
    """The flagship LM at full width on one chip: compile, >= 5 steps,
    finite falling loss on a fixed batch, no recompile after step 1."""
    name = (f"lm L{cfg.n_layers} D{cfg.d_model} F{cfg.d_ff} "
            f"H{cfg.n_heads} S{seq} B{batch} V{cfg.vocab}")
    run = lm_steps(log, name, cfg, batch, seq, steps, MeshSpec(),
                   jax.devices()[:1])
    if on_tpu() and "tpu_custom_call" not in run["text"]:
        raise AssertionError("the LM step holds no Mosaic custom call: "
                             "flash attention was not compiled into it")
    med = statistics.median(run["secs"][1:])
    information(name, f"{batch * seq / med:,.0f} tokens/s")


def resnet50_eager(log: CompileLog, batch: int = 128, image: int = 224,
                   depth: int = 50, steps: int = 6) -> None:
    """ResNet-50 B=128 bf16 (the paper's configuration) on the eager path:
    a jitted value_and_grad, then `hvd.DistributedOptimizer.step`."""
    name = f"resnet{depth} B{batch} {image}px bf16 eager"
    params, stats = resnet.init(jax.random.PRNGKey(0), depth=depth,
                                dtype=jnp.bfloat16)
    # the Horovod idiom: rank 0's model state (BN statistics included)
    # goes to every rank — which also commits it to the device, so that
    # step 2 sees the same input placement as step 1
    params, stats = hvd.broadcast_parameters((params, stats), root_rank=0)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, image, image, 3)),
                    jnp.bfloat16)
    y = jnp.asarray(rng.integers(0, 1000, (batch,)), jnp.int32)
    opt = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9))
    state = [params, stats, opt.init(params)]
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, s, x, y: resnet.loss_fn(p, s, (x, y), depth=depth,
                                          train=True), has_aux=True))

    def one_step():
        (loss, state[1]), grads = grad_fn(state[0], state[1], x, y)
        state[0], state[2] = opt.step(grads, state[0], state[2])
        jax.block_until_ready(state[0])
        return loss

    _, secs = timed_steps(name, one_step, steps, log)
    if getattr(opt, "_apply_eager", False):
        raise AssertionError("DistributedOptimizer fell back to the "
                             "un-jitted optimizer apply")
    information(name, f"{batch / statistics.median(secs[1:]):,.0f} images/s")


# ---------------------------------------------------------- four-chip phases

def launched_worker(platform: str) -> None:
    """Body of one `launch -np N` worker: owns exactly one device of
    `platform`, agrees with the other ranks on an allreduce and on a few
    `DistributedOptimizer` steps over different data."""
    hvd.init()
    k, r = hvd.size(), hvd.rank()
    local = jax.local_devices()
    if k != int(os.environ["HOROVOD_SIZE"]) or len(local) != 1 \
            or local[0].platform != platform:
        raise AssertionError(
            f"rank {r}: size {k}, local devices {local} — expected "
            f"{os.environ['HOROVOD_SIZE']} ranks with one {platform} "
            "device each")
    total = np.asarray(hvd.allreduce(np.full(8, r + 1.0, np.float32),
                                     op=hvd.Sum))
    np.testing.assert_allclose(total, k * (k + 1) / 2)
    # rank-addressed collectives: the launcher's rank r is row r / root r
    mine = np.full(1, r, np.int32)
    np.testing.assert_array_equal(np.asarray(hvd.allgather(mine)),
                                  np.arange(k))
    np.testing.assert_array_equal(
        np.asarray(hvd.broadcast(mine, root_rank=k - 1)), [k - 1])
    w = {"w": jax.random.normal(jax.random.PRNGKey(r), (64, 64)),
         "b": jnp.zeros((64,))}
    w = hvd.broadcast_parameters(w, root_rank=0)
    data = jax.random.normal(jax.random.PRNGKey(100 + r), (32, 64))
    opt = hvd.DistributedOptimizer(optax.sgd(0.1))
    opt_state = opt.init(w)
    grad_fn = jax.jit(jax.grad(
        lambda p: jnp.mean((jnp.tanh(data @ p["w"]) + p["b"] - 1.0) ** 2)))
    for _ in range(3):
        w, opt_state = opt.step(grad_fn(w), w, opt_state)
    sums = np.asarray(hvd.allgather(jnp.sum(w["w"]).reshape(1)))
    if sums.shape != (k,) or not np.all(sums == sums[0]):
        raise AssertionError(f"rank {r}: parameters diverged across "
                             f"ranks after 3 steps: {sums}")
    say(f"SMOKE_WORKER_OK rank={r}/{k} device={local[0]} "
        f"kind={local[0].device_kind!r} param_sum={sums[0]:.6f}")
    hvd.shutdown()


def launcher_one_process_per_chip(log: CompileLog, np_: int = 4,
                                  platform: str = "tpu") -> None:
    """`python -m horovod_tpu.runner.launch -np N` — the README's launch.
    Runs while this process has not touched a JAX backend: a chip belongs
    to one process at a time."""
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        raise AssertionError("the parent already holds a JAX backend; "
                             "launched workers could not open their chips")
    say(f"[native control plane] {native.status()}")  # built once, here
    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
           "-np", str(np_), "-H", f"localhost:{np_}", sys.executable, "-c",
           f"import chip_smoke; chip_smoke.launched_worker({platform!r})"]
    proc = subprocess.Popen(cmd, cwd=REPO, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            start_new_session=True)
    try:
        out, err = proc.communicate(timeout=300)
    except subprocess.TimeoutExpired:
        proc.terminate()  # the launcher stops its workers on the way out
        try:
            out, err = proc.communicate(timeout=30)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            out, err = proc.communicate()
        raise AssertionError(f"launch -np {np_} hung\nstdout:\n"
                             f"{out[-4000:]}\nstderr:\n{err[-4000:]}")
    ok = [ln.strip() for ln in out.splitlines() if "SMOKE_WORKER_OK" in ln]
    for ln in ok:
        say(f"[launch -np {np_}] {ln}")
    if proc.returncode != 0 or len(ok) != np_:
        raise AssertionError(
            f"launch -np {np_} exited {proc.returncode} with {len(ok)} "
            f"worker(s) ok\nstdout:\n{out[-4000:]}\nstderr:\n{err[-4000:]}")
    devices = {ln.split("device=")[1].split(" kind=")[0] for ln in ok}
    if platform == "tpu" and len(devices) != np_:
        raise AssertionError(f"workers did not hold {np_} distinct chips: "
                             f"{sorted(devices)}")
    say(f"[launch -np {np_}] {np_} workers, one {platform} device each, "
        "agree on an allreduce and on 3 DistributedOptimizer steps")


def single_controller_lm(log: CompileLog, cfg=FLAGSHIP, batch: int = 12,
                         seq: int = 1024, steps: int = 4,
                         specs=(MeshSpec(dp=4), MeshSpec(dp=2, tp=2))
                         ) -> None:
    """One process over all chips: the LM over each mesh in `specs`,
    against the one-device run of the same global batch and seed."""
    devices = topology.state().devices
    tag = f"L{cfg.n_layers} D{cfg.d_model} S{seq} B{batch}"
    ref = lm_steps(log, f"lm {tag} one device", cfg, batch, seq, steps,
                   MeshSpec(), devices[:1])["losses"]
    for spec in specs:
        name = f"lm {tag} {spec.describe()}"
        run = lm_steps(log, name, cfg, batch, seq, steps, spec,
                       devices[:spec.total])
        np.testing.assert_allclose(
            run["losses"], ref, rtol=BF16_RTOL,
            err_msg=f"{name}: losses leave the one-device run's")
        for what, devs in run["placed"].items():
            if len(devs) != spec.total:
                raise AssertionError(
                    f"{name}: the {what} sit on {len(devs)} device(s), "
                    f"not {spec.total}")
        if "all-reduce" not in run["text"]:
            raise AssertionError(f"{name}: no all-reduce in the compiled "
                                 "program")
        say(f"[{name}] losses match the one-device run within "
            f"{BF16_RTOL:.3g} at each step; params and batch on "
            f"{spec.total} distinct devices; all-reduce in the compiled "
            "program")


# ---------------------------------------------------------------------- main

#: chips -> (phases run before this process touches JAX, phases run after
#: `hvd.init()`). With --chips 4 only what exists across chips runs, and what
#: it is compared with.
PHASES = {
    1: ((), (eager_api, flash_kernel, grouped_kernel, gated_delta_scan,
             kda_scan, causal_conv_pass, selective_scan_pass, ssd_scan_pass,
             windowed_grouped_flash, row_sum_pass, embed_grad_pass,
             flagship_lm, resnet50_eager)),
    4: ((launcher_one_process_per_chip,), (single_controller_lm,)),
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=sorted(PHASES), default=1,
                    help="4: run only the cross-chip paths (default 1)")
    chips = ap.parse_args(argv).chips
    t0 = time.perf_counter()
    log = CompileLog()
    off_jax, on_device = PHASES[chips]
    for phase in off_jax:
        phase(log)
    preflight(chips)
    for phase in on_device:
        phase(log)
    dev = jax.devices()[0]
    say(f"[done] {time.perf_counter() - t0:.0f} s; {log.requests} compile "
        f"request(s), persistent cache {log.hits} hit(s) / {log.misses} "
        "miss(es)")
    say(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
