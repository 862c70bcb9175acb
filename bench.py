"""Headline benchmarks: ResNet-50 + transformer-LM synthetic training.

Mirrors the reference's synthetic benchmark recipe
(examples/pytorch/pytorch_synthetic_benchmark.py — random data, images/sec;
docs/benchmarks.rst:15-42) and extends it with the proof the reference never
gives: **MFU** (model FLOPs ÷ chip peak), a per-chip batch sweep, and a
fusion-threshold sweep on the eager grouped-allreduce path.

Both models run through the framework's own data-parallel train-step path
(gradients psum'd inside one compiled XLA program). Prints ONE JSON line:
{"metric", "value", "unit", "vs_baseline", "extra": {...}} — the headline
stays the ResNet-50 images/sec/chip for round-over-round comparability;
everything else rides in "extra".
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.core import topology
from horovod_tpu.models import resnet
from horovod_tpu.models import transformer as tfm
from horovod_tpu.optim.optimizer import reduce_gradients_in_jit
from horovod_tpu.parallel.mesh import MeshSpec, build_mesh
from horovod_tpu.profiler import flops as F
from horovod_tpu.profiler import perfscope as pscope

BASELINE_PER_CHIP = 1656.8 / 16  # images/sec/GPU, reference docs/benchmarks.rst:40-42


def peak_flops_per_chip():
    """Peak dense bf16 FLOP/s (profiler/flops.py owns the spec table;
    HOROVOD_BENCH_PEAK_TFLOPS overrides). None on unknown chip / CPU."""
    return F.peak_flops_per_chip()


def _hlo_lint_enabled():
    """HOROVOD_HLO_LINT gate, checked BEFORE any lowering happens —
    with the stamp disabled a section must not pay a trace+lower it
    would otherwise skip."""
    try:
        from horovod_tpu.analysis import hlo
        return hlo.lint_enabled()
    except Exception:
        return False


def _hlo_lint_lowered(lowered):
    """hvdhlo stamp for one section's already-lowered step program
    (docs/static_analysis.md): the compile-time perf lint rides the
    lowering the bench produces anyway. Returns {} when disabled
    (HOROVOD_HLO_LINT=0) or on any analysis failure — the lint is a
    diagnostic stamp here, never a bench-killer."""
    try:
        from horovod_tpu.analysis import hlo
        if not hlo.lint_enabled():
            return {}
        return hlo.lint_summary(lowered.as_text(), path="<lowered>")
    except Exception:
        return {}


def _memory_stamp(compiled):
    """Per-section `memory` stamp (docs/perf.md): the static per-device
    peak-HBM estimate from the section's already-compiled program
    (analysis/shard.py donation-aware liveness over the post-opt
    schedule) next to the live ``device.memory_stats()`` actuals, plus
    their ratio. scripts/perf_gate.py structurally requires this stamp
    and fails any section whose estimate exceeds the chip budget.
    Returns {} on any analysis failure — a diagnostic, never a
    bench-killer."""
    try:
        from horovod_tpu.analysis import shard
        est = shard.estimate_compiled_text(compiled.as_text())
    except Exception:
        return {}
    if est is None:
        return {}
    out = {
        "static_peak_device_bytes": est.peak_bytes,
        "static_peak_device_mb": round(est.peak_bytes / 2**20, 2),
        "args_mb": round(est.args_bytes / 2**20, 2),
        "donated_mb": round(est.donated_bytes / 2**20, 2),
        "model": "donation-aware liveness over the post-opt schedule "
                 "(analysis/shard.py)",
    }
    try:
        stats = jax.local_devices()[0].memory_stats() or {}
        measured = (stats.get("peak_bytes_in_use")
                    or stats.get("bytes_in_use"))
        if measured:
            out["measured_peak_device_bytes"] = int(measured)
            out["measured_peak_device_mb"] = round(measured / 2**20, 2)
            # >1: the estimate overshoots the device's observed peak
            # (safe side); <1: other live programs/arenas dominate.
            out["static_vs_measured_ratio"] = round(
                est.peak_bytes / measured, 3)
    except Exception:
        pass  # CPU devices expose no memory_stats
    try:
        from horovod_tpu.analysis.shard_rules import hbm_budget_bytes
    except Exception:
        return out
    # NOT exception-guarded: a malformed HOROVOD_HLO_LINT_HBM_BUDGET /
    # HOROVOD_BENCH_HBM_GB raises by design — swallowing it would
    # silently disarm the budget gate in exactly the runs that set it.
    budget = hbm_budget_bytes() or F.hbm_bytes_per_chip()
    if budget:
        out["hbm_budget_bytes"] = budget
        out["within_budget"] = est.peak_bytes <= budget
    return out


def _scan_timed(local_body, state, chain, reps, warmup=2,
                flops_out=None, profile_out=None, profile_steps=3,
                hlo_out=None, mem_out=None):
    """Time `chain` training steps chained inside ONE compiled program
    (lax.scan), returning seconds per step: after the warm-up calls,
    `reps` calls on the host clock, ending in `block_until_ready`. All
    arrays ride in the carry (donated), so nothing is copied per call.

    `flops_out` (dict): filled with the XLA cost-analysis FLOPs of the
    compiled program, per step (`program_flops_per_step`, per
    participating device — the SPMD module is per-device code). The
    program is compiled ONCE via AOT lower+compile and that same
    executable is what gets timed, so the cost analysis is free and
    describes exactly the program that ran (profiler/flops.py).
    HOROVOD_PERFSCOPE_XLA_FLOPS=0 skips it (hand-constant fallbacks
    take over, docs/perf.md).

    `profile_out` (dict): filled with a perfscope summary
    (`{"summary": ...}`) from `profile_steps` individually-synced extra
    calls — per-step wall percentiles plus the dispatch /
    device_compute phase split."""
    jbody = jax.jit(lambda s: lax.scan(
        lambda c, _: (local_body(c), ()), s, None, length=chain)[0],
        donate_argnums=(0,))  # alias carry in/out: no double-buffered params
    body = jbody
    lowered = None
    want_hlo = hlo_out is not None and _hlo_lint_enabled()
    if (flops_out is not None and F.xla_flops_enabled()) or want_hlo:
        try:
            lowered = jbody.lower(state)  # ONE lowering: lint + compile
        except Exception:
            lowered = None
    if lowered is not None and want_hlo:
        hlo_out.update(_hlo_lint_lowered(lowered))
    if lowered is not None and flops_out is not None \
            and F.xla_flops_enabled():
        compiled = None
        try:
            compiled = lowered.compile()
            total = F.compiled_cost_flops(compiled)
            if total:
                flops_out["program_flops_per_step"] = total / chain
                flops_out["source"] = "xla"
            body = compiled  # reuse: one compile for analysis AND timing
        except Exception:
            compiled = None
            body = jbody  # AOT path unavailable: timing still works
        if compiled is not None and mem_out is not None:
            # Free off the compile the cost analysis already paid for —
            # same executable that gets timed below. OUTSIDE the AOT
            # try: a malformed budget knob must raise loudly (its
            # design), not silently demote the section to the non-AOT
            # body after mfu_source="xla" was already recorded.
            mem_out.update(_memory_stamp(compiled))

    for _ in range(max(warmup, 1)):  # the first call compiles
        state = body(state)
    jax.block_until_ready(state)
    reps = max(reps, 1)
    t0 = time.perf_counter()
    for _ in range(reps):
        state = body(state)
    jax.block_until_ready(state)
    sec_per_step = (time.perf_counter() - t0) / (reps * chain)
    if profile_out is not None:
        ps = pscope.get()
        ps.reset()
        for _ in range(max(profile_steps, 2)):
            # weight=chain: one call is `chain` training steps — the
            # scope divides wall and phases back to per-step.
            with ps.step(weight=chain):
                state = body(state)
                with ps.phase("device_compute"):
                    jax.block_until_ready(state)
        s = ps.summary()
        if s:
            profile_out["summary"] = s
    return sec_per_step


def _perf_stamp(r, name, flops_info, prof, fallback_flops_per_step,
                hlo_info=None, mem_info=None):
    """Attach the section's StepProfile (docs/perf.md) to its result
    dict: per-step wall percentiles, the perfscope phase breakdown, and
    MFU with its source — "xla" when the FLOPs came from cost analysis
    of the program that actually ran, "fallback" when only the hand
    constants (profiler/flops.py) were available. `hlo_info` (the
    hvdhlo compile-time lint of the same lowered program,
    docs/static_analysis.md) rides along as `hlo_lint`.

    Convention note: the StepProfile compares XLA FLOPs against the
    "flops" (mul+add) fallback convention; the section's legacy `mfu`
    field keeps the historical MAC-based constants for round-over-round
    BENCH comparability (flops.py module docstring)."""
    if r is None:
        return r
    xla = flops_info.get("program_flops_per_step")
    flops_per_step, source = F.pick_flops(xla, fallback_flops_per_step)
    sp = {"name": name, "perfscope": pscope.SUMMARY_VERSION}
    summary = prof.get("summary") or {}
    sp.update(summary)
    sp["model_flops_per_step"] = flops_per_step
    sp["mfu_source"] = source
    if xla and fallback_flops_per_step:
        sp["xla_vs_fallback_flops_ratio"] = round(
            xla / fallback_flops_per_step, 3)
    peak = F.peak_flops_per_chip()
    wall = summary.get("wall") or {}
    mean = wall.get("mean_s")
    if peak and flops_per_step and mean:
        sp["peak_flops_per_chip"] = peak
        sp["mfu"] = round(flops_per_step / mean / peak, 4)
    r["perfscope"] = sp
    r["mfu_source"] = source
    if hlo_info:
        r["hlo_lint"] = hlo_info
    if mem_info:
        r["memory"] = mem_info
    if wall:
        r["step_time_percentiles_ms"] = {
            k: round(wall[f"{k}_s"] * 1e3, 2)
            for k in ("mean", "p50", "p95", "max")}
    r["hvdwatch"] = _watch_stamp()
    return r


_watch_last_counts = {}


def _watch_stamp():
    """Per-section hvdwatch block (observability/watch.py): run one
    detection pass over the samples the section just produced, then
    stamp how many anomalies this section added. Clean runs stamp zero
    everywhere — scripts/perf_gate.py asserts exactly that, so a bench
    whose own workloads trip a detector fails CI instead of silently
    publishing a number measured during an anomaly."""
    global _watch_last_counts
    counts = {}
    try:
        from horovod_tpu.observability import watch
        watch.get().tick()
        counts = watch.get().counts()
    except Exception:
        pass
    prev, _watch_last_counts = _watch_last_counts, dict(counts)
    new = {k: v - prev.get(k, 0) for k, v in counts.items()
           if v - prev.get(k, 0) > 0}
    return {"anomalies_total": sum(new.values()),
            "by_detector": new,
            "cumulative_total": sum(counts.values())}


# --------------------------------------------------------------------------
# ResNet-50 (the reference's own headline model)
# --------------------------------------------------------------------------


def _stage_inputs(mesh, rng, batch, img, dtype, num_classes=1000):
    """The ONE synthetic input-staging path for the conv sections
    (images + labels onto the mesh) — through the device-resident
    double-buffered feed (data/data_loader.DeviceFeed, docs/perf.md
    "conv fast path"), so the conv sections measure the input pipeline
    they recommend: the host→device transfer happens on the feed's
    prefetch thread, off the critical path, and any starvation would
    land in perfscope ``input_wait``. The staged arrays then ride the
    scan carry (fully device-resident steps). Returns
    (images, labels, input_pipeline stamp)."""
    from horovod_tpu.data import DeviceFeed

    sh = NamedSharding(mesh, P("hvd"))
    host = (rng.standard_normal((batch, img, img, 3),
                                np.float32).astype(dtype),
            rng.integers(0, num_classes, (batch,)))
    feed = DeviceFeed(iter([host]), sharding=sh, depth=2)
    images, labels = next(iter(feed))
    feed.close()
    stamp = {"mode": "device_double_buffered", "depth": 2,
             "staged_mb": round(
                 (images.nbytes + labels.nbytes) / 2**20, 1)}
    return images, labels, stamp


def _layout_stamp(plan=None, note=None):
    """Per-section layout stamp (scripts/perf_gate.py asserts its
    presence and, for the ResNet sections, the padded mode — a revert
    to the unpadded layout fails the gate structurally)."""
    from horovod_tpu.ops.conv_block import conv_block_enabled

    if plan is not None:
        s = plan.summary()
    else:
        s = {"mode": "as_declared"}
        if note:
            s["note"] = note
    s["conv_block_fused"] = conv_block_enabled()
    return s


def bench_resnet(mesh, k, on_cpu, per_chip_batch, steps, warmup, depth=50):
    img = 32 if on_cpu else 224
    dtype = jnp.float32 if on_cpu else jnp.bfloat16
    batch = per_chip_batch * k

    params, stats = resnet.init(jax.random.PRNGKey(0), depth=depth,
                                num_classes=1000, dtype=dtype)
    # Conv fast path (docs/perf.md): lane-pad the declared conv stack so
    # the compiled program clears hvdhlo HVD204 — the stage-0 width-64
    # convs otherwise run the MXU at 50% padding waste on every step.
    # HOROVOD_LAYOUT_PAD=0 reverts (and the perf gate's layout stamp
    # check then fails, by design).
    from horovod_tpu.ops import layout as L
    lay = L.plan(params, resnet.conv_stack(depth))
    params, stats = lay.pad(params), lay.pad(stats)
    opt = optax.sgd(0.1, momentum=0.9)
    opt_state = opt.init(params)

    def local_step(params, stats, opt_state, batch):
        def loss(p):
            return resnet.loss_fn(p, stats, batch, depth=depth, train=True,
                                  axis_name="hvd")
        (l, new_stats), grads = jax.value_and_grad(loss, has_aux=True)(params)
        grads = reduce_gradients_in_jit(grads, num_ranks=k)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, new_stats, opt_state, lax.pmean(l, "hvd")

    step = jax.shard_map(local_step, mesh=mesh,
                         in_specs=(P(), P(), P(), P("hvd")),
                         out_specs=(P(), P(), P(), P()),
                         check_vma=False)

    rng = np.random.default_rng(0)
    images, labels, feed_stamp = _stage_inputs(mesh, rng, batch, img,
                                               dtype)

    def body(carry):
        p, s, o, im, lb, _ = carry
        p, s, o, l = step(p, s, o, (im, lb))
        return (p, s, o, im, lb, l)

    state = (params, stats, opt_state, images, labels, jnp.zeros(()))
    chain = max(steps // 3, 1)
    flops_info, prof, hlo_info, mem_info = {}, {}, {}, {}
    sec_per_step = _scan_timed(body, state, chain=chain,
                               reps=3, warmup=max(warmup // 2, 1),
                               flops_out=flops_info, profile_out=prof,
                               hlo_out=hlo_info, mem_out=mem_info)

    ips = batch / sec_per_step
    # Training FLOPs ≈ 3× forward. MAC convention (flops.py) — the
    # historical BENCH numbers; the StepProfile compares XLA against
    # the mul+add variant.
    flops_per_img = F.resnet_train_flops_per_image(depth, "macs") \
        if not on_cpu else None
    r = {
        "images_per_sec_per_chip": round(ips / k, 2),
        "per_chip_batch": per_chip_batch,
        "dtype": str(dtype.__name__ if hasattr(dtype, "__name__") else dtype),
        "step_ms": round(sec_per_step * 1e3, 2),
        "model_flops_per_image": flops_per_img,
        "timing": f"host clock over calls of a {chain}-step device-side "
                  "scan, behind block_until_ready",
        "layout": _layout_stamp(lay),
        "input_pipeline": feed_stamp,
    }
    # CPU smoke shrinks the image to 32px — the @224 constants would be
    # ~50x off there, so the fallback (and the vs-XLA ratio) is TPU-only.
    return _perf_stamp(
        r, f"resnet{depth}", flops_info, prof,
        None if on_cpu else
        F.resnet_train_flops_per_image(depth, "flops") * per_chip_batch,
        hlo_info=hlo_info, mem_info=mem_info)


def bench_inception(mesh, k, on_cpu, steps=12, warmup=2):
    """Inception V3 @299 — THE reference headline model (README.rst:102:
    90% scaling efficiency on 512 GPUs is the original Horovod result)."""
    from horovod_tpu.models import inception

    # CPU smoke: >=75px or reduction_b collapses spatial dims to 0x0
    # (global mean over zero elements = NaN)
    img = 80 if on_cpu else 299
    # B=128 is the measured v5e sweet spot: +42% over B=64 (r05 sweep
    # 32/64/96/128/192/256/384 -> 1460/1477/1557/2091/1495/2005/1951
    # img/s; docs/benchmarks.md)
    b = 2 if on_cpu else 128
    dtype = jnp.float32 if on_cpu else jnp.bfloat16
    batch = b * k
    params, stats = inception.init(jax.random.PRNGKey(0), dtype=dtype)
    opt = optax.sgd(0.1, momentum=0.9)
    opt_state = opt.init(params)

    def local_step(params, stats, opt_state, batch_):
        def loss(p):
            return inception.loss_fn(p, stats, batch_, train=True,
                                     axis_name="hvd")
        (l, ns), g = jax.value_and_grad(loss, has_aux=True)(params)
        g = reduce_gradients_in_jit(g, num_ranks=k)
        updates, opt_state = opt.update(g, opt_state, params)
        return (optax.apply_updates(params, updates), ns, opt_state,
                lax.pmean(l, "hvd"))

    step = jax.shard_map(local_step, mesh=mesh,
                         in_specs=(P(), P(), P(), P("hvd")),
                         out_specs=(P(), P(), P(), P()),
                         check_vma=False)
    rng = np.random.default_rng(0)
    images, labels, feed_stamp = _stage_inputs(mesh, rng, batch, img,
                                               dtype)

    def body(carry):
        p, s, o, im, lb, _ = carry
        p, s, o, l = step(p, s, o, (im, lb))
        return (p, s, o, im, lb, l)

    state = (params, stats, opt_state, images, labels, jnp.zeros(()))
    flops_info, prof, hlo_info, mem_info = {}, {}, {}, {}
    sec = _scan_timed(body, state, chain=max(steps // 3, 1), reps=3,
                      warmup=warmup, flops_out=flops_info,
                      profile_out=prof, hlo_out=hlo_info,
                      mem_out=mem_info)
    # Inception V3 fwd @299 ≈ 5.73 GMAC/img (torchvision convention,
    # flops.py) → training step ≈ 3×.
    r = {"images_per_sec_per_chip": round(b / sec, 2),
         "per_chip_batch": b, "image_size": img,
         "step_ms": round(sec * 1e3, 2),
         "model_flops_per_image":
             F.inception_v3_train_flops_per_image("macs")
             if not on_cpu else None,
         "layout": _layout_stamp(
             note="no conv_stack declaration yet (mixed 5x5/7x1 "
                  "channel plan; HVD204 stamp names the dims)"),
         "input_pipeline": feed_stamp}
    # @299 constants vs the 80px CPU smoke: fallback is TPU-only.
    return _perf_stamp(
        r, "inception_v3", flops_info, prof,
        None if on_cpu else
        F.inception_v3_train_flops_per_image("flops") * b,
        hlo_info=hlo_info, mem_info=mem_info)


# --------------------------------------------------------------------------
# Transformer LM (the framework flagship; MXU-bound)
# --------------------------------------------------------------------------

def bench_flash_attention(S=8192, iters=10):
    """Long-context attention: the Pallas flash kernel
    (ops/flash_attention.py) vs XLA's score-materializing attention,
    fwd+bwd at S=8192 — the long-sequence regime the kernel exists for."""
    import time

    from horovod_tpu.ops.flash_attention import flash_attention
    from horovod_tpu.parallel.ring_attention import (
        blockwise_attention_reference)

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (2, 16, S, 128), jnp.bfloat16)
               for kk in ks)

    def timed(fn, qkv, n_iters, warmup=5):
        g = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)),
            argnums=(0, 1, 2)))

        def run(n):
            t0 = time.perf_counter()
            for _ in range(n):
                out = g(*qkv)
            jax.block_until_ready(out)
            return time.perf_counter() - t0

        run(warmup)  # the first call compiles
        return run(n_iters) / n_iters * 1e3

    flash_fn = lambda q, k, v: flash_attention(q, k, v, causal=True)  # noqa: E731
    t_flash = timed(flash_fn, (q, k, v), iters)
    t_naive = timed(lambda q, k, v: blockwise_attention_reference(
        q, k, v, causal=True), (q, k, v), iters)

    # Capability unlock: S=32768 on ONE chip — the naive path's score
    # matrix alone (B·H·S² bf16 = 32 GiB) cannot fit 16 GB HBM; flash
    # streams it in O(S) blocks.
    qkv32 = tuple(jax.random.normal(kk, (1, 16, 32768, 128), jnp.bfloat16)
                  for kk in ks)
    t_32k = timed(flash_fn, qkv32, 5, warmup=3)

    return {"flash_fwd_bwd_ms": round(t_flash, 2),
            "naive_fwd_bwd_ms": round(t_naive, 2),
            "speedup": round(t_naive / t_flash, 2),
            "s32768_flash_fwd_bwd_ms": round(t_32k, 2),
            "s32768_naive": "OOM (score matrix alone 32 GiB bf16)"}


def bench_vgg16(mesh, k, steps=12, warmup=2):
    """VGG-16 — the reference's third headline model (README.rst:108:
    68% scaling on 512 GPUs; its all-conv3x3 body is the most
    MXU-friendly of the trio). TPU-only: ~20 s/step on the emulated-CPU
    mesh, so main() never calls it there (the model itself has CPU
    coverage via examples/synthetic_benchmark.py in test_examples)."""
    from horovod_tpu.models import vgg

    # B=128: +23% over B=64 on v5e (r05 sweep 32/64/96/128/192/256 ->
    # 1092/1202/1302/1481/1340/1487 img/s; plateau from 128)
    img, b, dtype = 224, 128, jnp.bfloat16
    batch = b * k
    params = vgg.init(jax.random.PRNGKey(0), depth=16, dtype=dtype,
                      image_size=img)
    opt = optax.sgd(0.1, momentum=0.9)
    opt_state = opt.init(params)

    def local_step(params, opt_state, batch_):
        def loss(p):
            return vgg.loss_fn(p, batch_, depth=16)
        l, g = jax.value_and_grad(loss)(params)
        g = reduce_gradients_in_jit(g, num_ranks=k)
        updates, opt_state = opt.update(g, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                lax.pmean(l, "hvd"))

    step = jax.shard_map(local_step, mesh=mesh,
                         in_specs=(P(), P(), P("hvd")),
                         out_specs=(P(), P(), P()), check_vma=False)
    rng = np.random.default_rng(0)
    images, labels, feed_stamp = _stage_inputs(mesh, rng, batch, img,
                                               dtype)

    def body(carry):
        p, o, im, lb, _ = carry
        p, o, l = step(p, o, (im, lb))
        return (p, o, im, lb, l)

    state = (params, opt_state, images, labels, jnp.zeros(()))
    flops_info, prof, hlo_info, mem_info = {}, {}, {}, {}
    sec = _scan_timed(body, state, chain=max(steps // 3, 1), reps=3,
                      warmup=warmup, flops_out=flops_info,
                      profile_out=prof, hlo_out=hlo_info,
                      mem_out=mem_info)
    # VGG-16 fwd @224 ≈ 15.5 GMAC/img (flops.py) → train ≈ 3×.
    r = {"images_per_sec_per_chip": round(b / sec, 2),
         "per_chip_batch": b, "image_size": img,
         "step_ms": round(sec * 1e3, 2),
         "model_flops_per_image": F.vgg16_train_flops_per_image("macs"),
         "layout": _layout_stamp(
             note="no conv_stack declaration yet (all-3x3 body — the "
                  "1x1 fast path does not apply; HVD204 stamp names "
                  "any unaligned dims)"),
         "input_pipeline": feed_stamp}
    return _perf_stamp(r, "vgg16", flops_info, prof,
                       F.vgg16_train_flops_per_image("flops") * b,
                       hlo_info=hlo_info, mem_info=mem_info)


def bench_transformer(on_cpu, steps, warmup):
    if on_cpu:
        cfg = tfm.TransformerConfig(vocab=256, d_model=64, n_heads=4,
                                    d_ff=256, n_layers=2, max_seq=128,
                                    attn="local")
        batch, seq = 2, 64
    else:
        # attn="flash": the Pallas kernel in the real train step — 11%
        # faster end-to-end than XLA's fused naive attention at S=1024
        # (266 vs 300 ms/step on v5e; the gap grows with S).
        cfg = tfm.TransformerConfig(vocab=32768, d_model=2048, n_heads=16,
                                    d_ff=8192, n_layers=12, max_seq=1024,
                                    attn="flash", dtype=jnp.bfloat16,
                                    remat=True)
        # B=12 is the HBM sweet spot on a 16 GiB v5e core: ~5% more
        # tok/s than B=8; B=16 OOMs under adam + remat.
        batch, seq = 12, 1024
    mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
    params = tfm.shard_params(tfm.init(jax.random.PRNGKey(0), cfg), cfg, mesh)
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)
    step = tfm.build_train_step(cfg, mesh, opt)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq),
                                0, cfg.vocab)
    targets = jnp.roll(tokens, -1, axis=1)

    def body(carry):
        p, o, tok, tgt, _ = carry
        p, o, l = step(p, o, tok, tgt)
        return (p, o, tok, tgt, l)

    state = (params, opt_state, tokens, targets, jnp.zeros(()))
    chain = max(steps // 3, 1)
    flops_info, prof, hlo_info, mem_info = {}, {}, {}, {}
    sec = _scan_timed(body, state, chain=chain, reps=3,
                      warmup=max(warmup // 2, 1), flops_out=flops_info,
                      profile_out=prof, hlo_out=hlo_info,
                      mem_out=mem_info)
    dt, steps = sec * steps, steps  # keep downstream arithmetic unchanged

    # Analytical model FLOPs: the standard 6N + attention accounting
    # (profiler/flops.py; PaLM appendix B) — counts mul+add separately,
    # so directly comparable with the XLA cost analysis (remat makes the
    # XLA number HIGHER: recomputed forwards are real executed FLOPs).
    flops_tok = F.transformer_train_flops_per_token(
        cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.vocab, seq)
    toks = batch * seq
    tps = toks * steps / dt
    r = {
        "tokens_per_sec_per_chip": round(tps, 1),
        "config": f"L{cfg.n_layers} D{cfg.d_model} F{cfg.d_ff} "
                  f"H{cfg.n_heads} S{seq} B{batch} V{cfg.vocab} bf16",
        "step_ms": round(dt / steps * 1e3, 2),
        "model_flops_per_token": flops_tok,
        "params_m": round(F.transformer_matmul_params(
            cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.vocab) / 1e6, 1),
    }
    return _perf_stamp(r, "transformer_lm", flops_info, prof,
                       flops_tok * toks, hlo_info=hlo_info,
                       mem_info=mem_info)


# --------------------------------------------------------------------------
# Fusion-threshold sweep on the eager grouped-allreduce path
# --------------------------------------------------------------------------
# BERT-base fine-tune shape through the EAGER DistributedOptimizer with
# Adasum + gradient predivide (BASELINE.md tracked config; reference:
# examples/pytorch synthetic benchmark with --use-adasum +
# gradient_predivide_factor). Unlike the SPMD LM bench, every step's
# gradients leave the jit and ride the eager fused-collective engine —
# this is the hvd.DistributedOptimizer migration path's cost.
# --------------------------------------------------------------------------

def bench_bert_adasum(on_cpu, steps=10, warmup=3):
    from horovod_tpu.common import types as T
    from horovod_tpu.optim.optimizer import DistributedOptimizer

    if on_cpu:
        cfg = tfm.TransformerConfig(vocab=256, d_model=64, n_heads=4,
                                    d_ff=256, n_layers=2, max_seq=64,
                                    attn="local")
        batch, seq, steps, warmup = 2, 32, 2, 1
    else:
        # BERT-base shape: L12 D768 H12 F3072, fine-tune seq 128
        cfg = tfm.TransformerConfig(vocab=30522, d_model=768, n_heads=12,
                                    d_ff=3072, n_layers=12, max_seq=128,
                                    attn="local", dtype=jnp.bfloat16)
        batch, seq = 32, 128
    mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
    params = tfm.shard_params(tfm.init(jax.random.PRNGKey(0), cfg), cfg,
                              mesh)
    dist_opt = DistributedOptimizer(
        optax.adam(2e-5), op=T.ReduceOp.ADASUM)
    # reference BERT runs also exercise predivide; Adasum forbids it
    # (Average-only), so predivide is measured on a second optimizer
    pre_opt = DistributedOptimizer(
        optax.adam(2e-5), op=T.ReduceOp.AVERAGE,
        gradient_predivide_factor=2.0)
    fwd = tfm.build_forward(cfg, mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq),
                                0, cfg.vocab)
    targets = jnp.roll(tokens, -1, axis=1)

    def loss_fn(p):
        logits = fwd(p, tokens)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(
            logp, targets[..., None], axis=-1))

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))

    def one(opt, state):
        l, g = grad_fn(params)
        return opt.step(g, params, state)[1], l

    out = {}
    # ONE AOT lower+compile of the jitted fwd+bwd feeds all three
    # stamps: the XLA cost-analysis FLOPs for the StepProfile, the
    # hvdhlo lint of the eager migration path (the allreduce rides the
    # eager collective engine, covered by the SPMD sections' stamps),
    # and the static peak-HBM memory stamp. The enabled checks come
    # FIRST — lowering BERT fwd+bwd just to throw it away under
    # HOROVOD_HLO_LINT=0 + XLA-flops-off would defeat both knobs.
    xla_flops = None
    hlo_info, mem_info = {}, {}
    compiled = None
    if F.xla_flops_enabled() or _hlo_lint_enabled():
        try:
            lowered = grad_fn.lower(params)
            if _hlo_lint_enabled():
                hlo_info = _hlo_lint_lowered(lowered)
            if F.xla_flops_enabled():
                compiled = lowered.compile()
                xla_flops = F.compiled_cost_flops(compiled)
        except Exception:
            pass
    if compiled is not None:
        mem_info = _memory_stamp(compiled)
    fallback_flops = F.transformer_train_flops_per_token(
        cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.vocab, seq) * batch * seq
    for name, opt in (("adasum", dist_opt), ("predivide", pre_opt)):
        state = opt.init(params)
        for _ in range(warmup):
            state, l = one(opt, state)

        def run(n):
            # block on the optimizer STATE, not just the loss — the
            # allreduce+update chain is what this bench measures and the
            # loss does not depend on it.
            nonlocal state
            t0 = time.perf_counter()
            for _ in range(n):
                state, l = one(opt, state)
            jax.block_until_ready(state)
            return time.perf_counter() - t0

        run(1)
        dt = run(steps) / steps
        out[f"{name}_samples_per_sec"] = round(batch / dt, 2)
        out[f"{name}_step_ms"] = round(dt * 1e3, 2)
        if name == "adasum":
            # perfscope sampling on the eager migration path: explicit
            # synced steps so the auto-hooked DistributedOptimizer
            # phases (comms / optimizer) land inside them.
            ps = pscope.get()
            ps.reset()
            for _ in range(2 if on_cpu else 3):
                with ps.step():
                    state, l = one(opt, state)
                    with ps.phase("device_compute"):
                        jax.block_until_ready(state)
            s = ps.summary()
            prof = {"summary": s} if s else {}
            _perf_stamp(out, "bert_base_finetune",
                        {"program_flops_per_step": xla_flops}
                        if xla_flops else {},
                        prof, fallback_flops, hlo_info=hlo_info,
                        mem_info=mem_info)
    out["config"] = f"L{cfg.n_layers} D{cfg.d_model} H{cfg.n_heads} " \
                    f"S{seq} B{batch} (BERT-base shape)"
    return out


def _serving_trace_stamp():
    """hvdtrace evidence for the serving section: the loopback bench
    runs every plane in one process, so the in-process tracer holds the
    full client → frontend → batcher → pool → replica → engine span
    tree. Join it with the doctor's own analyzer and stamp the slowest
    request's queue/dispatch/device split — perf_gate requires this
    block structurally (a serving bench without trace evidence is an
    observability regression, not just a perf one)."""
    from horovod_tpu.observability import doctor, tracing
    tr = tracing.get()
    stats = tr.stats()
    report = doctor.analyze_traces([tr.payload()]) or {}
    slowest = report.get("slowest") or []
    pick = next((e for e in slowest if e.get("complete")),
                slowest[0] if slowest else None)

    def ms(v):
        return round(v * 1e3, 3) if isinstance(v, (int, float)) else None

    return {
        "version": tracing.TRACE_VERSION,
        "sampled": stats.get("started", 0),
        "finished": stats.get("finished", 0),
        "requests_joined": report.get("requests", 0),
        "complete": report.get("complete", 0),
        "slowest": {
            "trace_id": pick.get("trace_id"),
            "rid": pick.get("rid"),
            "total_ms": ms(pick.get("total_s")),
            "queue_ms": ms(pick.get("queue_s")),
            "dispatch_ms": ms(pick.get("dispatch_s")),
            "device_ms": ms(pick.get("device_s")),
        } if pick else None,
    }


def bench_serving(on_cpu, duration=None, threads=8):
    """Serving tier under load (docs/serving.md): an in-process
    loopback replica pool — frontend → continuous batcher → per-bucket
    AOT engine — driven by paced client threads approximating open-loop
    arrivals. Reports requests/sec/chip and p50/p99 end-to-end request
    latency (the serving acceptance numbers), mean formed batch size,
    and the engine's hvdhlo stamp of the lowered inference program.

    Loopback on one host: the numbers measure the service's control
    plane + batching + a real AOT device step, not multi-host fanout —
    both replicas share device 0, so chips=1 in the per-chip rate."""
    import threading as th

    from horovod_tpu.observability import tracing
    from horovod_tpu.runner import secret as secret_mod
    from horovod_tpu.runner.rendezvous import KVClient, RendezvousServer
    from horovod_tpu.serve.batching import ContinuousBatcher
    from horovod_tpu.serve.engine import InferenceEngine
    from horovod_tpu.serve.frontend import Frontend, ServeClient
    from horovod_tpu.serve.pool import ReplicaPool
    from horovod_tpu.serve.replica import ReplicaServer

    # Force hvdtrace on for this section (restored below): the stamped
    # `trace` block must be deterministic regardless of the caller's
    # environment, because perf_gate fails the round without it.
    prev_trace_env = os.environ.get(tracing.TRACE_ENV)
    os.environ[tracing.TRACE_ENV] = "1"
    tracing.reset_for_tests()

    duration = duration or (2.0 if on_cpu else 6.0)
    # lane-aligned dims: the engine's own hvdhlo stamp (HVD204) holds
    # this model to the padding guidance it reports on
    features, hidden = 128, 256
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    params = {
        "w1": jax.random.normal(k1, (features, hidden), jnp.float32) / 8,
        "w2": jax.random.normal(k2, (hidden, 1), jnp.float32) / 16,
    }

    def infer_fn(p, x):
        return jnp.maximum(x @ p["w1"], 0.0) @ p["w2"]

    secret = secret_mod.make_secret_key().encode()
    rdv = RendezvousServer(secret=secret)
    rdv_port = rdv.start()
    batcher = ContinuousBatcher(max_batch=16, max_wait_s=0.002,
                                depth=4096)
    replicas = []
    stops = []
    lock = th.Lock()
    lat = []      # guarded-by: lock
    fails = []    # guarded-by: lock
    stop_load = th.Event()
    load_threads = []
    try:
        for r in range(2):
            rep = ReplicaServer(
                InferenceEngine(infer_fn, params, name=f"bench{r}"),
                kv=KVClient("127.0.0.1", rdv_port, secret=secret),
                secret=secret)
            rep.ident.update({"rank": r, "local_rank": r})
            rep.engine.warmup((features,), np.float32, batcher.buckets)
            rep.start()
            replicas.append(rep)
        pool = ReplicaPool(rdv, batcher, secret=secret,
                           discovery_interval=0.05)
        pool.start()
        stops.append(pool.stop)
        pool.wait_for_replicas(2, timeout=60)
        frontend = Frontend(batcher, secret=secret, port=0)
        front_port = frontend.start()
        stops.append(frontend.stop)
        addr = ("127.0.0.1", front_port)

        probe = ServeClient(addr, secret=secret)
        probe.infer(np.ones((features,), np.float32))  # prime the path
        probe.close()

        def load_worker():
            c = ServeClient(addr, secret=secret)
            x = np.ones((features,), np.float32)
            try:
                while not stop_load.is_set():
                    t0 = time.perf_counter()
                    try:
                        c.infer(x)
                    except Exception as e:
                        with lock:
                            fails.append(_err_str(e))
                        return
                    with lock:
                        lat.append(time.perf_counter() - t0)
                    time.sleep(0.002)
            finally:
                c.close()

        t_start = time.perf_counter()
        load_threads = [th.Thread(target=load_worker, daemon=True)
                        for _ in range(threads)]
        for t in load_threads:
            t.start()
        time.sleep(duration)
        stop_load.set()
        for t in load_threads:
            t.join(timeout=30)
        wall = time.perf_counter() - t_start

        with lock:
            samples = sorted(lat)
            errors = list(fails)
        if not samples:
            raise RuntimeError(
                "serving bench completed zero requests: "
                + "; ".join(errors[:3]))
        n = len(samples)
        p50 = samples[n // 2]
        p99 = samples[min(n - 1, int(n * 0.99))]
        batches = pool.batches_done
        return {
            "requests": n,
            "wall_seconds": round(wall, 3),
            "requests_per_sec": round(n / wall, 1),
            "requests_per_sec_per_chip": round(n / wall, 1),
            "chips": 1,
            "replicas": len(replicas),
            "p50_ms": round(p50 * 1e3, 3),
            "p99_ms": round(p99 * 1e3, 3),
            "batches": batches,
            "mean_batch_size": round(n / max(batches, 1), 2),
            "max_batch": batcher.max_batch,
            "buckets": list(batcher.buckets),
            "load_threads": threads,
            "hlo_lint": replicas[0].engine.hlo_lint() or None,
            "client_errors": errors[:5] or None,
            "trace": _serving_trace_stamp(),
        }
    finally:
        stop_load.set()
        for t in load_threads:
            t.join(timeout=10)
        for s in stops:
            s()
        for rep in replicas:
            rep.stop()
        rdv.stop()
        if prev_trace_env is None:
            os.environ.pop(tracing.TRACE_ENV, None)
        else:
            os.environ[tracing.TRACE_ENV] = prev_trace_env
        tracing.reset_for_tests()


# --------------------------------------------------------------------------
# Fusion sweep + autotune on an 8-device virtual CPU mesh (subprocess).
#
# One chip has no wire: a grouped allreduce over a one-device mesh
# measures dispatch overhead only, and earlier single-chip sweeps came out
# non-monotonic (round-4 verdict Weak #2/#3). These sections therefore
# COUNT what the knob controls — how many psums one grouped program
# compiles to — on an 8-device virtual CPU mesh in a subprocess, where
# every rank runs the identical shard_map/XLA path a pod runs. Their
# times are CPU-backend times, never device metrics (ROADMAP S1/S4).
# --------------------------------------------------------------------------

# ResNet-50-like gradient set: a few conv bodies + many small BN/bias
# grads (~26 MB total, 126 tensors). Small tensors are the regime where
# bucketing matters: the set compiles to 8/5/2/1 psums at 1/4/16/64 MB
# (pinned by tests/test_bench_timing.py).
_EAGER_SIZES = [(1000, 512), (512,)] + [(512, 512, 3, 3)] * 2 + \
    [(256, 256, 3, 3)] * 2 + [(128, 128, 3, 3)] * 2 + \
    [(512,)] * 60 + [(256,)] * 60


def _eager_cpu_mesh_child():
    """Child-process body (bench.py --eager-cpu-mesh): fusion sweep +
    autotune on the 8-device CPU mesh; prints one JSON line. Requires
    the bench_eager_cpu_mesh environment — a direct invocation without
    it would silently measure whatever backend came up and label it a
    CPU mesh, so enforce it here rather than trust the caller."""
    if jax.default_backend() != "cpu" or len(jax.devices()) < 2 or \
            not os.environ.get("HOROVOD_NO_REPLICATED_FAST"):
        raise SystemExit(
            "--eager-cpu-mesh needs JAX_PLATFORMS=cpu, "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 and "
            "HOROVOD_NO_REPLICATED_FAST=1 (run through bench.py's "
            "bench_eager_cpu_mesh wrapper)")
    hvd.init()
    from horovod_tpu.core.autotune import ParameterManager
    from horovod_tpu.ops.collectives import clear_compiled_cache

    tensors = [jnp.ones(s, jnp.float32) for s in _EAGER_SIZES]
    nbytes = sum(int(np.prod(s)) * 4 for s in _EAGER_SIZES)
    cfg = topology.raw_state().config
    result = {"platform": f"{len(jax.devices())}-device virtual CPU mesh "
                          "(subprocess)",
              "workload": f"grouped_allreduce of {len(_EAGER_SIZES)} "
                          f"tensors, {nbytes / 2**20:.1f} MB total"}

    def measure(calls=4, reps=3, fn=None):
        """Median-of-reps mean per-call ms: a plain mean over pipelined
        calls with one sync; the median across reps rejects host-load
        spikes."""
        fn = fn or hvd.grouped_allreduce

        def one():
            outs = None
            t0 = time.perf_counter()
            for _ in range(calls):
                outs = fn(tensors, op="sum")
            jax.block_until_ready(outs)
            return (time.perf_counter() - t0) / calls * 1e3

        one()  # compile
        one()  # settle
        xs = sorted(one() for _ in range(reps))
        return xs[len(xs) // 2]

    # --- fusion sweep, two INTERLEAVED runs (the stability evidence the
    # TPU-eager sweep never produced). Back-to-back full sweeps measured
    # ~27% point drift from slow host-load variation between the runs;
    # interleaving the passes (1,4,16,64, 1,4,16,64, ...) exposes every
    # threshold to the same load profile, and each run's number is the
    # median of its passes. Each pass measures BOTH dispatch paths:
    # "grouped" (one XLA program for the whole set, buckets chunked to
    # the cap — the cliff fix) and "overlapped" (bucketed_allreduce: one
    # program per bucket, dispatched without blocking so transfers
    # pipeline). r05's 16/64MB points were ~465-490ms vs ~230-250ms at
    # 1-4MB; the cap + chunking must hold max_adjacent_ratio <= 1.5. ---
    thresholds = (1, 4, 16, 64)
    passes = 6
    samples = {mb: [] for mb in thresholds}
    osamples = {mb: [] for mb in thresholds}
    for _ in range(passes):
        for mb in thresholds:
            cfg.fusion_threshold_bytes = mb * 1024 * 1024
            clear_compiled_cache()
            samples[mb].append(measure(reps=1))
            osamples[mb].append(
                measure(reps=1, fn=hvd.bucketed_allreduce))
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    sweep = {
        "run1": {f"{mb}MB_ms": round(med(samples[mb][0::2]), 2)
                 for mb in thresholds},
        "run2": {f"{mb}MB_ms": round(med(samples[mb][1::2]), 2)
                 for mb in thresholds},
        "overlapped": {
            "run1": {f"{mb}MB_ms": round(med(osamples[mb][0::2]), 2)
                     for mb in thresholds},
            "run2": {f"{mb}MB_ms": round(med(osamples[mb][1::2]), 2)
                     for mb in thresholds},
        },
    }
    drift = max(abs(sweep["run1"][k] - sweep["run2"][k])
                / max(sweep["run1"][k], 1e-9)
                for k in sweep["run1"])
    sweep["max_run_to_run_drift_pct"] = round(drift * 100, 1)
    meds = [med(samples[mb]) for mb in thresholds]
    sweep["max_adjacent_ratio"] = round(
        max(max(a, b) / max(min(a, b), 1e-9)
            for a, b in zip(meds, meds[1:])), 3)
    from horovod_tpu.ops.fusion import effective_threshold, plan_buckets
    sweep["bucket_cap_mb"] = cfg.bucket_cap_bytes / 2**20
    # Buckets of the program each swept point actually compiles: the
    # cap chunks 16/64MB requests down to the sweet spot.
    sweep["buckets_per_program"] = {
        f"{mb}MB": len(plan_buckets(
            [(s, "float32") for s in _EAGER_SIZES],
            effective_threshold(mb * 1024 * 1024, cfg.bucket_cap_bytes)))
        for mb in (1, 4, 16, 64)}
    result["fusion_sweep"] = sweep
    result["lm_overlap"] = _lm_overlap_section(cfg)

    # --- autotune: start from the reference's own 64 MB default
    # (docs/tensor-fusion.rst), which the sweep above shows is WRONG for
    # this platform/workload (the XLA:CPU collective backend favors many
    # small buckets — threshold sensitivity is exactly why the reference
    # ships an autotuner). The GP must discover the small-bucket region;
    # the playoff freeze then re-measures its argmax against the 64 MB
    # start back-to-back and keeps the true winner. The bucket cap is
    # lifted for this section: it would silently clamp every >4MB sample
    # to the sweet spot and flatten the very landscape the GP tunes over.
    saved_cap = cfg.bucket_cap_bytes
    cfg.bucket_cap_bytes = 0
    cfg.fusion_threshold_bytes = 64 * 1024 * 1024
    cfg.autotune_warmup_samples = 1
    cfg.autotune_steps_per_sample = 2
    cfg.autotune_bayes_opt_max_samples = 10
    cfg.autotune = True
    clear_compiled_cache()
    pm = ParameterManager(cfg)
    # EVERY knob's starting value (threshold + cache + hierarchical if
    # meshed): default_ms below must measure the true default config, not
    # tuned-except-threshold
    start_vals = dict(pm._default_vals)
    steps = 0
    while not pm.frozen and steps < 400:
        ms = measure(calls=3, reps=1)
        pm.record(nbytes, ms / 1e3)
        if pm.update():
            clear_compiled_cache()
        steps += 1
    cfg.autotune = False
    tuned = pm.frozen_choice()
    tuned_mb = cfg.fusion_threshold_bytes / (1024 * 1024)
    tuned_ms = measure()
    pm._apply_raw(start_vals)  # restore ALL knobs to the starting config
    clear_compiled_cache()
    default_ms = measure()
    result["autotune"] = {
        "frozen": pm.frozen, "steps": steps,
        "start_threshold_mb": 64.0,
        "tuned_threshold_mb": round(tuned_mb, 1),
        "tuned_knobs": {k: (v if not isinstance(v, bool) else int(v))
                        for k, v in tuned.items()},
        "tuned_ms": round(tuned_ms, 2),
        "default_ms": round(default_ms, 2),
        "tuned_speedup_vs_default": round(default_ms / tuned_ms, 3),
        "playoff": pm.playoff_result,
        "bucket_cap": "lifted for this section (would clamp the GP's "
                      ">4MB samples)",
    }
    cfg.bucket_cap_bytes = saved_cap
    print(json.dumps(result), flush=True)


def _lm_overlap_section(cfg):
    """Backward-overlapped bucketed reduction vs one giant fused psum on
    the framework's OWN DP train step (optim.build_train_step →
    reduce_gradients_in_jit), with a transformer-LM-shaped parameter set:
    a tied 8 MB embedding (oversize → chunked across buckets) plus 6
    residual FFN blocks. The giant-fused variant is exactly the pre-PR-6
    program shape (one psum after the whole backward); the bucketed
    variant chunks to the cap in reverse production order so XLA can run
    bucket collectives while earlier layers still differentiate."""
    import optax

    from horovod_tpu.optim.optimizer import build_train_step

    rng = np.random.default_rng(1)
    D, F, V, NL = 256, 1024, 8192, 6
    params = {"emb": jnp.asarray(
        rng.standard_normal((V, D)) * 0.02, jnp.float32)}
    for i in range(NL):
        params[f"wi{i}"] = jnp.asarray(
            rng.standard_normal((D, F)) * 0.02, jnp.float32)
        params[f"wo{i}"] = jnp.asarray(
            rng.standard_normal((F, D)) * 0.02, jnp.float32)

    def loss_fn(p, batch):
        tok, tgt = batch
        h = p["emb"][tok]  # (B, S, D)
        for i in range(NL):
            h = h + jnp.tanh(h @ p[f"wi{i}"]) @ p[f"wo{i}"]
        logits = h @ p["emb"].T  # tied unembedding
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1))

    B, S = 16, 64
    tok = jnp.asarray(rng.integers(0, V, (B, S)))
    tgt = jnp.roll(tok, -1, axis=1)
    opt = optax.sgd(0.01)
    mb = 1024 * 1024

    out = {}
    variants = {"fused": (1 << 30, 0, False),
                "bucketed": (4 * mb, 4 * mb, True)}
    for label, (thresh, cap, rev) in variants.items():
        cfg.fusion_threshold_bytes = thresh
        cfg.bucket_cap_bytes = cap
        cfg.bucket_reverse = rev
        # donate=False: state is reused across timing reps below
        step = build_train_step(loss_fn, opt, donate=False)
        p = jax.tree_util.tree_map(jnp.copy, params)
        o = opt.init(p)
        for _ in range(3):
            p, o, l = step(p, o, (tok, tgt))
        jax.block_until_ready(l)

        def run(n=6):
            p2, o2 = p, o
            t0 = time.perf_counter()
            for _ in range(n):
                p2, o2, l2 = step(p2, o2, (tok, tgt))
            jax.block_until_ready(l2)
            return (time.perf_counter() - t0) / n * 1e3

        xs = sorted(run() for _ in range(3))
        out[f"{label}_step_ms"] = round(xs[1], 2)
    out["speedup_bucketed_vs_fused"] = round(
        out["fused_step_ms"] / out["bucketed_step_ms"], 3)
    out["config"] = (f"tied-emb LM shape V{V} D{D} F{F} L{NL} B{B} S{S} "
                     f"f32 (~{(V * D + 2 * NL * D * F) * 4 / 2**20:.0f}MB "
                     f"grads), 8-dev mesh")
    return out


# --------------------------------------------------------------------------
# GSPMD hybrid-parallel backend: the 8-device scaling bench
# (docs/parallelism.md; ROADMAP item 3). Pure-DP vs tp=4 x dp=2 on the
# SAME global batch through the SAME DistributedOptimizer sharded-step
# builder, reporting per-model throughput and scaling efficiency as
# structured JSON plus the per-axis (dp vs tp) comms split, the shard
# lint of the runtime program, and the static memory stamp. Runs on the
# 8-device virtual CPU mesh in a subprocess (single attached TPU chips
# cannot host a 2-D mesh; on the virtual mesh every rank runs the
# identical shard_map/XLA path a pod runs). NOTE on the numbers: the 8
# virtual devices share one host's cores, so absolute scaling
# efficiency is pessimistic there — the section's contract is the
# REPORTING pipeline (mesh/scaling/comms stamps, gated structurally by
# scripts/perf_gate.py); a real 8-chip slice fills in the real ratio.
# --------------------------------------------------------------------------

def _gspmd_variant(label, mesh_spec_text, pspecs_fn, cfg, batch, seq,
                   steps, want_analysis=False):
    """Train the tied LM on one mesh config and time it. Returns the
    per-variant result dict (+ lowered/compiled handles for stamps)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import tied_lm
    from horovod_tpu.parallel.mesh import MeshSpec, build_mesh

    spec = MeshSpec.parse(mesh_spec_text, None)
    mesh = build_mesh(spec, devices=jax.devices()[:spec.total])
    dist = hvd.DistributedOptimizer(
        optax.sgd(0.01), sharding_spec=pspecs_fn(cfg), mesh=mesh)
    step = dist.sharded_step(
        lambda p, b: tied_lm.local_loss(p, b[0], b[1], cfg),
        donate=False)
    params = dist.shard_params(tied_lm.init(0, cfg))
    tok, tgt = tied_lm.sample_batch(1, cfg, batch=batch, seq=seq)
    b = jax.device_put((tok, tgt), NamedSharding(mesh, P("dp")))
    st = dist.init(params)

    lowered = compiled = None
    run_fn = step
    if want_analysis:
        # ONE AOT lower+compile feeds the comms/memory/lint stamps AND
        # the timed loop (the _scan_timed recipe: analysis rides a
        # compile the bench pays for anyway).
        try:
            lowered = step.lower(params, st, b)
            compiled = lowered.compile()
            run_fn = lambda p, s, bb: compiled(p, s, bb)  # noqa: E731
        except Exception:
            lowered = compiled = None

    loss = None
    for _ in range(2):
        params, st, loss = run_fn(params, st, b)
    jax.block_until_ready(loss)

    def timed(ncalls):
        nonlocal params, st, loss
        t0 = time.perf_counter()
        for _ in range(ncalls):
            params, st, loss = run_fn(params, st, b)
        jax.block_until_ready(loss)
        return (time.perf_counter() - t0) / ncalls

    xs = sorted(timed(max(steps // 3, 2)) for _ in range(3))
    sec = xs[1]
    if want_analysis:
        # Perfscope-sampled steps on the same executable, so the
        # section carries a full StepProfile (incl. the trace-time
        # comms_axes split the sharded reduction recorded).
        ps = pscope.get()
        for _ in range(3):
            with ps.step():
                params, st, loss = run_fn(params, st, b)
                with ps.phase("device_compute"):
                    jax.block_until_ready(loss)
    toks = batch * seq
    return {
        "mesh": {"spec": spec.describe(), "devices": spec.total,
                 "shape": {a: int(s) for a, s in
                           zip(mesh.axis_names, mesh.devices.shape)
                           if int(s) > 1}},
        "steps_per_sec": round(1.0 / sec, 3),
        "tokens_per_sec": round(toks / sec, 1),
        "step_ms": round(sec * 1e3, 2),
        "global_batch": batch, "seq": seq,
        "final_loss": round(float(loss), 4),
    }, spec, lowered, compiled


def _gspmd_cpu_mesh_child():
    """Child-process body (bench.py --gspmd-cpu-mesh): the hybrid
    scaling section on the 8-device CPU mesh; prints one JSON line."""
    if jax.default_backend() != "cpu" or len(jax.devices()) < 8:
        raise SystemExit(
            "--gspmd-cpu-mesh needs JAX_PLATFORMS=cpu and "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "(run through bench.py's bench_gspmd_hybrid wrapper)")
    from horovod_tpu.models import tied_lm
    from horovod_tpu.parallel.mesh import AXIS_ORDER
    from horovod_tpu.analysis import shard as shard_mod

    cfg = tied_lm.canonical_config()
    B, S, steps = 64, 64, 9
    ps = pscope.get()
    ps.reset()

    dp1, _, _, _ = _gspmd_variant(
        "dp1", "dp=1", tied_lm.replicated_specs, cfg, B // 8, S, steps)
    dp8, _, _, _ = _gspmd_variant(
        "dp8", "dp=8", tied_lm.replicated_specs, cfg, B, S, steps)
    ps.reset()  # hybrid's trace-time comms_axes must not mix with DP's
    hybrid, spec, lowered, compiled = _gspmd_variant(
        "hybrid", "dp=2,tp=4", tied_lm.param_specs, cfg, B, S, steps,
        want_analysis=True)

    result = {
        "platform": f"{len(jax.devices())}-device virtual CPU mesh "
                    "(subprocess; devices share host cores — scaling "
                    "ratios are pessimistic here, the stamps are the "
                    "contract)",
        "model": f"tied_lm V{cfg.vocab} D{cfg.d_model} F{cfg.d_ff} "
                 f"L{cfg.n_layers} f32",
        "dp1": dp1, "dp8": dp8, "hybrid": hybrid,
        "mesh": hybrid["mesh"],
        "scaling": {
            "dp_tokens_per_sec": dp8["tokens_per_sec"],
            "hybrid_tokens_per_sec": hybrid["tokens_per_sec"],
            "efficiency_vs_dp": round(
                hybrid["tokens_per_sec"] / dp8["tokens_per_sec"], 3),
            "dp1_tokens_per_sec": dp1["tokens_per_sec"],
            "dp_scaling_efficiency": round(
                dp8["tokens_per_sec"] / (8 * dp1["tokens_per_sec"]), 3),
            "convention": "weak scaling (fixed per-dp-shard batch); "
                          "efficiency_vs_dp = hybrid/dp throughput on "
                          "the same global batch",
        },
    }
    if compiled is not None:
        text = compiled.as_text()
        try:
            result["comms_by_axis"] = shard_mod.comms_by_axis(
                text, list(zip(AXIS_ORDER, spec.sizes())))
        except Exception as e:
            result["comms_by_axis_error"] = _err_str(e)
        # The analytic hvdsched cost model, off the SAME compiled text
        # the measured comms_by_axis reads (docs/perf.md). The ratio
        # compares predicted wire bytes (payload x ring wire factor,
        # factors all in [0.5, 2.0)) against the measured payload
        # accounting — tracked across rounds by perfboard and
        # structurally required by scripts/perf_gate.py.
        try:
            from horovod_tpu.analysis import schedule as sched_mod
            cm = sched_mod.comms_model(
                text, list(zip(AXIS_ORDER, spec.sizes())))
            measured = sum(
                int(v.get("bytes_per_step", 0))
                for v in result.get("comms_by_axis", {}).values())
            if measured > 0:
                cm["predicted_vs_measured"] = round(
                    cm["predicted_bytes_per_step"] / measured, 4)
            result["comms_model"] = cm
        except Exception as e:
            result["comms_model_error"] = _err_str(e)
        # The hvdnum stamp, off the SAME compiled text: accumulation
        # dtypes seen plus the gradient-scale table (group size,
        # divisor, effective multiplier, axis attribution via the
        # shared shard.group_axis_label classifier). Structurally
        # required by scripts/perf_gate.py; perfboard carries the
        # finding count across rounds.
        try:
            from horovod_tpu.analysis import numerics as num_mod
            result["numerics"] = num_mod.stamp(
                text, list(zip(AXIS_ORDER, spec.sizes())),
                path="<gspmd>")
        except Exception as e:
            result["numerics_error"] = _err_str(e)
        result["memory"] = _memory_stamp(compiled)
        try:
            result["shard_lint"] = {
                "findings": len(shard_mod.lint_text(text,
                                                    path="<gspmd>")),
            }
        except Exception:
            pass
        flops_info = {}
        total = F.compiled_cost_flops(compiled)
        if total:
            flops_info["program_flops_per_step"] = total
        s = ps.summary()
        _perf_stamp(result, "gspmd_hybrid", flops_info,
                    {"summary": s} if s else {}, None)
    print(json.dumps(result), flush=True)


def bench_gspmd_hybrid(timeout=1800):
    """Parent wrapper: run the GSPMD hybrid scaling section in a
    CPU-mesh subprocess (single attached chips cannot host the 2-D
    mesh; see the block comment above)."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--gspmd-cpu-mesh"],
        env=env, capture_output=True, text=True, timeout=timeout)
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    if out.returncode != 0 or not lines:
        raise RuntimeError(
            f"gspmd-cpu-mesh subprocess failed rc={out.returncode}: "
            f"{out.stderr[-500:]}")
    return json.loads(lines[-1])


def bench_eager_cpu_mesh(timeout=1500):
    """Parent wrapper: run the eager fusion/autotune sections in a CPU-mesh
    subprocess (see block comment above; reference knob:
    HOROVOD_FUSION_THRESHOLD, docs/tensor-fusion.rst + docs/autotune.rst)."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["HOROVOD_NO_REPLICATED_FAST"] = "1"  # measure the real machinery
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--eager-cpu-mesh"],
        env=env, capture_output=True, text=True, timeout=timeout)
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    if out.returncode != 0 or not lines:
        raise RuntimeError(
            f"eager-cpu-mesh subprocess failed rc={out.returncode}: "
            f"{out.stderr[-500:]}")
    return json.loads(lines[-1])


def bench_checkpointing(on_cpu, steps=72, every=4):
    """Async checkpointing overhead (docs/checkpointing.md, ROADMAP
    item 5 acceptance): twin loops of the SAME jitted train step — one
    plain, one with an AsyncCheckpointer saving every `every` steps —
    stamp the measured overhead fraction (must stay <5%; perf_gate
    fails it), the save-phase split (snapshot = the only critical-path
    phase vs background persist/commit), bytes/s into the persist
    tier, and the worst per-step blocking excess on a save step (the
    'async save never blocks a step for more than the device-snapshot
    phase' check, stamped so regressions are visible in the record)."""
    import statistics
    import tempfile

    from horovod_tpu import ckpt as ckpt_mod
    from horovod_tpu.ckpt import manifest as ckpt_mf

    # sized so the step dwarfs the snapshot: the measurement needs the
    # ratio's denominator honest, not a tiny step that makes noise
    # look like overhead
    n = 768 if on_cpu else 2048
    key = jax.random.PRNGKey(0)
    params = {"w1": jax.random.normal(key, (n, n), jnp.float32),
              "w2": jax.random.normal(key, (n, n), jnp.float32)}
    x = jnp.ones((n, n), jnp.float32)

    @jax.jit
    def step_fn(p, x):
        h = jnp.tanh(x @ p["w1"])
        h = jnp.tanh(h @ p["w2"])
        h = jnp.tanh(h @ p["w1"])
        return h @ p["w2"]

    jax.block_until_ready(step_fn(params, x))  # compile outside timing

    def run(n_steps, saver=None, base_step=0):
        times = []
        for i in range(1, n_steps + 1):
            t0 = time.perf_counter()
            jax.block_until_ready(step_fn(params, x))
            if saver is not None and (base_step + i) % every == 0:
                saver.save(base_step + i, {"params": params})
            times.append(time.perf_counter() - t0)
        return times

    # Interleaved A/B windows with a median-of-rounds overhead: the
    # twin loops share each round's load regime (shared CI hosts drift
    # over seconds — r05-style sequential twins read that drift as
    # checkpoint overhead), and the median across rounds drops the odd
    # external spike while keeping the persist-thread contention that
    # IS real overhead inside each ckpt window.
    window = max(every * 2, 8)
    rounds = max(3, steps // window)
    root = tempfile.mkdtemp(prefix="hvd-bench-ckpt-")
    try:
        saver = ckpt_mod.AsyncCheckpointer(root, keep=2)
        run(window)                      # warm plain
        run(window, saver, base_step=0)  # warm ckpt (first commit incl.)
        saver.wait(60)
        plain, ckptd, per_round = [], [], []
        base = window
        for _ in range(rounds):
            p = run(window)
            c = run(window, saver, base_step=base)
            base += window
            plain.extend(p)
            ckptd.extend(c)
            per_round.append((sum(c) - sum(p)) / sum(p))
        saver.wait(60)
        # Overhead from 10%-trimmed per-step means, not round sums: a
        # shared host's scheduler spikes land on single steps, and a
        # ratio of 8-step window sums inherits them wholesale (±10-25%
        # per round measured on CI-class hosts). Trimming both arms
        # symmetrically drops the spikes while keeping what checkpoint
        # overhead actually looks like — a small shift across MANY
        # steps (snapshot on every save step, persist contention on
        # the steps behind it).
        overhead = max(0.0, (_trimmed_mean(ckptd) - _trimmed_mean(plain))
                       / _trimmed_mean(plain))
        steps = rounds * window
        return _ckpt_bench_result(
            on_cpu, saver, root, plain, ckptd, per_round, overhead,
            steps, every, rounds, window, params)
    finally:
        import shutil
        shutil.rmtree(root, ignore_errors=True)


def _trimmed_mean(xs, trim=0.1):
    xs = sorted(xs)
    k = int(len(xs) * trim)
    kept = xs[k:len(xs) - k] if len(xs) > 2 * k else xs
    return sum(kept) / len(kept)


def _ckpt_bench_result(on_cpu, saver, root, plain, ckptd, per_round,
                       overhead, steps, every, rounds, window, params):
    import statistics

    from horovod_tpu.ckpt import manifest as ckpt_mf

    payload_bytes = sum(int(np.asarray(v).nbytes)
                        for v in jax.tree_util.tree_leaves(params))
    committed = ckpt_mf.committed(root)
    phase = dict(saver.last_phase_seconds)
    persist_s = phase.get("persist", 0.0)
    save_idx = {i for i in range(len(ckptd))
                if (window + i + 1) % every == 0}
    save_steps = [t for i, t in enumerate(ckptd) if i in save_idx]
    other_steps = [t for i, t in enumerate(ckptd) if i not in save_idx]
    t_plain, t_ckpt = sum(plain), sum(ckptd)
    out = {
        "platform": "cpu" if on_cpu else jax.devices()[0].platform,
        "steps": steps,
        "save_every": every,
        "rounds": rounds,
        "plain_step_ms": round(1e3 * t_plain / steps, 3),
        "ckpt_step_ms": round(1e3 * t_ckpt / steps, 3),
        "overhead_fraction": round(overhead, 4),
        "overhead_per_round": [round(x, 4) for x in per_round],
        "snapshot_ms": round(1e3 * phase.get("snapshot", 0.0), 3),
        "persist_ms": round(1e3 * persist_s, 3),
        "commit_ms": round(1e3 * phase.get("commit", 0.0), 3),
        "bytes": payload_bytes,
        "bytes_per_sec": round(payload_bytes / persist_s, 1)
        if persist_s > 0 else None,
        "generations_committed": saver.last_committed[0]
        if saver.last_committed else 0,
        "generations_retained": len(committed),
        "skipped_saves": saver.skipped,
        # worst save-step excess over the non-save median: the async
        # contract says this should be ~ the snapshot phase, never the
        # persist time
        "max_save_step_excess_ms": round(
            1e3 * (max(save_steps) - statistics.median(other_steps)), 3)
        if save_steps and other_steps else None,
    }
    saver.close()
    return out


_SECTION_ERRORS = {}


def _provenance_meta():
    """Round provenance stamp (perfboard.provenance_meta), tolerant:
    a broken stamp must never cost the round its bench evidence —
    especially not on the fatal emit path."""
    try:
        from horovod_tpu.observability.perfboard import provenance_meta
        return provenance_meta(os.path.dirname(os.path.abspath(__file__)))
    except Exception as e:
        return {"meta_error": _err_str(e)}


def _err_str(e):
    head = str(e).splitlines()[0][:300] if str(e) else ""
    return f"{type(e).__name__}: {head}" if head else type(e).__name__


def _is_deterministic(e):
    """OOM and friends will fail identically on retry — don't waste the
    wall-clock re-running a 30-step bench into the same wall."""
    s = str(e)
    return "RESOURCE_EXHAUSTED" in s or "Out of memory" in s or "OOM" in s


def _section(name, fn, *args, retries=1, **kwargs):
    """Run one bench section, isolated: any failure is recorded in
    _SECTION_ERRORS instead of killing the whole run.

    Retries ride the resilience layer's RetryPolicy (PR 1,
    common/resilience.py): jittered backoff between attempts, a per-
    section deadline so one wedged section can't eat the whole bench budget,
    and HOROVOD_BENCH_RETRY_* env overrides. Deterministic failures
    (OOM) are not retryable — re-running a 30-step bench into the same
    wall wastes wall-clock.
    """
    import dataclasses

    from horovod_tpu.common.resilience import RetryError, RetryPolicy

    policy = dataclasses.replace(
        RetryPolicy.from_env(
            "HOROVOD_BENCH_RETRY", base_delay=2.0, max_delay=10.0,
            jitter=0.25, deadline=600.0, name="bench_section"),
        max_attempts=retries + 1,
        retryable=lambda e: not _is_deterministic(e))

    def on_retry(attempt, exc, delay):
        print(f"[bench] section {name!r} attempt {attempt} failed: "
              f"{_err_str(exc)}; retrying in {delay:.1f}s", flush=True)

    try:
        return policy.call(fn, *args, on_retry=on_retry, **kwargs)
    except RetryError as e:
        last = e.__cause__ or e
    except Exception as e:
        last = e
    print(f"[bench] section {name!r} failed: {_err_str(last)}", flush=True)
    _SECTION_ERRORS[name] = _err_str(last)
    return None


def main():
    hvd.init()
    mesh = topology.mesh()
    k = hvd.size()
    on_cpu = jax.devices()[0].platform == "cpu"
    peak = peak_flops_per_chip()

    def set_mfu(r, rate_key, flops_key):
        rate, fl = r[rate_key], r[flops_key]
        if peak and fl:
            r["mfu"] = round(rate * fl / peak, 4)

    # --- ResNet-50: per-chip batch sweep, report the best ---
    # Each sweep point is individually guarded: one OOM must not cost
    # the headline number.
    batches = (8,) if on_cpu else (64, 128, 256, 512)
    steps, warmup = (3, 1) if on_cpu else (30, 5)
    sweep = {}
    best = None
    for b in batches:
        r = _section(f"resnet_b{b}", bench_resnet, mesh, k, on_cpu, b,
                     steps, warmup)
        if r is None:
            sweep[f"batch_{b}"] = None
            continue
        sweep[f"batch_{b}"] = r["images_per_sec_per_chip"]
        if best is None or r["images_per_sec_per_chip"] > \
                best["images_per_sec_per_chip"]:
            best = r
    if best is not None:
        set_mfu(best, "images_per_sec_per_chip", "model_flops_per_image")
        best["batch_sweep"] = sweep

    # --- Transformer LM ---
    t_steps, t_warmup = (2, 1) if on_cpu else (20, 3)
    tr = _section("transformer_lm", bench_transformer, on_cpu,
                  t_steps, t_warmup)
    if tr is not None:
        set_mfu(tr, "tokens_per_sec_per_chip", "model_flops_per_token")

    incep = _section("inception_v3", bench_inception, mesh, k, on_cpu)
    if incep is not None and incep.get("model_flops_per_image"):
        set_mfu(incep, "images_per_sec_per_chip", "model_flops_per_image")
    # ResNet-101: the ONLY model the reference publishes an absolute
    # number for (1656.8 img/s on 16 GPUs, docs/benchmarks.rst:40-42) —
    # this section makes vs_baseline like-for-like. TPU-only (the model
    # has CPU coverage via examples/synthetic_benchmark.py).
    rn101 = None if on_cpu else _section(
        "resnet101", bench_resnet, mesh, k, on_cpu, 64, steps, warmup,
        depth=101)
    if rn101 is not None:
        set_mfu(rn101, "images_per_sec_per_chip", "model_flops_per_image")
        rn101["vs_baseline_like_for_like"] = round(
            rn101["images_per_sec_per_chip"] / BASELINE_PER_CHIP, 3)
    # VGG-16 is ~20 s/step on the emulated-CPU mesh — TPU runs only
    vgg16 = None if on_cpu else _section("vgg16", bench_vgg16, mesh, k)
    if vgg16 is not None:
        set_mfu(vgg16, "images_per_sec_per_chip",
                 "model_flops_per_image")
    bert = _section("bert_adasum", bench_bert_adasum, on_cpu)
    # fusion sweep + autotune ride the CPU-mesh subprocess (they never
    # touch the TPU; see bench_eager_cpu_mesh)
    eager = _section("eager_cpu_mesh", bench_eager_cpu_mesh)
    fusion = eager.get("fusion_sweep") if eager else None
    autotune = eager.get("autotune") if eager else None
    lm_overlap = eager.get("lm_overlap") if eager else None
    if lm_overlap is not None:
        lm_overlap["platform"] = eager["platform"]
    if fusion is not None:
        fusion["platform"] = eager["platform"]
        fusion["workload"] = eager["workload"]
    if autotune is not None:
        autotune["platform"] = eager["platform"]
    # GSPMD hybrid-parallel scaling section (docs/parallelism.md): DP
    # vs tp=4 x dp=2 on the 8-device CPU-mesh subprocess — it never
    # touches the TPU.
    gspmd = _section("gspmd_hybrid", bench_gspmd_hybrid)
    flash = None if on_cpu else _section(
        "flash_attention", bench_flash_attention)
    # Serving tier (docs/serving.md): loopback replica pool under paced
    # load. Control-plane + batching + one AOT device step per batch —
    # the number is dominated by the service, not the device.
    serving = _section("serving", bench_serving, on_cpu)
    # Async checkpointing overhead (docs/checkpointing.md): twin-loop
    # measurement; perf_gate structurally requires the stamp and fails
    # overhead_fraction > 5% (ROADMAP item 5 acceptance).
    checkpointing = _section("checkpointing", bench_checkpointing,
                             on_cpu)

    per_chip_ips = best["images_per_sec_per_chip"] if best else None
    print(json.dumps({
        "metric": "resnet50_synthetic_images_per_sec_per_chip",
        "value": per_chip_ips if per_chip_ips is not None else 0.0,
        "unit": "images/sec/chip",
        "vs_baseline": round(per_chip_ips / BASELINE_PER_CHIP, 3)
        if per_chip_ips else 0.0,
        # Provenance (git sha, UTC date, effective HOROVOD_* knob
        # fingerprint, device platform/count) — what lets perfboard
        # tell config drift from code regression across rounds.
        "meta": _provenance_meta(),
        "extra": {
            "peak_tflops_per_chip": peak / 1e12 if peak else None,
            "device": jax.devices()[0].device_kind,
            "num_chips": k,
            "timing_method": "host clock behind block_until_ready "
                             "(see _scan_timed)",
            "resnet50": best,
            "resnet101": rn101,
            "inception_v3": incep,
            "vgg16": vgg16,
            "transformer_lm": tr,
            "bert_base_finetune": bert,
            "fusion_sweep_grouped_allreduce": fusion,
            "gspmd_hybrid": gspmd,
            "lm_overlap_train_step": lm_overlap,
            "autotune": autotune,
            "flash_attention_s8192": flash,
            "serving": serving,
            "checkpointing": checkpointing,
            "section_errors": _SECTION_ERRORS or None,
        },
    }), flush=True)


if __name__ == "__main__":
    import sys as _sys
    if "--eager-cpu-mesh" in _sys.argv:
        _eager_cpu_mesh_child()
        raise SystemExit(0)
    if "--gspmd-cpu-mesh" in _sys.argv:
        _gspmd_cpu_mesh_child()
        raise SystemExit(0)
    try:
        main()
    except Exception as e:
        # A fatal error still leaves a parseable line naming it
        # (extra.fatal) — and a non-zero exit code, so no caller can
        # mistake the run for a result.
        print(json.dumps({
            "metric": "resnet50_synthetic_images_per_sec_per_chip",
            "value": 0.0, "unit": "images/sec/chip", "vs_baseline": 0.0,
            "meta": _provenance_meta(),
            "extra": {"fatal": _err_str(e),
                      "section_errors": _SECTION_ERRORS or None},
        }), flush=True)
        raise SystemExit(1)
