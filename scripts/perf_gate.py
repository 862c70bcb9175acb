"""perf_gate: the CI perf-regression sentinel (docs/perf.md).

Compares perfscope ``StepProfile`` records (profiler/perfscope.py)
against a checked-in, noise-tolerant baseline
(``scripts/perf_baseline.json``):

* **structure assertions** always run — every baseline section must be
  present, have recorded steps, a positive mean wall time, a phase
  breakdown whose phases cover >=90% of the wall (the perfscope
  invariant), the phases the section is expected to exhibit, and an
  ``mfu_source`` from the allowed set. These hold on any host, so CI's
  CPU runners gate them on every PR.
* **numeric assertions** (mean step time within a relative tolerance
  band) run only when explicitly armed — ``--numeric`` or
  ``HOROVOD_PERF_GATE_NUMERIC=1`` — because absolute step times on a
  shared CPU runner are noise. Arm them on dedicated perf hosts.

Usage::

    python scripts/perf_gate.py --run --baseline scripts/perf_baseline.json
    python scripts/perf_gate.py --emit /tmp/cur.json
    python scripts/perf_gate.py /tmp/cur.json --baseline scripts/perf_baseline.json
    python scripts/perf_gate.py --run --baseline scripts/perf_baseline.json --update
    python scripts/perf_gate.py /tmp/bench.json --bench   # a saved bench.py JSON line

``--emit`` runs two small synthetic workloads under perfscope on the CPU
backend (seconds of wall clock): an eager-``DistributedOptimizer`` MLP
step (exercises the auto-hooked ``comms``/``optimizer``/``compile``
phases plus user-marked ``input_wait``/``device_compute``) and a jitted
matmul scan with XLA cost-analysis FLOPs (``mfu_source == "xla"``).
``--bench`` instead treats the input as a ``bench.py`` JSON line and
structure-checks every section that carries a ``perfscope`` stamp.

Exit codes: 0 gate passed, 1 regression/structure failure, 2 usage/IO.
"""

import argparse
import json
import os
import sys
import tempfile

# Standalone invocation (CI, `make perf-gate`): the repo root is the
# import root for horovod_tpu.
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

#: Phase-coverage floor: the perfscope switching-timer invariant makes
#: phases sum to wall; anything below this means attribution broke.
MIN_COVERAGE = 0.9

DEFAULT_TOLERANCE = 1.0  # +-100% band when numeric checks are armed

#: Conv fast path structural contract (docs/perf.md): every conv bench
#: section must stamp the layout it ran under and the
#: device-double-buffered input pipeline, so a regression to the
#: unpadded/synchronous path fails the gate STRUCTURALLY — on any
#: host — not just numerically on a perf host.
CONV_SECTIONS = ("resnet50", "resnet101", "inception_v3", "vgg16")
#: Sections whose declared conv stack the layout pass pads (ResNet's
#: stage-0 width-64 edges); "as_declared" there means the pass is off.
PADDED_SECTIONS = ("resnet50", "resnet101")
#: Acceptance bar for the device-resident feed: measured input_wait
#: must stay under 5% of the step wall.
MAX_INPUT_WAIT_FRACTION = 0.05

#: GSPMD hybrid-parallel structural contract (docs/parallelism.md):
#: every sharded bench section must stamp the mesh it ran on, the
#: scaling comparison against its DP baseline, and the per-axis comms
#: split — the hybrid analog of the conv sections' layout/
#: input_pipeline stamps, so a regression that silently drops the
#: hybrid path (or its attribution) fails the gate on any host.
SHARDED_SECTIONS = ("gspmd_hybrid",)

#: The async-checkpointing bench section (docs/checkpointing.md) and
#: its hard acceptance: measured overhead above this fraction of step
#: time fails the gate (ROADMAP item 5: "checkpoint overhead <5% of
#: step time").
CKPT_SECTION = "checkpointing"
CKPT_MAX_OVERHEAD = 0.05

#: The serving bench section (docs/serving.md) and its hvdtrace
#: structural contract (docs/observability.md): the loopback bench
#: traces its own request path end to end and stamps the joined
#: evidence — a serving number whose slowest request cannot be split
#: into queue/dispatch/device time is unattributable.
SERVE_SECTION = "serving"


# ----------------------------------------------------------------- emit

def _force_cpu():
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=2").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    return jax


def emit_profiles() -> dict:
    """Run the synthetic workloads and return the current-profiles doc."""
    jax = _force_cpu()
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.profiler import flops as F
    from horovod_tpu.profiler import perfscope as P

    hvd.init()
    sections = {}

    def watch_stamp():
        """Run one hvdwatch detection pass over the section's samples
        and stamp its cumulative anomaly counts — the gate's zero-
        anomalies-on-clean-runs assertion needs the detectors to have
        actually LOOKED at this run."""
        from horovod_tpu.observability import watch
        watch.get().tick()
        counts = watch.get().counts()
        return {"anomalies_total": sum(counts.values()),
                "by_detector": dict(counts)}

    # --- eager MLP through DistributedOptimizer (the auto-hooked path)
    rng = np.random.default_rng(0)
    D, B = 64, 32
    w = {"w1": jnp.asarray(rng.standard_normal((D, D)) * 0.1, jnp.float32),
         "w2": jnp.asarray(rng.standard_normal((D, D)) * 0.1, jnp.float32)}

    def loss(p, batch):
        x, y = batch
        h = jnp.tanh(x @ p["w1"])
        return jnp.mean((h @ p["w2"] - y) ** 2)

    grad_fn = jax.jit(jax.value_and_grad(loss))
    opt = hvd.DistributedOptimizer(optax.adam(1e-3))
    state = opt.init(w)
    batch0 = (jnp.asarray(rng.standard_normal((B, D)), jnp.float32),
              jnp.asarray(rng.standard_normal((B, D)), jnp.float32))
    ps = P.get()
    ps.reset()
    xla = F.jit_cost_flops(grad_fn, w, batch0) \
        if F.xla_flops_enabled() else None
    # Analytic fwd+bwd fallback for the 2-matmul MLP (mul+add counted).
    ps.set_model_flops(*F.pick_flops(xla, 6.0 * 2 * D * D * B))
    for i in range(8):
        with ps.step():
            with ps.phase("input_wait"):
                batch = batch0  # synthetic input: the marker is the point
            l, g = grad_fn(w, batch)
            w, state = opt.step(g, w, state)
            with ps.phase("device_compute"):
                jax.block_until_ready(l)
    sections["eager_mlp"] = ps.step_profile("eager_mlp",
                                            hvdwatch=watch_stamp())

    # --- jitted matmul scan with XLA-derived FLOPs
    m = jnp.asarray(rng.standard_normal((128, 128)) * 0.05, jnp.float32)
    body = jax.jit(lambda s: jnp.tanh(s @ m))
    ps.reset()
    xla = F.jit_cost_flops(body, m) if F.xla_flops_enabled() else None
    ps.set_model_flops(*F.pick_flops(xla, 2.0 * 128 ** 3))
    s = m
    for _ in range(8):
        with ps.step():
            s = body(s)
            with ps.phase("device_compute"):
                jax.block_until_ready(s)
    sections["scan_matmul"] = ps.step_profile("scan_matmul",
                                              hvdwatch=watch_stamp())

    return {"perf_gate": 1,
            "platform": jax.devices()[0].platform,
            "sections": sections}


# ---------------------------------------------------------------- check

def _check_profile(name: str, prof: dict, spec: dict,
                   numeric: bool) -> list:
    errs = []
    if not prof:
        return [f"{name}: missing StepProfile"]
    if not prof.get("steps"):
        errs.append(f"{name}: no steps recorded")
    wall = prof.get("wall") or {}
    mean = wall.get("mean_s")
    if not mean or mean <= 0:
        errs.append(f"{name}: non-positive mean step time")
    for k in ("p50_s", "p95_s", "max_s"):
        if wall.get(k) is None:
            errs.append(f"{name}: wall.{k} missing")
    phases = prof.get("phases_s") or {}
    if not phases:
        errs.append(f"{name}: empty phase breakdown")
    cov = prof.get("coverage")
    if cov is None or cov < MIN_COVERAGE:
        errs.append(f"{name}: phase coverage {cov} < {MIN_COVERAGE} "
                    f"(phases must sum to >=90% of wall step time)")
    for ph in spec.get("require_phases", []):
        if ph not in phases:
            errs.append(f"{name}: required phase {ph!r} absent "
                        f"(got {sorted(phases)})")
    allowed = spec.get("mfu_source")
    if allowed and prof.get("mfu_source") not in allowed:
        errs.append(f"{name}: mfu_source {prof.get('mfu_source')!r} "
                    f"not in {allowed}")
    errs.extend(_check_watch(name, prof.get("hvdwatch")))
    base_mean = spec.get("wall_mean_s")
    if numeric and base_mean:
        tol = float(spec.get("tolerance", DEFAULT_TOLERANCE))
        lo, hi = base_mean / (1.0 + tol), base_mean * (1.0 + tol)
        if not (lo <= mean <= hi):
            errs.append(
                f"{name}: mean step {mean * 1e3:.2f} ms outside "
                f"[{lo * 1e3:.2f}, {hi * 1e3:.2f}] ms "
                f"(baseline {base_mean * 1e3:.2f} ms, tol {tol})")
    return errs


def _check_watch(name: str, block) -> list:
    """A clean run must record ZERO hvdwatch anomalies: a bench number
    measured while a detector was firing (input starvation, overlap
    collapse, a step-time shift) is not a baseline, it is an incident.
    Structural — runs wherever the gate runs, no numerics involved."""
    if block is None:
        return []  # section ran without the watch stamp (older doc)
    if not isinstance(block, dict):
        return [f"{name}: hvdwatch block is not a dict"]
    n = block.get("anomalies_total")
    if n is None:
        return [f"{name}: hvdwatch block missing anomalies_total"]
    if n:
        return [f"{name}: {n} hvdwatch anomaly(ies) during the run "
                f"({block.get('by_detector')}) — a clean run must "
                f"record zero"]
    return []


def compare(current: dict, baseline: dict, numeric: bool) -> list:
    errs = []
    sections = current.get("sections") or {}
    for name, spec in (baseline.get("sections") or {}).items():
        errs.extend(_check_profile(name, sections.get(name) or {},
                                   spec, numeric))
    return errs


def _check_conv_section(name: str, val: dict) -> list:
    """The conv-fast-path structural stamps (docs/perf.md): layout mode
    (ResNet sections must be lane-padded), the device-double-buffered
    input pipeline, measured input_wait under the 5% bar, and — when
    the chip peak was known — an actual MFU number."""
    errs = []
    lay = val.get("layout")
    if not isinstance(lay, dict) or "mode" not in lay:
        errs.append(f"{name}: layout stamp missing — the conv section "
                    "no longer reports what layout it measured")
    elif name in PADDED_SECTIONS and lay.get("mode") != "nhwc_padded":
        errs.append(f"{name}: layout mode {lay.get('mode')!r} != "
                    "'nhwc_padded' — the lane-padding pass is off "
                    "(HOROVOD_LAYOUT_PAD=0 or a plan() regression)")
    pipe = val.get("input_pipeline")
    if not isinstance(pipe, dict) or \
            pipe.get("mode") != "device_double_buffered":
        errs.append(f"{name}: input_pipeline "
                    f"{(pipe or {}).get('mode')!r} != "
                    "'device_double_buffered' — the section regressed "
                    "to the synchronous host feed")
    prof = val.get("perfscope")
    if isinstance(prof, dict) and prof.get("steps"):
        frac = (prof.get("phase_fractions") or {}).get("input_wait")
        if frac is not None and frac > MAX_INPUT_WAIT_FRACTION:
            errs.append(
                f"{name}: input_wait is {frac:.1%} of the step wall "
                f"(> {MAX_INPUT_WAIT_FRACTION:.0%}) — the feed is "
                "starving the step")
        if prof.get("peak_flops_per_chip") and prof.get("mfu") is None:
            errs.append(f"{name}: mfu missing from the StepProfile "
                        "despite a known chip peak — the conv MFU "
                        "acceptance number is gone")
    return errs


def _check_memory(name: str, val: dict) -> list:
    """The per-section `memory` stamp (docs/perf.md): every section
    whose XLA cost analysis ran (mfu_source == "xla" means the compile
    the stamp rides on happened) must carry the static per-device
    peak-HBM estimate, and an estimate over the chip budget fails the
    gate — the compile-time OOM sentinel (HVD303's bench face)."""
    errs = []
    mem = val.get("memory")
    prof = val.get("perfscope") or {}
    if not isinstance(mem, dict) or not mem:
        if prof.get("mfu_source") == "xla":
            errs.append(
                f"{name}: memory stamp missing despite a compiled "
                "program (mfu_source=xla) — the static peak-HBM "
                "estimate is gone (analysis/shard.py)")
        return errs
    static = mem.get("static_peak_device_bytes")
    if not isinstance(static, (int, float)) or static <= 0:
        errs.append(f"{name}: memory stamp carries no positive "
                    "static_peak_device_bytes")
        return errs
    budget = mem.get("hbm_budget_bytes")
    if budget and static > budget:
        errs.append(
            f"{name}: static per-device peak-HBM estimate "
            f"{static / 2**20:.1f} MB exceeds the chip budget "
            f"{budget / 2**20:.1f} MB — this section OOMs on the "
            "target chip (shrink the batch, donate inputs, or shard)")
    return errs


def _check_sharded_section(name: str, val: dict) -> list:
    """The mesh/scaling/comms stamps a GSPMD hybrid section must carry
    (docs/parallelism.md): mesh spec+shape (which 2-D config ran),
    scaling efficiency vs the DP baseline with both throughputs, and
    the per-axis comms-bytes split of the compiled program."""
    errs = []
    mesh = val.get("mesh")
    if not isinstance(mesh, dict) or not mesh.get("spec") \
            or not isinstance(mesh.get("shape"), dict):
        errs.append(f"{name}: mesh stamp missing/incomplete — the "
                    "sharded section no longer reports which mesh "
                    "config it measured (need spec + shape)")
    elif not mesh.get("devices"):
        errs.append(f"{name}: mesh stamp carries no device count")
    sc = val.get("scaling")
    if not isinstance(sc, dict):
        errs.append(f"{name}: scaling stamp missing — scaling "
                    "efficiency has nowhere to land")
    else:
        for k in ("efficiency_vs_dp", "dp_tokens_per_sec",
                  "hybrid_tokens_per_sec"):
            v = sc.get(k)
            if not isinstance(v, (int, float)) or v <= 0:
                errs.append(f"{name}: scaling.{k} missing or "
                            "non-positive")
    comms = val.get("comms_by_axis")
    if not isinstance(comms, dict) or not comms:
        errs.append(f"{name}: comms_by_axis stamp missing/empty — the "
                    "per-axis (dp/tp) wire-traffic split is gone "
                    "(analysis/shard.comms_by_axis)")
    else:
        for label, ent in comms.items():
            if not isinstance(ent, dict) or \
                    not isinstance(ent.get("bytes_per_step"),
                                   (int, float)):
                errs.append(f"{name}: comms_by_axis[{label!r}] carries "
                            "no bytes_per_step")
    cm = val.get("comms_model")
    if not isinstance(cm, dict):
        errs.append(f"{name}: comms_model stamp missing — the analytic "
                    "ICI/DCN prediction no longer rides beside the "
                    "measured comms_by_axis "
                    "(analysis/schedule.comms_model)")
    else:
        per = cm.get("per_axis")
        if not isinstance(per, dict) or not per:
            errs.append(f"{name}: comms_model.per_axis missing/empty — "
                        "no per-axis predicted bytes/time")
        else:
            for label, ent in per.items():
                if not isinstance(ent, dict) or not isinstance(
                        ent.get("wire_bytes_per_step"), (int, float)):
                    errs.append(f"{name}: comms_model.per_axis"
                                f"[{label!r}] carries no "
                                "wire_bytes_per_step")
        ratio = cm.get("predicted_vs_measured")
        if not isinstance(ratio, (int, float)):
            errs.append(f"{name}: comms_model.predicted_vs_measured "
                        "missing/non-numeric — the model can no "
                        "longer be tracked against measurement")
        elif not (0.5 <= ratio <= 2.0):
            errs.append(
                f"{name}: comms_model predicted-vs-measured bytes "
                f"ratio {ratio} outside [0.5, 2.0] — the analytic "
                "model and the measured comms_by_axis disagree on "
                "what the program moves (wire-factor regression or a "
                "group-classification split)")
    num = val.get("numerics")
    if not isinstance(num, dict):
        errs.append(f"{name}: numerics stamp missing — accumulation "
                    "dtypes and the gradient-scale table no longer "
                    "ride beside the comms stamps "
                    "(analysis/numerics.stamp)")
    else:
        if not isinstance(num.get("accum_dtypes"), list) \
                or not num["accum_dtypes"]:
            errs.append(f"{name}: numerics.accum_dtypes missing/empty "
                        "— the compiled step reports no accumulation "
                        "precision")
        gs = num.get("grad_scale")
        if not isinstance(gs, list) or not gs:
            errs.append(f"{name}: numerics.grad_scale missing/empty — "
                        "the gradient reductions lost their scale "
                        "table (sum-vs-mean drift is now invisible)")
        else:
            for i, ent in enumerate(gs):
                if not isinstance(ent, dict) or not isinstance(
                        ent.get("group_size"), int):
                    errs.append(f"{name}: numerics.grad_scale[{i}] "
                                "carries no group_size")
        if not isinstance(num.get("findings"), int):
            errs.append(f"{name}: numerics.findings missing — the "
                        "HVD5xx finding count can no longer be "
                        "tracked across rounds")
    return errs


def _check_ckpt_section(name: str, val: dict) -> list:
    """The stamps an async-checkpointing section must carry, and the
    one NUMERIC check that runs on every host (a ratio of twin loops
    in the same window is load-immune enough to gate everywhere):
    overhead_fraction <= CKPT_MAX_OVERHEAD."""
    errs = []
    for k in ("overhead_fraction", "snapshot_ms", "persist_ms",
              "plain_step_ms", "ckpt_step_ms", "bytes",
              "generations_committed", "save_every"):
        if not isinstance(val.get(k), (int, float)):
            errs.append(f"{name}: stamp `{k}` missing/non-numeric — "
                        "the two-phase save split is no longer "
                        "measured (docs/checkpointing.md)")
    if not isinstance(val.get("skipped_saves"), int):
        errs.append(f"{name}: skipped_saves missing — back-pressure "
                    "drops are no longer counted")
    gens = val.get("generations_committed")
    if isinstance(gens, (int, float)) and gens <= 0:
        errs.append(f"{name}: no generation committed — the save path "
                    "never reached a commit marker")
    ov = val.get("overhead_fraction")
    if isinstance(ov, (int, float)) and ov > CKPT_MAX_OVERHEAD:
        errs.append(
            f"{name}: measured checkpoint overhead {ov:.1%} exceeds "
            f"the {CKPT_MAX_OVERHEAD:.0%} budget (ROADMAP item 5 "
            "acceptance) — the async save is leaking onto the step "
            "critical path")
    return errs


def _check_serving_section(name: str, val: dict) -> list:
    """The hvdtrace stamp a serving section must carry
    (docs/observability.md): the bench forces the tracer on for its
    loopback run, joins the spans with the doctor's analyzer, and
    stamps the slowest request's queue/dispatch/device split. All
    structural — runs on any host, no numerics involved."""
    errs = []
    tr = val.get("trace")
    if not isinstance(tr, dict):
        errs.append(f"{name}: trace stamp missing — the serving bench "
                    "no longer carries hvdtrace evidence "
                    "(observability/tracing.py)")
        return errs
    if not isinstance(tr.get("version"), int):
        errs.append(f"{name}: trace.version missing/non-int — the "
                    "stamp cannot be version-gated")
    sampled = tr.get("sampled")
    if not isinstance(sampled, (int, float)) or sampled < 1:
        errs.append(f"{name}: trace.sampled missing or < 1 — the "
                    "tracer saw none of the bench's requests")
    slow = tr.get("slowest")
    if not isinstance(slow, dict):
        errs.append(f"{name}: trace.slowest missing — no request "
                    "trace survived to attribute the tail latency")
    else:
        for k in ("total_ms", "queue_ms", "dispatch_ms", "device_ms"):
            if not isinstance(slow.get(k), (int, float)):
                errs.append(f"{name}: trace.slowest.{k} missing/"
                            "non-numeric — the queue/dispatch/device "
                            "split is incomplete")
    return errs


def check_bench(doc: dict) -> list:
    """Structure-check every perfscope-stamped section of a bench.py
    JSON line (the StepProfile acceptance: phases cover >=90% of wall),
    plus the conv sections' fast-path stamps and the per-section
    memory stamps. Self-contained — no baseline involved."""
    extra = doc.get("extra") or {}
    errs = []
    found = 0
    for sec, val in sorted(extra.items()):
        if not isinstance(val, dict):
            continue
        if sec in CONV_SECTIONS:
            errs.extend(_check_conv_section(sec, val))
        if sec in SHARDED_SECTIONS:
            errs.extend(_check_sharded_section(sec, val))
        if sec == CKPT_SECTION:
            errs.extend(_check_ckpt_section(sec, val))
        if sec == SERVE_SECTION:
            errs.extend(_check_serving_section(sec, val))
        if "perfscope" not in val:
            continue
        prof = val["perfscope"]
        if not isinstance(prof, dict) or not prof.get("steps"):
            continue  # section ran without perfscope (env-disabled)
        found += 1
        errs.extend(_check_profile(
            sec, prof,
            {"mfu_source": ["xla", "fallback", "none"]}, numeric=False))
        errs.extend(_check_watch(sec, val.get("hvdwatch")))
        errs.extend(_check_memory(sec, val))
    if not found:
        errs.append("bench JSON carries no perfscope StepProfile "
                    "(HOROVOD_PERFSCOPE=0 on the bench run?)")
    # Presence is part of the sharded structural contract: a crashed /
    # deleted gspmd section would otherwise skip every stamp check and
    # silently drop the hybrid path from the record.
    for sec in SHARDED_SECTIONS:
        if not isinstance(extra.get(sec), dict):
            errs.append(
                f"{sec}: sharded bench section missing — the hybrid "
                "path did not run (or was dropped); its mesh/scaling/"
                "comms_by_axis stamps are structurally required "
                "(docs/parallelism.md)")
    if not isinstance(extra.get(CKPT_SECTION), dict):
        errs.append(
            f"{CKPT_SECTION}: checkpointing bench section missing — "
            "the async-save overhead is no longer measured; its "
            "overhead/phase-split stamps are structurally required "
            "(docs/checkpointing.md)")
    if not isinstance(extra.get(SERVE_SECTION), dict):
        errs.append(
            f"{SERVE_SECTION}: serving bench section missing — the "
            "serving tier was not measured (or was dropped); its "
            "hvdtrace `trace` stamp is structurally required "
            "(docs/observability.md)")
    return errs


def update_errors(current: dict) -> list:
    """Why `--update` must refuse to turn `current` into the baseline.

    A broken run must not silently become the new reference: a section
    whose phase coverage is below MIN_COVERAGE recorded broken
    attribution, and one whose ``mfu_source`` is a fallback recorded a
    run where the XLA cost analysis never fired — baselining either
    would teach the gate to accept exactly the failure it exists to
    catch."""
    sections = current.get("sections") or {}
    errs = []
    if not sections:
        errs.append("no sections in the current profiles")
    for name, prof in sorted(sections.items()):
        cov = (prof or {}).get("coverage")
        if cov is None or cov < MIN_COVERAGE:
            errs.append(f"{name}: coverage {cov} < {MIN_COVERAGE} — "
                        "phase attribution is broken in this run")
        src = (prof or {}).get("mfu_source")
        if src != "xla":
            errs.append(f"{name}: mfu_source {src!r} is a fallback — "
                        "the XLA cost analysis did not run")
    return errs


def round_profiles(path: str):
    """(current-profiles doc, refusal reasons) from a checked-in
    BENCH_rXX.json trajectory round — the `--update --from-round`
    source. The baseline regenerates from a *blessed* round the whole
    team can see in the trajectory, not from whatever the last local
    run produced; perfboard refuses rounds it flags as regressed,
    anomalous (hvdwatch fired during the run), failed, or truncated."""
    from horovod_tpu.observability.perfboard import (load_bench_round,
                                                     round_blessable)
    reasons = round_blessable(path)
    if reasons:
        return None, reasons
    rnd = load_bench_round(path)
    sections = {}
    for name, sec in sorted(rnd.sections.items()):
        prof = sec.get("perfscope") if isinstance(sec, dict) else None
        if isinstance(prof, dict) and prof.get("phases_s"):
            sections[name] = prof
    if not sections:
        return None, [f"round {rnd.label} carries no perfscope stamps"]
    return {"platform": rnd.platform(), "sections": sections}, []


def baseline_from(current: dict) -> dict:
    """Derive a fresh baseline doc from a current-profiles doc
    (numeric gating stays opt-in; reference numbers are informational
    until a host arms --numeric)."""
    sections = {}
    for name, prof in (current.get("sections") or {}).items():
        phases = sorted((prof.get("phases_s") or {}).keys())
        sections[name] = {
            "require_phases": phases,
            "mfu_source": ["xla", "fallback"],
            "wall_mean_s": (prof.get("wall") or {}).get("mean_s"),
            "tolerance": DEFAULT_TOLERANCE,
        }
    return {"perf_gate": 1,
            "platform": current.get("platform"),
            "note": "structure assertions always run; numeric "
                    "tolerances only under --numeric / "
                    "HOROVOD_PERF_GATE_NUMERIC=1 (CPU CI hosts are "
                    "noise)",
            "sections": sections}


# ------------------------------------------------------------------ cli

def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python scripts/perf_gate.py",
        description="perfscope StepProfile regression gate "
                    "(docs/perf.md)")
    p.add_argument("current", nargs="?", default="",
                   help="current-profiles JSON (from --emit) or, with "
                        "--bench, a bench.py JSON line file")
    p.add_argument("--baseline", default="",
                   help="checked-in baseline (scripts/perf_baseline.json)")
    p.add_argument("--emit", default="", metavar="PATH",
                   help="run the synthetic workloads and write the "
                        "current-profiles JSON here")
    p.add_argument("--run", action="store_true",
                   help="emit to a temp file and compare against "
                        "--baseline in one go (make perf-gate)")
    p.add_argument("--bench", action="store_true",
                   help="treat `current` as bench.py output and "
                        "structure-check its perfscope stamps")
    p.add_argument("--numeric", action="store_true",
                   help="arm the numeric tolerance checks "
                        "(HOROVOD_PERF_GATE_NUMERIC=1 equivalent)")
    p.add_argument("--update", action="store_true",
                   help="write --baseline from the current profiles "
                        "instead of gating")
    p.add_argument("--from-round", default="", metavar="BENCH_rXX.json",
                   help="with --update: regenerate the baseline from a "
                        "blessed trajectory round's perfscope stamps; "
                        "refuses rounds perfboard flags as regressed "
                        "or anomalous")
    args = p.parse_args(argv)
    from horovod_tpu.common.config import _env_bool
    numeric = args.numeric or _env_bool("HOROVOD_PERF_GATE_NUMERIC")

    temp_out = ""
    if args.from_round:
        if not args.update:
            print("perf_gate: --from-round only makes sense with "
                  "--update", file=sys.stderr)
            return 2
        current, reasons = round_profiles(args.from_round)
        if current is None:
            for r in reasons:
                print(f"perf_gate: FAIL {r}", file=sys.stderr)
            print(f"perf_gate: refusing to bless {args.from_round} as "
                  f"the numeric baseline ({len(reasons)} reason(s)); "
                  "land a clean round first", file=sys.stderr)
            return 1
    elif args.emit or args.run:
        current = emit_profiles()
        out = args.emit
        if not out:
            fd, out = tempfile.mkstemp(prefix="hvd_perf_", suffix=".json")
            os.close(fd)
            temp_out = out  # ours to clean up (kept only on failure)
        with open(out, "w") as f:
            json.dump(current, f, indent=2)
        print(f"perf_gate: wrote current profiles to {out}",
              file=sys.stderr)
        if not args.run and not args.update:
            return 0
    elif args.current:
        try:
            with open(args.current) as f:
                text = f.read()
        except OSError as e:
            print(f"perf_gate: cannot read {args.current}: {e}",
                  file=sys.stderr)
            return 2
        if args.bench:
            # Accept both shapes: the pretty-printed BENCH_rXX.json
            # artifact (one document) and raw bench stdout (log lines
            # around one compact JSON line — take the last such line).
            try:
                current = json.loads(text)
            except ValueError:
                lines = [ln for ln in text.splitlines()
                         if ln.strip().startswith("{")]
                current = None
                for ln in reversed(lines):
                    try:
                        current = json.loads(ln)
                        break
                    except ValueError:
                        continue
                if not isinstance(current, dict):
                    print("perf_gate: no JSON document in bench output",
                          file=sys.stderr)
                    return 2
        else:
            current = json.loads(text)
    else:
        p.print_help(sys.stderr)
        return 2

    if args.bench:
        # Bench mode is self-contained structure checking — no baseline.
        errs = check_bench(current)
        for e in errs:
            print(f"perf_gate: FAIL {e}", file=sys.stderr)
        print(f"perf_gate: {'%d failure(s)' % len(errs) if errs else 'OK'}"
              f" (bench StepProfile structure)", file=sys.stderr)
        return 1 if errs else 0

    if not args.baseline:
        print("perf_gate: --baseline is required to gate",
              file=sys.stderr)
        return 2

    if args.update:
        errs = update_errors(current)
        if errs:
            for e in errs:
                print(f"perf_gate: FAIL {e}", file=sys.stderr)
            print(f"perf_gate: refusing to regenerate {args.baseline} "
                  f"from a broken run ({len(errs)} failure(s)); fix the "
                  "run, don't lower the bar", file=sys.stderr)
            return 1
        doc = baseline_from(current)
        tmp = f"{args.baseline}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
        os.replace(tmp, args.baseline)
        print(f"perf_gate: baseline regenerated at {args.baseline} "
              f"(review the diff before committing)", file=sys.stderr)
        return 0

    try:
        with open(args.baseline) as f:
            baseline = json.load(f)
    except (OSError, ValueError) as e:
        print(f"perf_gate: unreadable baseline {args.baseline}: {e}",
              file=sys.stderr)
        return 2

    errs = compare(current, baseline, numeric)
    if errs:
        for e in errs:
            print(f"perf_gate: FAIL {e}", file=sys.stderr)
        print(f"perf_gate: {len(errs)} failure(s) vs {args.baseline}",
              file=sys.stderr)
        return 1  # temp profile kept for postmortem (path printed above)
    if temp_out:
        try:
            os.unlink(temp_out)
        except OSError:
            pass
    mode = "structure+numeric" if numeric else "structure-only"
    print(f"perf_gate: OK ({mode} vs {args.baseline})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
