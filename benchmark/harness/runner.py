"""Runs one cell: set-up, warm-up, the measured window, the checks, and the
metrics its `BENCHMARK.json` entries name.

The sequence is the same for every cell; what differs is found by name
(`spec.py`). With `trace=False` the window is `seconds` long, the profiler is
off and the end-to-end metrics are read. With `trace=True` an untraced
stretch of half that length gives the rate, then `trace_steps` steps (of the
traffic file) are traced with the benchmark's spans around its calls into
each layer, and the per-layer metrics are read.
"""

from __future__ import annotations

import contextlib
import glob
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass

import jax

from benchmark.harness import hlo, loop, peaks, report, spec, xplane

WARMUP_STEPS = 3
#: steps traced beyond the `trace_steps` that are measured: the first starts
#: on an idle device and the last is cut by the end of the trace (xplane.py)
TRACE_EDGE_STEPS = 2
TRACE_DIR = ".bench_trace"   # inside the checkout; listed in .gitignore


@dataclass
class Job:
    """What a path hands back: how to dispatch one step, and what to check."""
    step: object              # () -> loss (a device scalar), dispatched
    finish: object            # () -> None, blocks until all state is ready
    samples_per_step: int     # global: over all the cell's chips
    program: object = None    # the compiled step program (jax.stages.Compiled)
    compile_s: float = 0.0    # seconds spent compiling `program` ahead of time
    reference: object = None  # {"ok": bool, "detail": str} of the reference
    verify: object = None     # () -> [problem, ...] after the window


@dataclass
class Run:
    """Everything the metric readers may read."""
    cell: spec.Cell
    family: object
    chips: int
    peaks: object             # peaks.Peaks; None off the TPU
    samples_per_step: int
    setup_s: float
    init_s: float
    compile_s: float
    window: loop.Window       # the untraced window
    program: object
    instructions: dict        # hlo.index of the step program
    trace: object = None      # xplane.Trace of the traced steps


def say(msg: str) -> None:
    """A line of the run's log, on stderr, stamped with the host's clock so
    that the phases of set-up can be read off it."""
    print(f"[bench {time.perf_counter():.2f}] {msg}", file=sys.stderr,
          flush=True)


def program_bytes(compiled) -> int:
    """Device bytes one execution of a compiled program needs, per device:
    arguments + outputs + temporaries - what outputs share with arguments."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def memory_peak_bytes(run_program, devices) -> int:
    """The peak on the fullest chip. The runtime's own counter leaves out a
    program's temporaries on this runtime (PERF.md), so the larger of it and
    the step program's compiled footprint is reported."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    if run_program is not None:
        peak = max(peak, program_bytes(run_program))
    return peak


def _read_metrics(entries, kind: str, run: Run, dirs) -> dict:
    out = {}
    for entry in entries:
        reader = spec.load_module(kind, entry["name"], dirs)
        value = reader.read(run)
        if value is not None:
            out[entry["name"]] = (value, entry["unit"])
    return out


def _trace_steps(job: Job, n: int, trace_dir: str):
    """Trace `n` steps; returns (window, xplane.Trace)."""
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0   # the host's Python frames are not read
    options.enable_hlo_proto = False  # the program's text is read directly
    counter = iter(range(10**9))

    def traced_step():
        with jax.profiler.StepTraceAnnotation(xplane.STEP_SPAN,
                                              step_num=next(counter)):
            return job.step()

    def block(loss):
        with jax.profiler.TraceAnnotation("bench.block"):
            return float(loss)

    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        window = loop.run_steps(traced_step, loop.for_steps(n), block=block)
        job.finish()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {files}")
    return window, xplane.load(files[0])


def run_cell(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
             t0: float, platform: str = "tpu",
             checkout: str = spec.REPO) -> str:
    """Runs `cell` and returns the last line. `t0` is the host clock at
    process start. Any platform but the one asked for ends the run: the
    benchmark never falls back. (`platform` is "tpu" for every measurement;
    the tests rehearse the control flow on "cpu", and a line made there holds
    counts only.)"""
    clock = time.perf_counter
    log = loop.CompileLog()
    say(f"{cell.name}: process started at {t0:.2f}; imports done")
    devices = jax.devices()
    say(f"{cell.name}: {len(devices)} {devices[0].platform} device(s) up")
    if devices[0].platform != platform:
        raise SystemExit(f"benchmark: JAX came up on "
                         f"'{devices[0].platform}', not on '{platform}'")
    if len(devices) < cell.chips:
        raise SystemExit(f"benchmark: cell {cell.name} needs {cell.chips} "
                         f"chip(s), {len(devices)} visible")
    on_tpu = platform == "tpu"
    kind = devices[0].device_kind
    device_peaks = peaks.for_kind(kind) if on_tpu else None

    import horovod_tpu as hvd
    t = clock()
    if len(devices) == cell.chips:
        hvd.init()
    else:  # a larger host: the cell takes the chips it asks for
        hvd.init(devices=devices[:cell.chips])
    init_s = clock() - t
    if hvd.size() != cell.chips:
        raise SystemExit(f"benchmark: hvd.size() is {hvd.size()}, the cell "
                         f"asks for {cell.chips}")
    used = devices[:cell.chips]

    family = spec.load_module("families", cell.config["family"], cell.dirs)
    path = spec.load_module("paths", cell.traffic["path"], cell.dirs)
    span = jax.profiler.TraceAnnotation if trace else \
        (lambda name: contextlib.nullcontext())
    job = path.build(cell, family, seed=seed, devices=used, span=span)
    say(f"{cell.name}: state, batch and step program ready; reference "
        f"check: {job.reference}")

    warm = []
    for _ in range(WARMUP_STEPS):
        t = clock()
        warm.append(float(job.step()))
        if len(warm) == 1:
            first_step_s = clock() - t
    job.finish()
    compile_s = job.compile_s + first_step_s
    say(f"{cell.name}: warm-up losses {warm}; persistent cache "
        f"{log.hits} hit(s), {log.misses} miss(es); "
        f"{log.requests} compile request(s) in set-up")

    requests = log.requests
    setup_s = clock() - t0
    window = loop.run_steps(
        job.step, loop.for_seconds(seconds if not trace else seconds / 2))
    job.finish()
    traced, trace_data = None, xplane.Trace() if trace else None
    if trace and window.raised == 0:
        traced, trace_data = _trace_steps(
            job, cell.traffic["trace_steps"] + TRACE_EDGE_STEPS,
            os.path.join(checkout, TRACE_DIR, cell.name))
    in_window = log.requests - requests

    program = job.program
    run = Run(cell=cell, family=family, chips=cell.chips,
              peaks=device_peaks, samples_per_step=job.samples_per_step,
              setup_s=setup_s, init_s=init_s, compile_s=compile_s,
              window=window, program=program,
              instructions=hlo.index(program.as_text()) if program else {},
              trace=trace_data)

    problems = []
    if not (job.reference and job.reference["ok"]):
        problems.append(f"reference: {job.reference}")
    windows = [window] + ([traced] if traced else [])
    losses = warm + [x for w in windows for x in w.losses]
    failed = sum(w.failed for w in windows)
    if failed:
        problems.append(f"{failed} step(s) raised or gave a non-finite loss")
    if not all(math.isfinite(x) for x in losses) or \
            not losses[-1] < losses[0]:
        problems.append(f"loss did not fall on the fixed batch: "
                        f"{losses[0]} -> {losses[-1]}")
    if in_window:
        problems.append(f"{in_window} compile request(s) after warm-up")
    if job.verify is not None:
        problems.extend(job.verify())

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": memory_peak_bytes(program, used)}
    breakdown = None
    if trace:
        entries, reader_kind = cell.per_layer, "layer_metrics"
        totals = xplane.busy_and_window_seconds(trace_data)
        if not totals or totals[0] <= 0.0:
            problems.append("the trace holds no whole step in which an "
                            "operation ran on the device")
            totals = (0.0, 0.0)
        device["busy_s"], device["window_s"] = totals
        breakdown = xplane.breakdown(trace_data)
    else:
        entries, reader_kind = cell.end_to_end, "end_to_end"
    metrics = _read_metrics(entries, reader_kind, run, cell.dirs)
    if not on_tpu:
        # A time, a rate or a share taken off the chip is not a device
        # metric and is not written under one's name: counts alone remain.
        counted = {m["name"] for m in entries
                   if m["source"] == "program_counter"}
        metrics = {k: v for k, v in metrics.items() if k in counted}
        breakdown = breakdown and xplane.breakdown(xplane.Trace())
    for p in problems:
        say(f"{cell.name}: NOT CORRECT: {p}")
    ordered = sorted(window.step_seconds)
    say(f"{cell.name}: {window.dispatched} step(s) in the window, median "
        f"{window.median_step_seconds() * 1e3:.2f} ms, 5th and 25th "
        f"percentile {ordered[len(ordered) // 20] * 1e3:.2f} and "
        f"{ordered[len(ordered) // 4] * 1e3:.2f} ms, longest "
        f"{[round(x * 1e3, 1) for x in ordered[-3:]]} ms, stall share "
        f"{window.stall_share():.2%}; loss {losses[0]:.4f}"
        f" -> {losses[-1]:.4f}; set-up {setup_s:.1f} s (init {init_s:.1f}, "
        f"compile {compile_s:.1f}); persistent cache {log.hits} hit(s), "
        f"{log.misses} miss(es)")
    attempted = sum(w.dispatched + w.raised for w in windows)
    return report.last_line(correct=not problems, attempted=attempted,
                            failed=failed, metrics=metrics, device=device,
                            breakdown=breakdown)
