"""Reduces a profiler trace (`.xplane.pb`) to what the per-layer metrics read.

Read with `jax.profiler.ProfileData` alone (no protobuf or TensorFlow
dependency). A TPU's trace has one plane per chip, `/device:TPU:<n>`, whose
line `XLA Ops` holds one event per executed HLO instruction and whose line
`XLA Modules` one per executed program; the host's planes hold what
`jax.profiler.TraceAnnotation` recorded, on the same clock.

What a step is, on the device: the program that takes most of the device's
time (the train step, or the gradient program of an eager step) starts once
per step, so the interval from one of its starts to the next is one step,
whatever else runs in it. The measured window runs from its second start to
its last: the first traced step begins on a device the profiler's start-up
has let run dry, and the last one is cut by the end of the trace.
"""

from __future__ import annotations

import statistics
from dataclasses import dataclass, field

from benchmark.harness import hlo

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
STEP_SPAN = "bench.step"      # the outer span: one per dispatched step
NO_SPAN = "outside the benchmark's spans"


@dataclass(frozen=True)
class Event:
    name: str
    start: float   # seconds on the trace's clock
    dur: float     # seconds
    opcode: str | None = None   # of a device op, where the trace gives it

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Device:
    name: str
    ops: list = field(default_factory=list)       # sorted by start
    modules: list = field(default_factory=list)   # sorted by start


@dataclass
class Trace:
    devices: list = field(default_factory=list)   # sorted by plane name
    spans: list = field(default_factory=list)     # host `bench.*` spans


def module_name(event_name: str) -> str:
    """`jit_step(1234567)` -> `jit_step`: the fingerprint is not the name."""
    return event_name.split("(")[0].strip()


def reduce_profile(profile) -> Trace:
    """`profile` is a `jax.profiler.ProfileData`."""
    trace = Trace()
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE) and \
                plane.name[len(DEVICE_PLANE):].isdigit():
            dev = Device(plane.name)
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops = sorted(
                        (Event(hlo.op_name(e.name), e.start_ns * 1e-9,
                               e.duration_ns * 1e-9, hlo.opcode_of(e.name))
                         for e in line.events),
                        key=lambda e: e.start)
                elif line.name == MODULES_LINE:
                    dev.modules = sorted(
                        (Event(module_name(e.name), e.start_ns * 1e-9,
                               e.duration_ns * 1e-9) for e in line.events),
                        key=lambda e: e.start)
            trace.devices.append(dev)
        elif plane.name.startswith(HOST_PLANE):
            for line in plane.lines:
                trace.spans.extend(
                    Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    trace.devices.sort(key=lambda d: d.name)
    trace.spans.sort(key=lambda e: e.start)
    return trace


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


# ------------------------------------------------------------ intervals

def union(intervals) -> list:
    """Merged, sorted (start, end) pairs covering the same set of times."""
    merged = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def clip(intervals, window) -> list:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy, window) -> list:
    """What is left of `window` when the merged `busy` is taken away."""
    out, at = [], window[0]
    for a, b in clip(busy, window):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if window[1] > at:
        out.append((at, window[1]))
    return out


# ---------------------------------------------------------------- steps

def step_starts(dev: Device) -> list:
    """Starts of the program that takes most of this device's time."""
    total = {}
    for m in dev.modules:
        total[m.name] = total.get(m.name, 0.0) + m.dur
    if not total:
        return []
    main = max(total, key=total.get)
    return [m.start for m in dev.modules if m.name == main]


def step_intervals(dev: Device) -> list:
    """(start, end) of each whole traced step of the measured window."""
    starts = step_starts(dev)
    if len(starts) >= 3:
        starts = starts[1:]
    return list(zip(starts, starts[1:]))


def measured(dev: Device):
    """(start, end, steps) of the measured window, or None where the trace
    holds no whole step."""
    steps = step_intervals(dev)
    return (steps[0][0], steps[-1][1], len(steps)) if steps else None


def busy(dev: Device) -> list:
    """Merged intervals in which some operation ran. A loop's own event
    spans its whole body, idle gaps included, so only the body's count."""
    return union((e.start, e.end) for e in dev.ops
                 if e.opcode not in hlo.CONTAINERS)


def busy_and_window_seconds(trace: Trace):
    """(busy, window) seconds averaged over the chips, or None where the
    trace holds no whole step."""
    pairs = []
    for dev in trace.devices:
        m = measured(dev)
        if m is not None:
            pairs.append((length(clip(busy(dev), m[:2])), m[1] - m[0]))
    if not pairs:
        return None
    return (statistics.fmean(p[0] for p in pairs),
            statistics.fmean(p[1] for p in pairs))


def device_step_seconds(dev: Device) -> list:
    """Per traced step, the time in which some operation ran."""
    b = busy(dev)
    return [length(clip(b, step)) for step in step_intervals(dev)]


def _per_step(dev: Device, events, value, wanted) -> float | None:
    """Sum of `value(e)` over the window's `events` that `wanted(e.name)`
    accepts, per step."""
    m = measured(dev)
    if m is None:
        return None
    lo, hi, steps = m
    return sum(value(e) for e in events
               if lo <= e.start < hi and wanted(e.name)) / steps


def modules_per_step(dev: Device) -> float | None:
    return _per_step(dev, dev.modules, lambda e: 1, lambda name: True)


def op_seconds_per_step(dev: Device, wanted) -> float | None:
    """Summed device time per step of the events `wanted(name)` accepts."""
    return _per_step(dev, dev.ops, lambda e: e.dur, wanted)


def op_counts_per_step(dev: Device, wanted) -> float | None:
    return _per_step(dev, dev.ops, lambda e: 1, wanted)


# ------------------------------------------------------------ breakdown

def top_ops(dev: Device, n: int = 10) -> list:
    """[name, seconds] of the operations that took most of the window. A
    loop or a call is on the line as one event around its body's events,
    which are there too: only the body's are counted."""
    m = measured(dev)
    if m is None:
        return []
    total = {}
    for e in dev.ops:
        if m[0] <= e.start < m[1] and e.opcode not in hlo.CONTAINERS:
            total[e.name] = total.get(e.name, 0.0) + e.dur
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def inner_then_outer(spans) -> tuple:
    """The spans in the order they are asked: the calls into the layers,
    then the step's outer span around them."""
    spans = list(spans)
    return ([s for s in spans if s.name != STEP_SPAN],
            [s for s in spans if s.name == STEP_SPAN])


def span_of(gap, groups) -> str:
    """The benchmark span the host was in during `gap`: of the first of
    `groups` (`inner_then_outer`) that touches it, the span that overlaps
    it most."""
    for group in groups:
        overlap, name = max(
            ((min(s.end, gap[1]) - max(s.start, gap[0]), s.name)
             for s in group), default=(0.0, NO_SPAN))
        if overlap > 0.0:
            return name
    return NO_SPAN


def idle_gaps(dev: Device, spans, n: int = 10) -> list:
    """[span, seconds]: the window's idle time by what the host was doing,
    longest first."""
    m = measured(dev)
    if m is None:
        return []
    # only the spans that touch the window can hold one of its gaps
    groups = inner_then_outer(
        s for s in spans if s.end > m[0] and s.start < m[1])
    total = {}
    for gap in gaps(busy(dev), m[:2]):
        name = span_of(gap, groups)
        total[name] = total.get(name, 0.0) + (gap[1] - gap[0])
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(trace: Trace) -> dict:
    """The traced line's `breakdown`, from the first chip."""
    if not trace.devices:
        return {"device_ops": [], "idle_gaps": []}
    first = trace.devices[0]
    return {"device_ops": top_ops(first),
            "idle_gaps": idle_gaps(first, trace.spans)}
