"""The program's own host spans in a traced run: what
`jax.profiler.TraceAnnotation`s named `hvd.*` recorded, on the clock the
device trace has.

`xplane.reduce_profile` keeps the host events the benchmark itself records
(`bench.*`) and drops the rest, so this reader goes back to the file the
traced run left (`runner.TRACE_DIR/<cell>` of the checkout). It finds
nothing, and raises nothing, where the run was not traced, where the file
is not there, and where the program records no such span.
"""

from __future__ import annotations

import glob
import os
import statistics

from benchmark.harness import runner, spec, xplane

SPAN_PREFIX = "hvd."


def reduce_profile(profile) -> list:
    """The host planes' events named `hvd.*` of a
    `jax.profiler.ProfileData`, as `xplane.Event`s sorted by start."""
    spans = [xplane.Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
             for plane in profile.planes
             if plane.name.startswith(xplane.HOST_PLANE)
             for line in plane.lines for e in line.events
             if e.name.startswith(SPAN_PREFIX)]
    return sorted(spans, key=lambda e: e.start)


def traced_spans(run) -> list:
    """`reduce_profile` of the trace file of `run`'s cell; [] where the run
    was not traced or left no one file."""
    if run.trace is None:
        return []
    files = glob.glob(os.path.join(spec.REPO, runner.TRACE_DIR, run.cell.name,
                                   "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        return []
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(files[0]))


def median_ms(run, name: str):
    """Median length of the traced run's spans `name`, in ms; None where
    there are none."""
    lengths = [s.dur for s in traced_spans(run) if s.name == name]
    return statistics.median(lengths) * 1e3 if lengths else None
