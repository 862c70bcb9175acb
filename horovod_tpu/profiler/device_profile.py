"""Per-op device-time profiling for a training step — "where do the
milliseconds go", answered from a real device trace.

The reference's timeline (`timeline.cc`, docs/timeline.rst) records
host-side spans per collective; on TPU the interesting time lives
INSIDE the compiled program, invisible to host spans. This module runs
a step under `jax.profiler.trace`, parses the xplane protobuf the TPU
runtime emits, and aggregates the "XLA Ops" stream into per-op and
per-category tables (the tool that located ResNet-50's BN-backward HBM
wall, docs/benchmarks.md).

    from horovod_tpu.profiler.device_profile import profile_step
    prof = profile_step(lambda: step(state))     # runs it reps times
    print(prof.as_markdown())

TPU-only at runtime (the CPU backend emits no per-op device plane);
the xplane aggregation itself is platform-independent and unit-tested
against synthetic traces.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

_DEFAULT_BUCKETS: List[Tuple[str, str]] = [
    # (regex on op name, category) — first match wins. The xplane gives
    # only HLO op NAMES, and XLA names fusions after their root/producer
    # ops, so this is a heuristic: an UNANCHORED copy|bitcast pattern
    # once swallowed compute fusions like dynamic-slice_bitcast_fusion
    # and mislabeled half an Inception step "layout/copy" (r05). Copies
    # are matched only by anchored prefix; anything *_fusion with a
    # layout-ish name falls through to the compute buckets. Category
    # totals are indicative — the per-op table is the ground truth.
    (r"select.and.scatter|select_and_scatter", "maxpool backward"),
    (r"reduce.window|reduce_window", "pool forward"),
    (r"all.reduce|all.gather|reduce.scatter|all.to.all|collective",
     "collective"),
    # A Mosaic kernel's instruction takes the name of the scope it was
    # called under (models/transformer.py, parallel/moe.py: attention's
    # flash kernels, latent attention's, the gated delta rule's, the
    # experts' grouped matmul); the conv kernels name themselves. A bare
    # "jvp" says only that something was differentiated.
    (r"^%?(attn\.attend|mla\.attend|gdn\.scan|moe\.experts)\b"
     r"|conv1x1_bn|flash|pallas", "pallas kernel"),
    # before the conv bucket: r"conv" substring-matches "convert_*"
    (r"multiply_reduce|reduce_fusion|convert_reduce",
     "reduce fusion (stats/grads)"),
    (r"conv(?!ert)|^%?custom.call", "convolution/custom-call"),
    (r"dot|matmul", "matmul"),
    (r"^%?(copy|bitcast|transpose)\b", "layout/copy"),
    (r"fusion", "fused elementwise/compute"),
]


def classify(name: str,
             buckets: Optional[List[Tuple[str, str]]] = None) -> str:
    low = name.lower()
    for pat, cat in (buckets or _DEFAULT_BUCKETS):
        if re.search(pat, low):
            return cat
    return "other"


@dataclasses.dataclass
class DeviceProfile:
    per_op: Dict[str, float]        # op name -> ms per step
    per_category: Dict[str, float]  # category -> ms per step
    total_ms: float
    reps: int

    def top_ops(self, n: int = 15) -> List[Tuple[str, float]]:
        return sorted(self.per_op.items(), key=lambda kv: -kv[1])[:n]

    def as_markdown(self, top: int = 15) -> str:
        lines = [f"device ops total: {self.total_ms:.2f} ms/step "
                 f"(mean of {self.reps})", "",
                 "| category | ms/step | share |", "|---|---|---|"]
        for cat, d in sorted(self.per_category.items(),
                             key=lambda kv: -kv[1]):
            share = d / self.total_ms if self.total_ms else 0.0
            lines.append(f"| {cat} | {d:.2f} | {share:.1%} |")
        lines += ["", "| op | ms/step |", "|---|---|"]
        for name, d in self.top_ops(top):
            lines.append(f"| `{name[:70]}` | {d:.2f} |")
        return "\n".join(lines)


def aggregate_xspace(xspace, reps: int = 1,
                     buckets=None,
                     device_substr: str = "/device:TPU") -> DeviceProfile:
    """Aggregate an xplane XSpace's per-op device events.

    Uses the "XLA Ops" line of every plane whose name contains
    `device_substr` (one event per executed HLO op; the trace.json
    export nests module/op spans and double-counts)."""
    per_op: Dict[str, float] = {}
    per_cat: Dict[str, float] = {}
    total = 0.0
    for plane in xspace.planes:
        if device_substr not in plane.name:
            continue
        meta = plane.event_metadata
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                name = meta[e.metadata_id].name
                d = e.duration_ps / 1e9 / reps  # ps -> ms per step
                per_op[name] = per_op.get(name, 0.0) + d
                cat = classify(name, buckets)
                per_cat[cat] = per_cat.get(cat, 0.0) + d
                total += d
    return DeviceProfile(per_op=per_op, per_category=per_cat,
                         total_ms=total, reps=reps)


def _import_xplane_pb2():
    """The xplane protobuf bindings are an OPTIONAL dependency: only
    `load_xspace` needs them (parsing a trace off disk);
    `aggregate_xspace` and `classify` work on any object with the xplane
    shape and import nothing. Probed under both packagings, with an
    actionable error instead of a bare ImportError."""
    errors = []
    for mod in ("tensorflow.tsl.profiler.protobuf.xplane_pb2",
                "tsl.profiler.protobuf.xplane_pb2"):
        try:
            import importlib
            return importlib.import_module(mod)
        except ImportError as e:
            errors.append(f"{mod}: {e}")
    raise ImportError(
        "load_xspace needs the XPlane protobuf bindings, which ship with "
        "TensorFlow (tensorflow.tsl.profiler.protobuf.xplane_pb2) or the "
        "standalone `tsl` package — neither is installed. Install one "
        "(e.g. `pip install tensorflow-cpu`) or parse the .xplane.pb "
        "yourself and call aggregate_xspace(), which has no TF "
        "dependency. Probed: " + "; ".join(errors))


def load_xspace(trace_dir: str):
    xplane_pb2 = _import_xplane_pb2()

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb",
                             recursive=True))
    if not paths:
        raise FileNotFoundError(
            f"no xplane.pb under {trace_dir} — did the trace run?")
    xs = xplane_pb2.XSpace()
    with open(paths[-1], "rb") as fh:
        xs.ParseFromString(fh.read())
    return xs


# ---------------------------------------------------------------- capture
#
# On-demand capture hook (hvdwatch escalation, observability/watch.py):
# one process-wide lock serializes every jax.profiler trace started
# through here — jax raises on a second start_trace while one is live,
# and an anomaly-triggered capture must not collide with an operator's
# SIGUSR1-era poke or a second detector firing in the same window.
# Try-acquire semantics: a trigger that loses the race is SKIPPED (and
# reported False), never queued — a queued capture would record the
# post-anomaly steady state, which is not the evidence anyone wanted.

_capture_lock = threading.Lock()
_capture_skipped = 0  # diagnostics only; races are benign
# Interpreter-exit drain: a capture still running when the job finishes
# would be killed with its daemon thread BEFORE stop_trace flushes the
# artifact — losing exactly the evidence the escalation asked for. The
# exit hook tells the runner to cut its window short and waits (bounded)
# for the stop/flush to complete.
_exit_drain = threading.Event()
_active_runner: Optional[threading.Thread] = None
_drain_installed = False


def capture_active() -> bool:
    """True while an on-demand device trace is running."""
    return _capture_lock.locked()


def _drain_capture_at_exit() -> None:
    t = _active_runner
    if t is not None and t.is_alive():
        _exit_drain.set()
        # Bounded: profiler start/stop can take tens of seconds on slow
        # hosts; an unflushable trace must still not hang the exit.
        t.join(timeout=60.0)


def start_on_demand_capture(out_dir: str,
                            steps: int = 8,
                            step_count_fn: Optional[Callable[[], int]] = None,
                            timeout_s: float = 30.0,
                            poll_s: float = 0.05) -> bool:
    """Start a `jax.profiler` device trace that stops itself after
    `step_count_fn` advances by `steps` (or after `timeout_s`, whichever
    first — a stalled job must not trace forever). Returns True when the
    capture was scheduled; False when another capture holds the lock.

    The ENTIRE capture — including `start_trace`, whose first call can
    block for many seconds while the platform profiler initializes —
    runs on a daemon thread: the caller (the hvdwatch escalation on the
    metrics-exporter thread) must never stall on it, or the telemetry
    plane freezes for exactly the window it is trying to record.
    """
    global _capture_skipped
    if not _capture_lock.acquire(blocking=False):
        _capture_skipped += 1
        return False

    def _runner() -> None:
        try:
            try:
                import jax
                os.makedirs(out_dir, exist_ok=True)
                jax.profiler.start_trace(out_dir)
            except Exception:
                return  # no jax / trace already active out-of-band
            # Once the trace is live it MUST be stopped no matter what
            # the (caller-supplied) step counter does — a leaked trace
            # buffers for the job's lifetime and makes every later
            # start_trace fail, silently killing all future captures.
            try:
                start = step_count_fn() if step_count_fn is not None \
                    else 0
                deadline = time.monotonic() + max(timeout_s, poll_s)
                while time.monotonic() < deadline \
                        and not _exit_drain.is_set():
                    if step_count_fn is not None \
                            and step_count_fn() - start >= steps:
                        break
                    time.sleep(poll_s)
            finally:
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    pass
        finally:
            _capture_lock.release()

    global _active_runner, _drain_installed
    if not _drain_installed:
        _drain_installed = True
        import atexit
        atexit.register(_drain_capture_at_exit)
    t = threading.Thread(target=_runner, name="hvd-devprof-capture",
                         daemon=True)
    _active_runner = t  # single writer: the capture lock is held
    t.start()
    return True


def profile_step(run_once: Callable[[], object], reps: int = 3,
                 warmup: int = 1, buckets=None) -> DeviceProfile:
    """Trace `run_once` (called `reps` times) and aggregate device ops.

    `run_once` must block on its own completion (return a value the
    caller has synced, or sync internally); compile before calling —
    warmup executions here only drain post-compile slowness."""
    import jax

    for _ in range(warmup):
        jax.block_until_ready(run_once())
    tmpdir = tempfile.mkdtemp(prefix="hvd_devprof")
    with jax.profiler.trace(tmpdir):
        for _ in range(reps):
            out = run_once()
        jax.block_until_ready(out)
    prof = aggregate_xspace(load_xspace(tmpdir), reps=reps,
                            buckets=buckets)
    if not prof.per_op:
        raise RuntimeError(
            "trace contains no per-op device events — the CPU backend "
            "emits none; run on TPU (or pass the right device_substr)")
    return prof
