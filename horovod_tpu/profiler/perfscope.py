"""perfscope: always-on step-phase profiler with MFU accounting.

The reference's timeline (Sergeev & Del Balso, 2018; timeline.cc) only
traces collectives; nothing in the stack said where a *step* goes. This
module attributes every training step's wall time to phases and keeps a
rolling per-rank summary that feeds four sinks:

* live gauges in the metrics registry (PR 2) — `horovod_mfu`,
  `horovod_step_seconds`, `horovod_step_phase_seconds{phase}` — which the
  exporter also renders as Chrome-trace counter tracks,
* a compact per-rank summary pushed to the rendezvous KV (scope
  ``perf``) on the metrics-exporter cadence, persisted by the launcher at
  job end so ``hvddoctor`` gains a perf section that names stragglers
  *and their dominant phase*,
* a structured ``StepProfile`` dict on request (``step_profile``),
* ``hvd.perfscope()`` for ad-hoc inspection.

Phases
------

``input_wait``      host blocked fetching the next batch (user-marked)
``compile``         trace+compile on executable-cache misses (auto)
``dispatch``        host-side Python + JAX dispatch — the unattributed
                    remainder of a step (the base phase)
``device_compute``  host blocked waiting on device results (user-marked
                    around ``block_until_ready``)
``comms``           eager collective calls (auto, from the dispatch
                    choke point; per-bucket spans of the PR 6 pipelined
                    path included) — under async dispatch this covers
                    host-side dispatch, in elastic mode the full
                    completion wait
``optimizer``       the optax update + apply (auto, DistributedOptimizer)

Accounting is a single switching timer: a step has exactly one active
phase at a time, ``phase(name)`` switches it, and the remainder lands in
``dispatch`` — so the phases sum to the measured wall step time by
construction (runtime hooks that re-attribute time from inside the
active phase keep the invariant via `attribute`; clamping on pathological
nesting can only *lose* coverage, never double-count). Collectives that
run *inside* one compiled program (the SPMD `build_train_step` path)
cannot be split out on the host — they show up under ``device_compute``;
the eager `DistributedOptimizer` path gets full comms/optimizer
attribution automatically.

Steps are delimited either explicitly::

    scope = hvd.perfscope()
    with scope.step():
        with scope.phase("input_wait"):
            batch = next(it)
        loss, grads = grad_fn(params, batch)   # dispatch
        params, opt_state = opt.step(grads, params, opt_state)
        with scope.phase("device_compute"):
            jax.block_until_ready(loss)

or implicitly: ``DistributedOptimizer.step()`` auto-hooks the scope, so
an unmodified Horovod-style training loop gets per-step attribution
(step N = end of optimizer step N-1 to end of optimizer step N) with
comms/optimizer split out and everything else under ``dispatch``.

MFU is computed as in the PaLM paper (Chowdhery et al., 2022): model
FLOPs per step over wall time, divided by chip peak. Model FLOPs come
from XLA cost analysis (``profiler/flops.py``: 2 FLOPs a multiply-add,
as the peak counts) — `set_model_flops` records both the value and its
source.

Knobs: ``HOROVOD_PERFSCOPE=0`` swaps the scope for a no-op shell (same
pattern as ``HOROVOD_METRICS=0``); ``HOROVOD_PERFSCOPE_WINDOW`` sizes
the rolling per-step window the percentiles are computed over.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from horovod_tpu.common.config import _env_on

PERFSCOPE_ENV = "HOROVOD_PERFSCOPE"
PERFSCOPE_WINDOW_ENV = "HOROVOD_PERFSCOPE_WINDOW"

#: Rendezvous-KV scope per-rank summaries are pushed under.
SCOPE = "perf"

#: Schema tag in every pushed/persisted summary (doctor compatibility).
SUMMARY_VERSION = 1

DEFAULT_WINDOW = 512

#: Canonical phase names (free-form names are accepted; these order the
#: reports). `checkpoint` is the device→host snapshot of an async save
#: (ckpt/async_ckpt.py) — the ONLY checkpoint phase allowed on the
#: step critical path; persist/commit run on the writer thread and
#: never appear here.
PHASES = ("input_wait", "compile", "dispatch", "device_compute",
          "comms", "optimizer", "checkpoint")

#: The unattributed remainder of a step.
BASE_PHASE = "dispatch"

#: Phases that mean "waiting on peers", excluded from a rank's *local*
#: time — the quantity straggler attribution compares (in a synchronous
#: job every rank's WALL time matches; only the split differs).
WAIT_PHASES = frozenset({"comms"})


class _StepState:
    """Accounting for one in-flight step (thread-local: steps, and every
    hook that lands in them, run on the training thread)."""

    __slots__ = ("t0", "phases", "cur", "since", "pending_sub", "stack",
                 "implicit", "weight", "attributed")

    def __init__(self, t0: float, implicit: bool, weight: float) -> None:
        self.t0 = t0
        self.phases: Dict[str, float] = {}
        self.cur = BASE_PHASE
        self.since = t0
        self.pending_sub = 0.0   # re-attributed out of the current window
        self.stack: List[str] = []
        self.implicit = implicit
        self.weight = weight
        self.attributed = 0.0    # cumulative re-attributed seconds

    def flush(self, now: float) -> None:
        el = now - self.since - self.pending_sub
        if el > 0.0:
            self.phases[self.cur] = self.phases.get(self.cur, 0.0) + el
        self.since = now
        self.pending_sub = 0.0


class _NullCtx:
    """Shared do-nothing context manager (disabled scope / no-op paths)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_CTX = _NullCtx()


class _PhaseCtx:
    __slots__ = ("scope", "name", "active")

    def __init__(self, scope: "PerfScope", name: str) -> None:
        self.scope = scope
        self.name = name

    def __enter__(self):
        self.active = self.scope._phase_begin(self.name)
        return self

    def __exit__(self, *exc):
        if self.active:
            self.scope._phase_end()
        return False


class _StepCtx:
    __slots__ = ("scope", "weight", "active")

    def __init__(self, scope: "PerfScope", weight: float) -> None:
        self.scope = scope
        self.weight = weight

    def __enter__(self):
        self.active = self.scope._step_begin(implicit=False,
                                             weight=self.weight)
        return self

    def __exit__(self, *exc):
        if self.active:
            self.scope._step_end()
        return False


class PerfScope:
    """Step-phase profiler (see module docstring).

    The in-flight step lives in thread-local storage — the hot path
    (phase switches, attribution from the collectives choke point) takes
    no lock. The rolling summary state is lock-guarded and read by the
    exporter thread.

    `clock` is injectable for the fake-clock unit tests.
    """

    def __init__(self, window: Optional[int] = None,
                 clock=None) -> None:
        if window is None:
            try:
                window = int(os.environ.get(PERFSCOPE_WINDOW_ENV, "")
                             or DEFAULT_WINDOW)
            except ValueError:
                window = DEFAULT_WINDOW
        self._clock = clock or time.perf_counter
        self._tls = threading.local()
        self._lock = threading.Lock()
        # (wall, {phase: sec}) per recorded step, most recent last.
        self._recent: collections.deque = \
            collections.deque(maxlen=max(8, window))  # guarded-by: _lock
        self._steps = 0  # guarded-by: _lock
        self._total_wall = 0.0  # guarded-by: _lock
        self._totals: Dict[str, float] = {}  # guarded-by: _lock
        self._model_flops: Optional[float] = None  # guarded-by: _lock
        self._flops_source: str = "none"  # guarded-by: _lock
        # Free-form phase labels ever written to the per-phase gauge,
        # so absent ones can be zeroed each step (the gauge promises
        # "the LAST step's" split).  guarded-by: _lock
        self._gauge_phases: set = set()
        # Static per-axis comms attribution of the compiled step
        # (docs/parallelism.md): {"dp": bytes, "dp+tp": bytes, ...},
        # recorded at trace time by the sharded gradient reduction.
        self._comms_axes: Dict[str, float] = {}  # guarded-by: _lock
        self._kv = None
        self._kv_dead = False

    def set_comms_axes(self, bytes_by_axis: Dict[str, float]) -> None:
        """Record the hybrid step's planned per-device gradient-
        reduction bytes per mesh-axis group (optim.optimizer
        _record_axis_comms calls this at trace time). Shows up in
        summary()['comms_axes'] — the dp-vs-tp traffic split."""
        with self._lock:
            self._comms_axes = {str(k): float(v)
                                for k, v in bytes_by_axis.items()}

    # ------------------------------------------------------------ steps
    def step(self, weight: float = 1.0) -> Any:
        """Context manager delimiting one training step. `weight=N`
        declares the body covers N identical steps (a device-side scan
        chain): wall and phases are divided by N on record."""
        return _StepCtx(self, weight)

    def _step_begin(self, implicit: bool, weight: float = 1.0) -> bool:
        st = getattr(self._tls, "step", None)
        if st is not None:
            if not st.implicit:
                return False  # nested explicit step: inner one no-ops
            # Explicit step takes over from an implicit one mid-flight:
            # close the implicit interval so its time is not lost.
            self._record(st, self._clock())
        self._tls.step = _StepState(self._clock(), implicit, weight)
        from horovod_tpu.observability import tracing
        tracing.step_begin()
        return True

    def _step_end(self) -> None:
        st = getattr(self._tls, "step", None)
        if st is None:
            return
        self._tls.step = None
        self._record(st, self._clock())

    def step_entry(self) -> None:
        """DistributedOptimizer hook (entry): open an implicit step when
        the user delimited none, so comms/optimizer phases always land
        somewhere."""
        if getattr(self._tls, "step", None) is None:
            self._tls.step = _StepState(self._clock(), True, 1.0)
            from horovod_tpu.observability import tracing
            tracing.step_begin()

    def step_boundary(self) -> None:
        """DistributedOptimizer hook (exit): an optimizer step ends one
        training step. Implicit steps roll over here — step N spans end
        of optimizer call N-1 to end of call N; explicit user steps are
        left alone."""
        st = getattr(self._tls, "step", None)
        if st is None or not st.implicit:
            return
        now = self._clock()
        self._record(st, now)
        self._tls.step = _StepState(now, True, 1.0)
        from horovod_tpu.observability import tracing
        tracing.step_begin()

    # ----------------------------------------------------------- phases
    def phase(self, name: str) -> Any:
        """Context manager switching the step's active phase. No-op
        outside a step."""
        return _PhaseCtx(self, name)

    def _phase_begin(self, name: str) -> bool:
        st = getattr(self._tls, "step", None)
        if st is None:
            return False
        st.flush(self._clock())
        st.stack.append(st.cur)
        st.cur = name
        return True

    def _phase_end(self) -> None:
        st = getattr(self._tls, "step", None)
        if st is None:
            return
        st.flush(self._clock())
        st.cur = st.stack.pop() if st.stack else BASE_PHASE

    def attribute(self, name: str, seconds: float) -> None:
        """Re-attribute `seconds` of the currently-running phase to
        `name` (runtime hooks: compile spans, eager collective dispatch).
        The time is added to `name` and subtracted from the active
        phase's window at its next flush, keeping the sum-to-wall
        invariant. No-op outside a step, for non-positive durations, and
        when the active phase already *is* `name`."""
        st = getattr(self._tls, "step", None)
        if st is None or seconds <= 0.0:
            return
        st.attributed += seconds
        if st.cur == name:
            return
        st.phases[name] = st.phases.get(name, 0.0) + seconds
        st.pending_sub += seconds

    def attributed_marker(self) -> float:
        """Cumulative re-attributed seconds of the in-flight step — outer
        hooks diff two markers to subtract nested attributions (the
        compile inside a collective dispatch) from their own."""
        st = getattr(self._tls, "step", None)
        return st.attributed if st is not None else 0.0

    # ----------------------------------------------------------- record
    def _record(self, st: _StepState, now: float) -> None:
        # Close the step's hvdtrace span (observability/tracing.py):
        # _record is the single completion sink for every step path
        # (explicit end, boundary rollover, explicit takeover).
        from horovod_tpu.observability import tracing
        tracing.step_end()
        st.flush(now)
        wall = now - st.t0
        if wall <= 0.0:
            return
        w = st.weight if st.weight > 0 else 1.0
        wall /= w
        phases = {k: v / w for k, v in st.phases.items() if v > 0.0}
        with self._lock:
            self._recent.append((wall, phases))
            self._steps += 1
            self._total_wall += wall
            for k, v in phases.items():
                self._totals[k] = self._totals.get(k, 0.0) + v
            flops = self._model_flops
        self._update_metrics(wall, phases, flops)

    def _update_metrics(self, wall: float, phases: Dict[str, float],
                        flops: Optional[float]) -> None:
        from horovod_tpu.observability import metrics as m
        reg = m.registry()
        if not reg.enabled:
            return
        mx = _metric_handles(reg, m)
        mx["steps"].inc()
        mx["wall"].observe(wall)
        # Zero every phase absent from THIS step — canonical names and
        # previously-seen free-form ones alike: the gauge promises "the
        # last step's" split, and a compile (or a once-per-epoch user
        # phase) must not linger on the track for the rest of the run.
        with self._lock:
            self._gauge_phases.update(phases)
            labels = set(PHASES) | self._gauge_phases
        for k in labels:
            mx["phase"].labels(phase=k).set(phases.get(k, 0.0))
        if flops:
            from horovod_tpu.profiler import flops as F
            peak = F.peak_flops_per_chip()
            if peak:
                mx["mfu"].set(flops / wall / peak)

    # ---------------------------------------------------------- results
    def set_model_flops(self, flops_per_step: Optional[float],
                        source: str = "fallback") -> None:
        """Declare the model FLOPs one step performs (feeds the
        `horovod_mfu` gauge and summary MFU). `source` is "xla" when the
        number came from XLA cost analysis (profiler/flops.py), else
        "fallback" (the caller's own count)."""
        with self._lock:
            self._model_flops = float(flops_per_step) \
                if flops_per_step else None
            self._flops_source = source if self._model_flops else "none"

    def reset(self) -> None:
        """Drop accumulated stats (an elastic reset reuses the
        process-global scope). Also abandons the calling thread's in-flight
        step, so a stale implicit step left open by earlier optimizer
        calls cannot pollute the next first sample."""
        self._tls.step = None
        from horovod_tpu.observability import tracing
        tracing.step_end()
        with self._lock:
            self._recent.clear()
            self._steps = 0
            self._total_wall = 0.0
            self._totals = {}
            self._model_flops = None
            self._flops_source = "none"
            self._comms_axes = {}

    def step_count(self) -> int:
        """Total steps recorded (cheap — one locked int read)."""
        with self._lock:
            return self._steps

    def recent_samples(self, since_step: int = 0
                       ) -> "Tuple[int, List[Tuple[float, Dict[str, float]]]]":
        """Per-step samples recorded after step count `since_step`
        (bounded by the rolling window), plus the current total step
        count. The hvdwatch detectors (observability/watch.py) feed on
        this each exporter tick: callers track the returned total and
        pass it back so every step is consumed exactly once."""
        with self._lock:
            total = self._steps
            n = min(max(total - since_step, 0), len(self._recent))
            samples = [
                (w, dict(p)) for w, p in
                list(self._recent)[len(self._recent) - n:]] if n else []
        return total, samples

    def summary(self) -> Dict[str, Any]:
        """Rolling summary over the recent window: wall percentiles,
        mean per-phase seconds/fractions, coverage, dominant phases,
        MFU. Empty dict before the first recorded step."""
        with self._lock:
            recent = list(self._recent)
            steps = self._steps
            flops = self._model_flops
            source = self._flops_source
            comms_axes = dict(self._comms_axes)
        if not recent:
            return {}
        walls = sorted(w for w, _ in recent)
        n = len(walls)
        mean = sum(walls) / n
        p50 = walls[n // 2]
        p95 = walls[min(n - 1, int(n * 0.95))]
        phases: Dict[str, float] = {}
        local = 0.0
        for wall, ph in recent:
            for k, v in ph.items():
                phases[k] = phases.get(k, 0.0) + v
            local += wall - sum(v for k, v in ph.items()
                                if k in WAIT_PHASES)
        phases = {k: v / n for k, v in phases.items()}
        local /= n
        covered = sum(phases.values())
        order = {p: i for i, p in enumerate(PHASES)}
        key = lambda kv: (-kv[1], order.get(kv[0], 99))  # noqa: E731
        dominant = min(phases.items(), key=key)[0] if phases else None
        local_phases = {k: v for k, v in phases.items()
                        if k not in WAIT_PHASES}
        dominant_local = min(local_phases.items(), key=key)[0] \
            if local_phases else None
        out: Dict[str, Any] = {
            "steps": steps,
            "window_steps": n,
            "wall": {"mean_s": mean, "p50_s": p50, "p95_s": p95,
                     "max_s": walls[-1]},
            "phases_s": {k: phases[k] for k in
                         sorted(phases, key=lambda p: order.get(p, 99))},
            "phase_fractions": {k: (v / mean if mean else 0.0)
                                for k, v in phases.items()},
            "coverage": covered / mean if mean else 0.0,
            "local_mean_s": local,
            "dominant_phase": dominant,
            "dominant_local_phase": dominant_local,
            "model_flops_per_step": flops,
            "mfu_source": source,
        }
        if comms_axes:
            out["comms_axes"] = comms_axes
        from horovod_tpu.profiler import flops as F
        peak = F.peak_flops_per_chip()
        if peak:
            out["peak_flops_per_chip"] = peak
            if flops and mean > 0:
                out["mfu"] = flops / mean / peak
        return out

    def step_profile(self, name: str, **extra: Any) -> Dict[str, Any]:
        """The summary as one structured ``StepProfile`` record."""
        prof = {"name": name, "perfscope": SUMMARY_VERSION}
        prof.update(self.summary())
        prof.update(extra)
        return prof

    # --------------------------------------------------------- KV push
    def _identity(self) -> Dict[str, Any]:
        rank = size = None
        try:
            from horovod_tpu.core import topology
            rank = topology.rank_or_none()
            st = topology.raw_state()
            size = st.size if st.initialized else None
        except Exception:
            pass
        if rank is None:
            v = os.environ.get("HOROVOD_RANK", "")
            rank = int(v) if v.strip().isdigit() else None
        if size is None:
            v = os.environ.get("HOROVOD_SIZE", "")
            size = int(v) if v.strip().isdigit() else None
        v = os.environ.get("HOROVOD_ELASTIC_ROUND", "")
        return {"rank": rank, "size": size,
                "round": int(v) if v.strip().isdigit() else 0,
                "hostname": os.environ.get("HOROVOD_HOSTNAME", ""),
                "pid": os.getpid()}

    def kv_payload(self) -> Optional[Dict[str, Any]]:
        """The compact per-rank summary pushed to the rendezvous KV
        (None before the first step or mid-reset)."""
        s = self.summary()
        if not s:
            return None
        body = self._identity()
        if body["rank"] is None:
            return None  # mid-reset: an unkeyable summary would linger
        body["perfscope"] = SUMMARY_VERSION
        body["wall_time"] = time.time()
        body["summary"] = s
        return body

    def _kv_client(self):
        if self._kv is None and not self._kv_dead:
            try:
                from horovod_tpu.common import config as C
                from horovod_tpu.common.resilience import RetryPolicy
                from horovod_tpu.runner.rendezvous import KVClient
                addr = os.environ.get(C.HOROVOD_RENDEZVOUS_ADDR, "")
                port = os.environ.get(C.HOROVOD_RENDEZVOUS_PORT, "")
                if not addr or not port:
                    self._kv_dead = True
                    return None
                # Telemetry budget: one attempt, 2s transport cap — a
                # missed push is superseded by the next exporter tick.
                self._kv = KVClient(addr, int(port),
                                    retry_policy=RetryPolicy(max_attempts=1),
                                    request_timeout=2.0)
            except Exception:
                self._kv_dead = True
        return self._kv

    def push_summary(self) -> bool:
        """Best-effort KV push (exporter cadence). Keyed by (rank,
        round) like the flight tails: elastic resets reuse rank numbers,
        and a survivor's next-round summary must not clobber a dead
        rank's last one."""
        body = self.kv_payload()
        if body is None:
            return False
        kv = self._kv_client()
        if kv is None:
            return False
        try:
            kv.put(SCOPE, f"rank-{body['rank']}.r{body['round']}",
                   json.dumps(body).encode("utf-8"))
            return True
        except Exception:
            return False


class _NoopScope:
    """HOROVOD_PERFSCOPE=0 shell: every hook is a cheap no-op."""

    __slots__ = ()

    def step(self, weight: float = 1.0):
        return _NULL_CTX

    def phase(self, name: str):
        return _NULL_CTX

    def attribute(self, name: str, seconds: float) -> None:
        pass

    def attributed_marker(self) -> float:
        return 0.0

    def step_entry(self) -> None:
        pass

    def step_boundary(self) -> None:
        pass

    def set_model_flops(self, flops_per_step, source="fallback") -> None:
        pass

    def set_comms_axes(self, bytes_by_axis) -> None:
        pass

    def reset(self) -> None:
        pass

    def summary(self) -> Dict[str, Any]:
        return {}

    def step_count(self) -> int:
        return 0

    def recent_samples(self, since_step: int = 0):
        return 0, []

    def step_profile(self, name: str, **extra: Any) -> Dict[str, Any]:
        return {"name": name, "perfscope": SUMMARY_VERSION, **extra}

    def kv_payload(self) -> Optional[Dict[str, Any]]:
        return None

    def push_summary(self) -> bool:
        return False


NOOP = _NoopScope()

_metric_cache = None


def _metric_handles(reg, m):
    global _metric_cache
    if _metric_cache is None or _metric_cache[0] is not reg:
        _metric_cache = (reg, {
            "steps": reg.counter(
                "horovod_perfscope_steps_total",
                "Training steps recorded by perfscope"),
            "wall": reg.histogram(
                "horovod_step_seconds",
                "Wall time per training step (perfscope)",
                buckets=m.TIME_BUCKETS),
            "phase": reg.gauge(
                "horovod_step_phase_seconds",
                "Seconds the last step spent per phase (perfscope)",
                labelnames=("phase",)),
            "mfu": reg.gauge(
                "horovod_mfu",
                "Model FLOPs utilization of the last step (model FLOPs "
                "/ wall / chip peak; PaLM convention)"),
        })
    return _metric_cache[1]


_scope: Optional[object] = None
_scope_lock = threading.Lock()


def enabled() -> bool:
    return _env_on(PERFSCOPE_ENV, True)


def get():
    """The process-wide scope (NOOP shell under HOROVOD_PERFSCOPE=0)."""
    global _scope
    s = _scope
    if s is not None:
        return s
    with _scope_lock:
        if _scope is None:
            _scope = PerfScope() if enabled() else NOOP
        return _scope


def attribute(name: str, seconds: float) -> None:
    """Module-level hot-path hook (collectives/compile choke points)."""
    get().attribute(name, seconds)


def attributed_marker() -> float:
    return get().attributed_marker()


def push_summary() -> bool:
    """Exporter-cadence KV push (observability/export.py)."""
    return get().push_summary()


def reset_for_tests() -> None:
    """Drop the process-wide scope so the next get() re-reads env."""
    global _scope, _metric_cache
    with _scope_lock:
        _scope = None
        _metric_cache = None


def persist_kv_summaries(store, out_dir: Optional[str] = None
                         ) -> List[str]:
    """Launcher-side: write every pushed ``perf/`` summary the
    rendezvous server holds to `out_dir` (default: HOROVOD_FLIGHT_DIR,
    next to the flight tails) as ``perf-rank-<r>.r<round>.json``, so the
    doctor can merge step-time summaries offline — including from
    workers that died without a clean exit."""
    if out_dir is None:
        out_dir = os.environ.get("HOROVOD_FLIGHT_DIR", "")
    if not out_dir:
        return []
    try:
        items = store.scope_items(SCOPE)
    except Exception:
        return []
    written: List[str] = []
    for key, raw in sorted(items.items()):
        safe = key.replace("/", "_")
        path = os.path.join(out_dir, f"perf-{safe}.json")
        try:
            os.makedirs(out_dir, exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(raw)
            os.replace(tmp, path)
            written.append(path)
        except OSError:
            continue
    return written
