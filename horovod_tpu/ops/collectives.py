"""Eager named-tensor collectives, compiled onto the TPU mesh.

This module replaces the reference's entire L1-L3 stack — EnqueueTensor*
(horovod/common/operations.cc:1408-2058), the controller negotiation
(horovod/common/controller.cc:74), and the NCCL/MPI/Gloo op implementations
(horovod/common/ops/*) — with a TPU-native design:

* Each collective is a `jit(shard_map(...))` program over the process set's
  device mesh. XLA lowers `lax.psum`/`all_gather`/`psum_scatter`/`all_to_all`
  to ICI/DCN collectives directly; there is no runtime negotiation because
  readiness is implicit in the dataflow of a compiled program.

* The *response cache* (horovod/common/response_cache.cc) becomes a compiled-
  executable cache: the first call with a given signature pays a compile,
  every subsequent call is a cache hit that launches immediately. Capacity is
  governed by the same HOROVOD_CACHE_CAPACITY knob.

* The *fusion buffer* (horovod/common/fusion_buffer_manager.cc, 64-128MB
  threshold) becomes trace-time bucketing for grouped ops: tensors are
  flattened, concatenated into ≤-threshold buckets, reduced with one psum
  per bucket, and split back — all inside one XLA program, so the "memcpy
  into fusion buffer" is fused by the compiler instead of a batched D2D
  kernel (cuda_kernels.cu).

* JAX's async dispatch provides the handle/synchronize model natively
  (reference: horovod/torch/handle_manager.h) — returned arrays are futures;
  `synchronize()` is `block_until_ready`.

Per-rank tensor convention: with one process per chip (launcher default),
`allreduce(x)` takes this rank's local tensor. Under a single controller
owning L>1 devices (tests: 8-device CPU mesh; or a whole host), per-rank
tensors are stacked along a leading axis of length L, and results come back
stacked the same way (sharded over the mesh, so they stay distributed).
"""

from __future__ import annotations

import collections
import functools
import math
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.common import types as T
from horovod_tpu.common.exceptions import (DuplicateNameError,
                                           HorovodInternalError,
                                           HorovodTpuError)
from horovod_tpu.core import topology
from horovod_tpu.core.process_sets import ProcessSet, global_process_set
from horovod_tpu.observability import flight as _flight
from horovod_tpu.observability import tracing as _tracing
from horovod_tpu.profiler import perfscope as _pscope

_AXIS = "hvd"

# Runtime (not trace-time) failure types: a dead peer / aborted transport
# surfaces as one of these from XLA or the distributed client.
_COMM_ERRORS: tuple = (jax.errors.JaxRuntimeError,)

# A dead peer does NOT always surface as a typed runtime error: the CPU
# collectives backend raises plain ValueError("UNKNOWN: Gloo all-reduce
# failed: ... Connection closed by peer ..."), and the coordination client
# has its own wording. Message markers classify those.
_COMM_FAILURE_MARKERS = (
    "connection closed by peer", "connection reset", "connection refused",
    "gloo", "all-reduce failed", "broken pipe", "socket",
    "coordination service", "heartbeat", "task is unhealthy",
    "peer is unavailable", "deadline exceeded",
)


def is_comm_failure(e: BaseException) -> bool:
    """True if `e` looks like a transport/peer failure rather than user
    error — the trigger for HorovodInternalError in elastic mode."""
    if isinstance(e, _COMM_ERRORS):
        return True
    msg = str(e).lower()
    return any(m in msg for m in _COMM_FAILURE_MARKERS)


def _restore_grace_active(first_start: float, shutdown_sec: float) -> bool:
    """True while a peer's checkpoint restore should extend the stall
    deadline: the ckpt restore signal is fresh AND the total wait has
    not exhausted shutdown + HOROVOD_CKPT_RESTORE_GRACE_MAX. Probed at
    most once per armed deadline window (each re-arm buys a full
    shutdown_sec before the next probe), so the KV cost is negligible.
    Guarded: a broken ckpt import must not change watchdog behavior."""
    import time as _time
    try:
        from horovod_tpu.ckpt import resume as _ckpt_resume
        if _time.monotonic() - first_start >= \
                shutdown_sec + _ckpt_resume.grace_max_seconds():
            return False
        return _ckpt_resume.peer_restore_active()
    except Exception:
        return False


class StallWatchdog:
    """Python-side watchdog over a blocking collective wait.

    Built on the StallInspector bindings (native/__init__.py:247, or the
    pure-Python fallback common/resilience.py:PyStallInspector): the wait
    is registered via submit()/done() so the global watcher names it in
    warnings; guard() additionally BOUNDS the wait — it warns once at
    `warn_sec` and at `shutdown_sec` raises HorovodInternalError in the
    waiting thread, so the elastic retry loop (restore → re-rendezvous)
    owns recovery instead of a silent hang (or the non-elastic os._exit).

    Mechanics: `jax.block_until_ready` cannot be interrupted from Python,
    so the blocking call runs in a daemon thread and the caller polls its
    completion. On a shutdown raise the daemon thread stays blocked until
    the elastic reset tears the backend down (or the process exits) — it
    never outlives recovery. The thread is spawned per call on purpose:
    a reusable executor thread would be abandoned mid-block by exactly
    the timeouts this guard exists for, forcing respawn logic that
    degenerates to per-call spawn; the ~100 us spawn cost is noise next
    to a cross-process collective, and only elastic mode pays it.
    """

    def __init__(self, inspector, warn_sec: float, shutdown_sec: float,
                 poll_interval: float = 0.05):
        self.inspector = inspector
        self.warn_sec = warn_sec
        self.shutdown_sec = shutdown_sec
        self.poll_interval = poll_interval

    def guard(self, name: str, fn: Callable[[], Any]) -> Any:
        import time as _time

        from horovod_tpu.common.hvd_logging import get_logger

        self.inspector.submit(name)
        box: dict = {}
        finished = threading.Event()

        def run() -> None:
            try:
                box["value"] = fn()
            except BaseException as e:  # delivered to the caller below
                box["error"] = e
            finally:
                finished.set()

        t = threading.Thread(target=run, daemon=True,
                             name=f"hvd-guarded-wait-{name}")
        start = _time.monotonic()
        first_start = start
        t.start()
        warned = False
        try:
            while not finished.wait(self.poll_interval):
                age = _time.monotonic() - start
                if not warned and age >= self.warn_sec:
                    warned = True
                    _mx()["stall_warn"].labels(source="watchdog").inc()
                    _flight.record(
                        "stall", f"collective '{name}' stalled for "
                        f"{age:.1f}s (warning threshold "
                        f"{self.warn_sec:.0f}s)")
                    get_logger().warning(
                        "collective '%s' stalled for %.1fs "
                        "(HOROVOD_STALL_CHECK_TIME_SECONDS=%.0f)",
                        name, age, self.warn_sec)
                if self.shutdown_sec > 0 and age >= self.shutdown_sec \
                        and _restore_grace_active(first_start,
                                                 self.shutdown_sec):
                    # A rank is mid-checkpoint-restore (ckpt/resume
                    # heartbeat): its peers legitimately wait longer
                    # than the stall budget. Re-arm the deadline from
                    # NOW — i.e. from restore time, not round start —
                    # bounded overall by
                    # HOROVOD_CKPT_RESTORE_GRACE_MAX so a wedged
                    # restorer still cannot hang the job forever.
                    start = _time.monotonic()
                    _flight.record(
                        "ckpt", f"stall deadline re-armed for "
                        f"'{name}': peer checkpoint restore in "
                        f"progress (waited "
                        f"{start - first_start:.1f}s total)")
                    get_logger().info(
                        "collective '%s': stall deadline re-armed — "
                        "a peer's checkpoint restore is in progress",
                        name)
                    continue
                if self.shutdown_sec > 0 and age >= self.shutdown_sec:
                    stalled, _ = self.inspector.check()
                    _mx()["stall_shut"].inc()
                    # With HOROVOD_CHECK_COLLECTIVES=1 the fingerprint
                    # verifier turns the bare timeout into a diagnosis:
                    # last agreed call index + first divergent call
                    # (analysis/verifier.py stall_context). Guarded:
                    # the stall report must survive a broken analysis
                    # import.
                    try:
                        from horovod_tpu.analysis import verifier as _vf
                        fp_context = _vf.stall_context()
                    except Exception:
                        fp_context = ""
                    # The shutdown raise is exactly the moment the
                    # flight recorder exists for: every rank's ring
                    # still holds the calls leading into the hang.
                    try:
                        _flight.record(
                            "stall", f"collective '{name}' stalled past "
                            f"shutdown window {self.shutdown_sec:.0f}s")
                        _flight.dump("stall_watchdog")
                        flight_hint = _flight.dump_hint()
                    except Exception:
                        flight_hint = ""
                    raise HorovodInternalError(
                        f"collective '{name}' stalled past "
                        f"HOROVOD_STALL_SHUTDOWN_TIME_SECONDS="
                        f"{self.shutdown_sec:.0f}s"
                        + (f" (outstanding: {', '.join(stalled)})"
                           if stalled else "")
                        + fp_context + flight_hint)
            if "error" in box:
                raise box["error"]
            return box["value"]
        finally:
            self.inspector.done(name)


def _guarded_wait(name: str, fn: Callable[[], Any]) -> Any:
    """Run a blocking host-side wait under the stall inspector.

    Elastic mode with a shutdown window: the StallWatchdog bounds the wait
    (HorovodInternalError within shutdown_sec). Otherwise: plain call with
    submit/done bookkeeping, so the topology watcher can still warn (and,
    non-elastic, enforce its own shutdown via os._exit).
    """
    st = topology.raw_state()
    si = st.stall_inspector
    cfg = st.config
    if si is None or not cfg.elastic or cfg.stall_shutdown_seconds <= 0:
        _stall_submit(name)
        try:
            return fn()
        finally:
            _stall_done(name)
    return StallWatchdog(si, cfg.stall_warning_seconds,
                         cfg.stall_shutdown_seconds).guard(name, fn)


def _execute(fn: Callable, *args):
    """Run a compiled collective with failure propagation.

    Reference: op failures flow error Status → entry callbacks → frontends
    raise HorovodInternalError (SURVEY §5; common/operations.cc callbacks,
    elastic NCCL abort in nccl_operations.cc). Here: in elastic mode we
    force completion so a peer death surfaces HERE — inside the elastic
    retry scope — as HorovodInternalError, instead of as a raw
    XlaRuntimeError at some later readback the retry loop can't catch.
    The forced wait runs under the stall watchdog, so a PEER THAT NEVER
    ARRIVES (as opposed to one that dies loudly) also surfaces as
    HorovodInternalError within the shutdown window instead of hanging.
    Non-elastic runs keep fully async dispatch and raw errors.
    """
    elastic = topology.raw_state().config.elastic
    try:
        if elastic:
            # The guard must cover DISPATCH too: CPU/gloo executes the
            # collective synchronously inside fn(*args), so a missing
            # peer blocks there — before any block_until_ready.
            return _guarded_wait(
                "collective", lambda: jax.block_until_ready(fn(*args)))
        return fn(*args)
    except Exception as e:
        if elastic and is_comm_failure(e):
            # Dump before converting: the elastic retry loop is about
            # to tear the backend down, and this ring holds the calls
            # leading into the peer failure.
            _flight.record("error", f"collective execution failed: {e}")
            _flight.dump("internal_error")
            raise HorovodInternalError(
                f"collective execution failed: {e}") from e
        raise


# --------------------------------------------------------------------------
# Compiled-collective cache (the response-cache analog)
# --------------------------------------------------------------------------

class _CompiledCache:
    """LRU cache of compiled collective executables.

    Reference analog: ResponseCache (horovod/common/response_cache.cc:506) —
    there a hit skips the coordinator round-trip; here a hit skips tracing and
    compilation entirely.
    """

    def __init__(self) -> None:
        self._cache: "collections.OrderedDict[Any, Callable]" = \
            collections.OrderedDict()

    def _capacity(self) -> int:
        return topology.state().config.cache_capacity

    def get_or_build(self, key: Any, builder: Callable[[], Callable]) -> Callable:
        if key in self._cache:
            self._cache.move_to_end(key)
            _mx()["cache"].labels(event="hit").inc()
            return self._cache[key]
        _mx()["cache"].labels(event="miss").inc()
        fn = self._compile_timed(builder(), str(key[0]))
        self._cache[key] = fn
        cap = self._capacity()
        while cap > 0 and len(self._cache) > cap:
            self._cache.popitem(last=False)
        return fn

    @staticmethod
    def _compile_timed(fn: Callable, tag: str) -> Callable:
        """Record the cache miss's trace+compile as a COMPILE timeline span
        (reference: the timeline's per-tensor activity spans, timeline.cc).
        jit defers compilation to the first invocation, so that call — not
        the builder — is what gets timed."""
        first = [True]

        def wrapped(*args):
            if first[0]:
                first[0] = False
                tl = topology.state().timeline
                if tl is not None:
                    tl.span_begin(tag, "COMPILE")
                t0 = time.perf_counter()
                try:
                    return fn(*args)
                finally:
                    # Step-phase attribution (profiler/perfscope.py):
                    # the cache miss's trace+compile is `compile` time,
                    # not whatever phase the step happened to be in.
                    _pscope.attribute("compile",
                                      time.perf_counter() - t0)
                    if tl is not None:
                        tl.span_end(tag, "COMPILE")
            return fn(*args)

        return wrapped

    def clear(self) -> None:
        self._cache.clear()


_cache = _CompiledCache()


def clear_compiled_cache() -> None:
    _cache.clear()


# --------------------------------------------------------------------------
# Per-rank tensor plumbing
# --------------------------------------------------------------------------

def _resolve_ps(process_set: Optional[ProcessSet]) -> ProcessSet:
    ps = process_set if process_set is not None else global_process_set
    if ps.mesh is None:
        raise HorovodTpuError(
            f"process set {ps} is not registered; call hvd.add_process_set")
    return ps


def pidx_of() -> int:
    return jax.process_index()


def _local_member_count(ps: ProcessSet) -> int:
    """How many of this process's devices are in the set."""
    pidx = jax.process_index()
    return sum(1 for d in ps.mesh.devices.flat if d.process_index == pidx)


def _is_stacked(x: Any, ps: ProcessSet, L: int) -> bool:
    if L <= 1:
        return False
    shape = np.shape(x)
    return len(shape) >= 1 and shape[0] == L


def _to_global(x: Any, ps: ProcessSet) -> Tuple[jax.Array, bool]:
    """Lift a local (or locally-stacked) per-rank tensor to a global array
    sharded one-row-per-rank over the set's mesh.

    Returns (global_array, was_stacked). NOTE: the single-process lifting
    rule (stacked pass-through vs broadcast to (L, *shape)) is mirrored
    inside _lift_group's compiled batch lift — change them TOGETHER.
    """
    mesh = ps.mesh
    assert mesh is not None
    L = _local_member_count(ps)
    sharding = NamedSharding(mesh, P(_AXIS))
    stacked = _is_stacked(x, ps, L)
    if isinstance(x, jax.Array) and x.sharding == sharding and stacked:
        return x, True
    arr = jnp.asarray(x)
    T.check_supported_dtype(arr.dtype)
    if stacked:
        local = arr
    else:
        # A plain tensor is "this rank's tensor". When this process owns
        # L > 1 slots (single controller over many devices), replicate it to
        # every local slot — all emulated ranks contribute the same value.
        local = jnp.broadcast_to(arr[None], (max(L, 1),) + arr.shape)
    if jax.process_count() == 1:
        return jax.device_put(local, sharding), stacked
    # Multi-process: assemble the global array from per-slot ON-DEVICE
    # shards — no device→host→device round trip on the hot path.
    k = ps.size()
    global_shape = (k,) + tuple(local.shape[1:])
    my_devs = [d for d in mesh.devices.flat if d.process_index == pidx_of()]
    shards = [jax.device_put(local[i:i + 1], d)
              for i, d in enumerate(my_devs)]
    return jax.make_array_from_single_device_arrays(
        global_shape, sharding, shards), stacked


def _lift_group(tensors: Sequence[Any], ps: ProcessSet):
    """Lift a group of per-rank tensors to their global form — the one
    entry point for grouped ops.

    Single-process, for eligible tensors: ONE compiled program raises
    the whole group to its row-sharded form (out_shardings does the
    placement), collapsing 2N+1 dispatches to ~2 (per-dispatch host
    overhead is the dominant cost of eager grouped ops). COMMITTED arrays
    (outputs of previous collectives via _from_global, or user
    device_put-pinned inputs) cannot enter a jit whose out_shardings
    spans other devices ("incompatible devices"), so they take the
    per-tensor _to_global path, as does multi-process mode."""
    if jax.process_count() != 1:
        pairs = [_to_global(t, ps) for t in tensors]
        return [p[0] for p in pairs], [p[1] for p in pairs]
    mesh = ps.mesh
    assert mesh is not None
    L = _local_member_count(ps)
    sharding = NamedSharding(mesh, P(_AXIS))
    flags = []
    need: List[int] = []
    outs: List[Any] = [None] * len(tensors)
    arrs: List[Any] = [None] * len(tensors)
    for i, t in enumerate(tensors):
        stacked = _is_stacked(t, ps, L)
        flags.append(stacked)
        if isinstance(t, jax.Array):
            if t.sharding == sharding and stacked:
                outs[i] = t
                continue
            if getattr(t, "committed", getattr(t, "_committed", True)):
                outs[i] = _to_global(t, ps)[0]
                continue
        a = t if isinstance(t, (jax.Array, np.ndarray)) else jnp.asarray(t)
        T.check_supported_dtype(np.dtype(a.dtype))
        arrs[i] = a
        need.append(i)
    if need:
        key = ("lift", tuple((tuple(np.shape(arrs[i])),
                              str(arrs[i].dtype), flags[i])
                             for i in need), L, ps.cache_token)
        sub_flags = [flags[i] for i in need]

        def build() -> Callable:
            # MIRROR of _to_global's single-process lifting rule (stacked
            # pass-through vs broadcast to (L, *shape)) — keep in lockstep
            def lift(*xs):
                res = []
                for x, st in zip(xs, sub_flags):
                    res.append(x if st else jnp.broadcast_to(
                        x[None], (max(L, 1),) + x.shape))
                return tuple(res)
            return jax.jit(lift, out_shardings=(sharding,) * len(need))

        fn = _cache.get_or_build(key, build)
        lifted = fn(*[arrs[i] for i in need])
        for i, g in zip(need, lifted):
            outs[i] = g
    return outs, flags


def _from_global(y: jax.Array, stacked: bool) -> jax.Array:
    """Return the caller-facing view of a stacked global result."""
    if stacked:
        return y
    shards = y.addressable_shards
    assert shards, "result has no addressable shards on this process"
    shard = min(shards, key=lambda s: s.index[0].start or 0)
    return shard.data[0]


# --------------------------------------------------------------------------
# Reduction kernels (run inside shard_map; block shape (1, *tensor_shape))
# --------------------------------------------------------------------------

def _apply_reduce(block: jax.Array, op: T.ReduceOp, k: int,
                  prescale: float, postscale: float) -> jax.Array:
    """One rank's fused reduce body. block: (1, *shape)."""
    x = block
    if prescale != 1.0:
        x = x * jnp.asarray(prescale, x.dtype)
    if op in (T.ReduceOp.SUM, T.ReduceOp.AVERAGE):
        y = lax.psum(x, _AXIS)
        if op == T.ReduceOp.AVERAGE:
            if jnp.issubdtype(y.dtype, jnp.integer):
                y = y // jnp.asarray(k, y.dtype)
            else:
                y = y / jnp.asarray(k, y.dtype)
    elif op == T.ReduceOp.MIN:
        y = lax.pmin(x, _AXIS)
    elif op == T.ReduceOp.MAX:
        y = lax.pmax(x, _AXIS)
    elif op == T.ReduceOp.PRODUCT:
        g = lax.all_gather(x, _AXIS, axis=0)  # (k, 1, *shape)
        y = jnp.prod(g, axis=0)
    elif op == T.ReduceOp.ADASUM:
        from horovod_tpu.ops import adasum as adasum_mod
        y = adasum_mod.adasum_reduce_block(
            x, _AXIS, k, halving=topology.state().config.adasum_halving)
    else:
        raise HorovodTpuError(f"unsupported reduce op {op}")
    if postscale != 1.0:
        y = y * jnp.asarray(postscale, y.dtype)
    return y


def _replicated_reduce_one(x: jax.Array, op: T.ReduceOp, k: int,
                           prescale: float, postscale: float) -> jax.Array:
    """_apply_reduce's algebra when all k contributions are IDENTICAL.

    Single-controller mode with a non-stacked input means every emulated
    rank contributes the same tensor, so the collective has a closed
    form: sum = k·x, average/min/max = x, product = x^k. Computing it
    directly skips the per-tensor lift (broadcast + device_put — two
    dispatches EACH, which dominates eager-optimizer steps) and the
    fused psum program entirely.
    Semantics match _apply_reduce exactly, including integer-average
    flooring and pre/post scaling order.
    """
    if prescale != 1.0:
        x = x * jnp.asarray(prescale, x.dtype)
    if op == T.ReduceOp.SUM:
        y = x * jnp.asarray(k, x.dtype)
    elif op == T.ReduceOp.AVERAGE:
        if jnp.issubdtype(x.dtype, jnp.integer):
            y = (x * jnp.asarray(k, x.dtype)) // jnp.asarray(k, x.dtype)
        else:
            y = x
    elif op in (T.ReduceOp.MIN, T.ReduceOp.MAX):
        y = x
    elif op == T.ReduceOp.PRODUCT:
        y = x ** k
    elif op == T.ReduceOp.ADASUM:
        # Adasum of identical vectors is the vector itself: combine(a,a)
        # has dot = |a|^2 = na = nb, so a·(1 - dot/(2na)) + a·(1 -
        # dot/(2nb)) = a — at every VHDD level (adasum.h:195's combine is
        # idempotent on equal inputs, and the non-pow2 fold likewise).
        y = x
    else:  # pragma: no cover - all ops handled above
        raise HorovodTpuError(f"unsupported replicated reduce {op}")
    if postscale != 1.0:
        y = y * jnp.asarray(postscale, y.dtype)
    return y


def _replicated_fast_ok(ps: ProcessSet, rop: T.ReduceOp, hm,
                        tensors) -> bool:
    """Eligibility for the identical-contributions closed form: one
    process (multi-process inputs genuinely differ per rank), no
    hierarchical mesh, and no stacked per-slot inputs. Adasum qualifies
    too — its combine is idempotent on identical inputs (see
    _replicated_reduce_one) — which matters because the full path's
    per-tensor lift dominates eager Adasum optimizer steps.
    HOROVOD_NO_REPLICATED_FAST=1 forces the full collective machinery
    (used by benchmarks that measure it)."""
    from horovod_tpu.common.config import _env_bool

    if _env_bool("HOROVOD_NO_REPLICATED_FAST"):
        return False
    if jax.process_count() != 1 or hm is not None:
        return False
    L = _local_member_count(ps)
    return not any(_is_stacked(t, ps, L) for t in tensors)


def _builder_allreduce(mesh: Mesh, k: int, op: T.ReduceOp,
                       prescale: float, postscale: float,
                       num_tensors: int, donate: bool) -> Callable:
    def body(*blocks):
        outs = [_apply_reduce(b, op, k, prescale, postscale) for b in blocks]
        return tuple(outs) if num_tensors > 1 else outs[0]

    specs_in = (P(_AXIS),) * num_tensors
    specs_out = (P(_AXIS),) * num_tensors if num_tensors > 1 else P(_AXIS)
    fn = jax.shard_map(body, mesh=mesh, in_specs=specs_in,
                       out_specs=specs_out, check_vma=False)
    donate_argnums = tuple(range(num_tensors)) if donate else ()
    return jax.jit(fn, donate_argnums=donate_argnums)


# --------------------------------------------------------------------------
# Hierarchical (ici × dcn) variants
# --------------------------------------------------------------------------

_HIER_SPEC = P(("dcn", "ici"))  # dim 0 sharded over both axes, dcn-major —
# row r lands on the same device as the flat P("hvd") layout, so inputs
# lifted by _to_global need no resharding.


def _hier_usable(ps: ProcessSet) -> Optional[Mesh]:
    """The ("dcn","ici") mesh if hierarchical mode applies to this set."""
    if ps.ranks is not None:  # sub-sets keep the flat path
        return None
    return topology.state().hier_mesh


def _apply_reduce_hier(block: jax.Array, op: T.ReduceOp, k: int,
                       k_ici: int, prescale: float,
                       postscale: float) -> jax.Array:
    """ReduceScatter over ici → Allreduce over dcn → Allgather over ici.

    The reference's NCCLHierarchicalAllreduce structure
    (nccl_operations.cc:308: intra-node ncclReduceScatter → cross-node
    MPI_Allreduce → intra-node ncclAllgather), expressed as XLA
    collectives over the two mesh axes: only 1/k_ici of the payload
    crosses the slow dcn axis per rank.
    """
    x = block[0]
    if prescale != 1.0:
        x = x * jnp.asarray(prescale, x.dtype)
    v = x.reshape(-1)
    n = v.shape[0]
    pad = -n % k_ici
    if pad:
        v = jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
    s = lax.psum_scatter(v, "ici", scatter_dimension=0, tiled=True)
    s = lax.psum(s, "dcn")
    v = lax.all_gather(s, "ici", axis=0, tiled=True)
    if pad:
        v = v[:n]
    y = v.reshape(x.shape)
    if op == T.ReduceOp.AVERAGE:
        if jnp.issubdtype(y.dtype, jnp.integer):
            y = y // jnp.asarray(k, y.dtype)
        else:
            y = y / jnp.asarray(k, y.dtype)
    if postscale != 1.0:
        y = y * jnp.asarray(postscale, y.dtype)
    return y[None]


def _builder_allreduce_hier(hmesh: Mesh, k: int, op: T.ReduceOp,
                            prescale: float, postscale: float,
                            donate: bool) -> Callable:
    k_ici = hmesh.shape["ici"]

    def body(block):
        return _apply_reduce_hier(block, op, k, k_ici, prescale, postscale)

    fn = jax.shard_map(body, mesh=hmesh, in_specs=_HIER_SPEC,
                       out_specs=_HIER_SPEC, check_vma=False)
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


# --------------------------------------------------------------------------
# Public eager API
# --------------------------------------------------------------------------

def allreduce(tensor: Any,
              average: Optional[bool] = None,
              name: Optional[str] = None,
              op: Any = None,
              prescale_factor: float = 1.0,
              postscale_factor: float = 1.0,
              process_set: Optional[ProcessSet] = None,
              donate: bool = False) -> jax.Array:
    """Reduce a per-rank tensor across the process set.

    Reference API: hvd.allreduce (horovod/torch/mpi_ops.py:260,
    EnqueueTensorAllreduce operations.cc:1408). `average`/`op` semantics
    match: default AVERAGE.
    """
    ps = _resolve_ps(process_set)
    cfg = topology.state().config
    rop = _normalize_op(average, op)
    donate = donate or cfg.donate_buffers
    k = ps.size()
    hm = _hier_usable(ps) if (cfg.hierarchical_allreduce
                              and rop in (T.ReduceOp.SUM,
                                          T.ReduceOp.AVERAGE)) else None
    if _replicated_fast_ok(ps, rop, hm, (tensor,)):
        shape = tuple(np.shape(tensor))
        # np.result_type on a LIST parses it as a dtype spec (numpy 2.x);
        # np.asarray handles lists/scalars/arrays uniformly.
        dtype = tensor.dtype if hasattr(tensor, "dtype") \
            else np.asarray(tensor).dtype
        T.check_supported_dtype(np.dtype(dtype))
        key = ("ar_rep", shape, str(dtype), int(rop), ps.cache_token,
               float(prescale_factor), float(postscale_factor), k)
        # Output committed to the set's first mesh device — the same
        # placement _from_global's shard view gives on the full path
        # (subset process sets may exclude the default device).
        out_sh = jax.sharding.SingleDeviceSharding(
            ps.mesh.devices.flat[0])
        fn = _cache.get_or_build(key, lambda: jax.jit(
            lambda x: _replicated_reduce_one(
                x, rop, k, prescale_factor, postscale_factor),
            out_shardings=out_sh))
        _consistency(f"allreduce(shape={(k,) + shape},dtype={dtype},"
                     f"op={int(rop)},ps={ps.process_set_id})", ps,
                     name=name or "allreduce")
        with _instrument(name or "allreduce", "ALLREDUCE",
                         axis=getattr(ps, "mesh_axis", None),
                         nbytes_fn=lambda: (
                             (math.prod(shape) * k *
                              _dtype_info(dtype)[0]),
                             _dtype_info(dtype)[1])):
            return _execute(fn, jnp.asarray(tensor))
    g, stacked = _to_global(tensor, ps)
    key = ("ar", g.shape, str(g.dtype), int(rop), ps.cache_token,
           float(prescale_factor), float(postscale_factor), bool(donate),
           hm is not None,
           bool(cfg.adasum_halving) and rop == T.ReduceOp.ADASUM)
    if hm is not None:
        fn = _cache.get_or_build(key, lambda: _builder_allreduce_hier(
            hm, k, rop, prescale_factor, postscale_factor, donate))
    else:
        fn = _cache.get_or_build(key, lambda: _builder_allreduce(
            ps.mesh, k, rop, prescale_factor, postscale_factor, 1, donate))
    _consistency(f"allreduce(shape={g.shape},dtype={g.dtype},op={int(rop)},"
                 f"ps={ps.process_set_id})", ps, name=name or "allreduce")
    with _instrument(name or "allreduce", "ALLREDUCE", arrays=(g,),
                     axis=getattr(ps, "mesh_axis", None)):
        return _from_global(_execute(fn, g), stacked)


def grouped_allreduce(tensors: Sequence[Any],
                      average: Optional[bool] = None,
                      name: Optional[str] = None,
                      op: Any = None,
                      prescale_factor: float = 1.0,
                      postscale_factor: float = 1.0,
                      process_set: Optional[ProcessSet] = None) -> List[jax.Array]:
    """Reduce a group of tensors atomically, fused into ≤-threshold buckets.

    Reference: EnqueueTensorAllreduces (operations.cc:1436) + FuseResponses
    (controller.cc:901) + the fusion buffer. Here the group is one XLA
    program: tensors are bucketed (fusion.py) and each bucket is one psum.
    """
    ps = _resolve_ps(process_set)
    rop = _normalize_op(average, op)
    if not tensors:
        return []
    k = ps.size()
    cfg = topology.state().config
    hm = _hier_usable(ps) if (cfg.hierarchical_allreduce
                              and rop in (T.ReduceOp.SUM,
                                          T.ReduceOp.AVERAGE)) else None
    if _replicated_fast_ok(ps, rop, hm, tensors):
        shapes = tuple(tuple(np.shape(t)) for t in tensors)
        # np.asarray, not np.result_type: the latter parses a list input
        # as a dtype spec on numpy 2.x
        dtypes = tuple(str(t.dtype) if hasattr(t, "dtype")
                       else str(np.asarray(t).dtype) for t in tensors)
        for d in dtypes:  # same gate _to_global applies on the full path
            T.check_supported_dtype(np.dtype(d))
        key = ("gar_rep", shapes, dtypes, int(rop), ps.cache_token,
               float(prescale_factor), float(postscale_factor), k)
        out_sh = jax.sharding.SingleDeviceSharding(
            ps.mesh.devices.flat[0])

        def build_fast() -> Callable:
            def body(*xs):
                return tuple(_replicated_reduce_one(
                    x, rop, k, prescale_factor, postscale_factor)
                    for x in xs)
            return jax.jit(body, out_shardings=out_sh)

        fn = _cache.get_or_build(key, build_fast)
        _consistency(f"grouped_allreduce(n={len(tensors)},shapes="
                     f"{[(k,) + s for s in shapes]},op={int(rop)},"
                     f"ps={ps.process_set_id})", ps,
                     name=name or "grouped_allreduce")
        with _instrument(name or "grouped_allreduce", "ALLREDUCE",
                         ntensors=len(tensors),
                         axis=getattr(ps, "mesh_axis", None),
                         nbytes_fn=lambda: (
                             sum(math.prod(s) * k * _dtype_info(d)[0]
                                 for s, d in zip(shapes, dtypes)),
                             dtypes[0] if dtypes else "")):
            outs = _execute(fn, *[jnp.asarray(t) for t in tensors])
        return list(outs)
    gs, stackeds = _lift_group(tensors, ps)
    from horovod_tpu.ops import fusion
    eff_thresh = fusion.effective_threshold(cfg.fusion_threshold_bytes,
                                            cfg.bucket_cap_bytes)
    key = ("gar", tuple((g.shape, str(g.dtype)) for g in gs), int(rop),
           ps.cache_token, float(prescale_factor), float(postscale_factor),
           eff_thresh, cfg.bucket_reverse, cfg.disable_group_fusion,
           hm is not None,
           bool(cfg.adasum_halving) and rop == T.ReduceOp.ADASUM)

    def build() -> Callable:
        mesh_ = hm if hm is not None else ps.mesh
        spec = _HIER_SPEC if hm is not None else P(_AXIS)
        if hm is not None:
            k_ici = hm.shape["ici"]
            reduce_one = lambda b: _apply_reduce_hier(  # noqa: E731
                b, rop, k, k_ici, prescale_factor, postscale_factor)
        else:
            reduce_one = lambda b: _apply_reduce(  # noqa: E731
                b, rop, k, prescale_factor, postscale_factor)

        def body(*blocks):
            if cfg.disable_group_fusion or rop in (T.ReduceOp.ADASUM,):
                return tuple(reduce_one(b) for b in blocks)
            return fusion.fused_reduce_blocks(
                blocks, reduce_one, eff_thresh,
                reverse=cfg.bucket_reverse)

        fn = jax.shard_map(body, mesh=mesh_,
                           in_specs=(spec,) * len(gs),
                           out_specs=(spec,) * len(gs),
                           check_vma=False)
        return jax.jit(fn)

    fn = _cache.get_or_build(key, build)
    _consistency(f"grouped_allreduce(n={len(gs)},shapes="
                 f"{[tuple(g.shape) for g in gs]},op={int(rop)},"
                 f"ps={ps.process_set_id})", ps,
                 name=name or "grouped_allreduce")
    with _instrument(name or "grouped_allreduce", "ALLREDUCE",
                     arrays=tuple(gs), ntensors=len(gs),
                     axis=getattr(ps, "mesh_axis", None)):
        outs = _execute(fn, *gs)
    return [_from_global(o, s) for o, s in zip(outs, stackeds)]


# --------------------------------------------------------------------------
# Bucketed, pipelined allreduce (the backward-overlap path; docs/perf.md)
# --------------------------------------------------------------------------

class _BucketStats:
    """Cross-thread bucket-scheduler accounting (dispatch counters + the
    last measured overlap fraction, read by metrics/tests)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.dispatched = 0  # guarded-by: _lock
        self.profiled_calls = 0  # guarded-by: _lock
        self.last_overlap: float = 0.0  # guarded-by: _lock

    def record(self, n_buckets: int, overlap) -> None:
        with self._lock:
            self.dispatched += n_buckets
            if overlap is not None:
                self.profiled_calls += 1
                self.last_overlap = float(overlap)

    def snapshot(self) -> Tuple[int, int, float]:
        with self._lock:
            return self.dispatched, self.profiled_calls, self.last_overlap


_bucket_stats = _BucketStats()
# Per-thread (nbytes, seconds) samples of the most recent PROFILED call —
# thread-local on purpose: concurrent callers must not splice each other's
# timing vectors, and the consumer (the optimizer's tuner hook) reads it
# on the same thread right after its own call returns.
_bucket_tls = threading.local()


def last_bucket_timings() -> List[Tuple[int, float]]:
    """(global_payload_bytes, seconds) per bucket of this thread's most
    recent profiled `bucketed_allreduce` (empty if that call ran fully
    async). Feeds the online bucket tuner (core/autotune.py)."""
    return list(getattr(_bucket_tls, "timings", ()))


def bucket_overlap_stats() -> Tuple[int, int, float]:
    """(buckets_dispatched, profiled_calls, last_overlap_fraction)."""
    return _bucket_stats.snapshot()


def bucketed_allreduce(tensors: Sequence[Any],
                       average: Optional[bool] = None,
                       name: Optional[str] = None,
                       op: Any = None,
                       prescale_factor: float = 1.0,
                       postscale_factor: float = 1.0,
                       process_set: Optional[ProcessSet] = None,
                       profile: Optional[bool] = None) -> List[jax.Array]:
    """Reduce a group of tensors as independently dispatched fusion buckets.

    Where `grouped_allreduce` compiles the whole group into ONE XLA
    program (every bucket's psum fenced by the same program boundary),
    this path compiles one program PER bucket and dispatches them
    back-to-back without blocking: JAX's async dispatch keeps several
    buckets' ICI transfers in flight concurrently — the role of the
    reference's background thread draining the fusion buffer
    (operations.cc RunLoopOnce), and the eager counterpart of the
    in-jit overlap `reduce_gradients_in_jit` gets from the XLA scheduler.
    Oversize tensors are chunked across buckets (ops/fusion.py) and
    reassembled here.

    `profile=True` (or HOROVOD_BUCKET_PROFILE=1) forces completion of
    each bucket and records per-bucket wall times plus an
    `overlap_fraction` estimate (1 - wall_window / sum_of_bucket_spans,
    i.e. the fraction of in-flight time shared with another bucket) —
    the samples the online bucket tuner and the
    `horovod_overlap_fraction` gauge consume.

    Falls back to `grouped_allreduce` where per-bucket dispatch cannot
    help: single tensor, Adasum (never fused), hierarchical meshes,
    HOROVOD_DISABLE_GROUP_FUSION, HOROVOD_BUCKET_PIPELINE=0, or the
    replicated fast path.
    """
    ps = _resolve_ps(process_set)
    rop = _normalize_op(average, op)
    if not tensors:
        return []
    cfg = topology.state().config
    hm = _hier_usable(ps) if (cfg.hierarchical_allreduce
                              and rop in (T.ReduceOp.SUM,
                                          T.ReduceOp.AVERAGE)) else None
    if (len(tensors) == 1 or rop == T.ReduceOp.ADASUM
            or cfg.disable_group_fusion or hm is not None
            or not cfg.bucket_pipeline
            or _replicated_fast_ok(ps, rop, hm, tensors)):
        _bucket_tls.timings = ()
        return grouped_allreduce(
            tensors, name=name, op=rop, prescale_factor=prescale_factor,
            postscale_factor=postscale_factor, process_set=ps)
    from horovod_tpu.ops import fusion

    k = ps.size()
    gs, stackeds = _lift_group(tensors, ps)
    eff = fusion.effective_threshold(cfg.fusion_threshold_bytes,
                                     cfg.bucket_cap_bytes)
    metas = [(tuple(g.shape[1:]), str(g.dtype)) for g in gs]
    plan = fusion.plan_buckets(metas, eff, reverse=cfg.bucket_reverse)
    # The descriptor embeds the effective threshold AND the plan
    # fingerprint: ranks whose bucket thresholds diverged (a broken tuner
    # sync) dispatch visibly different descriptors, so the consistency
    # checker / fingerprint verifier name the divergence instead of the
    # mismatched programs deadlocking.
    _consistency(
        f"bucketed_allreduce(n={len(gs)},shapes="
        f"{[tuple(g.shape) for g in gs]},op={int(rop)},thresh={eff},"
        f"plan={fusion.plan_signature(plan)},ps={ps.process_set_id})",
        ps, name=name or "bucketed_allreduce")
    if profile is None:
        profile = cfg.bucket_profile
    base = name or "bucketed_allreduce"
    tl = topology.state().timeline
    records = []  # (bucket, members, layout, outs)
    launches: List[float] = []
    with _instrument(base, "ALLREDUCE", arrays=tuple(gs),
                     ntensors=len(gs), axis=getattr(ps, "mesh_axis", None)):
        for bi, bucket in enumerate(plan):
            members: List[int] = []
            pos_of: Dict[int, int] = {}
            layout: List[Tuple[int, int, int, bool]] = []
            for it in bucket.items:
                if it.index not in pos_of:
                    pos_of[it.index] = len(members)
                    members.append(it.index)
                whole = it.start == 0 and it.size == int(
                    np.prod(gs[it.index].shape[1:], dtype=np.int64))
                layout.append((pos_of[it.index], it.start, it.size, whole))
            lay = tuple(layout)
            key = ("bar",
                   tuple((tuple(gs[i].shape), str(gs[i].dtype))
                         for i in members),
                   lay, int(rop), ps.cache_token,
                   float(prescale_factor), float(postscale_factor))
            first_build = key not in _cache._cache

            def build(lay=lay, nmem=len(members)) -> Callable:
                def body(*blocks):
                    segs = [blocks[pos].reshape(1, -1)[:, s:s + n]
                            for pos, s, n, _w in lay]
                    fused = segs[0] if len(segs) == 1 \
                        else jnp.concatenate(segs, axis=1)
                    red = _apply_reduce(fused, rop, k, prescale_factor,
                                        postscale_factor)
                    outs, off = [], 0
                    for pos, _s, n, whole in lay:
                        piece = red[:, off:off + n]
                        outs.append(piece.reshape(blocks[pos].shape)
                                    if whole else piece)
                        off += n
                    return tuple(outs) if len(lay) > 1 else outs[0]

                specs_out = (P(_AXIS),) * len(lay) if len(lay) > 1 \
                    else P(_AXIS)
                fn = jax.shard_map(body, mesh=ps.mesh,
                                   in_specs=(P(_AXIS),) * nmem,
                                   out_specs=specs_out, check_vma=False)
                return jax.jit(fn)

            fn = _cache.get_or_build(key, build)
            if first_build:
                # One ring event per DISTINCT bucket program (not per
                # dispatch — steady-state steps must not evict the
                # collective history hvddoctor merges).
                _flight.record(
                    "bucket", f"{base} b{bi}/{len(plan)} "
                    f"{bucket.nbytes >> 10}KB x{len(bucket.items)} "
                    f"{bucket.dtype} (new program)")
            if tl is not None:
                tl.span_begin(f"{base}/b{bi}", "ALLREDUCE")
            launches.append(time.perf_counter())
            outs = _execute(fn, *[gs[i] for i in members])
            if tl is not None:
                tl.span_end(f"{base}/b{bi}", "ALLREDUCE")
            if len(layout) == 1:
                outs = (outs,)
            records.append((bucket, members, layout, outs))
        timings: List[Tuple[int, float]] = []
        overlap = None
        if profile and records:
            completes: List[float] = []
            for bi, (_, _, _, outs) in enumerate(records):
                # The complete half of the per-bucket track: the launch
                # span above covers dispatch; this WAIT span ends when
                # the bucket's collective actually finished, so a trace
                # shows the in-flight windows overlapping.
                if tl is not None:
                    tl.span_begin(f"{base}/b{bi}", "WAIT_FOR_DATA")
                jax.block_until_ready(outs)
                if tl is not None:
                    tl.span_end(f"{base}/b{bi}", "WAIT_FOR_DATA")
                completes.append(time.perf_counter())
            spans = [c - l for l, c in zip(launches, completes)]
            total = completes[-1] - launches[0]
            ssum = sum(spans)
            if len(spans) > 1 and ssum > 0:
                overlap = max(0.0, min(1.0, 1.0 - total / ssum))
            # Wire (per-rank) bucket bytes, the quantity the fusion
            # threshold bounds — what the bucket tuner's size classes key on.
            timings = [(rec[0].nbytes, s)
                       for rec, s in zip(records, spans)]
            if len(spans) > 1:
                med = sorted(spans)[len(spans) // 2]
                for bi, (rec, s) in enumerate(zip(records, spans)):
                    if med > 0 and s > 3.0 * med and s > 0.005:
                        _flight.record(
                            "bucket",
                            f"SLOW {base} b{bi}/{len(plan)} "
                            f"{rec[0].nbytes >> 10}KB took {s * 1e3:.1f}ms "
                            f"(median {med * 1e3:.1f}ms)")
        _bucket_tls.timings = tuple(timings)
        from horovod_tpu.observability import metrics as _m
        if _m.registry().enabled:
            mx = _mx()
            mx["bucket_n"].inc(len(plan))
            for bucket in plan:
                mx["bucket_bytes"].observe(bucket.nbytes * k)
            for _, s in timings:
                mx["bucket_secs"].observe(s)
            if overlap is not None:
                mx["overlap"].set(overlap)
        _bucket_stats.record(len(plan), overlap)
    results: List[Optional[jax.Array]] = [None] * len(gs)
    chunk_map: List[List[Tuple[int, jax.Array]]] = [[] for _ in gs]
    for _, members, layout, outs in records:
        for (pos, start, _n, whole), o in zip(layout, outs):
            if whole:
                results[members[pos]] = o
            else:
                chunk_map[members[pos]].append((start, o))
    for i, g in enumerate(gs):
        if results[i] is None:
            parts = [p for _, p in
                     sorted(chunk_map[i], key=lambda t: t[0])]
            flat = parts[0] if len(parts) == 1 \
                else jnp.concatenate(parts, axis=1)
            results[i] = flat.reshape(g.shape).astype(g.dtype)
    return [_from_global(r, s) for r, s in zip(results, stackeds)]


def broadcast(tensor: Any, root_rank: int,
              name: Optional[str] = None,
              process_set: Optional[ProcessSet] = None) -> jax.Array:
    """Broadcast the root rank's tensor to every rank in the set.

    Reference: EnqueueTensorBroadcast (operations.cc:1710).
    """
    ps = _resolve_ps(process_set)
    g, stacked = _to_global(tensor, ps)
    root = ps.rank_index(root_rank)
    k = ps.size()
    key = ("bc", g.shape, str(g.dtype), root, ps.cache_token)

    def build() -> Callable:
        def body(block):
            gathered = lax.all_gather(block, _AXIS, axis=0)  # (k, 1, *shape)
            return gathered[root]

        fn = jax.shard_map(body, mesh=ps.mesh, in_specs=P(_AXIS),
                           out_specs=P(_AXIS), check_vma=False)
        return jax.jit(fn)

    fn = _cache.get_or_build(key, build)
    _consistency(f"broadcast(shape={g.shape},dtype={g.dtype},root={root},"
                 f"ps={ps.process_set_id})", ps, name=name or "broadcast")
    with _instrument(name or "broadcast", "BROADCAST", arrays=(g,)):
        return _from_global(_execute(fn, g), stacked)


def allgather(tensor: Any, name: Optional[str] = None,
              process_set: Optional[ProcessSet] = None) -> jax.Array:
    """Concatenate per-rank tensors along dim 0; first dims may differ.

    Reference: EnqueueTensorAllgather (operations.cc:1551). Uneven first
    dimensions are negotiated with a size-exchange collective first (the
    role of the controller's response construction, controller.cc:447+).
    """
    ps = _resolve_ps(process_set)
    g, stacked = _to_global(tensor, ps)
    if g.ndim < 2:
        raise HorovodTpuError(
            "allgather requires per-rank tensors with at least one dimension")
    k = ps.size()
    # Consistency check BEFORE the blocking size exchange — a rank calling a
    # different collective would otherwise deadlock inside _exchange_sizes
    # before the diagnostic could fire. The signature excludes dim 0, which
    # may legitimately differ per rank (uneven allgather).
    _consistency(f"allgather(rest={tuple(g.shape[2:])},ndim={g.ndim},"
                 f"dtype={g.dtype},ps={ps.process_set_id})", ps,
                 name=name or "allgather")
    if stacked:
        # Single-controller stacked input: all rows share a shape — even path.
        sizes = (int(g.shape[1]),) * k
    else:
        sizes = _exchange_sizes(int(g.shape[1]), ps)
    max_d0 = max(sizes) if sizes else 0
    cfg = topology.state().config
    hm = _hier_usable(ps) if (cfg.hierarchical_allgather
                              and len(set(sizes)) == 1) else None
    key = ("ag", g.shape, str(g.dtype), tuple(sizes), ps.cache_token,
           hm is not None)

    def build() -> Callable:
        total = sum(sizes)

        if hm is not None:
            # Even sizes: gather within the fast ici axis first, then
            # across dcn — dcn-major rank order matches the flat layout
            # (reference structure: hierarchical allgather,
            # HOROVOD_HIERARCHICAL_ALLGATHER).
            def hier_body(block):
                x = block[0]
                g1 = lax.all_gather(x, "ici", axis=0, tiled=True)
                g2 = lax.all_gather(g1, "dcn", axis=0, tiled=True)
                return g2[None]

            fn = jax.shard_map(hier_body, mesh=hm, in_specs=_HIER_SPEC,
                               out_specs=_HIER_SPEC, check_vma=False)
            return jax.jit(fn)

        def body(block):
            x = block[0]  # (d0_local, *rest) — same static d0 across ranks here
            pad = max_d0 - x.shape[0]
            if pad:
                x = jnp.concatenate(
                    [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
            gathered = lax.all_gather(x, _AXIS, axis=0)  # (k, max_d0, *rest)
            pieces = [lax.slice_in_dim(gathered[i], 0, sizes[i], axis=0)
                      for i in range(k)]
            out = jnp.concatenate(pieces, axis=0)
            assert out.shape[0] == total
            return out[None]

        fn = jax.shard_map(body, mesh=ps.mesh, in_specs=P(_AXIS),
                           out_specs=P(_AXIS), check_vma=False)
        return jax.jit(fn)

    if len(set(sizes)) > 1 and not stacked:
        # Uneven: each rank pads its own tensor to max_d0 before the shared
        # program runs (shapes must agree across the SPMD program). After
        # the pre-pad, `build`'s in-program pad is a no-op and the cache key
        # (which includes the padded shape + per-rank sizes) distinguishes
        # this case — the same builder serves both paths.
        pad = max_d0 - (g.shape[1])
        if pad > 0:
            g = jnp.concatenate(
                [g, jnp.zeros((g.shape[0], pad) + g.shape[2:], g.dtype)], axis=1)
        key = ("ag", g.shape, str(g.dtype), tuple(sizes), ps.cache_token)
    fn = _cache.get_or_build(key, build)
    with _instrument(name or "allgather", "ALLGATHER", arrays=(g,)):
        return _from_global(_execute(fn, g), stacked)


def reducescatter(tensor: Any, op: Any = T.ReduceOp.AVERAGE,
                  name: Optional[str] = None,
                  prescale_factor: float = 1.0,
                  postscale_factor: float = 1.0,
                  process_set: Optional[ProcessSet] = None) -> jax.Array:
    """Reduce across ranks, then scatter slices of dim 0.

    Reference: EnqueueTensorReducescatter (operations.cc:1774). Rank i
    receives rows [sum(sizes[:i]), sum(sizes[:i+1])) where sizes follow
    Horovod's uneven rule: d0//k + (1 if i < d0%k else 0).
    """
    ps = _resolve_ps(process_set)
    rop = _normalize_op(None, op) if op is not None else T.ReduceOp.AVERAGE
    if rop not in (T.ReduceOp.SUM, T.ReduceOp.AVERAGE):
        raise HorovodTpuError("reducescatter supports SUM and AVERAGE only")
    g, stacked = _to_global(tensor, ps)
    k = ps.size()
    d0 = int(g.shape[1])
    even = (d0 % k == 0)
    key = ("rs", g.shape, str(g.dtype), int(rop), even, ps.cache_token,
           float(prescale_factor), float(postscale_factor))

    def build() -> Callable:
        def body(block):
            return _rs_block(block[0], k, rop, prescale_factor,
                             postscale_factor, d0)[None]

        fn = jax.shard_map(body, mesh=ps.mesh, in_specs=P(_AXIS),
                           out_specs=P(_AXIS), check_vma=False)
        return jax.jit(fn)

    fn = _cache.get_or_build(key, build)
    _consistency(f"reducescatter(shape={g.shape},dtype={g.dtype},"
                 f"op={int(rop)},ps={ps.process_set_id})", ps,
                 name=name or "reducescatter")
    with _instrument(name or "reducescatter", "REDUCESCATTER",
                     arrays=(g,)):
        out = _execute(fn, g)
    return _rs_trim(out, stacked, d0, k, ps)


def _rs_block(x, k: int, rop, prescale_factor: float,
              postscale_factor: float, d0: int):
    """Per-tensor reduce-scatter body (shared by single + grouped paths)."""
    if prescale_factor != 1.0:
        x = x * jnp.asarray(prescale_factor, x.dtype)
    if d0 % k == 0:
        y = lax.psum_scatter(x, _AXIS, scatter_dimension=0, tiled=True)
        if rop == T.ReduceOp.AVERAGE:
            y = y / jnp.asarray(k, y.dtype)
        if postscale_factor != 1.0:
            y = y * jnp.asarray(postscale_factor, y.dtype)
        return y
    # Uneven: full psum then per-rank slice of varying size. The slice
    # sizes differ per rank, which SPMD can't express with one static
    # shape — pad every slice to ceil; the wrapper trims on the way out.
    y = lax.psum(x, _AXIS)
    if rop == T.ReduceOp.AVERAGE:
        y = y / jnp.asarray(k, y.dtype)
    if postscale_factor != 1.0:
        y = y * jnp.asarray(postscale_factor, y.dtype)
    idx = lax.axis_index(_AXIS)
    big = d0 // k + 1
    rem = d0 % k
    start = jnp.minimum(idx, rem) * big + \
        jnp.maximum(idx - rem, 0) * (big - 1)
    return lax.dynamic_slice_in_dim(
        jnp.concatenate(
            [y, jnp.zeros((big,) + y.shape[1:], y.dtype)], axis=0),
        start, big, axis=0)


def _rs_trim(out, stacked: bool, d0: int, k: int, ps: ProcessSet):
    """Undo the uneven-path padding (shared by single + grouped paths)."""
    if d0 % k == 0:
        return _from_global(out, stacked)
    big = d0 // k + 1
    rem = d0 % k
    sizes = [big if i < rem else big - 1 for i in range(k)]
    if stacked:
        # Ragged per-rank sizes cannot stay stacked; trim on host view.
        return [out[i, :sizes[i]] for i in range(k)]
    my = _from_global(out, stacked)
    my_rank_in_set = ps.rank_index(topology.rank())
    return my[: sizes[my_rank_in_set]]


def grouped_reducescatter(tensors: Sequence[Any], op: Any = T.ReduceOp.AVERAGE,
                          name: Optional[str] = None,
                          prescale_factor: float = 1.0,
                          postscale_factor: float = 1.0,
                          process_set: Optional[ProcessSet] = None) -> List[Any]:
    """Atomic fused reduce-scatter of a group: ONE XLA program for all
    tensors (reference: grouped RS is an atomic fused response,
    tensorflow/mpi_ops.cc:1415 — not a loop of singles)."""
    ps = _resolve_ps(process_set)
    if not tensors:
        return []
    rop = _normalize_op(None, op) if op is not None else T.ReduceOp.AVERAGE
    if rop not in (T.ReduceOp.SUM, T.ReduceOp.AVERAGE):
        raise HorovodTpuError("reducescatter supports SUM and AVERAGE only")
    gs, stackeds = _lift_group(tensors, ps)
    k = ps.size()
    d0s = [int(g.shape[1]) for g in gs]
    key = ("grs", tuple((g.shape, str(g.dtype)) for g in gs), int(rop),
           ps.cache_token, float(prescale_factor), float(postscale_factor))

    def build() -> Callable:
        def body(*blocks):
            return tuple(
                _rs_block(b[0], k, rop, prescale_factor, postscale_factor,
                          d0s[i])[None]
                for i, b in enumerate(blocks))

        fn = jax.shard_map(body, mesh=ps.mesh,
                           in_specs=(P(_AXIS),) * len(gs),
                           out_specs=(P(_AXIS),) * len(gs), check_vma=False)
        return jax.jit(fn)

    fn = _cache.get_or_build(key, build)
    _consistency(f"grouped_reducescatter(n={len(gs)},shapes="
                 f"{[tuple(g.shape) for g in gs]},op={int(rop)},"
                 f"ps={ps.process_set_id})", ps,
                 name=name or "grouped_reducescatter")
    with _instrument(name or "grouped_reducescatter", "REDUCESCATTER",
                     arrays=tuple(gs), ntensors=len(gs)):
        outs = _execute(fn, *gs)
    return [_rs_trim(o, st, d0, k, ps)
            for o, st, d0 in zip(outs, stackeds, d0s)]


def grouped_allgather(tensors: Sequence[Any],
                      name: Optional[str] = None,
                      process_set: Optional[ProcessSet] = None) -> List[Any]:
    """Atomic fused allgather of a group: ONE XLA program and ONE size
    exchange for the whole group (reference: grouped allgather is an
    atomic fused response, tensorflow/mpi_ops.cc:788; the single-tensor
    path pays one blocking size exchange per call — the group pays one)."""
    ps = _resolve_ps(process_set)
    if not tensors:
        return []
    gs, stackeds = _lift_group(tensors, ps)
    for g in gs:
        if g.ndim < 2:
            raise HorovodTpuError(
                "allgather requires per-rank tensors with at least one "
                "dimension")
    k = ps.size()
    n = len(gs)
    _consistency(f"grouped_allgather(n={n},"
                 f"rests={[tuple(g.shape[2:]) for g in gs]},"
                 f"dtypes={[str(g.dtype) for g in gs]},"
                 f"ps={ps.process_set_id})", ps,
                 name=name or "grouped_allgather")
    if jax.process_count() == 1:
        sizes_matrix = np.tile(
            np.asarray([[int(g.shape[1]) for g in gs]], np.int64), (k, 1))
    else:
        sizes_matrix = _exchange_rows(
            np.asarray([int(g.shape[1]) for g in gs], np.int64), ps)
    max_d0 = sizes_matrix.max(axis=0)  # per tensor
    padded = []
    for i, g in enumerate(gs):
        pad = int(max_d0[i]) - int(g.shape[1])
        if pad > 0:
            g = jnp.concatenate(
                [g, jnp.zeros((g.shape[0], pad) + g.shape[2:], g.dtype)],
                axis=1)
        padded.append(g)
    cfg = topology.state().config
    all_even = all(len(set(sizes_matrix[:, i].tolist())) == 1
                   for i in range(n))
    hm = _hier_usable(ps) if (cfg.hierarchical_allgather
                              and all_even) else None
    key = ("gag", tuple((g.shape, str(g.dtype)) for g in padded),
           tuple(map(tuple, sizes_matrix.tolist())), ps.cache_token,
           hm is not None)

    def build() -> Callable:
        sm = sizes_matrix

        if hm is not None:
            # Even sizes: gather within the fast ici axis, then across dcn
            # — the same HOROVOD_HIERARCHICAL_ALLGATHER decomposition as
            # the single-tensor path, applied per group member.
            def hier_body(*blocks):
                outs = []
                for b in blocks:
                    g1 = lax.all_gather(b[0], "ici", axis=0, tiled=True)
                    g2 = lax.all_gather(g1, "dcn", axis=0, tiled=True)
                    outs.append(g2[None])
                return tuple(outs)

            fn = jax.shard_map(hier_body, mesh=hm,
                               in_specs=(_HIER_SPEC,) * n,
                               out_specs=(_HIER_SPEC,) * n, check_vma=False)
            return jax.jit(fn)

        def body(*blocks):
            outs = []
            for i, b in enumerate(blocks):
                gathered = lax.all_gather(b[0], _AXIS, axis=0)
                pieces = [lax.slice_in_dim(gathered[r], 0, int(sm[r, i]),
                                           axis=0) for r in range(k)]
                outs.append(jnp.concatenate(pieces, axis=0)[None])
            return tuple(outs)

        fn = jax.shard_map(body, mesh=ps.mesh, in_specs=(P(_AXIS),) * n,
                           out_specs=(P(_AXIS),) * n, check_vma=False)
        return jax.jit(fn)

    fn = _cache.get_or_build(key, build)
    with _instrument(name or "grouped_allgather", "ALLGATHER",
                     arrays=tuple(padded), ntensors=len(padded)):
        outs = _execute(fn, *padded)
    return [_from_global(o, st) for o, st in zip(outs, stackeds)]


def alltoall(tensor: Any, splits: Optional[Any] = None,
             name: Optional[str] = None,
             process_set: Optional[ProcessSet] = None) -> Tuple[jax.Array, jax.Array]:
    """Scatter dim-0 slices to every rank, gather received slices.

    Reference: EnqueueTensorAlltoall (operations.cc:1904). Returns
    (output, received_splits) like the reference torch API. With no
    `splits`, dim 0 must divide evenly by the set size.
    """
    ps = _resolve_ps(process_set)
    g, stacked = _to_global(tensor, ps)
    k = ps.size()
    if g.ndim < 2:
        # The stacked-input rule read a 1-D length-k tensor as k per-rank
        # SCALARS, which alltoall cannot split. The caller almost
        # certainly meant the classic one-element-per-peer alltoall —
        # re-lift as a replicated (k,) vector.
        g, stacked = _to_global(np.asarray(tensor)[None], ps)
        g = jnp.squeeze(g, axis=1) if g.ndim == 3 else g
        if g.ndim < 2:
            raise HorovodTpuError(
                "alltoall needs at least one dimension to split per rank")
    d0 = int(g.shape[1])
    if splits is None:
        if d0 % k:
            raise HorovodTpuError(
                f"alltoall without splits requires dim0 ({d0}) divisible by "
                f"set size ({k})")
        my_splits = np.full((k,), d0 // k, dtype=np.int64)
    else:
        my_splits = np.asarray(splits, dtype=np.int64)
        if my_splits.shape != (k,) or int(my_splits.sum()) != d0:
            raise HorovodTpuError("splits must have one entry per rank and "
                                  "sum to dim 0")

    # Consistency check BEFORE the blocking splits exchange (see allgather);
    # dim 0 = sum(splits) may legitimately differ per rank.
    _consistency(f"alltoall(rest={tuple(g.shape[2:])},ndim={g.ndim},"
                 f"dtype={g.dtype},ps={ps.process_set_id})", ps,
                 name=name or "alltoall")
    # Exchange the full splits matrix (controller's AlltoallGetRecvSplits,
    # controller.h:63). In stacked mode rows share `my_splits`.
    if stacked and splits is not None:
        raise HorovodTpuError(
            "stacked (single-controller) alltoall takes per-rank splits via "
            "a (k, k) splits matrix; pass splits=None or use multi-process")
    splits_matrix = np.tile(my_splits, (k, 1)) if (stacked or splits is None) \
        else _exchange_rows(my_splits, ps)

    recv_splits = splits_matrix[:, :]  # [src, dst]
    max_chunk = int(splits_matrix.max()) if splits_matrix.size else 0
    key = ("a2a", g.shape, str(g.dtype),
           tuple(map(tuple, splits_matrix.tolist())), ps.cache_token)

    def build() -> Callable:
        sm = jnp.asarray(splits_matrix)

        def body(block):
            x = block[0]  # (d0, *rest)
            idx = lax.axis_index(_AXIS)
            my = sm[idx]  # (k,) chunk sizes this rank sends
            starts = jnp.concatenate(
                [jnp.zeros((1,), my.dtype), jnp.cumsum(my)[:-1]])
            xpad = jnp.concatenate(
                [x, jnp.zeros((max_chunk,) + x.shape[1:], x.dtype)], axis=0)
            # One gather for all destinations — O(1) program size where a
            # per-destination dynamic-slice loop would be O(k) (matters at
            # 256 ranks).
            row_idx = starts[:, None] + \
                jnp.arange(max_chunk, dtype=starts.dtype)[None, :]
            chunks = xpad[row_idx]  # (k, max_chunk, *rest)
            recvd = lax.all_to_all(chunks, _AXIS, split_axis=0, concat_axis=0)
            # recvd[i] = chunk sent by rank i to me, padded to max_chunk.
            return recvd[None]

        fn = jax.shard_map(body, mesh=ps.mesh, in_specs=P(_AXIS),
                           out_specs=P(_AXIS), check_vma=False)
        return jax.jit(fn)

    fn = _cache.get_or_build(key, build)
    with _instrument(name or "alltoall", "ALLTOALL", arrays=(g,)):
        out = _execute(fn, g)  # (k_local_rows, k, max_chunk, *rest)

    def trim(rank_in_set: int, rowdata):
        pieces = [rowdata[i, : int(splits_matrix[i, rank_in_set])]
                  for i in range(k)]
        return jnp.concatenate(pieces, axis=0), \
            jnp.asarray(splits_matrix[:, rank_in_set])

    if stacked:
        results = [trim(i, out[i]) for i in range(k)]
        return results  # list of (output, recv_splits) per rank
    my_row = _from_global(out, stacked)
    my_rank_in_set = ps.rank_index(topology.rank())
    return trim(my_rank_in_set, my_row)


def barrier(process_set: Optional[ProcessSet] = None) -> None:
    """Block until every rank reaches the barrier.

    Reference: EnqueueBarrier (operations.cc:2020). A 1-element psum forces a
    full-mesh rendezvous; block_until_ready makes it synchronous host-side.
    """
    ps = _resolve_ps(process_set)
    key = ("barrier", ps.cache_token)

    def build() -> Callable:
        def body(block):
            return lax.psum(block, _AXIS)

        fn = jax.shard_map(body, mesh=ps.mesh, in_specs=P(_AXIS),
                           out_specs=P(_AXIS), check_vma=False)
        return jax.jit(fn)

    fn = _cache.get_or_build(key, build)
    L = max(1, _local_member_count(ps))
    ones = np.ones((L, 1), np.int32)
    g, _ = _to_global(ones if L > 1 else ones[0], ps)
    _consistency(f"barrier(ps={ps.process_set_id})", ps)
    # Blocking point: if another rank never arrives we hang here — exactly
    # what the stall inspector watches (reference: stall_inspector.cc).
    _stall_submit("barrier")
    try:
        with _instrument("barrier", "BARRIER"):
            jax.block_until_ready(_execute(fn, g))
    finally:
        _stall_done("barrier")


def synchronize(handle: Any) -> Any:
    """Wait for an async collective result (reference: mpi_ops.py:1269).

    JAX arrays are futures under async dispatch, so the handle IS the result.
    The wait runs under the stall watchdog (elastic mode: bounded by
    HOROVOD_STALL_SHUTDOWN_TIME_SECONDS → HorovodInternalError).
    """
    try:
        return _guarded_wait("synchronize",
                             lambda: jax.block_until_ready(handle))
    except Exception as e:
        if isinstance(e, HorovodInternalError):
            raise
        if topology.raw_state().config.elastic and is_comm_failure(e):
            raise HorovodInternalError(f"synchronize failed: {e}") from e
        raise


def poll(handle: Any) -> bool:
    """Non-blocking readiness check (reference: horovod_torch_poll)."""
    if hasattr(handle, "is_ready"):
        try:
            return bool(handle.is_ready())
        except Exception:
            pass
    return True


# Async aliases: JAX dispatch is already asynchronous; these exist for
# reference API parity (horovod/torch/mpi_ops.py allreduce_async etc.).
allreduce_async = allreduce
grouped_allreduce_async = grouped_allreduce
bucketed_allreduce_async = bucketed_allreduce
allgather_async = allgather
broadcast_async = broadcast
alltoall_async = alltoall
reducescatter_async = reducescatter


# --------------------------------------------------------------------------
# Helpers
# --------------------------------------------------------------------------

# In-flight named-operation registry (reference: TensorQueue's duplicate
# name detection -> DUPLICATE_NAME_ERROR, common/tensor_queue.cc:29-70).
# Sync eager ops complete before returning, so only truly-async surfaces
# (frontend async handles) can overlap; they register their name for the
# handle's lifetime.
_inflight_names: set = set()
_inflight_lock = threading.Lock()


def register_inflight_name(name: Optional[str]) -> bool:
    """Claim `name` until release_inflight_name; raises DuplicateNameError
    if an operation with that name is still pending. Returns False for
    anonymous ops (no claim)."""
    if not name:
        return False
    with _inflight_lock:
        if name in _inflight_names:
            raise DuplicateNameError(
                f"an operation named '{name}' is already in flight — "
                f"synchronize it before reusing the name (reference: "
                f"DUPLICATE_NAME_ERROR, common/tensor_queue.cc)")
        _inflight_names.add(name)
        return True


def release_inflight_name(name: Optional[str]) -> None:
    if name:
        with _inflight_lock:
            _inflight_names.discard(name)


def _normalize_op(average: Optional[bool], op: Any) -> T.ReduceOp:
    if average is not None and op is not None:
        raise HorovodTpuError("specify either average or op, not both "
                              "(reference: mpi_ops.py handle_average_backwards_"
                              "compatibility)")
    if op is not None:
        return T.normalize_reduce_op(op)
    if average is None:
        return T.ReduceOp.AVERAGE
    return T.ReduceOp.AVERAGE if average else T.ReduceOp.SUM


def _exchange_sizes(d0: int, ps: ProcessSet) -> Tuple[int, ...]:
    """All ranks learn every rank's dim-0 size (controller duty in the
    reference: Allgather2Ints, controller.h:67)."""
    k = ps.size()
    if jax.process_count() == 1:
        return (d0,) * k
    row = _exchange_rows(np.asarray([d0], np.int64), ps)
    return tuple(int(v) for v in row[:, 0])


def _exchange_rows(my_row: np.ndarray, ps: ProcessSet) -> np.ndarray:
    """Gather one small int row per rank → (k, len(row)) matrix on host."""
    k = ps.size()
    key = ("xrow", my_row.shape, ps.cache_token)

    def build() -> Callable:
        def body(block):
            return lax.all_gather(block[0], _AXIS, axis=0)[None]

        fn = jax.shard_map(body, mesh=ps.mesh, in_specs=P(_AXIS),
                           out_specs=P(_AXIS), check_vma=False)
        return jax.jit(fn)

    fn = _cache.get_or_build(key, build)
    g, _ = _to_global(my_row.astype(np.int64), ps)
    # Host readback blocks until every rank contributed — stall watchpoint.
    _stall_submit("exchange_rows")
    try:
        out = _execute(fn, g)
        shard = out.addressable_shards[0].data[0]
        return np.asarray(shard)
    except Exception as e:
        if isinstance(e, HorovodInternalError):
            raise
        if topology.raw_state().config.elastic and is_comm_failure(e):
            raise HorovodInternalError(
                f"size exchange failed: {e}") from e
        raise
    finally:
        _stall_done("exchange_rows")


def _stall_submit(name: str) -> None:
    si = topology.raw_state().stall_inspector
    if si is not None:
        si.submit(name)


def _stall_done(name: str) -> None:
    si = topology.raw_state().stall_inspector
    if si is not None:
        si.done(name)


def _consistency(desc: str, ps: ProcessSet,
                 name: Optional[str] = None) -> None:
    """Dispatch choke point for cross-rank call-sequence checking.

    Two independent verifiers hook here:

    * HOROVOD_CONSISTENCY_CHECK (core/consistency.py): synchronous
      per-call agreement on `desc` — the coordinator's mismatch
      checking, controller.cc:74-447, as an opt-in. Agreement runs
      among the process set's members only, on the set's own sequence —
      subset-set collectives must not involve (or desynchronize)
      outsiders.
    * HOROVOD_CHECK_COLLECTIVES (analysis/verifier.py): rolling
      fingerprint of (op-signature, name) tuples, cross-checked through
      the rendezvous KV every N calls — asymptotically free, raises
      CollectiveDivergenceError naming the divergent rank and call.
    """
    # Flight recorder first (observability/flight.py): one ring append
    # per dispatched collective, reusing the descriptor this choke point
    # already formatted — the always-on black box the doctor merges.
    _flight.record_collective(ps.process_set_id, desc, name or "")
    # hvdtrace ordering marker: an instant span under the ambient step
    # trace for dispatches whose duration the host cannot see (the
    # compiled path). Gated to a few loads when no trace is ambient.
    if _tracing.active():
        _tracing.record_dispatch(desc, name or "")
    from horovod_tpu.core import consistency as _cc
    from horovod_tpu.analysis import verifier as _vf
    checker = _cc.get()
    v = _vf.get()
    if checker is None and v is None:
        return
    ranks = ps.ranks  # None ⇒ world
    if ranks is None:
        group = "world"
    else:
        import hashlib as _hl
        member_tag = _hl.sha256(repr(tuple(ranks)).encode()).hexdigest()
        group = f"ps{ps.process_set_id}-{member_tag[:12]}"
    if checker is not None:
        checker.check(desc, ranks=ranks, group=group)
    if v is not None:
        # Scoped per process set, like the checker: only members
        # dispatch on a subset set, so it has its own sequence.
        v.record(f"{desc}|name={name}" if name else desc,
                 ranks=ranks, group=group)


# ---------------------------------------------------------------- metrics

_mx_cache = None
_cum_bytes: Dict[str, float] = {}
_cum_lock = threading.Lock()
_dtype_cache: Dict[Any, Tuple[int, str]] = {}


def _dtype_info(dt) -> Tuple[int, str]:
    """(itemsize, canonical name) memoized per dtype object — np.dtype()
    construction and str(dtype) cost ~10 us each, too hot for per-call."""
    info = _dtype_cache.get(dt)
    if info is None:
        ndt = np.dtype(dt)
        info = (ndt.itemsize, str(ndt))
        _dtype_cache[dt] = info
    return info


def _mx():
    """Lazy hot-path instrument handles (observability/metrics.py).
    Cached per registry instance; when metrics are disabled every family
    is the shared NOOP, so recording costs one no-op method call."""
    global _mx_cache
    from horovod_tpu.observability import metrics as m
    reg = m.registry()
    if _mx_cache is None or _mx_cache[0] is not reg:
        _mx_cache = (reg, {
            "calls": reg.counter(
                "horovod_collective_calls_total",
                "Eager collective calls", labelnames=("op", "dtype")),
            "bytes": reg.counter(
                "horovod_collective_bytes_total",
                "Global payload bytes moved by collectives",
                labelnames=("op", "dtype")),
            "seconds": reg.histogram(
                "horovod_collective_seconds",
                "Host-side wall time per collective call (dispatch under "
                "async, full completion in elastic mode)",
                labelnames=("op",), buckets=m.TIME_BUCKETS),
            "group": reg.histogram(
                "horovod_grouped_fusion_tensors",
                "Tensors per grouped (fused) collective call",
                labelnames=("op",), buckets=m.COUNT_BUCKETS),
            "cache": reg.counter(
                "horovod_compile_cache_total",
                "Compiled-executable cache lookups",
                labelnames=("event",)),
            "bucket_n": reg.counter(
                "horovod_bucket_dispatch_total",
                "Fusion buckets dispatched by the pipelined allreduce"),
            "bucket_bytes": reg.histogram(
                "horovod_bucket_bytes",
                "Global payload bytes per dispatched fusion bucket",
                buckets=m.SIZE_BUCKETS),
            "bucket_secs": reg.histogram(
                "horovod_bucket_seconds",
                "Per-bucket launch-to-complete wall time (profiled "
                "bucketed_allreduce calls only)",
                buckets=m.TIME_BUCKETS),
            "overlap": reg.gauge(
                "horovod_overlap_fraction",
                "Estimated fraction of bucket in-flight time shared with "
                "another bucket (1 - wall_window / sum_of_bucket_spans; "
                "profiled calls only)"),
            "axis_bytes": reg.counter(
                "horovod_axis_comms_bytes_total",
                "Eager collective payload bytes attributed to a named "
                "mesh axis (process sets built by axis_process_set; "
                "docs/parallelism.md)", labelnames=("axis", "op")),
            "stall_warn": reg.counter(
                "horovod_stall_warnings_total",
                "Stall warnings", labelnames=("source",)),
            "stall_shut": reg.counter(
                "horovod_stall_shutdowns_total",
                "Stall shutdown raises (elastic watchdog)"),
        })
    return _mx_cache[1]


def _record(activity: str, arrays, nbytes_fn, ntensors, seconds,
            tl, axis=None) -> None:
    """Post-call accounting (metrics enabled only): counters, the wall-
    time histogram, and a per-op cumulative-bytes counter track in the
    live timeline so the trace shows byte throughput next to the spans."""
    op = activity.lower()
    mx = _mx()
    nbytes = 0
    dtype = ""
    for a in arrays:
        try:
            isize, dname = _dtype_info(a.dtype)
            dtype = dtype or dname
            nbytes += int(a.size) * isize
        except Exception:
            pass
    if nbytes_fn is not None:
        try:
            extra_bytes, extra_dtype = nbytes_fn()
            nbytes += extra_bytes
            dtype = dtype or extra_dtype
        except Exception:
            pass
    mx["calls"].labels(op=op, dtype=dtype).inc()
    if nbytes:
        mx["bytes"].labels(op=op, dtype=dtype).inc(nbytes)
        if axis:
            # Per-axis comms attribution (docs/parallelism.md): eager
            # traffic over an axis_process_set sub-communicator lands in
            # its axis's series — the dp/tp split the hybrid backend's
            # scaling analysis reads.
            mx["axis_bytes"].labels(axis=axis, op=op).inc(nbytes)
    mx["seconds"].labels(op=op).observe(seconds)
    if ntensors is not None:
        mx["group"].labels(op=op).observe(ntensors)
    if tl is not None:
        with _cum_lock:
            _cum_bytes[op] = cum = _cum_bytes.get(op, 0.0) + nbytes
        tl.counter("horovod_collective_bytes_total", {op: cum})


class _instrument:
    """EXECUTE-style timeline span + metrics around eager dispatch
    (reference: the per-tensor op-activity spans, timeline.cc +
    operations.cc:286-330). Under async dispatch the measured window
    covers host-side dispatch; in elastic mode (_execute forces
    completion) it covers the full collective.

    Byte counts are computed lazily — from `arrays` (already-lifted
    global payloads) or `nbytes_fn` (fast paths that never materialize a
    global array) — only when metrics are enabled, so with both
    HOROVOD_METRICS=0 and HOROVOD_PERFSCOPE=0 the hot path pays a couple
    of cheap gates and no clock reads. With perfscope live the window is
    also attributed to the step's `comms` phase (minus whatever inner
    hooks — a compile on a cache miss — already re-attributed)."""

    __slots__ = ("name", "activity", "arrays", "nbytes_fn", "ntensors",
                 "tl", "enabled", "ps", "timed", "t0", "attr_mark",
                 "axis")

    def __init__(self, name: str, activity: str, arrays: Sequence = (),
                 nbytes_fn: Optional[Callable] = None,
                 ntensors: Optional[int] = None,
                 axis: Optional[str] = None) -> None:
        self.name = name
        self.activity = activity
        self.arrays = arrays
        self.nbytes_fn = nbytes_fn
        self.ntensors = ntensors
        self.axis = axis

    def __enter__(self) -> "_instrument":
        from horovod_tpu.observability import metrics as m
        self.enabled = m.registry().enabled
        self.ps = _pscope.get()
        self.tl = topology.state().timeline
        if self.tl is not None:
            self.tl.span_begin(self.name, self.activity)
        # Clock reads only when someone consumes the window (metrics
        # or a live perfscope) — the fully-disabled path stays free.
        self.timed = self.enabled or self.ps is not _pscope.NOOP
        if self.timed:
            self.attr_mark = self.ps.attributed_marker()
            self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self.timed:
            dt = time.perf_counter() - self.t0
        if self.tl is not None:
            self.tl.span_end(self.name, self.activity)
        if self.timed:
            # Step-phase attribution (profiler/perfscope.py): this
            # window is `comms` time, minus nested re-attributions.
            nested = self.ps.attributed_marker() - self.attr_mark
            self.ps.attribute("comms", dt - nested)
            if _tracing.active():
                # Per-collective child span under the ambient step
                # trace (observability/tracing.py) — the measured eager
                # dispatch window, with bytes when they are computable
                # without lifting anything.
                nbytes = None
                try:
                    if self.arrays:
                        nbytes = float(sum(a.nbytes for a in self.arrays))
                    elif self.nbytes_fn is not None:
                        nbytes = float(self.nbytes_fn())
                except Exception:
                    nbytes = None
                _tracing.collective_span(self.name, self.activity, dt,
                                         nbytes)
        if self.enabled:
            _record(self.activity, self.arrays, self.nbytes_fn,
                    self.ntensors, dt, self.tl, axis=self.axis)
        return False
