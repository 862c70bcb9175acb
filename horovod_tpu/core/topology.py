"""Process/device topology: init, rank/size, and the global device mesh.

TPU-native replacement for the reference's init path
(horovod/common/operations.cc:856 InitializeHorovodOnce +
horovod/common/basics.py:51 HorovodBasics.init). Key re-design:

* The reference spawns a background C++ thread per process that negotiates
  tensor readiness every cycle. On TPU, collectives inside a jitted step are
  compiled into the XLA program — there is nothing to negotiate. What remains
  host-side is *topology*: which processes exist, which devices they own, and
  the `jax.sharding.Mesh` every collective runs over.

* A Horovod "rank" maps to a *device slot*, not a process. With the
  canonical one-process-per-chip launch (our launcher mirrors
  horovod/runner/gloo_run.py) rank == process index. Under a single
  controller owning many devices (e.g. tests on an 8-device CPU mesh, or a
  whole v5e host), each local device is a rank and per-rank tensors carry a
  leading local-slot axis. This keeps Horovod's SPMD semantics while staying
  idiomatic JAX.

* Multi-process bootstrap goes through `jax.distributed.initialize`
  (coordinator = our launcher's rendezvous, replacing the Gloo HTTP KV store
  in horovod/common/gloo/gloo_context.cc).
"""

from __future__ import annotations

import atexit
import os
import threading
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

from horovod_tpu.common.config import Config
from horovod_tpu.common.exceptions import HorovodTpuError

_AXIS = "hvd"  # global mesh axis name used by every collective


class _GlobalState:
    """Singleton topology state (role of horovod/common/global_state.h)."""

    def __init__(self) -> None:
        self.initialized = False
        self.config: Config = Config()
        self.devices: List[jax.Device] = []
        self.mesh: Optional[Mesh] = None
        self.size: int = 0
        self.rank: int = 0
        self.local_size: int = 0
        self.local_rank: int = 0
        self.cross_size: int = 0
        self.cross_rank: int = 0
        self.local_slot_ranks: List[int] = []
        self.process_index: int = 0
        self.num_processes: int = 1
        self.lock = threading.RLock()
        # 2-axis ("dcn","ici") view of the same devices for hierarchical
        # collectives (HOROVOD_TPU_MESH_SHAPE); None = flat world.
        self.hier_mesh: Optional[Mesh] = None
        # GSPMD hybrid-parallel backend (docs/parallelism.md): the
        # HOROVOD_MESH-derived named-axis MeshSpec + the 5-axis Mesh
        # over the same devices in the same canonical order. None =
        # pure data-parallel world (the flat 'hvd' mesh above).
        self.mesh_spec = None       # parallel.mesh.MeshSpec | None
        self.hybrid_mesh: Optional[Mesh] = None
        # Set lazily by sibling modules to avoid import cycles.
        self.process_set_table = None
        self.timeline = None
        self.parameter_manager = None
        self.bucket_tuner = None
        self.stall_inspector = None
        self.joined = False  # guarded-by: lock

    def reset(self) -> None:
        self.__init__()


_state = _GlobalState()


def _canonical_devices(cfg: Config) -> List[jax.Device]:
    """All devices in rank order: sorted by (owning process, device id),
    processes in the order of the ranks the launcher gave them.

    This makes each process's devices contiguous in rank space, so
    local_rank arithmetic matches the reference launcher's slot model
    (horovod/runner/gloo_run.py host allocation), and puts the process
    the launcher calls rank r at mesh position r — the position every
    rank-addressed collective (broadcast root, allgather row) means.
    """
    devs = jax.devices()
    rank_of = {}
    if cfg.rank is not None and jax.process_count() > 1 \
            and devs[0].platform != "cpu":
        # On the CPU backend a device's process_index IS the rank we hand
        # jax.distributed. On a TPU it is the process's place in the
        # slice's topology, whatever the launcher called it: on a 2x2
        # host split one chip per process, the launcher's ranks 0,1,2,3
        # were processes 0,2,3,1 in one run and 3,2,0,1 in the next. So
        # ask every process.
        from jax.experimental import multihost_utils
        pairs = multihost_utils.process_allgather(
            np.array([jax.process_index(), cfg.rank], np.int32))
        rank_of = {int(p): int(r) for p, r in pairs}
    return sorted(devs, key=lambda d: (
        rank_of.get(d.process_index, d.process_index), d.id))


def _maybe_distributed_init(cfg: Config) -> None:
    """Bootstrap multi-process JAX if the launcher injected a rendezvous.

    Replaces the Gloo TCP rendezvous against the launcher HTTP store
    (horovod/common/gloo/gloo_context.cc + http_store.cc). Our launcher
    injects HOROVOD_RANK/SIZE and coordinator address; we hand them to
    jax.distributed (the TPU-native control plane over DCN).
    """
    if cfg.size is None or cfg.size <= 1:
        return
    if jax.distributed.is_initialized():
        return
    # The jax.distributed coordinator must be BOUND BY RANK 0 on rank 0's
    # host. An explicit HOROVOD_COORDINATOR_ADDR env wins (single-host
    # launches); otherwise rank 0 picks a port on its own host and
    # publishes it through the HTTP KV rendezvous, which works for
    # multi-host, Spark, and Ray launches where the launcher cannot know
    # rank 0's address. Keyed per elastic round so resets re-rendezvous.
    coord = os.environ.get("HOROVOD_COORDINATOR_ADDR", "")
    if not coord:
        if not cfg.rendezvous_addr:
            return  # no rendezvous: single-process mode
        from horovod_tpu.runner.launch import _free_port, _local_ip
        from horovod_tpu.runner.rendezvous import KVClient
        kv = KVClient(cfg.rendezvous_addr, cfg.rendezvous_port)
        key = f"r{os.environ.get('HOROVOD_ELASTIC_ROUND', '0')}"
        if (cfg.rank or 0) == 0:
            coord = f"{_local_ip()}:{_free_port()}"
            kv.put("jax_coordinator", key, coord.encode())
        else:
            data = kv.get("jax_coordinator", key, timeout=300.0)
            if data is None:
                raise HorovodTpuError(
                    "timed out waiting for rank 0 to publish the "
                    "jax.distributed coordinator address")
            coord = data.decode()
    if cfg.elastic:
        _elastic_distributed_init(coord, cfg)
    else:
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=cfg.size,
            process_id=cfg.rank or 0,
        )


def _elastic_distributed_init(coord: str, cfg: Config) -> None:
    """jax.distributed bootstrap for ELASTIC workers.

    Reference: in elastic mode the reference aborts NCCL communicators on
    peer failure instead of dying (nccl_operations.cc elastic handling) so
    HorovodInternalError can drive recovery. Two departures from the stock
    jax.distributed.initialize path make that possible here:

    1. The coordination SERVICE lives in the LAUNCHER, not in rank 0
       (elastic/driver.py run_elastic starts one per round): a worker crash
       can then never take the coordinator down, which is what turns peer
       failure into process-fatal error polling on the survivors.
    2. The client is built `recoverable` (no all-task shutdown barrier —
       workers leave the ring independently during a resize) and without a
       destructor-time RPC. With a live service and recoverable clients, a
       dead peer propagates NO fatal error to survivors (verified
       empirically); failures surface through the data-plane collectives
       as catchable errors instead.
    """
    # Private-API probe: the recoverable client only exists behind
    # jax._src internals, which any jaxlib bump may move or re-sign.
    # Probed here (not imported at module scope) with a DOCUMENTED
    # fallback — jax.distributed.initialize with a non-recoverable
    # client — so elastic degrades from in-process recovery to
    # worker-restart recovery instead of crashing at init
    # (docs/elastic.md "jaxlib compatibility").
    _dist = _jaxlib = None
    try:
        from jax._src import distributed as _dist

        from horovod_tpu.common.compat import jaxlib_extension
        _jaxlib = jaxlib_extension()
    except ImportError:
        pass
    factory = getattr(_jaxlib, "get_distributed_runtime_client", None)
    state = getattr(_dist, "global_state", None)
    rank = cfg.rank or 0
    from horovod_tpu.common.hvd_logging import get_logger
    if factory is not None and state is not None:
        hb = int(os.environ.get("HOROVOD_ELASTIC_HEARTBEAT_SECONDS", "10"))
        sd = int(os.environ.get("HOROVOD_ELASTIC_SHUTDOWN_SECONDS", "10"))
        try:
            from horovod_tpu.common.compat import make_distributed_client
            client = make_distributed_client(
                coord, rank, init_timeout=300, heartbeat_timeout=hb,
                shutdown_timeout=sd)
            client.connect()
            state.num_processes = cfg.size
            state.process_id = rank
            state.coordinator_address = coord
            state.client = client
            return
        except TypeError:
            pass  # jaxlib changed the factory signature — fall back
    get_logger().warning(
        "jax distributed-runtime client unavailable in this jaxlib "
        "(private API moved); elastic falls back to "
        "jax.distributed.initialize and worker-restart recovery")
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=cfg.size, process_id=rank)


# The jaxlib whose private distributed-runtime API this elastic path has
# been verified against: the installed one (see
# recoverable_client_contract).
RECOVERABLE_CLIENT_TESTED_JAXLIB = "0.9"


def recoverable_client_contract():
    """Probe — WITHOUT connecting — whether this jaxlib still exposes the
    recoverable distributed-runtime client `_elastic_distributed_init`
    needs (jax._src internals; any jaxlib bump may move or re-sign them).

    Returns (ok, reason). Used by tests/CI to fail LOUDLY on signature
    drift: the runtime path degrades gracefully (worker-restart
    recovery), but the degradation must never be silent — a CI run on a
    tested jaxlib version with a broken contract is a bug, not a
    fallback (docs/elastic.md "jaxlib compatibility")."""
    try:
        from jax._src import distributed as _dist  # noqa: F401

        from horovod_tpu.common.compat import jaxlib_extension
        _jaxlib = jaxlib_extension()
    except ImportError as e:
        return False, f"jax._src import moved: {e}"
    factory = getattr(_jaxlib, "get_distributed_runtime_client", None)
    if factory is None:
        return False, "get_distributed_runtime_client gone from jaxlib"
    if getattr(_dist, "global_state", None) is None:
        return False, "jax._src.distributed.global_state gone"
    try:
        # construct only — no .connect(), and shutdown_on_destruction
        # False means the destructor performs no RPC
        factory("127.0.0.1:1", 0, init_timeout=1, heartbeat_timeout=1,
                shutdown_timeout=1, use_compression=True,
                recoverable=True, shutdown_on_destruction=False)
    except TypeError as e:
        return False, f"factory signature drifted: {e}"
    except Exception as e:
        # kwargs were ACCEPTED (no TypeError) but the native ctor
        # rejected the dummy address/values at runtime — the signature
        # contract holds; note the caveat instead of raising out of a
        # probe documented to always return (ok, reason)
        return True, f"signature ok; ctor runtime caveat: {e!r}"
    return True, "ok"


def distributed_teardown() -> None:
    """Tear down the jax.distributed client/service, tolerating dead peers
    (used by the elastic reset; every step is best-effort because the ring
    may already be half-gone)."""
    try:
        from jax._src import distributed as _dist
        st = _dist.global_state
    except (ImportError, AttributeError):
        try:  # private state moved: best-effort public teardown
            jax.distributed.shutdown()
        except Exception:
            pass
        return
    if st.client is None and st.service is None:
        return
    try:
        if st.preemption_sync_manager is not None:
            st.preemption_sync_manager.shutdown()
    except Exception:
        pass
    st.preemption_sync_manager = None
    try:
        if st.client is not None:
            st.client.shutdown()
    except Exception:
        pass
    st.client = None
    try:
        if st.service is not None:
            st.service.shutdown()
    except Exception:
        pass
    st.service = None
    st.coordinator_address = None
    st.process_id = 0
    st.num_processes = 1


def _apply_cpu_emulation(n: int) -> None:
    """HOROVOD_TPU_EMULATE_RANKS=N: emulate an N-chip slice with XLA's
    host-platform device count (dev/test mode; mirrors how the reference's
    parallel suites run real collectives over loopback, SURVEY.md §4).

    Emulation is a CPU-backend affair (run it under JAX_PLATFORMS=cpu): a
    process whose JAX came up on an accelerator is refused, never switched
    to the CPU behind the user's back.
    """
    import re

    devs = jax.devices()
    if devs[0].platform != "cpu":
        raise HorovodTpuError(
            f"CPU emulation of {n} ranks was asked for, but JAX came up "
            f"on '{devs[0].platform}': set JAX_PLATFORMS=cpu to emulate; "
            "a live accelerator backend is never replaced by the CPU")
    if len(devs) >= n:
        return
    # XLA_FLAGS/jax_num_cpu_devices are consumed at client creation:
    # discard the too-small CPU client first.
    import jax.extend.backend as _jeb
    _jeb.clear_backends()
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}").strip()
    jax.config.update("jax_num_cpu_devices", n)
    if len(jax.devices()) < n:
        raise HorovodTpuError(
            f"CPU emulation failed: need {n} devices, have "
            f"{len(jax.devices())}")


#: Where compiled programs are kept when the environment names no other
#: place: a FIXED, git-ignored path inside the checkout — never a temp
#: name, pid or time, because a cache directory that moves never hits.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def compile_cache_dir(cfg: Config, platform: str
                      ) -> Tuple[Optional[str], bool]:
    """The persistent compile cache this process uses, and whether
    `init()` has to set it: `(directory, set_in_code)`.

    JAX_COMPILATION_CACHE_DIR wins and is left to JAX (nothing is set in
    code); else HOROVOD_TPU_COMPILE_CACHE; else the fixed
    `<checkout>/.jax_cache`. Launcher children inherit the environment
    and the checkout, so every rank resolves the same directory.

    The default is for accelerators, where a cold train step costs tens
    of seconds: on the CPU backend nothing is cached unless the
    environment asks (its compiles are cheap, and XLA:CPU logs a
    spurious machine-feature error on every cached executable it
    reloads)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env, False
    if cfg.compile_cache_dir:
        return cfg.compile_cache_dir, True
    if platform == "cpu":
        return None, False
    return DEFAULT_COMPILE_CACHE_DIR, True


def _check_one_chip_per_process(cfg: Config) -> None:
    """The launcher's one-process-per-chip contract, enforced: a TPU
    worker launched into a multi-slot host (HOROVOD_LOCAL_SIZE > 1) owns
    exactly the chip its slot was assigned (runner/hosts.py chip env).
    A worker that opened several chips would fight its neighbours for
    them — a failure, not a warning."""
    local = jax.local_devices()
    if (cfg.local_size or 1) > 1 and local[0].platform == "tpu" \
            and len(local) != 1:
        raise HorovodTpuError(
            f"rank {cfg.rank}: launched as one of {cfg.local_size} "
            f"workers on this host but sees {len(local)} local TPU chips; "
            "one process per chip needs the slot's chip assignment "
            "(TPU_VISIBLE_CHIPS etc., injected by the launcher for 4- and "
            "8-chip single-host jobs — runner/hosts.py)")


def init(process_sets: Optional[Sequence] = None,
         devices: Optional[Sequence[jax.Device]] = None) -> None:
    """Initialize the framework (reference API: hvd.init(), basics.py:51).

    Args:
      process_sets: optional list of ProcessSet objects to register beyond
        the global one (reference: horovod/common/process_sets.py).
      devices: optional explicit device list (for tests / sub-slice runs).
    """
    with _state.lock:
        if _state.initialized:
            return
        cfg = Config.from_env()
        _state.config = cfg
        if cfg.emulate_ranks > 0:
            _apply_cpu_emulation(cfg.emulate_ranks)
        _maybe_distributed_init(cfg)
        _check_one_chip_per_process(cfg)

        devs = list(devices) if devices is not None \
            else _canonical_devices(cfg)
        if not devs:
            raise HorovodTpuError("no JAX devices visible")
        _state.devices = devs
        _state.size = len(devs)
        _state.mesh = Mesh(np.asarray(devs), (_AXIS,))
        if cfg.mesh_shape:
            _state.hier_mesh = _build_hier_mesh(cfg.mesh_shape, devs)
        if cfg.mesh_spec:
            # HOROVOD_MESH: MeshSpec is the runtime's mesh authority —
            # the hybrid mesh shares the flat mesh's devices and
            # canonical order, so rank r IS mesh coordinate
            # unravel(r, spec.sizes()) and process sets map onto named
            # sub-axes (core/process_sets.axis_process_set).
            from horovod_tpu.parallel import mesh as mesh_mod
            _state.mesh_spec = mesh_mod.MeshSpec.parse(
                cfg.mesh_spec, len(devs))
            _state.hybrid_mesh = mesh_mod.build_mesh(
                _state.mesh_spec, devs)

        pidx = jax.process_index()
        pcount = jax.process_count()
        _state.process_index = pidx
        _state.num_processes = pcount
        _state.local_slot_ranks = [
            i for i, d in enumerate(devs) if d.process_index == pidx]
        if not _state.local_slot_ranks and devices is not None:
            # Explicit sub-slice that excludes this process: not a member.
            _state.local_slot_ranks = []

        # rank/local/cross, with launcher env taking precedence
        # (reference: env injected per-slot in runner/gloo_run.py:69-75).
        _state.rank = cfg.rank if cfg.rank is not None else (
            _state.local_slot_ranks[0] if _state.local_slot_ranks else 0)
        _state.local_size = cfg.local_size if cfg.local_size is not None else len(
            _state.local_slot_ranks)
        _state.local_rank = cfg.local_rank if cfg.local_rank is not None else 0
        _state.cross_size = cfg.cross_size if cfg.cross_size is not None else pcount
        _state.cross_rank = cfg.cross_rank if cfg.cross_rank is not None else pidx

        cache_dir, set_here = compile_cache_dir(cfg, devs[0].platform)
        if set_here:
            jax.config.update("jax_compilation_cache_dir", cache_dir)

        # Register the global process set (+ user sets) now that mesh exists.
        from horovod_tpu.core import process_sets as ps_mod
        _state.process_set_table = ps_mod.ProcessSetTable(_state)
        if process_sets:
            for ps in process_sets:
                _state.process_set_table.register(ps)

        if cfg.timeline_path and _state.rank == 0:
            # Reference: HOROVOD_TIMELINE auto-starts capture at init
            # (operations.cc:531) ON RANK 0 — the coordinator writes the
            # trace (timeline.cc); co-hosted ranks sharing the path would
            # clobber each other. Gates on the COMPUTED rank (launcher-
            # less multi-process runs have no HOROVOD_RANK env). Manual
            # hvd.start_timeline still works on any rank (point it at a
            # per-rank path).
            try:
                from horovod_tpu.profiler.timeline import Timeline
                _state.timeline = Timeline(
                    cfg.timeline_path, mark_cycles=cfg.timeline_mark_cycles)
                _state.timeline.start()
            except Exception as e:
                from horovod_tpu.common.hvd_logging import get_logger
                get_logger().warning("could not start timeline at %s: %s",
                                     cfg.timeline_path, e)
        if cfg.cycle_time_ms > 0.0:
            from horovod_tpu.common.hvd_logging import get_logger
            get_logger().info(
                "HOROVOD_CYCLE_TIME=%.1fms accepted but has no effect on "
                "TPU: collectives are compiled into the XLA program, so "
                "there is no background cycle to batch against "
                "(reference: operations.cc RunLoopOnce)", cfg.cycle_time_ms)
        if cfg.consistency_check:
            from horovod_tpu.core import consistency
            # Agreement is between PROCESSES: in single-controller mode
            # one process owns all N device-ranks but contributes once, so
            # sizing the check by rank count would make every collective
            # wait for contributions that can never arrive.
            consistency.maybe_init(cfg, jax.process_index(),
                                   jax.process_count())
        if cfg.check_collectives:
            # Fingerprint verifier (analysis/verifier.py): like the
            # consistency checker, agreement is between PROCESSES — a
            # single controller contributes one call sequence no matter
            # how many device-ranks it owns.
            from horovod_tpu.analysis import verifier as _vfmod
            _vfmod.maybe_init(cfg, jax.process_index(),
                              jax.process_count())
        if cfg.autotune:
            from horovod_tpu.core.autotune import ParameterManager
            _state.parameter_manager = ParameterManager(cfg)
        elif cfg.bucket_autotune:
            # Mutually exclusive with the GP tuner: both mutate
            # fusion_threshold_bytes and would fight over it.
            from horovod_tpu.core.autotune import OnlineBucketTuner
            _state.bucket_tuner = OnlineBucketTuner(cfg)
        if not cfg.stall_check_disable:
            try:
                from horovod_tpu import native as native_mod
                if native_mod.available():
                    _state.stall_inspector = native_mod.NativeStallInspector(
                        cfg.stall_warning_seconds,
                        cfg.stall_shutdown_seconds)
            except Exception:
                _state.stall_inspector = None
            if _state.stall_inspector is None:
                # No toolchain / load failure: same contract in pure
                # Python, so elastic-mode collective waits stay bounded
                # (ops/collectives.py StallWatchdog) everywhere.
                from horovod_tpu.common.resilience import PyStallInspector
                _state.stall_inspector = PyStallInspector(
                    cfg.stall_warning_seconds, cfg.stall_shutdown_seconds)

        # Metrics fan-out (observability/export.py): KV push to the
        # launcher's /metrics scrape, JSON dumps, timeline counter
        # tracks. Best-effort — telemetry never blocks init.
        try:
            from horovod_tpu.observability import export as _mexport
            _mexport.start_exporter(cfg)
        except Exception as e:
            from horovod_tpu.common.hvd_logging import get_logger
            get_logger().warning("metrics exporter not started: %s", e)

        from horovod_tpu.common.hvd_logging import get_logger
        get_logger().info(
            "horovod_tpu initialized: size=%d local_size=%d processes=%d "
            "platform=%s", _state.size, _state.local_size, pcount,
            devs[0].platform)
        _state.initialized = True
        # The watcher loop gates on _state.initialized — start it only
        # after the flag flips or it exits on its first slice.
        if _state.stall_inspector is not None:
            _start_stall_watch(_state.stall_inspector, cfg)


def _build_hier_mesh(spec: str, devs: Sequence[jax.Device]) -> Mesh:
    """Parse HOROVOD_TPU_MESH_SHAPE ("dcn:2,ici:4" or "2x4") into a
    2-axis ("dcn","ici") mesh over the same devices in the same order.
    Reference structure: NCCLHierarchicalAllreduce's node×local split
    (nccl_operations.cc:308) — here dcn=cross-slice, ici=within-slice.
    """
    axes = {"dcn": 1, "ici": 1}
    s = spec.strip().lower()
    try:
        if "x" in s and ":" not in s:
            a, b = s.split("x", 1)
            axes["dcn"], axes["ici"] = int(a), int(b)
        else:
            for part in s.split(","):
                name, n = part.split(":")
                if name.strip() not in axes:
                    raise ValueError(name)
                axes[name.strip()] = int(n)
    except (ValueError, TypeError):
        raise HorovodTpuError(
            f"bad HOROVOD_TPU_MESH_SHAPE '{spec}': expected 'dcn:A,ici:B' "
            f"or 'AxB'")
    if axes["dcn"] * axes["ici"] != len(devs):
        raise HorovodTpuError(
            f"HOROVOD_TPU_MESH_SHAPE '{spec}' = {axes['dcn']}x{axes['ici']} "
            f"does not cover {len(devs)} devices")
    return Mesh(np.asarray(devs).reshape(axes["dcn"], axes["ici"]),
                ("dcn", "ici"))


def hier_mesh() -> Optional[Mesh]:
    """The ("dcn","ici") mesh when HOROVOD_TPU_MESH_SHAPE is set, else
    None. Same devices and order as mesh() — a reshaped view."""
    return _require_init().hier_mesh


def _start_stall_watch(si, cfg: Config) -> None:
    """Background checker that surfaces stalled collectives (reference:
    CheckForStalledTensors runs in the coordinator's loop; here a watcher
    thread polls the native inspector)."""
    import time as _time

    from horovod_tpu.common.hvd_logging import get_logger

    def watch() -> None:
        while _state.initialized and _state.stall_inspector is si:
            stalled, shut = si.check()
            if stalled:
                who = ""
                try:
                    from horovod_tpu.core import consistency as _cc
                    checker = _cc.get()
                    if checker is not None:
                        lag = checker.lagging_ranks()
                        if lag:
                            who = f"; rank(s) {lag} have not arrived"
                except Exception:
                    pass
                try:
                    from horovod_tpu.analysis import verifier as _vf
                    who += _vf.stall_context()
                except Exception:
                    pass
                try:
                    from horovod_tpu.observability import metrics as _m
                    _m.registry().counter(
                        "horovod_stall_warnings_total",
                        "Stall warnings",
                        labelnames=("source",)).labels(
                            source="watcher").inc()
                except Exception:
                    pass
                try:
                    from horovod_tpu.observability import flight as _fl
                    _fl.record("stall",
                               f"watcher: collective(s) "
                               f"{', '.join(stalled)} stalled over "
                               f"{cfg.stall_warning_seconds:.0f}s{who}")
                except Exception:
                    pass
                get_logger().warning(
                    "One or more collectives stalled for over %.0fs: %s — "
                    "some ranks may not have reached them%s "
                    "(HOROVOD_STALL_CHECK_TIME_SECONDS)",
                    cfg.stall_warning_seconds, ", ".join(stalled), who)
            if shut:
                # Teardown race: a concurrent shutdown() means the "stall"
                # is just the process exiting — re-check before the hard
                # abort (reference: stall shutdown only fires while the
                # background loop is live, operations.cc).
                if not (_state.initialized and _state.stall_inspector is si):
                    return
                if cfg.elastic:
                    # Elastic mode: the StallWatchdog guarding the blocked
                    # wait (ops/collectives.py) raises HorovodInternalError
                    # in the waiting thread within shutdown_sec, handing
                    # recovery to the elastic retry loop — killing the
                    # process here would forfeit in-memory state.
                    get_logger().error(
                        "Stall exceeded "
                        "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS; elastic "
                        "watchdog will raise HorovodInternalError")
                else:
                    get_logger().error(
                        "Stall exceeded "
                        "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS; aborting")
                    # os._exit skips atexit — flush the flight recorder
                    # NOW or the abort leaves no black box behind.
                    try:
                        from horovod_tpu.observability import flight as _fl
                        _fl.dump("stall_abort")
                    except Exception:
                        pass
                    os._exit(1)
            _time.sleep(max(cfg.stall_warning_seconds / 2.0, 1.0))

    threading.Thread(target=watch, name="hvd-stall-watch",
                     daemon=True).start()


def shutdown() -> None:
    """Tear down (reference: horovod_shutdown, operations.cc:1009)."""
    with _state.lock:
        if not _state.initialized:
            return
        try:  # final metrics flush while rank/timeline are still valid
            from horovod_tpu.observability import export as _mexport
            _mexport.stop_exporter()
        except Exception:
            pass
        if _state.timeline is not None:
            _state.timeline.shutdown()
        from horovod_tpu.core import consistency as _cc
        _cc.reset()
        from horovod_tpu.analysis import verifier as _vfmod
        _vfmod.reset()
        from horovod_tpu.ops import collectives as _coll
        _coll.clear_compiled_cache()
        _state.reset()


atexit.register(shutdown)


def is_initialized() -> bool:
    return _state.initialized


def _require_init() -> _GlobalState:
    if not _state.initialized:
        raise HorovodTpuError(
            "horovod_tpu has not been initialized; call hvd.init() first.")
    return _state


def state() -> _GlobalState:
    return _require_init()


def raw_state() -> _GlobalState:
    return _state


def size() -> int:
    """Total number of ranks (device slots). Reference: horovod_size."""
    return _require_init().size


def rank() -> int:
    """This process's first rank. Reference: horovod_rank."""
    return _require_init().rank


def local_size() -> int:
    return _require_init().local_size


def local_rank() -> int:
    return _require_init().local_rank


def cross_size() -> int:
    return _require_init().cross_size


def cross_rank() -> int:
    return _require_init().cross_rank


def local_slot_ranks() -> List[int]:
    """Ranks whose devices this process owns (len == #local devices)."""
    return list(_require_init().local_slot_ranks)


def mesh() -> Mesh:
    """The global 1-D device mesh (axis name 'hvd')."""
    m = _require_init().mesh
    assert m is not None
    return m


def hybrid_mesh() -> Optional[Mesh]:
    """The HOROVOD_MESH-derived 5-axis (dp/pp/ep/sp/tp) mesh over the
    same devices as mesh(), or None when the job is pure data-parallel
    (docs/parallelism.md). Same device order as the flat mesh — rank r
    sits at coordinate unravel(r, mesh_spec().sizes())."""
    return _require_init().hybrid_mesh


def mesh_spec():
    """The parsed HOROVOD_MESH MeshSpec (parallel/mesh.py), or None."""
    return _require_init().mesh_spec


def axis_name() -> str:
    return _AXIS


def is_homogeneous() -> bool:
    """All processes own the same number of devices (reference:
    horovod_is_homogeneous, used to gate hierarchical allreduce)."""
    st = _require_init()
    counts: dict = {}
    for d in st.devices:
        counts[d.process_index] = counts.get(d.process_index, 0) + 1
    return len(set(counts.values())) <= 1


def rank_or_none() -> Optional[int]:
    return _state.rank if _state.initialized else None


# Capability flags (reference: mpi_built()/nccl_built()/... in basics.py).
def tpu_built() -> bool:
    return True


def mpi_built() -> bool:
    return False


def nccl_built() -> bool:
    return False


def gloo_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def gloo_enabled() -> bool:
    return False


def mpi_threads_supported() -> bool:
    return False
