"""horovod_tpu: a TPU-native distributed training framework.

Brand-new framework with the capabilities of Horovod (reference:
dalian-ai/horovod), re-designed for TPU: collectives are XLA programs over a
`jax.sharding.Mesh` (ICI/DCN) instead of NCCL/MPI calls, fusion is trace-time
bucketing instead of a runtime staging buffer, and the response cache is a
compiled-executable cache. See SURVEY.md for the full design mapping.

Public API mirrors `horovod.torch` / `horovod.tensorflow`
(reference: horovod/torch/__init__.py, horovod/tensorflow/__init__.py).
"""

from horovod_tpu.common.types import (  # noqa: F401
    Adasum, Average, Max, Min, Product, ReduceOp, Status, Sum,
)
from horovod_tpu.common.exceptions import (  # noqa: F401
    CollectiveDivergenceError, DuplicateNameError, HorovodInternalError,
    HorovodTpuError, HostsUpdatedInterrupt, TensorShapeMismatchError,
    VersionMismatchError,
)
from horovod_tpu.core.topology import (  # noqa: F401
    ccl_built, cross_rank, cross_size, cuda_built, ddl_built, gloo_built,
    gloo_enabled, hybrid_mesh, init, is_homogeneous, is_initialized,
    local_rank, local_size, local_slot_ranks, mesh, mesh_spec, mpi_built,
    mpi_enabled, mpi_threads_supported, nccl_built, rank, rocm_built,
    shutdown, size, tpu_built,
)
from horovod_tpu.core.process_sets import (  # noqa: F401
    ProcessSet, add_process_set, axis_process_set, get_process_set,
    global_process_set, remove_process_set,
)
from horovod_tpu.ops.collectives import (  # noqa: F401
    allgather, allgather_async, allreduce, allreduce_async, alltoall,
    alltoall_async, barrier, broadcast, broadcast_async,
    bucketed_allreduce, bucketed_allreduce_async, bucket_overlap_stats,
    grouped_allgather, grouped_allreduce, grouped_allreduce_async,
    grouped_reducescatter, poll, reducescatter, reducescatter_async,
    synchronize,
)
from horovod_tpu.ops.compression import Compression  # noqa: F401
from horovod_tpu.optim.optimizer import (  # noqa: F401
    DistributedOptimizer, DistributedGradientTransform,
)
from horovod_tpu.optim.functions import (  # noqa: F401
    broadcast_object, broadcast_optimizer_state, broadcast_parameters,
    broadcast_variables, allgather_object,
)
from horovod_tpu.core import join as _join_mod  # noqa: F401
from horovod_tpu.core.join import join  # noqa: F401
from horovod_tpu import elastic  # noqa: F401  (hvd.elastic.run / State)

__version__ = "0.1.0"

# hvdrace (analysis/race.py, docs/static_analysis.md): with
# HOROVOD_RACE_CHECK=1 the runtime's `# guarded-by:`-annotated classes
# are instrumented HERE, at import time — before any runtime instance
# exists — so every lock they create is tracked from birth. Without the
# env var nothing is imported or patched.
import os as _os  # noqa: E402

if _os.environ.get("HOROVOD_RACE_CHECK"):  # presence sniff: zero cost
    # when unset; race.env_enabled() owns the truthy-value parse.
    from horovod_tpu.analysis import race as _race
    _race.maybe_enable_from_env()


def metrics() -> dict:
    """This process's metrics registry as a plain-JSON snapshot
    (docs/observability.md has the catalog). Works before init();
    after init() the snapshot carries this process's rank."""
    from horovod_tpu.core import topology
    from horovod_tpu.observability import metrics as m
    return m.registry().snapshot(topology.rank_or_none())


def metrics_text() -> str:
    """This process's metrics in Prometheus text exposition format —
    what the rendezvous server's `/metrics` route serves job-wide."""
    from horovod_tpu.core import topology
    from horovod_tpu.observability import metrics as m
    return m.registry().render(topology.rank_or_none())


def perfscope():
    """The process-wide step-phase profiler (profiler/perfscope.py,
    docs/perf.md): delimit steps with `with hvd.perfscope().step():` and
    mark host input waits with `.phase("input_wait")`; comms, compile
    and optimizer time are attributed automatically through
    `DistributedOptimizer`. A no-op shell under HOROVOD_PERFSCOPE=0."""
    from horovod_tpu.profiler import perfscope as _ps
    return _ps.get()


def start_timeline(file_path: str, mark_cycles: bool = False) -> None:
    """Start runtime timeline capture (reference: operations.cc:1077)."""
    from horovod_tpu.profiler.timeline import Timeline
    from horovod_tpu.core import topology
    st = topology.state()
    if st.timeline is None:
        st.timeline = Timeline(file_path, mark_cycles=mark_cycles)
    st.timeline.start()


def stop_timeline() -> None:
    """Stop timeline capture (reference: horovod_stop_timeline)."""
    from horovod_tpu.core import topology
    st = topology.state()
    if st.timeline is not None:
        st.timeline.stop()
