"""hvdshard rules HVD301-HVD305: sharding contracts on the lowered
program — the static gate in front of the GSPMD backend (ROADMAP
item 3; docs/static_analysis.md).

GSPMD (Xu et al., arXiv:2105.04663) decides placement from
annotations, so every classic hybrid-parallel failure is visible in
the lowered text before anything runs. Megatron-LM's scaling analysis
(Narayanan et al., SC'21) names the two quantities that decide whether
a mesh config is viable — resharding traffic and per-device memory;
HVD302/HVD303 compute exactly those at lint time.

HVD301  a tensor >= HOROVOD_SHARD_LINT_MIN_REPLICATED_BYTES is fully
        replicated across a >1-partition mesh: every device pays full
        HBM for it and every update moves the full payload — the
        silently-replicated-table failure. (Replication across a
        *data* axis while sharded on the model axis is normal and not
        flagged; only shard_factor == 1 fires.)
HVD302  an all-gather / all-to-all / collective-permute the SPMD
        partitioner *inserted* (metadata traces to a dot/gather/...,
        not to a user collective) moving >=
        HOROVOD_SHARD_LINT_MIN_RESHARD_BYTES inside the step body:
        resharding traffic nobody asked for, usually two inconsistent
        annotations fighting.
HVD303  the static per-device peak-HBM estimate (donation-aware
        liveness over the post-opt schedule, analysis/shard.py)
        exceeds HOROVOD_HLO_LINT_HBM_BUDGET — the compile-time OOM
        gate. Silent when no budget is configured.
HVD304  the mesh carries more devices than the program's sharding can
        use: some devices hold identical shards of every annotated
        tensor >= HOROVOD_SHARD_LINT_MIN_SHARDED_BYTES — paid-for,
        unused parallelism (an axis that shards nothing).
HVD305  an all-reduce >= HOROVOD_SHARD_LINT_MIN_RESHARD_BYTES whose
        every consumer immediately slices out one shard: each device
        reduces and materializes the FULL tensor only to keep 1/k of
        it — that is a reduce-scatter (``lax.psum_scatter``) at k
        times less memory and (k-1)/k less wire traffic.

Rules self-select the textual form they can judge: HVD302/303 need
the post-SPMD module (per-device shapes, schedule, metadata), HVD304
needs the pre-partition annotations; HVD301 and HVD305 read both
(the psum+slice pattern is clearest pre-partition, where XLA hasn't
yet fused the slice away). Findings are baselined
(``scripts/hvdshard_baseline.json``), not suppressed inline — lowered
text has no comments.
"""

from __future__ import annotations

from typing import Iterable, Optional

from horovod_tpu.analysis.driver import Finding
from horovod_tpu.analysis.hlo import HloOp, HloProgram
from horovod_tpu.analysis import hlo_rules
from horovod_tpu.analysis import shard as S

HVD301 = "HVD301"
HVD302 = "HVD302"
HVD303 = "HVD303"
HVD304 = "HVD304"
HVD305 = "HVD305"

_MB = 1024 * 1024


def _min_replicated_bytes() -> int:
    return S._bytes_env("HOROVOD_SHARD_LINT_MIN_REPLICATED_BYTES",
                        4 * _MB)


def _min_reshard_bytes() -> int:
    return S._bytes_env("HOROVOD_SHARD_LINT_MIN_RESHARD_BYTES", _MB)


def _min_sharded_bytes() -> int:
    return S._bytes_env("HOROVOD_SHARD_LINT_MIN_SHARDED_BYTES", _MB)


def hbm_budget_bytes() -> Optional[int]:
    """HVD303 gate: the per-device HBM budget the static peak estimate
    is judged against; None (rule silent) when unset."""
    return S._bytes_env("HOROVOD_HLO_LINT_HBM_BUDGET", None)


def check_hvd301(prog: HloProgram) -> Iterable[Finding]:
    if prog.num_partitions <= 1:
        return
    floor = _min_replicated_bytes()
    for p in prog.entry_params:
        spec = S.parse_sharding(p.sharding)
        if spec is None or not spec.fully_replicated:
            continue
        nb = p.type.nbytes if p.type is not None else None
        if nb is None or nb < floor:
            continue
        yield Finding(
            prog.path, p.line, HVD301,
            f"input {p.name} ({p.type}, {nb / _MB:.1f} MB) is fully "
            f"replicated across the {prog.num_partitions}-partition "
            "mesh: every device pays the full HBM cost and every "
            "update moves the full payload — shard it over a model "
            "axis (NamedSharding/PartitionSpec, docs/parallelism.md)")


_RESHARD_OPCODES = {"all_gather", "all_to_all", "collective_permute"}


def check_hvd302(prog: HloProgram) -> Iterable[Finding]:
    if prog.fmt != "hlo" or prog.num_partitions <= 1:
        return
    floor = _min_reshard_bytes()
    for op in prog.ops:
        if op.opcode not in _RESHARD_OPCODES:
            continue
        if S.traceable_to_user_collective(op):
            continue
        nb = S._result_bytes(op)
        if nb < floor:
            continue
        yield Finding(
            prog.path, op.line, HVD302,
            f"partitioner-inserted {op.opcode} moving {nb / _MB:.1f} "
            "MB inside the step body (metadata traces to "
            f"'{_origin(op)}', not to a user collective): the SPMD "
            "partitioner is resharding to reconcile inconsistent "
            "annotations — align the producer/consumer shardings "
            "(docs/static_analysis.md)")


def _origin(op: HloOp) -> str:
    m = S._OP_NAME_RE.search(op.attrs)
    if not m:
        return "<no metadata>"
    return m.group(1).rsplit("/", 1)[-1] or "<no metadata>"


def check_hvd303(prog: HloProgram) -> Iterable[Finding]:
    budget = hbm_budget_bytes()
    if budget is None or prog.fmt != "hlo":
        return
    est = S.peak_memory(prog)
    if est is None or est.peak_bytes <= budget:
        return
    top = ", ".join(f"{n} {b / _MB:.1f} MB" for n, b in est.top)
    yield Finding(
        prog.path, est.peak_line, HVD303,
        f"static per-device peak-HBM estimate {est.peak_bytes / _MB:.1f}"
        f" MB exceeds the {budget / _MB:.1f} MB budget "
        "(HOROVOD_HLO_LINT_HBM_BUDGET) — this program OOMs at run "
        f"time; largest live buffers at the peak: {top}; donate dead "
        "inputs, shard the big tensors, or rematerialize "
        "(docs/static_analysis.md peak-memory model)")


def check_hvd304(prog: HloProgram) -> Iterable[Finding]:
    if prog.fmt != "stablehlo" or prog.num_partitions <= 1:
        return
    floor = _min_sharded_bytes()
    tensors = [t for t in S.annotated_tensors(prog)
               if t.type is not None and t.type.nbytes is not None
               and t.type.nbytes >= floor]
    if not tensors:
        return
    classes = S.partition_classes(tensors, prog.num_partitions)
    if classes is None or classes >= prog.num_partitions:
        return
    waste = prog.num_partitions // max(classes, 1)
    line = min(t.line for t in tensors)
    yield Finding(
        prog.path, line, HVD304,
        f"the mesh carries {prog.num_partitions} partitions but the "
        f"program's sharding only distinguishes {classes} device "
        f"group(s): {waste}x of the mesh holds identical shards of "
        f"every tensor >= {floor / _MB:.1f} MB — a mesh axis is paid "
        "for but shards nothing (drop the axis or shard a major "
        "tensor over it, docs/parallelism.md)")


_SLICE_OPCODES = {"dynamic_slice", "slice"}


def check_hvd305(prog: HloProgram) -> Iterable[Finding]:
    floor = _min_reshard_bytes()
    for op in prog.ops:
        if op.opcode != "all_reduce" or not op.result:
            continue
        nb = hlo_rules._collective_payload(op)
        if nb is None or nb < floor:
            continue
        uses = prog.uses(op.scope, op.result)
        if not uses:
            continue
        if all(u.opcode in _SLICE_OPCODES for u in uses):
            yield Finding(
                prog.path, op.line, HVD305,
                f"all_reduce of {nb / _MB:.1f} MB whose every consumer "
                "immediately slices out one shard: every device "
                "materializes the full reduction only to keep 1/k of "
                "it — use reduce_scatter (lax.psum_scatter) for k x "
                "less peak HBM and (k-1)/k less wire traffic "
                "(docs/parallelism.md)")


RULES = {
    HVD301: ("tensor above the replication threshold fully replicated "
             "across a >1-partition mesh", check_hvd301),
    HVD302: ("partitioner-inserted resharding collective (all-gather/"
             "all-to-all/collective-permute not traceable to a user "
             "collective) above the reshard threshold", check_hvd302),
    HVD303: ("static per-device peak-HBM estimate exceeds "
             "HOROVOD_HLO_LINT_HBM_BUDGET (compile-time OOM gate)",
             check_hvd303),
    HVD304: ("mesh axis paid for but sharding no tensor above the "
             "threshold (unused parallelism)", check_hvd304),
    HVD305: ("all-reduce whose every consumer keeps only its own "
             "shard (should be reduce-scatter/psum_scatter)",
             check_hvd305),
}
