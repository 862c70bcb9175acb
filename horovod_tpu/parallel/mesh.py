"""Multi-axis device mesh construction.

Replaces the reference's flat rank space + process sets
(horovod/common/process_set.cc) with a named-axis `jax.sharding.Mesh`:

  dp — data parallel (gradient psum; Horovod's whole purpose)
  pp — pipeline stages (ppermute ring between stages)
  tp — tensor parallel (sharded matmuls, psum on row-parallel outputs)
  sp — sequence/context parallel (ring attention over this axis)
  ep — expert parallel (all_to_all token dispatch)

Axis ordering puts dp outermost so that, on a real pod, dp rides DCN across
slices while tp/sp (the latency-sensitive axes) stay on ICI — mirroring the
reference's hierarchical allreduce split (nccl_operations.cc:308: NCCL
within node, MPI across).
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

from horovod_tpu.common.exceptions import HorovodTpuError

# Canonical axis order: latency-tolerant axes first (outermost / DCN),
# latency-sensitive last (innermost / ICI neighbours).
AXIS_ORDER: Tuple[str, ...] = ("dp", "pp", "ep", "sp", "tp")

#: The HOROVOD_MESH spec grammar (docs/parallelism.md): comma-separated
#: `axis=size` entries over the canonical axes, e.g. "dp=2,tp=4".
#: `auto` (or -1) gives one axis every device the others don't claim —
#: "tp=4" alone on 8 devices means dp=2 x tp=4, the same rule
#: MeshSpec.infer applies.
_SPEC_ENTRY_RE = re.compile(r"^([a-z]+)\s*=\s*(auto|-1|\d+)$")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Sizes per named parallelism axis; 1 = axis unused (but still present
    so the same compiled program works at any configuration)."""
    dp: int = 1
    pp: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1

    def __post_init__(self) -> None:
        # A zero/negative axis silently reshapes to an empty device
        # grid and every later error is a numpy shape crash — fail at
        # construction with the axis named.
        for a in AXIS_ORDER:
            if getattr(self, a) < 1:
                raise HorovodTpuError(
                    f"mesh axis {a}={getattr(self, a)} must be >= 1 "
                    "(use 1 for an unused axis)")

    def sizes(self) -> Tuple[int, ...]:
        return tuple(getattr(self, a) for a in AXIS_ORDER)

    @property
    def total(self) -> int:
        return int(math.prod(self.sizes()))

    @staticmethod
    def infer(n_devices: int, tp: int = 1, sp: int = 1, pp: int = 1,
              ep: int = 1) -> "MeshSpec":
        """Fix the model axes; give every remaining device to dp."""
        if n_devices < 1:
            raise HorovodTpuError(f"n_devices={n_devices} must be >= 1")
        inner = tp * sp * pp * ep
        if inner < 1 or n_devices % inner:
            raise HorovodTpuError(
                f"n_devices={n_devices} not divisible by tp*sp*pp*ep={inner}")
        return MeshSpec(dp=n_devices // inner, pp=pp, ep=ep, sp=sp, tp=tp)

    @staticmethod
    def parse(text: str, n_devices: Optional[int] = None) -> "MeshSpec":
        """Parse a ``HOROVOD_MESH``-grammar spec: ``"dp=2,tp=4"``.

        Axes are the canonical five (dp/pp/ep/sp/tp); unmentioned axes
        default to 1 — except ``dp``, which defaults to ``auto`` when
        `n_devices` is known, so ``HOROVOD_MESH=tp=4`` on an 8-device
        job means dp=2 x tp=4 (the MeshSpec.infer rule). At most one
        axis may be ``auto``/``-1``; with `n_devices` given, the spec's
        total must cover the devices exactly — a silent mismatch would
        strand devices outside every collective.
        """
        sizes: Dict[str, int] = {}
        auto_axis: Optional[str] = None
        for part in text.strip().split(","):
            part = part.strip()
            if not part:
                continue
            m = _SPEC_ENTRY_RE.match(part)
            if not m:
                raise HorovodTpuError(
                    f"bad HOROVOD_MESH entry {part!r}: expected "
                    f"axis=size with axis in {AXIS_ORDER} "
                    "(e.g. \"dp=2,tp=4\")")
            axis, val = m.group(1), m.group(2)
            if axis not in AXIS_ORDER:
                raise HorovodTpuError(
                    f"unknown mesh axis {axis!r} in HOROVOD_MESH "
                    f"(choose from {AXIS_ORDER})")
            if axis in sizes or axis == auto_axis:
                raise HorovodTpuError(
                    f"duplicate mesh axis {axis!r} in HOROVOD_MESH")
            if val in ("auto", "-1"):
                if auto_axis is not None:
                    raise HorovodTpuError(
                        "at most one HOROVOD_MESH axis may be auto")
                auto_axis = axis
            else:
                sizes[axis] = int(val)
        if not sizes and auto_axis is None:
            raise HorovodTpuError(f"empty HOROVOD_MESH spec {text!r}")
        if auto_axis is None and "dp" not in sizes and n_devices:
            auto_axis = "dp"  # the infer rule: leftover devices ride dp
        if auto_axis is not None:
            if not n_devices:
                raise HorovodTpuError(
                    f"HOROVOD_MESH axis {auto_axis}=auto needs a known "
                    "device count")
            fixed = math.prod(sizes.values()) if sizes else 1
            if fixed < 1 or n_devices % fixed:
                raise HorovodTpuError(
                    f"HOROVOD_MESH {text!r}: {n_devices} devices not "
                    f"divisible by the fixed axes' product {fixed}")
            sizes[auto_axis] = n_devices // fixed
        spec = MeshSpec(**sizes)
        if n_devices and spec.total != n_devices:
            raise HorovodTpuError(
                f"HOROVOD_MESH {text!r} covers {spec.total} devices, "
                f"job has {n_devices}")
        return spec

    def describe(self) -> str:
        """Canonical round-trippable spec string ("dp=2,tp=4"): only the
        axes with size > 1, in canonical order; "dp=1" for the trivial
        single-device mesh."""
        parts = [f"{a}={getattr(self, a)}" for a in AXIS_ORDER
                 if getattr(self, a) > 1]
        return ",".join(parts) if parts else "dp=1"

    def axis_groups(self, axes) -> List[List[int]]:
        """Partition of the flat rank space ``range(total)`` into the
        sub-communicators of `axes` (an axis name or a set of them):
        ranks in one group differ only in their coordinates along
        `axes`. This is the process-set face of the mesh — the TPU
        analog of the reference's per-axis NCCL sub-communicators
        (nccl_operations.cc:308 node/local split), used by
        core/process_sets.axis_process_set and by the per-axis comms
        attribution (analysis/shard.comms_by_axis).
        """
        wanted = {axes} if isinstance(axes, str) else set(axes)
        bad = wanted - set(AXIS_ORDER)
        if bad:
            raise HorovodTpuError(f"unknown mesh axes {sorted(bad)}")
        sizes = self.sizes()
        strides = [1] * len(sizes)
        for i in range(len(sizes) - 2, -1, -1):
            strides[i] = strides[i + 1] * sizes[i + 1]
        moving = [i for i, a in enumerate(AXIS_ORDER) if a in wanted]
        fixed = [i for i in range(len(sizes)) if i not in moving]
        groups: List[List[int]] = []
        for fcoord in itertools.product(*(range(sizes[i]) for i in fixed)):
            base = sum(c * strides[i] for c, i in zip(fcoord, fixed))
            group = [base + sum(c * strides[i] for c, i in
                                zip(mcoord, moving))
                     for mcoord in itertools.product(
                         *(range(sizes[i]) for i in moving))]
            groups.append(group)
        return groups

    def group_of(self, axis: str, rank: int) -> List[int]:
        """The ranks sharing `rank`'s sub-communicator along `axis`
        (rank included), in mesh order."""
        for g in self.axis_groups(axis):
            if rank in g:
                return g
        raise HorovodTpuError(
            f"rank {rank} outside the {self.sizes()} mesh")


def spec_from_env(n_devices: int) -> Optional[MeshSpec]:
    """The HOROVOD_MESH-derived MeshSpec, or None when the knob is
    unset/empty (pure data-parallel world)."""
    text = os.environ.get("HOROVOD_MESH", "").strip()
    if not text:
        return None
    return MeshSpec.parse(text, n_devices)


def build_mesh(spec: MeshSpec,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a Mesh with all five named axes from a flat device list.

    Device order defaults to the global topology's rank order
    (core/topology.py:_canonical_devices) — before `hvd.init()`, to the
    (process_index, id) sort it is built on — so innermost axes land on
    devices that are ICI neighbours on real hardware.
    """
    if devices is not None:
        devs = list(devices)
    else:
        from horovod_tpu.core import topology
        st = topology.raw_state()
        devs = list(st.devices) if st.initialized else sorted(
            jax.devices(), key=lambda d: (d.process_index, d.id))
    if spec.total != len(devs):
        raise HorovodTpuError(
            f"mesh spec {spec.sizes()} needs {spec.total} devices, "
            f"got {len(devs)}")
    if len({id(d) for d in devs}) != len(devs):
        raise HorovodTpuError(
            "duplicate devices in the mesh device list — a repeated "
            "device aliases two mesh coordinates and every collective "
            "over the affected axes deadlocks or double-counts")
    arr = np.asarray(devs, dtype=object).reshape(spec.sizes())
    return Mesh(arr, AXIS_ORDER)


def mesh_axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def slice_groups(n_devices: int, slices: int) -> List[List[int]]:
    """Partition of the flat rank space into `slices` equal contiguous
    slices — the declared ICI domain boundary of a hierarchical mesh
    (``HOROVOD_MESH_SLICES``; docs/parallelism.md). Ranks inside one
    slice talk over ICI; crossing a boundary rides the slow DCN tier.
    Contiguity in the flat C-order space keeps slices aligned with the
    outermost (dp) axis, matching how multi-slice deployments lay pods
    out. The hvdsched staging lint (HVD404, analysis/sched_rules.py)
    and the ICI/DCN cost model consume the same ``rank // per_slice``
    arithmetic on the analysis side.
    """
    if slices <= 0 or n_devices % slices:
        raise HorovodTpuError(
            f"HOROVOD_MESH_SLICES={slices} does not divide the "
            f"{n_devices}-device world into equal slices")
    per = n_devices // slices
    return [list(range(s * per, (s + 1) * per)) for s in range(slices)]
