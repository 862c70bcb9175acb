"""Trace-time tensor fusion (the fusion-buffer analog).

Reference: horovod/common/fusion_buffer_manager.cc + the MemcpyInFusionBuffer
machinery (collective_operations.h:89-124) and the FuseResponses rules
(controller.cc:901): only tensors with the same dtype fuse, and a fused
payload stays under HOROVOD_FUSION_THRESHOLD bytes.

TPU redesign: instead of a persistent 64-128MB device buffer plus batched D2D
memcpy kernels (cuda_kernels.cu), fusion happens at trace time — flatten,
concat into ≤-threshold buckets, run ONE collective per bucket, split back.
XLA fuses the reshapes/concats into the collective's prologue/epilogue, which
is exactly what the hand-written memcpy kernels were approximating.

Two properties the original greedy packer lacked. (Their only timing
evidence is a sweep on the 8-device virtual CPU mesh — 16-64 MB buckets
~2x slower than 1-4 MB there; on the chip it is not measured, ROADMAP
S4):

* **Oversize chunking** — a tensor larger than the threshold used to form
  its own oversized bucket (``max(threshold, nbytes)``), so one 64 MB
  gradient re-created exactly the giant payload the threshold exists to
  prevent. Now such tensors are SPLIT into near-equal chunks of at most
  ``max(threshold, _MIN_CHUNK_BYTES)`` bytes, and the chunks pack into
  buckets like ordinary tensors (PyTorch DDP's gradient-bucketing rule,
  Li et al., VLDB 2020 §4.2).

* **Reverse (backward-production) ordering** — gradients materialize in
  reverse forward order during the backward pass, so packing buckets from
  the LAST leaf backwards aligns each bucket with a contiguous span of
  early-available gradients. Inside one XLA program that lets the
  scheduler launch bucket collectives while the remaining backward compute
  is still running (the role of the reference's background RunLoopOnce
  cycle); with forward-order packing the first bucket depends on the very
  last gradient produced and nothing can overlap.

Both properties are CI-gated at the HLO level, not just unit-tested:
``make hlo-lint`` (hvdhlo rule HVD201, analysis/hlo_rules.py) lowers the
canonical DP step through this planner and fails on any fused all-reduce
payload above the bucket cap surviving to the program — a refactor here
that silently resurrects the pre-bucketing single-giant-allreduce plan
is caught at lower time on CPU-only CI (docs/static_analysis.md).
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Floor on chunk granularity: a sub-1MB chunk of a large tensor costs more
# in per-collective latency than it saves in pipelining, and pathological
# thresholds (tests use 1- and 8-BYTE thresholds to force one bucket per
# tensor) must not explode into thousands of chunks.
_MIN_CHUNK_BYTES = 1 << 20


@dataclasses.dataclass(frozen=True)
class BucketItem:
    """One contiguous slice of a (flattened) tensor inside a bucket."""

    index: int  # position in the submitted tensor list
    start: int  # element offset into the flattened tensor
    size: int   # element count


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One fusion bucket: same-dtype items reduced by ONE collective."""

    dtype: str
    itemsize: int
    items: Tuple[BucketItem, ...]

    @property
    def elems(self) -> int:
        return sum(it.size for it in self.items)

    @property
    def nbytes(self) -> int:
        return self.elems * self.itemsize


def effective_threshold(threshold_bytes: int, cap_bytes: int) -> int:
    """The bucket size actually used: ``min(threshold, cap)``.

    The cap (HOROVOD_BUCKET_CAP, default 4 MB — the measured sweet spot of
    the r05 fusion sweep) bounds the wire payload even when a user or the
    GP autotuner asks for a larger fusion threshold; 0 disables it.
    """
    t = max(int(threshold_bytes), 1)
    return min(t, int(cap_bytes)) if cap_bytes and cap_bytes > 0 else t


def plan_buckets(shapes_dtypes: Sequence[Tuple[Tuple[int, ...], str]],
                 threshold_bytes: int,
                 reverse: bool = False) -> List[Bucket]:
    """Partition tensors (or chunks of them) into fusion buckets.

    Same-dtype items pack greedily in submission order — reversed when
    ``reverse`` is set (see module docstring) — until the bucket would
    exceed ``threshold_bytes`` (FuseResponses greedy rule,
    controller.cc:901-980). Tensors larger than the chunk granularity
    ``max(threshold_bytes, 1MB)`` are split into near-equal chunks first,
    so no bucket ever exceeds the threshold because of a single oversize
    tensor (the 16-64 MB cliff fix). A tensor that exceeds the threshold
    but not the 1MB floor still gets a bucket of its own, preserving the
    tiny-threshold "one bucket per tensor" behavior tests rely on.

    Deterministic: identical inputs yield an identical plan on every rank
    (required — the plan shapes the compiled program every rank runs).
    """
    thresh = max(int(threshold_bytes), 1)
    chunk_bytes = max(thresh, _MIN_CHUNK_BYTES)
    buckets: List[dict] = []  # {"dtype","itemsize","bytes","items"}
    open_bucket: dict = {}    # dtype -> bucket index
    order = range(len(shapes_dtypes) - 1, -1, -1) if reverse \
        else range(len(shapes_dtypes))
    for i in order:
        shape, dtype = shapes_dtypes[i]
        itemsize = jnp.dtype(dtype).itemsize
        total = int(np.prod(shape, dtype=np.int64)) if shape else 1
        nbytes = total * itemsize
        if nbytes > chunk_bytes:
            per = max(chunk_bytes // itemsize, 1)
            nchunks = -(-total // per)  # ceil
            base, rem = divmod(total, nchunks)
            pieces = []
            off = 0
            for c in range(nchunks):
                sz = base + (1 if c < rem else 0)
                pieces.append(BucketItem(i, off, sz))
                off += sz
        else:
            pieces = [BucketItem(i, 0, total)]
        for it in pieces:
            it_bytes = it.size * itemsize
            bi = open_bucket.get(dtype)
            if bi is not None and \
                    buckets[bi]["bytes"] + it_bytes <= thresh:
                buckets[bi]["items"].append(it)
                buckets[bi]["bytes"] += it_bytes
            else:
                buckets.append({"dtype": dtype, "itemsize": itemsize,
                                "bytes": it_bytes, "items": [it]})
                open_bucket[dtype] = len(buckets) - 1
    return [Bucket(b["dtype"], b["itemsize"], tuple(b["items"]))
            for b in buckets]


def plan_signature(plan: Sequence[Bucket]) -> str:
    """Short stable fingerprint of a bucket plan.

    Embedded in the collective-dispatch descriptor, so the consistency
    checker / fingerprint verifier catch ranks whose thresholds (and hence
    plans, and hence compiled programs) diverged — the cheap cross-rank
    agreement proof the online bucket tuner leans on.
    """
    h = hashlib.sha256(repr([(b.dtype, b.items) for b in plan]).encode())
    return f"{len(plan)}b:{h.hexdigest()[:10]}"


def fused_reduce_blocks(blocks: Sequence[jax.Array],
                        reduce_fn: Callable[[jax.Array], jax.Array],
                        threshold_bytes: int,
                        reverse: bool = False) -> Tuple[jax.Array, ...]:
    """Reduce many (1, *shape) blocks with one collective per fusion bucket.

    `reduce_fn` maps a (1, n) fused block to its reduced (1, n) result.
    Tensors larger than the threshold are chunked across buckets and
    reassembled here; with ``reverse`` the buckets are packed in backward
    production order (see module docstring).
    """
    metas = [(tuple(b.shape[1:]), str(b.dtype)) for b in blocks]
    plan = plan_buckets(metas, threshold_bytes, reverse=reverse)
    flats = [b.reshape(1, -1) for b in blocks]
    pieces: List[List[Tuple[int, jax.Array]]] = [[] for _ in blocks]
    for bucket in plan:
        segs = [flats[it.index][:, it.start:it.start + it.size]
                for it in bucket.items]
        fused = segs[0] if len(segs) == 1 else jnp.concatenate(segs, axis=1)
        red = reduce_fn(fused)
        off = 0
        for it in bucket.items:
            pieces[it.index].append((it.start, red[:, off:off + it.size]))
            off += it.size
    out: List[jax.Array] = []
    for i, b in enumerate(blocks):
        ps = [p for _, p in sorted(pieces[i], key=lambda t: t[0])]
        flat = ps[0] if len(ps) == 1 else jnp.concatenate(ps, axis=1)
        out.append(flat.reshape(b.shape).astype(b.dtype))
    return tuple(out)
