"""Distributed optimizer wrappers.

Reference surfaces being re-designed here:
  * horovod/torch/optimizer.py:36 `_DistributedOptimizer` — per-parameter
    backward hooks firing async allreduces, synchronized in step().
  * horovod/tensorflow/__init__.py:631 `_make_allreduce_grads_fn` +
    :896 `DistributedOptimizer`, :1125 `DistributedGradientTape`.
  * horovod/tensorflow/gradient_aggregation.py `LocalGradientAggregationHelper`
    (backward_passes_per_step local accumulation).

TPU redesign: gradients of a jitted step function are available as one pytree
at trace time, so instead of per-tensor hooks + runtime fusion, we bucket the
whole gradient tree (ops/fusion.py) and emit one `psum` per bucket *inside
the compiled program*. XLA then overlaps those collectives with remaining
backward compute — the role of Horovod's background-thread/fusion-buffer
pipeline (horovod/common/operations.cc RunLoopOnce) is played by the XLA
scheduler over ICI.

Two entry points:
  * `DistributedGradientTransform` — an optax GradientTransformation for use
    INSIDE shard_map/pjit step functions (the SPMD fast path).
  * `DistributedOptimizer` — Horovod-style eager wrapper: takes per-rank
    gradient pytrees, runs fused eager collectives, applies an optax update.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.common import types as T
from horovod_tpu.common.exceptions import HorovodTpuError
from horovod_tpu.core import topology
from horovod_tpu.core.process_sets import ProcessSet, global_process_set
from horovod_tpu.ops import collectives, fusion
from horovod_tpu.ops.compression import Compression
from horovod_tpu.profiler import perfscope as _pscope

_AXIS = "hvd"

#: `DistributedOptimizer.step`'s two phases as host spans of the JAX
#: profiler's trace, on the clock a device trace has (docs/observability.md,
#: "Scopes of the compiled step"); outside a profiler session a
#: `TraceAnnotation` is one flag test
_REDUCE_SPAN = partial(jax.profiler.TraceAnnotation, "hvd.opt.reduce")
_APPLY_SPAN = partial(jax.profiler.TraceAnnotation, "hvd.opt.apply")

#: Mesh axes over which the shard-local loss formulations compute the
#: loss REDUNDANTLY (every member ends holding the same scalar, each
#: copy differentiated per rank): per-shard reverse AD then scales
#: every gradient by the axis size, and the sharded-step builder
#: divides it back out (models/transformer.py grad_reduce_axes has the
#: full derivation; models/tied_lm.py follows the same contract).
REDUNDANT_LOSS_AXES: Tuple[str, ...] = ("tp",)

#: Mesh axes a training batch shards over (gradient MEAN axes); the
#: remaining axes carry model shards, whose gradient psums are plain
#: sums of partial contributions.
BATCH_AXES: Tuple[str, ...] = ("dp", "ep", "sp")


def _scale_factors(op: T.ReduceOp, k: int, gradient_predivide_factor: float
                   ) -> Tuple[float, float, T.ReduceOp]:
    """Split averaging into pre/post scaling (reference:
    horovod/torch/optimizer.py gradient_predivide_factor handling: prescale
    1/f before the sum, postscale f/size after)."""
    if gradient_predivide_factor != 1.0:
        if op != T.ReduceOp.AVERAGE:
            raise HorovodTpuError(
                "gradient_predivide_factor requires op=Average")
        return (1.0 / gradient_predivide_factor,
                gradient_predivide_factor / k, T.ReduceOp.SUM)
    return 1.0, 1.0, op


def _spec_axis_names(spec) -> set:
    """Mesh axis names a PartitionSpec mentions (entries may be names,
    tuples of names, or None)."""
    names: set = set()
    if spec is None:
        return names
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            names.update(e for e in entry if e)
        else:
            names.add(entry)
    return names


def grad_axes_from_specs(param_specs: Any, mesh) -> Any:
    """Per-leaf gradient psum axes derived from a sharding spec.

    The rule (the multi-axis generalisation of "allreduce everything
    over the world"): a leaf's gradient must be psum'd over every mesh
    axis of size > 1 **absent from its PartitionSpec** — batch axes
    (the parameter is replicated across data shards) and any model axis
    the leaf is replicated over (each member's backward holds a partial
    sum). An axis the leaf IS sharded over contributes no psum: the
    shard's gradient lives only on its owners. This is exactly
    ``models/transformer.py grad_reduce_axes`` computed from the spec
    pytree instead of written by hand — the piece that lets
    ``DistributedOptimizer`` accept a user sharding spec and emit
    batch-axis-only traffic for model-sharded parameters.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    live = tuple(a for a in mesh.axis_names if sizes[a] > 1)

    def leaf(spec):
        mentioned = _spec_axis_names(spec)
        return tuple(a for a in live if a not in mentioned)

    return jax.tree_util.tree_map(
        leaf, param_specs, is_leaf=lambda x: isinstance(x, P) or x is None)


def opt_state_specs(opt_state: Any, params: Any, param_specs: Any) -> Any:
    """Per-leaf PartitionSpecs for an optax state — the restore-side
    twin of "moments inherit the parameter shardings" (the save side
    needs nothing: ckpt/sharded.py reads each array's ACTUAL sharding).

    Needed when a sharded checkpoint is restored onto a *different*
    mesh shape (docs/checkpointing.md): the params' target specs are
    known (`param_specs`), but the optimizer state's must be derived.
    The rule matches what GSPMD propagates in `build_sharded_train_step`:
    a state leaf whose (shape, dtype) matches a parameter's takes that
    parameter's spec (adam mu/nu, sgd momentum); everything else
    (counts, scalar schedules) is replicated. Ambiguity between
    parameters that share a shape but carry DIFFERENT specs falls back
    to replicated — correct, just more resharding traffic on the first
    step.
    """
    import numpy as _np

    by_shape: dict = {}
    p_leaves = jax.tree_util.tree_leaves(params)
    s_leaves = jax.tree_util.tree_leaves(
        param_specs, is_leaf=lambda x: isinstance(x, P) or x is None)
    for pl, sl in zip(p_leaves, s_leaves):
        key = (tuple(_np.shape(pl)), _np.dtype(
            getattr(pl, "dtype", _np.float32)).name)
        if key in by_shape and by_shape[key] != sl:
            by_shape[key] = P()  # ambiguous: replicate
        else:
            by_shape.setdefault(key, sl if sl is not None else P())

    def leaf(x):
        key = (tuple(_np.shape(x)), _np.dtype(
            getattr(x, "dtype", _np.float32)).name)
        spec = by_shape.get(key)
        return spec if spec is not None else P()

    return jax.tree_util.tree_map(leaf, opt_state)


def _record_axis_comms(bytes_by_label: dict) -> None:
    """Static per-axis comms attribution (docs/parallelism.md): planned
    per-device gradient-reduction bytes per mesh-axis group, recorded at
    trace time (the plan is a static property of the compiled step).
    Feeds the perfscope summary (`comms_axes`) and the
    `horovod_axis_comms_bytes` gauge family; best-effort — attribution
    must never break a trace."""
    try:
        _pscope.get().set_comms_axes(bytes_by_label)
    except Exception:
        pass
    try:
        from horovod_tpu.observability import metrics as m
        g = m.registry().gauge(
            "horovod_axis_comms_bytes",
            "Planned per-device gradient-reduction payload bytes per "
            "step, by mesh axis group (trace-time static attribution)",
            labelnames=("axis",))
        for label, nbytes in bytes_by_label.items():
            g.labels(axis=label).set(float(nbytes))
    except Exception:
        pass


def _reduce_gradients_by_axes(grads: Any, op: T.ReduceOp, axes: Any,
                              mean_axes: Tuple[str, ...],
                              compression, thresh: int, reverse: bool,
                              gradient_predivide_factor: float) -> Any:
    """Per-leaf multi-axis reduction: leaves are grouped by their psum
    axis tuple and bucketed per group (ops/fusion.py), so a tp-sharded
    parameter's gradient generates batch-axis traffic only and every
    group's buckets still chunk/overlap like the 1-D path. `mean_axes`
    are the batch axes an AVERAGE divides by (model-axis psums are
    plain partial-sum additions)."""
    if op not in (T.ReduceOp.SUM, T.ReduceOp.AVERAGE):
        raise HorovodTpuError(
            f"sharding-spec gradient reduction supports Sum/Average, "
            f"got {op}")
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    is_axes_leaf = lambda x: (isinstance(x, (tuple, list)) and  # noqa: E731
                              all(isinstance(e, str) for e in x))
    ax_leaves = [tuple(a) for a in jax.tree_util.tree_leaves(
        axes, is_leaf=is_axes_leaf)]
    if len(ax_leaves) != len(leaves):
        raise HorovodTpuError(
            f"gradient axes pytree has {len(ax_leaves)} leaves, "
            f"gradients have {len(leaves)} (build it with "
            "grad_axes_from_specs over the same structure)")
    out: list = [None] * len(leaves)
    groups: dict = {}
    for i, ax in enumerate(ax_leaves):
        groups.setdefault(ax, []).append(i)
    bytes_by_label: dict = {}
    for ax, idxs in groups.items():
        if not ax:  # unreduced leaf (sharded over every live axis)
            for i in idxs:
                out[i] = leaves[i]
            continue
        k = 1
        for a in ax:
            if a in mean_axes:
                k *= lax.axis_size(a)
        pre, post, rop = _scale_factors(op, k, gradient_predivide_factor)
        comp = [compression.compress(leaves[i]) for i in idxs]
        blocks = [c[0][None] for c in comp]

        def reduce_block(b: jax.Array, _ax=ax, _pre=pre, _post=post,
                         _rop=rop, _k=k) -> jax.Array:
            x = b
            if _pre != 1.0:
                x = x * jnp.asarray(_pre, x.dtype)
            y = lax.psum(x, _ax)
            if _rop == T.ReduceOp.AVERAGE and _k != 1:
                y = y / jnp.asarray(_k, y.dtype)
            if _post != 1.0:
                y = y * jnp.asarray(_post, y.dtype)
            return y

        reduced = fusion.fused_reduce_blocks(blocks, reduce_block,
                                             thresh, reverse=reverse)
        for i, r, c in zip(idxs, reduced, comp):
            out[i] = compression.decompress(r[0], c[1])
        label = "+".join(ax)
        bytes_by_label[label] = bytes_by_label.get(label, 0) + sum(
            int(np.prod(np.shape(b))) * np.dtype(b.dtype).itemsize
            for b in blocks)
    _record_axis_comms(bytes_by_label)
    return jax.tree_util.tree_unflatten(treedef, out)


def reduce_gradients_in_jit(grads: Any,
                            op: T.ReduceOp = T.ReduceOp.AVERAGE,
                            axis: str = _AXIS,
                            compression=Compression.none,
                            fusion_threshold_bytes: Optional[int] = None,
                            num_ranks: Optional[int] = None,
                            gradient_predivide_factor: float = 1.0,
                            reverse_bucket_order: Optional[bool] = None,
                            axes: Any = None,
                            mean_axes: Optional[Tuple[str, ...]] = None
                            ) -> Any:
    """Cross-replica gradient reduction for use inside shard_map'd code.

    Buckets the gradient pytree and emits one psum per bucket — the compiled
    counterpart of the fusion buffer + grouped allreduce path
    (controller.cc FuseResponses + EnqueueTensorAllreduces). Two properties
    give XLA's scheduler room to run each bucket's ICI transfer
    concurrently with the remaining backward compute (docs/perf.md;
    pinned by tests/test_overlap_hlo.py):

    * oversize gradients are CHUNKED across ≤-threshold buckets instead
      of forming one giant payload (the wire cap is
      min(fusion_threshold, HOROVOD_BUCKET_CAP) when the threshold comes
      from config; an explicit `fusion_threshold_bytes` is used as-is),
    * buckets are packed in REVERSE leaf order by default
      (`reverse_bucket_order`, HOROVOD_BUCKET_REVERSE), aligning each
      bucket with a contiguous span of early-available gradients — the
      backward pass produces the LAST layer's gradients first, so the
      first bucket's psum is ready while earlier layers are still
      differentiating (torch-DDP bucket ordering, Li et al. VLDB 2020).
    """
    thresh = fusion_threshold_bytes
    if thresh is None:
        if topology.is_initialized():
            cfg = topology.state().config
            thresh = fusion.effective_threshold(cfg.fusion_threshold_bytes,
                                                cfg.bucket_cap_bytes)
        else:
            thresh = 4 * 1024 * 1024
    reverse = reverse_bucket_order
    if reverse is None:
        reverse = (topology.state().config.bucket_reverse
                   if topology.is_initialized() else True)
    if axes is not None:
        # Hybrid-mesh mode (docs/parallelism.md): `axes` is a per-leaf
        # pytree of psum axis tuples (grad_axes_from_specs) — leaves
        # group per axis tuple and bucket per group, so model-sharded
        # parameters generate batch-axis traffic only.
        return _reduce_gradients_by_axes(
            grads, op, axes,
            tuple(mean_axes) if mean_axes is not None else BATCH_AXES,
            compression, thresh, reverse, gradient_predivide_factor)
    k = num_ranks if num_ranks is not None else lax.axis_size(axis)
    pre, post, rop = _scale_factors(op, k, gradient_predivide_factor)

    leaves, treedef = jax.tree_util.tree_flatten(grads)
    compressed, ctxs = zip(*[compression.compress(l) for l in leaves]) \
        if leaves else ((), ())
    blocks = [c[None] for c in compressed]

    def reduce_block(b: jax.Array) -> jax.Array:
        x = b
        if pre != 1.0:
            x = x * jnp.asarray(pre, x.dtype)
        if rop in (T.ReduceOp.SUM, T.ReduceOp.AVERAGE):
            y = lax.psum(x, axis)
            if rop == T.ReduceOp.AVERAGE:
                y = y / jnp.asarray(k, y.dtype)
        elif rop == T.ReduceOp.ADASUM:
            from horovod_tpu.ops import adasum as adasum_mod
            from horovod_tpu.core import topology as _topo
            y = adasum_mod.adasum_reduce_block(
                x, axis, k, halving=_topo.state().config.adasum_halving)
        else:
            raise HorovodTpuError(f"unsupported gradient reduce op {rop}")
        if post != 1.0:
            y = y * jnp.asarray(post, y.dtype)
        return y

    if rop == T.ReduceOp.ADASUM:
        reduced = tuple(reduce_block(b) for b in blocks)
    else:
        reduced = fusion.fused_reduce_blocks(blocks, reduce_block, thresh,
                                             reverse=reverse)
    out_leaves = [compression.decompress(r[0], c)
                  for r, c in zip(reduced, ctxs)]
    return jax.tree_util.tree_unflatten(treedef, out_leaves)


def DistributedGradientTransform(
        optimizer: optax.GradientTransformation,
        op: T.ReduceOp = T.ReduceOp.AVERAGE,
        axis: str = _AXIS,
        compression=Compression.none,
        gradient_predivide_factor: float = 1.0,
        num_ranks: Optional[int] = None,
        fusion_threshold_bytes: Optional[int] = None,
) -> optax.GradientTransformation:
    """Wrap an optax optimizer so update() reduces gradients across the mesh.

    SPMD analog of DistributedOptimizer (reference torch/optimizer.py:36):
    use inside a shard_map'd train step where `axis` is in scope.
    """

    def init_fn(params):
        return optimizer.init(params)

    def update_fn(grads, state, params=None, **extra):
        grads = reduce_gradients_in_jit(
            grads, op=op, axis=axis, compression=compression,
            fusion_threshold_bytes=fusion_threshold_bytes,
            num_ranks=num_ranks,
            gradient_predivide_factor=gradient_predivide_factor)
        return optimizer.update(grads, state, params, **extra)

    return optax.GradientTransformation(init_fn, update_fn)


class DistributedOptimizer:
    """Horovod-style eager optimizer wrapper.

    Reference: horovod/torch/optimizer.py `_DistributedOptimizer` +
    `DistributedOptimizer` factory (:560). Gradients are per-rank pytrees
    (plain tensors with one process per chip; leading-axis stacked under a
    single controller). Supports backward_passes_per_step local accumulation
    (reference gradient_aggregation.py) and Adasum (op=Adasum, reference
    `_DistributedAdasumOptimizer` optimizer.py:345).
    """

    def __init__(self,
                 optimizer: optax.GradientTransformation,
                 named_parameters: Optional[Any] = None,
                 compression=Compression.none,
                 backward_passes_per_step: int = 1,
                 op: Any = T.ReduceOp.AVERAGE,
                 gradient_predivide_factor: float = 1.0,
                 process_set: Optional[ProcessSet] = None,
                 sharding_spec: Any = None,
                 mesh: Any = None):
        del named_parameters  # tensor naming handled by pytree paths
        self.inner = optimizer
        self.compression = compression
        self.backward_passes_per_step = int(backward_passes_per_step)
        self.op = T.normalize_reduce_op(op)
        self.gradient_predivide_factor = float(gradient_predivide_factor)
        self.process_set = process_set or global_process_set
        # GSPMD hybrid-parallel backend (docs/parallelism.md): a
        # PartitionSpec pytree matching the params. With a spec set,
        # `sharded_step(loss_fn)` compiles the model-sharded train step
        # over `mesh` (default: the HOROVOD_MESH hybrid mesh) — grads
        # psum only over the batch axes while tp/pp/ep shards stay put.
        self.sharding_spec = sharding_spec
        self.mesh = mesh
        self._accum = None
        self._accum_count = 0

    def init(self, params: Any) -> Any:
        return self.inner.init(params)

    # -- GSPMD hybrid-parallel path ---------------------------------------
    def _spec_tree(self):
        """The sharding spec as a PartitionSpec pytree. NamedSharding
        leaves are accepted too (the ISSUE 14 API contract) — their
        specs are extracted and their mesh doubles as the default."""
        from jax.sharding import NamedSharding

        def leaf(s):
            return s.spec if isinstance(s, NamedSharding) else s

        return jax.tree_util.tree_map(
            leaf, self.sharding_spec,
            is_leaf=lambda x: isinstance(x, (P, NamedSharding))
            or x is None)

    def _resolve_mesh(self):
        m = self.mesh
        if m is None:
            from jax.sharding import NamedSharding
            for s in jax.tree_util.tree_leaves(
                    self.sharding_spec,
                    is_leaf=lambda x: isinstance(x, (P, NamedSharding))
                    or x is None):
                if isinstance(s, NamedSharding):
                    m = s.mesh
                    break
        if m is None and topology.is_initialized():
            m = topology.hybrid_mesh()
        if m is None:
            raise HorovodTpuError(
                "sharded_step needs a hybrid mesh: set HOROVOD_MESH "
                "(e.g. \"dp=2,tp=4\") before hvd.init(), or pass "
                "mesh= to DistributedOptimizer")
        return m

    def sharded_step(self, loss_fn: Callable,
                     batch_spec: Any = None,
                     donate: bool = True,
                     fusion_threshold_bytes: Optional[int] = None
                     ) -> Callable:
        """Compile the hybrid-parallel train step for this optimizer's
        sharding spec: ``step(params, opt_state, batch) -> (params,
        opt_state, loss)``. `loss_fn(params, batch)` is the SHARD-LOCAL
        loss (models/tied_lm.local_loss is the canonical example); see
        `build_sharded_train_step` for the full contract."""
        if self.sharding_spec is None:
            raise HorovodTpuError(
                "sharded_step requires DistributedOptimizer("
                "sharding_spec=<PartitionSpec pytree>)")
        return build_sharded_train_step(
            loss_fn, self.inner, mesh=self._resolve_mesh(),
            param_specs=self._spec_tree(), batch_spec=batch_spec,
            op=self.op, compression=self.compression,
            gradient_predivide_factor=self.gradient_predivide_factor,
            donate=donate,
            fusion_threshold_bytes=fusion_threshold_bytes)

    def shard_params(self, params: Any):
        """Place a global param pytree onto the hybrid mesh per this
        optimizer's sharding spec (jax.device_put with NamedSharding)."""
        if self.sharding_spec is None:
            raise HorovodTpuError("shard_params requires sharding_spec")
        from jax.sharding import NamedSharding
        m = self._resolve_mesh()
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(m, s)),
            params, self._spec_tree())

    # -- gradient reduction ------------------------------------------------
    def _allreduce_grads(self, grads: Any) -> Any:
        k = self.process_set.size()
        pre, post, rop = _scale_factors(
            self.op, k, self.gradient_predivide_factor)
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        comp = [self.compression.compress(l) for l in leaves]
        tensors = [c[0] for c in comp]
        ctxs = [c[1] for c in comp]
        L = collectives._local_member_count(self.process_set)
        stacked = [collectives._is_stacked(t, self.process_set, L)
                   for t in tensors]
        st = topology.state()
        pm = st.parameter_manager
        cfg = st.config
        # Instrumentation only while actively tuning: once frozen, the
        # block_until_ready sync would permanently defeat async dispatch.
        tuning = pm is not None and not pm.frozen
        # Per-bucket dispatch (docs/perf.md): each bucket's collective
        # launches independently so transfers pipeline across buckets;
        # Adasum keeps the grouped path (never fused).
        use_buckets = cfg.bucket_pipeline and rop != T.ReduceOp.ADASUM
        bt = st.bucket_tuner if use_buckets else None
        bt_active = bt is not None and not bt.frozen
        t0 = time.perf_counter() if tuning else 0.0
        if use_buckets:
            reduced = collectives.bucketed_allreduce(
                tensors, op=rop, prescale_factor=pre, postscale_factor=post,
                process_set=self.process_set,
                # Force per-bucket completion timing while either tuner is
                # live (the pm path blocks right below anyway).
                profile=True if (bt_active or tuning) else None)
        else:
            reduced = collectives.grouped_allreduce(
                tensors, op=rop, prescale_factor=pre, postscale_factor=post,
                process_set=self.process_set)
        if bt_active:
            for nb, sec in collectives.last_bucket_timings():
                bt.record_bucket(nb, sec)
            # May adjust cfg.fusion_threshold_bytes — rank 0 decides and
            # broadcasts, so every rank's NEXT plan (and compiled
            # programs) agree; no cache clear needed, the bucket cache
            # keys include the plan layout.
            bt.update()
        if tuning:
            jax.block_until_ready(reduced)
            nbytes = sum(int(np.prod(np.shape(t))) * np.dtype(
                getattr(t, "dtype", np.float32)).itemsize for t in tensors)
            pm.record(nbytes, time.perf_counter() - t0)
            # No cache clear on change: the grouped/bucketed cache keys
            # include the EFFECTIVE (cap-clamped) threshold, so a new
            # threshold simply misses and re-traces while other
            # executables stay warm — and the GP's search ceiling is
            # clamped to the cap (default_knobs), so its samples always
            # land where programs actually differ.
            pm.update()
        # Reduced per-rank rows are identical; collapse stacked inputs to a
        # single copy so updates apply to the (replicated) parameters.
        reduced = [r[0] if s else r for r, s in zip(reduced, stacked)]
        out = [self.compression.decompress(r, c)
               for r, c in zip(reduced, ctxs)]
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- step --------------------------------------------------------------
    def step(self, grads: Any, params: Any, opt_state: Any,
             **update_extra) -> Tuple[Any, Any]:
        """Reduce grads, apply the optax update. Returns (params, opt_state).

        With backward_passes_per_step > 1, gradients accumulate locally and
        the collective fires every Nth call (reference
        LocalGradientAggregationHelper.compute_gradients).

        perfscope auto-hook (profiler/perfscope.py): when the user
        delimited no explicit step, each call to this method closes one
        implicit training step — step N runs from the end of optimizer
        call N-1 to the end of call N — with the gradient reduction
        attributed to the `comms` phase and the update/apply to
        `optimizer`; everything in between (forward/backward dispatch,
        input) lands in the base `dispatch` phase.
        """
        scope = _pscope.get()
        scope.step_entry()
        try:
            return self._step_inner(grads, params, opt_state, scope,
                                    **update_extra)
        finally:
            # Accumulation-only calls (backward_passes_per_step > 1,
            # collective not fired: _accum_count left non-zero) are
            # micro-batches, not training steps — the implicit step
            # stays open so one record spans the whole accumulation
            # cycle and its comms/optimizer phases.
            if self._accum_count == 0:
                scope.step_boundary()

    def _step_inner(self, grads: Any, params: Any, opt_state: Any,
                    scope, **update_extra) -> Tuple[Any, Any]:
        if self.backward_passes_per_step > 1:
            if self._accum is None:
                self._accum = grads
            else:
                self._accum = jax.tree_util.tree_map(
                    jnp.add, self._accum, grads)
            self._accum_count += 1
            if self._accum_count < self.backward_passes_per_step:
                return params, opt_state
            grads = jax.tree_util.tree_map(
                lambda g: g / self.backward_passes_per_step, self._accum)
            self._accum = None
            self._accum_count = 0

        with scope.phase("comms"), _REDUCE_SPAN():
            avg = self._allreduce_grads(grads)
        if update_extra or getattr(self, "_apply_eager", False):
            # extra kwargs (e.g. loss for lookahead-style transforms) are
            # rare and may not be jit-stable — eager fallback; also used
            # permanently for inner transforms that cannot trace
            with scope.phase("optimizer"), _APPLY_SPAN():
                updates, new_state = self.inner.update(
                    avg, opt_state, params, **update_extra)
                return optax.apply_updates(params, updates), new_state
        try:
            with scope.phase("optimizer"), _APPLY_SPAN():
                out = self._jitted_apply()(avg, opt_state, params)
            # success means tracing worked; later errors of the caught
            # types are runtime failures, not traceability, and re-raise
            self._apply_traced_ok = True
            return out
        except (jax.errors.JAXTypeError, jax.errors.JAXIndexError,
                TypeError, ValueError) as e:
            # the user's transform does host-side / value-dependent work,
            # leaks tracers, or keeps non-array leaves in its state — all
            # legal before this path was jitted. Fall back for good, but
            # only for errors raised by TRACING: a failure from the
            # already-compiled executable (e.g. device OOM) re-raises.
            if getattr(self, "_apply_traced_ok", False):
                raise
            from horovod_tpu.common.hvd_logging import get_logger
            get_logger().warning(
                "optimizer apply not jittable (%s); running the update "
                "un-jitted from now on", type(e).__name__)
            self._apply_eager = True
            with scope.phase("optimizer"), _APPLY_SPAN():
                updates, new_state = self.inner.update(avg, opt_state,
                                                       params)
                return optax.apply_updates(params, updates), new_state

    def _jitted_apply(self):
        """The optax update + apply as ONE compiled program.

        Run eagerly, an adam update is ~6 small XLA ops per tensor —
        hundreds of dispatches per step that dominate wall clock and
        waste fusion. jit
        re-traces per (treedef, shapes) signature automatically; the
        cache is invalidated if `self.inner` is reassigned.
        """
        if getattr(self, "_apply_fn", None) is None or \
                getattr(self, "_apply_inner", None) is not self.inner:
            inner = self.inner

            def apply(avg, opt_state, params):
                updates, new_state = inner.update(avg, opt_state, params)
                return optax.apply_updates(params, updates), new_state

            self._apply_fn = jax.jit(apply)
            self._apply_inner = inner
        return self._apply_fn

    def update(self, grads: Any, opt_state: Any, params: Any = None,
               **extra) -> Tuple[Any, Any]:
        """optax-compatible update: returns (updates, new_opt_state)."""
        scope = _pscope.get()
        with scope.phase("comms"):
            avg = self._allreduce_grads(grads)
        with scope.phase("optimizer"):
            return self.inner.update(avg, opt_state, params, **extra)


# TF-parity alias (reference: DistributedGradientTape, tensorflow/__init__.py
# :1125): in JAX the "tape" is value_and_grad; distribution happens on the
# resulting gradient pytree, so the tape wrapper and the optimizer wrapper
# collapse into the same object.
DistributedGradientTape = DistributedOptimizer


def build_train_step(loss_fn: Callable,
                     optimizer: optax.GradientTransformation,
                     mesh=None,
                     op: T.ReduceOp = T.ReduceOp.AVERAGE,
                     compression=Compression.none,
                     gradient_predivide_factor: float = 1.0,
                     batch_spec: Any = None,
                     donate: bool = True) -> Callable:
    """Compile a full data-parallel SPMD train step over the mesh.

    The flagship fast path: params replicated, batch sharded over 'hvd',
    gradients bucketed+psum'd inside the program, optax update applied
    replicated. This is what `horovodrun`-launched training uses per step
    (the compiled counterpart of the reference's per-step hook machinery).

    loss_fn: (params, batch) -> scalar loss.
    Returns step(params, opt_state, batch) -> (params, opt_state, loss).
    """
    m = mesh if mesh is not None else topology.mesh()
    if _AXIS not in m.axis_names:
        raise HorovodTpuError(
            f"build_train_step requires a mesh with axis '{_AXIS}'")
    # Averaging divisor = the size of the axis actually psum'd over — NOT
    # the whole mesh (a multi-axis mesh would silently scale gradients).
    k = int(m.shape[_AXIS])
    bspec = batch_spec if batch_spec is not None else P(_AXIS)

    def local_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads = reduce_gradients_in_jit(
            grads, op=op, compression=compression, num_ranks=k,
            gradient_predivide_factor=gradient_predivide_factor)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        loss = lax.pmean(loss, _AXIS)
        return params, opt_state, loss

    sharded = jax.shard_map(
        local_step, mesh=m,
        in_specs=(P(), P(), bspec),
        out_specs=(P(), P(), P()),
        check_vma=False)
    donate_argnums = (0, 1) if donate else ()
    return jax.jit(sharded, donate_argnums=donate_argnums)


def build_sharded_train_step(loss_fn: Callable,
                             optimizer: optax.GradientTransformation,
                             mesh=None,
                             param_specs: Any = None,
                             batch_spec: Any = None,
                             op: T.ReduceOp = T.ReduceOp.AVERAGE,
                             compression=Compression.none,
                             gradient_predivide_factor: float = 1.0,
                             donate: bool = True,
                             fusion_threshold_bytes: Optional[int] = None
                             ) -> Callable:
    """Compile the GSPMD hybrid-parallel train step (docs/parallelism.md).

    The model-sharded sibling of `build_train_step`: parameters follow a
    user PartitionSpec pytree over the 5-axis hybrid mesh
    (parallel/mesh.py; HOROVOD_MESH), the batch shards over the batch
    axes, and the gradient reduction — bucketed and overlap-packed
    exactly like the DP path — psums each leaf only over the axes it is
    replicated across (grad_axes_from_specs): tp/pp/ep-sharded weights
    generate batch-axis traffic only.

    Contract for `loss_fn(params, batch) -> scalar`:

    * it runs UNDER shard_map — `params`/`batch` are the local shards
      and every mesh axis name is in scope (lax.psum etc.);
    * it returns the LOCAL batch shard's loss, not psum'd over the
      batch axes (the psum transpose would scale cotangents by the
      axis size — models/transformer.py NOTE);
    * over the model axes the loss value is computed REDUNDANTLY (every
      tp member holds the same scalar — models/tied_lm.local_loss's
      cooperative psums, or transformer.py's replicated activations);
      per-shard AD then scales gradients by the axis size, which this
      builder divides back out (REDUNDANT_LOSS_AXES).

    forward/backward and the gradient collectives run inside one
    shard_map; the optax update runs under GSPMD, which propagates the
    parameter shardings through the elementwise update (opt-state
    moments land sharded like their parameters — the ZeRO-style free
    lunch of spec-driven updates). Returns
    ``step(params, opt_state, batch) -> (params, opt_state, loss)``.
    """
    if mesh is None:
        m = topology.hybrid_mesh() if topology.is_initialized() else None
        if m is None:
            raise HorovodTpuError(
                "build_sharded_train_step needs a hybrid mesh "
                "(HOROVOD_MESH before hvd.init(), or mesh=)")
        mesh = m
    if param_specs is None:
        raise HorovodTpuError(
            "build_sharded_train_step requires param_specs "
            "(a PartitionSpec pytree matching the params)")
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if batch_spec is None:
        batch_spec = P("dp")
    axes = grad_axes_from_specs(param_specs, mesh)
    batch_axes = tuple(a for a in _spec_axis_names(batch_spec)
                       if sizes.get(a, 1) > 1)
    redundant = 1
    for a in REDUNDANT_LOSS_AXES:
        redundant *= sizes.get(a, 1)

    def local_step(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        if redundant != 1:
            # Per-shard AD of the redundantly-computed loss scaled every
            # gradient by the model-axis size; see the contract above.
            grads = jax.tree_util.tree_map(
                lambda g: g / jnp.asarray(redundant, g.dtype), grads)
        grads = reduce_gradients_in_jit(
            grads, op=op, compression=compression,
            fusion_threshold_bytes=fusion_threshold_bytes,
            gradient_predivide_factor=gradient_predivide_factor,
            axes=axes, mean_axes=batch_axes)
        if batch_axes:
            loss = lax.pmean(loss, batch_axes)
        return loss, grads

    sharded_lg = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(param_specs, batch_spec),
        out_specs=(P(), param_specs),
        check_vma=False)

    donate_argnums = (0, 1) if donate else ()

    @partial(jax.jit, donate_argnums=donate_argnums)
    def step(params, opt_state, batch):
        loss, grads = sharded_lg(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step
