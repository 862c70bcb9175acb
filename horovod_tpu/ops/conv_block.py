"""Fused 1x1-conv + BatchNorm + ReLU block as Pallas TPU kernels.

The conv-MFU gap's kernel-level lever (ROADMAP item 1): ResNet-style
models spend their step in conv+BN+activation triplets, and XLA
schedules each triplet as separate full-HBM streams around the
materialized conv output `y`:

    XLA schedule per 1x1-conv+BN+ReLU site (forward):
      matmul   read x, w          -> WRITE y
      stats    read y             -> mean, meansq   (fused reduce pair)
      norm     read y             -> write z        (normalize+scale+relu)

`y` is written once and read twice. The forward kernel here folds the
stats reduction INTO the matmul pass: each (block_m, C) tile of y is
formed on the MXU and its per-channel partial sums (sum, sum-of-squares)
accumulate into VMEM-resident f32 rows before the tile is stored — one
full stream of y disappears. A single XLA elementwise epilogue then
forms mean/var and applies normalize+scale+relu (that pass XLA already
runs at the streaming roofline, so it stays outside the kernel).

    fused forward:
      kernel   read x, w          -> write y, sum, sumsq   (one pass)
      norm     read y             -> write z

The backward extends ops/conv_bn_backward.py's fused dx/dW kernel with
the ReLU mask folded into the register pipeline: the upstream gradient
dz (w.r.t. the ReLU OUTPUT) is masked, run through the train-mode BN
backward, and fed to both MXU contractions without `dy` (or the mask)
ever existing in HBM:

    fused backward:
      pass A   read dz, y         -> dbeta, dgamma  (masked sums; XLA)
      kernel   read dz, y, x_in   -> write dx, dW   (one pass)

Only 1x1 convs qualify (their backward-input is a matmul the MXU eats
directly); 3x3 sites keep XLA's conv custom-calls. The family degrades
to `relu=False` for the block's conv3/projection sites (BN with no
activation before the residual add).

A plain `jax.lax` reference (`conv_block_reference`) defines the ground
truth; tests/test_conv_block.py pins fused-vs-reference equivalence for
forward, gradients, batch-stat cotangents, and the bf16 path. On
non-TPU backends the kernels run in Pallas interpret mode (the shared
decision in ops/_pallas.py), so tier-1 exercises the real pallas_call
path on CPU.

Model wiring: HOROVOD_CONV_BLOCK=1 routes models/resnet.py's profitable
1x1 sites through this family (docs/perf.md "conv fast path"); it
supersedes the backward-only HOROVOD_FUSE_CONV_BN opt-in.
"""

from __future__ import annotations

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops._pallas import pallas_call
from horovod_tpu.ops.conv_bn_backward import (_axis_size, _pick_block_m,
                                              _pmean)


CONV_BLOCK_ENV = "HOROVOD_CONV_BLOCK"


def conv_block_enabled() -> bool:
    """HOROVOD_CONV_BLOCK=1 opts the models into the fused block family
    (docs/perf.md, docs/env_vars.md)."""
    return os.environ.get(CONV_BLOCK_ENV, "").strip() in ("1", "true",
                                                          "True")


# --------------------------------------------------------------------------
# reference (ground truth for the equivalence suite)
# --------------------------------------------------------------------------

def conv_block_reference(x, w, scale, bias, eps=1e-5, axis_name=None,
                         relu=True):
    """Plain jax.lax math of the block over flattened rows: z =
    relu(batch_norm(x @ w)), train mode, returning (z, (mean, var)) —
    exactly what XLA computes unfused, and the contract the fused op
    must match."""
    y = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y.astype(x.dtype)
    mean = _pmean(jnp.mean(y, axis=0, dtype=jnp.float32), axis_name)
    meansq = _pmean(jnp.mean(jnp.square(y.astype(jnp.float32)), axis=0),
                    axis_name)
    var = meansq - jnp.square(mean)
    inv = jax.lax.rsqrt(var + eps)
    # The whole epilogue runs in f32 (xhat, scale, bias, ReLU) and only
    # the final z rounds to the storage dtype. This is a deliberate
    # contract: the backward MASK recomputes this exact f32 chain, and
    # f32 is the only dtype whose arithmetic XLA and the Pallas kernel
    # reproduce identically (bf16 mul+add keeps excess precision
    # inconsistently across lowerings, so a bf16 epilogue's boundary
    # signs would be irreproducible in the backward).
    zf = ((y.astype(jnp.float32) - mean) * inv) \
        * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    if relu:
        zf = jax.nn.relu(zf)
    return zf.astype(x.dtype), (mean, var)


# --------------------------------------------------------------------------
# forward kernel: y = x @ w with the BN stat sums fused into the pass
# --------------------------------------------------------------------------

def _pick_fwd_block_m(m: int, bc: int, cin: int, c: int,
                      vmem_budget=9 * 2**20) -> int:
    """Largest row block that divides m and keeps the streamed tiles
    (double-buffered) plus the resident f32 stat rows inside VMEM."""
    fixed = 2 * c * 4  # resident f32 sum + sumsq rows
    for bm in (1024, 512, 448, 256, 128, 64, 32, 16, 8):
        if m % bm:
            continue
        streamed = 2 * (bm * cin * 2 + cin * bc * 2 + bm * bc * 2)
        if fixed + streamed + bm * bc * 4 <= vmem_budget:
            return bm
    return 8


def _fwd_kernel(x_ref, w_ref, y_ref, sum_ref, sq_ref):
    """One (bm, bc) tile: y = x @ w on the MXU; the tile's per-channel
    sum and sum-of-squares accumulate into constant-index f32 rows that
    stay VMEM-resident across the whole sequential grid (copy-out at
    grid end) — the stats reduction never re-reads y from HBM."""
    i, j = pl.program_id(0), pl.program_id(1)
    bc = y_ref.shape[1]
    yt = jax.lax.dot_general(x_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y_ref[:] = yt.astype(y_ref.dtype)
    # Sums of the STORED (rounded) y, not the f32 accumulator values:
    # the batch stats must describe the activations every later pass
    # (epilogue, backward xhat) actually reads, or bf16 boundary signs
    # diverge from the unfused reference.
    ys = yt.astype(y_ref.dtype).astype(jnp.float32)
    part_sum = jnp.sum(ys, axis=0, keepdims=True)        # (1, bc)
    part_sq = jnp.sum(jnp.square(ys), axis=0, keepdims=True)
    col = pl.ds(pl.multiple_of(j * bc, 128), bc)

    @pl.when(i == 0)
    def _init():  # uninitialized VMEM may hold NaN bits: store, not 0*
        sum_ref[:, col] = part_sum
        sq_ref[:, col] = part_sq

    @pl.when(i > 0)
    def _acc():
        sum_ref[:, col] = sum_ref[:, col] + part_sum
        sq_ref[:, col] = sq_ref[:, col] + part_sq


def _lane_block(c: int) -> int:
    """Largest dividing lane-aligned C block <= 512 (same policy as
    conv_bn_backward: the wide sites must not collapse the row blocks)."""
    if c <= 512:
        return c
    bc = next((b for b in (512, 384, 256, 128) if c % b == 0), None)
    if bc is None:
        raise ValueError(
            f"conv_block: C={c} > 512 must be divisible by a "
            f"128-multiple block (got C % 128 == {c % 128})")
    return bc


def conv1x1_fwd_fused(x: jax.Array, w: jax.Array
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """y = x @ w plus the per-channel (sum, sumsq) f32 rows, one fused
    pass. x: (M, Cin); w: (Cin, C). Returns (y (M, C) in x.dtype,
    sum (C,) f32, sumsq (C,) f32) — the sums cover the REAL M rows
    (zero row padding contributes zero to both)."""
    m, cin = x.shape
    c = w.shape[1]
    m_pad = -m % 8
    if m_pad:
        x = jnp.pad(x, ((0, m_pad), (0, 0)))
    mp = m + m_pad
    bc = _lane_block(c)
    bm = _pick_fwd_block_m(mp, bc, cin, c)
    y, ssum, ssq = pallas_call(
        _fwd_kernel,
        grid=(mp // bm, c // bc),
        in_specs=[
            pl.BlockSpec((bm, cin), lambda i, j: (i, 0)),     # x
            pl.BlockSpec((cin, bc), lambda i, j: (0, j)),     # w
        ],
        out_specs=[
            pl.BlockSpec((bm, bc), lambda i, j: (i, j)),      # y
            # constant index: the f32 stat rows stay resident in VMEM
            # across the whole sequential grid, one copy-out at end
            pl.BlockSpec((1, c), lambda i, j: (0, 0)),        # sum
            pl.BlockSpec((1, c), lambda i, j: (0, 0)),        # sumsq
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, c), x.dtype),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),  # sequential
    )(x, w)
    return (y[:m] if m_pad else y), ssum.ravel(), ssq.ravel()


# --------------------------------------------------------------------------
# backward kernel: ReLU mask + BN backward + both MXU contractions
# --------------------------------------------------------------------------

def _bwd_kernel(dz_ref, y_ref, x_ref, w_ref, g_ref, mean_ref, inv_ref,
                a_ref, b_ref, s_ref, bias_ref, dx_ref, dw_ref,
                dx_acc_ref):
    """One (bm, bc) tile: recompute the ReLU mask from (y, stats,
    scale, bias), mask dz, form dy in registers, feed both MXU
    contractions. Layout and accumulator scheme match
    conv_bn_backward._bwd_kernel; the only addition is the mask — for
    relu=False sites the wrapper passes (scale=0, bias=1) rows, which
    make zpre = 1 > 0 everywhere (mask all-true, zero extra cost)."""
    i, j = pl.program_id(0), pl.program_id(1)
    nc = pl.num_programs(1)
    bc = dz_ref.shape[1]
    dz = dz_ref[:].astype(jnp.float32)          # (bm, bc)
    y = y_ref[:].astype(jnp.float32)            # (bm, bc)
    xhat = (y - mean_ref[:]) * inv_ref[:]       # stats bcast (1, bc)
    # The mask recomputes the FORWARD's f32 epilogue chain (see
    # _fwd_math: xhat, scale, bias all f32, only the final z rounds to
    # the storage dtype) — sign decisions are reproducible because no
    # low-precision rounding sits in the decision path.
    zpre = xhat * s_ref[:] + bias_ref[:]
    dzm = jnp.where(zpre > 0.0, dz, 0.0)
    dy = (g_ref[:] * dzm - a_ref[:] - b_ref[:] * xhat).astype(dz_ref.dtype)
    part_dx = jax.lax.dot_general(              # dy @ w_blk^T -> (bm, Cin)
        dy, w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _dx_init():
        dx_acc_ref[:] = part_dx

    @pl.when(j > 0)
    def _dx_acc():
        dx_acc_ref[:] += part_dx

    @pl.when(j == nc - 1)
    def _dx_emit():
        dx_ref[:] = dx_acc_ref[:].astype(dx_ref.dtype)

    part_dw = jax.lax.dot_general(              # x^T @ dy -> (Cin, bc)
        x_ref[:], dy, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    col = pl.ds(pl.multiple_of(j * bc, 128), bc)

    @pl.when(i == 0)
    def _dw_init():  # uninitialized VMEM may hold NaN bits: store, not 0*
        dw_ref[:, col] = part_dw

    @pl.when(i > 0)
    def _dw_acc():
        dw_ref[:, col] = dw_ref[:, col] + part_dw


def conv1x1_bn_act_bwd_fused(dz: jax.Array, y: jax.Array,
                             x_in: jax.Array, w: jax.Array,
                             scale: jax.Array, bias: jax.Array,
                             mean: jax.Array, inv: jax.Array,
                             dbeta: jax.Array, dgamma: jax.Array,
                             dmean=None, dvar=None, count=None,
                             relu: bool = True
                             ) -> Tuple[jax.Array, jax.Array]:
    """dx, dw for a 1x1 conv + train-mode BN + optional ReLU, given dz
    w.r.t. the BLOCK output and pass A's MASKED sums.

    dz, y: (M, C) rows (flattened N*H*W); x_in: (M, Cin); w: (Cin, C);
    mean/inv/dbeta/dgamma: (C,) f32; scale/bias: (C,) in the MODEL's
    dtype (the mask re-runs the forward's arithmetic chain in those
    dtypes). dbeta/dgamma are already the masked sums `_bn_act_sums`
    computes (with relu=False the mask is identity and they equal the
    plain BN sums).
    dmean/dvar: optional (C,) f32 cotangents on the batch-stat outputs,
    folded exactly into the per-channel vectors. count: total rows
    behind the batch stats (M * axis_size under sync-BN; defaults to
    M). Returns dx (M, Cin) in x_in.dtype and dw (Cin, C) f32."""
    m, c = dz.shape
    cin = x_in.shape[1]
    minv = 1.0 / (count if count is not None else m)
    scale = scale.astype(jnp.float32).ravel()
    g = scale * inv
    a_vec = g * dbeta * minv
    b_vec = g * dgamma * minv
    if dmean is not None:
        a_vec = a_vec - dmean * minv
    if dvar is not None:
        b_vec = b_vec - 2.0 * dvar * minv / inv
    # Padded x_in rows are ZERO, so padded-row dy never reaches dW
    # (0^T @ dy) and padded dx rows are sliced off below; padded-row dz
    # is zero too, so the mask cannot resurrect them. minv stays 1/m —
    # the real row count.
    m_pad = -m % 8
    if m_pad:
        pad = lambda a: jnp.pad(a, ((0, m_pad), (0, 0)))  # noqa: E731
        dz, y, x_in = pad(dz), pad(y), pad(x_in)
    mp = m + m_pad
    bc = _lane_block(c)
    bm = _pick_block_m(mp, bc, cin, c)
    row = lambda v: v.reshape(1, c).astype(jnp.float32)  # noqa: E731
    if relu:  # f32 rows: the mask reruns the forward's f32 epilogue
        s_row, b_row = scale, bias.astype(jnp.float32).ravel()
    else:  # mask all-true: zpre = xhat*0 + 1 > 0 everywhere
        s_row = jnp.zeros((c,), jnp.float32)
        b_row = jnp.ones((c,), jnp.float32)
    dx, dw = pallas_call(
        _bwd_kernel,
        grid=(mp // bm, c // bc),
        in_specs=[
            pl.BlockSpec((bm, bc), lambda i, j: (i, j)),     # dz
            pl.BlockSpec((bm, bc), lambda i, j: (i, j)),     # y
            pl.BlockSpec((bm, cin), lambda i, j: (i, 0)),    # x_in
            pl.BlockSpec((cin, bc), lambda i, j: (0, j)),    # w
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),      # g
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),      # mean
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),      # inv
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),      # A
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),      # B
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),      # scale
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),      # bias
        ],
        out_specs=[
            pl.BlockSpec((bm, cin), lambda i, j: (i, 0)),    # dx
            # constant index: the f32 dW accumulator stays resident in
            # VMEM across the whole sequential grid, one copy-out at end
            pl.BlockSpec((cin, c), lambda i, j: (0, 0)),     # dw
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, cin), x_in.dtype),
            jax.ShapeDtypeStruct((cin, c), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, cin), jnp.float32)],  # dx accum
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),  # sequential
    )(dz, y, x_in, w, row(g), row(mean), row(inv), row(a_vec),
      row(b_vec), row(s_row), row(b_row))
    return (dx[:m] if m_pad else dx), dw


# --------------------------------------------------------------------------
# custom_vjp wrapper: the model-facing fused block
# --------------------------------------------------------------------------

def _bn_act_sums(dz, y, mean, inv, scale, bias, relu):
    """Pass A (XLA): the MASKED BN-backward sums — dbeta = sum(dz*mask),
    dgamma = sum(dz*mask*xhat) — one fused read of dz+y, already at the
    streaming roofline (the mask is recomputed from y and the stats, no
    extra stream). dbeta doubles as dbias: dL/dbias = sum of the masked
    upstream gradient."""
    dzf = dz.astype(jnp.float32)
    xhat = (y.astype(jnp.float32) - mean) * inv
    if relu:
        # Same f32 epilogue chain as the forward and the kernel's mask
        # (see _fwd_math / _bwd_kernel): sign decisions match exactly.
        zpre = xhat * scale.astype(jnp.float32).ravel() \
            + bias.astype(jnp.float32).ravel()
        dzf = jnp.where(zpre > 0.0, dzf, 0.0)
    return jnp.sum(dzf, axis=0), jnp.sum(dzf * xhat, axis=0)


def _fwd_math(x, w, scale, bias, eps, axis_name, relu):
    y, ssum, ssq = conv1x1_fwd_fused(x, w)
    m = x.shape[0]
    # With axis_name: cross-replica (sync) batch stats — the fused
    # analog of models/resnet.batch_norm's pmean'd stats.
    mean = _pmean(ssum / m, axis_name)
    meansq = _pmean(ssq / m, axis_name)
    var = meansq - jnp.square(mean)
    inv = jax.lax.rsqrt(var + eps)
    # f32 epilogue, final rounding only — the same chain the reference
    # defines and the backward mask recomputes (the reproducibility
    # contract is documented on conv_block_reference).
    zf = ((y.astype(jnp.float32) - mean) * inv) \
        * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    if relu:
        zf = jax.nn.relu(zf)
    return zf.astype(x.dtype), (y, mean, var, inv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def conv1x1_bn_act(x, w, scale, bias, eps=1e-5, axis_name=None,
                   relu=True):
    """z = relu(batch_norm(x @ w)) over flattened rows, train mode —
    forward through the fused stats kernel, backward through the fused
    masked kernel. With `axis_name`, batch stats sync across that mesh
    axis (sync-BN semantics, models/resnet.batch_norm contract).
    `relu=False` drops the activation (the block's conv3/projection
    sites: BN straight into the residual add). Returns
    (z, (batch_mean, batch_var)); the aux stats feed running-stat
    updates exactly like models/resnet.batch_norm. Param/input grads
    are per-rank partials — the framework's gradient psum completes
    them, same as the unfused autodiff path."""
    z, (y, mean, var, inv) = _fwd_math(x, w, scale, bias, eps, axis_name,
                                       relu)
    return z, (mean, var)


def _conv_block_fwd(x, w, scale, bias, eps, axis_name, relu):
    z, (y, mean, var, inv) = _fwd_math(x, w, scale, bias, eps, axis_name,
                                       relu)
    return (z, (mean, var)), (x, w, scale, bias, y, mean, inv)


def _conv_block_bwd(eps, axis_name, relu, res, cts):
    x, w, scale, bias, y, mean, inv = res
    dz, (dmean, dvar) = cts
    dbeta, dgamma = _bn_act_sums(dz, y, mean, inv, scale, bias, relu)
    # Sync-BN backward needs the GLOBAL reductions and row count in the
    # dy formula; the RETURNED dscale/dbias stay per-rank partials (the
    # framework's later gradient psum makes them global, exactly like
    # unfused autodiff). dmean/dvar cotangents (zero in normal training
    # — optax treats batch stats as state — but exact when a loss does
    # use the aux stats) fold into the kernel's per-channel vectors.
    k = _axis_size(axis_name)
    db_g = dbeta if axis_name is None else jax.lax.psum(dbeta, axis_name)
    dg_g = dgamma if axis_name is None else jax.lax.psum(dgamma, axis_name)
    dm_g = dmean if axis_name is None else jax.lax.psum(dmean, axis_name)
    dv_g = dvar if axis_name is None else jax.lax.psum(dvar, axis_name)
    dx, dw = conv1x1_bn_act_bwd_fused(
        dz, y, x, w, scale, bias, mean, inv, db_g, dg_g,
        dmean=dm_g, dvar=dv_g, count=dz.shape[0] * k, relu=relu)
    return (dx, dw.astype(w.dtype), dgamma.astype(scale.dtype),
            dbeta.astype(bias.dtype))


conv1x1_bn_act.defvjp(_conv_block_fwd, _conv_block_bwd)


def conv1x1_bn_relu(x, w, scale, bias, eps=1e-5, axis_name=None):
    """The headline fused block: z = relu(batch_norm(x @ w))."""
    return conv1x1_bn_act(x, w, scale, bias, eps, axis_name, True)


def conv1x1_bn_act_nhwc(x, w, scale, bias, eps=1e-5, axis_name=None,
                        relu=True):
    """NHWC convenience wrapper: x (N, H, W, Cin), w (1, 1, Cin, Cout)
    or (Cin, Cout). Returns (z in NHWC, (mean, var))."""
    n, h, wd, cin = x.shape
    w2 = w.reshape(w.shape[-2], w.shape[-1])
    z, stats = conv1x1_bn_act(x.reshape(n * h * wd, cin), w2, scale,
                              bias, eps, axis_name, relu)
    return z.reshape(n, h, wd, -1), stats
