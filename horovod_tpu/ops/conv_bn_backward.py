"""Fused 1x1-conv + BatchNorm backward as a Pallas TPU kernel.

THE ResNet-50 step-time lever (round-4 trace, docs/benchmarks.md): the
BN-backward reduction family is ~33% of the step and sits at its own HBM
roofline because XLA materializes the BN-input gradient `dy` between the
BN-backward elementwise pass and the conv backward that consumes it:

    XLA schedule per 1x1-conv+BN site (all full HBM streams):
      pass A   read dz, y            -> dbeta, dgamma   (reductions)
      pass B   read dz, y            -> WRITE dy
      conv dx  read dy (+w)          -> write dx
      conv dW  read dy, x_in         -> write dW

`dy` is written once and read twice — three full streams of the largest
activation family in the network (the 4*width conv3 outputs alone are
~1.4 GB/step at B=128). This kernel fuses pass B INTO both consumer
matmuls: each (block_m, C) tile of dy is formed in registers from
(dz, y, stats, pass-A sums) and immediately fed to the MXU for
dx = dy @ w.T and the dW accumulation — dy never exists in HBM:

    fused:
      pass A   read dz, y            -> dbeta, dgamma   (XLA, unchanged)
      kernel   read dz, y, x_in      -> write dx, dW    (one pass)

Pass A stays in XLA: its reductions must COMPLETE before any dy tile can
be formed (two-phase dependency), and XLA already runs it at the
streaming roofline. Only 1x1 convs qualify (their backward-input is a
matmul the MXU eats directly); 3x3 sites keep XLA's conv custom-calls.

The dW accumulator rides as a constant-index f32 output block, resident
in VMEM across the sequential (row x C-block) grid; dx tiles stream out.
bf16 in, f32 accumulation, bf16 out — matching what XLA does for the
unfused sequence.

MEASURED OUTCOME (r05, v5e, scripts/bn_conv_bwd_ab.py +
docs/benchmarks.md): the kernel WINS at the layer level — 1.47-1.90x at
the dominant high-resolution conv3 sites, parity at conv1 — but LOSES
integrated into the ResNet-50 train step (80.9 vs 45.2 ms), because the
custom_vjp boundary de-fuses the surrounding graph: relu and its mask
become standalone full-size passes, the BN stat reduces detach from
their neighbors, and XLA inserts {3,0,2,1}<->{3,2,1,0} layout copies
between the flat (M, C) kernel operands and the 3x3 convs' preferred
batch-minor layouts (~tens of ms of copies in the trace). The model
integration therefore defaults OFF (models/resnet.py _fuse_conv_bn);
closing the gap would need relu/residual-add absorbed into the op
boundary AND layout-custom pallas outputs.

No reference counterpart (the reference wraps cuDNN's fused
BatchNormBackwardEx, torch/mxnet do the fusion below it); this is the
TPU-native equivalent of that fusion, one level deeper.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops._pallas import pallas_call


def _pick_block_m(m: int, bc: int, cin: int, c_full: int,
                  vmem_budget=9 * 2**20) -> int:
    """Largest row block that divides m, keeps the working set (streamed
    tiles double-buffered + the resident f32 dW output accumulator of
    the FULL (Cin, C)) inside VMEM, and stays a multiple of the 8-row
    sublane."""
    fixed = cin * c_full * 4  # resident f32 dW accumulator (output block)
    for bm in (1024, 512, 448, 256, 128, 64, 32, 16, 8):
        if m % bm:
            continue
        streamed = 2 * (2 * bm * bc * 2 + bm * cin * 2 + cin * bc * 2
                        + bm * cin * 2)  # dz,y,x_in,w,dx x2 buffers
        if fixed + streamed + bm * bc * 4 + bm * cin * 4 <= vmem_budget:
            return bm
    return 8


def _bwd_kernel(dz_ref, y_ref, x_ref, w_ref, g_ref, mean_ref, inv_ref,
                a_ref, b_ref, dx_ref, dw_ref, dx_acc_ref):
    """One (bm, bc) tile of a (rows x C-blocks) grid: form dy in
    registers, feed both MXU contractions.

    dy = g*dz - A - B*xhat — the full train-mode BN backward (gradients
    through batch mean/var, plus any cotangents on the aux stats outputs)
    pre-folded into per-channel vectors by the wrapper:
      g = gamma*inv,  A = g*dbeta/M - dmean/M,
      B = g*dgamma/M - 2*dvar/(M*inv).

    Grid is (row blocks, C blocks), C innermost. dx accumulates over the
    inner C loop in f32 scratch and is emitted once per row block; dW
    rides a CONSTANT-index f32 output block — resident in VMEM for the
    whole sequential grid (copy-out only at grid end), accumulated at
    the (0, j*bc) column slice each step."""
    i, j = pl.program_id(0), pl.program_id(1)
    nc = pl.num_programs(1)
    bc = dz_ref.shape[1]
    dz = dz_ref[:].astype(jnp.float32)          # (bm, bc)
    y = y_ref[:].astype(jnp.float32)            # (bm, bc)
    xhat = (y - mean_ref[:]) * inv_ref[:]       # stats bcast (1, bc)
    dy = (g_ref[:] * dz - a_ref[:] - b_ref[:] * xhat).astype(dz_ref.dtype)
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


def conv1x1_bn_bwd_fused(dz: jax.Array, y: jax.Array, x_in: jax.Array,
                         w: jax.Array, scale: jax.Array, mean: jax.Array,
                         inv: jax.Array, dbeta: jax.Array,
                         dgamma: jax.Array, dmean=None, dvar=None,
                         count=None) -> Tuple[jax.Array, jax.Array]:
    """dx, dw for a 1x1 conv followed by train-mode BN, given the
    upstream gradient dz w.r.t. the BN OUTPUT and pass A's sums.

    dz, y: (M, C) rows (flattened N*H*W); x_in: (M, Cin); w: (Cin, C);
    scale/mean/inv/dbeta/dgamma: (C,) f32. dmean/dvar: optional (C,) f32
    cotangents on the batch-stat outputs (exactly folded into the
    per-channel vectors — see _bwd_kernel). count: total rows behind the
    batch stats (M * axis_size under sync-BN; defaults to M). Returns
    dx (M, Cin) in x_in.dtype and dw (Cin, C) f32.
    """
    m, c = dz.shape
    cin = x_in.shape[1]
    minv = 1.0 / (count if count is not None else m)
    g = scale.astype(jnp.float32) * inv
    a_vec = g * dbeta * minv
    b_vec = g * dgamma * minv
    if dmean is not None:
        a_vec = a_vec - dmean * minv
    if dvar is not None:
        b_vec = b_vec - 2.0 * dvar * minv / inv
    # Pad rows to a sublane multiple: padded x_in rows are ZERO, so their
    # (nonzero) dy never reaches dW (0^T @ dy) and their dx rows are
    # sliced off below. minv stays 1/m — the real row count.
    m_pad = -m % 8
    if m_pad:
        pad = lambda a: jnp.pad(a, ((0, m_pad), (0, 0)))  # noqa: E731
        dz, y, x_in = pad(dz), pad(y), pad(x_in)
    mp = m + m_pad
    # C blocks: cap the per-step tile at 512 lanes so the resident f32
    # dW block (not per-C-block scratch) is the only Cin*C-sized buffer
    # and row blocks stay large at the wide sites (Cin=512, C=2048 used
    # to collapse to 16-row blocks).
    if c <= 512:
        bc = c
    else:  # largest dividing block <= 512, lane-aligned (c % 128 == 0
        # holds for all model channel counts; 768 -> bc=384, 2048 -> 512)
        bc = next((b for b in (512, 384, 256, 128) if c % b == 0), None)
        if bc is None:
            raise ValueError(
                f"conv1x1_bn_bwd_fused: C={c} > 512 must be divisible by "
                f"a 128-multiple block (got C % 128 == {c % 128})")
    bm = _pick_block_m(mp, bc, cin, c)
    row = lambda v: v.reshape(1, c).astype(jnp.float32)  # noqa: E731
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
    )(dz, y, x_in, w, row(g), row(mean), row(inv), row(a_vec), row(b_vec))
    return (dx[:m] if m_pad else dx), dw


# --------------------------------------------------------------------------
# custom_vjp wrapper: the model-facing fused op
# --------------------------------------------------------------------------

def _bn_sums(dz, y, mean, inv):
    """Pass A (XLA): dbeta = sum(dz), dgamma = sum(dz * xhat) — one fused
    read of dz+y, already at the streaming roofline."""
    dzf = dz.astype(jnp.float32)
    xhat = (y.astype(jnp.float32) - mean) * inv
    return jnp.sum(dzf, axis=0), jnp.sum(dzf * xhat, axis=0)


def _axis_size(axis_name) -> int:
    return 1 if axis_name is None else jax.lax.axis_size(axis_name)


def _pmean(v, axis_name):
    return v if axis_name is None else jax.lax.pmean(v, axis_name)


def _fwd_math(x, w, scale, bias, eps, axis_name):
    y = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y.astype(x.dtype)
    # With axis_name: cross-replica (sync) batch stats, the fused analog
    # of models/resnet.batch_norm's pmean'd stats.
    mean = _pmean(jnp.mean(y, axis=0, dtype=jnp.float32), axis_name)
    meansq = _pmean(jnp.mean(jnp.square(y.astype(jnp.float32)), axis=0),
                    axis_name)
    var = meansq - jnp.square(mean)
    inv = jax.lax.rsqrt(var + eps)
    z = ((y.astype(jnp.float32) - mean) * inv).astype(x.dtype) * scale + bias
    return z, (y, mean, var, inv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def conv1x1_bn(x, w, scale, bias, eps=1e-5, axis_name=None):
    """z = batch_norm(x @ w) over flattened rows, train mode — forward in
    plain XLA, backward through the fused Pallas kernel. With
    `axis_name`, batch stats are synced across that mesh axis (sync-BN
    semantics matching models/resnet.batch_norm). Returns
    (z, (batch_mean, batch_var)); the aux stats feed running-stat updates
    exactly like models/resnet.batch_norm does. Param/input grads are the
    per-rank partials — the framework's gradient psum completes them,
    same as the unfused autodiff path."""
    z, (y, mean, var, inv) = _fwd_math(x, w, scale, bias, eps, axis_name)
    return z, (mean, var)


def _conv1x1_bn_fwd(x, w, scale, bias, eps, axis_name):
    z, (y, mean, var, inv) = _fwd_math(x, w, scale, bias, eps, axis_name)
    return (z, (mean, var)), (x, w, scale, y, mean, inv)


def _conv1x1_bn_bwd(eps, axis_name, res, cts):
    x, w, scale, y, mean, inv = res
    dz, (dmean, dvar) = cts
    dbeta, dgamma = _bn_sums(dz, y, mean, inv)
    # Sync-BN backward needs the GLOBAL reductions and row count in the
    # dy formula; the RETURNED dscale/dbias stay per-rank partials (the
    # framework's later gradient psum makes them global, exactly like
    # unfused autodiff). dmean/dvar cotangents (zero in normal training —
    # optax treats batch stats as state — but exact when a loss does use
    # the aux stats) fold into the kernel's per-channel vectors for free.
    k = _axis_size(axis_name)
    db_g = dbeta if axis_name is None else jax.lax.psum(dbeta, axis_name)
    dg_g = dgamma if axis_name is None else jax.lax.psum(dgamma, axis_name)
    dm_g = dmean if axis_name is None else jax.lax.psum(dmean, axis_name)
    dv_g = dvar if axis_name is None else jax.lax.psum(dvar, axis_name)
    dx, dw = conv1x1_bn_bwd_fused(
        dz, y, x, w, scale.astype(jnp.float32).ravel(), mean, inv,
        db_g, dg_g, dmean=dm_g, dvar=dv_g, count=dz.shape[0] * k)
    return (dx, dw.astype(w.dtype), dgamma.astype(scale.dtype),
            dbeta.astype(scale.dtype))


conv1x1_bn.defvjp(_conv1x1_bn_fwd, _conv1x1_bn_bwd)


def conv1x1_bn_nhwc(x, w, scale, bias, eps=1e-5, axis_name=None):
    """NHWC convenience wrapper: x (N, H, W, Cin), w (1, 1, Cin, Cout) or
    (Cin, Cout). Returns (z in NHWC, (mean, var))."""
    n, h, wd, cin = x.shape
    w2 = w.reshape(w.shape[-2], w.shape[-1])
    z, stats = conv1x1_bn(x.reshape(n * h * wd, cin), w2, scale, bias,
                          eps, axis_name)
    return z.reshape(n, h, wd, -1), stats
