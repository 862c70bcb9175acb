"""Flash attention as a Pallas TPU kernel (forward + backward).

The hot op of the long-context story: exact attention with online softmax,
never materializing the (S, S) score matrix — O(S) HBM traffic per row
block instead of O(S²). This is the single-device building block under
`parallel/ring_attention.py` (which shards S over the `sp` axis and rides
ICI); here the block loop runs in VMEM with the MXU doing qkᵀ and pv.

No reference counterpart exists (the reference is a DP framework with no
attention ops); the kernel follows the standard FlashAttention-2
recurrence. Row statistics ride in lane-replicated (block_q, 128) buffers
to satisfy the TPU's (8, 128) tiling (same convention as stock Pallas TPU
kernels). Numerics are validated against
`parallel.ring_attention.blockwise_attention_reference` (forward AND
gradients) in tests/test_flash_attention.py.

Off the TPU the kernels run in Pallas interpret mode, so the same code
path is testable on the CPU mesh (tests/conftest.py); the decision is
`ops/_pallas.interpret`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops._pallas import pallas_call

_NEG_INF = -1e30
_LANES = 128  # lane-replication width for row statistics


def _rep(x):
    """Replicate a (bq, 1) column across the 128-lane minor dim."""
    return jnp.broadcast_to(x, (x.shape[0], _LANES))


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, block_q, block_k):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # Causal: blocks strictly above the diagonal contribute nothing.
    needed = jnp.logical_or(
        jnp.logical_not(causal),
        ik * block_k <= iq * block_q + block_q - 1)

    @pl.when(needed)
    def _step():
        q = q_ref[0].astype(jnp.float32)              # (bq, dh)
        k = k_ref[0].astype(jnp.float32)              # (bk, dh)
        v = v_ref[0].astype(jnp.float32)              # (bk, dh)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        if causal:
            rows = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, _NEG_INF)
        m_prev = m_ref[:, :1]                          # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                         # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                # (bq, 1)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = _rep(m_new)
        l_ref[:] = _rep(l_new)

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, :1] + jnp.log(safe_l)   # (bq, 1)


def _fwd(q, k, v, causal, scale, block_q, block_k):
    bh, sq, dh = q.shape
    sk = k.shape[1]
    nq = sq // block_q
    nk = sk // block_k
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k)
    o, lse = pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
            # lse rides a (bh, S, 1) array: the (block_q, 1) block is legal
            # tiling (minor dim equals the array dim) and 128x smaller than
            # lane-replicating a VJP residual that lives fwd->bwd.
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, dh), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dh), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
    )(q, k, v)
    return o, lse


# --------------------------------------------------------------------------
# Backward
# --------------------------------------------------------------------------

def _attn_block(q_ref, k_ref, lse_ref, *, scale, causal,
                iq, ik, block_q, block_k):
    """Recompute the probability block p = exp(s·scale − lse)."""
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if causal:
        rows = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(cols <= rows, s, _NEG_INF)
    return jnp.exp(s - lse_ref[0][:, :1])


def _delta_block(o_ref, do_ref):
    """delta = rowsum(do ∘ o): the softmax-jacobian correction term."""
    return jnp.sum(do_ref[0].astype(jnp.float32)
                   * o_ref[0].astype(jnp.float32), axis=-1, keepdims=True)


def _bwd_dkdv_kernel(*refs, scale, causal, block_q, block_k, has_dlse):
    if has_dlse:
        (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dlse_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        dlse_ref = None
    ik = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    needed = jnp.logical_or(
        jnp.logical_not(causal),
        iq * block_q + block_q - 1 >= ik * block_k)

    @pl.when(needed)
    def _step():
        p = _attn_block(q_ref, k_ref, lse_ref, scale=scale, causal=causal,
                        iq=iq, ik=ik, block_q=block_q, block_k=block_k)
        do = do_ref[0].astype(jnp.float32)             # (bq, dh)
        v = v_ref[0].astype(jnp.float32)               # (bk, dh)
        delta = _delta_block(o_ref, do_ref)            # (bq, 1)
        # dv += pᵀ · do
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # ds = p ∘ (do·vᵀ − delta [+ dlse]) · scale ;  dk += dsᵀ · q
        # (dlse: ∂lse/∂s = p — the lse output is differentiable so block
        # results can be merged OUTSIDE the kernel, e.g. per ring hop.)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bq, bk)
        bracket = dp - delta
        if dlse_ref is not None:
            bracket = bracket + dlse_ref[0][:, :1]
        ds = p * bracket * scale
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(*refs, scale, causal, block_q, block_k, has_dlse):
    if has_dlse:
        (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dlse_ref,
         dq_ref, dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
         dq_ref, dq_acc) = refs
        dlse_ref = None
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    needed = jnp.logical_or(
        jnp.logical_not(causal),
        ik * block_k <= iq * block_q + block_q - 1)

    @pl.when(needed)
    def _step():
        p = _attn_block(q_ref, k_ref, lse_ref, scale=scale, causal=causal,
                        iq=iq, ik=ik, block_q=block_q, block_k=block_k)
        do = do_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        delta = _delta_block(o_ref, do_ref)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        bracket = dp - delta
        if dlse_ref is not None:
            bracket = bracket + dlse_ref[0][:, :1]
        ds = p * bracket * scale                       # (bq, bk)
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd(q, k, v, o, lse, do, dlse, causal, scale, block_q, block_k):
    """dlse=None compiles lse-cotangent-free kernels (the plain
    flash_attention path never pays for a zero dlse buffer)."""
    bh, sq, dh = q.shape
    sk = k.shape[1]
    nq = sq // block_q
    nk = sk // block_k
    has_dlse = dlse is not None

    q_by_j = pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, j, 0))
    kv_by_i = pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, i, 0))
    lse_by_j = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, j, 0))
    in_specs = [q_by_j, kv_by_i, kv_by_i, q_by_j, q_by_j, lse_by_j]
    operands = [q, k, v, o, do, lse]
    if has_dlse:
        in_specs.append(lse_by_j)
        operands.append(dlse)
    dk, dv = pallas_call(
        functools.partial(_bwd_dkdv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          has_dlse=has_dlse),
        grid=(bh, nk, nq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, dh), q.dtype),
            jax.ShapeDtypeStruct((bh, sk, dh), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, dh), jnp.float32),
            pltpu.VMEM((block_k, dh), jnp.float32),
        ],
    )(*operands)

    q_by_i = pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0))
    kv_by_j = pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0))
    lse_by_i = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    in_specs = [q_by_i, kv_by_j, kv_by_j, q_by_i, q_by_i, lse_by_i]
    operands = [q, k, v, o, do, lse]
    if has_dlse:
        in_specs.append(lse_by_i)
        operands.append(dlse)
    dq = pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          has_dlse=has_dlse),
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dh), jnp.float32)],
    )(*operands)
    return dq, dk, dv


# --------------------------------------------------------------------------
# Public API with custom VJP
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_chunk(q, k, v, causal, scale, block_q, block_k):
    """Differentiable (o, lse) pair — lse cotangents feed the ds term so
    block results can be merged OUTSIDE the kernel (per ring hop)."""
    return _fwd(q, k, v, causal, scale, block_q, block_k)


def _flash_chunk_fwd(q, k, v, causal, scale, block_q, block_k):
    o, lse = _fwd(q, k, v, causal, scale, block_q, block_k)
    return (o, lse), (q, k, v, o, lse)


def _flash_chunk_bwd(causal, scale, block_q, block_k, res, cot):
    q, k, v, o, lse = res
    do, dlse = cot
    return _bwd(q, k, v, o, lse, do, dlse, causal, scale, block_q, block_k)


_flash_chunk.defvjp(_flash_chunk_fwd, _flash_chunk_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, block_q, block_k):
    o, _ = _fwd(q, k, v, causal, scale, block_q, block_k)
    return o


def _flash_fwd(q, k, v, causal, scale, block_q, block_k):
    o, lse = _fwd(q, k, v, causal, scale, block_q, block_k)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, res, do):
    q, k, v, o, lse = res
    # dlse=None: the o-only API never pays for a zero lse cotangent.
    return _bwd(q, k, v, o, lse, do, None, causal, scale, block_q, block_k)


_flash.defvjp(_flash_fwd, _flash_bwd)


def can_tile(Sq: int, Sk: Optional[int] = None,
             causal: bool = False) -> bool:
    """Public tileability predicate: True when the kernel path handles
    these sequence lengths (callers like ring_attention auto-dispatch on
    this instead of re-deriving the kernel's constraints)."""
    if _auto_block(Sq) is None:
        return False
    if Sk is not None and _auto_block(Sk) is None:
        return False
    if causal and Sk is not None and Sq != Sk:
        return False
    return True


def flash_attention_chunk(q, k, v, causal: bool = False,
                          scale: Optional[float] = None,
                          block_q: Optional[int] = None,
                          block_k: Optional[int] = None):
    """One attention chunk with mergeable outputs.

    q: (B, H, Sq, dh); k, v: (B, H, Sk, dh) — Sq and Sk may differ (ring
    hops attend local queries against a circulating K/V block). Returns
    (o, lse) with o: (B, H, Sq, dh) normalized within the chunk and
    lse: (B, H, Sq) float32; merge chunks with
    L = logaddexp(L1, L2), o = e^{L1−L}·o1 + e^{L2−L}·o2. Differentiable
    through BOTH outputs.
    """
    B, H, Sq, dh = q.shape
    Sk = k.shape[2]
    if scale is None:
        scale = dh ** -0.5
    bq = min(block_q, Sq) if block_q else _auto_block(Sq)
    bk = min(block_k, Sk) if block_k else _auto_block(Sk)
    if (bq is None or bk is None or Sq % bq or Sk % bk
            or (causal and Sq != Sk)):
        raise ValueError(
            f"flash_attention_chunk cannot tile Sq={Sq}, Sk={Sk} "
            f"(blocks {bq}, {bk}); causal chunks must be square")
    o, lse = _flash_chunk(q.reshape(B * H, Sq, dh),
                          k.reshape(B * H, Sk, dh),
                          v.reshape(B * H, Sk, dh),
                          causal, float(scale), bq, bk)
    return (o.reshape(B, H, Sq, dh),
            lse[..., 0].reshape(B, H, Sq))  # drop the unit minor dim


def _auto_block(S: int) -> Optional[int]:
    """Largest legal block for a sequence length (measured on v5e: big
    blocks win — 1024² blocks are ~2x naive XLA attention at S=8192;
    128² blocks lose to grid overhead)."""
    if S <= 1024:
        return S  # block == full dim is always a legal TPU tiling
    for b in (1024, 512, 256, 128):
        if S % b == 0:
            return b
    return None


def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None) -> jax.Array:
    """Exact attention via the Pallas flash kernel.

    q, k, v: (B, H, S, dh). Returns (B, H, S, dh). Differentiable
    (custom VJP with flash backward kernels). Block sizes default to a
    measured heuristic; falls back to the score-materializing reference
    for shapes the kernel cannot tile.
    """
    B, H, S, dh = q.shape
    if scale is None:
        scale = dh ** -0.5
    block_q = min(block_q, S) if block_q else _auto_block(S)
    block_k = min(block_k, S) if block_k else _auto_block(S)
    if (block_q is None or block_k is None
            or S % block_q or S % block_k):
        from horovod_tpu.parallel.ring_attention import (
            blockwise_attention_reference)
        return blockwise_attention_reference(q, k, v, causal=causal,
                                             scale=scale)
    qf = q.reshape(B * H, S, dh)
    kf = k.reshape(B * H, S, dh)
    vf = v.reshape(B * H, S, dh)
    o = _flash(qf, kf, vf, causal, float(scale), block_q, block_k)
    return o.reshape(B, H, S, dh)
