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
kernels). A causal kernel multiplies only what the causal half holds:
blocks under the diagonal run unmasked (in the forward kernel in strips of
256 rows, so that a strip's product need not wait for the row maxima of the
strip before it: `_whole_strips`), and a block on it is walked in row
strips that stop at the diagonal's tile (`_walk_strips`; the forward
kernel writes the next such strip's product ahead of a strip's softmax;
docs/kernels.md has the timings that chose the forms). With a `window` a
query sees the `window` keys that end with its own: a block the band's
lower edge crosses is walked in the same strips, each cut to the tiles the
band holds of it (`_windowed_strips`). Blocks above the diagonal or wholly
under the band are no grid steps at all: a kernel's grid is (heads, the
block pairs the mask holds), a query block's key blocks one after the other
(the backward: a key block's query blocks), and a step finds its pair from
static tables (`_Walk`). Keys and values may have fewer heads than the
queries (`group` query heads read one K/V head): the K/V blocks are found by
the index map `head // group`, and the backward walks a group's query heads
in turn and sums their dk and dv.
The backward pass is ONE kernel (`_bwd_fused_kernel`, PR 55): a walk by key
blocks that makes a block pair's probabilities and score gradient once and
adds into dv, dk and the query block's rows of a float32 dq accumulator that
stays in VMEM for a query head's whole walk: five score-sized products a
block pair and one pass of `exp`, where a dq and a dk/dv kernel made seven
and two. Those two stay for shapes whose accumulators over the sequence pass
the VMEM budget (`_fused_params`, from the static shapes alone; no cell of
the benchmark: `backward_products`).
Numerics are validated against
`parallel.ring_attention.blockwise_attention_reference` (forward AND
gradients) in tests/test_flash_attention.py.

Off the TPU the kernels run in Pallas interpret mode, so the same code
path is testable on the CPU mesh (tests/conftest.py); the decision is
`ops/_pallas.interpret`.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops._pallas import pallas_call

_NEG_INF = -1e30
_LANES = 128  # lane-replication width for row statistics


# --------------------------------------------------------------------------
# Which part of a block causal attention holds
# --------------------------------------------------------------------------

def _causal_tile(block_q: int, block_k: int) -> Optional[int]:
    """Edge of the tiles a block on the diagonal is walked in: the larger of
    256 and 128 that divides it. None where it runs as one masked product:
    blocks that are not square (the diagonal then enters them anywhere) and
    blocks neither divides (S <= 1024 is one block of any size)."""
    if block_q != block_k:
        return None
    for t in (256, 128):
        if t < block_q and block_q % t == 0:
            return t
    return None


def _crossed_strips(block_q: int, block_k: int):
    """The part of a block the diagonal crosses that causal attention holds,
    as static (row0, rows, cols) strips: rows [row0, row0 + rows) against
    the block's first `cols` columns. Per row of t x t tiles one strip that
    ends with the tile on the diagonal, so the tiles above it are in no
    product: 10 of a 1024² block's 16 at t = 256. No tile: the whole block."""
    t = _causal_tile(block_q, block_k)
    if t is None:
        return [(0, block_q, block_k)]
    return [(i * t, t, (i + 1) * t) for i in range(block_q // t)]


def window_back(block: int, window: int) -> int:
    """How many blocks before the diagonal's the band of `window` keys
    reaches: a query's first key lies at most this many blocks back."""
    return (window + block - 2) // block


def _windowed_strips(block_q: int, block_k: int, window: int, d: int,
                     t: Optional[int] = None):
    """What causal attention over the last `window` keys holds of a square
    block `d` blocks before the diagonal's (d = 0: on it), as static (row0,
    rows, col0, cols, masked) strips: per row of t x t tiles the tiles from
    the one the band's lower edge crosses to the one its upper edge (the
    diagonal) crosses; `masked` unless every entry of the strip is in the
    band. Key c of the block is seen by its query r iff
    d b - window < c - r <= d b."""
    b = block_q
    t = t or _causal_tile(block_q, block_k) or b
    strips = []
    for row0 in range(0, b, t):
        c_hi = min(row0 + t - 1 + d * b, b - 1)
        c_lo = max(row0 + d * b - window + 1, 0)
        if c_lo > c_hi:
            continue
        col0, end = c_lo // t * t, (c_hi // t + 1) * t
        whole = end - 1 - row0 <= d * b and \
            col0 - (row0 + t - 1) > d * b - window
        strips.append((row0, t, col0, end - col0, not whole))
    return strips


#: Rows of a strip of a block no mask enters, in the forward kernel: of 512,
#: 256 and 128 the fastest at every shape timed (docs/kernels.md, PR 49).
_WHOLE_STRIP_ROWS = 256


def _whole_strips(block_q: int, block_k: int,
                  rows: int = _WHOLE_STRIP_ROWS):
    """A block no mask enters (wholly under the diagonal, or not causal) as
    the forward kernel walks it: static (row0, rows, cols) strips of `rows`
    rows against all its columns. No row's maximum is known before its whole
    product is out of the MXU and no `exp` starts before that; in strips,
    one strip's product runs beside the `exp` of the one before. A block
    `rows` does not divide is one strip."""
    if block_q % rows:
        rows = block_q
    return [(row0, rows, block_k) for row0 in range(0, block_q, rows)]


def _walk_strips(run, *, causal, iq, ik, block_q, block_k, window=None,
                 whole=None):
    """Hand `run` what attention holds of block (iq, ik), one of those
    `held_blocks` lists, as a static list of (row0, rows, cols, masked, col0)
    strips, under the condition that the block is of that kind: all of it,
    unmasked, when not causal or wholly under the diagonal (as one strip, or
    as the strips `whole` lists: the forward kernel's `_whole_strips`);
    `_crossed_strips`, masked, when the diagonal crosses it. With a `window`
    (causal, square blocks): `_windowed_strips` of a block `iq - ik` before
    the diagonal's."""
    if window is not None:
        for d in range(window_back(block_q, window) + 1):
            pl.when(iq - ik == d)(lambda d=d: run(
                [(row0, rows, cols, masked, col0)
                 for row0, rows, col0, cols, masked in _windowed_strips(
                     block_q, block_k, window, d)]))
        return

    def _whole():
        run([(row0, rows, cols, False, 0)
             for row0, rows, cols in whole or [(0, block_q, block_k)]])

    if not causal:
        _whole()
        return
    under = ik * block_k + block_k - 1 <= iq * block_q
    pl.when(under)(_whole)
    pl.when(jnp.logical_not(under))(lambda: run(
        [(row0, rows, cols, True, 0)
         for row0, rows, cols in _crossed_strips(block_q, block_k)]))


def _for_each_strip(step, **block):
    """`_walk_strips`, one strip after the other: run
    `step(row0, rows, cols, masked, col0)` over what attention holds of the
    block."""
    def run(strips):
        for strip in strips:
            step(*strip)

    _walk_strips(run, **block)


def held_blocks(nq: int, nk: int, block_q: int, block_k: int, causal: bool,
                window: Optional[int] = None):
    """Per query block the (first, last) key block attention holds any of,
    by the predicate `_for_each_strip` computes on: every block when not
    causal; none wholly above the diagonal; with a `window` none more than
    `window_back` blocks before the diagonal's."""
    if not causal:
        return [(0, nk - 1)] * nq
    back = nq if window is None else window_back(block_q, window)
    return [(max(i - back, 0), ((i + 1) * block_q - 1) // block_k)
            for i in range(nq)]


def _by_key_block(rows, nk: int):
    """`held_blocks` the other way: per key block the (first, last) query
    block that holds any of it."""
    return [(min(i for i, (lo, hi) in enumerate(rows) if lo <= j <= hi),
             max(i for i, (lo, hi) in enumerate(rows) if lo <= j <= hi))
            for j in range(nk)]


class _Walk:
    """A grid axis of only the block pairs the mask holds: run r is the
    pairs (r, first[r]) ... (r, last[r]), a grid step each, gone through
    `heads` times in turn, run after run (forward and dq: a query block's
    key blocks; dk/dv: a key block's query blocks, once for each query head
    of the K/V head's group), or `head_major`, all the runs once a head (the
    fused backward: a query head's whole walk by key blocks, then the
    group's next head's). Index maps and kernel bodies find the pair of
    step s in static tables, a scalar compare and select a pass (passes of
    one length: a division), so the grid needs no scalar-prefetch operand
    and no step is idle."""

    def __init__(self, runs, heads: int = 1, head_major: bool = False):
        self.heads = heads
        self.head_major = head_major and heads > 1
        self.first, self.last = zip(*(
            run for run in runs
            for _ in range(1 if self.head_major else heads)))
        lengths = [hi - lo + 1 for lo, hi in zip(self.first, self.last)]
        self.starts = [sum(lengths[:n]) for n in range(len(lengths))]
        self.period = sum(lengths)      # the steps the tables hold
        self.steps = self.period * (heads if self.head_major else 1)
        self.length = lengths[0] if len(set(lengths)) == 1 else None

    def _of_pass(self, s, values):
        """values[the pass step s is of]."""
        if len(set(values)) == 1:
            return values[0]
        out = np.int32(values[0])
        for start, value in zip(self.starts[1:], values[1:]):
            out = jax.lax.select(jax.lax.ge(s, np.int32(start)),
                                 np.int32(value), out)
        return out

    def _split(self, s):
        """(Which head's going-through of the tables step s is of, s within
        it): a head-major walk goes through them once a head."""
        if self.head_major:
            return s // self.period, s % self.period
        return 0, s

    def run(self, s):
        """(The run of step s, which of the `heads` passes over it)."""
        head, s = self._split(s)
        n = s // self.length if self.length is not None else \
            self._of_pass(s, range(len(self.starts)))
        if self.heads > 1 and not self.head_major:
            return n // self.heads, n % self.heads
        return n, head

    def at(self, s):
        """Where in its run step s stands: first[r] <= at <= last[r]."""
        s = self._split(s)[1]
        if self.length is not None:
            return self._of_pass(s, self.first) + s % self.length
        return s - self._of_pass(
            s, [start - lo for start, lo in zip(self.starts, self.first)])

    def where(self, s):
        """Step s for a kernel body: (its run, where in the run it stands,
        whether it is the first of the run's steps, and the last: where an
        accumulator over the run is zeroed and written out)."""
        (run, head), at = self.run(s), self.at(s)
        t = self._split(s)[1]
        first = at == self._of_pass(t, self.first)
        last = at == self._of_pass(t, self.last)
        if self.heads > 1:
            first = jnp.logical_and(first, head == 0)
            last = jnp.logical_and(last, head == self.heads - 1)
        return run, at, first, last

    def head_ends(self, s):
        """Whether step s is the first of a head's going-through of all the
        runs, and the last: where an accumulator over a query head's whole
        walk is zeroed and written out."""
        t = self._split(s)[1]
        return t == 0, t == self.period - 1


def _block_maps(walk: _Walk, group: int, by_key_block: bool = False):
    """Index maps of a query-shaped and a key-shaped operand over the grid
    (heads, walk.steps). A walk by query blocks has a query head's steps on
    the grid's first axis, and its K/V are its group's; a walk by key blocks
    (`walk.heads` the group) has a K/V head's, each run gone through by the
    group's query heads in turn."""
    if by_key_block:
        return (lambda b, s: (b * group + walk.run(s)[1], walk.at(s), 0),
                lambda b, s: (b, walk.run(s)[0], 0))
    return (lambda b, s: (b, walk.run(s)[0], 0),
            lambda b, s: (b // group if group > 1 else b, walk.at(s), 0))


def _scores(q_ref, k_ref, row0, rows, cols, masked, col0=0, *,
            scale, iq, ik, block_q, block_k, window=None):
    """s = q·kᵀ·scale of one strip (the block's columns from `col0`), its
    entries above the diagonal, and with a `window` those under the band, at
    `_NEG_INF` where the strip is `masked`."""
    q = q_ref[0, row0:row0 + rows, :].astype(jnp.float32)
    k = k_ref[0, col0:col0 + cols, :].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if masked:
        row = iq * block_q + row0 + jax.lax.broadcasted_iota(
            jnp.int32, (rows, cols), 0)
        first = ik * block_k + col0 if col0 else ik * block_k
        col = first + jax.lax.broadcasted_iota(
            jnp.int32, (rows, cols), 1)
        seen = col <= row
        if window is not None:
            seen = jnp.logical_and(seen, col > row - window)
        s = jnp.where(seen, s, _NEG_INF)
    return s


def _compiler_params(dh: int, dhv: int, resident: int = 0) -> dict:
    """`pallas_call` arguments that give a kernel the VMEM its blocks need.
    Up to 128 lanes a head the 1024² blocks fit the compiler's default
    scope (16 MiB). A wider head takes two lane tiles a row in every q/k
    block and accumulator: at (192 | 128) the dk/dv kernel needs 17.2 MiB
    inside a train step (the TPU compiler's own count, PR 30), so such
    kernels are given twice the default; v5e has 128 MiB. `resident`: the
    bytes a kernel keeps beside its working blocks (the fused backward's
    accumulators and output blocks over the sequence,
    `fused_backward_bytes`), asked for on top."""
    wide = max(dh, dhv) > _LANES
    if not wide and not resident:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=(32 if wide else 16) * 2 ** 20 + resident)}


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

def _lanes(x, n):
    """A lane-replicated (rows, 128) statistic at n lanes. Whole vregs are
    repeated, which moves nothing across lanes; other widths broadcast."""
    if n % _LANES == 0:
        return pltpu.repeat(x, n // _LANES, axis=1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, block_q, block_k,
                walk, window=None):
    iq, ik, first, last = walk.where(pl.program_id(1))
    block = dict(iq=iq, ik=ik, block_q=block_q, block_k=block_k,
                 window=window)

    @pl.when(first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    scores = functools.partial(_scores, q_ref, k_ref, scale=scale, **block)

    def update(s, row0, rows, cols, masked, col0):
        """Online-softmax update of the strip's rows by its scores s. m and
        l stay lane-replicated as values too: sliced to a column and
        broadcast again, each update cost more than the tiles a strip
        skips."""
        r = slice(row0, row0 + rows)
        v = v_ref[0, col0:col0 + cols, :].astype(jnp.float32)
        m_prev = m_ref[r, :]                           # (rows, 128)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, cols))           # (rows, cols)
        alpha = jnp.exp(m_prev - m_new)                # (rows, 128)
        l_ref[r, :] = alpha * l_ref[r, :] + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[r, :] = m_new
        acc_ref[r, :] = (
            acc_ref[r, :] * _lanes(alpha, acc_ref.shape[1])
            + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32))

    def run(strips):
        """A strip's softmax waits for its whole q·kᵀ (no row's maximum is
        known before), so the MXU and the vector unit take turns unless the
        next strip's product is there to run beside it. Strips of one shape
        (a block no mask enters) the scheduler overlaps by itself; those of
        a block the diagonal or the band's edge crosses, each of another
        width, it does not, so there strip i + 1's product is written ahead
        of strip i's softmax (docs/kernels.md, PR 49: ahead everywhere lost
        at S = 16,384)."""
        if not any(masked for *_, masked, _ in strips):
            for strip in strips:
                update(scores(*strip), *strip)
            return
        s = scores(*strips[0])
        for strip, then in zip(strips, strips[1:] + [None]):
            s_then = scores(*then) if then else None
            update(s, *strip)
            s = s_then

    _walk_strips(run, causal=causal, **block,
                 whole=_whole_strips(block_q, block_k))

    @pl.when(last)
    def _finish():
        l = l_ref[:, :1]
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, :1] + jnp.log(safe_l)   # (bq, 1)


def _fwd(q, k, v, causal, scale, block_q, block_k, window=None):
    bh, sq, dh = q.shape            # dh: the queries' and keys' width
    sk, dhv = v.shape[1:]           # dhv: the values' (o follows v)
    walk = _Walk(held_blocks(sq // block_q, sk // block_k, block_q, block_k,
                             causal, window))
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, walk=walk,
                               window=window)
    by_q, by_k = _block_maps(walk, bh // k.shape[0])
    o, lse = pallas_call(
        kernel,
        grid=(bh, walk.steps),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), by_q),
            pl.BlockSpec((1, block_k, dh), by_k),
            pl.BlockSpec((1, block_k, dhv), by_k),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dhv), by_q),
            # lse rides a (bh, S, 1) array: the (block_q, 1) block is legal
            # tiling (minor dim equals the array dim) and 128x smaller than
            # lane-replicating a VJP residual that lives fwd->bwd.
            pl.BlockSpec((1, block_q, 1), by_q),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, dhv), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dhv), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        **_compiler_params(dh, dhv),
    )(q, k, v)
    return o, lse


# --------------------------------------------------------------------------
# Backward
# --------------------------------------------------------------------------

def _bwd_strip(refs, row0, rows, cols, masked, col0=0, *, scale, **block):
    """(p, ds) of one strip: the probabilities recomputed from the saved
    logsumexp, p = exp(s·scale − lse), and the score gradient
    ds = p ∘ (do·vᵀ − delta [+ dlse]) · scale, with
    delta = rowsum(do ∘ o) the softmax-jacobian correction term.
    (dlse: ∂lse/∂s = p — the lse output is differentiable so block
    results can be merged OUTSIDE the kernel, e.g. per ring hop.)"""
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dlse_ref = refs
    r = slice(row0, row0 + rows)
    s = _scores(q_ref, k_ref, row0, rows, cols, masked, col0, scale=scale,
                **block)
    p = jnp.exp(s - lse_ref[0, r, :])                  # (rows, cols)
    do = do_ref[0, r, :].astype(jnp.float32)           # (rows, dh)
    v = v_ref[0, col0:col0 + cols, :].astype(jnp.float32)
    delta = jnp.sum(do * o_ref[0, r, :].astype(jnp.float32),
                    axis=-1, keepdims=True)            # (rows, 1)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # (rows, cols)
    bracket = dp - delta
    if dlse_ref is not None:
        bracket = bracket + dlse_ref[0, r, :]
    return p, p * bracket * scale


def _bwd_dkdv_kernel(*refs, scale, causal, block_q, block_k, has_dlse,
                     walk, window=None):
    # q, k, v, o, do, lse, dlse (or None) as `_bwd_strip` takes them
    ins = refs[:6] + (refs[6] if has_dlse else None,)
    dk_ref, dv_ref, dk_acc, dv_acc = refs[6 + has_dlse:]
    q_ref, do_ref = ins[0], ins[4]
    # a run is a key block: the grid walks the query blocks that hold any of
    # it once for each query head of the group: one sum for the K/V head
    ik, iq, first, last = walk.where(pl.program_id(1))
    block = dict(iq=iq, ik=ik, block_q=block_q, block_k=block_k,
                 window=window)

    @pl.when(first)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def step(row0, rows, cols, masked, col0=0):
        p, ds = _bwd_strip(ins, row0, rows, cols, masked, col0,
                           scale=scale, **block)
        r = slice(row0, row0 + rows)
        c = slice(col0, col0 + cols)
        # dv += pᵀ · do ;  dk += dsᵀ · q   (the strip's columns only)
        dv_acc[c, :] = dv_acc[c, :] + jax.lax.dot_general(
            p, do_ref[0, r, :].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[c, :] = dk_acc[c, :] + jax.lax.dot_general(
            ds, q_ref[0, r, :].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _for_each_strip(step, causal=causal, **block)

    @pl.when(last)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(*refs, scale, causal, block_q, block_k, has_dlse,
                   walk, window=None):
    ins = refs[:6] + (refs[6] if has_dlse else None,)
    dq_ref, dq_acc = refs[6 + has_dlse:]
    k_ref = ins[1]
    iq, ik, first, last = walk.where(pl.program_id(1))
    block = dict(iq=iq, ik=ik, block_q=block_q, block_k=block_k,
                 window=window)

    @pl.when(first)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def step(row0, rows, cols, masked, col0=0):
        _, ds = _bwd_strip(ins, row0, rows, cols, masked, col0,
                           scale=scale, **block)
        r = slice(row0, row0 + rows)
        # dq += ds · k
        dq_acc[r, :] = dq_acc[r, :] + jax.lax.dot_general(
            ds, k_ref[0, col0:col0 + cols, :].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _for_each_strip(step, causal=causal, **block)

    @pl.when(last)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_fused_kernel(*refs, scale, causal, block_q, block_k, has_dlse,
                      walk, window=None):
    """dq, dk and dv in one walk by key blocks: a step makes `_bwd_strip`'s
    (p, ds) once and adds pᵀ·do into dv, dsᵀ·q into dk and ds·k into the
    query block's rows of dq's accumulator, which stays in VMEM for a query
    head's whole walk. dk's and dv's accumulators and output blocks hold the
    run's key block, or where a group's query heads walk the keys one after
    the other (`walk.heads` > 1), the keys' whole sequence."""
    ins = refs[:6] + (refs[6] if has_dlse else None,)
    dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = refs[6 + has_dlse:]
    q_ref, k_ref, do_ref = ins[0], ins[1], ins[4]
    step_id = pl.program_id(1)
    ik, iq, first, last = walk.where(step_id)
    head_first, head_last = walk.head_ends(step_id)
    block = dict(iq=iq, ik=ik, block_q=block_q, block_k=block_k,
                 window=window)

    def rows_of(acc, index, block, row0, rows):
        """Rows [row0, row0 + rows) of block `index` of an accumulator over
        a sequence (of its one block: static)."""
        if acc.shape[0] == block:
            return pl.ds(row0, rows)
        return pl.ds(pl.multiple_of(index * block + row0,
                                    math.gcd(block, row0)), rows)

    # the key block's rows of dk's and dv's accumulators and output blocks
    held = rows_of(dk_acc, ik, block_k, 0, block_k)

    @pl.when(head_first)
    def _init_dq():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(first)
    def _init_dkdv():
        dk_acc[held, :] = jnp.zeros((block_k, dk_acc.shape[1]), jnp.float32)
        dv_acc[held, :] = jnp.zeros((block_k, dv_acc.shape[1]), jnp.float32)

    def step(row0, rows, cols, masked, col0=0):
        p, ds = _bwd_strip(ins, row0, rows, cols, masked, col0,
                           scale=scale, **block)
        r = slice(row0, row0 + rows)
        c = rows_of(dk_acc, ik, block_k, col0, cols)
        rq = rows_of(dq_acc, iq, block_q, row0, rows)
        # dv += pᵀ · do ;  dk += dsᵀ · q   (the strip's columns only)
        dv_acc[c, :] = dv_acc[c, :] + jax.lax.dot_general(
            p, do_ref[0, r, :].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[c, :] = dk_acc[c, :] + jax.lax.dot_general(
            ds, q_ref[0, r, :].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dq += ds · k   (the strip's rows of the head's sequence)
        dq_acc[rq, :] = dq_acc[rq, :] + jax.lax.dot_general(
            ds, k_ref[0, col0:col0 + cols, :].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _for_each_strip(step, causal=causal, **block)

    @pl.when(last)
    def _finish_dkdv():
        dk_ref[0, held, :] = dk_acc[held, :].astype(dk_ref.dtype)
        dv_ref[0, held, :] = dv_acc[held, :].astype(dv_ref.dtype)

    @pl.when(head_last)
    def _finish_dq():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


#: The VMEM a fused backward kernel may ask for, of v5e's 128 MiB.
_FUSED_VMEM_LIMIT = 100 * 2 ** 20


def _vmem_bytes(rows: int, width: int, itemsize: int) -> int:
    """Bytes of a (rows, width) block in VMEM: a row takes whole lane
    tiles."""
    return rows * -(-width // _LANES) * _LANES * itemsize


def fused_backward_bytes(sq: int, sk: int, dh: int, dhv: int, group: int,
                         block_k: int, itemsize: int = 2) -> int:
    """VMEM the fused backward kernel keeps beside its working blocks, from
    the static shapes: dq's float32 accumulator over a query head's sequence
    and its output block (two buffers); dk's and dv's likewise, over the
    keys' sequence where a K/V head's `group` query heads walk it one after
    the other, over one key block where group == 1."""
    rows_k = sk if group > 1 else block_k
    each = 4 + 2 * itemsize
    return sum(_vmem_bytes(rows, width, each) for rows, width in (
        (sq, dh), (rows_k, dh), (rows_k, dhv)))


def _fused_params(sq, sk, dh, dhv, group, block_k, itemsize):
    """`_compiler_params` of the fused backward kernel at these shapes; None
    where what it keeps resident passes `_FUSED_VMEM_LIMIT` (sequences far
    past the cells' 16,384) and the dq and dk/dv kernels stay."""
    params = _compiler_params(dh, dhv, fused_backward_bytes(
        sq, sk, dh, dhv, group, block_k, itemsize))
    limit = params["compiler_params"].vmem_limit_bytes
    return params if limit <= _FUSED_VMEM_LIMIT else None


def _bwd(q, k, v, o, lse, do, dlse, causal, scale, block_q, block_k,
         window=None):
    """dlse=None compiles lse-cotangent-free kernels (the plain
    flash_attention path never pays for a zero dlse buffer). One kernel
    (`_bwd_fused_kernel`: five products a block pair) where dq's accumulator
    over the sequence fits VMEM, which the static shapes decide
    (`_fused_params`); else the dq and the dk/dv kernel (seven)."""
    bh, sq, dh = q.shape            # dq and dk follow q's width,
    sk, dhv = v.shape[1:]           # o, do and dv follow v's
    has_dlse = dlse is not None
    group = bh // k.shape[0]
    rows = held_blocks(sq // block_q, sk // block_k, block_q, block_k,
                       causal, window)
    by_key = _by_key_block(rows, sk // block_k)
    static = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, has_dlse=has_dlse, window=window)

    def specs(walk, by_key_block=False):
        """(by_q, by_k, the in_specs of `operands`) over `walk`'s grid."""
        by_q, by_k = _block_maps(walk, group, by_key_block)
        lse_spec = pl.BlockSpec((1, block_q, 1), by_q)
        return by_q, by_k, [pl.BlockSpec((1, block_q, dh), by_q),
                            pl.BlockSpec((1, block_k, dh), by_k),
                            pl.BlockSpec((1, block_k, dhv), by_k),
                            pl.BlockSpec((1, block_q, dhv), by_q),
                            pl.BlockSpec((1, block_q, dhv), by_q),
                            lse_spec] + [lse_spec] * has_dlse

    operands = [q, k, v, o, do, lse] + [dlse] * has_dlse
    out_shape = [jax.ShapeDtypeStruct((bh, sq, dh), q.dtype),
                 jax.ShapeDtypeStruct((bh // group, sk, dh), q.dtype),
                 jax.ShapeDtypeStruct((bh // group, sk, dhv), q.dtype)]

    fused = _fused_params(sq, sk, dh, dhv, group, block_k, q.dtype.itemsize)
    if fused is not None:
        walk = _Walk(by_key, heads=group, head_major=True)
        _, by_k, in_specs = specs(walk, by_key_block=True)
        # dk and dv: a group's sum over the keys' sequence, or a key block
        rows_k, of_keys = (sk, lambda b, s: (b, 0, 0)) if group > 1 else (
            block_k, by_k)
        return tuple(pallas_call(
            functools.partial(_bwd_fused_kernel, walk=walk, **static),
            grid=(bh // group, walk.steps),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, sq, dh), lambda b, s: (
                    b * group + walk.run(s)[1], 0, 0)),
                pl.BlockSpec((1, rows_k, dh), of_keys),
                pl.BlockSpec((1, rows_k, dhv), of_keys)],
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((sq, dh), jnp.float32),
                            pltpu.VMEM((rows_k, dh), jnp.float32),
                            pltpu.VMEM((rows_k, dhv), jnp.float32)],
            # dq takes do's buffer where their shapes agree: a head's do
            # blocks are all read before its dq block is written (at the
            # head's last step), and the three results of one kernel are
            # alive together, where dq could be consumed before dk and dv
            # were made: with it no cell's step needs more HBM than it did
            input_output_aliases={4: 0} if do.shape == q.shape
            and do.dtype == q.dtype else {},
            **fused,
        )(*operands))

    walk = _Walk(by_key, heads=group)
    _, by_k, in_specs = specs(walk, by_key_block=True)
    dk, dv = pallas_call(
        functools.partial(_bwd_dkdv_kernel, walk=walk, **static),
        grid=(bh // group, walk.steps),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, block_k, dh), by_k),
                   pl.BlockSpec((1, block_k, dhv), by_k)],
        out_shape=out_shape[1:],
        scratch_shapes=[
            pltpu.VMEM((block_k, dh), jnp.float32),
            pltpu.VMEM((block_k, dhv), jnp.float32),
        ],
        **_compiler_params(dh, dhv),
    )(*operands)

    walk = _Walk(rows)
    by_q, _, in_specs = specs(walk)
    dq = pallas_call(
        functools.partial(_bwd_dq_kernel, walk=walk, **static),
        grid=(bh, walk.steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, dh), by_q),
        out_shape=out_shape[0],
        scratch_shapes=[pltpu.VMEM((block_q, dh), jnp.float32)],
        **_compiler_params(dh, dhv),
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, window):
    o, _ = _fwd(q, k, v, causal, scale, block_q, block_k, window)
    return o


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, window):
    o, lse = _fwd(q, k, v, causal, scale, block_q, block_k, window)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, window, res, do):
    q, k, v, o, lse = res
    # dlse=None: the o-only API never pays for a zero lse cotangent.
    return _bwd(q, k, v, o, lse, do, None, causal, scale, block_q, block_k,
                window)


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

    q: (B, H, Sq, dh); k: (B, H, Sk, dh); v: (B, H, Sk, dv) — Sq and Sk may
    differ (ring hops attend local queries against a circulating K/V
    block), and so may the values' width and the keys'. Returns
    (o, lse) with o: (B, H, Sq, dv) normalized within the chunk and
    lse: (B, H, Sq) float32; merge chunks with
    L = logaddexp(L1, L2), o = e^{L1−L}·o1 + e^{L2−L}·o2. Differentiable
    through BOTH outputs.
    """
    B, H, Sq, dh = q.shape
    Sk, dv = v.shape[2:]
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
                          v.reshape(B * H, Sk, dv),
                          causal, float(scale), bq, bk)
    return (o.reshape(B, H, Sq, dv),
            lse[..., 0].reshape(B, H, Sq))  # drop the unit minor dim


def _auto_block(S: int) -> Optional[int]:
    """Largest legal block for a sequence length (measured on v5e: big
    blocks win — 1024² blocks are ~2x naive XLA attention at S=8192;
    128² blocks lose to grid overhead). A causal kernel walks the blocks
    on the diagonal in tiles (`_crossed_strips`), so the big block no
    longer means computing the half of it the mask throws away."""
    if S <= 1024:
        return S  # block == full dim is always a legal TPU tiling
    for b in (1024, 512, 256, 128):
        if S % b == 0:
            return b
    return None


def causal_tile_share(S: int, block: Optional[int] = None,
                      t: Optional[int] = None) -> float:
    """Score entries the causal kernels compute over the S²/2 the causal
    half holds (1.0: none wasted): blocks under the diagonal whole, blocks
    on it in tiles of edge `t` (`t == block`: whole, as before the walk).
    The defaults are the kernels' own choice for S."""
    if block is None:
        block = _auto_block(S)
    if t is None:
        t = _causal_tile(block, block) or block
    n, m = S // block, block // t
    computed = n * (n - 1) // 2 * block * block + n * m * (m + 1) // 2 * t * t
    return computed / (S * S / 2)


def window_tile_share(S: int, window: int, block: Optional[int] = None,
                      t: Optional[int] = None) -> float:
    """Score entries the windowed kernels compute over the entries the band
    holds (a query's last `window` keys, fewer at the sequence's start):
    1.0 would be none wasted. A block's strips are whole tiles of edge `t`,
    and both of the band's edges cross tiles: 1.5 at (8,192, 512) with the
    kernels' own 1024-blocks and 256-tiles. The defaults are the kernels'
    own choice for S."""
    if block is None:
        block = _auto_block(S)
    computed = 0
    for iq in range(S // block):
        for d in range(min(window_back(block, window), iq) + 1):
            computed += sum(
                rows * cols for _, rows, _, cols, _ in _windowed_strips(
                    block, block, window, d, t))
    w = min(window, S)
    return computed / (w * S - w * (w - 1) / 2)


def grid_step_share(S: int, window: Optional[int] = None,
                    block: Optional[int] = None) -> float:
    """Grid steps a head of a causal kernel runs over the steps that compute
    (1.0: none idle, none fetching a block nothing reads; a grid of every
    block pair reads 1.33 / 1.6 / 1.78 at S = 2,048 / 4,096 / 8,192 and 4.27
    at (8,192, 512)). The default is the kernels' own block."""
    block = block or _auto_block(S)
    n = S // block
    back = n if window is None or window >= S else window_back(block, window)
    computing = sum(min(i, back) + 1 for i in range(n))
    return _Walk(held_blocks(n, n, block, block, True, window)).steps \
        / computing


def row_strip_share(S: int, window: Optional[int] = None,
                    block: Optional[int] = None,
                    whole_rows: int = _WHOLE_STRIP_ROWS) -> float:
    """Score entries a head of the causal forward kernel computes in strips
    of at most `_WHOLE_STRIP_ROWS` rows over all it computes (1.0: no `exp`
    waits for the product of a longer strip). `whole_rows` is the strip of
    the blocks under the diagonal: the kernel's own by default; walked whole
    (`whole_rows=block`, as until PR 49) they left 0.56 at S = 2,048, 0.29 at
    4,096 and 0.077 at 16,384 in strips. The default block is the kernels'
    own."""
    block = block or _auto_block(S)
    if window is not None and window >= S:
        window = None
    n = S // block
    entries = {True: 0, False: 0}
    for iq, (lo, _) in enumerate(held_blocks(n, n, block, block, True,
                                             window)):
        for d in range(iq - lo + 1):
            if window is not None:
                strips = [(rows, cols) for _, rows, _, cols, _ in
                          _windowed_strips(block, block, window, d)]
            else:
                strips = [(rows, cols) for _, rows, cols in (
                    _whole_strips(block, block, whole_rows) if d
                    else _crossed_strips(block, block))]
            for rows, cols in strips:
                entries[rows <= _WHOLE_STRIP_ROWS] += rows * cols
    return entries[True] / (entries[True] + entries[False])


def backward_products(S: int, dh: int, dv: Optional[int] = None,
                      group: int = 1, Sk: Optional[int] = None,
                      itemsize: int = 2):
    """(Score-sized matrix products the backward pass makes a block pair,
    the VMEM bytes `fused_backward_bytes` reckons for these shapes): 5 where
    the fused kernel runs (q·kᵀ, do·vᵀ, pᵀ·do, dsᵀ·q, ds·k, and one pass of
    `exp` and the bracket), 7 where those bytes pass `_FUSED_VMEM_LIMIT` and
    the dq and the dk/dv kernel each make q·kᵀ, `exp` and do·vᵀ again. The
    choice is `_bwd`'s own, from the static shapes alone: S queries against
    Sk keys (S by default) of width dh, values dv wide (dh by default),
    `group` query heads a K/V head, in the kernels' own blocks."""
    dv, Sk = dv or dh, Sk or S
    block_k = _auto_block(Sk)
    fused = _fused_params(S, Sk, dh, dv, group, block_k, itemsize)
    return (7 if fused is None else 5,
            fused_backward_bytes(S, Sk, dh, dv, group, block_k, itemsize))


def masked_attention_reference(q, k, v, causal: bool = True,
                               scale: Optional[float] = None,
                               window: Optional[int] = None):
    """Attention with the scores materialised and the mask written out, in
    float32: q: (B, H, S, dh) against k: (B, G, S, dh), v: (B, G, S, dv)
    with H / G query heads reading one K/V head. For the tests of the
    kernels and for lengths they cannot tile."""
    B, H, S, dh = q.shape
    group = H // k.shape[1]
    if scale is None:
        scale = dh ** -0.5
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    kf, vf = (jnp.repeat(x, group, axis=1) for x in (kf, vf))
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    row = jnp.arange(S)[:, None]
    col = jnp.arange(S)[None, :]
    seen = jnp.ones((S, S), bool)
    if causal:
        seen = col <= row
    if window is not None:
        seen = jnp.logical_and(seen, col > row - window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vf).astype(q.dtype)


def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    window: Optional[int] = None) -> jax.Array:
    """Exact attention via the Pallas flash kernel.

    q: (B, H, S, dh); k: (B, G, S, dh); v: (B, G, S, dv), G a divisor of H:
    query head h reads K/V head h // (H / G). Returns (B, H, S, dv): the
    values may be narrower or wider than the queries and keys (latent
    attention: 192-wide keys, 128-wide values), and then o, do and dv have
    the values' width, dq and dk the keys'. `scale` defaults to dh^-1/2.
    With a `window` (causal only) a query sees the `window` keys that end
    with its own. Differentiable (custom VJP with flash backward kernels;
    dk and dv are summed over a group's query heads). Block sizes default
    to a measured heuristic; falls back to the score-materializing
    reference for shapes the kernel cannot tile.
    """
    B, H, S, dh = q.shape
    G, dv = k.shape[1], v.shape[-1]
    if H % G or v.shape[1] != G:
        raise ValueError(f"flash_attention: {H} query heads over {G} key "
                         f"and {v.shape[1]} value heads")
    if window is not None and (not causal or window < 1):
        raise ValueError("flash_attention: a window needs causal=True and "
                         f"at least one key (window={window})")
    if window is not None and window >= S:
        window = None       # the band is the whole causal half
    if scale is None:
        scale = dh ** -0.5
    block_q = min(block_q, S) if block_q else _auto_block(S)
    block_k = min(block_k, S) if block_k else _auto_block(S)
    if window is not None and block_q != block_k:
        raise ValueError("flash_attention: a window is walked in square "
                         f"blocks (block_q={block_q}, block_k={block_k})")
    if (block_q is None or block_k is None
            or S % block_q or S % block_k):
        if window is not None or G != H:
            return masked_attention_reference(q, k, v, causal, scale, window)
        from horovod_tpu.parallel.ring_attention import (
            blockwise_attention_reference)
        return blockwise_attention_reference(q, k, v, causal=causal,
                                             scale=scale)
    qf = q.reshape(B * H, S, dh)
    kf = k.reshape(B * G, S, dh)
    vf = v.reshape(B * G, S, dv)
    o = _flash(qf, kf, vf, causal, float(scale), block_q, block_k,
               None if window is None else int(window))
    return o.reshape(B, H, S, dv)
