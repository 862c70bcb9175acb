"""The gated delta rule over a sequence, in its chunked form, as Pallas TPU
kernels (forward + backward): the one scan over the sequence in this package.
Two forms of one rule: one log decay a head and token (Gated DeltaNet,
arXiv:2412.06464) and one a key channel (Kimi Delta Attention,
arXiv:2510.26692 section 3); `gated_delta_rule` takes either and tells them
by g's shape.

Per head, with keys k_t (dk wide), values v_t (dv wide), queries q_t, a log
decay g_t <= 0 (a number, or dk numbers: then exp(g_t) below is Diag(exp(g_t))
on the state's rows) and a write strength beta_t, a float32 state S (dk x dv)
that starts at zero goes through

    S <- exp(g_t) S;  u_t = beta_t (v_t - S^T k_t);  S <- S + k_t u_t^T;
    o_t = S^T q_t

(`recurrent_gated_delta_rule` below: one token at a time, for the tests).
`gated_delta_rule` computes the same outputs C tokens at a time (the WY form,
section 3 of either paper). With b_i the running sum of g inside a chunk, S_0
the state the chunk starts from and G[X, Y]_ij = sum over the channels c of
X_ic exp(b_ic - b_jc) Y_jc (with one decay a head that is (X Y^T)_ij exp(b_i
- b_j): the decay leaves the contraction),

    A   = beta_i G[K, K]_ij                for j < i, else 0
    T   = (I + A)^-1                        unit lower triangular
    W   = T (beta * exp(b) * K),  U_0 = T (beta * V)
    u   = U_0 - W S_0
    O   = (exp(b) * Q) S_0 + P u            P = G[Q, K]_ij for j <= i, else 0
    S_C = exp(b_C) S_0 + (exp(b_C - b) * K)^T u

Each form has two kernels, each a grid of (blocks of heads, chunks) that walks
a head's chunks in sequence (the grid's last, "arbitrary" axis) with the
state, or its cotangent, resident in VMEM: a scratch cleared at a head's first
chunk. A grid step takes `heads_a_step` heads, whose chains of small dependent
products are independent and interleave; no operand of a chunk goes through
HBM between its products.

* The forward kernels. First what does not depend on the state: G[K, K], G[Q,
  K], the decays, A, T, W and U_0. T is made by forward substitution in
  panels of 16 rows, in float32: a panel's rows start from I - A[panel,
  before] T[before], one product on the MXU at float32 precision (three
  bf16 pieces an operand, six passes), and inside the panel row j is final
  once rows 0..j-1 of the panel are subtracted from it on the vector unit,
  each step one column of A times one row of T. No power of A is formed,
  as the product (I - A)(I + A^2)(I + A^4).. would: beta reaches 2, so
  A's powers grow before they vanish and float32 loses the result. T then
  multiplies K and V exactly: its columns are scaled (float32), and where
  the inputs are bf16 the scaled matrix is split into three bf16 pieces
  that sum to it bit for bit, each a product with the bf16 K or V,
  accumulated in float32. Then the walk: u, O and the next state, four
  products, two of them on the chain from state to state.
* The backward kernels, the reverse walk with the state's cotangent resident,
  and in the same grid step everything else of the chunk's gradient:
  through the scores, the decays and T (the cotangent of A is
  -(T^T dW) W^T - (T^T dU_0) U_0^T below the diagonal: products only, no
  second solve). The scalar one writes dq, dk, dv, dbeta and the cotangent
  of b; g's is the reverse running sum of b's inside a chunk, in `jnp`.
* With a decay a channel b is never in HBM: both kernels take g, (c x dk)
  float32 a chunk and head, and sum it down the rows of the block they hold
  (`_running_sum`: six doubling steps of a sublane roll, a select and an
  add; the same code in both, so both passes hold the same bits), and the
  backward one sums b's cotangent from the chunk's last row the same way
  and writes g's.

No exponent is positive and nothing is divided by a decay, so a strong decay
underflows to zero and does nothing worse. With one decay a head every exponent
is of a difference b_i - b_j with j <= i, or of b itself, masked before `exp`.
With one a channel G cannot be had as (X * exp(b)) (Y * exp(-b))^T: exp(-b)
overflows. But exp(b_i - b_j) = exp(b_i - b_p) exp(b_p - b_j) for any pivot row
p with j < p <= i, both exponents <= 0 (b falls down the rows), and halving the
chunk again and again gives every pair j < i a pivot: level s (c, c/2, ..., 2)
cuts the chunk into groups of s rows and holds the pairs of one group with i in
its later half and j in its earlier half, about the group's middle row; each
pair is in one level, the diagonal (decay 1) is level 1. With e_s = exp(-|b -
b_p|), p the middle row of the row's group, G[X, K] is the sum over the levels
of (X * e_s)(K * e_s)^T where the level holds the pair: one whole-chunk `exp`
and one matrix product a level (G[K, K]'s and G[Q, K]'s left operands stacked),
no lane reduction and no column placed. The backward kernel takes G's
cotangents back level by level the same way: that of (X * e_s) is the level's
dG times (K * e_s), that of (K * e_s) its transpose times (X * e_s), then times
e_s; b's cotangent through G is then elementwise, q * dq + k * (dk as a row's -
dk as a column's). These helpers take one head's matrices and run a head after
the other (`_each_head`): G's work is throughput that no other head's chain
hides, and the four heads' operands side by side do not fit the vector
registers.

Precision: products take their operands in the inputs' type (bf16 in the
benchmark's cells) and accumulate in float32; g, b, beta, every decay, the
matrix A, its inverse T, the carried state and its cotangent are float32; u
and the state are rounded to the inputs' type only as operands of a
product, and so is a decayed X or Y of G's products. float32 inputs multiply
at the MXU's full float32 precision.

For the backward pass a forward kernel writes, besides o, W (inputs' type),
U_0, T and each chunk's entry state (float32: dk x dv a chunk and head), and
with a decay a channel P, which is dear to make again, beside T in T's array
(float32; G[K, K] is not kept: beta's gradient through A is had from what
A's cotangent sends to K's rows); the plain forward (no gradient asked, or
the first pass under remat) writes o alone. The per-channel kernels hold the
state transposed (dv x dk), so that a channel's decay runs along the lanes.
Off the TPU the same kernels run in the Pallas interpreter
(`ops/_pallas.interpret`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops._pallas import pallas_call

CHUNK = 64
#: rows of a float32 vector register: a chunk is whole registers of rows
_SUBLANES = 8
#: what the blocks and the resident state of a grid step may take of VMEM,
#: double-buffered: it decides how many heads a grid step takes
_VMEM_BUDGET = 4 * 2 ** 20
_F32 = jnp.float32
#: rows of a panel of the solve T = (I + A)^-1: what lies before a panel
#: reaches it by one product, what lies inside it by substitution
_PANEL = 16
#: (piece of x, piece of y) of the bf16 products that make a float32 x y:
#: the pairs of three pieces each whose orders sum to 2 or less
_PASSES = [(i, j) for i in range(3) for j in range(3 - i)]


def _chunk_size(chunk: int) -> int:
    if chunk < _SUBLANES or chunk % _SUBLANES:
        raise ValueError(f"gated_delta_rule: chunk {chunk} (a positive "
                         f"multiple of {_SUBLANES})")
    return chunk


def chunks_of(seq: int, chunk: int = CHUNK) -> int:
    """Chunks a sequence of `seq` tokens takes: the last one padded."""
    return -(-seq // _chunk_size(chunk))


def chunked_over_recurrent_macs(key_dim: int, value_dim: int,
                                chunk: int = CHUNK) -> float:
    """Multiply-adds a token a head of the chunked form's matrix products
    (K K^T, Q K^T, W S, (exp(b) Q) S, (Q K^T) u, K^T u, and the solve counted
    as a product with each right-hand side) over the recurrent form's
    3 dk dv: what the chunks cost beside the recurrence they replace."""
    dk, dv, c = key_dim, value_dim, _chunk_size(chunk)
    chunked = 2 * c * dk + c * (dk + dv) / 2 + 3 * dk * dv + c * dv
    return chunked / (3 * dk * dv)


def channel_gram_work(chunk: int = CHUNK, key_dim: int = 128) -> dict:
    """What the decayed products G of the form with a decay a channel cost a
    chunk and head, as (forward, backward): the halving's levels, matrix
    products, float32 registers (8 rows x 128 lanes) that go through `exp`,
    and lane reductions and lane broadcasts of a register, of which a level
    has none."""
    levels = len(_levels(_chunk_size(chunk)))
    registers = (levels - 1) * (chunk // _SUBLANES) * -(-key_dim // 128)
    return {"levels": levels, "products": (levels, 2 * levels),
            "exp_registers": (registers, registers),
            "lane_reductions": (0, 0), "lane_broadcasts": (0, 0)}


def solve_work(chunk: int = CHUNK) -> dict:
    """What the solve T = (I + A)^-1 costs a chunk and head, from the loops
    of `_unit_lower_inverse`: its panels, the exact products of a panel with
    the rows before it and their bf16 passes, the lane broadcasts of a
    register (a column of A times a row, a register the step touches) and
    the substitution's steps."""
    panels = _panels(_chunk_size(chunk))
    products = sum(1 for lo, _ in panels if lo)
    touched = [(rows - first) // _SUBLANES for _, rows in panels
               for _, first in _panel_steps(rows)]
    return {"panels": len(panels), "products": products,
            "bf16_passes": products * len(_PASSES),
            "lane_broadcasts": sum(touched), "steps": len(touched)}


def running_sum_work(chunk: int = CHUNK, key_dim: int = 128) -> dict:
    """What g's running sum inside a chunk costs a chunk and head of the
    form with a decay a channel, as (forward, backward), from
    `_running_sum`'s loop: its doubling steps (each a sublane roll, a
    select and an add of the chunk's float32 registers), the registers that
    go through a roll, and matrix products, of which it has none. The
    backward kernel sums g again and sums b's cotangent from the last row:
    twice the forward's."""
    steps = (_chunk_size(chunk) - 1).bit_length()
    registers = steps * (chunk // _SUBLANES) * -(-key_dim // 128)
    return {"steps": (steps, 2 * steps),
            "rolled_registers": (registers, 2 * registers),
            "products": (0, 0)}


def step_bytes(key_dim: int, value_dim: int, chunk: int = CHUNK,
               itemsize: int = 2, per_channel: bool = False) -> int:
    """VMEM a head takes of the backward kernel's grid step, the larger of
    the two: its blocks (q, k, W, dq, dk; v, dO, dv; U_0, T and the entry
    state in float32; with a decay a channel also g and its cotangent,
    (c x dk) float32 each, and P beside T), each twice for the pipeline,
    and the resident cotangent of the state."""
    dk, dv, c = key_dim, value_dim, _chunk_size(chunk)
    blocks = (5 * c * dk + 3 * c * dv) * itemsize \
        + 4 * (c * dv + c * c + dk * dv)
    if per_channel:
        blocks += 4 * (2 * c * dk + c * c)
    return 2 * blocks + 4 * dk * dv


def heads_a_step(heads: int, key_dim: int, value_dim: int,
                 chunk: int = CHUNK, itemsize: int = 2,
                 per_channel: bool = False) -> int:
    """Heads a grid step takes: as many as `_VMEM_BUDGET` holds of
    `step_bytes`, at least one, at most all; a divisor of `heads` where one
    lies in the upper half of that range (no head is padded), else the most
    (the heads are padded to whole blocks with rows that do nothing)."""
    most = max(1, min(heads, _VMEM_BUDGET // step_bytes(
        key_dim, value_dim, chunk, itemsize, per_channel)))
    for h in range(most, most // 2, -1):
        if heads % h == 0:
            return h
    return most


# --------------------------------------------------------------------------
# What the kernels share
# --------------------------------------------------------------------------

def _mm(a, b, contract):
    """Per head, a x b contracting `contract` = (a's axis, b's axis) of the
    (rows, columns) matrices of a, b: (heads, ., .); float32 accumulation,
    and float32 operands at float32 precision."""
    exact = lax.Precision.HIGHEST if a.dtype == _F32 else None
    dims = (((contract[0],), (contract[1],)), ((), ()))
    return jnp.stack([
        lax.dot_general(a[h], b[h], dims, precision=exact,
                        preferred_element_type=_F32)
        for h in range(a.shape[0])])


def _pieces(x, dt):
    """The float32 x as arrays of type `dt` that sum to it exactly: itself,
    or three bf16 (8 + 8 + 8 bits of its 24)."""
    if dt == _F32:
        return [x]
    pieces = []
    for _ in range(3):
        piece = x.astype(dt)
        pieces.append(piece)
        x = x - piece.astype(_F32)
    return pieces


def _mm_exact(x, y, contract):
    """`_mm` of the float32 x with y of the inputs' type, x not rounded."""
    parts = [_mm(piece, y, contract) for piece in _pieces(x, y.dtype)]
    return functools.reduce(jnp.add, parts)


def _masks(c: int):
    rows = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return rows == cols, rows > cols, rows >= cols


def _as_column(row, eye):
    """(heads, 1, c) -> (heads, c, 1): the diagonal of the row broadcast
    over rows, summed over lanes (exact: one term a row)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=2, keepdims=True)


def _as_row(col, eye):
    """(heads, c, 1) -> (heads, 1, c), the same way."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=1, keepdims=True)


def _decay(exponent, mask):
    """exp of the exponents `mask` holds, 0 elsewhere: masked before `exp`,
    where the exponent may be positive."""
    return jnp.exp(jnp.where(mask, exponent, -jnp.inf))


def _panels(c: int) -> list[tuple[int, int]]:
    """(first row, rows) of the solve's panels of a chunk of c rows: of
    `_PANEL` rows, the last one of what is left."""
    return [(lo, min(_PANEL, c - lo)) for lo in range(0, c, _PANEL)]


def _panel_steps(rows: int):
    """The substitution's steps inside a panel of `rows` rows: (j, the
    first row step j touches: that of row j's register, or of the next one
    where row j is its register's last)."""
    return [(j, (j + 1) // _SUBLANES * _SUBLANES) for j in range(rows - 1)]


def _mm_exact32(x, y):
    """x y per head, both float32, at float32 precision whatever runs the
    kernel: each in three bf16 pieces, the `_PASSES` products of a piece of
    x with a piece of y (what the other three would add is below float32's
    last bit)."""
    xs, ys = (_pieces(z, jnp.bfloat16) for z in (x, y))
    return functools.reduce(jnp.add, (_mm(xs[i], ys[j], (1, 0))
                                      for i, j in _PASSES))


def _unit_lower_inverse(a, eye):
    """(I + a)^-1 for a: (heads, c, c) float32, zero on and above the
    diagonal, by forward substitution in panels of `_PANEL` rows. A panel's
    rows start from the identity's less a[panel, before] times the finished
    rows before it, ONE exact product (the rows not yet made stand in it as
    zeros: the contraction stays c and every slice a register's); then, inside
    the panel, step j takes a's column j times row j from the panel's later
    rows, after which row j + 1 is final. Rows are held in registers of
    `_SUBLANES`: a step touches those from row j's on, or from the next one
    where row j is its register's last."""
    heads, c = a.shape[0], a.shape[-1]
    unit = eye.astype(_F32)
    done = []                        # the rows that are final, in registers
    for lo, rows in _panels(c):
        rest, top = jnp.broadcast_to(unit[lo:lo + rows], (heads, rows, c)), 0
        if lo:
            before = jnp.concatenate(
                done + [jnp.zeros((heads, c - lo, c), _F32)], axis=1)
            rest = rest - _mm_exact32(a[:, lo:lo + rows], before)
        for j, first in _panel_steps(rows):
            row = rest[:, j - top:j - top + 1]
            if first > top:
                done.append(rest[:, :first - top])
                rest, top = rest[:, first - top:], first
            rest = rest - a[:, lo + first:lo + rows, lo + j:lo + j + 1] * row
        done.append(rest)
    return jnp.concatenate(done, axis=1)


def _chunk_terms(b, b_col, mask):
    """Of a chunk, per head, from b as a row and as a column: exp(b) and
    exp(b_C - b) as columns, exp(b_C), and exp(b_i - b_j) where `mask`
    holds."""
    last = b[:, :, -1:]
    return (jnp.exp(b_col), jnp.exp(last - b_col), jnp.exp(last),
            _decay(b_col - b, mask))


def _levels(c: int) -> list[int]:
    """The halving's levels of a chunk of c rows, s = 2^n >= c, ..., 2, 1:
    level s holds the pairs of one group of s rows with i in its later half
    and j in its earlier half, on either side of the group's middle row; a
    pair j < i is in one level, the diagonal in level 1."""
    top = 1 << (c - 1).bit_length()
    return [top >> n for n in range(top.bit_length())]


def _level_mask(shape, s: int):
    """Whether level s holds (row, column) or (column, row), both counted
    modulo c = min(shape): (c, c), or two such stacked or side by side."""
    c = min(shape)
    apart = (lax.broadcasted_iota(jnp.int32, shape, 0) % c) \
        ^ (lax.broadcasted_iota(jnp.int32, shape, 1) % c)
    return (apart >= s // 2) & (apart < s)


def _level_decay(b, s: int):
    """exp(-|b - b_p|) of a head's b: (c, dk), p the middle row of the row's
    group of s rows: no exponent is positive. Whole registers take b_p as a
    sublane broadcast; inside a register the earlier half takes the rows s/2
    ahead and the bit below is folded away: sublane rolls and selects."""
    c, half = b.shape[0], s // 2
    if s == 1:
        return 1.0
    if s >= _SUBLANES:    # a last group with no later half keeps its rows
        pivot = jnp.concatenate([
            jnp.broadcast_to(b[r + half:r + half + 1], b[r:r + s].shape)
            if r + half < c else b[r:] for r in range(0, c, s)])
    else:
        token = lax.broadcasted_iota(jnp.int32, b.shape, 0)
        pivot = jnp.where(token & half != 0, b, pltpu.roll(b, c - half, 0))
        if half == 2:
            pivot = jnp.where(token & 1 != 0, pltpu.roll(pivot, 1, 0), pivot)
    return jnp.exp(-jnp.abs(b - pivot))


def _each_head(fn, *arrays):
    """`fn` of a head's matrices, a head after the other, so that few
    registers live at once; its results stacked."""
    heads = [fn(*(a[h] for a in arrays)) for h in range(arrays[0].shape[0])]
    return [jnp.stack(x) for x in zip(*heads)]


def _dot(a, b, contract):
    """`_mm` of one head's matrices."""
    return _mm(a[None], b[None], contract)[0]


def _channel_gram_level(q, k, b, s: int):
    """Level s of a head's G[k, k] and G[q, k]: (x * e)(k * e)^T over the
    level's pairs either way round, e its decay, 0 elsewhere: one product
    for both, its operands rounded to the inputs' type (stacked in float32,
    then cast: a packed array is dear to cut or to join)."""
    dt, c, e = k.dtype, k.shape[0], _level_decay(b, s)
    right = k.astype(_F32) * e
    left = jnp.concatenate([right, q.astype(_F32) * e]).astype(dt)
    both = jnp.where(_level_mask((2 * c, c), s),
                     _dot(left, right.astype(dt), (1, 1)), 0.0)
    return both[:c], both[c:]


def _channel_grams(q, k, b):
    """A head's G[k, k] and G[q, k]: (c, c) float32, sum_c x_ic exp(b_ic -
    b_jc) k_jc where j <= i, else 0; q, k: (c, dk), b float32, falling down
    the rows: the sum of the halving's levels, up to the diagonal."""
    c = k.shape[0]
    levels = [_channel_gram_level(q, k, b, s) for s in _levels(c)]
    return [jnp.where(_masks(c)[2], functools.reduce(jnp.add, of_x), 0.0)
            for of_x in zip(*levels)]


def _channel_grams_back(dp, da, dp_t, dkk_t, q, k, b):
    """A head's cotangents `dp` of G[q, k] and `da` of G[k, k] BEFORE its
    rows' scale, with the transposes of dp and of the scaled one, back to
    the rows: (what q's rows get, what k's rows get as G's rows before the
    scale, what they get as G's columns), each (c, dk) float32. By levels:
    the cotangent of (x * e) is the level's dG times (k * e), that of (k *
    e) its transpose times (x * e)."""
    dt, c = k.dtype, k.shape[0]
    qf, kf = q.astype(_F32), k.astype(_F32)
    d_rows = jnp.concatenate([dp, da])
    d_cols = jnp.concatenate([dp_t, dkk_t], axis=1)
    dq = dk_i = dk_j = jnp.zeros_like(b)
    for s in _levels(c):
        e = _level_decay(b, s)
        right = kf * e
        rows = _dot(jnp.where(_level_mask(d_rows.shape, s), d_rows, 0.0)
                    .astype(dt), right.astype(dt), (1, 0))
        cols = _dot(jnp.where(_level_mask(d_cols.shape, s), d_cols, 0.0)
                    .astype(dt), jnp.concatenate([qf * e, right]).astype(dt),
                    (1, 0))
        dq, dk_i, dk_j = (dq + e * rows[:c], dk_i + e * rows[c:],
                          dk_j + e * cols)
    return dq, dk_i, dk_j


# --------------------------------------------------------------------------
# The kernels
# --------------------------------------------------------------------------

def _forward_kernel(q_ref, k_ref, v_ref, b_ref, beta_ref, o_ref, *rest):
    *saved, state = rest
    dt = q_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _first_chunk():
        state[...] = jnp.zeros_like(state)

    q, k, v = q_ref[...], k_ref[...], v_ref[...]
    b, beta = b_ref[:, 0], beta_ref[:, 0]            # (heads, 1, c)
    eye, below, upto = _masks(q.shape[1])
    # what does not depend on the state
    b_col = _as_column(b, eye)
    a = _as_column(beta, eye) * _decay(b_col - b, below) * _mm(k, k, (1, 1))
    t = _unit_lower_inverse(a, eye)
    w = _mm_exact(t * (beta * jnp.exp(b)), k, (1, 0)).astype(dt)
    u0 = _mm_exact(t * beta, v, (1, 0))
    grow, shrink, whole, decay = _chunk_terms(b, b_col, upto)
    p = (_mm(q, k, (1, 1)) * decay).astype(dt)
    q_in = (q.astype(_F32) * grow).astype(dt)
    k_out = (k.astype(_F32) * shrink).astype(dt)
    # the walk: from the state the chunk starts from to the next chunk's
    s = state[...]
    s_op = s.astype(dt)
    u = (u0 - _mm(w, s_op, (1, 0))).astype(dt)
    o_ref[...] = (_mm(q_in, s_op, (1, 0)) + _mm(p, u, (1, 0))).astype(dt)
    state[...] = whole * s + _mm(k_out, u, (0, 0))
    if saved:
        w_ref, u0_ref, t_ref, s0_ref = saved
        w_ref[...], u0_ref[...], t_ref[...], s0_ref[:, 0] = w, u0, t, s


def _backward_kernel(q_ref, k_ref, v_ref, w_ref, u0_ref, t_ref, s0_ref,
                     b_ref, beta_ref, do_ref,
                     dq_ref, dk_ref, dv_ref, db_ref, dbeta_ref, d_state):
    dt = q_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _last_chunk():
        d_state[...] = jnp.zeros_like(d_state)

    q, k, v, w = q_ref[...], k_ref[...], v_ref[...], w_ref[...]
    b, beta = b_ref[:, 0], beta_ref[:, 0]
    c = q.shape[1]
    eye, below, upto = _masks(c)
    grow, shrink, whole, decay = _chunk_terms(b, _as_column(b, eye), upto)
    beta_col = _as_column(beta, eye)
    qf, kf, vf = (x.astype(_F32) for x in (q, k, v))
    kk = _mm(k, k, (1, 1))
    strict = jnp.where(below, decay, 0.0)            # exp(b_i - b_j), j < i
    scores = strict * kk                             # A over beta
    p32 = _mm(q, k, (1, 1)) * decay
    p = p32.astype(dt)
    q_in32, k_out32 = qf * grow, kf * shrink
    q_in, k_out = q_in32.astype(dt), k_out32.astype(dt)
    s0 = s0_ref[:, 0]
    s_op = s0.astype(dt)
    u0 = u0_ref[...]
    u = (u0 - _mm(w, s_op, (1, 0))).astype(dt)
    ds = d_state[...]
    ds_op = ds.astype(dt)
    do = do_ref[...]

    # the walk: what meets the state and its cotangent
    du32 = _mm(p, do, (0, 0)) + _mm(k_out, ds_op, (1, 0))
    du = du32.astype(dt)
    dp = jnp.where(upto, _mm(do, u, (1, 1)), 0.0)
    dq_in = _mm(do, s_op, (1, 1))
    dw = -_mm(du, s_op, (1, 1))
    dk_out = _mm(u, ds_op, (1, 1))
    d_state[...] = whole * ds + _mm(q_in, do, (0, 0)) - _mm(w, du, (0, 0))

    # through W = T (beta exp(b) K), U_0 = T (beta V) and T = (I + A)^-1
    t = t_ref[...]
    dkb = _mm_exact(t, dw.astype(dt), (0, 0))
    dvb = _mm_exact(t, du, (0, 0))
    da = -jnp.where(below, _mm(dkb.astype(dt), w, (1, 1))
                    + _mm(dvb.astype(dt), u0.astype(dt), (1, 1)), 0.0)

    # through the scores and the decays
    dqk = (dp * decay).astype(dt)
    dkk = (da * beta_col * strict).astype(dt)
    dq_ref[...] = (grow * dq_in + _mm(dqk, k, (1, 0))).astype(dt)
    dk_ref[...] = (shrink * dk_out + _mm(dqk, q, (0, 0))
                   + _mm(dkk, k, (1, 0)) + _mm(dkk, k, (0, 0))
                   + beta_col * grow * dkb).astype(dt)
    dv_ref[...] = (beta_col * dvb).astype(dt)

    def rows_of(x):
        return jnp.sum(x, axis=2, keepdims=True)

    through_kb = grow * rows_of(dkb * kf)
    dbeta_ref[:, 0] = _as_row(
        rows_of(da * scores) + through_kb + rows_of(dvb * vf), eye)
    # b: exp(b_i - b_j) in A and in the scores, exp(b) on K and Q, and
    # exp(b_C - b) on K with exp(b_C) on the state, which are b_C's
    e = da * beta_col * scores + dp * p32
    leaving = rows_of(dk_out * k_out32)
    db = _as_row(rows_of(e) + beta_col * through_kb
                 + rows_of(dq_in * q_in32) - leaving, eye) \
        - jnp.sum(e, axis=1, keepdims=True)
    at_last = jnp.sum(leaving, axis=1, keepdims=True) + whole * jnp.sum(
        rows_of(ds * s0), axis=1, keepdims=True)
    lane = lax.broadcasted_iota(jnp.int32, (1, c), 1)
    db_ref[:, 0] = db + jnp.where(lane == c - 1, at_last, 0.0)


def _running_sum(g, reverse: bool = False):
    """b = L g of a head's g: (c, dk) float32, L the (c x c) lower triangle
    of ones, diagonal included: g's running sum down the rows of a chunk. By
    doubling, on the vector unit: step s = 1, 2, 4, ... adds to every row
    the row s above it (a sublane roll, the rows that have none above masked),
    after which a row holds the sum of the 2 s rows that end in it: a tree
    of float32 additions. With `reverse` L^T g, the sum from a row to the
    chunk's last: g's cotangent from b's."""
    c = g.shape[0]
    token = lax.broadcasted_iota(jnp.int32, g.shape, 0)
    step = 1
    while step < c:
        shift, has_one = (c - step, token < c - step) if reverse \
            else (step, token >= step)
        g = g + jnp.where(has_one, pltpu.roll(g, shift, 0), 0.0)
        step *= 2
    return g


def _channel_terms(b):
    """Of a chunk with a decay a channel, b: (heads, c, dk): exp(b),
    exp(b_C - b), and exp(b_C): (heads, 1, dk), a row over the state's
    lanes."""
    last = b[:, -1:]
    return jnp.exp(b), jnp.exp(last - b), jnp.exp(last)


def _channel_forward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref,
                            *rest):
    *saved, state = rest                     # the state: (heads, dv, dk)
    dt = q_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _first_chunk():
        state[...] = jnp.zeros_like(state)

    q, k, v = q_ref[...], k_ref[...], v_ref[...]
    b = jnp.stack([_running_sum(g_ref[h]) for h in range(q.shape[0])])
    beta = beta_ref[:, 0]                            # (heads, 1, c)
    eye, below, _ = _masks(q.shape[1])
    # what does not depend on the state
    kk, p32 = _each_head(_channel_grams, q, k, b)
    kk = jnp.where(below, kk, 0.0)
    t = _unit_lower_inverse(_as_column(beta, eye) * kk, eye)
    grow, shrink, whole = _channel_terms(b)
    qf, kf = q.astype(_F32), k.astype(_F32)
    scaled = t * beta
    w = _mm_exact(scaled, (kf * grow).astype(dt), (1, 0)).astype(dt)
    u0 = _mm_exact(scaled, v, (1, 0))
    p = p32.astype(dt)
    q_in = (qf * grow).astype(dt)
    k_out = (kf * shrink).astype(dt)
    # the walk: from the state the chunk starts from to the next chunk's
    s = state[...]
    s_op = s.astype(dt)
    u = (u0 - _mm(w, s_op, (1, 1))).astype(dt)
    o_ref[...] = (_mm(q_in, s_op, (1, 1)) + _mm(p, u, (1, 0))).astype(dt)
    state[...] = whole * s + _mm(u, k_out, (0, 0))
    if saved:
        w_ref, u0_ref, tp_ref, s0_ref = saved
        w_ref[...], u0_ref[...], s0_ref[:, 0] = w, u0, s
        c = t.shape[-1]
        tp_ref[:, :, :c], tp_ref[:, :, c:] = t, p32


def _channel_backward_kernel(q_ref, k_ref, v_ref, w_ref, u0_ref, tp_ref,
                             s0_ref, g_ref, beta_ref, do_ref,
                             dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                             d_state):
    dt = q_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _last_chunk():
        d_state[...] = jnp.zeros_like(d_state)

    q, k, v, w = q_ref[...], k_ref[...], v_ref[...], w_ref[...]
    b = jnp.stack([_running_sum(g_ref[h]) for h in range(q.shape[0])])
    beta = beta_ref[:, 0]
    c = q.shape[1]
    eye, below, upto = _masks(c)
    grow, shrink, whole = _channel_terms(b)
    beta_col = _as_column(beta, eye)
    qf, kf, vf = (x.astype(_F32) for x in (q, k, v))
    q_in32, k_in32, k_out32 = qf * grow, kf * grow, kf * shrink
    q_in, k_out = q_in32.astype(dt), k_out32.astype(dt)
    t, p = tp_ref[:, :, :c], tp_ref[:, :, c:].astype(dt)
    s0 = s0_ref[:, 0]
    s_op = s0.astype(dt)
    u0 = u0_ref[...]
    u = (u0 - _mm(w, s_op, (1, 1))).astype(dt)
    ds = d_state[...]
    ds_op = ds.astype(dt)
    do = do_ref[...]

    # the walk: what meets the state and its cotangent
    du = (_mm(p, do, (0, 0)) + _mm(k_out, ds_op, (1, 1))).astype(dt)
    dp = jnp.where(upto, _mm(do, u, (1, 1)), 0.0)
    dp_t = jnp.where(below, 0.0, _mm(u, do, (1, 1)))
    dq_in = _mm(do, s_op, (1, 0))
    dw = -_mm(du, s_op, (1, 0))
    dk_out = _mm(u, ds_op, (1, 0))
    d_state[...] = whole * ds + _mm(do, q_in, (0, 0)) - _mm(du, w, (0, 0))

    # through W = T (beta exp(b) K), U_0 = T (beta V) and T = (I + A)^-1
    dkb = _mm_exact(t, dw.astype(dt), (0, 0))
    dvb = _mm_exact(t, du, (0, 0))
    dkb_op, dvb_op, u0_op = dkb.astype(dt), dvb.astype(dt), u0.astype(dt)
    da = -jnp.where(below, _mm(dkb_op, w, (1, 1))
                    + _mm(dvb_op, u0_op, (1, 1)), 0.0)
    da_t = -jnp.where(upto, 0.0, _mm(w, dkb_op, (1, 1))
                      + _mm(u0_op, dvb_op, (1, 1)))

    # through G[Q, K] and G[K, K] = A over beta: A's cotangent reaches the
    # rows of K through G[K, K]'s rows times beta, and beta through them
    dq_g, through_a, dk_j = _each_head(
        _channel_grams_back, dp, da, dp_t, da_t * beta, q, k, b)
    dk_i = beta_col * through_a
    dq_ref[...] = (grow * dq_in + dq_g).astype(dt)
    dk_ref[...] = (shrink * dk_out + dk_i + dk_j
                   + beta_col * grow * dkb).astype(dt)
    dv_ref[...] = (beta_col * dvb).astype(dt)

    def rows_of(x):
        return jnp.sum(x, axis=2, keepdims=True)

    through_kb = dkb * k_in32
    dbeta_ref[:, 0] = _as_row(
        rows_of(through_a * kf) + rows_of(through_kb) + rows_of(dvb * vf),
        eye)
    # b: inside G (elementwise, see the top), exp(b) on K and Q, and
    # exp(b_C - b) on K with exp(b_C) on the state, which are b_C's
    leaving = dk_out * k_out32
    at_last = jnp.sum(leaving, axis=1, keepdims=True) + whole * jnp.sum(
        ds * s0, axis=1, keepdims=True)
    token = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    db = qf * dq_g + kf * (dk_i - dk_j) + beta_col * through_kb \
        + dq_in * q_in32 - leaving + jnp.where(token == c - 1, at_last, 0.0)
    for h in range(db.shape[0]):
        dg_ref[h] = _running_sum(db[h], reverse=True)


# --------------------------------------------------------------------------
# The calls
# --------------------------------------------------------------------------

def _specs(heads: int, blocks: int, c: int, n: int, reverse: bool = False):
    """Block specs of a grid (blocks of `heads` heads, chunks) over the
    caller's arrays (batch, all heads, tokens, width), `blocks` blocks to a
    batch entry, and the kernels' own, which hold batch and heads as one
    axis: (., tokens, width), (., chunks, 1, c) and (., chunks, dk, dv).
    `reverse` walks the chunks from the last."""
    def chunk(j):
        return n - 1 - j if reverse else j

    def given(width):
        return pl.BlockSpec((None, heads, c, width), lambda i, j: (
            i // blocks, i % blocks, chunk(j), 0))

    def tokens(width):
        return pl.BlockSpec((heads, c, width), lambda i, j: (i, chunk(j), 0))

    gates = pl.BlockSpec((heads, 1, 1, c), lambda i, j: (i, chunk(j), 0, 0))

    def states(dk, dv):
        return pl.BlockSpec((heads, 1, dk, dv),
                            lambda i, j: (i, chunk(j), 0, 0))

    return given, tokens, gates, states


_PARAMS = {"compiler_params": pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))}


def _per_channel(b) -> bool:
    """Whether g is a decay a channel, (B, H, S, dk), and not one a head and
    token as the kernels hold it or its running sum b, (B H, n, 1, c)."""
    return b.shape[2] != 1


def _form(b, c: int, dk: int, dv: int, given, gates):
    """What the two forms' calls differ in: (the forward kernel, the
    backward one, the block spec of b, or of g with a decay a channel, the
    width of T's array, the state's shape). With a decay a channel T and P
    lie side by side in one float32 array, (., S, 2c): 128 lanes at the
    chunk of 64, where each alone would be padded to them; the state is
    transposed, (dv x dk)."""
    if _per_channel(b):
        return (_channel_forward_kernel, _channel_backward_kernel, given(dk),
                2 * c, (dv, dk))
    return _forward_kernel, _backward_kernel, gates, c, (dk, dv)


def _forward(q, k, v, b, beta, *, c: int, heads: int, save: bool):
    """q, k: (B, H, S, dk), v: (B, H, S, dv), beta: (B H, n, 1, c), b like
    beta, or in its place g: (B, H, S, dk) with a decay a channel, H a
    multiple of `heads` and S = n c. Returns o, and with `save` the
    residuals of the backward pass: W, U_0, T (with a decay a channel T |
    P) and the entry states, batch and heads one axis."""
    batch, n_heads, seq, dk = q.shape
    dv, dt, n, n_all = v.shape[-1], v.dtype, seq // c, batch * n_heads
    given, tokens, gates, states = _specs(heads, n_heads // heads, c, n)
    kernel, _, decays, kept, state = _form(b, c, dk, dv, given, gates)
    tall = jax.ShapeDtypeStruct
    o, *saved = pallas_call(
        kernel, grid=(n_all // heads, n),
        in_specs=[given(dk), given(dk), given(dv), decays, gates],
        out_specs=[given(dv)] + [tokens(dk), tokens(dv), tokens(kept),
                                 states(*state)] * save,
        out_shape=[tall(v.shape, dt)] + [
            tall((n_all, seq, dk), dt), tall((n_all, seq, dv), _F32),
            tall((n_all, seq, kept), _F32), tall((n_all, n) + state, _F32)
        ] * save,
        scratch_shapes=[pltpu.VMEM((heads,) + state, _F32)],
        **_PARAMS)(q, k, v, b, beta)
    return (o, tuple(saved)) if save else o


def _backward(q, k, v, b, beta, w, u0, t, s0, do, *, c: int, heads: int):
    dk, dv, n = q.shape[-1], v.shape[-1], q.shape[2] // c
    given, tokens, gates, states = _specs(heads, q.shape[1] // heads, c, n,
                                          reverse=True)
    _, kernel, decays, kept, state = _form(b, c, dk, dv, given, gates)
    tall = jax.ShapeDtypeStruct
    return pallas_call(
        kernel, grid=(beta.shape[0] // heads, n),
        in_specs=[given(dk), given(dk), given(dv), tokens(dk), tokens(dv),
                  tokens(kept), states(*state), decays, gates, given(dv)],
        out_specs=[given(dk), given(dk), given(dv), decays, gates],
        out_shape=[tall(q.shape, q.dtype), tall(k.shape, k.dtype),
                   tall(v.shape, v.dtype), tall(b.shape, _F32),
                   tall(beta.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((heads,) + state, _F32)],
        **_PARAMS)(q, k, v, w, u0, t, s0, b, beta, do)


def _running(g, reverse: bool = False):
    """b of one decay a head and token, (., n, 1, c): g's running sum inside
    each chunk. With `reverse`, g's cotangent from b's: g_j gets every b_i
    of its chunk with i >= j."""
    summed = functools.partial(lax.cumsum, reverse=True) if reverse \
        else jnp.cumsum
    return summed(g, axis=3)


def _decays(g, reverse: bool = False):
    """What the kernels take in g's place: b (`_running`), or with a decay a
    channel g itself, which those kernels sum in VMEM (`_running_sum`: b is
    never in HBM). With `reverse`, g's cotangent from what they hand
    back."""
    return g if _per_channel(g) else _running(g, reverse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, g, beta, c, heads):
    return _forward(q, k, v, _decays(g), beta, c=c, heads=heads, save=False)


def _rule_fwd(q, k, v, g, beta, c, heads):
    b = _decays(g)
    o, saved = _forward(q, k, v, b, beta, c=c, heads=heads, save=True)
    return o, (q, k, v, b, beta, *saved)


def _rule_bwd(c, heads, saved, do):
    dq, dk, dv, db, dbeta = _backward(*saved, do, c=c, heads=heads)
    return dq, dk, dv, _decays(db, reverse=True), dbeta


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = CHUNK):
    """o_t = S_t^T q_t of the gated delta rule, S_0 = 0, in chunks of
    `chunk` tokens (a multiple of 8).

    q, k: (B, H, S, dk); v: (B, H, S, dv); beta: (B, H, S) and g (log
    decay, <= 0): (B, H, S), one a head and token, or (B, H, S, dk), one a
    key channel; float32. q and k come normalised and scaled as the caller
    wants them. Returns (B, H, S, dv) in v's type. A length that is no
    multiple of the chunk is padded with rows of g = 0, beta = 0, which
    leave the state alone; heads that do not fill a grid step's block are
    padded the same way."""
    c = _chunk_size(chunk)
    B, H, S, dk = q.shape
    dt = v.dtype
    if dt not in (jnp.bfloat16, _F32):
        return gated_delta_rule(
            *(x.astype(_F32) for x in (q, k, v)), g, beta,
            chunk=chunk).astype(dt)
    per_channel = g.ndim == 4
    n = chunks_of(S, c)
    heads = heads_a_step(H, dk, v.shape[-1], c, dt.itemsize, per_channel)
    pad = ((0, 0), (0, -H % heads), (0, n * c - S))

    def whole(x):
        return jnp.pad(x, pad + ((0, 0),) * (x.ndim - 3)) \
            if pad[1][1] or pad[2][1] else x

    q, k, v = (whole(x.astype(dt)) for x in (q, k, v))
    g, beta = (whole(x.astype(_F32)) if x.ndim == 4
               else whole(x.astype(_F32)).reshape(-1, n, 1, c)
               for x in (g, beta))
    return _rule(q, k, v, g, beta, c, heads)[:, :H, :S]


def recurrent_gated_delta_rule(q, k, v, g, beta):
    """The same outputs one token at a time, all in float32: the recurrence
    as it is written at the top, for the tests of the chunked form; g: (B,
    H, S) or (B, H, S, dk)."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    if g.ndim == 3:
        g = g[..., None]

    def step(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = jnp.exp(g_t)[..., None] * state
        u = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state,
                                                  k_t))
        state = state + k_t[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    B, H, _, dk = q.shape
    per_token = tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, beta))
    with jax.default_matmul_precision("highest"):
        _, o = lax.scan(step, jnp.zeros((B, H, dk, v.shape[-1]), f32),
                        per_token)
    return jnp.moveaxis(o, 0, 2).astype(v.dtype)
