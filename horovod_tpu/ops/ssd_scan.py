"""The Mamba-2 recurrence (Dao & Gu, arXiv:2405.21060) over a sequence in its
chunked "state-space dual" form, as Pallas TPU kernels (forward + backward).

Per head h of width P, with inputs x_t (P wide), a step dt_t > 0, one decay
rate exp(a_log) a head, and B_t, C_t (N wide) that all heads share (one
group), a float32 state S (P x N) that starts at zero goes through

    S <- exp(-exp(a_log) dt_t) S + dt_t x_t (x) B_t;   y_t = S C_t + D x_t

(`recurrent_ssd_scan` below: one token at a time, for the tests).
`ssd_scan` computes the same outputs Q tokens at a time. With l_i the running
sum of the log decay -exp(a_log) dt inside a chunk (l_i <= 0, falling) and
S_0 the state the chunk starts from,

    G    = C B^T                            (Q x Q), once for all heads
    M    = G * exp(l_i - l_j)  for j <= i, else 0
    Y    = M (dt * X) + exp(l) * (C S_0^T) + D * X
    S_Q  = exp(l_Q) S_0 + ((exp(l_Q - l) dt * X))^T B

so the work is matrix products: Q P + 2 N P multiply-adds a token and head
beside N Q a token for G, where the recurrence has 2 N P on the vector unit.
Every exponent is of a difference l_i - l_j with j <= i, of l itself, or of
l_Q - l_j: none is positive, and nothing is divided by a decay, so a strong
decay underflows to zero and does nothing worse.

Layout. x and y stay token-major, (B, S, H P), as the input projection makes
them and the gate reads them: a grid step takes one chunk of `heads_a_step`
heads, whose channels are whole 128-lane tiles. A tile holds 128 / P heads
side by side; a head's (Q x Q) matrix multiplies the whole tile (the matrix
unit is 128 wide either way) and the head's own lanes are kept. The
per-token, per-head numbers (dt and l) come twice, tiny both: heads along
the lanes, (Q, heads), for what scales rows, and tokens along the lanes,
(heads, Q), for the columns of exp(l_i - l_j); the wrapper makes both.

Two kernels, each a grid of (batch, blocks of heads, chunks) that walks a
block's chunks in sequence (the grid's last, "arbitrary" axis) with the
state, or its cotangent, resident in VMEM as (N, heads P) float32: a scratch
cleared at a sequence's first chunk. The backward kernel walks the chunks
from the last, from each chunk's entry state, which the forward pass under
differentiation wrote out (float32, N x H P a chunk) beside y before its
rounding; it needs no row or column sums of a (Q x Q) matrix: the cotangent
of l_i is dy_i . (y_i - D x_i) - (dt x)_i . d(dt x)_i a head, plus what l_Q
carries. The two terms nearly cancel, which is why y comes in float32: from
the bf16 y the gradients of a_log and dt were as wrong as they were large.

Precision: products take their operands in the inputs' type (bf16 in the
benchmark's cell) and accumulate in float32; dt, l, every decay, the state
and its cotangent are float32, rounded to the inputs' type only as operands
of a product. float32 inputs multiply at the MXU's full float32 precision.
The gradients of B and C are written a block of heads at a time and added up
by the wrapper, as D's is a chunk at a time.

Off the TPU the same kernels run in the Pallas interpreter
(`ops/_pallas.interpret`). `chunked_ssd_scan` is the same algorithm in
`jnp`, the kernels' oracle in the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops import _pallas
from horovod_tpu.ops._pallas import pallas_call

#: tokens a chunk; the published `mamba_chunk_size` of the Granite hybrids.
#: docs/kernels.md has the chip's timings at 128 and 256.
CHUNK = 256
_LANES = 128
_SUBLANES = 8
#: most heads a grid step takes (their channels: 1,024 lanes at P = 64)
_HEADS_A_STEP = 16
_F32 = jnp.float32

_NN = (((1,), (0,)), ((), ()))      # a b
_NT = (((1,), (1,)), ((), ()))      # a b^T
_TN = (((0,), (0,)), ((), ()))      # a^T b


def _chunk_size(chunk: int) -> int:
    if chunk < _SUBLANES or chunk % _SUBLANES:
        raise ValueError(f"ssd_scan: chunk {chunk} (a positive multiple of "
                         f"{_SUBLANES})")
    return chunk


def chunks_of(seq: int, chunk: int = CHUNK) -> int:
    """Chunks a sequence of `seq` tokens takes: the last one padded."""
    return -(-seq // _chunk_size(chunk))


def heads_a_tile(heads: int, width: int) -> int:
    """Heads whose channels lie side by side in one 128-lane tile: as many
    as fit, a divisor of `heads`; one where a head is a tile or more."""
    most = max(1, _LANES // width)
    return next(g for g in range(min(most, heads), 0, -1) if heads % g == 0)


def heads_a_step(heads: int, width: int) -> int:
    """Heads a grid step takes: whole tiles, at most `_HEADS_A_STEP`, a
    divisor of `heads`."""
    tile = heads_a_tile(heads, width)
    return next(h for h in range(min(heads, max(_HEADS_A_STEP, tile)), 0, -1)
                if heads % h == 0 and h % tile == 0)


def chunked_over_recurrent_macs(heads: int, width: int, states: int,
                                chunk: int = CHUNK) -> float:
    """Multiply-adds a token of the chunked form's matrix products as the
    kernel runs them (G once, per head the whole Q x Q matrix times the
    head's inputs, the state's read-out and its update) over the recurrent
    form's 2 N P a head."""
    q = _chunk_size(chunk)
    chunked = states * q + heads * (q * width + 2 * states * width)
    return chunked / (heads * 2 * states * width)


# --------------------------------------------------------------------------
# What the kernels share
# --------------------------------------------------------------------------

def _dot(a, b, dims):
    """a x b with float32 accumulation; float32 operands at float32
    precision."""
    exact = lax.Precision.HIGHEST if a.dtype == _F32 else None
    return lax.dot_general(a, b, dims, precision=exact,
                           preferred_element_type=_F32)


def _decay(exponent, mask):
    """exp of the exponents `mask` holds, 0 elsewhere: masked before `exp`,
    where the exponent may be positive."""
    return jnp.exp(jnp.where(mask, exponent, -jnp.inf))


def _head_of_lane(tile: int, width: int):
    """(1, tile) int32: which of a tile's heads a lane belongs to."""
    return lax.broadcasted_iota(jnp.int32, (1, tile), 1) // width


def _over_lanes(col, first: int, group: int, head_of):
    """Columns first .. first + group of col: (Q, heads), each spread over
    its head's lanes of a tile: (Q, tile)."""
    out = col[:, first:first + 1]
    for k in range(1, group):
        out = jnp.where(head_of == k, col[:, first + k:first + k + 1], out)
    return jnp.broadcast_to(out, (col.shape[0], head_of.shape[1]))


def _head_sums(t, first: int, width: int, heads: int, dt):
    """The sums of t: (rows, tile) float32 over each head's lanes, as
    columns first .. of a (rows, max(heads, 128)) array that is zero
    elsewhere: a product with a 0/1 matrix. Float32 inputs at float32
    precision; else t goes as two pieces of the inputs' type (16 of its 24
    bits where that is bf16)."""
    out = max(heads, _LANES)
    shape = (t.shape[1], out)
    select = lax.broadcasted_iota(jnp.int32, shape, 0) // width + first \
        == lax.broadcasted_iota(jnp.int32, shape, 1)
    if dt == _F32:
        return _dot(t, select.astype(_F32), _NN)
    high = t.astype(dt)
    low = (t - high.astype(_F32)).astype(dt)
    return _dot(high, select.astype(dt), _NN) \
        + _dot(low, select.astype(dt), _NN)


# --------------------------------------------------------------------------
# The kernels
# --------------------------------------------------------------------------

def _forward_kernel(x_ref, cols_ref, lrow_ref, b_ref, c_ref, d_ref, y_ref,
                    *rest, width: int, group: int):
    *saved, state = rest
    dt = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state[...] = jnp.zeros_like(state)

    bm, cm = b_ref[...], c_ref[...]                       # (Q, N)
    q = bm.shape[0]
    scores = _dot(cm, bm, _NT)                            # C B^T
    upto = lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        >= lax.broadcasted_iota(jnp.int32, (q, q), 1)
    steps, sums = cols_ref[0], cols_ref[1]                # (Q, heads)
    tile = group * width
    head_of = _head_of_lane(tile, width)
    if saved:
        saved[0][...] = state[...]
    for t in range(x_ref.shape[1] // tile):
        lanes = slice(t * tile, (t + 1) * tile)
        first = t * group
        xt = x_ref[:, lanes]
        x32 = xt.astype(_F32)
        s = state[:, lanes]                               # (N, tile)
        step = _over_lanes(steps, first, group, head_of)
        l = _over_lanes(sums, first, group, head_of)
        last = l[q - 1:q]
        xdt = (x32 * step).astype(dt)
        y = jnp.exp(l) * _dot(cm, s.astype(dt), _NN) + d_ref[:, lanes] * x32
        for k in range(group):
            h = first + k
            m = (scores * _decay(sums[:, h:h + 1] - lrow_ref[h:h + 1, :],
                                 upto)).astype(dt)
            part = _dot(m, xdt, _NN)
            y = y + (part if group == 1 else
                     jnp.where(head_of == k, part, 0.0))
        y_ref[:, lanes] = y.astype(dt)
        if saved:
            saved[1][:, lanes] = y
        leaving = (x32 * (step * jnp.exp(last - l))).astype(dt)
        state[:, lanes] = jnp.exp(last) * s + _dot(bm, leaving, _TN)


def _backward_kernel(x_ref, cols_ref, lrow_ref, b_ref, c_ref, d_ref, s0_ref,
                     y_ref, dy_ref, dx_ref, dcols_ref, db_ref, dc_ref,
                     dd_ref, d_state, *, width: int, group: int):
    dt = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        d_state[...] = jnp.zeros_like(d_state)

    bm, cm = b_ref[...], c_ref[...]
    q = bm.shape[0]
    scores_t = _dot(bm, cm, _NT)                          # B C^T = G^T
    at_row = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    at_col = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    upto, from_ = at_row >= at_col, at_row <= at_col
    steps, sums = cols_ref[0], cols_ref[1]
    heads = steps.shape[1]
    tile = group * width
    head_of = _head_of_lane(tile, width)
    d_scores = jnp.zeros((q, q), _F32)
    db = jnp.zeros(bm.shape, _F32)
    dc = jnp.zeros(cm.shape, _F32)
    totals = None
    for t in range(x_ref.shape[1] // tile):
        lanes = slice(t * tile, (t + 1) * tile)
        first = t * group
        xt, dyt = x_ref[:, lanes], dy_ref[:, lanes]
        x32, dy32 = xt.astype(_F32), dyt.astype(_F32)
        skip = d_ref[:, lanes]
        s0, ds = s0_ref[:, lanes], d_state[:, lanes]      # (N, tile)
        step = _over_lanes(steps, first, group, head_of)
        l = _over_lanes(sums, first, group, head_of)
        last = l[q - 1:q]
        whole, shrink = jnp.exp(last), jnp.exp(last - l)
        xdt32 = x32 * step
        xdt = xdt32.astype(dt)
        # what the chunk's inputs get from the state it leaves
        from_state = shrink * _dot(bm, ds.astype(dt), _NN)
        d_xdt = from_state
        for k in range(group):
            h = first + k
            lc, lr = sums[:, h:h + 1], lrow_ref[h:h + 1, :]
            mine = dyt if group == 1 else \
                jnp.where(head_of == k, dyt, jnp.zeros((), dt))
            d_scores = d_scores + _dot(mine, xdt, _NT) * _decay(lc - lr, upto)
            m_t = (scores_t * _decay(lr - lc, from_)).astype(dt)
            part = _dot(m_t, dyt, _NN)
            d_xdt = d_xdt + (part if group == 1 else
                             jnp.where(head_of == k, part, 0.0))
        dx_ref[:, lanes] = (step * d_xdt + skip * dy32).astype(dt)
        dd_ref[:, lanes] = jnp.sum(dy32 * x32, axis=0, keepdims=True)
        # the read-out of the entry state, and the state's cotangent
        reading = (jnp.exp(l) * dy32).astype(dt)
        dc = dc + _dot(reading, s0.astype(dt), _NT)
        db = db + _dot((xdt32 * shrink).astype(dt), ds.astype(dt), _NT)
        d_state[:, lanes] = whole * ds + _dot(cm, reading, _TN)
        # per head: dt's own gradient, l's, and what l_Q carries besides
        # (l's terms nearly cancel in the sum behind a token, the
        # read-outs' against the inputs' and those against what l_Q
        # carries: each takes dt x as the products took it, rounded, or
        # what is left is rounding's)
        rounded = xdt.astype(_F32)
        direct = d_xdt * x32
        through = dy32 * (y_ref[:, lanes] - skip * x32) - d_xdt * rounded
        carried = jnp.sum(from_state * rounded, axis=0, keepdims=True) \
            + whole * jnp.sum(ds * s0, axis=0, keepdims=True)
        carried = jnp.broadcast_to(carried, (_SUBLANES, tile))
        got = [_head_sums(v, first, width, heads, dt)
               for v in (direct, through, carried)]
        totals = got if totals is None else [
            a + b for a, b in zip(totals, got)]
    d_scores = d_scores.astype(dt)
    db_ref[...] = db + _dot(d_scores, cm, _TN)
    dc_ref[...] = dc + _dot(d_scores, bm, _NN)
    d_step, d_sum, d_last = (v[:, :heads] for v in totals)
    row = lax.broadcasted_iota(jnp.int32, (q, heads), 0)
    dcols_ref[0] = d_step
    dcols_ref[1] = d_sum + jnp.where(row == q - 1, d_last[:1], 0.0)


# --------------------------------------------------------------------------
# The calls
# --------------------------------------------------------------------------

def _specs(q: int, heads: int, width: int, n: int, reverse: bool = False):
    """Block specs of a grid (batch, blocks of `heads` heads, chunks) over
    (B, S, channels) arrays, (B, S, N) ones, the per-head numbers' two
    forms, (B, blocks, 2, S, heads) and (B, blocks, n, heads, Q), the
    chunks' entry states (B, n, N, channels) and per block (B, blocks, S,
    N), per chunk (B, n, 1, channels). `reverse` walks the chunks from the
    last."""
    def chunk(j):
        return n - 1 - j if reverse else j

    lanes = heads * width
    return {
        "channels": pl.BlockSpec((None, q, lanes),
                                 lambda b, i, j: (b, chunk(j), i)),
        "shared": lambda states: pl.BlockSpec(
            (None, q, states), lambda b, i, j: (b, chunk(j), 0)),
        "cols": pl.BlockSpec((None, None, 2, q, heads),
                             lambda b, i, j: (b, i, 0, chunk(j), 0)),
        "rows": pl.BlockSpec((None, None, None, heads, q),
                             lambda b, i, j: (b, i, chunk(j), 0, 0)),
        "skip": pl.BlockSpec((1, lanes), lambda b, i, j: (0, i)),
        "states": lambda states: pl.BlockSpec(
            (None, None, states, lanes),
            lambda b, i, j: (b, chunk(j), 0, i)),
        "a_block": lambda states: pl.BlockSpec(
            (None, None, q, states), lambda b, i, j: (b, i, chunk(j), 0)),
        "a_chunk": pl.BlockSpec((None, None, 1, lanes),
                                lambda b, i, j: (b, chunk(j), 0, i)),
    }


_PARAMS = {"compiler_params": pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))}


# Each call below is one `jax.jit`ted function: a step that runs the scan at
# many sites (a layer pattern's layers are inline, each with a forward pass,
# its repeat under remat and a backward pass) traces and lowers a kernel's
# body once, not once a site (docs/kernels.md; PERF.md, PR 43).
# `interpreted` is what the trace reads besides its operands.

@functools.partial(jax.jit, static_argnames=("width", "save", "interpreted"))
def _forward(x, cols, lrow, b, c, skip, *, width: int, save: bool,
             interpreted: bool):
    """x: (B, S, E); cols: (B, blocks, 2, S, heads); lrow: (B, blocks, n,
    heads, Q); b, c: (B, S, N); skip: (1, E). Returns y, and with `save`
    each chunk's entry state, (B, n, N, E) float32, and y before its
    rounding, float32."""
    batch, _, channels = x.shape
    _, blocks, n, heads, q = lrow.shape
    states = b.shape[-1]
    spec = _specs(q, heads, width, n)
    tall = jax.ShapeDtypeStruct
    kernel = functools.partial(_forward_kernel, width=width,
                               group=heads_a_tile(heads, width))
    y, *saved = pallas_call(
        kernel, grid=(batch, blocks, n),
        in_specs=[spec["channels"], spec["cols"], spec["rows"],
                  spec["shared"](states), spec["shared"](states),
                  spec["skip"]],
        out_specs=[spec["channels"]] + [spec["states"](states),
                                        spec["channels"]] * save,
        out_shape=[tall(x.shape, x.dtype)] + [
            tall((batch, n, states, channels), _F32),
            tall(x.shape, _F32)] * save,
        scratch_shapes=[pltpu.VMEM((states, heads * width), _F32)],
        **_PARAMS)(x, cols, lrow, b, c, skip)
    return (y, *saved) if save else y


@functools.partial(jax.jit, static_argnames=("width", "interpreted"))
def _backward(x, cols, lrow, b, c, skip, s0, y, dy, *, width: int,
              interpreted: bool):
    batch, seq, channels = x.shape
    _, blocks, n, heads, q = lrow.shape
    states = b.shape[-1]
    spec = _specs(q, heads, width, n, reverse=True)
    tall = jax.ShapeDtypeStruct
    kernel = functools.partial(_backward_kernel, width=width,
                               group=heads_a_tile(heads, width))
    return pallas_call(
        kernel, grid=(batch, blocks, n),
        in_specs=[spec["channels"], spec["cols"], spec["rows"],
                  spec["shared"](states), spec["shared"](states),
                  spec["skip"], spec["states"](states), spec["channels"],
                  spec["channels"]],
        out_specs=[spec["channels"], spec["cols"],
                   spec["a_block"](states), spec["a_block"](states),
                   spec["a_chunk"]],
        out_shape=[tall(x.shape, x.dtype), tall(cols.shape, _F32),
                   tall((batch, blocks, seq, states), _F32),
                   tall((batch, blocks, seq, states), _F32),
                   tall((batch, n, 1, channels), _F32)],
        scratch_shapes=[pltpu.VMEM((states, heads * width), _F32)],
        **_PARAMS)(x, cols, lrow, b, c, skip, s0, y, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, cols, lrow, b, c, skip, width):
    return _forward(x, cols, lrow, b, c, skip, width=width, save=False,
                    interpreted=_pallas.interpret())


def _scan_fwd(x, cols, lrow, b, c, skip, width):
    y, s0, wide = _forward(x, cols, lrow, b, c, skip, width=width,
                           save=True, interpreted=_pallas.interpret())
    return y, (x, cols, lrow, b, c, skip, s0, wide)


def _scan_bwd(width, saved, dy):
    x, cols, lrow, b, c, skip, s0, y = saved
    dx, dcols, db, dc, dd = _backward(*saved, dy, width=width,
                                      interpreted=_pallas.interpret())
    # l reaches the kernels in two forms that are one array: its gradient
    # is the columns', and the rows' form gets none
    return (dx, dcols, jnp.zeros_like(lrow),
            jnp.sum(db, axis=1).astype(b.dtype),
            jnp.sum(dc, axis=1).astype(c.dtype),
            jnp.sum(dd, axis=(0, 1)))


_scan.defvjp(_scan_fwd, _scan_bwd)


def _per_head_forms(dt, a_log, q: int, heads: int):
    """dt: (B, n Q, H) float32 and the decay rates -> the two forms the
    kernels read: cols (B, H / heads, 2, n Q, heads) = [dt, l] and lrow (B,
    H / heads, n, heads, Q) = l, l the running sum of -exp(a_log) dt inside
    each chunk of Q."""
    batch, seq, n_heads = dt.shape
    n, blocks = seq // q, n_heads // heads
    log_decay = -jnp.exp(a_log.astype(_F32)) * dt
    sums = jnp.cumsum(log_decay.reshape(batch, n, q, n_heads), axis=2)
    blocked = jnp.stack([dt, sums.reshape(dt.shape)], axis=1).reshape(
        batch, 2, seq, blocks, heads)
    cols = jnp.transpose(blocked, (0, 3, 1, 2, 4))
    lrow = jnp.transpose(sums.reshape(batch, n, q, blocks, heads),
                         (0, 3, 1, 4, 2))
    # the rows' form is the columns' transposed, and takes no gradient
    return cols, lax.stop_gradient(lrow)


def ssd_scan(x, dt, a_log, b, c, d_skip, *, chunk: int = CHUNK):
    """y_t = S_t C_t + D x_t of the Mamba-2 recurrence, S_0 = 0, in chunks
    of `chunk` tokens (a multiple of 8).

    x: (B, S, H P), head h's channels h P .. (h + 1) P; dt: (B, S, H), the
    step after its softplus, float32; a_log, d_skip: (H,), the decay rate's
    logarithm and the skip's scale; b, c: (B, S, N), shared by the heads.
    Returns (B, S, H P) in x's type. A length that is no multiple of the
    chunk is padded with tokens of dt = 0, which leave the state alone."""
    return _ssd_scan(x, dt, a_log, b, c, d_skip, chunk=chunk,
                     interpreted=_pallas.interpret())


@functools.partial(jax.jit, static_argnames=("chunk", "interpreted"))
def _ssd_scan(x, dt, a_log, b, c, d_skip, *, chunk: int, interpreted: bool):
    """`ssd_scan`, traced once a program."""
    q = _chunk_size(chunk)
    batch, seq, channels = x.shape
    n_heads = dt.shape[-1]
    if channels % n_heads or a_log.shape != (n_heads,) \
            or d_skip.shape != (n_heads,):
        raise ValueError(f"ssd_scan: {channels} channels, dt of "
                         f"{n_heads} heads, a_log {a_log.shape}, d_skip "
                         f"{d_skip.shape}")
    kind = x.dtype
    if kind not in (jnp.bfloat16, _F32):
        return _ssd_scan(x.astype(_F32), dt, a_log, b.astype(_F32),
                         c.astype(_F32), d_skip, chunk=chunk,
                         interpreted=interpreted).astype(kind)
    width = channels // n_heads
    q = min(q, -(-seq // _SUBLANES) * _SUBLANES)
    pad = chunks_of(seq, q) * q - seq

    def whole(v):
        return jnp.pad(v, ((0, 0), (0, pad), (0, 0))) if pad else v

    cols, lrow = _per_head_forms(whole(dt.astype(_F32)), a_log, q,
                                 heads_a_step(n_heads, width))
    skip = jnp.repeat(d_skip.astype(_F32), width)[None, :]
    y = _scan(whole(x), cols, lrow, whole(b.astype(kind)),
              whole(c.astype(kind)), skip, width)
    return y[:, :seq] if pad else y


def chunked_ssd_scan(x, dt, a_log, b, c, d_skip, *, chunk: int = CHUNK):
    """The same chunked algorithm in `jnp`, float32 throughout: a `lax.scan`
    over the chunks with the state carried, the kernels' oracle."""
    batch, seq, channels = x.shape
    n_heads = dt.shape[-1]
    width = channels // n_heads
    q = min(_chunk_size(chunk), -(-seq // _SUBLANES) * _SUBLANES)
    n = chunks_of(seq, q)
    pad = n * q - seq

    def chunks(v):
        v = jnp.pad(v.astype(_F32), ((0, 0), (0, pad)) + ((0, 0),) * (
            v.ndim - 2))
        return jnp.moveaxis(v.reshape(batch, n, q, *v.shape[2:]), 1, 0)

    x4 = x.reshape(batch, seq, n_heads, width)
    rate = -jnp.exp(a_log.astype(_F32))
    upto = jnp.tril(jnp.ones((q, q), bool))

    def one_chunk(state, xs):       # state: (B, H, P, N)
        xc, dtc, bc, cc = xs
        l = jnp.cumsum(rate * dtc, axis=1)                     # (B, Q, H)
        decay = jnp.exp(jnp.where(
            upto[None, :, :, None], l[:, :, None] - l[:, None, :], -jnp.inf))
        m = jnp.einsum("bin,bjn->bij", cc, bc)[..., None] * decay
        xdt = xc * dtc[..., None]
        y = jnp.einsum("bijh,bjhp->bihp", m, xdt) \
            + jnp.exp(l)[..., None] * jnp.einsum("bin,bhpn->bihp", cc, state)
        last = l[:, -1]                                        # (B, H)
        state = jnp.exp(last)[..., None, None] * state + jnp.einsum(
            "bjhp,bjn->bhpn", xdt * jnp.exp(last[:, None] - l)[..., None],
            bc)
        return state, y

    with jax.default_matmul_precision("highest"):
        _, y = lax.scan(one_chunk, jnp.zeros(
            (batch, n_heads, width, b.shape[-1]), _F32),
            (chunks(x4), chunks(dt), chunks(b), chunks(c)))
    y = jnp.moveaxis(y, 0, 1).reshape(batch, n * q, n_heads, width)[:, :seq]
    y = y + d_skip.astype(_F32)[:, None] * x4.astype(_F32)
    return y.reshape(x.shape).astype(x.dtype)


def recurrent_ssd_scan(x, dt, a_log, b, c, d_skip):
    """The same outputs one token at a time, all in float32: the recurrence
    as it is written at the top, for the tests of the chunked form."""
    batch, seq, channels = x.shape
    n_heads = dt.shape[-1]
    x4 = x.astype(_F32).reshape(batch, seq, n_heads, channels // n_heads)
    rate = -jnp.exp(a_log.astype(_F32))

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs
        state = jnp.exp(rate * dt_t)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t)

    per_token = tuple(jnp.moveaxis(v.astype(_F32), 1, 0)
                      for v in (x4, dt, b, c))
    with jax.default_matmul_precision("highest"):
        _, y = lax.scan(step, jnp.zeros(x4.shape[:1] + x4.shape[2:]
                                        + b.shape[-1:], _F32), per_token)
    y = jnp.moveaxis(y, 0, 1) + d_skip.astype(_F32)[:, None] * x4
    return y.reshape(x.shape).astype(x.dtype)
