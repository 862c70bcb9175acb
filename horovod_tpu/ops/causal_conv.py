"""The short depthwise causal convolution in front of the gated delta rule,
with the SiLU after it and the L2 normalisation of queries and keys, as one
pass over the tokens: a Pallas TPU kernel forward and one backward.

For u: (B, H, S, d) in the layout `ops/gated_delta.py` takes and taps
w: (H, d, K),

    p_t = sum_j w_j u_(t-K+1+j)        zeros before the sequence's start
    y_t = SiLU(p_t)
    y_t <- l2_scale y_t / sqrt(sum_d y_t^2 + 1e-6)     with `l2_scale` set

(`reference_causal_conv_silu` below: the same in `jnp`, for the tests and
`chip_smoke.py`). Everything between reading u and writing y is float32 in
registers; y is rounded once, to u's type. No float32 array and no padded
copy of u reaches HBM.

Both kernels run a grid of (batch x blocks of heads, token tiles) and go
through a tile strip by strip (`strip_rows`: 128 rows at one lane tile of
width, 64 at two; twice that with the norm), a strip's chain from u to y
held in registers. A strip is loaded with the `_HALO` rows before it, rolled
along the sublanes once a tap, and the halo rows dropped; the rows before a
tile's first strip are a second, `_HALO`-row block of u that the pipeline
fetches beside the tile (zeros at the sequence's start), so the forward
kernel carries nothing from tile to tile.

* `_forward_kernel`: a tile of u read once, y written once.
* `_backward_kernel` walks the tiles, and in a tile the strips, from the
  last to the first. It recomputes a strip's p from u (the residuals are u
  and w alone), takes dy through the norm and the SiLU to dp, and writes
  du_t = sum_j w_j dp_(t+K-1-j): the halo is on the later side, the first
  `_HALO` rows of dp of the strip after it, carried in a VMEM scratch from
  strip to strip and from tile to tile (zero after the sequence's end).
  dw_j = sum_t dp_t u_(t-K+1+j) accumulates in float32 in the output's
  block, which stays in VMEM over a head block's tiles, as eight partial
  sums a tap (one a sublane: whole-register adds); the wrapper adds the
  eight, and the batch entries. One read of u and dy, one write of du.

A length that is no multiple of the tile is padded with zero rows at the
end: they come after every real token, so they change no y, and their dy is
zero, so they add nothing to du or dw. Off the TPU the same kernels run in
the Pallas interpreter (`ops/_pallas.interpret`).
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops._pallas import pallas_call

#: tokens a grid step takes (a multiple of `_HALO`)
TILE = 1024
#: rows before a strip that are loaded with it (and rows of dp after one
#: that are carried to it): at least K - 1, and a whole packed bf16 register
_HALO = 16
#: rows x lane tiles of a strip without the norm: sixteen float32 registers
#: an array; with the norm twice that, so that more rows' lane reductions
#: are in flight (the fastest of 64 to 512 on the chip, docs/kernels.md)
_STRIP = 128
#: what a grid step's block of u may take of VMEM (the kernels hold four to
#: six such blocks, double-buffered): it decides the heads a grid step takes
_BLOCK_BYTES = 5 * 2 ** 18
_LANES = 128
_EPS = 1e-6
_F32 = jnp.float32


def _lane_tiles(width: int) -> int:
    return -(-width // _LANES)


def strip_rows(width: int, tile: int, normed: bool) -> int:
    """Rows of a strip: `_STRIP` (twice with the norm) over the width's lane
    tiles, at least the halo's, halved until it divides `tile`."""
    rows = max(_HALO, _STRIP * (2 if normed else 1) // _lane_tiles(width))
    while tile % rows:
        rows //= 2
    return rows


def tile_of(seq: int) -> int:
    """Tokens a grid step takes of a sequence of `seq`: `TILE`, or for a
    shorter sequence all of it, in whole `_STRIP`s."""
    return min(TILE, -(-seq // _STRIP) * _STRIP)


def heads_a_step(heads: int, width: int, tile: int, itemsize: int = 2) -> int:
    """Heads a grid step takes: the largest divisor of `heads` whose block
    of u (the width padded to whole lane tiles) fits `_BLOCK_BYTES`."""
    a_head = tile * _lane_tiles(width) * _LANES * itemsize
    return max(h for h in range(1, heads + 1)
               if heads % h == 0 and (h == 1 or h * a_head <= _BLOCK_BYTES))


def least_bytes(width: int, itemsize: int = 2) -> tuple[int, int]:
    """Bytes a token and head a forward pass (u in, y out) and a backward
    pass (u and dy in, du out) move at the least, the width padded to whole
    lane tiles as the arrays lie in HBM."""
    row = _lane_tiles(width) * _LANES * itemsize
    return 2 * row, 3 * row


# --------------------------------------------------------------------------
# What the kernels share
# --------------------------------------------------------------------------

def _taps(w_ref, h):
    """The taps of head h as K rows (1, d) that broadcast over a strip."""
    w = w_ref[h].astype(_F32)
    return [w[j:j + 1] for j in range(w.shape[0])]


def _shifted(x, taps):
    """x: (`_HALO` + rows, d) float32, a strip behind its halo. For tap j
    the strip's rows of u_(t-K+1+j): x rolled down the sublanes by K-1-j,
    the halo's rows (where the roll wraps) dropped."""
    last = len(taps) - 1
    return [(x if j == last else pltpu.roll(x, last - j, 0))[_HALO:]
            for j in range(len(taps))]


def _each_strip(strip, before_ref, u_ref, h, at_start, rows: int,
                reverse: bool = False):
    """`strip(x, r0)` for every strip of `rows` rows of head h's tile, x the
    strip behind its halo in float32 and r0 its first row; `reverse` from
    the last strip to the first. Strip 0's halo is the block beside the
    tile, zeros where the tile is the sequence's first (`at_start`); every
    other strip's is in the tile."""
    n = u_ref.shape[1] // rows

    def first():
        halo = jnp.where(at_start, 0.0, before_ref[h].astype(_F32))
        strip(jnp.concatenate([halo, u_ref[h, :rows].astype(_F32)], axis=0),
              0)

    def later(i, _):
        r0 = pl.multiple_of((n - 1 - i if reverse else i + 1) * rows, rows)
        strip(u_ref[h, pl.ds(r0 - _HALO, _HALO + rows)].astype(_F32), r0)

    if not reverse:
        first()
    if n > 1:
        lax.fori_loop(0, n - 1, later, None)
    if reverse:
        first()


def _activated(p, l2_scale):
    """SiLU of p and its sigmoid; with `l2_scale` also the factor each row
    of the SiLU is multiplied by and the row's inverse norm."""
    sig = jax.nn.sigmoid(p)
    s = p * sig
    if l2_scale is None:
        return s, sig, None, None
    inv = lax.rsqrt(jnp.sum(s * s, axis=-1, keepdims=True) + _EPS)
    return s, sig, inv * l2_scale, inv


# --------------------------------------------------------------------------
# The kernels
# --------------------------------------------------------------------------

def _forward_kernel(before_ref, u_ref, w_ref, y_ref, *, l2_scale, rows):
    at_start = pl.program_id(1) == 0
    for h in range(u_ref.shape[0]):
        taps = _taps(w_ref, h)

        def strip(x, r0):
            p = sum(t * xs for t, xs in zip(taps, _shifted(x, taps)))
            s, _, factor, _ = _activated(p, l2_scale)
            y = s if factor is None else s * factor
            y_ref[h, pl.ds(r0, rows)] = y.astype(y_ref.dtype)

        _each_strip(strip, before_ref, u_ref, h, at_start, rows)


def _backward_kernel(before_ref, u_ref, w_ref, dy_ref, du_ref, dw_ref, after,
                     *, l2_scale, rows):
    width = u_ref.shape[2]
    at_start = pl.program_id(1) == pl.num_programs(1) - 1

    @pl.when(pl.program_id(1) == 0)
    def _last_tile():
        after[...] = jnp.zeros_like(after)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    for h in range(u_ref.shape[0]):
        taps = _taps(w_ref, h)
        last = len(taps) - 1

        def strip(x, r0):
            shifted = _shifted(x, taps)
            p = sum(t * xs for t, xs in zip(taps, shifted))
            s, sig, factor, inv = _activated(p, l2_scale)
            ds = dy_ref[h, pl.ds(r0, rows)].astype(_F32)
            if factor is not None:
                along = jnp.sum(ds * s, axis=-1, keepdims=True)
                ds = factor * (ds - s * (inv * inv * along))
            dp = ds * sig * (1.0 + p * (1.0 - sig))
            for j, xs in enumerate(shifted):
                part = (dp * xs).reshape(rows // 8, 8, width).sum(axis=0)
                dw_ref[h, 8 * j:8 * j + 8] += part
            # dp behind the first rows of the strip after it: tap j takes
            # dp_(t+K-1-j), the rows rolled up, the wrapped ones dropped
            ext = jnp.concatenate([dp, after[h]], axis=0)
            du = sum(t * (ext if j == last else pltpu.roll(
                ext, rows + _HALO - (last - j), 0))[:rows]
                for j, t in enumerate(taps))
            du_ref[h, pl.ds(r0, rows)] = du.astype(du_ref.dtype)
            after[h] = dp[:_HALO]

        _each_strip(strip, before_ref, u_ref, h, at_start, rows,
                    reverse=True)


# --------------------------------------------------------------------------
# The calls
# --------------------------------------------------------------------------

_PARAMS = {"compiler_params": pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))}


def _plan(u, normed: bool, reverse: bool):
    """For u: (B, H, S, d), S whole tiles: the grid (batch x blocks of heads,
    tiles of tokens), the heads and the strip's rows of a grid step, and the
    block specs: a tile, the `_HALO` rows before it (the sequence's first at
    tile 0, which the kernels take for zeros), the head block's taps
    (H, K, d) and its partial sums of dw (B, H, 8 K, d). `reverse` walks the
    tiles from the last."""
    batch, n_heads, seq, width = u.shape
    tile = tile_of(seq)
    heads = heads_a_step(n_heads, width, tile, u.dtype.itemsize)
    blocks, n = n_heads // heads, seq // tile

    def at(j):
        return n - 1 - j if reverse else j

    def taps(k):
        return pl.BlockSpec((heads, k, width), lambda i, j: (
            i % blocks, 0, 0))

    def sums(k):
        return pl.BlockSpec((None, heads, 8 * k, width), lambda i, j: (
            i // blocks, i % blocks, 0, 0))

    return types.SimpleNamespace(
        grid=(batch * blocks, n), heads=heads,
        rows=strip_rows(width, tile, normed),
        taps=taps, sums=sums,
        tiles=pl.BlockSpec((None, heads, tile, width), lambda i, j: (
            i // blocks, i % blocks, at(j), 0)),
        before=pl.BlockSpec((None, heads, _HALO, width), lambda i, j: (
            i // blocks, i % blocks,
            jnp.maximum(at(j) * (tile // _HALO) - 1, 0), 0)))


def _forward(u, w, l2_scale):
    plan = _plan(u, l2_scale is not None, reverse=False)
    return pallas_call(
        functools.partial(_forward_kernel, l2_scale=l2_scale, rows=plan.rows),
        grid=plan.grid,
        in_specs=[plan.before, plan.tiles, plan.taps(w.shape[1])],
        out_specs=plan.tiles,
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype), **_PARAMS)(u, u, w)


def _backward(u, w, dy, l2_scale):
    batch, n_heads, _, width = u.shape
    plan, k = _plan(u, l2_scale is not None, reverse=True), w.shape[1]
    du, dw = pallas_call(
        functools.partial(_backward_kernel, l2_scale=l2_scale,
                          rows=plan.rows),
        grid=plan.grid,
        in_specs=[plan.before, plan.tiles, plan.taps(k), plan.tiles],
        out_specs=[plan.tiles, plan.sums(k)],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct((batch, n_heads, 8 * k, width),
                                        _F32)],
        scratch_shapes=[pltpu.VMEM((plan.heads, _HALO, width), _F32)],
        **_PARAMS)(u, u, w, dy)
    return du, dw.reshape(batch, n_heads, k, 8, width).sum(axis=(0, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv(u, w, l2_scale):
    return _forward(u, w, l2_scale)


def _conv_fwd(u, w, l2_scale):
    return _forward(u, w, l2_scale), (u, w)


def _conv_bwd(l2_scale, saved, dy):
    return _backward(*saved, dy, l2_scale)


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv_silu(u, w, *, l2_scale=None):
    """y_t = SiLU(sum_j w_j u_(t-K+1+j)) over the tokens of u: (B, H, S, d)
    with the taps w: (H, d, K), zeros before the sequence's start; with
    `l2_scale` a number, each y_t is then divided by its L2 norm over d (eps
    1e-6 under the root) and multiplied by it. float32 inside, u's type
    out, rounded once."""
    if w.shape[-1] > _HALO + 1:
        raise ValueError(f"causal_conv_silu: {w.shape[-1]} taps (at most "
                         f"{_HALO + 1})")
    dt = u.dtype
    if dt not in (jnp.bfloat16, _F32):
        return causal_conv_silu(u.astype(_F32), w,
                                l2_scale=l2_scale).astype(dt)
    seq = u.shape[2]
    pad = -seq % tile_of(seq)
    if pad:
        u = jnp.pad(u, ((0, 0), (0, 0), (0, pad), (0, 0)))
    taps = jnp.swapaxes(w, 1, 2).astype(_F32)
    scale = None if l2_scale is None else float(l2_scale)
    return _conv(u, taps, scale)[:, :, :seq]


def reference_causal_conv_silu(u, w, *, l2_scale=None):
    """The same in plain `jnp`, all in float32: shifted slices of a padded
    copy multiplied by the taps and summed, for the tests of the kernels."""
    taps, seq = w.shape[-1], u.shape[2]
    padded = jnp.pad(u.astype(_F32), ((0, 0), (0, 0), (taps - 1, 0), (0, 0)))
    wf = w.astype(_F32)
    y = jax.nn.silu(sum(padded[:, :, j:j + seq] * wf[None, :, None, :, j]
                        for j in range(taps)))
    if l2_scale is not None:
        y = y * (l2_scale * lax.rsqrt(
            jnp.sum(jnp.square(y), axis=-1, keepdims=True) + _EPS))
    return y.astype(u.dtype)
