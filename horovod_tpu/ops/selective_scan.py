"""The selective state-space recurrence (Mamba-1, arXiv:2312.00752) over a
sequence, as Pallas TPU kernels (forward + backward).

Per channel e and state n, with an input c_t, an input-dependent step
delta_t > 0, a decay rate A[e, n] < 0, an input map B_t[n] and an output map
C_t[n], a float32 state that starts at zero goes through

    s_t[e, n] = exp(delta_t[e] A[e, n]) s_(t-1)[e, n]
                + delta_t[e] c_t[e] B_t[n]
    y_t[e]    = sum_n s_t[e, n] C_t[n] + D[e] c_t[e]

(`reference_selective_scan` below: one token at a time in `jnp`, for the
tests and `chip_smoke.py`). The decay differs by channel AND state, so no
chunk of tokens folds into a matrix product as the gated delta rule's does
(`ops/gated_delta.py`): the work is elementwise, 16 states a (token,
channel), on the vector unit.

Both kernels hold the channels on the lanes and the N states on the
sublanes (N = 16: two float32 registers a 128-channel block), and run a grid
of (batch, blocks of channels, tiles of tokens) that walks a channel block's
tiles in sequence (the grid's last, "arbitrary" axis) with the state
resident in VMEM: a scratch cleared at a sequence's first tile. Inside a
tile the channel block is walked `sub` channels at a time (all 1,024 in the
forward kernel, 512 in the backward), the state of those channels carried
in registers through a loop over the tile's tokens, so several 128-channel
chains are in flight and hide the recurrence's latency. B_t and C_t multiply along the sublanes: the wrapper hands them
over broadcast along 128 lanes, (B, S, N, 128) in the inputs' type, so that
no lane broadcast is left to the kernel.

* `_forward_kernel`: a tile of c, delta, B, C read once, y written once;
  with `save` (the pass a gradient is asked of) also the state each tile
  starts from, float32, (S / chunk, N, E) a sequence: the residuals are the
  inputs and these, never the (S, E, N) states (2.7 GB a layer at 8,192
  tokens of 5,120 channels).
* `_backward_kernel` walks the tiles from the last. It recomputes a tile's
  states from its entry state into a VMEM scratch, then walks the tokens
  back with the state's cotangent g carried (g_t = dy_t C_t + a_(t+1)
  g_(t+1)): dc, ddelta a token; dA accumulated over the tokens in its
  output block, which stays in VMEM over a channel block's tiles; dB_t[n] =
  sum_e g_t delta_t c_t and dC_t[n] = sum_e dy_t s_t are sums over the
  lanes, written as 128 partial sums a (token, state) and added up by the
  wrapper, in `jnp`, with the channel blocks'. dD = sum dy c is the
  wrapper's too.

Precision: the state, its cotangent, every decay and every sum are float32;
the operands are read in the type they come in (bf16 in the benchmark's
cell, delta in float32) and the results rounded once, to that type.
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

#: tokens a grid step takes, and the distance between saved states
CHUNK = 128
_LANES = 128
#: channels a grid step takes (the largest that divides E), and of those the
#: channels whose state a token loop carries in registers: the more chains in
#: flight the better (forward alone at the cell's shape 9.19 ms at 128, 5.43
#: at 256, 3.25 at 512, 2.21 at 1,024; the backward's loop holds three times
#: the values a chain and gains nothing past 512: docs/kernels.md)
_BLOCKS = (1024, 512, 256, 128)
_SUBS = {"forward": (1024, 512, 256, 128), "backward": (512, 256, 128)}
_F32 = jnp.float32
_VMEM = {"compiler_params": pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=40 * 2 ** 20)}


def _first_divisor(n: int, candidates) -> int:
    return next((c for c in candidates if n % c == 0), n)


def chunk_of(seq: int) -> int:
    """Tokens a grid step takes of a sequence of `seq`: `CHUNK` (a shorter
    sequence is one chunk). A sequence that is no whole number of chunks is
    refused."""
    chunk = min(CHUNK, seq)
    if seq % chunk:
        raise ValueError(f"selective_scan: a sequence of {seq} tokens is no "
                         f"whole number of chunks of {chunk}")
    return chunk


# --------------------------------------------------------------------------
# What the kernels share
# --------------------------------------------------------------------------

def _wide(x, sub: int):
    """x: (N, lanes), lane-replicated, at `sub` lanes: whole registers
    repeated, which moves nothing across lanes."""
    reps = sub // x.shape[1]
    return x if reps == 1 else pltpu.repeat(x, reps, axis=1)


def _folded(x, lanes: int):
    """x: (N, sub) as the sum of its `lanes`-wide pieces: whole-register
    adds."""
    return sum(x[:, i:i + lanes] for i in range(0, x.shape[1], lanes))


def _row(ref, t):
    return ref[pl.ds(t, 1), :]


def _advance(s, a, c_t, dl_t, b_t):
    """(the decay a_t, the state after token t)."""
    decay = jnp.exp(dl_t * a)
    return decay, decay * s + (dl_t * c_t) * b_t


# --------------------------------------------------------------------------
# The kernels
# --------------------------------------------------------------------------

def _forward_kernel(c_ref, dl_ref, a_ref, b_ref, o_ref, d_ref, y_ref, *rest,
                    sub, save):
    s0_ref = rest[0] if save else None
    state, c32, dl32, y32 = rest[save:]
    tokens, block = c_ref.shape

    @pl.when(pl.program_id(2) == 0)
    def _first_tile():
        state[...] = jnp.zeros_like(state)

    if save:
        s0_ref[...] = state[...]
    for lo in range(0, block, sub):
        lanes = slice(lo, lo + sub)
        c32[...] = c_ref[:, lanes].astype(_F32)
        dl32[...] = dl_ref[:, lanes].astype(_F32)
        a, d = a_ref[:, lanes], d_ref[:, lanes]

        def token(t, s):
            c_t, dl_t = _row(c32, t), _row(dl32, t)
            _, s = _advance(s, a, c_t, dl_t,
                            _wide(b_ref[t].astype(_F32), sub))
            y32[pl.ds(t, 1), :] = jnp.sum(
                s * _wide(o_ref[t].astype(_F32), sub), axis=0,
                keepdims=True) + d * c_t
            return s

        state[:, lanes] = lax.fori_loop(0, tokens, token, state[:, lanes])
        y_ref[:, lanes] = y32[...].astype(y_ref.dtype)


def _backward_kernel(c_ref, dl_ref, a_ref, b_ref, o_ref, d_ref, dy_ref,
                     s0_ref, dc_ref, ddl_ref, da_ref, db_ref, do_ref,
                     carry, states, c32, dl32, dy32, dc32, ddl32, *, sub):
    tokens, block = c_ref.shape
    lanes_out = db_ref.shape[-1]

    @pl.when(pl.program_id(2) == 0)
    def _last_tile():
        carry[...] = jnp.zeros_like(carry)
        da_ref[...] = jnp.zeros_like(da_ref)

    for lo in range(0, block, sub):
        lanes = slice(lo, lo + sub)
        c32[...] = c_ref[:, lanes].astype(_F32)
        dl32[...] = dl_ref[:, lanes].astype(_F32)
        dy32[...] = dy_ref[:, lanes].astype(_F32)
        a, d = a_ref[:, lanes], d_ref[:, lanes]

        # the tile's states again, from the state it started from
        states[0] = s0_ref[:, lanes]

        def again(t, s):
            _, s = _advance(s, a, _row(c32, t), _row(dl32, t),
                            _wide(b_ref[t].astype(_F32), sub))
            states[t + 1] = s
            return s

        lax.fori_loop(0, tokens, again, states[0])

        def token(i, loop):
            g_after, da = loop       # a_(t+1) g_(t+1); dA so far
            t = tokens - 1 - i
            c_t, dl_t, dy_t = _row(c32, t), _row(dl32, t), _row(dy32, t)
            b_t = _wide(b_ref[t].astype(_F32), sub)
            g = dy_t * _wide(o_ref[t].astype(_F32), sub) + g_after
            decay = jnp.exp(dl_t * a)
            through = g * states[t] * decay        # g s_(t-1) a_t
            to_c = _folded(dy_t * states[t + 1], lanes_out)
            to_b = _folded(g * (dl_t * c_t), lanes_out)
            if lo:
                to_c, to_b = do_ref[t] + to_c, db_ref[t] + to_b
            do_ref[t], db_ref[t] = to_c, to_b
            dx = jnp.sum(g * b_t, axis=0, keepdims=True)
            ddl32[pl.ds(t, 1), :] = jnp.sum(
                through * a, axis=0, keepdims=True) + dx * c_t
            dc32[pl.ds(t, 1), :] = dx * dl_t + d * dy_t
            return decay * g, da + through * dl_t

        g, da = lax.fori_loop(0, tokens, token,
                              (carry[:, lanes], jnp.zeros_like(a)))
        carry[:, lanes] = g
        da_ref[:, lanes] += da
        dc_ref[:, lanes] = dc32[...].astype(dc_ref.dtype)
        ddl_ref[:, lanes] = ddl32[...].astype(ddl_ref.dtype)


# --------------------------------------------------------------------------
# The calls
# --------------------------------------------------------------------------

def _plan(c, states: int, chunk: int, which: str, reverse: bool = False):
    """For c: (B, S, E): the grid (batch, blocks of channels, tiles of
    tokens), the channels a grid step takes and walks together, the lanes B
    and C come replicated at, and the block specs by what they follow."""
    batch, seq, channels = c.shape
    block = _first_divisor(channels, _BLOCKS)
    sub = _first_divisor(block, _SUBS[which])
    lanes = min(_LANES, sub)
    n = seq // chunk

    def at(t):
        return n - 1 - t if reverse else t

    return dict(
        grid=(batch, channels // block, n), block=block, sub=sub,
        lanes=lanes, n=n,
        tokens=pl.BlockSpec((None, chunk, block),
                            lambda b, e, t: (b, at(t), e)),
        maps=pl.BlockSpec((None, chunk, states, lanes),
                          lambda b, e, t: (b, at(t), 0, 0)),
        rates=pl.BlockSpec((states, block), lambda b, e, t: (0, e)),
        skip=pl.BlockSpec((1, block), lambda b, e, t: (0, e)),
        entry=pl.BlockSpec((None, None, states, block),
                           lambda b, e, t: (b, at(t), 0, e)),
        sums=pl.BlockSpec((None, states, block), lambda b, e, t: (b, 0, e)),
        partial=pl.BlockSpec((None, None, chunk, states, lanes),
                             lambda b, e, t: (b, e, at(t), 0, 0)))


def _replicated(x, lanes: int):
    """x: (B, S, N) along `lanes` lanes: (B, S, N, lanes)."""
    return jnp.broadcast_to(x[..., None], x.shape + (lanes,))


def _forward(c, delta, a, b_in, c_out, d_skip, *, chunk: int, save: bool):
    batch, seq, channels = c.shape
    states = a.shape[0]
    p = _plan(c, states, chunk, "forward")
    out_specs = [p["tokens"]]
    out_shape = [jax.ShapeDtypeStruct(c.shape, c.dtype)]
    if save:
        out_specs.append(p["entry"])
        out_shape.append(jax.ShapeDtypeStruct(
            (batch, p["n"], states, channels), _F32))
    tile = pltpu.VMEM((chunk, p["sub"]), _F32)
    out = pallas_call(
        functools.partial(_forward_kernel, sub=p["sub"], save=save),
        grid=p["grid"],
        in_specs=[p["tokens"], p["tokens"], p["rates"], p["maps"], p["maps"],
                  p["skip"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((states, p["block"]), _F32), tile, tile,
                        tile],
        **_VMEM)(c, delta, a, _replicated(b_in, p["lanes"]),
                 _replicated(c_out, p["lanes"]), d_skip)
    return out if save else out[0]


def _backward(c, delta, a, b_in, c_out, d_skip, s0, dy, *, chunk: int):
    batch, seq, channels = c.shape
    states = a.shape[0]
    p = _plan(c, states, chunk, "backward", reverse=True)
    blocks = channels // p["block"]
    partial = jax.ShapeDtypeStruct(
        (batch, blocks, seq, states, p["lanes"]), _F32)
    tile = pltpu.VMEM((chunk, p["sub"]), _F32)
    dc, ddelta, da, db, do = pallas_call(
        functools.partial(_backward_kernel, sub=p["sub"]),
        grid=p["grid"],
        in_specs=[p["tokens"], p["tokens"], p["rates"], p["maps"], p["maps"],
                  p["skip"], p["tokens"], p["entry"]],
        out_specs=[p["tokens"], p["tokens"], p["sums"], p["partial"],
                   p["partial"]],
        out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype),
                   jax.ShapeDtypeStruct(delta.shape, delta.dtype),
                   jax.ShapeDtypeStruct((batch, states, channels), _F32),
                   partial, partial],
        scratch_shapes=[pltpu.VMEM((states, p["block"]), _F32),
                        pltpu.VMEM((chunk + 1, states, p["sub"]), _F32),
                        tile, tile, tile, tile, tile],
        **_VMEM)(c, delta, a, _replicated(b_in, p["lanes"]),
                 _replicated(c_out, p["lanes"]), d_skip, dy, s0)
    return (dc, ddelta, da.sum(axis=0), db.sum(axis=(1, 4)),
            do.sum(axis=(1, 4)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(c, delta, a, b_in, c_out, d_skip, chunk):
    return _forward(c, delta, a, b_in, c_out, d_skip, chunk=chunk,
                    save=False)


def _scan_fwd(c, delta, a, b_in, c_out, d_skip, chunk):
    y, s0 = _forward(c, delta, a, b_in, c_out, d_skip, chunk=chunk,
                     save=True)
    return y, (c, delta, a, b_in, c_out, d_skip, s0)


def _scan_bwd(chunk, saved, dy):
    c, delta, a, b_in, c_out, d_skip, s0 = saved
    dc, ddelta, da, db, do = _backward(*saved[:6], s0, dy, chunk=chunk)
    dd = jnp.sum(dy.astype(_F32) * c.astype(_F32), axis=(0, 1))[None]
    return (dc, ddelta, da, db.astype(b_in.dtype), do.astype(c_out.dtype),
            dd)


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(c, delta, A, B, C, D_skip):
    """y of the recurrence above for c, delta: (B, S, E), A: (E, N),
    B, C: (B, S, N) and D_skip: (E,); (B, S, E) in c's type. The state is
    float32 and starts at zero; `CHUNK` tokens lie between the states a
    backward pass starts again from, and S is a whole number of them.
    Differentiable in all six."""
    a = jnp.swapaxes(A, 0, 1).astype(_F32)
    return _scan(c, delta, a, B, C, D_skip.astype(_F32)[None],
                 chunk_of(c.shape[1]))


def reference_selective_scan(c, delta, A, B, C, D_skip):
    """The same one token at a time in plain `jnp`, all in float32, for the
    tests of the kernels; returns float32."""
    c, delta, A, B, C, D_skip = (x.astype(_F32) for x in
                                 (c, delta, A, B, C, D_skip))

    def step(s, xs):
        c_t, dl_t, b_t, o_t = xs
        s = jnp.exp(dl_t[..., None] * A) * s \
            + (dl_t * c_t)[..., None] * b_t[:, None, :]
        return s, jnp.einsum("ben,bn->be", s, o_t) + D_skip * c_t

    per_token = tuple(jnp.moveaxis(x, 1, 0) for x in (c, delta, B, C))
    _, y = lax.scan(step, jnp.zeros(c.shape[::2] + A.shape[1:], _F32),
                    per_token)
    return jnp.moveaxis(y, 0, 1)
