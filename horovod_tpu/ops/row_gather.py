"""The expert layer's two row movers, each the other's backward pass, the
Pallas TPU kernel that sums rows where most of them do not count, and the
embedding's lookup, whose backward pass is a sort, a product and a gather.

On one rank the expert layer (`parallel/moe.py`) moves rows twice a layer:

    take   rows[i] = x[index[i]]               for i < n_valid, zero after
    sum    out[t]  = sum_c x[back[t*k + c]]    over the c with back < n_valid

`take_rows` and `sum_rows` are the two behind `custom_vjp`s: the backward
pass of a take is a sum of the cotangent's rows and the other way round, so
neither is ever a scatter-add. `reference_take` and `reference_sum` are their
`jnp` forms.

Where a chip holds a share of the experts (`n_valid` given) three quarters
(`smallthinker-1chip`) or seven eighths (`dsv2lite-1chip`) of a sum's T*k
entries do not count, and the `jnp` form gathers every one of them and masks
afterwards: 7.4 ms for 98,304 rows of which 24,716 count. There a sum is
`_sum_kernel`, which copies only the rows that count (`moved_share`), in
three steps:

1. `_entries`, plain `jnp`, no sort: which entries count (0 <= back <
   n_valid, and inside the array) and how many of each token's. A token's
   rows that count take its first slots (c ascending, so the float32 sum
   adds in the `jnp` form's order).
2. `_row_form`, a Pallas kernel: the source's rows before `n_valid` written
   once as rows of 32-bit words a copy can move (below). One pass.
3. `_sum_kernel`: a grid over tiles of tokens, one step ahead of itself.
   Step s starts one copy a counted row of tile s, HBM to a VMEM buffer (slot
   s % 2), all of a tile's in flight at once on one DMA semaphore; then it
   waits for the copies of tile s - 1, started a step earlier, and adds that
   tile's rows up into the result block, a strip of tokens at a time in
   registers, only as many places deep as the strip's fullest token.

Every entry's row is compared with the limit again inside the kernel, before
its copy is made: `back` may point anywhere (the `jnp` form clamps; a copy
from past an array's end faults the chip), and an entry that fails issues no
copy and adds nothing.

The 32-bit row. A copy moves whole (8, 128) tiles of 32-bit words: Mosaic
refuses a slice of fewer sublanes, in HBM as in VMEM, and a bf16 array holds
two rows to a word besides. So the source is rewritten as (rows * pieces,
128) uint32: row r is the `pieces` consecutive 128-word rows from r * pieces
on, `pieces` a multiple of 8 (one 4 KB tile for 2,048 bf16, two for 2,560:
the second is 3/8 used). Word j of a bf16 row holds columns j (low half) and
D / 2 + j (high half), so `word << 16` and `word & 0xffff0000` are the two
columns' values as float32 bits, which is what the sum wants, and both are
whole lane tiles of the result. In VMEM the buffer has the same form, and a
strided load (one sublane a row, `pieces` apart) brings piece j of eight
tokens' rows into one register.

The Mosaic calls take (2 operands, 1 result) and (5, 1): the benchmark tells
kernels by such counts (`benchmark/harness/scopes.py`,
`benchmark/layer_metrics/flash_roofline.py`) and these are none of theirs;
`tests/test_kernels_tpu_aot.py` holds them to that.

What keeps the `jnp` form, chosen from the static shapes when the call is
traced (timings in docs/kernels.md): every take (a copy a row costs ~20 ns
to start and to wait for, and the compiler's gather of 49,152 rows from the
84 MB token array takes 0.39 ms inside the step, 8 ns a row; its free rows
come from zero rows behind the array, 0.11 ms of padding where a
`jnp.where` over the 252 MB result took 0.76-1.53); a sum where every entry
counts (`n_valid` None,
`olmoe-1chip`: 2.7 ms against the kernel's 3.6); widths that are no whole
lane tiles and element types other than bfloat16 and float32. Off the TPU
the kernels run in the Pallas interpreter (`ops/_pallas.interpret`).

The lookup. `lookup_rows(table, ids)` is (`table[ids]`, `table`), the third
mover: the lookup's transpose adds the cotangent's T rows into the table's
V, and the compiler's scatter-add of them is serial (15.8 ms for 16,384 rows
of 2,560 into 37,984 where the bytes take 0.34; its time follows the table's
rows and the width: docs/kernels.md). The backward pass here holds no
scatter and no kernel, in plain `jnp`:

1. `_lookup_plan`, from the ids alone, in the forward pass: the tokens sorted
   by id, T padded to whole chunks of `LOOKUP_CHUNK` and one place more with
   the id V, which sorts last. In a chunk the ids are sorted, so its distinct
   ids are numbered by a running count of the places where the id changes:
   a place's slot. A histogram of the ids (a product of two small one-hots of
   v // 256 and v % 256: 2 T V operations on the MXU, no scatter either) and
   two running sums over it say where every row's run ends and in which slot.
2. `_lookup_grad`, behind the cotangent: its rows gathered in the sorted
   order; a chunk's sums by slot as ONE batched product, onehot(slot)^T times
   the chunk's rows, (C x C) (C x D), accumulated in float32 and rounded once
   as it is written (a one-hot in bfloat16 is exact); a run crosses a chunk's
   edge only through the chunk's first slot, so each chunk's first and last
   slots are summed once more in float32 and a chunk's first slot takes what
   the earlier chunks' last slots hold of its id (a (chunks x chunks) 0/1
   product); then the table's gradient is one gather of V rows, row v from
   the slot where v's run ends, a row no token asks for from a slot that is
   always free. Exact for any multiplicity, and the work follows T + V.

Two things the scatter-add gave the compiler's scheduler are kept, because
three cells' compiled steps needed 2 to 4.6% more memory without them
(docs/kernels.md, PERF.md section 6): the lookup's part is added onto a
buffer that exists before it, behind `lax.optimization_barrier` (the
gradient of whatever read the table after the lookup, which is why the
table is handed back and a tied head reads that one; zeros otherwise), and
the gather of the V rows stands in a loop of one trip.

A cotangent row that is not finite reaches every slot of its chunk (0 x inf
in the product), where the scatter-add spoils its own row alone.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops import _pallas
from horovod_tpu.ops._pallas import pallas_call

#: tokens of a grid step at most (fewer where `_BUFFER_BYTES` says so)
TOKEN_TILE = 256
#: what the two slots of the copy buffer may take of VMEM
_BUFFER_BYTES = 16 * 2 ** 20
#: rows of a grid step of `_row_form`
_FORM_TILE = 256
#: tokens whose fullest decides how deep a strip is summed
_DEPTH_TOKENS = 16
_LANES = 128
_U32 = jnp.uint32
_I32 = jnp.int32
_HIGH_HALF = 0xFFFF0000
_WHOLE = 0xFFFFFFFF


# --------------------------------------------------------------------------
# The jnp forms
# --------------------------------------------------------------------------

def reference_take(x, index, n_valid=None):
    """`x[index]`, zero from row `n_valid` on: the `jnp` form of a take.
    The free rows are gathered from zero rows put behind `x`, so that no
    pass over the result is needed to clear them."""
    if n_valid is None:
        return x[index]
    padded = jnp.concatenate([x, jnp.zeros((8, x.shape[1]), x.dtype)])
    return padded[jnp.where(jnp.arange(index.size) < n_valid, index,
                            x.shape[0])]


def reference_sum(x, back, k, n_valid=None):
    """Row t the float32 sum of the k rows x[back[t*k:(t+1)*k]], those at
    or past `n_valid` left out, rounded once: the `jnp` form of a sum."""
    if n_valid is None:
        picked = x[back]
    else:
        picked = jnp.where((back < n_valid)[:, None],
                           x[jnp.minimum(back, x.shape[0] - 1)],
                           jnp.zeros((), x.dtype))
    return jnp.sum(picked.reshape(-1, k, x.shape[-1]), axis=1,
                   dtype=jnp.promote_types(x.dtype, jnp.float32)
                   ).astype(x.dtype)


def moved_share(back: Sequence[int], n_valid: Optional[int] = None) -> float:
    """Rows the kernel copies over rows the `jnp` form gathers, for a sum's
    concrete `back`: the entries before `n_valid` over all of them (0.25 for
    the combine of a chip that holds 16 of 64 experts at an even load, 0.125
    for 8 of 64, 1.0 where every pair is held)."""
    if n_valid is None:
        return 1.0
    return sum(1 for i in back if 0 <= i < n_valid) / len(back)


# --------------------------------------------------------------------------
# The kernels
# --------------------------------------------------------------------------

def supported(x: jax.Array) -> bool:
    """Whether `_sum_kernel` moves rows like `x`'s: whole lane tiles of
    bfloat16 (two to a word) or float32."""
    lanes = {jnp.dtype(jnp.bfloat16): 2 * _LANES,
             jnp.dtype(jnp.float32): _LANES}.get(x.dtype)
    return bool(lanes) and x.ndim == 2 and x.shape[1] % lanes == 0 \
        and x.shape[0] > 0


def _pieces(x) -> int:
    """128-word rows a row of `x` takes in its row form: its 32-bit words
    (two bfloat16 a word) in whole sublane tiles."""
    words = x.shape[1] * jnp.dtype(x.dtype).itemsize // 4
    return -(-words // (8 * _LANES)) * 8


def _tile(n_tokens: int, k: int, pieces: int) -> int:
    """Tokens of a grid step: what `_BUFFER_BYTES` holds two slots of, in
    whole packed registers, `TOKEN_TILE` at most."""
    fit = _BUFFER_BYTES // (2 * k * pieces * _LANES * 4)
    most = max(16, min(TOKEN_TILE, fit // 16 * 16))
    if n_tokens <= most:
        return -(-n_tokens // 16) * 16
    # the largest that divides the tokens, if that is no less than half
    whole = [t for t in range(most, most // 2, -16) if n_tokens % t == 0]
    return whole[0] if whole else most


def _f32_bits(values):
    """The float32 bits of `values` (a bfloat16's are its own, 16 up)."""
    return lax.bitcast_convert_type(values.astype(jnp.float32), _U32)


def _f32(words):
    return lax.bitcast_convert_type(words, jnp.float32)


def _row_form_kernel(limit_ref, x_ref, o_ref, *, tile, pieces, packed):
    """A tile of rows, each written as `pieces` rows of 128 words."""
    words = x_ref.shape[1] // 2 if packed else x_ref.shape[1]
    strip = 16 if packed else 8

    @pl.when(pl.program_id(0) * tile < limit_ref[0])
    def _tile():
        def rows_of(r, carry):
            t0 = pl.multiple_of(r * strip, strip)
            at = pl.ds(t0, strip)

            def lane_tile(j, carry):
                lanes = pl.multiple_of(j * _LANES, _LANES)
                word = _f32_bits(x_ref[at, pl.ds(lanes, _LANES)])
                if packed:
                    high = x_ref[at, pl.ds(lanes + words, _LANES)]
                    word = (word >> 16) | _f32_bits(high)
                for u in range(0, strip, 8):
                    o_ref[pl.ds((t0 + u) * pieces + j, 8, stride=pieces),
                          :] = word[u:u + 8]
                return carry

            return lax.fori_loop(0, words // _LANES, lane_tile, carry)

        lax.fori_loop(0, tile // strip, rows_of, 0)


def _row_form(x, n_valid):
    """`x` (rows, D) as (rows * pieces, 128) uint32, row r the `pieces`
    128-word rows from r * pieces on. One pass; the tiles from row `n_valid`
    on are left as they are, unread and unwritten."""
    n_rows, width = x.shape
    pieces = _pieces(x)
    tile = min(_FORM_TILE, -(-n_rows // 16) * 16)
    n_tiles = -(-n_rows // tile)
    limit = jnp.minimum(n_valid, n_rows).astype(_I32).reshape(1)

    def block(s, limit):   # a tile past the limit: the last one before it
        return jnp.minimum(s, jnp.maximum(limit[0] - 1, 0) // tile), 0

    return pallas_call(
        functools.partial(_row_form_kernel, tile=tile, pieces=pieces,
                          packed=x.dtype == jnp.bfloat16),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tiles,),
            in_specs=[pl.BlockSpec((tile, width), block)],
            out_specs=pl.BlockSpec((tile * pieces, _LANES), block),
        ),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tile * pieces, _LANES),
                                       _U32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(limit, x)


def _entries(back, k: int, limit):
    """What `_sum_kernel` walks, for `back` (tokens * k,) and the rows before
    `limit` that count: (rows, filled, deepest). `rows` (k * tokens,), slot
    j of token t at j * tokens + t: a token's counted rows in its first
    slots, in the order they have in `back` (so the float32 sum adds in the
    `jnp` form's order); `filled` (tokens,): how many they are; `deepest`:
    the most of each `_DEPTH_TOKENS` tokens. No sort (one of 98,304 keys
    takes the chip's compiler 20 s): a counted entry's slot is the number of
    counted ones before it, and slot j of a token is the one entry of its k
    that has it."""
    n_tokens = back.size // k
    by_slot = back.reshape(n_tokens, k).T.reshape(-1)
    filled = jnp.zeros((n_tokens,), _I32)
    rows = [jnp.zeros_like(filled) for _ in range(k)]
    for c in range(k):       # flat arrays, the tokens along the lanes
        entry = by_slot[c * n_tokens:(c + 1) * n_tokens]
        counts = jnp.logical_and(entry >= 0, entry < limit)
        for j in range(c + 1):
            rows[j] += jnp.where(jnp.logical_and(counts, filled == j), entry,
                                 0)
        filled += counts
    deepest = jnp.max(filled.reshape(-1, _DEPTH_TOKENS), axis=1)
    return jnp.concatenate(rows), filled, deepest


def _sum_kernel(row_ref, filled_ref, deepest_ref, limit_ref, x_ref, o_ref,
                buf_ref, mask_ref, sem_ref, count_ref, *, k, tile, pieces,
                packed):
    """Step s starts the copies of tile s and finishes tile s - 1."""
    s = pl.program_id(0)
    n_tiles = pl.num_programs(0) - 1
    n_tokens = n_tiles * tile
    width = o_ref.shape[1]
    words = width // 2 if packed else width
    strip = 16 if packed else 8     # one packed register of result rows

    def copy(place, row, slot):
        return pltpu.make_async_copy(
            x_ref.at[pl.ds(pl.multiple_of(row * pieces, pieces), pieces)],
            buf_ref.at[pl.ds(pl.multiple_of(place * pieces, pieces), pieces)],
            sem_ref.at[slot])

    def start(slot):
        mask_ref[pl.ds(pl.multiple_of(slot * (k * tile), 16), k * tile),
                 :] = jnp.zeros((k * tile, _LANES), _U32)

        def token(t, carry):
            def entry(j, carry):
                row = row_ref[j * n_tokens + s * tile + t]
                place = slot * (k * tile) + j * tile + t

                @pl.when(jnp.logical_and(row >= 0, row < limit_ref[0]))
                def _copy():
                    copy(place, row, slot).start()
                    mask_ref[pl.ds(place, 1), :] = jnp.full(
                        (1, _LANES), _WHOLE, _U32)
                    count_ref[slot] += 1

                return carry

            # the token's filled slots, the first ones
            lax.fori_loop(0, filled_ref[s * tile + t], entry, 0)
            return carry

        count_ref[slot] = jnp.zeros((), _I32)
        lax.fori_loop(0, tile, token, 0)

    # one unrolled sum for each of a few depths (a loop over the slots
    # inside the loop over the lane tiles costs more than the sums
    # themselves; a copy for every depth costs set-up time): a strip is
    # summed to the next of them, its empty slots masked
    depths = sorted({-(-k * i // 3) for i in (1, 2, 3)})

    def finish(slot):
        def wait(_, carry):
            copy(0, 0, slot).wait()
            return carry

        lax.fori_loop(0, count_ref[slot], wait, 0)

        def add_up(t0, deep):
            """The first `deep` slots of the tokens [t0, t0 + strip), summed
            in registers, a lane tile of words at a time."""
            at = pl.ds(t0, strip)
            base = [slot * (k * tile) + c * tile + t0 for c in range(deep)]
            mask = [mask_ref[pl.ds(base[c], strip), :] for c in range(deep)]

            def lane_tile(j, carry):
                low = high = None
                for c in range(deep):
                    # sublane u of a load: piece j of the row at place
                    # (c, t0 + u), `pieces` rows of the buffer apart
                    word = jnp.concatenate([
                        buf_ref[pl.ds((base[c] + u) * pieces + j, 8,
                                      stride=pieces), :]
                        for u in range(0, strip, 8)], axis=0) & mask[c]
                    if packed:
                        part = _f32(word << 16)
                        low = part if low is None else low + part
                        part = _f32(word & _U32(_HIGH_HALF))
                        high = part if high is None else high + part
                    else:
                        part = _f32(word)
                        low = part if low is None else low + part
                lanes = pl.multiple_of(j * _LANES, _LANES)
                o_ref[at, pl.ds(lanes, _LANES)] = low.astype(o_ref.dtype)
                if packed:
                    o_ref[at, pl.ds(lanes + words, _LANES)] = high.astype(
                        o_ref.dtype)
                return carry

            # a loop, not `words // 128` copies of it: every copy is traced
            # and lowered in every program that holds the kernel, cached or
            # not (set-up time)
            lax.fori_loop(0, words // _LANES, lane_tile, 0)

        def tokens(r, carry):
            t0 = pl.multiple_of(r * strip, strip)
            deep = deepest_ref[((s - 1) * tile + t0) // _DEPTH_TOKENS]

            @pl.when(deep == 0)
            def _nothing():
                o_ref[pl.ds(t0, strip), :] = jnp.zeros((strip, width),
                                                       o_ref.dtype)

            for below, d in zip([0] + depths, depths):
                pl.when(jnp.logical_and(deep > below, deep <= d))(
                    functools.partial(add_up, t0, d))
            return carry

        lax.fori_loop(0, tile // strip, tokens, 0)

    @pl.when(s < n_tiles)
    def _start():
        start(s % 2)

    @pl.when(s > 0)
    def _finish():
        finish((s - 1) % 2)


def _kernel_sum(x, back, k: int, n_valid):
    """`reference_sum` by `_sum_kernel`. A jitted call: a step's layers (a
    forward pass, its remat repeat and a backward pass each) then share one
    trace and one lowering of the kernels, where each call site cost 0.2 s
    of set-up a cache hit does not save; the compiler inlines the calls and
    each site's instructions keep its scope."""
    return _jitted_sum(x, back, n_valid, k,
                       (TOKEN_TILE, _FORM_TILE, _pallas.interpret()))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _jitted_sum(x, back, n_valid, k: int, read_when_traced):
    """`read_when_traced`: what the trace reads besides its operands (the
    tiles, interpreted or not), so that the trace cache's key holds it."""
    n_tokens, width = back.size // k, x.shape[1]
    pieces = _pieces(x)
    tile = _tile(n_tokens, k, pieces)
    n_tiles = -(-n_tokens // tile)
    back = back.astype(_I32)
    if n_tiles * tile != n_tokens:      # whole tiles: entries that miss
        back = jnp.pad(back, (0, (n_tiles * tile - n_tokens) * k),
                       constant_values=-1)
    limit = jnp.minimum(n_valid, x.shape[0]).astype(_I32)
    rows, filled, deepest = _entries(back, k, limit)
    need = 2 * k * tile * (pieces + 1) * _LANES * 4 \
        + 2 * tile * width * jnp.dtype(x.dtype).itemsize
    out = pallas_call(
        functools.partial(_sum_kernel, k=k, tile=tile, pieces=pieces,
                          packed=x.dtype == jnp.bfloat16),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_tiles + 1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(
                (tile, width), lambda s, *_: (jnp.maximum(s - 1, 0), 0)),
            scratch_shapes=[
                pltpu.VMEM((2 * k * tile * pieces, _LANES), _U32),
                pltpu.VMEM((2 * k * tile, _LANES), _U32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), _I32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tile, width), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(32 * 2 ** 20, need + 16 * 2 ** 20)),
    )(rows, filled, deepest, limit.reshape(1), _row_form(x, limit))
    return out if out.shape[0] == n_tokens else out[:n_tokens]


# --------------------------------------------------------------------------
# The two row movers
# --------------------------------------------------------------------------

def _sum(x, back, k, n_valid):
    if n_valid is None or not supported(x):
        return reference_sum(x, back, k, n_valid)
    return _kernel_sum(x, back, k, n_valid)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def take_rows(x, rows, back, k, n_valid=None):
    """`x[rows]` for x: (n, D) and rows: (m,) int32, where `back` (n * k,)
    says which k rows of the result each row of `x` went to (row t to rows
    back[t*k:(t+1)*k]): the backward pass is then a gather and a sum over k
    (`sum_rows`), not a scatter-add. With `n_valid` the result is a row
    buffer of which the first n_valid rows count: the others are zero, and
    `back` may point past its end."""
    return reference_take(x, rows, n_valid)


def _take_rows_fwd(x, rows, back, k, n_valid=None):
    return reference_take(x, rows, n_valid), (rows, back, n_valid)


def _take_rows_bwd(k, indices, g):
    rows, back, n_valid = indices
    return _sum(g, back, k, n_valid), None, None, None


take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def sum_rows(x, back, rows, k, n_valid=None):
    """The transpose of `take_rows`: row t of the result is the sum, in
    float32 and rounded once to x's type, of the k rows
    x[back[t*k:(t+1)*k]], and row i of `x` went into row rows[i] alone: the
    backward pass is `g[rows]`, a gather from the small array. With
    `n_valid`, an entry of `back` at or past it adds nothing."""
    return _sum(x, back, k, n_valid)


def _sum_rows_fwd(x, back, rows, k, n_valid=None):
    return _sum(x, back, k, n_valid), (rows, back, n_valid)


def _sum_rows_bwd(k, indices, g):
    rows, back, n_valid = indices
    return reference_take(g, rows, n_valid), None, None, None


sum_rows.defvjp(_sum_rows_fwd, _sum_rows_bwd)


# --------------------------------------------------------------------------
# The lookup
# --------------------------------------------------------------------------

#: sorted tokens a chunk of `_lookup_grad`: the one-hot product's inner size
LOOKUP_CHUNK = 256


def _chunk_slots(s, chunk: int):
    """For sorted ids `s` (chunks * chunk,): the slot of every place, its
    chunk's distinct ids numbered from 0 by a running count of the places
    where `s` changes, as (chunks, chunk) int32."""
    rows = s.reshape(-1, chunk)
    changes = jnp.concatenate(
        [jnp.zeros((rows.shape[0], 1), _I32),
         (rows[:, 1:] != rows[:, :-1]).astype(_I32)], axis=1)
    return jnp.cumsum(changes, axis=1, dtype=_I32)


def _exact(x):
    """The precision at which a product with a 0/1 matrix adds x's values
    as they are (bfloat16 operands are exact in one pass)."""
    return None if x.dtype == jnp.bfloat16 else lax.Precision.HIGHEST


def slot_share(ids: Sequence[int], chunk: Optional[int] = None) -> float:
    """Slots of `_lookup_grad`'s chunks that hold a sum over the tokens, for
    concrete ids: the distinct ids of each chunk of the sorted ids, summed
    (1.0 where no id repeats inside a chunk: as many rows written as read;
    chunks / tokens where every token has one id)."""
    chunk = chunk or LOOKUP_CHUNK
    s = sorted(ids)
    return sum(len(set(s[at:at + chunk]))
               for at in range(0, len(s), chunk)) / len(s)


def _lookup_plan(ids, n_rows: int, chunk: int):
    """What `_lookup_grad` needs of the ids (T,) alone, all int32: `order`
    (padded,), the tokens by id with padding behind them; `slot` (chunks,
    chunk), a place's slot in its chunk, -1 for padding; `joins` (chunks,
    chunks), 1.0 where an earlier chunk's last id is the chunk's first;
    `place` (n_rows,), where in the chunks' slots each row's sum stands, a
    free slot for a row no token asks for."""
    n_tokens = ids.size
    ids = ids.astype(_I32)
    ids = jnp.where(ids < 0, ids + n_rows, ids)      # as the lookup reads
    # an id outside the table adds nothing: it is padding
    ids = jnp.where(jnp.logical_and(ids >= 0, ids < n_rows), ids, n_rows)
    # the tokens by id; at least one place is padding (the id `n_rows`,
    # which sorts last), so the last slot of the last chunk is always free
    n_chunks = n_tokens // chunk + 1
    padded = n_chunks * chunk
    s, order = lax.sort((ids, jnp.arange(n_tokens, dtype=_I32)), num_keys=1)
    s = jnp.pad(s, (0, padded - n_tokens), constant_values=n_rows)
    order = jnp.pad(order, (0, padded - n_tokens))
    slot = jnp.where(s.reshape(n_chunks, chunk) < n_rows,
                     _chunk_slots(s, chunk), -1)
    first, last = s[::chunk], s[chunk - 1::chunk]
    earlier = jnp.arange(n_chunks)[None, :] < jnp.arange(n_chunks)[:, None]
    joins = jnp.logical_and(earlier, last[None, :] == first[:, None])
    # where each row's run ends: tokens with an id <= v, from a histogram of
    # the ids that is a product of two small one-hots (v = high * 256 +
    # low), and the distinct ids among them
    highs = -(-n_rows // 256)
    hist = jnp.dot(
        (s[:, None] // 256 == jnp.arange(highs, dtype=_I32)[None, :]
         ).astype(jnp.bfloat16).T,
        (s[:, None] % 256 == jnp.arange(256, dtype=_I32)[None, :]
         ).astype(jnp.bfloat16),
        preferred_element_type=jnp.float32).reshape(-1)[:n_rows].astype(_I32)
    end = jnp.cumsum(hist, dtype=_I32) - 1
    rank = jnp.cumsum((hist > 0).astype(_I32), dtype=_I32) - 1
    at = jnp.maximum(end, 0) // chunk                 # the chunk it ends in
    # the rank of a chunk's first id: its first place's own
    base = rank[jnp.minimum(first, n_rows - 1)]
    base_at = jnp.sum(jnp.where(
        at[:, None] == jnp.arange(n_chunks, dtype=_I32)[None, :],
        base[None, :], 0), axis=1)
    place = jnp.where(hist > 0, at * chunk + rank - base_at, padded - 1)
    return order, slot, joins.astype(jnp.float32), place


def _lookup_grad(plan, g, dtype):
    """The gradient of `table[ids]` for `_lookup_plan(ids, ...)` and the
    cotangent g (T, D), for a table of `dtype`: row v the float32 sum of the
    rows g[t] with ids[t] == v, rounded once; zero where no token asks for
    v. No scatter: the tokens' rows gathered in the order of their ids, a
    one-hot product a chunk, and a gather of the table's rows."""
    order, slot, joins, place = plan
    n_chunks, chunk = slot.shape
    gs = g[order].reshape(n_chunks, chunk, g.shape[1])
    # a chunk's sums by slot, one batched product, rounded once as it is
    # written; padding has no slot
    onehot = (slot[:, None, :] == jnp.arange(chunk, dtype=_I32)[None, :, None]
              ).astype(g.dtype)
    sums = jnp.einsum("cup,cpd->cud", onehot, gs, precision=_exact(g),
                      preferred_element_type=jnp.float32).astype(dtype)
    # a run that crosses chunks' edges: the chunk c in which it goes on
    # holds it in slot 0, and every earlier chunk j whose last id is c's
    # first holds its part in its last slot. Those two slots of each chunk
    # once more, kept in float32
    last_slot = jnp.maximum(slot[:, -1:], 0)
    ends = jnp.stack([slot == 0, slot == last_slot], axis=1)
    ends = jnp.einsum("cep,cpd->ced", ends.astype(g.dtype), gs,
                      precision=_exact(g), preferred_element_type=jnp.float32)
    heads = ends[:, 0] + jnp.dot(joins, ends[:, 1],
                                 precision=lax.Precision.HIGHEST)
    sums = lax.dynamic_update_slice(sums, heads.astype(dtype)[:, None],
                                    (0, 0, 0)).reshape(-1, g.shape[1])
    # The gather of the table's rows stands in a loop of one trip, which the
    # compiler cannot count (a place is never negative) and so schedules as
    # one instruction, as the scatter-add was. As a plain gather it changes
    # what `lm-1chip`'s compiled step does with the loss's value, which
    # nothing waits for: computed after the optimizer's updates, the logits
    # kept until then, +0.25 GiB (PERF.md section 6, docs/kernels.md).
    once = (place[0] >= 0).astype(_I32)
    return lax.while_loop(
        lambda carry: carry[0] < once,
        lambda carry: (carry[0] + 1, sums[place]),
        (jnp.zeros((), _I32), jnp.zeros((place.size, g.shape[1]), dtype)))[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _lookup(table, ids, chunk):
    return table[ids], table


def _lookup_fwd(table, ids, chunk):
    # the ids' own work stands in the forward pass
    return (table[ids], table), _lookup_plan(ids.reshape(-1), table.shape[0],
                                             chunk)


def _lookup_bwd(chunk, plan, cotangents):
    g, onto = cotangents
    # The lookup's part is added onto a buffer that exists before it: the
    # gradient of whatever read the table after the lookup (a tied head), or
    # zeros, as the compiler's scatter-add was. Without the barrier the
    # compiler fuses a tied head's gradient product with this add and keeps
    # the logits' cotangent for it until the end of the step
    # (`phi4flash-1chip`: +0.52 GiB), and the untied steps' schedules change
    # (`smallthinker-1chip`: +0.56 GiB; PERF.md).
    onto = lax.optimization_barrier(onto)
    return onto + _lookup_grad(plan, g.reshape(-1, g.shape[-1]),
                               onto.dtype), None


_lookup.defvjp(_lookup_fwd, _lookup_bwd)


def lookup_rows(table, ids):
    """(`table[ids]`, `table`) for a table (V, D) and ids of any shape, the
    rows bit for bit. The backward pass is `_lookup_grad`, not the
    scatter-add the compiler makes of a gather's transpose; a caller that
    reads the table again (a tied head) reads the one handed back, and that
    reader's gradient is what the lookup's is added to."""
    return _lookup(table, ids, LOOKUP_CHUNK)
