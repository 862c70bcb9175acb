"""Grouped matmul as Pallas TPU kernels: rows sorted by group, one weight
matrix a group.

The expert layer's product (`parallel/moe.py` `_experts`): the rows of a
(rows, K) array lie in E consecutive groups of `group_sizes` rows, and the
rows of group e are multiplied by `weights[e]`, (K, N). Three products, one
family of kernels:

    forward            (rows, K) x (E, K, N)  -> (rows, N)
    towards the rows   (rows, N) x (E, K, N)T -> (rows, K)   the same kernel,
                       contracting the weights' last dimension: no transposed
                       copy of the weights is made
    towards weights    (rows, K)T x (rows, N) -> (E, K, N)   a group with no
                       rows gets exact zeros

The rows are walked in tiles of `ROW_TILE`, and a tile is visited once by
every group that has rows in it: where a group ends inside a tile, that tile
is visited again by the next group. The visits are made once from the group
sizes, in plain `jnp` (`visits`), and are the five scalar-prefetch operands
of every kernel: the group and the row tile of each visit, the first row of
its group and the row after its last, and how many visits there are (the
grid is static, `tiles + E - 1` visits, the most there can be; the rest are
skipped and repeat the last visit's blocks, so nothing is moved for them).
The index maps read them: consecutive visits of one group keep its weight
block in VMEM (forward, towards the rows) or its gradient's accumulator
(towards the weights), and consecutive visits of one row tile keep the
result tile, which each group fills its own rows of.

Inside a visit the tile is walked in strips of `_STRIP` rows, and a strip
with no row of the visit's group is not multiplied: what a tile that
straddles two groups costs twice is only the strip the boundary lies in.
`visit_share` counts the visits and `strip_share` the strips against the
fewest there could be.

Arithmetic: operands in their own dtype (bf16 in every benchmark cell),
float32 accumulation, one rounding on the way out. Every row belongs to a
group: rows past the sum of `group_sizes` are taken as the last group's
(`parallel/moe.py` puts its buffer's free rows, which are zero, there
itself).

Where the weights are one layer of a stack (L, E, K, N), as a layer scan
keeps them, the two kernels that read weights read that layer in place: the
stack is seen as its L * E matrices and the layer is added to the visits'
group numbers (`_at_layer`), so nothing copies the layer out of the stack
for the custom call, and the kernels' operands stay the seven they were.
The weights' gradient is one layer's (E, K, N) all the same, and goes to
the layer's own leaf (`grouped_matmul`).

The contraction (K) is never tiled and the other width only where the
blocks would not fit `_VMEM_BUDGET`: with whole widths every operand is
read once and every weight matrix once a group. The benchmark tells these
kernels by their operands, five of metadata and two matrices, and their one
result (`benchmark/harness/scopes.py`; `tests/test_kernels_tpu_aot.py` holds
them to it). Off the TPU they run in the Pallas interpreter
(`ops/_pallas.interpret`).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops._pallas import pallas_call

#: rows a kernel takes at a time (fewer rows than this are one tile)
ROW_TILE = 512
#: rows of a tile that are multiplied or skipped together
_STRIP = 128
#: what a kernel's blocks may take of VMEM, double-buffered (v5e has 128 MiB)
_VMEM_BUDGET = 48 * 2 ** 20


class Visits(NamedTuple):
    """The tile visits of a grouped matmul, each (n,) int32 but `count`
    (1,): visit v multiplies the rows [first_row[v], end_row[v]) of group
    `group[v]` that lie in row tile `tile[v]`; only the first `count[0]`
    are made."""
    group: jax.Array
    tile: jax.Array
    first_row: jax.Array
    end_row: jax.Array
    count: jax.Array


def _row_tile(n_rows: int) -> int:
    return min(ROW_TILE, n_rows)


def _padded(n_rows: int) -> int:
    tile = _row_tile(n_rows)
    return -(-n_rows // tile) * tile


def visits(group_sizes: jax.Array, n_rows: int) -> Visits:
    """The visits of `n_rows` rows in groups of `group_sizes` (E,): per
    group, in order, one for every row tile it has rows in, and one for a
    group without rows (its weights' gradient has to be written). Make them
    once where several products share the groups."""
    tile = _row_tile(n_rows)
    n_tiles = _padded(n_rows) // tile
    n_groups = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes.astype(jnp.int32), dtype=jnp.int32)
    # rows past the groups, and the padding to whole tiles, go to the last
    ends = ends.at[-1].set(n_tiles * tile)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    touched = jnp.where(ends > starts, -(-ends // tile) - starts // tile, 1)
    upto = jnp.cumsum(touched, dtype=jnp.int32)
    v = jnp.arange(n_tiles + n_groups - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.sum(v[:, None] >= upto[None, :], axis=1, dtype=jnp.int32),
        n_groups - 1)
    nth = v - (upto - touched)[group]       # of its group's visits
    tile_of = jnp.minimum(starts[group] // tile + nth, n_tiles - 1)
    return Visits(group, tile_of, starts[group], ends[group], upto[-1:])


def _strip(tile: int) -> int:
    return _STRIP if tile % _STRIP == 0 else tile


def _share(group_sizes: Sequence[int], n_rows: Optional[int], strips: bool):
    """Pieces (row tiles, or their strips) that hold rows of a group,
    counted once a group, over the pieces there are."""
    n_rows = sum(group_sizes) if n_rows is None else n_rows
    edge = _strip(_row_tile(n_rows)) if strips else _row_tile(n_rows)
    pieces = -(-n_rows // edge)
    made, start = 0, 0
    for i, size in enumerate(group_sizes):
        end = pieces * edge if i == len(group_sizes) - 1 else start + size
        if end > start:
            made += -(-end // edge) - start // edge
        start = end
    return made / pieces


def visit_share(group_sizes: Sequence[int],
                n_rows: Optional[int] = None) -> float:
    """Tile visits that multiply over the row tiles there are, for concrete
    group sizes (1.0: no tile is visited twice): a tile that holds rows of
    g groups is visited g times. (128 + 63) / 128 = 1.49 for 65,536 rows in
    64 groups whose ends lie inside tiles, (24 + 7) / 24 = 1.29 for 12,288
    in 8: what the blocks are moved for."""
    return _share(group_sizes, n_rows, strips=False)


def strip_share(group_sizes: Sequence[int],
                n_rows: Optional[int] = None) -> float:
    """Strips multiplied over the strips the rows make (1.0: none twice):
    the kernels' matmul work over the least there could be, and so the
    ceiling of their share of the roofline. 1.12 and 1.07 for the two
    above."""
    return _share(group_sizes, n_rows, strips=True)


def _width_tile(width: int, need) -> int:
    """The most of `width` columns a block may hold: all of them, or the
    largest divisor in whole lane tiles for which `need(columns)` bytes fit
    the budget."""
    options = [width] + [t for t in range(width - width % 128, 0, -128)
                         if t < width and width % t == 0]
    for t in options:
        if need(t) <= _VMEM_BUDGET:
            return t
    raise ValueError(
        f"no tile of {width} columns fits {_VMEM_BUDGET} bytes of VMEM "
        f"(the narrowest needs {need(options[-1])})")


def _params(need: int) -> dict:
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=max(32 * 2 ** 20, need + 16 * 2 ** 20))}


def _pad_rows(x, n_rows: int):
    return x if x.shape[0] == n_rows else jnp.pad(
        x, ((0, n_rows - x.shape[0]), (0, 0)))


# --------------------------------------------------------------------------
# The kernels
# --------------------------------------------------------------------------

def _for_each_piece(step, *, row0, first, end, tile, strip):
    """Run `step(rows, lo, masked)` over what a visit multiplies of its
    tile, which starts at row `row0`, for the group of rows [first, end):
    the whole tile at once where all of it is the group's, else the strips
    that hold rows of the group. `rows` is the piece's static slice of the
    tile, `lo` its first row, `masked` whether other groups' rows may lie in
    it."""
    whole = jnp.logical_and(row0 >= first, row0 + tile <= end)

    @pl.when(whole)
    def _tile():
        step(slice(0, tile), row0, False)

    @pl.when(jnp.logical_not(whole))
    def _strips():
        for s in range(0, tile, strip):
            lo = row0 + s

            @pl.when(jnp.logical_and(lo < end, lo + strip > first))
            def _strip():
                step(slice(s, s + strip), lo, True)


def _own(shape, lo, first, end):
    """Which rows of a piece that starts at row `lo` are the group's."""
    row = lo + lax.broadcasted_iota(jnp.int32, shape, 0)
    return jnp.logical_and(row >= first, row < end)


def _rows_kernel(group_ref, tile_ref, first_ref, end_ref, count_ref,
                 x_ref, w_ref, o_ref, *, tile, strip, transposed):
    """(rows, K) x weights -> (rows, N), forward and towards the rows: a
    visit writes its group's rows of the result tile."""
    del group_ref                       # the index maps read it
    v = pl.program_id(1)
    first, end = first_ref[v], end_ref[v]
    contract = (((1,), (1 if transposed else 0,)), ((), ()))

    def step(rows, lo, masked):
        y = lax.dot_general(x_ref[rows, :], w_ref[...], contract,
                            preferred_element_type=jnp.float32)
        if masked:
            # the other rows of the strip are another visit's
            y = jnp.where(_own(y.shape, lo, first, end), y,
                          o_ref[rows, :].astype(jnp.float32))
        o_ref[rows, :] = y.astype(o_ref.dtype)

    @pl.when(v < count_ref[0])
    def _visit():
        _for_each_piece(step, row0=tile_ref[v] * tile, first=first, end=end,
                        tile=tile, strip=strip)


def _weights_kernel(group_ref, tile_ref, first_ref, end_ref, count_ref,
                    x_ref, dy_ref, o_ref, acc_ref, *, tile, strip):
    """(rows, K)T x (rows, N) -> (E, K, N), towards the weights: a group's
    visits add up in `acc_ref`, which the visit its first row lies in
    clears and the visit its last row lies in writes. (Adding a product to
    the accumulator is what the compiler does well: starting the sum with
    the first product instead of zeros took twice as long at F = 1,408,
    docs/kernels.md.)"""
    del group_ref                       # the index maps read it
    v = pl.program_id(1)
    first, end = first_ref[v], end_ref[v]
    row0 = tile_ref[v] * tile

    def step(rows, lo, masked):
        dy = dy_ref[rows, :]
        if masked:
            dy = jnp.where(_own(dy.shape, lo, first, end), dy,
                           jnp.zeros_like(dy))
        acc_ref[...] += lax.dot_general(
            x_ref[rows, :], dy, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(v < count_ref[0])
    def _visit():
        # the group's first visit (the only one of a group without rows,
        # which writes the zeros)
        @pl.when(first >= row0)
        def _clear():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        _for_each_piece(step, row0=row0, first=first, end=end, tile=tile,
                        strip=strip)

        @pl.when(end <= row0 + tile)
        def _write():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _rows_product(x, weights, plan: Visits, transposed: bool):
    """x (rows, K) by weights (E, K, N), or (E, N, K) `transposed`, over
    the visits `plan`: (rows, N)."""
    n_rows, k = x.shape
    n = weights.shape[1 if transposed else 2]
    tile = _row_tile(n_rows)
    padded = _padded(n_rows)
    size = jnp.dtype(x.dtype).itemsize

    def need(tn):   # two buffers a block, and the float32 product
        return 2 * size * (tile * k + k * tn + tile * tn) + 4 * tile * tn

    tn = _width_tile(n, need)
    if transposed:
        w_spec = pl.BlockSpec((None, tn, k),
                              lambda j, v, g, *_: (g[v], j, 0))
    else:
        w_spec = pl.BlockSpec((None, k, tn),
                              lambda j, v, g, *_: (g[v], 0, j))
    out = pallas_call(
        functools.partial(_rows_kernel, tile=tile, strip=_strip(tile),
                          transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // tn, plan.group.shape[0]),
            in_specs=[
                pl.BlockSpec((tile, k), lambda j, v, g, t, *_: (t[v], 0)),
                w_spec,
            ],
            out_specs=pl.BlockSpec((tile, tn),
                                   lambda j, v, g, t, *_: (t[v], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((padded, n), x.dtype),
        **_params(need(tn)),
    )(*plan, _pad_rows(x, padded), weights)
    return out[:n_rows]


def _weights_product(x, dy, plan: Visits, n_groups: int):
    """The (E, K, N) gradient of the weights: per group the (rows, K) rows
    `x`, transposed, by the (rows, N) cotangent `dy`, over the visits
    `plan` of `n_groups` groups."""
    n_rows, k = x.shape
    n = dy.shape[1]
    tile = _row_tile(n_rows)
    padded = _padded(n_rows)
    size = jnp.dtype(x.dtype).itemsize

    def need(tn):   # the accumulator and a product for it, the blocks twice
        return 8 * k * tn + 2 * size * (k * tn + tile * k + tile * tn)

    tn = _width_tile(n, need)
    return pallas_call(
        functools.partial(_weights_kernel, tile=tile, strip=_strip(tile)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // tn, plan.group.shape[0]),
            in_specs=[
                pl.BlockSpec((tile, k), lambda j, v, g, t, *_: (t[v], 0)),
                pl.BlockSpec((tile, tn), lambda j, v, g, t, *_: (t[v], j)),
            ],
            out_specs=pl.BlockSpec((None, k, tn),
                                   lambda j, v, g, *_: (g[v], 0, j)),
            scratch_shapes=[pltpu.VMEM((k, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_groups, k, n), x.dtype),
        **_params(need(tn)),
    )(*plan, _pad_rows(x, padded), _pad_rows(dy, padded))


# --------------------------------------------------------------------------
# The product and its backward pass
# --------------------------------------------------------------------------

def _at_layer(stack, layer, plan: Visits):
    """(`stack` (L, E, K, N) seen as its L * E matrices, `plan` with every
    visit's group numbered among them): what the kernels that read weights
    take for layer `layer`. The layer rides in the group numbers, so the
    kernels keep the seven operands the benchmark tells them by."""
    n_groups = stack.shape[1]
    return (stack.reshape((-1,) + stack.shape[2:]),
            plan._replace(group=plan.group + layer * n_groups))


@jax.custom_vjp
def _product(rows, weights, stack, layer, plan):
    """`rows` by `stack[layer]`, whose numbers `weights` holds too: the
    products read the stack in place, the gradient goes to `weights`."""
    del weights
    return _rows_product(rows, *_at_layer(stack, layer, plan),
                         transposed=False)


def _product_fwd(rows, weights, stack, layer, plan):
    return _product(rows, weights, stack, layer, plan), \
        (rows, stack, layer, plan)


def _product_bwd(kept, g):
    rows, stack, layer, plan = kept
    return (_rows_product(g, *_at_layer(stack, layer, plan), transposed=True),
            _weights_product(rows, g, plan, stack.shape[1]),
            None, None, None)


_product.defvjp(_product_fwd, _product_bwd)


def grouped_matmul(rows: jax.Array, weights: jax.Array, group_sizes,
                   stack: Optional[jax.Array] = None, layer=0) -> jax.Array:
    """`rows` (rows, K) sorted by group times `weights` (E, K, N), the rows
    of group e by `weights[e]`: (rows, N) in the rows' dtype, accumulated
    in float32. `group_sizes` is the (E,) int32 rows of each group, or the
    `visits` made of them where several products share the groups; rows
    past their sum count as the last group's. Differentiable in `rows` and
    `weights`; both have one dtype.

    Where `weights` is one layer of a stack, `stack` (L, E, K, N) and
    `layer` (an int32, traced or not) say which: the kernels then read
    `stack[layer]` in place, and nothing has to copy the layer out of the
    stack for them (a layer scan's `xs[l]` in front of a custom call is a
    copy). The caller's contract: `stack[layer]` holds the numbers of
    `weights`. The gradient goes to `weights` all the same, so a scan still
    stacks it layer by layer, and `stack` gets none: hand it over under
    `lax.stop_gradient`, taken outside the scan, or the scan's transpose
    carries a stack of zeros for it. Without `stack` the weights are the
    stack of depth one at layer 0 of the same code."""
    if rows.dtype != weights.dtype:
        raise ValueError(
            f"rows are {rows.dtype} and weights {weights.dtype}: a grouped "
            "matmul multiplies operands of one dtype")
    if stack is None:
        stack, layer = lax.stop_gradient(weights)[None], 0
    elif stack.shape[1:] != weights.shape or stack.dtype != weights.dtype:
        raise ValueError(
            f"a stack of {stack.dtype}{list(stack.shape)} holds no layer of "
            f"{weights.dtype}{list(weights.shape)}")
    if not isinstance(group_sizes, Visits):
        group_sizes = visits(group_sizes, rows.shape[0])
    return _product(rows, weights, stack, jnp.asarray(layer, jnp.int32),
                    group_sizes)
