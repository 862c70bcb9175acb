"""The grouped matmul kernels (ops/grouped_matmul.py) in the Pallas
interpreter, against `lax.ragged_dot` and its `jax.vjp`: the three products
on group sizes that end inside row tiles and strips, an empty group, free
rows in the last group, widths no tile divides, rows no tile divides; the
counters `visit_share` and `strip_share` by hand. The row tile is 16 and the
strip 8 here, so that a few dozen rows make several tiles (the interpreter
runs a grid step in milliseconds); tests/test_kernels_tpu_aot.py compiles
the kernels at the benchmark's shapes and tiles.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from horovod_tpu.ops import grouped_matmul as gm


@pytest.fixture
def small_tiles(monkeypatch):
    monkeypatch.setattr(gm, "ROW_TILE", 16)
    monkeypatch.setattr(gm, "_STRIP", 8)


def _operands(n_rows, k, n, n_groups, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    rows = jax.random.normal(keys[0], (n_rows, k), jnp.float32)
    weights = jax.random.normal(keys[1], (n_groups, k, n), jnp.float32)
    cotangent = jax.random.normal(keys[2], (n_rows, n), jnp.float32)
    return (rows.astype(dtype), (weights / k ** 0.5).astype(dtype),
            cotangent.astype(dtype))


def _three_products(fn, rows, weights, cotangent):
    """The product and its two gradients as ONE compiled program (eagerly
    each is a trace and a compile of its own, a dozen a case)."""
    def three(rows, weights, cotangent):
        out, vjp = jax.vjp(fn, rows, weights)
        return (out,) + vjp(cotangent)
    return jax.jit(three)(rows, weights, cotangent)


def _close(got, want, dtype):
    # both accumulate in float32 and round once: a few float32 roundings
    # apart, which in bf16 is at most one step of the result
    tol = 2e-5 if dtype == jnp.float32 else 2 ** -7
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * np.abs(want).max())


#: (rows, K, N, group sizes): what each case is there for
CASES = {
    "ends-inside-tiles": (64, 8, 24, [10, 30, 3, 21]),
    "ends-on-tiles": (64, 8, 24, [16, 32, 16]),
    "an-empty-group": (64, 8, 24, [10, 0, 30, 24]),
    "empty-first-and-last": (48, 8, 24, [0, 20, 28, 0]),
    "one-group": (32, 8, 24, [32]),
    # D = 8 as tests/test_deepseek_v2.py has it, F = 1,408 / 64
    "widths-no-tile-divides": (48, 8, 22, [7, 19, 22]),
    "rows-no-tile-divides": (70, 16, 8, [40, 30]),
    "many-groups-in-one-tile": (32, 8, 16, [3, 2, 4, 1, 0, 6, 16]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", CASES)
def test_three_products_equal_ragged_dot_and_its_vjp(case, dtype,
                                                     small_tiles):
    n_rows, k, n, sizes = CASES[case]
    sizes = jnp.asarray(sizes, jnp.int32)
    operands = _operands(n_rows, k, n, sizes.shape[0], dtype)
    got = _three_products(lambda r, w: gm.grouped_matmul(r, w, sizes),
                          *operands)
    want = _three_products(lambda r, w: lax.ragged_dot(r, w, sizes),
                           *operands)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == dtype and g.shape == w.shape
        _close(g, w, dtype)
    empty = np.flatnonzero(np.asarray(sizes) == 0)
    assert not np.any(np.asarray(got[2], np.float32)[empty])   # exactly 0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_free_rows_lie_in_the_last_group_and_add_nothing(dtype,
                                                         small_tiles):
    """`parallel/moe.py`'s row buffer: the sizes add up to the buffer, the
    last group's rows beyond the held ones are zero. Their results are zero
    and the last group's gradient is its held rows' alone."""
    held, room = [12, 9, 6], 64
    rows, weights, cotangent = _operands(room, 8, 24, 3, dtype)
    rows = rows.at[sum(held):].set(0)
    sizes = jnp.asarray(held, jnp.int32).at[-1].add(room - sum(held))
    out, d_rows, d_weights = _three_products(
        lambda r, w: gm.grouped_matmul(r, w, sizes), rows, weights,
        cotangent)
    assert not np.any(np.asarray(out, np.float32)[sum(held):])
    want = _three_products(
        lambda r, w: lax.ragged_dot(r, w, jnp.asarray(held, jnp.int32)),
        rows[:sum(held)], weights, cotangent[:sum(held)])
    _close(out[:sum(held)], want[0], dtype)
    _close(d_rows[:sum(held)], want[1], dtype)
    _close(d_weights, want[2], dtype)


def test_rows_past_the_groups_count_as_the_last_groups(small_tiles):
    rows, weights, _ = _operands(40, 8, 24, 2, jnp.float32)
    got = gm.grouped_matmul(rows, weights, jnp.asarray([10, 20], jnp.int32))
    want = lax.ragged_dot(rows, weights, jnp.asarray([10, 30], jnp.int32))
    _close(got, want, jnp.float32)


def test_fewer_rows_than_a_tile_are_one_tile():
    """The default tile, 512 rows: 40 rows are one tile of 40."""
    sizes = jnp.asarray([10, 0, 30], jnp.int32)
    operands = _operands(40, 8, 24, 3, jnp.float32)
    plan = gm.visits(sizes, 40)
    assert plan.group.shape == (3,) and int(plan.count[0]) == 3
    assert np.asarray(plan.tile).tolist() == [0, 0, 0]
    got = _three_products(lambda r, w: gm.grouped_matmul(r, w, sizes),
                          *operands)
    want = _three_products(lambda r, w: lax.ragged_dot(r, w, sizes),
                           *operands)
    for g, w in zip(got, want):
        _close(g, w, jnp.float32)


def test_a_width_that_does_not_fit_vmem_is_tiled(monkeypatch, small_tiles):
    """N = 256 under a budget that holds 128 columns of it: two column
    tiles, the rows read once a tile. The contraction is never tiled."""
    sizes = jnp.asarray([10, 30, 24], jnp.int32)
    operands = _operands(64, 8, 256, 3, jnp.float32)
    want = _three_products(lambda r, w: gm.grouped_matmul(r, w, sizes),
                           *operands)
    monkeypatch.setattr(gm, "_VMEM_BUDGET", 52_000)
    tiles = []
    width_tile = gm._width_tile

    def recording(width, need):
        tiles.append(width_tile(width, need))
        return tiles[-1]

    monkeypatch.setattr(gm, "_width_tile", recording)
    got = _three_products(lambda r, w: gm.grouped_matmul(r, w, sizes),
                          *operands)
    assert tiles == [128, 8, 128]     # forward; towards the rows; the weights
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    monkeypatch.setattr(gm, "_VMEM_BUDGET", 1000)
    with pytest.raises(ValueError, match="fits 1000 bytes of VMEM"):
        gm.grouped_matmul(operands[0], operands[1], sizes)


def test_products_share_the_visits_made_once(small_tiles):
    sizes = jnp.asarray([10, 30, 3, 21], jnp.int32)
    rows, weights, _ = _operands(64, 8, 24, 4, jnp.float32)
    plan = gm.visits(sizes, 64)
    np.testing.assert_array_equal(
        np.asarray(gm.grouped_matmul(rows, weights, plan)),
        np.asarray(gm.grouped_matmul(rows, weights, sizes)))
    # tiles of 16: group 0 rows 0-9, 1 rows 10-39, 2 rows 40-42, 3 the rest
    count = int(plan.count[0])
    assert count == 7 and plan.group.shape == (4 + 4 - 1,)
    assert np.asarray(plan.group).tolist() == [0, 1, 1, 1, 2, 3, 3]
    assert np.asarray(plan.tile).tolist() == [0, 0, 1, 2, 2, 2, 3]
    assert np.asarray(plan.first_row).tolist() == [0, 10, 10, 10, 40, 43, 43]
    assert np.asarray(plan.end_row).tolist() == [10, 40, 40, 40, 43, 64, 64]


def test_visits_not_made_repeat_the_last_ones_blocks(small_tiles):
    """Group ends on tile boundaries: 4 visits of the 6 the grid has; the
    other two name the last visit's group and tile, so no block moves."""
    plan = gm.visits(jnp.asarray([16, 32, 16], jnp.int32), 64)
    assert int(plan.count[0]) == 4
    assert np.asarray(plan.group).tolist() == [0, 1, 1, 2, 2, 2]
    assert np.asarray(plan.tile).tolist() == [0, 1, 2, 3, 3, 3]


def test_operands_of_two_dtypes_are_refused():
    rows, weights, _ = _operands(16, 8, 8, 1, jnp.float32)
    with pytest.raises(ValueError, match="one dtype"):
        gm.grouped_matmul(rows.astype(jnp.bfloat16), weights,
                          jnp.asarray([16], jnp.int32))


@pytest.mark.parametrize("sizes,visit,strip", [
    # 64 rows, tiles of 16 and strips of 8; ends at 10, 40, 43:
    # tiles 0 and 2 are visited twice and thrice: 7 visits of 4 tiles;
    # the strips of rows 8-15 and 40-47 twice each: 10 of 8
    ([10, 30, 3, 21], 7 / 4, 10 / 8),
    # ends on tile boundaries: nothing twice
    ([16, 32, 16], 1.0, 1.0),
    # an end on a strip's boundary but inside a tile: the tile twice
    ([24, 40], 5 / 4, 1.0),
])
def test_visit_and_strip_share_by_hand(sizes, visit, strip, small_tiles):
    assert gm.visit_share(sizes) == visit
    assert gm.strip_share(sizes) == strip
    plan = gm.visits(jnp.asarray(sizes, jnp.int32), sum(sizes))
    assert int(plan.count[0]) == round(visit * 4)


def test_visit_share_of_the_benchmark_cells_even_loads():
    """ISSUE 31's numbers: (128 + 63) / 128 in `olmoe-1chip`, (24 + 7) / 24
    in `dsv2lite-1chip`, for even groups whose ends lie inside tiles."""
    olmoe = [1000] + [1024] * 62 + [1048]
    assert gm.visit_share(olmoe) == (128 + 63) / 128
    assert gm.strip_share(olmoe) == (512 + 63) / 512
    held = [750, 768, 768, 768, 768, 768, 768, 786]     # 6,144 rows held
    assert gm.visit_share(held, 12288) == (24 + 7) / 24
    assert gm.strip_share(held, 12288) == (96 + 7) / 96


# --------------------------------------------------------------------------
# The product over a stack of layers (PR 44): tests/test_grouped_matmul_stack.py
# --------------------------------------------------------------------------


def _bits(got, want):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


@pytest.mark.parametrize("case", CASES)
def test_the_depth_one_form_is_the_kernels_called_as_they_were(
        case, small_tiles):
    """Without a stack the weights are the stack of depth one at layer 0 of
    the same code: the bits of the three kernels called as the product
    called them before it knew of stacks (the weights as they are, the
    visits' groups unmoved)."""
    n_rows, k, n, sizes = CASES[case]
    sizes = jnp.asarray(sizes, jnp.int32)
    rows, weights, cotangent = _operands(n_rows, k, n, sizes.shape[0],
                                         jnp.bfloat16)
    plan = gm.visits(sizes, n_rows)
    want = jax.jit(lambda rows, weights, cotangent: (
        gm._rows_product(rows, weights, plan, transposed=False),
        gm._rows_product(cotangent, weights, plan, transposed=True),
        gm._weights_product(rows, cotangent, plan, sizes.shape[0])))(
            rows, weights, cotangent)
    _bits(_three_products(lambda r, w: gm.grouped_matmul(r, w, sizes),
                          rows, weights, cotangent), want)
    _bits(_three_products(
        lambda r, w: gm.grouped_matmul(r, w, plan, weights[None], 0),
        rows, weights, cotangent), want)
