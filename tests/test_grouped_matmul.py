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

from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import grouped_matmul as gm
from horovod_tpu.parallel import MeshSpec, build_mesh, moe


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
    out, vjp = jax.vjp(fn, rows, weights)
    return (out,) + vjp(cotangent)


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
# The product over a stack of layers (PR 44)
# --------------------------------------------------------------------------

#: (rows, K, N, group sizes, VMEM budget or None): what the stacked form has
#: to get right beside the plain one
STACKED = {
    "ends-inside-tiles": (64, 8, 24, [10, 30, 3, 21], None),
    "a-group-of-no-rows": (64, 8, 24, [10, 0, 30, 24], None),
    "rows-past-the-groups": (40, 8, 24, [10, 20], None),
    # N = 256 under a budget that holds 128 columns of it: two column tiles
    "a-width-that-is-tiled": (64, 8, 256, [10, 30, 24], 52_000),
}


def _stack(depth, n_groups, k, n, dtype):
    w = jax.random.normal(jax.random.PRNGKey(7), (depth, n_groups, k, n),
                          jnp.float32)
    return (w / k ** 0.5).astype(dtype)


def _bits(got, want):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


@pytest.mark.parametrize("depth, layer", [(1, 0), (3, 0), (3, 1), (3, 2)])
@pytest.mark.parametrize("case", STACKED)
def test_a_layer_read_in_the_stack_gives_its_own_products_bits(
        case, depth, layer, small_tiles, monkeypatch):
    """The three products (`jax.vjp`: forward, towards the rows, towards the
    weights) with the kernels reading `stack[layer]` in place, the layer a
    traced number as a scan's is, against the same over that layer's own
    (E, K, N): bit for bit in bf16, the gradient on the layer's own leaf."""
    n_rows, k, n, sizes, budget = STACKED[case]
    if budget:
        monkeypatch.setattr(gm, "_VMEM_BUDGET", budget)
    sizes = jnp.asarray(sizes, jnp.int32)
    rows, _, cotangent = _operands(n_rows, k, n, sizes.shape[0],
                                   jnp.bfloat16)
    stack = _stack(depth, sizes.shape[0], k, n, jnp.bfloat16)

    @jax.jit
    def in_the_stack(rows, weights, stack, layer, cotangent):
        return _three_products(
            lambda r, w: gm.grouped_matmul(r, w, sizes, stack, layer),
            rows, weights, cotangent)

    got = in_the_stack(rows, stack[layer], stack, jnp.int32(layer),
                       cotangent)
    want = _three_products(lambda r, w: gm.grouped_matmul(r, w, sizes),
                           rows, stack[layer], cotangent)
    _bits(got, want)
    # the other layers' numbers were never read: they may be anything
    other = jnp.full_like(stack, jnp.nan).at[layer].set(stack[layer])
    _bits(in_the_stack(rows, stack[layer], other, jnp.int32(layer),
                       cotangent), want)


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
    want = (gm._rows_product(rows, weights, plan, transposed=False),
            gm._rows_product(cotangent, weights, plan, transposed=True),
            gm._weights_product(rows, cotangent, plan, sizes.shape[0]))
    _bits(_three_products(lambda r, w: gm.grouped_matmul(r, w, sizes),
                          rows, weights, cotangent), want)
    _bits(_three_products(
        lambda r, w: gm.grouped_matmul(r, w, plan, weights[None], 0),
        rows, weights, cotangent), want)


@pytest.mark.parametrize("depth", [1, 3])
def test_the_stacks_cotangent_is_a_symbolic_zero(depth, small_tiles):
    """The gradient goes to the layer's own leaf and none to the stack: the
    backward pass, as a jaxpr, holds no array of the stack's shape, neither
    an accumulator nor zeros, whether the stack is a constant of the
    differentiated function or one of its arguments; asked for, the
    stack's gradient is zeros."""
    sizes = jnp.asarray([10, 30, 3, 21], jnp.int32)
    rows, _, cotangent = _operands(64, 8, 24, 4, jnp.float32)
    stack = _stack(depth, 4, 8, 24, jnp.float32)
    layer = jnp.int32(depth - 1)
    own = stack[depth - 1]

    def product(rows, own, stack):
        return gm.grouped_matmul(rows, own, sizes, stack, layer)

    def shapes_in(jaxpr):
        found = set()
        for eqn in jaxpr.eqns:
            found |= {tuple(v.aval.shape) for v in eqn.outvars}
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found |= shapes_in(sub)
        return found

    _, vjp = jax.vjp(lambda r, w: product(r, w, stack), rows, own)
    backward = jax.make_jaxpr(vjp)(cotangent)
    # the stack comes in as a constant and is seen as its matrices, no more
    made = shapes_in(backward.jaxpr)
    assert stack.shape not in made and own.shape in made
    grads = jax.grad(lambda r, w, s: product(r, w, s).sum(),
                     argnums=(0, 1, 2))(rows, own, stack)
    assert grads[2].shape == stack.shape and not np.any(np.asarray(grads[2]))
    assert np.any(np.asarray(grads[1]))


def test_a_stack_that_holds_no_such_layer_is_refused():
    rows, weights, _ = _operands(16, 8, 8, 2, jnp.float32)
    sizes = jnp.asarray([6, 10], jnp.int32)
    for stack in (jnp.zeros((3, 2, 8, 16)), jnp.zeros((3, 4, 8, 8)),
                  jnp.zeros((3, 2, 8, 8), jnp.bfloat16)):
        with pytest.raises(ValueError, match="holds no layer"):
            gm.grouped_matmul(rows, weights, sizes, stack, 1)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("dp", [1, 2], ids=["one-rank", "dp2"])
def test_the_layer_scan_hands_the_stack_over_and_the_bits_stay(
        dp, remat, monkeypatch):
    """`run_stack` gives every expert product its stacked leaf and the
    layer's number, and the contract holds there: `stack[layer]` is the
    scan's own slice, so the loss and every gradient leaf are, bit for bit,
    those of the same model whose products are given no stack and read the
    slices, as every product did before; at dp = 2 the layers' gradients
    leave through `grad_slots` inside the backward loop all the same."""
    cfg = tfm.TransformerConfig(
        vocab=64, d_model=32, n_heads=4, d_ff=16, n_layers=3, max_seq=32,
        num_experts=4, experts_per_token=2, load_balance_coef=0.01,
        router_z_coef=0.001, norm="rmsnorm", positions="rope", qk_norm=True,
        mlp="swiglu", attn="local", dtype=jnp.bfloat16, remat=remat)
    mesh = build_mesh(MeshSpec(dp=dp), jax.devices()[:dp])
    params = tfm.shard_params(tfm.init(jax.random.PRNGKey(0), cfg), cfg,
                              mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab,
                                jnp.int32)
    grouped_matmul = moe.grouped_matmul
    stacks = []

    def recording(rows, weights, plan, stack=None, layer=0):
        stacks.append(None if stack is None else stack.shape)
        return grouped_matmul(rows, weights, plan, stack, layer)

    def without(rows, weights, plan, stack=None, layer=0):
        return grouped_matmul(rows, weights, plan)

    def loss_and_grads(product):
        monkeypatch.setattr(moe, "grouped_matmul", product)
        return jax.jit(tfm.build_loss_and_grads(cfg, mesh))(
            params, tokens, jnp.roll(tokens, -1, axis=1))

    got = loss_and_grads(recording)
    # per shard: (layers, experts, D, F) up and gate, (layers, experts, F, D)
    assert stacks and set(stacks) == {(3, 4, 32, 16), (3, 4, 16, 32)}
    want = loss_and_grads(without)
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want)):
        assert g.dtype == w.dtype, jax.tree_util.keystr(path)
        np.testing.assert_array_equal(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            err_msg=jax.tree_util.keystr(path))
    assert np.any(np.asarray(got[1]["layers"]["we1"], np.float32))
