"""The grouped matmul over a stack of layers (ops/grouped_matmul.py, PR 44),
beside `tests/test_grouped_matmul.py` (the three products, whose helpers this
file shares): a layer read in place in the stack gives its own products'
bits, the stack takes no cotangent, and the model's layer scan hands the
stack over."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family as programs
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import grouped_matmul as gm
from horovod_tpu.parallel import MeshSpec, build_mesh, moe
from test_grouped_matmul import (_bits, _operands, _three_products,
                                 small_tiles)  # noqa: F401


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


@functools.lru_cache(maxsize=None)
def _products(case):
    """(The three products of `case` with the kernels reading `stack[layer]`
    in place, the same over a layer's own matrices), each one compiled
    program a depth: the layer is an operand, so the cases that differ in
    it alone share the program (traced under the case's tiles and budget,
    which every call of it holds)."""
    sizes = jnp.asarray(STACKED[case][3], jnp.int32)

    def in_the_stack(rows, weights, stack, layer, cotangent):
        return _three_products(
            lambda r, w: gm.grouped_matmul(r, w, sizes, stack, layer),
            rows, weights, cotangent)

    def alone(rows, weights, cotangent):
        return _three_products(lambda r, w: gm.grouped_matmul(r, w, sizes),
                               rows, weights, cotangent)

    return jax.jit(in_the_stack), jax.jit(alone)


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
    in_the_stack, alone = _products(case)
    got = in_the_stack(rows, stack[layer], stack, jnp.int32(layer),
                       cotangent)
    want = alone(rows, stack[layer], cotangent)
    _bits(got, want)
    # the other layers' numbers were never read: they may be anything
    other = jnp.full_like(stack, jnp.nan).at[layer].set(stack[layer])
    _bits(in_the_stack(rows, stack[layer], other, jnp.int32(layer),
                       cotangent), want)


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
    params = tfm.shard_params(programs.init(cfg), cfg, mesh)
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
