"""The chunked gated delta rule of `ops/gated_delta.py` (the scalar decay a
head, as the Olmo-Hybrid family's linear-attention layers run it) against the
token-by-token recurrence: outputs and the gradient of every input, several
heads a grid step at the cell's widths, the kernel's inverse against a
triangular solve, the saved states, bf16, and the static numbers of the
chunked form. The per-channel rule is `tests/test_kda.py`'s; the model that
runs this one, `tests/test_olmo_hybrid.py`'s."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import gated_delta
from horovod_tpu.ops.gated_delta import (chunked_over_recurrent_macs,
                                         chunks_of, gated_delta_rule,
                                         heads_a_step,
                                         recurrent_gated_delta_rule,
                                         solve_work)


def _rule_inputs(seq, *, strong, seed=0, batch=2, heads=3, dk=8, dv=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    f32 = jnp.float32

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (batch, heads, seq, dk), f32)) \
        * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (batch, heads, seq, dk), f32))
    v = jax.random.normal(ks[2], (batch, heads, seq, dv), f32)
    # strong: a state forgotten within a few tokens; weak: kept for hundreds
    g = -jax.random.uniform(ks[3], (batch, heads, seq), f32) \
        * (8.0 if strong else 0.02)
    # beta on both sides of 1: eigenvalues 1 - beta of both signs
    beta = 2 * jax.nn.sigmoid(
        2 * jax.random.normal(ks[4], (batch, heads, seq), f32))
    assert float(beta.min()) < 0.5 and float(beta.max()) > 1.5
    return q, k, v, g, beta


def _grads(rule, args, cot):
    """(One compiled program: eagerly the rule's forward and backward
    passes are a trace and a compile an operation.)"""
    return jax.jit(jax.grad(lambda *a: jnp.sum(rule(*a) * cot),
                            argnums=(0, 1, 2, 3, 4)))(*args)


@functools.lru_cache(maxsize=None)
def _programs(chunk):
    """(The chunked rule at `chunk`, the recurrence), each as (outputs,
    gradients of every input under a cotangent): compiled once a length,
    shared by the cases that differ in their numbers alone."""
    def both(rule):
        return (jax.jit(rule),
                jax.jit(jax.grad(lambda cot, *a: jnp.sum(rule(*a) * cot),
                                 argnums=(1, 2, 3, 4, 5))))
    return (both(functools.partial(gated_delta_rule, chunk=chunk)),
            both(recurrent_gated_delta_rule))


@pytest.mark.parametrize("strong", [False, True], ids=["weak", "strong"])
@pytest.mark.parametrize("seq", [64, 100, 128, 7])
@pytest.mark.parametrize("chunk", [16, 64])
def test_the_chunked_rule_is_the_recurrence(chunk, seq, strong):
    """Outputs and the gradient of every input, at lengths that are and are
    not multiples of the chunk (the padding rows leave the state alone)."""
    args = _rule_inputs(seq, strong=strong)
    (ours, our_grads), (theirs, their_grads) = _programs(chunk)
    got, want = ours(*args), theirs(*args)
    assert got.shape == want.shape == (2, 3, seq, 16)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    cot = jax.random.normal(jax.random.PRNGKey(9), want.shape, jnp.float32)
    for name, g, w in zip("q k v g beta".split(), our_grads(cot, *args),
                          their_grads(cot, *args)):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-4 * scale, name


@pytest.mark.parametrize("batch, heads", [(1, 7), (2, 4)],
                         ids=["7-heads-in-blocks-of-4", "2-x-4-heads"])
def test_the_rule_at_the_cells_widths_several_heads_a_grid_step(batch, heads):
    """Keys 96 and values 192 wide, as `olmohybrid-1chip` has them: four
    heads a grid step, which seven heads do not fill (the eighth is padding
    that does nothing) and which is all of a batch entry's four, two chunks
    and a part of a third."""
    assert heads_a_step(heads, 96, 192, itemsize=4) == 4
    args = _rule_inputs(150, strong=False, batch=batch, heads=heads, dk=96,
                        dv=192)
    got = jax.jit(gated_delta_rule)(*args)
    want = jax.jit(recurrent_gated_delta_rule)(*args)
    assert got.shape == want.shape == (batch, heads, 150, 192)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    cot = jax.random.normal(jax.random.PRNGKey(9), want.shape, jnp.float32)
    for name, g, w in zip("q k v g beta".split(),
                          jax.jit(lambda *a: _grads(gated_delta_rule, a,
                                                    cot))(*args),
                          jax.jit(lambda *a: _grads(
                              recurrent_gated_delta_rule, a, cot))(*args)):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-4 * scale, name


def test_heads_a_grid_step_come_from_vmem_and_the_head_count():
    # bf16 at the cell's widths: six of its thirty heads a step, no padding
    assert heads_a_step(30, 96, 192) == 6
    assert gated_delta.step_bytes(96, 192) * 6 <= gated_delta._VMEM_BUDGET
    assert gated_delta.step_bytes(96, 192) * 7 > gated_delta._VMEM_BUDGET
    # no divisor in the upper half of what fits: the most, heads padded
    assert heads_a_step(7, 96, 192) == 6
    assert heads_a_step(1, 8, 16) == 1 and heads_a_step(6, 8, 16) == 6
    # wide heads: one a step however little fits
    assert heads_a_step(4, 2048, 2048) == 1


def _strong_chunk(heads=2, c=64, dk=16, seed=3):
    """One chunk whose keys are nearly one direction and whose beta is all
    but 2: A's entries reach 1.9 under the diagonal and its powers grow to
    1e15 before they vanish."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    f32 = jnp.float32
    k = jax.random.normal(ks[0], (heads, 1, dk), f32) \
        + 0.2 * jax.random.normal(ks[1], (heads, c, dk), f32)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (heads, c, 32), f32)
    beta = 2.0 - 0.05 * jax.random.uniform(ks[3], (heads, 1, 1, c), f32)
    g = jnp.full((heads, 1, 1, c), -1e-3, f32)
    return k, v, jnp.cumsum(g, axis=-1), beta


def _a_of(k, b, beta):
    c = k.shape[1]
    b, beta = b[:, 0, 0], beta[:, 0, 0]
    below = jnp.arange(c)[:, None] > jnp.arange(c)[None, :]
    with jax.default_matmul_precision("highest"):
        kk = jnp.einsum("hik,hjk->hij", k, k)
    return jnp.where(below, beta[:, :, None] * jnp.exp(
        b[:, :, None] - b[:, None, :]) * kk, 0.0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_kernels_inverse_is_the_triangular_solve_at_strong_beta(dtype):
    """T = (I + A)^-1 as the forward kernel leaves it, and U_0 = T (beta V),
    against `lax.linalg.triangular_solve` in float32 on the same (rounded)
    inputs: an inverse, or the product that applies it, in bf16 would be
    2^-9 off, three hundred times the tolerance."""
    k, v, b, beta = _strong_chunk()
    k, v = k.astype(dtype), v.astype(dtype)
    heads, c, _ = k.shape
    _, (w, u0, t, _) = gated_delta._forward(
        k[None], k[None], v[None], b, beta, c=c, heads=heads, save=True)
    assert t.dtype == u0.dtype == jnp.float32 and w.dtype == dtype
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    a = _a_of(kf, b, beta)
    assert float(jnp.max(jnp.abs(a))) > 1.8
    eye = jnp.eye(c, dtype=jnp.float32)
    rhs = jnp.concatenate([jnp.broadcast_to(eye, a.shape),
                           beta[:, 0, 0, :, None] * vf], axis=-1)
    with jax.default_matmul_precision("highest"):
        solved = jax.lax.linalg.triangular_solve(
            a + eye, rhs, left_side=True, lower=True, unit_diagonal=True)
    for name, got, want in (("T", t, solved[..., :c]),
                            ("U_0", u0, solved[..., c:])):
        scale = float(jnp.max(jnp.abs(want)))
        assert float(jnp.max(jnp.abs(got - want))) <= 6e-6 * scale, name
    # what the doubling product (I - A)(I + A^2)(I + A^4).. would have to
    # carry in float32 on its way to entries of size `scale`
    power = a
    for _ in range(4):
        with jax.default_matmul_precision("highest"):
            power = power @ power
    assert float(jnp.max(jnp.abs(power))) > 1e9 * float(
        jnp.max(jnp.abs(solved[..., :c])))


def _solve_a(c, strong):
    """A of one chunk of c rows, two heads, no decay. Strong: every key
    nearly its neighbour's (a token said twice) and beta all but 2, so that
    the entries beside the diagonal reach 1.9 and the rest half of it; mild:
    keys in general position and beta under 1."""
    ks = jax.random.split(jax.random.PRNGKey(c), 3)
    f32 = jnp.float32
    k = jax.random.normal(ks[0], (2, c, 16), f32)
    beta = jax.random.uniform(ks[2], (2, 1, 1, c), f32)
    if strong:
        k = jnp.repeat(k[:, ::2], 2, axis=1) \
            + 0.1 * jax.random.normal(ks[1], k.shape, f32)
        beta = 2.0 - 0.05 * beta
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    return _a_of(k, jnp.zeros_like(beta), beta)


@pytest.mark.parametrize("strong", [False, True], ids=["mild", "strong"])
@pytest.mark.parametrize("c", [8, 16, 24, 64])
def test_the_solve_is_the_inverse(c, strong):
    """`_unit_lower_inverse` called as the kernels call it, against the
    inverse in float64: one panel and no product (8, 16), a last panel of 8
    rows (24), the cells' chunk (64)."""
    a = _solve_a(c, strong)
    assert (float(jnp.max(jnp.abs(a))) > 1.8) == strong
    t = jax.jit(gated_delta._unit_lower_inverse)(
        a, jnp.eye(c, dtype=bool))
    assert t.dtype == jnp.float32 and t.shape == a.shape
    want = np.linalg.inv(np.eye(c) + np.asarray(a, np.float64))
    assert np.abs(np.asarray(t) - want).max() <= 1e-6 * np.abs(want).max()
    assert not np.triu(np.asarray(t), 1).any()
    assert (np.diagonal(np.asarray(t), axis1=1, axis2=2) == 1.0).all()


@pytest.mark.parametrize("c, panels, products, broadcasts, steps", [
    (8, 1, 0, 7, 7), (16, 1, 0, 22, 15), (24, 2, 1, 29, 22),
    (64, 4, 3, 88, 60)])
def test_what_the_solve_costs_is_counted_from_its_loops(
        c, panels, products, broadcasts, steps):
    """`solve_work` against the traced helper of one head: its bf16 matrix
    products (six a float32 product) and its multiplications, one a step,
    each a column of A broadcast over the lanes times a row of T, a lane
    broadcast for every register of rows it covers; no other kind of
    product, nothing divided."""
    work = solve_work(c)
    assert work == {"panels": panels, "products": products,
                    "bf16_passes": 6 * products,
                    "lane_broadcasts": broadcasts, "steps": steps}
    a = jax.ShapeDtypeStruct((1, c, c), jnp.float32)
    traced = jax.make_jaxpr(gated_delta._unit_lower_inverse)(
        a, jnp.eye(c, dtype=bool))
    ops = [eqn.primitive.name for eqn in traced.jaxpr.eqns]
    assert ops.count("dot_general") == work["bf16_passes"]
    covered = [eqn.outvars[0].aval.shape[1] // gated_delta._SUBLANES
               for eqn in traced.jaxpr.eqns if eqn.primitive.name == "mul"]
    assert len(covered) == work["steps"]
    assert sum(covered) == work["lane_broadcasts"]
    assert "div" not in ops and "triangular_solve" not in ops


def test_the_saved_states_are_the_recurrences_in_float32():
    """The entry state of every chunk, as the forward saves it for the
    backward walk, against the recurrence's state at the same token: held
    in float32, so closer than bf16 could hold it."""
    q, k, v, g, beta = _rule_inputs(192, strong=False, batch=1, heads=2)
    heads, c = 2, 64
    gates = [x.reshape(2, 3, 1, c) for x in (g, beta)]
    _, (_, _, _, s0) = gated_delta._forward(
        q, k, v, jnp.cumsum(gates[0], axis=-1), gates[1], c=c, heads=heads,
        save=True)
    assert s0.shape == (2, 3, 8, 16) and s0.dtype == jnp.float32
    state = jnp.zeros((2, 8, 16), jnp.float32)
    for t in range(128):
        state = jnp.exp(g[0, :, t])[:, None, None] * state
        u = beta[0, :, t, None] * (v[0, :, t] - jnp.einsum(
            "hkv,hk->hv", state, k[0, :, t], precision="highest"))
        state = state + k[0, :, t, :, None] * u[:, None, :]
        if t + 1 in (64, 128):
            np.testing.assert_allclose(s0[:, (t + 1) // 64], state,
                                       atol=1e-5, rtol=1e-5)
    assert float(jnp.max(jnp.abs(s0[:, 0]))) == 0.0


def test_the_rule_in_bf16_keeps_its_state_in_float32():
    args = _rule_inputs(256, strong=False)
    q, k, v, g, beta = args
    got = gated_delta_rule(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                           v.astype(jnp.bfloat16), g, beta)
    assert got.dtype == jnp.bfloat16
    want = recurrent_gated_delta_rule(*args)
    err = jnp.sqrt(jnp.mean(jnp.square(got.astype(jnp.float32) - want))
                   / jnp.mean(jnp.square(want)))
    assert float(err) < 4 * 2.0 ** -8


def test_the_static_numbers_of_the_chunked_form():
    assert chunks_of(8192) == 128 and chunks_of(100) == 2
    assert chunks_of(100, 16) == 7
    # per token and head: K K^T and Q K^T 2 x 64 x 96, the solve
    # 64 x (96 + 192) / 2, three products with the state 3 x 96 x 192, the
    # scores' product 64 x 192, over the recurrence's 3 x 96 x 192
    macs = 2 * 64 * 96 + 64 * 288 / 2 + 3 * 96 * 192 + 64 * 192
    assert chunked_over_recurrent_macs(96, 192) == macs / (3 * 96 * 192)
    assert chunked_over_recurrent_macs(96, 192) == pytest.approx(1.61,
                                                                 abs=0.01)
    for chunk in (0, 4, 12):      # a chunk is whole registers of 8 rows
        with pytest.raises(ValueError):
            gated_delta_rule(*_rule_inputs(8, strong=False), chunk=chunk)

