"""The delta rule with a decay per key channel (Kimi Delta Attention,
`ops/gated_delta.py`'s second form) against the token-by-token recurrence:
outputs and the gradient of every input, float32 and bf16, at lengths that
are and are not whole chunks, heads that do and do not fill a grid step, and
under a decay so strong that exp(-b) would overflow inside one chunk; the
decayed products G block by block against their definition; g's running sums
inside a chunk, made in the kernels, against float64's, and that no pass
outside the kernels makes them; the per-channel kernels against the scalar
ones where g is constant over a head's channels; what the forward saves for
the backward pass; and how many heads a grid step takes."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import gated_delta
from horovod_tpu.ops._pallas import pallas_call
from horovod_tpu.ops.gated_delta import (channel_gram_work, gated_delta_rule,
                                         heads_a_step,
                                         recurrent_gated_delta_rule,
                                         running_sum_work, step_bytes)


def _inputs(seq, *, strong, dtype=jnp.float32, seed=0, batch=2, heads=2,
            dk=8, dv=16):
    """q and k normalised as the mixer hands them over; g a decay per
    channel: mild, or up to e^-60 a token (b reaches -3,800 inside a chunk
    of 64, where exp(-b) is far beyond float32)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    f32 = jnp.float32
    q = jax.random.normal(ks[0], (batch, heads, seq, dk), f32)
    k = jax.random.normal(ks[1], (batch, heads, seq, dk), f32)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (batch, heads, seq, dv), f32)
    g = -jax.random.uniform(ks[3], (batch, heads, seq, dk), f32) \
        * (60.0 if strong else 0.3)
    if strong:      # some channels hardly decay beside those that vanish
        g = g * (jnp.arange(dk) % 3 > 0)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (batch, heads, seq),
                                                f32))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


@functools.lru_cache(maxsize=None)
def _grad_program(fn):
    """The gradients of every input of `fn` under a cotangent, as one
    compiled program a shape (eagerly the rule's forward and backward passes
    are a trace and a compile an operation), kept for the cases that call
    the same rule at the same shapes with other numbers."""
    return jax.jit(jax.grad(
        lambda cot, *a: jnp.sum(fn(*a).astype(jnp.float32) * cot),
        argnums=(1, 2, 3, 4, 5)))


def _grads(fn, args, cot):
    return _grad_program(fn)(cot, *args)


_rule = jax.jit(gated_delta_rule)
_recurrence = jax.jit(recurrent_gated_delta_rule)


@pytest.mark.parametrize("seq, chunk, strong", [
    (40, 8, False), (150, 64, False), (150, 64, True), (72, 16, True)],
    ids=["5-chunks-of-8", "2-chunks-and-a-part", "strong-decay",
         "strong-decay-chunks-of-16"])
def test_the_per_channel_rule_equals_the_recurrence(seq, chunk, strong):
    """Outputs and the gradient of every input (g's a number a channel),
    finite under the strong decay and equal to the recurrence's there."""
    with jax.enable_x64(False):
        args = _inputs(seq, strong=strong)
        got = jax.jit(lambda *a: gated_delta_rule(*a, chunk=chunk))(*args)
        want = _recurrence(*args)
        assert got.shape == want.shape == (2, 2, seq, 16)
        assert bool(jnp.all(jnp.isfinite(got)))
        np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)
        cot = jax.random.normal(jax.random.PRNGKey(9), want.shape,
                                jnp.float32)
        ours = _grads(lambda *a: gated_delta_rule(*a, chunk=chunk), args,
                      cot)
        theirs = _grads(recurrent_gated_delta_rule, args, cot)
    assert ours[3].shape == args[3].shape == (2, 2, seq, 8)
    for name, g, w in zip("q k v g beta".split(), ours, theirs):
        assert bool(jnp.all(jnp.isfinite(g))), name
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        assert float(jnp.max(jnp.abs(g - w))) <= 2e-4 * scale, name


@pytest.mark.parametrize("strong", [False, True], ids=["mild", "strong"])
def test_the_rule_in_bf16_is_the_recurrence_on_the_rounded_inputs(strong):
    """bf16 operands with float32 accumulation, decays and state: within a
    few bf16 steps of the float32 recurrence on the same rounded inputs,
    outputs and gradients, and finite under the strong decay."""
    with jax.enable_x64(False):
        args = _inputs(100, strong=strong, dtype=jnp.bfloat16)
        got = _rule(*args)
        assert got.dtype == jnp.bfloat16
        got = got.astype(jnp.float32)
        exact = tuple(x.astype(jnp.float32) for x in args)
        want = _recurrence(*exact)
        scale = float(jnp.max(jnp.abs(want)))
        assert float(jnp.max(jnp.abs(got - want))) <= 2e-2 * scale
        cot = jax.random.normal(jax.random.PRNGKey(9), want.shape,
                                jnp.float32)
        ours = _grads(gated_delta_rule, args, cot)
        theirs = _grads(recurrent_gated_delta_rule, exact, cot)
    for name, g, w in zip("q k v g beta".split(), ours, theirs):
        g = g.astype(jnp.float32)
        assert bool(jnp.all(jnp.isfinite(g))), name
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        assert float(jnp.max(jnp.abs(g - w))) <= 3e-2 * scale, name


def test_heads_that_do_not_fill_a_grid_step_are_padded(monkeypatch):
    """Three heads in blocks of two: the fourth is padding that does
    nothing, in the forward and in every gradient."""
    monkeypatch.setattr(gated_delta, "_VMEM_BUDGET",
                        2 * step_bytes(8, 16, itemsize=4, per_channel=True))
    assert heads_a_step(3, 8, 16, itemsize=4, per_channel=True) == 2
    with jax.enable_x64(False):
        args = _inputs(70, strong=False, batch=1, heads=3)
        got = gated_delta_rule(*args)
        want = recurrent_gated_delta_rule(*args)
        np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)
        cot = jnp.ones(want.shape, jnp.float32)
        for name, g, w in zip("q k v g beta".split(),
                              _grads(gated_delta_rule, args, cot),
                              _grads(recurrent_gated_delta_rule, args, cot)):
            scale = float(jnp.max(jnp.abs(w))) or 1.0
            assert float(jnp.max(jnp.abs(g - w))) <= 2e-4 * scale, name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_with_one_decay_a_head_it_is_the_scalar_kernels_rule(dtype):
    """g constant over a head's channels is "gdn"'s rule: the per-channel
    kernels against the scalar ones on the same inputs, outputs and
    gradients; g's gradient a channel sums to the scalar's."""
    with jax.enable_x64(False):
        q, k, v, g, beta = _inputs(150, strong=False, dtype=dtype)
        scalar = g[..., 0]
        wide = jnp.broadcast_to(scalar[..., None], g.shape)
        got = _rule(q, k, v, wide, beta).astype(jnp.float32)
        want = _rule(q, k, v, scalar, beta).astype(jnp.float32)
        tol = 3e-5 if dtype == jnp.float32 else 2e-2
        scale = float(jnp.max(jnp.abs(want)))
        assert float(jnp.max(jnp.abs(got - want))) <= tol * scale
        cot = jax.random.normal(jax.random.PRNGKey(9), want.shape,
                                jnp.float32)
        ours = list(_grads(gated_delta_rule, (q, k, v, wide, beta), cot))
        theirs = _grads(gated_delta_rule, (q, k, v, scalar, beta), cot)
        ours[3] = jnp.sum(ours[3], axis=-1)
    for name, g_, w in zip("q k v g beta".split(), ours, theirs):
        g_, w = g_.astype(jnp.float32), w.astype(jnp.float32)
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        assert float(jnp.max(jnp.abs(g_ - w))) <= 10 * tol * scale, name


def _defined(x, k, b, held):
    """sum_c x_ic exp(b_ic - b_jc) k_jc over the pairs (i, j) that `held`
    holds, 0 elsewhere, the exponent masked: the definition written out, in
    float64."""
    diff = jnp.where(held[None, :, :, None],
                     b[:, :, None, :] - b[:, None, :, :], -jnp.inf)
    return jnp.einsum("hic,hijc,hjc->hij", x, jnp.exp(diff), k)


@pytest.mark.parametrize("side", ["levels", "cotangents"])
@pytest.mark.parametrize("c", [8, 16, 64])
@pytest.mark.parametrize("strong", [False, True], ids=["mild", "strong"])
def test_the_decayed_products_are_their_definition(strong, c, side):
    """G[x, k]_ij = sum_c x_ic exp(b_ic - b_jc) k_jc for j <= i and exactly
    0 above the diagonal against the sum written out in float64 with the
    exponent masked: under the strong decay too, where a factor exp(-b) does
    not exist in float32. "levels": each level of the halving is the
    definition over its own pairs, the levels' pairs are disjoint and
    together the lower triangle, and their sum is G. "cotangents":
    `_channel_grams_back` is `jax.vjp` of the definition for what q's rows
    get, what k's rows get as G[k, k]'s rows before beta, and what they get
    as the columns of both."""
    heads, dk = 2, 8
    _, k, _, g, beta = _inputs(c, strong=strong, batch=1, heads=heads, dk=dk)
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(keys[0], (heads, c, dk), jnp.float32)
    k, b, scale = k[0], jnp.cumsum(g[0], axis=1), beta[0][:, :, None]
    if strong:
        with np.errstate(over="ignore"):
            assert not np.all(np.isfinite(np.exp(-np.asarray(b))))
    b64, x64, k64 = (a.astype(jnp.float64) for a in (b, x, k))
    upto = np.arange(c)[:, None] >= np.arange(c)[None, :]
    below = upto & ~np.eye(c, dtype=bool)
    levels = gated_delta._levels(c)
    assert levels == [c >> n for n in range(c.bit_length())]
    if side == "levels":
        seen = np.zeros((c, c), int)
        level = jax.jit(gated_delta._channel_gram_level, static_argnums=3)
        with jax.enable_x64(False):
            whole = gated_delta._each_head(
                jax.jit(gated_delta._channel_grams), x, k, b)
            parts = [gated_delta._each_head(
                lambda x, k, b: level(x, k, b, s), x, k, b) for s in levels]
        for s, part in zip(levels, parts):
            either = np.asarray(gated_delta._level_mask((c, c), s))
            held = either & upto
            assert np.array_equal(either, held | held.T)
            assert np.array_equal(gated_delta._level_mask((2 * c, c), s),
                                  np.concatenate([either, either]))
            assert np.array_equal(gated_delta._level_mask((c, 2 * c), s),
                                  np.concatenate([either, either], axis=1))
            i, j = np.nonzero(held)
            assert np.all(i // s == j // s) and np.all(
                (j % s < s // 2) & (i % s >= s // 2) if s > 1 else i == j)
            seen += held
            for got, y64 in zip(part, (k64, x64)):
                got = np.asarray(got)
                assert np.all(got[:, ~either] == 0.0)
                np.testing.assert_allclose(
                    got[:, held], _defined(y64, k64, b64, held)[:, held],
                    atol=2e-5, rtol=2e-5)
        assert np.array_equal(seen, upto.astype(int))
        for got, y64 in zip(whole, (k64, x64)):
            assert np.all(np.asarray(got)[:, ~upto] == 0.0)
            np.testing.assert_allclose(got, _defined(y64, k64, b64, upto),
                                       atol=2e-5, rtol=2e-5)
        return
    dp = jnp.where(upto, jax.random.normal(keys[1], (heads, c, c)), 0.0)
    da = jnp.where(below, jax.random.normal(keys[2], (heads, c, c)), 0.0)
    dp, da = dp.astype(jnp.float32), da.astype(jnp.float32)
    with jax.enable_x64(False):
        got = gated_delta._each_head(
            jax.jit(gated_delta._channel_grams_back), dp, da,
            jnp.swapaxes(dp, 1, 2), jnp.swapaxes(da * scale, 1, 2), x, k, b)

    def grams(q, k_rows, k_cols):
        return (_defined(q, k_cols, b64, upto),
                _defined(k_rows, k_cols, b64, below))

    _, vjp = jax.vjp(grams, x64, k64, k64)
    dq, dk_i, _ = vjp((dp.astype(jnp.float64), da.astype(jnp.float64)))
    dk_j = vjp((dp.astype(jnp.float64), (da * scale).astype(jnp.float64)))[2]
    for name, ours, want in zip(("q", "k as rows", "k as columns"), got,
                                (dq, dk_i, dk_j)):
        assert ours.dtype == jnp.float32 and ours.shape == (heads, c, dk)
        np.testing.assert_allclose(ours, want, atol=3e-5, rtol=3e-5,
                                   err_msg=name)


def _counted(jaxpr, counts):
    """Of a traced helper, sub-jaxprs and all: matrix products, float32
    registers through `exp`, and registers reduced or broadcast along the
    lanes (the last axis)."""
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _counted(sub, counts)
        shape = eqn.invars[0].aval.shape if eqn.invars else ()
        registers = int(np.prod(shape[:-2], dtype=int)) * -(-shape[-2] // 8) \
            * -(-shape[-1] // 128) if len(shape) >= 2 else 0
        name = eqn.primitive.name
        if name == "dot_general":
            counts["products"] += 1
        elif name == "exp":
            counts["exp_registers"] += registers
        elif name.startswith("reduce_") and len(shape) - 1 in eqn.params[
                "axes"]:
            counts["lane_reductions"] += registers
        elif name == "broadcast_in_dim" and shape and shape[-1] == 1 \
                and eqn.outvars[0].aval.shape[-1] > 1:
            counts["lane_broadcasts"] += registers
    return counts


def test_what_the_decayed_products_cost_is_counted_from_the_helpers():
    """`channel_gram_work` (what `chip_smoke.py` prints) against the traced
    `_channel_grams` and `_channel_grams_back` of one head at the cell's
    chunk and width: a product a level forward and two backward, one
    whole-chunk `exp` a level but the diagonal's, no lane reduction and no
    lane broadcast."""
    c, dk = 64, 128
    x = jax.ShapeDtypeStruct((c, dk), jnp.bfloat16)
    b = jax.ShapeDtypeStruct((c, dk), jnp.float32)
    d = jax.ShapeDtypeStruct((c, c), jnp.float32)
    zero = dict.fromkeys(("products", "exp_registers", "lane_reductions",
                          "lane_broadcasts"), 0)
    with jax.enable_x64(False):
        forward = _counted(jax.make_jaxpr(gated_delta._channel_grams)(
            x, x, b).jaxpr, dict(zero))
        backward = _counted(jax.make_jaxpr(gated_delta._channel_grams_back)(
            d, d, d, d, x, x, b).jaxpr, dict(zero))
    said = channel_gram_work(c, dk)
    assert said["levels"] == 7
    assert {name: (forward[name], backward[name]) for name in zero} == {
        name: said[name] for name in zero}
    assert said["products"] == (7, 14) and said["exp_registers"] == (48, 48)
    assert channel_gram_work(8, 16)["exp_registers"] == (3, 3)


@pytest.mark.parametrize("reverse", [False, True], ids=["L g", "L^T db"])
@pytest.mark.parametrize("c", [8, 16, 64])
def test_the_running_sums_in_a_kernel_are_float64s(c, reverse):
    """`_running_sum` inside a kernel, as both per-channel kernels call it
    (a head at a time on the block's (c, dk) float32): b = L g under the
    strong decay (up to 60 a token: b to -3,800 at 64 rows) against
    float64's running sum within float32's rounding of the chunk's largest
    |b| (a tree of log2(c) additions a row: half a unit in the last place
    each; the rows of a padded tail, g = 0, hold b to that rounding and not
    to the bit, each row being a tree of its own), a channel that does not
    decay exactly 0; and L^T db, the sum from a row to the last, of
    cotangents of both signs."""
    heads, dk = 2, 8
    _, _, _, g, _ = _inputs(c, strong=True, batch=1, heads=heads, dk=dk)
    x = g[0].at[:, c - 3:].set(0.0)
    if reverse:
        x = x * jax.random.normal(jax.random.PRNGKey(5), x.shape,
                                  jnp.float32)

    def kernel(x_ref, y_ref):
        for h in range(heads):
            y_ref[h] = gated_delta._running_sum(x_ref[h], reverse)

    with jax.enable_x64(False):
        got = np.asarray(pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32))(x))
    x64 = np.asarray(x, np.float64)
    want = np.flip(np.cumsum(np.flip(x64, 1), axis=1), 1) if reverse \
        else np.cumsum(x64, axis=1)
    largest = np.cumsum(np.abs(x64), axis=1).max()
    if c == 64 and not reverse:
        assert want.min() < -1500
    steps = (c - 1).bit_length()
    assert np.abs(got - want).max() <= steps * 2.0 ** -24 * largest
    if not reverse:
        assert np.all(got[..., ::3] == 0.0)


def _equations(jaxpr):
    """Every equation of a jaxpr and of what it calls, but a kernel's
    body."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _equations(sub)


def test_no_pass_outside_the_kernels_sums_g():
    """The gradient of the rule with a decay a channel, traced: no `cumsum`
    and no `reduce_window`, and of (B, H, S, dk) float32 arrays only g,
    which comes in, and its cotangent, which the backward kernel writes: b
    and db are never in HBM. The kernels keep their operand and result
    counts, g where b stood. The scalar form still sums in `jnp`."""
    with jax.enable_x64(False):
        q, k, v, g, beta = _inputs(128, strong=False, dtype=jnp.bfloat16)
        assert g.shape == (2, 2, 128, 8) and g.dtype == jnp.float32

        def traced(g):
            return list(_equations(jax.make_jaxpr(jax.grad(
                lambda *a: jnp.sum(gated_delta_rule(*a).astype(jnp.float32)),
                argnums=(0, 1, 2, 3, 4)))(q, k, v, g, beta).jaxpr))

        per_channel, scalar = traced(g), traced(g[..., 0])
    summing = ("cumsum", "cumlogsumexp", "cumprod", "reduce_window",
               "reduce_window_sum")
    assert not [e for e in per_channel if e.primitive.name in summing]
    assert sum(e.primitive.name == "cumsum" for e in scalar) == 2
    wide = [(e.primitive.name, i) for e in per_channel
            for i, out in enumerate(e.outvars)
            if out.aval.shape == g.shape and out.aval.dtype == jnp.float32]
    assert wide == [("pallas_call", 3)]
    kernels = [e for e in per_channel if e.primitive.name == "pallas_call"]
    assert [(len(e.invars), len(e.outvars)) for e in kernels] == [(5, 5),
                                                                  (10, 5)]
    for e in kernels:
        assert g.shape in [x.aval.shape for x in e.invars
                           if x.aval.dtype == jnp.float32]


def test_what_the_running_sums_cost_is_counted_from_the_helper():
    """`running_sum_work` (what `chip_smoke.py` prints) against the traced
    `_running_sum` of one head at the cell's chunk and width: six doubling
    steps, each one roll of the chunk's eight registers, forward; the
    backward kernel sums g again and b's cotangent in reverse; no product."""
    c, dk = 64, 128
    g = jax.ShapeDtypeStruct((c, dk), jnp.float32)
    zero = dict.fromkeys(("steps", "rolled_registers", "products"), 0)

    def counted(reverse, counts):
        with jax.enable_x64(False):
            jaxpr = jax.make_jaxpr(functools.partial(
                gated_delta._running_sum, reverse=reverse))(g).jaxpr
        for eqn in _equations(jaxpr):
            if eqn.primitive.name == "roll":
                rows, lanes = eqn.invars[0].aval.shape
                counts["steps"] += 1
                counts["rolled_registers"] += -(-rows // 8) * -(-lanes // 128)
            counts["products"] += eqn.primitive.name == "dot_general"
        return counts

    forward = counted(False, dict(zero))
    backward = counted(True, counted(False, dict(zero)))
    said = running_sum_work(c, dk)
    assert {name: (forward[name], backward[name]) for name in zero} == said
    assert said == {"steps": (6, 12), "rolled_registers": (48, 96),
                    "products": (0, 0)}
    assert running_sum_work(8, 16)["steps"] == (3, 6)
    assert running_sum_work(24, 256)["rolled_registers"] == (30, 60)


def test_what_the_forward_saves_for_the_backward_pass():
    """W in the inputs' type; U_0, T beside P and each chunk's entry state
    in float32, the state transposed (dv x dk) and equal to the recurrence's
    at the same token; T unit lower triangular, P zero above the
    diagonal. The kernel takes g itself: b is its own to make."""
    with jax.enable_x64(False):
        q, k, v, g, beta = _inputs(192, strong=False, batch=1, heads=2,
                                   dtype=jnp.bfloat16)
        c, heads = 64, 2
        _, (w, u0, tp, s0) = gated_delta._forward(
            q, k, v, g, beta.reshape(2, 3, 1, c), c=c, heads=heads,
            save=True)
    assert w.dtype == jnp.bfloat16
    assert {u0.dtype, tp.dtype, s0.dtype} == {jnp.dtype("float32")}
    assert s0.shape == (2, 3, 16, 8) and tp.shape == (2, 192, 128)
    t, p = (np.asarray(tp).reshape(2, 3, 64, 2, 64)[:, :, :, i]
            for i in (0, 1))
    above = np.arange(64)[:, None] < np.arange(64)[None, :]
    assert np.all(t[..., above] == 0) and np.all(p[..., above] == 0)
    assert np.all(t[..., np.arange(64), np.arange(64)] == 1)
    assert np.abs(p).max() > 0.1
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    state = jnp.zeros((2, 8, 16), jnp.float32)
    for tok in range(128):
        state = jnp.exp(g[0, :, tok])[:, :, None] * state
        u = beta[0, :, tok, None] * (vf[0, :, tok] - jnp.einsum(
            "hkv,hk->hv", state, kf[0, :, tok], precision="highest"))
        state = state + kf[0, :, tok, :, None] * u[:, None, :]
        if tok + 1 in (64, 128):
            np.testing.assert_allclose(
                s0[:, (tok + 1) // 64], jnp.swapaxes(state, 1, 2),
                atol=2e-2, rtol=2e-2)      # u is a bf16 operand
    assert float(jnp.max(jnp.abs(s0[:, 0]))) == 0.0


def test_heads_a_grid_step_at_the_cells_widths():
    """`kimilinear-1chip`: 32 heads of 128 | 128 in bf16: four a step (the
    scalar form takes four there too); the per-channel blocks are b and its
    cotangent and P more."""
    assert heads_a_step(32, 128, 128, per_channel=True) == 4
    assert heads_a_step(32, 128, 128) == 4
    more = step_bytes(128, 128, per_channel=True) - step_bytes(128, 128)
    assert more == 2 * 4 * (2 * 64 * 128 + 64 * 64)
    assert step_bytes(128, 128, per_channel=True) * 4 \
        <= gated_delta._VMEM_BUDGET


def test_g_tells_the_form_by_its_shape(monkeypatch):
    """(B, H, S) runs the scalar kernels, (B, H, S, dk) the per-channel
    ones: no option chooses."""
    ran = []
    real = gated_delta.pallas_call
    monkeypatch.setattr(gated_delta, "pallas_call", lambda kernel, **kw: (
        ran.append(kernel.__name__) or real(kernel, **kw)))
    with jax.enable_x64(False):
        q, k, v, g, beta = _inputs(64, strong=False, batch=1, heads=1)
        gated_delta_rule(q, k, v, g[..., 0], beta)
        assert ran == ["_forward_kernel"]
        gated_delta_rule(q, k, v, g, beta)
    assert ran == ["_forward_kernel", "_channel_forward_kernel"]
