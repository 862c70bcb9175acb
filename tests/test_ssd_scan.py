"""The chunked state-space dual scan of `ops/ssd_scan.py` (the Pallas
kernels, run by the interpreter here) against its `jnp` form and against a
token-by-token float32 loop: outputs and all six gradients, at a length that
is several chunks and at one that ends inside a chunk, several blocks of
heads, heads narrower and wider than a lane tile, bf16 operands; the counts
it is sized by; and what it refuses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import ssd_scan as ssd
from horovod_tpu.ops.ssd_scan import (chunked_ssd_scan, recurrent_ssd_scan,
                                      ssd_scan)

NAMES = ("x", "dt", "a_log", "b", "c", "d_skip")


def _grad(fun, **kw):
    """`jax.grad` as one compiled program: eagerly a kernel's forward and
    backward passes are a trace and a compile an operation."""
    return jax.jit(jax.grad(fun, **kw))


def _inputs(batch=2, seq=40, heads=4, width=8, states=16,
            dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (batch, seq, heads * width), dtype)
    dt = jax.nn.softplus(
        jax.random.normal(ks[1], (batch, seq, heads), jnp.float32) - 1.0)
    a_log = jnp.log(jax.random.uniform(ks[2], (heads,), jnp.float32, 1.0,
                                       16.0))
    b = jax.random.normal(ks[3], (batch, seq, states), dtype)
    c = jax.random.normal(ks[4], (batch, seq, states), dtype)
    d = jax.random.normal(ks[5], (heads,), jnp.float32)
    return x, dt, a_log, b, c, d


def _by_hand(x, dt, a_log, b, c, d):
    """The recurrence in numpy, one token and head at a time."""
    x, dt, a_log, b, c, d = (np.asarray(v, np.float64)
                             for v in (x, dt, a_log, b, c, d))
    batch, seq, channels = x.shape
    heads = dt.shape[-1]
    width = channels // heads
    y = np.zeros_like(x)
    for n in range(batch):
        state = np.zeros((heads, width, b.shape[-1]))
        for t in range(seq):
            for h in range(heads):
                lanes = slice(h * width, (h + 1) * width)
                state[h] = np.exp(-np.exp(a_log[h]) * dt[n, t, h]) \
                    * state[h] + dt[n, t, h] * np.outer(x[n, t, lanes],
                                                        b[n, t])
                y[n, t, lanes] = state[h] @ c[n, t] + d[h] * x[n, t, lanes]
    return y


def test_the_token_loop_is_the_recurrence_by_hand():
    args = _inputs(batch=1, seq=9, heads=2, width=3, states=5)
    with jax.enable_x64(False):
        got = recurrent_ssd_scan(*args)
    np.testing.assert_allclose(got, _by_hand(*args), rtol=2e-5, atol=2e-5)


#: (tokens, chunk): several whole chunks; a length that ends inside a chunk;
#: one chunk that the sequence does not fill; the module's chunk
LENGTHS = [(48, 16), (40, 16), (20, 256), (72, 32)]


@pytest.mark.parametrize("seq, chunk", LENGTHS)
def test_outputs_equal_the_token_loop_and_the_jnp_form(seq, chunk):
    args = _inputs(seq=seq)
    with jax.enable_x64(False):
        got = ssd_scan(*args, chunk=chunk)
        oracle = chunked_ssd_scan(*args, chunk=chunk)
        want = recurrent_ssd_scan(*args)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(oracle, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got, oracle, rtol=2e-5, atol=2e-5)


@pytest.fixture(scope="module")
def gradients():
    """{(tokens, chunk): (the kernels' six gradients, the jnp form's, the
    token loop's)}."""
    found = {}
    with jax.enable_x64(False):
        for seq, chunk in LENGTHS[:2]:
            args = _inputs(seq=seq)
            weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

            def grads(fn):
                return _grad(lambda *a: jnp.sum(fn(*a) * weight),
                             argnums=tuple(range(6)))(*args)

            found[seq, chunk] = (
                grads(lambda *a: ssd_scan(*a, chunk=chunk)),
                grads(lambda *a: chunked_ssd_scan(*a, chunk=chunk)),
                grads(recurrent_ssd_scan))
    return found


@pytest.mark.parametrize("seq, chunk", LENGTHS[:2])
@pytest.mark.parametrize("which", range(6), ids=NAMES)
def test_each_gradient_equals_the_token_loops(gradients, seq, chunk, which):
    """Among them dt's and a_log's, which reach the kernels as running sums
    in two forms, and B's and C's, summed over the heads."""
    got, oracle, want = (side[which] for side in gradients[seq, chunk])
    assert got.shape == want.shape and got.dtype == want.dtype
    size = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5 * size)
    np.testing.assert_allclose(oracle, want, rtol=2e-4, atol=2e-5 * size)


@pytest.mark.parametrize("heads, width, a_step, a_tile", [
    (32, 8, 16, 16),      # two blocks of heads, a tile holds sixteen
    (6, 64, 6, 2),        # the cell's width: two heads a tile
    (3, 128, 3, 1),       # a head is a tile
    (2, 256, 2, 1),       # a head is two tiles
])
def test_blocks_of_heads_and_tiles_of_every_kind(heads, width, a_step,
                                                 a_tile):
    assert ssd.heads_a_step(heads, width) == a_step
    assert ssd.heads_a_tile(a_step, width) == a_tile
    args = _inputs(batch=1, seq=24, heads=heads, width=width, states=8,
                   seed=3)
    with jax.enable_x64(False):
        got = ssd_scan(*args, chunk=8)
        want = recurrent_ssd_scan(*args)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        ours = _grad(lambda *a: jnp.sum(ssd_scan(*a, chunk=8) ** 2),
                     argnums=tuple(range(6)))(*args)
        theirs = _grad(lambda *a: jnp.sum(recurrent_ssd_scan(*a) ** 2),
                       argnums=tuple(range(6)))(*args)
    for name, a, b in zip(NAMES, ours, theirs):
        np.testing.assert_allclose(
            a, b, rtol=5e-4, atol=5e-5 * float(jnp.max(jnp.abs(b))),
            err_msg=name)


def test_bf16_operands_round_once():
    """bf16 x, B, C with a float32 dt: y comes back in bf16, close to the
    float32 result of the same bf16 inputs; every gradient in its input's
    type, and close to the token loop's."""
    args = _inputs(seq=48, dtype=jnp.bfloat16)
    with jax.enable_x64(False):
        got = ssd_scan(*args, chunk=16)
        want = recurrent_ssd_scan(*(a.astype(jnp.float32) for a in args))
        ours = _grad(lambda *a: jnp.sum(ssd_scan(*a, chunk=16).astype(
            jnp.float32)), argnums=tuple(range(6)))(*args)
        theirs = _grad(lambda *a: jnp.sum(recurrent_ssd_scan(*a)),
                       argnums=tuple(range(6)))(
            *(a.astype(jnp.float32) for a in args))
    assert got.dtype == jnp.bfloat16
    scale = float(jnp.sqrt(jnp.mean(jnp.square(want))))
    assert float(jnp.sqrt(jnp.mean(jnp.square(
        got.astype(jnp.float32) - want)))) < 2 ** -6 * scale
    assert [g.dtype for g in ours] == [a.dtype for a in args]
    for name, a, b in zip(NAMES, ours, theirs):
        off = float(jnp.sqrt(jnp.mean(jnp.square(a.astype(jnp.float32) - b))))
        assert off < 2 ** -5 * float(jnp.sqrt(jnp.mean(jnp.square(b)))), name


def test_the_decays_gradient_survives_bf16_at_the_cells_widths():
    """Heads of 64 with 128 states in chunks of 256: the cotangent of the
    running sums is a difference of terms that nearly cancel behind each
    token. From y in bf16, or with dt x rounded on one side and not on the
    other, a_log's gradient was 13% to 45% off while every other was within
    0.4%; the backward kernel reads y in float32 and takes dt x as the
    products took it."""
    args = _inputs(batch=1, seq=512, heads=4, width=64, states=128,
                   dtype=jnp.bfloat16)
    exact = tuple(a.astype(jnp.float32) for a in args)
    with jax.enable_x64(False):
        weight = jax.random.normal(jax.random.PRNGKey(5), args[0].shape)
        ours = _grad(lambda *a: jnp.sum(ssd_scan(*a).astype(jnp.float32)
                                        * weight), argnums=(1, 2))(*args)
        theirs = _grad(lambda *a: jnp.sum(recurrent_ssd_scan(*a)
                                          * weight), argnums=(1, 2))(*exact)
    for name, a, b in zip(("dt", "a_log"), ours, theirs):
        off = float(jnp.sqrt(jnp.mean(jnp.square(a - b))))
        assert off < 0.02 * float(jnp.sqrt(jnp.mean(jnp.square(b)))), name


def test_the_state_is_carried_not_restarted():
    """A sequence's second half depends on its first: the scan of the whole
    differs from the scan of the halves run apart, and a strong decay
    underflows to nothing worse than zero."""
    x, dt, a_log, b, c, d = _inputs(batch=1, seq=16)
    with jax.enable_x64(False):
        whole = ssd_scan(x, dt, a_log, b, c, d, chunk=8)
        second = ssd_scan(x[:, 8:], dt[:, 8:], a_log, b[:, 8:], c[:, 8:], d,
                          chunk=8)
        strong = ssd_scan(x, dt * 1e4, a_log, b, c, d, chunk=8)
        loop = recurrent_ssd_scan(x, dt * 1e4, a_log, b, c, d)
    assert float(jnp.max(jnp.abs(whole[:, 8:] - second))) > 1e-3
    assert bool(jnp.all(jnp.isfinite(strong)))
    np.testing.assert_allclose(strong, loop, rtol=1e-4, atol=1e-2)


def test_other_types_go_through_float32():
    args = _inputs(batch=1, seq=8, dtype=jnp.float16)
    with jax.enable_x64(False):
        got = ssd_scan(*args, chunk=8)
        want = recurrent_ssd_scan(*args)
    assert got.dtype == jnp.float16
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), rtol=2e-2,
                               atol=2e-2)


def test_what_it_refuses():
    x, dt, a_log, b, c, d = _inputs(batch=1, seq=8)
    with pytest.raises(ValueError, match="chunk 12"):
        ssd_scan(x, dt, a_log, b, c, d, chunk=12)
    with pytest.raises(ValueError, match="chunk 4"):
        ssd.chunks_of(64, 4)
    with pytest.raises(ValueError, match="channels"):
        ssd_scan(x[..., :30], dt, a_log, b, c, d)
    with pytest.raises(ValueError, match="a_log"):
        ssd_scan(x, dt, a_log[:3], b, c, d)


def test_the_counts_by_hand():
    """The cell's scan: 64 heads of 64 with 128 states in chunks of 256."""
    assert ssd.CHUNK == 256 and ssd.chunks_of(4096) == 16
    assert ssd.chunks_of(4097) == 17 and ssd.chunks_of(40, 16) == 3
    assert ssd.heads_a_tile(64, 64) == 2 and ssd.heads_a_step(64, 64) == 16
    # a token: G once, 128 x 256; a head the whole 256 x 256 matrix times
    # its 64 inputs, the state read and written, 2 x 128 x 64: 2.13 M
    # multiply-adds against the recurrence's 1.05 M
    chunked = 128 * 256 + 64 * (256 * 64 + 2 * 128 * 64)
    assert chunked == 2_129_920
    assert ssd.chunked_over_recurrent_macs(64, 64, 128) == pytest.approx(
        chunked / (64 * 2 * 128 * 64)) == pytest.approx(2.031, abs=1e-3)
    assert ssd.chunked_over_recurrent_macs(64, 64, 128, 128) == \
        pytest.approx(1.516, abs=1e-3)
    # the entry states a backward pass reads: 16 chunks of (128, 4096) f32
    assert 16 * 128 * 4096 * 4 == 33_554_432
