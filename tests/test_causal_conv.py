"""`ops/causal_conv.py`: the Pallas kernels (interpreted here) against the
plain `jnp` form, `reference_causal_conv_silu`: values, the gradients of u
and of the taps, with and without the L2 normalisation; float32 and bf16;
any tap count, what leaks where, the static numbers. The shapes that differ
in kind (lengths below the tap count, off the tile and of several tiles,
several sequences and heads, the cell's widths and an odd one):
`tests/test_causal_conv_shapes.py`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import olmo_hybrid as reference
from horovod_tpu.ops import causal_conv
from horovod_tpu.ops.causal_conv import (causal_conv_silu, heads_a_step,
                                         least_bytes,
                                         reference_causal_conv_silu, strip_rows,
                                         tile_of)

F32, BF16 = jnp.float32, jnp.bfloat16


def _grad(fun, **kw):
    """`jax.grad` as one compiled program: eagerly a kernel's forward and
    backward passes are a trace and a compile an operation."""
    return jax.jit(jax.grad(fun, **kw))


def _inputs(shape, taps=4, dtype=F32, seed=0):
    batch, heads, seq, width = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    u = jax.random.normal(ks[0], shape, F32).astype(dtype)
    w = (0.5 * jax.random.normal(ks[1], (heads, width, taps), F32)).astype(
        dtype)
    cot = jax.random.normal(ks[2], shape, F32)
    return u, w, cot


@pytest.fixture()
def tile(request, monkeypatch):
    """The tokens a grid step takes, for this test: small, so that a short
    sequence is several tiles."""
    monkeypatch.setattr(causal_conv, "TILE", request.param)


def tiles_of(n):
    return pytest.mark.parametrize("tile", [n], indirect=True)


def _value_and_grads(fn, u, w, cot, l2_scale):
    def weighed(u, w):
        return jnp.sum(fn(u, w, l2_scale=l2_scale).astype(F32) * cot)

    return (fn(u, w, l2_scale=l2_scale),
            *_grad(weighed, argnums=(0, 1))(u, w))


def _rel(got, want):
    got, want = (np.asarray(x.astype(F32)) for x in (got, want))
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


@pytest.mark.parametrize("l2_scale", [None, 96 ** -0.5],
                         ids=["plain", "normed"])
@pytest.mark.parametrize("taps", [1, 2, 4])
@tiles_of(64)
def test_any_tap_count_up_to_the_halo(tile, taps, l2_scale):
    u, w, cot = _inputs((1, 2, 150, 24), taps=taps, seed=1)
    got = _value_and_grads(causal_conv_silu, u, w, cot, l2_scale)
    want = _value_and_grads(reference_causal_conv_silu, u, w, cot, l2_scale)
    for name, g, r in zip(("y", "du", "dw"), got, want):
        assert _rel(g, r) < 2e-6, name


@pytest.mark.parametrize("l2_scale", [None, 1.0], ids=["plain", "normed"])
@pytest.mark.parametrize("width", [96, 192])
@tiles_of(128)
def test_bf16_is_float32_inside_and_rounded_once(tile, width, l2_scale):
    """bf16 in, bf16 out: the result is the float32 form's of the same bf16
    inputs, rounded once (a relative error of 2**-8 at most, 2**-8.7 in the
    rms), and so are du and dw."""
    u, w, cot = _inputs((1, 3, 200, width), dtype=BF16, seed=2)
    got = _value_and_grads(causal_conv_silu, u, w, cot, l2_scale)
    want = _value_and_grads(reference_causal_conv_silu, u.astype(F32),
                            w.astype(F32), cot, l2_scale)
    for name, g, r in zip(("y", "du", "dw"), got, want):
        assert g.dtype == BF16, name
        assert _rel(g, r) < 2.0 ** -8.5, name
    # and bit for bit what the jnp form gives from the same float32 values,
    # up to float32's own rounding: the two round at the same place
    same = reference_causal_conv_silu(u, w, l2_scale=l2_scale)
    assert np.mean(np.asarray(got[0] != same)) < 1e-3


@tiles_of(64)
def test_nothing_leaks_across_sequences_heads_or_backwards(tile):
    """A change at (sequence 1, head 2, token 70) moves that row's tokens
    70..73 and nothing else; with the norm, the same (a row's norm is its
    own)."""
    u, w, _ = _inputs((2, 3, 150, 8), seed=3)
    for l2_scale in (None, 1.0):
        changed = np.argwhere(np.any(np.asarray(
            causal_conv_silu(u, w, l2_scale=l2_scale)
            != causal_conv_silu(u.at[1, 2, 70].add(1.0), w,
                                l2_scale=l2_scale)), axis=-1))
        assert changed.tolist() == [[1, 2, t] for t in (70, 71, 72, 73)]


def test_the_convolution_is_causal():
    """A change at token t moves nothing before t, in the mixer's output
    and so in the model's logits; and the kernel is the Olmo-Hybrid
    reference's shifted adds."""
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 20, 8), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(3), (3, 8, 4), jnp.float32)
    base = causal_conv_silu(u, w)
    moved = causal_conv_silu(u.at[:, :, 11].add(1.0), w)
    changed = np.flatnonzero(np.any(np.asarray(base != moved),
                                    axis=(0, 1, 3)))
    assert changed.tolist() == [11, 12, 13, 14]       # four taps
    # (the reference's layout is (B, S, H, d))
    want = reference.causal_conv(u.transpose(0, 2, 1, 3), w)
    np.testing.assert_allclose(base, want.transpose(0, 2, 1, 3), atol=1e-6)


@tiles_of(64)
def test_the_gradient_of_an_early_token_holds_the_later_tiles_rows(tile):
    """du_t takes dy of t..t+3: with a cotangent on token 64 alone (the
    first of the second tile), du is non-zero at 61..64 (the later side's
    halo crosses the tile's edge) and nowhere else."""
    u, w, _ = _inputs((1, 1, 128, 8), seed=4)
    cot = jnp.zeros(u.shape, F32).at[0, 0, 64].set(1.0)
    for fn in (causal_conv_silu, reference_causal_conv_silu):
        du = _grad(lambda u: jnp.sum(fn(u, w) * cot))(u)
        rows = np.flatnonzero(np.any(np.asarray(du != 0), axis=(0, 1, 3)))
        assert rows.tolist() == [61, 62, 63, 64]


def test_the_normed_rows_have_the_scales_length():
    u, w, _ = _inputs((1, 2, 40, 96), seed=5)
    y = causal_conv_silu(u, w, l2_scale=0.25)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=-1), 0.25,
                               rtol=1e-4)


def test_other_float_types_go_through_float32():
    u, w, _ = _inputs((1, 1, 20, 8), dtype=jnp.float16, seed=6)
    y = causal_conv_silu(u, w)
    assert y.dtype == jnp.float16
    want = reference_causal_conv_silu(u.astype(F32), w)
    np.testing.assert_allclose(np.asarray(y, np.float32), want, atol=2e-3)


def test_the_static_numbers():
    # a strip is sixteen float32 registers an array: 128 rows at one lane
    # tile of width, 64 at two, twice that with the norm; never fewer than
    # the halo's 16, and it divides the tile
    assert strip_rows(96, 1024, False) == 128
    assert strip_rows(192, 1024, False) == 64
    assert strip_rows(96, 1024, True) == 256
    assert strip_rows(1000, 1024, False) == 16
    assert strip_rows(96, 192, True) == 64 and strip_rows(96, 48, True) == 16
    # a short sequence is one tile of whole strips
    assert causal_conv.TILE % causal_conv._HALO == 0
    assert tile_of(8192) == 1024 and tile_of(64) == 128
    assert tile_of(3) == 128 and tile_of(200) == 256
    with pytest.raises(ValueError):      # the halo holds 16 rows
        causal_conv_silu(*_inputs((1, 1, 8, 8), taps=18)[:2])
    # the cell: 30 heads; a 96-wide head's tile of 1,024 bf16 rows takes a
    # whole lane tile, 256 KiB: five heads a step; 192 wide, two
    assert heads_a_step(30, 96, 1024) == 5
    assert heads_a_step(30, 192, 1024) == 2
    assert heads_a_step(7, 192, 1024) == 1 and heads_a_step(3, 8, 64) == 3
    # a forward pass moves a row twice, a backward pass three times, each
    # in whole lane tiles
    assert least_bytes(96) == (2 * 128 * 2, 3 * 128 * 2)
    assert least_bytes(192, 4) == (2 * 256 * 4, 3 * 256 * 4)
    assert causal_conv._HALO >= 4 - 1
