"""`mixers.short_conv_mix`, what lies between a gated short-convolution
layer's two products (C * conv(B * X), `models/mixers.py`): causality, the
taps' order against `torch.nn.Conv1d(E, E, K, groups=E, padding=K - 1)(z)[...,
:S]` written out in `numpy`, and its gradients in bf16 against the float32
form's at three shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.mixers import short_conv_mix

#: (batch, tokens, channels, taps): a token fewer than the taps, the tiny
#: models' shape, and a lane tile and a half of channels with four taps
SHAPES = [(1, 2, 8, 3), (2, 32, 64, 3), (3, 17, 192, 4)]


def _operands(shape, dtype=jnp.float32, seed=0):
    batch, seq, width, taps = shape
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k1, (batch, seq, 3 * width), dtype),
            (jax.random.normal(k2, (width, taps), jnp.float32)
             * taps ** -0.5).astype(dtype))


def _conv1d(z, taps):
    """torch's `Conv1d(E, E, K, groups=E, padding=K - 1, bias=False)` on z:
    (B, E, S), a cross-correlation over the padded sequence, cut to its
    first S outputs: out[b, e, t] = sum_j taps[e, j] * padded[b, e, t + j],
    `padded` being K - 1 zeros, z, K - 1 zeros."""
    batch, width, seq = z.shape
    reach = taps.shape[1] - 1
    padded = np.zeros((batch, width, seq + 2 * reach), z.dtype)
    padded[:, :, reach:reach + seq] = z
    out = np.zeros((batch, width, seq + reach), z.dtype)
    for t in range(seq + reach):
        for j in range(reach + 1):
            out[:, :, t] += taps[None, :, j] * padded[:, :, t + j]
    return out[..., :seq]


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_the_taps_order_is_torchs(shape):
    """The last tap on the token itself, the first on the token K - 1
    before, zeros before the sequence; then the gate C."""
    bcx, taps = (np.asarray(x, np.float64) for x in _operands(shape))
    width = shape[2]
    b, c, x = (bcx[..., i * width:(i + 1) * width] for i in range(3))
    conv = _conv1d((b * x).transpose(0, 2, 1), taps).transpose(0, 2, 1)
    with jax.enable_x64(False):
        got = short_conv_mix(jnp.asarray(bcx, jnp.float32),
                             jnp.asarray(taps, jnp.float32))
    np.testing.assert_allclose(got, c * conv, rtol=2e-5, atol=2e-6)
    # a filter that is 1 on its last tap alone is the product of the gates
    last = np.zeros_like(taps)
    last[:, -1] = 1
    with jax.enable_x64(False):
        got = short_conv_mix(jnp.asarray(bcx, jnp.float32),
                             jnp.asarray(last, jnp.float32))
    np.testing.assert_allclose(got, b * c * x, rtol=2e-6, atol=1e-7)


def test_a_token_reads_no_later_one_and_none_before_its_reach():
    """Token t's output moves with tokens t - 2, t - 1 and t alone."""
    shape = (1, 12, 16, 3)
    bcx, taps = _operands(shape)
    with jax.enable_x64(False):
        jac = jax.jacobian(lambda u: short_conv_mix(u, taps)[0, :, 0])(bcx)
    moved = np.abs(np.asarray(jac[:, 0])).sum(-1) > 0     # (out t, in t)
    t_out, t_in = np.nonzero(moved)
    assert set(t_out - t_in) == {0, 1, 2}
    assert moved.sum() == 12 + 11 + 10


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_bf16_gradients_equal_the_float32_forms(shape):
    """The cell's dtype: operands in bf16, the sums in float32, one rounding
    of the result; every gradient against the float32 form on the same
    (bf16-representable) numbers."""
    bcx, taps = _operands(shape, jnp.bfloat16, seed=1)
    weight = jax.random.normal(jax.random.PRNGKey(5), shape[:2] + shape[2:3],
                               jnp.float32)

    def loss(u, w):
        return jnp.sum(short_conv_mix(u, w).astype(jnp.float32) * weight)

    with jax.enable_x64(False):
        assert short_conv_mix(bcx, taps).dtype == jnp.bfloat16
        got = jax.grad(loss, argnums=(0, 1))(bcx, taps)
        want = jax.grad(loss, argnums=(0, 1))(bcx.astype(jnp.float32),
                                              taps.astype(jnp.float32))
    for g, w in zip(got, want):
        assert g.dtype == jnp.bfloat16
        size = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(g.astype(jnp.float32), w, rtol=2e-2,
                                   atol=1e-2 * size)
