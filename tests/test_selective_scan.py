"""The selective scan of `ops/selective_scan.py` (the Pallas kernels, run by
the interpreter here) against a token-by-token float32 loop: outputs and all
six gradients, across chunk edges, channel blocks walked in several pieces,
bf16 operands; and what it refuses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import selective_scan as ss
from horovod_tpu.ops.selective_scan import (chunk_of,
                                            reference_selective_scan,
                                            selective_scan)

NAMES = ("c", "delta", "A", "B", "C", "D_skip")


def _grad(fun, **kw):
    """`jax.grad` as one compiled program: eagerly a kernel's forward and
    backward passes are a trace and a compile an operation."""
    return jax.jit(jax.grad(fun, **kw))


def _inputs(batch=2, seq=32, channels=24, states=4, dtype=jnp.float32,
            seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    c = jax.random.normal(ks[0], (batch, seq, channels), dtype)
    delta = jax.nn.softplus(
        jax.random.normal(ks[1], (batch, seq, channels), jnp.float32) - 1.0)
    a = -jnp.exp(jax.random.normal(ks[2], (channels, states), jnp.float32))
    b = jax.random.normal(ks[3], (batch, seq, states), dtype)
    o = jax.random.normal(ks[4], (batch, seq, states), dtype)
    d = jax.random.normal(ks[5], (channels,), jnp.float32)
    return c, delta, a, b, o, d


def _by_hand(c, delta, a, b, o, d):
    """The recurrence in numpy, one token, channel and state at a time."""
    c, delta, a, b, o, d = (np.asarray(x, np.float64)
                            for x in (c, delta, a, b, o, d))
    batch, seq, channels = c.shape
    y = np.zeros_like(c)
    for n in range(batch):
        s = np.zeros(a.shape)
        for t in range(seq):
            s = np.exp(delta[n, t][:, None] * a) * s \
                + (delta[n, t] * c[n, t])[:, None] * b[n, t][None, :]
            y[n, t] = s @ o[n, t] + d * c[n, t]
    return y


def test_the_reference_is_the_recurrence_by_hand():
    args = _inputs(batch=1, seq=9, channels=5, states=3)
    with jax.enable_x64(False):
        got = reference_selective_scan(*args)
    np.testing.assert_allclose(got, _by_hand(*args), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("chunk", [8, 16, 32, 128])
def test_outputs_equal_the_token_loop_across_chunk_edges(chunk, monkeypatch):
    monkeypatch.setattr(ss, "CHUNK", chunk)   # 128, the module's: one chunk
    args = _inputs()
    with jax.enable_x64(False):
        got = selective_scan(*args)
        want = reference_selective_scan(*args)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.fixture(scope="module")
def gradients():
    """{chunk: (the kernels' six gradients, the token loop's)}."""
    args = _inputs()
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    found = {}
    with jax.enable_x64(False):
        want = _grad(lambda *a: jnp.sum(
            reference_selective_scan(*a) * weight), argnums=range(6))(*args)
        for chunk in (8, 32):
            with pytest.MonkeyPatch.context() as patch:
                patch.setattr(ss, "CHUNK", chunk)
                found[chunk] = (_grad(lambda *a: jnp.sum(
                    selective_scan(*a) * weight),
                    argnums=range(6))(*args), want)
    return found


@pytest.mark.parametrize("chunk", [8, 32])
@pytest.mark.parametrize("which", range(6), ids=NAMES)
def test_each_gradient_equals_the_token_loops(gradients, chunk, which):
    got, want = (side[which] for side in gradients[chunk])
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(
        got, want, rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(want))))


def test_a_channel_block_walked_in_pieces_and_several_blocks(monkeypatch):
    """256 channels as two blocks of 128, each walked 64 channels at a time,
    the maps replicated along 64 lanes: what the cell's 5,120 channels do
    at 1,024 | 512 | 128."""
    monkeypatch.setattr(ss, "_BLOCKS", (128,))
    monkeypatch.setattr(ss, "_SUBS", {"forward": (64,), "backward": (64,)})
    monkeypatch.setattr(ss, "_LANES", 32)
    monkeypatch.setattr(ss, "CHUNK", 8)
    args = _inputs(batch=1, seq=16, channels=256, states=8, seed=3)
    with jax.enable_x64(False):
        got = selective_scan(*args)
        want = reference_selective_scan(*args)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        ours = _grad(lambda *a: jnp.sum(selective_scan(*a) ** 2),
                     argnums=range(6))(*args)
        theirs = _grad(lambda *a: jnp.sum(
            reference_selective_scan(*a) ** 2), argnums=range(6))(*args)
    for name, a, b in zip(NAMES, ours, theirs):
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(b))),
            err_msg=name)


def test_bf16_operands_round_once(monkeypatch):
    """bf16 c, B, C with a float32 delta: y comes back in bf16, a rounding
    of the float32 result of the same bf16 inputs."""
    monkeypatch.setattr(ss, "CHUNK", 16)
    args = _inputs(dtype=jnp.bfloat16)
    with jax.enable_x64(False):
        got = selective_scan(*args)
        want = reference_selective_scan(*args)
        grads = _grad(lambda *a: jnp.sum(selective_scan(
            *a).astype(jnp.float32)), argnums=range(6))(*args)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=2 ** -7,
                               atol=2 ** -7)
    assert [g.dtype for g in grads] == [a.dtype for a in args]


def test_the_state_is_carried_not_restarted(monkeypatch):
    """A sequence's second half depends on its first: the scan of the whole
    differs from the scan of the halves run apart."""
    monkeypatch.setattr(ss, "CHUNK", 8)
    args = _inputs(batch=1, seq=16)
    with jax.enable_x64(False):
        whole = selective_scan(*args)
        c, delta, a, b, o, d = args
        second = selective_scan(c[:, 8:], delta[:, 8:], a, b[:, 8:],
                                o[:, 8:], d)
    assert float(jnp.max(jnp.abs(whole[:, 8:] - second))) > 1e-3


@pytest.mark.parametrize("seq, chunk", [(40, 16), (200, 128), (136, 128)])
def test_a_sequence_that_is_no_whole_number_of_chunks_is_refused(
        seq, chunk, monkeypatch):
    monkeypatch.setattr(ss, "CHUNK", chunk)
    args = _inputs(batch=1, seq=seq, channels=8)
    with pytest.raises(ValueError,
                       match=f"no whole number of chunks of {chunk}"):
        selective_scan(*args)


def test_the_chunk_by_hand():
    assert ss.CHUNK == 128 and chunk_of(8192) == 128 and chunk_of(40) == 40
    # the (S, E, N) states a backward pass never holds, at the cell's
    # 8,192 tokens x 5,120 channels x 16 states, against what it does hold
    assert 8192 * 5120 * 16 * 4 == 2_684_354_560
    assert 8192 // chunk_of(8192) * 16 * 5120 * 4 == 20_971_520
