"""Pallas flash-attention kernel numerics (forward AND gradients) against
the exact score-materializing oracle. Runs in interpret mode on the CPU
mesh; the identical kernel compiles on TPU. The grid of block pairs, three
and more blocks a side and the forward's row strips:
`tests/test_flash_attention_grid.py`."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.flash_attention import (_compiler_params,
                                             flash_attention,
                                             flash_attention_chunk)
from horovod_tpu.parallel.ring_attention import (
    blockwise_attention_reference)


def _grad(fun, **kw):
    """`jax.grad` as one compiled program: eagerly a kernel's forward and
    backward passes are a trace and a compile an operation."""
    return jax.jit(jax.grad(fun, **kw))


def _qkv(key, B=2, H=2, S=256, dh=64, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    mk = lambda k: jax.random.normal(k, (B, H, S, dh), dtype)  # noqa: E731
    return mk(ks[0]), mk(ks[1]), mk(ks[2])


def _written_out(q, k, v, causal, window):
    """(o, lse) with the scores materialised: `masked_attention_reference`
    for o, the same mask's logsumexp beside it."""
    from horovod_tpu.ops.flash_attention import masked_attention_reference
    Sq, Sk = q.shape[2], k.shape[2]
    group = q.shape[1] // k.shape[1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, group, axis=1),
                   precision="highest") * q.shape[-1] ** -0.5
    row, col = jnp.arange(Sq)[:, None], jnp.arange(Sk)[None, :]
    seen = jnp.ones((Sq, Sk), bool)
    if causal:
        seen = col <= row
    if window is not None:
        seen = seen & (col > row - window)
    lse = jax.nn.logsumexp(jnp.where(seen, s, -jnp.inf), axis=-1)
    if Sq != Sk:    # a ring hop's off-diagonal chunk: no mask to write out
        p = jnp.exp(s - lse[..., None])
        return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest"), lse
    return masked_attention_reference(q, k, v, causal, None, window), lse


@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_matches_reference(causal):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    got = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    want = blockwise_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_gradients_match_reference():
    q, k, v = _qkv(jax.random.PRNGKey(1), S=256)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        o = blockwise_attention_reference(q, k, v, causal=True)
        return jnp.sum(jnp.sin(o))

    gf = _grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = _grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name} mismatch")


def test_flash_small_seq_full_block():
    """S <= 1024 takes the kernel with block == S (always-legal tiling)."""
    q, k, v = _qkv(jax.random.PRNGKey(2), S=100)
    got = flash_attention(q, k, v, causal=True)
    want = blockwise_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_untileable_seq_falls_back():
    """S > 1024 with no 128-multiple divisor actually exercises the
    reference fallback branch (S=1100: _auto_block returns None)."""
    from horovod_tpu.ops.flash_attention import _auto_block, can_tile
    assert _auto_block(1100) is None
    assert not can_tile(1100)
    q, k, v = _qkv(jax.random.PRNGKey(4), S=1100, B=1, H=2)
    got = flash_attention(q, k, v, causal=True)
    want = blockwise_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_smaller_blocks():
    q, k, v = _qkv(jax.random.PRNGKey(3), S=256)
    got = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    want = blockwise_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# What each case runs (ops/flash_attention.py `_for_each_strip`):
#   lm-blocks        S=2048 in 1024 blocks, the LM cells': one block under the
#                    diagonal (unmasked), two on it (walked in 256-tiles),
#                    one above (skipped); head_dim 128
#   one-diagonal     S=1024 as a single block, walked in 256-tiles
#   tile-divides     S=512: one block, 256-tiles
#   small-tile       S=256: one block, the smaller tile (128)
#   no-tile-100/640  blocks no tile divides: one masked product
#   no-tile-192      one block of a lane tile and a half: one masked product
#   under-in-strips  S=1536 in 512 blocks: the three blocks under the diagonal
#                    walked in two 256-row strips each by the forward kernel,
#                    whole by the backward kernels
#   not-causal-strips  the same blocks with no mask: every block in strips
#   not-square       block_q != block_k: every crossed block one masked product
#   non-causal       one unmasked product a block
#   chunk-dlse       flash_attention_chunk, causal, with an lse cotangent
_PATHS = {
    "lm-blocks": dict(S=2048, dh=128),
    "one-diagonal": dict(S=1024),
    "tile-divides": dict(S=512),
    "small-tile": dict(S=256),
    "no-tile-100": dict(S=100),
    "no-tile-640": dict(S=640),
    "no-tile-192": dict(S=192),
    "under-in-strips": dict(S=1536, block_q=512, block_k=512),
    "not-causal-strips": dict(S=1024, block_q=512, block_k=512, causal=False),
    "not-square": dict(S=512, block_q=256, block_k=128),
    "non-causal": dict(S=512, causal=False),
    "chunk-dlse": dict(S=512, chunk=True),
}


@pytest.mark.parametrize("case", list(_PATHS))
def test_flash_paths_match_reference_forward_and_gradients(case):

    kw = dict(_PATHS[case])
    S, dh = kw.pop("S"), kw.pop("dh", 64)
    causal, chunk = kw.pop("causal", True), kw.pop("chunk", False)
    q, k, v = _qkv(jax.random.PRNGKey(S), B=1, H=1, S=S, dh=dh)
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)
    wl = jax.random.normal(jax.random.PRNGKey(8), q.shape[:3], jnp.float32)

    def loss_and_o(attn, q, k, v):
        if chunk:   # a non-zero cotangent for lse as well as for o
            o, lse = attn(q, k, v)
            return jnp.sum(o * w) + jnp.sum(lse * wl), o
        o = attn(q, k, v)
        return jnp.sum(o * w), o

    if chunk:
        flash = lambda q, k, v: flash_attention_chunk(  # noqa: E731
            q, k, v, causal=True)
        ref = lambda q, k, v: _written_out(  # noqa: E731
            q, k, v, True, None)
    else:
        flash = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, causal=causal, **kw)
        ref = lambda q, k, v: blockwise_attention_reference(  # noqa: E731
            q, k, v, causal=causal)

    def both(attn):
        return jax.jit(jax.value_and_grad(
            functools.partial(loss_and_o, attn),
            argnums=(0, 1, 2), has_aux=True))(q, k, v)

    (_, o), grads = both(flash)
    (_, o_ref), grads_ref = both(ref)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)
    for got, want, name in zip(grads, grads_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"{case}: d{name} mismatch")


def test_crossed_strips_cover_the_causal_half_exactly_once():
    """The strips of a diagonal block hold every (row, col <= row) once and,
    where a tile divides the block, nothing beyond the tile on the diagonal:
    what `causal_tile_share` counts is what the kernels multiply."""
    from horovod_tpu.ops.flash_attention import (_causal_tile,
                                                 _crossed_strips,
                                                 causal_tile_share)
    for bq, bk in ((1024, 1024), (512, 512), (256, 256), (640, 640),
                   (100, 100), (256, 128)):
        seen = np.zeros((bq, bk), np.int32)
        for row0, rows, cols in _crossed_strips(bq, bk):
            seen[row0:row0 + rows, :cols] += 1
        lower = np.tril(np.ones((bq, bk), bool))
        assert np.all(seen[lower] == 1) and seen.max() == 1, (bq, bk)
        t = _causal_tile(bq, bk)
        if t is None:
            assert seen.sum() == bq * bk
        else:   # one diagonal block is S = block
            assert seen.sum() == causal_tile_share(bq, bq, t) * bq * bq / 2
            above = np.triu(np.ones((bq, bk), bool), k=t)
            assert not seen[above].any()


def test_causal_tile_share_at_the_cells_shapes():
    from horovod_tpu.ops.flash_attention import causal_tile_share
    # what the kernels computed before the walk: whole blocks
    assert causal_tile_share(2048, 1024, 1024) == 1.5
    assert causal_tile_share(4096, 1024, 1024) == 1.25
    assert causal_tile_share(2048, 1024, 256) == 1.125
    assert causal_tile_share(4096, 1024, 256) == 1.0625
    # what they choose themselves: lm-1chip / lm-dp4, olmoe-1chip, the smoke
    assert causal_tile_share(2048) <= 1.125
    assert causal_tile_share(4096) <= 1.07
    assert causal_tile_share(1024) <= 1.25
    for S in (100, 128, 256, 512, 640, 1024, 2048, 4096, 8192, 32768):
        assert 1.0 <= causal_tile_share(S) <= 2.0, S


# ------------------------------------------------- a window, grouped K/V

def _banded_reference(q, k, v, window):
    """`blockwise_attention_reference` cannot leave keys out, so the mask is
    written out here: scores of every (query, key), the keys over the
    diagonal and under the band at -inf, a group's query heads against one
    repeated K/V head."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    row = jnp.arange(q.shape[2])[:, None]
    col = jnp.arange(q.shape[2])[None, :]
    seen = col <= row
    if window is not None:
        seen = seen & (col > row - window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


#: (S, block, window, query heads, K/V heads, dv): one block masked whole;
#: square blocks walked in tiles with the band's lower edge inside the
#: diagonal's block, in the block before it, and two blocks back; a group of
#: two and of four; values wider than keys (differential attention's)
_BANDED = {
    "one-block-w24-g2": (64, None, 24, 4, 2, 16),
    "one-block-w5-g4": (64, None, 5, 4, 1, 8),
    "no-window-g2": (256, 128, None, 4, 2, 8),
    "tiles-w300-g1": (2048, 512, 300, 2, 2, 16),
    "tiles-w700-g2": (1536, 512, 700, 2, 1, 8),
    "tiles-w100-t128": (1024, 256, 100, 2, 2, 8),
    "tiles-w1100-two-back": (2048, 512, 1100, 2, 1, 8),
    "window-wider-than-S": (128, 64, 500, 2, 1, 8),
}


@functools.lru_cache(maxsize=None)
def _banded_case(case):
    S, block, window, H, G, dv = _BANDED[case]
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (1, H, S, 8), jnp.float32)
    k = jax.random.normal(ks[1], (1, G, S, 8), jnp.float32)
    v = jax.random.normal(ks[2], (1, G, S, dv), jnp.float32)
    w = jax.random.normal(ks[3], (1, H, S, dv), jnp.float32)

    def ours(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=block, block_k=block)

    def theirs(q, k, v):
        return _banded_reference(q, k, v, window)

    out = [jax.jit(f)(q, k, v) for f in (ours, theirs)]
    grads = [_grad(lambda *a, f=f: jnp.sum(f(*a) * w),
                   argnums=(0, 1, 2))(q, k, v) for f in (ours, theirs)]
    return out, grads


@pytest.mark.parametrize("what", ["o", "dq", "dk", "dv"])
@pytest.mark.parametrize("case", list(_BANDED))
def test_window_and_group_match_the_mask_written_out(case, what):
    (got_o, want_o), (got_g, want_g) = _banded_case(case)
    at = "o dq dk dv".split().index(what)
    got, want = (got_o, want_o) if at == 0 else (got_g[at - 1],
                                                 want_g[at - 1])
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_windowed_strips_cover_the_band_exactly_once():
    """Every score entry the band holds lies in exactly one strip of exactly
    one block, and every strip marked unmasked lies wholly in the band."""
    from horovod_tpu.ops.flash_attention import (_windowed_strips,
                                                 window_back)
    for S, block, window in ((2048, 512, 300), (2048, 512, 1100),
                             (1024, 256, 100), (1024, 1024, 512),
                             (4096, 1024, 512)):
        seen = np.zeros((S, S), np.int32)
        row, col = np.arange(S)[:, None], np.arange(S)[None, :]
        band = (col <= row) & (col > row - window)
        for iq in range(S // block):
            for d in range(min(window_back(block, window), iq) + 1):
                ik = iq - d
                for r0, rows, c0, cols, masked in _windowed_strips(
                        block, block, window, d):
                    r = slice(iq * block + r0, iq * block + r0 + rows)
                    c = slice(ik * block + c0, ik * block + c0 + cols)
                    seen[r, c] += 1
                    if not masked:
                        assert band[r, c].all()
        assert seen.max() == 1 and (seen[band] == 1).all()


def test_window_tile_share_at_the_cells_shape():
    from horovod_tpu.ops.flash_attention import window_tile_share
    # 8 diagonal blocks of 9 tiles and 7 blocks before one of 3, 256² each,
    # over the band's 8,192 x 512 - 512 x 511 / 2 entries
    assert window_tile_share(8192, 512) == pytest.approx(
        (8 * 9 + 7 * 3) * 256 * 256 / (8192 * 512 - 512 * 511 / 2))
    assert window_tile_share(8192, 512) == pytest.approx(1.49991, abs=1e-5)
    # blocks walked whole: the band costs a quarter of all there is
    assert window_tile_share(8192, 512, t=1024) == pytest.approx(
        15 * 1024 * 1024 / (8192 * 512 - 512 * 511 / 2))


def test_a_window_needs_causal_attention_and_the_heads_must_divide():
    q, k, v = _qkv(jax.random.PRNGKey(3), H=4, S=64, dh=8)
    with pytest.raises(ValueError, match="a window needs causal"):
        flash_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="query heads over"):
        flash_attention(q, k[:, :3], v[:, :3], causal=True)
    with pytest.raises(ValueError, match="square blocks"):
        flash_attention(q, k, v, causal=True, window=8, block_q=32,
                        block_k=64)


# ------------------------- keys wider than values (latent attention), or narrower

def _qkv_widths(dqk, dv, seq=256, heads=2, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (1, heads, seq, dqk), dtype)
    k = jax.random.normal(ks[1], (1, heads, seq, dqk), dtype)
    v = jax.random.normal(ks[2], (1, heads, seq, dv), dtype)
    do = jax.random.normal(ks[3], (1, heads, seq, dv), dtype)
    return q, k, v, do


@pytest.mark.parametrize("dqk,dv,block", [(24, 16, None), (192, 128, None),
                                          (48, 32, 128), (16, 24, None)],
                         ids=["24-16", "192-128", "48-32-tiled", "16-24"])
def test_flash_kernels_at_unequal_widths_match_plain_attention(dqk, dv,
                                                               block):
    """Forward and all three gradients (interpreted); `block` 128 walks the
    diagonal block in strips, as the 1,024 blocks of S = 4,096 do."""
    q, k, v, do = _qkv_widths(dqk, dv, seq=512 if block else 256)
    scale = 1.5896 * dqk ** -0.5

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=scale,
                                  block_q=block and 4 * block,
                                  block_k=block and 4 * block)

    def plain(q, k, v):
        return blockwise_attention_reference(q, k, v, causal=True,
                                             scale=scale)

    def with_gradients(attn):   # one compiled program a side
        def both(q, k, v, do):
            out, vjp = jax.vjp(attn, q, k, v)
            return out, vjp(do)
        return jax.jit(both)(q, k, v, do)

    (out, grads), (want, want_grads) = (with_gradients(flash),
                                        with_gradients(plain))
    assert out.shape == v.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4,
                               atol=2e-4)
    for name, got, ref in zip("qkv", grads, want_grads):
        assert got.shape == ref.shape, name
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=5e-4, atol=5e-4, err_msg=name)


def test_flash_kernels_at_equal_widths_are_unchanged():
    """One width: no compiler parameter is added and the result is the
    plain one; the chunk API (ring hops) takes unequal widths too."""
    assert _compiler_params(128, 128) == {}
    assert _compiler_params(64, 128) == {}
    wide = _compiler_params(192, 128)["compiler_params"]
    assert wide.vmem_limit_bytes == 32 * 2 ** 20
    q, k, v, _ = _qkv_widths(32, 32)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=True)),
        np.asarray(blockwise_attention_reference(q, k, v, causal=True)),
        rtol=2e-4, atol=2e-4)
    q, k, v, _ = _qkv_widths(24, 16)
    o, lse = flash_attention_chunk(q, k, v, causal=True)
    assert o.shape == v.shape and lse.shape == q.shape[:3]
    np.testing.assert_allclose(
        np.asarray(o),
        np.asarray(blockwise_attention_reference(q, k, v, causal=True)),
        rtol=2e-4, atol=2e-4)
