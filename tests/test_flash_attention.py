"""Pallas flash-attention kernel numerics (forward AND gradients) against
the exact score-materializing oracle. Runs in interpret mode on the CPU
mesh; the identical kernel compiles on TPU."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel.ring_attention import (
    blockwise_attention_reference)


def _qkv(key, B=2, H=2, S=256, dh=64, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    mk = lambda k: jax.random.normal(k, (B, H, S, dh), dtype)  # noqa: E731
    return mk(ks[0]), mk(ks[1]), mk(ks[2])


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

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
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
    from horovod_tpu.ops.flash_attention import flash_attention_chunk

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

    out = [f(q, k, v) for f in (ours, theirs)]
    grads = [jax.grad(lambda *a, f=f: jnp.sum(f(*a) * w),
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


# ------------------------------ a grid of only the blocks the mask holds

def _mask_holds(i, j, block_q, block_k, causal, window):
    """Whether any (query, key) of block pair (i, j) is in the mask, from
    the mask's own definition: key <= query, and with a window key > query -
    window. query - key of the block's entries spans [lo, hi]."""
    if not causal:
        return True
    lo = i * block_q - (j + 1) * block_k + 1
    hi = (i + 1) * block_q - 1 - j * block_k
    return hi >= 0 and (window is None or lo <= window - 1)


#: (S, block_q, block_k, causal, window, group): the cells' lengths in the
#: kernels' own 1,024-blocks, with the window of `phi4flash-1chip`, one that
#: reaches two blocks back, and a group of two; blocks that are not square
#: (the diagonal then leaves a row's last block anywhere); small blocks (63
#: compares a step); not causal (every pair: a rectangle, rows of one length)
_GRIDS = [
    pytest.param(S, 1024, 1024, True, window, group,
                 id=f"S{S}-w{window}-g{group}")
    for S in (2048, 4096, 8192) for window in (None, 512, 1500)
    for group in (1, 2)
] + [
    pytest.param(2048, 256, 128, True, None, 1, id="wide-query-blocks"),
    pytest.param(2048, 128, 512, True, None, 2, id="wide-key-blocks"),
    pytest.param(8192, 128, 128, True, 512, 1, id="small-blocks-w512"),
    pytest.param(4096, 1024, 1024, False, None, 2, id="not-causal"),
    pytest.param(2048, 1024, 512, False, None, 1, id="not-causal-not-square"),
    pytest.param(1024, 1024, 1024, True, None, 1, id="one-block"),
]


@pytest.mark.parametrize("S,block_q,block_k,causal,window,group", _GRIDS)
def test_the_grid_steps_are_the_block_pairs_the_mask_holds_each_once(
        S, block_q, block_k, causal, window, group):
    """The enumeration alone (`held_blocks`, `_Walk`: pure Python here). The
    forward and dq kernels' steps are the held pairs row-major, the dk/dv
    kernel's column-major with a group's query heads in turn: the order the
    whole rectangle had, so every sum adds in the parent's order. `_init`
    fires at a row's (column's) first held step and `_finish` at its last."""
    from horovod_tpu.ops.flash_attention import (_by_key_block, _Walk,
                                                 grid_step_share,
                                                 held_blocks)
    nq, nk = S // block_q, S // block_k
    held = [(i, j) for i in range(nq) for j in range(nk)
            if _mask_holds(i, j, block_q, block_k, causal, window)]
    rows = held_blocks(nq, nk, block_q, block_k, causal, window)

    walk = _Walk(rows)
    steps = [(int(walk.run(s)[0]), int(walk.at(s)))
             for s in map(np.int32, range(walk.steps))]   # as program_id is
    assert steps == held            # each once, row-major
    for s, (i, j) in enumerate(steps):
        first, last = (bool(x) for x in walk.where(np.int32(s))[2:])
        assert first == (s == 0 or steps[s - 1][0] != i)
        assert last == (s == len(steps) - 1 or steps[s + 1][0] != i)
    assert {i for i, _ in steps} == set(range(nq))     # every o block

    walk = _Walk(_by_key_block(rows, nk), heads=group)
    steps = [(*map(int, walk.run(s)), int(walk.at(s)))
             for s in map(np.int32, range(walk.steps))]
    for s, (j, _, _) in enumerate(steps):
        first, last = (bool(x) for x in walk.where(np.int32(s))[2:])
        assert first == (s == 0 or steps[s - 1][0] != j)
        assert last == (s == len(steps) - 1 or steps[s + 1][0] != j)
    assert steps == sorted((j, g, i) for i, j in held for g in range(group))
    assert {j for j, _, _ in steps} == set(range(nk))  # every dk, dv block

    if causal and block_q == block_k:
        assert grid_step_share(S, window, block_q) == 1.0


def test_grid_step_share_is_one_where_the_rectangle_ran_idle_steps():
    """What the whole rectangle of block pairs cost (docs/kernels.md keeps
    the parent's values): steps a head ran over steps that computed."""
    from horovod_tpu.ops.flash_attention import grid_step_share, window_back
    for S, window, was in ((2048, None, 4 / 3), (4096, None, 1.6),
                           (8192, None, 64 / 36), (8192, 512, 64 / 15)):
        n = S // 1024
        back = n if window is None else window_back(1024, window)
        computing = sum(min(i, back) + 1 for i in range(n))
        assert n * n / computing == pytest.approx(was)
        assert grid_step_share(S, window) == 1.0
    assert grid_step_share(1024) == 1.0 and grid_step_share(100) == 1.0


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


#: (Sq, Sk, block_q, block_k, causal, window, query heads, K/V heads, dlse):
#: three or more blocks a side, so a row has a first, a middle and a last
#: held step and rows differ in length
_MANY_BLOCKS = {
    "causal-3": (384, 384, 128, 128, True, None, 2, 2, False),
    "causal-5-g2": (320, 320, 64, 64, True, None, 4, 2, False),
    "window-one-back-g2": (512, 512, 128, 128, True, 100, 4, 2, False),
    "window-two-back": (512, 512, 128, 128, True, 200, 2, 2, False),
    "wide-query-blocks": (384, 384, 128, 64, True, None, 2, 2, False),
    "wide-key-blocks-g2": (384, 384, 64, 128, True, None, 2, 1, False),
    "not-causal-3x3": (384, 384, 128, 128, False, None, 2, 2, False),
    "chunk-causal-dlse": (384, 384, 128, 128, True, None, 2, 2, True),
    "chunk-3x4-dlse": (384, 512, 128, 128, False, None, 2, 2, True),
}


@functools.lru_cache(maxsize=None)
def _many_blocks_case(case):
    from horovod_tpu.ops import flash_attention as fa
    Sq, Sk, bq, bk, causal, window, H, G, with_dlse = _MANY_BLOCKS[case]
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    q = jax.random.normal(ks[0], (1, H, Sq, 8), jnp.float32)
    k = jax.random.normal(ks[1], (1, G, Sk, 8), jnp.float32)
    v = jax.random.normal(ks[2], (1, G, Sk, 16), jnp.float32)
    w = jax.random.normal(ks[3], (1, H, Sq, 16), jnp.float32)
    wl = jax.random.normal(ks[4], (1, H, Sq), jnp.float32) * with_dlse

    def ours(q, k, v):
        if with_dlse:
            return fa.flash_attention_chunk(q, k, v, causal=causal,
                                            block_q=bq, block_k=bk)
        return fa.flash_attention(q, k, v, causal=causal, window=window,
                                  block_q=bq, block_k=bk), 0.0

    def theirs(q, k, v):
        return _written_out(q, k, v, causal, window)

    def loss(f, q, k, v):
        o, lse = f(q, k, v)
        return jnp.sum(o * w) + jnp.sum(lse * wl)

    # the forward kernel's own second result, whichever entry point ran it
    o, lse = fa._fwd(q[0], k[0], v[0], causal, 8 ** -0.5, bq, bk, window)
    want_o, want_lse = theirs(q, k, v)
    got = [o[None], lse[None, ..., 0]]
    want = [want_o, want_lse]
    for f, into in ((ours, got), (theirs, want)):
        into.extend(jax.grad(functools.partial(loss, f),
                             argnums=(0, 1, 2))(q, k, v))
    return got, want


@pytest.mark.parametrize("what", ["o", "lse", "dq", "dk", "dv"])
@pytest.mark.parametrize("case", list(_MANY_BLOCKS))
def test_three_and_more_blocks_a_side_match_the_mask_written_out(case, what):
    got, want = _many_blocks_case(case)
    at = "o lse dq dk dv".split().index(what)
    assert got[at].shape == want[at].shape
    np.testing.assert_allclose(np.asarray(got[at]), np.asarray(want[at]),
                               rtol=2e-4, atol=2e-5)


# ------------------------ whole blocks in row strips (PR 49, `_fwd_kernel`)

#: (S, block, window, query heads, K/V heads, keys' width, values' width):
#: blocks of 512 (a block under the diagonal is two strips of 256 rows in
#: the forward kernel) and of 256 (one), three and four blocks a side; a
#: window whose lower edge crosses a block; one query head a K/V head and
#: seven; the cells' three pairs of widths
_STRIPS = {
    "g7-128-128": (1536, 512, None, 7, 1, 128, 128),
    "g1-128-128-w300": (768, 256, 300, 2, 2, 128, 128),
    "g1-192-128": (1536, 512, None, 2, 2, 192, 128),
    "g7-192-128-w500": (1024, 256, 500, 7, 1, 192, 128),
    "g7-64-128": (2048, 512, None, 7, 1, 64, 128),
    "g1-64-128-w700": (1536, 512, 700, 2, 2, 64, 128),
}


@functools.lru_cache(maxsize=None)
def _strips_case(case):
    """Scores built so that every strip's update matters: key j's score is
    raised by 8 for each block before its own, so a row's maximum rises in
    every key block and last in its LAST one (the diagonal's), where all
    that the strips before have summed is rescaled by a small alpha; and by
    6 more in ONE lane tile of its block (another one each block), which
    then carries e^6 of a strip's sum against 1 for each of the others."""
    from horovod_tpu.ops import flash_attention as fa
    S, block, window, H, G, dk, dv = _STRIPS[case]
    ks = jax.random.split(jax.random.PRNGKey(49), 4)
    q = jax.random.normal(ks[0], (1, H, S, dk), jnp.float32)
    k = jax.random.normal(ks[1], (1, G, S, dk), jnp.float32)
    v = jax.random.normal(ks[2], (1, G, S, dv), jnp.float32)
    w = jax.random.normal(ks[3], (1, H, S, dv), jnp.float32)
    col = jnp.arange(S)
    tiles = block // 128
    raised = 8.0 * (col // block) + 6.0 * (
        col % block // 128 == col // block % tiles)
    q = q.at[..., 0].set(dk ** 0.5)         # times scale: 1 a unit of k[0]
    k = k.at[..., 0].set(raised)

    def ours(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window,
                                  block_q=block, block_k=block)

    def theirs(q, k, v):
        return _written_out(q, k, v, True, window)[0]

    o, lse = fa._fwd(q[0], k[0], v[0], True, dk ** -0.5, block, block,
                     window)
    want_o, want_lse = _written_out(q, k, v, True, window)
    got, want = [o[None], lse[None, ..., 0]], [want_o, want_lse]
    for f, into in ((ours, got), (theirs, want)):
        into.extend(jax.grad(lambda *a, f=f: jnp.sum(f(*a) * w),
                             argnums=(0, 1, 2))(q, k, v))
    return got, want


@pytest.mark.parametrize("what", ["o", "lse", "dq", "dk", "dv"])
@pytest.mark.parametrize("case", list(_STRIPS))
def test_whole_blocks_in_strips_match_the_mask_written_out(case, what):
    got, want = _strips_case(case)
    at = "o lse dq dk dv".split().index(what)
    assert got[at].shape == want[at].shape
    np.testing.assert_allclose(np.asarray(got[at]), np.asarray(want[at]),
                               rtol=2e-4, atol=2e-5)


def test_whole_strips_cover_a_block_exactly_once():
    """The strips of a block no mask enters hold each of its entries once,
    256 rows a strip against all its columns where 256 divides the block's
    rows, else the block as one strip."""
    from horovod_tpu.ops.flash_attention import _whole_strips
    for bq, bk in ((1024, 1024), (512, 512), (256, 256), (128, 128),
                   (640, 640), (100, 100), (512, 128), (128, 512),
                   (768, 256)):
        seen = np.zeros((bq, bk), np.int32)
        strips = _whole_strips(bq, bk)
        for row0, rows, cols in strips:
            assert cols == bk
            seen[row0:row0 + rows, :cols] += 1
        assert (seen == 1).all(), (bq, bk)
        assert len(strips) == (bq // 256 if bq % 256 == 0 else 1), (bq, bk)
    # the strip the timings did not choose, for `row_strip_share`'s parent
    assert _whole_strips(1024, 1024, 1024) == [(0, 1024, 1024)]
    assert len(_whole_strips(1024, 1024, 512)) == 2


def test_only_the_forward_kernel_walks_whole_blocks_in_strips(monkeypatch):
    """The backward kernels take a block under the diagonal as one strip, as
    before PR 49: their steps are counted here by the walk they share."""
    from horovod_tpu.ops import flash_attention as fa
    calls = []
    walk = fa._walk_strips

    def counting(run, **kw):
        calls.append(kw.get("whole"))
        return walk(run, **kw)

    monkeypatch.setattr(fa, "_walk_strips", counting)
    q, k, v = _qkv(jax.random.PRNGKey(5), B=1, H=1, S=1024, dh=8)
    jax.grad(lambda q: jnp.sum(fa.flash_attention(
        q, k, v, causal=True, block_q=512, block_k=512)))(q)
    assert calls.count(None) == 2               # dk/dv and dq
    assert [w for w in calls if w] == [[(0, 256, 512), (256, 256, 512)]]


def test_the_forward_writes_the_next_product_ahead_only_where_a_mask_enters(
        monkeypatch):
    """Order of the forward kernel's body as it is traced: a whole block's
    strips one after the other (product, softmax, product, softmax), a
    crossed block's with strip i + 1's product in front of strip i's
    softmax. An update is seen by its two `_lanes` calls."""
    from horovod_tpu.ops import flash_attention as fa
    events = []
    scores, lanes = fa._scores, fa._lanes

    def seen_scores(q_ref, k_ref, row0, rows, cols, masked, *a, **kw):
        events.append(("masked" if masked else "whole", row0))
        return scores(q_ref, k_ref, row0, rows, cols, masked, *a, **kw)

    def seen_lanes(x, n):
        events.append("softmax")
        return lanes(x, n)

    monkeypatch.setattr(fa, "_scores", seen_scores)
    monkeypatch.setattr(fa, "_lanes", seen_lanes)
    q, k, v = _qkv(jax.random.PRNGKey(7), B=1, H=1, S=1024, dh=8)
    fa._fwd(q[0], k[0], v[0], True, 1.0, 512, 512)
    assert events == [
        ("whole", 0), "softmax", "softmax",
        ("whole", 256), "softmax", "softmax",
        ("masked", 0), ("masked", 256), "softmax", "softmax",
        "softmax", "softmax"]


def test_row_strip_share_at_the_cells_shapes():
    """Every score entry of the forward is computed in a strip of 256 rows
    or fewer at the cells' shapes; with the blocks under the diagonal whole
    (until PR 49) only the diagonal's blocks were: 10 tiles of 256² a
    diagonal block over those and the 1,024² blocks under it."""
    from horovod_tpu.ops.flash_attention import row_strip_share
    for S, window in ((2048, None), (4096, None), (8192, None),
                      (8192, 512), (16384, None), (16384, 4096)):
        assert row_strip_share(S, window) == 1.0
        under = 0 if window else (S // 1024) * (S // 1024 - 1) // 2
        diagonal = (S // 1024) * 10 * 256 * 256
        if not window:
            assert row_strip_share(S, window, whole_rows=1024) == \
                pytest.approx(diagonal / (diagonal + under * 1024 * 1024))
    assert row_strip_share(16384, whole_rows=1024) == pytest.approx(1 / 13)
    # a window's blocks were walked in 256-row strips before
    assert row_strip_share(8192, 512, whole_rows=1024) == 1.0
    # one block: all of it on the diagonal, in tiles or as one short strip
    assert row_strip_share(1024) == row_strip_share(100) == 1.0
