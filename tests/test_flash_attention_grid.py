"""The flash kernels' grid (`ops/flash_attention.py`): the steps are the
block pairs the mask holds, each once; three and more blocks a side against
the mask written out; whole blocks walked in row strips by the forward
kernel (PR 49). Interpreted on the CPU, beside `tests/test_flash_attention.py`
(the kernels' numerics, windows and groups)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_flash_attention import _qkv, _written_out


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

    def walked(walk):
        """Every step of `walk` as a kernel finds it: [(run, pass, at)], and
        whether it is its run's first and last; the steps int32 scalars as
        `program_id` is, all of them in one traced call."""
        run, at, where = jax.vmap(
            lambda s: (walk.run(s), walk.at(s), walk.where(s)[2:]))(
                jnp.arange(walk.steps, dtype=jnp.int32))
        n = walk.steps
        (i, g), at, ends = jax.tree_util.tree_map(
            lambda x: np.broadcast_to(np.asarray(x), (n,)).tolist(),
            (run, at, where))
        return list(zip(i, g, at)), list(zip(*ends))

    visited, ends = walked(_Walk(rows))
    steps = [(i, j) for i, _, j in visited]
    assert steps == held            # each once, row-major
    for s, (i, j) in enumerate(steps):
        first, last = ends[s]
        assert first == (s == 0 or steps[s - 1][0] != i)
        assert last == (s == len(steps) - 1 or steps[s + 1][0] != i)
    assert {i for i, _ in steps} == set(range(nq))     # every o block

    steps, ends = walked(_Walk(_by_key_block(rows, nk), heads=group))
    for s, (j, _, _) in enumerate(steps):
        first, last = ends[s]
        assert first == (s == 0 or steps[s - 1][0] != j)
        assert last == (s == len(steps) - 1 or steps[s + 1][0] != j)
    assert steps == sorted((j, g, i) for i, j in held for g in range(group))
    assert {j for j, _, _ in steps} == set(range(nk))  # every dk, dv block

    # the fused backward: the same pairs by key block, a query head's whole
    # walk before the group's next head's; dk and dv zeroed at a key block's
    # first step of the first head and written at its last of the last, dq
    # at the first and last step of each head's walk
    fused = _Walk(_by_key_block(rows, nk), heads=group, head_major=True)
    steps, ends = walked(fused)
    assert steps == sorted(((j, g, i) for i, j in held for g in range(group)),
                           key=lambda step: (step[1], step[0], step[2]))
    head_ends = jax.vmap(fused.head_ends)(
        jnp.arange(fused.steps, dtype=jnp.int32))
    head_first, head_last = (np.asarray(x).tolist() for x in head_ends)
    for s, (j, g, _) in enumerate(steps):
        first, last = ends[s]
        assert first == (g == 0 and (s == 0 or steps[s - 1][0] != j))
        assert last == (g == group - 1 and (
            s == len(steps) - 1 or steps[s + 1][0] != j))
        assert head_first[s] == (s == 0 or steps[s - 1][1] != g)
        assert head_last[s] == (s == len(steps) - 1 or steps[s + 1][1] != g)

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
    o, lse = jax.jit(lambda q, k, v: fa._fwd(
        q[0], k[0], v[0], causal, 8 ** -0.5, bq, bk, window))(q, k, v)
    want_o, want_lse = jax.jit(theirs)(q, k, v)
    got = [o[None], lse[None, ..., 0]]
    want = [want_o, want_lse]
    for f, into in ((ours, got), (theirs, want)):
        into.extend(jax.jit(jax.grad(functools.partial(loss, f),
                                     argnums=(0, 1, 2)))(q, k, v))
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

    o, lse = jax.jit(lambda q, k, v: fa._fwd(
        q[0], k[0], v[0], True, dk ** -0.5, block, block, window))(q, k, v)
    want_o, want_lse = jax.jit(
        lambda q, k, v: _written_out(q, k, v, True, window))(q, k, v)
    got, want = [o[None], lse[None, ..., 0]], [want_o, want_lse]
    for f, into in ((ours, got), (theirs, want)):
        into.extend(jax.jit(jax.grad(lambda *a, f=f: jnp.sum(f(*a) * w),
                                     argnums=(0, 1, 2)))(q, k, v))
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


@pytest.mark.parametrize("form", ["fused", "split"])
def test_only_the_forward_kernel_walks_whole_blocks_in_strips(monkeypatch,
                                                              form):
    """The backward kernels take a block under the diagonal as one strip, as
    before PR 49: their steps are counted here by the walk they share. The
    fused backward kernel walks once where dk/dv and dq walked once each."""
    from horovod_tpu.ops import flash_attention as fa
    calls = []
    walk = fa._walk_strips

    def counting(run, **kw):
        calls.append(kw.get("whole"))
        return walk(run, **kw)

    monkeypatch.setattr(fa, "_walk_strips", counting)
    if form == "split":
        monkeypatch.setattr(fa, "_FUSED_VMEM_LIMIT", 0)
    q, k, v = _qkv(jax.random.PRNGKey(5), B=1, H=1, S=1024, dh=8)
    jax.grad(lambda q: jnp.sum(fa.flash_attention(
        q, k, v, causal=True, block_q=512, block_k=512)))(q)
    # the one backward kernel, or dk/dv and dq
    assert calls.count(None) == {"fused": 1, "split": 2}[form]
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
