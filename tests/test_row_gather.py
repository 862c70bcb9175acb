"""The expert layer's row movers (ops/row_gather.py) in the Pallas
interpreter: `_sum_kernel` and `_row_form` against the `jnp` forms, bit for
bit, at the cells' widths and k, with n_valid 0, 1, a tile's edge and the
whole buffer, `back` entries past the buffer's end, negative ones and
duplicated rows; the two `custom_vjp`s' forward and backward passes; the
counter `moved_share` by hand; and `moe_ffn` on the kernel path against the
same call on the `jnp` forms. The token tile is 16 here, so that a few dozen
tokens make several grid steps; tests/test_kernels_tpu_aot.py compiles the
kernels at the benchmark's shapes and tiles. Then the third mover,
`lookup_rows` (the embedding's lookup): its backward pass, a sort, a one-hot
product a chunk of 16 sorted tokens here and a gather, against the float32
scatter-add rounded once, and its lowered text, which holds no scatter.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu.ops import row_gather as rg
from horovod_tpu.parallel import moe


@pytest.fixture
def small_tiles(monkeypatch):
    monkeypatch.setattr(rg, "TOKEN_TILE", 16)
    monkeypatch.setattr(rg, "_FORM_TILE", 16)


def _rows(n_rows, width, dtype, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (n_rows, width),
                             jnp.float32).astype(dtype)


def _back(n_tokens, k, n_rows, seed=1, beyond=0):
    """Indices of `n_tokens * k` entries into `n_rows` rows, some of them up
    to `beyond` past the end."""
    return jax.random.randint(jax.random.PRNGKey(seed), (n_tokens * k,), 0,
                              n_rows + beyond).astype(jnp.int32)


#: one compilation a shape: `n_valid` is an operand (every test that calls
#: these holds the small tiles)
_kernel_sum = jax.jit(rg._kernel_sum, static_argnums=2)
_reference_sum = jax.jit(rg.reference_sum, static_argnums=2)


def _masked_take(x, index, n_valid):
    """A take written out: the gather, then zeros from row `n_valid` on."""
    if n_valid is None:
        return x[index]
    return jnp.where((jnp.arange(index.size) < n_valid)[:, None], x[index],
                     jnp.zeros((), x.dtype))


def _same(got, want):
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))


#: (tokens, k, rows of the source): what each case is there for
SHAPES = {
    "k6-several-tiles": (40, 6, 48),
    "k8-tokens-no-tile-divides": (37, 8, 64),
    "k1-a-take": (48, 1, 32),
    "k2-one-tile": (16, 2, 24),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("n_valid", ["none", "half", "all", "edge"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_kernel_sum_is_the_jnp_sum(small_tiles, shape, n_valid, dtype):
    n_tokens, k, n_rows = SHAPES[shape]
    x = _rows(n_rows, 256, dtype)
    limit = {"none": 0, "half": n_rows // 2, "all": n_rows, "edge": 16}[
        n_valid]
    back = _back(n_tokens, k, n_rows)
    _same(_kernel_sum(x, back, k, jnp.int32(limit)),
          _reference_sum(x, back, k, jnp.int32(limit)))


@pytest.mark.parametrize("limit", [1, 17, 40])
@pytest.mark.parametrize("width,k", [(2560, 6), (2048, 2), (2560, 1)])
def test_kernel_sum_at_the_cells_widths(small_tiles, width, k, limit):
    """2,048 bf16 are one 4 KB tile of words a row, 2,560 one and a
    quarter: the second tile's free pieces are never read. (k = 6 and 8 at
    a narrow width above: the interpreter takes half a minute to build a
    wide kernel's k unrolled sums.)"""
    x = _rows(40, width, jnp.bfloat16)
    back = _back(24, k, 40)
    _same(_kernel_sum(x, back, k, jnp.int32(limit)),
          _reference_sum(x, back, k, jnp.int32(limit)))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_entries_past_the_end_and_below_zero_add_nothing(small_tiles, dtype):
    """`back` may point past the buffer's end (the `jnp` form survives that
    by clamping; a copy from there would fault the chip) and nothing says it
    is not negative: such an entry adds nothing, whatever `n_valid`."""
    n_rows, k = 32, 4
    x = _rows(n_rows, 256, dtype)
    back = _back(24, k, n_rows, beyond=40)
    back = back.at[::7].set(-3).at[5].set(2 ** 31 - 1).at[6].set(-2 ** 31)
    inside = jnp.logical_and(back >= 0, back < n_rows)
    for limit in (0, 20, n_rows, n_rows + 100):
        want = _reference_sum(x, jnp.where(inside, back, n_rows + 1), k,
                              jnp.int32(min(limit, n_rows)))
        _same(_kernel_sum(x, back, k, jnp.int32(limit)), want)


def test_duplicated_rows_are_added_each_time(small_tiles):
    x = _rows(8, 256, jnp.bfloat16)
    back = jnp.asarray([3, 3, 3, 0, 7, 7, 1, 2, 3, 3, 7, 0], jnp.int32)
    got = _kernel_sum(x, back, 3, jnp.int32(8))
    _same(got, _reference_sum(x, back, 3, jnp.int32(8)))
    # 3 * x[3] in float32, rounded once
    _same(got[0], (3 * x[3].astype(jnp.float32)).astype(jnp.bfloat16))


@pytest.mark.parametrize("dtype,width", [(jnp.bfloat16, 256),
                                         (jnp.bfloat16, 2560),
                                         (jnp.float32, 128),
                                         (jnp.float32, 1152)])
@pytest.mark.parametrize("n_valid", [0, 1, 16, 23, 40])
def test_row_form_holds_every_row_before_the_limit(small_tiles, dtype, width,
                                                   n_valid):
    """Row r of the form is `pieces` rows of 128 words; a bf16 word holds
    columns j and D / 2 + j. Tiles from `n_valid` on are not written."""
    x = _rows(40, width, dtype)
    form = np.asarray(rg._row_form(x, jnp.int32(n_valid)))
    pieces = rg._pieces(x)
    assert form.shape == (48 * pieces, 128) and pieces % 8 == 0
    words = np.asarray(x.astype(jnp.float32)).view(np.uint32)
    if dtype == jnp.bfloat16:
        words = (words[:, :width // 2] >> 16) | words[:, width // 2:]
    written = -(-n_valid // 16) * 16     # whole tiles of 16 rows
    got = form.reshape(48, pieces * 128)[:min(written, 40), :words.shape[1]]
    np.testing.assert_array_equal(got, words[:min(written, 40)])


def test_entries_by_hand():
    back = jnp.asarray([5, 1, 9,   7, 8, 9,   0, -1, 2,   3, 4, 6], jnp.int32)
    rows, filled, deepest = rg._entries(
        jnp.concatenate([back, jnp.full((36,), 99, jnp.int32)]), 3,
        jnp.int32(6))
    assert rows.reshape(3, -1)[:, :4].T.tolist() == [
        [5, 1, 0], [0, 0, 0], [0, 2, 0], [3, 4, 0]]
    assert filled[:4].tolist() == [2, 0, 2, 2] and not filled[4:].any()
    assert deepest.tolist() == [2]


def _held_back(n_tokens, k, n_experts, n_local, seed=0):
    """`back` and `n_valid` of a chip that holds the first `n_local` of
    `n_experts` experts, each token sent to k distinct ones at random."""
    rng = np.random.default_rng(seed)
    experts = np.argsort(rng.random((n_tokens, n_experts)), axis=1)[:, :k]
    key = np.where(experts.reshape(-1) < n_local, experts.reshape(-1),
                   n_local)
    order = np.argsort(key, kind="stable")
    return np.argsort(order), int((key < n_local).sum())


@pytest.mark.parametrize("cell,n_tokens,k,n_experts,n_local,share", [
    ("smallthinker-1chip", 16384, 6, 64, 16, 0.25),
    ("dsv2lite-1chip", 8192, 6, 64, 8, 0.125),
    ("olmoe-1chip", 8192, 8, 64, 64, 1.0),
])
def test_moved_share_at_the_cells_loads(cell, n_tokens, k, n_experts,
                                        n_local, share):
    """By hand: of 4 entries [0, 5, 2, 9] before 3 two are copied; at a
    cell's load the held share of the pairs, within the routing's noise."""
    assert rg.moved_share([0, 5, 2, 9], 3) == 0.5
    assert rg.moved_share([0, 5, 2, -1], 6) == 0.75
    assert rg.moved_share([0, 5, 2, 9]) == 1.0
    back, held = _held_back(n_tokens, k, n_experts, n_local)
    got = rg.moved_share(back.tolist(), held if n_local < n_experts else None)
    assert got == pytest.approx(share, rel=0.03)
    if n_local < n_experts:
        assert got == held / back.size


@functools.lru_cache(maxsize=None)
def _movers(k, held):
    """`take_rows` and `sum_rows` with their backward passes as one compiled
    program a (k, whether a share is held): `n_valid` is an operand, so the
    cases that differ in it alone share the program (every test that calls
    it holds the small tiles)."""
    def movers(x, ys, token_of, inverse, n_valid):
        valid = n_valid if held else None
        taken, take_vjp = jax.vjp(
            lambda x: rg.take_rows(x, token_of, inverse, k, valid), x)
        summed, sum_vjp = jax.vjp(
            lambda ys: rg.sum_rows(ys, inverse, token_of, k, valid), ys)
        return taken, take_vjp(ys)[0], summed, sum_vjp(x)[0]
    return jax.jit(movers)


@pytest.mark.parametrize("n_valid", [None, 0, 1, 16, 30, 48])
@pytest.mark.parametrize("k", [1, 6])
def test_the_two_movers_and_their_backward_passes(small_tiles, k, n_valid):
    """`take_rows` and `sum_rows`, forward and `jax.vjp`, against the `jnp`
    forms and THEIR transposes: a take's gradient is a sum of the cotangent's
    rows (the kernel, with `n_valid`), a sum's a take."""
    n_tokens, width = 24, 256
    room = min(48, n_tokens * k)
    x = _rows(n_tokens, width, jnp.bfloat16)
    ys = _rows(room, width, jnp.bfloat16, seed=3)
    order = jax.random.permutation(jax.random.PRNGKey(2), n_tokens * k)
    inverse = jnp.argsort(order).astype(jnp.int32)
    token_of = (order[:room] // k).astype(jnp.int32)
    if n_valid is None:     # every pair is held: the buffer is all of them
        room = n_tokens * k
        token_of = (order // k).astype(jnp.int32)
        ys = _rows(room, width, jnp.bfloat16, seed=3)
    taken, d_take, summed, d_sum = _movers(k, n_valid is not None)(
        x, ys, token_of, inverse, jnp.int32(n_valid or 0))
    _same(taken, _masked_take(x, token_of, n_valid))
    _same(d_take, rg.reference_sum(ys, inverse, k, n_valid))
    _same(summed, rg.reference_sum(ys, inverse, k, n_valid))
    _same(d_sum, _masked_take(x, token_of, n_valid))


def test_which_sums_the_kernel_takes(monkeypatch):
    """The kernel where a share is held and the rows are whole lane tiles of
    bfloat16 or float32; the `jnp` form for everything else."""
    called = []
    monkeypatch.setattr(rg, "_kernel_sum",
                        lambda *a: called.append(a) or rg.reference_sum(*a))
    back = jnp.arange(8, dtype=jnp.int32)
    for dtype, width, n_valid, kernel in [
            (jnp.bfloat16, 256, 4, True), (jnp.float32, 128, 4, True),
            (jnp.bfloat16, 256, None, False), (jnp.bfloat16, 128, 4, False),
            (jnp.float32, 64, 4, False), (jnp.float16, 256, 4, False)]:
        called.clear()
        rg.sum_rows(jnp.ones((8, width), dtype), back, back, 2, n_valid)
        assert bool(called) == kernel, (dtype, width, n_valid)


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_moe_ffn_on_the_kernel_is_moe_ffn_on_the_jnp_forms(monkeypatch,
                                                           small_tiles,
                                                           gated):
    """A chip that holds 2 of 8 experts, D = 256 bf16: output, auxiliary
    numbers and every gradient are the same bits with `_sum_kernel` in the
    combine and in the dispatch's backward pass as with the `jnp` sum."""
    n_tokens, d, f, n_experts, n_local, k = 48, 256, 128, 8, 2, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (n_tokens, d), jnp.float32).astype(
        jnp.bfloat16)
    params = {
        "router": jax.random.normal(ks[1], (d, n_experts), jnp.float32
                                    ).astype(jnp.bfloat16),
        "up": (jax.random.normal(ks[2], (n_local, d, f)) * d ** -0.5
               ).astype(jnp.bfloat16),
        "down": (jax.random.normal(ks[3], (n_local, f, d)) * f ** -0.5
                 ).astype(jnp.bfloat16),
    }
    if gated:
        params["gate"] = (jax.random.normal(ks[4], (n_local, d, f))
                          * d ** -0.5).astype(jnp.bfloat16)
    cot = jax.random.normal(ks[5], (n_tokens, d), jnp.float32).astype(
        jnp.bfloat16)

    def run():
        def ffn(x, params):
            out, aux, _ = moe.moe_ffn(
                x, params["router"], params["up"], params["down"],
                params.get("gate"), top_k=k, first_expert=2)
            return out, aux
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("ep",))

        def step(x, params, cot):
            (out, aux), vjp = jax.vjp(ffn, x, params)
            return out, aux, vjp((cot, jnp.zeros_like(aux)))
        return jax.jit(jax.shard_map(
            step, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
            check_vma=False))(x, params, cot)

    calls = []
    kernel_sum = rg._kernel_sum
    monkeypatch.setattr(rg, "_kernel_sum",
                        lambda *a: calls.append(1) or kernel_sum(*a))
    got = run()
    assert len(calls) == 2      # the combine, the dispatch's backward pass
    monkeypatch.setattr(rg, "supported", lambda x: False)
    want = run()
    assert len(calls) == 2
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _same(g, w)
    assert float(got[1][2]) == 0.0      # no held pair was left out


# --------------------------------------------------------------------------
# The lookup
# --------------------------------------------------------------------------

@pytest.fixture
def small_chunks(monkeypatch):
    monkeypatch.setattr(rg, "LOOKUP_CHUNK", 16)


def _zipf_like(n_tokens, n_rows, seed=0):
    """Ids as text has them: a few by the dozen, most once or never."""
    draws = np.random.default_rng(seed).zipf(1.3, n_tokens) - 1
    return np.minimum(draws, n_rows - 1)


#: (ids, rows of the table) with chunks of 16: what each case is there for
LOOKUPS = {
    "uniform-tokens-no-chunk-divides": (
        np.random.default_rng(1).integers(0, 37, 100), 37),
    "uniform-whole-chunks": (np.random.default_rng(2).integers(0, 20, 64), 20),
    "every-id-the-same": (np.full(70, 5), 11),
    "zipf-like-a-run-over-several-chunks": (_zipf_like(200, 50), 50),
    "runs-end-at-the-chunks-edges": (np.repeat(np.arange(4), 16), 20),
    "a-run-of-two-whole-chunks-between-others": (
        np.array([1] * 8 + [3] * 48 + [4] * 5 + [9] * 19), 12),
    "fewer-tokens-than-a-chunk": (np.array([3, 0, 3, 8, 3]), 9),
    "ids-at-0-and-at-the-last-row": (np.array([0] * 20 + [7] * 28), 8),
    "every-row-asked-for-once": (np.random.default_rng(3).permutation(48),
                                 48),
    "one-token": (np.array([2]), 4),
}


def _cotangent(n_tokens, width, dtype, seed=4):
    """Quarters between -8 and 8: every partial sum is exact in float32 in
    whatever order it is added, and few of them are a bfloat16."""
    return (jax.random.randint(jax.random.PRNGKey(seed), (n_tokens, width),
                               -32, 33) / 4).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", sorted(LOOKUPS))
def test_the_lookups_backward_is_the_float32_scatter_add_rounded_once(
        small_chunks, case, dtype):
    ids, n_rows = LOOKUPS[case]
    ids = jnp.asarray(ids, jnp.int32)
    table = _rows(n_rows, 128, dtype, seed=5)
    g = _cotangent(ids.size, 128, dtype)
    want = jnp.zeros((n_rows, 128), jnp.float32).at[ids].add(
        g.astype(jnp.float32)).astype(dtype)
    # through the `custom_vjp`, the ids in two dimensions as a batch has them
    shape = (2, ids.size // 2) if ids.size % 2 == 0 else (1, ids.size)
    # (one compiled program a case: eagerly the backward pass's sort, its
    # products and its gather are a compile each)
    def both(table, g):
        out, vjp = jax.vjp(
            lambda t: rg.lookup_rows(t, ids.reshape(shape))[0], table)
        return out, vjp(g)[0]

    out, got = jax.jit(both)(table, g.reshape(shape + (128,)))
    _same(out, table[ids.reshape(shape)])
    _same(got, want)
    assert rg.slot_share(np.asarray(ids)) <= 1.0


def test_a_later_readers_gradient_is_what_the_lookups_is_added_to(
        small_chunks):
    """A tied head reads the table `lookup_rows` hands back: the table's
    gradient is the head's plus the lookup's, each as plain indexing and a
    plain product give them."""
    ids = jnp.asarray(LOOKUPS["zipf-like-a-run-over-several-chunks"][0],
                      jnp.int32)
    table = _rows(50, 128, jnp.float32, seed=5)
    g = _cotangent(ids.size, 128, jnp.float32)

    def tied(lookup):
        def loss(t):
            rows, again = lookup(t, ids)
            return jnp.sum(rows * g) + jnp.sum((rows @ again.T) ** 2)
        return jax.jit(jax.grad(loss))(table)

    np.testing.assert_allclose(
        np.asarray(tied(rg.lookup_rows)),
        np.asarray(tied(lambda t, i: (t[i], t))), rtol=1e-5, atol=1e-3)


def test_the_lookups_backward_reads_odd_ids_as_the_lookup_does(small_chunks):
    """A negative id counts from the table's end, as in `table[ids]`; one
    past either end adds nothing."""
    n_rows = 10
    ids = jnp.asarray([-1, 3, -10, 9, 10, -11, 3, 25, 0, -4] * 3, jnp.int32)
    g = _cotangent(ids.size, 128, jnp.float32)
    table = _rows(n_rows, 128, jnp.float32)
    want = jax.vjp(lambda t: t[ids], table)[1](g)[0]
    _same(jax.jit(lambda table, g: jax.vjp(
        lambda t: rg.lookup_rows(t, ids)[0], table)[1](g)[0])(table, g), want)


@pytest.mark.parametrize("ids,share", [
    (np.arange(64), 1.0), (np.zeros(64, int), 4 / 64),
    (np.repeat(np.arange(8), 8), 8 / 64), (np.array([5, 5, 7]), 2 / 3)])
def test_slot_share_by_hand(ids, share):
    assert rg.slot_share(ids, chunk=16) == share


def test_the_lookups_backward_lowers_to_no_scatter(small_chunks):
    """Plain indexing's does, so the search would find one."""
    table = jax.ShapeDtypeStruct((37, 128), jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((2, 50), jnp.int32)

    def lowered(lookup):
        return jax.jit(jax.grad(lambda t, i: lookup(t, i)[0].astype(
            jnp.float32).sum())).lower(table, ids).as_text()

    assert "scatter" in lowered(lambda t, i: (t[i], t))
    assert "scatter" not in lowered(rg.lookup_rows)
