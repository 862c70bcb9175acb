"""The flash backward pass as one kernel (`ops/flash_attention.py`
`_bwd_fused_kernel`, PR 55): dq, dk and dv against the mask written out and
against the dq and the dk/dv kernel it took the place of (which stay for
shapes whose accumulators pass the VMEM budget), in float32 and bf16; the
rule that chooses the form from the static shapes, and `backward_products`,
held to what `_bwd` traces. Interpreted on the CPU, beside
`tests/test_flash_attention.py` and `tests/test_flash_attention_grid.py`."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import flash_attention as fa
from test_flash_attention import _written_out
from test_flash_tpu_aot import CELL_FLASH


#: (Sq, Sk, block_q, block_k, causal, window, query heads, K/V heads, keys'
#: width, values' width, with an lse cotangent): three blocks a side; a
#: window whose lower edge crosses a block, under a group of two; groups of
#: four and of seven (dk and dv summed over the group in one float32
#: accumulator over the sequence); the cells' pairs of widths; a ring hop's
#: chunks, square and causal or 3 x 4 blocks and not, with an lse cotangent;
#: one block of a size no tile divides, and one walked in 256-tiles
_CASES = {
    "causal-3-blocks": (384, 384, 128, 128, True, None, 2, 2, 8, 16, False),
    "window-g2": (512, 512, 128, 128, True, 100, 4, 2, 8, 16, False),
    "window-two-back": (512, 512, 128, 128, True, 200, 2, 2, 8, 8, False),
    "g4": (256, 256, 128, 128, True, None, 4, 1, 8, 8, False),
    "g7": (256, 256, 128, 128, True, None, 7, 1, 8, 8, False),
    "64-64-g2": (256, 256, 128, 128, True, None, 2, 1, 64, 64, False),
    "64-128": (256, 256, 128, 128, True, None, 2, 2, 64, 128, False),
    "192-128": (256, 256, 128, 128, True, None, 1, 1, 192, 128, False),
    "chunk-causal-dlse": (256, 256, 128, 128, True, None, 2, 2, 8, 16, True),
    "chunk-3x4-dlse": (384, 512, 128, 128, False, None, 2, 2, 8, 16, True),
    "not-square-blocks": (384, 384, 128, 64, True, None, 2, 1, 8, 8, False),
    "one-block-100": (100, 100, 100, 100, True, None, 2, 1, 8, 8, False),
    "one-block-in-tiles": (512, 512, 512, 512, True, None, 1, 1, 8, 8, False),
}

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


@functools.lru_cache(maxsize=None)
def _case(case, dtype):
    """(dq, dk, dv) of the fused kernel, of the dq and dk/dv kernels, and of
    the mask written out in float32, on the same operands (rounded to
    `dtype`; o and lse the forward kernel's)."""
    Sq, Sk, bq, bk, causal, window, H, G, dk, dv, with_dlse = _CASES[case]
    dtype = _DTYPES[dtype]
    ks = jax.random.split(jax.random.PRNGKey(55), 5)
    q = jax.random.normal(ks[0], (H, Sq, dk), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (G, Sk, dk), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (G, Sk, dv), jnp.float32).astype(dtype)
    do = jax.random.normal(ks[3], (H, Sq, dv), jnp.float32).astype(dtype)
    dlse = jax.random.normal(ks[4], (H, Sq, 1), jnp.float32) \
        if with_dlse else None
    scale = dk ** -0.5

    def backward(q, k, v, o, lse, do, dlse):
        return fa._bwd(q, k, v, o, lse, do, dlse, causal, scale, bq, bk,
                       window)

    def without_budget(*operands):
        """`backward` traced under a budget no accumulator fits: the dq and
        the dk/dv kernel."""
        limit = fa._FUSED_VMEM_LIMIT
        try:
            fa._FUSED_VMEM_LIMIT = 0
            return backward(*operands)
        finally:
            fa._FUSED_VMEM_LIMIT = limit

    def written_out(q, k, v):
        return _written_out(q[None], k[None], v[None], causal, window)

    def all_three(q, k, v, do, dlse):   # one compiled program a case
        o, lse = fa._fwd(q, k, v, causal, scale, bq, bk, window)
        operands = (q, k, v, o, lse, do, dlse)
        cotangent = (do.astype(jnp.float32)[None],
                     jnp.zeros((1, H, Sq), jnp.float32) if dlse is None
                     else dlse[None, ..., 0])
        want = jax.vjp(written_out, *(x.astype(jnp.float32)
                                      for x in (q, k, v)))[1](cotangent)
        return backward(*operands), without_budget(*operands), want

    fused, split, want = jax.jit(all_three)(q, k, v, do, dlse)
    return fused, split, want


@pytest.mark.parametrize("what", ["dq", "dk", "dv"])
@pytest.mark.parametrize("dtype", list(_DTYPES))
@pytest.mark.parametrize("case", list(_CASES))
def test_the_fused_backward_matches_the_mask_written_out(case, dtype, what):
    """float32 at the tolerances of the forward's tests; bf16 operands and
    results against the float32 gradients of the same rounded operands, by
    the result's own range (a bf16 number keeps 8 bits; the sums run in
    float32 and are rounded once)."""
    fused, _, want = _case(case, dtype)
    at = "dq dk dv".split().index(what)
    got, want = np.asarray(fused[at], np.float32), np.asarray(want[at])
    assert fused[at].dtype == _DTYPES[dtype] and got.shape == want.shape
    if dtype == "float32":
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    else:
        assert np.max(np.abs(got - want)) <= 2 ** -6 * np.max(np.abs(want))


@pytest.mark.parametrize("what", ["dq", "dk", "dv"])
@pytest.mark.parametrize("dtype", list(_DTYPES))
@pytest.mark.parametrize("case", list(_CASES))
def test_the_fused_backward_is_the_two_kernels_bit_for_bit(case, dtype, what):
    """The sums keep their order: a query block's key blocks arrive
    ascending in the walk by key blocks too (dq), and a key block's query
    blocks head after head of the group, each head's ascending (dk, dv), as
    the dk/dv kernel's run-major walk had them; the strips and their
    products are the same. So every result is the two kernels' to the bit."""
    fused, split, _ = _case(case, dtype)
    at = "dq dk dv".split().index(what)
    assert fused[at].dtype == split[at].dtype
    np.testing.assert_array_equal(np.asarray(fused[at], np.float32),
                                  np.asarray(split[at], np.float32))


# --------------------------------------- which form runs: the static shapes

#: MiB `fused_backward_bytes` reckons at the shapes the benchmark's cells
#: run the flash kernels at (`test_flash_tpu_aot.CELL_FLASH`: every
#: transformer cell's; a window changes no block's size)
CELL_MIB = {
    "lm-1chip": 4, "olmoe-1chip": 6, "dsv2lite-1chip": 11,
    "olmohybrid-1chip": 10, "phi4flash-1chip-full": 24,
    "phi4flash-1chip-window": 24, "smallthinker-1chip-full": 48,
    "kimilinear-1chip": 35, "lfm2moe-1chip": 48,
}
#: and the cell whose flash shape that table leaves out
CELL_FLASH = {**CELL_FLASH, "granite4h-1chip": (16, 4, 4096, 128, 128, None)}
CELL_MIB["granite4h-1chip"] = 12


def _kernels(jaxpr, found=None):
    """The number of `dot_general`s in every `pallas_call`'s kernel of a
    jaxpr, in the order met."""
    found = [] if found is None else found

    def dots(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            n += eqn.primitive.name == "dot_general"
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += dots(sub)
        return n

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(dots(eqn.params["jaxpr"]))
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _kernels(sub, found)
    return found


def _traced_backward(heads, kv_heads, seq, dk, dv, causal=False):
    """The kernels `_bwd` traces at a shape (nothing is run or allocated),
    each as its number of matrix products. Not causal: every block is one
    strip, so a kernel's products are a block pair's."""
    block = fa._auto_block(seq)
    q, k, v, o = (jax.ShapeDtypeStruct((n, seq, width), jnp.bfloat16)
                  for n, width in ((heads, dk), (kv_heads, dk),
                                   (kv_heads, dv), (heads, dv)))
    avals = (q, k, v, o, jax.ShapeDtypeStruct((heads, seq, 1), jnp.float32),
             o)
    return _kernels(jax.make_jaxpr(lambda *a: fa._bwd(
        *a, None, causal, dk ** -0.5, block, block))(*avals).jaxpr)


@pytest.mark.parametrize("cell", list(CELL_FLASH))
def test_every_cells_shape_takes_the_fused_backward(cell):
    """`backward_products` is what `_bwd` traces: one kernel of five
    products a block pair at every cell's shape, with the bytes its
    accumulators and output blocks keep resident."""
    (heads, kv_heads, seq, dk, dv, _), mib = CELL_FLASH[cell], CELL_MIB[cell]
    products, resident = fa.backward_products(seq, dk, dv, heads // kv_heads)
    assert products == 5 and resident == mib * 2 ** 20
    assert _traced_backward(heads, kv_heads, seq, dk, dv) == [5]
    limit = fa._fused_params(seq, seq, dk, dv, heads // kv_heads,
                             fa._auto_block(seq), 2)[
                                 "compiler_params"].vmem_limit_bytes
    assert limit == resident + (32 if dk > 128 else 16) * 2 ** 20
    assert limit <= fa._FUSED_VMEM_LIMIT


#: past the budget: a K/V head's own queries at 131,072 tokens, a group's at
#: 32,768 (dk and dv span the sequence too), 192-wide keys at 65,536
@pytest.mark.parametrize("heads,kv_heads,seq,dk,dv", [
    (1, 1, 131072, 128, 128), (4, 1, 32768, 128, 128),
    (1, 1, 65536, 192, 128)], ids=["g1-131072", "g4-32768", "192-65536"])
def test_past_the_budget_the_two_kernels_stay(heads, kv_heads, seq, dk, dv):
    products, resident = fa.backward_products(seq, dk, dv, heads // kv_heads)
    assert products == 7
    assert resident + 16 * 2 ** 20 > fa._FUSED_VMEM_LIMIT
    # dk/dv's four products, then dq's three
    assert _traced_backward(heads, kv_heads, seq, dk, dv) == [4, 3]


def test_the_form_follows_the_shapes_on_both_sides_of_the_budget():
    """One width and group, the length alone decides; a ring hop's chunk is
    S / sp and fits where the whole sequence does not."""
    assert fa.backward_products(65536, 128)[0] == 5
    assert fa.backward_products(131072, 128)[0] == 7
    assert fa.backward_products(131072 // 4, 128)[0] == 5
    assert fa.backward_products(16384, 128, group=7)[0] == 5
    assert fa.backward_products(32768, 128, group=7)[0] == 7
    # the group's size does not count: its heads share one accumulator
    assert fa.backward_products(16384, 128, group=7)[1] == \
        fa.backward_products(16384, 128, group=2)[1]
    # a 192-wide row takes two lane tiles, a 64-wide one a whole tile
    assert fa.fused_backward_bytes(1024, 1024, 192, 128, 1, 1024) == \
        8 * 1024 * (256 + 256 + 128)
    assert fa.fused_backward_bytes(1024, 1024, 64, 64, 1, 1024) == \
        fa.fused_backward_bytes(1024, 1024, 128, 128, 1, 1024)
    # float32 operands: the output blocks are twice as large
    assert fa.fused_backward_bytes(2048, 2048, 128, 128, 2, 1024, 4) == \
        12 * 2048 * 128 * 3
