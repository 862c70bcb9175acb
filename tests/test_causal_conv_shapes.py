"""`ops/causal_conv.py` at the shapes that differ in kind, beside
`tests/test_causal_conv.py` (taps, bf16, leaks, the static numbers): values
and the gradients of u and of the taps against the plain `jnp` form, with and
without the L2 normalisation."""

import pytest

from horovod_tpu.ops.causal_conv import (causal_conv_silu,
                                         reference_causal_conv_silu)
from test_causal_conv import (F32, _inputs, _rel, _value_and_grads,
                              tile)  # noqa: F401


#: (batch, heads, tokens, width), tile: fewer tokens than taps; one strip;
#: a length off the strip; several tiles of one strip (the halo on both
#: sides is another grid step's) and of two (a strip's halo inside a tile);
#: several sequences and heads with a head count the block does not hold
#: whole; the cell's two widths and an odd one
SHAPES = [
    pytest.param((1, 2, 3, 8), 1024, id="S3-below-the-taps"),
    pytest.param((1, 1, 64, 8), 1024, id="S64-one-strip"),
    pytest.param((2, 3, 20, 8), 1024, id="B2-H3-S20"),
    pytest.param((2, 3, 200, 7), 64, id="S200-tiles-of-64-odd-width"),
    pytest.param((1, 2, 300, 16), 128, id="S300-tiles-of-128"),
    pytest.param((1, 1, 500, 8), 256, id="S500-tiles-of-two-strips"),
    pytest.param((1, 2, 100, 16), 48, id="S100-tiles-of-48"),
    pytest.param((2, 5, 130, 96), 64, id="B2-H5-S130-width-96"),
    pytest.param((1, 2, 70, 192), 64, id="S70-width-192"),
]


@pytest.mark.parametrize("l2_scale", [None, 1.0, 96 ** -0.5],
                         ids=["plain", "normed", "normed-scaled"])
@pytest.mark.parametrize("shape,tile", SHAPES, indirect=["tile"])
def test_values_and_gradients_match_the_jnp_form(shape, tile, l2_scale):
    u, w, cot = _inputs(shape)
    got = _value_and_grads(causal_conv_silu, u, w, cot, l2_scale)
    want = _value_and_grads(reference_causal_conv_silu, u, w, cot, l2_scale)
    for name, g, r in zip(("y", "du", "dw"), got, want):
        assert g.shape == r.shape and g.dtype == r.dtype == F32, name
        assert _rel(g, r) < 2e-6, name
