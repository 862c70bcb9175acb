"""The Kimi Linear stack of `models/transformer.py` beside
`tests/test_kimi_linear.py` (whose statement `FAMILY` this file shares), as
the cell runs it (`attn` "flash", remat "full"). Of `tests/family_cases.py`:
the loss and every leaf's gradient against the plain reference's, `dp` = 2
without remat against one rank under it, what `validate_cfg_for_mesh`
refuses. Its own: a dense prefix inside a layer pattern (the segments behind
it, a stack of a whole period that ends inside the next against the
reference)."""

import dataclasses

import jax
import numpy as np
import pytest

import family as programs
from benchmark.families import kimi_linear as family
from benchmark.reference import kimi_linear as reference
from family_cases import (  # noqa: F401  (the fixtures, the shared tests)
    ours, params, pytest_generate_tests, stated, theirs,
    test_dp_2_without_remat_equals_one_rank_under_remat,
    test_every_leafs_gradient_equals_the_references,
    test_loss_equals_the_references,
    test_validate_accepts_the_model_where_it_runs,
    test_validate_refuses_by_name)
from horovod_tpu.common.exceptions import HorovodTpuError
from horovod_tpu.models import transformer as tfm
from test_kimi_linear import (CFG, FAMILY, FIRST, PATTERN, TOP_K, _lively,
                              chunks_of_eight)  # noqa: F401


# ------------------------------------------------------------------ the stack

def test_the_stack_behind_the_dense_layer_starts_inside_the_period():
    """The dense layer is the pattern's first layer; behind it the rest of
    its period, the whole periods, and what is left of a last one."""
    def segments(**changes):
        return tfm._pattern_segments(dataclasses.replace(CFG, **changes))

    rest = (("kda", "kda", "mla"), 1)
    assert segments() == (rest, (("kda",), 1))
    assert segments(n_layers=6) == (rest, (("kda", "kda"), 1))
    assert segments(n_layers=7) == (rest, (("kda", "kda", "kda"), 1))
    assert segments(n_layers=8) == (rest, (PATTERN, 1))
    assert segments(n_layers=14) == (rest, (PATTERN, 2),
                                     (("kda", "kda"), 1))
    assert segments(n_layers=4) == (rest,)
    assert segments(n_layers=3) == ((("kda", "kda"), 1),)
    # without a dense prefix a pattern still runs whole periods only
    assert segments(first_k_dense=0, n_layers=8) == ((PATTERN, 2),)
    with pytest.raises(HorovodTpuError, match="no whole number of periods"):
        segments(first_k_dense=0)
    for changes in ({"first_k_dense": 4, "n_layers": 9},
                    {"first_k_dense": 1, "n_layers": 1},
                    {"layer_pattern": ("kda", "mla", "kda", "kda"),
                     "first_k_dense": 2}):
        with pytest.raises(HorovodTpuError, match="pattern's first layers"):
            segments(**changes)


def test_a_stack_that_ends_elsewhere_equals_the_reference():
    """Nine layers: the dense one, the rest of its period, a whole period
    and one layer more, each a segment of its own. ONE configuration holds
    what six (the cell's: two layers behind the first period) and eight (two
    whole periods) showed, each with a program of its own; `CFG`'s own five
    are the least the issue's rule leaves."""
    cfg = dataclasses.replace(CFG, n_layers=9)
    kinds = (PATTERN * 3)[:9]
    assert len(tfm._pattern_segments(cfg)) == 3
    tokens, _ = FAMILY.batch
    p = _lively(programs.init(cfg))
    with jax.enable_x64(False):
        got = programs.forward(cfg)(p, tokens)
        want = reference.forward(family.reference_weights(p, kinds), tokens,
                                 kinds, TOP_K, FIRST)
    np.testing.assert_allclose(got, want, atol=5e-3, rtol=5e-3)
