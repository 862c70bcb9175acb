"""The Granite 4.0-H block's loss and gradients, beside
`tests/test_granite_hybrid.py` (whose statement `FAMILY` this file shares,
and which holds the logits and the limits), all of them
`tests/family_cases.py`'s: loss and every leaf's gradient of one rank as the
cell runs it (`attn` "flash" under remat "dots") against the reference's;
`dp` = 2 without remat against that rank. (The train step:
`tests/test_step_scopes.py`.)"""

from family_cases import (  # noqa: F401  (the fixtures, the shared tests)
    ours, params, pytest_generate_tests, stated, theirs,
    test_dp_2_without_remat_equals_one_rank_under_remat,
    test_every_leafs_gradient_equals_the_references,
    test_loss_equals_the_references)
from test_granite_hybrid import FAMILY  # noqa: F401
