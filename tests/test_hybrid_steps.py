"""The lowered train steps of `tests/step_cases.py`, a third of them: the
Olmo-Hybrid pattern and the Phi-4-mini-flash segments, as
`tests/test_lowered_steps.py` holds its families'."""

from step_cases import (  # noqa: F401  (the tests, cut to FAMILIES)
    parents, pytest_generate_tests,
    test_a_scope_is_in_the_forward_and_in_the_backward_pass,
    test_no_instruction_lies_under_two_layers_scopes,
    test_the_lookup_leaves_the_step_one_scatter_fewer,
    test_the_lowered_step_is_the_parents,
    test_the_reduction_has_its_scope_where_something_is_reduced,
    test_the_step_has_its_scopes_and_no_other,
    test_three_steps_lower_the_loss)

FAMILIES = ("olmo_hybrid", "phi4_flash")
