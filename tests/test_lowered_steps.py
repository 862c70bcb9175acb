"""The lowered train steps of `tests/step_cases.py`, a third of them: the
GPT-2 block, OLMoE's, DeepSeek-V2's, the Kimi Linear stack and the LFM2 one,
each lowered
once in this process, its text held to the fixture's
(`tests/fixtures/hlo/lowered_steps.json.gz`) and its compiled scopes read.
The other families: `tests/test_hybrid_steps.py`, `tests/test_step_scopes.py`.
What the tests are, whose text each entry of the fixture is and how it is
taken anew (`write_fixture`): `tests/step_cases.py`."""

from step_cases import (  # noqa: F401  (the tests, cut to FAMILIES)
    parents, pytest_generate_tests, write_fixture,
    test_a_scope_is_in_the_forward_and_in_the_backward_pass,
    test_no_instruction_lies_under_two_layers_scopes,
    test_the_lookup_leaves_the_step_one_scatter_fewer,
    test_the_lowered_step_is_the_parents,
    test_the_reduction_has_its_scope_where_something_is_reduced,
    test_the_step_has_its_scopes_and_no_other,
    test_three_steps_lower_the_loss)

FAMILIES = ("gpt2", "olmoe", "deepseek_v2", "kimi_linear", "lfm2_moe")
