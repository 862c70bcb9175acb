"""`tests/family_cases.py` itself, which the family files' tests and seconds
rest on: a statement that lacks a field, or whose timed configuration is
another model than its tiny one, is refused; no two families' statements
share a program; `pytest_generate_tests` gives a family's file exactly the
cases its statement names, and a file that imports a shared test imports
the fixtures it needs; and a family's shared cases, run here on the
smallest statement there is (one GPT-2 block), build and trace TWO gradient
programs and no third."""

import dataclasses
import importlib
import inspect
import types

import jax.numpy as jnp
import pytest

import family as programs
import family_cases as cases
from benchmark.families import gpt2_block
from benchmark.reference import gpt2_block as gpt2_reference
from family_cases import (  # noqa: F401  (the fixtures, the shared tests)
    Family, ours, params, pytest_generate_tests, stated, theirs,
    test_dp_2_without_remat_equals_one_rank_under_remat,
    test_every_leafs_gradient_equals_the_references,
    test_loss_equals_the_references)
from horovod_tpu.models import transformer as tfm

CFG = tfm.TransformerConfig(vocab=32, d_model=16, n_heads=2, d_ff=32,
                            n_layers=1, max_seq=8, attn="local",
                            dtype=jnp.float32)
#: what a statement must say: the fields without a default
STATED = dict(
    cfg=CFG, timed=dataclasses.replace(CFG, remat=True), family=gpt2_block,
    reference=types.SimpleNamespace(
        loss=lambda weights, tokens, targets: gpt2_reference.next_token_loss(
            gpt2_reference.logits(weights, tokens), targets)),
    weights=(), args=(), data=(2, 8), refused=())
FAMILY = Family(**STATED)
#: the files that state a family, and beside each those that share it
FILES = {
    "test_olmo_hybrid": (), "test_deepseek_v2": (), "test_smallthinker": (),
    "test_phi4_flash": (), "test_olmoe": (),
    "test_granite_hybrid": ("test_granite_hybrid_grads",),
    "test_kimi_linear": ("test_kimi_linear_stack",),
    "test_lfm2_moe": ("test_lfm2_moe_stack",)}


@pytest.fixture(scope="module", autouse=True)
def built():
    """`tfm.build_loss_and_grads` counted for this module's tests, as
    `tests/test_family.py` counts it: (what was built, what was traced)."""
    with programs.counted_builds() as counts:
        yield counts


# ------------------------------------------------------------ the statement

@pytest.mark.parametrize("field", sorted(STATED))
def test_a_statement_that_lacks_a_field_is_refused_by_name(field):
    with pytest.raises(TypeError, match=f"'{field}'"):
        Family(**{k: v for k, v in STATED.items() if k != field})
    required = [f.name for f in dataclasses.fields(Family)
                if f.default is dataclasses.MISSING
                and f.default_factory is dataclasses.MISSING]
    assert sorted(required) == sorted(STATED)


@pytest.mark.parametrize("changed", [{"remat": False}, {"d_ff": 48},
                                     {"dtype": jnp.bfloat16}],
                         ids=["no-remat", "another-width", "another-dtype"])
def test_a_timed_configuration_that_is_another_model_is_refused(changed):
    """What is held to the reference is the tiny model as the cell runs it:
    its algorithm and its remat policy may differ, nothing else."""
    timed = dataclasses.replace(STATED["timed"], **changed)
    with pytest.raises(ValueError, match="timed is cfg under remat"):
        Family(**dict(STATED, timed=timed))
    Family(**dict(STATED, timed=dataclasses.replace(
        CFG, remat=True, remat_policy="full", attn="flash")))


@pytest.mark.parametrize("name", sorted(FILES))
def test_two_families_statements_share_no_program(name):
    """The memo of `tests/family.py` knows a program by its configuration:
    a family's two are no other family's (nor this file's)."""
    stated = {other: importlib.import_module(other).FAMILY
              for other in FILES}
    mine = {stated[name].cfg, stated[name].timed}
    assert len(mine) == 2
    for other, theirs in stated.items():
        if other != name:
            assert not mine & {theirs.cfg, theirs.timed}, other
    assert not mine & {FAMILY.cfg, FAMILY.timed}
    for shares in FILES[name]:
        assert importlib.import_module(shares).FAMILY is stated[name]


# ------------------------------------------------------------------ the cases

def _shared_tests(module):
    return {name: thing for name, thing in vars(module).items()
            if name.startswith("test_") and inspect.isfunction(thing)
            and thing.__module__ == cases.__name__}


@pytest.mark.parametrize("name", sorted(
    FILES) + sorted(n for shares in FILES.values() for n in shares))
def test_a_file_that_imports_a_shared_test_imports_what_it_needs(name):
    """The fixtures a shared test asks for (and those they ask for) are
    found by name in the module that collected it, with the hook that gives
    the test its cases."""
    module = importlib.import_module(name)
    shared = _shared_tests(module)
    assert shared and isinstance(module.FAMILY, Family)
    assert module.pytest_generate_tests is cases.pytest_generate_tests
    fixtures = {n for n, thing in vars(cases).items()
                if hasattr(thing, "_fixture_function_marker")}
    assert {"stated", "params", "ours", "theirs"} <= fixtures
    wanted, seen = [a for test in shared.values()
                    for a in inspect.signature(test).parameters], set()
    while wanted:
        arg = wanted.pop()
        if arg in fixtures and arg not in seen:
            seen.add(arg)
            assert getattr(module, arg, None) is getattr(cases, arg), \
                (name, arg)
            # (a fixture's signature is its function's: `__wrapped__`)
            wanted += list(inspect.signature(getattr(cases, arg)).parameters)


class _Metafunc:
    """What `pytest_generate_tests` reads of a test being collected, and
    what it says back."""

    def __init__(self, function, module):
        self.function, self.module = function, module
        self.fixturenames = list(inspect.signature(function).parameters)
        self.said = []

    def parametrize(self, names, values):
        self.said.append((names, list(values)))


@pytest.mark.parametrize("names", sorted(cases.CASES))
def test_a_test_gets_exactly_the_cases_the_statement_names(names):
    argument, of = names.split(",")[0], cases.CASES[names]
    stated = importlib.import_module("test_lfm2_moe").FAMILY
    module = types.SimpleNamespace(FAMILY=stated)
    tests = [t for t in _shared_tests(cases).values()
             if argument in inspect.signature(t).parameters]
    assert tests, argument
    for test in tests:
        metafunc = _Metafunc(test, module)
        cases.pytest_generate_tests(metafunc)
        mine = [said for said in metafunc.said if said[0] == names]
        assert mine == [(names, list(of(stated)))]
        assert len(mine[0][1]) > 0
    want = {"leaf": programs.leaf_names(stated.cfg),
            "fault": stated.reference.FAULTS, "attn": stated.attns,
            "mesh": stated.refused}.get(argument)
    if want is not None:
        assert list(of(stated)) == list(want)


def test_a_test_of_another_file_gets_no_cases():
    def test_of_a_familys_own(leaf, fault):
        pass

    metafunc = _Metafunc(test_of_a_familys_own,
                         types.SimpleNamespace(FAMILY=FAMILY))
    cases.pytest_generate_tests(metafunc)
    assert metafunc.said == []


# --------------------------------------------------------------- the programs

def test_the_shared_cases_built_two_gradient_programs_and_no_third(
        built, ours, theirs):
    """The last test of this file, behind the shared cases it imports: the
    loss and every leaf's gradient were read off ONE program, one rank under
    remat, and `dp` = 2 without remat was the other; each traced once. (The
    reference is no program of `tfm`'s.)"""
    one, two = (FAMILY.timed, 1), (dataclasses.replace(FAMILY.timed,
                                                       remat=False), 2)
    assert built == ([one, two], [one, two])
