"""The tests every model family has, written once, as `tests/step_cases.py`
holds the step's: a family's file states the family in `FAMILY` (a `Family`
below: its tiny configuration, the configuration its cell times, its
reference and how weights and arguments map to it, its data, its refusals,
its tolerances where they differ by nature) and imports by name the fixtures
and the tests it runs; `pytest_generate_tests` gives a test its cases from
the statement of the module that collected it. What only one family has (the
shares of an expert layer, a router's rule, a mixer alone, counts at the
published widths) stays in its file. Nothing here asks which family it is:
what differs is data in the statement.

**Two gradient programs a family.** `ours` is ONE rank under remat, with the
policy and the attention the family's cell runs (`remat_policy` of its file
under `benchmark/configs/`): what the cell times is what is held to the
reference, the loss and every leaf's gradient, and the same program counts
the held pairs its row buffers left out. `dp` = 2 WITHOUT remat is held to
`ours`: two ranks and no recomputation say the same numbers. The remat
policies against each other keep one witness, `tests/test_olmo_hybrid.py`
(`jax.checkpoint` around the layer scan is `models/transformer.py`'s, no
family's own code). A tiny model's gradient program is 15-60 s of compiling
and nothing of running: a third one is a file's long pole
(`tests/test_family_cases.py` counts them). The train step is no program of
a family's file: `tests/step_cases.py` compiles each family's for its scopes
and runs it (`test_three_steps_lower_the_loss`).

A family that splits its tests over two files states `FAMILY` in one and
imports it into the other; each file imports the tests it runs, and a
program two tests share is built in the file that holds both (the memo of
`tests/family.py` is a process's own)."""

import dataclasses
import functools
import types
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family as programs
from family import mesh_of
from horovod_tpu.common.exceptions import HorovodTpuError
from horovod_tpu.models import transformer as tfm

EIGHT_BIT = {"e4m3": jnp.float8_e4m3fn, "e5m2": jnp.float8_e5m2}


def as_drawn(params):
    return params


def lively(louder, moved=("_scale",), shifted=(), keys=128):
    """`init`'s tree -> the tree with the leaves it draws as ones moved
    (names ending in `moved`: times 1 + 0.3 N(0, 1)), those it draws as
    zeros shifted (`shifted`: plus 0.3 N(0, 1)), and the parts whose faults
    are planted made loud enough to show at a tiny size (`louder`: a leaf's
    name -> what multiplies it). One compiled program."""
    @jax.jit
    def of(params):
        ks = iter(jax.random.split(jax.random.PRNGKey(11), keys))

        def one(path, leaf):
            name = path[-1].key
            if name.endswith(moved):
                return leaf * (1 + 0.3 * jax.random.normal(next(ks),
                                                           leaf.shape))
            if name in shifted:
                return leaf + 0.3 * jax.random.normal(next(ks), leaf.shape)
            return leaf * louder.get(name, 1.0)

        with jax.enable_x64(False):
            return jax.tree_util.tree_map_with_path(one, params)
    return of


@dataclasses.dataclass(frozen=True)
class Family:
    """A family's statement. The first eight fields have no default: a
    statement that lacks one is refused by name."""
    #: the tiny configuration: the logits, the limits, the refusals
    cfg: tfm.TransformerConfig
    #: `cfg` as the cell times it: its attention algorithm, under remat with
    #: its policy; `ours`, and without remat `dp` = 2
    timed: tfm.TransformerConfig
    #: `benchmark/families/<family>.py` and `benchmark/reference/<family>.py`
    family: types.ModuleType
    reference: types.ModuleType
    #: `family.reference_weights(params, *weights)`
    weights: tuple
    #: what follows (weights, tokens[, targets]) in the reference's `loss`,
    #: `forward` and `final_hidden`, and (params, tokens, logits) in
    #: `family.compare`
    args: tuple
    #: (sequences, tokens a sequence) of the one batch
    data: tuple
    #: (mesh sizes, changed fields, the refusal's words as a pattern)
    refused: tuple
    #: ... and by keyword (`first_expert=`)
    kwargs: dict = dataclasses.field(default_factory=dict)
    #: `init`'s tree -> the tree the tests run on (scales moved off one)
    lively: Callable = as_drawn
    #: (changed fields, mesh sizes) that validate, beside `cfg` on one rank
    #: and `timed` without remat on two
    accepted: tuple = ()
    #: the algorithms whose logits are held to the reference's, (atol, rtol)
    attns: tuple = ("local", "flash")
    logits_tol: tuple = (2e-5, 2e-4)
    #: the sound reference reads below this by the family's comparison
    sound_below: float = 1e-5
    #: a leaf's gradient: rtol, and the share of the leaf's largest entry
    #: allowed as absolute error beside it; the size below which a leaf has
    #: "nothing to compare"; leaves that take no gradient on either side;
    #: leaves that may be fainter than `least`
    leaf_rtol: float = 2e-3
    leaf_atol: float = 2e-4
    least: float = 1e-7
    no_gradient: tuple = ()
    faint: tuple = ()
    #: `dp` = 2 without remat against `ours`: the loss's rtol, and
    #: `assert_trees_close`'s keywords for the gradients
    two_ranks_loss: float = 1e-6
    two_ranks: dict = dataclasses.field(
        default_factory=lambda: {"rtol": 1e-4, "atol": 1e-7})

    def __post_init__(self):
        same = dataclasses.replace(self.timed, attn=self.cfg.attn,
                                   remat=self.cfg.remat,
                                   remat_policy=self.cfg.remat_policy)
        if same != self.cfg or not self.timed.remat:
            raise ValueError(
                "timed is cfg under remat, by the cell's algorithm and "
                "policy, and nothing else of it differs")

    @functools.cached_property
    def batch(self):
        return programs.data(self.cfg.vocab, *self.data)

    def reference_weights(self, params):
        return self.family.reference_weights(params, *self.weights)

    def their(self, function, params, *batch, **more):
        """`reference.<function>` on `params`' weights and `batch`."""
        return getattr(self.reference, function)(
            self.reference_weights(params), *batch, *self.args,
            **self.kwargs, **more)

    def compare(self, params, logits, **more):
        return self.family.compare(params, self.batch[0], logits, *self.args,
                                   **self.kwargs, **more)


#: the arguments of a test that are parametrized together: their cases, from
#: the module's statement
CASES = {
    "leaf": lambda f: programs.leaf_names(f.cfg),
    "fault": lambda f: f.reference.FAULTS,
    "operands": lambda f: [pytest.param(v, id=k)
                           for k, v in EIGHT_BIT.items()],
    "attn": lambda f: f.attns,
    "mesh, changed, message": lambda f: f.refused,
}


def pytest_generate_tests(metafunc):
    """A test of this file, collected in a module that imported it, gets the
    cases that module's `FAMILY` states."""
    if metafunc.function.__module__ != __name__:
        return
    for names, cases in CASES.items():
        if names.split(",")[0] in metafunc.fixturenames:
            metafunc.parametrize(names, cases(metafunc.module.FAMILY))


# ------------------------------------------------------------- the fixtures

@pytest.fixture(scope="module")
def stated(request):
    return request.module.FAMILY


@pytest.fixture(scope="module")
def params(stated):
    with jax.enable_x64(False):
        return stated.lively(programs.init(stated.cfg))


@pytest.fixture(scope="module")
def ours(stated, params):
    """(loss, gradients, counts) of the program on one rank, as the cell
    runs it."""
    with jax.enable_x64(False):
        return programs.loss_and_grads(stated.timed, metrics=True)(
            params, *stated.batch)


@pytest.fixture(scope="module")
def theirs(stated, params):
    """(loss, gradients) of the reference, in the program's tree (one
    compiled program: eagerly it is a dispatch and a compile an operation,
    three times the seconds)."""
    with jax.enable_x64(False):
        return jax.jit(jax.value_and_grad(lambda p: stated.their(
            "loss", p, *stated.batch)))(params)


@pytest.fixture(scope="module")
def logits(stated, params):
    """The program's logits for the batch's tokens, once (`cfg`'s own
    algorithm)."""
    with jax.enable_x64(False):
        return programs.forward(stated.cfg)(params, stated.batch[0])


@pytest.fixture(scope="module")
def their_logits(stated, params):
    with jax.enable_x64(False):
        return stated.their("forward", params, stated.batch[0])


@pytest.fixture(scope="module")
def sound(stated, params, logits):
    """The family's comparison of `logits` with the sound reference: (rms,
    the program's loss, the reference's)."""
    with jax.enable_x64(False):
        return [float(x) for x in stated.compare(params, logits)[:3]]


# ---------------------------------------------- the program and the reference

def test_logits_equal_the_references(stated, params, their_logits, attn):
    atol, rtol = stated.logits_tol
    with jax.enable_x64(False):
        got = programs.forward(dataclasses.replace(stated.cfg, attn=attn))(
            params, stated.batch[0])
    np.testing.assert_allclose(got, their_logits, atol=atol, rtol=rtol)


def test_loss_equals_the_references(ours, theirs):
    """... and the held pairs that found no room in a row buffer are
    counted: none at these sizes (a model without experts counts none
    either; a buffer that overflows is `tests/test_kimi_linear_stack.py`'s
    and `tests/test_deepseek_v2_share.py`'s)."""
    np.testing.assert_allclose(ours[0], theirs[0], rtol=1e-5)
    assert int(ours[2]["experts_dropped"]) == 0


def test_every_leafs_gradient_equals_the_references(stated, ours, theirs,
                                                    leaf):
    """One case a leaf, so that a failure names it: a reader's cotangent
    that did not arrive, a sum over heads left out, a tied table read once,
    is a gradient that differs."""
    got, want = (programs.leaves(x[1])[leaf] for x in (ours, theirs))
    size = float(jnp.max(jnp.abs(want)))
    if any(name in leaf for name in stated.no_gradient):
        assert size == 0.0 == float(jnp.max(jnp.abs(got)))
        return
    assert size > stated.least or any(name in leaf for name in stated.faint),\
        "nothing to compare"
    np.testing.assert_allclose(
        got, want, rtol=stated.leaf_rtol,
        atol=stated.leaf_atol * size + stated.least / 10)


# ------------------------------------------------------------ meshes, remat

def test_dp_2_without_remat_equals_one_rank_under_remat(stated, params, ours):
    """`ours` is one rank under remat. The same model WITHOUT remat on two
    ranks, the batch cut between them: the layers' gradients reduced inside
    the backward loop, nothing recomputed; loss and every gradient the same.
    (One program for both questions.)"""
    cfg = dataclasses.replace(stated.timed, remat=False)
    with jax.enable_x64(False):
        mesh = mesh_of(dp=2)
        tfm.validate_cfg_for_mesh(cfg, mesh)
        two = programs.loss_and_grads(cfg, dp=2)(
            tfm.shard_params(params, cfg, mesh), *stated.batch)
    np.testing.assert_allclose(two[0], ours[0], rtol=stated.two_ranks_loss)
    programs.assert_trees_close(two[1], ours[1], **stated.two_ranks)


# --------------------------------------------------------------- the limits

def test_the_limits_refuse_a_planted_fault(stated, params, logits, sound,
                                           fault):
    """The program's logits against the reference computed with one
    mechanism wrong: by one of the family's limits it is not correct, and
    against the sound reference it is, with room."""
    with jax.enable_x64(False):
        wrong = [float(x) for x in
                 stated.compare(params, logits, fault=fault)[:3]]
    assert all(stated.family.within(*sound))
    assert sound[0] < stated.sound_below
    assert not all(stated.family.within(*wrong)), wrong


def test_an_unknown_fault_is_refused(stated, params):
    with pytest.raises(ValueError, match="choose from"):
        stated.their("final_hidden", params, stated.batch[0],
                     fault="no_such_fault")


def test_the_limits_refuse_an_8_bit_float(stated, params, logits, operands):
    with jax.enable_x64(False):
        found = stated.compare(params, logits, operands=operands)
    assert not all(stated.family.within(*(float(x) for x in found[:3])))


def test_the_familys_comparison_reads_zero_for_the_reference(stated, params,
                                                             their_logits):
    """`family.compare` (the reference's head a block of tokens at a time)
    against the reference's whole forward pass and its loss; where experts
    are held, its count of their rows against the routes themselves, of the
    expert layers (a leading dense layer routes nothing, and its weights are
    a dense MLP's of its own width); a tied head has no leaf."""
    cfg, (tokens, targets) = stated.cfg, stated.batch
    with jax.enable_x64(False):
        weights = stated.reference_weights(params)
        rms, got, want, *rows = stated.compare(params, their_logits)
        loss = stated.their("loss", params, tokens, targets)
    assert float(rms) < 1e-6
    np.testing.assert_allclose([float(got), float(want)], float(loss),
                               rtol=1e-6)
    assert ("head" in weights) != cfg.tied_head
    for layer in weights["layers"][:cfg.first_k_dense]:
        assert "router" not in layer
        assert layer["w_up"].shape == (cfg.d_model, cfg.d_ff_dense)
    if not cfg.experts_held:
        assert not rows
        return
    with jax.enable_x64(False):
        _, routes = stated.their("final_hidden", params, tokens)
    layers, held = cfg.n_layers - cfg.first_k_dense, cfg.experts_held
    assert routes.shape == (layers, *stated.data, cfg.experts_per_token)
    assert rows[0].shape == (layers, held)
    assert [int(np.sum(np.asarray(routes) == cfg.first_expert + e))
            for e in range(held)] \
        == [int(rows[0][:, e].sum()) for e in range(held)]


# -------------------------------------------------------------- refusals

def test_validate_refuses_by_name(stated, mesh, changed, message):
    cfg = dataclasses.replace(stated.cfg, **changed)
    with pytest.raises(HorovodTpuError, match=message):
        tfm.validate_cfg_for_mesh(cfg, mesh_of(**mesh))


def test_validate_accepts_the_model_where_it_runs(stated):
    tfm.validate_cfg_for_mesh(stated.cfg, mesh_of())
    tfm.validate_cfg_for_mesh(dataclasses.replace(stated.timed, remat=False),
                              mesh_of(dp=2))
    for changed, mesh in stated.accepted:
        tfm.validate_cfg_for_mesh(dataclasses.replace(stated.cfg, **changed),
                                  mesh_of(**mesh))
