"""The train step of nine tiny configurations of the kinds the benchmark's
LM cells run, lowered ONCE a (family, `dp`) on the CPU (`family.lowered_step`)
and read twice: its text against what an earlier commit lowered
(`tests/fixtures/hlo/lowered_steps.json.gz`), and the scopes of the compiled
step (`transformer.STEP_SCOPES` and the mixers' `moe.*`, `mla.*`, `gdn.*`,
`kda.*`, `ssm.*`, `gmu.*`, `shortconv.*`) in the compiled text's `op_name`s.
The tests stand here and run in three files, `tests/test_lowered_steps.py`,
`tests/test_hybrid_steps.py` and `tests/test_step_scopes.py`, each of which
imports them and names its families in `FAMILIES`: `pytest_generate_tests`
below cuts a test's cases to them. A family's cases stand in one file so that
one process lowers its step (the memo of `tests/family.py` is a process's
own), and in three so that none is a long pole of the suite.

The configurations: a GPT-2 block, OLMoE's, DeepSeek-V2's; since PR 45 the
`CFG`s of `tests/test_olmo_hybrid.py`, a layer pattern,
`tests/test_phi4_flash.py`, segments, and `tests/test_smallthinker.py`, a
pattern with a share of the experts; since PR 46
`tests/test_granite_hybrid.py`'s, Mamba-2 layers to one attention layer with
the four multipliers; since PR 50 `tests/test_kimi_linear.py`'s; since PR 54
`tests/test_lfm2_moe.py`'s. One rank,
where nothing is reduced, and `dp` = 2, where the layers' gradients are
reduce-scattered inside the backward loop (a segmented stack's are summed
after it).

**The texts.** A change to `models/` that is not meant to touch these
models' programs leaves the text as it is; one that is meant to takes the
fixture anew (`write_fixture()` below, on the tree whose programs are the
new truth) and says so. Whose text each entry is: the GPT-2 block's is the
commit's before the layer pattern (PR 31's, a31c4fe). PR 43 took the two
expert models' anew: the row movers' two `custom_vjp`s moved from
`parallel/moe.py` to `ops/row_gather.py` (the order in which the layer
scan's constants are handed to its body changed), and where a share of the
experts is held (`deepseek_v2`) a take's free rows are gathered from zero
rows behind the source where a select cleared them; PR 44 took them anew
again: the layer scan hands the experts' products their stacked leaves and
the layer's number (`ops/grouped_matmul.py`), so the scan has the stacks as
constants and the layers' numbers among its `xs`, and each product adds
`layer * E` to its visits' groups. The three families of PR 45 were written
on PR 44's commit (bf13d0e), before PR 45 moved the mixers, the FFNs and the
gradient reduction out of `models/transformer.py` and gave patterns and
segments one runner (`write_fixture(only_new=True)`: the older entries
untouched). The Granite family's is PR 46's own, the PR that brought its
mixer and the multipliers, whose defaults leave the six older texts as they
were. PR 47 took every entry anew: the embedding's lookup is
`ops/row_gather.py` `lookup_rows`, whose backward pass is a sort, a batched
product and a gather where the gather's transpose was a scatter-add, in
every model's step. PR 49 (the flash forward walks a whole block in strips
of 256 rows and writes a masked block's next product ahead of a strip's
softmax) left every entry as it was: these models' 32 tokens are one block
of 32 rows, which no strip divides and whose one strip has none after it, so
nothing was taken anew. PR 50 wrote the Kimi Linear family's entry (one
rank) on its own tree (`write_fixture(only_new=True)`:
`tests/test_kimi_linear.py`'s `CFG` as the cell runs it, a dense prefix
inside a pattern whose stack is two segments, the per-channel rule's
kernels, sigmoid scores with a selection bias) and left the thirteen older
texts as they were: the scalar rule of `olmo_hybrid` lowers to the kernels
it lowered to, the softmax router to the program it was. PR 51 took the Kimi
Linear family's entry anew (the per-channel rule's kernels make their
decayed products by a halving of pivots, a product a level) and left the
thirteen older texts byte for byte the parent's. PR 52 moved these tests
here and changed no program: no entry was taken anew. PR 53 took the OLMo
Hybrid family's two entries and the Kimi Linear family's anew (both forms of
the rule make T = (I + A)^-1 by panels of 16 rows: `_unit_lower_inverse`)
and left the eleven others byte for byte the parent's. PR 54 wrote the LFM2
family's entry (one rank) on its own tree (`write_fixture(only_new=True)`:
`tests/test_lfm2_moe.py`'s `CFG` as the cell runs it: gated short-convolution
layers to one grouped-query attention layer with QK-norm per head behind a
dense layer, sigmoid scores renormalised over their sum + 1e-6, a tied head)
and left the fourteen older texts byte for byte the parent's: QK-norm over the
whole vector is the code it was, and a renormalisation whose epsilon is 0
divides by the sum alone. PR 55 took the thirteen entries whose models run
the flash kernels anew (every family but `smallthinker`, whose two stay byte
for byte the parent's): the backward pass of `ops/flash_attention.py` is one
`pallas_call` where it was two, in each of those steps (700 to 4,000 lines
fewer of the interpreted kernels' text an entry; nothing else of a step
differs).

The text is JAX's StableHLO without locations, so it does not depend on
where the checkout lies; it does depend on the JAX version (0.9.0)."""

import dataclasses
import functools
import gzip
import json
import os
import re

import jax
import jax.numpy as jnp
import optax
import pytest

import family
from horovod_tpu.models import transformer as tfm
from test_granite_hybrid import CFG as GRANITE
from test_kimi_linear import CFG as KIMI
from test_lfm2_moe import TIMED as LFM2
from test_olmo_hybrid import CFG as HYBRID
from test_phi4_flash import CFG as PHI4_FLASH
from test_smallthinker import CFG as SMALLTHINKER

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "hlo", "lowered_steps.json.gz")

CONFIGS = {
    "gpt2": tfm.TransformerConfig(
        vocab=96, d_model=64, n_heads=4, d_ff=128, n_layers=2, max_seq=32,
        attn="flash", dtype=jnp.bfloat16, remat=True),
    "olmoe": tfm.TransformerConfig(
        vocab=96, d_model=64, n_heads=4, d_ff=32, n_layers=2, max_seq=32,
        num_experts=4, experts_per_token=2, load_balance_coef=0.01,
        router_z_coef=0.001, norm="rmsnorm", positions="rope", qk_norm=True,
        mlp="swiglu", attn="flash", dtype=jnp.bfloat16, remat=True),
    "deepseek_v2": tfm.TransformerConfig(
        vocab=96, d_model=64, n_heads=4, d_ff=32, n_layers=3, max_seq=32,
        num_experts=8, experts_per_token=2, experts_held=2, first_expert=2,
        shared_experts=2, first_k_dense=1, d_ff_dense=96,
        load_balance_coef=0.002, balance_per_sequence=True, norm="rmsnorm",
        rms_norm_eps=1e-6, positions="rope",
        yarn=tfm.Yarn(factor=40, original_max=4096, beta_fast=32,
                      beta_slow=1, mscale=0.707, mscale_all_dim=0.707),
        attention="mla", kv_latent=24, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16, mlp="swiglu", attn="flash", dtype=jnp.bfloat16,
        remat=True),
    # a pattern, segments, a pattern with a share of the experts
    "olmo_hybrid": HYBRID,
    "phi4_flash": PHI4_FLASH,
    "smallthinker": SMALLTHINKER,
    # a pattern of Mamba-2 layers and one attention layer, experts beside a
    # shared MLP, a tied head, the four multipliers; as the cell runs it
    "granite_hybrid": dataclasses.replace(
        GRANITE, attn="flash", dtype=jnp.bfloat16, remat=True,
        remat_policy="full"),
    # Kimi Delta Attention layers to one latent-attention layer without a
    # rotation behind a dense KDA layer, sigmoid-scored experts with a
    # selection bias beside a shared one; as the cell runs it
    "kimi_linear": dataclasses.replace(
        KIMI, attn="flash", dtype=jnp.bfloat16, remat=True,
        remat_policy="full"),
    # gated short-convolution layers to one grouped-query attention layer
    # with QK-norm per head behind a dense layer, a share of sigmoid-scored
    # experts, a tied head; as the cell runs it
    "lfm2_moe": dataclasses.replace(LFM2, dtype=jnp.bfloat16,
                                    remat_policy="dots"),
}
#: the scopes are read off the flash kernels' step (`attn.window`); the text
#: the fixture holds for SmallThinker is its `CFG`'s own, `attn` "local"
SCOPED = dict(CONFIGS, smallthinker=dataclasses.replace(SMALLTHINKER,
                                                        attn="flash"))
#: (a share of the experts is one rank's, with an expert axis; a data-parallel
#: axis of two is the Granite and SmallThinker families' to show)
#: (the Kimi Linear family's text is three times any other's, the rule's
#: per-channel kernels unrolled in it: one rank holds it; `dp` = 2 of its
#: two-segment stack is held to one rank's numbers in
#: `tests/test_kimi_linear_stack.py`, the LFM2 family's in
#: `tests/test_lfm2_moe_stack.py`)
LOWERED = [(name, dp) for name in CONFIGS for dp in (1, 2)
           if not (name in ("deepseek_v2", "kimi_linear", "lfm2_moe")
                   and dp == 2)]

SSD = ("ssd.project", "ssd.conv", "ssd.scan", "ssd.gate", "ssd.out")
ATTN = ("attn.project", "attn.attend", "attn.out")
VOCAB = ("vocab.embed", "vocab.head", "vocab.loss")
MOE = ("moe.route", "moe.dispatch", "moe.experts", "moe.combine")
#: the scopes each model's step has, but the two of no model's own
#: (`opt.update`, `grad.reduce`)
HAS = {
    "gpt2": ATTN + ("mlp.dense",) + VOCAB,
    "olmoe": ATTN + MOE + VOCAB,
    "deepseek_v2": ("mla.project", "mla.rope", "mla.attend", "mla.out",
                    "mlp.dense", "moe.shared") + MOE + VOCAB,
    "olmo_hybrid": ATTN + ("gdn.project", "gdn.conv", "gdn.scan", "gdn.gate",
                           "gdn.out", "mlp.dense") + VOCAB,
    "phi4_flash": ATTN + ("attn.window", "ssm.project", "ssm.conv",
                          "ssm.scan", "ssm.gate", "ssm.out", "gmu.project",
                          "gmu.gate", "gmu.out", "mlp.dense") + VOCAB,
    "smallthinker": ATTN + ("attn.window",) + MOE + VOCAB,
    "granite_hybrid": ATTN + SSD + ("moe.shared",) + MOE + VOCAB,
    "kimi_linear": ("kda.project", "kda.conv", "kda.scan", "kda.gate",
                    "kda.out", "mla.project", "mla.rope", "mla.attend",
                    "mla.out", "mlp.dense", "moe.shared") + MOE + VOCAB,
    "lfm2_moe": ATTN + ("shortconv.project", "shortconv.mix",
                        "shortconv.out", "mlp.dense") + MOE + VOCAB,
}
_OP_NAME = re.compile(r'op_name="([^"]*)"')

#: a test's argument names and cases, a case's first value its family
CASES = {
    "test_the_lowered_step_is_the_parents": ("name, dp", LOWERED),
    "test_the_lookup_leaves_the_step_one_scatter_fewer": ("name", [
        pytest.param("gpt2", id="untied"),
        pytest.param("phi4_flash", id="tied")]),
    "test_a_scope_is_in_the_forward_and_in_the_backward_pass": (
        "name, scope",
        [(name, scope) for name, scopes in HAS.items() for scope in scopes]),
    "test_the_step_has_its_scopes_and_no_other": ("name", sorted(HAS)),
    # (but the Kimi Linear family's: the CPU runtime has no bf16 x bf16 ->
    # f32 product for the per-channel rule's kernels as the cell computes
    # them, "Unsupported element type for DotThunk"; its step is compiled
    # and read here and run by `tests/benchmark/` at a tiny size)
    "test_three_steps_lower_the_loss": (
        "name", sorted(set(HAS) - {"kimi_linear"})),
    "test_the_reduction_has_its_scope_where_something_is_reduced": (
        "name", ["gpt2", "olmoe", "olmo_hybrid", "phi4_flash",
                 "smallthinker", "granite_hybrid"]),
    "test_no_instruction_lies_under_two_layers_scopes": ("name, dp", [
        ("gpt2", 2), ("olmoe", 2), ("deepseek_v2", 1), ("olmo_hybrid", 2),
        ("phi4_flash", 2), ("smallthinker", 2), ("granite_hybrid", 2),
        ("kimi_linear", 1), ("lfm2_moe", 1)]),
}


def pytest_generate_tests(metafunc):
    """A test of `CASES`, collected in a module that imported it, gets its
    cases of the families that module names in `FAMILIES`."""
    if metafunc.function.__name__ in CASES:
        names, cases = CASES[metafunc.function.__name__]

        def family_of(case):
            values = getattr(case, "values", case)
            return values if isinstance(values, str) else values[0]

        metafunc.parametrize(names, [
            case for case in cases
            if family_of(case) in metafunc.module.FAMILIES])


# ------------------------------------------------------------- the texts

def write_fixture(only_new: bool = False) -> None:
    """Takes the fixture anew; with `only_new`, only the cases it lacks."""
    texts = {}
    if only_new:
        with gzip.open(FIXTURE, "rt") as f:
            texts = json.load(f)
    texts.update({key: family.lowered_step(CONFIGS[name], dp).as_text()
                  for name, dp in LOWERED
                  if (key := f"{name}-dp{dp}") not in texts})
    with gzip.open(FIXTURE, "wt") as f:
        json.dump(texts, f)


@pytest.fixture(scope="module")
def parents():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


def test_the_lowered_step_is_the_parents(parents, name, dp):
    got = family.lowered_step(CONFIGS[name], dp).as_text()
    want = parents[f"{name}-dp{dp}"]
    if got != want:
        a, b = got.splitlines(), want.splitlines()
        first = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                     min(len(a), len(b)))
        pytest.fail(f"{name} dp={dp}: {len(a)} lines against the parent's "
                    f"{len(b)}; first difference at line {first + 1}:\n"
                    f"  now:    {a[first][:300] if first < len(a) else ''}\n"
                    f"  parent: {b[first][:300] if first < len(b) else ''}")


def test_the_lookup_leaves_the_step_one_scatter_fewer(monkeypatch, name):
    """Plain indexing in the lookup's place puts one scatter (the gather's
    transpose, a scatter-add of the tokens' rows into the table) and takes
    one sort out of the lowered step; nothing else of the step is one."""
    def count(lowered):
        text = lowered.as_text()
        return tuple(text.count(f'"stablehlo.{what}"(')
                     for what in ("scatter", "sort"))

    scatters, sorts = count(family.lowered_step(CONFIGS[name]))
    monkeypatch.setattr(tfm, "lookup_rows",
                        lambda table, ids: (table[ids], table))
    # (built anew, past the memo, which holds the step as it is)
    assert count(family.lowered_step.__wrapped__(CONFIGS[name])) \
        == (scatters + 1, sorts - 1)


# ------------------------------------------------------------ the scopes

@functools.lru_cache(maxsize=None)
def compiled(name: str, dp: int):
    """`name`'s train step compiled for `dp` CPU devices."""
    return family.lowered_step(SCOPED[name], dp).compile()


@functools.lru_cache(maxsize=None)
def compiled_text(name: str, dp: int) -> str:
    return compiled(name, dp).as_text()


def op_names(name: str, dp: int) -> frozenset:
    return frozenset(_OP_NAME.findall(compiled_text(name, dp)))


def scopes_of(op_name: str) -> list:
    """The components of an `op_name` that are scopes of the step, with the
    wrappers of the transformations applied around them taken off
    (`transpose(jvp(vocab.head))`)."""
    bare = (re.sub(r"^(?:[\w\-]+\()+", "", c).rstrip(")")
            for c in op_name.split("/"))
    return [c for c in bare if re.match(
        r"(attn|mlp|vocab|grad|opt|moe|mla|gdn|kda|ssm|ssd|gmu|shortconv)\.",
        c)]


def under(names, scope: str, backward: bool) -> list:
    return [n for n in names if scope in scopes_of(n)
            and ("transpose(" in n) == backward]


def test_a_scope_is_in_the_forward_and_in_the_backward_pass(name, scope):
    names = op_names(name, 1)
    assert under(names, scope, backward=False), (name, scope)
    assert under(names, scope, backward=True), (name, scope)


def test_the_step_has_its_scopes_and_no_other(name):
    found = {s for n in op_names(name, 1) for s in scopes_of(n)}
    assert found == set(HAS[name]) | {"opt.update"}
    assert under(op_names(name, 1), "opt.update", backward=False)


def test_the_reduction_has_its_scope_where_something_is_reduced(name):
    """On one rank nothing is reduced and the scope is absent; at `dp` = 2
    the halving inside the backward loop and the sums after it have it (a
    segmented stack's gradients are all summed after it)."""
    assert not [n for n in op_names(name, 1) if "grad.reduce" in n]
    names = op_names(name, 2)
    if not CONFIGS[name].segments:
        assert under(names, "grad.reduce", backward=True)    # in the loop
    assert under(names, "grad.reduce", backward=False)   # after it
    found = {s for n in names for s in scopes_of(n)}
    assert found == set(HAS[name]) | {"opt.update", "grad.reduce"}


def test_no_instruction_lies_under_two_layers_scopes(name, dp):
    """`mlp.dense` is entered by `ffns`' two dense rows and not in `_mlp`,
    which the shared experts run under `moe.shared`; the reduction
    inside the backward loop is no part of the layer whose gradient it
    sums."""
    for n in op_names(name, dp):
        layers = {s.split(".")[0] for s in scopes_of(n)}
        assert len(layers) <= 1, n


# -------------------------------------------------------------- the step

def test_three_steps_lower_the_loss(name):
    """The compiled step whose scopes are read above, run: three steps of
    AdamW on one batch, the model as the cell computes it, and the loss
    falls. (No family's file builds a train step of its own.)"""
    cfg, step = SCOPED[name], compiled(name, 1)
    with jax.enable_x64(False):
        params = family.init(cfg)
        state = (params, optax.adamw(1e-3).init(params))
        batch, losses = family.data(cfg.vocab, 2, 32), []
        for _ in range(3):
            *state, loss = step(*state, *batch)
            losses.append(float(loss))
    assert losses[2] < losses[0], losses
