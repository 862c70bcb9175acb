"""The scopes of the compiled train step (`transformer.STEP_SCOPES` and the
mixers' `moe.*`, `mla.*`, `gdn.*`, `kda.*`, `ssm.*`, `gmu.*`): for tiny
configurations of the eight kinds the benchmark's LM cells run, compiled on the CPU, every scope the
model has is in the compiled text's `op_name`s, in the forward pass and in
the backward pass; the gradient reduction's only where something is
reduced; and `DistributedOptimizer.step` records its two phases as spans of
the JAX profiler. That the scopes change nothing but names is
`tests/test_lowered_steps.py`'s to show: its fixture is untouched."""

import dataclasses
import functools
import glob
import inspect
import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.profiler import ProfileData

from horovod_tpu.models import ffns, mixers, transformer as tfm
from horovod_tpu.parallel.mesh import MeshSpec, build_mesh
from test_lowered_steps import CONFIGS
from test_olmo_hybrid import CFG as HYBRID
from test_phi4_flash import CFG as PHI4_FLASH
from test_smallthinker import CFG as SMALLTHINKER

CONFIGS = dict(CONFIGS, olmo_hybrid=HYBRID, phi4_flash=PHI4_FLASH,
               smallthinker=dataclasses.replace(SMALLTHINKER, attn="flash"))
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
}
_OP_NAME = re.compile(r'op_name="([^"]*)"')


@functools.lru_cache(maxsize=None)
def op_names(name: str, dp: int) -> frozenset:
    """The `op_name`s of `name`'s train step compiled for `dp` CPU devices."""
    cfg = CONFIGS[name]
    mesh = build_mesh(MeshSpec(dp=dp), devices=jax.devices()[:dp])
    opt = optax.adamw(1e-3)
    with jax.enable_x64(False):   # as the benchmark runs
        params = jax.eval_shape(lambda k: tfm.init(k, cfg),
                                jax.random.PRNGKey(0))
        state = jax.eval_shape(opt.init, params)
        tokens = jax.ShapeDtypeStruct((2 * dp, 32), jnp.int32)
        text = tfm.build_train_step(cfg, mesh, opt).lower(
            params, state, tokens, tokens).compile().as_text()
    return frozenset(_OP_NAME.findall(text))


def scopes_of(op_name: str) -> list:
    """The components of an `op_name` that are scopes of the step, with the
    wrappers of the transformations applied around them taken off
    (`transpose(jvp(vocab.head))`)."""
    bare = (re.sub(r"^(?:[\w\-]+\()+", "", c).rstrip(")")
            for c in op_name.split("/"))
    return [c for c in bare if re.match(
        r"(attn|mlp|vocab|grad|opt|moe|mla|gdn|kda|ssm|ssd|gmu)\.", c)]


def under(names, scope: str, backward: bool) -> list:
    return [n for n in names if scope in scopes_of(n)
            and ("transpose(" in n) == backward]


@pytest.mark.parametrize("name, scope", [
    (name, scope) for name, scopes in HAS.items() for scope in scopes])
def test_a_scope_is_in_the_forward_and_in_the_backward_pass(name, scope):
    names = op_names(name, 1)
    assert under(names, scope, backward=False), (name, scope)
    assert under(names, scope, backward=True), (name, scope)


@pytest.mark.parametrize("name", sorted(HAS))
def test_the_step_has_its_scopes_and_no_other(name):
    found = {s for n in op_names(name, 1) for s in scopes_of(n)}
    assert found == set(HAS[name]) | {"opt.update"}
    assert under(op_names(name, 1), "opt.update", backward=False)


@pytest.mark.parametrize("name", ["gpt2", "olmoe", "olmo_hybrid",
                                  "phi4_flash", "smallthinker",
                                  "granite_hybrid"])
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


@pytest.mark.parametrize("name, dp", [
    ("gpt2", 2), ("olmoe", 2), ("deepseek_v2", 1), ("olmo_hybrid", 2),
    ("phi4_flash", 2), ("smallthinker", 2), ("granite_hybrid", 2),
    ("kimi_linear", 1)])
def test_no_instruction_lies_under_two_layers_scopes(name, dp):
    """`mlp.dense` is entered by `ffns`' two dense rows and not in `_mlp`,
    which the shared experts run under `moe.shared`; the reduction
    inside the backward loop is no part of the layer whose gradient it
    sums."""
    for n in op_names(name, dp):
        layers = {s.split(".")[0] for s in scopes_of(n)}
        assert len(layers) <= 1, n


def test_the_vocabulary_is_what_the_source_enters():
    """`STEP_SCOPES` is every scope `models/transformer.py` and its layer
    parts (`models/mixers.py`, `models/ffns.py`) enter outside the mixers'
    own (`moe.shared`, `mla.*`, `gdn.*`, `kda.*`, `ssm.*`, `gmu.*`; a Mamba-2
    layer's `ssd.*` are listed in it), no more and no less; and a part enters its scopes whatever the stack: the rows of
    `MIXERS` and `FFNS` know of no pattern."""
    parts = inspect.getsource(mixers) + inspect.getsource(ffns)
    entered = set(re.findall(r'named_scope[(,]\s*"([^"]+)"',
                             inspect.getsource(tfm) + parts))
    own = {s for s in entered
           if s.startswith(("moe.", "mla.", "gdn.", "kda.", "ssm.", "gmu."))}
    assert entered - own == set(tfm.STEP_SCOPES)
    assert len(set(tfm.STEP_SCOPES)) == len(tfm.STEP_SCOPES)
    assert "layer_pattern" not in parts + inspect.getsource(tfm._layer)
    assert own >= {"mla.project", "gdn.scan", "kda.scan", "ssm.scan",
                   "gmu.gate", "moe.shared"}
    assert set(tfm.STEP_SCOPES) >= set(SSD)


def test_the_optimizers_phases_are_spans_of_the_profiler(hvd, tmp_path):
    """`hvd.opt.reduce` and `hvd.opt.apply`, once a step each, on the host
    plane of a `jax.profiler` trace, the apply after the reduction."""
    opt = hvd.DistributedOptimizer(optax.sgd(0.1))
    params = {"w": jnp.ones((4, 4), jnp.float32)}
    grads = {"w": jnp.full((4, 4), 0.5, jnp.float32)}
    state = opt.init(params)
    params, state = opt.step(grads, params, state)   # compiles, untraced
    steps = 3
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(steps):
            params, state = opt.step(grads, params, state)
        jax.block_until_ready(params)
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert len(files) == 1
    spans = sorted(
        (e.start_ns, e.name) for plane in ProfileData.from_file(
            files[0]).planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.startswith("hvd."))
    assert [name for _, name in spans] == \
        ["hvd.opt.reduce", "hvd.opt.apply"] * steps
