"""The lowered train steps of `tests/step_cases.py`, a third of them: the
SmallThinker pattern and the Granite 4.0-H one, as
`tests/test_lowered_steps.py` holds its families'; what their layers put
under which scope, read off the same compiled steps; the vocabulary of
scopes against the source; and `DistributedOptimizer.step`'s two phases as
spans of the JAX profiler."""

import dataclasses
import glob
import inspect
import re

import jax
import jax.numpy as jnp
import optax
from jax.profiler import ProfileData

import family
from benchmark.harness import hlo, scope_time
from horovod_tpu.models import ffns, mixers, transformer as tfm
from step_cases import (  # noqa: F401  (the tests, cut to FAMILIES)
    SCOPED, SSD, compiled_text, parents, pytest_generate_tests,
    test_a_scope_is_in_the_forward_and_in_the_backward_pass,
    test_no_instruction_lies_under_two_layers_scopes,
    test_the_lowered_step_is_the_parents,
    test_the_reduction_has_its_scope_where_something_is_reduced,
    test_the_step_has_its_scopes_and_no_other,
    test_three_steps_lower_the_loss)

FAMILIES = ("smallthinker", "granite_hybrid")


def _ops_under(text):
    """prefix -> the `op_name`s of `text`'s instructions under that scope,
    as the benchmark's reader finds them."""
    table = hlo.index(text)
    ops = dict(re.findall(r'%?([\w.\-]+) = [^\n]*op_name="([^"]*)"', text))
    return lambda prefix: {ops[name] for name in scope_time.names_under(
        text, table, prefix)}


def test_no_instruction_of_granites_layers_lies_outside_a_scope():
    """The two products of the input projection under `ssd.project`, the
    shifted sums under `ssd.conv`, the softplus, the decays and the
    kernels under `ssd.scan`, the gate and the norm's rsqrt under
    `ssd.gate`, the output product under `ssd.out`; the renormalised
    weights under `moe.route` and the shared MLP under `moe.shared`."""
    under = _ops_under(compiled_text("granite_hybrid", 1))
    assert set(tfm.STEP_SCOPES) >= set(SSD)
    assert any("ssd.project/" in op and op.endswith("/dot_general")
               for op in under("ssd.project"))
    assert under("ssd.conv") and under("ssd.out")
    scan = under("ssd.scan")
    assert any(op.endswith("/exp") for op in scan)     # the decays
    assert any("softplus" in op or "log1p" in op or "logaddexp" in op
               for op in scan)
    assert any(op.endswith("rsqrt") for op in under("ssd.gate"))
    assert any(op.endswith("moe.route/div") for op in under("moe.route"))
    assert under("moe.shared")
    # forward and backward, every scope
    for scope in SSD:
        assert any("transpose(" in op for op in under(scope)), scope
        assert any("transpose(" not in op for op in under(scope)), scope


def test_no_instruction_of_smallthinkers_layers_lies_outside_a_scope():
    """The scores (renormalisation included) under `moe.route`, the rotation
    under `attn.project` in rotating layers only, the windowed kernels under
    `attn.attend/attn.window`, the full layer's under `attn.attend` alone,
    the ReLU gate under `moe.experts`."""
    text = compiled_text("smallthinker", 1)
    under = _ops_under(text)
    route = under("moe.route")
    assert any(op.endswith("moe.route/div") for op in route)   # w / sum w
    assert any(op.endswith("moe.route/top_k") for op in route)
    assert any(op.endswith("moe.route/dot_general") for op in route)
    assert any("moe.experts/jit(relu)/max" in op for op in under("moe."))
    assert not any("silu" in op or "logistic" in op for op in under("moe."))
    # rotate-half: the two halves joined again, in `attn.project`
    assert any(op.endswith("attn.project/concatenate")
               for op in under("attn.project"))
    windowed, attended = under("attn.window"), under("attn.attend")
    assert windowed and windowed < attended
    assert all("attn.attend/attn.window" in op for op in windowed)
    # a stack that rotates no kind has no rotation under `attn.project`:
    # not in what is lowered, so in nothing compiled from it
    none = family.lowered_step(dataclasses.replace(
        SCOPED["smallthinker"], unrotated=("full", "window"))).as_text(
            debug_info=True)
    assert "attn.project/concatenate" not in none
    assert "attn.project/concatenate" in text
    assert "attn.project/concatenate" in family.lowered_step(
        SCOPED["smallthinker"]).as_text(debug_info=True)


def test_the_vocabulary_is_what_the_source_enters():
    """`STEP_SCOPES` is every scope `models/transformer.py` and its layer
    parts (`models/mixers.py`, `models/ffns.py`) enter outside the mixers'
    own (`moe.shared`, `mla.*`, `gdn.*`, `kda.*`, `ssm.*`, `gmu.*`,
    `shortconv.*`; a Mamba-2 layer's `ssd.*` are listed in it), no more and no less; and a part enters its scopes whatever the stack: the rows of
    `MIXERS` and `FFNS` know of no pattern."""
    parts = inspect.getsource(mixers) + inspect.getsource(ffns)
    entered = set(re.findall(r'named_scope[(,]\s*"([^"]+)"',
                             inspect.getsource(tfm) + parts))
    own = {s for s in entered
           if s.startswith(("moe.", "mla.", "gdn.", "kda.", "ssm.", "gmu.",
                            "shortconv."))}
    assert entered - own == set(tfm.STEP_SCOPES)
    assert len(set(tfm.STEP_SCOPES)) == len(tfm.STEP_SCOPES)
    assert "layer_pattern" not in parts + inspect.getsource(tfm._layer)
    assert own >= {"mla.project", "gdn.scan", "kda.scan", "ssm.scan",
                   "gmu.gate", "moe.shared", "shortconv.mix"}
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
