"""What the tests of the model families share (`tests/test_olmo_hybrid.py`
and its like, `tests/test_step_scopes.py`, `tests/test_lowered_steps.py`):
a mesh of CPU devices, `tfm.init` as one compiled program, and the jitted
programs of `models/transformer.py` built ONCE for a (configuration, mesh).

A `jax.jit(tfm.build_...(cfg, mesh))` written inside a test is a new function
each time it runs: JAX traces it and XLA compiles it again, the interpreted
Pallas kernels unrolled in it, at 20-70 s a program. The builders here are
memoised on their arguments (`TransformerConfig` is a frozen dataclass), so
however many tests of a module ask for a program, the module compiles it
once. The memo is a process's own: tests that share a program stand in one
file (`--dist loadfile` gives a file to one worker). A test that swaps a
part of the model out (`monkeypatch`) asks for `builder.__wrapped__`, which
builds anew and leaves nothing behind.

No configuration lives here: each family's file keeps its `CFG`, its data
shapes and its tolerances."""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax

from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel import MeshSpec, build_mesh


def mesh_of(**sizes):
    spec = MeshSpec(**sizes)
    return build_mesh(spec, jax.devices()[:spec.total])


def init(cfg, key=0):
    """`tfm.init`'s tree as the benchmark makes it: one compiled program,
    without x64 (eagerly it is a dispatch and a small compile a leaf)."""
    with jax.enable_x64(False):
        return jax.jit(lambda k: tfm.init(k, cfg))(jax.random.PRNGKey(key))


def shapes(cfg):
    """`tfm.init`'s tree as shapes and dtypes, nothing drawn."""
    return jax.eval_shape(lambda k: tfm.init(k, cfg), jax.random.PRNGKey(0))


@functools.lru_cache(maxsize=None)
def forward(cfg, **sizes):
    return jax.jit(tfm.build_forward(cfg, mesh_of(**sizes)))


@functools.lru_cache(maxsize=None)
def loss_and_grads(cfg, metrics=False, **sizes):
    return jax.jit(tfm.build_loss_and_grads(cfg, mesh_of(**sizes),
                                            metrics=metrics))


@functools.lru_cache(maxsize=None)
def train_step(cfg, opt, metrics=False, **sizes):
    """(An optax optimizer is a tuple of functions: the memo knows it by
    identity, so a module that wants one program holds one optimizer.)"""
    return tfm.build_train_step(cfg, mesh_of(**sizes), opt, metrics=metrics)


@contextlib.contextmanager
def counted_builds():
    """`tfm.build_loss_and_grads` wrapped for the block, with the memo of
    `loss_and_grads` emptied before and after it (nothing of the wrapper
    stays): (built, traced), each the (configuration, `dp`) of a program in
    the order it was built or traced."""
    built, traced = [], []
    build = tfm.build_loss_and_grads

    def counting(cfg, mesh, **options):
        key = (cfg, mesh.shape["dp"])
        built.append(key)
        program = build(cfg, mesh, **options)

        def traced_once(*args):
            traced.append(key)
            return program(*args)
        return traced_once

    tfm.build_loss_and_grads = counting
    loss_and_grads.cache_clear()
    try:
        yield built, traced
    finally:
        tfm.build_loss_and_grads = build
        loss_and_grads.cache_clear()


def data(vocab, batch, seq):
    """A batch of tokens and its next-token targets."""
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                vocab, jnp.int32)
    return tokens, jnp.roll(tokens, -1, axis=1)


def assert_specs_cover(cfg, params):
    """`param_specs` and `grad_reduce_axes` have `params`' tree."""
    structure = jax.tree_util.tree_structure(params)
    assert jax.tree_util.tree_structure(tfm.param_specs(cfg)) == structure
    assert jax.tree_util.tree_structure(jax.tree_util.tree_map(
        lambda x: 0, tfm.grad_reduce_axes(cfg),
        is_leaf=lambda x: isinstance(x, tuple))) == structure


@functools.lru_cache(maxsize=None)
def lowered_step(cfg, dp=1):
    """The train step of `cfg` under AdamW lowered for `dp` CPU devices, two
    sequences of 32 tokens a rank, as the benchmark runs it (no x64):
    `.as_text()` is what `tests/test_lowered_steps.py` holds,
    `.compile().as_text()` what the scopes are read from."""
    opt = optax.adamw(1e-3)
    with jax.enable_x64(False):
        params = shapes(cfg)
        state = jax.eval_shape(opt.init, params)
        tokens = jax.ShapeDtypeStruct((2 * dp, 32), jnp.int32)
        return tfm.build_train_step(cfg, mesh_of(dp=dp), opt).lower(
            params, state, tokens, tokens)


def leaves(tree):
    """{a leaf's path as `keystr` writes it: the leaf}."""
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def leaf_names(cfg):
    """The paths of `tfm.init`'s tree, sorted, as `models/leaves.py`
    declares them for `cfg`: nothing made and nothing traced (a module asks
    when it is collected, in every worker)."""
    return sorted(leaves(tfm.param_specs(cfg)))


def assert_trees_close(got, want, rtol, atol=0.0, scaled=0.0):
    """Every leaf of `got` against its leaf of `want`, the failure naming
    the leaf; `scaled` adds that share of a leaf's largest wanted entry to
    `atol`."""
    got, want = leaves(got), leaves(want)
    assert list(got) == list(want)
    for name, leaf in got.items():
        size = float(np.max(np.abs(np.asarray(want[name])))) if scaled else 0
        np.testing.assert_allclose(leaf, want[name], rtol=rtol,
                                   atol=atol + scaled * size, err_msg=name)
