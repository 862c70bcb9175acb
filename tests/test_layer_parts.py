"""The parameter tree of the nine tiny family configurations of
`tests/test_lowered_steps.py`: `init` draws, leaf by leaf, the numbers the
commit before the layer parts (PR 44's, bf13d0e) drew
(`tests/fixtures/init_digests.json`, written there by `write_fixture()`; the
Granite family's entry on PR 46's own tree, the Kimi Linear family's on
PR 50's, the LFM2 family's on PR 54's, `write_fixture(only_new=True)`);
and what the parts of `models/mixers.py` and `models/ffns.py` declare is one
tree, each leaf of a layer declared by one part."""

import hashlib
import json
import os

import jax
import numpy as np
import pytest

import family
from horovod_tpu.models import transformer as tfm
from step_cases import CONFIGS

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "init_digests.json")
#: (the digests are of bits drawn by programs compiled with them)
pytestmark = pytest.mark.usefixtures("xla_optimizations")


def digests(name: str) -> dict:
    """{path of a leaf: shape, dtype and a digest of its bytes}."""
    params = family.init(CONFIGS[name])   # as the benchmark runs
    return {jax.tree_util.keystr(path): "%s %s %s" % (
        "x".join(map(str, leaf.shape)), leaf.dtype, hashlib.sha256(
            np.asarray(leaf).tobytes()).hexdigest()[:16])
        for path, leaf in jax.tree_util.tree_leaves_with_path(params)}


def write_fixture(only_new: bool = False) -> None:
    """Takes the fixture anew; with `only_new`, only the families it
    lacks."""
    kept = {}
    if only_new:
        with open(FIXTURE) as f:
            kept = json.load(f)
    kept.update({name: digests(name) for name in CONFIGS
                 if name not in kept})
    with open(FIXTURE, "w") as f:
        json.dump(kept, f, indent=0, sort_keys=True)


@pytest.fixture(scope="module")
def parents():
    with open(FIXTURE) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_init_draws_what_the_parent_drew(parents, name):
    got, want = digests(name), parents[name]
    assert sorted(got) == sorted(want)
    assert {k: v for k, v in got.items() if v != want[k]} == {}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_the_three_maps_are_one_tree_of_once_declared_leaves(name):
    cfg = CONFIGS[name]
    params = family.shapes(cfg)
    specs, axes = tfm.param_specs(cfg), tfm.grad_reduce_axes(cfg)
    shape = jax.tree_util.tree_structure(params)
    assert jax.tree_util.tree_structure(specs) == shape
    assert jax.tree_util.tree_structure(
        axes, is_leaf=lambda x: isinstance(x, tuple)) == shape
    # a spec shards dimensions the leaf has, and no axis twice
    for leaf, spec in zip(jax.tree_util.tree_leaves(params),
                          jax.tree_util.tree_leaves(specs)):
        named = [a for a in spec if a is not None]
        assert len(spec) <= leaf.ndim and len(set(named)) == len(named)
    # the layers of each kind and stack: no part declares another's leaf
    for layer_cfg in tfm._layer_cfgs(cfg):
        parts = tfm._layer_parts(layer_cfg)
        names = [n for part in parts for n in part]
        assert len(set(names)) == len(names), sorted(names)
