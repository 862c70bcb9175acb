"""The Granite 4.0-H block's loss and gradients, beside
`tests/test_granite_hybrid.py` (whose tiny `CFG` and parameters this file
shares, and which holds the logits and the limits): loss and every leaf's
gradient against the reference's, `attn` "local" and "flash"; `dp` = 2
against one rank; a train step; remat."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import family as programs
from benchmark.families import granite_hybrid as family
from benchmark.reference import granite_hybrid as reference
from family import mesh_of
from horovod_tpu.models import transformer as tfm
from test_granite_hybrid import (ATTNS, CFG, FIRST, KINDS, TOP_K, _data,
                                 params)  # noqa: F401


def _one_rank(params, cfg=CFG):
    """(loss, gradients) of the program on one rank."""
    with jax.enable_x64(False):
        return programs.loss_and_grads(cfg)(params, *_data())


@pytest.fixture(scope="module", params=ATTNS)
def ours(request, params):
    """`_one_rank` by each algorithm."""
    return _one_rank(params, dataclasses.replace(CFG, attn=request.param))


@pytest.fixture(scope="module")
def theirs(params):
    """(loss, gradients) of the reference, in the program's tree."""
    tokens, targets = _data()
    with jax.enable_x64(False):
        return jax.value_and_grad(lambda p: reference.loss(
            family.reference_weights(p, KINDS), tokens, targets, KINDS,
            TOP_K, FIRST))(params)


def test_loss_equals_the_references(ours, theirs):
    np.testing.assert_allclose(ours[0], theirs[0], rtol=1e-5)


@pytest.mark.parametrize("leaf", programs.leaf_names(CFG))
def test_every_leafs_gradient_equals_the_references(ours, theirs, leaf):
    """Among them `ssd_a_log` and `ssd_dt_bias`, whose gradients come
    through the running sums of the chunked form, the two leaves of the
    input projection, and the tied embedding's, read twice."""
    got, want = (programs.leaves(x[1])[leaf] for x in (ours, theirs))
    size = float(jnp.max(jnp.abs(want)))
    assert size > 1e-7, "nothing to compare"
    np.testing.assert_allclose(got, want, rtol=2e-3,
                               atol=2e-4 * size + 1e-8)


def test_dp2_equals_one_rank(params):
    want_loss, want = _one_rank(params)
    with jax.enable_x64(False):
        mesh = mesh_of(dp=2)
        tfm.validate_cfg_for_mesh(CFG, mesh)
        loss, grads = programs.loss_and_grads(CFG, dp=2)(
            tfm.shard_params(params, CFG, mesh), *_data())
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    programs.assert_trees_close(grads, want, rtol=1e-4, atol=1e-6)


def test_a_train_step_lowers_the_loss_and_counts_what_it_drops(params):
    opt = optax.adamw(1e-2)
    cfg = dataclasses.replace(CFG, remat=True, attn="flash")
    with jax.enable_x64(False):
        results = programs.train(cfg, opt, params, _data(), 3, metrics=True)
    assert all(int(counts["experts_dropped"]) == 0 for _, counts in results)
    assert float(results[2][0]) < float(results[0][0]), results


def test_remat_changes_no_result(params):
    want_loss, want = _one_rank(params)
    for policy in ("dots", "full"):
        loss, grads = _one_rank(params, dataclasses.replace(
            CFG, remat=True, remat_policy=policy))
        np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
        programs.assert_trees_close(grads, want, rtol=1e-4, atol=1e-7)
