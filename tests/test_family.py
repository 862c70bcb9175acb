"""The memo of `tests/family.py`, which the family files' seconds rest on:
equal configurations share one jitted program, traced once; configurations
that differ in one field get one each."""

import dataclasses

import jax
import jax.numpy as jnp

import family
from horovod_tpu.models import transformer as tfm

CFG = tfm.TransformerConfig(vocab=32, d_model=16, n_heads=2, d_ff=32,
                            n_layers=1, max_seq=8, attn="local",
                            dtype=jnp.float32)


def test_equal_configurations_share_a_program_traced_once():
    with family.counted_builds() as (built, traced):
        equal = dataclasses.replace(CFG)
        assert equal == CFG and equal is not CFG
        one, two = family.loss_and_grads(CFG), family.loss_and_grads(equal)
        assert one is two and built == [(CFG, 1)]
        params = family.init(CFG)
        tokens = jnp.zeros((2, 8), jnp.int32)
        first, _ = one(params, tokens, tokens)
        again, _ = two(params, tokens, tokens)
        assert traced == [(CFG, 1)] and float(first) == float(again)
        # one field apart, on the same mesh: a program of its own
        other = dataclasses.replace(CFG, d_ff=48)
        assert family.loss_and_grads(other) is not one
        assert built == [(CFG, 1), (other, 1)]
        # and another mesh of the same configuration is another program
        assert family.loss_and_grads(CFG, dp=2) is not one
        assert built == [(CFG, 1), (other, 1), (CFG, 2)]
        # past the memo, for a test that swaps a part of the model out
        assert family.loss_and_grads.__wrapped__(CFG) is not one
        assert family.loss_and_grads(CFG) is one


def test_init_is_the_eager_tree_as_one_program():
    """(To an ulp: the compiled program fuses a draw with its scale.)"""
    with jax.enable_x64(False):      # as the benchmark draws them
        eager = tfm.init(jax.random.PRNGKey(0), CFG)
    family.assert_trees_close(family.init(CFG), eager, rtol=1e-6)
    assert family.leaf_names(CFG) == sorted(family.leaves(eager))
