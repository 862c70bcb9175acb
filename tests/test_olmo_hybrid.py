"""The Olmo-Hybrid pattern of `models/transformer.py` (three gated-delta-rule
linear-attention layers to one full-attention layer, post-sub-layer norms,
no rotary embedding) against the plain reference
`benchmark/reference/olmo_hybrid.py`, at a small size in float32: the family's
statement for `tests/family_cases.py` (`FAMILY`) and the shared cases (logits;
loss and every leaf's gradient of one rank as the cell runs it, under remat
"dots"; `dp` = 2 without remat against it; what `validate_cfg_for_mesh`
refuses; the train step: `tests/test_hybrid_steps.py`); and THE witness of the remat policies
against each other, for every family: the gradients without remat, under
"dots" and under "full" against the reference's (the policy is
`jax.checkpoint`'s around the layer scan in `models/transformer.py`, no
family's own code; this family's delta rule is a Pallas kernel with a
`custom_vjp`, so the witness holds a kernel under both). (The chunked rule
alone: `tests/test_gated_delta.py`; the convolution:
`tests/test_causal_conv.py`.) Every program is `tests/family.py`'s, built
once for the module."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family as programs
from benchmark.families import olmo_hybrid as family
from benchmark.reference import olmo_hybrid as reference
from family_cases import (  # noqa: F401  (the fixtures, the shared tests)
    Family, logits, ours, params, pytest_generate_tests, stated,
    their_logits, theirs,
    test_dp_2_without_remat_equals_one_rank_under_remat,
    test_every_leafs_gradient_equals_the_references,
    test_logits_equal_the_references, test_loss_equals_the_references,
    test_validate_accepts_the_model_where_it_runs,
    test_validate_refuses_by_name)
from horovod_tpu.models import transformer as tfm
from horovod_tpu.models.mixers import MIXERS

PATTERN = ("linear", "linear", "linear", "full")
CFG = tfm.TransformerConfig(
    vocab=96, d_model=48, n_heads=3, d_ff=80, n_layers=8, max_seq=64,
    norm="rmsnorm", rms_norm_eps=1e-6, positions="none", qk_norm=True,
    mlp="swiglu", post_norm=True, layer_pattern=PATTERN, gdn_heads=3,
    gdn_key_dim=8, gdn_value_dim=16, gdn_conv=4, gdn_neg_eigval=True,
    attn="flash", dtype=jnp.float32)


def _moved(params):
    """Norm scales off their initial ones, so that a misplaced one shows."""
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 64))

    def moved(path, x):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return x + 0.3 * jax.random.normal(next(keys), x.shape, x.dtype)
        return x
    return jax.tree_util.tree_map_with_path(moved, params)


#: what `validate_cfg_for_mesh` refuses: (mesh, changed fields, its words)
REFUSED = (
    ({"sp": 2}, {}, "linear-attention layers require sp=1"),
    ({"tp": 3}, {}, "linear-attention layers require tp=1"),
    ({"pp": 2}, {"microbatches": 2}, "a layer pattern requires pp=1"),
    # a pattern's layers may be expert layers since PR 38, and may be
    # windowed: what is still refused is a window of no keys
    ({}, {"layer_pattern": ("linear", "window")},
     "'window' layers need window > 0"),
    ({}, {"n_layers": 6}, "no whole number of periods"),
    ({}, {"layer_pattern": ("linear", "sparse")}, "names the kind 'sparse'"),
    # a kind since PR 36, which a pattern alone cannot hold
    ({}, {"layer_pattern": ("linear", "ssm")}, "need segments"),
    ({"sp": 2}, {"layer_pattern": (), "attention": "gdn"},
     "linear-attention layers require sp=1"),
)
#: the cell's remat policy. (`dp` = 2 reduces the new leaves inside the
#: backward loop like any layer's; in float32 the rule's running sums of the
#: log decay leave ~1e-4 of a leaf's largest entry between two batch shapes,
#: which the Kimi Linear and LFM2 families' tolerance admits.)
FAMILY = Family(
    cfg=CFG, family=family, reference=reference,
    timed=dataclasses.replace(CFG, remat=True, remat_policy="dots"),
    weights=(PATTERN,), args=(), data=(4, 40), refused=REFUSED,
    lively=_moved, accepted=(({}, {"dp": 2, "ep": 2}),), attns=("flash",),
    logits_tol=(1e-3, 1e-3), leaf_atol=2e-3, two_ranks_loss=1e-5,
    two_ranks={"rtol": 2e-3, "atol": 1e-8, "scaled": 2e-4})


# ----------------------------------------------------------- the mixer

def test_a_later_token_moves_no_earlier_logit(params, logits):
    tokens, _ = FAMILY.batch
    with jax.enable_x64(False):
        moved = programs.forward(CFG)(
            params, tokens.at[0, 25].set((tokens[0, 25] + 1) % 96))
    np.testing.assert_array_equal(logits[:, :25], moved[:, :25])
    np.testing.assert_array_equal(logits[1:], moved[1:])
    assert float(jnp.max(jnp.abs(logits[:, 25:] - moved[:, 25:]))) > 1e-3


def test_the_pattern_has_the_leaves_each_kind_has(params):
    layers = params["layers"]
    assert sorted(layers) == ["full", "linear"]
    assert "pos" not in params and "lnf_bias" not in params
    shared = {"ln1_scale", "ln2_scale", "w1", "w2", "w_gate", "wo"}
    assert set(layers["full"]) == shared | {"wq", "wk", "wv", "q_scale",
                                            "k_scale"}
    gdn = set(MIXERS["gdn"].leaves(CFG))
    assert len(gdn) == 13 and {n for n in gdn if n[:4] != "gdn_"} == {"wo"}
    assert set(layers["linear"]) == shared | gdn
    # (periods, layers of the kind in a period, ...)
    assert layers["full"]["wq"].shape == (2, 1, 48, 3, 16)
    assert layers["linear"]["gdn_wq"].shape == (2, 3, 48, 3, 8)
    assert layers["linear"]["gdn_wv"].shape == (2, 3, 48, 3, 16)
    assert layers["linear"]["gdn_conv_v"].shape == (2, 3, 3, 16, 4)
    assert layers["linear"]["gdn_o_scale"].shape == (2, 3, 16)
    assert layers["linear"]["wo"].shape == (2, 3, 3, 16, 48)
    specs, axes = tfm.param_specs(CFG), tfm.grad_reduce_axes(CFG)
    same = jax.tree_util.tree_structure(params)
    assert jax.tree_util.tree_structure(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)) \
        == same
    assert jax.tree_util.tree_structure(
        axes, is_leaf=lambda x: isinstance(x, tuple)) == same
    assert len(specs["layers"]["linear"]["gdn_wq"]) == 5
    # decays as the public implementation draws them: A in (0, 16), the
    # step in (0.001, 0.1)
    rate = jnp.exp(layers["linear"]["gdn_a_log"])
    step = jax.nn.softplus(layers["linear"]["gdn_dt_bias"])
    assert 0 < float(rate.min()) and float(rate.max()) <= 16
    assert 1e-3 <= float(step.min()) and float(step.max()) <= 0.1 + 1e-6
    # and the reference's weights are a layer's each, in the pattern's order
    weights = family.reference_weights(params, PATTERN)
    assert len(weights["layers"]) == 8
    assert ["a_log" in w for w in weights["layers"]] == \
        [True, True, True, False] * 2


def test_a_stack_of_one_kind_keeps_its_leaves():
    plain = tfm.TransformerConfig(vocab=32, d_model=16, n_heads=2, d_ff=32,
                                  n_layers=2)
    p = programs.init(plain)
    assert not set(p["layers"]) & set(MIXERS["gdn"].leaves(CFG)) - {"wo"}
    assert p["layers"]["wq"].shape == (2, 16, 2, 8)
    # a whole stack of linear layers needs no pattern
    linear = dataclasses.replace(CFG, layer_pattern=(), attention="gdn",
                                 n_layers=2)
    p = programs.init(linear)
    assert p["layers"]["gdn_wq"].shape == (2, 48, 3, 8)
    assert "wq" not in p["layers"]


# ------------------------------------------- the model and the reference

@pytest.mark.parametrize("remat_policy", [None, "dots", "full"])
def test_every_gradient_leaf_matches_the_reference(params, theirs,
                                                   remat_policy):
    """The witness of the policies, with no program of its own but "full":
    "dots" is `ours`, and without remat the model runs on two ranks (the
    shared `dp` = 2 case's program), held to the reference here."""
    cfg = dataclasses.replace(FAMILY.timed, remat=remat_policy is not None,
                              remat_policy=remat_policy or "dots")
    with jax.enable_x64(False):
        if cfg.remat:      # (with the counts, as `ours` is built)
            grads = programs.loss_and_grads(cfg, metrics=True)(
                params, *FAMILY.batch)[1]
        else:
            grads = programs.loss_and_grads(cfg, dp=2)(tfm.shard_params(
                params, cfg, programs.mesh_of(dp=2)), *FAMILY.batch)[1]
    assert len(programs.leaves(grads)) == 3 + 11 + 18
    assert all(float(jnp.max(jnp.abs(w))) > 0
               for w in jax.tree_util.tree_leaves(theirs[1]))
    programs.assert_trees_close(grads, theirs[1], rtol=0, scaled=2e-3)


def test_the_limits_refuse_lower_precisions():
    """At a toy width, in float32 on the CPU: the reference with 8-bit
    operands is far outside the logits' limit; its state carried in bf16 is
    a real difference (the chip's readings are in the family's file)."""
    cfg = dataclasses.replace(CFG, d_model=96, n_heads=3, d_ff=160,
                              n_layers=4)
    p = programs.init(cfg, 4)
    tokens, _ = programs.data(CFG.vocab, 1, 64)
    weights = family.reference_weights(p, PATTERN)
    want = reference.forward(weights, tokens)

    def rms(got):
        return float(jnp.sqrt(jnp.mean(jnp.square(got - want))
                              / jnp.mean(jnp.square(want))))

    assert rms(reference.forward(weights, tokens,
                                 operands=jnp.float8_e4m3fn)) \
        > 3 * family.LOGITS_RMS_TOL
    assert rms(reference.forward(weights, tokens, state=jnp.bfloat16)) > 1e-4
    assert family.within(0.02, 9.9, 9.9) == (True, True)
    assert family.within(0.03, 9.9, 9.9006) == (False, False)


# ------------------------------------------------------------ the meshes

def test_two_ranks_scatter_the_new_leaves_in_the_backward_loop(params):
    """(The shared `dp` = 2 case's program and arguments, lowered: remat or
    none, the exchanges are the same.)"""
    cfg = dataclasses.replace(FAMILY.timed, remat=False)
    with jax.enable_x64(False):
        text = programs.loss_and_grads(cfg, dp=2).lower(tfm.shard_params(
            params, cfg, programs.mesh_of(dp=2)), *FAMILY.batch).as_text()
    # one exchange inside the loop for each leaf that is no vector: 13 of a
    # linear layer's 18 leaves (all but the two norms' scales, the gated
    # norm's, A_log and dt_bias) and 9 of a full layer's 11; the vectors
    # are psum'd after the loop
    assert text.count("collective_permute") == 13 + 9
