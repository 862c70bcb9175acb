"""The LFM2 block of `models/transformer.py` (a pattern of gated
short-convolution layers and one grouped-query attention layer whose queries
and keys are normed per head and rotated; a leading dense layer that is the
pattern's first layer; sigmoid-scored experts with a selection bias, a top-k
renormalised over its sum + 1e-6, of which a share is held, no shared
expert; a tied head) against the plain reference
`benchmark/reference/lfm2_moe.py`, at a small size in float32: the family's
statement for `tests/family_cases.py` (`FAMILY`), each mixer alone, and of
the shared cases the logits (`attn` "local"), every planted fault and an
8-bit float refused by the family's limits. The loss and every leaf's
gradient (`attn` "flash" under remat "dots", as the cell runs it), the stack
behind the dense layer, the eight shares of an expert layer, `dp` = 2, the
refusals and the family's counts at the published widths:
`tests/test_lfm2_moe_stack.py`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family as programs
from benchmark.families import lfm2_moe as family
from benchmark.reference import lfm2_moe as reference
from family_cases import (  # noqa: F401  (the fixtures, the shared tests)
    Family, lively, logits, params, pytest_generate_tests, sound, stated,
    their_logits, test_an_unknown_fault_is_refused,
    test_logits_equal_the_references,
    test_the_familys_comparison_reads_zero_for_the_reference,
    test_the_limits_refuse_a_planted_fault,
    test_the_limits_refuse_an_8_bit_float)
from horovod_tpu.models import mixers, transformer as tfm

PATTERN = ("shortconv", "full", "shortconv", "shortconv")
KINDS = PATTERN + PATTERN[:1]    # five layers: the least the cell's rule leaves
TOP_K, FIRST = 4, 4
# 4 query | 2 key heads of 16; 16 experts, 4 a token, experts 4-7 held; the
# first layer's dense MLP 184 wide where an expert is 24 (11,776 : 1,536)
CFG = tfm.TransformerConfig(
    vocab=96, d_model=64, n_heads=4, n_kv_heads=2, d_ff=24, n_layers=5,
    max_seq=64, num_experts=16, experts_per_token=TOP_K, experts_held=4,
    first_expert=FIRST, first_k_dense=1, d_ff_dense=184, norm_topk=True,
    norm_topk_eps=reference.RENORM_EPS, router_scoring="sigmoid",
    router_bias=True, norm="rmsnorm", rms_norm_eps=reference.RMS_EPS,
    positions="rope", rope_theta=reference.ROPE_THETA, qk_norm="head",
    layer_pattern=PATTERN, mlp="swiglu", tied_head=True, shortconv_taps=3,
    attn="local", dtype=jnp.float32)
#: the cell's algorithm and remat: the loss and the gradients go through it
#: (and without remat `dp` = 2); the logits through `CFG`'s own
TIMED = dataclasses.replace(CFG, attn="flash", remat=True,
                            remat_policy="dots")
SEQ = 32

#: `init`'s tree with the norms' scales and the per-head scales of q and k
#: moved off one, the selection bias off zero, and router scores away from
#: one half, mixers that weigh against the residual stream (a tied embedding
#: is drawn small)
_lively = lively({"wo": 3.0, "we2": 3.0, "router": 4.0, "sc_w_out": 3.0,
                  "w2": 2.0, "embed": 20.0}, shifted=("router_bias",))
#: what `validate_cfg_for_mesh` refuses: (mesh, changed fields, its words)
REFUSED = (
    ({"sp": 2}, {"attn": "ring", "n_kv_heads": 0},
     "short-convolution layers require sp=1"),
    ({"tp": 2}, {"n_kv_heads": 0}, "short-convolution layers require tp=1"),
    ({"pp": 2}, {"microbatches": 2, "n_kv_heads": 0}, "require pp=1"),
    ({"tp": 2}, {"layer_pattern": (), "first_k_dense": 0, "n_kv_heads": 0},
     "qk_norm='head' requires tp=1"),
    ({"ep": 2}, {}, "ep > 1 with experts_held < num_experts"),
    ({}, {"shortconv_taps": 0}, "'shortconv' layers need shortconv_taps"),
    ({}, {"qk_norm": "heads"}, "qk_norm='heads'"),
    ({}, {"first_k_dense": 2}, "pattern's first layers"),
)
#: (among the leaves: the taps', whose gradients come through the shifted
#: sums, the per-head scales', summed over the heads, the tied table's, which
#: the lookup and the head both reach, and the selection bias, which takes
#: none on either side: it chooses and never weighs)
FAMILY = Family(
    cfg=CFG, timed=TIMED, family=family, reference=reference,
    weights=(KINDS,), args=(KINDS, TOP_K, FIRST), data=(2, SEQ),
    refused=REFUSED, lively=_lively, attns=("local",),
    logits_tol=(5e-3, 5e-3), sound_below=2e-4, leaf_atol=3e-4,
    no_gradient=("router_bias",), two_ranks_loss=1e-5,
    two_ranks={"rtol": 2e-3, "atol": 1e-8, "scaled": 2e-4})


# ------------------------------------------------------------------ the tree

def test_the_tree_has_each_kinds_leaves_and_no_others(params):
    assert sorted(params) == ["dense_layers", "embed", "layers", "lnf_scale"]
    first, second = params["layers"]          # a segment each
    assert sorted(first) == ["full", "shortconv"]
    assert sorted(second) == ["shortconv"]
    ffn = {"ln1_scale", "ln2_scale", "router", "router_bias", "we1", "we2",
           "we_gate"}
    conv = {"sc_w_in", "sc_conv", "sc_w_out"}
    assert set(first["shortconv"]) == set(second["shortconv"]) == ffn | conv
    assert set(first["full"]) == ffn | {"wq", "wk", "wv", "wo", "q_scale",
                                        "k_scale"}
    # the dense layer is a short-convolution layer with a dense MLP
    assert set(params["dense_layers"]) == conv | {
        "ln1_scale", "ln2_scale", "w1", "w2", "w_gate"}
    assert params["dense_layers"]["w1"].shape == (1, 64, 184)
    # stacked over (periods, the kind's layers in a period), a segment each
    assert first["shortconv"]["sc_w_in"].shape == (1, 2, 64, 192)
    assert first["shortconv"]["sc_conv"].shape == (1, 2, 64, 3)
    assert second["shortconv"]["sc_w_out"].shape == (1, 1, 64, 64)
    assert first["full"]["wq"].shape == (1, 1, 64, 4, 16)
    assert first["full"]["wk"].shape == (1, 1, 64, 2, 16)
    # one scale of a head's width for all the heads
    assert first["full"]["q_scale"].shape == (1, 1, 16)
    assert first["full"]["k_scale"].shape == (1, 1, 16)
    assert first["full"]["router"].shape == (1, 1, 64, 16)   # whole
    assert first["full"]["router_bias"].shape == (1, 1, 16)
    assert first["full"]["we_gate"].shape == (1, 1, 4, 64, 24)   # four held
    programs.assert_specs_cover(CFG, params)
    # the whole-vector form keeps a scale a head and its (heads, width)
    whole = programs.shapes(dataclasses.replace(CFG, qk_norm=True,
                                                n_kv_heads=0))
    assert whole["layers"][0]["full"]["q_scale"].shape == (1, 1, 4, 16)


# ---------------------------------------------- the program and the reference

@pytest.mark.parametrize("kind, at", [("shortconv", 2), ("full", 1)])
def test_a_mixer_alone_equals_the_references(params, kind, at):
    """`MIXERS[kind]` on a normed state against the reference's mixer on the
    same leaves."""
    cfg = tfm._kind_cfg(CFG, kind)
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 64), jnp.float32)
    lp = {k: v[0, 0] for k, v in params["layers"][0][kind].items()}
    w = family.reference_weights(params, KINDS)["layers"][at]
    angles = mixers.rope_angles(jnp.arange(12), 16, reference.ROPE_THETA)
    with jax.enable_x64(False), jax.default_matmul_precision("highest"):
        got, handed = mixers.MIXERS[cfg.attention].apply(u, lp, cfg, angles,
                                                         {}, at)
        want = (reference.short_conv if kind == "shortconv"
                else reference.attention)(u, w)
    assert handed is None
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)


def _config():
    return {
        "vocab_size": 96, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "intermediate_size": 184,
        "moe_intermediate_size": 24, "n_layer": 5, "num_hidden_layers": 40,
        "max_position_embeddings": 64, "num_experts": 4,
        "num_experts_per_tok": 4, "num_dense_layers": 2, "conv_L_cache": 3,
        "conv_bias": False, "norm_eps": 1e-5, "norm_topk_prob": True,
        "use_expert_bias": True, "routed_scaling_factor": 1,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "layer_types": ["conv", "conv", "full_attention", "conv"] * 10,
        "published": {"num_experts": 16},
        "deployment": {"expert_rank": 1, "first_layer": 1},
        "program": {"dtype": "float32", "attn": "local", "remat": False,
                    "remat_policy": "dots", "load_balance_coef": 0.0,
                    "router_z_coef": 0.0, "held_capacity": 2.0}}


def test_check_logits_knows_the_configuration_by_its_shapes(params, logits):
    """What `check_logits` cannot read off an array it takes from the
    configuration `transformer_config` was asked about."""
    config = _config()
    cfg = family.transformer_config(config)
    assert cfg == CFG
    assert family.kinds(config) == KINDS
    assert family.pattern(config) == PATTERN
    assert family.first_expert(config) == FIRST
    assert family.dense_layers(config) == 1
    with jax.enable_x64(False):
        found = family.check_logits(params, FAMILY.batch[0], logits)
    assert found["ok"], found
    assert "rows of the 4 held experts" in found["detail"]
    for changed in ({"conv_bias": True}, {"norm_topk_prob": False},
                    {"use_expert_bias": False}, {"num_dense_layers": 1}):
        with pytest.raises(ValueError, match="no equations for"):
            family.transformer_config(dict(config, **changed))
    with pytest.raises(ValueError, match="differs from the constants"):
        family.transformer_config(dict(config, routed_scaling_factor=2.0))
