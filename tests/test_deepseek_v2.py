"""The DeepSeek-V2 block of `models/transformer.py` (latent attention with
keys wider than values, YaRN, a leading dense gated MLP, shared experts and a
share of the routed experts with a per-sequence balance loss) against the
plain reference `benchmark/reference/deepseek_v2.py`, at a small size in
float32: the family's statement for `tests/family_cases.py` (`FAMILY`) and of
the shared cases the loss and every leaf's gradient of one rank as the cell
runs it (under remat) and `dp` = 2 without remat against it; `dp` = 2 x `tp`
= 2; the family's limits; and what is refused. (The share of
`parallel/moe.py` and its row buffer: `tests/test_deepseek_v2_share.py`; the
flash kernels at unequal widths: `tests/test_flash_attention.py`.) Every
program of the model is `tests/family.py`'s, built once for the module."""

import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import family as programs
from benchmark.families import deepseek_v2 as family
from benchmark.reference import deepseek_v2 as reference
from family_cases import (  # noqa: F401  (the fixtures, the shared tests)
    Family, ours, params, pytest_generate_tests, stated, theirs,
    test_dp_2_without_remat_equals_one_rank_under_remat,
    test_every_leafs_gradient_equals_the_references,
    test_loss_equals_the_references)
from horovod_tpu.common.exceptions import HorovodTpuError
from horovod_tpu.models import mixers, transformer as tfm
from family import mesh_of

TOP_K = 2
FIRST = 2       # the share held: experts 2 and 3 of 8
YARN = tfm.Yarn(factor=40, original_max=4096, beta_fast=32, beta_slow=1,
                mscale=0.707, mscale_all_dim=0.707)
CFG = tfm.TransformerConfig(
    vocab=96, d_model=64, n_heads=4, d_ff=32, n_layers=3, max_seq=64,
    num_experts=8, experts_per_token=TOP_K, experts_held=2,
    first_expert=FIRST, shared_experts=2, first_k_dense=1, d_ff_dense=96,
    # alpha for each of the two expert layers' terms; the program takes
    # their mean
    load_balance_coef=2 * 0.001, balance_per_sequence=True, norm="rmsnorm",
    rms_norm_eps=1e-6, positions="rope", yarn=YARN, attention="mla",
    kv_latent=24, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
    mlp="swiglu", attn="flash", dtype=jnp.float32)
WHOLE = dataclasses.replace(CFG, experts_held=0, first_expert=0)


#: the cell's remat (the program's default policy); the gradients to a
#: leaf's largest entry, as they were held before the shared cases. (The
#: older refusals of this file stand below, a test each.)
FAMILY = Family(
    cfg=CFG, timed=dataclasses.replace(CFG, remat=True), family=family,
    reference=reference, weights=(), args=(TOP_K,),
    kwargs={"first_expert": FIRST}, data=(4, 32), refused=(), leaf_rtol=0,
    leaf_atol=3e-5)


def _data(batch=4, seq=32, vocab=CFG.vocab):
    return programs.data(vocab, batch, seq)


# -------------------------------------------------------------- the block

def test_the_block_has_the_leaves_the_architecture_has(params):
    attention = ["ln1_scale", "ln2_scale", "wq", "wkv_a", "kv_scale",
                 "wkv_b", "wo"]
    assert sorted(params) == ["dense_layers", "embed", "layers", "lnf_scale",
                              "unembed"]
    assert sorted(params["dense_layers"]) == sorted(
        attention + ["w_gate", "w1", "w2"])
    assert sorted(params["layers"]) == sorted(
        attention + ["router", "we_gate", "we1", "we2", "ws_gate", "ws1",
                     "ws2"])
    shapes = {k: v.shape for k, v in params["layers"].items()}
    assert shapes["wq"] == (2, 64, 4, 24) and shapes["wkv_a"] == (2, 64, 32)
    assert shapes["wkv_b"] == (2, 24, 4, 32) and shapes["wo"] == (2, 4, 16, 64)
    assert shapes["router"] == (2, 64, 8)        # the router keeps its width
    assert shapes["we1"] == (2, 2, 64, 32)       # two experts are held
    assert shapes["ws1"] == (2, 64, 64)          # two shared experts, one MLP
    assert params["dense_layers"]["w1"].shape == (1, 64, 96)
    structure = jax.tree_util.tree_structure(params)
    is_leaf = lambda x: isinstance(x, (P, tuple))  # noqa: E731
    specs = tfm.param_specs(CFG)
    assert jax.tree_util.tree_structure(specs, is_leaf=is_leaf) == structure
    assert jax.tree_util.tree_structure(
        tfm.grad_reduce_axes(CFG), is_leaf=is_leaf) == structure
    # heads over tp; the down-projection and the latent's norm belong to no
    # head; the shared experts as a dense MLP; the prefix on no stage
    lp = specs["layers"]
    assert lp["wq"] == lp["wkv_b"] == P("pp", None, "tp", None)
    assert lp["wkv_a"] == lp["router"] and lp["kv_scale"] == P("pp", None)
    assert (lp["ws1"], lp["ws2"]) == (P("pp", None, "tp"), P("pp", "tp", None))
    assert specs["dense_layers"]["w1"] == P(None, None, "tp")


def test_a_configuration_without_the_new_fields_keeps_its_leaves():
    """The GPT-2 and OLMoE trees are what they were."""
    gpt = programs.init(tfm.TransformerConfig(
        vocab=32, d_model=16, n_heads=2, d_ff=32, n_layers=1, max_seq=8))
    assert sorted(gpt["layers"]) == sorted([
        "ln1_scale", "ln1_bias", "wq", "wk", "wv", "wo", "ln2_scale",
        "ln2_bias", "w1", "b1", "w2", "b2"])
    assert "dense_layers" not in gpt


def test_logits_and_loss_match_the_reference(params, ours):
    tokens, targets = _data()
    logits = programs.forward(CFG)(params, tokens)
    weights = family.reference_weights(params)
    want = reference.logits(weights, tokens, TOP_K, first_expert=FIRST)
    assert logits.shape == (4, 32, CFG.vocab)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    want_loss = reference.loss(weights, tokens, targets, TOP_K,
                               first_expert=FIRST)
    np.testing.assert_allclose(float(ours[0]), float(want_loss), rtol=1e-5)
    # the balance term is in it, per sequence
    bare = reference.next_token_loss(want, targets)
    assert float(want_loss) - float(bare) > 1e-4


@pytest.mark.parametrize("sizes", [{"dp": 2, "tp": 2}], ids=["dp2-tp2"])
def test_every_gradient_leaf_matches_the_reference(params, theirs, sizes):
    """`build_loss_and_grads` against `jax.grad` of the reference's loss, the
    balance term included: it is each sequence's own, so a data-parallel
    mesh changes nothing. On a mesh that reduces, both stacks' gradients go
    through `grad_reduce.scattered_in_backward`; here with the heads cut
    over `tp` as well. (One rank and `dp` = 2 alone: the shared cases.)"""
    tokens, targets = _data()
    mesh = mesh_of(**sizes)
    tfm.validate_cfg_for_mesh(CFG, mesh)
    loss, grads = programs.loss_and_grads(CFG, **sizes)(
        tfm.shard_params(params, CFG, mesh), tokens, targets)
    want_loss, want = theirs
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    assert len(programs.leaves(grads)) == 10 + 14 + 3
    assert all(float(jnp.max(jnp.abs(ref))) > 0
               for ref in jax.tree_util.tree_leaves(want))
    programs.assert_trees_close(grads, want, rtol=0, scaled=3e-5)


def test_attn_local_takes_the_plain_path_and_agrees_with_flash(params):
    tokens, _ = _data()
    flash = programs.forward(CFG)(params, tokens)
    local = programs.forward(dataclasses.replace(CFG, attn="local"))(
        params, tokens)
    np.testing.assert_allclose(np.asarray(local), np.asarray(flash),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------- refusals

@pytest.mark.parametrize("attn", ["ring", "ulysses"])
def test_latent_attention_refuses_the_one_width_algorithms(params, attn):
    cfg = dataclasses.replace(CFG, attn=attn)
    with pytest.raises(HorovodTpuError, match="attention='mla'"):
        tfm.validate_cfg_for_mesh(cfg, mesh_of())
    tokens, _ = _data()
    with pytest.raises(HorovodTpuError, match="different widths"):
        programs.forward(cfg)(params, tokens)


def test_latent_attention_refuses_a_sharded_sequence():
    with pytest.raises(HorovodTpuError, match="sp=1"):
        tfm.validate_cfg_for_mesh(CFG, mesh_of(sp=2))


def test_a_pipeline_with_leading_dense_layers_is_refused():
    """No silent third state: the prefix belongs to no stage."""
    cfg = dataclasses.replace(CFG, microbatches=2)
    with pytest.raises(HorovodTpuError, match="first_k_dense"):
        tfm.validate_cfg_for_mesh(cfg, mesh_of(pp=2))
    # without the prefix the same mesh is accepted
    tfm.validate_cfg_for_mesh(
        dataclasses.replace(cfg, first_k_dense=0, n_layers=2), mesh_of(pp=2))


def test_microbatches_without_stages_run_the_prefix_on_the_whole_batch(
        params, ours):
    tokens, targets = _data()
    two, _ = programs.loss_and_grads(dataclasses.replace(
        CFG, microbatches=2))(params, tokens, targets)
    np.testing.assert_allclose(float(two), float(ours[0]), rtol=1e-5)


# ------------------------------------------------------------------- YaRN

def test_yarn_frequencies_and_scale_by_hand():
    """64 rotary columns, theta 10,000, 4,096 original positions: the pair
    that turns 32 times is 64 ln(4096 / 64 pi) / (2 ln 10^4) = 10.47 -> 10,
    the pair that turns once 22.51 -> 23."""
    assert 64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(1e4)) \
        == pytest.approx(10.472, abs=1e-3)
    assert 64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(1e4)) \
        == pytest.approx(22.513, abs=1e-3)
    freq = YARN.frequencies(64, 10000.0)
    plain = 10000.0 ** (-np.arange(32) / 32)
    assert freq.shape == (32,) and freq.dtype == np.float32
    np.testing.assert_allclose(freq[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(freq[23:], plain[23:] / 40, rtol=1e-6)
    # pair 16 is 6/13 of the way: 7/13 of itself + 6/13 of itself / 40
    np.testing.assert_allclose(
        freq[16], plain[16] * (7 / 13 + 6 / 13 / 40), rtol=1e-6)
    np.testing.assert_allclose(freq, reference.yarn_frequencies(64),
                               rtol=1e-7)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert YARN.score_factor == pytest.approx(1.5896, abs=1e-4)
    assert YARN.rotation_factor == 1.0
    cfg = dataclasses.replace(CFG, qk_nope_dim=128, qk_rope_dim=64)
    assert cfg.score_scale == pytest.approx(192 ** -0.5 * 1.5896, rel=1e-4)
    assert cfg.rope_dim == 64
    # without YaRN nothing is passed on: the kernels' own default holds
    assert dataclasses.replace(cfg, yarn=None).score_scale is None
    cos, sin = mixers.rope_angles(jnp.arange(5), 64, 10000.0, YARN)
    np.testing.assert_allclose(np.asarray(cos[3]), np.cos(3 * freq),
                               rtol=1e-5, atol=1e-6)
    old = mixers.rope_angles(jnp.arange(5), 64, 10000.0)
    np.testing.assert_allclose(np.asarray(old[1][3]), np.sin(3 * plain),
                               rtol=1e-5, atol=1e-6)


def test_rms_norm_eps_is_read(params):
    x = jnp.full((1, 4), 1e-3, jnp.float32)
    near = mixers.rms(x, jnp.ones(4), 1e-6)
    far = mixers.rms(x, jnp.ones(4))
    assert float(near[0, 0]) == pytest.approx(1 / math.sqrt(2), rel=1e-4)
    assert float(far[0, 0]) == pytest.approx(1e-3 / math.sqrt(1.1e-5),
                                             rel=1e-4)


# ------------------------------------------------------------- the limits

def _no_shared_experts(cfg, weights, monkeypatch):
    layers = dict(weights["layers"],
                  ws2=jnp.zeros_like(weights["layers"]["ws2"]))
    return cfg, dict(weights, layers=layers)


def _no_yarn_scale(cfg, weights, monkeypatch):
    """m^2 off the scores (and m off cos and sin), the frequencies kept."""
    flat = dataclasses.replace(cfg.yarn, mscale_all_dim=0.0, mscale=0.0)
    assert flat.score_factor == 1.0 and flat.rotation_factor == 1.0
    return dataclasses.replace(cfg, yarn=flat), weights


def _key_unrotated(cfg, weights, monkeypatch):
    """The shared rotary key (one head) passes through unrotated."""
    rope = mixers.rope
    monkeypatch.setattr(
        mixers, "rope", lambda x, a: x if x.shape[1] == 1 else rope(x, a))
    return cfg, weights


FAULTS = {"no-shared-experts": _no_shared_experts,
          "no-yarn-scale": _no_yarn_scale, "key-unrotated": _key_unrotated}


@pytest.fixture(scope="module")
def tiny():
    """The benchmark tests' tiny cell: its configuration, a batch, weights,
    and the verdict on the program's own logits."""
    cfg = family.transformer_config(_tiny_config())
    tokens, _ = _data(batch=4, seq=64, vocab=512)
    weights = programs.init(cfg, 3)
    sound = family.check_logits(weights, tokens,
                                programs.forward(cfg)(weights, tokens))
    return cfg, tokens, weights, sound


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_limits_refuse_a_planted_fault(tiny, fault, monkeypatch):
    """The program in float32 is correct by both limits. Without the shared
    experts, without YaRN's m^2 on the scores or with the shared rotary key
    left unrotated it fails at least one."""
    cfg, tokens, weights, sound = tiny
    assert sound["ok"]
    cfg, used = FAULTS[fault](cfg, weights, monkeypatch)
    # (built anew where a part of the model is swapped out: not the memo's)
    build = programs.forward.__wrapped__ if fault == "key-unrotated" \
        else programs.forward
    logits = build(cfg)(used, tokens)
    verdict = family.check_logits(weights, tokens, logits)
    assert not verdict["ok"], verdict


def test_the_logits_limit_refuses_an_8_bit_float(tiny):
    _, tokens, weights, _ = tiny
    eight = reference.logits(family.reference_weights(weights), tokens, 2,
                             first_expert=family.first_expert(_tiny_config()),
                             operands=jnp.float8_e4m3fn)
    verdict = family.check_logits(weights, tokens, eight)
    assert not verdict["ok"], verdict
    assert "held experts" in verdict["detail"]


def _tiny_config():
    """The configuration of the benchmark tests' tiny cell."""
    from benchmark.harness import spec
    here = os.path.dirname(os.path.abspath(__file__))
    return spec.load_cell("tiny-dsv2lite-1chip", root=os.path.join(
        here, "benchmark", "fixtures", "tiny-deepseek-v2")).config
