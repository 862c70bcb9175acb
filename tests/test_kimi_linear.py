"""The Kimi Linear block of `models/transformer.py` (a pattern of Kimi Delta
Attention layers, the delta rule with a decay per key channel, and one
latent-attention layer without a rotation; a leading dense layer that is the
pattern's first layer; sigmoid-scored experts with a selection bias, a
renormalised and scaled top-k, of which a share is held, beside one shared
expert; an untied head) against the plain reference
`benchmark/reference/kimi_linear.py`, at a small size in float32: each mixer
alone, logits, loss and every leaf's gradient, `attn` "local" and "flash";
every planted fault refused by the family's limits;
and the family's counts at the published widths. The stack, the share of the
experts, the router's rule, `unrotated`, `dp` = 2 and the refusals:
`tests/test_kimi_linear_stack.py`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family as programs
from benchmark.families import kimi_linear as family
from benchmark.reference import kimi_linear as reference
from horovod_tpu.models import mixers, transformer as tfm
from horovod_tpu.ops import gated_delta

PATTERN = ("kda", "kda", "kda", "mla")
KINDS = PATTERN + PATTERN[:1]       # five layers: the least the cell's rule leaves
TOP_K, FIRST = 4, 4
# 2 KDA heads of 8 | 8 through a rank of 8; 4 MLA heads of (8 + 8) | 8 over a
# latent of 16; 16 experts, 4 a token, experts 4-7 held, beside one shared
# expert; the first layer's dense MLP nine experts wide
CFG = tfm.TransformerConfig(
    vocab=96, d_model=64, n_heads=4, d_ff=24, n_layers=5, max_seq=64,
    num_experts=16, experts_per_token=TOP_K, experts_held=4,
    first_expert=FIRST, shared_experts=1, first_k_dense=1, d_ff_dense=216,
    norm_topk=True, router_scoring="sigmoid", router_bias=True,
    routed_scale=reference.ROUTED_SCALING_FACTOR, norm="rmsnorm",
    rms_norm_eps=reference.RMS_EPS, positions="none", layer_pattern=PATTERN,
    mlp="swiglu", attention="kda", gdn_heads=2, gdn_key_dim=8,
    gdn_value_dim=8, gdn_conv=4, kda_rank=8, kv_latent=16, qk_nope_dim=8,
    qk_rope_dim=8, v_head_dim=8, attn="local", dtype=jnp.float32)
SEQ = 32
#: the loss and the gradients go through the cell's algorithm; the logits
#: (`system_logits`) through the other
ATTNS = ("flash",)


@pytest.fixture(scope="module", autouse=True)
def chunks_of_eight():
    """The models of this file run the rule in chunks of 8 tokens, four to
    a sequence of `SEQ`, so that a layer's state goes from chunk to chunk and
    the kernels' unrolled blocks stay small; the chunk of 64 and its blocks
    of rows are `tests/test_kda.py`'s, `tests/test_lowered_steps.py`'s and
    the benchmark fixture's."""
    real = gated_delta.gated_delta_rule
    gated_delta.gated_delta_rule = lambda *a: real(*a, chunk=8)
    yield
    gated_delta.gated_delta_rule = real


def _data(batch=2, seq=SEQ):
    return programs.data(CFG.vocab, batch, seq)


#: what `_lively` multiplies the drawn leaves by
_LOUDER = {"wq": 6.0, "wkv_b": 2.0, "wo": 3.0, "we2": 3.0, "router": 4.0,
           "kda_wf_up": 4.0, "kda_wg_up": 4.0, "w2": 2.0}


@jax.jit
def _lively(params):
    """`init`'s tree with the leaves it draws as ones or zeros moved (the
    norms' scales, the selection bias, the gate's bias), and the parts whose
    faults are planted made loud enough to show at this size: attention
    scores of order one, a decay that differs from channel to channel, a
    gate away from its middle, router scores away from one half."""
    keys = iter(jax.random.split(jax.random.PRNGKey(11), 128))

    def moved(path, leaf):
        name = path[-1].key
        if name.endswith("_scale"):
            return leaf * (1 + 0.3 * jax.random.normal(next(keys),
                                                       leaf.shape))
        if name in ("router_bias", "kda_bg"):
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape)
        return leaf * _LOUDER.get(name, 1.0)

    with jax.enable_x64(False):
        return jax.tree_util.tree_map_with_path(moved, params)


@pytest.fixture(scope="module")
def params():
    return _lively(programs.init(CFG))


@pytest.fixture(scope="module", params=ATTNS)
def ours(request, params):
    """(loss, gradients) of the program on one rank, by each algorithm."""
    tokens, targets = _data()
    cfg = dataclasses.replace(CFG, attn=request.param)
    with jax.enable_x64(False):
        return programs.loss_and_grads(cfg)(params, tokens, targets)


@pytest.fixture(scope="module")
def system_logits(params):
    """The program's logits for `_data()`'s tokens, once."""
    with jax.enable_x64(False):
        return programs.forward(CFG)(params, _data()[0])


@pytest.fixture(scope="module")
def theirs(params):
    """(loss, gradients) of the reference, in the program's tree."""
    tokens, targets = _data()
    with jax.enable_x64(False):
        return jax.value_and_grad(lambda p: reference.loss(
            family.reference_weights(p, KINDS), tokens, targets, KINDS,
            TOP_K, FIRST))(params)


# ------------------------------------------------------------------ the tree

def test_the_tree_has_each_kinds_leaves_and_no_others(params):
    assert sorted(params) == ["dense_layers", "embed", "layers", "lnf_scale",
                              "unembed"]
    first, second = params["layers"]          # a segment each
    assert sorted(first) == ["kda", "mla"] and sorted(second) == ["kda"]
    ffn = {"ln1_scale", "ln2_scale", "router", "router_bias", "we1", "we2",
           "we_gate", "ws1", "ws2", "ws_gate"}
    kda = {"kda_wq", "kda_wk", "kda_wv", "kda_wf_down", "kda_wf_up",
           "kda_wg_down", "kda_wg_up", "kda_bg", "kda_wb", "kda_a_log",
           "kda_dt_bias", "kda_conv_q", "kda_conv_k", "kda_conv_v",
           "kda_o_scale", "wo"}
    assert set(first["kda"]) == set(second["kda"]) == ffn | kda
    assert set(first["mla"]) == ffn | {"wq", "wkv_a", "kv_scale", "wkv_b",
                                       "wo"}
    # the dense layer is a KDA layer with a dense MLP of its own width
    assert set(params["dense_layers"]) == kda | {
        "ln1_scale", "ln2_scale", "w1", "w2", "w_gate"}
    assert params["dense_layers"]["w1"].shape == (1, 64, 216)
    # stacked over (periods, the kind's layers in a period), a segment each
    assert first["kda"]["kda_wq"].shape == (1, 2, 64, 2, 8)
    assert second["kda"]["kda_wq"].shape == (1, 1, 64, 2, 8)
    assert first["kda"]["kda_wf_down"].shape == (1, 2, 64, 8)
    assert first["kda"]["kda_wf_up"].shape == (1, 2, 8, 2, 8)
    assert first["kda"]["kda_dt_bias"].shape == (1, 2, 2, 8)    # a channel
    assert first["kda"]["kda_a_log"].shape == (1, 2, 2)         # a head
    assert first["mla"]["wq"].shape == (1, 1, 64, 4, 16)
    assert first["mla"]["wkv_a"].shape == (1, 1, 64, 16 + 8)
    assert first["mla"]["router"].shape == (1, 1, 64, 16)   # whole
    assert first["mla"]["router_bias"].shape == (1, 1, 16)
    assert first["mla"]["we_gate"].shape == (1, 1, 4, 64, 24)   # four held
    assert first["mla"]["ws1"].shape == (1, 1, 64, 24)          # one shared
    programs.assert_specs_cover(CFG, params)


def test_the_seeded_leaves_are_gated_deltanets_own():
    """A ~ U(0, 16) a head held as its logarithm, the step's bias a channel
    the inverse softplus of log-U(0.001, 0.1); the selection bias and the
    gate's bias zero, the norms' scales one."""
    p = programs.init(dataclasses.replace(CFG, gdn_heads=64, n_layers=8),
                      3)["layers"][1]["kda"]
    rate = np.exp(np.asarray(p["kda_a_log"]))
    assert rate.min() >= 1e-3 * 0.999 and rate.max() <= 16 and rate.std() > 3
    step = np.asarray(jax.nn.softplus(p["kda_dt_bias"]))
    assert step.shape == (1, 3, 64, 8)
    assert step.min() >= 1e-3 * 0.999 and step.max() <= 0.1 * 1.001
    assert np.log(step).std() > 1
    assert np.all(np.asarray(p["router_bias"]) == 0)
    assert np.all(np.asarray(p["kda_bg"]) == 0)
    assert np.all(np.asarray(p["kda_o_scale"]) == 1)


# ---------------------------------------------- the program and the reference

@pytest.mark.parametrize("kind, at", [("kda", 1), ("mla", 3)])
def test_a_mixer_alone_equals_the_references(params, kind, at):
    """`MIXERS[kind]` on a normed state against the reference's mixer on the
    same leaves; the rule's sequence a chunk and a half long."""
    cfg = tfm._kind_cfg(CFG, kind)
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 64), jnp.float32)
    lp = {k: v[0, 0] for k, v in params["layers"][0][kind].items()}
    w = family.reference_weights(params, KINDS)["layers"][at]
    with jax.enable_x64(False), jax.default_matmul_precision("highest"):
        got, handed = mixers.MIXERS[kind].apply(u, lp, cfg, None, {}, at)
        want = getattr(reference, kind)(u, w)
    assert handed is None
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)


def test_logits_equal_the_references(params, system_logits):
    """(`attn` "local"; "flash" is held by the loss and the gradients.)"""
    with jax.enable_x64(False):
        want = reference.forward(family.reference_weights(params, KINDS),
                                 _data()[0], KINDS, TOP_K, FIRST)
    np.testing.assert_allclose(system_logits, want, atol=5e-3, rtol=5e-3)


def test_loss_equals_the_references(ours, theirs):
    np.testing.assert_allclose(ours[0], theirs[0], rtol=1e-5)


@pytest.mark.parametrize("leaf", programs.leaf_names(CFG))
def test_every_leafs_gradient_equals_the_references(ours, theirs, leaf):
    """Among them `kda_a_log`, `kda_dt_bias` and the decay's two matrices,
    whose gradients come through the running sums of the chunked form and
    the decayed products, and the selection bias, which takes none on either
    side: it chooses and never weighs."""
    got, want = (programs.leaves(x[1])[leaf] for x in (ours, theirs))
    size = float(jnp.max(jnp.abs(want)))
    if "router_bias" in leaf:
        assert size == 0.0 == float(jnp.max(jnp.abs(got)))
        return
    assert size > 1e-7, "nothing to compare"
    np.testing.assert_allclose(got, want, rtol=2e-3,
                               atol=3e-4 * size + 1e-8)


# --------------------------------------------------------------- the limits

@pytest.mark.parametrize("fault", reference.FAULTS)
def test_the_limits_refuse_a_planted_fault(params, system_logits, fault):
    """The program's logits against the reference computed with one
    mechanism wrong: by one of the family's limits it is not correct, and
    against the sound reference it is, with room."""
    tokens, logits = _data()[0], system_logits
    with jax.enable_x64(False):
        sound = family.compare(params, tokens, logits, KINDS, TOP_K, FIRST)
        wrong = family.compare(params, tokens, logits, KINDS, TOP_K, FIRST,
                               fault=fault)
    assert all(family.within(*(float(x) for x in sound[:3])))
    assert float(sound[0]) < 2e-4
    assert not all(family.within(*(float(x) for x in wrong[:3]))), \
        [float(x) for x in wrong[:3]]
    with pytest.raises(ValueError, match="choose from"):
        reference.final_hidden(family.reference_weights(params, KINDS),
                               tokens, KINDS, TOP_K, FIRST,
                               fault="no_such_fault")


@pytest.mark.parametrize("operands", [jnp.float8_e4m3fn, jnp.float8_e5m2],
                         ids=["e4m3", "e5m2"])
def test_the_limits_refuse_an_8_bit_float(params, system_logits, operands):
    with jax.enable_x64(False):
        rms, got, want, _ = family.compare(
            params, _data()[0], system_logits, KINDS, TOP_K, FIRST,
            operands=operands)
    assert not all(family.within(float(rms), float(got), float(want)))


def test_the_familys_comparison_reads_zero_for_the_reference(params):
    """`family.compare` (the reference's head a block of tokens at a time)
    against the reference's whole forward pass and its blockwise loss; its
    count of the held experts' rows against the routes themselves, of the
    four expert layers (the dense layer routes nothing)."""
    tokens, targets = _data()
    with jax.enable_x64(False):
        weights = family.reference_weights(params, KINDS)
        logits = reference.forward(weights, tokens, KINDS, TOP_K, FIRST)
        _, routes = reference.final_hidden(weights, tokens, KINDS, TOP_K,
                                           FIRST)
        rms, got, want, rows = family.compare(params, tokens, logits, KINDS,
                                              TOP_K, FIRST)
        loss = reference.loss(weights, tokens, targets, KINDS, TOP_K, FIRST)
    assert float(rms) < 1e-6
    np.testing.assert_allclose([float(got), float(want)], float(loss),
                               rtol=1e-6)
    assert routes.shape == (4, 2, SEQ, TOP_K) and rows.shape == (4, 4)
    assert [int(np.sum(np.asarray(routes) == FIRST + e)) for e in range(4)] \
        == [int(rows[:, e].sum()) for e in range(4)]
    assert "router" not in weights["layers"][0]
    assert weights["layers"][0]["w_up"].shape == (64, 216)


def _config():
    return {
        "vocab_size": 96, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 4, "intermediate_size": 216,
        "moe_intermediate_size": 24, "n_layer": 5, "num_hidden_layers": 27,
        "model_max_length": 64, "num_experts": 4, "num_experts_per_token": 4,
        "num_shared_experts": 1, "first_k_dense_replace": 1,
        "hidden_act": "silu", "mla_use_nope": True, "q_lora_rank": None,
        "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
        "moe_layer_freq": 1, "num_expert_group": 1, "topk_group": 1,
        "tie_word_embeddings": False, "num_nextn_predict_layers": 0,
        "rms_norm_eps": 1e-5, "routed_scaling_factor": 2.446,
        "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
        "v_head_dim": 8,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27],
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                           19, 21, 22, 23, 25, 26],
            "head_dim": 8, "num_heads": 2, "short_conv_kernel_size": 4},
        "published": {"num_experts": 16},
        "deployment": {"expert_rank": 1},
        "assumed": {"kda_rank": 8},
        "program": {"dtype": "float32", "attn": "local", "remat": False,
                    "remat_policy": "dots", "load_balance_coef": 0.0,
                    "router_z_coef": 0.0, "held_capacity": 2.0}}


def test_check_logits_knows_the_configuration_by_its_shapes(params,
                                                            system_logits):
    """What `check_logits` cannot read off an array it takes from the
    configuration `transformer_config` was asked about."""
    config = _config()
    cfg = family.transformer_config(config)
    assert cfg == CFG
    assert family.kinds(config) == KINDS
    assert family.pattern(config) == PATTERN
    assert family.first_expert(config) == FIRST
    with jax.enable_x64(False):
        found = family.check_logits(params, _data()[0], system_logits)
    assert found["ok"], found
    assert "rows of the 4 held experts" in found["detail"]
    with pytest.raises(ValueError, match="no equations for"):
        family.transformer_config(dict(config, mla_use_nope=False))
    with pytest.raises(ValueError, match="no equations for"):
        family.transformer_config(dict(
            config, moe_router_activation_func="softmax"))
    with pytest.raises(ValueError, match="differs from the constants"):
        family.transformer_config(dict(config, routed_scaling_factor=1.0))


def test_the_familys_counts_at_the_published_widths():
    """The parameters of the cell's cut, leaf by leaf from `tfm.init`'s
    shapes, and the family's FLOPs and least work at its shapes."""
    import json
    import os
    from benchmark.harness import spec
    with open(os.path.join(spec.REPO, "benchmark", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        config = json.load(f)
    cfg = family.transformer_config(config)
    count = {name: int(np.prod(x.shape))
             for name, x in programs.leaves(programs.shapes(cfg)).items()}

    def of(*parts, without=()):
        return sum(n for name, n in count.items()
                   if all(p in name for p in parts)
                   and not any(w in name for w in without))

    ffn = ("router", "we", "ws", "ln")
    assert of("[1]['kda']", without=ffn) == 2 * 39_518_368
    assert of("[0]['mla']", without=ffn) == 29_114_880
    assert of("[0]['mla']['we1']") == 16 * 2_304 * 1_024
    assert of("['dense_layers']") == 39_518_368 + 3 * 2_304 * 9_216 \
        + 2 * 2_304
    assert of("['embed']") + of("['unembed']") == 2 * 20_480 * 2_304
    assert sum(count.values()) == config["check"]["parameters"]
    traffic = {"per_chip_batch": 1, "seq_len": 16_384}
    assert family.flash_kernel_shape(config, traffic) == (1, 32, 16_384, 192,
                                                          128)
    assert family.grouped_matmul_shape(config, traffic) == (8_192, 2_304,
                                                            1_024, 16)
    forward, backward = family.kda_scan_work(config, traffic)
    layers = family.kinds(config).count("kda")
    assert forward[0] == 2 * layers and backward[0] == layers
    rows = 16_384 * 32
    assert forward[1] == 2 * 3 * 128 * 128 * rows
    assert forward[2] == rows * (3 * 128 * 2 + 128 * 4 + 4 + 128 * 2)
    flops = family.forward_flops_per_token(config, 16_384)
    assert flops["kda_rule"] == layers * 2 * 3 * 32 * 128 * 128
    assert family.flops_per_sample(config, traffic) == 3 * sum(
        flops.values())
