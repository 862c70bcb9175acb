"""The Kimi Linear block of `models/transformer.py` (a pattern of Kimi Delta
Attention layers, the delta rule with a decay per key channel, and one
latent-attention layer without a rotation; a leading dense layer that is the
pattern's first layer; sigmoid-scored experts with a selection bias, a
renormalised and scaled top-k, of which a share is held, beside one shared
expert; an untied head) against the plain reference
`benchmark/reference/kimi_linear.py`, at a small size in float32: the family's
statement for `tests/family_cases.py` (`FAMILY`), each mixer alone, and of the
shared cases the logits (`attn` "local"), every planted fault and an 8-bit
float refused by the family's limits; the family's counts at the published
widths; and the expert layer alone (the sixteen shares adding up to the uncut
layer with the shared expert counted once, the router's sigmoid rule, the
held experts' row buffer); "mla" under `unrotated`. The loss and every leaf's
gradient (`attn` "flash" under remat "full", as the cell runs it), `dp` = 2,
the stack and the refusals: `tests/test_kimi_linear_stack.py`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import family as programs
from benchmark.families import kimi_linear as family
from benchmark.reference import kimi_linear as reference
from family import mesh_of
from family_cases import (  # noqa: F401  (the fixtures, the shared tests)
    Family, lively, logits, params, pytest_generate_tests, sound, stated,
    their_logits, test_an_unknown_fault_is_refused,
    test_logits_equal_the_references,
    test_the_familys_comparison_reads_zero_for_the_reference,
    test_the_limits_refuse_a_planted_fault,
    test_the_limits_refuse_an_8_bit_float)
from horovod_tpu.models import mixers, transformer as tfm
from horovod_tpu.ops import gated_delta
from horovod_tpu.parallel import moe_ffn

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
#: the cell's algorithm and remat: the loss and the gradients go through it
#: (and without remat `dp` = 2); the logits through `CFG`'s own
TIMED = dataclasses.replace(CFG, attn="flash", remat=True,
                            remat_policy="full")
SEQ = 32


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


#: `init`'s tree with the norms' scales moved off one, the selection bias
#: and the gate's bias off zero, and attention scores of order one, a decay
#: that differs from channel to channel, a gate away from its middle, router
#: scores away from one half
_lively = lively({"wq": 6.0, "wkv_b": 2.0, "wo": 3.0, "we2": 3.0,
                  "router": 4.0, "kda_wf_up": 4.0, "kda_wg_up": 4.0,
                  "w2": 2.0}, shifted=("router_bias", "kda_bg"))
#: what `validate_cfg_for_mesh` refuses: (mesh, changed fields, its words)
REFUSED = (
    ({"sp": 2}, {}, "linear-attention layers require sp=1"),
    ({"tp": 2}, {}, "linear-attention layers require tp=1"),
    ({"pp": 2}, {"microbatches": 2}, "a layer pattern requires pp=1"),
    ({"ep": 2}, {}, "ep > 1 with experts_held < num_experts"),
    ({}, {"kda_rank": 0}, "'kda' layers need kda_rank > 0"),
    ({}, {"router_scoring": "tanh"}, "router_scoring='tanh'"),
    ({}, {"positions": "learned"}, "attention='mla' with positions="
     "'learned'"),
    ({}, {"attn": "ring"}, "attention='mla' needs attn 'flash' or 'local'"),
    ({}, {"first_k_dense": 4, "n_layers": 8}, "pattern's first layers"),
    ({}, {"unrotated": ("mla",)}, "positions='rope'"),
)
#: (among the leaves: `kda_a_log`, `kda_dt_bias` and the decay's two
#: matrices, whose gradients come through the running sums of the chunked
#: form and the decayed products, and the selection bias, which takes none on
#: either side: it chooses and never weighs)
FAMILY = Family(
    cfg=CFG, timed=TIMED, family=family, reference=reference,
    weights=(KINDS,), args=(KINDS, TOP_K, FIRST), data=(2, SEQ),
    refused=REFUSED, lively=_lively, attns=("local",),
    logits_tol=(5e-3, 5e-3), sound_below=2e-4, leaf_atol=3e-4,
    no_gradient=("router_bias",), two_ranks_loss=1e-5,
    two_ranks={"rtol": 2e-3, "atol": 1e-8, "scaled": 2e-4})


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


def test_check_logits_knows_the_configuration_by_its_shapes(params, logits):
    """What `check_logits` cannot read off an array it takes from the
    configuration `transformer_config` was asked about."""
    config = _config()
    cfg = family.transformer_config(config)
    assert cfg == CFG
    assert family.kinds(config) == KINDS
    assert family.pattern(config) == PATTERN
    assert family.first_expert(config) == FIRST
    with jax.enable_x64(False):
        found = family.check_logits(params, FAMILY.batch[0], logits)
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


# --------------------------------------------------------- NoPE by `unrotated`

def test_mla_takes_no_rotation_where_unrotated_names_it():
    """positions "rope" with both kinds named in `unrotated` is positions
    "none"; with "mla" left out its layer is rotated and the logits move.
    (Four layers: the dense one and the rest of its period.)"""
    short = dataclasses.replace(CFG, n_layers=4)
    tokens, _ = FAMILY.batch
    p = _lively(programs.init(short))

    def logits(**changes):
        cfg = dataclasses.replace(short, **changes)
        tfm.validate_cfg_for_mesh(cfg, mesh_of())
        with jax.enable_x64(False):
            return programs.forward(cfg)(p, tokens)

    plain = logits(positions="rope", unrotated=("kda", "mla"))
    np.testing.assert_allclose(plain, logits(), atol=1e-6)
    rotated = logits(positions="rope", unrotated=("kda",))
    assert float(jnp.max(jnp.abs(rotated - plain))) > 1e-2
    assert tfm._kind_cfg(dataclasses.replace(
        CFG, positions="rope", unrotated=("mla",)), "mla").positions == "none"


# --------------------------------------------------------------- the share

def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """One expert of 16 on each of sixteen chips, each scoring all 16 with
    the sigmoid, choosing on score + bias and renormalising over all four
    chosen: the routed parts that `moe_ffn` gives, with the shared expert,
    which every chip computes alike, counted ONCE, add up to what the
    reference's layer gives with every expert held."""
    d, f, tokens, n = 64, 24, 48, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 10)
    rows = jax.random.normal(ks[1], (1, tokens, d), jnp.float32)
    w = {"router": jax.random.normal(ks[2], (d, n), jnp.float32) / 4,
         "bias": 0.4 * jax.random.normal(ks[8], (n,), jnp.float32),
         "w_gate": jax.random.normal(ks[3], (n, d, f), jnp.float32) / 8,
         "w_up": jax.random.normal(ks[4], (n, d, f), jnp.float32) / 8,
         "w_down": jax.random.normal(ks[5], (n, f, d), jnp.float32) / 5,
         "ws_gate": jax.random.normal(ks[6], (d, f), jnp.float32) / 8,
         "ws_up": jax.random.normal(ks[7], (d, f), jnp.float32) / 8,
         "ws_down": jax.random.normal(ks[0], (f, d), jnp.float32) / 5}

    def share(first):
        held = slice(first, first + 1)
        return jax.jit(jax.shard_map(
            lambda x, r, b, up, down, gate: moe_ffn(
                x, r, up, down, gate, top_k=TOP_K, first_expert=first,
                renormalise=True, scoring="sigmoid", selection_bias=b,
                weight_scale=reference.ROUTED_SCALING_FACTOR)[:2],
            mesh=mesh_of(), in_specs=P(), out_specs=P(), check_vma=False))(
                rows[0], w["router"], w["bias"], w["w_up"][held],
                w["w_down"][held], w["w_gate"][held])

    with jax.enable_x64(False), jax.default_matmul_precision("highest"):
        parts = [share(first) for first in range(n)]
        shared = reference.gated_mlp(rows[0], w["ws_gate"], w["ws_up"],
                                     w["ws_down"])
        whole, routes = reference.moe(rows, w, TOP_K)
        one, _ = reference.moe(rows, dict(w, **{
            k: w[k][5:6] for k in ("w_gate", "w_up", "w_down")}), TOP_K,
            first_expert=5)
    assert all(float(aux[2]) == 0 for _, aux in parts)   # nothing left out
    np.testing.assert_allclose(sum(out for out, _ in parts) + shared,
                               whole[0], rtol=3e-5, atol=3e-5)
    # a chip's own result holds the shared expert whole, as the reference's
    np.testing.assert_allclose(parts[5][0] + shared, one[0], rtol=3e-5,
                               atol=3e-5)
    # the bias moved the choice: without it other experts are chosen
    _, plain = reference.router_weights(
        jnp.einsum("nd,de->ne", rows[0], w["router"]), 0.0, TOP_K)
    assert np.any(np.sort(np.asarray(plain)) != np.sort(
        np.asarray(routes[0])))


def test_the_routers_rule():
    """`route` with sigmoid scores: the choice on score + bias, the weights
    the chosen SCORES over their sum times the scale; no gradient to the
    bias, one to the router through the weights."""
    from horovod_tpu.parallel.moe import route
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(ks[0], (20, 16), jnp.float32)
    w = jax.random.normal(ks[1], (16, 8), jnp.float32) / 2
    bias = jnp.zeros((8,), jnp.float32).at[3].set(5.0)     # always chosen
    with jax.enable_x64(False):
        weights, experts, counts, _ = route(
            x, w, 2, renormalise=True, scoring="sigmoid",
            selection_bias=bias, weight_scale=2.446)
        scores = jax.nn.sigmoid(x @ w)
        assert int(counts[3]) == 20 and bool(jnp.all(experts[:, 0] == 3))
        chosen = jnp.take_along_axis(scores, experts, axis=-1)
        np.testing.assert_allclose(
            weights, 2.446 * chosen / chosen.sum(-1, keepdims=True),
            rtol=1e-6)
        np.testing.assert_allclose(weights.sum(-1), 2.446, rtol=1e-6)

        def total(w, bias):
            return jnp.sum(route(x, w, 2, renormalise=True,
                                 scoring="sigmoid", selection_bias=bias,
                                 weight_scale=2.446)[0][:, 0])

        dw, dbias = jax.grad(total, argnums=(0, 1))(w, bias)
    assert float(jnp.max(jnp.abs(dbias))) == 0.0
    assert float(jnp.max(jnp.abs(dw))) > 0.0
    # the softmax rule is what it was: its defaults change nothing
    plain = route(x, w, 2)
    top, _ = jax.lax.top_k(jax.nn.softmax(x @ w, axis=-1), 2)
    np.testing.assert_allclose(plain[0], top, rtol=1e-6)


def test_the_held_experts_buffer_has_the_room_it_is_given():
    """A selection bias that sends every token to the one held expert: 4,096
    held pairs where an even routing sends 1,024. At the default room, twice
    the even load, 2,048 find none, are counted and add nothing; at
    `held_factor` 4 (`TransformerConfig.capacity_factor`, the cell's
    `held_capacity`) every pair is computed."""
    from horovod_tpu.parallel.moe import held_rows
    tokens, d, f, n = 4096, 16, 8, 8
    assert held_rows(tokens * 2, 1, n) == 2048
    assert held_rows(tokens * 2, 1, n, 4.0) == 4096
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (tokens, d), jnp.float32)
    router = jax.random.normal(ks[1], (d, n), jnp.float32) / 4
    bias = jnp.zeros((n,), jnp.float32).at[0].set(9.0)
    up, gate = (jax.random.normal(k, (1, d, f), jnp.float32) / 4
                for k in ks[2:4])
    down = jax.random.normal(ks[4], (1, f, d), jnp.float32) / 3

    def layer(factor):
        return jax.jit(jax.shard_map(
            lambda x, r, b, up, down, gate: moe_ffn(
                x, r, up, down, gate, top_k=2, renormalise=True,
                scoring="sigmoid", selection_bias=b, held_factor=factor)[:2],
            mesh=mesh_of(), in_specs=P(), out_specs=P(), check_vma=False))(
                x, router, bias, up, down, gate)

    with jax.enable_x64(False), jax.default_matmul_precision("highest"):
        tight, loose = layer(2.0), layer(4.0)
        w = {"router": router, "bias": bias, "w_gate": gate, "w_up": up,
             "w_down": down, "ws_gate": jnp.zeros((d, f)),
             "ws_up": jnp.zeros((d, f)), "ws_down": jnp.zeros((f, d))}
        want, _ = reference.moe(x[None], w, 2)
    assert float(tight[1][2]) == 2048 and float(loose[1][2]) == 0
    np.testing.assert_allclose(loose[0], want[0] / reference.
                               ROUTED_SCALING_FACTOR, rtol=3e-5, atol=3e-5)
    assert float(jnp.max(jnp.abs(tight[0] - loose[0]))) > 1e-2
