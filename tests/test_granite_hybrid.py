"""The Granite 4.0-H block of `models/transformer.py` (a pattern of Mamba-2
state-space layers and one attention layer without positions over fewer key
and value heads than query heads, every layer followed by SiLU-gated experts
with a renormalised top-k, of which a share is held, beside a shared MLP, a
tied head, and the four scalar multipliers) against the plain reference
`benchmark/reference/granite_hybrid.py`, at a small size in float32: the
family's statement for `tests/family_cases.py` (`FAMILY`) and of the shared
cases the logits, `attn` "local" and "flash", every planted fault refused by
the family's limits and what `validate_cfg_for_mesh` refuses; the mixer
alone; the shares of the experts adding up to the uncut layer with the
shared MLP counted once, and the held heads' scan output the matching slice
of the whole mixer's; the multipliers' defaults. (Loss and gradients as the
cell runs them and `dp` = 2: `tests/test_granite_hybrid_grads.py`; the
compiled step, its scopes and three steps of it:
`tests/test_step_scopes.py`.) Every program is `tests/family.py`'s, built
once for the module."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import family as programs
from benchmark.families import granite_hybrid as family
from benchmark.reference import granite_hybrid as reference
from family_cases import (  # noqa: F401  (the fixtures, the shared tests)
    Family, lively, logits, params, pytest_generate_tests, sound, stated,
    their_logits, test_an_unknown_fault_is_refused,
    test_logits_equal_the_references,
    test_the_familys_comparison_reads_zero_for_the_reference,
    test_the_limits_refuse_a_planted_fault,
    test_the_limits_refuse_an_8_bit_float,
    test_validate_accepts_the_model_where_it_runs,
    test_validate_refuses_by_name)
from horovod_tpu.models import mixers, transformer as tfm
from horovod_tpu.ops import ssd_scan as ssd
from family import mesh_of
from horovod_tpu.parallel import moe_ffn

KINDS = ("mamba2", "mamba2", "full", "mamba2")
TOP_K, FIRST = 3, 2
# 4 Mamba-2 heads of 16 with 8 states; 2 | 1 attention heads of 16 on a
# 64-wide model (heads x width != d_model); 8 experts, 3 a token, experts 2
# and 3 held, beside a shared MLP of twice an expert's width; the reference's
# four multipliers
CFG = tfm.TransformerConfig(
    vocab=96, d_model=64, n_heads=2, n_kv_heads=1, d_head=16, d_ff=24,
    n_layers=4, max_seq=64, num_experts=8, experts_per_token=TOP_K,
    experts_held=2, first_expert=FIRST, shared_experts=2, norm_topk=True,
    norm="rmsnorm", rms_norm_eps=reference.RMS_EPS, positions="none",
    layer_pattern=KINDS, mlp="swiglu", tied_head=True, ssd_heads=4,
    ssd_head_dim=16, ssd_state=8, ssd_conv=4,
    embed_scale=reference.EMBEDDING_MULTIPLIER,
    residual_scale=reference.RESIDUAL_MULTIPLIER,
    attn_scale=reference.ATTENTION_MULTIPLIER,
    logit_scale=1.0 / reference.LOGITS_SCALING, attn="local",
    dtype=jnp.float32)
SEQ = 32

#: `init`'s tree with the norms' scales and D moved off one, and scores of
#: order one under the 1/128 multiplier, the attention layer's, the routed
#: experts' and the scans' outputs a larger share of the residual stream
_lively = lively({"wq": 16.0, "wo": 4.0, "we2": 4.0, "ssd_w_out": 2.0},
                 moved=("_scale", "ssd_d_skip"), keys=64)
#: what `validate_cfg_for_mesh` refuses: (mesh, changed fields, its words)
REFUSED = (
    (dict(sp=2), {"attn": "local"},
     "state-space dual layers require sp=1"),
    (dict(tp=2), {}, "state-space dual layers require tp=1"),
    (dict(pp=2), {"microbatches": 2},
     "state-space dual layers require pp=1"),
    ({}, {"ssd_heads": 0}, "'mamba2' layers need ssd_heads > 0"),
    ({}, {"attn": "ring"}, "cannot run them|needs attn"),
    ({}, {"layer_pattern": KINDS[:3] + ("mamba",)}, "names the kind 'mamba'"),
    ({}, {"n_layers": 6}, "no whole number of periods"),
)
#: the cell's algorithm and remat policy; (among the leaves: `ssd_a_log` and
#: `ssd_dt_bias`, whose gradients come through the running sums of the
#: chunked form, the two leaves of the input projection, and the tied
#: embedding's, read twice)
FAMILY = Family(
    cfg=CFG, family=family, reference=reference,
    timed=dataclasses.replace(CFG, attn="flash", remat=True,
                              remat_policy="dots"),
    weights=(KINDS,), args=(KINDS, TOP_K, FIRST), data=(2, SEQ),
    refused=REFUSED, lively=_lively,
    accepted=(({"attn": "flash"}, {"dp": 4}),))


def test_the_tree_has_each_kinds_leaves_and_no_others(params):
    assert sorted(params) == ["embed", "layers", "lnf_scale"]   # tied head
    assert sorted(params["layers"]) == ["full", "mamba2"]
    ffn = {"ln1_scale", "ln2_scale", "router", "we1", "we2", "we_gate",
           "ws1", "ws2", "ws_gate"}
    full, mamba = params["layers"]["full"], params["layers"]["mamba2"]
    assert set(full) == ffn | {"wq", "wk", "wv", "wo"}
    assert set(mamba) == ffn | {
        "ssd_w_in", "ssd_w_dt", "ssd_conv", "ssd_conv_bias", "ssd_dt_bias",
        "ssd_a_log", "ssd_d_skip", "ssd_norm_scale", "ssd_w_out"}
    # stacked over (periods, the kind's layers in a period)
    assert mamba["ssd_w_in"].shape == (1, 3, 64, 64 + 64 + 2 * 8)
    assert mamba["ssd_w_dt"].shape == (1, 3, 64, 4)
    assert mamba["ssd_conv"].shape == (1, 3, 64 + 2 * 8, 4)
    assert mamba["ssd_a_log"].shape == (1, 3, 4) == mamba["ssd_d_skip"].shape
    assert mamba["ssd_norm_scale"].shape == (1, 3, 64)
    assert full["wq"].shape == (1, 1, 64, 2, 16)
    assert full["wk"].shape == (1, 1, 64, 1, 16)
    assert mamba["router"].shape == (1, 3, 64, 8)      # the router is whole
    assert mamba["we_gate"].shape == (1, 3, 2, 64, 24)     # two are held
    assert mamba["ws1"].shape == (1, 3, 64, 48)            # shared: 2 x 24
    programs.assert_specs_cover(CFG, params)


def test_the_seeded_leaves_are_mamba_2s_own():
    """A ~ U(1, 16) held as its logarithm, the step's bias the inverse
    softplus of log-U(0.001, 0.1), D = 1."""
    p = programs.init(dataclasses.replace(CFG, ssd_heads=64),
                      3)["layers"]["mamba2"]
    rate = np.exp(np.asarray(p["ssd_a_log"]))
    assert rate.min() >= 1 and rate.max() <= 16 and rate.std() > 3
    step = np.asarray(jax.nn.softplus(p["ssd_dt_bias"]))
    assert step.min() >= 1e-3 * 0.999 and step.max() <= 0.1 * 1.001
    assert np.log(step).std() > 1
    assert np.all(np.asarray(p["ssd_d_skip"]) == 1)
    assert np.all(np.asarray(p["ssd_norm_scale"]) == 1)


def test_the_mixer_alone_equals_the_references(params):
    """`MIXERS["mamba2"]` on a normed state against `reference.mamba2` on
    the same leaves, the sequence a chunk and a half long."""
    kind = tfm._kind_cfg(CFG, "mamba2")
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 64), jnp.float32)
    lp = {k: v[0, 1] for k, v in params["layers"]["mamba2"].items()}
    w = family.reference_weights(params, KINDS)["layers"][1]
    with jax.enable_x64(False), jax.default_matmul_precision("highest"):
        real = ssd.ssd_scan
        try:
            ssd.ssd_scan = lambda *a: real(*a, chunk=8)
            got, handed = mixers.MIXERS["mamba2"].apply(u, lp, kind, None,
                                                        {}, 1)
        finally:
            ssd.ssd_scan = real
        want = reference.mamba2(u, w)
    assert handed is None
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)


def test_check_logits_knows_the_configuration_by_its_shapes(params, logits):
    """What `check_logits` cannot read off an array it takes from the
    configuration `transformer_config` was asked about."""
    config = {
        "vocab_size": 96, "hidden_size": 64, "num_attention_heads": 2,
        "num_key_value_heads": 1, "intermediate_size": 24,
        "shared_intermediate_size": 48, "n_layer": 4,
        "max_position_embeddings": 64, "num_local_experts": 2,
        "num_experts_per_tok": TOP_K, "mamba_n_heads": 4, "mamba_d_head": 16,
        "mamba_d_state": 8, "mamba_d_conv": 4, "mamba_expand": 1,
        "mamba_n_groups": 1, "mamba_conv_bias": True,
        "mamba_proj_bias": False, "mamba_chunk_size": 256,
        "attention_bias": False, "hidden_act": "silu",
        "position_embedding_type": "nope",
        "normalization_function": "rmsnorm", "tie_word_embeddings": True,
        "rms_norm_eps": 1e-5, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "attention_multiplier": 0.0078125,
        "logits_scaling": 16,
        "layer_types": ["mamba", "mamba", "attention", "mamba"] * 2,
        "published": {"num_local_experts": 8, "num_attention_heads": 4,
                      "mamba_n_heads": 4},
        "deployment": {"expert_rank": 1},
        "program": {"dtype": "float32", "attn": "local", "remat": False,
                    "remat_policy": "dots", "load_balance_coef": 0.0,
                    "router_z_coef": 0.0}}
    cfg = family.transformer_config(config)
    assert cfg == CFG
    assert family.kinds(config) == KINDS == family.pattern(config)
    assert family.first_expert(config) == FIRST
    with jax.enable_x64(False):
        found = family.check_logits(params, FAMILY.batch[0], logits)
    assert found["ok"], found
    assert "rows of the 2 held experts" in found["detail"]
    with pytest.raises(ValueError, match="no equations for"):
        family.transformer_config(dict(config, mamba_n_groups=8))
    with pytest.raises(ValueError, match="differs from the constants"):
        family.transformer_config(dict(config, residual_multiplier=0.5))


# --------------------------------------------------------------- the share

def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Experts 0-1, 2-3, 4-5 and 6-7 of 8 on four chips, each routing over
    all 8 and renormalising over all three chosen: the routed parts that
    `moe_ffn` gives, with the shared MLP, which every chip computes alike,
    counted ONCE, add up to what the reference's layer gives with every
    expert held."""
    d, f, tokens = 64, 24, 48
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    rows = jax.random.normal(ks[1], (1, tokens, d), jnp.float32)
    w = {"router": jax.random.normal(ks[2], (d, 8), jnp.float32) / 8,
         "w_gate": jax.random.normal(ks[3], (8, d, f), jnp.float32) / 8,
         "w_up": jax.random.normal(ks[4], (8, d, f), jnp.float32) / 8,
         "w_down": jax.random.normal(ks[5], (8, f, d), jnp.float32) / 5,
         "ws_gate": jax.random.normal(ks[6], (d, 2 * f), jnp.float32) / 8,
         "ws_up": jax.random.normal(ks[7], (d, 2 * f), jnp.float32) / 8,
         "ws_down": jax.random.normal(ks[0], (2 * f, d), jnp.float32) / 7}

    def share(first):
        held = slice(first, first + 2)
        return jax.jit(jax.shard_map(
            lambda x, r, up, down, gate: moe_ffn(
                x, r, up, down, gate, top_k=TOP_K, first_expert=first,
                renormalise=True)[:2],
            mesh=mesh_of(), in_specs=P(), out_specs=P(), check_vma=False))(
                rows[0], w["router"], w["w_up"][held], w["w_down"][held],
                w["w_gate"][held])

    def held(first):
        return dict(w, **{k: w[k][first:first + 2]
                          for k in ("w_gate", "w_up", "w_down")})

    with jax.enable_x64(False), jax.default_matmul_precision("highest"):
        parts = [share(first) for first in (0, 2, 4, 6)]
        shared = reference.gated_mlp(rows[0], w["ws_gate"], w["ws_up"],
                                     w["ws_down"])
        whole, routes = reference.moe(rows, w, TOP_K)
        one, _ = reference.moe(rows, held(2), TOP_K, first_expert=2)
    assert all(float(aux[2]) == 0 for _, aux in parts)   # nothing left out
    np.testing.assert_allclose(sum(out for out, _ in parts) + shared,
                               whole[0], rtol=2e-5, atol=2e-5)
    # a chip's own result holds the shared MLP whole, as the reference's
    np.testing.assert_allclose(parts[1][0] + shared, one[0], rtol=2e-5,
                               atol=2e-5)
    counts = [int(np.sum(np.asarray(routes) // 2 == s)) for s in range(4)]
    assert min(counts) > 0 and sum(counts) == tokens * TOP_K


def test_the_held_heads_scan_is_the_whole_mixers_slice(params):
    """A chip of a pair holds half the Mamba-2 heads: its columns of W_in
    ([z | x] of its heads, B and C whole, its heads' steps), its taps, its
    A, D and step biases. Before the gated norm what it computes is the
    matching slice of the whole mixer's scan output; the norm's mean square
    then runs over the channels held, in the program and in the reference
    alike, where the pair would sum it."""
    heads, width, states = 4, 16, 8
    chans = heads * width
    w = family.reference_weights(params, KINDS)["layers"][0]
    u = jax.random.normal(jax.random.PRNGKey(4), (2, 20, 64), jnp.float32)

    def scan_output(w, heads):
        chans = heads * width
        zx = jnp.einsum("bsd,de->bse", u, w["w_in"])
        mixed = reference.causal_conv(zx[..., chans:2 * chans + 2 * states],
                                      w["conv"], w["conv_b"])
        delta = jax.nn.softplus(zx[..., 2 * chans + 2 * states:] + w["dt_b"])
        return mixed, delta

    def half(of, first):       # heads first, first + 1 of the four
        lanes = np.arange(first * width, (first + 2) * width)
        shared = np.arange(chans, chans + 2 * states)
        cols = np.concatenate([lanes, chans + lanes, chans + shared,
                               2 * chans + 2 * states + np.arange(
                                   first, first + 2)])
        taps = np.concatenate([lanes, shared])
        pick = slice(first, first + 2)
        return dict(of, w_in=of["w_in"][:, cols], conv=of["conv"][taps],
                    conv_b=of["conv_b"][taps], dt_b=of["dt_b"][pick],
                    a_log=of["a_log"][pick], d_skip=of["d_skip"][pick],
                    norm_g=of["norm_g"][lanes], w_out=of["w_out"][lanes])

    with jax.enable_x64(False), jax.default_matmul_precision("highest"):
        mixed, delta = scan_output(w, heads)
        whole = ssd.ssd_scan(mixed[..., :chans], delta, w["a_log"],
                             mixed[..., chans:chans + states],
                             mixed[..., chans + states:], w["d_skip"],
                             chunk=8)
        for first in (0, 2):
            mine = half(w, first)
            m, d = scan_output(mine, 2)
            part = ssd.ssd_scan(m[..., :2 * width], d, mine["a_log"],
                                m[..., 2 * width:2 * width + states],
                                m[..., 2 * width + states:], mine["d_skip"],
                                chunk=8)
            np.testing.assert_allclose(
                part, whole[..., first * width:(first + 2) * width],
                rtol=1e-5, atol=1e-5)
            # and the program's mixer on those leaves is the reference's
            lp = {"ssd_w_in": mine["w_in"][:, :-2],
                  "ssd_w_dt": mine["w_in"][:, -2:], "ssd_conv": mine["conv"],
                  "ssd_conv_bias": mine["conv_b"],
                  "ssd_dt_bias": mine["dt_b"], "ssd_a_log": mine["a_log"],
                  "ssd_d_skip": mine["d_skip"],
                  "ssd_norm_scale": mine["norm_g"],
                  "ssd_w_out": mine["w_out"]}
            got, _ = mixers.MIXERS["mamba2"].apply(
                u, lp, dataclasses.replace(CFG, ssd_heads=2), None, {}, 0)
            np.testing.assert_allclose(got, reference.mamba2(u, mine),
                                       rtol=2e-4, atol=2e-5)


# --------------------------------------------------------- the multipliers

def test_the_multipliers_default_to_no_change():
    """(1, 1, none, 1): a configuration that states none lowers to the
    program it lowered to before they existed (`tests/test_step_scopes.py`
    holds the text), and `score_scale` is YaRN's alone."""
    plain = tfm.TransformerConfig()
    assert (plain.embed_scale, plain.residual_scale, plain.attn_scale,
            plain.logit_scale) == (1.0, 1.0, None, 1.0)
    assert plain.score_scale is None
    assert CFG.score_scale == reference.ATTENTION_MULTIPLIER
    yarn = tfm.Yarn(factor=40.0, original_max=4096, mscale=0.707,
                    mscale_all_dim=0.707)
    mla = dataclasses.replace(plain, attention="mla", qk_nope_dim=128,
                              qk_rope_dim=64, yarn=yarn)
    assert mla.score_scale == 192 ** -0.5 * yarn.score_factor
    assert dataclasses.replace(mla, attn_scale=0.5).score_scale \
        == 0.5 * yarn.score_factor
    x = jnp.ones((2, 3))
    assert tfm._scaled(x, 1) is x and tfm._scaled(x, 1.0) is x


@pytest.mark.parametrize("field, fault", [
    ("embed_scale", None), ("residual_scale", "unit_residual"),
    ("attn_scale", "sqrt_scale"), ("logit_scale", "unscaled_logits")])
def test_each_multiplier_is_in_the_program(params, their_logits, field,
                                           fault):
    """Without one multiplier the program's logits are the reference's with
    the matching fault (the embedding's has none: they just differ)."""
    tokens, _ = FAMILY.batch
    default = tfm.TransformerConfig.__dataclass_fields__[field].default
    cfg = dataclasses.replace(CFG, **{field: default})
    with jax.enable_x64(False):
        got = programs.forward(cfg)(params, tokens)
        assert float(jnp.max(jnp.abs(got - their_logits))) > 1e-2
        if fault:
            want = reference.forward(
                family.reference_weights(params, KINDS), tokens, KINDS,
                TOP_K, FIRST, fault=fault)
            np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-3)
