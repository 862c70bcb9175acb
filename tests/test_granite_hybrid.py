"""The Granite 4.0-H block of `models/transformer.py` (a pattern of Mamba-2
state-space layers and one attention layer without positions over fewer key
and value heads than query heads, every layer followed by SiLU-gated experts
with a renormalised top-k, of which a share is held, beside a shared MLP, a
tied head, and the four scalar multipliers) against the plain reference
`benchmark/reference/granite_hybrid.py`, at a small size in float32: the
mixer alone, logits, loss and every leaf's gradient, `attn` "local" and
"flash"; every planted fault refused by the family's limits; the shares of
the experts adding up to the uncut layer with the shared MLP counted once,
and the held heads' scan output the matching slice of the whole mixer's;
the multipliers' defaults; and what `validate_cfg_for_mesh` refuses. (Loss,
gradients, `dp` = 2, the train step and remat:
`tests/test_granite_hybrid_grads.py`; the scopes of the compiled step:
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
from horovod_tpu.common.exceptions import HorovodTpuError
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
ATTNS = ("local", "flash")


def _data(batch=2, seq=SEQ):
    return programs.data(CFG.vocab, batch, seq)


#: what `_lively` multiplies the drawn leaves by
_LOUDER = {"wq": 16.0, "wo": 4.0, "we2": 4.0, "ssd_w_out": 2.0}


def _lively(params):
    """`init`'s tree with the leaves it draws as ones moved (the norms'
    scales, D), and the parts whose faults are planted made loud enough to
    show at this size: scores of order one under the 1/128 multiplier, the
    attention layer's, the routed experts' and the scans' outputs a larger
    share of the residual stream."""
    keys = iter(jax.random.split(jax.random.PRNGKey(11), 64))

    def moved(path, leaf):
        name = jax.tree_util.keystr(path)
        if name.endswith(("_scale']", "ssd_d_skip']")):
            return leaf * (1 + 0.3 * jax.random.normal(next(keys),
                                                       leaf.shape))
        return leaf * _LOUDER.get(path[-1].key, 1.0)

    return jax.tree_util.tree_map_with_path(moved, params)


@pytest.fixture(scope="module")
def params():
    with jax.enable_x64(False):
        return _lively(programs.init(CFG))


@pytest.fixture(scope="module")
def logits(params):
    """The program's logits for `_data()`'s tokens, once."""
    with jax.enable_x64(False):
        return programs.forward(CFG)(params, _data()[0])


@pytest.fixture(scope="module")
def their_logits(params):
    with jax.enable_x64(False):
        return reference.forward(family.reference_weights(params, KINDS),
                                 _data()[0], KINDS, TOP_K, FIRST)


@pytest.fixture(scope="module")
def sound(params, logits):
    """The family's comparison of `logits` with the sound reference."""
    with jax.enable_x64(False):
        return family.compare(params, _data()[0], logits, KINDS, TOP_K,
                              FIRST)


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


@pytest.mark.parametrize("attn", ATTNS)
def test_logits_equal_the_references(params, their_logits, attn):
    with jax.enable_x64(False):
        got = programs.forward(dataclasses.replace(CFG, attn=attn))(
            params, _data()[0])
    np.testing.assert_allclose(got, their_logits, atol=2e-5, rtol=2e-4)


# --------------------------------------------------------------- the limits

@pytest.mark.parametrize("fault", reference.FAULTS)
def test_the_limits_refuse_a_planted_fault(params, logits, sound, fault):
    """The program's logits against the reference computed with one
    mechanism wrong: by one of the family's limits it is not correct, and
    against the sound reference it is, with room."""
    tokens, _ = _data()
    with jax.enable_x64(False):
        wrong = family.compare(params, tokens, logits, KINDS, TOP_K, FIRST,
                               fault=fault)
    assert all(family.within(*(float(x) for x in sound[:3])))
    assert float(sound[0]) < 1e-5
    assert not all(family.within(*(float(x) for x in wrong[:3]))), \
        [float(x) for x in wrong[:3]]
    with pytest.raises(ValueError, match="choose from"):
        reference.final_hidden(family.reference_weights(params, KINDS),
                               tokens, KINDS, TOP_K, FIRST,
                               fault="no_such_fault")


@pytest.mark.parametrize("operands", [jnp.float8_e4m3fn, jnp.float8_e5m2],
                         ids=["e4m3", "e5m2"])
def test_the_limits_refuse_an_8_bit_float(params, logits, operands):
    tokens, _ = _data()
    with jax.enable_x64(False):
        rms, got, want, _ = family.compare(
            params, tokens, logits, KINDS, TOP_K, FIRST, operands=operands)
    assert not all(family.within(float(rms), float(got), float(want)))


def test_the_familys_comparison_reads_zero_for_the_reference(params):
    """`family.compare` (the reference's head a block of tokens at a time)
    against the reference's whole forward pass and its blockwise loss; its
    count of the held experts' rows against the routes themselves."""
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
    assert rows.shape == (4, 2)
    assert [int(np.sum(np.asarray(routes) == FIRST + e)) for e in (0, 1)] \
        == [int(rows[:, e].sum()) for e in (0, 1)]


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
        found = family.check_logits(params, _data()[0], logits)
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
    tokens, _ = _data()
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


# -------------------------------------------------------------- refusals

REFUSED = [
    (dict(sp=2), {"attn": "local"},
     "state-space dual layers require sp=1"),
    (dict(tp=2), {}, "state-space dual layers require tp=1"),
    (dict(pp=2), {"microbatches": 2},
     "state-space dual layers require pp=1"),
    ({}, {"ssd_heads": 0}, "'mamba2' layers need ssd_heads > 0"),
    ({}, {"attn": "ring"}, "cannot run them|needs attn"),
    ({}, {"layer_pattern": KINDS[:3] + ("mamba",)}, "names the kind 'mamba'"),
    ({}, {"n_layers": 6}, "no whole number of periods"),
]


@pytest.mark.parametrize("mesh, changed, message", REFUSED)
def test_validate_refuses_by_name(mesh, changed, message):
    cfg = dataclasses.replace(CFG, **changed)
    with pytest.raises(HorovodTpuError, match=message):
        tfm.validate_cfg_for_mesh(cfg, mesh_of(**mesh))


def test_validate_accepts_the_model_where_it_runs():
    tfm.validate_cfg_for_mesh(CFG, mesh_of())
    tfm.validate_cfg_for_mesh(CFG, mesh_of(dp=2))
    tfm.validate_cfg_for_mesh(dataclasses.replace(CFG, attn="flash"),
                              mesh_of(dp=4))
