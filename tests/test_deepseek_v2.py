"""The DeepSeek-V2 block of `models/transformer.py` (latent attention with
keys wider than values, YaRN, a leading dense gated MLP, shared experts and a
share of the routed experts with a per-sequence balance loss) against the
plain reference `benchmark/reference/deepseek_v2.py`, at a small size in
float32; the flash kernels at unequal widths; the share of `parallel/moe.py`
and its row buffer; and what is refused."""

import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchmark.families import deepseek_v2 as family
from benchmark.reference import deepseek_v2 as reference
from horovod_tpu.common.exceptions import HorovodTpuError
from horovod_tpu.models import mixers, transformer as tfm
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops.grouped_matmul import ROW_TILE
from horovod_tpu.parallel import MeshSpec, build_mesh, moe, moe_ffn
from horovod_tpu.parallel.ring_attention import blockwise_attention_reference

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


def mesh_of(**sizes):
    spec = MeshSpec(**sizes)
    return build_mesh(spec, jax.devices()[:spec.total])


def _data(batch=4, seq=32, vocab=CFG.vocab):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                vocab, jnp.int32)
    return tokens, jnp.roll(tokens, -1, axis=1)


@pytest.fixture(scope="module")
def params():
    return tfm.init(jax.random.PRNGKey(0), CFG)


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
    gpt = tfm.init(jax.random.PRNGKey(0), tfm.TransformerConfig(
        vocab=32, d_model=16, n_heads=2, d_ff=32, n_layers=1, max_seq=8))
    assert sorted(gpt["layers"]) == sorted([
        "ln1_scale", "ln1_bias", "wq", "wk", "wv", "wo", "ln2_scale",
        "ln2_bias", "w1", "b1", "w2", "b2"])
    assert "dense_layers" not in gpt


def test_logits_and_loss_match_the_reference(params):
    tokens, targets = _data()
    logits = jax.jit(tfm.build_forward(CFG, mesh_of()))(params, tokens)
    weights = family.reference_weights(params)
    want = reference.logits(weights, tokens, TOP_K, first_expert=FIRST)
    assert logits.shape == (4, 32, CFG.vocab)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    loss, _ = jax.jit(tfm.build_loss_and_grads(CFG, mesh_of()))(
        params, tokens, targets)
    want_loss = reference.loss(weights, tokens, targets, TOP_K,
                               first_expert=FIRST)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    # the balance term is in it, per sequence
    bare = reference.next_token_loss(want, targets)
    assert float(want_loss) - float(bare) > 1e-4


@pytest.mark.parametrize("sizes", [{}, {"dp": 2}, {"dp": 2, "tp": 2}],
                         ids=["one-rank", "dp2", "dp2-tp2"])
def test_every_gradient_leaf_matches_the_reference(params, sizes):
    """`build_loss_and_grads` against `jax.grad` of the reference's loss, the
    balance term included: it is each sequence's own, so a data-parallel
    mesh changes nothing. On a mesh that reduces, both stacks' gradients go
    through `grad_reduce.scattered_in_backward`."""
    tokens, targets = _data()
    mesh = mesh_of(**sizes)
    tfm.validate_cfg_for_mesh(CFG, mesh)
    loss, grads = jax.jit(tfm.build_loss_and_grads(CFG, mesh))(
        tfm.shard_params(params, CFG, mesh), tokens, targets)

    def ref_loss(p):
        return reference.loss(family.reference_weights(p), tokens, targets,
                              TOP_K, first_expert=FIRST)

    want_loss, want = jax.value_and_grad(ref_loss)(params)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    assert len(flat) == 10 + 14 + 3
    for (path, got), ref in zip(flat, jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(ref))) + 1e-12
        assert float(jnp.max(jnp.abs(got - ref))) <= 3e-5 * scale, \
            jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(ref))) > 0, jax.tree_util.keystr(path)


def test_remat_changes_no_gradient(params):
    tokens, targets = _data()
    plain = jax.jit(tfm.build_loss_and_grads(CFG, mesh_of()))(
        params, tokens, targets)
    remat = jax.jit(tfm.build_loss_and_grads(
        dataclasses.replace(CFG, remat=True), mesh_of()))(
            params, tokens, targets)
    for a, b in zip(jax.tree_util.tree_leaves(plain),
                    jax.tree_util.tree_leaves(remat)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


def test_attn_local_takes_the_plain_path_and_agrees_with_flash(params):
    tokens, _ = _data()
    flash = jax.jit(tfm.build_forward(CFG, mesh_of()))(params, tokens)
    local = jax.jit(tfm.build_forward(
        dataclasses.replace(CFG, attn="local"), mesh_of()))(params, tokens)
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
        jax.jit(tfm.build_forward(cfg, mesh_of()))(params, tokens)


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
        params):
    tokens, targets = _data()
    one, _ = jax.jit(tfm.build_loss_and_grads(CFG, mesh_of()))(
        params, tokens, targets)
    two, _ = jax.jit(tfm.build_loss_and_grads(
        dataclasses.replace(CFG, microbatches=2), mesh_of()))(
            params, tokens, targets)
    np.testing.assert_allclose(float(two), float(one), rtol=1e-5)


def test_a_share_across_ranks_is_refused():
    with pytest.raises(HorovodTpuError, match="experts_held"):
        tfm.validate_cfg_for_mesh(CFG, mesh_of(ep=2))
    x = jnp.zeros((16, 8), jnp.float32)
    router, up, down, gate = _experts(jax.random.PRNGKey(2), 8, 8, 16)
    with pytest.raises(HorovodTpuError, match="across ranks"):
        _run(x, router, up[:2], down[:2], gate[:2], 2, ep=2)
    with pytest.raises(HorovodTpuError, match="first_expert"):
        _run(x, router, up[:4], down[:4], gate[:4], 2, ep=1, first=5)


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


# ------------------------------------------------- kernels, unequal widths

def _qkv(dqk, dv, seq=256, heads=2, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (1, heads, seq, dqk), dtype)
    k = jax.random.normal(ks[1], (1, heads, seq, dqk), dtype)
    v = jax.random.normal(ks[2], (1, heads, seq, dv), dtype)
    do = jax.random.normal(ks[3], (1, heads, seq, dv), dtype)
    return q, k, v, do


@pytest.mark.parametrize("dqk,dv,block", [(24, 16, None), (192, 128, None),
                                          (48, 32, 128), (16, 24, None)],
                         ids=["24-16", "192-128", "48-32-tiled", "16-24"])
def test_flash_kernels_at_unequal_widths_match_plain_attention(dqk, dv,
                                                               block):
    """Forward and all three gradients (interpreted); `block` 128 walks the
    diagonal block in strips, as the 1,024 blocks of S = 4,096 do."""
    q, k, v, do = _qkv(dqk, dv, seq=512 if block else 256)
    scale = 1.5896 * dqk ** -0.5

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, scale=scale,
                                  block_q=block and 4 * block,
                                  block_k=block and 4 * block)

    def plain(q, k, v):
        return blockwise_attention_reference(q, k, v, causal=True,
                                             scale=scale)

    out, vjp = jax.vjp(flash, q, k, v)
    want, want_vjp = jax.vjp(plain, q, k, v)
    assert out.shape == v.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4,
                               atol=2e-4)
    for name, got, ref in zip("qkv", vjp(do), want_vjp(do)):
        assert got.shape == ref.shape, name
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=5e-4, atol=5e-4, err_msg=name)


def test_flash_kernels_at_equal_widths_are_unchanged():
    """One width: no compiler parameter is added and the result is the
    plain one; the chunk API (ring hops) takes unequal widths too."""
    assert fa._compiler_params(128, 128) == {}
    assert fa._compiler_params(64, 128) == {}
    wide = fa._compiler_params(192, 128)["compiler_params"]
    assert wide.vmem_limit_bytes == 32 * 2 ** 20
    q, k, v, _ = _qkv(32, 32)
    np.testing.assert_allclose(
        np.asarray(fa.flash_attention(q, k, v, causal=True)),
        np.asarray(blockwise_attention_reference(q, k, v, causal=True)),
        rtol=2e-4, atol=2e-4)
    q, k, v, _ = _qkv(24, 16)
    o, lse = fa.flash_attention_chunk(q, k, v, causal=True)
    assert o.shape == v.shape and lse.shape == q.shape[:3]
    np.testing.assert_allclose(
        np.asarray(o),
        np.asarray(blockwise_attention_reference(q, k, v, causal=True)),
        rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------- the share

def _experts(key, n_experts, d, f):
    ks = jax.random.split(key, 4)
    router = jax.random.normal(ks[0], (d, n_experts), jnp.float32)
    up = jax.random.normal(ks[1], (n_experts, d, f), jnp.float32) / d ** 0.5
    down = jax.random.normal(ks[2], (n_experts, f, d), jnp.float32) / f ** 0.5
    gate = jax.random.normal(ks[3], (n_experts, d, f), jnp.float32) / d ** 0.5
    return router, up, down, gate


def _run(x, router, up, down, gate, top_k, ep=1, first=0, sequences=0,
         whole=False):
    spec = P("ep")

    def local(xx, r, u, d, g):
        out, aux, experts = moe_ffn(xx, r, u, d, g, top_k=top_k,
                                    axis_name="ep", first_expert=first,
                                    sequences=sequences)
        return (out, aux, experts) if whole else out

    return jax.jit(jax.shard_map(
        local, mesh=mesh_of(ep=ep), in_specs=(spec, P(), spec, spec, spec),
        out_specs=(spec, P(), spec) if whole else spec,
        check_vma=False))(x, router, up, down, gate)


def test_the_eight_shares_and_the_shared_experts_add_up_to_the_uncut_layer():
    """The share test: at a small size the routed parts that the eight
    shares of a layer give, with what every chip computes alike (the shared
    experts) counted once, add up to what the uncut reference gives for the
    whole layer. Through `_layer`'s own code: each share is the block with
    `experts_held=1` of 8 and `first_expert=i`."""
    wide = tfm.init(jax.random.PRNGKey(4), WHOLE)
    lp = {k: v[0] for k, v in wide["layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, CFG.d_model),
                          jnp.float32)
    w = family.reference_weights(wide)["layers"][-2]
    uncut, _, routes = reference.moe(x, w, TOP_K)
    shared = reference.gated_mlp(x, w["ws_gate"], w["ws_up"], w["ws_down"])

    def ffn(cfg, leaves):
        """The block's FFN half alone: the layer with attention zeroed and
        the norm's scale at one gives x + f(x / rms)."""
        def run(h):
            return moe.moe_ffn(
                h.reshape(-1, CFG.d_model), leaves["router"], leaves["we1"],
                leaves["we2"], leaves["we_gate"], top_k=TOP_K,
                first_expert=cfg.first_expert,
                sequences=2)[0].reshape(h.shape)
        return jax.jit(jax.shard_map(run, mesh=mesh_of(), in_specs=P(),
                                     out_specs=P(), check_vma=False))(x)

    total = jnp.zeros_like(x)
    for i in range(8):
        part = dict(lp, **{k: lp[k][i:i + 1]
                           for k in ("we1", "we2", "we_gate")})
        total = total + ffn(dataclasses.replace(CFG, experts_held=1,
                                                first_expert=i), part)
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(uncut), rtol=2e-4, atol=2e-4)
    # and the uncut program agrees with the sum of its shares
    np.testing.assert_allclose(np.asarray(ffn(WHOLE, lp)), np.asarray(total),
                               rtol=2e-4, atol=2e-4)
    assert routes.shape == (2, 16, TOP_K)


def test_a_share_matches_the_reference_and_takes_no_gradient_elsewhere():
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 16, 8), jnp.float32)
    router, up, down, gate = _experts(jax.random.PRNGKey(9), 8, 8, 16)
    w = {"router": router, "w_gate": gate[3:6], "w_up": up[3:6],
         "w_down": down[3:6], "ws_gate": jnp.zeros((8, 4)),
         "ws_up": jnp.zeros((8, 4)), "ws_down": jnp.zeros((4, 8))}
    want, balance, routes = reference.moe(x, w, 2, first_expert=3)
    out, aux, experts = _run(x.reshape(32, 8), router, up[3:6], down[3:6],
                             gate[3:6], 2, first=3, sequences=2, whole=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want.reshape(32, 8)),
                               rtol=2e-4, atol=2e-4)
    assert np.array_equal(np.sort(np.asarray(experts), axis=-1),
                          np.sort(np.asarray(routes.reshape(32, 2)), axis=-1))
    # [balance over all 8 experts per sequence, router z, pairs left out]
    assert aux.shape == (3,) and float(aux[2]) == 0.0
    np.testing.assert_allclose(float(aux[0]), float(balance), rtol=1e-5)
    # a token none of whose experts is held gets nothing from this share
    held = np.isin(np.asarray(experts), [3, 4, 5]).any(axis=-1)
    assert 0 < held.sum() < 32
    assert np.all(np.asarray(out)[~held] == 0.0)
    assert np.all(np.abs(np.asarray(out)[held]).max(axis=-1) > 0)

    def total(r):
        return jnp.sum(_run(x.reshape(32, 8), r, up[3:6], down[3:6],
                            gate[3:6], 2, first=3) ** 2)

    def ref_total(r):
        return jnp.sum(reference.moe(x, dict(w, router=r), 2,
                                     first_expert=3)[0] ** 2)

    np.testing.assert_allclose(np.asarray(jax.grad(total)(router)),
                               np.asarray(jax.grad(ref_total)(router)),
                               rtol=1e-3, atol=1e-5)


def test_the_balance_loss_is_each_sequences_own():
    x = jax.random.normal(jax.random.PRNGKey(10), (4 * 16, 8), jnp.float32)
    router, up, down, gate = _experts(jax.random.PRNGKey(9), 8, 8, 16)
    _, _, counts, flat = moe.route(x, router, 2)
    _, _, seq_counts, per_seq = moe.route(x, router, 2, 4)
    assert np.array_equal(np.asarray(counts), np.asarray(seq_counts))
    probs = jax.nn.softmax(x @ router, axis=-1).reshape(4, 16, 8)
    chosen = jax.lax.top_k(probs, 2)[1]
    by_hand = np.mean([
        sum(float(np.sum(np.asarray(chosen[s]) == e)) * 8 / (2 * 16)
            * float(probs[s, :, e].mean()) for e in range(8))
        for s in range(4)])
    assert float(per_seq[0]) == pytest.approx(by_hand, rel=1e-5)
    assert float(per_seq[1]) == pytest.approx(float(flat[1]), rel=1e-6)
    assert abs(float(per_seq[0]) - float(flat[0])) > 1e-4


def test_every_token_to_held_experts_and_none_is_dropped():
    """Dropless: under a routing that sends every pair to the two held
    experts the buffer (all T*k rows at this size) takes them all."""
    x = jax.random.normal(jax.random.PRNGKey(3), (32, 8), jnp.float32)
    _, up, down, gate = _experts(jax.random.PRNGKey(4), 8, 8, 16)
    x = x.at[:, 0].set(1.0)
    router = jnp.zeros((8, 8), jnp.float32).at[0, 5].set(30.0) \
        .at[0, 6].set(29.0)
    assert moe.held_rows(64, 2, 8) == 64
    out, aux, experts = _run(x, router, up[5:7], down[5:7], gate[5:7], 2,
                             first=5, whole=True)
    assert np.all(np.sort(np.asarray(experts), axis=-1) == [5, 6])
    assert float(aux[2]) == 0.0
    w = {"router": router, "w_gate": gate[5:7], "w_up": up[5:7],
         "w_down": down[5:7], "ws_gate": jnp.zeros((8, 4)),
         "ws_up": jnp.zeros((8, 4)), "ws_down": jnp.zeros((4, 8))}
    want = reference.moe(x[None], w, 2, first_expert=5)[0][0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4,
                               atol=2e-4)
    assert float(jnp.min(jnp.max(jnp.abs(out), axis=-1))) > 0.0


def test_the_row_buffer_is_bounded_and_counts_what_it_leaves_out():
    """Twice the even share in whole row tiles, never more than all pairs.
    Under an even routing it changes nothing; when every pair goes to the one
    held expert the pairs beyond it are counted, the others still computed,
    and a train step says so with a NaN loss."""
    assert moe.held_rows(8192 * 6, 8, 64) == 12288 == 24 * moe.ROW_TILE
    assert moe.held_rows(4096, 1, 8) == 1024
    assert moe.held_rows(4096, 8, 8) == 4096
    assert moe.held_rows(100, 1, 8) == 100
    x = jax.random.normal(jax.random.PRNGKey(3), (2048, 8), jnp.float32)
    router, up, down, gate = _experts(jax.random.PRNGKey(4), 8, 8, 16)
    w = {"router": router, "w_gate": gate[1:2], "w_up": up[1:2],
         "w_down": down[1:2], "ws_gate": jnp.zeros((8, 4)),
         "ws_up": jnp.zeros((8, 4)), "ws_down": jnp.zeros((4, 8))}
    out, aux, _ = _run(x, router, up[1:2], down[1:2], gate[1:2], 2, first=1,
                       whole=True)
    assert float(aux[2]) == 0.0
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(reference.moe(x[None], w, 2, first_expert=1)[0][0]),
        rtol=2e-4, atol=2e-4)
    # every token's first choice is expert 1: 2,048 held pairs, 1,024 rows
    forced = router.at[:, 1].set(0.0)
    xs = x.at[:, 0].set(1.0)
    forced = forced.at[0, 1].set(30.0)
    out, aux, experts = _run(xs, forced, up[1:2], down[1:2], gate[1:2], 2,
                             first=1, whole=True)
    assert np.all(np.asarray(experts)[:, 0] == 1)
    assert float(aux[2]) == 1024.0
    want = reference.moe(xs[None], dict(w, router=forced), 2,
                         first_expert=1)[0][0]
    served = np.abs(np.asarray(out)).max(axis=-1) > 0
    assert served.sum() == 1024      # in token order: the first 1,024
    assert served[:1024].all()
    np.testing.assert_allclose(np.asarray(out)[:1024],
                               np.asarray(want)[:1024], rtol=2e-4, atol=2e-4)
    assert np.all(np.isfinite(np.asarray(out)))


@pytest.mark.parametrize("to_held", [None, 0.0, 30.0],
                         ids=["as-routed", "none-held", "all-held"])
def test_the_grouped_products_take_the_whole_buffer(to_held, monkeypatch):
    """A step's work does not follow the routing: the rows that every
    grouped matmul of a share multiplies add up to the row buffer, the free rows in the
    last held expert's group, and what they add is nothing (the result is
    the reference's, the gradients are finite and the free rows' are 0)."""
    x = jax.random.normal(jax.random.PRNGKey(3), (512, 8), jnp.float32)
    router, up, down, gate = _experts(jax.random.PRNGKey(4), 8, 8, 16)
    if to_held is not None:
        # experts 2 and 3 are held: every token's logits for them are equal
        # and the smallest (no pair held) or the largest (every pair held)
        x = x.at[:, 0].set(1.0)
        router = router.at[:, 2:4].set(0.0).at[0, 2:4].set(
            to_held if to_held else -30.0)
    room = moe.held_rows(1024, 2, 8)
    assert room == 512
    seen = []
    grouped_matmul = moe.grouped_matmul

    def recording(rows, w, plan):
        edge = min(ROW_TILE, rows.shape[0])

        def rows_multiplied(first, end, tile, count):
            # every visit made multiplies its group's rows in its row tile
            own = np.clip(np.minimum(end, (tile + 1) * edge)
                          - np.maximum(first, tile * edge), 0, None)
            seen.append(int(own[:int(count[0])].sum()))

        jax.debug.callback(rows_multiplied, plan.first_row, plan.end_row,
                           plan.tile, plan.count)
        return grouped_matmul(rows, w, plan)

    monkeypatch.setattr(moe, "grouped_matmul", recording)

    def loss(xx, u, d, g):
        out, aux, _ = _run(xx, router, u, d, g, 2, first=2, whole=True)
        return jnp.sum(out ** 2), (out, aux)

    (_, (out, aux)), grads = jax.value_and_grad(loss, (0, 1, 2, 3),
                                                has_aux=True)(
        x, up[2:4], down[2:4], gate[2:4])
    jax.effects_barrier()
    assert len(seen) >= 3 and all(n == room for n in seen)
    held = {None: None, 0.0: 0, 30.0: 1024}[to_held]
    if held is not None:
        assert float(aux[2]) == max(0, held - room)
    if held == 0:
        assert not np.any(np.asarray(out))
        assert not any(np.any(np.asarray(g)) for g in grads)
    if held != 1024:
        w = {"router": router, "w_gate": gate[2:4], "w_up": up[2:4],
             "w_down": down[2:4], "ws_gate": jnp.zeros((8, 4)),
             "ws_up": jnp.zeros((8, 4)), "ws_down": jnp.zeros((4, 8))}
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(reference.moe(x[None], w, 2, first_expert=2)[0][0]),
            rtol=2e-4, atol=2e-4)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in grads)


def test_a_step_that_leaves_a_held_pair_out_counts_it(monkeypatch):
    """The loss stays a loss: the count of the held pairs left out of the
    row buffers is a result of its own, of `build_loss_and_grads` and of the
    train step alike, and 0 while the pairs fit."""
    import optax
    cfg = dataclasses.replace(CFG, n_layers=2, load_balance_coef=0.001)
    tokens, targets = _data()
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    sound, grads, counts = jax.jit(tfm.build_loss_and_grads(
        cfg, mesh_of(), metrics=True))(params, tokens, targets)
    assert int(counts["experts_dropped"]) == 0
    plain, plain_grads = jax.jit(tfm.build_loss_and_grads(cfg, mesh_of()))(
        params, tokens, targets)
    assert float(plain) == float(sound)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(plain_grads)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # room for 8 rows where the one expert layer's held experts get more
    monkeypatch.setattr(moe, "held_rows", lambda *sizes: 8)
    routes = np.asarray(reference.forward(
        family.reference_weights(params), tokens, TOP_K,
        first_expert=FIRST)[2])
    held = int(np.sum((routes >= FIRST) & (routes < FIRST + 2)))
    assert held > 8
    loss, grads, counts = jax.jit(tfm.build_loss_and_grads(
        cfg, mesh_of(), metrics=True))(params, tokens, targets)
    assert int(counts["experts_dropped"]) == held - 8
    assert np.isfinite(float(loss)) and float(loss) != float(sound)
    assert all(np.all(np.isfinite(np.asarray(g)))
               for g in jax.tree_util.tree_leaves(grads))
    opt = optax.sgd(0.1)
    step = tfm.build_train_step(cfg, mesh_of(), opt, metrics=True)
    _, _, step_loss, step_counts = step(
        params, tfm.init_opt_state(opt, params, mesh_of()), tokens, targets)
    assert float(step_loss) == float(loss)
    assert int(step_counts["experts_dropped"]) == held - 8


def test_two_ranks_with_every_expert_held_equal_the_parent_bit_for_bit():
    """`ep` = 2, full coverage: outputs, auxiliary terms, routes and every
    gradient equal what the parent of PR 30 (commit 2baf953) gave for the
    same seeds on this CPU mesh, in bf16, and in float32 but for the order
    its grouped matmul added in (tests/fixtures/moe_ep2_parent_pr29.npz)."""
    golden = np.load(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "fixtures", "moe_ep2_parent_pr29.npz"))

    def run(dtype):
        ks = jax.random.split(jax.random.PRNGKey(11), 5)
        d, f, e, t, k = 16, 32, 8, 64, 2
        x = jax.random.normal(ks[0], (t, d), jnp.float32).astype(dtype)
        router = jax.random.normal(ks[1], (d, e), jnp.float32).astype(dtype)
        up = (jax.random.normal(ks[2], (e, d, f), jnp.float32)
              / d ** 0.5).astype(dtype)
        down = (jax.random.normal(ks[3], (e, f, d), jnp.float32)
                / f ** 0.5).astype(dtype)
        gate = (jax.random.normal(ks[4], (e, d, f), jnp.float32)
                / d ** 0.5).astype(dtype)
        spec = P("ep")

        def local(xx, r, u, dn, g):
            out, aux, experts = moe_ffn(xx, r, u, dn, g, top_k=k,
                                        axis_name="ep",
                                        capacity_factor=1.25)
            return out, aux[None], experts

        sharded = jax.shard_map(
            local, mesh=mesh_of(ep=2), in_specs=(spec, P(), spec, spec, spec),
            out_specs=(spec, spec, spec), check_vma=False)

        def loss(args):
            out, aux, _ = sharded(*args)
            return jnp.sum(jnp.sin(out.astype(jnp.float32))) + jnp.sum(aux)

        args = (x, router, up, down, gate)
        return (*jax.jit(sharded)(*args), *jax.jit(jax.grad(loss))(args))

    for i, got in enumerate(run(jnp.bfloat16)):
        assert np.array_equal(np.asarray(got.astype(jnp.float32)),
                              golden[f"bf16_{i}"]), i
    # float32: the parent's grouped matmul was `lax.ragged_dot`, the
    # kernels of ops/grouped_matmul.py add in another order (4e-7 here)
    for i, got in enumerate(run(jnp.float32)):
        want = golden[f"f32_{i}"]
        np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                                   atol=2e-6 * np.abs(want).max(),
                                   err_msg=str(i))


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


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_limits_refuse_a_planted_fault(fault, monkeypatch):
    """The program in float32 is correct by both limits. Without the shared
    experts, without YaRN's m^2 on the scores or with the shared rotary key
    left unrotated it fails at least one."""
    tiny = family.transformer_config(_tiny_config())
    tokens, _ = _data(batch=4, seq=64, vocab=512)
    weights = tfm.init(jax.random.PRNGKey(3), tiny)
    sound = jax.jit(tfm.build_forward(tiny, mesh_of()))(weights, tokens)
    assert family.check_logits(weights, tokens, sound)["ok"]
    cfg, used = FAULTS[fault](tiny, weights, monkeypatch)
    logits = jax.jit(tfm.build_forward(cfg, mesh_of()))(used, tokens)
    verdict = family.check_logits(weights, tokens, logits)
    assert not verdict["ok"], verdict


def test_the_logits_limit_refuses_an_8_bit_float():
    tiny = family.transformer_config(_tiny_config())
    tokens, _ = _data(batch=4, seq=64, vocab=512)
    weights = tfm.init(jax.random.PRNGKey(3), tiny)
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
