"""The OLMoE block of `models/transformer.py` (RMSNorm, rotary positions,
QK-norm, top-k of E gated-SiLU experts, auxiliary losses) against the plain
reference `benchmark/reference/olmoe.py`, at a small size in float32: the
family's statement for `tests/family_cases.py` (`FAMILY`) and of the shared
cases the loss and every leaf's gradient of one rank as the cell runs it
(`attn` "flash" under remat); the meshes, where each shard of the batch takes
the auxiliary terms over its own tokens, so that two ranks are held to the
reference given the same shards and not to one rank; and the routing of
`parallel/moe.py`: dropless on one rank, capacity-bounded across ranks."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import family as programs
from benchmark.families import olmoe as family
from benchmark.reference import olmoe as reference
from family_cases import (  # noqa: F401  (the fixtures, the shared tests)
    Family, ours, params, pytest_generate_tests, stated, theirs,
    test_every_leafs_gradient_equals_the_references,
    test_loss_equals_the_references)
from horovod_tpu.models import transformer as tfm
from family import mesh_of
from horovod_tpu.parallel import moe, moe_ffn

TOP_K = 2
CFG = tfm.TransformerConfig(
    vocab=64, d_model=32, n_heads=4, d_ff=16, n_layers=2, max_seq=32,
    num_experts=8, experts_per_token=TOP_K, load_balance_coef=0.01,
    router_z_coef=0.001, norm="rmsnorm", positions="rope", qk_norm=True,
    mlp="swiglu", attn="local", dtype=jnp.float32)


#: the cell's algorithm and remat (the program's default policy); the
#: gradients to a leaf's largest entry, as they were held before the shared
#: cases
FAMILY = Family(
    cfg=CFG, timed=dataclasses.replace(CFG, attn="flash", remat=True),
    family=family, reference=reference, weights=(), args=(TOP_K,),
    data=(4, 16), refused=(), leaf_rtol=0, leaf_atol=2e-5)


def _data(batch=4, seq=16):
    return programs.data(CFG.vocab, batch, seq)


def test_the_block_has_the_leaves_the_architecture_has(params):
    layers = params["layers"]
    assert sorted(params) == ["embed", "layers", "lnf_scale", "unembed"]
    assert sorted(layers) == sorted([
        "ln1_scale", "ln2_scale", "wq", "wk", "wv", "wo", "q_scale",
        "k_scale", "router", "we_gate", "we1", "we2"])
    structure = jax.tree_util.tree_structure(params)
    is_leaf = lambda x: isinstance(x, (P, tuple))  # noqa: E731
    assert jax.tree_util.tree_structure(
        tfm.param_specs(CFG), is_leaf=is_leaf) == structure
    assert jax.tree_util.tree_structure(
        tfm.grad_reduce_axes(CFG), is_leaf=is_leaf) == structure


def test_the_expert_layer_routes_as_the_reference_does(params):
    """`moe_ffn` and the reference's `moe` on the same activations and one
    layer's weights: identical routes (the same set of experts for every
    token), output and auxiliary terms."""
    x = jax.random.normal(jax.random.PRNGKey(9), (4, 16, CFG.d_model),
                          jnp.float32)
    layer = family.reference_weights(params)["layers"][0]
    want, want_aux, want_routes = reference.moe(x, layer, TOP_K)
    got, aux, routes = jax.jit(jax.shard_map(
        lambda h: moe_ffn(h, layer["router"], layer["w_up"], layer["w_down"],
                          layer["w_gate"], top_k=TOP_K),
        mesh=mesh_of(), in_specs=P(), out_specs=P(), check_vma=False))(
            x.reshape(-1, CFG.d_model))
    np.testing.assert_array_equal(
        np.sort(np.asarray(routes), axis=-1),
        np.sort(np.asarray(want_routes).reshape(-1, TOP_K), axis=-1))
    np.testing.assert_allclose(np.asarray(got).reshape(x.shape),
                               np.asarray(want), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(aux), np.asarray(want_aux),
                               rtol=1e-5)


def test_logits_and_loss_match_the_reference(params, ours):
    tokens, targets = _data()
    logits = programs.forward(CFG)(params, tokens)
    weights = family.reference_weights(params)
    want, aux, _ = reference.forward(weights, tokens, TOP_K)
    # float32 logits this close imply the same routes; the expert layer's
    # are compared above
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    full = reference.loss(weights, tokens, targets, TOP_K)
    np.testing.assert_allclose(float(ours[0]), float(full), rtol=1e-5)
    # the auxiliary terms are in it: 0.01 x ~1 and 0.001 x ~ln(8)^2
    plain = reference.next_token_loss(want, targets)
    balance, z = np.mean(np.asarray(aux), axis=0)
    assert balance >= 1.0 and z > 1.0
    np.testing.assert_allclose(float(full - plain),
                               0.01 * balance + 0.001 * z, rtol=1e-4)


def test_reference_held_to_given_routes_uses_them(params):
    tokens, _ = _data()
    weights = family.reference_weights(params)
    free, _, routes = reference.forward(weights, tokens, TOP_K)
    held = reference.logits(weights, tokens, TOP_K, routes=routes)
    np.testing.assert_allclose(np.asarray(held), np.asarray(free),
                               rtol=1e-6, atol=1e-6)
    other = (routes + 1) % CFG.num_experts
    moved = reference.logits(weights, tokens, TOP_K, routes=other)
    assert float(jnp.max(jnp.abs(moved - free))) > 1e-3


@pytest.mark.parametrize("sizes", [{"dp": 2}, {"dp": 2, "tp": 2}],
                         ids=["dp2", "dp2-tp2"])
def test_every_gradient_leaf_matches_the_reference(params, sizes):
    """`build_loss_and_grads` against `jax.grad` of the reference's loss,
    auxiliary terms included. On a mesh each shard of the batch takes the
    auxiliary terms over its own tokens and the reference is given the same
    shards. (One rank: the shared cases, leaf by leaf.)"""
    tokens, targets = _data()
    mesh = mesh_of(**sizes)
    tfm.validate_cfg_for_mesh(CFG, mesh)
    loss, grads = programs.loss_and_grads(CFG, **sizes)(
        tfm.shard_params(params, CFG, mesh), tokens, targets)

    def ref_loss(p):
        return reference.loss(family.reference_weights(p), tokens, targets,
                              TOP_K, shards=sizes.get("dp", 1))

    want_loss, want = jax.value_and_grad(ref_loss)(params)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    programs.assert_trees_close(grads, want, rtol=0, atol=1e-17,
                                scaled=2e-5)


def test_pipeline_stages_carry_the_auxiliary_losses(params):
    """With microbatches each microbatch takes the auxiliary terms over its
    own tokens, on one stage or two; every stage adds its own layers'."""
    tokens, targets = _data()
    cfg = dataclasses.replace(CFG, microbatches=2)
    losses = {}
    for pp in (1, 2):
        mesh = mesh_of(pp=pp)
        tfm.validate_cfg_for_mesh(cfg, mesh)
        losses[pp], _ = programs.loss_and_grads(cfg, pp=pp)(
            tfm.shard_params(params, cfg, mesh), tokens, targets)
    np.testing.assert_allclose(float(losses[2]), float(losses[1]), rtol=1e-5)
    want = reference.loss(family.reference_weights(params), tokens, targets,
                          TOP_K, shards=2)
    np.testing.assert_allclose(float(losses[1]), float(want), rtol=1e-5)


def test_a_gated_mlp_without_experts_is_a_dense_one():
    """PR 30: the gated SiLU MLP is no longer an expert only. Without
    experts the block has a dense one, `tp`-sharded like the GELU MLP and
    without its biases (tests/test_deepseek_v2.py runs it)."""
    cfg = dataclasses.replace(CFG, num_experts=0)
    layers = programs.init(cfg, 2)["layers"]
    mlp = {"w_gate", "w1", "w2"}
    assert mlp <= set(layers)
    assert not set(layers) & {"b1", "b2", "router", "we1", "we2", "we_gate"}
    assert layers["w_gate"].shape == layers["w1"].shape == \
        (cfg.n_layers, cfg.d_model, cfg.d_ff)
    for tree in (tfm.param_specs(cfg), tfm.grad_reduce_axes(cfg)):
        assert set(tree["layers"]) == set(layers)
    assert tfm.param_specs(cfg)["layers"]["w_gate"] == \
        tfm.param_specs(cfg)["layers"]["w1"]


# ------------------------------------------------------------ routing

def _experts(key, n_experts, d, f, gated):
    ks = jax.random.split(key, 4)
    router = jax.random.normal(ks[0], (d, n_experts), jnp.float32)
    up = jax.random.normal(ks[1], (n_experts, d, f), jnp.float32) / d ** 0.5
    down = jax.random.normal(ks[2], (n_experts, f, d), jnp.float32) / f ** 0.5
    gate = jax.random.normal(ks[3], (n_experts, d, f), jnp.float32) \
        / d ** 0.5 if gated else None
    return router, up, down, gate


def _oracle(x, router, up, down, gate, top_k, keep=None, *, scored=None,
            renormalise=False, act="silu"):
    """Every expert on every token, summed over the token's top-k with their
    softmax weights; `keep` (T, k) drops pairs. `scored`: what the router
    reads where that is not x; `renormalise`: the k weights over their sum;
    `act`: a gated expert's activation."""
    probs = jax.nn.softmax((x if scored is None else scored) @ router,
                           axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)
    if renormalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    hidden = jnp.einsum("td,edf->tef", x, up)
    hidden = jax.nn.gelu(hidden) if gate is None else \
        getattr(jax.nn, act)(jnp.einsum("td,edf->tef", x, gate)) * hidden
    every = jnp.einsum("tef,efd->ted", hidden, down)
    chosen = jnp.take_along_axis(every, experts[..., None], axis=1)
    if keep is not None:
        weights = weights * keep
    return jnp.sum(chosen * weights[..., None], axis=1), experts


def _run(x, router, up, down, w_gate, top_k, ep, capacity_factor,
         scored=None, **options):
    spec = P("ep")
    gspec = None if w_gate is None else spec
    sspec = None if scored is None else spec
    return jax.jit(jax.shard_map(
        lambda xx, r, u, d, g, s: moe_ffn(
            xx, r, u, d, g, top_k=top_k, axis_name="ep",
            capacity_factor=capacity_factor, router_input=s, **options)[0],
        mesh=mesh_of(ep=ep), in_specs=(spec, P(), spec, spec, gspec, sspec),
        out_specs=spec, check_vma=False))(x, router, up, down, w_gate, scored)


@pytest.mark.parametrize("capacity_factor", [1e-3, 1.25])
def test_one_rank_drops_nothing_when_every_token_takes_one_expert(
        capacity_factor):
    """Dropless: all 32 tokens to expert 5 of 8, and the capacity factor
    means nothing."""
    x = jax.random.normal(jax.random.PRNGKey(3), (32, 8), jnp.float32)
    _, up, down, gate = _experts(jax.random.PRNGKey(4), 8, 8, 16, True)
    router = jnp.zeros((8, 8), jnp.float32)
    x = x.at[:, 0].set(1.0)
    router = router.at[0, 5].set(30.0)
    want, experts = _oracle(x, router, up, down, gate, 1)
    assert np.all(np.asarray(experts) == 5)
    got = _run(x, router, up, down, gate, 1, 1, capacity_factor)
    assert float(jnp.min(jnp.max(jnp.abs(got), axis=-1))) > 0.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


#: SmallThinker's three (PR 38): the k weights renormalised, a ReLU gate,
#: the router reading an input of its own
_THREE = {"renormalise": True, "gate": "relu", "router_input": True}


def _options(options):
    """(`moe_ffn`'s keywords, `_oracle`'s, whether the router has an input
    of its own) of a case's options."""
    options = dict(options)
    own = options.pop("router_input", False)
    theirs = {"act" if k == "gate" else k: v for k, v in options.items()}
    return options, theirs, own


@pytest.mark.parametrize("top_k,gated,options", [
    (1, False, {}), (2, True, {}), (3, True, _THREE)],
    ids=["top1-gelu", "top2-swiglu", "top3-reglu-renormalised-own-input"])
def test_two_ranks_match_the_single_rank_oracle(top_k, gated, options):
    """Across ranks the same router, gate and weights as on one rank: the
    exchange carries rows, and the weights multiply what comes back."""
    ours, theirs, own = _options(options)
    x = jax.random.normal(jax.random.PRNGKey(5), (32, 8), jnp.float32)
    scored = jax.random.normal(jax.random.PRNGKey(9), (32, 8), jnp.float32) \
        if own else None
    weights = _experts(jax.random.PRNGKey(6), 8, 8, 16, gated)
    want, _ = _oracle(x, *weights, top_k, scored=scored, **theirs)
    got = _run(x, *weights, top_k, 2, 64.0, scored, **ours)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-4)
    one = _run(x, *weights, top_k, 1, 64.0, scored, **ours)
    np.testing.assert_allclose(np.asarray(one), np.asarray(want), rtol=2e-4,
                               atol=2e-4)


@pytest.mark.parametrize("top_k,gated,options", [
    (1, False, {}), (2, True, {}), (8, True, {}),
    (3, True, {"renormalise": True}), (3, True, {"gate": "relu"}),
    (3, True, {"router_input": True}), (3, True, _THREE)],
    ids=["top1-gelu", "top2-swiglu", "top8-swiglu", "top3-renormalised",
         "top3-reglu", "top3-own-router-input", "top3-all-three"])
def test_one_rank_and_its_gradients_match_the_dense_oracle(top_k, gated,
                                                           options):
    """`moe_ffn` on one rank weights the experts' hidden rows before the
    down product; the oracle runs every expert on every token and weights
    the results after it. Output and the gradient of every argument agree
    in float32, with expert 0 receiving most rows and expert 11 none.
    Renormalised weights take their gradient through the sum; with an input
    of its own the router's gradient reaches that input through the scores
    alone, and the rows' input through the experts alone."""
    ours, theirs, own = _options(options)
    n_experts, d, f = 12, 8, 16
    x = jax.random.normal(jax.random.PRNGKey(11), (48, d), jnp.float32)
    router, up, down, gate = _experts(jax.random.PRNGKey(12), n_experts, d,
                                      f, gated)
    x = x.at[:, 0].set(1.0)
    router = router.at[0].set(0.0).at[0, 0].set(6.0).at[0, 11].set(-60.0)
    probe = jax.random.normal(jax.random.PRNGKey(13), x.shape, jnp.float32)
    leaves = (x, router, up, down) + ((gate,) if gated else ())
    if own:
        # the router reads this, with the column that skews the load; the
        # experts read other rows
        leaves += (x,)
        leaves = (jax.random.normal(jax.random.PRNGKey(14), x.shape,
                                    jnp.float32),) + leaves[1:]

    def program(xx, r, u, dn, g=None, s=None):
        return jax.shard_map(
            lambda *a: moe_ffn(*a[:5], top_k=top_k, router_input=a[5],
                               **ours)[0], mesh=mesh_of(),
            in_specs=P(), out_specs=P(), check_vma=False)(xx, r, u, dn, g, s)

    def oracle(xx, r, u, dn, g=None, s=None):
        return _oracle(xx, r, u, dn, g, top_k, scored=s, **theirs)[0]

    def probed(ffn):
        def loss(*a):
            out = ffn(*a)
            return jnp.sum(out * probe), out
        return jax.jit(jax.value_and_grad(loss, tuple(range(len(leaves))),
                                          has_aux=True))

    rows = np.bincount(np.asarray(_oracle(
        *leaves[:4], gate, top_k, scored=leaves[5] if own else None)[1])
        .reshape(-1), minlength=n_experts)
    assert rows[11] == 0 and rows[0] == max(rows) >= len(x) // 2, rows
    (_, got), got_grads = probed(program)(*leaves)
    (_, want), want_grads = probed(oracle)(*leaves)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    for name, g, w in zip(("x", "router_w", "w_up", "w_down", "w_gate",
                           "router_input"), got_grads, want_grads):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0.0, name
        assert float(jnp.max(jnp.abs(g - w))) <= 2e-5 * scale, name


def test_the_drop_rule_across_ranks():
    """ep = 2, top-2 of 4 experts, 8 tokens a rank: cap = ceil(0.5 * 8 * 2 /
    4) = 2 rows per expert per sending rank. Of a rank's pairs for one
    expert, in token order, the first two are kept; a dropped pair adds
    nothing, the token's other expert still counts."""
    ranks, per_rank, top_k, n_experts = 2, 8, 2, 4
    x = jax.random.normal(jax.random.PRNGKey(7), (ranks * per_rank, 8),
                          jnp.float32)
    weights = _experts(jax.random.PRNGKey(8), n_experts, 8, 16, True)
    _, experts = _oracle(x, *weights, top_k)
    experts = np.asarray(experts)
    keep = np.zeros(experts.shape, np.float32)
    for r in range(ranks):
        seen = np.zeros(n_experts, int)
        for t in range(r * per_rank, (r + 1) * per_rank):
            for c in range(top_k):
                keep[t, c] = seen[experts[t, c]] < 2
                seen[experts[t, c]] += 1
    assert 0 < keep.sum() < keep.size       # some kept, some dropped
    want, _ = _oracle(x, *weights, top_k, keep=jnp.asarray(keep))
    got = _run(x, *weights, top_k, ranks, 0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-4)


# ---------------------------------------------------------- tolerance

def _register(dtype):
    """Tells the family about CFG, as the benchmark would from a file."""
    family.transformer_config({
        "vocab_size": CFG.vocab, "hidden_size": CFG.d_model,
        "num_attention_heads": CFG.n_heads, "intermediate_size": CFG.d_ff,
        "n_layer": CFG.n_layers, "max_position_embeddings": CFG.max_seq,
        "num_experts": CFG.num_experts, "num_experts_per_tok": TOP_K,
        "rms_norm_eps": 1e-5, "rope_theta": 10000,
        "program": {"load_balance_coef": 0.01, "router_z_coef": 0.001,
                    "attn": "local", "dtype": dtype, "remat": False}})


def test_the_logits_limit_admits_bf16_and_refuses_an_8_bit_float():
    """The family's comparison on the program computing in bf16, and on
    logits computed with 8-bit-float operands in every matrix product (the
    nearest precision below the configuration's): the first is within the
    logits' limit, the second is not. (The loss's limit is the published
    widths' over 4,096 tokens; over these 256 at toy widths a bf16 program
    does not meet it, and the chip's runs are what hold it to it. At these
    widths a token's two weights are ~0.3 each, so the few tokens the two
    sides route differently decide the reading: 1.9-4.6% of the rms over
    twelve seeds of the weights, the same before and after the weights
    moved in front of the down product (PR 29), against 12.6-22.1% with
    8-bit operands. The weights' seed is one that reads 1.9%.)"""
    _register("bfloat16")
    tokens, _ = _data(batch=8, seq=32)
    params = programs.init(CFG, 3)
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    low = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    logits = programs.forward(cfg)(low, tokens)
    rms, got, want, _ = family._compare(low, tokens, logits, TOP_K)
    assert family.within(float(rms), float(got), float(want))[0]
    eight = reference.logits(family.reference_weights(low), tokens, TOP_K,
                             operands=jnp.float8_e4m3fn)
    verdict = family.check_logits(low, tokens, eight)
    assert not verdict["ok"], verdict
    assert "rows per expert" in verdict["detail"]


#: planted faults: what an implementation of the routing gets wrong, as a
#: change to the k weights (T, k) of a token's experts, and a head that is 2%
#: out of scale, as a change to the logits
FAULTS = {
    "renormalised-weights":
        ("weights", lambda w: w / jnp.sum(w, axis=-1, keepdims=True)),
    "dropped-expert": ("weights", lambda w: w.at[:, -1].set(0.0)),
    "logits-2-percent-out-of-scale": ("logits", lambda z: z * 1.02),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_loss_limit_refuses_a_planted_fault(params, fault, monkeypatch):
    """The program in float32 is correct by both limits; with a fault planted
    it is not, and the loss's limit alone says so. The scale on the logits
    (2% rms) is within the logits' limit: only the loss's catches it."""
    _register("float32")
    tokens, _ = _data(batch=8, seq=32)
    where, change = FAULTS[fault]

    def verdict(logits_fault=lambda z: z, build=programs.forward):
        logits = logits_fault(build(CFG)(params, tokens))
        rms, got, want, _ = family._compare(params, tokens, logits, TOP_K)
        return (family.check_logits(params, tokens, logits)["ok"],
                family.within(float(rms), float(got), float(want)))

    assert verdict() == (True, (True, True))
    if where == "logits":
        assert verdict(change) == (False, (True, False))
        return
    sound = moe.route

    def faulty(x, router_w, top_k, *sequences):
        weights, *rest = sound(x, router_w, top_k, *sequences)
        return (change(weights), *rest)

    monkeypatch.setattr(moe, "route", faulty)
    # (built anew, past the memo, which holds the program as it is)
    ok, (_, loss_ok) = verdict(build=programs.forward.__wrapped__)
    assert not ok and not loss_ok


def test_check_logits_tells_configurations_apart_by_their_shapes():
    _register("float32")
    with pytest.raises(ValueError, match="num_experts_per_tok"):
        family.transformer_config({
            "vocab_size": CFG.vocab, "hidden_size": CFG.d_model,
            "intermediate_size": CFG.d_ff, "n_layer": CFG.n_layers,
            "num_experts": CFG.num_experts, "rms_norm_eps": 1e-5,
            "num_experts_per_tok": TOP_K + 1, "program": {}})
    with pytest.raises(ValueError, match="rms_norm_eps"):
        family.transformer_config({"rms_norm_eps": 1e-6, "program": {}})
