"""The SmallThinker block of `models/transformer.py` (a pattern of one NoPE
full-attention layer to three rotating windowed ones over fewer key and value
heads than query heads, a head width that is not d_model / n_heads, every
layer a layer of ReLU-gated experts whose router reads the layer's input and
whose top-k weights are renormalised, a share of the experts held) against
the plain reference `benchmark/reference/smallthinker.py`, at a small size in
float32: logits, loss and every leaf's gradient, `attn` "local" and "flash";
every planted fault refused by the family's limits; the four shares of the
experts adding up to the uncut layer; `dp` = 2 against one rank; and what
`validate_cfg_for_mesh` refuses. (The scopes of the compiled step:
`tests/test_step_scopes.py`.) Every program is `tests/family.py`'s, built
once for the module."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import family as programs
from benchmark.families import smallthinker as family
from benchmark.reference import smallthinker as reference
from horovod_tpu.common.exceptions import HorovodTpuError
from horovod_tpu.models import transformer as tfm
from family import mesh_of
from horovod_tpu.parallel import moe_ffn

KINDS = ("full", "window", "window", "window")
WINDOW, TOP_K, FIRST = 8, 3, 2
# 4 | 2 heads of 32 on a 64-wide model: heads x width != d_model; 8 experts,
# 3 a token, experts 2 and 3 held
CFG = tfm.TransformerConfig(
    vocab=96, d_model=64, n_heads=4, n_kv_heads=2, d_head=32, d_ff=48,
    n_layers=4, max_seq=64, num_experts=8, experts_per_token=TOP_K,
    experts_held=2, first_expert=FIRST, norm_topk=True, router_input="layer",
    norm="rmsnorm", rms_norm_eps=1e-6, positions="rope", rope_theta=1.5e6,
    layer_pattern=KINDS, unrotated=("full",), window=WINDOW, mlp="reglu",
    attn="local", dtype=jnp.float32)
SEQ = 32          # four windows long: the band matters
ATTNS = ("local", "flash")


def _data(batch=2, seq=SEQ):
    return programs.data(CFG.vocab, batch, seq)


@pytest.fixture(scope="module")
def params():
    return programs.init(CFG)


def _one_rank(params, cfg=CFG):
    """(loss, gradients) of the program on one rank."""
    with jax.enable_x64(False):
        return programs.loss_and_grads(cfg)(params, *_data())


@pytest.fixture(scope="module", params=ATTNS)
def ours(request, params):
    """`_one_rank` by each algorithm."""
    return _one_rank(params, dataclasses.replace(CFG, attn=request.param))


@pytest.fixture(scope="module")
def theirs(params):
    """(loss, gradients) of the reference, in the program's tree."""
    tokens, targets = _data()
    with jax.enable_x64(False):
        return jax.value_and_grad(lambda p: reference.loss(
            family.reference_weights(p, KINDS), tokens, targets, KINDS,
            WINDOW, TOP_K, FIRST))(params)


@pytest.fixture(scope="module")
def logits(params):
    """The program's logits for `_data()`'s tokens, once."""
    with jax.enable_x64(False):
        return programs.forward(CFG)(params, _data()[0])


@pytest.fixture(scope="module")
def sound(params, logits):
    """The family's comparison of `logits` with the sound reference."""
    with jax.enable_x64(False):
        return family.compare(params, _data()[0], logits, KINDS, WINDOW,
                              TOP_K, FIRST)


def test_the_tree_has_each_kinds_leaves_and_no_others(params):
    assert sorted(params) == ["embed", "layers", "lnf_scale", "unembed"]
    assert sorted(params["layers"]) == ["full", "window"]
    leaves = {"ln1_scale", "ln2_scale", "wq", "wk", "wv", "wo", "router",
              "we1", "we2", "we_gate"}
    assert set(params["layers"]["full"]) == leaves == \
        set(params["layers"]["window"])
    # stacked over (periods, the kind's layers in a period); 4 x 32 != 64
    full, window = params["layers"]["full"], params["layers"]["window"]
    assert full["wq"].shape == (1, 1, 64, 4, 32)
    assert window["wk"].shape == (1, 3, 64, 2, 32)
    assert window["wo"].shape == (1, 3, 4, 32, 64)
    assert full["router"].shape == (1, 1, 64, 8)       # the router is whole
    assert window["we_gate"].shape == (1, 3, 2, 64, 48)    # two are held
    assert CFG.head_dim == 32 and CFG.rope_dim == 32
    assert dataclasses.replace(CFG, d_head=0).head_dim == 16
    programs.assert_specs_cover(CFG, params)


@pytest.mark.parametrize("attn", ATTNS)
def test_logits_equal_the_references(params, attn):
    tokens, _ = _data()
    cfg = dataclasses.replace(CFG, attn=attn)
    with jax.enable_x64(False):
        got = programs.forward(cfg)(params, tokens)
        want = reference.forward(family.reference_weights(params, KINDS),
                                 tokens, KINDS, WINDOW, TOP_K, FIRST)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)


def test_loss_equals_the_references(ours, theirs):
    np.testing.assert_allclose(ours[0], theirs[0], rtol=1e-5)


@pytest.mark.parametrize("leaf", programs.leaf_names(CFG))
def test_every_leafs_gradient_equals_the_references(ours, theirs, leaf):
    """Among them the routers', whose gradient comes through the layer's
    input and the renormalised weights, and `wk`, `wv`, summed over a
    group's two query heads."""
    got, want = (programs.leaves(x[1])[leaf] for x in (ours, theirs))
    size = float(jnp.max(jnp.abs(want)))
    assert size > 1e-6, "nothing to compare"
    np.testing.assert_allclose(got, want, rtol=2e-3,
                               atol=2e-4 * size + 1e-7)


def test_two_periods_stack_by_kind_and_equal_the_reference():
    """Eight layers: each kind's leaves over (2 periods, its layers in one),
    the periods' auxiliary numbers gathered layer by layer."""
    cfg = dataclasses.replace(CFG, n_layers=8, load_balance_coef=0.01)
    tokens, targets = _data()
    with jax.enable_x64(False):
        p = programs.init(cfg, 5)
        assert p["layers"]["window"]["wq"].shape == (2, 3, 64, 4, 32)
        got = programs.forward(cfg)(p, tokens)
        want = reference.forward(family.reference_weights(p, KINDS * 2),
                                 tokens, KINDS * 2, WINDOW, TOP_K, FIRST)
        loss, _ = programs.loss_and_grads(cfg)(p, tokens, targets)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-4)
    # the balance term of all eight layers is in the loss (>= 1 a layer)
    plain = float(reference.next_token_loss(want, targets))
    assert float(loss) - plain >= 0.01 * 0.9


# --------------------------------------------------------------- the limits

@pytest.mark.parametrize("fault", reference.FAULTS)
def test_the_limits_refuse_a_planted_fault(params, logits, sound, fault):
    """The program's logits against the reference computed with one
    mechanism wrong: by one of the family's limits it is not correct, and
    against the sound reference it is, with room."""
    tokens, _ = _data()
    with jax.enable_x64(False):
        wrong = family.compare(params, tokens, logits, KINDS, WINDOW, TOP_K,
                               FIRST, fault=fault)
    assert all(family.within(*(float(x) for x in sound[:3])))
    assert float(sound[0]) < 1e-5
    assert not all(family.within(*(float(x) for x in wrong[:3]))), \
        [float(x) for x in wrong[:3]]
    with pytest.raises(ValueError, match="choose from"):
        reference.final_hidden(family.reference_weights(params, KINDS),
                               tokens, KINDS, WINDOW, TOP_K, FIRST,
                               fault="no_such_fault")


@pytest.mark.parametrize("operands", [jnp.float8_e4m3fn, jnp.float8_e5m2],
                         ids=["e4m3", "e5m2"])
def test_the_limits_refuse_an_8_bit_float(params, logits, operands):
    tokens, _ = _data()
    with jax.enable_x64(False):
        rms, got, want, _ = family.compare(
            params, tokens, logits, KINDS, WINDOW, TOP_K, FIRST,
            operands=operands)
    assert not all(family.within(float(rms), float(got), float(want)))


def test_the_familys_comparison_reads_zero_for_the_reference(params):
    """`family.compare` (the reference's head a block of tokens at a time)
    against the reference's whole forward pass; its count of the held
    experts' rows against the routes themselves."""
    tokens, targets = _data()
    with jax.enable_x64(False):
        weights = family.reference_weights(params, KINDS)
        logits = reference.forward(weights, tokens, KINDS, WINDOW, TOP_K,
                                   FIRST)
        _, routes = reference.final_hidden(weights, tokens, KINDS, WINDOW,
                                           TOP_K, FIRST)
        rms, got, want, rows = family.compare(params, tokens, logits, KINDS,
                                              WINDOW, TOP_K, FIRST)
        loss = reference.next_token_loss(logits, targets)
    assert float(rms) < 1e-6
    np.testing.assert_allclose([float(got), float(want)], float(loss),
                               rtol=1e-6)
    assert rows.shape == (4, 2)
    assert [int(np.sum(np.asarray(routes) == FIRST + e)) for e in (0, 1)] \
        == [int(rows[:, e].sum()) for e in (0, 1)]


# --------------------------------------------------------------- the share

def test_the_four_shares_add_up_to_the_uncut_layer():
    """Experts 0-1, 2-3, 4-5 and 6-7 of 8 on four chips, each routing over
    all 8 with the router's own input and renormalising over all three
    chosen: the parts `moe_ffn` gives add up to what the reference's layer
    gives with every expert held. Nothing is counted twice: the model has no
    shared expert."""
    d, f, tokens = 64, 48, 48
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    scored = jax.random.normal(ks[0], (1, tokens, d), jnp.float32)
    rows = jax.random.normal(ks[1], (1, tokens, d), jnp.float32)
    w = {"router": jax.random.normal(ks[2], (d, 8), jnp.float32) / 8,
         "w_gate": jax.random.normal(ks[3], (8, d, f), jnp.float32) / 8,
         "w_up": jax.random.normal(ks[4], (8, d, f), jnp.float32) / 8,
         "w_down": jax.random.normal(ks[5], (8, f, d), jnp.float32) / 7}

    def share(first):
        held = slice(first, first + 2)
        return jax.jit(jax.shard_map(
            lambda x, r, up, down, gate, s: moe_ffn(
                x, r, up, down, gate, top_k=TOP_K, first_expert=first,
                router_input=s, renormalise=True, gate="relu")[:2],
            mesh=mesh_of(), in_specs=P(), out_specs=P(), check_vma=False))(
                rows[0], w["router"], w["w_up"][held], w["w_down"][held],
                w["w_gate"][held], scored[0])

    with jax.enable_x64(False), jax.default_matmul_precision("highest"):
        parts = [share(first) for first in (0, 2, 4, 6)]
        whole, routes = reference.moe(scored, rows, w, TOP_K)
        one, _ = reference.moe(scored, rows, dict(
            w, **{k: w[k][2:4] for k in ("w_gate", "w_up", "w_down")}),
            TOP_K, first_expert=2)
    assert all(float(aux[2]) == 0 for _, aux in parts)   # nothing left out
    np.testing.assert_allclose(sum(out for out, _ in parts), whole[0],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(parts[1][0], one[0], rtol=2e-5, atol=2e-5)
    # every share does some of the work, and none all of it
    held = [int(np.sum(np.asarray(routes) // 2 == s)) for s in range(4)]
    assert min(held) > 0 and sum(held) == tokens * TOP_K


# ------------------------------------------------------ meshes, step, remat

def test_dp2_equals_one_rank(params):
    want_loss, want = _one_rank(params)
    with jax.enable_x64(False):
        mesh = mesh_of(dp=2)
        tfm.validate_cfg_for_mesh(CFG, mesh)
        loss, grads = programs.loss_and_grads(CFG, dp=2)(
            tfm.shard_params(params, CFG, mesh), *_data())
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    programs.assert_trees_close(grads, want, rtol=1e-4, atol=1e-6)


def test_a_train_step_lowers_the_loss_and_counts_what_it_drops(params):
    opt = optax.adamw(1e-2)
    cfg = dataclasses.replace(CFG, remat=True)
    with jax.enable_x64(False):
        results = programs.train(cfg, opt, params, _data(), 3, metrics=True)
    assert all(int(counts["experts_dropped"]) == 0 for _, counts in results)
    assert float(results[2][0]) < float(results[0][0]), results


def test_remat_changes_no_result(params):
    want_loss, want = _one_rank(params)
    for policy in ("dots", "full"):
        loss, grads = _one_rank(params, dataclasses.replace(
            CFG, remat=True, remat_policy=policy))
        np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
        programs.assert_trees_close(grads, want, rtol=1e-4, atol=1e-7)


# -------------------------------------------------------------- refusals

REFUSED = [
    ({}, {"attn": "ring"}, "d_head \\* n_heads != d_model needs attn"),
    ({}, {"attn": "ulysses"}, "d_head \\* n_heads != d_model needs attn"),
    ({}, {"attn": "ring", "n_kv_heads": 0, "window": 0,
          "layer_pattern": (), "unrotated": ()},
     "d_head \\* n_heads != d_model needs attn"),
    (dict(tp=2), {"n_kv_heads": 0, "window": 0, "layer_pattern": (),
                  "unrotated": ()},
     "d_head \\* n_heads != d_model requires sp=tp=pp=1"),
    (dict(sp=2), {"n_kv_heads": 0, "window": 0, "layer_pattern": (),
                  "unrotated": ()},
     "d_head \\* n_heads != d_model requires sp=tp=pp=1"),
    (dict(tp=2), {"d_head": 0},
     "need attn 'flash' or 'local' and sp=tp=pp=1"),
    ({}, {"attention": "mla"}, "d_head is plain attention's head width"),
    ({}, {"positions": "none"}, "unrotated names kinds that take no "
                                "rotation"),
    ({}, {"unrotated": ("linear",)}, "unrotated names a kind the pattern "
                                     "lacks"),
    ({}, {"router_input": "attention"}, "router_input='attention'"),
    ({}, {"router_input": "layer", "post_norm": True},
     "router_input='layer' with post_norm"),
    ({}, {"mlp": "geglu"}, "mlp='geglu'"),
    ({}, {"window": 0}, "'window' layers need window > 0"),
    (dict(pp=2), {"microbatches": 2}, "requires sp=tp=pp=1"),
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
    # a head width that happens to be d_model / n_heads is no width apart
    tfm.validate_cfg_for_mesh(tfm.TransformerConfig(d_head=64, attn="ring"),
                              mesh_of(sp=2))


def test_an_unknown_gate_is_refused_by_the_expert_layer():
    x = jnp.ones((8, 4), jnp.float32)
    w = jnp.ones((2, 4, 4), jnp.float32)
    with pytest.raises(HorovodTpuError, match="gate='gelu'"):
        jax.shard_map(lambda: moe_ffn(x, jnp.ones((4, 2)), w, w, w,
                                      gate="gelu")[0],
                      mesh=mesh_of(), in_specs=(), out_specs=P(),
                      check_vma=False)()
