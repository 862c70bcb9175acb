"""The SmallThinker block of `models/transformer.py` (a pattern of one NoPE
full-attention layer to three rotating windowed ones over fewer key and value
heads than query heads, a head width that is not d_model / n_heads, every
layer a layer of ReLU-gated experts whose router reads the layer's input and
whose top-k weights are renormalised, a share of the experts held) against
the plain reference `benchmark/reference/smallthinker.py`, at a small size in
float32: logits, loss and every leaf's gradient, `attn` "local" and "flash";
every planted fault refused by the family's limits; the four shares of the
experts adding up to the uncut layer; `dp` = 2 against one rank; the scopes
of the compiled step; and what `validate_cfg_for_mesh` refuses."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from benchmark.families import smallthinker as family
from benchmark.harness import hlo, scope_time
from benchmark.reference import smallthinker as reference
from horovod_tpu.common.exceptions import HorovodTpuError
from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel import MeshSpec, build_mesh, moe_ffn

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


def mesh_of(**sizes):
    spec = MeshSpec(**sizes)
    return build_mesh(spec, jax.devices()[:spec.total])


def _data(batch=2, seq=SEQ):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                CFG.vocab, jnp.int32)
    return tokens, jnp.roll(tokens, -1, axis=1)


@pytest.fixture(scope="module")
def params():
    with jax.enable_x64(False):
        return tfm.init(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module", params=ATTNS)
def ours(request, params):
    """(loss, gradients) of the program on one rank, by each algorithm."""
    tokens, targets = _data()
    cfg = dataclasses.replace(CFG, attn=request.param)
    with jax.enable_x64(False):
        return jax.jit(tfm.build_loss_and_grads(cfg, mesh_of()))(
            params, tokens, targets)


@pytest.fixture(scope="module")
def theirs(params):
    """(loss, gradients) of the reference, in the program's tree."""
    tokens, targets = _data()
    with jax.enable_x64(False):
        return jax.value_and_grad(lambda p: reference.loss(
            family.reference_weights(p, KINDS), tokens, targets, KINDS,
            WINDOW, TOP_K, FIRST))(params)


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
    specs, axes = tfm.param_specs(CFG), tfm.grad_reduce_axes(CFG)
    structure = jax.tree_util.tree_structure(params)
    assert jax.tree_util.tree_structure(specs) == structure
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda x: 0, axes,
                               is_leaf=lambda x: isinstance(x, tuple))) \
        == structure


@pytest.mark.parametrize("attn", ATTNS)
def test_logits_equal_the_references(params, attn):
    tokens, _ = _data()
    cfg = dataclasses.replace(CFG, attn=attn)
    with jax.enable_x64(False):
        got = jax.jit(tfm.build_forward(cfg, mesh_of()))(params, tokens)
        want = reference.forward(family.reference_weights(params, KINDS),
                                 tokens, KINDS, WINDOW, TOP_K, FIRST)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)


def test_loss_equals_the_references(ours, theirs):
    np.testing.assert_allclose(ours[0], theirs[0], rtol=1e-5)


def _leaves(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


LEAVES = sorted(_leaves(jax.eval_shape(lambda k: tfm.init(k, CFG),
                                       jax.random.PRNGKey(0))))


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leafs_gradient_equals_the_references(ours, theirs, leaf):
    """Among them the routers', whose gradient comes through the layer's
    input and the renormalised weights, and `wk`, `wv`, summed over a
    group's two query heads."""
    got, want = _leaves(ours[1])[leaf], _leaves(theirs[1])[leaf]
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
        p = tfm.init(jax.random.PRNGKey(5), cfg)
        assert p["layers"]["window"]["wq"].shape == (2, 3, 64, 4, 32)
        got = jax.jit(tfm.build_forward(cfg, mesh_of()))(p, tokens)
        want = reference.forward(family.reference_weights(p, KINDS * 2),
                                 tokens, KINDS * 2, WINDOW, TOP_K, FIRST)
        loss, _ = jax.jit(tfm.build_loss_and_grads(cfg, mesh_of()))(
            p, tokens, targets)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-4)
    # the balance term of all eight layers is in the loss (>= 1 a layer)
    plain = float(reference.next_token_loss(want, targets))
    assert float(loss) - plain >= 0.01 * 0.9


# --------------------------------------------------------------- the limits

@pytest.mark.parametrize("fault", reference.FAULTS)
def test_the_limits_refuse_a_planted_fault(params, fault):
    """The program's logits against the reference computed with one
    mechanism wrong: by one of the family's limits it is not correct, and
    against the sound reference it is, with room."""
    tokens, _ = _data()
    with jax.enable_x64(False):
        logits = jax.jit(tfm.build_forward(CFG, mesh_of()))(params, tokens)
        sound = family.compare(params, tokens, logits, KINDS, WINDOW, TOP_K,
                               FIRST)
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
def test_the_limits_refuse_an_8_bit_float(params, operands):
    tokens, _ = _data()
    with jax.enable_x64(False):
        logits = jax.jit(tfm.build_forward(CFG, mesh_of()))(params, tokens)
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
    tokens, targets = _data()
    with jax.enable_x64(False):
        want_loss, want = jax.jit(tfm.build_loss_and_grads(CFG, mesh_of()))(
            params, tokens, targets)
        mesh = mesh_of(dp=2)
        tfm.validate_cfg_for_mesh(CFG, mesh)
        loss, grads = jax.jit(tfm.build_loss_and_grads(CFG, mesh))(
            tfm.shard_params(params, CFG, mesh), tokens, targets)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for (path, got), w in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            got, w, rtol=1e-4, atol=1e-6,
            err_msg=jax.tree_util.keystr(path))


def test_a_train_step_lowers_the_loss_and_counts_what_it_drops(params):
    tokens, targets = _data()
    mesh, opt = mesh_of(), optax.adamw(1e-2)
    cfg = dataclasses.replace(CFG, remat=True)
    with jax.enable_x64(False):
        # (the step donates its state: a copy, not the fixture's arrays)
        state = [tfm.shard_params(jax.tree_util.tree_map(jnp.copy, params),
                                  cfg, mesh)]
        state.append(tfm.init_opt_state(opt, state[0], mesh))
        step = tfm.build_train_step(cfg, mesh, opt, metrics=True)
        losses = []
        for _ in range(3):
            state[0], state[1], loss, counts = step(state[0], state[1],
                                                    tokens, targets)
            losses.append(float(loss))
            assert int(counts["experts_dropped"]) == 0
    assert losses[2] < losses[0], losses


def test_remat_changes_no_result(params):
    tokens, targets = _data()
    with jax.enable_x64(False):
        want_loss, want = jax.jit(tfm.build_loss_and_grads(CFG, mesh_of()))(
            params, tokens, targets)
    for policy in ("dots", "full"):
        cfg = dataclasses.replace(CFG, remat=True, remat_policy=policy)
        with jax.enable_x64(False):
            loss, grads = jax.jit(tfm.build_loss_and_grads(cfg, mesh_of()))(
                params, tokens, targets)
        np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
        for got, w in zip(jax.tree_util.tree_leaves(grads),
                          jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(got, w, rtol=1e-4, atol=1e-7)


# ------------------------------------------------------------------ scopes

def _compiled_step(cfg):
    opt = optax.adamw(1e-3)
    with jax.enable_x64(False):   # as the benchmark runs
        shapes = jax.eval_shape(lambda k: tfm.init(k, cfg),
                                jax.random.PRNGKey(0))
        state = jax.eval_shape(opt.init, shapes)
        tokens = jax.ShapeDtypeStruct((2, SEQ), jnp.int32)
        return tfm.build_train_step(cfg, mesh_of(), opt, metrics=True).lower(
            shapes, state, tokens, tokens).compile().as_text()


def test_no_instruction_of_the_new_layer_lies_outside_a_scope():
    """The scores (renormalisation included) under `moe.route`, the rotation
    under `attn.project` in rotating layers only, the windowed kernels under
    `attn.attend/attn.window`, the full layer's under `attn.attend` alone,
    the ReLU gate under `moe.experts`."""
    cfg = dataclasses.replace(CFG, attn="flash", remat=True)
    text = _compiled_step(cfg)
    table = hlo.index(text)
    ops = dict(re.findall(r'%?([\w.\-]+) = [^\n]*op_name="([^"]*)"', text))

    def under(prefix):
        return {ops[name] for name in scope_time.names_under(text, table,
                                                              prefix)}

    route = under("moe.route")
    assert any(op.endswith("moe.route/div") for op in route)   # w / sum w
    assert any(op.endswith("moe.route/top_k") for op in route)
    assert any(op.endswith("moe.route/dot_general") for op in route)
    assert any("moe.experts/jit(relu)/max" in op for op in under("moe."))
    assert not any("silu" in op or "logistic" in op for op in under("moe."))
    # rotate-half: the two halves joined again, in `attn.project`
    assert any(op.endswith("attn.project/concatenate")
               for op in under("attn.project"))
    windowed, attended = under("attn.window"), under("attn.attend")
    assert windowed and windowed < attended
    assert all("attn.attend/attn.window" in op for op in windowed)
    # a stack that rotates no kind has no rotation under `attn.project`
    none = _compiled_step(dataclasses.replace(
        cfg, unrotated=("full", "window")))
    assert "attn.project/concatenate" not in none
    assert "attn.project/concatenate" in text


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
