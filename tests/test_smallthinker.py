"""The SmallThinker block of `models/transformer.py` (a pattern of one NoPE
full-attention layer to three rotating windowed ones over fewer key and value
heads than query heads, a head width that is not d_model / n_heads, every
layer a layer of ReLU-gated experts whose router reads the layer's input and
whose top-k weights are renormalised, a share of the experts held) against
the plain reference `benchmark/reference/smallthinker.py`, at a small size in
float32: the family's statement for `tests/family_cases.py` (`FAMILY`) and
the shared cases (logits, `attn` "local" and "flash"; loss and every leaf's
gradient of one rank as the cell runs it, `attn` "flash" under remat "dots";
`dp` = 2 without remat against it; every planted fault refused by the
family's limits; what `validate_cfg_for_mesh` refuses); two periods; the four
shares of the experts adding up to the uncut layer. (The compiled step, its
scopes and three steps of it: `tests/test_step_scopes.py`.) Every program is
`tests/family.py`'s, built once for the module."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import family as programs
from benchmark.families import smallthinker as family
from benchmark.reference import smallthinker as reference
from family_cases import (  # noqa: F401  (the fixtures, the shared tests)
    Family, logits, ours, params, pytest_generate_tests, sound, stated,
    their_logits, theirs,
    test_an_unknown_fault_is_refused,
    test_dp_2_without_remat_equals_one_rank_under_remat,
    test_every_leafs_gradient_equals_the_references,
    test_logits_equal_the_references, test_loss_equals_the_references,
    test_the_familys_comparison_reads_zero_for_the_reference,
    test_the_limits_refuse_a_planted_fault,
    test_the_limits_refuse_an_8_bit_float,
    test_validate_accepts_the_model_where_it_runs,
    test_validate_refuses_by_name)
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
#: what `validate_cfg_for_mesh` refuses: (mesh, changed fields, its words)
REFUSED = (
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
)
#: the cell's algorithm and remat policy; (among the leaves: the routers',
#: whose gradient comes through the layer's input and the renormalised
#: weights, and `wk`, `wv`, summed over a group's two query heads)
FAMILY = Family(
    cfg=CFG, family=family, reference=reference,
    timed=dataclasses.replace(CFG, attn="flash", remat=True,
                              remat_policy="dots"),
    weights=(KINDS,), args=(KINDS, WINDOW, TOP_K, FIRST), data=(2, SEQ),
    refused=REFUSED, least=1e-6)


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


def test_two_periods_stack_by_kind_and_equal_the_reference():
    """Eight layers: each kind's leaves over (2 periods, its layers in one),
    the periods' auxiliary numbers gathered layer by layer."""
    cfg = dataclasses.replace(CFG, n_layers=8, load_balance_coef=0.01)
    tokens, targets = FAMILY.batch
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


def test_a_head_width_that_is_d_model_over_heads_is_no_width_apart():
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
