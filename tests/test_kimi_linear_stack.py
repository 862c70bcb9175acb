"""The Kimi Linear stack of `models/transformer.py` beside
`tests/test_kimi_linear.py` (whose tiny `CFG` this file shares): a dense
prefix inside a layer pattern (the segments behind it, stacks that end inside
a period against the reference); the sixteen shares of the experts adding up
to the uncut layer with the shared expert counted once; the router's sigmoid
rule; "mla" under `unrotated`; `dp` = 2 against one rank; and what
`validate_cfg_for_mesh` refuses."""

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
from horovod_tpu.common.exceptions import HorovodTpuError
from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel import moe_ffn
from test_kimi_linear import (CFG, FIRST, PATTERN, TOP_K, _data, _lively,
                              chunks_of_eight)  # noqa: F401


# ------------------------------------------------------------------ the stack

def test_the_stack_behind_the_dense_layer_starts_inside_the_period():
    """The dense layer is the pattern's first layer; behind it the rest of
    its period, the whole periods, and what is left of a last one."""
    def segments(**changes):
        return tfm._pattern_segments(dataclasses.replace(CFG, **changes))

    rest = (("kda", "kda", "mla"), 1)
    assert segments() == (rest, (("kda",), 1))
    assert segments(n_layers=6) == (rest, (("kda", "kda"), 1))
    assert segments(n_layers=7) == (rest, (("kda", "kda", "kda"), 1))
    assert segments(n_layers=8) == (rest, (PATTERN, 1))
    assert segments(n_layers=14) == (rest, (PATTERN, 2),
                                     (("kda", "kda"), 1))
    assert segments(n_layers=4) == (rest,)
    assert segments(n_layers=3) == ((("kda", "kda"), 1),)
    # without a dense prefix a pattern still runs whole periods only
    assert segments(first_k_dense=0, n_layers=8) == ((PATTERN, 2),)
    with pytest.raises(HorovodTpuError, match="no whole number of periods"):
        segments(first_k_dense=0)
    for changes in ({"first_k_dense": 4, "n_layers": 9},
                    {"first_k_dense": 1, "n_layers": 1},
                    {"layer_pattern": ("kda", "mla", "kda", "kda"),
                     "first_k_dense": 2}):
        with pytest.raises(HorovodTpuError, match="pattern's first layers"):
            segments(**changes)


@pytest.mark.parametrize("layers", [6, 8])
def test_a_stack_that_ends_elsewhere_equals_the_reference(layers):
    """Six layers (the dense one, the rest of its period and two more: the
    cell's) and eight (two whole periods): whatever the issue's rule leaves
    of the depth; `CFG`'s own five are the least it leaves."""
    cfg = dataclasses.replace(CFG, n_layers=layers)
    kinds = (PATTERN * 2)[:layers]
    tokens, _ = _data()
    p = _lively(programs.init(cfg))
    with jax.enable_x64(False):
        got = programs.forward(cfg)(p, tokens)
        want = reference.forward(family.reference_weights(p, kinds), tokens,
                                 kinds, TOP_K, FIRST)
    np.testing.assert_allclose(got, want, atol=5e-3, rtol=5e-3)


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


# ------------------------------------------------- NoPE by `unrotated`, meshes

def test_mla_takes_no_rotation_where_unrotated_names_it():
    """positions "rope" with both kinds named in `unrotated` is positions
    "none"; with "mla" left out its layer is rotated and the logits move.
    (Four layers: the dense one and the rest of its period.)"""
    short = dataclasses.replace(CFG, n_layers=4)
    tokens, _ = _data()
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


def test_dp_2_equals_one_rank():
    """The layers' gradients are reduce-scattered inside the backward loop
    segment by segment (a pattern of (kda, mla) behind its dense KDA layer:
    a segment of the one MLA layer, then a whole period): loss and every
    gradient as on one rank."""
    cfg = dataclasses.replace(CFG, n_layers=4, layer_pattern=("kda", "mla"))
    assert tfm._pattern_segments(cfg) == ((("mla",), 1),
                                          (("kda", "mla"), 1))
    tokens, targets = _data(batch=4)
    p = _lively(programs.init(cfg))
    with jax.enable_x64(False):
        one = programs.loss_and_grads(cfg)(p, tokens, targets)
        mesh = mesh_of(dp=2)
        tfm.validate_cfg_for_mesh(cfg, mesh)
        two = programs.loss_and_grads(cfg, dp=2)(
            tfm.shard_params(p, cfg, mesh), tokens, targets)
    assert isinstance(p["layers"], list) and len(p["layers"]) == 2
    np.testing.assert_allclose(two[0], one[0], rtol=1e-5)
    programs.assert_trees_close(two[1], one[1], rtol=2e-3, atol=1e-8,
                                scaled=2e-4)


@pytest.mark.parametrize("mesh, changes, what", [
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
])
def test_what_the_mesh_check_refuses(mesh, changes, what):
    cfg = dataclasses.replace(CFG, **changes)
    with pytest.raises(HorovodTpuError) as refused:
        tfm.validate_cfg_for_mesh(cfg, mesh_of(**mesh))
    assert what in str(refused.value)
