"""The LFM2 stack of `models/transformer.py` beside `tests/test_lfm2_moe.py`
(whose statement `FAMILY` this file shares), as the cell runs it (`attn`
"flash", remat "dots"). Of `tests/family_cases.py`: the loss and every
leaf's gradient against the plain reference's, `dp` = 2 without remat against
one rank under it, what `validate_cfg_for_mesh` refuses (the train step:
`tests/test_lowered_steps.py`). Its own: the dense layer inside the layer pattern and the segments behind it;
the eight shares of an expert layer adding up to the uncut layer; the
renormalisation's epsilon; and the family's counts at the published
widths."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import family as programs
from benchmark.families import lfm2_moe as family
from benchmark.reference import lfm2_moe as reference
from family import mesh_of
from family_cases import (  # noqa: F401  (the fixtures, the shared tests)
    ours, params, pytest_generate_tests, stated, theirs,
    test_dp_2_without_remat_equals_one_rank_under_remat,
    test_every_leafs_gradient_equals_the_references,
    test_loss_equals_the_references, test_validate_accepts_the_model_where_it_runs,
    test_validate_refuses_by_name)
from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel import moe_ffn
from test_lfm2_moe import CFG, FAMILY, PATTERN, TOP_K  # noqa: F401


# ------------------------------------------------------------------ the stack

def test_the_stack_behind_the_dense_layer_is_the_published_order():
    """The dense layer is the pattern's first layer; behind it the rest of
    its period, the whole periods, and what is left of a last one: at nine
    layers the cell's three segments, published layers 1-9. Nothing there
    needed a new rule."""
    def segments(layers):
        return tfm._pattern_segments(dataclasses.replace(CFG,
                                                         n_layers=layers))

    rest = (("full", "shortconv", "shortconv"), 1)
    assert segments(5) == (rest, (("shortconv",), 1))
    assert segments(9) == (rest, (PATTERN, 1), (("shortconv",), 1))
    assert segments(8) == (rest, (PATTERN, 1))
    assert tfm._stack_cfg(CFG, "dense_layers").attention == "shortconv"


# --------------------------------------------------------------- the share

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Two experts of 16 on each of eight chips, each scoring all 16 with
    the sigmoid, choosing on score + bias and renormalising over all four
    chosen (+ 1e-6): the parts that `moe_ffn` gives add up to what the
    reference's layer gives with every expert held (no shared expert)."""
    d, f, tokens, n, held = 64, 24, 48, 16, 2
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    rows = jax.random.normal(ks[1], (1, tokens, d), jnp.float32)
    w = {"router": jax.random.normal(ks[2], (d, n), jnp.float32) / 4,
         "bias": 0.4 * jax.random.normal(ks[6], (n,), jnp.float32),
         "w_gate": jax.random.normal(ks[3], (n, d, f), jnp.float32) / 8,
         "w_up": jax.random.normal(ks[4], (n, d, f), jnp.float32) / 8,
         "w_down": jax.random.normal(ks[5], (n, f, d), jnp.float32) / 5}
    def share(first):
        mine = slice(first, first + held)
        return jax.jit(jax.shard_map(
            lambda x, r, b, up, down, gate: moe_ffn(
                x, r, up, down, gate, top_k=TOP_K, first_expert=first,
                renormalise=True, renormalise_eps=reference.RENORM_EPS,
                scoring="sigmoid", selection_bias=b)[:2],
            mesh=mesh_of(), in_specs=P(), out_specs=P(), check_vma=False))(
                rows[0], w["router"], w["bias"], w["w_up"][mine],
                w["w_down"][mine], w["w_gate"][mine])

    with jax.enable_x64(False), jax.default_matmul_precision("highest"):
        parts = [share(first) for first in range(0, n, held)]
        whole, routes = reference.moe(rows, w, TOP_K)
        one, _ = reference.moe(rows, dict(w, **{
            k: w[k][4:6] for k in ("w_gate", "w_up", "w_down")}), TOP_K,
            first_expert=4)
    assert len(parts) == 8
    assert all(float(aux[2]) == 0 for _, aux in parts)   # nothing left out
    np.testing.assert_allclose(sum(out for out, _ in parts), whole[0],
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(parts[2][0], one[0], rtol=3e-5, atol=3e-5)
    # the bias moved the choice: without it other experts are chosen
    _, plain = reference.router_weights(
        jnp.einsum("nd,de->ne", rows[0], w["router"]), 0.0, TOP_K)
    assert np.any(np.sort(np.asarray(plain)) != np.sort(
        np.asarray(routes[0])))


def test_the_renormalisations_epsilon():
    """`route` divides the chosen scores by (their sum + eps): 1e-6 moves
    the weights by a millionth of their sum's inverse, 0 is the division it
    was, and the default is 0."""
    from horovod_tpu.parallel.moe import route
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    x = jax.random.normal(ks[0], (20, 16), jnp.float32)
    w = jax.random.normal(ks[1], (16, 8), jnp.float32) / 2
    with jax.enable_x64(False):
        plain = route(x, w, 2, renormalise=True, scoring="sigmoid")
        same = route(x, w, 2, renormalise=True, scoring="sigmoid",
                     renormalise_eps=0.0)
        big = route(x, w, 2, renormalise=True, scoring="sigmoid",
                    renormalise_eps=0.5)
        scores = jnp.take_along_axis(jax.nn.sigmoid(x @ w), plain[1],
                                     axis=-1)
    np.testing.assert_array_equal(plain[0], same[0])
    np.testing.assert_allclose(plain[0].sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(
        big[0], scores / (scores.sum(-1, keepdims=True) + 0.5), rtol=1e-6)


# ------------------------------------------------------- the published widths

def test_the_familys_counts_at_the_published_widths():
    """The parameters of the cell's cut, leaf by leaf from `tfm.init`'s
    shapes, and the family's FLOPs and least work at its shapes, by hand."""
    from benchmark.harness import spec
    with open(os.path.join(spec.REPO, "benchmark", "configs",
                           "lfm2-24b-a2b.json")) as f:
        config = json.load(f)
    cfg = family.transformer_config(config)
    count = {name: int(np.prod(x.shape))
             for name, x in programs.leaves(programs.shapes(cfg)).items()}

    def of(*parts):
        return sum(n for name, n in count.items()
                   if all(p in name for p in parts))

    conv_mixer = 2_048 * 6_144 + 2_048 * 3 + 2_048 * 2_048
    assert of("['dense_layers']", "sc_") == conv_mixer == 16_783_360
    assert of("[1]['shortconv']", "sc_") == 3 * conv_mixer
    assert sum(of("[0]['full']", w) for w in ("wq", "wk", "wv", "wo")) \
        == 2 * 2_048 * 2_048 + 2 * 2_048 * 512 == 10_485_760
    assert of("[0]['full']['q_scale']") == 64
    assert of("[0]['full']['we1']") == 8 * 2_048 * 1_536
    assert of("['dense_layers']['w") == 3 * 2_048 * 11_776
    assert of("['embed']") == 8_192 * 2_048 and not of("unembed")
    assert sum(count.values()) == config["check"]["parameters"] \
        == 832_652_032
    traffic = {"per_chip_batch": 1, "seq_len": 16_384}
    assert family.kinds(config).count("shortconv") == 7
    assert family.flash_kernel_shape(config, traffic) == (1, 32, 16_384, 64,
                                                          64)
    assert family.grouped_matmul_shape(config, traffic) == (8_192, 2_048,
                                                            1_536, 8)
    flops = family.forward_flops_per_token(config, 16_384)
    assert flops["shortconv_projections"] == 7 * 2 * 4 * 2_048 * 2_048
    assert flops["attention"] == 2 * 2 * 32 * 2 * 64 * 16_385 / 2
    assert flops["experts"] == 8 * 4 * 8 / 64 * 6 * 2_048 * 1_536
    assert flops["dense_mlp"] == 6 * 2_048 * 11_776
    assert flops["head"] == 2 * 2_048 * 8_192
    assert family.flops_per_sample(config, traffic) == 3 * sum(
        flops.values())
