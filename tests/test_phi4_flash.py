"""The Phi-4-mini-flash (SambaY) decoder of `models/transformer.py` (segments
of Mamba-1 state-space layers, windowed and full differential attention over
fewer key and value heads than query heads, Gated Memory Units and
cross-attention that read one layer's memory and keys and values, a tied
head) against the plain reference `benchmark/reference/phi4_flash.py`, at a
small size in float32: logits, loss and every leaf's gradient, the shared
memory's and the shared keys' and values' among them; `dp` = 2 against one
rank; and what `validate_cfg_for_mesh` refuses. Every program is
`tests/family.py`'s, built once for the module."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import family as programs
from benchmark.families import phi4_flash as family
from benchmark.reference import phi4_flash as reference
from horovod_tpu.common.exceptions import HorovodTpuError
from horovod_tpu.models import transformer as tfm
from horovod_tpu.models.mixers import MIXERS
from family import mesh_of

SEGMENTS = ((("ssm", "window"), 2), (("ssm", "full"), 1),
            (("gmu", "cross"), 2))
KINDS = tuple(kind for pattern, periods in SEGMENTS for _ in range(periods)
              for kind in pattern)
WINDOW = 8
CFG = tfm.TransformerConfig(
    vocab=96, d_model=32, n_heads=8, n_kv_heads=4, d_ff=48, n_layers=10,
    max_seq=64, positions="none", mlp="swiglu", segments=SEGMENTS,
    window=WINDOW, tied_head=True, attention_bias=True, diff_attention=True,
    ssm_state=4, ssm_conv=4, ssm_expand=2, attn="flash", dtype=jnp.float32)
SEQ = 24          # three windows long: the band matters
#: (remat against none at 1e-7: without them four entries in a thousand of
#: one leaf differ by 1e-6)
pytestmark = pytest.mark.usefixtures("xla_optimizations")


def _data(batch=2, seq=SEQ):
    return programs.data(CFG.vocab, batch, seq)


@pytest.fixture(scope="module")
def params():
    return programs.init(CFG)


def _one_rank(params, cfg=CFG):
    with jax.enable_x64(False):
        return programs.loss_and_grads(cfg)(params, *_data())


@pytest.fixture(scope="module")
def ours(params):
    """(loss, gradients) of the program on one rank."""
    return _one_rank(params)


@pytest.fixture(scope="module")
def theirs(params):
    """(loss, gradients) of the reference, in the program's tree."""
    tokens, targets = _data()
    with jax.enable_x64(False):
        return jax.value_and_grad(lambda p: reference.loss(
            family.reference_weights(p, KINDS), tokens, targets, KINDS,
            WINDOW))(params)


@pytest.fixture(scope="module")
def their_logits(params):
    with jax.enable_x64(False):
        return reference.forward(family.reference_weights(params, KINDS),
                                 _data()[0], KINDS, WINDOW)


def test_the_tree_has_each_kinds_leaves_and_no_others(params):
    assert sorted(params) == ["embed", "lnf_bias", "lnf_scale", "segments"]
    first, middle, last = params["segments"]
    assert sorted(first) == ["ssm", "window"] and \
        sorted(middle) == ["full", "ssm"] and sorted(last) == ["cross", "gmu"]
    shared = {"ln1_scale", "ln1_bias", "ln2_scale", "ln2_bias", "w1", "w2",
              "w_gate"}
    ssm, gmu = set(MIXERS["ssm"].leaves(CFG)), set(MIXERS["gmu"].leaves(CFG))
    assert len(ssm) == 9 and all(n[:4] == "ssm_" for n in ssm)
    assert gmu == {"gmu_w1", "gmu_w2"}
    assert set(first["ssm"]) == shared | ssm
    assert set(last["gmu"]) == shared | gmu
    cross = shared | {"wq", "bq", "wo", "bo", "lambda_q1", "lambda_k1",
                      "lambda_q2", "lambda_k2", "subln_scale"}
    assert set(MIXERS["cross"].leaves(tfm._kind_cfg(CFG, "cross"))) == \
        cross - shared
    assert set(last["cross"]) == cross
    assert set(first["window"]) == set(middle["full"]) == \
        cross | {"wk", "bk", "wv", "bv"}
    # stacked over (periods, the kind's layers in a period); G < H
    assert first["ssm"]["ssm_a_log"].shape == (2, 1, 64, 4)
    assert middle["full"]["wk"].shape == (1, 1, 32, 4, 4)
    assert middle["full"]["wq"].shape == (1, 1, 32, 8, 4)
    programs.assert_specs_cover(CFG, params)


def test_logits_equal_the_references(params, their_logits):
    with jax.enable_x64(False):
        got = programs.forward(CFG)(params, _data()[0])
    np.testing.assert_allclose(got, their_logits, atol=2e-5, rtol=2e-4)


def test_loss_equals_the_references(ours, theirs):
    np.testing.assert_allclose(ours[0], theirs[0], rtol=1e-5)


@pytest.mark.parametrize("leaf", programs.leaf_names(CFG))
def test_every_leafs_gradient_equals_the_references(ours, theirs, leaf):
    """Among them `wk`, `wv`, `bk`, `bv` of the "full" layer, which every
    "cross" layer reads, the memory's "ssm" layer (segment 1), which every
    "gmu" layer reads, and the tied embedding, read at both ends: a reader's
    cotangent that did not arrive is a gradient that differs."""
    got, want = (programs.leaves(x[1])[leaf] for x in (ours, theirs))
    size = float(jnp.max(jnp.abs(want)))
    # (a key bias moves every score of a query alike and the softmax does
    # not see it: its gradient is zero on both sides, up to rounding)
    assert size > 1e-6 or "'bk'" in leaf, "nothing to compare"
    np.testing.assert_allclose(got, want, rtol=2e-3,
                               atol=2e-4 * size + 1e-7)


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_a_mechanism_left_out_moves_the_logits(params, their_logits, fault):
    """Each fault the chip's limits must refuse changes the reference's
    logits at this size too: the mechanisms are in the function computed."""
    sound = their_logits
    with jax.enable_x64(False):
        wrong = reference.forward(family.reference_weights(params, KINDS),
                                  _data()[0], KINDS, WINDOW, fault=fault)
    off = float(jnp.sqrt(jnp.mean(jnp.square(wrong - sound))
                         / jnp.mean(jnp.square(sound))))
    assert off > 1e-3, off


def test_the_familys_comparison_reads_zero_for_the_reference(params):
    """`family.compare` (the reference's head a block of tokens at a time)
    against the reference's whole forward pass."""
    tokens, targets = _data()
    with jax.enable_x64(False):
        weights = family.reference_weights(params, KINDS)
        logits = reference.forward(weights, tokens, KINDS, WINDOW)
        rms, got, want = family.compare(params, tokens, logits, KINDS,
                                        WINDOW)
        loss = reference.next_token_loss(logits, targets)
    assert float(rms) < 1e-6
    np.testing.assert_allclose([float(got), float(want)], float(loss),
                               rtol=1e-6)


def test_dp2_equals_one_rank(params, ours):
    tokens, targets = _data()
    with jax.enable_x64(False):
        loss, grads = programs.loss_and_grads(CFG, dp=2)(
            tfm.shard_params(params, CFG, mesh_of(dp=2)), tokens, targets)
    np.testing.assert_allclose(loss, ours[0], rtol=1e-6)
    programs.assert_trees_close(grads, ours[1], rtol=1e-4, atol=1e-6)


def test_a_train_step_lowers_the_loss(params):
    cfg = dataclasses.replace(CFG, remat=True)
    with jax.enable_x64(False):
        losses = [float(loss) for loss, in programs.train(
            cfg, optax.adamw(1e-2), params, _data(), 3)]
    assert losses[2] < losses[0], losses


def test_remat_changes_no_result(params, ours):
    for policy in ("dots", "full"):
        loss, grads = _one_rank(params, dataclasses.replace(
            CFG, remat=True, remat_policy=policy))
        np.testing.assert_allclose(loss, ours[0], rtol=1e-6)
        programs.assert_trees_close(grads, ours[1], rtol=1e-4, atol=1e-7)


REFUSED = [
    (dict(sp=2), {}, "segments require sp=tp=pp=1"),
    (dict(tp=2), {}, "segments require sp=tp=pp=1"),
    (dict(pp=2), {"microbatches": 2}, "segments require sp=tp=pp=1"),
    ({}, {"attn": "ring"}, "need attn 'flash' or 'local'"),
    ({}, {"window": 0}, "'window' layers need window > 0"),
    ({}, {"diff_attention": False}, "'cross' layers are differential"),
    ({}, {"segments": SEGMENTS[2:] + SEGMENTS[:2]},
     "need an earlier segment"),
    ({}, {"segments": (SEGMENTS[0], ((("window", "full"), 1)),
                       SEGMENTS[2])}, "need an earlier segment"),
    ({}, {"n_layers": 12}, "do not add up to n_layers"),
    ({}, {"n_kv_heads": 3}, "n_heads % n_kv_heads"),
    ({}, {"segments": (), "layer_pattern": ("ssm", "full")},
     "need segments"),
    ({}, {"segments": ((("ssm", "sparse"), 5),)}, "names the kind"),
]


@pytest.mark.parametrize("mesh, changed, message", REFUSED)
def test_validate_refuses_by_name(mesh, changed, message):
    cfg = dataclasses.replace(CFG, **changed)
    with pytest.raises(HorovodTpuError, match=message):
        tfm.validate_cfg_for_mesh(cfg, mesh_of(**mesh))


def test_validate_accepts_the_model_on_dp():
    tfm.validate_cfg_for_mesh(CFG, mesh_of(dp=2))


def test_grouped_windowed_attention_without_the_difference():
    """`n_kv_heads` and `window` on a plain stack: every layer windowed,
    two query heads a key head, against the mask written out."""
    cfg = tfm.TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_kv_heads=2, d_ff=48, n_layers=2,
        max_seq=32, window=5, attn="flash", dtype=jnp.float32)
    local = dataclasses.replace(cfg, attn="local")
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 64)
    with jax.enable_x64(False):
        p = programs.init(cfg, 3)
        assert p["layers"]["wk"].shape == (2, 32, 2, 8)
        a = programs.forward(cfg)(p, tokens)
        b = programs.forward(local)(p, tokens)
        whole = programs.forward(dataclasses.replace(local, window=0))(
            p, tokens)
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-4)
    assert float(jnp.max(jnp.abs(b - whole))) > 1e-3
