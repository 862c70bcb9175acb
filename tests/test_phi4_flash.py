"""The Phi-4-mini-flash (SambaY) decoder of `models/transformer.py` (segments
of Mamba-1 state-space layers, windowed and full differential attention over
fewer key and value heads than query heads, Gated Memory Units and
cross-attention that read one layer's memory and keys and values, a tied
head) against the plain reference `benchmark/reference/phi4_flash.py`, at a
small size in float32: the family's statement for `tests/family_cases.py`
(`FAMILY`) and the shared cases (logits; loss and every leaf's gradient of one
rank as the cell runs it, under remat "full", the shared memory's and the
shared keys' and values' among them; `dp` = 2 without remat against it; what
`validate_cfg_for_mesh` refuses); each mechanism in the function computed;
grouped windowed attention alone. (The train step:
`tests/test_hybrid_steps.py`.) Every program is
`tests/family.py`'s, built once for the module."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family as programs
from benchmark.families import phi4_flash as family
from benchmark.reference import phi4_flash as reference
from family_cases import (  # noqa: F401  (the fixtures, the shared tests)
    Family, ours, params, pytest_generate_tests, stated, their_logits,
    theirs,
    test_dp_2_without_remat_equals_one_rank_under_remat,
    test_every_leafs_gradient_equals_the_references,
    test_logits_equal_the_references, test_loss_equals_the_references,
    test_the_familys_comparison_reads_zero_for_the_reference,
    test_validate_accepts_the_model_where_it_runs,
    test_validate_refuses_by_name)
from horovod_tpu.models import transformer as tfm
from horovod_tpu.models.mixers import MIXERS

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
#: what `validate_cfg_for_mesh` refuses: (mesh, changed fields, its words)
REFUSED = (
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
)
#: the cell's remat policy; (among the leaves: `wk`, `wv`, `bk`, `bv` of the
#: "full" layer, which every "cross" layer reads, the memory's "ssm" layer
#: (segment 1), which every "gmu" layer reads, and the tied embedding, read
#: at both ends. A key bias moves every score of a query alike and the
#: softmax does not see it: its gradient is zero on both sides, up to
#: rounding, and may be fainter than the least. `dp` = 2 against one rank
#: to 1e-6, as `test_dp2_equals_one_rank` held it: two ranks sum the tied
#: table's gradient in another order, and 8 of its 3,072 entries, of 1e-4 to
#: 1e-3, then stand 2 to 5e-7 from one rank's)
FAMILY = Family(
    cfg=CFG, family=family, reference=reference,
    timed=dataclasses.replace(CFG, remat=True, remat_policy="full"),
    weights=(KINDS,), args=(KINDS, WINDOW), data=(2, SEQ), refused=REFUSED,
    attns=("flash",), least=1e-6, faint=("'bk'",),
    two_ranks={"rtol": 1e-4, "atol": 1e-6})


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


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_a_mechanism_left_out_moves_the_logits(params, their_logits, fault):
    """Each fault the chip's limits must refuse changes the reference's
    logits at this size too: the mechanisms are in the function computed."""
    sound = their_logits
    with jax.enable_x64(False):
        wrong = FAMILY.their("forward", params, FAMILY.batch[0], fault=fault)
    off = float(jnp.sqrt(jnp.mean(jnp.square(wrong - sound))
                         / jnp.mean(jnp.square(sound))))
    assert off > 1e-3, off


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
