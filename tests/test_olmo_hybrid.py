"""The Olmo-Hybrid pattern of `models/transformer.py` (three gated-delta-rule
linear-attention layers to one full-attention layer, post-sub-layer norms,
no rotary embedding) against the plain reference
`benchmark/reference/olmo_hybrid.py`, at a small size in float32; the
chunked gated delta rule of `ops/gated_delta.py` against the token-by-token
recurrence, outputs and gradients; the convolution's causality; `dp` = 2
against one rank; and what `validate_cfg_for_mesh` refuses."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import olmo_hybrid as family
from benchmark.reference import olmo_hybrid as reference
from horovod_tpu.common.exceptions import HorovodTpuError
from horovod_tpu.models import transformer as tfm
from horovod_tpu.models.mixers import MIXERS
from horovod_tpu.ops import gated_delta
from horovod_tpu.ops.causal_conv import causal_conv_silu
from horovod_tpu.ops.gated_delta import (chunked_over_recurrent_macs,
                                         chunks_of, gated_delta_rule,
                                         heads_a_step,
                                         recurrent_gated_delta_rule)
from horovod_tpu.parallel import MeshSpec, build_mesh

PATTERN = ("linear", "linear", "linear", "full")
CFG = tfm.TransformerConfig(
    vocab=96, d_model=48, n_heads=3, d_ff=80, n_layers=8, max_seq=64,
    norm="rmsnorm", rms_norm_eps=1e-6, positions="none", qk_norm=True,
    mlp="swiglu", post_norm=True, layer_pattern=PATTERN, gdn_heads=3,
    gdn_key_dim=8, gdn_value_dim=16, gdn_conv=4, gdn_neg_eigval=True,
    attn="flash", dtype=jnp.float32)


def mesh_of(**sizes):
    spec = MeshSpec(**sizes)
    return build_mesh(spec, jax.devices()[:spec.total])


def _data(batch=4, seq=40):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                CFG.vocab, jnp.int32)
    return tokens, jnp.roll(tokens, -1, axis=1)


@pytest.fixture(scope="module")
def params():
    p = tfm.init(jax.random.PRNGKey(0), CFG)
    # norm scales off their initial ones, so that a misplaced one shows
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 64))

    def moved(path, x):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return x + 0.3 * jax.random.normal(next(keys), x.shape, x.dtype)
        return x
    return jax.tree_util.tree_map_with_path(moved, p)


# ------------------------------------------------------ the chunked rule

def _rule_inputs(seq, *, strong, seed=0, batch=2, heads=3, dk=8, dv=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    f32 = jnp.float32

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (batch, heads, seq, dk), f32)) \
        * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (batch, heads, seq, dk), f32))
    v = jax.random.normal(ks[2], (batch, heads, seq, dv), f32)
    # strong: a state forgotten within a few tokens; weak: kept for hundreds
    g = -jax.random.uniform(ks[3], (batch, heads, seq), f32) \
        * (8.0 if strong else 0.02)
    # beta on both sides of 1: eigenvalues 1 - beta of both signs
    beta = 2 * jax.nn.sigmoid(
        2 * jax.random.normal(ks[4], (batch, heads, seq), f32))
    assert float(beta.min()) < 0.5 and float(beta.max()) > 1.5
    return q, k, v, g, beta


def _grads(rule, args, cot):
    return jax.grad(lambda *a: jnp.sum(rule(*a) * cot),
                    argnums=(0, 1, 2, 3, 4))(*args)


@pytest.mark.parametrize("strong", [False, True], ids=["weak", "strong"])
@pytest.mark.parametrize("seq", [64, 100, 128, 7])
@pytest.mark.parametrize("chunk", [16, 64])
def test_the_chunked_rule_is_the_recurrence(chunk, seq, strong):
    """Outputs and the gradient of every input, at lengths that are and are
    not multiples of the chunk (the padding rows leave the state alone)."""
    args = _rule_inputs(seq, strong=strong)
    got = gated_delta_rule(*args, chunk=chunk)
    want = recurrent_gated_delta_rule(*args)
    assert got.shape == want.shape == (2, 3, seq, 16)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    cot = jax.random.normal(jax.random.PRNGKey(9), want.shape, jnp.float32)
    for name, g, w in zip("q k v g beta".split(),
                          _grads(lambda *a: gated_delta_rule(*a, chunk=chunk),
                                 args, cot),
                          _grads(recurrent_gated_delta_rule, args, cot)):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-4 * scale, name


@pytest.mark.parametrize("batch, heads", [(1, 7), (2, 4)],
                         ids=["7-heads-in-blocks-of-4", "2-x-4-heads"])
def test_the_rule_at_the_cells_widths_several_heads_a_grid_step(batch, heads):
    """Keys 96 and values 192 wide, as `olmohybrid-1chip` has them: four
    heads a grid step, which seven heads do not fill (the eighth is padding
    that does nothing) and which is all of a batch entry's four, two chunks
    and a part of a third."""
    assert heads_a_step(heads, 96, 192, itemsize=4) == 4
    args = _rule_inputs(150, strong=False, batch=batch, heads=heads, dk=96,
                        dv=192)
    got = jax.jit(gated_delta_rule)(*args)
    want = jax.jit(recurrent_gated_delta_rule)(*args)
    assert got.shape == want.shape == (batch, heads, 150, 192)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    cot = jax.random.normal(jax.random.PRNGKey(9), want.shape, jnp.float32)
    for name, g, w in zip("q k v g beta".split(),
                          jax.jit(lambda *a: _grads(gated_delta_rule, a,
                                                    cot))(*args),
                          jax.jit(lambda *a: _grads(
                              recurrent_gated_delta_rule, a, cot))(*args)):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-4 * scale, name


def test_heads_a_grid_step_come_from_vmem_and_the_head_count():
    # bf16 at the cell's widths: six of its thirty heads a step, no padding
    assert heads_a_step(30, 96, 192) == 6
    assert gated_delta.step_bytes(96, 192) * 6 <= gated_delta._VMEM_BUDGET
    assert gated_delta.step_bytes(96, 192) * 7 > gated_delta._VMEM_BUDGET
    # no divisor in the upper half of what fits: the most, heads padded
    assert heads_a_step(7, 96, 192) == 6
    assert heads_a_step(1, 8, 16) == 1 and heads_a_step(6, 8, 16) == 6
    # wide heads: one a step however little fits
    assert heads_a_step(4, 2048, 2048) == 1


def _strong_chunk(heads=2, c=64, dk=16, seed=3):
    """One chunk whose keys are nearly one direction and whose beta is all
    but 2: A's entries reach 1.9 under the diagonal and its powers grow to
    1e15 before they vanish."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    f32 = jnp.float32
    k = jax.random.normal(ks[0], (heads, 1, dk), f32) \
        + 0.2 * jax.random.normal(ks[1], (heads, c, dk), f32)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (heads, c, 32), f32)
    beta = 2.0 - 0.05 * jax.random.uniform(ks[3], (heads, 1, 1, c), f32)
    g = jnp.full((heads, 1, 1, c), -1e-3, f32)
    return k, v, jnp.cumsum(g, axis=-1), beta


def _a_of(k, b, beta):
    c = k.shape[1]
    b, beta = b[:, 0, 0], beta[:, 0, 0]
    below = jnp.arange(c)[:, None] > jnp.arange(c)[None, :]
    with jax.default_matmul_precision("highest"):
        kk = jnp.einsum("hik,hjk->hij", k, k)
    return jnp.where(below, beta[:, :, None] * jnp.exp(
        b[:, :, None] - b[:, None, :]) * kk, 0.0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_kernels_inverse_is_the_triangular_solve_at_strong_beta(dtype):
    """T = (I + A)^-1 as the forward kernel leaves it, and U_0 = T (beta V),
    against `lax.linalg.triangular_solve` in float32 on the same (rounded)
    inputs: an inverse, or the product that applies it, in bf16 would be
    2^-9 off, three hundred times the tolerance."""
    k, v, b, beta = _strong_chunk()
    k, v = k.astype(dtype), v.astype(dtype)
    heads, c, _ = k.shape
    _, (w, u0, t, _) = gated_delta._forward(
        k[None], k[None], v[None], b, beta, c=c, heads=heads, save=True)
    assert t.dtype == u0.dtype == jnp.float32 and w.dtype == dtype
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    a = _a_of(kf, b, beta)
    assert float(jnp.max(jnp.abs(a))) > 1.8
    eye = jnp.eye(c, dtype=jnp.float32)
    rhs = jnp.concatenate([jnp.broadcast_to(eye, a.shape),
                           beta[:, 0, 0, :, None] * vf], axis=-1)
    with jax.default_matmul_precision("highest"):
        solved = jax.lax.linalg.triangular_solve(
            a + eye, rhs, left_side=True, lower=True, unit_diagonal=True)
    for name, got, want in (("T", t, solved[..., :c]),
                            ("U_0", u0, solved[..., c:])):
        scale = float(jnp.max(jnp.abs(want)))
        assert float(jnp.max(jnp.abs(got - want))) <= 6e-6 * scale, name
    # what the doubling product (I - A)(I + A^2)(I + A^4).. would have to
    # carry in float32 on its way to entries of size `scale`
    power = a
    for _ in range(4):
        with jax.default_matmul_precision("highest"):
            power = power @ power
    assert float(jnp.max(jnp.abs(power))) > 1e9 * float(
        jnp.max(jnp.abs(solved[..., :c])))


def test_the_saved_states_are_the_recurrences_in_float32():
    """The entry state of every chunk, as the forward saves it for the
    backward walk, against the recurrence's state at the same token: held
    in float32, so closer than bf16 could hold it."""
    q, k, v, g, beta = _rule_inputs(192, strong=False, batch=1, heads=2)
    heads, c = 2, 64
    gates = [x.reshape(2, 3, 1, c) for x in (g, beta)]
    _, (_, _, _, s0) = gated_delta._forward(
        q, k, v, jnp.cumsum(gates[0], axis=-1), gates[1], c=c, heads=heads,
        save=True)
    assert s0.shape == (2, 3, 8, 16) and s0.dtype == jnp.float32
    state = jnp.zeros((2, 8, 16), jnp.float32)
    for t in range(128):
        state = jnp.exp(g[0, :, t])[:, None, None] * state
        u = beta[0, :, t, None] * (v[0, :, t] - jnp.einsum(
            "hkv,hk->hv", state, k[0, :, t], precision="highest"))
        state = state + k[0, :, t, :, None] * u[:, None, :]
        if t + 1 in (64, 128):
            np.testing.assert_allclose(s0[:, (t + 1) // 64], state,
                                       atol=1e-5, rtol=1e-5)
    assert float(jnp.max(jnp.abs(s0[:, 0]))) == 0.0


def test_the_rule_in_bf16_keeps_its_state_in_float32():
    args = _rule_inputs(256, strong=False)
    q, k, v, g, beta = args
    got = gated_delta_rule(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                           v.astype(jnp.bfloat16), g, beta)
    assert got.dtype == jnp.bfloat16
    want = recurrent_gated_delta_rule(*args)
    err = jnp.sqrt(jnp.mean(jnp.square(got.astype(jnp.float32) - want))
                   / jnp.mean(jnp.square(want)))
    assert float(err) < 4 * 2.0 ** -8


def test_the_static_numbers_of_the_chunked_form():
    assert chunks_of(8192) == 128 and chunks_of(100) == 2
    assert chunks_of(100, 16) == 7
    # per token and head: K K^T and Q K^T 2 x 64 x 96, the solve
    # 64 x (96 + 192) / 2, three products with the state 3 x 96 x 192, the
    # scores' product 64 x 192, over the recurrence's 3 x 96 x 192
    macs = 2 * 64 * 96 + 64 * 288 / 2 + 3 * 96 * 192 + 64 * 192
    assert chunked_over_recurrent_macs(96, 192) == macs / (3 * 96 * 192)
    assert chunked_over_recurrent_macs(96, 192) == pytest.approx(1.61,
                                                                 abs=0.01)
    for chunk in (0, 4, 12):      # a chunk is whole registers of 8 rows
        with pytest.raises(ValueError):
            gated_delta_rule(*_rule_inputs(8, strong=False), chunk=chunk)


# ----------------------------------------------------------- the mixer

def test_the_convolution_is_causal():
    """A change at token t moves nothing before t, in the mixer's output
    and so in the model's logits."""
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 20, 8), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(3), (3, 8, 4), jnp.float32)
    base = causal_conv_silu(u, w)
    moved = causal_conv_silu(u.at[:, :, 11].add(1.0), w)
    changed = np.flatnonzero(np.any(np.asarray(base != moved),
                                    axis=(0, 1, 3)))
    assert changed.tolist() == [11, 12, 13, 14]       # four taps
    # against the reference's shifted adds (its layout is (B, S, H, d))
    want = reference.causal_conv(u.transpose(0, 2, 1, 3), w)
    np.testing.assert_allclose(base, want.transpose(0, 2, 1, 3), atol=1e-6)


def test_a_later_token_moves_no_earlier_logit(params):
    tokens, _ = _data(batch=1)
    fwd = jax.jit(tfm.build_forward(CFG, mesh_of()))
    base = fwd(params, tokens)
    moved = fwd(params, tokens.at[0, 25].set((tokens[0, 25] + 1) % 96))
    np.testing.assert_array_equal(base[:, :25], moved[:, :25])
    assert float(jnp.max(jnp.abs(base[:, 25:] - moved[:, 25:]))) > 1e-3


def test_the_pattern_has_the_leaves_each_kind_has(params):
    layers = params["layers"]
    assert sorted(layers) == ["full", "linear"]
    assert "pos" not in params and "lnf_bias" not in params
    shared = {"ln1_scale", "ln2_scale", "w1", "w2", "w_gate", "wo"}
    assert set(layers["full"]) == shared | {"wq", "wk", "wv", "q_scale",
                                            "k_scale"}
    gdn = set(MIXERS["gdn"].leaves(CFG))
    assert len(gdn) == 13 and {n for n in gdn if n[:4] != "gdn_"} == {"wo"}
    assert set(layers["linear"]) == shared | gdn
    # (periods, layers of the kind in a period, ...)
    assert layers["full"]["wq"].shape == (2, 1, 48, 3, 16)
    assert layers["linear"]["gdn_wq"].shape == (2, 3, 48, 3, 8)
    assert layers["linear"]["gdn_wv"].shape == (2, 3, 48, 3, 16)
    assert layers["linear"]["gdn_conv_v"].shape == (2, 3, 3, 16, 4)
    assert layers["linear"]["gdn_o_scale"].shape == (2, 3, 16)
    assert layers["linear"]["wo"].shape == (2, 3, 3, 16, 48)
    specs, axes = tfm.param_specs(CFG), tfm.grad_reduce_axes(CFG)
    same = jax.tree_util.tree_structure(params)
    assert jax.tree_util.tree_structure(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)) \
        == same
    assert jax.tree_util.tree_structure(
        axes, is_leaf=lambda x: isinstance(x, tuple)) == same
    assert len(specs["layers"]["linear"]["gdn_wq"]) == 5
    # decays as the public implementation draws them: A in (0, 16), the
    # step in (0.001, 0.1)
    rate = jnp.exp(layers["linear"]["gdn_a_log"])
    step = jax.nn.softplus(layers["linear"]["gdn_dt_bias"])
    assert 0 < float(rate.min()) and float(rate.max()) <= 16
    assert 1e-3 <= float(step.min()) and float(step.max()) <= 0.1 + 1e-6


def test_a_stack_of_one_kind_keeps_its_leaves():
    plain = tfm.TransformerConfig(vocab=32, d_model=16, n_heads=2, d_ff=32,
                                  n_layers=2)
    p = tfm.init(jax.random.PRNGKey(0), plain)
    assert not set(p["layers"]) & set(MIXERS["gdn"].leaves(CFG)) - {"wo"}
    assert p["layers"]["wq"].shape == (2, 16, 2, 8)
    # a whole stack of linear layers needs no pattern
    linear = dataclasses.replace(CFG, layer_pattern=(), attention="gdn",
                                 n_layers=2)
    p = tfm.init(jax.random.PRNGKey(0), linear)
    assert p["layers"]["gdn_wq"].shape == (2, 48, 3, 8)
    assert "wq" not in p["layers"]


# ------------------------------------------- the model and the reference

def test_logits_and_loss_match_the_reference(params):
    tokens, targets = _data()
    logits = jax.jit(tfm.build_forward(CFG, mesh_of()))(params, tokens)
    weights = family.reference_weights(params, PATTERN)
    assert len(weights["layers"]) == 8
    assert ["a_log" in w for w in weights["layers"]] == \
        [True, True, True, False] * 2
    want = reference.forward(weights, tokens)
    np.testing.assert_allclose(logits, want, atol=1e-3, rtol=1e-3)
    loss, _ = jax.jit(tfm.build_loss_and_grads(CFG, mesh_of()))(
        params, tokens, targets)
    assert float(loss) == pytest.approx(
        float(reference.loss(weights, tokens, targets)), rel=1e-5)


@pytest.mark.parametrize("remat_policy", [None, "dots", "full"])
def test_every_gradient_leaf_matches_the_reference(params, remat_policy):
    cfg = CFG if remat_policy is None else dataclasses.replace(
        CFG, remat=True, remat_policy=remat_policy)
    tokens, targets = _data()
    _, grads = jax.jit(tfm.build_loss_and_grads(cfg, mesh_of()))(
        params, tokens, targets)
    want = jax.grad(lambda p: reference.loss(
        family.reference_weights(p, PATTERN), tokens, targets))(params)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    wanted = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    assert len(flat) == len(wanted) == 3 + 11 + 18
    for path, g in flat:
        w = wanted[path]
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(g - w))) <= 2e-3 * scale, \
            jax.tree_util.keystr(path)


def test_the_limits_refuse_lower_precisions():
    """At a toy width, in float32 on the CPU: the reference with 8-bit
    operands is far outside the logits' limit; its state carried in bf16 is
    a real difference (the chip's readings are in the family's file)."""
    cfg = dataclasses.replace(CFG, d_model=96, n_heads=3, d_ff=160,
                              n_layers=4)
    p = tfm.init(jax.random.PRNGKey(4), cfg)
    tokens, _ = _data(batch=1, seq=64)
    weights = family.reference_weights(p, PATTERN)
    want = reference.forward(weights, tokens)

    def rms(got):
        return float(jnp.sqrt(jnp.mean(jnp.square(got - want))
                              / jnp.mean(jnp.square(want))))

    assert rms(reference.forward(weights, tokens,
                                 operands=jnp.float8_e4m3fn)) \
        > 3 * family.LOGITS_RMS_TOL
    assert rms(reference.forward(weights, tokens, state=jnp.bfloat16)) > 1e-4
    assert family.within(0.02, 9.9, 9.9) == (True, True)
    assert family.within(0.03, 9.9, 9.9006) == (False, False)


# ------------------------------------------------------------ the meshes

def test_two_data_parallel_ranks_equal_one(params):
    """`dp` = 2 reduces the new leaves inside the backward loop like any
    layer's: loss and every gradient leaf as on one rank. (In float32 the
    rule's running sums of the log decay leave ~1e-4 between two batch
    shapes; the comparison is made in float64.)"""
    cfg = dataclasses.replace(CFG, dtype=jnp.float64)
    p64 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float64), params)
    tokens, targets = _data()
    out = {}
    for dp in (1, 2):
        mesh = mesh_of(dp=dp)
        tfm.validate_cfg_for_mesh(cfg, mesh)
        out[dp] = jax.jit(tfm.build_loss_and_grads(cfg, mesh))(
            tfm.shard_params(p64, cfg, mesh), tokens, targets)
    assert float(out[2][0]) == pytest.approx(float(out[1][0]), rel=1e-6)
    for (path, one), two in zip(
            jax.tree_util.tree_flatten_with_path(out[1][1])[0],
            jax.tree_util.tree_leaves(out[2][1])):
        scale = float(np.max(np.abs(np.asarray(one))))
        assert float(np.max(np.abs(np.asarray(one) - np.asarray(two)))) \
            <= 1e-5 * scale, jax.tree_util.keystr(path)


def test_two_ranks_scatter_the_new_leaves_in_the_backward_loop():
    mesh = mesh_of(dp=2)
    cfg = dataclasses.replace(CFG, remat=True)
    shapes = jax.eval_shape(lambda k: tfm.init(k, cfg), jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((4, 40), jnp.int32)
    text = jax.jit(tfm.build_loss_and_grads(cfg, mesh)).lower(
        shapes, tokens, tokens).as_text()
    # one exchange inside the loop for each leaf that is no vector: 13 of a
    # linear layer's 18 leaves (all but the two norms' scales, the gated
    # norm's, A_log and dt_bias) and 9 of a full layer's 11; the vectors
    # are psum'd after the loop
    assert text.count("collective_permute") == 13 + 9


def test_the_train_step_learns_the_fixed_batch(params):
    import optax
    mesh = mesh_of()
    opt = optax.adamw(3e-3)
    cfg = dataclasses.replace(CFG, remat=True)
    p = tfm.shard_params(jax.tree_util.tree_map(jnp.copy, params), cfg, mesh)
    state = tfm.init_opt_state(opt, p, mesh)
    step = tfm.build_train_step(cfg, mesh, opt)
    tokens, targets = _data()
    losses = []
    for _ in range(4):
        p, state, loss = step(p, state, tokens, targets)
        losses.append(float(loss))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]


@pytest.mark.parametrize("sizes, cfg, message", [
    ({"sp": 2}, CFG, "linear-attention layers require sp=1"),
    ({"tp": 3}, CFG, "linear-attention layers require tp=1"),
    ({"pp": 2}, dataclasses.replace(CFG, microbatches=2),
     "a layer pattern requires pp=1"),
    # a pattern's layers may be expert layers since PR 38, and may be
    # windowed: what is still refused is a window of no keys
    ({}, dataclasses.replace(CFG, layer_pattern=("linear", "window")),
     "'window' layers need window > 0"),
    ({}, dataclasses.replace(CFG, n_layers=6),
     "no whole number of periods"),
    ({}, dataclasses.replace(CFG, layer_pattern=("linear", "sparse")),
     "names the kind 'sparse'"),
    # a kind since PR 36, which a pattern alone cannot hold
    ({}, dataclasses.replace(CFG, layer_pattern=("linear", "ssm")),
     "need segments"),
    ({"sp": 2}, dataclasses.replace(CFG, layer_pattern=(), attention="gdn"),
     "linear-attention layers require sp=1"),
])
def test_what_linear_layers_cannot_do_yet_is_refused_by_name(sizes, cfg,
                                                             message):
    with pytest.raises(HorovodTpuError, match=message):
        tfm.validate_cfg_for_mesh(cfg, mesh_of(**sizes))


def test_the_pattern_validates_on_the_meshes_it_runs_on():
    tfm.validate_cfg_for_mesh(CFG, mesh_of())
    tfm.validate_cfg_for_mesh(CFG, mesh_of(dp=2))
    tfm.validate_cfg_for_mesh(CFG, mesh_of(dp=2, ep=2))
