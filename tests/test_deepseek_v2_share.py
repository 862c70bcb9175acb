"""The share of the experts a DeepSeek-V2 chip holds (`parallel/moe.py`
`moe_ffn` with `first_expert`) and its row buffer, beside
`tests/test_deepseek_v2.py` (whose tiny `CFG` this file shares): the eight
shares adding up to the uncut layer, a share against the reference, the
balance loss a sequence, what the buffer leaves out and how a step counts
it, and two ranks with every expert held against the parent's bits."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import family as programs
from benchmark.families import deepseek_v2 as family
from benchmark.reference import deepseek_v2 as reference
from family import mesh_of
from horovod_tpu.common.exceptions import HorovodTpuError
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops.grouped_matmul import ROW_TILE
from horovod_tpu.parallel import moe, moe_ffn
from test_deepseek_v2 import CFG, FIRST, TOP_K, WHOLE, _data

#: (`moe_ep2_parent_pr29.npz` holds bits of programs compiled with them)
pytestmark = pytest.mark.usefixtures("xla_optimizations")


def test_a_share_across_ranks_is_refused():
    with pytest.raises(HorovodTpuError, match="experts_held"):
        tfm.validate_cfg_for_mesh(CFG, mesh_of(ep=2))
    x = jnp.zeros((16, 8), jnp.float32)
    router, up, down, gate = _experts(jax.random.PRNGKey(2), 8, 8, 16)
    with pytest.raises(HorovodTpuError, match="across ranks"):
        _run(x, router, up[:2], down[:2], gate[:2], 2, ep=2)
    with pytest.raises(HorovodTpuError, match="first_expert"):
        _run(x, router, up[:4], down[:4], gate[:4], 2, ep=1, first=5)


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
    wide = programs.init(WHOLE, 4)
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
    params = programs.init(cfg)
    sound, grads, counts = programs.loss_and_grads(cfg, metrics=True)(
        params, tokens, targets)
    assert int(counts["experts_dropped"]) == 0
    plain, plain_grads = programs.loss_and_grads(cfg)(params, tokens,
                                                      targets)
    assert float(plain) == float(sound)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(plain_grads)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # room for 8 rows where the one expert layer's held experts get more
    # (programs built anew, past the memo, which holds them as they are)
    monkeypatch.setattr(moe, "held_rows", lambda *sizes: 8)
    routes = np.asarray(reference.forward(
        family.reference_weights(params), tokens, TOP_K,
        first_expert=FIRST)[2])
    held = int(np.sum((routes >= FIRST) & (routes < FIRST + 2)))
    assert held > 8
    loss, grads, counts = programs.loss_and_grads.__wrapped__(
        cfg, metrics=True)(params, tokens, targets)
    assert int(counts["experts_dropped"]) == held - 8
    assert np.isfinite(float(loss)) and float(loss) != float(sound)
    assert all(np.all(np.isfinite(np.asarray(g)))
               for g in jax.tree_util.tree_leaves(grads))
    opt = optax.sgd(0.1)
    step = programs.train_step.__wrapped__(cfg, opt, metrics=True)
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
