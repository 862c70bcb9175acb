"""Parallelism-strategy correctness vs single-device oracles.

Mirrors the reference's test style (numerical oracle comparison, e.g.
test_adasum_pytorch.py compares against a NumPy implementation): every
sharded program must match the unsharded math bit-for-bit or to fp tolerance
on the 8-device CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import family as programs
from family import mesh_of
from horovod_tpu.parallel import (
    moe_ffn, pipeline_apply, ring_attention, ulysses_attention,
)
from horovod_tpu.parallel.ring_attention import blockwise_attention_reference
from horovod_tpu.models import transformer as tfm


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("use_flash", [True, False])
def test_ring_attention_matches_oracle(causal, use_flash):
    """Both ring paths: per-hop Pallas flash chunks with log-space merge,
    and the streaming jnp fallback."""
    B, H, S, dh, SP = 2, 4, 16, 8, 4
    key = jax.random.PRNGKey(0)
    q, k, v = [jax.random.normal(kk, (B, H, S, dh))
               for kk in jax.random.split(key, 3)]
    oracle = blockwise_attention_reference(q, k, v, causal=causal)

    m = mesh_of(sp=SP)
    spec = P(None, None, "sp", None)

    def f(q, k, v):
        return ring_attention(q, k, v, "sp", causal=causal,
                              use_flash=use_flash)

    out = jax.jit(jax.shard_map(
        f, mesh=m, in_specs=(spec,) * 3, out_specs=spec,
        check_vma=False))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("use_flash", [True, False])
def test_ring_attention_grad_matches_oracle(use_flash):
    B, H, S, dh, SP = 1, 2, 8, 4, 4
    key = jax.random.PRNGKey(1)
    q, k, v = [jax.random.normal(kk, (B, H, S, dh))
               for kk in jax.random.split(key, 3)]

    def loss_oracle(qkv):
        return jnp.sum(blockwise_attention_reference(*qkv, causal=True) ** 2)

    go = jax.grad(loss_oracle)((q, k, v))

    m = mesh_of(sp=SP)
    spec = P(None, None, "sp", None)

    def local(qkv):
        # Local loss contribution only — no psum before grad: psum's
        # transpose would scale cotangents by the axis size. The ppermute
        # transposes route k/v cotangents back to their source ranks.
        out = ring_attention(*qkv, "sp", causal=True,
                             use_flash=use_flash)
        return jnp.sum(out ** 2)

    def loss_sharded(qkv):
        f = jax.shard_map(lambda t: jax.grad(local)(t), mesh=m,
                          in_specs=((spec,) * 3,), out_specs=(spec,) * 3,
                          check_vma=False)
        return f(qkv)

    gs = jax.jit(loss_sharded)((q, k, v))
    for a, b in zip(gs, go):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_ulysses_matches_oracle():
    B, H, S, dh, SP = 2, 8, 16, 4, 4
    key = jax.random.PRNGKey(2)
    q, k, v = [jax.random.normal(kk, (B, H, S, dh))
               for kk in jax.random.split(key, 3)]
    oracle = blockwise_attention_reference(q, k, v, causal=True)
    m = mesh_of(sp=SP)
    spec = P(None, None, "sp", None)
    out = jax.jit(jax.shard_map(
        lambda a, b, c: ulysses_attention(a, b, c, "sp", causal=True),
        mesh=m, in_specs=(spec,) * 3, out_specs=spec,
        check_vma=False))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)


def test_pipeline_matches_sequential():
    PP, L, M, mb, D = 4, 8, 4, 2, 16
    key = jax.random.PRNGKey(3)
    ws = jax.random.normal(key, (L, D, D)) / D ** 0.5
    x = jax.random.normal(jax.random.PRNGKey(4), (M, mb, D))

    def layer(a, w):
        return jnp.tanh(a @ w), None

    def seq_apply(xm):
        out, _ = lax.scan(layer, xm, ws)
        return out

    oracle = jax.vmap(seq_apply)(x)

    m = mesh_of(pp=PP)

    def stage_fn(stage_ws, act):
        out, _ = lax.scan(layer, act, stage_ws)
        return out

    def run(ws_sharded, xm):
        y = pipeline_apply(stage_fn, ws_sharded, xm, "pp")
        # emit zeros except on last stage; psum collapses to the real value
        return lax.psum(y, "pp")

    out = jax.jit(jax.shard_map(
        run, mesh=m, in_specs=(P("pp"), P()), out_specs=P(),
        check_vma=False))(ws, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)


def test_moe_sharded_matches_single():
    EP, T, D, F, E = 4, 32, 8, 16, 8
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (T, D))
    router = jax.random.normal(ks[1], (D, E))
    w1 = jax.random.normal(ks[2], (E, D, F)) / D ** 0.5
    w2 = jax.random.normal(ks[3], (E, F, D)) / F ** 0.5

    # Oracle: dense top-1 MoE with no capacity drops.
    logits = x @ router
    probs = jax.nn.softmax(logits, -1)
    eidx = jnp.argmax(probs, -1)
    gate = jnp.max(probs, -1)
    h = jax.nn.gelu(jnp.einsum("td,edf->tef", x, w1))
    y_all = jnp.einsum("tef,efd->ted", h, w2)
    oracle = y_all[jnp.arange(T), eidx] * gate[:, None]

    m = mesh_of(ep=EP)
    out = jax.jit(jax.shard_map(
        lambda xx, r, a, b: moe_ffn(xx, r, a, b, top_k=1, axis_name="ep",
                                    capacity_factor=64.0)[0],
        mesh=m,
        in_specs=(P("ep"), P(), P("ep"), P("ep")),
        out_specs=P("ep"), check_vma=False))(x, router, w1, w2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Transformer flagship: sharded loss == single-device loss; step runs.
# ---------------------------------------------------------------------------

CFG = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, d_ff=64,
                            n_layers=4, max_seq=64, attn="ring")


def _data(cfg, B=8, S=16):
    k = jax.random.PRNGKey(7)
    tokens = jax.random.randint(k, (B, S), 0, cfg.vocab)
    targets = jnp.roll(tokens, -1, axis=1)
    return tokens, targets


def _loss_single(cfg, params, tokens, targets):
    return programs.loss_and_grads(cfg)(params, tokens, targets)


@pytest.mark.parametrize("spec", [
    dict(dp=2, tp=2, sp=2),
    dict(dp=2, sp=4),
    dict(dp=8),
    dict(tp=4, dp=2),
])
def test_transformer_loss_matches_single_device(spec):
    cfg = CFG
    params = programs.init(cfg)
    tokens, targets = _data(cfg)
    loss1, grads1 = _loss_single(cfg, params, tokens, targets)

    m = mesh_of(**spec)
    tfm.validate_cfg_for_mesh(cfg, m)
    loss, grads = programs.loss_and_grads(cfg, **spec)(params, tokens,
                                                       targets)
    np.testing.assert_allclose(float(loss), float(loss1), rtol=1e-4)
    programs.assert_trees_close(grads, grads1, rtol=5e-4, atol=5e-4)


def test_transformer_pipeline_loss_matches():
    cfg = dataclasses_replace(CFG, microbatches=2)
    params = programs.init(cfg)
    tokens, targets = _data(cfg)
    loss1, grads1 = _loss_single(
        dataclasses_replace(CFG, microbatches=1), params, tokens, targets)

    loss, grads = programs.loss_and_grads(cfg, pp=2, dp=2, sp=2)(
        params, tokens, targets)
    np.testing.assert_allclose(float(loss), float(loss1), rtol=1e-4)
    programs.assert_trees_close(grads, grads1, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("remat,policy", [(True, "dots"), (True, "full"),
                                          (False, "dots")])
def test_transformer_grads_reduced_in_backward_match(remat, policy):
    """The layers' gradients leave the backward loop reduce-scattered
    (halving over dp x sp, the /tp rescale inside) whatever the layer scan
    saves: same loss and gradients as one device, leaf by leaf."""
    cfg = dataclasses_replace(CFG, remat=remat, remat_policy=policy)
    params = programs.init(cfg)
    tokens, targets = _data(cfg)
    loss1, grads1 = _loss_single(cfg, params, tokens, targets)
    assert tfm._reduces_in_backward(cfg, mesh_of(dp=2, tp=2, sp=2))
    loss, grads = programs.loss_and_grads(cfg, dp=2, tp=2, sp=2)(
        params, tokens, targets)
    np.testing.assert_allclose(float(loss), float(loss1), rtol=1e-4)
    programs.assert_trees_close(grads, grads1, rtol=5e-4, atol=5e-4)


def _collectives(jaxpr):
    """(primitive, axes, operand shape, result shape) of every collective
    of `jaxpr`, those of nested jaxprs (shard_map, scan, remat) included;
    one in a scan body counts once."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("psum", "ppermute", "all_gather", "reduce_scatter"):
            axes = eqn.params.get("axes", eqn.params.get("axis_name"))
            axes = axes if isinstance(axes, tuple) else (axes,)
            found.extend((name, axes, x.aval.shape, y.aval.shape)
                         for x, y in zip(eqn.invars, eqn.outvars))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found.extend(_collectives(sub))
    return found


@pytest.mark.parametrize("spec,micro", [
    (dict(dp=8), 1),
    (dict(dp=2, tp=2, sp=2), 1),
    (dict(pp=2, dp=2, sp=2), 2),
])
def test_every_gradient_leaf_is_reduced_once_per_axis(spec, micro):
    """In the traced program every gradient leaf meets exactly one
    reduction per reduce axis of more than one rank: none twice, none
    missed. A psum covers its axes; so does the one all-gather that
    completes a leaf reduce-scattered in the backward loop, which also has
    its log2(n) halving exchanges and no psum. Leaves are told by their
    per-shard shapes: sizes at which no activation, and no piece of a
    scattered leaf, has the shape of another leaf."""
    from collections import Counter
    from horovod_tpu.parallel.mesh import mesh_axis_sizes

    cfg = dataclasses_replace(CFG, microbatches=micro, n_layers=6, vocab=80,
                              max_seq=128)
    m = mesh_of(**spec)
    tfm.validate_cfg_for_mesh(cfg, m)
    sizes = mesh_axis_sizes(m)
    params = programs.init(cfg)
    tokens, targets = _data(cfg)
    jaxpr = jax.make_jaxpr(programs.loss_and_grads(cfg, **spec))(
        params, tokens, targets).jaxpr

    def shard_shape(x, pspec):
        names = tuple(pspec) + (None,) * (x.ndim - len(pspec))
        return tuple(d // (sizes[a] if a else 1)
                     for d, a in zip(x.shape, names))

    shapes = jax.tree_util.tree_map(shard_shape, params,
                                    tfm.param_specs(cfg))
    is_axes = lambda x: isinstance(x, tuple)   # noqa: E731
    leaves = list(zip(
        jax.tree_util.tree_leaves(shapes, is_leaf=is_axes),
        jax.tree_util.tree_leaves(tfm.grad_reduce_axes(cfg),
                                  is_leaf=is_axes)))
    want = Counter((shape, a) for shape, axes in leaves for a in axes
                   if sizes[a] > 1)

    collectives = _collectives(jaxpr)
    got, exchanges = Counter(), Counter()
    for name, axes, operand, result in collectives:
        covered = {"psum": operand, "all_gather": result}.get(name)
        for a in axes:
            if sizes[a] > 1 and covered is not None:
                got[(covered, a)] += 1
        if name == "ppermute":
            exchanges[operand] += 1
    leaf_shapes = {shape for shape, _ in leaves}
    got = Counter({k: v for k, v in got.items() if k[0] in leaf_shapes})
    assert got == want

    # the halving exchanges of the leaves scattered in the backward loop
    halves = Counter()
    if tfm._reduces_in_backward(cfg, m):
        for shape, axes in leaves:
            axes = [a for a in axes if sizes[a] > 1]
            n = int(np.prod([sizes[a] for a in axes]))
            layer = shape[1:]
            dim = next((d for d, s in enumerate(layer) if s % n == 0), None)
            if len(shape) < 3 or dim is None:      # not a stacked matrix
                continue
            pieces = {layer}
            while n > 1:
                layer = layer[:dim] + (layer[dim] // 2,) + layer[dim + 1:]
                halves[layer] += 1
                pieces.add(layer)
                n //= 2
            # ... and is psum'd nowhere on its way through the loop
            assert not [c for c in collectives if c[0] == "psum"
                        and c[2] in pieces
                        and any(sizes[a] > 1 for a in c[1])]
        assert halves, "no leaf is reduced inside the backward loop"
    assert {s: exchanges[s] for s in halves} == dict(halves)
    if micro > 1:
        assert not any(name == "all_gather" for name, *_ in collectives)


def test_transformer_moe_train_step_runs():
    cfg = dataclasses_replace(CFG, num_experts=4, attn="ring")
    params = programs.init(cfg)
    tokens, targets = _data(cfg)
    m = mesh_of(dp=2, ep=2, sp=2)
    tfm.validate_cfg_for_mesh(cfg, m)
    opt = optax.sgd(1e-2)
    params = tfm.shard_params(params, cfg, m)
    before = jax.tree_util.tree_map(np.asarray, params)  # step donates params
    step = programs.train_step(cfg, opt, dp=2, ep=2, sp=2)
    opt_state = opt.init(params)
    p2, _, loss = step(params, opt_state, tokens, targets)
    assert np.isfinite(float(loss))
    # Params actually moved.
    moved = jax.tree_util.tree_map(
        lambda a, b: float(np.max(np.abs(np.asarray(a) - b))), p2, before)
    assert max(jax.tree_util.tree_leaves(moved)) > 0


def dataclasses_replace(cfg, **kw):
    import dataclasses
    return dataclasses.replace(cfg, **kw)


def test_ring_attention_bf16_tolerance():
    """bf16 inputs through the flash-chunk ring: the merge accumulates in
    f32 (chunks are upcast), so error stays at bf16-input level — not
    P per-hop quantizations."""
    B, H, S, dh, SP = 1, 2, 16, 8, 4
    key = jax.random.PRNGKey(3)
    q, k, v = [jax.random.normal(kk, (B, H, S, dh), jnp.bfloat16)
               for kk in jax.random.split(key, 3)]
    oracle = blockwise_attention_reference(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32), causal=True)

    m = mesh_of(sp=SP)
    spec = P(None, None, "sp", None)
    out = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=True),
        mesh=m, in_specs=(spec,) * 3, out_specs=spec,
        check_vma=False))(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(oracle), rtol=2e-2, atol=2e-2)
