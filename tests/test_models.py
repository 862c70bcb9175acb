"""Model zoo smoke + correctness tests (reference analog: the synthetic
benchmark models, examples/pytorch/pytorch_synthetic_benchmark.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from horovod_tpu.models import mlp, resnet
from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel import MeshSpec, build_mesh


def test_mlp_trains():
    params = mlp.init(jax.random.PRNGKey(0), (16, 32, 4))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
    y = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 4)
    loss0 = float(mlp.loss_fn(params, (x, y)))
    g = jax.grad(mlp.loss_fn)(params, (x, y))
    params = jax.tree_util.tree_map(lambda p, gg: p - 0.5 * gg, params, g)
    assert float(mlp.loss_fn(params, (x, y))) < loss0


def test_resnet50_forward_backward():
    params, stats = resnet.init(jax.random.PRNGKey(0), depth=50,
                                num_classes=10)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3), jnp.float32)
    y = jnp.asarray([1, 2])

    def loss(p):
        l, ns = resnet.loss_fn(p, stats, (x, y), depth=50, train=True)
        return l, ns

    (l, ns), g = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    assert np.isfinite(float(l))
    # BN stats updated.
    assert float(jnp.abs(ns["stem"]["mean"]).sum()) > 0
    # Every param got a gradient.
    leaves = jax.tree_util.tree_leaves(g)
    assert all(np.all(np.isfinite(np.asarray(x))) for x in leaves)


def test_resnet_eval_mode_uses_running_stats():
    params, stats = resnet.init(jax.random.PRNGKey(0), depth=50,
                                num_classes=10)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3), jnp.float32)
    logits, ns = resnet.apply(params, stats, x, depth=50, train=False)
    assert logits.shape == (2, 10)
    # Eval mode must not mutate stats.
    same = jax.tree_util.tree_map(
        lambda a, b: np.array_equal(np.asarray(a), np.asarray(b)), stats, ns)
    assert all(jax.tree_util.tree_leaves(same))


def test_transformer_forward_shapes():
    cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, d_ff=64,
                                n_layers=2, max_seq=64)
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    mesh = build_mesh(MeshSpec(), jax.devices()[:1])
    fwd = jax.jit(tfm.build_forward(cfg, mesh))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    logits = fwd(params, tokens)
    assert logits.shape == (2, 16, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits)))


def test_transformer_flash_attention_matches_local():
    """attn='flash' (Pallas kernel, ops/flash_attention.py) must produce
    the same logits and gradients as the exact 'local' attention."""
    import jax.numpy as jnp
    mk = lambda attn: tfm.TransformerConfig(  # noqa: E731
        vocab=64, d_model=32, n_heads=4, d_ff=64, n_layers=2, max_seq=64,
        attn=attn)
    params = tfm.init(jax.random.PRNGKey(0), mk("local"))
    mesh = build_mesh(MeshSpec(), jax.devices()[:1])
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 64)

    out = {}
    for attn in ("local", "flash"):
        fwd = jax.jit(tfm.build_forward(mk(attn), mesh))
        out[attn] = np.asarray(fwd(params, tokens))
    np.testing.assert_allclose(out["flash"], out["local"],
                               rtol=2e-4, atol=2e-4)

    grads = {}
    for attn in ("local", "flash"):
        cfg = mk(attn)
        fwd = tfm.build_forward(cfg, mesh)

        def loss(p):
            return jnp.mean(jnp.square(fwd(p, tokens)))
        grads[attn] = jax.jit(jax.grad(loss))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4),
        grads["flash"], grads["local"])


def test_graft_entry_hooks():
    import __graft_entry__ as ge
    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert out.ndim == 3
    ge.dryrun_multichip(8)


def test_vgg16_forward_backward():
    """VGG-16 (reference headline scaling model, README.rst:108): fwd
    shapes and a gradient step at a small image size."""
    from horovod_tpu.models import vgg

    params = vgg.init(jax.random.PRNGKey(0), depth=16, num_classes=10,
                      dtype=jnp.float32, image_size=32)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 32, 32, 3)),
                    jnp.float32)
    y = jnp.asarray([1, 7])
    logits = vgg.apply(params, x, depth=16)
    assert logits.shape == (2, 10)
    g = jax.grad(lambda p: vgg.loss_fn(p, (x, y), depth=16))(params)
    gn = sum(float(jnp.sum(jnp.abs(a)))
             for a in jax.tree_util.tree_leaves(g))
    assert np.isfinite(gn) and gn > 0
    # VGG-16 @224/1000 classes is the classic 138M-parameter model
    # (counted from shapes: nothing of that size is allocated)
    p224 = jax.eval_shape(lambda k: vgg.init(
        k, depth=16, num_classes=1000, dtype=jnp.float32, image_size=224),
        jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(p224))
    assert abs(n - 138_357_544) < 1e6, n


def test_inception_v3_forward_backward():
    """Inception V3 (THE reference headline model, README.rst:102): fwd
    shapes, param-count parity with the canonical model, BN stats
    update, gradient step."""
    from horovod_tpu.models import inception

    shapes, _ = jax.eval_shape(lambda k: inception.init(
        k, num_classes=1000, dtype=jnp.float32), jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape))
            for a in jax.tree_util.tree_leaves(shapes))
    # torchvision inception_v3 (aux_logits excluded): 23,834,568
    assert abs(n - 23_834_568) < 5e5, n

    params, stats = inception.init(jax.random.PRNGKey(0), num_classes=7,
                                   dtype=jnp.float32)
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((2, 299, 299, 3)),
        jnp.float32)
    y = jnp.asarray([1, 4])
    # ONE 299x299 pass covers loss, gradients, logits path, and the BN
    # stats refresh (aux) — a separate apply() would double the test cost.
    # Jitted: op by op the same pass compiles each of its ~100 distinct
    # convolutions on its own and takes 4x as long.
    (l, ns), g = jax.jit(jax.value_and_grad(
        lambda p: inception.loss_fn(p, stats, (x, y)), has_aux=True))(params)
    assert np.isfinite(float(l))
    assert not np.allclose(np.asarray(ns["stem"]["c0"]["mean"]),
                           np.asarray(stats["stem"]["c0"]["mean"]))
    gn = sum(float(jnp.sum(jnp.abs(a)))
             for a in jax.tree_util.tree_leaves(g))
    assert np.isfinite(gn) and gn > 0
