"""Model zoo smoke + correctness tests, the image models (reference analog:
the synthetic benchmark models,
examples/pytorch/pytorch_synthetic_benchmark.py). The transformer's and the
graft entry: `tests/test_models_lm.py` (two files, so that the two long
passes, Inception V3's and the entry's dry run, end in parallel: a file of
few tests is handed out last and is the run's tail)."""

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models import mlp, resnet


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
    logits, ns = jax.jit(lambda p, s: resnet.apply(
        p, s, x, depth=50, train=False))(params, stats)
    assert logits.shape == (2, 10)
    # Eval mode must not mutate stats.
    same = jax.tree_util.tree_map(
        lambda a, b: np.array_equal(np.asarray(a), np.asarray(b)), stats, ns)
    assert all(jax.tree_util.tree_leaves(same))


def test_vgg16_forward_backward():
    """VGG-16 (reference headline scaling model, README.rst:108): fwd
    shapes and a gradient step at a small image size."""
    from horovod_tpu.models import vgg

    params = vgg.init(jax.random.PRNGKey(0), depth=16, num_classes=10,
                      dtype=jnp.float32, image_size=32)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 32, 32, 3)),
                    jnp.float32)
    y = jnp.asarray([1, 7])
    # (jitted: op by op each layer's operations compile on their own)
    logits = jax.jit(lambda p: vgg.apply(p, x, depth=16))(params)
    assert logits.shape == (2, 10)
    g = jax.jit(jax.grad(lambda p: vgg.loss_fn(p, (x, y), depth=16)))(params)
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
