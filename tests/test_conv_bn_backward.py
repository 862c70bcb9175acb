"""Fused conv1x1+BN backward (ops/conv_bn_backward.py) vs autodiff.

The kernel runs in interpret mode on the CPU mesh (same fallback as
flash_attention), so these tests exercise the real pallas_call path.
Gradients are checked against jax.grad of the identical forward math —
the ground truth XLA would compute unfused.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.conv_bn_backward import (conv1x1_bn, conv1x1_bn_nhwc)


def _ref(x, w, scale, bias, eps=1e-5):
    y = x @ w
    mean = jnp.mean(y, axis=0)
    var = jnp.mean(y ** 2, axis=0) - mean ** 2
    inv = jax.lax.rsqrt(var + eps)
    z = (y - mean) * inv * scale + bias
    return z, (mean, var)


def _mk(m, cin, c, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (m, cin), dtype),
            jax.random.normal(ks[1], (cin, c), dtype) * 0.1,
            jax.random.normal(ks[2], (c,), dtype) * 0.5 + 1.0,
            jax.random.normal(ks[3], (c,), dtype) * 0.1)


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) <= tol * (np.max(np.abs(a)) + 1e-9), \
        (np.max(np.abs(a - b)), np.max(np.abs(a)))


@pytest.mark.parametrize("m,cin,c", [(256, 32, 48), (250, 16, 64)])
def test_grads_match_autodiff(m, cin, c):
    x, w, scale, bias = _mk(m, cin, c)

    def loss_f(f):
        return lambda *a: jnp.sum(jnp.sin(f(*a)[0]))

    gr = jax.grad(loss_f(_ref), argnums=(0, 1, 2, 3))(x, w, scale, bias)
    gf = jax.grad(loss_f(conv1x1_bn), argnums=(0, 1, 2, 3))(
        x, w, scale, bias)
    for a, b in zip(gr, gf):
        _close(a, b, 1e-5)


def test_forward_matches_and_stats():
    x, w, scale, bias = _mk(128, 8, 16)
    z_ref, (m_ref, v_ref) = _ref(x, w, scale, bias)
    z, (mean, var) = conv1x1_bn(x, w, scale, bias)
    _close(z_ref, z, 1e-5)
    _close(m_ref, mean, 1e-5)
    _close(v_ref, var, 1e-5)


def test_stats_cotangents_are_exact():
    """A loss that differentiates the returned batch stats (the aux
    outputs) still gets exact gradients — the dmean/dvar cotangents fold
    into the kernel's per-channel vectors."""
    x, w, scale, bias = _mk(96, 8, 16, seed=3)

    def loss_f(f):
        def L(*a):
            z, (mean, var) = f(*a)
            return (jnp.sum(jnp.sin(z)) + 0.3 * jnp.sum(jnp.cos(mean))
                    + 0.1 * jnp.sum(var ** 2))
        return L

    gr = jax.grad(loss_f(_ref), argnums=(0, 1, 2, 3))(x, w, scale, bias)
    gf = jax.grad(loss_f(conv1x1_bn), argnums=(0, 1, 2, 3))(
        x, w, scale, bias)
    for a, b in zip(gr, gf):
        _close(a, b, 1e-5)


def test_nhwc_wrapper_shapes():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 16),
                          jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 16, 32),
                          jnp.float32) * 0.1
    scale, bias = jnp.ones((32,)), jnp.zeros((32,))
    z, (mean, var) = conv1x1_bn_nhwc(x, w, scale, bias)
    assert z.shape == (2, 8, 8, 32)
    assert mean.shape == (32,) and var.shape == (32,)
    # matches the flattened-row reference
    z_ref, _ = _ref(x.reshape(-1, 16), w.reshape(16, 32), scale, bias)
    _close(z_ref.reshape(2, 8, 8, 32), z, 1e-5)


def test_bf16_path():
    x, w, scale, bias = _mk(256, 32, 48, dtype=jnp.bfloat16)
    scale, bias = scale.astype(jnp.float32), bias.astype(jnp.float32)

    def loss_f(f):
        return lambda *a: jnp.sum(jnp.sin(f(*a)[0].astype(jnp.float32)))

    gr = jax.grad(loss_f(_ref), argnums=(0, 1))(x, w, scale, bias)
    gf = jax.grad(loss_f(conv1x1_bn), argnums=(0, 1))(x, w, scale, bias)
    for a, b in zip(gr, gf):
        _close(a.astype(jnp.float32), b.astype(jnp.float32), 2e-2)


def test_resnet_fused_path_matches_unfused(monkeypatch):
    """The model-level wire-up (models/resnet.py _fused_conv_bn_site):
    loss, gradients, and running-stat updates are identical with the
    fused backward on and off. Mini 2-block depth keeps interpret-mode
    runtime testable."""
    from horovod_tpu.models import resnet

    resnet.STAGE_BLOCKS[8] = (1, 1)  # test-only mini depth
    try:
        params, stats = resnet.init(jax.random.PRNGKey(0), depth=8,
                                    num_classes=10, dtype=jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3),
                              jnp.float32)
        yl = jax.random.randint(jax.random.PRNGKey(2), (2,), 0, 10)

        def run(fuse):
            monkeypatch.setenv("HOROVOD_FUSE_CONV_BN",
                               "1" if fuse else "0")

            def loss(p):
                return resnet.loss_fn(p, stats, (x, yl), depth=8,
                                      train=True)
            (l, ns), g = jax.value_and_grad(loss, has_aux=True)(params)
            return l, ns, g

        l0, ns0, g0 = run(False)
        l1, ns1, g1 = run(True)
        assert abs(float(l0) - float(l1)) < 1e-5
        for a, b in zip(jax.tree_util.tree_leaves(g0),
                        jax.tree_util.tree_leaves(g1)):
            _close(a, b, 1e-4)
        for a, b in zip(jax.tree_util.tree_leaves(ns0),
                        jax.tree_util.tree_leaves(ns1)):
            _close(a, b, 1e-4)
    finally:
        resnet.STAGE_BLOCKS.pop(8, None)


def test_sync_bn_semantics_across_mesh():
    """Under shard_map with axis_name, the fused op computes GLOBAL batch
    stats and gradients whose psum equals the single-device oracle —
    sync-BN semantics (models/resnet.batch_norm contract)."""
    from functools import partial

    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs), ("hvd",))
    m, cin, c = 64, 8, 16
    x, w, scale, bias = _mk(m, cin, c, seed=7)

    def local(x_loc, w, scale, bias):
        def loss(x_loc, w, scale, bias):
            z, (mean, var) = conv1x1_bn(x_loc, w, scale, bias, 1e-5,
                                        "hvd")
            return jnp.sum(jnp.sin(z)), (mean, var)
        (l, st), g = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3), has_aux=True)(x_loc, w, scale,
                                                      bias)
        # param grads are per-rank partials; psum completes them (the
        # framework's gradient reduction role)
        gw = jax.lax.psum(g[1], "hvd")
        gs = jax.lax.psum(g[2], "hvd")
        gb = jax.lax.psum(g[3], "hvd")
        return jax.lax.psum(l, "hvd"), st, g[0], gw, gs, gb

    sharded = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("hvd"), P(), P(), P()),
        out_specs=(P(), P(), P("hvd"), P(), P(), P()),
        check_vma=False))
    l_sh, (mean_sh, var_sh), gx_sh, gw_sh, gs_sh, gb_sh = sharded(
        x, w, scale, bias)

    # single-device oracle: the same loss over the FULL batch
    def oracle_loss(x, w, scale, bias):
        z, st = _ref(x, w, scale, bias)
        return jnp.sum(jnp.sin(z)), st
    (l_o, (mean_o, var_o)), g_o = jax.value_and_grad(
        oracle_loss, argnums=(0, 1, 2, 3), has_aux=True)(x, w, scale,
                                                         bias)
    assert abs(float(l_sh) - float(l_o)) < 1e-4
    _close(mean_o, mean_sh, 1e-5)
    _close(var_o, var_sh, 1e-5)
    _close(g_o[0], gx_sh, 1e-4)
    _close(g_o[1], gw_sh, 1e-4)
    _close(g_o[2], gs_sh, 1e-4)
    _close(g_o[3], gb_sh, 1e-4)


def test_kernel_lowers_through_real_tpu_compiler(monkeypatch):
    """Pin the opt-in path's Mosaic lowering: the fused backward compiles
    for a real v5e topology (compile-only client, zero chips) at a
    representative site AND at the VMEM-tightest site that OOM'd during
    development (Cin=512, C=2048 — the resident f32 dW accumulator).
    Probe shared with the conv_block suite (tests/tpu_probe.py, which
    also switches the kernels from the interpreter to Mosaic); skips
    only where the topology cannot be described."""
    from tpu_probe import compile_kernel_text, tpu_topology

    topo = tpu_topology(monkeypatch)
    from horovod_tpu.ops.conv_bn_backward import conv1x1_bn_bwd_fused

    for m, cin, c in ((128 * 28 * 28, 128, 512), (6272, 512, 2048)):
        def st(shape, dt=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dt)
        vec = lambda: st((c,), jnp.float32)  # noqa: E731
        compile_kernel_text(
            topo, conv1x1_bn_bwd_fused,
            (st((m, c)), st((m, c)), st((m, cin)), st((cin, c)),
             vec(), vec(), vec(), vec(), vec()))
