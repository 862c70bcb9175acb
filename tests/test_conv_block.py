"""Fused conv+BN+ReLU block (ops/conv_block.py) vs the jax.lax
reference.

The kernels run in interpret mode on the CPU mesh (same fallback as
flash_attention / conv_bn_backward), so these tests exercise the real
pallas_call path: the fused forward (stats ride the matmul pass) and
the fused masked backward are checked against `conv_block_reference` —
the ground truth XLA would compute unfused — and against jax.grad of
the identical math.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.conv_block import (conv1x1_bn_act,
                                        conv1x1_bn_act_nhwc,
                                        conv1x1_bn_relu,
                                        conv1x1_fwd_fused,
                                        conv_block_reference)


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


def test_fwd_kernel_matmul_and_stat_sums():
    """The fused forward's three outputs: y bit-matches the matmul, and
    the resident-accumulator stat rows match the full reductions —
    including with row padding (M=250 is not a sublane multiple)."""
    x, w, _, _ = _mk(250, 16, 64)
    y, ssum, ssq = conv1x1_fwd_fused(x, w)
    yr = x @ w
    _close(y, yr, 1e-6)
    _close(ssum, yr.sum(0), 1e-5)
    _close(ssq, (yr ** 2).sum(0), 1e-5)


@pytest.mark.parametrize("relu", [True, False])
@pytest.mark.parametrize("m,cin,c", [(256, 32, 48), (250, 16, 64)])
def test_forward_matches_reference(m, cin, c, relu):
    x, w, scale, bias = _mk(m, cin, c)
    z_ref, (m_ref, v_ref) = conv_block_reference(x, w, scale, bias,
                                                 1e-5, None, relu)
    z, (mean, var) = conv1x1_bn_act(x, w, scale, bias, 1e-5, None, relu)
    _close(z_ref, z, 1e-5)
    _close(m_ref, mean, 1e-5)
    _close(v_ref, var, 1e-5)


@pytest.mark.parametrize("relu", [True, False])
def test_grads_match_autodiff(relu):
    """All four gradients (x, w, scale, bias) of the fused block match
    jax.grad of the reference — the ReLU mask folded into the kernel
    included."""
    x, w, scale, bias = _mk(256, 32, 48, seed=1)

    def loss_f(f):
        return lambda *a: jnp.sum(jnp.sin(f(*a, 1e-5, None, relu)[0]))

    gr = jax.grad(loss_f(conv_block_reference),
                  argnums=(0, 1, 2, 3))(x, w, scale, bias)
    gf = jax.grad(loss_f(conv1x1_bn_act),
                  argnums=(0, 1, 2, 3))(x, w, scale, bias)
    for a, b in zip(gr, gf):
        _close(a, b, 1e-5)


def test_stats_cotangents_are_exact():
    """A loss that differentiates the returned batch stats (the aux
    outputs) still gets exact gradients — the dmean/dvar cotangents
    fold into the kernel's per-channel vectors."""
    x, w, scale, bias = _mk(96, 8, 16, seed=3)

    def loss_f(f):
        def L(*a):
            z, (mean, var) = f(*a)
            return (jnp.sum(jnp.sin(z)) + 0.3 * jnp.sum(jnp.cos(mean))
                    + 0.1 * jnp.sum(var ** 2))
        return L

    gr = jax.grad(loss_f(conv_block_reference),
                  argnums=(0, 1, 2, 3))(x, w, scale, bias)
    gf = jax.grad(loss_f(conv1x1_bn_relu),
                  argnums=(0, 1, 2, 3))(x, w, scale, bias)
    for a, b in zip(gr, gf):
        _close(a, b, 1e-5)


def test_bf16_path():
    """bf16 in / f32 accumulation: gradients match the reference within
    bf16 tolerance (the ISSUE 12 acceptance bar)."""
    x, w, scale, bias = _mk(256, 32, 48, dtype=jnp.bfloat16)
    scale, bias = scale.astype(jnp.float32), bias.astype(jnp.float32)

    def loss_f(f):
        return lambda *a: jnp.sum(jnp.sin(f(*a)[0].astype(jnp.float32)))

    gr = jax.grad(loss_f(conv_block_reference), argnums=(0, 1))(
        x, w, scale, bias)
    gf = jax.grad(loss_f(conv1x1_bn_relu), argnums=(0, 1))(
        x, w, scale, bias)
    for a, b in zip(gr, gf):
        _close(a.astype(jnp.float32), b.astype(jnp.float32), 2e-2)


def test_bf16_boundary_mask_matches_forward():
    """The ReLU-boundary contract with a bf16 model: the backward mask
    must make the SAME sign decisions as the forward. The fused op's
    epilogue is deliberately all-f32 with final-rounding-only (see
    conv_block_reference) precisely so those decisions are
    reproducible; this test CONSTRUCTS exact boundaries — per channel,
    bias is the exact f32 negation of one row's pre-activation
    product, so the forward zpre is exactly 0 there (ReLU-dead, true
    gradient 0) — and demands tight gradient agreement, which a single
    mask flip (an O(1) elementwise error) breaks."""
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    m, cin, c = 64, 8, 16
    x = jax.random.normal(ks[0], (m, cin), jnp.bfloat16)
    w = jax.random.normal(ks[1], (cin, c), jnp.bfloat16) * 0.1
    scale = jnp.full((c,), 1.015625, jnp.bfloat16)
    # Reproduce the forward chain to place the boundaries.
    y = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32
                            ).astype(jnp.bfloat16)
    yf = y.astype(jnp.float32)
    mean = jnp.mean(yf, axis=0)
    var = jnp.mean(yf ** 2, axis=0) - mean ** 2
    inv = jax.lax.rsqrt(var + 1e-5)
    prod = np.asarray((yf - mean) * inv
                      * scale.astype(jnp.float32), np.float32)
    # zpre == ±1e-5 at one row per channel: a margin far ABOVE any
    # FMA-contraction residue (XLA may fuse the f32 mul+add, so exact-
    # zero cancellation points are not reproducible — measure-zero in
    # training) and far BELOW bf16 rounding (~1e-2 relative), so any
    # reintroduction of storage-dtype arithmetic into the epilogue or
    # the mask flips these signs and fails the tight tolerance.
    delta = 1e-5 * (-1.0) ** np.arange(c)
    bias = jnp.asarray(-prod[np.arange(c) % m, np.arange(c)] + delta,
                       jnp.float32)

    def loss_f(f):
        return lambda *a: jnp.sum(jnp.sin(f(*a)[0].astype(jnp.float32)))

    zr, _ = conv_block_reference(x, w, scale, bias)
    zf, _ = conv1x1_bn_relu(x, w, scale, bias)
    assert np.array_equal(np.asarray(zr, np.float32),
                          np.asarray(zf, np.float32))
    gr = jax.grad(loss_f(conv_block_reference),
                  argnums=(0, 1, 2, 3))(x, w, scale, bias)
    gf = jax.grad(loss_f(conv1x1_bn_relu),
                  argnums=(0, 1, 2, 3))(x, w, scale, bias)
    for a, b in zip(gr, gf):
        _close(a.astype(jnp.float32), b.astype(jnp.float32), 2e-2)


def test_nhwc_wrapper_shapes():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 16),
                          jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 16, 32),
                          jnp.float32) * 0.1
    scale, bias = jnp.ones((32,)), jnp.zeros((32,))
    z, (mean, var) = conv1x1_bn_act_nhwc(x, w, scale, bias)
    assert z.shape == (2, 8, 8, 32)
    assert mean.shape == (32,) and var.shape == (32,)
    z_ref, _ = conv_block_reference(x.reshape(-1, 16),
                                    w.reshape(16, 32), scale, bias)
    _close(z_ref.reshape(2, 8, 8, 32), z, 1e-5)


def test_relu_mask_actually_masks():
    """The backward really is the ReLU backward: gradients w.r.t. x are
    zero wherever the block output is clamped to zero (pin against a
    bias shift that clamps most of one channel)."""
    x, w, scale, _ = _mk(64, 8, 16, seed=5)
    bias = jnp.full((16,), -10.0)  # clamps every channel hard
    z, _ = conv1x1_bn_relu(x, w, scale, bias)
    assert float(jnp.max(z)) == 0.0
    g = jax.grad(lambda x: jnp.sum(conv1x1_bn_relu(
        x, w, scale, bias)[0]))(x)
    _close(g, jnp.zeros_like(g), 1e-12)


def test_sync_bn_semantics_across_mesh():
    """Under shard_map with axis_name, the fused block computes GLOBAL
    batch stats and gradients whose psum equals the single-device
    oracle — sync-BN semantics (models/resnet.batch_norm contract)."""
    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs), ("hvd",))
    m, cin, c = 64, 8, 16
    x, w, scale, bias = _mk(m, cin, c, seed=7)

    def local(x_loc, w, scale, bias):
        def loss(x_loc, w, scale, bias):
            z, st = conv1x1_bn_act(x_loc, w, scale, bias, 1e-5, "hvd",
                                   True)
            return jnp.sum(jnp.sin(z)), st
        (l, st), g = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3), has_aux=True)(x_loc, w, scale,
                                                      bias)
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

    def oracle_loss(x, w, scale, bias):
        z, st = conv_block_reference(x, w, scale, bias)
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


def test_resnet_block_path_matches_unfused(monkeypatch):
    """The model-level wire-up (models/resnet.py HOROVOD_CONV_BLOCK):
    loss, gradients, and running-stat updates are identical with the
    fused block family on and off. Mini 2-block depth keeps
    interpret-mode runtime testable."""
    from horovod_tpu.models import resnet

    resnet.STAGE_BLOCKS[8] = (1, 1)  # test-only mini depth
    try:
        params, stats = resnet.init(jax.random.PRNGKey(0), depth=8,
                                    num_classes=10, dtype=jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3),
                              jnp.float32)
        yl = jax.random.randint(jax.random.PRNGKey(2), (2,), 0, 10)

        def run(block):
            monkeypatch.setenv("HOROVOD_CONV_BLOCK",
                               "1" if block else "0")

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


def test_kernels_lower_through_real_tpu_compiler(monkeypatch):
    """Both new kernels compile for a real v5e topology (compile-only
    client, zero chips) at a representative ResNet site — probe shared
    with the conv_bn_backward suite (tests/tpu_probe.py, which also
    switches the kernels from the interpreter to Mosaic)."""
    from tpu_probe import compile_kernel_text, tpu_topology

    from horovod_tpu.ops.conv_block import (conv1x1_bn_act_bwd_fused,
                                            conv1x1_fwd_fused)

    topo = tpu_topology(monkeypatch)
    m, cin, c = 128 * 28 * 28, 128, 512

    def st(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt)
    vec = lambda: st((c,), jnp.float32)  # noqa: E731
    compile_kernel_text(topo, conv1x1_fwd_fused,
                        (st((m, cin)), st((cin, c))))
    compile_kernel_text(
        topo,
        lambda dz, y, x, w, s, b, mean, inv, db, dg:
        conv1x1_bn_act_bwd_fused(dz, y, x, w, s, b, mean, inv, db, dg),
        (st((m, c)), st((m, c)), st((m, cin)), st((cin, c)),
         vec(), vec(), vec(), vec(), vec(), vec()))
