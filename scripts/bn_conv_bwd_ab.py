"""Layer-level A/B: fused Pallas conv1x1+BN backward vs the XLA sequence.

Measures, per ResNet-50 layer site (B=128 shapes), the backward-path cost
the fusion targets:

  XLA:    dy = bn_bwd_elemwise(dz, y, sums)  [materialized in HBM]
          dx = dy @ w.T ; dw = x^T @ dy
  fused:  conv_bn_backward.conv1x1_bn_bwd_fused (dy never leaves VMEM)

Pass A (the dbeta/dgamma reductions) is identical in both and excluded.

Timing: CHAIN iterations inside one compiled lax.scan, with a
dependency injected through the scale vector (scale + 1e-30*prev_out) so
iterations cannot overlap or be elided — naive repeated calls with
constant inputs measured FASTER than the HBM roofline allows (r05 first
attempt: 0.18 ms for 0.33 GB = 1.8 TB/s, impossible), so those numbers
were artifacts. Plain host clock over whole scan calls, behind
`block_until_ready`.
"""

import time

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.ops.conv_bn_backward import conv1x1_bn_bwd_fused

# (name, M, Cin, C): conv1/conv3 sites of ResNet-50 at B=128, 224px
SITES = [
    ("s0.conv3 56x56 64->256", 128 * 56 * 56, 64, 256),
    ("s0.conv1 56x56 256->64", 128 * 56 * 56, 256, 64),
    ("s1.conv3 28x28 128->512", 128 * 28 * 28, 128, 512),
    ("s1.conv1 28x28 512->128", 128 * 28 * 28, 512, 128),
    ("s2.conv3 14x14 256->1024", 128 * 14 * 14, 256, 1024),
    ("s2.conv1 14x14 1024->256", 128 * 14 * 14, 1024, 256),
    ("s3.conv3 7x7 512->2048", 128 * 7 * 7, 512, 2048),
]
CHAIN = 64  # long chains: 64 iters x ~0.5-2 ms per call dwarfs the
# host's per-call dispatch cost


def xla_seq(dz, y, x, w, scale, mean, inv, db, dg):
    m = dz.shape[0]
    xhat = (y.astype(jnp.float32) - mean) * inv
    dy = ((scale * inv) * (dz.astype(jnp.float32)
                           - (db + xhat * dg) / m)).astype(dz.dtype)
    dx = lax.dot_general(dy, w, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32).astype(x.dtype)
    dw = lax.dot_general(x, dy, (((0,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32)
    return dx, dw


def _chain_ms(fn, args):
    """ms per call of fn(*args) with a scan-chained dependency: each
    iteration's scale is perturbed by the previous dw, forcing strict
    sequential execution on device."""
    scale = args[4]

    @jax.jit
    def prog(s0, dz, y, x, w, mean, inv, db, dg):
        # big operands are jit ARGUMENTS: closure-captured arrays would
        # be embedded in the program as 200 MB of literals
        def body(carry, _):
            s, prev = carry
            dx, dw = fn(dz, y, x, w, s, mean, inv, db, dg)
            # optimization_barrier: without it XLA slices the whole
            # computation to the one column the scalar dep reads (r05
            # first attempts measured 76 TB/s — dead-code elimination,
            # not speed). The barrier forces FULL dx/dw materialization
            # with zero extra memory traffic in both arms.
            dxb, dwb = jax.lax.optimization_barrier((dx, dw))
            dep = ((dxb[0, 0].astype(jnp.float32) + dwb[0, 0])
                   * 1e-30).astype(s0.dtype)
            return (s0 + dep, dep), ()

        return lax.scan(body, (s0, jnp.zeros((), s0.dtype)), None,
                        length=CHAIN)[0][1]

    def sync(o):
        jax.block_until_ready(o)
        float(o)

    pargs = (args[4], args[0], args[1], args[2], args[3], args[5],
             args[6], args[7], args[8])

    def run(n):
        t0 = time.perf_counter()
        o = None
        for _ in range(n):
            o = prog(*pargs)
        sync(o)
        return time.perf_counter() - t0

    sync(prog(*pargs))  # the first call compiles
    return run(3) / (3 * CHAIN) * 1e3


def main():
    print(f"device: {jax.devices()[0].device_kind}")
    total_xla, total_fused = 0.0, 0.0
    for name, m, cin, c in SITES:
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        args = (jax.random.normal(ks[0], (m, c), jnp.bfloat16),
                jax.random.normal(ks[1], (m, c), jnp.bfloat16),
                jax.random.normal(ks[2], (m, cin), jnp.bfloat16),
                jax.random.normal(ks[0], (cin, c), jnp.bfloat16) * 0.05,
                jnp.ones((c,), jnp.float32), jnp.zeros((c,), jnp.float32),
                jnp.ones((c,), jnp.float32), jnp.zeros((c,), jnp.float32),
                jnp.zeros((c,), jnp.float32))
        t_xla = _chain_ms(xla_seq, args)
        t_fused = _chain_ms(conv1x1_bn_bwd_fused, args)
        gb_unfused = (5 * m * c * 2 + 2 * m * cin * 2) / 2**30
        gb_fused = (2 * m * c * 2 + 2 * m * cin * 2) / 2**30
        print(f"{name:28s} XLA {t_xla:7.2f} ms ({gb_unfused / t_xla * 1e3:5.0f} GB/s)"
              f"   fused {t_fused:7.2f} ms ({gb_fused / t_fused * 1e3:5.0f} GB/s)"
              f"   {t_xla / t_fused:4.2f}x")
        total_xla += t_xla
        total_fused += t_fused
    print(f"{'TOTAL (sites above)':28s} XLA {total_xla:7.2f} ms   "
          f"fused {total_fused:7.2f} ms  ({total_xla / total_fused:4.2f}x)")


if __name__ == "__main__":
    main()
