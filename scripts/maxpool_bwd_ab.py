"""A/B: one-hot max-pool backward (ops/pooling.py) vs SelectAndScatter.

Times jax.grad of a pooled sum at the real Inception V3 / ResNet-50
pool sites, dependency-chained inside one lax.scan (same discipline as
scripts/bn_conv_bwd_ab.py — naive repeated calls get DCE'd/overlapped
and read as faster than HBM allows).
"""

import time

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.ops.pooling import max_pool

SITES = [  # (name, x-shape, window, strides, padding)
    ("incep stem pool1 147x147x64", (64, 147, 147, 64), (3, 3), (2, 2),
     "VALID"),
    ("incep stem pool2 71x71x192", (64, 71, 71, 192), (3, 3), (2, 2),
     "VALID"),
    ("incep reductionA 35x35x288", (64, 35, 35, 288), (3, 3), (2, 2),
     "VALID"),
    ("incep reductionB 17x17x768", (64, 17, 17, 768), (3, 3), (2, 2),
     "VALID"),
    ("resnet stem 112x112x64 SAME", (128, 112, 112, 64), (3, 3), (2, 2),
     "SAME"),
]
CHAIN = 48


def _ref_pool(x, window, strides, padding):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, *window, 1),
                             (1, *strides, 1), padding)


def _chain_ms(grad_fn, x):
    @jax.jit
    def prog(x):
        def body(carry, _):
            xc, _ = carry
            g = grad_fn(xc)
            gb = lax.optimization_barrier(g)
            dep = (gb[0, 0, 0, 0] * 1e-30).astype(x.dtype)
            return (x + dep, dep), ()
        return lax.scan(body, (x, jnp.zeros((), x.dtype)), None,
                        length=CHAIN)[0][1]

    def sync(o):
        jax.block_until_ready(o)
        float(o)

    def run(n):
        t0 = time.perf_counter()
        o = None
        for _ in range(n):
            o = prog(x)
        sync(o)
        return time.perf_counter() - t0

    sync(prog(x))  # the first call compiles
    return run(3) / (3 * CHAIN) * 1e3


def main():
    print(f"device: {jax.devices()[0].device_kind}")
    tot_sas, tot_fast = 0.0, 0.0
    for name, shape, window, strides, padding in SITES:
        x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.bfloat16)
        ref_grad = jax.grad(lambda x: jnp.sum(_ref_pool(
            x, window, strides, padding).astype(jnp.float32)))
        fast_grad = jax.grad(lambda x: jnp.sum(max_pool(
            x, window, strides, padding).astype(jnp.float32)))
        t_sas = _chain_ms(ref_grad, x)
        t_fast = _chain_ms(fast_grad, x)
        print(f"{name:30s} SelectAndScatter {t_sas:6.2f} ms   "
              f"one-hot {t_fast:6.2f} ms   ({t_sas / t_fast:4.2f}x)")
        tot_sas += t_sas
        tot_fast += t_fast
    print(f"{'TOTAL':30s} SelectAndScatter {tot_sas:6.2f} ms   "
          f"one-hot {tot_fast:6.2f} ms   ({tot_sas / tot_fast:4.2f}x)")


if __name__ == "__main__":
    main()
