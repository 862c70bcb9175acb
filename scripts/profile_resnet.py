"""Per-component ResNet-50 step breakdown (timed by bench.py
_scan_timed). Establishes where the step time goes
before attacking the ~50%-MFU HBM roofline (docs/benchmarks.md).

Usage: python scripts/profile_resnet.py [batch ...]
"""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

from bench import _scan_timed  # ONE copy of the timing logic
from horovod_tpu.models import resnet
from horovod_tpu.profiler import flops as F

# ONE home for peak/model FLOPs constants: profiler/flops.py (the MAC
# convention matches the historical numbers this script printed).
PEAK = F.peak_flops_per_chip("TPU v5 lite")
RESNET50_TRAIN_FLOPS = F.resnet_train_flops_per_image(50, "macs")
RESNET50_FWD_FLOPS = F.RESNET_FWD_GMACS[50] * 1e9


def timed(body, state, chain=10, reps=3, warmup=2):
    return _scan_timed(body, state, chain=chain, reps=reps, warmup=warmup)


def make_step(batch, fwd_only=False, dtype=jnp.bfloat16):
    params, stats = resnet.init(jax.random.PRNGKey(0), depth=50,
                                num_classes=1000, dtype=dtype)
    opt = optax.sgd(0.1, momentum=0.9)
    opt_state = opt.init(params)
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.standard_normal((batch, 224, 224, 3),
                                             np.float32).astype(dtype))
    labels = jnp.asarray(rng.integers(0, 1000, (batch,)))

    def loss(p, s):
        return resnet.loss_fn(p, s, (images, labels), depth=50, train=True)

    if fwd_only:
        def body(carry):
            p, s, o, _ = carry
            l, ns = loss(p, s)
            # feed the loss back into the params: without a carry
            # dependency XLA hoists the whole loop-invariant forward out
            # of the scan and the timing reads ~0
            p = jax.tree_util.tree_map(
                lambda a: a + (l * 1e-30).astype(a.dtype), p)
            return (p, ns, o, l)
    else:
        def body(carry):
            p, s, o, _ = carry
            (l, ns), g = jax.value_and_grad(loss, has_aux=True)(p, s)
            updates, no = opt.update(g, o, p)
            return (optax.apply_updates(p, updates), ns, no, l)
    state = (params, stats, opt_state, jnp.zeros(()))
    return body, state


def main():
    import horovod_tpu.models.resnet as rn
    batches = [int(b) for b in sys.argv[1:]] or [128, 256]
    # Patch the resnet module's own _reduce_window hook — NOT
    # jax.lax.reduce_window, which is shared process-wide.
    orig_rw = rn._reduce_window
    for b in batches:
        for label, patch in (
                ("maxpool  ", None),
                ("avgpool  ", "avg"),   # cheap-bwd pool: isolates
                ("nopool   ", "skip"),  # SelectAndScatter cost
        ):
            if patch == "avg":
                # init must be a CONCRETE scalar or reduce_window takes
                # the generic (non-differentiable) variadic path
                rn._reduce_window = lambda x, init, op, wd, ws, pad: \
                    orig_rw(x, np.zeros((), x.dtype)[()], lax.add, wd, ws,
                            pad) / 9.0
            elif patch == "skip":
                rn._reduce_window = \
                    lambda x, init, op, wd, ws, pad: x[:, ::2, ::2, :]
            try:
                body, state = make_step(b)
                t = timed(body, state)
                ips = b / t
                print(f"B={b} {label} full: {t*1e3:6.1f} ms, {ips:6.0f} "
                      f"img/s, MFU {ips*RESNET50_TRAIN_FLOPS/PEAK:.1%}",
                      flush=True)
                if patch is None:
                    body, state = make_step(b, fwd_only=True)
                    t = timed(body, state)
                    print(f"B={b} {label} fwd:  {t*1e3:6.1f} ms "
                          f"(fwd MFU {b/t*RESNET50_FWD_FLOPS/PEAK:.1%})",
                          flush=True)
            finally:
                rn._reduce_window = orig_rw


if __name__ == "__main__":
    main()
