"""Remat-policy A/B for the flagship transformer LM (v5e, B=12 S=1024).

Round-4 verdict Next #6: measure what the jax.checkpoint policy is worth
at the flagship config instead of asserting it. Candidates:

  none  - remat off: save every layer residual (baseline memory-heavy)
  dots  - dots_with_no_batch_dims_saveable: save projection/FFN matmul
          outputs, recompute batched dots (the shipping default)
  full  - policy=None: save nothing, recompute whole layers

Each is timed (host clock behind block_until_ready) at its own
feasibility: a policy that OOMs at B=12 reports so instead of a number.
"""

import time

import jax
import jax.numpy as jnp
import optax
from jax import lax

from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel.mesh import MeshSpec, build_mesh


def time_policy(remat, policy, batch=12, steps=18, chain=6):
    cfg = tfm.TransformerConfig(vocab=32768, d_model=2048, n_heads=16,
                                d_ff=8192, n_layers=12, max_seq=1024,
                                attn="flash", dtype=jnp.bfloat16,
                                remat=remat, remat_policy=policy)
    mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
    params = tfm.shard_params(tfm.init(jax.random.PRNGKey(0), cfg), cfg,
                              mesh)
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)
    step = tfm.build_train_step(cfg, mesh, opt)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, 1024),
                                0, cfg.vocab)
    targets = jnp.roll(tokens, -1, axis=1)

    def body(carry):
        p, o, tok, tgt, _ = carry
        p, o, l = step(p, o, tok, tgt)
        return (p, o, tok, tgt, l)

    scan = jax.jit(lambda s: lax.scan(
        lambda c, _: (body(c), ()), s, None, length=chain)[0],
        donate_argnums=(0,))

    state = (params, opt_state, tokens, targets, jnp.zeros(()))
    for _ in range(2):  # the first call compiles
        state = scan(state)
    jax.block_until_ready(state)
    calls = max(steps // chain, 1)
    t0 = time.perf_counter()
    for _ in range(calls):
        state = scan(state)
    jax.block_until_ready(state)
    sec = (time.perf_counter() - t0) / (calls * chain)
    return batch * 1024 / sec, sec * 1e3


def main():
    print(f"device: {jax.devices()[0].device_kind}")
    for label, remat, policy, batch in (
            ("remat=off B=12", False, "dots", 12),
            ("remat=dots B=12 (shipping)", True, "dots", 12),
            ("remat=full B=12", True, "full", 12),
            ("remat=off B=8", False, "dots", 8),
            ("remat=dots B=16", True, "dots", 16),
    ):
        try:
            tps, ms = time_policy(remat, policy, batch=batch)
            print(f"{label:30s} {tps:9.0f} tok/s   {ms:7.1f} ms/step")
        except Exception as e:
            msg = str(e).splitlines()[0][:120] if str(e) else type(e).__name__
            print(f"{label:30s} FAILED: {msg}")


if __name__ == "__main__":
    main()
