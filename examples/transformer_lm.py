"""Multi-axis parallel transformer LM training.

No reference equivalent (the reference is data-parallel only, SURVEY.md
§2.6); this showcases the mesh axes that make the framework TPU-first:
dp × tp × sp with ring attention for long context, or pp/ep variants.

Run:  python examples/transformer_lm.py --tp 2 --sp 2   (8 virtual devices)
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel import MeshSpec, build_mesh


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--sp", type=int, default=1)
    p.add_argument("--pp", type=int, default=1)
    p.add_argument("--ep", type=int, default=1)
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--n-layers", type=int, default=4)
    p.add_argument("--n-heads", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--attn", default="ring",
                   choices=["ring", "ulysses", "local"])
    p.add_argument("--num-experts", type=int, default=0)
    args = p.parse_args()

    hvd.init()
    n = len(jax.devices())
    spec = MeshSpec.infer(n, tp=args.tp, sp=args.sp, pp=args.pp, ep=args.ep)
    mesh = build_mesh(spec)
    cfg = tfm.TransformerConfig(
        vocab=8192, d_model=args.d_model, n_heads=args.n_heads,
        d_ff=args.d_model * 4, n_layers=args.n_layers,
        max_seq=args.seq_len * 2, attn=args.attn,
        num_experts=args.num_experts,
        microbatches=2 if args.pp > 1 else 1, dtype=jnp.bfloat16)
    tfm.validate_cfg_for_mesh(cfg, mesh)

    params = tfm.shard_params(tfm.init(jax.random.PRNGKey(0), cfg), cfg,
                              mesh)
    opt = optax.adamw(3e-4)
    opt_state = tfm.init_opt_state(opt, params, mesh)
    step = tfm.build_train_step(cfg, mesh, opt)

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab,
                                      (args.batch_size, args.seq_len)))
    targets = jnp.roll(tokens, -1, axis=1)

    params, opt_state, loss = step(params, opt_state, tokens, targets)
    print(f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"compile done, initial loss {float(loss):.3f}")

    t0 = time.perf_counter()
    for _ in range(args.steps):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
    final = float(loss)  # readback forces completion
    dt = time.perf_counter() - t0
    toks = args.batch_size * args.seq_len * args.steps
    print(f"{toks / dt:.0f} tokens/sec, final loss {final:.3f}")


if __name__ == "__main__":
    main()
