"""Hybrid-parallel (GSPMD) tied-LM training over HOROVOD_MESH.

The runtime face of the program `make shard-lint` gates: a
tied-embedding LM trained model-sharded through
`hvd.DistributedOptimizer(sharding_spec=...)` on the named-axis mesh
the HOROVOD_MESH knob declares (docs/parallelism.md). Run it on the
8-device virtual CPU mesh:

    JAX_PLATFORMS=cpu HOROVOD_TPU_EMULATE_RANKS=8 HOROVOD_MESH="dp=2,tp=4" \
        python examples/hybrid_lm.py

or leave HOROVOD_MESH unset for the pure data-parallel twin
(dp = all devices) — same model, same step builder, same loss
trajectory (pinned by tests/test_gspmd.py).
"""

import argparse
import time

import jax
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import tied_lm
from horovod_tpu.parallel.mesh import MeshSpec, build_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()

    hvd.init()
    mesh = hvd.hybrid_mesh()
    if mesh is None:
        # No HOROVOD_MESH: the pure-DP twin on the same builder.
        mesh = build_mesh(MeshSpec.infer(hvd.size()))
    spec = MeshSpec(**{a: int(s) for a, s in
                       zip(mesh.axis_names, mesh.devices.shape)})
    cfg = tied_lm.canonical_config()
    params = tied_lm.init(0, cfg)
    tok, tgt = tied_lm.sample_batch(1, cfg, batch=args.batch,
                                    seq=args.seq)

    opt = hvd.DistributedOptimizer(
        optax.adam(args.lr), sharding_spec=tied_lm.param_specs(cfg),
        mesh=mesh)
    step = opt.sharded_step(
        lambda p, b: tied_lm.local_loss(p, b[0], b[1], cfg),
        donate=False)
    params = opt.shard_params(params)
    batch = jax.device_put((tok, tgt), NamedSharding(mesh, P("dp")))
    opt_state = opt.init(params)

    t0 = time.perf_counter()
    loss = None
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, batch)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(loss):.4f}", flush=True)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    toks = args.batch * args.seq * args.steps
    print(f"mesh {spec.describe()} on {spec.total} devices: "
          f"{args.steps / dt:.2f} steps/s, {toks / dt:.0f} tokens/s")


if __name__ == "__main__":
    main()
