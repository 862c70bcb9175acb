"""Long-context attention showcase: flash kernel + ring/Ulysses scaling.

What the reference cannot do at all (no sequence parallelism, SURVEY §2.6)
and the heart of this framework's long-context story:

1. Single chip: the Pallas flash kernel runs exact causal attention at
   sequence lengths where score-materializing attention cannot exist
   (S=32k: the B·H·S² score matrix alone would be 32 GiB vs 16 GB HBM).
2. Beyond one chip: shard the sequence over the `sp` mesh axis — ring
   attention circulates K/V blocks over ICI with the SAME kernel inside
   each hop, keeping per-chip memory O(S/sp); Ulysses re-shards
   heads/sequence with all_to_all instead.

Run:  python examples/long_context.py --seq 8192
      python examples/long_context.py --seq 4096 --sp 4   (virtual CPU ok:
        XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu)
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def single_chip(seq: int, heads: int, dh: int):
    from horovod_tpu.ops.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (1, heads, seq, dh), jnp.bfloat16)
               for kk in ks)
    fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    # Warm up before timing: the first call compiles.
    for _ in range(5):
        out = fn(q, k, v)
    jax.block_until_ready(out)
    np.asarray(out[0, 0, 0])
    t0 = time.perf_counter()
    for _ in range(5):
        out = fn(q, k, v)
    jax.block_until_ready(out)
    np.asarray(out[0, 0, 0])
    dt = (time.perf_counter() - t0) / 5
    score_gib = 1 * heads * seq * seq * 2 / 2**30
    print(f"single-chip flash: S={seq} fwd {dt * 1e3:.1f} ms "
          f"(naive score matrix would be {score_gib:.1f} GiB)")


def sharded(seq: int, heads: int, dh: int, sp: int, mode: str):
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.parallel.mesh import MeshSpec, build_mesh
    from horovod_tpu.parallel.ring_attention import (
        blockwise_attention_reference, ring_attention)
    from horovod_tpu.parallel.ulysses import ulysses_attention

    mesh = build_mesh(MeshSpec(sp=sp), jax.devices()[:sp])
    spec = P(None, None, "sp", None)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (1, heads, seq, dh), jnp.float32)
               for kk in ks)

    attn = ring_attention if mode == "ring" else ulysses_attention
    f = jax.jit(jax.shard_map(
        lambda q, k, v: attn(q, k, v, "sp", causal=True),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False))
    out = f(q, k, v)
    oracle = blockwise_attention_reference(q, k, v, causal=True)
    err = float(jnp.max(jnp.abs(out - oracle)))
    print(f"{mode} over sp={sp}: S={seq} sharded to S/chip={seq // sp}, "
          f"max |err| vs exact oracle = {err:.2e}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--dh", type=int, default=128)
    ap.add_argument("--sp", type=int, default=0,
                    help="sequence-parallel ways (0: single-chip only)")
    args = ap.parse_args()

    single_chip(args.seq, args.heads, args.dh)
    if args.sp > 1:
        if len(jax.devices()) < args.sp:
            raise SystemExit(f"--sp {args.sp} needs {args.sp} devices "
                             f"(have {len(jax.devices())})")
        if args.seq % args.sp:
            raise SystemExit(f"--seq {args.seq} must be divisible by "
                             f"--sp {args.sp} (sequence is sharded)")
        sharded(args.seq, args.heads, args.dh, args.sp, "ring")
        if args.heads % args.sp == 0:
            sharded(args.seq, args.heads, args.dh, args.sp, "ulysses")
        else:
            print(f"(skipping ulysses: heads={args.heads} not divisible "
                  f"by sp={args.sp})")


if __name__ == "__main__":
    main()
