"""Regenerate the golden StableHLO fixtures for the hvdhlo rule suite.

Each fixture is a tiny jitted program lowered on the CPU backend and
checked in under ``tests/fixtures/hlo/`` so ``tests/test_hvdhlo.py``
stays hermetic on CPU CI (no lowering at test time; the rules run over
the committed text). One positive and, where the negative is not
covered by every other fixture, one negative twin per HVD2xx rule —
including the ResNet-block HVD204 pair (channels 64 vs lane-padded
128).

Run from the repo root after changing a fixture program::

    python scripts/gen_hlo_fixtures.py

and review the diff: fixture churn is rule-input churn.
"""

import os
import sys

os.environ.setdefault(
    "XLA_FLAGS",
    (os.environ.get("XLA_FLAGS", "")
     + " --xla_force_host_platform_device_count=8").strip())

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from horovod_tpu.optim.optimizer import (  # noqa: E402
    reduce_gradients_in_jit)

OUT = os.path.join(_REPO, "tests", "fixtures", "hlo")

_MB = 1024 * 1024


def _mesh():
    n = len(jax.devices())
    return Mesh(np.array(jax.devices()).reshape(n), ("hvd",)), n


def _dp_step_text(threshold_bytes):
    """Two ~8 MB weights through the framework's in-jit bucketed
    reduction: the 64 MB threshold resurrects the giant fused psum
    (HVD201 positive), the 4 MB default chunks it (negative)."""
    mesh, n = _mesh()

    def local_step(p, x):
        def loss(p):
            h = jnp.tanh(x @ p["w0"])
            h = jnp.tanh(h @ p["w1"])
            return jnp.sum(h ** 2)

        g = jax.grad(loss)(p)
        g = reduce_gradients_in_jit(g, num_ranks=n,
                                    fusion_threshold_bytes=threshold_bytes)
        # x rides back out (the caller reuses the batch buffer), so the
        # fixture isolates HVD201 — no incidental HVD203 on the input.
        return jax.tree_util.tree_map(lambda a, b: a - 0.1 * b, p, g), x

    params = {"w0": jnp.ones((1448, 1448), jnp.float32),
              "w1": jnp.ones((1448, 1448), jnp.float32)}
    step = jax.shard_map(local_step, mesh=mesh,
                         in_specs=(P(), P("hvd")),
                         out_specs=(P(), P("hvd")), check_vma=False)
    # 128 rows per shard: the backward dL/dW contracts over the local
    # batch, and 128 keeps that extent lane-aligned so this fixture
    # isolates HVD201 (no incidental HVD204).
    x = jnp.ones((128 * n, 1448), jnp.float32)
    return jax.jit(step, donate_argnums=0).lower(params, x).as_text()


def hvd201_giant_allreduce():
    return _dp_step_text(64 * _MB)


def hvd201_bucketed():
    return _dp_step_text(4 * _MB)


def hvd201_chained():
    """Global-norm clip done naively: the 8 MB gradient psum depends on
    the norm psum — a gradient-scale serialized dependency chain (small
    inherently-serial pairs like softmax's max->sum stay exempt via the
    bucket-cap floor on the chain's total payload)."""
    mesh, n = _mesh()

    def local(g, x):
        norm = lax.psum(jnp.sum(g * g), "hvd")
        return lax.psum(g / jnp.sqrt(norm), "hvd")

    step = jax.shard_map(local, mesh=mesh, in_specs=(P(), P("hvd")),
                         out_specs=P(), check_vma=False)
    return jax.jit(step).lower(jnp.ones((1448, 1448), jnp.float32),
                               jnp.ones((8 * n,), jnp.float32)).as_text()


def hvd202_host_callback():
    """A debug print left inside the step: lowers to a host callback
    custom-call — one device->host->device round-trip per step."""

    def step(x):
        s = jnp.sum(x)
        jax.debug.print("loss={s}", s=s)
        return x * 2.0

    return jax.jit(step).lower(jnp.ones((128,), jnp.float32)).as_text()


def _donation_step(donate):
    # x is 4 MB, shape-matches the output (so the donation is usable),
    # and is dead after its single use; w is referenced twice, so only
    # x is a donation candidate and the fixture isolates one finding.
    f = jax.jit(lambda x, w: jnp.tanh(x @ w) * jnp.sum(w),
                donate_argnums=(0,) if donate else ())
    x = jnp.ones((1024, 1024), jnp.float32)
    w = jnp.ones((1024, 1024), jnp.float32)
    return f.lower(x, w).as_text()


def hvd203_undonated():
    return _donation_step(donate=False)


def hvd203_donated():
    return _donation_step(donate=True)


def _resnet_block_text(channels):
    """A ResNet basic block (conv3x3-relu-conv3x3 + residual), NHWC
    bf16: channels=64 is the real ResNet-50 stage-1 width — every conv
    operand pads 64 -> 128 lanes, 50% of the block's FLOPs are padding
    (the static face of the 0.17-MFU conv gap). The lane-padded twin
    (channels=128) is clean."""

    def conv(x, k):
        return lax.conv_general_dilated(
            x, k, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def block(x, k1, k2):
        h = jax.nn.relu(conv(x, k1))
        return jax.nn.relu(conv(h, k2) + x)

    c = channels
    x = jnp.ones((8, 16, 16, c), jnp.bfloat16)
    k = jnp.ones((3, 3, c, c), jnp.bfloat16)
    return jax.jit(block).lower(x, k, k).as_text()


def hvd204_resnet_block():
    return _resnet_block_text(64)


def hvd204_resnet_block_padded():
    return _resnet_block_text(128)


def hvd205_upcast_matmul():
    """bf16 activations upcast to f32 BEFORE the matmul: the MXU runs
    the dot at the f32 rate for no precision benefit."""
    f = jax.jit(lambda x, w: jnp.tanh(x.astype(jnp.float32)) @ w)
    return f.lower(jnp.ones((128, 256), jnp.bfloat16),
                   jnp.ones((256, 128), jnp.float32)).as_text()


def hvd205_upcast_accum():
    """The legitimate upcast: bf16 -> f32 feeding a reduction
    (accumulate in f32) — must stay clean."""
    f = jax.jit(lambda x: jnp.sum(x.astype(jnp.float32)))
    return f.lower(jnp.ones((128, 256), jnp.bfloat16)).as_text()


# --------------------------------------------------- HVD3xx (hvdshard)

def _mesh_2d():
    """2 x 4 (batch x model) mesh over the 8 virtual CPU devices."""
    from jax.sharding import NamedSharding
    devs = np.array(jax.devices()).reshape(2, 4)
    mesh = Mesh(devs, ("batch", "model"))
    return mesh, (lambda *spec: NamedSharding(mesh, P(*spec)))


def _emb_program_text(replicated):
    """Tied-embedding lookup + vocab-parallel logits on the 2-D mesh.
    The 8 MB table replicated across all 8 partitions is the HVD301
    positive; the vocab-sharded twin is clean. The full-mesh logits
    constraint keeps HVD304 out of the picture (every device class is
    distinguished), so the pair isolates HVD301."""
    mesh, sh = _mesh_2d()
    V, D = 8192, 256
    s_emb = sh() if replicated else sh("model", None)
    s_tok = sh("batch", None)

    def f(emb, tok):
        h = emb[tok]
        logits = h @ emb.T
        logits = lax.with_sharding_constraint(
            logits, sh("batch", None, "model"))
        return jnp.sum(logits)

    emb = jnp.ones((V, D), jnp.float32)
    tok = jnp.zeros((16, 64), jnp.int32)
    return jax.jit(f, in_shardings=(s_emb, s_tok)).lower(
        jax.device_put(emb, s_emb), jax.device_put(tok, s_tok)).as_text()


def hvd301_replicated_emb():
    return _emb_program_text(replicated=True)


def hvd301_sharded_emb():
    return _emb_program_text(replicated=False)


def _matmul_chain_text(conflict):
    """Post-SPMD HLO of a sharded matmul chain. With a consumer
    constraint that contradicts the producer sharding (`conflict`) the
    partitioner inserts a 2 MB all-gather nobody asked for — the
    HVD302 positive; the consistent twin compiles resharding-free.
    Tensors stay under the 4 MiB HVD301 floor so the pair isolates
    HVD302."""
    mesh, sh = _mesh_2d()
    s_x, s_w = sh("batch", None), sh(None, "model")

    def f(x, w):
        y = jnp.tanh(x @ w)        # sharded [batch, model]
        if conflict:
            # demand the model dim replicated: partitioner all-gathers
            y = lax.with_sharding_constraint(y, sh("batch", None))
        z = y * 2.0
        return z

    x = jnp.ones((512, 512), jnp.float32)   # 1 MB
    w = jnp.ones((512, 1024), jnp.float32)  # 2 MB
    out = sh("batch", None) if conflict else sh("batch", "model")
    return jax.jit(f, in_shardings=(s_x, s_w),
                   out_shardings=out).lower(
        jax.device_put(x, s_x),
        jax.device_put(w, s_w)).compile().as_text()


def hvd302_allgather_inserted():
    return _matmul_chain_text(conflict=True)


def hvd302_reshard_free():
    return _matmul_chain_text(conflict=False)


def _donation_chain_text(donate):
    """Post-SPMD (single-device) HLO of two chained 16 MB matmuls.
    Undonated, the 16 MB input rides live next to both intermediates
    (static peak ~64 MB); donating it lets the liveness model free it
    after its single use (~48 MB) — the HVD303 pair, gated in tests
    with HOROVOD_HLO_LINT_HBM_BUDGET between the two peaks."""
    f = jax.jit(lambda x, w: (x @ w) @ w,
                donate_argnums=(0,) if donate else ())
    x = jnp.ones((2048, 2048), jnp.float32)
    return f.lower(x, x).compile().as_text()


def hvd303_overbudget():
    return _donation_chain_text(donate=False)


def hvd303_donated_underbudget():
    return _donation_chain_text(donate=True)


def _axis_usage_text(use_model_axis):
    """2-D mesh whose model axis shards nothing >= 1 MiB (HVD304
    positive) vs the twin whose weight and activation constraints use
    both axes (clean). Everything stays under the 4 MiB HVD301 floor."""
    mesh, sh = _mesh_2d()
    s_x = sh("batch", None)
    s_w = sh(None, "model") if use_model_axis else sh()

    def f(x, w):
        y = x @ w
        y = lax.with_sharding_constraint(
            y, sh("batch", "model") if use_model_axis
            else sh("batch", None))
        return jnp.tanh(y)

    x = jnp.ones((512, 512), jnp.float32)   # 1 MB, batch-sharded
    w = jnp.ones((512, 1024), jnp.float32)  # 2 MB
    return jax.jit(f, in_shardings=(s_x, s_w)).lower(
        jax.device_put(x, s_x), jax.device_put(w, s_w)).as_text()


def hvd304_unused_axis():
    return _axis_usage_text(use_model_axis=False)


def hvd304_used_axes():
    return _axis_usage_text(use_model_axis=True)


def _reduce_keep_shard_text(scatter):
    """shard_map gradient reduction where every rank keeps only its own
    shard: `psum` + slice materializes the full 2 MB reduction on every
    device first (HVD305 positive); `psum_scatter` is the clean twin."""
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("hvd",))
    n = len(jax.devices())
    R = 1024

    def local(g):
        if scatter:
            return lax.psum_scatter(g, "hvd", scatter_dimension=0,
                                    tiled=True)
        s = lax.psum(g, "hvd")
        i = lax.axis_index("hvd")
        return lax.dynamic_slice_in_dim(s, i * (R // n), R // n, 0)

    f = jax.shard_map(local, mesh=mesh, in_specs=P(),
                      out_specs=P("hvd"), check_vma=False)
    return jax.jit(f).lower(jnp.ones((R, 512), jnp.float32)).as_text()


def hvd305_allreduce_slice():
    return _reduce_keep_shard_text(scatter=False)


def hvd305_psum_scatter():
    return _reduce_keep_shard_text(scatter=True)


# --------------------------------------------------- HVD4xx (hvdsched)

def _hvd401_pair_text(big_first):
    """One half of the deliberately misordered MPMD-style pair: the
    same two gradient all-reduces (4 MB and 16 KB over all 8 devices),
    issued in OPPOSITE order in the two programs. Scalar data
    dependencies pin the order through compilation, so the divergence
    survives into the post-SPMD schedule. Each program alone is clean;
    linted together they are the HVD401 static deadlock."""
    mesh, n = _mesh()

    def local(a, b):
        if big_first:
            ga = lax.psum(a, "hvd")
            gb = lax.psum(b + ga[0, 0] * 0.0, "hvd")
        else:
            gb = lax.psum(b, "hvd")
            ga = lax.psum(a + gb[0, 0] * 0.0, "hvd")
        return ga, gb

    f = jax.shard_map(local, mesh=mesh, in_specs=(P(), P()),
                      out_specs=(P(), P()), check_vma=False)
    a = jnp.ones((1024, 1024), jnp.float32)  # 4 MB
    b = jnp.ones((64, 64), jnp.float32)      # 16 KB
    return jax.jit(f).lower(a, b).compile().as_text()


def hvd401_pair_a():
    return _hvd401_pair_text(big_first=True)


def hvd401_pair_b():
    return _hvd401_pair_text(big_first=False)


def hvd402_pp_1f1b():
    """Two-stage-style 1F1B skeleton on the pp ring: the forward
    activation shift and the reverse gradient shift are both FULL
    rings (every rank sends and receives) — the clean HVD402 twin."""
    n = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()).reshape(n), ("pp",))
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [((i + 1) % n, i) for i in range(n)]

    def stage(x):
        act = lax.ppermute(jnp.tanh(x), "pp", fwd)
        grad = lax.ppermute(act * 2.0, "pp", bwd)
        return grad

    f = jax.shard_map(stage, mesh=mesh, in_specs=P("pp"),
                      out_specs=P("pp"), check_vma=False)
    return jax.jit(f).lower(
        jnp.ones((8 * n, 128), jnp.float32)).as_text()


def _sp_ring_text(broken):
    """Ring-attention-style sp rotation: each step shifts the block
    one hop around the ring and accumulates. The clean twin closes the
    ring with the (n-1, 0) wraparound; the broken twin drops it — rank
    0 only sends and rank n-1 only receives, the HVD402 open chain."""
    n = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()).reshape(n), ("sp",))
    pairs = [(i, (i + 1) % n) for i in range(n)]
    if broken:
        pairs = pairs[:-1]  # no wraparound: an open chain

    def ring(x):
        blk = x
        acc = x
        for _ in range(2):
            blk = lax.ppermute(blk, "sp", pairs)
            acc = acc + blk
        return acc

    f = jax.shard_map(ring, mesh=mesh, in_specs=P("sp"),
                      out_specs=P("sp"), check_vma=False)
    return jax.jit(f).lower(
        jnp.ones((8 * n, 256), jnp.float32)).as_text()


def hvd402_sp_ring():
    return _sp_ring_text(broken=False)


def hvd402_sp_broken_ring():
    return _sp_ring_text(broken=True)


def hvd404_flat_allreduce():
    """A 2.25 MB gradient all-reduce over all 8 devices as ONE flat
    collective. Clean on a flat mesh; under HOROVOD_MESH_SLICES=2 the
    group spans the slice boundary with 4 members per slice, so the
    staged form is available and HVD404 fires."""
    mesh, n = _mesh()

    def local(g):
        return lax.psum(g, "hvd")

    f = jax.shard_map(local, mesh=mesh, in_specs=P(),
                      out_specs=P(), check_vma=False)
    return jax.jit(f).lower(
        jnp.ones((768, 768), jnp.float32)).as_text()


def hvd404_staged_allreduce():
    """The staged twin on the 2 x 4 (outer x inner) mesh: intra-slice
    reduce-scatter, inter-slice all-reduce over one-rank-per-slice
    groups, intra-slice all-gather. Under HOROVOD_MESH_SLICES=2 every
    cross-slice group has exactly one member per slice — the shape
    HVD404 asks for — so the twin lints clean."""
    devs = np.array(jax.devices()).reshape(2, 4)
    mesh = Mesh(devs, ("outer", "inner"))

    def local(g):
        piece = lax.psum_scatter(g, "inner", scatter_dimension=0,
                                 tiled=True)
        piece = lax.psum(piece, "outer")
        return lax.all_gather(piece, "inner", axis=0, tiled=True)

    f = jax.shard_map(local, mesh=mesh, in_specs=P(),
                      out_specs=P(), check_vma=False)
    return jax.jit(f).lower(
        jnp.ones((768, 768), jnp.float32)).as_text()


def comms_degenerate_group():
    """Hand-authored post-SPMD text (deterministic, no lowering): an
    all-reduce whose replica groups are ALL size-1 — the degenerate
    single-device-group shape a size-1 mesh axis produces. No wire
    traffic moves, so comms_by_axis / comms_model must skip it
    (shard.group_axis_label returns None), not file it under an axis
    or 'other'."""
    return """HloModule degenerate_single_device_groups, num_partitions=8

add {
  x = f32[] parameter(0)
  y = f32[] parameter(1)
  ROOT s = f32[] add(x, y)
}

ENTRY main {
  p0 = f32[256,256]{1,0} parameter(0)
  ar = f32[256,256]{1,0} all-reduce(p0), replica_groups={{0},{1},{2},{3},{4},{5},{6},{7}}, use_global_device_ids=true, channel_id=1, to_apply=add
  ROOT out = f32[256,256]{1,0} add(ar, ar)
}
"""


# ---------------------------------------------------- HVD5xx (hvdnum)

def _dot_text(widen):
    """bf16 matmul accumulating in bf16 (HVD501 positive) vs the free
    fix: preferred_element_type=f32 keeps MXU inputs narrow and
    accumulates wide (clean twin)."""
    if widen:
        f = jax.jit(lambda x, w: jnp.matmul(
            x, w, preferred_element_type=jnp.float32))
    else:
        f = jax.jit(lambda x, w: x @ w)
    return f.lower(jnp.ones((128, 256), jnp.bfloat16),
                   jnp.ones((256, 128), jnp.bfloat16)).as_text()


def hvd501_bf16_dot():
    return _dot_text(widen=False)


def hvd501_f32_accum():
    return _dot_text(widen=True)


def _downcast_reduce_text(downcast_first):
    """Gradient downcast on the WRONG side of its all-reduce: casting
    to bf16 before the psum rounds every summand first (HVD502
    positive); reducing in f32 and downcasting the single result is
    the clean twin — one rounding, after the sum."""
    mesh, n = _mesh()

    def local(g):
        if downcast_first:
            return lax.psum(g.astype(jnp.bfloat16), "hvd")
        return lax.psum(g, "hvd").astype(jnp.bfloat16)

    f = jax.shard_map(local, mesh=mesh, in_specs=P(),
                      out_specs=P(), check_vma=False)
    return jax.jit(f).lower(
        jnp.ones((512, 512), jnp.float32)).as_text()


def hvd502_downcast_then_reduce():
    return _downcast_reduce_text(downcast_first=True)


def hvd502_reduce_then_downcast():
    return _downcast_reduce_text(downcast_first=False)


def _grad_scale_text(divisor):
    """Hand-authored post-SPMD text (deterministic, no lowering): a
    4-member-group gradient all-reduce followed by an explicit divide.
    Dividing by the WORLD size 8 (printed in scientific notation, as
    XLA does — the literal-parser satellite) is the baked-constant
    HVD503 positive: stale the moment an elastic rescale changes the
    group. Dividing by the reducing group's own size 4 is the true
    mean, the clean twin."""
    return """HloModule grad_scale, num_partitions=8

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main (p0: f32[64]) -> f32[64] {
  %p0 = f32[64]{0} parameter(0)
  %ar = f32[64]{0} all-reduce(f32[64]{0} %p0), replica_groups={{0,1,2,3},{4,5,6,7}}, use_global_device_ids=true, channel_id=1, to_apply=%add
  %c = f32[] constant(@DIV@)
  %bc = f32[64]{0} broadcast(f32[] %c), dimensions={}
  ROOT %d = f32[64]{0} divide(f32[64]{0} %ar, f32[64]{0} %bc)
}
""".replace("@DIV@", divisor)


def hvd503_baked_world_divisor():
    return _grad_scale_text("8e0")


def hvd503_group_mean():
    return _grad_scale_text("4")


def hvd504_hazards():
    """Hand-authored: all three HVD504 determinism hazards in one
    module — a fused two-operand fp all-reduce (combining order across
    the fused buffers is schedule-dependent), replica groups of
    unequal sizes 6 and 2 (per-device combining trees differ in
    shape), and a keyless ``rng`` op (implicit per-device generator
    state does not survive a restore)."""
    return """HloModule determinism_hazards, num_partitions=8

%sum2 (a: f32[], b: f32[], c: f32[], d: f32[]) -> (f32[], f32[]) {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  %c = f32[] parameter(2)
  %d = f32[] parameter(3)
  %s0 = f32[] add(f32[] %a, f32[] %c)
  %s1 = f32[] add(f32[] %b, f32[] %d)
  ROOT %t = (f32[], f32[]) tuple(f32[] %s0, f32[] %s1)
}

ENTRY %main (p0: f32[64], p1: f32[64]) -> f32[64] {
  %p0 = f32[64]{0} parameter(0)
  %p1 = f32[64]{0} parameter(1)
  %ar = (f32[64]{0}, f32[64]{0}) all-reduce(f32[64]{0} %p0, f32[64]{0} %p1), replica_groups={{0,1,2,3,4,5},{6,7}}, use_global_device_ids=true, channel_id=1, to_apply=%sum2
  %g0 = f32[64]{0} get-tuple-element((f32[64]{0}, f32[64]{0}) %ar), index=0
  %g1 = f32[64]{0} get-tuple-element((f32[64]{0}, f32[64]{0}) %ar), index=1
  %lo = f32[] constant(0)
  %hi = f32[] constant(1)
  %noise = f32[64]{0} rng(f32[] %lo, f32[] %hi), distribution=rng_uniform
  %s = f32[64]{0} add(f32[64]{0} %g0, f32[64]{0} %g1)
  ROOT %out = f32[64]{0} add(f32[64]{0} %s, f32[64]{0} %noise)
}
"""


def hvd504_keyed_clean():
    """The clean twin: one tensor per all-reduce, equal-size groups,
    and randomness drawn through ``rng-bit-generator`` — which threads
    its state explicitly and so IS restore-deterministic (pins the
    HVD504 rng exemption)."""
    return """HloModule determinism_clean, num_partitions=8

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main (p0: f32[64], p1: f32[64], state: u64[2]) -> f32[64] {
  %p0 = f32[64]{0} parameter(0)
  %p1 = f32[64]{0} parameter(1)
  %state = u64[2]{0} parameter(2)
  %ar0 = f32[64]{0} all-reduce(f32[64]{0} %p0), replica_groups={{0,1,2,3},{4,5,6,7}}, use_global_device_ids=true, channel_id=1, to_apply=%add
  %ar1 = f32[64]{0} all-reduce(f32[64]{0} %p1), replica_groups={{0,1,2,3},{4,5,6,7}}, use_global_device_ids=true, channel_id=2, to_apply=%add
  %rbg = (u64[2]{0}, u32[64]{0}) rng-bit-generator(u64[2]{0} %state), algorithm=rng_default
  %bits = u32[64]{0} get-tuple-element((u64[2]{0}, u32[64]{0}) %rbg), index=1
  ROOT %out = f32[64]{0} add(f32[64]{0} %ar0, f32[64]{0} %ar1)
}
"""


def _mesh_restore_text(n, mean):
    """One half of the different-mesh-restore pair: the same step
    lowered for an n-device mesh. The bare-sum halves disagree on the
    effective multiplier (4 vs 8 — HVD505 fires when the pair is
    linted as one set); the mean halves each divide by their OWN
    group size, so the invariant holds under any mesh (clean twins).
    Each half alone is HVD503-clean: a bare sum is legitimate Sum
    semantics in-program, and the mean's divisor matches its group."""
    groups = "{" + ",".join(str(i) for i in range(n)) + "}"
    scale = """  %c = f32[] constant(@N@)
  %bc = f32[64]{0} broadcast(f32[] %c), dimensions={}
  ROOT %d = f32[64]{0} divide(f32[64]{0} %ar, f32[64]{0} %bc)""" \
        if mean else "  ROOT %out = f32[64]{0} add(f32[64]{0} %ar, f32[64]{0} %ar)"
    return """HloModule mesh@N@_step, num_partitions=@N@

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main (p0: f32[64]) -> f32[64] {
  %p0 = f32[64]{0} parameter(0)
  %ar = f32[64]{0} all-reduce(f32[64]{0} %p0), replica_groups={@G@}, use_global_device_ids=true, channel_id=1, to_apply=%add
@SCALE@
}
""".replace("@SCALE@", scale).replace("@G@", groups).replace("@N@", str(n))


def hvd505_mesh4_sum():
    return _mesh_restore_text(4, mean=False)


def hvd505_mesh8_sum():
    return _mesh_restore_text(8, mean=False)


def hvd505_mesh4_mean():
    return _mesh_restore_text(4, mean=True)


def hvd505_mesh8_mean():
    return _mesh_restore_text(8, mean=True)


FIXTURES = {
    "hvd201_giant_allreduce": hvd201_giant_allreduce,
    "hvd201_bucketed": hvd201_bucketed,
    "hvd201_chained": hvd201_chained,
    "hvd202_host_callback": hvd202_host_callback,
    "hvd203_undonated": hvd203_undonated,
    "hvd203_donated": hvd203_donated,
    "hvd204_resnet_block": hvd204_resnet_block,
    "hvd204_resnet_block_padded": hvd204_resnet_block_padded,
    "hvd205_upcast_matmul": hvd205_upcast_matmul,
    "hvd205_upcast_accum": hvd205_upcast_accum,
    "hvd301_replicated_emb": hvd301_replicated_emb,
    "hvd301_sharded_emb": hvd301_sharded_emb,
    "hvd302_allgather_inserted": hvd302_allgather_inserted,
    "hvd302_reshard_free": hvd302_reshard_free,
    "hvd303_overbudget": hvd303_overbudget,
    "hvd303_donated_underbudget": hvd303_donated_underbudget,
    "hvd304_unused_axis": hvd304_unused_axis,
    "hvd304_used_axes": hvd304_used_axes,
    "hvd305_allreduce_slice": hvd305_allreduce_slice,
    "hvd305_psum_scatter": hvd305_psum_scatter,
    "hvd401_pair_a": hvd401_pair_a,
    "hvd401_pair_b": hvd401_pair_b,
    "hvd402_pp_1f1b": hvd402_pp_1f1b,
    "hvd402_sp_ring": hvd402_sp_ring,
    "hvd402_sp_broken_ring": hvd402_sp_broken_ring,
    "hvd404_flat_allreduce": hvd404_flat_allreduce,
    "hvd404_staged_allreduce": hvd404_staged_allreduce,
    "comms_degenerate_group": comms_degenerate_group,
    "hvd501_bf16_dot": hvd501_bf16_dot,
    "hvd501_f32_accum": hvd501_f32_accum,
    "hvd502_downcast_then_reduce": hvd502_downcast_then_reduce,
    "hvd502_reduce_then_downcast": hvd502_reduce_then_downcast,
    "hvd503_baked_world_divisor": hvd503_baked_world_divisor,
    "hvd503_group_mean": hvd503_group_mean,
    "hvd504_hazards": hvd504_hazards,
    "hvd504_keyed_clean": hvd504_keyed_clean,
    "hvd505_mesh4_sum": hvd505_mesh4_sum,
    "hvd505_mesh8_sum": hvd505_mesh8_sum,
    "hvd505_mesh4_mean": hvd505_mesh4_mean,
    "hvd505_mesh8_mean": hvd505_mesh8_mean,
}


def main():
    os.makedirs(OUT, exist_ok=True)
    for name, fn in sorted(FIXTURES.items()):
        text = fn()
        # Post-SPMD fixtures (HVD302/303 consume the compiled module)
        # are HLO text, not MLIR — name the file for what it holds,
        # and drop the other-extension twin so a fixture that CHANGES
        # form can't leave a stale file the tests keep pinning.
        ext = "hlo" if text.startswith("HloModule") else "mlir"
        other = os.path.join(OUT, f"{name}.{'mlir' if ext == 'hlo' else 'hlo'}")
        if os.path.exists(other):
            os.unlink(other)
            print(f"removed stale {os.path.relpath(other, _REPO)}")
        path = os.path.join(OUT, f"{name}.{ext}")
        with open(path, "w", encoding="utf-8") as fh:
            fh.write(text)
        print(f"wrote {os.path.relpath(path, _REPO)} "
              f"({len(text.splitlines())} lines)")


if __name__ == "__main__":
    main()
