"""The jitted SPMD step of `models/transformer.py`: `tfm.init` ->
`param_specs` placement -> `init_opt_state` -> `build_train_step` over a
`MeshSpec`, one process over all the cell's chips."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark.harness import optimizers, seeds
from benchmark.harness.runner import Job, say
from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel.mesh import MeshSpec, build_mesh


def _on_first_device(x, device):
    """A copy of `x` that lives on `device` alone."""
    if x.sharding.is_fully_replicated:
        return next(s.data for s in x.addressable_shards
                    if s.device == device)
    return jax.device_put(x, device)


def _device_checksums(params, devices) -> list:
    """Per device, the float32 sum of every parameter shard it holds."""
    sums = {d: 0.0 for d in devices}
    for leaf in jax.tree_util.tree_leaves(params):
        for shard in leaf.addressable_shards:
            sums[shard.device] += float(
                jnp.sum(shard.data.astype(jnp.float32)))
    return [sums[d] for d in devices]


class _Setup:
    """What the real run and the compile-only rehearsal share: the program's
    configuration, mesh, shardings, optimizer and jitted step."""

    def __init__(self, cell, family, devices):
        traffic = cell.traffic
        self.cfg = family.transformer_config(cell.config)
        spec = MeshSpec(**traffic["mesh"])
        if spec.total != len(devices):
            raise SystemExit(f"{cell.name}: mesh {spec.describe()} spans "
                             f"{spec.total} device(s), the cell has "
                             f"{len(devices)}")
        self.mesh = build_mesh(spec, devices=devices)
        tfm.validate_cfg_for_mesh(self.cfg, self.mesh)
        self.batch = traffic["per_chip_batch"] * len(devices)
        self.seq = traffic["seq_len"]
        self.batch_sharding = NamedSharding(self.mesh,
                                            P(("dp", "ep"), "sp"))
        self.param_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), tfm.param_specs(self.cfg))
        self.opt = optimizers.make(traffic["optimizer"])
        self.step = tfm.build_train_step(self.cfg, self.mesh, self.opt)


def abstract_step(cell, family, devices):
    """(jitted step, its arguments as shapes placed on `devices`): what
    `benchmark/aot_check.py` compiles for a described topology."""
    s = _Setup(cell, family, devices)
    replicated = NamedSharding(s.mesh, P())

    def shaped(x, sharding):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    params = jax.tree_util.tree_map(
        shaped, jax.eval_shape(lambda k: tfm.init(k, s.cfg),
                               jax.random.PRNGKey(0)), s.param_shardings)
    structure = jax.tree_util.tree_structure(params)

    def like_params(node):
        return jax.tree_util.tree_structure(node) == structure

    # moments take their parameters' shardings, scalars are replicated: what
    # tfm.init_opt_state makes of a real state
    opt_state = jax.tree_util.tree_map(
        lambda node: jax.tree_util.tree_map(shaped, node, s.param_shardings)
        if like_params(node) else shaped(node, replicated),
        jax.eval_shape(s.opt.init, params), is_leaf=like_params)
    tokens = jax.ShapeDtypeStruct((s.batch, s.seq), jnp.int32,
                                  sharding=s.batch_sharding)
    return s.step, (params, opt_state, tokens, tokens)


def build(cell, family, *, seed: int, devices, span) -> Job:
    s = _Setup(cell, family, devices)
    cfg, mesh, batch, seq = s.cfg, s.mesh, s.batch, s.seq
    batch_sharding, param_shardings = s.batch_sharding, s.param_shardings
    traffic = cell.traffic
    seed = seeds.argument(seed)

    # weights and batches are made on the device(s), in the type they are
    # trained in, each in one jitted call of the seed
    params = jax.jit(lambda s: tfm.init(seeds.key(s, seeds.PARAMS), cfg),
                     out_shardings=param_shardings)(seed)

    jax.block_until_ready(params)
    say(f"{cell.name}: parameters made on {len(devices)} device(s)")

    def make_tokens(key, n):
        tokens = jax.random.randint(key, (n, seq), 0, cfg.vocab, jnp.int32)
        return tokens, jnp.roll(tokens, -1, axis=1)

    # one sequence per chip through the program's own forward pass over the
    # mesh, against the reference on the first chip; before the optimizer
    # state and the training batch exist, and freed before they do
    check_tokens, _ = jax.jit(
        lambda s: make_tokens(seeds.key(s, seeds.CHECK), len(devices)),
        out_shardings=(batch_sharding, batch_sharding))(seed)
    logits = jax.jit(tfm.build_forward(cfg, mesh))(params, check_tokens)
    first = devices[0]
    reference = family.check_logits(
        jax.tree_util.tree_map(lambda x: _on_first_device(x, first), params),
        jax.device_put(check_tokens, first), jax.device_put(logits, first))
    del logits, check_tokens
    say(f"{cell.name}: forward pass checked against the reference")

    tokens, targets = jax.jit(
        lambda s: make_tokens(seeds.key(s, seeds.BATCH), batch),
        out_shardings=(batch_sharding, batch_sharding))(seed)
    opt_state = tfm.init_opt_state(s.opt, params, mesh)
    step = s.step
    t = time.perf_counter()
    program = step.lower(params, opt_state, tokens, targets).compile()
    compile_s = time.perf_counter() - t
    placed = {"params": {s.device for leaf in jax.tree_util.tree_leaves(
                  params) for s in leaf.addressable_shards},
              "batch": {s.device for s in tokens.addressable_shards}}
    state = [params, opt_state]
    del params, opt_state   # the step donates them

    def one_step():
        with span("bench.spmd_step"):
            state[0], state[1], loss = step(state[0], state[1], tokens,
                                            targets)
        return loss

    def verify() -> list:
        problems = []
        for what, on in placed.items():
            if len(on) != len(devices):
                problems.append(f"the {what} sit on {len(on)} device(s), "
                                f"not {len(devices)}")
        if len(devices) > 1:
            if "all-reduce" not in program.as_text():
                problems.append("no all-reduce in the compiled step")
            sums = _device_checksums(state[0], devices)
            if len(set(sums)) != 1:
                problems.append("parameter checksums differ between the "
                                f"chips after the window: {sums}")
        return problems

    return Job(step=one_step,
               finish=lambda: jax.block_until_ready(state),
               samples_per_step=family.samples_per_step(traffic,
                                                        len(devices)),
               program=program, compile_s=compile_s, reference=reference,
               verify=verify)
