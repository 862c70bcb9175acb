"""The step of `examples/synthetic_benchmark.py` (upstream's synthetic
benchmark, mirrored): `shard_map` over the `hvd` axis, gradients reduced in
the program by `reduce_gradients_in_jit`, donated state, one program a step."""

from __future__ import annotations

import time

import jax
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from benchmark.harness import optimizers, seeds
from benchmark.harness.runner import Job, say
from horovod_tpu.core import topology
from horovod_tpu.optim.optimizer import reduce_gradients_in_jit


def _step(config, family, opt, mesh):
    def local_step(params, stats, opt_state, batch):
        (loss, new_stats), grads = jax.value_and_grad(
            lambda p: family.loss(config, p, stats, batch, axis_name="hvd"),
            has_aux=True)(params)
        grads = reduce_gradients_in_jit(grads)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, new_stats, opt_state, lax.pmean(loss, "hvd")

    return jax.jit(
        jax.shard_map(local_step, mesh=mesh,
                      in_specs=(P(), P(), P(), P("hvd")),
                      out_specs=(P(), P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1, 2))


def abstract_step(cell, family, devices):
    """(jitted step, its arguments as shapes placed on `devices`): what
    `benchmark/aot_check.py` compiles for a described topology."""
    config, traffic = cell.config, cell.traffic
    mesh = Mesh(np.asarray(devices), ("hvd",))
    replicated = NamedSharding(mesh, P())
    by_rank = NamedSharding(mesh, P("hvd"))

    def shaped(sharding):
        return lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                              sharding=sharding)

    key = jax.random.PRNGKey(0)
    opt = optimizers.make(traffic["optimizer"], lr_scale=len(devices))
    params, stats = jax.eval_shape(lambda k: family.init_state(config, k),
                                   key)
    opt_state = jax.eval_shape(opt.init, params)
    batch = jax.eval_shape(lambda k: family.make_batch(
        config, k, traffic["per_chip_batch"] * len(devices)), key)
    state = jax.tree_util.tree_map(shaped(replicated),
                                   (params, stats, opt_state))
    return _step(config, family, opt, mesh), \
        (*state, jax.tree_util.tree_map(shaped(by_rank), batch))


def build(cell, family, *, seed: int, devices, span) -> Job:
    config, traffic = cell.config, cell.traffic
    mesh, ranks = topology.mesh(), hvd.size()
    replicated = NamedSharding(mesh, P())
    by_rank = NamedSharding(mesh, P("hvd"))
    seed = seeds.argument(seed)
    params, stats = jax.jit(
        lambda s: family.init_state(config, seeds.key(s, seeds.PARAMS)),
        out_shardings=replicated)(seed)
    jax.block_until_ready(params)
    say(f"{cell.name}: parameters made")
    reference = family.check_reference(config, params, stats, seed)
    say(f"{cell.name}: forward pass checked against the reference")
    batch = jax.jit(
        lambda s: family.make_batch(config, seeds.key(s, seeds.BATCH),
                                    traffic["per_chip_batch"] * ranks),
        out_shardings=by_rank)(seed)
    opt = optimizers.make(traffic["optimizer"], lr_scale=ranks)
    opt_state = jax.jit(opt.init, out_shardings=replicated)(params)

    step = _step(config, family, opt, mesh)
    t = time.perf_counter()
    program = step.lower(params, stats, opt_state, batch).compile()
    compile_s = time.perf_counter() - t
    state = [params, stats, opt_state]
    del params, stats, opt_state   # the step donates them

    def one_step():
        with span("bench.spmd_step"):
            state[0], state[1], state[2], loss = step(*state, batch)
        return loss

    return Job(step=one_step,
               finish=lambda: jax.block_until_ready(state),
               samples_per_step=family.samples_per_step(traffic,
                                                        len(devices)),
               program=program, compile_s=compile_s, reference=reference)
