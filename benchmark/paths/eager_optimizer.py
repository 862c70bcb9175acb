"""Horovod's own loop, the migration path the framework is named for:
`hvd.broadcast_parameters`, a jitted `value_and_grad`, then
`hvd.DistributedOptimizer(...).step` on the gradient tree."""

from __future__ import annotations

import time

import jax

import horovod_tpu as hvd
from benchmark.harness import optimizers, seeds
from benchmark.harness.runner import Job, say


def _grad_fn(config, family):
    return jax.jit(jax.value_and_grad(
        lambda p, s, x, y: family.loss(config, p, s, (x, y)), has_aux=True))


def abstract_step(cell, family, devices):
    """(the jitted gradient program, its arguments as shapes on the one
    device): what `benchmark/aot_check.py` compiles. The optimizer's own
    small programs are dispatched eagerly and are not part of it."""
    config = cell.config
    on = jax.sharding.SingleDeviceSharding(devices[0])

    def shaped(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on)

    key = jax.random.PRNGKey(0)
    params, stats = jax.tree_util.tree_map(
        shaped, jax.eval_shape(lambda k: family.init_state(config, k), key))
    x, y = jax.tree_util.tree_map(shaped, jax.eval_shape(
        lambda k: family.make_batch(config, k,
                                    cell.traffic["per_chip_batch"]), key))
    return _grad_fn(config, family), (params, stats, x, y)


def build(cell, family, *, seed: int, devices, span) -> Job:
    config, traffic = cell.config, cell.traffic
    if hvd.size() != 1:
        raise SystemExit(
            f"{cell.name}: this path drives one rank from one process; "
            f"hvd.size() is {hvd.size()} (more ranks are launched one "
            "process per chip, which is a path of its own)")
    seed = seeds.argument(seed)
    params, stats = jax.jit(lambda s: family.init_state(
        config, seeds.key(s, seeds.PARAMS)))(seed)
    # the Horovod idiom: rank 0's model state goes to every rank (which also
    # commits it to the device, so that step 2 sees step 1's placement)
    params, stats = hvd.broadcast_parameters((params, stats), root_rank=0)
    jax.block_until_ready(params)
    say(f"{cell.name}: parameters made")
    reference = family.check_reference(config, params, stats, seed)
    say(f"{cell.name}: forward pass checked against the reference")
    x, y = jax.jit(lambda s: family.make_batch(
        config, seeds.key(s, seeds.BATCH), traffic["per_chip_batch"]))(seed)
    opt = hvd.DistributedOptimizer(optimizers.make(traffic["optimizer"]))
    state = [params, stats, opt.init(params)]
    grad_fn = _grad_fn(config, family)
    t = time.perf_counter()
    program = grad_fn.lower(params, stats, x, y).compile()
    compile_s = time.perf_counter() - t
    del params, stats

    def one_step():
        with span("bench.grad_fn"):
            (loss, state[1]), grads = grad_fn(state[0], state[1], x, y)
        with span("bench.opt_step"):
            state[0], state[2] = opt.step(grads, state[0], state[2])
        return loss

    def verify() -> list:
        if getattr(opt, "_apply_eager", False):
            return ["DistributedOptimizer fell back to the un-jitted apply"]
        return []

    return Job(step=one_step,
               finish=lambda: jax.block_until_ready(state),
               samples_per_step=family.samples_per_step(traffic,
                                                        len(devices)),
               program=program, compile_s=compile_s, reference=reference,
               verify=verify)
