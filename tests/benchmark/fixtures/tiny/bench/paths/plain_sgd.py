"""A path that exists only in the tests' fixtures: one jitted SGD step."""

import jax

from benchmark.harness import seeds
from benchmark.harness.runner import Job


def build(cell, family, *, seed, devices, span):
    config, traffic = cell.config, cell.traffic
    seed = seeds.argument(seed)
    params = jax.jit(lambda s: family.init_state(
        config, seeds.key(s, seeds.PARAMS)))(seed)
    batch = jax.jit(lambda s: family.make_batch(
        config, seeds.key(s, seeds.BATCH), traffic["per_chip_batch"]))(seed)
    lr = traffic["learning_rate"]

    @jax.jit
    def step(params, batch):
        loss, grads = jax.value_and_grad(
            lambda p: family.loss(config, p, batch))(params)
        return jax.tree_util.tree_map(lambda p, g: p - lr * g, params,
                                      grads), loss

    state = [params]

    def one_step():
        with span("bench.spmd_step"):
            state[0], loss = step(state[0], batch)
        return loss

    return Job(step=one_step, finish=lambda: jax.block_until_ready(state),
               samples_per_step=family.samples_per_step(traffic,
                                                        len(devices)),
               reference={"ok": True, "detail": "nothing to compare"})
