"""A family that exists only in the tests' fixtures: softmax regression."""

import jax
import jax.numpy as jnp

SAMPLE = "rows"


def init_state(config, key):
    return {"w": jax.random.normal(key, (config["features"],
                                         config["classes"]), jnp.float32)}


def make_batch(config, key, n):
    kx, ky = jax.random.split(key)
    return (jax.random.normal(kx, (n, config["features"]), jnp.float32),
            jax.random.randint(ky, (n,), 0, config["classes"], jnp.int32))


def loss(config, params, batch):
    x, y = batch
    logp = jax.nn.log_softmax(x @ params["w"])
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def samples_per_step(traffic, chips):
    return traffic["per_chip_batch"] * chips
