"""Random keys from `--seed`, made inside the jitted functions that use them.

`key(seed, stream)` is called under `jax.jit` with `seed` an argument (a
`numpy.uint32`): the program is then the same for every seed, and no small
program for making or splitting a key is compiled on the way (each is too
quick to compile to be kept in the persistent cache, so it would be compiled
again in every run: 1.3 s of set-up on the v5e)."""

import jax
import numpy as np

PARAMS, BATCH, CHECK = range(3)   # streams: what a key is for


def argument(seed: int) -> np.uint32:
    return np.uint32(seed)


def key(seed, stream: int):
    return jax.random.fold_in(jax.random.PRNGKey(stream), seed)
