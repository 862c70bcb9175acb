"""Pipeline parallelism: GPipe-style microbatch schedule over the `pp` axis.

Not present in the reference (SURVEY.md §2.6). TPU-native design: every
pipeline stage is the same SPMD program; stage identity comes from
`lax.axis_index(pp)`, activations hop stage→stage with `lax.ppermute`, and
the schedule is a `lax.scan` of length (n_micro + pp - 1) so the whole
pipeline — including its reverse-order backward, obtained by jax.grad
through the scan+ppermute — is one compiled XLA program.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax


def pipeline_apply(stage_fn: Callable[[jax.Array, jax.Array], jax.Array],
                   stage_params,
                   x_micro: jax.Array,
                   axis_name: str = "pp", has_aux: bool = False):
    """Run microbatches through the pipeline; returns last-stage outputs.

    stage_fn(stage_params, act) -> act, applied by every stage to whatever
    activation it currently holds.
    stage_params: this stage's parameter slice (pp-sharded pytree).
    x_micro: (n_micro, *act_shape) — stage 0's input microbatches. Other
      stages pass the same-shaped array (its values are ignored there).

    Returns (n_micro, *act_shape): on the LAST stage these are the pipeline
    outputs in microbatch order; on other stages zeros. Reduce/select over
    the pp axis afterwards (e.g. compute loss under `axis_index == pp-1`).

    With `has_aux`, stage_fn returns (act, aux) and the result is (outputs,
    the sum of this stage's aux over the ticks in which it held a real
    microbatch); aux is a pytree of arrays, or None.
    """
    P = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    n_micro = x_micro.shape[0]
    act_shape = x_micro.shape[1:]
    perm = [(i, (i + 1) % P) for i in range(P)]

    def tick(carry, t):
        held = carry  # activation each stage currently holds
        # Stage 0 injects microbatch t (clamped; ticks past n_micro-1 are
        # drain ticks whose stage-0 output is discarded downstream).
        inject = x_micro[jnp.minimum(t, n_micro - 1)]
        cur = jnp.where(stage == 0, inject, held)
        out, aux = stage_fn(stage_params, cur), None
        if has_aux:
            out, aux = out
            # Stage s holds microbatch (t - s) at tick t.
            real = jnp.logical_and(t >= stage, t < stage + n_micro)
            aux = jax.tree_util.tree_map(
                lambda a: jnp.where(real, a, jnp.zeros_like(a)), aux)
        # Last stage emits microbatch (t - (P-1)) at tick t.
        emit_valid = jnp.logical_and(stage == P - 1,
                                     jnp.logical_and(t >= P - 1, t < n_micro + P - 1))
        emitted = jnp.where(emit_valid, out, jnp.zeros_like(out))
        nxt = lax.ppermute(out, axis_name, perm)
        return nxt, (emitted, aux)

    held0 = jnp.zeros(act_shape, x_micro.dtype)
    _, (emitted, aux) = lax.scan(tick, held0, jnp.arange(n_micro + P - 1))
    # emitted[t] is microbatch t-(P-1); slice the valid window.
    outputs = lax.dynamic_slice_in_dim(emitted, P - 1, n_micro, axis=0)
    if not has_aux:
        return outputs
    return outputs, jax.tree_util.tree_map(lambda a: a.sum(axis=0), aux)
