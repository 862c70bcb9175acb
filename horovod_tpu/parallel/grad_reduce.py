"""The reduction of a sharded train step's gradients, as
`models/transformer.py` `build_loss_and_grads` issues it under `shard_map`:
a leaf's sum over its reduce axes after the backward pass (`psum_axes`), or
its reduce-scatter inside the backward loop that produces it (`scatter_plan`
says how a leaf is cut, `scatter_sum` is the collective,
`scattered_in_backward` hands a layer's cotangents to it), which the caller
completes with one all-gather a leaf. Nothing here knows the model."""

from __future__ import annotations

import math

import jax
from jax import lax


def psum_axes(x, axes):
    for a in axes:
        x = lax.psum(x, a)
    return x


def scatter_plan(shape, axes):
    """How a gradient leaf of per-shard `shape` is reduce-scattered over its
    reduce `axes`: (the axes of more than one rank, their product n, the
    dimension cut into n chunks), or None where the leaf stays a psum: a
    vector (norms and biases, 0.1% of the bytes), a leaf no dimension of
    which divides by n, or nothing to reduce over."""
    axes = tuple(a for a in axes if lax.axis_size(a) > 1)
    n = math.prod(lax.axis_size(a) for a in axes)
    dim = next((d for d, size in enumerate(shape) if size % n == 0), None)
    if not axes or len(shape) < 2 or dim is None:
        return None
    return axes, n, dim


def scatter_sum(g, axes, n, dim):
    """Reduce-scatter of `g` over `axes`: rank r of the n gets chunk r of
    `dim` of the sum, as `lax.psum_scatter(..., tiled=True)` gives it.

    For a power of two it is written as recursive halving over ppermutes:
    log2(n) exchanges with the rank whose index differs in one bit, highest
    bit first, each sending the half the partner keeps and adding the half
    received. A collective-permute is a DMA that the TPU runs beside the
    core's work (`-start`/`-done`), where the compiler's own reduce-scatter
    and all-reduce hold the core from issue to result (docs/perf.md,
    "overlap")."""
    if n & (n - 1):
        return lax.psum_scatter(g, axes, scatter_dimension=dim, tiled=True)
    r = lax.axis_index(axes)
    # the running sum is kept as its addends, so that the slices and the
    # adds of one level fuse into one pass over the half that is left
    terms = [g]
    step = n // 2
    while step:
        half = terms[0].shape[dim] // 2
        mine = (r // step) % 2       # which half this rank keeps
        send = sum(lax.dynamic_slice_in_dim(t, (1 - mine) * half, half, dim)
                   for t in terms)
        terms = [lax.dynamic_slice_in_dim(t, mine * half, half, dim)
                 for t in terms]
        terms.append(lax.ppermute(send, axes,
                                  [(i, i ^ step) for i in range(n)]))
        step //= 2
    return sum(terms)


def scattered_in_backward(lp, slots, scatter):
    """`lp` (one layer's parameters), unchanged. In the backward pass the
    cotangent of each leaf named in `slots` is handed to `scatter` and
    leaves through the cotangent of its slot, a zero array of the
    scattered shape; towards `lp` that leaf's cotangent is zero. The other
    leaves' cotangents pass untouched."""
    @jax.custom_vjp
    def identity(lp, slots):
        return lp

    def bwd(_, g):
        return ({k: None if k in slots else gk for k, gk in g.items()},
                {k: scatter(k, g[k]) for k in slots})

    identity.defvjp(lambda lp, slots: (lp, None), bwd)
    return identity(lp, slots)
