"""A layer's parameter leaves, each declared once, by the part of the layer
that owns it: a `Leaf` states one layer's shape, how the leaf is drawn and how
it is sharded, and what follows from those (its reduce axes). The parts are
the mixers of `models/mixers.py`, the FFNs of `models/ffns.py` and the
block's norms; `transformer.init`, `param_specs` and `grad_reduce_axes` are
three maps over what the parts of a configuration declare."""

from __future__ import annotations

import collections
import dataclasses
import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class Leaf:
    shape: Tuple[int, ...]        # one layer's, behind the stacking axes
    # (the stack's `key_streams`, the stacked shape, the dtype) -> the array
    draw: Callable
    # the mesh axis each dimension is sharded over; None: whole on every
    # rank (a norm's scale, the router, a gated-delta-rule or state-space
    # layer's own leaves: `validate_cfg_for_mesh` refuses tp > 1 with those)
    spec: Optional[Tuple[Optional[str], ...]] = None
    # a layer's leaf, once `transformer` knows its stack: (the stacking
    # axes' sizes, the mesh axes they lie over, what is folded into the
    # model's key for the stack's); None: a leaf of the model's own
    stack: Optional[Tuple] = None

    @property
    def partition(self) -> P:
        if self.stack is None:
            return P()
        return P(*self.stack[1], *(self.spec or (None,) * len(self.shape)))

    @property
    def reduce_axes(self) -> Tuple[str, ...]:
        """The mesh axes its partial gradients are summed over: for a
        layer's leaf those it is not sharded over
        (`transformer.grad_reduce_axes` says why `tp` is among them for a
        leaf every tp rank holds whole); all, for a leaf of the model's."""
        if self.stack is None:
            return ("dp", "ep", "sp", "pp", "tp")
        return tuple(a for a in ("dp", "ep", "sp", "tp")
                     if a not in (self.spec or ()))


#: A mixer or an FFN, one row of `mixers.MIXERS` or `ffns.FFNS`: `leaves`,
#: cfg -> {name: Leaf}; `apply`, as its table says; `checks`, (cfg, the
#: mesh's axis sizes) -> [(holds, what is refused otherwise)]
Part = collections.namedtuple("Part", "leaves apply checks",
                              defaults=[lambda cfg, ax: []])


def key_streams(key):
    """The keys the leaves of one stack of layers are drawn from: four
    streams of the stack's key, of which a leaf names one and its place."""
    fold = jax.random.fold_in
    return {"k": jax.random.split(key, 12),
            "x": jax.random.split(fold(key, 1), 6),
            "g": jax.random.split(fold(key, 3), 11),
            "m": jax.random.split(fold(key, 5), 16)}


def normal(stream: str, place: int, deviation: float):
    return lambda keys, shape, dtype: jax.random.normal(
        keys[stream][place], shape, dtype) * deviation


def fan_in(stream: str, place: int, width: int):
    """A matrix whose products of `width` terms have unit deviation."""
    return normal(stream, place, width ** -0.5)


def ones(keys, shape, dtype):
    return jnp.ones(shape, dtype)


def zeros(keys, shape, dtype):
    return jnp.zeros(shape, dtype)


def step_bias(stream: str, place: int):
    """dt ~ log-U(0.001, 0.1), held as softplus^-1(dt): Mamba's draw of a
    step's bias, and Gated DeltaNet's."""
    def draw(keys, shape, dtype):
        step = jnp.exp(jax.random.uniform(
            keys[stream][place], shape, jnp.float32, math.log(1e-3),
            math.log(0.1)))
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
    return draw
