"""Mixture-of-experts feed-forward: a softmax router over all experts, the
top-k of them per token, one grouped matmul over rows sorted by expert.

Not present in the reference (SURVEY.md §2.6 — `alltoall` is the substrate
it exposes for users to build this). For a token `h` with router weights
`Wr`:

    p   = softmax(h Wr)                 over all E experts, in float32
    out = sum over the k largest p_e of p_e * expert_e(h)

The k weights are not renormalised. `expert_e(h)` is `W_down,e (silu(W_gate,e
h) * (W_up,e h))` where gate weights are given and `W_down,e gelu(W_up,e h)`
where they are not.

On one rank of the expert axis (`ep` = 1) routing is dropless and
static-shaped: the T*k (token, expert) pairs are sorted by expert, their rows
gathered once, the experts applied as grouped matmuls (`lax.ragged_dot`, which
the TPU compiler lowers to its own Mosaic kernel) over the sorted rows with
the E group sizes, and the results gathered back and summed with the weights.
No token is dropped however uneven the load, and `capacity_factor` means
nothing.

Across ranks (`ep` > 1) the experts are sharded one group per rank and the
rows are exchanged by `lax.all_to_all` (compiled onto ICI), which needs a
static shape: each rank sends every expert at most `cap = ceil(capacity_factor
* T * k / E)` of its own rows. The drop rule: of one rank's pairs for one
expert, in token order, those beyond the first `cap` are dropped; a dropped
pair adds nothing to its token's output (the token's other experts still
count). The received rows go through the same grouped matmul. A dropless
exchange across chips is ROADMAP W7's.

The two auxiliary losses of a layer are computed from the tokens a shard
holds (OLMoE, arXiv:2409.02060 section 2):

    load balance = E * sum_e f_e * P_e    f_e: share of the T*k pairs sent to e
                                          P_e: mean of p_e over the tokens
    router z     = mean over tokens of logsumexp(h Wr)^2
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def route(x: jax.Array, router_w: jax.Array, top_k: int):
    """(weights (T, k) float32, experts (T, k) int32, rows per expert (E,)
    int32, [load balance, router z] float32) for the tokens x: (T, D)."""
    with jax.named_scope("moe.route"):
        n_experts = router_w.shape[1]
        logits = jnp.dot(x, router_w, preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = lax.top_k(probs, top_k)
        counts = jnp.sum(jax.nn.one_hot(experts, n_experts, dtype=jnp.int32),
                         axis=(0, 1), dtype=jnp.int32)
        share = counts.astype(jnp.float32) / experts.size
        load_balance = n_experts * jnp.sum(share * jnp.mean(probs, axis=0))
        z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
        return weights, experts.astype(jnp.int32), counts, \
            jnp.stack([load_balance, z])


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(x, rows, back, k):
    """`x[rows]`, where `back` says which k rows of the result each row of
    `x` went to (row t to rows back[t*k:(t+1)*k]): the backward pass is then
    a gather and a sum over k, not a scatter-add."""
    return x[rows]


def _take_rows_fwd(x, rows, back, k):
    return x[rows], back


def _take_rows_bwd(k, back, g):
    return g[back].reshape(-1, k, g.shape[-1]).sum(axis=1), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _experts(rows, group_sizes, w_up, w_down, w_gate):
    """The experts on rows sorted by expert: three grouped matmuls (two for
    an ungated expert)."""
    with jax.named_scope("moe.experts"):
        up = lax.ragged_dot(rows, w_up, group_sizes)
        if w_gate is None:
            hidden = jax.nn.gelu(up)
        else:
            hidden = jax.nn.silu(lax.ragged_dot(rows, w_gate,
                                                group_sizes)) * up
        return lax.ragged_dot(hidden, w_down, group_sizes)


def moe_ffn(x: jax.Array, router_w: jax.Array, w_up: jax.Array,
            w_down: jax.Array, w_gate: Optional[jax.Array] = None, *,
            top_k: int = 1, axis_name: str = "ep",
            capacity_factor: float = 1.25
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k mixture-of-experts feed-forward on one shard's tokens.

    Per-shard shapes:
      x: (T, D) local tokens (flatten batch*seq before calling)
      router_w: (D, E) with E = total experts across the axis
      w_up, w_gate: (E_local, D, F), w_down: (E_local, F, D) — this rank's
        experts
    Returns ((T, D), [load balance, router z] of these tokens, the (T, k)
    experts of each token in the order of their weights).
    """
    ranks = lax.axis_size(axis_name)
    T, D = x.shape
    k = top_k
    n_local = w_up.shape[0]
    n_experts = n_local * ranks
    assert router_w.shape[1] == n_experts, \
        "router width must equal total experts"

    weights, experts, counts, aux = route(x, router_w, k)

    with jax.named_scope("moe.dispatch"):
        # sorted position -> pair (t*k + c), and back
        order = jnp.argsort(experts.reshape(-1), stable=True).astype(jnp.int32)
        inverse = jnp.argsort(order).astype(jnp.int32)
        if ranks == 1:
            rows = _take_rows(x, order // k, inverse, k)
            sizes = counts
        else:
            # (plain indexing here: this path's backward pass may scatter)
            cap = max(1, math.ceil(capacity_factor * T * k / n_experts))
            expert_of = experts.reshape(-1)[order]
            start = jnp.cumsum(counts) - counts
            slot = jnp.arange(cap, dtype=jnp.int32)
            # slot c of expert e takes the c-th of this rank's rows for e
            source = order[jnp.minimum(start[:, None] + slot, T * k - 1)] // k
            send = jnp.where((slot < counts[:, None])[..., None], x[source],
                             jnp.zeros((), x.dtype))           # (E, cap, D)
            # Re-shard: chunk e∈[p*n_local,(p+1)*n_local) goes to rank p;
            # received slabs (one per source rank) stack along capacity →
            # (n_local, ranks*cap, D), segment s holding rank s's rows.
            rows = lax.all_to_all(send, axis_name, split_axis=0,
                                  concat_axis=1, tiled=True)
            rows = rows.reshape(n_local * ranks * cap, D)
            sizes = jnp.full((n_local,), ranks * cap, jnp.int32)

    ys = _experts(rows, sizes, w_up, w_down, w_gate)

    with jax.named_scope("moe.combine"):
        if ranks == 1:
            ys = _take_rows(ys, inverse, order, 1)
        else:
            # Inverse re-shard: capacity segment s returns to rank s;
            # received expert groups stack along axis 0 in rank (= global
            # expert) order.
            back = lax.all_to_all(ys.reshape(n_local, ranks * cap, D),
                                  axis_name, split_axis=1, concat_axis=0,
                                  tiled=True)                  # (E, cap, D)
            place = jnp.arange(T * k, dtype=jnp.int32) - start[expert_of]
            ys = jnp.where((place < cap)[:, None],
                           back[expert_of, jnp.minimum(place, cap - 1)],
                           jnp.zeros((), ys.dtype))[inverse]
        out = jnp.sum(ys.reshape(T, k, D).astype(jnp.float32)
                      * weights[..., None], axis=1)
        return out.astype(x.dtype), aux, experts
