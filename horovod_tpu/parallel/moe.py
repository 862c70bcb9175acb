"""Mixture-of-experts feed-forward: a router over all experts, the top-k of
them per token, one grouped matmul over rows sorted by expert.

Not present in the reference (SURVEY.md §2.6 — `alltoall` is the substrate
it exposes for users to build this). For a token `h` with router weights
`Wr`, under one of two scoring rules (`SCORINGS`):

    "softmax":  p = softmax(r Wr)        over all E experts, in float32
                chosen = the k largest p_e;  w_e = p_e
    "sigmoid":  s = sigmoid(r Wr)        each expert's own, in float32
                chosen = the k largest s_e + bias_e;  w_e = s_e
    out = sum over the chosen e of w_e * expert_e(h)

The selection bias (DeepSeek-V3's, arXiv:2412.19437 section 2.1.2; Kimi
Linear's) is a leaf of E numbers that chooses and never weighs: it is added
to the scores for the choice alone, under `stop_gradient`, so it is the one
leaf of the layer that takes no gradient (upstream moves it by a balancing
rule outside the loss; here it stays as it was seeded).

`r` is `h` itself unless the caller hands the router an input of its own
(`router_input`: SmallThinker, arXiv:2507.20984, scores the layer's input,
before attention, and its experts read the normed post-attention state). The
k weights `w_e` are as above, or with `renormalise` divided by their sum over
the k chosen (`norm_topk_prob`; LFM2 divides by the sum + 1e-6:
`renormalise_eps`), the gradient through the sum included, and then times
`weight_scale` (`routed_scaling_factor`). `expert_e(h)` is
`W_down,e (act(W_gate,e h) * (W_up,e h))` where gate weights are given, `act`
being `gate`: "silu" or "relu"; and `W_down,e gelu(W_up,e h)` where they are
not. Experts that every token goes through beside these (DeepSeek's shared
experts) are a dense MLP of the caller's, added to this layer's result
(`models/transformer.py`, scope `moe.shared`).

On one rank of the expert axis (`ep` = 1) routing is dropless and
static-shaped: the T*k (token, expert) pairs are sorted by expert, their rows
gathered once, the experts applied as grouped matmuls (the Pallas kernels of
`ops/grouped_matmul.py`, the only grouped matmul there is) over the sorted
rows with the E group sizes, and the results gathered back and summed over k.
No token is dropped however uneven the load, and `capacity_factor` means
nothing.

Where the weights multiply. The down product is linear, so `sum_c p_c *
(h_c W_down) = sum_c (p_c h_c) W_down`. On one rank the weights, in sorted
order, multiply the hidden rows `h_c` in float32 before their one rounding.
This is for the backward pass: a product after the down product would make
the gathered result a residual (the weights' gradient would be its inner
product with the cotangent), and under `jax.checkpoint` every layer's
backward would run the down product and a (T*k, D) gather again for a (T, k)
gradient. Weighted in front, nothing after the down product is kept or
recomputed, and the weights' gradient is a row sum over the hidden rows the
gate's backward needs anyway. The combine is then a plain gather and sum, the
transpose of the dispatch (`sum_rows`, `take_rows` of `ops/row_gather.py`):
each one's backward pass is the other, and the cotangent is gathered from the
(T, D) array, never from a (T*k, D) copy of it. Across ranks the weights
still multiply the returned rows: they come back in slot layout, where
dropped pairs are masked, and weighting in front would send the weights
through an exchange of their own.

A share of the experts. The router's width E is the model's; the expert
weights say how many are held, and `first_expert` which: experts
[first_expert, first_expert + n_local). Where one rank holds fewer than E
(one chip of an expert-parallel deployment, without the exchange) it routes
over all E and computes its own experts' part of the result for its own
tokens: pairs routed to held experts are sorted first, gathered, multiplied
and summed back as above; a pair routed elsewhere adds nothing, and its
weight gets no gradient through the experts (the sum back copies only the
rows that count: `ops/row_gather.py`'s kernel). Nothing stands in for the
absent ranks. The row buffer is static and sized from the shapes alone:
`held_rows`, twice the rows an even routing sends to the held experts (or
`held_factor` times: a router that chooses far in its scores' tail, 8 of 256
by sigmoid score, loads a seeded model's experts less evenly than twice). Its
free rows are zero and lie in the last held expert's group, where they add
nothing: the grouped matmuls' work is the buffer's, not the routing's, so a
step takes the same time whatever the router has learnt (a chip of the
deployment is held to a fixed budget too, arXiv:2405.04434 section 2.2.4).
Dropless stays the promise only while the held pairs fit: the count of those
beyond the buffer, which add nothing, is returned as a third auxiliary
number, and the train step hands it on (`models/transformer.py`
`build_train_step(..., metrics=True)`: `experts_dropped`). Across ranks every
rank holds E / ranks experts: a share there is refused.

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

(p the sigmoid scores under that rule) or, where `route` is handed the number
of sequences the tokens are (DeepSeek-V2's `seq_aux`, arXiv:2405.04434
section 2.1.3), the load balance is each sequence's own, f_e and P_e over
that sequence's tokens, averaged over the sequences. Either way it is over
all E experts, held or not.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.common.exceptions import HorovodTpuError
from horovod_tpu.ops.grouped_matmul import ROW_TILE, grouped_matmul, visits
from horovod_tpu.ops.row_gather import sum_rows, take_rows


#: how the router's logits become scores
SCORINGS = {"softmax": lambda logits: jax.nn.softmax(logits, axis=-1),
            "sigmoid": jax.nn.sigmoid}


def route(x: jax.Array, router_w: jax.Array, top_k: int, sequences: int = 0,
          renormalise: bool = False, scoring: str = "softmax",
          selection_bias: Optional[jax.Array] = None,
          weight_scale: float = 1.0, renormalise_eps: float = 0.0):
    """(weights (T, k) float32, experts (T, k) int32, rows per expert (E,)
    int32, [load balance, router z] float32) for the tokens x: (T, D), which
    are `sequences` sequences of equal length where the load balance is to
    be each sequence's own (0: of all T tokens at once). The scores are
    `SCORINGS[scoring]` of the logits; the k experts are those of the
    largest scores, or of the largest scores + `selection_bias` (E,), which
    takes no gradient; the weights are the chosen experts' scores, with
    `renormalise` divided by (their sum + `renormalise_eps`), times
    `weight_scale`."""
    with jax.named_scope("moe.route"):
        n_experts = router_w.shape[1]
        logits = jnp.dot(x, router_w, preferred_element_type=jnp.float32)
        probs = SCORINGS[scoring](logits)
        if selection_bias is None:
            weights, experts = lax.top_k(probs, top_k)
        else:
            _, experts = lax.top_k(probs + lax.stop_gradient(
                selection_bias.astype(jnp.float32)), top_k)
            weights = jnp.take_along_axis(probs, experts, axis=-1)
        if renormalise:
            total = jnp.sum(weights, axis=-1, keepdims=True)
            if renormalise_eps:   # (no add of a zero: the programs without
                total = total + renormalise_eps   # one stay as lowered)
            weights = weights / total
        if weight_scale != 1.0:
            weights = weights * weight_scale
        if sequences:
            per_seq = jnp.sum(
                jax.nn.one_hot(experts, n_experts, dtype=jnp.int32).reshape(
                    sequences, -1, n_experts), axis=1, dtype=jnp.int32)
            counts = jnp.sum(per_seq, axis=0, dtype=jnp.int32)
            share = per_seq.astype(jnp.float32) / (experts.size // sequences)
            mean_probs = jnp.mean(probs.reshape(sequences, -1, n_experts),
                                  axis=1)
            load_balance = n_experts * jnp.mean(
                jnp.sum(share * mean_probs, axis=-1))
        else:
            counts = jnp.sum(
                jax.nn.one_hot(experts, n_experts, dtype=jnp.int32),
                axis=(0, 1), dtype=jnp.int32)
            share = counts.astype(jnp.float32) / experts.size
            load_balance = n_experts * jnp.sum(
                share * jnp.mean(probs, axis=0))
        z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
        return weights, experts.astype(jnp.int32), counts, \
            jnp.stack([load_balance, z])


@jax.custom_vjp
def _permute(v, perm, inverse):
    """`v[perm]` for a vector `v` and a permutation whose inverse is
    `inverse`, as a sort by `inverse`: on the TPU an element gather takes
    ten times as long as the sort (0.56 against 0.06 ms for 65,536 floats,
    PERF.md, PR 29). The backward pass is the same with the two exchanged."""
    return lax.sort((inverse, v), num_keys=1)[1]


def _permute_fwd(v, perm, inverse):
    return _permute(v, perm, inverse), (perm, inverse)


def _permute_bwd(indices, g):
    perm, inverse = indices
    return _permute(g, inverse, perm), None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def held_rows(pairs: int, n_local: int, n_experts: int,
              factor: float = 2.0) -> int:
    """Rows of the buffer that one rank holding `n_local` of `n_experts`
    experts sorts its held (token, expert) pairs into, of `pairs` routed:
    `factor` times (twice) what an even routing sends it, in whole row
    tiles, and never more than all the pairs. (`ROW_TILE`: the rows a
    grouped matmul's kernel takes at a time.)"""
    even = pairs * n_local / n_experts
    return min(pairs, math.ceil(factor * even / ROW_TILE) * ROW_TILE)


#: what a gated expert applies to its gate's product
GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _experts(rows, group_sizes, w_up, w_down, w_gate, row_weights=None,
             gate="silu", stacks=(None, None, None), layer=0):
    """The experts on rows sorted by expert: three grouped matmuls (two for
    an ungated expert), the gate's product through `GATES[gate]`.
    `row_weights` (rows, 1) float32, where given, scale
    each row's hidden activations, in float32 from the products' results to
    the one rounding the down product's input has either way. Every row
    lies in a group. The kernels' tile visits are made here once, for the
    products and their backward passes alike. `stacks`, where given, are the
    stacks that `w_up`, `w_down` and `w_gate` are layer `layer` of: the
    products read them there (`grouped_matmul`)."""
    with jax.named_scope("moe.experts"):
        wide = rows.dtype if row_weights is None else jnp.float32
        plan = visits(group_sizes, rows.shape[0])

        def product(x, w, stack):
            # without a stack, the call as it is made anywhere else
            return grouped_matmul(x, w, plan) if stack is None else \
                grouped_matmul(x, w, plan, stack, layer)

        up, down, gates = stacks
        hidden = product(rows, w_up, up).astype(wide)
        if w_gate is None:
            hidden = jax.nn.gelu(hidden)
        else:
            hidden = GATES[gate](product(rows, w_gate, gates).astype(wide)) \
                * hidden
        if row_weights is not None:
            hidden = (hidden * row_weights).astype(rows.dtype)
        return product(hidden, w_down, down)


def moe_ffn(x: jax.Array, router_w: jax.Array, w_up: jax.Array,
            w_down: jax.Array, w_gate: Optional[jax.Array] = None, *,
            top_k: int = 1, axis_name: str = "ep",
            capacity_factor: float = 1.25, first_expert: int = 0,
            sequences: int = 0, router_input: Optional[jax.Array] = None,
            renormalise: bool = False, gate: str = "silu",
            scoring: str = "softmax",
            selection_bias: Optional[jax.Array] = None,
            weight_scale: float = 1.0, held_factor: float = 2.0,
            renormalise_eps: float = 0.0,
            stacks=(None, None, None), layer=0
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k mixture-of-experts feed-forward on one shard's tokens.

    Per-shard shapes:
      x: (T, D) local tokens (flatten batch*seq before calling)
      router_w: (D, E), E the experts the model has
      w_up, w_gate: (E_local, D, F), w_down: (E_local, F, D) — the experts
        this rank holds: E / ranks of them across ranks, and on one rank
        any E_local <= E, experts [first_expert, first_expert + E_local)
      sequences: how many sequences the T tokens are, where the load
        balance is each sequence's own (`route`)
      router_input: (T, D), what the router scores where that is not x
      renormalise: a token's k weights divided by their sum (+
        `renormalise_eps`)
      gate: what a gated expert applies to its gate's product (`GATES`)
      scoring, selection_bias, weight_scale: the router's rule (`route`)
      held_factor: where one rank holds a share, its row buffer as a
        multiple of what an even routing sends the held experts
        (`held_rows`)
      stacks, layer: the (L, E_local, ...) stacks that w_up, w_down and
        w_gate are layer `layer` of (an int32, traced in a layer scan),
        where the caller has them: the experts' products then read the
        stacks in place, under the contract of `ops/grouped_matmul.py`
        `grouped_matmul` (the same numbers; no gradient to the stacks)
    Returns ((T, D), [load balance, router z] of these tokens, the (T, k)
    experts of each token in the order of their weights). Where one rank
    holds a share (E_local < E) the second has a third number: the held
    pairs that found no room in the row buffer (`held_rows`) and were left
    out, 0 in a sound step.
    """
    ranks = lax.axis_size(axis_name)
    T, D = x.shape
    k = top_k
    n_local = w_up.shape[0]
    n_experts = router_w.shape[1]
    if ranks > 1 and n_local * ranks != n_experts:
        raise HorovodTpuError(
            f"{ranks} ranks of {n_local} experts each under a router over "
            f"{n_experts}: across ranks every expert is held by some rank, "
            "a share of the experts is one rank's")
    if first_expert < 0 or first_expert + n_local > n_experts or (
            ranks > 1 and first_expert):
        raise HorovodTpuError(
            f"first_expert={first_expert} with {n_local} of {n_experts} "
            f"experts on {ranks} rank(s)")
    if gate not in GATES:
        raise HorovodTpuError(f"gate={gate!r}: choose from {sorted(GATES)}")
    if scoring not in SCORINGS:
        raise HorovodTpuError(
            f"scoring={scoring!r}: choose from {sorted(SCORINGS)}")
    # one rank, holding some of the experts
    share = ranks == 1 and n_local < n_experts

    weights, experts, counts, aux = route(
        x if router_input is None else router_input, router_w, k, sequences,
        renormalise, scoring, selection_bias, weight_scale, renormalise_eps)

    with jax.named_scope("moe.dispatch"):
        key = experts.reshape(-1)
        if share:
            # held pairs first, by expert; the others behind them
            key = key - first_expert
            key = jnp.where((key >= 0) & (key < n_local), key, n_local)
        # sorted position -> pair (t*k + c), and back
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inverse = jnp.argsort(order).astype(jnp.int32)
        n_valid = None
        if ranks == 1:
            sizes, held_order = counts, order
            if share:
                room = held_rows(T * k, n_local, n_experts, held_factor)
                sizes = lax.slice(counts, (first_expert,),
                                  (first_expert + n_local,))
                start = jnp.cumsum(sizes, dtype=jnp.int32) - sizes
                held = jnp.sum(sizes, dtype=jnp.int32)
                # a group that crosses the buffer's end keeps what fits
                sizes = jnp.clip(room - start, 0, sizes)
                n_valid = jnp.minimum(held, room)
                # the buffer's free rows, which are zero, go through the
                # last held expert: the products' work is the buffer's,
                # whatever the routing
                sizes = sizes.at[-1].add(room - n_valid)
                aux = jnp.concatenate(
                    [aux, (held - n_valid).astype(aux.dtype)[None]])
                held_order = order[:room]
            token_of = held_order // k
            rows = take_rows(x, token_of, inverse, k, n_valid)
            # the down product is linear: weighting its input rows leaves
            # nothing after it for the backward pass to keep or recompute
            row_weights = _permute(weights.reshape(-1), order,
                                   inverse)[:, None]
            if share:
                row_weights = row_weights[:room]
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
            row_weights = None

    ys = _experts(rows, sizes, w_up, w_down, w_gate, row_weights, gate,
                  stacks, layer)

    with jax.named_scope("moe.combine"):
        if ranks == 1:
            out = sum_rows(ys, inverse, token_of, k, n_valid)
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
                          * weights[..., None], axis=1).astype(x.dtype)
        return out, aux, experts
