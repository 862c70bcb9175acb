"""The FFNs of a layer of `models/transformer.py`, each in one place: the
leaves it has under a configuration, its `apply`, and what it refuses of a
configuration and a mesh. `FFNS` holds them by `kind(cfg)`: routed experts
with their router (softmax or sigmoid scores, a selection bias) and the
shared experts beside them where the layer has experts, else a dense MLP,
gated (SiLU or ReLU, no biases) or GELU with biases.

An `apply` takes (h: the normed post-attention state (B, S_loc, D), lp: the
layer's leaves, cfg, arrived: the layer's input as it arrived, stacked: (the
stacks that lp's expert leaves are a layer of, which layer) where the caller
has them) and returns (the FFN's output, summed over `tp`; None, or for
experts the layer's [load balance, router z] of this shard's tokens, with
the count of held pairs that found no room as a third where the layer holds
a share of its experts: `parallel/moe.py`)."""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.models.leaves import Leaf, Part, fan_in, zeros
from horovod_tpu.parallel import moe as moe_mod

#: an expert layer's up, down and gate weights, (experts held, ., .) a layer,
#: in the order `moe.moe_ffn` takes them
EXPERT_LEAVES = ("we1", "we2", "we_gate")

_UP, _DOWN = (None, "tp"), ("tp", None)   # the hidden width is tp's


def kind(cfg) -> str:
    return "experts" if cfg.num_experts else "gated" if cfg.gate else "gelu"


def _mlp(h, w_gate, w_up, w_down, gate="silu"):
    """W_down (act(W_gate h) * W_up h), act the `gate` of `moe.GATES`, or
    W_down gelu(W_up h) without a gate; no biases. The hidden width is
    sharded over tp: this rank's part of the sum."""
    hidden = jnp.einsum("bsd,df->bsf", h, w_up)
    hidden = jax.nn.gelu(hidden) if w_gate is None else \
        moe_mod.GATES[gate](jnp.einsum("bsd,df->bsf", h, w_gate)) * hidden
    return jnp.einsum("bsf,fd->bsd", hidden, w_down)


def _dense_leaves(cfg) -> Dict[str, Leaf]:
    D, F = cfg.d_model, cfg.d_ff
    leaves = {"w1": Leaf((D, F), fan_in("k", 4, D), _UP),
              "w2": Leaf((F, D), fan_in("k", 5, F), _DOWN)}
    if cfg.gate:
        leaves["w_gate"] = Leaf((D, F), fan_in("x", 2, D), _UP)
    else:
        leaves["b1"] = Leaf((F,), zeros, ("tp",))
        leaves["b2"] = Leaf((D,), zeros)
    return leaves


def _gelu(h, lp: Dict[str, Any], cfg, arrived, stacked):
    with jax.named_scope("mlp.dense"):
        u = jnp.einsum("bsd,df->bsf", h, lp["w1"]) + lp["b1"]
        u = jax.nn.gelu(u)
        f = jnp.einsum("bsf,fd->bsd", u, lp["w2"])
        return lax.psum(f, "tp") + lp["b2"], None


def _gated(h, lp: Dict[str, Any], cfg, arrived, stacked):
    with jax.named_scope("mlp.dense"):
        return lax.psum(_mlp(h, lp["w_gate"], lp["w1"], lp["w2"], cfg.gate),
                        "tp"), None


def _shared_leaves(cfg) -> Dict[str, Leaf]:
    """The experts every token goes through beside the routed ones: one MLP
    of width shared_experts * d_ff, tp-sharded like a dense MLP."""
    D, width = cfg.d_model, cfg.shared_experts * cfg.d_ff
    leaves = {"ws1": Leaf((D, width), fan_in("x", 3, D), _UP),
              "ws2": Leaf((width, D), fan_in("x", 4, width), _DOWN)}
    if cfg.gate:
        leaves["ws_gate"] = Leaf((D, width), fan_in("x", 5, D), _UP)
    return leaves


def _expert_leaves(cfg) -> Dict[str, Leaf]:
    """The router over all num_experts and the experts this program holds
    (`experts_held`, or all), sharded over ep."""
    D, F, held = cfg.d_model, cfg.d_ff, cfg.experts_held or cfg.num_experts
    up, down, gate = EXPERT_LEAVES
    over_ep = ("ep", None, None)
    leaves = {"router": Leaf((D, cfg.num_experts), fan_in("k", 4, D)),
              up: Leaf((held, D, F), fan_in("k", 5, D), over_ep),
              down: Leaf((held, F, D), fan_in("k", 6, F), over_ep)}
    if cfg.gate:
        leaves[gate] = Leaf((held, D, F), fan_in("k", 10, D), over_ep)
    if cfg.router_bias:
        # chooses and never weighs: no gradient reaches it (`moe.route`)
        leaves["router_bias"] = Leaf((cfg.num_experts,), zeros)
    if cfg.shared_experts:
        leaves.update(_shared_leaves(cfg))
    return leaves


def _experts(h, lp: Dict[str, Any], cfg, arrived, stacked):
    B, S, D = h.shape
    stacks, layer = stacked or ({}, 0)
    out, aux, _ = moe_mod.moe_ffn(
        h.reshape(B * S, D), lp["router"],
        *(lp.get(k) for k in EXPERT_LEAVES),
        top_k=cfg.experts_per_token, axis_name="ep",
        capacity_factor=cfg.capacity_factor,
        held_factor=cfg.capacity_factor,
        first_expert=cfg.first_expert,
        sequences=B if cfg.balance_per_sequence else 0,
        router_input=arrived.reshape(B * S, D)
        if cfg.router_input == "layer" else None,
        renormalise=cfg.norm_topk, renormalise_eps=cfg.norm_topk_eps,
        gate=cfg.gate or "silu",
        scoring=cfg.router_scoring, selection_bias=lp.get("router_bias"),
        weight_scale=cfg.routed_scale,
        stacks=tuple(stacks.get(k) for k in EXPERT_LEAVES), layer=layer)
    f = out.reshape(B, S, D)
    if cfg.shared_experts:
        with jax.named_scope("moe.shared"):
            f = f + lax.psum(_mlp(h, lp.get("ws_gate"), lp["ws1"],
                                  lp["ws2"], cfg.gate), "tp")
    return f, aux


def _expert_checks(cfg, ax):
    return [
        (cfg.router_input in ("mlp", "layer"),
         f"router_input={cfg.router_input!r}: choose 'mlp' or 'layer'"),
        (cfg.router_scoring in moe_mod.SCORINGS,
         f"router_scoring={cfg.router_scoring!r}: choose from "
         f"{sorted(moe_mod.SCORINGS)}"),
        (cfg.router_input == "mlp" or not cfg.post_norm,
         "router_input='layer' with post_norm (the layer's input is the "
         "attention's too)"),
        (cfg.num_experts % ax["ep"] == 0, "num_experts % ep"),
        # every rank of the expert axis holds an equal part of ALL the
        # experts the router scores, or one rank holds a share of them
        # (parallel/moe.py): a share across ranks would need a second
        # exchange for the pairs that no rank here holds
        (ax["ep"] == 1 or cfg.experts_held in (0, cfg.num_experts),
         "ep > 1 with experts_held < num_experts (a share of the experts "
         "is one rank's)")]


FFNS = {
    "gelu": Part(_dense_leaves, _gelu),
    "gated": Part(_dense_leaves, _gated),
    "experts": Part(_expert_leaves, _experts, _expert_checks),
}
