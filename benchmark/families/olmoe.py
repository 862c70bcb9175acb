"""OLMoE-architecture decoders (top-k of E gated-SiLU experts, RMSNorm, rotary
positions, QK-norm) through `horovod_tpu.models.transformer`."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference import olmoe as reference
from horovod_tpu.models import transformer as tfm

SAMPLE = "tokens"

#: Agreement with the float32 reference on the same weights, each side
#: routing for itself. Two things separate them. The program computes in
#: bf16 (a relative rounding step of 2^-8) through some ten roundings a layer
#: on the residual path, as the GPT-2 family's does. And routing is
#: discontinuous: where a token's eighth and ninth largest router
#: probabilities lie closer than the rounding error of the router's logits,
#: the two sides send it to different experts, and one exchanged expert moves
#: that token's residual stream by about (its weight, ~0.03-0.05) x (an
#: expert's output, of the stream's own size). Measured on the v5e at the
#: published widths, one 4,096-token sequence a seed (PERF.md, Findings,
#: PR 26): the logits' root-mean-square error is 0.91-1.00% of their root
#: mean square over 28 seeds; held to the program's own routes the reference
#: is 0.49% away, so the 1.5-4.2% of tokens a layer that are routed
#: differently cost as much as the rounding does; the reference with bf16
#: operands in every matrix product is 0.63-0.66% from itself in float32,
#: and with 8-bit-float operands (e4m3, e5m2: the nearest precision below)
#: 8.5-9.1%. The tolerance, the GPT-2 family's, admits the program with a
#: factor of three to spare and refuses an 8-bit program by nearly as much,
#: so a comparison held to the program's routes is not needed to keep it
#: tight.
LOGITS_RMS_TOL = 8 * 2.0 ** -8

#: The mean next-token loss of those logits over the 4,096 tokens, against
#: the reference's. Random rounding and the odd exchanged expert average out
#: over the tokens, so it is held far closer than the logits are, and it is
#: there for what the logits' limit lets through: a fault that moves every
#: logit the same way. Measured on the v5e at the published widths (PERF.md,
#: Findings, PR 26): the sound program's |loss - reference's| / reference's
#: is at most 3.43e-5 over 34 seeds (standard deviation 1.4e-5), and the limit
#: is three times that; the reference with e4m3 operands is 4.9e-4 to 5.0e-4
#: away, and the program's logits 0.5% out of scale, which the logits' limit
#: admits at 1.2% rms, 6.4e-4 to 6.6e-4: neither is correct. It is the
#: published widths' and 4,096 tokens': at toy widths a bf16 program does
#: not meet it (tests/test_olmoe.py).
LOSS_RTOL = 1e-4

#: `check_logits` is handed arrays and no configuration, and the experts per
#: token are in no array's shape: each configuration `transformer_config`
#: was asked about leaves them here under the shapes its parameters have.
_top_k = {}


def within(rms: float, got: float, want: float) -> tuple:
    """Whether (the logits' limit, the loss's limit) hold."""
    return (rms <= LOGITS_RMS_TOL,
            abs(got - want) <= LOSS_RTOL * abs(want))


def transformer_config(config: dict) -> tfm.TransformerConfig:
    program = config["program"]
    if config["rms_norm_eps"] != 1e-5:
        raise ValueError(f"rms_norm_eps {config['rms_norm_eps']}: the "
                         "program's and the reference's RMSNorm have 1e-5")
    shapes = ((config["n_layer"], config["num_experts"],
               config["hidden_size"], config["intermediate_size"]),
              (config["vocab_size"], config["hidden_size"]))
    if _top_k.setdefault(shapes, config["num_experts_per_tok"]) != \
            config["num_experts_per_tok"]:
        raise ValueError("two configurations of these shapes with different "
                         "num_experts_per_tok in one process: check_logits "
                         "cannot tell them apart")
    return tfm.TransformerConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        d_ff=config["intermediate_size"], n_layers=config["n_layer"],
        max_seq=config["max_position_embeddings"],
        num_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        load_balance_coef=program["load_balance_coef"],
        router_z_coef=program["router_z_coef"],
        norm="rmsnorm", positions="rope", rope_theta=config["rope_theta"],
        qk_norm=True, mlp="swiglu", attn=program["attn"],
        dtype=jnp.dtype(program["dtype"]), remat=program["remat"])


def samples_per_step(traffic: dict, chips: int) -> int:
    return traffic["per_chip_batch"] * traffic["seq_len"] * chips


def forward_flops_per_token(config: dict, seq: int) -> dict:
    """FLOPs of the forward pass per token, by part, a multiply-add counted
    as 2; of the attention scores only the causal half."""
    d, f = config["hidden_size"], config["intermediate_size"]
    layers = config["n_layer"]
    return {
        "projections": layers * 2 * 4 * d * d,          # wq, wk, wv, wo
        "attention": layers * 2 * 2 * d * (seq + 1) / 2,
        "router": layers * 2 * d * config["num_experts"],
        "experts": layers * config["num_experts_per_tok"] * 3 * 2 * d * f,
        "head": 2 * d * config["vocab_size"],
    }


def flops_per_sample(config: dict, traffic: dict) -> float:
    """Model FLOPs per token of one training step: what the forward and
    backward passes require (backward = 2 x forward), recomputation not
    counted."""
    return 3.0 * sum(forward_flops_per_token(config,
                                             traffic["seq_len"]).values())


def flash_kernel_shape(config: dict, traffic: dict) -> tuple:
    """(batch, heads, seq, head_dim) of one flash-attention call on a chip."""
    return (traffic["per_chip_batch"], config["num_attention_heads"],
            traffic["seq_len"],
            config["hidden_size"] // config["num_attention_heads"])


def grouped_matmul_shape(config: dict, traffic: dict) -> tuple:
    """(rows, hidden, expert width, experts) of one grouped matmul of the
    expert layer on a chip: every (token, expert) pair is a row."""
    return (traffic["per_chip_batch"] * traffic["seq_len"]
            * config["num_experts_per_tok"], config["hidden_size"],
            config["intermediate_size"], config["num_experts"])


def reference_weights(params) -> dict:
    """The program's parameter tree (layers stacked on a leading axis) as the
    reference's weights, float32."""
    f32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    lp = f32["layers"]
    names = {"ln1_g": "ln1_scale", "ln2_g": "ln2_scale", "wq": "wq",
             "wk": "wk", "wv": "wv", "wo": "wo", "q_g": "q_scale",
             "k_g": "k_scale", "router": "router", "w_gate": "we_gate",
             "w_up": "we1", "w_down": "we2"}
    n_layers = lp["wq"].shape[0]
    return {"wte": f32["embed"], "lnf_g": f32["lnf_scale"],
            "head": f32["unembed"],
            "layers": [{ref: lp[ours][i] for ref, ours in names.items()}
                       for i in range(n_layers)]}


@partial(jax.jit, static_argnames="top_k")
def _compare(params, tokens, system_logits, top_k):
    targets = jnp.roll(tokens, -1, axis=1)
    want, _, routes = reference.forward(reference_weights(params), tokens,
                                        top_k)
    got = system_logits.astype(jnp.float32)
    rms = jnp.sqrt(jnp.mean(jnp.square(got - want))
                   / jnp.mean(jnp.square(want)))
    n_experts = params["layers"]["router"].shape[-1]
    rows = jnp.sum(jax.nn.one_hot(routes, n_experts, dtype=jnp.int32),
                   axis=(1, 2, 3))                      # (layers, experts)
    return (rms, reference.next_token_loss(got, targets),
            reference.next_token_loss(want, targets), rows)


def check_logits(params, tokens, system_logits) -> dict:
    """Compares the program's logits for `tokens` with the reference's on
    the same weights, each side routing for itself. All three arguments sit
    on one device."""
    top_k = _top_k[params["layers"]["we1"].shape, params["embed"].shape]
    rms, got, want, rows = _compare(params, tokens, system_logits, top_k)
    rms, got, want = float(rms), float(got), float(want)
    ok = all(within(rms, got, want))
    load = "; ".join(f"layer {i}: largest {int(r.max())}, mean "
                     f"{float(r.mean()):.0f}" for i, r in enumerate(rows))
    return {"ok": bool(ok),
            "detail": f"logits rms error {rms:.3e} of their rms (tolerance "
                      f"{LOGITS_RMS_TOL:.3e}); loss {got:.6f} against the "
                      f"reference's {want:.6f} (rtol {LOSS_RTOL:.3e}); rows "
                      f"per expert in the reference's routing of these "
                      f"{tokens.size} tokens: {load}"}
