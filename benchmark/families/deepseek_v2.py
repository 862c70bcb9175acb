"""DeepSeek-V2-architecture decoders (latent attention, a leading dense layer,
shared and routed gated-SiLU experts of which a chip holds its share, YaRN)
through `horovod_tpu.models.transformer`."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference import deepseek_v2 as reference
from horovod_tpu.models import transformer as tfm

SAMPLE = "tokens"

#: Agreement with the float32 reference on the same weights and the same
#: share of the experts, each side routing for itself. What separates them
#: is what separates the OLMoE family's two sides (`families/olmoe.py`): the
#: program computes in bf16 through some ten roundings a layer on the
#: residual path, and routing is discontinuous, so the few tokens whose sixth
#: and seventh largest router scores lie closer than the router's rounding
#: error go to different experts on the two sides. Measured on the v5e at
#: the published widths, one 4,096-token sequence a seed over the
#: 12,800-row vocabulary slice (PERF.md, Findings, PR 30; three seeds for
#: each fault): the sound program reads 1.342-1.384% of the logits' root
#: mean square over 30 seeds (the reference with bf16 operands 0.71-0.74%
#: from itself in float32); the reference with 8-bit-float operands (the
#: nearest precision below) 20.8-21.3% (e4m3) and 26.4-26.5% (e5m2); the
#: program without its shared experts 99.2-99.8%, without YaRN's m^2 on the
#: scores 22.5-22.7%, with the shared rotary key left unrotated 29.2-29.3%,
#: routing each token to five experts and not six 2.30-2.32%. The readings
#: hardly move with the seed, so the limit can stand close: 5 * 2^-8 = 1.95%
#: is 1.41 x the largest sound reading and 0.85 x the smallest faulty one
#: (the dropped sixth expert, which the other LM families' 8 * 2^-8 would
#: admit), a tenth of an 8-bit program's.
LOGITS_RMS_TOL = 5 * 2.0 ** -8

#: The mean next-token loss of those logits over the 4,096 tokens, against
#: the reference's: held far closer than the logits, for the faults that
#: move every logit the same way. Same runs: the sound program's
#: |loss - reference's| / reference's is at most 4.28e-5 over 30 seeds
#: (the next largest 3.24e-5 and 2.76e-5); the reference with e4m3
#: operands is 5.4e-4 to 8.2e-4 away, the program's logits 0.5% out of
#: scale, which the logits' limit admits at 1.56-1.58% rms, 7.3e-4 to
#: 7.5e-4, the program without m^2 3.7e-4 to 7.9e-4: none is correct. The
#: limit lies between: 3.5 x the largest sound reading, 3.6 x under the
#: smallest of an 8-bit reference. (A dropped sixth expert, 5e-6 to 5.8e-5,
#: and in one seed of three the unrotated key, 6.9e-5, pass it: those are
#: the logits' limit's to refuse.) It is the published widths' and 4,096
#: tokens': a bf16 program at toy widths does not meet it.
LOSS_RTOL = 1.5e-4

#: `check_logits` is handed arrays and no configuration, and neither the
#: experts per token nor the first expert held is in an array's shape: each
#: configuration `transformer_config` was asked about leaves them here under
#: the shapes its parameters have.
_unshaped = {}


def within(rms: float, got: float, want: float) -> tuple:
    """Whether (the logits' limit, the loss's limit) hold."""
    return (rms <= LOGITS_RMS_TOL,
            abs(got - want) <= LOSS_RTOL * abs(want))


def first_expert(config: dict) -> int:
    """The first routed expert this chip of the deployment holds."""
    return config["deployment"]["chip"] * config["n_routed_experts"]


def transformer_config(config: dict) -> tfm.TransformerConfig:
    program = config["program"]
    yarn = config["rope_scaling"]
    if yarn["type"] != "yarn" or config["q_lora_rank"] is not None or \
            config["topk_method"] != "greedy" or config["n_group"] != 1 or \
            config["norm_topk_prob"] or config["routed_scaling_factor"] != 1 \
            or config["scoring_func"] != "softmax" or not config["seq_aux"] \
            or config["moe_layer_freq"] != 1:
        raise ValueError("a deepseek_v2 configuration this family has no "
                         "equations for")
    if (config["rms_norm_eps"], config["rope_theta"], yarn) != (
            reference.RMS_EPS, reference.ROPE_THETA,
            dict(reference.YARN, type="yarn")):
        raise ValueError("rms_norm_eps, rope_theta or rope_scaling differ "
                         "from the constants of benchmark/reference/"
                         "deepseek_v2.py")
    held = config["n_routed_experts"]
    shapes = ((config["n_layer"] - config["first_k_dense_replace"], held,
               config["hidden_size"], config["moe_intermediate_size"]),
              (config["vocab_size"], config["hidden_size"]))
    kept = (config["num_experts_per_tok"], first_expert(config))
    if _unshaped.setdefault(shapes, kept) != kept:
        raise ValueError("two configurations of these shapes with different "
                         "num_experts_per_tok or first expert in one "
                         "process: check_logits cannot tell them apart")
    return tfm.TransformerConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        d_ff=config["moe_intermediate_size"], n_layers=config["n_layer"],
        max_seq=config["max_position_embeddings"],
        num_experts=config["published"]["n_routed_experts"],
        experts_per_token=config["num_experts_per_tok"],
        experts_held=held, first_expert=first_expert(config),
        shared_experts=config["n_shared_experts"],
        first_k_dense=config["first_k_dense_replace"],
        d_ff_dense=config["intermediate_size"],
        # every expert layer adds alpha times its term (the published
        # implementation); the program takes the layers' mean
        load_balance_coef=program["load_balance_coef"]
        * (config["n_layer"] - config["first_k_dense_replace"]),
        balance_per_sequence=config["seq_aux"],
        norm="rmsnorm", rms_norm_eps=config["rms_norm_eps"],
        positions="rope", rope_theta=config["rope_theta"],
        yarn=tfm.Yarn(
            factor=yarn["factor"],
            original_max=yarn["original_max_position_embeddings"],
            beta_fast=yarn["beta_fast"], beta_slow=yarn["beta_slow"],
            mscale=yarn["mscale"], mscale_all_dim=yarn["mscale_all_dim"]),
        attention="mla", kv_latent=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        mlp="swiglu", attn=program["attn"],
        dtype=jnp.dtype(program["dtype"]), remat=program["remat"])


def samples_per_step(traffic: dict, chips: int) -> int:
    return traffic["per_chip_batch"] * traffic["seq_len"] * chips


def forward_flops_per_token(config: dict, seq: int) -> dict:
    """FLOPs of the forward pass per token on this chip, by part, a
    multiply-add counted as 2; of the attention scores only the causal half;
    of the routed experts what the held ones do under an even routing
    (`num_experts_per_tok` x held / routed of an expert a token)."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    v, latent = config["v_head_dim"], config["kv_lora_rank"]
    layers = config["n_layer"]
    dense = config["first_k_dense_replace"]
    sparse = layers - dense
    routed = config["published"]["n_routed_experts"]
    width = config["moe_intermediate_size"]
    return {
        # q_proj, kv_a_proj_with_mqa, kv_b_proj, o_proj
        "projections": layers * 2 * (
            d * heads * (nope + rope) + d * (latent + rope)
            + latent * heads * (nope + v) + heads * v * d),
        # q.k at nope + rope wide, p.v at v wide
        "attention": layers * 2 * heads * (nope + rope + v) * (seq + 1) / 2,
        "dense_mlp": dense * 3 * 2 * d * config["intermediate_size"],
        "shared_experts": sparse * 3 * 2 * d
        * config["n_shared_experts"] * width,
        "router": sparse * 2 * d * routed,
        "experts": sparse * config["num_experts_per_tok"]
        * config["n_routed_experts"] / routed * 3 * 2 * d * width,
        "head": 2 * d * config["vocab_size"],
    }


def flops_per_sample(config: dict, traffic: dict) -> float:
    """Model FLOPs per token of one training step: what the forward and
    backward passes require (backward = 2 x forward), recomputation not
    counted."""
    return 3.0 * sum(forward_flops_per_token(config,
                                             traffic["seq_len"]).values())


def flash_kernel_shape(config: dict, traffic: dict) -> tuple:
    """(batch, heads, seq, the queries' and keys' width, the values') of one
    flash-attention call on a chip."""
    return (traffic["per_chip_batch"], config["num_attention_heads"],
            traffic["seq_len"],
            config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
            config["v_head_dim"])


def grouped_matmul_shape(config: dict, traffic: dict) -> tuple:
    """(rows, hidden, expert width, experts) of one grouped matmul of the
    expert layer on a chip. The rows are the EXPECTED ones, the (token,
    expert) pairs an even routing sends to the experts held (6,144 of the
    49,152 in the cell): the useful work of an even load. They are neither
    the rows a run routed there (the seed's and the step's: ~6,050 a layer
    at the seeded weights, more as the router learns; PERF.md, Findings,
    PR 30) nor the rows the kernels go through, which are always the row
    buffer's (`parallel/moe.py` `held_rows`, twice these, the free ones
    zero): `moe_experts_roofline`, which counts from this shape, is the
    even load's operations over the buffer's time."""
    pairs = traffic["per_chip_batch"] * traffic["seq_len"] \
        * config["num_experts_per_tok"]
    return (pairs * config["n_routed_experts"]
            // config["published"]["n_routed_experts"],
            config["hidden_size"], config["moe_intermediate_size"],
            config["n_routed_experts"])


def reference_weights(params) -> dict:
    """The program's parameter tree (each stack's layers on a leading axis)
    as the reference's weights, float32."""
    f32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    attention = {"ln1_g": "ln1_scale", "ln2_g": "ln2_scale", "wq": "wq",
                 "wkv_a": "wkv_a", "kv_g": "kv_scale", "wkv_b": "wkv_b",
                 "wo": "wo"}
    dense = {"w_gate": "w_gate", "w_up": "w1", "w_down": "w2"}
    experts = {"router": "router", "w_gate": "we_gate", "w_up": "we1",
               "w_down": "we2", "ws_gate": "ws_gate", "ws_up": "ws1",
               "ws_down": "ws2"}

    def layers_of(stack, names):
        names = dict(attention, **names)
        depth = stack["wq"].shape[0]
        return [{ref: stack[ours][i] for ref, ours in names.items()}
                for i in range(depth)]

    return {"wte": f32["embed"], "lnf_g": f32["lnf_scale"],
            "head": f32["unembed"],
            "layers": layers_of(f32["dense_layers"], dense)
            + layers_of(f32["layers"], experts)}


@partial(jax.jit, static_argnames=("top_k", "first"))
def _compare(params, tokens, system_logits, top_k, first):
    targets = jnp.roll(tokens, -1, axis=1)
    want, _, routes = reference.forward(reference_weights(params), tokens,
                                        top_k, first_expert=first)
    got = system_logits.astype(jnp.float32)
    rms = jnp.sqrt(jnp.mean(jnp.square(got - want))
                   / jnp.mean(jnp.square(want)))
    n_experts = params["layers"]["router"].shape[-1]
    rows = jnp.sum(jax.nn.one_hot(routes, n_experts, dtype=jnp.int32),
                   axis=(1, 2, 3))                      # (layers, experts)
    held = params["layers"]["we1"].shape[1]
    return (rms, reference.next_token_loss(got, targets),
            reference.next_token_loss(want, targets),
            rows[:, first:first + held])


def check_logits(params, tokens, system_logits) -> dict:
    """Compares the program's logits for `tokens` with the reference's on
    the same weights and the same share of the experts, each side routing
    for itself. All three arguments sit on one device."""
    top_k, first = _unshaped[params["layers"]["we1"].shape,
                             params["embed"].shape]
    rms, got, want, rows = _compare(params, tokens, system_logits, top_k,
                                    first)
    rms, got, want = float(rms), float(got), float(want)
    ok = all(within(rms, got, want))
    even = tokens.size * top_k * rows.shape[1] \
        // params["layers"]["router"].shape[-1]
    return {"ok": bool(ok),
            "detail": f"logits rms error {rms:.3e} of their rms (tolerance "
                      f"{LOGITS_RMS_TOL:.3e}); loss {got:.6f} against the "
                      f"reference's {want:.6f} (rtol {LOSS_RTOL:.3e}); rows "
                      f"of the {rows.shape[1]} held experts in the "
                      f"reference's routing of these {tokens.size} tokens: "
                      f"{int(rows.sum(axis=1).min())} to "
                      f"{int(rows.sum(axis=1).max())} a layer ({even} if "
                      f"even), one expert's largest {int(rows.max())}"}
