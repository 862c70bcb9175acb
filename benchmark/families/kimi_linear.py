"""Kimi-Linear-architecture decoders (`model_type` kimi_linear,
arXiv:2510.26692: three Kimi Delta Attention layers, the delta rule with a
decay per key channel, to one latent-attention layer without positions, a
leading dense layer inside that pattern, every other layer followed by
sigmoid-scored experts with a selection bias beside one shared expert, an
untied head), of which a chip holds its share of the experts and of the
vocabulary, through `horovod_tpu.models.transformer`."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import kimi_linear as reference
from horovod_tpu.models import transformer as tfm

SAMPLE = "tokens"

#: Agreement with the float32 reference on the same weights and the same
#: share of the model, each side routing for itself. What separates them is
#: what separates the other expert families' two sides
#: (`families/granite_hybrid.py`): the program computes in bf16 through some
#: ten roundings a layer on the residual path, the rule's products take bf16
#: operands in chunks of 64 tokens where the reference goes token by token
#: in float32, and routing is discontinuous: at seeded weights the 256
#: sigmoid scores lie close to one half and to each other, so bf16 moves
#: many tokens' eighth and ninth choices, and each of a token's eight
#: renormalised weights is 0.3 of an expert. Measured on the v5e at the
#: published widths and six layers, one 16,384-token sequence a seed over
#: the 20,480-row vocabulary slice (my chip runs, PR 50; PERF.md, Findings;
#: two seeds for the reference's variants): the sound program reads
#: 2.435-3.156% of the logits' root mean square over 48 seeds (against the
#: reference with bf16 operands 2.580-3.049% over ten of them); the
#: reference with 8-bit-float operands, the nearest precision below, 36.2
#: and 37.2% (e4m3), 33.5 and 33.4% (e5m2); each of `reference.FAULTS`:
#: softmax for the sigmoid scores 8.82 and 7.20%, no 2.446 12.8 and 11.1%,
#: one decay a head 49.6 and 49.6%, the dense layer at an expert's width
#: 62.9 and 63.4%, no renormalisation 81.8 and 76.5%, SiLU for the gate's
#: sigmoid 85.8 and 85.9%, no L2 norm not a number (the reference
#: overflows: refused); the bias counted into the weights reads the sound
#: 2.913 and 2.736% to the digit (the bias is zero at seeded weights) and a
#: rotation on MLA 3.405 and 3.247% against the same seeds' 2.913 and
#: 2.736, so no limit can refuse those two at these weights (the CPU tests
#: refuse them at a size where they show). The limit is 1.58 x the largest
#: sound reading, 0.69 x the smallest reading of the nearest fault it
#: refuses and a seventh of an 8-bit program's.
LOGITS_RMS_TOL = 0.05

#: The mean next-token loss of those logits over the 16,384 tokens, against
#: the reference's, as a share of the reference's. Same runs: the sound
#: program's |loss - reference's| / reference's is 1.65e-6 to 3.95e-5 over
#: 48 seeds (the reference with bf16 operands 2.5e-6 to 3.6e-5); the
#: reference with e4m3 operands 7.86e-4 and 2.87e-4, with e5m2 9.99e-5 and
#: 4.34e-4: three of the four refused by this limit too, one e5m2 reading a
#: hair under it (the logits' limit is the one that refuses them, by a
#: factor of seven). The limit is 2.5 x the largest sound reading. It does
#: not refuse every fault (seeded weights put the loss at 10.42-10.44
#: against ln 20,480 = 9.93 and a fault can leave the mean where it was:
#: 2.7e-6 to 1.1e-3 over the sixteen fault readings that are numbers).
LOSS_RTOL = 1e-4

#: tokens whose reference logits exist at a time
HEAD_BLOCK = reference.LOSS_BLOCK

#: `check_logits` is handed arrays and no configuration, and neither the
#: order of the layers, the experts per token nor the first expert held is
#: in an array's shape: each configuration `transformer_config` was asked
#: about leaves them here under the shapes its parameters have.
_unshaped = {}


def within(rms: float, got: float, want: float) -> tuple:
    """Whether (the logits' limit, the loss's limit) hold."""
    return (rms <= LOGITS_RMS_TOL,
            abs(got - want) <= LOSS_RTOL * abs(want))


def first_expert(config: dict) -> int:
    """The first expert this chip of the deployment holds."""
    return config["deployment"]["expert_rank"] * config["num_experts"]


def kinds(config: dict) -> tuple:
    """Each layer's kind, in the order the layers run: layers 1 to `n_layer`
    of `linear_attn_config`'s two lists (1-based, as published)."""
    listed = config["linear_attn_config"]
    kind_of = {**dict.fromkeys(listed["kda_layers"], "kda"),
               **dict.fromkeys(listed["full_attn_layers"], "mla")}
    return tuple(kind_of[i] for i in range(1, config["n_layer"] + 1))


def pattern(config: dict) -> tuple:
    """One period of the published order: up to and with the first
    latent-attention layer. The layers held are its repeats, the last period
    cut where the layers end (`transformer._pattern_segments`)."""
    published = kinds(dict(config, n_layer=config["num_hidden_layers"]))
    period = published[:published.index("mla") + 1]
    held = kinds(config)
    if held != (period * len(held))[:len(held)]:
        raise ValueError(f"the layers {held} are no repeats of {period}")
    return period


def _shapes(params) -> tuple:
    """What tells two configurations' parameter trees apart."""
    stacks = params["layers"]
    return params["embed"].shape + tuple(
        (kind, leaves["we1"].shape, leaves["router"].shape,
         leaves["wo"].shape)
        for of_kind in (stacks if isinstance(stacks, list) else [stacks])
        for kind, leaves in sorted(of_kind.items()))


def transformer_config(config: dict) -> tfm.TransformerConfig:
    program, published = config["program"], config["published"]
    linear = config["linear_attn_config"]
    if config["hidden_act"] != "silu" or not config["mla_use_nope"] \
            or config["q_lora_rank"] is not None \
            or config["moe_router_activation_func"] != "sigmoid" \
            or not config["moe_renormalize"] or config["moe_layer_freq"] != 1 \
            or config["num_expert_group"] != 1 or config["topk_group"] != 1 \
            or config["tie_word_embeddings"] \
            or config["num_nextn_predict_layers"] \
            or config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("a kimi_linear configuration this family has no "
                         "equations for")
    if (config["rms_norm_eps"], config["routed_scaling_factor"]) != (
            reference.RMS_EPS, reference.ROUTED_SCALING_FACTOR):
        raise ValueError("rms_norm_eps or routed_scaling_factor differs "
                         "from the constants of "
                         "benchmark/reference/kimi_linear.py")
    cfg = tfm.TransformerConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        d_ff=config["moe_intermediate_size"], n_layers=config["n_layer"],
        max_seq=config["model_max_length"],
        num_experts=published["num_experts"],
        experts_per_token=config["num_experts_per_token"],
        experts_held=config["num_experts"],
        first_expert=first_expert(config),
        shared_experts=config["num_shared_experts"],
        first_k_dense=config["first_k_dense_replace"],
        d_ff_dense=config["intermediate_size"],
        capacity_factor=program["held_capacity"],
        norm_topk=True, router_scoring="sigmoid", router_bias=True,
        routed_scale=config["routed_scaling_factor"],
        load_balance_coef=program["load_balance_coef"],
        router_z_coef=program["router_z_coef"],
        norm="rmsnorm", rms_norm_eps=config["rms_norm_eps"],
        positions="none", layer_pattern=pattern(config), mlp="swiglu",
        attention="kda", gdn_heads=linear["num_heads"],
        gdn_key_dim=linear["head_dim"], gdn_value_dim=linear["head_dim"],
        gdn_conv=linear["short_conv_kernel_size"],
        kda_rank=config["assumed"]["kda_rank"],
        kv_latent=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        attn=program["attn"], dtype=jnp.dtype(program["dtype"]),
        remat=program["remat"], remat_policy=program["remat_policy"])
    shapes = _shapes(jax.eval_shape(lambda k: tfm.init(k, cfg),
                                    jax.random.PRNGKey(0)))
    kept = (kinds(config), config["num_experts_per_token"],
            first_expert(config))
    if _unshaped.setdefault(shapes, kept) != kept:
        raise ValueError("two configurations of these shapes with different "
                         "layer orders, experts per token or first experts "
                         "in one process: check_logits cannot tell them "
                         "apart")
    return cfg


def samples_per_step(traffic: dict, chips: int) -> int:
    return traffic["per_chip_batch"] * traffic["seq_len"] * chips


def forward_flops_per_token(config: dict, seq: int) -> dict:
    """FLOPs of the forward pass per token on this chip, by part, a
    multiply-add counted as 2; of the attention scores what the causal mask
    holds; of the rule the recurrent form's 3 dk dv multiply-adds a token a
    head (decay and read, write, query), whatever the chunked form spends;
    of the experts what the held ones do under an even routing
    (`num_experts_per_token` x held / routed of an expert a token). Norms,
    the convolutions and the gates are elementwise and not counted."""
    d = config["hidden_size"]
    held = kinds(config)
    linear = config["linear_attn_config"]
    heads, width = linear["num_heads"], linear["head_dim"]
    rank = config["assumed"]["kda_rank"]
    mla_heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    latent, v = config["kv_lora_rank"], config["v_head_dim"]
    routed = config["published"]["num_experts"]
    dense = config["first_k_dense_replace"]
    expert_layers = len(held) - dense
    return {
        # q, k, v and the output; the decay's and the gate's two; beta
        "kda_projections": held.count("kda") * 2 * (
            4 * d * heads * width + 2 * (d * rank + rank * heads * width)
            + d * heads),
        "kda_rule": held.count("kda") * 2 * 3 * heads * width * width,
        # W_q, W_kv_a, W_kv_b, W_o
        "mla_projections": held.count("mla") * 2 * (
            d * mla_heads * (nope + rope) + d * (latent + rope)
            + latent * mla_heads * (nope + v) + mla_heads * v * d),
        # q.k (nope + rope wide) and p.v (v wide) over the causal half
        "attention": held.count("mla") * 2 * mla_heads * (nope + rope + v)
        * (seq + 1) / 2,
        "dense_mlp": dense * 3 * 2 * d * config["intermediate_size"],
        "router": expert_layers * 2 * d * routed,
        "experts": expert_layers * config["num_experts_per_token"]
        * config["num_experts"] / routed
        * 3 * 2 * d * config["moe_intermediate_size"],
        "shared": expert_layers * config["num_shared_experts"]
        * 3 * 2 * d * config["moe_intermediate_size"],
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
    flash-attention call on a chip: the latent-attention layers'."""
    return (traffic["per_chip_batch"], config["num_attention_heads"],
            traffic["seq_len"],
            config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
            config["v_head_dim"])


def grouped_matmul_shape(config: dict, traffic: dict) -> tuple:
    """(rows, hidden, expert width, experts) of one grouped matmul of the
    expert layer on a chip. The rows are the EXPECTED ones, the (token,
    expert) pairs an even routing sends to the experts held (8,192 of the
    131,072 in the cell), as `families/deepseek_v2.py` counts them: the
    useful work of an even load, not the rows the kernels go through, which
    are always the row buffer's (`parallel/moe.py` `held_rows`:
    `program.held_capacity` times these, four in the cell, the free ones
    zero)."""
    pairs = traffic["per_chip_batch"] * traffic["seq_len"] \
        * config["num_experts_per_token"]
    return (pairs * config["num_experts"]
            // config["published"]["num_experts"],
            config["hidden_size"], config["moe_intermediate_size"],
            config["num_experts"])


def rule_work(rows: int, dk: int, dv: int) -> tuple:
    """((FLOPs, bytes) of a forward pass, the same of a backward pass) of
    the delta rule with a decay per key channel over `rows` (token, head)
    pairs, at least: the recurrent form's 3 dk dv multiply-adds a pair
    forward (decay and read, write, query; 2 FLOPs each), twice that
    backward; a forward reads q, k, v (bf16), g (dk float32 numbers a pair)
    and beta (float32) and writes o once; a backward reads those and do and
    writes the five gradients."""
    qkv, gates, out = (2 * dk + dv) * 2, (dk + 1) * 4, dv * 2
    forward = (2 * 3 * dk * dv * rows, (qkv + gates + out) * rows)
    backward = (2 * forward[0], (qkv + gates + out + qkv + gates) * rows)
    return forward, backward


def kda_scan_work(config: dict, traffic: dict) -> tuple:
    """What the rule of a step's KDA layers needs at least: ((executions a
    step, FLOPs, bytes) of a forward pass over one layer's sequences, the
    same of a backward pass), from `rule_work`. Under remat the forward runs
    twice a layer."""
    linear = config["linear_attn_config"]
    layers = kinds(config).count("kda")
    forward, backward = rule_work(
        traffic["per_chip_batch"] * traffic["seq_len"] * linear["num_heads"],
        linear["head_dim"], linear["head_dim"])
    repeats = 2 if config["program"]["remat"] else 1
    return (layers * repeats, *forward), (layers, *backward)


_EVERY = {"ln1_g": "ln1_scale", "ln2_g": "ln2_scale", "wo": "wo"}
_MIXER = {
    "kda": {name: "kda_" + name for name in (
        "wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "wf_down", "wf_up",
        "wg_down", "wg_up", "bg", "wb", "a_log")}
    | {"dt_b": "kda_dt_bias", "o_g": "kda_o_scale"},
    "mla": {"wq": "wq", "wkv_a": "wkv_a", "kv_g": "kv_scale",
            "wkv_b": "wkv_b"}}
_FFN = {"experts": {"router": "router", "bias": "router_bias",
                    "w_gate": "we_gate", "w_up": "we1", "w_down": "we2",
                    "ws_gate": "ws_gate", "ws_up": "ws1", "ws_down": "ws2"},
        "dense": {"w_gate": "w_gate", "w_up": "w1", "w_down": "w2"}}


def reference_weights(params, layer_kinds: tuple) -> dict:
    """The program's parameter tree (the leading dense layers a stack of
    their own; behind them each segment's layers per kind, stacked over
    (periods, the kind's layers in a period)) as the reference's weights,
    float32, in the order the layers run (`layer_kinds`)."""
    f32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)

    def one(leaves, kind, ffn, at):
        return {ref: leaves[ours][at] for ref, ours in {
            **_EVERY, **_MIXER[kind], **_FFN[ffn]}.items()}

    dense = f32.get("dense_layers", {})
    layers = [one(dense, layer_kinds[i], "dense", (i,))
              for i in range(len(dense.get("wo", ())))]
    stacks = f32["layers"]
    for of_kind in stacks if isinstance(stacks, list) else [stacks]:
        periods = next(iter(of_kind.values()))["wo"].shape[0]
        in_a_period = sum(leaves["wo"].shape[1]
                          for leaves in of_kind.values())
        for p in range(periods):
            seen = dict.fromkeys(of_kind, 0)
            for kind in layer_kinds[len(layers):][:in_a_period]:
                layers.append(one(of_kind[kind], kind, "experts",
                                  (p, seen[kind])))
                seen[kind] += 1
    return {"wte": f32["embed"], "lnf_g": f32["lnf_scale"],
            "head": f32["unembed"], "layers": layers}


def compare(params, tokens, system_logits, layer_kinds, top_k, first=0,
            operands=None, fault=None):
    """(the logits' rms error over the reference's rms, the program's loss,
    the reference's, the rows of each held expert in the reference's routing
    (expert layers, held)): the reference's final hidden state whole, its
    head and both losses `HEAD_BLOCK` tokens at a time."""
    weights = reference_weights(params, layer_kinds)
    hidden, routes = reference.final_hidden(
        weights, tokens, layer_kinds, top_k, first, operands, fault)
    batch, seq = tokens.shape
    block = min(HEAD_BLOCK, seq)
    if seq % block:
        raise ValueError(f"{seq} tokens are no whole number of blocks of "
                         f"{block}")
    targets = jnp.roll(tokens, -1, axis=1)

    def of_block(start):
        def rows(x):
            return lax.dynamic_slice_in_dim(x, start, block, axis=1)

        want = reference.head(rows(hidden), weights, operands)
        got = rows(system_logits).astype(jnp.float32)
        aim = rows(targets)

        def nll(logits):
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.sum(jnp.take_along_axis(logp, aim[..., None],
                                                axis=-1))

        return (jnp.sum(jnp.square(got - want)), jnp.sum(jnp.square(want)),
                nll(got), nll(want))

    off, size, got, want = (jnp.sum(x) for x in lax.map(
        of_block, jnp.arange(0, seq, block)))
    routed = weights["layers"][-1]
    n_experts, held = routed["router"].shape[1], routed["w_up"].shape[0]
    rows_of = jnp.sum(jax.nn.one_hot(routes, n_experts, dtype=jnp.int32),
                      axis=(1, 2, 3))                   # (layers, experts)
    return (jnp.sqrt(off / size), got / (batch * seq), want / (batch * seq),
            rows_of[:, first:first + held])


_compare = jax.jit(compare, static_argnames=(
    "layer_kinds", "top_k", "first", "operands", "fault"))


def check_logits(params, tokens, system_logits) -> dict:
    """Compares the program's logits for `tokens` with the reference's on
    the same weights and the same share of the model, each side routing for
    itself. All three arguments sit on one device."""
    layer_kinds, top_k, first = _unshaped[_shapes(params)]
    rms, got, want, rows = _compare(params, tokens, system_logits,
                                    layer_kinds, top_k, first)
    rms, got, want = float(rms), float(got), float(want)
    ok = all(within(rms, got, want))
    stacks = params["layers"]
    routers = next(iter((stacks[0] if isinstance(stacks, list)
                         else stacks).values()))["router"].shape[-1]
    even = tokens.size * top_k * rows.shape[1] // routers
    return {"ok": bool(ok),
            "detail": f"logits rms error {rms:.3e} of their rms (tolerance "
                      f"{LOGITS_RMS_TOL:.3e}); loss {got:.6f} against the "
                      f"reference's {want:.6f} (rtol {LOSS_RTOL:.3e}); rows "
                      f"of the {rows.shape[1]} held experts in the "
                      f"reference's routing of these {tokens.size} tokens: "
                      f"{int(rows.sum(axis=1).min())} to "
                      f"{int(rows.sum(axis=1).max())} a layer ({even} if "
                      f"even), one expert's largest {int(rows.max())}"}
