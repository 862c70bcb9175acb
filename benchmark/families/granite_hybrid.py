"""Granite-4.0-H-architecture decoders (`model_type` granitemoehybrid: nine
Mamba-2 state-space layers, arXiv:2405.21060, to one grouped-query attention
layer without positions, every layer followed by routed SiLU-gated experts
beside one shared gated MLP, a tied head, and four scalar multipliers on the
embedding, the residual branches, the attention scores and the logits), of
which a chip holds its share of the experts, of the mixers' heads and of the
vocabulary, through `horovod_tpu.models.transformer`."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import granite_hybrid as reference
from horovod_tpu.models import transformer as tfm

SAMPLE = "tokens"

#: Agreement with the float32 reference on the same weights and the same
#: share of the model, each side routing for itself. What separates them is
#: what separates the other expert families' two sides
#: (`families/smallthinker.py`): the program computes in bf16 through some
#: ten roundings a layer on the residual path, the scan's products take bf16
#: operands in chunks where the reference goes token by token in float32,
#: and routing is discontinuous, so the few tokens whose tenth and eleventh
#: largest router logits lie closer than the router's rounding error go to
#: different experts on the two sides. Measured on the v5e at the published
#: widths, one 4,096-token sequence a seed over the 12,544-row vocabulary
#: slice (my chip runs, PR 46; PERF.md, Findings; two seeds for the
#: reference's variants): the sound program reads 1.430-1.514% of the
#: logits' root mean square over eleven seeds (against the reference with
#: bf16 operands 1.558 and 1.608%); the reference with 8-bit-float operands,
#: the nearest precision below, 28.07 and 28.12% (e4m3), 52.71 and 53.01%
#: (e5m2); each of `reference.FAULTS`: 128^-1/2 in place of 1/128 2.205 and
#: 2.231%, the ten weights not renormalised 7.22 and 7.30%, the norm before
#: the gate 51.5 and 53.2%, A ignored 68.2 and 71.7%, r = 1 82.5 and 85.4%,
#: the logits not divided 93.75%; rotary positions on the attention layer
#: 1.473 and 1.512%, where the same seeds' sound readings are 1.468 and
#: 1.506: under the 1/128 multiplier a seeded model's scores are of order
#: 0.1 and its attention all but uniform, so what rotates them moves
#: nothing, and no limit can refuse that fault at these weights (the CPU
#: tests refuse it at a size where the scores are of order one). The limit
#: is 1.21 x the largest sound reading, 0.83 x the smallest reading of the
#: nearest fault it refuses and a fifteenth of an 8-bit program's.
LOGITS_RMS_TOL = 0.0183

#: The mean next-token loss of those logits over the 4,096 tokens, against
#: the reference's, as a share of the reference's: held far closer than the
#: logits, for a fault that moves every logit the same way. Same runs: the
#: sound program's |loss - reference's| / reference's is 7e-7 to 5.3e-6
#: over eleven seeds (the reference with bf16 operands 5e-7 and 1.8e-6); the
#: reference with e4m3 operands 3.6e-5 and 1.0e-4, with e5m2 4.0e-5 and
#: 9.3e-5. The limit is 3.8 x the largest sound reading and 1.8 x under the
#: smallest 8-bit one. It does not refuse every fault (seeded weights
#: predict nearly uniformly, loss 9.44 against ln 12,544 = 9.437, so a fault
#: can leave the mean loss where it was: 1.2e-6 to 0.55 over the fourteen
#: fault readings); the logits' limit refuses all but the two of the
#: rotation.
LOSS_RTOL = 2e-5

#: tokens whose reference logits exist at a time
HEAD_BLOCK = reference.LOSS_BLOCK

#: `check_logits` is handed arrays and no configuration, and neither the
#: order of the layers, the experts per token nor the first expert held is
#: in an array's shape: each configuration `transformer_config` was asked
#: about leaves them here under the shapes its parameters have.
_unshaped = {}

_KIND = {"mamba": "mamba2", "attention": "full"}


def within(rms: float, got: float, want: float) -> tuple:
    """Whether (the logits' limit, the loss's limit) hold."""
    return (rms <= LOGITS_RMS_TOL,
            abs(got - want) <= LOSS_RTOL * abs(want))


def first_expert(config: dict) -> int:
    """The first expert this chip of the deployment holds."""
    return config["deployment"]["expert_rank"] * config["num_local_experts"]


def kinds(config: dict) -> tuple:
    """Each layer's kind, in the order the layers run: the first `n_layer`
    entries of `layer_types`."""
    return tuple(_KIND[t] for t in config["layer_types"][:config["n_layer"]])


def pattern(config: dict) -> tuple:
    """One period of `kinds`: the shortest run of layers they repeat."""
    held = kinds(config)
    return next(held[:n] for n in range(1, len(held) + 1)
                if len(held) % n == 0 and held[:n] * (len(held) // n) == held)


def head_dim(config: dict) -> int:
    """The width of an attention head: hidden_size over the PUBLISHED head
    count (config.json has no key for it)."""
    return config["hidden_size"] // config["published"]["num_attention_heads"]


def _shapes(params) -> tuple:
    """What tells two configurations' parameter trees apart."""
    return params["embed"].shape + tuple(
        (kind, leaves["we1"].shape, leaves["router"].shape)
        + tuple(leaves[k].shape for k in ("wq", "ssd_w_in") if k in leaves)
        for kind, leaves in sorted(params["layers"].items()))


def transformer_config(config: dict) -> tfm.TransformerConfig:
    program, published = config["program"], config["published"]
    if config["mamba_n_groups"] != 1 or not config["mamba_conv_bias"] \
            or config["mamba_proj_bias"] or config["attention_bias"] \
            or config["hidden_act"] != "silu" \
            or config["position_embedding_type"] != "nope" \
            or config["normalization_function"] != "rmsnorm" \
            or not config["tie_word_embeddings"] \
            or config["shared_intermediate_size"] \
            % config["intermediate_size"] \
            or config["mamba_expand"] * config["hidden_size"] \
            != published["mamba_n_heads"] * config["mamba_d_head"] \
            or len(config["layer_types"]) < config["n_layer"]:
        raise ValueError("a granitemoehybrid configuration this family has "
                         "no equations for")
    if (config["rms_norm_eps"], config["embedding_multiplier"],
            config["residual_multiplier"], config["attention_multiplier"],
            config["logits_scaling"]) != (
            reference.RMS_EPS, reference.EMBEDDING_MULTIPLIER,
            reference.RESIDUAL_MULTIPLIER, reference.ATTENTION_MULTIPLIER,
            reference.LOGITS_SCALING):
        raise ValueError("rms_norm_eps or a multiplier differs from the "
                         "constants of benchmark/reference/granite_hybrid.py")
    cfg = tfm.TransformerConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], d_head=head_dim(config),
        d_ff=config["intermediate_size"], n_layers=config["n_layer"],
        max_seq=config["max_position_embeddings"],
        num_experts=published["num_local_experts"],
        experts_per_token=config["num_experts_per_tok"],
        experts_held=config["num_local_experts"],
        first_expert=first_expert(config),
        shared_experts=config["shared_intermediate_size"]
        // config["intermediate_size"],
        norm_topk=True, load_balance_coef=program["load_balance_coef"],
        router_z_coef=program["router_z_coef"],
        norm="rmsnorm", rms_norm_eps=config["rms_norm_eps"],
        positions="none", layer_pattern=pattern(config), mlp="swiglu",
        tied_head=True, ssd_heads=config["mamba_n_heads"],
        ssd_head_dim=config["mamba_d_head"],
        ssd_state=config["mamba_d_state"], ssd_conv=config["mamba_d_conv"],
        embed_scale=config["embedding_multiplier"],
        residual_scale=config["residual_multiplier"],
        attn_scale=config["attention_multiplier"],
        logit_scale=1.0 / config["logits_scaling"],
        attn=program["attn"], dtype=jnp.dtype(program["dtype"]),
        remat=program["remat"], remat_policy=program["remat_policy"])
    shapes = _shapes(jax.eval_shape(lambda k: tfm.init(k, cfg),
                                    jax.random.PRNGKey(0)))
    kept = (kinds(config), config["num_experts_per_tok"],
            first_expert(config))
    if _unshaped.setdefault(shapes, kept) != kept:
        raise ValueError("two configurations of these shapes with different "
                         "layer orders, experts per token or first experts "
                         "in one process: check_logits cannot tell them "
                         "apart")
    return cfg


def samples_per_step(traffic: dict, chips: int) -> int:
    return traffic["per_chip_batch"] * traffic["seq_len"] * chips


def scan_macs_per_token(config: dict) -> float:
    """Multiply-adds a token of one Mamba-2 layer's scan in the chunked form
    at the published chunk Q, of the (Q x Q) scores and their product only
    what the causal mask holds: N Q for the scores C B^T (once a group) and
    (Q / 2 + 2 N) P a head held (the masked scores times the inputs, the
    state's read-out and its update)."""
    q, states = config["mamba_chunk_size"], config["mamba_d_state"]
    return states * q + config["mamba_n_heads"] * config["mamba_d_head"] * (
        q / 2 + 2 * states)


def forward_flops_per_token(config: dict, seq: int) -> dict:
    """FLOPs of the forward pass per token on this chip, by part, a
    multiply-add counted as 2; of the attention scores and of the scan's
    what the causal mask holds; of the experts what the held ones do under
    an even routing (`num_experts_per_tok` x held / routed of an expert a
    token). Norms, the convolution and the gates are elementwise and not
    counted."""
    d, width = config["hidden_size"], head_dim(config)
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    layers = config["n_layer"]
    held = kinds(config)
    channels = config["mamba_n_heads"] * config["mamba_d_head"]
    routed = config["published"]["num_local_experts"]
    return {
        # in_proj [z | x | B | C | dt] and out_proj
        "ssd_projections": held.count("mamba2") * 2 * d * (
            3 * channels + 2 * config["mamba_d_state"]
            + config["mamba_n_heads"]),
        "ssd_scan": held.count("mamba2") * 2 * scan_macs_per_token(config),
        # q_proj, k_proj, v_proj, o_proj
        "projections": held.count("full") * 2 * (
            d * (heads + 2 * kv) * width + heads * width * d),
        # q.k and p.v, each `width` wide, over the causal half
        "attention": held.count("full") * 2 * heads * 2 * width
        * (seq + 1) / 2,
        "router": layers * 2 * d * routed,
        "experts": layers * config["num_experts_per_tok"]
        * config["num_local_experts"] / routed
        * 3 * 2 * d * config["intermediate_size"],
        "shared": layers * 3 * 2 * d * config["shared_intermediate_size"],
        "head": 2 * d * config["vocab_size"],
    }


def flops_per_sample(config: dict, traffic: dict) -> float:
    """Model FLOPs per token of one training step: what the forward and
    backward passes require (backward = 2 x forward), recomputation not
    counted."""
    return 3.0 * sum(forward_flops_per_token(config,
                                             traffic["seq_len"]).values())


def grouped_matmul_shape(config: dict, traffic: dict) -> tuple:
    """(rows, hidden, expert width, experts) of one grouped matmul of the
    expert layer on a chip. The rows are the EXPECTED ones, the (token,
    expert) pairs an even routing sends to the experts held (5,120 of the
    40,960 in the cell), as `families/deepseek_v2.py` counts them: the
    useful work of an even load, not the rows the kernels go through, which
    are always the row buffer's (`parallel/moe.py` `held_rows`, twice
    these, the free ones zero)."""
    pairs = traffic["per_chip_batch"] * traffic["seq_len"] \
        * config["num_experts_per_tok"]
    return (pairs * config["num_local_experts"]
            // config["published"]["num_local_experts"],
            config["hidden_size"], config["intermediate_size"],
            config["num_local_experts"])


def scan_work(tokens: int, config: dict) -> tuple:
    """((FLOPs, bytes) of a forward pass, the same of a backward pass) of
    one layer's scan over `tokens` tokens, at least: `scan_macs_per_token`
    multiply-adds a token forward and twice that backward, every operand
    and result moved once: forward reads x, B, C (bf16) and dt (float32)
    and writes y (bf16); backward reads those and dy and writes dx, dB, dC
    (bf16) and ddt (float32)."""
    channels = config["mamba_n_heads"] * config["mamba_d_head"]
    shared = 2 * config["mamba_d_state"]
    flops = 2 * tokens * scan_macs_per_token(config)
    per_head = 4 * tokens * config["mamba_n_heads"]
    forward = (flops, tokens * 2 * (2 * channels + shared) + per_head)
    backward = (2 * flops,
                tokens * 2 * (4 * channels + 2 * shared) + 2 * per_head)
    return forward, backward


def ssd_scan_work(config: dict, traffic: dict) -> tuple:
    """What the scans of a step's Mamba-2 layers need at least:
    ((executions a step, FLOPs, bytes) of a forward pass over one layer's
    sequences, the same of a backward pass), from `scan_work`. Under remat
    the forward runs twice a layer."""
    layers = kinds(config).count("mamba2")
    forward, backward = scan_work(
        traffic["per_chip_batch"] * traffic["seq_len"], config)
    repeats = 2 if config["program"]["remat"] else 1
    return (layers * repeats, *forward), (layers, *backward)


def reference_weights(params, layer_kinds: tuple) -> dict:
    """The program's parameter tree (each kind's layers stacked over
    (periods, its layers in a period)) as the reference's weights, float32,
    in the order the layers run (`layer_kinds`). The program's two leaves of
    the input projection are the reference's one, side by side."""
    f32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    every = {"ln1_g": "ln1_scale", "ln2_g": "ln2_scale", "router": "router",
             "w_gate": "we_gate", "w_up": "we1", "w_down": "we2",
             "ws_gate": "ws_gate", "ws_up": "ws1", "ws_down": "ws2"}
    names = {
        "full": dict(every, wq="wq", wk="wk", wv="wv", wo="wo"),
        "mamba2": dict(every, conv="ssd_conv", conv_b="ssd_conv_bias",
                       dt_b="ssd_dt_bias", a_log="ssd_a_log",
                       d_skip="ssd_d_skip", norm_g="ssd_norm_scale",
                       w_out="ssd_w_out")}
    stacks = f32["layers"]
    periods = next(iter(stacks.values()))["router"].shape[0]
    in_a_period = len(layer_kinds) // periods
    layers = []
    for p in range(periods):
        seen = dict.fromkeys(stacks, 0)
        for kind in layer_kinds[:in_a_period]:
            at = (p, seen[kind])
            layer = {ref: stacks[kind][ours][at]
                     for ref, ours in names[kind].items()}
            if kind == "mamba2":
                layer["w_in"] = jnp.concatenate(
                    [stacks[kind]["ssd_w_in"][at],
                     stacks[kind]["ssd_w_dt"][at]], axis=1)
            layers.append(layer)
            seen[kind] += 1
    return {"wte": f32["embed"], "lnf_g": f32["lnf_scale"], "layers": layers}


def compare(params, tokens, system_logits, layer_kinds, top_k, first=0,
            operands=None, fault=None):
    """(the logits' rms error over the reference's rms, the program's loss,
    the reference's, the rows of each held expert in the reference's routing
    (layers, held)): the reference's final hidden state whole, its head and
    both losses `HEAD_BLOCK` tokens at a time."""
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

        want = reference.head(rows(hidden), weights, operands, fault)
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
    n_experts = weights["layers"][0]["router"].shape[1]
    held = weights["layers"][0]["w_up"].shape[0]
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
    even = tokens.size * top_k * rows.shape[1] \
        // next(iter(params["layers"].values()))["router"].shape[-1]
    return {"ok": bool(ok),
            "detail": f"logits rms error {rms:.3e} of their rms (tolerance "
                      f"{LOGITS_RMS_TOL:.3e}); loss {got:.6f} against the "
                      f"reference's {want:.6f} (rtol {LOSS_RTOL:.3e}); rows "
                      f"of the {rows.shape[1]} held experts in the "
                      f"reference's routing of these {tokens.size} tokens: "
                      f"{int(rows.sum(axis=1).min())} to "
                      f"{int(rows.sum(axis=1).max())} a layer ({even} if "
                      f"even), one expert's largest {int(rows.max())}"}
