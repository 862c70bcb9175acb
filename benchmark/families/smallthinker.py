"""SmallThinker-architecture decoders (arXiv:2507.20984: one NoPE
full-attention layer to three rotating windowed ones over fewer key and value
heads than query heads, a head width that is not hidden_size / heads, every
layer a layer of ReLU-gated experts whose router reads the layer's input and
whose top-k weights are renormalised, of which a chip holds its share)
through `horovod_tpu.models.transformer`."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import smallthinker as reference
from horovod_tpu.models import transformer as tfm

SAMPLE = "tokens"

#: Agreement with the float32 reference on the same weights and the same
#: share of the experts, each side routing for itself. What separates them
#: is what separates the other expert families' two sides
#: (`families/deepseek_v2.py`): the program computes in bf16 through some ten
#: roundings a layer on the residual path, and routing is discontinuous, so
#: the few tokens whose sixth and seventh largest router scores lie closer
#: than the router's rounding error go to different experts on the two
#: sides. Measured on the v5e at the published widths, one 16,384-token
#: sequence a seed over the 37,984-row vocabulary slice (my chip runs, PR 38;
#: PERF.md, Findings; three seeds for each fault): the sound program reads
#: 1.364-1.530% of the logits' root mean square over sixteen seeds (the
#: reference with bf16 operands 1.01% from itself in float32); the reference
#: with 8-bit-float operands, the nearest precision below, 13.27% (e4m3) and
#: 12.07% (e5m2); each of `reference.FAULTS`: the "full" layer rotated too
#: 5.09-5.19%, the window left out 5.45-5.84%, the router scoring the normed
#: post-attention state 6.57-7.23%, a SiLU gate 9.97-10.13%, the six weights
#: not renormalised 19.30-19.57%, query head i reading K/V head i mod 4
#: 19.37-19.94%. The readings hardly move with the seed. The limit, 8 * 2^-8
#: = 3.125% (the GPT-2 and OLMoE families'), is 2.0 x the largest sound
#: reading, 0.61 x the smallest faulty one and a quarter of an 8-bit
#: program's.
LOGITS_RMS_TOL = 8 * 2.0 ** -8

#: The mean next-token loss of those logits over the 16,384 tokens, against
#: the reference's: held far closer than the logits, for a fault that moves
#: every logit the same way. Same runs: the sound program's |loss -
#: reference's| / reference's is 5e-7 to 1.96e-5 over sixteen seeds (the
#: reference with bf16 operands 5.5e-6); the reference with e5m2 operands
#: 1.74e-4, with e4m3 2.29e-4. The limit is 4.1 x the largest sound reading
#: and 2.2 x under the smaller 8-bit one. It does not refuse every fault
#: (seeded weights predict nearly uniformly, loss 11.05 against ln 37,984 =
#: 10.54, so a fault can leave the mean loss where it was: 2.2e-6 to 1.7e-4
#: over the eighteen fault readings, two of them over the limit); the
#: logits' limit refuses all eighteen. It is the published widths' and
#: 16,384 tokens': a bf16 program at toy widths does not meet it.
LOSS_RTOL = 8e-5

#: tokens whose reference logits exist at a time: 1,024 x 37,984 x 4 B is
#: 0.16 GB where the whole sequence's would be 2.5 GB
HEAD_BLOCK = 1024

#: `check_logits` is handed arrays and no configuration, and neither the
#: window, the experts per token nor the first expert held is in an array's
#: shape: each configuration `transformer_config` was asked about leaves
#: them here under the shapes its parameters have.
_unshaped = {}


def within(rms: float, got: float, want: float) -> tuple:
    """Whether (the logits' limit, the loss's limit) hold."""
    return (rms <= LOGITS_RMS_TOL,
            abs(got - want) <= LOSS_RTOL * abs(want))


def first_expert(config: dict) -> int:
    """The first expert this chip of the deployment holds."""
    return config["deployment"]["chip"] * config["moe_num_primary_experts"]


def kinds(config: dict) -> tuple:
    """Each layer's kind, in the order the layers run: the first `n_layer`
    entries of `sliding_window_layout` (1: "window", 0: "full")."""
    layout = config["sliding_window_layout"][:config["n_layer"]]
    return tuple("window" if windowed else "full" for windowed in layout)


def pattern(config: dict) -> tuple:
    """One period of `kinds`: the shortest run of layers they repeat."""
    held = kinds(config)
    return next(held[:n] for n in range(1, len(held) + 1)
                if len(held) % n == 0 and held[:n] * (len(held) // n) == held)


def _shapes(params) -> tuple:
    """What tells two configurations' parameter trees apart."""
    return params["embed"].shape + tuple(
        (kind, leaves["wq"].shape, leaves["we1"].shape)
        for kind, leaves in sorted(params["layers"].items()))


def transformer_config(config: dict) -> tfm.TransformerConfig:
    program = config["program"]
    if not config["moe_primary_router_apply_softmax"] \
            or not config["norm_topk_prob"] or config["tie_word_embeddings"] \
            or config["rope_scaling"] is not None \
            or config["rope_layout"] != config["sliding_window_layout"] \
            or len(config["sliding_window_layout"]) < config["n_layer"]:
        raise ValueError("a smallthinker configuration this family has no "
                         "equations for")
    if (config["rms_norm_eps"], config["rope_theta"]) != (
            reference.RMS_EPS, reference.ROPE_THETA):
        raise ValueError("rms_norm_eps or rope_theta differ from the "
                         "constants of benchmark/reference/smallthinker.py")
    cfg = tfm.TransformerConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], d_head=config["head_dim"],
        d_ff=config["moe_ffn_hidden_size"], n_layers=config["n_layer"],
        max_seq=config["max_position_embeddings"],
        num_experts=config["published"]["moe_num_primary_experts"],
        experts_per_token=config["moe_num_active_primary_experts"],
        experts_held=config["moe_num_primary_experts"],
        first_expert=first_expert(config),
        norm_topk=config["norm_topk_prob"], router_input="layer",
        load_balance_coef=program["load_balance_coef"],
        router_z_coef=program["router_z_coef"],
        norm="rmsnorm", rms_norm_eps=config["rms_norm_eps"],
        positions="rope", rope_theta=config["rope_theta"],
        layer_pattern=pattern(config), unrotated=("full",),
        window=config["sliding_window_size"], mlp="reglu",
        attn=program["attn"], dtype=jnp.dtype(program["dtype"]),
        remat=program["remat"], remat_policy=program["remat_policy"])
    shapes = _shapes(jax.eval_shape(lambda k: tfm.init(k, cfg),
                                    jax.random.PRNGKey(0)))
    kept = (kinds(config), config["sliding_window_size"],
            config["moe_num_active_primary_experts"], first_expert(config))
    if _unshaped.setdefault(shapes, kept) != kept:
        raise ValueError("two configurations of these shapes with different "
                         "layer orders, windows, experts per token or first "
                         "experts in one process: check_logits cannot tell "
                         "them apart")
    return cfg


def samples_per_step(traffic: dict, chips: int) -> int:
    return traffic["per_chip_batch"] * traffic["seq_len"] * chips


def keys_seen(seq: int, window: int = 0) -> float:
    """Keys a query sees on average over a sequence of `seq`: the causal
    half, or with a `window` the band (fewer at the sequence's start)."""
    if not window or window >= seq:
        return (seq + 1) / 2
    return window - window * (window - 1) / (2 * seq)


def forward_flops_per_token(config: dict, seq: int) -> dict:
    """FLOPs of the forward pass per token on this chip, by part, a
    multiply-add counted as 2; of the attention scores only what the mask
    holds (the causal half of a "full" layer, the band of a "window" one);
    of the experts what the held ones do under an even routing
    (`moe_num_active_primary_experts` x held / routed of an expert a
    token). Norms, the rotation and the gates are elementwise and not
    counted."""
    d, width = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    layers = config["n_layer"]
    routed = config["published"]["moe_num_primary_experts"]
    held = kinds(config)
    return {
        # q_proj, k_proj, v_proj, o_proj
        "projections": layers * 2 * (d * (heads + 2 * kv) * width
                                     + heads * width * d),
        # q.k and p.v, each `width` wide
        "attention": 2 * heads * 2 * width * (
            held.count("full") * keys_seen(seq)
            + held.count("window") * keys_seen(
                seq, config["sliding_window_size"])),
        "router": layers * 2 * d * routed,
        "experts": layers * config["moe_num_active_primary_experts"]
        * config["moe_num_primary_experts"] / routed
        * 3 * 2 * d * config["moe_ffn_hidden_size"],
        "head": 2 * d * config["vocab_size"],
    }


def flops_per_sample(config: dict, traffic: dict) -> float:
    """Model FLOPs per token of one training step: what the forward and
    backward passes require (backward = 2 x forward), recomputation not
    counted."""
    return 3.0 * sum(forward_flops_per_token(config,
                                             traffic["seq_len"]).values())


def flash_kernel_shapes(config: dict, traffic: dict) -> dict:
    """What this family's flash-attention calls look like on a chip, in the
    form `layer_metrics/diff_flash_roofline.py` reads: `calls` a layer, each
    over (batch, query heads, K/V heads, seq, keys' width, values' width),
    and per kind of layer its number of layers and the keys a query sees on
    average (the band's of a windowed layer, the causal half's of a full
    one)."""
    seq = traffic["seq_len"]
    held = kinds(config)
    return {
        "calls": 1,
        "shape": (traffic["per_chip_batch"], config["num_attention_heads"],
                  config["num_key_value_heads"], seq, config["head_dim"],
                  config["head_dim"]),
        "layers": {
            "window": (held.count("window"),
                       keys_seen(seq, config["sliding_window_size"])),
            "full": (held.count("full"), keys_seen(seq))},
        "remat": bool(config["program"]["remat"]),
    }


def grouped_matmul_shape(config: dict, traffic: dict) -> tuple:
    """(rows, hidden, expert width, experts) of one grouped matmul of the
    expert layer on a chip. The rows are the EXPECTED ones, the (token,
    expert) pairs an even routing sends to the experts held (24,576 of the
    98,304 in the cell), as `families/deepseek_v2.py` counts them: the
    useful work of an even load, not the rows the kernels go through, which
    are always the row buffer's (`parallel/moe.py` `held_rows`, twice
    these, the free ones zero)."""
    pairs = traffic["per_chip_batch"] * traffic["seq_len"] \
        * config["moe_num_active_primary_experts"]
    return (pairs * config["moe_num_primary_experts"]
            // config["published"]["moe_num_primary_experts"],
            config["hidden_size"], config["moe_ffn_hidden_size"],
            config["moe_num_primary_experts"])


def reference_weights(params, layer_kinds: tuple) -> dict:
    """The program's parameter tree (each kind's layers stacked over
    (periods, its layers in a period)) as the reference's weights, float32,
    in the order the layers run (`layer_kinds`)."""
    f32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    names = {"ln1_g": "ln1_scale", "ln2_g": "ln2_scale", "wq": "wq",
             "wk": "wk", "wv": "wv", "wo": "wo", "router": "router",
             "w_gate": "we_gate", "w_up": "we1", "w_down": "we2"}
    stacks = f32["layers"]
    periods = next(iter(stacks.values()))["wq"].shape[0]
    in_a_period = len(layer_kinds) // periods
    layers = []
    for p in range(periods):
        seen = dict.fromkeys(stacks, 0)
        for kind in layer_kinds[:in_a_period]:
            layers.append({ref: stacks[kind][ours][p, seen[kind]]
                           for ref, ours in names.items()})
            seen[kind] += 1
    return {"wte": f32["embed"], "lnf_g": f32["lnf_scale"],
            "head": f32["unembed"], "layers": layers}


def compare(params, tokens, system_logits, layer_kinds, window, top_k,
            first=0, operands=None, fault=None):
    """(the logits' rms error over the reference's rms, the program's loss,
    the reference's, the rows of each held expert in the reference's routing
    (layers, held)): the reference's final hidden state whole, its head and
    both losses `HEAD_BLOCK` tokens at a time."""
    weights = reference_weights(params, layer_kinds)
    hidden, routes = reference.final_hidden(
        weights, tokens, layer_kinds, window, top_k, first, operands, fault)
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
    n_experts = weights["layers"][0]["router"].shape[1]
    held = weights["layers"][0]["w_up"].shape[0]
    rows_of = jnp.sum(jax.nn.one_hot(routes, n_experts, dtype=jnp.int32),
                      axis=(1, 2, 3))                   # (layers, experts)
    return (jnp.sqrt(off / size), got / (batch * seq), want / (batch * seq),
            rows_of[:, first:first + held])


_compare = jax.jit(compare, static_argnames=(
    "layer_kinds", "window", "top_k", "first", "operands", "fault"))


def check_logits(params, tokens, system_logits) -> dict:
    """Compares the program's logits for `tokens` with the reference's on
    the same weights and the same share of the experts, each side routing
    for itself. All three arguments sit on one device."""
    layer_kinds, window, top_k, first = _unshaped[_shapes(params)]
    rms, got, want, rows = _compare(params, tokens, system_logits,
                                    layer_kinds, window, top_k, first)
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
