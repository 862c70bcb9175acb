"""LFM2-architecture mixture-of-experts decoders (`model_type` lfm2_moe:
three gated short-convolution layers to one grouped-query attention layer
whose queries and keys are normed per head and rotated, leading dense layers
inside that pattern, every other layer followed by sigmoid-scored experts
with a selection bias and no shared expert, a tied head), of which a chip
holds its share of the experts and of the vocabulary, through
`horovod_tpu.models.transformer`."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import lfm2_moe as reference
from horovod_tpu.models import transformer as tfm

SAMPLE = "tokens"

#: Agreement with the float32 reference on the same weights and the same
#: share of the model, each side routing for itself. What separates them is
#: what separates the other expert families' two sides
#: (`families/kimi_linear.py`), and here most of it is the choice: the
#: program computes in bf16 through some ten roundings a layer on the
#: residual path, and routing is discontinuous. Half an expert a token is
#: held (4 of 64 chosen, 8 held), each with a quarter of the weight and no
#: shared expert beside it, so a token whose fourth and fifth scores change
#: places gains or loses a whole term. The reference with bf16-rounded
#: matmul operands alone reads 4.6% from itself in float32 at the published
#: widths, and 1.5% with every expert chosen (1,024 tokens, on the CPU: a
#: prior, not a reading); the float32 program on the same weights reads 2e-6.
#: Measured on the v5e at the published widths and nine layers, one
#: 16,384-token sequence a seed over the 8,192-row vocabulary slice (my chip
#: run, PR 54; PERF.md, Findings; two seeds for the reference's variants):
#: the sound program reads 5.642-6.221% of the logits' root mean square over
#: 48 seeds (quartiles 5.78, 5.90, 6.02; thirteen more in the timed runs 5.77
#: to 6.11; against the reference with bf16 operands 5.86 and 6.07%); the
#: reference with 8-bit-float operands, the nearest precision below, 42.5 and
#: 42.7% (e4m3), 64.0 and 63.9% (e5m2); each of `reference.FAULTS`: QK-norm
#: over the whole vector 7.01 and 7.02%, none 7.03 and 7.20% (the same
#: seeds' sound readings 5.76 and 6.16: refused by a thirtieth), softmax for
#: the sigmoid scores 14.3 and 14.5%, half the head rotated 15.5 and 15.6%,
#: no renormalisation 75.3 and 76.3%, the dense layer at an expert's width
#: 112%, a fourth tap 119%, SiLU behind the convolution 126%, the taps
#: reversed 138%, one gate 141%, another table for the head 141%; the bias
#: counted into the weights reads the sound 5.76 and 6.16% to the digit (the
#: bias is zero at seeded weights), so no limit can refuse it there (the CPU
#: tests refuse it at a size where the bias is not zero). The limit is 1.09 x
#: the largest sound reading (five of the readings' deviations of 0.18 above
#: their mean of 5.90), 0.97 x the smallest reading of the nearest fault and
#: a sixth of an 8-bit program's.
LOGITS_RMS_TOL = 0.068

#: The mean next-token loss of those logits over the 16,384 tokens, against
#: the reference's, as a share of the reference's. Same runs: the sound
#: program's |loss - reference's| / reference's is 1.0e-7 to 8.5e-5 over the
#: 48 seeds (median 2.5e-5), 1.1e-5 to 1.22e-4 over the timed runs' thirteen
#: (the reference with bf16 operands 3.6e-5 and 6.9e-6); the reference with
#: e4m3 operands 4.4e-4 and 5.2e-4, with e5m2 2.7e-4 and 9.6e-4: three of
#: the four refused by this limit too (the logits' limit is the one that
#: refuses an 8-bit program, by a factor of six). The limit is 2.5 x the
#: largest sound reading. It does not refuse every fault (seeded weights
#: put the loss at 9.40-9.44 against ln 8,192 = 9.01, and a fault can leave
#: the mean where it was: 2.8e-6 to 2.2e-3 over the 24 fault readings).
LOSS_RTOL = 3e-4

#: tokens whose reference logits exist at a time
HEAD_BLOCK = reference.LOSS_BLOCK

#: `check_logits` is handed arrays and no configuration, and neither the
#: order of the layers, the experts per token nor the first expert held is
#: in an array's shape: each configuration `transformer_config` was asked
#: about leaves them here under the shapes its parameters have.
_unshaped = {}

_KIND = {"conv": "shortconv", "full_attention": "full"}


def within(rms: float, got: float, want: float) -> tuple:
    """Whether (the logits' limit, the loss's limit) hold."""
    return (rms <= LOGITS_RMS_TOL,
            abs(got - want) <= LOSS_RTOL * abs(want))


def first_expert(config: dict) -> int:
    """The first expert this chip of the deployment holds."""
    return config["deployment"]["expert_rank"] * config["num_experts"]


def _published_kinds(config: dict) -> tuple:
    """Each published layer's kind from the first layer held on."""
    return tuple(_KIND[t] for t in config["layer_types"][
        config["deployment"]["first_layer"]:])


def kinds(config: dict) -> tuple:
    """Each layer's kind, in the order the layers run: `n_layer` entries of
    `layer_types` from `deployment.first_layer` on (0-based, as
    published)."""
    return _published_kinds(config)[:config["n_layer"]]


def dense_layers(config: dict) -> int:
    """The leading dense layers among the layers held."""
    return max(config["num_dense_layers"]
               - config["deployment"]["first_layer"], 0)


def pattern(config: dict) -> tuple:
    """One period of the published order from the first layer held on: the
    shortest run of kinds whose repeats it is."""
    published = _published_kinds(config)
    return next(published[:n] for n in range(1, len(published) + 1)
                if (published[:n] * len(published))[:len(published)]
                == published)


def _segments(params) -> list:
    stacks = params["layers"]
    return stacks if isinstance(stacks, list) else [stacks]


def _shapes(params) -> tuple:
    """What tells two configurations' parameter trees apart."""
    return params["embed"].shape + tuple(
        (kind, leaves["we1"].shape, leaves["router"].shape)
        for of_kind in _segments(params)
        for kind, leaves in sorted(of_kind.items()))


def transformer_config(config: dict) -> tfm.TransformerConfig:
    program, published = config["program"], config["published"]
    rope = config["rope_parameters"]
    if config["conv_bias"] or not config["norm_topk_prob"] \
            or not config["use_expert_bias"] \
            or rope["rope_type"] != "default" \
            or config["hidden_size"] % config["num_attention_heads"] \
            or not 0 < dense_layers(config) < len(pattern(config)):
        raise ValueError("an lfm2_moe configuration this family has no "
                         "equations for")
    if (config["norm_eps"], config["routed_scaling_factor"],
            rope["rope_theta"]) != (
            reference.RMS_EPS, reference.ROUTED_SCALING_FACTOR,
            reference.ROPE_THETA):
        raise ValueError("norm_eps, routed_scaling_factor or rope_theta "
                         "differs from the constants of "
                         "benchmark/reference/lfm2_moe.py")
    cfg = tfm.TransformerConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["moe_intermediate_size"], n_layers=config["n_layer"],
        max_seq=config["max_position_embeddings"],
        num_experts=published["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        experts_held=config["num_experts"],
        first_expert=first_expert(config),
        first_k_dense=dense_layers(config),
        d_ff_dense=config["intermediate_size"],
        capacity_factor=program["held_capacity"],
        norm_topk=True, norm_topk_eps=reference.RENORM_EPS,
        router_scoring="sigmoid", router_bias=True,
        routed_scale=config["routed_scaling_factor"],
        load_balance_coef=program["load_balance_coef"],
        router_z_coef=program["router_z_coef"],
        norm="rmsnorm", rms_norm_eps=config["norm_eps"],
        positions="rope", rope_theta=rope["rope_theta"], qk_norm="head",
        layer_pattern=pattern(config), mlp="swiglu", tied_head=True,
        shortconv_taps=config["conv_L_cache"],
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


def forward_flops_per_token(config: dict, seq: int) -> dict:
    """FLOPs of the forward pass per token on this chip, by part, a
    multiply-add counted as 2; of the attention scores what the causal mask
    holds; of the experts what the held ones do under an even routing
    (`num_experts_per_tok` x held / routed of an expert a token). Norms, the
    gates and the convolution's taps are elementwise and not counted."""
    d = config["hidden_size"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    width = d // heads
    held = kinds(config)
    routed = config["published"]["num_experts"]
    dense = dense_layers(config)
    expert_layers = len(held) - dense
    return {
        # [B | C | X] and the output
        "shortconv_projections": held.count("shortconv") * 2 * 4 * d * d,
        # q_proj, k_proj, v_proj, out_proj
        "projections": held.count("full") * 2 * (
            d * (heads + 2 * kv) * width + heads * width * d),
        # q.k and p.v, each `width` wide, over the causal half
        "attention": held.count("full") * 2 * heads * 2 * width
        * (seq + 1) / 2,
        "dense_mlp": dense * 3 * 2 * d * config["intermediate_size"],
        "router": expert_layers * 2 * d * routed,
        "experts": expert_layers * config["num_experts_per_tok"]
        * config["num_experts"] / routed
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
    """(batch, query heads, seq, the queries' and keys' width, the values')
    of one flash-attention call on a chip."""
    width = config["hidden_size"] // config["num_attention_heads"]
    return (traffic["per_chip_batch"], config["num_attention_heads"],
            traffic["seq_len"], width, width)


def grouped_matmul_shape(config: dict, traffic: dict) -> tuple:
    """(rows, hidden, expert width, experts) of one grouped matmul of the
    expert layer on a chip. The rows are the EXPECTED ones, the (token,
    expert) pairs an even routing sends to the experts held (8,192 of the
    65,536 in the cell), as `families/deepseek_v2.py` counts them: the
    useful work of an even load, not the rows the kernels go through, which
    are always the row buffer's (`parallel/moe.py` `held_rows`:
    `program.held_capacity` times these, the free ones zero)."""
    pairs = traffic["per_chip_batch"] * traffic["seq_len"] \
        * config["num_experts_per_tok"]
    return (pairs * config["num_experts"]
            // config["published"]["num_experts"],
            config["hidden_size"], config["moe_intermediate_size"],
            config["num_experts"])


def mix_work(tokens: int, width: int, taps: int) -> tuple:
    """((FLOPs, bytes) of a forward pass, the same of a backward pass) of
    what lies between a short-convolution layer's two products over `tokens`
    tokens of `width` channels, at least, whatever implements it: a forward
    pass reads B, C and X and writes C * conv(B * X) (4 bf16 numbers a
    channel), a backward pass reads the cotangent, B, C and X and writes
    three cotangents (7); a forward channel costs the gate B * X, `taps`
    multiply-adds and the gate C (2 taps + 2 FLOPs), a backward one twice
    that and the taps' own gradient."""
    channels = tokens * width
    forward = ((2 * taps + 2) * channels, 4 * 2 * channels)
    backward = ((6 * taps + 4) * channels, 7 * 2 * channels)
    return forward, backward


def shortconv_mix_work(config: dict, traffic: dict) -> tuple:
    """What `shortconv.mix` of a step's short-convolution layers needs at
    least: ((executions a step, FLOPs, bytes) of a forward pass over one
    layer's sequences, the same of a backward pass), from `mix_work`. Under
    remat (either policy: nothing of the mix is a product) the forward runs
    twice a layer."""
    layers = kinds(config).count("shortconv")
    forward, backward = mix_work(
        traffic["per_chip_batch"] * traffic["seq_len"],
        config["hidden_size"], config["conv_L_cache"])
    repeats = 2 if config["program"]["remat"] else 1
    return (layers * repeats, *forward), (layers, *backward)


_EVERY = {"ln1_g": "ln1_scale", "ln2_g": "ln2_scale"}
_MIXER = {
    "shortconv": {"w_in": "sc_w_in", "taps": "sc_conv", "w_out": "sc_w_out"},
    "full": {"wq": "wq", "wk": "wk", "wv": "wv", "q_g": "q_scale",
             "k_g": "k_scale", "wo": "wo"}}
_FFN = {"experts": {"router": "router", "bias": "router_bias",
                    "w_gate": "we_gate", "w_up": "we1", "w_down": "we2"},
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
              for i in range(len(dense.get("w1", ())))]
    for of_kind in _segments(f32):
        periods = next(iter(of_kind.values()))["router"].shape[0]
        in_a_period = sum(leaves["router"].shape[1]
                          for leaves in of_kind.values())
        for p in range(periods):
            seen = dict.fromkeys(of_kind, 0)
            for kind in layer_kinds[len(layers):][:in_a_period]:
                layers.append(one(of_kind[kind], kind, "experts",
                                  (p, seen[kind])))
                seen[kind] += 1
    return {"wte": f32["embed"], "lnf_g": f32["lnf_scale"], "layers": layers}


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
    routers = next(iter(_segments(params)[0].values()))["router"].shape[-1]
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
