"""Phi-4-mini-flash-architecture decoders (SambaY, arXiv:2507.06607: Mamba-1
state-space layers, windowed and full differential attention over fewer
key and value heads than query heads, a cross-decoder of Gated Memory Units
and cross-attention that read one layer's memory and keys and values, a
tied head, no positions) through `horovod_tpu.models.transformer`."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import phi4_flash as reference
from horovod_tpu.models import transformer as tfm

SAMPLE = "tokens"

#: Agreement with the float32 reference on the same weights. The model is
#: dense: nothing is routed, so the two sides differ by rounding alone. The
#: program computes in bf16 (a relative step of 2^-8) with float32
#: accumulation; the state-space layers carry their state in float32 on both
#: sides, the program tile by tile (`ops/selective_scan.py`) and the
#: reference token by token. Measured on the v5e at the published widths,
#: one 8,192-token sequence a seed over the 25,008-row vocabulary slice (my
#: chip runs, PR 36; PERF.md, Findings): the sound program reads 2.09-2.53%
#: of the logits' root mean square over thirteen seeds (the reference with bf16
#: operands 1.44% from itself in float32: ten layers, and differential
#: attention subtracts two rounded softmaxes, so the rounding shows more than
#: in a four-layer cell); the reference with 8-bit-float operands, the
#: nearest precision below, 52.6% (e4m3) and 57.9% (e5m2); each of
#: `reference.FAULTS` on three seeds: the Gated Memory Units reading the
#: wrong layer's memory 40.8-41.0%, the second softmax left out 41.4-62.9%,
#: the window left out 74.6-86.7%, query pair i reading K/V pair i mod 10
#: 124-126%. The limit, 16 * 2^-8 = 6.25%, is 2.5 x the largest sound
#: reading, an eighth of an 8-bit program's and under a sixth of the
#: smallest fault's.
LOGITS_RMS_TOL = 16 * 2.0 ** -8

#: The mean next-token loss of those logits over the 8,192 tokens against
#: the reference's, held closer than the logits, for a fault that moves
#: every logit the same way. Same runs: the sound program's |loss -
#: reference's| / reference's is at most 4.1e-5 over thirteen seeds (9.0e-7
#: to 4.1e-5); the reference with bf16 operands 2.8e-5, with e4m3 9.1e-4, with
#: e5m2 1.6e-3. The limit is 5 x the largest sound reading and under a
#: quarter of the smaller 8-bit one. It does not refuse every fault on every
#: seed (seeded weights predict nearly uniformly, loss 10.63 against ln
#: 25,008 = 10.13, so a fault can leave the mean loss where it was: 1.6e-5 to
#: 2.1e-3 over the twelve fault readings); the logits' limit refuses all
#: twelve. It is the published widths' and 8,192 tokens': a bf16 program at
#: toy widths does not meet it.
LOSS_RTOL = 2e-4

#: tokens whose reference logits exist at a time: 1,024 x 25,008 x 4 B is
#: 0.1 GB where the whole sequence's would be 0.8 GB, twice with the
#: program's in float32
HEAD_BLOCK = 1024

#: `check_logits` is handed arrays and no configuration, and neither the
#: order of a period's layers nor the window is in any array's shape: each
#: configuration `transformer_config` was asked about leaves both here under
#: the shapes its parameters have.
_layouts = {}


def within(rms: float, got: float, want: float) -> tuple:
    """Whether (the logits' limit, the loss's limit) hold."""
    return (rms <= LOGITS_RMS_TOL,
            abs(got - want) <= LOSS_RTOL * abs(want))


def segments(config: dict) -> tuple:
    """The configuration's `segments` as the program takes them."""
    return tuple((tuple(pattern), int(periods))
                 for pattern, periods in config["segments"])


def kinds(config: dict) -> tuple:
    """Each layer's kind, in the order the layers run."""
    return tuple(kind for pattern, periods in segments(config)
                 for _ in range(periods) for kind in pattern)


def _shapes(params) -> tuple:
    """What tells two configurations' parameter trees apart."""
    return params["embed"].shape + tuple(
        (kind, leaves["ln1_scale"].shape[:2])
        for stacks in params["segments"] for kind, leaves in stacks.items())


def transformer_config(config: dict) -> tfm.TransformerConfig:
    program, ssm = config["program"], config["ssm"]
    if config["model_type"] != "phi4flash" or config["hidden_act"] != "silu" \
            or config["mlp_bias"] or config["lm_head_bias"] \
            or not config["tie_word_embeddings"] \
            or config["mb_per_layer"] != 2:
        raise ValueError("a phi4flash configuration this family has no "
                         "equations for")
    if config["layer_norm_eps"] != reference.LN_EPS:
        raise ValueError("layer_norm_eps differs from the constant of "
                         "benchmark/reference/phi4_flash.py")
    if ssm["dt_rank"] != -(-config["hidden_size"] // 16):
        raise ValueError(f"dt_rank {ssm['dt_rank']} is not the ceiling of "
                         f"hidden_size / 16, which the program derives")
    if len(kinds(config)) != config["n_layer"]:
        raise ValueError(f"the segments hold {len(kinds(config))} layers, "
                         f"n_layer is {config['n_layer']}")
    cfg = tfm.TransformerConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], n_layers=config["n_layer"],
        max_seq=config["max_position_embeddings"],
        norm="layernorm", positions="none", mlp="swiglu",
        segments=segments(config), window=config["sliding_window"],
        tied_head=True, attention_bias=True, diff_attention=True,
        rms_norm_eps=reference.SUBLN_EPS,
        ssm_state=ssm["d_state"], ssm_conv=ssm["d_conv"],
        ssm_expand=ssm["expand"],
        attn=program["attn"], dtype=jnp.dtype(program["dtype"]),
        remat=program["remat"], remat_policy=program["remat_policy"])
    shapes = _shapes(jax.eval_shape(partial(tfm.init, cfg=cfg),
                                    jax.random.PRNGKey(0)))
    layout = (kinds(config), config["sliding_window"])
    if _layouts.setdefault(shapes, layout) != layout:
        raise ValueError("two configurations of these shapes with different "
                         "layer orders or windows in one process: "
                         "check_logits cannot tell them apart")
    return cfg


def samples_per_step(traffic: dict, chips: int) -> int:
    return traffic["per_chip_batch"] * traffic["seq_len"] * chips


def keys_seen(seq: int, window: int = 0) -> float:
    """Keys a query sees on average over a sequence of `seq`: the causal
    half, or with a `window` the band."""
    if not window or window >= seq:
        return (seq + 1) / 2
    return window - window * (window - 1) / (2 * seq)


def forward_flops_per_token(config: dict, seq: int) -> dict:
    """FLOPs of the forward pass per token, by part, a multiply-add counted
    as 2; of the attention scores only what the mask holds (the causal half,
    the band of a windowed layer); of differential attention both softmaxes,
    each pair's q.k at the head width and p.v at twice it; of the recurrence
    3 multiply-adds a (channel, state) (decay, write, read). Convolutions,
    norms and gates are elementwise and not counted."""
    d, f = config["hidden_size"], config["intermediate_size"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    width = d // heads
    ssm = config["ssm"]
    e, n = ssm["expand"] * d, ssm["d_state"]
    rank = ssm["dt_rank"]
    count = {kind: kinds(config).count(kind)
             for kind in ("ssm", "window", "full", "gmu", "cross")}
    # per (query, key): two softmaxes, each pairs x (width + 2 width)
    scores = 2 * 2 * (heads // 2) * 3 * width
    return {
        "mlps": config["n_layer"] * 3 * 2 * d * f,
        "ssm_projections": count["ssm"] * 2 * (
            d * 2 * e + e * (rank + 2 * n) + rank * e + e * d),
        "recurrence": count["ssm"] * 2 * 3 * e * n,
        "attention_projections":
            (count["window"] + count["full"]) * 2 * (
                d * (heads + 2 * kv) * width + d * d)
            + count["cross"] * 2 * 2 * d * d,
        "attention": scores * (
            count["window"] * keys_seen(seq, config["sliding_window"])
            + (count["full"] + count["cross"]) * keys_seen(seq)),
        "gmu": count["gmu"] * 2 * 2 * d * e,
        "head": 2 * d * config["vocab_size"],
    }


def flops_per_sample(config: dict, traffic: dict) -> float:
    """Model FLOPs per token of one training step: what the forward and
    backward passes require (backward = 2 x forward), recomputation not
    counted."""
    return 3.0 * sum(forward_flops_per_token(config,
                                             traffic["seq_len"]).values())


def scan_work(tokens: int, channels: int, states: int) -> tuple:
    """((FLOPs, bytes) of a forward pass, the same of a backward pass) of
    the selective scan over `tokens` tokens, at least, every operand and
    result moved once. Forward, per (token, channel, state): delta A, its
    exponential, the decay times the state, the write's product and add, the
    read's product and add (7), and per (token, channel) delta c, the skip's
    product and add (3); it reads c (bf16), delta (float32), B and C (bf16)
    and writes y (bf16). Backward: the state again (5 of the 7) and the
    cotangent's chain and the gradients (16), per (token, channel) 8; it
    reads those and dy and writes dc (bf16), ddelta (float32), dB, dC."""
    pairs, maps = tokens * channels, 2 * tokens * states * 2
    forward = ((7 * states + 3) * pairs, (2 + 4 + 2) * pairs + maps)
    backward = ((21 * states + 8) * pairs,
                (2 + 4 + 2 + 2 + 4) * pairs + 2 * maps)
    return forward, backward


def ssm_scan_work(config: dict, traffic: dict) -> tuple:
    """What the selective scans of a step's state-space layers need at
    least: ((executions a step, FLOPs, bytes) of a forward pass over one
    layer's sequences, the same of a backward pass), from `scan_work`. Under
    remat the forward runs twice a layer."""
    layers = kinds(config).count("ssm")
    forward, backward = scan_work(
        traffic["per_chip_batch"] * traffic["seq_len"],
        config["ssm"]["expand"] * config["hidden_size"],
        config["ssm"]["d_state"])
    repeats = 2 if config["program"]["remat"] else 1
    return (layers * repeats, *forward), (layers, *backward)


def flash_kernel_shapes(config: dict, traffic: dict) -> dict:
    """What this family's flash-attention calls look like on a chip:
    `calls` a layer (the two softmaxes of differential attention), each over
    (batch, query heads, K/V heads, seq, keys' width, values' width), and per
    kind of layer its number of layers and the keys a query sees on average
    (the band's of a windowed layer, the causal half's of the others)."""
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    width = config["hidden_size"] // heads
    seq = traffic["seq_len"]
    held = kinds(config)
    return {
        "calls": 2,
        "shape": (traffic["per_chip_batch"], heads // 2, kv // 2, seq, width,
                  2 * width),
        "layers": {
            "window": (held.count("window"),
                       keys_seen(seq, config["sliding_window"])),
            "full": (held.count("full") + held.count("cross"),
                     keys_seen(seq))},
        "remat": bool(config["program"]["remat"]),
    }


def reference_weights(params, layer_kinds: tuple) -> dict:
    """The program's parameter tree (per segment, each kind's layers stacked
    over (periods, its layers in a period)) as the reference's weights,
    float32, in the order the layers run (`layer_kinds`)."""
    f32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    shared = {"ln1_g": "ln1_scale", "ln1_b": "ln1_bias",
              "ln2_g": "ln2_scale", "ln2_b": "ln2_bias",
              "w_gate": "w_gate", "w_up": "w1", "w_down": "w2"}
    cross = dict(shared, wq="wq", bq="bq", wo="wo", bo="bo",
                 lq1="lambda_q1", lk1="lambda_k1", lq2="lambda_q2",
                 lk2="lambda_k2", sub_g="subln_scale")
    attention = dict(cross, wk="wk", bk="bk", wv="wv", bv="bv")
    names = {
        "ssm": dict(shared, w_in="ssm_w_in", conv="ssm_conv",
                    conv_b="ssm_conv_bias", w_x="ssm_w_x", w_dt="ssm_w_dt",
                    dt_b="ssm_dt_bias", a_log="ssm_a_log",
                    d_skip="ssm_d_skip", w_out="ssm_w_out"),
        "gmu": dict(shared, w_1="gmu_w1", w_2="gmu_w2"),
        "window": attention, "full": attention, "cross": cross}
    layers = []
    for stacks in f32["segments"]:
        periods = next(iter(stacks.values()))["ln1_scale"].shape[0]
        in_a_period = sum(leaves["ln1_scale"].shape[1]
                          for leaves in stacks.values())
        pattern = layer_kinds[len(layers):len(layers) + in_a_period]
        for p in range(periods):
            seen = dict.fromkeys(stacks, 0)
            for kind in pattern:
                layers.append({ref: stacks[kind][ours][p, seen[kind]]
                               for ref, ours in names[kind].items()})
                seen[kind] += 1
    return {"wte": f32["embed"], "lnf_g": f32["lnf_scale"],
            "lnf_b": f32["lnf_bias"], "layers": layers}


def compare(params, tokens, system_logits, layer_kinds, window,
            operands=None, fault=None):
    """(the logits' rms error over the reference's rms, the program's loss,
    the reference's): the reference's final hidden state whole, its head and
    both losses `HEAD_BLOCK` tokens at a time."""
    weights = reference_weights(params, layer_kinds)
    hidden = reference.final_hidden(weights, tokens, layer_kinds, window,
                                    operands, fault)
    batch, seq = tokens.shape
    block = min(HEAD_BLOCK, seq)
    if seq % block:
        raise ValueError(f"{seq} tokens are no whole number of blocks of "
                         f"{block}")
    targets = jnp.roll(tokens, -1, axis=1)

    def of_block(start):
        def rows(x):
            return lax.dynamic_slice_in_dim(x, start, block, axis=1)

        want = reference.head(rows(hidden), weights["wte"], operands)
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
    return (jnp.sqrt(off / size), got / (batch * seq),
            want / (batch * seq))


_compare = jax.jit(compare, static_argnames=("layer_kinds", "window",
                                             "operands", "fault"))


def check_logits(params, tokens, system_logits) -> dict:
    """Compares the program's logits for `tokens` with the reference's on
    the same weights. All three arguments sit on one device."""
    layer_kinds, window = _layouts[_shapes(params)]
    rms, got, want = (float(x) for x in _compare(
        params, tokens, system_logits, layer_kinds, window))
    ok = all(within(rms, got, want))
    return {"ok": bool(ok),
            "detail": f"logits rms error {rms:.3e} of their rms (tolerance "
                      f"{LOGITS_RMS_TOL:.3e}); loss {got:.6f} against the "
                      f"reference's {want:.6f} (rtol {LOSS_RTOL:.3e}); "
                      f"{tokens.size} tokens, the state-space layers token "
                      "by token in the reference"}
