"""LFM2 mixture-of-experts decoder (`model_type` lfm2_moe; `config.json` of
huggingface.co/LiquidAI/LFM2-24B-A2B; the dense sibling's layers as
`transformers` 4.57's `models/lfm2/modeling_lfm2.py` has them:
`Lfm2ShortConv`, `Lfm2Attention`, `Lfm2MLP`, `Lfm2DecoderLayer`): token
embedding, pre-norm blocks whose mixer is a gated short convolution or, one
layer in four, grouped-query attention with an RMSNorm per head on queries
and keys and a rotary embedding, the first blocks followed by a dense
SiLU-gated MLP and every other by sigmoid-scored routed experts without a
shared one, a final RMSNorm and the embedding's transpose as the head.
Float32 throughout, a Python loop over the layers, the convolution ONE TOKEN
AT A TIME (a `lax.scan` over the tokens that carries the last K - 1 products,
as a decoder's cache would), attention as a softmax over explicit scores
with the mask written out, a block of query rows at a time, a `lax.scan`
over the experts held, each applied to every token and kept where the token
chose it with a 0/1 mask, the head and the loss a block of tokens at a time:
no kernel, no sort, no buffer, no cache. Imports nothing of `horovod_tpu`.

    RMSNorm(x; g) = x * rsqrt(mean(x^2) + 1e-5) * g
    h_0 = wte[token]                                  (no positions added)
    every layer: h <- h + Mix(RMSNorm(h; g1));  h <- h + FFN(RMSNorm(h; g2))
    logits = RMSNorm(h_L; gf) wte^T                   (tied)

    "shortconv" (upstream's `conv`), on u with K taps w: (D, K):
        [B | C | X] = u W_in        (D -> 3 D, the chunks in that order)
        z = B * X
        c_t = sum_(j < K) w[:, j] * z_(t - (K - 1) + j)   (z zero before the
              sequence; w[:, K - 1] on the token itself: torch's Conv1d with
              padding K - 1, cut to the sequence; no bias, no activation)
        out = (C * c) W_out
    "full" (`full_attention`): q = u W_q (H heads of d); k = u W_k and
        v = u W_v (G heads of d);
        q, k <- RMSNorm over each head's d numbers, one scale (d,) for all q
        heads and one for all k heads; then the rotary embedding over the
        whole head (rotate-half pairs (i, i + d/2), theta 1,000,000); query
        head h reads K/V head h // (H / G);
        a = softmax(q k^T * d^-1/2 + causal mask) v;  out = a W_o
    dense MLP (the first `num_dense_layers` layers):
        W_down (silu(W_gate v) * (W_up v))
    experts(v): s = sigmoid(v W_r) over all E_all experts, in float32;
        chosen = the k largest of s + bias (`use_expert_bias`: the bias
        chooses and never weighs); w_e = s_e / (sum over the chosen of s +
        1e-6) (`norm_topk_prob`) * routed_scaling_factor (1);
        sum over the chosen e that are held of w_e Expert_e(v), every expert
        a gated MLP as above; no shared expert
    loss: next-token cross-entropy; config.json names no auxiliary
          coefficient: none.

The share. A chip of the deployment holds some of a layer's experts (`w_gate`,
`w_up`, `w_down` hold experts [first_expert, first_expert + their leading
size) of the E_all the router scores; a pair routed elsewhere adds nothing,
and that partial result goes on) and a slice of the vocabulary (whatever
`wte` holds). The mixers are whole.

Departures from the published description, as the configuration file lists
them: the selection bias stays at the value it is handed (upstream moves it
by a balancing rule outside the loss).

Weights, as the family hands them over (all float32):
    wte (V, D)  lnf_g (D,)
    layers: a list of dicts in the order the layers run, each with ln1_g
            ln2_g (D,), the leaves of its mixer (`kinds` names each layer's):
      shortconv: w_in (D, 3 D), taps (D, K), w_out (D, D)
      full: wq (D, H, d), wk wv (D, G, d), q_g k_g (d,), wo (H, d, D)
            and either
              w_gate w_up (D, F), w_down (F, D)                   dense, or
              router (D, E_all), bias (E_all,), w_gate w_up (E_held, D, F),
              w_down (E_held, F, D)

`operands`, where given, is a dtype every matrix product's operands are
rounded to (and back to float32) first: how a program computing in that
precision would differ, for fixing the tolerance of a comparison. `fault`,
where given, is one mechanism computed wrongly on purpose, which the
comparison's limits must refuse: "softmax_scores" (softmax over all experts
for the sigmoid scores), "bias_in_weights" (the selection bias counted into
the weights), "no_renorm" (the k scores not divided by their sum),
"whole_vector_qk_norm" (q and k normed over all their heads at once, the
scale repeated a head), "no_qk_norm", "silu_after_conv" (a SiLU behind the
convolution, as a Mamba layer has one), "one_gate" (C left out), "taps_reversed"
(w[:, 0] on the token itself), "four_taps" (a fourth tap, the first one's
weight again, on the token three before), "untied_head" (the head reads
another table than the embedding: its rows moved on by one), "dense_as_experts"
(the dense MLP cut to an expert's width), "rope_half" (half of each head
rotated).
"""

import jax
import jax.numpy as jnp
from jax import lax

RMS_EPS = 1e-5
RENORM_EPS = 1e-6
ROUTED_SCALING_FACTOR = 1.0
ROPE_THETA = 1_000_000.0
QUERY_BLOCK = 256      # query rows of attention scored at a time
LOSS_BLOCK = 1024      # tokens whose logits exist at a time
FAULTS = ("softmax_scores", "bias_in_weights", "no_renorm",
          "whole_vector_qk_norm", "no_qk_norm", "silu_after_conv", "one_gate",
          "taps_reversed", "four_taps", "untied_head", "dense_as_experts",
          "rope_half")
KINDS = ("shortconv", "full")


def _rounded(x, operands):
    return x if operands is None else x.astype(operands).astype(jnp.float32)


def _mm(spec, a, b, operands):
    return jnp.einsum(spec, _rounded(a, operands), _rounded(b, operands))


def rms_norm(x, g):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + RMS_EPS) * g


# --------------------------------------------------------------------------
# The gated short convolution
# --------------------------------------------------------------------------

def causal_conv(z, taps):
    """c_t = sum_j taps[:, j] z_(t - (K - 1) + j) for z: (B, S, D) and taps:
    (D, K), one token at a time: the carry is the K - 1 products before the
    token, zeros at the start."""
    reach = taps.shape[1] - 1

    def step(before, z_t):            # before: (B, K - 1, D), oldest first
        seen = jnp.concatenate([before, z_t[:, None]], axis=1)
        return seen[:, 1:], jnp.einsum("bkd,dk->bd", seen, taps)

    start = jnp.zeros((z.shape[0], reach, z.shape[2]), jnp.float32)
    _, c = lax.scan(step, start, jnp.moveaxis(z, 1, 0))
    return jnp.moveaxis(c, 0, 1)


def short_conv(u, w, operands=None, fault=None):
    """The gated short convolution on the normed u: (B, S, D)."""
    b, c, x = jnp.split(_mm("bsd,de->bse", u, w["w_in"], operands), 3,
                        axis=-1)
    taps = w["taps"]
    if fault == "taps_reversed":
        taps = taps[:, ::-1]
    if fault == "four_taps":
        taps = jnp.concatenate([taps[:, :1], taps], axis=1)
    mixed = causal_conv(b * x, taps)
    if fault == "silu_after_conv":
        mixed = jax.nn.silu(mixed)
    if fault != "one_gate":
        mixed = c * mixed
    return _mm("bse,ed->bsd", mixed, w["w_out"], operands)


# --------------------------------------------------------------------------
# Grouped-query attention, queries and keys normed per head and rotated
# --------------------------------------------------------------------------

def rope(x, width=None):
    """The rotary embedding on the first `width` numbers (all of them) of
    each head of x: (B, H, S, d), in rotate-half pairs (i, i + width / 2)."""
    width = x.shape[-1] if width is None else width
    half = width // 2
    freq = ROPE_THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * freq[None]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    turned, kept = x[..., :width], x[..., width:]
    rotated = jnp.concatenate([-turned[..., half:], turned[..., :half]],
                              axis=-1)
    return jnp.concatenate([turned * cos + rotated * sin, kept], axis=-1)


def _head_norm(x, g, fault):
    """x: (B, H, S, d) normed a head (the scale g: (d,) shared by them)."""
    if fault == "no_qk_norm":
        return x
    if fault == "whole_vector_qk_norm":
        ms = jnp.mean(jnp.square(x), axis=(1, 3), keepdims=True)
        return x * lax.rsqrt(ms + RMS_EPS) * g
    return rms_norm(x, g)


def _softmax_rows(q, k, v, scale, operands):
    """softmax(q k^T * scale + causal mask) v for q, k, v: (B, H, S, d), a
    block of query rows at a time."""
    seq = q.shape[2]
    block = min(QUERY_BLOCK, seq)
    if seq % block:
        raise ValueError(f"{seq} tokens are no whole number of blocks of "
                         f"{block} query rows")
    keys = jnp.arange(seq)

    def rows(start):
        q_rows = lax.dynamic_slice_in_dim(q, start, block, axis=2)
        scores = _mm("bhqk,bhsk->bhqs", q_rows, k, operands) * scale
        seen = keys[None, :] <= (start + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return _mm("bhqs,bhsk->bhqk", probs, v, operands)

    out = lax.map(rows, jnp.arange(0, seq, block))    # (blocks, B, H, q, d)
    return jnp.moveaxis(out, 0, 2).reshape(q.shape)


def attention(u, w, operands=None, fault=None):
    """Grouped-query attention of the normed u: (B, S, D)."""
    q = _head_norm(_mm("bsd,dhk->bhsk", u, w["wq"], operands), w["q_g"],
                   fault)
    k = _head_norm(_mm("bsd,dhk->bhsk", u, w["wk"], operands), w["k_g"],
                   fault)
    v = _mm("bsd,dhk->bhsk", u, w["wv"], operands)
    turned = q.shape[-1] // 2 if fault == "rope_half" else None
    q, k = rope(q, turned), rope(k, turned)
    heads, kv_heads = q.shape[1], k.shape[1]
    reads = jnp.arange(heads) // (heads // kv_heads)
    a = _softmax_rows(q, k[:, reads], v[:, reads], q.shape[-1] ** -0.5,
                      operands)
    return _mm("bhsk,hkd->bsd", a, w["wo"], operands)


# --------------------------------------------------------------------------
# The dense MLP, and the experts
# --------------------------------------------------------------------------

def gated_mlp(rows, w_gate, w_up, w_down, operands=None):
    hidden = jax.nn.silu(_mm("nd,df->nf", rows, w_gate, operands)) \
        * _mm("nd,df->nf", rows, w_up, operands)
    return _mm("nf,fd->nd", hidden, w_down, operands)


def router_weights(logits, bias, top_k, fault=None):
    """(the weight of every expert for every token, 0 where it was not
    chosen: (N, E_all); the chosen: (N, k))."""
    n_experts = logits.shape[-1]
    scores = jax.nn.softmax(logits, axis=-1) if fault == "softmax_scores" \
        else jax.nn.sigmoid(logits)
    _, routes = lax.top_k(scores + bias, top_k)
    chosen = jnp.any(routes[:, :, None] == jnp.arange(n_experts), axis=1)
    counted = scores + bias if fault == "bias_in_weights" else scores
    weight = jnp.where(chosen, counted, 0.0)
    if fault != "no_renorm":
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True)
                           + RENORM_EPS)
    return weight * ROUTED_SCALING_FACTOR, routes


def moe(v, w, top_k, first_expert=0, operands=None, fault=None):
    """(the held experts' part of the layer's result for v: (B, S, D);
    routes (B, S, k))."""
    batch, seq, width = v.shape
    rows = v.reshape(batch * seq, width)
    held = w["w_up"].shape[0]
    weight, routes = router_weights(
        _mm("nd,de->ne", rows, w["router"], operands), w["bias"], top_k,
        fault)

    def add_expert(out, e):
        w_gate, w_up, w_down, weight_e = e
        return out + weight_e[:, None] * gated_mlp(rows, w_gate, w_up, w_down,
                                                   operands), None

    mine = weight[:, first_expert:first_expert + held]
    out, _ = lax.scan(add_expert, jnp.zeros_like(rows),
                      (w["w_gate"], w["w_up"], w["w_down"], mine.T))
    return out.reshape(v.shape), routes.reshape(batch, seq, top_k)


def layer(x, w, kind, top_k, first_expert=0, operands=None, fault=None,
          expert_width=0):
    """One block on x: (B, S, D): (its output, its routes; None for a layer
    with a dense MLP). `expert_width`: an expert's, for the planted fault."""
    u = rms_norm(x, w["ln1_g"])
    x = x + (short_conv(u, w, operands, fault) if kind == "shortconv"
             else attention(u, w, operands, fault))
    v = rms_norm(x, w["ln2_g"])
    if "router" in w:
        out, routes = moe(v, w, top_k, first_expert, operands, fault)
        return x + out, routes
    w_gate, w_up, w_down = w["w_gate"], w["w_up"], w["w_down"]
    if fault == "dense_as_experts":
        w_gate, w_up = w_gate[:, :expert_width], w_up[:, :expert_width]
        w_down = w_down[:expert_width]
    return x + gated_mlp(v.reshape(-1, v.shape[-1]), w_gate, w_up, w_down,
                         operands).reshape(v.shape), None


def final_hidden(weights, tokens, kinds, top_k, first_expert=0,
                 operands=None, fault=None):
    """tokens: (B, S) int32 -> (the final RMSNorm's output (B, S, D), the
    expert layers' routes (L_experts, B, S, k)). `kinds`: each layer's kind,
    in the order they run."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r}: choose from {FAULTS}")
    if len(kinds) != len(weights["layers"]) or set(kinds) - set(KINDS):
        raise ValueError(f"{len(weights['layers'])} layers of the kinds "
                         f"{kinds}")
    expert_width = next((w["w_up"].shape[-1] for w in weights["layers"]
                         if "router" in w), 0)
    with jax.default_matmul_precision("highest"):
        x = weights["wte"][tokens]
        used = []
        for kind, w in zip(kinds, weights["layers"]):
            x, routes = layer(x, w, kind, top_k, first_expert, operands,
                              fault, expert_width)
            if routes is not None:
                used.append(routes)
        return rms_norm(x, weights["lnf_g"]), jnp.stack(used)


def head(hidden, weights, operands=None, fault=None):
    """The tied head: hidden (B, S, D) -> logits (B, S, V)."""
    table = weights["wte"]
    if fault == "untied_head":
        table = jnp.roll(table, 1, axis=0)
    with jax.default_matmul_precision("highest"):
        return _mm("bsd,vd->bsv", hidden, table, operands)


def forward(weights, tokens, kinds, top_k, first_expert=0, operands=None,
            fault=None):
    """tokens: (B, S) int32 -> logits (B, S, V) float32."""
    hidden, _ = final_hidden(weights, tokens, kinds, top_k, first_expert,
                             operands, fault)
    return head(hidden, weights, operands, fault)


def loss(weights, tokens, targets, kinds, top_k, first_expert=0):
    """The training loss: mean next-token cross-entropy, the head and the
    log-softmax `LOSS_BLOCK` tokens at a time."""
    hidden, _ = final_hidden(weights, tokens, kinds, top_k, first_expert)
    batch, seq = tokens.shape
    block = min(LOSS_BLOCK, seq)
    if seq % block:
        raise ValueError(f"{seq} tokens are no whole number of blocks of "
                         f"{block}")

    def of_block(start):
        logits = head(lax.dynamic_slice_in_dim(hidden, start, block, axis=1),
                      weights)
        aim = lax.dynamic_slice_in_dim(targets, start, block, axis=1)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, aim[..., None], axis=-1))

    return jnp.sum(lax.map(of_block, jnp.arange(0, seq, block))) \
        / (batch * seq)
