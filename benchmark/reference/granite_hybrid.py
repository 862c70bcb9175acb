"""Granite 4.0-H decoder (`model_type` granitemoehybrid; `config.json` of
huggingface.co/ibm-granite/granite-4.0-h-small): token embedding, pre-norm
blocks whose mixer is a Mamba-2 state-space layer (Dao & Gu,
arXiv:2405.21060) or, one layer in ten, grouped-query attention without any
positional encoding, each block followed by routed SiLU-gated experts beside
one shared gated MLP, a final RMSNorm and the embedding's transpose as head;
four scalar multipliers, on the embedding, on every residual branch, on the
attention scores and on the logits. Float32 throughout, a Python loop over the
layers, the state-space recurrence ONE TOKEN AT A TIME (a `lax.scan` over the
tokens, not the chunked form), the convolution as K shifted sums, attention
as a softmax over explicit scores with the mask written out, a block of query
rows at a time, a `lax.scan` over the experts held, each applied to every
token and kept where the token chose it, the head and the loss a block of
tokens at a time: no kernel, no sort, no buffer, no cache. Imports nothing of
`horovod_tpu`.

    RMSNorm(x; g) = x * rsqrt(mean(x^2) + 1e-5) * g
    h_0 = 12 * wte[token]                       (`embedding_multiplier`)
    every layer: h <- h + 0.22 * Mix(RMSNorm(h; g1))   (`residual_multiplier`)
                 v = RMSNorm(h; g2)
                 h <- h + 0.22 * (experts(v) + shared(v))
    logits = (RMSNorm(h_L; gf) wte^T) / 16      (`logits_scaling`; tied)

    "mamba2", on u with H heads held of width P (E = H P channels), one
    group of N states, K taps:
        [z | xBC | dt] = u W_in       widths E, E + 2N, H; no bias
        xBC <- silu(conv_K(xBC) + b_conv), depthwise and causal
        x (H, P), B (N), C (N) = split(xBC)
        D_t = softplus(dt_t + dt_bias)                     a head
        a_t = exp(-exp(A_log) D_t)                         a head
        S_t = a_t S_(t-1) + D_t x_t (x) B_t   (P x N a head, S_0 = 0)
        y_t = S_t C_t + D x_t                 (D one scalar a head)
        g   = y * silu(z)
        out = (g * rsqrt(mean over the E held channels of g^2 + 1e-5)
               * w_norm) W_out
    "full": q, k, v = u W_q (Hq heads), u W_k, u W_v (G heads) of width d;
        no positions, no biases; a = softmax(q k^T * (1 / 128) + causal
        mask) v (`attention_multiplier` 0.0078125, not d^-1/2), query head i
        reads K/V head floor(i / (Hq / G)); out = a W_o
    experts(v): s = v W_r over all E_all experts; the k largest chosen;
        w = softmax over those k (the softmax over all, renormalised over
        the chosen); sum over the chosen e that are held of
        w_e W_down,e (silu(W_gate,e v) * (W_up,e v))
    shared(v): W_down (silu(W_gate v) * (W_up v)), unweighted
    loss: next-token cross-entropy; config.json names no auxiliary
          coefficient: none.

The share. A chip of the deployment holds some of a layer's experts (`w_gate`,
`w_up`, `w_down` hold experts [first_expert, first_expert + their leading
size) of the E_all the router scores; a pair routed elsewhere adds nothing,
and that partial result goes on), some of its heads (the weights say how
many: with half the Mamba-2 heads the gated norm's mean square runs over
the channels HELD, where the deployment sums it over the pair of chips;
B, C and their convolution are whole on each chip) and a slice of the
vocabulary (whatever `wte` holds).

Weights, as the family hands them over (all float32):
    wte (V, D)  lnf_g (D,)
    layers: a list of dicts in the order the layers run, each with ln1_g
            ln2_g (D,), router (D, E_all), w_gate w_up (E_held, D, F),
            w_down (E_held, F, D), ws_gate ws_up (D, Fs), ws_down (Fs, D),
            and the leaves of its kind (`kinds` names each layer's):
      mamba2: w_in (D, 2E + 2N + H), conv (E + 2N, K), conv_b (E + 2N,),
              dt_b a_log d_skip (H,), norm_g (E,), w_out (E, D)
      full:   wq (D, Hq, d), wk wv (D, G, d), wo (Hq, d, D)

`operands`, where given, is a dtype every matrix product's operands are
rounded to (and back to float32) first, the recurrence's x, B and C among
them: how a program computing in that precision would differ, for fixing
the tolerance of a comparison. `fault`, where given, is one mechanism
computed wrongly on purpose, which the comparison's limits must refuse:
"sqrt_scale" (scores scaled by d^-1/2), "unit_residual" (r = 1),
"norm_before_gate" (the RMSNorm on y, the gate after it), "no_renorm" (the
k weights as the softmax over all experts gives them), "rope_on_attention"
(rotary positions on the attention layer), "unit_decay" (a_t = exp(-D_t):
A ignored), "unscaled_logits" (the logits not divided).
"""

import jax
import jax.numpy as jnp
from jax import lax

RMS_EPS = 1e-5
EMBEDDING_MULTIPLIER = 12.0
RESIDUAL_MULTIPLIER = 0.22
ATTENTION_MULTIPLIER = 0.0078125
LOGITS_SCALING = 16.0
ROPE_THETA = 10000.0   # config.json's, read by the planted fault alone
QUERY_BLOCK = 256      # query rows of attention scored at a time
LOSS_BLOCK = 1024      # tokens whose logits exist at a time
FAULTS = ("sqrt_scale", "unit_residual", "norm_before_gate", "no_renorm",
          "rope_on_attention", "unit_decay", "unscaled_logits")
KINDS = ("mamba2", "full")


def _rounded(x, operands):
    return x if operands is None else x.astype(operands).astype(jnp.float32)


def _mm(spec, a, b, operands):
    return jnp.einsum(spec, _rounded(a, operands), _rounded(b, operands))


def rms_norm(x, g):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + RMS_EPS) * g


# --------------------------------------------------------------------------
# Mamba-2
# --------------------------------------------------------------------------

def causal_conv(u, taps, bias):
    """silu of the depthwise causal convolution of u: (B, S, C) over S with
    taps: (C, K) and bias: (C,), as K shifted sums."""
    n = taps.shape[-1]
    y = jnp.zeros_like(u) + bias
    for j in range(n):
        back = n - 1 - j            # tap j sees the token `back` before
        shifted = jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :u.shape[1]]
        y = y + shifted * taps[:, j]
    return jax.nn.silu(y)


def recurrence(x, delta, a_log, b, c, d_skip, fault=None):
    """y_t = S_t C_t + D x_t, S_t = a_t S_(t-1) + delta_t x_t (x) B_t, one
    token at a time. x: (B, S, H, P); delta: (B, S, H); b, c: (B, S, N)."""
    rate = jnp.ones_like(a_log) if fault == "unit_decay" else jnp.exp(a_log)

    def step(state, at):            # state: (B, H, P, N)
        x_t, delta_t, b_t, c_t = at
        decay = jnp.exp(-rate * delta_t)                       # (B, H)
        state = decay[..., None, None] * state \
            + (delta_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t)

    start = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], jnp.float32)
    _, y = lax.scan(step, start, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, delta, b, c)))
    return jnp.moveaxis(y, 0, 1) + d_skip[:, None] * x


def mamba2(u, w, operands=None, fault=None):
    """The Mamba-2 mixer on the normed u: (B, S, D)."""
    heads = w["a_log"].shape[0]
    channels = w["norm_g"].shape[0]
    states = (w["conv"].shape[0] - channels) // 2
    batch, seq, _ = u.shape
    zxbcdt = _mm("bsd,de->bse", u, w["w_in"], operands)
    z = zxbcdt[..., :channels]
    mixed = causal_conv(zxbcdt[..., channels:2 * channels + 2 * states],
                        w["conv"], w["conv_b"])
    delta = jax.nn.softplus(zxbcdt[..., 2 * channels + 2 * states:]
                            + w["dt_b"])
    x = _rounded(mixed[..., :channels], operands).reshape(
        batch, seq, heads, channels // heads)
    y = recurrence(
        x, delta, w["a_log"],
        _rounded(mixed[..., channels:channels + states], operands),
        _rounded(mixed[..., channels + states:], operands), w["d_skip"],
        fault).reshape(batch, seq, channels)
    if fault == "norm_before_gate":
        gated = rms_norm(y, w["norm_g"]) * jax.nn.silu(z)
    else:
        gated = rms_norm(y * jax.nn.silu(z), w["norm_g"])
    return _mm("bse,ed->bsd", gated, w["w_out"], operands)


# --------------------------------------------------------------------------
# Attention without positions
# --------------------------------------------------------------------------

def _rope(x):
    """Rotary positions on x: (..., S, d): the planted fault's."""
    seq, width = x.shape[-2:]
    half = width // 2
    freq = ROPE_THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


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
    """Grouped-query attention of the normed u: (B, S, D), no positions."""
    q = _mm("bsd,dhk->bhsk", u, w["wq"], operands)
    k = _mm("bsd,dhk->bhsk", u, w["wk"], operands)
    v = _mm("bsd,dhk->bhsk", u, w["wv"], operands)
    if fault == "rope_on_attention":
        q, k = _rope(q), _rope(k)
    heads, kv_heads = q.shape[1], k.shape[1]
    reads = jnp.arange(heads) // (heads // kv_heads)
    scale = q.shape[-1] ** -0.5 if fault == "sqrt_scale" \
        else ATTENTION_MULTIPLIER
    a = _softmax_rows(q, k[:, reads], v[:, reads], scale, operands)
    return _mm("bhsk,hkd->bsd", a, w["wo"], operands)


# --------------------------------------------------------------------------
# Experts beside a shared MLP
# --------------------------------------------------------------------------

def gated_mlp(rows, w_gate, w_up, w_down, operands=None):
    hidden = jax.nn.silu(_mm("nd,df->nf", rows, w_gate, operands)) \
        * _mm("nd,df->nf", rows, w_up, operands)
    return _mm("nf,fd->nd", hidden, w_down, operands)


def moe(v, w, top_k, first_expert=0, operands=None, fault=None):
    """(the held experts' part of the layer's result plus the shared MLP's,
    for v: (B, S, D); routes (B, S, k))."""
    batch, seq, width = v.shape
    rows = v.reshape(batch * seq, width)
    n_experts = w["router"].shape[1]
    held = w["w_up"].shape[0]
    logits = _mm("nd,de->ne", rows, w["router"], operands)
    top, routes = lax.top_k(logits, top_k)
    chosen = jnp.any(routes[:, :, None] == jnp.arange(n_experts), axis=1)
    if fault == "no_renorm":
        weight = jnp.where(chosen, jax.nn.softmax(logits, axis=-1), 0.0)
    else:
        # the softmax over the k chosen logits
        weight = jnp.where(chosen, jnp.exp(logits - top[:, :1]), 0.0) \
            / jnp.sum(jnp.exp(top - top[:, :1]), axis=-1, keepdims=True)

    def add_expert(out, e):
        w_gate, w_up, w_down, weight_e = e
        return out + weight_e[:, None] * gated_mlp(rows, w_gate, w_up, w_down,
                                                   operands), None

    mine = weight[:, first_expert:first_expert + held]
    out, _ = lax.scan(add_expert, jnp.zeros_like(rows),
                      (w["w_gate"], w["w_up"], w["w_down"], mine.T))
    out = out + gated_mlp(rows, w["ws_gate"], w["ws_up"], w["ws_down"],
                          operands)
    return out.reshape(v.shape), routes.reshape(batch, seq, top_k)


def layer(x, w, kind, top_k, first_expert=0, operands=None, fault=None):
    """One block on x: (B, S, D): (its output, its routes)."""
    r = 1.0 if fault == "unit_residual" else RESIDUAL_MULTIPLIER
    u = rms_norm(x, w["ln1_g"])
    mixed = mamba2(u, w, operands, fault) if kind == "mamba2" \
        else attention(u, w, operands, fault)
    x = x + r * mixed
    out, routes = moe(rms_norm(x, w["ln2_g"]), w, top_k, first_expert,
                      operands, fault)
    return x + r * out, routes


def final_hidden(weights, tokens, kinds, top_k, first_expert=0,
                 operands=None, fault=None):
    """tokens: (B, S) int32 -> (the final RMSNorm's output (B, S, D), the
    layers' routes (L, B, S, k)). `kinds`: each layer's kind, in the order
    they run."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r}: choose from {FAULTS}")
    if len(kinds) != len(weights["layers"]) or set(kinds) - set(KINDS):
        raise ValueError(f"{len(weights['layers'])} layers of the kinds "
                         f"{kinds}")
    with jax.default_matmul_precision("highest"):
        x = EMBEDDING_MULTIPLIER * weights["wte"][tokens]
        used = []
        for kind, w in zip(kinds, weights["layers"]):
            x, routes = layer(x, w, kind, top_k, first_expert, operands,
                              fault)
            used.append(routes)
        return rms_norm(x, weights["lnf_g"]), jnp.stack(used)


def head(hidden, weights, operands=None, fault=None):
    """The tied head: hidden (B, S, D) -> logits (B, S, V)."""
    with jax.default_matmul_precision("highest"):
        logits = _mm("bsd,vd->bsv", hidden, weights["wte"], operands)
        return logits if fault == "unscaled_logits" \
            else logits / LOGITS_SCALING


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
