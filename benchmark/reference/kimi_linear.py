"""Kimi Linear decoder (`model_type` kimi_linear; `config.json` of
huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct; arXiv:2510.26692):
token embedding, pre-norm blocks whose mixer is Kimi Delta Attention (KDA:
the delta rule with a decay per key channel, section 3 of the paper) or, one
layer in four, multi-head latent attention without any positional encoding
(`mla_use_nope`), the first block followed by a dense SiLU-gated MLP and every
other by sigmoid-scored routed experts beside one shared expert, a final
RMSNorm and an untied linear head. Float32 throughout, a Python loop over the
layers, the delta rule ONE TOKEN AT A TIME (a `lax.scan` over the tokens, not
the chunked form), the convolution as K shifted sums, attention as a softmax
over explicit scores with the mask written out, a block of query rows at a
time, a `lax.scan` over the experts held, each applied to every token and
kept where the token chose it with a 0/1 mask, the head and the loss a block
of tokens at a time: no kernel, no sort, no buffer, no cache. Imports nothing
of `horovod_tpu`.

    RMSNorm(x; g) = x * rsqrt(mean(x^2) + 1e-5) * g
    h_0 = wte[token]
    every layer: h <- h + Mix(RMSNorm(h; g1));  h <- h + FFN(RMSNorm(h; g2))
    logits = RMSNorm(h_L; gf) W_head                     (untied)

    "kda", on u with H heads, keys and values dk = dv wide, K taps:
        q = L2Norm_head(silu(conv_K(u W_q))) * dk^-1/2
        k = L2Norm_head(silu(conv_K(u W_k)));  v = silu(conv_K(u W_v))
          (depthwise, causal, no bias; L2Norm(x) = x * rsqrt(sum x^2 + 1e-6))
        g = -exp(A_log_h) * softplus((u W_f_down) W_f_up + dt_bias)
                                                 (H x dk numbers, <= 0)
        beta = sigmoid(u W_beta)                 (one a head)
        per head, S (dk x dv) from zero:
            S <- Diag(exp(g_t)) S;  w_t = beta_t (v_t - S^T k_t);
            S <- S + k_t w_t^T;     o_t = S^T q_t
        out = (sigmoid((u W_g_down) W_g_up + b_g) * RMSNorm_head(o; g_o)) W_o
    "mla": q = u W_q per head (nope + rope wide);
        (c | k_r) = u W_kv_a, widths (latent | rope): ONE k_r a token, shared
        by the heads; (k_n | v) = RMSNorm(c; g_kv) W_kv_b per head; NOTHING is
        rotated (`mla_use_nope`); k = (k_n | k_r);
        a = softmax(q k^T * (nope + rope)^-1/2 + causal mask) v; out = a W_o
    dense MLP (the first `first_k_dense_replace` layers):
        W_down (silu(W_gate v) * (W_up v))
    experts(v): s = sigmoid(v W_r) over all E_all experts; chosen = the k
        largest of s + bias (one group: `num_expert_group` = `topk_group` =
        1); w_e = 2.446 * s_e / sum over the chosen of s (`moe_renormalize`,
        `routed_scaling_factor`); the bias chooses and never weighs;
        sum over the chosen e that are held of w_e Expert_e(v) + Shared(v),
        every expert a gated MLP as above
    loss: next-token cross-entropy; config.json names no auxiliary
          coefficient: none.

The share. A chip of the deployment holds some of a layer's experts (`w_gate`,
`w_up`, `w_down` hold experts [first_expert, first_expert + their leading
size) of the E_all the router scores; a pair routed elsewhere adds nothing,
and that partial result goes on) and a slice of the vocabulary (whatever
`wte` and `head` hold). The mixers and the shared expert are whole.

Departures from the published description, as the configuration file lists
them: the selection bias stays at the value it is handed (upstream moves it
after every step by a balancing rule whose speed config.json does not give);
the low-rank projections of the decay and of the output gate have the rank
their first matrix has (the paper's "rank equal to the head dimension"); the
output gate's second matrix has a bias and the decay's has none, as the
public implementation has them.

Weights, as the family hands them over (all float32):
    wte (V, D)  lnf_g (D,)  head (D, V)
    layers: a list of dicts in the order the layers run, each with ln1_g
            ln2_g (D,), the leaves of its mixer (`kinds` names each layer's):
      kda: wq wk (D, H, dk), wv (D, H, dv), conv_q conv_k (H, dk, K), conv_v
           (H, dv, K), wf_down wg_down (D, R), wf_up (R, H, dk), wg_up (R, H,
           dv), bg (H, dv), wb (D, H), a_log (H,), dt_b (H, dk), o_g (dv,),
           wo (H, dv, D)
      mla: wq (D, H, nope + rope), wkv_a (D, latent + rope), kv_g (latent,),
           wkv_b (latent, H, nope + v), wo (H, v, D)
            and either
              w_gate w_up (D, F), w_down (F, D)                   dense, or
              router (D, E_all), bias (E_all,), w_gate w_up (E_held, D, F),
              w_down (E_held, F, D), ws_gate ws_up (D, Fs), ws_down (Fs, D)

`operands`, where given, is a dtype every matrix product's operands are
rounded to (and back to float32) first, the recurrence's q, k and v among
them: how a program computing in that precision would differ, for fixing
the tolerance of a comparison. `fault`, where given, is one mechanism
computed wrongly on purpose, which the comparison's limits must refuse:
"scalar_decay" (one decay a head, the mean over its channels, for the one a
channel), "no_l2_norm" (q and k not normalised), "silu_gate" (SiLU for the
output gate's sigmoid), "softmax_scores" (softmax over all experts for the
sigmoid scores), "bias_in_weights" (the selection bias counted into the
weights), "no_renorm" (the k scores not divided by their sum), "unit_scale"
(no 2.446), "rope_on_mla" (q's and k's second parts rotated), "dense_as_experts"
(the first layer's dense MLP cut to an expert's width: 1 / 9 of it).
"""

import jax
import jax.numpy as jnp
from jax import lax

RMS_EPS = 1e-5
L2_EPS = 1e-6
ROUTED_SCALING_FACTOR = 2.446
ROPE_THETA = 10000.0   # config.json's, read by the planted fault alone
QUERY_BLOCK = 256      # query rows of attention scored at a time
LOSS_BLOCK = 1024      # tokens whose logits exist at a time
FAULTS = ("scalar_decay", "no_l2_norm", "silu_gate", "softmax_scores",
          "bias_in_weights", "no_renorm", "unit_scale", "rope_on_mla",
          "dense_as_experts")
KINDS = ("kda", "mla")


def _rounded(x, operands):
    return x if operands is None else x.astype(operands).astype(jnp.float32)


def _mm(spec, a, b, operands):
    return jnp.einsum(spec, _rounded(a, operands), _rounded(b, operands))


def rms_norm(x, g):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + RMS_EPS) * g


# --------------------------------------------------------------------------
# Kimi Delta Attention
# --------------------------------------------------------------------------

def causal_conv(u, taps):
    """silu of the depthwise causal convolution of u: (B, H, S, d) over S
    with taps: (H, d, K), as K shifted sums."""
    n = taps.shape[-1]
    y = jnp.zeros_like(u)
    for j in range(n):
        back = n - 1 - j            # tap j sees the token `back` before
        shifted = jnp.pad(u, ((0, 0), (0, 0), (back, 0), (0, 0)))[
            :, :, :u.shape[2]]
        y = y + shifted * taps[None, :, None, :, j]
    return jax.nn.silu(y)


def l2_norm(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                         + L2_EPS)


def delta_rule(q, k, v, g, beta):
    """o_t = S_t^T q_t, S_t = Diag(exp(g_t)) S_(t-1) + k_t w_t^T with w_t =
    beta_t (v_t - (Diag(exp(g_t)) S_(t-1))^T k_t), one token at a time. q,
    k, g: (B, H, S, dk); v: (B, H, S, dv); beta: (B, H, S)."""
    def step(state, at):            # state: (B, H, dk, dv)
        q_t, k_t, v_t, g_t, beta_t = at
        state = jnp.exp(g_t)[..., None] * state
        w_t = beta_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., :, None] * w_t[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    start = jnp.zeros(q.shape[:2] + (q.shape[-1], v.shape[-1]), jnp.float32)
    _, o = lax.scan(step, start, tuple(
        jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 2)


def kda(u, w, operands=None, fault=None):
    """The KDA mixer on the normed u: (B, S, D)."""
    width = w["wq"].shape[-1]
    q = causal_conv(_mm("bsd,dhk->bhsk", u, w["wq"], operands), w["conv_q"])
    k = causal_conv(_mm("bsd,dhk->bhsk", u, w["wk"], operands), w["conv_k"])
    v = causal_conv(_mm("bsd,dhk->bhsk", u, w["wv"], operands), w["conv_v"])
    if fault != "no_l2_norm":
        q, k = l2_norm(q), l2_norm(k)
    q = q * width ** -0.5
    f = _mm("bsr,rhk->bhsk", _mm("bsd,dr->bsr", u, w["wf_down"], operands),
            w["wf_up"], operands)
    g = -jnp.exp(w["a_log"])[None, :, None, None] * jax.nn.softplus(
        f + w["dt_b"][None, :, None, :])
    if fault == "scalar_decay":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(_mm("bsd,dh->bhs", u, w["wb"], operands))
    o = delta_rule(_rounded(q, operands), _rounded(k, operands),
                   _rounded(v, operands), g, beta)
    z = _mm("bsr,rhk->bhsk", _mm("bsd,dr->bsr", u, w["wg_down"], operands),
            w["wg_up"], operands) + w["bg"][None, :, None, :]
    gate = jax.nn.silu(z) if fault == "silu_gate" else jax.nn.sigmoid(z)
    return _mm("bhsk,hkd->bsd", gate * rms_norm(o, w["o_g"]), w["wo"],
               operands)


# --------------------------------------------------------------------------
# Latent attention without positions
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
    """softmax(q k^T * scale + causal mask) v for q, k: (B, H, S, d), v: (B,
    H, S, dv), a block of query rows at a time."""
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

    out = lax.map(rows, jnp.arange(0, seq, block))    # (blocks, B, H, q, dv)
    return jnp.moveaxis(out, 0, 2).reshape(q.shape[:3] + v.shape[3:])


def mla(u, w, operands=None, fault=None):
    """Latent attention of the normed u: (B, S, D), nothing rotated."""
    latent = w["kv_g"].shape[0]
    q = _mm("bsd,dhk->bhsk", u, w["wq"], operands)
    down = _mm("bsd,dc->bsc", u, w["wkv_a"], operands)
    kv = _mm("bsc,chk->bhsk", rms_norm(down[..., :latent], w["kv_g"]),
             w["wkv_b"], operands)
    shared = down[:, None, :, latent:]              # one a token
    nope = q.shape[-1] - shared.shape[-1]
    k_nope, v = kv[..., :nope], kv[..., nope:]
    if fault == "rope_on_mla":
        shared = _rope(shared)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:])], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(shared, k_nope.shape[:3]
                                  + shared.shape[3:])], axis=-1)
    a = _softmax_rows(q, k, v, q.shape[-1] ** -0.5, operands)
    return _mm("bhsk,hkd->bsd", a, w["wo"], operands)


# --------------------------------------------------------------------------
# The dense MLP, and the experts beside a shared one
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
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    if fault != "unit_scale":
        weight = weight * ROUTED_SCALING_FACTOR
    return weight, routes


def moe(v, w, top_k, first_expert=0, operands=None, fault=None):
    """(the held experts' part of the layer's result plus the shared
    expert's, for v: (B, S, D); routes (B, S, k))."""
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
    out = out + gated_mlp(rows, w["ws_gate"], w["ws_up"], w["ws_down"],
                          operands)
    return out.reshape(v.shape), routes.reshape(batch, seq, top_k)


def layer(x, w, kind, top_k, first_expert=0, operands=None, fault=None):
    """One block on x: (B, S, D): (its output, its routes; None for a layer
    with a dense MLP)."""
    u = rms_norm(x, w["ln1_g"])
    x = x + (kda(u, w, operands, fault) if kind == "kda"
             else mla(u, w, operands, fault))
    v = rms_norm(x, w["ln2_g"])
    if "router" in w:
        out, routes = moe(v, w, top_k, first_expert, operands, fault)
        return x + out, routes
    w_gate, w_up, w_down = w["w_gate"], w["w_up"], w["w_down"]
    if fault == "dense_as_experts":
        cut = w_up.shape[1] // 9
        w_gate, w_up, w_down = w_gate[:, :cut], w_up[:, :cut], w_down[:cut]
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
    with jax.default_matmul_precision("highest"):
        x = weights["wte"][tokens]
        used = []
        for kind, w in zip(kinds, weights["layers"]):
            x, routes = layer(x, w, kind, top_k, first_expert, operands,
                              fault)
            if routes is not None:
                used.append(routes)
        return rms_norm(x, weights["lnf_g"]), jnp.stack(used)


def head(hidden, weights, operands=None):
    """The untied head: hidden (B, S, D) -> logits (B, S, V)."""
    with jax.default_matmul_precision("highest"):
        return _mm("bsd,dv->bsv", hidden, weights["head"], operands)


def forward(weights, tokens, kinds, top_k, first_expert=0, operands=None,
            fault=None):
    """tokens: (B, S) int32 -> logits (B, S, V) float32."""
    hidden, _ = final_hidden(weights, tokens, kinds, top_k, first_expert,
                             operands, fault)
    return head(hidden, weights, operands)


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
