"""DeepSeek-V2 decoder (DeepSeek-AI, arXiv:2405.04434 section 2 and Appendix
B; `model_type` deepseek_v2 of huggingface.co/deepseek-ai/DeepSeek-V2-Lite):
token embedding, pre-norm blocks of multi-head latent attention (MLA) and
either a dense gated-SiLU MLP (the leading `first_k_dense_replace` layers)
or shared plus routed gated-SiLU experts, a final RMSNorm and an untied
linear head. Float32 throughout, a Python loop over the layers and a
`lax.scan` over the experts held, each applied to every token and kept where
the token chose it: no sorting, no kernel, no cache.

    RMSNorm(x; g) = x * rsqrt(mean(x^2) + 1e-6) * g
    attention:  h Wq -> per head (q_nope | q_pe), widths (nope | rope);
                h Wkva -> (c | k_pe), widths (latent | rope): ONE k_pe a
                token, shared by all heads; c = RMSNorm(c; g_kv);
                c Wkvb -> per head (k_nope | v), widths (nope | v).
                q_pe and k_pe are rotated (pairs (i, i + rope/2): the
                checkpoint's interleaved order (2i, 2i + 1) is a permutation
                of Wq's and Wkva's rotary columns, the same function).
                q = (q_nope | q_pe), k = (k_nope | k_pe);
                a = softmax(q k^T * (nope + rope)^-1/2 * m^2 + causal) v;
                o = a Wo. No query compression (`q_lora_rank` null), no
                biases.
    YaRN:       each of the rope/2 frequencies theta^(-2i/rope) is blended
                with itself / factor by a linear ramp between the pairs
                that turn beta_fast and beta_slow times over
                original_max_position_embeddings positions (`yarn_frequencies`);
                m = 0.1 * mscale_all_dim * ln(factor) + 1 multiplies the
                scores twice; cos and sin are multiplied by
                m(mscale) / m(mscale_all_dim), which is 1 for this model.
                Applied at every length, the original 4,096 included.
    dense MLP:  W_down (silu(W_gate h) * W_up h)
    experts:    s = softmax(h Wr) over all E experts, float32; the k largest
                s_e (greedy, one group), not renormalised,
                routed_scaling_factor 1;
                out = sum over them of s_e * expert_e(h) + shared(h),
                each expert and `shared` a gated-SiLU MLP as above.
    loss:       next-token cross-entropy + 0.001 * the sum over the expert
                layers of the balance term; per layer, the mean over the
                sequences of sum_e f_e P_e, f_e = (pairs of the sequence
                sent to e) * E / (k * S), P_e = mean of s_e over the
                sequence (`seq_aux`). No router z-loss.

The share. A chip of an expert-parallel deployment holds some of a layer's
routed experts: `w_gate`, `w_up`, `w_down` of an expert layer hold experts
[first_expert, first_expert + their leading size) of the E the router
scores. A pair routed to another expert adds nothing, here as in the
program: what the absent experts would have added is left out, and that
partial result goes on to the next layer. The vocabulary is whatever `wte`
and `head` hold.

Departures from the published model and its recipe, as the configuration
file lists them: the balance term's coefficient 0.001 is the paper's
(Appendix B), not a key of `config.json`; the device-level and
communication balance losses and the token-dropping of the paper's training
run are left out (they belong to the exchange, which one chip does not
have). The optimizer is not the reference's business.

Weights, as the family hands them over (all float32):
    wte (V, D)  lnf_g (D,)  head (D, V)
    layers: a list of dicts, each with ln1_g ln2_g (D,), wq (D, H, nope +
            rope), wkv_a (D, latent + rope), kv_g (latent,), wkv_b (latent,
            H, nope + v), wo (H, v, D), and either
              w_gate w_up (D, F), w_down (F, D)                   dense, or
              router (D, E), w_gate w_up (E_held, D, F), w_down (E_held, F,
              D), ws_gate ws_up (D, Fs), ws_down (Fs, D)          experts

`operands`, where given, is a dtype every matrix product's operands are
rounded to (and back to float32) first: how a program computing in that
precision would differ, for fixing the tolerance of a comparison.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

RMS_EPS = 1e-6
ROPE_THETA = 10000.0
YARN = {"factor": 40, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
        "mscale_all_dim": 0.707}
BALANCE_COEF = 0.001


def _mm(spec, a, b, operands):
    if operands is not None:
        a = a.astype(operands).astype(jnp.float32)
        b = b.astype(operands).astype(jnp.float32)
    return jnp.einsum(spec, a, b)


def rms_norm(x, g):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + RMS_EPS) * g


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(rope_dim, theta=ROPE_THETA, yarn=YARN):
    """The rope_dim / 2 corrected frequencies, float32."""
    def correction_dim(rotations):
        return rope_dim * math.log(
            yarn["original_max_position_embeddings"]
            / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(yarn["beta_slow"])), rope_dim - 1)
    if low == high:
        high += 0.001
    pair = np.arange(rope_dim // 2, dtype=np.float64)
    extrapolated = theta ** (-2 * pair / rope_dim)
    interpolated = extrapolated / yarn["factor"]
    keep = 1.0 - np.clip((pair - low) / (high - low), 0, 1)
    return (interpolated * (1 - keep) + extrapolated * keep).astype(
        np.float32)


def rope(x):
    """Rotary positions on x: (..., S, rope), position = index along S."""
    seq, width = x.shape[-2:]
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] \
        * jnp.asarray(yarn_frequencies(width))[None, :]
    factor = yarn_mscale(YARN["factor"], YARN["mscale"]) \
        / yarn_mscale(YARN["factor"], YARN["mscale_all_dim"])
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1) * factor
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1) * factor
    half = width // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def attention(x, w, operands=None):
    """Causal multi-head latent attention over x: (B, S, D)."""
    seq = x.shape[1]
    v_dim = w["wo"].shape[1]
    latent = w["wkv_b"].shape[0]
    nope = w["wkv_b"].shape[2] - v_dim
    q = _mm("bsd,dhk->bhsk", x, w["wq"], operands)
    down = _mm("bsd,dc->bsc", x, w["wkv_a"], operands)
    c = rms_norm(down[..., :latent], w["kv_g"])
    kv = _mm("bsc,chk->bhsk", c, w["wkv_b"], operands)
    k_pe = rope(down[:, None, :, latent:])             # (B, 1, S, rope)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:])], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(
            k_pe, kv.shape[:3] + k_pe.shape[3:])], axis=-1)
    m = yarn_mscale(YARN["factor"], YARN["mscale_all_dim"])
    scores = _mm("bhqk,bhsk->bhqs", q, k, operands) \
        * (q.shape[-1] ** -0.5 * m * m)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = _mm("bhqs,bhsk->bhqk", probs, kv[..., nope:], operands)
    return _mm("bhsk,hkd->bsd", out, w["wo"], operands)


def gated_mlp(h, w_gate, w_up, w_down, operands=None):
    hidden = jax.nn.silu(_mm("...d,df->...f", h, w_gate, operands)) \
        * _mm("...d,df->...f", h, w_up, operands)
    return _mm("...f,fd->...d", hidden, w_down, operands)


def moe(x, w, top_k, routes=None, first_expert=0, operands=None):
    """x: (B, S, D) -> (the held routed experts' part plus the shared
    experts', the balance term, routes (B, S, k)). `routes`, where given,
    are the experts each token is sent to, in place of its own k largest."""
    batch, seq, width = x.shape
    h = x.reshape(batch * seq, width)
    n_experts = w["router"].shape[1]
    held = w["w_up"].shape[0]
    logits = _mm("nd,de->ne", h, w["router"], operands)
    s = jax.nn.softmax(logits, axis=-1)
    if routes is None:
        routes = lax.top_k(s, top_k)[1]
    routes = routes.reshape(batch * seq, top_k)
    chosen = jnp.any(routes[:, :, None] == jnp.arange(n_experts), axis=1)
    weight = jnp.where(chosen, s, 0.0)      # (tokens, experts)

    def add_expert(out, e):
        w_gate, w_up, w_down, weight_e = e
        return out + weight_e[:, None] * gated_mlp(h, w_gate, w_up, w_down,
                                                   operands), None

    mine = weight[:, first_expert:first_expert + held]
    out, _ = lax.scan(add_expert, jnp.zeros_like(h),
                      (w["w_gate"], w["w_up"], w["w_down"], mine.T))
    out = out + gated_mlp(h, w["ws_gate"], w["ws_up"], w["ws_down"],
                          operands)

    def per_sequence(a):
        return a.reshape(batch, seq, n_experts)
    f = jnp.sum(per_sequence(chosen.astype(jnp.float32)), axis=1) \
        * n_experts / (top_k * seq)
    balance = jnp.mean(jnp.sum(f * jnp.mean(per_sequence(s), axis=1),
                               axis=-1))
    return out.reshape(x.shape), balance, routes.reshape(batch, seq, top_k)


def forward(weights, tokens, top_k, routes=None, first_expert=0,
            operands=None):
    """tokens: (B, S) int32 -> (logits (B, S, V) float32, the expert
    layers' balance terms (Le,), their routes (Le, B, S, k)). `routes`: per
    expert layer, the experts to use in place of the k largest."""
    with jax.default_matmul_precision("highest"):
        x = weights["wte"][tokens]
        balance, used = [], []
        for w in weights["layers"]:
            x = x + attention(rms_norm(x, w["ln1_g"]), w, operands)
            h = rms_norm(x, w["ln2_g"])
            if "router" not in w:
                x = x + gated_mlp(h, w["w_gate"], w["w_up"], w["w_down"],
                                  operands)
                continue
            out, layer_balance, layer_routes = moe(
                h, w, top_k, None if routes is None else routes[len(used)],
                first_expert, operands)
            x = x + out
            balance.append(layer_balance)
            used.append(layer_routes)
        x = rms_norm(x, weights["lnf_g"])
        return (_mm("bsd,dv->bsv", x, weights["head"], operands),
                jnp.stack(balance), jnp.stack(used))


def logits(weights, tokens, top_k, routes=None, first_expert=0,
           operands=None):
    return forward(weights, tokens, top_k, routes, first_expert,
                   operands)[0]


def next_token_loss(logits_, targets):
    """Mean cross-entropy of (B, S, V) logits against (B, S) targets."""
    logp = jax.nn.log_softmax(logits_.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None],
                                         axis=-1))


def loss(weights, tokens, targets, top_k, routes=None, first_expert=0,
         balance_coef=BALANCE_COEF):
    """The training loss: cross-entropy and the balance term."""
    logits_, balance, _ = forward(weights, tokens, top_k, routes,
                                  first_expert)
    return next_token_loss(logits_, targets) \
        + balance_coef * jnp.sum(balance)
