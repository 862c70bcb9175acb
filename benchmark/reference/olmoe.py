"""OLMoE decoder (Muennighoff et al., arXiv:2409.02060; `model_type` olmoe of
huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct): token embedding, pre-norm
blocks of causal multi-head attention and a top-k mixture of gated-SiLU
experts, a final RMSNorm and an untied linear head. Float32 throughout, a
Python loop over the layers and a loop over the experts, each expert applied
to every token and kept where the token chose it: no sorting, no kernel. (The
loop over the experts is a `lax.scan`, so that a compiled comparison holds
one expert's code and not 64 copies a layer: those took 144 s to compile on
the v5e; my chip run, PR 26.)

    RMSNorm(x; g) = x * rsqrt(mean(x^2) + 1e-5) * g
    attention:  q = RMSNorm(h Wq; g_q), k = RMSNorm(h Wk; g_k) over the whole
                projected vector, before it is split into heads; v = h Wv;
                rotary positions (theta 10,000, pairs (i, i + dh/2)) on q, k;
                causal softmax(q k^T / sqrt(dh)) v; o = a Wo. No biases.
    block:      x <- x + Attn(RMSNorm(x; g1)); x <- x + MoE(RMSNorm(x; g2))
    MoE(h):     p = softmax(h Wr) over all E experts; the k largest p_e;
                sum over them of p_e * W_down,e (silu(W_gate,e h) * W_up,e h).
                The k weights are not renormalised (`norm_topk_prob` false).
    loss:       next-token cross-entropy + 0.01 * load balance + 0.001 *
                router z-loss, each auxiliary term averaged over the layers.
                Per layer: load balance = E * sum_e f_e * P_e (f_e the share
                of the T*k assignments sent to e, P_e the mean of p_e over the
                tokens); z = mean over tokens of logsumexp(h Wr)^2.

Departures from the published model and its training recipe, as the
configuration file lists them: the auxiliary terms are computed per
data-parallel shard of the batch and the shards' means averaged (`shards`
below; the paper does not say over which tokens its implementation takes
them), and their coefficients 0.01 and 0.001 are the paper's, not keys of
`config.json`. The optimizer (bf16 Adam moments) is not the reference's
business.

Weights, as the family hands them over (all float32):
    wte (V, D)  lnf_g (D,)  head (D, V)
    layers: a list of dicts with ln1_g ln2_g (D,), wq wk wv (D, H, dh),
            wo (H, dh, D), q_g k_g (H, dh), router (D, E),
            w_gate w_up (E, D, F), w_down (E, F, D)

`operands`, where given, is a dtype every matrix product's operands are
rounded to (and back to float32) first: how a program computing in that
precision would differ, for fixing the tolerance of a comparison.
"""

import jax
import jax.numpy as jnp
from jax import lax

RMS_EPS = 1e-5
ROPE_THETA = 10000.0
LOAD_BALANCE_COEF = 0.01
ROUTER_Z_COEF = 0.001


def _mm(spec, a, b, operands):
    if operands is not None:
        a = a.astype(operands).astype(jnp.float32)
        b = b.astype(operands).astype(jnp.float32)
    return jnp.einsum(spec, a, b)


def rms_norm(x, g):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + RMS_EPS) * g


def rope(x):
    """Rotary positions on x: (B, H, S, dh), position = index along S."""
    seq, head_dim = x.shape[-2:]
    half = head_dim // 2
    freq = ROPE_THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def attention(x, w, operands=None):
    """Causal multi-head attention over x: (B, S, D)."""
    batch, seq, _ = x.shape
    heads, head_dim = w["wq"].shape[1:]

    def heads_of(y):      # (B, S, H*dh) -> (B, H, S, dh)
        return y.reshape(batch, seq, heads, head_dim).transpose(0, 2, 1, 3)

    def project(name):
        return _mm("bsd,de->bse", x, w[name].reshape(-1, heads * head_dim),
                   operands)

    q = rope(heads_of(rms_norm(project("wq"), w["q_g"].reshape(-1))))
    k = rope(heads_of(rms_norm(project("wk"), w["k_g"].reshape(-1))))
    v = heads_of(project("wv"))
    scores = _mm("bhqk,bhsk->bhqs", q, k, operands) / jnp.sqrt(
        jnp.float32(head_dim))
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = _mm("bhqs,bhsk->bhqk", probs, v, operands)
    return _mm("bhsk,hkd->bsd", out, w["wo"], operands)


def moe(x, w, top_k, routes=None, shards=1, operands=None):
    """x: (B, S, D) -> (output, [load balance, router z], routes (B, S, k)).
    `routes`, where given, are the experts each token is sent to, in place
    of its own k largest. The auxiliary terms are those of each of `shards`
    equal parts of the batch, averaged."""
    batch, seq, width = x.shape
    h = x.reshape(batch * seq, width)
    n_experts = w["router"].shape[1]
    logits = _mm("nd,de->ne", h, w["router"], operands)
    p = jax.nn.softmax(logits, axis=-1)
    if routes is None:
        routes = lax.top_k(p, top_k)[1]
    routes = routes.reshape(batch * seq, top_k)
    chosen = jnp.any(routes[:, :, None] == jnp.arange(n_experts), axis=1)
    weight = jnp.where(chosen, p, 0.0)      # (tokens, experts)

    def add_expert(out, e):
        w_gate, w_up, w_down, weight_e = e
        hidden = jax.nn.silu(_mm("nd,df->nf", h, w_gate, operands)) \
            * _mm("nd,df->nf", h, w_up, operands)
        y = _mm("nf,fd->nd", hidden, w_down, operands)
        return out + weight_e[:, None] * y, None

    out, _ = lax.scan(add_expert, jnp.zeros_like(h),
                      (w["w_gate"], w["w_up"], w["w_down"], weight.T))

    def per_shard(a):
        return a.reshape(shards, -1, *a.shape[1:])
    share = jnp.mean(per_shard(chosen.astype(jnp.float32)), axis=1) / top_k
    balance = n_experts * jnp.sum(share * jnp.mean(per_shard(p), axis=1),
                                  axis=-1)
    z = jnp.mean(jnp.square(per_shard(jax.nn.logsumexp(logits, axis=-1))),
                 axis=1)
    return (out.reshape(x.shape), jnp.stack([balance.mean(), z.mean()]),
            routes.reshape(batch, seq, top_k))


def forward(weights, tokens, top_k, routes=None, shards=1, operands=None):
    """tokens: (B, S) int32 -> (logits (B, S, V) float32, the layers'
    [load balance, router z] (L, 2), the layers' routes (L, B, S, k)).
    `routes`: per layer, the experts to use in place of the k largest."""
    with jax.default_matmul_precision("highest"):
        x = weights["wte"][tokens]
        aux, used = [], []
        for i, w in enumerate(weights["layers"]):
            x = x + attention(rms_norm(x, w["ln1_g"]), w, operands)
            out, layer_aux, layer_routes = moe(
                rms_norm(x, w["ln2_g"]), w, top_k,
                None if routes is None else routes[i], shards, operands)
            x = x + out
            aux.append(layer_aux)
            used.append(layer_routes)
        x = rms_norm(x, weights["lnf_g"])
        return (_mm("bsd,dv->bsv", x, weights["head"], operands),
                jnp.stack(aux), jnp.stack(used))


def logits(weights, tokens, top_k, routes=None, operands=None):
    return forward(weights, tokens, top_k, routes, operands=operands)[0]


def next_token_loss(logits_, targets):
    """Mean cross-entropy of (B, S, V) logits against (B, S) targets."""
    logp = jax.nn.log_softmax(logits_.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None],
                                         axis=-1))


def loss(weights, tokens, targets, top_k, routes=None, shards=1,
         load_balance_coef=LOAD_BALANCE_COEF, router_z_coef=ROUTER_Z_COEF):
    """The training loss: cross-entropy and the two auxiliary terms."""
    logits_, aux, _ = forward(weights, tokens, top_k, routes, shards)
    balance, z = jnp.mean(aux, axis=0)
    return (next_token_loss(logits_, targets) + load_balance_coef * balance
            + router_z_coef * z)
