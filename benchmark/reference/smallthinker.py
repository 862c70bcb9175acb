"""SmallThinker decoder (PowerInfer, arXiv:2507.20984; `config.json` of
huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct): token embedding,
pre-norm blocks of grouped-query attention and routed ReLU-gated experts, a
final RMSNorm and an untied linear head. Every layer is an expert layer; one
layer in four attends the whole causal half WITHOUT a positional encoding
(NoPE), the other three rotate and see a window. Float32 throughout, a Python
loop over the layers, masks written out token by token, attention scored a
block of query rows at a time (so that 16,384 tokens fit), a `lax.scan` over
the experts held, each applied to every token and kept where the token chose
it: no kernel, no sort, no buffer, no cache.

One block, layer `l` of kind `kinds[l]`, input x: (tokens, D):

    s      = x W_r                         the router reads x ITSELF, the
                                           layer's input, before the first
                                           norm and attention ("router
                                           placed before attention")
    h      = RMSNorm(x; g1)                x * rsqrt(mean(x^2) + 1e-6) * g1
    q,k,v  = h W_q (H heads), h W_k (G heads), h W_v (G heads)   no biases
    "window": q, k rotated (pairs (i, i + d/2), angle position *
              theta^(-2i/d), theta 1.5e6); query t sees keys
              t - window + 1 .. t (the window counts the query itself)
    "full":   no rotation; query t sees keys 0 .. t
    a      = softmax(q k^T / sqrt(d)) v, query head i reads K/V head
             floor(i / (H / G))
    x'     = x + a W_o
    h2     = RMSNorm(x'; g2)
    p      = softmax(s) over all E experts; the k largest p_e chosen;
             w_e = p_e / (their sum)       (`norm_topk_prob`)
    f      = sum over the chosen e that are held of
             w_e W_down,e (relu(W_gate,e h2) * (W_up,e h2))
    out    = x' + f

    loss:  next-token cross-entropy (config.json gives no auxiliary
           coefficient: none).

The share. A chip of an expert-parallel deployment holds some of a layer's
experts: `w_gate`, `w_up`, `w_down` hold experts [first_expert, first_expert
+ their leading size) of the E the router scores. A pair routed to another
expert adds nothing, here as in the program; the weights are renormalised
over all k chosen, held or not. That partial result goes on to the next
layer. The vocabulary is whatever `wte` and `head` hold.

Assumed, as the configuration file lists it: the router's input is the
un-normed layer input; the ReLU gate; rotate-half pairs; the window counts
the query; no auxiliary loss; the catalog's "secondary experts" have no key
in `config.json` and are left out.

Weights, as the family hands them over (all float32):
    wte (V, D)  lnf_g (D,)  head (D, V)
    layers: a list of dicts, each with ln1_g ln2_g (D,), wq (D, H, d),
            wk wv (D, G, d), wo (H, d, D), router (D, E), w_gate w_up
            (E_held, D, F), w_down (E_held, F, D)

`operands`, where given, is a dtype every matrix product's operands are
rounded to (and back to float32) first: how a program computing in that
precision would differ, for fixing the tolerance of a comparison. `fault`,
where given, is one mechanism computed wrongly on purpose, which the
comparison's limits must refuse: "no_window" (a "window" layer sees the
whole causal half), "rope_on_full" (the "full" layers rotate too),
"router_after_attention" (the router scores h2), "silu_gate", "no_renorm"
(the k weights as the softmax gives them), "kv_group" (query head i reads
K/V head i mod G).
"""

import jax
import jax.numpy as jnp
from jax import lax

RMS_EPS = 1e-6
ROPE_THETA = 1.5e6
QUERY_BLOCK = 256      # query rows of attention scored at a time
FAULTS = ("no_window", "rope_on_full", "router_after_attention", "silu_gate",
          "no_renorm", "kv_group")


def _mm(spec, a, b, operands):
    if operands is not None:
        a = a.astype(operands).astype(jnp.float32)
        b = b.astype(operands).astype(jnp.float32)
    return jnp.einsum(spec, a, b)


def rms_norm(x, g):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + RMS_EPS) * g


def rope(x):
    """Rotary positions on x: (..., S, d), position = index along S."""
    seq, width = x.shape[-2:]
    half = width // 2
    freq = ROPE_THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def _softmax_rows(q, k, v, window, operands):
    """softmax(q k^T / sqrt(d) + mask) v for q, k, v: (B, H, S, d): causal,
    and with `window` a query sees the `window` keys that end with its
    own."""
    seq, width = q.shape[2], q.shape[3]
    block = min(QUERY_BLOCK, seq)
    if seq % block:
        raise ValueError(f"{seq} tokens are no whole number of blocks of "
                         f"{block} query rows")
    keys = jnp.arange(seq)

    def rows(start):
        q_rows = lax.dynamic_slice_in_dim(q, start, block, axis=2)
        scores = _mm("bhqk,bhsk->bhqs", q_rows, k, operands) / jnp.sqrt(
            jnp.float32(width))
        at = (start + jnp.arange(block))[:, None]
        seen = keys[None, :] <= at
        if window:
            seen = seen & (keys[None, :] > at - window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return _mm("bhqs,bhsk->bhqk", probs, v, operands)

    out = lax.map(rows, jnp.arange(0, seq, block))    # (blocks, B, H, q, d)
    return jnp.moveaxis(out, 0, 2).reshape(q.shape)


def attention(h, w, kind, window, operands=None, fault=None):
    """Grouped-query attention of the normed h: (B, S, D), of a layer of
    `kind` "full" or "window"."""
    q = _mm("bsd,dhk->bhsk", h, w["wq"], operands)
    k = _mm("bsd,dhk->bhsk", h, w["wk"], operands)
    v = _mm("bsd,dhk->bhsk", h, w["wv"], operands)
    if kind == "window" or fault == "rope_on_full":
        q, k = rope(q), rope(k)
    heads, kv_heads = q.shape[1], k.shape[1]
    if fault == "kv_group":
        reads = jnp.arange(heads) % kv_heads
    else:
        reads = jnp.arange(heads) // (heads // kv_heads)
    seen = window if kind == "window" and fault != "no_window" else 0
    a = _softmax_rows(q, k[:, reads], v[:, reads], seen, operands)
    return _mm("bhsk,hkd->bsd", a, w["wo"], operands)


def expert(h, w_gate, w_up, w_down, operands=None, fault=None):
    act = jax.nn.silu if fault == "silu_gate" else jax.nn.relu
    hidden = act(_mm("nd,df->nf", h, w_gate, operands)) \
        * _mm("nd,df->nf", h, w_up, operands)
    return _mm("nf,fd->nd", hidden, w_down, operands)


def moe(scored, h2, w, top_k, first_expert=0, operands=None, fault=None):
    """The held experts' part of the layer's result for the rows h2: (B, S,
    D), routed by what the router makes of `scored`: (B, S, D). Returns (the
    part, routes (B, S, k))."""
    batch, seq, width = h2.shape
    rows = h2.reshape(batch * seq, width)
    n_experts = w["router"].shape[1]
    held = w["w_up"].shape[0]
    logits = _mm("nd,de->ne", scored.reshape(batch * seq, width),
                 w["router"], operands)
    p = jax.nn.softmax(logits, axis=-1)
    top, routes = lax.top_k(p, top_k)
    chosen = jnp.any(routes[:, :, None] == jnp.arange(n_experts), axis=1)
    weight = jnp.where(chosen, p, 0.0)                  # (tokens, experts)
    if fault != "no_renorm":
        weight = weight / jnp.sum(top, axis=-1, keepdims=True)

    def add_expert(out, e):
        w_gate, w_up, w_down, weight_e = e
        return out + weight_e[:, None] * expert(rows, w_gate, w_up, w_down,
                                                operands, fault), None

    mine = weight[:, first_expert:first_expert + held]
    out, _ = lax.scan(add_expert, jnp.zeros_like(rows),
                      (w["w_gate"], w["w_up"], w["w_down"], mine.T))
    return out.reshape(h2.shape), routes.reshape(batch, seq, top_k)


def layer(x, w, kind, window, top_k, first_expert=0, operands=None,
          fault=None):
    """One block on x: (B, S, D): (its output, its routes)."""
    x_in = x
    x = x + attention(rms_norm(x, w["ln1_g"]), w, kind, window, operands,
                      fault)
    h2 = rms_norm(x, w["ln2_g"])
    scored = h2 if fault == "router_after_attention" else x_in
    out, routes = moe(scored, h2, w, top_k, first_expert, operands, fault)
    return x + out, routes


def final_hidden(weights, tokens, kinds, window, top_k, first_expert=0,
                 operands=None, fault=None):
    """tokens: (B, S) int32 -> (the final RMSNorm's output (B, S, D), the
    layers' routes (L, B, S, k)). `kinds`: each layer's kind, in the order
    they run; `window`: the keys a query of a "window" layer sees."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r}: choose from {FAULTS}")
    if len(kinds) != len(weights["layers"]) or \
            set(kinds) - {"full", "window"}:
        raise ValueError(f"{len(weights['layers'])} layers of the kinds "
                         f"{kinds}")
    with jax.default_matmul_precision("highest"):
        x = weights["wte"][tokens]
        used = []
        for kind, w in zip(kinds, weights["layers"]):
            x, routes = layer(x, w, kind, window, top_k, first_expert,
                              operands, fault)
            used.append(routes)
        return rms_norm(x, weights["lnf_g"]), jnp.stack(used)


def head(hidden, weights, operands=None):
    """The untied head: hidden (B, S, D) -> logits (B, S, V)."""
    with jax.default_matmul_precision("highest"):
        return _mm("bsd,dv->bsv", hidden, weights["head"], operands)


def forward(weights, tokens, kinds, window, top_k, first_expert=0,
            operands=None, fault=None):
    """tokens: (B, S) int32 -> logits (B, S, V) float32."""
    hidden, _ = final_hidden(weights, tokens, kinds, window, top_k,
                             first_expert, operands, fault)
    return head(hidden, weights, operands)


def next_token_loss(logits_, targets):
    """Mean cross-entropy of (B, S, V) logits against (B, S) targets."""
    logp = jax.nn.log_softmax(logits_.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None],
                                         axis=-1))


def loss(weights, tokens, targets, kinds, window, top_k, first_expert=0):
    """The training loss: next-token cross-entropy."""
    return next_token_loss(forward(weights, tokens, kinds, window, top_k,
                                   first_expert), targets)
