"""Phi-4-mini-flash-reasoning's decoder (`model_type` phi4flash of
huggingface.co/microsoft/Phi-4-mini-flash-reasoning; the architecture is
SambaY, arXiv:2507.06607): a self-decoder in which Mamba-1 state-space layers
(Gu and Dao, arXiv:2312.00752) alternate with 512-token-window differential
attention (Ye et al., arXiv:2410.05258), one full differential-attention
layer, and a cross-decoder in which Gated Memory Units alternate with
cross-attention to that one layer's keys and values. Float32 throughout, a
Python loop over the layers, the state-space recurrence ONE TOKEN AT A TIME
(a `lax.scan` over the tokens), the convolution as shifted adds, attention as
a softmax over explicit scores with the mask written out, a block of query
rows at a time so that heads x S x S never exists. No kernel, no cache.
Imports nothing of `horovod_tpu`.

    LN(x; g, b) = (x - mean) * rsqrt(var + 1e-5) * g + b
    every layer l: x <- x + Mix_l(LN(x; g1, b1));
                   x <- x + W_down (silu(W_gate h) * W_up h), h = LN(x; g2, b2)
    after the last: logits = LN(x; gf, bf) . wte^T   (tied, no head bias)
    No positional encoding of any kind.

    "ssm":  [xt, z] = h W_in; c = silu(conv_K(xt) + b_conv), depthwise and
            causal over K taps; [r, B, C] = c W_x; delta = softplus(r W_dt +
            b_dt); A = -exp(A_log) (E x N); per channel e and state n,
            s_t = exp(delta_t[e] A[e,n]) s_(t-1) + delta_t[e] c_t[e] B_t[n],
            s_0 = 0; y_t[e] = sum_n s_t[e,n] C_t[n] + D[e] c_t[e];
            out = (y * silu(z)) W_out. The LAST "ssm" layer before the
            cross-decoder also hands y (before the gate) on as the memory m.
    "gmu":  out = (silu(h W_1) * m) W_2.
    "window" / "full", differential attention: [q, k, v] = h W + b, 2P query
            heads and 2Q key and value heads of d. Heads pair up as (2i,
            2i+1): q1, q2 the even and odd query heads (P pairs), k1, k2 the
            even and odd key heads (Q pairs), vbar = [v_even | v_odd], 2d
            wide. Query pair i reads K/V pair floor(i / (P / Q)).
            a1 = softmax(q1 k1^T / sqrt(d) + mask) vbar, a2 likewise of q2,
            k2; lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,
            lam0 = 0.8 - 0.6 exp(-0.3 l); a = (1 - lam0) RMSNorm(a1 - lam a2;
            g of 2d, eps 1e-5), read back as 2P heads of d; out = a W_o + b_o.
            mask: causal; in a "window" layer query t sees keys
            t - window + 1 ... t. The "full" layer's k and v are kept.
    "cross": q = h W_q + b_q only; k, v are the "full" layer's; the same
            differential attention with this layer's own lam vectors and
            scale, causal over the whole sequence.

What the published `config.json` has no key for is listed in the
configuration file under `assumed`. The optimizer is not the reference's
business.

Weights, as the family hands them over (all float32):
    wte (V, D)  lnf_g lnf_b (D,)
    layers: a list of dicts in the order the layers run, each with
            ln1_g ln1_b ln2_g ln2_b (D,), w_gate w_up (D, F), w_down (F, D),
            and the leaves of its kind (`kinds` names each layer's):
      ssm:    w_in (D, 2E), conv (E, K), conv_b (E,), w_x (E, R + 2N),
              w_dt (R, E), dt_b (E,), a_log (E, N), d_skip (E,),
              w_out (E, D)
      gmu:    w_1 (D, E), w_2 (E, D)
      window, full: wq (D, 2P, d), bq (2P, d), wk wv (D, 2Q, d),
              bk bv (2Q, d), wo (2P, d, D), bo (D,), lq1 lk1 lq2 lk2 (d,),
              sub_g (2d,)
      cross:  the same without wk, bk, wv, bv

`operands`, where given, is a dtype every matrix product's operands are
rounded to (and back to float32) first: how a program computing in that
precision would differ, for fixing the tolerance of a comparison. `fault`,
where given, is one mechanism computed wrongly on purpose, which the
comparison's limits must refuse: "no_window" (a "window" layer sees the whole
causal half), "kv_pair" (query pair i reads K/V pair i mod Q), "no_a2" (the
second softmax left out), "wrong_memory" (the Gated Memory Units read the
FIRST "ssm" layer's y).
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

LN_EPS = 1e-5
SUBLN_EPS = 1e-5
QUERY_BLOCK = 256      # query rows of attention scored at a time
FAULTS = ("no_window", "kv_pair", "no_a2", "wrong_memory")


def _mm(spec, a, b, operands):
    if operands is not None:
        a = a.astype(operands).astype(jnp.float32)
        b = b.astype(operands).astype(jnp.float32)
    return jnp.einsum(spec, a, b)


def layer_norm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + LN_EPS) * g + b


def mlp(x, w, operands=None):
    hidden = jax.nn.silu(_mm("bsd,df->bsf", x, w["w_gate"], operands)) \
        * _mm("bsd,df->bsf", x, w["w_up"], operands)
    return _mm("bsf,fd->bsd", hidden, w["w_down"], operands)


def lambda_init(layer: int) -> float:
    """lam0 of layer `layer` (its index in the model as run)."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


# --------------------------------------------------------------------------
# Mamba-1
# --------------------------------------------------------------------------

def causal_conv(u, taps, bias):
    """silu of the depthwise causal convolution of u: (B, S, E) over S with
    taps: (E, K) and bias: (E,), as K shifted adds."""
    n = taps.shape[-1]
    y = jnp.zeros_like(u) + bias
    for j in range(n):
        back = n - 1 - j            # tap j sees the token `back` before
        shifted = jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :u.shape[1]]
        y = y + shifted * taps[:, j]
    return jax.nn.silu(y)


def selective_scan(c, delta, a, b_in, c_out, d_skip):
    """The recurrence, one token at a time. c, delta: (B, S, E); a: (E, N);
    b_in, c_out: (B, S, N); d_skip: (E,). Returns y: (B, S, E)."""
    def step(s, xs):
        c_t, delta_t, b_t, o_t = xs
        s = jnp.exp(delta_t[..., None] * a) * s \
            + (delta_t * c_t)[..., None] * b_t[:, None, :]
        return s, jnp.einsum("ben,bn->be", s, o_t) + d_skip * c_t

    batch, _, channels = c.shape
    per_token = tuple(jnp.moveaxis(x, 1, 0) for x in (c, delta, b_in, c_out))
    _, y = lax.scan(step, jnp.zeros((batch, channels, a.shape[1]),
                                    jnp.float32), per_token)
    return jnp.moveaxis(y, 0, 1)


def ssm(x, w, operands=None):
    """A Mamba-1 mixer over x: (B, S, D); returns (out, y before the gate)."""
    channels, states = w["a_log"].shape
    rank = w["w_dt"].shape[0]
    both = _mm("bsd,de->bse", x, w["w_in"], operands)
    xt, z = both[..., :channels], both[..., channels:]
    c = causal_conv(xt, w["conv"], w["conv_b"])
    proj = _mm("bse,er->bsr", c, w["w_x"], operands)
    r, b_in, c_out = (proj[..., :rank], proj[..., rank:rank + states],
                      proj[..., rank + states:])
    delta = jax.nn.softplus(_mm("bsr,re->bse", r, w["w_dt"], operands)
                            + w["dt_b"])
    y = selective_scan(c, delta, -jnp.exp(w["a_log"]), b_in, c_out,
                       w["d_skip"])
    return _mm("bse,ed->bsd", y * jax.nn.silu(z), w["w_out"], operands), y


def gmu(x, w, memory, operands=None):
    gate = jax.nn.silu(_mm("bsd,de->bse", x, w["w_1"], operands))
    return _mm("bse,ed->bsd", gate * memory, w["w_2"], operands)


# --------------------------------------------------------------------------
# Differential attention
# --------------------------------------------------------------------------

def _softmax_rows(q, k, v, window, operands):
    """softmax(q k^T / sqrt(d) + mask) v for q: (B, P, S, d), k: (B, P, S,
    d), v: (B, P, S, dv): causal, and with `window` a query sees the
    `window` keys that end with its own."""
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

    out = lax.map(rows, jnp.arange(0, seq, block))    # (blocks, B, P, q, dv)
    return jnp.moveaxis(out, 0, 2).reshape(q.shape[:3] + v.shape[3:])


def keys_and_values(x, w, operands=None):
    """(k1, k2, vbar) of an attention layer: (B, Q, S, d) twice and (B, Q,
    S, 2d)."""
    def project(name, bias):
        return _mm("bsd,dhk->bhsk", x, w[name], operands) \
            + w[bias][None, :, None, :]

    k, v = project("wk", "bk"), project("wv", "bv")
    return (k[:, 0::2], k[:, 1::2],
            jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1))


def diff_attention(x, w, kv, layer, window=0, operands=None, fault=None):
    """Differential attention of x: (B, S, D) against kv = (k1, k2, vbar)."""
    k1, k2, vbar = kv
    q = _mm("bsd,dhk->bhsk", x, w["wq"], operands) \
        + w["bq"][None, :, None, :]
    q1, q2 = q[:, 0::2], q[:, 1::2]
    pairs, kv_pairs = q1.shape[1], k1.shape[1]
    if fault == "kv_pair":
        reads = jnp.arange(pairs) % kv_pairs
    else:
        reads = jnp.arange(pairs) // (pairs // kv_pairs)
    a1 = _softmax_rows(q1, k1[:, reads], vbar[:, reads], window, operands)
    a2 = _softmax_rows(q2, k2[:, reads], vbar[:, reads], window, operands)
    lam0 = lambda_init(layer)
    lam = jnp.exp(jnp.sum(w["lq1"] * w["lk1"])) \
        - jnp.exp(jnp.sum(w["lq2"] * w["lk2"])) + lam0
    a = a1 if fault == "no_a2" else a1 - lam * a2
    a = a * lax.rsqrt(jnp.mean(jnp.square(a), axis=-1, keepdims=True)
                      + SUBLN_EPS) * w["sub_g"] * (1.0 - lam0)
    # pair i is heads 2i and 2i + 1 of the output projection
    batch, _, seq, wide = a.shape
    a = a.reshape(batch, pairs, seq, 2, wide // 2).transpose(0, 1, 3, 2, 4)
    a = a.reshape(batch, 2 * pairs, seq, wide // 2)
    return _mm("bhsk,hkd->bsd", a, w["wo"], operands) + w["bo"]


# --------------------------------------------------------------------------
# The model
# --------------------------------------------------------------------------

def final_hidden(weights, tokens, kinds, window, operands=None, fault=None):
    """tokens: (B, S) int32 -> the final LayerNorm's output (B, S, D).
    `kinds`: each layer's kind, in the order they run; `window`: the keys a
    query of a "window" layer sees."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r}: choose from {FAULTS}")
    with jax.default_matmul_precision("highest"):
        x = weights["wte"][tokens]
        memory = kv = None
        for l, (kind, w) in enumerate(zip(kinds, weights["layers"])):
            h = layer_norm(x, w["ln1_g"], w["ln1_b"])
            if kind == "ssm":
                mixed, y = ssm(h, w, operands)
                if memory is None or fault != "wrong_memory":
                    memory = y      # the last one before a "gmu" is read
            elif kind == "gmu":
                mixed = gmu(h, w, memory, operands)
            elif kind == "cross":
                mixed = diff_attention(h, w, kv, l, 0, operands, fault)
            elif kind in ("window", "full"):
                own = keys_and_values(h, w, operands)
                seen = window if kind == "window" and fault != "no_window" \
                    else 0
                mixed = diff_attention(h, w, own, l, seen, operands, fault)
                if kind == "full":
                    kv = own
            else:
                raise ValueError(f"layer {l} is of the kind {kind!r}")
            x = x + mixed
            x = x + mlp(layer_norm(x, w["ln2_g"], w["ln2_b"]), w, operands)
        return layer_norm(x, weights["lnf_g"], weights["lnf_b"])


def head(hidden, wte, operands=None):
    """The tied head: hidden (B, S, D) . wte^T -> logits (B, S, V)."""
    with jax.default_matmul_precision("highest"):
        return _mm("bsd,vd->bsv", hidden, wte, operands)


def forward(weights, tokens, kinds, window, operands=None, fault=None):
    """tokens: (B, S) int32 -> logits (B, S, V) float32."""
    return head(final_hidden(weights, tokens, kinds, window, operands,
                             fault), weights["wte"], operands)


def next_token_loss(logits_, targets):
    """Mean cross-entropy of (B, S, V) logits against (B, S) targets."""
    logp = jax.nn.log_softmax(logits_.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None],
                                         axis=-1))


def loss(weights, tokens, targets, kinds, window):
    """The training loss: next-token cross-entropy."""
    return next_token_loss(forward(weights, tokens, kinds, window), targets)
