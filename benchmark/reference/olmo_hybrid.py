"""Olmo-Hybrid decoder (`model_type` olmo_hybrid of
huggingface.co/allenai/Olmo-Hybrid-7B): token embedding, blocks whose mixer
is by turns a Gated DeltaNet linear-attention layer (Yang, Kautz and
Hatamizadeh, arXiv:2412.06464) and a causal full-attention layer, as
`layer_types` orders them, each followed by a gated-SiLU MLP; a final RMSNorm
and an untied linear head. Float32 throughout, a Python loop over the
layers, the linear layers' rule ONE TOKEN AT A TIME (a `lax.scan` over the
tokens: no chunks, no WY form), the convolution as shifted adds, attention
as a softmax over explicit scores, a block of query rows at a time so that
heads x S x S never exists. No kernel, no cache.

    RMSNorm(x; g) = x * rsqrt(mean(x^2) + 1e-6) * g
    block:      x <- x + RMSNorm(Mixer(x); g1); x <- x + RMSNorm(MLP(x); g2)
                (each norm on its sub-layer's output, inside the residual)
    MLP(h):     W_down (silu(W_gate h) * W_up h). No biases anywhere.
    full:       q = RMSNorm(h Wq; g_q), k = RMSNorm(h Wk; g_k) over the whole
                projected vector, before it is split into heads; v = h Wv;
                NO rotary embedding; causal softmax(q k^T / sqrt(dh)) v;
                o = a Wo.
    linear, per head (keys dk wide, values dv):
      1. q = h Wq, k = h Wk (dk a head), v = h Wv, z = h Wz (dv a head),
         a = h Wa, b = h Wb (one a head).
      2. q, k, v each through a depthwise causal convolution over the
         sequence, K taps, no bias, then SiLU:
         y_t,c = silu(sum_j w_c,j u_(t-K+1+j),c), zeros before the start.
      3. q, k L2-normalised per head (x * rsqrt(sum x^2 + 1e-6)); q scaled
         by dk^-1/2.
      4. beta_t = 2 sigmoid(b_t) (the 2 is `linear_allow_neg_eigval`);
         g_t = -exp(A_log) softplus(a_t + dt_bias), alpha_t = exp(g_t).
      5. a state S (dk x dv) per head, zero at the start:
         S <- alpha_t S; u_t = beta_t (v_t - S^T k_t); S <- S + k_t u_t^T;
         o_t = S^T q_t.
      6. o_t <- RMSNorm(o_t; one scale of dv) * silu(z_t) per head; the
         heads concatenated through W_o.
    loss:       next-token cross-entropy over whatever vocabulary `wte` and
                `head` hold.

What the published `config.json` does not say is listed in the
configuration file under `assumed` (the norms' placement, no rotary
embedding, the Gated DeltaNet details of steps 2, 3 and 6). The optimizer
is not the reference's business.

Weights, as the family hands them over (all float32):
    wte (V, D)  lnf_g (D,)  head (D, V)
    layers: a list of dicts in the order the layers run, each with
            ln1_g ln2_g (D,), w_gate w_up (D, F), w_down (F, D),
            wo (H, dv, D), and the leaves of its kind (a layer that has
            `a_log` is a linear one):
      full:   wq wk wv (D, H, dh), q_g k_g (H, dh)
      linear: wq wk (D, H, dk), wv wz (D, H, dv), wa wb (D, H),
              a_log dt_bias (H,), conv_q conv_k (H, dk, K),
              conv_v (H, dv, K), o_g (dv,)

`operands`, where given, is a dtype every matrix product's operands are
rounded to (and back to float32) first; `state`, where given, a dtype the
linear layers' state S is rounded to after every token: how a program
computing, or carrying its state, in that precision would differ, for fixing
the tolerance of a comparison.
"""

import jax
import jax.numpy as jnp
from jax import lax

RMS_EPS = 1e-6
L2_EPS = 1e-6
QUERY_BLOCK = 256      # query rows of full attention scored at a time


def _mm(spec, a, b, operands):
    if operands is not None:
        a = a.astype(operands).astype(jnp.float32)
        b = b.astype(operands).astype(jnp.float32)
    return jnp.einsum(spec, a, b)


def rms_norm(x, g):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + RMS_EPS) * g


def mlp(x, w, operands=None):
    hidden = jax.nn.silu(_mm("bsd,df->bsf", x, w["w_gate"], operands)) \
        * _mm("bsd,df->bsf", x, w["w_up"], operands)
    return _mm("bsf,fd->bsd", hidden, w["w_down"], operands)


def attention(x, w, operands=None):
    """Causal multi-head attention over x: (B, S, D), no positions."""
    batch, seq, _ = x.shape
    heads, head_dim = w["wq"].shape[1:]

    def heads_of(y):      # (B, S, H*dh) -> (B, H, S, dh)
        return y.reshape(batch, seq, heads, head_dim).transpose(0, 2, 1, 3)

    def project(name):
        return _mm("bsd,de->bse", x, w[name].reshape(-1, heads * head_dim),
                   operands)

    q = heads_of(rms_norm(project("wq"), w["q_g"].reshape(-1)))
    k = heads_of(rms_norm(project("wk"), w["k_g"].reshape(-1)))
    v = heads_of(project("wv"))
    block = min(QUERY_BLOCK, seq)
    if seq % block:
        raise ValueError(f"{seq} tokens are no whole number of blocks of "
                         f"{block} query rows")
    keys = jnp.arange(seq)

    def rows(start):
        q_rows = lax.dynamic_slice_in_dim(q, start, block, axis=2)
        scores = _mm("bhqk,bhsk->bhqs", q_rows, k, operands) / jnp.sqrt(
            jnp.float32(head_dim))
        causal = keys[None, :] <= (start + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return _mm("bhqs,bhsk->bhqk", probs, v, operands)

    out = lax.map(rows, jnp.arange(0, seq, block))    # (blocks, B, H, q, dh)
    out = jnp.moveaxis(out, 0, 2).reshape(batch, heads, seq, head_dim)
    return _mm("bhsk,hkd->bsd", out, w["wo"], operands)


def causal_conv(u, taps):
    """SiLU of the depthwise causal convolution of u: (B, S, H, d) over S
    with taps: (H, d, K), as K shifted adds."""
    n = taps.shape[-1]
    y = jnp.zeros_like(u)
    for j in range(n):
        back = n - 1 - j            # tap j sees the token `back` before
        shifted = jnp.pad(u, ((0, 0), (back, 0), (0, 0), (0, 0)))[
            :, :u.shape[1]]
        y = y + shifted * taps[..., j]
    return jax.nn.silu(y)


def delta_rule(q, k, v, g, beta, state=None):
    """Step 5, one token at a time. q, k: (B, S, H, dk); v: (B, S, H, dv);
    g, beta: (B, S, H). Returns o: (B, S, H, dv)."""
    def rounded(s):
        # reduce_precision, not a pair of casts: the compiler may drop a
        # cast to a narrower type and back (it did, on the v5e)
        if state is None:
            return s
        info = jnp.finfo(state)
        return lax.reduce_precision(s, info.nexp, info.nmant)

    def step(s, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        s = jnp.exp(g_t)[..., None, None] * s
        u = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
        s = rounded(s + k_t[..., :, None] * u[..., None, :])
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    batch, _, heads, dk = q.shape
    per_token = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, o = lax.scan(step, jnp.zeros((batch, heads, dk, v.shape[-1]),
                                    jnp.float32), per_token)
    return jnp.moveaxis(o, 0, 1)


def linear_attention(x, w, operands=None, state=None):
    """A Gated DeltaNet mixer over x: (B, S, D)."""
    def project(name):
        spec = "bsd,dh->bsh" if w[name].ndim == 2 else "bsd,dhk->bshk"
        return _mm(spec, x, w[name], operands)

    q = causal_conv(project("wq"), w["conv_q"])
    k = causal_conv(project("wk"), w["conv_k"])
    v = causal_conv(project("wv"), w["conv_v"])
    z, a, b = project("wz"), project("wa"), project("wb")

    def l2_normed(y):
        return y * lax.rsqrt(jnp.sum(jnp.square(y), axis=-1, keepdims=True)
                             + L2_EPS)

    q = l2_normed(q) * q.shape[-1] ** -0.5
    k = l2_normed(k)
    beta = 2.0 * jax.nn.sigmoid(b)
    g = -jnp.exp(w["a_log"]) * jax.nn.softplus(a + w["dt_bias"])
    o = delta_rule(q, k, v, g, beta, state)
    o = rms_norm(o, w["o_g"]) * jax.nn.silu(z)
    return _mm("bshk,hkd->bsd", o, w["wo"], operands)


def forward(weights, tokens, operands=None, state=None):
    """tokens: (B, S) int32 -> logits (B, S, V) float32."""
    with jax.default_matmul_precision("highest"):
        x = weights["wte"][tokens]
        for w in weights["layers"]:
            mixed = linear_attention(x, w, operands, state) \
                if "a_log" in w else attention(x, w, operands)
            x = x + rms_norm(mixed, w["ln1_g"])
            x = x + rms_norm(mlp(x, w, operands), w["ln2_g"])
        x = rms_norm(x, weights["lnf_g"])
        return _mm("bsd,dv->bsv", x, weights["head"], operands)


def next_token_loss(logits_, targets):
    """Mean cross-entropy of (B, S, V) logits against (B, S) targets."""
    logp = jax.nn.log_softmax(logits_.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None],
                                         axis=-1))


def loss(weights, tokens, targets):
    """The training loss: next-token cross-entropy."""
    return next_token_loss(forward(weights, tokens), targets)
