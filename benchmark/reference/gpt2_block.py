"""GPT-2-architecture decoder (Radford et al. 2019; Cerebras-GPT,
arXiv:2304.03208 section 2): learned token and position embeddings, pre-LN
blocks of causal multi-head attention and a GELU MLP, a final LayerNorm and a
linear head. Float32 throughout.

Departures from the published model, as the configuration file lists them and
as `horovod_tpu/models/transformer.py` computes: no biases on the four
attention projections, an output head that is not tied to the token
embedding, the tanh approximation of GELU.

Weights, as the family hands them over (all float32):
    wte (V, D)  wpe (P, D)  lnf_g lnf_b (D,)  head (D, V)
    layers: a list of dicts with ln1_g ln1_b ln2_g ln2_b (D,),
            wq wk wv (D, H, dh), wo (H, dh, D),
            w_fc (D, F), b_fc (F,), w_proj (F, D), b_proj (D,)
"""

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def layer_norm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def attention(x, w):
    """Causal multi-head attention over x: (B, S, D)."""
    seq = x.shape[1]
    head_dim = w["wq"].shape[-1]
    q = jnp.einsum("bsd,dhk->bhsk", x, w["wq"])
    k = jnp.einsum("bsd,dhk->bhsk", x, w["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", x, w["wv"])
    scores = jnp.einsum("bhqk,bhsk->bhqs", q, k) / jnp.sqrt(
        jnp.float32(head_dim))
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqs,bhsk->bhqk", probs, v)
    return jnp.einsum("bhsk,hkd->bsd", out, w["wo"])


def mlp(x, w):
    return gelu_tanh(x @ w["w_fc"] + w["b_fc"]) @ w["w_proj"] + w["b_proj"]


def logits(weights, tokens):
    """tokens: (B, S) int32 -> (B, S, V) float32."""
    with jax.default_matmul_precision("highest"):
        seq = tokens.shape[1]
        x = weights["wte"][tokens] + weights["wpe"][:seq][None]
        for w in weights["layers"]:
            x = x + attention(layer_norm(x, w["ln1_g"], w["ln1_b"]), w)
            x = x + mlp(layer_norm(x, w["ln2_g"], w["ln2_b"]), w)
        x = layer_norm(x, weights["lnf_g"], weights["lnf_b"])
        return x @ weights["head"]


def next_token_loss(logits_, targets):
    """Mean cross-entropy of (B, S, V) logits against (B, S) targets."""
    logp = jax.nn.log_softmax(logits_.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None],
                                         axis=-1))
