"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464) over a sequence,
in its chunked form: the one scan over the sequence in this package.

Per head, with keys k_t (dk wide), values v_t (dv wide), queries q_t, a log
decay g_t <= 0 and a write strength beta_t, a float32 state S (dk x dv) that
starts at zero goes through

    S <- exp(g_t) S;  u_t = beta_t (v_t - S^T k_t);  S <- S + k_t u_t^T;
    o_t = S^T q_t

(`recurrent_gated_delta_rule` below: one token at a time, for the tests).
`gated_delta_rule` computes the same outputs C tokens at a time (section 3
of the paper, the WY form). With b_i the running sum of g inside a chunk
and S_0 the state the chunk starts from,

    A_ij = beta_i exp(b_i - b_j) k_i.k_j   for j < i, else 0
    (I + A) U_0 = beta * V                  one unit-lower-triangular solve,
    (I + A) W   = beta * exp(b) * K         both right-hand sides at once
    u   = U_0 - W S_0
    O   = (exp(b) * Q) S_0 + (Q K^T * M) u  M_ij = exp(b_i - b_j), j <= i
    S_C = exp(b_C) S_0 + (exp(b_C - b) * K)^T u

so everything inside a chunk is matrix products, computed for all chunks at
once, and a `lax.scan` over the chunks carries S alone. Every exponent is of
a difference b_i - b_j with j <= i, or of b itself: none is positive, and
nothing is divided by a decay, so a strong decay underflows to zero and
does nothing worse.

Precision: products take their operands in the inputs' type (bf16 in the
benchmark's cells) and accumulate in float32; g, b, beta, every decay, the
matrix A, the solve and the carried state are float32; u and the state are
rounded to the inputs' type only as operands of a product.

The backward pass is JAX's own, through the products, the solve and the scan
over chunks: per chunk the scan saves its state on entry (dk x dv float32 a
head) and u; nothing is saved per token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 64


def _chunk_size(chunk: int) -> int:
    if chunk < 1:
        raise ValueError(f"gated_delta_rule: chunk {chunk}")
    return chunk


def chunks_of(seq: int, chunk: int = CHUNK) -> int:
    """Chunks a sequence of `seq` tokens takes: the last one padded."""
    return -(-seq // _chunk_size(chunk))


def chunked_over_recurrent_macs(key_dim: int, value_dim: int,
                                chunk: int = CHUNK) -> float:
    """Multiply-adds a token a head of the chunked form's matrix products
    (K K^T, Q K^T, W S, (exp(b) Q) S, (Q K^T) u, K^T u, and the solve counted
    as a product with each right-hand side) over the recurrent form's
    3 dk dv: what the chunks cost beside the recurrence they replace."""
    dk, dv, c = key_dim, value_dim, _chunk_size(chunk)
    chunked = 2 * c * dk + c * (dk + dv) / 2 + 3 * dk * dv + c * dv
    return chunked / (3 * dk * dv)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = CHUNK):
    """o_t = S_t^T q_t of the gated delta rule, S_0 = 0, in chunks of
    `chunk` tokens.

    q, k: (B, H, S, dk); v: (B, H, S, dv); g (log decay, <= 0) and beta:
    (B, H, S), float32. q and k come normalised and scaled as the caller
    wants them. Returns (B, H, S, dv) in v's type. A length that is no
    multiple of the chunk is padded with rows of g = 0, beta = 0, which
    leave the state alone."""
    c = _chunk_size(chunk)
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    dt = v.dtype
    n = chunks_of(S, c)
    pad = n * c - S
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for x in (q, k, v))
        g, beta = (jnp.pad(x, ((0, 0), (0, 0), (0, pad))) for x in (g, beta))
    q, k, v = (x.reshape(B, H, n, c, x.shape[-1]) for x in (q, k, v))
    g = g.astype(jnp.float32).reshape(B, H, n, c)
    beta = beta.astype(jnp.float32).reshape(B, H, n, c)

    def mm(spec, a, b):
        return jnp.einsum(spec, a.astype(dt), b.astype(dt),
                          preferred_element_type=jnp.float32)

    b = jnp.cumsum(g, axis=-1)                         # (B, H, n, c)
    # exp(b_i - b_j) where j <= i; the exponent is masked, not the result:
    # above the diagonal it is positive and may overflow
    rows, cols = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    decay = jnp.exp(jnp.where(rows >= cols,
                              b[..., :, None] - b[..., None, :], -jnp.inf))
    kk = mm("bhnik,bhnjk->bhnij", k, k)
    a = jnp.where(rows > cols, beta[..., None] * decay * kk, 0.0)
    rhs = jnp.concatenate(
        [beta[..., None] * jnp.exp(b)[..., None] * k.astype(jnp.float32),
         beta[..., None] * v.astype(jnp.float32)], axis=-1)
    solved = lax.linalg.triangular_solve(
        a + jnp.eye(c, dtype=jnp.float32), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    w, u0 = solved[..., :dk], solved[..., dk:]
    attn = mm("bhnik,bhnjk->bhnij", q, k) * decay      # Q K^T * M
    q_in = q.astype(jnp.float32) * jnp.exp(b)[..., None]
    last = b[..., -1:]                                 # b_C
    k_out = k.astype(jnp.float32) * jnp.exp(last - b)[..., None]

    def step(state, xs):
        w, u0, attn, q_in, k_out, last = xs
        u = u0 - mm("bhik,bhkv->bhiv", w, state)
        o = mm("bhik,bhkv->bhiv", q_in, state) + mm("bhij,bhjv->bhiv",
                                                    attn, u)
        state = jnp.exp(last)[..., None] * state \
            + mm("bhik,bhiv->bhkv", k_out, u)
        return state, o.astype(dt)

    # the products' operands are rounded to the inputs' type once, here,
    # so that the scan saves them for its backward pass in that type
    w, attn, q_in, k_out = (x.astype(dt) for x in (w, attn, q_in, k_out))
    per_chunk = tuple(jnp.moveaxis(x, 2, 0)
                      for x in (w, u0, attn, q_in, k_out, last))
    _, o = lax.scan(step, jnp.zeros((B, H, dk, dv), jnp.float32), per_chunk)
    o = jnp.moveaxis(o, 0, 2).reshape(B, H, n * c, dv)
    return o[:, :, :S] if pad else o


def recurrent_gated_delta_rule(q, k, v, g, beta):
    """The same outputs one token at a time, all in float32: the recurrence
    as it is written at the top, for the tests of the chunked form."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))

    def step(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = jnp.exp(g_t)[..., None, None] * state
        u = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state,
                                                  k_t))
        state = state + k_t[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    B, H, _, dk = q.shape
    per_token = tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, beta))
    with jax.default_matmul_precision("highest"):
        _, o = lax.scan(step, jnp.zeros((B, H, dk, v.shape[-1]), f32),
                        per_token)
    return jnp.moveaxis(o, 0, 2).astype(v.dtype)
