"""The token mixers of a layer of `models/transformer.py`, each in one place:
the leaves it has under a configuration, its `apply`, and what it refuses of
a configuration and a mesh. `MIXERS` holds them by the `attention` a layer's
configuration states ("mha", "mla", "gdn", "kda" of `TransformerConfig`, and
what the kinds of `transformer.LAYER_KINDS` make of it: "ssm", "mamba2",
"gmu", "cross", "shortconv").

An `apply` takes (h: the normed residual (B, S_loc, D), lp: the layer's
leaves, cfg, rope: the rotation's (cos, sin) or None, shared: what an earlier
segment handed on, {"memory", "kv"}, depth: the layer's index in the model)
and returns (this rank's heads' part of the output, (B, S_loc, D), before the
sum over `tp` and the output bias; what the layer may hand on, or None)."""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.common.exceptions import HorovodTpuError
from horovod_tpu.models.leaves import (
    Leaf, Part, fan_in, normal, ones, step_bias, zeros)
from horovod_tpu.parallel import ulysses as ulysses_mod
from horovod_tpu.parallel.ring_attention import (
    blockwise_attention_reference, ring_attention)

_HEADS = (None, "tp", None)       # a projection to heads: (D, heads, width)
_PER_HEAD = ("tp", None)          # a bias or a scale a head: (heads, width)


def rms(x, scale, eps=1e-5):
    """RMSNorm: x * rsqrt(mean(x^2) + eps) * scale, statistics in float32."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * lax.rsqrt(ms + eps)).astype(x.dtype) * scale


def _qk_norm(x, scale, eps=1e-5):
    """RMSNorm of the projected queries or keys x: (B, H_loc, S, dh) over
    the whole projected vector, all heads of all `tp` ranks; scale: (H_loc,
    dh)."""
    xf = x.astype(jnp.float32)
    ss = lax.psum(jnp.sum(jnp.square(xf), axis=(1, 3), keepdims=True), "tp")
    width = x.shape[1] * x.shape[3] * lax.axis_size("tp")
    return (xf * lax.rsqrt(ss / width + eps)).astype(x.dtype) \
        * scale[None, :, None, :]


def rope_angles(positions, head_dim: int, theta: float, yarn=None):
    """(cos, sin), each (S, head_dim / 2) float32, of the rotary embedding
    at `positions`: pair i turns by position * theta^(-2i / head_dim), or by
    position * `yarn`'s corrected frequency (a `transformer.Yarn`), cos and
    sin then times its `rotation_factor`."""
    half = head_dim // 2
    if yarn is None:
        freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        factor = 1.0
    else:
        freq = jnp.asarray(yarn.frequencies(head_dim, theta))
        factor = yarn.rotation_factor
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return (cos, sin) if factor == 1.0 else (cos * factor, sin * factor)


def rope(x, angles):
    """Rotates x: (B, H, S, dh) in the rotate-half pairing (i, i + dh/2)."""
    cos, sin = angles
    half = x.shape[-1] // 2
    a = x[..., :half].astype(jnp.float32)
    b = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _attend(q, k, v, cfg):
    """Causal attention of q: (B, H_loc, S_loc, dq), k: (B, G_loc, S_loc,
    dq) and v: (B, G_loc, S_loc, dv) by the algorithm `cfg.attn` names;
    over the last `cfg.window` keys where there is a window."""
    # the default scale, (the keys' width)^-1/2, is left to each algorithm
    scale = {} if cfg.score_scale is None else {"scale": cfg.score_scale}
    banded = cfg.window or q.shape[1] != k.shape[1]
    if banded and cfg.attn not in ("flash", "local"):
        raise HorovodTpuError(
            f"a window or fewer key heads than query heads: attn="
            f"{cfg.attn!r} cannot run them; use 'flash' or 'local'")
    if cfg.attention == "mla" and cfg.attn not in ("flash", "local"):
        # ring and Ulysses attention build their buffers and exchanges from
        # one head width
        raise HorovodTpuError(
            f"attention='mla' has keys and values of different widths: "
            f"attn={cfg.attn!r} cannot run it; use 'flash' or 'local'")
    if cfg.attn == "ring":
        return ring_attention(q, k, v, "sp", causal=True, **scale)
    if cfg.attn == "ulysses":
        return ulysses_mod.ulysses_attention(q, k, v, "sp", causal=True,
                                             **scale)
    if cfg.attn == "flash":
        # Pallas flash kernel (ops/flash_attention.py) computes
        # shard-LOCAL attention; silently wrong under a sequence-sharded
        # mesh, so refuse — sharded sequences ride ring/Ulysses.
        if lax.axis_size("sp") > 1:
            raise HorovodTpuError(
                "attn='flash' requires sp=1 (shard-local attention); use "
                "attn='ring' or 'ulysses' for sequence parallelism")
        from horovod_tpu.ops.flash_attention import flash_attention
        if not cfg.window:
            return flash_attention(q, k, v, causal=True, **scale)
        # a scope of their own inside `attn.attend`: a windowed layer's
        # kernels have the shapes of a full layer's, and a reader of the
        # compiled step tells them apart by this name alone
        with jax.named_scope("attn.window"):
            return flash_attention(q, k, v, causal=True, window=cfg.window,
                                   **scale)
    if banded:
        from horovod_tpu.ops.flash_attention import (
            masked_attention_reference)
        return masked_attention_reference(
            q, k, v, True, cfg.score_scale, cfg.window or None)
    return blockwise_attention_reference(q, k, v, causal=True, **scale)


def _queries(cfg, width: int) -> Dict[str, Leaf]:
    return {"wq": Leaf((cfg.d_model, cfg.n_heads, width),
                       fan_in("k", 0, cfg.d_model), _HEADS)}


def _out_projection(cfg, heads: int, width: int) -> Dict[str, Leaf]:
    return {"wo": Leaf((heads, width, cfg.d_model),
                       fan_in("k", 3, heads * width), ("tp", None, None))}


# ---- plain attention ("mha"), and "cross": the same on another layer's keys
# ---- and values, without its own

def _mha_leaves(cfg) -> Dict[str, Leaf]:
    """wq, wk, wv of one head width and wo; biases on the four with
    `attention_bias`; the scales of QK-norm; differential attention's
    lambdas and the scale of its norm."""
    D, H, G, d = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    own_keys = cfg.attention != "cross"
    leaves = {**_queries(cfg, d), **_out_projection(cfg, H, d)}
    if own_keys:
        leaves["wk"] = Leaf((D, G, d), fan_in("k", 1, D), _HEADS)
        leaves["wv"] = Leaf((D, G, d), fan_in("k", 2, D), _HEADS)
    if cfg.attention_bias:
        leaves["bq"] = Leaf((H, d), normal("m", 7, 0.02), _PER_HEAD)
        leaves["bo"] = Leaf((D,), normal("m", 10, 0.02))
        if own_keys:
            leaves["bk"] = Leaf((G, d), normal("m", 8, 0.02), _PER_HEAD)
            leaves["bv"] = Leaf((G, d), normal("m", 9, 0.02), _PER_HEAD)
    if cfg.qk_norm == "head":     # one scale of a head's width for all heads
        leaves["q_scale"] = Leaf((d,), ones)
        if own_keys:
            leaves["k_scale"] = Leaf((d,), ones)
    elif cfg.qk_norm:
        leaves["q_scale"] = Leaf((H, d), ones, _PER_HEAD)
        if own_keys:
            leaves["k_scale"] = Leaf((H, d), ones, _PER_HEAD)
    if cfg.diff_attention:
        for place, name in enumerate(("lambda_q1", "lambda_k1", "lambda_q2",
                                      "lambda_k2")):
            leaves[name] = Leaf((d,), normal("m", 11 + place, 0.1))
        leaves["subln_scale"] = Leaf((2 * d,), ones)
    return leaves


def _projected(h, lp: Dict[str, Any], w: str, b: str):
    """h through the heads of lp[w], with the bias lp[b] where there is
    one: (B, heads, S, width)."""
    y = jnp.einsum("bsd,dhk->bhsk", h, lp[w])
    return y + lp[b][None, :, None, :] if b in lp else y


def _paired(x):
    """The even and the odd heads of x: (B, 2P, S, d), each (B, P, S, d)."""
    batch, heads, seq, width = x.shape
    x = x.reshape(batch, heads // 2, 2, seq, width)
    return x[:, :, 0], x[:, :, 1]


def _diff_keys_values(h, lp: Dict[str, Any]):
    """What differential attention reads of a layer: the even key heads, the
    odd ones, (B, Q, S, d) each, and each pair's two value heads side by
    side, (B, Q, S, 2d)."""
    k1, k2 = _paired(_projected(h, lp, "wk", "bk"))
    return k1, k2, jnp.concatenate(_paired(_projected(h, lp, "wv", "bv")),
                                   axis=-1)


def _diff_attention(h, lp: Dict[str, Any], cfg, depth, kv=None):
    """Differential attention (arXiv:2410.05258) on h: (B, S, D): query pair
    i = heads (2i, 2i + 1) reads K/V pair i // (P / Q); the pair's output is
    (1 - l0) RMSNorm(a1 - lam a2) over its 2d values, lam = exp(lq1 . lk1)
    - exp(lq2 . lk2) + l0 with l0 = 0.8 - 0.6 exp(-0.3 depth). `kv`:
    another layer's `_diff_keys_values` (a "cross" layer); None: this
    layer's own. Hands on the keys and values read."""
    f32 = jnp.float32
    with jax.named_scope("attn.project"):
        q1, q2 = _paired(_projected(h, lp, "wq", "bq"))
        if kv is None:
            kv = _diff_keys_values(h, lp)
    with jax.named_scope("attn.attend"):
        k1, k2, vv = kv
        a1, a2 = _attend(q1, k1, vv, cfg), _attend(q2, k2, vv, cfg)
        l0 = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, f32))
        lam = jnp.exp(jnp.sum(lp["lambda_q1"].astype(f32)
                              * lp["lambda_k1"].astype(f32))) \
            - jnp.exp(jnp.sum(lp["lambda_q2"].astype(f32)
                              * lp["lambda_k2"].astype(f32))) + l0
        a = a1.astype(f32) - lam * a2.astype(f32)
        a = (a * lax.rsqrt(jnp.mean(jnp.square(a), axis=-1, keepdims=True)
                           + cfg.rms_norm_eps)
             * lp["subln_scale"].astype(f32) * (1.0 - l0)).astype(h.dtype)
    with jax.named_scope("attn.out"):
        # pair i is heads 2i and 2i + 1 of the output projection
        wo = lp["wo"].reshape(a.shape[1], a.shape[3], -1)
        return jnp.einsum("bpsk,pkd->bsd", a, wo), kv


def _mha(h, lp: Dict[str, Any], cfg, rope_, shared, depth):
    if cfg.diff_attention:
        return _diff_attention(
            h, lp, cfg, depth,
            shared["kv"] if cfg.attention == "cross" else None)
    with jax.named_scope("attn.project"):
        q = _projected(h, lp, "wq", "bq")
        k = _projected(h, lp, "wk", "bk")
        v = _projected(h, lp, "wv", "bv")
        if cfg.qk_norm:
            # "head": each head over its own width, one scale for all
            normed = rms if cfg.qk_norm == "head" else _qk_norm
            q = normed(q, lp["q_scale"], cfg.rms_norm_eps)
            k = normed(k, lp["k_scale"], cfg.rms_norm_eps)
        if rope_ is not None:
            q, k = rope(q, rope_), rope(k, rope_)
    with jax.named_scope("attn.attend"):
        a = _attend(q, k, v, cfg)
    with jax.named_scope("attn.out"):
        return jnp.einsum("bhsk,hkd->bsd", a, lp["wo"]), None


def _mha_checks(cfg, ax):
    odd_width = cfg.d_head and cfg.d_head * cfg.n_heads != cfg.d_model
    return [
        (cfg.attention != "cross" or cfg.diff_attention,
         "'cross' layers are differential attention's (diff_attention)"),
        # a head width of its own runs where it is tested: no test takes
        # it through ring or Ulysses attention or shards such heads
        (not odd_width or cfg.attn in ("flash", "local"),
         f"d_head * n_heads != d_model needs attn 'flash' or 'local', not "
         f"{cfg.attn!r}"),
        (not odd_width or ax["sp"] == ax["tp"] == ax["pp"] == 1,
         "d_head * n_heads != d_model requires sp=tp=pp=1 (no mesh test "
         "shards heads of a width of their own)"),
        (cfg.n_heads % cfg.kv_heads == 0, "n_heads % n_kv_heads"),
        (cfg.qk_norm in (False, True, "head"),
         f"qk_norm={cfg.qk_norm!r}: choose False, True (over the whole "
         "projected vector) or 'head' (each head's own)"),
        # each head norms itself, so heads could be sharded; no mesh test
        # shards them yet
        (cfg.qk_norm != "head" or ax["tp"] == 1,
         "qk_norm='head' requires tp=1 (no mesh test shards its heads)")]


# ---- "mla": DeepSeek-V2's latent attention

def _mla_leaves(cfg) -> Dict[str, Leaf]:
    """The latent's down-projection and norm belong to no head: they are
    whole on every tp rank, as the router is."""
    D, latent, nope = cfg.d_model, cfg.kv_latent, cfg.qk_nope_dim
    return {
        **_queries(cfg, nope + cfg.qk_rope_dim),
        "wkv_a": Leaf((D, latent + cfg.qk_rope_dim), fan_in("x", 0, D)),
        "kv_scale": Leaf((latent,), ones),
        "wkv_b": Leaf((latent, cfg.n_heads, nope + cfg.v_head_dim),
                      fan_in("x", 1, latent), _HEADS),
        **_out_projection(cfg, cfg.n_heads, cfg.v_head_dim)}


def _mla(h, lp: Dict[str, Any], cfg, rope_, shared, depth):
    """On the normed residual h: (B, S_loc, D): the keys' second part is one
    per token, shared by the heads; it and the queries' second part are
    rotated, or with `rope_` None (a NoPE layer: a kind `cfg.unrotated`
    names, or positions "none") left as they are projected."""
    nope, latent = cfg.qk_nope_dim, cfg.kv_latent
    with jax.named_scope("mla.project"):
        q = jnp.einsum("bsd,dhk->bhsk", h, lp["wq"])
        down = jnp.einsum("bsd,dc->bsc", h, lp["wkv_a"])
        c = rms(down[..., :latent], lp["kv_scale"], cfg.rms_norm_eps)
        kv = jnp.einsum("bsc,chk->bhsk", c, lp["wkv_b"])
        k_nope, v = kv[..., :nope], kv[..., nope:]
    with jax.named_scope("mla.rope"):
        # one such key a token, shared by all heads
        k_pe = down[:, None, :, latent:]
        if rope_ is not None:
            k_pe = rope(k_pe, rope_)
            q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], rope_)],
                                axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:3]
                                      + k_pe.shape[3:])], axis=-1)
    with jax.named_scope("mla.attend"):
        a = _attend(q, k, v, cfg)
    with jax.named_scope("mla.out"):
        return jnp.einsum("bhsk,hkd->bsd", a, lp["wo"]), None


def _mla_checks(cfg, ax):
    return [
        (not cfg.attention_bias,
         "attention_bias is plain attention's: attention='mla' has no "
         "biases"),
        (cfg.attn in ("flash", "local"),
         "attention='mla' needs attn 'flash' or 'local'"),
        (cfg.positions != "learned",
         "attention='mla' with positions='learned': the shared part of its "
         "keys is rotated (positions='rope') or left as it is (a kind "
         "`unrotated` names, or positions='none')"),
        (ax["sp"] == 1, "attention='mla' requires sp=1")]


# ---- "gdn": a Gated DeltaNet mixer (arXiv:2412.06464)

def _decay_rate(keys, shape, dtype):
    """A ~ U(0, 16), held as its logarithm (Gated DeltaNet's own draw)."""
    return jnp.log(jax.random.uniform(
        keys["g"][9], shape, jnp.float32, 1e-3, 16.0)).astype(dtype)


def _gdn_leaves(cfg) -> Dict[str, Leaf]:
    D, H, dk, dv, taps = (cfg.d_model, cfg.gdn_heads, cfg.gdn_key_dim,
                          cfg.gdn_value_dim, cfg.gdn_conv)
    return {
        "gdn_wq": Leaf((D, H, dk), fan_in("g", 0, D)),
        "gdn_wk": Leaf((D, H, dk), fan_in("g", 1, D)),
        "gdn_wv": Leaf((D, H, dv), fan_in("g", 2, D)),
        "gdn_wz": Leaf((D, H, dv), fan_in("g", 3, D)),
        "gdn_wa": Leaf((D, H), fan_in("g", 4, D)),
        "gdn_wb": Leaf((D, H), fan_in("g", 5, D)),
        "gdn_a_log": Leaf((H,), _decay_rate),
        "gdn_dt_bias": Leaf((H,), step_bias("g", 10)),
        "gdn_conv_q": Leaf((H, dk, taps), fan_in("g", 6, taps)),
        "gdn_conv_k": Leaf((H, dk, taps), fan_in("g", 7, taps)),
        "gdn_conv_v": Leaf((H, dv, taps), fan_in("g", 8, taps)),
        "gdn_o_scale": Leaf((dv,), ones),
        **_out_projection(cfg, H, dv)}


def _gdn(h, lp: Dict[str, Any], cfg, rope_, shared, depth):
    """On h: (B, S, D): the gated delta rule of `ops/gated_delta.py` on
    convolved, normalised queries and keys, its output normed per head,
    gated and projected."""
    from horovod_tpu.ops.causal_conv import causal_conv_silu
    from horovod_tpu.ops.gated_delta import gated_delta_rule
    with jax.named_scope("gdn.project"):
        q = jnp.einsum("bsd,dhk->bhsk", h, lp["gdn_wq"])
        k = jnp.einsum("bsd,dhk->bhsk", h, lp["gdn_wk"])
        v = jnp.einsum("bsd,dhk->bhsk", h, lp["gdn_wv"])
        z = jnp.einsum("bsd,dhk->bhsk", h, lp["gdn_wz"])
        a = jnp.einsum("bsd,dh->bhs", h, lp["gdn_wa"],
                       preferred_element_type=jnp.float32)
        b = jnp.einsum("bsd,dh->bhs", h, lp["gdn_wb"],
                       preferred_element_type=jnp.float32)
    with jax.named_scope("gdn.conv"):
        q = causal_conv_silu(q, lp["gdn_conv_q"],
                             l2_scale=cfg.gdn_key_dim ** -0.5)
        k = causal_conv_silu(k, lp["gdn_conv_k"], l2_scale=1.0)
        v = causal_conv_silu(v, lp["gdn_conv_v"])
    with jax.named_scope("gdn.scan"):
        beta = jax.nn.sigmoid(b) * (2.0 if cfg.gdn_neg_eigval else 1.0)
        rate = jnp.exp(lp["gdn_a_log"].astype(jnp.float32))[None, :, None]
        g = -rate * jax.nn.softplus(
            a + lp["gdn_dt_bias"].astype(jnp.float32)[None, :, None])
        o = gated_delta_rule(q, k, v, g, beta)
    with jax.named_scope("gdn.gate"):
        o = (rms(o, lp["gdn_o_scale"], cfg.rms_norm_eps).astype(jnp.float32)
             * jax.nn.silu(z.astype(jnp.float32))).astype(h.dtype)
    with jax.named_scope("gdn.out"):
        return jnp.einsum("bhsk,hkd->bsd", o, lp["wo"]), None


def _gdn_checks(cfg, ax):
    # a gated-delta-rule layer carries a state along the whole sequence
    # and holds all its heads: neither crosses shards yet
    return [
        (ax["sp"] == 1,
         "linear-attention layers require sp=1 (the state of the gated "
         "delta rule would have to cross the sequence's shards)"),
        (ax["tp"] == 1,
         "linear-attention layers require tp=1 (their heads are not "
         "sharded)")]


# ---- "kda": Kimi Delta Attention (arXiv:2510.26692 section 3), the delta
# ---- rule with a decay per key channel

def _kda_leaves(cfg) -> Dict[str, Leaf]:
    """"gdn"'s heads and convolutions (`gdn_heads`, `gdn_key_dim`,
    `gdn_value_dim`, `gdn_conv`); the decay's and the output gate's
    projections go through a rank of `kda_rank`; a_log a head, dt_bias a
    channel, drawn as Gated DeltaNet draws them."""
    D, H, dk, dv, taps, R = (cfg.d_model, cfg.gdn_heads, cfg.gdn_key_dim,
                             cfg.gdn_value_dim, cfg.gdn_conv, cfg.kda_rank)
    return {
        "kda_wq": Leaf((D, H, dk), fan_in("g", 0, D)),
        "kda_wk": Leaf((D, H, dk), fan_in("g", 1, D)),
        "kda_wv": Leaf((D, H, dv), fan_in("g", 2, D)),
        "kda_wf_down": Leaf((D, R), fan_in("g", 4, D)),
        "kda_wf_up": Leaf((R, H, dk), fan_in("x", 0, R)),
        "kda_wg_down": Leaf((D, R), fan_in("g", 3, D)),
        "kda_wg_up": Leaf((R, H, dv), fan_in("x", 1, R)),
        "kda_bg": Leaf((H, dv), zeros),
        "kda_wb": Leaf((D, H), fan_in("g", 5, D)),
        "kda_a_log": Leaf((H,), _decay_rate),
        "kda_dt_bias": Leaf((H, dk), step_bias("g", 10)),
        "kda_conv_q": Leaf((H, dk, taps), fan_in("g", 6, taps)),
        "kda_conv_k": Leaf((H, dk, taps), fan_in("g", 7, taps)),
        "kda_conv_v": Leaf((H, dv, taps), fan_in("g", 8, taps)),
        "kda_o_scale": Leaf((dv,), ones),
        **_out_projection(cfg, H, dv)}


def _kda(h, lp: Dict[str, Any], cfg, rope_, shared, depth):
    """On h: (B, S, D): the delta rule of `ops/gated_delta.py` with a decay
    per key channel on convolved, normalised queries and keys, its output
    normed per head, gated by a sigmoid and projected."""
    from horovod_tpu.ops.causal_conv import causal_conv_silu
    from horovod_tpu.ops.gated_delta import gated_delta_rule
    f32 = jnp.float32
    with jax.named_scope("kda.project"):
        q = jnp.einsum("bsd,dhk->bhsk", h, lp["kda_wq"])
        k = jnp.einsum("bsd,dhk->bhsk", h, lp["kda_wk"])
        v = jnp.einsum("bsd,dhk->bhsk", h, lp["kda_wv"])
        low_f = jnp.einsum("bsd,dr->bsr", h, lp["kda_wf_down"])
        low_g = jnp.einsum("bsd,dr->bsr", h, lp["kda_wg_down"])
        f = jnp.einsum("bsr,rhk->bhsk", low_f, lp["kda_wf_up"],
                       preferred_element_type=f32)
        z = jnp.einsum("bsr,rhk->bhsk", low_g, lp["kda_wg_up"])
        b = jnp.einsum("bsd,dh->bhs", h, lp["kda_wb"],
                       preferred_element_type=f32)
    with jax.named_scope("kda.conv"):
        q = causal_conv_silu(q, lp["kda_conv_q"],
                             l2_scale=cfg.gdn_key_dim ** -0.5)
        k = causal_conv_silu(k, lp["kda_conv_k"], l2_scale=1.0)
        v = causal_conv_silu(v, lp["kda_conv_v"])
    with jax.named_scope("kda.scan"):
        rate = jnp.exp(lp["kda_a_log"].astype(f32))[None, :, None, None]
        g = -rate * jax.nn.softplus(
            f + lp["kda_dt_bias"].astype(f32)[None, :, None, :])
        o = gated_delta_rule(q, k, v, g, jax.nn.sigmoid(b))
    with jax.named_scope("kda.gate"):
        gate = jax.nn.sigmoid(z.astype(f32)
                              + lp["kda_bg"].astype(f32)[None, :, None, :])
        o = (rms(o, lp["kda_o_scale"], cfg.rms_norm_eps).astype(f32)
             * gate).astype(h.dtype)
    with jax.named_scope("kda.out"):
        return jnp.einsum("bhsk,hkd->bsd", o, lp["wo"]), None


def _kda_checks(cfg, ax):
    return [(cfg.kda_rank > 0, "'kda' layers need kda_rank > 0"),
            (cfg.gdn_heads > 0, "'kda' layers need gdn_heads > 0")] \
        + _gdn_checks(cfg, ax)


# ---- "ssm": a Mamba-1 mixer (arXiv:2312.00752), and "gmu": a Gated Memory
# ---- Unit (arXiv:2507.06607) on what an "ssm" layer handed on

def _ssm_leaves(cfg) -> Dict[str, Leaf]:
    D, E, N, R = cfg.d_model, cfg.ssm_channels, cfg.ssm_state, cfg.dt_rank

    def rates(keys, shape, dtype):
        # A = -(1 .. N) per channel, held as its logarithm (Mamba's own)
        return jnp.broadcast_to(jnp.log(jnp.arange(
            1, N + 1, dtype=jnp.float32)), shape).astype(dtype)

    return {
        "ssm_w_in": Leaf((D, 2 * E), fan_in("m", 0, D)),
        "ssm_conv": Leaf((E, cfg.ssm_conv), fan_in("m", 1, cfg.ssm_conv)),
        "ssm_conv_bias": Leaf((E,), normal("m", 2, 0.02)),
        "ssm_w_x": Leaf((E, R + 2 * N), fan_in("m", 3, E)),
        "ssm_w_dt": Leaf((R, E), fan_in("m", 4, R)),
        "ssm_dt_bias": Leaf((E,), step_bias("m", 6)),
        "ssm_a_log": Leaf((E, N), rates),
        "ssm_d_skip": Leaf((E,), ones),
        "ssm_w_out": Leaf((E, D), fan_in("m", 5, E))}


def _conv_silu(u, taps, bias):
    """SiLU of the depthwise causal convolution of u: (B, S, E) over S with
    taps: (E, K) and a bias: (E,), zeros before the sequence's start; K
    shifted multiply-adds in float32 that the compiler fuses into one pass.
    (`ops/causal_conv.py` holds heads-major (B, H, S, d) arrays; a
    state-space layer's channels are token-major, and two transposes of the
    array would cost more than the convolution.)"""
    taps_n, seq = taps.shape[-1], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps_n - 1, 0), (0, 0)))
    y = bias.astype(jnp.float32) + sum(
        padded[:, j:j + seq].astype(jnp.float32)
        * taps[:, j].astype(jnp.float32) for j in range(taps_n))
    return jax.nn.silu(y).astype(u.dtype)


def _ssm(h, lp: Dict[str, Any], cfg, rope_, shared, depth):
    """On h: (B, S, D): the selective scan of `ops/selective_scan.py` on the
    convolved input, gated and projected. Hands on the scan's output y
    before the gate, (B, S, E): what a Gated Memory Unit reads."""
    from horovod_tpu.ops.selective_scan import selective_scan
    E, N, R = cfg.ssm_channels, cfg.ssm_state, cfg.dt_rank
    f32 = jnp.float32
    with jax.named_scope("ssm.project"):
        xz = jnp.einsum("bsd,dte->tbse", h,
                        lp["ssm_w_in"].reshape(-1, 2, E))
    with jax.named_scope("ssm.conv"):
        c = _conv_silu(xz[0], lp["ssm_conv"], lp["ssm_conv_bias"])
    with jax.named_scope("ssm.project"):
        low = jnp.einsum("bse,er->bsr", c, lp["ssm_w_x"])
        step = jnp.einsum("bsr,re->bse", low[..., :R], lp["ssm_w_dt"],
                          preferred_element_type=f32)
    with jax.named_scope("ssm.scan"):
        delta = jax.nn.softplus(step + lp["ssm_dt_bias"].astype(f32))
        y = selective_scan(c, delta, -jnp.exp(lp["ssm_a_log"].astype(f32)),
                           low[..., R:R + N], low[..., R + N:],
                           lp["ssm_d_skip"].astype(f32))
    with jax.named_scope("ssm.gate"):
        gated = (y.astype(f32) * jax.nn.silu(xz[1].astype(f32))).astype(
            h.dtype)
    with jax.named_scope("ssm.out"):
        return jnp.einsum("bse,ed->bsd", gated, lp["ssm_w_out"]), y


# ---- "mamba2": a Mamba-2 mixer (arXiv:2405.21060), the chunked state-space
# ---- dual scan of `ops/ssd_scan.py`

def _mamba2_leaves(cfg) -> Dict[str, Leaf]:
    """The input projection lies in two leaves, [z | x | B | C] and the
    step's, so that the step's product stays in float32; a published
    `in_proj` is the two side by side."""
    D, H, N, taps = cfg.d_model, cfg.ssd_heads, cfg.ssd_state, cfg.ssd_conv
    E = H * cfg.ssd_head_dim
    mixed = E + 2 * N            # what the convolution runs over: x, B, C

    def decay_rate(keys, shape, dtype):
        # A ~ U(1, 16), held as its logarithm (Mamba-2's own draw)
        return jnp.log(jax.random.uniform(
            keys["m"][7], shape, jnp.float32, 1.0, 16.0)).astype(dtype)

    return {
        "ssd_w_in": Leaf((D, E + mixed), fan_in("m", 0, D)),
        "ssd_w_dt": Leaf((D, H), fan_in("m", 3, D)),
        "ssd_conv": Leaf((mixed, taps), fan_in("m", 1, taps)),
        "ssd_conv_bias": Leaf((mixed,), normal("m", 2, 0.02)),
        "ssd_dt_bias": Leaf((H,), step_bias("m", 6)),
        "ssd_a_log": Leaf((H,), decay_rate),
        "ssd_d_skip": Leaf((H,), ones),
        "ssd_norm_scale": Leaf((E,), ones),
        "ssd_w_out": Leaf((E, D), fan_in("m", 5, E))}


def _mamba2(h, lp: Dict[str, Any], cfg, rope_, shared, depth):
    """On h: (B, S, D): [z | xBC] = h W_in and the step h W_dt; SiLU of the
    convolution over x, B and C together; the scan of `ops/ssd_scan.py`;
    the gate first, then an RMSNorm over all the channels held (one group);
    the output projection."""
    from horovod_tpu.ops.ssd_scan import ssd_scan
    N = cfg.ssd_state
    E = cfg.ssd_heads * cfg.ssd_head_dim
    f32 = jnp.float32
    with jax.named_scope("ssd.project"):
        zx = jnp.einsum("bsd,de->bse", h, lp["ssd_w_in"])
        step = jnp.einsum("bsd,dh->bsh", h, lp["ssd_w_dt"],
                          preferred_element_type=f32)
    with jax.named_scope("ssd.conv"):
        mixed = _conv_silu(zx[..., E:], lp["ssd_conv"], lp["ssd_conv_bias"])
    with jax.named_scope("ssd.scan"):
        delta = jax.nn.softplus(step + lp["ssd_dt_bias"].astype(f32))
        y = ssd_scan(mixed[..., :E], delta, lp["ssd_a_log"],
                     mixed[..., E:E + N], mixed[..., E + N:],
                     lp["ssd_d_skip"])
    with jax.named_scope("ssd.gate"):
        gated = y.astype(f32) * jax.nn.silu(zx[..., :E].astype(f32))
        gated = (gated * lax.rsqrt(
            jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
            + cfg.rms_norm_eps)).astype(h.dtype) * lp["ssd_norm_scale"]
    with jax.named_scope("ssd.out"):
        return jnp.einsum("bse,ed->bsd", gated, lp["ssd_w_out"]), None


def _mamba2_checks(cfg, ax):
    # the scan carries a state along the whole sequence, and the layer holds
    # all its heads and B, C whole: neither crosses shards or stages yet
    return [
        (cfg.ssd_heads > 0, "'mamba2' layers need ssd_heads > 0"),
        (ax["sp"] == 1,
         "state-space dual layers require sp=1 (the scan's state would have "
         "to cross the sequence's shards)"),
        (ax["tp"] == 1,
         "state-space dual layers require tp=1 (their heads are not "
         "sharded, and the gated norm runs over all of them)"),
        (ax["pp"] == 1,
         "state-space dual layers require pp=1 (they come in a layer "
         "pattern, which the pipeline schedule does not place)")]


# ---- "shortconv": a gated short convolution (the `conv` layers of the
# ---- `lfm2` and `lfm2_moe` families): two gates around a depthwise causal
# ---- convolution of a few taps, no activation, no state beyond their reach

def _shortconv_leaves(cfg) -> Dict[str, Leaf]:
    """The input projection to [B | C | X], each d_model wide, the taps a
    channel (the last on the token itself) and the output projection."""
    D, taps = cfg.d_model, cfg.shortconv_taps
    return {"sc_w_in": Leaf((D, 3 * D), fan_in("m", 0, D)),
            "sc_conv": Leaf((D, taps), fan_in("m", 1, taps)),
            "sc_w_out": Leaf((D, D), fan_in("m", 5, D))}


def short_conv_mix(bcx, taps):
    """C * conv(B * X) for bcx: (B, S, 3 E) = [B | C | X] and taps: (E, K):
    c_t = sum_j taps[:, j] z_(t - (K - 1) + j) with z = B * X and zeros
    before the sequence's start: K shifted multiply-adds over the token
    axis, token-major as the projection leaves it, in float32 and rounded
    once; no bias, no activation."""
    f32 = jnp.float32
    (width, taps_n), seq = taps.shape, bcx.shape[1]
    b, c, x = (bcx[..., i * width:(i + 1) * width].astype(f32)
               for i in range(3))
    padded = jnp.pad(b * x, ((0, 0), (taps_n - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + seq] * taps[:, j].astype(f32)
               for j in range(taps_n))
    return (c * conv).astype(bcx.dtype)


def _shortconv(h, lp: Dict[str, Any], cfg, rope_, shared, depth):
    """On h: (B, S, D): [B | C | X] = h W_in; (C * conv(B * X)) W_out."""
    with jax.named_scope("shortconv.project"):
        bcx = jnp.einsum("bsd,de->bse", h, lp["sc_w_in"])
    with jax.named_scope("shortconv.mix"):
        y = short_conv_mix(bcx, lp["sc_conv"])
    with jax.named_scope("shortconv.out"):
        return jnp.einsum("bse,ed->bsd", y, lp["sc_w_out"]), None


def _shortconv_checks(cfg, ax):
    # a token's convolution reads the tokens before it and the layer holds
    # all its channels: neither crosses shards or stages yet
    return [
        (cfg.shortconv_taps > 0, "'shortconv' layers need shortconv_taps > 0"),
        (ax["sp"] == 1,
         "short-convolution layers require sp=1 (a shard's first tokens "
         "read the last tokens of the shard before it)"),
        (ax["tp"] == 1,
         "short-convolution layers require tp=1 (their channels are not "
         "sharded)"),
        (ax["pp"] == 1,
         "short-convolution layers require pp=1 (they come in a layer "
         "pattern, which the pipeline schedule does not place)")]


def _gmu_leaves(cfg) -> Dict[str, Leaf]:
    D, E = cfg.d_model, cfg.ssm_channels
    return {"gmu_w1": Leaf((D, E), fan_in("m", 0, D)),
            "gmu_w2": Leaf((E, D), fan_in("m", 5, E))}


def _gmu(h, lp: Dict[str, Any], cfg, rope_, shared, depth):
    """On h: (B, S, D): the memory (B, S, E) an earlier state-space layer
    handed on, gated by h and projected."""
    with jax.named_scope("gmu.project"):
        gate = jnp.einsum("bsd,de->bse", h, lp["gmu_w1"])
    with jax.named_scope("gmu.gate"):
        gated = (jax.nn.silu(gate.astype(jnp.float32))
                 * shared["memory"].astype(jnp.float32)).astype(h.dtype)
    with jax.named_scope("gmu.out"):
        return jnp.einsum("bse,ed->bsd", gated, lp["gmu_w2"]), None


MIXERS = {
    "mha": Part(_mha_leaves, _mha, _mha_checks),
    "cross": Part(_mha_leaves, _mha, _mha_checks),
    "mla": Part(_mla_leaves, _mla, _mla_checks),
    "gdn": Part(_gdn_leaves, _gdn, _gdn_checks),
    "kda": Part(_kda_leaves, _kda, _kda_checks),
    "ssm": Part(_ssm_leaves, _ssm),
    "mamba2": Part(_mamba2_leaves, _mamba2, _mamba2_checks),
    "gmu": Part(_gmu_leaves, _gmu),
    "shortconv": Part(_shortconv_leaves, _shortconv, _shortconv_checks),
}
