"""GPT-2-architecture decoders through `horovod_tpu.models.transformer`."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import gpt2_block as reference
from horovod_tpu.models import transformer as tfm

SAMPLE = "tokens"

#: Agreement with the float32 reference on the same weights. The program
#: computes in bf16 (8 bits of mantissa, a relative rounding step of 2^-8)
#: through 2 + 8 roundings a layer on the residual path, so the logits'
#: root-mean-square error is a few such steps of their own root mean square;
#: measured on the v5e at L12 D2048 S2048: see PERF.md, Findings. A program
#: that computed in an 8-bit float (step 2^-4 or 2^-3) would be out by ten
#: times the tolerance. The mean loss over thousands of tokens averages the
#: rounding out and is held much closer.
LOGITS_RMS_TOL = 8 * 2.0 ** -8
LOSS_RTOL = 2.0 ** -10


def transformer_config(config: dict) -> tfm.TransformerConfig:
    program = config["program"]
    return tfm.TransformerConfig(
        vocab=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], d_ff=config["n_inner"],
        n_layers=config["n_layer"], max_seq=config["n_positions"],
        attn=program["attn"], dtype=jnp.dtype(program["dtype"]),
        remat=program["remat"])


def samples_per_step(traffic: dict, chips: int) -> int:
    return traffic["per_chip_batch"] * traffic["seq_len"] * chips


def flops_per_sample(config: dict, traffic: dict) -> float:
    """Model FLOPs per token of one training step: what the forward and
    backward passes require (backward = 2 x forward), a multiply-add
    counted as 2, recomputation not counted, and of the attention scores
    only the causal half, which is all the mathematics needs."""
    d, f, v, layers = (config["n_embd"], config["n_inner"],
                       config["vocab_size"], config["n_layer"])
    seq = traffic["seq_len"]
    projections = 2 * 4 * d * d            # wq, wk, wv, wo
    mlp = 2 * 2 * d * f
    attention = 2 * 2 * d * (seq + 1) / 2  # q.k and p.v, keys 1..position
    forward = layers * (projections + mlp + attention) + 2 * d * v
    return 3.0 * forward


def flash_kernel_shape(config: dict, traffic: dict) -> tuple:
    """(batch, heads, seq, head_dim) of one flash-attention call on a chip."""
    return (traffic["per_chip_batch"], config["n_head"], traffic["seq_len"],
            config["n_embd"] // config["n_head"])


def reference_weights(params) -> dict:
    """The program's parameter tree (layers stacked on a leading axis) as the
    reference's weights, float32."""
    f32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    lp = f32["layers"]
    names = {"ln1_g": "ln1_scale", "ln1_b": "ln1_bias", "ln2_g": "ln2_scale",
             "ln2_b": "ln2_bias", "wq": "wq", "wk": "wk", "wv": "wv",
             "wo": "wo", "w_fc": "w1", "b_fc": "b1", "w_proj": "w2",
             "b_proj": "b2"}
    n_layers = lp["wq"].shape[0]
    return {"wte": f32["embed"], "wpe": f32["pos"],
            "lnf_g": f32["lnf_scale"], "lnf_b": f32["lnf_bias"],
            "head": f32["unembed"],
            "layers": [{ref: lp[ours][i] for ref, ours in names.items()}
                       for i in range(n_layers)]}


@jax.jit
def _compare(params, tokens, system_logits):
    targets = jnp.roll(tokens, -1, axis=1)
    want = reference.logits(reference_weights(params), tokens)
    got = system_logits.astype(jnp.float32)
    rms = jnp.sqrt(jnp.mean(jnp.square(got - want))
                   / jnp.mean(jnp.square(want)))
    return (rms, reference.next_token_loss(got, targets),
            reference.next_token_loss(want, targets))


def check_logits(params, tokens, system_logits) -> dict:
    """Compares the program's logits for `tokens` with the reference's on
    the same weights. All three arguments sit on one device."""
    rms, got, want = (float(x) for x in
                      _compare(params, tokens, system_logits))
    ok = rms <= LOGITS_RMS_TOL and abs(got - want) <= LOSS_RTOL * abs(want)
    return {"ok": bool(ok),
            "detail": f"logits rms error {rms:.3e} of their rms (tolerance "
                      f"{LOGITS_RMS_TOL:.3e}); loss {got:.6f} against the "
                      f"reference's {want:.6f} (rtol {LOSS_RTOL:.3e})"}
