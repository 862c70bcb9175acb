"""ResNet-50/101/152 through `horovod_tpu.models.resnet`."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.harness import seeds
from benchmark.reference import resnet as reference
from horovod_tpu.models import resnet

SAMPLE = "images"
CHECK_IMAGES = 8

#: Agreement with the float32 reference on the same weights, in training
#: mode on `CHECK_IMAGES` images. bf16 activations (rounding step 2^-8)
#: through 53 convolutions, each followed by a normalisation over only 8
#: images' statistics, which amplifies rounding where a channel's variance
#: over the small batch is small: measured on the v5e, see PERF.md, Findings.
#: An 8-bit float (step 2^-4) would be out by several times the tolerance.
LOGITS_RMS_TOL = 48 * 2.0 ** -8
LOSS_RTOL = 8 * 2.0 ** -8


def _dtype(config: dict):
    return jnp.dtype(config["program"]["dtype"])


def init_state(config: dict, key):
    """(params, batch_stats) as the program makes them."""
    return resnet.init(key, depth=config["depth"],
                       num_classes=config["num_classes"],
                       dtype=_dtype(config))


def loss(config: dict, params, stats, batch, axis_name=None):
    """(loss, new_stats) of a training step's forward pass."""
    return resnet.loss_fn(params, stats, batch, depth=config["depth"],
                          train=True, axis_name=axis_name)


def make_batch(config: dict, key, n: int):
    kx, ky = jax.random.split(key)
    size = config["image_size"]
    x = jax.random.normal(kx, (n, size, size, config["image_channels"]),
                          _dtype(config))
    y = jax.random.randint(ky, (n,), 0, config["num_classes"], jnp.int32)
    return x, y


def samples_per_step(traffic: dict, chips: int) -> int:
    return traffic["per_chip_batch"] * chips


def conv_shapes(config: dict) -> list:
    """(out_size, kernel, cin, cout) of every convolution, in order."""
    size = config["image_size"] // 2
    convs = [(size, 7, config["image_channels"], config["stem_channels"])]
    size //= 2                                   # the max-pool
    cin = config["stem_channels"]
    for stage, (blocks, width) in enumerate(zip(config["stage_blocks"],
                                                config["stage_widths"])):
        cout = width * config["expansion"]
        for block in range(blocks):
            stride = 2 if (block == 0 and stage > 0) else 1
            convs.append((size, 1, cin, width))
            size //= stride                      # v1.5: the 3x3 strides
            convs.append((size, 3, width, width))
            convs.append((size, 1, width, cout))
            if block == 0:
                convs.append((size, 1, cin, cout))
            cin = cout
    return convs


def flops_per_sample(config: dict, traffic: dict) -> float:
    """Model FLOPs per image of one training step: convolutions and the
    classifier, forward and backward (2 x forward), a multiply-add counted
    as 2. Normalisation, activations and pooling are not counted."""
    forward = sum(2 * size * size * k * k * cin * cout
                  for size, k, cin, cout in conv_shapes(config))
    final = config["stage_widths"][-1] * config["expansion"]
    forward += 2 * final * config["num_classes"]
    return 3.0 * forward


def reference_weights(config: dict, params) -> dict:
    f32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    blocks = []
    for stage, n in enumerate(config["stage_blocks"]):
        for block in range(n):
            p = f32[f"s{stage}b{block}"]
            w = {"stride": 2 if (block == 0 and stage > 0) else 1}
            for i in ("1", "2", "3"):
                w["conv" + i] = p["conv" + i]
                w["g" + i] = p["bn" + i]["scale"]
                w["b" + i] = p["bn" + i]["bias"]
            if "proj" in p:
                w["proj"] = p["proj"]
                w["gp"], w["bp"] = p["bnp"]["scale"], p["bnp"]["bias"]
            blocks.append(w)
    return {"stem": {"conv": f32["stem"]["conv"],
                     "g": f32["stem"]["bn"]["scale"],
                     "b": f32["stem"]["bn"]["bias"]},
            "blocks": blocks, "fc_w": f32["fc"]["w"], "fc_b": f32["fc"]["b"]}


def check_reference(config: dict, params, stats, seed) -> dict:
    """The program's training-mode forward pass on `CHECK_IMAGES` seeded
    images against the reference's on the same weights. `seed` is what
    `seeds.argument` made of the run's."""
    def compare(params, stats, seed):
        x, y = make_batch(config, seeds.key(seed, seeds.CHECK), CHECK_IMAGES)
        got, _ = resnet.apply(params, stats, x, depth=config["depth"],
                              train=True)
        got = got.astype(jnp.float32)
        want = reference.logits(reference_weights(config, params),
                                x.astype(jnp.float32))
        rms = jnp.sqrt(jnp.mean(jnp.square(got - want))
                       / jnp.mean(jnp.square(want)))
        return (rms, reference.classification_loss(got, y),
                reference.classification_loss(want, y))

    rms, got, want = (float(v) for v in
                      jax.jit(compare)(params, stats, seed))
    ok = rms <= LOGITS_RMS_TOL and abs(got - want) <= LOSS_RTOL * abs(want)
    return {"ok": bool(ok),
            "detail": f"logits rms error {rms:.3e} of their rms (tolerance "
                      f"{LOGITS_RMS_TOL:.3e}); loss {got:.6f} against the "
                      f"reference's {want:.6f} (rtol {LOSS_RTOL:.3e})"}
