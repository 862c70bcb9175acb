"""ResNet-50/101/152 (He et al., arXiv:1512.03385, Table 1) in training mode:
7x7/2 stem, 3x3/2 max-pool, four stages of bottleneck blocks
(1x1, 3x3, 1x1 with an expansion of 4), batch normalisation with the
batch's own statistics after every convolution, global average pool, linear
classifier. Float32 throughout, NHWC.

Departures from the paper, as the configuration file lists them and as
`horovod_tpu/models/resnet.py` computes: the stride of a stage's first block
sits on its 3x3 convolution ("v1.5", as in torchvision, which the upstream
job runs), not on its first 1x1; and every window is padded as XLA's "SAME"
pads it, which at stride 2 on an even size puts the odd pixel of padding
after the data (stem (2, 3), 3x3/2 and the max-pool (0, 1)) where the paper's
symmetric padding puts it before: the same operations on windows shifted by
one pixel.

Weights, as the family hands them over (all float32):
    stem: {conv (7,7,3,64), g, b}
    blocks: a list, in order, of dicts with stride and conv1 g1 b1 conv2 g2
            b2 conv3 g3 b3 and, for a stage's first block, proj gp bp
    fc_w (2048, classes)  fc_b (classes,)
"""

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5


def conv(x, w, stride=1):
    return lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)


def batch_norm(x, g, b):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + BN_EPS) * g + b


def logits(weights, images):
    """images: (N, H, W, 3) float32 -> (N, classes) float32."""
    with jax.default_matmul_precision("highest"):
        stem = weights["stem"]
        x = conv(images, stem["conv"], stride=2)
        x = jax.nn.relu(batch_norm(x, stem["g"], stem["b"]))
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
        for w in weights["blocks"]:
            y = jax.nn.relu(batch_norm(conv(x, w["conv1"]), w["g1"], w["b1"]))
            y = conv(y, w["conv2"], stride=w["stride"])
            y = jax.nn.relu(batch_norm(y, w["g2"], w["b2"]))
            y = batch_norm(conv(y, w["conv3"]), w["g3"], w["b3"])
            if "proj" in w:
                shortcut = batch_norm(
                    conv(x, w["proj"], stride=w["stride"]), w["gp"], w["bp"])
            else:
                shortcut = x
            x = jax.nn.relu(y + shortcut)
        x = jnp.mean(x, axis=(1, 2))
        return x @ weights["fc_w"] + weights["fc_b"]


def classification_loss(logits_, labels):
    logp = jax.nn.log_softmax(logits_.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
