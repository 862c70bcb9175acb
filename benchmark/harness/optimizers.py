"""The optimizer a traffic file names, with the arguments it gives and no
others: `{"name": "adam", "learning_rate": 0.001}`."""

import optax


def make(spec: dict, lr_scale: float = 1.0) -> optax.GradientTransformation:
    """`lr_scale` is the number of ranks where the job scales the learning
    rate with them, as upstream's synthetic benchmark does."""
    args = {k: v for k, v in spec.items() if k != "name"}
    args["learning_rate"] = args["learning_rate"] * lr_scale
    return getattr(optax, spec["name"])(**args)
