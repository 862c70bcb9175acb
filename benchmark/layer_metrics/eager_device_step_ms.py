"""`device_step_ms` where it moves `eager_samples_per_s_per_chip`: a per-layer metric
names the one end-to-end metric it moves and is reported only where that
metric is, so the eager cells have this one under a name of their own."""

from benchmark.layer_metrics.device_step_ms import read  # noqa: F401
