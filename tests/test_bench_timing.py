"""The bench's timing helpers (bench.py): a host clock over calls of a
device-side scan, behind `block_until_ready`. CPU, deterministic-ish:
we assert sanity properties (positive, right order of magnitude), not
exact values. The helpers are shared (scripts/profile_resnet.py imports
them), so their contracts get pinned here.
"""

import sys
import os


sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import bench  # noqa: E402


def test_scan_timed_positive_and_sane():
    # body: one matmul step on a small carry
    a = jnp.ones((64, 64), jnp.float32)

    def body(carry):
        x, n = carry
        return (jnp.tanh(x @ a), n + 1)

    sec = bench._scan_timed(body, (a, jnp.zeros(())), chain=4, reps=2,
                            warmup=2)
    assert 0 < sec < 1.0  # a 64x64 matmul step is micro/milliseconds


def test_eager_sizes_are_threshold_sensitive():
    """The CPU-mesh fusion sweep (bench.py --eager-cpu-mesh) only proves
    anything if its gradient set actually buckets differently across the
    swept thresholds — pin that property."""
    from horovod_tpu.ops.fusion import plan_buckets

    metas = [(s, "float32") for s in bench._EAGER_SIZES]
    counts = [len(plan_buckets(metas, mb * 1024 * 1024))
              for mb in (1, 4, 16, 64)]
    assert counts[0] > counts[1] > counts[2] >= counts[3] >= 1, counts


def test_fatal_error_exits_nonzero(monkeypatch, capsys):
    """bench.py on a fatal error still prints a parseable line naming it
    — and exits non-zero, so no caller can read the run as a result."""
    import json
    import runpy

    import pytest

    import horovod_tpu as hvd

    def boom():
        raise RuntimeError("synthetic fatal")

    monkeypatch.setattr(hvd, "init", boom)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    with pytest.raises(SystemExit) as exit_:
        runpy.run_path(bench.__file__, run_name="__main__")
    assert exit_.value.code == 1
    doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "synthetic fatal" in doc["extra"]["fatal"]
    assert doc["value"] == 0.0
