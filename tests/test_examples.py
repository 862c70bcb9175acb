"""Example scripts run end to end (subprocess, CPU mesh) — user-facing
entry points must not rot (the reference smoke-runs its examples in CI,
.buildkite/gen-pipeline.sh)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_example(name, *args, timeout=240):
    # (under `TEST_LIMIT_S`, tests/conftest.py: the child is reaped here, not
    # orphaned by the limit firing first)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    # (as `tests/conftest.py` compiles the suite's own stand-ins)
    env["JAX_DISABLE_MOST_OPTIMIZATIONS"] = "1"
    env["PYTHONPATH"] = REPO
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", name), *args],
        env=env, capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, \
        f"{name} failed:\nstdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_mnist_example():
    out = _run_example("mnist.py")
    assert "loss" in out or "epoch" in out, out


def test_torch_mnist_example():
    pytest.importorskip("torch")
    out = _run_example("torch_mnist.py")
    assert "epoch 2" in out, out


def test_tf_keras_mnist_example():
    pytest.importorskip("tensorflow")
    out = _run_example("tf_keras_mnist.py")
    assert "epoch 2" in out, out


def test_long_context_example_sharded():
    out = _run_example("long_context.py", "--seq", "512", "--sp", "4")
    assert "ring over sp=4" in out, out
    assert "ulysses over sp=4" in out, out


def test_estimator_example():
    out = _run_example("estimator_linreg.py", "--np", "2", "--epochs", "6")
    assert "learned w" in out, out
    assert "epoch 5" in out, out


def test_data_service_example():
    out = _run_example("data_service_train.py", "--workers", "2",
                       "--steps", "60")
    assert "service-fed batches" in out, out
    # the demo must actually LEARN: w_true = [1, -2, 0.5, 3]
    import re
    m = re.search(r"learned w: \[([^\]]+)\]", out)
    assert m, out
    w = [float(v) for v in m.group(1).split(",")]
    import numpy as _np
    assert _np.allclose(w, [1.0, -2.0, 0.5, 3.0], atol=0.35), (w, out)


def test_frontend_overhead_example():
    pytest.importorskip("torch")
    pytest.importorskip("tensorflow")
    out = _run_example("frontend_overhead.py", "--steps", "3")
    assert "native JAX" in out and "vs native" in out, out
    assert "torch frontend" in out and "TF frontend" in out, out
    assert "[skipped]" not in out, out


def test_tf_keras_fit_example():
    """compile+fit with the distributed optimizer and callbacks — the
    reference's canonical Keras workflow (keras_mnist.py)."""
    pytest.importorskip("tensorflow")
    pytest.importorskip("keras")
    out = _run_example("tf_keras_fit_mnist.py")
    assert "final accuracy" in out, out


def test_hybrid_lm_example():
    """The GSPMD hybrid-parallel entry point (docs/parallelism.md):
    tied-LM training tp=4 x dp=2 over HOROVOD_MESH through
    DistributedOptimizer(sharding_spec=...), and its pure-DP twin with
    the knob unset — same script, same builder."""
    env_extra = {"HOROVOD_MESH": "dp=2,tp=4"}
    import os as _os
    saved = _os.environ.get("HOROVOD_MESH")
    try:
        _os.environ["HOROVOD_MESH"] = env_extra["HOROVOD_MESH"]
        out = _run_example("hybrid_lm.py", "--steps", "4")
    finally:
        if saved is None:
            _os.environ.pop("HOROVOD_MESH", None)
        else:
            _os.environ["HOROVOD_MESH"] = saved
    assert "mesh dp=2,tp=4 on 8 devices" in out, out
    assert "tokens/s" in out, out
    out = _run_example("hybrid_lm.py", "--steps", "2")
    assert "mesh dp=8 on 8 devices" in out, out


def test_scaling_report():
    """--scaling-report 1 vs 8 on the virtual CPU mesh: the full harness
    behind the reference's north-star metric (90% efficiency 1→N,
    README.rst:102-108; BASELINE.md) runs end to end and emits a
    schema-complete JSON line. On a pod the identical flag measures real
    1→N chip efficiency — this rehearsal pins the harness so the pod run
    is a parameter change, not new code."""
    import json

    out = _run_example("synthetic_benchmark.py", "--scaling-report", "8",
                       "--batch-size", "2", "--image-size", "32",
                       "--num-iters", "1", "--num-batches-per-iter", "1",
                       "--dtype", "float32")
    line = [ln for ln in out.splitlines()
            if ln.startswith("{")][-1]
    rec = json.loads(line)
    assert set(rec) == {"model", "per_rank_batch", "ips_1chip",
                        "ips_per_chip_at_n", "n", "scaling_efficiency"}
    assert rec["model"] == "resnet50" and rec["per_rank_batch"] == 2
    assert rec["n"] == 8
    assert rec["ips_1chip"] > 0 and rec["ips_per_chip_at_n"] > 0
    # Sane-bounds check, not a perf gate: the 8 virtual CPU "chips" share
    # one host's cores, so per-chip efficiency is far below a pod's —
    # anything in (0, 1.5] proves the harness computes a real ratio
    # (NaN/0/negative/>>1 all indicate a broken measurement).
    eff = rec["scaling_efficiency"]
    assert 0.0 < eff <= 1.5, rec
    # consistency of the reported fields — eff is computed from UNROUNDED
    # rates while ips_* are rounded to 1 decimal, so the tolerance must
    # absorb the rounding error of both rates (±0.05 each)
    ratio = rec["ips_per_chip_at_n"] / rec["ips_1chip"]
    tol = eff * (0.05 / rec["ips_per_chip_at_n"]
                 + 0.05 / rec["ips_1chip"]) + 1e-3
    assert abs(eff - ratio) <= tol, rec
