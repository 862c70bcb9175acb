"""The examples that train a model through the JAX API, run end to end
(`tests/example_runs.py`): MNIST, its torch twin, the long-context attention
demo and the GSPMD hybrid-parallel language model."""

import pytest

from example_runs import run_example


def test_mnist_example():
    out = run_example("mnist.py")
    assert "loss" in out or "epoch" in out, out


def test_torch_mnist_example():
    pytest.importorskip("torch")
    out = run_example("torch_mnist.py")
    assert "epoch 2" in out, out


def test_long_context_example_sharded():
    out = run_example("long_context.py", "--seq", "512", "--sp", "4")
    assert "ring over sp=4" in out, out
    assert "ulysses over sp=4" in out, out


def test_hybrid_lm_example(monkeypatch):
    """The GSPMD hybrid-parallel entry point (docs/parallelism.md):
    tied-LM training tp=4 x dp=2 over HOROVOD_MESH through
    DistributedOptimizer(sharding_spec=...), and its pure-DP twin with
    the knob unset — same script, same builder."""
    out = run_example("hybrid_lm.py", "--steps", "4",
                      HOROVOD_MESH="dp=2,tp=4")
    assert "mesh dp=2,tp=4 on 8 devices" in out, out
    assert "tokens/s" in out, out
    monkeypatch.delenv("HOROVOD_MESH", raising=False)
    out = run_example("hybrid_lm.py", "--steps", "2")
    assert "mesh dp=8 on 8 devices" in out, out
