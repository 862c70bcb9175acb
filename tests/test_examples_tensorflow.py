"""The examples of the TensorFlow and Keras frontends, run end to end
(`tests/example_runs.py`): a custom loop, `compile` + `fit`, and the
frontends' overhead beside native JAX (torch's with them)."""

import pytest

from example_runs import run_example


def test_tf_keras_mnist_example():
    pytest.importorskip("tensorflow")
    out = run_example("tf_keras_mnist.py")
    assert "epoch 2" in out, out


def test_tf_keras_fit_example():
    """compile+fit with the distributed optimizer and callbacks — the
    reference's canonical Keras workflow (keras_mnist.py)."""
    pytest.importorskip("tensorflow")
    pytest.importorskip("keras")
    out = run_example("tf_keras_fit_mnist.py")
    assert "final accuracy" in out, out


def test_frontend_overhead_example():
    pytest.importorskip("torch")
    pytest.importorskip("tensorflow")
    out = run_example("frontend_overhead.py", "--steps", "3")
    assert "native JAX" in out and "vs native" in out, out
    assert "torch frontend" in out and "TF frontend" in out, out
    assert "[skipped]" not in out, out
