"""Pluggable execution backends for the estimator.

Reference: horovod/spark/common/backend.py — Backend/SparkBackend run the
remote training function on the cluster. The TPU-first change: Spark is
just one placement provider, so a `LocalBackend` (our own multi-process
launcher over loopback/pods) trains the same estimator with no Spark
installed — which is also how the estimator stack is tested end-to-end.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional


class Backend:
    """Reference: backend.py Backend interface (run / num_processes)."""

    def run(self, fn: Callable[..., Any], args=(),
            env: Optional[dict] = None) -> List[Any]:
        raise NotImplementedError()

    def num_processes(self) -> int:
        raise NotImplementedError()


class LocalBackend(Backend):
    """Train with horovod_tpu's own launcher: one subprocess per rank on
    this host (JAX CPU or the attached TPU chips). No Spark required."""

    def __init__(self, num_proc: int = 1,
                 extra_env: Optional[dict] = None,
                 use_cpu: bool = True):
        self._np = num_proc
        self._env = dict(extra_env or {})
        if use_cpu:
            # Workers share one host; pin each to its own CPU device
            # rather than fighting over a single attached accelerator.
            # Under JAX_PLATFORMS=cpu task_runner also scrubs a parent
            # pytest's virtual-device XLA flags.
            self._env.setdefault("JAX_PLATFORMS", "cpu")

    def num_processes(self) -> int:
        return self._np

    def run(self, fn, args=(), env=None) -> List[Any]:
        from horovod_tpu import runner

        merged = dict(self._env)
        merged.update(env or {})
        return runner.run(lambda: fn(*args), np=self._np,
                          extra_env=merged)


class SparkBackend(Backend):
    """Run the trainer inside Spark tasks (reference: backend.py
    SparkBackend → spark/runner.py run)."""

    def __init__(self, num_proc: Optional[int] = None, verbose: int = 1,
                 extra_env: Optional[dict] = None):
        self._np = num_proc
        self._verbose = verbose
        self._env = dict(extra_env or {})

    def num_processes(self) -> int:
        if self._np is not None:
            return self._np
        import pyspark

        sc = pyspark.SparkContext._active_spark_context
        if sc is None:
            raise RuntimeError("no active SparkContext; pass num_proc")
        return sc.defaultParallelism

    def run(self, fn, args=(), env=None) -> List[Any]:
        from horovod_tpu import spark as hvd_spark

        merged = dict(self._env)
        merged.update(env or {})
        return hvd_spark.run(fn, args=args, num_proc=self.num_processes(),
                             extra_env=merged, verbose=self._verbose)
