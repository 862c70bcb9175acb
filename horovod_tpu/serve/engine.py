"""AOT-compiled inference engine for serving replicas.

One executable per (bucket, item shape, dtype): the batch assembler
pads every batch to a bucket (serve/batching.py), so after `warmup()`
the serving hot path NEVER traces or compiles — each request shape hits
a `lower().compile()` executable built ahead of time (the same AOT
discipline `benchmark.aot_check` uses for its compiles).

Observability hooks:

* perfscope — inference runs under the replica's step scope with the
  compile attributed to ``compile`` and the device wait to
  ``device_compute``, so the doctor's perf section attributes serving
  stragglers by phase exactly like training ranks.
* hvdhlo — the lowered program of each bucket is linted with the HVD2xx
  rules (`analysis/hlo.lint_summary`); findings are recorded as flight
  `serve` events and surfaced via `hlo_lint()` (bench stamps them).
* flight — each compilation is a `serve` event (a compile on the hot
  path after warmup is a bug worth seeing in a postmortem).

Loading weights: `InferenceEngine.from_checkpoint` restores the params
subtree of a *training* checkpoint without constructing an optimizer
(checkpoint.restore_params) — serving replicas must not need the
training-side optimizer state or its classes.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple


class InferenceEngine:
    """Wraps ``infer_fn(params, batch) -> outputs`` with per-bucket AOT
    executables. `batch` is ``(bucket, *item_shape)``; outputs must keep
    the batch dimension first so the pool can slice off padding rows."""

    def __init__(self, infer_fn: Callable[[Any, Any], Any],
                 params: Any, name: str = "serve") -> None:
        self.infer_fn = infer_fn
        self.params = params
        self.name = name
        # compiles are serialized by the caller (warmup, then the
        # replica's single handler path) — no lock needed
        self._compiled: Dict[Tuple, Any] = {}
        self._lint: Dict[Tuple, Dict[str, Any]] = {}
        self.compiles = 0

    # ---------------------------------------------------------- weights
    @classmethod
    def from_checkpoint(cls, path: str,
                        infer_fn: Callable[[Any, Any], Any],
                        like_params: Optional[Any] = None,
                        name: str = "serve") -> "InferenceEngine":
        """Params-only restore of a training checkpoint (no optimizer
        state is read, none needs to be constructible).

        `path` may be either a single orbax checkpoint dir
        (checkpoint.save — its ``.done`` commit marker is verified, a
        partial dir is a typed CheckpointCorruptError) or an
        ``AsyncCheckpointer`` ROOT of generation-numbered manifests
        (horovod_tpu/ckpt/) — then the newest COMMITTED generation's
        params shards are read and reassembled, so a replica can serve
        straight from a live training job's checkpoint root, sharded
        models included (docs/checkpointing.md)."""
        import jax
        import jax.numpy as jnp

        from horovod_tpu import ckpt as _ckpt
        from horovod_tpu import checkpoint as ckpt
        if _ckpt.latest_committed(path) is not None:
            params = _ckpt.load_params(path, like=like_params)
        else:
            params = ckpt.restore_params(path, like=like_params)
        params = jax.tree_util.tree_map(jnp.asarray, params)
        return cls(infer_fn, params, name=name)

    # ---------------------------------------------------------- compile
    @staticmethod
    def _key(shape: Tuple[int, ...], dtype: Any) -> Tuple:
        import numpy as np
        # normalize: np.float32 (the type), dtype('float32'), "float32"
        # must all hit the same executable
        return (tuple(shape), np.dtype(dtype).name)

    def compile_for(self, batch_shape: Tuple[int, ...],
                    dtype: Any) -> Any:
        """Build (or fetch) the AOT executable for one padded batch
        shape. Returns the compiled executable."""
        import jax

        key = self._key(batch_shape, dtype)
        exe = self._compiled.get(key)
        if exe is not None:
            return exe
        from horovod_tpu.observability import flight
        from horovod_tpu.profiler import perfscope
        from horovod_tpu.serve import telemetry
        t0 = time.perf_counter()
        spec = jax.ShapeDtypeStruct(tuple(batch_shape), dtype)
        lowered = jax.jit(self.infer_fn).lower(self.params, spec)
        exe = lowered.compile()
        dt = time.perf_counter() - t0
        perfscope.attribute("compile", dt)
        telemetry.handles()["compiles"].inc()
        self.compiles += 1
        flight.record(
            "serve", f"compile engine={self.name} shape={batch_shape} "
                     f"dtype={dtype} seconds={dt:.3f}")
        self._compiled[key] = exe
        self._lint[key] = self._lint_lowered(lowered, key)
        return exe

    def _lint_lowered(self, lowered, key) -> Dict[str, Any]:
        """hvdhlo over the lowered inference program (never fatal — a
        lint crash must not take the replica down)."""
        try:
            from horovod_tpu.analysis import hlo
            if not hlo.lint_enabled():
                return {"skipped": True}
            summary = hlo.lint_summary(
                lowered.as_text(), path=f"<serve:{self.name}:{key[0]}>")
            if not summary.get("clean", True):
                from horovod_tpu.observability import flight
                flight.record(
                    "serve", f"hlo_lint engine={self.name} shape={key[0]} "
                             f"findings={summary.get('count')}")
            return summary
        except Exception as e:
            return {"error": f"{type(e).__name__}: {e}"}

    def warmup(self, item_shape: Tuple[int, ...], dtype: Any,
               buckets) -> None:
        """Precompile every bucket so serving never compiles in-band."""
        for b in buckets:
            self.compile_for((int(b),) + tuple(item_shape), dtype)

    def hlo_lint(self) -> Dict[str, Any]:
        """Merged lint stamp over every compiled bucket (bench + replica
        startup logs)."""
        total = 0
        rules: Dict[str, int] = {}
        findings = []
        for s in self._lint.values():
            total += int(s.get("count", 0) or 0)
            for r, n in (s.get("rules") or {}).items():
                rules[r] = rules.get(r, 0) + n
            findings.extend(s.get("findings") or [])
        out: Dict[str, Any] = {"count": total, "clean": total == 0,
                               "programs": len(self._lint)}
        if rules:
            out["rules"] = rules
            out["findings"] = findings[:20]
        return out

    # -------------------------------------------------------------- run
    def infer(self, batch) -> Any:
        """Run one padded batch through its AOT executable, blocking
        until device results are ready (perfscope: device_compute)."""
        import jax
        import numpy as np

        from horovod_tpu.observability import tracing
        from horovod_tpu.profiler import perfscope
        arr = np.asarray(batch)
        exe = self.compile_for(arr.shape, arr.dtype)
        scope = perfscope.get()
        # Ambient-gated trace span: records the device time with the
        # bucket/padded-shape attributes when a sampled trace rode the
        # batch RPC; an untraced call (warmup) records nothing.
        with tracing.span("engine.execute",
                          attrs={"bucket": int(arr.shape[0]),
                                 "padded_shape": list(arr.shape),
                                 "dtype": str(arr.dtype)}):
            with scope.phase("device_compute"):
                out = exe(self.params, arr)
                out = jax.block_until_ready(out)
        return np.asarray(out)
