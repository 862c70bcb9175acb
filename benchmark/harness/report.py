"""The last line of a run: one JSON object, the only thing the driver reads."""

from __future__ import annotations

import json
import math

KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACED_DEVICE_KEYS = ("busy_s", "window_s")
BREAKDOWN_KEYS = ("device_ops", "idle_gaps")
BREAKDOWN_ROWS = 10


def last_line(*, correct: bool, attempted: int, failed: int, metrics: dict,
              device: dict, breakdown: dict | None = None) -> str:
    """`metrics` maps a name to `(value, unit)`; values go out as measured,
    with all their digits. Raises on anything the contract would refuse, so
    that a malformed line is a failed run and not a silently ignored one."""
    out_metrics = {}
    for name, (value, unit) in metrics.items():
        value = float(value)
        if not math.isfinite(value):
            raise ValueError(f"metric {name} is {value}")
        out_metrics[name] = {"value": value, "unit": unit}
    missing = [k for k in DEVICE_KEYS if k not in device]
    if breakdown is not None:
        missing += [k for k in TRACED_DEVICE_KEYS if k not in device]
        if sorted(breakdown) != sorted(BREAKDOWN_KEYS):
            raise ValueError(f"breakdown has keys {sorted(breakdown)}")
        breakdown = {k: [[str(n), float(s)] for n, s in rows[:BREAKDOWN_ROWS]]
                     for k, rows in breakdown.items()}
    if missing:
        raise ValueError(f"device lacks {missing}")
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": out_metrics,
            "device": dict(device)}
    if breakdown is not None:
        line["breakdown"] = breakdown
    return json.dumps(line)
