"""The examples that start processes of their own, run end to end
(`tests/example_runs.py`): an estimator's two-process fit, a training loop
fed by the data service's workers, and the scaling report's 1 -> 8 harness."""

import json
import re

import numpy as np

from example_runs import run_example


def test_estimator_example():
    out = run_example("estimator_linreg.py", "--np", "2", "--epochs", "6")
    assert "learned w" in out, out
    assert "epoch 5" in out, out


def test_data_service_example():
    out = run_example("data_service_train.py", "--workers", "2",
                      "--steps", "60")
    assert "service-fed batches" in out, out
    # the demo must actually LEARN: w_true = [1, -2, 0.5, 3]
    m = re.search(r"learned w: \[([^\]]+)\]", out)
    assert m, out
    w = [float(v) for v in m.group(1).split(",")]
    assert np.allclose(w, [1.0, -2.0, 0.5, 3.0], atol=0.35), (w, out)


def test_scaling_report():
    """--scaling-report 1 vs 8 on the virtual CPU mesh: the full harness
    behind the reference's north-star metric (90% efficiency 1→N,
    README.rst:102-108; BASELINE.md) runs end to end and emits a
    schema-complete JSON line. On a pod the identical flag measures real
    1→N chip efficiency — this rehearsal pins the harness so the pod run
    is a parameter change, not new code."""
    out = run_example("synthetic_benchmark.py", "--scaling-report", "8",
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
