"""Rehearsal of the harness and of each path at a tiny size on the CPU
(`lm-dp4`'s on four virtual devices), driven from the tiny cells under
`fixtures/tiny`, which also hold a family, a path and a per-layer metric
that exist nowhere under `benchmark/`: a later PR adds files and entries and
edits no file that is there.

The CPU allowance lives here: `run.py` has no switch for it. A line made on
the CPU is stamped `cpu` and carries counts only, never a time, a rate or a
share under a device metric's name.
"""

import json
import os
import time

import pytest

import horovod_tpu as hvd
from benchmark.harness import runner, spec
from horovod_tpu.models import resnet

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                    "tiny")
#: the one thing a CPU trace cannot show; anything else is a real failure
NO_DEVICE_PLANE = "the trace holds no whole step"


@pytest.fixture()
def rehearse(monkeypatch, tmp_path, capfd):
    """Runs one tiny cell on the CPU and returns (line, the harness's log)."""
    monkeypatch.setitem(resnet.STAGE_BLOCKS, 8, (1, 1))
    hvd.shutdown()   # the cell initialises on exactly its own devices

    def run(name, trace):
        cell = spec.load_cell(name, root=TINY)
        try:
            line = runner.run_cell(cell, seed=3, seconds=0.5, trace=trace,
                                   t0=time.perf_counter(), platform="cpu",
                                   checkout=str(tmp_path))
        finally:
            hvd.shutdown()
        return json.loads(line), capfd.readouterr().err

    return run


def check_line(line, log, trace):
    assert sorted(set(line) - {"breakdown"}) == sorted(
        ["correct", "attempted", "failed", "metrics", "device"])
    assert line["device"]["platform"] == "cpu"
    assert line["failed"] == 0 and line["attempted"] >= 2
    problems = [ln for ln in log.splitlines() if "NOT CORRECT" in ln]
    if trace:
        assert [NO_DEVICE_PLANE in p for p in problems] == [True]
        assert line["correct"] is False
        assert line["breakdown"] == {"device_ops": [], "idle_gaps": []}
        assert line["device"]["busy_s"] == 0.0
    else:
        assert problems == [] and line["correct"] is True
        assert "breakdown" not in line
    assert "reference check: {'ok': True" in log
    assert "compile request(s) after warm-up" not in log


@pytest.mark.parametrize("name, trace, counts", [
    ("tiny-lm-1chip", False, {}),
    ("tiny-lm-dp4", True, {"allreduce_bytes_per_step"}),
    ("tiny-resnet-eager", True, set()),
    ("tiny-resnet-jit", False, set()),
])
def test_each_path_runs_end_to_end_at_a_tiny_size(rehearse, name, trace,
                                                  counts):
    line, log = rehearse(name, trace)
    check_line(line, log, trace)
    # no time, rate or share from the CPU under a device metric's name
    assert set(line["metrics"]) == set(counts)
    if name == "tiny-lm-dp4":
        # every parameter's gradient, bf16, once: the model's size in bytes
        cfg = spec.load_cell(name, root=TINY).config
        d, f, v, p, layers = (cfg["n_embd"], cfg["n_inner"],
                              cfg["vocab_size"], cfg["n_positions"],
                              cfg["n_layer"])
        params = layers * (4 * d * d + 2 * d * f + f + 5 * d) \
            + 2 * v * d + p * d + 2 * d
        assert line["metrics"]["allreduce_bytes_per_step"]["value"] >= \
            2 * params + 4


def test_a_cell_is_added_by_files_and_entries_alone(rehearse):
    """`tiny-added` names a family, a path and a per-layer metric that live
    only under the tests' fixtures; nothing under `benchmark/` knows them."""
    for kind, name in (("families", "linear"), ("paths", "plain_sgd"),
                       ("layer_metrics", "steps_counted")):
        assert not os.path.exists(
            os.path.join(spec.PACKAGE_DIR, kind, name + ".py"))
    cell = spec.load_cell("tiny-added", root=TINY)
    assert cell.config["family"] == "linear"
    # a metric is read only where the metric it moves is reported
    assert [m["name"] for m in cell.per_layer] == ["init_s",
                                                   "device_step_ms",
                                                   "steps_counted"]
    assert [m["name"] for m in cell.end_to_end] == ["samples_per_s_per_chip",
                                                    "setup_s"]
    line, log = rehearse("tiny-added", True)
    check_line(line, log, True)
    assert set(line["metrics"]) == {"steps_counted"}
    assert line["metrics"]["steps_counted"]["unit"] == "count"
    # the half-length untraced stretch plus the traced steps and their edges
    traced = cell.traffic["trace_steps"] + runner.TRACE_EDGE_STEPS
    assert line["attempted"] == \
        line["metrics"]["steps_counted"]["value"] + traced


def test_the_wrong_platform_or_too_few_chips_ends_the_run(monkeypatch):
    cell = spec.load_cell("tiny-lm-1chip", root=TINY)
    with pytest.raises(SystemExit, match="not on 'tpu'"):
        runner.run_cell(cell, seed=0, seconds=0.1, trace=False, t0=0.0)
    import dataclasses
    big = dataclasses.replace(cell, chips=64)
    with pytest.raises(SystemExit, match="needs 64 chip"):
        runner.run_cell(big, seed=0, seconds=0.1, trace=False, t0=0.0,
                        platform="cpu")
