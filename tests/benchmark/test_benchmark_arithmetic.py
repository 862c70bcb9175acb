"""The yardstick's arithmetic against values computed by hand: model FLOPs
per sample of both configurations, the table of peaks, the timing loop's
rate, and the last line's keys."""

import json
import math

import pytest

from benchmark.harness import loop, peaks, report, spec


def cell_and_family(name):
    cell = spec.load_cell(name)
    return cell, spec.load_module("families", cell.config["family"],
                                  cell.dirs)


def test_lm_flops_per_token_by_hand():
    cell, family = cell_and_family("lm-1chip")
    d, f, v, s, layers = 2048, 8192, 50257, 2048, 12
    assert (cell.config["n_embd"], cell.config["n_inner"],
            cell.config["vocab_size"], cell.traffic["seq_len"],
            cell.config["n_layer"]) == (d, f, v, s, layers)
    per_layer = (4 * 2 * d * d        # q, k, v, o projections
                 + 2 * 2 * d * f      # the MLP's two matmuls
                 + 2 * 2 * d * (s + 1) / 2)  # scores and values, causal
    forward = layers * per_layer + 2 * d * v
    assert family.flops_per_sample(cell.config, cell.traffic) == \
        pytest.approx(3 * forward)
    # 4.54 GFLOP a token
    assert 3 * forward == pytest.approx(4.5436e9, rel=1e-4)
    assert family.samples_per_step(cell.traffic, 4) == \
        4 * cell.traffic["per_chip_batch"] * s
    assert family.flash_kernel_shape(cell.config, cell.traffic) == \
        (cell.traffic["per_chip_batch"], 16, 2048, 128)


def test_resnet50_flops_per_image_by_hand():
    cell, family = cell_and_family("resnet50-jit")
    convs = family.conv_shapes(cell.config)
    assert len(convs) == 53                       # 1 + 3*16 + 4 projections
    assert convs[0] == (112, 7, 3, 64)
    assert convs[1:5] == [(56, 1, 64, 64), (56, 3, 64, 64),
                          (56, 1, 64, 256), (56, 1, 64, 256)]
    # stage 1's first block strides in its 3x3 (v1.5)
    assert convs[11:15] == [(56, 1, 256, 128), (28, 3, 128, 128),
                            (28, 1, 128, 512), (28, 1, 256, 512)]
    assert convs[-1] == (7, 1, 512, 2048)
    # the well-known 4.09 GMAC of ResNet-50 v1.5 at 224 px, classifier in
    macs = sum(n * n * k * k * cin * cout for n, k, cin, cout in convs) \
        + 2048 * 1000
    assert macs == pytest.approx(4.09e9, rel=5e-3)
    assert family.flops_per_sample(cell.config, cell.traffic) == \
        pytest.approx(3 * 2 * macs)
    assert family.samples_per_step(cell.traffic, 1) == 128


def test_mfu_reader_uses_the_untraced_rate_and_the_table():
    cell, family = cell_and_family("lm-1chip")
    window = loop.Window(stamps=[0.0, 0.5, 1.0, 1.5], losses=[1.0] * 4)
    run = type("Run", (), dict(
        peaks=peaks.for_kind("TPU v5 lite"), family=family, cell=cell,
        window=window, samples_per_step=8192, chips=1))
    mfu = spec.load_module("layer_metrics", "mfu", cell.dirs).read(run)
    flops = family.flops_per_sample(cell.config, cell.traffic)
    assert mfu == pytest.approx(100 * flops * (8192 / 0.5) / 197e12)
    run.peaks = None        # off the TPU there is no utilisation
    assert spec.load_module("layer_metrics", "mfu", cell.dirs).read(run) \
        is None


def test_peaks_table_has_sources_and_no_default():
    v5e = peaks.for_kind("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bytes_per_s, v5e.ici_bits_per_s) == \
        (197e12, 819e9, 1600e9)
    assert all(p.source for p in peaks.PEAKS.values())
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.for_kind("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.for_kind("cpu")


def test_rate_is_one_step_per_median_step_time_and_stalls_are_a_share():
    w = loop.Window(stamps=[10.0, 10.3, 10.6, 11.6, 11.9],
                    losses=[3.0, 2.0, 1.0, 0.5, float("nan")])
    assert w.step_seconds == pytest.approx([0.3, 0.3, 1.0, 0.3])
    assert w.median_step_seconds() == pytest.approx(0.3)
    assert w.steps_per_second() == pytest.approx(1 / 0.3)   # not 4 / 1.9
    assert w.stall_share() == pytest.approx(1 - 4 * 0.3 / 1.9)
    assert w.failed == 1
    steady = loop.Window(stamps=[0.0, 0.5, 1.0, 1.5])
    assert steady.stall_share() == pytest.approx(0.0, abs=1e-12)
    with pytest.raises(ValueError):
        loop.Window(stamps=[1.0]).steps_per_second()
    stall = spec.load_module("layer_metrics", "window_stall_share",
                             (spec.PACKAGE_DIR,))
    assert stall.read(type("R", (), {"window": w})) == \
        pytest.approx(100 * (1 - 1.2 / 1.9))
    assert stall.read(type("R", (), {"window": loop.Window(
        stamps=[0.0, 1.0])})) is None


def test_loop_dispatches_one_step_ahead_and_stamps_each_completion():
    events, clock = [], iter(range(100))
    losses = iter([5.0, 4.0, 3.0, 2.0, 1.0, 0.5])

    def dispatch():
        events.append("dispatch")
        return next(losses)

    def block(loss):
        events.append(f"block {loss}")
        return loss

    w = loop.run_steps(dispatch, loop.for_steps(4), block=block,
                       clock=lambda: next(clock))
    assert events == ["dispatch", "dispatch", "block 5.0", "dispatch",
                      "block 4.0", "dispatch", "block 3.0", "block 2.0"]
    assert w.dispatched == 4 and len(w.stamps) == 4 and w.failed == 0
    assert w.losses == [5.0, 4.0, 3.0, 2.0]


def test_loop_counts_a_step_that_raises_and_stops(capsys):
    calls = []

    def dispatch():
        calls.append(1)
        if len(calls) == 3:
            raise RuntimeError("device lost")
        return 1.0

    w = loop.run_steps(dispatch, lambda w: False)
    assert w.raised == 1 and w.dispatched == 2 and w.failed == 1
    assert len(w.stamps) == 2
    assert "device lost" in capsys.readouterr().err


def test_for_seconds_ends_the_window_on_the_clock():
    now = [0.0]
    done = loop.for_seconds(10.0, clock=lambda: now[0])
    now[0] = 9.9
    assert not done(None)
    now[0] = 10.0
    assert done(None)


DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 13958643712}


def test_last_line_has_the_contracts_keys_and_all_digits():
    line = report.last_line(
        correct=True, attempted=400, failed=0, device=DEVICE,
        metrics={"samples_per_s_per_chip": (26012.123456789, "samples/s/chip"),
                 "setup_s": (17, "s")})
    assert "\n" not in line
    out = json.loads(line)
    assert sorted(out) == sorted(report.KEYS)
    assert out["metrics"]["samples_per_s_per_chip"] == {
        "value": 26012.123456789, "unit": "samples/s/chip"}
    assert out["metrics"]["setup_s"]["value"] == 17.0
    assert out["device"] == DEVICE and out["correct"] is True


def test_traced_last_line_carries_busy_window_and_breakdown():
    traced = dict(DEVICE, busy_s=1.25, window_s=1.5)
    rows = [[f"op{i}", 0.1 * i] for i in range(14)]
    out = json.loads(report.last_line(
        correct=False, attempted=7, failed=1, metrics={}, device=traced,
        breakdown={"device_ops": rows, "idle_gaps": [["bench.block", 0.2]]}))
    assert sorted(out) == sorted(report.KEYS + ("breakdown",))
    assert len(out["breakdown"]["device_ops"]) == report.BREAKDOWN_ROWS
    assert out["breakdown"]["idle_gaps"] == [["bench.block", 0.2]]
    assert out["device"]["busy_s"] == 1.25


@pytest.mark.parametrize("bad", [
    dict(metrics={"x": (math.nan, "s")}),
    dict(device={"platform": "tpu"}),
    dict(breakdown={"device_ops": [], "idle_gaps": []}),   # no busy_s
    dict(device=dict(DEVICE, busy_s=1.0, window_s=2.0),
         breakdown={"device_ops": []}),
])
def test_last_line_refuses_what_the_contract_would(bad):
    args = dict(correct=True, attempted=1, failed=0, metrics={},
                device=DEVICE)
    args.update(bad)
    with pytest.raises(ValueError):
        report.last_line(**args)
