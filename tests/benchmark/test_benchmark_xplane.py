"""The reduction from a profiler trace to numbers (`benchmark/harness/
xplane.py`, `hlo.py`) and the readers built on it, against a hand-made trace
with known answers and against a trace recorded on the v5e."""

import gzip
import json
import os

import pytest
from jax.profiler import ProfileData

from benchmark.harness import hlo, peaks, spec, xplane

import benchmark_fakes as fakes

US = 1e-6
RECORDED = os.path.join(spec.PACKAGE_DIR, "fixtures")


@pytest.fixture(scope="module")
def trace():
    return xplane.reduce_profile(
        ProfileData.from_text_proto(fakes.hand_made_xspace()))


@pytest.fixture(scope="module")
def table():
    return hlo.index(fakes.HLO_TEXT)


def reader(name):
    return spec.load_module("layer_metrics", name, (spec.PACKAGE_DIR,))


def test_only_chip_planes_and_bench_spans_are_kept(trace):
    assert [d.name for d in trace.devices] == ["/device:TPU:0",
                                               "/device:TPU:1"]
    assert {s.name for s in trace.spans} == {"bench.step", "bench.spmd_step",
                                             "bench.block"}
    names = {e.name for e in trace.devices[0].ops}
    assert "fusion.1" in names and "noise" not in names
    assert {m.name for m in trace.devices[0].modules} == {"jit_step",
                                                          "jit_add"}


def test_union_clip_and_gaps():
    merged = xplane.union([(5, 7), (0, 3), (2, 4), (7, 8), (9, 9)])
    assert merged == [(0, 4), (5, 8)]
    assert xplane.length(merged) == 7
    assert xplane.clip(merged, (3, 6)) == [(3, 4), (5, 6)]
    assert xplane.gaps(merged, (-1, 10)) == [(-1, 0), (4, 5), (8, 10)]
    assert xplane.gaps([], (0, 2)) == [(0, 2)]


def test_steps_are_split_at_the_main_programs_starts(trace):
    dev = trace.devices[0]
    starts = xplane.step_starts(dev)
    assert starts == pytest.approx([i * 100 * US for i in range(5)])
    # the first traced step opens the window and is not measured
    steps = xplane.step_intervals(dev)
    assert [a for a, _ in steps] == pytest.approx([100 * US, 200 * US,
                                                   300 * US])
    assert [b for _, b in steps] == pytest.approx([200 * US, 300 * US,
                                                   400 * US])
    assert xplane.measured(dev) == pytest.approx((100 * US, 400 * US, 3))


def test_busy_union_per_step_and_idle_share(trace):
    # [0,30) with the all-reduce-start inside it, [40,70), [90,95): 65 us;
    # the loop's own event around them ([0,95)) is not an operation
    for dev in trace.devices:
        assert xplane.device_step_seconds(dev) == pytest.approx([65 * US] * 3)
    busy, window = xplane.busy_and_window_seconds(trace)
    assert busy == pytest.approx(195 * US) and \
        window == pytest.approx(300 * US)
    run = fakes.fake_run(trace, {})
    assert reader("device_step_ms").read(run) == pytest.approx(0.065)
    assert reader("device_idle_share").read(run) == pytest.approx(35.0)


def test_programs_per_step(trace):
    assert xplane.modules_per_step(trace.devices[0]) == 2
    assert reader("programs_per_step").read(fakes.fake_run(trace, {})) == 2


def test_collective_and_kernel_sums_go_by_the_programs_text(trace, table):
    run = fakes.fake_run(trace, table)
    # start (5 us, hidden in fusion.1) + done (10 us): total, not exposed
    assert reader("collective_ms_per_step").read(run) == \
        pytest.approx(0.015)
    assert reader("flash_ms_per_step").read(run) == pytest.approx(0.020)
    # no table, nothing to tell a collective or a kernel by
    bare = fakes.fake_run(trace, {})
    assert reader("collective_ms_per_step").read(bare) == 0.0
    assert reader("flash_ms_per_step").read(bare) is None


def test_opt_step_span_reader(trace):
    assert reader("opt_step_host_ms").read(fakes.fake_run(trace, {})) is None
    trace2 = xplane.Trace(devices=trace.devices, spans=[
        xplane.Event("bench.opt_step", 0.0, d) for d in (0.001, 0.003, 0.1)])
    assert reader("opt_step_host_ms").read(fakes.fake_run(trace2, {})) == \
        pytest.approx(3.0)


def test_flash_roofline_from_shapes_and_trace(trace, table):
    roof = reader("flash_roofline")
    assert [roof.kernel_kind(table[n]) for n in
            ("jvp__.1", "transpose_jvp___.2", "transpose_jvp___.3")] == \
        ["forward", "dkdv", "dq"]
    shape = (2, 4, 2048, 128)                     # batch, heads, seq, head_dim
    flops, moved = roof.causal_work("forward", shape)
    # q.k and p.v over the causal half: 2 * (2*S*S*d / 2) per head
    assert flops == 2 * 4 * 2 * 2048 * 2048 * 128
    assert moved == 4 * (2 * 4 * 2048 * 128 * 2) + 2 * 4 * 2048 * 4
    assert roof.causal_work("dkdv", shape)[0] == 2 * flops
    assert roof.causal_work("dq", shape)[0] == 1.5 * flops
    v5e = peaks.for_kind("TPU v5 lite")
    seconds, bound = roof.least_seconds("forward", shape, v5e)
    assert bound == "compute" and seconds == pytest.approx(flops / 197e12)
    # a short sequence moves more than it computes: the memory bound holds
    assert roof.least_seconds("forward", (2, 4, 128, 128), v5e)[1] == "memory"
    # in the trace only the forward kernel ran: once a step, 20 us
    family = type("F", (), {"flash_kernel_shape":
                            staticmethod(lambda c, t: shape)})
    run = fakes.fake_run(trace, table, peaks=v5e, family=family,
                         cell=type("C", (), {"config": {}, "traffic": {}}))
    assert roof.read(run) == pytest.approx(100 * seconds / (20 * US))


def test_breakdown_top_ops_and_idle_gaps_by_span(trace):
    dev = trace.devices[0]
    top = xplane.top_ops(dev)
    assert top[0] == ["fusion.1", pytest.approx(90 * US)]
    assert "while.9" not in dict(map(tuple, top))
    assert dict(map(tuple, top))["all-reduce-done.1"] == \
        pytest.approx(30 * US)
    # per step: [30,40) lies in bench.spmd_step; [70,90) and [95,100) in
    # bench.block; the outer bench.step covers all and is asked last
    gaps = dict(map(tuple, xplane.idle_gaps(dev, trace.spans)))
    assert gaps == {"bench.block": pytest.approx(75 * US),
                    "bench.spmd_step": pytest.approx(30 * US)}
    assert xplane.span_of((0.0, 1.0), xplane.inner_then_outer([])) == \
        xplane.NO_SPAN


def test_a_trace_without_a_whole_step_reads_as_nothing():
    empty = xplane.reduce_profile(ProfileData.from_text_proto(
        'planes { id: 1 name: "/host:CPU" }'))
    assert empty.devices == [] and \
        xplane.busy_and_window_seconds(empty) is None
    run = fakes.fake_run(empty, {})
    for name in ("device_step_ms", "device_idle_share", "programs_per_step",
                 "collective_ms_per_step", "flash_ms_per_step"):
        assert reader(name).read(run) is None


# ------------------------------------------------------- the program's text

def test_hlo_index_opcodes_results_operands_targets(table):
    assert table["fusion.1"].opcode == "fusion"
    assert table["add.1"].opcode == "add" and table["mul.7"].opcode == \
        "multiply"
    kernel = table["jvp__.1"]
    assert kernel.is_mosaic_kernel and kernel.n_operands == 3
    assert kernel.results == (("bf16", (2, 64, 128)), ("f32", (2, 64, 1)))
    assert table["transpose_jvp___.2"].n_operands == 6
    assert not table["other.4"].is_mosaic_kernel
    assert table["all-reduce-start.1"].collective == "all-reduce"
    assert table["all-reduce-done.1"].collective == "all-reduce"
    assert table["fusion.1"].collective is None
    assert hlo.op_name("%fusion.1 = bf16[8,128]{1,0} fusion(%a)") == \
        "fusion.1"
    assert hlo.opcode_of("%fusion.1 = bf16[8,128]{1,0} fusion(%a)") == \
        "fusion"
    assert hlo.opcode_of("fusion.1") is None


def test_allreduce_bytes_counts_starts_once(table):
    # (bf16[8,128], f32[16]) = 2048 + 64 bytes; the -done is not a second one
    assert hlo.allreduce_bytes(table) == 8 * 128 * 2 + 16 * 4


# ------------------------------------------------------ recorded on the chip

def recorded(name):
    path = os.path.join(RECORDED, name)
    with gzip.open(path) as f:
        return xplane.reduce_profile(
            ProfileData.from_serialized_xspace(f.read()))


def recorded_table(name):
    with gzip.open(os.path.join(RECORDED, name), "rt") as f:
        return hlo.index(f.read())


def recorded_line(name):
    with open(os.path.join(RECORDED, name)) as f:
        return json.load(f)


def test_recorded_one_chip_trace_reduces_to_what_the_chip_run_printed():
    """`tiny-lm-1chip` of the tests' fixtures (2 layers, D256, S256, flash,
    remat) through the real harness on a `TPU v5 lite`, 3 + 2 traced steps
    (my chip run, PR 22). The fixture holds the trace, the step program's
    text and the line that run printed."""
    trace = recorded("tiny-lm-1chip.xplane.pb.gz")
    table = recorded_table("tiny-lm-1chip.hlo.txt.gz")
    line = recorded_line("tiny-lm-1chip.line.json")
    assert [d.name for d in trace.devices] == ["/device:TPU:0"]
    dev = trace.devices[0]
    assert {m.name for m in dev.modules} == {"jit_step"}
    assert len(xplane.step_starts(dev)) == 5
    assert len(xplane.step_intervals(dev)) == 3
    assert xplane.modules_per_step(dev) == 1
    # the trace names its events by their instructions' whole text
    assert all(e.opcode for e in dev.ops)
    assert any(e.opcode == "while" for e in dev.ops)     # the layer scan
    assert not any(dict(map(tuple, xplane.top_ops(dev, 50))).keys()
                   & {e.name for e in dev.ops if e.opcode == "while"})
    # the same numbers the run on the chip printed from the same trace
    busy, window = xplane.busy_and_window_seconds(trace)
    assert busy == pytest.approx(line["device"]["busy_s"], rel=1e-9)
    assert window == pytest.approx(line["device"]["window_s"], rel=1e-9)
    assert 0 < busy < window
    run = fakes.fake_run(trace, table)
    assert reader("device_step_ms").read(run) == pytest.approx(
        line["metrics"]["device_step_ms"]["value"], rel=1e-9)
    assert line["device"]["kind"] == "TPU v5 lite" and line["correct"]
    # the step's spans, on the device's clock
    counts = {}
    for s in trace.spans:
        counts[s.name] = counts.get(s.name, 0) + 1
    assert counts == {"bench.step": 5, "bench.spmd_step": 5, "bench.block": 5}
    gaps = dict(map(tuple, xplane.idle_gaps(dev, trace.spans)))
    assert set(gaps) <= {"bench.block", "bench.spmd_step", "bench.step",
                         xplane.NO_SPAN}
    assert sum(gaps.values()) == pytest.approx(window - busy, rel=1e-6)
    # four Mosaic kernels, named after whatever made them, told by signature;
    # each runs once a layer: the forward twice (once more under remat)
    roof = reader("flash_roofline")
    kernels = {n: roof.kernel_kind(i) for n, i in table.items()
               if i.is_mosaic_kernel}
    assert sorted(kernels.values()) == ["dkdv", "dq", "forward", "forward"]
    assert not any("flash" in n or "pallas" in n for n in kernels)
    for name in kernels:
        assert xplane.op_counts_per_step(dev, name.__eq__) == 2   # layers
    flash_ms = reader("flash_ms_per_step").read(run)
    assert flash_ms == pytest.approx(1e3 * sum(
        xplane.op_seconds_per_step(dev, n.__eq__) for n in kernels))
    assert 0 < flash_ms < line["metrics"]["device_step_ms"]["value"]
    # one chip: the program holds no collective
    assert not any(i.collective for i in table.values())
    assert reader("collective_ms_per_step").read(run) == 0.0
    assert hlo.allreduce_bytes(table) == 0


def test_recorded_four_chip_trace_collectives_and_per_chip_means():
    """`tiny-lm-dp4` on a four-chip `TPU v5 lite` host, one process (my chip
    run, PR 22): the readers give what that run printed from the same trace,
    and the program's text gives the all-reduced bytes exactly."""
    trace = recorded("tiny-lm-dp4.xplane.pb.gz")
    table = recorded_table("tiny-lm-dp4.hlo.txt.gz")
    line = recorded_line("tiny-lm-dp4.line.json")
    assert [d.name for d in trace.devices] == [f"/device:TPU:{i}"
                                               for i in range(4)]
    assert line["device"]["count"] == 4 and line["correct"]
    assert [len(xplane.step_intervals(d)) for d in trace.devices] == [3] * 4
    busy, window = xplane.busy_and_window_seconds(trace)
    assert busy == pytest.approx(line["device"]["busy_s"], rel=1e-9)
    assert window == pytest.approx(line["device"]["window_s"], rel=1e-9)
    run = fakes.fake_run(trace, table)
    for name in ("device_step_ms", "flash_ms_per_step",
                 "collective_ms_per_step", "device_idle_share",
                 "programs_per_step", "allreduce_bytes_per_step"):
        assert reader(name).read(run) == pytest.approx(
            line["metrics"][name]["value"], rel=1e-9), name
    # the gradient of every parameter, bf16, in one all-reduce, and the
    # float32 loss in another; each runs once a step on every chip
    cfg = {"d": 256, "f": 512, "v": 512, "p": 256, "layers": 2}
    params = cfg["layers"] * (4 * cfg["d"] ** 2 + 2 * cfg["d"] * cfg["f"]
                              + cfg["f"] + 5 * cfg["d"]) \
        + 2 * cfg["v"] * cfg["d"] + cfg["p"] * cfg["d"] + 2 * cfg["d"]
    collectives = {n: i for n, i in table.items() if i.collective}
    assert sorted(i.result_bytes for i in collectives.values()) == \
        [4, 2 * params]
    assert hlo.allreduce_bytes(table) == 2 * params + 4
    for dev in trace.devices:
        for name in collectives:
            assert xplane.op_counts_per_step(dev, name.__eq__) == 1
    assert 0 < reader("collective_ms_per_step").read(run) < \
        reader("device_step_ms").read(run)
