"""What PR 26 adds to the benchmark: the `olmoe` family's arithmetic against
the configuration's published numbers, the reading of `moe.*` scopes and of
the grouped-matmul kernels from a compiled program's text, the four
`moe_*` readers on a hand-made trace, and the cell's path rehearsed at a
tiny size on the CPU (`fixtures/tiny-olmoe`)."""

import json
import os
import time

import pytest
from jax.profiler import ProfileData

import benchmark_fakes as fakes
import horovod_tpu as hvd
from benchmark.harness import hlo, peaks, runner, scopes, spec, xplane

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                    "tiny-olmoe")
US = 1e-6
METRICS = ("moe_ms_per_step", "moe_experts_ms_per_step",
           "moe_dispatch_ms_per_step", "moe_experts_roofline")


def reader(name):
    return spec.load_module("layer_metrics", name, (spec.PACKAGE_DIR,))


@pytest.fixture(scope="module")
def cell_and_family():
    cell = spec.load_cell("olmoe-1chip")
    return cell, spec.load_module("families", cell.config["family"],
                                  cell.dirs)


# ------------------------------------------------------------ arithmetic

def test_the_configuration_holds_the_published_numbers(cell_and_family):
    cell, _ = cell_and_family
    published = {"attention_bias": False, "clip_qkv": None,
                 "hidden_act": "silu", "hidden_size": 2048,
                 "intermediate_size": 1024, "max_position_embeddings": 4096,
                 "model_type": "olmoe", "norm_topk_prob": False,
                 "num_attention_heads": 16, "num_experts": 64,
                 "num_experts_per_tok": 8, "num_key_value_heads": 16,
                 "rms_norm_eps": 1e-05, "rope_scaling": None,
                 "rope_theta": 10000, "tie_word_embeddings": False,
                 "vocab_size": 50304}
    assert {k: cell.config[k] for k in published} == published
    assert cell.config["published"] == {"num_hidden_layers": 16}
    assert list(cell.config["reduced"]) == ["n_layer"]
    assert cell.config["n_layer"] == 3
    assert (cell.traffic["seq_len"], cell.traffic["per_chip_batch"],
            cell.traffic["mesh"], cell.chips) == (4096, 2, {}, 1)


def test_parameters_and_bytes_as_the_configuration_file_says(cell_and_family):
    cell, family = cell_and_family
    import jax
    from horovod_tpu.models import transformer as tfm
    cfg = family.transformer_config(cell.config)
    shapes = jax.eval_shape(lambda k: tfm.init(k, cfg), jax.random.PRNGKey(0))
    count = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    layer = 4 * 2048 ** 2 + 64 * 3 * 2048 * 1024 + 2048 * 64 + 4 * 2048
    assert layer == 419_569_664
    assert count == 3 * layer + 2 * 50304 * 2048 + 2048 == 1_464_756_224
    # bf16 weight, gradient and two Adam moments: 11.7 GB = 10.9 GiB
    assert 8 * count / 2 ** 30 == pytest.approx(10.91, abs=0.01)
    assert all(x.dtype == "bfloat16" for x in
               jax.tree_util.tree_leaves(shapes))


def test_olmoe_flops_per_token_by_hand(cell_and_family):
    cell, family = cell_and_family
    parts = family.forward_flops_per_token(cell.config, 4096)
    assert parts["projections"] == 3 * 33_554_432
    assert parts["attention"] == pytest.approx(3 * 16.78e6, rel=1e-3)
    assert parts["router"] == 3 * 262_144
    assert parts["experts"] == 3 * 8 * 3 * 2 * 2048 * 1024 == 3 * 100_663_296
    assert parts["head"] == 206_045_184
    forward = sum(parts.values())
    assert forward == pytest.approx(659.8e6, rel=1e-3)
    assert family.flops_per_sample(cell.config, cell.traffic) == \
        pytest.approx(3 * forward)
    # what the cut to three layers distorts (the configuration's `reduced`)
    assert parts["head"] / forward == pytest.approx(0.31, abs=0.005)
    assert parts["experts"] / forward == pytest.approx(0.46, abs=0.005)
    full = family.forward_flops_per_token(
        dict(cell.config, n_layer=16), 4096)
    assert full["head"] / sum(full.values()) == pytest.approx(0.08, abs=0.005)
    assert full["experts"] / sum(full.values()) == \
        pytest.approx(0.61, abs=0.005)
    assert family.samples_per_step(cell.traffic, 1) == 8192
    assert family.flash_kernel_shape(cell.config, cell.traffic) == \
        (2, 16, 4096, 128)
    assert family.grouped_matmul_shape(cell.config, cell.traffic) == \
        (65536, 2048, 1024, 64)


def test_grouped_matmul_work_and_its_bound():
    roof = reader("moe_experts_roofline")
    flops, moved = roof.grouped_matmul_work((65536, 2048, 1024, 64))
    # the TPU compiler's own cost estimate for this kernel says the same
    # FLOPs and 256 bytes more (its metadata)
    assert flops == 274_877_906_944
    assert moved == 2 * (65536 * 2048 + 64 * 2048 * 1024 + 65536 * 1024) \
        == 671_088_640
    v5e = peaks.for_kind("TPU v5 lite")
    seconds, bound = roof.least_seconds((65536, 2048, 1024, 64), v5e)
    assert bound == "compute" and seconds == pytest.approx(1.3953e-3,
                                                           rel=1e-4)
    # few rows per expert: the weights' bytes hold
    assert roof.least_seconds((512, 2048, 1024, 64), v5e)[1] == "memory"


# ------------------------------------------------------- scopes, readers

#: A compiled step in miniature: a router fusion, a sort in a loop of its
#: own, a gather, the compiler's grouped-matmul kernels, the gate, a flash
#: kernel, and a fusion of the block that is no part of the expert layer.
HLO_TEXT = """
HloModule jit_step

ENTRY %main (a: bf16[8,128]) -> bf16[8,128] {
  %a = bf16[8,128]{1,0} parameter(0)
  %fusion.1 = f32[8,8]{1,0} fusion(%a), kind=kOutput, calls=%f1, metadata={op_name="jit(step)/jvp()/while/body/closed_call/moe.route/dot_general" stack_frame_id=7}
  %while.2 = (s32[64]{0}, s32[64]{0}) while(%t), condition=%c, body=%b, metadata={op_name="jit(step)/jvp()/while/body/closed_call/moe.dispatch/sort"}
  %sort.3 = (s32[64]{0}, s32[64]{0}) sort(%k, %i), dimensions={0}, to_apply=%lt, metadata={op_name="jit(step)/jvp()/while/body/closed_call/moe.dispatch/sort"}
  %gather.4 = bf16[64,128]{1,0} gather(%a, %i), metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/moe.dispatch/gather"}
  %ragged-dot-metadata = (s32[9]{0}, s32[15]{0}, s32[15]{0}, s32[1]{0}) custom-call(%sizes), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %ragged-dot-none.1 = bf16[64,64]{1,0} custom-call(%m0, %m1, %m2, %m3, %m0, /*index=5*/%rows, %w), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %multiply.5 = bf16[64,64]{1,0} multiply(%g, %u), metadata={op_name="jit(step)/jvp()/while/body/closed_call/moe.experts/mul"}
  %fusion.6 = bf16[8,128]{1,0} fusion(%y, %p), kind=kLoop, calls=%f6, metadata={op_name="jit(step)/jvp()/while/body/closed_call/moe.combine/reduce_sum"}
  %jvp__.1 = (bf16[2,64,128]{2,1,0}, f32[2,64,1]{2,1,0}) custom-call(%q, %k, /*index=2*/%v), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp()/while/body/closed_call/pallas_call"}
  ROOT %fusion.7 = bf16[8,128]{1,0} fusion(%a), kind=kLoop, calls=%f7, metadata={op_name="jit(step)/jvp()/while/body/closed_call/add"}
}
"""

#: per step, in microseconds: (name, start, duration)
STEP_OPS = (("%while.2 = (s32[64]{0}, s32[64]{0}) while(%t), condition=%c, "
             "body=%b", 0, 12),            # spans sort.3: not counted itself
            ("fusion.1", 12, 4), ("sort.3", 2, 8), ("gather.4", 16, 6),
            ("ragged-dot-metadata", 22, 1), ("ragged-dot-none.1", 23, 10),
            ("multiply.5", 33, 2), ("ragged-dot-none.1", 35, 10),
            ("fusion.6", 45, 5), ("jvp__.1", 50, 20), ("fusion.7", 70, 10))


@pytest.fixture(scope="module")
def table():
    return hlo.index(HLO_TEXT)


@pytest.fixture(scope="module")
def trace():
    rows = [(n, step * 100 + start, dur) for step in range(5)
            for n, start, dur in STEP_OPS]
    modules = [("jit_step(1)", step * 100, 85) for step in range(5)]
    return xplane.reduce_profile(ProfileData.from_text_proto(fakes._plane(
        1, "/device:TPU:0", [("XLA Modules", modules), ("XLA Ops", rows)])))


def test_scopes_are_read_from_the_programs_own_text(table):
    assert scopes.part_of("jit(step)/transpose(jvp())/while/body/"
                          "checkpoint/moe.route/dot_general") == "route"
    assert scopes.part_of("jit(step)/jvp()/add") is None
    assert scopes.part_of("a/moe.unknown/moe.combine/b/moe.route/c") == \
        "combine"
    assert scopes.moe_parts(HLO_TEXT, table) == {
        "fusion.1": "route", "sort.3": "dispatch", "gather.4": "dispatch",
        "ragged-dot-metadata": "experts", "ragged-dot-none.1": "experts",
        "multiply.5": "experts", "fusion.6": "combine"}
    # the loop around the sort, the flash kernel and the block's own fusion
    # are no part of it
    assert scopes.grouped_kernels(table) == {
        "ragged-dot-metadata": scopes.GROUPED_METADATA,
        "ragged-dot-none.1": scopes.GROUPED_MATMUL}


def test_the_four_readers_on_a_hand_made_trace(trace, table):
    program = type("P", (), {"as_text": staticmethod(lambda: HLO_TEXT)})
    shape = (64, 128, 64, 8)
    family = type("F", (), {"grouped_matmul_shape":
                            staticmethod(lambda c, t: shape)})
    v5e = peaks.for_kind("TPU v5 lite")
    run = fakes.fake_run(trace, table, program=program, peaks=v5e,
                         family=family,
                         cell=type("C", (), {"config": {}, "traffic": {}}))
    assert reader("moe_experts_ms_per_step").read(run) == \
        pytest.approx((1 + 10 + 2 + 10) * 1e-3)
    assert reader("moe_dispatch_ms_per_step").read(run) == \
        pytest.approx((4 + 8 + 6 + 5) * 1e-3)
    assert reader("moe_ms_per_step").read(run) == pytest.approx(46e-3)
    roof = reader("moe_experts_roofline")
    least = roof.least_seconds(shape, v5e)[0]
    # two executions a step in 21 us of kernels (metadata included)
    assert roof.read(run) == pytest.approx(100 * 2 * least / (21 * US))


def test_a_program_without_the_expert_layer_reads_as_nothing():
    """The parent's program, or a cell of another family: every reader
    returns None and raises nothing."""
    table = hlo.index(fakes.HLO_TEXT)
    trace = xplane.reduce_profile(
        ProfileData.from_text_proto(fakes.hand_made_xspace()))
    program = type("P", (), {"as_text": staticmethod(lambda: fakes.HLO_TEXT)})
    run = fakes.fake_run(trace, table, program=program,
                         peaks=peaks.for_kind("TPU v5 lite"),
                         family=type("F", (), {}), cell=None)
    assert [reader(m).read(run) for m in METRICS] == [None] * 4
    for bare in (fakes.fake_run(None, {}, program=program, peaks=None),
                 fakes.fake_run(xplane.Trace(), {}, program=None,
                                peaks=None, family=None)):
        assert [reader(m).read(bare) for m in METRICS] == [None] * 4


def test_scopes_without_a_grouped_matmul_kernel_are_an_error(trace):
    """A compiler that builds or names the grouped matmuls otherwise: no
    reader may go on reading the expert layer without them."""
    text = "\n".join(ln for ln in HLO_TEXT.splitlines()
                     if "ragged-dot" not in ln)
    program = type("P", (), {"as_text": staticmethod(lambda: text)})
    run = fakes.fake_run(trace, hlo.index(text), program=program,
                         peaks=peaks.for_kind("TPU v5 lite"),
                         family=type("F", (), {}), cell=None)
    for m in METRICS:
        with pytest.raises(RuntimeError, match="grouped matmul's signature"):
            reader(m).read(run)


def test_the_entries_are_the_cells_and_name_the_expert_layer():
    cell = spec.load_cell("olmoe-1chip")
    assert [m["name"] for m in cell.end_to_end] == [
        "samples_per_s_per_chip", "step_hbm_gib", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert names == ["init_s", "compile_s", "device_step_ms", "mfu",
                     "device_idle_share", "window_stall_share", *METRICS]
    assert {m["layer"] for m in cell.per_layer if m["name"] in METRICS} == \
        {"expert layer"}
    # the step holds the compiler's grouped-matmul kernels beside the flash
    # ones, which the flash readers would take for flash kernels
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if m["name"].startswith("flash_"):
            assert "olmoe-1chip" not in m["workloads"]


# -------------------------------------------------------------- rehearsal

@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_the_cell_runs_end_to_end_at_a_tiny_size(trace, tmp_path, capfd):
    hvd.shutdown()   # the cell initialises on exactly its own devices
    cell = spec.load_cell("tiny-olmoe-1chip", root=TINY)
    assert cell.config["family"] == "olmoe"
    try:
        line = json.loads(runner.run_cell(
            cell, seed=2**31 + 5, seconds=0.5, trace=trace,
            t0=time.perf_counter(), platform="cpu", checkout=str(tmp_path)))
    finally:
        hvd.shutdown()
    log = capfd.readouterr().err
    assert line["device"]["platform"] == "cpu"
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert "reference check: {'ok': True" in log
    assert "rows per expert" in log
    assert "compile request(s) after warm-up" not in log
    problems = [ln for ln in log.splitlines() if "NOT CORRECT" in ln]
    if trace:   # the one thing a CPU trace cannot show
        assert ["the trace holds no whole step" in p for p in problems] == \
            [True]
    else:
        assert problems == [] and line["correct"] is True
    # no time, rate or share from the CPU under a device metric's name
    assert line["metrics"] == {}
