"""What PR 30 adds to the benchmark: the `deepseek_v2` family's arithmetic
against the configuration's published numbers, the three new readers
(`mla_ms_per_step`, `mla_flash_roofline`, `moe_shared_ms_per_step`) with the
counts they rest on, on a hand-made trace, and the cell's path rehearsed at a
tiny size on the CPU (`fixtures/tiny-deepseek-v2`)."""

import json
import os
import time

import pytest
from jax.profiler import ProfileData

import benchmark_fakes as fakes
import horovod_tpu as hvd
from benchmark.harness import hlo, peaks, runner, scope_time, scopes, spec, \
    xplane

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                    "tiny-deepseek-v2")
US = 1e-6
NEW = ("mla_ms_per_step", "mla_flash_roofline", "moe_shared_ms_per_step")
JOINED = ("samples_per_s_per_chip", "step_hbm_gib", "device_step_ms", "mfu",
          "device_idle_share", "window_stall_share", "moe_ms_per_step",
          "moe_experts_ms_per_step", "moe_dispatch_ms_per_step",
          "moe_experts_roofline")


def reader(name):
    return spec.load_module("layer_metrics", name, (spec.PACKAGE_DIR,))


@pytest.fixture(scope="module")
def cell_and_family():
    cell = spec.load_cell("dsv2lite-1chip")
    return cell, spec.load_module("families", cell.config["family"],
                                  cell.dirs)


# ------------------------------------------------------------ arithmetic

def test_the_configuration_holds_the_published_numbers(cell_and_family):
    """Every number of the catalog row's `config`, letter for letter, but
    the three keys `reduced` names, whose published values stand beside."""
    cell, _ = cell_and_family
    published = {
        "attention_bias": False, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10944, "kv_lora_rank": 512,
        "max_position_embeddings": 163840, "model_type": "deepseek_v2",
        "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1,
        "n_shared_experts": 2, "norm_topk_prob": False,
        "num_attention_heads": 16, "num_experts_per_tok": 6,
        "num_hidden_layers": 27, "num_key_value_heads": 16,
        "q_lora_rank": None, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 0.707, "mscale_all_dim": 0.707,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "rope_theta": 10000, "routed_scaling_factor": 1,
        "scoring_func": "softmax", "seq_aux": True,
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "greedy", "v_head_dim": 128}
    assert {k: cell.config[k] for k in published} == published
    assert cell.config["published"] == {
        "num_hidden_layers": 27, "n_routed_experts": 64,
        "vocab_size": 102400}
    assert sorted(cell.config["reduced"]) == ["n_layer", "n_routed_experts",
                                              "vocab_size"]
    assert (cell.config["n_layer"], cell.config["n_routed_experts"],
            cell.config["vocab_size"]) == (10, 8, 12800)
    # the floors: a whole period and >= 4 expert layers after the dense one,
    # >= 8 routed experts, >= an eighth of the vocabulary
    assert cell.config["n_layer"] - 1 >= 8 and 12800 * 8 == 102400
    deployment = cell.config["deployment"]
    assert (deployment["chips_sharing_a_layer"], deployment["chip"]) == (8, 0)
    assert deployment["chips_sharing_a_layer"] * 8 == 64
    assert (cell.traffic["seq_len"], cell.traffic["per_chip_batch"],
            cell.traffic["mesh"], cell.chips) == (4096, 2, {}, 1)
    assert cell.traffic["optimizer"] == {
        "name": "adamw", "learning_rate": 4.2e-04, "b1": 0.9, "b2": 0.95,
        "eps": 1e-08, "weight_decay": 0.1}


def test_the_program_is_the_configurations(cell_and_family):
    cell, family = cell_and_family
    cfg = family.transformer_config(cell.config)
    assert (cfg.d_model, cfg.n_heads, cfg.kv_latent, cfg.qk_nope_dim,
            cfg.qk_rope_dim, cfg.v_head_dim) == (2048, 16, 512, 128, 64, 128)
    assert (cfg.d_ff_dense, cfg.d_ff, cfg.shared_experts,
            cfg.first_k_dense) == (10944, 1408, 2, 1)
    # the router keeps its published width; 8 experts are held, from 0
    assert (cfg.num_experts, cfg.experts_held, cfg.first_expert,
            cfg.experts_per_token) == (64, 8, 0, 6)
    assert cfg.rms_norm_eps == 1e-6 and cfg.balance_per_sequence
    # alpha_1 for each of the 9 expert layers' terms (the program averages)
    assert cfg.load_balance_coef == pytest.approx(9 * 0.001)
    assert cfg.router_z_coef == 0.0
    assert cfg.score_scale == pytest.approx(192 ** -0.5 * 1.5896, rel=1e-4)
    assert (cfg.attention, cfg.attn, cfg.remat, str(cfg.dtype)) == \
        ("mla", "flash", True, "bfloat16")
    assert family.first_expert(dict(
        cell.config, deployment={"chip": 3})) == 24
    with pytest.raises(ValueError, match="no equations"):
        family.transformer_config(dict(cell.config, q_lora_rank=1536))
    with pytest.raises(ValueError, match="constants"):
        family.transformer_config(dict(cell.config, rms_norm_eps=1e-5))


def test_parameters_and_bytes_as_the_configuration_file_says(cell_and_family):
    cell, family = cell_and_family
    import jax
    from horovod_tpu.models import transformer as tfm
    cfg = family.transformer_config(cell.config)
    shapes = jax.eval_shape(lambda k: tfm.init(k, cfg), jax.random.PRNGKey(0))

    def count(tree):
        return sum(x.size for x in jax.tree_util.tree_leaves(tree))

    mla = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 2048 * 2048
    assert mla == 13_762_560
    norms = 2 * 2048 + 512
    dense = mla + 3 * 2048 * 10944 + norms
    expert_layer = mla + 3 * 2048 * 2816 + 2048 * 64 \
        + 8 * 3 * 2048 * 1408 + norms
    assert count(shapes["dense_layers"]) == dense == 81_007_104
    assert count(shapes["layers"]) == 9 * expert_layer == 9 * 100_405_760
    total = count(shapes)
    assert total == dense + 9 * expert_layer + 2 * 12800 * 2048 + 2048 \
        == 1_037_089_792
    # bf16 weight, gradient and two Adam moments: 8.30 GB = 7.73 GiB
    assert 8 * total / 2 ** 30 == pytest.approx(7.73, abs=0.01)
    assert all(x.dtype == "bfloat16" for x in
               jax.tree_util.tree_leaves(shapes))
    # uncut, one expert layer alone is 584.9 M parameters = 4.68 GB
    whole = expert_layer + 56 * 3 * 2048 * 1408
    assert whole == pytest.approx(584.9e6, rel=1e-3)
    assert shapes["layers"]["router"].shape == (9, 2048, 64)
    assert shapes["layers"]["we1"].shape == (9, 8, 2048, 1408)


def test_flops_per_token_by_hand(cell_and_family):
    cell, family = cell_and_family
    parts = family.forward_flops_per_token(cell.config, 4096)
    assert parts["projections"] == 10 * 2 * 13_762_560
    # q.k at 192 and p.v at 128 over the causal half, 16 heads
    assert parts["attention"] == 10 * 2 * 16 * 320 * 4097 / 2
    assert parts["dense_mlp"] == 2 * 3 * 2048 * 10944 == 134_479_872
    assert parts["shared_experts"] == 9 * 2 * 3 * 2048 * 2816
    assert parts["router"] == 9 * 2 * 2048 * 64
    # 6 x 8 / 64 of an expert a token: the held experts' expected work
    assert parts["experts"] == 9 * 0.75 * 2 * 3 * 2048 * 1408
    assert parts["head"] == 2 * 2048 * 12800
    forward = sum(parts.values())
    assert forward == pytest.approx(1.1025e9, rel=1e-4)
    assert family.flops_per_sample(cell.config, cell.traffic) == \
        pytest.approx(3 * forward)
    # an expert layer by forward multiply-adds a token: the new mechanisms
    # (latent attention, shared and held routed experts) do 86% of a layer
    layer = {k: parts[k] / 9 for k in ("shared_experts", "router", "experts")}
    layer["projections"] = parts["projections"] / 10
    layer["attention"] = parts["attention"] / 10
    whole = sum(layer.values())
    assert layer["projections"] / whole == pytest.approx(0.285, abs=0.002)
    assert layer["attention"] / whole == pytest.approx(0.218, abs=0.002)
    assert layer["shared_experts"] / whole == pytest.approx(0.359, abs=0.002)
    assert layer["experts"] / whole == pytest.approx(0.135, abs=0.002)
    assert family.samples_per_step(cell.traffic, 1) == 8192
    assert family.flash_kernel_shape(cell.config, cell.traffic) == \
        (2, 16, 4096, 192, 128)
    # 8,192 x 6 x 8 / 64 expected held rows: 768 an expert
    assert family.grouped_matmul_shape(cell.config, cell.traffic) == \
        (6144, 2048, 1408, 8)


def test_flash_work_at_unequal_widths_by_hand():
    """(2, 16, 4,096, 192 | 128): the causal half holds 2 x 16 x 4,096^2 / 2
    score entries; a product with q or k is 192 deep, one with v or do 128."""
    roof = reader("mla_flash_roofline")
    shape = (2, 16, 4096, 192, 128)
    half = 2 * 16 * 4096 * 4096 // 2
    qk, vo = 2 * half * 192, 2 * half * 128
    wide, narrow = 2 * 16 * 4096 * 192 * 2, 2 * 16 * 4096 * 128 * 2
    lse = 2 * 16 * 4096 * 4
    assert roof.causal_work("forward", shape) == (
        qk + vo, 2 * wide + 2 * narrow + lse)
    assert roof.causal_work("forward", shape)[0] == 171_798_691_840
    assert roof.causal_work("dkdv", shape) == (
        2 * qk + 2 * vo, 3 * wide + 4 * narrow + lse)
    assert roof.causal_work("dq", shape) == (
        2 * qk + vo, 3 * wide + 3 * narrow + lse)
    # a layer's kernels (forward twice under remat): 0.96 TFLOP, as the
    # issue reckons, 4.9 ms at the peak
    layer = 2 * roof.causal_work("forward", shape)[0] \
        + roof.causal_work("dkdv", shape)[0] + roof.causal_work("dq", shape)[0]
    assert layer == pytest.approx(0.96e12, rel=0.01)
    v5e = peaks.for_kind("TPU v5 lite")
    assert layer / v5e.bf16_flops == pytest.approx(4.9e-3, rel=0.01)
    for kind in ("forward", "dkdv", "dq"):
        assert roof.least_seconds(kind, shape, v5e)[1] == "compute"
    # at one width it is the accepted flash reader's count
    old = reader("flash_roofline")
    for kind in ("forward", "dkdv", "dq"):
        assert roof.causal_work(kind, (2, 16, 4096, 128, 128)) == \
            old.causal_work(kind, (2, 16, 4096, 128))
    with pytest.raises(ValueError):
        roof.causal_work("other", shape)


# ---------------------------------------------------------------- readers

#: A compiled step in miniature: the latent attention's projection and
#: rotation, its three flash kernels (forward under its scope, the backward
#: kernels as the compiler may name them), the shared experts, the routed
#: experts' grouped matmul and router, and a fusion of the block.
HLO_TEXT = """
HloModule jit_step

ENTRY %main (a: bf16[8,128]) -> bf16[8,128] {
  %a = bf16[8,128]{1,0} parameter(0)
  %fusion.1 = bf16[8,192]{1,0} fusion(%a), kind=kOutput, calls=%f1, metadata={op_name="jit(step)/jvp()/while/body/closed_call/mla.project/dot_general"}
  %while.2 = (s32[]{:T(128)}, bf16[8,128]{1,0}) while(%t), condition=%c, body=%b, metadata={op_name="jit(step)/jvp()/while/body/closed_call/mla.rope/while"}
  %fusion.3 = bf16[8,192]{1,0} fusion(%q), kind=kLoop, calls=%f3, metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/mla.rope/concatenate"}
  %mla.attend.4 = (bf16[2,64,128]{2,1,0}, f32[2,64,1]{2,1,0}) custom-call(%q, %k, /*index=2*/%v), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp()/while/body/closed_call/mla.attend/pallas_call"}
  %transpose_jvp___.5 = (bf16[2,64,192]{2,1,0}, bf16[2,64,128]{2,1,0}) custom-call(%q, %k, %v, %o, %do, /*index=5*/%lse), custom_call_target="tpu_custom_call"
  %transpose_jvp___.6 = bf16[2,64,192]{2,1,0} custom-call(%q, %k, %v, %o, %do, /*index=5*/%lse), custom_call_target="tpu_custom_call"
  %fusion.7 = bf16[8,128]{1,0} fusion(%o), kind=kOutput, calls=%f7, metadata={op_name="jit(step)/jvp()/while/body/closed_call/mla.out/dot_general"}
  %fusion.8 = bf16[8,256]{1,0} fusion(%h), kind=kOutput, calls=%f8, metadata={op_name="jit(step)/jvp()/while/body/closed_call/moe.shared/dot_general"}
  %fusion.9 = f32[8,8]{1,0} fusion(%h), kind=kOutput, calls=%f9, metadata={op_name="jit(step)/jvp()/while/body/closed_call/moe.route/dot_general"}
  %ragged-dot-metadata = (s32[9]{0}, s32[15]{0}, s32[15]{0}, s32[1]{0}) custom-call(%sizes), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %ragged-dot-none.1 = bf16[64,64]{1,0} custom-call(%m0, %m1, %m2, %m3, %m0, /*index=5*/%rows, %w), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  ROOT %fusion.10 = bf16[8,128]{1,0} fusion(%a), kind=kLoop, calls=%f10, metadata={op_name="jit(step)/jvp()/while/body/closed_call/add"}
}
"""

#: per step, in microseconds: (name, start, duration)
STEP_OPS = (("%while.2 = (s32[]{:T(128)}, bf16[8,128]{1,0}) while(%t), "
             "condition=%c, body=%b", 0, 9),   # spans fusion.3: not counted
            ("fusion.1", 9, 4), ("fusion.3", 2, 6), ("mla.attend.4", 13, 10),
            ("mla.attend.4", 23, 10), ("transpose_jvp___.5", 33, 14),
            ("transpose_jvp___.6", 47, 11), ("fusion.7", 58, 3),
            ("fusion.8", 61, 7), ("fusion.9", 68, 2),
            ("ragged-dot-metadata", 70, 1), ("ragged-dot-none.1", 71, 8),
            ("fusion.10", 79, 5))


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


def _run(trace, table, text=HLO_TEXT, shape=(2, 2, 64, 192, 128)):
    program = type("P", (), {"as_text": staticmethod(lambda: text)})
    family = type("F", (), {
        "flash_kernel_shape": staticmethod(lambda c, t: shape),
        "grouped_matmul_shape": staticmethod(lambda c, t: (64, 128, 64, 8))})
    return fakes.fake_run(trace, table, program=program,
                          peaks=peaks.for_kind("TPU v5 lite"), family=family,
                          cell=type("C", (), {"config": {}, "traffic": {}}))


def test_scopes_and_kernels_are_told_from_the_programs_own_text(table):
    assert scope_time.names_under(HLO_TEXT, table, "mla.") == {
        "fusion.1", "fusion.3", "mla.attend.4", "fusion.7"}   # not the loop
    assert scope_time.names_under(HLO_TEXT, table, "moe.shared") == \
        {"fusion.8"}
    assert scope_time.names_under(HLO_TEXT, table, "none.") == set()
    assert reader("mla_flash_roofline").flash_kernels(table) == {
        "mla.attend.4": "forward", "transpose_jvp___.5": "dkdv",
        "transpose_jvp___.6": "dq"}
    # the accepted expert-layer readers see neither the shared experts nor
    # the flash kernels
    assert scopes.moe_parts(HLO_TEXT, table) == {
        "fusion.9": "route", "ragged-dot-metadata": "experts",
        "ragged-dot-none.1": "experts"}


def test_the_three_readers_on_a_hand_made_trace(trace, table):
    run = _run(trace, table)
    # scoped instructions and the three kernels, the forward counted once
    # though it is both scoped and a kernel
    assert reader("mla_ms_per_step").read(run) == pytest.approx(
        (4 + 6 + 10 + 10 + 14 + 11 + 3) * 1e-3)
    assert reader("moe_shared_ms_per_step").read(run) == pytest.approx(7e-3)
    assert reader("moe_ms_per_step").read(run) == pytest.approx(11e-3)
    roof = reader("mla_flash_roofline")
    v5e = peaks.for_kind("TPU v5 lite")
    shape = (2, 2, 64, 192, 128)
    least = 2 * roof.least_seconds("forward", shape, v5e)[0] \
        + roof.least_seconds("dkdv", shape, v5e)[0] \
        + roof.least_seconds("dq", shape, v5e)[0]
    assert roof.read(run) == pytest.approx(100 * least / (45 * US))
    # a family with one width is the accepted flash reader's, not this one's
    assert roof.read(_run(trace, table, shape=(2, 2, 64, 128))) is None


def test_a_program_without_the_scopes_reads_as_nothing():
    """The parent's program, or a cell of another family: every new reader
    returns None and raises nothing."""
    table = hlo.index(fakes.HLO_TEXT)
    trace = xplane.reduce_profile(
        ProfileData.from_text_proto(fakes.hand_made_xspace()))
    run = _run(trace, table, text=fakes.HLO_TEXT, shape=(2, 2, 64, 128))
    assert [reader(m).read(run) for m in NEW] == [None] * 3
    program = type("P", (), {"as_text": staticmethod(lambda: fakes.HLO_TEXT)})
    for bare in (fakes.fake_run(None, {}, program=program, peaks=None),
                 fakes.fake_run(xplane.Trace(), {}, program=None,
                                peaks=None, family=None)):
        assert [reader(m).read(bare) for m in NEW] == [None] * 3


def test_the_entries_are_the_cells_and_name_their_layers():
    cell = spec.load_cell("dsv2lite-1chip")
    assert [m["name"] for m in cell.end_to_end] == [
        "samples_per_s_per_chip", "step_hbm_gib", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert names == ["init_s", "compile_s", "device_step_ms", "mfu",
                     "device_idle_share", "window_stall_share",
                     "moe_ms_per_step", "moe_experts_ms_per_step",
                     "moe_dispatch_ms_per_step", "moe_experts_roofline",
                     *NEW]
    layers = {m["name"]: m["layer"] for m in cell.per_layer}
    assert (layers["mla_ms_per_step"], layers["mla_flash_roofline"],
            layers["moe_shared_ms_per_step"]) == (
        "latent attention", "latent attention", "expert layer")
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [w["name"] for w in bench["workloads"]][-1] == "dsv2lite-1chip"
    assert [c["name"] for c in bench["configs"]][-1] == "deepseek-v2-lite"
    assert [m["name"] for m in bench["per_layer"]][-3:] == list(NEW)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in JOINED:
            assert m["workloads"][-1] == "dsv2lite-1chip", m["name"]
        elif m["name"] in NEW:
            assert m["workloads"] == ["dsv2lite-1chip"]
        else:   # the flash readers take every Mosaic kernel for their own
            assert "dsv2lite-1chip" not in m.get("workloads", ()), m["name"]


# -------------------------------------------------------------- rehearsal

@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_the_cell_runs_end_to_end_at_a_tiny_size(trace, tmp_path, capfd):
    hvd.shutdown()   # the cell initialises on exactly its own devices
    cell = spec.load_cell("tiny-dsv2lite-1chip", root=TINY)
    assert cell.config["family"] == "deepseek_v2"
    try:
        line = json.loads(runner.run_cell(
            cell, seed=2**31 + 7, seconds=0.5, trace=trace,
            t0=time.perf_counter(), platform="cpu", checkout=str(tmp_path)))
    finally:
        hvd.shutdown()
    log = capfd.readouterr().err
    assert line["device"]["platform"] == "cpu"
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert "reference check: {'ok': True" in log
    assert "held experts" in log
    assert "compile request(s) after warm-up" not in log
    problems = [ln for ln in log.splitlines() if "NOT CORRECT" in ln]
    if trace:   # the one thing a CPU trace cannot show
        assert ["the trace holds no whole step" in p for p in problems] == \
            [True]
    else:
        assert problems == [] and line["correct"] is True
    # no time, rate or share from the CPU under a device metric's name
    assert line["metrics"] == {}
