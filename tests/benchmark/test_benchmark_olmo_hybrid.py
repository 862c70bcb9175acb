"""What PR 32 adds to the benchmark: the `olmo_hybrid` family's arithmetic
against the configuration's published numbers, the five new readers
(`gdn_ms_per_step`, `gdn_scan_ms_per_step`, `gdn_scan_roofline`,
`attn_flash_ms_per_step`, `attn_flash_roofline`) with the counts they rest
on, on a hand-made trace, and the cell's path rehearsed at a tiny size on
the CPU (`fixtures/tiny-olmo-hybrid`)."""

import json
import os
import time

import pytest
from jax.profiler import ProfileData

import benchmark_fakes as fakes
import horovod_tpu as hvd
from benchmark.harness import hlo, peaks, runner, scope_time, spec, xplane

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                    "tiny-olmo-hybrid")
US = 1e-6
CELL = "olmohybrid-1chip"
NEW = ("gdn_ms_per_step", "gdn_scan_ms_per_step", "gdn_scan_roofline",
       "attn_flash_ms_per_step", "attn_flash_roofline")
JOINED = ("samples_per_s_per_chip", "step_hbm_gib", "device_step_ms", "mfu",
          "device_idle_share", "window_stall_share")


def reader(name):
    return spec.load_module("layer_metrics", name, (spec.PACKAGE_DIR,))


@pytest.fixture(scope="module")
def cell_and_family():
    cell = spec.load_cell(CELL)
    return cell, spec.load_module("families", cell.config["family"],
                                  cell.dirs)


# ------------------------------------------------------------ arithmetic

def test_the_configuration_holds_the_published_numbers(cell_and_family):
    """Every number of the catalog row's `config`, letter for letter, but
    the vocabulary, whose published size stands beside; the depth the
    program reads is `n_layer`."""
    cell, _ = cell_and_family
    pattern = ["linear_attention"] * 3 + ["full_attention"]
    published = {
        "model_type": "olmo_hybrid", "hidden_size": 3840,
        "intermediate_size": 11008, "num_hidden_layers": 32,
        "num_attention_heads": 30, "num_key_value_heads": 30,
        "hidden_act": "silu", "max_position_embeddings": 65536,
        "attention_bias": False, "rms_norm_eps": 1e-06,
        "tie_word_embeddings": False, "layer_types": pattern * 8,
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}}
    assert {k: cell.config[k] for k in published} == published
    assert cell.config["published"] == {"num_hidden_layers": 32,
                                        "vocab_size": 100352}
    assert sorted(cell.config["reduced"]) == ["n_layer", "vocab_size"]
    # the floors: one whole period and at least four layers; an eighth of
    # the vocabulary
    assert (cell.config["n_layer"], cell.config["vocab_size"]) == (4, 12544)
    assert 12544 * 8 == 100352
    for key in ("assumed", "departures", "deployment"):
        assert cell.config[key]
    assert set(cell.config["assumed"]) >= {
        "norm_placement", "no_rotary_embedding", "gated_deltanet",
        "optimizer", "sequence"}
    deployment = cell.config["deployment"]
    assert (deployment["chips_sharing_a_layer"],
            deployment["chips_sharing_the_vocabulary"],
            deployment["chip"]) == (1, 8, 0)
    assert (cell.traffic["seq_len"], cell.traffic["per_chip_batch"],
            cell.traffic["mesh"], cell.traffic["trace_steps"],
            cell.chips) == (8192, 1, {}, 5, 1)
    assert cell.traffic["optimizer"] == {
        "name": "adamw", "learning_rate": 3e-04, "b1": 0.9, "b2": 0.95,
        "eps": 1e-08, "weight_decay": 0.1}


def test_the_program_is_the_configurations(cell_and_family):
    cell, family = cell_and_family
    cfg = family.transformer_config(cell.config)
    assert cfg.layer_pattern == ("linear", "linear", "linear", "full")
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff, cfg.n_layers,
            cfg.vocab) == (3840, 30, 128, 11008, 4, 12544)
    assert (cfg.gdn_heads, cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.gdn_conv,
            cfg.gdn_neg_eigval) == (30, 96, 192, 4, True)
    assert (cfg.norm, cfg.rms_norm_eps, cfg.positions, cfg.qk_norm, cfg.mlp,
            cfg.post_norm) == ("rmsnorm", 1e-6, "none", True, "swiglu", True)
    assert cfg.num_experts == 0 and cfg.score_scale is None
    assert (cfg.attention, cfg.attn, cfg.remat, cfg.remat_policy,
            str(cfg.dtype)) == ("mha", "flash", True, "dots", "bfloat16")
    with pytest.raises(ValueError, match="no equations"):
        family.transformer_config(dict(cell.config, linear_num_key_heads=15))
    with pytest.raises(ValueError, match="no equations"):
        family.transformer_config(dict(
            cell.config, rope_parameters={"rope_theta": 500000}))
    with pytest.raises(ValueError, match="constant"):
        family.transformer_config(dict(cell.config, rms_norm_eps=1e-5))
    with pytest.raises(ValueError, match="whole number of periods"):
        family.transformer_config(dict(cell.config, n_layer=6))
    # the period is read off the published list, not assumed to be four
    assert family.layer_pattern(dict(
        cell.config, n_layer=2,
        layer_types=["linear_attention", "full_attention"] * 16)) == \
        ("linear", "full")


def test_parameters_and_bytes_as_the_configuration_file_says(cell_and_family):
    cell, family = cell_and_family
    import jax
    from horovod_tpu.models import transformer as tfm
    cfg = family.transformer_config(cell.config)
    shapes = jax.eval_shape(lambda k: tfm.init(k, cfg), jax.random.PRNGKey(0))

    def count(tree):
        return sum(x.size for x in jax.tree_util.tree_leaves(tree))

    d, f = 3840, 11008
    mixer = (2 * d * 30 * 96 + 2 * d * 30 * 192 + 30 * 192 * d   # q k, v z, o
             + 2 * d * 30 + 2 * 30                     # a, b; A_log, dt_bias
             + (2 * 96 + 192) * 30 * 4 + 192)          # taps; the gated norm
    assert mixer == 88_750_332
    mlp, norms = 3 * d * f, 2 * d
    assert mlp == 126_812_160
    linear = mixer + mlp + norms
    full = 4 * d * d + 2 * d + mlp + norms             # + the QK-norm scales
    assert (linear, full) == (215_570_172, 185_809_920)
    assert count(shapes["layers"]["linear"]) == 3 * linear
    assert count(shapes["layers"]["full"]) == full
    period = 3 * linear + full
    assert period / 4 == pytest.approx(208.1e6, rel=1e-3)   # "about 208M"
    total = count(shapes)
    assert total == period + 2 * 12544 * d + d == 928_862_196
    # bf16 weight, gradient and two Adam moments: 7.43 GB = 6.92 GiB
    assert 8 * total / 2 ** 30 == pytest.approx(6.92, abs=0.01)
    assert all(x.dtype == "bfloat16" for x in
               jax.tree_util.tree_leaves(shapes))
    assert shapes["layers"]["linear"]["gdn_wv"].shape == (1, 3, d, 30, 192)
    assert shapes["layers"]["full"]["wq"].shape == (1, 1, d, 30, 128)
    # the numbers the configuration file writes out
    assert "928,862,196 parameters x 8 bytes" in \
        cell.config["reduced"]["n_layer"]


def test_flops_per_token_by_hand(cell_and_family):
    cell, family = cell_and_family
    parts = family.forward_flops_per_token(cell.config, 8192)
    d = 3840
    assert parts["mlps"] == 4 * 2 * 3 * d * 11008 == 1_014_497_280
    assert parts["linear_projections"] == 3 * 2 * (
        2 * d * 2880 + 3 * d * 5760 + 2 * d * 30) == 532_224_000
    assert parts["rule"] == 3 * 2 * 3 * 30 * 96 * 192 == 9_953_280
    assert parts["full_projections"] == 2 * 4 * d * d == 117_964_800
    # q.k and p.v over the causal half, 30 heads of 128
    assert parts["attention"] == 2 * 2 * d * 8193 / 2 == 62_922_240
    assert parts["head"] == 2 * d * 12544 == 96_337_920
    forward = sum(parts.values())
    assert forward == pytest.approx(1.834e9, rel=1e-3)
    assert family.flops_per_sample(cell.config, cell.traffic) == \
        pytest.approx(3 * forward) == pytest.approx(5.50e9, rel=1e-3)
    # the head keeps its published share of the forward multiply-adds
    assert parts["head"] / forward == pytest.approx(0.0525, abs=0.001)
    # what no other cell runs: the linear mixers, 30% of the forward pass
    assert (parts["linear_projections"] + parts["rule"]) / forward == \
        pytest.approx(0.296, abs=0.002)
    assert family.samples_per_step(cell.traffic, 1) == 8192
    assert family.flash_kernel_shape(cell.config, cell.traffic) == \
        (1, 30, 8192, 128)


def test_the_scans_least_work_by_hand(cell_and_family):
    cell, family = cell_and_family
    rows = 8192 * 30                                   # (token, head) pairs
    forward, backward = family.rule_work(rows, 96, 192)
    # q, k 96 wide and v 192 in bf16, g and beta in float32, o 192 in bf16
    assert forward == (2 * 3 * 96 * 192 * rows,
                       rows * (2 * 96 * 2 + 192 * 2 + 8 + 192 * 2))
    assert forward == (27_179_089_920, 285_081_600)
    # reads those and do, writes dq, dk, dv, dg, dbeta
    assert backward == (2 * forward[0],
                        rows * (1160 + 2 * 96 * 2 + 192 * 2 + 8))
    assert backward[1] == 475_791_360
    work = family.gdn_scan_work(cell.config, cell.traffic)
    # three linear layers; under remat the forward runs twice
    assert work == ((6, *forward), (3, *backward))
    assert family.gdn_scan_work(
        dict(cell.config, program=dict(cell.config["program"], remat=False)),
        cell.traffic)[0][0] == 3
    roof = reader("gdn_scan_roofline")
    v5e = peaks.for_kind("TPU v5 lite")
    fwd_s, bound = roof.least_seconds(work[0], v5e)
    assert bound == "memory"               # 0.348 ms a pass against 0.138
    assert fwd_s == pytest.approx(6 * 285_081_600 / 819e9)
    bwd_s, bound = roof.least_seconds(work[1], v5e)
    assert bound == "memory"
    # 3.83 ms a step at the least: under 1% of a 540 ms step
    assert (fwd_s + bwd_s) * 1e3 == pytest.approx(3.83, abs=0.01)


# ---------------------------------------------------------------- readers

#: A compiled step in miniature: a linear layer's projection, convolution,
#: scan (a loop, a fusion inside it, its remat repeat, a Mosaic kernel a
#: later PR may put under the scope), gate and output; the full layer's
#: projection and its three flash kernels at (1 x 2, 64, 128); a kernel of
#: a flash signature but another shape; an MLP fusion outside every scope.
HLO_TEXT = """
HloModule jit_step

ENTRY %main (a: bf16[8,128]) -> bf16[8,128] {
  %a = bf16[8,128]{1,0} parameter(0)
  %fusion.1 = bf16[8,96]{1,0} fusion(%a), kind=kOutput, calls=%f1, metadata={op_name="jit(step)/jvp()/while/body/closed_call/checkpoint/gdn.project/bsd,dhk->bhsk/dot_general"}
  %fusion.2 = bf16[8,96]{1,0} fusion(%q), kind=kLoop, calls=%f2, metadata={op_name="jit(step)/jvp()/while/body/closed_call/checkpoint/gdn.conv/mul"}
  %while.3 = (s32[]{:T(128)}, f32[2,96,192]{2,1,0}) while(%t), condition=%c, body=%b, metadata={op_name="jit(step)/jvp()/while/body/closed_call/checkpoint/gdn.scan/while"}
  %fusion.4 = f32[2,96,192]{2,1,0} fusion(%s), kind=kOutput, calls=%f4, metadata={op_name="jit(step)/jvp()/while/body/closed_call/checkpoint/gdn.scan/while/body/bhik,bhkv->bhiv/dot_general"}
  %fusion.5 = f32[2,96,192]{2,1,0} fusion(%s), kind=kOutput, calls=%f5, metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/gdn.scan/while/body/bhik,bhkv->bhiv/dot_general"}
  %gdn.scan.6 = bf16[2,64,192]{2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp()/while/body/closed_call/checkpoint/gdn.scan/pallas_call"}
  %fusion.7 = bf16[8,192]{1,0} fusion(%o), kind=kLoop, calls=%f7, metadata={op_name="jit(step)/jvp()/while/body/closed_call/checkpoint/gdn.gate/mul"}
  %fusion.8 = bf16[8,128]{1,0} fusion(%o), kind=kOutput, calls=%f8, metadata={op_name="jit(step)/jvp()/while/body/closed_call/checkpoint/gdn.out/dot_general"}
  %fusion.9 = bf16[8,128]{1,0} fusion(%a), kind=kOutput, calls=%f9, metadata={op_name="jit(step)/jvp()/while/body/closed_call/checkpoint/attn.project/dot_general"}
  %attn.attend.10 = (bf16[2,64,128]{2,1,0}, f32[2,64,1]{2,1,0}) custom-call(%q, %k, /*index=2*/%v), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp()/while/body/closed_call/checkpoint/attn.attend/pallas_call"}
  %attn.attend.11 = (bf16[2,64,128]{2,1,0}, bf16[2,64,128]{2,1,0}) custom-call(%q, %k, %v, %o, %do, /*index=5*/%lse), custom_call_target="tpu_custom_call"
  %attn.attend.12 = bf16[2,64,128]{2,1,0} custom-call(%q, %k, %v, %o, %do, /*index=5*/%lse), custom_call_target="tpu_custom_call"
  %other.13 = (bf16[2,64,192]{2,1,0}, f32[2,64,1]{2,1,0}) custom-call(%q, %k, /*index=2*/%v), custom_call_target="tpu_custom_call"
  ROOT %fusion.14 = bf16[8,128]{1,0} fusion(%a), kind=kOutput, calls=%f14, metadata={op_name="jit(step)/jvp()/while/body/closed_call/checkpoint/bsd,df->bsf/dot_general"}
}
"""

#: per step, in microseconds: (name, start, duration)
STEP_OPS = (("fusion.1", 0, 5), ("fusion.2", 5, 3),
            ("%while.3 = (s32[]{:T(128)}, f32[2,96,192]{2,1,0}) while(%t), "
             "condition=%c, body=%b", 8, 20),   # spans fusion.4: not counted
            ("fusion.4", 9, 6), ("fusion.4", 16, 6), ("fusion.5", 28, 7),
            ("gdn.scan.6", 35, 4), ("fusion.7", 39, 2), ("fusion.8", 41, 3),
            ("fusion.9", 44, 4), ("attn.attend.10", 48, 10),
            ("attn.attend.10", 58, 10), ("attn.attend.11", 68, 14),
            ("attn.attend.12", 82, 11), ("other.13", 93, 2),
            ("fusion.14", 95, 3))


@pytest.fixture(scope="module")
def table():
    return hlo.index(HLO_TEXT)


@pytest.fixture(scope="module")
def trace():
    rows = [(n, step * 100 + start, dur) for step in range(5)
            for n, start, dur in STEP_OPS]
    modules = [("jit_step(1)", step * 100, 99) for step in range(5)]
    return xplane.reduce_profile(ProfileData.from_text_proto(fakes._plane(
        1, "/device:TPU:0", [("XLA Modules", modules), ("XLA Ops", rows)])))


SCAN_WORK = ((2, 3.0e6, 4.0e3), (1, 6.0e6, 7.0e3))


def _run(trace, table, text=HLO_TEXT, shape=(1, 2, 64, 128), work=SCAN_WORK):
    program = type("P", (), {"as_text": staticmethod(lambda: text)})
    family = type("F", (), {
        "flash_kernel_shape": staticmethod(lambda c, t: shape),
        "gdn_scan_work": staticmethod(lambda c, t: work)})
    return fakes.fake_run(trace, table, program=program,
                          peaks=peaks.for_kind("TPU v5 lite"), family=family,
                          cell=type("C", (), {"config": {}, "traffic": {}}))


def test_scopes_and_kernels_are_told_from_the_programs_own_text(table):
    assert scope_time.names_under(HLO_TEXT, table, "gdn.") == {
        "fusion.1", "fusion.2", "fusion.4", "fusion.5", "gdn.scan.6",
        "fusion.7", "fusion.8"}                       # not the loop itself
    assert scope_time.names_under(HLO_TEXT, table, "gdn.scan") == {
        "fusion.4", "fusion.5", "gdn.scan.6"}
    assert scope_time.names_under(HLO_TEXT, table, "attn.") == {
        "fusion.9", "attn.attend.10"}
    roof = reader("attn_flash_roofline")
    # by signature AND shape: not the scan's kernel (three operands, one
    # result), not the flash-like kernel of another width
    assert roof.flash_kernels(table, (1, 2, 64, 128)) == {
        "attn.attend.10": "forward", "attn.attend.11": "dkdv",
        "attn.attend.12": "dq"}
    assert roof.flash_kernels(table, (1, 2, 64, 192)) == {
        "other.13": "forward"}
    assert roof.flash_kernels(table, (1, 2, 128, 128)) == {}


def test_the_five_readers_on_a_hand_made_trace(trace, table):
    run = _run(trace, table)
    assert reader("gdn_ms_per_step").read(run) == pytest.approx(
        (5 + 3 + 6 + 6 + 7 + 4 + 2 + 3) * 1e-3)
    # the kernel under the scope is counted without an edit
    assert reader("gdn_scan_ms_per_step").read(run) == pytest.approx(
        (6 + 6 + 7 + 4) * 1e-3)
    v5e = peaks.for_kind("TPU v5 lite")
    least = 2 * max(3.0e6 / v5e.bf16_flops, 4.0e3 / v5e.hbm_bytes_per_s) \
        + max(6.0e6 / v5e.bf16_flops, 7.0e3 / v5e.hbm_bytes_per_s)
    assert reader("gdn_scan_roofline").read(run) == pytest.approx(
        100 * least / (23 * US))
    assert reader("attn_flash_ms_per_step").read(run) == pytest.approx(
        (10 + 10 + 14 + 11) * 1e-3)
    roof, old = reader("attn_flash_roofline"), reader("flash_roofline")
    shape = (1, 2, 64, 128)
    flash_least = 2 * old.least_seconds("forward", shape, v5e)[0] \
        + old.least_seconds("dkdv", shape, v5e)[0] \
        + old.least_seconds("dq", shape, v5e)[0]
    assert roof.read(run) == pytest.approx(100 * flash_least / (45 * US))
    assert roof.least_seconds("dq", shape, v5e) == \
        old.least_seconds("dq", shape, v5e)
    # a family with two widths is the latent attention reader's
    wide = _run(trace, table, shape=(1, 2, 64, 192, 128))
    assert roof.read(wide) is None
    assert reader("attn_flash_ms_per_step").read(wide) is None


def test_a_program_without_the_scopes_reads_as_nothing(trace, table):
    """The parent's program, or a cell of another family: every new reader
    returns None and raises nothing."""
    plain = hlo.index(fakes.HLO_TEXT)
    old_trace = xplane.reduce_profile(
        ProfileData.from_text_proto(fakes.hand_made_xspace()))
    run = _run(old_trace, plain, text=fakes.HLO_TEXT, shape=(2, 1, 64, 128))
    assert [reader(m).read(run) for m in NEW] == [None] * 5
    program = type("P", (), {"as_text": staticmethod(lambda: fakes.HLO_TEXT)})
    for bare in (fakes.fake_run(None, {}, program=program, peaks=None),
                 fakes.fake_run(xplane.Trace(), {}, program=None,
                                peaks=None, family=None)):
        assert [reader(m).read(bare) for m in NEW] == [None] * 5
    # the scopes without a family that counts the scan's work: the times
    # read, the share does not
    family = type("F", (), {})
    no_work = fakes.fake_run(
        trace, table, peaks=peaks.for_kind("TPU v5 lite"), family=family,
        program=type("P", (), {"as_text": staticmethod(lambda: HLO_TEXT)}),
        cell=type("C", (), {"config": {}, "traffic": {}}))
    assert reader("gdn_scan_roofline").read(no_work) is None
    assert reader("gdn_scan_ms_per_step").read(no_work) == pytest.approx(
        23e-3)
    assert reader("attn_flash_roofline").read(no_work) is None


def test_the_entries_are_the_cells_and_name_their_layers():
    cell = spec.load_cell(CELL)
    assert [m["name"] for m in cell.end_to_end] == [
        "samples_per_s_per_chip", "step_hbm_gib", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert names == ["init_s", "compile_s", "device_step_ms", "mfu",
                     "device_idle_share", "window_stall_share", *NEW]
    layers = {m["name"]: m["layer"] for m in cell.per_layer}
    assert [layers[m] for m in NEW] == ["linear attention"] * 3 \
        + ["Pallas kernels"] * 2
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # by name and by order, never by "last": the next PR appends after these
    cells = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    metrics = [m["name"] for m in bench["per_layer"]]
    assert cells.index("dsv2lite-1chip") < cells.index(CELL)
    assert configs.index("deepseek-v2-lite") < configs.index("olmo-hybrid-7b")
    entry = bench["workloads"][cells.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "olmo-hybrid-7b", "spmd-dp1-s8192", 1)
    assert bench["configs"][configs.index("olmo-hybrid-7b")]["reduced"] == \
        ["n_layer", "vocab_size"]
    first = metrics.index(NEW[0])
    assert metrics[first:first + 5] == list(NEW)
    before = ("mla_ms_per_step", "mla_flash_roofline",
              "moe_shared_ms_per_step")             # dsv2lite-1chip's own
    assert metrics[first - 3:first] == list(before)
    for m in bench["end_to_end"] + bench["per_layer"]:
        listed = m.get("workloads", ())
        if m["name"] in JOINED:
            assert listed.index("dsv2lite-1chip") < listed.index(CELL), \
                m["name"]
        elif m["name"] in NEW:
            assert listed == [CELL]
            assert (m["source"], m["moves"]) == ("device_trace",
                                                 "samples_per_s_per_chip")
        else:   # the accepted flash readers take every Mosaic kernel
            assert CELL not in listed, m["name"]
        if m["name"] in before:
            assert listed == ["dsv2lite-1chip"]


# -------------------------------------------------------------- rehearsal

@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_the_cell_runs_end_to_end_at_a_tiny_size(trace, tmp_path, capfd):
    hvd.shutdown()   # the cell initialises on exactly its own devices
    cell = spec.load_cell("tiny-olmohybrid-1chip", root=TINY)
    assert cell.config["family"] == "olmo_hybrid"
    try:
        line = json.loads(runner.run_cell(
            cell, seed=2**31 + 11, seconds=0.5, trace=trace,
            t0=time.perf_counter(), platform="cpu", checkout=str(tmp_path)))
    finally:
        hvd.shutdown()
    log = capfd.readouterr().err
    assert line["device"]["platform"] == "cpu"
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert "reference check: {'ok': True" in log
    assert "token by token in the reference" in log
    assert "compile request(s) after warm-up" not in log
    problems = [ln for ln in log.splitlines() if "NOT CORRECT" in ln]
    if trace:   # the one thing a CPU trace cannot show
        assert ["the trace holds no whole step" in p for p in problems] == \
            [True]
    else:
        assert problems == [] and line["correct"] is True
    # no time, rate or share from the CPU under a device metric's name
    assert line["metrics"] == {}
