"""What PR 54 adds to the benchmark: the `lfm2_moe` family's configuration
against the published numbers, its limits with their readings, the program it
makes of the configuration, the three new readers (`shortconv_ms_per_step`,
`shortconv_mix_ms_per_step`, `shortconv_mix_roofline`) on a hand-made trace
and the family's `shortconv_mix_work` by hand, the entries BY NAME (never by
position or as "the last": the next PR appends after these), and the cell's
path rehearsed at a tiny size on the CPU (`fixtures/tiny-lfm2moe`). The
family's parameters and FLOPs are held by hand in
`tests/test_lfm2_moe_stack.py`."""

import json
import os
import time

import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

import benchmark_fakes as fakes
import horovod_tpu as hvd
from benchmark.harness import hlo, peaks, runner, spec, xplane

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                    "tiny-lfm2moe")
US = 1e-6
CELL = "lfm2moe-1chip"
CONFIG = "lfm2-24b-a2b"
TRAFFIC = "spmd-dp1-s16384-lfm2moe"
NEW = ("shortconv_ms_per_step", "shortconv_mix_ms_per_step",
       "shortconv_mix_roofline")
#: the lists the cell joined: every LM cell's and the expert layer's four
JOINED = ("samples_per_s_per_chip", "step_hbm_gib", "device_step_ms", "mfu",
          "device_idle_share", "window_stall_share")
MOE = ("moe_ms_per_step", "moe_experts_ms_per_step",
       "moe_dispatch_ms_per_step", "moe_experts_roofline")
#: the lists whose readers would read this program right and which it did
#: NOT join: an older test of this directory holds each to the letter
#: (PERF.md section 7)
HELD_TO_THE_LETTER = ("attn_ms_per_step", "mlp_ms_per_step",
                      "vocab_ms_per_step", "opt_update_ms_per_step",
                      "other_ms_per_step", "full_flash_ms_per_step",
                      "full_flash_roofline")
LAYER_TYPES = ["conv", "conv", "full_attention", "conv"] * 10


def reader(name):
    return spec.load_module("layer_metrics", name, (spec.PACKAGE_DIR,))


@pytest.fixture(scope="module")
def cell_and_family():
    cell = spec.load_cell(CELL)
    return cell, spec.load_module("families", cell.config["family"],
                                  cell.dirs)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


# --------------------------------------------------------- the configuration

def test_the_configuration_holds_the_published_numbers(cell_and_family):
    """Every number of the catalog row's `config`, letter for letter, but
    the two counts of what is held, whose published sizes stand beside; the
    depth the program reads is `n_layer`."""
    cell, _ = cell_and_family
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "layer_types": LAYER_TYPES,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1536, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True}
    assert {k: cell.config[k] for k in published} == published
    assert cell.config["published"] == {
        "num_hidden_layers": 40, "num_experts": 64, "vocab_size": 65536}
    assert sorted(cell.config["reduced"]) == ["n_layer", "num_experts",
                                              "vocab_size"]
    assert cell.config["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    # the floors: a whole period and at least four layers behind the dense
    # one, at least 8 experts, at least an eighth of the vocabulary
    assert (cell.config["n_layer"], cell.config["num_experts"],
            cell.config["vocab_size"]) == (9, 8, 8192)
    assert 8192 * 8 == 65536 and 8 * 8 == 64
    for key in ("assumed", "departures", "deployment", "check"):
        assert cell.config[key]
    assert set(cell.config["assumed"]) >= {
        "tie_word_embeddings", "intermediate_size", "head_dim", "expert_block",
        "optimizer"}
    assert set(cell.config["departures"]) >= {
        "selection_bias", "optimizer_state_dtype", "weight_decay", "mix",
        "documents", "row_buffer", "leading_dense_layers"}
    assert set(cell.config["check"]["limits"]) == {"LOGITS_RMS_TOL",
                                                   "LOSS_RTOL"}
    deployment = cell.config["deployment"]
    assert (deployment["chips_sharing_a_layer"], deployment["chip"],
            deployment["expert_rank"], deployment["first_layer"]) == (
        8, 0, 0, 1)
    assert "no code stands in" in deployment["how"]
    assert "GiB" in deployment["step_hbm_gib"]
    # the issue's rule and what it found are in the file
    assert "layers 1-9" in cell.config["reduced"]["n_layer"]
    assert "pipeline stages" in cell.config["reduced"]["n_layer"]
    assert (cell.traffic["seq_len"], cell.traffic["per_chip_batch"],
            cell.traffic["mesh"], cell.traffic["trace_steps"],
            cell.traffic["path"], cell.chips) == (16384, 1, {}, 5,
                                                  "tfm_spmd", 1)
    assert cell.traffic["optimizer"] == {
        "name": "adamw", "learning_rate": 3e-04, "b1": 0.9, "b2": 0.95,
        "eps": 1e-08, "weight_decay": 0.1}


def test_the_limits_are_the_familys_with_their_readings(cell_and_family):
    cell, family = cell_and_family
    limits = cell.config["check"]["limits"]
    assert limits["LOGITS_RMS_TOL"].startswith(
        f"{family.LOGITS_RMS_TOL:g} = ")
    assert limits["LOSS_RTOL"].startswith(f"{family.LOSS_RTOL:g} of ")
    for text in limits.values():
        assert "my chip run, PR 54" in text
    for word in ("e4m3", "e5m2", "bf16 operands", "seeds"):
        assert word in limits["LOGITS_RMS_TOL"], word
    for fault in family.reference.FAULTS:
        assert fault in limits["LOGITS_RMS_TOL"], fault
    assert family.within(family.LOGITS_RMS_TOL, 1.0, 1.0) == (True, True)
    assert family.within(family.LOGITS_RMS_TOL * 1.01, 1.0,
                         1.0 + 1.01 * family.LOSS_RTOL) == (False, False)
    assert family.within(float("nan"), 1.0, 1.0)[0] is False


def test_the_program_is_the_configurations(cell_and_family):
    cell, family = cell_and_family
    cfg = family.transformer_config(cell.config)
    conv = "shortconv"
    assert family.kinds(cell.config) == (
        conv, "full", conv, conv, conv, "full", conv, conv, conv)
    assert cfg.layer_pattern == (conv, "full", conv, conv) == \
        family.pattern(cell.config)
    assert (cfg.d_model, cfg.n_layers, cfg.vocab, cfg.first_k_dense,
            cfg.d_ff_dense, cfg.d_ff) == (2048, 9, 8192, 1, 11776, 1536)
    assert (cfg.attention, cfg.n_heads, cfg.kv_heads, cfg.head_dim,
            cfg.qk_norm, cfg.shortconv_taps, cfg.window) == (
        "mha", 32, 8, 64, "head", 3, 0)
    assert (cfg.num_experts, cfg.experts_held, cfg.first_expert,
            cfg.experts_per_token, cfg.shared_experts, cfg.norm_topk,
            cfg.norm_topk_eps, cfg.router_scoring, cfg.router_bias,
            cfg.routed_scale) == (64, 8, 0, 4, 0, True, 1e-6, "sigmoid",
                                  True, 1)
    # the held experts' row buffer: `held_capacity` times the even load
    from horovod_tpu.parallel.moe import held_rows
    assert cfg.capacity_factor == cell.config["program"]["held_capacity"]
    assert held_rows(16384 * 4, 8, 64) == 16384
    assert held_rows(16384 * 4, 8, 64, cfg.capacity_factor) \
        == int(8192 * cfg.capacity_factor)
    assert (cfg.positions, cfg.rope_theta, cfg.rope_dim, cfg.unrotated,
            cfg.norm, cfg.rms_norm_eps, cfg.mlp, cfg.tied_head,
            cfg.post_norm) == ("rope", 1e6, 64, (), "rmsnorm", 1e-5,
                               "swiglu", True, False)
    assert (cfg.attn, cfg.dtype, cfg.remat, cfg.remat_policy,
            cfg.load_balance_coef, cfg.router_z_coef) == (
        "flash", jnp.bfloat16, True, cell.config["program"]["remat_policy"],
        0.0, 0.0)
    assert family.samples_per_step(cell.traffic, 1) == 16384
    assert family.first_expert(cell.config) == 0
    assert family.dense_layers(cell.config) == 1


def test_the_least_work_of_the_mix_by_hand(cell_and_family):
    """A forward pass reads B, C, X and writes one result, 4 x 2 bytes a
    channel; a backward pass reads four and writes three, 7 x 2: per layer
    two forward passes (remat) and one backward, 60 KiB a token."""
    cell, family = cell_and_family
    forward, backward = family.mix_work(16_384, 2_048, 3)
    assert forward == (8 * 16_384 * 2_048, 8 * 16_384 * 2_048)
    assert backward == (22 * 16_384 * 2_048, 14 * 16_384 * 2_048)
    work = family.shortconv_mix_work(cell.config, cell.traffic)
    assert work == ((14, *forward), (7, *backward))
    moved = sum(n * b for n, _, b in work)
    assert moved == 7 * 16_384 * 60 * 1_024 == 7_046_430_720
    # memory-bound by two orders: 8.6 ms a step at 819 GB/s
    from benchmark.layer_metrics.gdn_scan_roofline import least_seconds
    v5e = peaks.for_kind("TPU v5 lite")
    assert [least_seconds(w, v5e)[1] for w in work] == ["memory"] * 2
    assert sum(least_seconds(w, v5e)[0] for w in work) == pytest.approx(
        moved / 819e9) == pytest.approx(8.604e-3, rel=1e-3)
    # without remat one forward pass a layer
    plain = dict(cell.config, program=dict(cell.config["program"],
                                           remat=False))
    assert family.shortconv_mix_work(plain, cell.traffic)[0][0] == 7


# ------------------------------------------------------------ the readers

#: A step of a short-convolution layer as the compiled text names it: the
#: input product, the mix's fusions (forward, the remat repeat, backward
#: with the taps' gradient), the output product; beside them a grouped
#: matmul of the experts, a flash kernel of the attention layer and its
#: projection.
_IN = "jit(step)/jvp()/while/body/closed_call/checkpoint"
_BACK = "jit(step)/transpose(jvp())/while/body/closed_call/checkpoint"
HLO_TEXT = f"""
HloModule jit_step

ENTRY %main (a: bf16[8,128]) -> bf16[8,128] {{
  %a = bf16[8,128]{{1,0}} parameter(0)
  %fusion.1 = bf16[8,384]{{1,0}} fusion(%a), kind=kOutput, calls=%f1, metadata={{op_name="{_IN}/shortconv.project/bsd,de->bse/dot_general"}}
  %fusion.2 = bf16[8,128]{{1,0}} fusion(%a), kind=kLoop, calls=%f2, metadata={{op_name="{_IN}/shortconv.mix/mul"}}
  %fusion.3 = bf16[8,128]{{1,0}} fusion(%a), kind=kLoop, calls=%f3, metadata={{op_name="{_BACK}/rematted_computation/shortconv.mix/mul"}}
  %fusion.4 = bf16[8,384]{{1,0}} fusion(%a), kind=kLoop, calls=%f4, metadata={{op_name="{_BACK}/shortconv.mix/mul"}}
  %fusion.5 = f32[128,3]{{1,0}} fusion(%a), kind=kInput, calls=%f5, metadata={{op_name="{_BACK}/shortconv.mix/reduce_sum"}}
  %fusion.6 = bf16[8,128]{{1,0}} fusion(%a), kind=kOutput, calls=%f6, metadata={{op_name="{_IN}/shortconv.out/bse,ed->bsd/dot_general"}}
  %moe.experts.7 = bf16[96,48]{{1,0}} custom-call(%m0, %m1, %m2, %m3, %m4, /*index=5*/%rows, %w), custom_call_target="tpu_custom_call", metadata={{op_name="{_IN}/moe.experts/pallas_call"}}
  %attn.attend.8 = (bf16[4,64,64]{{2,1,0}}, f32[4,64,1]{{2,1,0}}) custom-call(%q, %k, /*index=2*/%v), custom_call_target="tpu_custom_call", metadata={{op_name="{_IN}/attn.attend/pallas_call"}}
  ROOT %fusion.9 = bf16[8,128]{{1,0}} fusion(%a), kind=kOutput, calls=%f9, metadata={{op_name="{_IN}/attn.project/dot_general"}}
}}
"""

#: per step, in microseconds: (name, start, duration)
STEP_OPS = (("fusion.1", 0, 9), ("fusion.2", 9, 2), ("fusion.3", 11, 2),
            ("fusion.4", 13, 5), ("fusion.5", 18, 1), ("fusion.6", 19, 4),
            ("moe.experts.7", 23, 6), ("attn.attend.8", 29, 8),
            ("fusion.9", 37, 3))
#: (executions, FLOPs, bytes): passes bound by their bytes
WORK = ((2, 1e3, 819e3), (1, 1e3, 1638e3))


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


def _run(trace, table, text=HLO_TEXT, work=WORK, v5e=True):
    program = type("P", (), {"as_text": staticmethod(lambda: text)})
    members = {} if work is None else {
        "shortconv_mix_work": staticmethod(lambda c, t: work)}
    return fakes.fake_run(
        trace, table, program=program,
        peaks=peaks.for_kind("TPU v5 lite") if v5e else None,
        family=type("F", (), members),
        cell=type("C", (), {"config": {}, "traffic": {}, "name": "fake"}))


def test_the_three_readers_on_a_hand_made_trace(trace, table):
    run = _run(trace, table)
    # everything under `shortconv.*`: 9 + 2 + 2 + 5 + 1 + 4
    assert reader("shortconv_ms_per_step").read(run) == pytest.approx(23e-3)
    # under `shortconv.mix`: forward, the repeat, backward, the taps' sum
    assert reader("shortconv_mix_ms_per_step").read(run) == pytest.approx(
        10e-3)
    # least: 2 x 819e3 / 819e9 + 1638e3 / 819e9 = 2 x 1 us + 2 us
    assert reader("shortconv_mix_roofline").read(run) == pytest.approx(
        100 * 4 * US / (10 * US))
    # off the chip (no peaks): the times read, the share does not
    bare = _run(trace, table, v5e=False)
    assert reader("shortconv_mix_roofline").read(bare) is None
    assert reader("shortconv_mix_ms_per_step").read(bare) == pytest.approx(
        10e-3)
    # the expert layer's reader tells its kernel on the same trace, and no
    # other family's readers find anything of theirs in this program
    assert reader("moe_experts_ms_per_step").read(run) == pytest.approx(6e-3)
    for other in ("kda_ms_per_step", "gdn_ms_per_step", "ssd_ms_per_step",
                  "mla_ms_per_step"):
        assert not reader(other).read(run), other


def test_a_program_without_the_scopes_reads_as_nothing(trace, table):
    """The parent's program, or a cell of another family: every new reader
    returns None and raises nothing."""
    plain = hlo.index(fakes.HLO_TEXT)
    old_trace = xplane.reduce_profile(
        ProfileData.from_text_proto(fakes.hand_made_xspace()))
    run = _run(old_trace, plain, text=fakes.HLO_TEXT)
    assert [reader(m).read(run) for m in NEW] == [None] * 3
    program = type("P", (), {"as_text": staticmethod(lambda: fakes.HLO_TEXT)})
    for bare in (fakes.fake_run(None, {}, program=program, peaks=None),
                 fakes.fake_run(xplane.Trace(), {}, program=None,
                                peaks=None, family=None)):
        assert [reader(m).read(bare) for m in NEW] == [None] * 3
    # the scopes without a family that counts the work: the times alone
    no_work = _run(trace, table, work=None)
    assert reader("shortconv_mix_roofline").read(no_work) is None
    assert reader("shortconv_ms_per_step").read(no_work) == pytest.approx(
        23e-3)


# ---------------------------------------------------------------- entries

def _named(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def test_the_entries_are_the_cells_found_by_name(bench):
    entry = _named(bench["workloads"], CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, TRAFFIC, 1)
    config = _named(bench["configs"], CONFIG)
    assert config["reduced"] == ["n_layer", "num_experts", "vocab_size"]
    assert config["file"] == "benchmark/configs/lfm2-24b-a2b.json"
    assert config["source"].endswith("LFM2-24B-A2B/blob/main/config.json")
    assert len(config["source"]) <= 200
    assert len(entry["why"]) <= 200 and len(config["why"]) <= 200
    # appended behind the cells and configurations that were there
    cells = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    assert cells.index("kimilinear-1chip") < cells.index(CELL)
    assert configs.index("kimi-linear-48b-a3b") < configs.index(CONFIG)
    assert len(cells) >= 12
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    metrics = [m["name"] for m in bench["per_layer"]]
    first = metrics.index(NEW[0])
    assert metrics[first:first + 3] == list(NEW)
    assert metrics.index("kda_scan_roofline") < first
    for name in NEW:
        m = _named(bench["per_layer"], name)
        assert m["workloads"] == [CELL]
        assert (m["source"], m["layer"], m["moves"]) == (
            "device_trace", "short convolution", "samples_per_s_per_chip")
        assert (m["unit"], m["better"]) == (
            ("%", "higher") if name.endswith("_roofline") else ("ms",
                                                               "lower"))
        assert sorted(m) == ["better", "layer", "moves", "name", "source",
                             "unit", "workloads"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        listed = m.get("workloads", ())
        if m["name"] in JOINED:
            assert listed.index("kimilinear-1chip") < listed.index(CELL), \
                m["name"]
        elif m["name"] in MOE:
            assert listed[:6] == ["olmoe-1chip", "dsv2lite-1chip",
                                  "smallthinker-1chip", "granite4h-1chip",
                                  "kimilinear-1chip", CELL], m["name"]
        elif m["name"] not in NEW and m["name"] != "setup_s":
            assert CELL not in listed, m["name"]
    for name in HELD_TO_THE_LETTER:
        assert CELL not in _named(bench["per_layer"], name)["workloads"]


def test_what_the_cell_reports(bench):
    cell = spec.load_cell(CELL)
    assert [m["name"] for m in cell.end_to_end] == [
        "samples_per_s_per_chip", "step_hbm_gib", "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert names == {"init_s", "compile_s", "device_step_ms", "mfu",
                     "device_idle_share", "window_stall_share", *MOE, *NEW}
    layers = {m["name"]: m["layer"] for m in cell.per_layer}
    assert {layers[m] for m in NEW} == {"short convolution"}
    assert {layers[m] for m in MOE} == {"expert layer"}
    # every reader the cell names is a file beside the others
    for name in names:
        assert callable(reader(name).read)
    # no older cell reads the new metrics
    for w in bench["workloads"]:
        if w["name"] != CELL:
            assert not [m for m in spec.load_cell(w["name"]).per_layer
                        if m["name"] in NEW], w["name"]


# -------------------------------------------------------------- rehearsal

@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_the_cell_runs_end_to_end_at_a_tiny_size(trace, tmp_path, capfd):
    hvd.shutdown()   # the cell initialises on exactly its own devices
    cell = spec.load_cell("tiny-lfm2moe-1chip", root=TINY)
    assert cell.config["family"] == "lfm2_moe"
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
    assert "rows of the 4 held experts in the reference's routing" in log
    assert "compile request(s) after warm-up" not in log
    problems = [ln for ln in log.splitlines() if "NOT CORRECT" in ln]
    if trace:   # the one thing a CPU trace cannot show
        assert ["the trace holds no whole step" in p for p in problems] == \
            [True]
    else:
        assert problems == [] and line["correct"] is True
    # no time, rate or share from the CPU under a device metric's name
    assert line["metrics"] == {}
