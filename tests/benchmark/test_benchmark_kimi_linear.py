"""What PR 50 adds to the benchmark: the `kimi_linear` family's configuration
against the published numbers, its limits with their readings, the program it
makes of the configuration, the three new readers (`kda_ms_per_step`,
`kda_scan_ms_per_step`, `kda_scan_roofline`) on a hand-made trace, the entries
BY NAME (never by position or as "the last": the next PR appends after
these), and the cell's path rehearsed at a tiny size on the CPU
(`fixtures/tiny-kimilinear`). The family's arithmetic (parameters, FLOPs,
`kda_scan_work`) is held by hand in `tests/test_kimi_linear.py`."""

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
                    "tiny-kimilinear")
US = 1e-6
CELL = "kimilinear-1chip"
CONFIG = "kimi-linear-48b-a3b"
TRAFFIC = "spmd-dp1-s16384-kimilinear"
NEW = ("kda_ms_per_step", "kda_scan_ms_per_step", "kda_scan_roofline")
#: the lists the cell joined: every LM cell's and the expert layer's four
JOINED = ("samples_per_s_per_chip", "step_hbm_gib", "device_step_ms", "mfu",
          "device_idle_share", "window_stall_share")
MOE = ("moe_ms_per_step", "moe_experts_ms_per_step",
       "moe_dispatch_ms_per_step", "moe_experts_roofline")
#: the lists whose readers would read this program right and which it could
#: NOT join: an older test of this directory holds each to the letter
#: (`mla_*` and the shared experts' to `dsv2lite-1chip` alone:
#: `test_benchmark_olmo_hybrid.py`; PERF.md section 7)
HELD_TO_THE_LETTER = ("mla_ms_per_step", "mla_flash_roofline",
                      "moe_shared_ms_per_step", "attn_ms_per_step",
                      "mlp_ms_per_step", "vocab_ms_per_step",
                      "opt_update_ms_per_step", "other_ms_per_step")
KDA_LAYERS = [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22,
              23, 25, 26]


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
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": KDA_LAYERS, "num_heads": 32,
            "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576,
        "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts_per_token": 8,
        "num_hidden_layers": 27, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 0, "num_shared_experts": 1,
        "q_lora_rank": None, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 10000, "routed_scaling_factor": 2.446,
        "tie_word_embeddings": False, "topk_group": 1,
        "use_grouped_topk": True, "v_head_dim": 128}
    assert {k: cell.config[k] for k in published} == published
    assert cell.config["published"] == {
        "num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840}
    assert sorted(cell.config["reduced"]) == ["n_layer", "num_experts",
                                              "vocab_size"]
    assert cell.config["source"] == (
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
        "blob/main/config.json")
    # the floors: more than four layers with a whole period among them, at
    # least 8 experts, at least an eighth of the vocabulary
    assert (cell.config["n_layer"], cell.config["num_experts"],
            cell.config["vocab_size"]) == (6, 16, 20480)
    assert 20480 * 8 == 163840 and 16 * 16 == 256
    for key in ("assumed", "departures", "deployment", "check"):
        assert cell.config[key]
    assert cell.config["assumed"]["kda_rank"] == 128 == \
        cell.config["linear_attn_config"]["head_dim"]
    assert set(cell.config["assumed"]) >= {
        "kda_rank_why", "gate_bias", "seeded_leaves", "conv", "beta",
        "router", "mla", "optimizer"}
    assert set(cell.config["departures"]) >= {
        "selection_bias", "optimizer_state_dtype", "weight_decay", "rule",
        "documents", "row_buffer"}
    assert set(cell.config["check"]["limits"]) == {"LOGITS_RMS_TOL",
                                                   "LOSS_RTOL"}
    deployment = cell.config["deployment"]
    assert (deployment["chips_sharing_a_layer"], deployment["chip"],
            deployment["expert_rank"]) == (16, 0, 0)
    assert "no code stands in" in deployment["how"]
    assert "GiB" in deployment["step_hbm_gib"]
    # the issue's rule and what it found are in the file
    assert "from the END" in cell.config["reduced"]["n_layer"]
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
        assert "my chip run, PR 50" in text
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
    assert family.kinds(cell.config) == (
        "kda", "kda", "kda", "mla", "kda", "kda")
    assert cfg.layer_pattern == ("kda", "kda", "kda", "mla") == \
        family.pattern(cell.config)
    assert (cfg.d_model, cfg.n_layers, cfg.vocab, cfg.first_k_dense,
            cfg.d_ff_dense, cfg.d_ff) == (2304, 6, 20480, 1, 9216, 1024)
    assert (cfg.attention, cfg.gdn_heads, cfg.gdn_key_dim,
            cfg.gdn_value_dim, cfg.gdn_conv, cfg.kda_rank,
            cfg.gdn_neg_eigval) == ("kda", 32, 128, 128, 4, 128, False)
    assert (cfg.n_heads, cfg.kv_latent, cfg.qk_nope_dim, cfg.qk_rope_dim,
            cfg.v_head_dim) == (32, 512, 128, 64, 128)
    assert (cfg.num_experts, cfg.experts_held, cfg.first_expert,
            cfg.experts_per_token, cfg.shared_experts, cfg.norm_topk,
            cfg.router_scoring, cfg.router_bias, cfg.routed_scale) == (
        256, 16, 0, 8, 1, True, "sigmoid", True, 2.446)
    # the held experts' row buffer: four times the even load's rows
    from horovod_tpu.parallel.moe import held_rows
    assert cfg.capacity_factor == 4.0 == cell.config["program"][
        "held_capacity"]
    assert held_rows(16384 * 8, 16, 256, cfg.capacity_factor) == 32768
    assert held_rows(16384 * 8, 16, 256) == 16384
    assert (cfg.positions, cfg.unrotated, cfg.norm, cfg.rms_norm_eps,
            cfg.mlp, cfg.tied_head, cfg.post_norm) == (
        "none", (), "rmsnorm", 1e-5, "swiglu", False, False)
    assert (cfg.attn, cfg.dtype, cfg.remat, cfg.remat_policy,
            cfg.load_balance_coef, cfg.router_z_coef) == (
        "flash", jnp.bfloat16, True, "full", 0.0, 0.0)
    assert family.samples_per_step(cell.traffic, 1) == 16384
    assert family.first_expert(cell.config) == 0
    with pytest.raises(ValueError, match="no repeats of"):
        family.pattern(dict(
            cell.config, num_hidden_layers=6, linear_attn_config=dict(
                cell.config["linear_attn_config"], full_attn_layers=[4, 6],
                kda_layers=[1, 2, 3, 5])))


# ------------------------------------------------------------ the readers

#: A step of a KDA layer as the compiled text names it: the projections, a
#: convolution, the decay's softplus and the forward kernel under `kda.scan`
#: (twice a step under remat: the plain one, and the one that writes the
#: residuals), the backward kernel (its scope in the metadata), the reverse
#: running sum for g, the gate, the output product; beside them a grouped
#: matmul of the experts, the shared expert and a flash kernel of the MLA
#: layer.
_IN = "jit(step)/jvp()/while/body/closed_call/checkpoint"
_BACK = "jit(step)/transpose(jvp())/while/body/closed_call/checkpoint"
HLO_TEXT = f"""
HloModule jit_step

ENTRY %main (a: bf16[8,128]) -> bf16[8,128] {{
  %a = bf16[8,128]{{1,0}} parameter(0)
  %fusion.1 = bf16[8,128]{{1,0}} fusion(%a), kind=kOutput, calls=%f1, metadata={{op_name="{_IN}/kda.project/bsd,dhk->bhsk/dot_general"}}
  %kda.conv.2 = bf16[8,128]{{1,0}} custom-call(%before, %u, %w), custom_call_target="tpu_custom_call", metadata={{op_name="{_IN}/kda.conv/pallas_call"}}
  %fusion.3 = f32[8,128]{{1,0}} fusion(%a), kind=kLoop, calls=%f3, metadata={{op_name="{_IN}/kda.scan/jit(softplus)/log1p"}}
  %kda.scan.4 = bf16[8,128]{{1,0}} custom-call(%q, %k, %v, %b, %beta), custom_call_target="tpu_custom_call", metadata={{op_name="{_IN}/kda.scan/pallas_call"}}
  %kda.scan.5 = (bf16[8,128]{{1,0}}, bf16[8,128]{{1,0}}, f32[8,128]{{1,0}}, f32[8,128]{{1,0}}, f32[1,128,128]{{2,1,0}}) custom-call(%q, %k, %v, %b, %beta), custom_call_target="tpu_custom_call", metadata={{op_name="{_BACK}/rematted_computation/kda.scan/pallas_call"}}
  %kda.scan.6 = (bf16[8,128]{{1,0}}, bf16[8,128]{{1,0}}, bf16[8,128]{{1,0}}, f32[8,128]{{1,0}}, f32[1,1,8]{{2,1,0}}) custom-call(%q, %k, %v, %w, %u0, /*index=5*/%tp, %s0, %b, %beta, %do), custom_call_target="tpu_custom_call", metadata={{op_name="{_BACK}/kda.scan/pallas_call"}}
  %fusion.7 = f32[8,128]{{1,0}} fusion(%a), kind=kLoop, calls=%f7, metadata={{op_name="{_BACK}/kda.scan/cumsum"}}
  %fusion.8 = bf16[8,128]{{1,0}} fusion(%a), kind=kLoop, calls=%f8, metadata={{op_name="{_IN}/kda.gate/logistic"}}
  %fusion.9 = bf16[8,128]{{1,0}} fusion(%a), kind=kOutput, calls=%f9, metadata={{op_name="{_IN}/kda.out/bhsk,hkd->bsd/dot_general"}}
  %moe.experts.10 = bf16[96,48]{{1,0}} custom-call(%m0, %m1, %m2, %m3, %m4, /*index=5*/%rows, %w), custom_call_target="tpu_custom_call", metadata={{op_name="{_IN}/moe.experts/pallas_call"}}
  %fusion.11 = bf16[8,128]{{1,0}} fusion(%a), kind=kOutput, calls=%f11, metadata={{op_name="{_IN}/moe.shared/dot_general"}}
  %mla.attend.12 = (bf16[4,64,16]{{2,1,0}}, f32[4,64,1]{{2,1,0}}) custom-call(%q, %k, /*index=2*/%v), custom_call_target="tpu_custom_call", metadata={{op_name="{_IN}/mla.attend/pallas_call"}}
  ROOT %fusion.13 = bf16[8,128]{{1,0}} fusion(%a), kind=kOutput, calls=%f13, metadata={{op_name="{_IN}/mla.out/dot_general"}}
}}
"""

#: per step, in microseconds: (name, start, duration)
STEP_OPS = (("fusion.1", 0, 9), ("kda.conv.2", 9, 3), ("fusion.3", 12, 1),
            ("kda.scan.4", 13, 4), ("kda.scan.5", 17, 5),
            ("kda.scan.6", 22, 10), ("fusion.7", 32, 1), ("fusion.8", 33, 2),
            ("fusion.9", 35, 5), ("moe.experts.10", 40, 6),
            ("fusion.11", 46, 7), ("mla.attend.12", 53, 8),
            ("fusion.13", 61, 3))
#: (executions, FLOPs, bytes): a forward pass bound by its bytes, a backward
#: pass by its products
WORK = ((2, 1e6, 819e3), (1, 394e6, 1e3))


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
        "kda_scan_work": staticmethod(lambda c, t: work)}
    return fakes.fake_run(
        trace, table, program=program,
        peaks=peaks.for_kind("TPU v5 lite") if v5e else None,
        family=type("F", (), members),
        cell=type("C", (), {"config": {}, "traffic": {}, "name": "fake"}))


def test_the_three_readers_on_a_hand_made_trace(trace, table):
    run = _run(trace, table)
    # everything under `kda.*`: 9 + 3 + 1 + 4 + 5 + 10 + 1 + 2 + 5
    assert reader("kda_ms_per_step").read(run) == pytest.approx(40e-3)
    # under `kda.scan`: the softplus, the three kernels, g's reverse sum
    assert reader("kda_scan_ms_per_step").read(run) == pytest.approx(21e-3)
    # least: 2 x max(1e6 / 197e12, 819e3 / 819e9) + max(394e6 / 197e12, ..)
    # = 2 x 1 us + 2 us
    assert reader("kda_scan_roofline").read(run) == pytest.approx(
        100 * 4 * US / (21 * US))
    # off the chip (no peaks): the times read, the share does not
    bare = _run(trace, table, v5e=False)
    assert reader("kda_scan_roofline").read(bare) is None
    assert reader("kda_scan_ms_per_step").read(bare) == pytest.approx(21e-3)
    # the expert layer's and the latent attention's readers tell their
    # kernels on the same trace; none of the rule's three kernels has a
    # grouped matmul's signature or a flash kernel's
    assert reader("moe_experts_ms_per_step").read(run) == pytest.approx(6e-3)
    assert reader("moe_shared_ms_per_step").read(run) == pytest.approx(7e-3)
    assert reader("mla_ms_per_step").read(run) == pytest.approx(11e-3)
    from benchmark.harness import scopes
    from benchmark.layer_metrics import mla_flash_roofline
    assert set(scopes.grouped_kernels(table)) == {"moe.experts.10"}
    assert set(mla_flash_roofline.flash_kernels(table)) == {"mla.attend.12"}
    # the scalar rule's readers find nothing of theirs in this program
    assert reader("gdn_scan_ms_per_step").read(run) is None
    assert reader("gdn_ms_per_step").read(run) is None


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
    assert reader("kda_scan_roofline").read(no_work) is None
    assert reader("kda_ms_per_step").read(no_work) == pytest.approx(40e-3)


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
    assert config["file"] == "benchmark/configs/kimi-linear-48b-a3b.json"
    assert config["source"].endswith("Kimi-Linear-48B-A3B-Instruct/blob/"
                                     "main/config.json")
    assert len(config["source"]) <= 200
    assert len(entry["why"]) <= 200 and len(config["why"]) <= 200
    # appended behind the cells and configurations that were there
    cells = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    assert cells.index("granite4h-1chip") < cells.index(CELL)
    assert configs.index("granite-4.0-h-small") < configs.index(CONFIG)
    assert len(cells) >= 11
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    metrics = [m["name"] for m in bench["per_layer"]]
    first = metrics.index(NEW[0])
    assert metrics[first:first + 3] == list(NEW)
    assert metrics.index("ssd_scan_roofline") < first
    for name in NEW:
        m = _named(bench["per_layer"], name)
        assert m["workloads"] == [CELL]
        assert (m["source"], m["layer"], m["moves"]) == (
            "device_trace", "linear attention", "samples_per_s_per_chip")
        assert (m["unit"], m["better"]) == (
            ("%", "higher") if name.endswith("_roofline") else ("ms",
                                                               "lower"))
        assert sorted(m) == ["better", "layer", "moves", "name", "source",
                             "unit", "workloads"]
    # the layer's name is the scalar rule's readers', letter for letter
    assert _named(bench["per_layer"], "gdn_scan_roofline")["layer"] == \
        "linear attention"
    for m in bench["end_to_end"] + bench["per_layer"]:
        listed = m.get("workloads", ())
        if m["name"] in JOINED:
            assert listed.index("granite4h-1chip") < listed.index(CELL), \
                m["name"]
        elif m["name"] in MOE:
            assert listed[:5] == ["olmoe-1chip", "dsv2lite-1chip",
                                  "smallthinker-1chip", "granite4h-1chip",
                                  CELL], m["name"]
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
    assert {layers[m] for m in NEW} == {"linear attention"}
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
    cell = spec.load_cell("tiny-kimilinear-1chip", root=TINY)
    assert cell.config["family"] == "kimi_linear"
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
