"""What PR 38 adds to the benchmark: the `smallthinker` family's arithmetic
against the configuration's published numbers, the four new readers
(`swa_flash_ms_per_step`, `swa_flash_roofline`, `full_flash_ms_per_step`,
`full_flash_roofline`) with the counts they rest on, on a hand-made trace,
the entries BY NAME (never by position or as "the last": the next PR appends
after these), and the cell's path rehearsed at a tiny size on the CPU
(`fixtures/tiny-smallthinker`)."""

import json
import os
import time

import pytest
from jax.profiler import ProfileData

import benchmark_fakes as fakes
import horovod_tpu as hvd
from benchmark.harness import hlo, peaks, runner, spec, xplane

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                    "tiny-smallthinker")
US = 1e-6
CELL = "smallthinker-1chip"
CONFIG = "smallthinker-21b-a3b"
NEW = ("swa_flash_ms_per_step", "swa_flash_roofline",
       "full_flash_ms_per_step", "full_flash_roofline")
#: the lists the cell joined: every LM cell's, and the expert layer's four
JOINED = ("samples_per_s_per_chip", "step_hbm_gib", "device_step_ms", "mfu",
          "device_idle_share", "window_stall_share")
MOE = ("moe_ms_per_step", "moe_experts_ms_per_step",
       "moe_dispatch_ms_per_step", "moe_experts_roofline")
#: the four by-scope parts whose readers would read this program right and
#: whose lists it could NOT join: an older test of this directory holds each
#: list to the letter (PERF.md section 7 says which, and what it costs)
HELD_TO_THE_LETTER = ("attn_ms_per_step", "vocab_ms_per_step",
                      "opt_update_ms_per_step", "other_ms_per_step")
BAND = 4096 - 4096 * 4095 / (2 * 16384)


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


# ------------------------------------------------------------ arithmetic

def test_the_configuration_holds_the_published_numbers(cell_and_family):
    """Every number of the catalog row's `config`, letter for letter, but
    the experts held and the vocabulary, whose published sizes stand beside;
    the depth the program reads is `n_layer`."""
    cell, _ = cell_and_family
    layout = [0, 1, 1, 1] * 13
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct",
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_hidden_layers": 52,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_layout": layout, "rope_scaling": None, "rope_theta": 1500000,
        "sliding_window_layout": layout, "sliding_window_size": 4096,
        "tie_word_embeddings": False}
    assert {k: cell.config[k] for k in published} == published
    assert cell.config["published"] == {
        "num_hidden_layers": 52, "moe_num_primary_experts": 64,
        "vocab_size": 151936}
    assert sorted(cell.config["reduced"]) == [
        "moe_num_primary_experts", "n_layer", "vocab_size"]
    assert cell.config["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
        "blob/main/config.json")
    # the floors: a whole period and four layers, at least 8 experts, at
    # least an eighth of the vocabulary
    assert (cell.config["n_layer"], cell.config["moe_num_primary_experts"],
            cell.config["vocab_size"]) == (4, 16, 37984)
    assert 37984 * 4 == 151936 and 16 * 4 == 64
    for key in ("assumed", "departures", "deployment", "check"):
        assert cell.config[key]
    assert set(cell.config["assumed"]) >= {
        "router_input", "gate", "rotary_order", "window", "nope",
        "auxiliary_losses", "secondary_experts", "optimizer"}
    limits = cell.config["check"]["limits"]
    assert set(limits) == {"LOGITS_RMS_TOL", "LOSS_RTOL"}
    # the limits the family holds, with the readings that set them
    from benchmark.families import smallthinker as family
    assert (family.LOGITS_RMS_TOL, family.LOSS_RTOL) == (8 * 2.0 ** -8, 8e-5)
    assert limits["LOGITS_RMS_TOL"].startswith("8 * 2^-8") and \
        "1.530%" in limits["LOGITS_RMS_TOL"] and \
        "5.09" in limits["LOGITS_RMS_TOL"]
    assert limits["LOSS_RTOL"].startswith("8e-5") and \
        "1.96e-5" in limits["LOSS_RTOL"] and "1.74e-4" in limits["LOSS_RTOL"]
    assert "12.31 GiB" in cell.config["program"]["note"]
    deployment = cell.config["deployment"]
    assert (deployment["chips_sharing_a_layer"], deployment["chip"]) == (4,
                                                                         0)
    assert "pipeline stages" in cell.config["reduced"]["n_layer"]
    assert (cell.traffic["seq_len"], cell.traffic["per_chip_batch"],
            cell.traffic["mesh"], cell.traffic["trace_steps"],
            cell.traffic["path"], cell.chips) == (16384, 1, {}, 5,
                                                  "tfm_spmd", 1)
    assert cell.traffic["optimizer"] == {
        "name": "adamw", "learning_rate": 3e-04, "b1": 0.9, "b2": 0.95,
        "eps": 1e-08, "weight_decay": 0.1}


def test_the_program_is_the_configurations(cell_and_family):
    cell, family = cell_and_family
    cfg = family.transformer_config(cell.config)
    assert family.kinds(cell.config) == ("full", "window", "window",
                                         "window")
    assert cfg.layer_pattern == ("full", "window", "window", "window")
    assert cfg.unrotated == ("full",) and cfg.segments == ()
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_ff,
            cfg.n_layers, cfg.vocab, cfg.window, cfg.max_seq) == (
        2560, 28, 4, 128, 768, 4, 37984, 4096, 16384)
    assert cfg.n_heads * cfg.head_dim == 3584 != cfg.d_model
    assert (cfg.num_experts, cfg.experts_per_token, cfg.experts_held,
            cfg.first_expert, cfg.shared_experts, cfg.first_k_dense) == (
        64, 6, 16, 0, 0, 0)
    assert (cfg.norm_topk, cfg.router_input, cfg.mlp, cfg.gate) == (
        True, "layer", "reglu", "relu")
    assert (cfg.norm, cfg.rms_norm_eps, cfg.positions, cfg.rope_theta,
            cfg.yarn, cfg.qk_norm, cfg.tied_head, cfg.attention_bias) == (
        "rmsnorm", 1e-6, "rope", 1.5e6, None, False, False, False)
    assert (cfg.load_balance_coef, cfg.router_z_coef) == (0.0, 0.0)
    assert cfg.score_scale is None                      # 128 ** -0.5
    assert (cfg.attention, cfg.attn, cfg.remat, cfg.remat_policy,
            str(cfg.dtype)) == ("mha", "flash", True,
                                cell.config["program"]["remat_policy"],
                                "bfloat16")
    # a deeper cut of the same lists: two periods
    assert family.pattern(dict(cell.config, n_layer=8)) == cfg.layer_pattern
    assert family.first_expert(dict(
        cell.config, deployment=dict(cell.config["deployment"], chip=3))) \
        == 48
    with pytest.raises(ValueError, match="no equations"):
        family.transformer_config(dict(cell.config,
                                       tie_word_embeddings=True))
    with pytest.raises(ValueError, match="no equations"):
        family.transformer_config(dict(cell.config, norm_topk_prob=False))
    with pytest.raises(ValueError, match="no equations"):
        family.transformer_config(dict(cell.config,
                                       rope_layout=[1, 1, 1, 1] * 13))
    with pytest.raises(ValueError, match="constants"):
        family.transformer_config(dict(cell.config, rope_theta=10000))


def test_parameters_and_bytes_as_the_configuration_file_says(cell_and_family):
    cell, family = cell_and_family
    import jax
    from horovod_tpu.models import transformer as tfm
    cfg = family.transformer_config(cell.config)
    shapes = jax.eval_shape(lambda k: tfm.init(k, cfg), jax.random.PRNGKey(0))

    def count(tree):
        return sum(x.size for x in jax.tree_util.tree_leaves(tree))

    d = 2560
    attention = 2 * d * 28 * 128 + 2 * d * 4 * 128
    experts = 16 * 3 * d * 768
    layer = attention + d * 64 + 2 * d + experts
    assert (attention, experts, layer) == (20_971_520, 94_371_840,
                                           115_512_320)
    assert count(shapes["layers"]["full"]) == layer
    assert count(shapes["layers"]["window"]) == 3 * layer
    total = count(shapes)
    assert total == 4 * layer + 2 * 37984 * d + d == 656_529_920
    # bf16 weight, gradient and two Adam moments: 5.25 GB = 4.89 GiB
    assert 8 * total / 2 ** 30 == pytest.approx(4.89, abs=0.01)
    assert all(x.dtype == "bfloat16" for x in
               jax.tree_util.tree_leaves(shapes))
    assert shapes["layers"]["full"]["wq"].shape == (1, 1, d, 28, 128)
    assert shapes["layers"]["window"]["wk"].shape == (1, 3, d, 4, 128)
    assert shapes["layers"]["window"]["we_gate"].shape == (1, 3, 16, d, 768)
    assert shapes["layers"]["full"]["router"].shape == (1, 1, d, 64)
    assert shapes["unembed"].shape == (d, 37984)
    # the numbers the configuration file writes out
    assert "656,529,920 parameters x 8 bytes" in \
        cell.config["reduced"]["n_layer"]
    # whole, by the same leaves: the catalog's 21 B
    whole = family.transformer_config(dict(
        cell.config, n_layer=52, vocab_size=151936,
        moe_num_primary_experts=64))
    assert count(jax.eval_shape(lambda k: tfm.init(k, whole),
                                jax.random.PRNGKey(0))) == 21_506_562_560
    # the row buffer of the departures: twice the even load's rows
    from horovod_tpu.parallel.moe import held_rows
    assert held_rows(16384 * 6, 16, 64) == 49152


def test_flops_per_token_by_hand(cell_and_family):
    cell, family = cell_and_family
    parts = family.forward_flops_per_token(cell.config, 16384)
    d = 2560
    assert parts["projections"] == 4 * 2 * (d * 36 * 128 + 3584 * d) \
        == 167_772_160
    assert parts["router"] == 4 * 2 * d * 64 == 1_310_720
    # six experts a token, a quarter of them held: 1.5 experts' three
    # products a token a layer
    assert parts["experts"] == 4 * 1.5 * 3 * 2 * d * 768 == 70_778_880
    assert parts["head"] == 2 * d * 37984 == 194_478_080
    assert family.keys_seen(16384, 4096) == pytest.approx(BAND) \
        == pytest.approx(3584.12, abs=0.01)
    assert family.keys_seen(16384) == 8192.5
    assert family.keys_seen(2048, 4096) == 1024.5     # the window never binds
    # the band is 43.7% of the causal half
    assert BAND / 8192.5 == pytest.approx(0.4375, abs=0.0005)
    # q.k and p.v, 28 heads of 128, 2 FLOPs a multiply-add
    assert parts["attention"] == pytest.approx(
        2 * 28 * 256 * (8192.5 + 3 * BAND))
    forward = sum(parts.values())
    assert forward == pytest.approx(7.059e8, rel=1e-3)
    assert family.flops_per_sample(cell.config, cell.traffic) == \
        pytest.approx(3 * forward) == pytest.approx(2.118e9, rel=1e-3)
    # attention is the largest part, the head next
    assert parts["attention"] / forward == pytest.approx(0.385, abs=0.002)
    assert parts["head"] / forward == pytest.approx(0.2755, abs=0.001)
    assert family.samples_per_step(cell.traffic, 1) == 16384
    assert family.flash_kernel_shapes(cell.config, cell.traffic) == {
        "calls": 1, "shape": (1, 28, 4, 16384, 128, 128),
        "layers": {"window": (3, pytest.approx(BAND)),
                   "full": (1, 8192.5)},
        "remat": True}
    # the even load's rows of the 98,304 pairs; the buffer holds twice these
    assert family.grouped_matmul_shape(cell.config, cell.traffic) == (
        24576, d, 768, 16)


def test_a_flash_calls_least_work_by_hand():
    """The band's work, keys and values moved once a group of seven."""
    roof = reader("diff_flash_roofline")    # whose `work` the readers use
    shape = (1, 28, 4, 16384, 128, 128)
    v5e = peaks.for_kind("TPU v5 lite")
    q, k = 28 * 16384 * 128 * 2, 4 * 16384 * 128 * 2
    lse = 28 * 16384 * 4
    entries = 28 * 16384 * BAND
    assert roof.work("forward", shape, BAND) == (
        2 * entries * 256, q + 2 * k + q + lse)
    assert roof.work("dkdv", shape, BAND) == (
        2 * entries * 512, q + 2 * k + 2 * q + lse + 2 * k)
    assert roof.work("dq", shape, BAND) == (
        2 * entries * 384, q + 2 * k + 2 * q + lse + q)
    # every kernel is compute-bound: the full layer's forward 9.77 ms at the
    # peak, a windowed layer's 43.7% of it; a layer's forward, its remat
    # repeat, dk/dv (2 x) and dq (1.5 x): 53.7 ms, and 23.5 windowed
    full, bound = roof.least_seconds("forward", shape, 8192.5, v5e)
    assert bound == "compute" and full * 1e3 == pytest.approx(9.768,
                                                              abs=0.005)
    banded, bound = roof.least_seconds("forward", shape, BAND, v5e)
    assert bound == "compute" and banded / full == pytest.approx(
        BAND / 8192.5, rel=1e-6)


# ---------------------------------------------------------------- readers

#: A compiled step in miniature at (1 x 4 | 1 x 2, 64, 16 | 16): the router
#: (which reads the layer's input, ahead of the attention), a windowed
#: layer's projection and its three flash kernels under `attn.window` (the
#: backward ones once with their scope in the metadata, once in the name
#: alone), a full layer's under `attn.attend` alone, a grouped matmul of the
#: experts, a kernel of a flash signature and another shape.
_IN = "jit(step)/jvp()/while/body/closed_call/checkpoint"
_BACK = "jit(step)/transpose(jvp())/while/body/closed_call/checkpoint"
HLO_TEXT = f"""
HloModule jit_step

ENTRY %main (a: bf16[8,128]) -> bf16[8,128] {{
  %a = bf16[8,128]{{1,0}} parameter(0)
  %fusion.1 = f32[8,64]{{1,0}} fusion(%a), kind=kOutput, calls=%f1, metadata={{op_name="{_IN}/moe.route/dot_general"}}
  %fusion.2 = bf16[8,128]{{1,0}} fusion(%a), kind=kOutput, calls=%f2, metadata={{op_name="{_IN}/attn.project/bsd,dhk->bhsk/dot_general"}}
  %attn.window.3 = (bf16[4,64,16]{{2,1,0}}, f32[4,64,1]{{2,1,0}}) custom-call(%q, %k, /*index=2*/%v), custom_call_target="tpu_custom_call", metadata={{op_name="{_IN}/attn.attend/attn.window/pallas_call"}}
  %attn.window.4 = (bf16[2,64,16]{{2,1,0}}, bf16[2,64,16]{{2,1,0}}) custom-call(%q, %k, %v, %o, %do, /*index=5*/%lse), custom_call_target="tpu_custom_call", metadata={{op_name="{_BACK}/attn.attend/attn.window/pallas_call"}}
  %attn.window.5 = bf16[4,64,16]{{2,1,0}} custom-call(%q, %k, %v, %o, %do, /*index=5*/%lse), custom_call_target="tpu_custom_call"
  %attn.attend.6 = (bf16[4,64,16]{{2,1,0}}, f32[4,64,1]{{2,1,0}}) custom-call(%q, %k, /*index=2*/%v), custom_call_target="tpu_custom_call", metadata={{op_name="{_IN}/attn.attend/pallas_call"}}
  %attn.attend.7 = (bf16[2,64,16]{{2,1,0}}, bf16[2,64,16]{{2,1,0}}) custom-call(%q, %k, %v, %o, %do, /*index=5*/%lse), custom_call_target="tpu_custom_call", metadata={{op_name="{_BACK}/attn.attend/pallas_call"}}
  %attn.attend.8 = bf16[4,64,16]{{2,1,0}} custom-call(%q, %k, %v, %o, %do, /*index=5*/%lse), custom_call_target="tpu_custom_call", metadata={{op_name="{_BACK}/attn.attend/pallas_call"}}
  %moe.experts.9 = bf16[96,48]{{1,0}} custom-call(%m0, %m1, %m2, %m3, %m4, /*index=5*/%rows, %w), custom_call_target="tpu_custom_call", metadata={{op_name="{_IN}/moe.experts/pallas_call"}}
  %other.10 = (bf16[4,64,64]{{2,1,0}}, f32[4,64,1]{{2,1,0}}) custom-call(%q, %k, /*index=2*/%v), custom_call_target="tpu_custom_call"
  ROOT %fusion.11 = bf16[8,128]{{1,0}} fusion(%a), kind=kOutput, calls=%f11, metadata={{op_name="{_IN}/attn.out/dot_general"}}
}}
"""

#: per step, in microseconds: (name, start, duration); under remat the
#: forward kernels run twice
STEP_OPS = (("fusion.1", 0, 2), ("fusion.2", 2, 4),
            ("attn.window.3", 6, 3), ("attn.window.3", 9, 3),
            ("attn.window.4", 12, 7), ("attn.window.5", 19, 5),
            ("attn.attend.6", 24, 8), ("attn.attend.6", 32, 8),
            ("attn.attend.7", 40, 17), ("attn.attend.8", 57, 12),
            ("moe.experts.9", 69, 6), ("other.10", 75, 2),
            ("fusion.11", 77, 3))
SHAPES = {"calls": 1, "shape": (1, 4, 2, 64, 16, 16),
          "layers": {"window": (1, 8.0), "full": (1, 32.5)}, "remat": True}


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


def _run(trace, table, text=HLO_TEXT, shapes=SHAPES, v5e=True):
    program = type("P", (), {"as_text": staticmethod(lambda: text)})
    family = type("F", (), {
        "flash_kernel_shapes": staticmethod(lambda c, t: shapes)})
    return fakes.fake_run(
        trace, table, program=program,
        peaks=peaks.for_kind("TPU v5 lite") if v5e else None, family=family,
        cell=type("C", (), {"config": {}, "traffic": {}, "name": "fake"}))


def test_the_four_readers_on_a_hand_made_trace(trace, table, capfd):
    run = _run(trace, table)
    roof = reader("diff_flash_roofline")
    v5e = peaks.for_kind("TPU v5 lite")
    kernels, windowed = roof.traced_kernels(run)
    # by signature AND shape: not the grouped matmul, not the flash-like
    # kernel of another width; the windowed ones by their scope, in the
    # metadata or in the name
    assert kernels == {
        "attn.window.3": "forward", "attn.window.4": "dkdv",
        "attn.window.5": "dq", "attn.attend.6": "forward",
        "attn.attend.7": "dkdv", "attn.attend.8": "dq"}
    assert windowed == {"attn.window.3", "attn.window.4", "attn.window.5"}

    def least_of(keys_seen):
        return sum(n * roof.least_seconds(kind, SHAPES["shape"], keys_seen,
                                          v5e)[0]
                   for kind, n in (("forward", 2), ("dkdv", 1), ("dq", 1)))

    assert reader("swa_flash_ms_per_step").read(run) == pytest.approx(18e-3)
    assert reader("full_flash_ms_per_step").read(run) == pytest.approx(45e-3)
    assert reader("swa_flash_roofline").read(run) == pytest.approx(
        100 * least_of(8.0) / (18 * US))
    assert reader("full_flash_roofline").read(run) == pytest.approx(
        100 * least_of(32.5) / (45 * US))
    part = reader("swa_flash_roofline").part
    assert part(run, "window") == (pytest.approx(18e-3),
                                   pytest.approx(least_of(8.0) * 1e3), 1)
    log = capfd.readouterr().err
    assert "swa_flash_ms_per_step: 0.018 ms over 1 layer(s)" in log
    assert "full_flash_ms_per_step: 0.045 ms over 1 layer(s)" in log
    # off the chip (no peaks): the times read, the shares do not
    bare = _run(trace, table, v5e=False)
    assert reader("swa_flash_roofline").read(bare) is None
    assert reader("full_flash_roofline").read(bare) is None
    assert reader("swa_flash_ms_per_step").read(bare) == pytest.approx(18e-3)
    # the expert layer's readers tell its kernel on the same trace
    assert reader("moe_experts_ms_per_step").read(run) == pytest.approx(6e-3)
    assert reader("moe_ms_per_step").read(run) == pytest.approx(8e-3)


def test_a_program_without_the_scopes_reads_as_nothing(trace, table):
    """The parent's program, or a cell of another family: every new reader
    returns None and raises nothing."""
    plain = hlo.index(fakes.HLO_TEXT)
    old_trace = xplane.reduce_profile(
        ProfileData.from_text_proto(fakes.hand_made_xspace()))
    run = _run(old_trace, plain, text=fakes.HLO_TEXT)
    assert [reader(m).read(run) for m in NEW] == [None] * 4
    program = type("P", (), {"as_text": staticmethod(lambda: fakes.HLO_TEXT)})
    for bare in (fakes.fake_run(None, {}, program=program, peaks=None),
                 fakes.fake_run(xplane.Trace(), {}, program=None,
                                peaks=None, family=None)):
        assert [reader(m).read(bare) for m in NEW] == [None] * 4
    # the scopes without a family that gives the shapes
    no_shapes = fakes.fake_run(
        trace, table, peaks=peaks.for_kind("TPU v5 lite"),
        family=type("F", (), {}),
        program=type("P", (), {"as_text": staticmethod(lambda: HLO_TEXT)}),
        cell=type("C", (), {"config": {}, "traffic": {}, "name": "fake"}))
    assert [reader(m).read(no_shapes) for m in NEW] == [None] * 4
    # a family that names one kind of layer: the other kind's readers read
    # nothing
    full_only = _run(trace, table, shapes=dict(
        SHAPES, layers={"full": (1, 32.5)}))
    assert reader("swa_flash_ms_per_step").read(full_only) is None
    assert reader("swa_flash_roofline").read(full_only) is None
    assert reader("full_flash_ms_per_step").read(full_only) == \
        pytest.approx(45e-3)


# ---------------------------------------------------------------- entries

def _named(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def test_the_entries_are_the_cells_found_by_name(bench):
    entry = _named(bench["workloads"], CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "spmd-dp1-s16384-smallthinker", 1)
    config = _named(bench["configs"], CONFIG)
    assert config["reduced"] == ["n_layer", "moe_num_primary_experts",
                                 "vocab_size"]
    assert config["file"] == "benchmark/configs/smallthinker-21b-a3b.json"
    assert len(entry["why"]) <= 200 and len(config["why"]) <= 200
    # appended behind the cells and configurations that were there
    cells = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    assert cells.index("phi4flash-1chip") < cells.index(CELL)
    assert configs.index("phi-4-mini-flash") < configs.index(CONFIG)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    metrics = [m["name"] for m in bench["per_layer"]]
    first = metrics.index(NEW[0])
    assert metrics[first:first + 4] == list(NEW)
    assert metrics.index("diff_flash_roofline") < first
    for name in NEW:
        m = _named(bench["per_layer"], name)
        assert m["workloads"] == [CELL]
        assert (m["source"], m["layer"], m["moves"]) == (
            "device_trace", "Pallas kernels", "samples_per_s_per_chip")
        assert (m["unit"], m["better"]) == (
            ("%", "higher") if name.endswith("_roofline") else ("ms",
                                                               "lower"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        listed = m.get("workloads", ())
        if m["name"] in JOINED:
            assert listed.index("phi4flash-1chip") < listed.index(CELL), \
                m["name"]
        elif m["name"] in MOE:
            assert listed == ["olmoe-1chip", "dsv2lite-1chip", CELL], \
                m["name"]
        elif m["name"] not in NEW and m["name"] != "setup_s":
            # not the dense MLP's, the other flash readers', the other
            # mixers'; nor the four held to the letter elsewhere
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
    assert {layers[m] for m in NEW} == {"Pallas kernels"}
    assert {layers[m] for m in MOE} == {"expert layer"}
    # no older cell reads the new metrics
    for w in bench["workloads"]:
        if w["name"] != CELL:
            assert not [m for m in spec.load_cell(w["name"]).per_layer
                        if m["name"] in NEW], w["name"]


# -------------------------------------------------------------- rehearsal

@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_the_cell_runs_end_to_end_at_a_tiny_size(trace, tmp_path, capfd):
    hvd.shutdown()   # the cell initialises on exactly its own devices
    cell = spec.load_cell("tiny-smallthinker-1chip", root=TINY)
    assert cell.config["family"] == "smallthinker"
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
    assert "rows of the 2 held experts in the reference's routing" in log
    assert "compile request(s) after warm-up" not in log
    problems = [ln for ln in log.splitlines() if "NOT CORRECT" in ln]
    if trace:   # the one thing a CPU trace cannot show
        assert ["the trace holds no whole step" in p for p in problems] == \
            [True]
    else:
        assert problems == [] and line["correct"] is True
    # no time, rate or share from the CPU under a device metric's name
    assert line["metrics"] == {}
