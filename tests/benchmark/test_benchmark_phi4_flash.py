"""What PR 36 adds to the benchmark: the `phi4_flash` family's arithmetic
against the configuration's published numbers, the six new readers
(`ssm_ms_per_step`, `ssm_scan_ms_per_step`, `ssm_scan_roofline`,
`gmu_ms_per_step`, `diff_flash_ms_per_step`, `diff_flash_roofline`) with the
counts they rest on, on a hand-made trace, the entries by name and by order,
and the cell's path rehearsed at a tiny size on the CPU
(`fixtures/tiny-phi4-flash`)."""

import json
import os
import time

import pytest
from jax.profiler import ProfileData

import benchmark_fakes as fakes
import horovod_tpu as hvd
from benchmark.harness import hlo, peaks, runner, scope_time, spec, xplane

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                    "tiny-phi4-flash")
US = 1e-6
CELL = "phi4flash-1chip"
NEW = ("ssm_ms_per_step", "ssm_scan_ms_per_step", "ssm_scan_roofline",
       "gmu_ms_per_step", "diff_flash_ms_per_step", "diff_flash_roofline")
JOINED = ("samples_per_s_per_chip", "step_hbm_gib", "device_step_ms", "mfu",
          "device_idle_share", "window_stall_share")
#: PR 34's eight entries, and the cells each lists: as that PR's own test
#: has them, with this cell behind the others where its scopes run (the
#: attention, the MLPs, the vocabulary's work, the update, and what no known
#: prefix holds: `ssm.*` and `gmu.*` among it until `PREFIXES` knows them)
EIGHT = ("attn_ms_per_step", "mlp_ms_per_step", "vocab_ms_per_step",
         "opt_update_ms_per_step", "grad_reduce_ms_per_step",
         "other_ms_per_step", "opt_reduce_host_ms", "opt_apply_host_ms")
BY_SCOPE = ("attn_ms_per_step", "mlp_ms_per_step", "vocab_ms_per_step",
            "opt_update_ms_per_step", "other_ms_per_step")
LISTS = {"attn_ms_per_step": ["lm-1chip", "lm-dp4", CELL],
         "mlp_ms_per_step": ["lm-1chip", "lm-dp4", "dsv2lite-1chip", CELL],
         "vocab_ms_per_step": ["lm-1chip", "lm-dp4", "dsv2lite-1chip", CELL],
         "opt_update_ms_per_step": ["lm-1chip", "lm-dp4", "dsv2lite-1chip",
                                    CELL],
         "grad_reduce_ms_per_step": ["lm-dp4"],
         "other_ms_per_step": ["lm-1chip", "lm-dp4", "dsv2lite-1chip", CELL],
         "opt_reduce_host_ms": ["resnet50-eager"],
         "opt_apply_host_ms": ["resnet50-eager"]}
SEGMENTS = [[["ssm", "window"], 2], [["ssm", "full"], 1],
            [["gmu", "cross"], 2]]


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
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False}
    assert {k: cell.config[k] for k in published} == published
    assert cell.config["published"] == {
        "num_hidden_layers": 32, "vocab_size": 200064,
        "segments": [[["ssm", "window"], 8], [["ssm", "full"], 1],
                     [["gmu", "cross"], 7]]}
    assert sorted(cell.config["reduced"]) == ["n_layer", "vocab_size"]
    assert cell.config["source"] == (
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/"
        "main/config.json")
    # the floors: every kind, at least four layers; an eighth of the
    # vocabulary
    assert (cell.config["n_layer"], cell.config["vocab_size"]) == (10, 25008)
    assert 25008 * 8 == 200064
    assert cell.config["segments"] == SEGMENTS
    assert cell.config["ssm"] == {"d_state": 16, "d_conv": 4, "expand": 2,
                                  "dt_rank": 160}
    for key in ("assumed", "departures", "deployment"):
        assert cell.config[key]
    assert set(cell.config["assumed"]) >= {
        "state_space", "attention", "positions", "window", "handing_on",
        "mup", "optimizer", "sequence"}
    assert "not a pipeline stage" in cell.config["reduced"]["n_layer"]
    deployment = cell.config["deployment"]
    assert (deployment["chips_sharing_a_layer"],
            deployment["chips_sharing_the_vocabulary"],
            deployment["chip"]) == (1, 8, 0)
    assert (cell.traffic["seq_len"], cell.traffic["per_chip_batch"],
            cell.traffic["mesh"], cell.traffic["trace_steps"],
            cell.traffic["path"], cell.chips) == (8192, 1, {}, 5, "tfm_spmd",
                                                  1)
    assert cell.traffic["optimizer"] == {
        "name": "adamw", "learning_rate": 3e-04, "b1": 0.9, "b2": 0.95,
        "eps": 1e-08, "weight_decay": 0.1}


def test_the_program_is_the_configurations(cell_and_family):
    cell, family = cell_and_family
    cfg = family.transformer_config(cell.config)
    assert cfg.segments == ((("ssm", "window"), 2), (("ssm", "full"), 1),
                            (("gmu", "cross"), 2))
    assert family.kinds(cell.config) == (
        "ssm", "window", "ssm", "window", "ssm", "full", "gmu", "cross",
        "gmu", "cross")
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_ff,
            cfg.n_layers, cfg.vocab, cfg.window) == (
        2560, 40, 20, 64, 10240, 10, 25008, 512)
    assert (cfg.ssm_channels, cfg.ssm_state, cfg.ssm_conv, cfg.dt_rank) == \
        (5120, 16, 4, 160)
    assert (cfg.norm, cfg.positions, cfg.mlp, cfg.tied_head,
            cfg.attention_bias, cfg.diff_attention, cfg.post_norm) == (
        "layernorm", "none", "swiglu", True, True, True, False)
    assert cfg.num_experts == 0 and cfg.score_scale is None
    assert (cfg.attention, cfg.attn, cfg.remat, cfg.remat_policy,
            str(cfg.dtype)) == ("mha", "flash", True, "full", "bfloat16")
    with pytest.raises(ValueError, match="no equations"):
        family.transformer_config(dict(cell.config,
                                       tie_word_embeddings=False))
    with pytest.raises(ValueError, match="no equations"):
        family.transformer_config(dict(cell.config, mlp_bias=True))
    with pytest.raises(ValueError, match="constant"):
        family.transformer_config(dict(cell.config, layer_norm_eps=1e-6))
    with pytest.raises(ValueError, match="n_layer is 12"):
        family.transformer_config(dict(cell.config, n_layer=12))
    with pytest.raises(ValueError, match="dt_rank 128 is not the ceiling"):
        family.transformer_config(dict(
            cell.config, ssm=dict(cell.config["ssm"], dt_rank=128)))


def test_parameters_and_bytes_as_the_configuration_file_says(cell_and_family):
    cell, family = cell_and_family
    import jax
    from horovod_tpu.models import transformer as tfm
    cfg = family.transformer_config(cell.config)
    shapes = jax.eval_shape(lambda k: tfm.init(k, cfg), jax.random.PRNGKey(0))

    def count(tree):
        return sum(x.size for x in jax.tree_util.tree_leaves(tree))

    d, f, e = 2560, 10240, 5120
    ssm = (d * 2 * e + e * 4 + e + e * (160 + 32) + 160 * e + e + e * 16 + e
           + e * d)
    attention = d * (40 + 2 * 20) * 64 + (40 + 2 * 20) * 64 + d * d + d \
        + 4 * 64 + 128
    gmu = 2 * d * e
    cross = 2 * (d * d + d) + 4 * 64 + 128
    assert (ssm, attention, gmu, cross) == (41_241_600, 19_668_864,
                                            26_214_400, 13_112_704)
    mlp, norms = 3 * d * f, 4 * d
    assert mlp == 78_643_200
    first, middle, last = shapes["segments"]
    assert count(first["ssm"]) == 2 * (ssm + mlp + norms)
    assert count(first["window"]) == 2 * (attention + mlp + norms)
    assert count(middle["ssm"]) == ssm + mlp + norms
    assert count(middle["full"]) == attention + mlp + norms
    assert count(last["gmu"]) == 2 * (gmu + mlp + norms)
    assert count(last["cross"]) == 2 * (cross + mlp + norms)
    layers = 3 * ssm + 3 * attention + 2 * gmu + 2 * cross \
        + 10 * (mlp + norms)
    assert layers == 1_047_920_000
    total = count(shapes)
    assert total == layers + 25008 * d + 2 * d == 1_111_945_600
    assert "unembed" not in shapes                     # the head is tied
    # bf16 weight, gradient and two Adam moments: 8.90 GB = 8.28 GiB
    assert 8 * total / 2 ** 30 == pytest.approx(8.28, abs=0.01)
    assert all(x.dtype == "bfloat16" for x in
               jax.tree_util.tree_leaves(shapes))
    assert first["ssm"]["ssm_a_log"].shape == (2, 1, e, 16)
    assert middle["full"]["wk"].shape == (1, 1, d, 20, 64)
    assert last["cross"]["wq"].shape == (2, 1, d, 40, 64)
    # the numbers the configuration file writes out
    assert "1,111,945,600 parameters x 8 bytes" in \
        cell.config["reduced"]["n_layer"]
    # whole, by the same leaves: the catalog's 3.8 B
    whole = family.transformer_config(dict(
        cell.config, n_layer=32, vocab_size=200064,
        segments=cell.config["published"]["segments"]))
    assert count(jax.eval_shape(lambda k: tfm.init(k, whole),
                                jax.random.PRNGKey(0))) == 3_852_562_944


def test_flops_per_token_by_hand(cell_and_family):
    cell, family = cell_and_family
    parts = family.forward_flops_per_token(cell.config, 8192)
    d, e = 2560, 5120
    assert parts["mlps"] == 10 * 2 * 3 * d * 10240 == 1_572_864_000
    assert parts["ssm_projections"] == 3 * 2 * (
        d * 2 * e + e * 192 + 160 * e + e * d) == 246_743_040
    assert parts["recurrence"] == 3 * 2 * 3 * e * 16 == 1_474_560
    assert parts["attention_projections"] == 3 * 2 * (
        d * 80 * 64 + d * d) + 2 * 2 * 2 * d * d == 170_393_600
    assert parts["gmu"] == 2 * 2 * 2 * d * e == 104_857_600
    assert parts["head"] == 2 * d * 25008 == 128_040_960
    # both softmaxes: 20 pairs, q.k at 64 and p.v at 128, 2 FLOPs each
    a_pair = 2 * 2 * 20 * (64 + 128)
    band = 512 - 512 * 511 / (2 * 8192)
    assert family.keys_seen(8192, 512) == pytest.approx(band) \
        == pytest.approx(496.03, abs=0.01)
    assert family.keys_seen(8192) == 4096.5
    assert parts["attention"] == pytest.approx(
        a_pair * (2 * band + 3 * 4096.5))
    forward = sum(parts.values())
    assert forward == pytest.approx(2.428e9, rel=1e-3)
    assert family.flops_per_sample(cell.config, cell.traffic) == \
        pytest.approx(3 * forward) == pytest.approx(7.285e9, rel=1e-3)
    # the head's share fell with the deeper cut of the vocabulary
    assert parts["head"] / forward == pytest.approx(0.0527, abs=0.001)
    # what no other cell runs: scans, differential attention, the GMUs
    own = sum(parts[k] for k in ("ssm_projections", "recurrence",
                                 "attention_projections", "attention", "gmu"))
    assert own / forward == pytest.approx(0.300, abs=0.003)
    assert family.samples_per_step(cell.traffic, 1) == 8192
    assert family.flash_kernel_shapes(cell.config, cell.traffic) == {
        "calls": 2, "shape": (1, 20, 10, 8192, 64, 128),
        "layers": {"window": (2, pytest.approx(band)),
                   "full": (3, 4096.5)},
        "remat": True}


def test_the_scans_least_work_by_hand(cell_and_family):
    cell, family = cell_and_family
    pairs = 8192 * 5120                             # (token, channel) pairs
    forward, backward = family.scan_work(8192, 5120, 16)
    # c in bf16, delta in float32, y in bf16; B and C in bf16
    assert forward == ((7 * 16 + 3) * pairs,
                       pairs * (2 + 4 + 2) + 2 * 8192 * 16 * 2)
    assert forward == (4_823_449_600, 336_068_608)
    # reads those and dy, writes dc, ddelta, dB, dC
    assert backward == ((21 * 16 + 8) * pairs,
                        pairs * 14 + 4 * 8192 * 16 * 2)
    assert backward[1] == 588_251_136
    work = family.ssm_scan_work(cell.config, cell.traffic)
    # three state-space layers; under remat the forward runs twice
    assert work == ((6, *forward), (3, *backward))
    assert family.ssm_scan_work(
        dict(cell.config, program=dict(cell.config["program"], remat=False)),
        cell.traffic)[0][0] == 3
    from benchmark.layer_metrics.gdn_scan_roofline import least_seconds
    v5e = peaks.for_kind("TPU v5 lite")
    fwd_s, bound = least_seconds(work[0], v5e)
    assert bound == "memory"               # 0.410 ms a pass against 0.024
    assert fwd_s == pytest.approx(6 * 336_068_608 / 819e9)
    bwd_s, bound = least_seconds(work[1], v5e)
    assert bound == "memory"
    # 4.62 ms a step at the least
    assert (fwd_s + bwd_s) * 1e3 == pytest.approx(4.62, abs=0.01)


def test_a_flash_calls_least_work_by_hand():
    roof = reader("diff_flash_roofline")
    shape = (1, 20, 10, 8192, 64, 128)
    v5e = peaks.for_kind("TPU v5 lite")
    q, k = 20 * 8192 * 64 * 2, 10 * 8192 * 64 * 2
    o, v = 20 * 8192 * 128 * 2, 10 * 8192 * 128 * 2
    lse = 20 * 8192 * 4
    entries = 20 * 8192 * 4096.5
    assert roof.work("forward", shape, 4096.5) == (
        2 * entries * 192, q + k + v + o + lse)
    assert roof.work("dkdv", shape, 4096.5) == (
        2 * entries * 384, q + k + v + 2 * o + lse + k + v)
    assert roof.work("dq", shape, 4096.5) == (
        2 * entries * 256, q + k + v + 2 * o + lse + q)
    # a full layer's forward call is compute-bound, 1.31 ms at the peak; the
    # band's an eighth of it
    seconds, bound = roof.least_seconds("forward", shape, 4096.5, v5e)
    assert bound == "compute" and seconds * 1e3 == pytest.approx(1.308,
                                                                 abs=0.002)
    banded, _ = roof.least_seconds("forward", shape, 496.03, v5e)
    assert banded / seconds == pytest.approx(496.03 / 4096.5, rel=1e-3)
    with pytest.raises(ValueError):
        roof.work("sideways", shape, 1.0)


# ---------------------------------------------------------------- readers

#: A compiled step in miniature: a state-space layer's projection,
#: convolution, scan (the softplus fusion, the forward kernel, its remat
#: repeat, the backward kernel), gate and output; a Gated Memory Unit's
#: three parts; a windowed layer's three flash kernels under `attn.window`
#: (the backward ones once with their scope in the metadata, once in the
#: name alone) and a full layer's, all at (1 x 4 | 1 x 2, 64, 16 | 32); a
#: kernel of a flash signature and another shape; an MLP fusion.
_IN = "jit(step)/jvp()/while/body/closed_call/checkpoint"
_BACK = "jit(step)/transpose(jvp())/while/body/closed_call/checkpoint"
HLO_TEXT = f"""
HloModule jit_step

ENTRY %main (a: bf16[8,128]) -> bf16[8,128] {{
  %a = bf16[8,128]{{1,0}} parameter(0)
  %fusion.1 = bf16[8,96]{{1,0}} fusion(%a), kind=kOutput, calls=%f1, metadata={{op_name="{_IN}/ssm.project/bsd,dte->tbse/dot_general"}}
  %fusion.2 = bf16[8,96]{{1,0}} fusion(%q), kind=kLoop, calls=%f2, metadata={{op_name="{_IN}/ssm.conv/mul"}}
  %fusion.3 = f32[8,96]{{1,0}} fusion(%s), kind=kLoop, calls=%f3, metadata={{op_name="{_IN}/ssm.scan/softplus"}}
  %ssm.scan.4 = bf16[1,64,96]{{2,1,0}} custom-call(%c, %dl, %a, %b, %o, /*index=5*/%d), custom_call_target="tpu_custom_call", metadata={{op_name="{_IN}/ssm.scan/pallas_call"}}
  %ssm.scan.5 = (bf16[1,64,96]{{2,1,0}}, f32[1,2,8,96]{{3,2,1,0}}) custom-call(%c, %dl, %a, %b, %o, /*index=5*/%d), custom_call_target="tpu_custom_call", metadata={{op_name="{_BACK}/rematted_computation/ssm.scan/pallas_call"}}
  %ssm.scan.6 = (bf16[1,64,96]{{2,1,0}}, f32[1,64,96]{{2,1,0}}, f32[1,8,96]{{2,1,0}}, f32[1,1,64,8,96]{{4,3,2,1,0}}, f32[1,1,64,8,96]{{4,3,2,1,0}}) custom-call(%c, %dl, %a, %b, %o, /*index=5*/%d, %dy, %s0), custom_call_target="tpu_custom_call", metadata={{op_name="{_BACK}/ssm.scan/pallas_call"}}
  %fusion.7 = bf16[8,96]{{1,0}} fusion(%o), kind=kLoop, calls=%f7, metadata={{op_name="{_IN}/ssm.gate/mul"}}
  %fusion.8 = bf16[8,128]{{1,0}} fusion(%o), kind=kOutput, calls=%f8, metadata={{op_name="{_IN}/ssm.out/dot_general"}}
  %fusion.9 = bf16[8,96]{{1,0}} fusion(%a), kind=kOutput, calls=%f9, metadata={{op_name="{_IN}/gmu.project/dot_general"}}
  %fusion.10 = bf16[8,96]{{1,0}} fusion(%a), kind=kLoop, calls=%f10, metadata={{op_name="{_IN}/gmu.gate/mul"}}
  %fusion.11 = bf16[8,128]{{1,0}} fusion(%a), kind=kOutput, calls=%f11, metadata={{op_name="{_IN}/gmu.out/dot_general"}}
  %fusion.12 = bf16[8,128]{{1,0}} fusion(%a), kind=kOutput, calls=%f12, metadata={{op_name="{_IN}/attn.project/dot_general"}}
  %attn.window.13 = (bf16[4,64,32]{{2,1,0}}, f32[4,64,1]{{2,1,0}}) custom-call(%q, %k, /*index=2*/%v), custom_call_target="tpu_custom_call", metadata={{op_name="{_IN}/attn.attend/attn.window/pallas_call"}}
  %attn.window.14 = (bf16[2,64,16]{{2,1,0}}, bf16[2,64,32]{{2,1,0}}) custom-call(%q, %k, %v, %o, %do, /*index=5*/%lse), custom_call_target="tpu_custom_call", metadata={{op_name="{_BACK}/attn.attend/attn.window/pallas_call"}}
  %attn.window.15 = bf16[4,64,16]{{2,1,0}} custom-call(%q, %k, %v, %o, %do, /*index=5*/%lse), custom_call_target="tpu_custom_call"
  %attn.attend.16 = (bf16[4,64,32]{{2,1,0}}, f32[4,64,1]{{2,1,0}}) custom-call(%q, %k, /*index=2*/%v), custom_call_target="tpu_custom_call", metadata={{op_name="{_IN}/attn.attend/pallas_call"}}
  %attn.attend.17 = (bf16[2,64,16]{{2,1,0}}, bf16[2,64,32]{{2,1,0}}) custom-call(%q, %k, %v, %o, %do, /*index=5*/%lse), custom_call_target="tpu_custom_call", metadata={{op_name="{_BACK}/attn.attend/pallas_call"}}
  %attn.attend.18 = bf16[4,64,16]{{2,1,0}} custom-call(%q, %k, %v, %o, %do, /*index=5*/%lse), custom_call_target="tpu_custom_call", metadata={{op_name="{_BACK}/attn.attend/pallas_call"}}
  %other.19 = (bf16[4,64,64]{{2,1,0}}, f32[4,64,1]{{2,1,0}}) custom-call(%q, %k, /*index=2*/%v), custom_call_target="tpu_custom_call"
  ROOT %fusion.20 = bf16[8,128]{{1,0}} fusion(%a), kind=kOutput, calls=%f20, metadata={{op_name="{_IN}/mlp.dense/bsd,df->bsf/dot_general"}}
}}
"""

#: per step, in microseconds: (name, start, duration)
STEP_OPS = (("fusion.1", 0, 5), ("fusion.2", 5, 3), ("fusion.3", 8, 2),
            ("ssm.scan.4", 10, 6), ("ssm.scan.5", 16, 6),
            ("ssm.scan.6", 22, 9), ("fusion.7", 31, 2), ("fusion.8", 33, 3),
            ("fusion.9", 36, 4), ("fusion.10", 40, 1), ("fusion.11", 41, 3),
            ("fusion.12", 44, 4), ("attn.window.13", 48, 2),
            ("attn.window.13", 50, 2), ("attn.window.14", 52, 3),
            ("attn.window.15", 55, 2), ("attn.attend.16", 57, 8),
            ("attn.attend.16", 65, 8), ("attn.attend.17", 73, 11),
            ("attn.attend.18", 84, 9), ("other.19", 93, 2),
            ("fusion.20", 95, 3))
SHAPES = {"calls": 2, "shape": (1, 4, 2, 64, 16, 32),
          "layers": {"window": (1, 8.0), "full": (2, 32.5)}, "remat": True}
SCAN_WORK = ((2, 3.0e6, 4.0e3), (1, 6.0e6, 7.0e3))


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


def _run(trace, table, text=HLO_TEXT, shapes=SHAPES, work=SCAN_WORK):
    program = type("P", (), {"as_text": staticmethod(lambda: text)})
    family = type("F", (), {
        "flash_kernel_shapes": staticmethod(lambda c, t: shapes),
        "ssm_scan_work": staticmethod(lambda c, t: work)})
    return fakes.fake_run(
        trace, table, program=program, peaks=peaks.for_kind("TPU v5 lite"),
        family=family,
        cell=type("C", (), {"config": {}, "traffic": {}, "name": "fake"}))


def test_scopes_and_kernels_are_told_from_the_programs_own_text(table):
    assert scope_time.names_under(HLO_TEXT, table, "ssm.") == {
        "fusion.1", "fusion.2", "fusion.3", "ssm.scan.4", "ssm.scan.5",
        "ssm.scan.6", "fusion.7", "fusion.8"}
    assert scope_time.names_under(HLO_TEXT, table, "ssm.scan") == {
        "fusion.3", "ssm.scan.4", "ssm.scan.5", "ssm.scan.6"}
    assert scope_time.names_under(HLO_TEXT, table, "gmu.") == {
        "fusion.9", "fusion.10", "fusion.11"}
    roof = reader("diff_flash_roofline")
    # by signature AND shape: not the scan's kernels (of which two have a
    # flash kernel's operands and results), not the flash-like kernel of
    # another width
    assert roof.flash_kernels(table, SHAPES["shape"]) == {
        "attn.window.13": "forward", "attn.window.14": "dkdv",
        "attn.window.15": "dq", "attn.attend.16": "forward",
        "attn.attend.17": "dkdv", "attn.attend.18": "dq"}
    assert roof.flash_kernels(table, (1, 4, 2, 64, 16, 64)) == {
        "other.19": "forward", "attn.window.15": "dq",
        "attn.window.14": "dkdv", "attn.attend.17": "dkdv",
        "attn.attend.18": "dq"}
    assert roof.flash_kernels(table, (1, 4, 2, 128, 16, 32)) == {}


def test_the_six_readers_on_a_hand_made_trace(trace, table, capfd):
    run = _run(trace, table)
    assert reader("ssm_ms_per_step").read(run) == pytest.approx(
        (5 + 3 + 2 + 6 + 6 + 9 + 2 + 3) * 1e-3)
    assert reader("ssm_scan_ms_per_step").read(run) == pytest.approx(
        (2 + 6 + 6 + 9) * 1e-3)
    assert reader("gmu_ms_per_step").read(run) == pytest.approx(
        (4 + 1 + 3) * 1e-3)
    v5e = peaks.for_kind("TPU v5 lite")
    least = 2 * max(3.0e6 / v5e.bf16_flops, 4.0e3 / v5e.hbm_bytes_per_s) \
        + max(6.0e6 / v5e.bf16_flops, 7.0e3 / v5e.hbm_bytes_per_s)
    assert reader("ssm_scan_roofline").read(run) == pytest.approx(
        100 * least / (23 * US))
    roof = reader("diff_flash_roofline")
    # the windowed kernels by their scope, in the metadata or in the name
    kernels, windowed = roof.traced_kernels(run)
    assert windowed == {"attn.window.13", "attn.window.14",
                        "attn.window.15"} and len(kernels) == 6
    parts = roof.parts(run)

    def least_of(keys_seen):
        return 2 * roof.least_seconds("forward", SHAPES["shape"], keys_seen,
                                      v5e)[0] \
            + roof.least_seconds("dkdv", SHAPES["shape"], keys_seen, v5e)[0] \
            + roof.least_seconds("dq", SHAPES["shape"], keys_seen, v5e)[0]

    assert parts["window"] == (pytest.approx(9e-3),
                               pytest.approx(least_of(8.0) * 1e3))
    assert parts["full"] == (pytest.approx(36e-3),
                             pytest.approx(least_of(32.5) * 1e3))
    assert reader("diff_flash_ms_per_step").read(run) == pytest.approx(45e-3)
    assert roof.read(run) == pytest.approx(
        100 * (least_of(8.0) + least_of(32.5)) / (45 * US))
    log = capfd.readouterr().err
    assert "diff_flash_ms_per_step by part: window 0.009 ms over 1 layer" \
        in log and "full 0.036 ms over 2 layer(s), 0.018 a layer" in log
    # the parts of the two layers' scopes add up to the scopes' time
    from benchmark.layer_metrics.gdn_scan_ms_per_step import ms_under
    assert sum(ms_under(run, s) for s in (
        "ssm.project", "ssm.conv", "ssm.scan", "ssm.gate", "ssm.out")) == \
        pytest.approx(reader("ssm_ms_per_step").read(run))


def test_a_program_without_the_scopes_reads_as_nothing(trace, table):
    """The parent's program, or a cell of another family: every new reader
    returns None and raises nothing."""
    plain = hlo.index(fakes.HLO_TEXT)
    old_trace = xplane.reduce_profile(
        ProfileData.from_text_proto(fakes.hand_made_xspace()))
    run = _run(old_trace, plain, text=fakes.HLO_TEXT)
    assert [reader(m).read(run) for m in NEW] == [None] * 6
    program = type("P", (), {"as_text": staticmethod(lambda: fakes.HLO_TEXT)})
    for bare in (fakes.fake_run(None, {}, program=program, peaks=None),
                 fakes.fake_run(xplane.Trace(), {}, program=None,
                                peaks=None, family=None)):
        assert [reader(m).read(bare) for m in NEW] == [None] * 6
    # the scopes without a family that counts the work: the times read, the
    # shares do not
    no_work = fakes.fake_run(
        trace, table, peaks=peaks.for_kind("TPU v5 lite"),
        family=type("F", (), {}),
        program=type("P", (), {"as_text": staticmethod(lambda: HLO_TEXT)}),
        cell=type("C", (), {"config": {}, "traffic": {}, "name": "fake"}))
    assert reader("ssm_scan_roofline").read(no_work) is None
    assert reader("ssm_scan_ms_per_step").read(no_work) == pytest.approx(
        23e-3)
    assert reader("diff_flash_roofline").read(no_work) is None
    assert reader("diff_flash_ms_per_step").read(no_work) is None
    # an older family's cell, whose flash reader goes by one head width
    olmo = spec.load_cell("olmohybrid-1chip")
    assert not [m for m in olmo.per_layer if m["name"] in NEW]


def test_the_entries_are_the_cells_and_name_their_layers():
    cell = spec.load_cell(CELL)
    assert [m["name"] for m in cell.end_to_end] == [
        "samples_per_s_per_chip", "step_hbm_gib", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert names == ["init_s", "compile_s", "device_step_ms", "mfu",
                     "device_idle_share", "window_stall_share", *BY_SCOPE,
                     *NEW]
    layers = {m["name"]: m["layer"] for m in cell.per_layer}
    assert [layers[m] for m in NEW] == ["state-space layers"] * 4 \
        + ["Pallas kernels"] * 2
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # by name and by order, never by "last": the next PR appends after these
    cells = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    metrics = [m["name"] for m in bench["per_layer"]]
    assert cells.index("olmohybrid-1chip") < cells.index(CELL)
    assert configs.index("olmo-hybrid-7b") < configs.index("phi-4-mini-flash")
    entry = bench["workloads"][cells.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "phi-4-mini-flash", "spmd-dp1-s8192-phi4flash", 1)
    assert bench["configs"][configs.index("phi-4-mini-flash")]["reduced"] \
        == ["n_layer", "vocab_size"]
    first = metrics.index(NEW[0])
    assert metrics[first:first + 6] == list(NEW)
    for m in bench["end_to_end"] + bench["per_layer"]:
        listed = m.get("workloads", ())
        if m["name"] in JOINED:
            assert listed.index("olmohybrid-1chip") < listed.index(CELL), \
                m["name"]
        elif m["name"] in BY_SCOPE:
            assert listed.index("lm-dp4") < listed.index(CELL), m["name"]
        elif m["name"] in NEW:
            assert listed == [CELL]
            assert (m["source"], m["moves"]) == ("device_trace",
                                                 "samples_per_s_per_chip")
        else:   # the older flash readers, the kinds of layer it has not
            assert CELL not in listed, m["name"]


def test_pr34s_eight_entries_hold_what_their_pinned_test_held():
    """`test_benchmark_step_scopes.py`'s test of the entries takes the LAST
    eight per-layer metrics for PR 34's, which the six appended here make
    false (`tests/conftest.py` expects it to fail). Every line it held,
    with the entries found by name: the eight stand together and in their
    order, in front of this PR's; each one's unit, direction, source, layer,
    what it moves and its `workloads` to the letter; how many of them each
    cell reads. The five whose scopes this cell runs list it last."""
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    metrics = [m["name"] for m in bench["per_layer"]]
    first = metrics.index(EIGHT[0])
    assert metrics[first:first + 8] == list(EIGHT)
    assert metrics[first + 8:first + 14] == list(NEW)
    for m in bench["per_layer"][first:first + 8]:
        assert (m["unit"], m["better"], m["workloads"]) == (
            "ms", "lower", LISTS[m["name"]]), m["name"]
        if m["name"] in EIGHT[:6]:
            assert (m["source"], m["layer"], m["moves"]) == (
                "device_trace", "jitted SPMD step", "samples_per_s_per_chip")
        else:
            assert (m["source"], m["layer"], m["moves"]) == (
                "host_clock", "eager optimizer path",
                "eager_samples_per_s_per_chip")
    for cell, named in (("lm-1chip", 5), ("lm-dp4", 6), ("dsv2lite-1chip", 4),
                        ("resnet50-eager", 2), ("olmoe-1chip", 0),
                        ("olmohybrid-1chip", 0), ("resnet50-jit", 0),
                        (CELL, 5)):
        new = [m["name"] for m in spec.load_cell(cell).per_layer
               if m["name"] in EIGHT]
        assert len(new) == named, cell
    assert [m["name"] for m in spec.load_cell(CELL).per_layer
            if m["name"] in EIGHT] == list(BY_SCOPE)


# -------------------------------------------------------------- rehearsal

@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_the_cell_runs_end_to_end_at_a_tiny_size(trace, tmp_path, capfd):
    hvd.shutdown()   # the cell initialises on exactly its own devices
    cell = spec.load_cell("tiny-phi4flash-1chip", root=TINY)
    assert cell.config["family"] == "phi4_flash"
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
