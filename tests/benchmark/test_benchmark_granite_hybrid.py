"""What PR 46 adds to the benchmark: the `granite_hybrid` family's arithmetic
against the configuration's published numbers (`ssd_scan_work` and the FLOPs
a token by hand), the three new readers (`ssd_ms_per_step`,
`ssd_scan_ms_per_step`, `ssd_scan_roofline`) on a hand-made trace, the
entries BY NAME (never by position or as "the last": the next PR appends
after these), and the cell's path rehearsed at a tiny size on the CPU
(`fixtures/tiny-granite4h`)."""

import json
import os
import time

import pytest
from jax.profiler import ProfileData

import benchmark_fakes as fakes
import horovod_tpu as hvd
from benchmark.harness import hlo, peaks, runner, spec, xplane

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                    "tiny-granite4h")
US = 1e-6
CELL = "granite4h-1chip"
CONFIG = "granite-4.0-h-small"
TRAFFIC = "spmd-dp1-s4096-granite4h"
NEW = ("ssd_ms_per_step", "ssd_scan_ms_per_step", "ssd_scan_roofline")
#: the lists the cell joined: every LM cell's and the expert layer's four
#: (the shared experts' list is held to the letter by an older test)
JOINED = ("samples_per_s_per_chip", "step_hbm_gib", "device_step_ms", "mfu",
          "device_idle_share", "window_stall_share")
MOE = ("moe_ms_per_step", "moe_experts_ms_per_step",
       "moe_dispatch_ms_per_step", "moe_experts_roofline")
SHARED = "moe_shared_ms_per_step"
#: the by-scope parts whose readers would read this program right and whose
#: lists it could NOT join: an older test of this directory holds each list
#: to the letter (PERF.md section 7)
HELD_TO_THE_LETTER = ("attn_ms_per_step", "vocab_ms_per_step",
                      "opt_update_ms_per_step", "other_ms_per_step")
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
D, E, N, H, P, Q = 4096, 4096, 128, 64, 64, 256


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
    the six counts of what is held, whose published sizes stand beside;
    the depth the program reads is `n_layer`."""
    cell, _ = cell_and_family
    published = {
        "attention_bias": False, "attention_multiplier": 0.0078125,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 768,
        "layer_types": PERIOD * 4, "logits_scaling": 16,
        "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_proj_bias": False,
        "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_experts_per_tok": 10,
        "num_hidden_layers": 40, "position_embedding_type": "nope",
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 1536, "tie_word_embeddings": True}
    assert {k: cell.config[k] for k in published} == published
    assert cell.config["published"] == {
        "num_hidden_layers": 40, "num_local_experts": 72,
        "vocab_size": 100352, "mamba_n_heads": 128,
        "num_attention_heads": 32, "num_key_value_heads": 8}
    assert sorted(cell.config["reduced"]) == [
        "mamba_n_heads", "n_layer", "num_attention_heads",
        "num_key_value_heads", "num_local_experts", "vocab_size"]
    assert cell.config["source"] == (
        "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/"
        "config.json")
    # the floors: a whole period, at least 8 experts, at least an eighth of
    # the vocabulary; and half the mixers' heads
    assert (cell.config["n_layer"], cell.config["num_local_experts"],
            cell.config["vocab_size"], cell.config["mamba_n_heads"],
            cell.config["num_attention_heads"],
            cell.config["num_key_value_heads"]) == (10, 9, 12544, 64, 16, 4)
    assert 12544 * 8 == 100352 and 9 * 8 == 72
    # no width is cut: the inner width is the published heads'
    assert cell.config["mamba_expand"] * 4096 == 128 * 64
    for key in ("assumed", "departures", "deployment", "check"):
        assert cell.config[key]
    assert set(cell.config["assumed"]) >= {
        "seeded_leaves", "time_step_limit", "gated_norm", "router",
        "attention", "optimizer"}
    assert set(cell.config["departures"]) >= {
        "optimizer_state_dtype", "weight_decay", "small_leaves", "scan",
        "documents", "row_buffer", "input_projection"}
    limits = cell.config["check"]["limits"]
    assert set(limits) == {"LOGITS_RMS_TOL", "LOSS_RTOL"}
    deployment = cell.config["deployment"]
    assert (deployment["chips_sharing_a_layer"], deployment["chip"],
            deployment["expert_rank"]) == (8, 0, 0)
    assert "no code stands in" in deployment["how"]
    assert "GiB" in deployment["step_hbm_gib"]
    assert "pipeline stages" in cell.config["reduced"]["n_layer"]
    assert "channels held" in cell.config["reduced"]["mamba_n_heads"]
    assert (cell.traffic["seq_len"], cell.traffic["per_chip_batch"],
            cell.traffic["mesh"], cell.traffic["trace_steps"],
            cell.traffic["path"], cell.chips) == (4096, 1, {}, 5,
                                                  "tfm_spmd", 1)
    assert cell.traffic["optimizer"] == {
        "name": "adamw", "learning_rate": 3e-04, "b1": 0.9, "b2": 0.95,
        "eps": 1e-08, "weight_decay": 0.1}


def test_the_limits_are_the_familys_with_their_readings(cell_and_family):
    cell, family = cell_and_family
    limits = cell.config["check"]["limits"]
    for name in ("LOGITS_RMS_TOL", "LOSS_RTOL"):
        assert f"{getattr(family, name):.4g}" in limits[name] \
            or str(getattr(family, name)) in limits[name], name
        assert "my chip run" in limits[name]
        # both readings: the sound program's, and the nearest precision
        # below, which must be out
        assert "e4m3" in limits[name] and "e5m2" in limits[name]
    assert family.within(0.5 * family.LOGITS_RMS_TOL, 10.0, 10.0) == (
        True, True)
    assert family.within(2 * family.LOGITS_RMS_TOL, 10.0,
                         10.0 * (1 + 2 * family.LOSS_RTOL)) == (False, False)


def test_the_program_is_the_configurations(cell_and_family):
    cell, family = cell_and_family
    cfg = family.transformer_config(cell.config)
    period = ("mamba2",) * 5 + ("full",) + ("mamba2",) * 4
    assert family.kinds(cell.config) == period == cfg.layer_pattern
    assert cfg.segments == () and cfg.unrotated == ()
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_ff,
            cfg.n_layers, cfg.vocab, cfg.window, cfg.max_seq) == (
        4096, 16, 4, 128, 768, 10, 12544, 0, 131072)
    assert cfg.n_heads * cfg.head_dim == 2048 != cfg.d_model
    assert (cfg.ssd_heads, cfg.ssd_head_dim, cfg.ssd_state, cfg.ssd_conv) \
        == (64, 64, 128, 4)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.experts_held,
            cfg.first_expert, cfg.shared_experts, cfg.first_k_dense) == (
        72, 10, 9, 0, 2, 0)
    assert (cfg.norm_topk, cfg.router_input, cfg.mlp, cfg.gate) == (
        True, "mlp", "swiglu", "silu")
    assert (cfg.norm, cfg.rms_norm_eps, cfg.positions, cfg.yarn,
            cfg.qk_norm, cfg.tied_head, cfg.attention_bias) == (
        "rmsnorm", 1e-5, "none", None, False, True, False)
    assert (cfg.embed_scale, cfg.residual_scale, cfg.attn_scale,
            cfg.logit_scale) == (12, 0.22, 0.0078125, 0.0625)
    assert cfg.score_scale == 1 / 128 != 128 ** -0.5
    assert (cfg.load_balance_coef, cfg.router_z_coef) == (0.0, 0.0)
    assert (cfg.attention, cfg.attn, cfg.remat, cfg.remat_policy,
            str(cfg.dtype)) == ("mha", "flash", True,
                                cell.config["program"]["remat_policy"],
                                "bfloat16")
    # a deeper cut of the same list: two periods
    assert family.pattern(dict(cell.config, n_layer=20)) == period
    assert family.first_expert(dict(
        cell.config, deployment=dict(cell.config["deployment"],
                                     expert_rank=7))) == 63
    for wrong in ({"mamba_n_groups": 8}, {"tie_word_embeddings": False},
                  {"mamba_expand": 1}, {"position_embedding_type": "rope"},
                  {"mamba_conv_bias": False}):
        with pytest.raises(ValueError, match="no equations"):
            family.transformer_config(dict(cell.config, **wrong))
    with pytest.raises(ValueError, match="constants"):
        family.transformer_config(dict(cell.config, logits_scaling=8))


def test_parameters_and_bytes_as_the_configuration_file_says(cell_and_family):
    cell, family = cell_and_family
    import jax
    from horovod_tpu.models import transformer as tfm
    cfg = family.transformer_config(cell.config)
    shapes = jax.eval_shape(lambda k: tfm.init(k, cfg), jax.random.PRNGKey(0))

    def count(tree):
        return sum(x.size for x in jax.tree_util.tree_leaves(tree))

    mixer = D * (2 * E + 2 * N) + D * H + (E + 2 * N) * 5 + 3 * H + E + E * D
    attention = 2 * D * 16 * 128 + 2 * D * 4 * 128
    ffn = 3 * D * 1536 + D * 72 + 9 * 3 * D * 768 + 2 * D
    assert (mixer, attention, ffn) == (51_668_416, 20_971_520, 104_112_128)
    assert count(shapes["layers"]["mamba2"]) == 9 * (mixer + ffn) \
        == 9 * 155_780_544
    assert count(shapes["layers"]["full"]) == attention + ffn == 125_083_648
    total = count(shapes)
    assert total == 9 * 155_780_544 + 125_083_648 + 12544 * D + D \
        == 1_578_492_864
    # bf16 weight, gradient and two Adam moments: 12.63 GB = 11.76 GiB
    assert 8 * total / 2 ** 30 == pytest.approx(11.76, abs=0.01)
    assert all(x.dtype == "bfloat16" for x in
               jax.tree_util.tree_leaves(shapes))
    mamba = shapes["layers"]["mamba2"]
    assert mamba["ssd_w_in"].shape == (1, 9, D, 2 * E + 2 * N)
    assert mamba["ssd_w_dt"].shape == (1, 9, D, H)
    assert mamba["ssd_conv"].shape == (1, 9, E + 2 * N, 4)
    assert mamba["we_gate"].shape == (1, 9, 9, D, 768)
    assert mamba["router"].shape == (1, 9, D, 72)
    assert mamba["ws1"].shape == (1, 9, D, 1536)
    assert shapes["layers"]["full"]["wq"].shape == (1, 1, D, 16, 128)
    assert shapes["layers"]["full"]["wk"].shape == (1, 1, D, 4, 128)
    assert "unembed" not in shapes and shapes["embed"].shape == (12544, D)
    # the numbers the configuration file writes out
    text = cell.config["reduced"]["n_layer"]
    for number in ("51,668,416", "155,780,544", "125,083,648",
                   "1,578,492,864 parameters x 8 bytes",
                   "32,207,337,984"):
        assert number in text, number
    # whole, by the same leaves: the catalog's 32 B
    whole = family.transformer_config(dict(
        cell.config, n_layer=40, vocab_size=100352, num_local_experts=72,
        mamba_n_heads=128, num_attention_heads=32, num_key_value_heads=8))
    assert count(jax.eval_shape(lambda k: tfm.init(k, whole),
                                jax.random.PRNGKey(0))) == 32_207_337_984
    # the row buffer of the departures: twice the even load's rows
    from horovod_tpu.parallel.moe import held_rows
    assert held_rows(4096 * 10, 9, 72) == 10240


def test_flops_per_token_by_hand(cell_and_family):
    cell, family = cell_and_family
    parts = family.forward_flops_per_token(cell.config, 4096)
    # [z | x | B | C | dt] in, E out, nine layers
    assert parts["ssd_projections"] == 9 * 2 * D * (3 * E + 2 * N + H) \
        == 929_562_624
    # the scores once a group, 128 x 256; a head the causal half of its
    # (256 x 256) matrix times 64 inputs, the state read and written
    assert family.scan_macs_per_token(cell.config) == \
        N * Q + H * P * (Q // 2 + 2 * N) == 1_605_632
    assert parts["ssd_scan"] == 9 * 2 * 1_605_632 == 28_901_376
    assert parts["projections"] == 2 * (D * 24 * 128 + 2048 * D) \
        == 41_943_040
    assert parts["attention"] == 2 * 16 * 256 * 2048.5 == 16_781_312
    assert parts["router"] == 10 * 2 * D * 72 == 5_898_240
    # ten experts a token, an eighth of them held: 1.25 experts' three
    # products a token a layer
    assert parts["experts"] == 10 * 1.25 * 3 * 2 * D * 768 == 235_929_600
    assert parts["shared"] == 10 * 3 * 2 * D * 1536 == 377_487_360
    assert parts["head"] == 2 * D * 12544 == 102_760_448
    forward = sum(parts.values())
    assert forward == 1_739_264_000
    assert family.flops_per_sample(cell.config, cell.traffic) == 3 * forward
    # the Mamba-2 mixer is 63% of a mamba layer's model FLOPs, and 56% of
    # its multiply-adds as run (ISSUE 46's reckoning: the whole (Q x Q)
    # matrix a head and the buffer's zero rows counted)
    mixer = (parts["ssd_projections"] + parts["ssd_scan"]) / 9
    layer = mixer + (parts["router"] + parts["experts"]
                     + parts["shared"]) / 10
    assert mixer / layer == pytest.approx(0.63, abs=0.01)
    as_run = 2 * (N * Q + H * P * (Q + 2 * N))
    assert (parts["ssd_projections"] / 9 + as_run) / (
        parts["ssd_projections"] / 9 + as_run + parts["router"] / 10
        + 2 * parts["experts"] / 10 + parts["shared"] / 10) == \
        pytest.approx(0.557, abs=0.005)
    assert family.samples_per_step(cell.traffic, 1) == 4096
    # the even load's rows of the 40,960 pairs; the buffer holds twice these
    assert family.grouped_matmul_shape(cell.config, cell.traffic) == (
        5120, D, 768, 9)


def test_ssd_scan_work_by_hand(cell_and_family):
    cell, family = cell_and_family
    tokens = 4096
    flops = 2 * tokens * 1_605_632
    forward_bytes = tokens * 2 * (2 * E + 2 * N) + 4 * tokens * H
    backward_bytes = tokens * 2 * (4 * E + 4 * N) + 8 * tokens * H
    assert family.scan_work(tokens, cell.config) == (
        (flops, forward_bytes), (2 * flops, backward_bytes))
    assert (flops, forward_bytes, backward_bytes) == (
        13_153_337_344, 70_254_592, 140_509_184)
    # nine layers: two forward passes each under remat, one backward
    assert family.ssd_scan_work(cell.config, cell.traffic) == (
        (18, flops, forward_bytes), (9, 2 * flops, backward_bytes))
    assert family.ssd_scan_work(
        dict(cell.config, program=dict(cell.config["program"], remat=False)),
        cell.traffic)[0][0] == 9
    # the forward is bound by its bytes (85.8 us against 66.8 of products),
    # the backward by its products (133.5 against 171.6 ... of bytes)
    from benchmark.layer_metrics.gdn_scan_roofline import least_seconds
    v5e = peaks.for_kind("TPU v5 lite")
    fwd, bound = least_seconds((1, flops, forward_bytes), v5e)
    assert bound == "memory" and fwd == pytest.approx(85.78e-6, rel=1e-3)
    bwd, bound = least_seconds((1, 2 * flops, backward_bytes), v5e)
    assert bound == "memory" and bwd == pytest.approx(171.56e-6, rel=1e-3)
    assert 2 * flops / v5e.bf16_flops == pytest.approx(133.5e-6, rel=1e-3)
    # a step's least: 18 x 85.8 + 9 x 171.6 = 3.09 ms
    assert 18 * fwd + 9 * bwd == pytest.approx(3.088e-3, rel=1e-3)


# ---------------------------------------------------------------- readers

#: A compiled step in miniature: a Mamba-2 layer's input projection, its
#: convolution, the softplus and the forward kernel under `ssd.scan` (twice
#: a step under remat), the backward kernel (its scope in the metadata),
#: the sum of dB's partial sums under `ssd.scan`, the gate, the output
#: product; beside them a grouped matmul of the experts, the shared MLP and
#: a flash kernel of the attention layer.
_IN = "jit(step)/jvp()/while/body/closed_call/checkpoint"
_BACK = "jit(step)/transpose(jvp())/while/body/closed_call/checkpoint"
HLO_TEXT = f"""
HloModule jit_step

ENTRY %main (a: bf16[8,128]) -> bf16[8,128] {{
  %a = bf16[8,128]{{1,0}} parameter(0)
  %fusion.1 = bf16[8,128]{{1,0}} fusion(%a), kind=kOutput, calls=%f1, metadata={{op_name="{_IN}/ssd.project/bsd,de->bse/dot_general"}}
  %fusion.2 = bf16[8,128]{{1,0}} fusion(%a), kind=kLoop, calls=%f2, metadata={{op_name="{_IN}/ssd.conv/jit(silu)/mul"}}
  %fusion.3 = f32[8,4]{{1,0}} fusion(%a), kind=kLoop, calls=%f3, metadata={{op_name="{_IN}/ssd.scan/jit(softplus)/log1p"}}
  %ssd.scan.4 = bf16[8,128]{{1,0}} custom-call(%x, %cols, %rows, %b, %c, /*index=5*/%d), custom_call_target="tpu_custom_call", metadata={{op_name="{_IN}/ssd.scan/jit(_ssd_scan)/jit(_forward)/pallas_call"}}
  %ssd.scan.5 = (bf16[8,128]{{1,0}}, f32[2,8,4]{{2,1,0}}, f32[8,16]{{1,0}}, f32[8,16]{{1,0}}, f32[1,128]{{1,0}}) custom-call(%x, %cols, %rows, %b, %c, /*index=5*/%d, %s0, %y, %dy), custom_call_target="tpu_custom_call", metadata={{op_name="{_BACK}/ssd.scan/jit(_ssd_scan)/jit(_backward)/pallas_call"}}
  %fusion.6 = bf16[8,16]{{1,0}} fusion(%a), kind=kLoop, calls=%f6, metadata={{op_name="{_BACK}/ssd.scan/reduce_sum"}}
  %fusion.7 = bf16[8,128]{{1,0}} fusion(%a), kind=kLoop, calls=%f7, metadata={{op_name="{_IN}/ssd.gate/rsqrt"}}
  %fusion.8 = bf16[8,128]{{1,0}} fusion(%a), kind=kOutput, calls=%f8, metadata={{op_name="{_IN}/ssd.out/bse,ed->bsd/dot_general"}}
  %moe.experts.9 = bf16[96,48]{{1,0}} custom-call(%m0, %m1, %m2, %m3, %m4, /*index=5*/%rows, %w), custom_call_target="tpu_custom_call", metadata={{op_name="{_IN}/moe.experts/pallas_call"}}
  %fusion.10 = bf16[8,128]{{1,0}} fusion(%a), kind=kOutput, calls=%f10, metadata={{op_name="{_IN}/moe.shared/dot_general"}}
  %attn.attend.11 = (bf16[4,64,16]{{2,1,0}}, f32[4,64,1]{{2,1,0}}) custom-call(%q, %k, /*index=2*/%v), custom_call_target="tpu_custom_call", metadata={{op_name="{_IN}/attn.attend/pallas_call"}}
  ROOT %fusion.12 = bf16[8,128]{{1,0}} fusion(%a), kind=kOutput, calls=%f12, metadata={{op_name="{_IN}/attn.out/dot_general"}}
}}
"""

#: per step, in microseconds: (name, start, duration); under remat the
#: forward kernel runs twice
STEP_OPS = (("fusion.1", 0, 9), ("fusion.2", 9, 3), ("fusion.3", 12, 1),
            ("ssd.scan.4", 13, 4), ("ssd.scan.4", 17, 4),
            ("ssd.scan.5", 21, 10), ("fusion.6", 31, 1), ("fusion.7", 32, 2),
            ("fusion.8", 34, 5), ("moe.experts.9", 39, 6),
            ("fusion.10", 45, 7), ("attn.attend.11", 52, 8),
            ("fusion.12", 60, 3))
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
        "ssd_scan_work": staticmethod(lambda c, t: work)}
    return fakes.fake_run(
        trace, table, program=program,
        peaks=peaks.for_kind("TPU v5 lite") if v5e else None,
        family=type("F", (), members),
        cell=type("C", (), {"config": {}, "traffic": {}, "name": "fake"}))


def test_the_three_readers_on_a_hand_made_trace(trace, table):
    run = _run(trace, table)
    # everything under `ssd.*`: 9 + 3 + 1 + 4 + 4 + 10 + 1 + 2 + 5
    assert reader("ssd_ms_per_step").read(run) == pytest.approx(39e-3)
    # under `ssd.scan`: the softplus, both kernels (the forward one twice),
    # the partial sums' sum
    assert reader("ssd_scan_ms_per_step").read(run) == pytest.approx(20e-3)
    # least: 2 x max(1e6 / 197e12, 819e3 / 819e9) + max(394e6 / 197e12, ..)
    # = 2 x 1 us + 2 us
    assert reader("ssd_scan_roofline").read(run) == pytest.approx(
        100 * 4 * US / (20 * US))
    # off the chip (no peaks): the times read, the share does not
    bare = _run(trace, table, v5e=False)
    assert reader("ssd_scan_roofline").read(bare) is None
    assert reader("ssd_scan_ms_per_step").read(bare) == pytest.approx(20e-3)
    # the expert layer's readers tell their kernel on the same trace, and
    # neither of the scan's kernels has a grouped matmul's signature
    assert reader("moe_experts_ms_per_step").read(run) == pytest.approx(6e-3)
    assert reader("moe_ms_per_step").read(run) == pytest.approx(6e-3)
    assert reader("moe_shared_ms_per_step").read(run) == pytest.approx(7e-3)
    from benchmark.harness import scopes
    assert set(scopes.grouped_kernels(table)) == {"moe.experts.9"}


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
    assert reader("ssd_scan_roofline").read(no_work) is None
    assert reader("ssd_ms_per_step").read(no_work) == pytest.approx(39e-3)


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
    assert config["reduced"] == [
        "n_layer", "num_local_experts", "vocab_size", "mamba_n_heads",
        "num_attention_heads", "num_key_value_heads"]
    assert config["file"] == "benchmark/configs/granite-4.0-h-small.json"
    assert config["source"].endswith("granite-4.0-h-small/blob/main/"
                                     "config.json")
    assert len(entry["why"]) <= 200 and len(config["why"]) <= 200
    # appended behind the cells and configurations that were there
    cells = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    assert cells.index("smallthinker-1chip") < cells.index(CELL)
    assert configs.index("smallthinker-21b-a3b") < configs.index(CONFIG)
    assert len(cells) >= 10
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    metrics = [m["name"] for m in bench["per_layer"]]
    first = metrics.index(NEW[0])
    assert metrics[first:first + 3] == list(NEW)
    assert metrics.index("full_flash_roofline") < first
    for name in NEW:
        m = _named(bench["per_layer"], name)
        assert m["workloads"] == [CELL]
        assert (m["source"], m["layer"], m["moves"]) == (
            "device_trace", "state-space dual layers",
            "samples_per_s_per_chip")
        assert (m["unit"], m["better"]) == (
            ("%", "higher") if name.endswith("_roofline") else ("ms",
                                                               "lower"))
        assert sorted(m) == ["better", "layer", "moves", "name", "source",
                             "unit", "workloads"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        listed = m.get("workloads", ())
        if m["name"] in JOINED:
            assert listed.index("smallthinker-1chip") < listed.index(CELL), \
                m["name"]
        elif m["name"] in MOE:
            assert listed[:4] == ["olmoe-1chip", "dsv2lite-1chip",
                                  "smallthinker-1chip", CELL], m["name"]
        elif m["name"] not in NEW and m["name"] != "setup_s":
            # not the dense MLP's, the flash readers', the other mixers',
            # nor the shared experts' (`moe_shared_ms_per_step`'s list is
            # held to the letter by an older test, as the four below are)
            assert CELL not in listed, m["name"]
    for name in (*HELD_TO_THE_LETTER, SHARED):
        assert CELL not in _named(bench["per_layer"], name)["workloads"]


def test_pr38s_entries_hold_what_their_pinned_test_held(bench):
    """`test_benchmark_smallthinker.py`'s test of its entries holds the four
    moe_* lists to end with its own cell, which this PR's cell, running the
    same expert layer, is appended after: it is expected to fail
    (`tests/conftest.py`), and every line it held runs here, the lists'
    first three names to the letter."""
    cell, config = "smallthinker-1chip", "smallthinker-21b-a3b"
    new = ("swa_flash_ms_per_step", "swa_flash_roofline",
           "full_flash_ms_per_step", "full_flash_roofline")
    entry = _named(bench["workloads"], cell)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        config, "spmd-dp1-s16384-smallthinker", 1)
    held = _named(bench["configs"], config)
    assert held["reduced"] == ["n_layer", "moe_num_primary_experts",
                               "vocab_size"]
    assert held["file"] == "benchmark/configs/smallthinker-21b-a3b.json"
    assert len(entry["why"]) <= 200 and len(held["why"]) <= 200
    cells = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    assert cells.index("phi4flash-1chip") < cells.index(cell)
    assert configs.index("phi-4-mini-flash") < configs.index(config)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    metrics = [m["name"] for m in bench["per_layer"]]
    first = metrics.index(new[0])
    assert metrics[first:first + 4] == list(new)
    assert metrics.index("diff_flash_roofline") < first
    for name in new:
        m = _named(bench["per_layer"], name)
        assert m["workloads"] == [cell]
        assert (m["source"], m["layer"], m["moves"]) == (
            "device_trace", "Pallas kernels", "samples_per_s_per_chip")
        assert (m["unit"], m["better"]) == (
            ("%", "higher") if name.endswith("_roofline") else ("ms",
                                                               "lower"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        listed = m.get("workloads", ())
        if m["name"] in JOINED:
            assert listed.index("phi4flash-1chip") < listed.index(cell), \
                m["name"]
        elif m["name"] in MOE:
            assert listed[:3] == ["olmoe-1chip", "dsv2lite-1chip", cell], \
                m["name"]
        elif m["name"] not in new and m["name"] != "setup_s":
            assert cell not in listed, m["name"]
    for name in HELD_TO_THE_LETTER:
        assert cell not in _named(bench["per_layer"], name)["workloads"]


def test_the_prefixes_are_the_programs_vocabulary_but_the_newest_mixers():
    """What `test_benchmark_step_scopes.py` holds of
    `harness/step_scopes.PREFIXES`, with the one prefix the harness does
    not know yet taken out: `transformer.STEP_SCOPES` lists a Mamba-2
    layer's `ssd.*` since PR 46, and the harness's file is no cell PR's to
    edit, so a partition by scope books `ssd.*` (as `ssm.*` and `gmu.*`)
    under `other`; this cell's three readers go by scope and prefix alone
    (`scope_time.names_under`) and miss nothing (PERF.md section 7)."""
    from benchmark.harness import step_scopes
    from horovod_tpu.models import transformer as tfm
    own = {scope.split(".")[0] + "." for scope in tfm.STEP_SCOPES}
    assert "ssd." in own
    assert set(step_scopes.PREFIXES) == (own - {"ssd."}) | {
        "moe.", "mla.", "gdn."}
    assert len(set(step_scopes.PREFIXES)) == len(step_scopes.PREFIXES)
    assert step_scopes.OTHER == "other" and \
        not step_scopes.OTHER.startswith(step_scopes.PREFIXES)
    assert step_scopes.scope_of(
        "jit(step)/jvp()/while/body/ssd.scan/pallas_call") is None


def test_what_the_cell_reports(bench):
    cell = spec.load_cell(CELL)
    assert [m["name"] for m in cell.end_to_end] == [
        "samples_per_s_per_chip", "step_hbm_gib", "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert names == {"init_s", "compile_s", "device_step_ms", "mfu",
                     "device_idle_share", "window_stall_share", *MOE, *NEW}
    layers = {m["name"]: m["layer"] for m in cell.per_layer}
    assert {layers[m] for m in NEW} == {"state-space dual layers"}
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
    cell = spec.load_cell("tiny-granite4h-1chip", root=TINY)
    assert cell.config["family"] == "granite_hybrid"
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
