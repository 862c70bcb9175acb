"""What PR 34 adds to the benchmark: the step's device time partitioned by the
program's scopes (`harness/step_scopes.py`) and its six readers, on a
hand-made program and trace in which the parts are known and on the two
traces recorded before the scopes; the program's host spans
(`harness/host_spans.py`) and their two readers on a hand-made host plane;
the eight entries."""

import gzip
import json
import os

import pytest
from jax.profiler import ProfileData

import benchmark_fakes as fakes
from benchmark.harness import hlo, host_spans, runner, spec, step_scopes, xplane
from horovod_tpu.models import transformer as tfm

RECORDED = os.path.join(spec.PACKAGE_DIR, "fixtures")
DEVICE = ("attn_ms_per_step", "mlp_ms_per_step", "vocab_ms_per_step",
          "opt_update_ms_per_step", "grad_reduce_ms_per_step",
          "other_ms_per_step")
HOST = ("opt_reduce_host_ms", "opt_apply_host_ms")
LM, WITH_DSV2 = ["lm-1chip", "lm-dp4"], ["lm-1chip", "lm-dp4",
                                         "dsv2lite-1chip"]
LISTS = {"attn_ms_per_step": LM, "mlp_ms_per_step": WITH_DSV2,
         "vocab_ms_per_step": WITH_DSV2, "opt_update_ms_per_step": WITH_DSV2,
         "grad_reduce_ms_per_step": ["lm-dp4"],
         "other_ms_per_step": WITH_DSV2,
         "opt_reduce_host_ms": ["resnet50-eager"],
         "opt_apply_host_ms": ["resnet50-eager"]}

#: A compiled step in miniature. Inside the layer scan a scope is a plain
#: component; outside it the transformation applied around it wraps it. The
#: flash kernels carry their scope's name, and the two the backward pass
#: makes have no metadata. `fusion.9` is the shared experts' (`_mlp` under
#: `moe.shared`; were `mlp.dense` entered inside `_mlp`, its `op_name` would
#: be this one).
STEP = "jit(step)/jvp()/while/body/closed_call/checkpoint"
BACK = "jit(step)/transpose(jvp())/while/body/closed_call/checkpoint"
HLO_TEXT = f"""
HloModule jit_step

ENTRY %main (a: bf16[8,128]) -> bf16[8,128] {{
  %a = bf16[8,128]{{1,0}} parameter(0)
  %fusion.1 = bf16[8,128]{{1,0}} fusion(%a), kind=kLoop, calls=%f1, metadata={{op_name="jit(step)/jvp(vocab.embed)/gather"}}
  %while.2 = (s32[]{{:T(128)}}, bf16[8,128]{{1,0}}) while(%t), condition=%c, body=%b, metadata={{op_name="jit(step)/jvp()/while"}}
  %fusion.3 = bf16[8,128]{{1,0}} fusion(%a), kind=kOutput, calls=%f3, metadata={{op_name="{STEP}/attn.project/bsd,dhk->bhsk/dot_general"}}
  %attn.attend.4 = (bf16[2,64,128]{{2,1,0}}, f32[2,64,1]{{2,1,0}}) custom-call(%q, %k, /*index=2*/%v), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/attn.attend/pallas_call"}}
  %attn.attend.5 = (bf16[2,64,128]{{2,1,0}}, bf16[2,64,128]{{2,1,0}}) custom-call(%q, %k, %v, %o, %do, /*index=5*/%lse), custom_call_target="tpu_custom_call"
  %attn.attend.6 = bf16[2,64,128]{{2,1,0}} custom-call(%q, %k, %v, %o, %do, /*index=5*/%lse), custom_call_target="tpu_custom_call"
  %fusion.7 = bf16[8,128]{{1,0}} fusion(%o), kind=kOutput, calls=%f7, metadata={{op_name="{BACK}/attn.out/bhsk,hkd->bsd/dot_general"}}
  %fusion.8 = bf16[8,512]{{1,0}} fusion(%x), kind=kOutput, calls=%f8, metadata={{op_name="{BACK}/rematted_computation/mlp.dense/bsd,df->bsf/dot_general"}}
  %fusion.9 = bf16[8,512]{{1,0}} fusion(%x), kind=kOutput, calls=%f9, metadata={{op_name="{STEP}/moe.shared/mlp.dense/bsd,df->bsf/dot_general"}}
  %fusion.10 = f32[8,128]{{1,0}} fusion(%x), kind=kLoop, calls=%f10, metadata={{op_name="{STEP}/reduce_sum"}}
  %copy.11 = bf16[8,128]{{1,0}} copy(%x)
  %fusion.12 = bf16[8,96]{{1,0}} fusion(%x), kind=kOutput, calls=%f12, metadata={{op_name="jit(step)/jvp(vocab.head)/bsd,dv->bsv/dot_general"}}
  %fusion.13 = f32[8,96]{{1,0}} fusion(%x), kind=kLoop, calls=%f13, metadata={{op_name="jit(step)/transpose(jvp(vocab.loss))/jit(log_softmax)/sub"}}
  %collective-permute-start.14 = (bf16[4,128]{{1,0}}, bf16[4,128]{{1,0}}) collective-permute-start(%g), metadata={{op_name="{BACK[:-11]}/grad.reduce/ppermute"}}
  %fusion.15 = bf16[8,128]{{1,0}} fusion(%g), kind=kLoop, calls=%f15, metadata={{op_name="jit(step)/grad.reduce/all_gather"}}
  %fusion.16 = bf16[8,128]{{1,0}} fusion(%g), kind=kLoop, calls=%f16, metadata={{op_name="jit(step)/opt.update/add"}}
  %other.17 = f32[4]{{0}} custom-call(%a), custom_call_target="tpu_custom_call"
  ROOT %fusion.18 = bf16[8,128]{{1,0}} fusion(%a), kind=kOutput, calls=%f18, metadata={{op_name="{STEP}/mla.attend/reshape"}}
}}
"""

#: per step, in microseconds: (name, start, duration)
STEP_OPS = (("fusion.1", 0, 2),
            ("%while.2 = (s32[]{:T(128)}, bf16[8,128]{1,0}) while(%t), "
             "condition=%c, body=%b", 2, 60),   # spans its body: not counted
            ("fusion.3", 2, 6), ("attn.attend.4", 8, 5),
            ("attn.attend.4", 13, 5), ("attn.attend.5", 18, 7),
            ("attn.attend.6", 25, 6), ("fusion.7", 31, 3), ("fusion.8", 34, 11),
            ("fusion.9", 45, 4), ("fusion.10", 49, 1), ("copy.11", 50, 2),
            ("fusion.12", 62, 8), ("fusion.13", 70, 3),
            ("collective-permute-start.14", 40, 9),   # beside fusion.8, .9
            ("fusion.15", 73, 5), ("fusion.16", 78, 12), ("other.17", 90, 1),
            ("fusion.18", 91, 4))
PARTS = {"fusion.1": "vocab.embed", "fusion.3": "attn.project",
         "attn.attend.4": "attn.attend", "attn.attend.5": "attn.attend",
         "attn.attend.6": "attn.attend", "fusion.7": "attn.out",
         "fusion.8": "mlp.dense", "fusion.9": "moe.shared",
         "fusion.10": "other", "copy.11": "other", "fusion.12": "vocab.head",
         "fusion.13": "vocab.loss",
         "collective-permute-start.14": "grad.reduce",
         "fusion.15": "grad.reduce", "fusion.16": "opt.update",
         "other.17": "other", "fusion.18": "mla.attend", "a": "other"}
MS = {"attn_ms_per_step": 6 + 5 + 5 + 7 + 6 + 3, "mlp_ms_per_step": 11,
      "vocab_ms_per_step": 2 + 8 + 3, "opt_update_ms_per_step": 12,
      "grad_reduce_ms_per_step": 9 + 5, "other_ms_per_step": 1 + 2 + 1}


def reader(name):
    return spec.load_module("layer_metrics", name, (spec.PACKAGE_DIR,))


def program(text):
    return type("P", (), {"as_text": staticmethod(lambda: text)})


@pytest.fixture(scope="module")
def run():
    rows = [(n, step * 100 + start, dur) for step in range(5)
            for n, start, dur in STEP_OPS]
    modules = [("jit_step(1)", step * 100, 99) for step in range(5)]
    trace = xplane.reduce_profile(ProfileData.from_text_proto(fakes._plane(
        1, "/device:TPU:0", [("XLA Modules", modules), ("XLA Ops", rows)])))
    return fakes.fake_run(trace, hlo.index(HLO_TEXT),
                          program=program(HLO_TEXT))


# --------------------------------------------------------------- device

def test_every_instruction_goes_to_one_part(run):
    """The outermost known scope, wrappers off; a Mosaic kernel without
    metadata to the scope whose name it carries; a loop to none."""
    assert step_scopes.partition(HLO_TEXT, run.instructions) == PARTS
    assert "while.2" in run.instructions
    assert step_scopes.scope_of(
        "jit(step)/transpose(jvp(vocab.head))/mul") == "vocab.head"
    assert step_scopes.scope_of(f"{STEP}/moe.shared/mlp.dense/dot_general") \
        == "moe.shared"
    assert step_scopes.scope_of("jit(step)/jvp()/while/body/add") is None
    assert step_scopes.scope_of("jit(optimum)/attention/add") is None


def test_the_parts_are_disjoint_and_sum_to_the_instructions_time(run):
    parts = step_scopes.traced_partition(run)
    by_part = {part: step_scopes.ms_per_step(run, part.__eq__)
               for part in set(parts.values())}
    every = step_scopes.ms_per_step(run, lambda part: True)
    counted = sum(dur for name, _, dur in STEP_OPS if " = " not in name)
    assert every == pytest.approx(counted * 1e-3)
    assert sum(by_part.values()) == pytest.approx(every)
    # beside each other on the device: the sum may pass the busy time
    busy = xplane.device_step_seconds(run.trace.devices[0])[0] * 1e3
    assert every - busy == pytest.approx(9e-3)
    # the six readers and the mixers' parts are the same partition
    mixers = by_part["moe.shared"] + by_part["mla.attend"]
    assert sum(MS.values()) * 1e-3 + mixers == pytest.approx(every)


@pytest.mark.parametrize("name", DEVICE)
def test_a_reader_on_the_hand_made_trace(run, name):
    assert reader(name).read(run) == pytest.approx(MS[name] * 1e-3)


@pytest.mark.parametrize("name", DEVICE)
@pytest.mark.parametrize("fixture", ["tiny-lm-1chip", "tiny-lm-dp4"])
def test_a_program_recorded_before_the_scopes_reads_as_nothing(fixture, name):
    """The parent's program: no part, `other` included, and nothing raised."""
    with gzip.open(os.path.join(RECORDED, fixture + ".xplane.pb.gz")) as f:
        trace = xplane.reduce_profile(
            ProfileData.from_serialized_xspace(f.read()))
    with gzip.open(os.path.join(RECORDED, fixture + ".hlo.txt.gz"), "rt") as f:
        text = f.read()
    old = fakes.fake_run(trace, hlo.index(text), program=program(text))
    assert reader(name).read(old) is None
    parts = step_scopes.partition(text, old.instructions)
    assert set(parts.values()) == {step_scopes.OTHER}


@pytest.mark.parametrize("name", DEVICE)
def test_a_run_without_a_trace_or_a_program_reads_as_nothing(run, name):
    for bare in (fakes.fake_run(None, {}, program=program(HLO_TEXT)),
                 fakes.fake_run(xplane.Trace(), {}, program=None),
                 fakes.fake_run(run.trace, run.instructions, program=None)):
        assert reader(name).read(bare) is None
    # a model with experts and no dense layer has no `mlp.dense`: that part
    # alone reads as nothing
    text = HLO_TEXT.replace("rematted_computation/mlp.dense/", "")
    no_mlp = fakes.fake_run(run.trace, hlo.index(text), program=program(text))
    expected = {"mlp_ms_per_step": None,
                "other_ms_per_step": pytest.approx((4 + 11) * 1e-3)}
    assert reader(name).read(no_mlp) == expected.get(
        name, pytest.approx(MS[name] * 1e-3))


def test_the_prefixes_are_the_programs_vocabulary_and_the_three_mixers():
    own = {scope.split(".")[0] + "." for scope in tfm.STEP_SCOPES}
    assert set(step_scopes.PREFIXES) == own | {"moe.", "mla.", "gdn."}
    assert len(set(step_scopes.PREFIXES)) == len(step_scopes.PREFIXES)
    assert step_scopes.OTHER == "other" and \
        not step_scopes.OTHER.startswith(step_scopes.PREFIXES)


# ----------------------------------------------------------------- host

#: per step, in microseconds, as `DistributedOptimizer.step` records them
#: inside the benchmark's own span
HOST_SPANS = (("bench.opt_step", 40, 33), ("hvd.opt.reduce", 41, 24),
              ("hvd.opt.apply", 66, 6), ("hvd.elsewhere", 0, 1),
              ("not.ours", 0, 100))


def host_plane_text(first_longer_by=0):
    rows = [(n, step * 100 + start, dur + (first_longer_by if step == 0
                                            else 0))
            for step in range(5) for n, start, dur in HOST_SPANS]
    return (fakes._plane(11, "/host:CPU", [("python3", rows)])
            + fakes._plane(1, "/device:TPU:0", [("XLA Ops", [
                ("hvd.opt.reduce", 0, 50)])]))   # a device plane is not read


def test_the_programs_spans_are_read_off_the_host_planes():
    spans = host_spans.reduce_profile(
        ProfileData.from_text_proto(host_plane_text()))
    assert [s.name for s in spans[:3]] == ["hvd.elsewhere", "hvd.opt.reduce",
                                           "hvd.opt.apply"]
    assert len(spans) == 15 and spans[1].dur == pytest.approx(24e-6)
    assert spans == sorted(spans, key=lambda s: s.start)


@pytest.mark.parametrize("name, ms", [("opt_reduce_host_ms", 24e-3),
                                      ("opt_apply_host_ms", 6e-3)])
def test_a_host_reader_gives_the_median_span(name, ms, tmp_path, monkeypatch):
    """From the file the traced run left under `TRACE_DIR/<cell>` of the
    checkout; None where there is no trace, no file, or no such span."""
    monkeypatch.setattr(spec, "REPO", str(tmp_path))
    cell = type("C", (), {"name": "some-cell"})
    traced = fakes.fake_run(xplane.Trace(), {}, cell=cell)
    assert reader(name).read(traced) is None               # no file
    where = tmp_path / runner.TRACE_DIR / "some-cell" / "plugins" / "profile"
    where.mkdir(parents=True)

    def leave(profile_text_proto, file="host.xplane.pb"):
        (where / file).write_bytes(profile_text_proto)

    # one long first span does not move the median
    leave(ProfileData.text_proto_to_serialized_xspace(host_plane_text(500)))
    assert reader(name).read(traced) == pytest.approx(ms)
    assert reader(name).read(fakes.fake_run(None, {}, cell=cell)) is None
    other = type("C", (), {"name": "another-cell"})
    assert reader(name).read(fakes.fake_run(xplane.Trace(), {},
                                            cell=other)) is None
    # the parent's program records no such span
    leave(ProfileData.text_proto_to_serialized_xspace(
        fakes._plane(11, "/host:CPU", [("python3", [
            ("bench.opt_step", 40, 33)])])))
    assert reader(name).read(traced) is None
    leave(b"", file="second.xplane.pb")                    # not one file
    assert reader(name).read(traced) is None


# -------------------------------------------------------------- entries

def test_the_eight_entries_stand_at_the_end_and_list_their_cells():
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = bench["per_layer"][-8:]
    assert [m["name"] for m in entries] == list(DEVICE + HOST)
    for m in entries:
        assert (m["unit"], m["better"], m["workloads"]) == (
            "ms", "lower", LISTS[m["name"]])
        if m["name"] in DEVICE:
            assert (m["source"], m["layer"], m["moves"]) == (
                "device_trace", "jitted SPMD step", "samples_per_s_per_chip")
        else:
            assert (m["source"], m["layer"], m["moves"]) == (
                "host_clock", "eager optimizer path",
                "eager_samples_per_s_per_chip")
    for cell, named in (("lm-1chip", 5), ("lm-dp4", 6), ("dsv2lite-1chip", 4),
                        ("resnet50-eager", 2), ("olmoe-1chip", 0),
                        ("olmohybrid-1chip", 0), ("resnet50-jit", 0)):
        new = [m["name"] for m in spec.load_cell(cell).per_layer
               if m["name"] in DEVICE + HOST]
        assert len(new) == named, cell
