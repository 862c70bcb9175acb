"""perfscope unit suite (ISSUE 7 tentpole).

Fake-clock tests pin the phase-attribution semantics exactly (the
switching timer, re-attribution with the sum-to-wall invariant, weight
scaling, implicit optimizer-driven steps); further tests cover the NOOP
shell + its overhead, the rolling summary/percentiles, MFU accounting,
the KV-summary plumbing, the launcher-side persistence, the doctor's
perf straggler attribution, the `scripts/perf_gate.py` checks, and the
flops.py constant dedupe. The 2-process slow-input e2e lives in
tests/test_perfscope_e2e.py (`make doctor-smoke`).
"""

import json
import os
import sys
import time

import pytest

from horovod_tpu.observability import doctor
from horovod_tpu.profiler import flops as F
from horovod_tpu.profiler import perfscope

HERE = os.path.dirname(__file__)
REPO = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(REPO, "scripts"))

import perf_gate  # noqa: E402  (scripts/perf_gate.py)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.fixture()
def fresh(monkeypatch):
    for var in (perfscope.PERFSCOPE_ENV, perfscope.PERFSCOPE_WINDOW_ENV,
                "HOROVOD_RANK", "HOROVOD_SIZE", "HOROVOD_ELASTIC_ROUND",
                "HOROVOD_BENCH_PEAK_TFLOPS"):
        monkeypatch.delenv(var, raising=False)
    perfscope.reset_for_tests()
    yield
    perfscope.reset_for_tests()


def scope(clock=None, window=None):
    return perfscope.PerfScope(window=window, clock=clock)


# ------------------------------------------------------- attribution

def test_phase_attribution_pinned(fresh):
    """The switching timer: marked phases get their window, the
    remainder lands in `dispatch`, and phases sum to the wall exactly."""
    clk = FakeClock()
    ps = scope(clock=clk)
    with ps.step():
        clk.advance(1.0)                 # dispatch
        with ps.phase("input_wait"):
            clk.advance(2.0)
        clk.advance(0.5)                 # dispatch
        with ps.phase("device_compute"):
            clk.advance(0.25)
    s = ps.summary()
    assert s["steps"] == 1
    assert s["wall"]["mean_s"] == pytest.approx(3.75)
    assert s["phases_s"]["input_wait"] == pytest.approx(2.0)
    assert s["phases_s"]["dispatch"] == pytest.approx(1.5)
    assert s["phases_s"]["device_compute"] == pytest.approx(0.25)
    assert s["coverage"] == pytest.approx(1.0)
    assert s["dominant_phase"] == "input_wait"


def test_nested_phases_restore_outer(fresh):
    clk = FakeClock()
    ps = scope(clock=clk)
    with ps.step():
        with ps.phase("comms"):
            clk.advance(1.0)
            with ps.phase("compile"):
                clk.advance(0.5)
            clk.advance(1.0)             # back in comms
    s = ps.summary()
    assert s["phases_s"]["comms"] == pytest.approx(2.0)
    assert s["phases_s"]["compile"] == pytest.approx(0.5)
    assert s["coverage"] == pytest.approx(1.0)


def test_attribute_moves_time_out_of_active_phase(fresh):
    """attribute() (the collectives/compile runtime hooks) adds to the
    target phase and subtracts from the active one — never double
    counts."""
    clk = FakeClock()
    ps = scope(clock=clk)
    with ps.step():
        clk.advance(3.0)
        ps.attribute("comms", 1.0)       # 1s of those 3 were a collective
    s = ps.summary()
    assert s["phases_s"]["comms"] == pytest.approx(1.0)
    assert s["phases_s"]["dispatch"] == pytest.approx(2.0)
    assert s["wall"]["mean_s"] == pytest.approx(3.0)
    assert s["coverage"] == pytest.approx(1.0)


def test_attribute_into_active_phase_is_noop(fresh):
    clk = FakeClock()
    ps = scope(clock=clk)
    with ps.step():
        with ps.phase("comms"):
            clk.advance(2.0)
            ps.attribute("comms", 1.5)   # optimizer wraps the hook's phase
    s = ps.summary()
    assert s["phases_s"]["comms"] == pytest.approx(2.0)
    assert s["coverage"] == pytest.approx(1.0)


def test_attributed_marker_subtracts_nested(fresh):
    """The _instrument pattern: an outer hook diffs markers so a nested
    compile attribution is not double counted as comms."""
    clk = FakeClock()
    ps = scope(clock=clk)
    with ps.step():
        m0 = ps.attributed_marker()
        clk.advance(4.0)                 # "collective dispatch window"
        ps.attribute("compile", 1.0)     # cache miss inside it
        nested = ps.attributed_marker() - m0
        ps.attribute("comms", 4.0 - nested)
    s = ps.summary()
    assert s["phases_s"]["compile"] == pytest.approx(1.0)
    assert s["phases_s"]["comms"] == pytest.approx(3.0)
    assert s["phases_s"].get("dispatch", 0.0) == pytest.approx(0.0)
    assert s["coverage"] == pytest.approx(1.0)


def test_attribute_outside_step_is_noop(fresh):
    ps = scope(clock=FakeClock())
    ps.attribute("comms", 5.0)
    assert ps.summary() == {}


def test_step_weight_scales_to_per_step(fresh):
    """bench's device-side scan: one call = `chain` steps."""
    clk = FakeClock()
    ps = scope(clock=clk)
    with ps.step(weight=10):
        clk.advance(5.0)
        with ps.phase("device_compute"):
            clk.advance(5.0)
    s = ps.summary()
    assert s["wall"]["mean_s"] == pytest.approx(1.0)
    assert s["phases_s"]["dispatch"] == pytest.approx(0.5)
    assert s["phases_s"]["device_compute"] == pytest.approx(0.5)


def test_implicit_optimizer_steps(fresh):
    """DistributedOptimizer hooks: step N = end of optimizer call N-1
    to end of call N, comms/optimizer split out."""
    clk = FakeClock()
    ps = scope(clock=clk)

    def one_training_step(fwd_bwd):
        ps.step_entry()
        clk.advance(fwd_bwd)             # user code before opt.step
        with ps.phase("comms"):
            clk.advance(0.5)
        with ps.phase("optimizer"):
            clk.advance(0.25)
        ps.step_boundary()

    one_training_step(1.0)               # first boundary opens the cycle
    one_training_step(2.0)
    one_training_step(2.0)
    s = ps.summary()
    assert s["steps"] == 3
    # steps 2 and 3 span boundary-to-boundary: 2.0 + 0.5 + 0.25
    assert s["wall"]["max_s"] == pytest.approx(2.75)
    assert s["phases_s"]["comms"] == pytest.approx(0.5)
    assert s["phases_s"]["optimizer"] == pytest.approx(0.25)
    assert s["coverage"] == pytest.approx(1.0)


def test_explicit_step_supersedes_implicit(fresh):
    clk = FakeClock()
    ps = scope(clock=clk)
    ps.step_entry()                      # implicit opened
    clk.advance(1.0)
    with ps.step():                      # explicit takes over (implicit
        clk.advance(2.0)                 # interval recorded, not lost)
        ps.step_entry()                  # optimizer inside: no-op
        ps.step_boundary()               # explicit active: no-op
        clk.advance(0.5)
    s = ps.summary()
    assert s["steps"] == 2
    assert s["wall"]["max_s"] == pytest.approx(2.5)


def test_reset_abandons_inflight_step(fresh):
    clk = FakeClock()
    ps = scope(clock=clk)
    ps.step_entry()
    clk.advance(100.0)                   # stale implicit step
    ps.reset()
    with ps.step():
        clk.advance(1.0)
    s = ps.summary()
    assert s["steps"] == 1
    assert s["wall"]["max_s"] == pytest.approx(1.0)


# ------------------------------------------------------------ summary

def test_summary_percentiles(fresh):
    clk = FakeClock()
    ps = scope(clock=clk)
    for dt in [0.1] * 10 + [0.2] * 9 + [1.0]:
        with ps.step():
            clk.advance(dt)
    s = ps.summary()
    assert s["steps"] == 20
    assert s["wall"]["p50_s"] == pytest.approx(0.2)
    assert s["wall"]["p95_s"] == pytest.approx(1.0)
    assert s["wall"]["max_s"] == pytest.approx(1.0)
    assert s["wall"]["mean_s"] == pytest.approx(
        (0.1 * 10 + 0.2 * 9 + 1.0) / 20)


def test_summary_window_bounded(fresh):
    clk = FakeClock()
    ps = scope(clock=clk, window=16)
    for _ in range(100):
        with ps.step():
            clk.advance(0.1)
    s = ps.summary()
    assert s["steps"] == 100
    assert s["window_steps"] == 16


def test_mfu_from_model_flops(fresh, monkeypatch):
    monkeypatch.setenv("HOROVOD_BENCH_PEAK_TFLOPS", "100")  # 1e14 FLOP/s
    clk = FakeClock()
    ps = scope(clock=clk)
    ps.set_model_flops(5e13, "xla")      # 0.5s of peak work
    with ps.step():
        clk.advance(1.0)
    s = ps.summary()
    assert s["mfu"] == pytest.approx(0.5)
    assert s["mfu_source"] == "xla"
    assert s["model_flops_per_step"] == pytest.approx(5e13)


def test_dominant_local_phase_excludes_waits(fresh):
    clk = FakeClock()
    ps = scope(clock=clk)
    with ps.step():
        with ps.phase("input_wait"):
            clk.advance(0.4)
        with ps.phase("comms"):
            clk.advance(3.0)             # waiting on a slow peer
    s = ps.summary()
    assert s["dominant_phase"] == "comms"
    assert s["dominant_local_phase"] == "input_wait"
    assert s["local_mean_s"] == pytest.approx(0.4)


# ------------------------------------------------------- NOOP + env

def test_disabled_env_returns_noop(fresh, monkeypatch):
    monkeypatch.setenv(perfscope.PERFSCOPE_ENV, "0")
    perfscope.reset_for_tests()
    ps = perfscope.get()
    assert ps is perfscope.NOOP
    with ps.step():
        with ps.phase("input_wait"):
            pass
    ps.attribute("comms", 1.0)
    assert ps.summary() == {}
    assert ps.kv_payload() is None
    assert not ps.push_summary()
    prof = ps.step_profile("x")
    assert prof["name"] == "x"


def test_default_enabled_singleton(fresh):
    assert isinstance(perfscope.get(), perfscope.PerfScope)
    assert perfscope.get() is perfscope.get()


def test_noop_shell_overhead(fresh, monkeypatch):
    """The disabled shell must be cheap enough for per-step use: 10k
    step+phase+attribute rounds in well under a second."""
    monkeypatch.setenv(perfscope.PERFSCOPE_ENV, "0")
    perfscope.reset_for_tests()
    ps = perfscope.get()
    t0 = time.perf_counter()
    for _ in range(10000):
        with ps.step():
            with ps.phase("input_wait"):
                pass
            ps.attribute("comms", 0.001)
    assert time.perf_counter() - t0 < 1.0


def test_enabled_hot_path_overhead(fresh):
    """The live scope's per-step cost stays micro: 5k full step/phase
    rounds in under 2s (they are a handful of perf_counter calls)."""
    ps = scope()
    t0 = time.perf_counter()
    for _ in range(5000):
        with ps.step():
            with ps.phase("input_wait"):
                pass
            ps.attribute("comms", 1e-6)
    assert time.perf_counter() - t0 < 2.0


# ----------------------------------------------------------- KV push

def test_kv_payload_and_rank_gate(fresh, monkeypatch):
    clk = FakeClock()
    ps = scope(clock=clk)
    with ps.step():
        clk.advance(0.5)
    assert ps.kv_payload() is None       # no rank resolvable: unkeyable
    monkeypatch.setenv("HOROVOD_RANK", "3")
    monkeypatch.setenv("HOROVOD_ELASTIC_ROUND", "2")
    body = ps.kv_payload()
    assert body["rank"] == 3 and body["round"] == 2
    assert body["perfscope"] == perfscope.SUMMARY_VERSION
    assert body["summary"]["wall"]["mean_s"] == pytest.approx(0.5)


def test_push_summary_uses_rank_round_key(fresh, monkeypatch):
    monkeypatch.setenv("HOROVOD_RANK", "1")
    monkeypatch.setenv("HOROVOD_ELASTIC_ROUND", "4")
    clk = FakeClock()
    ps = scope(clock=clk)
    with ps.step():
        clk.advance(0.25)
    puts = []

    class FakeKV:
        def put(self, scope_, key, value):
            puts.append((scope_, key, json.loads(value.decode())))

    ps._kv = FakeKV()
    assert ps.push_summary()
    (sc, key, body), = puts
    assert sc == perfscope.SCOPE
    assert key == "rank-1.r4"
    assert body["summary"]["steps"] == 1


def test_persist_kv_summaries(fresh, tmp_path):
    class Store:
        def scope_items(self, scope_):
            assert scope_ == perfscope.SCOPE
            return {"rank-0.r1": json.dumps(
                        {"perfscope": 1, "rank": 0, "round": 1,
                         "summary": {"steps": 2}}).encode(),
                    "rank-1.r1": json.dumps(
                        {"perfscope": 1, "rank": 1, "round": 1,
                         "summary": {"steps": 2}}).encode()}

    out = tmp_path / "flight"
    written = perfscope.persist_kv_summaries(Store(), str(out))
    assert sorted(os.path.basename(p) for p in written) == \
        ["perf-rank-0.r1.json", "perf-rank-1.r1.json"]
    body = json.load(open(written[0]))
    assert body["rank"] == 0


def test_persist_kv_summaries_noop_without_dir(fresh):
    class Store:
        def scope_items(self, scope_):  # pragma: no cover - not reached
            raise AssertionError

    assert perfscope.persist_kv_summaries(Store(), "") == []


# ------------------------------------------------------------ doctor

def _summary(rank, round_, phases, steps=20):
    wall = sum(phases.values())
    wait = sum(v for k, v in phases.items()
               if k in perfscope.WAIT_PHASES)
    local = {k: v for k, v in phases.items()
             if k not in perfscope.WAIT_PHASES}
    dom = max(phases, key=phases.get)
    return {
        "perfscope": 1, "rank": rank, "round": round_,
        "hostname": f"h{rank}", "pid": 1000 + rank,
        "summary": {
            "steps": steps, "window_steps": steps,
            "wall": {"mean_s": wall, "p50_s": wall, "p95_s": wall,
                     "max_s": wall},
            "phases_s": phases,
            "phase_fractions": {k: v / wall for k, v in phases.items()},
            "coverage": 1.0,
            "local_mean_s": wall - wait,
            "dominant_phase": dom,
            "dominant_local_phase": max(local, key=local.get),
            "model_flops_per_step": None, "mfu_source": "none",
        },
    }


def test_doctor_perf_straggler_named_with_dominant_phase(fresh):
    """The ISSUE 7 acceptance shape: the slow-input rank comes out by
    name with `input_wait` as its dominant phase, even though every
    rank's WALL time is identical (the fast ranks park the difference
    in comms)."""
    slow = _summary(0, 1, {"input_wait": 0.40, "dispatch": 0.05,
                           "comms": 0.02})
    fast = _summary(1, 1, {"input_wait": 0.01, "dispatch": 0.05,
                           "comms": 0.41})
    perf = doctor.analyze_perf([slow, fast])
    assert len(perf["stragglers"]) == 1
    s = perf["stragglers"][0]
    assert s["rank"] == 0 and s["round"] == 1
    assert s["dominant_phase"] == "input_wait"
    assert s["slowdown_vs_median"] > 2.0
    report = doctor.merge([], perf=[slow, fast])
    text = doctor.render(report)
    assert "PERF STRAGGLER rank 0" in text, text
    assert "input_wait" in text, text


def test_doctor_perf_no_straggler_when_balanced(fresh):
    a = _summary(0, 0, {"dispatch": 0.1, "comms": 0.02})
    b = _summary(1, 0, {"dispatch": 0.105, "comms": 0.02})
    perf = doctor.analyze_perf([a, b])
    assert perf["stragglers"] == []
    text = doctor.render(doctor.merge([], perf=[a, b]))
    assert "no perf straggler" in text


def test_doctor_dedupe_perf_keeps_most_steps(fresh):
    old = _summary(0, 1, {"dispatch": 0.1}, steps=5)
    new = _summary(0, 1, {"dispatch": 0.1}, steps=50)
    kept = doctor.dedupe_perf([old, new])
    assert len(kept) == 1 and kept[0]["summary"]["steps"] == 50


def test_doctor_load_perf_dir_and_main_json(fresh, tmp_path, capsys):
    d = tmp_path / "flight"
    d.mkdir()
    slow = _summary(0, 1, {"input_wait": 0.4, "comms": 0.02})
    fast = _summary(1, 1, {"input_wait": 0.01, "comms": 0.41})
    (d / "perf-rank-0.r1.json").write_text(json.dumps(slow))
    (d / "perf-rank-1.r1.json").write_text(json.dumps(fast))
    (d / "perf-bad.json").write_text("not json")
    (d / "unrelated.json").write_text(json.dumps({"events": []}))
    loaded = doctor.load_perf_dir(str(d))
    assert len(loaded) == 2
    rc = doctor.main(["--dir", str(d), "--json"])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["perf"]["stragglers"][0]["rank"] == 0
    assert report["perf"]["stragglers"][0]["dominant_phase"] == \
        "input_wait"


# ---------------------------------------------------------- perf_gate

def _gate_profile(**over):
    prof = {
        "name": "sec", "perfscope": 1, "steps": 8, "window_steps": 8,
        "wall": {"mean_s": 0.01, "p50_s": 0.01, "p95_s": 0.012,
                 "max_s": 0.02},
        "phases_s": {"dispatch": 0.008, "device_compute": 0.002},
        "coverage": 1.0, "mfu_source": "xla",
    }
    prof.update(over)
    return prof


def test_perf_gate_structure_pass_and_failures(fresh):
    base = {"sections": {"sec": {
        "require_phases": ["dispatch", "device_compute"],
        "mfu_source": ["xla", "fallback"],
        "wall_mean_s": 0.01, "tolerance": 1.0}}}
    cur = {"sections": {"sec": _gate_profile()}}
    assert perf_gate.compare(cur, base, numeric=False) == []
    # missing section
    assert perf_gate.compare({"sections": {}}, base, numeric=False)
    # broken coverage
    bad = {"sections": {"sec": _gate_profile(coverage=0.4)}}
    errs = perf_gate.compare(bad, base, numeric=False)
    assert any("coverage" in e for e in errs)
    # missing required phase
    bad = {"sections": {"sec": _gate_profile(
        phases_s={"dispatch": 0.01})}}
    assert any("device_compute" in e
               for e in perf_gate.compare(bad, base, numeric=False))
    # bad mfu_source
    bad = {"sections": {"sec": _gate_profile(mfu_source="vibes")}}
    assert any("mfu_source" in e
               for e in perf_gate.compare(bad, base, numeric=False))


def test_perf_gate_numeric_tolerance(fresh):
    base = {"sections": {"sec": {"wall_mean_s": 0.01, "tolerance": 0.5}}}
    ok = {"sections": {"sec": _gate_profile(
        wall={"mean_s": 0.012, "p50_s": 0.012, "p95_s": 0.012,
              "max_s": 0.012})}}
    assert perf_gate.compare(ok, base, numeric=True) == []
    slow = {"sections": {"sec": _gate_profile(
        wall={"mean_s": 0.10, "p50_s": 0.1, "p95_s": 0.1,
              "max_s": 0.1})}}
    errs = perf_gate.compare(slow, base, numeric=True)
    assert any("outside" in e for e in errs)
    # numeric off: the same regression passes structure-only
    assert perf_gate.compare(slow, base, numeric=False) == []


def test_perf_gate_baseline_from_roundtrip(fresh):
    cur = {"platform": "cpu", "sections": {"sec": _gate_profile()}}
    base = perf_gate.baseline_from(cur)
    assert perf_gate.compare(cur, base, numeric=True) == []
    assert base["sections"]["sec"]["require_phases"] == \
        ["device_compute", "dispatch"]


def test_perf_gate_checked_in_baseline_is_valid(fresh):
    """The committed baseline must parse and demand the committed
    emitter's sections (guards against baseline/emitter drift)."""
    path = os.path.join(REPO, "scripts", "perf_baseline.json")
    base = json.load(open(path))
    assert base["perf_gate"] == 1
    assert set(base["sections"]) == {"eager_mlp", "scan_matmul"}
    for spec in base["sections"].values():
        assert spec["require_phases"]


def _conv_stamps(mode="nhwc_padded"):
    """The conv-fast-path stamps bench sections carry (docs/perf.md)."""
    return {"layout": {"mode": mode},
            "input_pipeline": {"mode": "device_double_buffered",
                               "depth": 2},
            **_memory_stamp()}


def _memory_stamp(static=64 << 20):
    """The per-section static peak-HBM stamp (ISSUE 13): required
    whenever the section's XLA cost analysis ran (mfu_source=xla)."""
    return {"memory": {"static_peak_device_bytes": static}}


def _ckpt_section(overhead=0.01):
    """A minimal valid checkpointing section (ISSUE 15): check_bench
    requires its PRESENCE with the overhead/phase-split stamps."""
    return {"checkpointing": {
        "overhead_fraction": overhead, "snapshot_ms": 1.0,
        "persist_ms": 5.0, "plain_step_ms": 10.0,
        "ckpt_step_ms": 10.1, "bytes": 2 << 20,
        "generations_committed": 6, "save_every": 4,
        "skipped_saves": 0,
    }}


def _serving_section():
    """A minimal valid serving section (ISSUE 20): check_bench
    requires its PRESENCE with the hvdtrace `trace` stamp carrying the
    slowest request's queue/dispatch/device split."""
    return {"serving": {
        "requests": 100, "requests_per_sec": 50.0,
        "trace": {"version": 1, "sampled": 100, "finished": 100,
                  "requests_joined": 8, "complete": 8,
                  "slowest": {"trace_id": "ab" * 8, "rid": 7,
                              "total_ms": 12.0, "queue_ms": 3.0,
                              "dispatch_ms": 8.5, "device_ms": 4.0}},
    }}


def _gspmd_section():
    """A minimal valid sharded section (ISSUE 14) plus the ISSUE 15
    checkpointing and ISSUE 20 serving sections: check_bench requires
    the PRESENCE of all three with their stamps, so the synthetic docs
    below carry them to isolate what each test actually checks."""
    return {"gspmd_hybrid": {
        "mesh": {"spec": "dp=2,tp=4", "devices": 8,
                 "shape": {"dp": 2, "tp": 4}},
        "scaling": {"efficiency_vs_dp": 1.0,
                    "dp_tokens_per_sec": 1.0,
                    "hybrid_tokens_per_sec": 1.0},
        "comms_by_axis": {"dp": {"bytes_per_step": 1}},
        "comms_model": {
            "link_gbps": {"ici": 90.0, "dcn": 12.5},
            "per_axis": {"dp": {"bytes_per_step": 1,
                                "wire_bytes_per_step": 1,
                                "predicted_s": 1e-9, "ops": 1,
                                "tier": "ici"}},
            "predicted_vs_measured": 1.0,
        },
        "numerics": {
            "accum_dtypes": ["f32"],
            "grad_scale": [{"opcode": "all_reduce", "dtype": "f32",
                            "group_size": 2, "bytes": 1,
                            "divisor": None, "multiplier": 2.0,
                            "axis": "dp"}],
            "findings": 0, "clean": True,
        },
    }, **_ckpt_section(), **_serving_section()}


def test_perf_gate_bench_mode(fresh):
    doc = {"extra": {"resnet50": {"perfscope": _gate_profile(),
                                  **_conv_stamps()},
                     "vgg16": None, "autotune": {"frozen": True},
                     **_gspmd_section()}}
    assert perf_gate.check_bench(doc) == []
    assert perf_gate.check_bench({"extra": {}})  # nothing stamped


def test_perf_gate_conv_section_requires_stamps(fresh):
    """ISSUE 12 satellite: a conv section without the layout /
    input_pipeline stamps fails the gate STRUCTURALLY."""
    doc = {"extra": {"resnet50": {"perfscope": _gate_profile()}}}
    errs = perf_gate.check_bench(doc)
    assert any("layout stamp missing" in e for e in errs)
    assert any("input_pipeline" in e for e in errs)
    # ...and without a memory stamp (ISSUE 13): also structural
    assert any("memory stamp missing" in e for e in errs)
    # non-conv sections carry the memory obligation but no conv stamps
    doc = {"extra": {"transformer_lm": {"perfscope": _gate_profile(),
                                        **_memory_stamp()},
                     **_gspmd_section()}}
    assert perf_gate.check_bench(doc) == []


def test_perf_gate_conv_section_unpadded_resnet_fails(fresh):
    """A ResNet section measured under the as-declared (unpadded)
    layout is a structural regression; inception may legitimately run
    as-declared (no conv_stack declaration yet)."""
    doc = {"extra": {"resnet50": {"perfscope": _gate_profile(),
                                  **_conv_stamps("as_declared")}}}
    errs = perf_gate.check_bench(doc)
    assert any("nhwc_padded" in e for e in errs)
    doc = {"extra": {"inception_v3": {"perfscope": _gate_profile(),
                                      **_conv_stamps("as_declared")},
                     **_gspmd_section()}}
    assert perf_gate.check_bench(doc) == []


def test_perf_gate_conv_section_input_wait_bar(fresh):
    """Measured input_wait above 5% of the step wall fails — the
    device-resident pipeline acceptance (docs/perf.md)."""
    prof = _gate_profile()
    prof["phase_fractions"] = {"input_wait": 0.2}
    doc = {"extra": {"resnet50": {"perfscope": prof, **_conv_stamps()},
                     **_gspmd_section()}}
    errs = perf_gate.check_bench(doc)
    assert any("starving" in e for e in errs)
    prof["phase_fractions"] = {"input_wait": 0.01}
    assert perf_gate.check_bench(doc) == []


def test_perf_gate_ckpt_section_overhead_and_stamps(fresh):
    """ISSUE 15 satellite: the checkpointing section is structurally
    required, its stamps must be present, and measured overhead above
    the 5% budget fails the gate on ANY host."""
    base = {"transformer_lm": {"perfscope": _gate_profile(),
                               **_memory_stamp()}}
    doc = {"extra": {**base, **_gspmd_section()}}
    assert perf_gate.check_bench(doc) == []
    # overhead above budget: numeric fail everywhere
    doc["extra"]["checkpointing"]["overhead_fraction"] = 0.09
    errs = perf_gate.check_bench(doc)
    assert any("overhead" in e and "5%" in e for e in errs)
    # a missing phase-split stamp: structural fail
    doc["extra"].update(_ckpt_section())
    del doc["extra"]["checkpointing"]["snapshot_ms"]
    errs = perf_gate.check_bench(doc)
    assert any("snapshot_ms" in e for e in errs)
    # zero commits: the save path never reached a marker
    doc["extra"].update(_ckpt_section())
    doc["extra"]["checkpointing"]["generations_committed"] = 0
    assert any("commit" in e for e in perf_gate.check_bench(doc))
    # absent section: fail, not skip
    doc["extra"].pop("checkpointing")
    errs = perf_gate.check_bench(doc)
    assert any("checkpointing" in e and "missing" in e for e in errs)


def test_perf_gate_conv_section_mfu_presence(fresh):
    """With a known chip peak the StepProfile must carry an actual
    `mfu` number (the conv-MFU acceptance metric); without a peak
    (CPU hosts) its absence is fine."""
    prof = _gate_profile()
    prof["peak_flops_per_chip"] = 197e12
    doc = {"extra": {"vgg16": {"perfscope": prof, **_conv_stamps()},
                     **_gspmd_section()}}
    errs = perf_gate.check_bench(doc)
    assert any("mfu missing" in e for e in errs)
    prof["mfu"] = 0.41
    assert perf_gate.check_bench(doc) == []


# ------------------------------------------------------------- flops

def test_flops_fallbacks_match_legacy_constants(fresh):
    """The dedupe satellite: the constants bench/scripts used inline
    must survive the move byte-for-byte (MAC convention)."""
    assert F.resnet_train_flops_per_image(50, "macs") == \
        pytest.approx(12.3e9)
    assert F.resnet_train_flops_per_image(101, "macs") == \
        pytest.approx(23.4e9)
    assert F.inception_v3_train_flops_per_image("macs") == \
        pytest.approx(17.2e9, rel=1e-3)
    assert F.vgg16_train_flops_per_image("macs") == \
        pytest.approx(46.5e9, rel=2e-3)
    assert F.PEAK_TFLOPS["TPU v5 lite"] == 197.0
    # the mul+add convention is exactly 2x (XLA comparability)
    assert F.resnet_train_flops_per_image(50, "flops") == \
        pytest.approx(2 * 12.3e9)
    with pytest.raises(ValueError):
        F.resnet_train_flops_per_image(50, "bogus")


def test_flops_transformer_formula_matches_legacy_inline(fresh):
    """The exact expression bench.py used to inline for the TPU LM
    config (L12 D2048 F8192 V32768 S1024)."""
    D, Fd, L, V, S = 2048, 8192, 12, 32768, 1024
    n_matmul = L * (4 * D * D + 2 * D * Fd)
    legacy = 6 * n_matmul + 6 * L * S * D + 6 * D * V
    assert F.transformer_train_flops_per_token(D, Fd, L, V, S) == legacy
    assert F.transformer_matmul_params(D, Fd, L, V) == \
        n_matmul + 2 * D * V


def test_flops_peak_env_override(fresh, monkeypatch):
    monkeypatch.setenv("HOROVOD_BENCH_PEAK_TFLOPS", "123")
    assert F.peak_flops_per_chip("anything") == pytest.approx(123e12)
    monkeypatch.delenv("HOROVOD_BENCH_PEAK_TFLOPS")
    assert F.peak_flops_per_chip("TPU v5 lite") == pytest.approx(197e12)
    assert F.peak_flops_per_chip("Unknown Chip") is None
    assert F.peak_flops_per_chip("cpu") is None
    # a TPU the table does not know is an error where an MFU is computed
    from horovod_tpu.common.exceptions import HorovodTpuError
    with pytest.raises(HorovodTpuError, match="TPU v99"):
        F.peak_flops_per_chip("TPU v99")
    # garbage must fail LOUDLY: a silent spec-table fallback would skew
    # every MFU in exactly the runs that set the override
    monkeypatch.setenv("HOROVOD_BENCH_PEAK_TFLOPS", "157,0")
    with pytest.raises(ValueError):
        F.peak_flops_per_chip("TPU v5 lite")


def test_flops_pick(fresh):
    assert F.pick_flops(10.0, 5.0) == (10.0, "xla")
    assert F.pick_flops(None, 5.0) == (5.0, "fallback")
    assert F.pick_flops(None, None) == (None, "none")


def test_flops_xla_cost_on_cpu(fresh):
    """cost_analysis works on the CPU backend — the primary source is
    live even in tier-1 (a 64^3 matmul is ~2*64^3 flops)."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda a: a @ a)
    x = jnp.ones((64, 64), jnp.float32)
    got = F.jit_cost_flops(fn, x)
    if got is None:
        pytest.skip("this CPU backend exposes no cost model")
    assert got >= 2 * 64 ** 3 * 0.9


# ----------------------------------------------- optimizer auto-hook

def test_distributed_optimizer_records_implicit_steps(fresh, hvd):
    """The auto-hook: a plain Horovod-style loop (no explicit step
    marks) still yields per-step records with comms/optimizer split."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd_mod

    perfscope.reset_for_tests()
    ps = perfscope.get()
    ps.reset()
    k = hvd.size()
    rng = np.random.RandomState(0)
    grads = {"w": jnp.asarray(rng.randn(k, 4, 3).astype(np.float32))}
    params = {"w": jnp.zeros((4, 3))}
    opt = hvd_mod.DistributedOptimizer(optax.sgd(0.1))
    state = opt.init(params)
    for _ in range(3):
        params, state = opt.step(grads, params, state)
    s = ps.summary()
    # first call only OPENS the implicit cycle; 2 full boundary-to-
    # boundary steps follow
    assert s["steps"] >= 2
    assert "optimizer" in s["phases_s"]
    assert "comms" in s["phases_s"]
    assert s["coverage"] >= 0.9


def test_accumulation_microbatches_not_counted_as_steps(fresh, hvd):
    """backward_passes_per_step > 1: accumulation-only calls are
    micro-batches — the implicit step must close only when the
    collective fires, so one record spans the whole cycle."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd_mod

    perfscope.reset_for_tests()
    ps = perfscope.get()
    ps.reset()
    k = hvd.size()
    rng = np.random.RandomState(0)
    grads = {"w": jnp.asarray(rng.randn(k, 4, 3).astype(np.float32))}
    params = {"w": jnp.zeros((4, 3))}
    opt = hvd_mod.DistributedOptimizer(optax.sgd(0.1),
                                       backward_passes_per_step=2)
    state = opt.init(params)
    for _ in range(4):                   # 4 calls = 2 real steps
        params, state = opt.step(grads, params, state)
    s = ps.summary()
    assert s["steps"] == 2, s
    # every recorded step contains the fired collective + apply
    assert "comms" in s["phases_s"] and "optimizer" in s["phases_s"]


# ------------------------- flops cost_analysis() shape handling
# (ISSUE 8 satellite: both shapes jax has shipped, pinned by fixture)

class _FakeCompiled:
    """Stands in for jit(f).lower(...).compile(): only cost_analysis()
    is consulted by compiled_cost_flops."""

    def __init__(self, ca):
        self._ca = ca

    def cost_analysis(self):
        if isinstance(self._ca, Exception):
            raise self._ca
        return self._ca


def test_flops_cost_analysis_dict_form(fresh):
    """Newer JAX: cost_analysis() returns ONE dict."""
    assert F.compiled_cost_flops(_FakeCompiled({"flops": 123.0})) == 123.0
    # missing / zero / garbage flops entries all mean "no cost model"
    assert F.compiled_cost_flops(_FakeCompiled({})) is None
    assert F.compiled_cost_flops(_FakeCompiled({"flops": 0.0})) is None
    assert F.compiled_cost_flops(_FakeCompiled({"flops": "n/a"})) is None


def test_flops_cost_analysis_per_device_list_form(fresh):
    """Older JAX: cost_analysis() returns a per-device list of dicts;
    under SPMD the module is per-device code, so any populated entry
    describes the program."""
    assert F.compiled_cost_flops(
        _FakeCompiled([{"flops": 7.0}, {"flops": 7.0}])) == 7.0
    # device 0's dict can be empty on some backends: later entries count
    assert F.compiled_cost_flops(
        _FakeCompiled([{}, {"flops": 9.0}])) == 9.0
    # -1 / non-numeric placeholders must not shadow a populated entry
    assert F.compiled_cost_flops(
        _FakeCompiled([{"flops": -1}, {"flops": 9.0}])) == 9.0
    assert F.compiled_cost_flops(
        _FakeCompiled([{"flops": "n/a"}, {"flops": 9.0}])) == 9.0
    assert F.compiled_cost_flops(_FakeCompiled([])) is None
    assert F.compiled_cost_flops(_FakeCompiled(["bogus"])) is None
    assert F.compiled_cost_flops(_FakeCompiled((({"flops": 5.0},)))) == 5.0


def test_flops_cost_analysis_failure_paths(fresh):
    assert F.compiled_cost_flops(
        _FakeCompiled(RuntimeError("no cost model"))) is None
    assert F.compiled_cost_flops(_FakeCompiled("not a dict")) is None


# ------------------------- perf_gate --update refusal (ISSUE 8
# satellite: a broken run must not silently become the new baseline)

def test_perf_gate_update_errors_refuse_broken_runs(fresh):
    good = {"sections": {"sec": _gate_profile()}}
    assert perf_gate.update_errors(good) == []
    low_cov = {"sections": {"sec": _gate_profile(coverage=0.5)}}
    assert any("coverage" in e
               for e in perf_gate.update_errors(low_cov))
    fb = {"sections": {"sec": _gate_profile(mfu_source="fallback")}}
    assert any("fallback" in e for e in perf_gate.update_errors(fb))
    assert perf_gate.update_errors({"sections": {}})  # nothing profiled


def test_perf_gate_update_cli_refuses_and_preserves_baseline(
        fresh, tmp_path):
    cur = tmp_path / "cur.json"
    cur.write_text(json.dumps(
        {"platform": "cpu",
         "sections": {"sec": _gate_profile(mfu_source="fallback")}}))
    base = tmp_path / "base.json"
    base.write_text("{\"sentinel\": true}")
    rc = perf_gate.main([str(cur), "--baseline", str(base), "--update"])
    assert rc == 1
    # the refusal must not have touched the existing baseline
    assert json.loads(base.read_text()) == {"sentinel": True}


def test_perf_gate_update_cli_accepts_healthy_run(fresh, tmp_path):
    cur = tmp_path / "cur.json"
    cur.write_text(json.dumps(
        {"platform": "cpu", "sections": {"sec": _gate_profile()}}))
    base = tmp_path / "base.json"
    rc = perf_gate.main([str(cur), "--baseline", str(base), "--update"])
    assert rc == 0
    doc = json.loads(base.read_text())
    assert "sec" in doc["sections"]
