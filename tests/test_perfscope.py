"""perfscope unit suite (ISSUE 7 tentpole).

Fake-clock tests pin the phase-attribution semantics exactly (the
switching timer, re-attribution with the sum-to-wall invariant, weight
scaling, implicit optimizer-driven steps); further tests cover the NOOP
shell + its overhead, the rolling summary/percentiles, MFU accounting,
the KV-summary plumbing, the launcher-side persistence, the doctor's
perf straggler attribution, and profiler/flops.py's peak and XLA
cost-analysis readers. The 2-process slow-input e2e lives in
tests/test_perfscope_e2e.py (`make doctor-smoke`).
"""

import json
import os
import time

import pytest

from horovod_tpu.observability import doctor
from horovod_tpu.profiler import flops as F
from horovod_tpu.profiler import perfscope


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


# ------------------------------------------------------------- flops

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
