"""perfboard: round loader pins against small SYNTHETIC rounds — one
per format the loader still parses, written into a temp directory —
trajectory integrity (a hand-edited round breaks CI loudly), the
Detector-over-rounds diff engine, attribution, and the gate run both
ways: a clean trajectory passes, a synthetically regressed fixture
round fails naming the section AND the dominant moved phase. (The
repository keeps no round files of its own: the driver's record is
PERF_LEDGER.jsonl. Every number below is made up.)
"""

import copy
import glob
import json
import os

import pytest

from horovod_tpu.observability import perfboard as pb

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CMD = "python bench.py"
_HEADLINE = {"metric": "resnet50_synthetic_images_per_sec_per_chip",
             "value": 1000.0, "unit": "images/sec/chip",
             "vs_baseline": 10.0}


def _meta(**over):
    meta = {"meta_version": pb.META_VERSION, "git_sha": "0" * 40,
            "date_utc": "2026-01-01T00:00:00Z", "hostname": "synthetic",
            "python": "3.12.0", "device_platform": "cpu",
            "device_kind": "cpu", "num_devices": 8, "knobs": {},
            "uncataloged_knobs": None}
    meta.update(over)
    meta["fingerprint"] = pb.meta_fingerprint(meta)
    return meta


def _section(step_ms, **extra):
    """A bench section with the perfscope stamp attribution reads."""
    return dict({
        "step_ms": step_ms,
        "perfscope": {"wall": {"mean_s": step_ms / 1e3},
                      "phases_s": {"fprop": step_ms / 4e3,
                                   "bprop": step_ms / 2e3,
                                   "allreduce": step_ms / 4e3}}}, **extra)


def _bench_docs():
    """n -> driver artifact {n, cmd, rc, tail, parsed}, one per format:
    headline / failed / tail-json / partial (device scalar survives) /
    partial (platform from window_tflops stamps only) / full+meta."""
    full3 = dict(_HEADLINE, extra={
        "device": "TPU v5 lite", "num_chips": 1,
        "resnet50": {"step_ms": 40.0, "mfu": 0.25},
        "transformer_lm": {"step_ms": 400.0, "mfu": 0.5},
        "section_errors": None})
    # Head-truncated stdout tails: the JSON line's head scrolled out of
    # the captured window, every complete section after the cut did not.
    tail4 = ('alth": {"matmul_tflops": 1.0}, "device": "TPU v5 lite", '
             '"num_chips": 1, "resnet50": {"step_ms": 40.0, "mfu": 0.26, '
             '"window_tflops": 1.0}, "vgg16": {"step_ms": 80.0, '
             '"mfu": 0.3, "window_tflops": 1.0}, "section_errors": null}}\n')
    tail5 = ('step_ms": 40.0}, "vgg16": {"step_ms": 80.0, "mfu": 0.35, '
             '"window_tflops": 1.0}, "transformer_lm": {"step_ms": 400.0, '
             '"mfu": 0.7, "window_tflops": 1.0}, "section_errors": null}}\n')
    full6 = dict(_HEADLINE, meta=_meta(), extra={
        "device": "cpu", "num_chips": 8,
        "resnet50": _section(100.0, images_per_sec_per_chip=80.0),
        "transformer_lm": _section(50.0),
        "section_errors": None})
    return {
        1: {"rc": 0, "tail": json.dumps(_HEADLINE) + "\n",
            "parsed": _HEADLINE},
        2: {"rc": 1, "parsed": None,
            "tail": "Traceback (most recent call last):\n"
                    "RuntimeError: synthetic failure\n"},
        3: {"rc": 0, "tail": json.dumps(full3) + "\n", "parsed": full3},
        4: {"rc": 0, "tail": tail4, "parsed": None},
        5: {"rc": 0, "tail": tail5, "parsed": None},
        6: {"rc": 0, "tail": "", "parsed": full6},
    }


def _multichip_docs():
    """r01 a failed legacy blob, r02-r05 presence-only legacy blobs,
    r06 the structured dryrun report."""
    legacy_ok = {"n_devices": 8, "rc": 0, "ok": True, "skipped": False,
                 "tail": "dryrun ok\n"}
    step = {"steps_per_sec": 10.0, "tokens_per_sec": 1000.0,
            "step_ms": 100.0, "final_loss": 4.0, "devices": 8}
    report = {"n_devices": 8,
              "models": {"transformer_ring_dp_sp_tp":
                         dict(step, mesh="dp=2,sp=2,tp=2")},
              "tied_lm_dp": dict(step, mesh="dp=8"),
              "tied_lm_hybrid": dict(step, mesh="dp=2,tp=4"),
              "scaling": {"dp_tokens_per_sec": 1000.0,
                          "hybrid_tokens_per_sec": 900.0,
                          "efficiency_vs_dp": 0.9},
              "meta": _meta()}
    docs = {n: legacy_ok for n in (2, 3, 4, 5)}
    docs[1] = {"n_devices": 8, "rc": 1, "ok": False, "skipped": False,
               "tail": "Traceback (most recent call last):\n"
                       "AssertionError: need 8 devices, have 1\n"}
    docs[6] = {"n": 6, "n_devices": 8, "rc": 0, "ok": True,
               "skipped": False, "tail": "", "parsed": report}
    return docs


def write_rounds(dirpath):
    for n, doc in _bench_docs().items():
        doc = dict({"n": n, "cmd": _CMD}, **doc)
        with open(os.path.join(dirpath, f"BENCH_r{n:02d}.json"), "w") as f:
            json.dump(doc, f)
    for n, doc in _multichip_docs().items():
        with open(os.path.join(dirpath,
                               f"MULTICHIP_r{n:02d}.json"), "w") as f:
            json.dump(doc, f)
    return str(dirpath)


@pytest.fixture(scope="module")
def rounds_dir(tmp_path_factory):
    return write_rounds(tmp_path_factory.mktemp("rounds"))


def _rounds(dirpath, pattern):
    return sorted(glob.glob(os.path.join(dirpath, pattern)))


# ----------------------------------------------------- trajectory integrity

def test_every_round_format_validates(rounds_dir):
    """Integrity: a round of every format the loader parses passes the
    perfboard schema validator — corruption of a trajectory is a
    failure, not a silent attribution skew."""
    paths = _rounds(rounds_dir, pb.BENCH_GLOB) + \
        _rounds(rounds_dir, pb.MULTICHIP_GLOB)
    assert len(paths) == 12
    problems = []
    for p in paths:
        problems.extend(pb.validate_file(p))
    assert problems == []


def test_validator_catches_truncation(tmp_path, rounds_dir):
    src = _rounds(rounds_dir, pb.BENCH_GLOB)[0]
    dst = tmp_path / os.path.basename(src)
    dst.write_text(open(src).read()[:100])
    assert any("unreadable" in e for e in pb.validate_file(str(dst)))


def test_validator_catches_round_number_mismatch(tmp_path, rounds_dir):
    doc = json.load(open(_rounds(rounds_dir, pb.BENCH_GLOB)[0]))
    doc["n"] = 42
    dst = tmp_path / "BENCH_r01.json"
    dst.write_text(json.dumps(doc))
    assert any("disagrees with" in e for e in pb.validate_file(str(dst)))


def test_validator_rejects_bad_filename(tmp_path):
    dst = tmp_path / "BENCH_latest.json"
    dst.write_text("{}")
    assert pb.validate_file(str(dst))


# ------------------------------------------ loader pins (one per format)

def test_r01_is_headline_only(rounds_dir):
    r = pb.load_bench_round(os.path.join(rounds_dir, "BENCH_r01.json"))
    assert r.format == "headline"
    assert r.headline["value"] == pytest.approx(1000.0)
    assert r.sections == {}
    assert r.meta is None
    assert any("legacy" in n for n in r.notes)


def test_r02_is_failed_with_reason(rounds_dir):
    r = pb.load_bench_round(os.path.join(rounds_dir, "BENCH_r02.json"))
    assert r.format == "failed"
    assert r.rc == 1 and r.ok is False
    assert r.notes  # the traceback tail is surfaced, not swallowed


def test_r03_full_doc_recovered_from_tail(rounds_dir):
    r = pb.load_bench_round(os.path.join(rounds_dir, "BENCH_r03.json"))
    assert r.format == "tail-json"
    assert r.sections["resnet50"]["mfu"] == pytest.approx(0.25)
    assert r.sections["transformer_lm"]["mfu"] == pytest.approx(0.5)
    assert r.platform() == "tpu"


def test_r04_partial_brace_scan_recovery(rounds_dir):
    """r04's tail is head-truncated mid-`device_health`; every complete
    section object after the cut must still be recovered."""
    r = pb.load_bench_round(os.path.join(rounds_dir, "BENCH_r04.json"))
    assert r.format == "partial"
    assert r.sections["resnet50"]["mfu"] == pytest.approx(0.26)
    assert r.sections["vgg16"]["mfu"] == pytest.approx(0.3)
    assert r.platform() == "tpu"  # from the surviving "device" scalar


def test_r05_partial_recovery_and_platform_inference(rounds_dir):
    """r05 lost even the `device` scalar — platform must come from the
    structural tell (TPU-only window_tflops stamps)."""
    r = pb.load_bench_round(os.path.join(rounds_dir, "BENCH_r05.json"))
    assert r.format == "partial"
    assert "device" not in r.top
    assert r.sections["vgg16"]["mfu"] == pytest.approx(0.35)
    assert r.sections["transformer_lm"]["mfu"] == pytest.approx(0.7)
    assert r.platform() == "tpu"


def test_r06_is_full_with_meta(rounds_dir):
    """The first meta-stamped round: full format, provenance block with
    fingerprint, CPU-mesh platform."""
    r = pb.load_bench_round(os.path.join(rounds_dir, "BENCH_r06.json"))
    assert r.format == "full"
    assert r.meta is not None
    for key in ("git_sha", "date_utc", "device_platform",
                "num_devices", "knobs", "fingerprint"):
        assert key in r.meta
    assert r.meta["device_platform"] == "cpu"
    assert r.meta["num_devices"] == 8
    assert r.platform() == "cpu"
    assert "resnet50" in r.sections


def test_multichip_legacy_rounds_presence_only(rounds_dir):
    """r01–r05 are legacy {rc, ok, tail} blobs — classified, not
    crashed on and not silently skipped."""
    r1 = pb.load_multichip_round(
        os.path.join(rounds_dir, "MULTICHIP_r01.json"))
    assert r1.format == "legacy"
    assert r1.rc == 1 and r1.ok is False
    assert any("need 8 devices" in n for n in r1.notes)
    for n in (2, 3, 4, 5):
        r = pb.load_multichip_round(
            os.path.join(rounds_dir, f"MULTICHIP_r{n:02d}.json"))
        assert r.format == "legacy"
        assert r.ok is True
        assert r.top["n_devices"] == 8
        assert any("presence-only" in note for note in r.notes)


def test_multichip_r06_is_structured(rounds_dir):
    r = pb.load_multichip_round(
        os.path.join(rounds_dir, "MULTICHIP_r06.json"))
    assert r.format == "full"
    assert r.meta is not None
    assert "transformer_ring_dp_sp_tp" in r.sections
    assert "scaling" in r.sections


# ------------------------------------------------------- recovery mechanics

def test_recover_sections_skips_incomplete_objects():
    tail = ('runcated": {"x": 1, "resnet50": {"step_ms": 10.0, '
            '"nested": {"a": [1, "}{"]}}, "autotune": {"tuned_ms": 5.0')
    out = pb.recover_sections(tail)
    assert out["resnet50"]["step_ms"] == 10.0
    assert out["resnet50"]["nested"]["a"][1] == "}{"  # brace in string
    assert "autotune" not in out  # never closed — skipped, not guessed


# ------------------------------------------------------------ provenance

def test_provenance_meta_shape_and_fingerprint():
    meta = pb.provenance_meta(REPO)
    assert meta["meta_version"] == pb.META_VERSION
    assert len(meta["git_sha"]) == 40
    assert meta["fingerprint"] == pb.meta_fingerprint(meta)
    # sha/date/hostname must NOT move the comparability fingerprint...
    m2 = dict(meta, git_sha="0" * 40, date_utc="1970-01-01T00:00:00Z",
              hostname="elsewhere")
    assert pb.meta_fingerprint(m2) == meta["fingerprint"]
    # ...a knob change must.
    m3 = dict(meta, knobs=dict(meta["knobs"] or {},
                               HOROVOD_FUSION_THRESHOLD_MB="512"))
    assert pb.meta_fingerprint(m3) != meta["fingerprint"]


def test_uncataloged_knob_is_quarantined(monkeypatch):
    monkeypatch.setenv("HOROVOD_NOT_A_REAL_KNOB_XYZ", "1")
    meta = pb.provenance_meta(REPO)
    assert "HOROVOD_NOT_A_REAL_KNOB_XYZ" not in (meta["knobs"] or {})
    assert "HOROVOD_NOT_A_REAL_KNOB_XYZ" in (meta["uncataloged_knobs"]
                                             or [])


# ----------------------------------------------------------- diff engine

def _series(vals, platform="cpu", fp="abc"):
    return [{"round": i + 1, "value": v, "platform": platform,
             "fingerprint": fp} for i, v in enumerate(vals)]


def test_judge_series_flags_regression_not_noise():
    flat = _series([100.0, 101.0, 99.0, 100.5, 100.0])
    ok = pb.judge_series(flat, +1, z=4.0, rel_floor=0.10, min_points=2)
    assert not ok["regressed"]
    bad = pb.judge_series(_series([100.0, 101.0, 99.0, 100.5, 160.0]),
                          +1, z=4.0, rel_floor=0.10, min_points=2)
    assert bad["regressed"]
    assert bad["delta_pct"] > 20


def test_judge_series_direction_sense():
    # Throughput (direction -1): a DROP regresses, a jump improves.
    drop = pb.judge_series(_series([1000.0, 990.0, 1010.0, 400.0]),
                           -1, z=4.0, rel_floor=0.10, min_points=2)
    assert drop["regressed"]
    jump = pb.judge_series(_series([1000.0, 990.0, 1010.0, 2000.0]),
                           -1, z=4.0, rel_floor=0.10, min_points=2)
    assert not jump["regressed"] and jump["improved"]


def test_judge_series_needs_min_points():
    assert pb.judge_series(_series([1.0, 2.0]), +1, 4.0, 0.1, 2) is None


def test_attribution_names_dominant_phase():
    ref = pb.Round("bench", 6, "x")
    cur = pb.Round("bench", 7, "x")
    ref.sections["resnet50"] = {"perfscope": {"phases_s": {
        "fprop": 0.010, "bprop": 0.020, "allreduce": 0.005}}}
    cur.sections["resnet50"] = {"perfscope": {"phases_s": {
        "fprop": 0.010, "bprop": 0.020, "allreduce": 0.030}}}
    att = pb.attribute("resnet50", cur, ref)
    assert att["dominant_phase"] == "allreduce"
    assert att["dominant_delta_ms"] == pytest.approx(25.0)
    assert any("allreduce" in c for c in att["causes"])


def test_attribution_flags_config_drift_over_phases():
    ref = pb.Round("bench", 5, "x")
    cur = pb.Round("bench", 6, "x")
    ref.meta = {"device_platform": "tpu", "knobs": {}}
    ref.meta["fingerprint"] = pb.meta_fingerprint(ref.meta)
    cur.meta = {"device_platform": "cpu", "knobs": {}}
    cur.meta["fingerprint"] = pb.meta_fingerprint(cur.meta)
    ref.sections["resnet50"] = {}
    cur.sections["resnet50"] = {}
    att = pb.attribute("resnet50", cur, ref)
    assert "config_drift" in att
    assert "tpu -> cpu" in att["config_drift"]


def test_attribution_reads_hvdwatch_and_layout_stamps():
    ref = pb.Round("bench", 6, "x")
    cur = pb.Round("bench", 7, "x")
    ref.sections["s"] = {"hvdwatch": {"anomalies_total": 0},
                         "layout": {"mode": "auto"}}
    cur.sections["s"] = {"hvdwatch": {"anomalies_total": 3},
                         "layout": {"mode": "forced"}}
    att = pb.attribute("s", cur, ref)
    assert att["hvdwatch_anomalies"]["current"] == 3
    assert att["layout_change"] == "auto -> forced"


# -------------------------------------------------- the gate, both ways

def _fixture_dir(tmp_path, regress=None):
    """A rounds dir: r01–r06 plus a clean r07 copy of r06 and, when
    `regress` is given, an r08 with the regression injected into
    (section, metric, factor, phase)."""
    write_rounds(tmp_path)
    r06 = json.load(open(tmp_path / "BENCH_r06.json"))
    r07 = copy.deepcopy(r06)
    r07["n"] = 7
    (tmp_path / "BENCH_r07.json").write_text(json.dumps(r07))
    if regress:
        sec_name, metric, factor, phase = regress
        r08 = copy.deepcopy(r06)
        r08["n"] = 8
        sec = r08["parsed"]["extra"][sec_name]
        sec[metric] = sec[metric] * factor
        # Pour the whole delta into one perfscope phase so attribution
        # has a right answer to find.
        ps = sec["perfscope"]
        delta_s = sec[metric] / factor * (factor - 1) / 1e3
        ps["phases_s"][phase] = ps["phases_s"].get(phase, 0.0) + delta_s
        ps["wall"]["mean_s"] += delta_s
        (tmp_path / "BENCH_r08.json").write_text(json.dumps(r08))
    return str(tmp_path)


def test_gate_passes_on_mixed_format_trajectory(rounds_dir):
    """Acceptance: a trajectory of every legacy format ending at r06
    gates clean (structural AND numeric) — r06 is the first
    meta-stamped round, so nothing is provenance-comparable to it yet,
    and legacy/TPU deltas are drift, not regressions."""
    rounds = pb.load_rounds(rounds_dir)
    analysis = pb.analyze(rounds)
    rc, msgs = pb.gate(analysis, rounds, rounds_dir, numeric=True)
    assert rc == 0, msgs
    assert analysis["regressions"] == []


def test_gate_fails_on_injected_regression(tmp_path):
    """Acceptance: a fixture round with a >=20% step-time regression
    (here 50%, poured into bprop) fails the gate, and the report names
    the section AND the dominant moved perfscope phase."""
    d = _fixture_dir(tmp_path,
                     regress=("resnet50", "step_ms", 1.5, "bprop"))
    rounds = pb.load_rounds(d)
    analysis = pb.analyze(rounds)
    assert any(e["section"] == "resnet50"
               for e in analysis["regressions"])
    rc, msgs = pb.gate(analysis, rounds, d, numeric=True)
    assert rc == 1
    joined = "\n".join(msgs)
    assert "resnet50" in joined
    assert "dominant moved phase: bprop" in joined


def test_gate_clean_fixture_round_passes(tmp_path):
    """Same fixture machinery without the injection: a faithful new
    round must NOT trip the gate (no false positives from the copy)."""
    d = _fixture_dir(tmp_path)
    rounds = pb.load_rounds(d)
    analysis = pb.analyze(rounds)
    rc, msgs = pb.gate(analysis, rounds, d, numeric=True)
    assert rc == 0, msgs


def test_gate_structural_missing_meta(tmp_path):
    """A NEW round without meta provenance is a structural failure —
    the bench stamp regressing is itself gated."""
    d = _fixture_dir(tmp_path)
    r09 = json.load(open(tmp_path / "BENCH_r06.json"))
    r09["n"] = 9
    del r09["parsed"]["meta"]
    (tmp_path / "BENCH_r09.json").write_text(json.dumps(r09))
    rounds = pb.load_rounds(d)
    analysis = pb.analyze(rounds)
    rc, msgs = pb.gate(analysis, rounds, d, numeric=False)
    assert rc == 1
    assert any("meta provenance" in m for m in msgs)


# ------------------------------------------------------ blessed baselines

def test_round_blessable_refuses_failed_round(rounds_dir):
    reasons = pb.round_blessable(os.path.join(rounds_dir, "BENCH_r02.json"))
    assert any("FAILED" in r for r in reasons)


def test_round_blessable_refuses_regressed_round(tmp_path):
    d = _fixture_dir(tmp_path,
                     regress=("resnet50", "step_ms", 1.5, "bprop"))
    reasons = pb.round_blessable(os.path.join(d, "BENCH_r08.json"))
    assert any("perfboard flags" in r for r in reasons)


def test_round_blessable_accepts_r06(rounds_dir):
    assert pb.round_blessable(
        os.path.join(rounds_dir, "BENCH_r06.json")) == []


# ------------------------------------------------------------- surfaces

def test_report_and_html_render(rounds_dir):
    rounds = pb.load_rounds(rounds_dir)
    analysis = pb.analyze(rounds)
    text = pb.render_report(analysis)
    assert "[rounds]" in text
    assert "BENCH r06" in text
    assert "resnet50" in text
    html = pb.render_html(analysis)
    assert "<svg" in html and "perfboard" in html


def test_doctor_summary_shape(rounds_dir):
    s = pb.doctor_summary(rounds_dir)
    assert s is not None
    assert s["latest"]["n"] == 6
    assert isinstance(s["regressions"], list)


def test_cli_json_and_gate(tmp_path, capsys, rounds_dir):
    rc = pb.main(["--dir", rounds_dir, "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["latest"] == 6
    out = tmp_path / "board.html"
    assert pb.main(["--dir", rounds_dir, "--html", str(out),
                    "--gate"]) == 0
    assert out.exists() and "<svg" in out.read_text()


def test_cli_validate_mode(tmp_path, rounds_dir):
    assert pb.main(["--dir", rounds_dir, "--validate"]) == 0
    (tmp_path / "BENCH_r01.json").write_text("{broken")
    assert pb.main(["--dir", str(tmp_path), "--validate"]) == 1


# -------------------------------------------------------------- metrics

def test_metrics_preregistered():
    from horovod_tpu.observability import metrics as m
    pb.preregister_metrics()
    reg = m.registry()
    assert reg.peek("hvdperfboard_rounds_loaded_total") is not None
    assert reg.peek("hvdperfboard_regressions_total") is not None
