"""hvdtrace unit suite (ISSUE 20 tentpole).

Covers the span model (ids, nesting, ambient contextvar propagation,
error capture), head sampling and the tail-based always-keep rules
(error/timeout/requeued/slowest), the bounded flight-style store and
its eviction order, trace-context propagation across the data-service
frame boundary, the serving Request lifecycle stamps + queue-wait
histogram (satellite 1), the KV-tail push/persist plumbing, and the
doctor's cross-process join — the [traces] section and the Perfetto
flow-event export (satellite 2). The live 2-process serving paths are e2e-pinned in
tests/test_serve_e2e.py (`make trace-smoke`).
"""

import json
import os
import socket
import sys
import time

import numpy as np
import pytest

from horovod_tpu.observability import doctor, tracing


@pytest.fixture()
def fresh(monkeypatch):
    """Isolated tracer: clean env, fresh instance, restored after."""
    for var in (tracing.TRACE_ENV, tracing.TRACE_SAMPLE_ENV,
                tracing.TRACE_CAPACITY_ENV, tracing.TRACE_KV_TAIL_ENV,
                tracing.TRACE_SLOW_KEEP_ENV, tracing.DIR_ENV,
                "HOROVOD_RANK", "HOROVOD_SIZE", "HOROVOD_ELASTIC_ROUND",
                "HOROVOD_HOSTNAME"):
        monkeypatch.delenv(var, raising=False)
    tracing.reset_for_tests()
    yield monkeypatch
    tracing.reset_for_tests()


class FakeKV:
    """Records puts; `suppressed_during` proves the push self-suppresses
    (a KV put made from inside the tracer must not spawn trace spans)."""

    def __init__(self, fail: bool = False):
        self.fail = fail
        self.puts = []
        self.suppressed_during = None

    def put(self, scope, key, value):
        self.suppressed_during = tracing.suppressed()
        if self.fail:
            raise ConnectionError("kv down")
        self.puts.append((scope, key, value))


# ------------------------------------------------------------ span model

def test_span_ids_nest_through_ambient_context(fresh):
    tr = tracing.get()
    assert isinstance(tr, tracing.Tracer)
    root = tr.start_span("root", new=True, root=True)
    assert tracing.active()
    assert tracing.current_context() == {"t": root.trace_id,
                                         "s": root.span_id}
    with tracing.span("child", attrs={"k": 1}):
        pass
    root.end()
    assert not tracing.active()
    [frag] = tr.snapshot()
    by_name = {s["name"]: s for s in frag["spans"]}
    assert frag["tid"] == root.trace_id
    assert by_name["child"]["psid"] == root.span_id
    assert by_name["child"]["tid"] == root.trace_id
    assert by_name["child"]["attrs"] == {"k": 1}
    assert by_name["root"]["psid"] is None
    assert frag["done"] and frag["dur"] == by_name["root"]["dur"]
    assert tr.stats()["started"] == 1 and tr.stats()["finished"] == 1


def test_span_exit_captures_exception_and_pins_trace(fresh):
    tr = tracing.get()
    with pytest.raises(RuntimeError):
        with tr.start_span("boom", new=True, root=True):
            raise RuntimeError("bad step")
    [frag] = tr.snapshot()
    [sp] = frag["spans"]
    assert sp["status"] == "error"
    assert sp["attrs"]["error"] == "RuntimeError: bad step"
    assert frag["kept"] == "error"
    assert not tracing.active()  # token reset even on the raise path


def test_head_sampling_zero_returns_noop_but_keeps_adopted(fresh):
    fresh.setenv(tracing.TRACE_SAMPLE_ENV, "0")
    tr = tracing.get()
    assert tr.start_span("r", new=True, root=True) is tracing.NOOP_SPAN
    assert tr.request_context(None) is None
    assert tr.stats()["unsampled"] == 2
    # An upstream-sampled trace is NOT re-sampled: explicit parents
    # always record (the sampling decision is made once, at the head).
    sp = tr.start_span("child", parent={"t": "aa", "s": "bb"})
    assert sp is not tracing.NOOP_SPAN
    sp.end()
    assert [t["tid"] for t in tr.snapshot()] == ["aa"]
    assert tr.request_context({"t": "cc", "s": "dd"}) is not None


def test_disabled_tracer_is_noop_shell(fresh):
    fresh.setenv(tracing.TRACE_ENV, "0")
    tracing.reset_for_tests()
    t = tracing.get()
    assert t is tracing.NOOP
    assert tracing.start_trace("x") is tracing.NOOP_SPAN
    assert tracing.span("y") is tracing.NOOP_SPAN
    assert tracing.adopt({"t": "aa", "s": "bb"}) is None
    assert not tracing.active()
    assert t.request_context(None) is None
    assert t.add_span("n", 0.0, 0.1, trace_id="aa") == ""
    tracing.step_begin()
    tracing.step_end()
    tracing.collective_span("g", "allreduce", 0.01)
    tracing.record_dispatch("allreduce(f32[4])", "g")
    assert t.snapshot() == [] and t.payload() == {}
    assert tracing.dump("manual") is None
    assert not tracing.push_tail()


def test_request_context_adopts_or_head_samples(fresh):
    tr = tracing.get()
    fresh_ctx = tr.request_context(None)
    assert set(fresh_ctx) == {"t", "s"}
    adopted = tr.request_context({"t": "cafe", "s": "feed"})
    assert adopted["t"] == "cafe"
    assert adopted["p"] == "feed"          # the client's span id
    assert adopted["s"] not in ("cafe", "feed")  # pre-allocated req sid
    assert tr.stats()["started"] == 2


def test_adopt_and_clear_roundtrip(fresh):
    assert tracing.adopt("not a context") is None
    assert tracing.adopt({"s": "no-trace-id"}) is None
    tok = tracing.adopt({"t": "cafe", "s": "feed"})
    assert tok is not None and tracing.active()
    assert tracing.current_context() == {"t": "cafe", "s": "feed"}
    tracing.clear(tok)
    assert not tracing.active()
    tracing.clear()  # idempotent without a token


# -------------------------------------------- retention: keep + eviction

def test_tail_keep_pins_error_timeout_requeued_and_slowest(fresh):
    tr = tracing.Tracer(capacity=8, slow_keep=1)
    tr.add_span("serve.request", 0.0, 0.5, trace_id="err",
                status="error", root=True)
    tr.add_span("serve.request", 0.0, 0.5, trace_id="tmo",
                status="timeout", root=True)
    tr.add_span("serve.request", 0.0, 0.5, trace_id="rq",
                attrs={"requeues": 1}, root=True)
    tr.add_span("serve.request", 0.0, 9.0, trace_id="slow", root=True)
    for i in range(20):
        tr.add_span("serve.request", 0.0, 0.001 * i,
                    trace_id=f"ok{i}", root=True)
    snap = {t["tid"]: t for t in tr.snapshot()}
    assert len(snap) == 8
    assert snap["err"]["kept"] == "error"
    assert snap["tmo"]["kept"] == "timeout"
    assert snap["rq"]["kept"] == "requeued"
    assert snap["slow"]["kept"] == "slow"
    assert tr.stats()["evicted"] == 24 - 8


def test_errored_child_pins_ok_root_trace(fresh):
    tr = tracing.Tracer(capacity=8, slow_keep=0)
    tr.add_span("serve.dispatch", 0.0, 0.01, trace_id="t1",
                status="error")
    tr.add_span("serve.request", 0.0, 0.05, trace_id="t1", root=True)
    [frag] = tr.snapshot()
    assert frag["kept"] == "error"


def test_slow_keep_demotes_when_a_slower_trace_lands(fresh):
    tr = tracing.Tracer(capacity=8, slow_keep=1)
    tr.add_span("r", 0.0, 1.0, trace_id="a", root=True)
    tr.add_span("r", 0.0, 2.0, trace_id="b", root=True)
    snap = {t["tid"]: t for t in tr.snapshot()}
    assert snap["a"]["kept"] is None  # demoted: evictable again
    assert snap["b"]["kept"] == "slow"


def test_eviction_is_fifo_and_bounded_even_when_all_kept(fresh):
    tr = tracing.Tracer(capacity=8, slow_keep=0)
    for i in range(12):
        tr.add_span("r", 0.0, 0.1, trace_id=f"e{i}",
                    status="error", root=True)
    tids = [t["tid"] for t in tr.snapshot()]
    assert tids == [f"e{i}" for i in range(4, 12)]
    assert tr.stats()["evicted"] == 4


def test_spans_per_trace_bounded(fresh):
    tr = tracing.Tracer(capacity=8, slow_keep=0)
    for i in range(tracing.MAX_SPANS_PER_TRACE + 44):
        tr.add_span(f"s{i}", 0.0, 0.001, trace_id="one")
    [frag] = tr.snapshot()
    assert len(frag["spans"]) == tracing.MAX_SPANS_PER_TRACE
    assert tr.stats()["spans"] == tracing.MAX_SPANS_PER_TRACE


def test_payload_tail_budget_always_includes_kept(fresh):
    tr = tracing.Tracer(capacity=64, slow_keep=0)
    tr.add_span("r", 0.0, 0.1, trace_id="err", status="error", root=True)
    for i in range(10):
        tr.add_span("r", 0.0, 0.1, trace_id=f"ok{i}", root=True)
    body = tr.payload(tail_spans=3)
    assert body["version"] == tracing.TRACE_VERSION
    assert "stats" in body and "wall_time" in body
    tids = [t["tid"] for t in body["traces"]]
    # kept first, then the newest non-kept within the span budget
    assert tids == ["err", "ok8", "ok9"]


# ------------------------------------------------------- training plane

def test_step_spans_parent_collective_children(fresh):
    tr = tracing.get()
    tracing.step_begin()
    assert tracing.active()
    tracing.step_begin()  # idempotent while a step is open
    tracing.record_dispatch("allreduce(f32[4]) ps0#0", "grads")
    tracing.collective_span("grads", "allreduce", 0.01, nbytes=16.0)
    tracing.step_end()
    assert not tracing.active()
    tracing.step_end()  # idempotent once closed
    [frag] = tr.snapshot()
    by_name = {s["name"]: s for s in frag["spans"]}
    root = by_name["train.step"]
    assert by_name["dispatch"]["psid"] == root["sid"]
    assert by_name["dispatch"]["attrs"]["op"] == "grads"
    coll = by_name["collective.grads"]
    assert coll["psid"] == root["sid"]
    assert coll["attrs"] == {"activity": "allreduce", "nbytes": 16.0}
    assert coll["dur"] == pytest.approx(0.01)


def test_step_begin_defers_to_an_adopted_ambient_trace(fresh):
    """A serving replica's per-batch perfscope step runs under the
    adopted batch context — step_begin must not clobber it with a
    fresh train.step trace."""
    tracing.get()
    tok = tracing.adopt({"t": "cafe", "s": "feed"})
    tracing.step_begin()
    assert getattr(tracing._tls, "step_span", None) is None
    assert tracing.current_context() == {"t": "cafe", "s": "feed"}
    tracing.clear(tok)


# ------------------------------- serving Request stamps (satellite 1)

def test_request_lifecycle_stamps_and_queue_wait_histogram(fresh):
    from horovod_tpu.observability import metrics
    from horovod_tpu.serve import telemetry
    from horovod_tpu.serve.batching import ContinuousBatcher
    metrics.reset_for_tests()
    try:
        clk = {"t": 100.0}
        b = ContinuousBatcher(max_batch=4, max_wait_s=0.05, depth=16,
                              clock=lambda: clk["t"])
        r1 = b.offer(np.zeros((2,), np.float32))
        clk["t"] = 100.01
        r2 = b.offer(np.zeros((2,), np.float32))
        assert (r1.t_enqueue, r1.t_dequeue, r1.t_done) == \
            (100.0, None, None)
        assert b.poll() is None          # not full, not due
        clk["t"] = 100.06                # past max_wait for the group
        batch = b.poll()
        assert batch is not None and len(batch.requests) == 2
        assert r1.t_dequeue == r2.t_dequeue == 100.06
        clk["t"] = 100.09
        assert r1.complete("ok")
        assert r2.fail("replica died")
        assert r1.t_done == r2.t_done == 100.09
        assert not r1.complete("again")  # first outcome wins
        assert r1.t_done == 100.09       # stamp not re-written
        h = telemetry.handles()["queue_wait"].labels()
        assert h.count == 2
        assert h.sum == pytest.approx((100.06 - 100.0)
                                      + (100.06 - 100.01))
    finally:
        metrics.reset_for_tests()


# --------------------------- frame propagation (satellite 4)

def test_trace_context_rides_data_service_frames(fresh):
    """The causal id crosses the data-service frame boundary exactly
    when a sampled trace is ambient — and the server clears the adopted
    context after each request so it cannot leak across requests on the
    same persistent connection."""
    from horovod_tpu.data import service as dsvc
    seen = []

    def handler(req):
        seen.append((req, tracing.current_context()))
        return ("ok", req)

    srv, port = dsvc._serve(handler, None)
    try:
        with socket.create_connection(("127.0.0.1", port),
                                      timeout=10) as s:
            # no ambient context: the frame goes bare
            dsvc._send_frame(s, ("ping", 1), None)
            assert dsvc._recv_frame(s, None) == ("ok", ("ping", 1))
            # ambient context: wrapped, adopted server-side
            tok = tracing.adopt({"t": "11" * 8, "s": "22" * 8})
            dsvc._send_frame(s, ("ping", 2), None)
            assert dsvc._recv_frame(s, None) == ("ok", ("ping", 2))
            tracing.clear(tok)
            tracing.clear()  # the reply's adopted echo, if any
            # bare again: the server must have cleared request 2's ctx
            dsvc._send_frame(s, ("ping", 3), None)
            assert dsvc._recv_frame(s, None) == ("ok", ("ping", 3))
    finally:
        srv.shutdown()
        srv.server_close()
    assert [r for r, _ in seen] == [("ping", 1), ("ping", 2),
                                    ("ping", 3)]
    assert seen[0][1] is None
    assert seen[1][1] == {"t": "11" * 8, "s": "22" * 8}
    assert seen[2][1] is None  # no cross-request leak


def test_frames_stay_bare_when_tracing_disabled(fresh):
    fresh.setenv(tracing.TRACE_ENV, "0")
    tracing.reset_for_tests()
    from horovod_tpu.data import service as dsvc
    a, b = socket.socketpair()
    try:
        dsvc._send_frame(a, ("x", 1), None)
        assert dsvc._recv_frame(b, None) == ("x", 1)
    finally:
        a.close()
        b.close()


# ------------------------------------------------------------- overhead

def test_span_overhead_budget(fresh):
    """Flight convention: the instrumented hot path must stay cheap —
    20k retroactive spans (the serving completion path) under 2s."""
    tr = tracing.Tracer(capacity=64, slow_keep=4)
    t0 = time.perf_counter()
    for i in range(20000):
        tr.add_span("serve.request", 0.0, 0.001, trace_id=f"t{i}",
                    attrs={"rid": i, "requeues": 0}, root=True)
    assert time.perf_counter() - t0 < 2.0
    assert len(tr.snapshot()) == 64


# ------------------------------------------------------- dump + KV tail

def test_dump_writes_rank_and_round_keyed_file(fresh, tmp_path):
    tracing.get()
    assert tracing.dump("manual", push_kv=False) is None  # no dir set
    fresh.setenv(tracing.DIR_ENV, str(tmp_path))
    fresh.setenv("HOROVOD_RANK", "3")
    fresh.setenv("HOROVOD_ELASTIC_ROUND", "2")
    tracing.get().add_span("train.step", 0.0, 0.1, trace_id="aa",
                           root=True)
    path = tracing.dump("manual", push_kv=False)
    assert path == str(tmp_path / "trace-3.r2.json")
    with open(path) as f:
        body = json.load(f)
    assert body["version"] == tracing.TRACE_VERSION
    assert body["rank"] == 3 and body["round"] == 2
    assert body["trigger"] == "manual"
    assert [t["tid"] for t in body["traces"]] == ["aa"]
    assert body["stats"]["finished"] == 1
    assert [n for n in os.listdir(tmp_path) if ".tmp" in n] == []


def test_push_tail_is_rank_round_keyed_and_self_suppressing(fresh):
    fresh.setenv("HOROVOD_RANK", "1")
    fresh.setenv("HOROVOD_ELASTIC_ROUND", "4")
    tr = tracing.get()
    tr._kv = FakeKV()
    tr.add_span("r", 0.0, 0.1, trace_id="aa", root=True)
    assert tracing.push_tail()
    [(scope, key, value)] = tr._kv.puts
    assert scope == tracing.SCOPE
    assert key == "rank-1.r4"
    assert tr._kv.suppressed_during  # no spans born inside the push
    body = json.loads(value.decode("utf-8"))
    assert body["rank"] == 1 and body["round"] == 4
    assert [t["tid"] for t in body["traces"]] == ["aa"]


def test_push_tail_skips_unkeyable_or_empty_and_swallows_failure(fresh):
    tr = tracing.get()
    tr._kv = FakeKV()
    tr.add_span("r", 0.0, 0.1, trace_id="aa", root=True)
    assert not tracing.push_tail()  # rank unknown: unkeyable tail
    assert tr._kv.puts == []
    fresh.setenv("HOROVOD_RANK", "0")
    tracing.reset_for_tests()
    tr = tracing.get()
    tr._kv = FakeKV()
    assert not tracing.push_tail()  # nothing recorded yet
    tr.add_span("r", 0.0, 0.1, trace_id="bb", root=True)
    tr._kv = FakeKV(fail=True)
    assert not tracing.push_tail()  # transport failure never raises


def test_persist_kv_spans_from_rendezvous_server(fresh, tmp_path):
    from horovod_tpu.runner.rendezvous import RendezvousServer
    rdv = RendezvousServer()
    rdv.start()
    try:
        rdv.put(tracing.SCOPE, "rank-0.r1", b'{"traces": []}')
        rdv.put(tracing.SCOPE, "rank-1.r1", b'{"traces": []}')
        rdv.put("metrics", "rank-0", b"not a trace key")
        out = tmp_path / "fl"
        written = tracing.persist_kv_spans(rdv, str(out))
        assert sorted(os.path.basename(p) for p in written) == \
            ["trace-kv-rank-0.r1.json", "trace-kv-rank-1.r1.json"]
        for p in written:
            assert os.path.dirname(p) == str(out)
    finally:
        rdv.stop()


def test_persist_kv_spans_noop_without_dir(fresh):
    class Store:
        def scope_items(self, scope):  # pragma: no cover - must not run
            raise AssertionError("scraped without an out dir")
    assert tracing.persist_kv_spans(Store(), "") == []


# ----------------------------------------------------- doctor: fragments

def _span(tid, sid, psid, name, t0, dur, status="ok", attrs=None):
    return {"tid": tid, "sid": sid, "psid": psid, "name": name,
            "t0": t0, "dur": dur, "status": status,
            "attrs": dict(attrs or {})}


def _frag(rank, pid, spans, round=0, host="h0"):
    traces = {}
    for sp in spans:
        traces.setdefault(sp["tid"], []).append(sp)
    return {"version": tracing.TRACE_VERSION, "rank": rank,
            "size": 2, "round": round, "hostname": host, "pid": pid,
            "wall_time": 11.0,
            "stats": {"started": len(traces), "finished": len(traces),
                      "unsampled": 0, "spans": len(spans), "evicted": 0},
            "traces": [{"tid": tid, "done": True, "dur": None,
                        "kept": None, "spans": sps}
                       for tid, sps in traces.items()]}


def _serving_fragments():
    """A two-process serving story: the frontend/pool process saw a
    requeued request T1 (failed attempt on a replica that died, retry
    on the survivor) and a second request T2 that shared T1's batch;
    the replica process executed that batch."""
    frontend = _frag(0, 100, [
        _span("T1", "req1", "cli1", "serve.request", 10.0, 0.1,
              attrs={"rid": 5, "requeues": 1}),
        _span("T1", "q1", "req1", "serve.queue", 10.0, 0.02),
        _span("T1", "d0", "req1", "serve.dispatch", 10.02, 0.01,
              status="error",
              attrs={"replica": "h1:111", "attempt": 0, "batch": "B0"}),
        _span("T1", "d1", "req1", "serve.dispatch", 10.03, 0.06,
              attrs={"replica": "h1:222", "attempt": 1, "batch": "B1"}),
        _span("T1", "B1", "req1", "serve.batch", 10.03, 0.06,
              attrs={"replica": "h1:222", "size": 2}),
        _span("T2", "req2", None, "serve.request", 10.01, 0.09,
              attrs={"rid": 6, "requeues": 0}),
        _span("T2", "q2", "req2", "serve.queue", 10.01, 0.01),
        _span("T2", "d2", "req2", "serve.dispatch", 10.03, 0.06,
              attrs={"replica": "h1:222", "attempt": 0, "batch": "B1"}),
    ])
    replica = _frag(1, 222, [
        _span("T1", "rb1", "B1", "replica.infer_batch", 10.035, 0.05),
        _span("T1", "e1", "rb1", "engine.execute", 10.04, 0.04,
              attrs={"bucket": 8, "padded_shape": "(8, 2)"}),
    ], host="h1")
    return frontend, replica


def test_parse_trace_version_gates_and_sanitizes(fresh, capsys):
    ok = _frag(0, 1, [_span("T", "a", None, "r", 0.0, 0.1)])
    assert doctor._parse_trace(json.dumps(ok).encode(), "x") is not None
    newer = dict(ok, version=tracing.TRACE_VERSION + 1)
    assert doctor._parse_trace(json.dumps(newer).encode(), "x") is None
    assert "newer than this tool" in capsys.readouterr().err
    assert doctor._parse_trace(b"not json", "x") is None
    assert doctor._parse_trace(b'{"version": 1}', "x") is None
    dirty = dict(ok)
    dirty["traces"] = [
        {"tid": "T", "spans": [
            {"tid": "T", "sid": "a", "t0": "1.5", "dur": 2,
             "attrs": "not a dict"},
            {"tid": "T"},                      # no sid: dropped
            "not a span",
        ]},
        {"tid": "U", "spans": ["junk only"]},  # no valid span: dropped
        "not a trace",
    ]
    rec = doctor._parse_trace(json.dumps(dirty).encode(), "x")
    [t] = rec["traces"]
    [sp] = t["spans"]
    assert sp["t0"] == 1.5 and sp["dur"] == 2.0
    assert sp["attrs"] == {} and sp["status"] == "ok"


def test_dedupe_trace_keeps_fullest_payload_per_process(fresh):
    small = _frag(0, 100, [_span("T", "a", None, "r", 0.0, 0.1)])
    big = _frag(0, 100, [_span("T", "a", None, "r", 0.0, 0.1),
                         _span("T", "b", "a", "c", 0.0, 0.05)])
    other = _frag(1, 200, [_span("U", "x", None, "r", 0.0, 0.1)])
    out = doctor.dedupe_trace([small, other, big])
    assert [(r["rank"], len(r["traces"][0]["spans"])) for r in out] == \
        [(0, 2), (1, 1)]


def test_analyze_traces_joins_cross_process_split(fresh):
    frontend, replica = _serving_fragments()
    serve = {"replicas": [{"host": "h1", "pid": 222, "rank": 1,
                           "state": "up", "batches": 1}],
             "deaths": [{"host": "h1", "pid": 111, "rank": 0,
                         "requeued": 1}]}
    rep = doctor.analyze_traces([frontend, replica], serve=serve)
    assert rep["requests"] == 2 and rep["complete"] == 2
    assert rep["train_steps"] == 0
    slowest = rep["slowest"][0]
    assert slowest["trace_id"] == "T1" and slowest["rid"] == 5
    assert slowest["total_s"] == pytest.approx(0.1)
    assert slowest["queue_s"] == pytest.approx(0.02)
    assert slowest["dispatch_s"] == pytest.approx(0.07)
    assert slowest["device_s"] == pytest.approx(0.04)
    assert slowest["complete"]
    # the requeued request carries BOTH dispatch attempts, in order
    [rq] = rep["requeued"]
    assert [(a["attempt"], a["status"], a["replica"])
            for a in rq["attempts"]] == \
        [(0, "error", "h1:111"), (1, "ok", "h1:222")]
    assert any("attempt 0 hit replica death" in n
               for n in rq["corroborated_by"])
    # T2 never joined a replica fragment of its own: its device time
    # resolves through the batch span its dispatch named (the links
    # stitch into T1's replica.infer_batch/engine.execute)
    t2 = next(e for e in rep["slowest"] if e["trace_id"] == "T2")
    assert t2["device_s"] == pytest.approx(0.04)
    assert t2["complete"]


def test_analyze_traces_counts_train_steps_and_empty_is_none(fresh):
    assert doctor.analyze_traces([]) is None
    frag = _frag(0, 1, [_span("S", "a", None, "train.step", 0.0, 0.5)])
    rep = doctor.analyze_traces([frag])
    assert rep["train_steps"] == 1 and rep["requests"] == 0


def test_doctor_reports_traces_from_dir(fresh, tmp_path, capsys):
    frontend, replica = _serving_fragments()
    (tmp_path / "trace-0.json").write_text(json.dumps(frontend))
    (tmp_path / "trace-1.json").write_text(json.dumps(replica))
    (tmp_path / "trace-bad.json.tmp.1").write_text("partial")
    assert doctor.main(["--dir", str(tmp_path), "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["traces"]["requests"] == 2
    assert report["traces"]["slowest"][0]["rid"] == 5
    assert doctor.main(["--dir", str(tmp_path)]) == 0
    text = capsys.readouterr().out
    assert "[traces]" in text
    assert "SLOWEST request rid=5 trace=T1" in text
    assert "queue 20.0 ms, dispatch 70.0 ms, device 40.0 ms" in text
    assert "REQUEUED request rid=5" in text
    assert "attempt 0 -> replica h1:111: error" in text


def test_doctor_exits_2_when_nothing_loadable(fresh, tmp_path):
    assert doctor.main(["--dir", str(tmp_path)]) == 2


# --------------------------- Perfetto export flows (satellite 2)

def test_export_trace_emits_nested_tracks_and_flow_events(fresh,
                                                          tmp_path):
    frontend, replica = _serving_fragments()
    out = tmp_path / "trace.json"
    doctor.export_trace([], str(out), traces=[frontend, replica])
    with open(out) as f:
        evs = json.load(f)["traceEvents"]
    slices = [e for e in evs if e.get("ph") == "X"]
    assert {e["pid"] for e in slices} == {0, 1}
    assert all(e["cat"] == "hvdtrace" for e in slices)
    # nesting depth -> distinct thread tracks, with names
    fe_tids = {e["name"]: e["tid"] for e in slices if e["pid"] == 0}
    assert fe_tids["serve.request"] == 0
    assert fe_tids["serve.queue"] == fe_tids["serve.dispatch"] == 1
    threads = [e for e in evs if e.get("ph") == "M"
               and e["name"] == "thread_name"]
    assert {(e["pid"], e["args"]["name"]) for e in threads} >= \
        {(0, "span depth 0"), (0, "span depth 1")}
    procs = [e["args"]["name"] for e in evs if e.get("ph") == "M"
             and e["name"] == "process_name"]
    assert any(p.startswith("hvdtrace rank 0") for p in procs)
    assert any(p.startswith("hvdtrace rank 1") for p in procs)
    # cross-process flows: one arrow per (batch, request trace) pair,
    # from the dispatch slice into the replica's batch execution;
    # d0's batch B0 never executed anywhere, so it gets no arrow
    starts = [e for e in evs if e.get("ph") == "s"]
    finishes = [e for e in evs if e.get("ph") == "f"]
    assert {e["id"] for e in starts} == {"B1:T1", "B1:T2"}
    assert {e["id"] for e in finishes} == {"B1:T1", "B1:T2"}
    assert all(e["cat"] == "hvdtrace.flow" for e in starts + finishes)
    assert all(e["pid"] == 0 for e in starts)      # dispatch side
    assert all(e["pid"] == 1 and e["bp"] == "e" for e in finishes)


def test_export_trace_flows_fall_back_to_batch_slice(fresh, tmp_path):
    """When the replica fragment never arrived (SIGKILL before any
    push), the arrow lands on the pool's own serve.batch slice."""
    frontend, _ = _serving_fragments()
    out = tmp_path / "trace.json"
    doctor.export_trace([], str(out), traces=[frontend])
    with open(out) as f:
        evs = json.load(f)["traceEvents"]
    finishes = [e for e in evs if e.get("ph") == "f"]
    assert {e["id"] for e in finishes} == {"B1:T1", "B1:T2"}
    assert all(e["pid"] == 0 for e in finishes)  # same-process fallback
