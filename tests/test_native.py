"""Native control-plane tests (KV/coordination server, timeline writer,
stall inspector).

Reference analogs: the Gloo rendezvous/http_store path (exercised in the
reference via gloo_run + C++ http_store.cc), controller bitvector
coordination (controller.cc:159-190), test_timeline.py (validates the
Chrome-trace JSON), test_stall.py.
"""

import json
import os
import threading
import time

import pytest

from horovod_tpu import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library unavailable (no toolchain)")


@pytest.fixture()
def kv():
    srv = native.NativeKVServer()
    yield srv
    srv.stop()


def test_kv_put_get_roundtrip(kv):
    c = native.NativeKVClient("127.0.0.1", kv.port)
    assert c.ping()
    c.put("a/b", b"hello world")
    assert c.get("a/b") == b"hello world"
    assert c.get("missing") is None
    c.close()


def test_kv_add_atomic_across_clients(kv):
    def worker(n):
        c = native.NativeKVClient("127.0.0.1", kv.port)
        for _ in range(n):
            c.add("ctr", 1)
        c.close()

    threads = [threading.Thread(target=worker, args=(100,)) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    c = native.NativeKVClient("127.0.0.1", kv.port)
    assert c.add("ctr", 0) == 800
    c.close()


def test_kv_barrier(kv):
    results = []

    def worker(i):
        c = native.NativeKVClient("127.0.0.1", kv.port)
        ok = c.barrier("round1", size=4, timeout=10.0)
        results.append((i, ok))
        c.close()

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(ok for _, ok in results) and len(results) == 4


def test_kv_bitvector_and_or(kv):
    """The cache-coordination pattern: every rank contributes its bitvector,
    then reads the combined result once all ranks checked in (reference:
    CoordinateCacheAndState, controller.cc:159)."""
    vecs = [bytes([0b1110]), bytes([0b0111]), bytes([0b1101])]

    def worker(i):
        c = native.NativeKVClient("127.0.0.1", kv.port)
        c.bitwise("cache_and", vecs[i], op="and")
        got = c.get_when("cache_and", expected=3, timeout=10.0)
        results[i] = got
        c.close()

    results = [None] * 3
    threads = [threading.Thread(target=worker, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(r == bytes([0b1110 & 0b0111 & 0b1101]) for r in results)


def test_timeline_writes_valid_chrome_trace(tmp_path):
    path = str(tmp_path / "tl.json")
    tl = native.NativeTimeline(path)
    t0 = int(time.time() * 1e6)
    tl.emit("allreduce.grad0", "NEGOTIATE_ALLREDUCE", "B", t0)
    tl.emit("allreduce.grad0", "NEGOTIATE_ALLREDUCE", "E", t0 + 50)
    tl.emit("allreduce.grad0", "ALLREDUCE", "X", t0 + 60, dur_us=400)
    tl.emit('weird"name\\x', "CAT", "i", t0 + 500)
    tl.close()
    events = json.load(open(path))
    assert len(events) == 4
    assert events[2]["ph"] == "X" and events[2]["dur"] == 400
    assert events[0]["name"] == "allreduce.grad0"


def test_stall_inspector_flags_old_submissions():
    si = native.NativeStallInspector(warn_sec=0.05, shutdown_sec=0.0)
    si.submit("tensor_a")
    si.submit("tensor_b")
    si.done("tensor_b")
    time.sleep(0.1)
    stalled, shutdown = si.check()
    assert stalled == ["tensor_a"]
    assert not shutdown
    si.done("tensor_a")
    stalled, _ = si.check()
    assert stalled == []
    si.free()


def test_stall_inspector_shutdown_window():
    si = native.NativeStallInspector(warn_sec=0.01, shutdown_sec=0.05)
    si.submit("t")
    time.sleep(0.1)
    stalled, shutdown = si.check()
    assert stalled == ["t"] and shutdown
    si.free()


def test_kv_get_larger_than_buffer_refetches(kv):
    """Values larger than the client's buffer must come back whole, not
    silently truncated (advisor finding: native/__init__.py get/get_when)."""
    c = native.NativeKVClient("127.0.0.1", kv.port)
    big = bytes(range(256)) * 1024  # 256 KiB
    c.put("big", big)
    assert c.get("big", maxlen=1024) == big
    c.bitwise("bigc", big, op="or")
    assert c.get_when("bigc", expected=1, timeout=5.0, maxlen=1024) == big
    c.close()


# -- KVServer::Stop() must return whatever the clients are doing. Each
# stop() runs on a helper thread joined with a timeout, so a deadlock is a
# failed assertion here and not a worker lost to the suite's time limit.

def _stop_within(srv, seconds=10.0):
    t = threading.Thread(target=srv.stop, daemon=True)
    t.start()
    t.join(seconds)
    if t.is_alive():
        # Leak the handle: a second stop() from __del__ would delete a
        # server whose threads still run, and that aborts the process.
        srv._h = None
        pytest.fail(f"NativeKVServer.stop() still blocked after {seconds} s")


def _connected_clients(port, n):
    clients = [native.NativeKVClient("127.0.0.1", port) for _ in range(n)]
    assert all(c.ping() for c in clients)
    return clients


def test_kv_stop_returns_with_idle_clients_connected():
    srv = native.NativeKVServer()
    clients = _connected_clients(srv.port, 3)
    _stop_within(srv)
    for c in clients:
        c.close()


def test_kv_stop_racing_client_close_leaks_no_thread():
    """The launcher's case: the workers' connections close at the moment
    the server stops (runner/launch.py stops it right after the workers
    died)."""
    rounds, n_clients = 200, 8
    n_threads_before = len(os.listdir("/proc/self/task"))
    port = [0]
    gate = threading.Barrier(n_clients + 1, timeout=30.0)

    def client_loop():
        for _ in range(rounds):
            gate.wait()  # the round's server is up
            c = native.NativeKVClient("127.0.0.1", port[0])
            c.ping()
            gate.wait()  # every client is connected
            c.close()

    threads = [threading.Thread(target=client_loop, daemon=True)
               for _ in range(n_clients)]
    for t in threads:
        t.start()
    for _ in range(rounds):
        srv = native.NativeKVServer()
        port[0] = srv.port
        gate.wait()
        gate.wait()
        _stop_within(srv)
    for t in threads:
        t.join(30.0)
    assert not any(t.is_alive() for t in threads)
    # Stop() joined every thread it started. Thread.join() returns a moment
    # before the OS thread is gone, hence the short wait; <= because a
    # thread of some other library may have ended meanwhile (a leak would
    # show as up to 200 x 9 more).
    deadline = time.monotonic() + 5.0
    while (len(os.listdir("/proc/self/task")) > n_threads_before
           and time.monotonic() < deadline):
        time.sleep(0.01)
    assert len(os.listdir("/proc/self/task")) <= n_threads_before


def test_kv_request_after_stop_fails_cleanly():
    srv = native.NativeKVServer()
    clients = _connected_clients(srv.port, 3)
    clients[0].put("k", b"v")
    _stop_within(srv)
    answers = []
    t = threading.Thread(
        target=lambda: answers.extend(
            (c.ping(), c.get("k"), c.add("n", 1)) for c in clients),
        daemon=True)
    t.start()
    t.join(10.0)
    assert not t.is_alive(), "a request to a stopped server blocked"
    assert answers == [(False, None, -100)] * 3
    for c in clients:
        c.close()
