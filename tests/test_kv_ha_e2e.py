"""Replicated-rendezvous chaos e2e (`make kv-ha-smoke`; ISSUE 16
acceptance).

Two real jobs under HOROVOD_KV_REPLICAS=3 with the PRIMARY KV replica's
process group SIGKILLed mid-run:

* a 2-process elastic TRAINING job (the ckpt-mode worker) with a
  `host_kill` fault rule armed inside replica 0's client-write path —
  the job must finish rc 0 with monotone step progress (no committed
  step re-executed), a surviving committed checkpoint + `ckpt/latest`
  pointer, and a doctor `[control-plane]` section naming the failover
  (old/new primary, epoch 1->2);
* the SERVING tier under open-loop load while the primary replica dies —
  ZERO dropped accepted requests, every answer right, clean drain.

`HOROVOD_KV_REPLICAS=1` byte-identical-behavior coverage lives in the
unmodified existing suites (`make chaos`, `make ckpt-smoke`,
`make doctor-smoke`) plus test_kv_ha.py's single-endpoint client test.

Marked `faults`: minutes of runtime, excluded from tier 1.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from test_elastic_e2e import finish, start_job, write_hosts

HERE = os.path.dirname(__file__)
REPO = os.path.dirname(HERE)

pytestmark = pytest.mark.faults

TOTAL_STEPS = 10


def _leader(endpoints):
    """(info, endpoint) of the current primary, probing every replica."""
    for host, port in endpoints:
        try:
            with urllib.request.urlopen(
                    f"http://{host}:{port}/leader", timeout=2) as r:
                info = json.loads(r.read().decode())
        except Exception:
            continue
        if info.get("role") == "primary":
            return info, (host, port)
    return None, None


def _doctor_report(flight_dir):
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.observability.doctor",
         "--dir", str(flight_dir), "--json"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    return json.loads(r.stdout)


def test_training_survives_primary_kv_replica_host_kill(tmp_path):
    """The headline chaos e2e: replica r0 (the boot primary) host_kills
    its own process group mid-write, mid-training."""
    flight_dir = tmp_path / "flight"
    ckpt_dir = tmp_path / "ckpts"
    proc, hosts_file, progress = start_job(
        tmp_path, "ckpt", total_steps=TOTAL_STEPS,
        extra_env={
            "HOROVOD_KV_REPLICAS": "3",
            "HOROVOD_KV_PROBE_INTERVAL": "0.1",
            "HOROVOD_FLIGHT_DIR": str(flight_dir),
            "HOROVOD_CKPT_DIR": str(ckpt_dir),
            "HOROVOD_RENDEZVOUS_PORT_FILE": str(tmp_path / "rdv.port"),
            # the chaos: the 7th client write replicated through the
            # boot primary takes its whole process group down — the
            # exact window where an un-replicated ack would lose data
            "HOROVOD_FAULT_SPEC":
                "site=kv_ha.put.r0,kind=host_kill,after=6,count=1",
        })
    write_hosts(hosts_file, "localhost:1,127.0.0.1:1")
    # (under `TEST_LIMIT_S`, tests/conftest.py, which would orphan the job)
    out = finish(proc, timeout=240.0)

    # The job finished: both workers, full trajectory, no respawns —
    # the control-plane failover is invisible to training.
    assert out.count("ELASTIC_DONE") == 2, out
    assert out.count("WORKER_BOOT") == 2, out
    for line in out.splitlines():
        if "ELASTIC_DONE" in line:
            assert f"step={TOTAL_STEPS}" in line, line

    # Monotone, exactly-once step progress through the failover.
    steps = [int(x) for x in progress.read_text().split()]
    assert sorted(set(steps)) == sorted(steps), \
        f"a committed step was re-executed: {steps}"
    assert max(steps) == TOTAL_STEPS, steps

    # A committed checkpoint survived (the ckpt/latest KV pointer was
    # re-homed onto the new primary before the job ended).
    from horovod_tpu.ckpt import manifest as mf
    latest = mf.latest_committed(str(ckpt_dir))
    assert latest is not None and latest[1] >= 1, latest

    # Doctor names the failover: r0 died as primary, r1 promoted
    # under epoch 2, and the [control-plane] text section renders it.
    report = _doctor_report(flight_dir)
    cp = report["control_plane"]
    assert cp is not None, report
    assert cp["replicas"] == 3, cp
    assert any(d["replica"] == 0 and d["primary"]
               for d in cp["deaths"]), cp
    assert cp["failovers"], cp
    fo = cp["failovers"][0]
    assert fo["old_primary"] == 0 and fo["new_primary"] == 1, fo
    assert (fo["old_epoch"], fo["epoch"]) == (1, 2), fo
    assert cp["epoch"] == 2, cp
    assert not cp["errors"], cp
    text = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.observability.doctor",
         "--dir", str(flight_dir)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=120).stdout
    assert "[control-plane]" in text, text
    assert "FAILOVER: primary r0 -> r1, epoch 1->2" in text, text


def test_serving_survives_primary_kv_replica_kill_under_load(tmp_path):
    """Serving chaos: the PRIMARY KV replica (not a serve replica) is
    SIGKILLed while client load runs — the data plane must not drop a
    single accepted request while the control plane fails over."""
    from test_serve_e2e import (FEATURES, SECRET, _expected, _finish,
                                _save_checkpoint, _start_service,
                                _write_hosts)

    from horovod_tpu.runner.rendezvous import read_endpoints
    from horovod_tpu.serve.frontend import ServeClient, wait_for_port_file

    ckpt_path = _save_checkpoint(tmp_path)
    rdv_port_file = tmp_path / "rdv.port"
    # ride the serve harness, adding the HA control plane on top
    real_popen = subprocess.Popen

    def popen_with_ha(cmd, env=None, **kw):
        env = dict(env or os.environ)
        env.update({"HOROVOD_KV_REPLICAS": "3",
                    "HOROVOD_KV_PROBE_INTERVAL": "0.1",
                    "HOROVOD_RENDEZVOUS_PORT_FILE": str(rdv_port_file)})
        return real_popen(cmd, env=env, **kw)

    subprocess.Popen = popen_with_ha
    try:
        proc, hosts_file, port_file, flight_dir, pid_dir = \
            _start_service(tmp_path, ckpt_path)
    finally:
        subprocess.Popen = real_popen
    _write_hosts(hosts_file, "localhost:1,127.0.0.1:1")
    try:
        port = wait_for_port_file(str(port_file), timeout=90)
        addr = ("127.0.0.1", port)
        probe = ServeClient(addr, secret=SECRET.encode())
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            try:
                if len(os.listdir(pid_dir)) >= 2:
                    out = probe.infer(
                        np.full((FEATURES,), 1.0, np.float32))
                    assert abs(float(out) - _expected(1.0)) < 1e-4
                    break
            except Exception:
                time.sleep(0.2)
        else:
            pytest.fail("replicas never came up")

        lock = threading.Lock()
        results = []     # (value, answer)  guarded-by: lock
        failures = []    # guarded-by: lock
        stop_load = threading.Event()

        def load_worker(tid):
            c = ServeClient(addr, secret=SECRET.encode())
            i = 0
            try:
                while not stop_load.is_set():
                    v = float(tid * 10000 + i)
                    try:
                        out = c.infer(
                            np.full((FEATURES,), v, np.float32))
                    except Exception as e:
                        with lock:
                            failures.append((v, repr(e)))
                        return
                    with lock:
                        results.append((v, float(np.ravel(out)[0])))
                    i += 1
                    time.sleep(0.01)
            finally:
                c.close()

        threads = [threading.Thread(target=load_worker, args=(t,),
                                    daemon=True) for t in range(4)]
        for t in threads:
            t.start()
        time.sleep(2.0)  # steady state

        # Kill the current PRIMARY KV replica's process group.
        eps = read_endpoints(str(rdv_port_file))
        assert len(eps) == 3, eps
        info, _ = _leader(eps)
        assert info is not None, "no primary found to kill"
        os.killpg(os.getpgid(int(info["pid"])), signal.SIGKILL)

        time.sleep(3.0)  # keep the load on through the failover
        stop_load.set()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)

        with lock:
            res = list(results)
            fails = list(failures)
        # --- acceptance: zero dropped accepted requests, right answers
        assert not fails, fails
        assert len(res) > 100, f"too little load ran: {len(res)}"
        for v, out_v in res:
            assert abs(out_v - _expected(v)) \
                < max(1e-3, 1e-6 * abs(out_v)), (v, out_v)

        # the control plane really did fail over while load ran
        new_info, _ = _leader(read_endpoints(str(rdv_port_file)))
        assert new_info is not None and new_info["epoch"] >= 2, new_info
        assert new_info["replica_id"] != info["replica_id"], new_info

        probe.shutdown()
        probe.close()
        _finish(proc)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()

    report = _doctor_report(flight_dir)
    cp = report["control_plane"]
    assert cp is not None and cp["failovers"], report
    assert cp["failovers"][0]["old_primary"] == info["replica_id"], cp
    assert cp["epoch"] >= 2, cp
