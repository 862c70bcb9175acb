"""Elastic training on Spark (reference: spark/runner.py:312
run_elastic — elastic Horovod where each Spark task hosts one worker).

Control is inverted versus the CLI elastic launcher: the launcher can
ssh-spawn worker processes, but a Spark driver cannot start individual
tasks — tasks are where the compute already lives. So each Spark task
runs a long-lived AGENT that places worker subprocesses on command:

  agent/<i>                       heartbeat {host, ts} (registration)
  fn                              cloudpickled user fn (driver → agents)
  launch/<round>/<host>           worker env for a fresh slot
  kill/<host>                     terminate this agent's worker
  status/<round>/<host>/<slot>    worker exit code (agent → driver)
  result/<round>/<rank>           pickled fn() result (agent → driver)
  stopall                         job over; agents exit

(all keys in the job rendezvous KV, scope "spark_elastic", HMAC-signed
like every control-plane write). The driver side reuses the SAME
ElasticDriver/RoundPublisher/drive_elastic_loop as the CLI path —
discovery reads agent heartbeats instead of a discovery script, and
spawn/stop write KV commands instead of ssh-ing. Survivor preservation,
round bumps, and in-worker re-rendezvous are identical.

The agent protocol is Spark-agnostic (it only needs a KV client), which
is also how it is tested: agents in threads + real worker subprocesses,
no Spark installed.
"""

from __future__ import annotations

import json
import os
import pickle
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

_SCOPE = "spark_elastic"
HEARTBEAT_SECONDS = 2.0
STALE_AFTER_SECONDS = 15.0


# ----------------------------------------------------------------------
# agent (runs inside each Spark task)
# ----------------------------------------------------------------------

def agent_main(kv, index: int, stop_event: Optional[threading.Event] = None,
               poll_interval: float = 0.2) -> None:
    """One placement agent. `kv` is a KVClient bound to the job
    rendezvous; `index` is the agent's stable id (its Spark task index).
    Returns when the driver writes `stopall`."""
    host = f"agent{index}"
    stop_event = stop_event or threading.Event()
    proc: Optional[subprocess.Popen] = None
    proc_round = -1
    fn_path: Optional[str] = None

    def beat():
        while not stop_event.is_set():
            try:
                kv.put(_SCOPE, f"agent/{index}",
                       json.dumps({"host": host,
                                   "ts": time.time()}).encode())
            except Exception:
                pass
            stop_event.wait(HEARTBEAT_SECONDS)

    hb = threading.Thread(target=beat, daemon=True)
    hb.start()
    proc_dirs: List[str] = []
    last_kv_ok = time.monotonic()
    try:
        while not stop_event.is_set():
            try:
                if kv.get(_SCOPE, "stopall", timeout=0) is not None:
                    break
                raw_round = kv.get(_SCOPE, "round_hint", timeout=0)
                last_kv_ok = time.monotonic()
            except Exception:
                # Transient KV outage must not kill the agent — capacity
                # would vanish permanently. But a dead rendezvous (job
                # torn down) must not leave agents spinning either.
                if time.monotonic() - last_kv_ok > 60.0:
                    break
                stop_event.wait(poll_interval)
                continue
            cur_round = int(raw_round) if raw_round else 0
            # launch command for this host at the current (or previous —
            # publish precedes the hint bump) round
            for rid in (cur_round, cur_round + 1):
                raw = kv.get(_SCOPE, f"launch/{rid}/{host}", timeout=0)
                if raw is None:
                    continue
                rec = json.loads(raw)
                if rec["round"] <= proc_round:
                    continue
                if proc is not None and proc.poll() is None:
                    # A still-running worker with NO newer launch record
                    # is a survivor (it re-rendezvouses in-process; the
                    # driver only writes launch for slots it actually
                    # spawned). But a newer launch record for this host
                    # means the driver replaced the worker — if its kill
                    # command was swallowed by spawn()'s stale-key
                    # cleanup before we consumed it (ADVICE r2), the old
                    # process would live forever and stall the host.
                    # The launch record IS the authoritative kill.
                    proc.terminate()
                    try:
                        proc.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        proc.kill()
                        try:
                            proc.wait(timeout=30)
                        except subprocess.TimeoutExpired:
                            # unreapable (D-state): abandon the corpse
                            # rather than crash the agent and lose the
                            # host's capacity for good
                            pass
                if fn_path is None:
                    blob = kv.get(_SCOPE, "fn")
                    with tempfile.NamedTemporaryFile(
                            "wb", suffix=".pkl", delete=False) as f:
                        f.write(blob)
                        fn_path = f.name
                out_dir = tempfile.mkdtemp(prefix=f"hvd_spark_el_{index}_")
                proc_dirs.append(out_dir)
                env = dict(os.environ)
                env.update(rec["env"])
                env["HOROVOD_RUN_FUNC_FILE"] = fn_path
                env["HOROVOD_RUN_RESULT_DIR"] = out_dir
                proc = subprocess.Popen(
                    [sys.executable, "-m",
                     "horovod_tpu.runner.task_runner"], env=env)
                proc_round = rec["round"]
                proc_rank = rec["rank"]
                proc_dir = out_dir
            if kv.get(_SCOPE, f"kill/{host}", timeout=0) is not None:
                if proc is not None and proc.poll() is None:
                    proc.terminate()
                # consume the command: a lingering kill key would
                # murder every future worker on this agent
                kv.delete(_SCOPE, f"kill/{host}")
            if proc is not None:
                code = proc.poll()
                if code is not None:
                    if code == 0:
                        # rank_<n>.pkl is named by the SPAWN-time rank
                        # env (task_runner), which is proc_rank even if
                        # the worker re-ranked as a survivor — results
                        # are therefore published HOST-keyed and the
                        # driver maps host -> final rank.
                        res = os.path.join(proc_dir,
                                           f"rank_{proc_rank}.pkl")
                        try:
                            with open(res, "rb") as f:
                                kv.put(_SCOPE, f"result/{host}", f.read())
                        except OSError:
                            code = 1
                    kv.put(_SCOPE, f"status/{proc_round}/{host}/0",
                           str(code).encode())
                    proc = None
            time.sleep(poll_interval)
    finally:
        stop_event.set()
        if proc is not None and proc.poll() is None:
            proc.terminate()
        hb.join(timeout=2)
        import shutil
        if fn_path:
            try:
                os.unlink(fn_path)
            except OSError:
                pass
        for d in proc_dirs:
            shutil.rmtree(d, ignore_errors=True)


# ----------------------------------------------------------------------
# driver side
# ----------------------------------------------------------------------

class KVAgentDiscovery:
    """HostDiscovery over agent heartbeats (duck-typed for HostManager).
    Agents register under fixed indices, so discovery polls
    agent/0..max_agents-1 — the KV has no key listing by design."""

    def __init__(self, kv, max_agents: int):
        self.kv = kv
        self.max_agents = max_agents

    def __init_last_seen(self):
        if not hasattr(self, "_last_seen"):
            self._last_seen: Dict[int, tuple] = {}

    def find_available_hosts_and_slots(self) -> Dict[str, int]:
        # Staleness is judged by when the heartbeat VALUE last changed on
        # the DRIVER's clock — executor clocks can be skewed arbitrarily,
        # so the remote "ts" field is treated as an opaque nonce.
        self.__init_last_seen()
        now = time.monotonic()
        out: Dict[str, int] = {}
        for i in range(self.max_agents):
            raw = self.kv.get(_SCOPE, f"agent/{i}", timeout=0)
            if raw is None:
                continue
            prev = self._last_seen.get(i)
            if prev is None or prev[0] != raw:
                self._last_seen[i] = (raw, now)
            if now - self._last_seen[i][1] <= STALE_AFTER_SECONDS:
                out[json.loads(raw)["host"]] = 1
        return out


class _AgentHandle:
    """Worker handle whose liveness is the agent-reported status key."""

    def __init__(self, kv, round_id: int, host: str):
        self.kv = kv
        self.round_id = round_id
        self.host = host
        self._killed = False

    def poll(self) -> Optional[int]:
        raw = self.kv.get(_SCOPE, f"status/{self.round_id}/{self.host}/0",
                          timeout=0)
        if raw is not None:
            return int(raw)
        if self._killed:
            return 143
        return None

    def terminate(self) -> None:
        self._killed = True
        self.kv.put(_SCOPE, f"kill/{self.host}", b"1")


def run_elastic(fn, args=(), kwargs=None,
                num_proc: Optional[int] = None,
                min_num_proc: int = 1,
                max_num_proc: Optional[int] = None,
                start_timeout: float = 600.0,
                elastic_timeout: float = 600.0,
                reset_limit: Optional[int] = None,
                extra_env: Optional[dict] = None,
                verbose: int = 1,
                _agent_runner=None) -> List[Any]:
    """Elastic run over Spark tasks (reference: spark/runner.py:312).

    `_agent_runner(n, kv_factory)` is injectable for tests (threads); the
    default submits a Spark job with n long-lived agent tasks.
    """
    import cloudpickle

    from horovod_tpu.common import config as C
    from horovod_tpu.elastic.driver import (ElasticDriver, HostManager,
                                            RoundPublisher,
                                            drive_elastic_loop)
    from horovod_tpu.runner import secret as secret_mod
    from horovod_tpu.runner.launch import _local_ip
    from horovod_tpu.runner.rendezvous import KVClient, RendezvousServer

    job_secret = secret_mod.make_secret_key()
    rdv = RendezvousServer(secret=job_secret.encode())
    rdv_port = rdv.start()
    ip = _local_ip()
    kv = KVClient(ip, rdv_port, secret=job_secret.encode())
    kv.put(_SCOPE, "fn",
           cloudpickle.dumps(lambda: fn(*args, **(kwargs or {}))))

    n_agents = num_proc or max_num_proc or min_num_proc
    max_agents = max_num_proc or n_agents

    if _agent_runner is None:
        _agent_runner = _spark_agent_runner(ip, rdv_port, job_secret,
                                            verbose)
    agent_job = _agent_runner(n_agents, max_agents)

    publisher = RoundPublisher(rdv, ip)
    base_env = dict(extra_env or {})
    base_env.update({
        C.HOROVOD_RENDEZVOUS_ADDR: ip,
        C.HOROVOD_RENDEZVOUS_PORT: str(rdv_port),
        secret_mod.SECRET_ENV: job_secret,
        C.HOROVOD_ELASTIC: "1",
        "HOROVOD_ELASTIC_TIMEOUT": str(elastic_timeout),
        # agents share the launch host in tests; workers must own one CPU
        # device each unless the caller overrides
        "JAX_PLATFORMS": base_env.get("JAX_PLATFORMS", "cpu"),
    })

    def spawn(slot, round_id: int):
        env = dict(base_env)
        env.update({
            "HOROVOD_ELASTIC_ROUND": str(round_id),
            "HOROVOD_COORDINATOR_ADDR": publisher.round_coords[round_id],
            "HOROVOD_RANK": str(slot.rank),
            "HOROVOD_SIZE": str(slot.size),
            "HOROVOD_LOCAL_RANK": str(slot.local_rank),
        })
        # Clear stale commands/results from this host's previous life —
        # a lingering kill would murder the fresh worker on arrival.
        kv.delete(_SCOPE, f"kill/{slot.hostname}")
        kv.delete(_SCOPE, f"result/{slot.hostname}")
        kv.put(_SCOPE, f"launch/{round_id}/{slot.hostname}",
               json.dumps({"round": round_id, "rank": slot.rank,
                           "env": env}).encode())
        kv.put(_SCOPE, "round_hint", str(round_id).encode())
        return _AgentHandle(kv, round_id, slot.hostname)

    hm = HostManager(KVAgentDiscovery(kv, max_agents))
    driver = ElasticDriver(
        hm, spawn, lambda h: h.terminate(),
        min_num_proc=min_num_proc,
        max_num_proc=max_num_proc,
        reset_limit=reset_limit,
        publish_fn=publisher.publish)

    deadline = time.monotonic() + start_timeout

    def _poll_agents() -> bool:
        # update_available_hosts may raise (discovery hiccup, injected
        # flap): absorb until start_timeout — the deadline below stays
        # the single bound on this wait, like wait_for_available_slots.
        try:
            return bool(hm.update_available_hosts())
        except Exception as e:
            print(f"elastic spark: discovery error while waiting for "
                  f"agents: {e}", file=sys.stderr)
            return False

    while not (_poll_agents() or hm.current_hosts):
        if time.monotonic() > deadline:
            kv.put(_SCOPE, "stopall", b"1")
            rdv.stop()
            raise TimeoutError(
                "no Spark agent registered before start_timeout")
        time.sleep(0.2)

    remaining = max(0.0, deadline - time.monotonic())
    driver.start(start_timeout=max(remaining, 1.0))
    try:
        rc = drive_elastic_loop(driver, elastic_timeout)
        if rc != 0:
            raise RuntimeError(f"elastic spark job failed (rc={rc})")
        # Results are HOST-keyed (survivors' spawn-time ranks go stale on
        # resize); the driver owns the final host -> rank mapping
        # (snapshotted by driver.stop()).
        slots = getattr(driver, "last_round_slots", None) or \
            driver.current_slots()
        results: List[Any] = [None] * len(slots)
        for slot in slots:
            raw = kv.get(_SCOPE, f"result/{slot.hostname}", timeout=30.0)
            if raw is not None:
                results[slot.rank] = pickle.loads(raw)
        return results
    finally:
        kv.put(_SCOPE, "stopall", b"1")
        publisher.close()
        if agent_job is not None:
            try:
                agent_job.join(timeout=10)
            except Exception:
                pass
        rdv.stop()


def _spark_agent_runner(ip: str, port: int, job_secret: str, verbose: int):
    """Default agent placement: one long-lived Spark task per agent."""

    def runner(n_agents: int, max_agents: int):
        import pyspark

        sc = pyspark.SparkContext._active_spark_context
        if sc is None:
            raise RuntimeError("no active SparkContext; create a "
                               "SparkSession first")

        def task(index, _it):
            import os as _os

            from horovod_tpu.runner.rendezvous import KVClient as _KV
            _os.environ[
                "HOROVOD_SECRET_KEY"] = job_secret  # noqa: F841
            from horovod_tpu.spark.elastic import agent_main
            agent_main(_KV(ip, port, secret=job_secret.encode()), index)
            yield index

        t = threading.Thread(
            target=lambda: (sc.parallelize(range(n_agents), n_agents)
                            .mapPartitionsWithIndex(task).collect()),
            daemon=True)
        t.start()
        return t

    return runner
