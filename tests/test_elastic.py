"""Elastic subsystem tests.

Reference analogs: test/single/test_elastic_driver.py (driver with mocked
workers + scripted discovery), test_elastic_discovery.py, and the state
commit/restore semantics exercised by test/parallel elastic torch tests.
"""

import os
import stat
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.common.exceptions import (HorovodInternalError,
                                           HostsUpdatedInterrupt)
from horovod_tpu.elastic import (ElasticDriver, FixedHosts, HostDiscoveryScript,
                                 HostManager, JaxState, ObjectState, run)
from horovod_tpu.elastic.discovery import _Blacklist


# ----------------------------------------------------------------- discovery

def test_discovery_script(tmp_path):
    script = tmp_path / "discover.sh"
    script.write_text("#!/bin/sh\necho host1:2\necho host2\n")
    script.chmod(script.stat().st_mode | stat.S_IEXEC)
    d = HostDiscoveryScript(str(script), default_slots=4)
    assert d.find_available_hosts_and_slots() == {"host1": 2, "host2": 4}


def test_blacklist_cooldown_backoff(monkeypatch):
    bl = _Blacklist()
    t = [0.0]
    monkeypatch.setattr(time, "monotonic", lambda: t[0])
    bl.blacklist("h")
    assert bl.is_blacklisted("h")
    t[0] += bl.INIT_COOLDOWN + 0.1
    assert not bl.is_blacklisted("h")
    bl.blacklist("h")  # second failure: cooldown doubles
    t[0] += bl.INIT_COOLDOWN + 0.1
    assert bl.is_blacklisted("h")
    t[0] += bl.INIT_COOLDOWN + 0.1
    assert not bl.is_blacklisted("h")


def test_host_manager_excludes_blacklisted():
    hm = HostManager(FixedHosts({"a": 2, "b": 2}))
    hm.update_available_hosts()
    assert hm.available_slots() == 4
    hm.blacklist("b")
    hm.update_available_hosts()
    assert [h.hostname for h in hm.current_hosts] == ["a"]


# -------------------------------------------------------------------- driver

class MockSpawner:
    def __init__(self):
        self.spawned = []   # (slot, round_id)
        self.stopped = []

    def spawn(self, slot, round_id):
        handle = object()
        self.spawned.append((slot, round_id, handle))
        return handle

    def stop(self, handle):
        self.stopped.append(handle)


def make_driver(hosts, **kw):
    fixed = FixedHosts(hosts)
    hm = HostManager(fixed)
    sp = MockSpawner()
    d = ElasticDriver(hm, sp.spawn, sp.stop, discovery_interval=0.05, **kw)
    return d, sp, fixed, hm


def test_driver_initial_round_assigns_all_slots():
    d, sp, fixed, hm = make_driver({"a": 2, "b": 2})
    d.start()
    try:
        slots = d.current_slots()
        assert [s.rank for s in slots] == [0, 1, 2, 3]
        assert {s.hostname for s in slots} == {"a", "b"}
        assert all(s.size == 4 for s in slots)
    finally:
        d.stop()


def test_driver_scale_up_preserves_existing_hosts_first():
    d, sp, fixed, hm = make_driver({"a": 2})
    d.start()
    try:
        assert d.world_size == 2
        fixed.hosts["b"] = 2
        hm.update_available_hosts()
        d._host_change.set()
        assert d.maybe_reset()
        slots = d.current_slots()
        assert [s.rank for s in slots] == [0, 1, 2, 3]
        # Existing host 'a' keeps the leading ranks.
        assert [s.hostname for s in slots][:2] == ["a", "a"]
        assert [s.hostname for s in slots][2:] == ["b", "b"]
    finally:
        d.stop()


def test_driver_worker_failure_blacklists_and_scales_down():
    d, sp, fixed, hm = make_driver({"a": 2, "b": 2})
    d.start()
    try:
        victim = [s for s in d.current_slots() if s.hostname == "b"][0]
        d.handle_worker_exit(victim.rank, 1, host_failure=True)
        hm.update_available_hosts()
        assert d.maybe_reset()
        slots = d.current_slots()
        assert {s.hostname for s in slots} == {"a"}
        assert all(s.size == 2 for s in slots)
    finally:
        d.stop()


def test_driver_reset_limit():
    d, sp, fixed, hm = make_driver({"a": 2}, reset_limit=1)
    d.start()
    try:
        d._host_change.set()
        d.maybe_reset()
        d._host_change.set()
        with pytest.raises(Exception):
            d.maybe_reset()
    finally:
        d.stop()


def test_driver_respects_max_num_proc():
    d, sp, fixed, hm = make_driver({"a": 4}, max_num_proc=2)
    d.start()
    try:
        assert d.world_size == 2
    finally:
        d.stop()


# --------------------------------------------------------------------- state

def test_object_state_commit_restore(hvd):
    s = ObjectState(epoch=3, batch=7)
    s.epoch = 5
    s.restore()
    assert s.epoch == 3 and s.batch == 7
    s.epoch = 5
    s.commit()
    s.epoch = 9
    s.restore()
    assert s.epoch == 5


def test_jax_state_save_restore_sync(hvd):
    params = {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))}
    s = JaxState(params=params, opt_state={"m": jnp.zeros((4, 4))}, epoch=0)
    s.params["w"] = s.params["w"] * 3
    s.restore()
    np.testing.assert_allclose(np.asarray(s.params["w"]), 1.0)
    s.epoch = 2
    s.commit()
    s.sync()  # single-controller: broadcast over the local mesh
    assert s.epoch == 2
    np.testing.assert_allclose(np.asarray(s.params["w"]), 1.0)


def test_elastic_run_retries_on_internal_error(hvd):
    calls = {"n": 0, "restores": 0, "syncs": 0}

    class S(ObjectState):
        def restore(self):
            calls["restores"] += 1
            super().restore()

        def sync(self):
            calls["syncs"] += 1
            super().sync()

    state = S(step=0)

    @run
    def train(st):
        calls["n"] += 1
        if calls["n"] == 1:
            raise HorovodInternalError("simulated collective failure")
        return "done"

    assert train(state) == "done"
    assert calls["restores"] == 1
    assert calls["n"] == 2
    assert calls["syncs"] == 2  # initial + post-reset


def test_elastic_run_hosts_updated_skips_restore(hvd):
    calls = {"n": 0, "restores": 0}

    class S(ObjectState):
        def restore(self):
            calls["restores"] += 1
            super().restore()

    state = S(step=0)

    @run
    def train(st):
        calls["n"] += 1
        if calls["n"] == 1:
            raise HostsUpdatedInterrupt(False)
        return 42

    assert train(state) == 42
    assert calls["restores"] == 0


def test_driver_counts_consecutive_all_failed_rounds():
    """A round where every worker fails must be observable so the launcher
    can stop instead of blacklisting/cooldown-respawning forever (advisor
    finding; reference: registration.py fails the job when the last worker
    exits and none succeeded)."""
    d, sp, fixed, hm = make_driver({"a": 2})
    d.start()
    try:
        assert d.consecutive_failed_rounds == 0
        for s in d.current_slots():
            d.handle_worker_exit(s.rank, 1, host_failure=True)
        assert d.consecutive_failed_rounds == 1
        # Host reappears after cooldown; the next all-failed round bumps it.
        hm._blacklist._entries.clear()
        hm.update_available_hosts()
        d._host_change.set()
        assert d.maybe_reset()
        for s in d.current_slots():
            d.handle_worker_exit(s.rank, 1, host_failure=True)
        assert d.consecutive_failed_rounds == 2
    finally:
        d.stop()


def test_driver_success_resets_failed_round_counter():
    d, sp, fixed, hm = make_driver({"a": 2})
    d.start()
    try:
        slots = d.current_slots()
        d.handle_worker_exit(slots[0].rank, 1)
        d.handle_worker_exit(slots[1].rank, 0)
        assert d.consecutive_failed_rounds == 0
    finally:
        d.stop()


def test_elastic_init_survives_missing_private_api(monkeypatch):
    """VERDICT r2 #8: a jaxlib that moved/changed the private recoverable-
    client API must degrade to the public jax.distributed.initialize
    path, not crash elastic init."""
    import jax

    from horovod_tpu.common.config import Config
    from horovod_tpu.core import topology

    calls = {}

    def fake_initialize(coordinator_address=None, num_processes=None,
                        process_id=None):
        calls["args"] = (coordinator_address, num_processes, process_id)

    monkeypatch.setattr(jax.distributed, "initialize", fake_initialize)

    # 1) factory vanished entirely (resolve the extension through compat,
    # like the production path)
    from horovod_tpu.common.compat import jaxlib_extension
    _jaxlib = jaxlib_extension()
    monkeypatch.delattr(_jaxlib, "get_distributed_runtime_client")
    cfg = Config(rank=1, size=4, elastic=True)
    topology._elastic_distributed_init("10.0.0.1:9999", cfg)
    assert calls["args"] == ("10.0.0.1:9999", 4, 1)

    # 2) factory exists but its signature changed (TypeError)
    calls.clear()

    def new_signature_factory(*a, **kw):
        raise TypeError("unexpected keyword argument 'recoverable'")

    monkeypatch.setattr(_jaxlib, "get_distributed_runtime_client",
                        new_signature_factory, raising=False)
    topology._elastic_distributed_init("10.0.0.2:9998", cfg)
    assert calls["args"] == ("10.0.0.2:9998", 4, 1)


def test_recoverable_client_contract_pinned():
    """The elastic in-process recovery path leans on jax._src internals
    (core/topology.py _elastic_distributed_init). On the jaxlib the
    repository is written for it must NOT have silently decayed to the
    worker-restart fallback; on any other jaxlib a broken contract is a
    documented degradation (skip, visibly)."""
    import jaxlib

    from horovod_tpu.core.topology import (
        RECOVERABLE_CLIENT_TESTED_JAXLIB, recoverable_client_contract)

    tested = RECOVERABLE_CLIENT_TESTED_JAXLIB
    ok, reason = recoverable_client_contract()
    if not jaxlib.__version__.startswith(tested + "."):
        if not ok:
            pytest.skip(f"jaxlib {jaxlib.__version__} is not the tested "
                        f"{tested}; contract broken: {reason} — "
                        f"elastic degrades to worker-restart recovery")
        return
    assert ok, (
        f"jaxlib {jaxlib.__version__} is the tested {tested} but the "
        f"recoverable-client contract broke: {reason}. "
        "Fix _elastic_distributed_init.")


def test_elastic_reset_warm_compile_cache(tmp_path):
    """SURVEY §7 names fast reset as THE elastic risk: a post-reset
    re-init must skip recompiles. The framework wires
    HOROVOD_TPU_COMPILE_CACHE → jax_compilation_cache_dir at init
    (core/topology.py); two worker 'rounds' (process restart = the
    worker-restart recovery path) share the cache dir: every program the
    cold round compiled and stored, the warm round finds there. Counted by
    JAX's own cache events, not by the clock (a loaded host lengthens a
    round: the comparison of seconds failed at PRs 25 and 30)."""
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import collections, os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax
        jax.config.update("jax_platforms", "cpu")
        # CPU compiles are fast; drop the persistence threshold so the
        # test program is cacheable (TPU compiles clear the default 1 s)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        seen = collections.Counter()
        jax.monitoring.register_event_listener(
            lambda event, **kw: seen.update([event.rsplit("/", 1)[-1]]))
        import horovod_tpu as hvd
        hvd.init()
        import jax.numpy as jnp
        @jax.jit
        def f(x):
            for i in range(30):
                x = jnp.tanh(x @ x) + i
            return x
        f(jnp.ones((128, 128), jnp.float32)).block_until_ready()
        print("CACHE", seen["cache_hits"], seen["cache_misses"])
    """)
    env = dict(os.environ)
    env["HOROVOD_TPU_COMPILE_CACHE"] = str(tmp_path)
    env.pop("JAX_PLATFORMS", None)

    def round_counts():
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr
        for ln in r.stdout.splitlines():
            if ln.startswith("CACHE"):
                return tuple(int(n) for n in ln.split()[1:])
        raise AssertionError(f"no counts in output: {r.stdout}")

    hits, compiled = round_counts()
    assert hits == 0 and compiled >= 1, (hits, compiled)
    assert os.listdir(str(tmp_path)), \
        "init did not wire the persistent compile cache"
    assert round_counts() == (compiled, 0), \
        "post-reset re-init recompiled — compile cache not effective"
