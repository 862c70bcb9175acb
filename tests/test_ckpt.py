"""horovod_tpu/ckpt unit suite (docs/checkpointing.md): manifest/commit
protocol, sharded snapshot/assemble, the two-phase AsyncCheckpointer
(back-pressure, generations, quarantine fallback, KV pointer),
TrainLoopState resume, the restore-signal stall grace, the typed
checkpoint.py marker contract, and the doctor [ckpt] section.

Runs on the tier-1 8-device virtual CPU mesh (conftest) — the sharded
save/restore tests use REAL NamedSharding arrays, so the replica-0
dedup and re-shard paths are the production code paths, not mocks.
"""

import json
import os
import pickle
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu import ckpt
from horovod_tpu.ckpt import async_ckpt, manifest as mf, resume, sharded
from horovod_tpu.common.exceptions import CheckpointCorruptError


class FakeKV:
    def __init__(self):
        self.store = {}
        self.puts = []

    def put(self, scope, key, value):
        self.puts.append((scope, key))
        self.store[f"{scope}/{key}"] = value

    def get(self, scope, key, timeout=0.0):
        return self.store.get(f"{scope}/{key}")


def mesh_2d(dp=2, tp=4):
    devs = np.array(jax.devices()[:dp * tp]).reshape(dp, tp)
    return Mesh(devs, ("dp", "tp"))


def small_tree():
    return {"params": {"w": jnp.arange(8, dtype=jnp.float32),
                       "b": jnp.float32(0.5)},
            "opt_state": {"mu": {"w": jnp.ones((8,), jnp.float32)}}}


def host_like(tree):
    return jax.tree_util.tree_map(
        lambda x: np.zeros(np.shape(x), np.asarray(x).dtype), tree)


# ----------------------------------------------------------- manifest

def test_marker_protocol_and_latest_committed(tmp_path):
    root = str(tmp_path)
    assert mf.latest_committed(root) is None
    # a dir WITHOUT a marker does not exist as a checkpoint
    os.makedirs(os.path.join(root, mf.dirname_for(10)))
    assert mf.latest_committed(root) is None
    mf.write_marker(root, 10, generation=1)
    assert mf.latest_committed(root) == (1, 10)
    # generations order commits even when steps regress (elastic round
    # reset a counter): newest GENERATION wins
    os.makedirs(os.path.join(root, mf.dirname_for(4)))
    mf.write_marker(root, 4, generation=2)
    assert mf.latest_committed(root) == (2, 4)
    # a marker whose dir vanished is skipped
    os.rmdir(os.path.join(root, mf.dirname_for(4)))
    assert mf.latest_committed(root) == (1, 10)


def test_sweep_quarantines_only_stale_uncommitted(tmp_path):
    root = str(tmp_path)
    os.makedirs(os.path.join(root, mf.dirname_for(5)))
    mf.write_marker(root, 5, generation=1)
    # older, marker-less: a writer died mid-save — quarantined
    os.makedirs(os.path.join(root, mf.dirname_for(3)))
    # NEWER marker-less: may be an in-flight save — left alone
    os.makedirs(os.path.join(root, mf.dirname_for(8)))
    swept = mf.sweep_stale(root)
    assert swept == [3]
    assert not os.path.isdir(os.path.join(root, mf.dirname_for(3)))
    assert os.path.isdir(os.path.join(root, mf.dirname_for(8)))
    qdir = os.path.join(root, mf.QUARANTINE_DIR)
    assert len(os.listdir(qdir)) == 1


def test_gc_removes_marker_before_dir(tmp_path):
    root = str(tmp_path)
    for step, gen in ((1, 1), (2, 2), (3, 3)):
        os.makedirs(os.path.join(root, mf.dirname_for(step)))
        mf.write_marker(root, step, generation=gen)
    dropped = mf.gc(root, keep=2)
    assert dropped == [1]
    assert mf.committed(root) == [(2, 2), (3, 3)]
    assert not os.path.exists(mf.marker_path(root, 1))


# ------------------------------------------------------------ sharded

def test_snapshot_writes_only_replica0_shards(tmp_path):
    """P('tp', None) on dp=2 x tp=4: exactly 4 distinct shard files —
    the dp replicas are never written (the 'each dp-replica-0 rank
    writes only its model shards' contract)."""
    mesh = mesh_2d()
    arr = jax.device_put(
        jnp.arange(32 * 8, dtype=jnp.float32).reshape(32, 8),
        NamedSharding(mesh, P("tp", None)))
    snaps, nbytes = sharded.snapshot_tree({"emb": arr})
    assert len(snaps) == 1 and len(snaps[0].shards) == 4
    assert nbytes == arr.nbytes  # one copy of the data, not dp copies
    d = str(tmp_path)
    written = sharded.write_snapshots(d, snaps)
    assert written == arr.nbytes
    files = [f for f in os.listdir(d) if f.endswith(".npy")]
    assert len(files) == 4
    # spec recorded for the re-shard path
    assert snaps[0].entry.spec == [["tp"], None]
    got = sharded.assemble_leaf(d, snaps[0].entry)
    np.testing.assert_array_equal(got, np.asarray(arr))


def test_assemble_detects_missing_and_truncated_shards(tmp_path):
    mesh = mesh_2d()
    arr = jax.device_put(jnp.ones((16, 4), jnp.float32),
                         NamedSharding(mesh, P("tp", None)))
    snaps, _ = sharded.snapshot_tree({"x": arr})
    d = str(tmp_path)
    sharded.write_snapshots(d, snaps)
    entry = snaps[0].entry
    victim = os.path.join(d, entry.files[1]["file"])
    os.remove(victim)
    with pytest.raises(CheckpointCorruptError, match="unreadable"):
        sharded.assemble_leaf(d, entry)
    # wrong-shape shard (truncated rewrite) is typed too
    np.save(victim, np.ones((1, 1), np.float32), allow_pickle=False)
    with pytest.raises(CheckpointCorruptError, match="shape"):
        sharded.assemble_leaf(d, entry)


def test_restore_tree_without_like_rebuilds_dicts(tmp_path):
    snaps, _ = sharded.snapshot_tree(
        {"a": {"b": np.arange(3, dtype=np.float64)}, "c": np.float32(2)})
    d = str(tmp_path)
    sharded.write_snapshots(d, snaps)
    out = sharded.restore_tree(d, [s.entry for s in snaps])
    np.testing.assert_array_equal(out["a"]["b"], np.arange(3))
    assert float(out["c"]) == 2.0


def test_spec_json_roundtrip():
    for spec in (P("tp", None), P(("dp", "tp")), P(), None):
        j = sharded.spec_to_json(spec)
        back = sharded.spec_from_json(j)
        if spec is None:
            assert back is None
        else:
            assert tuple(back) == tuple(spec)


# ---------------------------------------------------- AsyncCheckpointer

def test_async_save_restore_roundtrip_with_objects(tmp_path):
    tree = small_tree()
    s = ckpt.AsyncCheckpointer(str(tmp_path), kv=FakeKV())
    assert s.save(7, tree, objects={"step": 7, "cursor": 3,
                                    "rng": np.uint32(5)})
    assert s.wait(20)
    assert s.last_committed == (1, 7)
    got = s.restore_latest(like=host_like(tree))
    assert got.step == 7 and got.generation == 1
    assert got.objects["cursor"] == 3 and got.objects["rng"] == 5
    np.testing.assert_allclose(got.tree["params"]["w"], np.arange(8))


def test_async_save_never_blocks_and_skips_under_backpressure(
        tmp_path, monkeypatch):
    """The back-pressure contract: with one save in flight, another
    save() returns immediately as a SKIP (counted) — never stalls the
    step, never queues a second payload."""
    s = ckpt.AsyncCheckpointer(str(tmp_path), kv=FakeKV(),
                               queue_depth=1)
    release = threading.Event()
    real_persist = s._persist

    def slow_persist(job):
        release.wait(20)
        real_persist(job)

    monkeypatch.setattr(s, "_persist", slow_persist)
    tree = {"w": np.ones((1024,), np.float32)}
    t0 = time.perf_counter()
    assert s.save(1, tree) is True
    dt_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    assert s.save(2, tree) is False   # writer busy: skip-and-count
    assert s.save(3, tree) is False
    dt_skip = time.perf_counter() - t0
    assert dt_skip < 1.0 and dt_first < 5.0  # nobody waited on disk
    assert s.skipped == 2
    release.set()
    assert s.wait(20)
    # only the accepted save committed
    assert s.last_committed == (1, 1)
    assert s.close()


def test_generation_numbering_continues_across_instances(tmp_path):
    tree = {"w": np.zeros((2,), np.float32)}
    s1 = ckpt.AsyncCheckpointer(str(tmp_path), kv=FakeKV())
    s1.save(1, tree, block=True)
    s1.save(2, tree, block=True)
    assert s1.last_committed == (2, 2)
    # a new process (fresh instance) continues the numbering
    s2 = ckpt.AsyncCheckpointer(str(tmp_path), kv=FakeKV())
    s2.save(9, tree, block=True)
    assert s2.last_committed == (3, 9)


def test_keep_gc_bounds_committed_generations(tmp_path):
    tree = {"w": np.zeros((2,), np.float32)}
    s = ckpt.AsyncCheckpointer(str(tmp_path), keep=2, kv=FakeKV())
    for step in (1, 2, 3, 4):
        s.save(step, tree, block=True)
    assert [st for _, st in mf.committed(str(tmp_path))] == [3, 4]


def test_restore_quarantines_corrupt_and_falls_back(tmp_path):
    tree = small_tree()
    s = ckpt.AsyncCheckpointer(str(tmp_path), kv=FakeKV())
    s.save(1, tree, objects={"step": 1}, block=True)
    s.save(2, tree, objects={"step": 2}, block=True)
    # corrupt the NEWEST committed generation: delete a leaf file
    d2 = os.path.join(str(tmp_path), mf.dirname_for(2))
    victims = [f for f in os.listdir(d2) if f.endswith(".npy")]
    os.remove(os.path.join(d2, victims[0]))
    got = s.restore_latest(like=host_like(tree))
    assert got is not None and got.step == 1  # fell back one generation
    # the corrupt dir is in quarantine, not deleted
    qdir = os.path.join(str(tmp_path), mf.QUARANTINE_DIR)
    assert any(mf.dirname_for(2) in n for n in os.listdir(qdir))
    # nothing left to fall back to after corrupting the survivor too
    d1 = os.path.join(str(tmp_path), mf.dirname_for(1))
    with open(os.path.join(d1, mf.MANIFEST_NAME), "w") as f:
        f.write("not json")
    assert s.restore_latest(like=host_like(tree)) is None


def test_commit_publishes_kv_latest_pointer(tmp_path):
    kv = FakeKV()
    s = ckpt.AsyncCheckpointer(str(tmp_path), kv=kv)
    s.save(5, {"w": np.zeros((2,), np.float32)}, block=True)
    raw = kv.store.get(f"{async_ckpt.KV_SCOPE}/{async_ckpt.KV_LATEST_KEY}")
    assert raw is not None
    body = json.loads(raw.decode())
    assert body["step"] == 5 and body["generation"] == 1
    assert body["root"] == str(tmp_path)
    assert resume.latest_pointer(kv)["generation"] == 1


def test_multi_writer_fragments_merge_before_commit(tmp_path,
                                                    monkeypatch):
    """The sharded multi-process protocol, driven through the REAL
    writer path for both ranks (same directory, same leaf indices):
    shard filenames are offset-derived so concurrent writers can never
    clobber each other, the peer publishes its fragment keyed by STEP,
    and the primary's merged manifest covers the whole leaf."""
    kv = FakeKV()
    root = str(tmp_path)

    def snaps_for(lo, hi, val):
        return [sharded.LeafSnapshot(
            mf.LeafEntry(path="['w']", shape=(8,), dtype="float32",
                         spec=[["tp"]]),
            [((lo,), (hi,),
              np.full((hi - lo,), val, np.float32))])]

    peer = ckpt.AsyncCheckpointer(root, writers=2, kv=kv)
    monkeypatch.setattr(peer, "_rank", lambda: 1)
    peer._persist(async_ckpt._Job(3, 1, snaps_for(4, 8, 2.0), 16,
                                  {}, 0.0))
    # the peer persisted its files + fragment but did NOT commit
    assert mf.latest_committed(root) is None
    primary = ckpt.AsyncCheckpointer(root, writers=2, kv=kv)
    monkeypatch.setattr(primary, "_rank", lambda: 0)
    primary._persist(async_ckpt._Job(3, 1, snaps_for(0, 4, 1.0), 16,
                                     {}, 0.0))
    assert mf.latest_committed(root) == (1, 3)
    d = os.path.join(root, mf.dirname_for(3))
    man = mf.read_manifest(d)
    assert len(man.leaves) == 1 and len(man.leaves[0].files) == 2
    names = {f["file"] for f in man.leaves[0].files}
    assert len(names) == 2  # offset-derived names never collided
    full = sharded.assemble_leaf(d, man.leaves[0])
    np.testing.assert_array_equal(full, [1, 1, 1, 1, 2, 2, 2, 2])


def test_multi_writer_commit_aborts_without_fragments(tmp_path):
    kv = FakeKV()
    primary = ckpt.AsyncCheckpointer(str(tmp_path), writers=2, kv=kv)
    primary.commit_timeout = 0.2
    snaps, _ = sharded.snapshot_tree({"w": np.zeros((4,), np.float32)})
    job = async_ckpt._Job(1, 1, snaps, 16, {}, 0.0)
    primary._persist(job)  # peer fragment never arrives
    assert mf.latest_committed(str(tmp_path)) is None  # no commit


def test_save_failure_releases_inflight_slot(tmp_path, monkeypatch):
    """A snapshot exception must give the reserved queue slot back —
    otherwise one bad save wedges every future save into the skip
    branch and checkpointing silently dies for the process."""
    s = ckpt.AsyncCheckpointer(str(tmp_path), kv=FakeKV())
    boom = {"on": True}
    real = sharded.snapshot_tree

    def maybe_boom(tree):
        if boom["on"]:
            raise RuntimeError("buffer deleted")
        return real(tree)

    monkeypatch.setattr(sharded, "snapshot_tree", maybe_boom)
    with pytest.raises(RuntimeError, match="buffer deleted"):
        s.save(1, {"w": np.zeros((2,), np.float32)})
    boom["on"] = False
    assert s.save(2, {"w": np.zeros((2,), np.float32)},
                  block=True) is True
    # the failed save consumed generation 1 (a harmless gap —
    # monotonicity is the invariant, not density)
    assert s.last_committed == (2, 2)


def test_single_writer_incomplete_coverage_aborts_commit(tmp_path):
    """writers=1 on a multi-process sharded job (this rank addresses
    only part of a leaf) must NOT write a commit marker over an
    unrestorable checkpoint — it aborts loudly at save time."""
    s = ckpt.AsyncCheckpointer(str(tmp_path), kv=FakeKV())
    half = np.full((4,), 1.0, np.float32)
    snaps = [sharded.LeafSnapshot(
        mf.LeafEntry(path="['w']", shape=(8,), dtype="float32",
                     spec=[["tp"]]),
        [((0,), (4,), half)])]  # covers 4/8 elements
    s._persist(async_ckpt._Job(1, 1, snaps, 16, {}, 0.0))
    assert mf.latest_committed(str(tmp_path)) is None
    assert "writers=" in (s.last_error or "")


def test_concurrent_inflight_saves_get_distinct_generations(
        tmp_path, monkeypatch):
    """queue_depth >= 2: the generation is claimed in the same
    critical section as the queue slot, so two in-flight saves can
    never commit duplicate generation numbers (the total-order
    invariant restore/gc depend on)."""
    s = ckpt.AsyncCheckpointer(str(tmp_path), kv=FakeKV(),
                               queue_depth=2)
    release = threading.Event()
    real_persist = s._persist

    def slow_persist(job):
        release.wait(20)
        real_persist(job)

    monkeypatch.setattr(s, "_persist", slow_persist)
    tree = {"w": np.zeros((4,), np.float32)}
    assert s.save(1, tree) and s.save(2, tree)  # both in flight
    release.set()
    assert s.wait(20)
    assert [g for g, _ in mf.committed(str(tmp_path))] == [1, 2]


def test_serve_from_trainloopstate_root(tmp_path):
    """The production wiring end to end: a TrainLoopState-written root
    (payload wrapped under 'trees') must load through
    from_checkpoint/load_params — the advertised serve-straight-from-
    a-live-training-job path."""
    import horovod_tpu as hvd
    from horovod_tpu.serve.engine import InferenceEngine

    st = hvd.elastic.TrainLoopState(
        params={"w": jnp.arange(4, dtype=jnp.float32)}, step=0,
        checkpointer=ckpt.AsyncCheckpointer(str(tmp_path), kv=FakeKV()))
    st.step = 2
    st.commit()
    assert st.checkpoint(block=True)
    got = ckpt.load_params(str(tmp_path))
    np.testing.assert_allclose(got["w"], np.arange(4))
    eng = InferenceEngine.from_checkpoint(
        str(tmp_path), lambda p, b: b + p["w"][1])
    np.testing.assert_allclose(np.asarray(eng.params["w"]),
                               np.arange(4))


def test_restore_signal_staleness_scales_with_heartbeat(monkeypatch):
    """HOROVOD_CKPT_RESTORE_HEARTBEAT=30 must not silently disable the
    grace it feeds: the staleness window scales to 3x the heartbeat
    (10s floor)."""
    assert resume.stale_seconds() == resume.STALE_SECONDS
    monkeypatch.setenv("HOROVOD_CKPT_RESTORE_HEARTBEAT", "30")
    assert resume.stale_seconds() == 90.0
    kv = FakeKV()
    kv.put("ckpt", "restoring", json.dumps(
        {"ts": time.time() - 60}).encode())  # 60s old, heartbeat 30
    assert resume.peer_restore_active(kv=kv)
    monkeypatch.setenv("HOROVOD_CKPT_RESTORE_HEARTBEAT", "1")
    assert not resume.peer_restore_active(kv=kv)


def test_snapshot_attributed_to_perfscope_checkpoint_phase(tmp_path):
    from horovod_tpu.profiler import perfscope as pscope

    assert "checkpoint" in pscope.PHASES
    scope = pscope.PerfScope(window=16)
    s = ckpt.AsyncCheckpointer(str(tmp_path), kv=FakeKV(), scope=scope)
    with scope.step():
        s.save(1, {"w": np.ones((4,), np.float32)})
    s.wait(20)
    summ = scope.summary()
    assert "checkpoint" in summ["phases_s"]
    assert summ["phases_s"]["checkpoint"] >= 0.0


# ------------------------------------------------------ TrainLoopState

def test_trainloopstate_resume_roundtrip(tmp_path):
    import horovod_tpu as hvd

    st = hvd.elastic.TrainLoopState(
        params={"w": jnp.zeros((4,), jnp.float32)}, step=0,
        checkpointer=ckpt.AsyncCheckpointer(str(tmp_path), kv=FakeKV()))
    for _ in range(3):
        st.params = {"w": st.params["w"] + 1.0}
        st.step += 1
        st.record_batch(4)
        st.commit()
    assert st.checkpoint(block=True)
    fresh = hvd.elastic.TrainLoopState(
        params={"w": jnp.zeros((4,), jnp.float32)}, step=0,
        checkpointer=ckpt.AsyncCheckpointer(str(tmp_path), kv=FakeKV()))
    assert fresh.maybe_resume() is True
    assert fresh.last_resume_source == "checkpoint"
    assert fresh.step == 3 and fresh.cursor == 12
    np.testing.assert_allclose(np.asarray(fresh.params["w"]), 3.0)


def test_trainloopstate_survivor_memory_wins(tmp_path):
    import horovod_tpu as hvd

    saver = ckpt.AsyncCheckpointer(str(tmp_path), kv=FakeKV())
    st = hvd.elastic.TrainLoopState(
        params={"w": jnp.zeros((2,), jnp.float32)}, step=0,
        checkpointer=saver)
    st.step = 5
    st.commit()
    st.checkpoint(block=True)
    st.step = 9  # memory moved past the newest commit (survivor)
    st.commit()
    assert st.maybe_resume() is False
    assert st.last_resume_source == "memory"
    assert st.step == 9  # untouched


def test_trainloopstate_checkpoint_saves_committed_not_live(tmp_path):
    import horovod_tpu as hvd

    saver = ckpt.AsyncCheckpointer(str(tmp_path), kv=FakeKV())
    st = hvd.elastic.TrainLoopState(
        params={"w": jnp.ones((2,), jnp.float32)}, step=4,
        checkpointer=saver)
    st.commit()
    st.step = 99  # uncommitted live mutation
    assert st.checkpoint(block=True)
    assert saver.last_committed[1] == 4  # the COMMITTED step


def test_trainloopstate_every_n_gate(tmp_path, monkeypatch):
    import horovod_tpu as hvd

    monkeypatch.setenv("HOROVOD_CKPT_EVERY", "3")
    st = hvd.elastic.TrainLoopState(
        params={"w": jnp.zeros((2,), jnp.float32)}, step=0,
        checkpointer=ckpt.AsyncCheckpointer(str(tmp_path), kv=FakeKV()))
    saved = []
    monkeypatch.setattr(st, "checkpoint", lambda **kw: saved.append(
        st.step) or True)
    for i in range(1, 8):
        st.step = i
        st.commit()
        st.maybe_checkpoint()
    assert saved == [3, 6]


def test_trainloopstate_resume_disabled_by_env(tmp_path, monkeypatch):
    import horovod_tpu as hvd

    saver = ckpt.AsyncCheckpointer(str(tmp_path), kv=FakeKV())
    saver.save(5, {"trees": {"params": {"w": np.ones((2,), np.float32)}}},
               objects={"step": 5}, block=True)
    monkeypatch.setenv("HOROVOD_CKPT_RESUME", "0")
    st = hvd.elastic.TrainLoopState(
        params={"w": jnp.zeros((2,), jnp.float32)}, step=0,
        checkpointer=saver)
    assert st.maybe_resume() is False and st.step == 0


def test_sharded_dataset_skip_to():
    from horovod_tpu.data.data_loader import ShardedDataset

    ds = ShardedDataset(list(range(40)), rank=0, size=2, batch_size=2,
                        shuffle=False)
    first = [b for b in ds]
    ds.skip_to(4)
    assert [b for b in ds] == first[2:]


# ------------------------------------------- restore signal / watchdog

def test_restore_signal_heartbeats_and_clears():
    kv = FakeKV()
    with resume.signal_restore(kv=kv):
        assert resume.peer_restore_active(kv=kv)
        raw = json.loads(kv.store["ckpt/restoring"].decode())
        assert raw["ts"] > 0
    # exit writes an explicitly-stale record
    assert not resume.peer_restore_active(kv=kv)
    # stale heartbeat (dead restorer) is ignored
    kv.put("ckpt", "restoring", json.dumps(
        {"ts": time.time() - 2 * resume.STALE_SECONDS}).encode())
    assert not resume.peer_restore_active(kv=kv)


def test_stall_watchdog_rearms_while_peer_restores(monkeypatch):
    """The ISSUE 15 satellite: a long restore must not eat the
    collective-wait budget — while the restore signal is fresh the
    deadline re-arms from restore time; once it clears, the (re-armed)
    deadline applies again."""
    from horovod_tpu.common.exceptions import HorovodInternalError
    from horovod_tpu.common.resilience import PyStallInspector
    from horovod_tpu.ops import collectives

    restoring = {"on": True}
    monkeypatch.setattr(resume, "peer_restore_active",
                        lambda kv=None: restoring["on"])
    wd = collectives.StallWatchdog(PyStallInspector(10.0, 0.0),
                                   warn_sec=0.05, shutdown_sec=0.6,
                                   poll_interval=0.01)
    release = threading.Event()

    def blocked():
        release.wait(10.0)
        return "done"

    # stop "restoring" well past the bare shutdown window, then let
    # the wait finish inside the re-armed window: no raise. (The deadline
    # re-arms at 0.6 and 1.2 s, so it stands at 1.8 s when the signal
    # clears at 1.3 and the wait ends at 1.4, 0.4 s before it. With a
    # window of 0.15 s, the signal cleared at 0.5 s and the end at 0.6 s,
    # the last deadline WAS 0.6 s, and a loaded run failed now and then.)
    threading.Timer(1.3, lambda: restoring.update(on=False)).start()
    threading.Timer(1.4, release.set).start()
    assert wd.guard("resume_bcast", blocked) == "done"

    # without the signal the same wait raises within the window
    restoring["on"] = False
    release.clear()
    wd2 = collectives.StallWatchdog(PyStallInspector(10.0, 0.0),
                                    warn_sec=0.05, shutdown_sec=0.15,
                                    poll_interval=0.01)
    with pytest.raises(HorovodInternalError, match="stalled past"):
        wd2.guard("resume_bcast", lambda: release.wait(10.0))
    release.set()


def test_stall_grace_is_bounded_by_grace_max(monkeypatch):
    """A wedged restorer whose signal never clears cannot hang the job:
    HOROVOD_CKPT_RESTORE_GRACE_MAX bounds the total extension."""
    from horovod_tpu.common.exceptions import HorovodInternalError
    from horovod_tpu.common.resilience import PyStallInspector
    from horovod_tpu.ops import collectives

    monkeypatch.setattr(resume, "peer_restore_active",
                        lambda kv=None: True)
    monkeypatch.setenv("HOROVOD_CKPT_RESTORE_GRACE_MAX", "0.2")
    wd = collectives.StallWatchdog(PyStallInspector(10.0, 0.0),
                                   warn_sec=0.05, shutdown_sec=0.1,
                                   poll_interval=0.01)
    release = threading.Event()
    t0 = time.monotonic()
    with pytest.raises(HorovodInternalError, match="stalled past"):
        wd.guard("resume_bcast", lambda: release.wait(10.0))
    assert time.monotonic() - t0 < 5.0
    release.set()


# ------------------------------------------------- checkpoint.py marker

def test_restore_params_requires_commit_marker(tmp_path, monkeypatch):
    from horovod_tpu import checkpoint as orbax_ckpt

    path = str(tmp_path / "ck")
    orbax_ckpt.save(path, {"params": {"w": jnp.ones((2,), jnp.float32)}})
    assert mf.has_done_marker(path)
    got = orbax_ckpt.restore_params(path)
    np.testing.assert_allclose(np.asarray(got["w"]), 1.0)
    # strip the marker: the same dir is now "a writer died mid-save"
    os.remove(path + mf.DONE_SUFFIX)
    with pytest.raises(CheckpointCorruptError, match="commit marker"):
        orbax_ckpt.restore_params(path)
    # legacy escape hatch
    monkeypatch.setenv("HOROVOD_CKPT_REQUIRE_MARKER", "0")
    got = orbax_ckpt.restore_params(path)
    np.testing.assert_allclose(np.asarray(got["w"]), 1.0)


def test_restore_params_types_partial_dir_errors(tmp_path):
    """A committed-looking but gutted orbax dir raises the typed
    CheckpointCorruptError, not raw orbax/KeyError noise."""
    from horovod_tpu import checkpoint as orbax_ckpt

    path = str(tmp_path / "ck")
    orbax_ckpt.save(path, {"params": {"w": jnp.ones((2,), jnp.float32)}})
    # gut the orbax payload but keep the marker (bit rot / partial copy)
    import shutil
    for name in os.listdir(path):
        full = os.path.join(path, name)
        shutil.rmtree(full) if os.path.isdir(full) else os.remove(full)
    with pytest.raises(CheckpointCorruptError):
        orbax_ckpt.restore_params(path)


def test_serve_engine_from_manifest_root(tmp_path):
    """serve/engine.from_checkpoint rides the new restore: pointing it
    at an AsyncCheckpointer ROOT loads the newest committed
    generation's params without touching the optimizer subtree."""
    from horovod_tpu.serve.engine import InferenceEngine

    tree = small_tree()
    s = ckpt.AsyncCheckpointer(str(tmp_path), kv=FakeKV())
    s.save(4, tree, block=True)
    eng = InferenceEngine.from_checkpoint(
        str(tmp_path), lambda p, b: b * p["w"][0])
    np.testing.assert_allclose(np.asarray(eng.params["w"]),
                               np.arange(8))
    out = eng.infer(np.ones((2, 1), np.float32))
    np.testing.assert_allclose(out, 0.0)  # w[0] == 0


# --------------------------------------------------- doctor [ckpt]

def _ckpt_dump(events, rank=None):
    return {"version": 1, "rank": rank, "size": None, "trigger": "test",
            "hostname": "h", "pid": 1, "round": 0, "rounds": {},
            "recorded": len(events), "dropped": 0,
            "collective_calls": 0, "wall_time": 0.0,
            "events": [[i, float(i), "ckpt", desc]
                       for i, desc in enumerate(events)]}


def test_doctor_ckpt_section_names_commit_restore_and_stale():
    from horovod_tpu.observability import doctor

    body = _ckpt_dump([
        "snapshot step=4 gen=3 bytes=100 seconds=0.010 rank=0 round=1",
        "persist step=4 gen=3 bytes=100 seconds=0.020 rank=0 round=1",
        "commit step=4 gen=3 rank=0 round=1",
        "restore step=4 gen=3 source=checkpoint seconds=0.45 rank=0 "
        "round=2",
        "restore step=4 gen=3 source=memory rank=1 round=2",
        # rank 2 restored an OLDER generation than the round committed
        "commit step=6 gen=4 rank=0 round=2",
        "restore step=4 gen=3 source=checkpoint seconds=0.30 rank=2 "
        "round=2",
        "skip step=5 skipped=3 (writer busy) rank=0 round=2",
        "quarantine step=2 gen=1 reason=CheckpointCorruptError rank=0 "
        "round=2",
        # rank 3: restore_latest emits BOTH a restore and its
        # restore-stale annotation — they must fold into ONE entry
        "restore step=2 gen=2 source=checkpoint seconds=0.10 rank=3 "
        "round=2",
        "restore-stale step=2 gen=2 latest=4 rank=3 round=2",
    ])
    rd = doctor.RankDump(body, "<mem>", tail_only=False)
    ck = doctor.analyze_ckpt([rd])
    assert ck is not None
    assert ck["rounds"]["1"]["generation"] == 3
    assert ck["rounds"]["2"]["generation"] == 4
    srcs = {(r["rank"], r["source"]) for r in ck["restores"]}
    assert (0, "checkpoint") in srcs and (1, "memory") in srcs
    # rank 3's restore + restore-stale pair folded into ONE entry
    assert len([r for r in ck["restores"] if r["rank"] == 3]) == 1
    stale_ranks = sorted(s["rank"] for s in ck["stale_restores"])
    assert stale_ranks == [2, 3]
    by_rank = {s["rank"]: s for s in ck["stale_restores"]}
    assert by_rank[2]["stale_vs"] == 4
    assert by_rank[3]["stale_vs"] == 4
    assert ck["skipped"]["0"] == 3
    assert len(ck["quarantines"]) == 1
    report = doctor.merge([rd])
    text = doctor.render(report)
    assert "[ckpt]" in text
    assert "last committed generation 4" in text, text
    assert "restored generation 3 (step 4) from checkpoint" in text
    assert "STALE RESTORE rank 2" in text, text
    assert "QUARANTINED step 2" in text
    assert "3 save(s) skipped by back-pressure" in text
    # --json path stays serializable
    json.dumps(report)


def test_doctor_ckpt_section_absent_without_events():
    from horovod_tpu.observability import doctor

    body = _ckpt_dump([])
    body["events"] = [[0, 0.0, "elastic", "round 1"]]
    rd = doctor.RankDump(body, "<mem>", tail_only=False)
    assert doctor.analyze_ckpt([rd]) is None
    assert "[ckpt]" not in doctor.render(doctor.merge([rd]))


# -------------------------------------------------- optim spec helper

def test_opt_state_specs_inherit_param_shardings():
    import optax

    from horovod_tpu.optim.optimizer import opt_state_specs

    params = {"emb": jnp.zeros((32, 8)), "b": jnp.zeros((3,))}
    pspecs = {"emb": P("tp", None), "b": P()}
    opt = optax.adam(1e-3)
    st = opt.init(params)
    specs = opt_state_specs(st, params, pspecs)
    mu = st[0].mu
    mu_specs = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(lambda *_: 0, mu))  # structure probe
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    by_path = {jax.tree_util.keystr(kp): v for kp, v in flat}
    emb_specs = [v for k, v in by_path.items() if "'emb'" in k]
    assert emb_specs and all(s == P("tp", None) for s in emb_specs)
    # the scalar count is replicated
    count_specs = [v for k, v in by_path.items() if "count" in k]
    assert all(s == P() for s in count_specs)
