"""Frontend elastic state objects (reference: torch/elastic/state.py
TorchState + sampler.py ElasticSampler; tensorflow/elastic.py)."""

import numpy as np
import pytest



@pytest.fixture(scope="module", autouse=True)
def _torch():
    """`torch` as this module's global, imported when the first test here
    runs and not when the file is collected (`tests/test_torch_frontend.py`
    says why). Without it the file's tests are skipped."""
    globals()["torch"] = pytest.importorskip("torch")


def test_torch_state_commit_restore(hvd):
    import horovod_tpu.frontends.torch as thvd
    model = torch.nn.Linear(3, 2)
    opt = torch.optim.SGD(model.parameters(), lr=0.1)
    state = thvd.elastic.TorchState(model=model, optimizer=opt, epoch=0,
                                    batch=0)
    w0 = model.weight.detach().clone()
    state.commit()

    # Mutate weights + bookkeeping, then roll back.
    with torch.no_grad():
        model.weight += 1.0
    state.epoch = 5
    state.restore()
    assert torch.allclose(model.weight, w0)
    assert state.epoch == 0

    # Commit after a real step persists the new weights.
    model(torch.randn(4, 3)).sum().backward()
    opt.step()
    w1 = model.weight.detach().clone()
    state.commit()
    with torch.no_grad():
        model.weight.zero_()
    state.restore()
    assert torch.allclose(model.weight, w1)


def test_torch_state_sync(hvd):
    import horovod_tpu.frontends.torch as thvd
    model = torch.nn.Linear(2, 2)
    state = thvd.elastic.TorchState(model=model, epoch=3)
    state.sync()  # identical ranks: broadcast is an identity, must not die
    assert state.epoch == 3


def test_elastic_sampler_reshard_and_resume(hvd):
    import horovod_tpu.frontends.torch as thvd
    k = thvd.size()
    n = 10 * k
    data = list(range(n))
    s = thvd.elastic.ElasticSampler(data, shuffle=False)
    per_rank = n // k
    assert len(s) == per_rank  # sharded over the world
    # This in-process "rank" is rank 0: its shard is the first slice.
    assert s.indices == list(range(per_rank))

    s.record_batch(0, 4)
    assert s.processed_indices == [0, 1, 2, 3]
    sd = s.state_dict()

    s2 = thvd.elastic.ElasticSampler(data, shuffle=False)
    s2.load_state_dict(sd)
    # Resumed sampler shards only the REMAINING n-4 indices.
    assert len(s2) == (n - 4) // k
    assert not set(s2.indices) & {0, 1, 2, 3}
    s2.sync()  # allgather union across (identical) ranks
    assert not set(s2.indices) & {0, 1, 2, 3}

    s2.set_epoch(1)  # new epoch: everything back in play
    assert len(s2) == per_rank


def test_torch_state_setattr_rebinds_handler(hvd):
    """Reference parity (torch/elastic/state.py:66-69): reassigning a
    handler-managed attribute (state.sampler = new_sampler) must rebind
    the registered handler to the NEW object — commit/restore/sync on the
    stale object would silently diverge from what training uses."""
    import horovod_tpu.frontends.torch_elastic as te

    old = te.ElasticSampler(list(range(12)), shuffle=False)
    state = te.TorchState(model=torch.nn.Linear(2, 2), sampler=old)
    assert state._handlers["sampler"].value is old

    new = te.ElasticSampler(list(range(24)), shuffle=False)
    state.sampler = new
    assert state.sampler is new
    assert state._handlers["sampler"].value is new  # handler rebound

    # set_value snapshots on rebind: restore() rolls the NEW object back
    # to its state at assignment time.
    new.record_batch(0, 4)
    assert new.processed_indices
    state.restore()
    assert new.processed_indices == []

    # commit/restore after rebinding track the new object, not the old
    # (batch size 1: shard length is world-size dependent).
    first = new.indices[0]
    new.record_batch(0, 1)
    state.commit()
    new.record_batch(1, 1)
    assert len(new.processed_indices) == 2
    state.restore()
    assert new.processed_indices == [first]

    # model/optimizer ride the same handler mechanism: swapping the module
    # mid-training must rebind + snapshot, so restore() rolls back the NEW
    # module (not load the old module's state dict into it).
    new_model = torch.nn.Linear(4, 4)
    state.model = new_model
    assert state._handlers["model"].value is new_model
    w0 = new_model.weight.detach().clone()
    with torch.no_grad():
        new_model.weight.add_(1.0)
    state.restore()
    assert torch.allclose(new_model.weight, w0)

    # A model assigned AFTER construction (none at init) becomes managed
    # too — the pre-handler code read self.model live and this must not
    # regress into a silently-untracked module.
    late_state = te.TorchState(epoch=0)
    late = torch.nn.Linear(2, 2)
    late_state.model = late
    assert "model" in late_state._handlers
    lw0 = late.weight.detach().clone()
    late_state.commit()
    with torch.no_grad():
        late.weight.add_(1.0)
    late_state.restore()
    assert torch.allclose(late.weight, lw0)


def test_tf_keras_state_commit_restore(hvd):
    tf = pytest.importorskip("tensorflow")
    import keras

    import horovod_tpu.frontends.tensorflow as tfvd
    model = keras.Sequential([keras.layers.Dense(2, input_shape=(3,))])
    state = tfvd.elastic.TfKerasState(model=model, epoch=0)
    w0 = [v.numpy().copy() for v in model.variables]
    state.commit()
    for v in model.variables:
        v.assign(v + 1.0)
    state.epoch = 2
    state.restore()
    for v, w in zip(model.variables, w0):
        np.testing.assert_allclose(v.numpy(), w)
    assert state.epoch == 0
    state.sync()  # identity broadcast across identical ranks


def test_torch_state_checkpoint_resume_roundtrip(hvd, tmp_path):
    """ISSUE 16 satellite: TorchState rides CheckpointableState — a
    committed snapshot persists through ckpt.AsyncCheckpointer (torch
    tensors through the pickled object channel) and a freshly-booted
    state at step 0 adopts it in sync()'s resume probe."""
    import horovod_tpu.frontends.torch_elastic as te

    model = torch.nn.Linear(3, 2)
    opt = torch.optim.SGD(model.parameters(), lr=0.1)
    state = te.TorchState(model=model, optimizer=opt, step=0, epoch=0,
                          root=str(tmp_path))
    assert state.checkpointer is not None
    model(torch.randn(4, 3)).sum().backward()
    opt.step()
    state.step, state.epoch = 7, 1
    state.commit()
    assert state.checkpoint(block=True)
    want = {k: v.clone() for k, v in model.state_dict().items()}

    # "New process": same root, fresh weights, step 0 -> disk is ahead.
    model2 = torch.nn.Linear(3, 2)
    opt2 = torch.optim.SGD(model2.parameters(), lr=0.1)
    state2 = te.TorchState(model=model2, optimizer=opt2, step=0, epoch=0,
                           root=str(tmp_path))
    state2.sync()  # resume probe + identity broadcast
    assert state2.last_resume_source == "checkpoint"
    assert (state2.step, state2.epoch) == (7, 1)
    for k, v in want.items():
        assert torch.allclose(model2.state_dict()[k], v), k

    # Survivor: memory at least as fresh as disk -> memory wins.
    state2.step = 9
    state2.commit()
    assert not state2.maybe_resume()
    assert state2.last_resume_source == "memory"
    assert state2.step == 9


def test_torch_state_maybe_checkpoint_cadence(hvd, tmp_path,
                                              monkeypatch):
    """HOROVOD_CKPT_DIR/_EVERY drive the frontend states exactly like
    TrainLoopState: maybe_checkpoint() fires only on the cadence."""
    monkeypatch.setenv("HOROVOD_CKPT_DIR", str(tmp_path))
    monkeypatch.setenv("HOROVOD_CKPT_EVERY", "4")
    import horovod_tpu.frontends.torch_elastic as te
    state = te.TorchState(model=torch.nn.Linear(2, 2), step=0)
    assert state.every_n == 4
    state.step = 3
    state.commit()
    assert not state.maybe_checkpoint()
    state.step = 4
    state.commit()
    assert state.maybe_checkpoint()
    assert state.checkpointer.wait()


def test_tf_keras_state_checkpoint_resume_roundtrip(hvd, tmp_path):
    """TfKerasState persists its committed numpy variable snapshots as
    the checkpoint's array tree; duck-typed variables keep the test
    independent of a real TensorFlow install."""
    import horovod_tpu.frontends.tensorflow_elastic as tfe

    class FakeVar:
        def __init__(self, a):
            self.a = np.asarray(a, dtype=np.float32)

        def numpy(self):
            return self.a

        def assign(self, v):
            self.a = np.asarray(v, dtype=np.float32).copy()

    class FakeModel:
        def __init__(self):
            self.variables = [FakeVar([1.0, 2.0]), FakeVar([[3.0]])]

    m = FakeModel()
    state = tfe.TfKerasState(model=m, step=0, root=str(tmp_path))
    m.variables[0].assign([7.0, 8.0])
    state.step = 4
    state.save()
    assert state.checkpoint(block=True)

    m2 = FakeModel()
    state2 = tfe.TfKerasState(model=m2, step=0, root=str(tmp_path))
    assert state2.maybe_resume()
    assert state2.last_resume_source == "checkpoint"
    assert state2.step == 4
    np.testing.assert_allclose(m2.variables[0].numpy(), [7.0, 8.0])
    np.testing.assert_allclose(m2.variables[1].numpy(), [[3.0]])


def test_torch_state_handler_registry(hvd):
    """Reference parity (torch/elastic/state.py:71-160): extra TorchState
    kwargs resolve through the handler registry — an extra nn.Module gets
    a ModelStateHandler, an ElasticSampler a SamplerStateHandler; custom
    types can be registered."""
    import torch

    import horovod_tpu.frontends.torch_elastic as te

    aux = torch.nn.Linear(2, 2)
    sampler = te.ElasticSampler(list(range(12)), shuffle=False)
    state = te.TorchState(model=torch.nn.Linear(3, 3),
                          optimizer=torch.optim.SGD(aux.parameters(),
                                                    lr=0.1),
                          aux_model=aux, sampler=sampler, epoch=5)
    assert isinstance(state._handlers["aux_model"], te.ModelStateHandler)
    assert isinstance(state._handlers["sampler"], te.SamplerStateHandler)
    assert state.epoch == 5  # plain value -> ObjectState

    # commit/restore round-trips the handler-managed aux module
    state.commit()
    with torch.no_grad():
        aux.weight.add_(1.0)
    changed = aux.weight.detach().clone()
    state.restore()
    assert not torch.allclose(changed, aux.weight)

    # custom registry entry wins for custom types
    class Thing:
        def __init__(self):
            self.v = 0

    class ThingHandler(te.StateHandler):
        def save(self):
            self._saved = self.value.v

        def restore(self):
            self.value.v = self._saved

        def sync(self):
            pass

    te.set_handler_registry(te.get_handler_registry()
                            + [(Thing, ThingHandler)])
    try:
        thing = Thing()
        st2 = te.TorchState(thing=thing)
        st2.commit()
        thing.v = 42
        st2.restore()
        assert thing.v == 0
    finally:
        te.set_handler_registry(te._default_registry())
