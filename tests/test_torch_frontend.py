"""Torch frontend tests (reference analog: test/parallel/test_torch.py —
collective semantics through the torch API surface)."""

import numpy as np
import pytest



@pytest.fixture(scope="module", autouse=True)
def _torch():
    """`torch` as this module's global, imported when the first test here
    runs and not when the file is collected: every worker collects every
    file, one runs this one. Without it the file's tests are skipped."""
    globals()["torch"] = pytest.importorskip("torch")


def test_torch_allreduce_roundtrip(hvd):
    import horovod_tpu.frontends.torch as thvd
    x = torch.arange(6, dtype=torch.float32).reshape(2, 3)
    y = thvd.allreduce(x)  # average of identical copies == identity
    assert isinstance(y, torch.Tensor)
    np.testing.assert_allclose(y.numpy(), x.numpy())


def test_torch_broadcast_inplace(hvd):
    import horovod_tpu.frontends.torch as thvd
    x = torch.ones(4) * (thvd.rank() + 3)
    thvd.broadcast_(x, root_rank=0)
    np.testing.assert_allclose(x.numpy(), 3.0)


def test_torch_distributed_optimizer_steps(hvd):
    import horovod_tpu.frontends.torch as thvd
    model = torch.nn.Linear(4, 2)
    opt = thvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.1))
    thvd.broadcast_parameters(model.state_dict(), root_rank=0)
    x = torch.randn(8, 4)
    y = torch.randn(8, 2)
    before = model.weight.detach().clone()
    loss = torch.nn.functional.mse_loss(model(x), y)
    loss.backward()
    opt.step()
    assert not torch.allclose(before, model.weight)


def test_torch_broadcast_optimizer_state(hvd):
    import horovod_tpu.frontends.torch as thvd
    model = torch.nn.Linear(3, 3)
    opt = torch.optim.Adam(model.parameters(), lr=1e-3)
    loss = model(torch.randn(2, 3)).sum()
    loss.backward()
    opt.step()
    thvd.broadcast_optimizer_state(opt, root_rank=0)
    assert opt.state_dict()["state"]


def test_torch_async_handles(hvd):
    """poll/synchronize with REAL in-flight handles (reference:
    mpi_ops.py allreduce_async_ + handle_manager)."""
    import horovod_tpu.frontends.torch as thvd
    x = torch.arange(4, dtype=torch.float32)
    h = thvd.allreduce_async(x, op=thvd.Sum)
    out = thvd.synchronize(h)
    assert thvd.poll(h)  # completed after synchronize
    np.testing.assert_allclose(out.numpy(), x.numpy() * thvd.size())

    # In-place variant copies back into the original tensor.
    y = torch.ones(3)
    h2 = thvd.allreduce_async_(y, op=thvd.Sum)
    got = thvd.synchronize(h2)
    assert got is y
    np.testing.assert_allclose(y.numpy(), thvd.size())

    # Submission order is preserved (single-thread executor): a burst of
    # handles completes in order with correct values.
    handles = [thvd.allreduce_async(torch.full((2,), float(i)), op=thvd.Sum)
               for i in range(5)]
    for i, h in enumerate(handles):
        np.testing.assert_allclose(thvd.synchronize(h).numpy(),
                                   i * thvd.size())


def test_torch_fp16_compression(hvd):
    """compression=Compression.fp16 must actually compress and round-trip
    (reference: torch/optimizer.py applies compress/decompress around the
    collective — previously silently ignored here)."""
    import horovod_tpu.frontends.torch as thvd
    t = torch.randn(16)
    comp, ctx = thvd.Compression.fp16.compress(t)
    assert comp.dtype == torch.float16
    back = thvd.Compression.fp16.decompress(comp, ctx)
    assert back.dtype == torch.float32

    model = torch.nn.Linear(4, 2)
    opt = thvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.0),
        compression=thvd.Compression.fp16)
    model(torch.randn(8, 4)).sum().backward()
    grads_before = [p.grad.detach().clone()
                    for g in opt.opt.param_groups for p in g["params"]]
    opt.step()
    grads_after = [p.grad for g in opt.opt.param_groups
                   for p in g["params"]]
    for b, a in zip(grads_before, grads_after):
        assert a.dtype == torch.float32  # decompressed back
        np.testing.assert_allclose(a.numpy(), b.numpy(),
                                   rtol=1e-2, atol=1e-2)  # fp16 tolerance


def test_torch_gradient_predivide(hvd):
    import horovod_tpu.frontends.torch as thvd
    # Average-only, as the reference enforces.
    with pytest.raises(ValueError):
        thvd.DistributedOptimizer(
            torch.optim.SGD(torch.nn.Linear(2, 2).parameters(), lr=0.1),
            op=thvd.Sum, gradient_predivide_factor=2.0)
    # With Average the pre/post split is mathematically a no-op.
    model = torch.nn.Linear(4, 2)
    opt = thvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.0),
        gradient_predivide_factor=4.0)
    model(torch.ones(2, 4)).sum().backward()
    expect = [p.grad.detach().clone()
              for g in opt.opt.param_groups for p in g["params"]]
    opt.step()
    got = [p.grad for g in opt.opt.param_groups for p in g["params"]]
    for e, a in zip(expect, got):  # identical ranks → mean == local grad
        np.testing.assert_allclose(a.numpy(), e.numpy(), rtol=1e-5)


def test_torch_sparse_allreduce(hvd):
    """Sparse gradients ride allgather+coalesce (reference:
    torch/mpi_ops.py sparse path)."""
    import horovod_tpu.frontends.torch as thvd
    i = torch.tensor([[0, 2], [1, 0]])
    v = torch.tensor([3.0, 4.0])
    sp = torch.sparse_coo_tensor(i, v, (3, 2))
    out = thvd.allreduce(sp, op=thvd.Average)
    assert out.is_sparse
    np.testing.assert_allclose(out.to_dense().numpy(),
                               sp.to_dense().numpy(), rtol=1e-6)

    # Through the optimizer: embedding-style sparse grad.
    emb = torch.nn.Embedding(5, 3, sparse=True)
    opt = thvd.DistributedOptimizer(
        torch.optim.SGD(emb.parameters(), lr=0.0))
    emb(torch.tensor([1, 3])).sum().backward()
    assert emb.weight.grad.is_sparse
    dense_before = emb.weight.grad.to_dense().clone()
    opt.step()
    np.testing.assert_allclose(emb.weight.grad.to_dense().numpy(),
                               dense_before.numpy(), rtol=1e-6)

    # sparse_as_dense densifies before the dense fused path.
    emb2 = torch.nn.Embedding(4, 2, sparse=True)
    opt2 = thvd.DistributedOptimizer(
        torch.optim.SGD(emb2.parameters(), lr=0.0), sparse_as_dense=True)
    emb2(torch.tensor([0, 2])).sum().backward()
    opt2.step()
    assert not emb2.weight.grad.is_sparse


def test_torch_duplicate_name_error(hvd):
    """Overlapping async ops sharing a name raise DuplicateNameError
    (reference: DUPLICATE_NAME_ERROR, common/tensor_queue.cc)."""
    import horovod_tpu.frontends.torch as thvd
    from horovod_tpu.common.exceptions import DuplicateNameError

    h1 = thvd.allreduce_async(torch.ones(1024), name="grad0")
    try:
        with pytest.raises(DuplicateNameError):
            thvd.allreduce_async(torch.ones(1024), name="grad0")
    finally:
        thvd.synchronize(h1)
    # After synchronize the name is free IMMEDIATELY (release happens
    # before the future resolves) — the canonical per-step reuse pattern.
    for _ in range(5):
        h = thvd.allreduce_async(torch.ones(4), name="grad0")
        thvd.synchronize(h)


def test_torch_optimizer_hook_overlap(hvd):
    """named_parameters enables per-parameter backward hooks firing async
    allreduces as gradients materialize (reference: torch/optimizer.py
    _register_hooks :131-173); step() waits and applies. Results must
    match the step-time fused path exactly."""
    import horovod_tpu.frontends.torch as thvd

    torch.manual_seed(0)
    model_a = torch.nn.Sequential(torch.nn.Linear(4, 8), torch.nn.ReLU(),
                                  torch.nn.Linear(8, 2))
    model_b = torch.nn.Sequential(torch.nn.Linear(4, 8), torch.nn.ReLU(),
                                  torch.nn.Linear(8, 2))
    model_b.load_state_dict(model_a.state_dict())

    opt_hook = thvd.DistributedOptimizer(
        torch.optim.SGD(model_a.parameters(), lr=0.1),
        named_parameters=model_a.named_parameters())
    opt_fused = thvd.DistributedOptimizer(
        torch.optim.SGD(model_b.parameters(), lr=0.1))

    assert opt_hook._hooked, "hooks were not registered"
    x = torch.randn(16, 4)
    y = torch.randn(16, 2)
    for _ in range(3):
        for model, opt in ((model_a, opt_hook), (model_b, opt_fused)):
            opt.zero_grad()
            torch.nn.functional.mse_loss(model(x), y).backward()
            opt.step()
        assert not opt_hook._handles  # all drained by step()
    for pa, pb in zip(model_a.parameters(), model_b.parameters()):
        np.testing.assert_allclose(pa.detach().numpy(),
                                   pb.detach().numpy(), rtol=1e-5,
                                   atol=1e-6)


def test_torch_optimizer_hook_with_compression(hvd):
    import horovod_tpu.frontends.torch as thvd
    model = torch.nn.Linear(4, 2)
    opt = thvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.0),
        named_parameters=model.named_parameters(),
        compression=thvd.Compression.fp16,
        gradient_predivide_factor=2.0)
    model(torch.ones(2, 4)).sum().backward()
    before = [p.grad.detach().clone() for p in model.parameters()]
    opt.step()
    for p, b in zip(model.parameters(), before):
        assert p.grad.dtype == torch.float32
        np.testing.assert_allclose(p.grad.numpy(), b.numpy(),
                                   rtol=1e-2, atol=1e-2)


def test_torch_backward_passes_per_step_defers_apply(hvd):
    """Accumulation passes must NOT apply raw local gradients (they would
    diverge the ranks); the update lands only on the Nth step with the
    reduced accumulated gradient."""
    import horovod_tpu.frontends.torch as thvd
    p = torch.nn.Parameter(torch.zeros(2))
    opt = thvd.DistributedOptimizer(
        torch.optim.SGD([p], lr=1.0), backward_passes_per_step=2)

    (p * 1.0).sum().backward()
    assert opt.step() is None                 # accumulation pass: no apply
    np.testing.assert_allclose(p.detach().numpy(), 0.0)

    (p * 2.0).sum().backward()                # grads accumulate: 1 + 2
    opt.step()
    np.testing.assert_allclose(p.detach().numpy(), -3.0, rtol=1e-6)


def test_optimizer_explicit_groups_plan(hvd):
    """`groups=[[...]]` pins co-fused tensors into one engine call each;
    `groups=N` splits into N calls (VERDICT r2 #6; reference:
    torch/optimizer.py:88-165)."""
    import horovod_tpu.frontends.torch as thvd

    model = torch.nn.Sequential(
        torch.nn.Linear(4, 8), torch.nn.Linear(8, 8), torch.nn.Linear(8, 2))
    params = [p for p in model.parameters()]

    def run_step(opt):
        calls = []
        orig = thvd.grouped_allreduce

        def spy(tensors, **kw):
            calls.append(len(tensors))
            return orig(tensors, **kw)

        thvd.grouped_allreduce = spy
        try:
            opt.zero_grad()
            loss = model(torch.ones(3, 4)).sum()
            loss.backward()
            opt.step()
        finally:
            thvd.grouped_allreduce = orig
        return calls

    # explicit list groups: [w0,b0] together, [w1] alone, rest defaulted
    opt = thvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.01),
        groups=[[params[0], params[1]], [params[2]]])
    calls = run_step(opt)
    # 3 calls: group0 (2 tensors), group1 (1), remainder (3)
    assert calls == [2, 1, 3], calls

    # groups=N: N calls covering all 6 tensors
    opt = thvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.01), groups=2)
    calls = run_step(opt)
    assert len(calls) == 2 and sum(calls) == 6, calls

    # groups=0 behaves like default single fused call
    opt = thvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.01), groups=0)
    calls = run_step(opt)
    assert calls == [6], calls

    with pytest.raises(ValueError, match="groups"):
        thvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.01), groups=-1)
    with pytest.raises(ValueError, match="groups"):
        thvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.01),
            groups=[params[0]])  # not a list of lists


def test_optimizer_groups_numerics(hvd):
    """Grouped plans must not change results: reduced grads equal the
    ungrouped reduction (identical ranks -> local grads)."""
    import horovod_tpu.frontends.torch as thvd

    torch.manual_seed(7)
    model = torch.nn.Linear(5, 3)
    x = torch.randn(4, 5)

    def grads_with(group_fn):
        m = torch.nn.Linear(5, 3)
        m.load_state_dict(model.state_dict())
        opt = thvd.DistributedOptimizer(
            torch.optim.SGD(m.parameters(), lr=0.0),
            groups=group_fn(m) if group_fn else None)
        opt.zero_grad()
        m(x).sum().backward()
        opt.step()
        return [p.grad.clone() for p in m.parameters()]

    base = grads_with(None)
    for group_fn in (lambda m: 2,
                     lambda m: [[next(iter(m.parameters()))]]):
        got = grads_with(group_fn)
        for a, b in zip(base, got):
            torch.testing.assert_close(a, b)


def test_sparse_allreduce_async_api(hvd):
    """Reference name parity: torch/mpi_ops.py:567 sparse_allreduce_async
    returns a handle; synchronize yields the reduced sparse tensor."""
    import horovod_tpu.frontends.torch as thvd

    i = torch.tensor([[0, 2]])
    v = torch.tensor([[1.0, 2.0], [3.0, 4.0]])
    sp = torch.sparse_coo_tensor(i, v, (3, 2))
    h = thvd.sparse_allreduce_async(sp, name="s", op=thvd.Sum)
    assert thvd.poll(h)
    out = thvd.synchronize(h)
    assert out.is_sparse
    k = thvd.size()
    torch.testing.assert_close(out.to_dense()[0], torch.tensor([1.0, 2.0]) * k)


def test_torch_bfloat16_roundtrip(hvd):
    """bf16 tensors cross the boundary via DLPack (numpy has no bfloat16 —
    the numpy bridge raises on them), preserving dtype end to end."""
    import horovod_tpu.frontends.torch as thvd

    # shape (5,): avoid the emulated-world-size leading dim, which the
    # engine interprets as an already-stacked per-rank input
    t = torch.arange(5, dtype=torch.float32).to(torch.bfloat16)
    out = thvd.allreduce(t, op=thvd.Sum, name="bf16rt")
    assert out.dtype == torch.bfloat16
    assert out.shape == t.shape
    torch.testing.assert_close(
        out.float(), t.float() * thvd.size(), rtol=0.02, atol=0.02)


def test_torch_dlpack_zero_copy_ingest(hvd):
    """The torch→engine bridge hands over a DLPack view, not a copy, for
    contiguous CPU tensors (the migration path's per-step boundary cost)."""
    from horovod_tpu.frontends.torch import _to_np

    t = torch.arange(6, dtype=torch.float32)
    a = _to_np(t)
    t[0] = 42.0  # shared memory: the view sees the write
    assert float(np.asarray(a)[0]) == 42.0


def test_torch_min_max_product_ops(hvd):
    """Reference exports hvd.Min/Max/Product (torch/mpi_ops.py:80-82) and
    reduces with them; single-controller semantics: every emulated rank
    contributes the same tensor, so min=max=input and product=x^size."""
    import horovod_tpu.frontends.torch as thvd

    t = torch.tensor([1.0, 2.0, 3.0])
    out_min = thvd.allreduce(t, op=thvd.Min, name="mn")
    out_max = thvd.allreduce(t, op=thvd.Max, name="mx")
    out_prod = thvd.allreduce(t, op=thvd.Product, name="pr")
    torch.testing.assert_close(out_min, t)
    torch.testing.assert_close(out_max, t)
    torch.testing.assert_close(out_prod, t ** thvd.size())


def test_torch_grouped_and_async_variants(hvd):
    """Round-4 API sweep vs reference torch surface: grouped allgather/
    reducescatter (+async), grouped in-place, alltoall_async,
    reducescatter_async (reference: torch/mpi_ops.py grouped_* and
    *_async families)."""
    import horovod_tpu.frontends.torch as thvd

    k = thvd.size()
    ts = [torch.arange(4, dtype=torch.float32),
          torch.ones(2, 3)]

    # grouped in-place: tensors mutate to the reduced values
    clones = [t.clone() for t in ts]
    got = thvd.grouped_allreduce_(clones, op=thvd.Sum)
    assert got is clones
    torch.testing.assert_close(clones[0], ts[0] * k)

    # grouped allgather: first axis grows by k
    outs = thvd.grouped_allgather([torch.ones(2, 3), torch.zeros(1, 5)])
    assert outs[0].shape == (2 * k, 3) and outs[1].shape == (k, 5)

    # grouped reducescatter: rows divided across ranks (shapes chosen to
    # avoid the leading-dim==world-size stacked-input interpretation)
    rs_in = [torch.ones(k * 2, 3), torch.ones(k * 3, 4)]
    outs = thvd.grouped_reducescatter(rs_in, op=thvd.Sum)
    assert outs[0].shape == (2, 3) and outs[1].shape == (3, 4)
    torch.testing.assert_close(outs[0], torch.full((2, 3), float(k)))

    # async grouped + poll/synchronize
    h = thvd.grouped_allreduce_async(ts, op=thvd.Sum, name="ga0")
    outs = thvd.synchronize(h)
    assert thvd.poll(h)
    torch.testing.assert_close(outs[0], ts[0] * k)

    h2 = thvd.grouped_allgather_async([torch.ones(1, 2)])
    assert thvd.synchronize(h2)[0].shape == (k, 2)

    h3 = thvd.grouped_reducescatter_async([torch.ones(k * 2, 2)],
                                          op=thvd.Sum)
    torch.testing.assert_close(thvd.synchronize(h3)[0],
                               torch.full((2, 2), float(k)))

    # async in-place grouped
    ips = [torch.ones(3)]
    h4 = thvd.grouped_allreduce_async_(ips, op=thvd.Sum)
    got4 = thvd.synchronize(h4)
    assert all(a is b for a, b in zip(got4, ips))  # same tensor objects
    torch.testing.assert_close(ips[0], torch.full((3,), float(k)))

    # reducescatter_async
    h5 = thvd.reducescatter_async(torch.ones(k * 2, 2), op=thvd.Sum)
    torch.testing.assert_close(thvd.synchronize(h5),
                               torch.full((2, 2), float(k)))

    # alltoall_async returns (tensor, received_splits)
    h6 = thvd.alltoall_async(torch.arange(k, dtype=torch.float32))
    out, recv = thvd.synchronize(h6)
    assert recv.dtype == torch.int64 and recv.shape == (k,)
