"""TensorFlow frontend tests (reference analog: test/parallel/
test_tensorflow.py — collective semantics through the TF API surface)."""

import numpy as np
import pytest



@pytest.fixture(scope="module", autouse=True)
def _tensorflow():
    """TensorFlow as this module's global `tf`, imported when the first test
    here runs and not when the file is collected: every worker collects
    every file, one runs this one. Without it the file's tests are skipped."""
    globals()["tf"] = pytest.importorskip("tensorflow")


def test_tf_allreduce_roundtrip(hvd):
    import horovod_tpu.frontends.tensorflow as tfvd
    x = tf.reshape(tf.range(6, dtype=tf.float32), (2, 3))
    y = tfvd.allreduce(x)  # average of identical copies == identity
    assert isinstance(y, tf.Tensor)
    np.testing.assert_allclose(y.numpy(), x.numpy())
    s = tfvd.allreduce(x, op=tfvd.Sum)
    np.testing.assert_allclose(s.numpy(), x.numpy() * tfvd.size())


def test_tf_broadcast_variables(hvd):
    import horovod_tpu.frontends.tensorflow as tfvd
    v = tf.Variable(tf.ones((3,)) * (tfvd.rank() + 7))
    tfvd.broadcast_variables([v], root_rank=0)
    np.testing.assert_allclose(v.numpy(), 7.0)


def test_tf_allgather_alltoall(hvd):
    import horovod_tpu.frontends.tensorflow as tfvd
    k = tfvd.size()
    g = tfvd.allgather(tf.ones((2, 3)))
    assert g.shape == (2 * k, 3)
    out, recv = tfvd.alltoall(tf.ones((2 * k, 3)))
    assert out.shape == (2 * k, 3)
    np.testing.assert_array_equal(recv.numpy(), np.full(k, 2))


def test_tf_distributed_gradient_tape(hvd):
    import horovod_tpu.frontends.tensorflow as tfvd
    w = tf.Variable([[2.0]])
    with tf.GradientTape() as tape:
        loss = tf.reduce_sum(w * 3.0)
    dtape = tfvd.DistributedGradientTape(tape)
    (grad,) = dtape.gradient(loss, [w])
    # identical ranks → average == local gradient
    np.testing.assert_allclose(grad.numpy(), [[3.0]], rtol=1e-6)


def test_tf_tape_compression_and_predivide(hvd):
    import horovod_tpu.frontends.tensorflow as tfvd
    with pytest.raises(ValueError):
        tfvd.DistributedGradientTape(tf.GradientTape(), op=tfvd.Sum,
                                     gradient_predivide_factor=2.0)
    w = tf.Variable(tf.ones((4, 4)))
    with tf.GradientTape() as tape:
        loss = tf.reduce_sum(w * 0.5)
    dtape = tfvd.DistributedGradientTape(
        tape, compression=tfvd.Compression.fp16,
        gradient_predivide_factor=4.0)
    (grad,) = dtape.gradient(loss, [w])
    assert grad.dtype == tf.float32  # decompressed back
    np.testing.assert_allclose(grad.numpy(), 0.5, rtol=1e-2)


def test_tf_distributed_optimizer(hvd):
    import keras

    import horovod_tpu.frontends.tensorflow as tfvd
    v = tf.Variable(1.0)
    opt = tfvd.DistributedOptimizer(keras.optimizers.SGD(learning_rate=0.1))
    opt.apply_gradients([(tf.constant(2.0), v)])
    # mean grad over identical ranks == 2.0 → v = 1 - 0.1*2
    np.testing.assert_allclose(v.numpy(), 0.8, rtol=1e-6)


def test_tf_optimizer_local_aggregation(hvd):
    import keras

    import horovod_tpu.frontends.tensorflow as tfvd
    v = tf.Variable(0.0)
    opt = tfvd.DistributedOptimizer(keras.optimizers.SGD(learning_rate=1.0),
                                    backward_passes_per_step=2)
    opt.apply_gradients([(tf.constant(1.0), v)])
    np.testing.assert_allclose(v.numpy(), 0.0)  # first pass only accumulates
    opt.apply_gradients([(tf.constant(3.0), v)])
    # second pass applies the local mean (1+3)/2 = 2
    np.testing.assert_allclose(v.numpy(), -2.0, rtol=1e-6)


def test_tf_function_allreduce(hvd):
    """Collectives inside tf.function lower to the py_function bridge
    (reference: tensorflow/mpi_ops.cc:461 AsyncOpKernels work in graphs)."""
    import horovod_tpu.frontends.tensorflow as tfvd

    @tf.function
    def f(x):
        return tfvd.allreduce(x, op=tfvd.Sum)

    x = tf.reshape(tf.range(6, dtype=tf.float32), (2, 3))
    y = f(x)
    assert y.shape == (2, 3)
    np.testing.assert_allclose(y.numpy(), x.numpy() * tfvd.size())

    @tf.function
    def g(x):
        out = tfvd.allgather(x)
        b = tfvd.broadcast(x, root_rank=0)
        return out, b

    out, b = g(tf.ones((2, 3)))
    assert out.shape == (2 * tfvd.size(), 3)
    np.testing.assert_allclose(b.numpy(), 1.0)


def test_tf_function_reducescatter_alltoall_barrier(hvd):
    """The remaining collectives work through the graph bridge too."""
    import horovod_tpu.frontends.tensorflow as tfvd
    k = tfvd.size()

    @tf.function
    def f(x):
        rs = tfvd.reducescatter(x, op=tfvd.Sum)
        out, recv = tfvd.alltoall(x)
        b = tfvd.barrier()
        return rs, out, recv, b

    x = tf.ones((2 * k, 3))
    rs, out, recv, b = f(x)
    np.testing.assert_allclose(rs.numpy(), np.full((2, 3), float(k)))
    assert out.shape == (2 * k, 3)
    np.testing.assert_array_equal(recv.numpy(), np.full(k, 2))
    assert int(b) == 0


def test_tf_function_gradient_tape_step(hvd):
    """A tf.function-wrapped train step with DistributedGradientTape
    converges (VERDICT r2 #3)."""
    import horovod_tpu.frontends.tensorflow as tfvd
    w = tf.Variable([[2.0]])
    opt_lr = 0.1

    @tf.function
    def train_step(x):
        with tf.GradientTape() as tape:
            loss = tf.reduce_sum(tf.square(w * x - 3.0))
        dtape = tfvd.DistributedGradientTape(tape)
        (grad,) = dtape.gradient(loss, [w])
        w.assign_sub(opt_lr * grad)
        return loss

    losses = [float(train_step(tf.constant([[1.0]]))) for _ in range(20)]
    assert losses[-1] < losses[0] * 1e-3, losses
    np.testing.assert_allclose(w.numpy(), 3.0, rtol=1e-2)


def test_tf_function_grouped_order_chained(hvd):
    """Bridge ops in one graph are chained with control dependencies so
    execution order == trace order on every rank."""
    import horovod_tpu.frontends.tensorflow as tfvd

    @tf.function
    def f(a, b):
        x = tfvd.allreduce(a, op=tfvd.Sum)
        y = tfvd.allreduce(b, op=tfvd.Sum)  # no data dep on x
        return x, y

    cf = f.get_concrete_function(
        tf.TensorSpec((2,), tf.float32), tf.TensorSpec((3,), tf.float32))
    eager_ops = [op for op in cf.graph.get_operations()
                 if op.type == "EagerPyFunc"]
    assert len(eager_ops) == 2
    assert any(c is eager_ops[0] for c in eager_ops[1].control_inputs), \
        f"second collective not chained: {eager_ops[1].control_inputs}"


def test_tf_function_bpps_keras_native(hvd):
    """Keras-3 path: bpps maps onto gradient_accumulation_steps and works
    inside tf.function."""
    import keras

    import horovod_tpu.frontends.tensorflow as tfvd
    v = tf.Variable(0.0)
    opt = tfvd.DistributedOptimizer(keras.optimizers.SGD(learning_rate=1.0),
                                    backward_passes_per_step=2)
    assert isinstance(opt, keras.optimizers.Optimizer)

    @tf.function
    def step(g):
        opt.apply_gradients([(g, v)])

    step(tf.constant(1.0))
    np.testing.assert_allclose(v.numpy(), 0.0)  # accumulating
    step(tf.constant(3.0))
    np.testing.assert_allclose(v.numpy(), -2.0, rtol=1e-6)  # mean applied


def test_tf_function_bpps_eager_wrapper_raises(hvd):
    """Non-Keras optimizers keep the eager wrapper, whose Python-state
    accumulation cannot be traced."""
    import horovod_tpu.frontends.tensorflow as tfvd

    class _DummyOpt:
        def apply_gradients(self, gv, **kw):
            pass

    opt = tfvd.DistributedOptimizer(_DummyOpt(), backward_passes_per_step=2)
    v = tf.Variable(1.0)

    @tf.function
    def step():
        opt.apply_gradients([(tf.constant(2.0), v)])

    with pytest.raises(NotImplementedError, match="backward_passes_per_step"):
        step()


def test_tf_metric_average_callback(hvd):
    import horovod_tpu.frontends.tensorflow as tfvd
    cb = tfvd.MetricAverageCallback()
    logs = {"loss": 4.0}
    cb.on_epoch_end(0, logs)
    np.testing.assert_allclose(logs["loss"], 4.0)  # identical ranks


def test_callbacks_namespace_and_lr_schedule(hvd):
    """Reference spelling parity: hvd.callbacks.* exists
    (tensorflow/keras/callbacks.py), and LearningRateScheduleCallback
    applies a multiplier over its epoch range."""
    import keras

    import horovod_tpu.frontends.tensorflow as tfvd

    for name in ("BroadcastGlobalVariablesCallback", "MetricAverageCallback",
                 "LearningRateWarmupCallback",
                 "LearningRateScheduleCallback"):
        assert hasattr(tfvd.callbacks, name)

    model = keras.Sequential([keras.layers.Input((2,)),
                              keras.layers.Dense(1)])
    model.compile(optimizer=keras.optimizers.SGD(learning_rate=1.0),
                  loss="mse")
    cb = tfvd.callbacks.LearningRateScheduleCallback(
        initial_lr=1.0, multiplier=lambda e: 0.1 ** e,
        start_epoch=1, end_epoch=3)
    cb.set_model(model)
    cb.on_epoch_begin(0)
    np.testing.assert_allclose(float(model.optimizer.learning_rate), 1.0)
    cb.on_epoch_begin(1)
    np.testing.assert_allclose(float(model.optimizer.learning_rate), 0.1)
    cb.on_epoch_begin(2)
    np.testing.assert_allclose(float(model.optimizer.learning_rate), 0.01,
                               rtol=1e-6)
    cb.on_epoch_begin(3)  # out of range: unchanged
    np.testing.assert_allclose(float(model.optimizer.learning_rate), 0.01,
                               rtol=1e-6)


def test_lr_schedule_smooth_and_reference_kwargs(hvd):
    """staircase=False interpolates per batch; reference kwargs
    (momentum_correction, steps_per_epoch) are accepted
    (reference: _keras/callbacks.py:108)."""
    import keras

    import horovod_tpu.frontends.tensorflow as tfvd

    model = keras.Sequential([keras.layers.Input((2,)),
                              keras.layers.Dense(1)])
    model.compile(optimizer=keras.optimizers.SGD(learning_rate=1.0),
                  loss="mse")
    cb = tfvd.callbacks.LearningRateScheduleCallback(
        initial_lr=1.0, multiplier=lambda e: 0.5 ** e,
        staircase=False, momentum_correction=False, steps_per_epoch=4)
    cb.set_model(model)
    cb.on_epoch_begin(1)
    cb.on_train_batch_end(1)  # epoch 1.5 -> 0.5**1.5
    np.testing.assert_allclose(float(model.optimizer.learning_rate),
                               0.5 ** 1.5, rtol=1e-5)


def test_tf_jit_compile_pinned_error(hvd):
    """`tf.function(jit_compile=True)` around a collective fails with TF's
    unsupported-op (EagerPyFunc) error: the graph bridge re-enters the
    eager engine via py_function, which TF-XLA cannot compile. Pinned here
    so the failure mode is a contract, not a surprise; the migration path
    is documented in docs/migration.md ("TF-XLA training steps"). The
    reference compiles collectives under TF-XLA via paired async custom
    calls (tensorflow/xla_mpi_ops.cc:176-218) — an intentionally
    unreplicated design: this framework's XLA-native path is the jax
    frontend, where the collective IS an XLA op inside the jitted step.
    """
    import tensorflow as tf

    import horovod_tpu.frontends.tensorflow as tfvd

    @tf.function(jit_compile=True)
    def step(x):
        return tfvd.allreduce(x, op=tfvd.Sum, name="xla_pin")

    with pytest.raises(Exception) as ei:
        step(tf.constant([1.0, 2.0]))
    msg = str(ei.value)
    assert "EagerPyFunc" in msg or "unsupported operations" in msg
    # plain tf.function (no jit_compile) with the same collective works
    @tf.function
    def step_ok(x):
        return tfvd.allreduce(x, op=tfvd.Sum, name="xla_pin_ok")

    out = step_ok(tf.constant([1.0, 2.0]))
    np.testing.assert_allclose(out.numpy(),
                               np.array([1.0, 2.0]) * hvd.size())


def test_tf_min_max_product_exports(hvd):
    """Reference exports Min/Max/Product on the TF surface too
    (tensorflow/mpi_ops.py:85-87)."""
    import tensorflow as tf

    import horovod_tpu.frontends.tensorflow as tfvd

    t = tf.constant([2.0, 5.0])
    out = tfvd.allreduce(t, op=tfvd.Product, name="tfpr")
    np.testing.assert_allclose(out.numpy(),
                               np.array([2.0, 5.0]) ** hvd.size())
    out2 = tfvd.allreduce(t, op=tfvd.Max, name="tfmx")
    np.testing.assert_allclose(out2.numpy(), t.numpy())


def test_tf_api_sweep_round4(hvd):
    """Round-4 TF surface sweep vs reference mpi_ops.py/functions.py:
    grouped allgather/reducescatter, topology *_op tensors, broadcast_
    over Variables, broadcast_object_fn."""
    import tensorflow as tf

    import horovod_tpu.frontends.tensorflow as tfvd

    k = hvd.size()
    outs = tfvd.grouped_allgather([tf.ones((2, 3)), tf.zeros((1, 5))])
    assert outs[0].shape == (2 * k, 3) and outs[1].shape == (k, 5)

    outs = tfvd.grouped_reducescatter([tf.ones((k * 2, 3))],
                                      op=tfvd.Sum)
    np.testing.assert_allclose(outs[0].numpy(),
                               np.full((2, 3), float(k)))

    assert int(tfvd.size_op()) == k
    assert int(tfvd.rank_op()) == hvd.rank()
    assert int(tfvd.local_rank_op()) == hvd.local_rank()
    assert int(tfvd.local_size_op()) == hvd.local_size()
    assert int(tfvd.process_set_included_op()) == 1

    v = tf.Variable([1.0, 2.0])
    got = tfvd.broadcast_([v], root_rank=0)
    assert got[0] is v
    np.testing.assert_allclose(v.numpy(), [1.0, 2.0])

    fn = tfvd.broadcast_object_fn(root_rank=0)
    assert fn({"a": 1}) == {"a": 1}


def test_tf_keras_load_model_rewraps_optimizer(hvd, tmp_path):
    """hvd.load_model reloads a model saved with a DistributedOptimizer
    and keeps it distributed for retraining (reference:
    tensorflow/keras/__init__.py:234)."""
    import keras

    import horovod_tpu.frontends.tensorflow as tfvd

    m = keras.Sequential([keras.layers.Input((4,)), keras.layers.Dense(2)])
    m.compile(optimizer=tfvd.DistributedOptimizer(
        keras.optimizers.SGD(0.1)), loss="mse")
    m.fit(np.ones((8, 4)), np.ones((8, 2)), epochs=1, verbose=0)
    path = str(tmp_path / "m.keras")
    m.save(path)

    m2 = tfvd.load_model(path)
    assert type(m2.optimizer).__name__ == "DistributedSGD"
    assert float(m2.optimizer.learning_rate) == pytest.approx(0.1)
    m2.fit(np.ones((8, 4)), np.ones((8, 2)), epochs=1, verbose=0)


def test_tf_grouped_ops_inside_tf_function(hvd):
    """grouped_allgather/grouped_reducescatter must ride the py_function
    bridge like every other collective (parity row 24: 'eager AND inside
    tf.function')."""
    import tensorflow as tf

    import horovod_tpu.frontends.tensorflow as tfvd

    k = hvd.size()

    @tf.function
    def f(x, y):
        ag = tfvd.grouped_allgather([x])
        rs = tfvd.grouped_reducescatter([y], op=tfvd.Sum)
        return ag[0], rs[0]

    ag, rs = f(tf.ones((2, 3)), tf.ones((k * 2, 3)))
    assert ag.shape == (2 * k, 3)
    np.testing.assert_allclose(rs.numpy(), np.full((2, 3), float(k)))


def test_partial_distributed_tape_and_optimizer(hvd):
    """PartialDistributed{GradientTape,Optimizer}: local layers' grads
    are never reduced and (by default) divided by the set size
    (reference: tensorflow/__init__.py:1205, keras/__init__.py:116,
    pull/3695 scaling)."""
    import keras
    import tensorflow as tf

    import horovod_tpu.frontends.tensorflow as tfvd

    k = hvd.size()

    # tape path: one global var, one local var. With identical ranks the
    # averaged global grad equals the local grad; the LOCAL one is
    # divided by k.
    g_var = tf.Variable([2.0])
    l_var = tf.Variable([3.0])
    with tf.GradientTape() as tape:
        loss = 4.0 * g_var[0] + 8.0 * l_var[0]
    # wrap with local_layers=... needs Layer objects for the helper, so
    # register directly on the tape
    dtape = tfvd.DistributedGradientTape(tape)
    dtape.register_local_source(l_var)
    gg, lg = dtape.gradient(loss, [g_var, l_var])
    np.testing.assert_allclose(gg.numpy(), [4.0])
    np.testing.assert_allclose(lg.numpy(), [8.0 / k])

    # optimizer path via local_layers: the local Dense layer's weights
    # step by grad/k; equality of updates is checked vs manual math
    local_layer = keras.layers.Dense(1, use_bias=False,
                                     kernel_initializer="ones")
    local_layer.build((None, 1))
    opt = tfvd.PartialDistributedOptimizer(
        keras.optimizers.SGD(1.0), local_layers=[local_layer])
    assert type(opt).__name__ == "PartialDistributedSGD"
    w = local_layer.trainable_weights[0]
    grads = [tf.ones_like(w)]
    opt.apply(grads, [w])
    # w started at 1, lr=1, grad 1 scaled by 1/k -> w = 1 - 1/k
    np.testing.assert_allclose(w.numpy(), [[1.0 - 1.0 / k]], rtol=1e-6)

    # with no local layers it degrades to the plain DistributedOptimizer
    opt2 = tfvd.PartialDistributedOptimizer(keras.optimizers.SGD(0.1))
    assert type(opt2).__name__ == "DistributedSGD"


def test_keras_alias_module(hvd):
    """`horovod.keras`-shaped import surface (reference:
    horovod/keras/__init__.py re-exports)."""
    import horovod_tpu.frontends.keras as khvd

    assert khvd.size() == hvd.size()
    out = khvd.allreduce(np.ones(3, np.float32), op=khvd.Sum)
    np.testing.assert_allclose(np.asarray(out), hvd.size())
    assert callable(khvd.callbacks.BroadcastGlobalVariablesCallback)


def test_partial_local_scaling_keeps_indexed_slices(hvd):
    """Local-gradient scaling must not densify IndexedSlices (embedding
    grads — the canonical local layer); reference scales .values."""
    import tensorflow as tf

    import horovod_tpu.frontends.tensorflow as tfvd

    k = hvd.size()
    v = tf.Variable(tf.ones((10, 4)))
    with tf.GradientTape() as tape:
        rows = tf.gather(v, [1, 3])
        loss = tf.reduce_sum(rows)
    dtape = tfvd.DistributedGradientTape(tape)
    dtape.register_local_source(v)
    g = dtape.gradient(loss, v)
    assert isinstance(g, tf.IndexedSlices), "local grad was densified"
    np.testing.assert_allclose(g.values.numpy(),
                               np.ones((2, 4)) / k)


def test_partial_optimizer_unbuilt_layer_resolves_lazily(hvd):
    """local_layers passed BEFORE the layer builds must still be treated
    as local at apply time (review finding: silent degrade to full
    allreduce)."""
    import keras
    import tensorflow as tf

    import horovod_tpu.frontends.tensorflow as tfvd

    k = hvd.size()
    layer = keras.layers.Dense(1, use_bias=False,
                               kernel_initializer="ones")
    # NOT built yet when the optimizer wraps it
    opt = tfvd.PartialDistributedOptimizer(
        keras.optimizers.SGD(1.0), local_layers=[layer])
    assert type(opt).__name__ == "PartialDistributedSGD"
    layer.build((None, 1))  # builds after wrapping
    w = layer.trainable_weights[0]
    opt.apply([tf.ones_like(w)], [w])
    # local semantics: grad scaled by 1/k -> w = 1 - 1/k
    np.testing.assert_allclose(w.numpy(), [[1.0 - 1.0 / k]], rtol=1e-6)

    # same laziness through the tape wrapper
    layer2 = keras.layers.Dense(1, use_bias=False,
                                kernel_initializer="ones")
    with tf.GradientTape() as t:
        pass
    dtape = tfvd.PartialDistributedGradientTape(t, local_layers=[layer2])
    layer2.build((None, 1))
    assert dtape._is_local(layer2.trainable_weights[0])
