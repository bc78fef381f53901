"""ISSUE 5 — stall-free fit loop: DeviceQueueIter async H2D pipeline,
device-resident metrics, dispatch-ahead stepping, and the iterator
lifecycle satellites (PrefetchingIter close, NDArrayIter zero-copy)."""
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, profiler
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel import DeviceQueueIter, make_mesh
from mxnet_tpu.parallel.feed import expected_sharding, is_preplaced


# ---------------------------------------------------------------------------
# fixtures / helpers
# ---------------------------------------------------------------------------
def _mlp():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _data(n=256, d=16, classes=4, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    W = rng.randn(d, classes)
    y = X.dot(W).argmax(axis=1).astype(np.float32)
    return X, y


def _fused_module(X, y, batch=64, contexts=None, seed=0):
    it = mx.io.NDArrayIter(X, y, batch_size=batch, shuffle=False)
    mod = mx.mod.Module(_mlp(), context=contexts or
                        [mx.cpu(i) for i in range(8)])
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mx.random.seed(seed)
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(kvstore="tpu", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    assert mod._fused is not None, "fused SPMD path was not taken"
    return mod, it


def _no_feed_thread():
    return not any(t.name == "DeviceQueueIter" and t.is_alive()
                   for t in threading.enumerate())


class _CountingIter(mx.io.DataIter):
    """Wraps a DataIter, counting next() calls and supporting close()."""

    def __init__(self, inner, delay=0.0):
        super().__init__(inner.batch_size)
        self.inner = inner
        self.pulled = 0
        self.closed = False
        self.delay = delay

    @property
    def provide_data(self):
        return self.inner.provide_data

    @property
    def provide_label(self):
        return self.inner.provide_label

    def reset(self):
        self.inner.reset()

    def next(self):
        if self.delay:
            time.sleep(self.delay)
        batch = self.inner.next()
        self.pulled += 1
        return batch

    def close(self):
        self.closed = True


# ---------------------------------------------------------------------------
# DeviceQueueIter core semantics
# ---------------------------------------------------------------------------
def test_device_queue_matches_sync_path_bitexact():
    import jax

    X, y = _data(n=128)
    mesh = make_mesh({"dp": 8})
    sharding = expected_sharding(mesh, ("dp",))
    sync_it = mx.io.NDArrayIter(X, y, batch_size=32)
    with DeviceQueueIter(mx.io.NDArrayIter(X, y, batch_size=32),
                         mesh=mesh) as dq:
        for sync_b, dev_b in zip(sync_it, dq):
            for host, placed in zip(sync_b.data + sync_b.label,
                                    dev_b.data + dev_b.label):
                val = placed._data()
                assert is_preplaced(val, sharding), val.sharding
                ref = jax.device_put(host._data(), sharding)
                np.testing.assert_array_equal(np.asarray(ref),
                                              np.asarray(val))


def test_device_queue_ordering_and_epoch_parity():
    X, y = _data(n=192)
    mesh = make_mesh({"dp": 8})
    with DeviceQueueIter(mx.io.NDArrayIter(X, y, batch_size=32),
                         mesh=mesh) as dq:
        seen = np.concatenate([b.label[0].asnumpy() for b in dq])
        np.testing.assert_array_equal(seen, y)
        with pytest.raises(StopIteration):
            dq.next()  # repeated next() keeps raising post-epoch
        dq.reset()     # restart after StopIteration
        seen2 = np.concatenate([b.label[0].asnumpy() for b in dq])
        np.testing.assert_array_equal(seen2, y)


def test_device_queue_bounded_depth():
    X, y = _data(n=512)
    mesh = make_mesh({"dp": 8})
    src = _CountingIter(mx.io.NDArrayIter(X, y, batch_size=32))
    with DeviceQueueIter(src, mesh=mesh, depth=2) as dq:
        dq.next()
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline and src.pulled < 4:
            time.sleep(0.02)
        time.sleep(0.1)  # give an over-eager worker time to overshoot
        # consumed 1 + queue depth 2 + 1 being placed on the worker
        assert src.pulled <= 4, src.pulled


def test_device_queue_reset_mid_epoch_and_close():
    X, y = _data(n=256)
    mesh = make_mesh({"dp": 8})
    src = _CountingIter(mx.io.NDArrayIter(X, y, batch_size=32))
    dq = DeviceQueueIter(src, mesh=mesh)
    dq.next()
    dq.reset()  # abandon the epoch mid-stream
    seen = sum(1 for _ in dq)
    assert seen == 8
    dq.close()
    assert dq._thread is None
    assert src.closed  # close propagates to the source
    dq.close()  # idempotent
    with pytest.raises(MXNetError):
        dq.next()
    with pytest.raises(MXNetError):
        dq.reset()
    assert _no_feed_thread()


def test_device_queue_depth_validation():
    X, y = _data(n=64)
    with pytest.raises(MXNetError):
        DeviceQueueIter(mx.io.NDArrayIter(X, y, batch_size=32),
                        mesh=make_mesh({"dp": 8}), depth=0)
    with pytest.raises(MXNetError):
        DeviceQueueIter(mx.io.NDArrayIter(X, y, batch_size=32))  # no mesh


def test_device_queue_worker_error_surfaces():
    class _Boom(mx.io.DataIter):
        provide_data = [("data", (8, 4))]
        provide_label = [("softmax_label", (8,))]

        def next(self):
            raise ValueError("decoder exploded")

    with DeviceQueueIter(_Boom(), mesh=make_mesh({"dp": 8})) as dq:
        with pytest.raises(ValueError, match="decoder exploded"):
            dq.next()
        with pytest.raises(ValueError):
            dq.next()  # sticky


def test_device_queue_indivisible_batch_raises():
    X, y = _data(n=60)
    with DeviceQueueIter(mx.io.NDArrayIter(X, y, batch_size=30),
                         mesh=make_mesh({"dp": 8})) as dq:
        with pytest.raises(MXNetError, match="not divisible"):
            dq.next()


def test_device_queue_passthrough_without_fused_group():
    X, y = _data(n=128)
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mod = mx.mod.Module(_mlp(), context=mx.cpu(0))
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(kvstore="local", optimizer="sgd")
    with DeviceQueueIter(mx.io.NDArrayIter(X, y, batch_size=32),
                         module=mod) as dq:
        with pytest.warns(UserWarning, match="no fused SPMD group"):
            batch = dq.next()
        # host batch passed through unchanged
        assert batch.data[0].asnumpy().shape == (32, 16)


# ---------------------------------------------------------------------------
# the stall-free fit loop: zero host syncs, device metrics, dispatch-ahead
# ---------------------------------------------------------------------------
def _fit_epochs(mod, feed, metric, epochs):
    for _ in range(epochs):
        feed.reset()
        metric.reset()
        for batch in feed:
            mod.forward_backward(batch)
            mod.update()
            mod.update_metric(metric, batch.label)
    return metric


def test_fit_loop_steady_state_has_zero_host_syncs():
    X, y = _data(n=256)
    mod, it = _fused_module(X, y)
    metric = mx.metric.Accuracy()
    with DeviceQueueIter(mx.io.NDArrayIter(X, y, batch_size=64),
                         group=mod._fused) as dq:
        _fit_epochs(mod, dq, metric, 1)  # warmup/compile epoch
        profiler.pipeline_reset()
        _fit_epochs(mod, dq, metric, 2)
        name, acc = metric.get()  # boundary drain — NOT a per-batch sync
    stats = profiler.pipeline_stats()
    assert stats["host_syncs"] == 0, stats
    assert stats["preplaced"] == 2 * 4 * 2, stats  # 4 batches x 2 arrays
    assert stats["steps"] == 8, stats
    assert acc > 0.5


def test_device_metric_parity_with_host_metrics_incl_padding(monkeypatch):
    # n=200, batch=64 -> last batch padded by 56; both paths must count
    # identically (the host metric sees the padded rows too)
    X, y = _data(n=200, seed=5)

    def run(device_metrics):
        monkeypatch.setenv("MXNET_TPU_DEVICE_METRICS",
                           "1" if device_metrics else "0")
        mod, it = _fused_module(X, y, seed=11)
        metric = mx.metric.CompositeEvalMetric(
            metrics=[mx.metric.Accuracy(), mx.metric.CrossEntropy()])
        _fit_epochs(mod, it, metric, 3)
        return dict(zip(*metric.get()))

    host = run(False)
    dev = run(True)
    assert host.keys() == dev.keys()
    for k in host:
        np.testing.assert_allclose(dev[k], host[k], rtol=1e-5,
                                   err_msg="metric %s diverged" % k)


def test_device_metrics_fall_back_for_unsupported_metric():
    X, y = _data(n=128)
    mod, it = _fused_module(X, y)
    metric = mx.metric.MSE()  # not reducible in-step -> host fallback
    profiler.pipeline_reset()
    _fit_epochs(mod, it, metric, 1)
    assert metric.num_inst > 0
    # the fallback materializes outputs: host syncs are counted
    assert profiler.pipeline_stats()["host_syncs"] > 0


def test_host_fallback_metric_with_preplaced_labels():
    # host-path metric fed by the pipeline: labels arrive as NDArrays
    # wrapping placed device arrays and must survive update_dict
    X, y = _data(n=128)
    mod, _ = _fused_module(X, y)
    metric = mx.metric.MSE()
    with DeviceQueueIter(mx.io.NDArrayIter(X, y, batch_size=64),
                         group=mod._fused) as dq:
        _fit_epochs(mod, dq, metric, 1)
    assert metric.num_inst > 0


def test_local_rows_host_reassembles_shards():
    import jax

    from mxnet_tpu.module.spmd_group import FusedSPMDGroup
    from mxnet_tpu.parallel.spmd import replicated

    mesh = make_mesh({"dp": 8})
    value = np.arange(64, dtype=np.float32).reshape(16, 4)
    sharded = jax.device_put(value, expected_sharding(mesh, ("dp",)))
    np.testing.assert_array_equal(
        FusedSPMDGroup._local_rows_host(sharded), value)
    repl = jax.device_put(value, replicated(mesh))
    np.testing.assert_array_equal(
        FusedSPMDGroup._local_rows_host(repl), value)


def test_speedometer_interval_drain(monkeypatch):
    """get() at a Speedometer-style interval folds the device stats and
    auto_reset clears them — counts never double."""
    X, y = _data(n=256)
    mod, it = _fused_module(X, y)
    metric = mx.metric.Accuracy()
    it.reset()
    total = 0
    for i, batch in enumerate(it):
        mod.forward_backward(batch)
        mod.update()
        mod.update_metric(metric, batch.label)
        if (i + 1) % 2 == 0:  # interval drain, auto_reset style
            metric._fold_device_sources()
            total += metric.num_inst
            metric.reset()
    assert total == 256
    assert metric.num_inst == 0


def test_dispatch_ahead_bounded_and_drained(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_TPU_MAX_INFLIGHT", "3")
    X, y = _data(n=256)
    mod, it = _fused_module(X, y)
    profiler.pipeline_reset()
    _fit_epochs(mod, it, mx.metric.Accuracy(), 2)
    group = mod._fused
    assert group._max_inflight == 3
    assert len(group._inflight) <= 3
    assert profiler.pipeline_stats()["max_inflight"] <= 3
    # checkpoint boundary drains the pipeline (the PR 3 quiesce path
    # reuses this through save_optimizer_states)
    mod.save_optimizer_states(str(tmp_path / "fit.states"))
    assert len(group._inflight) == 0
    _fit_epochs(mod, it, mx.metric.Accuracy(), 1)
    assert len(group._inflight) > 0
    mod.get_params()  # epoch-boundary param sync drains too
    assert len(group._inflight) == 0


def test_max_inflight_knob_validated(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_MAX_INFLIGHT", "0")
    from mxnet_tpu.module.spmd_group import FusedSPMDGroup

    X, y = _data(n=64)
    sym = _mlp()
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    rng = np.random.RandomState(0)
    shapes, _, _ = sym.infer_shape(data=(2, 16))
    args = {n: nd.NDArray(rng.normal(0, 0.1, s).astype(np.float32))
            for n, s in zip(sym.list_arguments(), shapes)
            if n not in ("data", "softmax_label")}
    with pytest.raises(MXNetError, match="MXNET_TPU_MAX_INFLIGHT"):
        FusedSPMDGroup(sym, [mx.cpu(i) for i in range(4)],
                       mx.optimizer.SGD(learning_rate=0.1),
                       args, {}, ["data"], ["softmax_label"])


def test_chaos_crash_fires_deterministically_with_dispatch_ahead(monkeypatch):
    """PR 3 fault injection: a crash@step rule must fire at the exact
    step even while the loop dispatches ahead of the device."""
    from mxnet_tpu import chaos

    monkeypatch.setenv("MXNET_TPU_MAX_INFLIGHT", "4")
    monkeypatch.setenv("MXNET_FAULT_SPEC", "worker:0:crash@step=3")
    monkeypatch.setenv("DMLC_ROLE", "worker")
    chaos.reset_engine()

    class _Crashed(Exception):
        pass

    def _raise(_code):
        raise _Crashed()

    try:
        chaos.engine()._exit = _raise  # the documented test injection
        X, y = _data(n=256)
        mod, it = _fused_module(X, y)
        it.reset()
        steps = 0
        with pytest.raises(_Crashed):
            for batch in it:
                mod.forward_backward(batch)
                mod.update()
                steps += 1
        assert steps == 2  # raised on the 3rd step, before its update
    finally:
        monkeypatch.delenv("MXNET_FAULT_SPEC")
        chaos.reset_engine()


def test_fit_api_end_to_end_with_pipeline(tmp_path):
    """Module.fit proper (epoch boundaries, eval, checkpoint callback)
    over the wrapped iterator."""
    X, y = _data(n=256, seed=2)
    it = mx.io.NDArrayIter(X, y, batch_size=64)
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(8)])
    with DeviceQueueIter(mx.io.NDArrayIter(X, y, batch_size=64),
                         module=mod) as dq:
        mod.fit(dq, eval_data=it, num_epoch=4, kvstore="tpu",
                optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                initializer=mx.initializer.Xavier(),
                epoch_end_callback=mx.callback.do_checkpoint(
                    str(tmp_path / "pipe"), period=4))
    assert mod._fused is not None
    acc = dict(mod.score(it, mx.metric.Accuracy()))["accuracy"]
    assert acc > 0.8
    assert os.path.exists(str(tmp_path / "pipe-0004.params"))


# ---------------------------------------------------------------------------
# ISSUE 27: Module.fit puts the queue around the caller's iterator itself
# ---------------------------------------------------------------------------
class _ThreadLoggingIter(_CountingIter):
    """Also notes the thread each next() ran on; ``boom_at`` makes the
    n-th next() raise."""

    def __init__(self, inner, boom_at=None):
        super().__init__(inner)
        self.threads = []
        self.boom_at = boom_at

    def next(self):
        self.threads.append(threading.current_thread().name)
        if self.boom_at is not None and self.pulled == self.boom_at:
            raise ValueError("decoder exploded")
        return super().next()


def _plain_fit(src, kvstore="tpu", num_epoch=2, contexts=None, **kw):
    mod = mx.mod.Module(_mlp(), context=contexts or
                        [mx.cpu(i) for i in range(8)])
    mod.fit(src, num_epoch=num_epoch, kvstore=kvstore, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.initializer.Xavier(), **kw)
    return mod


def test_module_fit_places_every_batch_on_the_queue_thread(monkeypatch):
    from mxnet_tpu.module import spmd_group
    from mxnet_tpu.parallel import feed as feed_mod

    copied_on, found_placed_on = [], []
    real = feed_mod.place_batch_array

    def spy(mesh, data_axes, distributed, name, value, sharding=None):
        placed = is_preplaced(value, sharding)
        (found_placed_on if placed else copied_on).append(
            threading.current_thread().name)
        return real(mesh, data_axes, distributed, name, value,
                    sharding=sharding)

    monkeypatch.setattr(feed_mod, "place_batch_array", spy)    # the worker's
    monkeypatch.setattr(spmd_group, "place_batch_array", spy)  # the step's
    X, y = _data(n=256)
    src = _ThreadLoggingIter(mx.io.NDArrayIter(X, y, batch_size=64))
    seen = []
    profiler.pipeline_reset()
    mod = _plain_fit(src, batch_end_callback=lambda p: seen.append(
        p.locals["data_batch"]))
    assert mod._fused is not None
    stats = profiler.pipeline_stats()
    steps = 2 * 4                       # 2 epochs x 4 batches
    assert stats["steps"] == steps, stats
    # the worker read and copied every batch; the step found them placed
    assert copied_on == ["DeviceQueueIter"] * (2 * steps)
    assert found_placed_on == [threading.current_thread().name] * (2 * steps)
    assert set(src.threads) == {"DeviceQueueIter"}
    assert stats["puts"] == 2 * steps and stats["batches"] == steps, stats
    assert stats["preplaced"] == 2 * steps, stats    # two arrays a step
    assert 1 <= stats["max_queue_depth"] <= 2, stats
    # callbacks see the device-resident batch the step ran on
    sharding = mod._fused._batch_sharding
    assert len(seen) == steps
    assert all(is_preplaced(a._data(), sharding)
               for b in seen for a in b.data + b.label)


def test_module_fit_through_queue_matches_host_batches_bitexact():
    X, y = _data(n=256, seed=3)
    opt = {"learning_rate": 0.1, "momentum": 0.9}
    ctx = [mx.cpu(i) for i in range(8)]
    probe, _ = _fused_module(X, y, seed=21)
    arg0, aux0 = probe.get_params()
    arg0 = {k: v.asnumpy() for k, v in arg0.items()}

    def start():
        return {k: nd.array(v) for k, v in arg0.items()}

    fitted = mx.mod.Module(_mlp(), context=ctx)
    fitted.fit(mx.io.NDArrayIter(X, y, batch_size=64), num_epoch=2,
               kvstore="tpu", optimizer="sgd", optimizer_params=opt,
               arg_params=start(), aux_params=aux0)

    it = mx.io.NDArrayIter(X, y, batch_size=64)
    driven = mx.mod.Module(_mlp(), context=ctx)
    driven.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    driven.init_params(arg_params=start(), aux_params=aux0)
    driven.init_optimizer(kvstore="tpu", optimizer="sgd",
                          optimizer_params=opt)
    profiler.pipeline_reset()
    for _ in range(2):
        it.reset()
        for batch in it:                # host batches, copied by the step
            driven.forward_backward(batch)
            driven.update()
    assert profiler.pipeline_stats()["preplaced"] == 0
    got, want = fitted.get_params()[0], driven.get_params()[0]
    assert got.keys() == want.keys()
    for k in want:
        np.testing.assert_array_equal(got[k].asnumpy(), want[k].asnumpy(),
                                      err_msg=k)
        assert not np.array_equal(want[k].asnumpy(), arg0[k]), k


def test_module_fit_leaves_no_feed_thread_and_the_iterator_reset():
    X, y = _data(n=256)
    src = _ThreadLoggingIter(mx.io.NDArrayIter(X, y, batch_size=64))
    _plain_fit(src, num_epoch=3)
    assert _no_feed_thread()
    # fit read exactly the batches it trained on: its last reset starts
    # no read-ahead, so the iterator is left as the bare loop leaves it
    assert src.pulled == 3 * 4
    assert not src.closed
    assert sum(1 for _ in src) == 4


def test_module_fit_surfaces_iterator_error_and_stops_the_feed():
    X, y = _data(n=256)
    src = _ThreadLoggingIter(mx.io.NDArrayIter(X, y, batch_size=64),
                             boom_at=6)  # third batch of the second epoch
    profiler.pipeline_reset()
    with pytest.raises(ValueError, match="decoder exploded"):
        _plain_fit(src, num_epoch=3)
    assert _no_feed_thread()
    assert not src.closed
    # every batch read before the fault was trained on
    assert profiler.pipeline_stats()["steps"] == 6


def test_module_fit_twice_on_the_callers_iterator():
    X, y = _data(n=256, seed=6)
    src = _ThreadLoggingIter(mx.io.NDArrayIter(X, y, batch_size=64))
    _plain_fit(src, num_epoch=1)
    profiler.pipeline_reset()
    mod = _plain_fit(src, num_epoch=4)
    assert not src.closed and src.pulled == 5 * 4
    stats = profiler.pipeline_stats()
    assert stats["steps"] == 16 and stats["preplaced"] == 32, stats
    assert _no_feed_thread()
    acc = dict(mod.score(mx.io.NDArrayIter(X, y, batch_size=64),
                         mx.metric.Accuracy()))["accuracy"]
    assert acc > 0.8


def test_module_fit_local_kvstore_starts_no_feed_thread(monkeypatch):
    from mxnet_tpu.parallel import feed as feed_mod

    def refuse(self):
        raise AssertionError("kvstore='local' started a feed thread")

    monkeypatch.setattr(feed_mod.DeviceQueueIter, "_start", refuse)
    X, y = _data(n=128)
    src = _ThreadLoggingIter(mx.io.NDArrayIter(X, y, batch_size=64))
    profiler.pipeline_reset()
    mod = _plain_fit(src, kvstore="local", num_epoch=1,
                     contexts=[mx.cpu(0)])
    assert mod._fused is None
    assert set(src.threads) == {threading.current_thread().name}
    assert profiler.pipeline_stats().get("preplaced", 0) == 0


def test_module_fit_does_not_wrap_a_queue_twice(monkeypatch):
    X, y = _data(n=256)
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(8)])
    dq = DeviceQueueIter(mx.io.NDArrayIter(X, y, batch_size=64), module=mod)
    built = []
    real_init = DeviceQueueIter.__init__

    def counting_init(self, *args, **kwargs):
        built.append(self)
        real_init(self, *args, **kwargs)

    monkeypatch.setattr(DeviceQueueIter, "__init__", counting_init)
    profiler.pipeline_reset()
    mod.fit(dq, num_epoch=2, kvstore="tpu", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.initializer.Xavier())
    assert built == []
    stats = profiler.pipeline_stats()
    assert stats["puts"] == 16 and stats["preplaced"] == 16, stats
    # the caller's queue is the caller's to close
    assert not dq._closed
    assert sum(1 for _ in dq) == 4
    dq.close()
    assert _no_feed_thread()


# ---------------------------------------------------------------------------
# ISSUE 34: the worker stages host batches in reused host buffers
# ---------------------------------------------------------------------------
class _StagedQueue(DeviceQueueIter):
    """The queue as it runs beside an accelerator: its worker gets a ring.
    On this suite's host-backend mesh the real one never does."""

    def _new_ring(self):
        from mxnet_tpu.parallel.feed import _StagingRing

        return _StagingRing()


@pytest.fixture
def copying_put(monkeypatch):
    """``jax.device_put`` as an accelerator's: the device array is a copy.
    The host backend may alias an aligned numpy source, which is why the
    real queue stages nothing on it."""
    import jax

    real = jax.device_put

    def put(x, *args, **kwargs):
        if isinstance(x, np.ndarray):
            x = np.array(x)
        return real(x, *args, **kwargs)

    monkeypatch.setattr(jax, "device_put", put)


class _Transfer:
    """Stands for the device array made from a slot: ready when told."""

    def __init__(self):
        self.done = threading.Event()

    def block_until_ready(self):
        assert self.done.wait(10.0)
        return self


def test_staging_ring_does_not_rewrite_a_slot_before_its_transfer_is_ready():
    from mxnet_tpu.parallel.feed import _StagingRing

    ring = _StagingRing()
    batches = [np.full((8, 4), i, np.float32) for i in range(3)]
    first = ring.fill(batches[0])
    first.sent = _Transfer()
    second = ring.fill(batches[1])          # the other slot: no wait
    second.sent = _Transfer()
    assert second is not first
    assert not np.shares_memory(first.buffer, second.buffer)
    profiler.pipeline_reset()
    profiler.h2d_record(batches=1)          # so the snapshot is not empty
    got = []
    t = threading.Thread(target=lambda: got.append(ring.fill(batches[2])))
    t.start()
    time.sleep(0.3)                         # the test holds the transfer back
    assert t.is_alive() and not got
    np.testing.assert_array_equal(first.buffer, batches[0])  # untouched
    first.sent.done.set()
    t.join(10.0)
    assert not t.is_alive() and got == [first]
    np.testing.assert_array_equal(first.buffer, batches[2])
    np.testing.assert_array_equal(second.buffer, batches[1])
    assert first.sent is None               # the caller's to set again
    assert profiler.pipeline_stats()["stage_wait_seconds"] >= 0.25
    # a slot whose array is ready costs no wait worth counting
    profiler.pipeline_reset()
    profiler.h2d_record(batches=1)
    second.sent.done.set()
    assert ring.fill(batches[0]) is second
    assert profiler.pipeline_stats()["stage_wait_seconds"] < 0.1


def test_staging_ring_keeps_shapes_and_dtypes_apart():
    from mxnet_tpu.parallel.feed import _StagingRing

    ring = _StagingRing()
    full = np.arange(32, dtype=np.float32).reshape(8, 4)
    slot = ring.fill(full)
    tail = ring.fill(full[:4] + 100)        # a tail batch: its own slots
    ints = ring.fill(full.astype(np.int32) + 200)
    assert tail.buffer.shape == (4, 4) and ints.buffer.dtype == np.int32
    np.testing.assert_array_equal(slot.buffer, full)    # nobody wrote here
    assert len({id(s.buffer) for s in (slot, tail, ints)}) == 3
    # the ring made one buffer a fill, and the second slot of a shape only
    # when a second batch of that shape came
    made = [s.buffer for pair in ring._slots.values() for s in pair]
    assert sum(b is not None for b in made) == 3 and len(made) == 6


def test_host_rows_reads_views_and_host_arrays_in_place():
    import jax

    from mxnet_tpu.parallel.feed import _host_rows

    X, y = _data(n=128)
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    it.next()
    view = it.next().data[0]                # rows 32:64 of the source
    base = np.asarray(it.data[0][1]._data())
    rows = _host_rows(view)
    np.testing.assert_array_equal(rows, X[32:64])
    assert np.shares_memory(rows, base)
    assert view._view_cache is None         # the view was never realized
    whole = nd.array(X[:32])
    assert np.shares_memory(_host_rows(whole), np.asarray(whole._data()))
    assert _host_rows(X) is X
    # a view no numpy slice stands for is realized, as before
    row = nd.array(X)[3]
    np.testing.assert_array_equal(_host_rows(row), X[3])
    # arrays laid out over several devices are not the worker's to read
    mesh = make_mesh({"dp": 8})
    placed = jax.device_put(X[:32], expected_sharding(mesh, ("dp",)))
    assert _host_rows(placed) is None and _host_rows(nd.NDArray(placed)) is None


class _BatchesIter(mx.io.DataIter):
    """Full batches of 64 rows and a tail of 32, each batch as ``kind``
    says: ``view`` (NDArray views of one host array, the tail a whole
    array), ``numpy`` (a fresh numpy array a batch) or ``placed`` (device
    arrays already laid out as the step wants them)."""

    def __init__(self, X, y, kind, sharding=None, tail=32):
        super().__init__(64)
        self.X, self.y, self.kind, self.sharding = X, y, kind, sharding
        self.ndX, self.ndy = nd.array(X), nd.array(y)
        self.cuts = [(i, i + 64) for i in range(0, len(X) - tail, 64)]
        if tail:
            self.cuts.append((len(X) - tail, len(X)))
        self.at = 0

    @property
    def provide_data(self):
        return [mx.io.DataDesc("data", (64,) + self.X.shape[1:])]

    @property
    def provide_label(self):
        return [mx.io.DataDesc("softmax_label", (64,))]

    def reset(self):
        self.at = 0

    def next(self):
        import jax

        if self.at == len(self.cuts):
            raise StopIteration
        lo, hi = self.cuts[self.at]
        self.at += 1
        data, label = self.X[lo:hi].copy(), self.y[lo:hi].copy()
        if self.kind == "placed":
            data, label = (nd.NDArray(jax.device_put(a, self.sharding))
                           for a in (data, label))
        elif self.kind == "view" and hi - lo == 64:
            data, label = self.ndX[lo:hi], self.ndy[lo:hi]
        elif self.kind == "view":           # the tail: a whole host array
            data, label = nd.array(data), nd.array(label)
        return mx.io.DataBatch([data], [label], pad=0)


@pytest.mark.parametrize("kind", ["view", "numpy", "placed"])
def test_staged_queue_counts_by_where_the_bytes_are(kind, copying_put):
    X, y = _data(n=224)
    mesh = make_mesh({"dp": 8})
    sharding = expected_sharding(mesh, ("dp",))
    profiler.pipeline_reset()
    with _StagedQueue(_BatchesIter(X, y, kind, sharding), mesh=mesh) as dq:
        for _ in range(2):
            got = [(b.data[0].asnumpy(), b.label[0].asnumpy()) for b in dq]
            np.testing.assert_array_equal(np.concatenate([g[0] for g in got]), X)
            np.testing.assert_array_equal(np.concatenate([g[1] for g in got]), y)
            assert [len(g[1]) for g in got] == [64, 64, 64, 32]
            dq.reset()
    stats = profiler.pipeline_stats()
    arrays = 2 * 4 * 2                      # epochs x batches x (data, label)
    assert stats["batches"] == 2 * 4, stats
    if kind == "placed":
        assert (stats["puts"], stats["staged"], stats["preplaced"]) \
            == (0, 0, arrays), stats
        assert "staged_share" not in stats
    else:
        assert (stats["puts"], stats["staged"], stats["preplaced"]) \
            == (arrays, arrays, 0), stats
        assert stats["staged_share"] == 1.0


def test_module_fit_through_a_staging_queue_matches_host_batches_bitexact(
        copying_put):
    X, y = _data(n=224, seed=5)
    probe, _ = _fused_module(X, y, seed=34)
    arg0, aux0 = probe.get_params()
    arg0 = {k: v.asnumpy() for k, v in arg0.items()}

    def fit(queue_of):
        mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(8)])
        src = _BatchesIter(X, y, "view")
        profiler.pipeline_reset()
        with queue_of(src, mod) as dq:
            mod.fit(dq, num_epoch=2, kvstore="tpu", optimizer="sgd",
                    optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                    arg_params={k: nd.array(v) for k, v in arg0.items()},
                    aux_params=aux0)
        return mod.get_params()[0], profiler.pipeline_stats()

    want, plain = fit(lambda src, mod: DeviceQueueIter(src, module=mod))
    got, staged = fit(lambda src, mod: _StagedQueue(src, module=mod))
    # two epochs of three full batches (views) and a 32-row tail
    assert plain["steps"] == staged["steps"] == 8
    assert plain["puts"] == staged["puts"] == 16
    assert plain["staged"] == 0 and staged["staged"] == 16, (plain, staged)
    assert got.keys() == want.keys()
    for k in want:
        np.testing.assert_array_equal(got[k].asnumpy(), want[k].asnumpy(),
                                      err_msg=k)
        assert not np.array_equal(want[k].asnumpy(), arg0[k]), k
    assert _no_feed_thread()


def test_host_backend_mesh_stages_nothing():
    X, y = _data(n=256)
    profiler.pipeline_reset()
    src = _ThreadLoggingIter(mx.io.NDArrayIter(X, y, batch_size=64))
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(8)])
    with DeviceQueueIter(src, module=mod) as dq:
        mod.fit(dq, num_epoch=2, kvstore="tpu", optimizer="sgd",
                optimizer_params={"learning_rate": 0.1},
                initializer=mx.initializer.Xavier())
        assert dq._new_ring() is None       # the mesh is the host backend
    stats = profiler.pipeline_stats()
    assert stats["puts"] == 16 and stats["batches"] == 8, stats
    assert stats["staged"] == 0 and stats["staged_share"] == 0.0, stats
    assert stats["stage_wait_seconds"] == 0.0, stats


def test_staging_ring_goes_with_the_worker_on_reset_and_close(copying_put):
    X, y = _data(n=256)
    mesh = make_mesh({"dp": 8})
    dq = _StagedQueue(mx.io.NDArrayIter(X, y, batch_size=32), mesh=mesh)
    assert dq._ring is None and dq._thread is None   # nothing before next()
    dq.next()
    ring = dq._ring
    assert ring is not None and ring._slots
    dq.reset()                              # mid-epoch
    assert dq._ring is None and dq._thread is None and _no_feed_thread()
    assert sum(1 for _ in dq) == 8          # a new worker, a new ring
    assert dq._ring is not None and dq._ring is not ring
    dq.close()
    assert dq._ring is None and dq._thread is None and _no_feed_thread()


def test_importing_the_feed_allocates_nothing():
    import subprocess
    import sys

    code = (
        "import numpy as np\n"
        "import mxnet_tpu\n"
        "from mxnet_tpu import profiler\n"
        "from mxnet_tpu.parallel import feed\n"
        "assert profiler.pipeline_stats() == {}, profiler.pipeline_stats()\n"
        "held = [k for k, v in vars(feed).items() if isinstance(\n"
        "    v, (np.ndarray, feed._StagingRing, feed._Slot, dict, list))\n"
        "    and not k.startswith('__')]\n"
        "assert held == [], held\n"
        "print('clean')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env, timeout=120,
                         capture_output=True, text=True)
    assert out.returncode == 0 and out.stdout.strip() == "clean", out.stderr


def test_feedforward_fit_uses_pipeline():
    """model.FeedForward.fit trains through the queue Module.fit puts in."""
    X, y = _data(n=256, seed=4)
    ff = mx.model.FeedForward(_mlp(), ctx=[mx.cpu(i) for i in range(4)],
                              num_epoch=3, learning_rate=0.1,
                              initializer=mx.initializer.Xavier())
    profiler.pipeline_reset()
    ff.fit(X, y, kvstore="tpu")
    stats = profiler.pipeline_stats()
    assert stats.get("preplaced", 0) > 0, stats  # pipeline engaged
    assert _no_feed_thread()  # closed after fit


def test_feedforward_refit_keeps_user_iterator_usable():
    """The auto-wrap teardown must not close a CALLER-owned iterator —
    a second fit() (continued training) reuses it."""
    X, y = _data(n=256, seed=5)
    src = mx.io.PrefetchingIter(mx.io.NDArrayIter(X, y, batch_size=64))
    ff = mx.model.FeedForward(_mlp(), ctx=[mx.cpu(i) for i in range(4)],
                              num_epoch=1, learning_rate=0.1,
                              initializer=mx.initializer.Xavier())
    ff.fit(src, kvstore="tpu")
    profiler.pipeline_reset()
    ff.fit(src, kvstore="tpu")  # raised "iterator is closed" pre-fix
    # the refit rebuilt the fused group and re-engaged the pipeline
    # (force_rebind used to orphan the optimizer on the unfused path)
    assert profiler.pipeline_stats().get("preplaced", 0) > 0
    src.close()


def test_update_metric_two_metrics_same_batch():
    """A second metric object updated for the same batch gets the same
    device stats — the consumed guard is per metric, not per batch."""
    X, y = _data(n=128)
    mod, it = _fused_module(X, y)
    m1, m2 = mx.metric.Accuracy(), mx.metric.Accuracy()
    it.reset()
    for batch in it:
        mod.forward_backward(batch)
        mod.update()
        mod.update_metric(m1, batch.label)
        mod.update_metric(m2, batch.label)
    (_, v1), (_, v2) = m1.get(), m2.get()
    assert m1.num_inst == 128 and m2.num_inst == 128
    assert v1 == v2


# ---------------------------------------------------------------------------
# satellites: PrefetchingIter lifecycle, NDArrayIter zero-copy, metric D2H
# ---------------------------------------------------------------------------
def test_prefetching_iter_close_joins_threads():
    X, y = _data(n=96)
    pf = mx.io.PrefetchingIter(mx.io.NDArrayIter(X, y, batch_size=32))
    threads = list(pf.prefetch_threads)
    next(iter(pf))  # stop early mid-epoch
    pf.close()
    assert all(not t.is_alive() for t in threads)
    pf.close()  # idempotent
    with pytest.raises(MXNetError):
        pf.reset()
    with pytest.raises(MXNetError):
        pf.iter_next()


def test_prefetching_iter_close_mid_fetch_joins_promptly():
    # worker blocked inside the source's next() when close() lands: the
    # worker's data_taken.clear() after the fetch would erase a single
    # set(), so close must keep re-signalling until the thread exits
    X, y = _data(n=96)
    fetching = threading.Event()

    class _SignallingIter(_CountingIter):
        def next(self):
            if self.pulled >= 1:  # fetch #2 onward: announce, then stall
                fetching.set()
                time.sleep(0.4)
            return super().next()

    src = _SignallingIter(mx.io.NDArrayIter(X, y, batch_size=32))
    pf = mx.io.PrefetchingIter(src)
    threads = list(pf.prefetch_threads)
    next(iter(pf))
    assert fetching.wait(timeout=5), "worker never started fetch #2"
    t0 = time.monotonic()
    pf.close()
    assert time.monotonic() - t0 < 3.0, "close() hit the join timeout"
    assert all(not t.is_alive() for t in threads)


def test_prefetching_iter_context_manager_and_source_close():
    X, y = _data(n=96)
    src = _CountingIter(mx.io.NDArrayIter(X, y, batch_size=32))
    with mx.io.PrefetchingIter(src) as pf:
        threads = list(pf.prefetch_threads)
        next(iter(pf))
    assert all(not t.is_alive() for t in threads)
    assert src.closed


def test_prefetching_iter_reset_after_stopiteration():
    X, y = _data(n=96)
    with mx.io.PrefetchingIter(
            mx.io.NDArrayIter(X, y, batch_size=32)) as pf:
        first = [b.label[0].asnumpy().copy() for b in pf]
        assert len(first) == 3
        pf.reset()
        second = [b.label[0].asnumpy().copy() for b in pf]
        assert len(second) == 3
        for a, b in zip(first, second):
            np.testing.assert_array_equal(a, b)


def test_ndarray_iter_zero_copy_views():
    X, y = _data(n=128)
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    for batch in it:
        # aligned batches are views into the source, not copies
        assert batch.data[0]._base is not None
        assert batch.label[0]._base is not None
    np.testing.assert_array_equal(
        next(iter(mx.io.NDArrayIter(X, y, batch_size=32))).data[0].asnumpy(),
        X[:32])


def test_ndarray_iter_padded_tail_reuses_buffer():
    X, y = _data(n=100)
    it = mx.io.NDArrayIter(X, y, batch_size=32)  # pad=28 on last batch
    tails = []
    for _epoch in range(2):
        it.reset()
        last = None
        for batch in it:
            last = batch
        assert last.pad == 28
        tails.append(last.data[0].asnumpy().copy())
        assert len(it._tail_bufs) == 2  # one staging buffer per source
    # wraparound contents are correct and stable across epochs
    np.testing.assert_array_equal(tails[0],
                                  np.concatenate([X[96:], X[:28]]))
    np.testing.assert_array_equal(tails[0], tails[1])


def test_nested_slice_views_compose():
    a = nd.array(np.arange(40, dtype=np.float32).reshape(20, 2))
    v = a[4:16]
    w = v[2:6]  # slice of a slice composes against the root
    np.testing.assert_array_equal(w.asnumpy(), np.arange(40).reshape(20, 2)[6:10])
    # clipped against the outer view's extent
    np.testing.assert_array_equal(v[8:999].asnumpy(),
                                  np.arange(40).reshape(20, 2)[12:16])
    # int / negative / stepped keys compose against the root too —
    # write-through views, same contract as single-level views
    ref = np.arange(40, dtype=np.float32).reshape(20, 2)[4:16]
    np.testing.assert_array_equal(v[0].asnumpy(), ref[0])       # int
    np.testing.assert_array_equal(v[-2:].asnumpy(), ref[-2:])   # negative
    np.testing.assert_array_equal(v[::2].asnumpy(), ref[::2])   # step
    rows = [r.asnumpy() for r in v]                             # iteration
    np.testing.assert_array_equal(np.stack(rows), ref)
    w = v[::2]
    assert w._base is not None
    w[:] = 0.0  # flows back to the root
    got = a.asnumpy()
    expect = np.arange(40, dtype=np.float32).reshape(20, 2)
    expect[4:16:2] = 0.0
    np.testing.assert_array_equal(got, expect)
    # keys with no single-root-index form (fancy/tuple) materialize a
    # detached copy, like take()
    t = v[(slice(0, 2), 0)]
    assert t._base is None
    np.testing.assert_array_equal(t.asnumpy(), expect[4:6, 0])


def test_multi_context_local_training_with_view_batches():
    """The per-executor path re-slices iterator batches per device —
    zero-copy views must survive that (slice-of-slice)."""
    X, y = _data(n=128, seed=9)
    it = mx.io.NDArrayIter(X, y, batch_size=64)
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(0), mx.cpu(1)])
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(kvstore="local", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    metric = mx.metric.Accuracy()
    for _ in range(3):
        it.reset()
        for batch in it:
            mod.forward_backward(batch)
            mod.update()
            mod.update_metric(metric, batch.label)
    assert metric.num_inst == 3 * 128


def test_metric_update_dict_batches_device_get(monkeypatch):
    """update_dict does ONE tree device_get for all device arrays."""
    import jax

    calls = []
    orig = jax.device_get

    def counting_device_get(x):
        calls.append(x)
        return orig(x)

    monkeypatch.setattr(jax, "device_get", counting_device_get)
    m = mx.metric.CompositeEvalMetric(
        metrics=[mx.metric.Accuracy(), mx.metric.MSE()])
    rng = np.random.RandomState(0)
    probs = jax.numpy.asarray(rng.rand(16, 4).astype(np.float32))
    label = jax.numpy.asarray(rng.randint(0, 4, (16,)).astype(np.float32))
    m.update_dict({"softmax_label": label},
                  {"softmax_output": nd.NDArray(probs)})
    assert len(calls) == 1  # one batched fetch, not one per array
    assert m.metrics[0].num_inst == 16


def test_bench_input_tool_smoke(tmp_path):
    """tools/bench_input.py emits the bench.py-style JSON line with the
    sync/pipelined/device-resident comparison and zero pipelined host
    syncs (ISSUE 5 CI satellite; absolute rates are host-dependent)."""
    from test_io_pipeline import _run_tool

    lines = _run_tool("bench_input.py", "--batch-size", "64",
                      "--num-batches", "4", "--dim", "128", "--hidden",
                      "32", "--classes", "4", "--epochs", "2", timeout=300)
    (rec,) = [l for l in lines
              if l.get("metric") == "input_pipeline_fit_throughput"]
    assert rec["value"] > 0
    for field in ("sync_img_s", "pipelined_img_s", "device_resident_img_s",
                  "pipeline_speedup", "host_syncs_sync"):
        assert field in rec, rec
    assert rec["host_syncs_pipelined"] == 0, rec
