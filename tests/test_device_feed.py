"""io.DeviceFeed — async host→device input pipeline (ISSUE 4).

Contract under test: device-fed training is bitwise-identical to host-fed
(the feed only moves bytes earlier), feeder failures re-raise the ORIGINAL
exception in the consumer with a bounded consecutive-restart budget
(PrefetchingIter semantics), sharding-aware placement over a dp mesh,
transparent estimator/DataLoader opt-in via MXNET_PREFETCH_TO_DEVICE, and
the FusedTrainStep redundant-transfer skip.
"""
import json

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import fault, gluon, io as mxio
from incubator_mxnet_tpu import optimizer as opt_mod, parallel, profiler
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.gluon.contrib import FusedTrainStep
from incubator_mxnet_tpu.io.device_feed import DeviceFeed, maybe_device_put


def _batches(n=4, b=8, din=8, dout=4, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(b, din).astype(np.float32),
             rng.randn(b, dout).astype(np.float32)) for _ in range(n)]


def _mlp(seed=0):
    mx.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu", in_units=8),
            nn.Dense(4, in_units=16))
    net.initialize()
    return net


# ---------------------------------------------------------------------------
# staging basics
# ---------------------------------------------------------------------------
def test_device_feed_stages_batches_committed():
    import jax
    data = _batches(5)
    feed = DeviceFeed(data, depth=2)
    out = list(feed)
    assert len(out) == 5
    for (hx, hy), staged in zip(data, out):
        x, y = staged
        assert isinstance(x, mx.nd.NDArray)
        assert isinstance(x._arr, jax.Array) and x._arr.committed
        np.testing.assert_array_equal(x.asnumpy(), hx)
        np.testing.assert_array_equal(y.asnumpy(), hy)
    # a second epoch re-iterates the source
    assert len(list(feed)) == 5
    assert len(feed) == 5


def test_device_feed_databatch_and_passthrough():
    it = mxio.NDArrayIter(np.random.rand(20, 3).astype(np.float32),
                          np.arange(20, dtype=np.float32), batch_size=5)
    n = 0
    for b in DeviceFeed(it):
        n += 1
        assert isinstance(b, mxio.DataBatch)
        assert b.data[0]._arr.committed and b.label[0]._arr.committed
        assert b.pad == 0
    assert n == 4
    # non-array leaves pass through untouched
    feed = DeviceFeed([{"x": np.ones(2, np.float32), "tag": "a", "n": 3}])
    out = list(feed)[0]
    assert out["tag"] == "a" and out["n"] == 3
    assert out["x"]._arr.committed


def test_device_feed_preserves_namedtuple_batches():
    from collections import namedtuple
    Batch = namedtuple("Batch", ["x", "y"])
    src = [Batch(np.ones((4, 2), np.float32), np.zeros(4, np.float32))]
    out = list(DeviceFeed(src))[0]
    assert type(out) is Batch               # field access survives staging
    assert out.x._arr.committed and out.y._arr.committed


def test_device_feed_depth_validation_and_env(monkeypatch):
    with pytest.raises(mx.MXNetError, match="depth"):
        DeviceFeed([], depth=0)
    monkeypatch.setenv("MXNET_DEVICE_FEED_DEPTH", "3")
    assert DeviceFeed([])._depth == 3


def test_device_feed_honors_consumer_device_scope():
    """The consumer thread's `with mx.cpu(i):` scope decides placement —
    the feeder thread's (empty) thread-local stacks must not."""
    import jax
    want = jax.local_devices(backend="cpu")[1]   # 8 forced host devices
    with mx.cpu(1):
        out = list(DeviceFeed([np.ones((4, 2), np.float32)]))[0]
    assert out._arr.committed
    assert tuple(out._arr.sharding.device_set) == (want,)


def test_device_feed_reset_passthrough():
    it = mxio.NDArrayIter(np.arange(12, dtype=np.float32).reshape(12, 1),
                          batch_size=4)
    feed = DeviceFeed(it)
    assert len(list(feed)) == 3
    feed.reset()     # forwards to NDArrayIter.reset -> epoch 2 has batches
    assert len(list(feed)) == 3


# ---------------------------------------------------------------------------
# parity: device-fed == host-fed, bitwise
# ---------------------------------------------------------------------------
def test_device_fed_fused_step_bitwise_parity():
    data = _batches(6, seed=3)
    loss_fn = gluon.loss.L2Loss()

    def make_step(net):
        return FusedTrainStep(
            net, lambda n, x, y: loss_fn(n(x), y).mean(),
            opt_mod.create("sgd", learning_rate=0.1, momentum=0.9))

    net_a = _mlp(1)
    step = make_step(net_a)
    for x, y in data:                       # host-fed
        step(mx.np.array(x), mx.np.array(y))

    net_b = _mlp(1)
    step = make_step(net_b)
    for x, y in DeviceFeed(data):           # device-fed
        step(x, y)

    pa, pb = net_a.collect_params(), net_b.collect_params()
    assert set(pa) == set(pb)
    for k in pa:
        a, b = pa[k].data().asnumpy(), pb[k].data().asnumpy()
        np.testing.assert_array_equal(a, b, err_msg=k)  # BITWISE


# ---------------------------------------------------------------------------
# failure semantics
# ---------------------------------------------------------------------------
def test_feeder_death_surfaces_original_exception():
    class Boom(RuntimeError):
        pass

    def source():
        yield np.zeros(3, np.float32)
        raise Boom("feeder died")

    feed = DeviceFeed(source())
    it = iter(feed)
    next(it)
    with pytest.raises(Boom, match="feeder died"):
        next(it)


def test_feeder_restart_budget():
    profiler.feed_stats(reset=True)
    # persistent transient fault: budget of 2 consecutive restarts is
    # consumed, the 3rd hit re-raises the ORIGINAL IOError in the consumer
    with fault.scope("io.device_feed:*:ioerror"):
        feed = DeviceFeed([np.zeros(2, np.float32)] * 3, max_restarts=2)
        with pytest.raises(IOError, match="injected ioerror"):
            list(feed)
    s = profiler.feed_stats()
    assert s["restarts"] == 2
    assert s["failures"] == 1
    # a single transient hit is retried in place: nothing lost
    with fault.scope("io.device_feed:2:ioerror"):
        feed = DeviceFeed([np.zeros(2, np.float32)] * 3, max_restarts=2)
        assert len(list(feed)) == 3


# ---------------------------------------------------------------------------
# sharding over a dp mesh
# ---------------------------------------------------------------------------
def test_prefetch_to_device_dp_sharding():
    mesh = parallel.make_mesh(dp=8)
    with mesh:
        feed = mxio.prefetch_to_device(
            [np.random.rand(16, 4).astype(np.float32) for _ in range(3)])
        outs = list(feed)
    assert len(outs) == 3
    want = mesh.sharding("dp", None)
    for b in outs:
        assert b._arr.sharding.is_equivalent_to(want, 2)
    # helper returns None with no mesh / no dp axis
    assert parallel.data_sharding(2) is None


# ---------------------------------------------------------------------------
# FusedTrainStep input staging (satellite: redundant-transfer skip)
# ---------------------------------------------------------------------------
def test_fused_step_skips_committed_inputs():
    data = _batches(3, seed=5)
    loss_fn = gluon.loss.L2Loss()
    net = _mlp(2)
    step = FusedTrainStep(net, lambda n, x, y: loss_fn(n(x), y).mean(),
                          opt_mod.create("sgd", learning_rate=0.1))
    profiler.feed_stats(reset=True)
    for x, y in DeviceFeed(data):
        step(x, y)
    s = profiler.feed_stats()
    # the feed transferred each leaf once; the step re-transferred NOTHING
    assert s["host_transfers"] == 6       # 3 batches x 2 leaves, feed-side
    assert s["device_put_skipped"] == 6   # step-side: all skips
    # raw numpy fed straight to the step counts as a real transfer
    profiler.feed_stats(reset=True)
    step(data[0][0], data[0][1])
    s = profiler.feed_stats()
    assert s["host_transfers"] == 2 and s["device_put_skipped"] == 0


def test_maybe_device_put_counters():
    import jax
    import jax.numpy as jnp
    profiler.feed_stats(reset=True)
    a = maybe_device_put(np.ones(4, np.float32))       # host -> transfer
    assert a.committed
    b = maybe_device_put(a)                            # committed -> skip
    assert b is a
    c = maybe_device_put(jnp.ones(4))                  # uncommitted -> pin
    assert c.committed
    s = profiler.feed_stats()
    assert (s["host_transfers"], s["device_put_skipped"],
            s["recommitted"]) == (1, 1, 1)


# ---------------------------------------------------------------------------
# transparent opt-in: estimator.fit + DataLoader
# ---------------------------------------------------------------------------
def test_estimator_env_optin(monkeypatch):
    monkeypatch.setenv("MXNET_PREFETCH_TO_DEVICE", "1")
    net = _mlp(4)
    est = gluon.contrib.estimator.Estimator(
        net, gluon.loss.L2Loss(),
        train_metrics=gluon.metric.Loss("l"),
        trainer=gluon.Trainer(net.collect_params(), "sgd",
                              {"learning_rate": 0.05}))
    data = [(mx.np.array(x), mx.np.array(y)) for x, y in _batches(3)]
    profiler.feed_stats(reset=True)
    est.fit(train_data=data, epochs=2)
    s = profiler.feed_stats()
    assert s["batches_consumed"] == 6     # fit consumed through the feed
    assert s["epochs"] == 2


def test_dataloader_prefetch_to_device():
    from incubator_mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    ds = ArrayDataset(np.arange(24, dtype=np.float32).reshape(12, 2),
                      np.arange(12, dtype=np.float32))
    dl = DataLoader(ds, batch_size=4, prefetch_to_device=True)
    assert dl._feeds_device
    profiler.feed_stats(reset=True)
    seen = list(dl)
    assert len(seen) == 3
    for x, y in seen:
        assert x._arr.committed and y._arr.committed
    assert profiler.feed_stats()["batches_fed"] == 3
    # off by default: plain host batches, no feeder involvement
    dl = DataLoader(ds, batch_size=4)
    assert not dl._feeds_device


def test_estimator_respects_explicit_loader_optout(monkeypatch):
    """DataLoader(prefetch_to_device=False) is an explicit opt-out the
    env-driven estimator wrap must not override."""
    from incubator_mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    monkeypatch.setenv("MXNET_PREFETCH_TO_DEVICE", "1")
    ds = ArrayDataset(np.random.rand(12, 8).astype(np.float32),
                      np.random.rand(12, 4).astype(np.float32))
    dl = DataLoader(ds, batch_size=4, prefetch_to_device=False)
    assert dl._prefetch_opt_out
    net = _mlp(6)
    est = gluon.contrib.estimator.Estimator(
        net, gluon.loss.L2Loss(),
        train_metrics=gluon.metric.Loss("l"),
        trainer=gluon.Trainer(net.collect_params(), "sgd",
                              {"learning_rate": 0.05}))
    profiler.feed_stats(reset=True)
    est.fit(train_data=dl, epochs=1)
    assert profiler.feed_stats()["batches_consumed"] == 0  # no feed involved


# ---------------------------------------------------------------------------
# satellite: PrefetchingIter composition fixes
# ---------------------------------------------------------------------------
def test_prefetching_iter_multi_iter_message_names_wrapper():
    it = mxio.NDArrayIter(np.zeros((8, 2), np.float32), batch_size=4)
    it2 = mxio.NDArrayIter(np.zeros((8, 2), np.float32), batch_size=4)
    with pytest.raises(mx.MXNetError, match="DeviceFeed"):
        mxio.PrefetchingIter([it, it2])


def test_prefetching_iter_len_passthrough():
    it = mxio.NDArrayIter(np.zeros((10, 2), np.float32), batch_size=4)
    assert len(it) == 3                    # pad: ceil(10/4)
    pf = mxio.PrefetchingIter(it)
    assert len(pf) == 3
    assert pf.provide_data == it.provide_data
    # composes with DeviceFeed (feeds DataBatches through) and epoch loops
    feed = DeviceFeed(pf)
    assert len(feed) == 3
    assert sum(1 for _ in feed) == 3


# ---------------------------------------------------------------------------
# stats + trace lane
# ---------------------------------------------------------------------------
def test_feed_stats_occupancy_and_stall_accounting():
    profiler.feed_stats(reset=True)
    feed = DeviceFeed(_batches(4), depth=2)
    list(feed)
    s = profiler.feed_stats()
    assert s["batches_fed"] == 4 and s["batches_consumed"] == 4
    assert s["occupancy_samples"] == 4     # REAL batches only, no sentinel
    assert 0.0 < s["occupancy_mean"] <= 3.0
    assert s["stall_data_us"] >= 0.0 and s["stall_compute_us"] >= 0.0
    # reset zeroes
    s = profiler.feed_stats(reset=True)
    assert profiler.feed_stats()["batches_fed"] == 0


def test_feed_chrome_trace_lane(tmp_path):
    profiler.start()
    try:
        list(DeviceFeed(_batches(2)))
    finally:
        profiler.stop()
    out = str(tmp_path / "trace.json")
    profiler.dump(filename=out)
    with open(out) as f:
        names = {e["name"] for e in json.load(f)["traceEvents"]}
    assert "io.feed" in names and "feed.stage" in names
