"""Estimator, probability, and native-IO tests (≙ reference
tests/python/unittest/test_gluon_estimator.py, test_gluon_probability_v2.py)."""
import os
import struct

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon
from incubator_mxnet_tpu.gluon import nn


# ---------------------------------------------------------------------------
# estimator
# ---------------------------------------------------------------------------
def _toy_loader(n=64, d=8, batch=16):
    from incubator_mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    rng = np.random.default_rng(0)
    X = rng.standard_normal((n, d)).astype(np.float32)
    Y = (X.sum(1) > 0).astype(np.int32)
    return DataLoader(ArrayDataset(X, Y), batch_size=batch)


def _toy_net():
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(2))
    net.initialize()
    return net


def test_estimator_fit_and_handlers():
    from incubator_mxnet_tpu.gluon.contrib import estimator as est
    net = _toy_net()
    e = est.Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                      trainer=gluon.Trainer(net.collect_params(), "adam",
                                            {"learning_rate": 0.05}))
    events = []

    class Spy(est.EpochBegin, est.EpochEnd, est.BatchEnd):
        def epoch_begin(self, estimator, **kw):
            events.append("eb")

        def epoch_end(self, estimator, **kw):
            events.append("ee")

        def batch_end(self, estimator, **kw):
            events.append("b")

    e.fit(_toy_loader(), epochs=2, event_handlers=[Spy()])
    assert events.count("eb") == 2 and events.count("ee") == 2
    assert events.count("b") == 8
    name, acc = e.train_metrics[0].get()
    assert name == "accuracy" and 0 <= acc <= 1


def test_estimator_early_stopping_and_checkpoint(tmp_path):
    from incubator_mxnet_tpu.gluon import metric
    from incubator_mxnet_tpu.gluon.contrib import estimator as est
    net = _toy_net()
    e = est.Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss())
    loss_metric = e.train_metrics[-1]
    early = est.EarlyStoppingHandler(loss_metric, patience=0, mode="min")
    ckpt = est.CheckpointHandler(str(tmp_path), save_best=False)
    e.fit(_toy_loader(), epochs=5, event_handlers=[early, ckpt])
    files = os.listdir(tmp_path)
    assert any(f.endswith(".params.npz") for f in files)


def test_estimator_max_batches():
    from incubator_mxnet_tpu.gluon.contrib import estimator as est
    net = _toy_net()
    e = est.Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss())
    e.fit(_toy_loader(), batches=3)
    # StoppingHandler halted inside the first epoch
    assert e.stop_training


# ---------------------------------------------------------------------------
# probability
# ---------------------------------------------------------------------------
def test_normal_logprob_matches_scipy_form():
    from incubator_mxnet_tpu.gluon import probability as pr
    n = pr.Normal(loc=0.0, scale=1.0)
    lp = float(n.log_prob(mx.np.zeros(())).asnumpy())
    assert abs(lp - (-0.5 * np.log(2 * np.pi))) < 1e-5


def test_normal_sampling_moments():
    from incubator_mxnet_tpu.gluon import probability as pr
    mx.seed(42)
    n = pr.Normal(loc=2.0, scale=3.0)
    s = n.sample((20000,)).asnumpy()
    assert abs(s.mean() - 2.0) < 0.1
    assert abs(s.std() - 3.0) < 0.1


def test_kl_normal_closed_form():
    from incubator_mxnet_tpu.gluon import probability as pr
    p = pr.Normal(1.0, 2.0)
    q = pr.Normal(0.0, 1.0)
    kl = float(pr.kl_divergence(p, q).asnumpy())
    expected = np.log(1 / 2.0) + (4 + 1) / 2.0 - 0.5
    assert abs(kl - expected) < 1e-5


def test_bernoulli_categorical():
    from incubator_mxnet_tpu.gluon import probability as pr
    b = pr.Bernoulli(prob=mx.np.array([0.3]))
    lp = b.log_prob(mx.np.array([1.0])).asnumpy()
    np.testing.assert_allclose(lp, np.log(0.3), rtol=1e-5)
    with pytest.raises(mx.MXNetError):
        pr.Bernoulli()
    c = pr.Categorical(logit=mx.np.array(np.zeros((4,), np.float32)))
    lp = float(c.log_prob(mx.np.array(2)).asnumpy())
    assert abs(lp - np.log(0.25)) < 1e-5


def test_gamma_beta_dirichlet():
    from incubator_mxnet_tpu.gluon import probability as pr
    mx.seed(3)
    g = pr.Gamma(shape=3.0, scale=2.0)
    s = g.sample((5000,)).asnumpy()
    assert abs(s.mean() - 6.0) < 0.3
    d = pr.Dirichlet(mx.np.array([1.0, 1.0, 1.0]))
    samp = d.sample((100,)).asnumpy()
    np.testing.assert_allclose(samp.sum(-1), np.ones(100), rtol=1e-5)


def test_mvn_logprob():
    from incubator_mxnet_tpu.gluon import probability as pr
    cov = np.array([[2.0, 0.5], [0.5, 1.0]], np.float32)
    m = pr.MultivariateNormal(loc=mx.np.zeros((2,)), cov=mx.np.array(cov))
    lp = float(m.log_prob(mx.np.zeros((2,))).asnumpy())
    expected = -0.5 * np.log((2 * np.pi) ** 2 * np.linalg.det(cov))
    assert abs(lp - expected) < 1e-4


def test_stochastic_block_collects_losses():
    from incubator_mxnet_tpu.gluon import probability as pr

    class VAEBlock(pr.StochasticBlock):
        def forward(self, x):
            self.add_loss(x.sum())
            return x * 2

    blk = VAEBlock()
    out = blk(mx.np.ones((2, 2)))
    assert len(blk.losses) == 1
    assert float(blk.losses[0].asnumpy()) == 4.0


# ---------------------------------------------------------------------------
# native recordio
# ---------------------------------------------------------------------------
def test_native_recordio_matches_python(tmp_path):
    from incubator_mxnet_tpu import recordio
    from incubator_mxnet_tpu.native import load_recordio, NativeRecordFile
    if load_recordio() is None:
        pytest.skip("no C++ toolchain")
    path = str(tmp_path / "t.rec")
    w = recordio.MXRecordIO(path, "w")
    payloads = [b"hello", b"B" * 1000,
                b"A" * 5 + struct.pack("<I", 0x3ed7230a) + b"C" * 7]
    for p in payloads:
        w.write(p)
    w.close()
    nr = NativeRecordFile(path)
    assert len(nr) == 3
    for i, p in enumerate(payloads):
        assert nr.read(i) == p
    batch = nr.read_batch([0, 2], stride=8)
    assert batch.shape == (2, 8)
    assert batch[0].tobytes()[:5] == b"hello"
    nr.close()


# ---------------------------------------------------------------------------
# checkpoint / visualization
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path):
    from incubator_mxnet_tpu import checkpoint
    net = _toy_net()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    x = mx.np.ones((2, 8))
    with mx.autograd.record():
        net(x).sum().backward()
    trainer.step(2)
    path = str(tmp_path / "ckpt.npz")
    checkpoint.save_checkpoint(path, net, step=7, trainer=trainer)
    net2 = _toy_net()
    trainer2 = gluon.Trainer(net2.collect_params(), "sgd",
                             {"learning_rate": 0.1, "momentum": 0.9})
    params, step = checkpoint.load_checkpoint(path, net=net2,
                                              trainer=trainer2)
    assert step == 7
    for k, p in net.collect_params().items():
        np.testing.assert_array_equal(
            p.data().asnumpy(), net2.collect_params()[k].data().asnumpy())


def test_checkpoint_extensionless_path_and_underscore_keys(tmp_path):
    """Regression: np.savez silently appends .npz (breaking save->load on
    extension-less paths), and '__' in a param name used to collide with
    the '/' separator encoding."""
    from incubator_mxnet_tpu import checkpoint
    params = {"encoder__block_1": {"w__bias": mx.np.ones((2, 2)),
                                   "_private": mx.np.zeros((3,))}}
    path = checkpoint.save_checkpoint(str(tmp_path / "ckpt"), params, step=4)
    assert path.endswith(".npz")
    loaded, step = checkpoint.load_checkpoint(str(tmp_path / "ckpt"))
    assert step == 4
    assert set(loaded) == {"encoder__block_1/w__bias",
                           "encoder__block_1/_private"}
    np.testing.assert_array_equal(
        loaded["encoder__block_1/w__bias"].asnumpy(), np.ones((2, 2)))


def test_checkpoint_legacy_v1_format_loads(tmp_path):
    """v1 files (no __fmt__ marker, '/'->'__' keys) still load correctly."""
    from incubator_mxnet_tpu import checkpoint
    path = str(tmp_path / "old.npz")
    np.savez(path, __step__=np.asarray(3),
             **{"encoder__w": np.ones((2, 2))})
    loaded, step = checkpoint.load_checkpoint(path)
    assert step == 3
    assert set(loaded) == {"encoder/w"}


def test_sharded_checkpoint_restore_with_target_resharding(tmp_path):
    """load_sharded(target=...) must honor the target tree's shardings
    (orbax args API) instead of silently ignoring it."""
    from incubator_mxnet_tpu import checkpoint
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    try:
        import orbax.checkpoint  # noqa: F401
    except ImportError:
        pytest.skip("orbax unavailable")
    tree = {"w": jnp.arange(16.0).reshape(8, 2)}
    checkpoint.save_sharded(str(tmp_path / "s"), tree, step=1)
    devs = jax.devices("cpu")[:4]
    mesh = jax.sharding.Mesh(np.array(devs), ("dp",))
    sharding = NamedSharding(mesh, P("dp", None))
    target = {"w": jax.device_put(jnp.zeros((8, 2)), sharding)}
    restored, step = checkpoint.load_sharded(str(tmp_path / "s"),
                                             target=target)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(16.0).reshape(8, 2))
    assert restored["w"].sharding.is_equivalent_to(sharding, 2)


def test_sharded_checkpoint_roundtrip(tmp_path):
    from incubator_mxnet_tpu import checkpoint
    import jax.numpy as jnp
    try:
        import orbax.checkpoint  # noqa: F401
    except ImportError:
        pytest.skip("orbax unavailable")
    tree = {"layer": {"w": jnp.arange(6.0).reshape(2, 3),
                      "b": jnp.zeros(3)},
            "step_count": jnp.asarray(5)}
    checkpoint.save_sharded(str(tmp_path / "sharded"), tree, step=3)
    assert checkpoint.latest_step(str(tmp_path / "sharded")) == 3
    restored, step = checkpoint.load_sharded(str(tmp_path / "sharded"))
    assert step == 3
    np.testing.assert_array_equal(np.asarray(restored["layer"]["w"]),
                                  np.arange(6.0).reshape(2, 3))


def test_visualization(tmp_path):
    from incubator_mxnet_tpu import visualization
    net = _toy_net()
    dot = visualization.plot_network(net, save_path=str(tmp_path / "g.dot"))
    assert "digraph" in dot and "Dense" in dot
    assert (tmp_path / "g.dot").exists()


def test_opperf_harness_smoke():
    """The per-op benchmark harness must run and produce rows (opperf
    parity, /root/reference/benchmark/opperf)."""
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    try:
        import opperf
    finally:
        sys.path.pop(0)
    res = opperf.run(categories=["optimizer"])
    rows = res["optimizer"]
    assert len(rows) == 2
    for r in rows:
        assert "error" not in r, r
        assert r["jit_us"] > 0
