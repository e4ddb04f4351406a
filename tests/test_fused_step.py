"""FusedTrainStep: one-XLA-program training must match the eager tape path.

≙ the reference's fused RNN training capability (src/operator/rnn.cc) —
here generalized: fwd + loss + bwd + clip + optimizer update in one jit.
"""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, optimizer as opt_mod
from incubator_mxnet_tpu.gluon import nn, rnn
from incubator_mxnet_tpu.gluon.contrib import FusedTrainStep


def _mlp(seed=0):
    mx.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu", in_units=8), nn.Dense(4,
                                                                  in_units=16))
    net.initialize()
    return net


def test_fused_step_matches_eager_sgd():
    x = mx.np.array(np.random.randn(8, 8).astype(np.float32))
    y = mx.np.array(np.random.randn(8, 4).astype(np.float32))
    loss_fn = gluon.loss.L2Loss()

    # eager tape path
    net_a = _mlp(1)
    tr = gluon.Trainer(net_a.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    for _ in range(3):
        with mx.autograd.record():
            L = loss_fn(net_a(x), y).mean()
        L.backward()
        tr.step(1, ignore_stale_grad=True)

    # fused path, same seed -> identical init
    net_b = _mlp(1)
    opt = opt_mod.create("sgd", learning_rate=0.1, momentum=0.9)
    step = FusedTrainStep(net_b, lambda net, x, y: loss_fn(net(x), y).mean(),
                          opt)
    for _ in range(3):
        L2 = step(x, y)
    assert np.isfinite(float(L2.asnumpy()))
    pa, pb = net_a.collect_params(), net_b.collect_params()
    for k in pa:
        np.testing.assert_allclose(pa[k].data().asnumpy(),
                                   pb[k].data().asnumpy(),
                                   rtol=1e-5, atol=1e-6)


def test_fused_step_adam_with_extras_and_clip():
    """Adam (traced t), pass-through extras (recurrent states), grad clip."""
    mx.seed(7)
    net = rnn.LSTM(16, 1, input_size=8)
    net.initialize()
    x = mx.np.array(np.random.randn(5, 4, 8).astype(np.float32))
    states = net.begin_state(4)
    _ = net(x, states)  # resolve shapes
    opt = opt_mod.create("adam", learning_rate=1e-2)

    def fn(net, x, h, c):
        out, (h2, c2) = net(x, [h, c])
        return (out * out).mean(), h2, c2

    step = FusedTrainStep(net, fn, opt, clip_global_norm=1.0)
    h, c = states
    losses = []
    for _ in range(4):
        L, h, c = step(x, h, c)
        losses.append(float(L.asnumpy()))
    assert all(np.isfinite(v) for v in losses)
    assert losses[-1] < losses[0]  # optimizes
    assert h.shape == (1, 4, 16)


def test_fused_step_batchnorm_aux_updates():
    """BN running stats (grad_req='null' params) update through the step."""
    mx.seed(3)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4), nn.BatchNorm(in_channels=8))
    net.initialize()
    loss_fn = gluon.loss.L2Loss()
    x = mx.np.array(np.random.randn(16, 4).astype(np.float32) * 3 + 1)
    y = mx.np.array(np.zeros((16, 8), np.float32))
    before = {k: p.data().asnumpy().copy()
              for k, p in net.collect_params().items()
              if "running" in k}
    assert before
    step = FusedTrainStep(net, lambda net, x, y: loss_fn(net(x), y).mean(),
                          "sgd")
    step(x, y)
    after = {k: p.data().asnumpy()
             for k, p in net.collect_params().items() if "running" in k}
    changed = any(np.abs(before[k] - after[k]).max() > 1e-7 for k in before)
    assert changed, "running stats did not update"


def test_fused_step_requires_initialized_net():
    net = nn.Dense(4)  # deferred in_units
    net.initialize()
    with pytest.raises(mx.MXNetError, match="initialized"):
        FusedTrainStep(net, lambda n, x: n(x).sum(), "sgd")


def test_fused_step_honors_param_multipliers():
    """lr_mult/wd_mult on Parameters must flow into the fused update the
    same way gluon.Trainer resolves them (via optimizer.param_dict)."""
    x = mx.np.array(np.random.randn(8, 8).astype(np.float32))
    y = mx.np.array(np.random.randn(8, 4).astype(np.float32))
    loss_fn = gluon.loss.L2Loss()

    def freeze_mults(net):
        for name, p in net.collect_params().items():
            if name.endswith("bias"):
                p.lr_mult = 0.0   # biases must not move at all

    net_a = _mlp(3)
    freeze_mults(net_a)
    tr = gluon.Trainer(net_a.collect_params(), "sgd",
                       {"learning_rate": 0.1})
    with mx.autograd.record():
        L = loss_fn(net_a(x), y).mean()
    L.backward()
    tr.step(1, ignore_stale_grad=True)

    net_b = _mlp(3)
    freeze_mults(net_b)
    step = FusedTrainStep(net_b, lambda n, xx, yy: loss_fn(n(xx), yy).mean(),
                          opt_mod.create("sgd", learning_rate=0.1))
    step(x, y)

    for (name, pa), (_, pb) in zip(sorted(net_a.collect_params().items()),
                                   sorted(net_b.collect_params().items())):
        np.testing.assert_allclose(pa.data().asnumpy(),
                                   pb.data().asnumpy(), rtol=1e-6,
                                   atol=1e-6, err_msg=name)
        if name.endswith("bias"):
            # and specifically: unchanged from init
            net_c = _mlp(3)
            init = dict(net_c.collect_params().items())[name]
            np.testing.assert_allclose(pb.data().asnumpy(),
                                       init.data().asnumpy(), rtol=0,
                                       atol=0, err_msg=name)


def test_remat_policies_numerically_identical():
    """remat trades FLOPs for residual HBM traffic — it must never change
    the math. All three policies produce identical losses and weights."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu import optimizer as opt_mod
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.gluon.contrib import FusedTrainStep

    def make():
        mx.seed(5)
        net = nn.HybridSequential()
        net.add(nn.Conv2D(8, 3, padding=1, layout="NHWC"),
                nn.BatchNorm(axis=3), nn.Activation("relu"),
                nn.Flatten(), nn.Dense(10))
        net.initialize()
        net.hybridize()
        return net

    x = mx.np.array(np.random.RandomState(0).rand(4, 8, 8, 3)
                    .astype(np.float32))
    y = mx.np.array(np.random.RandomState(1).randint(0, 10, (4,)))
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    outs = {}
    for remat in (None, "full", "dots"):
        net = make()
        net(x)
        step = FusedTrainStep(net, lambda n, a, b: L(n(a), b).sum(),
                              opt_mod.create("sgd", learning_rate=0.1),
                              remat=remat)
        for _ in range(3):
            loss = step(x, y)
        outs[remat] = (float(loss.asnumpy()),
                       list(net.collect_params().values())[0]
                       .data().asnumpy())
    for k in ("full", "dots"):
        np.testing.assert_allclose(outs[k][0], outs[None][0], rtol=1e-5)
        np.testing.assert_allclose(outs[k][1], outs[None][1],
                                   rtol=1e-5, atol=1e-6)
    with pytest.raises(mx.MXNetError):
        FusedTrainStep(make(), lambda n, a, b: L(n(a), b).sum(),
                       opt_mod.create("sgd"), remat="bogus")
