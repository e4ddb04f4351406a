"""Nightly: ResNet-50 short-horizon convergence on the real chip.

≙ the reference's tests/python/train/ convergence suite: a few hundred
fused train steps on a fixed synthetic 16-class problem must drive the loss
decisively below its initial value (loss-trajectory assertion — the
north-star "identical convergence" clause needs automated evidence, not
examples).
"""
import os

import numpy as np
import pytest


def _skip_cpu_convergence():
    # the suite conftest forces the CPU platform; 120 ResNet-50 steps
    # there blow any CI budget regardless of the advertised core count
    # (sandboxed many-core hosts report 24 cores and deliver a fraction
    # of that — the old <4-core carve-out silently turned this into a
    # >14-minute tier-1 hang). On a real accelerator backend the test is
    # cheap and always runs; MXTPU_NIGHTLY_CPU_CONVERGENCE=1 opts a
    # genuinely beefy CPU host back in.
    import jax
    try:
        backend = jax.default_backend()
    except RuntimeError:
        backend = "cpu"
    return (backend == "cpu"
            and os.environ.get("MXTPU_NIGHTLY_CPU_CONVERGENCE") != "1")


@pytest.mark.nightly
@pytest.mark.skipif(
    _skip_cpu_convergence(),
    reason="CPU fallback platform: 120 ResNet-50 train steps blow the CI "
           "budget (MXTPU_NIGHTLY_CPU_CONVERGENCE=1 opts in); the "
           "chip's train step is `resnet50_train.feed` (chipbench)")
def test_resnet50_loss_trajectory_on_chip():
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import amp, gluon
    from incubator_mxnet_tpu import optimizer as opt_mod
    from incubator_mxnet_tpu.gluon.contrib import FusedTrainStep
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    amp.init("bfloat16")
    try:
        net = vision.resnet50_v1(classes=16, layout="NHWC")
        net.initialize()
        net.hybridize()
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

        rng = np.random.RandomState(0)
        n, bs = 256, 32
        # separable synthetic data: class-dependent mean patches
        ys = rng.randint(0, 16, (n,))
        xs = rng.randn(n, 224, 224, 3).astype(np.float32) * 0.5
        for i in range(n):
            xs[i] += (ys[i] / 16.0 - 0.5)
        net(mx.np.array(xs[:bs]))
        opt = opt_mod.create("sgd", learning_rate=0.02, momentum=0.9,
                             rescale_grad=1.0 / bs)
        step = FusedTrainStep(net, lambda m, x, y: loss_fn(m(x), y).sum(),
                              opt)

        losses = []
        for it in range(120):
            i0 = (it * bs) % n
            L = step(mx.np.array(xs[i0:i0 + bs]),
                     mx.np.array(ys[i0:i0 + bs]))
            losses.append(float(L.asnumpy()) / bs)
        first = np.mean(losses[:8])
        last = np.mean(losses[-8:])
        assert last < first * 0.5, (first, last)
        assert np.isfinite(losses).all()
    finally:
        amp.uninit()
