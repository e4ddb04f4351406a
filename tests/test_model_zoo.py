"""Model zoo + flagship transformer tests (≙ reference
tests/python/unittest/test_gluon_model_zoo.py). Small inputs on the CPU mesh;
the full-resolution step is the benchmark's (`resnet50_train.feed`)."""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.gluon.model_zoo import vision


@pytest.mark.parametrize("name", ["resnet18_v1", "resnet18_v2",
                                  "mobilenet0.25", "squeezenet1.1"])
def test_zoo_forward(name):
    net = vision.get_model(name, classes=10)
    net.initialize()
    x = mx.np.array(np.random.randn(1, 3, 64, 64).astype(np.float32))
    y = net(x)
    assert y.shape == (1, 10)


def test_resnet50_structure():
    net = vision.resnet50_v1(classes=7)
    net.initialize()
    params = net.collect_params()
    # bottleneck resnet50: 53 conv layers + fc
    n_conv = sum(1 for k in params if k.endswith("weight") and
                 len(params[k].shape or ()) == 4)
    assert n_conv == 53
    x = mx.np.array(np.random.randn(1, 3, 96, 96).astype(np.float32))
    assert net(x).shape == (1, 7)


def test_zoo_train_step():
    from incubator_mxnet_tpu import gluon
    net = vision.get_model("resnet18_v1", classes=4)
    net.initialize()
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = mx.np.array(np.random.randn(2, 3, 32, 32).astype(np.float32))
    y = mx.np.array(np.array([0, 1]))
    before = net.output.weight.data().asnumpy().copy()
    with mx.autograd.record():
        L = loss_fn(net(x), y).mean()
    L.backward()
    trainer.step(2)
    after = net.output.weight.data().asnumpy()
    assert not np.allclose(before, after)
    assert np.isfinite(after).all()


def test_get_model_unknown():
    with pytest.raises(mx.MXNetError):
        vision.get_model("resnet9000")


def test_transformer_forward_and_grad():
    import jax
    from incubator_mxnet_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=64, num_layers=2, d_model=32,
                                num_heads=4, d_ff=64, max_seq_len=16,
                                dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = np.random.randint(0, 64, (2, 9)).astype(np.int32)
    logits = tfm.forward(params, tokens, cfg)
    assert logits.shape == (2, 9, 64)
    loss, grads = jax.value_and_grad(
        lambda p: tfm.loss_fn(p, {"tokens": tokens}, cfg))(params)
    assert np.isfinite(float(loss))
    flat = jax.tree_util.tree_leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)


def test_transformer_train_step_reduces_loss():
    import jax
    from incubator_mxnet_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=32, num_layers=1, d_model=32,
                                num_heads=4, d_ff=64, max_seq_len=16,
                                dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    opt = tfm.init_opt_state(params)
    step_fn = tfm.make_train_step(cfg, learning_rate=1e-2)
    tokens = np.tile(np.arange(9, dtype=np.int32), (4, 1))  # memorizable
    batch = {"tokens": tokens}
    losses = []
    for i in range(10):
        params, opt, loss = step_fn(params, opt, batch, np.int32(i))
        losses.append(float(loss))
    assert losses[-1] < losses[0]
