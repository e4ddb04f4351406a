"""Fused kernel tier (ISSUE 8 — mx.ops.fused + ops/pallas_kernels).

Coverage: gradient-parity sweep of every fused op fwd+bwd against its
unfused composition (f32 exact on the fallback path — it IS the
composition — and tolerance-checked on the interpret-mode Pallas kernel
path, custom_vjp backward included; bf16 tolerances), the grad_req
add/null axis through the npx wrappers, gluon block rewires and
model-zoo residual-block parity, FusedTrainStep fused-vs-unfused +
donate on/off parity with ZERO retraces after warmup, fusion gating
(scope / default / MXNET_USE_FUSION), the registration surface (AMP
classes, dispatch-record layout stamps), and `tools/opperf.py`'s fused
category.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, optimizer as opt_mod
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.gluon.contrib import FusedTrainStep
from incubator_mxnet_tpu.ops import fused as F
from incubator_mxnet_tpu.ops import nn as NN
from incubator_mxnet_tpu.ops import registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RNG = np.random.RandomState(7)


def _f(shape, dtype=np.float32):
    return RNG.uniform(-1.5, 1.5, shape).astype(dtype)


def _pos(shape, dtype=np.float32):
    return RNG.uniform(0.5, 1.5, shape).astype(dtype)


# ---------------------------------------------------------------------------
# raw-op parity sweep: fused vs unfused composition, fwd + bwd
# ---------------------------------------------------------------------------
def _pool(x, interpret):
    """The op (the composition on every platform) or, in interpret mode,
    the Pallas pair it no longer routes to, through its custom_vjp."""
    if not interpret:
        return F.avg_pool2d(x, (2, 2))
    _, h, w, _ = x.shape
    return F._kernel_avg_pool(h, w, 2, 2, str(x.dtype), True)(x)


def _op_cases():
    x = _f((64, 128))
    s = _pos((128,))
    b = _f((128,))
    r = _f((64, 128))
    m = _f((128,))
    v = _pos((128,))
    xp = _f((2, 8, 8, 128))
    return [
        ("bias_act",
         lambda ip: F.bias_act(x, b, act_type="relu", interpret=ip),
         lambda: F.bias_act_ref(x, b, act_type="relu"),
         (x, b),
         lambda ip, *a: F.bias_act(*a, act_type="relu", interpret=ip),
         lambda *a: F.bias_act_ref(*a, act_type="relu")),
        ("norm_act_residual",
         lambda ip: F.norm_act_residual(x, s, b, r, act_type="relu",
                                        interpret=ip),
         lambda: F.norm_act_residual_ref(x, s, b, r, act_type="relu"),
         (x, s, b, r),
         lambda ip, *a: F.norm_act_residual(*a, act_type="relu",
                                            interpret=ip),
         lambda *a: F.norm_act_residual_ref(*a, act_type="relu")),
        ("bn_inference",
         lambda ip: F.bn_inference(x, s, b, m, v, act_type="silu",
                                   interpret=ip),
         lambda: F.bn_inference_ref(x, s, b, m, v, act_type="silu"),
         (x, s, b, m, v),
         lambda ip, *a: F.bn_inference(*a, act_type="silu", interpret=ip),
         lambda *a: F.bn_inference_ref(*a, act_type="silu")),
        ("avg_pool2d",
         lambda ip: _pool(xp, ip),
         lambda: F.avg_pool2d_ref(xp, (2, 2)),
         (xp,),
         lambda ip, x: _pool(x, ip),
         lambda *a: F.avg_pool2d_ref(*a, pool_size=(2, 2))),
    ]


@pytest.mark.parametrize("case", _op_cases(), ids=lambda c: c[0])
def test_fallback_is_exactly_the_composition(case):
    """Off-TPU without interpret mode, the fused op IS the unfused jnp
    composition — f32 parity is bitwise by construction."""
    _, fused, ref, *_ = case
    got = np.asarray(fused(False))
    want = np.asarray(ref())
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("case", _op_cases(), ids=lambda c: c[0])
def test_pallas_kernel_forward_parity(case):
    """Interpret-mode Pallas kernel vs the unfused composition."""
    _, fused, ref, *_ = case
    np.testing.assert_allclose(np.asarray(fused(True)),
                               np.asarray(ref()), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("case", _op_cases(), ids=lambda c: c[0])
def test_pallas_kernel_backward_parity(case):
    """custom_vjp (Pallas fwd + hand-derived bwd) vs jax AD of the
    unfused composition, for every differentiable input."""
    import jax
    import jax.numpy as jnp
    name, _, _, args, fused_of, ref_of = case
    argnums = tuple(range(len(args)))
    gk = jax.grad(lambda *a: jnp.sum(fused_of(True, *a) ** 2),
                  argnums=argnums)(*[jnp.asarray(a) for a in args])
    gr = jax.grad(lambda *a: jnp.sum(ref_of(*a) ** 2),
                  argnums=argnums)(*[jnp.asarray(a) for a in args])
    for got, want in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("case", _op_cases(), ids=lambda c: c[0])
def test_bf16_kernel_parity(case):
    """bf16 inputs: kernel vs composition within bf16 tolerances (both
    compute in f32 internally and cast out)."""
    import jax.numpy as jnp
    name, _, _, args, fused_of, ref_of = case
    bf = [jnp.asarray(a).astype(jnp.bfloat16) for a in args]
    got = np.asarray(fused_of(True, *bf).astype(jnp.float32))
    want = np.asarray(ref_of(*bf).astype(jnp.float32))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2,
                               err_msg=name)


def test_fused_batch_norm_matches_unfused_chain():
    """fused batch_norm (train + inference) vs nn.batch_norm + relu +
    residual-add, outputs AND running stats AND input grads."""
    import jax
    import jax.numpy as jnp
    x = jnp.asarray(_f((4, 6, 6, 32)))
    res = jnp.asarray(_f((4, 6, 6, 32)))
    g = jnp.asarray(_pos((32,)))
    b = jnp.asarray(_f((32,)))
    rm = jnp.zeros((32,), jnp.float32)
    rv = jnp.ones((32,), jnp.float32)
    for training in (True, False):
        o1, m1, v1 = F.batch_norm(x, g, b, rm, rv, axis=-1,
                                  training=training, act_type="relu",
                                  residual=res)
        o2, m2, v2 = NN.batch_norm(x, g, b, rm, rv, axis=-1,
                                   training=training)
        o2 = jax.nn.relu(o2 + res)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(m1), np.asarray(m2),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                                   rtol=1e-6)

    def lk(x):
        return jnp.sum(F.batch_norm(x, g, b, rm, rv, axis=-1,
                                    training=True, act_type="relu")[0] ** 2)

    def lr(x):
        out, _, _ = NN.batch_norm(x, g, b, rm, rv, axis=-1, training=True)
        return jnp.sum(jax.nn.relu(out) ** 2)

    np.testing.assert_allclose(np.asarray(jax.grad(lk)(x)),
                               np.asarray(jax.grad(lr)(x)),
                               rtol=2e-4, atol=2e-4)


def test_unsupported_act_raises_and_pool_shape_strict():
    import jax.numpy as jnp
    x = jnp.ones((8, 16))
    with pytest.raises(ValueError, match="unsupported fused activation"):
        F.bias_act(x, jnp.ones((16,)), act_type="mish")
    with pytest.raises(ValueError, match="NHWC"):
        F.avg_pool2d(jnp.ones((2, 16, 8, 8)), 2, layout="NCHW")
    with pytest.raises(ValueError, match="divide"):
        F.avg_pool2d(jnp.ones((2, 7, 8, 4)), 2)


# ---------------------------------------------------------------------------
# npx wrappers: registration surface + grad_req axis
# ---------------------------------------------------------------------------
def test_registration_surface():
    """Every fused op (and flash attention) is a first-class dispatch
    record: registered name, declared AMP class, layout stamping."""
    amp_classes = {
        "npx.fused_bias_act": "safe",
        "npx.fused_norm_act_residual": "unsafe",
        "npx.fused_bn_inference": "unsafe",
        "npx.fused_batch_norm": "unsafe",
        "npx.fused_avg_pool2d": "safe",
        "npx.flash_attention": "safe",
        "npx.convolution": "safe",
        "npx.deconvolution": "safe",
        "npx.pooling": "safe",
    }
    ops = registry.list_ops()
    for name, amp in amp_classes.items():
        assert name in ops
        assert registry.get_op(name).amp == amp, name
    # the npx pool wrapper stamps its layout on the dispatch record
    xi = mx.np.array(_f((1, 4, 4, 8)))
    mx.npx.fused_avg_pool2d(xi, 2, layout="NHWC")
    assert registry.get_op("npx.fused_avg_pool2d").layout == "NHWC"
    mx.npx.pooling(xi, kernel=(2, 2), pool_type="avg", stride=(2, 2),
                   layout="NHWC")
    assert registry.get_op("npx.pooling").layout == "NHWC"


@pytest.mark.parametrize("req", ["add", "null"])
def test_grad_req_axis_on_fused_ops(req):
    """kWriteTo/kAddTo/kNullOp contract through the fused wrappers —
    same protocol as test_op_sweep.py's GRAD_REQ_OPS axis (which also
    sweeps npx.fused_bias_act / npx.fused_norm_act_residual)."""
    x = _f((8, 32))
    b = _f((32,))

    def run(reqs, rounds):
        nds = [mx.np.array(x), mx.np.array(b)]
        for nd, r in zip(nds, reqs):
            nd.attach_grad(grad_req=r)
        for _ in range(rounds):
            with mx.autograd.record():
                out = mx.npx.fused_bias_act(nds[0], nds[1],
                                            act_type="relu")
                loss = (out * out).sum()
            loss.backward()
        return nds

    base = run(["write", "write"], 1)
    nds = run([req, "write"], 2)
    if req == "null":
        assert nds[0].grad is None
    else:
        np.testing.assert_allclose(nds[0].grad.asnumpy(),
                                   2.0 * base[0].grad.asnumpy(),
                                   rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(nds[1].grad.asnumpy(),
                               base[1].grad.asnumpy(),
                               rtol=2e-5, atol=1e-6)


def test_flash_attention_npx_wrapper_fwd_bwd():
    """npx.flash_attention (the registered surface) vs the einsum
    composition, forward and eager-autograd backward."""
    q = mx.np.array(_f((2, 64, 32)))
    k = mx.np.array(_f((2, 64, 32)))
    v = mx.np.array(_f((2, 64, 32)))
    out = mx.npx.flash_attention(q, k, v, causal=True)
    ref = mx.npx.scaled_dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out.asnumpy(), ref.asnumpy(),
                               rtol=2e-3, atol=2e-3)
    for a in (q, k, v):
        a.attach_grad()
    with mx.autograd.record():
        loss = (mx.npx.flash_attention(q, k, v) ** 2).sum()
    loss.backward()
    with mx.autograd.record():
        loss_r = (mx.npx.scaled_dot_product_attention(q, k, v) ** 2).sum()
    gq = q.grad.asnumpy().copy()
    loss_r.backward()   # grad_req=write overwrites with the ref grad
    np.testing.assert_allclose(gq, q.grad.asnumpy(), rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# fusion gating
# ---------------------------------------------------------------------------
def test_fusion_gating_scope_default_env():
    assert not F.fusion_enabled()            # eager default: off
    with F.fusion_scope(True):
        assert F.fusion_enabled()
        with F.fusion_scope(False):          # nested force-off
            assert not F.fusion_enabled()
        assert F.fusion_enabled()
    assert not F.fusion_enabled()
    prev = F.set_fusion_default(True)
    try:
        assert F.fusion_enabled()
        # MXNET_USE_FUSION=0 kills the tier even inside a scope
        F.set_use_fusion(False)
        try:
            assert not F.fusion_enabled()
            with F.fusion_scope(True):
                assert not F.fusion_enabled()
        finally:
            F.set_use_fusion(True)
        assert F.fusion_enabled()
    finally:
        F.set_fusion_default(prev)
        F.set_use_fusion(None)


def test_fused_stats_counters_move():
    """'pallas_calls' and 'fallback_calls' both observable: interpret
    mode takes the kernel path, plain CPU the composition."""
    import jax.numpy as jnp
    x = jnp.asarray(_f((32, 128)))
    b = jnp.asarray(_f((128,)))
    F.fused_stats(reset=True)
    F.bias_act(x, b, interpret=True)
    F.bias_act(x, b, interpret=False)
    snap = F.fused_stats(reset=True)
    assert snap["pallas_calls"] == 1
    assert snap["fallback_calls"] == 1
    from incubator_mxnet_tpu import profiler
    assert set(profiler.fused_stats()) == {"pallas_calls",
                                           "fallback_calls",
                                           "device_augment_calls",
                                           "paged_attention_calls",
                                           "paged_flat_traces",
                                           "paged_head_major_traces",
                                           "paged_shared_traces",
                                           "paged_window_traces"}


def test_set_interpret_toggle_not_served_stale_programs():
    """The npx wrappers resolve the interpret flag into the DISPATCH KEY:
    a set_interpret() toggle must recompile onto the kernel path, not
    replay the program cached for the fallback (same shapes, same op)."""
    x = mx.np.array(_f((16, 64)))
    b = mx.np.array(_f((64,)))
    F.set_interpret(False)
    try:
        F.fused_stats(reset=True)
        mx.npx.fused_bias_act(x, b, act_type="relu").asnumpy()
        assert F.fused_stats(reset=True)["fallback_calls"] >= 1
        F.set_interpret(True)
        out = mx.npx.fused_bias_act(x, b, act_type="relu")
        ref = F.bias_act_ref(x._data, b._data, act_type="relu")
        snap = F.fused_stats(reset=True)
        assert snap["pallas_calls"] >= 1, snap   # NOT a stale replay
        np.testing.assert_allclose(out.asnumpy(), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)
    finally:
        F.set_interpret(None)   # back to the env default


# ---------------------------------------------------------------------------
# gluon rewires
# ---------------------------------------------------------------------------
def _gluon_net():
    mx.seed(11)
    net = nn.HybridSequential()
    net.add(nn.Conv2D(16, 3, padding=1, layout="NHWC", activation="relu",
                      use_bias=True),
            nn.BatchNorm(axis=3),
            nn.Activation("relu"),
            nn.AvgPool2D((2, 2), layout="NHWC"),
            nn.GlobalAvgPool2D(layout="NHWC"),
            nn.Flatten(),
            nn.Dense(8, activation="relu"),
            nn.Dense(4))
    net.initialize()
    return net


def test_gluon_rewires_forward_and_grad_parity():
    """The same net, fusion scope on vs off: outputs and parameter grads
    agree (the rewires change the program, not the math)."""
    x = mx.np.array(_f((4, 8, 8, 3)))
    y = mx.np.array(_f((4, 4)))
    L = gluon.loss.L2Loss()
    outs = {}
    for on in (False, True):
        net = _gluon_net()
        with F.fusion_scope(on):
            with mx.autograd.record():
                loss = L(net(x), y).mean()
            loss.backward()
        outs[on] = (loss.asnumpy(),
                    {k: p.grad().asnumpy().copy()
                     for k, p in net.collect_params().items()
                     if p.grad_req != "null"})
    np.testing.assert_allclose(outs[True][0], outs[False][0],
                               rtol=2e-5, atol=2e-6)
    for k in outs[False][1]:
        np.testing.assert_allclose(outs[True][1][k], outs[False][1][k],
                                   rtol=2e-4, atol=2e-5, err_msg=k)


def test_batchnormrelu_and_fused_forward_method():
    x = mx.np.array(_f((4, 6, 6, 8)))
    mx.seed(3)
    bn = nn.BatchNormReLU(axis=3)
    bn.initialize()
    off = bn(x).asnumpy()
    with F.fusion_scope(True):
        on = bn(x).asnumpy()
    np.testing.assert_allclose(on, off, rtol=2e-5, atol=2e-6)
    # explicit fused_forward with residual: relu(bn(x) + res)
    res = mx.np.array(_f((4, 6, 6, 8)))
    want = np.maximum(
        nn.BatchNorm.forward(bn, x).asnumpy() + res.asnumpy(), 0.0)
    got = bn.fused_forward(x, act_type="relu", residual=res).asnumpy()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_model_zoo_residual_blocks_fused_parity():
    from incubator_mxnet_tpu.gluon.model_zoo.vision import (
        BasicBlockV1, BasicBlockV2, BottleneckV1, BottleneckV2)
    x = mx.np.array(_f((2, 8, 8, 16)))
    for cls in (BasicBlockV1, BottleneckV1, BasicBlockV2, BottleneckV2):
        mx.seed(4)
        blk = cls(16, 1, downsample=True, in_channels=16, layout="NHWC")
        blk.initialize()
        off = blk(x).asnumpy()
        with F.fusion_scope(True):
            on = blk(x).asnumpy()
        np.testing.assert_allclose(on, off, rtol=2e-4, atol=2e-5,
                                   err_msg=cls.__name__)


def test_hybridized_cache_keys_on_fusion_state():
    """A hybridized net traced fusion-off must not serve the fusion-on
    call (and vice versa): the cache keys on the fusion fingerprint."""
    net = _gluon_net()
    net.hybridize()
    x = mx.np.array(_f((2, 8, 8, 3)))
    off1 = net(x).asnumpy()                  # eager shape-resolve pass
    off2 = net(x).asnumpy()                  # cached, fusion off
    with F.fusion_scope(True):
        on = net(x).asnumpy()                # fresh cache entry
    off3 = net(x).asnumpy()                  # back to the off entry
    np.testing.assert_allclose(on, off2, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(off3, off2, rtol=0, atol=0)
    keys = set(net._cached_graph)
    assert {k[1][1] for k in keys} == {False, True}


# ---------------------------------------------------------------------------
# FusedTrainStep: fusion on/off + donate on/off parity, zero retraces
# ---------------------------------------------------------------------------
def _train_setup():
    x = mx.np.array(_f((4, 8, 8, 3)))
    y = mx.np.array(RNG.randint(0, 10, (4,)))
    L = gluon.loss.SoftmaxCrossEntropyLoss()

    def make():
        mx.seed(5)
        net = nn.HybridSequential()
        net.add(nn.Conv2D(8, 3, padding=1, layout="NHWC"),
                nn.BatchNorm(axis=3), nn.Activation("relu"),
                nn.GlobalAvgPool2D(layout="NHWC"),
                nn.Flatten(), nn.Dense(10))
        net.initialize()
        net.hybridize()
        net(x)
        return net
    return make, x, y, L


def test_fused_train_step_fusion_and_donate_parity():
    make, x, y, L = _train_setup()
    results = {}
    for tag, kw in (("base", dict(use_fusion=False)),
                    ("fused", dict(use_fusion=True)),
                    ("fused_nodonate", dict(use_fusion=True,
                                            donate=False))):
        net = make()
        step = FusedTrainStep(net, lambda n, a, b: L(n(a), b).sum(),
                              opt_mod.create("sgd", learning_rate=0.1),
                              **kw)
        for _ in range(3):
            loss = step(x, y)
        warm = step._jit._cache_size()
        for _ in range(3):
            loss = step(x, y)
        assert step._jit._cache_size() == warm, \
            f"{tag}: retraced after warmup"
        results[tag] = (float(loss.asnumpy()),
                        list(net.collect_params().values())[0]
                        .data().asnumpy())
    for tag in ("fused", "fused_nodonate"):
        np.testing.assert_allclose(results[tag][0], results["base"][0],
                                   rtol=2e-4, err_msg=tag)
        np.testing.assert_allclose(results[tag][1], results["base"][1],
                                   rtol=2e-4, atol=2e-5, err_msg=tag)


def test_fused_train_step_takes_no_kernel_even_in_interpret_mode():
    """Batch norm and the global pool lower to their jnp composition
    whoever asks: with MXNET_FUSION_INTERPRET on (where every op that
    wants a kernel takes it) the fused step counts no Pallas dispatch
    and no fallback, and equals the step with fusion off."""
    make, x, y, L = _train_setup()

    def first_loss(use_fusion):
        step = FusedTrainStep(make(), lambda n, a, b: L(n(a), b).sum(),
                              opt_mod.create("sgd", learning_rate=0.1),
                              use_fusion=use_fusion)
        return float(step(x, y).asnumpy())

    loss_off = first_loss(False)
    prev = F.set_interpret(True)
    F.fused_stats(reset=True)
    try:
        loss_on = first_loss(True)
    finally:
        F.set_interpret(prev)
    stats = F.fused_stats()
    assert stats["pallas_calls"] == 0 and stats["fallback_calls"] == 0, stats
    np.testing.assert_allclose(loss_on, loss_off, rtol=2e-4)


# ---------------------------------------------------------------------------
# tools/opperf.py: the fused category
# ---------------------------------------------------------------------------
def test_opperf_fused_category_speedup_column():
    """opperf --quick includes the fused category with the
    fused-vs-unfused speedup column."""
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "opperf.json")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tools", "opperf.py"),
             "--quick", "--categories", "fused", "--json", path],
            capture_output=True, text=True, timeout=600, cwd=REPO,
            env=env)
        assert r.returncode == 0, r.stderr[-2000:]
        with open(path) as f:
            data = json.load(f)
    rows = {r["op"]: r for r in data["results"]["fused"]}
    assert "fused_norm_act_residual" in rows
    assert "flash_attention_8x256x64" in rows
    for row in rows.values():
        assert "error" not in row, row
        assert row["speedup_vs_unfused"] > 0
        assert row["unfused_jit_us"] > 0
