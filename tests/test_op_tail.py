"""Round-3 op-tail coverage: grouped transposed conv, top-k / expert-choice
MoE routing, and the la_op family (reference src/operator/tensor/la_op.cc).
"""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import parallel
from incubator_mxnet_tpu.numpy import linalg as L


# ---------------------------------------------------------------------------
# grouped transposed convolution (ops/nn.py conv_transpose)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("layout", ["NCHW", "NHWC"])
def test_grouped_deconv_matches_per_group(layout):
    from incubator_mxnet_tpu.ops import nn as onn
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    g, cin, cout = 2, 4, 6
    if layout == "NCHW":
        x = rng.randn(2, cin, 8, 8).astype(np.float32)
        w = rng.randn(cin, cout // g, 3, 3).astype(np.float32)
        ch = 1
    else:
        x = rng.randn(2, 8, 8, cin).astype(np.float32)
        w = rng.randn(3, 3, cout // g, cin).astype(np.float32)
        ch = 3
    y = np.asarray(onn.conv_transpose(jnp.asarray(x), jnp.asarray(w),
                                      stride=2, padding=1, groups=g,
                                      layout=layout))
    # reference semantics: per-group single deconv over channel slices
    xs = np.split(x, g, axis=ch)
    ws = np.split(w, g, axis=0 if layout == "NCHW" else 3)
    refs = [np.asarray(onn.conv_transpose(jnp.asarray(xg), jnp.asarray(wg),
                                          stride=2, padding=1, groups=1,
                                          layout=layout))
            for xg, wg in zip(xs, ws)]
    np.testing.assert_allclose(y, np.concatenate(refs, axis=ch),
                               rtol=1e-5, atol=1e-5)


def test_grouped_deconv_gluon_layer():
    from incubator_mxnet_tpu.gluon import nn
    net = nn.Conv2DTranspose(8, 4, strides=2, padding=1, groups=2,
                             in_channels=4)
    net.initialize()
    x = mx.np.array(np.random.RandomState(1).randn(2, 4, 8, 8)
                    .astype(np.float32))
    y = net(x)
    assert y.shape == (2, 8, 16, 16)


# ---------------------------------------------------------------------------
# MoE routing variants (8-device mesh via conftest)
# ---------------------------------------------------------------------------
def _run_moe(router, top_k=1, capacity=None):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from incubator_mxnet_tpu.parallel.moe import (moe_dispatch,
                                                  moe_dispatch_expert_choice)
    E, T, D = 4, 8, 4
    rng = np.random.RandomState(2)
    x = rng.randn(E * T, D).astype(np.float32)
    logits = rng.randn(E * T, E).astype(np.float32)

    def expert_fn_of(rank_mul):
        def f(tokens):
            return tokens * rank_mul
        return f

    m = parallel.Mesh({"ep": 4})

    def inner(xl, ll):
        rank = jax.lax.axis_index("ep")
        mul = (rank + 1).astype(jnp.float32)
        if router == "expert_choice":
            y, aux = moe_dispatch_expert_choice(
                xl, ll, lambda t: t * mul, axis_name="ep",
                capacity=capacity)
        else:
            y, aux = moe_dispatch(xl, ll, lambda t: t * mul,
                                  axis_name="ep", capacity=capacity,
                                  top_k=top_k)
        return y, aux

    f = parallel.shard_map(inner, m,
                           in_specs=(P("ep", None), P("ep", None)),
                           out_specs=(P("ep", None), P()),
                           check_vma=False)
    with m:
        y, aux = f(x, logits)
    return x, logits, np.asarray(y), float(np.asarray(aux).reshape(-1)[0])


def test_moe_top2_matches_dense_routing():
    """top-2 with ample capacity == dense computation: sum of the two best
    experts' outputs weighted by renormalized gates."""
    x, logits, y, aux = _run_moe("top_k", top_k=2, capacity=64)
    E = 4
    p = np.exp(logits - logits.max(1, keepdims=True))
    p = p / p.sum(1, keepdims=True)
    top2 = np.argsort(-p, axis=1)[:, :2]
    ref = np.zeros_like(x)
    for t in range(x.shape[0]):
        g = p[t, top2[t]]
        g = g / g.sum()
        for j, e in enumerate(top2[t]):
            ref[t] += g[j] * x[t] * (e + 1)   # expert e multiplies by e+1
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-5)
    assert aux > 0


def test_moe_top2_capacity_overflow_passthrough():
    """Under a tiny capacity, tokens whose every choice overflowed pass
    through unchanged; kept choices still contribute."""
    x, logits, y, aux = _run_moe("top_k", top_k=2, capacity=1)
    # every row is either a gated combination (scaled) or exact passthrough;
    # at least one of each must occur at capacity=1
    same = np.isclose(y, x, atol=1e-6).all(axis=1)
    assert same.any() and (~same).any()


def test_moe_expert_choice_balanced():
    """Expert-choice: every expert processes exactly C tokens (perfect
    balance) and unchosen tokens pass through."""
    x, logits, y, aux = _run_moe("expert_choice", capacity=2)
    assert aux == 0.0
    same = np.isclose(y, x, atol=1e-6).all(axis=1)
    # each of the 4 ranks picks top-C local tokens for each of 4 experts:
    # at most R * E * C = 32 tokens transformed in total
    assert (~same).sum() <= 4 * 4 * 2


# ---------------------------------------------------------------------------
# la_op family (≙ src/operator/tensor/la_op.cc)
# ---------------------------------------------------------------------------
def test_la_syrk_trmm_trsm():
    rng = np.random.RandomState(3)
    A = mx.np.array(rng.randn(4, 4).astype(np.float32))
    B = mx.np.array(rng.randn(4, 3).astype(np.float32))
    a, b = A.asnumpy(), B.asnumpy()
    np.testing.assert_allclose(L.syrk(A, alpha=2.0).asnumpy(),
                               2.0 * a @ a.T, rtol=1e-5)
    np.testing.assert_allclose(L.syrk(A, transpose=True).asnumpy(),
                               a.T @ a, rtol=1e-5)
    np.testing.assert_allclose(L.trmm(A, B).asnumpy(),
                               np.tril(a) @ b, rtol=1e-5)
    X = L.trsm(A, B).asnumpy()
    np.testing.assert_allclose(np.tril(a) @ X, b, rtol=1e-3, atol=1e-4)


def test_la_potrf_potri_gelqf_syevd_gemm2():
    rng = np.random.RandomState(4)
    M = rng.randn(5, 5).astype(np.float32)
    S = M @ M.T + 5 * np.eye(5, dtype=np.float32)
    A = mx.np.array(S)
    Lc = L.potrf(A)
    np.testing.assert_allclose(Lc.asnumpy() @ Lc.asnumpy().T, S,
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(L.potri(Lc).asnumpy(), np.linalg.inv(S),
                               rtol=1e-2, atol=1e-3)

    R = mx.np.array(rng.randn(3, 5).astype(np.float32))
    lo, q = L.gelqf(R)
    np.testing.assert_allclose(lo.asnumpy() @ q.asnumpy(), R.asnumpy(),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(q.asnumpy() @ q.asnumpy().T, np.eye(3),
                               rtol=1e-4, atol=1e-4)

    U, lam = L.syevd(A)
    u, la_ = U.asnumpy(), lam.asnumpy()
    np.testing.assert_allclose(u.T @ np.diag(la_) @ u, S,
                               rtol=1e-3, atol=1e-3)

    X = mx.np.array(rng.randn(2, 4).astype(np.float32))
    Y = mx.np.array(rng.randn(3, 4).astype(np.float32))
    np.testing.assert_allclose(
        L.gemm2(X, Y, transpose_b=True, alpha=0.5).asnumpy(),
        0.5 * X.asnumpy() @ Y.asnumpy().T, rtol=1e-5)


def test_la_ops_differentiable():
    """la_ops ride the tape like every other invoke-dispatched op."""
    A = mx.np.array(np.eye(3, dtype=np.float32) * 2.0)
    A.attach_grad()
    with mx.autograd.record():
        y = L.syrk(A).sum()
    y.backward()
    assert A.grad is not None and float(np.abs(A.grad.asnumpy()).sum()) > 0
