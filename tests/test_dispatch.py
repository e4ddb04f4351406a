"""PR2 eager-dispatch fast path: per-op dispatch records, compiled-kernel
caches, cached VJP taping, dispatch-stats counters — plus the satellite
regressions (sparse retain ordering, ONNX NMS boundary, put_along_axis
divergence warning).

Semantics contract under test: AMP autocast, autograd taping (incl. the
cached VJP), views, lazy/bulked inputs and MXNET_ENGINE_TYPE=NaiveEngine all
produce IDENTICAL results through the fast path, and the counters report
plausible hit rates (ISSUE 2 acceptance).
"""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import amp, autograd, engine, profiler
from incubator_mxnet_tpu.ops import registry, segment


@pytest.fixture
def immediate():
    """Bulking off: every invoke takes the immediate (fast) path."""
    prev = engine.set_bulk_size(0)
    yield
    engine.set_bulk_size(prev)


def _chain(x):
    y = (x * 2.0 + 1.0) * x
    z = mx.npx.relu(y - 0.5)
    return (z.sum() + y.mean()) * 1.5


# ---------------------------------------------------------------------------
# identical results through every engine configuration
# ---------------------------------------------------------------------------
def test_fast_path_matches_bulked_and_naive():
    xs = np.random.RandomState(0).randn(6, 6).astype(np.float32)

    def run():
        return float(_chain(mx.np.array(xs)).asnumpy())

    ref = run()                         # bulked (default)
    prev = engine.set_bulk_size(0)
    try:
        imm = run()                     # immediate fast path
        registry.set_dispatch_jit(False)
        try:
            plain = run()               # immediate, fast path disabled
        finally:
            registry.set_dispatch_jit(True)
    finally:
        engine.set_bulk_size(prev)
    prev_naive = engine.set_naive(True)
    try:
        naive = run()                   # NaiveEngine (block per op)
    finally:
        engine.set_naive(prev_naive)
    np.testing.assert_allclose([imm, plain, naive], [ref] * 3, rtol=1e-6)


def test_fast_path_autograd_matches_bulked(immediate):
    xs = np.random.RandomState(1).randn(5, 5).astype(np.float32)

    def run():
        x = mx.np.array(xs)
        x.attach_grad()
        with autograd.record():
            loss = _chain(x)
        loss.backward()
        return x.grad.asnumpy()

    g_imm = run()
    prev = engine.set_bulk_size(4096)
    try:
        g_bulk = run()
    finally:
        engine.set_bulk_size(0)
        engine.set_bulk_size(prev)      # restore via fixture anyway
    np.testing.assert_allclose(g_imm, g_bulk, rtol=1e-5, atol=1e-6)


def test_fast_path_views_and_mixed_lazy_inputs():
    # a view arg + a still-pending (lazy) arg + a concrete arg in one invoke
    a = mx.np.array(np.arange(16, dtype=np.float32).reshape(4, 4))
    pending = a * 3.0                   # deferred under default bulking
    view = a[1:3]                       # basic-index view of a
    out = (pending[1:3] + view).sum()
    expect = (np.arange(16, dtype=np.float32).reshape(4, 4) * 3.0
              )[1:3] + np.arange(16, dtype=np.float32).reshape(4, 4)[1:3]
    np.testing.assert_allclose(float(out.asnumpy()), expect.sum(), rtol=1e-6)
    # write through the view, then dispatch again: refresh must be seen
    view[:] = 0.0
    np.testing.assert_allclose((a[1:3] * 1.0).asnumpy(), 0.0)


def test_fast_path_amp_autocast_matches(immediate):
    xs = np.random.RandomState(2).rand(8, 8).astype(np.float32)
    ws = np.random.RandomState(3).rand(8, 8).astype(np.float32)
    amp.init("bfloat16")
    try:
        y = mx.np.dot(mx.np.array(xs), mx.np.array(ws))   # BF16_FUNCS
        assert str(y.dtype) == "bfloat16"
        z = mx.np.exp(mx.np.array(xs))                    # FP32_FUNCS
        assert str(z.dtype) == "float32"
    finally:
        amp.uninit()
    np.testing.assert_allclose(
        y.asnumpy().astype(np.float32), xs @ ws, rtol=2e-2, atol=2e-2)


def test_dispatch_record_amp_class_fallback():
    # record metadata covers names the amp lists don't know: contrib
    # roi_align registered 'unsafe' → _amp_dtype pins fp32 under autocast
    info = registry.get_op("npx.roi_align")
    assert info.amp == "unsafe"
    amp.init("bfloat16")
    try:
        assert registry._amp_dtype("roi_align", info) == "float32"
        # list names still win over records (user overrides intact)
        d = registry.get_op("npx.relu")
        assert registry._amp_dtype("relu", d) == "bfloat16"
    finally:
        amp.uninit()
    assert registry._amp_dtype("roi_align", info) is None


# ---------------------------------------------------------------------------
# counters + caches
# ---------------------------------------------------------------------------
def test_dispatch_stats_plausible_hit_rates(immediate):
    x = mx.np.array(np.ones((8, 8), np.float32))
    (x + 1.0).asnumpy()                 # prime compile outside the window
    profiler.dispatch_stats(reset=True)
    for _ in range(10):
        ((x + 1.0) * 2.0).asnumpy()
    s = profiler.dispatch_stats()
    assert s["dispatch"] == 20
    assert s["fast_path"] == 20         # every op keyed + compiled
    assert s["jit_cache_hit"] >= 18     # at most one miss for the new op
    assert s["bulked"] == 0
    # same dict via the engine facade
    assert engine.stats()["dispatch"] == s["dispatch"]


def test_recording_no_python_vjp_retrace(immediate):
    x = mx.np.array(np.random.RandomState(4).rand(6, 6).astype(np.float32))
    x.attach_grad()

    def step():
        with autograd.record():
            y = ((x * x + 3.0) * x).sum()
        y.backward()

    step()                              # builds + traces the VJP kernels
    profiler.dispatch_stats(reset=True)
    for _ in range(5):
        step()
    s = profiler.dispatch_stats()
    assert s["vjp_trace"] == 0          # no python jax.vjp retrace on repeats
    assert s["vjp_cache_hit"] > 0 and s["vjp_cache_miss"] == 0
    np.testing.assert_allclose(
        x.grad.asnumpy(), 3.0 * x.asnumpy() ** 2 + 3.0, rtol=1e-5)


def test_unjittable_fn_blacklisted_and_correct(immediate):
    calls = {"n": 0}

    def hostish(a):
        # concretizes under trace → jit probe fails → eager fallback
        calls["n"] += 1
        return a + float(np.asarray(a).sum())

    from incubator_mxnet_tpu.ops.registry import invoke
    x = mx.np.array(np.ones((2, 2), np.float32))
    profiler.dispatch_stats(reset=True)
    r1 = invoke(hostish, (x,), name="hostish").asnumpy()
    r2 = invoke(hostish, (x,), name="hostish").asnumpy()
    np.testing.assert_allclose(r1, 5.0)
    np.testing.assert_allclose(r2, 5.0)
    s = profiler.dispatch_stats()
    assert s["eager_fallback"] >= 2     # probe fell back, then stayed eager
    assert s["fast_path"] == 0


def test_user_error_does_not_blacklist_fast_path(immediate):
    a = mx.np.array(np.ones((4, 4), np.float32))
    w = mx.np.array(np.ones((4, 3), np.float32))
    mx.np.dot(a, w).asnumpy()           # compile + prime the kernel
    with pytest.raises(Exception):      # genuine user error re-raises
        mx.np.dot(a, mx.np.array(np.ones((5, 5), np.float32))).asnumpy()
    profiler.dispatch_stats(reset=True)
    mx.np.dot(a, w).asnumpy()           # same key must STILL be fast
    s = profiler.dispatch_stats()
    assert s["fast_path"] == 1 and s["eager_fallback"] == 0


def test_contrib_records_are_raw_kernels(immediate):
    # apply_op dispatch over the registered contrib record must tape the
    # PURE kernel (a wrapper would re-enter invoke with tracers at backward)
    from incubator_mxnet_tpu.ops import contrib
    info = registry.get_op("npx.box_iou")
    assert info.fn is contrib.box_iou
    b1 = mx.np.array(np.array([[0., 0., 2., 2.]], np.float32))
    b2 = mx.np.array(np.array([[1., 1., 3., 3.]], np.float32))
    b1.attach_grad()
    with autograd.record():
        loss = registry.apply_op("npx.box_iou", b1, b2).sum()
    loss.backward()
    np.testing.assert_allclose(float(loss.asnumpy()), 1.0 / 7.0, rtol=1e-5)
    assert np.isfinite(b1.grad.asnumpy()).all()


def test_key_cache_and_record_keys():
    # registered records precompute a stable key at register_op time
    def my_kernel(x):
        return x * 2.0

    registry.register_op("test.dispatch_key_op", my_kernel)
    info = registry.get_op("test.dispatch_key_op")
    assert info.key is not None
    r = registry.apply_op("test.dispatch_key_op",
                          mx.np.array(np.ones((2, 2), np.float32)))
    np.testing.assert_allclose(r.asnumpy(), 2.0)
    # derive_key_cached memoizes closure-less callables
    f = segment.derive_key  # any module-level function without closure
    segment.DISPATCH_STATS["key_cache_hit"] = 0
    k1 = segment.derive_key_cached(f)
    k2 = segment.derive_key_cached(f)
    assert k1 == k2 and segment.DISPATCH_STATS["key_cache_hit"] >= 1


def test_set_dispatch_jit_knob(immediate):
    prev = registry.set_dispatch_jit(False)
    try:
        profiler.dispatch_stats(reset=True)
        x = mx.np.array(np.ones((4, 4), np.float32))
        (x + 1.0).asnumpy()
        s = profiler.dispatch_stats()
        assert s["fast_path"] == 0 and s["eager_fallback"] == 1
    finally:
        registry.set_dispatch_jit(prev)


# ---------------------------------------------------------------------------
# satellite regressions
# ---------------------------------------------------------------------------
def test_sparse_retain_sorts_kept_rows():
    from incubator_mxnet_tpu.ndarray import sparse
    data = np.arange(8, dtype=np.float32).reshape(4, 2)
    rows = np.array([1, 3, 5, 7])
    r = sparse.row_sparse_array((data, rows), shape=(9, 2))
    # unsorted (and duplicated) request must still yield a valid RSP
    kept = r.retain(mx.np.array(np.array([7, 1, 5, 7])))
    kept.check_format()
    np.testing.assert_array_equal(kept._indices_np, [1, 5, 7])
    dense = np.zeros((9, 2), np.float32)
    dense[[1, 5, 7]] = data[[0, 2, 3]]
    np.testing.assert_allclose(kept.asnumpy(), dense)


def test_onnx_nms_keeps_boxes_at_score_threshold():
    from incubator_mxnet_tpu.onnx._runtime import _nms_numpy
    boxes = np.array([[[0, 0, 1, 1], [5, 5, 6, 6], [10, 10, 11, 11]]],
                     np.float32)
    scores = np.array([[[0.9, 0.5, 0.4]]], np.float32)
    sel = _nms_numpy(boxes, scores, -1, 0.5, 0.5)
    # score == threshold is KEPT (ONNX semantics: score > spec's
    # score_threshold filter uses >=-at-boundary like onnxruntime)
    assert sel.shape == (2, 3)
    assert set(sel[:, 2].tolist()) == {0, 1}


def test_put_along_axis_warns_on_raw_array():
    arr = mx.np.array(np.zeros((2, 3), np.float32))
    idx = mx.np.array(np.array([[1], [0]], np.int64))
    out = mx.np.put_along_axis(arr, idx, mx.np.array([[7.0], [8.0]]), 1)
    np.testing.assert_allclose(arr.asnumpy(), out.asnumpy())  # written back
    assert arr.asnumpy()[0, 1] == 7.0
    with pytest.warns(UserWarning, match="cannot mutate"):
        raw = np.zeros((2, 3), np.float32)
        out2 = mx.np.put_along_axis(raw, np.array([[1], [0]]),
                                    np.array([[7.0], [8.0]], np.float32), 1)
    assert raw[0, 1] == 0.0                                   # NOT mutated
    assert out2.asnumpy()[0, 1] == 7.0
