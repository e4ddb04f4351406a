"""mx.serve: dynamic-batching inference server over exported artifacts.

Contracts under test (ISSUE 3 acceptance):
  * batched results are bit-identical to direct ExportedModel.run
  * a mixed-batch-size request stream performs ZERO recompiles after
    warmup (compile/dispatch counters: `programs_compiled` and the jit
    compile-cache size both stay flat)
  * overload sheds or rejects per policy instead of deadlocking, proven
    under MXNET_FAULT_SPEC injection (env-armed subprocess + fault.scope)
  * deadlines fail fast with typed errors; execution faults fail the batch
    but not the server
  * ExportedModel.run is safe to share across worker threads (the
    jit-call concurrency contract deploy.py documents)
"""
import json
import os
import subprocess
import sys
import threading
import time
from concurrent.futures import wait

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import fault, profiler, serve
from incubator_mxnet_tpu.gluon import nn


@pytest.fixture(scope="module")
def exported(tmp_path_factory):
    """One small block exported at buckets {1, 2, 4} + the live block."""
    d = tmp_path_factory.mktemp("serve_artifacts")
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu", in_units=6), nn.Dense(3))
    net.initialize()
    net.hybridize()
    model = serve.BucketedModel.export_block(net, (6,), [1, 2, 4], str(d),
                                             name="mlp")
    return net, model


def _rows(n, dim=6, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.rand(dim).astype(np.float32) for _ in range(n)]


def _callable_model(dim=3, buckets=(1, 2, 4)):
    import jax.numpy as jnp
    W = np.linspace(-1, 1, dim * 2).reshape(dim, 2).astype(np.float32)
    return serve.CallableModel(lambda x: jnp.tanh(x @ W), buckets,
                               [((dim,), "float32")]), W


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------
def test_batched_matches_direct_run(exported):
    net, model = exported
    with serve.Server(model, batch_timeout_ms=5.0) as srv:
        xs = _rows(11)
        futs = [srv.submit(x) for x in xs]
        for x, f in zip(xs, futs):
            ref = net(mx.np.array(x[None])).asnumpy()[0]
            np.testing.assert_allclose(f.result(timeout=30), ref,
                                       rtol=1e-5, atol=1e-6)
        st = srv.stats()
        assert st["replies"] == 11
        assert st["buckets"] == [1, 2, 4]


def test_concurrent_submitters_all_served(exported):
    net, model = exported
    with serve.Server(model, batch_timeout_ms=2.0, max_queue=512) as srv:
        results = {}
        lock = threading.Lock()

        def client(tid):
            xs = _rows(8, seed=tid)
            outs = [srv.predict(x, timeout=30) for x in xs]
            with lock:
                results[tid] = (xs, outs)

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert len(results) == 8
        for xs, outs in results.values():
            for x, o in zip(xs, outs):
                ref = net(mx.np.array(x[None])).asnumpy()[0]
                np.testing.assert_allclose(o, ref, rtol=1e-5, atol=1e-6)


def test_multi_input_model():
    import jax.numpy as jnp
    model = serve.CallableModel(lambda a, b: a * 2.0 + b, (1, 2),
                                [((3,), "float32"), ((3,), "float32")],
                                single_output=True)
    with serve.Server(model, batch_timeout_ms=1.0) as srv:
        a, b = np.ones(3, np.float32), np.arange(3, dtype=np.float32)
        np.testing.assert_allclose(srv.predict(a, b), a * 2 + b)


def test_bfloat16_rows_batch_and_pad():
    """bf16 exports serve correctly: row casts and pad-row allocation go
    through the bf16-aware dtype mapping, not raw numpy dtype strings."""
    import jax.numpy as jnp
    model = serve.CallableModel(lambda x: x * 2.0, (1, 2, 4),
                                [((3,), "bfloat16")])
    with serve.Server(model, batch_timeout_ms=2.0) as srv:
        xs = _rows(3, dim=3)                     # float32 in, cast to bf16
        outs = [srv.predict(x, timeout=30) for x in xs]
        for x, o in zip(xs, outs):
            assert str(o.dtype) == "bfloat16"
            np.testing.assert_allclose(o.astype(np.float32), x * 2.0,
                                       rtol=2e-2)


def test_input_validation(exported):
    _, model = exported
    with serve.Server(model) as srv:
        with pytest.raises(serve.ServeError, match="sample shape"):
            srv.submit(np.zeros((2, 6), np.float32))   # batched input
        with pytest.raises(serve.ServeError, match="takes 1 inputs"):
            srv.submit(np.zeros(6, np.float32), np.zeros(6, np.float32))


def test_pick_bucket():
    assert serve.pick_bucket(1, [1, 2, 4]) == 1
    assert serve.pick_bucket(3, [1, 2, 4]) == 4
    assert serve.pick_bucket(4, [1, 2, 4]) == 4
    assert serve.pick_bucket(5, [1, 2, 4]) is None


# ---------------------------------------------------------------------------
# zero-retrace steady state (the compile/dispatch-counter acceptance)
# ---------------------------------------------------------------------------
def test_mixed_batch_stream_zero_recompiles_after_warmup(exported):
    net, model = exported
    with serve.Server(model, batch_timeout_ms=1.0) as srv:
        warm_ccs = model.compile_cache_size()
        assert warm_ccs == 3          # one program per bucket, compiled
        warm_programs = srv.stats()["programs_compiled"]
        assert warm_programs == 3
        # mixed-size bursts: 1, 3, 2, 4, 1, 2 ... pad onto {1,2,4}
        for burst in (1, 3, 2, 4, 1, 2, 3, 4, 1):
            futs = [srv.submit(x) for x in _rows(burst, seed=burst)]
            wait(futs, timeout=30)
            assert all(f.exception() is None for f in futs)
        st = srv.stats()
        assert st["compile_cache_size"] == warm_ccs, \
            "steady-state serving retraced a bucket program"
        assert st["programs_compiled"] == warm_programs
        # occupancy histogram saw more than one bucket
        assert len(st["batch_occupancy"]) >= 2


# ---------------------------------------------------------------------------
# overload: admission control, shed/reject policies, deadlines
# ---------------------------------------------------------------------------
def test_reject_newest_policy_fails_fast():
    model, _ = _callable_model()
    srv = serve.Server(model, max_queue=2, batch_timeout_ms=50.0,
                       overload_policy="reject").start()
    try:
        with fault.scope("serve.execute:*:stall:0.15"):
            admitted = []
            rejected = 0
            for x in _rows(20, dim=3):
                try:
                    admitted.append(srv.submit(x))
                except serve.QueueFullError as e:
                    assert e.policy == "reject"
                    rejected += 1
            assert rejected > 0
        # server keeps serving: drain succeeds, no deadlock
        srv.close(drain=True)
        done = [f for f in admitted if f.exception() is None]
        assert done, "no admitted request was ever served"
        assert srv.stats()["rejected"] == rejected
    finally:
        srv.close()


def test_shed_oldest_policy_fails_queued_requests():
    model, _ = _callable_model()
    srv = serve.Server(model, max_queue=2, batch_timeout_ms=50.0,
                       overload_policy="shed").start()
    try:
        with fault.scope("serve.execute:*:stall:0.15"):
            futs = [srv.submit(x) for x in _rows(12, dim=3)]
        srv.close(drain=True)
        shed = [f for f in futs if isinstance(f.exception(),
                                              serve.QueueFullError)]
        served = [f for f in futs if f.exception() is None]
        assert shed and served
        assert all(e.exception().policy == "shed" for e in shed)
        assert srv.stats()["shed"] == len(shed)
    finally:
        srv.close()


def test_deadline_expires_in_queue():
    model, _ = _callable_model()
    srv = serve.Server(model, batch_timeout_ms=1.0).start()
    try:
        with fault.scope("serve.execute:1:stall:0.25"):
            f1 = srv.submit(np.ones(3, np.float32))   # occupies the batcher
            time.sleep(0.02)
            f2 = srv.submit(np.ones(3, np.float32), deadline_ms=50)
            with pytest.raises(serve.RequestTimeout):
                f2.result(timeout=10)
            assert f1.result(timeout=10) is not None
        assert srv.stats()["timeouts"] == 1
    finally:
        srv.close()


def test_overload_no_deadlock_under_env_fault_spec(tmp_path):
    """The acceptance wording verbatim: overload sheds/rejects per policy
    under MXNET_FAULT_SPEC (armed via the env var, fresh process)."""
    prog = r"""
import numpy as np
from incubator_mxnet_tpu import serve
import jax.numpy as jnp
model = serve.CallableModel(lambda x: x * 2.0, [1, 2],
                            [((3,), "float32")])
srv = serve.Server(model, max_queue=2, batch_timeout_ms=20.0,
                   overload_policy="shed").start()
futs = [srv.submit(np.ones(3, np.float32)) for _ in range(12)]
srv.close(drain=True)
shed = sum(isinstance(f.exception(), serve.QueueFullError) for f in futs)
served = sum(f.exception() is None for f in futs)
assert shed > 0 and served > 0, (shed, served)
print("SHED", shed, "SERVED", served)
"""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               MXNET_FAULT_SPEC="serve.execute:*:stall:0.1")
    r = subprocess.run([sys.executable, "-c", prog], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "SHED" in r.stdout


def test_execute_fault_fails_batch_not_server():
    model, W = _callable_model()
    with serve.Server(model, batch_timeout_ms=1.0) as srv:
        with fault.scope("serve.execute:1:error"):
            f = srv.submit(np.ones(3, np.float32))
            with pytest.raises(fault.InjectedFault):
                f.result(timeout=10)
        # server still alive and correct afterwards
        x = np.full(3, 0.5, np.float32)
        np.testing.assert_allclose(srv.predict(x, timeout=10),
                                   np.tanh(x @ W), rtol=1e-5)
        st = srv.stats()
        assert st["errors"] == 1 and st["replies"] == 1


def test_closed_server_rejects_submissions():
    model, _ = _callable_model()
    srv = serve.Server(model).start()
    srv.close()
    with pytest.raises(serve.ServerClosed):
        srv.submit(np.ones(3, np.float32))


def test_close_without_drain_fails_pending():
    model, _ = _callable_model()
    srv = serve.Server(model, batch_timeout_ms=100.0, max_queue=64).start()
    with fault.scope("serve.execute:*:stall:0.2"):
        futs = [srv.submit(x) for x in _rows(6, dim=3)]
        srv.close(drain=False)
    failed = [f for f in futs if isinstance(f.exception(),
                                            serve.ServerClosed)]
    assert failed, "non-draining close left requests pending"


# ---------------------------------------------------------------------------
# regression (ISSUE 14 satellite): pad-row mask. Batches are zero-padded
# up to their bucket, so output rows [n:] are pad garbage — the execute
# path must slice them off explicitly, and an output that does not carry
# the batch dim (no row<->request correspondence: indexing it would hand
# requesters data mixing in pad rows) must fail TYPED, never reply.
# ---------------------------------------------------------------------------
def test_pad_rows_never_leak_into_replies():
    # fn(x) maps zero pad rows to the sentinel 5.0 — if any pad row
    # leaked into a reply, the requester would see 5s instead of its
    # own transform
    model = serve.CallableModel(lambda x: x * 2.0 + 5.0, (4,),
                                [((3,), "float32")])
    with serve.Server(model, batch_timeout_ms=1.0) as srv:
        xs = _rows(7, dim=3, seed=21)
        outs = [srv.predict(x, timeout=30) for x in xs]
        for x, o in zip(xs, outs):
            assert o.shape == (3,)
            np.testing.assert_allclose(o, x * 2.0 + 5.0, rtol=1e-6)


def test_batch_reducing_output_fails_typed_not_garbage():
    # a model that reduces over the batch axis: its output has NO pad
    # mask (every element mixes the zero pad rows in) — the server must
    # fail the batch with a typed error instead of slicing nonsense
    model = serve.CallableModel(lambda x: x.sum(axis=0), (2,),
                                [((3,), "float32")])
    with serve.Server(model, batch_timeout_ms=1.0) as srv:
        f = srv.submit(np.ones(3, np.float32))
        with pytest.raises(serve.ServeError, match="pad"):
            f.result(timeout=30)
        # the server survives the failed batch
        assert srv.stats()["errors"] == 1


# ---------------------------------------------------------------------------
# metrics + observability
# ---------------------------------------------------------------------------
def test_metrics_surface(exported):
    _, model = exported
    serve.stats(reset=True)
    with serve.Server(model, batch_timeout_ms=1.0) as srv:
        futs = [srv.submit(x) for x in _rows(9)]
        wait(futs, timeout=30)
        st = srv.stats()
    assert st["requests"] == 9 and st["replies"] == 9
    assert st["p50_ms"] is not None and st["p99_ms"] is not None
    assert st["p50_ms"] <= st["p99_ms"]
    assert st["requests_per_sec"] > 0
    occ = st["batch_occupancy"]
    assert sum(r["rows"] for r in occ.values()) == 9
    for b, r in occ.items():
        assert 0 < r["mean_occupancy"] <= 1.0
    # process-wide counter surface (profiler-style), also via profiler
    agg = profiler.serve_stats()
    assert agg["replies"] >= 9
    assert json.dumps(st)      # snapshot is plain json-able data


def test_chrome_trace_serve_lane(exported, tmp_path):
    _, model = exported
    profiler.start()
    try:
        with serve.Server(model, batch_timeout_ms=1.0) as srv:
            wait([srv.submit(x) for x in _rows(5)], timeout=30)
    finally:
        profiler.stop()
    f = str(tmp_path / "trace.json")
    profiler.dump(filename=f)
    events = json.load(open(f))["traceEvents"]
    lane = [e for e in events if e["name"] == "serve.batch"]
    assert lane, "no serve.batch events in the Chrome trace"
    assert all(e["cat"] == "serve" for e in lane)
    assert all("bucket" in e["args"] and "occupancy" in e["args"]
               for e in lane)


# ---------------------------------------------------------------------------
# deploy.py concurrency contract (satellite)
# ---------------------------------------------------------------------------
def test_exported_model_run_thread_safe(exported):
    net, model = exported
    m1 = model._models[1]
    m1.warmup()
    ccs0 = m1.compile_cache_size()
    xs = _rows(8, seed=11)
    refs = [net(mx.np.array(x[None])).asnumpy()[0] for x in xs]
    errs = []

    def hammer(tid):
        try:
            for _ in range(25):
                got = m1.run(xs[tid][None])
                np.testing.assert_allclose(got[0], refs[tid],
                                           rtol=1e-5, atol=1e-6)
        except Exception as e:
            errs.append(e)

    threads = [threading.Thread(target=hammer, args=(t,)) for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errs, errs
    assert m1.compile_cache_size() == ccs0, \
        "concurrent run() retraced the exported program"


# ---------------------------------------------------------------------------
# regression (mxlint lock-shared-mutation): SERVE_STATS increments are a
# read-modify-write — off-lock they lose counts under thread contention,
# and serve_stats(reset=True) could eat increments landing between its
# snapshot and its zeroing. Both now run under metrics._STATS_LOCK.
# ---------------------------------------------------------------------------
def test_serve_stats_counters_exact_under_contention():
    from incubator_mxnet_tpu.serve.metrics import ServeMetrics

    n_threads, n_iter = 8, 500
    before = profiler.serve_stats()
    m = ServeMetrics()
    errs = []

    def hammer():
        try:
            for _ in range(n_iter):
                m.count("requests")
                m.observe_batch(bucket=2, occupancy=1, exec_ms=0.0,
                                queue_depth=0)
        except BaseException as e:   # pragma: no cover - diagnostics
            errs.append(e)

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errs, errs

    total = n_threads * n_iter
    snap = m.snapshot()
    assert snap["requests"] == total
    assert snap["batches"] == total
    assert snap["padded_rows"] == total          # one pad row per batch
    after = profiler.serve_stats()
    assert after["requests"] - before["requests"] == total
    assert after["batches"] - before["batches"] == total
    assert after["padded_rows"] - before["padded_rows"] == total


def test_serve_stats_reset_is_atomic_with_snapshot():
    from incubator_mxnet_tpu.serve import metrics as sm

    profiler.serve_stats(reset=True)
    stop = threading.Event()
    sent = [0]

    def incrementer():
        m = sm.ServeMetrics()
        n = 0
        while not stop.is_set():
            m.count("replies")
            n += 1
        sent[0] = n

    t = threading.Thread(target=incrementer)
    t.start()
    try:
        # snapshot+zero is one atomic step, so every increment lands in
        # EXACTLY one reset window: the windowed sums must add up to the
        # incrementer's own call count — the pre-fix racy reset lost the
        # increments that arrived between its copy and its zeroing
        collected = 0
        for _ in range(200):
            collected += profiler.serve_stats(reset=True)["replies"]
    finally:
        stop.set()
        t.join(timeout=60)
    collected += profiler.serve_stats(reset=True)["replies"]
    assert collected == sent[0]
    assert profiler.serve_stats()["replies"] == 0
