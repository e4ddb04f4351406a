"""mx.telemetry — unified metrics registry and step-timeline attribution.

Covers: counter/gauge/histogram semantics under an 8-thread hammer,
snapshot(reset) conservation, Prometheus exposition golden text, span
nesting + Chrome-trace round-trip, MFU against a hand-counted matmul,
legacy *_stats() shim parity (keys + reset semantics, registry-backed),
StepTimeline data-stall attribution, the /metrics endpoint, and that
importing the package initialises no jax backend.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import profiler, telemetry
from incubator_mxnet_tpu.telemetry.registry import Registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------
def test_counter_gauge_histogram_semantics():
    reg = Registry()
    c = reg.counter("t.hits", help="hits")
    c.inc()
    c.inc(4)
    assert c.get() == 5
    with pytest.raises(ValueError):
        c.inc(-1)                      # counters are monotonic
    g = reg.gauge("t.depth")
    g.set(7)
    g.dec(2)
    assert g.get() == 5.0
    h = reg.histogram("t.lat_us", buckets=(10, 100))
    for v in (5, 50, 500):
        h.observe(v)
    s = h.get()
    assert s["count"] == 3 and s["sum"] == 555.0
    assert s["min"] == 5.0 and s["max"] == 500.0
    assert s["buckets"] == [1, 1, 1]   # <=10, <=100, +Inf


def test_registry_type_collision_is_an_error():
    reg = Registry()
    reg.counter("t.x")
    with pytest.raises(ValueError):
        reg.gauge("t.x")
    c = reg.counter("t.y", labels=("op",))
    with pytest.raises(ValueError):
        reg.counter("t.y")             # same name, different labels
    with pytest.raises(ValueError):
        c.labels(wrong="k")


def test_labeled_metrics_key_independently():
    reg = Registry()
    c = reg.counter("t.by_op", labels=("op",))
    c.labels(op="add").inc(2)
    c.labels(op="mul").inc(3)
    snap = reg.snapshot()
    assert snap['t.by_op{op="add"}'] == 2
    assert snap['t.by_op{op="mul"}'] == 3


def test_eight_thread_hammer_exact_counts():
    """8 threads x 1000 increments each on counter + histogram + a
    StatsGroup: exact totals — the one-lock discipline loses nothing."""
    reg = Registry()
    c = reg.counter("t.hammer")
    h = reg.histogram("t.hammer_lat")
    grp = reg.stats_group("hammer", {"hits": 0})
    N, T = 1000, 8
    barrier = threading.Barrier(T)

    def work():
        barrier.wait()
        for _ in range(N):
            c.inc()
            h.observe(1.0)
            with grp._owner_lock:
                grp["hits"] += 1

    threads = [threading.Thread(target=work) for _ in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.get() == N * T
    assert h.get()["count"] == N * T
    assert grp.snapshot()["hits"] == N * T


def test_snapshot_reset_conservation():
    """Windowed snapshot(reset=True) reads sum to the un-windowed total:
    no increment is lost between copy and zero, and gauges (levels)
    survive the reset."""
    reg = Registry()
    c = reg.counter("t.flow")
    g = reg.gauge("t.level")
    g.set(42)
    grp = reg.stats_group("win", {"n": 0})
    total, seen = 600, 0
    stop = threading.Event()

    def incs():
        for _ in range(total):
            c.inc()
            with grp._owner_lock:
                grp["n"] += 1
        stop.set()

    t = threading.Thread(target=incs)
    t.start()
    while not stop.is_set():
        s = reg.snapshot(reset=True)
        seen += s["t.flow"] + s["win.n"]
    t.join()
    s = reg.snapshot(reset=True)
    seen += s["t.flow"] + s["win.n"]
    assert seen == 2 * total
    assert reg.snapshot()["t.level"] == 42.0   # gauge kept its level


def test_prometheus_exposition_golden():
    reg = Registry()
    c = reg.counter("demo.hits", help="demo hits")
    c.inc(3)
    g = reg.gauge("demo.depth")
    g.set(2)
    h = reg.histogram("demo.lat_us", labels=("op",), buckets=(10.0, 100.0))
    h.labels(op="add").observe(5)
    h.labels(op="add").observe(50)
    grp = reg.stats_group("demo_grp", {"k": 0}, help="demo group")
    with grp._owner_lock:
        grp["k"] += 7
    assert reg.prometheus_text() == """\
# TYPE mx_demo_depth gauge
mx_demo_depth 2
# HELP mx_demo_hits demo hits
# TYPE mx_demo_hits counter
mx_demo_hits 3
# TYPE mx_demo_lat_us histogram
mx_demo_lat_us_bucket{op="add",le="10"} 1
mx_demo_lat_us_bucket{op="add",le="100"} 2
mx_demo_lat_us_bucket{op="add",le="+Inf"} 2
mx_demo_lat_us_sum{op="add"} 55
mx_demo_lat_us_count{op="add"} 2
# HELP mx_demo_grp demo group
mx_demo_grp_k 7
"""


def test_snapshot_json_round_trips():
    reg = Registry()
    reg.counter("t.a").inc()
    assert json.loads(reg.snapshot_json()) == {"t.a": 1.0}


# ---------------------------------------------------------------------------
# legacy shim parity: keys and reset semantics, registry-backed
# ---------------------------------------------------------------------------
def test_dispatch_stats_shim_parity():
    from incubator_mxnet_tpu.ops import segment
    profiler.dispatch_stats(reset=True)
    x = mx.np.ones((4, 4))
    (x * 2 + 1).asnumpy()
    s = profiler.dispatch_stats()
    assert set(s) == set(segment.DISPATCH_STATS)
    assert s["dispatch"] >= 1
    # the SAME counters through the registry pane
    assert telemetry.snapshot()["dispatch.dispatch"] == s["dispatch"]
    # reset zeroes both views atomically
    profiler.dispatch_stats(reset=True)
    assert profiler.dispatch_stats()["dispatch"] == 0
    assert telemetry.snapshot()["dispatch.dispatch"] == 0


def test_serve_and_feed_stats_shim_parity():
    from incubator_mxnet_tpu.io.device_feed import FEED_STATS
    from incubator_mxnet_tpu.serve.metrics import SERVE_STATS
    sv = profiler.serve_stats()
    assert set(sv) == set(SERVE_STATS)
    fd = profiler.feed_stats()
    assert set(fd) == set(FEED_STATS) | {"occupancy_mean"}
    # registry carries both groups under their family prefixes
    snap = telemetry.snapshot()
    assert all(f"serve.{k}" in snap for k in SERVE_STATS)
    assert all(f"feed.{k}" in snap for k in FEED_STATS)
    # reset-window conservation through the shim (the old hand-rolled
    # semantics, now StatsGroup.snapshot)
    base = profiler.serve_stats(reset=True)  # noqa: F841  (zero the window)
    SERVE_STATS.snapshot(reset=True)
    from incubator_mxnet_tpu.serve.metrics import ServeMetrics
    m = ServeMetrics()
    m.observe_batch(bucket=2, occupancy=2, exec_ms=1.0, queue_depth=0)
    win = profiler.serve_stats(reset=True)
    assert win["batches"] == 1
    assert profiler.serve_stats()["batches"] == 0


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
def test_span_nesting_and_chrome_trace_round_trip(tmp_path):
    profiler._events.clear()
    profiler.start()
    try:
        with telemetry.span("outer.step", step=1):
            assert telemetry.current_span() == "outer.step"
            with telemetry.span("inner.op"):
                assert telemetry.current_span() == "inner.op"
                time.sleep(0.001)
        assert telemetry.current_span() is None
    finally:
        profiler.stop()
    path = str(tmp_path / "trace.json")
    profiler.dump(filename=path)
    with open(path) as f:
        trace = json.load(f)
    by_name = {e["name"]: e for e in trace["traceEvents"]}
    assert "outer.step" in by_name and "inner.op" in by_name
    # nesting recorded: the child carries its parent's name
    assert by_name["inner.op"]["args"]["parent"] == "outer.step"
    assert by_name["outer.step"]["args"]["step"] == 1
    # the child's window is inside the parent's
    o, i = by_name["outer.step"], by_name["inner.op"]
    assert o["ts"] <= i["ts"] and i["ts"] + i["dur"] <= o["ts"] + o["dur"]
    # registry aggregates ride along in the trace artifact
    tele = trace["otherData"]["telemetry"]
    assert tele['span.count{name="inner.op"}'] >= 1
    # and the span histograms exist under their registered names
    snap = telemetry.snapshot()
    assert 'span.duration_us{name="outer.step"}' in snap
    assert snap['span.duration_us{name="inner.op"}']["count"] >= 1


def test_span_metric_names_registered():
    # the two object metrics of the span layer (lint: metric catalog)
    names = telemetry.REGISTRY.names()
    assert "span.duration_us" in names
    assert "span.count" in names


def test_record_event_timestamps_monotonic_across_threads():
    """_now_us is one process-wide monotonic clock: events recorded
    after a cross-thread join can never carry earlier timestamps."""
    assert profiler._now_us() == pytest.approx(
        time.perf_counter_ns() // 1000, abs=200000)
    stamps = []

    def worker():
        stamps.append(profiler._now_us())

    t0 = profiler._now_us()
    th = threading.Thread(target=worker)
    th.start()
    th.join()
    t1 = profiler._now_us()
    assert t0 <= stamps[0] <= t1


def test_spans_disabled_by_env(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    telemetry.trace._expire_env_memo()   # the knob is TTL-cached (50ms)
    before = telemetry.snapshot().get('span.count{name="off.span"}', 0)
    with telemetry.span("off.span"):
        pass
    after = telemetry.snapshot().get('span.count{name="off.span"}', 0)
    assert after == before


def test_profiler_dumps_includes_telemetry_sections():
    telemetry.REGISTRY.counter("t.dumps_probe").inc(3)
    with telemetry.span("dumps.span"):
        pass
    table = profiler.dumps()
    assert "Span (telemetry)" in table
    assert "Telemetry metric" in table
    assert "t.dumps_probe" in table
    j = json.loads(profiler.dumps(format="json"))
    assert j["telemetry"]["t.dumps_probe"] == 3.0


# ---------------------------------------------------------------------------
# MFU: XLA-counted flops vs hand math
# ---------------------------------------------------------------------------
def test_model_flops_matches_hand_counted_matmul():
    import jax.numpy as jnp
    m, k, n = 32, 64, 16
    a = jnp.ones((m, k), jnp.float32)
    b = jnp.ones((k, n), jnp.float32)
    fl = telemetry.model_flops(lambda x, y: x @ y, a, b)
    assert fl == pytest.approx(2 * m * k * n, rel=0.01)  # MAC = 2 flops
    # memoized: the second call is a dict hit (same id + avals)
    assert telemetry.model_flops(lambda x, y: x @ y, a, b) >= 0  # no crash


def test_block_fwd_flops_dense_net_within_10pct_of_hand_math():
    from incubator_mxnet_tpu import gluon
    bs, din, dout = 16, 32, 64
    net = gluon.nn.Dense(dout, in_units=din)
    net.initialize()
    x = mx.np.array(np.random.rand(bs, din).astype(np.float32))
    net(x)
    hand = 2 * bs * din * dout + bs * dout    # matmul + bias add
    xla = telemetry.block_fwd_flops(net, x)
    assert abs(xla - hand) / hand < 0.10


def test_steptimeline_mfu_and_stall_attribution():
    """A loop fed by a deliberately slow source: the timeline's
    data_stall dominates, and the reported MFU equals hand math from the
    same counters within 10%."""
    from incubator_mxnet_tpu.io import DeviceFeed

    def slow_source():
        for i in range(4):
            time.sleep(0.02)          # the feed can't keep up
            yield np.full((4, 4), i, np.float32)

    flops = 1e6
    peak = 1e9
    tl = telemetry.StepTimeline(flops_per_step=flops, peak_flops=peak)
    for batch in DeviceFeed(slow_source(), depth=1):
        with tl.step():
            float(np.asarray(batch.asnumpy()).sum())
    rep = tl.report()
    assert rep["steps"] == 4
    assert rep["data_stall_us"] > 0
    assert 0 < rep["stall_pct"] <= 100
    assert rep["compute_us"] == pytest.approx(
        rep["total_us"] - rep["data_stall_us"] - rep["allreduce_us"],
        abs=1.0)
    hand_mfu = flops * rep["steps"] / (rep["total_us"] * 1e-6) / peak
    assert rep["mfu"] == pytest.approx(hand_mfu, rel=0.10)
    # the feeder-side staging clock advanced too (overlapped H2D lane)
    assert profiler.feed_stats()["stage_us"] > 0


def test_estimator_fit_reports_step_timeline_with_live_mfu():
    """Acceptance: an estimator train run reports a step timeline with
    data-stall vs compute attribution and a live-counter MFU within 10%
    of the hand-computed value."""
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.gluon.contrib.estimator import (
        Estimator, StepTimelineHandler)
    bs, din, dout = 8, 16, 10
    net = gluon.nn.Dense(dout, in_units=din)
    net.initialize()
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    x = mx.np.array(np.random.rand(bs, din).astype(np.float32))
    y = mx.np.array(np.random.randint(0, dout, (bs,)))
    data = [(x, y)] * 3
    hand_fwd = 2 * bs * din * dout + bs * dout
    peak = 1e9
    est = Estimator(net, loss, train_metrics=gluon.metric.Accuracy())
    est.fit(data, epochs=1, event_handlers=[
        StepTimelineHandler(flops_per_batch=3 * hand_fwd,
                            peak_flops=peak)])
    rep = est.step_timeline
    assert rep is not None and rep["steps"] == 3
    for key in ("data_stall_us", "compute_us", "stall_pct", "compute_pct",
                "h2d_stage_us", "allreduce_us"):
        assert key in rep
    hand_mfu = (3 * hand_fwd) * rep["steps"] / (rep["total_us"] * 1e-6) \
        / peak
    assert rep["mfu"] == pytest.approx(hand_mfu, rel=0.10)
    # auto_flops path: XLA-counts the forward on the first batch
    est2 = Estimator(net, loss, train_metrics=gluon.metric.Accuracy())
    est2.fit(data, epochs=1, event_handlers=[
        StepTimelineHandler(auto_flops=True, peak_flops=peak)])
    rep2 = est2.step_timeline
    assert rep2["mfu"] == pytest.approx(
        3 * telemetry.block_fwd_flops(net, x) * rep2["steps"]
        / (rep2["total_us"] * 1e-6) / peak, rel=0.10)


def test_fused_step_flops_per_call_counts_fwd_bwd_update():
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.gluon.contrib import FusedTrainStep
    bs, din, dout = 8, 16, 10
    net = gluon.nn.Dense(dout, in_units=din)
    net.initialize()
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    x = mx.np.array(np.random.rand(bs, din).astype(np.float32))
    y = mx.np.array(np.random.randint(0, dout, (bs,)))
    net(x)
    step = FusedTrainStep(net, lambda n, a, b: loss(n(a), b).sum(), "sgd")
    fl = step.flops_per_call(x, y)
    fwd = 2 * bs * din * dout
    # fwd + bwd(2x fwd-class matmuls) + update: at least 2x the forward,
    # bounded by a generous 6x (loss/softmax/update overheads ride along)
    assert 2 * fwd <= fl <= 6 * fwd + 1e4


def test_kvstore_allreduce_timings_feed_the_registry():
    from incubator_mxnet_tpu.kvstore import KV_STATS, create
    kv = create("local")
    base = dict(KV_STATS.snapshot())
    many = kv._cross_process_sum_many(
        [mx.np.ones((64,)), mx.np.ones((32,))])
    assert len(many) == 2
    snap = KV_STATS.snapshot()
    assert snap["allreduce_us"] > base["allreduce_us"]
    assert snap["allreduce_buckets"] > base["allreduce_buckets"]
    assert snap["allreduce_bytes"] >= base["allreduce_bytes"] + (64 + 32) * 4
    # the same clock surfaces through the registry pane
    assert telemetry.snapshot()["kvstore.allreduce_us"] == \
        snap["allreduce_us"]


# ---------------------------------------------------------------------------
# serve: request timeline + /metrics
# ---------------------------------------------------------------------------
def test_server_timeline_and_metrics_text():
    import jax.numpy as jnp
    from incubator_mxnet_tpu import serve
    W = np.linspace(-1, 1, 6).reshape(3, 2).astype(np.float32)
    model = serve.CallableModel(lambda x: jnp.tanh(x @ W), (1, 2),
                                [((3,), "float32")])
    with serve.Server(model, batch_timeout_ms=1.0) as srv:
        for _ in range(4):
            srv.predict(np.ones(3, np.float32))
        tl = srv.timeline()
        assert tl["exec_ms"] > 0
        assert tl["queue_wait_ms"] >= 0
        assert tl["queue_wait_pct"] + tl["exec_pct"] == pytest.approx(
            100.0, abs=0.1)
        text = srv.metrics_text()
    assert "# TYPE mx_span_duration_us histogram" in text
    assert "mx_serve_batches" in text                 # process group
    assert 'mx_server_queue_depth{server="serve"}' in text
    assert "mx_server_exec_ms_total" in text


def test_metrics_http_endpoint():
    import urllib.request
    srv = telemetry.start_metrics_server(0)
    try:
        port = srv.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics").read().decode()
        assert "# TYPE mx_span_duration_us histogram" in body
        js = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json").read().decode())
        assert "dispatch.dispatch" in js
        with pytest.raises(Exception):
            urllib.request.urlopen(f"http://127.0.0.1:{port}/nope")
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# the package and the chip
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("module", [
    "incubator_mxnet_tpu.telemetry", "incubator_mxnet_tpu.inspect",
    "incubator_mxnet_tpu.inspect.roofline",
    "incubator_mxnet_tpu.inspect.memory"])
def test_public_names_resolve(module):
    """The modules a helper was last taken out of: what `__all__` still
    lists is there, so `from module import *` cannot fail."""
    import importlib
    mod = importlib.import_module(module)
    assert [n for n in mod.__all__ if not hasattr(mod, n)] == []
    assert len(set(mod.__all__)) == len(mod.__all__)


def test_importing_the_package_initialises_no_jax_backend():
    """A chip belongs to one process: a parent that imports the package
    (a launcher, the tune trial runner, `chipbench/tests/spread.py`'s
    kind) must stay off jax, or it holds the chip and starves the
    children that need it."""
    code = ("import incubator_mxnet_tpu\n"
            "from incubator_mxnet_tpu import deploy, serve, telemetry\n"
            "from incubator_mxnet_tpu.tune.space import scrubbed_env\n"
            "scrubbed_env()\n"
            "from jax._src import xla_bridge\n"
            "print(sorted(xla_bridge._backends))\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == "[]"
