"""mx.telemetry.trace — end-to-end request tracing, crash flight recorder,
and the open-loop tail-latency harness (ISSUE 13).

Covers: TraceContext mint/serialize/attach semantics and deterministic
head sampling; span nesting carried ACROSS thread hops (the DeviceFeed
feeder regression — feed.stage must nest under the consumer's step); the
one-trace-per-request acceptance on serve (caller → batcher thread
boundary with correct parentage, batch span linking its members);
shm-worker decode lanes landing in the consuming iterator's Chrome trace;
the flight-recorder ring/spool/dump contract (capacity knob, fault-logger
chokepoint, watchdog + overload wiring, JSONL SIGKILL spool); the top-K
slowest-requests timeline table and trace.*/flightrec.* exposure in
metrics_text; open-loop arrivals against a bounded queue accounting for
every request; and the
SIGKILL-parity crashtest --flightrec run (slow-marked).
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx  # noqa: F401  (package init: jax config)
from incubator_mxnet_tpu import fault, profiler, telemetry
from incubator_mxnet_tpu.telemetry import trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY_REC = os.path.join(REPO, "tests", "data", "tiny_imagerec.rec")


# ---------------------------------------------------------------------------
# trace context
# ---------------------------------------------------------------------------
def test_context_mint_child_and_serialize_round_trip():
    root = trace.new_context("req.root")
    assert root is not None and root.parent_span_id is None
    child = trace.child_context(root, "req.stage")
    assert child.trace_id == root.trace_id
    assert child.span_id != root.span_id
    assert child.parent_span_id == root.span_id
    assert child.parent_name == "req.root"
    # process-boundary round trip
    back = trace.TraceContext.from_dict(
        json.loads(json.dumps(child.to_dict())))
    assert (back.trace_id, back.span_id, back.parent_span_id) \
        == (child.trace_id, child.span_id, child.parent_span_id)
    assert trace.TraceContext.from_dict(None) is None
    assert trace.TraceContext.from_dict({}) is None


def test_attach_detach_and_cross_thread_current_span():
    got = {}
    with telemetry.span("consumer.step"):
        ctx = trace.current_context()
        assert ctx is not None and ctx.name == "consumer.step"

        def worker():
            assert telemetry.current_span() is None  # fresh thread: empty
            token = trace.attach(ctx)
            got["name"] = telemetry.current_span()
            trace.detach(token)
            got["after"] = telemetry.current_span()

        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert got == {"name": "consumer.step", "after": None}
    assert trace.current_context() is None


def test_trace_sampling_deterministic(monkeypatch):
    # rate 0: every root sampled out, counted in trace.sampled_out
    monkeypatch.setenv("MXNET_TRACE_SAMPLE", "0")
    before = telemetry.snapshot()["trace.sampled_out"]
    assert trace.new_context("x") is None
    assert telemetry.snapshot()["trace.sampled_out"] == before + 1
    # a sampled-out root span still records its histogram, just no ids
    with telemetry.span("sampled.out.span") as sp:
        assert sp.context is None
    assert telemetry.snapshot()[
        'span.count{name="sampled.out.span"}'] >= 1
    # rate 0.5: exactly half of a long run of roots mint
    monkeypatch.setenv("MXNET_TRACE_SAMPLE", "0.5")
    minted = sum(trace.new_context("y") is not None for _ in range(100))
    assert minted == 50
    monkeypatch.setenv("MXNET_TRACE_SAMPLE", "1.0")
    assert trace.new_context("z") is not None
    # the counters exercised above exist under their registered names
    snap = telemetry.snapshot()
    assert "trace.traces" in snap and "trace.attaches" in snap
    assert "trace.spans" in snap


def test_trace_and_flightrec_counter_groups():
    """The hot-path counters are LOCK-FREE stats groups (the documented
    DISPATCH_STATS pattern — a registry-lock inc() convoyed 32 submitter
    threads): every key exists, surfaces under its dotted name, and
    snapshot(reset) is conservation-safe."""
    for key in ("traces", "spans", "attaches", "sampled_out"):
        assert key in trace.TRACE_STATS
    for key in ("events", "dropped", "dumps"):
        assert key in trace.FLIGHTREC_STATS
    before = telemetry.snapshot()["trace.traces"]
    assert trace.new_context("group.probe") is not None
    assert telemetry.snapshot()["trace.traces"] == before + 1
    telemetry.flightrec_record("test", "group.probe")
    assert telemetry.snapshot()["flightrec.events"] >= 1


def test_span_ids_in_chrome_args_and_exception_safety(tmp_path):
    profiler._events.clear()
    profiler.start()
    try:
        with pytest.raises(RuntimeError):
            with telemetry.span("outer.traced"):
                with telemetry.span("inner.traced"):
                    raise RuntimeError("boom")
        # the stack healed: a fresh span is a root again
        assert telemetry.current_span() is None
    finally:
        profiler.stop()
    by = {e["name"]: e for e in profiler._events}
    o, i = by["outer.traced"], by["inner.traced"]
    assert i["args"]["trace_id"] == o["args"]["trace_id"]
    assert i["args"]["parent_span_id"] == o["args"]["span_id"]
    assert i["args"]["parent"] == "outer.traced"


# ---------------------------------------------------------------------------
# DeviceFeed: nesting survives the feeder-thread hop (the satellite bugfix)
# ---------------------------------------------------------------------------
def test_device_feed_stage_spans_nest_under_consumer_step(tmp_path):
    from incubator_mxnet_tpu.io import DeviceFeed

    def source():
        for i in range(4):
            yield np.full((2, 3), i, np.float32)

    profiler._events.clear()
    profiler.start()
    try:
        with telemetry.span("train.step.feedtest"):
            feed = DeviceFeed(source(), depth=2)
            for batch in feed:
                pass
    finally:
        profiler.stop()
    stage = [e for e in profiler._events if e["name"] == "feed.stage"]
    consumed = [e for e in profiler._events if e["name"] == "io.feed"]
    root = [e for e in profiler._events
            if e["name"] == "train.step.feedtest"][0]
    assert stage and consumed
    # the regression: feeder-thread spans used to start a fresh stack and
    # render parentless — now they carry the consumer's trace id
    for e in stage + consumed:
        assert e["args"].get("trace_id") == root["args"]["trace_id"], \
            f"{e['name']} rendered outside the consumer's trace"
    assert stage[0]["args"]["parent"] == "train.step.feedtest"
    # and the hop was counted
    assert telemetry.snapshot()["trace.attaches"] >= 1


# ---------------------------------------------------------------------------
# serve: one request = one trace across the thread boundary (acceptance)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_server():
    from incubator_mxnet_tpu import serve

    def fn(x):
        import jax.numpy as jnp
        return jnp.sum(x, axis=1)

    model = serve.CallableModel(fn, [1, 2, 4], [((8,), "float32")])
    with serve.Server(model, batch_timeout_ms=1.0) as srv:
        yield srv


def test_serve_one_submit_renders_one_trace(tiny_server):
    profiler._events.clear()
    profiler.start()
    try:
        with telemetry.span("client.call"):
            tiny_server.predict(np.ones(8, np.float32))
    finally:
        profiler.stop()
    evs = [e for e in profiler._events if e["cat"] == "serve"]
    by = {}
    for e in evs:
        by.setdefault(e["name"], []).append(e)
    root = [e for e in profiler._events if e["name"] == "client.call"][0]
    tid_root = root["args"]["trace_id"]
    req = by["serve.request"][-1]
    # ONE trace: every stage of this request shares the client's trace id
    assert req["args"]["trace_id"] == tid_root
    stages = ("serve.enqueue", "serve.queue_wait", "serve.execute",
              "serve.reply")
    for name in stages:
        e = by[name][-1]
        assert e["args"]["trace_id"] == tid_root, name
        # correct parentage: each stage hangs under the request root span
        assert e["args"]["parent_span_id"] == req["args"]["span_id"], name
        assert e["args"]["parent"] == "serve.request", name
    # the request root itself hangs under the caller's span
    assert req["args"]["parent_span_id"] == root["args"]["span_id"]
    # and the spans CROSS the thread boundary: enqueue on the caller
    # thread, execute on the batcher thread
    assert by["serve.enqueue"][-1]["tid"] != by["serve.execute"][-1]["tid"]
    # the batch span links its member requests
    batch = by["serve.batch"][-1]
    assert tid_root in batch["args"].get("member_traces", "")


def test_serve_timeline_slowest_table_and_metrics_text(tiny_server,
                                                       monkeypatch):
    # an explicitly-set sample rate forces request-root minting even with
    # no profiler/spool attached (trace.collector_active) — the cheap way
    # to get trace ids into the slowest table in production
    monkeypatch.setenv("MXNET_TRACE_SAMPLE", "1.0")
    trace._expire_env_memo()   # the knob is TTL-cached (50ms)
    for _ in range(3):
        tiny_server.predict(np.ones(8, np.float32), deadline_ms=5000)
    st = tiny_server.stats()
    slow = st["timeline"]["slowest"]
    assert slow, "top-K slowest table is empty after replies"
    assert len(slow) <= 8
    totals = [r["total_ms"] for r in slow]
    assert totals == sorted(totals, reverse=True)
    row = slow[0]
    for key in ("trace_id", "total_ms", "queue_wait_ms", "exec_ms",
                "batch_size", "deadline_margin_ms"):
        assert key in row
    assert row["trace_id"]           # traced by default (sample rate 1)
    assert row["queue_wait_ms"] >= 0 and row["exec_ms"] >= 0
    # at least one row carries a deadline margin (the deadline_ms calls)
    assert any(r["deadline_margin_ms"] is not None for r in slow)
    # metrics_text exposes the new counter families
    text = tiny_server.metrics_text()
    for needle in ("mx_trace_traces", "mx_trace_spans",
                   "mx_flightrec_events"):
        assert needle in text, needle


# ---------------------------------------------------------------------------
# shm-worker decode lanes join the consuming iterator's trace (acceptance)
# ---------------------------------------------------------------------------
def test_imagerec_worker_lanes_in_consumer_trace(tmp_path):
    from incubator_mxnet_tpu.io import ImageRecordIter

    it = ImageRecordIter(path_imgrec=TINY_REC, data_shape=(32, 32, 3),
                         batch_size=3, resize=36, workers=1, lookahead=1,
                         round_batch=False, prefetch=True)
    try:
        profiler._events.clear()
        profiler.start()
        try:
            with telemetry.span("train.step.rectest"):
                # deeper than the lookahead so at least one batch is
                # SUBMITTED inside the consumer's span (construction-time
                # submits predate it by design)
                for _ in range(4):
                    it.next()
        finally:
            profiler.stop()
    finally:
        it.close()
    root = [e for e in profiler._events
            if e["name"] == "train.step.rectest"][0]
    lanes = [e for e in profiler._events if e["name"] == "io.worker.decode"]
    assert lanes, "no decode-worker lane events in the Chrome trace"
    in_trace = [e for e in lanes
                if e["args"].get("trace_id") == root["args"]["trace_id"]]
    assert in_trace, ("worker decode lanes never joined the consuming "
                      "iterator's trace")
    assert "worker" in in_trace[0]["args"]


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
@pytest.fixture
def fresh_flightrec(monkeypatch):
    trace.FLIGHTREC._reset_for_tests()
    yield trace.FLIGHTREC
    trace.FLIGHTREC._reset_for_tests()


def test_flightrec_ring_capacity_and_dropped(fresh_flightrec, monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHTREC_EVENTS", "16")
    before = telemetry.snapshot()["flightrec.dropped"]
    before_ev = telemetry.snapshot()["flightrec.events"]
    for i in range(40):
        telemetry.flightrec_record("test", "ring.probe", i=i)
    evs = telemetry.flightrec_events()
    assert len(evs) == 16
    assert [e["i"] for e in evs] == list(range(24, 40))  # newest retained
    assert telemetry.snapshot()["flightrec.dropped"] == before + 24
    assert telemetry.snapshot()["flightrec.events"] == before_ev + 40


def test_flightrec_spool_and_dump(fresh_flightrec, monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_FLIGHTREC_DIR", str(tmp_path))
    telemetry.flightrec_record("test", "spool.probe", detail="x")
    with telemetry.span("spooled.span", step=3):
        time.sleep(0.06)     # past the 50ms close-event duration floor
    with telemetry.span("fast.span"):
        pass                 # under the floor: open spooled, close not
    spool = fresh_flightrec.spool_path
    assert spool and os.path.exists(spool)
    lines = [json.loads(l) for l in open(spool) if l.strip()]
    assert lines[0]["name"] == "spool.probe"
    opens = [l for l in lines if l["kind"] == "span_open"]
    closes = [l for l in lines if l["kind"] == "span"]
    assert opens and opens[0]["name"] == "spooled.span"
    assert opens[0]["step"] == 3
    assert closes and closes[0]["name"] == "spooled.span"
    assert closes[0]["dur_us"] >= 50e3
    # the duration floor: fast spans record their OPEN (the in-flight
    # marker) but not a close event
    assert any(o["name"] == "fast.span" for o in opens)
    assert not any(c["name"] == "fast.span" for c in closes)
    # dump: one JSON black box, atomic, counted
    before = telemetry.snapshot()["flightrec.dumps"]
    path = telemetry.flightrec_dump(reason="unit")
    assert path and os.path.dirname(path) == str(tmp_path)
    with open(path) as f:
        payload = json.load(f)
    assert payload["reason"] == "unit"
    assert payload["pid"] == os.getpid()
    assert payload["n_events"] == len(payload["events"]) > 0
    assert telemetry.snapshot()["flightrec.dumps"] == before + 1


def test_flightrec_no_files_without_dir(fresh_flightrec, monkeypatch):
    monkeypatch.delenv("MXNET_FLIGHTREC_DIR", raising=False)
    telemetry.flightrec_record("test", "quiet.probe")
    assert fresh_flightrec.spool_path is None
    # rate-limited dumps are no-ops without the dir (no surprise files)
    assert telemetry.flightrec_maybe_dump("unit") is None


def test_fault_log_events_feed_flightrec(fresh_flightrec):
    fault.clear()
    fault.install("resilient.step", "error", at=1)
    try:
        with pytest.raises(fault.InjectedFault):
            fault.inject("resilient.step")
    finally:
        fault.clear()
    evs = [e for e in telemetry.flightrec_events()
           if e["name"] == "fault.injected"]
    assert evs, "fault injection never reached the flight recorder"
    assert evs[-1]["point"] == "resilient.step"
    assert evs[-1]["kind"] == "fault"          # envelope kind preserved
    assert evs[-1]["f_kind"] == "error"        # the rule's kind, prefixed


def test_watchdog_timeout_dumps_flightrec(fresh_flightrec, monkeypatch,
                                          tmp_path):
    monkeypatch.setenv("MXNET_FLIGHTREC_DIR", str(tmp_path))
    with pytest.raises(fault.WatchdogTimeout):
        with fault.watchdog(0.05):
            time.sleep(0.4)
    dump = os.path.join(str(tmp_path), f"flightrec-{os.getpid()}.json")
    assert os.path.exists(dump), "watchdog expiry left no black box"
    with open(dump) as f:
        payload = json.load(f)
    assert any(e["kind"] == "watchdog" for e in payload["events"])


def test_serve_overload_shed_records_and_dumps(fresh_flightrec,
                                               monkeypatch, tmp_path):
    from incubator_mxnet_tpu import serve

    monkeypatch.setenv("MXNET_FLIGHTREC_DIR", str(tmp_path))

    class SlowModel:
        # host-side slow model (a sleep inside a jitted fn would only
        # fire at trace time): every batch takes 50ms, so rapid submits
        # overflow the 1-deep queue and the shed policy fires
        batch_sizes = [1]
        row_specs = [((4,), "float32")]
        single_output = True

        def run_batch(self, bucket, arrs):
            time.sleep(0.05)
            return (np.zeros((bucket, 1), np.float32),)

        def warmup(self):
            pass

        def compile_cache_size(self):
            return 1

    with serve.Server(SlowModel(), max_queue=1, overload_policy="shed",
                      batch_timeout_ms=0.1) as srv:
        for i in range(8):
            try:
                srv.submit(np.ones(4, np.float32))
            except serve.QueueFullError:
                pass
    sheds = [e for e in telemetry.flightrec_events()
             if e["kind"] == "serve.shed"]
    assert sheds, "overload shedding never reached the flight recorder"
    dump = os.path.join(str(tmp_path), f"flightrec-{os.getpid()}.json")
    assert os.path.exists(dump), "overload shedding left no black box"


# ---------------------------------------------------------------------------
# open-loop arrivals against a bounded queue
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy,deadline_ms", [
    ("reject", None), ("shed", None), ("reject", 12.0)])
def test_open_loop_arrivals_account_for_every_request(policy, deadline_ms):
    """Arrivals sent on a seeded schedule, never waiting for replies, at
    several times what the server can take: every request ends as exactly
    one of completed / dropped-by-kind, none is lost or counted twice,
    and the server's own counters agree with the caller's tally."""
    from incubator_mxnet_tpu import serve

    class SlowModel:
        batch_sizes = [1, 2]
        row_specs = [((4,), "float32")]
        single_output = True

        def run_batch(self, bucket, arrs):
            time.sleep(0.01)
            return (np.zeros((bucket, 1), np.float32),)

        def warmup(self):
            pass

        def compile_cache_size(self):
            return 1

    n = 60
    gaps = np.random.RandomState(13).exponential(1.0 / 800.0, size=n)
    lock = threading.Lock()
    completed, drops, futures = [], {}, []

    def _drop(e):
        with lock:
            drops[type(e).__name__] = drops.get(type(e).__name__, 0) + 1

    def _done(f):
        try:
            f.result()
        except Exception as e:
            _drop(e)
        else:
            with lock:
                completed.append(f)

    with serve.Server(SlowModel(), max_queue=4, overload_policy=policy,
                      default_deadline_ms=deadline_ms,
                      batch_timeout_ms=0.1) as srv:
        arrival = time.perf_counter()
        for i in range(n):
            arrival += gaps[i]
            wait = arrival - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            try:
                fut = srv.submit(np.ones(4, np.float32))
            except serve.QueueFullError as e:
                _drop(e)
                continue
            fut.add_done_callback(_done)
            futures.append(fut)
        for f in futures:
            try:
                f.result(timeout=30)
            except serve.ServeError:
                pass
        st = srv.stats()
    dropped = sum(drops.values())
    assert len(completed) + dropped == n, (len(completed), drops)
    assert len(completed) > 0 and dropped > 0, (len(completed), drops)
    assert set(drops) <= {"QueueFullError", "RequestTimeout"}, drops
    # the server's own counters tell the same story, kind by kind
    assert st["replies"] == len(completed)
    assert st["rejected"] + st["shed"] == drops.get("QueueFullError", 0)
    assert st["timeouts"] == drops.get("RequestTimeout", 0)
    if policy == "shed":
        # shed-oldest admits every arrival; the drops are queued requests
        assert len(futures) == n and st["rejected"] == 0
    else:
        assert st["shed"] == 0
    if deadline_ms is None:
        assert set(drops) == {"QueueFullError"}
    else:
        assert drops.get("RequestTimeout", 0) > 0, drops


# ---------------------------------------------------------------------------
# SIGKILL parity (slow): crashtest --flightrec
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_crashtest_flightrec_sigkill_parity(tmp_path):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "crashtest.py"),
         "--flightrec", "--steps", "10", "--ckpt-every", "3",
         "--kill-at", "6", "--dir", str(tmp_path)],
        capture_output=True, text=True, timeout=600, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "flight recorder OK" in r.stdout
    assert "in-flight elastic.step" in r.stdout
