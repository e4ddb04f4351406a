"""The hot paths' live spans on the profiler's clock (counts and
structure, never times): the engine's wave loop and the fused train step
under a `jax.profiler` session, read back from `/host:CPU` of the
`.xplane.pb` and from `profiler.events()`; the off path; the bounded
buffer; a request's public timeline; and the names the benchmark's
reducer relies on (program module names, kernel names, scopes)."""
import ast
import glob
import json
import os
import re
import sys
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, profiler, serve, telemetry
from incubator_mxnet_tpu import optimizer as opt_mod
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.gluon.contrib import FusedTrainStep
from incubator_mxnet_tpu.io.device_feed import DeviceFeed
from incubator_mxnet_tpu.serve.metrics import percentile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench.paths.serve_engine import COUNTED  # noqa: E402
from chipbench.paths.serve_hybrid import cache_counters  # noqa: E402
from chipbench.readers import (cache_live_share, engine_stats,  # noqa: E402
                               feed_stats, span_mean_ms, span_ms)

KERNEL_FILES = ("incubator_mxnet_tpu/ops/pallas_kernels.py",
                "incubator_mxnet_tpu/ops/pallas_attention.py")
PROGRAM_METRICS = sorted(glob.glob(os.path.join(
    ROOT, "chipbench", "layer_metrics", "*_prog_ms.*.json")))
DECODE_CHILDREN = ["serve.decode_batch.pack", "serve.decode_batch.dispatch",
                   "serve.decode_batch.readback", "serve.decode_batch.emit"]
# pack and dispatch are the wave the span hands the device, readback and
# emit the wave before it: the first span of a busy stretch has only the
# former, the span that finds nothing more to dispatch only the latter
DECODE_SHAPES = (DECODE_CHILDREN, DECODE_CHILDREN[:2], DECODE_CHILDREN[2:])
PREFILL_CHILDREN = ["serve.prefill_batch.pack",
                    "serve.prefill_batch.dispatch"]


@pytest.fixture(autouse=True)
def _no_collector(monkeypatch):
    """No collector but the one a test opens."""
    monkeypatch.delenv("MXNET_TRACE_SAMPLE", raising=False)
    monkeypatch.delenv("MXNET_FLIGHTREC_DIR", raising=False)
    telemetry.FLIGHTREC._reset_for_tests()
    telemetry.trace._expire_env_memo()
    profiler.stop()
    profiler._events.clear()
    yield
    profiler._events.clear()


def toy_engine(**kw):
    cfg = serve.DecoderConfig(vocab=64, embed=32, layers=2, heads=4,
                              head_dim=8, max_len=48)
    return serve.ContinuousEngine(
        serve.CachedDecoder(cfg, seed=11), max_slots=4,
        prefix_cache_slots=1, prefix_block=4, prefill_window=16,
        decode_steps=2, **kw)


def toy_step():
    mx.seed(3)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu", in_units=8),
            nn.Dense(4, in_units=16))
    net.initialize()
    loss_fn = gluon.loss.L2Loss()
    step = FusedTrainStep(net, lambda n, x, y: loss_fn(n(x), y).mean(),
                          opt_mod.create("sgd", learning_rate=0.1))
    rng = np.random.RandomState(0)
    data = [(rng.randn(8, 8).astype(np.float32),
             rng.randn(8, 4).astype(np.float32)) for _ in range(3)]
    return step, data


def host_events(trace_dir, prefix):
    """[(thread line, name, start_ns, end_ns, stats)] of the `/host:CPU`
    events whose name starts with `prefix`, by start."""
    import jax
    pb = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                   recursive=True)[0]
    out = []
    for plane in jax.profiler.ProfileData.from_file(pb).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append((line.name, ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: (e[2], -e[3]))


def inside(events, outer):
    """Names of the events that lie inside `outer`, in order."""
    return [e[1] for e in events
            if e is not outer and e[0] == outer[0]
            and outer[2] <= e[2] and e[3] <= outer[3]]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A toy engine under one `jax.profiler` session: a cold wave, then a
    request whose prompt hits the prefix the first wave published."""
    import jax
    trace_dir = str(tmp_path_factory.mktemp("serve_trace"))
    profiler.stop()
    profiler._events.clear()
    eng = toy_engine().start()
    try:
        jax.profiler.start_trace(trace_dir)
        try:
            # the iteration that was waiting when the session opened had
            # asked its gate before: one request takes it
            eng.generate([9, 9, 9], 2)
            s0, t0_us = eng.stats(), profiler._now_us()
            futs = [eng.submit([1, 2, 3, 4, 5 + i], 6) for i in range(3)]
            [f.result(timeout=120) for f in futs]
            futs.append(eng.submit([1, 2, 3, 4, 5, 7, 8], 5))
            futs[-1].result(timeout=120)
            s1 = eng.stats()
        finally:
            jax.profiler.stop_trace()
        buffered = profiler.events()
        after = len(profiler.events())
        eng.generate([4, 4, 4], 2)           # the session is closed
        quiet = len(profiler.events()) == after
    finally:
        eng.close()
    return {"host": host_events(trace_dir, "serve."), "buffer": buffered,
            "s0": s0, "s1": s1, "t0_us": t0_us, "futs": futs,
            "quiet": quiet}


def test_one_decode_span_per_decode_iteration_on_host_plane(served):
    """Every wave is packed once and emitted once, a span later; a span
    that does both stands for one `decode_iterations`, and a busy stretch
    has one span more than waves (its first only dispatches, its last
    only reads)."""
    grew = (served["s1"]["decode_iterations"]
            - served["s0"]["decode_iterations"])
    assert grew >= 3
    since = [e for e in served["buffer"] if e["ts"] >= served["t0_us"]]
    for child in ("pack", "emit"):
        assert sum(1 for e in since
                   if e["name"] == "serve.decode_batch." + child) == grew
    buffered = [e for e in served["buffer"]
                if e["name"] == "serve.decode_batch"]
    spans = [e for e in buffered if e["ts"] >= served["t0_us"]]
    ahead = sum(e["args"]["ahead"] for e in spans)
    assert ahead == (served["s1"]["waves_ahead"]
                     - served["s0"]["waves_ahead"])
    # a wave not dispatched ahead opens a stretch: one more span each
    assert len(spans) == grew + (grew - ahead)
    on_host = [e for e in served["host"] if e[1] == "serve.decode_batch"]
    assert len(on_host) == len(buffered)
    assert sum(e[4]["tokens"] for e in on_host[-len(spans):]) == (
        served["s1"]["decode_tokens"] - served["s0"]["decode_tokens"])


def test_decode_span_has_its_four_children_in_order(served):
    waves = [e for e in served["host"] if e[1] == "serve.decode_batch"]
    assert waves
    for w in waves:
        kids = inside(served["host"], w)
        assert kids in DECODE_SHAPES
        assert w[4]["steps"] == 2 and w[4]["parent"] == "serve.wave"
        # dispatched while the wave before it was unread: all four
        assert w[4]["ahead"] in (0, 1)
        assert w[4]["ahead"] == 0 or kids == DECODE_CHILDREN
        if "serve.decode_batch.emit" in kids:
            assert w[4]["active"] >= 1 and w[4]["tokens"] >= 1
        else:
            assert w[4]["active"] == w[4]["tokens"] == 0
    assert any(w[4]["ahead"] for w in waves)


def test_prefill_span_children_and_copy_spans(served):
    host = served["host"]
    waves = [e for e in host if e[1] == "serve.prefill_batch"]
    assert waves
    cold = [w for w in waves if inside(host, w) == PREFILL_CHILDREN]
    hit = [w for w in waves if "serve.copy.dispatch" in inside(host, w)]
    assert cold and len(hit) == 1
    # a hit copies the cached row in, then prefills its suffix by chunks
    assert inside(host, hit[0]) == ["serve.copy.dispatch"] + PREFILL_CHILDREN
    copies = [e[4] for e in host if e[1] == "serve.copy.dispatch"]
    assert {c["why"] for c in copies} == {"hit", "publish"}
    # one pair a copy, one 4-token block a pair: the span says how many
    # positions it moved, and `stats()` sums them
    assert all((c["pairs"], c["positions"]) == (1, 4) for c in copies)
    assert sum(c["positions"] for c in copies) == (
        served["s1"]["copied_positions"] - served["s0"]["copied_positions"])
    retires = [e for e in host if e[1] == "serve.retire"]
    # the lead request was admitted in the iteration that was not yet
    # armed (the four admissions below) and read back in the next
    assert retires and sum(e[4]["n"] for e in retires) == 5
    published = [r for r in retires
                 if "serve.copy.dispatch" in inside(host, r)]
    assert published
    admits = [e for e in host if e[1] == "serve.admit"]
    assert sum(e[4]["admitted"] for e in admits) == 4
    assert all("waiting" in e[4] and "expired" in e[4] for e in admits)


def test_buffer_holds_the_same_spans_with_wave_trace_ids(served):
    buf = [e for e in served["buffer"] if e["cat"] == "serve"]
    names = [e["name"] for e in buf]
    host_names = [e[1] for e in served["host"]]
    for name in set(host_names):
        assert names.count(name) == host_names.count(name), name
    waves = [e for e in buf if e["name"] == "serve.decode_batch"]
    for w in waves:
        tid = w["args"]["trace_id"]
        assert re.fullmatch(r"wave-\d+", tid)
        kids = [e["name"] for e in buf
                if e["args"].get("trace_id") == tid
                and e["args"].get("parent") == "serve.decode_batch"]
        assert kids in DECODE_SHAPES
    assert len({w["args"]["trace_id"] for w in waves}) == len(waves)
    # per-request spans stay request scale: buffer only, their own traces
    assert names.count("serve.request") == 5      # request scale: all
    assert not any(n in host_names for n in
                   ("serve.request", "serve.prefill", "serve.decode"))
    assert served["quiet"], "spans were buffered after the session closed"


def test_every_retired_request_leaves_its_queue_and_prefill_span(served):
    """Request scale, noted at retirement from `fut.timing`'s fields: the
    wait for a slot and the prefill, an async pair each (they overlap
    other requests' without nesting), with the request's number and the
    wave whose iteration admitted it."""
    pairs = {}
    for e in served["buffer"]:
        if e["ph"] in "be":
            pairs.setdefault((e["name"], e["id"]), {})[e["ph"]] = e
    assert sorted(n for n, _ in pairs) == ["serve.prefill"] * 5 \
        + ["serve.queue"] * 5
    for (name, rid), pair in pairs.items():
        b, e = pair["b"], pair["e"]
        assert abs(e["ts"] - b["ts"] - e["dur"]) < 0.01
        assert e["dur"] >= 0 and b["dur"] == 0
        assert e["args"]["request"] == rid == b["args"]["request"]
        assert re.fullmatch(r"wave-\d+", e["args"]["cause"])
        # noted when the request retired, after the span's own end
        assert e["args"]["retired_us"] >= e["ts"]
        assert e["args"]["parent"] == "serve.request"
        if name == "serve.queue":       # ends where the prefill begins
            after = pairs["serve.prefill", rid]
            assert abs(after["b"]["ts"] - e["ts"]) < 1.0
            assert after["e"]["args"]["cause"] == e["args"]["cause"]
    # the durations are the timeline's
    waits = sorted(p["e"]["dur"] for (n, _), p in pairs.items()
                   if n == "serve.queue")
    mine = sorted((f.timing.t_admit - f.timing.t_submit) * 1e6
                  for f in served["futs"])
    assert all(any(abs(w - m) < 1.0 for w in waits) for m in mine)
    # and the reader's mean over the spans that end in the interval
    complete = [(e["name"], e["tid"], e["ts"], e["dur"])
                for e in served["buffer"] if e["ph"] == "X"]
    ends = [(e["name"], e["args"]["retired_us"], e["dur"])
            for e in served["buffer"] if e["ph"] == "e"]
    retired = sorted(f.timing.t_done * 1e6 for f in served["futs"])
    assert all(any(abs(r - t) < 1.0 for _, t, _ in ends) for r in retired)
    got = span_mean_ms.reduce(complete, ends,
                              {"name": r"^serve\.queue$"}, 3600.0)
    assert got == pytest.approx(1e-3 * sum(waits) / 5)


def test_a_request_queued_before_the_collector_moves_no_complete_span():
    """Such a request's spans begin before the collector was armed; the
    buffer's complete spans, whose first start places every span metric's
    interval (`span_ms`), still begin after it."""
    eng = toy_engine().start()
    try:
        with eng._cv:           # nothing is admitted before the collector
            fut = eng.submit([1, 2, 3], 6)
            armed_us = profiler._now_us()
            profiler.start()
        try:
            fut.result(timeout=120)
            eng.generate([4, 5], 2)
        finally:
            profiler.stop()
    finally:
        eng.close()
    events = profiler.events()
    begun = [e for e in events if e["ph"] == "b" and e["id"] == 1]
    assert sorted(e["name"] for e in begun) == ["serve.prefill",
                                                "serve.queue"]
    queue = [e for e in begun if e["name"] == "serve.queue"][0]
    assert queue["ts"] < armed_us
    # submitted unarmed, so without a root: under the retirement that
    # noted it
    assert queue["args"]["parent"] == "serve.retire"
    complete = [e for e in events if e["ph"] == "X"]
    assert complete and min(e["ts"] for e in complete) >= armed_us
    assert span_mean_ms.reduce(
        span_ms.spans(), span_mean_ms.ended(),
        {"name": r"^serve\.queue$"}, 3600.0) > 0
    # the aggregate table counts a pair once
    assert profiler.dumps(format="json").count('"serve.queue"') == 1
    assert json.loads(profiler.dumps(format="json"))["events"][
        "serve.queue"]["calls"] == 2


def test_events_accessor_filters_and_copies():
    profiler.start()
    try:
        with telemetry.span("outer", k=1):
            with telemetry.span("inner", cat="io"):
                pass
    finally:
        profiler.stop()
    assert [e["name"] for e in profiler.events("io")] == ["inner"]
    got = profiler.events()
    assert [e["name"] for e in got] == ["inner", "outer"]
    assert got[0]["args"]["parent"] == "outer"
    got[0]["name"] = "changed"
    assert profiler.events()[0]["name"] == "inner"


def test_unarmed_engine_buffers_nothing_and_builds_no_annotation(
        monkeypatch):
    import jax

    class Refuse:
        is_enabled = staticmethod(lambda: False)

        def __init__(self, *a, **k):
            raise AssertionError("an annotation was built off the hot "
                                 "path's gate")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Refuse)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Refuse)
    monkeypatch.setattr(profiler, "_annotation", [Refuse])
    assert not telemetry.trace.armed()
    opens = telemetry.snapshot()["flightrec.events"]
    request_scale = ['span.count{name="%s"}' % n for n in (
        "serve.queue", "serve.prefill", "serve.decode", "serve.request")]
    counted = [telemetry.snapshot().get(k, 0) for k in request_scale]
    eng = toy_engine().start()
    try:
        # submit -> retirement records no span, request scale or wave
        out = eng.generate([1, 2, 3], 6)
        assert eng.stats()["decode_iterations"] >= 2
        assert eng.stats()["retired"] == 1
    finally:
        eng.close()
    assert [telemetry.snapshot().get(k, 0)
            for k in request_scale] == counted
    step, data = toy_step()
    for x, y in DeviceFeed(data):
        step(x, y)
    assert len(out) == 6
    assert profiler.events() == []
    assert telemetry.snapshot()["flightrec.events"] == opens


def test_armed_without_a_session_spans_reach_no_buffer(monkeypatch):
    """`MXNET_TRACE_SAMPLE` arms the collectors; the buffer still belongs
    to `mx.profiler` or a `jax.profiler` session."""
    monkeypatch.setenv("MXNET_TRACE_SAMPLE", "1")
    telemetry.trace._expire_env_memo()
    assert telemetry.trace.armed()
    before = telemetry.snapshot().get(
        'span.count{name="serve.decode_batch.pack"}', 0)
    ring = telemetry.snapshot()["flightrec.events"]
    eng = toy_engine().start()
    try:
        eng.generate([1, 2, 3], 6)
        waves = eng.stats()["decode_iterations"]
    finally:
        eng.close()
    assert profiler.events() == []
    assert telemetry.snapshot()[
        'span.count{name="serve.decode_batch.pack"}'] - before == waves
    # wave-scale spans open under the wave's context: none of them
    # writes an in-flight marker into the flight recorder's ring
    opened = [e for e in telemetry.flightrec_events()
              if e["kind"] == "span_open" and e["name"].startswith("serve.")]
    assert opened == []
    assert telemetry.snapshot()["flightrec.events"] - ring < waves


def test_decode_span_says_how_many_lanes_sampled():
    """`serve.decode_batch` carries `sampled=<lanes>` of the wave it
    emits: 0 on a greedy wave, the lanes with `temperature > 0`
    otherwise; the waves with any are `stats()["sampled_waves"]`."""
    def emitted():
        return [e["args"]["sampled"] for e in profiler.events("serve")
                if e["name"] == "serve.decode_batch" and e["args"]["tokens"]]

    eng = toy_engine().start()
    profiler.start()
    try:
        eng.generate([1, 2, 3], 6)
        greedy = emitted()
        eng.generate([4, 5, 6], 7, temperature=0.8, top_p=0.9, seed=2)
        both = emitted()
        stats = eng.stats()
    finally:
        profiler.stop()
        eng.close()
    assert greedy and set(greedy) == {0}
    assert both[len(greedy):] == [1, 1, 1]       # 1 + 3 waves x 2 tokens
    assert stats["sampled_waves"] == 3


def test_first_tokens_alone_are_read_in_the_prefill_span():
    """A request that ends at its first token dispatches no wave: the
    iteration after its prefill finds nothing to dispatch and reads the
    token at once, in a `serve.prefill_batch` span with the one child."""
    eng = toy_engine().start()
    profiler.start()
    try:
        eng.generate([9, 9, 9], 2)       # takes the not-yet-armed iteration
        mark = len(profiler.events("serve"))
        out = eng.generate([1, 2, 3], 1)
        got = profiler.events("serve")[mark:]
    finally:
        profiler.stop()
        eng.close()
    assert len(out) == 1
    names = [e["name"] for e in got if e["name"] != "serve.idle"
             and e["args"].get("parent") in ("serve.wave",
                                             "serve.prefill_batch")]
    assert names == ["serve.admit",
                     "serve.prefill_batch.pack",
                     "serve.prefill_batch.dispatch", "serve.prefill_batch",
                     "serve.admit",
                     "serve.prefill_batch.readback", "serve.prefill_batch",
                     "serve.retire"]


def test_event_buffer_is_bounded():
    assert profiler._events.maxlen == profiler.EVENTS_CAP
    profiler.start()
    try:
        for i in range(profiler.EVENTS_CAP + 7):
            profiler.record_event("e", "op", 0.0, ts_us=i)
    finally:
        profiler.stop()
    assert len(profiler._events) == profiler.EVENTS_CAP
    assert profiler.events()[0]["ts"] == 7      # the oldest went first


def test_request_timing_is_public_monotone_and_whole_at_resolution(served):
    seen = []
    eng = toy_engine().start()
    try:
        fut = eng.submit([1, 2, 3, 4, 5], 6)
        fut.add_done_callback(lambda f: seen.append(f.timing.as_dict()))
        t = fut.timing
        assert t.t_submit is not None and t.prompt_tokens == 5
        fut.result(timeout=120)
    finally:
        eng.close()
    for tm in [f.timing for f in served["futs"]] + [fut.timing]:
        assert tm.t_submit <= tm.t_admit <= tm.t_first <= tm.t_done
        assert tm.tokens >= 5 and tm.slot is not None
    # whole before the future resolved: the callback saw the final record
    assert seen and seen[0] == fut.timing.as_dict()
    assert seen[0]["t_done"] is not None and seen[0]["tokens"] == 6
    hit = served["futs"][-1].timing
    assert hit.cached_tokens == 4 and hit.prompt_tokens == 7
    assert served["futs"][0].timing.cached_tokens == 0
    with pytest.raises(AttributeError):
        fut.timing.t_done = 0.0


def test_stats_percentiles_come_from_the_timing_records():
    eng = toy_engine().start()
    t_started = time.perf_counter()
    try:
        st = eng.stats()
        # nothing from warm-up: no record, and the clock starts after it
        assert st["ttft_p50_ms"] is None and st["e2e_p99_ms"] is None
        assert st["elapsed_s"] <= time.perf_counter() - t_started + 0.01
        futs = [eng.submit([1, 2, 3 + i], 4 + i) for i in range(5)]
        [f.result(timeout=120) for f in futs]
        st = eng.stats()
    finally:
        eng.close()
    tm = [f.timing for f in futs]
    ttft = sorted((t.t_first - t.t_submit) * 1e3 for t in tm)
    e2e = sorted((t.t_done - t.t_submit) * 1e3 for t in tm)
    tpot = sorted((t.t_done - t.t_first) * 1e3 / (t.tokens - 1) for t in tm)
    for name, vals in (("ttft", ttft), ("e2e", e2e), ("tpot", tpot)):
        for q in (50, 99):
            assert st[f"{name}_p{q}_ms"] == round(percentile(vals, q), 3)


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """Three calls of a toy fused step, fed by a DeviceFeed, under one
    `jax.profiler` session."""
    import jax
    trace_dir = str(tmp_path_factory.mktemp("train_trace"))
    profiler.stop()
    profiler._events.clear()
    step, data = toy_step()
    step(*data[0]).wait_to_read()           # compiled before the session
    jax.profiler.start_trace(trace_dir)
    try:
        for x, y in DeviceFeed(data):
            loss = step(x, y)
        loss.wait_to_read()
    finally:
        jax.profiler.stop_trace()
    return {"host": host_events(trace_dir, "train.")
            + host_events(trace_dir, "io.feed"),
            "buffer": profiler.events()}


def test_train_step_spans_over_three_calls(trained):
    steps = [e for e in trained["host"] if e[1] == "train.step"]
    assert len(steps) == 3
    assert [e[4]["step_num"] for e in steps] == [2, 3, 4]
    for s in steps:
        assert inside(trained["host"], s) == ["train.step.dispatch"]
    buf = [e for e in trained["buffer"] if e["name"].startswith("train.")]
    assert [e["name"] for e in buf] == ["train.step.dispatch",
                                        "train.step"] * 3
    assert all(e["args"]["parent"] == "train.step" for e in buf[::2])


def test_feed_wait_is_a_live_span(trained):
    waits = [e for e in trained["host"] if e[1] == "io.feed"]
    # three batches and the terminal sentinel
    assert len(waits) == 4
    assert all("buffer" in e[4] for e in waits)
    steps = [e for e in trained["host"] if e[1] == "train.step"]
    # a wait lies between steps, not inside one
    assert not any(s[2] <= w[2] and w[3] <= s[3]
                   for w in waits for s in steps)


def module_name(lowered):
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


@pytest.fixture(scope="module")
def program_names():
    """Module names of the five programs, lowered at toy size."""
    import jax
    eng = toy_engine()
    low = eng.lowered_programs()
    slab = jax.ShapeDtypeStruct(eng.pool.shape, eng.pool.dtype)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), eng.model.params)
    S, P, W = eng.pool.max_slots, eng.prefill_lanes, eng.prefill_window
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, "int32")  # noqa: E731
    chunk = eng._chunk_progs[eng._chunk_extents[0]].lower(
        params, slab, slab, i32(S, W), i32(S), i32(S))
    copy = eng._copy_prog.lower(slab, slab, i32(P), i32(P), i32(P))
    step, data = toy_step()
    return {"decode": module_name(low["decode"]),
            "prefill": module_name(low["prefill"]),
            "chunk_prefill": module_name(chunk),
            "copy": module_name(copy),
            "train": module_name(step.lowered(*data[0]))}


FOUND_BY = {"decode_prog_ms.serve": ["decode"],
            "prefill_prog_ms.serve": ["prefill", "chunk_prefill"],
            "copy_prog_ms.serve": ["copy"],
            "train_prog_ms.train": ["train"]}


def test_every_program_metric_is_under_the_contract():
    names = {os.path.basename(p)[:-len(".json")] for p in PROGRAM_METRICS}
    assert names == set(FOUND_BY)


@pytest.mark.parametrize("path", PROGRAM_METRICS,
                         ids=[os.path.basename(p) for p in PROGRAM_METRICS])
def test_program_metric_pattern_finds_its_module_name(path, program_names):
    """The profiler's `XLA Modules` line names an execution
    `<module>(<fingerprint>)`; the benchmark's patterns find the programs
    by the names their factories give them."""
    with open(path) as f:
        pattern = json.load(f)["params"]["pattern"]
    metric = os.path.basename(path)[:-len(".json")]
    for prog, name in program_names.items():
        found = re.search(pattern, name + "(1234)") is not None
        assert found == (prog in FOUND_BY[metric]), (metric, prog, name)


# ---------------------------------------------------------------------------
# the program keeps what the benchmark's host-side readers read
# ---------------------------------------------------------------------------
HOST_READERS = ("span_ms", "engine_stats", "cache_live_share", "feed_stats")


def _host_metrics():
    out = []
    for path in sorted(glob.glob(os.path.join(
            ROOT, "chipbench", "layer_metrics", "*.json"))):
        with open(path) as f:
            if json.load(f)["reader"] in HOST_READERS:
                out.append(path)
    return out


HOST_METRICS = _host_metrics()


def split_bars(body):
    """`body` cut at the `|`s that lie in no group of its own."""
    parts, depth, cur, i = [], 0, "", 0
    while i < len(body):
        c = body[i]
        if c == "\\":
            cur, i = cur + body[i:i + 2], i + 2
            continue
        depth += (c == "(") - (c == ")")
        if c == "|" and depth == 0:
            parts, cur = parts + [cur], ""
        else:
            cur += c
        i += 1
    return parts + [cur]


def alternatives(pattern):
    """Every pattern got by keeping one side of each `|` of `pattern`."""
    parts = split_bars(pattern)
    if len(parts) > 1:
        return [a for part in parts for a in alternatives(part)]
    i = 0
    while i < len(pattern):
        if pattern[i] == "\\":
            i += 2
            continue
        if pattern[i] == "(":
            depth, j = 1, i + 1
            while depth:
                if pattern[j] == "\\":
                    j += 1
                depth += (pattern[j] == "(") - (pattern[j] == ")")
                j += 1
            body = pattern[i + 1:j - 1]
            head = "?:" if body.startswith("?:") else ""
            sides = split_bars(body[len(head):])
            if len(sides) > 1:
                return [a for side in sides for a in alternatives(
                    pattern[:i] + "(?:" + side + ")" + pattern[j:])]
        i += 1
    return [pattern]


def test_alternatives_keeps_one_side_of_every_bar():
    assert alternatives(r"^a\.(b|c\.(d|e))$") == [
        r"^a\.(?:b)$", r"^a\.(?:c\.(?:d))$", r"^a\.(?:c\.(?:e))$"]
    assert alternatives(r"^x\|y$") == [r"^x\|y$"]
    assert alternatives("p|q") == ["p", "q"]


@pytest.fixture(scope="module")
def fed():
    """`profiler.feed_stats()` around a toy step fed by a `DeviceFeed`."""
    step, data = toy_step()
    before = profiler.feed_stats()
    for x, y in DeviceFeed(data):
        loss = step(x, y)
    loss.wait_to_read()
    return before, profiler.feed_stats()


def test_every_host_side_metric_is_under_the_contract():
    names = {os.path.basename(p)[:-len(".json")] for p in HOST_METRICS}
    assert names == {"wave_host_ms.serve", "wave_pack_ms.serve",
                     "wave_turnover_ms.serve", "step_host_ms.train",
                     "sched_occupancy.serve", "cache_live_share.serve",
                     "feed_stall.train"}


@pytest.mark.parametrize("path", HOST_METRICS,
                         ids=[os.path.basename(p) for p in HOST_METRICS])
def test_program_records_what_the_host_side_metric_reads(
        path, served, trained, fed):
    """A span or counter renamed in the program would reach the chip as a
    per-layer metric that reads `None`: here the benchmark's own readers
    and patterns, taken as data, read a tiny run of the program."""
    with open(path) as f:
        metric = json.load(f)
    reader, params = metric["reader"], metric.get("params", {})
    s0, s1 = served["s0"], served["s1"]
    if reader == "span_ms":
        run = served if path.endswith(".serve.json") else trained
        spans = [(e["name"], e["tid"], e["ts"], e["dur"])
                 for e in run["buffer"] if e.get("ph") == "X"]
        recorded = {name for name, *_ in spans}
        for key in ("sum", "per"):
            for alt in alternatives(params[key]):
                assert any(re.search(alt, n) for n in recorded), \
                    (key, alt, sorted(recorded))
        got = span_ms.reduce(spans, dict(params, skip_head_s=0.0), 3600.0)
        assert got is not None and got > 0
    elif reader == "engine_stats":
        assert set(COUNTED) <= set(s0)
        counters = {k: s1[k] - s0[k] for k in COUNTED}
        got = engine_stats.read(params, {
            "counters": dict(counters, max_slots=s1["pool"]["max_slots"])})
        assert 0 < got <= 100
    elif reader == "cache_live_share":
        assert all({"bytes", "live_bytes_sum"} <= set(kind)
                   for kind in s1["cache"].values())
        counters = dict(cache_counters(s0, s1), decode_iterations=(
            s1["decode_iterations"] - s0["decode_iterations"]))
        got = cache_live_share.read(params, {"counters": counters})
        assert 0 < got <= 100
    else:
        before, after = fed
        assert after["batches_consumed"] - before["batches_consumed"] == 3
        stall_s = (after["stall_data_us"] - before["stall_data_us"]) * 1e-6
        got = feed_stats.read(params, {
            "counters": {"feed_stall_data_s": stall_s}, "window_s": 1.0})
        assert got is not None and got >= 0


def pallas_calls(node):
    return [n for n in ast.walk(node) if isinstance(n, ast.Call)
            and isinstance(n.func, ast.Attribute)
            and n.func.attr == "pallas_call"]


@pytest.mark.parametrize("path", KERNEL_FILES)
def test_every_pallas_call_is_named_after_its_wrapper(path):
    with open(os.path.join(ROOT, path)) as f:
        tree = ast.parse(f.read())
    seen = []
    for fn in [n for n in ast.walk(tree) if isinstance(n, ast.FunctionDef)]:
        for call in pallas_calls(fn):
            name = {k.arg: k.value for k in call.keywords}.get("name")
            assert isinstance(name, ast.Constant), (path, call.lineno)
            assert name.value.startswith(fn.name.lstrip("_")), \
                (fn.name, name.value)
            seen.append(name.value)
    assert len(seen) == len(pallas_calls(tree)) == 4
    assert len(set(seen)) == len(seen)


def test_kernel_name_reaches_the_lowered_program():
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import pallas_kernels

    def f(x, shift):
        return pallas_kernels.apply_scale_shift_act(
            x, None, shift, None, "relu", interpret=False)

    text = jax.jit(f).trace(
        jax.ShapeDtypeStruct((256, 128), jnp.float32),
        jax.ShapeDtypeStruct((128,), jnp.float32)).jaxpr.pretty_print()
    assert "apply_scale_shift_act" in text


def test_program_scopes_are_in_the_lowered_programs():
    eng = toy_engine()
    low = eng.lowered_programs()
    decode = low["decode"].as_text(debug_info=True)
    for scope in ("embed", "layer0/attn", "layer0/mlp", "layer1/attn",
                  "layer1/mlp", "head", "sampler"):
        assert f'"{scope}' in decode or f"/{scope}" in decode, scope
    prefill = low["prefill"].as_text(debug_info=True)
    assert "layer1/mlp" in prefill and "sampler" not in prefill
    step, data = toy_step()
    text = step.lowered(*data[0]).as_text(debug_info=True)
    for scope in ("forward", "transpose(jvp(forward))", "update"):
        assert scope in text, scope
