"""Step-timeline attribution: spans, per-step breakdown, live-counter MFU.

Before this module the Chrome-trace lanes (`serve.batch`, `io.feed`,
`feed.stage`) each hand-rolled their `profiler.record_event` call and no
single object could answer "where did this step's time go?". Now:

  * `span(name, **attrs)` — nesting-aware tracer. Nesting rides the trace
    ContextVar (`telemetry.trace`), so it follows `trace.attach(ctx)`
    across thread hops and every span carries trace/span/parent ids.
    Every span lands in the profiler's Chrome-trace buffer (cat "span",
    with its parent's name in args so the tree reconstructs), in the
    registry histogram `span.duration_us{name=...}`, and in the flight
    recorder, so `profiler.dump()` shows the lane and
    `telemetry.snapshot()` shows the aggregate without re-parsing traces.

  * `StepTimeline` — the per-step breakdown a train loop or server wants:
    wall time split into data-stall vs compute vs H2D-staging vs allreduce,
    pulled from live counters (DeviceFeed stall/staging counters, kvstore
    bucket timings) around each step, not hand-math after the fact. With
    `flops_per_step` it also reports MFU against a peak.

  * `model_flops` / `block_fwd_flops` — the MFU numerator computed ONCE
    from XLA's own cost analysis (`jax.jit(...).lower().cost_analysis()`),
    the same MAC=2 convention as the chip spec, so every reporter
    (estimator.fit, FusedTrainStep) shares one number instead of copies of
    hand-math.

Attribution semantics (documented, not magic): `data_stall_us` and the
collective clocks (`allreduce_us`, and the ZeRO lanes `reduce_scatter_us`
/ `allgather_us`) are time the CONSUMER thread provably spent inside the
step window waiting (empty feed buffer; collective dispatch).
`h2d_stage_us` is feeder-thread staging time — it overlaps compute by
design, so it is reported alongside, never subtracted. `compute_us` is the
remainder: `total - data_stall - allreduce - reduce_scatter - allgather`.
"""
from __future__ import annotations

import weakref
from collections import OrderedDict

from ..base import MXNetError, get_env
from .registry import REGISTRY
from . import trace as _trace

__all__ = ["span", "NO_SPAN", "current_span", "record_span", "StepTimeline",
           "model_flops", "block_fwd_flops", "cost_flops",
           "device_peak_flops", "SPAN_DURATION", "SPAN_COUNT"]

# one histogram family for every span name: static registration (lint +
# docs cover it), dynamic span names become label values, not new metrics
SPAN_DURATION = REGISTRY.histogram(
    "span.duration_us", help="telemetry.span durations by span name",
    labels=("name",))
SPAN_COUNT = REGISTRY.counter(
    "span.count", help="telemetry.span completions by span name",
    labels=("name",))

# process-wide memory high-water over every StepTimeline/MemoryMonitor
# sample (mx.inspect.memory catalog); the python cell avoids a locked
# gauge read per step
MEM_PEAK = REGISTRY.gauge(
    "mem.peak_hbm_bytes", help="high-water bytes_in_use across every "
    "step-timeline / memory-monitor sample this process took "
    "(source per profiler.read_memory_sample: device HBM, or host RSS "
    "on backends without memory_stats)")
_mem_peak_seen = [0]


def _note_memory_sample(b):
    if b > _mem_peak_seen[0]:
        _mem_peak_seen[0] = b
        MEM_PEAK.set(b)

_enabled = _trace.enabled


# span name -> (bound duration histogram, bound counter): the label-value
# resolution is a dict+tuple build per call — memoized off the hot path
# (span names are a small closed set; labeled children are never removed)
_bound_memo = {}

# flight-recorder duration floor for CLOSED spans (see record_span): only
# spans at least this long are black-box-worthy; span_open events are
# never floored
FLIGHTREC_SPAN_FLOOR_US = 50_000.0


def current_span():
    """Name of the innermost open span on this execution context (follows
    an attached TraceContext across thread hops), or None."""
    ctx = _trace.current_context()
    return ctx.name if ctx is not None else None


def record_span(name, dur_us, ts_us=None, cat="span", ctx=None,
                async_id=None, **attrs):
    """Record an externally-timed span: the one implementation behind
    every Chrome-trace lane (`serve.batch`, `io.feed`, `feed.stage`, and
    `with span(...)` itself). Feeds the `span.duration_us{name=...}`
    histogram always (when telemetry is on), the flight-recorder ring,
    and the profiler's Chrome-trace buffer when the profiler is running.

    Trace linkage: pass `ctx` (a TraceContext) to record AS that node of
    a request tree; with no `ctx`, the ambient `trace.current_context()`
    — if any — becomes the parent and a fresh child id is minted. Either
    way the trace/span/parent ids land in the event args, so the
    cross-thread tree reassembles from the exported trace JSON.
    `async_id`: see `profiler.record_event` (an interval that overlaps
    others without nesting: an async pair in the Chrome trace)."""
    if not _enabled():
        return
    bounds = _bound_memo.get(name)
    if bounds is None:
        bounds = _bound_memo[name] = (SPAN_DURATION.labels(name=name),
                                      SPAN_COUNT.labels(name=name))
    bounds[0].observe(dur_us)
    bounds[1].inc()
    if ctx is None:
        ambient = _trace.current_context()
        if ambient is not None:
            ctx = _trace.child_context(ambient, name)
    if ctx is not None:
        attrs.setdefault("trace_id", ctx.trace_id)
        attrs.setdefault("span_id", ctx.span_id)
        if ctx.parent_span_id is not None:
            attrs.setdefault("parent_span_id", ctx.parent_span_id)
        if ctx.parent_name is not None:
            attrs.setdefault("parent", ctx.parent_name)
        _trace.TRACE_STATS["spans"] += 1  # mxlint: disable=lock-shared-mutation -- documented lock-free diagnostics (DISPATCH_STATS pattern)
    if dur_us >= FLIGHTREC_SPAN_FLOOR_US:
        # duration floor: step/request-scale spans are black-box-worthy;
        # sub-50ms spans at thousands/sec would evict the interesting
        # history from the bounded ring in well under a second (and cost
        # a spool write each when MXNET_FLIGHTREC_DIR is set). Span OPEN
        # events (the in-flight marker) are not floored — only `span`
        # class entries emit them, at step scale.
        _trace.flightrec_record("span", name, dur_us=round(dur_us, 1),
                                **attrs)
    # cached module ref, not `from .. import profiler`: record_span runs
    # per batch on the serving path and the import machinery costs ~1us
    # + import-lock traffic per call
    if _trace._profiler_running():
        _trace._profiler_mod[0].record_event(name, cat, dur_us,
                                             ts_us=ts_us, args=attrs,
                                             async_id=async_id)


# (TraceAnnotation, StepTraceAnnotation), resolved under the first open
# `jax.profiler` session: this module never imports jax on its own
_annotations = []


class span:
    """`with telemetry.span("train.step", step=n):` — time a region.

    Nesting is tracked through the trace ContextVar: entering mints a
    child `TraceContext` of whatever is current (starting a new trace at
    the root, subject to MXNET_TRACE_SAMPLE), so the Chrome-trace event
    carries the enclosing span's name in `args["parent"]` PLUS the
    trace/span/parent ids, and — after `trace.attach(ctx)` on a worker
    thread — nesting survives thread hops. The registry histogram
    `span.duration_us{name=...}` aggregates durations, and a ROOT span's
    open/close pair feeds the flight recorder (an in-flight span at
    process death is named by its `span_open` spool line; nested spans
    write none, so wave-scale children cannot evict the ring).

    While a `jax.profiler` session is open the span also enters a
    `jax.profiler.TraceAnnotation(name, **attrs)` (a `StepTraceAnnotation`
    when given a `step_num`), so it lands on `/host:CPU` of that trace,
    on the clock the device planes are on. `set(**attrs)` adds attributes
    known only inside the region. `cat` names the Chrome-trace lane.

    A span is cheap when `MXNET_TELEMETRY=0` (no clock reads, no
    records) and touches jax only under an open session. Reentrant and
    exception-safe (the span closes on the error path too — ContextVar
    tokens reset correctly even when an inner span leaked open, so
    traces stay balanced)."""

    __slots__ = ("name", "cat", "attrs", "_t0", "_parent", "_armed",
                 "_dur", "_ctx", "_token", "_ann")

    def __init__(self, name, cat="span", **attrs):
        self.name = name
        self.cat = cat
        self.attrs = attrs
        self._t0 = None
        self._parent = None
        self._armed = False
        self._dur = None
        self._ctx = None
        self._token = None
        self._ann = None

    def __enter__(self):
        self._armed = _enabled()
        if not self._armed:
            return self
        from .. import profiler
        raw = _trace._raw_context()
        if raw is _trace.NOT_SAMPLED:
            # inside a sampled-out trace: inherit the decision — minting
            # a fresh root per inner span would fill the Chrome trace
            # with orphan mid-request fragments and count one "trace"
            # per span (head sampling samples TREES, not spans)
            self._parent = None
            self._ctx = None
        else:
            self._parent = raw.name if raw is not None else None
            self._ctx = _trace.child_context(raw, self.name)
            if self._ctx is not None:
                self._token = _trace._push(self._ctx)
                if raw is None:
                    _trace.flightrec_record("span_open", self.name,
                                            **self.attrs)
            elif raw is None:
                # root draw came up sampled-out: mark the subtree
                self._token = _trace._push(_trace.NOT_SAMPLED)
        self._t0 = profiler._now_us()
        if profiler.jax_session_open():
            if not _annotations:
                from jax.profiler import (StepTraceAnnotation,
                                          TraceAnnotation)
                _annotations.extend((TraceAnnotation, StepTraceAnnotation))
            kind = _annotations["step_num" in self.attrs]
            kw = self.attrs if self._parent is None \
                else dict(self.attrs, parent=self._parent)
            self._ann = kind(self.name, **kw)
            self._ann.__enter__()
        return self

    def set(self, **attrs):
        """Attributes known only inside the region (counts an admission
        produced, tokens a wave emitted); a no-op on an un-armed span."""
        if self._armed:
            self.attrs.update(attrs)
            if self._ann is not None:
                self._ann.set_metadata(**attrs)

    def __exit__(self, *exc):
        if not self._armed:
            return False
        from .. import profiler
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        t1 = profiler._now_us()
        if self._token is not None:
            _trace._reset(self._token)
            self._token = None
        attrs = dict(self.attrs)
        if self._parent is not None:
            attrs["parent"] = self._parent
        self._dur = t1 - self._t0
        record_span(self.name, self._dur, ts_us=self._t0, cat=self.cat,
                    ctx=self._ctx, **attrs)
        return False

    @property
    def duration_us(self):
        """Set only after exit (None while open or telemetry disabled)."""
        return self._dur

    @property
    def context(self):
        """The span's TraceContext (None before entry, when telemetry is
        off, or when the root was sampled out)."""
        return self._ctx


class _NoSpan:
    """What a hot path enters in a span's place while nothing collects:
    one shared object, no clock read, nothing built."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass


NO_SPAN = _NoSpan()


def _stall_counters():
    """One consistent read of the cross-subsystem counters a step window
    diffs: (feed stall/staging, kvstore allreduce). Missing subsystems
    read as zeros so a loop with no feed or no kvstore still reports."""
    out = {"data_stall_us": 0.0, "h2d_stage_us": 0.0, "host_transfers": 0,
           "allreduce_us": 0.0, "allreduce_buckets": 0,
           "reduce_scatter_us": 0.0, "reduce_scatter_buckets": 0,
           "allgather_us": 0.0, "allgather_buckets": 0}
    try:
        from ..io.device_feed import feed_stats
        f = feed_stats()
        out["data_stall_us"] = f.get("stall_data_us", 0.0)
        out["h2d_stage_us"] = f.get("stage_us", 0.0)
        out["host_transfers"] = f.get("host_transfers", 0)
    except Exception:
        pass
    try:
        from ..kvstore import KV_STATS
        out["allreduce_us"] = KV_STATS.get("allreduce_us", 0.0)
        out["allreduce_buckets"] = KV_STATS.get("allreduce_buckets", 0)
        # ZeRO collective lanes (mx.fault.elastic): dispatch-side clocks
        # of the bucketed reduce-scatter / all-gather, same semantics as
        # the allreduce clock
        out["reduce_scatter_us"] = KV_STATS.get("reduce_scatter_us", 0.0)
        out["reduce_scatter_buckets"] = KV_STATS.get(
            "reduce_scatter_buckets", 0)
        out["allgather_us"] = KV_STATS.get("allgather_us", 0.0)
        out["allgather_buckets"] = KV_STATS.get("allgather_buckets", 0)
    except Exception:
        pass
    return out


class StepTimeline:
    """Per-step time attribution over a training (or serving) loop.

    ::

        tl = telemetry.StepTimeline(flops_per_step=fl, peak_flops=peak)
        for batch in feed:
            with tl.step():
                loss = train_step(*batch)
        report = tl.report()
        # {'steps', 'total_us', 'data_stall_us', 'compute_us',
        #  'h2d_stage_us', 'allreduce_us', 'stall_pct', 'compute_pct',
        #  'mfu', 'achieved_flops_per_sec', ...}

    Attribution runs over the LOOP WINDOW — first `step()` entry to the
    latest `step()` exit — with the cross-subsystem counters (DeviceFeed
    stall clock, kvstore allreduce clock) diffed continuously across it.
    That window deliberately includes the time BETWEEN steps, because that
    is where a `for batch in feed:` loop blocks on data — a per-step-only
    window would attribute an input-bound loop as pure compute. The report
    divides:

      data_stall_us   consumer blocked on an empty feed buffer (input-bound)
      allreduce_us    gradient-collective dispatch time inside the window
      reduce_scatter_us / allgather_us
                      ZeRO collective dispatch time (mx.fault.elastic
                      bucketed reduce-scatter / param all-gather)
      compute_us      total - data_stall - allreduce - reduce_scatter -
                      allgather (the XLA side)
      h2d_stage_us    feeder staging incl. async H2D dispatch — overlapped
                      work, reported for visibility, never subtracted
      step_time_us    sum of the in-step spans (loop-body time only)

    MFU = flops_per_step * steps / total_seconds / peak_flops, available
    to any fit loop.
    """

    def __init__(self, flops_per_step=None, peak_flops=None,
                 name="train.step"):
        self.name = name
        self.flops_per_step = flops_per_step
        self.peak_flops = (peak_flops if peak_flops is not None
                           else device_peak_flops())
        self.steps = 0
        self.step_time_us = 0.0
        # memory lane: per-step-exit bytes_in_use samples, high-water
        # over the loop window (profiler.read_memory_sample provenance:
        # "device" on accelerators, "host_rss" on CPU backends)
        self.peak_hbm_bytes = 0
        self.mem_source = None
        self.deltas = {"data_stall_us": 0.0, "h2d_stage_us": 0.0,
                       "allreduce_us": 0.0, "host_transfers": 0,
                       "allreduce_buckets": 0,
                       "reduce_scatter_us": 0.0,
                       "reduce_scatter_buckets": 0,
                       "allgather_us": 0.0, "allgather_buckets": 0}
        self._base = None        # counters at first step entry
        self._t_first = None
        self._t_last = None

    class _Step:
        __slots__ = ("tl", "span")

        def __init__(self, tl):
            self.tl = tl
            self.span = span(tl.name, step=tl.steps)

        def __enter__(self):
            from .. import profiler
            tl = self.tl
            if tl._base is None:
                tl._base = _stall_counters()
                tl._t_first = profiler._now_us()
            self.span.__enter__()
            return self

        def __exit__(self, *exc):
            from .. import profiler
            t0 = self.span._t0
            self.span.__exit__(*exc)
            tl = self.tl
            tl.steps += 1
            if t0 is None:       # telemetry disabled: count steps only
                return False
            now = profiler._now_us()
            tl._t_last = now
            tl.step_time_us += now - t0
            after = _stall_counters()
            for k in tl.deltas:
                tl.deltas[k] = after[k] - tl._base[k]
            # memory lane: one cheap sample per step exit (PJRT
            # memory_stats / one /proc read) — the loop high-water folds
            # into the report AND the process-wide mem.peak_hbm_bytes
            try:
                b, source = profiler.read_memory_sample()
                if b > tl.peak_hbm_bytes:
                    tl.peak_hbm_bytes = b
                tl.mem_source = source
                _note_memory_sample(b)
            except Exception:
                pass
            return False

    def step(self):
        """Context manager for one step of the loop."""
        return self._Step(self)

    @property
    def total_us(self):
        """The loop window: first step entry to latest step exit."""
        if self._t_first is None or self._t_last is None:
            return 0.0
        return float(self._t_last - self._t_first)

    def report(self):
        """Plain-data breakdown; safe to json.dumps."""
        total = self.total_us
        stall = self.deltas["data_stall_us"]
        allred = self.deltas["allreduce_us"]
        rs = self.deltas["reduce_scatter_us"]
        ag = self.deltas["allgather_us"]
        compute = max(0.0, total - stall - allred - rs - ag)
        out = {
            "name": self.name,
            "steps": self.steps,
            "total_us": round(total, 1),
            "step_time_us": round(self.step_time_us, 1),
            "step_mean_us": round(self.step_time_us / self.steps, 1)
            if self.steps else 0.0,
            "data_stall_us": round(stall, 1),
            "allreduce_us": round(allred, 1),
            "reduce_scatter_us": round(rs, 1),
            "allgather_us": round(ag, 1),
            "compute_us": round(compute, 1),
            "h2d_stage_us": round(self.deltas["h2d_stage_us"], 1),
            "host_transfers": self.deltas["host_transfers"],
            "allreduce_buckets": self.deltas["allreduce_buckets"],
            "reduce_scatter_buckets":
                self.deltas["reduce_scatter_buckets"],
            "allgather_buckets": self.deltas["allgather_buckets"],
            "stall_pct": round(100.0 * stall / total, 2) if total else 0.0,
            "compute_pct": round(100.0 * compute / total, 2) if total
            else 0.0,
            "peak_hbm_bytes": int(self.peak_hbm_bytes),
            "mem_source": self.mem_source,
        }
        if self.flops_per_step and total > 0:
            achieved = self.flops_per_step * self.steps / (total * 1e-6)
            out["achieved_flops_per_sec"] = achieved
            if self.peak_flops:
                # 6 decimals: tiny test-scale MFUs must not round to 0.0
                out["mfu"] = round(achieved / self.peak_flops, 6)
        return out


# ---------------------------------------------------------------------------
# MFU numerator: XLA-counted model FLOPs, computed once per (fn, shapes)
# ---------------------------------------------------------------------------
# key -> (fn, flops): the cached callable is held STRONGLY so its id can
# never be recycled by a different function while the entry lives; BOUNDED
# (FIFO) so per-call closures (block_fwd_flops' `fwd`) cannot pin models
# without limit
_flops_cache = OrderedDict()
_FLOPS_CACHE_CAP = 128


def _sig(a):
    """Structural signature of one argument: shapes/dtypes recurse through
    lists/tuples/dicts (a param-buffer list must contribute its shapes to
    the memo key, not collapse to 'list')."""
    if hasattr(a, "shape") and hasattr(a, "dtype"):
        return (tuple(a.shape), str(a.dtype))
    if isinstance(a, (list, tuple)):
        return (type(a).__name__, tuple(_sig(v) for v in a))
    if isinstance(a, dict):
        return ("dict", tuple((k, _sig(v)) for k, v in sorted(a.items())))
    return ("scalar", type(a).__name__, a if isinstance(
        a, (int, float, bool, str, type(None))) else None)


def cost_flops(lowered, what="program"):
    """FLOPs from a lowered jit program's XLA cost analysis (MAC = 2 —
    the same convention as accelerator peak specs), the one numerator
    every MFU uses (`model_flops`, `FusedTrainStep.flops_per_call`);
    falls back to the pre-compile analysis when
    `.compile().cost_analysis()` raises."""
    import numpy as _np
    try:
        ca = lowered.compile().cost_analysis()
    except Exception:
        ca = lowered.cost_analysis()
    if not ca or "flops" not in ca:
        raise MXNetError(
            f"XLA cost analysis returned no flops for {what}")
    return float(_np.asarray(ca["flops"]))


def model_flops(fn, *args):
    """FLOPs of ONE execution of `fn(*args)` per XLA's cost analysis of the
    compiled program (`jax.jit(fn).lower(...).compile().cost_analysis()`),
    MAC = 2 flops — the same convention as accelerator peak specs. Memoized
    on (fn identity, structural arg signature); the entry holds `fn`
    strongly so a recycled id can never alias another function. The
    lowering+compile lands in jax's jit cache, so a subsequent real
    `jax.jit(fn)` call with the same avals does not recompile."""
    import jax

    key = (id(fn), tuple(_sig(a) for a in args))
    hit = _flops_cache.get(key)
    if hit is not None and hit[0] is fn:
        return hit[1]
    flops = cost_flops(jax.jit(fn).lower(*args), what=repr(fn))
    _flops_cache[key] = (fn, flops)
    while len(_flops_cache) > _FLOPS_CACHE_CAP:
        _flops_cache.popitem(last=False)
    return flops


# net (weak) -> {(param shapes, input sig): flops} — repeat calls on the
# SAME net skip the relowering entirely, and a dead net drops its entries
_block_flops_memo = weakref.WeakKeyDictionary()


def block_fwd_flops(net, x):
    """FLOPs of one compiled FORWARD of an initialized HybridBlock on batch
    `x` (total for the batch, MAC=2). Train-step flops are conventionally
    ~3x this (fwd + 2x bwd). Uses the same parameter buffer-swap trick the
    fused paths use so the traced function is pure in its buffers.
    Memoized per net (weakly — the model is never pinned): the cost
    analysis runs once per (net, param shapes, batch signature)."""
    import jax
    from .. import autograd, random as _random
    from ..ndarray import NDArray, _wrap

    params = [p for _, p in sorted(net.collect_params().items())]
    for p in params:
        if p._data is None:
            raise MXNetError("block_fwd_flops needs an initialized net: "
                             "run one forward first")
    raw0 = x._arr if isinstance(x, NDArray) else x
    memo_key = (tuple(_sig(p.data()._arr) for p in params), _sig(raw0))
    try:
        memo = _block_flops_memo.setdefault(net, {})
    except TypeError:          # unweakrefable net: skip the memo
        memo = {}
    if memo_key in memo:
        return memo[memo_key]

    def fwd(pbufs, xr):
        saved = []
        for p, b in zip(params, pbufs):
            nd = p.data()
            saved.append(nd._data)
            nd._data = b
            nd._version += 1
        try:
            key = jax.random.PRNGKey(0)
            with autograd._Scope(recording=False, training=False), \
                    _random.trace_key_scope(key):
                out = net(_wrap(xr))
        finally:
            for p, old in zip(params, saved):
                # trace-time buffer swap, restored before tracing ends
                p.data()._data = old  # mxlint: disable=trace-closure-mutation
        return out._arr

    pbufs = [p.data()._arr for p in params]
    # cost_flops directly, NOT model_flops: the per-call `fwd` closure can
    # never hit model_flops' id-keyed cache again, and caching it there
    # would strongly pin `net` and its buffers until FIFO eviction — the
    # weak per-net memo above is the only cache this path needs
    flops = cost_flops(jax.jit(fwd).lower(pbufs, raw0),
                       what=f"forward of {type(net).__name__}")
    memo[memo_key] = flops
    return flops


# (platform, device-kind substring) -> advertised bf16 peak FLOP/s (MAC=2).
# The published figures; what the chip attains is in PERF.md §5.
_PEAKS = (
    ("tpu", "v5 lite", 197e12),
    ("tpu", "v5e", 197e12),
    ("tpu", "v4", 275e12),
    ("tpu", "v3", 123e12),
    ("tpu", "v2", 45e12),
)


def device_peak_flops(device=None):
    """Spec bf16 peak FLOP/s for the attached accelerator, or None when
    unknown (CPU, exotic chips): MFU is then omitted rather than wrong."""
    try:
        import jax
        d = device or jax.devices()[0]
        plat = d.platform.lower()
        kind = getattr(d, "device_kind", "").lower()
        for p, sub, peak in _PEAKS:
            if plat == p and sub in kind:
                return peak
    except Exception:
        pass
    return None
