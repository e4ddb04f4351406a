"""mx.profiler — Chrome-trace profiling (≙ python/mxnet/profiler.py:34-363 +
src/profiler/profiler.h:264).

TPU-native: two layers.
  1. Framework events: set_config/start/stop record Python-side op invokes +
     user Task/Frame/Counter objects into an in-process buffer, dumped as
     Chrome tracing JSON (`dump`) or an aggregate table (`dumps`) — the
     reference's lock-free per-thread ProfileObject buffers ≙ a list guarded
     by the GIL here, since op dispatch is not the hot path (XLA is).
  2. Device traces: profile via jax.profiler (XLA's own instrumentation)
     writing TensorBoard/perfetto data when `profile_device=True` — replacing
     the reference's per-worker device lanes.
"""
from __future__ import annotations

import json
import re
import sys
import threading
import time
from collections import OrderedDict, defaultdict, deque

from .base import MXNetError, get_env

__all__ = ["set_config", "start", "stop", "pause", "resume", "dump", "dumps",
           "state", "Task", "Frame", "Event", "Counter", "Domain", "Marker",
           "profiler_scope", "scope", "dispatch_stats", "serve_stats",
           "feed_stats", "events", "collecting", "register_program",
           "program_scopes"]

_lock = threading.Lock()
# chrome trace events, newest EVENTS_CAP kept: a collector left running
# (or a long jax.profiler session) must not grow the process without bound
EVENTS_CAP = 1 << 17
_events = deque(maxlen=EVENTS_CAP)
_state = {"running": False, "config": {}, "jax_trace_dir": None,
          "t0": None}


def _now_us():
    """Event timestamp in microseconds on ONE process-wide monotonic clock.

    `perf_counter_ns` is CLOCK_MONOTONIC(_RAW): a single epoch shared by
    every thread in the process (unlike per-thread CPU clocks), so events
    recorded from threaded feeders/batchers interleave in true
    happens-before order in the Chrome trace, and never go backwards on
    NTP steps the way wall-clock timestamps would. Integer nanoseconds
    avoid the float precision loss `perf_counter()*1e6` accumulates after
    long uptimes (floats lose sub-µs resolution past ~2**33 µs)."""
    return time.perf_counter_ns() // 1000


def set_config(**kwargs):
    """≙ profiler.set_config(profile_all=, profile_symbolic=, filename=...)."""
    _state["config"].update(kwargs)


def start(profile_process="worker"):
    """≙ profiler.set_state('run')."""
    _state["running"] = True
    if _state["t0"] is None:
        _state["t0"] = _now_us()
    if _state["config"].get("profile_device") or \
            _state["config"].get("profile_all"):
        import jax
        import tempfile
        d = _state["config"].get("device_trace_dir") or tempfile.mkdtemp(
            prefix="mx_device_trace_")
        try:
            jax.profiler.start_trace(d)
            _state["jax_trace_dir"] = d
        except Exception:
            _state["jax_trace_dir"] = None


def stop(profile_process="worker"):
    """≙ profiler.set_state('stop')."""
    _state["running"] = False
    if _state["jax_trace_dir"]:
        import jax
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        _state["jax_trace_dir"] = None


def pause(profile_process="worker"):
    _state["running"] = False


def resume(profile_process="worker"):
    _state["running"] = True


def state():
    return "run" if _state["running"] else "stop"


# jax.profiler.TraceAnnotation, resolved once jax is loaded: this module
# stays importable without jax, and a session needs jax to be open
_annotation = [None]


def jax_session_open():
    """True while a `jax.profiler` trace session is collecting (whoever
    opened it: `start_trace`, `jax.profiler.trace`, the profiler server).
    Costs one C++ flag read; never imports jax itself."""
    ann = _annotation[0]
    if ann is None:
        if "jax" not in sys.modules:
            return False
        from jax.profiler import TraceAnnotation as ann
        _annotation[0] = ann
    return ann.is_enabled()


def collecting():
    """True while the event buffer accepts events: between `start()` and
    `stop()`, or while a `jax.profiler` session is open."""
    return _state["running"] or jax_session_open()


def events(cat=None):
    """A copy of the in-memory event buffer, oldest first (the newest
    `EVENTS_CAP` events; `cat` keeps one category, e.g. `"span"` or
    `"serve"`). The events are Chrome-trace dicts: `name`, `cat`, `ts` and
    `dur` in microseconds on `time.perf_counter`'s clock, `tid`, `args`
    (a span's attributes, its `parent` name and its trace ids)."""
    with _lock:
        return [dict(e) for e in _events
                if cat is None or e["cat"] == cat]


# -- the compiled programs of this process, and their scopes ---------------
# Whoever runs a jitted program over and over (a serving engine at warm-up,
# a fused train step at its first call) registers it here: the jitted
# function and the abstract values it is called with, no buffer. The newest
# PROGRAMS_CAP are kept; a table is made when somebody asks for it.
PROGRAMS_CAP = 64
_programs = OrderedDict()     # (module, owner, id(fn), avals) -> _Program


class _Program:
    """One registered program: `lower()` gives its `jax.stages.Lowered`
    at the registered shapes; `table` is its memoised scope table."""

    __slots__ = ("module", "fn", "args", "owner", "table")

    def __init__(self, module, fn, args, owner):
        self.module, self.fn, self.args = module, fn, args
        self.owner, self.table = owner, None

    def lower(self):
        return self.fn.lower(*self.args)


def _abstract(args):
    """`args` with every array replaced by its shape and dtype."""
    import jax

    def one(a):
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            spread = getattr(a, "sharding", None)
            if spread is not None and len(spread.device_set) < 2:
                spread = None           # one device: wherever the call puts it
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=spread,
                weak_type=getattr(a, "weak_type", False))
        return a
    return jax.tree_util.tree_map(one, tuple(args))


def register_program(fn, args, owner=None):
    """Note that this process runs the jitted `fn` with arguments shaped
    like `args` (arrays, or `jax.ShapeDtypeStruct`s: only shapes and
    dtypes are kept). The program's name is the one a device trace
    prints, `jit_<fn.__name__>`; `owner` (any hashable) says which
    programs were registered together, an engine's for one (see
    `program_scopes`). Nothing is lowered or compiled; `fn` itself is
    kept, and with it whatever it closes over (a serving program closes
    over its config, a fused step over its net), until `PROGRAMS_CAP`
    newer programs have pushed it out. Returns the registered program
    (`.module`, `.lower()`)."""
    import jax
    module = f"jit_{fn.__name__}"
    owner = id(fn) if owner is None else owner
    args = _abstract(args)
    leaves, tree = jax.tree_util.tree_flatten(args)
    key = (module, owner, id(fn), tree, tuple(
        (a.shape, str(a.dtype)) if hasattr(a, "shape") else a
        for a in leaves))
    with _lock:
        prog = _programs.pop(key, None) or _Program(module, fn, args, owner)
        _programs[key] = prog
        while len(_programs) > PROGRAMS_CAP:
            _programs.popitem(last=False)
    return prog


def _registered(pattern=None):
    """The registered programs whose module name, written as a device
    trace writes it (`jit_decode(`), the regular expression finds."""
    rx = re.compile(pattern) if pattern else None
    with _lock:
        return [p for p in _programs.values()
                if rx is None or rx.search(p.module + "(")]


def _fresh_compile(lowered):
    """The lowering compiled anew, never the executable that runs: jax's
    persistent cache keys a program WITHOUT its `op_name` metadata, so the
    running executable may be one that another commit compiled, with that
    commit's scopes. Past the executable jax keeps in memory: a compiler
    option that changes no code. Past the persistent cache, for this
    thread and this compile alone: a key that holds the metadata, which
    no entry has, and a least compile time out of reach, so that none is
    written. Every process that asks pays the compile."""
    from jax._src import config as jax_config
    with jax_config.compilation_cache_include_metadata_in_key(True), \
            jax_config.persistent_cache_min_compile_time_secs(float("inf")):
        return lowered.compile(
            compiler_options={"xla_dump_hlo_as_proto": False})


def program_scopes(pattern=None):
    """`{module name: {instruction name: (scope path, pass)}}` of the
    registered programs whose module name matches `pattern` (all of them
    without one): what `inspect.scope_table` reads from each program,
    compiled here on demand from its registered shapes, once. A device
    trace names an operation by its instruction (`%fusion.17 = ...`), the
    table says under which `jax.named_scope` of the program it runs; the
    two are joined by `chipbench/readers/xplane_scope_ms.py`.

    Works after the engine or step that registered is closed and gone.
    Where several owners registered programs of one module name (a
    second engine in the process), the one that registered last answers
    for it. One owner's programs of one name (a ladder of chunk rungs)
    share a table, without the instruction names on whose scope they
    disagree. The first call for a program costs its compile (seconds to
    a minute at real sizes); call it outside a measured window."""
    from .inspect.hlo import scope_table
    progs = _registered(pattern)
    newest = {prog.module: prog.owner for prog in progs}
    out, clash = {}, {}
    for prog in progs:
        if prog.owner != newest[prog.module]:
            continue
        if prog.table is None:
            prog.table = scope_table(_fresh_compile(prog.lower()))
        table = out.setdefault(prog.module, {})
        bad = clash.setdefault(prog.module, set())
        for name, where in prog.table.items():
            if table.setdefault(name, where) != where:
                bad.add(name)
    for module, bad in clash.items():
        for name in bad:
            del out[module][name]
    return out


def record_event(name, category, dur_us, ts_us=None, args=None,
                 async_id=None):
    """Internal hook: ops.registry calls this when profiling is on.
    With `async_id` the interval is one that overlaps others of its
    thread without nesting in them (a request's wait for a slot, noted
    when the request retires): it is recorded as Chrome's async pair,
    `b` at its start and `e` at its end under that id, and the `e` event
    carries `dur` too. A complete event (`X`) never starts before the
    collector was armed; an async pair may."""
    if not collecting():
        return
    ts = ts_us if ts_us is not None else _now_us()
    event = {"name": name, "cat": category, "ph": "X", "ts": ts,
             "dur": dur_us, "pid": 0,
             "tid": threading.get_ident() % 100000, "args": args or {}}
    with _lock:
        if async_id is None:
            _events.append(event)
        else:
            _events.append(dict(event, ph="b", dur=0, id=async_id))
            _events.append(dict(event, ph="e", ts=ts + dur_us,
                                id=async_id))


def dump(finished=True, profile_process="worker", filename=None):
    """Write Chrome tracing JSON (≙ profiler.dump). The telemetry registry
    snapshot rides along under `otherData.telemetry` (trace viewers ignore
    unknown top-level keys), so one artifact carries both the timeline and
    the counter state at dump time."""
    fname = filename or _state["config"].get("filename", "profile.json")
    with _lock:
        payload = {"traceEvents": list(_events), "displayTimeUnit": "ms"}
    try:
        from . import telemetry
        payload["otherData"] = {"telemetry": telemetry.snapshot()}
    except Exception:
        pass
    with open(fname, "w") as f:
        json.dump(payload, f)
    return fname


def dispatch_stats(reset=False):
    """Counters from the eager dispatch layer (ops/registry + ops/segment):
    dispatch count, bulked vs immediate split, fast-path (compiled kernel)
    hits, key-cache / jit-cache / vjp-cache hits and misses, python
    jax.vjp (re)trace count, segment flushes and replay-cache reuse.

    Always on (plain int increments — no measurable dispatch cost), so it
    works outside start()/stop() windows too. `reset=True` zeroes the
    counters after the snapshot. See docs/PERF.md for field meanings."""
    from .ops.registry import dispatch_stats as _ds
    return _ds(reset=reset)


def serve_stats(reset=False):
    """Process-wide serving counters from mx.serve (requests, replies,
    rejected/shed/timeouts, batches, padded rows, programs compiled) —
    the serving analog of dispatch_stats(). Per-server latency percentiles
    and the batch-occupancy histogram live on `Server.stats()`. Executed
    batches also land in the Chrome trace as "serve.batch" events (cat
    "serve") while the profiler runs — the serving lane."""
    from .serve.metrics import serve_stats as _ss
    return _ss(reset=reset)


def feed_stats(reset=False):
    """Counters from the device-feed input pipeline (io.DeviceFeed /
    prefetch_to_device and the FusedTrainStep input-staging guard):
    batches fed/consumed, real H2D transfers vs redundant-transfer skips
    (`device_put_skipped`), buffer occupancy, and stall time split into
    waiting-on-data (`stall_data_us` — the pipeline is input-bound) vs
    waiting-on-compute (`stall_compute_us` — the feed is keeping up).

    Always on, like dispatch_stats(). `reset=True` zeroes after the
    snapshot. While the profiler runs, consumer waits land in the Chrome
    trace as "io.feed" events and feeder staging as "feed.stage" (cat
    "io") — the input-pipeline lane. See docs/PERF.md "Input pipeline"."""
    from .io.device_feed import feed_stats as _fs
    return _fs(reset=reset)


def io_stats(reset=False):
    """Counters from the ImageRecordIter decode pipeline (io/__init__.py +
    io/imagerec_pool.py): batches/images delivered, corrupt records
    zero-filled, consumer staging vs waited-on-decode time, host bytes
    handed to `device_put` (the uint8-handoff 4x reduction shows up
    here), device-augment batches, slot-aliasing defensive copies, and
    submit/worker restart counts — plus the native decoder's per-stage
    clocks (read/decode/augment ns + decoded records, mirrored into the
    telemetry registry as `io.imagerec.*` gauges). Always on, like
    dispatch_stats(); `reset=True` zeroes both after the snapshot. See
    docs/PERF.md "Input pipeline"."""
    from .io import io_stats as _ios
    return _ios(reset=reset)


def fused_stats(reset=False):
    """Counters from the fused kernel tier (ops/fused.py): dispatches
    that took a Pallas kernel path (`pallas_calls`) vs the jnp
    composition fallback (`fallback_calls` — off-TPU, unsupported layout
    or an untileable shape). Inside a jitted step these count per TRACE
    (the path choice is baked into the program); eagerly they count per
    call. Always on, like dispatch_stats(); `reset=True` zeroes after
    the snapshot. See docs/PERF.md "Kernel tier"."""
    from .ops.fused import fused_stats as _fus
    return _fus(reset=reset)


def dumps(reset=False, format="table"):
    """Aggregate stats table (≙ profiler.dumps / aggregate_stats.cc).

    The table carries three sections: the Chrome-trace event aggregate,
    the telemetry span aggregate (`span.duration_us` histogram per span
    name — populated even when the event profiler never ran), and the
    full telemetry registry snapshot (dispatch/serve/feed/kvstore counter
    groups + every registered metric). `format="json"` returns the same
    content as a JSON string."""
    with _lock:
        agg = defaultdict(lambda: [0, 0.0, float("inf"), 0.0])
        for e in _events:
            if e["ph"] == "b":       # its `e` partner carries the duration
                continue
            a = agg[e["name"]]
            a[0] += 1
            a[1] += e["dur"]
            a[2] = min(a[2], e["dur"])
            a[3] = max(a[3], e["dur"])
        if reset:
            _events.clear()
    try:
        from . import telemetry
        snap = telemetry.snapshot()
    except Exception:
        snap = {}
    spans = {k: v for k, v in snap.items()
             if k.startswith("span.duration_us")}
    scalars = {k: v for k, v in snap.items() if not isinstance(v, dict)}
    if format == "json":
        return json.dumps({
            "events": {name: {"calls": a[0], "total_us": a[1],
                              "min_us": (0.0 if a[0] == 0 else a[2]),
                              "max_us": a[3]}
                       for name, a in agg.items()},
            "telemetry": snap}, sort_keys=True)
    lines = [f"{'Name':<40}{'Calls':>8}{'Total(us)':>14}{'Min(us)':>12}"
             f"{'Max(us)':>12}",
             "-" * 86]
    for name, (calls, total, mn, mx) in sorted(agg.items(),
                                               key=lambda kv: -kv[1][1]):
        lines.append(f"{name:<40}{calls:>8}{total:>14.1f}{mn:>12.1f}"
                     f"{mx:>12.1f}")
    if spans:
        lines.append("")
        lines.append(f"{'Span (telemetry)':<40}{'Count':>8}"
                     f"{'Total(us)':>14}{'Min(us)':>12}{'Max(us)':>12}")
        lines.append("-" * 86)
        for name, h in sorted(spans.items(), key=lambda kv: -kv[1]["sum"]):
            lines.append(f"{name:<40}{h['count']:>8}{h['sum']:>14.1f}"
                         f"{h['min']:>12.1f}{h['max']:>12.1f}")
    if scalars:
        lines.append("")
        lines.append(f"{'Telemetry metric':<56}{'Value':>20}")
        lines.append("-" * 86)
        for name, v in sorted(scalars.items()):
            vv = f"{v:.1f}" if isinstance(v, float) else str(v)
            lines.append(f"{name:<56}{vv:>20}")
    return "\n".join(lines)


class Domain:
    """≙ profiler.Domain."""

    def __init__(self, name):
        self.name = name


class _Timed:
    def __init__(self, name, domain=None):
        self.name = name
        self.domain = domain
        self._start = None

    def start(self):
        self._start = _now_us()

    def stop(self):
        if self._start is not None:
            record_event(self.name, type(self).__name__.lower(),
                         _now_us() - self._start, ts_us=self._start)
            self._start = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()


class Task(_Timed):
    """≙ profiler.Task."""


class Frame(_Timed):
    """≙ profiler.Frame."""


class Event(_Timed):
    """≙ profiler.Event."""


class Counter:
    """≙ profiler.Counter."""

    def __init__(self, domain, name, value=0):
        self.name = name
        self.value = value

    def set_value(self, value):
        self.value = value
        record_event(self.name, "counter", 0,
                     args={"value": value})

    def increment(self, delta=1):
        self.set_value(self.value + delta)

    def decrement(self, delta=1):
        self.set_value(self.value - delta)


class Marker:
    """≙ profiler.Marker (instant event)."""

    def __init__(self, domain, name):
        self.name = name

    def mark(self, scope="process"):
        record_event(self.name, "marker", 0)


class profiler_scope:
    """with profiler.scope('name'): annotate a region."""

    def __init__(self, name):
        self._task = Task(name)

    def __enter__(self):
        self._task.start()
        return self

    def __exit__(self, *exc):
        self._task.stop()


scope = profiler_scope


# ---------------------------------------------------------------------------
# storage profiler lanes (≙ src/profiler/storage_profiler.{h,cc}: per-alloc
# timeline + pool stats dump). PJRT owns the allocator, so the equivalent
# is a sampled device-memory timeline — the Chrome-trace "storage lane"
# the reference renders from its per-alloc events. (XLA's pprof heap dump
# is one call of jax's own: `jax.profiler.device_memory_profile()`.)
# ---------------------------------------------------------------------------
def read_memory_sample(device=None):
    """ONE memory reading with an honest provenance stamp:
    `(bytes_in_use, source)`.

    `source == "device"`: PJRT `memory_stats()["bytes_in_use"]` — real
    accelerator HBM. `source == "host_rss"`: the CPU backend (and some
    PJRT builds) expose no memory stats, so the fallback is process RSS
    from `/proc/self/statm` — a HOST number that still moves with
    allocations, making the timeline lane meaningful on CI instead of a
    flat 0. `source == "unavailable"`: neither worked (bytes is 0).

    Shared by `MemoryMonitor`, `telemetry.StepTimeline`'s
    `peak_hbm_bytes` lane, and the OOM dump — one reader, one stamp."""
    try:
        import jax
        dev = device or jax.devices()[0]
        stats = getattr(dev, "memory_stats", lambda: None)()
        if stats and stats.get("bytes_in_use") is not None:
            return int(stats["bytes_in_use"]), "device"
    except Exception:
        pass
    try:
        with open("/proc/self/statm") as f:
            rss_pages = int(f.read().split()[1])
        import resource
        page = resource.getpagesize()
        return rss_pages * page, "host_rss"
    except Exception:
        return 0, "unavailable"


class MemoryMonitor:
    """Sampled device-memory timeline (≙ the storage profiler's
    MemoryManagerProfiler lane). Each sample lands in the Chrome trace as a
    counter event, so `profiler.dump()` renders a memory lane alongside op
    events.

        with profiler.MemoryMonitor(interval=0.01):
            train()

    Samples are `(ts_us, bytes, source)`; `source` is "device" on real
    accelerators and "host_rss" where `memory_stats()` is unavailable
    (CPU backends) — process RSS instead of a silently meaningless flat 0
    (the counter events carry the same stamp). Default interval:
    `MXNET_MEM_SAMPLE_INTERVAL`.
    """

    def __init__(self, interval=None, device=None):
        if interval is None:
            interval = get_env("MXNET_MEM_SAMPLE_INTERVAL", 0.05,
                               typ=float)
        self.interval = float(interval)
        self.device = device
        self.samples = []          # (ts_us, bytes_in_use, source)
        self.source = None         # stamp of the most recent sample
        self._stop = None
        self._thread = None

    def _read(self):
        b, source = read_memory_sample(self.device)
        # handoff ordered by Thread start/join like samples (see __enter__)
        self.source = source  # mxlint: disable=lock-shared-mutation
        # feed the process-wide mem.peak_hbm_bytes high-water — the
        # cataloged gauge covers MemoryMonitor AND StepTimeline samples,
        # so a monitor-only loop must move it too
        try:
            from .telemetry.steptrace import _note_memory_sample
            _note_memory_sample(b)
        except Exception:
            pass
        return b, source

    def __enter__(self):
        import threading
        # handoff ordered by Thread start/join, not a lock: _stop and
        # samples are written before start() and read after join()
        self._stop = threading.Event()  # mxlint: disable=lock-shared-mutation

        def loop():
            while not self._stop.is_set():
                b, source = self._read()
                self.samples.append((_now_us(), b, source))  # mxlint: disable=lock-shared-mutation
                self._stop.wait(self.interval)

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()
        return self

    def __exit__(self, *exc):
        import threading as _threading
        self._stop.set()
        self._thread.join(timeout=5)
        # proper Chrome COUNTER events ('ph': 'C') appended unconditionally:
        # the user explicitly asked for this lane by entering the context,
        # whether or not the op profiler is also running
        with _lock:
            for ts, b, source in self.samples:
                _events.append({
                    "name": "device_memory", "cat": "storage", "ph": "C",
                    "ts": ts, "pid": 0,
                    "tid": _threading.get_ident() % 100000,
                    "args": {"bytes_in_use": b, "source": source},
                })

    @property
    def peak_bytes(self):
        return max((b for _, b, _src in self.samples), default=0)
