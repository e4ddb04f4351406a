"""mx.io.DeviceFeed — async host→device input pipeline (double buffering).

The reference hides input latency behind compute with a threaded prefetcher
(src/io/iter_prefetcher.h) feeding the async engine. The JAX-era counterpart
is device-side prefetch (flax's `prefetch_to_device` idiom): a background
feeder pulls batches from any host iterator (gluon DataLoader, mx.io
DataIter, a plain generator), starts the **asynchronous** `jax.device_put`
— with `NamedSharding` placement over the data-parallel mesh axis when one
is active (`parallel.data_sharding`) — and parks the in-flight batch in a
bounded buffer. Host decode/augment and the H2D transfer for batch N+1 then
overlap the (asynchronously dispatched) compute of batch N, so a training
loop pays `max(data_time, step_time)` instead of their sum.

    feed = mx.io.DeviceFeed(loader, depth=2)       # or prefetch_to_device()
    for batch in feed:                             # device-resident NDArrays
        loss = step(*batch)

Failure semantics match `PrefetchingIter`: a feeder-thread exception
re-raises **in the consumer** (never a silently short epoch); transient
I/O errors (IOError/OSError/TimeoutError) retry in place up to
`max_restarts` consecutive times (default `MXNET_PREFETCH_RESTARTS`).
Fault-injection point: `io.device_feed` (fires per source fetch, before
the fetch — an injected transient never consumes a batch).

Observability: `profiler.feed_stats()` (batches fed/consumed, H2D
transfers vs redundant-transfer skips, buffer occupancy, stall time split
into waiting-on-data vs waiting-on-compute) and an `io.feed` Chrome-trace
lane (consumer waits + feeder staging spans) while the profiler runs.

Opt-in everywhere: `MXNET_PREFETCH_TO_DEVICE=1` makes `estimator.fit` and
`gluon.data.DataLoader` route batches through a feed transparently;
`MXNET_DEVICE_FEED_DEPTH` sets the default buffer depth (2 = classic
double buffering).
"""
from __future__ import annotations

import queue as _queue
import threading
import time

import numpy as _np

from ..base import MXNetError, get_env
from ..telemetry import span as _span, NO_SPAN, trace as _trace
from ..telemetry.registry import stats_group as _stats_group

__all__ = ["DeviceFeed", "prefetch_to_device", "feed_stats",
           "maybe_device_put", "FEED_STATS"]


# ---------------------------------------------------------------------------
# counters (always on — plain increments under one lock, like DISPATCH_STATS;
# adopted into the telemetry registry as the `feed` stats group, so they
# surface in telemetry.snapshot()/prometheus_text() too)
# ---------------------------------------------------------------------------
_STATS_LOCK = threading.Lock()

FEED_STATS = _stats_group("feed", {
    "batches_fed": 0,          # staged + buffered by feeder threads
    "batches_consumed": 0,     # delivered to the consumer
    "epochs": 0,               # completed feed iterations
    "host_transfers": 0,       # real H2D device_puts issued (host arrays)
    "recommitted": 0,          # uncommitted device arrays pinned in place
    "device_put_skipped": 0,   # already committed + right sharding: no copy
    "stall_data_us": 0.0,      # consumer waited on an EMPTY buffer
    "stall_compute_us": 0.0,   # feeder waited on a FULL buffer
    "stage_us": 0.0,           # feeder staging time (decode handoff + async
    #                            H2D dispatch) — overlaps compute by design
    "occupancy_sum": 0,        # buffer depth seen at each consume (incl. the
    "occupancy_samples": 0,    # batch being taken)
    "restarts": 0,             # transient feeder errors retried in place
    "failures": 0,             # terminal feeder failures re-raised downstream
}, lock=_STATS_LOCK,
    help="device-feed input-pipeline counters (profiler.feed_stats)")


def _bump(key, delta=1):
    with _STATS_LOCK:
        FEED_STATS[key] += delta


def feed_stats(reset=False):
    """Snapshot of the device-feed counters (plus derived
    `occupancy_mean`). `reset=True` zeroes the counters after the
    snapshot (atomically — no increment is lost between copy and zero).
    Exposed as `profiler.feed_stats()`; the same counters surface in
    `telemetry.snapshot()` as `feed.*`."""
    snap = FEED_STATS.snapshot(reset=reset)
    snap["occupancy_mean"] = (
        snap["occupancy_sum"] / snap["occupancy_samples"]
        if snap["occupancy_samples"] else 0.0)
    return snap


# ---------------------------------------------------------------------------
# placement
# ---------------------------------------------------------------------------
def maybe_device_put(raw, sharding=None):
    """Start an async device placement for `raw`, skipping the transfer
    when it is already a committed device array with the right placement
    (the redundant-transfer guard FusedTrainStep and DeviceFeed share).

    Three cases, each counted in FEED_STATS:
      - committed `jax.Array` whose sharding matches (or no sharding was
        requested): returned as-is — `device_put_skipped`
      - uncommitted `jax.Array` (e.g. a fresh `jnp.asarray` result): pinned
        to the requested placement, no host round-trip — `recommitted`
      - host array (numpy): real async H2D transfer — `host_transfers`
    """
    import jax
    if isinstance(raw, jax.Array):
        committed = getattr(raw, "committed", None)
        if committed is None:  # very old jax: private field
            committed = getattr(raw, "_committed", False)
        if committed and (sharding is None
                          or _sharding_matches(raw, sharding)):
            _bump("device_put_skipped")
            return raw
        _bump("recommitted")
    else:
        _bump("host_transfers")
    if sharding is None:
        from ..device import current_device
        sharding = current_device().jax_device
    return jax.device_put(raw, sharding)


def _sharding_matches(arr, sharding):
    try:
        import jax
        if isinstance(sharding, jax.sharding.Sharding):
            return arr.sharding.is_equivalent_to(sharding, arr.ndim)
        # a bare Device: equivalent iff the array lives on just that device
        return tuple(arr.sharding.device_set) == (sharding,)
    except Exception:
        return False


class _FeedFailure:
    """Terminal sentinel: the feeder died; holds the original exception."""

    __slots__ = ("error",)

    def __init__(self, error):
        self.error = error


def _fetch_with_restarts(source, point, max_restarts, on_restart=None):
    """Shared fetch loop for prefetch workers (PrefetchingIter._worker and
    DeviceFeed._worker): inject the fault `point` BEFORE each fetch (a
    transient injected fault must not consume a batch from the source),
    retry transient I/O errors (IOError/OSError/TimeoutError) in place up
    to `max_restarts` CONSECUTIVE times with a structured log per retry,
    and re-raise the original exception once the budget is exhausted (or
    immediately for non-transient errors). Yields fetched batches."""
    from .. import fault as _fault
    it = iter(source)
    restarts = 0
    while True:
        try:
            _fault.inject(point)
            batch = next(it)
        except StopIteration:
            return
        except (IOError, OSError, TimeoutError) as e:
            if restarts < max_restarts:
                restarts += 1
                if on_restart is not None:
                    on_restart()
                _fault._log_event(point + "_restart", attempt=restarts,
                                  error=repr(e))
                continue
            raise
        restarts = 0   # budget bounds CONSECUTIVE errors, not lifetime
        yield batch


# ---------------------------------------------------------------------------
# the feed
# ---------------------------------------------------------------------------
class DeviceFeed:
    """Background device-feed over any batch iterator (single consumer).

    Parameters
    ----------
    source : iterable
        Anything yielding batches: gluon `DataLoader`, `mx.io` DataIter
        (DataBatch elements are staged field-wise), or a generator of
        (nested) tuples/lists/dicts of NDArray/numpy leaves. Non-array
        leaves pass through untouched.
    depth : int, optional
        Buffer depth — batches staged ahead of the consumer (default
        `MXNET_DEVICE_FEED_DEPTH`, 2 = double buffering).
    sharding : jax.sharding.Sharding or callable, optional
        Placement for every leaf (a callable receives the leaf ndim).
        Default: `parallel.data_sharding` over the active mesh's 'dp'
        axis, else the current default device.
    batch_axis : int
        The axis split over 'dp' when a mesh drives placement.
    max_restarts : int, optional
        Consecutive transient-error retries before the feeder gives up
        (default `MXNET_PREFETCH_RESTARTS`).

    Each `iter(feed)` starts one fresh pass over `source` (epoch); `reset`
    stops the feeder and forwards to `source.reset()` when it exists, and
    `len(feed)` forwards to the source, so epoch loops written against
    DataIter/DataLoader work unchanged.
    """

    _feeds_device = True   # integration marker (estimator/DataLoader)

    def __init__(self, source, depth=None, sharding=None, batch_axis=0,
                 max_restarts=None):
        if depth is None:
            depth = get_env("MXNET_DEVICE_FEED_DEPTH", 2, typ=int)
        if int(depth) < 1:
            raise MXNetError("DeviceFeed depth must be >= 1")
        self._source = source
        self._depth = int(depth)
        self._sharding = sharding
        self._batch_axis = int(batch_axis)
        self._max_restarts = (get_env("MXNET_PREFETCH_RESTARTS", 3, typ=int)
                              if max_restarts is None else int(max_restarts))
        self._queue = None
        self._stop = None
        self._thread = None
        self._mesh = None
        self._device = None
        self._shard_cache = {}
        self._exhausted = False
        self.batch_size = getattr(source, "batch_size", None)

    # -- epoch lifecycle ------------------------------------------------
    def __iter__(self):
        self._start_epoch()
        return self

    def _start_epoch(self):
        self._shutdown()
        self._exhausted = False
        if self._sharding is None:
            # capture BOTH thread-local contexts here on the consumer
            # thread — the feeder thread has empty mesh/device stacks, so
            # resolving them lazily there would silently ignore an active
            # `with mx.cpu():` / `with mesh:` scope
            from .. import parallel
            from ..device import current_device
            self._mesh = parallel.current_mesh()
            self._device = (None if self._mesh is not None
                            else current_device().jax_device)
            self._shard_cache = {}
        q = self._queue = _queue.Queue(maxsize=self._depth)
        stop = self._stop = threading.Event()
        # trace propagation across the thread hop: a fresh thread starts
        # with an EMPTY contextvars context, so without this capture the
        # feeder's `feed.stage` spans would render parentless instead of
        # nesting under the consumer's step (the ctx travels as a Thread
        # arg — ordered by Thread.start, no shared attribute)
        from ..telemetry import trace as _trace
        ctx = _trace.current_context()
        self._thread = threading.Thread(
            target=self._worker, args=(q, stop, ctx), daemon=True,
            name="mx-device-feed")
        self._thread.start()

    def __next__(self):
        if self._queue is None:
            if self._exhausted:    # stays exhausted until iter() restarts
                raise StopIteration
            self._start_epoch()
        # the consumer's wait, live on the profiler's clock while a
        # collector is armed (the terminal sentinel's wait shows too; the
        # counters below still leave it out)
        t0 = time.perf_counter()
        with (_span("io.feed", cat="io", buffer=self._queue.qsize())
              if _trace.armed() else NO_SPAN):
            item = self._queue.get()
        if item is None:
            self._finish_epoch()
            self._exhausted = True
            raise StopIteration
        if isinstance(item, _FeedFailure):
            self._finish_epoch()
            self._exhausted = True
            raise item.error
        # stats only for REAL batches: the terminal sentinel's wait must
        # not skew stall_data_us / occupancy (they feed the committed
        # overlap metrics)
        waited_us = (time.perf_counter() - t0) * 1e6
        with _STATS_LOCK:
            FEED_STATS["stall_data_us"] += waited_us
            FEED_STATS["occupancy_sum"] += self._queue.qsize() + 1
            FEED_STATS["occupancy_samples"] += 1
            FEED_STATS["batches_consumed"] += 1
        return item

    next = __next__

    def __len__(self):
        return len(self._source)

    def reset(self):
        """Stop the feeder and reset the underlying source (when it can)."""
        self._shutdown()
        self._exhausted = False
        r = getattr(self._source, "reset", None)
        if r is not None:
            r()

    def close(self):
        """Stop the feeder thread (idempotent; also runs at GC)."""
        self._shutdown()

    def _finish_epoch(self):
        t, self._thread = self._thread, None
        self._queue = None
        self._stop = None
        if t is not None:
            t.join(timeout=10)
        _bump("epochs")

    def _shutdown(self):
        if self._thread is None:
            return
        self._stop.set()
        try:            # drain so a feeder blocked on a full buffer wakes
            while True:
                self._queue.get_nowait()
        except _queue.Empty:
            pass
        self._thread.join(timeout=10)
        if self._thread.is_alive():
            # a fetch stalled past the join window: the old feeder may
            # still advance the shared source when it wakes, racing a new
            # epoch's feeder — surface it instead of silently proceeding
            from .. import fault as _fault
            _fault._log_event("io.device_feed_shutdown_timeout",
                              source=type(self._source).__name__)
        self._thread = None
        self._queue = None
        self._stop = None

    def __del__(self):
        try:
            self._shutdown()
        except Exception:
            pass

    # -- feeder thread --------------------------------------------------
    def _worker(self, q, stop, ctx=None):
        from ..telemetry import record_span, trace as _trace
        if ctx is not None:
            # adopt the consumer's trace context: feed.stage spans nest
            # under the step that was open when this epoch started
            _trace.attach(ctx)
        fetch = _fetch_with_restarts(self._source, "io.device_feed",
                                     self._max_restarts,
                                     on_restart=lambda: _bump("restarts"))
        while not stop.is_set():
            try:
                batch = next(fetch)
            except StopIteration:
                self._put(q, stop, None)
                return
            except BaseException as e:   # re-raised in the consumer
                _bump("failures")
                self._put(q, stop, _FeedFailure(e))
                return
            try:
                t0 = time.perf_counter()
                staged = self._stage(batch)
                stage_us = (time.perf_counter() - t0) * 1e6
            except BaseException as e:
                _bump("failures")
                self._put(q, stop, _FeedFailure(e))
                return
            _bump("stage_us", stage_us)
            record_span("feed.stage", stage_us, ts_us=t0 * 1e6, cat="io")
            if not self._put(q, stop, staged):
                return
            _bump("batches_fed")

    def _put(self, q, stop, item):
        """Blocking put that aborts on shutdown. Time spent here means the
        buffer is full — compute is the bottleneck, not data."""
        t0 = time.perf_counter()
        while not stop.is_set():
            try:
                q.put(item, timeout=0.05)
            except _queue.Full:
                continue
            _bump("stall_compute_us", (time.perf_counter() - t0) * 1e6)
            return True
        return False

    # -- staging --------------------------------------------------------
    def _stage(self, batch):
        from . import DataBatch
        if isinstance(batch, DataBatch):
            return DataBatch(self._stage(batch.data),
                             label=self._stage(batch.label),
                             pad=batch.pad, index=batch.index,
                             provide_data=batch.provide_data,
                             provide_label=batch.provide_label)
        if isinstance(batch, dict):
            return {k: self._stage(v) for k, v in batch.items()}
        if isinstance(batch, tuple):
            staged = [self._stage(v) for v in batch]
            if hasattr(batch, "_fields"):     # namedtuple: keep the type
                return type(batch)(*staged)
            return tuple(staged)
        if isinstance(batch, list):
            return [self._stage(v) for v in batch]
        return self._stage_leaf(batch)

    def _stage_leaf(self, x):
        import jax
        from ..ndarray import NDArray, _wrap
        raw = x._arr if isinstance(x, NDArray) else x
        if not isinstance(raw, (jax.Array, _np.ndarray, _np.generic)):
            return x                       # scalars/strings pass through
        out = maybe_device_put(raw, self._leaf_sharding(raw.ndim))
        # census attribution (mx.inspect.memory): in-flight staged
        # batches are the feed's resident set (depth x batch bytes) —
        # a weakref-registry write per leaf, never able to break staging
        try:
            from ..inspect import memory as _mem
            _mem.register(out, owner="device_feed")
        except Exception:
            pass
        return _wrap(out)

    def _leaf_sharding(self, ndim):
        if self._sharding is not None:
            return (self._sharding(ndim) if callable(self._sharding)
                    else self._sharding)
        if self._mesh is None:
            return self._device            # consumer-thread device scope
        s = self._shard_cache.get(ndim)
        if s is None and ndim not in self._shard_cache:
            from .. import parallel
            s = parallel.data_sharding(ndim, batch_axis=self._batch_axis,
                                       mesh=self._mesh)
            self._shard_cache[ndim] = s
        return s


def prefetch_to_device(loader, size=None, sharding=None, batch_axis=0):
    """flax-style convenience: `for batch in prefetch_to_device(loader):`
    — wraps `loader` in a DeviceFeed of depth `size` (default
    MXNET_DEVICE_FEED_DEPTH, 2 = double buffering, 3 = triple). See
    DeviceFeed for sharding/mesh behavior."""
    return DeviceFeed(loader, depth=size, sharding=sharding,
                      batch_axis=batch_axis)
