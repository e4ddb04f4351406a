"""mx.kvstore — KVStore API facade over XLA collectives.

Reference: include/mxnet/kvstore.h:59-466 + python/mxnet/kvstore/
(KVStoreBase registry base.py:74-245, native wrapper kvstore.py:54, horovod/
byteps bridges). The reference's backends (CommCPU/CommDevice/CommDeviceTree
reductions, ps-lite dist_sync/dist_async servers, NCCL) are replaced by ONE
TPU-native implementation: values live as (optionally mesh-sharded)
NDArrays; `push` aggregates gradients (the engine-ordered Comm::Reduce
becomes one XLA add or a psum over the dp axis when running multi-process
SPMD); `pull` hands back the stored weight.

Semantic mapping:
  init(k, v)        ≙ KVStore::Init — register initial weight
  push(k, vals)     ≙ Push — sum(vals) [* then updater if set_updater]
  pull(k, outs)     ≙ Pull — copy current value into outs
  pushpull(k, v, o) ≙ PushPull fused (kvstore.h:226)
  broadcast(k,v,o)  ≙ Broadcast (init+pull fused, kvstore.h:203)
  rank/num_workers  ≙ get_rank/get_group_size → jax process index/count
  barrier           ≙ Barrier → blocking sync on all local arrays

`create('local'|'device'|'nccl'|'dist_sync'|'dist_device_sync'|'dist_async'|
'horovod'|'byteps'|'tpu')` all resolve to this implementation — the type
string only toggles update_on_kvstore defaults, matching trainer.py:188-275
decision logic.
"""
from __future__ import annotations

import pickle
import threading
import time as _time
from collections import OrderedDict

import numpy as _np

from ..base import MXNetError, get_env
from .. import fault as _fault
from ..telemetry.registry import stats_group as _stats_group

__all__ = ["KVStore", "KVStoreBase", "create", "KV_STATS",
           "BarrierTimeout", "reduce_scatter_buckets", "allgather_buckets"]

# Collective timings for step-timeline attribution (telemetry.StepTimeline
# diffs allreduce_us around each train step — the distributed analog of the
# DeviceFeed stall clock). Increments under _KV_STATS_LOCK; the `*_us`
# clocks are DISPATCH-side wall time of the bucketed collective
# (concatenate + collective issue + result split) — buckets dispatch
# asynchronously, so device-side reduction overlap is not in these clocks
# (`ElasticTrainer.overlap_fraction()` counts it by events).
_KV_STATS_LOCK = threading.Lock()

KV_STATS = _stats_group("kvstore", {
    "allreduce_us": 0.0,       # wall time inside bucketed-collective calls
    "allreduce_buckets": 0,    # collective buckets dispatched
    "allreduce_bytes": 0,      # payload bytes across those buckets
    "reduce_scatter_us": 0.0,  # wall time inside bucketed reduce-scatter
    "reduce_scatter_buckets": 0,
    "reduce_scatter_bytes": 0,
    "allgather_us": 0.0,       # wall time inside bucketed all-gather
    "allgather_buckets": 0,
    "allgather_bytes": 0,
}, lock=_KV_STATS_LOCK,
    help="kvstore collective timings (telemetry step-timeline attribution)")


# process-wide barrier sequence: two KVStore instances in one process
# must never reuse a sequence number, or their arrival announcements
# would collide in the coordinator KV store and corrupt attribution.
# (Ranks agree on numbers through the usual SPMD discipline — every
# process makes the same barrier calls in the same order; a lone rank
# restarting mid-job is not a supported barrier mode, whole-job restart
# gets a fresh coordinator store.)
_BARRIER_SEQ_LOCK = threading.Lock()
_BARRIER_SEQ = [0]


def _next_barrier_seq():
    with _BARRIER_SEQ_LOCK:
        _BARRIER_SEQ[0] += 1
        return _BARRIER_SEQ[0]


class BarrierTimeout(MXNetError):
    """A kvstore barrier rendezvous exceeded its deadline. `missing_ranks`
    names the peers that provably never announced their arrival (empty when
    no coordinator KV store is available to attribute the stall)."""

    def __init__(self, message, missing_ranks=None):
        super().__init__(message)
        self.missing_ranks = list(missing_ranks or [])


def _note_collective(kind, t0, nbytes, keys):
    """One collective bucket of `kind` (allreduce / reduce_scatter /
    allgather) dispatched at perf_counter seconds `t0`: advance the
    KV_STATS clocks and record the `kv.<kind>` span lane — the single
    implementation every bucketed collective path shares."""
    from ..telemetry import record_span
    dur_us = (_time.perf_counter() - t0) * 1e6
    with _KV_STATS_LOCK:
        KV_STATS[kind + "_us"] += dur_us
        KV_STATS[kind + "_buckets"] += 1
        KV_STATS[kind + "_bytes"] += nbytes
    record_span("kv." + kind, dur_us, ts_us=t0 * 1e6, cat="kv",
                nbytes=nbytes, keys=keys)


def _note_allreduce(t0, nbytes, keys):
    _note_collective("allreduce", t0, nbytes, keys)


# ---------------------------------------------------------------------------
# bucketed dp-axis collectives (the ZeRO data path, mx.fault.elastic)
# ---------------------------------------------------------------------------
# compiled shard_map programs keyed on (kind, mesh, axis, shapes/dtypes,
# scale). Entries hold the mesh STRONGLY so a recycled id() can never alias
# a different mesh while the entry lives; FIFO-bounded so elastic mesh
# shrinks don't accumulate programs for dead meshes forever.
_COLL_FN_CACHE = OrderedDict()
_COLL_FN_CACHE_CAP = 64
_COLL_FN_LOCK = threading.Lock()


def _coll_fn(kind, jmesh, axis, sig, scale, build):
    key = (kind, id(jmesh), axis, sig, scale)
    with _COLL_FN_LOCK:
        hit = _COLL_FN_CACHE.get(key)
        if hit is not None and hit[0] is jmesh:
            return hit[1]
    fn = build()   # tracing outside the lock: compiles can be slow
    with _COLL_FN_LOCK:
        _COLL_FN_CACHE[key] = (jmesh, fn)
        while len(_COLL_FN_CACHE) > _COLL_FN_CACHE_CAP:
            _COLL_FN_CACHE.popitem(last=False)
    return fn


def collective_compiled_surfaces():
    """Inspection snapshot of the cached bucketed-collective programs:
    ``[{"kind", "axis", "fn", "avals"}]`` — the jitted shard_map program
    plus abstract ``jax.ShapeDtypeStruct`` args reconstructed from the
    cache key's signature, so `mx.inspect.memory.collective_memory_plans`
    can lower each program for a memory plan without touching live
    gradient/shard buffers (lowering at the same avals hits the same jit
    cache entry — no extra compile, no retrace)."""
    import jax
    out = []
    with _COLL_FN_LOCK:
        entries = list(_COLL_FN_CACHE.items())
    for (kind, _mid, axis, sig, _scale), (_jmesh, fn) in entries:
        avals = tuple(jax.ShapeDtypeStruct(tuple(item[0]), item[1])
                      for item in sig)
        out.append({"kind": kind, "axis": axis, "fn": fn, "avals": avals})
    return out


def _bucketize(raws, bytes_of_idx, bucket_bytes):
    """Greedy ~bucket_bytes buckets of indices into `raws`,
    dtype-segregated, order-preserving within dtype (≙ the kvstore_dist
    key batching)."""
    by_dtype = {}
    for i, a in enumerate(raws):
        by_dtype.setdefault(str(a.dtype), []).append(i)
    buckets = []
    for _, idxs in by_dtype.items():
        cur, cur_bytes = [], 0
        for i in idxs:
            sz = bytes_of_idx(i)
            if cur and cur_bytes + sz > bucket_bytes:
                buckets.append(cur)
                cur, cur_bytes = [], 0
            cur.append(i)
            cur_bytes += sz
        if cur:
            buckets.append(cur)
    return buckets


def reduce_scatter_buckets(grads, mesh, axis="dp", scale=None,
                           bucket_bytes=None):
    """Bucketed reduce-scatter over the dp mesh axis — the gradient half of
    the ZeRO step (`mx.fault.elastic`).

    `grads`: list of per-replica-stacked arrays of global shape
    ``(dp, *shape)`` sharded ``P(axis, ...)`` — row r is replica r's local
    gradient. Each ~4MB bucket dispatches as ONE jitted shard_map program:
    per param, the local gradient is flattened, zero-padded to ``dp * L``,
    and `lax.psum_scatter`'d so rank r receives the REDUCED elements of
    shard r only (`scale` multiplies the sum — pass ``1/dp`` for a mean).
    Returns ``(dp, L_i)`` shard views sharded ``P(axis, None)``, the layout
    `optimizer.sharded` updates in place.

    Buckets dispatch asynchronously, so bucket k+1's issue overlaps bucket
    k's reduction AND the still-in-flight backward that produced the
    grads. Each bucket
    hits the `kvstore.reduce_scatter` fault point and lands in
    KV_STATS reduce_scatter_us/buckets/bytes + the `kv.reduce_scatter`
    span lane.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from ..parallel import shard_map as _shard_map

    jmesh = getattr(mesh, "jax_mesh", mesh)
    if axis not in jmesh.shape:
        raise MXNetError(f"mesh {dict(jmesh.shape)} has no {axis!r} axis")
    dp = int(jmesh.shape[axis])
    bucket_bytes = bucket_bytes or KVStore._BUCKET_BYTES
    raws = [getattr(g, "_arr", g) for g in grads]
    for i, g in enumerate(raws):
        if g.ndim < 1 or g.shape[0] != dp:
            raise MXNetError(
                f"grads[{i}] must be per-replica stacked (dp={dp}, ...), "
                f"got shape {tuple(g.shape)}")

    def per_replica_bytes(g):
        n = 1
        for s in g.shape[1:]:
            n *= s
        return max(n, 1) * g.dtype.itemsize

    results = [None] * len(raws)
    for bucket in _bucketize(raws, lambda i: per_replica_bytes(raws[i]),
                             bucket_bytes):
        sig = tuple((tuple(raws[i].shape), str(raws[i].dtype))
                    for i in bucket)

        def build(bucket=bucket, sig=sig):
            shapes = [raws[i].shape for i in bucket]

            def body(*locals_):
                outs = []
                for gl, shp in zip(locals_, shapes):
                    n = 1
                    for s in shp[1:]:
                        n *= s
                    flat = gl.reshape(-1)
                    L = -(-n // dp)
                    if n < dp * L:
                        flat = jnp.concatenate(
                            [flat, jnp.zeros((dp * L - n,), flat.dtype)])
                    red = jax.lax.psum_scatter(
                        flat, axis, scatter_dimension=0, tiled=True)
                    if scale is not None:
                        red = red * jnp.asarray(scale, red.dtype)
                    outs.append(red.reshape(1, L))
                return tuple(outs)

            in_specs = tuple(P(axis, *([None] * (len(s[0]) - 1)))
                             for s in sig)
            out_specs = tuple(P(axis, None) for _ in sig)
            return jax.jit(_shard_map(body, jmesh, in_specs, out_specs))

        fn = _coll_fn("reduce_scatter", jmesh, axis, sig,
                      None if scale is None else float(scale), build)
        _fault.inject("kvstore.reduce_scatter")
        t0 = _time.perf_counter()
        outs = fn(*[raws[i] for i in bucket])
        nbytes = sum(per_replica_bytes(raws[i]) for i in bucket)
        _note_collective("reduce_scatter", t0, nbytes, len(bucket))
        for i, o in zip(bucket, outs):
            results[i] = o
    return results


def allgather_buckets(shards, metas, mesh, axis="dp", bucket_bytes=None):
    """Bucketed all-gather over the dp mesh axis — the parameter half of
    the ZeRO step: each rank contributes its fresh ``(1, L)`` shard row and
    every rank receives the full parameter.

    `shards`: list of ``(dp, L_i)`` arrays sharded ``P(axis, None)``;
    `metas`: congruent list of ``(numel, shape)`` to unpad and reshape the
    gathered flats. Returns fully-replicated arrays of the original
    shapes. Per-bucket `kvstore.allgather` fault point, KV_STATS
    allgather_us/buckets/bytes, `kv.allgather` span lane.
    """
    import jax
    from jax.sharding import PartitionSpec as P
    from ..parallel import shard_map as _shard_map

    jmesh = getattr(mesh, "jax_mesh", mesh)
    if axis not in jmesh.shape:
        raise MXNetError(f"mesh {dict(jmesh.shape)} has no {axis!r} axis")
    dp = int(jmesh.shape[axis])
    if len(shards) != len(metas):
        raise MXNetError("shards and metas must be congruent lists")
    bucket_bytes = bucket_bytes or KVStore._BUCKET_BYTES
    raws = [getattr(s, "_arr", s) for s in shards]

    def full_bytes(i):
        numel, _ = metas[i]
        return max(int(numel), 1) * raws[i].dtype.itemsize

    results = [None] * len(raws)
    for bucket in _bucketize(raws, full_bytes, bucket_bytes):
        sig = tuple((tuple(raws[i].shape), str(raws[i].dtype),
                     int(metas[i][0]), tuple(metas[i][1])) for i in bucket)

        def build(bucket=bucket, sig=sig):
            items = [(int(metas[i][0]), tuple(metas[i][1]))
                     for i in bucket]

            def body(*locals_):
                outs = []
                for sl, (numel, shape) in zip(locals_, items):
                    full = jax.lax.all_gather(
                        sl.reshape(-1), axis, tiled=True)
                    outs.append(full[:numel].reshape(shape))
                return tuple(outs)

            in_specs = tuple(P(axis, None) for _ in sig)
            out_specs = tuple(P() for _ in sig)
            return jax.jit(_shard_map(body, jmesh, in_specs, out_specs))

        fn = _coll_fn("allgather", jmesh, axis, sig, None, build)
        _fault.inject("kvstore.allgather")
        t0 = _time.perf_counter()
        outs = fn(*[raws[i] for i in bucket])
        nbytes = sum(full_bytes(i) for i in bucket)
        _note_collective("allgather", t0, nbytes, len(bucket))
        for i, o in zip(bucket, outs):
            results[i] = o
    return results


class KVStoreBase:
    """Registry base (≙ python/mxnet/kvstore/base.py:74)."""

    OPTIMIZER = "optimizer"
    _kv_registry = {}

    @staticmethod
    def register(klass):
        name = klass.__name__.lower()
        KVStoreBase._kv_registry[name] = klass
        return klass

    @staticmethod
    def is_capable(capability):
        raise NotImplementedError

    # subclass surface: broadcast, pushpull, rank, num_workers


def create(name="local"):
    """≙ mx.kv.create. All native types map to the TPU store; custom
    registered stores (KVStoreBase.register) are honored."""
    if not isinstance(name, str):
        raise TypeError("name must be a string")
    base = name.split("_")[0].lower()
    custom = KVStoreBase._kv_registry.get(name.lower())
    if custom is not None and custom is not KVStore:
        return custom()
    known = ("local", "device", "nccl", "dist", "horovod", "byteps", "tpu")
    if base not in known and name.lower() not in (
            "dist_sync", "dist_async", "dist_device_sync", "dist_sync_device"):
        raise MXNetError(f"unknown kvstore type {name!r}")
    return KVStore(name)


@KVStoreBase.register
class KVStore(KVStoreBase):
    """The TPU-native key-value store."""

    def __init__(self, kv_type="local"):
        self.type = kv_type
        self._store = {}
        self._updater = None
        self._optimizer = None
        self._opt_states = {}
        self._compression = None
        # wire accounting for the compressed dist push path: bytes this
        # process actually sent per key on its last push (packed payload)
        self.wire_bytes_last_push = {}
        self._wire_bytes_total = 0

    @property
    def wire_bytes_total(self):
        """Total compressed payload bytes this process has pushed (dist
        compressed path only; 0 otherwise)."""
        return self._wire_bytes_total

    def set_gradient_compression(self, compression_params):
        """≙ KVStore::SetGradientCompression (gradient_compression.cc)."""
        from .gradient_compression import GradientCompression
        self._compression = GradientCompression(**compression_params)

    @staticmethod
    def is_capable(capability):
        return capability == KVStoreBase.OPTIMIZER

    # ------------------------------------------------------------------
    @property
    def rank(self):
        import jax
        return jax.process_index()

    @property
    def num_workers(self):
        import jax
        return jax.process_count()

    def get_rank(self):
        return self.rank

    def get_group_size(self):
        return self.num_workers

    # ------------------------------------------------------------------
    def _dist_active(self):
        """True when this is a dist-type store in a real multi-process run —
        push/broadcast/barrier then use actual cross-process collectives
        (≙ ps-lite servers; here: jax multihost collectives over DCN)."""
        if self.type.split("_")[0] not in ("dist", "horovod", "byteps"):
            return False
        import jax
        try:
            return jax.process_count() > 1
        except RuntimeError:
            return False

    @staticmethod
    def _cross_process_sum(agg):
        """Sum ONE value across processes (small-key / fallback path).

        Deliberately NOT retried per-process: one participant re-entering a
        collective while its peers have moved on pairs the retry with the
        peers' NEXT collective — a hang or silently wrong sums. Collective
        failures fail fast here; recovery is whole-job restart via
        fault.run_resilient (and the barrier's watchdog bounds the hang)."""
        from jax.experimental import multihost_utils
        from ..ndarray import NDArray, array
        _fault.inject("kvstore.collective")
        raw = agg._arr if isinstance(agg, NDArray) else agg
        t0 = _time.perf_counter()
        gathered = multihost_utils.process_allgather(raw)  # (P, *shape)
        out = array(_np.asarray(gathered).sum(axis=0))
        _note_allreduce(t0, nbytes=int(getattr(raw, "size", 0)) * getattr(
            getattr(raw, "dtype", None), "itemsize", 4), keys=1)
        return out

    _BUCKET_BYTES = 4 << 20   # ≙ kvstore_dist key-sharding granularity

    def _cross_process_sum_many(self, aggs):
        """Bucketed fused allreduce across processes.

        ≙ src/kvstore/kvstore_dist.h:262-382 — the reference shards big keys
        and batches small ones so the wire sees few large messages. Here:
        gradients are flattened and concatenated into ~4MB buckets; each
        bucket is ONE device-path collective (a global-mesh jit whose sum
        over the process axis XLA lowers to AllReduce over ICI/DCN), not a
        per-key host round-trip. Buckets dispatch asynchronously, so
        bucket k+1's transfer overlaps bucket k's reduction (the priority
        overlap the reference gets from engine priorities). Falls back to
        the host path when the topology is irregular.
        """
        import jax
        import jax.numpy as jnp
        from ..ndarray import NDArray, _wrap

        if len(aggs) == 1:
            return [self._cross_process_sum(aggs[0])]
        raws = [a._arr if isinstance(a, NDArray) else jnp.asarray(a)
                for a in aggs]
        try:
            reduce_flat = self._world_allreduce()
        except Exception:
            return [self._cross_process_sum(a) for a in aggs]

        # bucket by dtype, ~4MB each, preserving order within dtype
        order = list(range(len(raws)))
        results = [None] * len(raws)
        by_dtype = {}
        for i in order:
            by_dtype.setdefault(str(raws[i].dtype), []).append(i)
        for _, idxs in by_dtype.items():
            bucket, nbytes = [], 0
            pending = []
            for i in idxs:
                sz = raws[i].size * raws[i].dtype.itemsize
                if bucket and nbytes + sz > self._BUCKET_BYTES:
                    pending.append(bucket)
                    bucket, nbytes = [], 0
                bucket.append(i)
                nbytes += sz
            if bucket:
                pending.append(bucket)
            reduced = []
            for bucket in pending:   # async dispatch: transfers overlap
                t0 = _time.perf_counter()
                flat = jnp.concatenate([raws[i].reshape(-1)
                                        for i in bucket])
                reduced.append((bucket, reduce_flat(flat)))
                _note_allreduce(t0, nbytes=int(flat.size)
                                * flat.dtype.itemsize, keys=len(bucket))
            for bucket, red in reduced:
                off = 0
                for i in bucket:
                    n = raws[i].size
                    results[i] = _wrap(
                        red[off:off + n].reshape(raws[i].shape))
                    off += n
        return results

    def _world_allreduce(self):
        """jit'd flat-vector sum over a global device mesh spanning all
        processes (XLA AllReduce, ≙ the NCCL ring the reference's
        kvstore_nccl uses)."""
        fn = getattr(self, "_world_allreduce_fn", None)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp
        import numpy as onp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        devs = jax.devices()
        mesh = Mesh(onp.array(devs), ("world",))
        repl = NamedSharding(mesh, P())
        spec = NamedSharding(mesh, P("world"))
        summed = jax.jit(lambda x: jnp.sum(x, axis=0), out_shardings=repl)

        def reduce_flat(flat):
            W = len(devs)
            # this process's contribution rides its first local device;
            # other local devices contribute exact zeros
            shards = []
            for i, d in enumerate(jax.local_devices()):
                v = flat if i == 0 else jnp.zeros_like(flat)
                shards.append(jax.device_put(v[None], d))
            garr = jax.make_array_from_single_device_arrays(
                (W, flat.shape[0]), spec, shards)
            return summed(garr).addressable_data(0)

        self._world_allreduce_fn = reduce_flat
        return reduce_flat

    @staticmethod
    def _bcast_from_root(v):
        """Rank 0's value to every process (≙ KVStore::Init server copy)."""
        from jax.experimental import multihost_utils
        from ..ndarray import NDArray, array
        raw = v._arr if isinstance(v, NDArray) else v
        return array(_np.asarray(multihost_utils.broadcast_one_to_all(raw)))

    def init(self, key, value):
        keys, values = _pairs(key, value)
        dist = self._dist_active()
        for k, v in zip(keys, values):
            if k not in self._store:
                v0 = _one(v)
                self._store[k] = (self._bcast_from_root(v0) if dist
                                  else v0.copy())

    def broadcast(self, key, value, out=None, priority=0):
        """≙ KVStore::Broadcast (kvstore.h:203): init then pull."""
        self.init(key, value)
        if out is not None:
            self.pull(key, out, priority)
        return out

    def push(self, key, value, priority=0):
        _fault.inject("kvstore.push")
        keys, values = _pairs(key, value)
        dist = self._dist_active()
        if self._compression is not None and dist:
            # ≙ the reference's dist compressed push
            # (src/kvstore/kvstore_dist.h:262-382 + gradient_compression.cc):
            # the LOCALLY-REDUCED gradient is quantized with error-feedback,
            # bit-packed into uint32 words, and the PACKED words are what
            # cross the wire (process allgather); every process then unpacks
            # all workers' payloads and sums — the server-side reconstruction.
            import jax.numpy as jnp
            from jax.experimental import multihost_utils
            local_aggs, payloads = [], []
            for k, v in zip(keys, values):
                agg = _aggregate(v)
                local_aggs.append(agg)
                packed = self._compression.compress_packed(k, agg)
                nbytes = int(packed.size) * 4
                self.wire_bytes_last_push[k] = nbytes
                self._wire_bytes_total += nbytes
                payloads.append(packed)
            # ONE gather for all keys (≙ the bucketed key batching of
            # kvstore_dist.h): packed words concatenate into a single
            # uint32 wire message instead of a per-key rendezvous
            flat = (payloads[0] if len(payloads) == 1
                    else jnp.concatenate(payloads))
            gathered = multihost_utils.process_allgather(flat)  # (P, W)
            aggs, off = [], 0
            for k, agg, packed in zip(keys, local_aggs, payloads):
                w = int(packed.size)
                aggs.append(self._compression.decompress_sum(
                    gathered[:, off:off + w], agg.shape, agg.dtype))
                off += w
            self._finish_push(keys, values, aggs)
            return
        aggs = []
        for k, v in zip(keys, values):
            if self._compression is not None:
                # local stores: same quantize-with-error-feedback semantics,
                # applied per pushed value (no wire to pack for)
                vs = v if isinstance(v, (list, tuple)) else [v]
                v = [self._compression.compress((k, i), g)
                     for i, g in enumerate(vs)]
            aggs.append(_aggregate(v))
        if dist:
            # ≙ dist_sync: the server's sum over workers, as ONE fused
            # bucketed collective set over all pushed keys. Every process
            # contributes its local aggregate and receives the global sum,
            # so updater/optimizer runs identically everywhere.
            aggs = self._cross_process_sum_many(aggs)
        self._finish_push(keys, values, aggs)

    def _finish_push(self, keys, values, aggs):
        for k, v, agg in zip(keys, values, aggs):
            if self._updater is not None:
                if k not in self._store:
                    self._store[k] = _one(v).copy()
                self._updater(_key_int(k), agg, self._store[k])
            elif self._optimizer is not None:
                w = self._store[k]
                if k not in self._opt_states:
                    self._opt_states[k] = \
                        self._optimizer.create_state_multi_precision(
                            _key_int(k), w)
                self._optimizer.update_multi_precision(
                    _key_int(k), w, agg, self._opt_states[k])
            else:
                self._store[k] = agg

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        _fault.inject("kvstore.pull")
        keys, outs = _pairs(key, out)
        for k, o in zip(keys, outs):
            if k not in self._store:
                raise MXNetError(f"key {k!r} not initialized in kvstore")
            val = self._store[k]
            for target in (o if isinstance(o, (list, tuple)) else [o]):
                target[:] = val
        return out

    def pushpull(self, key, value, out=None, priority=0):
        """≙ KVStore::PushPull (fused allreduce path, kvstore.h:226)."""
        self.push(key, value, priority)
        if out is not None:
            # pure allreduce semantics when no updater: out = sum(values)
            self.pull(key, out, priority)
        return out

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """≙ KVStore::PullRowSparse (kvstore.h:320 + trainer.py:325): pull
        only the requested rows of a stored table. Dense-native semantics:
        `out` of shape (len(rows), D) receives the gathered rows; `out` of
        full table shape receives the rows written in place (other rows
        untouched). Cost scales with rows requested, not the table."""
        import jax.numpy as jnp
        if row_ids is None or out is None:
            raise MXNetError("row_sparse_pull needs out= and row_ids=")
        keys, outs = _pairs(key, out)
        rids = row_ids if isinstance(row_ids, (list, tuple)) \
            else [row_ids] * len(keys)
        for k, o, r in zip(keys, outs, rids):
            if k not in self._store:
                raise MXNetError(f"key {k!r} not initialized in kvstore")
            val = self._store[k]
            idx = jnp.asarray(
                r._arr if hasattr(r, "_arr") else _np.asarray(r)
            ).reshape(-1).astype(jnp.int32)
            rows = val._arr[idx]
            targets = o if isinstance(o, (list, tuple)) else [o]
            from ..ndarray.sparse import RowSparseNDArray
            for t in targets:
                if isinstance(t, RowSparseNDArray):
                    # sparse out: becomes exactly the pulled row block
                    # (≙ the reference's RSP pull filling data+indices aux).
                    # Validate now — a mismatched container would only blow
                    # up much later in asnumpy; duplicate ids are uniqued
                    # (the reference guarantees unique RSP rows)
                    if tuple(t.shape) != tuple(val.shape):
                        raise MXNetError(
                            f"row_sparse_pull out shape {tuple(t.shape)} "
                            f"does not match value {tuple(val.shape)}")
                    uniq = _np.unique(_np.asarray(idx, _np.int64))
                    t._data_np = _np.asarray(
                        val._arr[uniq]).astype(t.dtype)
                    t._indices_np = uniq
                elif tuple(t.shape) == tuple(rows.shape):
                    t._set_arr(rows)
                elif tuple(t.shape) == tuple(val.shape):
                    t._set_arr(t._arr.at[idx].set(rows))
                else:
                    raise MXNetError(
                        f"row_sparse_pull out shape {tuple(t.shape)} "
                        f"matches neither rows {tuple(rows.shape)} nor "
                        f"table {tuple(val.shape)}")
        return out

    # ------------------------------------------------------------------
    def set_updater(self, updater):
        """≙ KVStore::set_updater — run optimizer on the store."""
        self._updater = updater

    def set_optimizer(self, optimizer):
        """≙ kvstore.set_optimizer (server-side optimizer in dist mode)."""
        self._optimizer = optimizer

    def save_optimizer_states(self, fname, dump_optimizer=False):
        states = {k: _to_np_state(s) for k, s in self._opt_states.items()}
        payload = (states, self._optimizer) if dump_optimizer else states
        with _fault.atomic_output(fname) as f:
            pickle.dump(payload, f)

    def load_optimizer_states(self, fname):
        with open(fname, "rb") as f:
            data = pickle.load(f)
        if isinstance(data, tuple):
            data, self._optimizer = data
        self._opt_states = {k: _from_np_state(s) for k, s in data.items()}

    def barrier(self):
        """≙ KVStore::Barrier: local completion + (in dist mode) a real
        cross-process rendezvous. A dead peer would hang the rendezvous
        forever; set MXNET_KVSTORE_BARRIER_TIMEOUT (seconds; legacy alias
        MXNET_KV_BARRIER_TIMEOUT) to abort with a typed `BarrierTimeout`
        NAMING the ranks that never announced their arrival, instead of
        hanging. Arrival is announced through the jax.distributed
        coordinator's KV store before the rendezvous, so a stalled barrier
        can attribute WHICH peer is missing; when no coordinator store is
        reachable the error still fires, with `missing_ranks=[]`. The
        rendezvous runs in a watcher thread, so the timeout works off the
        main thread too (the old watchdog was main-thread-preemptive
        only)."""
        from ..ndarray import waitall
        waitall()
        if not self._dist_active():
            return
        timeout = get_env("MXNET_KVSTORE_BARRIER_TIMEOUT", typ=float)
        if timeout is None:
            timeout = get_env("MXNET_KV_BARRIER_TIMEOUT", typ=float)
        seq = _next_barrier_seq()
        # announce UNCONDITIONALLY (one cheap best-effort key_value_set):
        # a peer whose own timeout env is unset must still be attributable
        # as present when some OTHER rank's barrier times out
        self._barrier_announce(seq)
        if timeout is None or timeout <= 0:
            self._barrier_sync(seq)
            self._barrier_retract(seq)
            return
        done = threading.Event()
        errs = []

        def _rendezvous():
            try:
                self._barrier_sync(seq)
            except Exception as e:   # surfaced to the caller below
                errs.append(e)
            finally:
                done.set()

        t = threading.Thread(target=_rendezvous, daemon=True,
                             name=f"mx-kv-barrier-{seq}")
        t.start()
        if not done.wait(timeout):
            missing = self._barrier_missing_ranks(seq)
            who = (f"rank(s) {', '.join(map(str, missing))} never arrived"
                   if missing else
                   "missing ranks unknown (no coordinator KV store)")
            # the abandoned daemon thread stays blocked in the rendezvous;
            # the job is about to be torn down/restarted, which is the only
            # way out of a half-entered cross-process barrier anyway
            raise BarrierTimeout(
                f"kvstore barrier #{seq} timed out after {timeout:.3g}s; "
                f"{who}", missing_ranks=missing)
        self._barrier_retract(seq)
        if errs:
            raise errs[0]

    def _barrier_sync(self, seq):
        from jax.experimental import multihost_utils
        # seq-suffixed name: a count mismatch between processes surfaces as
        # a loud coordinator error instead of silently pairing two
        # different barriers
        multihost_utils.sync_global_devices(f"mx_kvstore_barrier_{seq}")

    @staticmethod
    def _coordinator_client():
        """The jax.distributed coordinator KV client, or None (single
        process, or a jax without the internal handle)."""
        try:
            from jax._src import distributed
            return distributed.global_state.client
        except Exception:
            return None

    def _barrier_announce(self, seq):
        """Best-effort arrival announcement for stall attribution."""
        client = self._coordinator_client()
        if client is None:
            return
        try:
            client.key_value_set(f"mx/barrier/{seq}/{self.rank}", "1")
        except Exception:
            pass

    def _barrier_retract(self, seq):
        """Best-effort cleanup after a COMPLETED rendezvous: each rank
        deletes its own announcement so the coordinator store doesn't
        grow one key per rank per barrier for the life of the job."""
        client = self._coordinator_client()
        if client is None:
            return
        try:
            client.key_value_delete(f"mx/barrier/{seq}/{self.rank}")
        except Exception:
            pass

    def _barrier_missing_ranks(self, seq):
        """Ranks with no arrival announcement for barrier `seq` (self
        always announced). Empty when attribution is impossible."""
        client = self._coordinator_client()
        if client is None:
            return []
        present = set()
        try:
            # one directory read for every announced rank (newer jax also
            # has key_value_try_get; dir_get exists on every jaxlib with
            # a coordinator client)
            entries = client.key_value_dir_get(f"mx/barrier/{seq}/")
            for k, _v in entries:
                tail = str(k).rsplit("/", 1)[-1]
                if tail.isdigit():
                    present.add(int(tail))
        except Exception:
            return []
        missing = [r for r in range(self.num_workers)
                   if r not in present]
        if self.rank in missing:
            # we DID announce — the store cannot be read back at all, so
            # per-rank attribution would be noise, not signal
            return []
        return missing

    def _send_command_to_servers(self, head, body):
        pass  # no server processes in the SPMD runtime

    def __repr__(self):
        return f"KVStore(type={self.type}, keys={len(self._store)})"


def _pairs(key, value):
    if isinstance(key, (list, tuple)):
        return list(key), list(value)
    return [key], [value]


def _one(v):
    return v[0] if isinstance(v, (list, tuple)) else v


def _key_int(k):
    try:
        return int(k)
    except (TypeError, ValueError):
        return k


def _aggregate(v):
    """Sum a list of per-device gradients (≙ Comm::Reduce). With SPMD
    sharding there is exactly one global array — the psum already happened
    inside the step function."""
    if not isinstance(v, (list, tuple)):
        return v
    if len(v) == 1:
        return v[0]
    out = v[0]
    for x in v[1:]:
        out = out + x
    return out


def _to_np_state(s):
    if s is None:
        return None
    if isinstance(s, tuple):
        return tuple(_to_np_state(x) for x in s)
    return s.asnumpy()


def _from_np_state(s):
    from ..ndarray import array
    if s is None:
        return None
    if isinstance(s, tuple):
        return tuple(_from_np_state(x) for x in s)
    return array(s)
