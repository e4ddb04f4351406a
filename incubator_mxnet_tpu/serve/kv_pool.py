"""Slotted KV-cache pool for the continuous-batching decode engine.

vLLM-style slot memory rebuilt JAX-native (PAPER.md's CachedOp/imperative
survey: state must live as plain sharded buffers a single compiled program
reads and writes, never as per-request Python objects the tracer sees):

  * ONE pair of fixed-shape device buffers carved at startup —
    `k`/`v` of shape `(max_slots + 1, layers, max_len, heads, head_dim)`.
    Slot granularity: each admitted request claims one row (its whole
    `max_len` page); row `max_slots` is the GARBAGE ROW, a write target
    for the pad lanes of a fixed-shape scatter (a prefill program always
    writes `P` rows — inactive lanes land in garbage instead of branching,
    which would retrace).
  * Claim/free is pure host bookkeeping under one lock: the buffers never
    reallocate, so join/leave can never change a compiled program's
    shapes — the zero-retrace contract of `serve.continuous`.
  * Stale bytes are a CORRECTNESS boundary, not a hygiene one: a freed
    slot's cache rows are NOT zeroed (that would cost a device write per
    retire). Instead the attention masks in `serve.continuous` clamp
    every read to `[0, cur_len]` of the CURRENT request, so a reused slot
    cannot read its predecessor's cache. `poison()` exists so tests can
    prove that: fill the slab with a sentinel, run a request through a
    reused slot, and check the output matches a fresh-pool reference
    bit-for-bit (tests/test_continuous.py).
  * `dtype="int8"` opts a pool into QUANTIZED KV storage: int8 slabs plus
    float32 per-(slot, layer, position) scale buffers (`k_scale` /
    `v_scale`). Each written position is quantized by its absmax over
    (heads, head_dim) — a position's scale is final the moment its KV is
    written, so slot reuse never requantizes and a stale scale is exactly
    as unreachable as a stale KV row (the same `[0, cur_len]` mask
    governs both; `poison()` poisons the scales too so tests can prove
    it). ~3-4x more slots per HBM byte (`slots_per_gb()`).

A pool is built from a CACHE SPEC: a list of `CacheLeaf`s, each naming
one device array with a row per slot (`shape` is one row's), its dtype and
its `kind` — `full` (grows with the request, one position a token, read
through a `[0, cur_len]` mask), `ring` (the newest `positions` positions,
position p at `p % positions`) or `state` (a recurrent state, rewritten
whole at every token). The model declares the spec and `new_pool` hands it
over; the classic decoder's spec is the `k`/`v` slab pair (plus the int8
scales) that `layers=..., heads=...` describe. The unzeroed-free-slot
contract above is the `full` kind's: a `ring` hides stale positions by the
same arithmetic, but a `state` leaf has no mask, so the model's programs
must overwrite it before they read it (`HybridDecoder`: the prefill at
offset 0 starts every state from zero; tests/test_hybrid_decoder.py
poison-fills every leaf to show it).

Exhaustion is typed: `claim()` past capacity raises `SlotsFullError`
(a `ServeError`), the admission signal the engine's deadline-aware
scheduler acts on instead of blocking.

Counters: `KVPOOL_STATS` ("kvpool" stats group — `profiler`-style surface
via `serve.kv_pool.kvpool_stats()`; catalog in docs/OBSERVABILITY.md).
"""
from __future__ import annotations

import threading
from collections import namedtuple

import numpy as _np

from ..base import get_env
from ..telemetry.registry import REGISTRY, stats_group as _stats_group
from .batcher import ServeError

__all__ = ["SlotsFullError", "CacheKindError", "CacheLeaf", "CACHE_KINDS", "KVCachePool",
           "KVPOOL_STATS", "kvpool_stats"]

CACHE_KINDS = ("full", "ring", "state")

# one leaf of a cache spec: `shape` is ONE ROW's shape (the pool adds the
# slot axis in front), `positions` the length of the axis that a request
# fills token by token (a `full` leaf's max_len, a `ring`'s capacity; 0
# for a `state`, which is live whole from the first token)
CacheLeaf = namedtuple("CacheLeaf", "name shape dtype kind positions")


def _itemsize(dtype):
    import numpy as _np
    import ml_dtypes  # noqa: F401  (bf16 dtype string resolution)
    try:
        return _np.dtype(dtype).itemsize
    except TypeError:
        return 2      # bfloat16


def _numel(shape):
    n = 1
    for d in shape:
        n *= d
    return n


class SlotsFullError(ServeError):
    """`claim()` found no free KV slot: the pool is at capacity. The
    engine's admission loop treats this as "stay queued" (and fails the
    request only when its deadline expires); direct callers get a typed,
    actionable error instead of an index out of range."""


class CacheKindError(ServeError):
    """An engine option that treats a request's past as rows of K and V
    (prefix reuse, speculation, int8 storage) met a model whose cache
    spec holds a `ring` or `state` leaf."""


# Guards every KVPOOL_STATS mutation AND the free-list bookkeeping of all
# pools (claim/free are rare, request-scale events — one shared lock keeps
# snapshot+reset atomic exactly like serve/metrics.py's _STATS_LOCK).
_STATS_LOCK = threading.Lock()

KVPOOL_STATS = _stats_group("kvpool", {
    "claims": 0,       # slots successfully claimed
    "frees": 0,        # slots returned to the pool
    "exhausted": 0,    # claim() attempts that found no free slot
}, lock=_STATS_LOCK,
    help="KV-cache slot-pool counters (serve.kv_pool.kvpool_stats)")


def kvpool_stats(reset=False):
    """Process-wide KV-pool counter snapshot (atomic with the optional
    reset, the serve_stats() contract)."""
    return KVPOOL_STATS.snapshot(reset=reset)


# the single biggest planned allocation in serving, previously invisible:
# set at every carve (allocate/reallocate/poison) so dashboards see the
# slab without holding a pool reference. Level, not
# flow — survives snapshot(reset=True). With several pools alive it holds
# the most recent carve; per-pool numbers live on pool.stats().
_SLAB_GAUGE = REGISTRY.gauge(
    "kvpool.slab_bytes",
    help="bytes of the most recently carved KV slab pair (k+v, incl. "
         "the garbage row)")


def _note_slab(pool):
    """Stamp the gauge and attribute the slab buffers to the census
    owner `kv_pool` (mx.inspect.memory). Attribution must never be able
    to break serving — failures are swallowed."""
    try:
        _SLAB_GAUGE.set(pool.nbytes())
        from ..inspect import memory as _mem
        _mem.register(tuple(pool.leaves.values()), owner="kv_pool")
    except Exception:
        pass


class KVCachePool:
    """Preallocated KV-cache slab + slot claim/free bookkeeping.

    ::

        pool = KVCachePool(max_slots=8, layers=2, max_len=128,
                           heads=4, head_dim=16)
        slot = pool.claim()          # 0 <= slot < max_slots
        ...                          # compiled steps read/write pool.k/v
        pool.free(slot)

    The device buffers `k` and `v` are plain jax arrays the engine's
    donated step programs consume and replace (`swap_buffers`), so
    updates are in-place on accelerators. `garbage_row == max_slots` is
    the scatter target for inactive lanes.

    Thread safety: `claim`/`free`/`free_count`/`in_use` take the module
    lock (the engine claims on its scheduler thread while tests hammer
    from many); buffer access is single-writer by the engine contract
    (exactly one scheduler thread runs the compiled steps).
    """

    def __init__(self, max_slots=None, *, layers=None, max_len=None,
                 heads=None, head_dim=None, dtype="float32", spec=None,
                 allocate=True):
        self.max_slots = int(
            max_slots if max_slots is not None
            else get_env("MXNET_SERVE_MAX_SLOTS", 8, typ=int))
        if self.max_slots < 1:
            raise ServeError("KVCachePool needs max_slots >= 1")
        self.dtype = str(dtype)
        # int8 = quantized storage: slabs hold int8 codes, the paired
        # k_scale/v_scale buffers hold one f32 dequant factor per
        # written (slot, layer, position)
        self.quantized = self.dtype == "int8"
        # the classic layout is the decoder's K/V slab pair, handed to the
        # programs as two arguments; a model's own spec rides as one dict
        self.classic = spec is None
        if self.classic:
            self.layers = int(layers)
            self.max_len = int(max_len)
            self.heads = int(heads)
            self.head_dim = int(head_dim)
            row = (self.layers, self.max_len, self.heads, self.head_dim)
            spec = [CacheLeaf("k", row, self.dtype, "full", self.max_len),
                    CacheLeaf("v", row, self.dtype, "full", self.max_len)]
            if self.quantized:
                spec += [CacheLeaf(n, row[:2], "float32", "full",
                                   self.max_len)
                         for n in ("k_scale", "v_scale")]
        self.spec = tuple(CacheLeaf(*leaf) for leaf in spec)
        for leaf in self.spec:
            if leaf.kind not in CACHE_KINDS:
                raise ServeError(f"cache leaf {leaf.name!r}: unknown kind "
                                 f"{leaf.kind!r} (one of {CACHE_KINDS})")
        # a row's bytes by (kind, positions): what `bytes_by_kind` sums
        self._row_bytes = {}
        for leaf in self.spec:
            key = (leaf.kind, leaf.positions)
            self._row_bytes[key] = self._row_bytes.get(key, 0) \
                + _numel(leaf.shape) * _itemsize(leaf.dtype)
        # LIFO free list: a just-freed slot is re-claimed first, which is
        # exactly what the poison-fill reuse test needs to exercise
        self._free = list(range(self.max_slots - 1, -1, -1))
        self._claimed = set()
        self.leaves = {}
        if allocate:
            self._allocate()

    # the classic slab pair by name (tests and the slot canary write them)
    k = property(lambda self: self.leaves.get("k"),
                 lambda self, a: self.leaves.__setitem__("k", a))
    v = property(lambda self: self.leaves.get("v"),
                 lambda self, a: self.leaves.__setitem__("v", a))
    k_scale = property(lambda self: self.leaves.get("k_scale"),
                       lambda self, a: self.leaves.__setitem__("k_scale", a))
    v_scale = property(lambda self: self.leaves.get("v_scale"),
                       lambda self, a: self.leaves.__setitem__("v_scale", a))

    # -- buffers -----------------------------------------------------------
    @property
    def shape(self):
        """Slab shape incl. the garbage row (the compiled-program view)."""
        return (self.max_slots + 1, self.layers, self.max_len,
                self.heads, self.head_dim)

    @property
    def scale_shape(self):
        """Per-position dequant-scale buffer shape (quantized pools)."""
        return (self.max_slots + 1, self.layers, self.max_len)

    @property
    def garbage_row(self):
        """Scatter target for a fixed-shape step's inactive lanes."""
        return self.max_slots

    def kinds(self):
        """The cache kinds this pool's spec holds."""
        return {leaf.kind for leaf in self.spec}

    def _allocate(self):
        import jax.numpy as jnp
        self.leaves = {
            leaf.name: jnp.zeros((self.max_slots + 1,) + tuple(leaf.shape),
                                 dtype=leaf.dtype)
            for leaf in self.spec}
        _note_slab(self)

    def buffers(self):
        """The cache arguments the step programs take after `params`, as
        a tuple: the classic pair `(k, v)` (`(slab, scales)` pairs for a
        quantized pool — the program variant is chosen by `quantized` at
        build time, so the pytree STRUCTURE is a trace-time constant), or
        the one `{name: array}` dict of a model's own spec. A program
        returns them first, in the same order, for `swap_buffers`."""
        if not self.classic:
            return (dict(self.leaves),)
        if self.quantized:
            return (self.k, self.k_scale), (self.v, self.v_scale)
        return self.k, self.v

    def avals(self):
        """`buffers()` as `jax.ShapeDtypeStruct`s (lowering without
        touching a buffer)."""
        import jax
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            self.buffers())

    def reallocate(self):
        """Replace the slab with fresh zeroed buffers. The engine's
        step-failure path needs this: the compiled programs DONATE the
        buffers, so an exception raised mid-execution leaves `k`/`v`
        pointing at already-invalidated arrays — without reallocation
        every later wave would die on 'Array has been deleted'."""
        self._allocate()

    def nbytes(self):
        """Host-visible size of every leaf incl. the garbage row
        (capacity-planning aid)."""
        return (self.max_slots + 1) * self.bytes_per_slot()

    def bytes_per_slot(self):
        """Marginal device bytes one slot row costs, all leaves."""
        return sum(self._row_bytes.values())

    def bytes_by_kind(self, lengths=None):
        """{kind: bytes}: allocated (`lengths` None, garbage row
        included), or LIVE for lanes at these cache lengths — the
        positions a `full` leaf holds so far, a `ring`'s newest
        `positions`, a `state` whole."""
        if lengths is not None:
            lengths = _np.asarray(lengths, dtype=_np.int64)
        out = {}
        for (kind, positions), row in self._row_bytes.items():
            if lengths is None:
                n = row * (self.max_slots + 1)
            elif kind == "state":
                n = row * lengths.size
            else:
                n = int(_np.minimum(lengths, positions).sum()) \
                    * row // positions
            out[kind] = out.get(kind, 0) + n
        return out

    def slots_per_gb(self):
        """KV slots one GiB of device memory buys at this pool's shape
        (int8 pools fit ~3-4x the slots of float32 at the same (layers, max_len, heads, dim))."""
        return round((1 << 30) / self.bytes_per_slot(), 2)

    def swap_buffers(self, *cache):
        """Install the step program's output buffers (the donated-update
        swap idiom: the old arrays were consumed by donation), in the
        order and structure `buffers()` hands them out."""
        if not self.classic:
            (leaves,) = cache
            self.leaves = dict(leaves)
        elif self.quantized:
            (self.k, self.k_scale), (self.v, self.v_scale) = cache
        else:
            self.k, self.v = cache
        _note_slab(self)

    def _sentinels(self, value):
        """{leaf: the poison value}: on a quantized pool the codes are
        set to 1 and the SCALES to `value`, so a stale-scale read is as
        loud as a stale-code one."""
        return {leaf.name: 1 if leaf.dtype == "int8" else value
                for leaf in self.spec}

    def poison(self, value=1e9):
        """Overwrite EVERY leaf whole with a sentinel. Test hook for the
        slot-reuse isolation contract: after poisoning, any read that
        escapes the `[0, cur_len]` mask — or a recurrent state that a
        new tenant did not start from zero — shows up as the sentinel in
        the output. Never called on the serving path."""
        import jax.numpy as jnp
        fill = self._sentinels(value)
        self.leaves = {n: jnp.full(a.shape, fill[n], dtype=a.dtype)
                       for n, a in self.leaves.items()}
        _note_slab(self)

    def poison_slot(self, slot, value=1e9):
        """`poison()` at slot granularity: overwrite ONE row of every
        leaf with the sentinel, leaving every other slot's live cache
        intact. Test hook for the shared-prefix isolation contract:
        poison a FREED prefix-cache row, keep serving, and any tenant
        that could still read it shows the sentinel. Never called on the
        serving path."""
        import jax.numpy as jnp
        slot = int(slot)
        if not 0 <= slot <= self.max_slots:
            raise ServeError(
                f"slot {slot} outside [0, {self.max_slots}]")
        fill = self._sentinels(value)
        self.leaves = {
            n: a.at[slot].set(jnp.asarray(fill[n], dtype=a.dtype))
            for n, a in self.leaves.items()}
        _note_slab(self)

    # -- slot bookkeeping --------------------------------------------------
    def claim(self):
        """Take a free slot (int in [0, max_slots)); raises SlotsFullError
        when the pool is exhausted."""
        with _STATS_LOCK:
            if not self._free:
                KVPOOL_STATS["exhausted"] += 1
                raise SlotsFullError(
                    f"all {self.max_slots} KV slots are claimed")
            slot = self._free.pop()
            self._claimed.add(slot)
            KVPOOL_STATS["claims"] += 1
            return slot

    def free(self, slot):
        """Return a slot. Double-free (or freeing an unclaimed slot) is a
        bookkeeping bug upstream and raises ServeError rather than
        silently handing one slot to two requests."""
        slot = int(slot)
        with _STATS_LOCK:
            if slot not in self._claimed:
                raise ServeError(
                    f"KV slot {slot} is not claimed (double free?)")
            self._claimed.remove(slot)
            self._free.append(slot)
            KVPOOL_STATS["frees"] += 1

    def free_count(self):
        with _STATS_LOCK:
            return len(self._free)

    def in_use(self):
        with _STATS_LOCK:
            return sorted(self._claimed)

    def stats(self):
        """Plain-data snapshot of this pool's occupancy."""
        with _STATS_LOCK:
            used = len(self._claimed)
        return {"max_slots": self.max_slots, "in_use": used,
                "free": self.max_slots - used,
                "dtype": self.dtype,
                "slots_per_gb": self.slots_per_gb(),
                "slab_bytes": self.nbytes() if self.leaves else 0}
