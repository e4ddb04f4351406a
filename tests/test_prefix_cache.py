"""serve.prefix_cache: ref-counted shared-prefix KV cache bookkeeping.

Contracts under test (ISSUE 19 acceptance):
  * block-quantized longest-prefix match, capped at len(prompt)-1 so at
    least one suffix token always remains to prefill
  * a hash hit is NEVER trusted: the stored token block is compared
    against the prompt, a mismatch counts `prefix.collisions` and falls
    through to shorter prefixes / recompute — wrong KV is impossible by
    construction (forced via the `_hash_override` test hook)
  * ref-counted pinning: LRU eviction can never reclaim an entry whose
    refcount > 0, `clear()` refuses with live refs, and releasing an
    unheld entry is a typed `PrefixCacheError` (double release)
  * `PREFIX_STATS` counter catalog: "hits", "misses", "cached_tokens",
    "evictions", "collisions" (docs/OBSERVABILITY.md `prefix.*`)

Pure host bookkeeping — no jax, no engine; the engine-level integration
(row copies, suffix prefill, budget billing) lives in
tests/test_continuous.py.
"""
import numpy as np
import pytest

from incubator_mxnet_tpu import serve
from incubator_mxnet_tpu.serve import prefix_cache as pc
from incubator_mxnet_tpu.serve.prefix_cache import (
    PREFIX_STATS, PrefixCache, PrefixCacheError, prefix_stats,
    rolling_hash)


def _prompt(*tokens):
    return np.asarray(tokens, dtype=np.int32)


# ---------------------------------------------------------------------------
# hashing + block-quantized match
# ---------------------------------------------------------------------------
def test_rolling_hash_is_prefix_consistent_and_order_sensitive():
    toks = [5, 9, 1, 7]
    assert rolling_hash(toks) == rolling_hash(np.asarray(toks))
    assert rolling_hash(toks) != rolling_hash([9, 5, 1, 7])
    # leading token id 0 must not hash like the empty prefix
    assert rolling_hash([0]) != rolling_hash([])


def test_match_returns_longest_verified_block_prefix():
    cache = PrefixCache(block=4, rows=[10, 11])
    p = _prompt(*range(1, 11))                    # 10 tokens
    short_row, short_len = cache.insert(p[:4])    # 4-token entry
    row, length = cache.insert(p)                 # 8 of 10 tokens
    assert {short_row, row} == {10, 11}
    assert (short_len, length) == (4, 8)          # what the copy moves
    assert [e[0] for e in cache.entries()] == [4, 8]
    before = prefix_stats()
    entry, n = cache.match(p)
    assert entry is not None and n == 8 and entry.refs == 1
    # a prompt equal to an entry's tokens may reuse at most len-1 of
    # them (one suffix token must remain to prefill), so the walk
    # falls back to the SHORTER cached entry
    e2, n2 = cache.match(p[:8])
    assert n2 == 4 and e2.row == short_row
    after = prefix_stats()
    assert after["hits"] - before["hits"] == 2
    assert after["cached_tokens"] - before["cached_tokens"] == 12
    cache.release(entry)
    cache.release(e2)
    # shorter-than-one-block prompts can never match (and misses count)
    assert cache.match(_prompt(1, 2, 3)) == (None, 0)
    assert prefix_stats()["misses"] - after["misses"] == 1


def test_match_acquire_false_is_a_free_peek():
    cache = PrefixCache(block=2, rows=[0])
    cache.insert(_prompt(1, 2, 3, 4))
    before = prefix_stats()
    entry, n = cache.match(_prompt(1, 2, 3, 4, 5), acquire=False)
    assert n == 4 and entry.refs == 0
    after = prefix_stats()
    assert after["hits"] == before["hits"]
    assert after["cached_tokens"] == before["cached_tokens"]


# ---------------------------------------------------------------------------
# hash-collision safety (the _hash_override hook)
# ---------------------------------------------------------------------------
def test_hash_collision_is_verified_rejected_and_counted():
    cache = PrefixCache(block=4, rows=[7])
    cache._hash_override = lambda tokens: 42      # every block collides
    assert cache.insert(_prompt(1, 2, 3, 4)) == (7, 4)
    before = prefix_stats()
    # same hash bucket, different tokens: verify MUST reject the entry
    # and fall through to a miss (recompute), never reuse wrong KV
    entry, n = cache.match(_prompt(9, 9, 9, 9, 5))
    assert (entry, n) == (None, 0)
    after = prefix_stats()
    assert after["collisions"] - before["collisions"] == 1
    assert after["misses"] - before["misses"] == 1
    # the true owner of the bucket still hits, through the collision
    entry, n = cache.match(_prompt(1, 2, 3, 4, 5))
    assert n == 4 and entry.row == 7
    cache.release(entry)


def test_collision_on_insert_appends_to_chain_not_overwrites():
    cache = PrefixCache(block=2, rows=[0, 1])
    cache._hash_override = lambda tokens: 13
    assert cache.insert(_prompt(1, 2)) is not None
    assert cache.insert(_prompt(3, 4)) is not None   # same bucket
    ea, na = cache.match(_prompt(1, 2, 9))
    eb, nb = cache.match(_prompt(3, 4, 9))
    assert na == nb == 2 and ea.row != eb.row
    cache.release(ea)
    cache.release(eb)


# ---------------------------------------------------------------------------
# ref-counted pinning vs LRU eviction
# ---------------------------------------------------------------------------
def test_lru_evicts_only_unpinned_and_refuses_when_all_pinned():
    cache = PrefixCache(block=2, rows=[0, 1])
    pa = _prompt(1, 2)
    pb = _prompt(3, 4)
    assert cache.insert(pa) is not None
    assert cache.insert(pb) is not None
    # pin A (the LRU-older entry); publishing C must evict B, never A
    ea, _ = cache.match(_prompt(1, 2, 9))
    before = prefix_stats()
    rc = cache.insert(_prompt(5, 6))
    assert rc is not None
    assert prefix_stats()["evictions"] - before["evictions"] == 1
    lens_rows = cache.entries()
    assert (2, ea.row, 1) in lens_rows
    assert cache.match(_prompt(3, 4, 9)) == (None, 0)   # B is gone
    # pin C too: every row referenced -> insert REFUSES, no eviction
    ec, _ = cache.match(_prompt(5, 6, 9))
    before = prefix_stats()
    assert cache.insert(_prompt(7, 8)) is None
    assert prefix_stats()["evictions"] == before["evictions"]
    cache.release(ea)
    cache.release(ec)


def test_reinsert_of_cached_prefix_touches_lru_instead_of_duplicating():
    cache = PrefixCache(block=2, rows=[0, 1])
    assert cache.insert(_prompt(1, 2)) is not None
    assert cache.insert(_prompt(3, 4)) is not None
    # re-publish A: no new row, but A becomes most-recently-used...
    assert cache.insert(_prompt(1, 2)) is None
    # ...so the next eviction takes B
    assert cache.insert(_prompt(5, 6)) is not None
    assert cache.match(_prompt(1, 2, 9), acquire=False)[1] == 2
    assert cache.match(_prompt(3, 4, 9), acquire=False) == (None, 0)


# ---------------------------------------------------------------------------
# lifecycle misuse is typed
# ---------------------------------------------------------------------------
def test_double_release_raises_typed_prefix_cache_error():
    cache = PrefixCache(block=2, rows=[0])
    cache.insert(_prompt(1, 2))
    entry, _ = cache.match(_prompt(1, 2, 3))
    cache.release(entry)
    with pytest.raises(PrefixCacheError, match="double release"):
        cache.release(entry)
    # typed: admission/retire paths catch it as a ServeError
    assert issubclass(PrefixCacheError, serve.ServeError)


def test_clear_refuses_with_live_refs_then_reclaims_rows():
    cache = PrefixCache(block=2, rows=[4, 5])
    cache.insert(_prompt(1, 2))
    entry, _ = cache.match(_prompt(1, 2, 3))
    with pytest.raises(PrefixCacheError, match="live reference"):
        cache.clear()
    cache.release(entry)
    cache.clear()
    assert cache.entries() == []
    # both rows are claimable again
    assert cache.insert(_prompt(1, 2)) is not None
    assert cache.insert(_prompt(3, 4)) is not None


# ---------------------------------------------------------------------------
# stats plumbing
# ---------------------------------------------------------------------------
def test_prefix_stats_group_keys_and_reset():
    snap = prefix_stats()
    assert set(snap) == {"hits", "misses", "cached_tokens", "evictions",
                         "collisions"}
    assert PREFIX_STATS is not None
    # snapshot+reset is atomic (the serve_stats contract)
    prefix_stats(reset=True)
    z = prefix_stats()
    assert all(v == 0 for v in z.values())


def test_cache_stats_snapshot_tracks_residency_and_refs():
    cache = PrefixCache(block=4, rows=[0, 1, 2])
    cache.insert(_prompt(*range(1, 9)))
    entry, _ = cache.match(_prompt(*range(1, 10)))
    st = cache.stats()
    assert st == {"block": 4, "capacity": 3, "entries": 1,
                  "resident_tokens": 8, "live_refs": 1}
    cache.release(entry)
    assert cache.stats()["live_refs"] == 0


def test_prefix_family_dotted_telemetry_surface():
    """Every PREFIX_STATS counter surfaces under the dotted `prefix.*`
    telemetry names (the mxlint `stats-family-untested` coverage rule
    requires the family's dotted export to be pinned by a test)."""
    from incubator_mxnet_tpu import telemetry
    before = telemetry.snapshot()
    for name in ("prefix.hits", "prefix.misses", "prefix.cached_tokens",
                 "prefix.evictions", "prefix.collisions"):
        assert name in before, name
    cache = PrefixCache(block=2, rows=[0])
    cache.insert(_prompt(1, 2, 3, 4))
    entry, n = cache.match(_prompt(1, 2, 3, 4, 5))  # acquiring lookup
    if entry is not None:
        cache.release(entry)
    after = telemetry.snapshot()
    # a live lookup moved the family's dotted counters, not just the dict
    assert (after["prefix.hits"] + after["prefix.misses"]
            > before["prefix.hits"] + before["prefix.misses"])
