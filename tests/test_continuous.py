"""serve.continuous: iteration-level batching over slotted KV-cache pools.

Contracts under test (ISSUE 14 acceptance):
  * mixed ragged traffic through the engine produces token-for-token the
    same outputs as a scheduling-free single-slot reference decode
  * ZERO retraces after warmup over any join/leave pattern, observed via
    the PR-3 `programs_compiled` counter AND `compile_cache_size()`
  * KV-slot lifecycle: claim/free under concurrent hammering, typed
    `SlotsFullError` on exhaustion, and slot REUSE cannot read a prior
    request's cache (poison-fill + value check — the mask contract)
  * deadline-aware admission: waiting deadline-holders get slots before
    FIFO order; a deadline that expires while WAITING fails fast
  * one request = ONE trace across its N iterations (serve.request root
    with serve.prefill / serve.decode children, same trace id)
  * `MXNET_COMPILE_CACHE_DIR` makes a warm replica skip compilation
  * PR-3 pad-row mask regression: outputs that cannot be pad-masked
    fail typed instead of leaking pad garbage (tests/test_serve.py side
    covers the server; here the engine never pads replies by design)

ISSUE 19 additions (shared-prefix KV cache + chunked prefill; cache
bookkeeping unit tests live in tests/test_prefix_cache.py):
  * prompts longer than `prefill_window` stream through window-sized
    chunks (the engine's extent ladder: one program a rung for a model
    whose chunk reads its cache densely, ONE program for `CachedDecoder`,
    whose read follows the live blocks), token-exact and zero-retrace
  * a prefix-cache hit copies cached KV and prefills ONLY the suffix:
    billing, EDF post-cache-cost ranking, and poison-fill isolation of
    the pinned cache rows all hold; hit / int8-hit outputs match the
    explicit `reference_generate(cached_prefix_len=...)` oracle
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from hlo_branches import buffers_of_at_least, slab_slices

from incubator_mxnet_tpu import profiler, serve
from incubator_mxnet_tpu.serve.kv_pool import KVPOOL_STATS


CFG = dict(vocab=64, embed=32, layers=2, heads=4, head_dim=8, max_len=48)


@pytest.fixture(scope="module")
def decoder():
    """One small CachedDecoder + a weight-sharing reference twin (its own
    jits, so reference calls never touch the engine's compile caches)."""
    cfg = serve.DecoderConfig(**CFG)
    model = serve.CachedDecoder(cfg, seed=3)
    ref = serve.CachedDecoder(cfg, params=model.params)
    return model, ref


@pytest.fixture(scope="module", params=["classic", "sparse_moe"])
def any_decoder(request, decoder):
    """The contract tests that ask nothing of a model beyond the engine's
    protocol, over the classic decoder and over the latent-attention,
    sparse-attention, sparse-expert one (`models.sparse_moe_decoder`; the
    contexts here pass its `index_topk` of 8, so its decode chooses)."""
    if request.param == "classic":
        return decoder
    from incubator_mxnet_tpu.models import sparse_moe_decoder as sm
    cfg = sm.SparseMoEConfig(vocab=64, max_len=48)
    model = sm.SparseMoEDecoder(cfg, seed=3)
    return model, sm.SparseMoEDecoder(cfg, params=model.params)


def _workload(n, seed=0, vocab=64, max_new_hi=20):
    rng = np.random.RandomState(seed)
    return [(rng.randint(1, vocab, size=rng.randint(2, 12)).tolist(),
             int(rng.randint(1, max_new_hi))) for _ in range(n)]


# ---------------------------------------------------------------------------
# correctness + zero retraces
# ---------------------------------------------------------------------------
def test_engine_matches_reference_and_never_retraces(any_decoder):
    model, ref = any_decoder
    work = _workload(16)
    before = profiler.serve_stats()
    with serve.ContinuousEngine(model, max_slots=4, decode_steps=3) as eng:
        warm_ccs = eng.compile_cache_size()
        warm_programs = profiler.serve_stats()["programs_compiled"]
        futs = [eng.submit(p, m) for p, m in work]
        outs = [f.result(timeout=120) for f in futs]
        st = eng.stats()
        # join/leave churned the mixed batch every iteration; the two
        # compiled programs must have been enough for all of it
        assert eng.assert_no_retraces() == 0
        assert eng.compile_cache_size() == warm_ccs
        assert profiler.serve_stats()["programs_compiled"] == warm_programs
    for (p, m), o in zip(work, outs):
        np.testing.assert_array_equal(
            o, ref.reference_generate(p, m),
            err_msg=f"engine output diverged for prompt {p} max_new {m}")
        assert len(o) == m
    # decode_* counter family moved (stats-key + catalog contract):
    # "decode_iterations", "decode_tokens", "decode_prefill_tokens",
    # "decode_admitted", "decode_retired" aggregate process-wide
    after = profiler.serve_stats()
    assert after["decode_retired"] - before["decode_retired"] == 16
    assert after["decode_admitted"] - before["decode_admitted"] == 16
    assert after["decode_tokens"] - before["decode_tokens"] \
        == sum(m for _, m in work) - 16      # first tokens come from prefill
    assert after["decode_prefill_tokens"] - before["decode_prefill_tokens"] \
        == sum(len(p) for p, _ in work)
    assert after["decode_iterations"] > before["decode_iterations"]
    assert st["decode_tokens_per_sec"] > 0
    assert st["ttft_p50_ms"] is not None
    assert json.dumps(st)


def test_multi_step_decode_equals_single_step(any_decoder):
    """decode_steps is pure amortization: K=1 and K=6 produce identical
    tokens (the scan replays the exact single-step math)."""
    model, ref = any_decoder
    work = _workload(6, seed=5)
    outs = {}
    for steps in (1, 6):
        with serve.ContinuousEngine(model, max_slots=2,
                                    decode_steps=steps) as eng:
            outs[steps] = [eng.generate(p, m, timeout=120)
                           for p, m in work]
    for a, b in zip(outs[1], outs[6]):
        np.testing.assert_array_equal(a, b)


def test_eos_stops_generation_and_frees_early(any_decoder):
    model, ref = any_decoder
    prompt, max_new = [7, 3, 19], 16
    base = ref.reference_generate(prompt, max_new)
    # pick a token the model actually emits mid-sequence as the eos
    eos = int(base[len(base) // 2])
    expect = ref.reference_generate(prompt, max_new, eos_id=eos)
    assert len(expect) < len(base)
    eng = serve.ContinuousEngine(model, max_slots=2, decode_steps=4,
                                 eos_id=eos).start()
    try:
        out = eng.generate(prompt, max_new, timeout=120)
    finally:
        eng.close()
    np.testing.assert_array_equal(out, expect)
    assert out[-1] == eos


def test_eos_mid_wave_keeps_exact_token_accounting(any_decoder):
    """Regression: eos zeroes a lane's remaining budget in-scan, so
    deriving per-lane emission from the steps_left delta OVERCOUNTED
    (inflating cache_len, appending garbage 0-tokens, and keeping the
    slot past eos). The scan now counts emitted tokens exactly."""
    model, ref = any_decoder
    prompt, max_new = [7, 3, 19], 16
    base = ref.reference_generate(prompt, max_new)
    eos = int(base[len(base) // 2])
    expect = ref.reference_generate(prompt, max_new, eos_id=eos)
    # decode_steps far larger than the post-eos remainder: eos fires
    # mid-wave with budget left
    eng = serve.ContinuousEngine(model, max_slots=2, decode_steps=8,
                                 eos_id=eos).start()
    try:
        out = eng.generate(prompt, max_new, timeout=120)
        st = eng.stats()
    finally:
        eng.close()
    np.testing.assert_array_equal(out, expect)
    # exact accounting: the only decode tokens are the reply minus the
    # prefill-emitted first token — no phantom post-eos tokens
    assert st["decode_tokens"] == len(out) - 1
    assert st["replies"] == 1 and st["pool"]["in_use"] == 0


def test_page_full_token_count_is_decode_steps_invariant(decoder):
    """Regression: the per-wave page-space cap allowed one token more
    than _finished/reference at a full page, so the token COUNT depended
    on decode_steps. K must be pure amortization."""
    cfg = serve.DecoderConfig(**dict(CFG, max_len=12))
    model = serve.CachedDecoder(cfg, seed=3)
    ref = serve.CachedDecoder(cfg, params=model.params)
    prompt, max_new = [7, 3, 19], 30           # page-limited, not count-
    expect = ref.reference_generate(prompt, max_new)
    for steps in (1, 7):
        with serve.ContinuousEngine(model, max_slots=2,
                                    decode_steps=steps) as eng:
            out = eng.generate(prompt, max_new, timeout=120)
        np.testing.assert_array_equal(
            out, expect, err_msg=f"decode_steps={steps} diverged at "
            f"page-full from the reference")


def test_step_failure_after_donation_engine_keeps_serving(decoder):
    """Regression: the compiled steps DONATE the pool buffers; an
    exception raised mid-execution (after donation) used to leave
    pool.k/v invalidated, killing every later wave. The failure path
    now reallocates the slab."""
    model, ref = decoder
    eng = serve.ContinuousEngine(model, max_slots=2,
                                 decode_steps=2).start()
    real = eng._decode_prog

    def boom_after_donation(params, k, v, *rest):
        real(params, k, v, *rest)    # consumes (donates) k and v
        raise RuntimeError("transient failure after donation")

    try:
        eng._decode_prog = boom_after_donation
        f = eng.submit([1, 2, 3], 6)
        with pytest.raises(serve.ServeError, match="engine step failed"):
            f.result(timeout=60)
        eng._decode_prog = real
        # the engine must keep serving correct results on fresh buffers
        out = eng.generate([4, 5], 5, timeout=60)
        st = eng.stats()
    finally:
        eng.close()
    np.testing.assert_array_equal(out, ref.reference_generate([4, 5], 5))
    assert st["errors"] == 1 and st["replies"] == 1


def test_long_prompt_streams_in_window_sized_chunks(any_decoder):
    """PR-14 rejected prompts longer than `prefill_window`; chunked
    prefill streams them window-sized slices per wave instead (through
    the warmed extent ladder), token-exact and zero-retrace, while
    short prompts keep using the cheap windowed head program."""
    model, ref = any_decoder
    long_prompt = list(range(1, 40))          # 39 tokens = 3 chunks @ 16
    with serve.ContinuousEngine(model, max_slots=2,
                                prefill_window=16) as eng:
        out = eng.generate(long_prompt, 4, timeout=120)
        short = eng.generate([1, 2, 3], 4, timeout=60)
        assert eng.assert_no_retraces() == 0
    np.testing.assert_array_equal(
        out, ref.reference_generate(long_prompt, 4, window=16),
        err_msg="chunked prefill diverged from the reference")
    np.testing.assert_array_equal(
        short, ref.reference_generate([1, 2, 3], 4, window=16))


KV_POOLS = pytest.mark.parametrize("kv", [{}, {"kv_dtype": "int8"}],
                                   ids=["float32", "int8"])


@KV_POOLS
def test_classic_chunk_is_one_program_whatever_the_extent(decoder, kv):
    """The engine asks `CachedDecoder` for a rung an extent (16, 32, 48
    here) and is handed ONE program: warmed once, listed once, and
    token-exact on a prompt whose chunks end at 32 and 45 (the two upper
    rungs) and on a prefix hit's suffix at offset 8 (the first rung,
    which exists only with a prefix cache), with nothing retraced."""
    model, ref = decoder
    prompt = list(range(1, 46))               # 45 tokens = 3 chunks @ 16
    short = list(range(50, 59))               # 9 tokens: publishes 8
    suffix = short[:8] + [40, 41]             # 1 cached block of 8 + 2
    with serve.ContinuousEngine(model, max_slots=2, prefill_window=16,
                                prefix_block=8, prefix_cache_slots=2,
                                **kv) as eng:
        assert eng._chunk_extents == (16, 32, 48)
        assert len(set(eng._chunk_progs.values())) == 1
        cold = eng.generate(prompt, 3, timeout=120)
        eng.generate(short, 3, timeout=120)
        hot = eng.generate(suffix, 3, timeout=120)
        assert eng.prefix_hit_count() == 1
        assert eng.assert_no_retraces() == 0
        assert [n for n in eng.lowered_programs()
                if n.startswith("chunk_prefill")] == ["chunk_prefill[16]"]
    np.testing.assert_array_equal(
        cold, ref.reference_generate(prompt, 3, window=16, **kv))
    np.testing.assert_array_equal(
        hot, ref.reference_generate(suffix, 3, window=16,
                                    cached_prefix_len=8, **kv))


@KV_POOLS
def test_classic_chunk_program_slices_no_slab(decoder, kv):
    """The extent never reaches the traced function: the lowered chunk
    program holds no slice of a whole slab (nor of an int8 pool's
    scales), which the rung below the full extent was made of."""
    import jax
    model, _ = decoder
    eng = serve.ContinuousEngine(model, max_slots=2, prefill_window=16,
                                 prefix_block=8, prefix_cache_slots=2, **kv)
    shape = eng.pool.shape
    assert slab_slices(eng.lowered_programs()["chunk_prefill[16]"],
                       shape) == []
    # the helper does see one: the rung the program used to be
    bounded = jax.jit(lambda slab: slab[:, :, :16].sum()).lower(
        jax.ShapeDtypeStruct(shape, "float32"))
    assert len(slab_slices(bounded, shape)) == 1


# ---------------------------------------------------------------------------
# shared-prefix KV cache (engine integration; unit tests in
# tests/test_prefix_cache.py)
# ---------------------------------------------------------------------------
def test_prefix_cache_hit_is_token_exact_and_bills_suffix_only(decoder):
    """A second request sharing a cached prefix gets its KV via the row
    copy and prefills ONLY the suffix: `decode_prefill_tokens` (the
    MXNET_SERVE_PREFILL_BUDGET billing basis) moves by the suffix
    length, and the output still matches the explicit hit-path
    reference (`cached_prefix_len`)."""
    model, ref = decoder
    shared = list(range(1, 25))               # 24 tokens = 3 blocks of 8
    with serve.ContinuousEngine(model, max_slots=2, prefill_window=16,
                                prefix_block=8,
                                prefix_cache_slots=2) as eng:
        cold = eng.generate(shared + [30, 31], 6, timeout=120)
        before = profiler.serve_stats()["decode_prefill_tokens"]
        hot = eng.generate(shared + [32, 33], 6, timeout=120)
        after = profiler.serve_stats()["decode_prefill_tokens"]
        st = eng.stats()
        assert eng.prefix_hit_count() == 1
        assert eng.assert_no_retraces() == 0
    # 24 of the hit's 26 prompt tokens came from the copy: the budget
    # was billed 2 suffix tokens, not the full prompt
    assert after - before == 2
    assert st["prefix_hit_rate"] == 0.5       # 1 hit, 1 cold miss
    assert st["prefill_cached_token_share"] > 0.4
    assert st["prefix_cache"]["entries"] == 1
    np.testing.assert_array_equal(
        cold, ref.reference_generate(shared + [30, 31], 6, window=16))
    np.testing.assert_array_equal(
        hot, ref.reference_generate(shared + [32, 33], 6, window=16,
                                    cached_prefix_len=24),
        err_msg="prefix-cache hit diverged from the hit-path reference")


def test_prefix_cache_hit_token_exact_int8(decoder):
    """Same contract on a quantized pool: the row copy moves codes AND
    scales, so a hit dequantizes bit-identically to cold provenance."""
    model, ref = decoder
    shared = list(range(3, 19))               # 16 tokens = 2 blocks of 8
    with serve.ContinuousEngine(model, max_slots=2, prefill_window=16,
                                prefix_block=8, prefix_cache_slots=2,
                                kv_dtype="int8") as eng:
        cold = eng.generate(shared + [33], 5, timeout=120)
        hot = eng.generate(shared + [34, 35], 5, timeout=120)
        assert eng.prefix_hit_count() == 1
        assert eng.assert_no_retraces() == 0
    np.testing.assert_array_equal(
        cold, ref.reference_generate(shared + [33], 5, window=16,
                                     kv_dtype="int8"))
    np.testing.assert_array_equal(
        hot, ref.reference_generate(shared + [34, 35], 5, window=16,
                                    kv_dtype="int8", cached_prefix_len=16))


def test_shared_prefix_poison_isolation(decoder):
    """Poison every slab row EXCEPT the cache's pinned rows after the
    prefix is published: a later hit reads only the cache row (copied
    into its slot) and its own suffix KV, so the output must match the
    hit-path reference bit-for-bit — nothing a prior tenant wrote, and
    nothing beyond the copied prefix, is reachable."""
    model, ref = decoder
    eng = serve.ContinuousEngine(model, max_slots=1, prefill_window=16,
                                 prefix_block=8, prefix_cache_slots=1,
                                 decode_steps=2).start()
    try:
        shared = list(range(2, 18))           # 16 tokens = 2 blocks
        eng.generate(shared + [30], 6, timeout=120)    # publishes [0,16)
        cache_rows = set(eng.pool.in_use())   # only the cache's claim
        assert len(cache_rows) == 1
        for s in range(eng.pool.max_slots + 1):        # incl. garbage
            if s not in cache_rows:
                eng.pool.poison_slot(s, 1e9)
        hot = eng.generate(shared + [31, 32], 6, timeout=120)
        assert eng.prefix_hit_count() == 1
    finally:
        eng.close()
    np.testing.assert_array_equal(
        hot, ref.reference_generate(shared + [31, 32], 6, window=16,
                                    cached_prefix_len=16),
        err_msg="a poisoned row leaked into a shared-prefix hit")


def test_admission_budget_uses_post_cache_cost(decoder):
    """The EDF grant bills waiters at their POST-CACHE prefill cost: a
    fully-cached long prompt (1-token suffix) fits a nearly-exhausted
    `prefill_budget` and is admitted PAST an earlier-submitted cold
    prompt whose full-window cost does not — the budget sees the
    suffix, not the prompt length (pre-PR-19 both billed full-window
    and the cold one, being first, would have won the slot)."""
    model, _ = decoder
    eng = serve.ContinuousEngine(model, max_slots=2, prefill_lanes=2,
                                 prefill_window=16, prefix_block=8,
                                 prefix_cache_slots=1, prefill_budget=8,
                                 decode_steps=1).start()
    try:
        shared = list(range(1, 17))           # 16 tokens = 2 blocks
        eng.generate(shared + [20], 2, timeout=120)    # publish prefix
        held = [eng.pool.claim(), eng.pool.claim()]    # block admission
        first = eng.submit([40, 41, 42, 43], 2)        # cost 4 (>=1 grant)
        cold = eng.submit(list(range(30, 44)), 2)      # cost 14 > budget
        hot = eng.submit(shared + [21], 2)             # cost 1, fits
        time.sleep(0.05)                      # all three demonstrably wait
        for s in held:
            eng.pool.free(s)
        for fut in (first, cold, hot):
            fut.result(timeout=120)
    finally:
        eng.close()
    # wave 1 admits `first` (the >=1 grant, 4 of 8 budget) and `hot`
    # (1 token fits the 4 left); `cold` (14) waits for the next wave. Read
    # from the engine's own timeline: threads that watch the futures wake
    # in any order once the two waves are a few ms apart
    admitted = {n: f.timing.t_admit for n, f in
                (("first", first), ("cold", cold), ("hot", hot))}
    assert admitted["hot"] < admitted["cold"], \
        f"suffix-cost waiter was not granted a slot first: {admitted}"


def test_short_hit_into_a_slot_that_held_a_longer_request(decoder):
    """The copy moves the matched prefix's blocks and no more, so the one
    slot keeps a longer previous tenant's KV beyond them: the hit's own
    suffix and tokens overwrite what they reach, the lengths mask the
    rest, and the output is the hit-path reference's."""
    model, ref = decoder
    shared = list(range(1, 9))                # 8 tokens = 1 block of 8
    long_prompt = list(range(20, 60))         # 40 of the row's 48 positions
    with serve.ContinuousEngine(model, max_slots=1, prefill_window=16,
                                prefix_block=8,
                                prefix_cache_slots=2) as eng:
        eng.generate(shared + [30], 2, timeout=120)    # publishes [0, 8)
        eng.generate(long_prompt, 6, timeout=120)      # fills the slot
        hot = eng.generate(shared + [31, 32], 8, timeout=120)
        assert eng.prefix_hit_count() == 1
        assert eng.assert_no_retraces() == 0
        # two publishes (8 and 40 tokens) and one hit (8)
        assert eng.stats()["copied_positions"] == 8 + 40 + 8
    np.testing.assert_array_equal(
        hot, ref.reference_generate(shared + [31, 32], 8, window=16,
                                    cached_prefix_len=8),
        err_msg="a previous tenant's positions showed through a short hit")


def test_short_prefix_published_over_a_long_entry_then_hit(decoder):
    """One cache row: a short prompt's publish evicts a long entry and
    moves only its own block into the row, whose later positions still
    hold the long entry's KV. A hit on the short prefix whose suffix runs
    on into those positions is token-exact all the same."""
    model, ref = decoder
    long_prompt = list(range(20, 60))         # entry of 40 tokens
    short = list(range(1, 9))                 # entry of 8 tokens
    suffix = list(range(40, 60))              # the hit's prompt: 28 tokens
    with serve.ContinuousEngine(model, max_slots=2, prefill_window=16,
                                prefix_block=8,
                                prefix_cache_slots=1) as eng:
        eng.generate(long_prompt, 2, timeout=120)
        assert eng.stats()["prefix_cache"]["resident_tokens"] == 40
        eng.generate(short + [30], 2, timeout=120)     # evicts, publishes
        assert eng.stats()["prefix_cache"]["resident_tokens"] == 8
        hot = eng.generate(short + suffix, 6, timeout=120)
        assert eng.prefix_hit_count() == 1
        assert eng.assert_no_retraces() == 0
    np.testing.assert_array_equal(
        hot, ref.reference_generate(short + suffix, 6, window=16,
                                    cached_prefix_len=8),
        err_msg="the evicted entry's positions showed through the hit")


# ---------------------------------------------------------------------------
# the copy program alone: it moves the positions it is given, in whole
# blocks, and nothing else
# ---------------------------------------------------------------------------
ROWS, LANES, COPY_LEN = 8, 4, 512             # 7 rows + garbage; 4 blocks
COPY_BLOCK = 128
POISON = 77.0


def _whole_rows(k_cache, v_cache, src_rows, dst_rows):
    """The oracle: the whole-row gather this program replaced (rows whole,
    every lane live)."""
    import jax
    return jax.tree_util.tree_map(
        lambda leaf: leaf.at[dst_rows].set(leaf[src_rows]),
        (k_cache, v_cache))


def _copy_slabs(kv_dtype, seed):
    """K and V caches of (ROWS, 2, COPY_LEN, 2, 4) with distinct content
    everywhere; int8 pools are (codes, scales) pairs."""
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    shape = (ROWS, 2, COPY_LEN, 2, 4)

    def one():
        if kv_dtype == "int8":
            return (jnp.asarray(rng.randint(-127, 128, shape), jnp.int8),
                    jnp.asarray(rng.rand(*shape[:3]), jnp.float32))
        return jnp.asarray(rng.randn(*shape), kv_dtype)
    return one(), one()


def _leaves(cache):
    import jax
    return [np.asarray(leaf, np.float32)
            for leaf in jax.tree_util.tree_leaves(cache)]


COPY_CASES = {
    # one live lane, row 1 -> row 4, at the lengths around a block's edge
    **{f"n={n}": [(1, 4, n)] for n in (
        0, 1, COPY_BLOCK - 1, COPY_BLOCK, COPY_BLOCK + 1, 3 * COPY_BLOCK,
        COPY_LEN)},
    "src_is_dst": [(2, 2, 200)],
    "idle_lanes_name_live_rows": [(0, 3, 0), (1, 4, 0), (2, 5, 0)],
    "lanes_of_different_lengths": [(0, 3, 130), (1, 4, 1), (2, 5, COPY_LEN)],
}


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("case", list(COPY_CASES))
def test_copy_program_moves_the_positions_it_is_given(case, kv_dtype):
    """Against the whole-row oracle: positions [0, n) of every destination
    equal the source's (codes and scales alike); with the destinations
    poisoned first, every position from the end of a lane's last block on
    still holds the poison, and every other row — idle lanes' rows and the
    garbage row among them — is what it was."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.serve.continuous import (_copy_block,
                                                      _copy_slot_rows)
    assert _copy_block(COPY_LEN) == COPY_BLOCK
    pairs = COPY_CASES[case]
    lanes = np.zeros((3, LANES), np.int32)
    lanes[:, :len(pairs)] = np.asarray(pairs, np.int32).T
    src, dst, n = (jnp.asarray(a) for a in lanes)
    k, v = _copy_slabs(kv_dtype, seed=len(case))
    poisoned = jnp.asarray([d for s, d, _ in pairs if s != d], jnp.int32)
    k, v = jax.tree_util.tree_map(
        lambda leaf: leaf.at[poisoned].set(jnp.asarray(POISON, leaf.dtype)),
        (k, v))
    before = [_leaves(k), _leaves(v)]
    want = [_leaves(c) for c in _whole_rows(k, v, src, dst)]
    got = jax.jit(_copy_slot_rows)(k, v, src, dst, n)
    for was, oracle, cache in zip(before, want, got):
        for a, o, leaf in zip(was, oracle, _leaves(cache)):
            expect = a.copy()
            for s, d, length in pairs:
                edge = -(-length // COPY_BLOCK) * COPY_BLOCK
                np.testing.assert_array_equal(leaf[d, :, :length],
                                              o[d, :, :length])
                expect[d, :, :edge] = a[s, :, :edge]
            # beyond each lane's last block, and in every other row,
            # nothing moved
            np.testing.assert_array_equal(leaf, expect)


@pytest.mark.parametrize("form,kv_dtype", [
    ("blocks", "float32"), ("blocks", "int8"), ("whole_rows", "float32")])
def test_copy_program_makes_no_buffer_of_a_row(form, kv_dtype):
    """From the compiled HLO: apart from the donated slabs themselves the
    program holds pieces, never a row or a slab; the whole-row oracle is
    the control that the check can fail. (float32 and int8: this
    backend widens a bfloat16 slab to update it; the bfloat16 program is
    compiled for the chip in tests/test_chip_compile.py.)"""
    import jax
    from incubator_mxnet_tpu.serve.continuous import _copy_slot_rows
    k, v = jax.eval_shape(lambda: _copy_slabs(kv_dtype, seed=0))
    lanes = jax.ShapeDtypeStruct((LANES,), "int32")
    if form == "blocks":
        compiled = jax.jit(_copy_slot_rows, donate_argnums=(0, 1)).lower(
            k, v, lanes, lanes, lanes).compile()
    else:
        compiled = jax.jit(_whole_rows, donate_argnums=(0, 1)).lower(
            k, v, lanes, lanes).compile()
    row = 2 * COPY_LEN * 2 * 4
    big = buffers_of_at_least(compiled, row)
    assert (big == []) == (form == "blocks"), big


def test_copy_program_traces_once_whatever_the_lengths():
    import jax.numpy as jnp
    model = serve.CachedDecoder(serve.DecoderConfig(**CFG), seed=3)
    prog = model.copy_program()
    k, v = _copy_slabs("float32", seed=0)
    src = jnp.arange(LANES, dtype=jnp.int32)
    sizes = []
    for lengths in ([0, 0, 0, 0], [5, 0, 300, 0], [COPY_LEN] * LANES):
        k, v = prog(k, v, src, src + 3, jnp.asarray(lengths, jnp.int32))
        sizes.append(model.compile_cache_size())
    assert sizes[0] >= 1 and sizes == sizes[:1] * 3


# ---------------------------------------------------------------------------
# KV-slot lifecycle
# ---------------------------------------------------------------------------
def test_kv_pool_claim_free_and_typed_exhaustion():
    pool = serve.KVCachePool(max_slots=3, layers=1, max_len=8, heads=2,
                             head_dim=4, allocate=False)
    before = serve.kvpool_stats()
    slots = [pool.claim() for _ in range(3)]
    assert sorted(slots) == [0, 1, 2]
    assert pool.free_count() == 0
    with pytest.raises(serve.SlotsFullError):
        pool.claim()
    # SlotsFullError is a typed ServeError (admission can catch it)
    assert issubclass(serve.SlotsFullError, serve.ServeError)
    pool.free(slots[0])
    assert pool.free_count() == 1
    with pytest.raises(serve.ServeError, match="double free"):
        pool.free(slots[0])
    after = serve.kvpool_stats()
    # "claims" / "frees" / "exhausted" process-wide counters moved
    assert after["claims"] - before["claims"] == 3
    assert after["frees"] - before["frees"] == 1
    assert after["exhausted"] - before["exhausted"] == 1
    assert KVPOOL_STATS["claims"] >= 3
    st = pool.stats()
    assert st["in_use"] == 2 and st["free"] == 1 and st["max_slots"] == 3


def test_kv_pool_concurrent_claim_free_hammer():
    """8 threads churn claim/free; bookkeeping stays exact: no slot is
    ever handed to two holders, counts balance, capacity is respected."""
    pool = serve.KVCachePool(max_slots=4, layers=1, max_len=8, heads=2,
                             head_dim=4, allocate=False)
    errs, held_twice = [], []
    lock = threading.Lock()
    held = set()

    def hammer(tid):
        rng = np.random.RandomState(tid)
        try:
            for _ in range(300):
                try:
                    s = pool.claim()
                except serve.SlotsFullError:
                    continue
                with lock:
                    if s in held:
                        held_twice.append(s)
                    held.add(s)
                if rng.rand() < 0.5:
                    time.sleep(0)
                with lock:
                    held.discard(s)
                pool.free(s)
        except BaseException as e:   # pragma: no cover - diagnostics
            errs.append(e)

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errs, errs
    assert not held_twice, f"slots double-claimed: {held_twice}"
    assert pool.free_count() == 4 and pool.in_use() == []


def test_slot_reuse_cannot_read_prior_request_cache(any_decoder):
    """Poison-fill + value check: fill the WHOLE slab with a sentinel,
    then run a request through a reused slot — output must match the
    fresh-pool reference bit-for-bit, proving no read escapes the
    current request's [0, cur_len] window (prefill_window < max_len, so
    the page is NOT fully overwritten at claim: only the mask protects
    the tail)."""
    model, ref = any_decoder
    eng = serve.ContinuousEngine(model, max_slots=1, prefill_window=16,
                                 decode_steps=2).start()
    try:
        # tenant 1 dirties slot 0 with its own KV
        eng.generate([9, 8, 7, 6], 10, timeout=60)
        assert eng.pool.in_use() == []
        # now poison EVERYTHING the compiled programs could read
        eng.pool.poison(1e9)
        out = eng.generate([1, 2, 3], 8, timeout=60)
    finally:
        eng.close()
    np.testing.assert_array_equal(
        out, ref.reference_generate([1, 2, 3], 8, window=16),
        err_msg="reused slot leaked a prior tenant's cache into decode")


def test_requests_queue_when_slots_full_then_complete(any_decoder):
    model, ref = any_decoder
    work = _workload(10, seed=9)
    with serve.ContinuousEngine(model, max_slots=2,
                                decode_steps=2) as eng:
        futs = [eng.submit(p, m) for p, m in work]
        outs = [f.result(timeout=120) for f in futs]
        st = eng.stats()
    assert st["pool"]["in_use"] == 0
    assert st["replies"] == 10
    for (p, m), o in zip(work, outs):
        np.testing.assert_array_equal(o, ref.reference_generate(p, m))


# ---------------------------------------------------------------------------
# SLO-aware admission
# ---------------------------------------------------------------------------
def test_deadline_aware_slot_grant_beats_fifo(decoder):
    """With the pool exhausted, a LATER-submitted request holding a
    deadline is granted the next slot before an earlier deadline-less
    one. The slot is held by a DIRECT pool claim (no request timing to
    race): admission can only happen after the test frees it."""
    model, _ = decoder
    eng = serve.ContinuousEngine(model, max_slots=1, prefill_lanes=1,
                                 decode_steps=1).start()
    order = []
    lock = threading.Lock()
    try:
        held = eng.pool.claim()                    # engine cannot admit
        fifo = eng.submit([1, 2], 4)               # waiting, no deadline
        slo = eng.submit([3, 4], 4, deadline_ms=30000)   # waiting, SLO

        def watch(name, fut):
            fut.result(timeout=120)
            with lock:
                order.append(name)

        ts = [threading.Thread(target=watch, args=(n, f))
              for n, f in (("fifo", fifo), ("slo", slo))]
        for t in ts:
            t.start()
        time.sleep(0.05)                           # both demonstrably wait
        eng.pool.free(held)
        for t in ts:
            t.join(timeout=120)
    finally:
        eng.close()
    assert order and order[0] == "slo", \
        f"deadline-holder was not granted the slot first: {order}"


def test_deadline_expires_while_waiting_for_slot(decoder):
    model, _ = decoder
    before = profiler.serve_stats()["timeouts"]
    eng = serve.ContinuousEngine(model, max_slots=1, prefill_lanes=1,
                                 decode_steps=1).start()
    try:
        held = eng.pool.claim()                    # engine cannot admit
        doomed = eng.submit([1, 2], 4, deadline_ms=15)
        with pytest.raises(serve.RequestTimeout, match="KV slot"):
            doomed.result(timeout=60)
        eng.pool.free(held)
        # the engine keeps serving after the expiry
        assert eng.generate([3, 3], 3, timeout=60).size == 3
    finally:
        eng.close()
    assert profiler.serve_stats()["timeouts"] == before + 1


def test_queue_full_rejects_typed(decoder):
    model, _ = decoder
    eng = serve.ContinuousEngine(model, max_slots=1, prefill_lanes=1,
                                 max_queue=2, decode_steps=1).start()
    try:
        futs = [eng.submit([5, 5], 40)]
        rejected = 0
        for _ in range(12):
            try:
                futs.append(eng.submit([1, 2], 2))
            except serve.QueueFullError as e:
                assert e.policy == "reject"
                rejected += 1
        assert rejected > 0
        for f in futs:
            f.result(timeout=120)
    finally:
        eng.close()


def test_closed_engine_rejects_and_drains(decoder):
    model, ref = decoder
    eng = serve.ContinuousEngine(model, max_slots=2).start()
    futs = [eng.submit(p, m) for p, m in _workload(6, seed=2)]
    eng.close(drain=True)
    assert all(f.exception() is None for f in futs)
    with pytest.raises(serve.ServerClosed):
        eng.submit([1, 2], 4)


def test_submit_during_drain_raises_typed_replica_draining(decoder):
    """DRAINING is not CLOSED: while the engine is still finishing its
    resident requests before a restart, submit() must raise the typed
    ReplicaDraining (a ServerClosed subclass the fleet router re-routes
    silently), and revert to plain ServerClosed once the drain is done."""
    model, _ = decoder
    eng = serve.ContinuousEngine(model, max_slots=2, decode_steps=2).start()
    resident = eng.submit([1, 2, 3], 10)
    eng.begin_drain()
    assert eng.draining
    with pytest.raises(serve.ReplicaDraining, match="draining"):
        eng.submit([4], 2)
    assert issubclass(serve.ReplicaDraining, serve.ServerClosed)
    # the resident lane still finishes: drain never cancels admitted work
    assert resident.result(timeout=120).size == 10
    eng.close()
    assert not eng.draining
    try:
        eng.submit([4], 2)
        pytest.fail("closed engine accepted a request")
    except serve.ReplicaDraining:
        pytest.fail("closed engine must raise plain ServerClosed")
    except serve.ServerClosed:
        pass


def test_drain_completes_when_waiting_lane_expires_mid_drain(decoder):
    """A waiting request whose deadline fires DURING the drain must not
    wedge close(drain=True): the loop drops the expired waiter and exits."""
    model, _ = decoder
    eng = serve.ContinuousEngine(model, max_slots=1, prefill_lanes=1,
                                 decode_steps=1).start()
    held = eng.pool.claim()            # the waiter can never be admitted
    doomed = eng.submit([3], 4, deadline_ms=300)
    t0 = time.time()
    eng.close(drain=True, timeout=30)
    dt = time.time() - t0
    # gated by the 300ms deadline, not wedged and not instant
    assert 0.2 <= dt < 10, dt
    with pytest.raises(serve.RequestTimeout, match="KV slot"):
        doomed.result(timeout=1)
    eng.pool.free(held)


# ---------------------------------------------------------------------------
# tracing: one request = one trace across N iterations
# ---------------------------------------------------------------------------
def test_one_trace_across_iterations(decoder, tmp_path):
    model, _ = decoder
    profiler.start()
    try:
        with serve.ContinuousEngine(model, max_slots=2,
                                    decode_steps=2) as eng:
            futs = [eng.submit([3, 1, 4], 9), eng.submit([2, 7], 7)]
            for f in futs:
                f.result(timeout=120)
            st = eng.stats()
            assert st["decode_iterations"] >= 2
    finally:
        profiler.stop()
    f = str(tmp_path / "trace.json")
    profiler.dump(filename=f)
    events = json.load(open(f))["traceEvents"]
    roots = [e for e in events if e["name"] == "serve.request"
             and "tokens" in e.get("args", {})]
    assert len(roots) == 2
    tids = {e["args"]["trace_id"] for e in roots}
    assert len(tids) == 2, "each request must be its own trace"
    for root in roots:
        tid = root["args"]["trace_id"]
        span_id = root["args"]["span_id"]
        # (the wait for a slot and the prefill overlap other requests':
        # an async pair each, `e` at the end with the duration)
        queue = [e for e in events if e["name"] == "serve.queue"
                 and e["ph"] == "e" and e["args"].get("trace_id") == tid]
        prefill = [e for e in events if e["name"] == "serve.prefill"
                   and e["ph"] == "e" and e["args"].get("trace_id") == tid]
        decode = [e for e in events if e["name"] == "serve.decode"
                  and e["args"].get("trace_id") == tid]
        # submit->admission, admission->first-token and first->last-token
        # (N iterations) hang off the SAME request root: one trace
        assert len(queue) == 1 and len(prefill) == 1 and len(decode) == 1
        assert queue[0]["args"]["parent_span_id"] == span_id
        assert abs(queue[0]["ts"] + prefill[0]["dur"]
                   - prefill[0]["ts"]) < 1.0
        assert prefill[0]["args"]["parent_span_id"] == span_id
        assert decode[0]["args"]["parent_span_id"] == span_id
        assert decode[0]["args"]["tokens"] == root["args"]["tokens"]
    # the engine's wave lanes recorded too (collector was active)
    assert any(e["name"] == "serve.decode_batch" for e in events)
    assert any(e["name"] == "serve.prefill_batch" for e in events)


# ---------------------------------------------------------------------------
# persistent compilation cache: warm replica skips compile
# ---------------------------------------------------------------------------
_REPLICA_PROG = r"""
import sys
from incubator_mxnet_tpu import serve
cfg = serve.DecoderConfig(vocab=64, embed=32, layers=2, heads=4,
                          head_dim=8, max_len=48)
model = serve.CachedDecoder(cfg, seed=11)
eng = serve.ContinuousEngine(model, max_slots=2).start()
out = eng.generate([1, 2, 3], 5, timeout=60)
eng.close()
print("WARMUP_S", eng.warmup_s)
print("TOKENS", ",".join(str(t) for t in out))
"""


def test_compile_cache_dir_warms_second_replica(tmp_path):
    """Two FRESH processes sharing one MXNET_COMPILE_CACHE_DIR — the
    real replica semantics: the first compiles and serializes, the
    second deserializes. (In-process clear_caches() would corrupt live
    compiled programs elsewhere in the suite; replicas are processes.)"""
    d = str(tmp_path / "cc")
    os.makedirs(d)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_COMPILE_CACHE_DIR=d)

    def replica():
        r = subprocess.run([sys.executable, "-c", _REPLICA_PROG],
                           env=env, capture_output=True, text=True,
                           timeout=300)
        assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
        warm_s = float(r.stdout.split("WARMUP_S")[1].split()[0])
        toks = r.stdout.split("TOKENS")[1].split()[0]
        return warm_s, toks

    cold, toks_cold = replica()
    assert len(os.listdir(d)) > 0, \
        "no executables persisted to MXNET_COMPILE_CACHE_DIR"
    warm, toks_warm = replica()
    # same executables -> same tokens; the warm replica deserializes
    # instead of compiling. On a busy CI host we only assert it is NOT
    # SLOWER (what a cell pays is `setup_s`, PERF.md)
    assert toks_cold == toks_warm
    assert warm <= cold * 1.2, (cold, warm)


_CACHE_RULE_PROG = """
import json
import jax
from incubator_mxnet_tpu import deploy
if {runner}:
    deploy.default_compile_cache_to_checkout()
armed = deploy.maybe_enable_compile_cache()
print(json.dumps({{"armed": armed,
                  "dir": jax.config.jax_compilation_cache_dir,
                  "checkout": deploy.CHECKOUT_COMPILE_CACHE_DIR}}))
"""


def _cache_rule(tmp_path, env, runner=False):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base = {k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR",
                         "MXNET_COMPILE_CACHE_DIR")}
    r = subprocess.run(
        [sys.executable, "-c", _CACHE_RULE_PROG.format(runner=runner)],
        env=dict(base, JAX_PLATFORMS="cpu", **env), cwd=repo,
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_compile_cache_placed_from_outside_is_left_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax already uses it, and no other
    directory is set in code — MXNET_COMPILE_CACHE_DIR included."""
    placed, other = str(tmp_path / "placed"), str(tmp_path / "other")
    got = _cache_rule(tmp_path, {"JAX_COMPILATION_CACHE_DIR": placed,
                                 "MXNET_COMPILE_CACHE_DIR": other},
                      runner=True)
    assert got["armed"] is True and got["dir"] == placed


def test_compile_cache_default_is_the_fixed_checkout_path(tmp_path):
    """No variable: library callers arm nothing; the repo's runners
    (chip_smoke.py, tools/crashtest.py: `default_compile_cache_to_checkout()`
    first) get `<checkout>/.jax_cache`, a fixed path."""
    got = _cache_rule(tmp_path, {})
    assert got["armed"] is False and got["dir"] is None
    got = _cache_rule(tmp_path, {}, runner=True)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert got["armed"] is True
    assert got["dir"] == got["checkout"] == os.path.join(repo, ".jax_cache")
    # MXNET_COMPILE_CACHE_DIR still places it when jax's own is unset
    mine = str(tmp_path / "mine")
    got = _cache_rule(tmp_path, {"MXNET_COMPILE_CACHE_DIR": mine},
                      runner=True)
    assert got["dir"] == mine


# ---------------------------------------------------------------------------
# closed-loop callers (the benchmark's traffic shape)
# ---------------------------------------------------------------------------
def test_closed_loop_callers_never_retrace_and_drain(any_decoder):
    """More callers than slots, each sending its next request when the
    last one's reply arrives (arrivals depend on completions, as in every
    serve cell): token-exact, zero retraces after warm-up, the compiled
    programs are there to be cached, and the pool ends empty."""
    model, ref = any_decoder
    callers, rounds = 6, 3
    work = _workload(callers * rounds, seed=21)
    outs, errors = {}, []

    def caller(c):
        try:
            for r in range(rounds):
                i = c * rounds + r
                p, m = work[i]
                outs[i] = eng.generate(p, m, timeout=120)
        except Exception as e:       # surfaced below, not lost in a thread
            errors.append(e)

    with serve.ContinuousEngine(model, max_slots=2, decode_steps=2) as eng:
        warm = eng.compile_cache_size()
        threads = [threading.Thread(target=caller, args=(c,))
                   for c in range(callers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        st = eng.stats()
        assert eng.assert_no_retraces() == 0
        assert eng.compile_cache_size() == warm > 0
    assert not errors, errors
    assert st["replies"] == callers * rounds
    assert st["pool"]["in_use"] == 0
    for i, (p, m) in enumerate(work):
        np.testing.assert_array_equal(outs[i], ref.reference_generate(p, m))
