"""The engine's depth-1 look-ahead (`serve/continuous.py` `_iterate`): a
decode wave's `tokens`, `lengths` and `steps_left` stay on the device, wave
n+1 is dispatched before wave n is read back, and what a caller sees is
what lock-step gave it.

  * token streams equal `reference_generate`'s for all four served models,
    with lanes joining and leaving mid-stream
  * an eos inside a wave: nothing after it is delivered, the slot frees one
    wave later (the host learns of it at the read), the next tenant's
    stream is exact
  * `max_new_tokens=1` and a prompt that fills the page dispatch no wave
  * the order itself: wave n+1's program call precedes wave n's read;
    `waves_ahead / decode_iterations` under full lanes, 0 for one wave
  * a lone request's last token needs no further traffic
  * a failure at readback fails the requests of both outstanding waves,
    and the engine serves the next one on fresh buffers
  * `close(drain=True)` and `begin_drain` flush what is in flight
"""
import numpy as np
import pytest

from incubator_mxnet_tpu import serve


def _classic():
    cfg = serve.DecoderConfig(vocab=64, embed=32, layers=2, heads=4,
                              head_dim=8, max_len=48)
    return serve.CachedDecoder(cfg, seed=3), {}, {}


def _hybrid():
    from incubator_mxnet_tpu.models import hybrid_decoder as hd
    cfg = hd.HybridConfig(vocab=64, embed=32, layers=4, heads=4, kv_heads=2,
                          head_dim=8, mlp_hidden=64, window=8, d_state=4,
                          d_conv=4, expand=2, max_len=48, dtype="float32")
    return (hd.HybridDecoder(cfg, seed=3),
            dict(prefill_window=6, prefix_cache_slots=0, draft_tokens=0),
            dict(window=6))


def _sparse_moe():
    from incubator_mxnet_tpu.models import sparse_moe_decoder as sm
    cfg = sm.SparseMoEConfig(vocab=64, max_len=48)
    return (sm.SparseMoEDecoder(cfg, seed=3), dict(prefill_window=8),
            dict(window=8))


def _delta_moe():
    from incubator_mxnet_tpu.models import delta_moe_decoder as dm
    cfg = dm.DeltaMoEConfig(
        vocab=64, embed=64, heads=4, kda_lower_bound=-20.0,
        mixer_types=("kda", "kda", "mla", "kda"),
        mlp_types=("dense", "sparse", "sparse", "sparse"),
        routed_experts=16, experts_per_token=2, n_group=4, topk_group=2,
        held_count=4, max_len=48)
    return (dm.DeltaMoEDecoder(cfg, seed=3), dict(prefill_window=8),
            dict(window=8))


MODELS = {"classic": _classic, "hybrid": _hybrid, "sparse_moe": _sparse_moe,
          "delta_moe": _delta_moe}


@pytest.fixture(scope="module", params=sorted(MODELS))
def served(request):
    """(model, the engine options it needs, the reference's) of one of the
    four served model classes, tiny. References are taken before an engine
    starts (their 1-slot pool variants compile)."""
    if request.param == "delta_moe":
        from incubator_mxnet_tpu.models import delta_moe_decoder as dm
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(dm, "CHUNK", 8)      # a window of 8 is one chunk
            yield MODELS[request.param]()
    else:
        yield MODELS[request.param]()


@pytest.fixture(scope="module")
def classic():
    model, _, _ = _classic()
    return model, serve.CachedDecoder(model.config, params=model.params)


def prompt_of(n, seed):
    return np.random.default_rng(seed).integers(1, 64, size=n).astype(
        np.int32)


def test_streams_exact_with_joins_and_leaves_mid_stream(served):
    model, eng_kw, ref_kw = served
    first = [(prompt_of(n, 10 + i), out) for i, (n, out) in enumerate(
        [(3, 9), (13, 4), (8, 17), (5, 1)])]
    later = [(prompt_of(n, 20 + i), out) for i, (n, out) in enumerate(
        [(11, 11), (4, 2), (9, 7)])]
    want = [model.reference_generate(p, n, **ref_kw) for p, n in first + later]
    with serve.ContinuousEngine(model, max_slots=3, prefill_lanes=2,
                                decode_steps=3, **eng_kw) as eng:
        futs = [eng.submit(p, n) for p, n in first]
        futs[1].result(timeout=300)       # the others are mid-stream now
        futs += [eng.submit(p, n) for p, n in later]
        got = [f.result(timeout=300) for f in futs]
        st = eng.stats()
        assert eng.assert_no_retraces() == 0
    for (p, n), g, w in zip(first + later, got, want):
        np.testing.assert_array_equal(g, w)
        assert len(g) == n
    assert 0 < st["waves_ahead"] < st["decode_iterations"]
    assert st["decode_tokens"] == sum(n - 1 for _, n in first + later)
    assert st["pool"]["in_use"] == 0


def test_eos_inside_a_wave_frees_the_slot_a_wave_later(served):
    model, eng_kw, ref_kw = served
    prompt, follower, max_new = prompt_of(5, 31), prompt_of(7, 32), 16
    base = model.reference_generate(prompt, max_new, **ref_kw)
    eos = int(base[len(base) // 2])
    want = model.reference_generate(prompt, max_new, eos_id=eos, **ref_kw)
    want_next = model.reference_generate(follower, 6, eos_id=eos, **ref_kw)
    assert len(want) < max_new and want[-1] == eos
    with serve.ContinuousEngine(model, max_slots=1, decode_steps=2,
                                eos_id=eos, **eng_kw) as eng:
        fut, nxt = eng.submit(prompt, max_new), eng.submit(follower, 6)
        got = fut.result(timeout=300)
        got_next = nxt.result(timeout=300)
    st = eng.stats()                               # closed: all is read
    np.testing.assert_array_equal(got, want)       # nothing after the eos
    np.testing.assert_array_equal(got_next, want_next)
    assert st["decode_tokens"] == len(want) - 1 + len(want_next) - 1
    # one slot: a wave advances one lane or none. The wave after an eos
    # went out before the host had read it and advanced none; the slot
    # came back at that read, and its next tenant started clean
    waves = sum(-(-(len(w) - 1) // 2) for w in (want, want_next))
    assert st["active_sum"] == waves
    assert st["decode_iterations"] - waves == 1 + (len(want_next) < 6)
    assert st["pool"]["in_use"] == 0


@pytest.mark.parametrize("case", ["one_token", "full_page"])
def test_a_request_that_ends_at_its_first_token_dispatches_no_wave(
        served, case):
    model, eng_kw, ref_kw = served
    prompt, n = ((prompt_of(6, 41), 1) if case == "one_token"
                 else (prompt_of(47, 42), 5))     # max_len - 1: page full
    want = model.reference_generate(prompt, n, **ref_kw)
    assert len(want) == 1
    with serve.ContinuousEngine(model, max_slots=2, decode_steps=3,
                                **eng_kw) as eng:
        got = eng.generate(prompt, n, timeout=300)
        st = eng.stats()
    np.testing.assert_array_equal(got, want)
    assert st["decode_iterations"] == 0 and st["replies"] == 1


def _log_order(eng, log):
    """Record the engine's decode program calls and record reads in
    `log`, in the order the scheduler thread makes them."""
    prog, read = eng._decode_prog, eng._read

    def dispatch(*args):
        log.append("dispatch")
        return prog(*args)

    def reading(rec, *args):
        if rec.wave is not None:
            log.append("read")
        return read(rec, *args)

    eng._decode_prog, eng._read = dispatch, reading


def test_wave_is_dispatched_before_the_previous_one_is_read(classic):
    model, ref = classic
    want = ref.reference_generate([5, 6, 7], 9)
    eng = serve.ContinuousEngine(model, max_slots=2, decode_steps=2).start()
    log = []
    try:
        _log_order(eng, log)
        got = eng.generate([5, 6, 7], 9, timeout=120)
        st = eng.stats()
    finally:
        eng.close()
    np.testing.assert_array_equal(got, want)
    # four waves of two tokens: each but the first goes out while its
    # predecessor is unread, and the last is read with nothing behind it
    assert log == ["dispatch", "dispatch", "read", "dispatch", "read",
                   "dispatch", "read", "read"]
    assert st["decode_iterations"] == 4 and st["waves_ahead"] == 3
    assert st["active_sum"] == 4


def test_waves_go_out_ahead_under_full_lanes_and_not_for_one_wave(classic):
    model, ref = classic
    jobs = [(prompt_of(4 + i, 50 + i).tolist(), 30 + i) for i in range(6)]
    want = [ref.reference_generate(p, n) for p, n in jobs]
    with serve.ContinuousEngine(model, max_slots=2, decode_steps=2) as eng:
        futs = [eng.submit(p, n) for p, n in jobs]
        got = [f.result(timeout=120) for f in futs]
        st = eng.stats()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert st["waves_ahead"] / st["decode_iterations"] >= 0.9
    # a slot idles the wave in which its spent lane waits to be read
    assert 1.0 < st["mean_active_slots"] <= 2.0
    with serve.ContinuousEngine(model, max_slots=2, decode_steps=4) as eng:
        got = eng.generate(jobs[0][0], 4, timeout=120)    # 1 + one wave of 3
        st = eng.stats()
    np.testing.assert_array_equal(got, want[0][:4])
    assert st["decode_iterations"] == 1 and st["waves_ahead"] == 0


def test_last_token_of_a_lone_request_needs_no_further_traffic(classic):
    model, ref = classic
    with serve.ContinuousEngine(model, max_slots=4, decode_steps=3) as eng:
        fut = eng.submit([9, 8, 7, 6], 8)      # 1 + 3 + 3 + 1
        got = fut.result(timeout=60)           # nobody else ever submits
        assert eng._unread is None
        assert eng.queue_depth() == (0, 0)
        assert fut.timing.t_done is not None and fut.timing.tokens == 8
    np.testing.assert_array_equal(got, ref.reference_generate([9, 8, 7, 6], 8))


def test_failure_at_readback_fails_both_outstanding_waves(classic):
    """Two waves are outstanding when a read fails: the one being read and
    the one dispatched before it. The requests of both fail, their slots
    come back, the lane state and the slabs are fresh, and the next
    request is served exactly."""
    model, ref = classic
    eng = serve.ContinuousEngine(model, max_slots=3, decode_steps=2).start()
    read, reads = eng._read, []

    def failing(rec, *args):
        reads.append(rec)
        if len(reads) == 3:
            # the third record is read after the fourth was dispatched
            assert eng._pending is not rec.counters
            raise RuntimeError("device lost at readback")
        return read(rec, *args)

    try:
        eng._read = failing
        a, b = eng.submit([1, 2, 3], 30), eng.submit([4, 5], 30)
        for f in (a, b):
            with pytest.raises(serve.ServeError, match="engine step failed"):
                f.result(timeout=60)
        eng._read = read
        assert eng._unread is None and eng.pool.stats()["in_use"] == 0
        got = eng.generate([6, 7, 8], 7, timeout=60)
        st = eng.stats()
    finally:
        eng.close()
    np.testing.assert_array_equal(got, ref.reference_generate([6, 7, 8], 7))
    assert st["errors"] == 2 and st["replies"] == 1
    assert eng.retraces_after_warmup() == 0


@pytest.mark.parametrize("how", ["close", "begin_drain"])
def test_drain_flushes_what_is_in_flight(classic, how):
    model, ref = classic
    jobs = [(prompt_of(3 + i, 60 + i).tolist(), 6 + 3 * i) for i in range(5)]
    want = [ref.reference_generate(p, n) for p, n in jobs]
    eng = serve.ContinuousEngine(model, max_slots=2, decode_steps=2).start()
    futs = [eng.submit(p, n) for p, n in jobs]
    if how == "begin_drain":
        eng.begin_drain()
        with pytest.raises(serve.ReplicaDraining):
            eng.submit([1, 2], 3)
    eng.close(drain=True, timeout=120)
    assert not eng._thread.is_alive() and eng._unread is None
    for f, w in zip(futs, want):
        np.testing.assert_array_equal(f.result(timeout=1), w)
    st = eng.stats()
    assert st["replies"] == 5 and st["pool"]["in_use"] == 0
