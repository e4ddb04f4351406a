"""`models.hybrid_decoder.HybridDecoder` against the plain reference
(`chipbench/reference/sambay.py`, which imports nothing of the program), by
LOGITS, on a tiny preset (L = 8, d = 64, 4/2 heads of 16, window 8, N = 4,
vocabulary 128) with seeded random float32 weights and non-zero biases.

The tolerance, and why it is what it is. Program and reference are both
float32 here; they differ in the order of their sums (a cache read in
blocks against one masked einsum, a chunked scan against one scan, XLA's
default float32 matmul against `highest`). The logits reach 3 in size and
the two agree to 4.4e-6 at worst (measured, over the prefill and 26 decode
steps of `decode_gaps`). The planted faults read far above that: a
selective-scan state rounded to bfloat16 after every step moves the logits
by 4.9e-4, a window of 9 keys instead of 8 by 1.6, a state that is never
updated, not carried from one window-sized chunk to the next, or left
from a previous tenant (`sambay.FAULTS`) by 0.06 to 0.13. TOL = 3e-5 sits
seven times above the sound reading and sixteen times below the nearest
fault; the `xfail(strict=True)` cases run the same comparison with a fault
planted and must fail it.
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from incubator_mxnet_tpu import serve  # noqa: E402
from incubator_mxnet_tpu.models import hybrid_decoder as hd  # noqa: E402
from incubator_mxnet_tpu.ops import fused  # noqa: E402
from incubator_mxnet_tpu.serve.kv_pool import CacheKindError  # noqa: E402
from chipbench.reference import sambay  # noqa: E402
from hlo_branches import sorts_and_conditionals  # noqa: E402

TOL = 3e-5
WINDOW = 8
TINY = dict(vocab=128, embed=64, layers=8, heads=4, kv_heads=2, head_dim=16,
            mlp_hidden=128, window=WINDOW, d_state=4, d_conv=4, expand=2,
            max_len=64, dtype="float32")


def make_params(config, seed=1):
    """Seeded weights: the program's initializer (`hd.init_hybrid_params`:
    Mamba-1's own conv taps and delta projection, the scan's input
    projection at the scale where its state reaches the output), with the
    N(0, 0.02) matrices five times that (at d = 64 they would leave every
    activation at 0.16) and every bias non-zero (zeros would hide a
    forgotten bias)."""
    params = hd.init_hybrid_params(config, seed)
    key = jax.random.PRNGKey(seed + 100)
    for i, (name, (_, kind)) in enumerate(
            sorted(hd.param_shapes(config).items())):
        if kind == "normal":
            params[name] = 5.0 * params[name]
        elif name.endswith("_b") and name != "m_dt_b":
            params[name] = 0.1 * jax.random.normal(
                jax.random.fold_in(key, i), params[name].shape)
    return params


@pytest.fixture(scope="module")
def tiny():
    config = hd.HybridConfig(**TINY)
    params = make_params(config)
    return {"config": config, "params": params,
            "model": hd.HybridDecoder(config, params=params),
            "forward": sambay.make_forward(config.as_dict())}


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(1, 128, size=n).astype(
        np.int32)


def reference_logits(forward, params, tokens):
    return np.asarray(sambay.logits(forward, params, tokens))


def one(v, dtype=jnp.int32):
    return jnp.asarray([v], dtype=dtype)


def prefill_logits(model, pool, prompt, window):
    """The prompt through the prefill at offset 0 and then the chunk
    program, on row 0 of `pool` -> the logits after each chunk."""
    out, pos = [], 0
    while pos < prompt.size:
        n = min(window, prompt.size - pos)
        toks = np.zeros((1, window), np.int32)
        toks[0, :n] = prompt[pos:pos + n]
        (cache,) = pool.buffers()
        if pos == 0:
            cache, logits = model.prefill_program(window)(
                model.params, cache, jnp.asarray(toks), one(n), one(0))
        else:
            cache, logits = model.chunk_prefill_program(window)(
                model.params, cache, jnp.asarray(toks), one(pos), one(n),
                one(0))
        pool.swap_buffers(cache)
        pos += n
        out.append((pos, np.asarray(logits[0])))
    return out


def decode_gaps(model, forward, prompt, steps, window=WINDOW):
    """Largest |program - reference| logit over a prefill and `steps`
    greedy decode steps through the cache, each against the full forward."""
    pool = model.new_pool(max_slots=1)
    logits = prefill_logits(model, pool, prompt, window)[-1][1]
    micro = jax.jit(hd._make_micro(model.config))
    tokens, got = list(prompt), [logits]
    for _ in range(steps):
        tokens.append(int(np.argmax(got[-1])))
        (cache,) = pool.buffers()
        cache, logits = micro(model.params, cache, one(tokens[-1]),
                              one(len(tokens) - 1), jnp.asarray([True]))
        pool.swap_buffers(cache)
        got.append(np.asarray(logits[0]))
    want = reference_logits(forward, model.params, np.asarray(tokens))
    return max(np.abs(g - want[prompt.size - 1 + i]).max()
               for i, g in enumerate(got))


# ---------------------------------------------------------------------------
# the reference against hand-checked small cases
# ---------------------------------------------------------------------------
def test_layer_pattern_of_the_published_depth():
    kinds = [k for k, _ in sambay.layer_kinds(32)]
    assert [kinds.count(k) for k in ("mamba", "swa", "full", "cross",
                                     "gmu")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "full"
    assert kinds[18] == "gmu" and kinds[19] == "cross" and kinds[31] == "cross"
    assert sambay.layer_kinds(32) == hd.layer_kinds(32)
    assert sambay.lambda_init(0) == pytest.approx(0.2)
    assert hd.lambda_init(7) == sambay.lambda_init(7)


def test_reference_scan_against_a_loop_by_hand():
    m = {"d_state": 2, "d_conv": 3, "dt_rank": 1, "expand": 1, "embed": 2}
    rng = np.random.default_rng(3)
    w = {"m_in": rng.normal(size=(2, 4)), "m_conv_w": rng.normal(size=(3, 2)),
         "m_conv_b": rng.normal(size=(2,)), "m_x": rng.normal(size=(2, 5)),
         "m_dt_w": rng.normal(size=(1, 2)), "m_dt_b": rng.normal(size=(2,)),
         "m_A_log": rng.normal(size=(2, 2)), "m_D": rng.normal(size=(2,)),
         "m_out": rng.normal(size=(2, 2))}
    h = rng.normal(size=(5, 2))
    out, y = sambay._mamba(m, False, sambay._matmul(False))(
        jnp.asarray(h, jnp.float32),
        {k: jnp.asarray(v, jnp.float32) for k, v in w.items()})
    # the same, one scalar at a time
    silu = lambda a: a / (1.0 + np.exp(-a))  # noqa: E731
    u, z = (h @ w["m_in"])[:, :2], (h @ w["m_in"])[:, 2:]
    s = np.zeros((2, 2))                                   # (channel, n)
    want_y = np.zeros((5, 2))
    for t in range(5):
        u1 = np.zeros(2)
        for ch in range(2):
            acc = w["m_conv_b"][ch]
            for k in range(3):
                if t - (2 - k) >= 0:
                    acc += w["m_conv_w"][k, ch] * u[t - (2 - k), ch]
            u1[ch] = silu(acc)
        rbc = u1 @ w["m_x"]
        delta = np.log1p(np.exp(rbc[:1] @ w["m_dt_w"] + w["m_dt_b"]))
        for ch in range(2):
            for n in range(2):
                a = -np.exp(w["m_A_log"][n, ch])
                s[ch, n] = np.exp(delta[ch] * a) * s[ch, n] \
                    + delta[ch] * u1[ch] * rbc[1 + n]
            want_y[t, ch] = s[ch] @ rbc[3:5] + w["m_D"][ch] * u1[ch]
    np.testing.assert_allclose(np.asarray(y), want_y, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(out),
                               (want_y * silu(z)) @ w["m_out"],
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("window", [None, 3])
def test_reference_differential_attention_by_hand(window):
    m = {"heads": 4, "kv_heads": 2, "head_dim": 2, "ln_eps": 1e-5}
    rng = np.random.default_rng(4)
    T, l = 6, 5
    q, k, v = (rng.normal(size=(T, h, 2)) for h in (4, 2, 2))
    w = {n: rng.normal(size=(4,)) * 0.3 for n in ("lq1", "lk1", "lq2", "lk2")}
    w["sub"] = rng.normal(size=(4,))
    got = sambay._diff_attention(m, window)(
        *(jnp.asarray(a, jnp.float32) for a in (q, k, v)),
        {n: jnp.asarray(a, jnp.float32) for n, a in w.items()}, l)
    lam = np.exp(w["lq1"] @ w["lk1"]) - np.exp(w["lq2"] @ w["lk2"]) \
        + 0.8 - 0.6 * np.exp(-0.3 * l)

    def soft(qh, kh, t):
        lo = 0 if window is None else max(0, t - window + 1)
        s = np.array([qh[t] @ kh[j] / np.sqrt(2.0) for j in range(lo, t + 1)])
        p = np.exp(s - s.max())
        return lo, p / p.sum()

    want = np.zeros((T, 8))
    vv = np.concatenate([v[:, 0], v[:, 1]], -1)           # the one KV pair
    for t in range(T):
        for i in range(2):                                 # query pairs
            lo, p1 = soft(q[:, 2 * i], k[:, 0], t)
            _, p2 = soft(q[:, 2 * i + 1], k[:, 1], t)
            diff = p1 @ vv[lo:t + 1] - lam * (p2 @ vv[lo:t + 1])
            diff = diff / np.sqrt(np.mean(diff ** 2) + 1e-5)
            want[t, 4 * i:4 * i + 4] = \
                (1 - (0.8 - 0.6 * np.exp(-0.3 * l))) * diff * w["sub"]
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# the programs against the reference, by logits
# ---------------------------------------------------------------------------
def test_dense_prefill_last_position_logits(tiny):
    prompt = prompt_of(WINDOW)
    pool = tiny["model"].new_pool(max_slots=2)
    (_, got), = prefill_logits(tiny["model"], pool, prompt, WINDOW)
    want = reference_logits(tiny["forward"], tiny["params"], prompt)[-1]
    assert np.abs(got - want).max() < TOL


def test_prefill_lanes_are_independent_and_idle_lanes_hit_the_garbage_row(
        tiny):
    model = tiny["model"]
    pool = model.new_pool(max_slots=3)
    a, b = prompt_of(5, 1), prompt_of(8, 2)
    toks = np.zeros((3, WINDOW), np.int32)
    toks[0, :5], toks[2, :8] = a, b
    (cache,) = pool.buffers()
    before = np.asarray(cache["ssm0"][1])
    cache, logits = model.prefill_program(WINDOW)(
        model.params, cache, jnp.asarray(toks),
        jnp.asarray([5, 1, 8], jnp.int32),
        jnp.asarray([2, pool.garbage_row, 0], jnp.int32))
    for lane, p in ((0, a), (2, b)):
        want = reference_logits(tiny["forward"], tiny["params"], p)[-1]
        assert np.abs(np.asarray(logits[lane]) - want).max() < TOL
    np.testing.assert_array_equal(np.asarray(cache["ssm0"][1]), before)


def test_chunk_prefill_carries_state_and_wraps_the_ring(tiny):
    """Four chunks of 6 over a 23-token prompt, window 8: the scan's state
    and the conv tail cross three chunk edges, the ring wraps twice, and
    every chunk's last logits are the full forward's."""
    prompt = prompt_of(23, 5)
    pool = tiny["model"].new_pool(max_slots=1)
    got = prefill_logits(tiny["model"], pool, prompt, 6)
    assert len(got) == 4
    want = reference_logits(tiny["forward"], tiny["params"], prompt)
    for end, logits in got:
        assert np.abs(logits - want[end - 1]).max() < TOL, end


def test_decode_through_the_cache_at_every_step(tiny):
    """3 x window + 2 decode steps after a chunked prompt longer than the
    window: every step's logits against the full forward."""
    gap = decode_gaps(tiny["model"], tiny["forward"], prompt_of(13, 6),
                      3 * WINDOW + 2)
    assert gap < TOL


@pytest.mark.xfail(strict=True, reason="planted fault: the scan's state "
                   "rounded to bfloat16 after every step")
def test_planted_bf16_state_fails_the_tolerance(tiny):
    low = sambay.make_forward(tiny["config"].as_dict(), "bf16_state")
    assert decode_gaps(tiny["model"], low, prompt_of(13, 6),
                       3 * WINDOW + 2) < TOL


@pytest.mark.xfail(strict=True, reason="planted fault of the recurrent state")
@pytest.mark.parametrize("fault", sambay.FAULTS)
def test_planted_state_fault_fails_the_tolerance(tiny, fault):
    bad = sambay.make_forward(tiny["config"].as_dict(), fault)
    assert decode_gaps(tiny["model"], bad, prompt_of(13, 6),
                       3 * WINDOW + 2) < TOL


@pytest.mark.xfail(strict=True, reason="planted fault: a window of 9 keys "
                   "where the reference has 8")
def test_planted_window_off_by_one_fails_the_tolerance(tiny):
    wide = hd.HybridDecoder(hd.HybridConfig(**dict(TINY, window=WINDOW + 1)),
                            params=tiny["params"])
    assert decode_gaps(wide, tiny["forward"], prompt_of(13, 6),
                       3 * WINDOW + 2) < TOL


# ---------------------------------------------------------------------------
# through the engine
# ---------------------------------------------------------------------------
ENGINE = dict(max_slots=3, prefill_lanes=2, prefill_window=6,
              prefill_budget=64, decode_steps=3, prefix_cache_slots=0,
              draft_tokens=0)


def served_gap(tiny, prompt, tokens):
    """How far below the reference's best logit the served tokens lie."""
    return sambay.served_gaps(tiny["forward"], tiny["params"], prompt,
                              tokens, 64).max()


def test_engine_lanes_join_and_leave_mid_wave(tiny):
    """Seven requests of mixed lengths over 3 slots, 2 prefill lanes and
    3-step waves: lanes join while others decode and leave mid-wave; every
    request's tokens are the 1-slot reference's and the plain reference's
    own choice, with no retrace."""
    model = tiny["model"]
    jobs = [(prompt_of(n, 10 + i), out) for i, (n, out) in enumerate(
        [(3, 9), (19, 4), (8, 17), (30, 2), (11, 11), (5, 1), (23, 7)])]
    want = [model.reference_generate(p, n, window=6) for p, n in jobs]
    with serve.ContinuousEngine(model, **ENGINE) as eng:
        futs = [eng.submit(p, n) for p, n in jobs]
        got = [f.result(timeout=120) for f in futs]
        stats = eng.stats()
        assert eng.retraces_after_warmup() == 0
    for (p, n), g, w in zip(jobs, got, want):
        np.testing.assert_array_equal(g, w)
        assert len(g) == n
        assert served_gap(tiny, p, g) < TOL
    cache = stats["cache"]
    assert set(cache) == {"full", "ring", "state"}
    pool = model.new_pool(max_slots=3)
    assert {k: v["bytes"] for k, v in cache.items()} == pool.bytes_by_kind()
    assert all(v["live_bytes_sum"] > 0 for v in cache.values())
    # a full leaf is live by its positions, a ring by at most its capacity
    assert pool.bytes_by_kind([4]) == {
        "full": 2 * 4 * 32 * 4, "ring": 2 * 2 * 4 * 32 * 4,
        "state": 3 * (4 * 128 * 4 + 3 * 128 * 4)}
    assert pool.bytes_by_kind([40])["ring"] == 2 * 2 * 8 * 32 * 4


def test_sampled_request_joins_greedy_waves_and_leaves(tiny):
    """The shared sampler's branch through this model's decode program: no
    wave of a greedy run counts as sampled; a short sampled request (the
    model card's temperature 0.6, top-p 0.95) queues behind greedy ones,
    rides with lanes that are mid-decode and leaves before them; every
    request draws its 1-slot reference's tokens, with no retrace, and
    `sampled_waves` reads the waves that request lived through."""
    model = tiny["model"]
    kw = {"temperature": 0.6, "top_p": 0.95, "seed": 9}
    quiet_jobs = [(prompt_of(n, 30 + i), out, {}) for i, (n, out) in
                  enumerate([(4, 6), (9, 3), (13, 8)])]
    jobs = ([(prompt_of(n, 40 + i), out, {}) for i, (n, out) in
             enumerate([(5, 21), (17, 26), (8, 24), (3, 30)])]
            + [(prompt_of(7, 50), 7, kw)]
            + [(prompt_of(n, 60 + i), out, {}) for i, (n, out) in
               enumerate([(6, 25), (10, 28)])])
    want = [model.reference_generate(p, n, window=6, **k)
            for p, n, k in quiet_jobs + jobs]
    greedy_twin = model.reference_generate(jobs[4][0], 7, window=6)
    assert not np.array_equal(want[len(quiet_jobs) + 4], greedy_twin)
    with serve.ContinuousEngine(model, **ENGINE) as eng:
        got = [f.result(timeout=120) for f in
               [eng.submit(p, n) for p, n, _ in quiet_jobs]]
        quiet = eng.stats()
        assert quiet["decode_iterations"] > 0
        assert quiet["sampled_waves"] == 0
        got += [f.result(timeout=120) for f in
                [eng.submit(p, n, **k) for p, n, k in jobs]]
        busy = eng.stats()
        assert eng.retraces_after_warmup() == 0
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    # one token from prefill, then three a wave: six tokens, two waves
    assert busy["sampled_waves"] == 2
    assert busy["decode_iterations"] - quiet["decode_iterations"] > 2


def test_decode_program_sorts_only_behind_the_conditional(tiny):
    """From the compiled decode program's HLO: the sampler's sort lies in a
    branch computation of the one `conditional` of the scanned micro-step."""
    eng = serve.ContinuousEngine(tiny["model"], **ENGINE)   # never started
    sorts, conditionals, unguarded = sorts_and_conditionals(
        eng.lowered_programs()["decode"].compile())
    assert sorts >= 1 and conditionals == 1
    assert unguarded == [], f"sorts that always run: {unguarded}"


def test_slot_reused_after_a_poison_fill_of_every_leaf(tiny):
    """A freed slot's leaves are not zeroed; the next tenant's prefill at
    offset 0 writes its state without reading it and the masks hide every
    stale position. Poison EVERY leaf between two tenants of one slot: the
    second one's tokens and logits are a fresh pool's."""
    model = tiny["model"]
    p1, p2 = prompt_of(17, 20), prompt_of(21, 21)
    want = model.reference_generate(p2, 12, window=6)
    with serve.ContinuousEngine(model, max_slots=1, prefill_lanes=1,
                                prefill_window=6, decode_steps=2,
                                prefix_cache_slots=0, draft_tokens=0) as eng:
        eng.generate(p1, 5, timeout=120)
        eng.pool.poison(1e9)
        assert all(float(jnp.min(a)) == 1e9 for a in eng.pool.leaves.values())
        got = eng.generate(p2, 12, timeout=120)
    np.testing.assert_array_equal(got, want)
    assert served_gap(tiny, p2, got) < TOL


@pytest.mark.parametrize("leaf", ["ssm1", "conv0", "ring_k1", "shared_v"])
def test_slot_canary_watches_every_kind_of_leaf(tiny, leaf):
    """`MXNET_SANITIZE=slot` claims one row and poisons it in EVERY leaf:
    an idle lane must write the garbage row and keep its state. Clean
    waves are silent; a row that lost its sentinel in any one leaf (as a
    decode step that rewrote an idle lane's state would leave it) fails
    the next wave with the typed error, and the engine serves on."""
    from incubator_mxnet_tpu import sanitize
    with sanitize.scope("slot"):
        with serve.ContinuousEngine(tiny["model"], max_slots=3,
                                    prefill_window=6, decode_steps=2,
                                    prefix_cache_slots=0,
                                    draft_tokens=0) as eng:
            assert eng._canary is not None
            eng.generate(prompt_of(9, 30), 7, timeout=120)
            assert eng._canary.waves > 0
            eng.pool.leaves[leaf] = eng.pool.leaves[leaf].at[
                eng._canary.slot].set(0)
            with pytest.raises(sanitize.SlotCanaryError, match=leaf):
                eng.submit(prompt_of(5, 31), 6).result(timeout=120)
            assert len(eng.generate(prompt_of(4, 32), 3, timeout=120)) == 3


@pytest.mark.parametrize("option, value", [("prefix_cache_slots", 2),
                                           ("draft_tokens", 2),
                                           ("kv_dtype", "int8")])
def test_engine_refuses_what_needs_rows_of_k_and_v(tiny, option, value):
    kw = dict(max_slots=2, prefix_cache_slots=0, draft_tokens=0,
              kv_dtype=None)
    kw[option] = value
    with pytest.raises(CacheKindError, match="ring and state"):
        serve.ContinuousEngine(tiny["model"], **kw)
    assert issubclass(CacheKindError, serve.ServeError)
    # the classic decoder's spec is all `full`: nothing is refused
    classic = serve.CachedDecoder(serve.DecoderConfig(max_len=32))
    assert {leaf.kind for leaf in classic.cache_spec()} == {"full"}
    assert [leaf.name for leaf in classic.cache_spec("int8")] == [
        "k", "v", "k_scale", "v_scale"]


def test_every_program_names_its_layers_by_kind(tiny):
    model = tiny["model"]
    with serve.ContinuousEngine(model, max_slots=2, prefill_window=6,
                                prefix_cache_slots=0, draft_tokens=0) as eng:
        lowered = eng.lowered_programs()
    scopes = ["layer0/mamba", "layer1/swa", "layer4/mamba", "layer5/full",
              "layer6/gmu", "layer7/cross", "layer7/mlp", "sampler"]
    text = lowered["decode"].as_text(debug_info=True)
    assert all(s in text for s in scopes), [s for s in scopes if s not in text]
    text = lowered["prefill"].as_text(debug_info=True)
    assert all(s in text for s in scopes[:-1])


# ---------------------------------------------------------------------------
# the paged read's new modes
# ---------------------------------------------------------------------------
def leaf_case(dtype=jnp.float32):
    key = jax.random.PRNGKey(0)
    S, H, D, T, HKV = 5, 8, 16, 64, 2
    q = jax.random.normal(key, (S, 1, H, D), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (S + 1, T, HKV * D),
                          dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (S + 1, T, HKV * D),
                          dtype)
    return q, k, v, jnp.asarray([0, 3, 17, 40, 63], jnp.int32)


@pytest.mark.parametrize("rows", [None, [4, 2, 0, 1, 5]])
def test_shared_leaf_kernel_against_the_oracle(rows):
    """More query heads than KV heads over a named leaf, rows as data, a
    given scale, float32 out: the Pallas kernel (interpret mode) against
    the jnp composition, and the composition against a loop by hand."""
    q, k, v, lens = leaf_case()
    rows = None if rows is None else jnp.asarray(rows, jnp.int32)
    before = fused.fused_stats()
    want = fused.paged_attention_ref(q, k, v, lens, None, rows=rows,
                                     scale=0.3)
    got = fused.paged_attention(q, k, v, lens, None, rows=rows, scale=0.3,
                                out_dtype=jnp.float32, interpret=True)
    after = fused.fused_stats()
    assert after["paged_shared_traces"] == before["paged_shared_traces"] + 1
    assert after["pallas_calls"] == before["pallas_calls"] + 1
    assert after["fallback_calls"] == before["fallback_calls"]
    assert after["paged_flat_traces"] == before["paged_flat_traces"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    s, g = 2, 5                      # lane 2, query head 5 -> KV head 1
    row = s if rows is None else int(rows[s])
    n = int(lens[s]) + 1
    kk = np.asarray(k[row, :n]).reshape(n, 2, 16)[:, g // 4]
    vv = np.asarray(v[row, :n]).reshape(n, 2, 16)[:, g // 4]
    sc = np.asarray(q[s, 0, g]) @ kk.T * 0.3
    p = np.exp(sc - sc.max())
    np.testing.assert_allclose(np.asarray(want)[s, 0, g], p / p.sum() @ vv,
                               atol=2e-6)


def test_shared_leaf_kernel_in_bfloat16_with_chunk_queries():
    q, k, v, lens = leaf_case(jnp.bfloat16)
    q3 = jnp.concatenate([q, q * 0.5, -q], 1)            # C == 3
    lens = jnp.minimum(lens, 60)
    want = fused.paged_attention_ref(q3, k, v, lens, None,
                                     out_dtype=jnp.float32)
    got = fused.paged_attention(q3, k, v, lens, None, interpret=True,
                                out_dtype=jnp.float32)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-2)


def test_window_read_over_a_ring_against_a_loop_by_hand():
    q, k, v, lens = leaf_case()
    window = cap = 8
    ring_k = np.zeros((6, cap, 32), np.float32)
    ring_v = np.zeros((6, cap, 32), np.float32)
    for s in range(5):
        for pos in range(int(lens[s]) + 1):               # position p at p % cap
            ring_k[s, pos % cap] = k[s, pos]
            ring_v[s, pos % cap] = v[s, pos]
    before = fused.fused_stats()["paged_window_traces"]
    got = fused.paged_attention(q, jnp.asarray(ring_k), jnp.asarray(ring_v),
                                lens, None, window=window, scale=0.3)
    assert fused.fused_stats()["paged_window_traces"] == before + 1
    for s in range(5):
        n = int(lens[s])
        lo = max(0, n - window + 1)
        for g in (0, 6):
            kk = np.asarray(k[s, lo:n + 1]).reshape(-1, 2, 16)[:, g // 4]
            vv = np.asarray(v[s, lo:n + 1]).reshape(-1, 2, 16)[:, g // 4]
            sc = np.asarray(q[s, 0, g]) @ kk.T * 0.3
            p = np.exp(sc - sc.max())
            np.testing.assert_allclose(np.asarray(got)[s, 0, g],
                                       p / p.sum() @ vv, atol=2e-6)
    with pytest.raises(ValueError):
        fused.paged_attention(jnp.concatenate([q, q], 1), jnp.asarray(ring_k),
                              jnp.asarray(ring_v), lens, None, window=window)
    with pytest.raises(ValueError):                       # leaf options, slab
        fused.paged_attention(q, jnp.zeros((6, 1, 64, 8, 16)),
                              jnp.zeros((6, 1, 64, 8, 16)), lens, 0,
                              window=window)


def test_engine_tokens_are_the_same_through_the_kernel(tiny):
    """The decode and prefill programs with the shared read in the Pallas
    kernel (interpret mode) serve the tokens of the jnp composition."""
    model = hd.HybridDecoder(tiny["config"], params=tiny["params"])
    prompt = prompt_of(14, 30)
    want = tiny["model"].reference_generate(prompt, 6, window=6)
    fused.set_interpret(True)
    try:
        before = fused.fused_stats()
        got = model.reference_generate(prompt, 6, window=6)
        after = fused.fused_stats()
    finally:
        fused.set_interpret(None)
    np.testing.assert_array_equal(got, want)
    assert after["fallback_calls"] == before["fallback_calls"]
    assert after["pallas_calls"] > before["pallas_calls"]
