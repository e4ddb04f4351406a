"""serve.decode: sampled + speculative decoding and the paged-attention
kernel over the slotted KV pool.

Contracts under test (ISSUE 17 acceptance):
  * temperature/top-k/top-p sampling is ARRAY DATA: mixed greedy/sampled
    traffic shares one compiled decode program (zero retraces), and a
    sampled request is deterministic in its seed — the engine matches the
    scheduling-free seeded reference token-for-token because the draw key
    is a pure function of (seed, cache position), never of wave schedule
  * `_sample_tokens` draws from the right distribution (chi-square over
    >= 10k draws against known logits) and top-k/top-p truncate support
    exactly
  * speculative decoding emits EXACTLY the tokens plain decode would
    (exact-verification acceptance), for greedy and sampled lanes alike,
    with per-lane acceptance counts as in-scan data — acceptance-rate
    variance across lanes never retraces, and eos inside an accepted
    draft block keeps exact token accounting
  * the Pallas paged-attention kernel (interpret mode on CPU CI) matches
    the masked-einsum reference to float tolerance, reads int8 slabs via
    per-position dequant scales, and slot poison-fill cannot leak across
    lanes through the kernel's clamped block reads
  * int8 KV halves slab bytes (slots_per_gb >= 2x float32) without
    changing tokens vs the int8 reference, and the quantized pool shape
    shows up in the engine's memory plans
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hlo_branches import sorts_and_conditionals

from incubator_mxnet_tpu import profiler, serve
from incubator_mxnet_tpu.ops import fused as F
from incubator_mxnet_tpu.ops import pallas_kernels as PK
from incubator_mxnet_tpu.serve import sampling
from incubator_mxnet_tpu.serve.continuous import _sample_tokens, _seed_key

CFG = dict(vocab=64, embed=32, layers=2, heads=4, head_dim=8, max_len=48)


@pytest.fixture(scope="module")
def decoder():
    """One small CachedDecoder + a weight-sharing reference twin (its own
    jits, so reference calls never touch the engine's compile caches)."""
    cfg = serve.DecoderConfig(**CFG)
    model = serve.CachedDecoder(cfg, seed=3)
    ref = serve.CachedDecoder(cfg, params=model.params)
    return model, ref


@pytest.fixture(scope="module")
def spec_engine(decoder):
    """Shared speculative engine (draft=2): spec-vs-plain token equality
    and acceptance-variance tests reuse one warmup. Built on a PRIVATE
    weight-sharing model so other tests compiling programs on the shared
    model cannot pollute this engine's retrace counter."""
    model, _ = decoder
    twin = serve.CachedDecoder(serve.DecoderConfig(**CFG),
                               params=model.params)
    eng = serve.ContinuousEngine(twin, max_slots=4, decode_steps=2,
                                 draft_tokens=2).start()
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def int8_engine(decoder):
    """Shared int8-KV speculative engine: quantized slab + draft path
    (private weight-sharing model, same reason as spec_engine). The
    prefill window is SMALLER than max_len so slot positions past the
    window keep stale bytes — the poison-isolation test relies on the
    decode mask being the only guard."""
    model, _ = decoder
    twin = serve.CachedDecoder(serve.DecoderConfig(**CFG),
                               params=model.params)
    eng = serve.ContinuousEngine(twin, max_slots=4, decode_steps=2,
                                 draft_tokens=2, prefill_window=16,
                                 kv_dtype="int8").start()
    yield eng
    eng.close()


def _workload(n, seed=0, vocab=64, max_new_hi=20):
    rng = np.random.RandomState(seed)
    return [(rng.randint(1, vocab, size=rng.randint(2, 12)).tolist(),
             int(rng.randint(1, max_new_hi))) for _ in range(n)]


# ---------------------------------------------------------------------------
# sampling as data: engine == seeded reference, one program for all lanes
# ---------------------------------------------------------------------------
def test_mixed_greedy_sampled_matches_reference_zero_retraces(decoder):
    """Greedy and sampled requests interleave in ONE compiled program;
    every sampled lane reproduces the seeded reference exactly (the draw
    key depends on (seed, position), not on which wave served it)."""
    model, ref = decoder
    work = _workload(8, seed=1)
    mix = [
        {} if i % 2 == 0
        else {"temperature": 3.0, "top_k": 8, "seed": 100 + i}
        for i in range(len(work))]
    before = profiler.serve_stats()
    with serve.ContinuousEngine(model, max_slots=4, decode_steps=3) as eng:
        warm_ccs = eng.compile_cache_size()
        warm_programs = profiler.serve_stats()["programs_compiled"]
        futs = [eng.submit(p, m, **kw)
                for (p, m), kw in zip(work, mix)]
        outs = [f.result(timeout=120) for f in futs]
        assert eng.assert_no_retraces() == 0
        assert eng.compile_cache_size() == warm_ccs
        assert profiler.serve_stats()["programs_compiled"] == warm_programs
        stats = eng.stats()
    assert 0 < stats["sampled_waves"] <= stats["decode_iterations"]
    for (p, m), kw, o in zip(work, mix, outs):
        np.testing.assert_array_equal(
            o, ref.reference_generate(p, m, **kw),
            err_msg=f"engine diverged for prompt {p} sampling {kw}")
        assert len(o) == m
    # only temperature > 0 lanes count as sampled
    sampled_max_new = sum(m for (_, m), kw in zip(work, mix) if kw)
    after = profiler.serve_stats()
    delta = after["decode_sampled_tokens"] - before["decode_sampled_tokens"]
    assert 0 < delta <= sampled_max_new


def test_seed_determinism_and_divergence(decoder):
    """Same seed -> identical tokens; across seeds at high temperature
    the outputs actually diverge (the PRNG is live, not a greedy alias)."""
    _, ref = decoder
    prompt, m = [9, 4, 33, 2], 12
    a = ref.reference_generate(prompt, m, temperature=8.0, seed=7)
    b = ref.reference_generate(prompt, m, temperature=8.0, seed=7)
    np.testing.assert_array_equal(a, b)
    outs = {tuple(int(t) for t in
                  ref.reference_generate(prompt, m, temperature=8.0,
                                         seed=s))
            for s in range(10)}
    assert len(outs) >= 4, f"only {len(outs)} distinct outputs at T=8"


def test_sample_tokens_distribution_chi_square():
    """>= 10k draws from fixed logits land on the known distribution
    (chi-square, df=7), greedy lanes return argmax, and top-k / top-p
    truncate the support exactly."""
    probs = np.array([0.4, 0.3, 0.1, 0.1, 0.05, 0.03, 0.01, 0.01])
    n = 20000
    logits = jnp.asarray(np.tile(np.log(probs), (n, 1)),
                         dtype=jnp.float32)
    keys = jnp.asarray(np.tile(_seed_key(123), (n, 1)))
    positions = jnp.arange(n, dtype=jnp.int32)
    ones = jnp.ones((n,), dtype=jnp.float32)
    zeros_i = jnp.zeros((n,), dtype=jnp.int32)

    draws = np.asarray(_sample_tokens(logits, ones, zeros_i, ones, keys,
                                      positions))
    counts = np.bincount(draws, minlength=len(probs))
    chi2 = float(np.sum((counts - n * probs) ** 2 / (n * probs)))
    assert chi2 < 30.0, f"chi2={chi2:.2f} counts={counts.tolist()}"

    greedy = np.asarray(_sample_tokens(
        logits, jnp.zeros((n,), jnp.float32), zeros_i, ones, keys,
        positions))
    assert (greedy == int(np.argmax(probs))).all()

    topk = np.asarray(_sample_tokens(
        logits, ones, jnp.full((n,), 2, jnp.int32), ones, keys,
        positions))
    assert set(np.unique(topk)) == {0, 1}
    # nucleus 0.69 keeps exactly {0.4, 0.3}: csum passes 0.69 at token 1
    topp = np.asarray(_sample_tokens(
        logits, ones, zeros_i, jnp.full((n,), 0.69, jnp.float32), keys,
        positions))
    assert set(np.unique(topp)) == {0, 1}


def _always_sort(logits, temps, top_ks, top_ps, keys, positions):
    """The sampler as it stood before its branch: every lane sorted,
    truncated and drawn, greedy lanes selected at the last line. The oracle
    `sample_tokens` has to agree with element for element."""
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    ids = jax.lax.broadcasted_iota(jnp.int32, scaled.shape, 1)
    neg, order = jax.lax.sort_key_val(-scaled, ids, dimension=1)
    srt = -neg
    kth = jnp.take_along_axis(
        srt, jnp.clip(top_ks - 1, 0, V - 1)[:, None], axis=-1)
    keep_k = (top_ks[:, None] <= 0) | (srt >= kth)
    probs = jax.nn.softmax(srt, axis=-1)
    csum = jnp.cumsum(probs, axis=-1)
    keepn = jnp.sum((csum - probs) < top_ps[:, None], axis=-1)
    pth = jnp.take_along_axis(
        srt, jnp.clip(keepn - 1, 0, V - 1)[:, None], axis=-1)
    masked = jnp.where(keep_k & (srt >= pth), srt, -1e30)
    kfold = jax.vmap(jax.random.fold_in)(keys, positions)
    rank = jax.vmap(
        lambda kk, lg: jax.random.categorical(kk, lg))(kfold, masked)
    sampled = jnp.take_along_axis(order, rank[:, None], axis=-1)[:, 0]
    return jnp.where(temps > 0, sampled.astype(jnp.int32), greedy)


def _sampler_case(mix, dtype, lanes=16, vocab=1000, seed=0):
    """Random lanes for the sampler: temperatures by `mix`, random top-k,
    top-p, keys and positions, one row whose maximum is tied."""
    rng = np.random.RandomState(seed)
    logits = 3.0 * rng.standard_normal((lanes, vocab))
    logits[3, [17, vocab // 2, vocab - 1]] = logits[3].max() + 1.0
    warm = {"all_greedy": np.zeros(lanes, bool),
            "mixed": rng.rand(lanes) < 0.5,
            "all_sampled": np.ones(lanes, bool)}[mix]
    if mix == "mixed":
        warm[3], warm[4] = True, False
    temps = np.where(warm, rng.uniform(0.3, 2.0, lanes), 0.0)
    return (jnp.asarray(logits, dtype=dtype),
            jnp.asarray(temps, dtype=jnp.float32),
            jnp.asarray(rng.choice([0, 1, 5, 50], lanes), dtype=jnp.int32),
            jnp.asarray(rng.choice([1.0, 0.9, 0.5], lanes),
                        dtype=jnp.float32),
            jnp.asarray(rng.randint(0, 2 ** 31, (lanes, 2)),
                        dtype=jnp.uint32),
            jnp.asarray(rng.randint(0, 2048, lanes), dtype=jnp.int32))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("mix", ["all_greedy", "mixed", "all_sampled"])
def test_sample_tokens_matches_the_always_sort_oracle(mix, dtype):
    """The branch changes no answer: alone and as the tail of a scanned
    micro-step (positions advancing), `sample_tokens` returns the always-
    sort formulation's tokens, lane for lane."""
    args = _sampler_case(mix, dtype)
    want = np.asarray(jax.jit(_always_sort)(*args))
    np.testing.assert_array_equal(
        np.asarray(jax.jit(_sample_tokens)(*args)), want)
    np.testing.assert_array_equal(np.asarray(_sample_tokens(*args)), want)
    greedy = np.asarray(args[1]) == 0
    np.testing.assert_array_equal(
        want[greedy], np.argmax(np.asarray(args[0], np.float32), -1)[greedy])
    if mix == "all_greedy":
        assert want[3] == 17                    # first of the tied maxima

    def scanned(fn):
        def run(logits, temps, top_ks, top_ps, keys, positions):
            def step(pos, _):
                return pos + 1, fn(logits, temps, top_ks, top_ps, keys, pos)
            return jax.lax.scan(step, positions, None, length=3)[1]
        return np.asarray(jax.jit(run)(*args))
    np.testing.assert_array_equal(scanned(_sample_tokens),
                                  scanned(_always_sort))


@pytest.mark.parametrize("draft", [0, 2], ids=["plain", "spec"])
def test_sampled_request_joins_greedy_waves_and_leaves(decoder, draft):
    """`stats()["sampled_waves"]` stays 0 over a greedy run; then a short
    sampled request queues behind greedy ones, takes a slot while other
    lanes are mid-decode, and leaves before them: every request still
    draws its reference's tokens, nothing retraces, and the counter reads
    the waves the sampled request lived through and no other."""
    model, ref = decoder
    twin = serve.CachedDecoder(serve.DecoderConfig(**CFG),
                               params=model.params)
    K, kw = 2, {"temperature": 1.5, "top_k": 8, "top_p": 0.9, "seed": 77}
    with serve.ContinuousEngine(twin, max_slots=4, decode_steps=K,
                                draft_tokens=draft) as eng:
        first = _workload(5, seed=21)
        outs = [f.result(timeout=120)
                for f in [eng.submit(p, m) for p, m in first]]
        quiet = eng.stats()
        assert quiet["decode_iterations"] > 0
        assert quiet["sampled_waves"] == 0
        jobs = ([(p, m + 20, {}) for p, m in _workload(5, seed=22)]
                + [([3, 4, 5], 7, kw)]
                + [(p, m + 20, {}) for p, m in _workload(3, seed=23)])
        outs += [f.result(timeout=120)
                 for f in [eng.submit(p, m, **k) for p, m, k in jobs]]
        busy = eng.stats()
        assert eng.assert_no_retraces() == 0
    for (p, m, k), o in zip([(p, m, {}) for p, m in first] + jobs, outs):
        np.testing.assert_array_equal(
            o, ref.reference_generate(p, m, **k),
            err_msg=f"engine diverged for prompt {p} sampling {k}")
    waves = busy["sampled_waves"]
    # the first token comes from prefill; plain decode then emits K a wave
    assert (waves == 3) if not draft else (1 <= waves <= 6)
    assert waves < busy["decode_iterations"] - quiet["decode_iterations"]


def _vmapped_sampler(*args):
    """What the branch must never become: under `vmap` a `lax.cond` is a
    select and both sides run."""
    return jax.vmap(
        lambda *row: _sample_tokens(*(a[None] for a in row))[0])(*args)


@pytest.mark.parametrize("program", ["sample_first", "decode", "spec_decode",
                                     "vmapped"])
def test_the_sort_lies_behind_one_conditional(decoder, program):
    """From the compiled HLO: every `sort` lies inside a branch computation
    of a `conditional` and there is one conditional per sampler call (the
    decode programs call it once, in the scanned micro-step). The vmapped
    sampler is the control that the check can fail."""
    model, _ = decoder
    args = _sampler_case("all_greedy", "float32", lanes=4, vocab=CFG["vocab"])
    if program == "sample_first":
        sampling.sample_first(*args)
        compiled = sampling._SAMPLE_JIT.lower(*args).compile()
    elif program == "vmapped":
        compiled = jax.jit(_vmapped_sampler).lower(*args).compile()
    else:
        eng = serve.ContinuousEngine(          # never started: shapes only
            model, max_slots=4, decode_steps=3,
            draft_tokens=2 if program == "spec_decode" else 0)
        compiled = eng.lowered_programs()["decode"].compile()
    sorts, conditionals, unguarded = sorts_and_conditionals(compiled)
    assert sorts >= 1, "the sampled body is not in the program"
    if program == "vmapped":
        assert conditionals == 0 and unguarded
    else:
        assert conditionals == 1
        assert unguarded == [], f"sorts that always run: {unguarded}"


def test_submit_validates_sampling_params(decoder):
    model, _ = decoder
    eng = serve.ContinuousEngine(model, max_slots=2)   # never started
    with pytest.raises(serve.ServeError, match="temperature"):
        eng.submit([1, 2], 4, temperature=-0.5)
    with pytest.raises(serve.ServeError, match="top_k"):
        eng.submit([1, 2], 4, temperature=1.0, top_k=-1)
    with pytest.raises(serve.ServeError, match="top_p"):
        eng.submit([1, 2], 4, temperature=1.0, top_p=0.0)
    with pytest.raises(serve.ServeError, match="top_p"):
        eng.submit([1, 2], 4, temperature=1.0, top_p=1.5)


# ---------------------------------------------------------------------------
# speculative decoding: exact verification, acceptance counters, eos
# ---------------------------------------------------------------------------
def test_spec_decode_token_exact_vs_plain_reference(decoder, spec_engine):
    """The whole point of exact-verification: speculative decode is a
    pure SPEED change. Greedy and sampled lanes through the draft+verify
    engine emit byte-identical tokens to the plain (draft=0) reference,
    and the acceptance counters actually move."""
    _, ref = decoder
    work = _workload(10, seed=2)
    sampling = [
        {} if i % 3 else {"temperature": 3.0, "top_k": 8, "seed": 50 + i}
        for i in range(len(work))]
    before = profiler.serve_stats()
    futs = [spec_engine.submit(p, m, **kw)
            for (p, m), kw in zip(work, sampling)]
    outs = [f.result(timeout=120) for f in futs]
    assert spec_engine.assert_no_retraces() == 0
    for (p, m), kw, o in zip(work, sampling, outs):
        np.testing.assert_array_equal(
            o, ref.reference_generate(p, m, **kw),
            err_msg=f"spec engine diverged for prompt {p} sampling {kw}")
    after = profiler.serve_stats()
    acc = after["decode_draft_accepted"] - before["decode_draft_accepted"]
    rej = after["decode_draft_rejected"] - before["decode_draft_rejected"]
    assert acc > 0, "no draft tokens accepted on a repetitive workload"
    assert acc + rej > 0
    st = spec_engine.stats()
    assert st["draft_tokens"] == 2
    assert 0.0 < st["draft_acceptance"] <= 1.0
    assert json.dumps(st)


def test_spec_reference_matches_plain_reference(decoder):
    """reference_generate(draft_tokens=k) — the one-wave-at-a-time
    speculative oracle — is itself token-exact against plain decode."""
    _, ref = decoder
    for prompt, m in _workload(4, seed=9, max_new_hi=14):
        plain = ref.reference_generate(prompt, m)
        for k in (1, 3):
            np.testing.assert_array_equal(
                plain, ref.reference_generate(prompt, m, draft_tokens=k),
                err_msg=f"draft={k} diverged for prompt {prompt}")


def test_spec_eos_mid_draft_block_exact_accounting(decoder):
    """eos emitted INSIDE an accepted draft block truncates the block
    (tokens after eos are discarded), frees the lane, and matches the
    plain-decode eos contract exactly."""
    model, ref = decoder
    prompt, max_new = [7, 3, 19], 16
    base = ref.reference_generate(prompt, max_new)
    eos = int(base[len(base) // 2])
    expect = ref.reference_generate(prompt, max_new, eos_id=eos)
    assert len(expect) < len(base)
    np.testing.assert_array_equal(
        expect,
        ref.reference_generate(prompt, max_new, eos_id=eos,
                               draft_tokens=2))
    eng = serve.ContinuousEngine(model, max_slots=2, decode_steps=3,
                                 eos_id=eos, draft_tokens=2).start()
    try:
        out = eng.generate(prompt, max_new, timeout=120)
        assert eng.assert_no_retraces() == 0
    finally:
        eng.close()
    np.testing.assert_array_equal(out, expect)
    assert out[-1] == eos


def test_spec_acceptance_variance_never_retraces(decoder, spec_engine):
    """Lanes accepting 0..k draft tokens per wave is pure DATA: ragged
    traffic with wildly different acceptance behavior replays the same
    two compiled programs."""
    _, ref = decoder
    warm_ccs = spec_engine.compile_cache_size()
    warm_programs = profiler.serve_stats()["programs_compiled"]
    work = _workload(14, seed=11, max_new_hi=16)
    futs = [spec_engine.submit(p, m) for p, m in work]
    outs = [f.result(timeout=120) for f in futs]
    assert spec_engine.assert_no_retraces() == 0
    assert spec_engine.compile_cache_size() == warm_ccs
    assert profiler.serve_stats()["programs_compiled"] == warm_programs
    for (p, m), o in zip(work, outs):
        np.testing.assert_array_equal(o, ref.reference_generate(p, m))


# ---------------------------------------------------------------------------
# paged-attention kernel: interpret-mode exactness, routing counters
# ---------------------------------------------------------------------------
def _paged_oracle(q, k, v, lengths):
    """float64 numpy evaluation of the masked read, lane by lane."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    T, D = k.shape[1], q.shape[-1]
    scores = np.einsum("schd,sthd->shct", q, k) / np.sqrt(D)
    reach = lengths[:, None, None] + np.arange(q.shape[1])[None, :, None]
    scores = np.where((np.arange(T)[None, None, :] <= reach)[:, None],
                      scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    return np.einsum("shct,sthd->schd", p / p.sum(-1, keepdims=True), v)


# bf16 slabs: the largest distance from the float64 oracle that the kernel
# this one replaced (grid (S, T/bt), float32 casts, `pl.when`-skipped dead
# blocks) read on the same inputs, by (heads, chunk); the kernel may not be
# further away. Nearly all of it is the output's own rounding to bf16.
_PAGED_BF16_BOUND = {
    ((4, 8), 1): 0.000748, ((4, 8), 3): 0.003818,
    ((2, 128), 1): 0.000975, ((2, 128), 3): 0.006951,
    ((16, 32), 1): 0.001561, ((16, 32), 3): 0.007553,
}


@pytest.mark.parametrize("heads", [(4, 8), (2, 128), (16, 32)],
                         ids=["h4x8", "h2x128-lane-width", "h16x32-flat"])
@pytest.mark.parametrize("kv", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("C", [1, 3], ids=["C1", "C3-verify"])
def test_paged_attention_kernel_matches_ref_interpret(C, kv, heads):
    """Pallas kernel (interpret mode) vs the references over a
    multi-block slab (T=384 -> three 128-wide blocks, the narrowest an
    int8 slab's scale blocks may be on a TPU): chunk widths 1 (plain
    decode) and 3 (speculative verify); float32, bf16 and int8+scales;
    heads that take the head-major body ((4, 8), (2, 128)) and the flat
    one ((16, 32): whole sublane tiles); lanes at cache lengths 0,
    bt - 1, bt, bt + 1 and T - C, the last beside an inactive lane."""
    H, D = heads
    T, L, bt = 384, 2, 128
    lengths = np.asarray([0, bt - 1, bt, bt + 1, T - C, 0], np.int32)
    S = len(lengths)
    rng = np.random.RandomState(0)
    layer = 1               # non-zero: the slab's layer stride is live
    slab = (S + 1, L, T, H, D)
    scales = {}
    if kv == "int8":
        k_slab, v_slab = (jnp.asarray(rng.randint(
            -127, 128, slab, dtype=np.int64).astype(np.int8))
            for _ in range(2))
        scales = {n: jnp.asarray(
            (rng.rand(S + 1, L, T) * 0.1 + 0.01).astype(np.float32))
            for n in ("k_scale", "v_scale")}
        dtype = jnp.float32
    else:
        dtype = jnp.dtype(kv)
        k_slab, v_slab = (jnp.asarray(rng.randn(*slab), dtype)
                          for _ in range(2))
    q = jnp.asarray(rng.randn(S, C, H, D), dtype)
    assert PK._paged_blocks(T, C, H, D, q.dtype.itemsize,
                            k_slab.dtype.itemsize,
                            L if kv == "int8" else 0) == bt
    out = PK.paged_attention_fwd(q, k_slab, v_slab, jnp.asarray(lengths),
                                 layer, interpret=True, **scales)
    assert out is not None and out.dtype == q.dtype
    body = PK.paged_body(q, k_slab, scales.get("k_scale"))
    assert body == ("flat" if heads == (16, 32) and kv != "int8"
                    else "head_major")
    if kv == "bfloat16":
        want = _paged_oracle(q, k_slab[:S, layer], v_slab[:S, layer],
                             lengths)
        err = np.abs(np.asarray(out, np.float64) - want).max()
        assert err <= _PAGED_BF16_BOUND[heads, C], err
    else:
        ref = F.paged_attention_ref(q, k_slab, v_slab,
                                    jnp.asarray(lengths), layer, **scales)
        # 2e-5 everywhere but int8 at 128-wide heads: 128-term float32 sums
        # of dequantized values up to 12 in a different order; the kernel
        # this one replaced needs 4.5e-5 on the same inputs, to the digit
        atol = 6e-5 if (kv, heads) == ("int8", (2, 128)) else 2e-5
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=atol)


@pytest.mark.parametrize("q_dtype,kv_dtype,H,body", [
    ("bfloat16", "float32", 8, "head_major"),
    ("bfloat16", "float32", 16, "flat"),
    ("float32", "bfloat16", 8, "head_major"),
    ("float32", "float32", 8, "flat"),
    ("bfloat16", "bfloat16", 48, "flat"),
    ("bfloat16", "bfloat16", 96, "head_major")],
    ids=["bf16q-f32kv-h8", "bf16q-f32kv-h16", "f32q-bf16kv-h8", "f32-h8",
         "bf16-h48-144-rows", "bf16-h96-288-rows"])
def test_paged_attention_body_follows_the_narrower_dtype(q_dtype, kv_dtype,
                                                         H, body):
    """Queries and slab of different widths: the flat body views both the
    (C, H, D) queries and the (bt, H, D) tile as matrices, so H has to be
    whole sublane tiles of the NARROWER dtype (16 rows of bf16, 8 of
    float32), and C*H within the 256 rows up to which it was measured to
    win; either body reads the same answer."""
    S, C, D, T, L, layer = 3, 3, 32, 256, 2, 1
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(S, C, H, D), jnp.dtype(q_dtype))
    k_slab, v_slab = (jnp.asarray(rng.randn(S + 1, L, T, H, D),
                                  jnp.dtype(kv_dtype)) for _ in range(2))
    lengths = np.asarray([0, 127, T - C], np.int32)
    assert PK.paged_body(q, k_slab) == body
    out = PK.paged_attention_fwd(q, k_slab, v_slab, jnp.asarray(lengths),
                                 layer, interpret=True)
    assert out is not None and out.dtype == q.dtype
    want = _paged_oracle(q, k_slab[:S, layer], v_slab[:S, layer], lengths)
    # a bf16 output carries its own rounding: half an ulp of values to 4
    tol = 2e-5 if q_dtype == "float32" else 2.0 ** -7
    np.testing.assert_allclose(np.asarray(out, np.float64), want,
                               rtol=tol, atol=tol)


def test_paged_attention_routing_and_counters():
    """fused.paged_attention routes to the Pallas kernel under interpret
    (pallas_calls) and to the reference off-TPU (fallback_calls); the
    per-trace dispatch counter moves either way."""
    S, C, H, D, T = 2, 1, 4, 8, 16
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(S, C, H, D).astype(np.float32))
    slab = jnp.asarray(rng.randn(S + 1, 1, T, H, D).astype(np.float32))
    lengths = jnp.asarray([3, 9], dtype=jnp.int32)

    F.fused_stats(reset=True)
    ref_out = F.paged_attention(q, slab, slab, lengths, 0)
    st = F.fused_stats(reset=True)
    assert st["paged_attention_calls"] == 1
    assert st["fallback_calls"] == 1 and st["pallas_calls"] == 0

    prev = F.set_interpret(True)
    try:
        k_out = F.paged_attention(q, slab, slab, lengths, 0)
    finally:
        F.set_interpret(prev)
    st = F.fused_stats(reset=True)
    assert st["paged_attention_calls"] == 1
    assert st["pallas_calls"] == 1 and st["fallback_calls"] == 0
    # four heads are no whole sublane tile: the head-major body
    assert (st["paged_head_major_traces"], st["paged_flat_traces"]) == (1, 0)
    np.testing.assert_allclose(k_out, ref_out, rtol=2e-5, atol=2e-5)


def test_engine_on_interpret_kernel_path_poison_isolation():
    """End-to-end engine traffic THROUGH the Pallas kernel (interpret
    mode, CPU CI): outputs match the reference running on the same
    routing, slot poison-fill never leaks into any lane (the kernel's
    clamped block reads honor [0, cur_len)), and pallas_calls prove the
    kernel actually ran."""
    prev = F.set_interpret(True)
    F.fused_stats(reset=True)
    try:
        # model built INSIDE the scope: kernel routing is decided at
        # trace time, so both engine and reference trace the kernel path
        model = serve.CachedDecoder(serve.DecoderConfig(**CFG), seed=3)
        work = _workload(4, seed=5, max_new_hi=8)
        with serve.ContinuousEngine(model, max_slots=2, decode_steps=2,
                                    prefill_window=16) as eng:
            eng.pool.poison(1e9)
            futs = [eng.submit(p, m) for p, m in work]
            outs = [f.result(timeout=120) for f in futs]
        expect = [model.reference_generate(p, m, window=16)
                  for p, m in work]
        st = F.fused_stats(reset=True)
        assert st["pallas_calls"] > 0
        assert st["paged_attention_calls"] > 0
    finally:
        F.set_interpret(prev)
    for (p, m), o, e in zip(work, outs, expect):
        np.testing.assert_array_equal(
            o, e, err_msg=f"poison leaked through the kernel for {p}")


# ---------------------------------------------------------------------------
# int8 KV: token parity, density, poison isolation, memory plans
# ---------------------------------------------------------------------------
def test_int8_engine_matches_int8_reference(decoder, int8_engine):
    """int8 slab + speculative decode: engine tokens equal the int8
    reference (same quantized math, scheduling-free)."""
    _, ref = decoder
    work = _workload(8, seed=4)
    sampling = [
        {} if i % 2 else {"temperature": 3.0, "top_k": 8, "seed": 70 + i}
        for i in range(len(work))]
    futs = [int8_engine.submit(p, m, **kw)
            for (p, m), kw in zip(work, sampling)]
    outs = [f.result(timeout=120) for f in futs]
    assert int8_engine.assert_no_retraces() == 0
    for (p, m), kw, o in zip(work, sampling, outs):
        np.testing.assert_array_equal(
            o, ref.reference_generate(p, m, kv_dtype="int8", **kw),
            err_msg=f"int8 engine diverged for prompt {p} sampling {kw}")
    assert int8_engine.stats()["pool"]["dtype"] == "int8"


def test_int8_pool_doubles_slots_per_gb(decoder, int8_engine):
    model, _ = decoder
    fp32 = model.new_pool(max_slots=4)
    ratio = int8_engine.pool.slots_per_gb() / fp32.slots_per_gb()
    assert ratio >= 2.0, f"int8 density ratio {ratio:.2f} < 2x"


def test_int8_pool_poison_isolation(decoder, int8_engine):
    """Slot reuse on a QUANTIZED pool: poisoned codes+scales in every
    uninitialized position (the fixture's prefill window leaves positions
    past 16 untouched) never reach any lane's output — through the
    SPECULATIVE verify path too, since the fixture drafts."""
    _, ref = decoder
    work = _workload(6, seed=6, max_new_hi=10)
    int8_engine.pool.poison(1e9)
    futs = [int8_engine.submit(p, m) for p, m in work]
    outs = [f.result(timeout=120) for f in futs]
    assert int8_engine.assert_no_retraces() == 0
    for (p, m), o in zip(work, outs):
        np.testing.assert_array_equal(
            o, ref.reference_generate(p, m, window=16, kv_dtype="int8"),
            err_msg=f"int8 poison leaked for prompt {p}")


def test_memory_plans_cover_quantized_spec_programs(int8_engine):
    """memory_plans() lowers the EXACT warmup avals — int8 slab +
    per-position scale pairs and the speculative token-history page —
    so the PR-15 plan surface keeps working on the new program family."""
    plans = int8_engine.memory_plans()
    # every program that holds the model: the two, and what the engine's
    # options add (chunk rungs, the prefix copy)
    assert {"prefill", "decode"} <= set(plans) <= {
        n for n in int8_engine.lowered_programs()}
    assert not any(n.startswith(("sample_first", "join_lanes",
                                 "advance_lanes")) for n in plans)
    for key, plan in plans.items():
        assert plan["name"].endswith(key)
        assert plan.get("complete") in (True, False)


# ---------------------------------------------------------------------------
# fleet wire: sampling params ride the request message
# ---------------------------------------------------------------------------
def test_fleet_submit_validates_and_stub_wire_compat(tmp_path):
    """Fleet.submit validates sampling params router-side, and a sampled
    request survives the wire to a stub replica (which ignores sampling
    but must ACCEPT the message — protocol compatibility with engines
    that predate the knobs)."""
    spec = {"version": "v1", "stub": True, "stub_delay_ms": 2.0}
    fleet = serve.Fleet(spec, replicas=1, heartbeat_ms=200,
                        workdir=str(tmp_path))
    fleet.start()
    try:
        with pytest.raises(serve.ServeError, match="temperature"):
            fleet.submit([1, 2], 4, temperature=-1.0)
        with pytest.raises(serve.ServeError, match="top_p"):
            fleet.submit([1, 2], 4, temperature=1.0, top_p=0.0)
        greedy = fleet.generate([3, 1, 4], max_new_tokens=6, timeout=60)
        sampled = fleet.generate([3, 1, 4], max_new_tokens=6, timeout=60,
                                 temperature=3.0, top_k=8, seed=42)
    finally:
        fleet.close()
    # the stub's deterministic pattern ignores sampling: identical output
    # proves the extra wire fields were carried and tolerated
    np.testing.assert_array_equal(greedy, sampled)
