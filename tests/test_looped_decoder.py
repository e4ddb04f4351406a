"""`models.looped_decoder.LoopedDecoder` against the plain reference
(`chipbench/reference/ouro_loop.py`, which imports nothing of the program),
by LOGITS, on a tiny preset (3 layers run 3 times, d = 32, 2 heads of 8,
vocabulary 96: no count coincides with another) with seeded random float32
weights.

The tolerance, and why it is what it is. Program and reference are both
float32 here; they differ in the order of their sums (a cache read under a
mask against one causal einsum, XLA's default float32 matmul against
`highest`, rotary as a roll of the flat axis against slices of a head) and
nine passes of normalised layers carry that on. The logits reach 5.5 in
size and the two agree to 3.2e-4 at worst (measured: the dense prefill,
every chunk edge and 20 decode steps of `decode_gaps`). The planted faults
of the reference read far above that: the least of them moves the same
logits by 4.1 (`pass_short`), W8A8 by 5.0. TOL = 2e-3 sits six times above
the sound reading and two thousand times below the nearest fault
(`FAULT_FACTOR` holds every fault to at least 100 times TOL); the
`xfail(strict=True)` cases run the
same comparison with a fault planted and must fail it.
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
from incubator_mxnet_tpu.models import looped_decoder as ld  # noqa: E402
from incubator_mxnet_tpu.models import sparse_moe_decoder as sm  # noqa: E402
from incubator_mxnet_tpu.ops import fused  # noqa: E402
from incubator_mxnet_tpu.serve.batcher import ServeError  # noqa: E402
from incubator_mxnet_tpu.serve.kv_pool import CacheKindError  # noqa: E402
from chipbench import weights_ouro, work_ouro  # noqa: E402
from chipbench.reference import ouro_loop  # noqa: E402

TOL = 2e-3
FAULT_FACTOR = 100
WINDOW = 8
TINY = dict(vocab=96, embed=32, layers=3, heads=2, head_dim=8, mlp_hidden=48,
            ut_steps=3, max_len=64, dtype="float32")
#: matrices ten times the conventional 0.02 (at d = 32 that would leave
#: every projection at 0.1 of its input), queries and keys large enough
#: that softmax chooses among positions, the embedding at the stream's size
SCALES = {"normal": 0.2, "emb": 1.0, "q": 0.5, "k": 0.5}


@pytest.fixture(scope="module")
def tiny():
    config = ld.LoopedConfig(**TINY)
    params = ld.init_looped_params(config, 3, SCALES)
    # norm weights and the gate's bias away from their initial 1 and 0: a
    # forgotten weight would not show against ones
    key = jax.random.PRNGKey(103)
    for i, name in enumerate(("n1", "n2", "n3", "n4", "nf")):
        params[name] = 1.0 + 0.3 * jax.random.normal(
            jax.random.fold_in(key, i), params[name].shape)
    params["gate_b"] = jnp.asarray([0.3])
    return {"config": config, "params": params,
            "model": ld.LoopedDecoder(config, params=params),
            "forward": ouro_loop.make_forward(config.as_dict())}


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(1, 96, size=n).astype(
        np.int32)


def reference_logits(forward, params, tokens):
    return np.asarray(ouro_loop.logits(forward, params, tokens))


def one(v, dt=jnp.int32):
    return jnp.asarray([v], dtype=dt)


def prefill_logits(model, pool, prompt, window, row=0):
    """[(positions prefilled so far, logits of the chunk's last position)]:
    the dense prefill at offset 0, then chunks of `window`."""
    out, pos = [], 0
    while pos < prompt.size:
        n = min(window, prompt.size - pos)
        toks = np.zeros((1, window), np.int32)
        toks[0, :n] = prompt[pos:pos + n]
        (cache,) = pool.buffers()
        if pos == 0:
            cache, logits, _ = model.prefill_program(window)(
                model.params, cache, jnp.asarray(toks), one(n), one(row))
        else:
            cache, logits, _ = model.chunk_prefill_program(window)(
                model.params, cache, jnp.asarray(toks), one(pos), one(n),
                one(row))
        pool.swap_buffers(cache)
        pos += n
        out.append((pos, np.asarray(logits[0])))
    return out


def decode_gaps(model, forward, prompt, steps, window=WINDOW):
    """Widest gap between the program's logits, at the prompt's end and at
    each of `steps` decode steps through the cache (feeding the
    reference's sequence), and `forward`'s over the whole sequence."""
    tokens = np.concatenate([prompt, prompt_of(steps, 77)])
    want = reference_logits(forward, model.params, tokens)
    pool = model.new_pool(max_slots=1)
    got = prefill_logits(model, pool, prompt, window)[-1][1]
    gap = np.abs(got - want[prompt.size - 1]).max()
    micro = jax.jit(ld._make_micro(model.config))
    for t in range(prompt.size, tokens.size):
        (cache,) = pool.buffers()
        cache, logits, _ = micro(model.params, cache, one(tokens[t]), one(t),
                                 jnp.asarray([True]))
        pool.swap_buffers(cache)
        gap = max(gap, np.abs(np.asarray(logits[0]) - want[t]).max())
    return gap


# ---------------------------------------------------------------------------
# the pieces
# ---------------------------------------------------------------------------
def test_rope_half_and_rope_pair_different_values():
    """The two rotaries on one input: each matches its closed form (the
    pair (x_i, x_{i+n/2}), or (x_2i, x_2i+1), turned by t theta^(-2i/n)),
    they differ from each other, and heads side by side turn alike."""
    n, theta = 8, 100.0
    x = np.random.default_rng(1).normal(size=(5, n)).astype(np.float32)
    pos = np.array([0, 1, 2, 7, 31])
    ang = pos[:, None] * theta ** (-np.arange(0, n, 2) / n)
    half = np.concatenate(
        [x[:, :4] * np.cos(ang) - x[:, 4:] * np.sin(ang),
         x[:, 4:] * np.cos(ang) + x[:, :4] * np.sin(ang)], -1)
    inter = np.empty_like(x)
    inter[:, 0::2] = x[:, 0::2] * np.cos(ang) - x[:, 1::2] * np.sin(ang)
    inter[:, 1::2] = x[:, 0::2] * np.sin(ang) + x[:, 1::2] * np.cos(ang)
    got_half = np.asarray(ld.rope_half(jnp.asarray(x), jnp.asarray(pos),
                                       theta))
    got_inter = np.asarray(sm.rope(jnp.asarray(x), jnp.asarray(pos), theta))
    np.testing.assert_allclose(got_half, half, atol=1e-5)
    np.testing.assert_allclose(got_inter, inter, atol=1e-5)
    assert np.abs(got_half[1:] - got_inter[1:]).max() > 0.1
    np.testing.assert_array_equal(got_half[0], x[0])       # position 0
    two = np.asarray(ld.rope_half(jnp.asarray(np.concatenate([x, 2 * x], -1)),
                                  jnp.asarray(pos), theta, heads=2))
    np.testing.assert_allclose(two, np.concatenate([half, 2 * half], -1),
                               atol=1e-5)
    # the reference's own two rotaries are the same two
    t3 = jnp.asarray(x)[:, None, :]
    np.testing.assert_allclose(
        np.asarray(ouro_loop.rotary(t3, jnp.asarray(pos), theta))[:, 0],
        half, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(ouro_loop.rotary(t3, jnp.asarray(pos), theta, True))[:, 0],
        inter, atol=1e-5)


def test_published_sizes_reckon_as_the_issue_does():
    """`chipbench/configs/ouro26b_serve.json`: 2,667,974,657 parameters, a
    cached position of 192 planes x 2 x 4096 B, 9 rows of 512 positions."""
    import json
    with open(os.path.join(ROOT, "chipbench/configs/ouro26b_serve.json")) as f:
        cfg = json.load(f)
    m, e = cfg["model"], cfg["engine"]
    assert weights_ouro.param_count(m) == work_ouro.param_count(m) \
        == 2_667_974_657
    assert work_ouro.position_cache_bytes(m) == 1_572_864
    model = ld.LoopedDecoder(weights_ouro.looped_config(m), params={})
    spec = model.cache_spec()
    assert len(spec) == 2 * m["layers"]
    assert all(leaf.kind == "full" and leaf.positions == m["max_len"]
               and leaf.shape == (4, 512, 2048) for leaf in spec)
    row = sum(int(np.prod(leaf.shape)) * 2 for leaf in spec)
    assert row == m["max_len"] * 1_572_864
    assert (e["max_slots"] + 1) * row == 7_247_757_312
    for k, v in cfg["published"].items():
        assert cfg[k] == v, k
    assert cfg["reduced"] == []
    assert (m["embed"], m["layers"], m["heads"], m["head_dim"],
            m["mlp_hidden"], m["vocab"], m["ut_steps"]) == tuple(
        cfg["published"][k] for k in (
            "hidden_size", "num_hidden_layers", "num_attention_heads",
            "head_dim", "intermediate_size", "vocab_size", "total_ut_steps"))


def test_exit_distribution_is_a_distribution_and_one_serves_the_last(tiny):
    hs = tiny["forward"].hidden(tiny["params"], jnp.asarray(prompt_of(12)),
                                every_pass=True)
    p = np.asarray(ouro_loop.exit_distribution(tiny["params"], hs))
    assert p.shape == (3, 12) and (p > 0).all()
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    lam = 1 / (1 + np.exp(-(np.asarray(hs[0]) @ np.asarray(
        tiny["params"]["gate_w"]) + 0.3)))
    np.testing.assert_allclose(p[0], lam, atol=1e-5)
    assert (np.asarray(ouro_loop.served_pass(p, 1.0)) == 2).all()
    early = np.asarray(ouro_loop.served_pass(p, 0.3))
    assert (early == np.argmax(np.cumsum(p, 0) >= 0.3, 0)).all() \
        and early.min() < 2


def test_a_threshold_other_than_one_is_refused():
    with pytest.raises(ServeError, match="different depths"):
        ld.LoopedDecoder(ld.LoopedConfig(**dict(TINY,
                                                early_exit_threshold=0.9)))
    with pytest.raises(ServeError, match="odd"):
        ld.LoopedConfig(**dict(TINY, head_dim=7))


# ---------------------------------------------------------------------------
# program against reference
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
    before = np.asarray(cache["k1"][1])
    cache, logits, counted = model.prefill_program(WINDOW)(
        model.params, cache, jnp.asarray(toks),
        jnp.asarray([5, 0, 8], jnp.int32),
        jnp.asarray([2, pool.garbage_row, 0], jnp.int32))
    for lane, p in ((0, a), (2, b)):
        want = reference_logits(tiny["forward"], tiny["params"], p)[-1]
        assert np.abs(np.asarray(logits[lane]) - want).max() < TOL
    np.testing.assert_array_equal(np.asarray(cache["k1"][1]), before)
    # 13 valid positions through 3 passes of the stack; no decode read
    assert np.asarray(counted["loop"]).tolist() == [39, 0]


def test_chunk_prefill_reads_what_earlier_chunks_wrote(tiny):
    """Four chunks of 6 over a 23-token prompt: the second, third and
    fourth read the planes that the ones before wrote, each pass its own,
    across three chunk edges, and every chunk's last logits are the full
    forward's."""
    prompt = prompt_of(23, 5)
    pool = tiny["model"].new_pool(max_slots=1)
    got = prefill_logits(tiny["model"], pool, prompt, 6)
    assert len(got) == 4
    want = reference_logits(tiny["forward"], tiny["params"], prompt)
    for end, logits in got:
        assert np.abs(logits - want[end - 1]).max() < TOL, end


def test_decode_through_the_cache_at_every_step(tiny):
    """20 decode steps after a chunked prompt longer than the window:
    every step's logits against the full forward."""
    assert decode_gaps(tiny["model"], tiny["forward"], prompt_of(13, 6),
                       20) < TOL


@pytest.mark.xfail(strict=True, reason="planted fault of the reference")
@pytest.mark.parametrize("fault", ouro_loop.FAULTS + ("int8",))
def test_planted_fault_fails_the_tolerance(tiny, fault):
    kind = dict(precision=fault) if fault in ouro_loop.PRECISIONS \
        else dict(fault=fault)
    bad = ouro_loop.make_forward(tiny["config"].as_dict(), edge=WINDOW,
                                 **kind)
    assert decode_gaps(tiny["model"], bad, prompt_of(13, 6), 20) \
        < FAULT_FACTOR * TOL


def test_a_pass_reads_its_own_plane_only(tiny):
    """A decode step over a cache in which, for the pass under test, every
    OTHER pass's plane is poisoned AFTER the prompt was written: the logits
    are the clean cache's only if no pass reads another's plane; and they
    move when a pass's own plane is poisoned."""
    model, c = tiny["model"], tiny["config"]
    prompt = prompt_of(11, 8)
    micro = jax.jit(ld._make_micro(c))

    def step(spoil):
        pool = model.new_pool(max_slots=1)
        prefill_logits(model, pool, prompt, WINDOW)
        (cache,) = pool.buffers()
        cache = {n: spoil(n, a) for n, a in cache.items()}
        _, logits, _ = micro(model.params, cache, one(5), one(11),
                             jnp.asarray([True]))
        return np.asarray(logits[0])

    clean = step(lambda n, a: a)
    # positions beyond the request's length hold anything in every plane
    np.testing.assert_array_equal(
        step(lambda n, a: a.at[:, :, 12:].set(1e9)), clean)
    # a pass's own plane, at a live position, is read
    assert np.abs(step(lambda n, a: a.at[0, 1, 3].set(7.0) if n == "k1"
                       else a) - clean).max() > 1e-3
    # the three passes write planes that differ
    pool = model.new_pool(max_slots=1)
    prefill_logits(model, pool, prompt, WINDOW)
    k1 = np.asarray(pool.buffers()[0]["k1"][0, :, :11])
    assert np.abs(k1[0] - k1[1]).max() > 0.1 \
        and np.abs(k1[1] - k1[2]).max() > 0.1
    # swap what two passes hold: a pass that read another's plane (the
    # reference's `first_plane`, `last_plane`) would not notice
    swapped = step(lambda n, a: a.at[:, jnp.asarray([0, 1])].set(
        a[:, jnp.asarray([1, 0])]))
    assert np.abs(swapped - clean).max() > 0.05


def test_one_micro_step_traces_one_paged_read_a_layer(tiny):
    """The pass is a loop in the program: a trace of the decode
    micro-step, of the chunk and of a 2-step decode program holds `layers`
    calls of `ops.fused.paged_attention`, not `layers * ut_steps`; the
    dense prefill holds none."""
    c, model = tiny["config"], tiny["model"]
    pool = model.new_pool(max_slots=2)
    (cache,) = pool.buffers()
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731

    def calls(fn, *args):
        before = fused.fused_stats()["paged_attention_calls"]
        jax.make_jaxpr(fn)(*args)
        return fused.fused_stats()["paged_attention_calls"] - before

    assert calls(ld._make_micro(c), model.params, cache, i32(2), i32(2),
                 jnp.ones((2,), bool)) == c.layers
    assert calls(ld._make_chunk(c, WINDOW, c.max_len, False), model.params,
                 cache, i32(1, WINDOW), i32(1), i32(1), i32(1)) == c.layers
    assert calls(ld._make_chunk(c, WINDOW, c.max_len, True), model.params,
                 cache, i32(1, WINDOW), i32(1), i32(1)) == 0
    decode = sm._make_decode(c, 2, None, ld._make_micro(c), model.counters)
    assert calls(decode, model.params, cache, i32(2), i32(2), i32(2),
                 jnp.zeros((2,)), i32(2), jnp.ones((2,)),
                 jnp.zeros((2, 2), jnp.uint32)) == c.layers
    # every extent is the one chunk program
    assert model.chunk_prefill_program(WINDOW, extent=16) \
        is model.chunk_prefill_program(WINDOW, extent=c.max_len)


# ---------------------------------------------------------------------------
# through the engine
# ---------------------------------------------------------------------------
ENGINE = dict(max_slots=3, prefill_lanes=2, prefill_window=6,
              prefill_budget=64, decode_steps=3, prefix_cache_slots=0,
              draft_tokens=0)


def served_gap(tiny, prompt, tokens):
    """How far below the reference's best logit the served tokens lie."""
    return ouro_loop.served_gaps(tiny["forward"], tiny["params"], prompt,
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
    # the model's own counters: every prompt position and every decoded
    # token went through 3 passes; a decode step read its live positions
    # in each of the 9 planes
    loop = stats["loop"]
    assert loop["stack_passes"] >= 3 * sum(
        p.size + n - 1 for p, n in jobs)
    assert loop["plane_positions_read"] >= 9 * sum(
        sum(range(p.size + 1, p.size + n)) for p, n in jobs)
    cache = stats["cache"]
    assert set(cache) == {"full"}
    pool = model.new_pool(max_slots=3)
    assert cache["full"]["bytes"] == pool.bytes_by_kind()["full"] \
        == 4 * 6 * 3 * 64 * 16 * 4
    # a row is live by its positions in EVERY pass's plane
    assert pool.bytes_by_kind([4]) == {"full": 6 * 3 * 4 * 16 * 4}
    assert cache["full"]["live_bytes_sum"] > 0


def test_slot_reused_after_a_poison_fill_of_every_leaf(tiny):
    """A freed slot's planes are not zeroed: the masks hide every stale
    position. Poison every leaf between two tenants of one slot: the second
    one's tokens and logits are a fresh pool's."""
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


@pytest.mark.parametrize("option, value", [("prefix_cache_slots", 2),
                                           ("draft_tokens", 2),
                                           ("kv_dtype", "int8")])
def test_engine_refuses_what_this_decoder_has_no_program_for(tiny, option,
                                                             value):
    kw = dict(ENGINE, **{option: value})
    with pytest.raises((CacheKindError, ServeError)):
        serve.ContinuousEngine(tiny["model"], **kw)


def test_every_program_names_its_layers_and_the_loop_s_norm(tiny):
    """Every equation of the three programs runs under `layer{l}/attn`,
    `layer{l}/mlp`, `head/loop_norm`, `embed`, `head` or `sampler`: the
    scopes that the benchmark's partition of a decode program's device time
    matches."""
    import re
    eng = serve.ContinuousEngine(tiny["model"], **ENGINE)
    low = eng.lowered_programs()
    want = {f"layer{l}/{k}" for l in range(3) for k in ("attn", "mlp")} \
        | {"head/loop_norm", "embed", "head"}
    chunk, = (n for n in low if n.startswith("chunk_prefill"))
    for name in ("prefill", "decode", chunk):
        text = low[name].as_text(debug_info=True)
        seen = set(re.findall(
            r"((?:layer\d+/(?:attn|mlp))|head/loop_norm|embed|head|sampler)"
            r"/", text))
        assert want <= seen, (name, want - seen)


def test_engine_tokens_are_the_same_through_the_kernel(tiny):
    """The decode and chunk programs with the plane read in the Pallas
    kernel (interpret mode: rows as data, a chunk as lanes of
    `CHUNK_QUERIES` queries) serve the tokens of the jnp composition."""
    model = ld.LoopedDecoder(tiny["config"], params=tiny["params"])
    prompt = prompt_of(21, 30)
    want = tiny["model"].reference_generate(prompt, 5, window=8)
    fused.set_interpret(True)
    try:
        before = fused.fused_stats()
        got = model.reference_generate(prompt, 5, window=8)
        after = fused.fused_stats()
    finally:
        fused.set_interpret(None)
    np.testing.assert_array_equal(got, want)
    assert after["fallback_calls"] == before["fallback_calls"]
    assert after["pallas_calls"] > before["pallas_calls"]
