"""models.delta_moe_decoder: Kimi-Delta-Attention layers whose cache is a
float32 matrix a head, a latent-attention layer among them read densely,
and group-limited routed experts of which this process holds a share,
served by `serve.ContinuousEngine` and held to the plain reference
`chipbench/reference/ling_kda.py` in float32.

What is under test:
  * the chunkwise form equals the recurrence one position at a time, with
    windows and valid lengths that divide neither chunk nor sub-chunk, at
    the published lower bound and sub-chunk (exponents up to 80)
  * prefill, chunked prefill and decode through the cache give the full
    forward's logits at every step: state and conv tail carried over
    chunk boundaries, within a tolerance that a bfloat16 state fails
  * the reference's planted faults (state cut at a chunk edge, a previous
    tenant's state, the tail cut, one decay a head, no delta correction, no
    group limit, the wrong experts) all fail it
  * group-limited `route` against a plain loop; the share test: the routed
    parts of all the shares plus the shared expert once are the uncut layer
  * a poison-filled pool and a reused slot; lanes joining and leaving
    without a retrace; the counters and the cache's price in `stats()`
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.reference import ling_kda  # noqa: E402
from incubator_mxnet_tpu import serve  # noqa: E402
from incubator_mxnet_tpu.models import delta_moe_decoder as dm  # noqa: E402
from incubator_mxnet_tpu.models import sparse_moe_decoder as sm  # noqa: E402
from incubator_mxnet_tpu.serve.kv_pool import CacheKindError  # noqa: E402

# float32 program against float32 reference: the chunkwise form reorders
# the recurrence's sums (a triangular solve and K / Gamma at exponents up
# to 80), which reads 1e-6 on logits of order 1; a state rounded to
# bfloat16 reads 1e-3 and more (the control below)
TOL = 5e-5
QB = 8                       # the reference's query block at this size
# scales at which every mechanism shows in the logits: decays whose
# half-lives run from one position to hundreds, a peaked softmax, a
# router whose choice is the scores'
SCALES = dict(dm.INIT_SCALES, q=0.3, kv_b=0.1, router=0.1, kda_f=0.1,
              kda_beta=0.1, kda_bf=(-7.5, -0.5), kda_A=(-0.5, 0.5),
              router_bias=0.05)


def make_config(**over):
    """A lower bound of -20 puts 4 positions in a sub-chunk (the published
    -5 puts 16), so that these short windows hold several."""
    kw = dict(vocab=96, embed=64, heads=4, kda_lower_bound=-20.0,
              mixer_types=("kda", "kda", "mla", "kda"),
              mlp_types=("dense", "sparse", "sparse", "sparse"),
              routed_experts=16, experts_per_token=2, n_group=4,
              topk_group=2, held_count=4, max_len=48)
    kw.update(over)
    return dm.DeltaMoEConfig(**kw)


@pytest.fixture(scope="module", autouse=True)
def chunks_of_8():
    """Every program of this module is traced with chunks of 8 positions
    (two sub-chunks), so that a window of 32 is a scan over 4."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(dm, "CHUNK", 8)
        yield


@pytest.fixture(scope="module")
def tiny():
    c = make_config()
    params = dm.init_delta_moe_params(c, 1, SCALES)
    return (dm.DeltaMoEDecoder(c, params), c,
            ling_kda.make_forward(c.as_dict(), q_block=QB))


def prompt_of(n, seed=0, vocab=96):
    return np.random.default_rng(seed).integers(
        1, vocab, size=n).astype(np.int32)


def reference_logits(forward, params, tokens):
    """(len(tokens), vocab): the sequence padded to whole query blocks
    (causal, so the pad is never read)."""
    tokens = np.asarray(tokens, np.int32)
    padded = np.zeros((-(-tokens.size // QB) * QB,), np.int32)
    padded[:tokens.size] = tokens
    rows = forward[0](params, jnp.asarray(padded), tokens.size,
                      tokens.size)[0]
    return np.asarray(forward[1](params, rows))[:tokens.size]


def one(v, dtype=jnp.int32):
    return jnp.asarray([v], dtype=dtype)


def prefill_logits(model, pool, prompt, window):
    """The last position's logits after a windowed prefill + chunks."""
    pos, logits = 0, None
    while pos < prompt.size:
        n = min(window, prompt.size - pos)
        toks = np.zeros((1, window), np.int32)
        toks[0, :n] = prompt[pos:pos + n]
        (cache,) = pool.buffers()
        if pos == 0:
            cache, logits, _ = model.prefill_program(window)(
                model.params, cache, jnp.asarray(toks), one(n), one(0))
        else:
            cache, logits, _ = model.chunk_prefill_program(window)(
                model.params, cache, jnp.asarray(toks), one(pos), one(n),
                one(0))
        pool.swap_buffers(cache)
        pos += n
    return np.asarray(logits)[0]


def decode_gap(model, forward, prompt, steps, window):
    """max |program - reference| over the logits of prefill + `steps`
    decode steps through the cache, feeding the reference's own choices.
    Every leaf is poison-filled first: a state or tail read before it is
    written shows."""
    pool = model.new_pool(max_slots=1)
    pool.poison(1e9)
    seq = list(prompt)
    got = [prefill_logits(model, pool, prompt, window)]
    micro = jax.jit(dm._make_micro(model.config), donate_argnums=(1,))
    for _ in range(steps):
        seq.append(int(np.argmax(got[-1])))
        (cache,) = pool.buffers()
        cache, logits, _ = micro(model.params, cache, one(seq[-1]),
                                 one(len(seq) - 1), jnp.asarray([True]))
        pool.swap_buffers(cache)
        got.append(np.asarray(logits)[0])
    want = reference_logits(forward, model.params, seq)[prompt.size - 1:]
    return float(np.max(np.abs(np.stack(got) - want)))


# ---------------------------------------------------------------------------
# the KDA layer: chunkwise = the recurrence
# ---------------------------------------------------------------------------
def _kda_inputs(B, W, H, D, lb, seed):
    rng = np.random.default_rng(seed)

    def unit(a):
        return a / np.linalg.norm(a, axis=-1, keepdims=True)

    q = unit(rng.standard_normal((B, W, H, D))) * D ** -0.5
    k = unit(rng.standard_normal((B, W, H, D)))
    v = rng.standard_normal((B, W, H, D))
    # decays from none to the bound itself, channel by channel
    g = lb * rng.uniform(size=(B, W, H, D)) ** 3
    g[:, :, 0, :D // 2] = lb
    beta = rng.uniform(size=(B, W, H))
    s0 = rng.standard_normal((B, H, D, D))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta, s0))


def _sequential(q, k, v, g, beta, state):
    def step(s, xs):
        o, s = dm.kda_step(*xs, s)
        return s, o
    state, o = jax.lax.scan(step, state, tuple(
        a.swapaxes(0, 1) for a in (q, k, v, g, beta)))
    return o.swapaxes(0, 1), state


@pytest.mark.parametrize("W, chunk, lb", [
    (37, 8, -20.0),        # a window that divides neither
    (16, 16, -20.0), (5, 8, -20.0), (64, 64, -5.0), (150, 64, -5.0)])
def test_chunkwise_form_is_the_recurrence(W, chunk, lb, monkeypatch):
    """At lb = -5 and its sub-chunk of 16 (at -20 and 4) a channel that
    decays at the bound all along puts K / Gamma at exp(80): inside
    float32, and the masked product beside it is discarded, not multiplied
    by 0. In float64 the two forms agree to 1e-16; in float32 the
    unit-triangular system of 64 positions whose keys have 16 values (far
    from orthogonal: the served heads have 128) amplifies rounding to
    5e-5, and an exponent of 80 carries a float32 sum's rounding (80 x
    6e-8 of the factor) into K / Gamma: 1.0e-4 read, 2e-4 allowed."""
    monkeypatch.setattr(dm, "CHUNK", chunk)
    sub = make_config(kda_lower_bound=lb).sub_chunk
    assert -lb * sub == 80
    q, k, v, g, beta, s0 = _kda_inputs(2, W, 3, 16, lb, W)
    o_seq, s_seq = _sequential(q, k, v, g, beta, s0)
    o, s = jax.jit(dm.kda_chunk, static_argnums=(6,))(
        q, k, v, g, beta, s0, sub)
    assert np.all(np.isfinite(np.asarray(o)))
    atol = 2e-4
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_seq), atol=atol)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_seq), atol=atol)


def test_a_pad_position_leaves_the_state_as_it_is():
    """beta 0 and g 0 (what the chunk program gives the positions past a
    lane's valid ones): the state after 11 real positions and 5 pads is
    the state after the 11."""
    q, k, v, g, beta, s0 = _kda_inputs(1, 16, 2, 8, -5.0, 3)
    real = jnp.arange(16) < 11
    g = jnp.where(real[None, :, None, None], g, 0.0)
    beta = jnp.where(real[None, :, None], beta, 0.0)
    _, s = dm.kda_chunk(q, k, v, g, beta, s0, 4)
    _, want = _sequential(*(a[:, :11] for a in (q, k, v, g, beta)), s0)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want), atol=2e-5)


def test_the_step_by_hand():
    """S' = (I - beta k k^T) Diag(alpha) S + beta k v^T; o = S'^T q."""
    q, k, v, g, beta, s0 = (np.asarray(a, np.float64)
                            for a in _kda_inputs(1, 1, 1, 8, -5.0, 5))
    q, k, v, g, b, s0 = q[0, 0, 0], k[0, 0, 0], v[0, 0, 0], g[0, 0, 0], \
        beta[0, 0, 0], s0[0, 0]
    want = (np.eye(8) - b * np.outer(k, k)) @ (np.exp(g)[:, None] * s0) \
        + b * np.outer(k, v)
    f = lambda a: jnp.asarray(a, jnp.float32)[None, None]  # noqa: E731
    o, s = dm.kda_step(f(q), f(k), f(v), f(g), f(b), f(s0))
    np.testing.assert_allclose(np.asarray(s)[0, 0], want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(o)[0, 0], want.T @ q, atol=1e-5)


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("plen, window", [(5, 32), (9, 32), (30, 32),
                                          (21, 8), (23, 6), (40, 16)])
def test_prefill_then_decode_is_the_full_forward(tiny, plen, window):
    """Whole prompts in one window, and prompts split over windows of 8,
    6 (which divides neither chunk nor sub-chunk) and 16: the state and
    the conv tail of every KDA layer and the latent rows are carried over
    each boundary, then through 6 decode steps."""
    model, c, forward = tiny
    assert decode_gap(model, forward, prompt_of(plen, plen), 6,
                      window) < TOL


def test_chunk_extents_read_the_same_positions(tiny):
    """The extent bounds what the MLA layer's chunk reads, not what it
    computes."""
    model, c, forward = tiny
    prompt = prompt_of(20, 4)
    want = reference_logits(forward, model.params, prompt)[-1]
    for extent in (32, 48):
        pool = model.new_pool(max_slots=1)
        prefill_logits(model, pool, prompt[:16], 16)
        toks = np.zeros((1, 16), np.int32)
        toks[0, :4] = prompt[16:]
        (cache,) = pool.buffers()
        _, logits, _ = model.chunk_prefill_program(16, extent)(
            model.params, cache, jnp.asarray(toks), one(16), one(4), one(0))
        assert np.max(np.abs(np.asarray(logits)[0] - want)) < TOL


@pytest.mark.parametrize("control", ["bfloat16", "bf16_state", "int8"])
def test_a_lower_precision_fails_the_tolerance(tiny, control):
    """The controls of the tolerance: the same pass all in bfloat16, with
    the state alone rounded to bfloat16, and in 8-bit codes."""
    model, c, forward = tiny
    seq = prompt_of(24, 5)
    low = ling_kda.make_forward(c.as_dict(), control, q_block=QB)
    gap = np.max(np.abs(reference_logits(low, model.params, seq)
                        - reference_logits(forward, model.params, seq)))
    assert gap > 20 * TOL


@pytest.mark.parametrize("fault", ling_kda.FAULTS)
def test_planted_fault_is_outside_the_tolerance(tiny, fault):
    """Each planted fault of the reference moves the logits by far more
    than the tolerance: the comparison sees the state and the tail
    crossing a chunk edge, whose state it is, the per-channel decay, the
    delta correction, the group limit and the routing."""
    model, c, forward = tiny
    seq = prompt_of(40, 6)
    bad = ling_kda.make_forward(c.as_dict(), fault, q_block=QB, edge=8)
    gap = np.max(np.abs(reference_logits(bad, model.params, seq)
                        - reference_logits(forward, model.params, seq)))
    assert gap > 20 * TOL


# ---------------------------------------------------------------------------
# the shared functions' new options
# ---------------------------------------------------------------------------
def test_group_limited_routing_against_a_plain_loop():
    c = make_config()
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.standard_normal((9, 64)), jnp.float32)
    r_w = jnp.asarray(rng.standard_normal((64, 16)) * 0.3, jnp.float32)
    r_b = jnp.asarray(rng.standard_normal((16,)) * 0.2, jnp.float32)
    idx, gates, kept = sm.route(h, r_w, r_b, c, with_kept=True)
    sig = 1 / (1 + np.exp(-np.asarray(h @ r_w, np.float64)))
    biased = sig + np.asarray(r_b)
    limited = 0
    for t in range(9):
        score = [np.sort(biased[t, 4 * j:4 * j + 4])[-2:].sum()
                 for j in range(4)]
        groups = sorted(np.argsort(score)[-2:])
        assert sorted(np.flatnonzero(np.asarray(kept)[t])) == groups
        allowed = [e for j in groups for e in range(4 * j, 4 * j + 4)]
        want = sorted(allowed, key=lambda e: -biased[t, e])[:2]
        assert sorted(np.asarray(idx)[t]) == sorted(want)
        limited += sorted(want) != sorted(np.argsort(-biased[t])[:2])
        picked = sig[t, np.asarray(idx)[t]]
        np.testing.assert_allclose(np.asarray(gates)[t],
                                   2.5 * picked / picked.sum(), rtol=1e-5)
    assert limited > 0          # the limit changed some token's choice


def test_one_group_is_todays_routing_bit_for_bit():
    """`n_group` 1 (the sparse-attention decoder's) takes the same top-k
    of the same scores as before the option was there."""
    c = sm.SparseMoEConfig()
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.standard_normal((7, 64)), jnp.float32)
    r_w = jnp.asarray(rng.standard_normal((64, 16)) * 0.3, jnp.float32)
    r_b = jnp.asarray(rng.standard_normal((16,)) * 0.5, jnp.float32)
    idx, gates, kept = sm.route(h, r_w, r_b, c, with_kept=True)
    sig = jax.nn.sigmoid(jnp.dot(h, r_w, preferred_element_type=jnp.float32))
    _, want = jax.lax.top_k(sig + r_b, c.experts_per_token)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want))
    g = jnp.take_along_axis(sig, want, -1)
    np.testing.assert_array_equal(
        np.asarray(gates),
        np.asarray(c.routed_scaling_factor * g / jnp.sum(g, -1,
                                                         keepdims=True)))
    assert np.asarray(kept).all() and kept.shape == (7, 1)


def test_dense_absorbed_read_is_the_rebuilt_read_of_the_live_row():
    """`mla_read_absorbed` over the cache leaf itself with `lengths`
    (nothing chosen) against `mla_read_rebuilt` under the causal mask."""
    c = make_config()
    rng = np.random.default_rng(5)

    def f(*s):
        return jnp.asarray(rng.standard_normal(s), jnp.float32)

    S, T = 3, 12
    leaf = f(S + 1, T, c.lat_stored)
    q_nope, q_rope = f(S, c.heads, 12), f(S, c.heads, 4)
    wkv_b = f(c.kv_lora_rank, c.heads * (12 + c.v_head_dim)) * 0.3
    lengths = jnp.asarray([0, 5, 11], jnp.int32)
    got = sm.mla_read_absorbed(q_nope, q_rope, leaf, None, wkv_b, c,
                               lengths=lengths)
    mask = jnp.arange(T)[None, None, :] <= lengths[:, None, None]
    want = sm.mla_read_rebuilt(q_nope[:, None], q_rope[:, None], leaf[:S],
                               mask, wkv_b, c)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_all_the_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """The guide's share test: an expert layer cut into 4 shares of 4
    experts, one routing group a share. The residual stream after the
    layer is x + routed + shared; the shares' routed parts (each share's
    output minus x minus the shared expert's term) add up to the uncut
    layer's, and a token visits at most `topk_group` shares."""
    c = make_config(held_count=16)
    params = dm.init_delta_moe_params(c, 2, SCALES)
    x = params["emb"][jnp.asarray(prompt_of(16, 8))]
    w = dm._weights(params, c, 1)
    ok = jnp.ones((16,), bool)
    whole, counted = sm._ffn(x, w, c, 1, ok)
    assert int(counted[4]) == int(counted[5]) == 16     # every group is here
    h = sm.rms_norm(x, w["ln2_w"], c.norm_eps)
    shared = sm.gated_mlp(h, w["s_gate_up"], w["s_down"])
    parts, visits = jnp.zeros_like(x), 0
    for first in (0, 4, 8, 12):
        cut = make_config(held_first=first, held_count=4)
        assert cut.held_groups == (first // 4,)
        # this share's experts are rows [first, first + 4) of the stack
        y, counted = sm._ffn(x, dict(w, e_row0=first), cut, 1, ok)
        parts = parts + (y - x - shared)
        visits += int(counted[4])
        assert int(counted[0]) <= 2 * int(counted[4])   # pairs in kept only
    assert visits == 16 * c.topk_group
    np.testing.assert_allclose(np.asarray(x + parts + shared),
                               np.asarray(whole), atol=1e-5)


# ---------------------------------------------------------------------------
# through the engine
# ---------------------------------------------------------------------------
def served_gap(tiny, prompt, tokens):
    """How far below the reference's best logit the served tokens lie."""
    model, c, forward = tiny
    seq = np.concatenate([prompt, tokens[:-1]])
    lg = reference_logits(forward, model.params, seq)[prompt.size - 1:]
    return float(np.max(lg.max(-1) - lg[np.arange(len(tokens)), tokens]))


def test_engine_lanes_join_and_leave_without_a_retrace(tiny):
    model, c, forward = tiny
    rng = np.random.default_rng(11)
    work = [(prompt_of(int(rng.integers(3, 30)), 100 + i),
             int(rng.integers(2, 12))) for i in range(10)]
    with serve.ContinuousEngine(model, max_slots=3, prefill_window=8,
                                prefill_lanes=2, decode_steps=3) as eng:
        futs = [eng.submit(p, n) for p, n in work]
        outs = [f.result(timeout=300) for f in futs]
        st = eng.stats()
        assert eng.assert_no_retraces() == 0
    for (p, n), o in zip(work, outs):
        assert len(o) == n
        assert served_gap(tiny, p, o) < TOL
    # every prefilled position went through the 3 KDA layers' chunkwise
    # form, every served one (but a request's first) through their step
    state = st["state"]
    assert state["chunk_positions"] == 3 * st["prefill_tokens"]
    assert state["lane_layer_steps"] == 3 * st["decode_tokens"]
    moe = st["moe"]
    tokens = st["prefill_tokens"] + st["decode_tokens"]
    # 3 expert layers; this share's group is among a token's 2 of 4 about
    # half the time, and only then can a pair land here
    assert moe["tokens_routed"] == 3 * tokens
    assert 0.25 < moe["groups_kept_here"] / moe["tokens_routed"] < 0.75
    assert 0 < moe["pairs_held"] <= 2 * moe["groups_kept_here"]
    assert 0 < moe["experts_hit"] <= moe["experts_offered"]
    # the cache's price: 3 states of 4 x 16 x 16 float32 and 3 tails of
    # 3 x 192 beside one latent leaf of 20 a position; 3 slots + garbage
    assert st["cache"]["state"]["bytes"] == 4 * 3 * (4096 + 3 * 192 * 4)
    assert st["cache"]["full"]["bytes"] == 4 * 48 * 20 * 4
    # a state leaf is live whole from the first token
    assert st["cache"]["state"]["live_bytes_sum"] > 0


def test_slot_reused_after_a_poison_fill_of_every_leaf(tiny):
    """A reused slot equals a fresh pool: the next tenant's prefill at
    offset 0 starts state and tail from zero whatever the row holds."""
    model, c, forward = tiny
    eng = serve.ContinuousEngine(model, max_slots=1, prefill_window=8,
                                 decode_steps=2).start()
    try:
        eng.generate(prompt_of(19, 21), 9, timeout=300)
        assert eng.pool.in_use() == []
        eng.pool.poison(1e9)
        prompt = prompt_of(13, 22)
        out = eng.generate(prompt, 8, timeout=300)
    finally:
        eng.close()
    assert served_gap(tiny, prompt, out) < TOL


def test_a_served_request_leaves_the_references_state_in_its_row(tiny):
    """`fut.timing.slot` names the pool row; an idle lane keeps what it
    held, so after the request the row's `kda{i}` leaves hold the state
    after prompt + served[:-1] (prefill, two chunks, then decode steps
    through the cache): the float32 recurrence's, which a state rounded to
    bfloat16 misses a hundred times wider. What `chipbench/paths/
    serve_delta_moe.py` compares on the chip."""
    model, c, forward = tiny
    prompt = prompt_of(21, 31)
    with serve.ContinuousEngine(model, max_slots=2, prefill_window=8,
                                decode_steps=3) as eng:
        # the neighbour decodes on for 20 steps after the request retired
        fut, other = eng.submit(prompt, 11), eng.submit(prompt_of(9, 32), 31)
        out = fut.result(timeout=300)
        assert len(other.result(timeout=300)) == 31
        leaves, = eng.pool.buffers()
        got = [np.asarray(leaves[f"kda{i}"][fut.timing.slot])
               for i in range(c.n_kda)]
    assert {fut.timing.slot, other.timing.slot} == {0, 1}
    want = ling_kda.served_rows_and_states(forward, model.params, prompt,
                                           out, 32)[1]
    assert max(ling_kda.state_gaps(got, want)) < 1e-5
    low = ling_kda.served_rows_and_states(
        ling_kda.make_forward(c.as_dict(), "bf16_state", q_block=QB),
        model.params, prompt, out, 32)[1]
    assert min(ling_kda.state_gaps(low, want)) > 1e-3


def test_cache_spec_is_state_leaves_and_one_latent(tiny):
    model, c, forward = tiny
    spec = model.cache_spec()
    assert [leaf.name for leaf in spec] == [
        "kda0", "conv0", "kda1", "conv1", "kda2", "conv2", "lat0"]
    assert [leaf.kind for leaf in spec] == ["state"] * 6 + ["full"]
    assert spec[0].shape == (4, 16, 16) and spec[0].dtype == "float32"
    assert spec[1].shape == (3, 192) and spec[-1].shape == (48, 20)
    pool = model.new_pool(max_slots=2)
    row = 3 * (4096 + 3 * 192 * 4)
    assert pool.bytes_by_kind([10, 3]) == {"state": 2 * row,
                                           "full": 13 * 20 * 4}


@pytest.mark.parametrize("option, value, error", [
    ("prefix_cache_slots", 2, CacheKindError),
    ("draft_tokens", 2, CacheKindError),
    ("kv_dtype", "int8", CacheKindError)])
def test_engine_refuses_what_a_state_has_no_form_of(tiny, option, value,
                                                    error):
    model, c, forward = tiny
    with pytest.raises(error):
        serve.ContinuousEngine(model, max_slots=2, **{option: value})


def test_every_program_names_its_layers_by_scope(tiny):
    model, c, forward = tiny
    pool = model.new_pool(max_slots=2)
    avals = pool.avals()
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    chunk = model.chunk_prefill_program(16, 48).lower(
        model.params, *avals, i32(1, 16), i32(1), i32(1), i32(1))
    decode = model.decode_program(2).lower(
        model.params, *avals, i32(2), i32(2), i32(2), f32(2), i32(2),
        f32(2), jax.ShapeDtypeStruct((2, 2), jnp.uint32))
    for lowered in (chunk, decode):
        text = lowered.as_text(debug_info=True)
        for scope in ("layer0/kda_proj", "layer0/kda_conv",
                      "layer1/kda_state", "layer2/mla", "layer0/mlp",
                      "layer1/router", "layer1/experts",
                      "layer3/shared_expert", "layer3/kda_state"):
            assert scope in text, scope
        assert "layer2/kda_state" not in text       # the MLA layer


def test_config_refuses_what_it_cannot_mean():
    with pytest.raises(serve.ServeError, match="mla"):
        make_config(mixer_types=("kda",) * 4)
    with pytest.raises(serve.ServeError, match="held experts"):
        make_config(held_first=14, held_count=4)
    with pytest.raises(serve.ServeError, match="same"):
        make_config(mlp_types=("dense",))
    with pytest.raises(serve.ServeError, match="float32"):
        make_config(kda_lower_bound=-100.0)
    assert make_config(kda_lower_bound=-5).sub_chunk == 16
    with pytest.raises(serve.ServeError, match="n_group"):
        make_config(n_group=3)
