"""models.sparse_moe_decoder: latent attention over one cached vector a
position, a learned choice of the positions a query reads (shared between
layers), and routed experts of which this process holds a share, served by
`serve.ContinuousEngine` and held to the plain reference
`chipbench/reference/glm_dsa.py` in float32.

What is under test:
  * prefill, chunked prefill and decode through the cache give the full
    forward's logits at every step, within a tolerance that a bfloat16
    cache fails, at contexts below, at and above `index_topk`
  * the reference's planted faults (newest positions for chosen ones, a
    stale choice in the `shared` layers, no rotary on the shared key, the
    wrong experts, gates normalised over the held experts) all fail it
  * rebuilt and absorbed latent attention agree; rotary by hand and at
    offsets; the exact top-k mask with ties; the grouped expert matmul
    against a dense sum, with a load that changes without a retrace
  * the share test: the routed parts of all the shares plus the shared
    expert counted once are the uncut layer
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

from chipbench.reference import glm_dsa  # noqa: E402
from incubator_mxnet_tpu import serve  # noqa: E402
from incubator_mxnet_tpu.models import sparse_moe_decoder as sm  # noqa: E402
from incubator_mxnet_tpu.serve.kv_pool import CacheKindError  # noqa: E402

# float32 program against float32 reference: rounding alone reads 1e-6;
# a cache rounded to bfloat16 reads 1e-3 and more (the control below)
TOL = 5e-5
QB = 8                       # the reference's query block at this size
# scales at which the softmax is peaked and the router's choice is the
# scores' (so that which positions and which experts were read shows)
SCALES = dict(sm.INIT_SCALES, q_b=0.3, kv_b=0.1, router=0.1, index_q=0.1)


def make_config(**over):
    kw = dict(vocab=96, embed=64, heads=4, index_topk=8,
              indexer_types=("full", "shared", "shared", "full"),
              mlp_types=("dense", "sparse", "sparse", "sparse"),
              routed_experts=16, experts_per_token=2, held_count=4,
              max_len=48)
    kw.update(over)
    return sm.SparseMoEConfig(**kw)


@pytest.fixture(scope="module")
def tiny():
    c = make_config()
    params = sm.init_sparse_moe_params(c, 1, SCALES)
    return (sm.SparseMoEDecoder(c, params), c,
            glm_dsa.make_forward(c.as_dict(), q_block=QB))


def prompt_of(n, seed=0, vocab=96):
    return np.random.default_rng(seed).integers(
        1, vocab, size=n).astype(np.int32)


def reference_logits(forward, params, tokens):
    """(len(tokens), vocab): the sequence padded to whole query blocks
    (causal, so the pad is never read)."""
    tokens = np.asarray(tokens, np.int32)
    padded = np.zeros((-(-tokens.size // QB) * QB,), np.int32)
    padded[:tokens.size] = tokens
    return np.asarray(glm_dsa.logits(forward, params, padded))[:tokens.size]


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
    decode steps through the cache, feeding the reference's own choices."""
    pool = model.new_pool(max_slots=1)
    pool.poison(1e9)
    seq = list(prompt)
    got = [prefill_logits(model, pool, prompt, window)]
    micro = jax.jit(sm._make_micro(model.config), donate_argnums=(1,))
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
# the program against the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("plen", [5, 8, 9, 30])
def test_prefill_then_decode_below_at_and_above_topk(tiny, plen):
    """Contexts below, at and above `index_topk` (8): from position 8 on
    the indexer chooses."""
    model, c, forward = tiny
    assert decode_gap(model, forward, prompt_of(plen, plen), 6, 32) < TOL


def test_prompt_split_over_chunk_boundaries(tiny):
    """21 tokens in chunks of 8: the second and third chunk read what the
    earlier ones cached and choose among it."""
    model, c, forward = tiny
    assert decode_gap(model, forward, prompt_of(21, 3), 10, 8) < TOL


def test_chunk_extents_read_the_same_positions(tiny):
    """The extent bounds what a chunk reads, not what it computes."""
    model, c, forward = tiny
    prompt = prompt_of(20, 4)
    got = []
    for extent in (32, 48):
        pool = model.new_pool(max_slots=1)
        prefill_logits(model, pool, prompt[:16], 16)
        toks = np.zeros((1, 16), np.int32)
        toks[0, :4] = prompt[16:]
        (cache,) = pool.buffers()
        _, logits, _ = model.chunk_prefill_program(16, extent)(
            model.params, cache, jnp.asarray(toks), one(16), one(4), one(0))
        got.append(np.asarray(logits)[0])
    want = reference_logits(forward, model.params, prompt)[-1]
    assert np.max(np.abs(got[0] - want)) < TOL
    assert np.max(np.abs(got[1] - want)) < TOL


def test_a_bfloat16_cache_fails_the_tolerance(tiny):
    """The control of the tolerance: the same pass with activations and
    cache rounded to bfloat16 is far outside it."""
    model, c, forward = tiny
    seq = prompt_of(24, 5)
    low = glm_dsa.make_forward(c.as_dict(), "bfloat16", q_block=QB)
    gap = np.max(np.abs(reference_logits(low, model.params, seq)
                        - reference_logits(forward, model.params, seq)))
    assert gap > 20 * TOL


@pytest.mark.parametrize("fault", glm_dsa.FAULTS)
def test_planted_fault_is_outside_the_tolerance(tiny, fault):
    """Each planted fault of the reference moves the logits of a context
    above `index_topk` by far more than the tolerance: the comparison sees
    the choice of positions, its sharing, the rotary and the routing."""
    model, c, forward = tiny
    seq = prompt_of(40, 6)
    bad = glm_dsa.make_forward(c.as_dict(), fault, q_block=QB)
    gap = np.max(np.abs(reference_logits(bad, model.params, seq)
                        - reference_logits(forward, model.params, seq)))
    assert gap > 20 * TOL


def test_shared_layers_read_the_full_layers_choice(tiny):
    """The reference's S of a `shared` layer IS the `full` layer's below,
    and the program agreeing with the reference (above) reads it too."""
    model, c, forward = tiny
    sels = forward[2](model.params, jnp.asarray(prompt_of(24, 7)))
    assert len(sels) == 4
    for shared in (1, 2):
        np.testing.assert_array_equal(sels[shared], sels[0])
    assert not np.array_equal(sels[3], sels[0])
    counts = np.asarray(sels[3]).sum(-1)
    np.testing.assert_array_equal(counts, np.minimum(np.arange(24) + 1, 8))


# ---------------------------------------------------------------------------
# layer functions by hand
# ---------------------------------------------------------------------------
def test_rotary_by_hand_and_at_offsets():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((5, 3, 8)).astype(np.float32)
    pos = np.array([0, 1, 7, 100, 4000])
    got = np.asarray(sm.rope(jnp.asarray(x), jnp.asarray(pos), 10000.0))
    want = np.empty_like(x)
    for t, p in enumerate(pos):
        for i in range(4):
            ang = p * 10000.0 ** (-2 * i / 8)
            a, b = x[t, :, 2 * i], x[t, :, 2 * i + 1]
            want[t, :, 2 * i] = a * np.cos(ang) - b * np.sin(ang)
            want[t, :, 2 * i + 1] = a * np.sin(ang) + b * np.cos(ang)
    np.testing.assert_allclose(got, want, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(glm_dsa.rope(jnp.asarray(x), jnp.asarray(pos), 10000.0)),
        got, atol=1e-6)
    # a rotated query and key meet at their distance alone
    q, k = x[0, 0], x[1, 0]
    dots = [float(jnp.dot(sm.rope(jnp.asarray(q), jnp.asarray(t + 5), 1e4),
                          sm.rope(jnp.asarray(k), jnp.asarray(t), 1e4)))
            for t in (0, 3, 40)]
    np.testing.assert_allclose(dots, dots[0], atol=1e-4)


def test_rebuilt_and_absorbed_latent_attention_agree():
    c = make_config()
    rng = np.random.default_rng(1)
    S, K = 3, 11
    H, dn, dr, dv, kvr = (c.heads, c.qk_nope_head_dim, c.qk_rope_head_dim,
                          c.v_head_dim, c.kv_lora_rank)
    q_nope = jnp.asarray(rng.standard_normal((S, H, dn)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((S, H, dr)), jnp.float32)
    ckr = jnp.asarray(rng.standard_normal((S, K, kvr + dr)), jnp.float32)
    wkv_b = jnp.asarray(rng.standard_normal((kvr, H * (dn + dv))) * 0.2,
                        jnp.float32)
    valid = jnp.asarray(rng.random((S, K)) < 0.7).at[:, 0].set(True)
    absorbed = sm.mla_read_absorbed(q_nope, q_rope, ckr, valid, wkv_b, c)
    rebuilt = sm.mla_read_rebuilt(q_nope[:, None], q_rope[:, None], ckr,
                                  valid[:, None], wkv_b, c)[:, 0]
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(rebuilt),
                               atol=1e-5)


@pytest.mark.parametrize("k", [1, 4, 9, 20])
def test_select_mask_is_the_exact_top_k_with_ties(k):
    rng = np.random.default_rng(k)
    scores = rng.standard_normal((3, 5, 16)).astype(np.float32)
    scores[0, 0, :] = 0.5                      # all equal
    scores[1, 2, 3:9] = scores[1, 2, 3]        # a tie across the edge
    scores[2, 1, :] = -np.abs(scores[2, 1, :])  # negatives
    live = rng.random((3, 5, 16)) < 0.8
    live[0, 1, :] = False
    got = np.asarray(sm.select_mask(jnp.asarray(scores), jnp.asarray(live),
                                    k))
    for idx in np.ndindex(3, 5):
        alive = np.flatnonzero(live[idx])
        # the lower position first among equals: a stable sort descending
        order = alive[np.argsort(-scores[idx][alive], kind="stable")]
        want = np.zeros(16, bool)
        want[order[:k]] = True
        np.testing.assert_array_equal(got[idx], want, err_msg=str(idx))


def dense_experts(h, idx, gates, ok, w_gate_up, w_down, first):
    """Every held expert over every token, weighted by its gate."""
    held = w_gate_up.shape[0]
    y = np.zeros(h.shape, np.float32)
    for e in range(held):
        g = np.where(ok, (gates * (idx == first + e)).sum(-1), 0.0)
        gu = h @ w_gate_up[e]
        F = gu.shape[-1] // 2
        y += g[:, None] * ((gu[:, :F] / (1 + np.exp(-gu[:, :F]))
                            * gu[:, F:]) @ w_down[e])
    return y


def test_grouped_expert_matmul_against_the_dense_sum_without_a_retrace():
    """Loads from none to every pair on one expert (several blocks of
    rows), through ONE trace."""
    rng = np.random.default_rng(2)
    T, k, d, F, held, first = 40, 2, 16, 8, 4, 4
    h = rng.standard_normal((T, d)).astype(np.float32)
    w_gu = (rng.standard_normal((held, d, 2 * F)) * 0.3).astype(np.float32)
    w_dn = (rng.standard_normal((held, F, d)) * 0.3).astype(np.float32)
    fn = jax.jit(sm.routed_experts, static_argnums=(6, 7))
    old_block, sm.EXPERT_BLOCK = sm.EXPERT_BLOCK, 16
    try:
        cases = {
            "mixed": rng.integers(0, 16, size=(T, k)),
            "none held": rng.integers(8, 16, size=(T, k)),
            "all on one": np.full((T, k), 5),
            "all held": rng.integers(4, 8, size=(T, k)),
        }
        for name, idx in cases.items():
            gates = rng.random((T, k)).astype(np.float32)
            ok = rng.random(T) < 0.9
            y, loads = fn(jnp.asarray(h), jnp.asarray(idx, jnp.int32),
                          jnp.asarray(gates), jnp.asarray(ok),
                          jnp.asarray(w_gu), jnp.asarray(w_dn), first, held)
            np.testing.assert_allclose(
                np.asarray(y), dense_experts(h, idx, gates, ok, w_gu, w_dn,
                                             first),
                atol=1e-4, err_msg=name)
            np.testing.assert_array_equal(
                np.asarray(loads),
                [(ok[:, None] & (idx == first + e)).sum()
                 for e in range(held)], err_msg=name)
        assert fn._cache_size() == 1
    finally:
        sm.EXPERT_BLOCK = old_block


def test_routing_gates_are_normalised_over_all_the_chosen():
    c = make_config()
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.standard_normal((7, 64)), jnp.float32)
    r_w = jnp.asarray(rng.standard_normal((64, 16)) * 0.3, jnp.float32)
    r_b = jnp.asarray(rng.standard_normal((16,)) * 0.5, jnp.float32)
    idx, gates = sm.route(h, r_w, r_b, c)
    sig = 1 / (1 + np.exp(-np.asarray(h @ r_w)))
    want = np.argsort(-(sig + np.asarray(r_b)), -1)[:, :2]
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(want, -1))
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.5, rtol=1e-5)
    picked = np.take_along_axis(sig, np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.asarray(gates), 2.5 * picked / picked.sum(-1, keepdims=True),
        rtol=1e-5)


def test_all_the_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """The guide's share test, on the whole model's logits' last layer:
    a one-layer model cut into 4 shares of 4 experts. The residual stream
    after the expert layer is x + routed + shared; the shares' routed
    parts (each share's output minus x minus the shared expert's term,
    read off a share that holds an expert nobody is routed to) add up to
    the uncut reference's routed part."""
    c = make_config(indexer_types=("full",), mlp_types=("sparse",),
                    held_count=16)
    params = sm.init_sparse_moe_params(c, 2, SCALES)
    seq = prompt_of(16, 8)
    x = params["emb"][jnp.asarray(seq)]
    w = sm._weights(params, c, 0)
    ok = jnp.ones((16,), bool)
    whole, _ = sm._ffn(x, w, c, 0, ok)
    h = sm.rms_norm(x, w["ln2_w"], c.norm_eps)
    shared = sm.gated_mlp(h, w["s_gate_up"], w["s_down"])
    parts = jnp.zeros_like(x)
    for first in (0, 4, 8, 12):
        cut = make_config(indexer_types=("full",), mlp_types=("sparse",),
                          held_first=first, held_count=4)
        # this share's experts are rows [first, first + 4) of the stack
        y, counted = sm._ffn(x, dict(w, e_row0=first), cut, 0, ok)
        parts = parts + (y - x - shared)
    np.testing.assert_allclose(np.asarray(x + parts + shared),
                               np.asarray(whole), atol=1e-5)
    # and the uncut reference's layer is the program's uncut layer
    fwd = glm_dsa.make_forward(c.as_dict(), q_block=QB)
    got = np.asarray(sm._head(
        params, sm._ffn(_attend_like_reference(params, c, seq), w, c, 0,
                        ok)[0], c))
    np.testing.assert_allclose(
        got, reference_logits(fwd, params, seq), atol=TOL)


def _attend_like_reference(params, c, seq):
    """The stream after the one layer's attention, by the program's chunk
    functions at offset 0 (no cache before it)."""
    w = sm._weights(params, c, 0)
    x = params["emb"][jnp.asarray(seq)][None]
    pos = jnp.arange(len(seq))[None]
    h = sm.rms_norm(x, w["ln1_w"], c.norm_eps)
    cq, q_nope, q_rope, ckr = sm.mla_project(w, c, h, pos)
    live = jnp.arange(len(seq))[None, None, :] <= pos[..., None]
    qI, wI, kI = sm.index_project(w, c, h, cq, pos)
    mask = sm.select_mask(sm.index_scores(qI, wI, kI), live, c.index_topk)
    o = sm.mla_read_rebuilt(q_nope, q_rope, ckr, mask, w["wkv_b"], c)
    return (x + o @ w["wo"])[0]


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
    # the counters: every served or prefilled position is a query in each
    # of the 4 layers; it reads min(t + 1, 8) of its t + 1 live positions
    sp = st["sparse"]
    tokens = st["prefill_tokens"] + st["decode_tokens"]
    assert sp["queries"] == 4 * tokens
    want_live = want_chosen = 0
    for (p, n), o in zip(work, outs):
        t = np.arange(p.size + n - 1)
        want_live += 4 * int((t + 1).sum())
        want_chosen += 4 * int(np.minimum(t + 1, 8).sum())
    assert sp["live_positions"] == want_live
    assert sp["chosen_positions"] == want_chosen
    moe = st["moe"]
    # 3 expert layers, 2 of 16 experts a token, 4 held: a quarter or so
    assert 0 < moe["pairs_held"] < 3 * 2 * tokens
    assert 0 < moe["experts_hit"] <= moe["experts_offered"]
    assert moe["experts_offered"] % (3 * 4) == 0
    assert moe["max_load_sum"] >= moe["experts_hit"] / 4
    # the cache's price: 4 latent leaves of 20 and 2 index leaves of 8
    # float32 values a position, 3 slots and the garbage row
    assert st["cache"]["full"]["bytes"] == 4 * 48 * (4 * 20 + 2 * 8) * 4
    assert st["cache"]["full"]["live_bytes_sum"] > 0


def test_slot_reused_after_a_poison_fill_of_every_leaf(tiny):
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


def test_cache_spec_is_full_leaves_only(tiny):
    model, c, forward = tiny
    spec = model.cache_spec()
    assert [leaf.name for leaf in spec] == [
        "lat0", "lat1", "lat2", "lat3", "idx0", "idx1"]
    assert {leaf.kind for leaf in spec} == {"full"}
    assert spec[0].shape == (48, 20) and spec[-1].shape == (48, 8)
    pool = model.new_pool(max_slots=2)
    assert pool.bytes_per_slot() == 48 * (4 * 20 + 2 * 8) * 4
    assert pool.bytes_by_kind([10, 3]) == {"full": 13 * (4 * 20 + 2 * 8) * 4}


@pytest.mark.parametrize("option, value, error", [
    ("prefix_cache_slots", 2, CacheKindError),
    ("draft_tokens", 2, serve.ServeError),
    ("kv_dtype", "int8", serve.ServeError)])
def test_engine_refuses_what_this_cache_has_no_form_of(tiny, option, value,
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
        for scope in ("layer0/mla", "layer0/indexer", "layer0/select",
                      "layer1/sparse_read", "layer0/mlp", "layer1/router",
                      "layer1/experts", "layer1/shared_expert",
                      "layer3/select"):
            assert scope in text, scope
        assert "layer1/select" not in text      # a `shared` layer


def test_config_refuses_what_it_cannot_mean():
    with pytest.raises(serve.ServeError, match="first layer"):
        make_config(indexer_types=("shared", "full", "full", "full"))
    with pytest.raises(serve.ServeError, match="held experts"):
        make_config(held_first=14, held_count=4)
    with pytest.raises(serve.ServeError, match="same"):
        make_config(mlp_types=("dense",))
