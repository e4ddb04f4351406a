"""models.mamba_moe_decoder: Mamba-2 mixers whose cache is a float32 state a
head and a conv tail, sigmoid-routed relu^2 experts of which this process
holds a share with a shared one, and attention with no positional
encoding, one of the three a layer, served by `serve.ContinuousEngine` and
held to the plain reference `chipbench/reference/nemotron_h.py` in float32.

What is under test:
  * the reference against a loop over the equations written by hand
  * the chunked (SSD) form equals the recurrence one position at a time,
    over chunk edges, at windows that divide no chunk and with pad lanes
  * prefill, chunked prefill and decode through the cache give the full
    forward's logits at every step, from a poison-filled pool; the state a
    served request leaves in its row is the reference's
  * the controls (bfloat16, a bfloat16 state, 8-bit codes) and the planted
    faults (the state cut at a chunk edge, a previous tenant's state, the
    tail cut, the wrong group, relu for relu^2, the norm before the gate)
    all fail the tolerance
  * `routed_experts`' relu^2 body against a plain loop; the share test: the
    routed parts of all 16 shares plus the shared expert once are the
    uncut layer, the program's and the reference's
  * the paged leaf kernel at 16 query heads a KV head in interpret mode
  * lanes joining and leaving without a retrace; the counters and the
    cache's price in `stats()`; what the engine refuses
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.reference import nemotron_h  # noqa: E402
from incubator_mxnet_tpu import serve  # noqa: E402
from incubator_mxnet_tpu.models import mamba_moe_decoder as mm  # noqa: E402
from incubator_mxnet_tpu.models import sparse_moe_decoder as sm  # noqa: E402
from incubator_mxnet_tpu.serve.kv_pool import CacheKindError  # noqa: E402

# float32 program against float32 reference: the chunked form reorders the
# recurrence's sums, which reads 1e-6 on logits of order 1; a state rounded
# to bfloat16 reads 1e-3 and more (the controls below)
TOL = 5e-5
QB = 8                       # the reference's query block at this size
# scales at which every mechanism shows in the logits: a stream the
# mixers move, decays from one position to tens, a router whose choice is
# the scores'
SCALES = dict(mm.INIT_SCALES, normal=0.15, emb=1.0, o=0.1, down=0.1,
              router=0.3, router_bias=0.05, A_log=(0.5, 4.0),
              dt_bias=(1e-2, 0.3, 1e-4))


def make_config(**over):
    """Every kind at least twice, an attention layer first among them
    after a Mamba layer; chunks of 4 positions, so that windows of 8 and
    more hold several."""
    kw = dict(vocab=96, embed=32, pattern="ME*MEM*E", mamba_heads=8,
              mamba_head_dim=4, n_groups=2, ssm_state=8, chunk_size=4,
              heads=4, kv_heads=2, head_dim=8, expert_hidden=16,
              shared_hidden=24, routed_experts=16, experts_per_token=3,
              held_first=4, held_count=4, max_len=48)
    kw.update(over)
    return mm.MambaMoEConfig(**kw)


@pytest.fixture(scope="module")
def tiny():
    c = make_config()
    params = mm.init_mamba_moe_params(c, 1, SCALES)
    return (mm.MambaMoEDecoder(c, params), c,
            nemotron_h.make_forward(c.as_dict(), q_block=QB))


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
    micro = jax.jit(mm._make_micro(model.config), donate_argnums=(1,))
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
# the reference against the equations, by hand
# ---------------------------------------------------------------------------
def _hand_forward(c, p, tokens):
    """The model's equations as plain float64 loops over positions, heads
    and experts: the logits (T, vocab)."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    T, d = len(tokens), c.embed
    H, P, G, N, K = (c.mamba_heads, c.mamba_head_dim, c.n_groups,
                     c.ssm_state, c.conv_kernel)
    Di = H * P

    def norm(v, w):
        return v / np.sqrt(np.mean(v * v) + c.norm_eps) * w

    def silu(v):
        return v / (1 + np.exp(-v))

    x = [p["emb"][t].copy() for t in tokens]
    for l, kind in enumerate(c.kinds):
        i = c.slots[l]
        h = [norm(x[t], p["norm"][l]) for t in range(T)]
        if kind == "mamba":
            proj = [h[t] @ p["m_in"][i] for t in range(T)]
            raw = [pr[Di:Di + c.conv_dim] for pr in proj]
            S = np.zeros((H, P, N))
            for t in range(T):
                conv = p["m_conv_b"][i].copy()
                for j in range(K):
                    if t - (K - 1) + j >= 0:
                        conv += p["m_conv"][i][j] * raw[t - (K - 1) + j]
                xbc = silu(conv)
                xs = xbc[:Di].reshape(H, P)
                Bm = xbc[Di:Di + G * N].reshape(G, N)
                Cm = xbc[Di + G * N:].reshape(G, N)
                z = proj[t][:Di]
                y = np.zeros((H, P))
                for hh in range(H):
                    g = hh // (H // G)
                    dt = np.log1p(np.exp(proj[t][Di + c.conv_dim + hh]
                                         + p["m_dt_bias"][i][hh]))
                    A = -np.exp(p["m_A_log"][i][hh])
                    S[hh] = np.exp(dt * A) * S[hh] \
                        + dt * np.outer(xs[hh], Bm[g])
                    y[hh] = S[hh] @ Cm[g] + p["m_D"][i][hh] * xs[hh]
                gated = (y.reshape(Di) * silu(z)).reshape(G, -1)
                normed = np.concatenate([v / np.sqrt(np.mean(v * v)
                                                     + c.norm_eps)
                                         for v in gated])
                x[t] = x[t] + (normed * p["m_norm"][i]) @ p["m_out"][i]
        elif kind == "attn":
            q = [(hh @ p["a_q"][i]).reshape(c.heads, c.head_dim) for hh in h]
            k = [(hh @ p["a_k"][i]).reshape(c.kv_heads, c.head_dim)
                 for hh in h]
            v = [(hh @ p["a_v"][i]).reshape(c.kv_heads, c.head_dim)
                 for hh in h]
            new = []
            for t in range(T):
                o = np.zeros((c.heads, c.head_dim))
                for hh in range(c.heads):
                    kv = hh // (c.heads // c.kv_heads)
                    s = np.array([q[t][hh] @ k[r][kv]
                                  for r in range(t + 1)]) / np.sqrt(c.head_dim)
                    w = np.exp(s - s.max())
                    w /= w.sum()
                    o[hh] = sum(w[r] * v[r][kv] for r in range(t + 1))
                new.append(x[t] + o.reshape(-1) @ p["a_o"][i])
            x = new
        else:
            for t in range(T):
                sig = 1 / (1 + np.exp(-(h[t] @ p["r_w"][i])))
                chosen = np.argsort(-(sig + p["r_b"][i]),
                                    kind="stable")[:c.experts_per_token]
                y = np.maximum(h[t] @ p["s_up"][i], 0) ** 2 @ p["s_down"][i]
                for e in chosen:
                    if c.held_first <= e < c.held_first + c.held_count:
                        j = e - c.held_first
                        g = c.routed_scaling_factor * sig[e] \
                            / sig[chosen].sum()
                        y = y + g * (np.maximum(p["e_up"][i][j] @ h[t], 0)
                                     ** 2 @ p["e_down"][i][j])
                x[t] = x[t] + y
    return np.stack([norm(v, p["lnf_w"]) @ p["head"] for v in x])


def test_the_reference_is_the_equations_by_hand(tiny):
    model, c, forward = tiny
    seq = prompt_of(11, 2)
    want = _hand_forward(c, model.params, seq)
    got = reference_logits(forward, model.params, seq)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# the Mamba-2 mixer: chunked = the recurrence
# ---------------------------------------------------------------------------
def _ssm_inputs(L, W, H, P, G, N, seed):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((L, W, H, P))
    # steps from 1e-3 to 0.3 at decays A of -1 to -16: per position a
    # factor of 0.999 to 0.008
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.3), (L, W, H)))
    A = -rng.uniform(1, 16, (H,))
    B = rng.standard_normal((L, W, G, N))
    C = rng.standard_normal((L, W, G, N))
    D = rng.standard_normal((H,))
    s0 = rng.standard_normal((L, H, P, N))
    return tuple(jnp.asarray(a, jnp.float32) for a in (x, dt, A, B, C, D, s0))


def _sequential(x, dt, A, B, C, D, state):
    def step(s, xs):
        x_t, dt_t, B_t, C_t = xs
        y, s = mm.mamba2_step(x_t, dt_t, A, B_t, C_t, D, s)
        return s, y
    state, y = jax.lax.scan(step, state, tuple(
        a.swapaxes(0, 1) for a in (x, dt, B, C)))
    return y.swapaxes(0, 1), state


@pytest.mark.parametrize("W, chunk", [
    (37, 8),               # a window that divides no chunk
    (16, 16), (5, 8), (40, 4), (256, 128)])
def test_chunked_form_is_the_recurrence(W, chunk):
    """Over chunk edges: the state passed from chunk to chunk, every
    position of a chunk against the state carried in. At the served chunk
    of 128 a decay of exp(-16 x 0.3) a position puts s_Q at -600: masked
    before the exponential, and no ratio of exponentials is ever formed."""
    x, dt, A, B, C, D, s0 = _ssm_inputs(2, W, 8, 4, 2, 8, W)
    y_seq, s_seq = _sequential(x, dt, A, B, C, D, s0)
    y, s = jax.jit(mm.mamba2_chunk, static_argnums=(7,))(
        x, dt, A, B, C, D, s0, chunk)
    assert np.all(np.isfinite(np.asarray(y)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_seq), atol=2e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_seq), atol=2e-5,
                               rtol=1e-5)


def test_a_pad_position_leaves_the_state_as_it_is():
    """dt 0 (what the chunk program gives the positions past a lane's
    valid ones, and a whole idle lane): the state after 11 real positions
    and 5 pads is the state after the 11; a lane of pads keeps its state
    bit for bit."""
    x, dt, A, B, C, D, s0 = _ssm_inputs(2, 16, 4, 4, 2, 8, 3)
    real = (jnp.arange(16) < 11)[None, :] & (jnp.arange(2) == 0)[:, None]
    dt = jnp.where(real[..., None], dt, 0.0)
    _, s = mm.mamba2_chunk(x, dt, A, B, C, D, s0, 8)
    _, want = _sequential(*(a[:1, :11] for a in (x, dt)), A,
                          *(a[:1, :11] for a in (B, C)), D, s0[:1])
    np.testing.assert_allclose(np.asarray(s)[:1], np.asarray(want),
                               atol=2e-5)
    np.testing.assert_array_equal(np.asarray(s)[1], np.asarray(s0)[1])
    _, kept = mm.mamba2_step(x[:, 0], jnp.zeros_like(dt[:, 0]), A, B[:, 0],
                             C[:, 0], D, s0)
    np.testing.assert_array_equal(np.asarray(kept), np.asarray(s0))


def test_the_step_by_hand():
    """S' = exp(dt A) S + dt x B^T, y = S' C + D x, head h over group
    h // (H / G)."""
    x, dt, A, B, C, D, s0 = (np.asarray(a, np.float64) for a in
                             _ssm_inputs(1, 1, 4, 3, 2, 5, 5))
    y, s = mm.mamba2_step(*(jnp.asarray(a, jnp.float32) for a in
                            (x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], D, s0)))
    for h in range(4):
        g = h // 2
        want = np.exp(dt[0, 0, h] * A[h]) * s0[0, h] \
            + dt[0, 0, h] * np.outer(x[0, 0, h], B[0, 0, g])
        np.testing.assert_allclose(np.asarray(s)[0, h], want, atol=1e-5)
        np.testing.assert_allclose(np.asarray(y)[0, h],
                                   want @ C[0, 0, g] + D[h] * x[0, 0, h],
                                   atol=1e-5)


def test_the_gated_norm_is_by_group_after_the_gate():
    rng = np.random.default_rng(6)
    y, z = rng.standard_normal((2, 3, 12))
    w = rng.standard_normal((12,))
    got = mm.gated_group_rms_norm(jnp.asarray(y, jnp.float32),
                                  jnp.asarray(z, jnp.float32),
                                  jnp.asarray(w, jnp.float32), 3, 1e-5)
    g = (y * z / (1 + np.exp(-z))).reshape(3, 3, 4)
    want = (g / np.sqrt(np.mean(g * g, -1, keepdims=True) + 1e-5)).reshape(
        3, 12) * w
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("plen, window", [(5, 16), (9, 16), (16, 16),
                                          (21, 8), (23, 6), (40, 16)])
def test_prefill_then_decode_is_the_full_forward(tiny, plen, window):
    """Whole prompts in one window, and prompts split over windows of 8,
    6 (which divides no chunk of 4) and 16: the state and the conv tail of
    every Mamba layer and the K/V rows are carried over each boundary, then
    through 6 decode steps."""
    model, c, forward = tiny
    assert decode_gap(model, forward, prompt_of(plen, plen), 6,
                      window) < TOL


@pytest.mark.parametrize("control", ["bfloat16", "bf16_state", "int8"])
def test_a_lower_precision_fails_the_tolerance(tiny, control):
    """The controls of the tolerance: the same pass all in bfloat16, with
    the state alone rounded to bfloat16, and in 8-bit codes."""
    model, c, forward = tiny
    seq = prompt_of(24, 5)
    low = nemotron_h.make_forward(c.as_dict(), control, q_block=QB)
    gap = np.max(np.abs(reference_logits(low, model.params, seq)
                        - reference_logits(forward, model.params, seq)))
    assert gap > 20 * TOL


@pytest.mark.parametrize("fault", nemotron_h.FAULTS)
def test_planted_fault_is_outside_the_tolerance(tiny, fault):
    """Each planted fault of the reference moves the logits by far more
    than the tolerance: the comparison sees the state and the tail
    crossing a chunk edge, whose state it is, which group a head reads,
    the square of the experts' relu and where the norm sits."""
    model, c, forward = tiny
    seq = prompt_of(40, 6)
    bad = nemotron_h.make_forward(c.as_dict(), fault, q_block=QB, edge=8)
    gap = np.max(np.abs(reference_logits(bad, model.params, seq)
                        - reference_logits(forward, model.params, seq)))
    assert gap > 20 * TOL


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------
def test_relu2_body_against_a_plain_loop():
    rng = np.random.default_rng(3)
    T, d, F, n, k = 9, 16, 8, 6, 2
    h = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    idx = jnp.asarray(rng.permuted(np.tile(np.arange(n), (T, 1)),
                                   axis=1)[:, :k], jnp.int32)
    gates = jnp.asarray(rng.uniform(size=(T, k)), jnp.float32)
    up = jnp.asarray(rng.standard_normal((n, F, d)), jnp.float32)
    down = jnp.asarray(rng.standard_normal((n, F, d)), jnp.float32)
    ok = jnp.asarray(np.arange(T) != 4)
    # experts [2, 5) held, rows [1, 4) of the stack
    y, loads, _ = sm.routed_experts(h, idx - 1, gates, ok, up, down, 1, 3,
                                    row0=1, up=mm.relu2_up)
    want = np.zeros((T, d))
    count = np.zeros(3, int)
    for t in range(T):
        for j in range(k):
            e = int(idx[t, j]) - 1
            if 1 <= e < 4 and t != 4:
                r = np.maximum(np.asarray(up[e]) @ np.asarray(h[t]), 0)
                want[t] += float(gates[t, j]) * (r * r) @ np.asarray(down[e])
                count[e - 1] += 1
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-3)
    np.testing.assert_array_equal(np.asarray(loads), count)


def test_all_the_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """The guide's share test: an expert layer of 32 experts cut into 16
    shares of 2. The residual stream after the layer is x + routed +
    shared; the shares' routed parts (each share's output minus x minus
    the shared expert's term) add up to the uncut layer's, the program's
    and the plain reference's."""
    whole = make_config(routed_experts=32, held_first=0, held_count=32)
    params = mm.init_mamba_moe_params(whole, 2, SCALES)
    x = params["emb"][jnp.asarray(prompt_of(16, 8))]
    w = mm._weights(params, whole, 1)
    ok = jnp.ones((16,), bool)
    uncut, counted = mm._moe(x, w, whole, 1, ok, jnp.zeros((5,), jnp.int32))
    assert int(counted[0]) == 16 * whole.experts_per_token
    h = sm.rms_norm(x, w["norm"], whole.norm_eps)
    shared = mm._relu2_mlp(h, w["s_up"], w["s_down"])
    parts, pairs = jnp.zeros_like(x), 0
    for first in range(0, 32, 2):
        cut = make_config(routed_experts=32, held_first=first, held_count=2)
        # this share's experts are rows [first, first + 2) of the stack
        y, counted = mm._moe(x, dict(w, e_row0=first), cut, 1, ok,
                             jnp.zeros((5,), jnp.int32))
        parts = parts + (y - x - shared)
        pairs += int(counted[0])
    assert pairs == 16 * whole.experts_per_token
    np.testing.assert_allclose(np.asarray(x + parts + shared),
                               np.asarray(uncut), atol=1e-5)
    ref = nemotron_h.make_forward(whole.as_dict(), q_block=QB)
    h32, g32, shared32 = ref[2]["moe"](x, {
        "norm": params["norm"][1],
        **{n: params[n][0] for n in ("r_w", "r_b", "s_up", "s_down")}})
    want = np.asarray(x) + np.asarray(shared32)
    for e in range(32):
        r = jnp.maximum(h32 @ params["e_up"][0, e].T, 0)
        want = want + np.asarray(g32[:, e:e + 1] * ((r * r)
                                                    @ params["e_down"][0, e]))
    np.testing.assert_allclose(np.asarray(x + parts + shared), want,
                               atol=1e-4)


# ---------------------------------------------------------------------------
# the paged leaf kernel at the served group of 16 queries a KV head
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("C", [1, 8])
def test_paged_leaf_kernel_at_16_queries_a_kv_head(C):
    """32 query heads over 2 KV heads of 128 (a 256-wide leaf), rows as
    data, a decode query a lane and a chunk of 8: the kernel in interpret
    mode against `paged_attention_ref`."""
    from incubator_mxnet_tpu.ops import fused, pallas_kernels
    rng = np.random.default_rng(9)
    S, T, H, Hk, D = 3, 256, 32, 2, 128
    q = jnp.asarray(rng.standard_normal((S, C, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((S + 1, T, Hk * D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((S + 1, T, Hk * D)), jnp.bfloat16)
    lengths = jnp.asarray([0, 130, 200], jnp.int32)
    rows = jnp.asarray([2, 0, 3], jnp.int32)
    got = pallas_kernels.paged_attention_fwd(
        q, k, v, lengths, None, interpret=True, scale=D ** -0.5, rows=rows,
        out_dtype=jnp.float32)
    assert got is not None
    want = fused.paged_attention_ref(q, k, v, lengths, None, rows=rows,
                                     scale=D ** -0.5, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-2)


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
    # every prefilled position went through the 3 Mamba layers' chunked
    # form, every served one (but a request's first) through their step
    state = st["state"]
    assert state["chunk_positions"] == 3 * st["prefill_tokens"]
    assert state["lane_layer_steps"] == 3 * st["decode_tokens"]
    moe = st["moe"]
    assert 0 < moe["pairs_held"] and 0 < moe["experts_hit"] \
        <= moe["experts_offered"]
    assert moe["experts_hit"] <= moe["blocks"] <= moe["pairs_held"]
    # the cache's price: 3 states of 8 x 4 x 8 float32 and 3 tails of
    # 3 x 64 beside 2 K and 2 V leaves of 16 a position; 3 slots + garbage
    assert st["cache"]["state"]["bytes"] == 4 * 3 * (1024 + 3 * 64 * 4)
    assert st["cache"]["full"]["bytes"] == 4 * 4 * 48 * 16 * 4
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
    held, so after the request the row's `ssm{i}` leaves hold the state
    after prompt + served[:-1] (prefill, two chunks, then decode steps):
    the float32 recurrence's, which a state rounded to bfloat16 misses far
    wider. What `chipbench/paths/serve_mamba_moe.py` compares on the
    chip."""
    model, c, forward = tiny
    prompt = prompt_of(21, 31)
    with serve.ContinuousEngine(model, max_slots=2, prefill_window=8,
                                decode_steps=3) as eng:
        # the neighbour decodes on for 20 steps after the request retired
        fut, other = eng.submit(prompt, 11), eng.submit(prompt_of(9, 32), 31)
        out = fut.result(timeout=300)
        assert len(other.result(timeout=300)) == 31
        leaves, = eng.pool.buffers()
        got = [np.asarray(leaves[f"ssm{i}"][fut.timing.slot])
               for i in range(c.n_mamba)]
    assert {fut.timing.slot, other.timing.slot} == {0, 1}
    want = nemotron_h.served_rows_and_states(forward, model.params, prompt,
                                             out, 32)[1]
    assert max(nemotron_h.state_gaps(got, want)) < 1e-5
    low = nemotron_h.served_rows_and_states(
        nemotron_h.make_forward(c.as_dict(), "bf16_state", q_block=QB),
        model.params, prompt, out, 32)[1]
    assert min(nemotron_h.state_gaps(low, want)) > 1e-3


def test_cache_spec_is_states_tails_and_kv_leaves(tiny):
    model, c, forward = tiny
    spec = model.cache_spec()
    assert [leaf.name for leaf in spec] == [
        "ssm0", "conv0", "ssm1", "conv1", "ssm2", "conv2",
        "k0", "v0", "k1", "v1"]
    assert [leaf.kind for leaf in spec] == ["state"] * 6 + ["full"] * 4
    assert spec[0].shape == (8, 4, 8) and spec[0].dtype == "float32"
    assert spec[1].shape == (3, 64) and spec[-1].shape == (48, 16)


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
    assert model.chunk_prefill_program(16, 32) \
        is model.chunk_prefill_program(16)        # one program, any extent
    decode = model.decode_program(2).lower(
        model.params, *avals, i32(2), i32(2), i32(2), f32(2), i32(2),
        f32(2), jax.ShapeDtypeStruct((2, 2), jnp.uint32))
    for lowered in (chunk, decode):
        text = lowered.as_text(debug_info=True)
        for scope in ("layer0/mamba/proj", "layer0/mamba/conv",
                      "layer3/mamba/state", "layer5/mamba/out",
                      "layer2/attn", "layer1/router", "layer1/experts",
                      "layer7/shared_expert"):
            assert scope in text, scope
        assert "layer2/mamba" not in text           # an attention layer


def test_config_reads_the_published_pattern():
    c = make_config(pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*"
                            "EMEMEMEME")
    assert (c.n_mamba, c.n_moe, c.n_attn) == (23, 23, 6)
    assert [l for l, k in enumerate(c.kinds) if k == "attn"] == [
        5, 12, 19, 26, 33, 42]
    with pytest.raises(serve.ServeError, match="attention"):
        make_config(pattern="MEME")
    with pytest.raises(serve.ServeError, match="pattern"):
        make_config(pattern="ME*-")
    with pytest.raises(serve.ServeError, match="held experts"):
        make_config(held_first=14, held_count=4)
    with pytest.raises(serve.ServeError, match="divides"):
        make_config(mamba_heads=6, n_groups=4)
