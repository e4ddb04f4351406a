"""Plain reference of the served Ling-3.0-flash cut (`configs/
ling3f_serve.json`: Kimi-Delta-Attention layers with a matrix state, one
latent-attention layer among them read densely, group-limited
sigmoid-routed experts of which this chip holds `held_count`, one shared
expert): the whole causal forward pass over a prompt and the tokens served
after it, in float32 with `highest` matmul precision. No cache, no
batching, no kernels, no chunkwise form: the KDA state goes through the
recurrence one position at a time (`lax.scan`), attention is a masked
einsum over all positions in blocks of `Q_BLOCK` queries. Imports nothing
of the program.

For l = 0 .. L-1 (RMSNorm eps `norm_eps`, no biases; h the normed input):
  x <- x + Mix_l(RMSNorm(x));  x <- x + FFN_l(RMSNorm(x))
logits = RMSNorm(x) W_head, untied from the embedding.

KDA (`mixer_types[l] == "kda"`; H heads of D = `head_dim`, K = `conv_kernel`):
  [q~ | k~ | v~] = h W_qkv; x_t = SiLU(sum_{i<K} w_i * x~_{t-K+1+i}) on every
  channel of the three (zeros before the sequence), no bias;
  q <- l2(q) D^-1/2, k <- l2(k) per head, l2(x) = x / sqrt(|x|^2 + 1e-6);
  g_t = lb sigmoid(exp(A_h) (h W_f + b_f)) per channel, lb =
  `kda_lower_bound`; beta_t = sigmoid(h W_beta) per head;
  S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T,
  S_{-1} = 0; o_t = S_t^T q_t;
  Mix = [RMSNorm_head(o_t) (one weight of D) * sigmoid(h W_g)] W_o.

MLA (`"mla"`): q = h W_q -> H heads of [q_nope | q_rope]; [c | kr] = h
  W_kv_a, c <- RMSNorm(c); rotary R_t on q_rope and kr (interleaved pairs
  (x_2i, x_2i+1), angle t theta^(-2i/rope)); [k_nope_h | v_h] = c W_kv_b;
  s_{t,s,h} = (q_nope.k_nope_{s,h} + q_rope.kr_s) / sqrt(nope + rope);
  p = softmax over all s <= t; o_h = (sum p v_{s,h}) sigmoid(h W_a)_h;
  Mix = concat_h(o_h) W_o.

Experts (a `sparse` layer): sigma = sigmoid(h W_r), sigma' = sigma + b; the
  `routed_experts` experts lie in `n_group` contiguous groups; a group's
  score is the sum of its 2 largest sigma'; the `topk_group` best groups
  are kept; chosen = the `experts_per_token` largest sigma' inside them;
  g_i = scale * sigma_i / sum_chosen sigma;
  y = sum_{i in chosen and held} g_i E_i(h) + E_shared(h),
  E(h) = (silu(h W1) * h W3) W2, held = [held_first, held_first +
  held_count): the chip's share of the published sum, which is what goes
  on to the next layer. A `dense` layer is one E of width `mlp_hidden`.

Controls, each the same pass one step below what the configuration
states: `bfloat16` rounds every matmul's operands and result, every
norm's output, the conv's inputs, the cached [c | kr] and the residual
stream to bfloat16 (float32 state, decay, softmax, router and logits: what
a served bfloat16 model keeps); `bf16_state` is float32 but for the state,
rounded to bfloat16 after every position; `int8` rounds every matmul's two
operands and [c | kr] to 8-bit codes (symmetric, one scale per row of the
left operand, per column of the right one, per cached position).

Besides the logits a pass gives every KDA layer's state after the last of
the sequence's own positions (`served_rows_and_states`): what the
program's cache holds for a request that has just been served, and the one
thing a state kept in a narrower type moves far more than it moves a logit
(`state_gaps`).

Planted faults (`FAULTS`), for showing that a comparison of logits sees
what is new here. With `edge` the prefill window: `state_cut` starts the
state from zero again at every multiple of `edge` (a state not carried
over a chunk edge); `stale_state` starts it from what another tenant left
(the same layer over the sequence reversed); `tail_cut` gives the
convolution zeros for what lies before a program's own positions: before
every multiple of `edge` inside the prompt, and before every position from
the first decode step on (each step is a program call of its own);
`head_decay` decays a head by the mean of its channels' g; `no_delta`
drops the correction (S_t = Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T);
`no_group_limit` routes over all groups; `first_experts` sends every token
to the first `experts_per_token` held experts."""
from __future__ import annotations

import math

# the rounding controls and the small functions are `reference/sambay.py`'s,
# the rotary embedding and the two readers of a forward's logits
# `reference/glm_dsa.py`'s
from .glm_dsa import (_rmsnorm, first_choices,
                      gaps_below_best, rope)
from .sambay import _bf16, _f32, _fake_int8, _matmul, _silu

Q_BLOCK = 256               # queries a block of attention
HEAD_BLOCK = 16             # heads a pass of attention
L2_EPS = 1e-6
PRECISIONS = ("float32", "bfloat16", "bf16_state", "int8")
FAULTS = ("state_cut", "stale_state", "tail_cut", "head_decay", "no_delta",
          "no_group_limit", "first_experts")


def layer_slots(m):
    """[(index among the mixers of the layer's kind, index among the dense
    or the sparse feed-forward layers)] by layer."""
    out, n = [], {"kda": 0, "mla": 0, "dense": 0, "sparse": 0}
    for kinds in zip(m["mixer_types"], m["mlp_types"]):
        out.append(tuple(n[k] for k in kinds))
        for k in kinds:
            n[k] += 1
    return out


def make_forward(m, precision="float32", q_block=Q_BLOCK, edge=None):
    """-> (hidden, head, mixers): `hidden(params, tokens (T,) int32, n,
    first_step)` gives the final-norm activations (T, d) float32 of a
    sequence of n tokens padded to T (a multiple of `q_block`; the pad is
    after the tokens and causal, and leaves the state as it is) and the
    KDA layers' states after position n - 1, [(H, D, D) float32] (n also
    tells `stale_state` where the tenant's own tokens end, `first_step`
    tells `tail_cut` the position of the first decode step),
    `head(params, rows (n, d))` the logits (n, vocab) float32,
    `mixers` the two jitted mixer layers by kind (for a compile check of
    their size). `precision` is one of `PRECISIONS` or a planted fault of
    `FAULTS` (computed in float32); `edge` is the prefill window that
    `state_cut` and `tail_cut` cut at."""
    import jax
    import jax.numpy as jnp
    if precision not in PRECISIONS + FAULTS:
        raise ValueError(f"unknown reference precision {precision!r}")
    fault = precision if precision in FAULTS else None
    if fault in ("state_cut", "tail_cut") and not edge:
        raise ValueError(f"{fault} needs the `edge` it cuts at")
    state_act = _bf16 if precision == "bf16_state" else (lambda a: a)
    if fault or precision == "bf16_state":
        precision = "float32"
    H, D, K = m["heads"], m["head_dim"], m["conv_kernel"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    kvr = m["kv_lora_rank"]
    n_exp, per_tok = m["routed_experts"], m["experts_per_token"]
    n_group, topk_group = m["n_group"], m["topk_group"]
    first, held = m["held_first"], m["held_count"]
    theta, eps, lb = float(m["rope_theta"]), m["norm_eps"], \
        float(m["kda_lower_bound"])
    low = precision == "int8"
    act = _bf16 if precision == "bfloat16" else (lambda a: a)
    _mm = _matmul(precision)
    slots = layer_slots(m)

    def cached(a):
        """What a cache of this precision would hand back."""
        return _fake_int8(a, -1) if low else act(a)

    def l2(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)

    def recurrence(q, k, v, g, beta, s0, reset):
        """The state through the positions, one at a time. q, k, g (T, H,
        D), v (T, H, D), beta (T, H), reset (T,) bool -> (o (T, H, D), the
        last state)."""
        def step(s, xs):
            q_t, k_t, v_t, g_t, b_t, z_t = xs
            s = jnp.where(z_t, 0.0, s) * jnp.exp(g_t)[..., None]
            if fault == "no_delta":
                u = b_t[:, None] * v_t
            else:
                u = b_t[:, None] * (v_t - jnp.einsum(
                    "hk,hkv->hv", k_t, s, precision="highest"))
            s = state_act(s + k_t[..., None] * u[:, None, :])
            return s, jnp.einsum("hk,hkv->hv", q_t, s, precision="highest")

        s, o = jax.lax.scan(step, s0, (q, k, v, g, beta, reset))
        return o, s

    @jax.jit
    def kda(x, w, n, first_step):
        w = _f32(w)
        T = x.shape[0]
        pos = jnp.arange(T)
        h = act(_rmsnorm(x, w["ln1_w"], eps))
        own = act(_mm(h, w["k_qkv"]))                       # (T, 3 H D)
        conv = jnp.zeros_like(own)
        for i in range(K):
            back = K - 1 - i            # tap i meets the row `back` before
            rows = jnp.concatenate(
                [jnp.zeros((back, own.shape[1])), own[:T - back]], 0)
            ok = pos >= back
            if fault == "tail_cut":
                ok = ok & jnp.where(pos >= first_step, back == 0,
                                    (pos - back) // edge == pos // edge)
            conv = conv + jnp.where(ok[:, None], rows, 0.0) * w["k_conv"][i]
        q, k, v = (a.reshape(T, H, D) for a in jnp.split(_silu(conv), 3, -1))
        q, k = l2(q) * D ** -0.5, l2(k)
        f = (_mm(h, w["k_f"], keep=True) + w["k_bf"]).reshape(T, H, D)
        g = lb * jax.nn.sigmoid(f * jnp.exp(w["k_A"])[:, None])
        if fault == "head_decay":
            g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
        beta = jax.nn.sigmoid(_mm(h, w["k_beta"], keep=True))
        reset = jnp.zeros((T,), bool)
        if fault == "state_cut":
            reset = (pos % edge == 0) & (pos > 0)
        # a pad position (g 0, beta 0) leaves the state as it is
        live = pos < n
        g = jnp.where(live[:, None, None], g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)
        s0 = jnp.zeros((H, D, D))
        if fault == "stale_state":
            # what another tenant left: this layer over the same n tokens'
            # inputs, the last first
            back = jnp.where(live, n - 1 - pos, pos)
            s0 = recurrence(q[back], k[back], v[back], g[back], beta[back],
                            s0, reset)[1]
        o, s = recurrence(q, k, v, g, beta, s0, reset)
        o = _rmsnorm(o, w["k_onorm"], eps)
        gate = jax.nn.sigmoid(_mm(h, w["k_g"]))
        return act(x + _mm(act(o.reshape(T, H * D) * gate), w["k_o"])), s

    def causal(T, q0, n):
        return jnp.arange(T)[None, :] <= (q0 + jnp.arange(n))[:, None]

    def attend(q_nope, q_rope, k_nope, kr, v):
        """Some heads' queries (T, g, .) over their keys and values (T, g,
        .) and the one shared rotated key (T, rope) -> (T, g, v)."""
        T = kr.shape[0]

        def block(q0):
            qn = jax.lax.dynamic_slice_in_dim(q_nope, q0, q_block)
            qr = jax.lax.dynamic_slice_in_dim(q_rope, q0, q_block)
            s = (jnp.einsum("bhd,shd->hbs", qn, k_nope, precision="highest")
                 + jnp.einsum("bhd,sd->hbs", qr, kr, precision="highest")) \
                / math.sqrt(dn + dr)
            s = jnp.where(causal(T, q0, q_block)[None], s, -1e30)
            p = jax.nn.softmax(s, -1)
            return jnp.einsum("hbs,shd->bhd", p, v, precision="highest")

        o = jax.lax.map(block, jnp.arange(0, T, q_block))
        return o.reshape(T, -1, dv)

    @jax.jit
    def mla(x, w):
        """The heads go through in groups of `HEAD_BLOCK`, one after the
        other, so that the float32 queries, keys and values of all of them
        never exist at once."""
        w = _f32(w)
        T = x.shape[0]
        pos = jnp.arange(T)
        g = min(HEAD_BLOCK, H)
        h = act(_rmsnorm(x, w["ln1_w"], eps))
        ckr = _mm(h, w["m_wkv_a"])
        c = cached(act(_rmsnorm(ckr[:, :kvr], w["m_kv_norm"], eps)))
        kr = cached(act(rope(ckr[:, kvr:], pos, theta)))

        def heads(ws):
            wq_g, wkv_g = ws          # (d, g (dn+dr)), (kvr, g (dn+dv))
            q = _mm(h, wq_g).reshape(T, g, dn + dr)
            q_nope, q_rope = q[..., :dn], act(rope(q[..., dn:], pos, theta))
            if low:
                q_nope = _fake_int8(q_nope, -1)
                q_rope = _fake_int8(q_rope, -1)
            kv = _mm(c, wkv_g).reshape(T, g, dn + dv)
            return attend(q_nope, q_rope, kv[..., :dn], kr, kv[..., dn:])

        by_group = lambda a: jnp.moveaxis(  # noqa: E731
            a.reshape(a.shape[0], H // g, -1), 1, 0)
        o = jax.lax.map(heads, (by_group(w["m_wq"]), by_group(w["m_wkv_b"])))
        o = jnp.moveaxis(o, 0, 1).reshape(T, H, dv)
        gate = jax.nn.sigmoid(_mm(h, w["m_wa"], keep=True))
        o = act(o * gate[..., None]).reshape(T, H * dv)
        return act(x + _mm(o, w["m_wo"]))

    def gated(h, gate_up, down):
        gu = _mm(h, gate_up)
        F = gu.shape[-1] // 2
        return _mm(act(_silu(gu[:, :F]) * gu[:, F:]), down)

    @jax.jit
    def dense_ffn(x, w):
        w = _f32(w)
        h = act(_rmsnorm(x, w["ln2_w"], eps))
        return act(x + gated(h, w["d_gate_up"], w["d_down"]))

    @jax.jit
    def route(x, w):
        """-> (h, gates (T, held) float32, shared expert's term)."""
        w = _f32(w)
        h = act(_rmsnorm(x, w["ln2_w"], eps))
        sig = jax.nn.sigmoid(_mm(h, w["r_w"], keep=True))
        T = h.shape[0]
        biased = sig + w["r_b"]
        if n_group > 1 and fault != "no_group_limit":
            grouped = biased.reshape(T, n_group, -1)
            score = jnp.sum(jax.lax.top_k(grouped, 2)[0], -1)
            _, best = jax.lax.top_k(score, topk_group)
            keep = jnp.zeros((T, n_group), bool).at[
                jnp.arange(T)[:, None], best].set(True)
            biased = jnp.where(keep[..., None], grouped, -jnp.inf).reshape(
                T, n_exp)
        if fault == "first_experts":
            idx = jnp.broadcast_to(first + jnp.arange(per_tok), (T, per_tok))
        else:
            _, idx = jax.lax.top_k(biased, per_tok)
        chosen = jnp.zeros((T, n_exp), bool).at[
            jnp.arange(T)[:, None], idx].set(True)
        g = jnp.where(chosen, sig, 0.0)
        g = m["routed_scaling_factor"] * g / jnp.sum(g, -1, keepdims=True)
        return h, g[:, first:first + held], gated(h, w["s_gate_up"],
                                                  w["s_down"])

    @jax.jit
    def expert(y, h, gate, gate_up, down):
        return y + gate[:, None] * gated(h, gate_up.astype(jnp.float32),
                                         down.astype(jnp.float32))

    def sparse_ffn(x, params, l, i):
        h, g, y = route(x, {"ln2_w": params["ln2_w"][l],
                            **{n: params[n][i] for n in
                               ("r_w", "r_b", "s_gate_up", "s_down")}})
        for e in range(held):
            y = expert(y, h, g[:, e], params["e_gate_up"][i, e],
                       params["e_down"][i, e])
        return act(x + act(y))

    prefixes = {"kda": "k_", "mla": "m_"}

    def hidden(params, tokens, n, first_step):
        x = act(params["emb"][tokens].astype(jnp.float32))
        states = []
        for l, (mi, fi) in enumerate(slots):
            kind = m["mixer_types"][l]
            w = {name: a[mi] for name, a in params.items()
                 if name.startswith(prefixes[kind])}
            w["ln1_w"] = params["ln1_w"][l]
            if kind == "kda":
                x, s = kda(x, w, n, first_step)
                states.append(s)
            else:
                x = mla(x, w)
            if m["mlp_types"][l] == "dense":
                x = dense_ffn(x, {"ln2_w": params["ln2_w"][l],
                                  "d_gate_up": params["d_gate_up"][fi],
                                  "d_down": params["d_down"][fi]})
            else:
                x = sparse_ffn(x, params, l, fi)
        return act(_rmsnorm(x, params["lnf_w"].astype(jnp.float32), eps)), \
            states

    @jax.jit
    def head(params, rows):
        return _mm(rows, params["head"].astype(jnp.float32), keep=True)

    return hidden, head, {"kda": kda, "mla": mla}


def _padded(prompt, served, pad_to):
    import numpy as np
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    if seq.size > pad_to:
        raise ValueError(f"sequence of {seq.size} tokens over {pad_to}")
    padded = np.zeros((pad_to,), np.int32)
    padded[:seq.size] = seq
    return padded, seq.size, slice(prompt.size - 1,
                                   prompt.size - 1 + served.size)


def served_rows_and_states(forward, params, prompt, served, pad_to):
    """The final-norm activations at the positions that produced the
    served tokens, and the KDA layers' states after the last of them: one
    pass over prompt + served[:-1] (what a program has been fed when it
    has served `served`), padded to `pad_to` positions (a multiple of the
    forward's `q_block`) with token 0 (causal, so never read)."""
    import jax.numpy as jnp
    padded, n, at = _padded(prompt, served, pad_to)
    rows, states = forward[0](params, jnp.asarray(padded), n, at.start + 1)
    return rows[at], states


def state_gaps(got, want):
    """[|got_i - want_i| / |want_i|] (Frobenius norms over a whole layer's
    state, float64) of the KDA layers' states: how far the states that a
    program (or a control) holds after a request lie from the float32
    recurrence's."""
    import numpy as np
    return [float(np.linalg.norm(np.asarray(a, np.float64)
                                 - np.asarray(b, np.float64))
                  / np.linalg.norm(np.asarray(b, np.float64)))
            for a, b in zip(got, want)]
