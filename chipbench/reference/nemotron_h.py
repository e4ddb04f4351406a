"""Plain reference of the served NVIDIA-Nemotron-3-Nano-30B-A3B share
(`configs/nemotron3nano_serve.json`: Mamba-2 mixers, sigmoid-routed relu^2
experts of which this chip holds `held_count` and a shared one, attention
with no positional encoding, one layer of one kind each): the whole causal
forward pass over a prompt and the tokens served after it, in float32 with
`highest` matmul precision. No cache, no batching, no kernels, no chunked
form: the Mamba-2 state goes through the recurrence one position at a time
(`lax.scan`), attention is a masked softmax over all positions in blocks of
`Q_BLOCK` queries, the held experts' share is computed densely (every token
through each held expert, times its gate or 0). Imports nothing of the
program.

For l = 0 .. L-1 (RMSNorm eps `norm_eps`, no biases but the conv's; h the
normed input), by `pattern[l]`:
  x <- x + F_l(RMSNorm(x));   logits = RMSNorm(x) W_head, untied.

M (H heads of P, G groups, state N, conv K, d_inner = H P):
  [z | xBC | dt~] = h W_in; xBC <- SiLU(b + sum_{i<K} w_i * xBC_{t-K+1+i})
  (zeros before the sequence); [x | B | C] = xBC, x (H, P), B, C (G, N);
  dt = softplus(dt~ + dt_bias) a head, A_h = -exp(A_log_h); head h reads
  group h // (H / G);
  S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t,  S_{-1} = 0 (P, N);
  y_t = S_t C_t + D_h x_t;
  F = W_out (w * RMSNorm_g(y * silu(z))), the norm over each of G groups
  of d_inner / G values (after the gate).
E: sigma = sigmoid(h W_r); chosen = the `experts_per_token` largest of
  sigma + b; g_i = scale sigma_i / sum_chosen sigma;
  F = sum_{i held} g_i [i chosen] W_down,i relu(W_up,i h)^2 (`e_up` holds
      W_up^T, (F, d), an expert)
      + W_down,s relu(W_up,s h)^2,
  held = [held_first, held_first + held_count): the chip's share of the
  published sum, which is what goes on to the next layer.
*: q, k, v = h W_q, h W_k, h W_v (heads over kv_heads of head_dim, query
  head g over KV head g // (heads / kv_heads)); softmax over s <= t of
  q.k / sqrt(head_dim); F = concat(o) W_o. No rotary.

Controls, each the same pass one step below what the configuration
states: `bfloat16` rounds every matmul's operands and result, every norm's
output, the convolution's inputs and the residual stream to bfloat16
(float32 state, dt, softmax, router and logits: what a served bfloat16
model keeps); `bf16_state` is float32 but for the state, rounded to
bfloat16 after every position; `int8` rounds every matmul's two operands to
8-bit codes (symmetric, one scale per row of the left operand, per column
of the right one).

Besides the logits a pass gives every Mamba layer's state after the last
of the sequence's own positions (`served_rows_and_states`): what the
program's cache holds for a request that has just been served.

Planted faults (`FAULTS`). With `edge` the prefill window: `state_cut`
starts the state from zero again at every multiple of `edge` (a state not
carried over a chunk edge); `stale_state` starts it from what another
tenant left (the same layer over the sequence reversed); `tail_cut` gives
the convolution zeros for what lies before a program's own positions:
before every multiple of `edge` inside the prompt, and before every
position from the first decode step on; `group_mod` has head h read group
h % G for h // (H / G); `relu` takes relu for relu^2 in every expert;
`norm_first` normalises y before the gate (RMSNorm_g(y) * silu(z))."""
from __future__ import annotations

import math

# the rounding controls and the small functions are `reference/sambay.py`'s,
# the norm and the two readers of a forward's logits `reference/glm_dsa.py`'s,
# the pass that also gives the states and a state's gap over a whole layer
# `reference/ling_kda.py`'s
from .glm_dsa import _rmsnorm, first_choices, gaps_below_best  # noqa: F401
from .ling_kda import served_rows_and_states  # noqa: F401
from .ling_kda import state_gaps as layer_state_gaps  # noqa: F401
from .sambay import _bf16, _f32, _fake_int8, _matmul, _silu

Q_BLOCK = 256               # queries a block of attention
PRECISIONS = ("float32", "bfloat16", "bf16_state", "int8")
FAULTS = ("state_cut", "stale_state", "tail_cut", "group_mod", "relu",
          "norm_first")
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


def layer_slots(m):
    """[(kind, index among the layers of that kind)] by layer."""
    out, n = [], {k: 0 for k in KINDS.values()}
    for p in m["pattern"]:
        kind = KINDS[p]
        out.append((kind, n[kind]))
        n[kind] += 1
    return out


def make_forward(m, precision="float32", q_block=Q_BLOCK, edge=None):
    """-> (hidden, head, layers): `hidden(params, tokens (T,) int32, n,
    first_step)` gives the final-norm activations (T, d) float32 of a
    sequence of n tokens padded to T (a multiple of `q_block`; the pad is
    after the tokens and causal, and leaves the state as it is) and the
    Mamba layers' states after position n - 1, [(H, P, N) float32] (n also
    tells `stale_state` where the tenant's own tokens end, `first_step`
    tells `tail_cut` the position of the first decode step); `head(params,
    rows (n, d))` the logits (n, vocab) float32; `layers` the jitted layers
    by kind. `precision` is one of `PRECISIONS` or a planted fault of
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
    H, P, G, N = (m["mamba_heads"], m["mamba_head_dim"], m["n_groups"],
                  m["ssm_state"])
    K, Di = m["conv_kernel"], m["mamba_heads"] * m["mamba_head_dim"]
    GN = G * N
    nq, nkv, hd = m["heads"], m["kv_heads"], m["head_dim"]
    n_exp, per_tok = m["routed_experts"], m["experts_per_token"]
    first, held = m["held_first"], m["held_count"]
    eps = m["norm_eps"]
    act = _bf16 if precision == "bfloat16" else (lambda a: a)
    _mm = _matmul(precision)
    slots = layer_slots(m)
    group_of = (jnp.arange(H) % G if fault == "group_mod"
                else jnp.arange(H) // (H // G))

    def recurrence(x, dt, A, B, C, s0, reset):
        """The state through the positions, one at a time. x (T, H, P), dt
        (T, H), B, C (T, H, N) (the head's group), reset (T,) bool -> (S_t
        C_t (T, H, P), the last state)."""
        def step(s, xs):
            x_t, dt_t, B_t, C_t, z_t = xs
            s = jnp.where(z_t, 0.0, s) * jnp.exp(dt_t * A)[:, None, None] \
                + (dt_t[:, None] * x_t)[..., None] * B_t[:, None, :]
            s = state_act(s)
            return s, jnp.sum(s * C_t[:, None, :], -1)

        s, y = jax.lax.scan(step, s0, (x, dt, B, C, reset))
        return y, s

    @jax.jit
    def mamba(x, w, n, first_step):
        w = _f32(w)
        T = x.shape[0]
        pos = jnp.arange(T)
        h = act(_rmsnorm(x, w["norm"], eps))
        zxd = _mm(h, w["m_in"])
        z, xbc, dt = zxd[:, :Di], act(zxd[:, Di:Di + Di + 2 * GN]), \
            zxd[:, Di + Di + 2 * GN:]
        conv = jnp.zeros_like(xbc) + w["m_conv_b"]
        for i in range(K):
            back = K - 1 - i            # tap i meets the row `back` before
            rows = jnp.concatenate([jnp.zeros((back, xbc.shape[1])),
                                    xbc[:T - back]], 0)
            ok = pos >= back
            if fault == "tail_cut":
                ok = ok & jnp.where(pos >= first_step, back == 0,
                                    (pos - back) // edge == pos // edge)
            conv = conv + jnp.where(ok[:, None], rows, 0.0) * w["m_conv"][i]
        xbc = _silu(conv)
        xs = xbc[:, :Di].reshape(T, H, P)
        B = xbc[:, Di:Di + GN].reshape(T, G, N)[:, group_of]
        C = xbc[:, Di + GN:].reshape(T, G, N)[:, group_of]
        dt = jax.nn.softplus(dt + w["m_dt_bias"])
        A = -jnp.exp(w["m_A_log"])
        reset = jnp.zeros((T,), bool)
        if fault == "state_cut":
            reset = (pos % edge == 0) & (pos > 0)
        # a pad position (dt 0) leaves the state as it is
        live = pos < n
        dt = jnp.where(live[:, None], dt, 0.0)
        s0 = jnp.zeros((H, P, N))
        if fault == "stale_state":
            # what another tenant left: this layer over the same n tokens'
            # inputs, the last first
            back = jnp.where(live, n - 1 - pos, pos)
            s0 = recurrence(xs[back], dt[back], A, B[back], C[back], s0,
                            reset)[1]
        y, s = recurrence(xs, dt, A, B, C, s0, reset)
        y = (y + w["m_D"][:, None] * xs).reshape(T, Di)
        gate = _silu(z)
        if fault == "norm_first":
            y = _rmsnorm(y.reshape(T, G, -1), 1.0, eps).reshape(T, Di) * gate
        else:
            y = _rmsnorm((y * gate).reshape(T, G, -1), 1.0, eps).reshape(
                T, Di)
        out = _mm(act(y * w["m_norm"]), w["m_out"])
        return act(x + out), s

    def causal(T, q0, n):
        return jnp.arange(T)[None, :] <= (q0 + jnp.arange(n))[:, None]

    @jax.jit
    def attn(x, w):
        w = _f32(w)
        T = x.shape[0]
        h = act(_rmsnorm(x, w["norm"], eps))
        q = act(_mm(h, w["a_q"])).reshape(T, nkv, nq // nkv, hd)
        k = act(_mm(h, w["a_k"])).reshape(T, nkv, hd)
        v = act(_mm(h, w["a_v"])).reshape(T, nkv, hd)
        if precision == "int8":
            q, k, v = (_fake_int8(a, -1) for a in (q, k, v))

        def block(q0):
            qb = jax.lax.dynamic_slice_in_dim(q, q0, q_block)
            s = jnp.einsum("bkgd,skd->kgbs", qb, k, precision="highest") \
                / math.sqrt(hd)
            s = jnp.where(causal(T, q0, q_block)[None, None], s, -1e30)
            return jnp.einsum("kgbs,skd->bkgd", jax.nn.softmax(s, -1), v,
                              precision="highest")

        o = jax.lax.map(block, jnp.arange(0, T, q_block))
        o = act(o.reshape(T, nq * hd))
        return act(x + _mm(o, w["a_o"]))

    def relu2(a):
        r = jnp.maximum(a, 0.0)
        return r if fault == "relu" else r * r

    @jax.jit
    def route(x, w):
        """-> (h, gates (T, held) float32, shared expert's term)."""
        w = _f32(w)
        h = act(_rmsnorm(x, w["norm"], eps))
        sig = jax.nn.sigmoid(_mm(h, w["r_w"], keep=True))
        T = h.shape[0]
        _, idx = jax.lax.top_k(sig + w["r_b"], per_tok)
        chosen = jnp.zeros((T, n_exp), bool).at[
            jnp.arange(T)[:, None], idx].set(True)
        g = jnp.where(chosen, sig, 0.0)
        g = m["routed_scaling_factor"] * g / jnp.sum(g, -1, keepdims=True)
        shared = _mm(act(relu2(_mm(h, w["s_up"]))), w["s_down"])
        return h, g[:, first:first + held], shared

    @jax.jit
    def expert(y, h, gate, up, down):
        up, down = up.astype(jnp.float32), down.astype(jnp.float32)
        return y + gate[:, None] * _mm(act(relu2(_mm(h, up.T))), down)

    def moe(x, params, l, i):
        h, g, y = route(x, {"norm": params["norm"][l],
                            **{n: params[n][i] for n in
                               ("r_w", "r_b", "s_up", "s_down")}})
        for e in range(held):
            y = expert(y, h, g[:, e], params["e_up"][i, e],
                       params["e_down"][i, e])
        return act(x + act(y))

    prefixes = {"mamba": "m_", "attn": "a_"}

    def hidden(params, tokens, n, first_step):
        x = act(params["emb"][tokens].astype(jnp.float32))
        states = []
        for l, (kind, i) in enumerate(slots):
            if kind == "moe":
                x = moe(x, params, l, i)
                continue
            w = {name: a[i] for name, a in params.items()
                 if name.startswith(prefixes[kind])}
            w["norm"] = params["norm"][l]
            if kind == "mamba":
                x, s = mamba(x, w, n, first_step)
                states.append(s)
            else:
                x = attn(x, w)
        return act(_rmsnorm(x, params["lnf_w"].astype(jnp.float32), eps)), \
            states

    @jax.jit
    def head(params, rows):
        return _mm(rows, params["head"].astype(jnp.float32), keep=True)

    return hidden, head, {"mamba": mamba, "attn": attn, "moe": route}


def state_gaps(got, want):
    """By Mamba layer, the widest head's |got_h - want_h| / |want_h|
    (Frobenius norms over a head's (P, N) state, float64): how far the
    states that a program (or a control) holds after a request lie from
    the float32 recurrence's, in the head where they lie furthest. A
    state's own rounding gathers over the positions a head remembers, so
    it shows in the slow heads; a norm over the whole layer
    (`layer_state_gaps`) is ruled by the heads with the largest states and
    dilutes it (a bfloat16 state reads 1.5-2.5 times the program's over
    the whole layer, 3-10 times in its widest head: CPU, one layer at the
    served widths), where a narrower product feeding the state moves
    every head and shows over the whole layer."""
    import numpy as np
    out = []
    for a, b in zip(got, want):
        a = np.asarray(a, np.float64).reshape(len(a), -1)
        b = np.asarray(b, np.float64).reshape(len(b), -1)
        out.append(float(np.max(np.linalg.norm(a - b, axis=1)
                                / np.linalg.norm(b, axis=1))))
    return out
