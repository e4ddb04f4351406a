"""Plain reference of the served SambaY decoder (Phi-4-mini-flash-reasoning:
Mamba-1 + sliding-window attention in the self-decoder, one full-attention
layer whose K/V every later attention layer reads, gated memory units over
the last Mamba layer's memory, differential attention throughout): the
whole causal forward pass over a prompt and the tokens served after it, in
float32 with `highest` matmul precision. No cache, no batching, no kernels;
the selective scan is a plain `lax.scan` over time, attention a masked
einsum over all positions. Imports nothing of the program.

For l = 0 .. L-1:  x <- x + Mix_l(LN(x));  x <- x + MLP_l(LN(x));
logits = LN_f(x) E^T.  x_0 = E[token]: the model has no positional term.
Layer kinds by index (L divisible by 4):
  even l <= L/2      Mamba-1 mixer; layer L/2 also emits the memory m
  odd  l <  L/2      attention, own K/V, window `window`
  l == L/2 + 1       attention, own K/V, full causal
  odd  l >= L/2 + 3  attention, query only, over layer L/2+1's K/V
  even l >= L/2 + 2  gated memory unit over m

One layer's bf16 weights are cast to float32 at a time (one jitted function
a layer kind, called from a Python loop), and the head runs in vocabulary
blocks at the served positions only, so the pass fits beside the bf16 tree.

Controls, each the same pass one step below what the configuration
states: `precision="bfloat16"` rounds every matmul's operands and result,
every norm's output and the residual stream to bfloat16 (float32 sums,
softmax, scan and logits: what a served bfloat16 model keeps), and says
how far bfloat16 alone moves the logits; `precision="bf16_state"` rounds
the selective scan's state to bfloat16 after every step (the
configuration's state is float32); `precision="int8"` rounds every
matmul's two operands, and K and V as they would sit in a cache, to 8-bit
codes (symmetric, one scale per row of the left operand, per column of the
right one, per head and position of K and V), as `reference/decoder.py`'s
control does.

Planted faults of the recurrent state, for showing that a comparison of
logits can see it (`FAULTS`): `state_unchanged` never updates the state
(it stays zero: the scan's term s.C is lost), `state_reset` does not carry
it from one `window`-sized chunk to the next (zeroed at every position
that is a multiple of `window`), `state_stale` starts from what a previous
tenant of the slot left (the state after a first pass over the same
sequence) and not from zero."""
from __future__ import annotations

import math

HEAD_BLOCK = 16384          # vocabulary rows a head block
PRECISIONS = ("float32", "bfloat16", "bf16_state", "int8")
FAULTS = ("state_unchanged", "state_reset", "state_stale")


def layer_kinds(L):
    """[(kind, index among the layers that share that kind's leaves)].
    `swa` and `full` share the attention leaves (`a_*`)."""
    if L % 4:
        raise ValueError(f"the layer pattern needs L divisible by 4, got {L}")
    half = L // 2
    out, count = [], {"mamba": 0, "attn": 0, "cross": 0, "gmu": 0}
    for l in range(L):
        if l % 2 == 0 and l <= half:
            kind, group = "mamba", "mamba"
        elif l % 2 == 1 and l < half:
            kind, group = "swa", "attn"
        elif l == half + 1:
            kind, group = "full", "attn"
        elif l % 2 == 1:
            kind, group = "cross", "cross"
        else:
            kind, group = "gmu", "gmu"
        out.append((kind, count[group]))
        count[group] += 1
    return out


def lambda_init(l):
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def _layernorm(x, w, b, eps):
    import jax.numpy as jnp
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _silu(x):
    import jax
    return x * jax.nn.sigmoid(x)


def _fake_int8(a, axis):
    import jax.numpy as jnp
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(a / scale) * scale


def _bf16(a):
    """Round to bfloat16's 8 bits of mantissa, staying float32. Not a
    cast there and back: on the TPU the compiler keeps excess precision
    and drops such a pair (the bf16-state control then reads exactly 0),
    `reduce_precision` it has to keep."""
    import jax
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _matmul(precision):
    """`mm(x, w, keep=False)`: the product under `precision`; `keep` leaves
    a bfloat16 pass's result in float32 (the head's logits)."""
    import jax.numpy as jnp

    def mm(x, w, keep=False):
        if precision == "int8":
            x, w = _fake_int8(x, -1), _fake_int8(w, 0)
        elif precision == "bfloat16":
            x = _bf16(x)
        out = jnp.matmul(x, w, precision="highest")
        return _bf16(out) if precision == "bfloat16" and not keep else out
    return mm


def _f32(tree):
    import jax.numpy as jnp
    return {k: v.astype(jnp.float32) for k, v in tree.items()}


def _mamba(m, precision, _mm):
    """h (T, d) -> (out (T, d), y (T, d_in)): y is the scan's output before
    the gate (the memory, where the layer is L/2)."""
    import jax
    import jax.numpy as jnp
    N, K, R = m["d_state"], m["d_conv"], m["dt_rank"]
    d_in = m["expand"] * m["embed"]

    def mixer(h, w):
        w = _f32(w)
        T = h.shape[0]
        uz = _mm(h, w["m_in"])
        u, z = uz[:, :d_in], uz[:, d_in:]
        # causal depthwise convolution: tap k meets u_{t-(K-1-k)}
        padded = jnp.concatenate([jnp.zeros((K - 1, d_in)), u], 0)
        conv = sum(padded[k:k + T] * w["m_conv_w"][k] for k in range(K))
        u1 = _silu(conv + w["m_conv_b"])
        rbc = _mm(u1, w["m_x"])
        r, B, C = rbc[:, :R], rbc[:, R:R + N], rbc[:, R + N:]
        delta = jax.nn.softplus(_mm(r, w["m_dt_w"]) + w["m_dt_b"])
        A = -jnp.exp(w["m_A_log"])                       # (N, d_in)
        carried = jnp.ones((T,))
        if precision == "state_reset":
            carried = jnp.where(jnp.arange(T) % m["window"] == 0, 0.0, 1.0)

        def step(s, xs):
            d_t, u_t, B_t, C_t, c_t = xs
            if precision != "state_unchanged":
                s = jnp.exp(d_t[None, :] * A) * (s * c_t) \
                    + (d_t * u_t)[None, :] * B_t[:, None]
            if precision == "bf16_state":
                s = _bf16(s)
            return s, jnp.sum(s * C_t[:, None], 0)

        xs = (delta, u1, B, C, carried)
        s0 = jnp.zeros((N, d_in))
        if precision == "state_stale":
            s0, _ = jax.lax.scan(step, s0, xs)
        _, y = jax.lax.scan(step, s0, xs)
        y = y + w["m_D"] * u1
        return _mm(y * _silu(z), w["m_out"]), y

    return mixer


def _diff_attention(m, window):
    """(q (T, Hq, D), k, v (T, Hkv, D), lambda leaves, layer index) ->
    (T, Hq * D). Query heads (2i, 2i+1) are (q1, q2) of pair i, KV heads
    (2p, 2p+1) give (k1, k2) and v = [v_2p | v_2p+1] of KV pair p = i // 2.
    `window` None is full causal; else position t sees t-window+1 .. t."""
    import jax
    import jax.numpy as jnp
    Hq, Hkv, D = m["heads"], m["kv_heads"], m["head_dim"]
    per = (Hq // 2) // (Hkv // 2)            # query pairs a KV pair

    def attend(q, k, v, w, l):
        T = q.shape[0]
        pos = jnp.arange(T)
        mask = pos[:, None] >= pos[None, :]
        if window is not None:
            mask &= pos[:, None] - pos[None, :] < window
        lam = jnp.exp(jnp.sum(w["lq1"] * w["lk1"])) \
            - jnp.exp(jnp.sum(w["lq2"] * w["lk2"])) + lambda_init(l)

        def softmax_v(qh, kh, vv):
            s = jnp.einsum("qd,kd->qk", qh, kh, precision="highest") \
                / math.sqrt(D)
            p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
            return jnp.einsum("qk,kd->qd", p, vv, precision="highest")

        def kv_pair(args):
            qp, kp, vv = args          # (T, per, 2, D), (T, 2, D), (T, 2D)
            outs = []
            for j in range(per):
                a1 = softmax_v(qp[:, j, 0], kp[:, 0], vv)
                a2 = softmax_v(qp[:, j, 1], kp[:, 1], vv)
                diff = a1 - lam * a2
                rms = jnp.sqrt(jnp.mean(jnp.square(diff), -1, keepdims=True)
                               + m["ln_eps"])
                outs.append((1.0 - lambda_init(l)) * diff / rms * w["sub"])
            return jnp.stack(outs, 1)                    # (T, per, 2D)

        # one KV pair at a time (`lax.map`): the (T, T) score tiles of all
        # forty heads at once would not fit beside the weights
        o = jax.lax.map(kv_pair, (
            q.reshape(T, Hkv // 2, per, 2, D).swapaxes(0, 1),
            k.reshape(T, Hkv // 2, 2, D).swapaxes(0, 1),
            v.reshape(T, Hkv // 2, 2 * D).swapaxes(0, 1)))
        return o.swapaxes(0, 1).reshape(T, Hq * D)

    return attend


def make_forward(m, precision="float32"):
    """-> (hidden, head_block): `hidden(params, tokens (T,) int32)` gives
    the final-norm activations (T, d) float32, `head_block(emb_rows, rows
    (n, d))` the logits (n, len(emb_rows)) of those vocabulary rows."""
    import jax
    import jax.numpy as jnp
    if precision not in PRECISIONS + FAULTS:
        raise ValueError(f"unknown reference precision {precision!r}")
    L, d = m["layers"], m["embed"]
    Hq, Hkv, D = m["heads"], m["kv_heads"], m["head_dim"]
    eps = m["ln_eps"]
    kinds = layer_kinds(L)
    low = precision == "int8"
    # a bfloat16 pass keeps what leaves a norm and the stream in bfloat16
    act = _bf16 if precision == "bfloat16" else (lambda a: a)
    _mm = _matmul(precision)
    mixer = _mamba(m, precision, _mm)
    attend = {"swa": _diff_attention(m, m["window"]),
              "full": _diff_attention(m, None)}
    attend["cross"] = attend["full"]

    def lam_leaves(w, prefix):
        return {n: w[f"{prefix}_{n}"] for n in ("lq1", "lk1", "lq2", "lk2",
                                                "sub")}

    def norm(x, w, which):
        return act(_layernorm(x, w[which + "_w"], w[which + "_b"], eps))

    def mlp(x, w):
        gu = _mm(norm(x, w, "ln2"), w["mlp_gate_up"])
        F = gu.shape[-1] // 2
        return act(x + _mm(_silu(gu[:, :F]) * gu[:, F:], w["mlp_down"]))

    @jax.jit
    def mamba_layer(x, w):
        w = _f32(w)
        out, y = mixer(norm(x, w, "ln1"), w)
        return mlp(act(x + out), w), y

    def attn_layer(kind):
        def layer(x, w, l):
            w = _f32(w)
            T = x.shape[0]
            qkv = act(_mm(norm(x, w, "ln1"), w["a_qkv"]) + w["a_qkv_b"])
            q = qkv[:, :Hq * D].reshape(T, Hq, D)
            k = qkv[:, Hq * D:(Hq + Hkv) * D].reshape(T, Hkv, D)
            v = qkv[:, (Hq + Hkv) * D:].reshape(T, Hkv, D)
            if low:
                q, k, v = (_fake_int8(a, -1) for a in (q, k, v))
            o = attend[kind](q, k, v, lam_leaves(w, "a"), l)
            x = act(x + _mm(o, w["a_o"]) + w["a_o_b"])
            return mlp(x, w), (k, v)
        return jax.jit(layer, static_argnums=2)

    def cross(x, w, k, v, l):
        w = _f32(w)
        T = x.shape[0]
        q = act(_mm(norm(x, w, "ln1"), w["c_q"]) + w["c_q_b"]).reshape(
            T, Hq, D)
        if low:
            q = _fake_int8(q, -1)
        o = attend["cross"](q, k, v, lam_leaves(w, "c"), l)
        return mlp(act(x + _mm(o, w["c_o"]) + w["c_o_b"]), w)

    cross_layer = jax.jit(cross, static_argnums=4)

    @jax.jit
    def gmu_layer(x, w, mem):
        w = _f32(w)
        g = _silu(_mm(norm(x, w, "ln1"), w["g_w1"])) * mem
        return mlp(act(x + _mm(g, w["g_w2"])), w)

    swa_layer, full_layer = attn_layer("swa"), attn_layer("full")
    groups = {"mamba": "m_", "swa": "a_", "full": "a_", "cross": "c_",
              "gmu": "g_"}

    def weights_of(params, l, kind, i):
        w = {n: params[n][l] for n in ("ln1_w", "ln1_b", "ln2_w", "ln2_b",
                                       "mlp_gate_up", "mlp_down")}
        w.update({n: a[i] for n, a in params.items()
                  if n.startswith(groups[kind])})
        return w

    def hidden(params, tokens):
        x = params["emb"][tokens].astype(jnp.float32)
        mem = shared = None
        for l, (kind, i) in enumerate(kinds):
            w = weights_of(params, l, kind, i)
            if kind == "mamba":
                x, y = mamba_layer(x, w)
                if l == L // 2:
                    mem = y
            elif kind == "swa":
                x, _ = swa_layer(x, w, l)
            elif kind == "full":
                x, shared = full_layer(x, w, l)
            elif kind == "cross":
                x = cross_layer(x, w, shared[0], shared[1], l)
            else:
                x = gmu_layer(x, w, mem)
        return act(_layernorm(x, params["lnf_w"].astype(jnp.float32),
                              params["lnf_b"].astype(jnp.float32), eps))

    @jax.jit
    def head_block(emb_rows, rows):
        return _mm(rows, emb_rows.astype(jnp.float32).T, keep=True)

    return hidden, head_block


def served_rows(forward, params, prompt, served, pad_to):
    """The final-norm activations at the positions that produced the
    served tokens: one pass over prompt + served[:-1], padded to `pad_to`
    positions with token 0 (causal, so never read)."""
    import numpy as np
    import jax.numpy as jnp
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    if seq.size > pad_to:
        raise ValueError(f"sequence of {seq.size} tokens over {pad_to}")
    padded = np.zeros((pad_to,), np.int32)
    padded[:seq.size] = seq
    hidden, _ = forward
    return hidden(params, jnp.asarray(padded))[
        prompt.size - 1:prompt.size - 1 + served.size]


def _head_blocks(forward, params, rows):
    _, head_block = forward
    for start in range(0, params["emb"].shape[0], HEAD_BLOCK):
        yield start, head_block(params["emb"][start:start + HEAD_BLOCK], rows)


def gaps_below_best(forward, params, rows, tokens):
    """For each of `rows`, how far the logit of its token lies below the
    row's best (0 where the token is this forward's own choice); the head
    in vocabulary blocks. numpy float32."""
    import numpy as np
    import jax.numpy as jnp
    tokens = jnp.asarray(tokens, jnp.int32)
    best = jnp.full((rows.shape[0],), -jnp.inf, jnp.float32)
    picked = jnp.zeros((rows.shape[0],), jnp.float32)
    for start, logits in _head_blocks(forward, params, rows):
        best = jnp.maximum(best, logits.max(-1))
        inside = (tokens >= start) & (tokens < start + logits.shape[1])
        idx = jnp.clip(tokens - start, 0, logits.shape[1] - 1)
        here = jnp.take_along_axis(logits, idx[:, None], axis=1)[:, 0]
        picked = jnp.where(inside, here, picked)
    return np.asarray(best - picked, np.float32)


def first_choices(forward, params, rows):
    """The token this forward puts first at each of `rows`."""
    import jax.numpy as jnp
    best = jnp.full((rows.shape[0],), -jnp.inf, jnp.float32)
    arg = jnp.zeros((rows.shape[0],), jnp.int32)
    for start, logits in _head_blocks(forward, params, rows):
        top = logits.max(-1)
        arg = jnp.where(top > best,
                        start + jnp.argmax(logits, -1).astype(jnp.int32), arg)
        best = jnp.maximum(best, top)
    return arg


def served_gaps(forward, params, prompt, served, pad_to, judge=None):
    """For each served token, how far its logit lies below the row's best
    in this forward's logits. With `judge` (a lower-precision or faulted
    forward over the same sequence), the token judged at each position is
    the one `judge` puts first."""
    rows = served_rows(forward, params, prompt, served, pad_to)
    tokens = served if judge is None else first_choices(
        judge, params, served_rows(judge, params, prompt, served, pad_to))
    return gaps_below_best(forward, params, rows, tokens)


def logits(forward, params, tokens):
    """All logits (T, vocab) float32 of a short sequence: the tests'
    entry point."""
    import jax.numpy as jnp
    hidden, head_block = forward
    rows = hidden(params, jnp.asarray(tokens, jnp.int32))
    V = params["emb"].shape[0]
    return jnp.concatenate(
        [head_block(params["emb"][s:s + HEAD_BLOCK], rows)
         for s in range(0, V, HEAD_BLOCK)], -1)
