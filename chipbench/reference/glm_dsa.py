"""Plain reference of the served GLM-5.2 cut (`configs/glm52_serve.json`:
multi-head latent attention, learned sparse attention with IndexShare,
sigmoid-routed experts of which this chip holds `held_count`, one shared
expert): the whole causal forward pass over a prompt and the tokens served
after it, in float32 with `highest` matmul precision. No cache, no
batching, no kernels; attention is a masked einsum over all positions in
blocks of `Q_BLOCK` queries, the choice of positions a plain `lax.top_k`
over each query's index scores. Imports nothing of the program.

For l = 0 .. L-1 (RMSNorm eps `norm_eps`, no biases; h the normed input):
  x <- x + MLA_l(RMSNorm(x));  x <- x + FFN_l(RMSNorm(x))
logits = RMSNorm(x) W_head, untied from the embedding.

Rotary R_t: on 64 values as 32 interleaved pairs (x_2i, x_2i+1), angle
t * theta^(-2i/64).

MLA:  cq = RMSNorm(h Wq_a); q = cq Wq_b -> H heads of [q_nope | q_rope],
  q_rope <- R_t q_rope; [c | kr] = h Wkv_a, c <- RMSNorm(c), kr <- R_t kr
  (one key head for all H); [k_nope_h | v_h] = c Wkv_b per head;
  s_{t,s,h} = (q_nope.k_nope_{s,h} + q_rope.kr_s) / sqrt(nope + rope);
  p = softmax over s in S_t; o_h = sum p v_{s,h}; out = concat_h(o_h) Wo.

Indexer (a `full` layer): qI = cq WqI -> J heads of D_I, rotary on the
  first 64 of each; kI = LayerNorm(h WkI), rotary on its first 64;
  w = (h Ww) J^-1/2 D_I^-1/2; I_{t,s} = sum_j w_{t,j} ReLU(qI_{t,j}.kI_s);
  S_t = the `index_topk` positions s <= t of largest I_{t,s} (the lower
  position first among equals), all of them while t < index_topk. A
  `shared` layer reads the S_t of the nearest `full` layer below.

Experts (a `sparse` layer): sigma = sigmoid(h Wg); chosen = top-k of
  sigma + b; g_i = scale * sigma_i / sum_chosen sigma;
  y = sum_{i in chosen and held} g_i E_i(h) + E_shared(h),
  E(h) = (silu(h W1) * h W3) W2, held = [held_first, held_first +
  held_count): the chip's share of the published sum, which is what goes
  on to the next layer. A `dense` layer is one E of width `mlp_hidden`.

One layer's bf16 weights are cast to float32 at a time, a held expert at a
time, so that 13k positions at width 6144 fit beside the bf16 tree.

Controls, each the same pass one step below what the configuration
states: `precision="bfloat16"` rounds every matmul's operands and result,
every norm's output, the cached [c | kr] and kI and the residual stream to
bfloat16 (float32 sums, softmax, router and index scores and logits: what
a served bfloat16 model keeps); `precision="int8"` rounds every matmul's
two operands, and [c | kr] and kI as they would sit in a cache, to 8-bit
codes (symmetric, one scale per row of the left operand, per column of the
right one, per position of a cached vector).

Planted faults (`FAULTS`), for showing that a comparison of logits sees
what is new here: `newest_topk` reads the newest `index_topk` positions in
place of the chosen ones; `stale_select` gives the `shared` layers the
positions chosen for another tenant (the same indexer over the sequence
reversed); `no_rope_kr` leaves the rotation off kr; `first_experts` sends
every token to the first `experts_per_token` held experts; `held_norm`
normalises the gates over the chosen experts that are held only."""
from __future__ import annotations

import math

# the rounding controls and the small functions are `reference/sambay.py`'s
from .sambay import _bf16, _f32, _fake_int8, _layernorm, _matmul, _silu

Q_BLOCK = 256               # queries a block of attention and of selection
HEAD_BLOCK = 16             # heads a pass of attention
PRECISIONS = ("float32", "bfloat16", "int8")
FAULTS = ("newest_topk", "stale_select", "no_rope_kr", "first_experts",
          "held_norm")


def _rmsnorm(x, w, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """R_t on the last axis of x (.., T, .., n) as n/2 interleaved pairs;
    `pos` (T,) broadcasts from the axis that x's first axis is."""
    import jax.numpy as jnp
    n = x.shape[-1]
    freq = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]      # (T, n/2)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (n // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(
        x.shape)


def layer_slots(m):
    """[(index among the `full` indexers or None, index among the dense
    or the sparse feed-forward layers)] by layer."""
    out, nf, nd, ns = [], 0, 0, 0
    for it, mt in zip(m["indexer_types"], m["mlp_types"]):
        fi = None
        if it == "full":
            fi, nf = nf, nf + 1
        if mt == "dense":
            mi, nd = nd, nd + 1
        else:
            mi, ns = ns, ns + 1
        out.append((fi, mi))
    return out


def make_forward(m, precision="float32", q_block=Q_BLOCK):
    """-> (hidden, head, selections, mla_layer): `hidden(params, tokens
    (T,) int32)` gives the final-norm activations (T, d) float32,
    `head(params, rows (n, d))` the logits (n, vocab) float32,
    `selections(params, tokens)` every layer's S as (T, T) bool;
    `mla_layer` is one attention layer's jitted function (for a compile
    check of its size). T is a multiple of `q_block`.
    `precision` is one of `PRECISIONS` or a planted fault of `FAULTS`
    (computed in float32)."""
    import jax
    import jax.numpy as jnp
    if precision not in PRECISIONS + FAULTS:
        raise ValueError(f"unknown reference precision {precision!r}")
    fault = precision if precision in FAULTS else None
    if fault:
        precision = "float32"
    H = m["heads"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    kvr = m["kv_lora_rank"]
    J, DI, topk = m["index_heads"], m["index_head_dim"], m["index_topk"]
    n_exp, per_tok = m["routed_experts"], m["experts_per_token"]
    first, held = m["held_first"], m["held_count"]
    theta, eps = float(m["rope_theta"]), m["norm_eps"]
    low = precision == "int8"
    act = _bf16 if precision == "bfloat16" else (lambda a: a)
    _mm = _matmul(precision)
    slots = layer_slots(m)

    def cached(a):
        """What a cache of this precision would hand back."""
        return _fake_int8(a, -1) if low else act(a)

    def causal(T, q0, n):
        return jnp.arange(T)[None, :] <= (q0 + jnp.arange(n))[:, None]

    def select(qI, wI, kI):
        """(T, T) bool: row t holds S_t."""
        T = kI.shape[0]
        k = min(topk, T)

        def block(q0):
            q = jax.lax.dynamic_slice_in_dim(qI, q0, q_block)   # (b, J, DI)
            w = jax.lax.dynamic_slice_in_dim(wI, q0, q_block)   # (b, J)
            live = causal(T, q0, q_block)
            if fault == "newest_topk":
                return live & (jnp.arange(T)[None, :]
                               > (q0 + jnp.arange(q_block))[:, None] - topk)
            s = jnp.einsum("bjd,sd->bjs", q, kI, precision="highest")
            score = jnp.sum(w[..., None] * jnp.maximum(s, 0.0), 1)
            score = jnp.where(live, score, -jnp.inf)
            vals, idx = jax.lax.top_k(score, k)
            rows = jnp.arange(q_block)[:, None]
            return jnp.zeros((q_block, T), bool).at[rows, idx].set(
                vals > -jnp.inf)

        return jax.lax.map(block, jnp.arange(0, T, q_block)).reshape(T, T)

    def attend(q_nope, q_rope, k_nope, kr, v, sel):
        """Some heads' queries (T, g, .) over their keys and values (T, g,
        .) and the one shared rotated key (T, rope) -> (T, g, v)."""
        T = kr.shape[0]

        def block(q0):
            qn = jax.lax.dynamic_slice_in_dim(q_nope, q0, q_block)
            qr = jax.lax.dynamic_slice_in_dim(q_rope, q0, q_block)
            mask = jax.lax.dynamic_slice_in_dim(sel, q0, q_block)
            s = (jnp.einsum("bhd,shd->hbs", qn, k_nope, precision="highest")
                 + jnp.einsum("bhd,sd->hbs", qr, kr, precision="highest")) \
                / math.sqrt(dn + dr)
            s = jnp.where(mask[None], s, -1e30)
            p = jax.nn.softmax(s, -1)
            return jnp.einsum("hbs,shd->bhd", p, v, precision="highest")

        o = jax.lax.map(block, jnp.arange(0, T, q_block))
        return o.reshape(T, -1, dv)

    def index_parts(h, cq, w, pos):
        T = h.shape[0]
        qI = act(_mm(cq, w["i_wq"])).reshape(T, J, DI)
        qI = jnp.concatenate([rope(qI[..., :dr], pos, theta), qI[..., dr:]],
                             -1)
        kI = act(_layernorm(_mm(h, w["i_wk"]), w["i_k_norm_w"],
                            w["i_k_norm_b"], eps))
        kI = jnp.concatenate([rope(kI[:, :dr], pos, theta), kI[:, dr:]], -1)
        wI = _mm(h, w["i_ww"], keep=True) / math.sqrt(J * DI)
        if low:
            qI = _fake_int8(qI, -1)
        return act(qI), wI, cached(kI)

    def mla(x, w, sel, full):
        """-> (x + attention, S as (T, T) bool). The heads go through in
        groups of `HEAD_BLOCK`, one after the other, so that the float32
        queries, keys and values of all of them never exist at once."""
        w = _f32(w)
        T = x.shape[0]
        pos = jnp.arange(T)
        g = min(HEAD_BLOCK, H)
        h = act(_rmsnorm(x, w["ln1_w"], eps))
        cq = act(_rmsnorm(_mm(h, w["wq_a"]), w["q_norm"], eps))
        ckr = _mm(h, w["wkv_a"])
        c = act(_rmsnorm(ckr[:, :kvr], w["kv_norm"], eps))
        kr = ckr[:, kvr:]
        if fault != "no_rope_kr":
            kr = act(rope(kr, pos, theta))
        c, kr = cached(c), cached(kr)
        if full:
            sel = select(*index_parts(h, cq, w, pos))

        def heads(ws):
            wq_g, wkv_g = ws                    # (qr, g (dn+dr)), (kvr, g (dn+dv))
            q = _mm(cq, wq_g).reshape(T, g, dn + dr)
            q_nope, q_rope = q[..., :dn], act(rope(q[..., dn:], pos, theta))
            if low:
                q_nope = _fake_int8(q_nope, -1)
                q_rope = _fake_int8(q_rope, -1)
            kv = _mm(c, wkv_g).reshape(T, g, dn + dv)
            return attend(q_nope, q_rope, kv[..., :dn], kr, kv[..., dn:], sel)

        by_group = lambda a: jnp.moveaxis(  # noqa: E731
            a.reshape(a.shape[0], H // g, -1), 1, 0)
        o = jax.lax.map(heads, (by_group(w["wq_b"]), by_group(w["wkv_b"])))
        o = act(jnp.moveaxis(o, 0, 1).reshape(T, H * dv))
        return act(x + _mm(o, w["wo"])), sel

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
        if fault == "first_experts":
            idx = jnp.broadcast_to(first + jnp.arange(per_tok), (T, per_tok))
        else:
            _, idx = jax.lax.top_k(sig + w["r_b"], per_tok)
        chosen = jnp.zeros((T, n_exp), bool).at[
            jnp.arange(T)[:, None], idx].set(True)
        if fault == "held_norm":
            chosen = chosen & ((jnp.arange(n_exp) >= first)
                               & (jnp.arange(n_exp) < first + held))[None]
        g = jnp.where(chosen, sig, 0.0)
        g = m["routed_scaling_factor"] * g \
            / jnp.maximum(jnp.sum(g, -1, keepdims=True), 1e-30)
        return h, g[:, first:first + held], gated(h, w["s_gate_up"],
                                                  w["s_down"])

    @jax.jit
    def expert(y, h, gate, gate_up, down):
        return y + gate[:, None] * gated(h, gate_up.astype(jnp.float32),
                                         down.astype(jnp.float32))

    def sparse_ffn(x, params, i):
        h, g, y = route(x, {"ln2_w": params["ln2_w_l"],
                            **{n: params[n][i] for n in
                               ("r_w", "r_b", "s_gate_up", "s_down")}})
        for e in range(held):
            y = expert(y, h, g[:, e], params["e_gate_up"][i, e],
                       params["e_down"][i, e])
        return act(x + act(y))

    mla_layer = jax.jit(mla, static_argnums=3)
    attn_names = ("ln1_w", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
                  "wkv_b", "wo")
    index_names = ("i_wq", "i_wk", "i_k_norm_w", "i_k_norm_b", "i_ww")

    def ffn(x, params, l, mi):
        if m["mlp_types"][l] == "dense":
            return dense_ffn(x, {"ln2_w": params["ln2_w"][l],
                                 "d_gate_up": params["d_gate_up"][mi],
                                 "d_down": params["d_down"][mi]})
        return sparse_ffn(x, dict(params, ln2_w_l=params["ln2_w"][l]), mi)

    def run(params, tokens, stale=None):
        """-> (final-norm activations, [S of every layer]); `stale` gives
        the `shared` layers another pass's S in place of their own."""
        x = act(params["emb"][tokens].astype(jnp.float32))
        sel, sels = None, []
        for l, (fi, mi) in enumerate(slots):
            w = {n: params[n][l] for n in attn_names}
            if fi is not None:
                w.update({n: params[n][fi] for n in index_names})
            elif stale is not None:
                sel = stale[l]
            x, sel = mla_layer(x, w, sel, fi is not None)
            sels.append(sel)
            x = ffn(x, params, l, mi)
        return act(_rmsnorm(x, params["lnf_w"].astype(jnp.float32),
                            eps)), sels

    def hidden(params, tokens):
        stale = None
        if fault == "stale_select":
            # the positions that another tenant's tokens chose
            stale = run(params, tokens[::-1])[1]
        return run(params, tokens, stale)[0]

    def selections(params, tokens):
        return run(params, tokens)[1]

    @jax.jit
    def head(params, rows):
        return _mm(rows, params["head"].astype(jnp.float32), keep=True)

    return hidden, head, selections, mla_layer


def _padded(prompt, served, pad_to):
    import numpy as np
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    if seq.size > pad_to:
        raise ValueError(f"sequence of {seq.size} tokens over {pad_to}")
    padded = np.zeros((pad_to,), np.int32)
    padded[:seq.size] = seq
    return padded, slice(prompt.size - 1, prompt.size - 1 + served.size)


def served_rows(forward, params, prompt, served, pad_to):
    """The final-norm activations at the positions that produced the
    served tokens: one pass over prompt + served[:-1], padded to `pad_to`
    positions (a multiple of the forward's `q_block`) with token 0 (causal, so never
    read)."""
    import jax.numpy as jnp
    padded, at = _padded(prompt, served, pad_to)
    return forward[0](params, jnp.asarray(padded))[at]


def served_selections(forward, params, prompt, served, pad_to):
    """S_t of the served positions in every layer: [(n, pad_to) bool]."""
    import jax.numpy as jnp
    padded, at = _padded(prompt, served, pad_to)
    return [s[at] for s in forward[2](params, jnp.asarray(padded))]


def gaps_below_best(forward, params, rows, tokens):
    """For each of `rows`, how far the logit of its token lies below the
    row's best (0 where the token is this forward's own choice)."""
    import numpy as np
    import jax.numpy as jnp
    logits = forward[1](params, rows)
    picked = jnp.take_along_axis(
        logits, jnp.asarray(tokens, jnp.int32)[:, None], axis=1)[:, 0]
    return np.asarray(logits.max(-1) - picked, np.float32)


def first_choices(forward, params, rows):
    """The token this forward puts first at each of `rows`."""
    import jax.numpy as jnp
    return jnp.argmax(forward[1](params, rows), -1).astype(jnp.int32)


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
    """All logits (T, vocab) float32 of a short sequence whose length is
    a multiple of the forward's `q_block`: the tests' entry point."""
    import jax.numpy as jnp
    return forward[1](params, forward[0](params, jnp.asarray(tokens,
                                                             jnp.int32)))
