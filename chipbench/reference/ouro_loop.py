"""Plain reference of the served looped decoder (Ouro-2.6B, the looped
language model of arXiv:2510.25741): the whole causal forward pass over a
prompt and the tokens served after it, in float32 with `highest` matmul
precision. No cache, no batching, no kernels, no loop in a program: ONE
layer is jitted and called `ut_steps * layers` times from Python, each call
with that layer's bfloat16 weights cast to float32 inside it, so the pass
compiles one body and fits beside the bfloat16 tree. Imports nothing of the
program (its rounding helpers are `reference/sambay.py`'s, its RMSNorm
`reference/glm_dsa.py`'s).

    h^0 = E[token]                              (no learned positions)
    for u = 1 .. U:  h^u = N_f(L_n(.. L_1(h^{u-1})))     the same n layers
    one layer:  x <- x + N2(Attn(N1(x)));  x <- x + N4(MLP(N3(x)))
      Attn: q, k, v = h W_q, h W_k, h W_v, H heads of D; rotary on all D
            values of q and k in half-split pairs (x_i, x_{i+D/2}) at angle
            t * theta^(-2i/D); causal softmax of q.k / sqrt(D); W_o
      MLP:  W_down(silu(h W_gate) * (h W_up))
    exit gate: lambda_u = sigmoid(h^u . w_g + b_g); the exit distribution
      p_u = lambda_u prod_{j<u}(1 - lambda_j) for u < U, the remaining mass
      at u = U; the pass served is the first at which the cumulative mass
      reaches `early_exit_threshold` (at the published 1.0: the last)
    logits = h^served W_head

Controls, each the same pass one step below what the configuration states:
`precision="bfloat16"` rounds every matmul's operands and result, every
norm's output, q, k, v and the stream to bfloat16 (float32 sums, softmax
and logits: what a served bfloat16 model keeps) and says how far bfloat16
alone moves the logits; `precision="int8"` (W8A8) rounds every matmul's two
operands, and q, k and v as they would sit in a cache, to 8-bit codes
(symmetric, one scale per row of the left operand, per column of the right
one, per head and position of q, k and v), as `reference/decoder.py`'s
control does.

Planted faults (`FAULTS`, `fault=`), each what a program that got one thing
of this architecture wrong would compute, for showing that the comparison
of logits can see it:
  first_plane       every pass attends to the keys and values of pass 1
  last_plane        every pass attends to the keys and values of the LAST
                    pass (of a first, sound sweep): the paper's decode-time
                    cache sharing, a different model
  pass_short        U - 1 passes for U
  no_loop_norm      N_f after the last pass only, not between passes
  no_post_norms     N2 and N4 left out (a pre-norm block)
  interleaved_rope  rotary on pairs (x_2i, x_2i+1) for (x_i, x_{i+D/2})
  chunk_blind       a position sees no key before the last multiple of
                    `edge` at or below it: a prefill chunk that does not
                    read what the chunk before it wrote
"""
from __future__ import annotations

import collections
import math

from .glm_dsa import _rmsnorm
from .sambay import _bf16, _f32, _fake_int8, _matmul

HEAD_BLOCK = 16384          # vocabulary columns a head block
PRECISIONS = ("float32", "bfloat16", "int8")
FAULTS = ("first_plane", "last_plane", "pass_short", "no_loop_norm",
          "no_post_norms", "interleaved_rope", "chunk_blind")
LAYER_LEAVES = ("n1", "n2", "n3", "n4", "wq", "wk", "wv", "wo",
                "mlp_gate_up", "mlp_down")

#: `hidden(params, tokens (T,), every_pass=False)` -> h^U (T, d) float32, or
#: [h^1 .. h^U]; `head_block(head columns (d, n), rows (r, d))` -> logits
#: (r, n); `steps`, the passes this forward runs
Forward = collections.namedtuple("Forward", "hidden head_block steps")


def rotary(x, pos, theta, interleaved=False):
    """x (T, H, D) at positions pos (T,): pairs (x_i, x_{i+D/2}), or
    (x_2i, x_2i+1) when `interleaved`, turned by pos * theta^(-2i/D)."""
    import jax.numpy as jnp
    D = x.shape[-1]
    freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos.astype(jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if interleaved:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                         -1).reshape(x.shape)
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def make_forward(m, precision="float32", fault=None, edge=None):
    """The forward pass of the `model` group `m` as a `Forward`."""
    import jax
    import jax.numpy as jnp
    if precision not in PRECISIONS:
        raise ValueError(f"unknown reference precision {precision!r}")
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown planted fault {fault!r}")
    if fault == "chunk_blind" and not edge:
        raise ValueError("chunk_blind needs the chunk's length, edge=")
    L, H, D = m["layers"], m["heads"], m["head_dim"]
    U = m["ut_steps"] - (fault == "pass_short")
    eps, theta = m["norm_eps"], float(m["rope_theta"])
    low = precision == "int8"
    # a bfloat16 pass keeps what leaves a norm and the stream in bfloat16
    act = _bf16 if precision == "bfloat16" else (lambda a: a)
    post = fault != "no_post_norms"

    mm = _matmul(precision)

    def norm(x, w):
        return act(_rmsnorm(x, w, eps))

    def layer(x, w, k_over=None, v_over=None):
        w = _f32(w)
        T = x.shape[0]
        t = jnp.arange(T)
        h = norm(x, w["n1"])
        q, k, v = (act(mm(h, w[n])).reshape(T, H, D)
                   for n in ("wq", "wk", "wv"))
        q, k = (act(rotary(a, t, theta, fault == "interleaved_rope"))
                for a in (q, k))
        if low:
            q, k, v = (_fake_int8(a, -1) for a in (q, k, v))
        own = (k, v)
        if k_over is not None:
            k, v = k_over, v_over
        sco = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") \
            / math.sqrt(D)
        sees = t[:, None] >= t[None, :]
        if fault == "chunk_blind":
            sees = sees & (t[None, :] >= (t[:, None] // edge) * edge)
        p = jax.nn.softmax(jnp.where(sees[None], sco, -jnp.inf), -1)
        att = jnp.einsum("hqk,khd->qhd", p, v, precision="highest")
        o = mm(act(att.reshape(T, H * D)), w["wo"])
        x = act(x + (norm(o, w["n2"]) if post else o))
        gu = mm(norm(x, w["n3"]), w["mlp_gate_up"])
        F = gu.shape[-1] // 2
        y = mm(act(jax.nn.silu(gu[:, :F]) * gu[:, F:]), w["mlp_down"])
        return act(x + (norm(y, w["n4"]) if post else y)), own

    layer = jax.jit(layer)
    loop_norm = jax.jit(lambda x, w: norm(x, w.astype(jnp.float32)))

    def sweep(params, x, planes=None, keep=None):
        """U passes over x -> [h^1 .. h^U]. `planes` {layer: (k, v)} are
        read in place of a pass's own keys and values (by every pass, or
        by the passes after the first when `keep` is "first", which fills
        them from the first); `keep` "last" returns the last pass's."""
        hs, held = [], dict(planes or {})
        for u in range(U):
            for l in range(L):
                w = {n: params[n][l] for n in LAYER_LEAVES}
                # (a plane is held only once its layer has run: the first
                # pass of `keep="first"` reads its own)
                x, own = layer(x, w, *held.get(l, (None, None)))
                if (keep == "first" and u == 0) \
                        or (keep == "last" and u == U - 1):
                    held[l] = own
            if fault != "no_loop_norm" or u == U - 1:
                x = loop_norm(x, params["nf"])
            hs.append(x)
        return hs, held

    def hidden(params, tokens, every_pass=False):
        x = params["emb"][tokens].astype(jnp.float32)
        if fault == "first_plane":
            hs, _ = sweep(params, x, keep="first")
        elif fault == "last_plane":
            _, planes = sweep(params, x, keep="last")
            hs, _ = sweep(params, x, planes)
        else:
            hs, _ = sweep(params, x)
        return hs if every_pass else hs[-1]

    @jax.jit
    def head_block(head_cols, rows):
        return mm(rows, head_cols.astype(jnp.float32), keep=True)

    return Forward(hidden, head_block, U)


def exit_distribution(params, hs):
    """[h^1 .. h^U] -> (U, T): the mass with which a position leaves the
    loop after each pass (reported; the program serves at threshold 1.0)."""
    import jax
    import jax.numpy as jnp
    w = params["gate_w"].astype(jnp.float32)
    b = params["gate_b"].astype(jnp.float32)
    lam = jnp.stack([jax.nn.sigmoid(
        jnp.matmul(h, w, precision="highest") + b[0]) for h in hs])
    stay = jnp.cumprod(1.0 - lam, 0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], 0)
    return jnp.concatenate([(lam * before)[:-1], before[-1:]], 0)


def served_pass(p, threshold):
    """(U, T) exit distribution -> (T,) index of the pass whose logits are
    served: the first at which the cumulative mass reaches `threshold`
    (the last where none does before it)."""
    import jax.numpy as jnp
    hit = jnp.cumsum(p, 0) >= threshold
    hit = hit.at[-1].set(True)
    return jnp.argmax(hit, 0)


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


def _head(forward, params, rows):
    import jax.numpy as jnp
    V = params["head"].shape[1]
    return jnp.concatenate(
        [forward.head_block(params["head"][:, s:s + HEAD_BLOCK], rows)
         for s in range(0, V, HEAD_BLOCK)], -1)


def served_logits(forward, params, prompt, served, pad_to, threshold=1.0):
    """Logits (len(served), vocab) float32, on the device, at the
    positions that produced each served token: one pass over prompt +
    served[:-1], padded to `pad_to` positions with token 0 (causal, so
    never read), the head at those positions only. With `threshold` below
    1.0 each position's row is the pass's that the exit gate serves."""
    import jax.numpy as jnp
    padded, at = _padded(prompt, served, pad_to)
    if threshold >= 1.0:
        rows = forward.hidden(params, jnp.asarray(padded))[at]
    else:
        hs = forward.hidden(params, jnp.asarray(padded), every_pass=True)
        which = served_pass(exit_distribution(params, hs), threshold)[at]
        rows = jnp.take_along_axis(jnp.stack([h[at] for h in hs]),
                                   which[None, :, None], 0)[0]
    return _head(forward, params, rows)


def gaps_below_best(logits, tokens):
    """For each row, how far the token's logit lies below the row's best
    (0 where the token is the reference's own choice). numpy float32."""
    import numpy as np
    import jax.numpy as jnp
    tokens = jnp.asarray(np.asarray(tokens, np.int32))
    picked = jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]
    return np.asarray(logits.max(-1) - picked, np.float32)


def served_gaps(forward, params, prompt, served, pad_to, judge=None):
    """For each served token, how far its logit lies below the row's best
    in this forward's logits. With `judge` (a lower-precision or faulted
    forward over the same sequence), the token judged at each position is
    the one `judge` puts first."""
    import jax.numpy as jnp
    at = served_logits(forward, params, prompt, served, pad_to)
    tokens = served if judge is None else jnp.argmax(
        served_logits(judge, params, prompt, served, pad_to), -1)
    return gaps_below_best(at, tokens)


def logits(forward, params, tokens):
    """All logits (T, vocab) float32 of a short sequence: the tests'
    entry point."""
    import jax.numpy as jnp
    return _head(forward, params,
                 forward.hidden(params, jnp.asarray(tokens, jnp.int32)))
