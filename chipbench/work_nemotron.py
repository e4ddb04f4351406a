"""Operations that the WORK of the state-space, sparse-expert decoder needs
ON THIS CHIP'S SHARE, from shapes and lengths alone (`work.py`'s rules:
nothing here looks at how the program does it; a multiply-add counts 2).
The share: every Mamba-2 mixer and attention layer whole, the experts HELD
here only (a token's `experts_per_token` choices land on a held expert
`held_count / routed_experts` of the time: the expectation under even
routing), the shared expert, the vocabulary's slice.

What the work needs of a Mamba-2 layer at a position is the recurrence
itself: the decay and the rank-one update of the (P, N) state of each head
(3 a state entry) and the read S C (2): 5 H P N, and the convolution's
taps. Of an attention layer: 4 head_dim FLOPs a query head a live key (the
scores and the weighted sum)."""
from __future__ import annotations


def _counts(m):
    p = m["pattern"]
    return p.count("M"), p.count("*"), p.count("E")


def _widths(m):
    d_inner = m["mamba_heads"] * m["mamba_head_dim"]
    conv_dim = d_inner + 2 * m["n_groups"] * m["ssm_state"]
    return d_inner, conv_dim


def matmul_params(m):
    """Weights one position passes through, by piece (no embedding, no
    head). `experts` is the expected share of the routed experts."""
    d = m["embed"]
    d_inner, conv_dim = _widths(m)
    qw = m["heads"] * m["head_dim"]
    kvw = m["kv_heads"] * m["head_dim"]
    return {
        "mamba": d * (d_inner + conv_dim + m["mamba_heads"]) + d_inner * d,
        "attn": 2 * d * qw + 2 * d * kvw,
        "router": d * m["routed_experts"],
        "expert": 2 * d * m["expert_hidden"],
        "experts": 2 * d * m["expert_hidden"] * m["experts_per_token"]
        * m["held_count"] / m["routed_experts"],
        "shared": 2 * d * m["shared_hidden"],
    }


def held_param_count(m):
    """Parameters this chip holds: what `weights_nemotron.param_count`
    counts leaf by leaf (norms, the conv taps and bias, dt_bias, A_log, D
    and the router's bias included)."""
    p = matmul_params(m)
    d, H = m["embed"], m["mamba_heads"]
    d_inner, conv_dim = _widths(m)
    nm, na, ne = _counts(m)
    small_mamba = m["conv_kernel"] * conv_dim + conv_dim + 3 * H + d_inner
    return (nm * (p["mamba"] + small_mamba) + na * p["attn"]
            + ne * (p["router"] + m["routed_experts"]
                    + m["held_count"] * p["expert"] + p["shared"])
            + (nm + na + ne) * d + 2 * m["vocab"] * d + d)


def state_bytes(m):
    """One lane's float32 state in one Mamba layer."""
    return 4 * m["mamba_heads"] * m["mamba_head_dim"] * m["ssm_state"]


def ssm_state_work(m, lane_layer_steps=1, itemsize=2):
    """(flops, bytes) that a decode micro-step's state update owes for
    `lane_layer_steps` (live lane, Mamba layer) pairs: each pair's float32
    state read once and written once, and its conv tail (K-1 rows of xBC
    in the cache's type) read once and written once; 5 H P N FLOPs."""
    _, conv_dim = _widths(m)
    tail = (m["conv_kernel"] - 1) * conv_dim * itemsize
    per = 2 * state_bytes(m) + 2 * tail
    flops = 5 * m["mamba_heads"] * m["mamba_head_dim"] * m["ssm_state"]
    return float(flops * lane_layer_steps), float(per * lane_layer_steps)


def _keys_live(first, n):
    """sum of t + 1 over the n positions t = first .. first+n-1."""
    return n * (2 * first + n + 1) // 2


def position_flops(m):
    """One position through every layer's matmuls, the convolution and the
    recurrence, without attention's reads."""
    p = matmul_params(m)
    _, conv_dim = _widths(m)
    nm, na, ne = _counts(m)
    mamba = 2 * p["mamba"] + 2 * m["conv_kernel"] * conv_dim \
        + 5 * m["mamba_heads"] * m["mamba_head_dim"] * m["ssm_state"]
    return (nm * mamba + na * 2 * p["attn"]
            + ne * 2 * (p["router"] + p["experts"] + p["shared"]))


def request_flops(m, prompt, out):
    """A whole request: `prompt` tokens prefilled, `out` served. Positions
    0 .. prompt+out-2 go through the layers, position t's queries meet its
    t + 1 live keys in every attention layer; the head runs once a served
    token."""
    n = prompt + out - 1
    _, na, _ = _counts(m)
    read = na * 4 * m["heads"] * m["head_dim"] * _keys_live(0, n)
    return n * position_flops(m) + read + out * 2 * m["vocab"] * m["embed"]


def attn_read_interval_work(m, requests, t_a, t_b, window, itemsize=2):
    """(flops, bytes) that attention's reads of the cached K and V owe the
    interval [t_a, t_b), from each request's own timeline and nothing the
    program counts (`work_sambay.shared_attn_interval_work`'s method):
    `requests` is [(prompt, out, t_admit, t_first, t_done)]. Token j
    (1 <= j < out) comes from a decode step whose query meets the prompt +
    j live keys, taken to come at t_first + j (t_done - t_first) /
    (out - 1). A prompt is prefilled `window` positions at a time: the
    first window's attention is its own product and not a read of the
    cache; window c >= 1 of n, positions [c W, e_c), reads the K and V of
    positions [0, e_c) once and meets t + 1 keys a query, taken to come at
    t_admit + c (t_first - t_admit) / (n - 1). Every attention layer reads
    a position's K and V, `kv_heads * head_dim` wide each; a query head
    spends 4 head_dim FLOPs a key."""
    import numpy as np
    keys = read = 0
    for prompt, out, t_admit, t_first, t_done in requests:
        n = -(-prompt // window)
        for c in range(1, n):
            if t_a <= t_admit + c * (t_first - t_admit) / (n - 1) < t_b:
                end = min((c + 1) * window, prompt)
                read += end
                keys += _keys_live(c * window, end - c * window)
        if out > 1:
            j = np.arange(1, out)
            t_j = t_first + j * ((t_done - t_first) / (out - 1))
            inside = (t_j >= t_a) & (t_j < t_b)
            seen = int(inside.sum()) * prompt + int(j[inside].sum())
            keys += seen
            read += seen
    _, na, _ = _counts(m)
    kvw = m["kv_heads"] * m["head_dim"]
    return (float(na * 4 * m["heads"] * m["head_dim"] * keys),
            float(na * 2 * kvw * itemsize * read))

