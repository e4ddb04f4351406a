"""Operations and bytes that the WORK of the SambaY decoder needs, from
shapes and lengths alone (`work.py`'s rules: nothing here looks at how the
program does it; a multiply-add counts 2). What the work needs of a prompt
is the architecture's own linear-time prefill: every prompt position
through layers 0 .. L/2 and layer L/2+1's K/V projection, and only the
positions that produce a served token through the layers above."""
from __future__ import annotations


def _sizes(m):
    d, F = m["embed"], m["mlp_hidden"]
    di, N, K, R = (m["expand"] * m["embed"], m["d_state"], m["d_conv"],
                   m["dt_rank"])
    qw, kvw = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    return d, F, di, N, K, R, qw, kvw


def matmul_params(m):
    """Weights one position passes through, by layer kind (no embedding,
    no head)."""
    d, F, di, N, K, R, qw, kvw = _sizes(m)
    return {"mlp": 3 * d * F,
            "mamba": d * 2 * di + di * (R + 2 * N) + R * di + di * d,
            "attn": d * (qw + 2 * kvw) + qw * d,
            "attn_kv": d * 2 * kvw,
            "cross": 2 * d * qw,
            "gmu": 2 * d * di}


def scan_flops(m):
    """One position through one Mamba mixer beside its matmuls: the
    convolution's K taps, and per state element the decay, the input
    term, the update and the read (6 in all)."""
    _, _, di, N, K, _, _, _ = _sizes(m)
    return 2 * K * di + 6 * di * N


def attention_flops_per_key(m):
    """One query position over one key, all heads: a D-wide score and a
    2D-wide value per query head (differential attention's paired value)."""
    return 6 * m["heads"] * m["head_dim"]


def _lower_position_flops(m):
    """Layers 0 .. L/2 and layer L/2+1's K/V projection, without the
    window layers' attention."""
    p, q = matmul_params(m), m["layers"] // 4
    return 2 * ((q + 1) * (p["mamba"] + p["mlp"]) + q * (p["attn"] + p["mlp"])
                + p["attn_kv"]) + (q + 1) * scan_flops(m)


def _upper_position_flops(m):
    """Layer L/2+1's query, attention output and MLP, and the layers
    above, without the shared read."""
    p, q = matmul_params(m), m["layers"] // 4
    return 2 * (p["attn"] - p["attn_kv"] + p["mlp"]
                + (q - 1) * (p["cross"] + p["gmu"] + 2 * p["mlp"]))


def _window_keys(n, w):
    """Keys that positions 0 .. n-1 see under a window of w."""
    full = min(n, w)
    return full * (full + 1) // 2 + max(0, n - w) * w


def request_flops(m, prompt, out):
    """A whole request: `prompt` tokens prefilled, `out` served. Positions
    0 .. prompt+out-2 go through the lower layers; the last prompt position
    and the out-1 fed-back tokens through the upper layers and the shared
    read; the head runs once a served token."""
    n = prompt + out - 1
    q = m["layers"] // 4
    per_key = attention_flops_per_key(m)
    lower = n * _lower_position_flops(m) \
        + q * per_key * _window_keys(n, m["window"])
    served = out                    # positions prompt-1 .. prompt+out-2
    seen = served * prompt + served * (served - 1) // 2
    upper = served * _upper_position_flops(m) + q * per_key * seen
    return lower + upper + out * 2 * m["vocab"] * m["embed"]


def shared_attn_interval_work(m, requests, t_a, t_b, kv_itemsize=2):
    """(flops, bytes) that the shared-cache read owes the interval
    [t_a, t_b), from each request's own timeline and nothing the program
    counts: `requests` is [(prompt, out, t_first, t_done)], the sizes the
    driver sent and served and the clock times of the first and the last
    token. The first token comes from the prefill's read of `prompt`
    positions; token j (1 <= j < out) comes from a decode step that reads
    prompt + j positions (K and V, `kv_heads * head_dim` wide each, in each
    of the L/4 layers that read layer L/2+1's cache) and is taken to come
    at t_first + j (t_done - t_first) / (out - 1): a request decodes in
    every wave from its first token to its last. Weighing a request's
    whole work by the share of its life inside the interval would
    overstate it where the interval sees young requests: the reads grow
    with a request's age."""
    import numpy as np
    seen = 0
    for prompt, out, t_first, t_done in requests:
        if t_a <= t_first < t_b:
            seen += prompt
        if out > 1:
            j = np.arange(1, out)
            t_j = t_first + j * ((t_done - t_first) / (out - 1))
            inside = (t_j >= t_a) & (t_j < t_b)
            seen += int(inside.sum()) * prompt + int(j[inside].sum())
    q = m["layers"] // 4
    kvw = m["kv_heads"] * m["head_dim"]
    return (float(q * attention_flops_per_key(m) * seen),
            float(q * 2 * seen * kvw * kv_itemsize))
