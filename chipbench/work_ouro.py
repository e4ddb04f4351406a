"""Operations and bytes that the WORK of the looped decoder needs, from
shapes and lengths alone (`work.py`'s rules: nothing here looks at how the
program does it; a multiply-add counts 2). The loop is the model's own
work: every position goes through `ut_steps` passes of the `layers`-deep
stack, each pass with its own keys and values, so a position's matmuls and
its attention count `ut_steps` times and the head once."""
from __future__ import annotations


def matmul_params(m):
    """Weights one position passes through in ONE pass of the stack (no
    embedding, no head): q, k, v, o and the gated MLP's three, a layer."""
    d, HD, F = m["embed"], m["heads"] * m["head_dim"], m["mlp_hidden"]
    return m["layers"] * (4 * d * HD + 3 * d * F)


def param_count(m):
    """What `weights_ouro.param_count` counts leaf by leaf: the layers with
    their four norms, embedding and head, the final norm and the gate."""
    d = m["embed"]
    return (matmul_params(m) + m["layers"] * 4 * d + 2 * m["vocab"] * d
            + 2 * d + 1)


def planes(m):
    """(pass, layer) pairs: each keeps a K and a V of its own."""
    return m["ut_steps"] * m["layers"]


def position_cache_bytes(m, itemsize=2):
    """One cached position: K and V, `heads * head_dim` wide, a plane."""
    return planes(m) * 2 * m["heads"] * m["head_dim"] * itemsize


def attention_flops_per_key(m):
    """One query position over one key in one plane, all heads: a D-wide
    score and a D-wide value a head."""
    return 4 * m["heads"] * m["head_dim"]


def request_flops(m, prompt, out):
    """A whole request: `prompt` tokens prefilled, `out` served. Positions
    0 .. prompt+out-2 go through every pass of the stack and position t
    meets its t + 1 keys in every plane; the head runs once a served
    token, on the last pass's stream."""
    n = prompt + out - 1
    keys = n * (n + 1) // 2
    return (m["ut_steps"] * n * 2 * matmul_params(m)
            + planes(m) * attention_flops_per_key(m) * keys
            + out * 2 * m["vocab"] * m["embed"])


def loop_read_interval_work(m, requests, t_a, t_b, itemsize=2):
    """(flops, bytes) that the decode steps' paged read owes the interval
    [t_a, t_b), from each request's own timeline and nothing the program
    counts (`work_sambay.shared_attn_interval_work`'s method): `requests`
    is [(prompt, out, t_first, t_done)]. Token j (1 <= j < out) comes from
    a decode step that reads the prompt + j live positions of every one of
    the `ut_steps * layers` planes, K and V, and is taken to come at
    t_first + j (t_done - t_first) / (out - 1). The positions are the
    work's own, not rounded up to a block of the kernel's; the prompt's
    own read is the dense prefill's and is not this read."""
    import numpy as np
    seen = 0
    for prompt, out, t_first, t_done in requests:
        if out > 1:
            j = np.arange(1, out)
            t_j = t_first + j * ((t_done - t_first) / (out - 1))
            inside = (t_j >= t_a) & (t_j < t_b)
            seen += int(inside.sum()) * prompt + int(j[inside].sum())
    return (float(planes(m) * attention_flops_per_key(m) * seen),
            float(seen * position_cache_bytes(m, itemsize)))
