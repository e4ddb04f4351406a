"""Operations that the WORK of the linear-attention, latent-attention,
sparse-expert decoder needs ON THIS CHIP'S SHARE, from shapes and lengths
alone (`work.py`'s rules: nothing here looks at how the program does it; a
multiply-add counts 2). The share: every layer's mixer whole, the experts
HELD here only (a token's `experts_per_token` choices land on a held expert
`held_count / routed_experts` of the time: the expectation under even
routing, which the group limit does not change), the shared expert, the
vocabulary's slice.

What the work needs of a KDA layer is the recurrence itself, a position:
the decay (1 a state entry), S^T k and S^T q (2 each), the rank-one update
(2): 7 H d_k d_v, and the convolution's taps. Of the MLA layer the cheaper
form at each position: a prompt position rebuilds its own K and V once and
meets per-head keys; a served position absorbs W_kv_b and meets the latent
itself. Both read all t + 1 live positions."""
from __future__ import annotations


def _counts(m):
    mix, mlp = m["mixer_types"], m["mlp_types"]
    return (mix.count("kda"), mix.count("mla"), mlp.count("dense"),
            mlp.count("sparse"))


def matmul_params(m):
    """Weights one position passes through, by piece (no embedding, no
    head). `experts` is the expected share of the routed experts."""
    d, H = m["embed"], m["heads"]
    HD = H * m["head_dim"]
    kvr, dn, dr, dv = (m["kv_lora_rank"], m["qk_nope_head_dim"],
                       m["qk_rope_head_dim"], m["v_head_dim"])
    Fe = m["expert_hidden"]
    return {
        "kda": 6 * d * HD + d * H,      # q, k, v, f, g, o; beta
        "mla": d * H * (dn + dr) + d * (kvr + dr) + d * H + H * dv * d,
        "kv_b": kvr * H * (dn + dv),
        "dense": 3 * d * m["mlp_hidden"],
        "router": d * m["routed_experts"],
        "expert": 3 * d * Fe,
        "experts": 3 * d * Fe * m["experts_per_token"] * m["held_count"]
        / m["routed_experts"],
    }


def held_param_count(m):
    """Parameters this chip holds: what `weights_ling.param_count` counts
    leaf by leaf (norms, the conv taps, the decay's A and b_f and the
    router's bias included)."""
    p = matmul_params(m)
    d, H, D = m["embed"], m["heads"], m["head_dim"]
    nk, nm, nd, ns = _counts(m)
    small_kda = m["conv_kernel"] * 3 * H * D + H * D + H + D
    norms = (nk + nm) * 2 * d + d + nm * m["kv_lora_rank"]
    return (nk * (p["kda"] + small_kda) + nm * (p["mla"] + p["kv_b"])
            + nd * p["dense"]
            + ns * (p["router"] + m["routed_experts"]
                    + (m["held_count"] + 1) * p["expert"])
            + 2 * m["vocab"] * d + norms)


def kda_state_bytes(m, lane_layer_steps=1):
    """Bytes a decode micro-step has to move for `lane_layer_steps` (lane,
    KDA layer) pairs: each pair's float32 state read once and written
    once."""
    return 2 * 4 * m["heads"] * m["head_dim"] ** 2 * lane_layer_steps


def latent_read_interval_work(m, requests, t_a, t_b, itemsize=2):
    """(flops, bytes) that the decode steps' read of the latent cache owes
    the interval [t_a, t_b), from each request's own timeline and nothing
    the program counts (`work_sambay.shared_attn_interval_work`'s method):
    `requests` is [(prompt, out, t_first, t_done)]. Token j (1 <= j < out)
    comes from a decode step that reads the prompt + j live positions of
    each MLA layer's leaf, `kv_lora_rank + qk_rope_head_dim` values a
    position (what is stored beyond them to whole tiles is no work), and
    meets them in the latent space, key and value; it is taken to come at
    t_first + j (t_done - t_first) / (out - 1). The prompt's own read
    rebuilds K and V in the prefill programs and is not this read."""
    import numpy as np
    seen = 0
    for prompt, out, t_first, t_done in requests:
        if out > 1:
            j = np.arange(1, out)
            t_j = t_first + j * ((t_done - t_first) / (out - 1))
            inside = (t_j >= t_a) & (t_j < t_b)
            seen += int(inside.sum()) * prompt + int(j[inside].sum())
    nm = _counts(m)[1]
    kvr, dr = m["kv_lora_rank"], m["qk_rope_head_dim"]
    return (float(nm * 2 * m["heads"] * (2 * kvr + dr) * seen),
            float(nm * seen * (kvr + dr) * itemsize))


def _keys_live(first, n):
    """sum of t + 1 over the n positions t = first .. first+n-1."""
    return n * (2 * first + n + 1) // 2


def position_flops(m):
    """One position through every layer's matmuls, the convolution and the
    recurrence, without attention's reads, K/V rebuilding or absorbing."""
    p = matmul_params(m)
    H, D = m["heads"], m["head_dim"]
    nk, nm, nd, ns = _counts(m)
    kda = 2 * p["kda"] + 2 * m["conv_kernel"] * 3 * H * D + 7 * H * D * D
    return (nk * kda + nm * 2 * p["mla"] + nd * 2 * p["dense"]
            + ns * 2 * (p["router"] + p["expert"] + p["experts"]))


def request_flops(m, prompt, out):
    """A whole request: `prompt` tokens prefilled, `out` served. Positions
    0 .. prompt+out-2 go through the layers; the head runs once a served
    token."""
    H, kvr, dn, dr, dv = (m["heads"], m["kv_lora_rank"],
                          m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                          m["v_head_dim"])
    nm = _counts(m)[1]
    n = prompt + out - 1
    served = out - 1                        # positions prompt .. n-1
    rebuilt = 2 * H * (dn + dr + dv)        # per key, per-head K and V
    absorbed = 2 * H * (2 * kvr + dr)       # per key, in the latent space
    read = nm * (prompt * 2 * matmul_params(m)["kv_b"]
                 + rebuilt * _keys_live(0, prompt)
                 + served * 2 * H * (dn * kvr + kvr * dv)
                 + absorbed * _keys_live(prompt, served))
    return n * position_flops(m) + read + out * 2 * m["vocab"] * m["embed"]
