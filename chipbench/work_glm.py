"""Operations that the WORK of the latent-attention, sparse-attention,
sparse-expert decoder needs ON THIS CHIP'S SHARE, from shapes and lengths
alone (`work.py`'s rules: nothing here looks at how the program does it; a
multiply-add counts 2). The share: every layer's attention whole, the
experts HELD here only (a token's `experts_per_token` choices land on a
held expert `held_count / routed_experts` of the time: the expectation
under even routing, which the router's bias skews a little either way), the
shared expert, the vocabulary's slice.

What the work needs of the attention is the cheaper of the two forms at
each position: a prompt position rebuilds its own K and V once (c Wkv_b)
and meets per-head keys; a served position absorbs Wkv_b into its query and
output and meets the latent itself. Both read min(t + 1, index_topk)
positions; a `full` indexer scores all t + 1."""
from __future__ import annotations


def _sizes(m):
    return (m["embed"], m["heads"], m["q_lora_rank"], m["kv_lora_rank"],
            m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"])


def matmul_params(m):
    """Weights one position passes through, by piece (no embedding, no
    head). `experts` is the expected share of the routed experts."""
    d, H, qr, kvr, dn, dr, dv = _sizes(m)
    J, DI = m["index_heads"], m["index_head_dim"]
    Fe = m["expert_hidden"]
    return {
        "attn": d * qr + qr * H * (dn + dr) + d * (kvr + dr) + H * dv * d,
        "kv_b": kvr * H * (dn + dv),
        "indexer": qr * J * DI + d * DI + d * J,
        "dense": 3 * d * m["mlp_hidden"],
        "router": d * m["routed_experts"],
        "expert": 3 * d * Fe,
        "experts": 3 * d * Fe * m["experts_per_token"] * m["held_count"]
        / m["routed_experts"],
    }


def held_param_count(m):
    """Parameters this chip holds: what `weights_glm.glm_param_count`
    counts leaf by leaf (norms and the router's bias included)."""
    p = matmul_params(m)
    d = m["embed"]
    L = len(m["indexer_types"])
    nf = m["indexer_types"].count("full")
    nd = m["mlp_types"].count("dense")
    ns = L - nd
    norms = L * (2 * d + m["q_lora_rank"] + m["kv_lora_rank"]) + d \
        + nf * 2 * m["index_head_dim"]
    return (L * (p["attn"] + p["kv_b"]) + nf * p["indexer"] + nd * p["dense"]
            + ns * (p["router"] + m["routed_experts"]
                    + (m["held_count"] + 1) * p["expert"])
            + 2 * m["vocab"] * d + norms)


def _keys_read(first, n, k):
    """sum of min(t + 1, k) over the n positions t = first .. first+n-1."""
    below = max(0, min(first + n, k) - first)   # positions with t + 1 <= k
    return below * (2 * first + below + 1) // 2 + (n - below) * k


def _keys_live(first, n):
    """sum of t + 1 over the same positions."""
    return n * (2 * first + n + 1) // 2


def position_flops(m):
    """One position through every layer's matmuls, without attention's
    reads, the indexer's scoring, K/V rebuilding or absorbing."""
    p = matmul_params(m)
    L = len(m["indexer_types"])
    nf = m["indexer_types"].count("full")
    nd = m["mlp_types"].count("dense")
    return 2 * (L * p["attn"] + nf * p["indexer"] + nd * p["dense"]
                + (L - nd) * (p["router"] + p["expert"] + p["experts"]))


def request_flops(m, prompt, out):
    """A whole request: `prompt` tokens prefilled, `out` served. Positions
    0 .. prompt+out-2 go through the layers; the head runs once a served
    token."""
    d, H, qr, kvr, dn, dr, dv = _sizes(m)
    L = len(m["indexer_types"])
    nf = m["indexer_types"].count("full")
    k = m["index_topk"]
    n = prompt + out - 1
    served = out - 1                        # positions prompt .. n-1
    rebuilt = 2 * H * (dn + dr + dv)        # per key, per-head K and V
    absorbed = 2 * H * (2 * kvr + dr)       # per key, in the latent space
    read = L * (prompt * 2 * matmul_params(m)["kv_b"]
                + rebuilt * _keys_read(0, prompt, k)
                + served * 2 * H * (dn * kvr + kvr * dv)
                + absorbed * _keys_read(prompt, served, k))
    index = nf * 2 * m["index_heads"] * m["index_head_dim"] \
        * _keys_live(0, n)
    return n * position_flops(m) + read + index + out * 2 * m["vocab"] * d
