"""A latent-attention, sparse-attention, sparse-expert decoder for
`serve.ContinuousEngine`: multi-head latent attention (MLA) over ONE cached
vector a position, a learned indexer that chooses which cached positions a
query reads (with the choice shared between layers), and a feed-forward
layer of routed experts of which this process holds a contiguous share
(the DeepSeek-V3.2 / GLM `glm_moe_dsa` block).

    x_0 = E[token];  x <- x + MLA_l(RMSNorm(x));  x <- x + FFN_l(RMSNorm(x))
    logits = RMSNorm(x) W_head                  (float32, untied from E)

Per layer `l` the cache holds `lat{l}` (max_len, kv_lora_rank + rope, in
whole lane tiles: `SparseMoEConfig.lat_stored`): the normalised latent c
and the rotated shared key kr of every position, and per
`full`-indexer layer `idx{i}` (max_len, index_head_dim): its index key.
Both are `full` leaves of `serve.KVCachePool` (one position a token, read
through the `[0, cur_len]` mask): the pool needed no new kind.

  MLA      cq = RMSNorm(h Wq_a); q = cq Wq_b -> H x [q_nope | q_rope];
           [c | kr] = h Wkv_a, c <- RMSNorm(c); rotary on q_rope and kr;
           [k_nope_h | v_h] = c Wkv_b. A prefill chunk REBUILDS k_nope and
           v of the cached positions from c (`mla_read_rebuilt`); a decode
           step ABSORBS Wkv_b into the query and the output and reads c
           itself (`mla_read_absorbed`): the same numbers.
  indexer  I_{t,s} = sum_j w_{t,j} ReLU(qI_{t,j} . kI_s) in float32; S_t =
           the `index_topk` positions s <= t of largest I (all while
           t < index_topk; the lower position first among equals). A
           `shared` layer reads the S_t of the nearest `full` layer below.
           A chunk reads its keys densely under the mask that S_t makes
           (`select_mask`: the exact k-th largest by bisection on the
           scores' bits, no sort); a decode step takes `lax.top_k` and
           gathers the chosen positions.
  experts  sigma = sigmoid(h Wg) float32; chosen = top-k of sigma + b;
           g_i = scale sigma_i / sum_chosen sigma; the layer is TOLD which
           experts it holds (`held_first`, `held_count`), routes over all
           of them and returns sum_{chosen and held} g_i E_i(h) +
           E_shared(h): the share of the published sum that one process
           of an expert-parallel deployment computes, with no capacity and
           no dropped token (`routed_experts`: token-expert pairs sorted by
           expert, one `while_loop` over the blocks of rows that exist this
           call, each a matmul with ITS expert's weights; an expert nobody
           chose costs nothing).

`SparseMoEDecoder` builds the engine's three programs (`prefill`,
`chunk_prefill`, `decode`: fixed shapes, donated cache, lanes as data) and
is the third implementer of the engine's model protocol. Each program
returns, last, the counters of `SparseMoEDecoder.counters` (`moe`,
`sparse`), which `ContinuousEngine.stats()` sums.

What a program may assume, and what it sees to:
  * nothing of a claimed slot is read before it is written: every read is
    under `s <= t` of the CURRENT request, and a chunk writes its own
    positions before it reads (tests poison-fill every leaf to show it).
  * the S_t that a `shared` layer reads was made in the same program, for
    the same lanes, by the `full` layer below: it is never cached.
  * router scores, index scores, softmax and logits are float32 whatever
    the weights' type.
"""
from __future__ import annotations

import math

import numpy as _np

from .. import sanitize as _sanitize
from ..serve.batcher import ServeError
from ..serve.kv_pool import CacheLeaf, KVCachePool
from .hybrid_decoder import gated_mlp, layer_norm, silu

__all__ = ["SparseMoEConfig", "SparseMoEDecoder", "init_sparse_moe_params",
           "param_shapes", "draw_leaf", "rope", "select_mask",
           "routed_experts", "mla_read_rebuilt", "mla_read_absorbed"]

#: heads a step of a chunk's attention (the float32 scores of one step are
#: HEAD_GROUP x window x extent) and index heads a step of its scoring
HEAD_GROUP = 4
#: rows a block of the grouped expert matmul at most
EXPERT_BLOCK = 128
#: float32 bytes the per-head index scores of one step may take
SCORE_BYTES = 2 ** 28


def whole_tiles(w):
    """Width of a `lat{l}` leaf's row: the latent's width in whole lane
    tiles of 128. The device tiles a narrower last axis up to that anyway,
    and for a last axis that is NOT whole tiles it stores the array with
    the positions innermost, which every program then undoes and redoes
    with a copy of the whole leaf (found when the decode program was first
    compiled for the chip: five copies of 660 MB each way). Rows narrower
    than one tile (the tests' sizes) are left alone."""
    return w if w < 128 else -(-w // 128) * 128


class SparseMoEConfig:
    """Static shape record (ints, floats and tuples of strings; nothing
    here ever becomes a tracer). `indexer_types[l]` is `full` or `shared`,
    `mlp_types[l]` is `dense` or `sparse`; `held_first`, `held_count` say
    which of the `routed_experts` this process holds."""

    FIELDS = ("vocab", "embed", "heads", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "rope_theta", "index_heads", "index_head_dim", "index_topk",
              "indexer_types", "mlp_types", "mlp_hidden", "expert_hidden",
              "routed_experts", "experts_per_token", "routed_scaling_factor",
              "held_first", "held_count", "max_len", "dtype", "norm_eps")
    #: `route`'s group limit; 1 group is no limit (this model's)
    n_group = topk_group = 1

    def __init__(self, vocab=128, embed=64, heads=4, q_lora_rank=32,
                 kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
                 v_head_dim=16, rope_theta=10000.0, index_heads=4,
                 index_head_dim=8, index_topk=8,
                 indexer_types=("full", "shared"),
                 mlp_types=("dense", "sparse"), mlp_hidden=128,
                 expert_hidden=32, routed_experts=16, experts_per_token=2,
                 routed_scaling_factor=2.5, held_first=0, held_count=4,
                 max_len=64, dtype="float32", norm_eps=1e-5):
        for k in self.FIELDS:
            v = locals()[k]
            setattr(self, k, tuple(v) if isinstance(v, (list, tuple)) else v)
        self.rope_theta = float(rope_theta)
        self.dtype = str(dtype)
        if len(self.indexer_types) != len(self.mlp_types):
            raise ServeError("indexer_types and mlp_types name the same "
                             "layers: their lengths differ")
        if not self.indexer_types or self.indexer_types[0] != "full":
            raise ServeError("the first layer's indexer must be `full`: a "
                             "`shared` one has no choice below it to read")
        if set(self.indexer_types) - {"full", "shared"} \
                or set(self.mlp_types) - {"dense", "sparse"}:
            raise ServeError("indexer_types holds `full` / `shared`, "
                             "mlp_types `dense` / `sparse`")
        if self.qk_rope_head_dim % 2 \
                or self.qk_rope_head_dim > self.index_head_dim:
            raise ServeError("rotary pairs need an even qk_rope_head_dim, "
                             "no wider than index_head_dim")
        if self.heads % HEAD_GROUP \
                or self.index_heads & (self.index_heads - 1) \
                or self.index_heads < HEAD_GROUP:
            raise ServeError(
                f"heads are read {HEAD_GROUP} at a time; index_heads in "
                f"powers of two from {HEAD_GROUP}")
        if not (0 <= self.held_first and self.held_count >= 1
                and self.held_first + self.held_count
                <= self.routed_experts):
            raise ServeError(
                f"held experts [{self.held_first}, "
                f"{self.held_first + self.held_count}) outside "
                f"[0, {self.routed_experts})")
        if not 1 <= self.experts_per_token <= self.routed_experts:
            raise ServeError("experts_per_token outside [1, routed_experts]")

    layers = property(lambda self: len(self.indexer_types))
    lat_width = property(lambda self: self.kv_lora_rank
                         + self.qk_rope_head_dim)

    lat_stored = property(lambda self: whole_tiles(self.lat_width))
    n_full = property(lambda self: self.indexer_types.count("full"))
    n_dense = property(lambda self: self.mlp_types.count("dense"))
    n_sparse = property(lambda self: self.mlp_types.count("sparse"))

    @property
    def slots(self):
        """[(index among the `full` indexers or None, index among the
        dense or the sparse feed-forward layers)] by layer."""
        out, nf, nd, ns = [], 0, 0, 0
        for it, mt in zip(self.indexer_types, self.mlp_types):
            fi = None
            if it == "full":
                fi, nf = nf, nf + 1
            if mt == "dense":
                mi, nd = nd, nd + 1
            else:
                mi, ns = ns, ns + 1
            out.append((fi, mi))
        return out

    def as_dict(self):
        return {k: getattr(self, k) for k in self.FIELDS}


#: the initializer's scales by kind of leaf (`param_shapes`): normals
INIT_SCALES = {"normal": 0.02, "emb": 0.02, "q_b": 0.02, "kv_b": 0.02,
               "o": 0.02, "down": 0.02, "index_q": 0.02, "index_k": 0.02,
               "router": 0.02, "router_bias": 0.02}


def param_shapes(c):
    """name -> (shape, kind of initial value): the leaves, stacked on a
    leading axis over the layers that have them (attention: every layer;
    `i_*`: the `full` indexers; `d_*`: the dense feed-forward layers; `r_*`,
    `e_*`, `s_*`: the sparse ones, `e_*` over the experts HELD too). This
    is the one table of the model's leaves. Kinds: `ones`, `zeros`, and
    normals at `INIT_SCALES[kind]`. `*_gate_up` is [W1 | W3]; `wkv_b` is
    H heads of [k_nope | v] columns."""
    L, d, V, H = c.layers, c.embed, c.vocab, c.heads
    qr, kvr = c.q_lora_rank, c.kv_lora_rank
    dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    J, DI, F, Fe = (c.index_heads, c.index_head_dim, c.mlp_hidden,
                    c.expert_hidden)
    nf, nd, ns, E, held = (c.n_full, c.n_dense, c.n_sparse,
                           c.routed_experts, c.held_count)
    return {
        "emb": ((V, d), "emb"), "head": ((d, V), "normal"),
        "lnf_w": ((d,), "ones"),
        "ln1_w": ((L, d), "ones"), "ln2_w": ((L, d), "ones"),
        "wq_a": ((L, d, qr), "normal"), "q_norm": ((L, qr), "ones"),
        "wq_b": ((L, qr, H * (dn + dr)), "q_b"),
        "wkv_a": ((L, d, kvr + dr), "normal"), "kv_norm": ((L, kvr), "ones"),
        "wkv_b": ((L, kvr, H * (dn + dv)), "kv_b"),
        "wo": ((L, H * dv, d), "o"),
        "i_wq": ((nf, qr, J * DI), "index_q"),
        "i_wk": ((nf, d, DI), "index_k"),
        "i_k_norm_w": ((nf, DI), "ones"), "i_k_norm_b": ((nf, DI), "zeros"),
        "i_ww": ((nf, d, J), "normal"),
        "d_gate_up": ((nd, d, 2 * F), "normal"),
        "d_down": ((nd, F, d), "down"),
        "r_w": ((ns, d, E), "router"), "r_b": ((ns, E), "router_bias"),
        "e_gate_up": ((ns, held, d, 2 * Fe), "normal"),
        "e_down": ((ns, held, Fe, d), "down"),
        "s_gate_up": ((ns, d, 2 * Fe), "normal"),
        "s_down": ((ns, Fe, d), "down"),
    }


#: leaves kept in float32 whatever `config.dtype` is
FLOAT32_LEAVES = ("r_b",)


def draw_leaf(key, shape, kind, scales=INIT_SCALES):
    """One leaf's initial value in float32: ones, zeros, a normal at the
    standard deviation `scales[kind]`, or a uniform draw where that is a
    (low, high) pair."""
    import jax
    import jax.numpy as jnp
    if kind == "ones":
        return jnp.ones(shape)
    if kind == "zeros":
        return jnp.zeros(shape)
    if isinstance(scales[kind], (tuple, list)):     # (low, high): uniform
        low, high = scales[kind]
        return jax.random.uniform(key, shape, minval=low, maxval=high)
    return jax.random.normal(key, shape) * scales[kind]


def init_sparse_moe_params(config, seed=0, scales=INIT_SCALES):
    """Deterministic random parameters in `config.dtype`."""
    import jax
    key = jax.random.PRNGKey(seed)
    return {name: draw_leaf(jax.random.fold_in(key, i), shape, kind,
                            scales).astype(
                "float32" if name in FLOAT32_LEAVES else config.dtype)
            for i, (name, (shape, kind)) in enumerate(
                sorted(param_shapes(config).items()))}


# ---------------------------------------------------------------------------
# layer library: plain functions of (weights, activations, cache, lengths)
# ---------------------------------------------------------------------------
def rms_norm(x, w, eps):
    """RMSNorm with a weight; float32 statistics."""
    import jax
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def rope(x, pos, theta):
    """Rotary embedding on the last axis of x as interleaved pairs
    (x_2i, x_2i+1) at angle pos * theta^(-2i/n), in float32. `pos` has x's
    leading axes: x (.., n) with pos (..), or x (.., heads, n) with pos
    (..) one axis short."""
    import jax.numpy as jnp
    n = x.shape[-1]
    freq = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = pos.astype(jnp.float32)[..., None] * freq
    if x.ndim == ang.ndim + 1:                  # a heads axis before n
        ang = ang[..., None, :]
    xf = x.astype(jnp.float32)
    a, b = xf[..., 0::2], xf[..., 1::2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(
        x.shape).astype(x.dtype)


def _rope_head(x, pos, n, theta):
    """Rotary on the first n of the last axis, the rest as it is."""
    import jax.numpy as jnp
    return jnp.concatenate([rope(x[..., :n], pos, theta), x[..., n:]], -1)


def mla_project(w, c, h, pos):
    """h (.., d) at positions pos (..) -> (cq (.., q_lora_rank), q_nope
    (.., H, nope), q_rope (.., H, rope) rotated, ckr (.., lat_stored): the
    position's cache entry [c normalised | kr rotated | zeros to whole
    tiles]). With `c.q_lora_rank` None the queries are h `w["wq"]` and cq
    is None."""
    import jax.numpy as jnp
    dn, dr, kvr = c.qk_nope_head_dim, c.qk_rope_head_dim, c.kv_lora_rank
    if c.q_lora_rank is None:
        cq, q = None, h @ w["wq"]
    else:
        cq = rms_norm(h @ w["wq_a"], w["q_norm"], c.norm_eps)
        q = cq @ w["wq_b"]
    q = q.reshape(h.shape[:-1] + (c.heads, dn + dr))
    ckr = h @ w["wkv_a"]
    parts = [rms_norm(ckr[..., :kvr], w["kv_norm"], c.norm_eps),
             rope(ckr[..., kvr:], pos, c.rope_theta)]
    if c.lat_stored > c.lat_width:
        parts.append(jnp.zeros(
            ckr.shape[:-1] + (c.lat_stored - c.lat_width,), ckr.dtype))
    ckr = jnp.concatenate(parts, -1)
    return cq, q[..., :dn], rope(q[..., dn:], pos, c.rope_theta), ckr


def index_project(w, c, h, cq, pos):
    """-> (qI (.., J, D_I) rotated on its first rope values, wI (.., J)
    float32 with both scales in, kI (.., D_I): the position's index key)."""
    import jax.numpy as jnp
    J, DI, dr = c.index_heads, c.index_head_dim, c.qk_rope_head_dim
    qI = _rope_head((cq @ w["i_wq"]).reshape(h.shape[:-1] + (J, DI)), pos,
                    dr, c.rope_theta)
    kI = _rope_head(layer_norm(h @ w["i_wk"], w["i_k_norm_w"],
                               w["i_k_norm_b"], c.norm_eps), pos, dr,
                    c.rope_theta)
    wI = jnp.dot(h, w["i_ww"], preferred_element_type=jnp.float32) \
        / math.sqrt(J * DI)
    return qI, wI, kI


def index_scores(qI, wI, kI):
    """I = sum_j w_j ReLU(qI_j . kI_s) in float32. qI (B, W, J, D_I), wI
    (B, W, J), kI (B, E, D_I) -> (B, W, E). The per-head scores of a step
    stay under `SCORE_BYTES`: a chunk's 1024 queries go `HEAD_GROUP` heads
    a step, a decode step's one query a lane takes all heads at once (every
    step reads kI whole: eight steps read a 138 MB leaf eight times)."""
    import jax
    import jax.numpy as jnp
    B, W, J, DI = qI.shape
    g = HEAD_GROUP
    while g < J and 4 * B * W * kI.shape[1] * 2 * g <= SCORE_BYTES:
        g *= 2
    G = J // g
    qg = qI.reshape(B, W, G, g, DI).transpose(2, 0, 1, 3, 4)
    wg = wI.reshape(B, W, G, g).transpose(2, 0, 1, 3)

    def step(acc, qw):
        q, w = qw
        s = jnp.einsum("bwjd,bed->bwje", q, kI,
                       preferred_element_type=jnp.float32)
        return acc + jnp.sum(w[..., None] * jnp.maximum(s, 0.0), 2), None

    acc, _ = jax.lax.scan(
        step, jnp.zeros((B, W, kI.shape[1]), jnp.float32), (qg, wg))
    return acc


def select_mask(scores, live, k):
    """The k entries of largest `scores` (float32) among `live`, along the
    last axis, as a mask; every live entry where there are no more than k;
    the lower index first among equals. The k-th largest value is found
    exactly, by bisection on the scores' bits (32 counts, no sort)."""
    import jax
    import jax.numpy as jnp
    u = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    # float32 order as unsigned order; a dead entry sorts below all
    key = jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))
    key = jnp.where(live, key, jnp.uint32(0))

    def bit(i, thr):
        cand = thr | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(key >= cand[..., None], -1) >= k
        return jnp.where(enough, cand, thr)

    thr = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(scores.shape[:-1], jnp.uint32))
    above = key > thr[..., None]
    equal = key == thr[..., None]
    room = k - jnp.sum(above, -1, dtype=jnp.int32)
    chosen = above | (equal & (jnp.cumsum(equal, -1, dtype=jnp.int32)
                               <= room[..., None]))
    few = jnp.sum(live, -1, dtype=jnp.int32) <= k
    return jnp.where(few[..., None], live, chosen & live)


def mla_read_rebuilt(q_nope, q_rope, ckr, mask, wkv_b, c):
    """A chunk's queries over cached positions, K and V rebuilt from the
    latent. q_nope (B, W, H, nope), q_rope (B, W, H, rope), ckr (B, E,
    lat_stored), mask (B, W, E) -> (B, W, H * v_head_dim), `HEAD_GROUP`
    heads a step."""
    import jax
    import jax.numpy as jnp
    B, W, H, dn = q_nope.shape
    dv, kvr = c.v_head_dim, c.kv_lora_rank
    G, g = H // HEAD_GROUP, HEAD_GROUP
    lat, kr = ckr[..., :kvr], ckr[..., kvr:c.lat_width]
    wg = wkv_b.reshape(kvr, G, g * (dn + dv)).transpose(1, 0, 2)
    qn = q_nope.reshape(B, W, G, g, dn).transpose(2, 0, 1, 3, 4)
    qr = q_rope.reshape(B, W, G, g, -1).transpose(2, 0, 1, 3, 4)
    scale = 1.0 / math.sqrt(dn + c.qk_rope_head_dim)

    def step(_, xs):
        w, qn_g, qr_g = xs
        kv = (lat @ w).reshape(B, -1, g, dn + dv)
        s = (jnp.einsum("bwhd,behd->bhwe", qn_g, kv[..., :dn],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bwhd,bed->bhwe", qr_g, kr,
                          preferred_element_type=jnp.float32)) * scale
        p = jax.nn.softmax(jnp.where(mask[:, None], s, -1e30), -1)
        return None, jnp.einsum("bhwe,behd->bwhd", p.astype(lat.dtype),
                                kv[..., dn:])

    _, o = jax.lax.scan(step, None, (wg, qn, qr))       # (G, B, W, g, dv)
    return o.transpose(1, 2, 0, 3, 4).reshape(B, W, H * dv)


def mla_read_absorbed(q_nope, q_rope, ckr, valid, wkv_b, c, lengths=None):
    """One query a lane over ITS chosen positions, in the latent space:
    q~_h = q_nope_h Wuk_h^T meets c itself, and Wuv_h follows the read.
    q_nope (S, H, nope), q_rope (S, H, rope), ckr (S, K, lat_stored) the
    gathered cache entries, valid (S, K) -> (S, H * v_head_dim).

    With `lengths` (S,) nothing was chosen: `ckr` is the cache leaf itself
    (rows, max_len, lat_stored), `valid` None, and lane s reads positions
    [0, lengths[s]] of row s, all of them: H query heads over the one
    cached vector a position (`ops.fused.paged_attention`'s leaf read: its
    time follows the live positions)."""
    import jax
    import jax.numpy as jnp
    S, H, dn = q_nope.shape
    dv, kvr = c.v_head_dim, c.kv_lora_rank
    w = wkv_b.reshape(kvr, H, dn + dv)
    q_lat = jnp.einsum("shd,chd->shc", q_nope, w[..., :dn])
    if lengths is not None:
        from ..ops import fused as _fused
        # the stored entry [c | kr | 0] is key and value at once
        q = jnp.concatenate(
            [q_lat, q_rope, jnp.zeros((S, H, c.lat_stored - c.lat_width),
                                      q_lat.dtype)], -1).astype(ckr.dtype)
        o_lat = _fused.paged_attention(
            q[:, None], ckr, ckr, lengths, None,
            scale=1.0 / math.sqrt(dn + c.qk_rope_head_dim),
            out_dtype=jnp.float32)[:, 0, :, :kvr].astype(ckr.dtype)
        return jnp.einsum("shc,chd->shd", o_lat, w[..., dn:]).reshape(
            S, H * dv)
    s = (jnp.einsum("shc,skc->shk", q_lat, ckr[..., :kvr],
                    preferred_element_type=jnp.float32)
         + jnp.einsum("shd,skd->shk", q_rope, ckr[..., kvr:c.lat_width],
                      preferred_element_type=jnp.float32)) \
        / math.sqrt(dn + c.qk_rope_head_dim)
    p = jax.nn.softmax(jnp.where(valid[:, None], s, -1e30), -1)
    o_lat = jnp.einsum("shk,skc->shc", p.astype(ckr.dtype), ckr[..., :kvr])
    return jnp.einsum("shc,chd->shd", o_lat, w[..., dn:]).reshape(S, H * dv)


def route(h, r_w, r_b, c, with_kept=False):
    """h (T, d) -> (expert ids (T, k) int32, gates (T, k) float32): the
    top-k of sigma + b, gated by sigma normalised over the k chosen.

    With `c.n_group` > 1 the choice is group-limited (DeepSeek-V3's
    `noaux_tc`): the experts lie in `n_group` contiguous groups, a group
    scores the sum of its 2 largest sigma + b, and the top-k is taken
    inside the `c.topk_group` best groups only. `with_kept` appends the
    kept groups (T, n_group) bool."""
    import jax
    import jax.numpy as jnp
    sig = jax.nn.sigmoid(jnp.dot(h, r_w, preferred_element_type=jnp.float32))
    biased = sig + r_b.astype(jnp.float32)
    kept = jnp.ones(sig.shape[:-1] + (1,), bool)
    if c.n_group > 1:
        grouped = biased.reshape(sig.shape[:-1] + (c.n_group, -1))
        score = jnp.sum(jax.lax.top_k(grouped, 2)[0], -1)
        _, best = jax.lax.top_k(score, c.topk_group)
        kept = jnp.any(best[..., None] == jnp.arange(c.n_group), -2)
        biased = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(
            sig.shape)
    _, idx = jax.lax.top_k(biased, c.experts_per_token)
    g = jnp.take_along_axis(sig, idx, -1)
    gates = c.routed_scaling_factor * g / jnp.sum(g, -1, keepdims=True)
    return (idx, gates, kept) if with_kept else (idx, gates)


def routed_experts(h, idx, gates, token_ok, w_gate_up, w_down, held_first,
                   held, row0=0):
    """The held experts' share of the routed sum. h (T, d); idx, gates
    (T, k) from `route`; token_ok (T,) bool (a pad token routes nowhere);
    w_gate_up (n, d, 2F), w_down (n, F, d): a stack of experts' weights
    whose rows [row0, row0 + held) are experts [held_first, held_first +
    held) (the whole model's stack, every layer's experts on one axis: a
    slice of one layer's would be written anew at every call, because the
    `while_loop` below carries what it reads). -> (y (T, d) in h's type,
    loads (held,) int32: the tokens each held expert received).

    The T*k token-expert pairs are sorted by expert with the pairs of
    experts held elsewhere last; a held expert's pairs are then a run of
    rows, cut into blocks of at most `EXPERT_BLOCK`. One `while_loop` runs
    over the blocks that exist in THIS call (their number is data, so a
    changed load retraces nothing): a block gathers its tokens, multiplies
    them with its expert's weights and writes its rows of the sorted
    result, in order, so that a later block rewrites whatever an earlier
    one's tail left in its rows. Every pair of a held expert is computed
    whatever the load: there is no capacity."""
    import jax
    import jax.numpy as jnp
    T, k = idx.shape
    d = h.shape[-1]
    M = T * k
    bm = min(EXPERT_BLOCK, -(-T // 16) * 16)
    local = idx - held_first
    mine = (local >= 0) & (local < held) & token_ok[:, None]
    group = jnp.where(mine, local, held).reshape(M)
    order = jnp.argsort(group)
    s_group = group[order]
    s_token = (order // k).astype(jnp.int32)
    s_gate = gates.reshape(M)[order]
    loads = jnp.sum(group[:, None] == jnp.arange(held)[None, :], 0,
                    dtype=jnp.int32)                              # (held,)
    ends = jnp.cumsum(loads)
    starts = ends - loads
    blocks = (loads + bm - 1) // bm
    block_ends = jnp.cumsum(blocks)

    def body(carry):
        i, out = carry
        e = jnp.sum(block_ends <= i).astype(jnp.int32)
        r0 = starts[e] + (i - (block_ends[e] - blocks[e])) * bm
        rows = r0 + jnp.arange(bm)
        ok = rows < ends[e]
        rows = jnp.minimum(rows, M - 1)
        x = h[s_token[rows]]
        gu = x @ jax.lax.dynamic_index_in_dim(w_gate_up, row0 + e, 0, False)
        F = gu.shape[-1] // 2
        y = jnp.dot(silu(gu[:, :F]) * gu[:, F:],
                    jax.lax.dynamic_index_in_dim(w_down, row0 + e, 0, False),
                    preferred_element_type=jnp.float32)
        y = y * jnp.where(ok, s_gate[rows], 0.0)[:, None]
        return i + 1, jax.lax.dynamic_update_slice_in_dim(
            out, y.astype(out.dtype), r0, 0)

    n_blocks = block_ends[-1]
    _, out = jax.lax.while_loop(
        lambda carry: carry[0] < n_blocks, body,
        (jnp.int32(0), jnp.zeros((M + bm, d), h.dtype)))
    # each pair's row of the sorted result, back at its token
    place = jnp.zeros((M,), jnp.int32).at[order].set(
        jnp.arange(M, dtype=jnp.int32))
    pairs = jnp.where((s_group < held)[place][:, None], out[place], 0)
    y = jnp.sum(pairs.reshape(T, k, d).astype(jnp.float32), 1)
    return y.astype(h.dtype), loads


# ---------------------------------------------------------------------------
# the three programs
# ---------------------------------------------------------------------------
ATTN_LEAVES = ("ln1_w", "ln2_w", "wq_a", "q_norm", "wq_b", "wkv_a",
               "kv_norm", "wkv_b", "wo")


def _weights(params, c, l):
    """Layer l's leaves: slices of the stacked tree."""
    fi, mi = c.slots[l]
    w = {n: params[n][l] for n in ATTN_LEAVES}
    if fi is not None:
        w.update({n: a[fi] for n, a in params.items() if n.startswith("i_")})
    prefixes = ("d_",) if c.mlp_types[l] == "dense" else ("r_", "s_")
    w.update({n: a[mi] for n, a in params.items()
              if n.startswith(prefixes)})
    if c.mlp_types[l] == "sparse":
        # every layer's held experts on one axis, this layer's from `e_row0`
        for n in ("e_gate_up", "e_down"):
            w[n] = params[n].reshape((-1,) + params[n].shape[2:])
        w["e_row0"] = mi * c.held_count
    return w


def _ffn(x, w, c, l, token_ok, total=None):
    """x (T, d) -> (x + FFN_l(RMSNorm(x)), moe counters (4,) int32:
    token-expert pairs on held experts, held experts that received a
    token, held experts offered, the largest load). A group-limited router
    (`c.n_group` > 1) counts two more: the tokens whose kept groups hold
    one of this process's experts (`c.held_groups`), and the tokens routed.
    With `total` the counters come added to it. Every equation runs under
    one of the layer's scopes (the norm under `/mlp`, the counts under
    `/experts`), in the order it always had: the lowered program is the
    same text, its locations apart."""
    import jax
    import jax.numpy as jnp
    count_groups = c.n_group > 1
    dense = c.mlp_types[l] == "dense"
    with jax.named_scope(f"layer{l}/mlp"):
        h = rms_norm(x, w["ln2_w"], c.norm_eps)
        if dense:
            x = x + gated_mlp(h, w["d_gate_up"], w["d_down"])
            counted = jnp.zeros((6 if count_groups else 4,), jnp.int32)
    if not dense:
        with jax.named_scope(f"layer{l}/router"):
            idx, gates, kept = route(h, w["r_w"], w["r_b"], c,
                                     with_kept=True)
        with jax.named_scope(f"layer{l}/experts"):
            y, loads = routed_experts(h, idx, gates, token_ok,
                                      w["e_gate_up"], w["e_down"],
                                      c.held_first, c.held_count,
                                      w["e_row0"])
        with jax.named_scope(f"layer{l}/shared_expert"):
            y = y + gated_mlp(h, w["s_gate_up"], w["s_down"])
        with jax.named_scope(f"layer{l}/experts"):
            any_token = jnp.any(token_ok).astype(jnp.int32)
            counted = [jnp.sum(loads), jnp.sum(loads > 0, dtype=jnp.int32),
                       c.held_count * any_token, jnp.max(loads)]
            if count_groups:
                here = jnp.any(kept[:, jnp.asarray(c.held_groups)], -1) \
                    & token_ok
                counted += [jnp.sum(here, dtype=jnp.int32),
                            jnp.sum(token_ok, dtype=jnp.int32)]
        with jax.named_scope(f"layer{l}/shared_expert"):
            x = x + y
    with jax.named_scope(f"layer{l}/" + ("mlp" if dense else "experts")):
        if not dense:
            counted = jnp.stack(counted)
        return x, counted if total is None else total + counted


def _head(params, x, c):
    import jax
    import jax.numpy as jnp
    with jax.named_scope("head"):
        return jnp.dot(rms_norm(x, params["lnf_w"], c.norm_eps),
                       params["head"], preferred_element_type=jnp.float32)


def _sparse_counters(ok, live, chosen):
    """(3,) int32: queries, their live positions, their chosen ones."""
    import jax.numpy as jnp
    return jnp.stack([jnp.sum(ok, dtype=jnp.int32),
                      jnp.sum(jnp.where(ok, live, 0), dtype=jnp.int32),
                      jnp.sum(jnp.where(ok, chosen, 0), dtype=jnp.int32)])


def _make_chunk(config, window, extent, fresh):
    """The prefill step over one window-sized slice a lane, reading the
    cached positions [0, extent). `fresh` is the prefill at offset 0
    (`prefill(params, cache, tokens, lengths, slot_rows)`, extent =
    window); else the chunk at an offset (`chunk_prefill(params, cache,
    tokens, offsets, nvalid, slot_rows)`). Lanes are PREFILL lanes with
    their pool rows as data; an idle lane carries the garbage row. Both
    return (cache, logits of each lane's last position, counters)."""
    import jax
    import jax.numpy as jnp
    c = config
    W, E = int(window), int(extent)
    if not 1 <= W <= E <= c.max_len:
        raise ServeError(f"chunk window {W} and extent {E} outside "
                         f"1 <= window <= extent <= max_len={c.max_len}")
    selects = E > c.index_topk

    def core(params, cache, tokens, offsets, nvalid, rows):
        cache = dict(cache)
        B = tokens.shape[0]
        G = cache["lat0"].shape[0] - 1                   # garbage row
        j = jnp.arange(W)
        # an idle lane carries the garbage row (and whatever length)
        valid = (j[None, :] < nvalid[:, None]) & (rows != G)[:, None]
        wrows = jnp.where(valid, rows[:, None], G)
        pos = offsets[:, None] + j[None, :]
        wpos = jnp.clip(pos, 0, c.max_len - 1)
        live = jnp.arange(E)[None, None, :] <= pos[..., None]   # (B, W, E)
        n_live = jnp.minimum(pos + 1, E)
        with jax.named_scope("embed"):
            x = params["emb"][tokens]                            # (B, W, d)
        mask = live
        moe = jnp.zeros((4,), jnp.int32)
        sparse = jnp.zeros((3,), jnp.int32)
        for l in range(c.layers):
            fi, _ = c.slots[l]
            w = _weights(params, c, l)
            h = rms_norm(x, w["ln1_w"], c.norm_eps)
            with jax.named_scope(f"layer{l}/mla"):
                cq, q_nope, q_rope, ckr = mla_project(w, c, h, pos)
                lat = cache[f"lat{l}"].at[wrows, wpos].set(ckr)
                cache[f"lat{l}"] = lat
            if fi is not None:
                with jax.named_scope(f"layer{l}/indexer"):
                    qI, wI, kI = index_project(w, c, h, cq, pos)
                    keys = cache[f"idx{fi}"].at[wrows, wpos].set(kI)
                    cache[f"idx{fi}"] = keys
                    if selects:
                        scores = index_scores(qI, wI, keys[rows, :E])
                if selects:
                    with jax.named_scope(f"layer{l}/select"):
                        mask = select_mask(scores, live, c.index_topk)
            with jax.named_scope(f"layer{l}/sparse_read"):
                o = mla_read_rebuilt(q_nope, q_rope, lat[rows, :E], mask,
                                     w["wkv_b"], c)
                sparse = sparse + _sparse_counters(
                    valid, n_live, jnp.sum(mask, -1, dtype=jnp.int32))
            with jax.named_scope(f"layer{l}/mla"):
                x = x + o @ w["wo"]
            y, counted = _ffn(x.reshape(B * W, -1), w, c, l,
                              valid.reshape(B * W))
            x, moe = y.reshape(x.shape), moe + counted
        last = jnp.maximum(nvalid - 1, 0)
        logits = _head(params, x[jnp.arange(B), last], c)
        return cache, logits, {"moe": moe, "sparse": sparse}

    if fresh:
        def prefill(params, cache, tokens, lengths, slot_rows):
            return core(params, cache, tokens, jnp.zeros_like(lengths),
                        lengths, slot_rows)
        return prefill

    def chunk_prefill(params, cache, tokens, offsets, nvalid, slot_rows):
        return core(params, cache, tokens, offsets, nvalid, slot_rows)
    return chunk_prefill


def _make_micro(config):
    """One token for every active lane, lane s = pool row s:
    `micro(params, cache, tokens, lengths, active) -> (cache, logits,
    counters)`. tokens (S,) the last emitted token, lengths (S,) the cache
    length (the new token's entries land at position `lengths`); an idle
    lane writes the garbage row."""
    import jax
    import jax.numpy as jnp
    c = config
    K = min(c.index_topk, c.max_len)

    def micro(params, cache, tokens, lengths, active):
        cache = dict(cache)
        S = tokens.shape[0]
        lane = jnp.arange(S)
        # (every equation runs under one of the program's scopes, the
        # lanes' bookkeeping and a layer's first norm too:
        # `profiler.program_scopes` names the device's time by them)
        with jax.named_scope("embed"):
            rows = jnp.where(active, lane, S)            # garbage row = S
            wpos = jnp.clip(lengths, 0, c.max_len - 1)
            live = jnp.arange(c.max_len)[None, :] <= lengths[:, None]
            n_live = jnp.minimum(lengths + 1, c.max_len)
            x = params["emb"][tokens]                            # (S, d)
        chosen = ok = None
        moe = jnp.zeros((4,), jnp.int32)
        sparse = jnp.zeros((3,), jnp.int32)
        for l in range(c.layers):
            fi, _ = c.slots[l]
            with jax.named_scope(f"layer{l}/mla"):
                w = _weights(params, c, l)
                h = rms_norm(x, w["ln1_w"], c.norm_eps)
                cq, q_nope, q_rope, ckr = mla_project(w, c, h, lengths)
                lat = cache[f"lat{l}"].at[rows, wpos].set(ckr)
                cache[f"lat{l}"] = lat
            if fi is not None:
                with jax.named_scope(f"layer{l}/indexer"):
                    qI, wI, kI = index_project(w, c, h, cq, lengths)
                    keys = cache[f"idx{fi}"].at[rows, wpos].set(kI)
                    cache[f"idx{fi}"] = keys
                    # over every pool row, the garbage row too: a slice
                    # of the leaf's first S rows is a copy of the leaf
                    pad = ((0, 1), (0, 0), (0, 0))
                    scores = index_scores(
                        jnp.pad(qI, pad)[:, None], jnp.pad(wI, pad[:2])[:, None],
                        keys)[:S, 0]
                with jax.named_scope(f"layer{l}/select"):
                    top, chosen = jax.lax.top_k(
                        jnp.where(live, scores, -jnp.inf), K)
                    ok = top > -jnp.inf
            with jax.named_scope(f"layer{l}/sparse_read"):
                o = mla_read_absorbed(q_nope, q_rope,
                                      lat[lane[:, None], chosen], ok,
                                      w["wkv_b"], c)
                sparse = sparse + _sparse_counters(
                    active, n_live, jnp.sum(ok, -1, dtype=jnp.int32))
            with jax.named_scope(f"layer{l}/mla"):
                x = x + o @ w["wo"]
            x, moe = _ffn(x, w, c, l, active, moe)
        return cache, _head(params, x, c), {"moe": moe, "sparse": sparse}

    return micro


#: what every program of this decoder counts (`SparseMoEDecoder.counters`)
COUNTERS = {
    "moe": ("pairs_held", "experts_hit", "experts_offered", "max_load_sum"),
    "sparse": ("queries", "live_positions", "chosen_positions"),
}


def _make_decode(config, steps, eos_id, micro=None, counter_fields=COUNTERS):
    """The decode step: every pool slot advances up to `steps` tokens in
    one program (`lax.scan` over the micro-step),
    `serve.continuous._make_decode`'s contract with the counters last:
    `decode(params, cache, tokens, lengths, steps_left, temps, top_ks,
    top_ps, keys) -> (cache, out_tokens (steps, S), emitted, counters)`.
    Another block's decoder hands in its own `micro` and the fields it
    counts ({name: field names})."""
    import jax
    import jax.numpy as jnp
    from ..serve.sampling import sample_tokens
    micro = micro or _make_micro(config)

    def decode(params, cache, tokens, lengths, steps_left, temps, top_ks,
               top_ps, keys):
        def step(carry, _):
            cache, last, lens, left, emitted, counters = carry
            with jax.named_scope("sampler"):
                act = left > 0
            cache, logits, counted = micro(params, cache, last, lens, act)
            nxt = sample_tokens(logits, temps, top_ks, top_ps, keys, lens)
            # the lanes' carry: what the sampler's token does to each
            with jax.named_scope("sampler"):
                nxt = jnp.where(act, nxt, 0)
                new_left = jnp.where(act, left - 1, left)
                if eos_id is not None:
                    new_left = jnp.where(act & (nxt == eos_id), 0,
                                         new_left)
                lens = jnp.where(act, lens + 1, lens)
                last = jnp.where(act, nxt, last)
                emitted = emitted + act.astype(jnp.int32)
                counters = jax.tree_util.tree_map(jnp.add, counters,
                                                  counted)
            return (cache, last, lens, new_left, emitted, counters), nxt

        zero = jnp.zeros_like(steps_left)
        counters = {name: jnp.zeros((len(fields),), jnp.int32)
                    for name, fields in counter_fields.items()}
        (cache, _, _, _, emitted, counters), toks = jax.lax.scan(
            step, (cache, tokens, lengths, steps_left, zero, counters), None,
            length=steps)
        return cache, toks, emitted, counters

    return decode


class SparseMoEDecoder:
    """The model side of the continuous engine for the latent-attention,
    sparse-attention, sparse-expert decoder: jitted programs over a pool
    built from `cache_spec()`. The engine's model protocol: `config` (with
    `max_len`), `params`, `cache_spec`, `new_pool`, `prefill_program`,
    `chunk_prefill_program`, `decode_program`, `compile_cache_size`,
    `reference_generate`; `chunk_rows_as_data` (the chunk program's lanes
    are prefill lanes whose pool rows ride as a fourth array) and
    `counters` (every program returns, last, {name: int32 vector} with
    these fields, which the engine sums into `stats()[name]`)."""

    chunk_rows_as_data = True
    counters = COUNTERS
    # what a decoder of another block brings (`models.delta_moe_decoder`):
    # its initializer, its chunk and micro-step builders, `counters` and
    # `cache_spec`; the pool, the program table and `reference_generate`
    # are the same
    _init_params = staticmethod(init_sparse_moe_params)
    _make_chunk = staticmethod(_make_chunk)
    _make_micro = staticmethod(_make_micro)

    def __init__(self, config, params=None, seed=0):
        from ..deploy import maybe_enable_compile_cache
        maybe_enable_compile_cache()
        self.config = config
        self.params = params if params is not None \
            else self._init_params(config, seed)
        try:
            from ..inspect import memory as _mem
            _mem.register(self.params, owner="decoder_params")
        except Exception:
            pass
        self._programs = {}

    def cache_spec(self):
        """The cache leaves of one slot row: a latent entry a layer, an
        index key a `full` indexer, every one a `full` leaf."""
        c = self.config
        return [CacheLeaf(f"lat{l}", (c.max_len, c.lat_stored), c.dtype,
                          "full", c.max_len) for l in range(c.layers)] \
            + [CacheLeaf(f"idx{i}", (c.max_len, c.index_head_dim), c.dtype,
                         "full", c.max_len) for i in range(c.n_full)]

    def new_pool(self, max_slots=None, dtype=None):
        if dtype is not None and str(dtype) != self.config.dtype:
            raise ServeError(
                f"this decoder's cache is stored in its own dtype "
                f"({self.config.dtype}); kv_dtype={dtype!r} has no latent, "
                f"index-key or state form")
        return KVCachePool(max_slots, dtype=self.config.dtype,
                           spec=self.cache_spec())

    def _program(self, key, build, label):
        import jax
        fn = self._programs.get(key)
        if fn is None:
            fn = _sanitize.maybe_wrap_donated(
                jax.jit(build(), donate_argnums=(1,)), (1,), label)
            self._programs[key] = fn
        return fn

    def prefill_program(self, window):
        w = int(window)
        return self._program(("prefill", w),
                             lambda: self._make_chunk(self.config, w, w,
                                                      True),
                             f"prefill[w={w}]")

    def chunk_prefill_program(self, window, extent=None):
        """`extent` bounds the cached positions a chunk reads: the dense
        read under the mask costs what the extent is long."""
        w = int(window)
        e = int(extent if extent is not None else self.config.max_len)
        return self._program(("chunk", w, e),
                             lambda: self._make_chunk(self.config, w, e,
                                                      False),
                             f"chunk_prefill[w={w},e={e}]")

    def decode_program(self, steps, eos_id=None, draft=0):
        if draft:
            raise ServeError(
                "speculative decode verifies drafts against a dense read "
                "of K and V rows; a read of chosen positions of a latent "
                "cache has no verify program yet, and a recurrent state "
                "cannot be rolled back to the last accepted token")
        key = ("decode", int(steps), eos_id)
        return self._program(
            key, lambda: _make_decode(self.config, key[1], eos_id,
                                      self._make_micro(self.config),
                                      self.counters),
            f"decode[s={key[1]},eos={eos_id}]")

    def compile_cache_size(self):
        sizes = [int(getattr(f, "_cache_size", lambda: -1)())
                 for f in self._programs.values()]
        return -1 if any(s < 0 for s in sizes) else sum(sizes)

    def reference_generate(self, prompt, max_new_tokens, eos_id=None,
                           window=None, temperature=0.0, top_k=0,
                           top_p=1.0, seed=0):
        """Generation through a PRIVATE 1-slot pool with the same
        compiled math: a windowed prefill at offset 0, the remainder in
        window-sized chunks, then one decode step at a time. Greedy by
        default; `temperature > 0` draws as the engine does for that
        request seed."""
        import jax.numpy as jnp
        from ..serve.sampling import sample_first, seed_key
        c = self.config
        pool = self.new_pool(max_slots=1)
        W = int(window if window is not None else c.max_len)
        prompt = _np.asarray(prompt, dtype=_np.int32).ravel()
        plen = int(prompt.size)
        if plen < 1 or plen >= c.max_len:
            raise ServeError(f"prompt length {plen} outside "
                             f"[1, max_len-1={c.max_len - 1}]")
        one = lambda v, dt=jnp.int32: jnp.asarray([v], dtype=dt)  # noqa: E731
        sample = (one(temperature, jnp.float32), one(top_k),
                  one(top_p, jnp.float32), jnp.asarray(seed_key(seed)[None]))
        pos, logits = 0, None
        while pos < plen:
            n = min(W, plen - pos)
            toks = _np.zeros((1, W), dtype=_np.int32)
            toks[0, :n] = prompt[pos:pos + n]
            (cache,) = pool.buffers()
            if pos == 0:
                cache, logits, _ = self.prefill_program(W)(
                    self.params, cache, jnp.asarray(toks), one(n), one(0))
            else:
                cache, logits, _ = self.chunk_prefill_program(W)(
                    self.params, cache, jnp.asarray(toks), one(pos), one(n),
                    one(0))
            pool.swap_buffers(cache)
            pos += n
        out = [int(sample_first(logits, *sample, one(plen - 1))[0])]
        cache_len = plen
        decode = self.decode_program(1, eos_id)
        while (len(out) < max_new_tokens
               and (eos_id is None or out[-1] != eos_id)
               and cache_len + 1 < c.max_len):
            (cache,) = pool.buffers()
            cache, toks1, _, _ = decode(self.params, cache, one(out[-1]),
                                        one(cache_len), one(1), *sample)
            pool.swap_buffers(cache)
            out.append(int(toks1[0, 0]))
            cache_len += 1
        return _np.asarray(out, dtype=_np.int32)
