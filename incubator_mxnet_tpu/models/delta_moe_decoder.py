"""A linear-attention, latent-attention, sparse-expert decoder for
`serve.ContinuousEngine`: Kimi Delta Attention (KDA, arXiv:2510.26692) layers
whose cache is one float32 MATRIX a head, one multi-head latent attention
(MLA) layer among every few, read densely, and a feed-forward layer of
group-limited sigmoid-routed experts of which this process holds a
contiguous share (the `bailing_hybrid` block of Ling-3.0-flash).

    x_0 = E[token];  x <- x + Mix_l(RMSNorm(x));  x <- x + FFN_l(RMSNorm(x))
    logits = RMSNorm(x) W_head                  (float32, untied from E)

`mixer_types[l]` is `kda` or `mla`. With h = RMSNorm(x), H heads of d_k = d_v:

  KDA  [q~ | k~ | v~] = h W_qkv; a causal depthwise convolution of
       `conv_kernel` taps over time on each channel, then SiLU:
       q_t = SiLU(sum_i w_i * q~_{t-K+1+i}) (same for k, v; no bias). The
       last K-1 pre-convolution rows are the CONV TAIL.
       Per head: q <- l2(q) d_k^-1/2, k <- l2(k),
       l2(x) = x rsqrt(|x|^2 + 1e-6).
       Per-CHANNEL log-decay g_t = lb sigmoid(exp(A_h) (h W_f + b_f)) in
       (lb, 0)^{d_k} with lb = `kda_lower_bound` < 0, alpha_t = exp(g_t);
       beta_t = sigmoid(h W_beta), one scalar a head. The state S (d_k, d_v)
       a head, float32:
         S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
         o_t = S_t^T q_t
       Mix(h)_t = W_o [RMSNorm_head(o_t) * sigmoid(h W_g)] (the norm over
       each head's d_v values, one weight of d_v).
       A decode step is the recurrence itself (`kda_step`). A prefill
       chunk is its CHUNKWISE form (`kda_chunk`): with u_r = beta_r (v_r -
       (Diag(alpha_r) S_{r-1})^T k_r) the recurrence is S_r = Diag(alpha_r)
       S_{r-1} + k_r u_r^T, and over a chunk of C positions with cumulative
       decay Gamma_r = prod_{i<=r} alpha_i the u's solve
         (I + strictLower(Diag(beta) (K*Gamma)(K/Gamma)^T)) U
             = Diag(beta) (V - (K*Gamma) S_0),
       a C x C unit-triangular system (the WY form): T = (I + ...)^-1
       Diag(beta) is made for every chunk at once and what is left to the
       scan over chunks is matmuls against the carried state:
         U = T V - (T (K*Gamma)) S_0;  O = (Q*Gamma) S_0 + lower((Q*Gamma)
         (K/Gamma)^T) U;  S_C = Diag(Gamma_C) S_0 + (K Gamma_C/Gamma)^T U.
       K/Gamma is formed a SUB-CHUNK at a time against the decay at that
       sub-chunk's start: with g >= lb the exponent stays within
       -lb * sub_chunk <= 80 (`DeltaMoEConfig.sub_chunk`: 16 positions at
       the published -5), inside float32; a chunk is `CHUNK` positions.
  MLA  q = h W_q -> H x [q_nope | q_rope] (no q-LoRA); [c | kr] = h W_kv_a,
       c <- RMSNorm(c); interleaved rotary on q_rope and kr; [k_nope_h |
       v_h] = c W_kv_b; softmax over ALL s <= t at scale (nope + rope)^-1/2;
       head-wise output gate o_h <- o_h sigmoid(h W_a)_h; W_o. A prefill
       chunk rebuilds K and V, a decode step absorbs W_kv_b
       (`sparse_moe_decoder.mla_project`, `mla_read_rebuilt`,
       `mla_read_absorbed` with the whole live row for a chosen set).
  FFN  `dense`: one gated MLP. `sparse`: sigma = sigmoid(h W_r) float32,
       sigma' = sigma + b; the experts in `n_group` contiguous groups, a
       group scores the sum of its 2 largest sigma', the `topk_group` best
       groups are kept, the token's experts are the top-k of sigma' inside
       them, gates scale sigma_i / sum_chosen sigma; plus the shared
       expert (`sparse_moe_decoder.route`, `routed_experts`): the layer is
       told which experts it holds and returns their part.

Per KDA layer i the cache holds `kda{i}` (H, d_k, d_v) float32 and
`conv{i}` (K-1, 3 H d_k) (the three tails side by side), both `state`
leaves; per MLA layer `lat{j}` (max_len, lat_stored), a `full` leaf.

What a program may assume, and what it sees to:
  * a `state` leaf has no mask: the prefill at offset 0 never READS state
    or tail (it starts both from zero) and writes them whole; a chunk at an
    offset > 0 carries the lane's own on (tests poison-fill every leaf).
  * a decode step reads and rewrites every row of a state leaf in place,
    the garbage row too (a slice of the first S rows would be a copy of
    the leaf: 270 MB a layer at the served size); an idle lane keeps what
    it held.
  * the state, the decay, the router's scores, softmax and logits are
    float32 whatever the weights' type.
"""
from __future__ import annotations

import math

from ..serve.batcher import ServeError
from ..serve.kv_pool import CacheLeaf
from . import sparse_moe_decoder as _sm
from .hybrid_decoder import silu
from .sparse_moe_decoder import (_ffn, _head, draw_leaf, mla_project,
                                 mla_read_absorbed, mla_read_rebuilt,
                                 rms_norm, whole_tiles)

__all__ = ["DeltaMoEConfig", "DeltaMoEDecoder", "init_delta_moe_params",
           "param_shapes", "draw_leaf", "kda_step", "kda_chunk",
           "kda_project"]

L2_EPS = 1e-6
#: positions a step of the chunkwise form (`kda_chunk`): the side of the
#: triangular system and of every matmul against the carried state
CHUNK = 64


class DeltaMoEConfig:
    """Static shape record. `mixer_types[l]` is `kda` or `mla`,
    `mlp_types[l]` `dense` or `sparse`; `held_first`, `held_count` say
    which of the `routed_experts` this process holds."""

    FIELDS = ("vocab", "embed", "heads", "head_dim", "conv_kernel",
              "kda_lower_bound", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "rope_theta", "mixer_types",
              "mlp_types", "mlp_hidden", "expert_hidden", "routed_experts",
              "experts_per_token", "n_group", "topk_group",
              "routed_scaling_factor", "held_first", "held_count", "max_len",
              "dtype", "norm_eps")
    #: the queries are h W_q (`mla_project`)
    q_lora_rank = None

    def __init__(self, vocab=128, embed=64, heads=4, head_dim=16,
                 conv_kernel=4, kda_lower_bound=-5.0, kv_lora_rank=16,
                 qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
                 rope_theta=10000.0, mixer_types=("kda", "kda", "mla"),
                 mlp_types=("dense", "sparse", "sparse"), mlp_hidden=128,
                 expert_hidden=32, routed_experts=16, experts_per_token=2,
                 n_group=4, topk_group=2, routed_scaling_factor=2.5,
                 held_first=0, held_count=4, max_len=64, dtype="float32",
                 norm_eps=1e-6):
        for k in self.FIELDS:
            v = locals()[k]
            setattr(self, k, tuple(v) if isinstance(v, (list, tuple)) else v)
        self.rope_theta = float(rope_theta)
        self.kda_lower_bound = float(kda_lower_bound)
        self.dtype = str(dtype)
        if len(self.mixer_types) != len(self.mlp_types):
            raise ServeError("mixer_types and mlp_types name the same "
                             "layers: their lengths differ")
        if set(self.mixer_types) - {"kda", "mla"} \
                or set(self.mlp_types) - {"dense", "sparse"}:
            raise ServeError("mixer_types holds `kda` / `mla`, mlp_types "
                             "`dense` / `sparse`")
        if "mla" not in self.mixer_types:
            raise ServeError("at least one `mla` layer: its `full` leaf is "
                             "where the pool learns a request's length")
        if self.qk_rope_head_dim % 2 or self.heads % _sm.HEAD_GROUP:
            raise ServeError(
                f"rotary pairs need an even qk_rope_head_dim; heads are "
                f"read {_sm.HEAD_GROUP} at a time")
        if not -80.0 <= self.kda_lower_bound < 0:
            raise ServeError(
                "kda_lower_bound in [-80, 0): the log-decay's bound is "
                "what keeps K / Gamma of a sub-chunk inside float32")
        if self.n_group < 2 or self.routed_experts % self.n_group \
                or not 1 <= self.topk_group <= self.n_group \
                or self.routed_experts // self.n_group < 2:
            raise ServeError("this block's router is group-limited (its "
                             "programs count the kept groups): n_group >= 2 "
                             "divides routed_experts into groups of at "
                             "least 2; topk_group in [1, n_group]")
        if not (0 <= self.held_first and self.held_count >= 1
                and self.held_first + self.held_count
                <= self.routed_experts):
            raise ServeError(
                f"held experts [{self.held_first}, "
                f"{self.held_first + self.held_count}) outside "
                f"[0, {self.routed_experts})")
        if not 1 <= self.experts_per_token <= \
                self.topk_group * (self.routed_experts // self.n_group):
            raise ServeError("experts_per_token outside [1, the experts of "
                             "topk_group groups]")

    layers = property(lambda self: len(self.mixer_types))
    n_kda = property(lambda self: self.mixer_types.count("kda"))
    n_mla = property(lambda self: self.mixer_types.count("mla"))
    n_dense = property(lambda self: self.mlp_types.count("dense"))
    n_sparse = property(lambda self: self.mlp_types.count("sparse"))
    kda_width = property(lambda self: self.heads * self.head_dim)
    lat_width = property(lambda self: self.kv_lora_rank
                         + self.qk_rope_head_dim)
    lat_stored = property(lambda self: whole_tiles(self.lat_width))

    @property
    def sub_chunk(self):
        """Positions a block of K / Gamma: the largest power of two whose
        cumulative log-decay stays within 80 (16 at the published -5)."""
        return 2 ** int(math.log2(80.0 / -self.kda_lower_bound))

    @property
    def slots(self):
        """[(index among the mixers of the layer's kind, index among the
        dense or the sparse feed-forward layers)] by layer."""
        out, n = [], {"kda": 0, "mla": 0, "dense": 0, "sparse": 0}
        for kinds in zip(self.mixer_types, self.mlp_types):
            out.append(tuple(n[k] for k in kinds))
            for k in kinds:
                n[k] += 1
        return out

    @property
    def held_groups(self):
        """The routing groups that hold one of this process's experts."""
        size = self.routed_experts // self.n_group
        return tuple(range(self.held_first // size,
                           (self.held_first + self.held_count - 1) // size
                           + 1))

    def as_dict(self):
        return {k: getattr(self, k) for k in self.FIELDS}


#: the initializer's scales by kind of leaf (`param_shapes`). `kda_A` and
#: `kda_bf` are (low, high) of a uniform draw: with exp(A) (h W_f + b_f) = z
#: a channel's decay is exp(lb sigmoid(z)) a position, so z decides its
#: half-life; `router_bias`, `normal`, ... are standard deviations
INIT_SCALES = {"normal": 0.02, "emb": 0.02, "o": 0.02, "down": 0.02,
               "q": 0.02, "kv_b": 0.02, "kda_f": 0.02, "kda_beta": 0.02,
               "conv": 0.5, "router": 0.02, "router_bias": 0.02,
               "kda_A": (0.0, 0.0), "kda_bf": (-8.0, -2.0)}


def param_shapes(c):
    """name -> (shape, kind of initial value): the leaves, stacked on a
    leading axis over the layers that have them (`k_*`: the KDA mixers,
    `m_*`: the MLA mixers, `d_*`: the dense feed-forward layers, `r_*`,
    `e_*`, `s_*`: the sparse ones, `e_*` over the experts HELD too). The
    one table of the model's leaves. `k_qkv` is [W_q | W_k | W_v] and
    `k_conv` its taps, `*_gate_up` [W1 | W3], `m_wkv_b` H heads of
    [k_nope | v] columns."""
    L, d, V, H = c.layers, c.embed, c.vocab, c.heads
    HD, K = c.kda_width, c.conv_kernel
    kvr, dn, dr, dv = (c.kv_lora_rank, c.qk_nope_head_dim,
                       c.qk_rope_head_dim, c.v_head_dim)
    F, Fe = c.mlp_hidden, c.expert_hidden
    nk, nm, nd, ns = c.n_kda, c.n_mla, c.n_dense, c.n_sparse
    E, held = c.routed_experts, c.held_count
    return {
        "emb": ((V, d), "emb"), "head": ((d, V), "normal"),
        "lnf_w": ((d,), "ones"),
        "ln1_w": ((L, d), "ones"), "ln2_w": ((L, d), "ones"),
        "k_qkv": ((nk, d, 3 * HD), "normal"),
        "k_conv": ((nk, K, 3 * HD), "conv"),
        "k_f": ((nk, d, HD), "kda_f"), "k_bf": ((nk, HD), "kda_bf"),
        "k_A": ((nk, H), "kda_A"),
        "k_beta": ((nk, d, H), "kda_beta"),
        "k_g": ((nk, d, HD), "normal"),
        "k_onorm": ((nk, c.head_dim), "ones"),
        "k_o": ((nk, HD, d), "o"),
        "m_wq": ((nm, d, H * (dn + dr)), "q"),
        "m_wkv_a": ((nm, d, kvr + dr), "normal"),
        "m_kv_norm": ((nm, kvr), "ones"),
        "m_wkv_b": ((nm, kvr, H * (dn + dv)), "kv_b"),
        "m_wa": ((nm, d, H), "normal"),
        "m_wo": ((nm, H * dv, d), "o"),
        "d_gate_up": ((nd, d, 2 * F), "normal"),
        "d_down": ((nd, F, d), "down"),
        "r_w": ((ns, d, E), "router"), "r_b": ((ns, E), "router_bias"),
        "e_gate_up": ((ns, held, d, 2 * Fe), "normal"),
        "e_down": ((ns, held, Fe, d), "down"),
        "s_gate_up": ((ns, d, 2 * Fe), "normal"),
        "s_down": ((ns, Fe, d), "down"),
    }


#: leaves kept in float32 whatever `config.dtype` is
FLOAT32_LEAVES = ("r_b", "k_A", "k_bf")


def init_delta_moe_params(config, seed=0, scales=INIT_SCALES):
    """Deterministic random parameters in `config.dtype`."""
    import jax
    key = jax.random.PRNGKey(seed)
    return {name: draw_leaf(jax.random.fold_in(key, i), shape, kind,
                            scales).astype(
                "float32" if name in FLOAT32_LEAVES else config.dtype)
            for i, (name, (shape, kind)) in enumerate(
                sorted(param_shapes(config).items()))}


# ---------------------------------------------------------------------------
# the KDA layer: plain functions of (weights, activations, state)
# ---------------------------------------------------------------------------
def kda_project(w, c, h, taps):
    """h (.., n, d) over n positions with `taps` (.., K-1+n, 3 H d_k), the
    conv tail followed by the positions' own h W_qkv -> (q, k, v (.., n, H,
    d_k) float32, q and k normalised; g (.., n, H, d_k) the log-decay; beta
    (.., n, H))."""
    import jax
    import jax.numpy as jnp
    n, H, D = h.shape[-2], c.heads, c.head_dim
    cw = w["k_conv"].astype(jnp.float32)
    conv = sum(taps[..., i:i + n, :].astype(jnp.float32) * cw[i]
               for i in range(c.conv_kernel))
    q, k, v = (a.reshape(a.shape[:-1] + (H, D))
               for a in jnp.split(silu(conv), 3, -1))

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)

    f = jnp.dot(h, w["k_f"], preferred_element_type=jnp.float32) + w["k_bf"]
    g = c.kda_lower_bound * jax.nn.sigmoid(
        f.reshape(f.shape[:-1] + (H, D)) * jnp.exp(w["k_A"])[:, None])
    beta = jax.nn.sigmoid(jnp.dot(h, w["k_beta"],
                                  preferred_element_type=jnp.float32))
    return l2(q) * D ** -0.5, l2(k), v, g, beta


def kda_output(w, c, h, o):
    """o (.., H, d_v) float32 -> W_o [RMSNorm_head(o) * sigmoid(h W_g)]."""
    import jax
    import jax.numpy as jnp
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + c.norm_eps) \
        * w["k_onorm"].astype(jnp.float32)
    gate = jax.nn.sigmoid((h @ w["k_g"]).astype(jnp.float32))
    return (o.reshape(gate.shape) * gate).astype(h.dtype) @ w["k_o"]


def kda_step(q, k, v, g, beta, state):
    """The recurrence, one position: q, k, g (R, H, d_k), v (R, H, d_v),
    beta (R, H), state (R, H, d_k, d_v) float32 -> (o (R, H, d_v), state').
    Two passes over the state and no decayed copy of it: the decay meets q
    and k before they meet the state (S_d^T k = S^T (alpha k)), one pass
    reads it for both products at once, one rewrites it."""
    import jax.numpy as jnp
    alpha = jnp.exp(g)
    both = jnp.sum((jnp.stack([q, k], -2) * alpha[..., None, :])[..., None]
                   * state[..., None, :, :], -2)            # (R, H, 2, d_v)
    u = beta[..., None] * (v - both[..., 1, :])
    o = both[..., 0, :] + u * jnp.sum(q * k, -1, keepdims=True)
    return o, state * alpha[..., None] + k[..., None] * u[..., None, :]


def kda_chunk(q, k, v, g, beta, state, sub_chunk):
    """The chunkwise form over W positions a lane: q, k, g (B, W, H, d_k),
    v (B, W, H, d_v), beta (B, W, H), state (B, H, d_k, d_v) -> (o (B, W, H,
    d_v), state'), `CHUNK` positions a step (whole sub-chunks of them, and
    no more than W needs). A position with beta 0 and g 0 leaves the state
    as it is (a pad). float32 at `highest` precision throughout: the state
    is float32 and a bfloat16 pass of it would not be."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import solve_triangular
    B, W, H, dk = k.shape
    sub = sub_chunk
    C = min(-(-CHUNK // sub) * sub, -(-W // sub) * sub)
    pad = -W % C
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                    * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    N, A = (W + pad) // C, C // sub
    # (B, N, H, C, .): chunks apart, heads before positions
    q, k, v, g = (a.reshape(B, N, C, H, -1).swapaxes(2, 3)
                  for a in (q, k, v, g))
    beta = beta.reshape(B, N, C, H).swapaxes(2, 3)
    hi = jax.lax.Precision.HIGHEST
    # log Gamma_r in two parts, a sub-chunk's own cumulative sum and the
    # sum of the sub-chunks before it: what meets inside one sub-chunk is
    # then exact to the rounding of a sum of `sub` terms, not of C
    own = jnp.cumsum(g.reshape(g.shape[:-2] + (A, sub, dk)), -2)
    total = own[..., -1, :]                      # (B, N, H, A, dk)
    starts = jnp.cumsum(total, -2) - total
    gam = (starts[..., None, :] + own).reshape(g.shape)
    to_sub = jnp.exp(own).reshape(g.shape)       # in (exp(lb sub), 1]
    # K / Gamma against sub-chunk a's start, for every a: (B, N, H, A, C, dk);
    # positions after the sub-chunk are under the mask (clipped, not inf)
    since = starts[..., :, None, None, :] - starts[..., None, :, None, :] \
        - own[..., None, :, :, :]                # (B, N, H, A, A, sub, dk)
    k_inv = k[..., None, :, :] * jnp.exp(jnp.minimum(since, 85.0)).reshape(
        g.shape[:-2] + (A, C, dk))

    def against(x):
        """(x * Gamma)(K / Gamma)^T, a sub-chunk of rows at a time."""
        rows = (x * to_sub).reshape(x.shape[:-2] + (A, sub, dk))
        return jnp.einsum("...asd,...aid->...asi", rows, k_inv,
                          precision=hi).reshape(x.shape[:-2] + (C, C))

    r = jnp.arange(C)
    kk = jnp.where(r[:, None] > r[None, :], against(k), 0.0)
    qk = jnp.where(r[:, None] >= r[None, :], against(q), 0.0)
    T = solve_triangular(jnp.eye(C) + beta[..., None] * kk,
                         beta[..., None] * jnp.eye(C), lower=True,
                         unit_diagonal=True)
    decay = jnp.exp(gam)
    tv = jnp.einsum("...ri,...iv->...rv", T, v, precision=hi)
    tk = jnp.einsum("...ri,...id->...rd", T, k * decay, precision=hi)
    q_in = q * decay
    k_out = k * jnp.exp(gam[..., -1:, :] - gam)
    end = decay[..., -1, :]

    def step(s, xs):
        tv_n, tk_n, q_n, qk_n, k_n, end_n = xs
        u = tv_n - jnp.einsum("bhrd,bhdv->bhrv", tk_n, s, precision=hi)
        o = jnp.einsum("bhrd,bhdv->bhrv", q_n, s, precision=hi) \
            + jnp.einsum("bhri,bhiv->bhrv", qk_n, u, precision=hi)
        s = end_n[..., None] * s \
            + jnp.einsum("bhrd,bhrv->bhdv", k_n, u, precision=hi)
        return s, o

    state, o = jax.lax.scan(
        step, state, tuple(a.swapaxes(0, 1)
                           for a in (tv, tk, q_in, qk, k_out, end)))
    o = o.swapaxes(0, 1).swapaxes(2, 3).reshape(B, N * C, H, -1)
    return o[:, :W], state


# ---------------------------------------------------------------------------
# the three programs
# ---------------------------------------------------------------------------
_PREFIXES = {"kda": ("k_",), "mla": ("m_",), "dense": ("d_",),
             "sparse": ("r_", "s_")}
_MLA_NAMES = {"m_wq": "wq", "m_wkv_a": "wkv_a", "m_kv_norm": "kv_norm",
              "m_wkv_b": "wkv_b"}


def _weights(params, c, l):
    """Layer l's leaves: slices of the stacked tree (the MLA projections
    under the names `mla_project` reads)."""
    kinds = (c.mixer_types[l], c.mlp_types[l])
    w = {"ln1_w": params["ln1_w"][l], "ln2_w": params["ln2_w"][l]}
    for kind, i in zip(kinds, c.slots[l]):
        w.update({_MLA_NAMES.get(n, n): a[i] for n, a in params.items()
                  if n.startswith(_PREFIXES[kind])})
    if kinds[1] == "sparse":
        # every layer's held experts on one axis, this layer's from `e_row0`
        for n in ("e_gate_up", "e_down"):
            w[n] = params[n].reshape((-1,) + params[n].shape[2:])
        w["e_row0"] = c.slots[l][1] * c.held_count
    return w


def _make_chunk(config, window, extent, fresh):
    """The prefill step over one window-sized slice a lane, the MLA
    layers reading the cached positions [0, extent). `fresh` is the
    prefill at offset 0 (`prefill(params, cache, tokens, lengths,
    slot_rows)`, extent = window): every state and tail starts from zero.
    Else the chunk at an offset (`chunk_prefill(params, cache, tokens,
    offsets, nvalid, slot_rows)`): the lane's own are carried on. Lanes
    are PREFILL lanes with their pool rows as data; an idle lane carries
    the garbage row. Both return (cache, logits of each lane's last
    position, counters)."""
    import jax
    import jax.numpy as jnp
    c = config
    W, E = int(window), int(extent)
    if not 1 <= W <= E <= c.max_len:
        raise ServeError(f"chunk window {W} and extent {E} outside "
                         f"1 <= window <= extent <= max_len={c.max_len}")
    K1 = c.conv_kernel - 1

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
        with jax.named_scope("embed"):
            x = params["emb"][tokens]                            # (B, W, d)
        moe = jnp.zeros((7,), jnp.int32)
        for l in range(c.layers):
            i = c.slots[l][0]
            w = _weights(params, c, l)
            h = rms_norm(x, w["ln1_w"], c.norm_eps)
            if c.mixer_types[l] == "kda":
                with jax.named_scope(f"layer{l}/kda_proj"):
                    own = h @ w["k_qkv"]
                with jax.named_scope(f"layer{l}/kda_conv"):
                    tail = jnp.zeros((B, K1, own.shape[-1]), own.dtype) \
                        if fresh else cache[f"conv{i}"][rows]
                    taps = jnp.concatenate([tail, own], 1)
                    q, k, v, g, beta = kda_project(w, c, h, taps)
                    # the K-1 rows before the lane's first unwritten one
                    keep = nvalid[:, None] + jnp.arange(K1)[None, :]
                    cache[f"conv{i}"] = cache[f"conv{i}"].at[rows].set(
                        jnp.take_along_axis(taps, keep[..., None], axis=1))
                with jax.named_scope(f"layer{l}/kda_state"):
                    # a pad position leaves the state as it is
                    g = jnp.where(valid[..., None, None], g, 0.0)
                    beta = jnp.where(valid[..., None], beta, 0.0)
                    state = jnp.zeros(
                        (B, c.heads, c.head_dim, c.head_dim), jnp.float32) \
                        if fresh else cache[f"kda{i}"][rows]
                    o, state = kda_chunk(q, k, v, g, beta, state,
                                         c.sub_chunk)
                    cache[f"kda{i}"] = cache[f"kda{i}"].at[rows].set(state)
                with jax.named_scope(f"layer{l}/kda_proj"):
                    x = x + kda_output(w, c, h, o)
            else:
                with jax.named_scope(f"layer{l}/mla"):
                    _, q_nope, q_rope, ckr = mla_project(w, c, h, pos)
                    lat = cache[f"lat{i}"].at[wrows, wpos].set(ckr)
                    cache[f"lat{i}"] = lat
                    o = mla_read_rebuilt(q_nope, q_rope, lat[rows, :E], live,
                                         w["wkv_b"], c)
                    x = x + _head_gate(o, h, w, c) @ w["m_wo"]
            y, counted = _ffn(x.reshape(B * W, -1), w, c, l,
                              valid.reshape(B * W))
            x, moe = y.reshape(x.shape), moe + counted
        last = jnp.maximum(nvalid - 1, 0)
        logits = _head(params, x[jnp.arange(B), last], c)
        n_valid = jnp.sum(valid, dtype=jnp.int32)
        return cache, logits, {"moe": moe, "state": jnp.stack(
            [jnp.zeros((), jnp.int32), n_valid * c.n_kda])}

    if fresh:
        def prefill(params, cache, tokens, lengths, slot_rows):
            return core(params, cache, tokens, jnp.zeros_like(lengths),
                        lengths, slot_rows)
        return prefill

    def chunk_prefill(params, cache, tokens, offsets, nvalid, slot_rows):
        return core(params, cache, tokens, offsets, nvalid, slot_rows)
    return chunk_prefill


def _head_gate(o, h, w, c):
    """o (.., H * d_v) -> o_h sigmoid(h W_a)_h, head by head."""
    import jax
    import jax.numpy as jnp
    gate = jax.nn.sigmoid(jnp.dot(h, w["m_wa"],
                                  preferred_element_type=jnp.float32))
    shape = o.shape[:-1] + (c.heads, c.v_head_dim)
    return (o.reshape(shape) * gate[..., None].astype(o.dtype)).reshape(
        o.shape)


def _make_micro(config):
    """One token for every active lane, lane s = pool row s:
    `micro(params, cache, tokens, lengths, active) -> (cache, logits,
    counters)`. tokens (S,) the last emitted token, lengths (S,) the cache
    length (the new token's latent lands at position `lengths`); an idle
    lane writes the garbage row and keeps its state and tail."""
    import jax
    import jax.numpy as jnp
    c = config

    def micro(params, cache, tokens, lengths, active):
        cache = dict(cache)
        S = tokens.shape[0]

        def every(a):
            """A state leaf is read and rewritten whole, the garbage row
            too: one more row of zeros under a lane's inputs."""
            return jnp.pad(a, ((0, 1),) + ((0, 0),) * (a.ndim - 1))

        # (every equation runs under one of the program's scopes, the
        # lanes' bookkeeping and a layer's first norm too:
        # `profiler.program_scopes` names the device's time by them)
        with jax.named_scope("embed"):
            rows = jnp.where(active, jnp.arange(S), S)   # garbage row = S
            wpos = jnp.clip(lengths, 0, c.max_len - 1)
            x = params["emb"][tokens]                            # (S, d)
            keep = every(active)[:, None, None, None]
        moe = jnp.zeros((7,), jnp.int32)
        for l in range(c.layers):
            i = c.slots[l][0]
            kda = c.mixer_types[l] == "kda"
            with jax.named_scope(f"layer{l}/" + ("kda_proj" if kda
                                                 else "mla")):
                w = _weights(params, c, l)
                h = rms_norm(x, w["ln1_w"], c.norm_eps)
            if kda:
                with jax.named_scope(f"layer{l}/kda_proj"):
                    own = h @ w["k_qkv"]
                with jax.named_scope(f"layer{l}/kda_conv"):
                    old = cache[f"conv{i}"]
                    taps = jnp.concatenate(
                        [old[:S], own[:, None].astype(old.dtype)], 1)
                    q, k, v, g, beta = (a[:, 0] for a in kda_project(
                        w, c, h[:, None], taps))
                    cache[f"conv{i}"] = old.at[:S].set(jnp.where(
                        active[:, None, None], taps[:, 1:], old[:S]))
                with jax.named_scope(f"layer{l}/kda_state"):
                    old = cache[f"kda{i}"]
                    o, new = kda_step(*(every(a) for a in (q, k, v, g, beta)),
                                      old)
                    cache[f"kda{i}"] = jnp.where(keep, new, old)
                with jax.named_scope(f"layer{l}/kda_proj"):
                    x = x + kda_output(w, c, h, o[:S])
            else:
                with jax.named_scope(f"layer{l}/mla"):
                    _, q_nope, q_rope, ckr = mla_project(w, c, h, lengths)
                    lat = cache[f"lat{i}"].at[rows, wpos].set(ckr)
                    cache[f"lat{i}"] = lat
                    o = mla_read_absorbed(q_nope, q_rope, lat, None,
                                          w["wkv_b"], c, lengths=lengths)
                    x = x + _head_gate(o, h, w, c) @ w["m_wo"]
            x, moe = _ffn(x, w, c, l, active, moe)
        with jax.named_scope("head"):
            touched = jnp.sum(active, dtype=jnp.int32) * c.n_kda
        logits = _head(params, x, c)
        with jax.named_scope("head"):
            state = jnp.stack([touched, jnp.zeros((), jnp.int32)])
        return cache, logits, {"moe": moe, "state": state}

    return micro


class DeltaMoEDecoder(_sm.SparseMoEDecoder):
    """The model side of the continuous engine for the linear-attention,
    latent-attention, sparse-expert decoder: the fourth implementer of the
    engine's model protocol. The pool, the program table and
    `reference_generate` are `SparseMoEDecoder`'s; the initializer, the
    chunk and micro-step builders, the cache spec and the counters are this
    block's. `stats()["state"]`: `lane_layer_steps` (active lanes x KDA
    layers, a decode micro-step: times 4 H d_k d_v the float32 bytes of
    state that the step had to read and to write) and `chunk_positions`
    (valid positions x KDA layers that went through the chunkwise form)."""

    counters = {
        "moe": ("pairs_held", "experts_hit", "experts_offered",
                "max_load_sum", "groups_kept_here", "tokens_routed",
                "blocks"),
        "state": ("lane_layer_steps", "chunk_positions"),
    }
    _init_params = staticmethod(init_delta_moe_params)
    _make_chunk = staticmethod(_make_chunk)
    _make_micro = staticmethod(_make_micro)

    def cache_spec(self):
        """The cache leaves of one slot row: a matrix state and the conv
        tails a KDA layer (`state`), a latent entry an MLA layer (`full`)."""
        c = self.config
        H, D = c.heads, c.head_dim
        spec = []
        for i in range(c.n_kda):
            spec += [CacheLeaf(f"kda{i}", (H, D, D), "float32", "state", 0),
                     CacheLeaf(f"conv{i}", (c.conv_kernel - 1, 3 * H * D),
                               c.dtype, "state", 0)]
        return spec + [CacheLeaf(f"lat{j}", (c.max_len, c.lat_stored),
                                 c.dtype, "full", c.max_len)
                       for j in range(c.n_mla)]
