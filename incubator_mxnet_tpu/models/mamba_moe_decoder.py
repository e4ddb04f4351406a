"""A state-space, sparse-expert decoder for `serve.ContinuousEngine`: every
layer is ONE of a Mamba-2 mixer (SSD, arXiv:2405.21060), a layer of
sigmoid-routed relu^2 experts of which this process holds a contiguous share
with a shared expert, or grouped-query attention with no positional
encoding (the `nemotron_h` block of NVIDIA-Nemotron-3-Nano-30B-A3B).

    x_0 = E[token];  x <- x + F_l(RMSNorm(x))   F_l by `pattern[l]`
    logits = RMSNorm(x) W_head                  (float32, untied from E)

`pattern[l]` is `M` (Mamba-2), `E` (experts) or `*` (attention), the
config's `hybrid_override_pattern`. With h = RMSNorm(x), d_inner = H P:

  M  [z | xBC | dt] = h W_in (d_inner, d_inner + 2 G N, H wide); xBC <-
     SiLU(causal depthwise conv of `conv_kernel` taps + bias) over every
     channel (the last K-1 pre-convolution rows are the CONV TAIL);
     [x | B | C] = xBC with x (H, P), B, C (G, N): head h reads group
     h // (H / G). dt <- softplus(dt + dt_bias) a head, A_h = -exp(A_log_h).
     The state S (P, N) a head, float32:
       S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D_h x_t
     F = W_out GroupRMSNorm(y * silu(z)): the norm over each of G groups of
     d_inner / G values, after the gate, one weight of d_inner
     (`gated_group_rms_norm`). A decode step is the recurrence itself
     (`mamba2_step`); a prefill chunk is its CHUNKED form (`mamba2_chunk`,
     `chunk_size` positions a chunk): with s_t the cumulative sum of dt A
     inside a chunk, Y = (C B^T o L)(dt x) + exp(s) C S_prev within it,
     L[t, r] = exp(s_t - s_r) for r <= t, and the state passed on is
     exp(s_Q) S_prev + sum_r exp(s_Q - s_r) dt_r x_r (x) B_r.
  E  sigma = sigmoid(h W_r) float32; chosen = top-k of sigma + b (the bias
     moves the choice only); g_i = scale sigma_i / sum_chosen sigma;
     F = sum_{chosen and held} g_i W_down,i relu(W_up,i h)^2 + the shared
     expert W_down,s relu(W_up,s h)^2 (`sparse_moe_decoder.route`,
     `routed_experts` with its relu^2 body): the share of the published sum
     that one process of an expert-parallel deployment computes.
  *  q, k, v = h W_q, h W_k, h W_v (`heads` query heads over `kv_heads` KV
     heads of `head_dim`, no bias, no rotary); causal softmax at
     head_dim^-1/2; W_o. A decode step and a chunk at an offset read the
     cache through `ops.fused.paged_attention`'s leaf mode.

Per Mamba layer i the cache holds `ssm{i}` (H, P, N) float32 and `conv{i}`
(K-1, d_inner + 2 G N), both `state` leaves; per attention layer j `k{j}`
and `v{j}` (max_len, kv_heads * head_dim), `full` leaves.

What a program may assume, and what it sees to:
  * the prefill at offset 0 never READS a state or a tail (it starts both
    from zero) and writes them whole; a chunk at an offset carries the
    lane's own on (tests poison-fill every leaf).
  * a position past a lane's valid ones has dt = 0: it neither decays nor
    feeds the state, and it is kept out of the new conv tail.
  * a decode step rewrites every row of a state leaf in place, the garbage
    row too, from lane inputs padded by one row of zeros: an idle lane's dt
    is 0, so its row keeps what it held, bit for bit.
  * the state, dt, the router's scores, softmax and logits are float32
    whatever the weights' type.
"""
from __future__ import annotations

import math

from ..serve.batcher import ServeError
from ..serve.kv_pool import CacheLeaf
from . import sparse_moe_decoder as _sm
from .hybrid_decoder import silu
from .sparse_moe_decoder import (ExpertUp, _head, draw_leaf, rms_norm,
                                 route, routed_experts)

__all__ = ["MambaMoEConfig", "MambaMoEDecoder", "init_mamba_moe_params",
           "param_shapes", "draw_mamba_leaf", "mamba2_project",
           "mamba2_step", "mamba2_chunk", "gated_group_rms_norm"]

#: the layer kinds by their letter in `pattern`
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}
#: queries a lane of the chunk program's paged read: a chunk of W positions
#: reads as W / CHUNK_QUERIES lanes of this many queries (8 x 32 heads are
#: 256 rows of a 256-wide query tile at the served widths)
CHUNK_QUERIES = 8


class MambaMoEConfig:
    """Static shape record (ints, floats and strings; nothing here ever
    becomes a tracer). `pattern` holds one letter a layer (`KINDS`);
    `held_first`, `held_count` say which of the `routed_experts` this
    process holds."""

    FIELDS = ("vocab", "embed", "pattern", "mamba_heads", "mamba_head_dim",
              "n_groups", "ssm_state", "conv_kernel", "chunk_size", "heads",
              "kv_heads", "head_dim", "expert_hidden", "shared_hidden",
              "routed_experts", "experts_per_token", "routed_scaling_factor",
              "held_first", "held_count", "max_len", "dtype", "norm_eps")
    #: `route` without a group limit (`n_group` 1 in the published config)
    n_group = topk_group = 1

    def __init__(self, vocab=128, embed=64, pattern="ME*EME",
                 mamba_heads=8, mamba_head_dim=8, n_groups=2, ssm_state=16,
                 conv_kernel=4, chunk_size=8, heads=4, kv_heads=2,
                 head_dim=16, expert_hidden=32, shared_hidden=64,
                 routed_experts=16, experts_per_token=2,
                 routed_scaling_factor=2.5, held_first=0, held_count=4,
                 max_len=64, dtype="float32", norm_eps=1e-5):
        for k in self.FIELDS:
            setattr(self, k, locals()[k])
        self.pattern = str(pattern)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_eps = float(norm_eps)
        self.dtype = str(dtype)
        if set(self.pattern) - set(KINDS):
            raise ServeError(f"pattern holds {sorted(KINDS)} only, one a "
                             f"layer: {self.pattern!r}")
        if "*" not in self.pattern:
            raise ServeError("at least one attention layer (`*`): its `full` "
                             "leaves are where the pool learns a request's "
                             "length")
        if self.mamba_heads % self.n_groups or self.heads % self.kv_heads:
            raise ServeError("n_groups divides mamba_heads and kv_heads "
                             "divides heads")
        if self.d_inner % self.n_groups:
            raise ServeError("the gated norm's groups divide d_inner")
        if not (0 <= self.held_first and self.held_count >= 1
                and self.held_first + self.held_count
                <= self.routed_experts):
            raise ServeError(
                f"held experts [{self.held_first}, "
                f"{self.held_first + self.held_count}) outside "
                f"[0, {self.routed_experts})")
        if not 1 <= self.experts_per_token <= self.routed_experts:
            raise ServeError("experts_per_token outside [1, routed_experts]")

    layers = property(lambda self: len(self.pattern))
    kinds = property(lambda self: tuple(KINDS[p] for p in self.pattern))
    n_mamba = property(lambda self: self.pattern.count("M"))
    n_attn = property(lambda self: self.pattern.count("*"))
    n_moe = property(lambda self: self.pattern.count("E"))
    d_inner = property(lambda self: self.mamba_heads * self.mamba_head_dim)
    conv_dim = property(lambda self: self.d_inner
                        + 2 * self.n_groups * self.ssm_state)
    kv_width = property(lambda self: self.kv_heads * self.head_dim)

    @property
    def slots(self):
        """The index of each layer among the layers of its kind."""
        out, n = [], {k: 0 for k in KINDS.values()}
        for kind in self.kinds:
            out.append(n[kind])
            n[kind] += 1
        return out

    def as_dict(self):
        return {k: getattr(self, k) for k in self.FIELDS}


#: the initializer's scales by kind of leaf (`param_shapes`): standard
#: deviations of normals, (low, high) of uniform draws, and Mamba-2's own
#: initial values: `A_log` = log U[low, high], `dt_bias` the inverse
#: softplus of dt log-uniform in [low, high] floored at the third value
INIT_SCALES = {"normal": 0.02, "emb": 0.02, "o": 0.02, "down": 0.02,
               "router": 0.02, "router_bias": 0.02, "conv": (-0.5, 0.5),
               "A_log": (1.0, 16.0), "dt_bias": (1e-3, 1e-1, 1e-4)}


def param_shapes(c):
    """name -> (shape, kind of initial value): the leaves, stacked on a
    leading axis over the layers of their kind (`m_*`: the Mamba mixers,
    `a_*`: the attention layers, `r_*`, `e_*`, `s_*`: the expert layers,
    `e_*` over the experts HELD too). The one table of the model's leaves.
    `norm` is every layer's pre-norm; `m_in` is [W_z | W_xBC | W_dt];
    `e_up` holds W_up^T an expert, (F, d), as `routed_experts`' relu^2
    body reads it (at F = 1856, not whole lane tiles, the (d, F) form was
    stored with d innermost and copied whole at every program call: 1.84
    GB, found by the compile for a described v5e)."""
    L, d, V = c.layers, c.embed, c.vocab
    H, Di, K = c.mamba_heads, c.d_inner, c.conv_kernel
    nm, na, ne = c.n_mamba, c.n_attn, c.n_moe
    F, Fs, E, held = (c.expert_hidden, c.shared_hidden, c.routed_experts,
                      c.held_count)
    return {
        "emb": ((V, d), "emb"), "head": ((d, V), "normal"),
        "lnf_w": ((d,), "ones"), "norm": ((L, d), "ones"),
        "m_in": ((nm, d, Di + c.conv_dim + H), "normal"),
        "m_conv": ((nm, K, c.conv_dim), "conv"),
        "m_conv_b": ((nm, c.conv_dim), "conv"),
        "m_dt_bias": ((nm, H), "dt_bias"), "m_A_log": ((nm, H), "A_log"),
        "m_D": ((nm, H), "ones"), "m_norm": ((nm, Di), "ones"),
        "m_out": ((nm, Di, d), "o"),
        "a_q": ((na, d, c.heads * c.head_dim), "normal"),
        "a_k": ((na, d, c.kv_width), "normal"),
        "a_v": ((na, d, c.kv_width), "normal"),
        "a_o": ((na, c.heads * c.head_dim, d), "o"),
        "r_w": ((ne, d, E), "router"), "r_b": ((ne, E), "router_bias"),
        "e_up": ((ne, held, F, d), "normal"),
        "e_down": ((ne, held, F, d), "down"),
        "s_up": ((ne, d, Fs), "normal"), "s_down": ((ne, Fs, d), "down"),
    }


#: leaves kept in float32 whatever `config.dtype` is
FLOAT32_LEAVES = ("r_b", "m_dt_bias", "m_A_log", "m_D")


def draw_mamba_leaf(key, shape, kind, scales=INIT_SCALES):
    """`draw_leaf`, and Mamba-2's own initial values of A_log and dt_bias."""
    import jax
    import jax.numpy as jnp
    if kind == "A_log":
        low, high = scales[kind]
        return jnp.log(jax.random.uniform(key, shape, minval=low,
                                          maxval=high))
    if kind == "dt_bias":
        low, high, floor = scales[kind]
        dt = jnp.exp(jax.random.uniform(key, shape) * (
            math.log(high) - math.log(low)) + math.log(low))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1
    return draw_leaf(key, shape, kind, scales)


def init_mamba_moe_params(config, seed=0, scales=INIT_SCALES):
    """Deterministic random parameters in `config.dtype`."""
    import jax
    key = jax.random.PRNGKey(seed)
    return {name: draw_mamba_leaf(jax.random.fold_in(key, i), shape, kind,
                                  scales).astype(
                "float32" if name in FLOAT32_LEAVES else config.dtype)
            for i, (name, (shape, kind)) in enumerate(
                sorted(param_shapes(config).items()))}


# ---------------------------------------------------------------------------
# the Mamba-2 mixer: plain functions of (weights, activations, state)
# ---------------------------------------------------------------------------
def mamba2_project(w, c, h, tail):
    """h (.., n, d) over n positions after `tail` (.., K-1, conv_dim), the
    rows of xBC before them (the cache's) -> (z (.., n, d_inner), taps (..,
    K-1+n, conv_dim) the tail then the positions' own xBC, x (.., n, H, P),
    B, C (.., n, G, N), dt (.., n, H) softplus'd, all float32). The
    positions' own rows meet the convolution as the in-projection's float32
    accumulators, not rounded to the weights' type: that rounding is most
    of what keeps a program's state from the float32 recurrence's (0.0032
    against 0.0025 of the state's norm at the served widths, a bfloat16
    state 0.0047; CPU, one Mamba layer, 512 positions); the tail a later
    program reads is the cache's. The in-projection under scope `proj`,
    the convolution under `conv`."""
    import jax
    import jax.numpy as jnp
    n, H, G = h.shape[-2], c.mamba_heads, c.n_groups
    Di, GN = c.d_inner, c.n_groups * c.ssm_state
    with jax.named_scope("proj"):
        zxd = jnp.dot(h, w["m_in"], preferred_element_type=jnp.float32)
        z = zxd[..., :Di]
        own = zxd[..., Di:Di + c.conv_dim]
        dt = jax.nn.softplus(zxd[..., Di + c.conv_dim:] + w["m_dt_bias"])
    with jax.named_scope("conv"):
        taps = jnp.concatenate([tail.astype(jnp.float32), own], -2)
        cw = w["m_conv"].astype(jnp.float32)
        xbc = silu(sum(taps[..., i:i + n, :] * cw[i]
                       for i in range(c.conv_kernel))
                   + w["m_conv_b"].astype(jnp.float32))
        lead = xbc.shape[:-1]
        x = xbc[..., :Di].reshape(lead + (H, c.mamba_head_dim))
        B = xbc[..., Di:Di + GN].reshape(lead + (G, c.ssm_state))
        C = xbc[..., Di + GN:].reshape(lead + (G, c.ssm_state))
    return z, taps, x, B, C, dt


def mamba2_step(x, dt, A, B, C, D, state):
    """The recurrence, one position: x (R, H, P), dt (R, H), A, D (H,), B,
    C (R, G, N), state (R, H, P, N) float32 -> (y (R, H, P), state'). One
    pass reads the state and one rewrites it, with no second state-sized
    array: y comes from the state read, S' C = a (S C) + dt x (B . C)."""
    import jax.numpy as jnp
    R, H, P = x.shape
    G, N = B.shape[-2:]
    s = state.reshape(R, G, H // G, P, N)
    a = jnp.exp(dt * A).reshape(R, G, H // G)
    u = (dt[..., None] * x).reshape(R, G, H // G, P)
    sc = jnp.sum(s * C[:, :, None, None, :], -1)              # (R, G, hg, P)
    bc = jnp.sum(B * C, -1)[:, :, None, None]                   # (R, G, 1, 1)
    y = (a[..., None] * sc + u * bc).reshape(R, H, P) + D[:, None] * x
    new = s * a[..., None, None] + u[..., None] * B[:, :, None, None, :]
    return y, new.reshape(state.shape)


def mamba2_chunk(x, dt, A, B, C, D, state, chunk):
    """The chunked (SSD) form over W positions a lane: x (L, W, H, P), dt
    (L, W, H) (0 at a pad: it neither decays nor feeds the state), B, C
    (L, W, G, N), state (L, H, P, N) -> (y (L, W, H, P), state'), `chunk`
    positions a chunk (the window padded to whole chunks). Within a chunk
    the products of a masked matrix: L[t, r] = exp(s_t - s_r) for r <= t,
    s the cumulative sum of dt A, built from those sums (never as a ratio
    of exponentials, which overflows) and masked BEFORE the exponential;
    between chunks the state, passed on by a scan. float32 at `highest`
    precision throughout: the state is float32 and a bfloat16 pass of it
    would not be."""
    import jax
    import jax.numpy as jnp
    Lb, W, H, P = x.shape
    G, N = B.shape[-2:]
    Q = min(int(chunk), W)
    pad = -W % Q
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                               * (a.ndim - 2)) for a in (x, dt, B, C))
    nc = (W + pad) // Q
    hi = jax.lax.Precision.HIGHEST
    # (L, nc, Q, ..): chunks apart; heads by group
    x = x.reshape(Lb, nc, Q, G, H // G, P)
    dt = dt.reshape(Lb, nc, Q, G, H // G)
    B = B.reshape(Lb, nc, Q, G, N)
    C = C.reshape(Lb, nc, Q, G, N)
    s = jnp.cumsum(dt * A.reshape(G, H // G), 2)       # (L, nc, Q, G, hg)
    u = dt[..., None] * x                              # (L, nc, Q, G, hg, P)
    t = jnp.arange(Q)
    causal = t[:, None] >= t[None, :]
    seg = jnp.moveaxis(s, 2, -1)                       # (L, nc, G, hg, Q)
    diff = seg[..., :, None] - seg[..., None, :]       # s_t - s_r
    Lm = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)), 0.0)
    cb = jnp.einsum("lctgn,lcrgn->lcgtr", C, B, precision=hi)
    y = jnp.einsum("lcgjtr,lcrgjp->lctgjp", cb[:, :, :, None] * Lm, u,
                   precision=hi)
    # each chunk's own part of the state at its end, and its decay
    to_end = jnp.exp(s[:, :, -1:] - s)
    own = jnp.einsum("lcrgjp,lcrgn->lcgjpn", u * to_end[..., None], B,
                     precision=hi)
    decay = jnp.exp(s[:, :, -1])                       # (L, nc, G, hg)
    s0 = state.reshape(Lb, G, H // G, P, N)

    def step(S, xs):
        own_c, dec_c = xs
        return dec_c[..., None, None] * S + own_c, S

    end, before = jax.lax.scan(step, s0, (jnp.moveaxis(own, 1, 0),
                                          jnp.moveaxis(decay, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                # (L, nc, G, hg, P, N)
    y = y + jnp.exp(s)[..., None] * jnp.einsum(
        "lctgn,lcgjpn->lctgjp", C, before, precision=hi)
    y = y.reshape(Lb, nc * Q, H, P)[:, :W]
    return y + D[:, None] * x.reshape(Lb, nc * Q, H, P)[:, :W], \
        end.reshape(state.shape)


def gated_group_rms_norm(y, z, w, groups, eps):
    """RMSNorm over each of `groups` groups of y * silu(z) (the gate
    first), times w. y, z (.., d_inner) float32."""
    import jax
    import jax.numpy as jnp
    g = y * silu(z)
    gs = g.reshape(g.shape[:-1] + (groups, -1))
    gs = gs * jax.lax.rsqrt(jnp.mean(gs * gs, -1, keepdims=True) + eps)
    return gs.reshape(g.shape) * w.astype(jnp.float32)


def _mamba_out(w, c, y, z, like):
    """y (.., H, P) float32, z (.., d_inner) -> W_out GroupRMSNorm(y *
    silu(z)) in `like`'s type."""
    y = y.reshape(y.shape[:-2] + (c.d_inner,))
    return gated_group_rms_norm(y, z, w["m_norm"], c.n_groups,
                                c.norm_eps).astype(like.dtype) @ w["m_out"]


def _relu2_mlp(h, w_up, w_down):
    import jax.numpy as jnp
    return jnp.square(jnp.maximum(h @ w_up, 0)) @ w_down


def _relu2(u):
    import jax.numpy as jnp
    return jnp.square(jnp.maximum(u, 0))


#: a routed expert's activation relu(x W_up)^2: x (R, d), w_t (F, d), W_up
#: stored transposed (a last axis of 1856 is not whole lane tiles: stored
#: (d, F) the stack is laid with d innermost and copied whole at every call)
relu2_up = ExpertUp(_relu2, transposed=True)


# ---------------------------------------------------------------------------
# the three programs
# ---------------------------------------------------------------------------
_PREFIX = {"mamba": "m_", "attn": "a_", "moe": ("r_", "s_")}
#: the scope a layer's weight slices and pre-norm run under, by kind
_FIRST_SCOPE = {"mamba": "mamba/proj", "attn": "attn", "moe": "router"}


def _weights(params, c, l):
    """Layer l's leaves: slices of the stacked tree, the pre-norm as
    `norm`; an expert layer gets every layer's held experts on one axis
    and its own first row of them as `e_row0`."""
    kind, i = c.kinds[l], c.slots[l]
    w = {n: a[i] for n, a in params.items() if n.startswith(_PREFIX[kind])}
    w["norm"] = params["norm"][l]
    if kind == "moe":
        for n in ("e_up", "e_down"):
            w[n] = params[n].reshape((-1,) + params[n].shape[2:])
        w["e_row0"] = i * c.held_count
    return w


def _moe(x, w, c, l, token_ok, total):
    """x (T, d) -> (x + the layer's routed share and its shared expert,
    total + moe counters (5,) int32: token-expert pairs on held experts,
    held experts that received a token, held experts offered, the largest
    load, the blocks of rows the grouped matmul walked)."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope(f"layer{l}/router"):
        h = rms_norm(x, w["norm"], c.norm_eps)
        idx, gates = route(h, w["r_w"], w["r_b"], c)
    with jax.named_scope(f"layer{l}/experts"):
        y, loads, blocks = routed_experts(h, idx, gates, token_ok,
                                          w["e_up"], w["e_down"],
                                          c.held_first, c.held_count,
                                          w["e_row0"], up=relu2_up)
    with jax.named_scope(f"layer{l}/shared_expert"):
        y = y + _relu2_mlp(h, w["s_up"], w["s_down"])
    with jax.named_scope(f"layer{l}/experts"):
        any_token = jnp.any(token_ok).astype(jnp.int32)
        counted = jnp.stack([jnp.sum(loads),
                             jnp.sum(loads > 0, dtype=jnp.int32),
                             c.held_count * any_token, jnp.max(loads),
                             blocks])
    with jax.named_scope(f"layer{l}/shared_expert"):
        return x + y, total + counted


def _qkv(w, c, h):
    """h (.., d) -> q (.., heads, head_dim), and the position's cache
    entries k, v (.., kv_heads * head_dim). No rotary."""
    q = h @ w["a_q"]
    return (q.reshape(h.shape[:-1] + (c.heads, c.head_dim)), h @ w["a_k"],
            h @ w["a_v"])


def _make_chunk(config, window, extent, fresh):
    """The prefill step over one window-sized slice a lane. `fresh` is the
    prefill at offset 0 (`prefill(params, cache, tokens, lengths,
    slot_rows)`): states and tails start from zero and attention is the
    window's own causal product. Else the chunk at an offset
    (`chunk_prefill(params, cache, tokens, offsets, nvalid, slot_rows)`):
    the lane's state and tail are carried on, and its queries read the
    cache through the paged kernel as W / CHUNK_QUERIES lanes of
    CHUNK_QUERIES queries. Lanes are PREFILL lanes with their pool rows as
    data; an idle lane carries the garbage row. Both return (cache, logits
    of each lane's last position, counters). `extent` bounds nothing: the
    read follows the live blocks."""
    import jax
    import jax.numpy as jnp
    from ..ops import fused as _fused
    c = config
    W, T = int(window), c.max_len
    if not 1 <= W <= T:
        raise ServeError(f"prefill window {W} outside [1, max_len={T}]")
    K1 = c.conv_kernel - 1
    H, D, Hk = c.heads, c.head_dim, c.kv_heads
    Q = math.gcd(W, CHUNK_QUERIES)
    scale = 1.0 / math.sqrt(D)

    def core(params, cache, tokens, offsets, nvalid, rows):
        cache = dict(cache)
        B = tokens.shape[0]
        with jax.named_scope("embed"):
            G = cache["k0"].shape[0] - 1                 # garbage row
            j = jnp.arange(W)
            # an idle lane carries the garbage row (and whatever length)
            valid = (j[None, :] < nvalid[:, None]) & (rows != G)[:, None]
            wrows = jnp.where(valid, rows[:, None], G)
            pos = offsets[:, None] + j[None, :]
            wpos = jnp.clip(pos, 0, T - 1)
            x = params["emb"][tokens]                            # (B, W, d)
            if fresh:
                mask = (j[:, None] >= j[None, :])[None] & valid[:, None, :]
            else:
                # lane (b, i) holds queries i * Q .. i * Q + Q - 1 of lane b
                first = (offsets[:, None]
                         + jnp.arange(0, W, Q)[None, :]).reshape(-1)
                lane_rows = jnp.repeat(rows, W // Q)
        moe = jnp.zeros((5,), jnp.int32)
        for l, kind in enumerate(c.kinds):
            i = c.slots[l]
            with jax.named_scope(f"layer{l}/{_FIRST_SCOPE[kind]}"):
                w = _weights(params, c, l)
            if kind == "mamba":
                with jax.named_scope(f"layer{l}/mamba"):
                    with jax.named_scope("proj"):
                        h = rms_norm(x, w["norm"], c.norm_eps)
                    tail = jnp.zeros((B, K1, c.conv_dim), h.dtype) \
                        if fresh else cache[f"conv{i}"][rows]
                    z, taps, xs, Bm, Cm, dt = mamba2_project(w, c, h, tail)
                    with jax.named_scope("conv"):
                        # the K-1 rows before the lane's first unwritten one
                        keep = nvalid[:, None] + jnp.arange(K1)[None, :]
                        leaf = cache[f"conv{i}"]
                        cache[f"conv{i}"] = leaf.at[rows].set(
                            jnp.take_along_axis(taps, keep[..., None],
                                                1).astype(leaf.dtype))
                    with jax.named_scope("state"):
                        dt = jnp.where(valid[..., None], dt, 0.0)
                        state = jnp.zeros(
                            (B, c.mamba_heads, c.mamba_head_dim,
                             c.ssm_state), jnp.float32) \
                            if fresh else cache[f"ssm{i}"][rows]
                        y, state = mamba2_chunk(
                            xs, dt, -jnp.exp(w["m_A_log"]), Bm, Cm, w["m_D"],
                            state, c.chunk_size)
                        cache[f"ssm{i}"] = cache[f"ssm{i}"].at[rows].set(
                            state)
                    with jax.named_scope("out"):
                        x = x + _mamba_out(w, c, y, z, x)
            elif kind == "attn":
                with jax.named_scope(f"layer{l}/attn"):
                    h = rms_norm(x, w["norm"], c.norm_eps)
                    q, k, v = _qkv(w, c, h)
                    kc = cache[f"k{i}"].at[wrows, wpos].set(k)
                    vc = cache[f"v{i}"].at[wrows, wpos].set(v)
                    cache[f"k{i}"], cache[f"v{i}"] = kc, vc
                    if fresh:
                        qg = q.reshape(B, W, Hk, H // Hk, D)
                        sco = jnp.einsum(
                            "bqkgd,bskd->bkgqs", qg, k.reshape(B, W, Hk, D),
                            preferred_element_type=jnp.float32) * scale
                        p = jax.nn.softmax(
                            jnp.where(mask[:, None, None], sco, -1e30), -1)
                        att = jnp.einsum("bkgqs,bskd->bqkgd", p,
                                         v.reshape(B, W, Hk, D))
                    else:
                        att = _fused.paged_attention(
                            q.reshape(B * (W // Q), Q, H, D), kc, vc, first,
                            None, rows=lane_rows, scale=scale)
                    x = x + att.reshape(B, W, H * D).astype(x.dtype) \
                        @ w["a_o"]
            else:
                y, moe = _moe(x.reshape(B * W, -1), w, c, l,
                              valid.reshape(B * W), moe)
                x = y.reshape(x.shape)
        with jax.named_scope("head"):
            last = x[jnp.arange(B), jnp.maximum(nvalid - 1, 0)]
            n_valid = jnp.sum(valid, dtype=jnp.int32)
            counted = jnp.stack([jnp.zeros((), jnp.int32),
                                 n_valid * c.n_mamba])
        return cache, _head(params, last, c), {"moe": moe, "state": counted}

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
    length (the new token's K and V land at position `lengths`); an idle
    lane writes K and V to the garbage row and keeps its state and tail."""
    import jax
    import jax.numpy as jnp
    from ..ops import fused as _fused
    c = config
    scale = 1.0 / math.sqrt(c.head_dim)

    def micro(params, cache, tokens, lengths, active):
        cache = dict(cache)
        S = tokens.shape[0]

        def every(a):
            """A state leaf is rewritten whole, the garbage row too: one
            more row of zeros under a lane's inputs (dt 0: kept)."""
            return jnp.pad(a, ((0, 1),) + ((0, 0),) * (a.ndim - 1))

        # (every equation runs under one of the program's scopes, the
        # lanes' bookkeeping and a layer's norm too:
        # `profiler.program_scopes` names the device's time by them)
        with jax.named_scope("embed"):
            rows = jnp.where(active, jnp.arange(S), S)   # garbage row = S
            wpos = jnp.clip(lengths, 0, c.max_len - 1)
            x = params["emb"][tokens]                            # (S, d)
        moe = jnp.zeros((5,), jnp.int32)
        for l, kind in enumerate(c.kinds):
            i = c.slots[l]
            with jax.named_scope(f"layer{l}/{_FIRST_SCOPE[kind]}"):
                w = _weights(params, c, l)
            if kind == "mamba":
                with jax.named_scope(f"layer{l}/mamba"):
                    with jax.named_scope("proj"):
                        h = rms_norm(x, w["norm"], c.norm_eps)
                    old = cache[f"conv{i}"]
                    z, taps, xs, Bm, Cm, dt = mamba2_project(
                        w, c, h[:, None], old[:S])
                    with jax.named_scope("conv"):
                        cache[f"conv{i}"] = old.at[:S].set(jnp.where(
                            active[:, None, None],
                            taps[:, 1:].astype(old.dtype), old[:S]))
                    with jax.named_scope("state"):
                        dt = jnp.where(active[:, None], dt[:, 0], 0.0)
                        y, cache[f"ssm{i}"] = mamba2_step(
                            *(every(a) for a in (xs[:, 0], dt)),
                            -jnp.exp(w["m_A_log"]),
                            *(every(a[:, 0]) for a in (Bm, Cm)), w["m_D"],
                            cache[f"ssm{i}"])
                    with jax.named_scope("out"):
                        x = x + _mamba_out(w, c, y[:S], z[:, 0], x)
            elif kind == "attn":
                with jax.named_scope(f"layer{l}/attn"):
                    h = rms_norm(x, w["norm"], c.norm_eps)
                    q, k, v = _qkv(w, c, h)
                    kc = cache[f"k{i}"].at[rows, wpos].set(k)
                    vc = cache[f"v{i}"].at[rows, wpos].set(v)
                    cache[f"k{i}"], cache[f"v{i}"] = kc, vc
                    att = _fused.paged_attention(q[:, None], kc, vc, lengths,
                                                 None, rows=rows, scale=scale)
                    x = x + att.reshape(S, -1).astype(x.dtype) @ w["a_o"]
            else:
                x, moe = _moe(x, w, c, l, active, moe)
        with jax.named_scope("head"):
            touched = jnp.sum(active, dtype=jnp.int32) * c.n_mamba
            state = jnp.stack([touched, jnp.zeros((), jnp.int32)])
        return cache, _head(params, x, c), {"moe": moe, "state": state}

    return micro


class MambaMoEDecoder(_sm.SparseMoEDecoder):
    """The model side of the continuous engine for the state-space,
    sparse-expert decoder: the sixth implementer of the engine's model
    protocol. The pool, the program table, the decode scan and
    `reference_generate` are `SparseMoEDecoder`'s; the initializer, the
    functions that make the chunk and micro-step programs, the cache spec
    and the counters are this block's. `stats()["state"]`: `lane_layer_steps` (active lanes x Mamba
    layers, a decode micro-step: times 2 x 4 H P N the float32 bytes of
    state that the step had to read and to write) and `chunk_positions`
    (valid positions x Mamba layers that went through the chunked form)."""

    counters = {
        "moe": ("pairs_held", "experts_hit", "experts_offered",
                "max_load_sum", "blocks"),
        "state": ("lane_layer_steps", "chunk_positions"),
    }
    _init_params = staticmethod(init_mamba_moe_params)
    _make_chunk = staticmethod(_make_chunk)
    _make_micro = staticmethod(_make_micro)

    def cache_spec(self):
        """The cache leaves of one slot row: a state and a conv tail a
        Mamba layer (`state`), K and V an attention layer (`full`)."""
        c = self.config
        spec = []
        for i in range(c.n_mamba):
            spec += [CacheLeaf(f"ssm{i}", (c.mamba_heads, c.mamba_head_dim,
                                           c.ssm_state), "float32", "state",
                               0),
                     CacheLeaf(f"conv{i}", (c.conv_kernel - 1, c.conv_dim),
                               c.dtype, "state", 0)]
        for j in range(c.n_attn):
            spec += [CacheLeaf(f"{n}{j}", (c.max_len, c.kv_width), c.dtype,
                               "full", c.max_len) for n in ("k", "v")]
        return spec

    def chunk_prefill_program(self, window, extent=None):
        """`extent` is the bound of a dense read; the read here is the
        paged kernel's, on the live-block grid, and the state needs none,
        so every extent is the one program."""
        w = int(window)
        return self._program(
            ("chunk", w),
            lambda: self._make_chunk(self.config, w, self.config.max_len,
                                     False),
            f"chunk_prefill[w={w}]")
