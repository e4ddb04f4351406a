"""A hybrid decoder for `serve.ContinuousEngine`: state-space layers,
sliding-window attention, ONE full-attention cache that every later
attention layer reads, gated memory units, differential attention (the
SambaY decoder-hybrid-decoder of arXiv:2507.06607, with the differential
attention of arXiv:2410.05258 and the Mamba-1 mixer of arXiv:2312.00752).

    x_0 = E[token]                         (no positional term anywhere)
    x <- x + Mix_l(LN(x));  x <- x + MLP_l(LN(x));  logits = LN_f(x) E^T

Layer kinds by index (`layer_kinds`; L divisible by 4) and the cache leaf
each owns (`HybridDecoder.cache_spec`, handed to `serve.KVCachePool`):

  even l <= L/2      Mamba-1 mixer (layer L/2 also emits the memory m)
                     `ssm{i}` (N, d_in) float32 + `conv{i}` (K-1, d_in): state
  odd  l <  L/2      attention over its own K/V under a window
                     `ring_k{r}` / `ring_v{r}` (window, Hkv*D): ring
  l == L/2 + 1       attention over its own K/V, full causal
                     `shared_k` / `shared_v` (max_len, Hkv*D): full
  odd  l >= L/2 + 3  attention, query only, over `shared_k` / `shared_v`
  even l >= L/2 + 2  gated memory unit over m (nothing cached: it needs
                     the current token's m only)

The layer functions below are plain functions of (weights, activations,
cache leaves, lengths); `HybridDecoder` builds the engine's three programs
from them (`prefill`, `chunk_prefill`, `decode`: fixed shapes, donated
cache, lanes as data) and is the second implementer of the engine's model
protocol beside `serve.CachedDecoder`.

What a program may assume, and what it sees to:
  * a claimed slot's recurrent state and rings start from zero because the
    prefill at offset 0 never READS them: it scans from a zero state and
    writes the result (tests poison-fill every leaf to show it). A chunk at
    an offset > 0 carries the lane's state on.
  * a window layer reads its ring BEFORE it writes the chunk: writing first
    would destroy keys that the chunk's first queries still see. The chunk
    must fit the ring (`prefill_window <= window`).
  * prefill runs layer L/2+1's attention and the layers above it for each
    lane's LAST position only (those layers keep no cache of their own, so
    every logit the engine uses is the full forward's); the other positions
    go through layers 0..L/2 and layer L/2+1's K/V projection.
  * differential attention rides grouped-query attention: a KV pair
    [k_2p | k_2p+1] is one 2D-wide head, its value [v_2p | v_2p+1] is the
    2D-wide value, and each query is zero-padded in the half it does not
    use (`_pad_queries`); lambda, the subtraction and the sub-norm follow
    outside the read (`_diff_combine`).
  * the selective scan's state is float32, whatever the weights' type.
"""
from __future__ import annotations

import math

import numpy as _np

from .. import sanitize as _sanitize
from ..serve.batcher import ServeError
from ..serve.kv_pool import CacheLeaf, KVCachePool

__all__ = ["HybridConfig", "HybridDecoder", "init_hybrid_params",
           "param_shapes", "draw_leaf", "layer_kinds", "lambda_init"]


class HybridConfig:
    """Static shape record (all ints and floats; nothing here ever becomes
    a tracer). `window` is the number of keys a window layer's query sees,
    its own included."""

    FIELDS = ("vocab", "embed", "layers", "heads", "kv_heads", "head_dim",
              "mlp_hidden", "window", "d_state", "d_conv", "expand",
              "dt_rank", "max_len", "dtype", "ln_eps")

    def __init__(self, vocab=128, embed=64, layers=8, heads=4, kv_heads=2,
                 head_dim=16, mlp_hidden=None, window=8, d_state=4,
                 d_conv=4, expand=2, dt_rank=None, max_len=64,
                 dtype="float32", ln_eps=1e-5):
        self.vocab = int(vocab)
        self.embed = int(embed)
        self.layers = int(layers)
        self.heads = int(heads)
        self.kv_heads = int(kv_heads)
        self.head_dim = int(head_dim)
        self.mlp_hidden = int(mlp_hidden if mlp_hidden is not None
                              else 4 * embed)
        self.window = int(window)
        self.d_state = int(d_state)
        self.d_conv = int(d_conv)
        self.expand = int(expand)
        self.dt_rank = int(dt_rank if dt_rank is not None
                           else -(-self.embed // 16))
        self.max_len = int(max_len)
        self.dtype = str(dtype)
        self.ln_eps = float(ln_eps)
        if self.layers % 4:
            raise ServeError("the hybrid layer pattern needs layers "
                             f"divisible by 4, got {self.layers}")
        if self.heads % 2 or self.kv_heads % 2 \
                or (self.heads // 2) % (self.kv_heads // 2):
            raise ServeError(
                f"differential attention pairs heads: {self.heads} query "
                f"and {self.kv_heads} KV heads do not pair up")
        if not 1 <= self.window <= self.max_len:
            raise ServeError(f"window {self.window} outside "
                             f"[1, max_len={self.max_len}]")

    d_inner = property(lambda self: self.expand * self.embed)
    kv_width = property(lambda self: self.kv_heads * self.head_dim)
    kinds = property(lambda self: layer_kinds(self.layers))

    def as_dict(self):
        return {k: getattr(self, k) for k in self.FIELDS}


def layer_kinds(L):
    """[(kind, index among the layers that share that kind's leaves)];
    `swa` and `full` share the attention leaves (`a_*`)."""
    half = L // 2
    out, count = [], {"mamba": 0, "attn": 0, "cross": 0, "gmu": 0}
    for l in range(L):
        if l % 2 == 0 and l <= half:
            kind, group = "mamba", "mamba"
        elif l % 2 == 1 and l < half:
            kind, group = "swa", "attn"
        elif l == half + 1:
            kind, group = "full", "attn"
        elif l % 2 == 1:
            kind, group = "cross", "cross"
        else:
            kind, group = "gmu", "gmu"
        out.append((kind, count[group]))
        count[group] += 1
    return out


def lambda_init(l):
    """Differential attention's depth-dependent lambda offset."""
    return 0.8 - 0.6 * math.exp(-0.3 * l)


#: the initializer's scales by kind of leaf (`param_shapes`). Matrices and
#: the embedding N(0, 0.02), the lambda vectors N(0, 0.1). The three Mamba
#: leaves that decide whether the scan's state reaches the output are NOT
#: drawn at 0.02: with conv taps that small u' = silu(conv(u)) is 0.02
#: itself, the scan's term s.C is nothing beside the skip term D * u', and
#: no comparison of logits can see the state. The conv taps take Mamba-1's
#: own U(+-1/sqrt(K)) and the delta projection its own U(+-R**-0.5); the
#: input projection W_x takes N(0, 0.065), eight times Mamba-1's
#: U(+-1/sqrt(d_in)), at which s.C is about the size of D * u' (0.9 of it
#: at d_in 5120, N 16).
INIT_SCALES = {"normal": 0.02, "lambda": 0.1, "x_proj": 0.065}


def param_shapes(c):
    """name -> (shape, kind of initial value): the leaves, stacked by
    layer kind on a leading axis. This is the one table of the model's
    leaves: whoever makes weights for it (`init_hybrid_params`, a
    benchmark) reads it. Kinds: `normal`, `lambda`, `x_proj` (normals at
    `INIT_SCALES`), `conv` (uniform +-1/sqrt(K)), `dt_proj` (uniform
    +-R**-0.5), `ones`, `zeros`, `a_log` (log 1..N), `dt_bias` (inverse
    softplus of log-uniform [1e-3, 1e-1])."""
    L, d, F, V = c.layers, c.embed, c.mlp_hidden, c.vocab
    di, N, K, R = c.d_inner, c.d_state, c.d_conv, c.dt_rank
    qw, kvw, D2 = c.heads * c.head_dim, c.kv_width, 2 * c.head_dim
    nm, na = L // 4 + 1, L // 4 + 1
    nc = ng = L // 4 - 1
    out = {
        "emb": ((V, d), "normal"),
        "ln1_w": ((L, d), "ones"), "ln1_b": ((L, d), "zeros"),
        "ln2_w": ((L, d), "ones"), "ln2_b": ((L, d), "zeros"),
        "lnf_w": ((d,), "ones"), "lnf_b": ((d,), "zeros"),
        "mlp_gate_up": ((L, d, 2 * F), "normal"),
        "mlp_down": ((L, F, d), "normal"),
        "m_in": ((nm, d, 2 * di), "normal"),
        "m_conv_w": ((nm, K, di), "conv"), "m_conv_b": ((nm, di), "zeros"),
        "m_x": ((nm, di, R + 2 * N), "x_proj"),
        "m_dt_w": ((nm, R, di), "dt_proj"), "m_dt_b": ((nm, di), "dt_bias"),
        "m_A_log": ((nm, N, di), "a_log"), "m_D": ((nm, di), "ones"),
        "m_out": ((nm, di, d), "normal"),
        "a_qkv": ((na, d, qw + 2 * kvw), "normal"),
        "a_qkv_b": ((na, qw + 2 * kvw), "zeros"),
        "a_o": ((na, qw, d), "normal"), "a_o_b": ((na, d), "zeros"),
        "c_q": ((nc, d, qw), "normal"), "c_q_b": ((nc, qw), "zeros"),
        "c_o": ((nc, qw, d), "normal"), "c_o_b": ((nc, d), "zeros"),
        "g_w1": ((ng, d, di), "normal"), "g_w2": ((ng, di, d), "normal"),
    }
    for prefix, n in (("a", na), ("c", nc)):
        for lam in ("lq1", "lk1", "lq2", "lk2"):
            out[f"{prefix}_{lam}"] = ((n, D2), "lambda")
        out[f"{prefix}_sub"] = ((n, D2), "ones")
    return out


def draw_leaf(key, shape, kind, scales=INIT_SCALES):
    """One leaf's initial value in float32 (the last two axes of a Mamba
    leaf are (K | N | R, d_in): a layer of a stacked leaf draws alike)."""
    import jax
    import jax.numpy as jnp
    if kind == "ones":
        return jnp.ones(shape)
    if kind == "zeros":
        return jnp.zeros(shape)
    if kind == "a_log":                         # (.., N, d_in): log(n + 1)
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[-2] + 1.0))[:, None], shape)
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape)
                     * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        return dt + jnp.log(-jnp.expm1(-dt))
    if kind in ("conv", "dt_proj"):             # (.., K | R, d_in)
        return jax.random.uniform(key, shape, minval=-1.0, maxval=1.0) \
            / math.sqrt(shape[-2])
    return jax.random.normal(key, shape) * scales[kind]


def init_hybrid_params(config, seed=0):
    """Deterministic random parameters in `config.dtype`."""
    import jax
    key = jax.random.PRNGKey(seed)
    return {name: draw_leaf(jax.random.fold_in(key, i), shape, kind).astype(
                config.dtype)
            for i, (name, (shape, kind)) in enumerate(
                sorted(param_shapes(config).items()))}


# ---------------------------------------------------------------------------
# layer library: plain functions of (weights, activations, cache, lengths)
# ---------------------------------------------------------------------------
def layer_norm(x, w, b, eps):
    """LayerNorm with weight and bias; float32 statistics."""
    import jax
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def silu(x):
    import jax
    return x * jax.nn.sigmoid(x)


def gated_mlp(h, w_gate_up, w_down):
    """W_down(silu(g) * u), [g; u] = W_gate_up h, no biases."""
    gu = h @ w_gate_up
    F = gu.shape[-1] // 2
    return (silu(gu[..., :F]) * gu[..., F:]) @ w_down


def gated_memory(h, mem, w1, w2):
    """W_2(silu(W_1 h) * m): `mem` is the memory at the same position, in
    float32 as the scan left it."""
    import jax.numpy as jnp
    g = silu((h @ w1).astype(jnp.float32)) * mem
    return g.astype(h.dtype) @ w2


def _mamba_inputs(w, c, u1):
    """(delta, B, C) in float32 from the convolved activations."""
    import jax
    import jax.numpy as jnp
    N, R = c.d_state, c.dt_rank
    rbc = u1.astype(w["m_x"].dtype) @ w["m_x"]
    delta = jax.nn.softplus(
        (rbc[..., :R] @ w["m_dt_w"]).astype(jnp.float32)
        + w["m_dt_b"].astype(jnp.float32))
    return (delta, rbc[..., R:R + N].astype(jnp.float32),
            rbc[..., R + N:].astype(jnp.float32))


def mamba_step(w, c, h, ssm, tail):
    """One token a lane. h (S, d); ssm (S, N, d_in) float32; tail
    (S, K-1, d_in), the last K-1 pre-convolution activations ->
    (out (S, d), y (S, d_in) float32, ssm', tail')."""
    import jax.numpy as jnp
    di = c.d_inner
    uz = h @ w["m_in"]
    u, z = uz[:, :di], uz[:, di:]
    taps = jnp.concatenate([tail, u[:, None].astype(tail.dtype)], 1)
    conv = jnp.sum(taps.astype(jnp.float32)
                   * w["m_conv_w"].astype(jnp.float32)[None], 1)
    u1 = silu(conv + w["m_conv_b"].astype(jnp.float32))        # (S, d_in)
    delta, B, C = _mamba_inputs(w, c, u1)
    A = -jnp.exp(w["m_A_log"].astype(jnp.float32))              # (N, d_in)
    ssm = jnp.exp(delta[:, None, :] * A[None]) * ssm \
        + (delta * u1)[:, None, :] * B[:, :, None]
    y = jnp.sum(ssm * C[:, :, None], 1) + w["m_D"].astype(jnp.float32) * u1
    out = (y * silu(z.astype(jnp.float32))).astype(h.dtype) @ w["m_out"]
    return out, y, ssm, taps[:, 1:]


def mamba_chunk(w, c, h, ssm, tail, nvalid):
    """A chunk of W positions a lane, state carried in and out: the
    chunked scan of prefill. h (B, W, d); positions >= nvalid[b] leave
    the state as it is (their delta is 0) and are not in the new tail."""
    import jax
    import jax.numpy as jnp
    di, K = c.d_inner, c.d_conv
    W = h.shape[1]
    uz = h @ w["m_in"]
    u, z = uz[..., :di], uz[..., di:]
    taps = jnp.concatenate([tail, u.astype(tail.dtype)], 1)  # (B, W+K-1, di)
    conv = sum(taps[:, k:k + W].astype(jnp.float32)
               * w["m_conv_w"][k].astype(jnp.float32) for k in range(K))
    u1 = silu(conv + w["m_conv_b"].astype(jnp.float32))
    delta, B, C = _mamba_inputs(w, c, u1)
    delta = jnp.where((jnp.arange(W)[None, :] < nvalid[:, None])[..., None],
                      delta, 0.0)
    A = -jnp.exp(w["m_A_log"].astype(jnp.float32))

    def step(s, xs):
        d_t, u_t, B_t, C_t = xs
        s = jnp.exp(d_t[:, None, :] * A[None]) * s \
            + (d_t * u_t)[:, None, :] * B_t[:, :, None]
        return s, jnp.sum(s * C_t[:, :, None], 1)

    ssm, y = jax.lax.scan(
        step, ssm, tuple(a.swapaxes(0, 1) for a in (delta, u1, B, C)),
        unroll=8)
    y = y.swapaxes(0, 1) + w["m_D"].astype(jnp.float32) * u1
    out = (y * silu(z.astype(jnp.float32))).astype(h.dtype) @ w["m_out"]
    keep = nvalid[:, None] + jnp.arange(K - 1)[None, :]
    tail = jnp.take_along_axis(taps, keep[..., None], axis=1)
    return out, y, ssm, tail


def _pad_queries(q, c):
    """(..., Hq, D) -> (..., Hq, 2D): query head g is q1 (g even) or q2
    (g odd) of its pair and meets k1 or k2, the first or second half of
    the KV pair's 2D-wide head; the other half is zero."""
    import jax.numpy as jnp
    half = (jnp.arange(c.heads) % 2)[:, None] == jnp.arange(2)[None, :]
    return (q[..., None, :] * half[..., None].astype(q.dtype)).reshape(
        q.shape[:-1] + (2 * c.head_dim,))


def _diff_combine(a, w, prefix, l, c):
    """(..., Hq, 2D) float32 reads (rows 2i, 2i+1 = a1, a2 of pair i) ->
    (..., Hq * D): (1 - lambda_init) RMSNorm(a1 - lambda a2), with layer
    l's lambda vectors and sub-norm weight from its leaves `w`."""
    import jax
    import jax.numpy as jnp
    f = {n: w[f"{prefix}_{n}"].astype(jnp.float32)
         for n in ("lq1", "lk1", "lq2", "lk2", "sub")}
    lam = jnp.exp(jnp.sum(f["lq1"] * f["lk1"])) \
        - jnp.exp(jnp.sum(f["lq2"] * f["lk2"])) + lambda_init(l)
    a = a.astype(jnp.float32)
    diff = a[..., 0::2, :] - lam * a[..., 1::2, :]
    diff = diff * jax.lax.rsqrt(
        jnp.mean(jnp.square(diff), -1, keepdims=True) + c.ln_eps)
    out = (1.0 - lambda_init(l)) * diff * f["sub"]
    return out.reshape(a.shape[:-2] + (c.heads * c.head_dim,))


def _read_leaf(q, k_leaf, v_leaf, lengths, c, rows=None, window=None):
    """One query a lane over a cache leaf, as grouped-query attention
    over Hkv/2 heads of 2D (`ops.fused.paged_attention`, leaf mode).
    The reads come back in float32: a1 - lambda a2 cancels most of them."""
    import jax.numpy as jnp
    from ..ops import fused as _fused
    att = _fused.paged_attention(
        _pad_queries(q, c)[:, None], k_leaf, v_leaf, lengths, None,
        rows=rows, window=window, scale=1.0 / math.sqrt(c.head_dim),
        out_dtype=jnp.float32)
    return att[:, 0]                                    # (S, Hq, 2D)


def window_attention_chunk(q, k, v, ring_k, ring_v, offsets, nvalid, c):
    """A chunk's queries over ring U chunk under the exact window mask,
    read BEFORE the chunk is written. q (B, W, Hq, D); k, v (B, W, Hkv*D);
    ring_* (B, cap, Hkv*D) or None (a prefill at offset 0: nothing is
    before the chunk). Ring slot j holds the newest position below
    `offsets` congruent to j. -> (B, W, Hq, 2D) float32 reads."""
    import jax
    import jax.numpy as jnp
    B, W = q.shape[:2]
    P, D = c.kv_heads // 2, c.head_dim
    per = (c.heads // 2) // P
    j = jnp.arange(W)
    qpos = offsets[:, None] + j[None, :]                         # (B, W)
    keys, vals = k, v
    kpos = jnp.where(j[None, :] < nvalid[:, None], qpos, -1)
    if ring_k is not None:
        cap = ring_k.shape[1]
        slot = jnp.arange(cap)[None, :]
        last = offsets[:, None] - 1
        held = slot + cap * ((last - slot) // cap)
        kpos = jnp.concatenate([jnp.where(held >= 0, held, -1), kpos], 1)
        keys = jnp.concatenate([ring_k, k], 1)
        vals = jnp.concatenate([ring_v, v], 1)
    Tk = keys.shape[1]
    mask = (kpos[:, None, :] >= 0) & (kpos[:, None, :] <= qpos[..., None]) \
        & (kpos[:, None, :] > qpos[..., None] - c.window)     # (B, W, Tk)
    qg = q.reshape(B, W, P, per, 2, D)
    kg = keys.reshape(B, Tk, P, 2, D)
    vg = vals.reshape(B, Tk, P, 2 * D)
    sco = jnp.einsum("bqpacd,bkpcd->bpacqk", qg, kg,
                     preferred_element_type=jnp.float32) / math.sqrt(D)
    sco = jnp.where(mask[:, None, None, None], sco, -1e30)
    att = jnp.einsum("bpacqk,bkpe->bqpace", jax.nn.softmax(sco, -1),
                     vg.astype(jnp.float32))
    return att.reshape(B, W, c.heads, 2 * D)


# ---------------------------------------------------------------------------
# the three programs
# ---------------------------------------------------------------------------
def _weights(params, l, kind, i):
    """Layer l's leaves: slices of the stacked tree (one that a layer
    function does not use is never computed)."""
    prefix = {"mamba": "m_", "swa": "a_", "full": "a_", "cross": "c_",
              "gmu": "g_"}[kind]
    w = {n: params[n][l] for n in ("ln1_w", "ln1_b", "ln2_w", "ln2_b",
                                   "mlp_gate_up", "mlp_down")}
    w.update({n: a[i] for n, a in params.items() if n.startswith(prefix)})
    return w


def _mlp_block(x, w, c, l):
    import jax
    with jax.named_scope(f"layer{l}/mlp"):
        h = layer_norm(x, w["ln2_w"], w["ln2_b"], c.ln_eps)
        return x + gated_mlp(h, w["mlp_gate_up"], w["mlp_down"])


def _upper(params, cache, x, mem, rows, lengths, c):
    """Layer L/2+1's attention and every layer above it, for ONE position
    a lane whose K/V at layer L/2+1 is already in the shared cache at
    position `lengths`. x (S, d) is the stream after layer L/2, mem
    (S, d_in) the memory at that position -> logits (S, vocab) float32."""
    import jax
    import jax.numpy as jnp
    qw = c.heads * c.head_dim
    for l in range(c.layers // 2 + 1, c.layers):
        kind, i = c.kinds[l]
        with jax.named_scope(f"layer{l}/{kind}"):
            w = _weights(params, l, kind, i)
            h = layer_norm(x, w["ln1_w"], w["ln1_b"], c.ln_eps)
            if kind == "gmu":
                x = x + gated_memory(h, mem, w["g_w1"], w["g_w2"])
            else:
                if kind == "full":
                    q = h @ w["a_qkv"][:, :qw] + w["a_qkv_b"][:qw]
                    prefix = "a"
                else:
                    q = h @ w["c_q"] + w["c_q_b"]
                    prefix = "c"
                a = _read_leaf(q.reshape(-1, c.heads, c.head_dim),
                               cache["shared_k"], cache["shared_v"],
                               lengths, c, rows=rows)
                o = _diff_combine(a, w, prefix, l, c).astype(x.dtype)
                x = x + o @ w[f"{prefix}_o"] + w[f"{prefix}_o_b"]
        x = _mlp_block(x, w, c, l)
    with jax.named_scope("head"):
        xf = layer_norm(x, params["lnf_w"], params["lnf_b"], c.ln_eps)
        return jnp.dot(xf, params["emb"].T,
                       preferred_element_type=jnp.float32)


def _write_shared(params, cache, x, rows, at, c):
    """Layer L/2+1's K/V projection of the stream `x`, written into the
    shared cache at [rows, at]; its attention is `_upper`'s first layer."""
    import jax
    l = c.layers // 2 + 1
    qw, kvw = c.heads * c.head_dim, c.kv_width
    with jax.named_scope(f"layer{l}/full"):
        w = _weights(params, l, *c.kinds[l])
        h = layer_norm(x, w["ln1_w"], w["ln1_b"], c.ln_eps)
        kv = h @ w["a_qkv"][:, qw:] + w["a_qkv_b"][qw:]
        cache["shared_k"] = cache["shared_k"].at[rows, at].set(kv[..., :kvw])
        cache["shared_v"] = cache["shared_v"].at[rows, at].set(kv[..., kvw:])
    return cache


def _make_chunk(config, window, fresh):
    """The prefill step over one window-sized slice a lane. `fresh` is the
    prefill at offset 0 (`prefill(params, cache, tokens, lengths,
    slot_rows)`): every state starts from zero and nothing is before the
    chunk. Else the chunk at an offset (`chunk_prefill(params, cache,
    tokens, offsets, nvalid, slot_rows)`): the lane's state, tail and rings
    are read from its row and carried on. Lanes are PREFILL lanes with
    their pool rows as data; an idle lane carries the garbage row."""
    import jax
    import jax.numpy as jnp
    c = config
    W = int(window)
    if not 1 <= W <= c.window:
        raise ServeError(
            f"prefill window {W} outside [1, window={c.window}]: a window "
            f"layer's ring holds {c.window} positions and is read before "
            f"the chunk is written, so a chunk must fit it")
    kinds = c.kinds
    half = c.layers // 2
    qw, kvw = c.heads * c.head_dim, c.kv_width
    T = c.max_len

    def core(params, cache, tokens, offsets, nvalid, rows):
        cache = dict(cache)
        B = tokens.shape[0]
        G = cache["shared_k"].shape[0] - 1               # garbage row
        j = jnp.arange(W)
        with jax.named_scope("embed"):
            valid = j[None, :] < nvalid[:, None]                 # (B, W)
            wrows = jnp.where(valid, rows[:, None], G)
            pos = offsets[:, None] + j[None, :]
            x = params["emb"][tokens]                            # (B, W, d)
        mem = None
        for l in range(half + 1):
            kind, i = kinds[l]
            with jax.named_scope(f"layer{l}/{kind}"):
                w = _weights(params, l, kind, i)
                h = layer_norm(x, w["ln1_w"], w["ln1_b"], c.ln_eps)
                if kind == "mamba":
                    if fresh:
                        ssm = jnp.zeros((B, c.d_state, c.d_inner),
                                        jnp.float32)
                        tail = jnp.zeros((B, c.d_conv - 1, c.d_inner),
                                         cache[f"conv{i}"].dtype)
                    else:
                        ssm = cache[f"ssm{i}"][rows]
                        tail = cache[f"conv{i}"][rows]
                    out, y, ssm, tail = mamba_chunk(w, c, h, ssm, tail,
                                                    nvalid)
                    cache[f"ssm{i}"] = cache[f"ssm{i}"].at[rows].set(ssm)
                    cache[f"conv{i}"] = cache[f"conv{i}"].at[rows].set(tail)
                    x = x + out
                    if l == half:
                        mem = y
                else:                                            # swa
                    qkv = h @ w["a_qkv"] + w["a_qkv_b"]
                    q = qkv[..., :qw].reshape(B, W, c.heads, c.head_dim)
                    k, v = qkv[..., qw:qw + kvw], qkv[..., qw + kvw:]
                    rk, rv = cache[f"ring_k{i}"], cache[f"ring_v{i}"]
                    a = window_attention_chunk(
                        q, k, v, None if fresh else rk[rows],
                        None if fresh else rv[rows], offsets, nvalid, c)
                    slot = pos % c.window
                    cache[f"ring_k{i}"] = rk.at[wrows, slot].set(k)
                    cache[f"ring_v{i}"] = rv.at[wrows, slot].set(v)
                    o = _diff_combine(a, w, "a", l, c)
                    x = x + o.astype(x.dtype) @ w["a_o"] + w["a_o_b"]
            x = _mlp_block(x, w, c, l)
        # layer L/2+1 keeps every position's K/V and nothing else of them
        cache = _write_shared(params, cache, x, wrows,
                              jnp.clip(pos, 0, T - 1), c)
        last = jnp.maximum(nvalid - 1, 0)
        lane = jnp.arange(B)
        logits = _upper(params, cache, x[lane, last], mem[lane, last], rows,
                        offsets + last, c)
        return cache, logits

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
    `micro(params, cache, tokens, lengths, active) -> (cache, logits)`.
    tokens (S,) the last emitted token, lengths (S,) the cache length (the
    new token's K/V and state land at position `lengths`); an idle lane
    writes the garbage row and keeps its state."""
    import jax
    import jax.numpy as jnp
    c = config
    kinds = c.kinds
    half = c.layers // 2
    qw, kvw = c.heads * c.head_dim, c.kv_width

    def micro(params, cache, tokens, lengths, active):
        cache = dict(cache)
        S = tokens.shape[0]
        # (every equation runs under one of the program's scopes, the
        # lanes' bookkeeping too: `profiler.program_scopes` names the
        # device's time by them)
        with jax.named_scope("embed"):
            rows = jnp.where(active, jnp.arange(S), S)   # garbage row = S
            wpos = jnp.clip(lengths, 0, c.max_len - 1)
            x = params["emb"][tokens]                            # (S, d)
        mem = None
        for l in range(half + 1):
            kind, i = kinds[l]
            with jax.named_scope(f"layer{l}/{kind}"):
                w = _weights(params, l, kind, i)
                h = layer_norm(x, w["ln1_w"], w["ln1_b"], c.ln_eps)
                if kind == "mamba":
                    # a state leaf is read and rewritten whole, in place:
                    # an idle lane keeps what it held
                    old_s, old_t = cache[f"ssm{i}"], cache[f"conv{i}"]
                    out, y, ssm, tail = mamba_step(w, c, h, old_s[:S],
                                                   old_t[:S])
                    keep = active[:, None, None]
                    cache[f"ssm{i}"] = old_s.at[:S].set(
                        jnp.where(keep, ssm, old_s[:S]))
                    cache[f"conv{i}"] = old_t.at[:S].set(
                        jnp.where(keep, tail, old_t[:S]))
                    x = x + out
                    if l == half:
                        mem = y
                else:                                            # swa
                    qkv = h @ w["a_qkv"] + w["a_qkv_b"]
                    slot = lengths % c.window
                    rk = cache[f"ring_k{i}"].at[rows, slot].set(
                        qkv[:, qw:qw + kvw])
                    rv = cache[f"ring_v{i}"].at[rows, slot].set(
                        qkv[:, qw + kvw:])
                    cache[f"ring_k{i}"], cache[f"ring_v{i}"] = rk, rv
                    a = _read_leaf(
                        qkv[:, :qw].reshape(S, c.heads, c.head_dim), rk, rv,
                        lengths, c, window=c.window)
                    o = _diff_combine(a, w, "a", l, c)
                    x = x + o.astype(x.dtype) @ w["a_o"] + w["a_o_b"]
            x = _mlp_block(x, w, c, l)
        cache = _write_shared(params, cache, x, rows, wpos, c)
        return cache, _upper(params, cache, x, mem, None, lengths, c)

    return micro


def _make_decode(config, steps, eos_id):
    """The decode step: every pool slot advances up to `steps` tokens in
    one program (`lax.scan` over the micro-step),
    `serve.continuous._make_decode`'s contract: `decode(params, cache,
    tokens, lengths, steps_left, temps, top_ks, top_ps, keys) -> (cache,
    out_tokens (steps, S), emitted)`."""
    import jax
    import jax.numpy as jnp
    from ..serve.sampling import sample_tokens
    micro = _make_micro(config)

    def decode(params, cache, tokens, lengths, steps_left, temps, top_ks,
               top_ps, keys):
        def step(carry, _):
            cache, last, lens, left, emitted = carry
            with jax.named_scope("sampler"):
                act = left > 0
            cache, logits = micro(params, cache, last, lens, act)
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
            return (cache, last, lens, new_left, emitted), nxt

        zero = jnp.zeros_like(steps_left)
        (cache, _, _, _, emitted), toks = jax.lax.scan(
            step, (cache, tokens, lengths, steps_left, zero), None,
            length=steps)
        return cache, toks, emitted

    return decode


class HybridDecoder:
    """The model side of the continuous engine for the hybrid decoder:
    jitted programs over a pool built from `cache_spec()`. The engine's
    model protocol: `config` (with `max_len`), `params`, `new_pool`,
    `prefill_program`, `chunk_prefill_program`, `decode_program`,
    `compile_cache_size`, `reference_generate`; `chunk_rows_as_data` tells
    the engine that the chunk program's lanes are prefill lanes whose pool
    rows ride as a fourth array (at pool width a chunk of this model would
    pay the whole model for every idle slot)."""

    chunk_rows_as_data = True

    def __init__(self, config, params=None, seed=0):
        from ..deploy import maybe_enable_compile_cache
        maybe_enable_compile_cache()
        self.config = config
        self.params = params if params is not None \
            else init_hybrid_params(config, seed)
        try:
            from ..inspect import memory as _mem
            _mem.register(self.params, owner="decoder_params")
        except Exception:
            pass
        self._programs = {}

    def cache_spec(self):
        """The cache leaves of one slot row, by layer kind."""
        c = self.config
        spec = [CacheLeaf(n, (c.max_len, c.kv_width), c.dtype, "full",
                          c.max_len) for n in ("shared_k", "shared_v")]
        for kind, i in c.kinds:
            if kind == "swa":
                spec += [CacheLeaf(f"{n}{i}", (c.window, c.kv_width),
                                   c.dtype, "ring", c.window)
                         for n in ("ring_k", "ring_v")]
            elif kind == "mamba":
                spec += [CacheLeaf(f"ssm{i}", (c.d_state, c.d_inner),
                                   "float32", "state", 0),
                         CacheLeaf(f"conv{i}", (c.d_conv - 1, c.d_inner),
                                   c.dtype, "state", 0)]
        return spec

    def new_pool(self, max_slots=None, dtype=None):
        if dtype is not None and str(dtype) != self.config.dtype:
            raise ServeError(
                f"the hybrid decoder's cache is stored in its own dtype "
                f"({self.config.dtype}); kv_dtype={dtype!r} has no ring or "
                f"state form")
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
                             lambda: _make_chunk(self.config, w, True),
                             f"prefill[w={w}]")

    def chunk_prefill_program(self, window, extent=None):
        """`extent` is the classic decoder's read bound; the shared read
        here is one query a lane on the live-block grid, so every extent
        is the one program."""
        w = int(window)
        return self._program(("chunk", w),
                             lambda: _make_chunk(self.config, w, False),
                             f"chunk_prefill[w={w}]")

    def decode_program(self, steps, eos_id=None, draft=0):
        if draft:
            raise ServeError(
                "speculative decode needs a state that can be rolled back "
                "to the last accepted token; a recurrent state cannot")
        key = ("decode", int(steps), eos_id)
        return self._program(
            key, lambda: _make_decode(self.config, key[1], eos_id),
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
        request seed (the key is a function of seed and position)."""
        import jax.numpy as jnp
        from ..serve.sampling import sample_first, seed_key
        c = self.config
        pool = self.new_pool(max_slots=1)
        W = int(window if window is not None else min(c.window, c.max_len))
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
                cache, logits = self.prefill_program(W)(
                    self.params, cache, jnp.asarray(toks), one(n), one(0))
            else:
                cache, logits = self.chunk_prefill_program(W)(
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
            cache, toks1, _ = decode(self.params, cache, one(out[-1]),
                                     one(cache_len), one(1), *sample)
            pool.swap_buffers(cache)
            out.append(int(toks1[0, 0]))
            cache_len += 1
        return _np.asarray(out, dtype=_np.int32)
