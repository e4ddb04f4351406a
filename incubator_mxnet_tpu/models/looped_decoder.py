"""A looped decoder for `serve.ContinuousEngine`: ONE stack of layers run
`ut_steps` times over the same weights, a key/value cache for every pass,
a norm before AND after each sublayer, rotary positions in half-split
pairs (the looped language model of arXiv:2510.25741, `model_type` `ouro`).

    h^0 = E[token]                               (no learned positions)
    for u = 1 .. U:   h^u = N_f(L_n(.. L_1(h^{u-1})))    the SAME n layers
    logits = h^U W_head                          (float32, untied from E)

One layer, on the stream x, with four RMSNorms of their own weights:

    x <- x + N2(Attn(N1(x)));    x <- x + N4(MLP(N3(x)))
    Attn: q, k, v = h W_q, h W_k, h W_v in H heads of D, no biases; rotary
          on all D values of q and k in half-split pairs (x_i, x_{i+D/2})
          at angle t * theta^(-2i/D) (`rope_half`); causal softmax of
          q.k / sqrt(D); W_o
    MLP:  W_down(silu(h W_gate) * (h W_up)), no biases

A token's keys and values differ from pass to pass, so the cache holds one
PLANE for each (pass, layer). A layer's leaf `k{l}` / `v{l}` keeps a row's
U planes side by side, `(U, max_len, H * D)` a row (`cache_spec`); a program
sees the leaf as `(rows * U, max_len, H * D)`, a reshape that moves no
byte, and pass u of pool row s writes and reads row `s * U + u` of it.

THE PASS IS A LOOP IN THE PROGRAM (`lax.fori_loop` over u with the layer
bodies once inside it): the pass index reaches the cache write and the
cache read (`ops.fused.paged_attention`, leaf mode, `rows=` as data) as
DATA, so the decode scan, the dense prefill and the chunk prefill each hold
`layers` layer bodies and `layers` paged reads, not `layers * ut_steps`.

The exit gate (`gate_w`, `gate_b`: lambda_u = sigmoid(h^u . w_g + b_g), the
pass served is the first at which the cumulative exit mass reaches
`early_exit_threshold`) is in `params`, the checkpoint's, and is NOT
computed: at the published threshold 1.0 every token runs every pass and
the last pass's logits are served. Any other threshold would leave the
lanes of one wave at different depths, which no program here can do, and
`LoopedDecoder` refuses it.

What a program may assume, and what it sees to:
  * a pass reads its OWN plane and no other (tests poison the others);
  * a decode step and a chunk write the new positions' K and V in plane
    (u, l) BEFORE they read it; the dense prefill at offset 0 reads
    nothing (its window is all there is) and writes every plane;
  * positions a row holds beyond its request's length are a previous
    tenant's or a pad's: every read is under the `[0, length]` mask;
  * norm statistics, rotary angles, softmax and logits are float32
    whatever the weights' type.

`stats()["loop"]`: `stack_passes` (passes of the stack executed, summed
over active lanes and valid chunk positions: `ut_steps` a token) and
`plane_positions_read` (live positions x planes that decode steps read).
"""
from __future__ import annotations

import math

from ..serve.batcher import ServeError
from ..serve.kv_pool import CacheLeaf
from . import sparse_moe_decoder as _sm
from .hybrid_decoder import gated_mlp
from .sparse_moe_decoder import draw_leaf, rms_norm

__all__ = ["LoopedConfig", "LoopedDecoder", "init_looped_params",
           "param_shapes", "draw_leaf", "rope_half"]

#: queries a lane of the chunk program's paged read: a chunk of W positions
#: reads as W / CHUNK_QUERIES lanes of this many queries each (at the served
#: widths 8 x 16 heads are the 128 rows of a 2048-wide query tile that the
#: leaf kernel's VMEM budget takes; the whole window in one lane is not)
CHUNK_QUERIES = 8


class LoopedConfig:
    """Static shape record (ints and floats; nothing here becomes a
    tracer). `ut_steps` is the number of passes of the one stack."""

    FIELDS = ("vocab", "embed", "layers", "heads", "head_dim", "mlp_hidden",
              "ut_steps", "rope_theta", "norm_eps", "early_exit_threshold",
              "max_len", "dtype")

    def __init__(self, vocab=128, embed=32, layers=2, heads=2, head_dim=16,
                 mlp_hidden=64, ut_steps=2, rope_theta=1e6, norm_eps=1e-6,
                 early_exit_threshold=1.0, max_len=64, dtype="float32"):
        for k in ("vocab", "embed", "layers", "heads", "head_dim",
                  "mlp_hidden", "ut_steps", "max_len"):
            setattr(self, k, int(locals()[k]))
        self.rope_theta = float(rope_theta)
        self.norm_eps = float(norm_eps)
        self.early_exit_threshold = float(early_exit_threshold)
        self.dtype = str(dtype)
        if self.head_dim % 2:
            raise ServeError(f"rotary pairs the head's values: head_dim "
                             f"{self.head_dim} is odd")
        if self.ut_steps < 1 or self.layers < 1:
            raise ServeError("at least one layer and one pass")

    kv_width = property(lambda self: self.heads * self.head_dim)

    def as_dict(self):
        return {k: getattr(self, k) for k in self.FIELDS}


#: the initializer's scales by kind of leaf (`param_shapes`)
INIT_SCALES = {"normal": 0.02, "emb": 0.02, "q": 0.02, "k": 0.02}


def param_shapes(c):
    """name -> (shape, kind of initial value): the leaves, the layers'
    stacked on a leading axis. The one table of the model's leaves: whoever
    makes weights for it reads it."""
    L, d, F, V, qw = c.layers, c.embed, c.mlp_hidden, c.vocab, c.kv_width
    return {
        "emb": ((V, d), "emb"), "head": ((d, V), "normal"),
        "n1": ((L, d), "ones"), "n2": ((L, d), "ones"),
        "n3": ((L, d), "ones"), "n4": ((L, d), "ones"),
        "nf": ((d,), "ones"),
        "wq": ((L, d, qw), "q"), "wk": ((L, d, qw), "k"),
        "wv": ((L, d, qw), "normal"), "wo": ((L, qw, d), "normal"),
        "mlp_gate_up": ((L, d, 2 * F), "normal"),
        "mlp_down": ((L, F, d), "normal"),
        "gate_w": ((d,), "normal"), "gate_b": ((1,), "zeros"),
    }


def init_looped_params(config, seed=0, scales=INIT_SCALES):
    """Deterministic random parameters in `config.dtype`."""
    import jax
    key = jax.random.PRNGKey(seed)
    return {name: draw_leaf(jax.random.fold_in(key, i), shape, kind,
                            scales).astype(config.dtype)
            for i, (name, (shape, kind)) in enumerate(
                sorted(param_shapes(config).items()))}


# ---------------------------------------------------------------------------
# layer library: plain functions of (weights, activations, cache, lengths)
# ---------------------------------------------------------------------------
def rope_half(x, pos, theta, heads=1):
    """Rotary embedding as HALF-SPLIT pairs (x_i, x_{i+n/2}) at angle
    pos * theta^(-2i/n), in float32 (`sparse_moe_decoder.rope` pairs
    (x_2i, x_2i+1): the same angles on other pairs). The last axis of x
    holds `heads` heads of n values side by side, each turned on its own;
    `pos` has x's other axes.

    [a | b] -> [a cos - b sin | b cos + a sin] is x * [cos | cos] +
    [-b | a] * [sin | sin], and [-b | a] on the FLAT axis is a roll by n/2
    one way in a head's first half and the other way in its second: the
    heads are never split apart before the rotation. (Reshaped to (.., H,
    n) first, the compiler folds the split into the projection and re-lays
    W_q and W_k whole for it, 2 x 403 MB a program at the served widths.)"""
    import jax.numpy as jnp
    n = x.shape[-1] // heads
    freq = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = pos.astype(jnp.float32)[..., None] * freq          # (.., n/2)
    cos = jnp.tile(jnp.cos(ang), 2 * heads)
    sin = jnp.tile(jnp.sin(ang), 2 * heads)
    first = jnp.arange(x.shape[-1]) % n < n // 2
    xf = x.astype(jnp.float32)
    turned = jnp.where(first, -jnp.roll(xf, -(n // 2), -1),
                       jnp.roll(xf, n // 2, -1))
    return (xf * cos + turned * sin).astype(x.dtype)


ATTN_LEAVES = ("n1", "n2", "wq", "wk", "wv", "wo")
MLP_LEAVES = ("n3", "n4", "mlp_gate_up", "mlp_down")


def _qkv(w, c, h, pos):
    """h (.., d) at positions pos (..) -> q (.., H, D) rotated, and the
    position's cache entries k rotated and v, each (.., H * D)."""
    q = rope_half(h @ w["wq"], pos, c.rope_theta, c.heads)
    k = rope_half(h @ w["wk"], pos, c.rope_theta, c.heads)
    return (q.reshape(h.shape[:-1] + (c.heads, c.head_dim)), k,
            h @ w["wv"])


def _mlp_block(x, params, c, l):
    import jax
    with jax.named_scope(f"layer{l}/mlp"):
        w = {n: params[n][l] for n in MLP_LEAVES}
        y = gated_mlp(rms_norm(x, w["n3"], c.norm_eps), w["mlp_gate_up"],
                      w["mlp_down"])
        return x + rms_norm(y, w["n4"], c.norm_eps)


def _planes(cache):
    """Every leaf (rows, U, T, HD) as (rows * U, T, HD): pass u of row s
    is row s * U + u. No byte moves."""
    return {n: a.reshape((-1,) + a.shape[2:]) for n, a in cache.items()}


def _rows(cache, like):
    """`_planes` undone: the leaves in the shapes `like` has them."""
    return {n: a.reshape(like[n].shape) for n, a in cache.items()}


def _loop(c, params, x, cache, attend):
    """`ut_steps` passes of the stack over the stream x, the final norm
    after each: `attend(u, l, w, h, cache) -> (cache, attention's output
    before W_o)` is the program's own way to write and read plane (u, l)."""
    import jax

    def one_pass(u, carry):
        x, cache = carry
        cache = dict(cache)
        for l in range(c.layers):
            with jax.named_scope(f"layer{l}/attn"):
                w = {n: params[n][l] for n in ATTN_LEAVES}
                h = rms_norm(x, w["n1"], c.norm_eps)
                cache, att = attend(u, l, w, h, cache)
                x = x + rms_norm(att.astype(x.dtype) @ w["wo"], w["n2"],
                                 c.norm_eps)
            x = _mlp_block(x, params, c, l)
        with jax.named_scope("head/loop_norm"):
            x = rms_norm(x, params["nf"], c.norm_eps)
        return x, cache

    return jax.lax.fori_loop(0, c.ut_steps, one_pass, (x, cache))


def _head(params, x):
    import jax
    import jax.numpy as jnp
    with jax.named_scope("head"):
        return jnp.dot(x, params["head"], preferred_element_type=jnp.float32)


def _count(c, passes_of, read):
    import jax.numpy as jnp
    return {"loop": jnp.stack([passes_of * c.ut_steps, read]).astype(
        jnp.int32)}


# ---------------------------------------------------------------------------
# the three programs
# ---------------------------------------------------------------------------
def _make_chunk(config, window, extent, fresh):
    """The prefill step over one window-sized slice a lane. `fresh` is the
    prefill at offset 0 (`prefill(params, cache, tokens, lengths,
    slot_rows)`): attention is the window's own causal product and nothing
    is read. Else the chunk at an offset (`chunk_prefill(params, cache,
    tokens, offsets, nvalid, slot_rows)`): the chunk's K and V are written
    first and its queries read plane (u, l) through the paged kernel, as
    W / CHUNK_QUERIES lanes of CHUNK_QUERIES queries a lane. Lanes are
    PREFILL lanes with their pool rows as data; an idle lane carries the
    garbage row. Both return (cache, logits of each lane's last position,
    counters). `extent` bounds nothing: the read follows the live blocks."""
    import jax
    import jax.numpy as jnp
    from ..ops import fused as _fused
    c = config
    W, T, U = int(window), c.max_len, c.ut_steps
    if not 1 <= W <= T:
        raise ServeError(f"prefill window {W} outside [1, max_len={T}]")
    H, D = c.heads, c.head_dim
    Q = math.gcd(W, CHUNK_QUERIES)
    scale = 1.0 / math.sqrt(D)

    def core(params, cache, tokens, offsets, nvalid, rows):
        B = tokens.shape[0]
        like, cache = cache, _planes(cache)
        with jax.named_scope("embed"):
            G = like["k0"].shape[0] - 1                  # garbage row
            j = jnp.arange(W)
            valid = j[None, :] < nvalid[:, None]                 # (B, W)
            wrows = jnp.where(valid, rows[:, None], G) * U
            pos = offsets[:, None] + j[None, :]
            wpos = jnp.clip(pos, 0, T - 1)
            x = params["emb"][tokens]                            # (B, W, d)
            if fresh:
                mask = (j[:, None] >= j[None, :])[None] & valid[:, None, :]
            else:
                # lane (b, i) holds queries i * Q .. i * Q + Q - 1 of lane b
                first = (offsets[:, None]
                         + jnp.arange(0, W, Q)[None, :]).reshape(-1)
                lane_rows = jnp.repeat(rows, W // Q) * U

        def attend(u, l, w, h, cache):
            q, k, v = _qkv(w, c, h, pos)
            kc = cache[f"k{l}"].at[wrows + u, wpos].set(k)
            vc = cache[f"v{l}"].at[wrows + u, wpos].set(v)
            cache[f"k{l}"], cache[f"v{l}"] = kc, vc
            if fresh:
                sco = jnp.einsum("bqhd,bkhd->bhqk", q,
                                 k.reshape(B, W, H, D),
                                 preferred_element_type=jnp.float32) * scale
                p = jax.nn.softmax(jnp.where(mask[:, None], sco, -1e30), -1)
                att = jnp.einsum("bhqk,bkhd->bqhd", p,
                                 v.reshape(B, W, H, D))
            else:
                att = _fused.paged_attention(
                    q.reshape(B * (W // Q), Q, H, D), kc, vc, first, None,
                    rows=lane_rows + u, scale=scale)
            return cache, att.reshape(B, W, H * D)

        x, cache = _loop(c, params, x, cache, attend)
        with jax.named_scope("head"):
            last = x[jnp.arange(B), jnp.maximum(nvalid - 1, 0)]
            counted = _count(c, jnp.sum(nvalid), jnp.zeros((), jnp.int32))
        return _rows(cache, like), _head(params, last), counted

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
    length (the new token's K and V land at position `lengths` of every
    plane); an idle lane writes and reads the garbage row."""
    import jax
    import jax.numpy as jnp
    from ..ops import fused as _fused
    c = config
    U = c.ut_steps
    scale = 1.0 / math.sqrt(c.head_dim)

    def micro(params, cache, tokens, lengths, active):
        S = tokens.shape[0]
        like, cache = cache, _planes(cache)
        # (every equation runs under one of the program's scopes, the
        # lanes' bookkeeping too: `profiler.program_scopes` names the
        # device's time by them)
        with jax.named_scope("embed"):
            rows = jnp.where(active, jnp.arange(S), S) * U   # garbage = S
            wpos = jnp.clip(lengths, 0, c.max_len - 1)
            x = params["emb"][tokens]                            # (S, d)

        def attend(u, l, w, h, cache):
            q, k, v = _qkv(w, c, h, lengths)
            kc = cache[f"k{l}"].at[rows + u, wpos].set(k)
            vc = cache[f"v{l}"].at[rows + u, wpos].set(v)
            cache[f"k{l}"], cache[f"v{l}"] = kc, vc
            att = _fused.paged_attention(q[:, None], kc, vc, lengths, None,
                                         rows=rows + u, scale=scale)
            return cache, att.reshape(S, c.kv_width)

        x, cache = _loop(c, params, x, cache, attend)
        with jax.named_scope("head"):
            counted = _count(
                c, jnp.sum(active, dtype=jnp.int32),
                jnp.sum(jnp.where(active, lengths + 1, 0)) * (U * c.layers))
        return _rows(cache, like), _head(params, x), counted

    return micro


class LoopedDecoder(_sm.SparseMoEDecoder):
    """The model side of the continuous engine for the looped decoder: the
    fifth implementer of the engine's model protocol. The pool, the program
    table, the decode scan and `reference_generate` are
    `SparseMoEDecoder`'s; the initializer, the chunk and micro-step
    builders, the cache spec and the counters are this block's."""

    counters = {"loop": ("stack_passes", "plane_positions_read")}
    _init_params = staticmethod(init_looped_params)
    _make_chunk = staticmethod(_make_chunk)
    _make_micro = staticmethod(_make_micro)

    def __init__(self, config, params=None, seed=0):
        if config.early_exit_threshold != 1.0:
            raise ServeError(
                f"early_exit_threshold {config.early_exit_threshold}: below "
                f"1.0 a token leaves the loop at the first pass whose "
                f"cumulative exit mass reaches the threshold, so the lanes "
                f"of one wave stand at different depths; what is missing is "
                f"a decode program with a pass count a lane, lane "
                f"bookkeeping (`_advance_lanes`) that knows it, and a rule "
                f"for the cache planes that a lane which left early never "
                f"wrote. At 1.0 every token runs all {config.ut_steps} "
                f"passes and the last one's logits are served")
        super().__init__(config, params, seed)

    def cache_spec(self):
        """The cache leaves of one slot row: K and V a layer, the passes'
        planes side by side, every one a `full` leaf."""
        c = self.config
        return [CacheLeaf(f"{n}{l}", (c.ut_steps, c.max_len, c.kv_width),
                          c.dtype, "full", c.max_len)
                for l in range(c.layers) for n in ("k", "v")]

    def chunk_prefill_program(self, window, extent=None):
        """`extent` is the bound of a dense read; the read here is the
        paged kernel's, on the live-block grid, so every extent is the one
        program."""
        w = int(window)
        return self._program(
            ("chunk", w),
            lambda: self._make_chunk(self.config, w, self.config.max_len,
                                     False),
            f"chunk_prefill[w={w}]")
