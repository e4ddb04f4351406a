"""Continuous (iteration-level) batching for autoregressive models.

`serve.Server` (PR 3) batches STATELESS single-shot requests: a request
joins exactly one batch, the batch runs one program, done. Autoregressive
models break that shape — one request is N sequential decode iterations
over private KV state, requests finish at different times, and a static
batch must run every member to the LONGEST member's length while admitted
work waits whole batches. This module is the Orca-style answer, rebuilt
JAX-native per the PAPER.md survey (one compiled decode program, state as
plain device buffers, zero retraces):

  * **Slot memory** (`serve.kv_pool.KVCachePool`): a fixed-shape KV slab
    carved once; each admitted request claims a slot ROW; join/leave is
    host bookkeeping and can never change a compiled program's shapes.
  * **Two fixed-shape programs** compiled once at warmup and reused for
    every mixed batch: `prefill` (writes a claimed slot's prompt KV page +
    emits the first token, pad lanes scatter into the pool's garbage row)
    and `decode` (steps ALL slots one token — inactive slots are masked
    lanes, their writes land in the garbage row). Both donate the KV
    buffers, so updates are in-place `dynamic_update_slice` scatters on
    accelerators (the `.at[rows, layer, pos].set(...)` idiom).
  * **Iteration-level scheduling**: every engine iteration admits
    waiting requests under a prefill token budget
    (`MXNET_SERVE_PREFILL_BUDGET` — bounds how much prefill work may
    delay in-flight decode iterations), dispatches their prefill
    programs and one decode wave for every slot with budget left, and
    only THEN reads back what the PREVIOUS iteration dispatched (first
    tokens, the wave's tokens), resolves and retires (a finished
    request's slot frees at that read, not at batch end) — so the
    device runs wave n+1 while the host does wave n's work. A wave's
    `tokens`, `lengths` and `steps_left` never pass through the host:
    they live on the device between waves (`_join_lanes`,
    `_advance_lanes`). Admission is DEADLINE-AWARE, not FIFO: waiting
    requests are granted slots earliest-deadline-first (SLO-aware
    admission over the PR-3 deadline plumbing), and a request whose
    deadline expires while waiting fails fast with `RequestTimeout`.
  * **Zero retraces after warmup** is asserted the PR-3 way: the
    `programs_compiled` counter and `compile_cache_size()` must stay flat
    over any join/leave pattern (tests/test_continuous.py drives ragged
    mixed traffic and checks both).
  * **O(load) warmup**: `deploy.maybe_enable_compile_cache()` wires
    `MXNET_COMPILE_CACHE_DIR` onto jax's persistent compilation cache
    before the first compile, so a second replica (or a restart) loads
    the serialized executables instead of recompiling (`setup_s` in
    PERF.md §2 is where a cell pays for it).

Tracing: one request = ONE trace across its N iterations. The root
`serve.request` context is minted at `submit()` (PR-13 plumbing); at
retirement the engine records `serve.queue` (submit -> admission),
`serve.prefill` (admission -> first token) and `serve.decode` (first ->
last token, N iterations) as its children and closes the root — while
the profiler collects, the whole request renders as a single tree in the
Chrome trace.

The bundled `CachedDecoder` is a small pre-norm transformer decoder over
the slot pool — the LLM-shaped model side for tests and the benchmark; any
object with the same `prefill`/`decode`/`compile_cache_size` contract
serves.

Decode raw speed (the ROADMAP item-2 axes), all inside the SAME two
fixed-shape programs so the zero-retrace contract survives untouched:

  * **Sampling as data**: temperature/top-k/top-p and a per-lane PRNG
    key ride into the compiled programs as (S,)-shaped ARRAYS (the PR-9
    key-as-data idiom) — a mixed greedy/sampled batch is just different
    array values through one program, whose sampler sorts the
    vocabulary only in a wave that holds a sampled lane (one on-device
    branch, `serve/sampling.py`). The per-token key is
    `fold_in(request_key, position)`, a pure function of the token's
    page position, so the key schedule is WAVE-INVARIANT: the engine
    (any decode_steps, any join/leave pattern) and the 1-slot
    `reference_generate` twin draw identical tokens.
  * **Speculative decoding** (`draft_tokens > 0`): each scan micro-step
    drafts k tokens by prompt-lookup (latest n-gram match over the
    lane's token page history, passed in as a fixed (S, max_len) array)
    and verifies them with ONE chunked forward over the k+1 positions —
    KV for the whole chunk scatters into the slot page, queries mask to
    `[0, cur_len + j]`. The longest draft prefix that EXACTLY matches
    the base model's own choice is emitted plus one bonus token, so
    output token streams are identical to non-speculative decoding for
    greedy AND sampled lanes (exact-match verification); acceptance
    counts are in-scan data, so acceptance variance never changes
    program shapes. Rejected-position KV is dead by construction: the
    next chunk overwrites positions `[cur_len, cur_len+k]` before any
    mask can reach them.
  * **Paged attention**: the decode-side attention read is
    `ops.fused.paged_attention` — a Pallas kernel over the slotted slab
    with block-sparse reads clamped to each lane's live prefix (TPU, or
    `MXNET_FUSION_INTERPRET=1` for CPU CI) and the identical
    masked-einsum jnp fallback elsewhere.
  * **int8 KV** (`kv_dtype="int8"`): the pool stores int8 codes + f32
    per-position scales; writes quantize by per-position absmax over
    (heads, head_dim), reads dequantize in the attention op. A
    position's scale is written exactly once with its KV, so the stale-
    scale story is the stale-KV story (same mask, same poison test).
"""
from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future

import numpy as _np

from ..base import MXNetError, get_env
from .. import fault as _fault
from .. import profiler as _profiler
from .. import sanitize as _sanitize
from ..telemetry import (record_span, span as _span, NO_SPAN,
                         trace as _trace, mem_on_oom, mem_install_oom_hook)
from .batcher import (ServeError, QueueFullError, RequestTimeout,
                      ServerClosed, ReplicaDraining, _fail, _profiler_on)
from .metrics import SERVE_STATS, _STATS_LOCK, percentile
from .kv_pool import CacheKindError, KVCachePool, SlotsFullError
from .prefix_cache import PrefixCache
from .sampling import (sample_first as _sample_first,
                       sample_first_program as _sample_first_program,
                       sample_tokens as _sample_tokens,
                       seed_key as _seed_key)

__all__ = ["DecoderConfig", "CachedDecoder", "ContinuousEngine",
           "RequestTiming", "init_decoder_params"]


# ---------------------------------------------------------------------------
# model: a small cached-KV transformer decoder (greedy, deterministic)
# ---------------------------------------------------------------------------
class DecoderConfig:
    """Static shape/config record for `CachedDecoder` (all ints; nothing
    here ever becomes a tracer)."""

    def __init__(self, vocab=256, embed=64, layers=2, heads=4,
                 head_dim=16, mlp_hidden=None, max_len=128,
                 dtype="float32"):
        self.vocab = int(vocab)
        self.embed = int(embed)
        self.layers = int(layers)
        self.heads = int(heads)
        self.head_dim = int(head_dim)
        self.mlp_hidden = int(mlp_hidden if mlp_hidden is not None
                              else 4 * embed)
        self.max_len = int(max_len)
        self.dtype = str(dtype)
        if self.heads * self.head_dim != self.embed:
            raise ServeError(
                f"heads*head_dim ({self.heads}x{self.head_dim}) must "
                f"equal embed ({self.embed})")

    def as_dict(self):
        return {k: getattr(self, k) for k in
                ("vocab", "embed", "layers", "heads", "head_dim",
                 "mlp_hidden", "max_len", "dtype")}


def init_decoder_params(config, seed=0):
    """Deterministic random params (pytree of jnp arrays, layer-stacked
    on a leading L axis so the layer loop indexes one buffer)."""
    import jax
    import jax.numpy as jnp
    c = config
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    s = 1.0 / _np.sqrt(c.embed)
    m = 1.0 / _np.sqrt(c.mlp_hidden)

    def rnd(key, shape, scale):
        return (jax.random.normal(key, shape) * scale).astype(c.dtype)

    return {
        "emb": rnd(keys[0], (c.vocab, c.embed), 1.0),
        "pos": rnd(keys[1], (c.max_len, c.embed), 0.1),
        "wq": rnd(keys[2], (c.layers, c.embed, c.embed), s),
        "wk": rnd(keys[3], (c.layers, c.embed, c.embed), s),
        "wv": rnd(keys[4], (c.layers, c.embed, c.embed), s),
        "wo": rnd(keys[5], (c.layers, c.embed, c.embed), s),
        "w1": rnd(keys[6], (c.layers, c.embed, c.mlp_hidden), s),
        "w2": rnd(keys[7], (c.layers, c.mlp_hidden, c.embed), m),
        "ln1": jnp.ones((c.layers, c.embed), dtype=c.dtype),
        "ln2": jnp.ones((c.layers, c.embed), dtype=c.dtype),
        "lnf": jnp.ones((c.embed,), dtype=c.dtype),
    }


def _rmsnorm(x, scale):
    import jax.numpy as jnp
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * scale / jnp.sqrt(var + 1e-6)


def _kv_split(cache):
    """A pool buffer is either a raw slab or a (codes, scales) pair
    (int8 mode); normalize to (slab, scales_or_None)."""
    if isinstance(cache, tuple):
        return cache
    return cache, None


def _quantize_kv(val):
    """int8 KV codes + f32 scale per written (lane, position): absmax
    over (heads, head_dim). The scale is final at write time — a
    position is quantized exactly once, with its KV."""
    import jax.numpy as jnp
    a = jnp.max(jnp.abs(val), axis=(-2, -1))
    s = jnp.maximum(a.astype(jnp.float32), 1e-8) / 127.0
    q = jnp.clip(jnp.round(val / s[..., None, None]), -127, 127)
    return q.astype(jnp.int8), s


def _store_page(cache, rows, l, W, val):
    """Scatter a (P, W, H, D) KV page into [rows, l, :W] (quantizing
    into codes+scales when the pool is int8)."""
    slab, scales = _kv_split(cache)
    if scales is None:
        return slab.at[rows, l, :W].set(val)
    q, s = _quantize_kv(val)
    return (slab.at[rows, l, :W].set(q), scales.at[rows, l, :W].set(s))


def _store_pos(cache, rows, l, wpos, val):
    """Scatter KV at explicit positions (rows/wpos broadcast to the
    leading dims of `val`), quantizing when the pool is int8."""
    slab, scales = _kv_split(cache)
    if scales is None:
        return slab.at[rows, l, wpos].set(val)
    q, s = _quantize_kv(val)
    return (slab.at[rows, l, wpos].set(q),
            scales.at[rows, l, wpos].set(s))


def _paged_attn(k_cache, v_cache, q, lengths, l):
    """Decode-side attention read over the slot slab via
    `ops.fused.paged_attention`: Pallas block-sparse kernel on TPU (or
    interpret-mode CI), identical masked-einsum jnp fallback elsewhere.
    q is (S, C, H, D); chunk offset j reads positions [0, lengths+j].

    The slab goes to the kernel WHOLE, as the donated buffer it is: the
    kernel's grid is one step per live (lane, block) pair, so what a
    lane has not reached costs no step and no byte, and a slice of the
    slab to bound the read would be a copy of it (once a layer for K and
    once for V: the write of layer `l` comes before its read)."""
    from ..ops import fused as _fused
    k_slab, k_scale = _kv_split(k_cache)
    v_slab, v_scale = _kv_split(v_cache)
    return _fused.paged_attention(q, k_slab, v_slab, lengths, l,
                                  k_scale=k_scale, v_scale=v_scale)


def _make_prefill(config, window=None):
    """Build the prefill step: full causal forward over the padded prompt
    page, KV written into the claimed slot rows, first token emitted.

    Shapes are FIXED by (P lanes, window, pool rows): the compiled
    program is reused for every admission wave — a lane that has no
    request this wave carries slot_row = garbage and its writes vanish.

    `window` (default max_len) is the prompt page width: attention and
    the KV write cover positions [0, window), so a serving config with
    short prompts pays O(window^2), not O(max_len^2), per wave. Slot
    positions past the window keep the PREVIOUS tenant's bytes — that is
    safe by the decode mask (reads clamp to the current request's
    `[0, cur_len]`), and exactly what the poison-fill isolation test
    proves."""
    import jax
    import jax.numpy as jnp
    c = config
    W = int(window if window is not None else c.max_len)
    if not 1 <= W <= c.max_len:
        raise ServeError(f"prefill window {W} outside [1, {c.max_len}]")
    scale = 1.0 / _np.sqrt(c.head_dim)

    def prefill(params, k_cache, v_cache, tokens, lengths, slot_rows):
        # tokens (P, W) int32, lengths (P,) int32, slot_rows (P,) int32
        P = tokens.shape[0]
        with jax.named_scope("embed"):
            x = params["emb"][tokens] + params["pos"][None, :W]
        pos = jnp.arange(W)
        key_valid = pos[None, :] < lengths[:, None]            # (P, W)
        causal = pos[:, None] >= pos[None, :]                  # (W, W)
        mask = causal[None, None] & key_valid[:, None, None]   # (P,1,W,W)
        for l in range(c.layers):
            with jax.named_scope(f"layer{l}/attn"):
                h = _rmsnorm(x, params["ln1"][l])
                q = (h @ params["wq"][l]).reshape(P, W, c.heads, c.head_dim)
                k = (h @ params["wk"][l]).reshape(P, W, c.heads, c.head_dim)
                v = (h @ params["wv"][l]).reshape(P, W, c.heads, c.head_dim)
                # positions past `lengths` hold pad-token KV, positions past
                # the window hold the previous tenant's bytes; both are
                # unreachable through the decode mask
                k_cache = _store_page(k_cache, slot_rows, l, W, k)
                v_cache = _store_page(v_cache, slot_rows, l, W, v)
                scores = jnp.einsum("pqhd,pkhd->phqk", q, k) * scale
                scores = jnp.where(mask, scores, -1e30)
                att = jnp.einsum("phqk,pkhd->pqhd",
                                 jax.nn.softmax(scores, axis=-1), v)
                x = x + att.reshape(P, W, c.embed) @ params["wo"][l]
            with jax.named_scope(f"layer{l}/mlp"):
                h2 = _rmsnorm(x, params["ln2"][l])
                x = x + jax.nn.gelu(h2 @ params["w1"][l]) @ params["w2"][l]
        with jax.named_scope("head"):
            xf = _rmsnorm(x, params["lnf"])
            last = xf[jnp.arange(P), jnp.maximum(lengths - 1, 0)]  # (P, E)
            logits = last @ params["emb"].T
        # the FIRST token is drawn from these logits by the caller
        # (`_sample_first`, the process-shared sampler program) at fold
        # position lengths-1, continuing into decode at `lengths`
        return k_cache, v_cache, logits

    return prefill


def _make_chunk_prefill(config, window=None, extent=None):
    """Build the CHUNK prefill step: one window-sized slice of a prompt,
    scattered into its slot page at an arbitrary offset — the PR-17
    spec-decode verify-chunk idiom (explicit-position `_store_pos`
    writes + a paged-attention read clamped to `[0, offset + j]`)
    widened from draft+1 to `window` positions. This is how prompts
    longer than `prefill_window` stream in across waves, and how a
    prefix-cache hit prefills only its suffix.

    Unlike `_make_prefill`, lanes here are POOL ROWS (lane s writes row
    s, exactly like decode): ONE fixed-shape dispatch advances EVERY
    slot with pending chunk work — a long cold prompt mid-stream and a
    cache hit's suffix alike — and a lane with `nvalid == 0` scatters
    into the garbage row. Queries at chunk offset j attend over slab
    positions [0, offsets + j]; positions below `offsets` must already
    hold the prefix KV (earlier chunks, or a prefix-cache row copy).
    Logits come from each lane's LAST valid chunk position — only
    meaningful for a lane whose chunk ends at its prompt tail, which is
    exactly when the engine samples the first token from them.

    `extent`, the bound a wave's furthest lane satisfies (offset +
    nvalid <= extent), is accepted, range-checked and NOT part of the
    program: the paged kernel visits a lane's live blocks and no other,
    so the bound is already the data's (`_paged_attn`), and every extent
    is the same traced function. The engine's extent ladder is for the
    models whose chunk reads the cached positions densely in plain XLA
    (`SparseMoEDecoder`, `DeltaMoEDecoder`), where the extent does bound
    the work."""
    import jax
    import jax.numpy as jnp
    c = config
    W = int(window if window is not None else c.max_len)
    if not 1 <= W <= c.max_len:
        raise ServeError(f"chunk window {W} outside [1, {c.max_len}]")
    if extent is not None and not W <= int(extent) <= c.max_len:
        raise ServeError(
            f"chunk extent {extent} outside [window={W}, {c.max_len}]")

    def chunk_prefill(params, k_cache, v_cache, tokens, offsets, nvalid):
        # tokens (S, W) int32 chunk slice; offsets (S,) page position of
        # tokens[:, 0]; nvalid (S,) valid token count (0 = idle lane)
        S = tokens.shape[0]
        T = c.max_len
        j = jnp.arange(W)
        wposs = jnp.clip(offsets[:, None] + j[None, :], 0, T - 1)
        valid = j[None, :] < nvalid[:, None]                    # (S, W)
        rows = jnp.where(valid, jnp.arange(S)[:, None], S)   # garbage=S
        with jax.named_scope("embed"):
            x = params["emb"][tokens] + params["pos"][wposs]
        for l in range(c.layers):
            with jax.named_scope(f"layer{l}/attn"):
                h = _rmsnorm(x, params["ln1"][l])
                q = (h @ params["wq"][l]).reshape(S, W, c.heads, c.head_dim)
                k = (h @ params["wk"][l]).reshape(S, W, c.heads, c.head_dim)
                v = (h @ params["wv"][l]).reshape(S, W, c.heads, c.head_dim)
                k_cache = _store_pos(k_cache, rows, l, wposs, k)
                v_cache = _store_pos(v_cache, rows, l, wposs, v)
                att = _paged_attn(k_cache, v_cache, q, offsets, l)
                x = x + att.reshape(S, W, c.embed) @ params["wo"][l]
            with jax.named_scope(f"layer{l}/mlp"):
                h2 = _rmsnorm(x, params["ln2"][l])
                x = x + jax.nn.gelu(h2 @ params["w1"][l]) @ params["w2"][l]
        with jax.named_scope("head"):
            xf = _rmsnorm(x, params["lnf"])
            last = xf[jnp.arange(S), jnp.maximum(nvalid - 1, 0)]
            logits = last @ params["emb"].T
        return k_cache, v_cache, logits

    return chunk_prefill


def _copy_block(max_len):
    """Positions one step of `_copy_slot_rows` moves: one lane width (the
    paged kernel's block) or, where 128 does not divide `max_len`, its
    largest power-of-two divisor."""
    return 128 if max_len % 128 == 0 else max_len & -max_len


def _copy_slot_rows(k_cache, v_cache, src_rows, dst_rows, lengths):
    """Slab-to-slab KV copy of the positions a prefix holds — the
    prefix-cache data mover: a cache row lands in a claimed request slot
    at admission (the memory-bound copy that replaces compute-bound
    prefill attention) and a retiring request's slot lands in a cache row
    at publish. Fixed (C,) lane shapes; lane i moves positions
    [0, lengths[i]) of every layer from `src_rows[i]` to `dst_rows[i]` in
    whole `_copy_block`s, so its last block may run past `lengths[i]` up
    to the block's edge (positions no read is allowed to see until they
    are rewritten) and a lane of length 0 moves nothing. One `while` on
    the device over the live blocks of all lanes, each step a
    `dynamic_slice` and an in-place `dynamic_update_slice` of the donated
    slab: the work follows the bytes, the trip count is data. int8 pools
    move codes AND scales over the same positions, so a copied position
    dequantizes bit-identically to the original."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    # slabs, and an int8 pool's scales: all (rows, layers, positions, ...)
    bufs, pools = jax.tree_util.tree_flatten((k_cache, v_cache))
    max_len = bufs[0].shape[2]
    bt = _copy_block(max_len)
    live = (jnp.clip(lengths, 0, max_len) + bt - 1) // bt
    ends = jnp.cumsum(live)

    def move(step, bufs):
        lane = jnp.sum(ends <= step)
        pos = (step - ends[lane] + live[lane]) * bt
        src, dst = src_rows[lane], dst_rows[lane]
        out = []
        for buf in bufs:
            tail = (0,) * (buf.ndim - 3)
            piece = lax.dynamic_slice(
                buf, (src, 0, pos) + tail,
                (1, buf.shape[1], bt) + buf.shape[3:])
            out.append(lax.dynamic_update_slice(buf, piece,
                                                (dst, 0, pos) + tail))
        return out

    return pools.unflatten(lax.fori_loop(0, ends[-1], move, bufs))


def _make_decode(config, steps=1, eos_id=None):
    """Build the decode step: EVERY pool slot advances up to `steps`
    tokens inside ONE compiled program (`lax.scan` over the micro-step).
    Fixed (S,) shapes and a FIXED step count, so join/leave — and lanes
    finishing mid-scan — never change the program; inactive lanes
    (steps_left == 0) write to the garbage row and their outputs are
    ignored. `steps > 1` amortizes the per-dispatch host cost over K
    tokens (the engine's `decode_steps` knob): admission/retirement move
    to wave granularity, TTFT stays prefill-bound.

    Signature: `decode(params, k_cache, v_cache, tokens, lengths,
    steps_left, temps, top_ks, top_ps, keys) -> (k_cache, v_cache,
    out_tokens (steps, S), emitted)`. `emitted[s]` is the EXACT number
    of tokens lane s produced this wave (rows [0:emitted] of its
    column) — counted in-scan, because deriving it from the steps_left
    delta would overcount when `eos_id` zeroes a lane's remaining
    budget mid-wave. Sampling params ride as (S,) data (greedy lane =
    temp 0), so a mixed batch replays the one program."""
    import jax
    import jax.numpy as jnp
    c = config

    def micro(params, k_cache, v_cache, tokens, lengths, active,
              temps, top_ks, top_ps, keys):
        # one token for every active lane. tokens (S,) int32 last emitted
        # token; lengths (S,) int32 current cache length (the new token's
        # KV lands at position `lengths`); active (S,) bool
        S = tokens.shape[0]
        T = c.max_len
        # (every equation of the program runs under one of its scopes, the
        # lanes' bookkeeping too: `profiler.program_scopes` names the
        # device's time by them)
        with jax.named_scope("embed"):
            rows = jnp.where(active, jnp.arange(S), S)   # garbage row = S
            wpos = jnp.clip(lengths, 0, T - 1)
            x = params["emb"][tokens] + params["pos"][wpos]  # (S, E)
        # attention reads positions 0..lengths INCLUSIVE (the new token's
        # KV is written before the read); anything past that — pad-token
        # KV from prefill or a previous tenant's garbage — is masked
        # inside paged_attention's [0, lengths + chunk_offset] clamp
        for l in range(c.layers):
            with jax.named_scope(f"layer{l}/attn"):
                h = _rmsnorm(x, params["ln1"][l])
                q = (h @ params["wq"][l]).reshape(S, c.heads, c.head_dim)
                k = (h @ params["wk"][l]).reshape(S, c.heads, c.head_dim)
                v = (h @ params["wv"][l]).reshape(S, c.heads, c.head_dim)
                k_cache = _store_pos(k_cache, rows, l, wpos, k)
                v_cache = _store_pos(v_cache, rows, l, wpos, v)
                att = _paged_attn(k_cache, v_cache, q[:, None], lengths,
                                  l)[:, 0]
                x = x + att.reshape(S, c.embed) @ params["wo"][l]
            with jax.named_scope(f"layer{l}/mlp"):
                h2 = _rmsnorm(x, params["ln2"][l])
                x = x + jax.nn.gelu(h2 @ params["w1"][l]) @ params["w2"][l]
        with jax.named_scope("head"):
            logits = _rmsnorm(x, params["lnf"]) @ params["emb"].T
        nxt = _sample_tokens(logits, temps, top_ks, top_ps, keys,
                             lengths)
        with jax.named_scope("sampler"):
            return k_cache, v_cache, jnp.where(active, nxt, 0)

    def decode(params, k_cache, v_cache, tokens, lengths, steps_left,
               temps, top_ks, top_ps, keys):
        def step(carry, _):
            k_cache, v_cache, last, lens, left, emitted = carry
            with jax.named_scope("sampler"):
                act = left > 0
            k_cache, v_cache, nxt = micro(params, k_cache, v_cache,
                                          last, lens, act,
                                          temps, top_ks, top_ps, keys)
            # the lanes' carry: what the sampler's token does to each
            with jax.named_scope("sampler"):
                new_left = jnp.where(act, left - 1, left)
                if eos_id is not None:
                    new_left = jnp.where(act & (nxt == eos_id), 0,
                                         new_left)
                lens = jnp.where(act, lens + 1, lens)
                last = jnp.where(act, nxt, last)
                emitted = emitted + act.astype(jnp.int32)
            return (k_cache, v_cache, last, lens, new_left, emitted), nxt

        zero = jnp.zeros_like(steps_left)
        (k_cache, v_cache, _, _, _, emitted), toks = jax.lax.scan(
            step, (k_cache, v_cache, tokens, lengths, steps_left, zero),
            None, length=steps)
        return k_cache, v_cache, toks, emitted

    return decode


def _make_spec_decode(config, steps=1, eos_id=None, draft=2):
    """Build the SPECULATIVE decode step: each of the `steps` scan
    micro-steps advances every active lane by up to `draft + 1` tokens
    — k drafted by prompt-lookup (latest n-gram match in the lane's
    token history page) plus one bonus token, verified by ONE chunked
    forward over the k+1 positions. Acceptance is EXACT match against
    the base model's own next-token choice, so the emitted stream is
    token-identical to non-speculative decode (greedy and sampled);
    acceptance counts are in-scan DATA, so acceptance variance never
    changes program shapes and the zero-retrace contract holds.

    Safety of the chunk writes:
      * a REJECTED position's KV is stale, but the lane's next chunk
        starts at its new length and rewrites [len, len+k] before any
        mask can expose them;
      * near the page end, write positions clip to max_len-1 and may
        collide — only queries whose outputs are DISCARDED (offset >=
        emitted count) ever sit past max_len-2, and a query only reads
        positions <= its own, so the clipped junk is unreachable from
        any emitted token.

    Signature: `spec(params, k_cache, v_cache, tokens, lengths,
    steps_left, temps, top_ks, top_ps, keys, token_buf) -> (k_cache,
    v_cache, tok_blocks (steps, S, draft+1), n_emits (steps, S),
    emitted (S,), accepted (S,), rejected (S,))`. `token_buf` is the
    (S, max_len) token history page (prompt + generated so far; entries
    [0, lengths] valid) — the draft source, updated in-scan exactly as
    a host rebuild would, so wave boundaries stay invisible. Lane s's
    wave output is rows `tok_blocks[i, s, :n_emits[i, s]]` in scan
    order; accepted/rejected count draft tokens for telemetry."""
    import jax
    import jax.numpy as jnp
    c = config
    draft = int(draft)
    if draft < 1:
        raise ServeError(f"draft must be >= 1, got {draft}")
    C = draft + 1

    def micro(params, k_cache, v_cache, last, lens, act, left,
              temps, top_ks, top_ps, keys, token_buf):
        S = last.shape[0]
        T = c.max_len
        rows = jnp.where(act, jnp.arange(S), S)          # garbage row
        coffs = jnp.arange(C)
        # -- prompt-lookup draft: the LATEST earlier occurrence of the
        # current tail token predicts its historical successors
        idx = jnp.arange(T)
        hit = (idx[None, :] < lens[:, None]) & (token_buf == last[:, None])
        p = jnp.max(jnp.where(hit, idx[None, :], -1), axis=1)    # (S,)
        dsrc = p[:, None] + 1 + jnp.arange(draft)[None, :]       # (S, k)
        ok = (p[:, None] >= 0) & (dsrc <= lens[:, None])
        cand = jnp.take_along_axis(token_buf, jnp.clip(dsrc, 0, T - 1),
                                   axis=1)
        drafts = jnp.where(ok, cand, last[:, None])              # (S, k)
        # -- ONE verify forward over the whole chunk [last, drafts...]
        chunk = jnp.concatenate([last[:, None], drafts], axis=1)  # (S, C)
        wposs = jnp.clip(lens[:, None] + coffs[None, :], 0, T - 1)
        with jax.named_scope("embed"):
            x = params["emb"][chunk] + params["pos"][wposs]   # (S, C, E)
        for l in range(c.layers):
            with jax.named_scope(f"layer{l}/attn"):
                h = _rmsnorm(x, params["ln1"][l])
                q = (h @ params["wq"][l]).reshape(S, C, c.heads, c.head_dim)
                k = (h @ params["wk"][l]).reshape(S, C, c.heads, c.head_dim)
                v = (h @ params["wv"][l]).reshape(S, C, c.heads, c.head_dim)
                k_cache = _store_pos(k_cache, rows[:, None], l, wposs, k)
                v_cache = _store_pos(v_cache, rows[:, None], l, wposs, v)
                att = _paged_attn(k_cache, v_cache, q, lens, l)
                x = x + att.reshape(S, C, c.embed) @ params["wo"][l]
            with jax.named_scope(f"layer{l}/mlp"):
                h2 = _rmsnorm(x, params["ln2"][l])
                x = x + jax.nn.gelu(h2 @ params["w1"][l]) @ params["w2"][l]
        with jax.named_scope("head"):
            logits = _rmsnorm(x, params["lnf"]) @ params["emb"].T
        # -- the base model's own choice at EVERY chunk position, keyed
        # by that position — identical draws to non-spec decode
        positions = (lens[:, None] + coffs[None, :]).reshape(-1)
        base_next = _sample_tokens(
            logits.reshape(S * C, -1),
            jnp.repeat(temps, C), jnp.repeat(top_ks, C),
            jnp.repeat(top_ps, C), jnp.repeat(keys, C, axis=0),
            positions).reshape(S, C)
        # -- accept the longest draft prefix the base model agrees with,
        # plus the bonus token sampled after it; cap to the lane budget
        match = jnp.cumprod(
            (drafts == base_next[:, :draft]).astype(jnp.int32), axis=1)
        n = jnp.minimum(jnp.sum(match, axis=1) + 1, left)
        if eos_id is not None:
            is_eos = (base_next == eos_id) & (coffs[None, :] < n[:, None])
            n = jnp.where(jnp.any(is_eos, axis=1),
                          jnp.argmax(is_eos, axis=1) + 1, n)
        n = jnp.where(act, n, 0)
        new_last = jnp.where(
            act,
            jnp.take_along_axis(base_next,
                                jnp.maximum(n - 1, 0)[:, None],
                                axis=1)[:, 0],
            last)
        new_lens = lens + n
        # -- history page update, exactly what a host rebuild would hold:
        # chunk token at each written position, new tail at new_lens
        buf2 = token_buf.at[jnp.arange(S)[:, None], wposs].set(
            jnp.concatenate([last[:, None], base_next[:, :draft]],
                            axis=1))
        buf2 = buf2.at[jnp.arange(S),
                       jnp.clip(new_lens, 0, T - 1)].set(new_last)
        token_buf = jnp.where(act[:, None], buf2, token_buf)
        return (k_cache, v_cache, token_buf, base_next, n, new_last,
                new_lens)

    def spec(params, k_cache, v_cache, tokens, lengths, steps_left,
             temps, top_ks, top_ps, keys, token_buf):
        def step(carry, _):
            (k_cache, v_cache, last, lens, left, emitted, buf,
             acc, rej) = carry
            act = left > 0
            (k_cache, v_cache, buf, base_next, n, new_last,
             new_lens) = micro(params, k_cache, v_cache, last, lens,
                               act, left, temps, top_ks, top_ps, keys,
                               buf)
            left = jnp.where(act, left - n, left)
            if eos_id is not None:
                left = jnp.where(act & (n > 0) & (new_last == eos_id),
                                 0, left)
            emitted = emitted + n
            acc = acc + jnp.where(act, n - 1, 0)
            rej = rej + jnp.where(act, draft - (n - 1), 0)
            return ((k_cache, v_cache, new_last, new_lens, left,
                     emitted, buf, acc, rej), (base_next, n))

        zero = jnp.zeros_like(steps_left)
        carry0 = (k_cache, v_cache, tokens, lengths, steps_left, zero,
                  token_buf, zero, zero)
        ((k_cache, v_cache, _, _, _, emitted, _, acc, rej),
         (tok_blocks, n_emits)) = jax.lax.scan(step, carry0, None,
                                               length=steps)
        return k_cache, v_cache, tok_blocks, n_emits, emitted, acc, rej

    return spec


def _join_lanes(tokens, lengths, steps_left, first, join, eos_id=None):
    """Lane state for the requests whose prompt ended in a prefill
    program, set on the device: lane s with `join[0, s] >= 0` takes
    `first[join[0, s]]` (that program's `sample_first` output, never
    read by the host before the wave that consumes it) as its last
    token, `join[1, s]` as its cache length and `join[2, s]` as its
    budget — 0 where the first token is `eos_id`, as `_finished` would
    have found on the host. Every other lane keeps what it has."""
    import jax.numpy as jnp
    src, lens, budget = join
    on = src >= 0
    tok = first[jnp.maximum(src, 0)]
    if eos_id is not None:
        budget = jnp.where(tok == eos_id, 0, budget)
    # a request that ends at its first token joins as the idle lane (0, 0, 0)
    live = budget > 0
    return (jnp.where(on, jnp.where(live, tok, 0), tokens),
            jnp.where(on, jnp.where(live, lens, 0), lengths),
            jnp.where(on, budget, steps_left))


def _advance_lanes(tokens, lengths, steps_left, out_tokens, emitted,
                   eos_id=None):
    """Lane state after a decode wave, from the wave's own outputs and
    without a host read: exactly the `(last, lens, left)` the decode scan
    carried and dropped. A lane that emitted n tokens holds its n-th as
    last token, n more positions and n less budget (0 after `eos_id`,
    which is then its last token); a lane left without budget is handed
    to the next wave as the idle lane the host used to pack (0, 0, 0),
    so its paged read stays one block."""
    import jax.numpy as jnp
    on = emitted > 0
    last = out_tokens[jnp.maximum(emitted - 1, 0),
                      jnp.arange(emitted.shape[0])]
    left = steps_left - emitted
    if eos_id is not None:
        left = jnp.where(on & (last == eos_id), 0, left)
    live = left > 0
    return (jnp.where(live, jnp.where(on, last, tokens), 0),
            jnp.where(live, lengths + emitted, 0), left)


def _lane_programs(eos_id):
    """One engine's own jits of `_join_lanes` and `_advance_lanes` (jax
    keys a jit's traces by the function it wraps: wrapped here, an
    engine's `compile_cache_size` counts its own), named `jit_join_lanes`
    and `jit_advance_lanes` in a device trace."""
    import jax

    def join_lanes(*state_first_join):
        return _join_lanes(*state_first_join, eos_id=eos_id)

    def advance_lanes(*state_and_outputs):
        return _advance_lanes(*state_and_outputs, eos_id=eos_id)

    return jax.jit(join_lanes), jax.jit(advance_lanes)


class CachedDecoder:
    """The model side of the continuous engine: two jitted programs over
    a KV slot pool. Programs are shape-generic in the POOL (the garbage
    row is `k_cache.shape[0] - 1` at trace time), so one CachedDecoder
    serves pools of any slot count — each pool size compiles once.

    `params=` shares weights across instances (e.g. a reference decoder
    for tests); `seed=` controls the deterministic random init.
    """

    def __init__(self, config, params=None, seed=0):
        import jax
        from ..deploy import maybe_enable_compile_cache
        # arm the persistent compilation cache BEFORE the first compile:
        # warm replicas deserialize instead of recompiling
        maybe_enable_compile_cache()
        self.config = config
        self.params = params if params is not None \
            else init_decoder_params(config, seed)
        # census attribution (mx.inspect.memory): the decoder weights are
        # serving's second-biggest resident set after the KV slabs
        try:
            from ..inspect import memory as _mem
            _mem.register(self.params, owner="decoder_params")
        except Exception:
            pass
        # programs keyed by their trace-time constants (prefill window /
        # decode scan length + eos), each its own jit: built once per
        # engine at construction — steady state replays, never re-builds
        self._prefills = {}
        self._chunks = {}
        self._decodes = {}
        self._copy = None
        self._prefill = self.prefill_program(config.max_len)
        self._decode = self.decode_program(1, None)

    @staticmethod
    def _greedy_defaults(jnp, n, temps, top_ks, top_ps, keys):
        """Fill missing sampling arrays with the greedy encoding (temp
        0 selects argmax in-program) so pre-sampling call sites keep
        working unchanged."""
        if temps is None:
            temps = jnp.zeros((n,), dtype=jnp.float32)
        if top_ks is None:
            top_ks = jnp.zeros((n,), dtype=jnp.int32)
        if top_ps is None:
            top_ps = jnp.ones((n,), dtype=jnp.float32)
        if keys is None:
            keys = jnp.zeros((n, 2), dtype=jnp.uint32)
        return temps, top_ks, top_ps, keys

    # lane s of the chunk program is pool row s (see `_make_chunk_prefill`)
    chunk_rows_as_data = False

    def new_pool(self, max_slots=None, dtype=None):
        c = self.config
        return KVCachePool(max_slots, layers=c.layers, max_len=c.max_len,
                           heads=c.heads, head_dim=c.head_dim,
                           dtype=dtype or c.dtype)

    def cache_spec(self, dtype=None):
        """The cache leaves of one slot row: the K and V slabs, all of
        kind `full` (what `new_pool` allocates, as the pool states it)."""
        return KVCachePool(1, layers=self.config.layers,
                           max_len=self.config.max_len,
                           heads=self.config.heads,
                           head_dim=self.config.head_dim,
                           dtype=dtype or self.config.dtype,
                           allocate=False).spec

    def prefill_program(self, window):
        """The jitted prefill program for a prompt-page width."""
        import jax
        key = int(window)
        fn = self._prefills.get(key)
        if fn is None:
            fn = _sanitize.maybe_wrap_donated(
                jax.jit(_make_prefill(self.config, window=key),
                        donate_argnums=(1, 2)),
                (1, 2), f"prefill[w={key}]")
            self._prefills[key] = fn
        return fn

    def chunk_prefill_program(self, window, extent=None):
        """The jitted CHUNK prefill program for a window: scatter one
        window-sized prompt slice at an arbitrary page offset and emit
        logits at each lane's chunk tail (chunked prefill of long
        prompts + prefix-cache suffix prefill, serve/continuous.py).
        `extent` is the protocol's read bound for a model whose chunk
        reads its cache densely; the read here follows each lane's live
        blocks (`_paged_attn`), so every extent is the one program."""
        import jax
        key = int(window)
        fn = self._chunks.get(key)
        if fn is None:
            fn = _sanitize.maybe_wrap_donated(
                jax.jit(_make_chunk_prefill(self.config, window=key),
                        donate_argnums=(1, 2)),
                (1, 2), f"chunk_prefill[w={key}]")
            self._chunks[key] = fn
        return fn

    def copy_program(self):
        """The jitted slab-to-slab KV copy program (prefix-cache
        admission hit / retire publish): a memory-bound move of the
        positions a prefix holds, no attention math, donated like every
        other slab consumer."""
        import jax
        if self._copy is None:
            self._copy = _sanitize.maybe_wrap_donated(
                jax.jit(_copy_slot_rows, donate_argnums=(0, 1)),
                (0, 1), "copy_slot_rows")
        return self._copy

    def decode_program(self, steps, eos_id=None, draft=0):
        """The jitted decode program for a (steps, eos, draft) variant
        (built and memoized on first request; the engine asks once at
        init). `draft > 0` selects the speculative program — a
        DIFFERENT fixed shape (chunked verify), compiled once like any
        other variant."""
        import jax
        key = (int(steps), eos_id, int(draft))
        fn = self._decodes.get(key)
        if fn is None:
            if key[2] > 0:
                built = _make_spec_decode(self.config, steps=key[0],
                                          eos_id=eos_id, draft=key[2])
            else:
                built = _make_decode(self.config, steps=key[0],
                                     eos_id=eos_id)
            fn = _sanitize.maybe_wrap_donated(
                jax.jit(built, donate_argnums=(1, 2)), (1, 2),
                f"decode[s={key[0]},eos={key[1]},d={key[2]}]")
            self._decodes[key] = fn
        return fn

    def prefill(self, k_cache, v_cache, tokens, lengths, slot_rows,
                temps=None, top_ks=None, top_ps=None, keys=None):
        # window inferred from the token page width (a compiled program
        # exists per width; the engine always sends its own window)
        import jax.numpy as jnp
        temps, top_ks, top_ps, keys = self._greedy_defaults(
            jnp, tokens.shape[0], temps, top_ks, top_ps, keys)
        k_cache, v_cache, logits = self.prefill_program(tokens.shape[1])(
            self.params, k_cache, v_cache, tokens, lengths, slot_rows)
        first = _sample_first(logits, temps, top_ks, top_ps, keys,
                              lengths - 1)
        return k_cache, v_cache, first

    def decode(self, k_cache, v_cache, tokens, lengths, steps_left,
               steps=1, eos_id=None, temps=None, top_ks=None,
               top_ps=None, keys=None, draft=0, token_buf=None):
        import jax.numpy as jnp
        temps, top_ks, top_ps, keys = self._greedy_defaults(
            jnp, tokens.shape[0], temps, top_ks, top_ps, keys)
        prog = self.decode_program(steps, eos_id, draft)
        if draft > 0:
            if token_buf is None:
                raise ServeError(
                    "speculative decode (draft > 0) needs token_buf — "
                    "the (S, max_len) prompt+generated history page")
            return prog(self.params, k_cache, v_cache, tokens, lengths,
                        steps_left, temps, top_ks, top_ps, keys,
                        token_buf)
        return prog(self.params, k_cache, v_cache, tokens, lengths,
                    steps_left, temps, top_ks, top_ps, keys)

    def compile_cache_size(self):
        """Total compiled programs across every jit (-1 unknown) — the
        zero-retrace observable (≙ ExportedModel.compile_cache_size)."""
        fns = (list(self._prefills.values())
               + list(self._chunks.values())
               + list(self._decodes.values()))
        if self._copy is not None:
            fns.append(self._copy)
        sizes = [int(getattr(f, "_cache_size", lambda: -1)())
                 for f in fns]
        if any(s < 0 for s in sizes):
            return -1
        return sum(sizes)

    def reference_generate(self, prompt, max_new_tokens, eos_id=None,
                           window=None, temperature=0.0, top_k=0,
                           top_p=1.0, seed=0, draft_tokens=0,
                           kv_dtype=None, cached_prefix_len=0):
        """Generation through a PRIVATE 1-slot pool — the
        scheduling-free reference the engine's mixed-batch outputs must
        match token-for-token (tests). Uses the same compiled math; pass
        the engine's `prefill_window` so the prefill page width (and so
        the float-op layout) matches bit-for-bit. Prompts longer than
        the window replay the engine's CHUNKED prefill: a windowed first
        chunk at offset 0, then window-sized slices through the chunk
        program. `cached_prefix_len=L` mirrors a prefix-cache HIT —
        positions [0, L) carry the canonical cold provenance (windowed
        head + chunked remainder; a cache row copy is bit-identical to
        that by the causal mask, which makes prefix KV depend only on
        prefix tokens), while the suffix [L, plen) goes through the
        chunk program exactly as the engine prefills it after the row
        copy — so hit-path outputs are checked against an explicit
        reference, never assumed. Sampling (`temperature > 0` with the
        request seed) matches the engine because the draw key is a pure
        function of (seed, position); `draft_tokens > 0` runs the
        speculative program one wave at a time with a host-rebuilt
        history page — same tokens, by the exact-verification contract.
        `kv_dtype="int8"` mirrors an int8 engine pool."""
        import jax.numpy as jnp
        pool = self.new_pool(max_slots=1, dtype=kv_dtype)
        W = int(window if window is not None else self.config.max_len)
        prompt = _np.asarray(prompt, dtype=_np.int32).ravel()
        plen = int(prompt.size)
        if plen < 1 or plen >= self.config.max_len:
            raise ServeError(
                f"prompt length {plen} outside [1, max_len-1="
                f"{self.config.max_len - 1}]")
        L = int(cached_prefix_len)
        if not 0 <= L < plen:
            raise ServeError(
                f"cached_prefix_len {L} outside [0, plen-1={plen - 1}]")
        temps = jnp.asarray([float(temperature)], dtype=jnp.float32)
        tks = jnp.asarray([int(top_k)], dtype=jnp.int32)
        tps = jnp.asarray([float(top_p)], dtype=jnp.float32)
        keys = jnp.asarray(_seed_key(seed)[None, :])
        # windowed head: a cold request's offset-0 wave covers
        # min(plen, W) tokens; a hit's head stops at the cache boundary
        # (its suffix is chunk-prefilled even when it would fit windowed)
        head = min(plen if L == 0 else L, W)
        toks = _np.zeros((1, W), dtype=_np.int32)
        toks[0, :head] = prompt[:head]
        k, v = pool.buffers()
        k, v, logits = self.prefill_program(W)(
            self.params, k, v, jnp.asarray(toks),
            jnp.asarray([head], dtype=jnp.int32),
            jnp.asarray([0], dtype=jnp.int32))
        pool.swap_buffers(k, v)
        # chunked remainder through the SAME chunk program the engine
        # dispatches; the final chunk's logits sit at the prompt tail
        pos = head
        while pos < plen:
            n = min(W, plen - pos)
            ctoks = _np.zeros((1, W), dtype=_np.int32)
            ctoks[0, :n] = prompt[pos:pos + n]
            k, v = pool.buffers()
            k, v, logits = self.chunk_prefill_program(W)(
                self.params, k, v, jnp.asarray(ctoks),
                jnp.asarray([pos], dtype=jnp.int32),
                jnp.asarray([n], dtype=jnp.int32))
            pool.swap_buffers(k, v)
            pos += n
        first = _sample_first(
            logits, temps, tks, tps, keys,
            jnp.asarray([plen - 1], dtype=jnp.int32))
        out = [int(first[0])]
        cache_len = plen
        draft = int(draft_tokens)
        while (len(out) < max_new_tokens
               and (eos_id is None or out[-1] != eos_id)
               and cache_len + 1 < self.config.max_len):
            k, v = pool.buffers()
            if draft > 0:
                left = min(max_new_tokens - len(out),
                           self.config.max_len - 1 - cache_len)
                buf = _np.zeros((1, self.config.max_len),
                                dtype=_np.int32)
                hist = list(prompt) + out
                buf[0, :len(hist)] = hist
                k, v, blocks, n_emits, _, _, _ = self.decode(
                    k, v, jnp.asarray([out[-1]], dtype=jnp.int32),
                    jnp.asarray([cache_len], dtype=jnp.int32),
                    jnp.asarray([left], dtype=jnp.int32),
                    steps=1, eos_id=eos_id, temps=temps, top_ks=tks,
                    top_ps=tps, keys=keys, draft=draft,
                    token_buf=jnp.asarray(buf))
                pool.swap_buffers(k, v)
                n = int(_np.asarray(n_emits)[0, 0])
                out.extend(int(t) for t in
                           _np.asarray(blocks)[0, 0, :n])
                cache_len += n
            else:
                k, v, toks1, _ = self.decode(
                    k, v, jnp.asarray([out[-1]], dtype=jnp.int32),
                    jnp.asarray([cache_len], dtype=jnp.int32),
                    jnp.asarray([1], dtype=jnp.int32),
                    temps=temps, top_ks=tks, top_ps=tps, keys=keys)
                pool.swap_buffers(k, v)
                out.append(int(toks1[0, 0]))
                cache_len += 1
        return _np.asarray(out, dtype=_np.int32)


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------
class RequestTiming:
    """A request's timeline, as `submit()`'s future carries it
    (`fut.timing`) from the moment it is returned: read-only, filled in by
    the engine as the request moves. Times are `time.perf_counter()`
    seconds and None until reached: `t_submit` (enqueued), `t_admit` (KV
    slot claimed), `t_first` (first token out of prefill), `t_done` (set
    just before the future resolves; stays None for a request that
    failed). `prompt_tokens`, `cached_tokens` (served from the prefix
    cache) and `tokens` (generated so far) are counts; `slot` is the pool
    row the request was given at `t_admit` (None before): what it left in
    `engine.pool`'s leaves stays there until the row's next tenant.
    `stats()`'s ttft/tpot/e2e percentiles are computed from these same
    fields."""

    __slots__ = ("_req",)

    def __init__(self, req):
        self._req = req

    t_submit = property(lambda self: self._req.t_submit)
    t_admit = property(lambda self: self._req.t_admit)
    t_first = property(lambda self: self._req.t_first)
    t_done = property(lambda self: self._req.t_done)
    prompt_tokens = property(lambda self: int(self._req.prompt.size))
    cached_tokens = property(lambda self: self._req.cached_len)
    tokens = property(lambda self: len(self._req.generated))
    slot = property(lambda self: self._req.slot)

    def as_dict(self):
        return {k: getattr(self, k) for k in (
            "t_submit", "t_admit", "t_first", "t_done", "prompt_tokens",
            "cached_tokens", "tokens", "slot")}

    def __repr__(self):
        return f"RequestTiming({self.as_dict()})"


class _GenRequest:
    __slots__ = ("prompt", "max_new", "future", "deadline", "t_submit",
                 "ctx", "slot", "generated", "cache_len", "t_admit",
                 "t_first", "t_last", "t_done", "temperature", "top_k",
                 "top_p", "key", "entry", "cached_len", "prefill_pos",
                 "left", "rid", "wave")

    def __init__(self, prompt, max_new, deadline, ctx,
                 temperature=0.0, top_k=0, top_p=1.0, key=None):
        self.prompt = prompt                 # np.int32 (plen,)
        self.max_new = max_new
        self.future = Future()
        self.future.timing = RequestTiming(self)
        self.deadline = deadline             # perf_counter deadline or None
        self.t_submit = time.perf_counter()
        self.ctx = ctx                       # serve.request root context
        self.slot = None
        self.generated = []
        self.cache_len = 0
        self.rid = None                      # the engine's n-th request
        self.wave = None                     # decode waves before admission
        self.t_admit = None                  # KV slot claimed
        self.t_first = None                  # first token (TTFT anchor)
        self.t_last = None
        self.t_done = None                   # just before the future resolves
        self.temperature = temperature       # 0.0 = greedy lane
        self.top_k = top_k
        self.top_p = top_p
        self.key = key if key is not None else _seed_key(0)  # uint32 (2,)
        self.entry = None        # pinned prefix-cache entry (hit path)
        self.cached_len = 0      # prompt tokens served from the cache
        self.prefill_pos = 0     # prompt tokens already in KV (chunked)
        # the budget the lane holds after every wave DISPATCHED so far, as
        # far as the host can know before reading them: exact without an
        # eos, an upper bound with one (the device holds the truth)
        self.left = 0

    def sort_key(self):
        """Earliest-deadline-first; deadline-less requests rank after
        every deadline-holder, FIFO among themselves."""
        return (self.deadline is None,
                self.deadline if self.deadline is not None
                else self.t_submit,
                self.t_submit)


class _Unread:
    """What one scheduler iteration handed the device and nobody has read
    back: `firsts`, a (first-token vector, [(request, its lane there)])
    pair for each prefill program in which a prompt ended; `wave`, the
    decode wave's (lanes by slot, device outputs, lanes it sampled for)
    or None; `counters`, the count vectors that the iteration's programs
    returned (a model that declares `counters`). False when empty."""

    __slots__ = ("firsts", "wave", "counters")

    def __init__(self):
        self.firsts = []
        self.wave = None
        self.counters = []

    def __bool__(self):
        return bool(self.firsts or self.wave is not None or self.counters)


class ContinuousEngine:
    """Iteration-level batching decode engine over a `CachedDecoder`.

    ::

        model = serve.CachedDecoder(serve.DecoderConfig(max_len=64))
        with serve.ContinuousEngine(model, max_slots=8) as eng:
            fut = eng.submit([3, 14, 15], max_new_tokens=16)
            tokens = fut.result()            # np.int32 generated ids

    Knobs (constructor arg > deployment profile (mx.tune) >
    MXNET_SERVE_* env > default — the profile is a measured,
    fingerprint-checked artifact, so ambient shell exports must not
    defeat it; MXNET_TUNE_DISABLE=1 restores raw env behavior):

      max_slots        KV slots = max concurrently-decoding requests
      prefill_budget   max prompt TOKENS prefilled per engine iteration
                       (bounds how long admission may stall in-flight
                       decode; >= 1 request always admitted when a slot
                       is free)
      prefill_lanes    FIXED lane count of the prefill program (default
                       min(max_slots, 8)): its cost is paid in full per
                       admission wave regardless of how many lanes carry
                       real requests, so it is sized for the admission
                       RATE, not the pool — a max_slots-wide prefill
                       would bill a 1-request wave the whole pool's
                       prefill FLOPs
      max_queue        waiting-request bound (admission control; reuses
                       MXNET_SERVE_MAX_QUEUE; reject-newest)
      default_deadline_ms  queue deadline (MXNET_SERVE_DEADLINE_MS);
                       expiry while WAITING fails fast with
                       RequestTimeout — admitted requests always finish
      draft_tokens     speculative decode depth k
                       (MXNET_SERVE_DRAFT_TOKENS, default 0 = off):
                       each scan micro-step drafts k tokens by
                       prompt-lookup and verifies them in one chunked
                       forward; output tokens are IDENTICAL to
                       draft_tokens=0 (exact-match verification)
      kv_dtype         KV pool storage dtype; "int8" stores quantized
                       codes + per-position f32 scales (~4x KV bytes
                       saved at float32 serving dtype — see
                       pool.stats()["slots_per_gb"])
      prefix_cache_slots  dedicated pool rows holding shared-prefix KV
                       (MXNET_SERVE_PREFIX_CACHE_SLOTS, default 0 =
                       off): admission matches the longest cached
                       prefix, row-copies its KV into the claimed slot,
                       and prefills ONLY the suffix
      prefix_block     prefix-cache granularity in tokens
                       (MXNET_SERVE_PREFIX_BLOCK): prefixes cache and
                       match on whole blocks only
      prefix_cache_insert  publish a retiring request's own prompt
                       prefix back into the cache
                       (MXNET_SERVE_PREFIX_CACHE_INSERT, default on)

    Prompts longer than `prefill_window` stream in window-sized CHUNKS
    across successive waves (the chunk program advances every
    mid-prefill lane per wave), so one long prompt never monopolizes a
    prefill wave and short requests' TTFT stays bounded.

    Exactly one scheduler thread runs the compiled steps, so the donated
    KV buffers have a single writer; submit() is safe from any thread.
    """

    def __init__(self, model, *, max_slots=None, prefill_budget=None,
                 prefill_lanes=None, prefill_window=None, decode_steps=None,
                 max_queue=None, default_deadline_ms=None, eos_id=None,
                 draft_tokens=None, kv_dtype=None, prefix_block=None,
                 prefix_cache_slots=None, prefix_cache_insert=None,
                 name="serve.continuous"):
        from ..tune.profile import resolve as _tune_resolve
        self.model = model
        self.name = name
        self.eos_id = eos_id
        if max_slots is None:
            max_slots = _tune_resolve("serve.max_slots")
        if kv_dtype is None:
            kv_dtype = _tune_resolve("serve.kv_dtype")
            if kv_dtype is None:
                kv_dtype = get_env("MXNET_SERVE_KV_DTYPE")
        self.kv_dtype = kv_dtype
        # shared-prefix reuse tier (serve/prefix_cache.py): cached
        # prefixes live in DEDICATED pool rows claimed once here, so
        # admission capacity (max_slots) and cache capacity are
        # independent knobs and SlotsFullError semantics are unchanged
        if prefix_block is None:
            prefix_block = _tune_resolve("serve.prefix_block")
            if prefix_block is None:
                prefix_block = get_env("MXNET_SERVE_PREFIX_BLOCK", 16,
                                       typ=int)
        self.prefix_block = int(prefix_block)
        if self.prefix_block < 1:
            raise ServeError("prefix_block must be >= 1")
        if prefix_cache_slots is None:
            prefix_cache_slots = _tune_resolve("serve.prefix_cache_slots")
            if prefix_cache_slots is None:
                prefix_cache_slots = get_env(
                    "MXNET_SERVE_PREFIX_CACHE_SLOTS", 0, typ=int)
        self.prefix_cache_slots = int(prefix_cache_slots)
        if self.prefix_cache_slots < 0:
            raise ServeError("prefix_cache_slots must be >= 0")
        if prefix_cache_insert is None:
            prefix_cache_insert = _tune_resolve(
                "serve.prefix_cache_insert")
            if prefix_cache_insert is None:
                prefix_cache_insert = bool(get_env(
                    "MXNET_SERVE_PREFIX_CACHE_INSERT", 1, typ=int))
        self.prefix_cache_insert = bool(prefix_cache_insert)
        if max_slots is None:
            max_slots = get_env("MXNET_SERVE_MAX_SLOTS", 8, typ=int)
        self.max_slots = int(max_slots)
        if self.max_slots < 1:
            raise ServeError("max_slots must be >= 1")
        if draft_tokens is None:
            draft_tokens = _tune_resolve("serve.draft_tokens")
        self.draft_tokens = int(
            draft_tokens if draft_tokens is not None
            else get_env("MXNET_SERVE_DRAFT_TOKENS", 0, typ=int))
        if self.draft_tokens < 0:
            raise ServeError("draft_tokens must be >= 0")
        # a request's past is rows of K and V only where every leaf is of
        # kind `full`: a ring forgets positions and a recurrent state has
        # none, so what copies, rolls back or requantizes rows is refused
        stateful = sorted({leaf.kind for leaf in model.cache_spec()}
                          - {"full"})
        if stateful:
            for on, what, needs in (
                    (self.prefix_cache_slots > 0, "prefix_cache_slots > 0",
                     "a prefix hit copies rows of K and V, and this cache "
                     "would need a snapshot of its state at the prefix's "
                     "end"),
                    (self.draft_tokens > 0, "draft_tokens > 0",
                     "a rejected draft needs the state rolled back to the "
                     "last accepted token"),
                    (str(kv_dtype) == "int8", 'kv_dtype="int8"',
                     "these leaves have no quantized form")):
                if on:
                    raise CacheKindError(
                        f"{what} cannot serve a model whose cache holds "
                        f"{' and '.join(stateful)} leaves: {needs}")
        if self.prefix_cache_slots and not hasattr(model, "copy_program"):
            raise CacheKindError(
                "prefix_cache_slots > 0 cannot serve a model without a "
                "`copy_program`: a prefix hit copies the leading positions "
                "of every cache leaf from row to row, and this model's "
                "leaves have no such program")
        # the pool is carved with max_slots REQUEST rows plus the
        # dedicated prefix-cache rows; self.max_slots stays the request
        # capacity every admission/queue bound sees
        self.pool = model.new_pool(
            self.max_slots + self.prefix_cache_slots, dtype=kv_dtype)
        self._cache = None
        if self.prefix_cache_slots:
            self._cache = PrefixCache(
                self.prefix_block,
                [self.pool.claim()
                 for _ in range(self.prefix_cache_slots)])
        # micro-iterations per compiled decode dispatch: >1 amortizes the
        # host round-trip over K tokens; admission/retirement happen at
        # wave granularity (a lane finishing mid-wave holds its slot
        # until the wave ends, never computes past its budget)
        if decode_steps is None:
            decode_steps = _tune_resolve("serve.decode_steps")
            if decode_steps is None:
                decode_steps = get_env("MXNET_SERVE_DECODE_STEPS", 4,
                                       typ=int)
        self.decode_steps = max(1, int(decode_steps))
        self._decode_prog = model.decode_program(self.decode_steps,
                                                 eos_id,
                                                 self.draft_tokens)
        # prompt page width: prompts are bounded by it, and the prefill
        # program pays O(window^2) attention instead of O(max_len^2) —
        # size it to the served prompt distribution, not the page
        self.prefill_window = int(
            prefill_window if prefill_window is not None
            else model.config.max_len)
        if not 1 <= self.prefill_window <= model.config.max_len:
            raise ServeError(
                f"prefill_window must be in [1, max_len], got "
                f"{self.prefill_window}")
        self._prefill_prog = model.prefill_program(self.prefill_window)
        # the chunk programs exist whenever a prompt can outgrow the
        # window (chunked streaming) or a cache hit leaves a suffix to
        # prefill at a nonzero page offset. The engine asks the model
        # for an EXTENT LADDER (window, 2*window, ... max_len) and picks
        # per wave the smallest rung that covers the furthest lane.
        # What a rung is, is the model's to say: where a chunk reads the
        # cached positions densely in plain XLA (SparseMoEDecoder,
        # DeltaMoEDecoder) each rung is a program whose read costs what
        # its extent is long; where the read is the paged kernel over a
        # lane's live blocks (CachedDecoder, HybridDecoder) the extent
        # bounds nothing and every rung is the ONE program. Each distinct
        # program is warmed once, so picking a rung is zero-retrace
        self._chunk_progs = None
        self._chunk_extents = ()
        if (self.prefill_window < model.config.max_len
                or self._cache is not None):
            # a chunk at an offset ends past one window unless a prefix
            # hit put it at a small offset: without a prefix cache the
            # first rung (extent == window) would be warmed and never run
            exts = []
            e = self.prefill_window * (1 if self._cache is not None else 2)
            while e < model.config.max_len:
                exts.append(e)
                e *= 2
            exts.append(model.config.max_len)
            self._chunk_extents = tuple(exts)
            self._chunk_progs = {
                x: model.chunk_prefill_program(self.prefill_window,
                                               extent=x)
                for x in exts}
        self._copy_prog = (model.copy_program()
                           if self._cache is not None else None)
        # the chunk program's lanes: pool rows (lane s writes row s), or
        # prefill lanes whose rows ride as data (the model says which)
        self._chunk_compact = bool(model.chunk_rows_as_data)
        self.prefill_budget = int(
            prefill_budget if prefill_budget is not None
            else get_env("MXNET_SERVE_PREFILL_BUDGET", 256, typ=int))
        if self.prefill_budget < 1:
            raise ServeError("prefill_budget must be >= 1")
        if prefill_lanes is None:
            prefill_lanes = _tune_resolve("serve.prefill_lanes")
            if prefill_lanes is None:
                prefill_lanes = get_env("MXNET_SERVE_PREFILL_LANES",
                                        typ=int)
        self.prefill_lanes = int(prefill_lanes if prefill_lanes is not None
                                 else min(self.max_slots, 8))
        if not 1 <= self.prefill_lanes <= self.max_slots:
            raise ServeError(
                f"prefill_lanes must be in [1, max_slots], got "
                f"{self.prefill_lanes}")
        self.max_queue = int(
            max_queue if max_queue is not None
            else get_env("MXNET_SERVE_MAX_QUEUE", 256, typ=int))
        dl = (default_deadline_ms if default_deadline_ms is not None
              else get_env("MXNET_SERVE_DEADLINE_MS", typ=float))
        self.default_deadline_s = None if dl is None else float(dl) / 1e3
        self.max_len = model.config.max_len

        self._cv = threading.Condition()
        self._waiting = deque()              # submitted, no slot yet
        self._prefilling = {}                # slot -> req, prompt KV partial
        self._running = {}                   # slot -> _GenRequest
        self._closing = False
        self._drain = True
        self._started = False
        self._warm_cache_size = None
        self._canary = None
        self.warmup_s = None
        self._thread = threading.Thread(
            target=self._loop, name=f"{name}-scheduler", daemon=True)

        # per-engine metrics (all mutation under _mlock)
        self._mlock = threading.Lock()
        self._t0 = time.perf_counter()
        self._counters = {k: 0 for k in (
            "requests", "replies", "rejected", "timeouts", "errors",
            "admitted", "retired", "decode_iterations", "decode_tokens",
            "prefill_tokens", "prefill_batches", "programs_compiled",
            "active_sum", "waves_ahead", "sampled_tokens", "sampled_waves",
            "draft_accepted",
            "draft_rejected", "prefix_hits", "prefix_misses",
            "prefix_cached_tokens", "copied_positions")}
        # per cache kind: the bytes the lanes of each decode wave held
        # live, summed over waves (beside `decode_iterations`)
        self._cache_live = {k: 0 for k in self.pool.kinds()}
        # a model may declare `counters` ({name: field names}): each of its
        # programs then returns, last, {name: int vector}; an iteration's
        # vectors gather in `_pending`, leave with its `_Unread` record
        # and are summed where that record is read
        self._counter_fields = dict(getattr(model, "counters", None) or {})
        self._model_counters = {
            name: _np.zeros((len(fields),), dtype=_np.int64)
            for name, fields in self._counter_fields.items()}
        self._pending = []
        # the decode wave's `tokens`, `lengths`, `steps_left` live on the
        # device between waves (`_lanes`): a prompt's end writes its lane
        # (`_join_prog`), a wave's outputs advance every lane
        # (`_advance_prog`), the host reads neither before the next
        # dispatch. `_unread` is what the last iteration dispatched and
        # nobody has read yet. The scheduler thread alone touches the three
        self._join_prog, self._advance_prog = _lane_programs(eos_id)
        # every program warm-up ran, by name (`_warm`), and the token
        # under which the process-wide registry keeps them together
        self._programs = {}
        self._programs_owner = object()
        self._reset_lanes()
        self._unread = None  # mxlint: disable=lock-shared-mutation -- warm-up runs before the scheduler thread starts; afterwards only that thread touches it
        self._auto_seed = 0                  # per-engine seed fountain
        self._submitted = 0                  # requests queued so far (_cv)
        # (ttft, tpot or None, e2e) ms of the newest retired requests, from
        # their RequestTiming fields: stats()'s one source of percentiles
        self._latencies = deque(maxlen=4096)

    # -- lifecycle ---------------------------------------------------------
    def start(self, warmup=True):
        """Compile (or persistent-cache-load) both step programs before
        traffic, then start the scheduler thread. Returns self; records
        the warm compile-cache size for the zero-retrace assertion."""
        if self._started:
            return self
        t0 = time.perf_counter()
        if warmup:
            self._warmup()
        with self._cv:
            self._warm_cache_size = self.compile_cache_size()
            self._started = True
        self.warmup_s = round(time.perf_counter() - t0, 3)
        with self._mlock:
            # rates and percentiles describe serving: the clock of
            # `elapsed_s` starts once warm-up is over
            self._t0 = time.perf_counter()
        if _sanitize.enabled("retrace"):
            # warmup compiled everything; from here any growth is a
            # broken zero-retrace contract (polled once per decode wave)
            _sanitize.arm()
        if _sanitize.enabled("slot"):
            with self._cv:
                if self._canary is None:
                    self._canary = _sanitize.SlotCanary(self.pool)
        _trace.install_crash_hooks()
        mem_install_oom_hook()
        self._thread.start()
        return self

    def _warmup(self):
        """One garbage-lane pass through EVERY step program (prefill +
        decode, their sampler and lane programs, plus the chunk-prefill
        and row-copy programs when configured): compiles (or loads from
        MXNET_COMPILE_CACHE_DIR) each without touching any real slot."""
        import jax
        cache, lanes = self._walk_programs(self._warm, self.pool.buffers())
        self.pool.swap_buffers(*cache)
        # wait for the compiles to actually finish so warmup_s is honest
        jax.block_until_ready((cache, lanes))
        self._reset_lanes()
        self._count("programs_compiled", sum(
            name.startswith(("prefill", "decode", "chunk_prefill", "copy"))
            for name in self._programs))

    def _walk_programs(self, call, cache):
        """Every step program this engine will run, once, with arguments
        under which no real slot is touched (every lane idle or writing
        the garbage row), in the order of a serving iteration:
        `call(name, program, *args)` -> the program's outputs. `cache` is
        the pool's buffers; each donating program's outputs take their
        place. Returns (cache, lane state) as the last calls left them.
        With `_warm` as `call` this is warm-up; with `_describe` nothing
        runs and `cache` may be the pool's avals."""
        import jax.numpy as jnp
        g = self.pool.garbage_row
        P = self.prefill_lanes
        S = self.pool.max_slots
        params = self.model.params
        sample_first = _sample_first_program()
        n = len(cache)
        # (a model that declares `counters` returns them last: nothing
        # counted here is kept)
        held = -1 if self._counter_fields else None
        lens = jnp.ones((P,), dtype=jnp.int32)
        *cache, logits = call(
            "prefill", self._prefill_prog, params, *cache,
            jnp.zeros((P, self.prefill_window), dtype=jnp.int32),
            lens, jnp.full((P,), g, dtype=jnp.int32))[:held]
        # the shared first-token sampler at this (P, vocab) shape — it is
        # part of the steady-state prefill wave — and the lane join at
        # its output's shape, with no lane joining
        first = call("sample_first", sample_first, logits,
                     jnp.zeros((P,), dtype=jnp.float32),
                     jnp.zeros((P,), dtype=jnp.int32),
                     jnp.ones((P,), dtype=jnp.float32),
                     jnp.zeros((P, 2), dtype=jnp.uint32), lens - 1)
        idle = jnp.zeros((S,), dtype=jnp.int32)
        nobody = jnp.full((3, S), -1, dtype=jnp.int32)
        lanes = call("join_lanes", self._join_prog, idle, idle, idle,
                     first, nobody)
        args = [idle, idle, idle,
                jnp.zeros((S,), dtype=jnp.float32),
                jnp.zeros((S,), dtype=jnp.int32),
                jnp.ones((S,), dtype=jnp.float32),
                jnp.zeros((S, 2), dtype=jnp.uint32)]
        if self.draft_tokens:
            args.append(jnp.zeros((S, self.max_len), dtype=jnp.int32))
        out = call("decode", self._decode_prog, params, *cache,
                   *args)[:held]
        cache = out[:n]
        if not self.draft_tokens:
            lanes = call("advance_lanes", self._advance_prog, *lanes,
                         *out[n:])
        if self._chunk_progs is not None:
            # all-idle chunk wave (every lane scatters into garbage)
            # through every rung's program, so wave-time extent
            # selection never compiles; the first-token sampler and the
            # join at the (C, vocab) shape the chunk path samples from too
            C = P if self._chunk_compact else S
            idle_c = [jnp.zeros((C, self.prefill_window), dtype=jnp.int32),
                      jnp.zeros((C,), dtype=jnp.int32),
                      jnp.zeros((C,), dtype=jnp.int32)]
            if self._chunk_compact:
                idle_c.append(jnp.full((C,), g, dtype=jnp.int32))
            # (a model whose chunk read the extent does not bound hands
            # back ONE program for every rung: it is called once, under
            # its first rung's name)
            rung_of = {}
            for x, prog in self._chunk_progs.items():
                rung_of.setdefault(prog, x)
            for prog, x in rung_of.items():
                *cache, logits = call(f"chunk_prefill[{x}]", prog, params,
                                      *cache, *idle_c)[:held]
            first = call("sample_first[chunk]", sample_first, logits,
                         jnp.zeros((C,), dtype=jnp.float32),
                         jnp.zeros((C,), dtype=jnp.int32),
                         jnp.ones((C,), dtype=jnp.float32),
                         jnp.zeros((C, 2), dtype=jnp.uint32),
                         jnp.zeros((C,), dtype=jnp.int32))
            lanes = call("join_lanes[chunk]", self._join_prog, *lanes,
                         first, nobody)
        if self._copy_prog is not None:
            # every lane at length 0: compiles, moves nothing
            idle_p = jnp.zeros((P,), dtype=jnp.int32)
            cache = call("copy", self._copy_prog, *cache, idle_p, idle_p,
                         idle_p)
        return tuple(cache), lanes

    def _warm(self, name, prog, *args):
        """One call of a step program, noted first under `name` with its
        arguments' shapes in the process-wide program registry
        (`profiler.register_program`: shapes and dtypes, no buffer): the
        one list that `lowered_programs()`, `memory_plans()` and
        `profiler.program_scopes()` read."""
        self._programs[name] = _profiler.register_program(  # mxlint: disable=lock-shared-mutation -- warm-up runs before the scheduler thread starts
            prog, args, owner=self._programs_owner)
        return prog(*args)

    def _describe(self, name, prog, *args):
        """`_warm` without the call: the outputs' shapes alone."""
        import jax
        self._programs[name] = _profiler.register_program(  # mxlint: disable=lock-shared-mutation -- an engine that was never started has no scheduler thread
            prog, args, owner=self._programs_owner)
        return jax.eval_shape(prog, *args)

    def _reset_lanes(self):
        """Every lane idle: what the device holds before the first join,
        and again after a failed step."""
        import jax.numpy as jnp
        idle = jnp.zeros((self.pool.max_slots,), dtype=jnp.int32)
        self._lanes = (idle, idle, idle)  # mxlint: disable=lock-shared-mutation -- warm-up runs before the scheduler thread starts; afterwards only that thread touches it

    def _outputs(self, out):
        """A step program's outputs without the model's counters: where
        the model declares `counters` they come last, and wait in
        `_pending` for the read of the iteration that dispatched them
        (`_read_counters`)."""
        if not self._counter_fields:
            return out
        for vec in out[-1].values():
            vec.copy_to_host_async()
        self._pending.append(out[-1])  # mxlint: disable=lock-shared-mutation -- warm-up runs before the scheduler thread starts; afterwards only that thread touches it
        return out[:-1]

    def _read_counters(self, trees):
        """Sum what an iteration's programs counted. Called where its
        tokens are read back anyway, so the programs that counted have
        finished; a model without `counters` pays one truth test."""
        if not trees:
            return
        got = [{name: _np.asarray(vec) for name, vec in tree.items()}
               for tree in trees]
        with self._mlock:
            for tree in got:
                for name, vec in tree.items():
                    self._model_counters[name] += vec

    def __enter__(self):
        return self.start()

    def close(self, drain=True, timeout=60.0):
        """Stop the scheduler. `drain=True` finishes admitted AND waiting
        requests first; `drain=False` fails the waiting queue (admitted
        requests still finish — their slots hold real state)."""
        with self._cv:
            if not self._closing:
                self._closing = True
                self._drain = drain
                pending = [] if drain else list(self._waiting)
                if not drain:
                    self._waiting.clear()
            else:
                pending = []
            self._cv.notify_all()
        for req in pending:
            _fail(req, ServerClosed("engine closed before admission"))
        if self._started:
            self._thread.join(timeout=timeout)
        with self._cv:
            canary, self._canary = self._canary, None
        if canary is not None:
            canary.release()

    def __exit__(self, *exc):
        self.close()

    def begin_drain(self):
        """Stop admitting (submit() raises `ReplicaDraining`) while the
        scheduler finishes every waiting AND admitted request. Non-blocking
        by design — the drain-and-swap replica keeps answering heartbeats
        while its KV-resident requests finish; `close()` joins after."""
        with self._cv:
            if not self._closing:
                self._closing = True
                self._drain = True
            self._cv.notify_all()

    @property
    def draining(self):
        """True while a drain is in progress (resident requests still
        finishing); False once the scheduler has exited."""
        return self._closing and self._drain and self._thread.is_alive()

    def queue_depth(self):
        """(waiting, running) request counts — the fleet router's
        least-loaded placement signal. Mid-prefill (chunk-streaming)
        requests hold slots, so they count as running."""
        with self._cv:
            return (len(self._waiting),
                    len(self._running) + len(self._prefilling))

    # -- submission --------------------------------------------------------
    def submit(self, prompt_tokens, max_new_tokens=16, deadline_ms=None,
               temperature=0.0, top_k=0, top_p=1.0, seed=None):
        """Enqueue one generation request; returns a Future resolving to
        the np.int32 array of generated token ids (cut at `eos_id`,
        `max_new_tokens`, or a full KV page).

        `temperature=0` (default) is greedy; `temperature > 0` samples
        with optional `top_k`/`top_p` truncation, deterministically in
        `seed` (auto-assigned from a per-engine counter when omitted).
        Both kinds share one compiled program — sampling params are
        array data, never shapes."""
        temperature = float(temperature)
        if temperature < 0.0:
            raise ServeError("temperature must be >= 0")
        top_k = int(top_k)
        if top_k < 0:
            raise ServeError("top_k must be >= 0")
        top_p = float(top_p)
        if not 0.0 < top_p <= 1.0:
            raise ServeError(f"top_p must be in (0, 1], got {top_p}")
        if not self._started:
            raise ServeError(
                "ContinuousEngine.start() (or `with engine:`) first")
        prompt = _np.asarray(prompt_tokens, dtype=_np.int32).ravel()
        if prompt.size < 1:
            raise ServeError("prompt must have at least one token")
        if prompt.size >= self.max_len:
            raise ServeError(
                f"prompt length {prompt.size} >= max_len {self.max_len} "
                f"(one slot page holds prompt + generated tokens)")
        # prompts longer than prefill_window are fine: they stream in
        # window-sized chunks across successive waves (chunked prefill)
        if max_new_tokens < 1:
            raise ServeError("max_new_tokens must be >= 1")
        _fault.inject("serve.enqueue")
        dl = (deadline_ms / 1e3 if deadline_ms is not None
              else self.default_deadline_s)
        if seed is None:
            with self._mlock:
                seed = self._auto_seed
                self._auto_seed += 1
        ctx = _trace.request_root("serve.request")
        req = _GenRequest(prompt, int(max_new_tokens),
                          None if dl is None
                          else time.perf_counter() + dl, ctx,
                          temperature=temperature, top_k=top_k,
                          top_p=top_p, key=_seed_key(seed))
        with self._cv:
            if self._closing:
                # typed split, not one generic ServerClosed: DRAINING means
                # "resident requests still finishing before a restart" and
                # the fleet router re-routes it silently; CLOSED is final
                if self._drain and self._thread.is_alive():
                    raise ReplicaDraining(
                        "engine is draining (finishing resident requests "
                        "before restart); route to another replica")
                raise ServerClosed("engine is closed")
            if len(self._waiting) >= self.max_queue:
                depth = len(self._waiting)
                rejected = True
            else:
                rejected = False
                req.rid = self._submitted = self._submitted + 1
                self._waiting.append(req)
                self._cv.notify()
        if rejected:
            self._count("rejected")
            _trace.flightrec_record(
                "serve.reject", self.name, depth=depth,
                trace_id=ctx.trace_id if ctx else None)
            _trace.flightrec_maybe_dump("serve.overload")
            raise QueueFullError(
                f"waiting queue full ({self.max_queue}); request "
                f"rejected", policy="reject")
        self._count("requests")
        return req.future

    def generate(self, prompt_tokens, max_new_tokens=16, timeout=None,
                 deadline_ms=None, temperature=0.0, top_k=0, top_p=1.0,
                 seed=None):
        """submit() + wait."""
        return self.submit(prompt_tokens, max_new_tokens,
                           deadline_ms=deadline_ms,
                           temperature=temperature, top_k=top_k,
                           top_p=top_p, seed=seed).result(timeout=timeout)

    # -- metrics -----------------------------------------------------------
    def _count(self, key, n=1):
        with self._mlock:
            self._counters[key] += n
        stats_key = _ENGINE_TO_SERVE_KEY.get(key)
        if stats_key is not None:
            with _STATS_LOCK:
                SERVE_STATS[stats_key] += n

    def compile_cache_size(self):
        """The model's compiled programs and the engine's own two (the
        lane join and advance), -1 where the jax version hides a count."""
        sizes = [self.model.compile_cache_size()] + [
            int(getattr(f, "_cache_size", lambda: -1)())
            for f in (self._join_prog, self._advance_prog)]
        return -1 if min(sizes) < 0 else sum(sizes)

    def prefix_hit_count(self):
        """Lifetime prefix-cache hits — the cheap accessor the replica
        heartbeat pong carries (full breakdown in `stats()`)."""
        with self._mlock:
            return self._counters["prefix_hits"]

    def retraces_after_warmup(self):
        """Compiled-program growth since start() — MUST be 0 in steady
        state (-1 when the jax version hides the counter)."""
        if self._warm_cache_size is None or self._warm_cache_size < 0:
            return -1
        now = self.compile_cache_size()
        return -1 if now < 0 else now - self._warm_cache_size

    def assert_no_retraces(self):
        r = self.retraces_after_warmup()
        if r > 0:
            raise MXNetError(
                f"continuous engine retraced {r} program(s) after warmup "
                f"— a shape leaked into the compiled step")
        return r

    def lowered_programs(self):
        """Every step program this engine runs, lowered at the exact
        shapes warm-up called it with (`{name: jax.stages.Lowered}`):
        `prefill`, `decode`, `sample_first`, `join_lanes`,
        `advance_lanes`, and where configured `chunk_prefill[<extent>]` a
        distinct chunk program (one a rung, or one in all under its first
        rung's name where the model's chunk is one program whatever the
        extent), `copy`, and the sampler and join at the chunk path's shape.
        Abstract values only — no buffers touched, no extra compile in
        steady state: the lowering hits the same jit cache entry the
        engine replays. An engine that was never started describes its
        programs by their shapes alone (`_describe`). The same registered
        programs answer `profiler.program_scopes()`, also once this
        engine is closed; `memory_plans()` reads them, and `chip_smoke.py`
        looks here for the paged-attention kernel in the compiled decode
        program."""
        if not self._programs:      # never warmed: shapes only
            self._walk_programs(self._describe, tuple(self.pool.avals()))
        return {name: prog.lower() for name, prog in self._programs.items()}

    def memory_plans(self):
        """Predicted device-memory plans of the compiled step programs
        that hold a model (`mx.inspect.memory.memory_plan` over
        `lowered_programs()`; the lane and sampler programs are a few
        vectors). The KV slab dominates the plans' argument size and is
        donated, so `alias_size` covering ~2x the slab is the
        zero-copy-update evidence."""
        from ..inspect.memory import memory_plan
        small = ("sample_first", "join_lanes", "advance_lanes")
        return {name: memory_plan(low, name=f"{self.name}.{name}")
                for name, low in self.lowered_programs().items()
                if not name.startswith(small)}

    def stats(self):
        """Plain-data snapshot: counters, slot occupancy, TTFT/TPOT
        percentiles, decode tokens/s, and the zero-retrace observables."""
        with self._mlock:
            c = dict(self._counters)
            lat = list(self._latencies)
            elapsed = time.perf_counter() - self._t0
        ttft, tpot, e2e = (sorted(r[i] for r in lat if r[i] is not None)
                           for i in range(3))
        out = dict(c)
        out["elapsed_s"] = round(elapsed, 3)
        out["decode_tokens_per_sec"] = round(
            c["decode_tokens"] / elapsed, 2) if elapsed > 0 else 0.0
        out["mean_active_slots"] = round(
            c["active_sum"] / c["decode_iterations"], 3) \
            if c["decode_iterations"] else 0.0
        for nm, vals in (("ttft", ttft), ("tpot", tpot), ("e2e", e2e)):
            for q in (50, 99):
                v = percentile(vals, q)
                out[f"{nm}_p{q}_ms"] = round(v, 3) if v is not None \
                    else None
        out["pool"] = self.pool.stats()
        with self._mlock:
            live = dict(self._cache_live)
        out["cache"] = {
            kind: {"bytes": n, "live_bytes_sum": live[kind]}
            for kind, n in self.pool.bytes_by_kind().items()}
        with self._mlock:
            for name, fields in self._counter_fields.items():
                out[name] = {f: int(v) for f, v in zip(
                    fields, self._model_counters[name])}
        out["decode_steps"] = self.decode_steps
        out["draft_tokens"] = self.draft_tokens
        if c["draft_accepted"] + c["draft_rejected"] > 0:
            out["draft_acceptance"] = round(
                c["draft_accepted"]
                / (c["draft_accepted"] + c["draft_rejected"]), 4)
        out["prefill_lanes"] = self.prefill_lanes
        out["prefill_window"] = self.prefill_window
        if self._cache is not None:
            out["prefix_block"] = self.prefix_block
            out["prefix_cache"] = self._cache.stats()
            if c["prefix_hits"] + c["prefix_misses"] > 0:
                out["prefix_hit_rate"] = round(
                    c["prefix_hits"]
                    / (c["prefix_hits"] + c["prefix_misses"]), 4)
            if c["prefill_tokens"] + c["prefix_cached_tokens"] > 0:
                # share of prompt tokens served by copy, not compute
                out["prefill_cached_token_share"] = round(
                    c["prefix_cached_tokens"]
                    / (c["prefill_tokens"]
                       + c["prefix_cached_tokens"]), 4)
        out["compile_cache_size"] = self.compile_cache_size()
        out["retraces_after_warmup"] = self.retraces_after_warmup()
        return out

    # -- scheduler ---------------------------------------------------------
    def _loop(self):
        import jax.numpy as jnp
        wave, token = 0, None
        while True:
            # ONE gate per iteration for every live span of the wave
            # (docs/OBSERVABILITY.md "Hot-path spans"): armed, the
            # iteration's spans form one tree under a `serve.wave` context
            # whose trace id is the wave number; un-armed, each site
            # enters the shared NO_SPAN
            if token is not None:
                _trace._reset(token)
            on = _trace.armed()
            token = _trace._push(_trace.TraceContext(
                f"wave-{wave}", f"wave-{wave}", "serve.wave")) \
                if on else None
            wave += 1
            with self._cv:
                if (not self._waiting and not self._running
                        and not self._prefilling and not self._closing):
                    with (_span("serve.idle", cat="serve") if on
                          else NO_SPAN):
                        while (not self._waiting and not self._running
                               and not self._prefilling
                               and not self._closing):
                            self._cv.wait()
                if self._closing and not self._running \
                        and not self._prefilling \
                        and (not self._drain or not self._waiting):
                    for req in self._waiting:
                        _fail(req, ServerClosed(
                            "engine closed before admission"))
                    self._waiting.clear()
                    return
                with (_span("serve.admit", cat="serve",
                            waiting=len(self._waiting)) if on
                      else NO_SPAN) as sp:
                    admitted, expired = self._admit_locked()
                    if on:
                        sp.set(admitted=len(admitted),
                               expired=len(expired))
            # expired waiters resolve OUTSIDE self._cv: Future callbacks
            # run inline and may re-enter submit()
            now = time.perf_counter()
            for req in expired:
                self._count("timeouts")
                _trace.flightrec_record(
                    "serve.timeout", self.name,
                    waited_ms=round((now - req.t_submit) * 1e3, 1),
                    trace_id=req.ctx.trace_id if req.ctx else None)
                _fail(req, RequestTimeout(
                    f"deadline expired after "
                    f"{(now - req.t_submit) * 1e3:.1f}ms waiting for a "
                    f"KV slot"))
            if (not admitted and not expired and not self._running
                    and not self._prefilling):
                # waiting requests exist but no slot freed up (something
                # outside the engine holds claims): timed wait, re-check —
                # never a busy spin
                with self._cv:
                    if (self._waiting and not self._running
                            and not self._prefilling):
                        self._cv.wait(timeout=0.005)
                continue
            try:
                self._iterate(admitted, jnp, on)
            except BaseException as e:
                # a step failure fails the IN-FLIGHT requests, frees
                # their slots, and the engine keeps serving (the PR-3
                # batch-error contract). A RESOURCE_EXHAUSTED step
                # additionally leaves the OOM black box (census + plans
                # + flightrec ring) BEFORE the slab reallocation below
                # rewrites the memory picture.
                mem_on_oom(e, where="serve.continuous")
                # what was dispatched and not read belongs to the requests
                # failed below: both outstanding iterations go unread
                self._unread = None  # mxlint: disable=lock-shared-mutation -- warm-up runs before the scheduler thread starts; afterwards only that thread touches it
                err = e if isinstance(e, MXNetError) else ServeError(
                    f"engine step failed: {type(e).__name__}: {e}")
                with self._cv:
                    doomed = (list(self._running.values())
                              + list(self._prefilling.values()))
                    self._running.clear()
                    self._prefilling.clear()
                for req in doomed:
                    if req.entry is not None:
                        self._cache.release(req.entry)
                        req.entry = None
                    if req.slot is not None:
                        self.pool.free(req.slot)
                    _fail(req, err)
                self._count("errors", len(doomed))
                # the step programs DONATE the KV buffers: an exception
                # raised mid-execution (after donation) leaves pool.k/v
                # invalidated — fresh buffers or every later wave dies
                # on 'Array has been deleted'. Every in-flight request
                # was just failed, so zeroed slabs are the correct state.
                self.pool.reallocate()
                self._pending.clear()  # mxlint: disable=lock-shared-mutation -- warm-up runs before the scheduler thread starts; afterwards only that thread touches it
                self._reset_lanes()
                if self._canary is not None:
                    # fresh zeroed slabs replaced the poisoned row
                    self._canary.rearm()
                if self._cache is not None:
                    # the reallocation zeroed the slab: every cached
                    # prefix's KV bytes are gone, so the index goes too
                    # (its dedicated rows stay claimed and refill later)
                    self._cache.clear()  # mxlint: disable=lock-shared-mutation -- PrefixCache serializes internally (leaf lock); every ref was just released above

    def _admit_locked(self):
        """Deadline-aware admission (runs under self._cv): drop expired
        waiters from the queue, then grant free slots
        earliest-deadline-first within the prefill token budget.

        A waiting request's cost is its POST-CACHE cost: the tokens the
        next prefill wave will actually process — the uncached suffix,
        capped at one window (longer suffixes stream chunk by chunk). So
        a fully-cached request is near-free, ranks ahead of a cold long
        prompt at an equal deadline, and a request that would bust the
        budget no longer blocks cheaper waiters behind it (`continue`,
        not `break` — the old full-prompt `break` both overbilled cache
        hits and head-of-line-blocked on them). Chunks already streaming
        bill the budget first. The >= 1 admission guarantee when a slot
        is free is unchanged. Returns (admitted, expired); the caller
        resolves expired futures off-lock."""
        now = time.perf_counter()
        expired = [r for r in self._waiting
                   if r.deadline is not None and now > r.deadline]
        if expired:
            dropset = set(id(r) for r in expired)
            self._waiting = deque(r for r in self._waiting  # mxlint: disable=lock-shared-mutation -- _admit_locked runs with self._cv held by its only caller (_loop)
                                  if id(r) not in dropset)
        admitted = []
        budget = self.prefill_budget
        for req in self._prefilling.values():
            budget -= min(self.prefill_window,
                          int(req.prompt.size) - req.prefill_pos)
        free = self.pool.free_count()
        if free and self._waiting:
            costs = {}
            for req in self._waiting:
                mlen = 0
                if self._cache is not None:
                    _, mlen = self._cache.match(req.prompt,
                                                acquire=False)
                costs[id(req)] = min(int(req.prompt.size) - mlen,
                                     self.prefill_window)
            ranked = sorted(
                self._waiting,
                key=lambda r: r.sort_key()[:2] + (costs[id(r)],
                                                  r.t_submit))
            for req in ranked:
                if not free or len(admitted) >= self.prefill_lanes:
                    break
                cost = costs[id(req)]
                if admitted and budget - cost < 0:
                    continue    # over budget; a cheaper waiter may fit
                try:
                    req.slot = self.pool.claim()
                except SlotsFullError:   # raced a test's direct claim
                    break
                req.t_admit = now
                req.wave = self._counters["decode_iterations"]
                if self._cache is not None:
                    # pin the matched prefix for this request's lifetime
                    # (released at retire); eviction can never reclaim
                    # the row while the copy/read is possible
                    entry, mlen = self._cache.match(req.prompt)
                    if entry is not None:
                        req.entry = entry
                        req.cached_len = mlen
                        req.prefill_pos = mlen
                free -= 1
                budget -= cost
                admitted.append(req)
            if admitted:
                dropset = set(id(r) for r in admitted)
                self._waiting = deque(r for r in self._waiting  # mxlint: disable=lock-shared-mutation -- _admit_locked runs with self._cv held by its only caller (_loop)
                                      if id(r) not in dropset)
        for req in admitted:
            self._prefilling[req.slot] = req  # mxlint: disable=lock-shared-mutation -- _admit_locked runs with self._cv held by its only caller (_loop)
        return admitted, expired

    def _iterate(self, admitted, jnp, on):
        """One iteration's device work, dispatch before read: the prefill
        programs, the decode wave, and only then the blocking read of
        what the PREVIOUS iteration dispatched (`_unread`: first tokens,
        the wave's tokens, counters), its bookkeeping and the retirement
        of what it finished — all of it while the device runs what this
        iteration just handed over. An iteration that finds nothing to
        dispatch reads at once, so a last token never waits for traffic.

        A drafting wave (`draft_tokens > 0`) packs a token history that
        the host builds from the tokens read so far: it reads its OWN
        iteration back as soon as it is dispatched, and a request's first
        wave is the one after its first token is read. Same order, the
        read one record earlier.

        The read sits in the iteration's `serve.decode_batch` span
        (`.readback`, `.emit`) whenever there is one — it dispatches a
        wave, or the record read holds one — and else, first tokens only,
        in a `serve.prefill_batch` span of its own (`.readback`)."""
        rec = _Unread()
        self._pending = rec.counters  # mxlint: disable=lock-shared-mutation -- warm-up runs before the scheduler thread starts; afterwards only that thread touches it
        reading = rec if self.draft_tokens else self._unread
        # a wave dispatched now goes out while this one is unread
        unread_wave = reading is not None and reading.wave is not None
        done = []
        # _prefilling is only ever mutated on this thread, so the
        # unlocked read is single-writer safe
        if admitted or self._prefilling:
            with (_span("serve.prefill_batch", cat="serve",
                        requests=len(admitted)) if on else NO_SPAN) as sp:
                self._dispatch_prefill(admitted, rec, jnp, on, sp)
        lanes = self._wave_lanes()
        if lanes or unread_wave:
            with (_span("serve.decode_batch", cat="serve",
                        steps=self.decode_steps) if on else NO_SPAN) as sp:
                if lanes:
                    self._dispatch_wave(lanes, rec, jnp, on)
                    if unread_wave:
                        self._count("waves_ahead")
                active = tokens = sampled = 0
                if reading:
                    done, active, tokens, sampled = self._read(
                        reading, True, on)
                if on:
                    sp.set(ahead=int(bool(lanes) and unread_wave),
                           active=active, tokens=tokens, sampled=sampled)
        elif reading:
            with (_span("serve.prefill_batch", cat="serve", requests=0)
                  if on else NO_SPAN):
                done = self._read(reading, False, on)[0]
        self._unread = rec if rec and not self.draft_tokens else None  # mxlint: disable=lock-shared-mutation -- warm-up runs before the scheduler thread starts; afterwards only that thread touches it
        self._retire(done, on)

    def _dispatch_prefill(self, admitted, rec, jnp, on, sp):
        """One prefill wave, dispatched and not read: slab-to-slab KV row
        copies for the admitted prefix-cache hits, the fixed-shape
        windowed program for lanes starting at page offset 0, then ONE
        chunk dispatch advancing EVERY lane with pending suffix/chunk
        work (admitted hits and long prompts mid-stream alike). A request
        whose prompt ends here — the host knows that from its size —
        JOINS the decode lanes on the device (`_join_lanes`: its first
        token straight from `sample_first`'s output, its length and
        budget from the host) and moves to `_running`; its first token
        reaches the host with `rec` (`rec.firsts`). `prefill_tokens`
        bills only tokens a program actually processed (suffix-only on a
        hit).

        Runs inside the loop's `serve.prefill_batch` span `sp`; armed
        (`on`), each program's host side is split into `.pack` (NumPy
        arrays and their transfers) and `.dispatch` (the jit calls
        returning, the join among them)."""
        _fault.inject("serve.execute")
        W = self.prefill_window
        g = self.pool.garbage_row
        hits = [r for r in admitted if r.cached_len > 0]
        cold = [r for r in admitted if r.cached_len == 0]
        if hits:
            # memory-bound copy replaces compute-bound prefill: the
            # pinned cache rows land in the claimed slots before this
            # wave's programs run (same thread, same device stream)
            self._dispatch_copy(
                [(r.entry.row, r.slot, r.cached_len) for r in hits],
                "hit", on)
            self._count("prefix_hits", len(hits))
            self._count("prefix_cached_tokens",
                        int(sum(r.cached_len for r in hits)))
        if self._cache is not None and cold:
            self._count("prefix_misses", len(cold))
        n_tokens = 0
        if cold:
            with (_span("serve.prefill_batch.pack", cat="serve") if on
                  else NO_SPAN):
                P = self.prefill_lanes
                toks = _np.zeros((P, W), dtype=_np.int32)
                lens = _np.ones((P,), dtype=_np.int32)
                rows = _np.full((P,), g, dtype=_np.int32)
                temps = _np.zeros((P,), dtype=_np.float32)
                tks = _np.zeros((P,), dtype=_np.int32)
                tps = _np.ones((P,), dtype=_np.float32)
                keys = _np.zeros((P, 2), dtype=_np.uint32)
                ended = []                   # (req, lane): prompt ends here
                for i, req in enumerate(cold):
                    head = min(int(req.prompt.size), W)
                    toks[i, :head] = req.prompt[:head]
                    lens[i] = head
                    rows[i] = req.slot
                    temps[i] = req.temperature
                    tks[i] = req.top_k
                    tps[i] = req.top_p
                    keys[i] = req.key
                    req.prefill_pos = head
                    n_tokens += head
                    if head == req.prompt.size:
                        ended.append((req, i))
                jtoks, jlens, jrows = (jnp.asarray(toks), jnp.asarray(lens),
                                       jnp.asarray(rows))
                sample = (jnp.asarray(temps), jnp.asarray(tks),
                          jnp.asarray(tps), jnp.asarray(keys), jlens - 1)
            with (_span("serve.prefill_batch.dispatch", cat="serve")
                  if on else NO_SPAN):
                cache = self.pool.buffers()
                *cache, logits = self._outputs(self._prefill_prog(
                    self.model.params, *cache, jtoks, jlens, jrows))
                first = _sample_first(logits, *sample)
                self.pool.swap_buffers(*cache)
                self._join(first, ended, rec, jnp)
        # chunk wave: admitted hits prefill their suffix, long prompts
        # mid-stream advance one window — ONE fixed-shape dispatch at
        # pool width; lanes with no chunk work scatter into garbage
        with self._cv:
            pre = [self._prefilling[s] for s in sorted(self._prefilling)]
        coldset = set(id(r) for r in cold)
        chunkers = [r for r in pre
                    if id(r) not in coldset
                    and r.prefill_pos < int(r.prompt.size)]
        if self._chunk_compact:
            # lanes are prefill lanes: the oldest admissions take them,
            # the chunkers beyond them wait a wave
            chunkers = sorted(chunkers, key=lambda r: r.t_admit)[
                :self.prefill_lanes]
            lane_of = {r.slot: i for i, r in enumerate(chunkers)}
        else:
            lane_of = {r.slot: r.slot for r in chunkers}
        if chunkers:
            with (_span("serve.prefill_batch.pack", cat="serve") if on
                  else NO_SPAN):
                S = (self.prefill_lanes if self._chunk_compact
                     else self.pool.max_slots)
                ctoks = _np.zeros((S, W), dtype=_np.int32)
                offs = _np.zeros((S,), dtype=_np.int32)
                nval = _np.zeros((S,), dtype=_np.int32)
                crows = _np.full((S,), g, dtype=_np.int32)
                temps = _np.zeros((S,), dtype=_np.float32)
                tks = _np.zeros((S,), dtype=_np.int32)
                tps = _np.ones((S,), dtype=_np.float32)
                keys = _np.zeros((S, 2), dtype=_np.uint32)
                fold = _np.zeros((S,), dtype=_np.int32)
                ended = []
                for req in chunkers:
                    s = lane_of[req.slot]
                    crows[s] = req.slot
                    n = min(W, int(req.prompt.size) - req.prefill_pos)
                    ctoks[s, :n] = req.prompt[req.prefill_pos:
                                              req.prefill_pos + n]
                    offs[s] = req.prefill_pos
                    nval[s] = n
                    temps[s] = req.temperature
                    tks[s] = req.top_k
                    tps[s] = req.top_p
                    keys[s] = req.key
                    fold[s] = int(req.prompt.size) - 1
                    req.prefill_pos += n
                    n_tokens += n
                    if req.prefill_pos == int(req.prompt.size):
                        ended.append((req, s))
                # smallest warmed extent covering the furthest lane: the
                # wave's attention read scales with streamed progress
                need = int((offs + nval).max())
                ext = next(x for x in self._chunk_extents if x >= need)
                chunk_args = [jnp.asarray(ctoks), jnp.asarray(offs),
                              jnp.asarray(nval)]
                if self._chunk_compact:
                    chunk_args.append(jnp.asarray(crows))
                sample = (jnp.asarray(temps), jnp.asarray(tks),
                          jnp.asarray(tps), jnp.asarray(keys),
                          jnp.asarray(fold))
            with (_span("serve.prefill_batch.dispatch", cat="serve")
                  if on else NO_SPAN):
                cache = self.pool.buffers()
                *cache, logits = self._outputs(self._chunk_progs[ext](
                    self.model.params, *cache, *chunk_args))
                first = _sample_first(logits, *sample)
                self.pool.swap_buffers(*cache)
                self._join(first, ended, rec, jnp)
        if admitted:
            self._count("admitted", len(admitted))
        if cold or chunkers:
            self._count("prefill_batches")
        if n_tokens:
            self._count("prefill_tokens", n_tokens)
        if on:
            sp.set(tokens=n_tokens)

    def _join(self, first, ended, rec, jnp):
        """The requests whose prompt ended in the program that produced
        `first` ((request, its lane there) pairs) become decode lanes:
        on the device now (`_join_lanes`), on the host by moving to
        `_running` with the budget the lane was given; `rec` keeps
        `first` for the read that delivers the tokens."""
        if not ended:
            return
        join = _np.full((3, self.pool.max_slots), -1, dtype=_np.int32)
        for req, lane in ended:
            plen = int(req.prompt.size)
            # what the request still wants after its first token, capped
            # by its page space (see `_wave_lanes`)
            req.left = min(req.max_new - 1, self.max_len - 1 - plen)
            join[:, req.slot] = lane, plen, req.left
        self._lanes = self._join_prog(*self._lanes, first, jnp.asarray(join))  # mxlint: disable=lock-shared-mutation -- warm-up runs before the scheduler thread starts; afterwards only that thread touches it
        first.copy_to_host_async()
        rec.firsts.append((first, ended))
        with self._cv:
            for req, _ in ended:
                self._prefilling.pop(req.slot, None)
                self._running[req.slot] = req

    def _dispatch_copy(self, pairs, why, on):
        """ONE fixed-shape donated program moves the leading positions of
        KV slot rows slab-to-slab, `(src row, dst row, positions)` a
        pair: cache row -> claimed slot at admission (`why` "hit", the
        matched length), retiring slot -> cache row at "publish" (the
        entry's length). Idle lanes carry length 0 and move nothing."""
        import jax.numpy as jnp
        positions = sum(n for _, _, n in pairs)
        with (_span("serve.copy.dispatch", cat="serve", pairs=len(pairs),
                    positions=positions, why=why) if on else NO_SPAN):
            lanes = _np.zeros((3, self.prefill_lanes), dtype=_np.int32)
            lanes[:, :len(pairs)] = _np.asarray(pairs, dtype=_np.int32).T
            kb, vb = self.pool.buffers()
            k, v = self._copy_prog(kb, vb, *(jnp.asarray(a) for a in lanes))
            self.pool.swap_buffers(k, v)
        self._count("copied_positions", positions)

    def _wave_lanes(self):
        """The lanes the next decode wave advances, by slot: the running
        requests with budget left as far as the host can know
        (`req.left`; with an `eos_id` the device may know better, and the
        lane then idles through a wave the host still counts on).

        A lane's budget is what the request still wants, capped by its
        page space. The cap mirrors `_finished`'s `cache_len + 1 >=
        max_len` stop: the K=1 engine (and the reference) emit their last
        token FROM state max_len - 2, so a multi-step wave may advance
        cache_len at most to max_len - 1 — not max_len, which would emit
        one extra token and break the K-invariance contract."""
        with self._cv:
            running = dict(self._running)
        if self.draft_tokens:
            # every dispatched wave has been read: the budget is exact, and
            # a request whose first token is not read yet waits a wave
            for req in running.values():
                req.left = min(req.max_new - len(req.generated),
                               self.max_len - 1 - req.cache_len) \
                    if req.generated else 0
        return {slot: req for slot, req in running.items() if req.left > 0}

    def _dispatch_wave(self, lanes, rec, jnp, on):
        """ONE decode wave, dispatched and not read: every lane of `lanes`
        advances up to `decode_steps` tokens (times up to `draft_tokens +
        1` when speculating) through the compiled multi-step program.
        Program lanes are ALL pool rows (request slots, mid-prefill
        slots, and prefix-cache rows alike) so lane index == slab row;
        the others are inactive and scatter into the garbage row. The
        wave's `tokens`, `lengths` and `steps_left` are the device's own
        (`_lanes`), and `_advance_lanes` derives the next wave's from this
        wave's outputs, so nothing here waits for the device; only the
        sampling parameters, constants of a request, are packed. A
        drafting wave consumes the token history besides, which the host
        builds from the tokens read so far, and packs all of its inputs
        there. The wave's outputs go to `rec.wave`.

        Runs inside the loop's `serve.decode_batch` span; armed (`on`),
        `.pack` is the NumPy arrays and their transfers, `.dispatch` the
        program calls returning."""
        S = self.pool.max_slots
        draft = self.draft_tokens
        with (_span("serve.decode_batch.pack", cat="serve") if on
              else NO_SPAN):
            temps = _np.zeros((S,), dtype=_np.float32)
            tks = _np.zeros((S,), dtype=_np.int32)
            tps = _np.ones((S,), dtype=_np.float32)
            keys = _np.zeros((S, 2), dtype=_np.uint32)
            if draft:
                state = _np.zeros((3, S), dtype=_np.int32)
                buf = _np.zeros((S, self.max_len), dtype=_np.int32)
            for slot, req in lanes.items():
                temps[slot] = req.temperature
                tks[slot] = req.top_k
                tps[slot] = req.top_p
                keys[slot] = req.key
                if draft:
                    state[:, slot] = (req.generated[-1], req.cache_len,
                                      req.left)
                    # the draft source: token history = prompt +
                    # generated, exactly cache_len + 1 valid entries (tail
                    # not yet in KV)
                    plen = req.prompt.size
                    buf[slot, :plen] = req.prompt
                    buf[slot, plen:plen + len(req.generated)] = \
                        req.generated
            if draft:
                self._lanes = tuple(jnp.asarray(a) for a in state)  # mxlint: disable=lock-shared-mutation -- warm-up runs before the scheduler thread starts; afterwards only that thread touches it
            args = [*self._lanes, jnp.asarray(temps), jnp.asarray(tks),
                    jnp.asarray(tps), jnp.asarray(keys)]
            if draft:
                args.append(jnp.asarray(buf))
        with (_span("serve.decode_batch.dispatch", cat="serve") if on
              else NO_SPAN):
            cache = self.pool.buffers()
            n = len(cache)
            out = self._outputs(self._decode_prog(self.model.params, *cache,
                                                  *args))
            self.pool.swap_buffers(*out[:n])
            out = out[n:]
            if not draft:
                self._lanes = self._advance_prog(*self._lanes, *out)  # mxlint: disable=lock-shared-mutation -- warm-up runs before the scheduler thread starts; afterwards only that thread touches it
                for req in lanes.values():
                    req.left -= min(req.left, self.decode_steps)
            for a in out:
                a.copy_to_host_async()
        # lanes whose temperature the wave packed above 0: with any, the
        # program's sampler took its sorting side for every lane
        rec.wave = (lanes, out, int(_np.count_nonzero(temps)))

    def _read(self, rec, in_wave, on):
        """Read back what an iteration dispatched (`rec`) and do its
        bookkeeping: first tokens, then the wave's. Returns (the requests
        that finished, lanes the wave advanced, tokens it emitted, lanes
        it sampled for). The blocking reads are the `.readback` span of
        the loop's open span (`serve.decode_batch` where `in_wave`, else
        `serve.prefill_batch`); the per-lane bookkeeping and counters are
        `serve.decode_batch.emit`."""
        name = "serve.decode_batch" if in_wave else "serve.prefill_batch"
        draft = self.draft_tokens
        with (_span(name + ".readback", cat="serve") if on else NO_SPAN):
            firsts = [(_np.asarray(first), ended)
                      for first, ended in rec.firsts]
            lanes, out, sampled_lanes = rec.wave or ({}, None, 0)
            if rec.wave is not None:
                # (decode_steps, S) tokens and (S,) counts; drafting,
                # (steps, S, draft+1) blocks, (steps, S) counts a block,
                # (S,) totals, accepted and rejected drafts
                out = [_np.asarray(a) for a in out]
                if self._canary is not None:
                    self._canary.check(where="serve.decode")
                _sanitize.poll(where="serve.decode")
            self._read_counters(rec.counters)
        n_active = n_tokens = 0
        with (_span("serve.decode_batch.emit", cat="serve")
              if on and in_wave else NO_SPAN):
            now = time.perf_counter()
            touched = []
            n_sampled = 0
            for first, ended in firsts:
                for req, lane in ended:
                    req.cache_len = int(req.prompt.size)
                    req.generated.append(int(first[lane]))
                    req.t_first = req.t_last = now
                    n_sampled += int(req.temperature > 0)
                    touched.append(req)
            if rec.wave is not None:
                emitted = out[2] if draft else out[1]
                lens = []
                for slot, req in lanes.items():
                    if req.t_done is not None:
                        # retired at the read before this one, by an eos
                        # the host had not seen when this wave went out:
                        # the lane idled here, the slot may have a tenant
                        continue
                    n_new = int(emitted[slot])
                    if n_new > 0:
                        if draft:
                            for i in range(out[1].shape[0]):
                                m = int(out[1][i, slot])
                                req.generated.extend(
                                    int(t) for t in out[0][i, slot, :m])
                        else:
                            req.generated.extend(
                                int(t) for t in out[0][:n_new, slot])
                        req.cache_len += n_new
                        req.t_last = now
                        n_active += 1
                        n_tokens += n_new
                        if req.temperature > 0:
                            n_sampled += n_new
                        lens.append(req.cache_len)
                    touched.append(req)
                self._count("decode_iterations")
                self._count("decode_tokens", n_tokens)
                # the lanes the wave advanced, not those it carried spent
                self._count("active_sum", n_active)
                if sampled_lanes:
                    self._count("sampled_waves")
                # the advanced lanes' lengths after this wave: one
                # vectorised sum a cache kind, no loop over lanes
                live = self.pool.bytes_by_kind(
                    _np.asarray(lens, dtype=_np.int64))
                with self._mlock:
                    for kind, nbytes in live.items():
                        self._cache_live[kind] += nbytes
                if draft:
                    self._count("draft_accepted", int(out[3].sum()))
                    self._count("draft_rejected", int(out[4].sum()))
            if n_sampled:
                self._count("sampled_tokens", n_sampled)
            # a request touched twice (its first token and its first wave
            # in one record) is judged once
            done = [req for req in dict.fromkeys(touched)
                    if self._finished(req)]
        return done, n_active, n_tokens, sampled_lanes

    def _finished(self, req):
        if len(req.generated) >= req.max_new:
            return True
        if self.eos_id is not None and req.generated[-1] == self.eos_id:
            return True
        # page full: the NEXT decode would write past the slot
        return req.cache_len + 1 >= self.max_len

    def _retire(self, done, on):
        """Free slots and resolve futures; one request's whole life —
        prefill + N decode iterations — closes as ONE trace here."""
        if not done:
            return
        with (_span("serve.retire", cat="serve", n=len(done)) if on
              else NO_SPAN):
            self._retire_each(done, on)

    def _retire_each(self, done, on):
        prof = _profiler_on()
        for req in done:
            with self._cv:
                self._running.pop(req.slot, None)
            if self._cache is not None:
                if req.entry is not None:
                    # the hit path never publishes: its suffix KV came
                    # from the chunk program, and the cache must stay
                    # canonical-provenance (windowed head + chunks) so
                    # every later hit is bit-identical to a cold build
                    self._cache.release(req.entry)  # mxlint: disable=lock-shared-mutation -- PrefixCache serializes internally (leaf lock)
                    req.entry = None
                elif self.prefix_cache_insert:
                    published = self._cache.insert(req.prompt)  # mxlint: disable=lock-shared-mutation -- PrefixCache serializes internally (leaf lock)
                    if published is not None:
                        # publish BEFORE free: the copy is dispatched on
                        # this thread ahead of any wave that could
                        # rewrite the retiring slot's row
                        self._dispatch_copy([(req.slot, *published)],
                                            "publish", on)
            self.pool.free(req.slot)
            out = _np.asarray(req.generated, dtype=_np.int32)
            if self.eos_id is not None:
                hits = _np.nonzero(out == self.eos_id)[0]
                if hits.size:
                    out = out[:int(hits[0]) + 1]
            # the timeline is whole before the future resolves: a done
            # callback reads every field of `fut.timing`
            now = req.t_done = time.perf_counter()
            if req.future.set_running_or_notify_cancel():
                req.future.set_result(out)
            total_ms = (now - req.t_submit) * 1e3
            n = len(req.generated)
            with self._mlock:
                self._latencies.append((
                    (req.t_first - req.t_submit) * 1e3,
                    (now - req.t_first) * 1e3 / (n - 1) if n > 1 else None,
                    total_ms))
            self._count("replies")
            self._count("retired")
            if prof and req.t_first is not None:
                # every request's wait for a slot and its prefill, from
                # the timeline it keeps anyway (no clock read here):
                # submit -> admission -> first token, caused by the wave
                # whose iteration admitted it. Children of the request's
                # root where it has one (submitted under a collector), of
                # this retirement's span else; `retired_us` says when
                # they were noted (a wait ends long before its request).
                # They begin before the collector was armed and overlap
                # other requests', hence an async pair, not `X`
                ids = dict(request=req.rid, cause=f"wave-{req.wave}",
                           slot=req.slot, retired_us=now * 1e6)
                for name, t0, t1 in (
                        ("serve.queue", req.t_submit, req.t_admit),
                        ("serve.prefill", req.t_admit, req.t_first)):
                    record_span(
                        name, (t1 - t0) * 1e6, ts_us=t0 * 1e6, cat="serve",
                        ctx=(None if req.ctx is None
                             else _trace.child_context(req.ctx, name)),
                        async_id=req.rid, prompt_tokens=req.prompt.size,
                        cached_tokens=req.cached_len, **ids)
            if req.ctx is not None and prof:
                if req.t_first is not None and req.t_last > req.t_first:
                    # first -> last token: the N decode iterations as one
                    # node (per-iteration spans at thousands of tokens/s
                    # would swamp the trace; the batch lane has them)
                    record_span("serve.decode",
                                (req.t_last - req.t_first) * 1e6,
                                ts_us=req.t_first * 1e6, cat="serve",
                                ctx=_trace.child_context(req.ctx,
                                                         "serve.decode"),
                                tokens=len(req.generated), slot=req.slot)
                record_span("serve.request", total_ms * 1e3,
                            ts_us=req.t_submit * 1e6, cat="serve",
                            ctx=req.ctx, tokens=len(req.generated))


# engine counter -> process-wide SERVE_STATS key (profiler.serve_stats()):
# the decode_* family is the continuous-batching analog of the PR-3 rows
_ENGINE_TO_SERVE_KEY = {
    "requests": "requests", "replies": "replies",
    "rejected": "rejected", "timeouts": "timeouts", "errors": "errors",
    "programs_compiled": "programs_compiled",
    "decode_iterations": "decode_iterations",
    "decode_tokens": "decode_tokens",
    "prefill_tokens": "decode_prefill_tokens",
    "admitted": "decode_admitted",
    "retired": "decode_retired",
    "sampled_tokens": "decode_sampled_tokens",
    "draft_accepted": "decode_draft_accepted",
    "draft_rejected": "decode_draft_rejected",
}
