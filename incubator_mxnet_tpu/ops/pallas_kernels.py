"""Pallas TPU kernels for the fused-op tier (ops/fused.py).

Reference contrast: MXNet's `USE_FUSION` RTC machinery generated pointwise
CUDA kernels at runtime (src/operator/fusion/fused_op.cu); here the worst
memory-bound offender classes the `mx.inspect` roofline attribution ranks
(`tools/offenders.py --model resnet18`) get hand-written TPU kernels
instead:

  * `apply_scale_shift_act` — ONE pass of `act(x*scale + shift [+ res])`
    over a (rows, channels) view: the normalize-scale-shift(-residual-relu)
    chains XLA splits into several 0.26-intensity `multiply_multiply`
    fusions become a single VMEM-resident sweep (one read of x/residual,
    one write of out — the roofline floor for this op class).
  * `avg_pool2d_fwd` / `avg_pool2d_bwd` — non-overlapping average pooling
    (kernel == stride, no padding; the GlobalAvgPool shape included) with a
    VMEM-tiled backward: the gradient is an in-register broadcast of the
    upstream tile instead of XLA's generic reduce-window gradient scatter
    (the 0.18-intensity `reduce-window` offender class).
  * `paged_attention_fwd` — decode attention over the serve.kv_pool
    slotted KV slab, read IN PLACE (no per-layer gather/copy of the
    `(slots, max_len, ...)` cache). Block-sparse by construction: the
    grid has one step per LIVE (lane, token-block) pair — its length is
    a run-time value, and the scalar-prefetched work list built from the
    per-lane `lengths` names each step's lane and block — so blocks past
    a lane's `[0, cur_len + C)` are neither fetched nor stepped over,
    and an idle lane costs one step. Online-softmax VMEM accumulators
    carry across a lane's consecutive blocks. At tile-aligned heads a
    plain-decode or verify block feeds K and V to the MXU as stored
    (bf16 codes, float32 sums; `_paged_body`). Optional per-position f32
    scales dequantize int8 slabs on the fly (serve.kv_pool
    `dtype="int8"`).
  * `grouped_matmul_fwd` — the held experts of a sparse-expert layer over
    the token-expert pairs sorted by expert: one grid step a (block of
    one expert's rows, F tile), the block's expert a scalar-prefetched
    index into the whole stack of experts, so each expert's weights
    stream once a block and an expert nobody chose costs nothing; the
    tokens' rows are gathered from, and the gated results summed into, a
    VMEM copy of the call's tokens.

Everything here takes and returns raw jax arrays and is shape-strict: the
caller (ops/fused.py) owns fallback policy, custom_vjp wiring and layout
handling. Kernels compute in float32 internally and cast to the input
dtype on the way out, matching ops/nn.py norm semantics under AMP.

Layout: channels-minor (the TPU-preferred NHWC family) — `x` is reshaped
by the caller to (M, C) for the apply kernel and kept (N, H, W, C) for
pooling. Tile sizes come from a VMEM budget (see `_block_rows`).
"""
from __future__ import annotations

import functools

__all__ = ["apply_scale_shift_act", "avg_pool2d_fwd", "avg_pool2d_bwd",
           "paged_attention_fwd", "grouped_matmul_fwd", "grouped_blocks",
           "expert_tile", "grouped_vmem_bytes", "supported_act", "ACTS"]

# activation set the kernels (and their hand-derived VJPs) support; None
# means identity. Kept in sync with ops/fused.py's dispatch tables. Exact
# gelu is not here: erf/erfc have no Pallas TPU lowering, so a gelu layer
# keeps its unfused composition (gluon checks this set before rewriting).
ACTS = (None, "relu", "sigmoid", "tanh", "silu")

_VMEM_BUDGET = 4 * 1024 * 1024   # bytes of f32 working set per program
# what one kernel may allocate in VMEM: the TPU compiler's default scoped
# limit is 16 MiB (v5e), and `_paged_blocks` sizes against 3/4 of it
_VMEM_SCOPED = 12 * 1024 * 1024
_LANES = 128


def _round_up(n, k):
    return -(-n // k) * k


def _tile_bytes(shape, itemsize):
    """Bytes a block of `shape` really occupies in VMEM: the minor dim is
    padded to 128 lanes and the second-minor to the dtype's sublane tile
    (8 rows of 32-bit, 16 of 16-bit, 32 of 8-bit). A (bt, 12, 64) bf16
    block takes bt*16*128*2 bytes, 2.7x its unpadded size."""
    *lead, rows, cols = shape
    n = _round_up(rows, 8 * (4 // itemsize)) * _round_up(cols, _LANES)
    for dim in lead:
        n *= dim
    return n * itemsize


def _pow2_block(n, footprint):
    """Largest power-of-two divisor `b` of `n` with `footprint(b)` inside
    `_VMEM_SCOPED`, or 0 when not even b = 1 fits."""
    b = n & -n                                # largest 2^k dividing n
    while b > 1 and footprint(b) > _VMEM_SCOPED:
        b //= 2
    return b if footprint(b) <= _VMEM_SCOPED else 0


def supported_act(act_type):
    return act_type in ACTS


def _act_f32(jax, jnp, u, act_type):
    if act_type is None:
        return u
    if act_type == "relu":
        return jax.nn.relu(u)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(u)
    if act_type == "tanh":
        return jnp.tanh(u)
    if act_type == "silu":
        return jax.nn.silu(u)
    raise ValueError(f"unsupported fused activation {act_type!r}")


def _block_rows(m, c, n_row_bufs, cap=1024):
    """Largest power-of-two row tile that divides `m` and keeps
    `n_row_bufs` (M, C)-shaped f32 buffers inside the VMEM budget (C
    counted as the 128-lane multiple a tile pads it to). A row tile must
    be a multiple of 8 sublanes or all of `m` (the TPU block rule), so
    an `m` without a factor of 8 is taken whole when it fits.
    Returns 0 when no legal tile fits."""
    row = _round_up(c, _LANES) * 4 * n_row_bufs
    bm = min(m & -m, cap)                     # largest 2^k dividing m
    while bm > 8 and bm * row > _VMEM_BUDGET:
        bm //= 2
    if bm % 8:
        bm = m
    return bm if bm * row <= _VMEM_BUDGET else 0


# ---------------------------------------------------------------------------
# fused scale/shift/activation/residual apply over (M, C)
# ---------------------------------------------------------------------------
def _apply_kernel(*refs, act_type, has_scale, has_residual):
    """out = act(x [*scale] + shift [+ residual]) on one (bm, C) tile.
    scale/shift are (1, C) rows broadcast down the tile."""
    import jax
    import jax.numpy as jnp

    it = iter(refs)
    x_ref = next(it)
    scale_ref = next(it) if has_scale else None
    shift_ref = next(it)
    res_ref = next(it) if has_residual else None
    o_ref = next(it)

    u = x_ref[...].astype(jnp.float32)
    if has_scale:
        u = u * scale_ref[...].astype(jnp.float32)
    u = u + shift_ref[...].astype(jnp.float32)
    if has_residual:
        u = u + res_ref[...].astype(jnp.float32)
    o_ref[...] = _act_f32(jax, jnp, u, act_type).astype(o_ref.dtype)


def apply_scale_shift_act(x2d, scale, shift, residual, act_type,
                          interpret=False):
    """Pallas apply pass. x2d/residual: (M, C); scale (optional): (C,);
    shift: (C,). Returns act(x*scale + shift + residual) in x2d.dtype, or
    None when the shape does not tile (caller falls back)."""
    import jax
    import jax.numpy as jnp
    import jax.experimental.pallas as pl

    m, c = x2d.shape
    n_bufs = 2 + (1 if residual is not None else 0)
    bm = _block_rows(m, c, n_bufs)
    if bm == 0 or m % bm:
        return None
    grid = (m // bm,)
    row_spec = pl.BlockSpec((bm, c), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, c), lambda i: (0, 0))
    in_specs = [row_spec]
    args = [x2d]
    if scale is not None:
        in_specs.append(vec_spec)
        args.append(scale.reshape(1, c))
    in_specs.append(vec_spec)
    args.append(shift.reshape(1, c))
    if residual is not None:
        in_specs.append(row_spec)
        args.append(residual)
    kernel = functools.partial(_apply_kernel, act_type=act_type,
                               has_scale=scale is not None,
                               has_residual=residual is not None)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((m, c), x2d.dtype),
        interpret=interpret,
        name="apply_scale_shift_act",
    )(*args)


# ---------------------------------------------------------------------------
# non-overlapping average pooling, NHWC
# ---------------------------------------------------------------------------
def _pool_fwd_kernel(x_ref, o_ref, *, ph, pw):
    import jax.numpy as jnp
    x = x_ref[0].astype(jnp.float32)          # (bh*ph, W, C)
    hh, w, c = x.shape
    x = x.reshape(hh // ph, ph, w // pw, pw, c)
    o_ref[0] = jnp.mean(x, axis=(1, 3)).astype(o_ref.dtype)


def _pool_bwd_kernel(dy_ref, dx_ref, *, ph, pw):
    """dX tile = upstream tile broadcast over each window / (ph*pw):
    the entire reduce-window gradient becomes an in-VMEM broadcast."""
    import jax.numpy as jnp
    dy = dy_ref[0].astype(jnp.float32)        # (bh, Wo, C)
    bh, wo, c = dy.shape
    g = dy * (1.0 / (ph * pw))
    g = jnp.broadcast_to(g[:, None, :, None, :], (bh, ph, wo, pw, c))
    dx_ref[0] = g.reshape(bh * ph, wo * pw, c).astype(dx_ref.dtype)


def _pool_blocks(n, h, w, c, ph, pw):
    """(grid, bh) row tiling for the pooling kernels, or None. The in
    and out blocks keep (W, C) whole as their tiled minor dims, so any
    `bh` is a legal block; it is sized so both blocks, double-buffered,
    and their f32 working copies fit (all counted at f32 width)."""
    if h % ph or w % pw:
        return None
    ho = h // ph

    def footprint(bh):
        return 4 * (_tile_bytes((bh * ph, w, c), 4)
                    + _tile_bytes((bh, w // pw, c), 4))

    bh = _pow2_block(ho, footprint)
    return ((n, ho // bh), bh) if bh else None


def avg_pool2d_fwd(x, ph, pw, interpret=False):
    """Forward non-overlapping NHWC average pool, or None (no tiling)."""
    import jax
    import jax.experimental.pallas as pl

    n, h, w, c = x.shape
    blocks = _pool_blocks(n, h, w, c, ph, pw)
    if blocks is None:
        return None
    grid, bh = blocks
    return pl.pallas_call(
        functools.partial(_pool_fwd_kernel, ph=ph, pw=pw),
        grid=grid,
        in_specs=[pl.BlockSpec((1, bh * ph, w, c), lambda b, i: (b, i, 0, 0))],
        out_specs=pl.BlockSpec((1, bh, w // pw, c), lambda b, i: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h // ph, w // pw, c), x.dtype),
        interpret=interpret,
        name="avg_pool2d_fwd",
    )(x)


# ---------------------------------------------------------------------------
# paged decode attention over the slotted KV slab
# ---------------------------------------------------------------------------
def _paged_body(c, h, q_bytes, kv_bytes, quantized):
    """Which of the two block bodies serves these static shapes (`c`
    queries a lane, `h` heads, `q_bytes`-wide queries over a slab of
    `kv_bytes`-wide elements).

    "flat": the (bt, H, D) tile is read as the (bt*H, D) matrix it already
    is in VMEM and every query row meets every (position, head) column on
    the MXU; the columns of other heads are masked. It needs H to be a
    whole number of sublane tiles of the narrower of the two dtypes (the
    views of the tile and of the (C, H, D) queries then move no byte) and
    no int8 scales (they sit on a position axis this view does not have).
    Its H-fold redundant columns cost matmul time in proportion to the
    C*H query rows, for every byte the block brings: on a v5e at (16, 128)
    bf16 heads it beats the head-major body 4x at 16 rows and 1.7x at
    256, loses from 512 on and cannot hold chunk prefill's 2048 rows in
    VMEM at all (PERF.md section 5), so it serves up to 256 rows.
    "head_major": the per-head batched contraction, for everything else."""
    sublanes = 8 * (4 // min(q_bytes, kv_bytes))
    if not quantized and h % sublanes == 0 and c * h <= 2 * _LANES:
        return "flat"
    return "head_major"


def paged_body(q, k_slab, k_scale=None):
    """The body `paged_attention_fwd` runs these arguments with: the one
    place that reads it off the arrays, for the kernel and for whoever
    counts its traces (`fused_stats()`)."""
    return _paged_body(q.shape[1], q.shape[2], q.dtype.itemsize,
                       k_slab.dtype.itemsize, k_scale is not None)


def _split_bf16(p):
    """The three bfloat16 pieces whose sum is the float32 `p` exactly
    (8 + 8 + 8 mantissa bits), stacked on the row axis: bf16 codes meet
    full-precision probabilities in single MXU passes, float32 sums."""
    import jax.numpy as jnp
    pieces = []
    for _ in range(3):
        piece = p.astype(jnp.bfloat16)
        pieces.append(piece)
        p = p - piece.astype(jnp.float32)
    return jnp.concatenate(pieces, axis=0)


def _live_blocks(length, chunk, bt, n_blocks):
    """Token blocks a lane at cache length `length` reads: its C queries
    see positions [0, length + C - 1]. An empty lane still reads one."""
    import jax.numpy as jnp
    return jnp.clip((length + chunk - 1) // bt + 1, 1, n_blocks)


def _paged_attn_kernel(lens_ref, lane_ref, blk_ref, *refs, bt, n_blocks,
                       chunk, scale, layer, quantized, body):
    """One LIVE (lane, token-block) pair of paged decode attention.

    The grid is one-dimensional and as long as the work: step i serves
    block `blk_ref[i]` of lane `lane_ref[i]`, the scalar-prefetched list
    of every lane's live blocks in lane order (`paged_attention_fwd`
    builds it from `lens_ref`), so a lane costs `ceil((len + C) / bt)`
    steps, an idle one a single step, and a dead tail none. A lane's
    blocks are consecutive steps: the VMEM scratch accumulators (running
    max `m`, normaliser `l`, weighted sum `acc`, all float32) carry its
    online softmax from its block 0 to its last, where the out block is
    written.

    Bodies (`_paged_body`), same mathematics, float32 scores,
    probabilities and sums in both:
      * flat — K and V enter the MXU as stored. Scores are one
        (C*H, D) x (bt*H, D)^T product: row (j, g) against column
        (t, h), kept where g == h and position t is inside the lane's
        `[0, len + j]`. Probabilities go to V as three bf16 pieces that
        sum to the float32 value (`_split_bf16`).
      * head_major — `chd,thd->hct` / `hct,thd->hcd` over float32 casts.
        int8 slabs: the scale blocks hold EVERY layer's scales for the
        token block (`(1, L, bt)` — a one-layer block would break the
        TPU rule that a block's second-minor dim is a multiple of 8 or
        the array's own) and row `layer` is picked here. A position's
        scale multiplies its score column / probability column, where
        positions already sit on the lane axis, instead of the
        (bt, H, D) codes."""
    import jax
    import jax.numpy as jnp
    import jax.experimental.pallas as pl

    it = iter(refs)
    q_ref = next(it)
    k_ref = next(it)
    v_ref = next(it)
    ks_ref = next(it) if quantized else None
    vs_ref = next(it) if quantized else None
    o_ref = next(it)
    m_ref = next(it)
    l_ref = next(it)
    acc_ref = next(it)

    i = pl.program_id(0)
    blk = blk_ref[i]
    lane_len = lens_ref[lane_ref[i]]
    _, h, d = q_ref.shape[1:]

    @pl.when(blk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # query j (the j-th chunk position) may read KV positions
    # [0, lane_len + j]: the in-chunk causal extension of the engine's
    # `t <= lengths` decode mask
    if body == "flat":
        rows, cols = chunk * h, bt * h
        mm_dtype = jnp.promote_types(q_ref.dtype, k_ref.dtype)
        q2 = q_ref[0].reshape(rows, d).astype(mm_dtype)
        k2 = k_ref[0, 0].reshape(cols, d).astype(mm_dtype)
        sco = jax.lax.dot_general(
            q2, k2, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (rows, cols)
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
        valid = jnp.logical_and(
            row % h == col % h,
            blk * bt + col // h <= lane_len + row // h)
        sco = jnp.where(valid, sco, -1e30)
        m_prev = m_ref[...]                               # (rows, 1)
        m_new = jnp.maximum(m_prev, jnp.max(sco, axis=-1, keepdims=True))
        p = jnp.exp(sco - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1,
                                                  keepdims=True)
        v2 = v_ref[0, 0].reshape(cols, d)
        if v2.dtype == jnp.bfloat16:
            pv = jnp.dot(_split_bf16(p), v2,
                         preferred_element_type=jnp.float32)
            pv = pv[:rows] + pv[rows:2 * rows] + pv[2 * rows:]
        else:
            pv = jnp.dot(p, v2.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new
    else:
        qf = q_ref[0].astype(jnp.float32)                 # (C, H, D)
        kf = k_ref[0, 0].astype(jnp.float32)              # (bt, H, D)
        vf = v_ref[0, 0].astype(jnp.float32)
        sco = jnp.einsum("chd,thd->hct", qf, kf) * scale
        if quantized:
            sco = sco * ks_ref[0, layer:layer + 1, :][None]   # (1, 1, bt)
        pos = blk * bt + jax.lax.broadcasted_iota(jnp.int32, (chunk, bt), 1)
        qoff = jax.lax.broadcasted_iota(jnp.int32, (chunk, bt), 0)
        sco = jnp.where((pos <= lane_len + qoff)[None], sco, -1e30)
        m_prev = m_ref[...]                               # (H, C)
        m_new = jnp.maximum(m_prev, jnp.max(sco, axis=-1))
        p = jnp.exp(sco - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        if quantized:
            p = p * vs_ref[0, layer:layer + 1, :][None]
        acc_ref[...] = (acc_ref[...] * alpha[..., None]
                        + jnp.einsum("hct,thd->hcd", p, vf))
        m_ref[...] = m_new

    @pl.when(blk == _live_blocks(lane_len, chunk, bt, n_blocks) - 1)
    def _finalize():
        if body == "flat":
            out = (acc_ref[...] / l_ref[...]).reshape(chunk, h, d)
        else:
            out = (acc_ref[...] / l_ref[...][..., None]).transpose(1, 0, 2)
        o_ref[0] = out.astype(o_ref.dtype)


def _paged_blocks(t, c, h, d, q_bytes, kv_bytes, n_layers=0):
    """Token-block size for paged attention, or 0 when nothing fits.

    A lane pays for whole blocks, so the block is the SMALLEST that keeps
    the lanes full: one lane width (128) of positions — the head-major
    score tile (H, C, bt) and an int8 slab's (L, bt) scale block have
    positions on the lane axis, and the flat score tile (C*H, bt*H) is
    whole vregs from bt*H >= 128 on. It is halved while the VMEM
    footprint exceeds `_VMEM_SCOPED`, and a `t` that 128 does not divide
    takes its largest power-of-two divisor. The footprint is counted on
    PADDED tiles (`_tile_bytes`), the way the TPU compiler allocates
    them, from what the kernel holds: the pipelined q/out/k/v(/scale)
    blocks twice over (double buffering), the float32 scratch
    accumulators, and the body's temporaries. `n_layers > 0` sizes an
    int8 slab's (L, bt) scale blocks, whose lane dim bt must be a
    multiple of 128 or all of `t`."""
    body = _paged_body(c, h, q_bytes, kv_bytes, bool(n_layers))

    def footprint(bt):
        pipelined = 2 * (2 * _tile_bytes((c, h, d), q_bytes)
                         + 2 * _tile_bytes((bt, h, d), kv_bytes))
        if n_layers:
            pipelined += 4 * _tile_bytes((n_layers, bt), 4)
        if body == "flat":
            rows, cols = c * h, bt * h
            scratch = (2 * _tile_bytes((rows, 1), 4)
                       + _tile_bytes((rows, d), 4))
            # scores, mask, probabilities and their residual in float32,
            # the three stacked bf16 pieces, the (3*rows, D) product
            temps = (4 * _tile_bytes((rows, cols), 4)
                     + _tile_bytes((3 * rows, cols), 2)
                     + _tile_bytes((3 * rows, d), 4))
        else:
            scratch = _tile_bytes((h, c, d), 4) + 2 * _tile_bytes((h, c), 4)
            # the float32 casts of the K and V tiles and the head-major
            # copy each einsum makes of its cast, the q cast, the (H, C,
            # bt) score and probability tiles
            temps = (2 * _tile_bytes((bt, h, d), 4)
                     + 2 * _tile_bytes((h, bt, d), 4)
                     + _tile_bytes((c, h, d), 4)
                     + 2 * _tile_bytes((h, c, bt), 4))
        return pipelined + scratch + temps

    bt = _pow2_block(_LANES if t % _LANES == 0 else t & -t, footprint)
    if n_layers and bt % _LANES and bt != t:
        return 0
    return bt


def _live_steps(lengths, c, bt, n_blocks):
    """The work list of a live-block grid: lane s owns steps
    [ends[s] - live[s], ends[s]) -> (ends, lane of each step, block of
    each step). Plain XLA ops outside the kernel; the calls of a
    micro-step's layers pass the same `lengths`, so the compiler keeps
    one copy of them."""
    import jax.numpy as jnp
    s_lanes = lengths.shape[0]
    live = _live_blocks(lengths, c, bt, n_blocks)
    ends = jnp.cumsum(live)
    steps = jnp.arange(s_lanes * n_blocks, dtype=jnp.int32)
    lane_of = jnp.minimum(
        jnp.sum(steps[:, None] >= ends[None, :], axis=1, dtype=jnp.int32),
        s_lanes - 1)
    blk_of = steps - (ends - live)[lane_of]
    return ends, lane_of, blk_of


def _paged_slab_call(q, k_slab, v_slab, lengths, layer, k_scale, v_scale,
                     scale, rows=None):
    """The pieces of `paged_attention_fwd`'s one `pallas_call` over the
    K/V slab pair (kernel, grid spec, out shape, arguments), or None when
    the shape does not tile. Lane s reads slab row s, or `rows[s]` where
    `rows` is given: a fourth scalar-prefetch list that the K/V and scale
    maps index by lane, as `_paged_leaf_call`'s do; without it the call is
    the lane-indexed one, operand for operand."""
    import jax
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    import jax.numpy as jnp

    s_lanes, c, h, d = q.shape
    t = k_slab.shape[2]
    if (rows is None and k_slab.shape[0] <= s_lanes) \
            or k_slab.shape[1] <= layer:
        return None
    quantized = k_scale is not None
    n_layers = k_slab.shape[1]
    body = paged_body(q, k_slab, k_scale)
    bt = _paged_blocks(t, c, h, d, q.dtype.itemsize,
                       k_slab.dtype.itemsize,
                       n_layers if quantized else 0)
    if bt == 0:
        return None
    n_blocks = t // bt
    scale = (1.0 / float(d) ** 0.5) if scale is None else float(scale)
    ends, lane_of, blk_of = _live_steps(lengths, c, bt, n_blocks)

    # scalar prefetch: lengths, [rows,] the lane and the block of each step
    prefetch = [lengths, lane_of, blk_of] if rows is None \
        else [lengths, rows, lane_of, blk_of]

    def row_of(i, refs):
        lane = refs[-2][i]
        return lane if rows is None else refs[1][lane]

    def qidx(i, *refs):
        return (refs[-2][i], 0, 0, 0)

    def kidx(i, *refs):
        return (row_of(i, refs), layer, refs[-1][i], 0, 0)

    def sidx(i, *refs):
        return (row_of(i, refs), 0, refs[-1][i])

    in_specs = [
        pl.BlockSpec((1, c, h, d), qidx),
        pl.BlockSpec((1, 1, bt, h, d), kidx),
        pl.BlockSpec((1, 1, bt, h, d), kidx),
    ]
    args = prefetch + [q, k_slab, v_slab]
    if quantized:
        in_specs.append(pl.BlockSpec((1, n_layers, bt), sidx))
        in_specs.append(pl.BlockSpec((1, n_layers, bt), sidx))
        args.extend([k_scale, v_scale])
    acc_shape = (c * h, d) if body == "flat" else (h, c, d)
    ml_shape = (c * h, 1) if body == "flat" else (h, c)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(ends[-1],),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, c, h, d), qidx),
        scratch_shapes=[
            pltpu.VMEM(ml_shape, jnp.float32),
            pltpu.VMEM(ml_shape, jnp.float32),
            pltpu.VMEM(acc_shape, jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_attn_kernel, bt=bt, n_blocks=n_blocks,
        chunk=c, scale=scale, layer=layer, quantized=quantized, body=body)
    if rows is not None:
        # the rows list is the index maps' alone: the body never reads it
        kernel = functools.partial(_skip_rows_ref, kernel)
    return (kernel, grid_spec,
            jax.ShapeDtypeStruct((s_lanes, c, h, d), q.dtype), tuple(args))


def _skip_rows_ref(kernel, lens_ref, rows_ref, *refs):
    return kernel(lens_ref, *refs)


def paged_attention_fwd(q, k_slab, v_slab, lengths, layer,
                        k_scale=None, v_scale=None, interpret=False,
                        scale=None, rows=None, out_dtype=None):
    """Pallas paged decode attention. `q`: (S, C, H, D) — C queries per
    lane at positions `lengths[s] + j` (C == 1 plain decode, C == k+1
    speculative verify, C == the window in chunk prefill).
    `k_slab`/`v_slab`: the whole KV pool slab (rows, layers, T, H, D);
    lane s reads row s of layer `layer` (row `rows[s]` where `rows` is
    given: lanes that are not pool rows, as chunk prefill's), positions
    clamped to `[0, lengths[s] + j]`. `k_scale`/`v_scale`: per-position
    f32 dequant scales (rows, layers, T) for int8 slabs.

    The grid has one step per LIVE (lane, token-block) pair and no other:
    its length is a run-time value (the sum of every lane's
    `ceil((len + C) / bt)`), and the two lists that name each step's lane
    and block ride as scalar prefetch beside `lengths`, for the index
    maps and the kernel. The call's time follows the live KV bytes.

    `layer=None` reads a cache LEAF (rows, T, Hkv * D) instead, any
    layer's, with grouped heads, `rows` as data and `out_dtype`
    (`_paged_leaf_call` has the contract): the same grid, the same name.

    Returns (S, C, H, D) in q.dtype, or None when the shape does not
    tile (the caller falls back and counts it)."""
    import jax.experimental.pallas as pl

    if layer is None:
        call = _paged_leaf_call(q, k_slab, v_slab, lengths, rows, scale,
                                out_dtype, interpret)
    else:
        call = _paged_slab_call(q, k_slab, v_slab, lengths, layer, k_scale,
                                v_scale, scale, rows)
    if call is None:
        return None
    kernel, grid_spec, out_shape, args = call
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="paged_attention_fwd",
    )(*args).reshape(q.shape)


def _paged_leaf_kernel(lens_ref, rows_ref, lane_ref, blk_ref, q_ref, k_ref,
                       v_ref, o_ref, m_ref, l_ref, acc_ref, *, bt, n_blocks,
                       chunk, heads, kv_heads, scale):
    """One LIVE (lane, token-block) pair of a cache LEAF's read: the
    block is the (bt, Hkv * D) matrix a `full` leaf stores per position
    run, every KV head side by side on the lane axis.

    Grouped heads cost no mask and no copy: query row (j, g) arrives
    zero outside the D columns of its KV head `g // (H // Hkv)`
    (`paged_leaf_attention_fwd` pads it), so ONE (C*H, Hkv*D) x
    (bt, Hkv*D)^T product gives each row the scores of its own head. The
    probabilities meet the whole V block (three bf16 pieces,
    `_split_bf16`) and the last block keeps, of each row's Hkv*D sums,
    the D columns of its own head. Live-block grid, online softmax and
    float32 sums as in `_paged_attn_kernel`."""
    import jax
    import jax.numpy as jnp
    import jax.experimental.pallas as pl

    i = pl.program_id(0)
    blk = blk_ref[i]
    lane_len = lens_ref[lane_ref[i]]
    rows_n, width = q_ref.shape[1:]
    d = width // kv_heads

    @pl.when(blk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    mm_dtype = jnp.promote_types(q_ref.dtype, k_ref.dtype)
    sco = jax.lax.dot_general(
        q_ref[0].astype(mm_dtype), k_ref[0].astype(mm_dtype),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale       # (rows, bt)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows_n, 1), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
    sco = jnp.where(blk * bt + col <= lane_len + row // heads, sco, -1e30)
    m_prev = m_ref[...]                                   # (rows, 1)
    m_new = jnp.maximum(m_prev, jnp.max(sco, axis=-1, keepdims=True))
    p = jnp.exp(sco - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    v2 = v_ref[0]
    if v2.dtype == jnp.bfloat16:
        pv = jnp.dot(_split_bf16(p), v2, preferred_element_type=jnp.float32)
        pv = pv[:rows_n] + pv[rows_n:2 * rows_n] + pv[2 * rows_n:]
    else:
        pv = jnp.dot(p, v2.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = m_new

    @pl.when(blk == _live_blocks(lane_len, chunk, bt, n_blocks) - 1)
    def _finalize():
        full = acc_ref[...] / l_ref[...]                  # (rows, Hkv * D)
        own = (row % heads) // (heads // kv_heads)        # (rows, 1)
        out = jnp.zeros((rows_n, d), jnp.float32)
        for kv in range(kv_heads):
            out = out + jnp.where(own == kv, full[:, kv * d:(kv + 1) * d],
                                  0.0)
        o_ref[0] = out.astype(o_ref.dtype)


def _paged_leaf_call(q, k_leaf, v_leaf, lengths, rows, scale, out_dtype,
                     interpret):
    """`paged_attention_fwd` over a named cache LEAF. `q`: (S, C, H, D)
    — C queries per lane at positions `lengths[s] + j`. `k_leaf`/
    `v_leaf`: (rows, T, Hkv * D), Hkv dividing H; lane s reads row
    `rows[s]` (default s), positions clamped to `[0, lengths[s] + j]`,
    query head g over KV head `g // (H // Hkv)`. The leaf may be any
    layer's: nothing here names one. The grid is `paged_attention_fwd`'s:
    one step per live (lane, token-block) pair.

    Returns the pieces of the one `pallas_call` (kernel, grid spec, out
    shape (S, C * H, D) in `out_dtype`, default q.dtype, arguments), or
    None when the shape does not tile."""
    import jax
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    import jax.numpy as jnp

    s_lanes, c, h, d = q.shape
    out_dtype = jnp.dtype(out_dtype or q.dtype)
    t, width = k_leaf.shape[1:]
    kv_heads = width // d
    if kv_heads * d != width or h % kv_heads:
        return None
    if width % _LANES and not interpret:      # a block's lane axis, whole
        return None
    q_rows = c * h

    def footprint(bt):
        kv = _tile_bytes((bt, width), k_leaf.dtype.itemsize)
        return (2 * (_tile_bytes((q_rows, width), q.dtype.itemsize)
                     + _tile_bytes((q_rows, d), out_dtype.itemsize) + 2 * kv)
                + _tile_bytes((q_rows, width), 4)
                + 2 * _tile_bytes((q_rows, 1), 4)
                # scores and probabilities, the three stacked pieces, the
                # (3 * rows, width) product and its sum
                + 3 * _tile_bytes((q_rows, bt), 4)
                + _tile_bytes((3 * q_rows, bt), 2)
                + 4 * _tile_bytes((q_rows, width), 4))

    bt = _pow2_block(_LANES if t % _LANES == 0 else t & -t, footprint)
    if bt == 0:
        return None
    n_blocks = t // bt
    scale = (1.0 / float(d) ** 0.5) if scale is None else float(scale)
    if rows is None:
        rows = jnp.arange(s_lanes, dtype=jnp.int32)
    # row (j, g) holds its D values in the columns of its KV head
    own = jnp.arange(h) // (h // kv_heads)
    onehot = (own[:, None] == jnp.arange(kv_heads)[None, :]).astype(q.dtype)
    q_wide = (q[:, :, :, None, :] * onehot[None, None, :, :, None]).reshape(
        s_lanes, q_rows, width)
    ends, lane_of, blk_of = _live_steps(lengths, c, bt, n_blocks)

    def qidx(i, lens_ref, rows_ref, lane_ref, blk_ref):
        return (lane_ref[i], 0, 0)

    def kidx(i, lens_ref, rows_ref, lane_ref, blk_ref):
        return (rows_ref[lane_ref[i]], blk_ref[i], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(ends[-1],),
        in_specs=[pl.BlockSpec((1, q_rows, width), qidx),
                  pl.BlockSpec((1, bt, width), kidx),
                  pl.BlockSpec((1, bt, width), kidx)],
        out_specs=pl.BlockSpec((1, q_rows, d), qidx),
        scratch_shapes=[pltpu.VMEM((q_rows, 1), jnp.float32),
                        pltpu.VMEM((q_rows, 1), jnp.float32),
                        pltpu.VMEM((q_rows, width), jnp.float32)],
    )
    kernel = functools.partial(
        _paged_leaf_kernel, bt=bt, n_blocks=n_blocks, chunk=c, heads=h,
        kv_heads=kv_heads, scale=scale)
    return (kernel, grid_spec,
            jax.ShapeDtypeStruct((s_lanes, q_rows, d), out_dtype),
            (lengths, rows, lane_of, blk_of, q_wide, k_leaf, v_leaf))


# ---------------------------------------------------------------------------
# the held experts' grouped matmul over token-expert pairs sorted by expert
# ---------------------------------------------------------------------------
#: weight bytes one grid step of the grouped matmul may bring (its gate, up
#: and down tiles); double-buffered, what the step's DMA hides behind
_EXPERT_STEP_BYTES = 10 * 1024 * 1024
#: VMEM one grouped-matmul call may ask for, its footprint and a quarter
#: more, of the 128 MiB a v5e core has: the rest is the compiler's
_EXPERT_VMEM = 100 * 1024 * 1024


def expert_tile(f, d, gated, itemsize):
    """Columns of the expert's hidden width F that one step of
    `grouped_matmul_fwd` reads: the largest divisor of `f` in whole lane
    tiles whose gate, up and down tiles stay inside `_EXPERT_STEP_BYTES`,
    or 0 when no divisor is whole lane tiles. The tile is the lane axis of
    the activation and the contraction of the down matmul, whatever the up
    stack's layout. A width with no such divisor (1856 = 14.5 lane tiles)
    is not the kernel's: on a v5e its 928-wide tiles compiled, but the
    first execution of a chunk program that carried them did not finish
    (`PERF.md` section 6). Interpret mode follows the same rule, so a test
    takes the route the chip takes."""
    per_col = (3 if gated else 2) * d * itemsize
    for tf in range(f - f % _LANES, 0, -_LANES):
        if f % tf == 0 and (tf * per_col <= _EXPERT_STEP_BYTES
                            or tf == _LANES):
            return tf
    return 0


def grouped_vmem_bytes(t, d, tf, bm, gated, itemsize):
    """VMEM a call of `grouped_matmul_fwd` takes: the call's tokens h and
    the sum y, (t, d) float32 each, whole; the block's rows, their sum and
    product (bm, d); the activation and its inputs (bm, tf); the weight
    and gate tiles double-buffered. h and y grow with t: a call whose
    footprint passes `_EXPERT_VMEM` is not the kernel's."""
    weights = (3 if gated else 2) * _tile_bytes((tf, d), itemsize)
    return (2 * (weights + _tile_bytes((bm, 1), 4))
            + 2 * _tile_bytes((t, d), 4)
            + 3 * _tile_bytes((bm, d), 4)
            + 4 * _tile_bytes((bm, tf), 4))


def _grouped_kernel(expert_ref, n_ref, tok_ref, h_hbm, g_ref, *refs, nf,
                    gated, transposed, act, dtype):
    """One block of the grouped matmul at one F tile: the block's rows,
    all of one expert's pairs, meet that expert's weights.

    Step (b, f) serves block b, whose expert (its row of the stacks) is
    the scalar-prefetched `expert_ref[b]` and whose rows are the tokens
    `tok_ref[b * bm:(b + 1) * bm]`. The first step brings the tokens' rows
    h into VMEM and zeroes the sum y; a block's first F tile gathers its
    rows from there, its last adds each row's gated result, rounded to the
    activations' type, into its token's row of y; the last step writes y
    out. A step past the last real block computes nothing, and its index
    maps repeat the last block's, so it fetches nothing either."""
    import jax
    import jax.numpy as jnp
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if gated:
        gate_w, up_w, down_w, y_hbm, h_v, y_v, x_v, acc_v, sem = refs
    else:
        up_w, down_w, y_hbm, h_v, y_v, x_v, acc_v, sem = refs
        gate_w = None
    b, f = pl.program_id(0), pl.program_id(1)
    bm = x_v.shape[0]

    @pl.when(jnp.logical_and(b == 0, f == 0))
    def _start():
        copy = pltpu.make_async_copy(h_hbm, h_v, sem)
        copy.start()
        y_v[...] = jnp.zeros_like(y_v)
        copy.wait()

    @pl.when(b < n_ref[0])
    def _block():
        @pl.when(f == 0)
        def _gather():
            def row(j, carry):
                x_v[pl.ds(j, 1), :] = h_v[pl.ds(tok_ref[b * bm + j], 1), :]
                return carry
            jax.lax.fori_loop(0, bm, row, 0)
            acc_v[...] = jnp.zeros_like(acc_v)

        x = x_v[...].astype(dtype)

        def up_of(w):
            # the matmul's output rounded to the activations' type, as the
            # XLA form of the body computes it
            dims = (((1,), (1,)), ((), ())) if transposed \
                else (((1,), (0,)), ((), ()))
            return jax.lax.dot_general(
                x, w, dims, preferred_element_type=jnp.float32).astype(
                    dtype).astype(jnp.float32)

        a = act(up_of(gate_w[0])) * up_of(up_w[0]) if gated \
            else act(up_of(up_w[0]))
        acc_v[...] += jnp.dot(a.astype(dtype), down_w[0],
                              preferred_element_type=jnp.float32)

        @pl.when(f == nf - 1)
        def _scatter():
            acc_v[...] = (acc_v[...] * g_ref[...]).astype(dtype).astype(
                jnp.float32)

            def row(j, carry):
                t = tok_ref[b * bm + j]
                y_v[pl.ds(t, 1), :] += acc_v[pl.ds(j, 1), :]
                return carry
            jax.lax.fori_loop(0, bm, row, 0)

    @pl.when(jnp.logical_and(b == pl.num_programs(0) - 1, f == nf - 1))
    def _finish():
        copy = pltpu.make_async_copy(y_v, y_hbm, sem)
        copy.start()
        copy.wait()


def grouped_matmul_fwd(h, tokens, gates, w_up, w_down, expert, n_blocks, *,
                       tf, gated, transposed, act, interpret=False):
    """The held experts' grouped matmul over blocks of rows of one expert.

    h (T, d) float32: the tokens (their values in the activations' type
    `w_down.dtype`); tokens (NB * bm,) int32 and gates (NB * bm, 1)
    float32: the token and the gate of each row of NB blocks of bm rows,
    each block one expert's pairs (`grouped_blocks` lays them out), gate 0
    on a padding row; w_up, w_down: the stacks of experts' weights,
    `w_down` (n, F, d), `w_up` (n, d, 2F) `gated` ([gate | up] columns) or
    (n, F, d) `transposed` (or (n, d, F)); `act` the float32 activation
    (of the gate where gated); expert (NB,) int32 each block's row of the
    stacks, n_blocks (1,) int32 the real blocks, the others repeating the
    last; `tf` the F tile (`expert_tile`: the caller checks there is one,
    and that `grouped_vmem_bytes` fits).

    -> y (T, d) float32: each token's sum over its rows of the gate times
    W_down act(...), each term rounded to the activations' type. The grid
    is (NB, F / tf); h and y stay in VMEM whole, the rows are gathered and
    summed there."""
    import jax
    import jax.numpy as jnp
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nb = expert.shape[0]
    t, d = h.shape
    bm = tokens.shape[0] // nb
    f = w_down.shape[1]
    dtype = w_down.dtype
    nf = f // tf

    def tile(b, fi, n_ref):
        # a step past the last block repeats the last block's last tile
        return jnp.where(b < n_ref[0], fi, nf - 1)

    def block(b, fi, expert_ref, n_ref, tok_ref):
        return (jnp.minimum(b, jnp.maximum(n_ref[0] - 1, 0)), 0)

    def up_tile(part):
        # the gate's tiles, then the up's: F / tf blocks further along
        def index(b, fi, expert_ref, n_ref, tok_ref):
            j = part * nf + tile(b, fi, n_ref)
            return (expert_ref[b], j, 0) if transposed \
                else (expert_ref[b], 0, j)
        return index

    def down_tile(b, fi, expert_ref, n_ref, tok_ref):
        return (expert_ref[b], tile(b, fi, n_ref), 0)

    parts = (0, 1) if gated else (0,)
    w_specs = [pl.BlockSpec((1, tf, d) if transposed else (1, d, tf),
                            up_tile(part)) for part in parts] \
        + [pl.BlockSpec((1, tf, d), down_tile)]
    w_args = [w_up] * len(parts)
    footprint = grouped_vmem_bytes(t, d, tf, bm, gated, dtype.itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nb, nf),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec((bm, 1), block)] + w_specs,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((t, d), jnp.float32),
                        pltpu.VMEM((t, d), jnp.float32),
                        pltpu.VMEM((bm, d), jnp.float32),
                        pltpu.VMEM((bm, d), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
    )
    kernel = functools.partial(_grouped_kernel, nf=nf, gated=gated,
                               transposed=transposed, act=act, dtype=dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(footprint + footprint // 4, _VMEM_SCOPED)),
        interpret=interpret,
        name="grouped_matmul_fwd",
    )(expert, n_blocks, tokens, h, gates, *w_args, w_down)


def grouped_blocks(starts, ends, bm, bound):
    """The layout of `grouped_matmul_fwd`: expert e's pairs, the sorted
    rows [starts[e], ends[e]), fill ceil(load / bm) blocks of bm rows from
    a block of their own. -> (expert (bound,) int32: each block's expert,
    the blocks past the real ones repeating the last real one's, so that a
    step there fetches nothing; n (1,) int32: the real blocks; shift
    (held,) int32: sorted row r of expert e is padded row r + shift[e]).
    Plain XLA ops outside the kernel."""
    import jax.numpy as jnp
    held = starts.shape[0]
    blocks = (ends - starts + bm - 1) // bm
    block_ends = jnp.cumsum(blocks)
    n = block_ends[-1]
    b = jnp.minimum(jnp.arange(bound, dtype=jnp.int32), jnp.maximum(n - 1, 0))
    expert = jnp.minimum(jnp.sum(block_ends[None, :] <= b[:, None], 1,
                                 dtype=jnp.int32), held - 1)
    return (expert, n.reshape(1).astype(jnp.int32),
            ((block_ends - blocks) * bm - starts).astype(jnp.int32))


def avg_pool2d_bwd(dy, h, w, ph, pw, interpret=False):
    """VMEM-tiled backward of the non-overlapping NHWC average pool:
    dX (N, h, w, C) from dY (N, h/ph, w/pw, C), or None (no tiling)."""
    import jax
    import jax.experimental.pallas as pl

    n, ho, wo, c = dy.shape
    blocks = _pool_blocks(n, h, w, c, ph, pw)
    if blocks is None:
        return None
    grid, bh = blocks
    return pl.pallas_call(
        functools.partial(_pool_bwd_kernel, ph=ph, pw=pw),
        grid=grid,
        in_specs=[pl.BlockSpec((1, bh, wo, c), lambda b, i: (b, i, 0, 0))],
        out_specs=pl.BlockSpec((1, bh * ph, w, c), lambda b, i: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h, w, c), dy.dtype),
        interpret=interpret,
        name="avg_pool2d_bwd",
    )(dy)
