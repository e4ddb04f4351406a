"""Flash attention as a Pallas TPU kernel.

Reference contrast: MXNet's attention kernels are fused strided-batch-GEMMs
(`_contrib_interleaved_matmul_selfatt_*`, src/operator/contrib/
transformer.cc:676-869) that materialize the full (T, T) score matrix. This
kernel is the TPU-first replacement: blockwise online-softmax attention
(flash attention) that keeps O(block_q x block_k) tiles in VMEM, never
materializing the score matrix — the HBM-bandwidth win that matters at long
sequence length (SURVEY §5.7: the capability gap this framework fills).

Layout: q,k,v are (batch*heads, T, head_dim). Grid = (bh, nq, nk) with the
k loop innermost; accumulators (m, l, acc) persist in VMEM scratch across
the nk steps (TPU grids iterate sequentially).

Falls back to the jnp composition off-TPU (tests run interpret=True or the
fallback — same math, tolerances in tests/test_attention.py).
"""
from __future__ import annotations

import functools
import math

import numpy as _np

__all__ = ["flash_attention"]

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale,
            causal, block_q, block_k, nk, causal_offset=0):
    import jax
    import jax.numpy as jnp
    import jax.experimental.pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (block_q, d)
        k = k_ref[0].astype(jnp.float32)          # (block_k, d)
        v = v_ref[0].astype(jnp.float32)          # (block_k, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            # end-aligned (≙ tril with k = tk - tq): query i attends keys
            # up to i + (tk - tq)
            q_pos = qi * block_q + causal_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev = m_ref[:]                          # (block_q, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                     # (block_q, block_k)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = alpha * acc_ref[:] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    if causal:
        # skip fully-masked k blocks (block entirely above the diagonal)
        @pl.when(ki * block_k <= qi * block_q + block_q - 1 + causal_offset)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        import jax.numpy as jnp
        denom = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)


def _kernel_with_lse(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                     acc_ref, *, scale, causal, block_q, block_k, nk,
                     causal_offset=0):
    """Forward kernel that also emits the log-sum-exp per query row — the
    residual the flash backward kernels consume."""
    import jax.numpy as jnp
    import jax.experimental.pallas as pl

    _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, scale=scale,
            causal=causal, block_q=block_q, block_k=block_k, nk=nk,
            causal_offset=causal_offset)

    ki = pl.program_id(2)

    @pl.when(ki == nk - 1)
    def _emit_lse():
        lse = jnp.where(l_ref[:] > 0.0,
                        m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-37)),
                        _NEG_INF)
        lse_ref[0] = lse.astype(lse_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, scale, causal, block_q, block_k, nk,
                   causal_offset=0):
    """dq = sum_k  ds @ k * scale,  ds = p * (dO v^T - delta),
    p = exp(s - lse). Grid (bh, nq, nk), k innermost; dq accumulates in
    VMEM scratch (standard flash attention backward, Dao et al. 2022)."""
    import jax
    import jax.numpy as jnp
    import jax.experimental.pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + causal_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        # all-masked query rows carry the _NEG_INF lse sentinel: s - lse
        # would be 0 there (both -1e30), turning exp into 1 — zero p
        # explicitly so fully-masked rows contribute no gradient
        lse_row = lse_ref[0]
        p = jnp.where(lse_row > _NEG_INF / 2,
                      jnp.exp(s - lse_row), 0.0)        # (bq, bk)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        acc_ref[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        @pl.when(ki * block_k <= qi * block_q + block_q - 1 + causal_offset)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    block_q, block_k, nq, causal_offset=0):
    """dv = sum_q p^T @ dO;  dk = sum_q ds^T @ q * scale.
    Grid (bh, nk, nq), q innermost; dk/dv accumulate in VMEM scratch."""
    import jax
    import jax.numpy as jnp
    import jax.experimental.pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + causal_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        # all-masked query rows carry the _NEG_INF lse sentinel: s - lse
        # would be 0 there (both -1e30), turning exp into 1 — zero p
        # explicitly so fully-masked rows contribute no gradient
        lse_row = lse_ref[0]
        p = jnp.where(lse_row > _NEG_INF / 2,
                      jnp.exp(s - lse_row), 0.0)        # (bq, bk)
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bk, d)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bk, d)

    if causal:
        @pl.when(ki * block_k <= qi * block_q + block_q - 1 + causal_offset)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _blockwise(q, k, v, scale, causal, block_k=512):
    """Differentiable blockwise attention: lax.scan over k blocks with
    online-softmax merging. Same math as the Pallas kernel, O(T·block_k)
    memory in BOTH directions (jax AD through scan recomputes per block) —
    this is the training path backing flash_attention's custom_vjp."""
    import jax
    import jax.numpy as jnp
    bh, tq, d = q.shape
    tk = k.shape[1]
    block_k = min(block_k, tk)
    if tk % block_k:
        return _reference(q, k, v, scale, causal)
    nk = tk // block_k
    kb = k.reshape(bh, nk, block_k, d).transpose(1, 0, 2, 3)
    vb = v.reshape(bh, nk, block_k, d).transpose(1, 0, 2, 3)
    q32 = q.astype(jnp.float32)
    q_pos = jnp.arange(tq)[:, None] + (tk - tq)  # end-aligned causal

    def step(carry, blk):
        m_run, l_run, acc = carry
        k_cur, v_cur, j = blk
        s = jnp.einsum("bqd,bkd->bqk", q32, k_cur.astype(jnp.float32)) * scale
        if causal:
            k_pos = j * block_k + jnp.arange(block_k)[None, :]
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_run, m_blk)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_run - m_new)
        l_new = alpha * l_run + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = alpha * acc + jnp.einsum(
            "bqk,bkd->bqd", p, v_cur.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((bh, tq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bh, tq, 1), jnp.float32)
    acc0 = jnp.zeros((bh, tq, d), jnp.float32)
    # remat: without it, AD through the scan saves the (bh, tq, block_k)
    # probabilities of every step — O(tq*tk), defeating blockwise memory
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(step), (m0, l0, acc0), (kb, vb, jnp.arange(nk)))
    denom = jnp.where(l == 0.0, 1.0, l)
    return (acc / denom).astype(q.dtype)


def _reference(q, k, v, scale, causal):
    import jax
    import jax.numpy as jnp
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _flash_forward_kernel(q, k, v, causal, scale, block_q, block_k,
                          interpret):
    import jax
    import jax.numpy as jnp
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, tq, d = q.shape
    tk = k.shape[1]
    nq = tq // block_q
    nk = tk // block_k
    grid = (bh, nq, nk)
    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, nk=nk,
                               causal_offset=tk - tq)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # m (running max)
            pltpu.VMEM((block_q, 1), jnp.float32),   # l (running denom)
            pltpu.VMEM((block_q, d), jnp.float32),   # acc
        ],
        interpret=interpret,
        name="flash_forward_kernel",
    )(q, k, v)


def _flash_forward_lse(q, k, v, causal, scale, block_q, block_k, interpret):
    """Forward returning (o, lse) — the training-path entry."""
    import jax
    import jax.numpy as jnp
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, tq, d = q.shape
    tk = k.shape[1]
    nq = tq // block_q
    nk = tk // block_k
    kernel = functools.partial(_kernel_with_lse, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, nk=nk,
                               causal_offset=tk - tq)
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_forward_lse",
    )(q, k, v)


def _flash_backward(q, k, v, do, lse, delta, causal, scale, block_q,
                    block_k, interpret):
    """Pallas dq + dkv kernels (flash attention backward as two sweeps)."""
    import jax
    import jax.numpy as jnp
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, tq, d = q.shape
    tk = k.shape[1]
    nq = tq // block_q
    nk = tk // block_k
    off = tk - tq

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk,
                          causal_offset=off),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_backward_dq",
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nq=nq,
                          causal_offset=off),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, tk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_backward_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _auto_blocks(tq, tk, d, vmem_budget=8 * 1024 * 1024):
    """Pick (block_q, block_k): the largest power-of-two tiles that DIVIDE
    the sequence lengths (halving preserves divisibility, so the kernel —
    not the dense fallback — runs for any even-pow2-factor length) and
    whose working set — q/k/v/do tiles, the (bq, bk) score tile, and f32
    accumulators — fits the VMEM budget, with `d` counted as the
    128-lane multiple a (block, d) tile is padded to (head_dim 64 takes
    the room of 128). Bigger tiles amortize HBM traffic; the cap keeps
    double-buffering viable."""
    from .pallas_kernels import _LANES, _round_up
    d = _round_up(d, _LANES)

    def fits(bq, bk):
        tiles = (bq * d * 4 * 2          # q tile + do tile
                 + bk * d * 4 * 4        # k, v tiles + dk/dv accums
                 + bq * bk * 4 * 2       # score + ds tiles
                 + bq * d * 4)           # acc
        return tiles * 2 <= vmem_budget  # x2: double buffering headroom

    def pow2_divisor(n, cap=1024):
        return min(n & -n, cap)          # largest 2^k dividing n

    bq = pow2_divisor(tq)
    while bq > 8:
        bk = pow2_divisor(tk)
        while bk > 8 and not fits(bq, bk):
            bk //= 2
        if fits(bq, bk):
            return bq, bk
        bq //= 2
    bk = pow2_divisor(tk)
    while bk > 8 and not fits(bq, bk):
        bk //= 2
    return bq, bk


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=False):
    """Blockwise attention. q: (bh, Tq, d), k/v: (bh, Tk, d) raw jax arrays.

    Forward AND backward are Pallas kernels on TPU (or interpret=True
    anywhere): forward emits (o, lse); backward runs the two-sweep flash
    gradient (dq sweep over k blocks, dk/dv sweep over q blocks) — no
    (T, T) score matrix in either direction. Block sizes default to the
    VMEM-budget autotune (_auto_blocks); pass block_q/block_k to pin.
    Falls back to the differentiable blockwise scan off-TPU and to the
    einsum composition on ragged shapes; either way the choice is
    counted in `fused_stats()` (`pallas_calls` / `fallback_calls`, per
    trace) like the rest of the kernel tier."""
    import jax
    import jax.numpy as jnp

    bh, tq, d = q.shape
    tk = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    from ..device import tpu_platform_available
    from .fused import FUSED_STATS, _fell_back, _resolve_interpret
    interpret = _resolve_interpret(interpret)
    if not (tpu_platform_available() or interpret):
        _fell_back("flash_attention", "no TPU: blockwise scan")
        return _blockwise(q, k, v, scale, causal,
                          block_k if block_k else 512)

    auto_q, auto_k = _auto_blocks(tq, tk, d)
    block_q = min(block_q or auto_q, tq)
    block_k = min(block_k or auto_k, tk)
    if tq % block_q or tk % block_k:
        # ragged tails: fall back (padding support comes with masked loads)
        _fell_back("flash_attention",
                   f"ragged tq={tq} tk={tk} for blocks "
                   f"({block_q}, {block_k}): dense einsum")
        return _reference(q, k, v, scale, causal)
    FUSED_STATS["pallas_calls"] += 1

    @jax.custom_vjp
    def _fa(q, k, v):
        # inference/primal path: the lse-free kernel (no wasted residual
        # output); the vjp fwd below runs the lse-emitting twin
        return _flash_forward_kernel(q, k, v, causal, scale, block_q,
                                     block_k, interpret)

    def _fa_fwd(q, k, v):
        o, lse = _flash_forward_lse(q, k, v, causal, scale, block_q,
                                    block_k, interpret)
        return o, (q, k, v, o, lse)

    def _fa_bwd(res, ct):
        q, k, v, o, lse = res
        # delta = rowsum(dO * O) per query (the softmax-normalizer term)
        delta = jnp.sum(ct.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)
        return _flash_backward(q, k, v, ct, lse, delta, causal, scale,
                               block_q, block_k, interpret)

    _fa.defvjp(_fa_fwd, _fa_bwd)
    return _fa(q, k, v)
