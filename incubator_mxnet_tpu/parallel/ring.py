"""Ring attention: sequence/context parallelism over the 'sp' mesh axis.

Green-field capability (SURVEY §5.7: the reference has NO sequence
parallelism of any kind). Each device holds a sequence shard of q/k/v; k/v
blocks rotate around the ring via `lax.ppermute` (riding ICI neighbor links)
while each device accumulates blockwise online-softmax attention against its
local q — full attention over sequences sp× longer than one device's memory,
with communication overlapped against the block compute by XLA.

Use inside shard_map with q,k,v sharded on axis 1 (time):

    f = parallel.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
        mesh, in_specs=P(None, "sp", None), out_specs=P(None, "sp", None))

Causal masking uses global positions: device r's q shard covers
[r*T_local, (r+1)*T_local); the k shard visiting at step s came from rank
(r - s) mod n.
"""
from __future__ import annotations

import math

__all__ = ["ring_attention", "ring_attention_nd"]

_NEG_INF = -1e30


def _block_attend(q, k, v, scale, mask):
    """One blockwise partial attention: returns (m, l, acc) contributions.

    q: (..., Tq, d), k/v: (..., Tk, d), mask broadcastable to (..., Tq, Tk).
    """
    import jax
    import jax.numpy as jnp
    s = jnp.einsum("...qd,...kd->...qk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    # guard fully-masked rows
    m = jnp.maximum(m, _NEG_INF)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("...qk,...kd->...qd", p, v.astype(jnp.float32))
    return m, l, acc


def ring_attention(q, k, v, axis_name="sp", causal=False, scale=None,
                   use_flash=False):
    """q,k,v: (B, T_local, H) or (B, H_heads, T_local, d) raw arrays, sharded
    on the time axis across `axis_name`. Returns local attention output of
    the same shape, equal to full-sequence attention.

    use_flash=True computes each hop's partial attention with the Pallas
    flash kernel (O(block) VMEM instead of the (T_local, T_local) score
    matrix) and merges hops through their log-sum-exp — the long-context
    configuration: sp x ring hops x flash blocks."""
    import jax
    import jax.numpy as jnp

    if use_flash:
        return _ring_attention_flash(q, k, v, axis_name, causal, scale)

    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    n = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    t_local = q.shape[-2]

    def local_mask(kv_src_rank):
        if not causal:
            return None
        q_pos = (rank * t_local
                 + jax.lax.broadcasted_iota(jnp.int32, (t_local, t_local), 0))
        k_pos = (kv_src_rank * t_local
                 + jax.lax.broadcasted_iota(jnp.int32, (t_local, t_local), 1))
        return q_pos >= k_pos

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, s):
        k_cur, v_cur, m_run, l_run, acc_run = carry
        src = (rank - s) % n
        m_blk, l_blk, acc_blk = _block_attend(q, k_cur, v_cur, scale,
                                              local_mask(src))
        m_new = jnp.maximum(m_run, m_blk)
        alpha = jnp.exp(m_run - m_new)
        beta = jnp.exp(m_blk - m_new)
        l_new = alpha * l_run + beta * l_blk
        acc_new = alpha * acc_run + beta * acc_blk
        # rotate k/v to the next rank (skip after the last step's compute
        # would be an optimization; keep simple & let XLA overlap)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, m_new, l_new, acc_new), None

    m0 = jnp.full(q.shape[:-1] + (1,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros(q.shape[:-1] + (1,), jnp.float32)
    acc0 = jnp.zeros(q.shape, jnp.float32)
    carry = (k, v, m0, l0, acc0)
    (k, v, m_run, l_run, acc_run), _ = jax.lax.scan(
        step, carry, jnp.arange(n))
    denom = jnp.where(l_run == 0.0, 1.0, l_run)
    return (acc_run / denom).astype(q.dtype)


def _ring_attention_flash(q, k, v, axis_name, causal, scale):
    """Ring attention with flash kernels per hop (Liu et al. ring
    attention over the Pallas kernels; green-field — the reference has
    neither).

    Forward: each ring hop runs the flash forward (o_hop, lse_hop) of the
    local q against the visiting k/v shard; hops merge exactly through
    their log-sum-exp. Backward is its own ring pass (custom_vjp): the
    flash backward kernels run per hop with the GLOBAL lse (so p is
    globally normalized), dq accumulates locally, and dk/dv ride the
    rotation with their shard — after n hops every gradient is home.
    Hop kinds under causal masking: src == rank -> causal kernel,
    src < rank -> unmasked kernel, src > rank -> zero contribution.
    Off-TPU the kernels run in interpret mode (tests)."""
    import jax
    import jax.numpy as jnp
    from ..ops.pallas_attention import (_auto_blocks, _flash_backward,
                                        _flash_forward_lse)

    orig_shape = q.shape
    if q.ndim == 4:                       # (B, heads, T, d) -> (bh, T, d)
        B, H, T, D = q.shape
        q = q.reshape(B * H, T, D)
        k = k.reshape(B * H, k.shape[2], D)
        v = v.reshape(B * H, v.shape[2], D)
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    interp = not _on_accel()
    n = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    bq, bk = _auto_blocks(q.shape[1], k.shape[1], d)

    def merge(out_run, lse_run, o_hop, lse_hop):
        m = jnp.maximum(lse_run, lse_hop)
        finite = m > _NEG_INF / 2
        w_run = jnp.where(finite, jnp.exp(lse_run - m), 0.0)
        w_hop = jnp.where(finite, jnp.exp(lse_hop - m), 0.0)
        w_sum = w_run + w_hop
        denom = jnp.where(w_sum == 0.0, 1.0, w_sum)
        out = (w_run * out_run + w_hop * o_hop) / denom
        lse = jnp.where(finite, m + jnp.log(denom), _NEG_INF)
        return out, lse

    def forward_core(q_, k_, v_):
        def fwd_hop(k_cur, v_cur, kind):
            """kind: 0 masked, 1 causal, 2 full. Returns (o, lse) f32."""
            if kind == 0:
                return (jnp.zeros(q_.shape, jnp.float32),
                        jnp.full(q_.shape[:-1] + (1,), _NEG_INF,
                                 jnp.float32))
            o, lse = _flash_forward_lse(q_, k_cur, v_cur, kind == 1, scale,
                                        bq, bk, interp)
            return o.astype(jnp.float32), lse

        def step(carry, s):
            k_cur, v_cur, out_run, lse_run = carry
            src = (rank - s) % n
            if causal:
                idx = jnp.where(src > rank, 0,
                                jnp.where(src == rank, 1, 2))
                o_hop, lse_hop = jax.lax.switch(
                    idx, [lambda _: fwd_hop(k_cur, v_cur, 0),
                          lambda _: fwd_hop(k_cur, v_cur, 1),
                          lambda _: fwd_hop(k_cur, v_cur, 2)], None)
            else:
                o_hop, lse_hop = fwd_hop(k_cur, v_cur, 2)
            out_new, lse_new = merge(out_run, lse_run, o_hop, lse_hop)
            k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
            return (k_nxt, v_nxt, out_new, lse_new), None

        out0 = jnp.zeros(q_.shape, jnp.float32)
        lse0 = jnp.full(q_.shape[:-1] + (1,), _NEG_INF, jnp.float32)
        (_, _, out, lse), _ = jax.lax.scan(
            step, (k_, v_, out0, lse0), jnp.arange(n))
        return out, lse

    @jax.custom_vjp
    def _ring(q_, k_, v_):
        out, _ = forward_core(q_, k_, v_)
        return out.astype(q_.dtype)

    def _ring_fwd(q_, k_, v_):
        out, lse = forward_core(q_, k_, v_)
        return out.astype(q_.dtype), (q_, k_, v_, out, lse)

    def _ring_bwd(res, ct):
        q_, k_, v_, out, lse = res
        ct32 = ct.astype(jnp.float32)
        delta = jnp.sum(ct32 * out, axis=-1, keepdims=True)

        def bwd_hop(k_cur, v_cur, kind):
            if kind == 0:
                return (jnp.zeros(q_.shape, jnp.float32),
                        jnp.zeros(k_cur.shape, jnp.float32),
                        jnp.zeros(v_cur.shape, jnp.float32))
            dq_h, dk_h, dv_h = _flash_backward(
                q_, k_cur, v_cur, ct32, lse, delta, kind == 1, scale,
                bq, bk, interp)
            return (dq_h.astype(jnp.float32), dk_h.astype(jnp.float32),
                    dv_h.astype(jnp.float32))

        def step(carry, s):
            k_cur, v_cur, dk_cur, dv_cur, dq_acc = carry
            src = (rank - s) % n
            if causal:
                idx = jnp.where(src > rank, 0,
                                jnp.where(src == rank, 1, 2))
                dq_h, dk_h, dv_h = jax.lax.switch(
                    idx, [lambda _: bwd_hop(k_cur, v_cur, 0),
                          lambda _: bwd_hop(k_cur, v_cur, 1),
                          lambda _: bwd_hop(k_cur, v_cur, 2)], None)
            else:
                dq_h, dk_h, dv_h = bwd_hop(k_cur, v_cur, 2)
            dq_acc = dq_acc + dq_h
            dk_cur = dk_cur + dk_h
            dv_cur = dv_cur + dv_h
            # k/v gradients travel WITH their shard around the ring: after
            # n rotations both the shard and its accumulated grads are home
            k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
            dk_nxt = jax.lax.ppermute(dk_cur, axis_name, perm)
            dv_nxt = jax.lax.ppermute(dv_cur, axis_name, perm)
            return (k_nxt, v_nxt, dk_nxt, dv_nxt, dq_acc), None

        zk = jnp.zeros(k_.shape, jnp.float32)
        zv = jnp.zeros(v_.shape, jnp.float32)
        zq = jnp.zeros(q_.shape, jnp.float32)
        (k_fin, v_fin, dk, dv, dq), _ = jax.lax.scan(
            step, (k_, v_, zk, zv, zq), jnp.arange(n))
        return (dq.astype(q_.dtype), dk.astype(k_.dtype),
                dv.astype(v_.dtype))

    _ring.defvjp(_ring_fwd, _ring_bwd)
    return _ring(q, k, v).reshape(orig_shape)


def _on_accel():
    import jax
    return any(dev.platform != "cpu" for dev in jax.devices())


def ring_attention_nd(q, k, v, axis_name="sp", causal=False, scale=None,
                      use_flash=False):
    """Convenience for (B, n_heads, T, d) inputs (same math)."""
    return ring_attention(q, k, v, axis_name=axis_name, causal=causal,
                          scale=scale, use_flash=use_flash)
