"""Expert parallelism: mixture-of-experts dispatch over the 'ep' mesh axis.

The last parallelism mode ABSENT from the reference (SURVEY §2.3). Each ep
rank hosts exactly ONE expert FFN (E == ep axis size); tokens route by a
learned gate with
fixed capacity, hop to their expert via `lax.all_to_all` (riding ICI), are
transformed, and hop back, scaled by the gate probability — the standard
switch-transformer dispatch, expressed with XLA collectives.

Use inside shard_map: tokens sharded over 'ep' (each rank holds T_local
tokens), expert weights sharded one-per-rank with P('ep', ...).
"""
from __future__ import annotations

__all__ = ["moe_dispatch", "moe_dispatch_expert_choice"]


def moe_dispatch(x, gate_logits, expert_fn, axis_name="ep", capacity=None,
                 stats_axes=None, top_k=1):
    """Top-k capacity-based MoE (top_k=1 ≙ Switch routing; top_k=2 ≙
    GShard/Mixtral-style routing with renormalized gates).

    x            (T_local, D)   this rank's tokens
    gate_logits  (T_local, E)   router scores (E = axis size)
    expert_fn    (tokens (R*C, D)) -> (R*C, D): THIS rank's expert applied to
                 the tokens it received (R = number of ranks)
    capacity     per-(source rank, expert) token budget C; tokens over
                 capacity pass through unchanged (standard overflow rule)
    stats_axes   mesh axes to average the load fractions over for the aux
                 loss (default: just `axis_name`). When tokens are also
                 sharded along other axes (e.g. 'sp'), include them so the
                 aux is the Switch eq.4 objective over the GLOBAL batch —
                 the fractions are linear in tokens, the aux product is not.

    Returns (T_local, D): gate-weighted expert outputs (+ passthrough for
    dropped tokens) and the load-balancing auxiliary loss (scalar).
    """
    import jax
    import jax.numpy as jnp

    T, D = x.shape
    E = jax.lax.axis_size(axis_name)
    assert gate_logits.shape[-1] == E, "one expert per ep rank"
    if capacity is None:
        # capacity scales with top_k (GShard): K*T assignments share the
        # per-expert slots, so a K-independent default would drop roughly
        # half the second choices even on perfectly balanced traffic
        capacity = max(int(top_k) * 2 * T // E, 1)
    C = capacity

    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    K = int(top_k)
    topk_probs, topk_idx = jax.lax.top_k(probs, K)           # (T, K)
    if K > 1:
        topk_probs = topk_probs / jnp.sum(topk_probs, axis=-1,
                                          keepdims=True)     # renormalize
    # flatten the (token, choice) pairs CHOICE-MAJOR so every token's first
    # choice outranks all second choices for capacity (GShard ordering)
    flat_idx = topk_idx.T.reshape(-1)                        # (K*T,)
    flat_gate = topk_probs.T.reshape(-1)                     # (K*T,)
    onehot_tok = jax.nn.one_hot(topk_idx[:, 0], E, dtype=jnp.int32)  # top-1
    onehot_flat = jax.nn.one_hot(flat_idx, E, dtype=jnp.int32)

    pos_in_expert = (jnp.cumsum(onehot_flat, axis=0) - 1)    # (K*T, E)
    slot = jnp.take_along_axis(pos_in_expert, flat_idx[:, None],
                               axis=1)[:, 0]                 # (K*T,)
    keep = slot < C

    # scatter tokens into the (E, C, D) send buffer. Additive scatter:
    # dropped tokens contribute zeros, so their clipped-slot collisions with
    # kept tokens are harmless (a .set would clobber nondeterministically)
    x_flat = jnp.tile(x, (K, 1))                             # (K*T, D)
    send = jnp.zeros((E, C, D), x.dtype)
    send = send.at[flat_idx, jnp.clip(slot, 0, C - 1)].add(
        jnp.where(keep[:, None], x_flat, 0.0))

    # all_to_all: dim0 switches from "destination expert" to "source rank"
    recv = jax.lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)                   # (E, C, D)
    out = expert_fn(recv.reshape(E * C, D)).reshape(E, C, D)
    back = jax.lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)                   # (E, C, D)

    # combine the kept choices, gate-weighted; tokens with NO surviving
    # choice pass through unchanged (standard overflow rule)
    gathered = back[flat_idx, jnp.clip(slot, 0, C - 1)]      # (K*T, D)
    contrib = jnp.where(keep[:, None],
                        flat_gate[:, None].astype(x.dtype) * gathered,
                        0.0)
    y_sum = contrib.reshape(K, T, D).sum(axis=0)             # (T, D)
    any_kept = keep.reshape(K, T).any(axis=0)
    y = jnp.where(any_kept[:, None], y_sum, x)

    # load-balancing aux loss (Switch eq. 4): E * sum_e f_e * P_e over the
    # GLOBAL batch — pmean the per-rank fractions (linear in tokens) over
    # every axis the tokens are sharded on, THEN take the product
    axes = stats_axes if stats_axes is not None else (axis_name,)
    frac_tokens = jax.lax.pmean(
        jnp.mean(onehot_tok.astype(jnp.float32), axis=0), axes)
    frac_probs = jax.lax.pmean(jnp.mean(probs, axis=0), axes)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return y, aux


def moe_dispatch_expert_choice(x, gate_logits, expert_fn, axis_name="ep",
                               capacity=None):
    """Expert-choice routing (Zhou et al. 2022): each EXPERT picks its
    top-C tokens, so load balance is perfect by construction and no aux
    loss is needed. Tokens chosen by no expert pass through unchanged.

    Same sharding contract as `moe_dispatch`; returns (y, aux) with aux=0
    for API symmetry.
    """
    import jax
    import jax.numpy as jnp

    T, D = x.shape
    E = jax.lax.axis_size(axis_name)
    assert gate_logits.shape[-1] == E
    C = capacity if capacity is not None else max(2 * T // E, 1)

    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    # each expert picks its top-C tokens by probability
    scores = probs.T                                         # (E, T)
    top_scores, top_tok = jax.lax.top_k(scores, C)           # (E, C)
    send = x[top_tok]                                        # (E, C, D)

    recv = jax.lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)
    out = expert_fn(recv.reshape(E * C, D)).reshape(E, C, D)
    back = jax.lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)                   # (E, C, D)

    # scatter-add each expert's contribution back to its chosen tokens
    y = jnp.zeros_like(x)
    y = y.at[top_tok.reshape(-1)].add(
        (top_scores.reshape(-1, 1).astype(x.dtype)
         * back.reshape(E * C, D)))
    chosen = jnp.zeros((T,), jnp.int32).at[top_tok.reshape(-1)].add(1)
    return jnp.where(chosen[:, None] > 0, y, x), jnp.zeros((), jnp.float32)
