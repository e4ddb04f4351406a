"""The sampler that every served model's programs share: per-lane
temperature / top-k / top-p with the parameters as data. It lives apart
from the engine (`serve.continuous`) so that a model's program factory
(`serve.CachedDecoder`, `models.hybrid_decoder.HybridDecoder`) needs to know
the sampler and not the engine."""
from __future__ import annotations

import numpy as _np


def seed_key(seed):
    """Host-side PRNG key bytes for a request seed — the same uint32
    pair `jax.random.PRNGKey(seed)` holds, built without a device
    round-trip so submit() stays cheap."""
    seed = int(seed) & 0xFFFFFFFFFFFFFFFF
    return _np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                     dtype=_np.uint32)


def sample_tokens(logits, temps, top_ks, top_ps, keys, positions):
    """Per-lane next-token choice with sampling params AS DATA: one
    compiled program serves any greedy/sampled mix. Every lane's greedy
    `argmax` is always evaluated; the temperature / top-k / top-p /
    categorical body (a sort of the whole `(lanes, vocab)` array) sits
    in the taken side of ONE on-device `lax.cond` on `any(temps > 0)`,
    so a wave whose lanes are all greedy (an idle lane's temperature is
    0) returns the argmax and never sorts, and a wave with one sampled
    lane runs the body for every lane, where a `temps > 0` select keeps
    its greedy lanes exactly argmax. The predicate is a device scalar
    read from the program's own input: no host read, no second program.
    `lax.cond` under `vmap` becomes a select that runs both sides: never
    vmap this function (flatten lanes instead, as the speculative path
    does). The draw key is `fold_in(lane_key, position)` (position = the
    query token's cache position), a pure function of request state, so
    any wave schedule draws the same tokens.

    The truncation and the draw happen in SORTED order, on the one array
    the sort returns, and the drawn rank maps back through the sort's
    own permutation. A threshold taken from the sorted values must never
    be compared with a second evaluation of `logits / temps`: the
    compiler may feed the sort from the logits matmul's float32
    accumulators and re-derive the other copy from their bfloat16
    rounding (seen on the TPU at 10 lanes), and a top logit that rounded
    down then fails its own threshold — the whole row is masked and
    token 0 comes out."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("sampler"):
        V = logits.shape[-1]
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def draw():
            scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
            ids = jax.lax.broadcasted_iota(jnp.int32, scaled.shape, 1)
            neg, order = jax.lax.sort_key_val(-scaled, ids, dimension=1)
            srt = -neg                               # descending, ties by id
            kth = jnp.take_along_axis(
                srt, jnp.clip(top_ks - 1, 0, V - 1)[:, None], axis=-1)
            keep_k = (top_ks[:, None] <= 0) | (srt >= kth)
            probs = jax.nn.softmax(srt, axis=-1)
            csum = jnp.cumsum(probs, axis=-1)
            # smallest prefix whose mass reaches top_p (the kept-set
            # INCLUDES the crossing token, hence the exclusive-cumsum
            # comparison)
            keepn = jnp.sum((csum - probs) < top_ps[:, None], axis=-1)
            pth = jnp.take_along_axis(
                srt, jnp.clip(keepn - 1, 0, V - 1)[:, None], axis=-1)
            masked = jnp.where(keep_k & (srt >= pth), srt, -1e30)
            kfold = jax.vmap(jax.random.fold_in)(keys, positions)
            rank = jax.vmap(
                lambda kk, lg: jax.random.categorical(kk, lg))(kfold, masked)
            sampled = jnp.take_along_axis(order, rank[:, None],
                                          axis=-1)[:, 0]
            return jnp.where(temps > 0, sampled.astype(jnp.int32), greedy)

        return jax.lax.cond(jnp.any(temps > 0), draw, lambda: greedy)


_SAMPLE_JIT = None


def sample_first_program():
    """The ONE process-wide jitted sampler behind `sample_first`
    (`jit_sample_tokens` in a device trace)."""
    global _SAMPLE_JIT
    if _SAMPLE_JIT is None:
        import jax
        _SAMPLE_JIT = jax.jit(sample_tokens)
    return _SAMPLE_JIT


def sample_first(logits, temps, top_ks, top_ps, keys, positions):
    """First-token draw from prefill logits through ONE process-wide
    jitted sampler. The sampling math compiles once per (lanes, vocab)
    shape for every model and engine in the process, instead of being
    re-traced into each model's prefill program (the decode program
    keeps its own in-scan copy, where it must live). Identical math
    either way, so engine == reference still holds bit-for-bit."""
    return sample_first_program()(logits, temps, top_ks, top_ps, keys,
                                  positions)
