"""Plain reference of the served decoder: the whole causal forward pass
over a prompt and the tokens served after it, in float32 with `highest`
matmul precision, no cache, no batching, no kernels. It follows the
program's block (pre-norm attention and MLP, RMSNorm with eps 1e-6, no
biases, learned positions, tanh gelu, the embedding as the output head),
which stands in for GPT-2's LayerNorm block: see the configuration's
`assumed`. Imports nothing of the program.

`precision="int8"` is the control: the same pass with every matmul's two
operands, and K and V as they would sit in a cache, rounded to 8-bit
codes (symmetric, one scale per row of the left operand, per column of
the right one, per head and position of K and V), the step below bf16
that a later PR could be tempted by."""
from __future__ import annotations

import math


def _fake_int8(a, axis):
    import jax.numpy as jnp
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(a / scale) * scale


def _rmsnorm(x, scale):
    import jax.numpy as jnp
    return x * scale / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                                + 1e-6)


def _gelu_tanh(x):
    import jax.numpy as jnp
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def make_forward(m, precision="float32"):
    """-> jitted f(params, tokens (T,) int32) -> logits (T, vocab) float32.
    One layer's weights are cast to float32 at a time (`lax.scan` over the
    stacked leaves), so the pass fits beside the bf16 tree."""
    import jax
    import jax.numpy as jnp
    if precision not in ("float32", "int8"):
        raise ValueError(f"unknown reference precision {precision!r}")
    H, D = m["heads"], m["head_dim"]
    low = precision == "int8"

    def mm(x, w):
        if low:
            x, w = _fake_int8(x, -1), _fake_int8(w, 0)
        return jnp.matmul(x, w, precision="highest")

    def layer(x, w):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        T = x.shape[0]
        h = _rmsnorm(x, w["ln1"])
        q = mm(h, w["wq"]).reshape(T, H, D)
        k = mm(h, w["wk"]).reshape(T, H, D)
        v = mm(h, w["wv"]).reshape(T, H, D)
        if low:
            q, k, v = (_fake_int8(a, -1) for a in (q, k, v))
        scores = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") \
            / math.sqrt(D)
        causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        att = jnp.einsum("hqk,khd->qhd", p, v, precision="highest")
        x = x + mm(att.reshape(T, H * D), w["wo"])
        h2 = _rmsnorm(x, w["ln2"])
        x = x + mm(_gelu_tanh(mm(h2, w["w1"])), w["w2"])
        return x, None

    @jax.jit
    def forward(params, tokens):
        T = tokens.shape[0]
        emb = params["emb"].astype(jnp.float32)
        x = emb[tokens] + params["pos"][:T].astype(jnp.float32)
        stacked = {k: params[k] for k in
                   ("ln1", "ln2", "wq", "wk", "wv", "wo", "w1", "w2")}
        x, _ = jax.lax.scan(layer, x, stacked)
        xf = _rmsnorm(x, params["lnf"].astype(jnp.float32))
        return mm(xf, emb.T)

    return forward


def served_logits(forward, params, prompt, served, pad_to):
    """Logits (len(served), vocab), on the device, at the positions that
    produced each served token: one pass over prompt + served[:-1], padded
    to `pad_to` positions with token 0 (causal, so never read)."""
    import numpy as np
    import jax.numpy as jnp
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    if seq.size > pad_to:
        raise ValueError(f"sequence of {seq.size} tokens over {pad_to}")
    padded = np.zeros((pad_to,), np.int32)
    padded[:seq.size] = seq
    logits = forward(params, jnp.asarray(padded))
    return logits[prompt.size - 1: prompt.size - 1 + served.size]


def gaps_below_best(logits, tokens):
    """For each row, how far the token's logit lies below the row's best
    (0 where the token is the reference's own choice). numpy float32."""
    import numpy as np
    import jax.numpy as jnp
    tokens = jnp.asarray(np.asarray(tokens, np.int32))
    picked = jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]
    return np.asarray(logits.max(-1) - picked, np.float32)
