"""Weights of the latent-attention, sparse-attention, sparse-expert decoder
(`configs/glm52_serve.json`) made from `--seed`, by the benchmark: on the
device, in the type they are served in, one jitted call a leaf and one
layer (or one expert) of a stacked leaf at a time, so that a draw's float32
stays a matrix's size. The plain reference is handed the same tree.

The leaves, their shapes and the kind of initial value each takes are the
program's one table (`models.sparse_moe_decoder.param_shapes`, drawn by its
`draw_leaf`); the scales are this configuration's (`model.init_std`,
`emb_std`, `q_b_std`, `kv_b_std`, `o_std`, `down_std`, `index_q_std`,
`index_k_std`, `router_std`,
`router_bias_std`), as its `assumed.weights` argues them;
`tests/test_sparse_moe_path.py` holds the drawn leaves to that statement."""
from __future__ import annotations

import math

from .weights import seed_key

SCALE_KEYS = {"normal": "init_std", "emb": "emb_std", "q_b": "q_b_std",
              "kv_b": "kv_b_std", "o": "o_std", "down": "down_std",
              "index_q": "index_q_std", "index_k": "index_k_std",
              "router": "router_std", "router_bias": "router_bias_std"}


def sparse_moe_config(m):
    """The program's static shape record from the `model` group."""
    from incubator_mxnet_tpu.models.sparse_moe_decoder import SparseMoEConfig
    return SparseMoEConfig(**{k: m[k] for k in SparseMoEConfig.FIELDS})


def glm_shapes(m):
    """name -> (shape, kind of initial value) of every leaf; `m` is the
    `model` group."""
    from incubator_mxnet_tpu.models import sparse_moe_decoder
    return sparse_moe_decoder.param_shapes(sparse_moe_config(m))


def glm_param_count(m):
    return sum(math.prod(shape) for shape, _ in glm_shapes(m).values())


def glm_params(m, seed):
    """The parameter tree on the default device, in `m['dtype']` (the
    router's bias in float32)."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.models.sparse_moe_decoder import (
        FLOAT32_LEAVES, draw_leaf)
    scales = {kind: m[key] for kind, key in SCALE_KEYS.items()}
    key = seed_key(seed)
    out = {}
    for i, (name, (shape, kind)) in enumerate(sorted(glm_shapes(m).items())):
        dtype = jnp.float32 if name in FLOAT32_LEAVES else jnp.dtype(
            m["dtype"])
        k = jax.random.fold_in(key, i)
        lead = shape[:-2]
        if lead and kind in scales:
            # one matrix at a time: the draw's float32 stays its size
            n = math.prod(lead)
            out[name] = jax.jit(
                lambda ks, s=shape, v=kind, dt=dtype: jax.lax.map(
                    lambda kk: draw_leaf(kk, s[-2:], v, scales).astype(dt),
                    ks).reshape(s))(jax.random.split(k, n))
        else:
            out[name] = jax.jit(
                lambda kk, s=shape, v=kind, dt=dtype: draw_leaf(
                    kk, s, v, scales).astype(dt))(k)
    return out
