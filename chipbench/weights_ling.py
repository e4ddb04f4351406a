"""Weights of the linear-attention, latent-attention, sparse-expert decoder
(`configs/ling3f_serve.json`) made from `--seed`, by the benchmark: on the
device, in the type they are served in, one jitted call a leaf and one
layer (or one expert) of a stacked leaf at a time, so that a draw's float32
stays a matrix's size. The plain reference is handed the same tree.

The leaves, their shapes and the kind of initial value each takes are the
program's one table (`models.delta_moe_decoder.param_shapes`, drawn by its
`draw_leaf`); the scales are this configuration's (`SCALE_KEYS`: standard
deviations, and for `kda_bf` the two ends of a uniform draw; the decay's
scale A_h keeps the program's own initial value, 0), as its
`assumed.weights` argues them; `tests/test_delta_moe_path.py` holds
the drawn leaves to that statement."""
from __future__ import annotations

import math

from .weights import seed_key

SCALE_KEYS = {"normal": "init_std", "emb": "emb_std", "o": "o_std",
              "down": "down_std", "q": "q_std", "kv_b": "kv_b_std",
              "kda_f": "kda_f_std", "kda_beta": "kda_beta_std",
              "conv": "conv_std", "router": "router_std",
              "router_bias": "router_bias_std", "kda_bf": "kda_bf_range"}


def delta_moe_config(m):
    """The program's static shape record from the `model` group."""
    from incubator_mxnet_tpu.models.delta_moe_decoder import DeltaMoEConfig
    return DeltaMoEConfig(**{k: m[k] for k in DeltaMoEConfig.FIELDS})


def ling_shapes(m):
    """name -> (shape, kind of initial value) of every leaf; `m` is the
    `model` group."""
    from incubator_mxnet_tpu.models import delta_moe_decoder
    return delta_moe_decoder.param_shapes(delta_moe_config(m))


def param_count(m):
    return sum(math.prod(shape) for shape, _ in ling_shapes(m).values())


def ling_params(m, seed):
    """The parameter tree on the default device, in `m['dtype']` (the
    router's bias and the decay's A and b_f in float32)."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.models.delta_moe_decoder import (
        FLOAT32_LEAVES, draw_leaf)
    from incubator_mxnet_tpu.models.delta_moe_decoder import INIT_SCALES
    scales = dict(INIT_SCALES,
                  **{kind: m[key] for kind, key in SCALE_KEYS.items()})
    key = seed_key(seed)
    out = {}
    for i, (name, (shape, kind)) in enumerate(sorted(ling_shapes(m).items())):
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
