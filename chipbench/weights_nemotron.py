"""Weights of the state-space, sparse-expert decoder
(`configs/nemotron3nano_serve.json`) made from `--seed`, by the benchmark:
on the device, in the type they are served in, one jitted call a leaf and
one layer (or one expert) of a stacked leaf at a time, so that a draw's
float32 stays a matrix's size. The plain reference is handed the same tree.

The leaves, their shapes and the kind of initial value each takes are the
program's one table (`models.mamba_moe_decoder.param_shapes`, drawn by its
`draw_mamba_leaf`); the scales are this configuration's (`SCALE_KEYS`:
standard deviations, and for `conv`, `A_log` and `dt_bias` the ranges of
Mamba-2's own initial values), as its `assumed.weights` argues them;
`chipbench/tests/test_mamba_moe_path.py` holds the drawn leaves to that
statement."""
from __future__ import annotations

import math

from .weights import seed_key

SCALE_KEYS = {"normal": "init_std", "emb": "emb_std", "o": "o_std",
              "down": "down_std", "router": "router_std",
              "router_bias": "router_bias_std", "conv": "conv_range",
              "A_log": "A_log_range", "dt_bias": "dt_range"}


def mamba_moe_config(m):
    """The program's static shape record from the `model` group."""
    from incubator_mxnet_tpu.models.mamba_moe_decoder import MambaMoEConfig
    return MambaMoEConfig(**{k: m[k] for k in MambaMoEConfig.FIELDS})


def nemotron_shapes(m):
    """name -> (shape, kind of initial value) of every leaf; `m` is the
    `model` group."""
    from incubator_mxnet_tpu.models import mamba_moe_decoder
    return mamba_moe_decoder.param_shapes(mamba_moe_config(m))


def param_count(m):
    return sum(math.prod(shape) for shape, _ in nemotron_shapes(m).values())


def nemotron_params(m, seed):
    """The parameter tree on the default device, in `m['dtype']` (the
    router's bias, dt_bias, A_log and D in float32)."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.models.mamba_moe_decoder import (
        FLOAT32_LEAVES, INIT_SCALES, draw_mamba_leaf)
    scales = dict(INIT_SCALES,
                  **{kind: tuple(m[key]) if isinstance(m[key], list)
                     else m[key] for kind, key in SCALE_KEYS.items()})
    key = seed_key(seed)
    out = {}
    for i, (name, (shape, kind)) in enumerate(
            sorted(nemotron_shapes(m).items())):
        dtype = jnp.float32 if name in FLOAT32_LEAVES else jnp.dtype(
            m["dtype"])
        k = jax.random.fold_in(key, i)
        lead = shape[:-2]
        if lead and kind in scales:
            # one matrix at a time: the draw's float32 stays its size
            n = math.prod(lead)
            out[name] = jax.jit(
                lambda ks, s=shape, v=kind, dt=dtype: jax.lax.map(
                    lambda kk: draw_mamba_leaf(kk, s[-2:], v,
                                               scales).astype(dt),
                    ks).reshape(s))(jax.random.split(k, n))
        else:
            out[name] = jax.jit(
                lambda kk, s=shape, v=kind, dt=dtype: draw_mamba_leaf(
                    kk, s, v, scales).astype(dt))(k)
    return out
