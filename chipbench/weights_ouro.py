"""Weights of the looped decoder (`configs/ouro26b_serve.json`) made from
`--seed`, by the benchmark: on the device, in the type they are served in,
one jitted call a leaf and one layer of a stacked leaf at a time, so that a
draw's float32 stays a matrix's size (the whole 2.67 B-parameter tree in
float32 is 10.7 GB). The plain reference is handed the same tree.

The leaves, their shapes and the kind of initial value each takes are the
program's one table (`models.looped_decoder.param_shapes`, drawn by its
`draw_leaf`); the scales are this configuration's (`SCALE_KEYS`), as its
`assumed.weights` argues them; `tests/test_looped_path.py` holds the drawn
leaves to that statement."""
from __future__ import annotations

import math

from .weights import seed_key

SCALE_KEYS = {"normal": "init_std", "emb": "emb_std", "q": "q_std",
              "k": "k_std"}


def looped_config(m):
    """The program's static shape record from the `model` group."""
    from incubator_mxnet_tpu.models.looped_decoder import LoopedConfig
    return LoopedConfig(**{k: m[k] for k in LoopedConfig.FIELDS})


def ouro_shapes(m):
    """name -> (shape, kind of initial value) of every leaf; `m` is the
    `model` group."""
    from incubator_mxnet_tpu.models import looped_decoder
    return looped_decoder.param_shapes(looped_config(m))


def param_count(m):
    return sum(math.prod(shape) for shape, _ in ouro_shapes(m).values())


def ouro_params(m, seed):
    """The parameter tree on the default device, in `m['dtype']`."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.models.looped_decoder import draw_leaf
    scales = {kind: m[key] for kind, key in SCALE_KEYS.items()}
    dtype = jnp.dtype(m["dtype"])
    key = seed_key(seed)
    out = {}
    for i, (name, (shape, kind)) in enumerate(sorted(ouro_shapes(m).items())):
        k = jax.random.fold_in(key, i)
        if len(shape) == 3:
            # one layer at a time: the draw's float32 stays a matrix's size
            out[name] = jax.jit(lambda ks, s=shape, v=kind: jax.lax.map(
                lambda kk: draw_leaf(kk, s[1:], v, scales).astype(dtype),
                ks))(jax.random.split(k, shape[0]))
        else:
            out[name] = jax.jit(lambda kk, s=shape, v=kind: draw_leaf(
                kk, s, v, scales).astype(dtype))(k)
    return out
