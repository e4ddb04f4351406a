"""Weights of the SambaY decoder (`configs/phi4mf_serve.json`) made from
`--seed`, by the benchmark: on the device, in the type they are served in,
one jitted call a leaf and one layer of a stacked leaf at a time (the
float32 draws of the whole 3.85 B-parameter tree at once would not fit).
The plain reference is handed the same tree.

The leaves, their shapes and the kind of initial value each takes are the
program's one table (`models.hybrid_decoder.param_shapes`, drawn by its
`draw_leaf`): the model declares what it is served with, and there is no
second copy here to drift from it. The scales are this configuration's
(`model.init_std`, `lambda_std`, `x_proj_std`), as its `assumed` states
them; `tests/test_hybrid_path.py` holds the drawn leaves to that
statement, so a change of the program's draw cannot move the benchmark's
weights unseen."""
from __future__ import annotations

import math

from .weights import seed_key


def hybrid_config(m):
    """The program's static shape record from the `model` group."""
    from incubator_mxnet_tpu.models.hybrid_decoder import HybridConfig
    return HybridConfig(**{k: m[k] for k in HybridConfig.FIELDS})


def sambay_shapes(m):
    """name -> (shape, kind of initial value) of every leaf, stacked by
    layer kind on a leading axis; `m` is the `model` group."""
    from incubator_mxnet_tpu.models import hybrid_decoder
    return hybrid_decoder.param_shapes(hybrid_config(m))


def sambay_param_count(m):
    return sum(math.prod(shape) for shape, _ in sambay_shapes(m).values())


def sambay_params(m, seed):
    """The parameter tree on the default device, in `m['dtype']`."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.models.hybrid_decoder import draw_leaf
    dtype = jnp.dtype(m["dtype"])
    scales = {"normal": m["init_std"], "lambda": m["lambda_std"],
              "x_proj": m["x_proj_std"]}
    key = seed_key(seed)

    def draw(k, shape, kind):
        return draw_leaf(k, shape, kind, scales).astype(dtype)

    out = {}
    for i, (name, (shape, kind)) in enumerate(sorted(
            sambay_shapes(m).items())):
        k = jax.random.fold_in(key, i)
        if len(shape) == 3 and kind in scales:
            # one layer at a time: the draw's float32 stays a layer's size
            out[name] = jax.jit(lambda ks, s=shape, v=kind: jax.lax.map(
                lambda kk: draw(kk, s[1:], v), ks))(
                    jax.random.split(k, shape[0]))
        else:
            out[name] = jax.jit(draw, static_argnums=(1, 2))(k, shape, kind)
    return out
