"""Weights and keys made from `--seed`, by the benchmark and not by the
program: one jitted call on the device, in the type they are served or
trained in. The program receives them as arguments; the plain references
call the same functions again with the same seed."""
from __future__ import annotations


def seed_key(seed):
    """A PRNG key from any whole number (seeds above 2**31 too)."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def decoder_shapes(m):
    """name -> (shape, std) of the decoder's leaves; `m` is the `model`
    group of a serve configuration file. std 0.02 everywhere is GPT-2's
    published initialiser (`initializer_range`), norms are ones."""
    L, E, F, V, T = (m["layers"], m["embed"], m["mlp_hidden"], m["vocab"],
                     m["max_len"])
    std = m["init_std"]
    return {
        "emb": ((V, E), std), "pos": ((T, E), std),
        "wq": ((L, E, E), std), "wk": ((L, E, E), std),
        "wv": ((L, E, E), std), "wo": ((L, E, E), std),
        "w1": ((L, E, F), std), "w2": ((L, F, E), std),
        "ln1": ((L, E), None), "ln2": ((L, E), None), "lnf": ((E,), None),
    }


def decoder_params(m, seed):
    """The decoder's parameter tree on the default device, in `m['dtype']`."""
    import jax
    import jax.numpy as jnp
    shapes = decoder_shapes(m)
    dtype = jnp.dtype(m["dtype"])

    @jax.jit
    def make(key):
        out = {}
        for i, (name, (shape, std)) in enumerate(sorted(shapes.items())):
            if std is None:
                out[name] = jnp.ones(shape, dtype)
            else:
                out[name] = (jax.random.normal(jax.random.fold_in(key, i),
                                               shape, jnp.float32)
                             * std).astype(dtype)
        return out

    return make(seed_key(seed))


# ---------------------------------------------------------------------------
# ResNet v1 (bottleneck) leaves, named as gluon names them
# ---------------------------------------------------------------------------
def resnet_shapes(m):
    """name -> (shape, kind) for every trainable leaf and every running
    statistic of a bottleneck ResNet v1 in NHWC; kind is one of conv,
    gamma, beta, mean, var, dense_w, dense_b. `m` is the `model` group of
    a train configuration file."""
    out = {}

    def conv(name, k, cin, cout):
        out[name + ".weight"] = ((k, k, cin, cout), "conv")

    def bn(name, c):
        out[name + ".gamma"] = ((c,), "gamma")
        out[name + ".beta"] = ((c,), "beta")
        out[name + ".running_mean"] = ((c,), "mean")
        out[name + ".running_var"] = ((c,), "var")

    stem = m["stem_channels"]
    conv("features.0", 7, m["in_channels"], stem)
    bn("features.1", stem)
    cin = stem
    for s, (blocks, cout) in enumerate(zip(m["blocks"], m["channels"])):
        mid = cout // 4
        for b in range(blocks):
            base = f"features.{4 + s}.{b}"
            conv(base + ".body.0", 1, cin, mid)
            bn(base + ".body.1", mid)
            conv(base + ".body.3", 3, mid, mid)
            bn(base + ".body.4", mid)
            conv(base + ".body.6", 1, mid, cout)
            bn(base + ".body.7", cout)
            if b == 0:
                conv(base + ".downsample.0", 1, cin, cout)
                bn(base + ".downsample.1", cout)
            cin = cout
    out["output.weight"] = ((m["classes"], cin), "dense_w")
    out["output.bias"] = ((m["classes"],), "dense_b")
    return out


def resnet_params(m, seed):
    """All leaves in float32 on the default device: He-normal convolutions
    (std sqrt(2 / fan_in), He et al. 2015), gamma 1, beta 0, dense
    N(0, 0.01) with zero bias, running mean 0 and variance 1."""
    import jax
    import jax.numpy as jnp
    shapes = resnet_shapes(m)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, i)
            if kind == "conv":
                fan_in = shape[0] * shape[1] * shape[2]
                out[name] = jax.random.normal(k, shape, jnp.float32) * \
                    (2.0 / fan_in) ** 0.5
            elif kind == "dense_w":
                out[name] = jax.random.normal(k, shape, jnp.float32) * 0.01
            elif kind in ("gamma", "var"):
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                out[name] = jnp.zeros(shape, jnp.float32)
        return out

    return make(seed_key(seed))


def trainable(kind):
    """Whether a ResNet leaf of this kind is trained (running statistics
    are carried, not trained)."""
    return kind not in ("mean", "var")
