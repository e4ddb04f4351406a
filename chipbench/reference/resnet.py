"""Plain reference of the trained ResNet v1 (bottleneck, He et al. 2015,
Table 1): forward, softmax cross-entropy summed over the batch, gradients
by `jax.grad`, SGD with momentum; float32 with `highest` precision, NHWC,
no kernels. The stride of a down-sampling block sits on its first 1x1
convolution (the original v1, as gluon's model zoo has it). Batch
normalisation uses the batch's own mean and biased variance, eps 1e-5.
Each block is rematerialised, so that a batch of 128 fits one chip in
float32. Imports nothing of the program.

`precision="fp8"` is the control: every tensor that bf16 AMP keeps in
bf16 (a convolution's or the dense layer's operands and result, a block's
output) rounded to float8_e4m3fn instead, forward and backward: the step
below the bf16 that the configuration states. `precision="bfloat16"`
rounds the same tensors to bf16: not a control, but what the calibration
reads to tell bf16's own share of a gap."""
from __future__ import annotations

EPS = 1e-5


def _rounder(precision):
    """-> r(a): `a` rounded to the lower type on the way forward, and its
    cotangent rounded the same way on the way back, as a tensor kept in
    that type would be. fp8 (e4m3) takes a per-tensor scale into its range,
    as fp8 recipes do."""
    import jax
    import jax.numpy as jnp

    def q(a):
        if precision == "fp8":
            scale = jnp.max(jnp.abs(a)) / 448.0
            scale = jnp.where(scale == 0, 1.0, scale)
            return (a / scale).astype(jnp.float8_e4m3fn).astype(
                jnp.float32) * scale
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    @jax.custom_vjp
    def r(a):
        return q(a)

    r.defvjp(lambda a: (q(a), None), lambda _, g: (q(g),))
    return r


def make_loss(m, precision="float32", one_pass_variance=False):
    """-> loss(params, x (B,H,W,C) f32, y (B,) int32) -> summed loss.
    `one_pass_variance` computes E[x^2] - E[x]^2 as the program does (the
    same quantity, worse conditioned in float32); only the calibration
    script asks for it, to tell that rounding from a fault."""
    import jax
    import jax.numpy as jnp
    if precision not in ("float32", "bfloat16", "fp8"):
        raise ValueError(f"unknown reference precision {precision!r}")
    # where bf16 AMP keeps a tensor in bf16 (the operands and the result of
    # every convolution and of the dense layer, a block's output), the lower
    # precisions round it; float32 rounds nothing
    r = _rounder(precision) if precision != "float32" else (lambda a: a)

    def conv(x, w, stride, pad):
        return r(jax.lax.conv_general_dilated(
            r(x), r(w), (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision="highest"))

    def bn(x, p, name):
        mean = jnp.mean(x, (0, 1, 2))
        if one_pass_variance:
            var = jnp.mean(jnp.square(x), (0, 1, 2)) - jnp.square(mean)
        else:
            var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
        return (x - mean) * jax.lax.rsqrt(var + EPS) * p[name + ".gamma"] \
            + p[name + ".beta"]

    def block(p, x, base, stride, down):
        res = x
        if down:
            res = bn(conv(x, p[base + ".downsample.0.weight"], stride, 0),
                     p, base + ".downsample.1")
        h = jax.nn.relu(bn(conv(x, p[base + ".body.0.weight"], stride, 0),
                           p, base + ".body.1"))
        h = jax.nn.relu(bn(conv(h, p[base + ".body.3.weight"], 1, 1),
                           p, base + ".body.4"))
        h = bn(conv(h, p[base + ".body.6.weight"], 1, 0), p, base + ".body.7")
        return r(jax.nn.relu(h + res))

    def loss(p, x, y):
        h = jax.nn.relu(bn(conv(x, p["features.0.weight"], 2, 3), p,
                           "features.1"))
        h = jax.lax.reduce_window(
            h, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
            [(0, 0), (1, 1), (1, 1), (0, 0)])
        for s, blocks in enumerate(m["blocks"]):
            for b in range(blocks):
                base = f"features.{4 + s}.{b}"
                stride = 2 if (b == 0 and s > 0) else 1
                names = [k for k in p if k.startswith(base + ".")]
                sub = {k: p[k] for k in names}
                h = jax.checkpoint(
                    lambda sp, hh, base=base, stride=stride, down=(b == 0):
                    block(sp, hh, base, stride, down))(sub, h)
        h = jnp.mean(h, (1, 2))
        logits = r(jnp.matmul(r(h), r(p["output.weight"]).T,
                              precision="highest")) + p["output.bias"]
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.sum(jnp.take_along_axis(logp, y[:, None], 1))

    return loss


def make_sgd_steps(m, opt, precision="float32", rows=None,
                   one_pass_variance=False):
    """-> jitted run(params, xs (n,B,H,W,C), ys (n,B)) -> dict with the
    summed loss of each of the n steps, the first gradient as the optimizer
    gets it (rescaled) and the parameters after the n steps. `rows` (a
    slice) plants the fault 'part of the batch left out, the mean taken
    over the rest' in the reference."""
    import jax
    loss = make_loss(m, precision, one_pass_variance)
    lr, momentum = opt["learning_rate"], opt["momentum"]

    @jax.jit
    def run(params, xs, ys):
        mom = jax.tree_util.tree_map(lambda a: a * 0.0, params)
        losses, first = [], None
        for i in range(xs.shape[0]):
            x, y = xs[i], ys[i]
            if rows is not None:
                x, y = x[rows], y[rows]
            value, g = jax.value_and_grad(loss)(params, x, y)
            g = jax.tree_util.tree_map(lambda a: a / x.shape[0], g)
            if first is None:
                first = g
            mom = jax.tree_util.tree_map(
                lambda mo, gg: momentum * mo - lr * gg, mom, g)
            params = jax.tree_util.tree_map(lambda w, mo: w + mo,
                                            params, mom)
            # the loss is reported at the batch's own size, as the program
            # sums over all rows
            losses.append(value * (xs.shape[1] / x.shape[0]))
        return {"losses": losses, "first_grad": first, "params": params}

    return run
