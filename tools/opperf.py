"""Per-operator benchmark harness (≙ /root/reference/benchmark/opperf/:
category-organized fwd/bwd latency tables for the operator surface).

TPU-native design: each op times three ways —
  * eager      — the imperative dispatch path users hit in a loop
  * jit        — the op compiled alone (XLA kernel latency; what a fused
                 graph pays, minus fusion wins)
  * bwd (jit)  — value_and_grad of the op compiled alone

and carries its roofline coordinates (`mx.inspect.roofline.callable_cost`):
estimated flops, bytes moved, arithmetic intensity (FLOP/B), and the
compute- vs memory-bound class against the ridge point of
`roofline.load_calibration()` (see `tools/bandwidth.py --calib`)
— so the latency table doubles as the offender work-list's per-op ground
truth. Backends whose cost analysis lacks bytes-accessed keys degrade to
the HLO shape model, and to flops-only rows when that fails too.

Measurements synchronize with block_until_ready and report median-of-N.
Categories mirror the reference's nd_operations modules: unary, binary
(broadcast + elementwise), gemm, reduction, sorting/searching, random,
activation, conv/pool, norm, optimizer-update.

Usage:
  python tools/opperf.py                       # all categories, table
  python tools/opperf.py --categories unary gemm --json out.json
  python tools/opperf.py --platform cpu        # force host platform
  python tools/opperf.py --quick --json out.json   # CI smoke
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _time_fn(fn, args, warmup=3, iters=10):
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e6)
    return statistics.median(ts)


_CALIB = {"c": None}


def _calib():
    if _CALIB["c"] is None:
        from incubator_mxnet_tpu.inspect import roofline
        _CALIB["c"] = roofline.load_calibration()
    return _CALIB["c"]


def _roofline_cols(fn, dev_args):
    """est_flops / est_bytes / intensity / bound columns for one op row
    (cost-analysis first, HLO shape model fallback; a totally opaque op
    yields nulls rather than killing the table)."""
    from incubator_mxnet_tpu.inspect import roofline
    try:
        cost = roofline.callable_cost(fn, *dev_args, calib=_calib())
    except Exception as e:
        return {"est_flops": None, "est_bytes": None, "intensity": None,
                "bound": None, "cost_error": str(e)[:120]}
    return {"est_flops": cost["est_flops"], "est_bytes": cost["est_bytes"],
            "intensity": cost["intensity"], "bound": cost["bound"],
            "bytes_estimated": cost["bytes_estimated"]}


def _bench_one(name, fn, arg_arrays, grad_idx=0, warmup=3, iters=10,
               unfused_fn=None):
    """Returns dict with eager/jit/bwd median microseconds + roofline
    coordinates. When `unfused_fn` is given (the fused-tier rows), the
    unfused composition is timed under jit too and the row carries a
    fused-vs-unfused `speedup_vs_unfused` column (fwd) and
    `bwd_speedup_vs_unfused`."""
    import jax
    import jax.numpy as jnp

    dev_args = [jax.device_put(a) for a in arg_arrays]
    row = {"op": name}
    row["eager_us"] = round(_time_fn(fn, dev_args, warmup, iters), 1)
    jfn = jax.jit(fn)
    row["jit_us"] = round(_time_fn(jfn, dev_args, warmup, iters), 1)
    try:
        def loss(*xs):
            return jnp.sum(jnp.abs(fn(*xs)))
        gfn = jax.jit(jax.grad(loss, argnums=grad_idx))
        row["bwd_us"] = round(_time_fn(gfn, dev_args, warmup, iters), 1)
    except Exception:
        row["bwd_us"] = None  # non-differentiable op
    if unfused_fn is not None:
        ujfn = jax.jit(unfused_fn)
        row["unfused_jit_us"] = round(
            _time_fn(ujfn, dev_args, warmup, iters), 1)
        if row["jit_us"] > 0:
            row["speedup_vs_unfused"] = round(
                row["unfused_jit_us"] / row["jit_us"], 3)
        try:
            def uloss(*xs):
                return jnp.sum(jnp.abs(unfused_fn(*xs)))
            ugfn = jax.jit(jax.grad(uloss, argnums=grad_idx))
            row["unfused_bwd_us"] = round(
                _time_fn(ugfn, dev_args, warmup, iters), 1)
            if row["bwd_us"]:
                row["bwd_speedup_vs_unfused"] = round(
                    row["unfused_bwd_us"] / row["bwd_us"], 3)
        except Exception:
            row["unfused_bwd_us"] = None
    row.update(_roofline_cols(jfn, dev_args))   # reuses the timed compile
    return row


def _rand(shape, dtype=np.float32, positive=False):
    rng = np.random.RandomState(hash(shape) % (2 ** 31))
    a = rng.uniform(0.5 if positive else -1.0, 1.0, shape)
    return a.astype(dtype)


# --------------------------------------------------------------------------
# category tables. Default shapes follow the reference's opperf defaults
# (1024x1024 tensors, 32x3x256x256 conv inputs scaled down to stay quick).
# --------------------------------------------------------------------------

def cat_unary(jnp, npx):
    big = (_rand((1024, 1024)),)
    pos = (_rand((1024, 1024), positive=True),)
    return [
        ("exp", lambda x: jnp.exp(x), big),
        ("log", lambda x: jnp.log(x), pos),
        ("sqrt", lambda x: jnp.sqrt(x), pos),
        ("rsqrt", lambda x: 1.0 / jnp.sqrt(x), pos),
        ("sigmoid", lambda x: 1 / (1 + jnp.exp(-x)), big),
        ("tanh", lambda x: jnp.tanh(x), big),
        ("erf", lambda x: __import__("jax").scipy.special.erf(x), big),
        ("abs", lambda x: jnp.abs(x), big),
        ("sign", lambda x: jnp.sign(x), big),
        ("round", lambda x: jnp.round(x), big),
        ("square", lambda x: x * x, big),
        ("reciprocal", lambda x: 1.0 / x, pos),
    ]


def cat_binary(jnp, npx):
    a = _rand((1024, 1024))
    b = _rand((1024, 1024))
    col = _rand((1024, 1))
    return [
        ("add", lambda x, y: x + y, (a, b)),
        ("sub", lambda x, y: x - y, (a, b)),
        ("mul", lambda x, y: x * y, (a, b)),
        ("div", lambda x, y: x / (y + 2.0), (a, b)),
        ("pow", lambda x, y: jnp.power(jnp.abs(x) + 0.5, y), (a, b)),
        ("maximum", lambda x, y: jnp.maximum(x, y), (a, b)),
        ("broadcast_add", lambda x, y: x + y, (a, col)),
        ("broadcast_mul", lambda x, y: x * y, (a, col)),
        ("equal", lambda x, y: (x == y).astype(jnp.float32), (a, b)),
        ("where", lambda x, y: jnp.where(x > 0, x, y), (a, b)),
    ]


def cat_gemm(jnp, npx):
    a = _rand((1024, 1024))
    b = _rand((1024, 1024))
    bt = _rand((32, 256, 256))
    return [
        ("dot_1024", lambda x, y: x @ y, (a, b)),
        ("dot_bf16_1024",
         lambda x, y: (x.astype(jnp.bfloat16) @ y.astype(jnp.bfloat16))
         .astype(jnp.float32), (a, b)),
        ("batch_dot_32x256", lambda x, y: jnp.einsum("bij,bjk->bik", x, y),
         (bt, bt)),
        ("transpose_dot", lambda x, y: x.T @ y, (a, b)),
    ]


def cat_reduction(jnp, npx):
    a = _rand((1024, 1024))
    return [
        ("sum", lambda x: jnp.sum(x), (a,)),
        ("sum_axis0", lambda x: jnp.sum(x, axis=0), (a,)),
        ("mean", lambda x: jnp.mean(x), (a,)),
        ("max", lambda x: jnp.max(x), (a,)),
        ("argmax_axis1", lambda x: jnp.argmax(x, axis=1), (a,)),
        ("norm", lambda x: jnp.sqrt(jnp.sum(x * x)), (a,)),
        ("softmax_axis1",
         lambda x: __import__("jax").nn.softmax(x, axis=1), (a,)),
        ("logsumexp",
         lambda x: __import__("jax").scipy.special.logsumexp(x, axis=1),
         (a,)),
    ]


def cat_sorting(jnp, npx):
    a = _rand((1024, 1024))
    return [
        ("sort_axis1", lambda x: jnp.sort(x, axis=1), (a,)),
        ("argsort_axis1", lambda x: jnp.argsort(x, axis=1), (a,)),
        ("topk_10", lambda x: __import__("jax").lax.top_k(x, 10)[0], (a,)),
    ]


def cat_random(jnp, npx):
    import jax
    key = np.zeros(2, np.uint32)
    return [
        ("uniform_1M",
         lambda k: jax.random.uniform(jax.random.wrap_key_data(
             k.astype(np.uint32)), (1024, 1024)), (key,)),
        ("normal_1M",
         lambda k: jax.random.normal(jax.random.wrap_key_data(
             k.astype(np.uint32)), (1024, 1024)), (key,)),
        ("bernoulli_1M",
         lambda k: jax.random.bernoulli(jax.random.wrap_key_data(
             k.astype(np.uint32)), 0.5, (1024, 1024)), (key,)),
    ]


def cat_activation(jnp, npx):
    import jax
    a = _rand((32, 1024))
    return [
        ("relu", lambda x: jax.nn.relu(x), (a,)),
        ("leaky_relu", lambda x: jax.nn.leaky_relu(x), (a,)),
        ("gelu", lambda x: jax.nn.gelu(x), (a,)),
        ("softrelu", lambda x: jax.nn.softplus(x), (a,)),
        ("hard_sigmoid", lambda x: jax.nn.hard_sigmoid(x), (a,)),
    ]


def cat_conv(jnp, npx):
    import jax
    x_nhwc = _rand((16, 64, 64, 32))
    w_hwio = _rand((3, 3, 32, 64)) * 0.1

    def conv(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def conv_bf16(x, w):
        return jax.lax.conv_general_dilated(
            x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")).astype(jnp.float32)

    def maxpool(x):
        return jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")

    return [
        ("conv3x3_nhwc_16x64x64x32", conv, (x_nhwc, w_hwio)),
        ("conv3x3_bf16", conv_bf16, (x_nhwc, w_hwio)),
        ("maxpool2x2", maxpool, (x_nhwc,)),
    ]


def cat_norm(jnp, npx):
    a = _rand((32, 128, 768))
    g = _rand((768,), positive=True)
    b = _rand((768,))

    def layernorm(x, gamma, beta):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * gamma + beta

    def batchnorm_infer(x, gamma, beta):
        return x * gamma + beta

    return [
        ("layernorm_32x128x768", layernorm, (a, g, b)),
        ("batchnorm_infer", batchnorm_infer, (a, g, b)),
    ]


def cat_optimizer(jnp, npx):
    w = _rand((1024, 1024))
    gr = _rand((1024, 1024))
    m = _rand((1024, 1024))
    v = np.abs(_rand((1024, 1024)))

    def sgd_mom(wt, g, mom):
        mom2 = 0.9 * mom - 0.01 * g
        return wt + mom2

    def adam(wt, g, mt, vt):
        m2 = 0.9 * mt + 0.1 * g
        v2 = 0.999 * vt + 0.001 * g * g
        return wt - 0.001 * m2 / (jnp.sqrt(v2) + 1e-8)

    return [
        ("sgd_momentum_update_1M", sgd_mom, (w, gr, m)),
        ("adam_update_1M", adam, (w, gr, m, v)),
    ]


def cat_fused(jnp, npx):
    """The fused kernel tier (ops/fused.py + npx.flash_attention): each
    row times the FUSED op against its UNFUSED composition under jit —
    the per-op ground truth for the offender work-list's projected wins
    (4-tuples: the extra element is the unfused fn)."""
    import functools
    from incubator_mxnet_tpu.ops import fused as F
    from incubator_mxnet_tpu.ops import nn as NN
    from incubator_mxnet_tpu.ops.pallas_attention import flash_attention

    x = _rand((32 * 28 * 28, 256))
    s = _rand((256,), positive=True)
    b = _rand((256,))
    r = _rand((32 * 28 * 28, 256))
    m = _rand((256,))
    v = _rand((256,), positive=True)
    xp = _rand((16, 28, 28, 256))
    q = _rand((8, 256, 64))

    def unfused_pool(t):
        return NN.pooling(t, (2, 2), "avg", stride=(2, 2), layout="NHWC")

    def unfused_attn(a, b_, c):
        return NN.scaled_dot_product_attention(a, b_, c)

    return [
        ("fused_bias_act_relu", functools.partial(F.bias_act,
                                                  act_type="relu"),
         functools.partial(F.bias_act_ref, act_type="relu"), (x, b)),
        ("fused_norm_act_residual",
         functools.partial(F.norm_act_residual, act_type="relu"),
         functools.partial(F.norm_act_residual_ref, act_type="relu"),
         (x, s, b, r)),
        ("fused_bn_inference_relu",
         functools.partial(F.bn_inference, act_type="relu"),
         functools.partial(F.bn_inference_ref, act_type="relu"),
         (x, s, b, m, v)),
        ("fused_avg_pool2d_2x2",
         functools.partial(F.avg_pool2d, pool_size=(2, 2)),
         unfused_pool, (xp,)),
        ("flash_attention_8x256x64", flash_attention, unfused_attn,
         (q, q, q)),
    ]


CATEGORIES = {
    "unary": cat_unary,
    "binary": cat_binary,
    "gemm": cat_gemm,
    "reduction": cat_reduction,
    "sorting": cat_sorting,
    "random": cat_random,
    "activation": cat_activation,
    "conv": cat_conv,
    "norm": cat_norm,
    "optimizer": cat_optimizer,
    "fused": cat_fused,
}


# a compute class, a memory class, and the fused tier (speedup column)
QUICK_CATEGORIES = ("gemm", "norm", "fused")


def run(categories=None, as_json=None, quick=False):
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu import npx

    platform = jax.devices()[0].platform
    warmup, iters = (1, 3) if quick else (3, 10)
    if categories is None:
        categories = QUICK_CATEGORIES if quick else list(CATEGORIES)
    results = {}
    for cat in categories:
        specs = CATEGORIES[cat](jnp, npx)
        rows = []
        for spec in specs:
            if len(spec) == 4:          # fused rows: (name, fn, unfused, args)
                name, fn, unfused_fn, args = spec
            else:
                (name, fn, args), unfused_fn = spec, None
            try:
                rows.append(_bench_one(name, fn, args, warmup=warmup,
                                       iters=iters, unfused_fn=unfused_fn))
            except Exception as e:  # keep the table going
                rows.append({"op": name, "error": str(e)[:120]})
        results[cat] = rows

    if as_json:
        with open(as_json, "w") as f:
            json.dump({"platform": platform, "quick": quick,
                       "calibration": _calib(), "results": results}, f,
                      indent=1)
    # render table
    cal = _calib()
    print(f"# opperf ({platform}; roofline ridge "
          f"{cal['ridge_flop_per_byte']:.1f} FLOP/B from "
          f"{cal.get('source', 'unknown')})")
    print(f"{'op':32s} {'eager_us':>10s} {'jit_us':>10s} {'bwd_us':>10s} "
          f"{'GFLOP':>8s} {'MB':>8s} {'FLOP/B':>8s} {'bound':>8s}")
    for cat, rows in results.items():
        print(f"-- {cat} " + "-" * 94)
        for r in rows:
            if "error" in r:
                print(f"{r['op']:32s} ERROR {r['error']}")
                continue
            bwd = f"{r['bwd_us']:10.1f}" if r["bwd_us"] is not None \
                else "       n/a"
            gf = (f"{r['est_flops'] / 1e9:8.3f}"
                  if r.get("est_flops") is not None else "     n/a")
            mb = (f"{r['est_bytes'] / 1e6:8.3f}"
                  if r.get("est_bytes") is not None else "     n/a")
            ai = (f"{r['intensity']:8.2f}"
                  if r.get("intensity") is not None else "     n/a")
            bound = r.get("bound") or "n/a"
            line = (f"{r['op']:32s} {r['eager_us']:10.1f} "
                    f"{r['jit_us']:10.1f} {bwd} {gf} {mb} {ai} {bound:>8s}")
            if r.get("speedup_vs_unfused") is not None:
                line += (f"  vs-unfused {r['speedup_vs_unfused']:.2f}x"
                         + (f" (bwd {r['bwd_speedup_vs_unfused']:.2f}x)"
                            if r.get("bwd_speedup_vs_unfused") else ""))
            print(line)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--categories", nargs="*", default=None,
                    choices=list(CATEGORIES))
    ap.add_argument("--json", default=None)
    ap.add_argument("--platform", default=None,
                    help="force a platform (e.g. cpu)")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: gemm+norm categories, 3 timed iters")
    args = ap.parse_args()
    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)
    run(args.categories, args.json, quick=args.quick)


if __name__ == "__main__":
    main()
