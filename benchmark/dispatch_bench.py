"""Eager-dispatch host-overhead benchmark (≙ the reference's op-bulking
motivation: per-op FFI/engine-push cost bounds imperative throughput,
src/imperative/cached_op.cc:665).

Measures what ONE eager op costs on the HOST — python dispatch, key
derivation, taping, wrap/unwrap — with device compute kept tiny so host
overhead dominates. Three engine configurations are timed:

  bulked     default engine (ops defer into a Segment, flush on sync)
  immediate  bulk size 0 (every invoke executes now; the fast-path target)
  naive      MXNET_ENGINE_TYPE=NaiveEngine semantics (block per op)

plus autograd-recording variants (forward taping + backward), and an
eager model step (ResNet-18 full mode / a small convnet in --quick) run
without hybridize so every layer goes through `invoke` — the "eager
ResNet step host overhead" row from ROADMAP open item 6.

Writes a JSON artifact (default benchmark/results/dispatch_bench.json).
Committed before/after pairs live in benchmark/results/dispatch_r06_*.json.

Usage:
  python benchmark/dispatch_bench.py                    # full, table + JSON
  python benchmark/dispatch_bench.py --quick --out /tmp/d.json
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Host-overhead benchmark: default to the CPU before jax initializes (same
# recipe as tests/conftest.py); the output is stamped with the platform
# jax really ran on.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np


def _median_us(fn, iters, warmup):
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1e6)
    return statistics.median(ts)


def _per_op_bench(mx, engine, iters, warmup, chain=32):
    """Per-op host latency, sync (asnumpy per op) and chained (one sync at
    the end of a dependent chain — amortized per-op cost)."""
    x = mx.np.array(np.zeros((8, 8), np.float32))

    def sync_one():
        (x + 1.0).asnumpy()

    def chained():
        y = x
        for _ in range(chain):
            y = y * 1.0 + 0.5
        y.asnumpy()

    out = {"sync_us": round(_median_us(sync_one, iters, warmup), 1),
           "chained_us_per_op": round(
               _median_us(chained, max(2, iters // 4), warmup) / chain, 1)}
    return out


def _recording_bench(mx, iters, warmup, chain=16):
    """Taping overhead: forward chain under record (fwd_us_per_op) and the
    full fwd+backward round trip (fwd_bwd_us_per_op)."""
    from incubator_mxnet_tpu import autograd
    x = mx.np.array(np.ones((8, 8), np.float32))
    x.attach_grad()

    def fwd_only():
        with autograd.record():
            y = x
            for _ in range(chain):
                y = y * 1.0 + 0.5
            y = y.sum()
        y.asnumpy()

    def fwd_bwd():
        with autograd.record():
            y = x
            for _ in range(chain):
                y = y * 1.0 + 0.5
            y = y.sum()
        y.backward()
        x.grad.asnumpy()

    return {"fwd_us_per_op": round(
                _median_us(fwd_only, iters, warmup) / chain, 1),
            "fwd_bwd_us_per_op": round(
                _median_us(fwd_bwd, iters, warmup) / chain, 1)}


def _make_model(quick):
    from incubator_mxnet_tpu import gluon
    if quick:
        # tiny convnet stand-in: same layer kinds as ResNet (conv/BN/relu/
        # pool/dense) so the smoke exercises the same dispatch surface
        # without ResNet-18's CPU compile cost
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Conv2D(8, 3, padding=1, in_channels=3),
                gluon.nn.BatchNorm(),
                gluon.nn.Activation("relu"),
                gluon.nn.MaxPool2D(2),
                gluon.nn.Flatten(),
                gluon.nn.Dense(10))
        return net, "convnet-small", (1, 3, 16, 16)
    from incubator_mxnet_tpu.gluon.model_zoo import vision
    return vision.resnet18_v1(), "resnet18_v1", (1, 3, 64, 64)


def _model_step_bench(mx, quick, iters, warmup):
    """Eager (non-hybridized) train step: fwd + loss + backward + SGD.
    Tiny spatial dims keep device compute small — the number is host
    overhead, the quantity the dispatch fast path attacks."""
    from incubator_mxnet_tpu import autograd, gluon
    net, name, shape = _make_model(quick)
    net.initialize()
    x = mx.np.array(np.random.RandomState(0).rand(*shape).astype(np.float32))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01})

    def step():
        with autograd.record():
            out = net(x)
            loss = out.sum()
        loss.backward()
        trainer.step(shape[0])
        loss.asnumpy()

    ms = _median_us(step, iters, warmup) / 1e3
    # rough op count per step for a per-op figure
    from incubator_mxnet_tpu.ops import registry as _registry
    stats_fn = getattr(_registry, "dispatch_stats", None)
    n_ops = None
    if stats_fn is not None:
        before = stats_fn().get("dispatch", 0)
        step()
        n_ops = stats_fn().get("dispatch", 0) - before
    row = {"model": name, "step_ms": round(ms, 2)}
    if n_ops:
        row["invokes_per_step"] = n_ops
        row["host_us_per_invoke"] = round(ms * 1e3 / n_ops, 1)
    return row


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--quick", action="store_true",
                   help="CI smoke: few iters, small convnet instead of "
                        "ResNet-18 (asserts valid JSON, not perf)")
    p.add_argument("--out", default=None, help="output JSON path")
    p.add_argument("--label", default=None,
                   help="free-form label stored in meta (e.g. 'pre-PR2')")
    p.add_argument("--iters", type=int, default=None)
    args = p.parse_args(argv)

    iters = args.iters or (5 if args.quick else 40)
    warmup = 2 if args.quick else 5

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import engine
    import jax

    result = {"meta": {"platform": jax.devices()[0].platform,
                       "quick": bool(args.quick),
                       "label": args.label,
                       "iters": iters}}

    # --- per-op, three engine configs ---------------------------------
    result["per_op"] = {}
    result["per_op"]["bulked"] = _per_op_bench(mx, engine, iters, warmup)
    prev = engine.set_bulk_size(0)
    try:
        result["per_op"]["immediate"] = _per_op_bench(mx, engine, iters,
                                                      warmup)
        result["recording_immediate"] = _recording_bench(mx, iters, warmup)
    finally:
        engine.set_bulk_size(prev)
    prev_naive = engine.set_naive(True)
    try:
        result["per_op"]["naive"] = _per_op_bench(mx, engine, iters, warmup)
    finally:
        engine.set_naive(prev_naive)
    result["recording_bulked"] = _recording_bench(mx, iters, warmup)

    # --- eager model step ---------------------------------------------
    result["model_step"] = {}
    result["model_step"]["bulked"] = _model_step_bench(
        mx, args.quick, max(3, iters // 4), warmup)
    prev = engine.set_bulk_size(0)
    try:
        result["model_step"]["immediate"] = _model_step_bench(
            mx, args.quick, max(3, iters // 4), warmup)
    finally:
        engine.set_bulk_size(prev)

    # --- dispatch-stats counters (post-PR2 registries only) ----------
    from incubator_mxnet_tpu.ops import registry as _registry
    stats_fn = getattr(_registry, "dispatch_stats", None)
    if stats_fn is not None:
        result["dispatch_stats"] = stats_fn()

    out_path = args.out or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "results",
        "dispatch_bench.json")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
        f.write("\n")

    print(json.dumps(result, indent=2, sort_keys=True))
    print(f"\nwrote {out_path}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
