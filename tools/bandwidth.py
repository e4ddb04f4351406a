"""Collective/transfer bandwidth measurement (≙ reference
tools/bandwidth/measure.py, which timed kvstore push-pull over NCCL/ps-lite).

TPU-native: measures, over the ambient device set,
  * allreduce (psum over a mesh axis — the DP gradient path),
  * all_gather and reduce_scatter/psum_scatter (the sharded paths),
  * host->device and device->host transfer,
for a sweep of tensor sizes. Prints a table and optional JSON.

    python tools/bandwidth.py [--sizes-mb 1 4 16 64] [--json out.json]
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/bandwidth.py            # virtual 8-device mesh

Roofline calibration (`--calib`): measures the DEVICE-LOCAL memory
bandwidth (a jitted streaming triad — the roofline's byte ceiling, distinct
from the interconnect numbers above) plus a dense-compute probe, and writes
a machine-readable file (`calib.json` in the working directory, or the
path given) that `mx.inspect.roofline` takes through `MXNET_INSPECT_CALIB`
or `load_calibration(path=)` for compute- vs memory-bound classification
(workflow: docs/PERF.md "Roofline calibration"). `--peak-tflops` pins the
compute ceiling; the triad bandwidth is measured either way.

    python tools/bandwidth.py --calib                    # ./calib.json
    python tools/bandwidth.py --calib --peak-tflops 22.4 # pin compute peak
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _time(fn, sync, reps=5):
    fn()
    sync()
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    sync()
    return (time.perf_counter() - t0) / reps


def measure(sizes_mb, reps):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.array(devs), ("x",))
    repl = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P("x"))
    rows = []
    for mb in sizes_mb:
        elems = int(mb * (1 << 20) // 4)
        elems = max((elems // max(n, 1)) * max(n, 1), n)
        host = np.random.RandomState(0).randn(elems).astype(np.float32)
        nbytes = host.nbytes

        # host -> device (block: device_put is async — unsynced timing
        # would measure enqueue cost, not the transfer)
        t_h2d = _time(
            lambda: jax.block_until_ready(jax.device_put(host, devs[0])),
            lambda: None, reps)
        dev = jax.device_put(host, devs[0])
        # device -> host
        t_d2h = _time(lambda: np.asarray(dev), lambda: None, reps)

        entry = {"size_mb": mb, "devices": n,
                 "h2d_gbps": round(nbytes / t_h2d / 1e9, 2),
                 "d2h_gbps": round(nbytes / t_d2h / 1e9, 2)}

        if n > 1:
            x = jax.device_put(host, shard)
            # allreduce: psum inside shard_map over the axis
            from jax import shard_map
            f_ar = jax.jit(shard_map(lambda v: jax.lax.psum(v, "x"),
                                     mesh=mesh, in_specs=P("x"),
                                     out_specs=P("x")))
            f_ag = jax.jit(shard_map(lambda v: jax.lax.all_gather(v, "x"),
                                     mesh=mesh, in_specs=P("x"),
                                     out_specs=P("x", None)))
            f_rs = jax.jit(shard_map(
                lambda v: jax.lax.psum_scatter(v, "x", tiled=True),
                mesh=mesh, in_specs=P("x"), out_specs=P("x")))
            out = {"y": None}

            def run_ar():
                out["y"] = f_ar(x)

            def run_ag():
                out["y"] = f_ag(x)

            def run_rs():
                out["y"] = f_rs(x)

            def sync():
                jax.block_until_ready(out["y"])

            t_ar = _time(run_ar, sync, reps)
            t_ag = _time(run_ag, sync, reps)
            t_rs = _time(run_rs, sync, reps)
            # algorithmic bandwidth convention: 2*(n-1)/n * bytes / t
            algo = 2 * (n - 1) / n * nbytes
            entry["allreduce_gbps"] = round(algo / t_ar / 1e9, 2)
            entry["allgather_gbps"] = round(
                (n - 1) / n * nbytes / t_ag / 1e9, 2)
            entry["reduce_scatter_gbps"] = round(
                (n - 1) / n * nbytes / t_rs / 1e9, 2)
        rows.append(entry)
    return rows


def measure_membw(size_mb=256, reps=5):
    """Device-local memory bandwidth: a jitted streaming triad
    (`out = a + b * c`, 3 reads + 1 write counted as 4 streams) over a
    buffer big enough to spill every cache tier. This is the roofline
    byte ceiling — what a memory-bound fusion can at best sustain —
    distinct from the interconnect/transfer numbers `measure()` reports."""
    import jax
    import jax.numpy as jnp

    elems = int(size_mb * (1 << 20) // 4)
    a = jnp.arange(elems, dtype=jnp.float32) * 1e-9
    b = a * 1.000001
    c = b * 0.999999
    triad = jax.jit(lambda x, y, z: x + y * z)
    out = {"y": None}

    def run():
        out["y"] = triad(a, b, c)

    def sync():
        jax.block_until_ready(out["y"])

    t = _time(run, sync, reps)
    streams = 4 * elems * 4          # 3 operand reads + 1 result write
    return {"triad_gbps": round(streams / t / 1e9, 2),
            "bytes_per_sec": streams / t, "size_mb": size_mb}


def measure_compute_peak(reps=4):
    """Cheap dense-compute probe for the roofline flop ceiling: a chained
    f32 matmul (bf16 on accelerators) sized to amortize dispatch. On a
    TPU pass --peak-tflops (the published peak); this probe exists so a
    CPU-only environment still gets a measured, if modest, ceiling."""
    import jax
    import jax.numpy as jnp

    plat = jax.devices()[0].platform
    n = 4096 if plat != "cpu" else 1024
    dt = jnp.bfloat16 if plat != "cpu" else jnp.float32
    x = jnp.ones((n, n), dt)
    f = jax.jit(lambda c: (c @ c) * dt(1.0 / n))
    out = {"y": None}

    def run():
        y = x
        for _ in range(4):           # 4 chained matmuls per timed rep
            y = f(y)
        out["y"] = y

    def sync():
        jax.block_until_ready(out["y"])

    t = _time(run, sync, reps) / 4
    flops = 2.0 * n ** 3
    return {"matmul_tflops": round(flops / t / 1e12, 3),
            "flops_per_sec": flops / t, "n": n, "dtype": str(dt.__name__)}


DEFAULT_CALIB_PATH = "calib.json"      # in the working directory


def write_calibration(path=None, peak_tflops=None, size_mb=256, reps=5):
    """Measure and write the roofline calibration file that
    `mx.inspect.roofline.load_calibration(path=)` consumes."""
    import jax
    path = path or DEFAULT_CALIB_PATH
    dev = jax.devices()[0]
    bw = measure_membw(size_mb=size_mb, reps=reps)
    if peak_tflops is not None:
        compute = {"pinned_tflops": float(peak_tflops),
                   "flops_per_sec": float(peak_tflops) * 1e12}
    else:
        compute = measure_compute_peak(reps=reps)
    calib = {
        "format_version": 1,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "peak_flops": compute["flops_per_sec"],
        "peak_bytes_per_sec": bw["bytes_per_sec"],
        "ridge_flop_per_byte": round(
            compute["flops_per_sec"] / bw["bytes_per_sec"], 3),
        "probes": {"membw": bw, "compute": compute},
        "source": "tools/bandwidth.py --calib",
    }
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(calib, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    print(f"wrote {path}: {bw['triad_gbps']} GB/s triad, "
          f"{calib['peak_flops'] / 1e12:.3f} TFLOP/s, "
          f"ridge {calib['ridge_flop_per_byte']} FLOP/B")
    return calib


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes-mb", type=float, nargs="+",
                    default=[1, 4, 16, 64])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--json", default=None)
    ap.add_argument("--calib", nargs="?", const=DEFAULT_CALIB_PATH,
                    default=None, metavar="PATH",
                    help="measure device membw + compute peak and write "
                         "the roofline calibration file (default "
                         "./calib.json)")
    ap.add_argument("--peak-tflops", type=float, default=None,
                    help="pin the calibration's compute ceiling (TFLOP/s) "
                         "instead of the quick matmul probe")
    ap.add_argument("--calib-size-mb", type=float, default=256,
                    help="triad buffer size for --calib (default 256)")
    args = ap.parse_args()
    if args.calib:
        write_calibration(args.calib, peak_tflops=args.peak_tflops,
                          size_mb=args.calib_size_mb, reps=args.reps)
        return
    rows = measure(args.sizes_mb, args.reps)
    cols = sorted({k for r in rows for k in r})
    print("  ".join(f"{c:>16}" for c in cols))
    for r in rows:
        print("  ".join(f"{r.get(c, '-'):>16}" for c in cols))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
