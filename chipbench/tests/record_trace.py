#!/usr/bin/env python3
"""Records the small trace kept in `chipbench/testdata/`: a named matmul
program and a Pallas kernel, a few executions each with host sleeps
between them, on the chip. Run once through the chip tool; the trace
comes back under `chiprun_out/testdata/`.

    python3 chipbench/tests/record_trace.py
"""
import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def scale_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    @jax.jit
    def small_matmul(a, b):
        return a @ b

    @jax.jit
    def small_kernel(x):
        return pl.pallas_call(
            scale_kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)

    a = jnp.ones((1024, 1024), jnp.bfloat16)
    x = jnp.ones((512, 512), jnp.float32)
    small_matmul(a, a).block_until_ready()
    small_kernel(x).block_until_ready()
    out = os.path.join(ROOT, "chiprun_out", "testdata")
    tmp = os.path.join(ROOT, ".chipbench_trace", "record")
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp)
    for _ in range(3):
        small_matmul(a, a).block_until_ready()
        time.sleep(0.002)
        small_kernel(x).block_until_ready()
        time.sleep(0.001)
    jax.profiler.stop_trace()
    os.makedirs(out, exist_ok=True)
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, os.path.join(out, "small.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)
    print(os.path.getsize(os.path.join(out, "small.xplane.pb")), "bytes")


if __name__ == "__main__":
    main()
