#!/usr/bin/env python3
"""The CPU rehearsal: the whole command at a tiny size, with the look for
a chip skipped and Pallas kernels in interpret mode. Its numbers are CPU
numbers of toys and are never written anywhere as device metrics.

    JAX_PLATFORMS=cpu python3 chipbench/tests/rehearse.py \
        --workload resnet_tiny_train.feed_tiny --seed 3 --seconds 2 --trace 0
"""
import os
import sys
import time

T0 = time.perf_counter()
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    from incubator_mxnet_tpu.ops import fused
    fused.set_interpret(True)
    argv = sys.argv[1:]
    if "--bench" not in argv:
        argv += ["--bench", "chipbench/tests/tiny/BENCHMARK.json"]
    sys.exit(harness.main(argv, ROOT, T0, check_device=False))
