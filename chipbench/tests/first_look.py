#!/usr/bin/env python3
"""A traced run of one cell that also keeps a description of the trace
(planes, lines, the heaviest operation and module names) under
`chiprun_out/`, for the person who writes a metric's name pattern.

    python3 chipbench/tests/first_look.py <cell> <seed> <seconds>
"""
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    cell, seed, seconds = sys.argv[1], int(sys.argv[2]), float(sys.argv[3])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    bench = harness.Bench(ROOT)
    line = harness.run_cell(
        bench, cell, seed, seconds, True, T0,
        describe_to=os.path.join(ROOT, "chiprun_out",
                                 f"trace_{cell}.json"))
    print(json.dumps(line))
