#!/usr/bin/env python3
"""The benchmark's one command:

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 chipbench/run.py --list

One new process: load, warm the cell's own shapes, measure for `--seconds`,
check what the timed path produced against the plain reference, print one
JSON object as the last line of standard output. Without a TPU, with fewer
chips than the cell asks for, or on a `device_kind` missing from
`chipbench/peaks.json`, it exits non-zero and prints no result."""
import os
import sys
import time

T_PROCESS_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], ROOT, T_PROCESS_START))
