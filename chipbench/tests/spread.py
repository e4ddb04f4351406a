#!/usr/bin/env python3
"""One set of runs of a cell, as the driver makes them: a new process per
seed (this parent stays off JAX, so each child gets the chip), the result
lines kept in a file, and per metric the median and the spread (distance
between the quartiles of `statistics.quantiles(values, n=4)` over the
median) printed at the end.

    python3 chipbench/tests/spread.py <out.jsonl> <cell> <seconds> <trace> <seed> [<seed> ...]
"""
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(out, cell, seconds, trace, seeds):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    lines = []
    for seed in seeds:
        t0 = time.time()
        done = subprocess.run(
            command + ["--workload", cell, "--seed", str(seed), "--seconds",
                       seconds, "--trace", trace],
            cwd=ROOT, capture_output=True, text=True)
        last = done.stdout.strip().splitlines()[-1] if done.stdout.strip() \
            else ""
        try:
            line = json.loads(last)
        except ValueError:
            line = {"error": done.stderr[-2000:]}
        line.update(seed=int(seed), rc=done.returncode,
                    wall_s=round(time.time() - t0, 1))
        lines.append(line)
        with open(out, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps({k: line.get(k) for k in
                          ("seed", "rc", "wall_s", "correct", "metrics",
                           "compared", "error")}), flush=True)
    good = [ln for ln in lines if "metrics" in ln]
    for name in sorted({k for ln in good for k in ln["metrics"]}):
        values = [ln["metrics"][name]["value"] for ln in good
                  if name in ln["metrics"]]
        if len(values) >= 2:
            q = statistics.quantiles(values, n=4)
            med = statistics.median(values)
            print(f"spread {cell} {name}: n={len(values)} median={med!r} "
                  f"iqr/median={(q[2] - q[0]) / med:.5f} "
                  f"min={min(values)!r} max={max(values)!r}", flush=True)
    print(f"correct in {sum(bool(ln.get('correct')) for ln in lines)} of "
          f"{len(lines)} runs", flush=True)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], sys.argv[3], sys.argv[4], sys.argv[5:])
