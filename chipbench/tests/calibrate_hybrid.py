#!/usr/bin/env python3
"""`calibrate_serve.py`'s method for a `serve_hybrid` cell: the readings
that the cell's limits are set from, on the chip at the cell's own size and
load, in ONE process. The engine is built and warmed once; each seed swaps
in its own weights (`model.params` is read at every dispatch) and drives a
short window of the cell's traffic. For every seed the program's served
tokens are read against the float32 reference (lower readings); for the
controls and planted faults named after a seed (`reference/sambay.py`:
`bfloat16`, `bf16_state`, `int8`; `state_unchanged`, `state_reset`,
`state_stale`) the first choices of that reference are read the same way,
over the same float32 pass (upper readings). Every reading goes through
`checks.served` and is set beside the configuration's limits as the harness
sets a run's: `correct` is what a run that served those tokens would print.

    python3 chipbench/tests/calibrate_hybrid.py <cell> <seconds> \
        <seed>[:control,control..] ...
"""
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import (checks, harness, tracing, traffic,  # noqa: E402
                       weights_sambay)
from chipbench.paths import serve_hybrid  # noqa: E402
from chipbench.paths.serve_engine import drive, sample  # noqa: E402
from chipbench.reference import sambay  # noqa: E402


def reading(gaps, limits):
    """What the harness would print for a run that served these tokens."""
    compared = checks.served(gaps)
    return {"tokens_off_best": int((gaps > 0).sum()), **compared,
            "correct": all(v <= limits[k] for k, v in compared.items())}


def main(cell_name, seconds, seeds):
    import numpy as np
    bench = harness.Bench(ROOT)
    cell = bench.cell(cell_name)
    harness.find_device(cell["chips"])
    harness.arm_compile_cache()
    cfg = bench.config(cell["config"])
    tr = traffic.load(bench.find("traffic", cell["traffic"]))
    m = cfg["model"]
    pad_to = -(-(tr["prompt"]["max"] + tr["output"]["max"]) // 128) * 128
    out_path = os.path.join(ROOT, "chiprun_out",
                            f"calibrate_{cell_name}.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    off = tracing.Tracer(False, None)
    exact = sambay.make_forward(m)
    params = weights_sambay.sambay_params(m, seeds[0][0])
    eng, model = serve_hybrid.build(cfg, params)
    try:
        for i, (seed, controls) in enumerate(seeds):
            if i:
                # two trees of 7.7 GB do not fit beside the cache: the
                # idle engine holds none while the next one is made
                model.params = params = None
                gc.collect()
                params = weights_sambay.sambay_params(m, seed)
                model.params = params
            d = drive(eng, tr, seed, m["vocab"], seconds, off)
            ok = [r for r in d["records"] if r["tokens"] is not None]
            picked = sample(tr, seed, ok)
            rows = [sambay.served_rows(exact, params, r["prompt"],
                                       r["tokens"], pad_to) for r in picked]
            gaps = np.concatenate([
                sambay.gaps_below_best(exact, params, at, r["tokens"])
                for at, r in zip(rows, picked)])
            row = {"seed": seed, "finished": len(ok),
                   "failed": len(d["records"]) - len(ok),
                   "tokens_checked": int(gaps.size),
                   "retraces": eng.retraces_after_warmup(),
                   "program": reading(gaps, cfg["limits"])}
            for control in controls:
                judge = sambay.make_forward(m, control)
                low = np.concatenate([
                    sambay.gaps_below_best(
                        exact, params, at, sambay.first_choices(
                            judge, params, sambay.served_rows(
                                judge, params, r["prompt"], r["tokens"],
                                pad_to)))
                    for at, r in zip(rows, picked)])
                row[control] = reading(low, cfg["limits"])
            print(json.dumps(row), flush=True)
            with open(out_path, "a") as f:
                f.write(json.dumps(row) + "\n")
    finally:
        eng.close(drain=False, timeout=30.0)


if __name__ == "__main__":
    main(sys.argv[1], float(sys.argv[2]),
         [(int(s.partition(":")[0]),
           [c for c in s.partition(":")[2].split(",") if c])
          for s in sys.argv[3:]])
