#!/usr/bin/env python3
"""Readings that a serve cell's limit is set from, on the chip at the
cell's own size and load, in ONE process: the engine is built and warmed
once, and each seed swaps in its own weights (`model.params` is read at
every dispatch) and drives a short window of the cell's traffic, long
enough to finish the mix's longest requests. For every seed the program's
served tokens are read against the float32 reference (lower readings); for
the first `--controls` seeds the int8 reference's own first choices are
read the same way (upper readings).

    python3 chipbench/tests/calibrate_serve.py <cell> <seconds> <controls> <seed> [<seed> ...]
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness, tracing, traffic, weights  # noqa: E402
from chipbench.paths import serve_engine  # noqa: E402


def main(cell_name, seconds, controls, seeds):
    import numpy as np
    bench = harness.Bench(ROOT)
    cell = bench.cell(cell_name)
    harness.find_device(cell["chips"])
    harness.arm_compile_cache()
    cfg = bench.config(cell["config"])
    tr = traffic.load(bench.find("traffic", cell["traffic"]))
    m = cfg["model"]
    out_path = os.path.join(ROOT, "chiprun_out",
                            f"calibrate_{cell_name}.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    off = tracing.Tracer(False, None)
    params = weights.decoder_params(m, seeds[0])
    eng, model = serve_engine.build(cfg, params)
    try:
        for i, seed in enumerate(seeds):
            params = weights.decoder_params(m, seed)
            model.params = params
            d = serve_engine.drive(eng, tr, seed, m["vocab"], seconds, off)
            ok = [r for r in d["records"] if r["tokens"] is not None]
            picked = serve_engine.sample(tr, seed, ok)
            gaps = serve_engine.served_gaps(cfg, tr, params, picked)
            row = {"seed": seed, "finished": len(ok),
                   "failed": len(d["records"]) - len(ok),
                   "tokens_checked": int(gaps.size),
                   "program_gap_max": float(gaps.max()),
                   "program_gap_p99": float(np.quantile(gaps, 0.99)),
                   "program_tokens_off_best": int((gaps > 0).sum()),
                   "retraces": eng.retraces_after_warmup()}
            if i < controls:
                low = serve_engine.served_gaps(cfg, tr, params, picked,
                                               precision="int8")
                row.update(control_gap_max=float(low.max()),
                           control_gap_p99=float(np.quantile(low, 0.99)),
                           control_tokens_off_best=int((low > 0).sum()))
            print(json.dumps(row), flush=True)
            with open(out_path, "a") as f:
                f.write(json.dumps(row) + "\n")
    finally:
        eng.close(drain=False, timeout=30.0)


if __name__ == "__main__":
    main(sys.argv[1], float(sys.argv[2]), int(sys.argv[3]),
         [int(s) for s in sys.argv[4:]])
