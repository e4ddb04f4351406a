#!/usr/bin/env python3
"""`calibrate_ling.py`'s method for a `serve_mamba_moe` cell: the readings
that the cell's limits are set from, on the chip at the cell's own size and
load, in ONE process. The engine is built and warmed once; each seed swaps
in its own weights (`model.params` is read at every dispatch) and drives a
short window of the cell's traffic. For every seed the program's served
tokens, and the Mamba states that its longest generations left in the
drained pool, are read against the float32 reference (lower readings);
for the controls and planted faults named after a seed (`reference/
nemotron_h.py`: `bfloat16`, `bf16_state`, `int8`; `state_cut`,
`stale_state`, `tail_cut`, `group_mod`, `relu`, `norm_first`) the first
choices of that forward and the states it leaves are read the same way,
over the same requests (upper readings). Every reading goes through the
path's own `read_against_reference`, `checks.served` and `state_check` and
is set beside the configuration's limits as the harness sets a run's:
`correct` is what a run that served those tokens and left those states
would print.

    python3 chipbench/tests/calibrate_nemotron.py <cell> <seconds> \
        <seed>[:control,control..] ...
"""
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

from chipbench import checks, harness, tracing, traffic, \
    weights_nemotron  # noqa: E402
from chipbench.paths import serve_mamba_moe  # noqa: E402
from chipbench.paths.serve_engine import drive, sample  # noqa: E402
from chipbench.paths.serve_hybrid import Timed  # noqa: E402


def reading(gaps, state_gaps, limits):
    """What the harness would print for a run that served these tokens and
    left these states."""
    compared = dict(checks.served(gaps),
                    **serve_mamba_moe.state_check(state_gaps))
    return {"tokens_off_best": int((gaps > 0).sum()), **compared,
            "state_gaps": [[[float(f"{g:.4g}") for g in gs] for gs in pair]
                           for pair in state_gaps],
            "correct": all(v <= limits[k] for k, v in compared.items())}


def main(cell_name, seconds, seeds):
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
    params = weights_nemotron.nemotron_params(m, seeds[0][0])
    eng, model = serve_mamba_moe.build(cfg, params)
    try:
        for i, (seed, controls) in enumerate(seeds):
            if i:
                # the idle engine holds no tree while the next one is made
                model.params = params = None
                gc.collect()
                params = weights_nemotron.nemotron_params(m, seed)
                model.params = params
            timed = Timed(eng)
            d = drive(timed, tr, seed, m["vocab"], seconds, off)
            ok = [r for r in d["records"] if r["tokens"] is not None]
            picked = sample(tr, seed, ok)
            left = serve_mamba_moe.left_states(
                eng, d["records"], timed.futures, tr["check_states"])
            passes = {}
            gaps, state_gaps = serve_mamba_moe.read_against_reference(
                cfg, tr, params, picked, left, exact_passes=passes)
            lengths = lambda rs: [[int(r["prompt"].size),  # noqa: E731
                                   len(r["tokens"])] for r in rs]
            row = {"seed": seed, "finished": len(ok),
                   "failed": len(d["records"]) - len(ok),
                   "tokens_checked": int(gaps.size),
                   "lengths_checked": lengths(picked),
                   "lengths_of_states": lengths(r for r, _ in left),
                   "retraces": eng.retraces_after_warmup(),
                   "program": reading(gaps, state_gaps, cfg["limits"])}
            for control in controls:
                row[control] = reading(
                    *serve_mamba_moe.read_against_reference(
                        cfg, tr, params, picked, left, precision=control,
                        exact_passes=passes), cfg["limits"])
                gc.collect()
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
