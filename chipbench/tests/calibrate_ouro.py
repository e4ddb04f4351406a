#!/usr/bin/env python3
"""`calibrate_serve.py`'s method for a `serve_looped` cell: the readings
that the cell's limits are set from, on the chip at the cell's own size and
load, in ONE process. The engine is built and warmed once; each seed swaps
in its own weights (`model.params` is read at every dispatch) and drives a
short window of the cell's traffic. For every seed the program's served
tokens are read against the float32 reference (lower readings); for the
controls and planted faults named after a seed (`reference/ouro_loop.py`:
`bfloat16`, `int8`; `first_plane`, `last_plane`, `pass_short`,
`no_loop_norm`, `no_post_norms`, `interleaved_rope`, `chunk_blind`; `all`
is every one of them) the first choices of that reference are read the same
way, over the same float32 pass (upper readings). Every reading goes
through `checks.served` and is set beside the configuration's limits as the
harness sets a run's: `correct` is what a run that served those tokens
would print.

    python3 chipbench/tests/calibrate_ouro.py <cell> <seconds> \
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
                       weights_ouro)
from chipbench.paths import serve_looped  # noqa: E402
from chipbench.paths.serve_engine import drive, sample  # noqa: E402
from chipbench.reference import ouro_loop  # noqa: E402

ALL = ("bfloat16", "int8") + ouro_loop.FAULTS


def reading(gaps, limits):
    """What the harness would print for a run that served these tokens."""
    compared = checks.served(gaps)
    return {"tokens_off_best": int((gaps > 0).sum()), **compared,
            "correct": all(v <= limits[k] for k, v in compared.items())}


def main(cell_name, seconds, seeds):
    from incubator_mxnet_tpu.ops import fused
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
    exact = ouro_loop.make_forward(m)
    params = weights_ouro.ouro_params(m, seeds[0][0])
    eng, model = serve_looped.build(cfg, params)
    try:
        for i, (seed, controls) in enumerate(seeds):
            if i:
                # two trees of 5.3 GB do not fit beside the cache: the
                # idle engine holds none while the next one is made
                model.params = params = None
                gc.collect()
                params = weights_ouro.ouro_params(m, seed)
                model.params = params
            d = drive(eng, tr, seed, m["vocab"], seconds, off)
            ok = [r for r in d["records"] if r["tokens"] is not None]
            picked = sample(tr, seed, ok)
            gaps = serve_looped.served_gaps(cfg, tr, params, picked,
                                            exact=exact)
            row = {"seed": seed, "finished": len(ok),
                   "failed": len(d["records"]) - len(ok),
                   "cut_short": sum(len(r["tokens"]) != r["n_out"]
                                    for r in ok),
                   "tokens_checked": int(gaps.size),
                   "retraces": eng.retraces_after_warmup(),
                   "fallbacks": fused.fused_stats()["fallback_calls"],
                   "program": reading(gaps, cfg["limits"])}
            for control in controls:
                kind = dict(precision=control) if control in \
                    ouro_loop.PRECISIONS else dict(fault=control)
                row[control] = reading(serve_looped.served_gaps(
                    cfg, tr, params, picked, exact=exact, **kind),
                    cfg["limits"])
            print(json.dumps(row), flush=True)
            with open(out_path, "a") as f:
                f.write(json.dumps(row) + "\n")
    finally:
        eng.close(drain=False, timeout=30.0)


if __name__ == "__main__":
    main(sys.argv[1], float(sys.argv[2]),
         [(int(s.partition(":")[0]),
           [c for part in s.partition(":")[2].split(",") if part
            for c in (ALL if part == "all" else (part,))])
          for s in sys.argv[3:]])
