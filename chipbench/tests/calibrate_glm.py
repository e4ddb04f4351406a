#!/usr/bin/env python3
"""`calibrate_hybrid.py`'s method for a `serve_sparse_moe` cell: the
readings that the cell's limits are set from, on the chip at the cell's own
size and load, in ONE process. The engine is built and warmed once; each
seed swaps in its own weights (`model.params` is read at every dispatch)
and drives a short window of the cell's traffic. For every seed the
program's served tokens are read against the float32 reference (lower
readings); for the controls and planted faults named after a seed
(`reference/glm_dsa.py`: `bfloat16`, `int8`; `newest_topk`, `stale_select`,
`no_rope_kr`, `first_experts`, `held_norm`) the first choices of that
forward are read the same way, over the same float32 pass (upper readings).
Every reading goes through `checks.served` and is set beside the
configuration's limits as the harness sets a run's: `correct` is what a run
that served those tokens would print.

For every checked request it also reports `select_agreement`: the share of
S_t, at the served positions, on which the PROGRAM's own first indexer
(its `index_project` and `index_scores` in the served type over the
embedded sequence, `lax.top_k` as its decode step takes it) and the
float32 reference's first layer agree.

    python3 chipbench/tests/calibrate_glm.py <cell> <seconds> \
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
                       weights_glm)
from chipbench.paths import serve_sparse_moe  # noqa: E402
from chipbench.paths.serve_engine import drive, sample  # noqa: E402
from chipbench.reference import glm_dsa  # noqa: E402


def reading(gaps, limits):
    """What the harness would print for a run that served these tokens."""
    compared = checks.served(gaps)
    return {"tokens_off_best": int((gaps > 0).sum()), **compared,
            "correct": all(v <= limits[k] for k, v in compared.items())}


def select_agreement(m, params, exact, r, pad_to):
    """Share of S_t on which the program's first indexer and the
    reference's agree, over the served positions of request `r`."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.models import sparse_moe_decoder as sm
    c = weights_glm.sparse_moe_config(m)
    seq = np.zeros((pad_to,), np.int32)
    n = r["prompt"].size + len(r["tokens"]) - 1
    seq[:n] = np.concatenate([r["prompt"], r["tokens"][:-1]])
    at = slice(r["prompt"].size - 1, n)

    @jax.jit
    def chosen(params, tokens):
        w = sm._weights(params, c, 0)
        pos = jnp.arange(pad_to)[None]
        h = sm.rms_norm(params["emb"][tokens][None], w["ln1_w"], c.norm_eps)
        cq, _, _, _ = sm.mla_project(w, c, h, pos)
        qI, wI, kI = sm.index_project(w, c, h, cq, pos)
        scores = sm.index_scores(qI[:, at], wI[:, at], kI)[0]
        live = jnp.arange(pad_to)[None, :] <= jnp.arange(pad_to)[at, None]
        _, idx = jax.lax.top_k(jnp.where(live, scores, -jnp.inf),
                               c.index_topk)
        return jnp.zeros(scores.shape, bool).at[
            jnp.arange(scores.shape[0])[:, None], idx].set(True) & live

    ours = np.asarray(chosen(params, jnp.asarray(seq)))
    theirs = np.asarray(glm_dsa.served_selections(
        exact, params, r["prompt"], r["tokens"], pad_to)[0])
    return {"positions": int(theirs.sum()),
            "agree_share": float((ours & theirs).sum() / theirs.sum()),
            "queries_with_a_swap": int((ours != theirs).any(-1).sum()),
            "queries": int(theirs.shape[0])}


def main(cell_name, seconds, seeds):
    import numpy as np
    bench = harness.Bench(ROOT)
    cell = bench.cell(cell_name)
    harness.find_device(cell["chips"])
    harness.arm_compile_cache()
    cfg = bench.config(cell["config"])
    tr = traffic.load(bench.find("traffic", cell["traffic"]))
    m = cfg["model"]
    pad_to = serve_sparse_moe.pad_to(tr)
    out_path = os.path.join(ROOT, "chiprun_out",
                            f"calibrate_{cell_name}.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    off = tracing.Tracer(False, None)
    exact = glm_dsa.make_forward(m)
    params = weights_glm.glm_params(m, seeds[0][0])
    eng, model = serve_sparse_moe.build(cfg, params)
    try:
        for i, (seed, controls) in enumerate(seeds):
            if i:
                # two trees of 7.8 GB do not fit beside the cache: the
                # idle engine holds none while the next one is made
                model.params = params = None
                gc.collect()
                params = weights_glm.glm_params(m, seed)
                model.params = params
            d = drive(eng, tr, seed, m["vocab"], seconds, off)
            ok = [r for r in d["records"] if r["tokens"] is not None]
            picked = sample(tr, seed, ok)
            rows = [glm_dsa.served_rows(exact, params, r["prompt"],
                                        r["tokens"], pad_to) for r in picked]
            gaps = np.concatenate([
                glm_dsa.gaps_below_best(exact, params, at, r["tokens"])
                for at, r in zip(rows, picked)])
            row = {"seed": seed, "finished": len(ok),
                   "failed": len(d["records"]) - len(ok),
                   "tokens_checked": int(gaps.size),
                   "retraces": eng.retraces_after_warmup(),
                   "program": reading(gaps, cfg["limits"]),
                   "select_agreement": [
                       select_agreement(m, params, exact, r, pad_to)
                       for r in picked]}
            for control in controls:
                judge = glm_dsa.make_forward(m, control)
                low = np.concatenate([
                    glm_dsa.gaps_below_best(
                        exact, params, at, glm_dsa.first_choices(
                            judge, params, glm_dsa.served_rows(
                                judge, params, r["prompt"], r["tokens"],
                                pad_to)))
                    for at, r in zip(rows, picked)])
                row[control] = reading(low, cfg["limits"])
                del judge
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
