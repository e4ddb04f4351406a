#!/usr/bin/env python3
"""Readings that the train cell's limits are set from, on the chip at the
cell's own size, in one process:

  every seed   the program's first three steps against the reference
               (the lower readings);
  first 3      the control (the reference in fp8) and the fault 'half of
               the batch left out' planted in the reference, against the
               reference (the upper readings); and for the look into the
               cause: the reference with bf16 operands, and with the
               program's one-pass variance.

    python3 chipbench/tests/calibrate_train.py <cell> <seed> [<seed> ...]
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import checks, harness, traffic  # noqa: E402
from chipbench.paths import train_fused  # noqa: E402


def leaf_table(got, want, names):
    """Per-leaf norm gaps, as checks.training measures them."""
    import numpy as np
    g, w = checks.leaf_norms(got, names), checks.leaf_norms(want, names)
    gap = np.abs(g - w) / np.maximum(w, np.median(w))
    order = np.argsort(-gap)
    return {"worst": float(gap.max()), "p90": float(np.quantile(gap, 0.9)),
            "median": float(np.median(gap)),
            "worst_leaves": [(names[i], float(gap[i]), float(g[i]),
                              float(w[i])) for i in order[:4]]}


def main(cell_name, seeds):
    import jax
    bench = harness.Bench(ROOT)
    cell = bench.cell(cell_name)
    dev = harness.find_device(cell["chips"])
    harness.arm_compile_cache()
    cfg = bench.config(cell["config"])
    tr = traffic.load(bench.find("traffic", cell["traffic"]))
    out_path = os.path.join(ROOT, "chiprun_out",
                            f"calibrate_{cell_name}.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    for i, seed in enumerate(seeds):
        p = train_fused.Program(cfg, tr, seed)
        try:
            losses, grad, after = p.first_steps()
            p.drain()
            pool, names = p.pool, p.names
            stats = dev.memory_stats()
        finally:
            p.close()
        ref = train_fused.reference_steps(cfg, seed, pool, names)
        w0 = ref[3]
        row = {"seed": seed, "losses": losses, "ref_losses": ref[0],
               "memory_stats": stats,
               "loss_gaps": checks.loss_gaps(losses, ref[0]),
               "program": checks.training(
                   losses, grad, {n: after[n] - w0[n] for n in names},
                   *ref[:3]),
               "program_grad": leaf_table(grad, ref[1], names),
               "program_change": leaf_table(
                   {n: after[n] - w0[n] for n in names}, ref[2], names)}
        if i < 3:
            for tag, how in (("control_fp8", {"precision": "fp8"}),
                             ("fault_half_batch",
                              {"rows": slice(0, tr["batch"] // 2)}),
                             ("look_bf16", {"precision": "bfloat16"})):
                alt = train_fused.reference_steps(cfg, seed, pool, names,
                                                  **how)
                row[tag] = checks.training(*alt[:3], *ref[:3])
                row[tag + "_loss_gaps"] = checks.loss_gaps(alt[0], ref[0])
                row[tag + "_grad"] = leaf_table(alt[1], ref[1], names)
                row[tag + "_change"] = leaf_table(alt[2], ref[2], names)
        print(json.dumps(row), flush=True)
        with open(out_path, "a") as f:
            f.write(json.dumps(row) + "\n")
    jax.clear_caches()


if __name__ == "__main__":
    main(sys.argv[1], [int(s) for s in sys.argv[2:]])
