"""mx.inspect — HLO roofline profiler and fusion-level offender attribution.

The XLA-era answer to the reference profiler's per-engine-op attribution
(PAPER.md layers 4-6): lower+compile any jitted step — `FusedTrainStep`,
`deploy.ExportedModel` bucket programs, bare `jax.jit` functions — walk the
optimized module's fusions, model each one's flops / bytes / arithmetic
intensity, classify compute- vs memory-bound against calibrated peaks, and
rank offenders by estimated time share:

    from incubator_mxnet_tpu import inspect as mxinspect
    report = mxinspect.inspect_step(step, x, y)   # FusedTrainStep + batch
    print(mxinspect.render_markdown(report))

CLI: `python tools/offenders.py --model resnet18 --json out.json`.
Calibration: `python tools/bandwidth.py --calib` writes a file for
`MXNET_INSPECT_CALIB` (see docs/PERF.md). Knobs:
`MXNET_INSPECT_TOP_K`, `MXNET_INSPECT_CALIB`.
Catalog of the `inspect.*` registry metrics: docs/OBSERVABILITY.md.
"""
from __future__ import annotations

from .hlo import (HloInstruction, HloComputation, HloModule, parse_module,
                  parse_shape, shape_bytes, scope_of, scope_table)
from .roofline import (analyze_compiled, analyze_module, callable_cost,
                       classify, cost_analysis_summary, instr_flops,
                       kernel_units, load_calibration, unit_cost)
from .report import (inspect_step, inspect_compiled, inspect_hlo_text,
                     render_markdown, lower_any, class_name, dump_json)
from .memory import (memory_plan, plan_from_compiled, assert_donation,
                     collective_memory_plans, active_plans, note_plan,
                     tag, register, current_tag, census, census_diff,
                     leakcheck, MemoryLeakError,
                     is_oom_error, on_oom, oom_report, dump_oom,
                     install_oom_hook)

__all__ = [
    "HloInstruction", "HloComputation", "HloModule", "parse_module",
    "parse_shape", "shape_bytes", "scope_of", "scope_table",
    "analyze_compiled", "analyze_module", "callable_cost", "classify",
    "cost_analysis_summary", "instr_flops", "kernel_units",
    "load_calibration", "unit_cost",
    "inspect_step", "inspect_compiled", "inspect_hlo_text",
    "render_markdown", "lower_any", "class_name", "dump_json",
    "memory_plan", "plan_from_compiled", "assert_donation",
    "collective_memory_plans", "active_plans", "note_plan",
    "tag", "register", "current_tag", "census", "census_diff",
    "leakcheck", "MemoryLeakError",
    "is_oom_error", "on_oom", "oom_report", "dump_oom",
    "install_oom_hook",
]
