"""Offender attribution reports: rank a compiled step's fusions.

`inspect_step(obj, *args)` lowers+compiles whatever it is handed — a
`gluon.contrib.FusedTrainStep`, a `deploy.ExportedModel` bucket program, a
bare `jax.jit` function, or an already lowered/compiled stage — walks the
optimized HLO through `roofline.analyze_compiled`, and produces the ranked
work-list the Pallas-kernel tier consumes ("worst offenders") at two
granularities:

  offenders        individual kernel units (fusions/dots/convs), ranked by
                   estimated time share — "which launch is slow";
  offender_groups  fusion CLASSES: units aggregated under their
                   de-instanced HLO name (`multiply_multiply_fusion.18
                   .clone` -> `multiply_multiply_fusion` — XLA names a
                   fusion after its constituent ops, so same pattern
                   across 20 ResNet layers = one class). A custom kernel
                   replaces a *class*, so this is the actionable ranking
                   and the one the coverage number is taken over.

Summary scalars of a report:

  offender_top1_share       est. time share of the worst fusion class
  memory_bound_byte_share   fraction of step bytes in memory-bound units
  est_step_mfu_ceiling      total flops / (sum of roofline unit times x
                            peak flops) — the MFU the CURRENT fusion
                            structure could reach if every unit hit its
                            roofline bound; the honest target for kernel
                            work

With an `execute=` callback the report also carries the wall-clock time
of real executions (`measured_wall_ms`); its shares stay cost-model
estimates. Device time per program and per kernel comes from a traced
run of the benchmark (`chipbench`, PERF.md), not from here.
"""
from __future__ import annotations

import json
import os
import re

from ..base import MXNetError, get_env, _register_env
from ..telemetry import REGISTRY, span
from . import roofline as _roofline

__all__ = ["inspect_step", "inspect_compiled", "render_markdown",
           "lower_any", "class_name", "INSPECT_RUNS", "INSPECT_UNITS"]

_register_env("MXNET_INSPECT_TOP_K", int, 10,
              "Offender-report depth: fusions listed by tools/offenders.py "
              "(totals always cover the whole module)")
_register_env("MXNET_INSPECT_CALIB", str, None,
              "Path to a roofline calibration JSON overriding the "
              "platform's peak table (see tools/bandwidth.py --calib)")

# inspection runs land in the registry so dashboards see profiling activity
INSPECT_RUNS = REGISTRY.counter(
    "inspect.runs", help="offender-attribution analyses performed")
INSPECT_UNITS = REGISTRY.counter(
    "inspect.units", help="kernel units (fusions/dots/convs) analyzed")
_TOP1 = REGISTRY.gauge(
    "inspect.top1_share", help="est. time share of the worst fusion in "
    "the most recent inspection")
_MEM_BYTES = REGISTRY.gauge(
    "inspect.memory_bound_byte_share", help="byte share in memory-bound "
    "units in the most recent inspection")
_MFU_CEIL = REGISTRY.gauge(
    "inspect.mfu_ceiling", help="roofline MFU ceiling of the most recent "
    "inspected program")


def lower_any(obj, *args):
    """Lower+compile any inspectable object to a `jax.stages.Compiled`.

    Accepts: FusedTrainStep / FusedInferStep (via `.lowered(*args)`),
    deploy.ExportedModel (via `.lowered()`), jitted functions and
    `jax.stages.Lowered` (via `.lower(...)`/`.compile()`), and
    already-compiled stages (pass-through)."""
    if hasattr(obj, "lowered"):                      # our framework objects
        lowered = obj.lowered(*args)
        return lowered.compile()
    # order matters below: jax.stages.Lowered also exposes as_text() +
    # cost_analysis(), but its text is pre-optimization StableHLO the
    # parser cannot use — anything still compilable must compile first
    if hasattr(obj, "compile") and not hasattr(obj, "lower"):
        return obj.compile()                         # jax.stages.Lowered
    if hasattr(obj, "lower"):                        # jitted callable
        return obj.lower(*args).compile()
    if hasattr(obj, "as_text") and hasattr(obj, "cost_analysis"):
        return obj                                   # already Compiled
    if callable(obj):
        import jax
        return jax.jit(obj).lower(*args).compile()
    raise MXNetError(
        f"don't know how to lower {type(obj).__name__} for inspection: "
        "pass a FusedTrainStep, ExportedModel, jitted function, or a "
        "lowered/compiled stage")


def inspect_step(obj, *args, name=None, top_k=None, calib=None,
                 execute=None):
    """Offender report for one compiled step. See module docstring.

    `execute`: zero-arg callable running the program once on real buffers;
    adds `measured_wall_ms`."""
    compiled = lower_any(obj, *args)
    return inspect_compiled(compiled, name=name or _name_of(obj),
                            top_k=top_k, calib=calib, execute=execute)


def _name_of(obj):
    n = type(obj).__name__
    return getattr(obj, "__name__", n)


def inspect_compiled(compiled, name="step", top_k=None, calib=None,
                     execute=None):
    """Report dict for an already compiled stage (json.dumps-safe)."""
    if top_k is None:
        top_k = get_env("MXNET_INSPECT_TOP_K", 10, typ=int)
    if calib is None:
        calib = _roofline.load_calibration()
    with span("inspect.analyze", target=name):
        records, totals, _module = _roofline.analyze_compiled(
            compiled, calib=calib)
        ca = _roofline.cost_analysis_summary(compiled)
        # the memory side of the same program, right next to the roofline
        # ranking: predicted peak HBM + argument/output/temp/alias split
        # (inspect/memory.py; degrades per its own contract, never raises)
        from . import memory as _memory
        memplan = _memory.plan_from_compiled(compiled, name=name)
    # degradation contract: no byte estimates anywhere (shape parse failed
    # AND cost analysis silent) -> flops-only ranking, flagged, no crash
    have_bytes = totals["bytes"] > 0 or ca["bytes_estimated"]
    if not have_bytes:
        records.sort(key=lambda r: r["flops"], reverse=True)
    groups = _group_records(records, have_bytes,
                            calib["ridge_flop_per_byte"])
    report = {
        "name": name,
        "platform": _platform(),
        "n_units": totals["units"],
        "top_k": top_k,
        "ranking": "est_time" if have_bytes else "flops_only",
        "bytes_estimated": have_bytes,
        "calibration": {
            "peak_flops": calib["peak_flops"],
            "peak_bytes_per_sec": calib["peak_bytes_per_sec"],
            "ridge_flop_per_byte": calib["ridge_flop_per_byte"],
            "source": calib.get("source", "unknown"),
        },
        "totals": totals,
        "cost_analysis": ca,
        "memory": memplan,
        "offenders": records[:top_k],
        "n_groups": len(groups),
        "offender_groups": groups[:top_k],
        "offender_top1_share": (groups[0]["time_share"]
                                if groups else 0.0),
        "memory_bound_byte_share": totals["memory_bound_byte_share"],
        "est_step_mfu_ceiling": _mfu_ceiling(totals, calib),
        "top10_byte_coverage": _byte_coverage(groups, 10, totals),
        "topk_byte_coverage": _byte_coverage(groups, top_k, totals),
        "topk_time_coverage": round(
            sum(g["time_share"] for g in groups[:top_k]), 6),
    }
    if ca["flops"] is not None and totals["flops"] > 0:
        report["model_vs_xla_flops"] = round(
            totals["flops"] / ca["flops"], 4) if ca["flops"] else None
    if execute is not None:
        report["measured_wall_ms"] = _wall_ms(execute)
    INSPECT_RUNS.inc()
    INSPECT_UNITS.inc(totals["units"])
    _TOP1.set(report["offender_top1_share"])
    _MEM_BYTES.set(report["memory_bound_byte_share"])
    _MFU_CEIL.set(report["est_step_mfu_ceiling"])
    return report


_INSTANCE_RE = re.compile(r"\.(clone|remat|\d+)")


def class_name(instr_name):
    """De-instanced fusion-class name: XLA names a fusion after its
    constituent ops and suffixes instances with `.N`/`.clone`/`.remat`,
    so stripping those folds the same pattern across layers into one
    class (`multiply_multiply_fusion.18.clone` ->
    `multiply_multiply_fusion`)."""
    return _INSTANCE_RE.sub("", instr_name)


def _group_records(records, have_bytes, ridge):
    """Aggregate unit records into ranked fusion-class groups."""
    groups = {}
    for r in records:
        cls = class_name(r["name"])
        g = groups.get(cls)
        if g is None:
            g = groups[cls] = {
                "class": cls, "opcode": r["opcode"], "count": 0,
                "flops": 0.0, "bytes": 0.0, "transcendentals": 0.0,
                "est_time_s": 0.0, "example": r["name"],
                "example_op_name": r["op_name"],
            }
        g["count"] += 1
        g["flops"] += r["flops"]
        g["bytes"] += r["bytes"]
        g["transcendentals"] += r["transcendentals"]
        g["est_time_s"] += r["est_time_s"]
    out = list(groups.values())
    total_time = sum(g["est_time_s"] for g in out) or 1.0
    for g in out:
        intensity = (g["flops"] / g["bytes"]) if g["bytes"] \
            else float("inf")
        g["intensity"] = (round(intensity, 4)
                          if intensity != float("inf") else None)
        g["bound"] = "compute" if intensity >= ridge else "memory"
        g["time_share"] = round(g["est_time_s"] / total_time, 6)
    out.sort(key=lambda g: (g["est_time_s"] if have_bytes
                            else g["flops"]), reverse=True)
    return out


def _platform():
    return _roofline._ambient_platform(default="unknown")


def _mfu_ceiling(totals, calib):
    """MFU if every unit ran exactly at its roofline bound: the ceiling
    the CURRENT fusion structure imposes. 0 when the module has no
    modelled flops (degenerate/opaque programs)."""
    t = totals["est_time_s"]
    if not t or not totals["flops"]:
        return 0.0
    return round(totals["flops"] / t / float(calib["peak_flops"]), 6)


def _byte_coverage(records, k, totals):
    if not totals["bytes"]:
        return 0.0
    return round(sum(r["bytes"] for r in records[:k]) / totals["bytes"], 6)


def _wall_ms(execute, reps=3):
    """Wall-clock ms per execution, the first (compiling) one left out."""
    import time as _time
    execute()
    t0 = _time.perf_counter()
    for _ in range(reps):
        execute()
    return round((_time.perf_counter() - t0) / reps * 1e3, 3)


def render_markdown(report):
    """Human-readable offender table (what `tools/offenders.py` prints)."""
    lines = []
    cal = report["calibration"]
    lines.append(f"# Offender attribution — {report['name']} "
                 f"({report['platform']})")
    lines.append("")
    lines.append(
        f"Roofline: peak {cal['peak_flops'] / 1e12:.1f} TFLOP/s, "
        f"{cal['peak_bytes_per_sec'] / 1e9:.1f} GB/s "
        f"(ridge {cal['ridge_flop_per_byte']:.1f} FLOP/B, "
        f"calibration: {cal['source']})")
    t = report["totals"]
    lines.append(
        f"Program: {t['units']} kernel units, "
        f"{t['flops'] / 1e9:.2f} GFLOP, {t['bytes'] / 1e6:.2f} MB moved, "
        f"{t['memory_bound_units']} memory-bound units "
        f"({report['memory_bound_byte_share'] * 100:.1f}% of bytes)")
    lines.append(
        f"MFU ceiling for this fusion structure: "
        f"{report['est_step_mfu_ceiling']:.3f}  |  top-1 class share: "
        f"{report['offender_top1_share'] * 100:.1f}%")
    lines.append("")
    lines.append(f"## Offender classes ({report['n_groups']} total)")
    lines.append("")
    lines.append("| # | fusion class | op | n | bound | GFLOP | MB | "
                 "FLOP/B | time share |")
    lines.append("|---|---|---|---|---|---|---|---|---|")
    for i, g in enumerate(report["offender_groups"], 1):
        inten = ("inf" if g["intensity"] is None
                 else f"{g['intensity']:.1f}")
        lines.append(
            f"| {i} | `{g['class']}` | {g['opcode']} | {g['count']} | "
            f"{g['bound']} | {g['flops'] / 1e9:.3f} | "
            f"{g['bytes'] / 1e6:.3f} | {inten} | "
            f"{g['time_share'] * 100:.1f}% |")
    lines.append("")
    lines.append(
        f"Top-{report['top_k']} classes cover "
        f"{report['topk_time_coverage'] * 100:.1f}% of estimated time, "
        f"{report['topk_byte_coverage'] * 100:.1f}% of bytes "
        f"(top-10: {report['top10_byte_coverage'] * 100:.1f}%).")
    lines.append("")
    lines.append("## Worst individual kernel units")
    lines.append("")
    lines.append("| # | unit | op | bound | GFLOP | MB | FLOP/B | "
                 "time share | source op |")
    lines.append("|---|---|---|---|---|---|---|---|---|")
    for i, r in enumerate(report["offenders"], 1):
        inten = ("inf" if r["intensity"] is None
                 else f"{r['intensity']:.1f}")
        src = (r["op_name"] or "")[-48:]
        lines.append(
            f"| {i} | `{r['name']}` | {r['opcode']} | {r['bound']} | "
            f"{r['flops'] / 1e9:.3f} | {r['bytes'] / 1e6:.3f} | {inten} | "
            f"{r['time_share'] * 100:.1f}% | `{src}` |")
    return "\n".join(lines)


def inspect_hlo_text(text, name="module", top_k=None, calib=None):
    """Offline path: analyze a saved HLO dump (no jax, no backend)."""
    class _Precompiled:
        def as_text(self):
            return text

        def cost_analysis(self):
            raise RuntimeError("offline HLO text carries no cost analysis")

    return inspect_compiled(_Precompiled(), name=name, top_k=top_k,
                            calib=calib)


def dump_json(report, path):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
