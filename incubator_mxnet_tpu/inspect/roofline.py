"""Roofline cost model over parsed HLO: per-fusion flops, bytes, intensity.

The MFU push (ROADMAP item 2) needs to know *which* fused regions are
memory-bound. XLA's `Compiled.cost_analysis()` answers only in aggregate
(total flops / total "bytes accessed"), so this module walks the optimized
module's kernel units — fusions, dots, convolutions, reduces, custom calls —
and models each one:

  flops       dot/conv from contraction shapes (MAC = 2, the chip-spec
              convention every MFU number in this repo already uses),
              elementwise = one flop per output element, reduce = input
              elements; fusions sum their called computation.
  bytes       the fusion BOUNDARY traffic: unique operand buffers read +
              output buffers written. Inner intermediates live in
              registers/vmem — that is the whole point of fusion — so the
              boundary is the HBM story.
  intensity   flops / bytes (arithmetic intensity, FLOP/B).
  class       compute-bound when intensity >= ridge point
              (peak_flops / peak_bytes_per_sec), memory-bound below it.
  est_time_s  max(flops / peak_flops, bytes / peak_bw) — the roofline
              execution-time estimate used to rank offenders.

Peaks come from a calibration file that the caller or `MXNET_INSPECT_CALIB`
names (written by `tools/bandwidth.py --calib`), so the ridge point tracks
the attached hardware; the package reads no file of its own accord. With
no calibration, a TPU v5e takes its published bf16 peak
(`telemetry.device_peak_flops`) and HBM bandwidth; any other TPU
`device_kind` is an error, not a default, because only the
v5e's bandwidth is recorded here. The CPU takes a modest fixed row.
"""
from __future__ import annotations

import json

from ..base import MXNetError, get_env
from . import hlo as _hlo

__all__ = ["instr_flops", "unit_cost", "kernel_units", "analyze_module",
           "analyze_compiled", "load_calibration", "classify",
           "cost_analysis_summary", "callable_cost",
           "DEFAULT_CALIBRATIONS"]

# fallback when no calibration is named and the platform is not a TPU:
# deliberately modest figures so CPU-only smoke runs classify sanely. A
# TPU's fallback is its published spec (`_tpu_spec_calibration`).
DEFAULT_CALIBRATIONS = {
    "cpu": {"peak_flops": 1.0e11, "peak_bytes_per_sec": 20e9,
            "source": "spec-fallback"},
}

# HBM bandwidth of one TPU v5e chip (Google Cloud documentation, "TPU
# v5e") — the one TPU this repo runs on today; the benchmark's peaks table
# (ROADMAP S1) takes this over, keyed by device_kind like the FLOP/s.
_TPU_V5E_HBM_BYTES_PER_SEC = 819e9
_TPU_V5E_KINDS = ("v5 lite", "v5e")


def _tpu_spec_calibration():
    import jax
    from ..telemetry.steptrace import device_peak_flops
    kind = getattr(jax.devices()[0], "device_kind", "")
    peak = device_peak_flops()
    if peak is None or not any(k in kind.lower() for k in _TPU_V5E_KINDS):
        # the FLOP/s table knows other TPU kinds; the bandwidth here is
        # the v5e's alone, and a ridge point from a mixed pair is wrong
        raise MXNetError(
            f"no published peak FLOP/s and HBM bandwidth for TPU "
            f"device_kind {kind!r} (only the v5e's are recorded), and no "
            "roofline calibration: pass one (path= / MXNET_INSPECT_CALIB) "
            "— a peak is never guessed")
    return {"peak_flops": peak,
            "peak_bytes_per_sec": _TPU_V5E_HBM_BYTES_PER_SEC,
            "source": "published-spec"}


# opcodes that move/relabel data without arithmetic: zero flops, and when
# they appear standalone (outside a fusion) they are pure-bandwidth units
_ZERO_FLOP = frozenset((
    "parameter", "constant", "iota", "copy", "copy-start", "copy-done",
    "bitcast", "bitcast-convert", "reshape", "transpose", "broadcast",
    "tuple", "get-tuple-element", "slice", "dynamic-slice",
    "dynamic-update-slice", "concatenate", "pad", "reverse", "gather",
    "after-all", "partition-id", "replica-id", "rng-get-and-update-state",
    "convert", "real", "imag", "infeed", "outfeed", "send", "recv",
    "send-done", "recv-done", "domain", "opt-barrier",
))

# one flop per output element (comparisons/selects count like the
# reference profiler counted them: a lane op is a lane op)
_ELEMENTWISE = frozenset((
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "and", "or", "xor", "not", "negate", "abs", "sign", "compare",
    "select", "clamp", "floor", "ceil", "round-nearest-afz",
    "round-nearest-even", "shift-left", "shift-right-arithmetic",
    "shift-right-logical", "remainder", "is-finite", "popcnt", "clz",
    "stochastic-convert", "map",
))

# transcendental lanes: still one flop per element in the MAC=2 accounting
# (matching XLA's own cost analysis, which counts them separately under
# "transcendentals"), tracked so the report can show them
_TRANSCENDENTAL = frozenset((
    "exponential", "exponential-minus-one", "log", "log-plus-one", "tanh",
    "sqrt", "rsqrt", "cbrt", "power", "sine", "cosine", "tan", "atan2",
    "logistic", "erf", "expm1", "log1p",
))


def _prod(dims):
    n = 1
    for d in dims:
        n *= d
    return n


def instr_flops(instr, module=None):
    """Modelled FLOPs of one instruction (MAC = 2 for dot/conv). Fusions,
    calls, and while loops recurse into their called computations (while
    bodies count ONCE — scan trip counts are not in the HLO text; the
    caller decides whether to scale)."""
    op = instr.opcode
    if op in _ZERO_FLOP:
        return 0.0
    if op == "dot":
        out = instr.out_elements
        lhs = instr.operand_shapes[0] if instr.operand_shapes else None
        contract = 1
        if lhs and not isinstance(lhs, list):
            for d in instr.dims_attr("lhs_contracting_dims"):
                if d < len(lhs[1]):
                    contract *= lhs[1][d]
        return 2.0 * out * contract
    if op == "convolution":
        return _conv_flops(instr)
    if op in ("reduce", "reduce-window", "select-and-scatter"):
        # ~one reducer application per input element (window ops touch
        # each input element once per covering window; stride==size for
        # the pooling shapes we care about)
        in_elems = sum(_hlo.num_elements(s)
                       for s in instr.operand_shapes[:1])
        return float(max(in_elems, instr.out_elements))
    if op in ("scatter",):
        return float(instr.out_elements)
    if op in ("rng", "rng-bit-generator"):
        return float(instr.out_elements)
    if op in ("fusion", "call", "async-start"):
        return _called_flops(instr, module)
    if op == "while":
        return _called_flops(instr, module)
    if op == "conditional":
        return _called_flops(instr, module)
    if op == "custom-call":
        return 0.0       # opaque: bytes still counted, flops unknowable
    if op in _ELEMENTWISE or op in _TRANSCENDENTAL:
        return float(instr.out_elements)
    # unknown opcode: assume one lane op per output element rather than
    # silently dropping it from the model
    return float(instr.out_elements)


def _called_flops(instr, module):
    if module is None:
        return 0.0
    total = 0.0
    for cname in instr.called:
        comp = module.computation(cname)
        if comp is None:
            continue
        for inner in comp.instructions:
            total += instr_flops(inner, module)
    return total


def _conv_flops(instr):
    """2 * output elements * (kernel spatial taps * input channels):
    kernel shape is operand 1; its output-feature dim comes from
    `dim_labels` (`b01f_01io->b01f` -> kernel layout `01io`, 'o' at
    position 3); feature groups divide the per-output input channels —
    the kernel shape already reflects that, so flops are simply
    2 * out * prod(kernel) / kernel_out_channels."""
    out = instr.out_elements
    if len(instr.operand_shapes) < 2:
        return 2.0 * out
    ker = instr.operand_shapes[1]
    if ker is None or isinstance(ker, list):
        return 2.0 * out
    kdims = ker[1]
    labels = instr.dim_labels
    out_ch = None
    if labels:
        try:
            kpart = labels.split("_")[1].split("-")[0]
            out_ch = kdims[kpart.index("o")]
        except (IndexError, ValueError):
            out_ch = None
    if out_ch is None:
        out_ch = kdims[-1] if kdims else 1
    return 2.0 * out * (_prod(kdims) / max(out_ch, 1))


def instr_transcendentals(instr, module=None):
    """Transcendental lane count (reported, not added to flops twice)."""
    op = instr.opcode
    if op in _TRANSCENDENTAL:
        return float(instr.out_elements)
    if op in ("fusion", "call", "while", "conditional"):
        total = 0.0
        if module is not None:
            for cname in instr.called:
                comp = module.computation(cname)
                if comp is None:
                    continue
                for inner in comp.instructions:
                    total += instr_transcendentals(inner, module)
        return total
    return 0.0


def unit_cost(instr, module=None):
    """Boundary cost of one kernel unit: flops (modelled), bytes (unique
    operand buffers read + output written), transcendentals."""
    seen = set()
    in_bytes = 0
    for name, shape in zip(instr.operands, instr.operand_shapes):
        if name in seen:      # the same buffer read twice is one read
            continue
        seen.add(name)
        in_bytes += _hlo.shape_bytes(shape)
    out_bytes = instr.out_bytes
    flops = instr_flops(instr, module)
    return {"flops": flops, "bytes": float(in_bytes + out_bytes),
            "in_bytes": float(in_bytes), "out_bytes": float(out_bytes),
            "transcendentals": instr_transcendentals(instr, module)}


# kernel units: instructions that map onto device kernel launches. A
# standalone zero-flop op (big copy/transpose outside any fusion) is still
# a unit — it moves bytes — but parameters/constants/tuples are free.
_NON_UNITS = frozenset((
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "iota", "partition-id", "replica-id", "domain",
    "opt-barrier", "get-dimension-size",
))


def kernel_units(module, computation=None, _seen=None):
    """Top-level kernel units of a computation (default: entry),
    transparently descending through `call` wrappers (the CPU backend
    wraps each fusion in a parallel-call shim) and while/conditional
    bodies (counted once; scan trip counts are not in the HLO)."""
    comp = computation or module.entry
    if comp is None:
        return []
    if _seen is None:
        _seen = set()
    if comp.name in _seen:
        return []
    _seen.add(comp.name)
    units = []
    for ins in comp.instructions:
        if ins.opcode in ("call", "while", "conditional"):
            for cname in ins.called:
                sub = module.computation(cname)
                if sub is not None:
                    units.extend(kernel_units(module, sub, _seen))
            continue
        if ins.opcode in _NON_UNITS:
            continue
        units.append(ins)
    return units


def classify(intensity, ridge):
    """'compute' above the ridge point (FLOP/B), 'memory' below it."""
    return "compute" if intensity >= ridge else "memory"


def load_calibration(path=None, platform=None):
    """Resolve the roofline peaks: explicit path > MXNET_INSPECT_CALIB >
    the platform's table (a TPU's published spec, which raises for a
    device_kind it does not know; the fixed CPU row otherwise). A named
    file that cannot be read or lacks a peak is passed over. Returns a dict
    with at least `peak_flops`, `peak_bytes_per_sec`, `ridge_flop_per_byte`,
    `source`."""
    if platform is None:
        platform = _ambient_platform()
    calib = None
    for cand in (path, get_env("MXNET_INSPECT_CALIB", None, typ=str)):
        if not cand:
            continue
        try:
            with open(cand) as f:
                data = json.load(f)
        except (OSError, ValueError):
            continue
        if not (data.get("peak_flops") and data.get("peak_bytes_per_sec")):
            continue
        calib = dict(data)
        calib.setdefault("source", cand)
        break
    if calib is None:
        calib = (_tpu_spec_calibration() if platform == "tpu"
                 else dict(DEFAULT_CALIBRATIONS["cpu"]))
    calib["ridge_flop_per_byte"] = (
        float(calib["peak_flops"]) / float(calib["peak_bytes_per_sec"]))
    return calib


def _ambient_platform(default="cpu"):
    try:
        import jax
        return jax.devices()[0].platform
    except Exception:
        return default


def analyze_module(module, calib=None):
    """Roofline records for every kernel unit of a parsed module, ranked
    by estimated time share (descending). Returns (records, totals)."""
    if calib is None:
        calib = load_calibration()
    peak_f = float(calib["peak_flops"])
    peak_b = float(calib["peak_bytes_per_sec"])
    ridge = peak_f / peak_b
    records = []
    for ins in kernel_units(module):
        cost = unit_cost(ins, module)
        flops, nbytes = cost["flops"], cost["bytes"]
        intensity = flops / nbytes if nbytes else float("inf")
        t_flops = flops / peak_f
        t_bytes = nbytes / peak_b
        records.append({
            "name": ins.name,
            "opcode": ins.opcode,
            "op_name": ins.op_name,
            "flops": flops,
            "bytes": nbytes,
            "in_bytes": cost["in_bytes"],
            "out_bytes": cost["out_bytes"],
            "transcendentals": cost["transcendentals"],
            "intensity": round(intensity, 4)
            if intensity != float("inf") else None,
            "bound": classify(intensity, ridge),
            "est_time_s": max(t_flops, t_bytes),
            "est_time_flops_s": t_flops,
            "est_time_bytes_s": t_bytes,
        })
    total_time = sum(r["est_time_s"] for r in records) or 1.0
    for r in records:
        r["time_share"] = round(r["est_time_s"] / total_time, 6)
    records.sort(key=lambda r: r["est_time_s"], reverse=True)
    totals = {
        "units": len(records),
        "flops": sum(r["flops"] for r in records),
        "bytes": sum(r["bytes"] for r in records),
        "est_time_s": sum(r["est_time_s"] for r in records),
        "memory_bound_units": sum(1 for r in records
                                  if r["bound"] == "memory"),
        "memory_bound_byte_share": round(
            sum(r["bytes"] for r in records if r["bound"] == "memory")
            / max(sum(r["bytes"] for r in records), 1.0), 6),
        "ridge_flop_per_byte": round(ridge, 3),
    }
    return records, totals


def analyze_compiled(compiled, calib=None):
    """`jax.stages.Compiled` (or anything with `.as_text()`) -> (records,
    totals, module)."""
    module = _hlo.parse_module(compiled.as_text())
    records, totals = analyze_module(module, calib=calib)
    return records, totals, module


# ---------------------------------------------------------------------------
# aggregate cost-analysis access with the degradation contract: backends
# whose cost_analysis() lacks bytes-accessed keys (or raises outright) must
# yield a usable flops-only summary, never a crash.
# ---------------------------------------------------------------------------

def cost_analysis_summary(compiled):
    """{'flops', 'bytes_accessed', 'bytes_estimated'} from
    `compiled.cost_analysis()`. `bytes_estimated` is True iff the
    bytes-accessed figure came from XLA itself; when the key is absent or
    the call raises, `bytes_accessed` is None and `bytes_estimated` is
    False — callers degrade to flops-only ranking."""
    out = {"flops": None, "bytes_accessed": None, "bytes_estimated": False}
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return out
    if not ca:
        return out
    try:
        if "flops" in ca:
            out["flops"] = float(ca["flops"])
        if "bytes accessed" in ca:
            out["bytes_accessed"] = float(ca["bytes accessed"])
            out["bytes_estimated"] = True
    except (TypeError, ValueError):
        pass
    return out


def callable_cost(fn, *args, calib=None):
    """Estimated cost of one execution of `fn(*args)` for the per-op
    tables (tools/opperf.py): flops + bytes + arithmetic intensity +
    roofline class. Prefers XLA's own cost analysis; falls back to the
    HLO shape model for bytes when the backend does not report them
    (`bytes_source: "hlo-model"`), and to the HLO model for flops when
    cost analysis is entirely absent (`flops_source: "hlo-model"`).
    An already-jitted `fn` is lowered directly, so a caller that timed
    `jax.jit(op)` hits the jit cache instead of recompiling."""
    import jax
    if calib is None:
        calib = load_calibration()
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    compiled = jitted.lower(*args).compile()
    summary = cost_analysis_summary(compiled)
    flops, bytes_ = summary["flops"], summary["bytes_accessed"]
    flops_source = "xla-cost-analysis" if flops is not None else None
    bytes_source = "xla-cost-analysis" if bytes_ is not None else None
    if flops is None or bytes_ is None:
        try:
            _, totals, _ = analyze_compiled(compiled, calib=calib)
        except Exception:
            totals = None
        if totals is not None:
            if flops is None:
                flops, flops_source = totals["flops"], "hlo-model"
            if bytes_ is None:
                bytes_, bytes_source = totals["bytes"], "hlo-model"
    out = {"est_flops": flops, "est_bytes": bytes_,
           "flops_source": flops_source, "bytes_source": bytes_source,
           "bytes_estimated": bytes_source is not None}
    if flops is not None and bytes_:
        intensity = flops / bytes_
        out["intensity"] = round(intensity, 4)
        out["bound"] = classify(intensity, calib["ridge_flop_per_byte"])
    else:
        out["intensity"] = None
        out["bound"] = None
    return out
