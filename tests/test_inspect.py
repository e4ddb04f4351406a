"""mx.inspect — HLO roofline profiler and fusion-level offender attribution
(ISSUE 7).

Covers: the HLO text parser on handwritten modules (fusion flops summed
from called computations, dot/conv contraction formulas, boundary-byte
dedup), kernel-unit discovery through call/while wrappers, calibration
resolution (explicit path > MXNET_INSPECT_CALIB > the platform's table),
the cost-analysis degradation contract
(missing bytes keys / raising backends -> flops-only ranking, never a
crash), inspection of every framework surface (jitted fn, FusedTrainStep,
FusedInferStep, deploy.ExportedModel), fusion-class grouping + coverage,
the wall-clock callback, the registry metrics, and the CLI smokes
(`tools/offenders.py --quick`, `tools/opperf.py --quick`).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, telemetry
from incubator_mxnet_tpu import optimizer as opt_mod
from incubator_mxnet_tpu.base import MXNetError
from incubator_mxnet_tpu.gluon.contrib import FusedInferStep, FusedTrainStep
from incubator_mxnet_tpu.inspect import hlo, report, roofline
from incubator_mxnet_tpu import inspect as mxinspect

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# HLO text parser
# ---------------------------------------------------------------------------
HLO_TEXT = """\
HloModule test_module, entry_computation_layout={(f32[128,256]{1,0})->f32[]}

%fused_computation (param_0: f32[128,256], param_1: f32[128,256]) -> f32[128,256] {
  %param_0 = f32[128,256]{1,0} parameter(0)
  %param_1 = f32[128,256]{1,0} parameter(1)
  %multiply.1 = f32[128,256]{1,0} multiply(f32[128,256]{1,0} %param_0, f32[128,256]{1,0} %param_1)
  ROOT %add.1 = f32[128,256]{1,0} add(f32[128,256]{1,0} %multiply.1, f32[128,256]{1,0} %param_1)
}

%wrapped_comp (p0: f32[2,8,8,3], p1: f32[3,3,3,16]) -> f32[2,8,8,16] {
  %p0 = f32[2,8,8,3]{3,2,1,0} parameter(0)
  %p1 = f32[3,3,3,16]{3,2,1,0} parameter(1)
  ROOT %convolution.1 = f32[2,8,8,16]{3,2,1,0} convolution(f32[2,8,8,3]{3,2,1,0} %p0, f32[3,3,3,16]{3,2,1,0} %p1), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f
}

ENTRY %main (a: f32[128,256], b: f32[64,128], c: f32[128,256]) -> (f32[128,256], f32[64,256]) {
  %a = f32[128,256]{1,0} parameter(0)
  %b = f32[64,128]{1,0} parameter(1)
  %c = f32[128,256]{1,0} parameter(2)
  %x = f32[2,8,8,3]{3,2,1,0} parameter(3)
  %k = f32[3,3,3,16]{3,2,1,0} parameter(4)
  %fusion = f32[128,256]{1,0} fusion(f32[128,256]{1,0} %a, f32[128,256]{1,0} %c), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/mul_add" source_file="model.py"}
  %call.1 = f32[2,8,8,16]{3,2,1,0} call(f32[2,8,8,3]{3,2,1,0} %x, f32[3,3,3,16]{3,2,1,0} %k), to_apply=%wrapped_comp
  %dot.1 = f32[64,256]{1,0} dot(f32[64,128]{1,0} %b, f32[128,256]{1,0} %fusion), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %tuple.1 = (f32[128,256]{1,0}, f32[64,256]{1,0}) tuple(f32[128,256]{1,0} %fusion, f32[64,256]{1,0} %dot.1)
}
"""


def test_parse_shape_and_bytes():
    assert hlo.parse_shape("f32[128,256]{1,0}") == ("f32", (128, 256))
    assert hlo.parse_shape("bf16[]") == ("bf16", ())
    assert hlo.shape_bytes(("f32", (128, 256))) == 128 * 256 * 4
    assert hlo.shape_bytes(("bf16", ())) == 2
    # tuple shapes sum their leaves
    tup = hlo.parse_shape("(f32[4,4]{1,0}, s32[8]{0})")
    assert hlo.shape_bytes(tup) == 4 * 4 * 4 + 8 * 4
    assert hlo.parse_shape("garbage") is None
    assert hlo.shape_bytes(None) == 0


def test_parse_module_structure():
    m = hlo.parse_module(HLO_TEXT)
    assert m.name == "test_module"
    assert m.entry_name == "main"
    assert set(m.computations) == {"main", "fused_computation",
                                   "wrapped_comp"}
    fusion = next(i for i in m.entry.instructions if i.opcode == "fusion")
    assert fusion.operands == ["a", "c"]
    assert fusion.called == ["fused_computation"]
    assert fusion.op_name == "jit(step)/mul_add"
    root = m.entry.root
    assert root.opcode == "tuple" and root.is_root


def test_fusion_flops_sum_called_computation():
    m = hlo.parse_module(HLO_TEXT)
    fusion = next(i for i in m.entry.instructions if i.opcode == "fusion")
    # multiply (128*256) + add (128*256) inside the called computation
    assert roofline.instr_flops(fusion, m) == 2 * 128 * 256


def test_dot_and_conv_flop_formulas():
    m = hlo.parse_module(HLO_TEXT)
    dot = next(i for i in m.entry.instructions if i.opcode == "dot")
    # 2 * out(64*256) * contract(128)
    assert roofline.instr_flops(dot, m) == 2.0 * 64 * 256 * 128
    conv = next(i for i in m.computations["wrapped_comp"].instructions
                if i.opcode == "convolution")
    # 2 * out(2*8*8*16) * kernel taps per output (3*3*3*16 / o=16 = 27)
    assert roofline.instr_flops(conv, m) == 2.0 * (2 * 8 * 8 * 16) * 27
    assert conv.dim_labels == "b01f_01io->b01f"


def test_unit_cost_dedups_repeated_operand_reads():
    text = """\
HloModule dedup
ENTRY %main (a: f32[64,64]) -> f32[64,64] {
  %a = f32[64,64]{1,0} parameter(0)
  ROOT %multiply.1 = f32[64,64]{1,0} multiply(f32[64,64]{1,0} %a, f32[64,64]{1,0} %a)
}
"""
    m = hlo.parse_module(text)
    sq = m.entry.root
    cost = roofline.unit_cost(sq, m)
    buf = 64 * 64 * 4
    assert cost["in_bytes"] == buf          # %a read twice = one buffer
    assert cost["out_bytes"] == buf
    assert cost["bytes"] == 2 * buf


def test_parse_module_without_name_sigils():
    """Newer XLA ToString forms drop the '%' sigil; operand attribution
    (and therefore boundary bytes) must survive, not silently collapse
    to output-only bytes."""
    bare = HLO_TEXT.replace("%", "")
    m_sig = hlo.parse_module(HLO_TEXT)
    m_bare = hlo.parse_module(bare)
    for comp in m_sig.computations:
        sig = m_sig.computations[comp].instructions
        bare_i = m_bare.computations[comp].instructions
        assert [i.operands for i in sig] == [i.operands for i in bare_i]
    f_sig = next(i for i in m_sig.entry.instructions
                 if i.opcode == "fusion")
    f_bare = next(i for i in m_bare.entry.instructions
                  if i.opcode == "fusion")
    cost_sig = roofline.unit_cost(f_sig, m_sig)
    cost_bare = roofline.unit_cost(f_bare, m_bare)
    assert cost_bare["in_bytes"] == cost_sig["in_bytes"] > 0
    assert cost_bare["flops"] == cost_sig["flops"]


def test_parse_module_keeps_computations_with_wide_tuple_parameters():
    """XLA marks every fifth element of a tuple shape `/*index=5*/`; the
    `=` in the mark is not an instruction's, and a loop body or a branch
    with such a parameter (every scanned decode step) is a computation."""
    text = """\
HloModule wide
%body (arg: (s32[], f32[4], f32[4], f32[4], f32[4], /*index=5*/f32[4])) -> (s32[], f32[4], f32[4], f32[4], f32[4], /*index=5*/f32[4]) {
  %arg = (s32[], f32[4], f32[4], f32[4], f32[4], /*index=5*/f32[4]) parameter(0)
  %x = f32[4]{0} get-tuple-element(%arg), index=5
  ROOT %sort.1 = f32[4]{0} sort(%x), dimensions={0}, to_apply=%lt
}
ENTRY %main (a: f32[4]) -> f32[4] {
  ROOT %a = f32[4]{0} parameter(0)
}
"""
    m = hlo.parse_module(text)
    assert set(m.computations) == {"body", "main"}
    assert [i.opcode for i in m.computations["body"].instructions] == [
        "parameter", "get-tuple-element", "sort"]


def test_kernel_units_descend_call_wrappers():
    m = hlo.parse_module(HLO_TEXT)
    units = roofline.kernel_units(m)
    # fusion + dot at top level, conv inside the %call wrapper; the call
    # itself, parameters, and the tuple are not kernel launches
    assert sorted(u.opcode for u in units) == ["convolution", "dot",
                                               "fusion"]


# ---------------------------------------------------------------------------
# calibration resolution + classification
# ---------------------------------------------------------------------------
def test_classify_against_ridge():
    assert roofline.classify(10.0, 5.0) == "compute"
    assert roofline.classify(2.0, 5.0) == "memory"


def test_load_calibration_explicit_path_and_ridge(tmp_path):
    p = tmp_path / "calib.json"
    p.write_text(json.dumps({"peak_flops": 1e12,
                             "peak_bytes_per_sec": 1e11,
                             "platform": "tpu"}))
    cal = roofline.load_calibration(path=str(p))
    # explicit paths are trusted even across platforms
    assert cal["peak_flops"] == 1e12
    assert cal["ridge_flop_per_byte"] == 10.0


def test_load_calibration_env_override(tmp_path, monkeypatch):
    p = tmp_path / "calib.json"
    p.write_text(json.dumps({"peak_flops": 2e12,
                             "peak_bytes_per_sec": 1e11}))
    monkeypatch.setenv("MXNET_INSPECT_CALIB", str(p))
    assert roofline.load_calibration()["peak_flops"] == 2e12


@pytest.mark.parametrize("explicit,env,want", [
    ("good", "good", "explicit"),        # the caller's path beats the variable
    ("broken", "good", "env"),           # a file without peaks is passed over
    ("missing", "broken", "table"),      # nothing usable named: the table
    (None, None, "table"),               # nothing named: no file is read
])
def test_load_calibration_order(tmp_path, monkeypatch, explicit, env, want):
    """explicit path > MXNET_INSPECT_CALIB > the platform's table; the
    package looks for no calibration file of its own accord."""
    def named(kind, peak):
        if kind is None:
            return None
        p = tmp_path / f"{kind}_{peak:g}.json"
        if kind == "good":
            p.write_text(json.dumps({"peak_flops": peak,
                                     "peak_bytes_per_sec": 1e11,
                                     "platform": "not_this_platform"}))
        elif kind == "broken":
            p.write_text("{not json")
        return str(p)                    # "missing": never written

    path, envp = named(explicit, 3e12), named(env, 5e12)
    if envp:
        monkeypatch.setenv("MXNET_INSPECT_CALIB", envp)
    else:
        monkeypatch.delenv("MXNET_INSPECT_CALIB", raising=False)
    opened = []
    real_open = open

    def spy(file, *a, **kw):
        opened.append(str(file))
        return real_open(file, *a, **kw)

    with monkeypatch.context() as m:
        m.setattr("builtins.open", spy)
        cal = roofline.load_calibration(path=path, platform="cpu")
    assert set(opened) <= {path, envp}, opened
    if want == "explicit":
        # a named file is trusted whatever platform it says it was made on
        assert cal["peak_flops"] == 3e12 and cal["source"] == path
    elif want == "env":
        assert cal["peak_flops"] == 5e12 and cal["source"] == envp
    else:
        assert cal["source"] == "spec-fallback"
        assert cal["peak_flops"] == roofline.DEFAULT_CALIBRATIONS[
            "cpu"]["peak_flops"]
    assert cal["ridge_flop_per_byte"] == \
        cal["peak_flops"] / cal["peak_bytes_per_sec"]


@pytest.mark.parametrize("kind,known", [
    ("TPU v5 lite", True), ("TPU v4", False), ("TPU v7x", False)])
def test_tpu_spec_fallback_is_the_v5e_pair_or_an_error(
        monkeypatch, kind, known):
    """With no calibration a TPU's peaks come from the published spec —
    FLOP/s and HBM bandwidth of the SAME chip. Only the v5e's bandwidth
    is recorded, so a v4 (whose FLOP/s the table knows) is an error like
    an unknown kind: a ridge from a mixed pair would be wrong."""
    import jax

    class _Dev:
        platform, device_kind = "tpu", kind

    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    monkeypatch.delenv("MXNET_INSPECT_CALIB", raising=False)
    if known:
        cal = roofline.load_calibration(platform="tpu")
        assert cal["source"] == "published-spec"
        assert cal["peak_flops"] == 197e12
        assert cal["peak_bytes_per_sec"] == 819e9
    else:
        with pytest.raises(MXNetError, match="never guessed"):
            roofline.load_calibration(platform="tpu")


def _flat_calib():
    return {"peak_flops": 1e12, "peak_bytes_per_sec": 1e11,
            "ridge_flop_per_byte": 10.0, "source": "test"}


def test_analyze_module_ranking_and_totals():
    m = hlo.parse_module(HLO_TEXT)
    records, totals = roofline.analyze_module(m, calib=_flat_calib())
    assert totals["units"] == 3
    assert totals["flops"] > 0 and totals["bytes"] > 0
    # ranked by est_time descending; shares sum to ~1
    times = [r["est_time_s"] for r in records]
    assert times == sorted(times, reverse=True)
    assert abs(sum(r["time_share"] for r in records) - 1.0) < 1e-6
    for r in records:
        assert r["bound"] in ("compute", "memory")
        if r["intensity"] is not None:
            assert (r["intensity"] >= 10.0) == (r["bound"] == "compute")
    assert 0.0 <= totals["memory_bound_byte_share"] <= 1.0


# ---------------------------------------------------------------------------
# cost-analysis degradation contract (satellite)
# ---------------------------------------------------------------------------
class _FakeCompiled:
    def __init__(self, text, ca):
        self._text, self._ca = text, ca

    def as_text(self):
        return self._text

    def cost_analysis(self):
        if isinstance(self._ca, Exception):
            raise self._ca
        return self._ca


def test_cost_analysis_summary_variants():
    ok = report._roofline.cost_analysis_summary(
        _FakeCompiled("", {"flops": 12.0, "bytes accessed": 34.0}))
    assert ok == {"flops": 12.0, "bytes_accessed": 34.0,
                  "bytes_estimated": True}
    # a backend that reports flops only: bytes stay unknown
    part = roofline.cost_analysis_summary(
        _FakeCompiled("", {"flops": 5.0}))
    assert part["flops"] == 5.0
    assert part["bytes_accessed"] is None and not part["bytes_estimated"]
    # raising backends degrade to all-None, never crash
    bad = roofline.cost_analysis_summary(
        _FakeCompiled("", RuntimeError("unsupported")))
    assert bad["flops"] is None and not bad["bytes_estimated"]


def test_inspect_compiled_without_cost_analysis_uses_hlo_model():
    rep = mxinspect.inspect_compiled(
        _FakeCompiled(HLO_TEXT, RuntimeError("no cost analysis here")),
        name="fake", calib=_flat_calib())
    assert rep["ranking"] == "est_time"          # HLO shapes carried bytes
    assert rep["bytes_estimated"] is True
    assert rep["cost_analysis"]["flops"] is None
    assert rep["n_units"] == 3 and rep["offenders"]


def test_flops_only_degradation_when_bytes_unknowable():
    """No parseable shapes AND no cost analysis -> flops-only ranking,
    flagged, not a crash (the acceptance contract for exotic backends)."""
    text = """\
HloModule opaque
ENTRY %main (p: f32[8]) -> f32[8] {
  %p = garbage parameter(0)
  ROOT %custom-call.1 = garbage custom-call(%p), custom_call_target="x"
}
"""
    rep = mxinspect.inspect_compiled(
        _FakeCompiled(text, RuntimeError("nope")), calib=_flat_calib())
    assert rep["ranking"] == "flops_only"
    assert rep["bytes_estimated"] is False
    assert rep["est_step_mfu_ceiling"] == 0.0    # no modelled work


def test_inspect_hlo_text_offline_no_backend():
    rep = mxinspect.inspect_hlo_text(HLO_TEXT, name="dump",
                                     calib=_flat_calib())
    assert rep["name"] == "dump"
    assert rep["n_units"] == 3
    assert rep["cost_analysis"]["flops"] is None


# ---------------------------------------------------------------------------
# grouping + rendering
# ---------------------------------------------------------------------------
def test_class_name_deinstances():
    assert report.class_name("multiply_multiply_fusion.18.clone") == \
        "multiply_multiply_fusion"
    assert report.class_name("loop_add_fusion.remat.3") == \
        "loop_add_fusion"
    assert report.class_name("dot.1") == "dot"
    assert report.class_name("fusion") == "fusion"


def test_offender_groups_fold_instances():
    text = """\
HloModule grouped
ENTRY %main (a: f32[256,256], b: f32[256,256]) -> f32[256,256] {
  %a = f32[256,256]{1,0} parameter(0)
  %b = f32[256,256]{1,0} parameter(1)
  %add_fusion.1 = f32[256,256]{1,0} add(f32[256,256]{1,0} %a, f32[256,256]{1,0} %b)
  %add_fusion.2 = f32[256,256]{1,0} add(f32[256,256]{1,0} %add_fusion.1, f32[256,256]{1,0} %b)
  %add_fusion.2.clone = f32[256,256]{1,0} add(f32[256,256]{1,0} %add_fusion.2, f32[256,256]{1,0} %a)
  ROOT %dot.7 = f32[256,256]{1,0} dot(f32[256,256]{1,0} %add_fusion.2.clone, f32[256,256]{1,0} %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
    rep = mxinspect.inspect_hlo_text(text, calib=_flat_calib())
    groups = {g["class"]: g for g in rep["offender_groups"]}
    assert groups["add_fusion"]["count"] == 3
    assert groups["dot"]["count"] == 1
    assert rep["n_groups"] == 2
    assert rep["offender_top1_share"] == rep["offender_groups"][0][
        "time_share"]
    # coverage over 2 groups is total
    assert abs(rep["topk_time_coverage"] - 1.0) < 1e-5


def test_render_markdown_tables():
    rep = mxinspect.inspect_hlo_text(HLO_TEXT, name="md",
                                     calib=_flat_calib())
    text = mxinspect.render_markdown(rep)
    assert "# Offender attribution — md" in text
    assert "| # | fusion class |" in text
    assert "`dot" in text and "memory" in text or "compute" in text
    assert "MFU ceiling" in text


def test_dump_json_atomic(tmp_path):
    rep = mxinspect.inspect_hlo_text(HLO_TEXT, calib=_flat_calib())
    out = tmp_path / "rep.json"
    mxinspect.dump_json(rep, str(out))
    assert json.loads(out.read_text())["n_units"] == 3
    assert not os.path.exists(str(out) + ".tmp")


# ---------------------------------------------------------------------------
# live surfaces: jitted fn, FusedTrainStep, FusedInferStep, ExportedModel
# ---------------------------------------------------------------------------
def test_inspect_jitted_fn_and_registry_metrics():
    import jax.numpy as jnp

    before = telemetry.REGISTRY.snapshot()
    rep = mxinspect.inspect_step(lambda x: (x @ x).sum(),
                                 jnp.ones((64, 64), jnp.float32))
    assert rep["n_units"] >= 1
    assert rep["ranking"] == "est_time"
    assert rep["totals"]["flops"] >= 2 * 64 ** 3   # the matmul at least
    assert 0.0 < rep["est_step_mfu_ceiling"] <= 1.0
    snap = telemetry.REGISTRY.snapshot()
    assert snap["inspect.runs"] == before.get("inspect.runs", 0) + 1
    assert snap["inspect.units"] >= before.get("inspect.units", 0) + 1
    assert snap["inspect.top1_share"] == rep["offender_top1_share"]
    assert snap["inspect.memory_bound_byte_share"] == \
        rep["memory_bound_byte_share"]
    assert snap["inspect.mfu_ceiling"] == rep["est_step_mfu_ceiling"]
    # the analysis ran under a span lane
    assert telemetry.REGISTRY.snapshot().get(
        'span.count{name="inspect.analyze"}', 0) >= 1


def _tiny_train_step(bs=4):
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu", in_units=8),
            gluon.nn.Dense(4, in_units=16))
    net.initialize()
    net.hybridize()
    x = mx.np.array(np.random.RandomState(0).randn(bs, 8).astype(np.float32))
    y = mx.np.array(np.random.RandomState(1).randn(bs, 4).astype(np.float32))
    loss = gluon.loss.L2Loss()
    opt = opt_mod.create("sgd", learning_rate=0.1)
    step = FusedTrainStep(net, lambda n, a, b: loss(n(a), b).mean(), opt)
    return step, x, y


def test_inspect_fused_train_step():
    step, x, y = _tiny_train_step()
    rep = mxinspect.inspect_step(step, x, y, name="tiny_train")
    assert rep["name"] == "tiny_train"
    assert rep["n_units"] >= 2                  # fwd+bwd+update fusions
    assert rep["bytes_estimated"] is True
    assert rep["offender_groups"][0]["time_share"] > 0
    # the lowered() refactor keeps flops_per_call working (MFU numerator)
    assert step.flops_per_call(x, y) > 0
    # and the step itself still trains after inspection
    assert np.isfinite(float(step(x, y).asnumpy()))


def test_inspect_fused_infer_step_and_seeding():
    net = gluon.nn.Dense(4, in_units=4)
    net.initialize()
    net.hybridize()
    step = FusedInferStep(net)
    with pytest.raises(MXNetError):
        step.lowered()                          # unseeded, no input
    x = mx.np.ones((2, 4))
    rep = mxinspect.inspect_step(step, x)
    assert rep["n_units"] >= 1


def test_inspect_exported_model(tmp_path):
    from incubator_mxnet_tpu import deploy

    net = gluon.nn.Dense(3, in_units=6)
    net.initialize()
    net.hybridize()
    x = mx.np.zeros((2, 6), dtype="float32")
    net(x)
    prefix = str(tmp_path / "net")
    net.export(prefix, example_inputs=x)
    model = deploy.ExportedModel(f"{prefix}-0000")
    rep = mxinspect.inspect_step(model)
    assert rep["n_units"] >= 1
    # inspection pre-populated the jit cache; run still works
    out = model.run(np.ones((2, 6), np.float32))
    assert np.asarray(out).shape == (2, 3)


def test_top_k_env_knob(monkeypatch):
    monkeypatch.setenv("MXNET_INSPECT_TOP_K", "2")
    rep = mxinspect.inspect_hlo_text(HLO_TEXT, calib=_flat_calib())
    assert rep["top_k"] == 2
    assert len(rep["offenders"]) <= 2
    assert len(rep["offender_groups"]) <= 2
    assert rep["totals"]["units"] == 3          # totals stay whole-module


def test_execute_callback_adds_wall_clock_only():
    """`execute=` times real executions on the wall clock; the report
    claims no device measurement (measured mode is gone)."""
    import jax.numpy as jnp
    x = jnp.ones((32, 32), jnp.float32)
    rep = mxinspect.inspect_step(
        lambda a: (a @ a).sum(), x,
        execute=lambda: (x @ x).sum().block_until_ready())
    assert rep["measured_wall_ms"] > 0
    assert "measured" not in rep
    assert "measured_unavailable_reason" not in rep
    assert "measured_wall_ms" not in mxinspect.inspect_step(
        lambda a: (a @ a).sum(), x)


def test_lower_any_rejects_unknown():
    with pytest.raises(MXNetError):
        mxinspect.lower_any(object())


def test_inspect_lowered_and_compiled_stages_agree():
    """A jax.stages.Lowered must be compiled before parsing (its as_text
    is StableHLO, not optimized HLO) — both stages and the jitted wrapper
    itself must yield the same non-degenerate analysis."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((32, 32), jnp.float32)
    rep_lowered = mxinspect.inspect_step(f.lower(x))
    rep_compiled = mxinspect.inspect_step(f.lower(x).compile())
    rep_jitted = mxinspect.inspect_step(f, x)
    assert rep_lowered["n_units"] >= 1
    assert rep_lowered["totals"]["flops"] >= 2 * 32 ** 3
    assert rep_lowered["n_units"] == rep_compiled["n_units"] \
        == rep_jitted["n_units"]
    assert rep_lowered["totals"]["flops"] == rep_compiled["totals"][
        "flops"]


def test_exported_model_lowered_input_validation(tmp_path):
    from incubator_mxnet_tpu import deploy

    net = gluon.nn.Dense(3, in_units=6)
    net.initialize()
    net.hybridize()
    x = mx.np.zeros((2, 6), dtype="float32")
    net(x)
    prefix = str(tmp_path / "net")
    net.export(prefix, example_inputs=x)
    model = deploy.ExportedModel(f"{prefix}-0000")
    # passing a spec-matching input (by analogy with every other surface)
    rep = mxinspect.inspect_step(model, np.ones((2, 6), np.float32))
    assert rep["n_units"] >= 1
    # wrong shape / wrong arity: descriptive errors, not a retrace
    with pytest.raises(MXNetError, match="does not match"):
        model.lowered(np.ones((4, 6), np.float32))
    with pytest.raises(MXNetError, match="expects"):
        model.lowered(np.ones((2, 6), np.float32),
                      np.ones((2, 6), np.float32))


def test_callable_cost_accepts_prejitted_fn():
    import jax
    import jax.numpy as jnp

    x = jnp.ones((64, 64), jnp.float32)
    plain = roofline.callable_cost(lambda a: a @ a, x,
                                   calib=_flat_calib())
    jitted = roofline.callable_cost(jax.jit(lambda a: a @ a), x,
                                    calib=_flat_calib())
    assert jitted["est_flops"] == plain["est_flops"]
    assert jitted["est_flops"] >= 2 * 64 ** 3
    assert jitted["bound"] in ("compute", "memory")


# ---------------------------------------------------------------------------
# CLI smokes
# ---------------------------------------------------------------------------
def _run(args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, timeout=timeout, cwd=REPO, env=env)


def test_offenders_cli_quick_json(tmp_path):
    out = tmp_path / "off.json"
    r = _run([os.path.join(REPO, "tools", "offenders.py"), "--quick",
              "--json", str(out)])
    assert r.returncode == 0, r.stdout + r.stderr
    rep = json.loads(out.read_text())
    assert rep["name"] == "tiny_train_bs4"
    assert rep["n_units"] > 0 and rep["offender_groups"]
    for key in ("offender_top1_share", "memory_bound_byte_share",
                "est_step_mfu_ceiling", "top10_byte_coverage"):
        assert key in rep
    assert rep["calibration"]["ridge_flop_per_byte"] > 0


def test_offenders_cli_hlo_file_offline(tmp_path):
    dump = tmp_path / "dump.txt"
    dump.write_text(HLO_TEXT)
    r = _run([os.path.join(REPO, "tools", "offenders.py"),
              "--hlo-file", str(dump), "--markdown", "-"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "Offender attribution" in r.stdout


def test_opperf_quick_json_smoke(tmp_path):
    """Satellite: opperf gains roofline columns + tier-1 coverage."""
    out = tmp_path / "opperf.json"
    r = _run([os.path.join(REPO, "tools", "opperf.py"), "--quick",
              "--json", str(out)])
    assert r.returncode == 0, r.stdout + r.stderr
    data = json.loads(out.read_text())
    assert data["quick"] is True
    assert data["calibration"]["ridge_flop_per_byte"] > 0
    rows = [row for rows in data["results"].values() for row in rows
            if "error" not in row]
    assert rows, "every opperf row errored"
    costed = [row for row in rows if row.get("est_flops") is not None]
    assert costed, "no opperf row carried roofline columns"
    for row in costed:
        assert row["est_bytes"] is None or row["est_bytes"] > 0
        if row.get("intensity") is not None:
            assert row["bound"] in ("compute", "memory")
    # gemm ops must rank more arithmetic-intense than norm ops
    gemm = [r_ for r_ in data["results"].get("gemm", [])
            if r_.get("intensity")]
    norm = [r_ for r_ in data["results"].get("norm", [])
            if r_.get("intensity")]
    if gemm and norm:
        assert max(g["intensity"] for g in gemm) > \
            min(n["intensity"] for n in norm)


def test_offender_groups_bound_agrees_with_the_ridge():
    """On a live report of a compiled train step: every class is tagged
    on the side of the ridge its intensity lies on, the shares are
    shares, and the ten heaviest classes cover the step's bytes."""
    step, x, y = _tiny_train_step()
    rep = mxinspect.inspect_step(step, x, y, name="tiny_train_ridge")
    ridge = rep["calibration"]["ridge_flop_per_byte"]
    assert ridge > 0 and rep["ranking"] == "est_time"
    assert rep["offender_groups"]
    for g in rep["offender_groups"]:
        assert g["bound"] in ("compute", "memory")
        if g["intensity"] is not None:
            assert (g["intensity"] >= ridge) == (g["bound"] == "compute")
    for key in ("offender_top1_share", "memory_bound_byte_share",
                "est_step_mfu_ceiling", "top10_byte_coverage"):
        assert 0.0 <= rep[key] <= 1.0, key
    assert rep["offender_top1_share"] > 0
