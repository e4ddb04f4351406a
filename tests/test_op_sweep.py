"""Systematic per-op numeric sweep (VERDICT-r4 Next #4; ≙ the reference's
tests/python/unittest/test_operator.py + test_numpy_op.py per-op
forward/backward checks).

Contract: EVERY op in ops.registry.list_ops() is either SWEPT — forward
compared against the NumPy reference implementation (dtype-aware
tolerances), backward via check_numeric_gradient for the differentiable
float ops — or EXEMPT with a reason string. test_registry_fully_classified
fails on any unclassified op, so newly registered ops must declare their
test. The classification counts are printed into the test log."""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.ops import registry
from incubator_mxnet_tpu.test_utils import check_numeric_gradient

RNG = np.random.RandomState(42)


def F(shape=(3, 4), lo=-2.0, hi=2.0):
    """Float input away from op singularities at 0/±1 edges."""
    return (lo + (hi - lo) * RNG.rand(*shape)).astype(np.float32)


def POS(shape=(3, 4), lo=0.5, hi=3.0):
    return F(shape, lo, hi)


def UNIT(shape=(3, 4)):      # open interval (-0.9, 0.9)
    return F(shape, -0.9, 0.9)


def INTS(shape=(3, 4), lo=0, hi=6):
    return RNG.randint(lo, hi, shape).astype(np.int32)


def BOOLS(shape=(3, 4)):
    return RNG.rand(*shape) > 0.5


# ---------------------------------------------------------------------------
# Spec table: name (without the np./npx. prefix resolution — keys are the
# full registry names) -> how to test it.
# ---------------------------------------------------------------------------
SPECS = {}


def spec(name, inputs, kw=None, ref=None, grad=False, rtol=2e-5, atol=1e-5,
         grad_atol=2e-3):
    SPECS[name] = dict(inputs=inputs, kw=kw or {}, ref=ref, grad=grad,
                       rtol=rtol, atol=atol, grad_atol=grad_atol)


def u(name, gen=F, grad=True, **k):
    """Unary op sharing its name + semantics with numpy."""
    spec(f"np.{name}", lambda: [gen()], grad=grad, **k)


def b(name, gen_a=F, gen_b=F, grad=True, **k):
    spec(f"np.{name}", lambda: [gen_a(), gen_b()], grad=grad, **k)


# ---- unary elementwise ----------------------------------------------------
for n in ["abs", "absolute", "arctan", "cbrt", "ceil", "conj", "conjugate",
          "cos", "deg2rad", "degrees", "exp", "exp2", "expm1", "fabs",
          "floor", "negative", "positive", "rad2deg", "radians", "rint",
          "sign", "sin", "sinc", "square", "tanh", "trunc", "round",
          "i0", "real", "imag", "nan_to_num", "spacing", "signbit"]:
    u(n, grad=n in {"arctan", "cos", "exp", "exp2", "expm1", "negative",
                    "sin", "square", "tanh", "cbrt", "sinc"})
for n in ["sqrt", "log", "log10", "log1p", "log2", "reciprocal"]:
    u(n, gen=POS, grad=True)
for n in ["arcsin", "arccos", "arctanh"]:
    u(n, gen=UNIT, grad=True)
u("arccosh", gen=lambda: POS(lo=1.2, hi=3.0), grad=True)
u("arcsinh", grad=True)
u("sinh", gen=UNIT, grad=True)
u("cosh", gen=UNIT, grad=True)
u("tan", gen=UNIT, grad=True)
u("logical_not", gen=BOOLS, grad=False)
u("invert", gen=INTS, grad=False)
u("bitwise_not", gen=INTS, grad=False)
for n in ["isfinite", "isinf", "isnan", "isneginf", "isposinf"]:
    spec(f"np.{n}",
         lambda: [np.array([[1.0, np.inf], [-np.inf, np.nan]], np.float32)])
u("angle", grad=False)

# ---- binary elementwise ---------------------------------------------------
for n in ["add", "subtract", "multiply", "arctan2", "hypot", "maximum",
          "minimum", "fmax", "fmin", "copysign", "logaddexp", "logaddexp2",
          "nextafter"]:
    b(n, grad=n not in {"copysign", "nextafter", "maximum", "minimum",
                        "fmax", "fmin"})
b("divide", gen_b=POS, grad=True)
b("true_divide", gen_b=POS, grad=True)
b("float_power", gen_a=POS, gen_b=lambda: F(lo=0.5, hi=2.0), grad=False)
b("power", gen_a=POS, gen_b=lambda: F(lo=0.5, hi=2.0), grad=True)
b("mod", gen_b=POS, grad=False)
b("fmod", gen_b=POS, grad=False)
b("remainder", gen_b=POS, grad=False)
b("floor_divide", gen_b=POS, grad=False)
b("heaviside", grad=False)
for n in ["equal", "not_equal", "greater", "greater_equal", "less",
          "less_equal"]:
    b(n, gen_a=lambda: INTS().astype(np.float32),
      gen_b=lambda: INTS().astype(np.float32), grad=False)
for n in ["logical_and", "logical_or", "logical_xor"]:
    b(n, gen_a=BOOLS, gen_b=BOOLS, grad=False)
for n in ["bitwise_and", "bitwise_or", "bitwise_xor", "gcd", "lcm"]:
    b(n, gen_a=lambda: INTS(lo=1, hi=9), gen_b=lambda: INTS(lo=1, hi=9),
      grad=False)
b("left_shift", gen_a=lambda: INTS(lo=1, hi=5),
  gen_b=lambda: INTS(lo=0, hi=3), grad=False)
b("right_shift", gen_a=lambda: INTS(lo=4, hi=64),
  gen_b=lambda: INTS(lo=0, hi=3), grad=False)
b("ldexp", gen_a=F, gen_b=lambda: INTS(lo=-2, hi=3), grad=False)

# ---- reductions -----------------------------------------------------------
for n in ["sum", "prod", "mean", "std", "var", "max", "min", "amax", "amin",
          "median", "ptp", "nansum", "nanprod", "nanmean", "nanstd",
          "nanvar", "nanmax", "nanmin", "nanmedian", "all", "any",
          "count_nonzero", "argmax", "argmin", "nanargmax", "nanargmin",
          "cumsum", "cumprod", "nancumsum", "nancumprod"]:
    spec(f"np.{n}", lambda: [F()], kw={"axis": 1},
         grad=n in {"sum", "mean", "cumsum"})
spec("np.average", lambda: [F()], kw={"axis": 0}, grad=True)
for n in ["percentile", "quantile", "nanpercentile", "nanquantile"]:
    spec(f"np.{n}", lambda: [F(), 30.0 if "percent" in n else 0.3],
         kw={"axis": 1})
spec("np.trapezoid", lambda: [F()], kw={"axis": 1}, grad=True)
spec("np.gradient", lambda: [F((6,))], grad=False)
spec("np.diff", lambda: [F()], kw={"axis": 1}, grad=True)
spec("np.ediff1d", lambda: [F((8,))], grad=True)

# ---- shape / indexing / assembly -----------------------------------------
for n, kw in [("transpose", {}), ("swapaxes", {"axis1": 0, "axis2": 1}),
              ("moveaxis", {"source": 0, "destination": 1}),
              ("rollaxis", {"axis": 1}), ("flip", {"axis": 0}),
              ("fliplr", {}), ("flipud", {}), ("roll", {"shift": 2}),
              ("rot90", {}), ("ravel", {}), ("squeeze", {}),
              ("expand_dims", {"axis": 1}), ("tril", {}), ("triu", {}),
              ("diagonal", {}), ("trace", {}),
              ("repeat", {"repeats": 2, "axis": 1}),
              ("tile", {"reps": (2, 1)}),
              ("around", {"decimals": 1}),
              ("resize", {"new_shape": (2, 6)}),
              ("broadcast_to", {"shape": (2, 3, 4)}),
              ("atleast_1d", {}), ("atleast_2d", {}), ("atleast_3d", {}),
              ("copy", {}), ("zeros_like", {}), ("ones_like", {}),
              ("full_like", {"fill_value": 2.5}),
              ("delete", {"obj": 1, "axis": 1}),
              ("insert", {"obj": 1, "values": 9.0, "axis": 1}),
              ("append", {"values": np.float32(3.0)}),
              ("pad", {"pad_width": 1}),
              ("sort", {"axis": 1}), ("argsort", {"axis": 1}),
              ("partition", {"kth": 2, "axis": 1}),
              ("argpartition", {"kth": 2, "axis": 1}),
              ("unique", {}), ("nonzero", {}), ("argwhere", {}),
              ("flatnonzero", {}), ("diag", {}), ("diagflat", {})]:
    # kwargs are passed positionally-compatible with numpy's own names
    spec(f"np.{n}", lambda: [F()], kw=kw,
         grad=n in {"transpose", "ravel", "reshape", "flip", "tril",
                    "triu"})
spec("np.squeeze", lambda: [F((3, 1, 4))], grad=True)
spec("np.reshape", lambda: [F(), (4, 3)], grad=False)
spec("np.frexp", lambda: [F()],
     ref=lambda x: tuple(np.frexp(x)))
spec("np.concatenate", lambda: [(F(), F())], kw={"axis": 1}, grad=False)
spec("np.stack", lambda: [(F(), F())], kw={"axis": 0}, grad=False)
for n in ["vstack", "hstack", "dstack", "column_stack"]:
    spec(f"np.{n}", lambda: [(F(), F())])
for n, kw in [("split", {"indices_or_sections": 2, "axis": 1}),
              ("array_split", {"indices_or_sections": 3, "axis": 1}),
              ("hsplit", {"indices_or_sections": 2}),
              ("vsplit", {"indices_or_sections": 3})]:
    # (3,4): axis 1 divides by 2, axis 0 (vsplit) by 3
    spec(f"np.{n}", lambda: [F((3, 4))], kw=kw)
spec("np.dsplit", lambda: [F((2, 2, 4))], kw={"indices_or_sections": 2})
spec("np.take", lambda: [F(), INTS((5,), 0, 4)], kw={"axis": 1})
spec("np.take_along_axis", lambda: [F(), INTS((3, 2), 0, 4)],
     kw={"axis": 1})
spec("np.put_along_axis",
     lambda: [F(), INTS((3, 1), 0, 4), np.float32(9.0), 1],
     ref=lambda a, i, v, ax: (np.put_along_axis(a, i, float(v), ax), a)[1])
spec("np.where", lambda: [BOOLS(), F(), F()])
spec("np.clip", lambda: [F()], kw={"a_min": -0.5, "a_max": 0.5}, grad=True)
spec("np.compress", lambda: [np.array([True, False, True]), F()],
     kw={"axis": 0})
spec("np.extract", lambda: [BOOLS(), F()])
spec("np.choose", lambda: [INTS((4,), 0, 3), F((3, 4))])
spec("np.select",
     lambda: [[BOOLS(), BOOLS()], [F(), F()]],
     ref=lambda c, v: np.select(list(c), list(v)))
spec("np.searchsorted", lambda: [np.sort(F((8,))), F((5,))])
spec("np.digitize", lambda: [F((6,)), np.sort(F((4,)))])
spec("np.isin", lambda: [INTS(), INTS((6,), 0, 6)])
spec("np.interp", lambda: [F((5,)), np.sort(F((6,))), F((6,))])
spec("np.piecewise",
     lambda: [F((6,)), [F((6,)) > 0, F((6,)) <= 0], [-1.0, 1.0]],
     ref=lambda x, c, v: np.piecewise(x, list(c), list(v)))

# ---- linear algebra style -------------------------------------------------
spec("np.dot", lambda: [F((3, 4)), F((4, 2))], grad=True)
spec("np.matmul", lambda: [F((3, 4)), F((4, 2))], grad=True)
spec("np.inner", lambda: [F((4,)), F((4,))], grad=True)
spec("np.outer", lambda: [F((3,)), F((4,))], grad=True)
spec("np.vdot", lambda: [F((4,)), F((4,))], grad=True)
spec("np.tensordot", lambda: [F((3, 4)), F((4, 2))], kw={"axes": 1},
     grad=True)
spec("np.einsum", lambda: ["ij,jk->ik", F((3, 4)), F((4, 2))], grad=False)
spec("np.kron", lambda: [F((2, 2)), F((2, 3))], grad=True)
spec("np.cross", lambda: [F((3,)), F((3,))], grad=True)
spec("np.convolve", lambda: [F((6,)), F((3,))])
spec("np.correlate", lambda: [F((6,)), F((3,))])
spec("np.vander", lambda: [F((4,))])
spec("np.corrcoef", lambda: [F((3, 8))], rtol=1e-4)
spec("np.cov", lambda: [F((3, 8))], rtol=1e-4)

# ---- polynomials ----------------------------------------------------------
spec("np.polyval", lambda: [F((3,)), F((5,))], grad=True)
spec("np.polyadd", lambda: [F((3,)), F((4,))])
spec("np.polysub", lambda: [F((3,)), F((4,))])
spec("np.polymul", lambda: [F((3,)), F((4,))])
spec("np.polyder", lambda: [F((5,))])
spec("np.polyint", lambda: [F((4,))])
spec("np.polyfit", lambda: [np.arange(6, dtype=np.float32),
                            F((6,)), 2], rtol=1e-3, atol=1e-3)

# ---- sets -----------------------------------------------------------------
for n in ["intersect1d", "setdiff1d", "setxor1d", "union1d"]:
    spec(f"np.{n}", lambda: [INTS((8,), 0, 6), INTS((8,), 0, 6)])

# ---- values / predicates / metadata ---------------------------------------
spec("np.allclose", lambda: [F(), F()])
spec("np.isclose", lambda: [F(), F()])
spec("np.array_equal", lambda: [INTS(), INTS()])
spec("np.array_equiv", lambda: [INTS(), INTS()])
spec("np.ndim", lambda: [F()])
spec("np.shape", lambda: [F()])
spec("np.size", lambda: [F()])
spec("np.iscomplexobj", lambda: [F()])
spec("np.isrealobj", lambda: [F()])
spec("np.isscalar", lambda: [3.0])
spec("np.can_cast", lambda: ["int32", "float32"],
     ref=lambda a, b: np.can_cast(a, b))
# dtype promotion follows the DEVICE stack's lattice (jax: i32+f32 -> f32),
# not host numpy's value-based one (f64) — the framework is TPU-native
spec("np.promote_types", lambda: ["int32", "float32"],
     ref=lambda a, b: "float32")
spec("np.result_type", lambda: [np.float32(1), np.int32(2)],
     ref=lambda a, b: "float32")

# ---- creation-style (value-defined) ---------------------------------------
spec("np.eye", lambda: [4], kw={"M": 5})
spec("np.identity", lambda: [4])
spec("np.tri", lambda: [4])
spec("np.linspace", lambda: [0.0, 1.0], kw={"num": 7})
spec("np.logspace", lambda: [0.0, 2.0], kw={"num": 5}, rtol=1e-4)
spec("np.geomspace", lambda: [1.0, 16.0], kw={"num": 5}, rtol=1e-4)
spec("np.indices", lambda: [(2, 3)],
     ref=lambda s: np.indices(s))
spec("np.fromfunction", lambda: [(lambda i, j: i + 2 * j), (3, 4)],
     ref=lambda f, s: np.fromfunction(f, s))
spec("np.meshgrid", lambda: [F((3,)), F((4,))])
spec("np.bartlett", lambda: [8])
spec("np.blackman", lambda: [8])
spec("np.hamming", lambda: [8])
spec("np.hanning", lambda: [8])
spec("np.kaiser", lambda: [8, 3.5])
spec("np.tril_indices", lambda: [4],
     ref=lambda n: tuple(np.tril_indices(n)))
spec("np.triu_indices", lambda: [4],
     ref=lambda n: tuple(np.triu_indices(n)))
spec("np.ix_", lambda: [INTS((2,), 0, 3), INTS((3,), 0, 3)],
     ref=lambda a, b: np.ix_(a, b))
spec("np.unravel_index", lambda: [INTS((4,), 0, 12), (3, 4)],
     ref=lambda i, s: np.unravel_index(i, s))
spec("np.ravel_multi_index",
     lambda: [(INTS((4,), 0, 3), INTS((4,), 0, 4)), (3, 5)],
     ref=lambda mi, s: np.ravel_multi_index(tuple(mi), s))

# ---- histograms -----------------------------------------------------------
spec("np.histogram", lambda: [F((30,))], kw={"bins": 5})
spec("np.histogram2d", lambda: [F((30,)), F((30,))], kw={"bins": 4})
spec("np.bincount", lambda: [INTS((20,), 0, 6)])

# ---- misc -----------------------------------------------------------------
spec("np.empty_like", lambda: [F()],
     ref=lambda x: np.zeros_like(x) * 0)   # only shape/dtype are defined
SPECS["np.empty_like"]["shape_only"] = True
spec("np.apply_along_axis", lambda: [(lambda r: r.sum()), 1, F()],
     ref=lambda f, ax, x: np.apply_along_axis(f, ax, x))
spec("np.apply_over_axes", lambda: [np.sum, F(), [0]],
     ref=lambda f, x, ax: np.apply_over_axes(f, x, ax))
spec("np.broadcast_arrays", lambda: [F((3, 1)), F((1, 4))],
     ref=lambda a, b: np.broadcast_arrays(a, b))

# ---------------------------------------------------------------------------
# npx ops: MXNet-specific semantics, reference implementations inline
# ---------------------------------------------------------------------------


def _np_softmax(x, axis=-1):
    e = np.exp(x - x.max(axis=axis, keepdims=True))
    return e / e.sum(axis=axis, keepdims=True)


spec("npx.relu", lambda: [F()], ref=lambda x: np.maximum(x, 0), grad=True)
spec("npx.sigmoid", lambda: [F()], ref=lambda x: 1 / (1 + np.exp(-x)),
     grad=True)
spec("npx.log_sigmoid", lambda: [F()],
     ref=lambda x: -np.log1p(np.exp(-x)), grad=True)
spec("npx.silu", lambda: [F()], ref=lambda x: x / (1 + np.exp(-x)),
     grad=True)
spec("npx.softplus", lambda: [F()], ref=lambda x: np.log1p(np.exp(x)),
     grad=True)
spec("npx.tanh", lambda: [F()], ref=np.tanh, grad=True)
spec("npx.erf", lambda: [F()],
     ref=lambda x: __import__("scipy.special", fromlist=["erf"]).erf(x),
     grad=True)
spec("npx.erfinv", lambda: [UNIT()],
     ref=lambda x: __import__("scipy.special",
                              fromlist=["erfinv"]).erfinv(x), grad=True)
spec("npx.gamma", lambda: [POS()],
     ref=lambda x: __import__("scipy.special",
                              fromlist=["gamma"]).gamma(x), rtol=1e-4)
spec("npx.gammaln", lambda: [POS()],
     ref=lambda x: __import__("scipy.special",
                              fromlist=["gammaln"]).gammaln(x), grad=True)
spec("npx.digamma", lambda: [POS()],
     ref=lambda x: __import__("scipy.special",
                              fromlist=["psi"]).psi(x), rtol=1e-4)
spec("npx.softmax", lambda: [F()], ref=_np_softmax, grad=True)
spec("npx.log_softmax", lambda: [F()],
     ref=lambda x: np.log(_np_softmax(x)), grad=True)
spec("npx.masked_softmax",
     lambda: [F(), BOOLS()],
     ref=lambda x, m: np.where(
         m, _np_softmax(np.where(m, x, -1e30)) * m, 0.0), rtol=1e-4)
spec("npx.activation", lambda: [F()], kw={"act_type": "softrelu"},
     ref=lambda x, act_type: np.log1p(np.exp(x)))
spec("npx.embedding", lambda: [INTS((2, 3), 0, 5), F((5, 4))],
     ref=lambda i, w: w[i])
spec("npx.one_hot", lambda: [INTS((4,), 0, 5), 5],
     ref=lambda i, d: np.eye(d, dtype=np.float32)[i])
spec("npx.pick", lambda: [F((3, 4)), INTS((3,), 0, 4)],
     ref=lambda x, i: x[np.arange(3), i])
spec("npx.topk", lambda: [F((3, 6))], kw={"k": 2},
     ref=lambda x, k: np.argsort(-x, axis=-1)[..., :k].astype(np.float32))
spec("npx.l2_normalization", lambda: [F((3, 4))],
     ref=lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-10))
spec("npx.layer_norm", lambda: [F((3, 4)), POS((4,)), F((4,))],
     ref=lambda x, g, bta: g * (x - x.mean(-1, keepdims=True))
     / np.sqrt(x.var(-1, keepdims=True) + 1e-5) + bta,
     grad=True, rtol=1e-4, atol=1e-4)
spec("npx.rms_norm", lambda: [F((3, 4)), POS((4,))],
     ref=lambda x, g: g * x / np.sqrt(
         (x * x).mean(-1, keepdims=True) + 1e-6), grad=True, rtol=1e-4)


def _np_group_norm(x, g, bta, num_groups):
    n, c = x.shape[:2]
    xs = x.reshape(n, num_groups, -1)
    mu = xs.mean(-1, keepdims=True)
    var = xs.var(-1, keepdims=True)
    xn = ((xs - mu) / np.sqrt(var + 1e-5)).reshape(x.shape)
    return xn * g.reshape(1, c, *([1] * (x.ndim - 2))) \
        + bta.reshape(1, c, *([1] * (x.ndim - 2)))


spec("npx.group_norm",
     lambda: [F((2, 4, 3)), POS((4,)), F((4,))], kw={"num_groups": 2},
     ref=lambda x, g, bta, num_groups: _np_group_norm(x, g, bta,
                                                      num_groups),
     rtol=1e-4, atol=1e-4)
spec("npx.instance_norm",
     lambda: [F((2, 4, 3)), POS((4,)), F((4,))],
     ref=lambda x, g, bta: _np_group_norm(x, g, bta, 4), rtol=1e-4,
     atol=1e-4)
spec("npx.sequence_mask",
     lambda: [F((4, 2, 3)), np.array([1, 2], np.float32)],
     kw={"use_sequence_length": True, "value": -1.0},
     ref=lambda x, ln, use_sequence_length, value: np.where(
         np.arange(4)[:, None, None] < ln[None, :, None].astype(int),
         x, value))


def _np_sdpa(q, k, v):
    a = _np_softmax(q @ k.transpose(0, 2, 1) / np.sqrt(q.shape[-1]))
    return a @ v


# grad_atol: the finite-difference side runs in float32 (x64 is off) with
# eps 1e-3 on loss = sum(out**2) ~ 20, so its rounding noise is about
# 20 * 2**-24 / 1e-3 = 1.2e-3 per element — the default 2e-3 sits on that
# floor (a 2.2e-3 miss on a gradient entry of 2e-3), 5e-3 clears it
spec("npx.scaled_dot_product_attention",
     lambda: [F((2, 3, 4)), F((2, 3, 4)), F((2, 3, 4))],
     ref=_np_sdpa, grad=True, rtol=1e-4, atol=1e-4, grad_atol=5e-3)
spec("npx.stop_gradient", lambda: [F()], ref=lambda x: x)

# ---- fused kernel tier (PR 8; ops/fused.py — off-TPU these ARE the jnp
# compositions, so the sweep checks the registered surface + gradients;
# the Pallas kernel path is interpret-mode swept in test_fused_ops.py)
spec("npx.fused_bias_act", lambda: [F((3, 8)), F((8,))],
     kw={"act_type": "relu"},
     ref=lambda x, b, act_type: np.maximum(x + b, 0.0), grad=True)
spec("npx.fused_norm_act_residual",
     lambda: [F((3, 8)), POS((8,)), F((8,)), F((3, 8))],
     kw={"act_type": "relu"},
     ref=lambda x, s, b, r, act_type: np.maximum(x * s + b + r, 0.0),
     grad=True, rtol=1e-4)


def _np_bn_inference(x, g, bta, m, v):
    scale = g / np.sqrt(v + 1e-5)
    return x * scale + (bta - m * scale)


# inputs conditioned so no output element sits near 0 (a zero-output
# element makes the f32 finite-difference check all-noise: FD reads 0
# where the analytic dL/dx = 2*out*scale is merely tiny)
spec("npx.fused_bn_inference",
     lambda: [POS((3, 8), 1.0, 2.0), POS((8,)), POS((8,), 1.0, 3.0),
              F((8,), -0.3, 0.3), POS((8,))],
     ref=_np_bn_inference, grad=True, rtol=1e-4, atol=1e-4)

# ---------------------------------------------------------------------------
# Exemptions: ops whose semantics are covered elsewhere or are not
# numeric-comparable. Every entry carries its reason.
# ---------------------------------------------------------------------------
EXEMPT = {
    "np.asarray": "identity on NDArray input; constructor covered by "
                  "test_numpy_ops creation tests",
    "npx.rnn": "fused multi-layer RNN — verified against torch.nn.LSTM/"
               "GRU weight-for-weight in test_npx_rnn.py",
    # PR2 registered the detection/contrib surface as dispatch records
    # (AMP-class metadata); the ops themselves are covered functionally in
    # test_detection_ops.py / test_detection_zoo.py / test_contrib_ops.py
    "npx.bilinear_resize2d": "covered in test_detection_ops.py",
    "npx.box_iou": "covered in test_detection_ops.py",
    "npx.box_nms": "covered in test_detection_ops.py",
    "npx.deformable_convolution": "covered in test_detection_ops.py",
    "npx.multibox_detection": "covered in test_detection_ops.py (SSD tail)",
    "npx.multibox_prior": "covered in test_detection_ops.py (SSD tail)",
    "npx.multibox_target": "covered in test_detection_ops.py (SSD tail)",
    "npx.proposal": "covered in test_detection_ops.py (RPN)",
    "npx.psroi_pooling": "covered in test_detection_ops.py (R-FCN)",
    "npx.roi_align": "covered in test_detection_ops.py",
    # PR 8 fused kernel tier: ops with tuple/stateful signatures the
    # numeric sweep cannot express — parity-swept in test_fused_ops.py
    "npx.fused_avg_pool2d": "pool_size-tuple op; fwd+bwd "
                            "parity in test_fused_ops.py",
    "npx.fused_batch_norm": "stats-writing multi-output; train+infer "
                            "parity in test_fused_ops.py",
    "npx.flash_attention": "covered in test_attention.py + "
                           "test_fused_ops.py (registered wrapper)",
    "npx.paged_attention": "slotted-KV decode attention (cache slab + "
                           "lengths inputs the generic sweep cannot "
                           "shape); kernel-vs-ref interpret parity, "
                           "int8 dequant, and engine poison isolation "
                           "in tests/test_decode.py",
    "npx.fused_image_augment": "PRNGKey-data input (uint32) the numeric "
                               "FD sweep cannot differentiate; numpy-"
                               "reference fwd + grad-through-normalize "
                               "parity in test_imagerec_pool.py",
    # layout-record dispatch registrations (note_layout surface); the
    # kernels are covered functionally elsewhere
    "npx.convolution": "covered in test_gluon.py / "
                       "test_layout_equivalence.py",
    "npx.deconvolution": "covered in test_gluon.py (Conv*DTranspose)",
    "npx.pooling": "covered in test_gluon.py / "
                   "test_layout_equivalence.py",
}


def _resolve(name):
    mod = mx.np if name.startswith("np.") else mx.npx
    return getattr(mod, name.split(".", 1)[1])


def _np_ref(name):
    return getattr(np, name.split(".", 1)[1])


def _to_host(x):
    if hasattr(x, "asnumpy"):
        return x.asnumpy()
    if isinstance(x, (list, tuple)):
        return type(x)(_to_host(e) for e in x)
    return x


def _compare(got, want, rtol, atol):
    if isinstance(want, (list, tuple)):
        got = _to_host(got)
        assert isinstance(got, (list, tuple)), f"want sequence, got {got!r}"
        assert len(got) == len(want), (len(got), len(want))
        for g, w in zip(got, want):
            _compare(g, w, rtol, atol)
        return
    if isinstance(want, str):
        assert str(got) == want, (got, want)
        return
    if isinstance(want, (bool, np.bool_)):
        assert bool(got) == bool(want), (got, want)
        return
    g = np.asarray(_to_host(got))
    w = np.asarray(want)
    assert g.shape == tuple(w.shape), (g.shape, w.shape, "shape mismatch")
    if w.dtype.kind in "fc":
        np.testing.assert_allclose(g.astype(np.float64),
                                   w.astype(np.float64),
                                   rtol=rtol, atol=atol, equal_nan=True)
    else:
        np.testing.assert_array_equal(g.astype(w.dtype), w)


def _as_mx(x):
    if isinstance(x, np.ndarray):
        return mx.np.array(x)
    return x


ALL_OPS = registry.list_ops()


def test_registry_fully_classified():
    """The contract: no unclassified ops. Prints the sweep census."""
    unclassified = [o for o in ALL_OPS if o not in SPECS and o not in EXEMPT]
    swept = sum(1 for o in ALL_OPS if o in SPECS)
    grads = sum(1 for o in ALL_OPS if SPECS.get(o, {}).get("grad"))
    print(f"\nop sweep census: {len(ALL_OPS)} registered, {swept} swept "
          f"({grads} with numeric-gradient checks), {len(EXEMPT)} exempt")
    assert not unclassified, f"unswept ops (add a spec or an exemption " \
                             f"with a reason): {unclassified}"
    stale = [o for o in list(SPECS) + list(EXEMPT) if o not in ALL_OPS]
    assert not stale, f"specs for unregistered ops: {stale}"


@pytest.mark.parametrize("name", [o for o in ALL_OPS if o in SPECS])
def test_forward(name):
    s = SPECS[name]
    raw = s["inputs"]()
    fn = _resolve(name)
    ref = s["ref"] or _np_ref(name)
    want = ref(*[x.copy() if isinstance(x, np.ndarray) else x
                 for x in raw], **s["kw"]) if s["ref"] else \
        _np_ref(name)(*[x.copy() if isinstance(x, np.ndarray) else x
                        for x in raw], **s["kw"])
    mx_args = [tuple(_as_mx(e) for e in x) if isinstance(x, tuple)
               else [_as_mx(e) for e in x] if isinstance(x, list)
               else _as_mx(x) for x in raw]
    got = fn(*mx_args, **s["kw"])
    if s.get("shape_only"):
        g = np.asarray(_to_host(got))
        assert g.shape == np.asarray(want).shape
        assert g.dtype == np.asarray(want).dtype
        return
    _compare(got, want, s["rtol"], s["atol"])


# ---------------------------------------------------------------------------
# grad_req add/null axis (VERDICT Next #3 down payment): the ~20 most-used
# differentiable ops, checked against the reference kWriteTo/kAddTo/kNullOp
# contract — 'add' accumulates across backwards instead of overwriting,
# 'null' allocates no grad buffer and backward leaves it None.
# ---------------------------------------------------------------------------
GRAD_REQ_OPS = [
    "np.add", "np.subtract", "np.multiply", "np.divide", "np.power",
    "np.exp", "np.log", "np.sqrt", "np.tanh", "np.sin", "np.cos",
    "np.square", "np.negative", "np.reciprocal", "np.arctan",
    "np.logaddexp", "np.dot", "np.matmul",
    "npx.relu", "npx.sigmoid",
    # PR 8: the fused kernel tier rides the same kWriteTo/kAddTo/kNullOp
    # contract as any op
    "npx.fused_bias_act", "npx.fused_norm_act_residual",
]


def _grad_once(name, raws, reqs):
    """One record+backward pass; returns the per-input grads (None for
    null-req inputs)."""
    from incubator_mxnet_tpu import autograd
    s = SPECS[name]
    fn = _resolve(name)
    nds = [mx.np.array(x) for x in raws]
    for nd, req in zip(nds, reqs):
        nd.attach_grad(grad_req=req)
    with autograd.record():
        out = fn(*nds, **s["kw"])
        loss = (out * out).sum()
    loss.backward()
    return nds, [nd.grad.asnumpy() if nd.grad is not None else None
                 for nd in nds]


@pytest.mark.parametrize("req", ["add", "null"])
@pytest.mark.parametrize("name", GRAD_REQ_OPS)
def test_backward_grad_req(name, req):
    s = SPECS[name]
    raws = s["inputs"]()
    assert all(isinstance(x, np.ndarray) and x.dtype.kind == "f"
               for x in raws), f"{name}: grad_req axis needs float inputs"
    # baseline: write semantics, single backward
    _, base = _grad_once(name, raws, ["write"] * len(raws))
    # axis under test on input 0; remaining inputs stay 'write' so the mix
    # is exercised too
    reqs = [req] + ["write"] * (len(raws) - 1)
    from incubator_mxnet_tpu import autograd
    fn = _resolve(name)
    nds = [mx.np.array(x) for x in raws]
    for nd, r in zip(nds, reqs):
        nd.attach_grad(grad_req=r)
    for _ in range(2):                      # two record+backward rounds
        with autograd.record():
            out = fn(*nds, **s["kw"])
            loss = (out * out).sum()
        loss.backward()
    if req == "null":
        assert nds[0].grad is None, \
            f"{name}: null grad_req allocated/wrote a grad buffer"
    else:
        np.testing.assert_allclose(
            nds[0].grad.asnumpy(), 2.0 * base[0], rtol=2e-4, atol=1e-5,
            err_msg=f"{name}: add grad_req did not accumulate")
    # write-req co-inputs overwrite (not accumulate) across the two rounds
    for nd, b in list(zip(nds, base))[1:]:
        np.testing.assert_allclose(nd.grad.asnumpy(), b,
                                   rtol=2e-4, atol=1e-5)


def test_grad_req_census():
    """Census line, printed like the forward sweep's."""
    missing = [o for o in GRAD_REQ_OPS if o not in SPECS
               or not SPECS[o].get("grad")]
    assert not missing, f"grad_req axis lists non-grad ops: {missing}"
    print(f"\ngrad_req sweep census: {len(GRAD_REQ_OPS)} most-used "
          f"differentiable ops x {{add, null}} axes "
          f"(write covered by test_backward_numeric)")


@pytest.mark.parametrize(
    "name", [o for o in ALL_OPS if SPECS.get(o, {}).get("grad")])
def test_backward_numeric(name):
    s = SPECS[name]
    raw = [x for x in s["inputs"]()]
    # only all-float-array signatures take the finite-difference path
    arrays = [x for x in raw if isinstance(x, np.ndarray)]
    others = [x for x in raw if not isinstance(x, np.ndarray)]
    assert arrays and not others and all(
        a.dtype.kind == "f" for a in arrays), \
        f"{name}: grad spec requires all-float inputs"
    fn = _resolve(name)

    def loss(*nds):
        out = fn(*nds, **s["kw"])
        return (out * out).sum() if name != "np.prod" else out.sum()

    check_numeric_gradient(loss, arrays, rtol=2e-2, atol=s["grad_atol"])
