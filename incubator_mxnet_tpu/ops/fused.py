"""mx.ops.fused — offender-driven fused op tier (Pallas + jnp fallback).

Reference: MXNet's `MXNET_USE_FUSION` pointwise RTC fusion
(src/operator/fusion/fused_op.cu) and the oneDNN/AMP graph passes fused
exactly these chains on GPU/CPU. TPU-native: the `mx.inspect` roofline
attribution (PR 7) ranks the compiled step's fusions by bytes moved, and
this module hand-fuses the top memory-bound classes it found in the
ResNet-18 train step (`tools/offenders.py --model resnet18` prints the
ranking; the classes' intensities below are the cost model's):

  op                      kills offender class              kernel
  ----------------------  --------------------------------  ----------------
  norm_act_residual       multiply_multiply_fusion (0.26    apply_scale_
                          FLOP/B, 59 instances: BN apply +  shift_act
                          relu + residual-add chains)
  bias_act                convert/select pointwise chains   apply_scale_
                          after dense/conv                  shift_act
  bn_inference            folded BN-inference scale/shift   apply_scale_
                          (+ optional act/residual)         shift_act
  batch_norm              training BN: batch stats + the    none: the jnp
                          apply (+ act, + residual) as ONE  composition,
                          dispatch-level op                 everywhere
  avg_pool2d              reduce-window (0.18 FLOP/B, 35    none: f32
                          instances) — non-overlapping avg  reshape+mean,
                          pool incl. GlobalAvgPool, with a  everywhere
                          broadcast backward

The first three are a Pallas TPU kernel (ops/pallas_kernels.py) with a
mathematically identical `jnp` composition fallback off-TPU — the
`*_ref` functions here ARE the fallback, so CPU gradient parity is exact
by construction and the kernels are interpret-mode tested against them.
On the kernel path the backward is a hand-derived custom_vjp (one
recompute of the pre-activation, then the analytic chain).

The two ops a train step is made of, `batch_norm` and `avg_pool2d`, take
no kernel on any platform: they lower to that composition (`_ref_apply`,
`avg_pool2d_ref`) and JAX differentiates it. A Pallas call takes its
(M, C) operand row-major, while the TPU compiler keeps convolution
activations batch-minor; every call in a train step was therefore wrapped
in two physical copies and a reshape of a whole activation, and the
element-wise work could no longer ride in the convolutions' own fusions
(PERF.md §6, PR 32: ResNet-50's step moved 116 GB where the composition
moves 47). The op granularity, the statistics protocol and the AMP class
are what they were; only the lowering of the apply stage changed.

Gating: the gluon rewrites (nn.Dense/_Conv/BatchNorm/_Pool, model-zoo
residual blocks) engage only when `fusion_enabled()` — an explicit
`fusion_scope(True)` / `set_fusion_default(True)` AND the
`MXNET_USE_FUSION` env knob (default on). `FusedTrainStep` /
`FusedInferStep` enter the scope automatically, so the flagship fused
step gets the fused ops by default while eager paths stay unchanged
unless opted in. `MXNET_FUSION_INTERPRET=1` runs the Pallas kernels in
interpret mode (CI exercises the kernel path on CPU); on a TPU, where
the kernels compile, asking for interpret mode is an error.

Counters: `profiler.fused_stats()` / telemetry `fused.*` —
`pallas_calls` (kernel-path dispatches) vs `fallback_calls` (a kernel
was wanted and the jnp composition served). `batch_norm` and
`avg_pool2d` count in neither: their composition is the route, not a
fallback. Inside a jitted step these count per TRACE (path choices
baked into the program), eagerly they count per call.
"""
from __future__ import annotations

import functools
import logging
import threading
from contextlib import contextmanager

import numpy as _np

from ..base import MXNetError, get_env
from ..telemetry.registry import stats_group as _stats_group
from . import pallas_kernels as _pk

__all__ = ["bias_act", "norm_act_residual", "bn_inference", "batch_norm",
           "avg_pool2d", "image_augment", "paged_attention",
           "bias_act_ref",
           "norm_act_residual_ref", "bn_inference_ref", "avg_pool2d_ref",
           "paged_attention_ref",
           "fusion_scope",
           "fusion_enabled", "set_fusion_default", "set_use_fusion",
           "set_interpret", "fused_stats", "FUSED_STATS", "FUSABLE_ACTS"]

FUSABLE_ACTS = _pk.ACTS

FUSED_STATS = _stats_group("fused", {
    "pallas_calls": 0,       # dispatches that took a Pallas kernel path
    "fallback_calls": 0,     # dispatches served by the jnp composition
    "device_augment_calls": 0,  # image_augment programs built (per trace)
    "paged_attention_calls": 0,  # paged_attention dispatches (per trace
                                 # inside the jitted decode programs)
    # of those that took the kernel, by the block body their static
    # shapes select (`pallas_kernels.paged_body`)
    "paged_flat_traces": 0,
    "paged_head_major_traces": 0,
    # reads of a named cache LEAF (`layer=None`), kernel or composition,
    # by kind: a leaf that grows with the request and may be another
    # layer's (`shared`), a ring under a window (`window`)
    "paged_shared_traces": 0,
    "paged_window_traces": 0,
})
_STATS = FUSED_STATS


logger = logging.getLogger("mx.ops.fused")


def fused_stats(reset=False):
    """Snapshot of the fused-tier path counters (see module docstring for
    the trace-time caveat). Also via profiler.fused_stats()."""
    return _STATS.snapshot(reset=reset)


def _fell_back(op, why):
    """Count one dispatch served by the jnp composition and say which op
    it was (DEBUG on the `mx.ops.fused` logger): the counter alone cannot
    name the op, and on a TPU a fallback is a kernel that did not run."""
    _STATS["fallback_calls"] += 1
    logger.debug("%s -> jnp composition: %s", op, why)


# ---------------------------------------------------------------------------
# gating: scope/default AND the MXNET_USE_FUSION env knob
# ---------------------------------------------------------------------------
_SCOPE = threading.local()
_DEFAULT = [False]
_ENV_FUSION = [None]       # None = re-read MXNET_USE_FUSION
_INTERPRET = [None]        # None = re-read MXNET_FUSION_INTERPRET


def _env_use_fusion():
    if _ENV_FUSION[0] is None:
        _ENV_FUSION[0] = bool(get_env("MXNET_USE_FUSION", True, bool))
    return _ENV_FUSION[0]


def set_use_fusion(flag):
    """Override the MXNET_USE_FUSION kill switch at runtime (None =
    re-read the env). Returns the previous effective setting."""
    prev = _env_use_fusion()
    _ENV_FUSION[0] = None if flag is None else bool(flag)
    return prev


@contextmanager
def fusion_scope(active=True):
    """Enable (or force-disable) the fused-op rewrites for the dynamic
    extent — the hook FusedTrainStep/FusedInferStep use around tracing."""
    prev = getattr(_SCOPE, "value", None)
    _SCOPE.value = bool(active)
    try:
        yield
    finally:
        _SCOPE.value = prev


def set_fusion_default(flag):
    """Process-wide default outside any fusion_scope (eager opt-in).
    Returns the previous default."""
    prev = _DEFAULT[0]
    _DEFAULT[0] = bool(flag)
    return prev


def fusion_enabled():
    """True when gluon blocks should route through the fused ops: an
    active scope (or the process default) AND MXNET_USE_FUSION."""
    v = getattr(_SCOPE, "value", None)
    if v is None:
        v = _DEFAULT[0]
    return bool(v) and _env_use_fusion()


def set_interpret(flag):
    """Run the Pallas kernels in interpret mode (CPU tests/CI; env:
    MXNET_FUSION_INTERPRET; an error on a TPU). None = re-read the env.
    Returns previous."""
    prev = _interpret()
    _INTERPRET[0] = None if flag is None else bool(flag)
    return prev


def _interpret():
    if _INTERPRET[0] is None:
        _INTERPRET[0] = bool(get_env("MXNET_FUSION_INTERPRET", False, bool))
    return _INTERPRET[0]


def _on_tpu():
    # the TPU platform only: any other accelerator must use the jnp
    # fallback, not the TPU-shaped Pallas kernels
    from ..device import tpu_platform_available
    return tpu_platform_available()


def _resolve_interpret(interpret):
    """The op's `interpret` argument, defaulted from set_interpret /
    MXNET_FUSION_INTERPRET. Interpret mode is how CPU tests reach the
    kernel bodies; on a TPU it would run them in the interpreter at a
    small fraction of the chip's speed while the counters report a
    kernel, so there it is an error."""
    interpret = _interpret() if interpret is None else bool(interpret)
    if interpret and _on_tpu():
        raise MXNetError(
            "Pallas interpret mode (MXNET_FUSION_INTERPRET / "
            "set_interpret / interpret=True) is for CPU tests; on a TPU "
            "the kernels compile — unset it")
    return interpret


# ---------------------------------------------------------------------------
# reference compositions — the off-TPU fallback AND the parity oracle
# ---------------------------------------------------------------------------
def _jnp():
    import jax.numpy as jnp
    return jnp


def _act32(u, act_type):
    import jax
    return _pk._act_f32(jax, _jnp(), u, act_type)


def _bshape(ndim, axis, c):
    shape = [1] * ndim
    shape[axis] = c
    return tuple(shape)


def _ref_apply(x, scale, shift, residual, act_type, axis):
    """act(x [*scale] + shift [+ residual]) — f32 internal, cast out."""
    jnp = _jnp()
    axis = axis % x.ndim
    c = x.shape[axis]
    bshape = _bshape(x.ndim, axis, c)
    u = x.astype(jnp.float32)
    if scale is not None:
        u = u * scale.reshape(bshape).astype(jnp.float32)
    u = u + shift.reshape(bshape).astype(jnp.float32)
    if residual is not None:
        u = u + residual.astype(jnp.float32)
    return _act32(u, act_type).astype(x.dtype)


def bias_act_ref(x, bias, act_type="relu", axis=-1):
    """Unfused composition of bias_act (the fallback and parity oracle)."""
    return _ref_apply(x, None, bias, None, act_type, axis)


def norm_act_residual_ref(x, scale, shift, residual, act_type="relu",
                          axis=-1):
    """Unfused composition of norm_act_residual."""
    return _ref_apply(x, scale, shift, residual, act_type, axis)


def _fold_bn(gamma, beta, mean, var, eps):
    """(scale, shift) f32 fold of the BN affine: scale = gamma*rsqrt(var
    + eps), shift = beta - mean*scale (gamma/beta optional)."""
    import jax
    jnp = _jnp()
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    scale = inv if gamma is None else gamma.astype(jnp.float32) * inv
    shift = -mean.astype(jnp.float32) * scale
    if beta is not None:
        shift = shift + beta.astype(jnp.float32)
    return scale, shift


def bn_inference_ref(x, gamma, beta, mean, var, eps=1e-5, axis=-1,
                     act_type=None, residual=None):
    """Unfused composition of bn_inference."""
    scale, shift = _fold_bn(gamma, beta, mean, var, eps)
    return _ref_apply(x, scale, shift, residual, act_type, axis)


def paged_attention_ref(q, k_slab, v_slab, lengths, layer,
                        k_scale=None, v_scale=None, rows=None, window=None,
                        scale=None, out_dtype=None):
    """Unfused composition of paged decode attention over the serve
    KV-pool slab — the fallback and parity oracle. Reads the WHOLE
    (S, T) page per lane and masks to `[0, lengths + j]` per chunk
    query j (the O(max_len) path the Pallas kernel's block-sparse
    clamped reads replace).

    `q`: (S, C, H, D) — C chunk queries per lane at positions
    `lengths[s] + j`. `k_slab`/`v_slab`: (rows, layers, T, H, D) with
    rows > S (lane s reads row s). `k_scale`/`v_scale`: optional
    per-position f32 dequant scales (rows, layers, T) for int8 slabs.

    `layer=None` reads a cache LEAF instead: `k_slab`/`v_slab` are
    (rows, T, Hkv * D), one named array that any layer may read, with
    Hkv KV heads of q's width D; query head g reads KV head
    `g // (H // Hkv)`. Lane s reads row `rows[s]` (default s).
    `window=w` makes the leaf a RING of T positions (position p at
    `p % T`, positions `[0, lengths]` written so far): the one query of
    a lane (C == 1) sees positions `(lengths - w, lengths]`.
    `scale` replaces `1 / sqrt(D)`, `out_dtype` q's dtype."""
    import jax
    jnp = _jnp()
    s_lanes, c, _h, d = q.shape
    scale = (1.0 / float(d) ** 0.5) if scale is None else float(scale)
    if layer is None:
        return _leaf_attention_ref(q, k_slab, v_slab, lengths, rows,
                                   window, scale).astype(out_dtype or q.dtype)
    t = k_slab.shape[2]
    kk = k_slab[:s_lanes, layer]
    vv = v_slab[:s_lanes, layer]
    if k_scale is not None:
        kk = kk.astype(jnp.float32) * k_scale[:s_lanes, layer][..., None,
                                                               None]
    if v_scale is not None:
        vv = vv.astype(jnp.float32) * v_scale[:s_lanes, layer][..., None,
                                                               None]
    scores = jnp.einsum("schd,sthd->shct", q, kk) * scale
    pos = jnp.arange(t)
    mask = pos[None, None, :] <= (lengths[:, None, None]
                                  + jnp.arange(c)[None, :, None])
    scores = jnp.where(mask[:, None], scores, -1e30)
    att = jnp.einsum("shct,sthd->schd",
                     jax.nn.softmax(scores, axis=-1), vv)
    return att.astype(q.dtype)


def _leaf_attention_ref(q, k_leaf, v_leaf, lengths, rows, window, scale):
    """`paged_attention_ref`'s leaf mode: grouped heads, rows as data,
    the ring's window. Scores and probabilities in float32."""
    import jax
    jnp = _jnp()
    s_lanes, c, h, d = q.shape
    t = k_leaf.shape[1]
    hkv = k_leaf.shape[2] // d
    g = h // hkv
    if rows is None:
        # lane s reads row s: every row of the leaf gets a lane (the
        # garbage row's is idle) rather than the leaf a sliced copy — on
        # the chip the slice of a (65, 512, 1280) ring was a copy of its
        # own, 16 a micro-step
        pad = k_leaf.shape[0] - s_lanes
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))
        lengths = jnp.pad(lengths, (0, pad))
        kk, vv = k_leaf, v_leaf
    else:
        kk, vv = k_leaf[rows], v_leaf[rows]
    n = q.shape[0]
    kk = kk.reshape(n, t, hkv, d)
    vv = vv.reshape(n, t, hkv, d)
    qg = q.reshape(n, c, hkv, g, d)
    scores = jnp.einsum("scpgd,stpd->spgct", qg, kk,
                        preferred_element_type=jnp.float32) * scale
    slot = jnp.arange(t)
    if window is None:
        mask = slot[None, None, :] <= (lengths[:, None, None]
                                       + jnp.arange(c)[None, :, None])
    else:
        # slot j holds the newest written position congruent to j
        last = lengths[:, None]                             # (S, 1)
        held = slot[None, :] + t * ((last - slot[None, :]) // t)
        mask = ((held >= 0) & (held > last - window))[:, None, :]
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    att = jnp.einsum("spgct,stpd->scpgd", jax.nn.softmax(scores, axis=-1),
                     vv.astype(jnp.float32))
    return att.reshape(n, c, h, d)[:s_lanes]


def paged_attention(q, k_slab, v_slab, lengths, layer,
                    k_scale=None, v_scale=None, interpret=None, *,
                    rows=None, window=None, scale=None, out_dtype=None):
    """Paged decode attention over the slotted KV slab — the serve
    engine's per-layer attention read, in place (no per-layer copy of
    the cache). Routes to the Pallas block-sparse kernel on TPU (or in
    interpret mode for CPU CI) and to the identical masked-einsum
    composition otherwise; the choice is static per trace. Honors the
    MXNET_USE_FUSION kill switch (falls back, never fails).

    `layer=None` reads a named cache leaf (rows, T, Hkv * D) with
    grouped heads, `rows` as data and an optional ring `window`
    (`paged_attention_ref` has the contract). The leaf that grows with
    the request runs in the kernel, on the live-block grid; the ring
    read (at most `window` positions a lane) is the composition."""
    interpret = _resolve_interpret(interpret)
    _STATS["paged_attention_calls"] += 1
    if layer is None:
        return _paged_leaf(q, k_slab, v_slab, lengths, rows, window, scale,
                           out_dtype or q.dtype, interpret)
    if rows is not None or window is not None or out_dtype is not None:
        raise ValueError("rows=, window= and out_dtype= read a cache leaf "
                         "(layer=None)")
    if (_on_tpu() or interpret) and _env_use_fusion():
        out = _pk.paged_attention_fwd(q, k_slab, v_slab, lengths, layer,
                                      k_scale=k_scale, v_scale=v_scale,
                                      interpret=interpret, scale=scale)
        if out is not None:
            _STATS["pallas_calls"] += 1
            body = _pk.paged_body(q, k_slab, k_scale)
            _STATS[f"paged_{body}_traces"] += 1
            return out
    _fell_back("paged_attention",
               f"q {tuple(q.shape)} over slab {tuple(k_slab.shape)} "
               f"{k_slab.dtype} does not tile, or no TPU / fusion off")
    return paged_attention_ref(q, k_slab, v_slab, lengths, layer,
                               k_scale=k_scale, v_scale=v_scale, scale=scale)


def _paged_leaf(q, k_leaf, v_leaf, lengths, rows, window, scale, out_dtype,
                interpret):
    if q.shape[2] % (k_leaf.shape[2] // q.shape[3]):
        raise ValueError(
            f"{k_leaf.shape[2] // q.shape[3]} KV heads do not divide "
            f"{q.shape[2]} query heads")
    if window is not None:
        if q.shape[1] != 1:
            raise ValueError("a ring is read by one query a lane")
        _STATS["paged_window_traces"] += 1
        return paged_attention_ref(q, k_leaf, v_leaf, lengths, None,
                                   rows=rows, window=window, scale=scale,
                                   out_dtype=out_dtype)
    _STATS["paged_shared_traces"] += 1
    if (_on_tpu() or interpret) and _env_use_fusion():
        out = _pk.paged_attention_fwd(q, k_leaf, v_leaf, lengths, None,
                                      interpret=interpret, scale=scale,
                                      rows=rows, out_dtype=out_dtype)
        if out is not None:
            _STATS["pallas_calls"] += 1
            return out
    _fell_back("paged_attention",
               f"q {tuple(q.shape)} over leaf {tuple(k_leaf.shape)} "
               f"{k_leaf.dtype} does not tile, or no TPU / fusion off")
    return paged_attention_ref(q, k_leaf, v_leaf, lengths, None, rows=rows,
                               scale=scale, out_dtype=out_dtype)


def avg_pool2d_ref(x, pool_size, layout="NHWC"):
    """Unfused composition of the non-overlapping NHWC average pool
    (f32-accumulated reshape+mean)."""
    jnp = _jnp()
    ph, pw = pool_size
    n, h, w, c = x.shape
    xf = x.astype(jnp.float32).reshape(n, h // ph, ph, w // pw, pw, c)
    return jnp.mean(xf, axis=(2, 4)).astype(x.dtype)


# ---------------------------------------------------------------------------
# custom_vjp kernels over the (M, C) view — one builder per arity, memoized
# per static config so repeat traces reuse one callable identity
# ---------------------------------------------------------------------------
def _bwd_core(xf, scale32, g32):
    """Shared backward tail: (dx_f32, dscale_f32, dshift_f32) given the
    f32 input, f32 scale (or None) and the post-activation cotangent."""
    jnp = _jnp()
    dx = g32 if scale32 is None else g32 * scale32
    dscale = None if scale32 is None else jnp.sum(g32 * xf, axis=0)
    dshift = jnp.sum(g32, axis=0)
    return dx, dscale, dshift


def _act_grad(u, ct, act_type):
    """d(act)/du applied to ct, both f32, via jax.vjp of the f32 act —
    exactly the derivative jax AD of the reference composition uses."""
    import jax
    if act_type is None:
        return ct
    _, vjp = jax.vjp(lambda v: _act32(v, act_type), u)
    return vjp(ct)[0]


@functools.lru_cache(maxsize=None)
def _kernel_bias_act(act_type, interpret):
    import jax
    jnp = _jnp()

    @jax.custom_vjp
    def f(x2d, shift):
        return _pk.apply_scale_shift_act(x2d, None, shift, None, act_type,
                                         interpret)

    def f_fwd(x2d, shift):
        return f(x2d, shift), (x2d, shift)

    def f_bwd(saved, ct):
        x2d, shift = saved
        xf = x2d.astype(jnp.float32)
        u = xf + shift.reshape(1, -1).astype(jnp.float32)
        g = _act_grad(u, ct.astype(jnp.float32), act_type)
        dx, _, dshift = _bwd_core(xf, None, g)
        return dx.astype(x2d.dtype), dshift.astype(shift.dtype)

    f.defvjp(f_fwd, f_bwd)
    return f


@functools.lru_cache(maxsize=None)
def _kernel_scale_shift_act(act_type, interpret):
    import jax
    jnp = _jnp()

    @jax.custom_vjp
    def f(x2d, scale, shift):
        return _pk.apply_scale_shift_act(x2d, scale, shift, None, act_type,
                                         interpret)

    def f_fwd(x2d, scale, shift):
        return f(x2d, scale, shift), (x2d, scale, shift)

    def f_bwd(saved, ct):
        x2d, scale, shift = saved
        xf = x2d.astype(jnp.float32)
        s32 = scale.reshape(1, -1).astype(jnp.float32)
        u = xf * s32 + shift.reshape(1, -1).astype(jnp.float32)
        g = _act_grad(u, ct.astype(jnp.float32), act_type)
        dx, dscale, dshift = _bwd_core(xf, s32, g)
        return (dx.astype(x2d.dtype), dscale.astype(scale.dtype),
                dshift.astype(shift.dtype))

    f.defvjp(f_fwd, f_bwd)
    return f


@functools.lru_cache(maxsize=None)
def _kernel_scale_shift_act_residual(act_type, interpret):
    import jax
    jnp = _jnp()

    @jax.custom_vjp
    def f(x2d, scale, shift, res):
        return _pk.apply_scale_shift_act(x2d, scale, shift, res, act_type,
                                         interpret)

    def f_fwd(x2d, scale, shift, res):
        return f(x2d, scale, shift, res), (x2d, scale, shift, res)

    def f_bwd(saved, ct):
        x2d, scale, shift, res = saved
        xf = x2d.astype(jnp.float32)
        s32 = scale.reshape(1, -1).astype(jnp.float32)
        u = (xf * s32 + shift.reshape(1, -1).astype(jnp.float32)
             + res.astype(jnp.float32))
        g = _act_grad(u, ct.astype(jnp.float32), act_type)
        dx, dscale, dshift = _bwd_core(xf, s32, g)
        return (dx.astype(x2d.dtype), dscale.astype(scale.dtype),
                dshift.astype(shift.dtype), g.astype(res.dtype))

    f.defvjp(f_fwd, f_bwd)
    return f


def _apply(x, scale, shift, residual, act_type, axis, interpret):
    """Route one apply through the Pallas kernel when viable (TPU or
    interpret mode, channels minor, VMEM-tileable, supported act), else
    the identical jnp composition. The decision is static per trace."""
    if act_type is not None and not _pk.supported_act(act_type):
        raise ValueError(f"unsupported fused activation {act_type!r}; "
                         f"supported: {FUSABLE_ACTS}")
    interpret = _resolve_interpret(interpret)
    axis_n = axis % x.ndim
    kernel_ok = (_on_tpu() or interpret) and axis_n == x.ndim - 1
    if kernel_ok:
        c = x.shape[-1]
        m = 1
        for d in x.shape[:-1]:
            m *= d
        n_bufs = 2 + (1 if residual is not None else 0)
        bm = _pk._block_rows(m, c, n_bufs)
        kernel_ok = bm > 0 and m % bm == 0
    if not kernel_ok:
        _fell_back("scale_shift_act",
                   f"x {tuple(x.shape)} axis {axis}: channels not minor, "
                   f"no row tile, or no TPU")
        return _ref_apply(x, scale, shift, residual, act_type, axis)
    _STATS["pallas_calls"] += 1
    c = x.shape[-1]
    x2d = x.reshape(-1, c)
    if scale is None:
        out = _kernel_bias_act(act_type, interpret)(x2d, shift)
    elif residual is None:
        out = _kernel_scale_shift_act(act_type, interpret)(x2d, scale,
                                                           shift)
    else:
        out = _kernel_scale_shift_act_residual(act_type, interpret)(
            x2d, scale, shift, residual.reshape(-1, c))
    return out.reshape(x.shape)


# ---------------------------------------------------------------------------
# public fused ops (raw jax arrays in/out; npx wrappers own NDArray glue)
# ---------------------------------------------------------------------------
def bias_act(x, bias, act_type="relu", axis=-1, interpret=None):
    """Fused y = act(x + bias) with per-channel bias on `axis`."""
    return _apply(x, None, bias, None, act_type, axis, interpret)


def norm_act_residual(x, scale, shift, residual, act_type="relu", axis=-1,
                      interpret=None):
    """Fused y = act(x*scale + shift + residual) — the normalize-apply /
    activation / residual-add tail of a residual block in ONE pass
    (scale/shift are the folded norm affine; see `bn_inference` for the
    fold). The 0.26-intensity `multiply_multiply_fusion` killer."""
    return _apply(x, scale, shift, residual, act_type, axis, interpret)


def bn_inference(x, gamma, beta, mean, var, eps=1e-5, axis=-1,
                 act_type=None, residual=None, interpret=None):
    """Folded BN-inference scale/shift (+ optional act/residual): the
    running stats fold into ONE per-channel affine at trace time, then a
    single fused apply pass."""
    scale, shift = _fold_bn(gamma, beta, mean, var, eps)
    return _apply(x, scale, shift, residual, act_type, axis, interpret)


def batch_norm(x, gamma, beta, running_mean, running_var, momentum=0.9,
               eps=1e-5, training=True, axis=1, use_global_stats=False,
               sync_axis_name=None, act_type=None, residual=None):
    """Batch norm + optional activation + optional pre-activation residual
    add as ONE dispatch-level op, lowered to the jnp composition.

    Identical stats protocol to ops.nn.batch_norm (same f32 moments, same
    pmean sync, same running-stat update; returns (out, new_rm, new_rv)).
    The normalize/scale/shift(/act/residual) is `_ref_apply` (f32 inside,
    cast out) on every platform, differentiated by JAX: no Pallas call,
    so that XLA fuses the apply with the convolution that produces `x`
    and the one that consumes the result, in the layout the convolutions
    want (see the module docstring). Gradients flow through the batch
    moments exactly as in the unfused composition — scale/shift are
    traced functions of x."""
    import jax
    jnp = _jnp()
    lax = jax.lax
    reduce_axes = tuple(i for i in range(x.ndim) if i != (axis % x.ndim))
    if training and not use_global_stats:
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=reduce_axes)
        mean_sq = jnp.mean(jnp.square(xf), axis=reduce_axes)
        if sync_axis_name is not None:
            mean = lax.pmean(mean, sync_axis_name)
            mean_sq = lax.pmean(mean_sq, sync_axis_name)
        var = mean_sq - jnp.square(mean)
        new_rm = momentum * running_mean + (1 - momentum) * mean
        new_rv = momentum * running_var + (1 - momentum) * var
    else:
        mean, var = running_mean, running_var
        new_rm, new_rv = running_mean, running_var
    scale, shift = _fold_bn(gamma, beta, mean, var, eps)
    out = _ref_apply(x, scale, shift, residual, act_type, axis)
    return out, new_rm, new_rv


def image_augment(images, key, mean=None, std=None, crop_hw=None,
                  rand_mirror=False, out_dtype="float32", interpret=None):
    """Device-side half of the input pipeline as ONE jitted batched kernel:
    optional per-image random crop (when the staged images are larger than
    `crop_hw`), optional per-image horizontal mirror, [0,1] scale +
    per-channel mean/std normalize, cast — the work `ImageRecordIter`'s
    float32 path used to burn host cores on (uint8 handoff moves it here,
    behind the 4x-smaller H2D transfer).

    `images`: (N, H, W, 3) NHWC — uint8 raw pixels (scaled by 1/255) or a
    float array already in [0, 1] (gradients flow through the affine for
    float inputs; the crop/mirror randomness does not block them).
    `key`: PRNGKey DATA as a uint32 (2,) array — an array argument, not a
    static seed, so per-(epoch, batch) keys swap without a retrace.
    `mean`/`std` are static
    per-channel tuples in [0, 1] units; `crop_hw`/`rand_mirror`/`out_dtype`
    are static too.

    jnp-only by design: every stage is pointwise/slice-shaped and XLA
    fuses the chain into one kernel on any backend — there is no separate
    Pallas path, so `interpret` is accepted for tier uniformity and
    ignored. Counted per program build in `fused.device_augment_calls`
    (inside jit the body runs at trace time only)."""
    import jax
    jnp = _jnp()
    _STATS["device_augment_calls"] += 1
    x = images
    if jnp.issubdtype(x.dtype, jnp.integer):
        x = x.astype(jnp.float32) * (1.0 / 255.0)
    elif x.dtype != jnp.float32:
        x = x.astype(jnp.float32)
    kc, km = jax.random.split(jnp.asarray(key))
    if crop_hw is not None:
        ch, cw = int(crop_hw[0]), int(crop_hw[1])
        n, h, w = x.shape[0], x.shape[1], x.shape[2]
        if (h, w) != (ch, cw):
            ky, kx = jax.random.split(kc)
            y0 = jax.random.randint(ky, (n,), 0, h - ch + 1)
            x0 = jax.random.randint(kx, (n,), 0, w - cw + 1)
            x = jax.vmap(
                lambda img, yy, xx: jax.lax.dynamic_slice(
                    img, (yy, xx, 0), (ch, cw, 3)))(x, y0, x0)
    if rand_mirror:
        flips = jax.random.bernoulli(km, 0.5, (x.shape[0],))
        x = jnp.where(flips[:, None, None, None], x[:, :, ::-1, :], x)
    if mean is not None:
        x = x - jnp.asarray(mean, jnp.float32)
    if std is not None:
        x = x / jnp.asarray(std, jnp.float32)
    return x.astype(out_dtype)


# bounded: the key includes the pooled SHAPE, and each entry pins a
# custom_vjp callable whose identity also keys jax's compiled-program
# caches — unbounded growth under variable-resolution workloads (same
# rationale as the telemetry model_flops FIFO bound)
@functools.lru_cache(maxsize=64)
def _kernel_avg_pool(h, w, ph, pw, dtype, interpret):
    import jax

    @jax.custom_vjp
    def f(x):
        return _pk.avg_pool2d_fwd(x, ph, pw, interpret)

    def f_fwd(x):
        return f(x), ()

    def f_bwd(_res, dy):
        return (_pk.avg_pool2d_bwd(dy, h, w, ph, pw,
                                   interpret).astype(dtype),)

    f.defvjp(f_fwd, f_bwd)
    return f


def avg_pool2d(x, pool_size, layout="NHWC"):
    """Non-overlapping (kernel == stride, no padding) NHWC average pool —
    covers AvgPool2D(k, k) and the GlobalAvgPool2D shape (pool_size =
    spatial dims, keepdims output). The f32 reshape+mean composition
    (`avg_pool2d_ref`) on every platform: its XLA gradient is already a
    broadcast, not a reduce-window scatter, and it pins no layout between
    the last convolution and the classifier (the Pallas pair
    `_kernel_avg_pool` did; no op routes to it)."""
    ph, pw = (pool_size, pool_size) if isinstance(pool_size, int) \
        else tuple(pool_size)
    if layout != "NHWC" or x.ndim != 4:
        raise ValueError("fused avg_pool2d is NHWC 2-D only "
                         f"(got layout={layout!r}, ndim={x.ndim})")
    h, w = x.shape[1], x.shape[2]
    if h % ph or w % pw:
        raise ValueError(f"pool {ph}x{pw} must divide spatial dims "
                         f"{h}x{w} (non-overlapping pooling)")
    return avg_pool2d_ref(x, (ph, pw))


# Dispatch-record AMP classes (PR2 metadata; picked up by register_op in
# numpy_extension): the apply ops compute in f32 internally and are safe
# to FEED in the autocast dtype — except the stats-bearing batch_norm
# family, pinned f32 like ops.nn.batch_norm. Pooling matches nn.pooling.
for _f, _cls in ((bias_act, "safe"), (norm_act_residual, "unsafe"),
                 (bn_inference, "unsafe"), (batch_norm, "unsafe"),
                 (avg_pool2d, "safe"), (image_augment, "neutral"),
                 (paged_attention, "safe")):
    _f._amp_class = _cls
del _f, _cls
