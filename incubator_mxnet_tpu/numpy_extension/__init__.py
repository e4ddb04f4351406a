"""mx.npx — NumPy extensions: NN operators + control flow.

Reference: python/mxnet/numpy_extension (the `_npx_*` op namespace: activations,
softmax, pick, topk, control flow `_npx_foreach/_npx_while_loop/_npx_cond`
(src/operator/npx_control_flow.cc:513-918), sequence ops, set_np scope).
TPU-native: wrappers over ops/nn.py jax compositions; control flow lowers to
lax.scan / lax.while_loop / lax.cond — autograd through foreach/cond is native
jax vjp; while_loop is forward-only exactly like XLA requires.
"""
from __future__ import annotations

import functools

import numpy as _onp

from ..base import MXNetError, name_to_dtype
from ..ndarray import NDArray, _as_nd, _wrap
from ..ops.registry import (invoke, register_op, get_op, record_key,
                            note_layout)
from ..ops import nn as _nn
from ..ops import fused as _fused_ops
from ..ops import segment as _segment
from .. import random as _grandom
from .. import autograd as _autograd

__all__ = [
    "relu", "sigmoid", "tanh", "softmax", "log_softmax", "masked_softmax",
    "gelu", "leaky_relu", "elu", "selu", "silu", "swish", "activation",
    "one_hot", "pick", "topk", "sequence_mask", "embedding", "dropout",
    "batch_norm", "layer_norm", "group_norm", "instance_norm", "rms_norm",
    "l2_normalization", "fully_connected", "convolution", "deconvolution",
    "pooling", "foreach", "while_loop", "cond", "scan",
    "set_np", "reset_np", "is_np_array", "is_np_shape", "use_np", "erf",
    "erfinv", "gamma", "gammaln", "digamma", "multi_sum_sq", "clip_by_global_norm",
    "arange_like", "broadcast_like", "shape_array", "stop_gradient",
    "smooth_l1", "scaled_dot_product_attention",
]


def _unary(jfn, name, amp="neutral"):
    base_key = _segment.derive_key_cached(jfn)

    def fn(x, **kwargs):
        return invoke(functools.partial(jfn, **kwargs) if kwargs else jfn,
                      (_as_nd(x),), name=name, op=info,
                      key=record_key(base_key, kwargs))
    fn.__name__ = name
    register_op("npx." + name, fn, amp=amp)
    info = get_op("npx." + name)
    return fn


def _make_nn(fname, name=None):
    f = getattr(_nn, fname)
    base_key = _segment.derive_key_cached(f)

    def fn(*arrays, **kwargs):
        arrs = tuple(_as_nd(a) if not isinstance(a, NDArray) else a
                     for a in arrays)
        # array-valued kwargs (masks, lengths) close over as raw buffers —
        # they are op attributes, not differentiated inputs
        kwargs = {k: (v._arr if isinstance(v, NDArray) else v)
                  for k, v in kwargs.items()}
        return invoke(functools.partial(f, **kwargs) if kwargs else f,
                      arrs, name=name or fname, op=info,
                      key=record_key(base_key, kwargs))
    fn.__name__ = name or fname
    register_op("npx." + (name or fname), fn,
                amp=getattr(f, "_amp_class", "neutral"))
    info = get_op("npx." + (name or fname))
    return fn


import jax  # noqa: E402
import jax.numpy as _jnp  # noqa: E402

relu = _unary(jax.nn.relu, "relu")
sigmoid = _unary(jax.nn.sigmoid, "sigmoid")
tanh = _unary(_jnp.tanh, "tanh")
erf = _unary(jax.scipy.special.erf, "erf")
erfinv = _unary(jax.scipy.special.erfinv, "erfinv")
gamma = _unary(lambda x: _jnp.exp(jax.scipy.special.gammaln(x)), "gamma")
gammaln = _unary(jax.scipy.special.gammaln, "gammaln")
digamma = _unary(jax.scipy.special.digamma, "digamma")
softplus = _unary(jax.nn.softplus, "softplus")
log_sigmoid = _unary(jax.nn.log_sigmoid, "log_sigmoid")
silu = _unary(jax.nn.silu, "silu")
swish = silu
stop_gradient = _unary(jax.lax.stop_gradient, "stop_gradient")

softmax = _make_nn("softmax")
log_softmax = _make_nn("log_softmax")
masked_softmax = _make_nn("masked_softmax")
activation = _make_nn("activation")
layer_norm = _make_nn("layer_norm")
group_norm = _make_nn("group_norm")
instance_norm = _make_nn("instance_norm")
rms_norm = _make_nn("rms_norm")
l2_normalization = _make_nn("l2_normalize", "l2_normalization")
one_hot = _make_nn("one_hot")
pick = _make_nn("pick")
topk = _make_nn("topk")
sequence_mask = _make_nn("sequence_mask")
embedding = _make_nn("embedding")


def rnn(data, parameters, state, state_cell=None, mode="lstm",
        state_size=None, num_layers=1, bidirectional=False, p=0.0,
        state_outputs=False):
    """Fused multi-layer (bi)RNN on a FLAT parameter vector (≙ the
    reference's `_npx.rnn` fused op, src/operator/rnn.cc:1 /
    python/mxnet/numpy_extension/_op.py:847 — VERDICT-r4 Next #10).

    data (T, N, C) time-major; `parameters` is the reference layout:
    all W_i2h/W_h2h gate blocks layer-major with direction inner, then
    all b_i2h/b_h2h pairs in the same order. Gate order LSTM [i,f,g,o],
    GRU [r,z,n] (reference/cuDNN convention). `state` (L*D, N, H) and,
    for LSTM, `state_cell` likewise. Returns `out`, or
    (out, h_n[, c_n]) when state_outputs=True."""
    if state_size is None:
        raise MXNetError("state_size is required")
    gates = {"lstm": 4, "gru": 3, "rnn_tanh": 1, "rnn_relu": 1}
    if mode not in gates:
        raise MXNetError(f"unknown rnn mode {mode!r}")
    G = gates[mode]
    # static layer-config ints, never traced values
    H, L = int(state_size), int(num_layers)  # mxlint: disable=trace-host-capture
    D = 2 if bidirectional else 1
    C = int(data.shape[-1])
    training = _autograd.is_training()
    key = _grandom.next_key() if (p > 0 and training) else None

    arrs = [_as_nd(data), _as_nd(parameters), _as_nd(state)]
    if mode == "lstm":
        if state_cell is None:
            raise MXNetError("lstm needs state_cell")
        arrs.append(_as_nd(state_cell))

    def run(x, flat, h0, *maybe_c):
        off = 0

        def take(n, shape):
            # static unpack offset over the flat param vector: advances
            # during tracing only, reset per run() call — by design
            nonlocal off
            w = flat[off:off + n].reshape(shape)
            off += n  # mxlint: disable=trace-closure-mutation
            return w

        params = {}
        for layer in range(L):
            insz = C if layer == 0 else H * D
            for d in range(D):
                params[(layer, d)] = {
                    "wx": take(G * H * insz, (G * H, insz)),
                    "wh": take(G * H * H, (G * H, H))}
        for layer in range(L):
            for d in range(D):
                params[(layer, d)]["bx"] = take(G * H, (G * H,))
                params[(layer, d)]["bh"] = take(G * H, (G * H,))
        if off != flat.shape[0]:
            # ≙ the reference op's parameter-size CHECK (rnn.cc): a
            # mismatched layout must not silently misalign every block
            raise MXNetError(
                f"parameters has {flat.shape[0]} elements; the "
                f"{mode} L={L} D={D} H={H} C={C} layout needs {off}")
        st = (h0,) + tuple(maybe_c)
        out, new_state = _nn.rnn(x, params, st, mode=mode, num_layers=L,
                                 bidirectional=(D == 2), dropout_rate=p,
                                 key=key, training=training)
        return (out,) + tuple(new_state)

    res = invoke(run, tuple(arrs), name="rnn_fused", multi_out=True)
    return tuple(res) if state_outputs else res[0]


register_op("npx.rnn", rnn)
__all__.append("rnn")
scaled_dot_product_attention = _make_nn("scaled_dot_product_attention")


def _make_fused(fname, name=None):
    """npx wrapper over an ops.fused kernel — same contract as _make_nn
    (arrays positional, static config via kwargs, dispatch-record key from
    the registration-precomputed base key)."""
    f = getattr(_fused_ops, fname)
    base_key = _segment.derive_key_cached(f)

    def fn(*arrays, **kwargs):
        arrs = tuple(_as_nd(a) if not isinstance(a, NDArray) else a
                     for a in arrays)
        # array-valued kwargs (e.g. bn_inference's residual=) close over
        # as raw buffers, same contract as _make_nn
        kwargs = {k: (v._arr if isinstance(v, NDArray) else v)
                  for k, v in kwargs.items()}
        # resolve the kernel-vs-fallback mode NOW so it enters the
        # dispatch key: a set_interpret() toggle must not replay programs
        # compiled for the other path
        kwargs.setdefault("interpret", _fused_ops._interpret())
        return invoke(functools.partial(f, **kwargs),
                      arrs, name=name or fname, op=info,
                      key=record_key(base_key, kwargs))
    fn.__name__ = name or fname
    register_op("npx." + (name or fname), fn,
                amp=getattr(f, "_amp_class", "neutral"))
    info = get_op("npx." + (name or fname))
    return fn


# fused kernel tier (ops/fused.py — Pallas on TPU, jnp composition
# elsewhere). Gluon blocks route here when fused.fusion_enabled().
fused_bias_act = _make_fused("bias_act", "fused_bias_act")
fused_norm_act_residual = _make_fused("norm_act_residual",
                                      "fused_norm_act_residual")
fused_bn_inference = _make_fused("bn_inference", "fused_bn_inference")
# device half of the uint8 input-pipeline handoff (crop/flip/normalize/
# cast as ONE batched kernel; ImageRecordIter device_augment mode and the
# DeviceFeed staging path call this) — jnp-only, no Pallas variant
fused_image_augment = _make_fused("image_augment", "fused_image_augment")


def fused_avg_pool2d(data, pool_size, layout="NHWC"):
    """Non-overlapping NHWC average pool (kernel == stride, no padding;
    GlobalAvgPool shapes included) as the f32 reshape+mean composition,
    whose backward is a broadcast — see ops.fused.avg_pool2d."""
    info = get_op("npx.fused_avg_pool2d")
    note_layout(info, layout)
    ps = (pool_size, pool_size) if isinstance(pool_size, int) \
        else tuple(pool_size)
    kw = {"pool_size": ps, "layout": layout}
    return invoke(functools.partial(_fused_ops.avg_pool2d, **kw),
                  (_as_nd(data),), name="fused_avg_pool2d", op=info,
                  key=record_key(_avg_pool_key, kw))


register_op("npx.fused_avg_pool2d", _fused_ops.avg_pool2d,
            amp=_fused_ops.avg_pool2d._amp_class)
_avg_pool_key = _segment.derive_key_cached(_fused_ops.avg_pool2d)


def fused_batch_norm(x, gamma, beta, running_mean, running_var, eps=1e-5,
                     momentum=0.9, axis=1, use_global_stats=False,
                     training=None, sync_axis_name=None, act_type=None,
                     residual=None):
    """Batch norm, optional activation and optional pre-activation
    residual add as ONE dispatch-level op (ops.fused.batch_norm); the
    apply stage is the jnp composition on every platform, left to XLA to
    fuse with its neighbours. Same running-stat write-back protocol as
    npx.batch_norm."""
    if training is None:
        training = _autograd.is_training()
    kw = dict(momentum=momentum, eps=eps, training=training, axis=axis,
              use_global_stats=use_global_stats,
              sync_axis_name=sync_axis_name, act_type=act_type)
    info = get_op("npx.fused_batch_norm")
    arrs = (_as_nd(x), _as_nd(gamma), _as_nd(beta), _as_nd(running_mean),
            _as_nd(running_var))
    if residual is not None:
        out, nm, nv = invoke(
            functools.partial(_bn_residual, **kw),
            arrs + (_as_nd(residual),),
            name="fused_batch_norm", op=info,
            key=record_key(_fused_bn_res_key, kw), multi_out=True)
    else:
        out, nm, nv = invoke(
            functools.partial(_fused_ops.batch_norm, **kw), arrs,
            name="fused_batch_norm", op=info,
            key=record_key(_fused_bn_key, kw), multi_out=True)
    if training and isinstance(running_mean, NDArray):
        with _autograd.pause():
            # adopt the (possibly pending) stat buffers like npx.batch_norm
            running_mean._set_arr(nm._data)
            running_var._set_arr(nv._data)
    return out


def _bn_residual(a, g, b, rm, rv, r, **kw):
    """Module-level residual variant: arrays positional so the dispatch
    derives a stable key (a per-call closure would key as None — no
    bulking, and a full vjp retrace per call under recording)."""
    return _fused_ops.batch_norm(a, g, b, rm, rv, residual=r, **kw)


register_op("npx.fused_batch_norm", _fused_ops.batch_norm,
            amp=_fused_ops.batch_norm._amp_class)
_fused_bn_key = _segment.derive_key_cached(_fused_ops.batch_norm)
_fused_bn_res_key = _segment.derive_key_cached(_bn_residual)


def flash_attention(query, key, value, causal=False, scale=None,
                    block_q=None, block_k=None):
    """Blockwise (flash) attention over (batch*heads, T, head_dim) —
    the ops.pallas_attention kernel registered as a first-class op:
    dispatch record + AMP class, so tools/opperf.py, AMP lists and inspect
    reports see it like any other op."""
    from ..ops.pallas_attention import flash_attention as _fa
    kw = dict(causal=causal, scale=scale, block_q=block_q,
              block_k=block_k)
    info = get_op("npx.flash_attention")
    return invoke(functools.partial(_fa, **kw),
                  (_as_nd(query), _as_nd(key), _as_nd(value)),
                  name="flash_attention", op=info,
                  key=record_key(_flash_key, kw))


def _register_flash_attention():
    from ..ops.pallas_attention import flash_attention as _fa
    _fa._amp_class = "safe"   # MXU-bound flops: run in the autocast dtype
    register_op("npx.flash_attention", _fa, amp="safe")
    return _segment.derive_key_cached(_fa)


_flash_key = _register_flash_attention()


def paged_attention(query, k_slab, v_slab, lengths, layer,
                    k_scale=None, v_scale=None, interpret=None):
    """Paged decode attention over a serve.kv_pool KV slab — the
    ops.fused block-sparse decode kernel registered as a first-class op
    (dispatch record + AMP class). `query` is (S, C, H, D) chunk queries;
    lane s reads slab row s of `layer`, positions `[0, lengths[s] + j]`;
    `k_scale`/`v_scale` dequantize int8 slabs per position."""
    from ..ops import fused as _fused
    kw = dict(layer=int(layer), interpret=interpret)
    info = get_op("npx.paged_attention")
    arrs = [_as_nd(query), _as_nd(k_slab), _as_nd(v_slab),
            _as_nd(lengths)]
    fn = _fused.paged_attention
    if k_scale is not None:
        arrs.extend([_as_nd(k_scale), _as_nd(v_scale)])
        call = lambda q, k, v, ln, ks, vs: fn(q, k, v, ln, k_scale=ks,
                                              v_scale=vs, **kw)
    else:
        call = lambda q, k, v, ln: fn(q, k, v, ln, **kw)
    return invoke(call, tuple(arrs), name="paged_attention", op=info,
                  key=record_key(_paged_key, kw))


def _register_paged_attention():
    from ..ops import fused as _fused
    register_op("npx.paged_attention", _fused.paged_attention,
                amp=_fused.paged_attention._amp_class)
    return _segment.derive_key_cached(_fused.paged_attention)


_paged_key = _register_paged_attention()

# layout-sensitive kernels get dispatch records too (PR 8): the npx
# wrappers below stamp each call's layout onto the record (note_layout),
# making the NHWC/NCHW choice introspectable next to the AMP class.
for _kn in ("conv", "conv_transpose", "pooling"):
    _k = getattr(_nn, _kn)
    register_op("npx." + {"conv": "convolution",
                          "conv_transpose": "deconvolution",
                          "pooling": "pooling"}[_kn], _k,
                amp=getattr(_k, "_amp_class", "neutral"))
del _kn, _k

__all__ += ["fused_bias_act", "fused_norm_act_residual",
            "fused_bn_inference", "fused_avg_pool2d", "fused_batch_norm",
            "fused_image_augment", "flash_attention", "paged_attention"]


def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25, **kwargs):
    arrs = (_as_nd(data),)
    kw = dict(act_type=act_type, slope=slope, **kwargs)
    if act_type == "prelu":
        return invoke(lambda x, g: _nn.leaky_relu(x, "prelu", gamma=g),
                      (_as_nd(data), _as_nd(gamma)), name="leaky_relu")
    if act_type == "rrelu" and _autograd.is_training():
        kw["key"] = _grandom.next_key()
        kw["training"] = True
    return invoke(functools.partial(_nn.leaky_relu, **kw), arrs,
                  name="leaky_relu")


def gelu(x, approximate=False):
    return invoke(functools.partial(jax.nn.gelu, approximate=approximate),
                  (_as_nd(x),), name="gelu")


def elu(x, alpha=1.0):
    return invoke(functools.partial(jax.nn.elu, alpha=alpha), (_as_nd(x),),
                  name="elu")


def selu(x):
    return invoke(jax.nn.selu, (_as_nd(x),), name="selu")


def dropout(data, p=0.5, axes=None, training=None):
    if training is None:
        training = _autograd.is_training()
    if not training or p <= 0:
        return _as_nd(data)
    key = _grandom.next_key()
    return invoke(lambda x: _nn.dropout(x, p, key, True, axes), (_as_nd(data),),
                  name="dropout")


def batch_norm(x, gamma, beta, running_mean, running_var, eps=1e-5,
               momentum=0.9, axis=1, use_global_stats=False, training=None,
               sync_axis_name=None):
    """Functional batch norm; returns output only and writes running stats
    in-place on the NDArrays (eager path). Inside traces use ops.nn.batch_norm
    directly through the state-update protocol (gluon/block.py)."""
    if training is None:
        training = _autograd.is_training()
    out, nm, nv = invoke(
        functools.partial(_nn.batch_norm, momentum=momentum, eps=eps,
                          training=training, axis=axis,
                          use_global_stats=use_global_stats,
                          sync_axis_name=sync_axis_name),
        (_as_nd(x), _as_nd(gamma), _as_nd(beta), _as_nd(running_mean),
         _as_nd(running_var)),
        name="batch_norm", multi_out=True)
    if training and isinstance(running_mean, NDArray):
        with _autograd.pause():
            # adopt the (possibly still pending) buffers — no materialization,
            # so a bulked eager step keeps BN stat updates in the segment
            running_mean._set_arr(nm._data)
            running_var._set_arr(nv._data)
    return out


def fully_connected(x, weight, bias=None, num_hidden=None, no_bias=False,
                    flatten=True):
    arrs = (_as_nd(x), _as_nd(weight)) + (() if no_bias or bias is None
                                          else (_as_nd(bias),))
    return invoke(functools.partial(_nn.dense, flatten=flatten), arrs,
                  name="fully_connected")


def convolution(data, weight, bias=None, kernel=None, stride=1, dilate=1,
                pad=0, num_filter=None, num_group=1, no_bias=False,
                layout="NCHW"):
    arrs = (_as_nd(data), _as_nd(weight)) + (() if no_bias or bias is None
                                             else (_as_nd(bias),))
    note_layout(get_op("npx.convolution"), layout)
    return invoke(functools.partial(_nn.conv, stride=stride, padding=pad,
                                    dilation=dilate, groups=num_group,
                                    layout=layout),
                  arrs, name="convolution")


def deconvolution(data, weight, bias=None, stride=1, dilate=1, pad=0, adj=0,
                  num_group=1, no_bias=False, layout="NCHW"):
    arrs = (_as_nd(data), _as_nd(weight)) + (() if no_bias or bias is None
                                             else (_as_nd(bias),))
    note_layout(get_op("npx.deconvolution"), layout)
    return invoke(functools.partial(_nn.conv_transpose, stride=stride,
                                    padding=pad, dilation=dilate,
                                    output_padding=adj, groups=num_group,
                                    layout=layout),
                  arrs, name="deconvolution")


def pooling(data, kernel=1, pool_type="max", stride=None, pad=0,
            global_pool=False, count_include_pad=True, layout="NCHW",
            ceil_mode=False, pooling_convention=None):
    if pooling_convention is not None:  # reference name: 'valid' | 'full'
        ceil_mode = pooling_convention == "full"
    note_layout(get_op("npx.pooling"), layout)
    return invoke(functools.partial(_nn.pooling, kernel=kernel,
                                    pool_type=pool_type, stride=stride,
                                    padding=pad, global_pool=global_pool,
                                    count_include_pad=count_include_pad,
                                    layout=layout, ceil_mode=ceil_mode),
                  (_as_nd(data),), name="pooling")


def box_iou(lhs, rhs, format="corner"):
    """≙ _contrib_box_iou (src/operator/contrib/bounding_box.cc)."""
    from ..ops import contrib as _contrib
    return invoke(functools.partial(_contrib.box_iou, fmt=format),
                  (_as_nd(lhs), _as_nd(rhs)), name="box_iou")


def box_nms(data, overlap_thresh=0.5, valid_thresh=0.0, topk=-1,
            coord_start=2, score_index=1, id_index=-1, force_suppress=False):
    """≙ _contrib_box_nms."""
    from ..ops import contrib as _contrib
    return invoke(functools.partial(
        _contrib.box_nms, overlap_thresh=overlap_thresh,
        valid_thresh=valid_thresh, topk=topk, coord_start=coord_start,
        score_index=score_index, id_index=id_index,
        force_suppress=force_suppress), (_as_nd(data),), name="box_nms")


def roi_align(data, rois, pooled_size, spatial_scale=1.0, sample_ratio=2):
    """≙ _contrib_ROIAlign."""
    from ..ops import contrib as _contrib
    return invoke(functools.partial(
        _contrib.roi_align, pooled_size=pooled_size,
        spatial_scale=spatial_scale, sample_ratio=sample_ratio),
        (_as_nd(data), _as_nd(rois)), name="roi_align")


def bilinear_resize2d(data, height, width, layout="NCHW"):
    """≙ _contrib_BilinearResize2D."""
    from ..ops import contrib as _contrib
    return invoke(functools.partial(_contrib.bilinear_resize2d,
                                    height=height, width=width,
                                    layout=layout),
                  (_as_nd(data),), name="bilinear_resize2d")


def multibox_prior(data, sizes=(1.0,), ratios=(1.0,), clip=False,
                   steps=(-1.0, -1.0), offsets=(0.5, 0.5), layout="NCHW"):
    """≙ _npx_multibox_prior (src/operator/contrib/multibox_prior.cc).

    Anchors depend only on the feature map's SHAPE, and the reference op
    has no backward — so `data` is detached before dispatch. Taping it
    (pre-r5 behavior) left anchors holding a tape node that a later
    backward severed: the usual compute-anchors-once-reuse-every-step
    pattern then crashed on the second iteration."""
    from ..ops import contrib as _contrib
    return invoke(functools.partial(
        _contrib.multibox_prior, sizes=tuple(sizes), ratios=tuple(ratios),
        clip=clip, steps=tuple(steps), offsets=tuple(offsets),
        layout=layout), (_as_nd(data).detach(),), name="multibox_prior")


def multibox_target(anchor, label, cls_pred, overlap_threshold=0.5,
                    ignore_label=-1.0, negative_mining_ratio=-1.0,
                    negative_mining_thresh=0.5, minimum_negative_samples=0,
                    variances=(0.1, 0.1, 0.2, 0.2)):
    """≙ _npx_multibox_target (src/operator/contrib/multibox_target.cc)."""
    from ..ops import contrib as _contrib
    return invoke(functools.partial(
        _contrib.multibox_target, overlap_threshold=overlap_threshold,
        ignore_label=ignore_label,
        negative_mining_ratio=negative_mining_ratio,
        negative_mining_thresh=negative_mining_thresh,
        minimum_negative_samples=minimum_negative_samples,
        variances=tuple(variances)),
        (_as_nd(anchor), _as_nd(label), _as_nd(cls_pred)),
        name="multibox_target", multi_out=True)


def multibox_detection(cls_prob, loc_pred, anchor, clip=True,
                       threshold=0.01, background_id=0, nms_threshold=0.5,
                       force_suppress=False,
                       variances=(0.1, 0.1, 0.2, 0.2), nms_topk=-1):
    """≙ _npx_multibox_detection (multibox_detection.cc)."""
    from ..ops import contrib as _contrib
    return invoke(functools.partial(
        _contrib.multibox_detection, clip=clip, threshold=threshold,
        background_id=background_id, nms_threshold=nms_threshold,
        force_suppress=force_suppress, variances=tuple(variances),
        nms_topk=nms_topk),
        (_as_nd(cls_prob), _as_nd(loc_pred), _as_nd(anchor)),
        name="multibox_detection")


def proposal(cls_prob, bbox_pred, im_info, rpn_pre_nms_top_n=6000,
             rpn_post_nms_top_n=300, threshold=0.7, rpn_min_size=16,
             scales=(4, 8, 16, 32), ratios=(0.5, 1, 2), feature_stride=16,
             output_score=False, iou_loss=False):
    """≙ _contrib_Proposal (src/operator/contrib/proposal.cc)."""
    from ..ops import contrib as _contrib
    return invoke(functools.partial(
        _contrib.proposal, rpn_pre_nms_top_n=rpn_pre_nms_top_n,
        rpn_post_nms_top_n=rpn_post_nms_top_n, threshold=threshold,
        rpn_min_size=rpn_min_size, scales=tuple(scales),
        ratios=tuple(ratios), feature_stride=feature_stride,
        output_score=output_score, iou_loss=iou_loss),
        (_as_nd(cls_prob), _as_nd(bbox_pred), _as_nd(im_info)),
        name="proposal", multi_out=output_score)


def deformable_convolution(data, offset, weight, bias=None, kernel=(3, 3),
                           stride=(1, 1), pad=(0, 0), dilate=(1, 1),
                           num_deformable_group=1):
    """≙ _npx_deformable_convolution (deformable_convolution.cc)."""
    from ..ops import contrib as _contrib
    fn = functools.partial(
        _contrib.deformable_convolution, kernel=tuple(kernel),
        stride=tuple(stride), pad=tuple(pad), dilate=tuple(dilate),
        num_deformable_group=num_deformable_group)
    args = (_as_nd(data), _as_nd(offset), _as_nd(weight))
    if bias is not None:
        args = args + (_as_nd(bias),)
        return invoke(lambda d, o, w, b: fn(d, o, w, bias=b), args,
                      name="deformable_convolution")
    return invoke(lambda d, o, w: fn(d, o, w), args,
                  name="deformable_convolution")


def psroi_pooling(data, rois, spatial_scale, output_dim, pooled_size,
                  group_size=0):
    """≙ _contrib_PSROIPooling (psroi_pooling.cc, R-FCN)."""
    from ..ops import contrib as _contrib
    return invoke(functools.partial(
        _contrib.psroi_pooling, spatial_scale=spatial_scale,
        output_dim=output_dim, pooled_size=pooled_size,
        group_size=group_size), (_as_nd(data), _as_nd(rois)),
        name="psroi_pooling")


def smooth_l1(x, scalar=1.0):
    """reference: smooth_l1 op (src/operator/tensor/elemwise_unary_op)"""
    def f(v):
        s2 = scalar * scalar
        return _jnp.where(_jnp.abs(v) < 1.0 / s2,
                          0.5 * s2 * v * v, _jnp.abs(v) - 0.5 / s2)
    return invoke(f, (_as_nd(x),), name="smooth_l1")


def multi_sum_sq(*arrays):
    """Sum of squares per array, fused (reference: multi_sum_sq op used by
    clip_global_norm / LARS)."""
    arrs = tuple(_as_nd(a) for a in arrays)
    return invoke(lambda *xs: tuple(_jnp.sum(_jnp.square(x)) for x in xs),
                  arrs, name="multi_sum_sq", multi_out=True)


def clip_by_global_norm(arrays, max_norm):
    """In-place global-norm clipping over a list of NDArrays; returns the norm
    (≙ gluon.utils.clip_global_norm)."""
    sqs = multi_sum_sq(*arrays)
    total = sqs[0]
    for s in sqs[1:]:
        total = total + s
    norm = total.sqrt()
    scale = float(max_norm) / max(float(norm.asscalar()), float(max_norm))
    if scale < 1.0:
        for a in arrays:
            a *= scale
    return norm


def arange_like(data, start=0.0, step=1.0, axis=None):
    def f(x):
        if axis is None:
            n = int(_onp.prod(x.shape))
            return _jnp.arange(start, start + step * n, step,
                               dtype=x.dtype).reshape(x.shape)
        n = x.shape[axis]
        return _jnp.arange(start, start + step * n, step, dtype=x.dtype)
    return invoke(f, (_as_nd(data),), name="arange_like")


def broadcast_like(lhs, rhs):
    return invoke(lambda a, b: _jnp.broadcast_to(a, b.shape),
                  (_as_nd(lhs), _as_nd(rhs)), name="broadcast_like")


def shape_array(data):
    return _wrap(_jnp.asarray(_as_nd(data).shape, dtype="int64"))


# ---------------------------------------------------------------------------
# control flow (reference: src/operator/npx_control_flow.cc:513-918 — stateful
# subgraph ops with LoopState; here: direct lax lowering, differentiable where
# XLA supports it)
# ---------------------------------------------------------------------------
def foreach(body, data, init_states):
    """Run `body(x_t, states) -> (out_t, new_states)` over axis 0 of data
    (≙ _npx_foreach). Differentiable (lax.scan)."""
    from jax import lax
    import jax.tree_util as jtu
    single_data = isinstance(data, NDArray)
    datas = (data,) if single_data else tuple(data)
    single_state = isinstance(init_states, NDArray)
    states = (init_states,) if single_state else tuple(init_states)
    n_data = len(datas)

    def call(*raws):
        xs = raws[:n_data]
        ss = raws[n_data:]

        def step(carry, x):
            xs_nd = [_wrap(xi) for xi in (x if n_data > 1 else (x,))]
            ss_nd = [_wrap(c) for c in carry]
            out, new_s = body(xs_nd[0] if single_data else xs_nd,
                              ss_nd[0] if single_state else ss_nd)
            outs = (out,) if isinstance(out, NDArray) else tuple(out)
            new_ss = (new_s,) if isinstance(new_s, NDArray) else tuple(new_s)
            return (tuple(s._arr for s in new_ss),
                    tuple(o._arr for o in outs))

        carry, ys = lax.scan(step, tuple(ss), xs if n_data > 1 else xs[0])
        return tuple(ys) + tuple(carry)

    res = invoke(call, datas + states, name="foreach", multi_out=True)
    n_out = len(res) - len(states)
    outs = res[:n_out]
    fin = res[n_out:]
    outs = outs[0] if n_out == 1 else list(outs)
    fin = fin[0] if single_state else list(fin)
    return outs, fin


def while_loop(cond_fn, func, loop_vars, max_iterations=None):
    """≙ _npx_while_loop. Lowers to lax.while_loop — forward-only (XLA cannot
    reverse-differentiate an unbounded loop; use `foreach` with
    max_iterations for a differentiable variant)."""
    from jax import lax
    single = isinstance(loop_vars, NDArray)
    lvs = (loop_vars,) if single else tuple(loop_vars)

    def call(*raws):
        def c(state):
            return cond_fn(*[_wrap(s) for s in state])._arr \
                if single is False else cond_fn(_wrap(state[0]))._arr

        def b(state):
            out = func(*[_wrap(s) for s in state]) if not single \
                else func(_wrap(state[0]))
            outs = (out,) if isinstance(out, NDArray) else tuple(out)
            return tuple(o._arr for o in outs)

        return lax.while_loop(c, b, tuple(raws))

    res = invoke(call, lvs, name="while_loop", multi_out=True)
    # reference returns (outputs, final_loop_vars); outputs unsupported here
    return [], (res[0] if single else list(res))


def cond(pred, then_func, else_func, inputs=None):
    """≙ _npx_cond. Differentiable (lax.cond)."""
    from jax import lax
    if inputs is None:
        inputs = []
    single = isinstance(inputs, NDArray)
    ins = (inputs,) if single else tuple(inputs)
    p = pred(*ins) if callable(pred) else pred

    def call(praw, *raws):
        def t(xs):
            out = then_func(*[_wrap(x) for x in xs]) if xs else then_func()
            outs = (out,) if isinstance(out, NDArray) else tuple(out)
            return tuple(o._arr for o in outs)

        def f(xs):
            out = else_func(*[_wrap(x) for x in xs]) if xs else else_func()
            outs = (out,) if isinstance(out, NDArray) else tuple(out)
            return tuple(o._arr for o in outs)

        return lax.cond(praw.astype(bool).reshape(()), t, f, raws)

    res = invoke(call, (_as_nd(p),) + ins, name="cond", multi_out=True)
    return res[0] if len(res) == 1 else list(res)


scan = foreach


# ---------------------------------------------------------------------------
# np-mode scopes (reference: mx.npx.set_np / is_np_array; the numpy frontend
# is always-on here, kept for script compatibility)
# ---------------------------------------------------------------------------
_np_mode = {"array": True, "shape": True}


def set_np(shape=True, array=True, dtype=None):
    _np_mode["array"] = array
    _np_mode["shape"] = shape


def reset_np():
    set_np()


def is_np_array():
    return _np_mode["array"]


def is_np_shape():
    return _np_mode["shape"]


def use_np(func):
    return func


def load(fname):
    from ..ndarray import load as _load
    return _load(fname)


def save(fname, data):
    from ..ndarray import save as _save
    return _save(fname, data)


def sequence_last(data, sequence_length=None, use_sequence_length=False,
                  axis=0):
    """≙ SequenceLast (src/operator/sequence_last.cc)."""
    data = _as_nd(data)
    if not use_sequence_length or sequence_length is None:
        idx = data.shape[axis] - 1
        return invoke(lambda x: _jnp.take(x, idx, axis=axis), (data,),
                      name="sequence_last")

    def f(x, lens):
        jnp = _jnp
        t = jnp.clip(lens.astype(jnp.int32) - 1, 0, x.shape[axis] - 1)
        moved = jnp.moveaxis(x, axis, 0)        # (T, N, ...)
        return jnp.take_along_axis(
            moved, t.reshape((1, -1) + (1,) * (moved.ndim - 2)), axis=0)[0]
    return invoke(f, (data, _as_nd(sequence_length)), name="sequence_last")


def sequence_reverse(data, sequence_length=None, use_sequence_length=False,
                     axis=0):
    """≙ SequenceReverse (src/operator/sequence_reverse.cc)."""
    data = _as_nd(data)
    if not use_sequence_length or sequence_length is None:
        return invoke(lambda x: _jnp.flip(x, axis=axis), (data,),
                      name="sequence_reverse")

    def f(x, lens):
        jnp = _jnp
        moved = jnp.moveaxis(x, axis, 0)        # (T, N, ...)
        T = moved.shape[0]
        t_idx = jnp.arange(T)[:, None]          # (T, 1)
        lens_i = lens.astype(jnp.int32)[None, :]
        rev = jnp.where(t_idx < lens_i, lens_i - 1 - t_idx, t_idx)
        out = jnp.take_along_axis(
            moved, rev.reshape(rev.shape + (1,) * (moved.ndim - 2)), axis=0)
        return jnp.moveaxis(out, 0, axis)
    return invoke(f, (data, _as_nd(sequence_length)), name="sequence_reverse")


__all__ += ["sequence_last", "sequence_reverse", "box_iou", "box_nms",
            "roi_align", "bilinear_resize2d", "multibox_prior",
            "multibox_target", "multibox_detection", "proposal",
            "deformable_convolution", "psroi_pooling"]


# Register the contrib/detection surface so the records exist for
# introspection + apply_op dispatch, carrying the AMP classes tagged in
# ops/contrib.py (PR2 dispatch-record metadata). The RAW kernels register —
# they are pure jax functions, so apply_op dispatch tapes/bulks/jits
# correctly; the python wrappers above (reference argument names,
# detach/multi_out handling) stay the mx.npx call surface. Registering a
# wrapper instead would re-enter invoke with tracer args at backward time
# (`_as_nd(tracer)` device_put → TracerArrayConversionError).
def _register_contrib_records():
    from ..ops import contrib as _contrib
    for _n in ("box_iou", "box_nms", "roi_align", "bilinear_resize2d",
               "multibox_prior", "multibox_target", "multibox_detection",
               "proposal", "deformable_convolution", "psroi_pooling"):
        kern = getattr(_contrib, _n)
        register_op("npx." + _n, kern,
                    amp=getattr(kern, "_amp_class", "neutral"))


_register_contrib_records()
