"""gluon.nn — neural network layers.

Reference: python/mxnet/gluon/nn/{basic_layers,conv_layers,activations}.py
(catalog in SURVEY.md Appendix B). Each layer is a HybridBlock whose forward
is written against mx.npx functional ops, so it runs eagerly op-by-op or
compiles to one XLA computation under hybridize().

Layout note: layers default to the reference's NCHW/`channels-first`
convention for API parity; `layout='NHWC'` is the TPU-preferred fast path
(XLA convs tile NHWC onto the MXU without transposes).
"""
from __future__ import annotations

import numpy as _np

from ...base import MXNetError, name_to_dtype
from ... import numpy_extension as npx
from ... import numpy as mxnp
from ...ops import fused as _fused
from ..block import Block, HybridBlock
from ..parameter import Parameter

# activation strings the fused-tier GATE may engage on: the intersection
# of the kernel contract (ops/fused.py FUSABLE_ACTS) and what
# npx.activation serves — the tier must be a pure optimization, so a
# block built with one of these must also run with fusion OFF
# (MXNET_USE_FUSION=0 A/B). silu/gelu are fusable by the kernels but
# have no unfused npx.activation, so the gate skips them.
_NPX_ACTS = frozenset(("relu", "sigmoid", "tanh", "softrelu", "softsign",
                       "log_sigmoid", "mish"))
_FUSABLE_ACTS = frozenset(a for a in _fused.FUSABLE_ACTS if a) & _NPX_ACTS


def _fusion_on():
    """Route this forward through the fused kernel tier? True inside a
    `fused.fusion_scope(True)` (FusedTrainStep/FusedInferStep enter one
    automatically) or after `fused.set_fusion_default(True)`, unless
    MXNET_USE_FUSION kills the tier. See docs/PERF.md 'Kernel tier'."""
    return _fused.fusion_enabled()

__all__ = [
    "Sequential", "HybridSequential", "Dense", "Dropout", "BatchNorm",
    "BatchNormReLU", "SyncBatchNorm", "Embedding", "Flatten", "InstanceNorm",
    "LayerNorm", "GroupNorm", "RMSNorm", "Lambda", "HybridLambda",
    "Concatenate", "HybridConcatenate", "Identity",
    "Activation", "LeakyReLU", "PReLU", "ELU", "SELU", "Swish", "SiLU", "GELU",
    "Conv1D", "Conv2D", "Conv3D",
    "Conv1DTranspose", "Conv2DTranspose", "Conv3DTranspose",
    "MaxPool1D", "MaxPool2D", "MaxPool3D",
    "AvgPool1D", "AvgPool2D", "AvgPool3D",
    "GlobalMaxPool1D", "GlobalMaxPool2D", "GlobalMaxPool3D",
    "GlobalAvgPool1D", "GlobalAvgPool2D", "GlobalAvgPool3D",
    "ReflectionPad2D",
]


# ---------------------------------------------------------------------------
# containers (≙ basic_layers.py Sequential:36 / HybridSequential:104)
# ---------------------------------------------------------------------------
class Sequential(Block):
    """Stack of blocks executed sequentially."""

    def __init__(self, *blocks):
        super().__init__()
        for b in blocks:
            self.add(b)

    def add(self, *blocks):
        for b in blocks:
            self.register_child(b)

    def forward(self, x, *args):
        for block in self._children.values():
            x = block(x, *args)
            args = ()
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        children = list(self._children.values())
        if isinstance(key, slice):
            net = type(self)()
            net.add(*children[key])
            return net
        return children[key]

    def __iter__(self):
        return iter(self._children.values())


class HybridSequential(Sequential, HybridBlock):
    """Hybridizable Sequential (≙ basic_layers.py:104)."""

    def __init__(self, *blocks):
        HybridBlock.__init__(self)
        for b in blocks:
            self.add(b)


# ---------------------------------------------------------------------------
# Dense (≙ basic_layers.py Dense:156; kernel: fully_connected.cc:252)
# ---------------------------------------------------------------------------
class Dense(HybridBlock):
    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype="float32", weight_initializer=None,
                 bias_initializer="zeros", in_units=0):
        super().__init__()
        self._units = units
        self._flatten = flatten
        self._act_type = activation
        self.weight = Parameter(shape=(units, in_units), dtype=dtype,
                                init=weight_initializer,
                                allow_deferred_init=True, name="weight")
        self.bias = (Parameter(shape=(units,), dtype=dtype,
                               init=bias_initializer,
                               allow_deferred_init=True, name="bias")
                     if use_bias else None)

    def infer_shape(self, x, *args):
        in_units = int(_np.prod(x.shape[1:])) if self._flatten else x.shape[-1]
        self.weight.shape = (self._units, in_units)
        if self.bias is not None:
            self.bias.shape = (self._units,)

    def forward(self, x):
        if (self._act_type in _FUSABLE_ACTS and self.bias is not None
                and _fusion_on()):
            # kernel tier: bias + activation fold into one fused pass
            y = npx.fully_connected(x, self.weight.data(), None,
                                    no_bias=True, flatten=self._flatten)
            return npx.fused_bias_act(y, self.bias.data(),
                                      act_type=self._act_type, axis=-1)
        y = npx.fully_connected(
            x, self.weight.data(),
            None if self.bias is None else self.bias.data(),
            no_bias=self.bias is None, flatten=self._flatten)
        if self._act_type:
            y = npx.activation(y, act_type=self._act_type)
        return y

    def __repr__(self):
        shape = self.weight.shape
        return (f"Dense({shape[1] if shape and len(shape) > 1 else None} -> "
                f"{self._units}, "
                f"{self._act_type if self._act_type else 'linear'})")


# ---------------------------------------------------------------------------
# Dropout (≙ basic_layers.py Dropout:253; src/operator/nn/dropout*)
# ---------------------------------------------------------------------------
class Dropout(HybridBlock):
    def __init__(self, rate, axes=()):
        super().__init__()
        self._rate = rate
        self._axes = axes

    def forward(self, x):
        return npx.dropout(x, p=self._rate, axes=self._axes or None)

    def __repr__(self):
        return f"Dropout(p = {self._rate}, axes={self._axes})"


# ---------------------------------------------------------------------------
# Norm layers (≙ basic_layers.py BatchNorm:414, LayerNorm:717, GroupNorm:808,
# InstanceNorm:616; kernels src/operator/nn/{batch_norm,layer_norm,group_norm}*)
# ---------------------------------------------------------------------------
class BatchNorm(HybridBlock):
    def __init__(self, axis=1, momentum=0.9, epsilon=1e-5, center=True,
                 scale=True, use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones",
                 running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0, **kwargs):
        super().__init__()
        self._axis = axis
        self._momentum = momentum
        self._eps = epsilon
        self._center = center
        self._scale = scale
        self._use_global_stats = use_global_stats
        ch = in_channels if in_channels > 0 else 0
        self.gamma = Parameter(shape=(ch,), init=gamma_initializer,
                               grad_req="write" if scale else "null",
                               allow_deferred_init=True, name="gamma")
        self.beta = Parameter(shape=(ch,), init=beta_initializer,
                              grad_req="write" if center else "null",
                              allow_deferred_init=True, name="beta")
        self.running_mean = Parameter(shape=(ch,),
                                      init=running_mean_initializer,
                                      grad_req="null",
                                      allow_deferred_init=True,
                                      name="running_mean")
        self.running_var = Parameter(shape=(ch,),
                                     init=running_variance_initializer,
                                     grad_req="null",
                                     allow_deferred_init=True,
                                     name="running_var")

    def infer_shape(self, x, *args):
        ch = x.shape[self._axis]
        for p in (self.gamma, self.beta, self.running_mean, self.running_var):
            p.shape = (ch,)

    def fused_forward(self, x, act_type=None, residual=None):
        """BN + optional activation + optional pre-activation residual
        add as ONE fused-tier op (npx.fused_batch_norm): one dispatch
        where the eager path takes three; its apply stage is the jnp
        composition, which XLA fuses into the neighbouring convolutions
        inside a compiled step. Numerics match forward() (+ activation,
        + add) within float association; running stats update
        identically."""
        return npx.fused_batch_norm(
            x, self.gamma.data(), self.beta.data(),
            self.running_mean.data(), self.running_var.data(),
            eps=self._eps, momentum=self._momentum, axis=self._axis,
            use_global_stats=self._use_global_stats, act_type=act_type,
            residual=residual)

    def forward(self, x):
        if _fusion_on():
            return self.fused_forward(x)
        return npx.batch_norm(
            x, self.gamma.data(), self.beta.data(),
            self.running_mean.data(), self.running_var.data(),
            eps=self._eps, momentum=self._momentum, axis=self._axis,
            use_global_stats=self._use_global_stats)

    def __repr__(self):
        return (f"BatchNorm(axis={self._axis}, momentum={self._momentum}, "
                f"eps={self._eps})")


class BatchNormReLU(BatchNorm):
    """Fused BN+ReLU (≙ basic_layers.py:478). Under `_fusion_on()` the
    whole normalize+scale/shift+relu chain is one op
    (npx.fused_batch_norm); otherwise BN + relu as before (XLA fuses
    pointwise)."""

    def forward(self, x):
        if _fusion_on():
            return self.fused_forward(x, act_type="relu")
        return npx.relu(BatchNorm.forward(self, x))


class SyncBatchNorm(BatchNorm):
    """Cross-device synchronized BatchNorm (≙ basic_layers.py SyncBatchNorm:1087,
    kernel src/operator/contrib/sync_batch_norm-inl.h:78-173).

    TPU-native: inside a pjit/shard_map over a mesh, batch stats reduce with
    `lax.pmean` over the data-parallel axis (`sync_axis_name`) instead of the
    reference's per-process SharedND barrier."""

    def __init__(self, in_channels=0, num_devices=None, momentum=0.9,
                 epsilon=1e-5, center=True, scale=True,
                 use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones",
                 running_mean_initializer="zeros",
                 running_variance_initializer="ones",
                 sync_axis_name="dp", **kwargs):
        super().__init__(axis=1, momentum=momentum, epsilon=epsilon,
                         center=center, scale=scale,
                         use_global_stats=use_global_stats,
                         beta_initializer=beta_initializer,
                         gamma_initializer=gamma_initializer,
                         running_mean_initializer=running_mean_initializer,
                         running_variance_initializer=running_variance_initializer,
                         in_channels=in_channels)
        self._sync_axis_name = sync_axis_name

    def forward(self, x):
        from ... import parallel
        axis_name = (self._sync_axis_name
                     if parallel.axis_is_bound(self._sync_axis_name) else None)
        return npx.batch_norm(
            x, self.gamma.data(), self.beta.data(),
            self.running_mean.data(), self.running_var.data(),
            eps=self._eps, momentum=self._momentum, axis=self._axis,
            use_global_stats=self._use_global_stats,
            sync_axis_name=axis_name)


class LayerNorm(HybridBlock):
    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0):
        super().__init__()
        self._axis = axis
        self._eps = epsilon
        ch = in_channels if in_channels > 0 else 0
        self.gamma = Parameter(shape=(ch,), init=gamma_initializer,
                               grad_req="write" if scale else "null",
                               allow_deferred_init=True, name="gamma")
        self.beta = Parameter(shape=(ch,), init=beta_initializer,
                              grad_req="write" if center else "null",
                              allow_deferred_init=True, name="beta")

    def infer_shape(self, x, *args):
        ch = x.shape[self._axis]
        self.gamma.shape = (ch,)
        self.beta.shape = (ch,)

    def forward(self, x):
        return npx.layer_norm(x, self.gamma.data(), self.beta.data(),
                              axis=self._axis, eps=self._eps)

    def __repr__(self):
        return f"LayerNorm(axis={self._axis}, eps={self._eps})"


class GroupNorm(HybridBlock):
    def __init__(self, num_groups=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0):
        super().__init__()
        self._num_groups = num_groups
        self._eps = epsilon
        ch = in_channels if in_channels > 0 else 0
        self.gamma = Parameter(shape=(ch,), init=gamma_initializer,
                               grad_req="write" if scale else "null",
                               allow_deferred_init=True, name="gamma")
        self.beta = Parameter(shape=(ch,), init=beta_initializer,
                              grad_req="write" if center else "null",
                              allow_deferred_init=True, name="beta")

    def infer_shape(self, x, *args):
        ch = x.shape[1]
        self.gamma.shape = (ch,)
        self.beta.shape = (ch,)

    def forward(self, x):
        return npx.group_norm(x, self.gamma.data(), self.beta.data(),
                              num_groups=self._num_groups, eps=self._eps)


class InstanceNorm(HybridBlock):
    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0):
        super().__init__()
        self._axis = axis
        self._eps = epsilon
        ch = in_channels if in_channels > 0 else 0
        self.gamma = Parameter(shape=(ch,), init=gamma_initializer,
                               grad_req="write" if scale else "null",
                               allow_deferred_init=True, name="gamma")
        self.beta = Parameter(shape=(ch,), init=beta_initializer,
                              grad_req="write" if center else "null",
                              allow_deferred_init=True, name="beta")

    def infer_shape(self, x, *args):
        ch = x.shape[self._axis]
        self.gamma.shape = (ch,)
        self.beta.shape = (ch,)

    def forward(self, x):
        return npx.instance_norm(x, self.gamma.data(), self.beta.data(),
                                 eps=self._eps)


class RMSNorm(HybridBlock):
    """RMS normalization — modern-transformer extension beyond the reference
    (used by the flagship transformer; no MXNet equivalent)."""

    def __init__(self, in_channels=0, epsilon=1e-6, gamma_initializer="ones"):
        super().__init__()
        self._eps = epsilon
        ch = in_channels if in_channels > 0 else 0
        self.gamma = Parameter(shape=(ch,), init=gamma_initializer,
                               allow_deferred_init=True, name="gamma")

    def infer_shape(self, x, *args):
        self.gamma.shape = (x.shape[-1],)

    def forward(self, x):
        return npx.rms_norm(x, self.gamma.data(), eps=self._eps)


# ---------------------------------------------------------------------------
# Embedding / Flatten / glue (≙ basic_layers.py Embedding:543, Flatten:596,
# Lambda:904, Concatenate:1002, Identity:1066)
# ---------------------------------------------------------------------------
class Embedding(HybridBlock):
    """≙ gluon.nn.Embedding. `sparse_grad=True` enables the TPU-native
    counterpart of the reference's row_sparse gradient path
    (python/mxnet/gluon/trainer.py:325 row-sparse pulls): the backward is
    XLA's scatter-add (cost scales with tokens touched), and gluon.Trainer
    applies a TOUCHED-ROWS optimizer update — only rows referenced since
    the last step are updated (the reference's lazy_update semantics:
    untouched rows receive no decay/momentum aging). Promoted from
    tests/nightly/test_large_vocab_embedding.py viability evidence."""

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, sparse_grad=False):
        super().__init__()
        self._input_dim = input_dim
        self._output_dim = output_dim
        self._sparse_grad = bool(sparse_grad)
        self.weight = Parameter(shape=(input_dim, output_dim), dtype=dtype,
                                init=weight_initializer, name="weight")
        if self._sparse_grad:
            self.weight._sparse_grad = True

    def forward(self, x):
        if self._sparse_grad:
            import jax
            from ... import autograd as _ag
            raw = x._arr if hasattr(x, "_arr") else x
            if isinstance(raw, jax.core.Tracer):
                # symbolic indices (hybridize/jit): the trainer falls back
                # to the dense update
                self.weight._last_tokens = None
            elif _ag.is_recording():
                # ACCUMULATE recorded batches (grad_req='add' / multiple
                # calls per iteration touch the union of their rows);
                # inference forwards between backward and step must not
                # disturb the recorded set
                prev = getattr(self.weight, "_last_tokens", None)
                self.weight._last_tokens = (list(prev) if prev else []) \
                    + [raw]
        return npx.embedding(x, self.weight.data())

    def __repr__(self):
        return f"Embedding({self._input_dim} -> {self._output_dim})"


class Flatten(HybridBlock):
    def forward(self, x):
        return x.reshape((x.shape[0], -1))

    def __repr__(self):
        return "Flatten"


class Identity(HybridBlock):
    def forward(self, x):
        return x


class Lambda(Block):
    def __init__(self, function):
        super().__init__()
        if isinstance(function, str):
            function = getattr(mxnp, function)
        self._func = function

    def forward(self, *args):
        return self._func(*args)


class HybridLambda(HybridBlock):
    def __init__(self, function):
        super().__init__()
        if isinstance(function, str):
            function = getattr(mxnp, function)
        self._func = function

    def forward(self, *args):
        return self._func(*args)


class Concatenate(Sequential):
    """Run children on the same input, concat outputs (≙ basic_layers.py:1002)."""

    def __init__(self, axis=-1):
        super().__init__()
        self._axis = axis

    def forward(self, x):
        outs = [block(x) for block in self._children.values()]
        return mxnp.concatenate(outs, axis=self._axis)


class HybridConcatenate(HybridSequential):
    def __init__(self, axis=-1):
        super().__init__()
        self._axis = axis

    def forward(self, x):
        outs = [block(x) for block in self._children.values()]
        return mxnp.concatenate(outs, axis=self._axis)


# ---------------------------------------------------------------------------
# activations (≙ gluon/nn/activations.py)
# ---------------------------------------------------------------------------
class Activation(HybridBlock):
    def __init__(self, activation):
        super().__init__()
        self._act_type = activation

    def forward(self, x):
        return npx.activation(x, act_type=self._act_type)

    def __repr__(self):
        return f"Activation({self._act_type})"


class LeakyReLU(HybridBlock):
    def __init__(self, alpha=0.01):
        super().__init__()
        self._alpha = alpha

    def forward(self, x):
        return npx.leaky_relu(x, act_type="leaky", slope=self._alpha)


class PReLU(HybridBlock):
    def __init__(self, alpha_initializer=None, in_channels=1):
        super().__init__()
        from ... import initializer as init_mod
        self.alpha = Parameter(shape=(in_channels,),
                               init=alpha_initializer or
                               init_mod.Constant(0.25), name="alpha")

    def forward(self, x):
        return npx.leaky_relu(x, gamma=self.alpha.data(), act_type="prelu")


class ELU(HybridBlock):
    def __init__(self, alpha=1.0):
        super().__init__()
        self._alpha = alpha

    def forward(self, x):
        return npx.elu(x, alpha=self._alpha)


class SELU(HybridBlock):
    def forward(self, x):
        return npx.selu(x)


class GELU(HybridBlock):
    def __init__(self, approximation="erf"):
        super().__init__()
        self._approx = approximation != "erf"

    def forward(self, x):
        return npx.gelu(x, approximate=self._approx)


class Swish(HybridBlock):
    def __init__(self, beta=1.0):
        super().__init__()
        self._beta = beta

    def forward(self, x):
        if self._beta == 1.0:
            return npx.silu(x)
        return x * npx.sigmoid(self._beta * x)


SiLU = Swish


# ---------------------------------------------------------------------------
# conv / pool layers (≙ gluon/nn/conv_layers.py:219-1204)
# ---------------------------------------------------------------------------
class _Conv(HybridBlock):
    def __init__(self, channels, kernel_size, strides, padding, dilation,
                 groups, layout, in_channels=0, activation=None, use_bias=True,
                 weight_initializer=None, bias_initializer="zeros",
                 op_name="convolution", adj=None, dtype="float32"):
        super().__init__()
        self._channels = channels
        self._in_channels = in_channels
        if isinstance(kernel_size, int):
            kernel_size = (kernel_size,) * (len(layout) - 2)
        self._kernel = tuple(kernel_size)
        self._strides = strides
        self._padding = padding
        self._dilation = dilation
        self._groups = groups
        self._layout = layout
        self._act_type = activation
        self._op_name = op_name
        self._adj = adj
        wshape = self._weight_shape(in_channels if in_channels else 0)
        self.weight = Parameter(shape=wshape, dtype=dtype,
                                init=weight_initializer,
                                allow_deferred_init=True, name="weight")
        self.bias = (Parameter(shape=(channels,), dtype=dtype,
                               init=bias_initializer,
                               allow_deferred_init=True, name="bias")
                     if use_bias else None)

    def _channel_axis(self):
        return 1 if self._layout.startswith("NC") else len(self._layout) - 1

    def _weight_shape(self, in_ch):
        # layouts: NCHW→OIHW weights; NHWC→HWIO (ops/nn.py conv contract)
        if self._op_name == "deconvolution":
            # reference deconv weight: (in, out/groups, *k) for NCHW
            if self._layout.startswith("NC"):
                return (in_ch, self._channels // self._groups) + self._kernel
            return self._kernel + (self._channels // self._groups, in_ch)
        if self._layout.startswith("NC"):
            return (self._channels,
                    (in_ch // self._groups) if in_ch else 0) + self._kernel
        return self._kernel + ((in_ch // self._groups) if in_ch else 0,
                               self._channels)

    def infer_shape(self, x, *args):
        in_ch = x.shape[self._channel_axis()]
        self.weight.shape = self._weight_shape(in_ch)
        if self.bias is not None:
            self.bias.shape = (self._channels,)

    def forward(self, x):
        bias = None if self.bias is None else self.bias.data()
        fuse_ba = (self._act_type in _FUSABLE_ACTS and bias is not None
                   and _fusion_on())
        if fuse_ba:
            bias_arr, bias = bias, None   # bias folds into the fused act
        if self._op_name == "convolution":
            y = npx.convolution(x, self.weight.data(), bias,
                                stride=self._strides, dilate=self._dilation,
                                pad=self._padding, num_group=self._groups,
                                no_bias=bias is None, layout=self._layout)
        else:
            y = npx.deconvolution(x, self.weight.data(), bias,
                                  stride=self._strides, dilate=self._dilation,
                                  pad=self._padding, adj=self._adj or 0,
                                  num_group=self._groups,
                                  no_bias=bias is None, layout=self._layout)
        if fuse_ba:
            return npx.fused_bias_act(y, bias_arr, act_type=self._act_type,
                                      axis=self._channel_axis())
        if self._act_type:
            y = npx.activation(y, act_type=self._act_type)
        return y

    def __repr__(self):
        return (f"{type(self).__name__}({self._channels}, "
                f"kernel_size={self._kernel}, stride={self._strides})")


class Conv1D(_Conv):
    def __init__(self, channels, kernel_size, strides=1, padding=0, dilation=1,
                 groups=1, layout="NCW", activation=None, use_bias=True,
                 weight_initializer=None, bias_initializer="zeros",
                 in_channels=0, **kwargs):
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, layout, in_channels, activation, use_bias,
                         weight_initializer, bias_initializer, **kwargs)


class Conv2D(_Conv):
    def __init__(self, channels, kernel_size, strides=(1, 1), padding=(0, 0),
                 dilation=(1, 1), groups=1, layout="NCHW", activation=None,
                 use_bias=True, weight_initializer=None,
                 bias_initializer="zeros", in_channels=0, **kwargs):
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, layout, in_channels, activation, use_bias,
                         weight_initializer, bias_initializer, **kwargs)


class Conv3D(_Conv):
    def __init__(self, channels, kernel_size, strides=(1, 1, 1),
                 padding=(0, 0, 0), dilation=(1, 1, 1), groups=1,
                 layout="NCDHW", activation=None, use_bias=True,
                 weight_initializer=None, bias_initializer="zeros",
                 in_channels=0, **kwargs):
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, layout, in_channels, activation, use_bias,
                         weight_initializer, bias_initializer, **kwargs)


class Conv1DTranspose(_Conv):
    def __init__(self, channels, kernel_size, strides=1, padding=0,
                 output_padding=0, dilation=1, groups=1, layout="NCW",
                 activation=None, use_bias=True, weight_initializer=None,
                 bias_initializer="zeros", in_channels=0, **kwargs):
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, layout, in_channels, activation, use_bias,
                         weight_initializer, bias_initializer,
                         op_name="deconvolution", adj=output_padding, **kwargs)


class Conv2DTranspose(_Conv):
    def __init__(self, channels, kernel_size, strides=(1, 1), padding=(0, 0),
                 output_padding=(0, 0), dilation=(1, 1), groups=1,
                 layout="NCHW", activation=None, use_bias=True,
                 weight_initializer=None, bias_initializer="zeros",
                 in_channels=0, **kwargs):
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, layout, in_channels, activation, use_bias,
                         weight_initializer, bias_initializer,
                         op_name="deconvolution", adj=output_padding, **kwargs)


class Conv3DTranspose(_Conv):
    def __init__(self, channels, kernel_size, strides=(1, 1, 1),
                 padding=(0, 0, 0), output_padding=(0, 0, 0),
                 dilation=(1, 1, 1), groups=1, layout="NCDHW", activation=None,
                 use_bias=True, weight_initializer=None,
                 bias_initializer="zeros", in_channels=0, **kwargs):
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, layout, in_channels, activation, use_bias,
                         weight_initializer, bias_initializer,
                         op_name="deconvolution", adj=output_padding, **kwargs)


class _Pool(HybridBlock):
    def __init__(self, pool_size, strides, padding, global_pool, pool_type,
                 layout, ceil_mode=False, count_include_pad=True):
        super().__init__()
        self._kernel = pool_size
        self._stride = strides if strides is not None else pool_size
        self._pad = padding
        self._global = global_pool
        self._type = pool_type
        self._layout = layout
        self._count_include_pad = count_include_pad
        self._ceil_mode = ceil_mode

    def _fused_pool_size(self, x):
        """(ph, pw) when this pool can take the fused non-overlapping
        NHWC op (reshape+mean, broadcast backward), else None: avg type,
        NHWC 2-D, zero padding, kernel == stride dividing the spatial
        dims — which covers AvgPool2D(k, k) and GlobalAvgPool2D."""
        if self._type != "avg" or self._layout != "NHWC" or x.ndim != 4:
            return None
        h, w = x.shape[1], x.shape[2]
        if self._global:
            return (h, w)
        k = (self._kernel,) * 2 if isinstance(self._kernel, int) \
            else tuple(self._kernel)
        s = (self._stride,) * 2 if isinstance(self._stride, int) \
            else tuple(self._stride)
        p = (self._pad,) * 2 if isinstance(self._pad, int) \
            else tuple(self._pad)
        if len(k) == 2 and k == s and p == (0, 0) \
                and h % k[0] == 0 and w % k[1] == 0:
            return k
        return None

    def forward(self, x):
        if _fusion_on():
            ps = self._fused_pool_size(x)
            if ps is not None:
                return npx.fused_avg_pool2d(x, ps, layout="NHWC")
        return npx.pooling(x, kernel=self._kernel, pool_type=self._type,
                           stride=self._stride, pad=self._pad,
                           global_pool=self._global,
                           count_include_pad=self._count_include_pad,
                           layout=self._layout, ceil_mode=self._ceil_mode)

    def __repr__(self):
        return (f"{type(self).__name__}(size={self._kernel}, "
                f"stride={self._stride}, padding={self._pad})")


class MaxPool1D(_Pool):
    def __init__(self, pool_size=2, strides=None, padding=0, layout="NCW",
                 ceil_mode=False, **kwargs):
        super().__init__(pool_size, strides, padding, False, "max", layout,
                         ceil_mode)


class MaxPool2D(_Pool):
    def __init__(self, pool_size=(2, 2), strides=None, padding=0,
                 layout="NCHW", ceil_mode=False, **kwargs):
        super().__init__(pool_size, strides, padding, False, "max", layout,
                         ceil_mode)


class MaxPool3D(_Pool):
    def __init__(self, pool_size=(2, 2, 2), strides=None, padding=0,
                 layout="NCDHW", ceil_mode=False, **kwargs):
        super().__init__(pool_size, strides, padding, False, "max", layout,
                         ceil_mode)


class AvgPool1D(_Pool):
    def __init__(self, pool_size=2, strides=None, padding=0, layout="NCW",
                 ceil_mode=False, count_include_pad=True, **kwargs):
        super().__init__(pool_size, strides, padding, False, "avg", layout,
                         ceil_mode, count_include_pad)


class AvgPool2D(_Pool):
    def __init__(self, pool_size=(2, 2), strides=None, padding=0,
                 layout="NCHW", ceil_mode=False, count_include_pad=True,
                 **kwargs):
        super().__init__(pool_size, strides, padding, False, "avg", layout,
                         ceil_mode, count_include_pad)


class AvgPool3D(_Pool):
    def __init__(self, pool_size=(2, 2, 2), strides=None, padding=0,
                 layout="NCDHW", ceil_mode=False, count_include_pad=True,
                 **kwargs):
        super().__init__(pool_size, strides, padding, False, "avg", layout,
                         ceil_mode, count_include_pad)


class GlobalMaxPool1D(_Pool):
    def __init__(self, layout="NCW", **kwargs):
        super().__init__(1, None, 0, True, "max", layout)


class GlobalMaxPool2D(_Pool):
    def __init__(self, layout="NCHW", **kwargs):
        super().__init__((1, 1), None, 0, True, "max", layout)


class GlobalMaxPool3D(_Pool):
    def __init__(self, layout="NCDHW", **kwargs):
        super().__init__((1, 1, 1), None, 0, True, "max", layout)


class GlobalAvgPool1D(_Pool):
    def __init__(self, layout="NCW", **kwargs):
        super().__init__(1, None, 0, True, "avg", layout)


class GlobalAvgPool2D(_Pool):
    def __init__(self, layout="NCHW", **kwargs):
        super().__init__((1, 1), None, 0, True, "avg", layout)


class GlobalAvgPool3D(_Pool):
    def __init__(self, layout="NCDHW", **kwargs):
        super().__init__((1, 1, 1), None, 0, True, "avg", layout)


class ReflectionPad2D(HybridBlock):
    """≙ conv_layers.py ReflectionPad2D (src/operator/pad.cc reflect mode)."""

    def __init__(self, padding=0):
        super().__init__()
        if isinstance(padding, int):
            padding = (padding,) * 4  # (left, right, top, bottom) per ref
        self._padding = padding

    def forward(self, x):
        p = self._padding
        return mxnp.pad(x, ((0, 0), (0, 0), (p[2], p[3]), (p[0], p[1])),
                        mode="reflect")


from .transformer import (MultiHeadAttention, TransformerEncoderCell,
                          TransformerDecoderCell, PositionalEmbedding)

__all__ += ["MultiHeadAttention", "TransformerEncoderCell",
            "TransformerDecoderCell", "PositionalEmbedding"]
