"""NDArray: the user-visible tensor.

Reference equivalents: include/mxnet/ndarray.h:82 (NDArray with Chunk +
engine var), src/ndarray/ndarray.cc (CopyFromTo :1411, Save/Load :1861,1994),
python/mxnet/ndarray/ndarray.py (5.1k LoC method surface).

TPU-native design: an NDArray wraps an immutable `jax.Array`. Mutation
(`a[:] = x`, `a += b`) is functional under the hood — the wrapper swaps its
buffer and bumps a version counter. Views (`a[1:3]`) keep a link to their base
with the source index, so writes through a view update the base (`.at[idx].set`)
and reads re-derive when the base version moved: a copy-on-write view layer
replacing the reference's zero-copy Chunk views (ndarray.h "Reshape/Slice share
var"). Async semantics come free from PJRT: every op returns a future-backed
buffer; `wait_to_read` ≙ WaitToRead maps to `block_until_ready`. The engine's
versioned-var dependency tracking (src/engine/threaded_engine.h:123) is
unnecessary because buffers are immutable.
"""
from __future__ import annotations

import numpy as _np

from .. import autograd
from ..base import MXNetError, name_to_dtype, dtype_to_name, numeric_types
from ..device import Device, current_device
from ..ops.segment import _LazyVal, flush_all as _flush_segments

__all__ = [
    "NDArray", "array", "zeros", "ones", "full", "empty", "arange",
    "zeros_like", "ones_like", "concat", "stack", "waitall", "save", "load",
    "from_numpy", "from_dlpack", "to_dlpack_for_read",
]


def _jnp():
    import jax.numpy as jnp
    return jnp


_jax_Array = None


def _wrap(data, device=None):
    """Wrap a raw jax/numpy array into an NDArray without copying.

    Fast constructor for the per-op dispatch path: every eager op output
    comes through here, so the common case (a jax.Array staying on its
    device) skips __init__'s isinstance/placement logic entirely and fills
    the slots directly (≙ the reference's NDArray(handle) C-side ctor)."""
    global _jax_Array
    if _jax_Array is None:
        import jax
        _jax_Array = jax.Array
    if device is None and isinstance(data, _jax_Array):
        nd = NDArray.__new__(NDArray)
        nd._entry = None
        nd._var = None
        nd._base = None
        nd._base_index = None
        nd._base_version = 0
        nd._version = 0
        nd._data = data
        return nd
    return NDArray(data, device=device, _raw=True)


def _wrap_lazy(lazyval):
    """Wrap a pending (deferred) op output into an NDArray. The buffer
    materializes at the first `_arr` access (segment flush)."""
    nd = NDArray.__new__(NDArray)
    nd._entry = None
    nd._var = None
    nd._base = None
    nd._base_index = None
    nd._base_version = 0
    nd._version = 0
    nd._data = lazyval
    return nd


def _place(arr, device):
    import jax
    if device is None:
        device = current_device()
    return jax.device_put(arr, device.jax_device)


class NDArray:
    """Multi-dimensional array on a device (≙ mxnet.nd.NDArray)."""

    __slots__ = ("_data", "_entry", "_var", "_base", "_base_index",
                 "_base_version", "_version", "__weakref__")

    # Make NDArray win against numpy in mixed dunder dispatch.
    __array_priority__ = 1000.0

    def __init__(self, source_array=None, device=None, dtype=None, _raw=False):
        import jax
        import jax.numpy as jnp
        self._entry = None
        self._var = None
        self._base = None
        self._base_index = None
        self._base_version = 0
        self._version = 0
        if _raw and isinstance(source_array, (jax.Array, _LazyVal)):
            self._data = source_array
        else:
            if isinstance(source_array, NDArray):
                source_array = source_array._arr
            arr = jnp.asarray(source_array,
                              dtype=name_to_dtype(dtype) if dtype else None)
            self._data = _place(arr, device)

    # ------------------------------------------------------------------
    # buffer access with view refresh (copy-on-write view layer)
    # ------------------------------------------------------------------
    @property
    def _arr(self):
        d = self._data
        if type(d) is _LazyVal:
            self._data = d = d.force() if d.value is None else d.value
        base = self._base
        if base is not None and self._base_version != base._version:
            self._data = base._arr[self._base_index]
            self._base_version = base._version
            return self._data
        return d

    def _set_arr(self, new_data):
        self._data = new_data
        self._version += 1

    @property
    def _aval(self):
        """Shape/dtype carrier without forcing a pending buffer."""
        d = self._data
        if type(d) is _LazyVal and d.value is None:
            return d.aval
        return self._arr

    # ------------------------------------------------------------------
    # basic properties
    # ------------------------------------------------------------------
    @property
    def shape(self):
        return tuple(self._aval.shape)

    @property
    def dtype(self):
        return self._aval.dtype

    @property
    def size(self):
        import math
        return int(math.prod(self._aval.shape))

    @property
    def ndim(self):
        return len(self._aval.shape)

    @property
    def itemsize(self):
        return _np.dtype(self._aval.dtype).itemsize

    @property
    def T(self):
        return self.transpose()

    @property
    def device(self):
        if type(self._data) is _LazyVal and self._data.value is None:
            return current_device()  # pending buffers land on the default device
        d = self._arr.devices().pop() if hasattr(self._arr, "devices") else None
        if d is None or d.platform == "cpu":
            return Device("cpu", getattr(d, "id", 0) if d else 0)
        return Device("tpu", d.id)

    # Reference naming: .ctx / .context
    ctx = device
    context = device

    @property
    def stype(self):
        """Storage type. Dense only: TPU/XLA has no row_sparse/csr storage; the
        reference's sparse NDArray (ndarray.h:61-65) is intentionally
        unsupported (SURVEY §7 hard-part #4)."""
        return "default"

    @property
    def grad(self):
        if self._var is None or self._var.grad is None:
            return None
        return self._var.grad

    # ------------------------------------------------------------------
    # materialization / sync
    # ------------------------------------------------------------------
    def asnumpy(self):
        """Blocking copy to host (≙ NDArray.asnumpy → WaitToRead + copy)."""
        return _np.asarray(self._arr)

    def item(self):
        return self._arr.item()

    def tolist(self):
        return self.asnumpy().tolist()

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(-1)[0]

    def wait_to_read(self):
        """≙ NDArray.WaitToRead (ndarray.h:395): block until computed."""
        import jax
        jax.block_until_ready(self._arr)
        return self

    wait_to_write = wait_to_read

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    def __dlpack__(self, **kwargs):
        return self._arr.__dlpack__(**kwargs)

    def __dlpack_device__(self):
        return self._arr.__dlpack_device__()

    # ------------------------------------------------------------------
    # conversion / movement
    # ------------------------------------------------------------------
    def astype(self, dtype, copy=True):
        from ..ops.registry import invoke
        dt = name_to_dtype(dtype)
        if not copy and self.dtype == dt:
            return self
        return invoke(lambda x: x.astype(dt), (self,), name="astype")

    def copy(self):
        from ..ops.registry import invoke
        return invoke(lambda x: x + 0, (self,), name="copy")

    def copyto(self, other):
        """≙ CopyFromTo (src/ndarray/ndarray.cc:1411): device-to-device copy."""
        if isinstance(other, NDArray):
            other._set_arr(_place(self._arr, other.device))
            return other
        if isinstance(other, Device):
            return _wrap(_place(self._arr, other))
        raise TypeError(f"copyto does not support type {type(other)}")

    def as_in_context(self, device):
        if device == self.device:
            return self
        return _wrap(_place(self._arr, device))

    as_in_ctx = as_in_context
    to_device = as_in_context

    def as_np_ndarray(self):
        return self

    def as_nd_ndarray(self):
        return self

    def detach(self):
        if self._base is None:
            d = self._data  # share the (possibly pending) buffer — immutable
            return _wrap_lazy(d) if type(d) is _LazyVal and d.value is None \
                else _wrap(d if type(d) is not _LazyVal else d.value)
        return _wrap(self._arr)

    def attach_grad(self, grad_req="write", stype=None):
        """Allocate a grad buffer and mark as autograd leaf
        (≙ ndarray.attach_grad / Imperative::MarkVariables)."""
        grad = zeros(self.shape, dtype=self.dtype) if grad_req != "null" else None
        self._var = autograd.Variable(grad_req, grad)

    def drop_grad(self):
        self._var = None

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    # ------------------------------------------------------------------
    # shape manipulation (methods delegate to the functional layer)
    # ------------------------------------------------------------------
    def reshape(self, *shape, **kwargs):
        """NumPy semantics (≙ mx.np.ndarray.reshape, multiarray.py:1621):
        -1 infers, 0 is a literal zero-size dim. The legacy 0=copy-dim
        magic lives in the module-level `reshape` (mx.nd parity)."""
        from ..ops.registry import invoke
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        if 0 in shape and self.size != 0:
            raise MXNetError(
                f"cannot reshape array of size {self.size} into shape "
                f"{shape}: 0 is a literal zero-size dim under np "
                f"semantics; for the legacy 0=copy-dim magic use "
                f"mx.nd.reshape(a, shape)")
        return invoke(lambda x: x.reshape(shape), (self,), name="reshape")

    def reshape_like(self, other):
        return self.reshape(other.shape)

    def transpose(self, *axes):
        from ..ops.registry import invoke
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        ax = axes if axes else None
        return invoke(lambda x: _jnp().transpose(x, ax), (self,), name="transpose")

    def swapaxes(self, a1, a2):
        from ..ops.registry import invoke
        return invoke(lambda x: _jnp().swapaxes(x, a1, a2), (self,), name="swapaxes")

    def flatten(self):
        # reference flatten: collapse all but first axis (operator Flatten)
        return self.reshape((self.shape[0], -1) if self.ndim > 1 else (-1,))

    def squeeze(self, axis=None):
        from ..ops.registry import invoke
        return invoke(lambda x: _jnp().squeeze(x, axis), (self,), name="squeeze")

    def expand_dims(self, axis):
        from ..ops.registry import invoke
        return invoke(lambda x: _jnp().expand_dims(x, axis), (self,), name="expand_dims")

    def broadcast_to(self, shape):
        from ..ops.registry import invoke
        return invoke(lambda x: _jnp().broadcast_to(x, shape), (self,), name="broadcast_to")

    def broadcast_like(self, other):
        return self.broadcast_to(other.shape)

    def repeat(self, repeats, axis=None):
        from ..ops.registry import invoke
        return invoke(lambda x: _jnp().repeat(x, repeats, axis), (self,), name="repeat")

    def tile(self, reps):
        from ..ops.registry import invoke
        return invoke(lambda x: _jnp().tile(x, reps), (self,), name="tile")

    def split(self, indices_or_sections, axis=0):
        from ..ops.registry import invoke
        return invoke(lambda x: tuple(_jnp().split(x, indices_or_sections, axis)),
                      (self,), name="split", multi_out=True)

    # ------------------------------------------------------------------
    # reductions / math methods (thin delegations; full set in mx.np)
    # ------------------------------------------------------------------
    def _delegate(self, fname, *args, **kwargs):
        from ..ops.registry import invoke
        jfn = getattr(_jnp(), fname)
        return invoke(lambda x: jfn(x, *args, **kwargs), (self,), name=fname)

    def sum(self, axis=None, keepdims=False, dtype=None):
        return self._delegate("sum", axis=axis, keepdims=keepdims, dtype=dtype)

    def mean(self, axis=None, keepdims=False, dtype=None):
        return self._delegate("mean", axis=axis, keepdims=keepdims, dtype=dtype)

    def max(self, axis=None, keepdims=False):
        return self._delegate("max", axis=axis, keepdims=keepdims)

    def min(self, axis=None, keepdims=False):
        return self._delegate("min", axis=axis, keepdims=keepdims)

    def prod(self, axis=None, keepdims=False):
        return self._delegate("prod", axis=axis, keepdims=keepdims)

    def std(self, axis=None, keepdims=False, ddof=0):
        return self._delegate("std", axis=axis, keepdims=keepdims, ddof=ddof)

    def var(self, axis=None, keepdims=False, ddof=0):
        return self._delegate("var", axis=axis, keepdims=keepdims, ddof=ddof)

    def argmax(self, axis=None):
        return self._delegate("argmax", axis=axis)

    def argmin(self, axis=None):
        return self._delegate("argmin", axis=axis)

    def cumsum(self, axis=None, dtype=None):
        return self._delegate("cumsum", axis=axis, dtype=dtype)

    def clip(self, a_min=None, a_max=None):
        return self._delegate("clip", a_min, a_max)

    def abs(self):
        return self._delegate("abs")

    def exp(self):
        return self._delegate("exp")

    def log(self):
        return self._delegate("log")

    def sqrt(self):
        return self._delegate("sqrt")

    def sign(self):
        return self._delegate("sign")

    def round(self):
        return self._delegate("round")

    def dot(self, other):
        from ..ops.registry import invoke
        return invoke(lambda a, b: _jnp().dot(a, b), (self, other), name="dot")

    def norm(self, ord=None, axis=None, keepdims=False):
        from ..ops.registry import invoke
        return invoke(lambda x: _jnp().linalg.norm(x, ord=ord, axis=axis,
                                                   keepdims=keepdims),
                      (self,), name="norm")

    def take(self, indices, axis=None, mode="clip"):
        from ..ops.registry import invoke
        return invoke(lambda x, i: _jnp().take(x, i, axis=axis,
                                               mode="clip" if mode == "clip" else "wrap"),
                      (self, _as_nd(indices)), name="take")

    def tostype(self, stype):
        if stype != "default":
            raise MXNetError("sparse storage types are unsupported on TPU "
                             "(SURVEY §7: no row_sparse/csr)")
        return self

    # ------------------------------------------------------------------
    # indexing
    # ------------------------------------------------------------------
    def __getitem__(self, key):
        from ..ops.registry import invoke
        nd_key = _index_to_raw(key)
        out = invoke(lambda x: x[nd_key], (self,) , name="getitem")
        # Basic (non-array) indices form write-through views of self.
        if _is_basic_index(key):
            out._base = self
            out._base_index = nd_key
            out._base_version = self._version
        return out

    def __setitem__(self, key, value):
        jnp = _jnp()
        if isinstance(value, NDArray):
            # full-slice overwrite with a matching buffer: adopt it without
            # materializing (keeps `grad[:] = ct` / param updates deferred —
            # buffers are immutable so sharing is safe)
            if (_is_plain_slice_all(key) and self._base is None
                    and value._base is None
                    and value.shape == self.shape
                    and value.dtype == self.dtype):
                d = value._data
                self._set_arr(d.value if type(d) is _LazyVal
                              and d.value is not None else d)
                return
            value = value._arr
        nd_key = _index_to_raw(key)
        if self._base is not None and _is_basic_index(self._base_index):
            # write-through view: update the base storage
            base = self._base
            cur = base._arr
            if _is_plain_slice_all(nd_key):
                new_base = cur.at[self._base_index].set(value)
            else:
                sub = cur[self._base_index].at[nd_key].set(value)
                new_base = cur.at[self._base_index].set(sub)
            base._set_arr(new_base)
            self._data = new_base[self._base_index]
            self._base_version = base._version
            self._version += 1
        else:
            if _is_plain_slice_all(nd_key) and not _np.isscalar(value):
                new = jnp.broadcast_to(jnp.asarray(value, self.dtype), self.shape)
            else:
                new = self._arr.at[nd_key].set(value)
            if new.shape != self.shape:
                raise MXNetError("in-place assignment cannot change shape")
            self._set_arr(new)

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # ------------------------------------------------------------------
    # arithmetic dunders
    # ------------------------------------------------------------------
    def _binop(self, other, fname, reflect=False):
        from ..ops.registry import invoke
        jfn = getattr(_jnp(), fname)
        if isinstance(other, NDArray) or isinstance(other, numeric_types) \
                or isinstance(other, _np.ndarray):
            # python scalars / numpy values pass through RAW: invoke handles
            # them (segment const slots; jit traces them weak-typed exactly
            # like the eager jnp call), and skipping the NDArray ctor saves
            # a per-op host device_put — the single biggest cost of eager
            # scalar arithmetic. Weak typing also
            # matches the reference's dtype-preserving scalar ops
            # (bf16 array * 2.0 stays bf16).
            a, b = (other, self) if reflect else (self, other)
            return invoke(lambda x, y: jfn(x, y), (a, b), name=fname)
        return NotImplemented

    def __add__(self, o): return self._binop(o, "add")
    def __radd__(self, o): return self._binop(o, "add", True)
    def __sub__(self, o): return self._binop(o, "subtract")
    def __rsub__(self, o): return self._binop(o, "subtract", True)
    def __mul__(self, o): return self._binop(o, "multiply")
    def __rmul__(self, o): return self._binop(o, "multiply", True)
    def __truediv__(self, o): return self._binop(o, "true_divide")
    def __rtruediv__(self, o): return self._binop(o, "true_divide", True)
    def __floordiv__(self, o): return self._binop(o, "floor_divide")
    def __rfloordiv__(self, o): return self._binop(o, "floor_divide", True)
    def __mod__(self, o): return self._binop(o, "mod")
    def __rmod__(self, o): return self._binop(o, "mod", True)
    def __pow__(self, o): return self._binop(o, "power")
    def __rpow__(self, o): return self._binop(o, "power", True)
    def __matmul__(self, o): return self._binop(o, "matmul")
    def __rmatmul__(self, o): return self._binop(o, "matmul", True)

    def __iadd__(self, o):
        out = self._binop(o, "add")
        self._adopt(out)
        return self

    def __isub__(self, o):
        out = self._binop(o, "subtract")
        self._adopt(out)
        return self

    def __imul__(self, o):
        out = self._binop(o, "multiply")
        self._adopt(out)
        return self

    def __itruediv__(self, o):
        out = self._binop(o, "true_divide")
        self._adopt(out)
        return self

    def _adopt(self, other):
        """In-place update: take other's buffer (and tape entry, so `x += y`
        inside record() stays differentiable like the reference's *WithRecord
        view ops, ndarray.cc:264-300)."""
        self._set_arr(other._arr)
        self._entry = other._entry

    def __neg__(self):
        from ..ops.registry import invoke
        return invoke(lambda x: -x, (self,), name="negative")

    def __abs__(self):
        return self.abs()

    def __eq__(self, o): return self._binop(o, "equal")
    def __ne__(self, o): return self._binop(o, "not_equal")
    def __lt__(self, o): return self._binop(o, "less")
    def __le__(self, o): return self._binop(o, "less_equal")
    def __gt__(self, o): return self._binop(o, "greater")
    def __ge__(self, o): return self._binop(o, "greater_equal")

    def __hash__(self):
        return id(self)

    def __bool__(self):
        if self.size == 1:
            return bool(self.asnumpy().reshape(-1)[0])
        raise MXNetError("The truth value of an NDArray with multiple elements "
                         "is ambiguous")

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __index__(self):
        if self.ndim == 0 and _np.issubdtype(_np.dtype(self.dtype), _np.integer):
            return int(self.asscalar())
        raise TypeError("only integer scalar arrays can be converted to an index")

    def __repr__(self):
        return f"{self.asnumpy()!r} <NDArray {self.shape} @{self.device}>"

    def __str__(self):
        return str(self.asnumpy())

    def __getstate__(self):
        return {"data": self.asnumpy(), "device": repr(self.device)}

    def __setstate__(self, state):
        import jax.numpy as jnp
        self._entry = None
        self._var = None
        self._base = None
        self._base_index = None
        self._base_version = 0
        self._version = 0
        self._data = jnp.asarray(state["data"])


def _as_nd(x, device=None, dtype=None):
    if isinstance(x, NDArray):
        return x
    return NDArray(x, device=device, dtype=dtype)


def _index_to_raw(key):
    """Convert NDArray / numpy-array / list components of an index into
    raw jax arrays (jax rejects non-tuple sequences and raw numpy bool
    masks would hit ambiguous-truth comparisons downstream)."""
    def conv(k):
        if isinstance(k, NDArray):
            return k._arr
        if isinstance(k, (list, _np.ndarray)):
            return _jnp().asarray(k)
        return k
    if isinstance(key, tuple):
        return tuple(conv(k) for k in key)
    return conv(key)


def _is_plain_slice_all(key):
    return isinstance(key, slice) and key == slice(None, None, None)


def _is_basic_index(key):
    if isinstance(key, (int, slice)) or key is None or key is Ellipsis:
        return True
    if isinstance(key, tuple):
        return all(isinstance(k, (int, slice)) or k is None or k is Ellipsis
                   for k in key)
    return False


# ---------------------------------------------------------------------------
# creation / io functions (mx.nd namespace surface)
# ---------------------------------------------------------------------------
def array(source_array, device=None, dtype=None, ctx=None):
    return NDArray(source_array, device=device or ctx, dtype=dtype)


def zeros(shape, device=None, dtype=None, ctx=None, **kwargs):
    jnp = _jnp()
    if isinstance(shape, int):
        shape = (shape,)
    return _wrap(_place(jnp.zeros(shape, name_to_dtype(dtype)), device or ctx))


def ones(shape, device=None, dtype=None, ctx=None, **kwargs):
    jnp = _jnp()
    if isinstance(shape, int):
        shape = (shape,)
    return _wrap(_place(jnp.ones(shape, name_to_dtype(dtype)), device or ctx))


def full(shape, val, device=None, dtype=None, ctx=None):
    jnp = _jnp()
    if isinstance(shape, int):
        shape = (shape,)
    return _wrap(_place(jnp.full(shape, val, name_to_dtype(dtype)), device or ctx))


def empty(shape, device=None, dtype=None, ctx=None):
    return zeros(shape, device=device or ctx, dtype=dtype)


def arange(start, stop=None, step=1.0, repeat=1, device=None, dtype=None, ctx=None):
    jnp = _jnp()
    out = jnp.arange(start, stop, step, name_to_dtype(dtype or "float32"))
    if repeat != 1:
        out = jnp.repeat(out, repeat)
    return _wrap(_place(out, device or ctx))


def reshape(a, shape, reverse=False):
    """Legacy mx.nd.reshape with the reference's magic values
    (≙ src/operator/tensor/matrix_op.cc Reshape): 0 = copy the input dim,
    -1 = infer; reverse=True aligns the magic from the right. (np users:
    use the method/`mx.np.reshape`, pure numpy semantics.)"""
    if isinstance(shape, int):
        shape = (shape,)
    if reverse:
        in_rev = a.shape[::-1]
        shape = tuple(in_rev[i] if s == 0 else s
                      for i, s in enumerate(shape[::-1]))[::-1]
    else:
        shape = tuple(a.shape[i] if s == 0 else s
                      for i, s in enumerate(shape))
    return a.reshape(shape)


def zeros_like(a):
    return zeros(a.shape, dtype=a.dtype)


def ones_like(a):
    return ones(a.shape, dtype=a.dtype)


def concat(*arrays, dim=1):
    from ..ops.registry import invoke
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = tuple(arrays[0])
    return invoke(lambda *xs: _jnp().concatenate(xs, axis=dim), arrays, name="concat")


def stack(*arrays, axis=0):
    from ..ops.registry import invoke
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = tuple(arrays[0])
    return invoke(lambda *xs: _jnp().stack(xs, axis=axis), arrays, name="stack")


def waitall():
    """≙ Engine::WaitForAll / mx.nd.waitall: barrier on all pending work.

    PJRT has no global 'wait for everything' call; flushing the pending op
    segment then blocking on every live array is the faithful equivalent (a
    dummy computation only proves the stream accepts work, not that queued
    computations finished).
    """
    import jax
    _flush_segments()
    for a in jax.live_arrays():
        a.block_until_ready()


def from_numpy(a, zero_copy=False):
    return NDArray(a)


def from_dlpack(capsule):
    import jax
    return _wrap(jax.dlpack.from_dlpack(capsule))


def to_dlpack_for_read(arr):
    return arr._arr.__dlpack__()


def save(fname, data):
    """Save dict/list of NDArrays (≙ mx.nd.save, ndarray.cc:1861). Uses the
    .npz container instead of the dmlc::Stream binary format."""
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, (list, tuple)):
        payload = {f"arr_{i}": a.asnumpy() for i, a in enumerate(data)}
        payload["__mx_list__"] = _np.array(1)
    elif isinstance(data, dict):
        payload = {k: v.asnumpy() for k, v in data.items()}
    else:
        raise TypeError("save expects NDArray, list or dict")
    # write through a file object: bare np.savez APPENDS '.npz' to a path
    # that lacks it, silently saving under a different name than asked
    with open(fname, "wb") as f:
        _np.savez(f, **payload)


def load(fname):
    """Load NDArrays saved by `save` (≙ mx.nd.load, ndarray.cc:1994)."""
    with _np.load(fname, allow_pickle=False) as f:
        keys = [k for k in f.files if k != "__mx_list__"]
        if "__mx_list__" in f.files:
            keys.sort(key=lambda k: int(k.split("_")[1]))
            return [array(f[k]) for k in keys]
        return {k: array(f[k]) for k in keys}


def __getattr__(name):
    """mx.nd.<op> delegates to the numpy frontend: the reference's legacy nd
    namespace (hundreds of generated wrappers, python/mxnet/ndarray/) shares
    one implementation with mx.np here."""
    if name == "sparse":   # mx.nd.sparse (≙ python/mxnet/ndarray/sparse.py)
        import importlib
        mod = importlib.import_module(".sparse", __name__)
        globals()[name] = mod
        return mod
    from .. import numpy as _mxnp
    fn = getattr(_mxnp, name, None)
    if fn is None:
        # the legacy nd namespace also carried the NN op surface
        from .. import numpy_extension as _mxnpx
        fn = getattr(_mxnpx, name, None)
    if fn is None:
        raise AttributeError(
            f"module 'mx.nd' has no attribute {name!r}")
    globals()[name] = fn
    return fn
