"""mx.library — extension loading (≙ python/mxnet/library.py MXLoadLib +
include/mxnet/lib_api.h custom-op ABI).

The reference loads a compiled .so implementing the 1.3k-LoC C ABI. The
TPU-native extension unit is a PYTHON module (jax kernels are Python-level;
there is no stable C kernel ABI to target): `load(path)` imports the file
and calls its `register_ops(mx)` hook, which registers custom ops
(mx.operator.register), kvstores (KVStoreBase.register), optimizers
(mx.optimizer.register) or metrics — the same extension points the
reference exposes through lib_api.h.
"""
from __future__ import annotations

import importlib.util
import os

from .base import MXNetError

__all__ = ["load", "load_native"]

_loaded = {}


def load(path, verbose=True):
    """Load an extension module and run its register hook."""
    path = os.path.abspath(path)
    if path in _loaded:
        return _loaded[path]
    if not os.path.exists(path):
        raise MXNetError(f"extension not found: {path}")
    if path.endswith(".so"):
        # native extension: the C-level ABI (≙ MXLoadLib of a lib_api.h
        # library); see load_native for the contract
        return load_native(path, verbose=verbose)
    spec = importlib.util.spec_from_file_location(
        f"mx_ext_{os.path.basename(path).removesuffix('.py')}", path)
    if spec is None or spec.loader is None:
        raise MXNetError(f"not a loadable python extension: {path}")
    mod = importlib.util.module_from_spec(spec)
    import sys
    sys.modules[spec.name] = mod  # required before exec (enables pickling)
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        sys.modules.pop(spec.name, None)
        raise
    if hasattr(mod, "register_ops"):
        import incubator_mxnet_tpu as mx
        mod.register_ops(mx)
    _loaded[path] = mod
    if verbose:
        print(f"loaded extension {path}")
    return mod


# ---------------------------------------------------------------------------
# Native (.so) extension ABI — the C-level counterpart (≙ MXLoadLib +
# include/mxnet/lib_api.h:649-771 CustomOp registration from an external
# shared library). TPU-native contract (original, small, and honest about
# where the code runs): extension ops are HOST kernels over f32 buffers,
# bridged into the compute graph with jax.pure_callback — so a loaded op
# works eagerly, under jit, and inside hybridized blocks alike.
#
# The library must export (C linkage):
#   int  mxtpu_ext_abi_version(void);             // must return 1
#   int  mxtpu_ext_num_ops(void);
#   const char* mxtpu_ext_op_name(int i);
#   // fill out_shape/out_ndim from the input shapes; rc 0 on success
#   // out_shape buffer holds up to 16 dims (MXTPU_MAX_NDIM); rc 0 ok
#   int  mxtpu_ext_infer_shape(const char* op, int n_in,
#                              const int64_t* shapes_flat, const int* ndims,
#                              int64_t* out_shape, int* out_ndim);
#   // compute out (f32, caller-allocated per the inferred shape); rc 0
#   int  mxtpu_ext_compute(const char* op, int n_in, const float** ins,
#                          const int64_t* shapes_flat, const int* ndims,
#                          float* out, const int64_t* out_shape,
#                          int out_ndim);
# ---------------------------------------------------------------------------

_native_loaded = {}


def load_native(path, verbose=True):
    """Load a native extension .so and register its ops (callable through
    mx.npx.<name>, the op registry, and MXImperativeInvoke)."""
    import ctypes

    import numpy as _np

    path = os.path.abspath(path)
    if path in _native_loaded:
        return _native_loaded[path]
    if not os.path.exists(path):
        raise MXNetError(f"extension not found: {path}")
    lib = ctypes.CDLL(path)
    for sym in ("mxtpu_ext_abi_version", "mxtpu_ext_num_ops",
                "mxtpu_ext_op_name", "mxtpu_ext_infer_shape",
                "mxtpu_ext_compute"):
        if not hasattr(lib, sym):
            raise MXNetError(
                f"{path}: missing symbol {sym} (not an mxtpu extension; "
                "see mx.library.load_native docs for the ABI)")
    lib.mxtpu_ext_abi_version.restype = ctypes.c_int
    ver = lib.mxtpu_ext_abi_version()
    if ver != 1:
        raise MXNetError(f"{path}: extension ABI version {ver} != 1")
    lib.mxtpu_ext_num_ops.restype = ctypes.c_int
    lib.mxtpu_ext_op_name.restype = ctypes.c_char_p
    lib.mxtpu_ext_op_name.argtypes = [ctypes.c_int]
    I64P = ctypes.POINTER(ctypes.c_int64)
    I32P = ctypes.POINTER(ctypes.c_int)
    F32P = ctypes.POINTER(ctypes.c_float)
    lib.mxtpu_ext_infer_shape.restype = ctypes.c_int
    lib.mxtpu_ext_infer_shape.argtypes = [
        ctypes.c_char_p, ctypes.c_int, I64P, I32P, I64P, I32P]
    lib.mxtpu_ext_compute.restype = ctypes.c_int
    lib.mxtpu_ext_compute.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.POINTER(F32P), I64P, I32P,
        F32P, I64P, ctypes.c_int]

    def _flat_shapes(shapes):
        flat = []
        ndims = []
        for s in shapes:
            ndims.append(len(s))
            flat.extend(int(d) for d in s)
        return ((ctypes.c_int64 * max(len(flat), 1))(*flat),
                (ctypes.c_int * max(len(ndims), 1))(*ndims))

    def _infer(op_b, shapes):
        flat, ndims = _flat_shapes(shapes)
        out_shape = (ctypes.c_int64 * 16)()
        out_ndim = ctypes.c_int()
        rc = lib.mxtpu_ext_infer_shape(op_b, len(shapes), flat, ndims,
                                       out_shape, ctypes.byref(out_ndim))
        if rc != 0:
            raise MXNetError(f"extension infer_shape failed (rc={rc})")
        if not 0 <= out_ndim.value <= 16:
            raise MXNetError(
                f"extension returned out_ndim={out_ndim.value}; the ABI "
                "bounds output rank at 16 (MXTPU_MAX_NDIM)")
        return tuple(out_shape[i] for i in range(out_ndim.value))

    def _make_op(op_name):
        op_b = op_name.encode()

        def host_kernel(out_shape, *host_arrays):
            arrays = [_np.ascontiguousarray(a, _np.float32)
                      for a in host_arrays]
            shapes = [a.shape for a in arrays]
            out = _np.zeros(out_shape, _np.float32)
            flat, ndims = _flat_shapes(shapes)
            ptrs = (F32P * max(len(arrays), 1))(*[
                a.ctypes.data_as(F32P) for a in arrays])
            oshape = (ctypes.c_int64 * max(len(out_shape), 1))(*out_shape)
            rc = lib.mxtpu_ext_compute(op_b, len(arrays), ptrs, flat, ndims,
                                       out.ctypes.data_as(F32P), oshape,
                                       len(out_shape))
            if rc != 0:
                raise MXNetError(f"extension op {op_name} failed (rc={rc})")
            return out

        def op(*inputs):
            import functools

            import jax
            import jax.numpy as jnp

            from .ndarray import NDArray, _wrap
            raws = [x._arr if isinstance(x, NDArray) else jnp.asarray(x)
                    for x in inputs]
            out_shape = _infer(op_b, [tuple(r.shape) for r in raws])
            if not any(isinstance(r, jax.core.Tracer) for r in raws):
                # eager: run the host kernel directly — outside a trace
                # there is no program to call back from
                out = host_kernel(out_shape,
                                  *[_np.asarray(r) for r in raws])
                result = jnp.asarray(out)
            else:
                # traced (jit/hybridize): bridge via pure_callback; on
                # platforms without host-callback support XLA raises at
                # run time — extension ops are host kernels by contract
                result = jax.pure_callback(
                    functools.partial(host_kernel, out_shape),
                    jax.ShapeDtypeStruct(out_shape, jnp.float32),
                    *[r.astype(jnp.float32) for r in raws])
            return _wrap(result) if any(isinstance(x, NDArray)
                                        for x in inputs) else result

        op.__name__ = op_name
        op.__doc__ = (f"native extension op {op_name!r} from {path} "
                      "(host kernel via jax.pure_callback)")
        return op

    from . import numpy_extension as npx
    ops = {}
    for i in range(lib.mxtpu_ext_num_ops()):
        nm = lib.mxtpu_ext_op_name(i).decode()
        if getattr(npx, nm, None) is not None:
            raise MXNetError(
                f"extension op {nm!r} collides with an existing npx op "
                "(duplicate registration is an error, reference semantics)")
        fn = _make_op(nm)
        ops[nm] = fn
        setattr(npx, nm, fn)
    _native_loaded[path] = {"lib": lib, "ops": ops}
    if verbose:
        print(f"loaded native extension {path}: ops {sorted(ops)}")
    return _native_loaded[path]
