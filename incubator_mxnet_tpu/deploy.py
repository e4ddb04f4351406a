"""Deployment runtime: load and execute `HybridBlock.export` artifacts.

Capability equivalent of the reference's predict/C-API stack
(`/root/reference/include/mxnet/c_predict_api.h`,
`/root/reference/src/c_api/c_predict_api.cc:120-310`): a self-contained
loader that serves inference from the exported artifact triple
(`<prefix>.jaxport` + `<prefix>.params.npz` + `<prefix>.deploy.json`)
without the model's Python class on the import path. It backs three
consumers:

  * Python — `ExportedModel` directly, or `gluon.SymbolBlock.imports`
  * the C ABI — `native/c_api.cc` (libmxtpu.so), the stable non-Python
    boundary playing the role of the reference's 240-function c_api.h
  * the C++ frontend — `cpp_package/include/mxtpu/*.hpp` (≙ cpp-package)

TPU-native design: the executable artifact is a versioned `jax.export`
serialization (StableHLO inside, lowered for both cpu and tpu) run through
one `jax.jit` on the ambient PJRT client; there is no NNVM graph, executor,
or ps-lite layer to re-create. The `_capi_*` functions at the bottom are
the C ABI's internal entry points — plain functions over plain types so the
embedded-interpreter side (c_api.cc) stays a thin marshalling layer.
"""
from __future__ import annotations

import json
import os

import numpy as _np

from .base import MXNetError, _register_env

_register_env("MXNET_COMPILE_CACHE_DIR", str, None,
              "Directory for jax's persistent compilation cache: every "
              "jit compile serializes its executable there, and a later "
              "process (replica, restart) DESERIALIZES instead of "
              "recompiling — replica warmup becomes O(load), not "
              "O(compile). Armed at the first ExportedModel load or "
              "serve.CachedDecoder build; share the dir across replicas. "
              "Ignored when JAX_COMPILATION_CACHE_DIR is set: jax already "
              "uses that directory and no other is set in code")

# where the repo's own runners (chip_smoke.py, tools/crashtest.py) keep
# the cache when no variable places it: a FIXED path inside
# the checkout (the path is part of a cache key's provenance — a directory
# that moves between runs never hits)
CHECKOUT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def default_compile_cache_to_checkout():
    """For a runner of this checkout, before it (or a child it spawns)
    arms the cache: when neither variable places it, export
    `MXNET_COMPILE_CACHE_DIR` as the fixed in-checkout path, so this
    process's and every child's `maybe_enable_compile_cache()` find the
    same directory. Never a temp-named one — it would not hit again.
    Touches the environment only (a router parent stays off jax)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ.setdefault("MXNET_COMPILE_CACHE_DIR",
                              CHECKOUT_COMPILE_CACHE_DIR)


# armed-once latch: jax.config.update is process-global, and re-applying
# it per model load would spam config churn
_COMPILE_CACHE_ARMED = [False]


def maybe_enable_compile_cache():
    """Arm jax's persistent compilation cache (idempotent). ONE rule for
    where it lives:

      1. `JAX_COMPILATION_CACHE_DIR` set — jax already uses it; no other
         directory is set in code (`MXNET_COMPILE_CACHE_DIR` is ignored);
      2. else `MXNET_COMPILE_CACHE_DIR` (the repo's own runners default it
         to `<checkout>/.jax_cache`: `default_compile_cache_to_checkout`);
      3. else no cache (returns False).

    Must run BEFORE the first compile of the programs it should cover —
    ExportedModel and serve.CachedDecoder call it in their constructors.
    The min-time / min-size thresholds are zeroed so even small serving
    programs (bucket MLPs, decode steps) persist: replica warmup is the
    target, and a second replica should skip EVERY compile, not just the
    slow ones. Returns True when the cache is armed."""
    if _COMPILE_CACHE_ARMED[0]:
        return True
    placed = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    d = None if placed else os.environ.get("MXNET_COMPILE_CACHE_DIR")
    if not placed and not d:
        return False
    import jax
    if d:
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax initializes its cache backend lazily at the FIRST compile and
    # then never re-reads the dir config: a process that compiled
    # anything before arming would silently keep running cache-less.
    # Reset forces re-initialization against the configured dir.
    from jax.experimental.compilation_cache import compilation_cache as _cc
    _cc.reset_cache()
    _COMPILE_CACHE_ARMED[0] = True
    return True

# Reference dtype codes (mshadow/base.h kFloat32..; c_api callers use these
# integers on the wire). bfloat16 appended at its reference index (12).
DTYPE_CODES = {
    0: "float32", 1: "float64", 2: "float16", 3: "uint8",
    4: "int32", 5: "int8", 6: "int64", 7: "bool",
    8: "int16", 9: "uint16", 10: "uint32", 11: "uint64",
    12: "bfloat16",
}
DTYPE_TO_CODE = {v: k for k, v in DTYPE_CODES.items()}


def _np_dtype(code_or_name):
    if isinstance(code_or_name, (int, _np.integer)):
        name = DTYPE_CODES.get(int(code_or_name))
        if name is None:
            raise MXNetError(f"unknown dtype code {code_or_name}")
    else:
        name = str(code_or_name)
    if name == "bfloat16":
        import ml_dtypes
        return _np.dtype(ml_dtypes.bfloat16)
    return _np.dtype(name)


class ExportedModel:
    """A loaded, runnable export artifact (≙ the reference PredictorHandle).

    Usage::

        model = ExportedModel("model-0000")      # or explicit paths
        out = model.run(x)                       # np.ndarray in, out

    Concurrency contract (the reference predictor requires one handle per
    thread; this one does not): `run`/`call_arrays` are safe to call from
    any number of threads on a SHARED instance. The jitted call is a
    compiled-program invocation on the PJRT client, which is thread-safe,
    and `run` touches no mutable instance state after construction. The
    only races are benign: concurrent FIRST calls may both enter tracing —
    jax serializes compilation internally — so latency-sensitive servers
    should `warmup()` once before going multi-threaded (serve.Server does).
    `compile_cache_size()` exposes the jit cache entry count so callers can
    assert the zero-retrace steady state (tests/test_serve.py holds this
    contract under an 8-thread hammer).
    """

    def __init__(self, prefix=None, *, jaxport=None, params=None,
                 manifest=None):
        if prefix is not None:
            jaxport = jaxport or f"{prefix}.jaxport"
            params = params or f"{prefix}.params.npz"
            manifest = manifest or f"{prefix}.deploy.json"
        if not (jaxport and params and manifest):
            raise MXNetError(
                "ExportedModel needs a prefix or explicit jaxport=, "
                "params=, manifest= paths")
        for p in (jaxport, params, manifest):
            if not os.path.exists(p):
                raise MXNetError(f"export artifact missing: {p}")

        with open(manifest) as f:
            self.manifest = json.load(f)
        if self.manifest.get("format_version") != 1:
            raise MXNetError(
                f"unsupported deploy manifest version "
                f"{self.manifest.get('format_version')!r}")

        import jax
        import jax.export as jexp
        # persistent-compilation-cache wiring: with MXNET_COMPILE_CACHE_DIR
        # set, this artifact's bucket program compiles once per FLEET, not
        # once per replica (armed before the jit below ever compiles)
        maybe_enable_compile_cache()
        with open(jaxport, "rb") as f:
            self._exported = jexp.deserialize(f.read())
        loaded = _np.load(params, allow_pickle=False)
        try:
            self._pbufs = tuple(
                jax.numpy.asarray(loaded[name])
                for name in self.manifest["params"])
        except KeyError as e:
            raise MXNetError(
                f"parameter {e} listed in manifest but absent from "
                f"{params}") from e
        self._key = jax.random.PRNGKey(0)
        self._call = jax.jit(self._exported.call)
        self.n_out = int(self.manifest["n_out"])
        self.single_output = bool(self.manifest["single_output"])
        self.input_specs = [
            (tuple(d["shape"]), d["dtype"]) for d in self.manifest["inputs"]]

    @property
    def num_inputs(self):
        return len(self.input_specs)

    @property
    def output_arity(self):
        return self.n_out

    @property
    def batch_size(self):
        """Leading dim of the first exported input (the batch bucket this
        artifact serves; exports are static-shape programs)."""
        return int(self.input_specs[0][0][0])

    def warmup(self):
        """Compile (and run once on zeros) ahead of traffic, so no serving
        thread ever hits tracing. Returns self."""
        self.run(*[_np.zeros(s, dtype=_np_dtype(d))
                   for s, d in self.input_specs])
        return self

    def lowered(self, *inputs):
        """The bucket program lowered at its exported shapes WITHOUT
        executing it: the inspection surface for
        `mx.inspect.inspect_step(model)` — fusion-level offender
        attribution of exactly the program `run()` dispatches. The
        lowering lands in the jit cache, so a later `run()`/`warmup()`
        does not recompile. `inputs` are optional (the exported shapes
        are fixed); when given they must match `input_specs` — lowering
        at any other shape would inspect a program `run()` never uses."""
        if inputs:
            if len(inputs) != len(self.input_specs):
                raise MXNetError(
                    f"ExportedModel.lowered got {len(inputs)} inputs, "
                    f"artifact expects {len(self.input_specs)}")
            arrs = []
            for a, (s, d) in zip(inputs, self.input_specs):
                a = _np.asarray(a)
                if tuple(a.shape) != tuple(s):
                    raise MXNetError(
                        f"ExportedModel.lowered input shape {a.shape} "
                        f"does not match the exported spec {tuple(s)} — "
                        f"the artifact's program is fixed-shape")
                arrs.append(a.astype(_np_dtype(d), copy=False))
        else:
            arrs = [_np.zeros(s, dtype=_np_dtype(d))
                    for s, d in self.input_specs]
        return self._call.lower(self._pbufs, self._key, *arrs)

    def compile_cache_size(self):
        """Entries in the jitted call's compile cache (1 after warmup; any
        growth in steady state is a retrace). -1 when the running jax
        version does not expose the counter."""
        return int(getattr(self._call, "_cache_size", lambda: -1)())

    def _check_inputs(self, inputs):
        if len(inputs) != len(self.input_specs):
            raise MXNetError(
                f"model takes {len(self.input_specs)} inputs, "
                f"got {len(inputs)}")
        arrs = []
        for i, (x, (shape, dtype)) in enumerate(
                zip(inputs, self.input_specs)):
            a = _np.asarray(getattr(x, "asnumpy", lambda: x)())
            if tuple(a.shape) != shape:
                raise MXNetError(
                    f"input {i}: shape {tuple(a.shape)} != exported "
                    f"{shape} (exports are static-shape programs)")
            if str(a.dtype) != dtype:
                a = a.astype(_np_dtype(dtype))
            arrs.append(a)
        return arrs

    def run(self, *inputs):
        """Execute the exported forward; returns np.ndarray or a tuple."""
        arrs = self._check_inputs(inputs)
        out_raw, _aux, _ = self._call(self._pbufs, self._key, *arrs)
        outs = tuple(_np.asarray(o) for o in out_raw)
        return outs[0] if self.single_output else outs

    def call_arrays(self, *arrs):
        """Traceable forward over jax arrays: safe to call inside another
        jit trace (SymbolBlock embedded in a hybridized parent) — no host
        transfer, no shape-check materialization. Returns the raw output
        tuple."""
        import jax.numpy as jnp
        cast = tuple(
            jnp.asarray(a, _np_dtype(dtype))
            for a, (_, dtype) in zip(arrs, self.input_specs))
        out_raw, _aux, _ = self._call(self._pbufs, self._key, *cast)
        return tuple(out_raw)


# --------------------------------------------------------------------------
# C ABI support functions (called from native/c_api.cc via the embedded
# interpreter). Handles crossing the boundary are ordinary Python objects
# whose refcounts the C side owns.
# --------------------------------------------------------------------------

def _capi_version():
    from . import __version__
    return __version__


def _capi_dtype_size(dtype_code):
    """Element width in bytes for a C-ABI dtype code (single source of
    truth for the boundary; c_api.cc queries this rather than keeping its
    own table)."""
    return int(_np_dtype(dtype_code).itemsize)


def _capi_ndarray_create(buf, shape, dtype_code):
    """bytes-like + shape list + reference dtype code -> NDArray."""
    from . import np as mxnp
    a = _np.frombuffer(bytes(buf), dtype=_np_dtype(dtype_code))
    a = a.reshape(tuple(shape))
    return mxnp.array(a)


def _capi_ndarray_zeros(shape, dtype_code):
    from . import np as mxnp
    return mxnp.zeros(tuple(shape), dtype=str(_np_dtype(dtype_code)))


def _capi_ndarray_shape(nd):
    return list(nd.shape)


def _capi_ndarray_dtype(nd):
    name = str(nd.dtype)
    if name not in DTYPE_TO_CODE:
        raise MXNetError(f"dtype {name} has no C ABI code")
    return DTYPE_TO_CODE[name]


def _capi_ndarray_tobytes(nd):
    return nd.asnumpy().tobytes()


def _capi_invoke(op_name, inputs, kwargs_json):
    """Generic imperative op dispatch (≙ MXImperativeInvokeEx,
    /root/reference/src/c_api/c_api_ndarray.cc:91): look the op up in the
    np/npx/nd namespaces, call with positional NDArray inputs + JSON
    kwargs, normalize to a list of NDArrays."""
    from . import np as mxnp, npx, nd
    from .ndarray import NDArray
    fn = None
    for ns in (mxnp, npx, nd):
        fn = getattr(ns, op_name, None)
        if fn is not None:
            break
    if fn is None:
        raise MXNetError(f"unknown operator {op_name!r}")
    kwargs = json.loads(kwargs_json) if kwargs_json else {}
    out = fn(*inputs, **kwargs)
    if isinstance(out, (list, tuple)):
        return [o if isinstance(o, NDArray) else mxnp.array(o) for o in out]
    return [out if isinstance(out, NDArray) else mxnp.array(out)]


def _capi_waitall():
    from .ndarray import waitall
    waitall()


# -- autograd group (≙ MXAutograd*, reference c_api.h:1308) ----------------
_GRAD_REQ_OF_CODE = {0: "null", 1: "write", 2: "write", 3: "add"}


def _capi_autograd_set_recording(flag):
    from . import autograd
    return int(autograd.set_recording(bool(flag)))


def _capi_autograd_set_training(flag):
    from . import autograd
    return int(autograd.set_training(bool(flag)))


def _capi_autograd_is_recording():
    from . import autograd
    return autograd.is_recording()


def _capi_autograd_is_training():
    from . import autograd
    return autograd.is_training()


def _capi_autograd_mark_variables(variables, req_codes):
    from . import autograd
    reqs = [_GRAD_REQ_OF_CODE[int(c)] for c in req_codes]
    for v, r in zip(variables, reqs):
        v.attach_grad(grad_req=r)
    return True


def _capi_autograd_backward(heads, head_grads, retain_graph):
    from . import autograd
    autograd.backward(list(heads),
                      list(head_grads) if head_grads is not None else None,
                      retain_graph=bool(retain_graph))
    return True


def _capi_autograd_backward_ex(heads, head_grads, variables, retain_graph,
                               create_graph, is_train):
    """≙ MXAutogradBackwardEx (c_api.h:1308): with `variables`, the
    autograd.grad path — returns new grad arrays; without, plain
    backward (grads land on marked variables)."""
    from . import autograd
    # per-element None entries mean default ones-like seeds; the backward
    # impl handles them directly
    hg = list(head_grads) if head_grads is not None else None
    if not variables:
        autograd.backward(list(heads), hg, retain_graph=bool(retain_graph),
                          create_graph=bool(create_graph),
                          train_mode=bool(is_train))
        return []
    return list(autograd.grad(list(heads), list(variables), head_grads=hg,
                              retain_graph=bool(retain_graph),
                              create_graph=bool(create_graph),
                              train_mode=bool(is_train)))


def _capi_ndarray_get_grad(nd):
    g = nd.grad
    if g is None:
        raise MXNetError("array has no gradient buffer "
                         "(not marked, or backward not run)")
    return g


# -- kvstore group (≙ MXKVStore*, reference c_api.h:2347) ------------------
def _capi_kv_create(type_str):
    from .kvstore import create
    return create(type_str)


def _capi_kv_init(kv, keys, vals, _priority):
    for k, v in zip(keys, vals):
        kv.init(int(k), v)
    return True


def _capi_kv_push(kv, keys, vals, priority):
    for k, v in zip(keys, vals):
        kv.push(int(k), v, priority=priority)
    return True


def _capi_kv_pull(kv, keys, outs, priority):
    for k, o in zip(keys, outs):
        kv.pull(int(k), out=o, priority=priority)
    return True


def _capi_kv_rank(kv):
    return int(kv.rank)


def _capi_kv_size(kv):
    return int(kv.num_workers)


def _capi_pred_create(jaxport_path, params_path, manifest_path):
    return ExportedModel(jaxport=jaxport_path, params=params_path,
                         manifest=manifest_path)


def _capi_pred_create_prefix(prefix):
    return ExportedModel(prefix)


def _capi_pred_num_inputs(model):
    return model.num_inputs


def _capi_pred_input_spec(model, i):
    shape, dtype = model.input_specs[i]
    return list(shape), DTYPE_TO_CODE[dtype]


def _capi_pred_forward(model, inputs):
    """NDArray inputs -> list of NDArray outputs (always a list)."""
    from . import np as mxnp
    out = model.run(*inputs)
    if not isinstance(out, tuple):
        out = (out,)
    return [mxnp.array(o) for o in out]


# ==========================================================================
# round-4 C ABI breadth (VERDICT-r3 Next #3): MXSymbol*, MXDataIter*/
# Dataset/Batchify, MXProfile*, MXEngine*, MXRecordIO*, and the NDArray /
# KVStore / misc tail. Same contract as above: plain functions over plain
# types; handles are the Python objects themselves.
# ==========================================================================

# -- NDArray tail ----------------------------------------------------------
def _capi_ndarray_create_none():
    from . import np as mxnp
    return mxnp.zeros((0,))


def _capi_ndarray_copy_from_bytes(nd, buf):
    a = _np.frombuffer(bytes(buf), dtype=str(nd.dtype)).reshape(nd.shape)
    nd[...] = a
    return True


def _capi_ndarray_at(nd, idx):
    return nd[int(idx)]


def _capi_ndarray_slice(nd, start, stop):
    return nd[int(start):int(stop)]


def _capi_ndarray_reshape(nd, shape, reverse=0):
    spec = [int(s) for s in shape]
    if int(reverse):
        # reference reverse inference: special values (0 = copy-dim,
        # -1 = infer) match from the RIGHT; flipping both views reduces
        # it to the forward rule
        cur = list(nd.shape)[::-1]
        spec = spec[::-1]
        out = []
        for i, d in enumerate(spec):
            out.append(cur[i] if d == 0 and i < len(cur) else d)
        return nd.reshape(tuple(out[::-1]))
    return nd.reshape(tuple(spec))


def _capi_ndarray_detach(nd):
    return nd.detach()


def _capi_ndarray_context(nd):
    dev = nd.device
    # reference dev_type codes: 1=cpu, 2=gpu; TPU reports as 6 (extension)
    code = {"cpu": 1, "gpu": 2, "tpu": 6}.get(dev.device_type, 1)
    return code, int(dev.device_id)


def _capi_ndarray_wait_to_read(nd):
    nd.wait_to_read()
    return True


def _capi_ndarray_storage_type(nd):
    """≙ NDArrayStorageType codes (include/mxnet/ndarray.h:62):
    default=0, row_sparse=1, csr=2."""
    from .ndarray.sparse import BaseSparseNDArray
    if isinstance(nd, BaseSparseNDArray):
        return {"row_sparse": 1, "csr": 2}[nd.stype]
    return 0


# ---- sparse storage group (≙ c_api.h:653-1077, sparse aux access) --------

def _capi_ndarray_create_sparse(storage_type, shape, dtype_code):
    from .ndarray import sparse as _sp
    stype = {1: "row_sparse", 2: "csr"}.get(int(storage_type))
    if stype is None:
        raise MXNetError(f"invalid sparse storage_type {storage_type}")
    return _sp.zeros(stype, tuple(shape), dtype=str(_np_dtype(dtype_code)))


def _sparse_aux_np(nd, i):
    """Aux array i of a sparse handle. CSR order ≙ csr::kIndPtr=0,
    csr::kIdx=1; RSP ≙ rowsparse::kIdx=0."""
    from .ndarray.sparse import CSRNDArray, RowSparseNDArray
    if isinstance(nd, CSRNDArray):
        if i == 0:
            return nd._indptr_np
        if i == 1:
            return nd._indices_np
    elif isinstance(nd, RowSparseNDArray) and i == 0:
        return nd._indices_np
    raise MXNetError(f"no aux array {i} on {type(nd).__name__}")


def _capi_ndarray_num_aux(nd):
    from .ndarray.sparse import CSRNDArray, RowSparseNDArray
    if isinstance(nd, CSRNDArray):
        return 2
    if isinstance(nd, RowSparseNDArray):
        return 1
    return 0


def _capi_ndarray_aux_type(nd, i):
    _sparse_aux_np(nd, i)      # validates the slot
    return DTYPE_TO_CODE["int64"]


class _HostNDArray:
    """Host-side array handle for the C boundary: sparse aux arrays are
    int64 by ABI contract, but the device stack (x64 disabled) would
    silently narrow them to int32 — so aux reads stay on the host."""

    def __init__(self, a):
        self._a = _np.ascontiguousarray(a)

    @property
    def shape(self):
        return self._a.shape

    @property
    def dtype(self):
        return self._a.dtype

    @property
    def ndim(self):
        return self._a.ndim

    @property
    def size(self):
        return self._a.size

    def asnumpy(self):
        return self._a

    def wait_to_read(self):
        return self


def _capi_ndarray_get_aux(nd, i):
    return _HostNDArray(_sparse_aux_np(nd, i))


def _capi_ndarray_get_data(nd):
    from .ndarray.sparse import BaseSparseNDArray
    if not isinstance(nd, BaseSparseNDArray):
        raise MXNetError("GetDataNDArray expects a sparse handle")
    return nd.data


def _capi_ndarray_sync_copy_from_ndarray(dst, src, i):
    """≙ MXNDArraySyncCopyFromNDArray: fill slot i of a sparse dst from a
    dense src (i == -1 -> data, else aux i). Aux writes may resize nnz;
    the paired data/indices slot is grown with it so the container stays
    structurally valid between the two copies."""
    import numpy as _onp

    from .ndarray.sparse import CSRNDArray, RowSparseNDArray
    a = src.asnumpy() if hasattr(src, "asnumpy") else _onp.asarray(src)
    if isinstance(dst, CSRNDArray):
        if i == -1:
            dst._data_np = a.astype(dst.dtype).ravel()
            if dst._indices_np.size != dst._data_np.size:
                dst._indices_np = _onp.resize(
                    dst._indices_np, dst._data_np.size)
        elif i == 0:
            dst._indptr_np = a.astype(_onp.int64).ravel()
        elif i == 1:
            dst._indices_np = a.astype(_onp.int64).ravel()
            if dst._data_np.size != dst._indices_np.size:
                dst._data_np = _onp.resize(dst._data_np,
                                           dst._indices_np.size)
        else:
            raise MXNetError(f"invalid slot {i} for csr")
        return True
    if isinstance(dst, RowSparseNDArray):
        if i == -1:
            dst._data_np = a.astype(dst.dtype).reshape(
                (-1,) + dst.shape[1:])
            if dst._indices_np.size != dst._data_np.shape[0]:
                dst._indices_np = _onp.resize(
                    dst._indices_np, dst._data_np.shape[0])
        elif i == 0:
            dst._indices_np = a.astype(_onp.int64).ravel()
            if dst._data_np.shape[0] != dst._indices_np.size:
                dst._data_np = _onp.resize(
                    dst._data_np,
                    (dst._indices_np.size,) + dst.shape[1:])
        else:
            raise MXNetError(f"invalid slot {i} for row_sparse")
        return True
    if i == -1:
        from . import np as mxnp
        dst[:] = mxnp.array(a)
        return True
    raise MXNetError("aux copy needs a sparse destination")


def _capi_kv_pull_row_sparse(kv, keys, outs, row_ids, priority):
    """≙ MXKVStorePullRowSparse (c_api.h:2569); keys may be int or str."""
    for k, out, rid in zip(keys, outs, row_ids):
        kv.row_sparse_pull(k, out=out, row_ids=rid, priority=priority)
    return True


def _capi_ndarray_check_format(nd, full_check):
    """≙ MXNDArraySyncCheckFormat: sparse handles validate their aux
    invariants; dense handles are trivially valid."""
    if hasattr(nd, "check_format"):
        nd.check_format(full_check=bool(full_check))
    return True


def _capi_ndarray_save(fname, arrays, names):
    from .ndarray import save
    if names:
        save(fname, dict(zip(names, arrays)))
    else:
        save(fname, list(arrays))
    return True


def _capi_ndarray_load(fname):
    from .ndarray import load
    from . import np as mxnp
    data = load(fname)
    if isinstance(data, dict):
        names = list(data.keys())
        arrays = [data[k] for k in names]
    else:
        names, arrays = [], list(data)
    return names, arrays


def _capi_ndarray_legacy_save(fname, arrays, names):
    """Write the reference binary .params container."""
    from .gluon.model_zoo.model_store import save_params_file
    save_params_file(fname, {n: a.asnumpy()
                             for n, a in zip(names, arrays)})
    return True


def _capi_random_seed(seed):
    from . import random as _random
    _random.seed(int(seed))
    return True


def _capi_list_all_op_names():
    out = set()
    from . import np as mxnp, npx
    for ns in (mxnp, npx):
        for nm in dir(ns):
            if not nm.startswith("_") and callable(getattr(ns, nm, None)):
                out.add(nm)
    return sorted(out)


def _capi_lib_features():
    from .runtime import Features
    return [(f.name, bool(f.enabled)) for f in Features().values()]


def _capi_device_count(kind):
    import jax
    try:
        if kind == "gpu":
            return 0       # TPU build: no CUDA devices, by design
        if kind == "tpu":
            return sum(1 for d in jax.devices() if d.platform == "tpu")
        return len(jax.devices())
    except RuntimeError:
        return 0


def _capi_memory_info(_dev_id):
    # (used, limit) per the C API contract. device_memory_info returns a
    # MemoryInfo namedtuple — the old code treated it as a dict (a latent
    # AttributeError) and a backend without bytes_limit now reads as an
    # explicit (0, 0) don't-know instead of fake zero headroom.
    from .device import device_memory_info
    info = device_memory_info()
    if not info.known:
        return 0, 0
    return int(info.total - info.free), int(info.total)


def _capi_is_numpy_shape():
    return 1   # np-shape semantics are the only mode in this framework


def _capi_is_numpy_default_dtype():
    return 1


# -- symbol group (≙ MXSymbol*, c_api.h:1448-2100) -------------------------
def _capi_symbol_create_variable(name):
    from . import symbol as sym
    return sym.Variable(name)


class _AtomicSymbol:
    """Uncomposed op template (CreateAtomicSymbol -> Compose two-step)."""

    def __init__(self, op, attrs):
        self.op = op
        self.attrs = attrs


def _capi_symbol_create_atomic(op_name, keys, vals):
    from . import symbol as sym
    if op_name not in sym.list_legacy_ops():
        raise MXNetError(f"unknown legacy op {op_name!r}")
    return _AtomicSymbol(op_name, dict(zip(keys, vals)))


def _capi_symbol_compose(holder, name, keys, args):
    """In-place compose (reference MXSymbolCompose mutates the handle):
    an _AtomicSymbol holder BECOMES the composed Symbol; a Symbol holder
    gets its free variables substituted."""
    from . import symbol as sym
    if isinstance(holder, _AtomicSymbol):
        maker = getattr(sym, holder.op)
        kwargs = dict(holder.attrs)
        if keys:
            composed = maker(name=name or None,
                             **dict(zip(keys, args)), **kwargs)
        else:
            composed = maker(*args, name=name or None, **kwargs)
        holder.__class__ = sym.Symbol
        holder.__dict__.clear()
        holder._outputs = list(composed._outputs)
        return True
    if keys:
        kwargs = dict(zip(keys, args))
    else:
        # positional composition: bind free variables in graph input order
        kwargs = dict(zip(holder.list_inputs(), args))
    composed = holder.compose(**kwargs)
    holder._outputs = list(composed._outputs)
    return True


def _capi_symbol_from_json(json_str):
    from . import symbol as sym
    return sym.load_json(json_str)


def _capi_symbol_to_json(s):
    return s.tojson()


def _capi_symbol_from_file(fname):
    from . import symbol as sym
    return sym.load(fname)


def _capi_symbol_save_file(s, fname):
    s.save(fname)
    return True


def _capi_symbol_copy(s):
    from . import symbol as sym
    return sym.load_json(s.tojson())


def _capi_symbol_print(s):
    return s.debug_str()


def _capi_symbol_get_name(s):
    return s.name or ""


def _capi_symbol_get_attr(s, key):
    return s.attr(key)   # None = absent; "" is a present empty value


def _capi_symbol_set_attr(s, key, value):
    s._set_attr(**{key: value})
    return True


def _capi_symbol_list_attr(s):
    flat = []
    for nm, attrs in s.attr_dict().items():
        for k, v in attrs.items():
            flat.extend([f"{nm}${k}", str(v)])
    return flat


def _capi_symbol_list_attr_shallow(s):
    flat = []
    for k, v in s.list_attr().items():
        flat.extend([k, str(v)])
    return flat


def _capi_symbol_list_arguments(s):
    return s.list_arguments()


def _capi_symbol_list_outputs(s):
    return s.list_outputs()


def _capi_symbol_list_aux(s):
    return s.list_auxiliary_states()


def _capi_symbol_get_internals(s):
    return s.get_internals()


def _capi_symbol_get_children(s):
    c = s.get_children()
    if c is None:
        raise MXNetError("symbol has no children")
    return c


def _capi_symbol_get_output(s, idx):
    return s[int(idx)]


def _capi_symbol_num_outputs(s):
    return s.num_outputs


def _capi_symbol_get_inputs(s):
    from . import symbol as sym
    return sym.Group([sym.Variable(n) for n in s.list_inputs()])


def _capi_symbol_create_group(symbols):
    from . import symbol as sym
    return sym.Group(list(symbols))


def _capi_symbol_infer_shape(s, names, shapes, partial):
    """Returns (arg_shapes, out_shapes, aux_shapes, complete) with -1 rows
    for still-unknown entries when partial."""
    kwargs = {n: tuple(sh) for n, sh in zip(names, shapes)}
    try:
        arg, out, aux = s.infer_shape(**kwargs)
    except MXNetError:
        if not partial:
            raise
        n_args = len(s.list_arguments())
        n_aux = len(s.list_auxiliary_states())
        return ([None] * n_args, [None] * s.num_outputs, [None] * n_aux, 0)
    complete = int(all(x is not None for x in list(arg) + list(aux)))
    return list(arg), list(out), list(aux), complete


def _capi_symbol_infer_type(s, names, type_codes=None):
    if type_codes:
        kwargs = {n: str(_np_dtype(c)) for n, c in zip(names, type_codes)}
    else:
        kwargs = {n: "float32" for n in names}
    arg, out, aux = s.infer_type(**kwargs)
    to_code = lambda ds: [DTYPE_TO_CODE[str(_np.dtype(d))] for d in ds]
    return to_code(arg), to_code(out), to_code(aux)


def _capi_symbol_list_atomic_creators():
    from . import symbol as sym
    return sym.list_legacy_ops()


def _capi_symbol_atomic_info(op_name):
    from . import symbol as sym
    if op_name not in sym.list_legacy_ops():
        raise MXNetError(f"unknown legacy op {op_name!r}")
    doc = f"legacy graph op {op_name} (executor: symbol/__init__.py)"
    return op_name, doc


# -- data iterator / dataset / batchify groups -----------------------------
_DATAITER_CREATORS = ("NDArrayIter", "ImageRecordIter", "CSVIter",
                      "LibSVMIter")


def _capi_list_data_iters():
    return list(_DATAITER_CREATORS)


def _capi_data_iter_info(name):
    if name not in _DATAITER_CREATORS:
        raise MXNetError(f"unknown iterator {name!r}")
    return name, f"{name} (io/__init__.py, ≙ reference src/io/iter_*.cc)"


def _capi_data_iter_create(name, keys, vals):
    from . import io as io_mod
    import ast
    if name not in _DATAITER_CREATORS:
        raise MXNetError(f"unknown iterator {name!r}")
    kwargs = {}
    for k, v in zip(keys, vals):
        try:
            kwargs[k] = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            kwargs[k] = v
    return _IterHandle(getattr(io_mod, name)(**kwargs))


class _IterHandle:
    """Current-batch cursor over a DataIter (the C iteration contract:
    Next() then GetData()/GetLabel()/GetPadNum())."""

    def __init__(self, it):
        self.it = it
        self.batch = None

    def next(self):
        try:
            self.batch = next(self.it)
            return 1
        except StopIteration:
            self.batch = None
            return 0

    def reset(self):
        self.it.reset()
        self.batch = None


def _capi_data_iter_next(h):
    return h.next()


def _capi_data_iter_before_first(h):
    h.reset()
    return True


def _capi_data_iter_data(h):
    if h.batch is None:
        raise MXNetError("no current batch: call MXDataIterNext first")
    return h.batch.data[0]


def _capi_data_iter_label(h):
    if h.batch is None:
        raise MXNetError("no current batch: call MXDataIterNext first")
    if not h.batch.label:
        raise MXNetError("iterator has no labels")
    return h.batch.label[0]


def _capi_data_iter_items(h):
    if h.batch is None:
        raise MXNetError("no current batch: call MXDataIterNext first")
    return list(h.batch.data) + list(h.batch.label or [])


def _capi_data_iter_pad_num(h):
    if h.batch is None:
        return 0
    return int(getattr(h.batch, "pad", 0) or 0)


def _capi_data_iter_index(h):
    if h.batch is None or getattr(h.batch, "index", None) is None:
        return []
    return [int(i) for i in h.batch.index]


def _capi_data_iter_len_hint(h):
    try:
        return len(h.it)
    except TypeError:
        return -1


_DATASET_CREATORS = ("ArrayDataset", "RecordFileDataset", "ImageRecordDataset")


def _capi_list_datasets():
    return list(_DATASET_CREATORS)


def _capi_dataset_info(name):
    if name not in _DATASET_CREATORS:
        raise MXNetError(f"unknown dataset {name!r}")
    return name, f"{name} (gluon/data, ≙ reference gluon.data datasets)"


def _capi_dataset_create(name, keys, vals):
    import ast
    from .gluon import data as gdata
    kwargs = {}
    for k, v in zip(keys, vals):
        try:
            kwargs[k] = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            kwargs[k] = v
    if name == "ArrayDataset":
        import numpy as np
        arrs = [np.asarray(v) for k, v in sorted(kwargs.items())]
        return gdata.ArrayDataset(*arrs)
    if name == "RecordFileDataset":
        return gdata.RecordFileDataset(**kwargs)
    if name == "ImageRecordDataset":
        return gdata.vision.ImageRecordDataset(**kwargs)
    raise MXNetError(f"unknown dataset {name!r}")


def _capi_dataset_len(ds):
    return len(ds)


def _capi_dataset_get_items(ds, idx):
    from . import np as mxnp
    from .ndarray import NDArray
    item = ds[int(idx)]
    if not isinstance(item, tuple):
        item = (item,)
    out = []
    for x in item:
        if isinstance(x, NDArray):
            out.append(x)
        elif isinstance(x, bytes):
            out.append(mxnp.array(_np.frombuffer(x, _np.uint8)))
        else:
            out.append(mxnp.array(_np.asarray(x)))
    return out


_BATCHIFY_FUNCS = ("Stack", "Pad", "Group")


def _capi_list_batchify():
    return list(_BATCHIFY_FUNCS)


def _capi_batchify_info(name):
    if name not in _BATCHIFY_FUNCS:
        raise MXNetError(f"unknown batchify {name!r}")
    return name, f"batchify.{name} (gluon/data/batchify.py)"


def _capi_batchify_create(name, keys, vals):
    from .gluon.data import batchify
    kw = dict(zip(keys, vals))
    if name == "Stack":
        return batchify.Stack()
    if name == "Pad":
        unknown = set(kw) - {"val", "pad_val", "axis", "dtype"}
        if unknown:
            raise MXNetError(f"Pad batchify: unknown params {sorted(unknown)}")
        return batchify.Pad(
            axis=int(kw.get("axis", 0)),
            val=float(kw.get("val", kw.get("pad_val", 0))),
            dtype=kw.get("dtype") or None)
    if name == "Group":
        # components default to Stack x N (N from 'size'); richer nesting
        # composes Python-side
        n = int(kw.get("size", 2))
        return batchify.Group(*[batchify.Stack() for _ in range(n)])
    raise MXNetError(f"unknown batchify {name!r}")


def _capi_batchify_invoke(fn, samples):
    from .gluon.data import batchify as B
    samples = list(samples)
    if isinstance(fn, B.Group):
        # the C wire is a FLAT handle array of num_samples*k entries
        # (sample-major, ≙ MXBatchifyFunctionInvoke's inputs layout);
        # regroup into per-sample component tuples
        k = len(fn._fns)
        if k and len(samples) % k:
            raise MXNetError(
                f"Group batchify got {len(samples)} arrays, not a "
                f"multiple of its {k} components")
        samples = [tuple(samples[i:i + k])
                   for i in range(0, len(samples), k)]
    out = fn(samples)
    if not isinstance(out, (list, tuple)):
        out = (out,)
    return list(out)


# -- profiler group (≙ MXProfile*, c_api.h:246-600) ------------------------
def _capi_profiler_set_config(keys, vals):
    from . import profiler
    profiler.set_config(**dict(zip(keys, vals)))
    return True


def _capi_profiler_set_state(state):
    from . import profiler
    if int(state):
        profiler.start()
    else:
        profiler.stop()
    return True


def _capi_profiler_pause(paused):
    from . import profiler
    if int(paused):
        profiler.pause()
    else:
        profiler.resume()
    return True


def _capi_profiler_dump(finished, filename):
    from . import profiler
    profiler.dump(finished=bool(finished),
                  filename=filename if filename else None)
    return True


def _capi_profiler_dumps(reset):
    from . import profiler
    return profiler.dumps(reset=bool(reset))


def _capi_profile_create_domain(name):
    from . import profiler
    return profiler.Domain(name)


def _capi_profile_create_task(domain, name):
    from . import profiler
    return profiler.Task(name, domain)


def _capi_profile_create_frame(domain, name):
    from . import profiler
    return profiler.Frame(name, domain)


def _capi_profile_create_event(name):
    from . import profiler
    return profiler.Event(name)


def _capi_profile_create_counter(domain, name, value):
    from . import profiler
    c = profiler.Counter(domain, name)
    if value is not None:
        c.set_value(int(value))
    return c


def _capi_profile_duration_start(obj):
    obj.start()
    return True


def _capi_profile_duration_stop(obj):
    obj.stop()
    return True


def _capi_profile_set_counter(c, value):
    c.set_value(int(value))
    return True


def _capi_profile_adjust_counter(c, delta):
    c.increment(int(delta)) if delta >= 0 else c.decrement(-int(delta))
    return True


def _capi_profile_set_marker(domain, name, scope):
    from . import profiler
    profiler.Marker(domain, name).mark(scope or "process")
    return True


# -- engine group (≙ MXEngine*, c_api.h:3028-3119) -------------------------
def _capi_engine_set_bulk_size(size):
    from . import engine
    # set_bulk_size returns the previously CONFIGURED value — not
    # effective_bulk_size(), which NaiveEngine forces to 0 and would make
    # the save/restore pattern permanently disable bulking
    return int(engine.set_bulk_size(int(size)))


import ctypes as _ctypes

# stable no-op completion callback for async engine pushes (kept as a
# module global so the function pointer outlives every call)
_ENGINE_NOOP_COMPLETE = _ctypes.CFUNCTYPE(None, _ctypes.c_void_p)(
    lambda _param: None)


def _capi_engine_push(fn_addr, param_addr, deleter_addr, is_async):
    """Execute a C callback through the engine (≙ MXEnginePushSync/Async).

    The TPU runtime has no user-visible dependency engine: callbacks run
    inline after the current bulking segment flushes — the NaiveEngine
    contract, which the reference also honors for sync pushes. The
    caller's param deleter runs after the function completes (reference
    EngineFuncParamDeleter contract)."""
    import ctypes
    from .ndarray import waitall
    waitall()
    try:
        if int(is_async):
            # async signature: void (*)(void* engine, void* param, void* cb).
            # cb must be a CALLABLE completion callback (the reference
            # contract requires the func to invoke it) — never NULL.
            CB = ctypes.CFUNCTYPE(None, ctypes.c_void_p, ctypes.c_void_p,
                                  ctypes.c_void_p)
            CB(fn_addr)(None, ctypes.c_void_p(param_addr or 0),
                        ctypes.cast(_ENGINE_NOOP_COMPLETE,
                                    ctypes.c_void_p))
        else:
            CB = ctypes.CFUNCTYPE(None, ctypes.c_void_p)
            CB(fn_addr)(ctypes.c_void_p(param_addr or 0))
    finally:
        if deleter_addr:
            DEL = ctypes.CFUNCTYPE(None, ctypes.c_void_p)
            DEL(deleter_addr)(ctypes.c_void_p(param_addr or 0))
    return True


# -- recordio group (≙ MXRecordIO*, c_api.h:2810-2900) ---------------------
def _capi_recordio_writer_create(path):
    from .recordio import MXRecordIO
    return MXRecordIO(path, "w")


def _capi_recordio_reader_create(path):
    from .recordio import MXRecordIO
    return MXRecordIO(path, "r")


def _capi_recordio_close(rec):
    rec.close()
    return True


def _capi_recordio_write(rec, buf):
    rec.write(bytes(buf))
    return True


def _capi_recordio_read(rec):
    # None = EOF; b"" is a legitimate zero-length record — the C side
    # distinguishes them (EOF -> *buf NULL, empty record -> non-NULL)
    return rec.read()


def _capi_recordio_tell(rec):
    return int(rec.tell())


def _capi_recordio_seek(rec, pos):
    rec.seek(int(pos))
    return True


# -- kvstore tail ----------------------------------------------------------
def _capi_kv_type(kv):
    return kv.type


def _capi_kv_barrier(kv):
    kv.barrier()
    return True


def _capi_kv_pushpull(kv, keys, invals, outvals, priority):
    # keys arrive as ints from the int-keyed entry points and as strs
    # from the Ex variants; the store keeps each key space verbatim
    for k, vin, vout in zip(keys, invals, outvals):
        kv.pushpull(k, vin, out=vout, priority=priority)
    return True


def _capi_kv_broadcast(kv, keys, invals, outvals, priority):
    for k, vin, vout in zip(keys, invals, outvals):
        kv.broadcast(k, vin, out=vout, priority=priority)
    return True


def _capi_kv_set_compression(kv, keys, vals):
    params = {}
    for k, v in zip(keys, vals):
        params[k] = float(v) if k == "threshold" else v
    kv.set_gradient_compression(params)
    return True


def _capi_kv_init_str(kv, keys, vals):
    for k, v in zip(keys, vals):
        kv.init(k, v)
    return True


def _capi_kv_push_str(kv, keys, vals, priority):
    for k, v in zip(keys, vals):
        kv.push(k, v, priority=priority)
    return True


def _capi_kv_pull_str(kv, keys, outs, priority):
    for k, o in zip(keys, outs):
        kv.pull(k, out=o, priority=priority)
    return True


def _capi_kv_set_updater(kv, fn_addr, handle_addr):
    """C-callback updater (≙ MXKVStoreSetUpdater): the callback receives
    (key, recv NDArrayHandle, local NDArrayHandle, user handle). Handles
    are borrowed PyObject* valid for the duration of the call."""
    import ctypes
    CB = ctypes.CFUNCTYPE(None, ctypes.c_int, ctypes.c_void_p,
                          ctypes.c_void_p, ctypes.c_void_p)
    cb = CB(fn_addr)

    def updater(key, recv, local):
        cb(int(key), id(recv), id(local),
           ctypes.c_void_p(handle_addr or 0))

    kv.set_updater(updater)
    return True


def _capi_kv_set_updater_ex(kv, int_addr, str_addr, handle_addr):
    """≙ MXKVStoreSetUpdaterEx: int keys dispatch to the int callback,
    string keys to the string callback (const char* first arg)."""
    import ctypes
    ICB = ctypes.CFUNCTYPE(None, ctypes.c_int, ctypes.c_void_p,
                           ctypes.c_void_p, ctypes.c_void_p)
    SCB = ctypes.CFUNCTYPE(None, ctypes.c_char_p, ctypes.c_void_p,
                           ctypes.c_void_p, ctypes.c_void_p)
    icb = ICB(int_addr) if int_addr else None
    scb = SCB(str_addr) if str_addr else None

    def updater(key, recv, local):
        h = ctypes.c_void_p(handle_addr or 0)
        if isinstance(key, str):
            if scb is None:
                raise MXNetError(
                    "string-keyed update but no string updater registered")
            scb(key.encode(), id(recv), id(local), h)
        else:
            if icb is None:
                raise MXNetError(
                    "int-keyed update but no int updater registered")
            icb(int(key), id(recv), id(local), h)

    kv.set_updater(updater)
    return True


def _capi_kv_is_worker(_kv):
    return 1   # SPMD runtime: every process is a worker (no server nodes)


def _capi_kv_is_server(_kv):
    return 0


def _capi_kv_is_scheduler(_kv):
    return 0


def _capi_kv_num_dead(_kv, _node_id):
    return 0   # PJRT surfaces failures as errors, not dead-node counts


def _capi_load_lib(path, verbose=0):
    """≙ MXLoadLib: load a Python or native (.so) extension."""
    from . import library
    library.load(path, verbose=bool(verbose))
    return True
