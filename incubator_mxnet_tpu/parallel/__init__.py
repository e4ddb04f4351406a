"""mx.parallel — SPMD meshes, sharding rules, and collectives.

This is the TPU-native replacement for the reference's entire distributed
stack (SURVEY §2.3): Comm{CPU,Device,DeviceTree} reductions, ps-lite
parameter-server, NCCL (`src/kvstore/kvstore_nccl.h`), gradient compression
and the dmlc launcher all collapse into ONE abstraction — a named device
mesh with XLA collectives over ICI/DCN:

  - `Mesh(axes)`         ≙ topology discovery (gpu_topology.h) — but the XLA
                           partitioner owns placement; we just name axes
                           (dp/tp/pp/sp/ep) and let GSPMD insert collectives.
  - `allreduce/psum...`  ≙ ncclAllReduce / CommDevice::Reduce — inside
                           shard_map/pjit these are `lax.psum`-class ops that
                           ride ICI.
  - sharding rules       ≙ nothing in the reference (TP/PP/SP are ABSENT
                           there, SURVEY §2.3) — green-field capability.

Multi-host: `initialize()` wraps jax.distributed.initialize — the DCN
equivalent of the dmlc tracker's DMLC_PS_ROOT_URI bootstrap.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager

import numpy as _np

from ..base import MXNetError, get_env

__all__ = [
    "Mesh", "current_mesh", "mesh_scope", "make_mesh", "dp_mesh",
    "initialize",
    "allreduce", "allgather", "reduce_scatter", "broadcast", "ppermute",
    "axis_is_bound", "shard", "replicate", "shard_map", "num_devices",
    "local_rank", "rank", "world_size", "DataParallel", "split_and_load",
    "data_sharding",
    "ring_attention", "pipeline_apply", "moe_dispatch",
]


def __getattr__(name):
    # lazy so `import parallel` stays light; the submodule is named `ring`
    # (not `ring_attention`) so this binding can never be shadowed by a
    # submodule import
    if name == "ring_attention":
        from .ring import ring_attention
        globals()[name] = ring_attention
        return ring_attention
    if name == "pipeline_apply":
        from .pipeline import pipeline_apply
        globals()[name] = pipeline_apply
        return pipeline_apply
    if name == "moe_dispatch":
        from .moe import moe_dispatch
        globals()[name] = moe_dispatch
        return moe_dispatch
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

_tls = threading.local()


# ---------------------------------------------------------------------------
# mesh
# ---------------------------------------------------------------------------
class Mesh:
    """A named device mesh (thin wrapper over jax.sharding.Mesh).

    Canonical axis names used across the framework:
      'dp' data parallel, 'tp' tensor parallel, 'pp' pipeline parallel,
      'sp' sequence/context parallel, 'ep' expert parallel.
    """

    def __init__(self, axis_shapes, devices=None):
        import jax
        if devices is None:
            devices = jax.devices()
        names = tuple(axis_shapes.keys())
        sizes = tuple(axis_shapes.values())
        n = int(_np.prod(sizes))
        if n > len(devices):
            raise MXNetError(
                f"mesh {dict(axis_shapes)} needs {n} devices, have "
                f"{len(devices)}")
        dev_array = _np.array(devices[:n]).reshape(sizes)
        self.jax_mesh = jax.sharding.Mesh(dev_array, names)
        self.axis_names = names
        self.axis_sizes = dict(axis_shapes)

    def __enter__(self):
        self.jax_mesh.__enter__()
        stack = getattr(_tls, "meshes", None)
        if stack is None:
            stack = _tls.meshes = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        _tls.meshes.pop()
        return self.jax_mesh.__exit__(*exc)

    def size(self, axis=None):
        if axis is None:
            return int(_np.prod(list(self.axis_sizes.values())))
        return self.axis_sizes[axis]

    def sharding(self, *pspec):
        """NamedSharding for a PartitionSpec over this mesh."""
        import jax
        return jax.sharding.NamedSharding(
            self.jax_mesh, jax.sharding.PartitionSpec(*pspec))

    def __repr__(self):
        return f"Mesh({self.axis_sizes})"


def make_mesh(dp=None, tp=1, pp=1, sp=1, ep=1, devices=None):
    """Build a mesh over the visible devices; dp absorbs the remainder."""
    import jax
    devices = devices or jax.devices()
    n = len(devices)
    denom = tp * pp * sp * ep
    if dp is None:
        if n % denom:
            raise MXNetError(f"{n} devices not divisible by tp*pp*sp*ep={denom}")
        dp = n // denom
    axes = {}
    for name, size in (("dp", dp), ("pp", pp), ("sp", sp), ("tp", tp),
                       ("ep", ep)):
        if size != 1 or name == "dp":
            axes[name] = size
    return Mesh(axes, devices)


def dp_mesh(dp=None, axis="dp", devices=None):
    """A bare 1-axis data-parallel `jax.sharding.Mesh` over the first `dp`
    visible devices (all of them when None) — the mesh shape the elastic
    ZeRO trainer (`mx.fault.elastic`) shards its (dp, L) state views
    over. Returns a RAW jax mesh (not `parallel.Mesh`): the callers are
    sharding/collective plumbing, not `with mesh:` scopes."""
    import jax
    devices = list(devices if devices is not None else jax.devices())
    dp = len(devices) if dp is None else int(dp)
    if dp < 1 or dp > len(devices):
        raise MXNetError(f"dp={dp} outside [1, {len(devices)}] visible "
                         "devices")
    return jax.sharding.Mesh(_np.array(devices[:dp]), (axis,))


def current_mesh():
    stack = getattr(_tls, "meshes", None)
    return stack[-1] if stack else None


@contextmanager
def mesh_scope(mesh):
    with mesh:
        yield mesh


def num_devices():
    import jax
    return jax.device_count()


def initialize(coordinator_address=None, num_processes=None, process_id=None):
    """Multi-host bootstrap over DCN (≙ dmlc tracker DMLC_PS_ROOT_URI env
    bootstrap, tools/launch.py). Reads MXNET_COORDINATOR/DMLC_* env when args
    are omitted."""
    import jax
    coordinator_address = coordinator_address or get_env("MXNET_COORDINATOR")
    if coordinator_address is None:
        return  # single host
    num_processes = num_processes or get_env("MXNET_NUM_PROCESSES", typ=int)
    process_id = process_id if process_id is not None \
        else get_env("MXNET_PROCESS_ID", typ=int)
    import os as _os
    if _os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        # multi-process CPU needs the gloo collectives backend to form one
        # global device view (the DCN-emulation test path)
        try:
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        except Exception:
            pass
    jax.distributed.initialize(coordinator_address, num_processes, process_id)


def rank():
    import jax
    return jax.process_index()


def local_rank():
    return 0


def world_size():
    import jax
    return jax.process_count()


# ---------------------------------------------------------------------------
# axis-name tracking (for layers like SyncBatchNorm that want to know whether
# they're running inside a shard_map with a given named axis)
# ---------------------------------------------------------------------------
def _bound_axes():
    s = getattr(_tls, "axes", None)
    if s is None:
        # lazy thread-local init; axis bindings are static per trace
        s = _tls.axes = []  # mxlint: disable=trace-closure-mutation
    return s


def axis_is_bound(name):
    return name in _bound_axes()


@contextmanager
def _axis_scope(names):
    s = _bound_axes()
    s.extend(names)
    try:
        yield
    finally:
        for n in names:
            s.remove(n)


# ---------------------------------------------------------------------------
# collectives — usable inside shard_map'd functions on NDArrays or raw arrays
# (≙ KVStore comm kernels / NCCL calls; lower to XLA AllReduce etc. on ICI)
# ---------------------------------------------------------------------------
def _raw(x):
    from ..ndarray import NDArray
    return x._arr if isinstance(x, NDArray) else x


def _wrap_like(x, out):
    from ..ndarray import NDArray, _wrap
    return _wrap(out) if isinstance(x, NDArray) else out


def allreduce(x, axis_name="dp", op="sum"):
    """≙ ncclAllReduce / CommDevice::Reduce+Broadcast."""
    import jax
    from ..ops.registry import invoke
    from ..ndarray import NDArray, _as_nd
    fns = {"sum": jax.lax.psum, "mean": jax.lax.pmean, "max": jax.lax.pmax,
           "min": jax.lax.pmin}
    if op not in fns:
        raise MXNetError(f"unsupported allreduce op {op!r}")
    if isinstance(x, NDArray):
        return invoke(lambda v: fns[op](v, axis_name), (x,), name="allreduce")
    return fns[op](x, axis_name)


def allgather(x, axis_name="dp", axis=0, tiled=True):
    import jax
    from ..ndarray import NDArray
    from ..ops.registry import invoke
    if isinstance(x, NDArray):
        return invoke(lambda v: jax.lax.all_gather(v, axis_name, axis=axis,
                                                   tiled=tiled),
                      (x,), name="allgather")
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name="dp", axis=0):
    import jax
    from ..ndarray import NDArray
    from ..ops.registry import invoke
    if isinstance(x, NDArray):
        return invoke(lambda v: jax.lax.psum_scatter(v, axis_name,
                                                     scatter_dimension=axis,
                                                     tiled=True),
                      (x,), name="reduce_scatter")
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=axis,
                                tiled=True)


def ppermute(x, perm, axis_name="dp"):
    import jax
    from ..ndarray import NDArray
    from ..ops.registry import invoke
    if isinstance(x, NDArray):
        return invoke(lambda v: jax.lax.ppermute(v, axis_name, perm), (x,),
                      name="ppermute")
    return jax.lax.ppermute(x, axis_name, perm)


def broadcast(x, axis_name="dp", src=0):
    """Broadcast from src rank along axis (≙ ncclBcast / Comm broadcast)."""
    import jax
    import jax.numpy as jnp

    def _b(v):
        idx = jax.lax.axis_index(axis_name)
        return jax.lax.psum(jnp.where(idx == src, v, jnp.zeros_like(v)),
                            axis_name)
    from ..ndarray import NDArray
    from ..ops.registry import invoke
    if isinstance(x, NDArray):
        return invoke(_b, (x,), name="broadcast")
    return _b(x)


# ---------------------------------------------------------------------------
# sharding helpers
# ---------------------------------------------------------------------------
def shard(x, *pspec, mesh=None):
    """Place an array on the mesh with a PartitionSpec (device_put)."""
    import jax
    from ..ndarray import NDArray, _wrap
    mesh = mesh or current_mesh()
    if mesh is None:
        raise MXNetError("no active mesh; enter `with mesh:` first")
    s = mesh.sharding(*pspec)
    raw = _raw(x)
    return _wrap_like(x, jax.device_put(raw, s))


def replicate(x, mesh=None):
    return shard(x, mesh=mesh)


def shard_map(fn, mesh, in_specs, out_specs, check_vma=False):
    """Wrap jax.shard_map, tracking bound axis names so framework layers
    (SyncBatchNorm) can detect their collective axes."""
    import jax

    names = tuple(mesh.axis_names if isinstance(mesh, Mesh)
                  else mesh.axis_names)
    jmesh = mesh.jax_mesh if isinstance(mesh, Mesh) else mesh

    inner = jax.shard_map(fn, mesh=jmesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=check_vma)

    def wrapped(*args):
        with _axis_scope(list(names)):
            return inner(*args)
    return wrapped


def data_sharding(ndim, batch_axis=0, mesh=None):
    """NamedSharding for an input batch of rank `ndim`: the batch axis
    split over 'dp', everything else replicated — the placement
    `io.DeviceFeed` uses for data-parallel device prefetch. Returns None
    when no mesh is active (or it has no 'dp' axis): callers then fall
    back to plain default-device placement."""
    mesh = mesh or current_mesh()
    if mesh is None or "dp" not in mesh.axis_sizes:
        return None
    spec = [None] * ndim
    if ndim > batch_axis:
        spec[batch_axis] = "dp"
    return mesh.sharding(*spec)


def split_and_load(data, ctx_list=None, batch_axis=0, even_split=True,
                   mesh=None):
    """≙ gluon.utils.split_and_load. On TPU: ONE sharded array over the dp
    axis instead of a python list of per-device copies; returns [global_array]
    (list for API compatibility)."""
    from ..ndarray import _as_nd
    data = _as_nd(data)
    mesh = mesh or current_mesh()
    if mesh is None:
        return [data]
    spec = [None] * data.ndim
    spec[batch_axis] = "dp"
    return [shard(data, *spec, mesh=mesh)]


class DataParallel:
    """Convenience SPMD data-parallel trainer wrapper: compiles
    fn(params, batch) under pjit with batch sharded on 'dp' and params
    replicated. The MXNet equivalent idiom is the
    `for ctx in ctx_list: autograd.record()...` loop + kvstore allreduce;
    here GSPMD inserts the gradient psum automatically."""

    def __init__(self, mesh=None):
        import jax
        self.mesh = mesh or make_mesh()

    def compile(self, step_fn, donate_argnums=()):
        import jax
        mesh = self.mesh

        def wrapped(*args, **kwargs):
            with mesh:
                return step_fn(*args, **kwargs)
        return jax.jit(wrapped, donate_argnums=donate_argnums)
