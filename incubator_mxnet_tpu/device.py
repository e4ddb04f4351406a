"""Device / Context abstraction over PJRT devices.

Reference equivalent: Context{kCPU,kGPU,kCPUPinned,kCPUShared} in
include/mxnet/base.h:92-118 and python/mxnet/context.py (`with mx.gpu(0):` scope,
num_gpus, gpu_memory_info). TPU-native design: a Device names a PJRT device
(`tpu(i)`, `cpu(i)`); there is no pinned/shared split because PJRT manages host
staging. `gpu(i)` is accepted as an alias for the accelerator so reference scripts
run unmodified (BASELINE.json north star: "mx.tpu() contexts").
"""
from __future__ import annotations

import threading
from typing import NamedTuple

from .base import MXNetError, get_env

__all__ = [
    "Device", "Context", "cpu", "tpu", "gpu", "current_device", "current_context",
    "num_gpus", "num_tpus", "MemoryInfo", "device_memory_info",
    "gpu_memory_info",
]

_state = threading.local()


class Device:
    """A named PJRT device with `with` scoping (≙ mxnet Context)."""

    _KINDS = ("cpu", "tpu", "gpu")

    def __init__(self, device_type="tpu", device_id=0):
        if device_type not in self._KINDS:
            raise MXNetError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- PJRT resolution ----------------------------------------------------
    @property
    def jax_device(self):
        """The underlying PJRT device; accelerator kinds resolve to the default
        jax backend (tpu), cpu resolves to the host backend."""
        import jax
        if self.device_type == "cpu":
            devs = (jax.local_devices(backend="cpu") if _has_platform("cpu")
                    else jax.local_devices())
        else:
            devs = _accelerator_devices()
            if not devs:  # CPU-only process (tests): transparent fallback
                devs = jax.local_devices()
        return devs[min(self.device_id, len(devs) - 1)]

    # -- scoping ------------------------------------------------------------
    def __enter__(self):
        stack = getattr(_state, "stack", None)
        if stack is None:
            stack = _state.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        _state.stack.pop()

    # -- identity -----------------------------------------------------------
    def __eq__(self, other):
        return (isinstance(other, Device)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"


# The reference exposes the same object as both Context and Device in 2.0.
Context = Device


def _has_platform(name):
    import jax
    try:
        return bool(jax.devices(name))
    except RuntimeError:
        return False


def _accelerator_devices():
    """This process's non-host PJRT devices (TPU chips). Local only: in
    multi-process runs, placing data on another process's device is
    invalid."""
    import jax
    return [d for d in jax.local_devices() if d.platform != "cpu"]


def tpu_platform_available():
    """Any local device on the TPU platform? The Pallas kernel tier keys
    on this: GPUs are accelerators too, but must NOT take the TPU-shaped
    kernel path — off-TPU the fused ops use their jnp composition
    fallback."""
    import jax
    return any(d.platform == "tpu" for d in jax.local_devices())


def cpu(device_id=0):
    return Device("cpu", device_id)


def tpu(device_id=0):
    return Device("tpu", device_id)


def gpu(device_id=0):
    """Alias for the accelerator device so reference scripts run unmodified."""
    return Device("tpu", device_id)


def _default_device():
    override = get_env("MXNET_DEFAULT_DEVICE")
    if override:
        kind, _, idx = override.partition("(")
        return Device(kind, int(idx.rstrip(")") or 0))
    return tpu(0) if _accelerator_devices() else cpu(0)


def current_device():
    stack = getattr(_state, "stack", None)
    if stack:
        return stack[-1]
    return _default_device()


current_context = current_device


def num_tpus():
    return len(_accelerator_devices())


def num_gpus():
    """Reference-API alias (mx.context.num_gpus): counts accelerator chips."""
    return num_tpus()


class MemoryInfo(NamedTuple):
    """`device_memory_info` result: the reference's `(free, total)` plus
    the `known` flag. CPU backends and some PJRT builds expose no
    `bytes_limit`, and before this flag `(0, 0)` was indistinguishable
    from "zero headroom" — a capacity-planning caller (deploy sizing,
    the OOM dump) MUST branch on `known` before trusting the numbers.

    DELIBERATE API break (ISSUE 15 satellite): `info[0]`/`info[1]` and
    attribute access keep working, but the tuple now iterates THREE
    elements, so `free, total = device_memory_info()` raises — exactly
    the call sites that were silently trusting no-data zeros and must be
    rewritten to consult `known` (the in-repo one, deploy's C-API shim,
    was)."""

    free: int
    total: int
    known: bool


def device_memory_info(device_id=0):
    """Free/total accelerator memory with a typed don't-know sentinel
    (≙ mx.context.gpu_memory_info): `MemoryInfo(free, total, known)`.
    `known=False` (free=total=0) means the backend reports no
    `bytes_limit` — NO DATA, not an exhausted device."""
    dev = tpu(device_id).jax_device
    try:
        stats = dev.memory_stats() or {}
    except Exception:
        stats = {}
    total = stats.get("bytes_limit")
    if not total:
        return MemoryInfo(0, 0, False)
    used = int(stats.get("bytes_in_use", 0))
    return MemoryInfo(int(total) - used, int(total), True)


gpu_memory_info = device_memory_info
