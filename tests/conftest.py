"""Test fixtures: force an 8-device CPU mesh and seed control.

Reference pattern: conftest.py:85-130 (MXNET_TEST_SEED reproduction) and the
`--xla_force_host_platform_device_count` emulation recipe (SURVEY §4: the
reference's `--launcher local` multi-process tests map onto a virtual device
mesh in-process).
"""
import os
import zlib

# Must happen before jax initializes: the tests run on the CPU backend with
# 8 virtual devices.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as _np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: nightly-scale tests (crashtest SIGKILL parity, convergence "
        "runs) excluded from the tier-1 '-m \"not slow\"' pass")


@pytest.fixture(autouse=True)
def _seed_all(request):
    """Per-test deterministic seeding, reproducible via MXNET_TEST_SEED
    (≙ reference conftest.py seed logging)."""
    import incubator_mxnet_tpu as mx
    seed = mx.get_env("MXNET_TEST_SEED", typ=int)
    if seed is None:
        # crc32, not hash(): str hashes are salted per process, and a
        # test's seed must be the same in every run and every worker
        seed = zlib.crc32(request.node.nodeid.encode()) % (2 ** 31)
    _np.random.seed(seed % (2 ** 31))
    mx.seed(seed)
    yield


@pytest.fixture(autouse=True)
def _fresh_trace_env_memo():
    """The tracing layer TTL-caches MXNET_TELEMETRY/MXNET_TRACE_SAMPLE
    (50ms, hot-path cost): expire around every test so a monkeypatched
    value from one test can never leak into the next."""
    from incubator_mxnet_tpu.telemetry import trace
    trace._expire_env_memo()
    yield
    trace._expire_env_memo()
