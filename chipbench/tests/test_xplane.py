"""The reducer on the small trace kept in `chipbench/testdata/` (recorded
on a TPU v5 lite by `record_trace.py`: three executions each of a 1024^3
bf16 matmul program and of a Pallas kernel over 512x512 float32, with host
sleeps between them). Values were read by hand from the trace with
`jax.profiler.ProfileData` and are restated here by a plain loop."""
import os

import pytest

from chipbench import harness, xplane

SMALL = os.path.join(harness.HERE, "testdata", "small.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return xplane.Trace(SMALL)


def by_hand(path):
    """Busy union and window of the first device, with nothing shared."""
    from jax.profiler import ProfileData
    plane = next(p for p in ProfileData.from_file(path).planes
                 if p.name.startswith("/device:TPU:"))
    ops = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    spans = sorted((e.start_ns, e.start_ns + e.duration_ns)
                   for e in ops.events)
    busy, (lo, hi) = 0.0, spans[0]
    for a, b in spans[1:]:
        if a > hi:
            busy += hi - lo
            lo, hi = a, b
        else:
            hi = max(hi, b)
    busy += hi - lo
    return busy * 1e-9, (max(b for _, b in spans) - spans[0][0]) * 1e-9


def test_one_device_plane_and_its_lines(trace):
    assert list(trace.devices) == ["/device:TPU:0"]
    lines = trace.devices["/device:TPU:0"]
    assert len(lines["XLA Modules"].names) == 6
    assert len(lines["XLA Ops"].names) == 12


def test_busy_union_and_idle_share_against_a_plain_loop(trace):
    busy, window = trace.busy_and_window_s()
    want_busy, want_window = by_hand(SMALL)
    assert busy == pytest.approx(want_busy, rel=1e-9)
    assert window == pytest.approx(want_window, rel=1e-9)
    # read by hand: 67.8 us busy in a 14.96 ms window (the host slept)
    assert busy == pytest.approx(67.843e-6, rel=1e-3)
    assert window == pytest.approx(14.9616e-3, rel=1e-3)
    assert 100 * (1 - busy / window) == pytest.approx(99.55, abs=0.01)


def test_a_modules_and_a_kernels_time(trace):
    seconds, count = trace.module_time_s(r"^jit_small_matmul\(")
    assert count == 3 and seconds == pytest.approx(57.0e-6, rel=2e-2)
    seconds, count = trace.module_time_s(r"^jit_small_kernel\(")
    assert count == 3 and seconds == pytest.approx(10.9e-6, rel=2e-2)
    seconds, count = trace.op_time_s('custom_call_target="tpu_custom_call"')
    assert count == 3 and seconds == pytest.approx(10.85e-6, rel=1e-2)
    assert trace.module_time_s("no_such_program") == (0.0, 0)


def test_breakdown_names_are_short_and_gaps_are_the_sleeps(trace):
    ops = trace.top_ops(3)
    assert ops[0][0] == "%fusion fusion"
    assert ops[1][0] == "%small_kernel.1 custom-call tpu_custom_call"
    # five sleeps between six executions, all over 1 ms; the sixth gap is
    # 2 ns inside a program
    gaps = trace.idle_gaps(6)
    assert [sec >= 1e-3 for _, sec in gaps] == [True] * 5 + [False]
    assert gaps[0][0].startswith("after %")


def test_readers_return_nothing_when_there_is_nothing_to_read(trace):
    from chipbench.readers import xplane_kernel_roofline, xplane_module_ms
    ctx = {"trace": trace, "counters": {}, "peaks":
           {"flops_per_s": 197e12, "bytes_per_s": 819e9}}
    assert xplane_module_ms.read({"pattern": "nothing"}, ctx) is None
    assert xplane_kernel_roofline.read(
        {"pattern": "tpu_custom_call", "work": "absent"}, ctx) is None
    # 512x512 float32 read and written once: 2 MiB in 10.85 us of kernels
    ctx["counters"] = {"k_flops": 0, "k_bytes": 3 * 2 * 512 * 512 * 4}
    share = xplane_kernel_roofline.read(
        {"pattern": "tpu_custom_call", "work": "k"}, ctx)
    assert share == pytest.approx(
        100 * (3 * 2 * 512 * 512 * 4 / 819e9) / 10.85e-6, rel=1e-2)
