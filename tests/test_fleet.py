"""serve.fleet: multi-replica serving with failover and drain-and-swap.

Contracts under test (ISSUE 16 acceptance):
  * per-replica metrics ports derive from the inherited MXNET_METRICS_PORT
    (base + replica index) — the port-collision regression — and the
    router learns the bound port from each replica's hello
  * replica SIGKILL mid-traffic: in-flight requests re-enqueue onto the
    survivors under the retry budget (zero client-visible failures) and
    the supervisor respawns the replica warm
  * all four fault points (`fleet.dispatch`, `fleet.heartbeat`,
    `fleet.respawn`, `fleet.swap`) injectable via MXNET_FAULT_SPEC with
    deterministic outcomes: transparent retry, hung-replica kill+respawn,
    bounded restarts with original-error resurfacing, typed swap abort
  * rolling drain-and-swap drops ZERO requests and flips the served
    version; `ReplicaDraining` is routed around, never client-visible
  * one trace per request even when the request survives a retry hop
  * real fleet: outputs byte-exact vs reference_generate, hellos report
    the persistent-compilation warmup, and `assert_no_retraces` holds
    fleet-wide from replica-reported pong counters

Stub replicas ({"stub": true} specs) keep the router/supervisor tests
jax-free and fast; the real-engine fixture proves the end-to-end path.
"""
import json
import os
import signal
import socket
import threading
import time
import urllib.request

import subprocess
import sys

import numpy as np
import pytest

from incubator_mxnet_tpu import fault, profiler, serve, telemetry


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = dict(vocab=64, embed=32, layers=2, heads=4, head_dim=8, max_len=48)

STUB_SPEC = {"version": "v1", "stub": True, "stub_delay_ms": 5.0}


def _stub_tokens(prompt, max_new, version):
    """The stub replica's deterministic token function (mirrors
    serve.replica._StubEngine) — lets tests prove WHICH version served."""
    vtag = sum(version.encode()) % 997
    base = int(np.sum(prompt)) % 997
    return [(base * 31 + i + vtag) % 97 for i in range(max_new)]


def _free_port_base(n=2, tries=50):
    """A base port such that base..base+n-1 are all currently bindable."""
    for _ in range(tries):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        base = s.getsockname()[1]
        s.close()
        if base + n >= 65500:
            continue
        ok = True
        for i in range(1, n):
            t = socket.socket()
            try:
                t.bind(("127.0.0.1", base + i))
            except OSError:
                ok = False
            finally:
                t.close()
            if not ok:
                break
        if ok:
            return base
    pytest.skip("could not find consecutive free ports")


def _wait(pred, timeout, msg):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.05)
    pytest.fail(f"timed out after {timeout}s waiting for {msg}")


def _serving(fleet):
    return sum(1 for r in fleet.stats()["replicas"]
               if r["state"] == "serving")


@pytest.fixture(scope="module")
def stub_fleet(tmp_path_factory):
    """2 stub replicas with a fast heartbeat; MXNET_METRICS_PORT is set
    only across start() so the children inherit it (the satellite-1
    port-derivation regression) without leaking into other tests."""
    base = _free_port_base(2)
    wd = tmp_path_factory.mktemp("stub_fleet")
    old = os.environ.get("MXNET_METRICS_PORT")
    os.environ["MXNET_METRICS_PORT"] = str(base)
    try:
        fleet = serve.Fleet(STUB_SPEC, replicas=2, heartbeat_ms=100,
                            retry_budget=2, drain_timeout_ms=10000,
                            heartbeat_misses=2, max_restarts=2,
                            workdir=str(wd)).start()
    finally:
        if old is None:
            os.environ.pop("MXNET_METRICS_PORT", None)
        else:
            os.environ["MXNET_METRICS_PORT"] = old
    yield fleet, base
    fleet.close()


# ---------------------------------------------------------------------------
# satellite 1: metrics-port derivation regression
# ---------------------------------------------------------------------------
def test_metrics_ports_derive_from_env_base_plus_index(stub_fleet):
    """Two replicas inheriting one MXNET_METRICS_PORT must NOT collide:
    each derives base + replica index, and the router learns the bound
    port from the hello (not by re-deriving)."""
    fleet, base = stub_fleet
    reps = fleet.stats()["replicas"]
    ports = {r["replica"]: r["metrics_port"] for r in reps}
    assert ports == {0: base, 1: base + 1}, ports
    for i, port in ports.items():
        txt = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        assert "mx_" in txt, f"replica {i} port {port} served no metrics"


def test_stub_fleet_serves_and_reports_live_replicas(stub_fleet):
    fleet, _ = stub_fleet
    futs = [fleet.submit([1, 2, 3], max_new_tokens=4) for _ in range(8)]
    for f in futs:
        assert f.result(timeout=30).tolist() == \
            _stub_tokens([1, 2, 3], 4, "v1")
    st = fleet.stats()
    assert st["replicas_live"] == 2
    assert st["version"] == "v1"


def test_prefix_affinity_pins_shared_prefix_to_one_replica(stub_fleet):
    """Requests sharing a block-quantized prefix route to the replica
    that served the prefix first (its prefix cache is warm there):
    after the first dispatch records the mapping, every follow-up
    counts `fleet.affinity_hits`. Sub-block prompts carry no affinity
    key and never touch the counter."""
    fleet, _ = stub_fleet
    prompt = list(range(1, 21))               # 20 tokens = 1 block of 16
    before = serve.fleet_stats()["affinity_hits"]
    for _ in range(4):                        # sequential: no load races
        fleet.submit(prompt, max_new_tokens=2).result(timeout=30)
    assert serve.fleet_stats()["affinity_hits"] - before == 3
    # shorter than one block (19//16 == 1 needs 17+ tokens): no key
    before = serve.fleet_stats()["affinity_hits"]
    fleet.submit([1, 2, 3], max_new_tokens=2).result(timeout=30)
    fleet.submit([1, 2, 3], max_new_tokens=2).result(timeout=30)
    assert serve.fleet_stats()["affinity_hits"] == before


# ---------------------------------------------------------------------------
# fault points: fleet.dispatch / fleet.heartbeat (fleet.respawn and
# fleet.swap below; the respawn-exhaustion test runs LAST — it
# permanently fails replica 0)
# ---------------------------------------------------------------------------
def test_dispatch_fault_is_retried_transparently(stub_fleet):
    fleet, _ = stub_fleet
    before = serve.fleet_stats()["retries"]
    with fault.scope("fleet.dispatch:1:error"):
        toks = fleet.submit([5, 6], max_new_tokens=3).result(timeout=30)
        assert fault.hits("fleet.dispatch") >= 1
    assert toks.tolist() == _stub_tokens([5, 6], 3, fleet.version)
    assert serve.fleet_stats()["retries"] >= before + 1


def test_one_trace_per_request_across_retry_hop(stub_fleet, tmp_path):
    """A request that survives a dispatch retry is still ONE trace: the
    router re-uses the same request root, recording a single
    fleet.request span whose `attempts` count exposes the hop."""
    fleet, _ = stub_fleet
    profiler.start()
    try:
        with fault.scope("fleet.dispatch:1:error"):
            fleet.submit([7, 7], max_new_tokens=2).result(timeout=30)
        fleet.submit([8], max_new_tokens=2).result(timeout=30)
    finally:
        profiler.stop()
    f = str(tmp_path / "trace.json")
    profiler.dump(filename=f)
    events = json.load(open(f))["traceEvents"]
    roots = [e for e in events if e["name"] == "fleet.request"]
    assert len(roots) == 2
    tids = {e["args"]["trace_id"] for e in roots}
    assert len(tids) == 2, "each fleet request must be its own trace"
    attempts = sorted(e["args"]["attempts"] for e in roots)
    assert attempts == [1, 2], attempts


def test_sigkill_failover_reenqueues_inflight_onto_survivor(stub_fleet):
    """Replica death with work in flight: every future still resolves
    (re-dispatched under the retry budget), the failover and retries are
    counted, and the supervisor respawns the replica."""
    fleet, _ = stub_fleet
    before = serve.fleet_stats()
    pid0 = fleet.stats()["replicas"][0]["pid"]
    futs = [fleet.submit([9, i], max_new_tokens=4) for i in range(16)]
    os.kill(pid0, signal.SIGKILL)
    for i, f in enumerate(futs):
        assert f.result(timeout=60).tolist() == \
            _stub_tokens([9, i], 4, fleet.version)
    after = serve.fleet_stats()
    assert after["failovers"] >= before["failovers"] + 1
    assert after["retries"] >= before["retries"] + 1
    _wait(lambda: _serving(fleet) == 2, 30, "respawn after SIGKILL")
    assert after["respawns"] >= before["respawns"] or \
        serve.fleet_stats()["respawns"] >= before["respawns"] + 1
    assert fleet.stats()["replicas"][0]["pid"] != pid0


def test_heartbeat_fault_declares_replica_hung_then_respawns(stub_fleet):
    """Persistent fleet.heartbeat failures count as missed heartbeats;
    past the miss budget the replica is killed and respawned."""
    fleet, _ = stub_fleet
    before = serve.fleet_stats()["respawns"]
    with fault.scope("fleet.heartbeat:1+:error"):
        _wait(lambda: serve.fleet_stats()["respawns"] >= before + 1,
              30, "hung-replica respawn")
        assert fault.hits("fleet.heartbeat") >= 2  # heartbeat_misses
    _wait(lambda: _serving(fleet) == 2, 60, "fleet recovery")
    toks = fleet.submit([3], max_new_tokens=2).result(timeout=30)
    assert toks.tolist() == _stub_tokens([3], 2, fleet.version)


# ---------------------------------------------------------------------------
# drain-and-swap: zero drops, version flip, typed abort
# ---------------------------------------------------------------------------
def test_rolling_swap_drops_zero_requests_and_flips_version(stub_fleet):
    fleet, _ = stub_fleet
    before = serve.fleet_stats()
    stop, errors, served = threading.Event(), [], [0]

    def pump():
        while not stop.is_set():
            try:
                fleet.submit([2, 7], max_new_tokens=3).result(timeout=60)
                served[0] += 1
            except Exception as e:          # noqa: BLE001 - test collects
                errors.append(e)

    t = threading.Thread(target=pump)
    t.start()
    try:
        fleet.swap(dict(STUB_SPEC, version="v2"))
    finally:
        stop.set()
        t.join()
    assert not errors, f"swap dropped {len(errors)}: {errors[:3]}"
    assert served[0] > 0
    assert fleet.version == "v2"
    assert all(r["version"] == "v2" for r in fleet.stats()["replicas"])
    after = serve.fleet_stats()
    assert after["swaps"] == before["swaps"] + 1
    assert after["drain_ms"] > before["drain_ms"]
    # v2 actually serves (the stub token function is version-keyed)
    toks = fleet.submit([1], max_new_tokens=2).result(timeout=30)
    assert toks.tolist() == _stub_tokens([1], 2, "v2")


def test_swap_fault_aborts_typed_and_fleet_keeps_serving(stub_fleet):
    fleet, _ = stub_fleet
    with fault.scope("fleet.swap:1:error"):
        with pytest.raises(serve.FleetError, match="aborted at replica"):
            fleet.swap(dict(STUB_SPEC, version="v9"))
    assert fleet.version == "v2"            # unchanged by the abort
    _wait(lambda: _serving(fleet) == 2, 60, "recovery after swap abort")
    toks = fleet.submit([4], max_new_tokens=2).result(timeout=30)
    assert toks.tolist() == _stub_tokens([4], 2, "v2")


# must stay LAST in the stub module: replica 0 ends permanently failed
def test_respawn_fault_exhausts_bounded_restarts(stub_fleet):
    """PR-9 restart protocol at fleet scope: persistent respawn failures
    bill consecutive restarts; past max_restarts the replica is marked
    `failed` (no hot-loop) and the fleet serves degraded on the
    survivor."""
    fleet, _ = stub_fleet
    pid0 = fleet.stats()["replicas"][0]["pid"]
    with fault.scope("fleet.respawn:1+:error"):
        os.kill(pid0, signal.SIGKILL)
        _wait(lambda: fleet.stats()["replicas"][0]["state"] == "failed",
              30, "replica 0 to exhaust its restart budget")
        assert fault.hits("fleet.respawn") >= 2
    r0 = fleet.stats()["replicas"][0]
    assert r0["consecutive_restarts"] > 2   # max_restarts exceeded
    toks = fleet.submit([6], max_new_tokens=2).result(timeout=30)
    assert toks.tolist() == _stub_tokens([6], 2, fleet.version)
    assert serve.fleet_stats()["replicas_live"] == 1


# ---------------------------------------------------------------------------
# real engines: reference-exact outputs, warm hellos, zero retraces
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def real_fleet(tmp_path_factory):
    cache = tmp_path_factory.mktemp("fleet_cc")
    wd = tmp_path_factory.mktemp("real_fleet")
    spec = {"version": "v1", "config": CFG, "seed": 0,
            "engine": {"max_slots": 4, "decode_steps": 2,
                       "prefill_window": 16}}
    old_cc = os.environ.get("MXNET_COMPILE_CACHE_DIR")
    old_mp = os.environ.pop("MXNET_METRICS_PORT", None)
    os.environ["MXNET_COMPILE_CACHE_DIR"] = str(cache)
    try:
        fleet = serve.Fleet(spec, replicas=2, heartbeat_ms=250,
                            workdir=str(wd)).start()
    finally:
        if old_cc is None:
            os.environ.pop("MXNET_COMPILE_CACHE_DIR", None)
        else:
            os.environ["MXNET_COMPILE_CACHE_DIR"] = old_cc
        if old_mp is not None:
            os.environ["MXNET_METRICS_PORT"] = old_mp
    yield fleet
    fleet.close()


def test_real_fleet_matches_reference_and_reports_warm_hello(real_fleet):
    model = serve.CachedDecoder(serve.DecoderConfig(**CFG), seed=0)
    prompts = [[3, 1, 4, 1], [5, 9, 2], [6, 5, 3, 5, 8], [2, 7]]
    futs = [real_fleet.submit(p, max_new_tokens=6) for p in prompts]
    for p, f in zip(prompts, futs):
        np.testing.assert_array_equal(
            f.result(timeout=120), model.reference_generate(p, 6),
            err_msg=f"fleet output diverged for prompt {p}")
    for r in real_fleet.stats()["replicas"]:
        assert r["warmup_s"] is not None and r["warmup_s"] > 0
        assert r["compile_cache_size"] >= 1
        assert r["metrics_port"] is None    # env unset -> no server


def test_real_fleet_zero_retraces_fleet_wide(real_fleet):
    # pongs carry each engine's retraces_after_warmup counter
    _wait(lambda: real_fleet.retraces_after_warmup() >= 0, 10,
          "a heartbeat pong from every replica")
    assert real_fleet.retraces_after_warmup() == 0
    assert real_fleet.assert_no_retraces() == 0


# ---------------------------------------------------------------------------
# observability surface: stats-group keys + replica-state gauge
# ---------------------------------------------------------------------------
def test_fleet_stats_group_and_replica_state_gauge(real_fleet):
    assert set(serve.FLEET_STATS) == {
        "replicas_live", "failovers", "retries", "respawns", "swaps",
        "drain_ms", "profile_divergence", "affinity_hits"}
    snap = telemetry.REGISTRY.snapshot()
    for key in ("fleet.replicas_live", "fleet.failovers", "fleet.retries",
                "fleet.respawns", "fleet.swaps", "fleet.drain_ms",
                "fleet.affinity_hits"):
        assert key in snap, key
    # serve.replica_state is a labeled gauge: one series per replica,
    # level 2 == serving
    assert sum(k.startswith("serve.replica_state") for k in snap) == 2
    assert snap['serve.replica_state{replica="0"}'] == 2
    assert snap['serve.replica_state{replica="1"}'] == 2


# ---------------------------------------------------------------------------
# nightly: real SIGKILL under open-loop Poisson traffic, and a real
# rolling swap under sustained load
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_crashtest_fleet_sigkill_under_poisson_traffic(tmp_path):
    """ISSUE 16 acceptance: SIGKILL one of two replicas mid-stream under
    the PR-13 open-loop generator — zero client-visible failures, kill
    window p99 within 3x steady, warm respawn via the compile cache."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "crashtest.py"),
         "--fleet", "--rate", "20", "--window", "5",
         "--dir", str(tmp_path)],
        capture_output=True, text=True, timeout=570,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "fleet SIGKILL parity OK" in proc.stdout
    assert "0 client-visible failures" in proc.stdout


@pytest.mark.slow
def test_real_rolling_swap_under_sustained_load(tmp_path):
    """Rolling drain-and-swap across real replicas while clients pump:
    zero drops, the new version's outputs are reference-exact, and the
    fleet-wide zero-retrace contract holds on the swapped fleet."""
    cache = tmp_path / "cc"
    cache.mkdir()
    old = os.environ.get("MXNET_COMPILE_CACHE_DIR")
    os.environ["MXNET_COMPILE_CACHE_DIR"] = str(cache)
    spec = {"version": "v1", "config": CFG, "seed": 0,
            "engine": {"max_slots": 4, "decode_steps": 2,
                       "prefill_window": 16}}
    try:
        fleet = serve.Fleet(spec, replicas=2, heartbeat_ms=250,
                            workdir=str(tmp_path / "fleet")).start()
    finally:
        if old is None:
            os.environ.pop("MXNET_COMPILE_CACHE_DIR", None)
        else:
            os.environ["MXNET_COMPILE_CACHE_DIR"] = old
    try:
        stop, errors, served = threading.Event(), [], [0]

        def pump():
            while not stop.is_set():
                try:
                    fleet.submit([2, 7], max_new_tokens=4).result(
                        timeout=120)
                    served[0] += 1
                except Exception as e:      # noqa: BLE001 - test collects
                    errors.append(e)

        threads = [threading.Thread(target=pump) for _ in range(3)]
        for t in threads:
            t.start()
        try:
            fleet.swap(dict(spec, version="v2", seed=1))
        finally:
            stop.set()
            for t in threads:
                t.join()
        assert not errors, f"swap dropped {len(errors)}: {errors[:3]}"
        assert served[0] > 0
        assert fleet.version == "v2"
        model = serve.CachedDecoder(serve.DecoderConfig(**CFG), seed=1)
        got = fleet.submit([3, 3], max_new_tokens=4).result(timeout=120)
        np.testing.assert_array_equal(got, model.reference_generate(
            [3, 3], 4))
        _wait(lambda: fleet.retraces_after_warmup() >= 0, 10,
              "post-swap pongs")
        assert fleet.assert_no_retraces() == 0
    finally:
        fleet.close()
