"""Fleet serving benchmark (ISSUE 16): multi-replica capacity, SIGKILL
tail latency, and drain-and-swap drop accounting.

Four segments over the SAME tiny decoder spec (replicas share one
persistent compilation cache, so every spawn after the first is warm):

  single   1-replica fleet under closed-loop pump threads -> requests/s
  fleet    2-replica fleet, same pump -> requests/s; the ratio is
           `fleet_vs_single_speedup` (router + process fan-out must buy
           real capacity, not just redundancy)
  kill     open-loop Poisson stream (PR-13 discipline: arrivals never
           wait for completions) over the 2-replica fleet, an identical
           mid-window burst in BOTH windows, replica 0 SIGKILLed at the
           kill-window burst -> `fleet_p99_ms_during_kill` vs
           `fleet_p99_ms_steady`, plus the client-visible failure count
           (must be 0 — in-flight work re-enqueues onto the survivor)
  swap     rolling drain-and-swap to a new version under sustained pump
           load -> `fleet_swap_dropped_requests` (must be 0) and the
           swap wall time

`--quick` swaps in stub replicas ({"stub": true} specs — the jax-free
deque engine in serve.replica): the router/failover/swap machinery is
identical, only the model work is simulated, and the output is stamped
`meta.stub` so a stub line can never be read as a real-engine number.
Trend keys are gated by tools/benchdiff.py; the committed artifact
(benchmark/results/fleet_r16.json) carries a full real-engine run.

Usage:
  python benchmark/fleet_bench.py --out /tmp/fleet.json
  python benchmark/fleet_bench.py --quick --duration 1.0
"""
import argparse
import json
import os
import signal
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Host-side serving benchmark: force CPU before jax initializes (same
# recipe as serve_bench.py).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

CFG = dict(vocab=64, embed=32, layers=2, heads=4, head_dim=8, max_len=48)


def _spec(version, seed, quick):
    if quick:
        return {"version": version, "stub": True, "stub_delay_ms": 3.0}
    return {"version": version, "seed": seed, "config": CFG,
            "engine": {"max_slots": 4, "decode_steps": 2,
                       "prefill_window": 16}}


def _pump(fleet, seconds, threads=8, max_new=4):
    """Closed-loop load: `threads` clients, each submit->wait->repeat.
    Returns (completed, errors, latencies_s)."""
    stop = threading.Event()
    lock = threading.Lock()
    done, errs, lats = [0], [], []
    rng = np.random.RandomState(0)
    prompts = [[int(t) for t in rng.randint(1, 64, size=n)]
               for n in rng.randint(2, 8, size=64)]

    def run(i):
        k = i
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                fleet.submit(prompts[k % len(prompts)],
                             max_new_tokens=max_new).result(timeout=120)
                with lock:
                    done[0] += 1
                    lats.append(time.perf_counter() - t0)
            except Exception as e:          # noqa: BLE001 - bench collects
                with lock:
                    errs.append(repr(e))
            k += threads

    ts = [threading.Thread(target=run, args=(i,)) for i in range(threads)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    time.sleep(seconds)
    stop.set()
    for t in ts:
        t.join()
    wall = time.perf_counter() - t0
    return done[0], errs, lats, wall


def _p99_ms(lats):
    if not lats:
        return None
    return round(float(np.percentile(lats, 99)) * 1e3, 3)


def _poisson_window(fleet, window, rate, rng, lat, failures, tag,
                    burst_at=0.25, burst=24, on_burst=None):
    """One open-loop window with a mid-window burst; `on_burst` (the
    SIGKILL) runs right after the burst fires."""
    lock = threading.Lock()

    def fire():
        t0 = time.perf_counter()

        def _done(f):
            try:
                f.result()
                with lock:
                    lat.append(time.perf_counter() - t0)
            except Exception as e:          # noqa: BLE001 - bench collects
                with lock:
                    failures.append((tag, repr(e)))

        prompt = [int(t) for t in rng.randint(1, 64,
                                              size=rng.randint(2, 8))]
        fleet.submit(prompt, max_new_tokens=4).add_done_callback(_done)

    def burster():
        for _ in range(burst):
            fire()
        if on_burst is not None:
            on_burst()

    timer = threading.Timer(window * burst_at, burster)
    timer.start()
    end = time.perf_counter() + window
    n = 0
    while time.perf_counter() < end:
        fire()
        n += 1
        time.sleep(rng.exponential(1.0 / rate))
    timer.join()
    return n + burst


def run(args):
    from incubator_mxnet_tpu import serve

    workdir = tempfile.mkdtemp(prefix="mx_fleet_bench_")
    if not args.quick:
        # replicas inherit one shared persistent cache (every spawn after
        # the first is warm), at a fixed path
        from incubator_mxnet_tpu.deploy import default_compile_cache_to_checkout
        default_compile_cache_to_checkout()
    seconds = args.duration
    out = {"meta": {"bench": "fleet_bench", "quick": bool(args.quick),
                    "stub": bool(args.quick), "duration_s": seconds,
                    "replicas": 2, "pump_threads": args.threads,
                    "host_cores": os.cpu_count(),
                    "model": None if args.quick else CFG}}
    try:
        out["meta"]["host_loadavg_1m"] = round(os.getloadavg()[0], 2)
    except OSError:
        pass
    if (os.cpu_count() or 1) < 2:
        out["meta"]["note"] = (
            "host has fewer cores than replicas: fleet_vs_single_speedup "
            "measures core contention, not added capacity — compare only "
            "against rounds on the same core count")
    out["backend_ok"] = True

    # -- single-replica capacity baseline -------------------------------
    single = serve.Fleet(_spec("v1", 0, args.quick), replicas=1,
                         heartbeat_ms=200,
                         workdir=os.path.join(workdir, "single")).start()
    try:
        done, errs, lats, wall = _pump(single, seconds,
                                       threads=args.threads)
        rps_single = round(done / wall, 2)
        out["single"] = {"requests_per_sec": rps_single,
                         "completed": done, "errors": len(errs),
                         "p99_ms": _p99_ms(lats)}
    finally:
        single.close()

    # -- 2-replica fleet: capacity, kill, swap --------------------------
    fleet = serve.Fleet(_spec("v1", 0, args.quick), replicas=2,
                        heartbeat_ms=200,
                        workdir=os.path.join(workdir, "fleet")).start()
    try:
        done, errs, lats, wall = _pump(fleet, seconds,
                                       threads=args.threads)
        rps_fleet = round(done / wall, 2)
        out["fleet"] = {"requests_per_sec": rps_fleet,
                        "completed": done, "errors": len(errs),
                        "p99_ms": _p99_ms(lats)}
        out["fleet_vs_single_speedup"] = (
            round(rps_fleet / rps_single, 3) if rps_single else None)

        # kill segment: open-loop at half the measured fleet capacity so
        # the survivor alone can absorb the stream (the latency question,
        # not the saturation question)
        rate = max(5.0, min(args.rate or rps_fleet * 0.5, 200.0))
        rng = np.random.RandomState(args.seed)
        steady_lat, kill_lat, failures = [], [], []
        n_steady = _poisson_window(fleet, seconds, rate, rng, steady_lat,
                                   failures, "steady")
        pid0 = fleet.stats()["replicas"][0]["pid"]
        n_kill = _poisson_window(
            fleet, seconds, rate, rng, kill_lat, failures, "kill",
            on_burst=lambda: os.kill(pid0, signal.SIGKILL))
        deadline = time.time() + 120
        while time.time() < deadline:
            if len(steady_lat) + len(kill_lat) + len(failures) \
                    >= n_steady + n_kill and \
                    sum(1 for r in fleet.stats()["replicas"]
                        if r["state"] == "serving") == 2:
                break
            time.sleep(0.1)
        st = fleet.stats()
        out["kill"] = {"offered_rps": round(rate, 1),
                       "sent": n_steady + n_kill,
                       "completed": len(steady_lat) + len(kill_lat),
                       "failures": len(failures),
                       "failovers": st["failovers"],
                       "retries": st["retries"],
                       "respawns": st["respawns"]}
        out["fleet_p99_ms_steady"] = _p99_ms(steady_lat)
        out["fleet_p99_ms_during_kill"] = _p99_ms(kill_lat)
        out["fleet_kill_failures"] = len(failures)

        # swap segment: rolling v1 -> v2 under sustained pump load
        stop = threading.Event()
        swap_errs, swap_done = [], [0]

        def pump_one():
            while not stop.is_set():
                try:
                    fleet.submit([2, 7], max_new_tokens=4).result(
                        timeout=120)
                    swap_done[0] += 1
                except Exception as e:      # noqa: BLE001 - bench collects
                    swap_errs.append(repr(e))

        pumps = [threading.Thread(target=pump_one) for _ in range(3)]
        for t in pumps:
            t.start()
        t0 = time.perf_counter()
        try:
            fleet.swap(_spec("v2", 1, args.quick))
            swap_ms = round((time.perf_counter() - t0) * 1e3, 1)
        finally:
            stop.set()
            for t in pumps:
                t.join()
        out["swap"] = {"swap_ms": swap_ms,
                       "served_during": swap_done[0],
                       "drain_ms_total": fleet.stats()["drain_ms"],
                       "version_after": fleet.version}
        out["fleet_swap_dropped_requests"] = len(swap_errs)
        if swap_errs:
            out["swap"]["first_errors"] = swap_errs[:3]
    finally:
        fleet.close()
    # stamped only now: the router process stays off jax while replica
    # processes hold the devices, and every fleet is closed by here
    import jax
    out["meta"]["platform"] = jax.devices()[0].platform
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="stub replicas + short windows (CI smoke; "
                         "stamped meta.stub)")
    ap.add_argument("--duration", type=float, default=None,
                    help="seconds per segment window (default 6.0, "
                         "quick 1.5)")
    ap.add_argument("--threads", type=int, default=8,
                    help="closed-loop pump clients for the capacity "
                         "segments")
    ap.add_argument("--rate", type=float, default=None,
                    help="open-loop rate for the kill segment "
                         "(default: half the measured fleet capacity)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(
        tempfile.gettempdir(), "fleet_bench.json"))
    args = ap.parse_args()
    if args.duration is None:
        args.duration = 1.5 if args.quick else 6.0

    out = run(args)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
