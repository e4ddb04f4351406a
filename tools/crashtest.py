#!/usr/bin/env python
"""Crash-consistency integration test (≙ the restart recipe the reference's
ps-lite elasticity story never shipped).

Spawns a training subprocess driven by `mx.fault.run_resilient`, SIGKILLs it
at a (by default random) step via the fault-injection spec
`resilient.step:<N>:kill`, restarts it with injection disarmed, and asserts
the restarted run converges to EXACTLY the same final parameters as an
uninterrupted reference run — proving the crash-consistent checkpoint commit
protocol plus auto-resume lose nothing.

Usage:
    python tools/crashtest.py [--steps 30] [--ckpt-every 5] [--kill-at N]
                              [--dir DIR] [--seed 0]
    python tools/crashtest.py --elastic [--resume-dp 4] [...]
    python tools/crashtest.py --flightrec [--steps 12] [...]
    python tools/crashtest.py --oom [--steps 8] [...]
    python tools/crashtest.py --fleet [--rate 20] [--window 6] [...]

`--fleet` is the serving-side SIGKILL-parity harness (ISSUE 16): a real
2-replica `mx.serve.Fleet` (replica subprocesses sharing one persistent
compilation cache) serves an OPEN-LOOP Poisson request stream (the PR-13
tail-latency discipline: arrivals never wait for completions, so a
stalled fleet cannot slow its own load down). Mid-stream the harness
SIGKILLs replica 0 and asserts (a) ZERO client-visible failures — every
in-flight request re-enqueues onto the survivor under the retry budget,
(b) the kill-window p99 stays within 3x the steady-state p99, and
(c) the supervisor's respawned replica rejoins WARM: its hello reports
the same compile_cache_size it died with and the fleet-wide zero-retrace
contract still holds.

`--oom` tests the OOM-forensics path (ISSUE 15): a BOUNDED planted
allocation bomb (32MB, census-registered as owner `oom_bomb`) rides an
elastic run that raises a RESOURCE_EXHAUSTED-shaped error mid-training;
the parent asserts run_elastic's `mem.on_oom` hook left an OOM dump
whose top census entry names the planted owner (plus live memory plans
and a parseable flightrec spool). Bounded on purpose: really exhausting
memory on a shared CI host invites the OS OOM killer into neighboring
processes.

`--flightrec` tests the flight recorder's SIGKILL parity (ISSUE 13): the
elastic child runs with `MXNET_FLIGHTREC_DIR` set, so every span open /
fault event is spooled as a flushed JSONL line; the child SIGKILLs itself
mid-step and the parent asserts the spool landed, every line parses as
JSON, and the tail names the in-flight step + mesh (the `elastic.step`
span_open with its `step`/`dp` fields) and the injected kill — a dead
process leaves a black box, with no handler having run.

`--elastic` switches to the distributed mode (ISSUE 12): the child trains
the ZeRO-sharded `mx.fault.elastic` trainer on an 8-way virtual CPU mesh,
is SIGKILLed mid-epoch via `elastic.step:<N>:kill`, and the restart —
optionally onto a SMALLER dp via `--resume-dp` (shard repartition
included) — must reproduce the uninterrupted run's parameters AND
optimizer-state shards bit-exactly.

Exact-arithmetic harness note: the elastic child's model is linear in the
parameters with integer-valued per-sample gradient contributions on a
2^-15 lattice (SGD momentum=1.0, lr=2^-2, ≤64 steps), so every partial
sum any reduction order can form is exactly representable in float32 —
cross-mesh reductions (dp=8 vs dp=4 group sums differently) are therefore
BIT-IDENTICAL, and the parity check tests the checkpoint/repartition
protocol, not float summation order.

Exit code 0 on parity; non-zero otherwise. Registered as slow-marked
pytests in tests/test_fault.py / tests/test_elastic.py so tier-1 stays
fast but nightly exercises a real SIGKILL.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import subprocess
import sys
import tempfile

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child(args):
    """Training subprocess: resilient loop over a deterministic quadratic
    descent, host-local npz checkpoints (fast, orbax-free)."""
    sys.path.insert(0, REPO)
    from incubator_mxnet_tpu import fault

    rng = np.random.RandomState(args.seed)
    init = {"w": rng.randn(16).astype(np.float64)}

    def step_fn(state, step):
        w = state["w"]
        w = w.asnumpy() if hasattr(w, "asnumpy") else np.asarray(w)
        loss = float(np.mean(w ** 2))
        return {"w": w * (1.0 - 0.05) + 0.01 * np.cos(step)}, loss

    run = fault.run_resilient(step_fn, init, args.dir, args.steps,
                              ckpt_every=args.ckpt_every, sharded=False,
                              keep_last=3)
    w = run.state["w"]
    w = w.asnumpy() if hasattr(w, "asnumpy") else np.asarray(w)
    with open(os.path.join(args.dir, "final.json"), "w") as f:
        json.dump({"w": w.tolist(), "resumed_from": run.resumed_from}, f)
    return 0


def _elastic_child(args):
    """Elastic-mode training subprocess: ZeRO trainer on an 8-way virtual
    CPU mesh, exact-lattice linear model (see module docstring), dp from
    --dp. Dumps final params + optimizer-state + accounting to
    final.json.

    OOM-bomb mode (`MXTPU_OOM_AT=<step>`, set by `--oom`): a 32MB device
    buffer is carved up-front and census-registered as the planted owner
    `oom_bomb`, and at the given step the batch supply raises a
    RESOURCE_EXHAUSTED-shaped error. Deterministic and BOUNDED on
    purpose: really exhausting host memory on a shared CI box invites
    the OS OOM killer into every neighboring process — the point of the
    test is the forensics path (run_elastic's on_oom hook dumps census +
    plans + the flightrec ring before re-raising), and a synthetic
    RESOURCE_EXHAUSTED drives exactly that path."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    sys.path.insert(0, REPO)
    import jax.numpy as jnp
    from incubator_mxnet_tpu.fault import elastic

    seed = args.seed

    def loss_fn(p, batch):
        # linear in w: grad wrt w is mean(c) — integer-valued data on an
        # exact f32 lattice, so any reduction order gives identical bits
        return jnp.mean(batch["c"] @ p["w"]) + jnp.mean(
            batch["c"][:, :8] @ p["v"].reshape(8, 2))

    def batch_fn(step):
        r = np.random.RandomState(seed * 100003 + step)
        return {"c": r.randint(-8, 9, (64, 24)).astype(np.float32)}

    oom_at = os.environ.get("MXTPU_OOM_AT")
    if oom_at is not None:
        oom_at = int(oom_at)
        from incubator_mxnet_tpu.inspect import memory as mem
        # the planted owner: dominates every other live buffer, so the
        # dump's top census entry MUST name it
        bomb = jnp.zeros((1024, 1024, 8), jnp.float32)      # 32 MB
        mem.register(bomb, owner="oom_bomb")
        real_batch_fn = batch_fn

        def batch_fn(step, _bomb=bomb):
            if step >= oom_at:
                # the collective programs exist by now — note their plans
                # so the dump's "what was supposed to fit" table is live
                try:
                    mem.collective_memory_plans()
                except Exception:
                    pass
                raise RuntimeError(
                    "RESOURCE_EXHAUSTED: Out of memory while trying to "
                    "allocate 34359738368 bytes (simulated allocation "
                    "bomb; tools/crashtest.py --oom)")
            return real_batch_fn(step)

    params = {"w": (np.arange(24, dtype=np.float32) - 12) / 4.0,
              "v": np.linspace(-1, 1, 16).astype(np.float32)}
    run = elastic.run_elastic(loss_fn, params, batch_fn, args.dir,
                              args.steps, optimizer="sgd", dp=args.dp,
                              ckpt_every=args.ckpt_every, keep_last=3,
                              momentum=1.0, learning_rate=0.25)
    out = {"resumed_from": run.resumed_from, "dp": run.trainer.dp,
           "params": {k: v.tolist() for k, v in run.params().items()},
           "opt": {k: [leaf.tolist() for leaf in _flat_state(v)]
                   for k, v in run.opt_state().items()}}
    with open(os.path.join(args.dir, "final.json"), "w") as f:
        json.dump(out, f)
    return 0


def _flat_state(st):
    if st is None:
        return []
    if isinstance(st, tuple):
        return [l for s in st for l in _flat_state(s)]
    return [st]


def _flightrec_mode(workdir, kill_at, run_child, point):
    """SIGKILL a flight-recorded elastic run and audit its black box."""
    import glob

    rec_dir = os.path.join(workdir, "flightrec")
    _d, proc = run_child("crash", {
        "MXNET_FAULT_SPEC": f"{point}:{kill_at}:kill",
        "MXNET_FLIGHTREC_DIR": rec_dir})
    if proc.returncode == 0:
        print("crashtest: child survived its own SIGKILL?", file=sys.stderr)
        return 1
    print(f"crashtest: child SIGKILLed at step hit {kill_at} "
          f"(rc={proc.returncode})")

    spools = glob.glob(os.path.join(rec_dir, "flightrec-*.jsonl"))
    if not spools:
        print(f"crashtest: NO flight-recorder spool in {rec_dir}",
              file=sys.stderr)
        return 1
    events = []
    for path in spools:
        with open(path) as f:
            for ln, line in enumerate(f, 1):
                line = line.strip()
                if not line:
                    continue
                try:
                    events.append(json.loads(line))
                except ValueError:
                    print(f"crashtest: {path}:{ln} is not valid JSON: "
                          f"{line[:120]}", file=sys.stderr)
                    return 1
    if not events:
        print("crashtest: spool parsed but holds zero events",
              file=sys.stderr)
        return 1

    # the tail must name the IN-FLIGHT step: the last elastic.step
    # span_open (the span never closed — the process died inside it)
    step_opens = [e for e in events
                  if e.get("kind") == "span_open" and e.get("name") == point]
    if not step_opens:
        print(f"crashtest: no span_open for {point!r} in the spool",
              file=sys.stderr)
        return 1
    last = step_opens[-1]
    if "step" not in last or "dp" not in last:
        print(f"crashtest: in-flight {point} event lacks step/dp: {last}",
              file=sys.stderr)
        return 1
    injected = [e for e in events
                if e.get("name") == "fault.injected"
                and e.get("point") == point]
    if not injected:
        print("crashtest: the injected-kill fault event is missing from "
              "the spool", file=sys.stderr)
        return 1
    tail_idx = {id(e): i for i, e in enumerate(events)}
    print(f"crashtest: flight recorder OK — {len(events)} spooled events, "
          f"in-flight {point} at step {last['step']} on dp={last['dp']} "
          f"(spool line {tail_idx[id(last)] + 1}/{len(events)}), "
          f"kill injected at hit {injected[-1].get('hit')}")
    return 0


def _sanitize_child(args):
    """Plant a use-after-donate and report what the process saw. With
    MXNET_SANITIZE=donation the wrapper must trap it as a typed
    DonationViolation at the offending call; with the sanitizer off the
    bug either sails through silently (platforms where donation is a
    no-op) or dies with an anonymous buffer-deleted error that names
    neither the program nor the argument."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu import sanitize
    step = sanitize.maybe_wrap_donated(
        jax.jit(lambda w, g: w - 0.1 * g, donate_argnums=(0,)),
        (0,), "crashtest.step")
    w = jnp.ones((64,))
    g = jnp.ones((64,))
    result = {"modes": sorted(sanitize.modes()), "error_type": None,
              "typed": False, "message": None}
    try:
        step(w, g)
        bad = step(w, g)          # planted: w was donated one line up
        float(jnp.sum(bad))       # force materialization either way
    except sanitize.DonationViolation as e:
        result.update(error_type="DonationViolation", typed=True,
                      message=str(e)[:300])
    except (RuntimeError, ValueError) as e:
        # the anonymous runtime failure: no program name, no argument
        # index, no hint of which call donated the buffer
        result.update(error_type=type(e).__name__, message=str(e)[:300])
    print(json.dumps(result))
    return 0


def _sanitize_mode(workdir):
    """Run the planted use-after-donate twice — sanitizer armed and off —
    and assert the armed arm produced the typed error + flightrec
    artifacts while the off arm shows the silent-on-CPU failure mode."""
    import glob

    rec_dir = os.path.join(workdir, "flightrec")
    base_env = {**os.environ, "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + os.pathsep
                + os.environ.get("PYTHONPATH", "")}
    base_env.pop("MXNET_SANITIZE", None)

    def run(tag, extra):
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child",
             "--sanitize"],
            env={**base_env, **extra}, capture_output=True, text=True,
            timeout=300)
        if proc.returncode != 0:
            print(proc.stdout + proc.stderr, file=sys.stderr)
            print(f"crashtest: sanitize {tag} child failed",
                  file=sys.stderr)
            return None
        return json.loads(proc.stdout.strip().splitlines()[-1])

    armed = run("armed", {"MXNET_SANITIZE": "donation",
                          "MXNET_FLIGHTREC_DIR": rec_dir})
    if armed is None:
        return 1
    if not armed["typed"] or armed["error_type"] != "DonationViolation":
        print(f"crashtest: armed run did NOT produce the typed "
              f"DonationViolation: {armed}", file=sys.stderr)
        return 1
    if "crashtest.step" not in (armed["message"] or ""):
        print(f"crashtest: violation lacks program provenance: "
              f"{armed['message']}", file=sys.stderr)
        return 1

    # the black box: spooled violation event + rate-limited dump file
    spools = glob.glob(os.path.join(rec_dir, "flightrec-*.jsonl"))
    events = []
    for path in spools:
        with open(path) as f:
            events += [json.loads(l) for l in f if l.strip()]
    violations = [e for e in events
                  if e.get("kind") == "sanitize.donation"]
    if not violations:
        print(f"crashtest: no sanitize.donation event spooled in "
              f"{rec_dir} ({len(events)} events)", file=sys.stderr)
        return 1
    dumps = glob.glob(os.path.join(rec_dir, "flightrec-*.json"))
    if not dumps:
        print(f"crashtest: no flightrec dump file in {rec_dir}",
              file=sys.stderr)
        return 1

    off = run("off", {})
    if off is None:
        return 1
    if off["typed"] or off["error_type"] == "DonationViolation":
        print(f"crashtest: UNSANITIZED run produced a typed violation "
              f"({off}) — the sanitizer is leaking into the off arm",
              file=sys.stderr)
        return 1
    if off["error_type"] is None:
        contrast = ("unsanitized run sailed through SILENTLY (the bug "
                    "class that only explodes on TPU)")
    elif "crashtest.step" in (off["message"] or ""):
        print(f"crashtest: unsanitized error unexpectedly carries "
              f"provenance ({off['message']}) — harness premise changed",
              file=sys.stderr)
        return 1
    else:
        contrast = (f"unsanitized run died with an anonymous "
                    f"{off['error_type']} carrying no program name or "
                    f"argument index")

    print(f"crashtest: sanitize OK — armed run trapped the planted "
          f"use-after-donate as DonationViolation naming "
          f"crashtest.step (flightrec: {len(violations)} violation "
          f"event(s) spooled, dump {os.path.basename(dumps[0])}); "
          f"{contrast}")
    return 0


def _oom_mode(workdir, kill_at, run_child):
    """Drive the OOM-forensics path: a planted allocation bomb under
    run_elastic must leave (a) a parseable flightrec spool recording the
    `oom` event, and (b) an OOM dump whose TOP census entry names the
    planted owner and whose plans table is non-empty."""
    import glob

    rec_dir = os.path.join(workdir, "flightrec")
    _d, proc = run_child("crash", {
        "MXNET_FLIGHTREC_DIR": rec_dir,
        "MXTPU_OOM_AT": str(kill_at)})
    if proc.returncode == 0:
        print("crashtest: child survived its own OOM?", file=sys.stderr)
        return 1
    print(f"crashtest: child OOMed at step {kill_at} "
          f"(rc={proc.returncode})")

    dumps = glob.glob(os.path.join(rec_dir, "oomdump-*.json"))
    if not dumps:
        print(f"crashtest: NO oom dump in {rec_dir}", file=sys.stderr)
        print(proc.stdout + proc.stderr, file=sys.stderr)
        return 1
    with open(dumps[0]) as f:
        dump = json.load(f)
    owners = (dump.get("census") or {}).get("owners") or {}
    if not owners:
        print("crashtest: oom dump carries no census", file=sys.stderr)
        return 1
    top = next(iter(owners))
    if top != "oom_bomb":
        print(f"crashtest: top census owner is {top!r}, wanted the "
              f"planted 'oom_bomb' "
              f"({ {k: v['bytes'] for k, v in owners.items()} })",
              file=sys.stderr)
        return 1
    if not dump.get("plans"):
        print("crashtest: oom dump carries no memory plans",
              file=sys.stderr)
        return 1
    if "RESOURCE_EXHAUSTED" not in (dump.get("error") or ""):
        print(f"crashtest: dump error field is not the OOM: "
              f"{dump.get('error')!r}", file=sys.stderr)
        return 1

    spools = glob.glob(os.path.join(rec_dir, "flightrec-*.jsonl"))
    if not spools:
        print("crashtest: no flightrec spool next to the oom dump",
              file=sys.stderr)
        return 1
    events = []
    for path in spools:
        with open(path) as f:
            for ln, line in enumerate(f, 1):
                line = line.strip()
                if not line:
                    continue
                try:
                    events.append(json.loads(line))
                except ValueError:
                    print(f"crashtest: {path}:{ln} is not valid JSON",
                          file=sys.stderr)
                    return 1
    oom_events = [e for e in events if e.get("kind") == "oom"]
    if not oom_events:
        print("crashtest: spool has no 'oom' event", file=sys.stderr)
        return 1
    print(f"crashtest: OOM forensics OK — dump names 'oom_bomb' as top "
          f"owner ({owners['oom_bomb']['bytes']} bytes), "
          f"{len(dump['plans'])} plan(s), {len(events)} spooled events "
          f"incl. the oom marker")
    return 0


def _fleet_mode(workdir, args):
    """Serving SIGKILL parity: open-loop Poisson traffic over a real
    2-replica fleet, replica 0 SIGKILLed mid-stream. Zero client-visible
    failures, bounded kill-window p99, warm respawn."""
    import signal
    import threading
    import time

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, REPO)
    from incubator_mxnet_tpu import serve
    # the replicas' shared persistent cache (warm respawn), at a fixed path
    from incubator_mxnet_tpu.deploy import default_compile_cache_to_checkout
    default_compile_cache_to_checkout()

    spec = {"version": "v1", "seed": args.seed,
            "config": dict(vocab=64, embed=32, layers=2, heads=4,
                           head_dim=8, max_len=48),
            "engine": {"max_slots": 4, "decode_steps": 2,
                       "prefill_window": 16}}
    fleet = serve.Fleet(spec, replicas=2, heartbeat_ms=200,
                        workdir=os.path.join(workdir, "fleet")).start()
    try:
        pre = {r["replica"]: r for r in fleet.stats()["replicas"]}
        print(f"crashtest: fleet up — warmups "
              f"{[round(r['warmup_s'], 2) for r in pre.values()]}s, "
              f"compile_cache_size "
              f"{[r['compile_cache_size'] for r in pre.values()]}")

        rng = np.random.RandomState(args.seed)
        lock = threading.Lock()
        lat = {"steady": [], "kill": []}
        failures = []

        def fire(window, prompt):
            t0 = time.perf_counter()

            def done(f):
                try:
                    f.result()
                    with lock:
                        lat[window].append(time.perf_counter() - t0)
                except Exception as e:          # noqa: BLE001 - harness
                    with lock:
                        failures.append((window, repr(e)))

            fleet.submit(prompt, max_new_tokens=4).add_done_callback(done)

        def poisson_window(window, seconds):
            # OPEN loop: exponential inter-arrival, arrivals never wait
            # for completions
            end = time.perf_counter() + seconds
            n = 0
            while time.perf_counter() < end:
                fire(window, [int(rng.randint(1, 64))
                              for _ in range(int(rng.randint(2, 8)))])
                n += 1
                time.sleep(rng.exponential(1.0 / args.rate))
            return n

        burst = 24
        rng2 = np.random.RandomState(args.seed + 1)

        def fire_burst(window):
            for _ in range(burst):
                fire(window, [int(rng2.randint(1, 64))
                              for _ in range(int(rng2.randint(2, 8)))])

        # the steady window carries the SAME mid-window burst as the kill
        # window, so the 3x p99 comparison is apples-to-apples: the kill
        # window differs ONLY by the SIGKILL
        buster = threading.Timer(args.window * 0.25, fire_burst,
                                 ("steady",))
        buster.start()
        n_steady = poisson_window("steady", args.window) + burst
        buster.join()
        pid0 = fleet.stats()["replicas"][0]["pid"]

        def kill_with_inflight():
            # the burst right before the SIGKILL guarantees requests are
            # IN FLIGHT on the doomed replica — the failover path under
            # test, not just the lucky between-requests case
            fire_burst("kill")
            os.kill(pid0, signal.SIGKILL)

        killer = threading.Timer(args.window * 0.25, kill_with_inflight)
        killer.start()
        n_kill = poisson_window("kill", args.window) + burst
        killer.join()

        # let the tail drain, then wait for the respawn to finish
        deadline = time.time() + 120
        while time.time() < deadline:
            st = fleet.stats()
            tail_done = len(lat["steady"]) + len(lat["kill"]) \
                + len(failures) >= n_steady + n_kill
            if tail_done and sum(1 for r in st["replicas"]
                                 if r["state"] == "serving") == 2:
                break
            time.sleep(0.1)

        p99s = float(np.percentile(lat["steady"], 99)) * 1e3
        p99k = float(np.percentile(lat["kill"], 99)) * 1e3
        st = fleet.stats()
        post0 = st["replicas"][0]
        print(f"crashtest: {n_steady} steady + {n_kill} kill-window "
              f"requests at ~{args.rate}/s, SIGKILL pid {pid0}")
        print(f"crashtest: p99 steady {p99s:.1f}ms, during kill "
              f"{p99k:.1f}ms; failovers={st['failovers']} "
              f"retries={st['retries']} respawns={st['respawns']}")
        if failures:
            print(f"crashtest: {len(failures)} CLIENT-VISIBLE FAILURES "
                  f"(first: {failures[0]})", file=sys.stderr)
            return 1
        if st["respawns"] < 1 or post0["state"] != "serving" \
                or post0["pid"] == pid0:
            print(f"crashtest: replica 0 did not respawn ({post0})",
                  file=sys.stderr)
            return 1
        if st["failovers"] < 1:
            print("crashtest: SIGKILL caught zero in-flight requests — "
                  "the failover path was not exercised", file=sys.stderr)
            return 1
        # warm rejoin: the respawned hello must report the compile cache
        # it died with — deserialization, not recompilation
        if (post0["compile_cache_size"] or 0) < \
                (pre[0]["compile_cache_size"] or 0):
            print(f"crashtest: respawned replica came back COLD "
                  f"(cache {post0['compile_cache_size']} < "
                  f"{pre[0]['compile_cache_size']})", file=sys.stderr)
            return 1
        time.sleep(0.5)                     # one more pong round-trip
        fleet.assert_no_retraces()
        # 3x steady-state p99 bound, with a small absolute floor so a
        # sub-ms steady p99 on an idle host cannot fail a healthy run
        bound = 3.0 * max(p99s, 25.0)
        if p99k > bound:
            print(f"crashtest: kill-window p99 {p99k:.1f}ms exceeds "
                  f"3x steady bound {bound:.1f}ms", file=sys.stderr)
            return 1
        print(f"crashtest: fleet SIGKILL parity OK — 0 client-visible "
              f"failures over {n_steady + n_kill} requests, kill-window "
              f"p99 {p99k:.1f}ms <= {bound:.1f}ms, warm respawn "
              f"(cache size {post0['compile_cache_size']}, warmup "
              f"{post0['warmup_s']:.2f}s), zero retraces fleet-wide")
        return 0
    finally:
        fleet.close()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--ckpt-every", type=int, default=5)
    ap.add_argument("--kill-at", type=int, default=0,
                    help="step hit at which the child SIGKILLs itself "
                         "(0 = random in [2, steps-1])")
    ap.add_argument("--dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--elastic", action="store_true",
                    help="distributed mode: ZeRO elastic trainer on the "
                         "8-way virtual CPU mesh")
    ap.add_argument("--dp", type=int, default=8,
                    help="elastic mode: initial dp size")
    ap.add_argument("--resume-dp", type=int, default=None,
                    help="elastic mode: dp size for the restarted run "
                         "(default: same as --dp; smaller = elastic "
                         "restart with shard repartition)")
    ap.add_argument("--flightrec", action="store_true",
                    help="flight-recorder SIGKILL-parity mode: kill an "
                         "elastic run mid-step, assert the JSONL spool "
                         "names the in-flight step/mesh")
    ap.add_argument("--oom", action="store_true",
                    help="OOM-forensics mode: a planted allocation bomb "
                         "under run_elastic must leave an OOM dump "
                         "naming the planted owner as top census entry")
    ap.add_argument("--sanitize", action="store_true",
                    help="sanitizer-parity mode: a planted use-after-"
                         "donate must trap as a typed DonationViolation "
                         "with a flightrec dump when MXNET_SANITIZE="
                         "donation, and sail through silently when off")
    ap.add_argument("--fleet", action="store_true",
                    help="serving SIGKILL-parity mode: open-loop Poisson "
                         "traffic over a real 2-replica fleet, replica 0 "
                         "SIGKILLed mid-stream — zero client-visible "
                         "failures, p99 <= 3x steady, warm respawn")
    ap.add_argument("--rate", type=float, default=20.0,
                    help="fleet mode: open-loop Poisson arrival rate "
                         "(requests/s)")
    ap.add_argument("--window", type=float, default=6.0,
                    help="fleet mode: seconds per traffic window "
                         "(steady and kill)")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.flightrec or args.oom:
        args.elastic = True

    if args.child:
        if args.sanitize:
            return _sanitize_child(args)
        return _elastic_child(args) if args.elastic else _child(args)

    workdir = args.dir or tempfile.mkdtemp(prefix="mx_crashtest_")
    if args.sanitize:
        return _sanitize_mode(workdir)
    if args.fleet:
        return _fleet_mode(workdir, args)
    kill_at = args.kill_at or random.randint(2, max(2, args.steps - 1))
    base_env = {**os.environ, "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + os.pathsep
                + os.environ.get("PYTHONPATH", "")}

    def run_child(tag, extra_env, dp=None):
        d = os.path.join(workdir, tag)
        cmd = [sys.executable, os.path.abspath(__file__), "--child",
               "--dir", d, "--steps", str(args.steps),
               "--ckpt-every", str(args.ckpt_every),
               "--seed", str(args.seed)]
        if args.elastic:
            cmd += ["--elastic", "--dp", str(dp or args.dp)]
        proc = subprocess.run(cmd, env={**base_env, **extra_env},
                              capture_output=True, text=True, timeout=600)
        return d, proc

    point = "elastic.step" if args.elastic else "resilient.step"

    if args.flightrec:
        return _flightrec_mode(workdir, kill_at, run_child, point)
    if args.oom:
        return _oom_mode(workdir, kill_at, run_child)

    # 1. uninterrupted reference
    ref_dir, proc = run_child("ref", {})
    if proc.returncode != 0:
        print(proc.stdout + proc.stderr, file=sys.stderr)
        print("crashtest: reference run failed", file=sys.stderr)
        return 1

    # 2. run that SIGKILLs itself mid-training
    crash_dir, proc = run_child(
        "crash", {"MXNET_FAULT_SPEC": f"{point}:{kill_at}:kill"})
    if proc.returncode == 0:
        print("crashtest: child survived its own SIGKILL?", file=sys.stderr)
        return 1
    print(f"crashtest: child SIGKILLed at step hit {kill_at} "
          f"(rc={proc.returncode})")

    # 3. restart with injection disarmed: must resume and finish —
    #    elastic mode optionally restarts onto a SMALLER dp mesh
    crash_dir, proc = run_child("crash", {}, dp=args.resume_dp)
    if proc.returncode != 0:
        print(proc.stdout + proc.stderr, file=sys.stderr)
        print("crashtest: restarted run failed", file=sys.stderr)
        return 1

    with open(os.path.join(ref_dir, "final.json")) as f:
        ref = json.load(f)
    with open(os.path.join(crash_dir, "final.json")) as f:
        got = json.load(f)
    print(f"crashtest: restarted run resumed from step "
          f"{got['resumed_from']}")
    if got["resumed_from"] is None and kill_at > args.ckpt_every:
        print("crashtest: restart did not resume from a checkpoint",
              file=sys.stderr)
        return 1
    if args.elastic:
        if args.resume_dp and got["dp"] != args.resume_dp:
            print(f"crashtest: restart ran dp={got['dp']}, wanted "
                  f"{args.resume_dp}", file=sys.stderr)
            return 1
        if set(ref["params"]) != set(got["params"]):
            print("crashtest: PARAM KEY SETS DIFFER", file=sys.stderr)
            return 1
        for name in ref["params"]:
            if not np.array_equal(ref["params"][name],
                                  got["params"][name]):
                print(f"crashtest: PARAM {name} DIVERGED", file=sys.stderr)
                return 1
        if set(ref["opt"]) != set(got["opt"]):
            print("crashtest: OPT STATE KEY SETS DIFFER", file=sys.stderr)
            return 1
        for name in ref["opt"]:
            # leaf-count check first: a restart that silently DROPPED the
            # optimizer state must not pass via an empty zip()
            if len(ref["opt"][name]) != len(got["opt"].get(name, [])):
                print(f"crashtest: OPT STATE {name} leaf count differs "
                      f"({len(ref['opt'][name])} vs "
                      f"{len(got['opt'].get(name, []))})", file=sys.stderr)
                return 1
            for i, (a, b) in enumerate(zip(ref["opt"][name],
                                           got["opt"][name])):
                if not np.array_equal(a, b):
                    print(f"crashtest: OPT STATE {name}[{i}] DIVERGED",
                          file=sys.stderr)
                    return 1
        print(f"crashtest: elastic parity OK over {args.steps} steps "
              f"(kill at {kill_at}, dp {args.dp} -> "
              f"{args.resume_dp or args.dp}, params + optimizer state "
              f"bit-exact)")
        return 0
    if not np.allclose(ref["w"], got["w"], rtol=0, atol=0):
        print("crashtest: FINAL PARAMS DIVERGED", file=sys.stderr)
        print(" ref:", ref["w"][:4], file=sys.stderr)
        print(" got:", got["w"][:4], file=sys.stderr)
        return 1
    print(f"crashtest: parity OK over {args.steps} steps "
          f"(kill at {kill_at}, ckpt every {args.ckpt_every})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
