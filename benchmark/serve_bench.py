"""Serving load generator: closed-loop A/B and open-loop Poisson sweeps.

Measures the request-level throughput/latency win of `mx.serve`'s dynamic
batcher over the capability the repo had before it — single-shot
`ExportedModel.run` calls serialized one request at a time (the reference's
c_predict_api contract: one predictor handle, one request, one forward).

Closed loop (the PR-3 A/B): `--concurrency` client threads each submitting
one sample at a time as fast as replies come back.

  serial    one bs-1 exported program; requests execute one at a time
            (lock-serialized, the pre-serve deployment story)
  batched   serve.Server over power-of-two batch buckets: concurrent
            requests coalesce into padded bucket batches, one compiled
            program per bucket

Open loop (`--open-loop`): a Poisson arrival process at each offered rate
in `--rates` — arrivals are SAMPLED (seeded exponential gaps) and sent on
schedule whether or not earlier requests have completed, which is what
real fleet traffic does and what closed-loop clients structurally cannot
show: past the saturation knee a closed loop self-throttles to the
server's pace, while the open loop exposes the latency blow-up and the
drop rate. The sweep emits a p50/p99/p999-vs-offered-rate curve, per-rate
drop accounting (rejects/sheds/timeouts), and a detected saturation knee
(`knee_rps` = the largest offered rate the server still tracks:
achieved >= 85% of offered — the drain-inclusive wall carries tail
noise — AND p99 within 3x of the lightest rate's AND drops <= 1%).
`--rates auto` calibrates a short closed-loop run first and sweeps
0.3x..2.6x around it (the closed loop underestimates open-loop
capacity, so the sweep must extend well past 1x to cross the knee).
The committed sweep lives in benchmark/results/serve_openloop_r13.json.

Autoregressive mode (`--autoregressive`, ISSUE 14): continuous
(iteration-level) batching vs the PR-3 static batcher on the SAME
decoder math — per-request token counts are heavy-tailed (truncated
exponential), so the static batcher pays its structural worst case
(every batch row decodes t_max steps; TTFT = whole-reply latency) while
`serve.ContinuousEngine` admits/retires per iteration. Reports decode
tokens/s, TTFT/TPOT p50/p99, the zero-retrace assertion, and the
`MXNET_COMPILE_CACHE_DIR` warm-replica compile skip; with `--open-loop`,
a Poisson TTFT-vs-offered-rate sweep of the engine. Committed artifact:
benchmark/results/serve_continuous_r14.json.

Model: ResNet-18 (thumbnail stem, NCHW, 32x32) exported per bucket; --quick
swaps in a small MLP and shorter runs for the CI smoke. Writes a JSON
artifact; the committed closed-loop before/after pair lives in
benchmark/results/serve_r07_{before,after}.json.

Usage:
  python benchmark/serve_bench.py                          # both modes, table + JSON
  python benchmark/serve_bench.py --quick --out /tmp/s.json
  python benchmark/serve_bench.py --modes serial           # baseline only
  python benchmark/serve_bench.py --open-loop --rates auto # Poisson sweep
  python benchmark/serve_bench.py --open-loop --rates 20,40,80,160
  python benchmark/serve_bench.py --autoregressive          # continuous A/B
  python benchmark/serve_bench.py --autoregressive --open-loop --rates auto
"""
import argparse
import json
import os
import statistics
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Host-side serving benchmark: force CPU before jax initializes (same recipe
# as dispatch_bench.py).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np


def _platform():
    """The platform the numbers were taken on, as jax reports it — never
    a literal, so a CPU number cannot carry a device's name."""
    import jax
    return jax.devices()[0].platform


def _percentiles(lat_ms):
    lat = sorted(lat_ms)
    from incubator_mxnet_tpu.serve.metrics import percentile
    out = {}
    for q in (50, 95, 99):
        v = percentile(lat, q)     # None when nothing completed in-window
        out[f"p{q}_ms"] = round(v, 3) if v is not None else None
    return out


def _build_and_export(quick, workdir):
    """Export the bench model once per bucket; returns (BucketedModel,
    sample factory, bucket list)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import serve
    from incubator_mxnet_tpu.gluon import nn

    if quick:
        buckets = [1, 2, 4, 8]
        net = nn.HybridSequential()
        net.add(nn.Dense(64, activation="relu", in_units=32),
                nn.Dense(10))
        net.initialize()
        net.hybridize()
        sample_shape = (32,)
        name = "mlp"
    else:
        from incubator_mxnet_tpu.gluon.model_zoo import vision
        buckets = [1, 2, 4, 8, 16, 32]
        net = vision.resnet18_v1(classes=10, thumbnail=True)
        net.initialize()
        net.hybridize()
        sample_shape = (3, 32, 32)
        name = "resnet18"

    model = serve.BucketedModel.export_block(
        net, sample_shape, buckets, workdir, name=name)
    rng = np.random.RandomState(7)
    pool = [rng.rand(*sample_shape).astype(np.float32) for _ in range(64)]

    def sample(i):
        return pool[i % len(pool)]

    return model, sample, buckets


def _drive(submit_fn, sample, concurrency, duration_s, warmup_s=0.5):
    """Closed-loop load: each client thread submits-and-waits in a loop.
    Returns (completed, wall_s, latencies_ms, error_counts).

    Only requests that start AND finish inside the measured window count —
    warmup-started requests and in-flight stragglers completing after
    stop would otherwise inflate requests/s (by up to `concurrency`
    completions, double-digit percent at short durations) and pollute the
    percentiles."""
    stop = threading.Event()
    lat_lock = threading.Lock()
    lats, errors = [], {}
    window = [float("inf"), float("-inf")]     # [start, end), set post-warmup

    def client(tid):
        i = tid
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                submit_fn(sample(i))
            except Exception as e:
                with lat_lock:
                    k = type(e).__name__
                    errors[k] = errors.get(k, 0) + 1
                time.sleep(0.001)
                continue
            finally:
                i += concurrency
            t1 = time.perf_counter()
            if t0 >= window[0] and t1 <= window[1]:
                with lat_lock:
                    lats.append((t1 - t0) * 1e3)

    threads = [threading.Thread(target=client, args=(t,), daemon=True)
               for t in range(concurrency)]
    for t in threads:
        t.start()
    time.sleep(warmup_s)
    t_start = time.perf_counter()
    window[0] = t_start
    window[1] = t_start + duration_s
    time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    return len(lats), duration_s, lats, errors


def bench_serial(model_bs1, sample, concurrency, duration_s):
    """Serial batch-1 serving: the pre-serve deployment path. One exported
    bs-1 program, one request at a time (the predictor's single-shot
    contract is not concurrent — a lock stands in for the request queue
    callers would have to build themselves)."""
    lock = threading.Lock()

    def submit(x):
        with lock:
            return model_bs1.run(x[None])

    model_bs1.warmup()
    done, wall, lats, errors = _drive(submit, sample, concurrency, duration_s)
    out = {"mode": "serial", "requests_per_sec": round(done / wall, 2),
           "completed": done, "wall_s": round(wall, 2), "errors": errors}
    out.update(_percentiles(lats))
    return out


def bench_batched(model, sample, concurrency, duration_s, batch_timeout_ms):
    from incubator_mxnet_tpu import serve
    with serve.Server(model, batch_timeout_ms=batch_timeout_ms,
                      max_queue=max(256, 8 * concurrency)) as srv:
        ccs_warm = model.compile_cache_size()

        def submit(x):
            return srv.predict(x, timeout=60)

        done, wall, lats, errors = _drive(submit, sample, concurrency,
                                          duration_s)
        st = srv.stats()
    out = {"mode": "batched", "requests_per_sec": round(done / wall, 2),
           "completed": done, "wall_s": round(wall, 2), "errors": errors,
           "batch_occupancy": st["batch_occupancy"],
           "batches": st["batches"],
           "programs_compiled": st["programs_compiled"],
           "compile_cache_size_after_warmup": ccs_warm,
           "compile_cache_size_final": st["compile_cache_size"],
           "queue_depth_max": st["queue_depth_max"]}
    out.update(_percentiles(lats))
    return out


def _percentile_of(lat_sorted, q):
    from incubator_mxnet_tpu.serve.metrics import percentile
    v = percentile(lat_sorted, q)
    return round(v, 3) if v is not None else None


def bench_open_loop_at(srv, sample, rate, duration_s, seed=11):
    """One offered rate: Poisson arrivals (seeded exponential gaps) sent
    ON SCHEDULE — the submitter never waits for replies. Latency is
    measured from each request's SCHEDULED arrival (late dispatch counts
    against the server's tail, the open-loop convention). Returns the
    per-rate row: achieved rate, p50/p99/p999, drop accounting."""
    import numpy as np
    import threading as _th
    rng = np.random.RandomState(int(seed * 100003 + rate))
    n = max(8, int(round(rate * duration_s)))
    gaps = rng.exponential(1.0 / rate, size=n)
    lock = _th.Lock()
    lats, drops = [], {}
    futures = []
    late = 0
    t0 = time.perf_counter()
    arrival = t0
    for i in range(n):
        arrival += gaps[i]
        now = time.perf_counter()
        if arrival > now:
            time.sleep(arrival - now)
        else:
            late += 1
        t_arr = arrival

        try:
            fut = srv.submit(sample(i))
        except Exception as e:
            with lock:
                k = type(e).__name__
                drops[k] = drops.get(k, 0) + 1
            continue

        def _done(f, t_arr=t_arr):
            t1 = time.perf_counter()
            try:
                f.result()
            except Exception as e:
                with lock:
                    k = type(e).__name__
                    drops[k] = drops.get(k, 0) + 1
            else:
                with lock:
                    lats.append((t1 - t_arr) * 1e3)

        fut.add_done_callback(_done)
        futures.append(fut)
    # drain in-flight stragglers (bounded: a wedged server must not hang
    # the sweep). Past the shared deadline, remaining futures are only
    # POLLED — waiting even 0.1s each would turn a wedged server into
    # O(0.1s x n_requests) of stall
    deadline = time.perf_counter() + max(30.0, 2 * duration_s)
    for f in futures:
        remaining = deadline - time.perf_counter()
        if remaining <= 0:
            break
        try:
            f.result(timeout=remaining)
        except Exception:
            pass
    wall = time.perf_counter() - t0
    with lock:
        lat_sorted = sorted(lats)
        drops_by = dict(drops)
    completed = len(lat_sorted)
    dropped = sum(drops_by.values())
    # every request resolves into exactly one of lats/drops, so the
    # undrained count is DERIVED from one consistent snapshot — counting
    # not-done futures separately could double-count a request that
    # completed between the poll and the snapshot
    undrained = max(0, n - completed - dropped)
    # achieved over the FULL wall including the drain: past saturation the
    # backlog stretches the wall, so achieved falls below offered — the
    # signal knee detection needs (dividing by duration_s alone would let
    # drain-window completions mask saturation as perfect goodput)
    row = {"offered_rps": round(float(rate), 2), "sent": n,
           "completed": completed,
           "achieved_rps": round(completed / wall, 2),
           "dropped": dropped, "drops_by_kind": drops_by,
           "drop_rate": round(dropped / n, 4),
           "late_arrivals": late, "undrained": undrained,
           "wall_s": round(wall, 2),
           "p50_ms": _percentile_of(lat_sorted, 50),
           "p99_ms": _percentile_of(lat_sorted, 99),
           "p999_ms": _percentile_of(lat_sorted, 99.9)}
    return row


def detect_knee(rows, goodput_floor=0.85, p99_blowup=3.0,
                drop_ceiling=0.01):
    """Saturation knee over a monotone offered-rate sweep: the largest
    offered rate where the server still TRACKS the load —

      achieved >= `goodput_floor` x offered  (achieved divides by the
          drain-inclusive wall, which carries ~5-10% of latency-tail and
          arrival-process noise even when healthy — hence 0.85, not 0.95;
          a saturated rate falls WELL below it),
      p99 <= `p99_blowup` x the lightest rate's p99 (1ms floor so
          microsecond baselines don't flag noise), and
      drop_rate <= `drop_ceiling` (admission rejects = saturation).

    Also interpolates p99 at 0.8x the knee (the SLO operating point
    benchdiff trends as `serve_p99_ms_at_0p8_knee`)."""
    rows = sorted(rows, key=lambda r: r["offered_rps"])
    if not rows:
        return None
    base_p99 = next((r["p99_ms"] for r in rows
                     if r["completed"] > 0 and r["p99_ms"] is not None),
                    None)
    knee = None
    for r in rows:
        # a zero-completion rate is TOTAL saturation: it must break the
        # scan like any failing row, never be skipped over (achieved 0
        # fails the goodput floor, so no special case beyond not
        # pre-filtering it out of the sweep)
        good = r["achieved_rps"] >= goodput_floor * r["offered_rps"]
        tail_ok = (base_p99 is None or r["p99_ms"] is None
                   or r["p99_ms"] <= p99_blowup * max(base_p99, 1.0))
        drops_ok = r.get("drop_rate", 0.0) <= drop_ceiling
        if good and tail_ok and drops_ok:
            knee = r
        else:
            break
    if knee is None:
        return {"knee_rps": None, "saturated_from_first_rate": True,
                "base_p99_ms": base_p99}
    target = 0.8 * knee["offered_rps"]
    p99_at = None
    prev = None
    for r in rows:
        if r["p99_ms"] is None:
            continue
        if r["offered_rps"] >= target:
            if prev is None or r["offered_rps"] == target:
                p99_at = r["p99_ms"]
            else:
                # linear interpolation between the bracketing rates
                x0, y0 = prev["offered_rps"], prev["p99_ms"]
                x1, y1 = r["offered_rps"], r["p99_ms"]
                frac = (target - x0) / (x1 - x0) if x1 > x0 else 0.0
                p99_at = round(y0 + frac * (y1 - y0), 3)
            break
        prev = r
    if p99_at is None and prev is not None:
        p99_at = prev["p99_ms"]
    return {"knee_rps": knee["offered_rps"],
            "knee_achieved_rps": knee["achieved_rps"],
            "knee_p99_ms": knee["p99_ms"],
            "knee_drop_rate": knee["drop_rate"],
            "p99_ms_at_0p8_knee": p99_at,
            "base_p99_ms": base_p99}


def bench_open_loop(model, sample, rates, duration_s, batch_timeout_ms,
                    max_queue=256, seed=11):
    """Sweep offered load (ascending) through ONE server instance; each
    rate gets a fresh latency window. Returns (rows, knee)."""
    from incubator_mxnet_tpu import serve
    rows = []
    with serve.Server(model, batch_timeout_ms=batch_timeout_ms,
                      max_queue=max_queue) as srv:
        for rate in sorted(rates):
            row = bench_open_loop_at(srv, sample, rate, duration_s,
                                     seed=seed)
            rows.append(row)
            print(f"open-loop {row['offered_rps']:>8.1f} req/s offered"
                  f"  achieved {row['achieved_rps']:>8.1f}"
                  f"  p50 {row['p50_ms'] or 0:>7.1f}ms"
                  f"  p99 {row['p99_ms'] or 0:>8.1f}ms"
                  f"  p999 {row['p999_ms'] or 0:>8.1f}ms"
                  f"  drops {row['dropped']}")
    knee = detect_knee(rows)
    return rows, knee


def bench_trace_ab(model, sample, concurrency, pairs=8, window_s=0.75,
                   batch_timeout_ms=2.0):
    """Tracing-overhead A/B, PAIRED, at TWO operating points against the
    same MXNET_TELEMETRY=0 baseline:

      default   MXNET_TELEMETRY=1, nothing else — the shipped default.
                No collector is armed, so the request path pays only the
                collector check (trace.request_root -> None). This is
                the ≤2% GUARDED number: the tracing layer as shipped.
      sampled   MXNET_TELEMETRY=1 + MXNET_TRACE_SAMPLE=1.0 — a
                collector armed, EVERY request minting a root, feeding
                the slowest table its trace id, and recording the
                serve.batch lane. Reported (serve_trace_sampled_*), not
                guarded: full per-request tracing costs real work
                (~10us/request here ≈ several % on this 100us-request
                microbench; amortizes to <0.5% on ms-scale models) and
                head-sampling scales it linearly — that is what
                MXNET_TRACE_SAMPLE is for.

    Methodology: one server, one continuously running closed-loop
    client pool, the env toggled between interleaved windows (the
    tracing layer re-reads it per call). Separate-process A/B runs on a
    shared host carry ±10% run-to-run noise — far above the effects
    measured. Robustness comes from pairing: each adjacent window pair
    yields one overhead sample (a host-noise burst hits ONE pair, whose
    windows share its regime), pair order alternates
    traced-first/untraced-first so intra-pair drift cancels, and the
    reported overhead is the MEDIAN over pairs. Restores both env knobs
    on exit."""
    import statistics
    from incubator_mxnet_tpu import serve
    from incubator_mxnet_tpu.telemetry import trace as _trace

    stop = threading.Event()
    lk = threading.Lock()
    n_done = [0]

    def set_mode(mode):
        if mode == "off":
            os.environ["MXNET_TELEMETRY"] = "0"
            os.environ.pop("MXNET_TRACE_SAMPLE", None)
        elif mode == "default":
            os.environ["MXNET_TELEMETRY"] = "1"
            os.environ.pop("MXNET_TRACE_SAMPLE", None)
        else:                                   # "sampled"
            os.environ["MXNET_TELEMETRY"] = "1"
            os.environ["MXNET_TRACE_SAMPLE"] = "1.0"
        _trace._expire_env_memo()   # TTL cache: take effect NOW

    def paired_windows(mode):
        """pairs x (mode vs off), alternating order; median overhead."""
        order = []
        for p in range(pairs):
            order += [mode, "off"] if p % 2 == 0 else ["off", mode]
        rates = []
        for m in order:
            set_mode(m)
            with lk:
                a = n_done[0]
            time.sleep(window_s)
            with lk:
                b = n_done[0]
            rates.append((m, (b - a) / window_s))
        overheads = []
        for p in range(pairs):
            (m0, r0), (m1, r1) = rates[2 * p], rates[2 * p + 1]
            tr = r0 if m0 == mode else r1
            un = r1 if m0 == mode else r0
            if un > 0:
                overheads.append((un - tr) / un * 100.0)
        on_med = statistics.median(r for m, r in rates if m == mode)
        off_med = statistics.median(r for m, r in rates if m == "off")
        med = round(statistics.median(overheads), 2) if overheads \
            else None
        return on_med, off_med, med, [round(o, 2) for o in overheads]

    saved = {k: os.environ.get(k)
             for k in ("MXNET_TELEMETRY", "MXNET_TRACE_SAMPLE")}
    with serve.Server(model, batch_timeout_ms=batch_timeout_ms,
                      max_queue=max(256, 8 * concurrency)) as srv:
        def client(tid):
            i = tid
            while not stop.is_set():
                try:
                    srv.predict(sample(i), timeout=60)
                except Exception:
                    time.sleep(0.001)
                else:
                    with lk:
                        n_done[0] += 1
                i += concurrency

        threads = [threading.Thread(target=client, args=(t,), daemon=True)
                   for t in range(concurrency)]
        for t in threads:
            t.start()
        time.sleep(1.0)                      # shared warmup
        try:
            d_on, d_off, d_med, d_pairs = paired_windows("default")
            s_on, s_off, s_med, s_pairs = paired_windows("sampled")
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            _trace._expire_env_memo()
            # stop the clients on the error path too: an exception here
            # closes the server, and 32 daemon threads busy-looping
            # predict -> ServerClosed would burn CPU through teardown
            stop.set()
            for t in threads:
                t.join(timeout=10)
    return {"serve_traced_requests_per_sec": round(d_on, 1),
            "serve_untraced_requests_per_sec": round(d_off, 1),
            "serve_trace_overhead_pct": d_med,
            "serve_trace_overhead_ok": (d_med is not None
                                        and d_med <= 2.0),
            "serve_trace_sampled_requests_per_sec": round(s_on, 1),
            "serve_trace_sampled_overhead_pct": s_med,
            "trace_ab_pairs": pairs,
            "trace_ab_pair_overheads_pct": d_pairs,
            "trace_ab_sampled_pair_overheads_pct": s_pairs}


# ---------------------------------------------------------------------------
# autoregressive serving: continuous (iteration-level) batching vs the PR-3
# static batcher on the SAME model math (ISSUE 14)
# ---------------------------------------------------------------------------
def _build_autoreg(quick):
    """Decoder config + a seeded workload of (prompt, max_new) pairs.

    Generation lengths are HEAVY-TAILED (truncated exponential — the
    fleet-realistic shape: most replies short, a tail of long ones).
    `t_max` is the static batcher's obligatory worst case: a static
    batch cannot retire a row early, so every member decodes to the
    longest request the service accepts, and the tail sets the bill for
    everyone — exactly the structural cost iteration-level batching
    removes."""
    from incubator_mxnet_tpu import serve
    if quick:
        cfg = serve.DecoderConfig(vocab=128, embed=32, layers=2, heads=4,
                                  head_dim=8, max_len=48)
        max_prompt, n_work = 12, 64
        new_lo, new_scale = 2, 8
    else:
        cfg = serve.DecoderConfig(vocab=256, embed=64, layers=3, heads=4,
                                  head_dim=16, max_len=96)
        max_prompt, n_work = 16, 256
        new_lo, new_scale = 4, 20
    t_max = cfg.max_len - max_prompt
    model = serve.CachedDecoder(cfg, seed=7)
    rng = np.random.RandomState(23)
    workload = []
    for _ in range(n_work):
        plen = int(rng.randint(3, max_prompt + 1))
        max_new = new_lo + min(int(rng.exponential(new_scale)),
                               t_max - new_lo)
        workload.append((
            rng.randint(1, cfg.vocab, size=plen).astype(np.int32),
            max_new))
    return model, workload, max_prompt, t_max


def _make_static_generate(model, max_prompt, t_max):
    """The static-batching baseline's callable: prefill + a fixed
    `t_max`-step `lax.scan` decode over an in-program KV cache, using the
    SAME compiled math as the continuous engine (serve.continuous's
    prefill/decode builders), so the A/B measures the SCHEDULER, not the
    model. Every batch row decodes all t_max steps — the structural
    static-batching waste (rows wanting fewer tokens still pay t_max;
    pad rows pay it too)."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.serve.continuous import (_make_prefill,
                                                      _make_decode)
    cfg = model.config
    # same windowed prefill as the engine (fair A/B: both sides pay
    # O(max_prompt^2) prefill attention, not O(max_len^2))
    prefill = _make_prefill(cfg, window=max_prompt)
    decode = _make_decode(cfg)
    params = model.params

    def gen(prompts, plens):
        # prompts (B, max_prompt) int32, plens (B,) int32
        B = prompts.shape[0]
        shape = (B + 1, cfg.layers, cfg.max_len, cfg.heads, cfg.head_dim)
        k = jnp.zeros(shape, dtype=cfg.dtype)
        v = jnp.zeros(shape, dtype=cfg.dtype)
        plens = jnp.maximum(plens, 1)       # pad rows: keep math benign
        # greedy lanes: temp 0 / full vocab / p=1, keys unused
        temps = jnp.zeros((B,), dtype=jnp.float32)
        top_ks = jnp.zeros((B,), dtype=jnp.int32)
        top_ps = jnp.ones((B,), dtype=jnp.float32)
        keys = jnp.zeros((B, 2), dtype=jnp.uint32)
        k, v, logits = prefill(params, k, v, prompts, plens,
                               jnp.arange(B))
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def step(carry, _):
            k, v, last, lens = carry
            k, v, toks, _ = decode(params, k, v, last, lens,
                                   jnp.ones((B,), dtype=jnp.int32),
                                   temps, top_ks, top_ps, keys)
            nxt = toks[0]
            return (k, v, nxt, lens + 1), nxt

        (_, _, _, _), rest = jax.lax.scan(
            step, (k, v, first, plens), None, length=t_max - 1)
        return jnp.concatenate([first[:, None], rest.T], axis=1)

    return gen


def _drive_autoreg(submit_fn, workload, concurrency, duration_s,
                   warmup_s=1.0):
    """Closed-loop autoregressive load: `concurrency` clients each
    running one request at a time. `submit_fn(i)` blocks until request
    i's tokens arrive and returns the USEFUL token count (what the
    client asked for). Returns (completed, tokens, lats_ms, errors) for
    requests fully inside the measured window."""
    stop = threading.Event()
    lk = threading.Lock()
    lats, errors, tokens = [], {}, [0]
    window = [float("inf"), float("-inf")]

    def client(tid):
        i = tid
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                n_tok = submit_fn(i)
            except Exception as e:
                with lk:
                    k = type(e).__name__
                    errors[k] = errors.get(k, 0) + 1
                time.sleep(0.001)
                continue
            finally:
                i += concurrency
            t1 = time.perf_counter()
            if t0 >= window[0] and t1 <= window[1]:
                with lk:
                    lats.append((t1 - t0) * 1e3)
                    tokens[0] += n_tok

    threads = [threading.Thread(target=client, args=(t,), daemon=True)
               for t in range(concurrency)]
    for t in threads:
        t.start()
    time.sleep(warmup_s)
    t_start = time.perf_counter()
    window[0] = t_start
    window[1] = t_start + duration_s
    time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    return len(lats), tokens[0], lats, errors


def bench_autoreg_static(model, workload, max_prompt, t_max, concurrency,
                         duration_s, batch_timeout_ms):
    """The PR-3 static batcher serving the autoregressive model: one
    request = one full generation, batched onto power-of-two buckets.
    TTFT == total latency (all tokens arrive at once) and every batch
    row pays t_max decode steps — the two structural costs continuous
    batching removes."""
    from incubator_mxnet_tpu import serve
    buckets = [1, 2, 4, 8] if t_max <= 16 else [1, 2, 4, 8, 16, 32]
    cm = serve.CallableModel(
        _make_static_generate(model, max_prompt, t_max), buckets,
        [((max_prompt,), "int32"), ((), "int32")])
    with serve.Server(cm, batch_timeout_ms=batch_timeout_ms,
                      max_queue=max(256, 8 * concurrency)) as srv:
        def submit(i):
            prompt, max_new = workload[i % len(workload)]
            row = np.zeros((max_prompt,), np.int32)
            row[:prompt.size] = prompt
            srv.predict(row, np.int32(prompt.size), timeout=120)
            return max_new           # useful tokens (rest is overrun)

        done, tokens, lats, errors = _drive_autoreg(
            submit, workload, concurrency, duration_s)
        st = srv.stats()
    lat_sorted = sorted(lats)
    out = {"mode": "static_batcher",
           "requests_per_sec": round(done / duration_s, 2),
           "decode_tokens_per_sec": round(tokens / duration_s, 2),
           "completed": done, "errors": errors,
           "t_max_steps": t_max,
           "programs_compiled": st["programs_compiled"],
           "compile_cache_size_final": st["compile_cache_size"],
           # all tokens arrive with the reply: TTFT == TPOT*n == latency
           "ttft_p50_ms": _percentile_of(lat_sorted, 50),
           "ttft_p99_ms": _percentile_of(lat_sorted, 99),
           "e2e_p50_ms": _percentile_of(lat_sorted, 50),
           "e2e_p99_ms": _percentile_of(lat_sorted, 99)}
    return out


def bench_autoreg_continuous(model, workload, concurrency, duration_s,
                             max_slots=None, max_prompt=None,
                             engine_kwargs=None):
    """The continuous engine on the same workload: per-iteration
    admit/retire, deadline-aware slot grants, zero retraces asserted.
    `engine_kwargs` reaches the ContinuousEngine constructor verbatim —
    the decode A/B passes `draft_tokens` / `kv_dtype` through it."""
    from incubator_mxnet_tpu import serve
    eng = serve.ContinuousEngine(
        model, max_slots=max_slots, prefill_window=max_prompt,
        max_queue=max(256, 8 * concurrency),
        **(engine_kwargs or {})).start()
    try:
        def submit(i):
            prompt, max_new = workload[i % len(workload)]
            out = eng.generate(prompt, max_new, timeout=120)
            return int(out.size)

        done, tokens, lats, errors = _drive_autoreg(
            submit, workload, concurrency, duration_s)
        eng.assert_no_retraces()
        st = eng.stats()
    finally:
        eng.close()
    lat_sorted = sorted(lats)
    out = {"mode": "continuous",
           "requests_per_sec": round(done / duration_s, 2),
           "decode_tokens_per_sec": round(tokens / duration_s, 2),
           "completed": done, "errors": errors,
           "max_slots": st["pool"]["max_slots"],
           "mean_active_slots": st["mean_active_slots"],
           "decode_iterations": st["decode_iterations"],
           "prefill_batches": st["prefill_batches"],
           "programs_compiled": st["programs_compiled"],
           "compile_cache_size_final": st["compile_cache_size"],
           "retraces_after_warmup": st["retraces_after_warmup"],
           "ttft_p50_ms": st["ttft_p50_ms"],
           "ttft_p99_ms": st["ttft_p99_ms"],
           "tpot_p50_ms": st["tpot_p50_ms"],
           "tpot_p99_ms": st["tpot_p99_ms"],
           "e2e_p50_ms": _percentile_of(lat_sorted, 50),
           "e2e_p99_ms": _percentile_of(lat_sorted, 99),
           "decode_steps": st["decode_steps"],
           "draft_tokens": st["draft_tokens"]}
    if st.get("draft_acceptance") is not None:
        out["draft_acceptance"] = st["draft_acceptance"]
    if engine_kwargs and engine_kwargs.get("kv_dtype"):
        out["kv_dtype"] = engine_kwargs["kv_dtype"]
    return out


def bench_sanitize_ab(quick, concurrency, duration_s, max_slots=None):
    """Runtime-sanitizer overhead A/B (ISSUE 20): the SAME quick
    autoregressive continuous workload run with `mx.sanitize` off, then
    with all three modes armed (donation poison-and-trap, retrace
    sentinel polled every wave, slot canary row). Each arm builds its
    own model so the sanitized arm's programs are actually wrapped at
    build time — exactly how `MXNET_SANITIZE` deploys. Emits
    `sanitize_overhead_pct` (benchdiff trend key, gated absolutely) and
    asserts the sanitized arm stayed silent: zero retraces, zero canary
    trips, zero donation violations on the clean loop."""
    from incubator_mxnet_tpu import sanitize, serve

    def one_arm(label):
        model, workload, max_prompt, _ = _build_autoreg(quick)
        slots = max_slots or min(32, concurrency)
        row = bench_autoreg_continuous(model, workload, concurrency,
                                       duration_s, max_slots=slots,
                                       max_prompt=max_prompt)
        row["arm"] = label
        return row

    off = one_arm("sanitize_off")
    with sanitize.scope("all"):
        on = one_arm("sanitize_all")
    sanitize.clear()
    tps_off = off["decode_tokens_per_sec"]
    tps_on = on["decode_tokens_per_sec"]
    overhead = (100.0 * (tps_off - tps_on) / tps_off if tps_off > 0
                else 0.0)
    errs = on["errors"]
    n_errs = (sum(errs.values()) if isinstance(errs, dict)
              else int(errs or 0))
    return {"sanitize_off": off, "sanitize_on": on,
            "sanitize_modes": "donation,retrace,slot",
            "sanitize_overhead_pct": round(overhead, 2),
            "sanitize_retraces": on["retraces_after_warmup"],
            "sanitize_errors": n_errs}


def bench_decode_ab(model, workload, concurrency, duration_s,
                    max_slots=None, max_prompt=None, draft=4):
    """Speculative-decoding A/B (ISSUE 17): the SAME engine/workload run
    plain vs with draft+verify waves, plus an int8-KV arm, a token-
    exactness spot check (speculation must be a pure SPEED change), the
    KV-pool density numbers, and an honest record of whether the Pallas
    paged-attention kernel served the traffic compiled (TPU) or the
    reference einsum did (CPU).

    TWO operating points, because speculative decoding's economics flip
    with batch occupancy: at SATURATION (concurrency-32 closed loop, the
    r14 operating point) a compute-bound host pays ~C× for the C-wide
    verify forward, so the wall-clock win only exists where that forward
    is memory-/overhead-bound; in the LATENCY-BOUND single-stream arm
    (concurrency 1 — the regime speculation is actually deployed in) the
    per-wave fixed cost dominates and the acceptance-weighted win is
    realized as wall-clock tokens/s on this host too."""
    from incubator_mxnet_tpu import serve
    from incubator_mxnet_tpu.ops import fused as F

    F.fused_stats(reset=True)
    plain = bench_autoreg_continuous(
        model, workload, concurrency, duration_s, max_slots=max_slots,
        max_prompt=max_prompt)
    print(f"plain     {plain['decode_tokens_per_sec']:>9.1f} tok/s  "
          f"{plain['requests_per_sec']:>7.1f} req/s  "
          f"retraces {plain['retraces_after_warmup']}")
    spec = bench_autoreg_continuous(
        model, workload, concurrency, duration_s, max_slots=max_slots,
        max_prompt=max_prompt, engine_kwargs={"draft_tokens": draft})
    spec["mode"] = "continuous_spec"
    print(f"spec k={draft} {spec['decode_tokens_per_sec']:>9.1f} tok/s  "
          f"{spec['requests_per_sec']:>7.1f} req/s  "
          f"acceptance {spec.get('draft_acceptance')}  "
          f"retraces {spec['retraces_after_warmup']}")
    spec8 = bench_autoreg_continuous(
        model, workload, concurrency, duration_s, max_slots=max_slots,
        max_prompt=max_prompt,
        engine_kwargs={"draft_tokens": draft, "kv_dtype": "int8"})
    spec8["mode"] = "continuous_spec_int8"
    print(f"spec int8 {spec8['decode_tokens_per_sec']:>9.1f} tok/s  "
          f"{spec8['requests_per_sec']:>7.1f} req/s  "
          f"acceptance {spec8.get('draft_acceptance')}  "
          f"retraces {spec8['retraces_after_warmup']}")
    out = {"plain": plain, "spec": spec, "spec_int8": spec8}
    if plain["decode_tokens_per_sec"]:
        out["serve_decode_saturation_speedup_spec"] = round(
            spec["decode_tokens_per_sec"]
            / plain["decode_tokens_per_sec"], 2)
        out["serve_decode_saturation_speedup_spec_int8"] = round(
            spec8["decode_tokens_per_sec"]
            / plain["decode_tokens_per_sec"], 2)
    # acceptance-weighted speedup: tokens emitted per verify forward —
    # the C-independent-cost (memory-bound accelerator) ceiling
    if spec.get("draft_acceptance") is not None:
        out["serve_decode_tokens_per_verify_wave"] = round(
            1.0 + draft * spec["draft_acceptance"], 2)

    # latency-bound arm: single-stream generation, where the per-wave
    # fixed cost dominates and speculation pays off in wall-clock
    lat_plain = bench_autoreg_continuous(
        model, workload, 1, duration_s, max_slots=1,
        max_prompt=max_prompt)
    lat_spec = bench_autoreg_continuous(
        model, workload, 1, duration_s, max_slots=1,
        max_prompt=max_prompt, engine_kwargs={"draft_tokens": draft})
    lat_spec["mode"] = "continuous_spec"
    out["latency_plain"] = lat_plain
    out["latency_spec"] = lat_spec
    print(f"single-stream plain {lat_plain['decode_tokens_per_sec']:>8.1f}"
          f" tok/s   spec {lat_spec['decode_tokens_per_sec']:>8.1f} tok/s"
          f"  acceptance {lat_spec.get('draft_acceptance')}")
    if lat_plain["decode_tokens_per_sec"]:
        out["serve_decode_speedup_spec"] = round(
            lat_spec["decode_tokens_per_sec"]
            / lat_plain["decode_tokens_per_sec"], 2)

    # token-exactness spot check: the speculative engine must emit the
    # byte-identical tokens the scheduling-free plain reference does
    eng = serve.ContinuousEngine(
        model, max_slots=max_slots, prefill_window=max_prompt,
        draft_tokens=draft).start()
    exact, checked = True, 0
    try:
        for prompt, max_new in workload[:8]:
            got = eng.generate(prompt, max_new, timeout=120)
            ref = model.reference_generate(prompt, max_new,
                                           window=max_prompt)
            checked += 1
            if not np.array_equal(got, ref):
                exact = False
                break
    finally:
        eng.close()
    out["spec_token_exact"] = exact
    out["spec_token_exact_checked"] = checked
    print(f"token-exact spot check: {checked} prompts "
          f"{'OK' if exact else 'DIVERGED'}")

    # KV density: int8 codes + per-position f32 scales vs the f32 slab
    p32 = model.new_pool(max_slots=max_slots or 4)
    p8 = model.new_pool(max_slots=max_slots or 4, dtype="int8")
    out["kv_slots_per_gb"] = {
        "float32": p32.slots_per_gb(), "int8": p8.slots_per_gb(),
        "ratio": round(p8.slots_per_gb() / p32.slots_per_gb(), 2)}
    print(f"kv slots/GB: f32 {out['kv_slots_per_gb']['float32']}  "
          f"int8 {out['kv_slots_per_gb']['int8']}  "
          f"({out['kv_slots_per_gb']['ratio']}x)")

    # honesty stamp: did the Pallas kernel actually trace into the
    # programs that served this traffic, or did the reference einsum?
    fs = F.fused_stats()
    out["paged_pallas_active"] = fs.get("pallas_calls", 0) > 0
    out["fused_stats"] = {
        k: fs.get(k, 0) for k in ("paged_attention_calls",
                                  "pallas_calls", "fallback_calls")}
    return out


def bench_autoreg_open_loop(model, workload, rates, duration_s, seed=11,
                            max_slots=None, max_prompt=None):
    """Open-loop Poisson sweep against the continuous engine (the PR-13
    arrival generator aimed at the autoregressive path): per offered
    rate — achieved req/s, decode tokens/s, TTFT/TPOT p50/p99, drop
    accounting. A fresh engine per rate gives clean per-rate reservoirs;
    the model's jit cache is shared, so no recompiles."""
    from incubator_mxnet_tpu import serve
    rows = []
    for rate in sorted(rates):
        eng = serve.ContinuousEngine(model, max_slots=max_slots,
                                     prefill_window=max_prompt,
                                     max_queue=512).start()
        try:
            rng = np.random.RandomState(int(seed * 100003 + rate))
            n = max(8, int(round(rate * duration_s)))
            gaps = rng.exponential(1.0 / rate, size=n)
            lk = threading.Lock()
            lats, drops = [], {}
            futures = []
            t0 = time.perf_counter()
            arrival = t0
            for i in range(n):
                arrival += gaps[i]
                now = time.perf_counter()
                if arrival > now:
                    time.sleep(arrival - now)
                prompt, max_new = workload[i % len(workload)]
                t_arr = arrival
                try:
                    fut = eng.submit(prompt, max_new)
                except Exception as e:
                    with lk:
                        k = type(e).__name__
                        drops[k] = drops.get(k, 0) + 1
                    continue

                def _done(f, t_arr=t_arr):
                    t1 = time.perf_counter()
                    try:
                        f.result()
                    except Exception as e:
                        with lk:
                            k = type(e).__name__
                            drops[k] = drops.get(k, 0) + 1
                    else:
                        with lk:
                            lats.append((t1 - t_arr) * 1e3)

                fut.add_done_callback(_done)
                futures.append(fut)
            deadline = time.perf_counter() + max(30.0, 2 * duration_s)
            for f in futures:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                try:
                    f.result(timeout=remaining)
                except Exception:
                    pass
            wall = time.perf_counter() - t0
            eng.assert_no_retraces()
            st = eng.stats()
        finally:
            eng.close()
        with lk:
            lat_sorted = sorted(lats)
            drops_by = dict(drops)
        dropped = sum(drops_by.values())
        row = {"offered_rps": round(float(rate), 2), "sent": n,
               "completed": len(lat_sorted),
               "achieved_rps": round(len(lat_sorted) / wall, 2),
               "decode_tokens_per_sec": round(
                   st["decode_tokens"] / wall, 2),
               "dropped": dropped, "drops_by_kind": drops_by,
               "drop_rate": round(dropped / n, 4),
               "mean_active_slots": st["mean_active_slots"],
               "ttft_p50_ms": st["ttft_p50_ms"],
               "ttft_p99_ms": st["ttft_p99_ms"],
               "tpot_p50_ms": st["tpot_p50_ms"],
               "tpot_p99_ms": st["tpot_p99_ms"],
               "e2e_p50_ms": _percentile_of(lat_sorted, 50),
               "e2e_p99_ms": _percentile_of(lat_sorted, 99),
               "wall_s": round(wall, 2)}
        rows.append(row)
        print(f"autoreg open-loop {row['offered_rps']:>7.1f} req/s "
              f"offered  achieved {row['achieved_rps']:>7.1f}  "
              f"tok/s {row['decode_tokens_per_sec']:>8.1f}  "
              f"ttft p99 {row['ttft_p99_ms'] or 0:>8.1f}ms  "
              f"drops {dropped}")
    return rows


_CACHE_CONFIG = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")


def bench_compile_cache_skip(quick):
    """Warm-replica start: with the persistent compilation cache on, build
    an engine (cold — compiles AND serializes both programs), then drop
    jax's in-memory caches (what a fresh replica process starts without)
    and build it again — the second warmup deserializes from the
    persistent cache instead of recompiling. Reports both warmup times;
    the acceptance is warm << cold. The experiment needs an EMPTY cache,
    so it points jax at a private directory for its duration and then
    restores exactly the configuration it found (an externally placed
    JAX_COMPILATION_CACHE_DIR included)."""
    import tempfile
    import jax
    from jax.experimental.compilation_cache import compilation_cache as _cc
    from incubator_mxnet_tpu import serve
    from incubator_mxnet_tpu import deploy

    cfg = (serve.DecoderConfig(vocab=128, embed=32, layers=2, heads=4,
                               head_dim=8, max_len=40) if quick else
           serve.DecoderConfig(vocab=256, embed=64, layers=3, heads=4,
                               head_dim=16, max_len=80))
    out = {}
    # arm the process's own configuration first, so the CachedDecoder
    # constructors below find nothing left to re-point
    deploy.maybe_enable_compile_cache()
    found = {k: getattr(jax.config, k) for k in _CACHE_CONFIG}
    with tempfile.TemporaryDirectory(prefix="mx_compile_cache_") as d:
        try:
            jax.config.update("jax_compilation_cache_dir", d)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0)
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes", -1)
            _cc.reset_cache()
            model = serve.CachedDecoder(cfg, seed=5)
            eng = serve.ContinuousEngine(model, max_slots=4).start()
            eng.close()
            out["compile_cache_cold_warmup_s"] = eng.warmup_s
            out["compile_cache_entries"] = len(os.listdir(d))
            # a fresh replica's state: no in-memory jit cache, same
            # persistent dir
            jax.clear_caches()
            model2 = serve.CachedDecoder(cfg, seed=5)
            eng2 = serve.ContinuousEngine(model2, max_slots=4).start()
            eng2.close()
            out["compile_cache_warm_warmup_s"] = eng2.warmup_s
            if eng2.warmup_s and eng2.warmup_s > 0:
                out["serve_compile_cache_warm_speedup"] = round(
                    eng.warmup_s / eng2.warmup_s, 2)
        finally:
            for k, v in found.items():
                jax.config.update(k, v)
            _cc.reset_cache()
    return out


# ---------------------------------------------------------------------------
# shared-prefix prefill A/B + chunked-prefill interference (ISSUE 19)
# ---------------------------------------------------------------------------
def _build_shared_prefix(quick):
    """N system prompts × M users: every request is one of `n_prefix`
    shared prefixes plus a short per-user suffix. The shared prefix
    spans MULTIPLE prefill windows — the production shape (system
    prompts are long; the per-wave window is sized for admission
    latency) and the one where reuse pays: a cold request needs
    ceil(plen/window) prefill waves, a hit needs one row copy plus a
    single suffix chunk. Returns (model, workload, block, window,
    n_prefix)."""
    from incubator_mxnet_tpu import serve
    if quick:
        cfg = serve.DecoderConfig(vocab=128, embed=32, layers=2, heads=4,
                                  head_dim=8, max_len=48)
        block, n_prefix, n_work, window = 8, 3, 48, 16
        shared_blocks = 4               # 32-token system prompt, 2 windows
    else:
        cfg = serve.DecoderConfig(vocab=256, embed=64, layers=3, heads=4,
                                  head_dim=16, max_len=128)
        block, n_prefix, n_work, window = 16, 4, 128, 32
        shared_blocks = 6               # 96-token system prompt, 3 windows
    model = serve.CachedDecoder(cfg, seed=7)
    rng = np.random.RandomState(31)
    shared = [rng.randint(1, cfg.vocab,
                          size=shared_blocks * block).astype(np.int32)
              for _ in range(n_prefix)]
    workload = []
    for i in range(n_work):
        sfx = rng.randint(1, cfg.vocab,
                          size=int(rng.randint(2, block))).astype(np.int32)
        prompt = np.concatenate([shared[i % n_prefix], sfx])
        workload.append((prompt, int(rng.randint(2, 5))))
    return model, workload, block, window, n_prefix, shared_blocks * block


def bench_prefill_ab(model, workload, block, window, n_prefix,
                     concurrency, duration_s):
    """Cache-on vs cache-off on the shared-prefix workload: identical
    engine, model, and compiled math — the only delta is
    `prefix_cache_slots`. The headline metric is PROMPT tokens ingested
    per second (client-side: every completed request bills its full
    prompt length, however the engine produced the KV), because that is
    what prefix reuse actually buys; the engine-side
    `prefill_cached_token_share` says how it was bought."""
    from incubator_mxnet_tpu import serve

    def run_arm(slots):
        eng = serve.ContinuousEngine(
            model, max_slots=8, prefill_window=window,
            prefix_cache_slots=slots, prefix_block=block,
            max_queue=max(256, 8 * concurrency)).start()
        try:
            def submit(i):
                prompt, max_new = workload[i % len(workload)]
                eng.generate(prompt, max_new, timeout=120)
                return int(prompt.size)     # bill PROMPT tokens ingested

            done, ptoks, lats, errors = _drive_autoreg(
                submit, workload, concurrency, duration_s)
            eng.assert_no_retraces()
            st = eng.stats()
        finally:
            eng.close()
        lat_sorted = sorted(lats)
        row = {"prefix_cache_slots": slots,
               "requests_per_sec": round(done / duration_s, 2),
               "prefill_tokens_per_sec": round(ptoks / duration_s, 2),
               "completed": done, "errors": errors,
               "ttft_p50_ms": st["ttft_p50_ms"],
               "ttft_p99_ms": st["ttft_p99_ms"],
               "e2e_p50_ms": _percentile_of(lat_sorted, 50),
               "e2e_p99_ms": _percentile_of(lat_sorted, 99),
               "programs_compiled": st["programs_compiled"],
               "retraces_after_warmup": st["retraces_after_warmup"]}
        if slots:
            row["prefix_hit_rate"] = st.get("prefix_hit_rate")
            row["prefill_cached_token_share"] = st.get(
                "prefill_cached_token_share")
            row["prefix_cache"] = st.get("prefix_cache")
        return row

    off = run_arm(0)
    print(f"cache off {off['prefill_tokens_per_sec']:>9.1f} prompt tok/s"
          f"  {off['requests_per_sec']:>7.1f} req/s  "
          f"ttft p50 {off['ttft_p50_ms'] or 0:.1f}ms  "
          f"retraces {off['retraces_after_warmup']}")
    on = run_arm(n_prefix + 1)
    print(f"cache on  {on['prefill_tokens_per_sec']:>9.1f} prompt tok/s"
          f"  {on['requests_per_sec']:>7.1f} req/s  "
          f"ttft p50 {on['ttft_p50_ms'] or 0:.1f}ms  "
          f"cached share {on.get('prefill_cached_token_share')}  "
          f"retraces {on['retraces_after_warmup']}")
    out = {"cache_off": off, "cache_on": on}
    if off["prefill_tokens_per_sec"]:
        out["serve_prefill_speedup_cached"] = round(
            on["prefill_tokens_per_sec"] / off["prefill_tokens_per_sec"],
            2)
    if (off["ttft_p50_ms"] or 0) > 0 and on["ttft_p50_ms"]:
        out["serve_prefill_ttft_p50_speedup"] = round(
            off["ttft_p50_ms"] / on["ttft_p50_ms"], 2)
    out["prefill_cached_token_share"] = on.get(
        "prefill_cached_token_share", 0.0)

    # token-exactness spot check: a HIT must emit byte-identical tokens
    # to the explicit cached-prefix reference, and a cold CHUNKED prompt
    # to the plain reference
    eng = serve.ContinuousEngine(
        model, max_slots=4, prefill_window=window,
        prefix_cache_slots=2, prefix_block=block).start()
    cut = min(model.config.max_len - 4, 2 * window + block)
    long_prompt = np.concatenate([p for p, _ in workload[:4]])[:cut]
    got = []
    try:
        # engine outputs first (cold publishes, the repeat hits), the
        # reference replays AFTER close — reference_generate reuses the
        # model's jit programs at 1-slot-pool shapes, which would read
        # as engine retraces if interleaved
        for prompt, max_new in workload[:3]:
            got.append((eng.generate(prompt, max_new, timeout=120),
                        eng.generate(prompt, max_new, timeout=120)))
        got_long = eng.generate(long_prompt, 2, timeout=120)
        eng.assert_no_retraces()
    finally:
        eng.close()
    exact, checked = True, 0
    for (prompt, max_new), (cold, hit) in zip(workload[:3], got):
        mlen = ((int(prompt.size) - 1) // block) * block
        ref_cold = model.reference_generate(prompt, max_new,
                                            window=window)
        ref_hit = model.reference_generate(prompt, max_new,
                                           window=window,
                                           cached_prefix_len=mlen)
        checked += 1
        if (not np.array_equal(cold, ref_cold)
                or not np.array_equal(hit, ref_hit)):
            exact = False
            break
    if exact:
        ref = model.reference_generate(long_prompt, 2, window=window)
        checked += 1
        exact = bool(np.array_equal(got_long, ref))
    out["prefill_token_exact"] = exact
    out["prefill_token_exact_checked"] = checked
    print(f"token-exact spot check (hit + chunked): {checked} prompts "
          f"{'OK' if exact else 'DIVERGED'}")
    return out


def bench_prefill_interference(model, window, duration_s,
                               concurrency=4):
    """Long-prompt interference on short-request TTFT: the old engine
    rejected prompts longer than `prefill_window`; chunked prefill
    streams them window-sized pieces per wave instead, so short requests
    keep admitting and decoding BETWEEN chunks. Shorts run `max_new=1`,
    making their client-observed e2e latency literally the time to first
    token; the A/B is shorts alone vs shorts + a continuous long-prompt
    client, and the acceptance bar is interference p99 ≤ 2× baseline."""
    from incubator_mxnet_tpu import serve
    cfg = model.config
    rng = np.random.RandomState(43)
    shorts = [(rng.randint(1, cfg.vocab, size=5).astype(np.int32), 1)
              for _ in range(32)]
    long_len = min(cfg.max_len - 4, int(2.5 * window))
    longs = [rng.randint(1, cfg.vocab, size=long_len).astype(np.int32)
             for _ in range(4)]

    def run(with_longs):
        eng = serve.ContinuousEngine(
            model, max_slots=6, prefill_window=window,
            max_queue=512).start()
        stop_long = threading.Event()

        def long_client():
            # max_new=1: longs are pure PREFILL streamers, so the A/B
            # isolates what chunking changes — prefill-wave interference
            # (decode interference exists with or without chunking and
            # is what the serve_decode phase measures)
            i = 0
            while not stop_long.is_set():
                try:
                    eng.generate(longs[i % len(longs)], 1, timeout=120)
                except Exception:
                    pass
                i += 1

        lt = None
        try:
            if with_longs:
                lt = threading.Thread(target=long_client, daemon=True)
                lt.start()

            def submit(i):
                prompt, max_new = shorts[i % len(shorts)]
                out = eng.generate(prompt, max_new, timeout=120)
                return int(out.size)

            done, _, lats, errors = _drive_autoreg(
                submit, shorts, concurrency, duration_s)
            eng.assert_no_retraces()
            st = eng.stats()
        finally:
            stop_long.set()
            if lt is not None:
                lt.join(timeout=30)
            eng.close()
        lat_sorted = sorted(lats)
        return {"short_completed": done, "errors": errors,
                "short_ttft_p50_ms": _percentile_of(lat_sorted, 50),
                "short_ttft_p99_ms": _percentile_of(lat_sorted, 99),
                "engine_ttft_p99_ms": st["ttft_p99_ms"],
                "prefill_batches": st["prefill_batches"],
                "programs_compiled": st["programs_compiled"],
                "retraces_after_warmup": st["retraces_after_warmup"]}

    base = run(False)
    infr = run(True)
    out = {"interference_long_prompt_len": long_len,
           "interference_window": window,
           "shorts_alone": base, "shorts_with_longs": infr,
           "serve_ttft_p99_ms_interference": infr["short_ttft_p99_ms"],
           "serve_ttft_p99_ms_no_longs": base["short_ttft_p99_ms"]}
    if base["short_ttft_p99_ms"]:
        out["interference_ttft_p99_blowup"] = round(
            (infr["short_ttft_p99_ms"] or 0)
            / base["short_ttft_p99_ms"], 2)
    print(f"interference: short TTFT p99 "
          f"{base['short_ttft_p99_ms'] or 0:.1f}ms alone vs "
          f"{infr['short_ttft_p99_ms'] or 0:.1f}ms with "
          f"{long_len}-token prompts streaming "
          f"(blowup {out.get('interference_ttft_p99_blowup')}x)")
    return out


def _auto_rates(model, sample, concurrency, batch_timeout_ms):
    """Calibrate a short closed-loop run and sweep 0.3x..2.6x around its
    throughput: clearly-underloaded through clearly-saturated."""
    cal = bench_batched(model, sample, concurrency, 2.0, batch_timeout_ms)
    base = max(1.0, cal["requests_per_sec"])
    # the closed loop UNDERESTIMATES open-loop capacity (batching gets
    # more efficient as the queue deepens), so the sweep must extend well
    # past 1x to actually cross the knee — the acceptance contract is a
    # sweep with at least one clearly-saturated rate
    return [round(base * f, 1)
            for f in (0.3, 0.5, 0.7, 0.85, 1.0, 1.2, 1.5, 2.0, 2.6)], base


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small MLP + short runs (CI smoke)")
    ap.add_argument("--concurrency", type=int, default=32)
    ap.add_argument("--duration", type=float, default=None,
                    help="seconds of measured load per mode")
    ap.add_argument("--batch-timeout-ms", type=float, default=2.0)
    ap.add_argument("--modes", default="serial,batched",
                    help="comma list: serial,batched")
    ap.add_argument("--open-loop", action="store_true",
                    help="Poisson offered-load sweep instead of the "
                         "closed-loop modes")
    ap.add_argument("--autoregressive", action="store_true",
                    help="autoregressive serving A/B: continuous "
                         "(iteration-level) batching vs the static "
                         "batcher on the same decoder; with --open-loop, "
                         "a Poisson TTFT/TPOT sweep of the engine")
    ap.add_argument("--decode", action="store_true",
                    help="decode-speed A/B on the continuous engine: "
                         "plain vs speculative (draft+verify) vs "
                         "speculative+int8-KV, with a token-exactness "
                         "spot check, KV slots/GB density, and the "
                         "paged-attention honesty stamp")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="shared-prefix prefill A/B: N system prompts x "
                         "M users, cache-on vs cache-off, plus the "
                         "long-prompt chunked-prefill interference arm "
                         "and a hit/chunked token-exactness spot check")
    ap.add_argument("--draft", type=int, default=None,
                    help="speculative draft tokens per wave (default "
                         "MXNET_SERVE_DRAFT_TOKENS or 4)")
    ap.add_argument("--max-slots", type=int, default=None,
                    help="continuous engine KV slots "
                         "(default MXNET_SERVE_MAX_SLOTS)")
    ap.add_argument("--rates", default="auto",
                    help="open-loop offered rates (req/s), comma list or "
                         "'auto' (closed-loop calibration x 0.3..2.6)")
    ap.add_argument("--seed", type=int, default=11,
                    help="open-loop arrival-process seed")
    ap.add_argument("--sanitize", action="store_true",
                    help="runtime-sanitizer overhead A/B: the quick "
                         "continuous workload with MXNET_SANITIZE off "
                         "vs all modes armed (ISSUE 20)")
    ap.add_argument("--trace-ab", action="store_true",
                    help="paired traced-vs-untraced A/B (interleaved "
                         "MXNET_TELEMETRY windows on one server) instead "
                         "of the load modes")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "results", "serve_bench.json"))
    args = ap.parse_args()
    duration = args.duration or (2.0 if args.quick else 10.0)
    modes = [m.strip() for m in args.modes.split(",") if m.strip()]

    # backend preflight: a dead backend must produce an artifact that SAYS
    # so (backend_ok=false), never a crash or a fantasy-zero row
    try:
        import jax
        import jax.numpy as jnp
        jnp.zeros((2,)).block_until_ready()
    except Exception as e:
        out = {"meta": {"bench": "serve_bench"}, "backend_ok": False,
               "error": f"backend preflight failed: "
                        f"{type(e).__name__}: {e}"}
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
        print(json.dumps(out))
        return 1

    if args.shared_prefix:
        out = {"meta": {"bench": "serve_bench", "mode": "shared_prefix",
                        "quick": bool(args.quick),
                        "concurrency": args.concurrency,
                        "duration_s": duration,
                        "host_cores": os.cpu_count(),
                        "platform": _platform()}}
        (model, workload, block, window, n_prefix,
         shared_len) = _build_shared_prefix(args.quick)
        out["meta"]["model"] = model.config.as_dict()
        out["meta"]["workload"] = {
            "n": len(workload), "n_prefix": n_prefix,
            "prefix_block": block, "prefill_window": window,
            "shared_prefix_len": shared_len,
            "mean_prompt_len": round(float(np.mean(
                [p.size for p, _ in workload])), 2)}
        conc = min(args.concurrency, 8)
        out.update(bench_prefill_ab(model, workload, block, window,
                                    n_prefix, conc, duration))
        if out.get("serve_prefill_speedup_cached"):
            print(f"shared-prefix prefill speedup: "
                  f"{out['serve_prefill_speedup_cached']}x prompt "
                  f"tokens/s (cache on vs off)")
        out.update(bench_prefill_interference(
            model, window // 2, duration))
        out["note"] = (
            "serve_bench --shared-prefix: cache-on vs cache-off on an "
            "N-system-prompts x M-users workload, same engine and "
            "compiled math, CPU host. prefill_tokens_per_sec bills each "
            "completed request's FULL prompt length client-side, so the "
            "cached arm's uplift is real ingest throughput, not an "
            "accounting artifact (the engine bills only suffix tokens "
            "against MXNET_SERVE_PREFILL_BUDGET). The interference arm "
            "measures short-request TTFT (max_new=1 e2e) with and "
            "without chunked long prompts streaming through the same "
            "engine; both arms assert zero retraces.")
        out["backend_ok"] = True
        try:
            from incubator_mxnet_tpu import telemetry
            out["telemetry"] = telemetry.scalar_snapshot()
        except Exception:
            pass
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {args.out}")
        return 0

    if args.decode:
        draft = args.draft if args.draft is not None else int(
            os.environ.get("MXNET_SERVE_DRAFT_TOKENS") or 4)
        out = {"meta": {"bench": "serve_bench", "mode": "decode",
                        "quick": bool(args.quick),
                        "concurrency": args.concurrency,
                        "duration_s": duration,
                        "draft_tokens": draft,
                        "host_cores": os.cpu_count(),
                        "platform": _platform()}}
        model, workload, max_prompt, t_max = _build_autoreg(args.quick)
        slots = args.max_slots or min(32, args.concurrency)
        out["meta"]["max_slots"] = slots
        out["meta"]["model"] = model.config.as_dict()
        out["meta"]["workload"] = {
            "n": len(workload), "max_prompt": max_prompt,
            "t_max": t_max,
            "mean_new_tokens": round(float(np.mean(
                [m for _, m in workload])), 2)}
        out.update(bench_decode_ab(model, workload, args.concurrency,
                                   duration, max_slots=slots,
                                   max_prompt=max_prompt, draft=draft))
        # benchdiff trend key: the speculative path's wall-clock tokens/s
        # in its deployment regime (single-stream latency-bound decode —
        # the saturation arm's plain key stays with serve_continuous)
        out["serve_decode_tokens_per_sec_spec"] = \
            out["latency_spec"]["decode_tokens_per_sec"]
        if out.get("serve_decode_speedup_spec"):
            print(f"speculative decoding speedup (single-stream): "
                  f"{out['serve_decode_speedup_spec']}x decode tokens/s")
        out["note"] = (
            "serve_bench --decode: plain vs speculative (draft+verify) "
            "vs speculative+int8-KV on the r14 autoregressive workload, "
            "same decoder, same host. CPU round: the Pallas "
            "paged-attention kernel falls back to the masked-einsum "
            "reference (paged_pallas_active=false) and the C-wide verify "
            "forward is compute-bound (costs ~C x a single-token step), "
            "so at concurrency-32 saturation speculation cannot beat "
            "plain batching in wall-clock here - the committed speedup "
            "is the single-stream latency-bound arm (speculation's "
            "deployment regime), where the win is realized on this host "
            "too; serve_decode_tokens_per_verify_wave is the "
            "acceptance-weighted ceiling a memory-bound accelerator "
            "converts to wall-clock at saturation. The TPU win is "
            "measured by re-running this mode on-chip.")
        out["backend_ok"] = True
        try:
            from incubator_mxnet_tpu import telemetry
            out["telemetry"] = telemetry.scalar_snapshot()
        except Exception:
            pass
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {args.out}")
        return 0

    if args.sanitize:
        out = {"meta": {"bench": "serve_bench", "mode": "sanitize",
                        "quick": bool(args.quick),
                        "concurrency": args.concurrency,
                        "duration_s": duration,
                        "host_cores": os.cpu_count(),
                        "platform": _platform()}}
        out.update(bench_sanitize_ab(args.quick, args.concurrency,
                                     duration, max_slots=args.max_slots))
        print(f"sanitizer overhead (all modes vs off): "
              f"{out['sanitize_overhead_pct']}% decode tokens/s, "
              f"{out['sanitize_retraces']} retraces, "
              f"{out['sanitize_errors']} errors")
        out["note"] = (
            "serve_bench --sanitize: the continuous engine's quick "
            "autoregressive workload with MXNET_SANITIZE off vs all "
            "three modes armed (donation poison-and-trap + per-wave "
            "retrace poll + slot canary row), same workload and host. "
            "sanitize_overhead_pct is the decode-tokens/s cost of "
            "arming everything; the ISSUE-20 budget is <= 5% and the "
            "sanitized arm must stay silent (zero retraces, zero "
            "errors) on the clean loop.")
        out["backend_ok"] = True
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {args.out}")
        return 0

    if args.autoregressive:
        out = {"meta": {"bench": "serve_bench", "mode": "autoregressive",
                        "quick": bool(args.quick),
                        "concurrency": args.concurrency,
                        "duration_s": duration,
                        "host_cores": os.cpu_count(),
                        "platform": _platform(),
                        "batch_timeout_ms": args.batch_timeout_ms}}
        model, workload, max_prompt, t_max = _build_autoreg(args.quick)
        # slot count defaults to the client concurrency (capped): the
        # engine's continuous occupancy is the point of the A/B
        slots = args.max_slots or min(32, args.concurrency)
        out["meta"]["max_slots"] = slots
        out["meta"]["model"] = model.config.as_dict()
        out["meta"]["workload"] = {
            "n": len(workload), "max_prompt": max_prompt,
            "t_max": t_max,
            "mean_new_tokens": round(float(np.mean(
                [m for _, m in workload])), 2)}
        if args.open_loop:
            out["meta"]["arrival_seed"] = args.seed
            if args.rates.strip() == "auto":
                # calibrate from a short continuous closed-loop run:
                # requests/s at saturation, swept 0.3x..2.0x
                cal = bench_autoreg_continuous(
                    model, workload, args.concurrency,
                    max(2.0, duration / 3), max_slots=slots,
                    max_prompt=max_prompt)
                base = max(1.0, cal["requests_per_sec"])
                rates = [round(base * f, 1)
                         for f in (0.3, 0.5, 0.7, 1.0, 1.4, 2.0)]
                out["meta"]["closed_loop_calibration_rps"] = base
            else:
                rates = [float(r) for r in args.rates.split(",")
                         if r.strip()]
            out["meta"]["rates"] = rates
            out["autoreg_open_loop"] = bench_autoreg_open_loop(
                model, workload, rates, duration, seed=args.seed,
                max_slots=slots, max_prompt=max_prompt)
        st = bench_autoreg_static(model, workload, max_prompt, t_max,
                                  args.concurrency, duration,
                                  args.batch_timeout_ms)
        print(f"static    {st['decode_tokens_per_sec']:>9.1f} tok/s  "
              f"{st['requests_per_sec']:>7.1f} req/s  "
              f"ttft p99 {st['ttft_p99_ms'] or 0:.0f}ms")
        ct = bench_autoreg_continuous(model, workload, args.concurrency,
                                      duration, max_slots=slots,
                                      max_prompt=max_prompt)
        print(f"continuous{ct['decode_tokens_per_sec']:>9.1f} tok/s  "
              f"{ct['requests_per_sec']:>7.1f} req/s  "
              f"ttft p99 {ct['ttft_p99_ms'] or 0:.0f}ms  "
              f"retraces {ct['retraces_after_warmup']}")
        out["static"] = st
        out["continuous"] = ct
        if st["decode_tokens_per_sec"]:
            out["serve_continuous_speedup_vs_static"] = round(
                ct["decode_tokens_per_sec"] / st["decode_tokens_per_sec"],
                2)
            print(f"continuous batching speedup: "
                  f"{out['serve_continuous_speedup_vs_static']}x "
                  f"decode tokens/s")
        # benchdiff trend keys
        out["serve_decode_tokens_per_sec"] = ct["decode_tokens_per_sec"]
        out["serve_ttft_p99_ms"] = ct["ttft_p99_ms"]
        cc = bench_compile_cache_skip(args.quick)
        out.update(cc)
        if cc.get("serve_compile_cache_warm_speedup"):
            print(f"compile cache: cold warmup "
                  f"{cc['compile_cache_cold_warmup_s']}s -> warm "
                  f"{cc['compile_cache_warm_warmup_s']}s "
                  f"({cc['serve_compile_cache_warm_speedup']}x)")
        out["backend_ok"] = True
        try:
            from incubator_mxnet_tpu import telemetry
            out["telemetry"] = telemetry.scalar_snapshot()
        except Exception:
            pass
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {args.out}")
        return 0

    with tempfile.TemporaryDirectory(prefix="serve_bench_") as d:
        model, sample, buckets = _build_and_export(args.quick, d)
        out = {"meta": {"bench": "serve_bench", "quick": bool(args.quick),
                        "model": "mlp64" if args.quick
                                 else "resnet18_thumb_32x32",
                        "concurrency": args.concurrency,
                        "duration_s": duration,
                        "buckets": buckets,
                        "batch_timeout_ms": args.batch_timeout_ms,
                        "host_cores": os.cpu_count(),
                        "platform": _platform()}}
        if args.trace_ab:
            out["meta"]["mode"] = "trace_ab"
            ab = bench_trace_ab(model, sample, args.concurrency,
                                batch_timeout_ms=args.batch_timeout_ms)
            out.update(ab)
            print(f"trace A/B: default-on "
                  f"{ab['serve_traced_requests_per_sec']} req/s vs off "
                  f"{ab['serve_untraced_requests_per_sec']} "
                  f"req/s -> overhead {ab['serve_trace_overhead_pct']}% "
                  f"(guard <= 2%: "
                  f"{'ok' if ab['serve_trace_overhead_ok'] else 'FAIL'}); "
                  f"full sampling "
                  f"{ab['serve_trace_sampled_requests_per_sec']} req/s "
                  f"-> {ab['serve_trace_sampled_overhead_pct']}% "
                  f"(reported, head-sampling scales it)")
            modes = []
        if args.open_loop:
            out["meta"]["mode"] = "open_loop"
            out["meta"]["arrival_seed"] = args.seed
            if args.rates.strip() == "auto":
                rates, cal_rps = _auto_rates(model, sample,
                                             args.concurrency,
                                             args.batch_timeout_ms)
                out["meta"]["closed_loop_calibration_rps"] = cal_rps
            else:
                rates = [float(r) for r in args.rates.split(",")
                         if r.strip()]
            out["meta"]["rates"] = rates
            rows, knee = bench_open_loop(model, sample, rates, duration,
                                         args.batch_timeout_ms,
                                         seed=args.seed)
            out["open_loop"] = {"rows": rows, "knee": knee}
            if knee and knee.get("knee_rps"):
                # top-level trend keys (what bench.py/benchdiff read)
                out["serve_knee_rps"] = knee["knee_rps"]
                out["serve_p99_ms_at_0p8_knee"] = knee["p99_ms_at_0p8_knee"]
                print(f"knee: {knee['knee_rps']} req/s offered "
                      f"(achieved {knee['knee_achieved_rps']}, "
                      f"p99 {knee['knee_p99_ms']}ms, drop rate "
                      f"{knee['knee_drop_rate']}); p99 at 0.8x knee = "
                      f"{knee['p99_ms_at_0p8_knee']}ms")
            else:
                print("knee: not detected (saturated from the first "
                      "rate? widen --rates downward)")
        if "serial" in modes and not args.open_loop:
            # bucket-1 artifact doubles as the serial baseline program
            bs1 = model._models[1]
            out["serial"] = bench_serial(bs1, sample, args.concurrency,
                                         duration)
            print(f"serial   {out['serial']['requests_per_sec']:>9.1f} req/s"
                  f"  p50 {out['serial']['p50_ms']:.1f}ms"
                  f"  p99 {out['serial']['p99_ms']:.1f}ms")
        if "batched" in modes and not args.open_loop:
            out["batched"] = bench_batched(model, sample, args.concurrency,
                                           duration, args.batch_timeout_ms)
            print(f"batched  {out['batched']['requests_per_sec']:>9.1f} req/s"
                  f"  p50 {out['batched']['p50_ms']:.1f}ms"
                  f"  p99 {out['batched']['p99_ms']:.1f}ms")
        if "serial" in modes and "batched" in modes and not args.open_loop:
            base = out["serial"]["requests_per_sec"]
            out["speedup_vs_serial"] = round(
                out["batched"]["requests_per_sec"] / base, 2) if base else None
            print(f"dynamic batching speedup: {out['speedup_vs_serial']}x")

    # the artifact reports through the telemetry registry: serving counters
    # (`serve.*`), span aggregates, and the preflight verdict ride along
    out["backend_ok"] = True
    try:
        from incubator_mxnet_tpu import telemetry
        out["telemetry"] = telemetry.scalar_snapshot()
    except Exception:
        pass
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
