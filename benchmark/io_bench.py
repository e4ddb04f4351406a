"""Data-pipeline throughput bench (≙ the reference's note_data_loading.md
measurement: ImageRecordIter ~3000 img/s with a full decode+augment
pipeline, docs/.../note_data_loading.md:181).

Default mode synthesizes a .rec of realistic JPEGs once (256px shorter
side), then measures ImageRecordIter end-to-end: threaded C++ JPEG decode +
shorter-side resize + random crop 224 + mirror + mean/std normalize +
contiguous NHWC batch. Prints one JSON line.

`--overlap` measures the INPUT-PIPELINE OVERLAP `io.DeviceFeed` provides,
directly: a synthetic augment-heavy pipeline (RNG sample + a chain of
elementwise host transforms per batch) feeds a jitted train-step proxy with
a per-step host sync (the "user reads the loss" loop). Four measures per
trial — data_ms (pipeline alone), compute_ms (pre-staged batch),
host_fed_step_ms (fetch→step serially: pays data+compute), and
device_fed_step_ms (through DeviceFeed: the feeder preps+transfers batch
N+1 while batch N computes) — plus the event-based hidden-input fraction
from `profiler.feed_stats()` stall accounting, which is stable where the
wall-clock ratio wobbles on a shared-core host (same convention as
overlap_bench.py). By default the XLA CPU pool is spun up while the
process is affinity-restricted to one cpu (`--no-pin` disables), so
"compute_ms" means the same thing alone and under the feed — the
shared-core-host analog of a dedicated accelerator.

Standalone mode measures THREE ImageRecordIter configurations
back-to-back: float32 handoff (reference semantics, the "before"), uint8
handoff through the persistent shm-worker pool (the PR-9 fast path), and
uint8 + device-side fused augmentation (zero-retrace asserted via
`fused.device_augment_calls`). `--pair-out` writes the
`io_r11_{before,after}.json` acceptance artifact pair.

Usage:
  python benchmark/io_bench.py [--n 768] [--batch 128] [--threads 0]
                               [--workers N] [--quick]
                               [--pair-out results/io_r11]
  python benchmark/io_bench.py --overlap [--quick] [--depth 2]
                               [--pair-out results/feed_r08] [--no-pin]
"""
import argparse
import io
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Host-pipeline bench: keep batches on the host platform.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

REFERENCE_IMG_S = 3000.0  # reference ImageRecordIter published figure


def make_rec(path, n, size=256):
    from PIL import Image
    from incubator_mxnet_tpu import recordio
    rng = np.random.RandomState(0)
    w = recordio.MXRecordIO(path, "w")
    # realistic JPEG content: smooth blobs + noise (compresses like photos)
    for i in range(n):
        h_ = size + int(rng.randint(0, 64))
        w_ = size + int(rng.randint(0, 96))
        yy, xx = np.mgrid[0:h_, 0:w_]
        base = (
            127 + 80 * np.sin(yy / 23.0 + i) + 40 * np.cos(xx / 17.0))
        img = np.stack([base, base * 0.8, base * 1.1], -1)
        img += rng.randn(h_, w_, 3) * 12
        img = np.clip(img, 0, 255).astype(np.uint8)
        buf = io.BytesIO()
        Image.fromarray(img).save(buf, format="JPEG", quality=85)
        w.write(recordio.pack(
            recordio.IRHeader(0, float(i % 1000), i, 0), buf.getvalue()))
    w.close()


def bench(rec_path, batch_size, threads, epochs=2, handoff="float32",
          device_augment=False, workers=0):
    """One ImageRecordIter configuration end-to-end: persistent decode pool
    (threads or `workers` shm processes), `handoff` float32 (reference
    semantics: normalized NHWC f32 from the host) or uint8 (raw cropped
    pixels, 1/4 the staged bytes; `device_augment` runs mirror/normalize
    on device as the fused jitted kernel). Returns the measured dict."""
    from incubator_mxnet_tpu import io as mxio
    from incubator_mxnet_tpu import native as mxnative
    from incubator_mxnet_tpu.ops.fused import FUSED_STATS
    # raw-uint8 handoff rejects mean/std (they would be silently unused:
    # normalization is the consumer's job there)
    norm = {} if (handoff == "uint8" and not device_augment) else dict(
        mean_r=123.68, mean_g=116.779, mean_b=103.939,
        std_r=58.393, std_g=57.12, std_b=57.375)
    it = mxio.ImageRecordIter(
        path_imgrec=rec_path, data_shape=(224, 224, 3),
        batch_size=batch_size, shuffle=True, rand_crop=True,
        rand_mirror=True, resize=256,
        preprocess_threads=threads, round_batch=False,
        handoff=handoff, device_augment=device_augment, workers=workers,
        **norm)
    native = it._native is not None
    # warm epoch (page cache, thread pool, device-augment program) —
    # consumed exactly like the timed loop, so every program the steady
    # state needs (augment + the bulked-segment replays around it) is
    # compiled BEFORE the retrace counter baseline is read
    for b in it:
        _ = float(b.label[0][0, 0]) + float(b.data[0][0, 0, 0, 0])
    mxnative.imagerec_stage_reset()
    mxio.io_stats(reset=True)
    warm_traces = int(FUSED_STATS["device_augment_calls"])
    t0 = time.perf_counter()
    total = 0
    checksum = 0.0
    for _ in range(epochs):
        it.reset()
        for b in it:
            total += b.data[0].shape[0]
            # consume: force materialization of the batch (labels fully, one
            # pixel of the image tensor — a real consumer hands the batch to
            # the model, it does not copy 77MB back to numpy)
            checksum += float(b.label[0][0, 0]) + float(b.data[0][0, 0, 0, 0])
    dt = time.perf_counter() - t0
    assert checksum == checksum  # not NaN
    ios = mxio.io_stats()
    it.close()
    out = {
        "images_per_sec": total / dt,
        "native": native,
        "mode": "processes" if workers else "threads",
        "handoff": handoff,
        "device_augment": bool(device_augment),
        "host_bytes_per_img": (ios["bytes_staged"] / ios["images"]
                               if ios["images"] else 0.0),
        "wait_us_per_batch": (ios["wait_us"] / ios["batches"]
                              if ios["batches"] else 0.0),
        "stage_us_per_batch": (ios["stage_us"] / ios["batches"]
                               if ios["batches"] else 0.0),
        # retraces of the fused augment kernel AFTER warmup (the
        # zero-retrace acceptance: per-batch PRNGKeys are array data)
        "device_augment_retraces":
            int(FUSED_STATS["device_augment_calls"]) - warm_traces,
    }
    if native:
        st = {k: ios.get(k, 0) for k in ("read_ns", "decode_ns",
                                         "augment_ns", "decoded_records")}
        if st["decoded_records"]:
            n_img = st["decoded_records"]
            tot = st["read_ns"] + st["decode_ns"] + st["augment_ns"]
            out["stage_read_ms_per_img"] = st["read_ns"] / n_img / 1e6
            out["stage_decode_ms_per_img"] = st["decode_ns"] / n_img / 1e6
            out["stage_augment_ms_per_img"] = st["augment_ns"] / n_img / 1e6
            out["stage_decode_share"] = (st["decode_ns"] / tot
                                         if tot else 0.0)
    return out


# ---------------------------------------------------------------------------
# --overlap: device-feed overlap measurement (ISSUE 4 acceptance artifact)
# ---------------------------------------------------------------------------
def _median(vals):
    vals = sorted(vals)
    return vals[len(vals) // 2]


def bench_overlap(quick=False, depth=2, trials=None, steps=None,
                  pin=True):
    """Steady-state per-step wall time of an augment-heavy pipeline, host-fed
    vs device-fed. Per-step medians inside each trial, median trial across
    `trials` (this box's XLA step time wobbles ±15% run to run).

    `pin=True` (default): the process affinity is restricted to ONE cpu
    while the XLA CPU client spins up its thread pool, then restored — the
    pool stays effectively single-core, so `compute_ms` means the same
    thing measured alone and under the feed (the shared-core-host analog
    of a dedicated accelerator; without it the idle measurement borrows
    the feeder's core and the comparison is apples-to-oranges)."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu import profiler
    from incubator_mxnet_tpu.io import DeviceFeed

    if quick:
        B, D, AUG, COMP = 128, 512, 8, 6
        steps = steps or 6
        trials = trials or 2
    else:
        B, D, AUG, COMP = 256, 1024, 45, 14
        steps = steps or 12
        trials = trials or 5

    class AugmentPipeline:
        """Synthetic augment-heavy host pipeline: per batch, an RNG sample
        (decode stand-in) + AUG chained elementwise transforms (augment).
        Pure numpy — releases the GIL, so a feeder thread can run it while
        the consumer's step computes."""

        def __init__(self, n):
            self.n = n

        def __len__(self):
            return self.n

        def __iter__(self):
            rng = np.random.RandomState(42)
            for _ in range(self.n):
                x = rng.standard_normal((B, D)).astype(np.float32)
                for _ in range(AUG):
                    x = np.sin(x) * 1.1 + np.cos(0.5 * x)
                yield x

    restore_affinity = None
    if pin and hasattr(os, "sched_setaffinity"):
        orig = os.sched_getaffinity(0)
        if len(orig) > 1:
            os.sched_setaffinity(0, {sorted(orig)[0]})
            restore_affinity = orig

    W = jnp.asarray(np.random.RandomState(0)
                    .standard_normal((D, D)).astype(np.float32) * 0.04)

    @jax.jit
    def train_step(x, w):
        y = x
        for _ in range(COMP):
            y = jnp.tanh(y @ w)
        return y.sum()

    dev = jax.devices()[0]
    # force client + thread-pool creation (and the compile) while pinned,
    # then give the feeder its core back
    float(train_step(jax.device_put(
        np.zeros((B, D), np.float32), dev), W))
    if restore_affinity is not None:
        os.sched_setaffinity(0, restore_affinity)

    def _timed_loop(batch_iter, consume):
        """Per-step wall time INCLUDING the fetch — the loop a real
        training epoch runs (fetch batch, step, read the loss)."""
        it = iter(batch_iter)
        ts = []
        while True:
            t0 = time.perf_counter()
            x = next(it, None)
            if x is None:
                break
            consume(x)
            ts.append(time.perf_counter() - t0)
        return ts

    rows = []
    for _ in range(trials):
        # 1. data: the host pipeline alone, per-batch
        it = iter(AugmentPipeline(steps))
        next(it)                                     # warm (allocator, rng)
        ts = []
        while True:
            t0 = time.perf_counter()
            x = next(it, None)
            if x is None:
                break
            ts.append(time.perf_counter() - t0)
        data_ms = _median(ts) * 1e3

        # 2. compute: pre-staged device batch, per-step host sync
        xd = jax.device_put(next(iter(AugmentPipeline(1))), dev)
        float(train_step(xd, W))                     # compile + warm
        ts = [0.0] * steps
        for i in range(steps):
            t0 = time.perf_counter()
            float(train_step(xd, W))
            ts[i] = time.perf_counter() - t0
        comp_ms = _median(ts) * 1e3

        # 3. host-fed (before): fetch -> step -> sync, strictly serial
        ts = _timed_loop(AugmentPipeline(steps + 1),
                         lambda x: float(train_step(x, W)))
        host_ms = _median(ts[1:]) * 1e3              # drop the cold step

        # 4. device-fed (after): DeviceFeed preps + transfers batch N+1
        #    while batch N computes
        profiler.feed_stats(reset=True)
        feed = DeviceFeed(AugmentPipeline(steps + 1), depth=depth)
        ts = _timed_loop(feed, lambda b: float(train_step(b._arr, W)))
        dev_ms = _median(ts[1:]) * 1e3
        fs = profiler.feed_stats()
        consumed = max(fs["batches_consumed"] - 1, 1)
        hidden = 1.0 - fs["stall_data_us"] / (consumed * data_ms * 1e3)
        rows.append({
            "data_ms": round(data_ms, 2),
            "compute_ms": round(comp_ms, 2),
            "host_fed_step_ms": round(host_ms, 2),
            "device_fed_step_ms": round(dev_ms, 2),
            "hidden_input_fraction": round(min(max(hidden, 0.0), 1.0), 4),
            "feed_occupancy_mean": round(fs["occupancy_mean"], 2),
        })

    def _med_key(key):
        return _median([r[key] for r in rows])

    data_ms = _med_key("data_ms")
    comp_ms = _med_key("compute_ms")
    host_ms = _med_key("host_fed_step_ms")
    dev_ms = _med_key("device_fed_step_ms")
    mx_ms = max(data_ms, comp_ms)
    out = {
        "metric": "input_pipeline_device_fed_step_ms",
        "value": round(dev_ms, 2),
        "unit": "ms/step",
        "data_ms": data_ms,
        "compute_ms": comp_ms,
        "host_fed_step_ms": host_ms,
        "device_fed_step_ms": dev_ms,
        "serial_sum_ms": round(data_ms + comp_ms, 2),
        "max_ms": round(mx_ms, 2),
        # acceptance metric: device-fed steady state vs max(data, compute)
        "device_fed_vs_max": round(dev_ms / mx_ms, 4),
        "device_fed_vs_max_best": round(
            min(r["device_fed_step_ms"]
                / max(r["data_ms"], r["compute_ms"]) for r in rows), 4),
        "host_fed_vs_sum": round(host_ms / (data_ms + comp_ms), 4),
        "speedup_vs_host_fed": round(host_ms / dev_ms, 4),
        # event-based: fraction of host data prep that provably ran while
        # compute was in flight (stable where wall-clock wobbles)
        "hidden_input_fraction": _med_key("hidden_input_fraction"),
        "overlap_wallclock_fraction": round(
            min(max((host_ms - dev_ms) / min(data_ms, comp_ms), 0.0), 1.0),
            4),
        "trials": rows,
    }
    return out


def bench_overlap_rec(rec_path, batch=128, workers=2, depth=2, epochs=3,
                      quick=False):
    """PR-4 overlap contract THROUGH the real decode path. PR 9 rolls the
    device staging INTO ImageRecordIter (async `device_put` straight from
    the shm ring + `MXNET_IMAGEREC_LOOKAHEAD` batches decoded ahead), so
    the iterator itself is the device-feeding prefetcher: a plain
    fetch -> step -> sync loop over it is the "device-fed" loop. Measured
    against `prefetch=False` (the serial before: decode THEN step, pays
    data+compute) and against max(data, compute); the acceptance metric
    is device_fed_step <= 1.15 x max(data, compute). Wrapping the
    iterator in `io.DeviceFeed` on top is reported as an A/B
    (`feed_wrapped_step_ms`) — for a source that already stages to
    device, the extra thread hop is pure overhead (use DeviceFeed for
    host-array sources; this shows why the staging moved inside)."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu import io as mxio

    if quick:
        epochs = 2
    h = w = 224

    def make_it(**kw):
        return mxio.ImageRecordIter(
            path_imgrec=rec_path, data_shape=(h, w, 3), batch_size=batch,
            shuffle=True, rand_crop=True, rand_mirror=True, resize=256,
            round_batch=False, handoff="uint8", workers=workers, **kw)

    W1 = jnp.asarray(np.random.RandomState(0)
                     .standard_normal((1024, 256)).astype(np.float32) * .03)
    W2 = jnp.asarray(np.random.RandomState(1)
                     .standard_normal((256, 256)).astype(np.float32) * .05)

    @jax.jit
    def train_step(x_u8):
        x = x_u8.astype(jnp.float32) * (1.0 / 255.0) - 0.45   # device aug
        x = x.reshape(x.shape[0], -1)[:, :1024]
        y = jnp.tanh(x @ W1)
        for _ in range(10):
            y = jnp.tanh(y @ W2)
        return y.sum()

    def consume(b):
        return float(train_step(b.data[0]._arr))

    def timed_epochs(it, body):
        """Wall clock per batch over `epochs` full passes (reset cost
        included — an epoch loop pays it too)."""
        for b in it:                              # warm pass
            body(b)
        t0 = time.perf_counter()
        n = 0
        for _ in range(epochs):
            it.reset()
            for b in it:
                body(b)
                n += 1
        dt = time.perf_counter() - t0
        it.close()
        return dt / n * 1e3

    # 1. data: the decode pipeline alone (force each staged batch)
    data_ms = timed_epochs(make_it(),
                           lambda b: b.data[0]._arr.block_until_ready())

    # 2. compute: pre-staged batch, per-step host sync
    xd = jax.device_put(np.zeros((batch, h, w, 3), np.uint8))
    float(train_step(xd))
    ts = []
    for _ in range(12):
        t0 = time.perf_counter()
        float(train_step(xd))
        ts.append(time.perf_counter() - t0)
    comp_ms = _median(ts) * 1e3

    # 3. serial (before): prefetch off — decode, then step, strictly
    serial_ms = timed_epochs(make_it(prefetch=False), consume)

    # 4. device-fed (after): the default iterator — lookahead decode +
    #    async staging overlap the consumer's step
    dev_ms = timed_epochs(make_it(), consume)

    # 5. A/B: DeviceFeed wrapped around the already-device-staging source
    it = make_it()
    feed = mxio.DeviceFeed(it, depth=depth)
    for b in feed:
        consume(b)
    t0 = time.perf_counter()
    n = 0
    for _ in range(epochs):
        feed.reset()                 # fresh source epoch through the feed
        for b in feed:
            consume(b)
            n += 1
    wrapped_ms = (time.perf_counter() - t0) / n * 1e3
    it.close()

    mx_ms = max(data_ms, comp_ms)
    return {
        "metric": "io_rec_device_fed_step_ms",
        "value": round(dev_ms, 2),
        "unit": "ms/step",
        "batch": batch,
        "workers": workers,
        "data_ms": round(data_ms, 2),
        "compute_ms": round(comp_ms, 2),
        "serial_step_ms": round(serial_ms, 2),
        "serial_sum_ms": round(data_ms + comp_ms, 2),
        "device_fed_step_ms": round(dev_ms, 2),
        "feed_wrapped_step_ms": round(wrapped_ms, 2),
        "max_ms": round(mx_ms, 2),
        "device_fed_vs_max": round(dev_ms / mx_ms, 4),
        "serial_vs_max": round(serial_ms / mx_ms, 4),
        "speedup_vs_serial": round(serial_ms / dev_ms, 4),
        "images_per_sec_device_fed": round(batch / (dev_ms / 1e3), 1),
    }


def _finalize(out):
    """Every io_bench artifact reports through the telemetry registry: the
    feed/dispatch counter groups and span aggregates ride along, plus the
    preflight verdict (backend_ok) benchdiff keys on."""
    out["backend_ok"] = True
    try:
        from incubator_mxnet_tpu import telemetry
        out["telemetry"] = telemetry.scalar_snapshot()
    except Exception:
        pass
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=768)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--threads", type=int, default=0)
    ap.add_argument("--workers", type=int, default=None,
                    help="shm decode workers for the uint8 fast-path "
                         "measurement (default: min(4, cores) when >= 2 "
                         "cores, else 0 = thread pool)")
    ap.add_argument("--rec", default=None)
    ap.add_argument("--overlap", action="store_true",
                    help="measure DeviceFeed input-pipeline overlap")
    ap.add_argument("--overlap-rec", action="store_true",
                    help="measure the PR-4 overlap contract through the "
                         "REAL decode path (ImageRecordIter uint8 + shm "
                         "workers -> DeviceFeed -> jitted step)")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--no-pin", action="store_true",
                    help="overlap mode: do not pin XLA compute to one "
                         "worker thread")
    ap.add_argument("--pair-out", default=None,
                    help="write <prefix>_before.json / <prefix>_after.json "
                         "artifact pair (overlap mode: host-fed vs "
                         "device-fed; standalone: float32 vs uint8 "
                         "handoff)")
    args = ap.parse_args()

    # backend preflight (io_bench forces the CPU backend, but even that can
    # wedge): the artifact must say backend_ok=false, never crash silently
    try:
        import jax.numpy as _jnp
        _jnp.zeros((2,)).block_until_ready()
    except Exception as e:
        print(json.dumps({"metric": "image_pipeline_images_per_sec",
                          "backend_ok": False,
                          "error": f"backend preflight failed: "
                                   f"{type(e).__name__}: {e}"}))
        return 1

    if args.overlap:
        pinned = not args.no_pin
        out = bench_overlap(quick=args.quick, depth=args.depth, pin=pinned)
        out["pinned_compute"] = pinned
        out["depth"] = args.depth
        out["quick"] = bool(args.quick)
        out["host_cores"] = os.cpu_count()
        out["host_loadavg_1m"] = round(os.getloadavg()[0], 2)
        if args.pair_out:
            meta = {"bench": "io_bench --overlap",
                    "quick": bool(args.quick),
                    "pinned_compute": pinned,
                    "depth": args.depth,
                    "host_cores": os.cpu_count(),
                    "host_loadavg_1m": round(os.getloadavg()[0], 2),
                    "platform": jax.devices()[0].platform,
                    "note": "measured back-to-back within ONE run on the "
                            "same host: 'before' is the host-fed serial "
                            "loop (fetch -> step -> sync), 'after' the "
                            "identical loop through io.DeviceFeed"}
            before = {
                "meta": dict(meta, label="host-fed (no DeviceFeed)"),
                "input_pipeline": {
                    "step_ms": out["host_fed_step_ms"],
                    "data_ms": out["data_ms"],
                    "compute_ms": out["compute_ms"],
                    "serial_sum_ms": out["serial_sum_ms"],
                    "vs_sum": out["host_fed_vs_sum"],
                    "vs_max": round(
                        out["host_fed_step_ms"] / out["max_ms"], 4),
                }}
            after = {
                "meta": dict(meta,
                             label=f"device-fed (DeviceFeed depth="
                                   f"{args.depth})"),
                "input_pipeline": {
                    "step_ms": out["device_fed_step_ms"],
                    "data_ms": out["data_ms"],
                    "compute_ms": out["compute_ms"],
                    "max_ms": out["max_ms"],
                    "vs_max": out["device_fed_vs_max"],
                    "vs_max_best": out["device_fed_vs_max_best"],
                    "speedup_vs_host_fed": out["speedup_vs_host_fed"],
                    "hidden_input_fraction": out["hidden_input_fraction"],
                    "trials": out["trials"],
                }}
            os.makedirs(os.path.dirname(os.path.abspath(
                args.pair_out + "_before.json")), exist_ok=True)
            for suffix, payload in (("_before", before), ("_after", after)):
                with open(args.pair_out + suffix + ".json", "w") as f:
                    json.dump(payload, f, indent=1)
        print(json.dumps(_finalize(out)))
        return

    if args.quick:
        args.n = min(args.n, 96)
        args.batch = min(args.batch, 32)
        epochs = 1
    else:
        epochs = 2
    if args.rec is None:
        # size-stamped per-user cache: no stale-count reuse, no /tmp clash
        import tempfile
        args.rec = os.path.join(
            tempfile.gettempdir(), f"io_bench_{os.getuid()}_{args.n}.rec")
    if not os.path.exists(args.rec):
        make_rec(args.rec, args.n)

    workers = args.workers
    if workers is None:
        # the shm worker pool wins once >= 2 cores feed it; stay honest on
        # a 1-core box (IPC overhead with nothing to parallelize)
        workers = min(4, os.cpu_count() or 1) if (os.cpu_count() or 1) >= 2 \
            else 0

    if args.overlap_rec:
        out = bench_overlap_rec(args.rec, batch=args.batch, workers=workers,
                                depth=args.depth, quick=args.quick)
        out["quick"] = bool(args.quick)
        out["host_cores"] = os.cpu_count()
        out["host_loadavg_1m"] = round(os.getloadavg()[0], 2)
        print(json.dumps(_finalize(out)))
        return
    # before: float32 handoff — reference semantics, host-side normalize
    # (the pre-uint8-handoff pipeline); after: uint8 handoff through the
    # same persistent pool. The native in-process thread pool is the fast
    # path when the toolchain built it (C++ decode releases the GIL, no
    # IPC); the shm process workers are measured alongside — they exist to
    # scale the PIL fallback across cores and are the only parallel path
    # without a toolchain. Device augment is measured separately (on a
    # CPU-only host the "device" burns the same cores the decoders need —
    # it is a win only with a real accelerator).
    f32 = bench(args.rec, args.batch, args.threads, epochs=epochs)
    u8 = bench(args.rec, args.batch, args.threads, epochs=epochs,
               handoff="uint8")
    u8_procs = None
    if workers > 0:
        u8_procs = bench(args.rec, args.batch, args.threads, epochs=epochs,
                         handoff="uint8", workers=workers)
        if not u8["native"]:
            u8 = u8_procs          # no native lib: the worker pool IS the
            #                        parallel path (PIL scaled across cores)
    aug = bench(args.rec, args.batch, args.threads, epochs=epochs,
                handoff="uint8", device_augment=True)
    ips = f32["images_per_sec"]
    out = {
        "metric": "image_pipeline_images_per_sec",
        "value": round(ips, 1),
        "unit": "images/sec",
        "vs_baseline": round(ips / REFERENCE_IMG_S, 4),
        "native": f32["native"],
        "decode_resize_crop_mirror_normalize": True,
        "quick": bool(args.quick),
        # the uint8 fast path (raw pixels staged, normalize deferred)
        "io_images_per_sec_uint8": round(u8["images_per_sec"], 1),
        "io_images_per_sec_uint8_device_augment":
            round(aug["images_per_sec"], 1),
        "io_uint8_speedup": round(u8["images_per_sec"] / ips, 4),
        "io_uint8_vs_reference": round(
            u8["images_per_sec"] / REFERENCE_IMG_S, 4),
        "io_reference_img_s": REFERENCE_IMG_S,
        "io_reference_reached": u8["images_per_sec"] >= REFERENCE_IMG_S,
        "io_host_bytes_per_img": round(f32["host_bytes_per_img"], 1),
        "io_host_bytes_per_img_uint8": round(u8["host_bytes_per_img"], 1),
        "io_bytes_reduction": round(
            f32["host_bytes_per_img"] / u8["host_bytes_per_img"], 4)
            if u8["host_bytes_per_img"] else 0.0,
        "io_uint8_mode": u8["mode"],
        "io_images_per_sec_uint8_shm_workers":
            round(u8_procs["images_per_sec"], 1) if u8_procs else None,
        "io_workers": workers,
        "device_augment_retraces": aug["device_augment_retraces"],
        # environment: the 3000 img/s reference row assumed a multi-core
        # host feeding 4+ decode threads; this box's capability is below
        "host_cores": os.cpu_count(),
        "host_loadavg_1m": round(os.getloadavg()[0], 2),
    }
    if "stage_decode_share" in f32:
        dec_ms = f32["stage_decode_ms_per_img"]
        aug_ms = f32["stage_augment_ms_per_img"]
        out["stage_read_ms_per_img"] = round(f32["stage_read_ms_per_img"],
                                             3)
        out["stage_decode_ms_per_img"] = round(dec_ms, 3)
        out["stage_augment_ms_per_img"] = round(aug_ms, 3)
        out["stage_other_ms_per_img"] = round(
            max(1000.0 / ips - dec_ms - aug_ms, 0.0), 3)
        # decode-bound evidence: throughput ceiling if decode were the ONLY
        # stage, given the measured per-core decode cost
        out["decode_only_ceiling_img_s_per_core"] = round(1000.0 / dec_ms, 1)
        out["decode_share"] = round(dec_ms / (dec_ms + aug_ms), 3)
        out["io_stage_decode_share"] = round(
            u8.get("stage_decode_share", 0.0), 4)
        out["io_stage_augment_ms_per_img_uint8"] = round(
            u8.get("stage_augment_ms_per_img", 0.0), 3)
    if args.pair_out:
        meta = {"bench": "io_bench (ImageRecordIter standalone)",
                "quick": bool(args.quick), "n": args.n, "batch": args.batch,
                "epochs": epochs, "host_cores": os.cpu_count(),
                "host_loadavg_1m": round(os.getloadavg()[0], 2),
                "platform": jax.devices()[0].platform, "backend_ok": True,
                "reference_img_s": REFERENCE_IMG_S,
                "note": "measured back-to-back within ONE run on the same "
                        "host: 'before' is the float32 handoff (reference "
                        "semantics, host-side normalize) through the SAME "
                        "persistent pool — the uint8 handoff's direct A/B, "
                        "NOT the pre-PR9 baseline (the committed r11 "
                        "before was measured from the actual pre-PR9 tree, "
                        "which also lacked the pool + in-place decode); "
                        "'after' is the uint8 handoff (native in-process "
                        "thread pool when built — C++ decode releases the "
                        "GIL, no IPC; the shm process-worker figure rides "
                        "along: the parallel path for the PIL fallback / "
                        "toolchain-less hosts); device-augment throughput "
                        "on this CPU-only host shares cores with the "
                        "decoders and is reported for honesty, not as "
                        "the win"}
        before = {"meta": dict(meta, label="float32 handoff (before)"),
                  "input_pipeline": {
                      "io_pipeline_images_per_sec": round(ips, 1),
                      "io_host_bytes_per_img": out["io_host_bytes_per_img"],
                      "stage_decode_ms_per_img":
                          out.get("stage_decode_ms_per_img"),
                      "stage_augment_ms_per_img":
                          out.get("stage_augment_ms_per_img"),
                      "vs_reference": out["vs_baseline"]}}
        after = {"meta": dict(meta,
                              label=f"uint8 handoff "
                                    f"({out['io_uint8_mode']} mode; shm "
                                    f"workers measured: {workers}) "
                                    f"(after)"),
                 "input_pipeline": {
                     "io_pipeline_images_per_sec":
                         out["io_images_per_sec_uint8"],
                     "io_images_per_sec_uint8":
                         out["io_images_per_sec_uint8"],
                     "io_images_per_sec_uint8_shm_workers":
                         out["io_images_per_sec_uint8_shm_workers"],
                     "io_images_per_sec_uint8_device_augment":
                         out["io_images_per_sec_uint8_device_augment"],
                     "speedup_vs_before": out["io_uint8_speedup"],
                     "io_host_bytes_per_img":
                         out["io_host_bytes_per_img_uint8"],
                     "io_bytes_reduction": out["io_bytes_reduction"],
                     "io_stage_decode_share":
                         out.get("io_stage_decode_share"),
                     "device_augment_retraces":
                         out["device_augment_retraces"],
                     "vs_reference": out["io_uint8_vs_reference"],
                     "reference_reached": out["io_reference_reached"]}}
        os.makedirs(os.path.dirname(os.path.abspath(
            args.pair_out + "_before.json")), exist_ok=True)
        for suffix, payload in (("_before", before), ("_after", after)):
            with open(args.pair_out + suffix + ".json", "w") as f:
                json.dump(payload, f, indent=1)
    print(json.dumps(_finalize(out)))


if __name__ == "__main__":
    sys.exit(main())
