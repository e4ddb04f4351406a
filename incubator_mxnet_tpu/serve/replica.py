"""mx.serve.replica — one fleet worker process.

Spawned by `serve.Fleet` as

    python -m incubator_mxnet_tpu.serve.replica \\
        --connect <router-port> --replica <index> --spec <spec.json>

and speaks newline-delimited JSON to the router over a localhost TCP
socket. The spec file is the VERSION-PINNED model artifact manifest:
decoder config + parameter seed + version tag (+ engine knobs); a
drain-and-swap restarts the replica against a new spec file, nothing else
changes.

Protocol (replica -> router unless noted):

  hello      first message: replica index, pid, model version, the bound
             /metrics port, warmup_s, compile_cache_size — the router
             DISCOVERS the metrics port from here instead of assuming it
  request    (router ->) prompt/max_new/deadline_ms/trace-context dict;
             answered by exactly one `reply` or `error`
  reply      generated token ids (+ serving version)
  error      typed failure: `kind` is the exception class name;
             kind=ReplicaDraining is the routed-around drain signal and
             never surfaces to clients
  ping/pong  (router ->)/(replica ->) heartbeat; pong carries queue depth
             (least-loaded routing signal), draining flag, and the
             zero-retrace observables
  drain      (router ->) stop admitting, finish KV-resident requests,
             answer `drained`, exit 0 (the supervisor respawns, possibly
             on a new version)
  stop       (router ->) hard close and exit

Metrics-port derivation (the PR-16 collision fix): `ensure_metrics_server`
is a process-wide singleton, so N replica children inheriting one
`MXNET_METRICS_PORT` would race to bind the SAME port and N-1 would lose.
Each replica derives `base + replica_index`, logs the choice, and reports
the actually-bound port in its hello.

Warm start: the spawning supervisor sets `MXNET_COMPILE_CACHE_DIR`
(inherited here), so `CachedDecoder.__init__` arms the persistent
compilation cache and `ContinuousEngine.start()` deserializes both step
programs instead of recompiling.
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import socket
import sys
import threading
import time
from collections import deque
from concurrent.futures import Future

import numpy as _np

from ..base import get_env
from .. import telemetry
from ..telemetry import trace as _trace
from .batcher import ReplicaDraining, ServerClosed

logger = logging.getLogger("mx.serve.fleet")

__all__ = ["main", "derive_metrics_port"]


def derive_metrics_port(base, replica_index):
    """Per-replica /metrics port: base + replica index (None when no base
    is configured). Keeping the offset an arithmetic rule (not an
    ephemeral bind) makes the port predictable for operators, while the
    hello message still carries the AUTHORITATIVE bound port."""
    if not base:
        return None
    return int(base) + int(replica_index)


def _start_metrics(replica_index):
    """Bind this replica's derived metrics port; returns the bound port
    (or None when MXNET_METRICS_PORT is unset / the port is taken)."""
    base = get_env("MXNET_METRICS_PORT", typ=int)
    port = derive_metrics_port(base, replica_index)
    if port is None:
        return None
    try:
        srv = telemetry.ensure_metrics_server(port)
    except OSError as e:
        logger.warning("replica %d: metrics port %d unavailable: %s",
                       replica_index, port, e)
        return None
    bound = srv.server_address[1]
    logger.info("replica %d: serving /metrics on port %d "
                "(MXNET_METRICS_PORT base %s + replica index %d)",
                replica_index, bound, base, replica_index)
    return bound


class _StubEngine:
    """jax-free stand-in engine for fleet protocol tests and the
    router-side fault-point suite: resolves each request after a fixed
    delay with a deterministic token pattern derived from (prompt,
    version). Mirrors the exact ContinuousEngine surface the replica loop
    touches — submit / queue_depth / begin_drain / draining / close /
    warmup_s / compile_cache_size / retraces_after_warmup /
    prefix_hit_count."""

    def __init__(self, spec):
        self.version = str(spec.get("version", "v0"))
        self._delay_s = float(spec.get("stub_delay_ms", 5.0)) / 1e3
        self.warmup_s = 0.0
        self._vtag = sum(self.version.encode()) % 997
        self._cv = threading.Condition()
        self._q = deque()
        self._closing = False
        self._drain = True
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="stub-engine")
        self._thread.start()

    def compile_cache_size(self):
        return 0

    def retraces_after_warmup(self):
        return 0

    def prefix_hit_count(self):
        return 0

    @property
    def draining(self):
        return self._closing and self._drain and self._thread.is_alive()

    def queue_depth(self):
        with self._cv:
            return len(self._q), 0

    def submit(self, prompt_tokens, max_new_tokens=16, deadline_ms=None,
               **sampling):
        # sampling params accepted for wire compatibility; the stub's
        # deterministic token pattern ignores them
        prompt = _np.asarray(prompt_tokens, dtype=_np.int64).ravel()
        fut = Future()
        with self._cv:
            if self._closing:
                if self._drain and self._thread.is_alive():
                    raise ReplicaDraining("stub engine is draining")
                raise ServerClosed("stub engine is closed")
            self._q.append((time.perf_counter() + self._delay_s,
                            prompt, int(max_new_tokens), fut))
            self._cv.notify()
        return fut

    def _loop(self):
        while True:
            with self._cv:
                while not self._q and not self._closing:
                    self._cv.wait()
                if not self._q and self._closing:
                    return
                due, prompt, max_new, fut = self._q.popleft()
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            base = int(prompt.sum()) % 997
            toks = _np.asarray(
                [(base * 31 + i + self._vtag) % 97 for i in range(max_new)],
                dtype=_np.int32)
            if fut.set_running_or_notify_cancel():
                fut.set_result(toks)

    def begin_drain(self):
        with self._cv:
            self._closing = True
            self._drain = True
            self._cv.notify_all()

    def close(self, drain=True, timeout=30.0):
        with self._cv:
            self._closing = True
            self._drain = drain
            pending = [] if drain else list(self._q)
            if not drain:
                self._q.clear()
            self._cv.notify_all()
        for _, _, _, fut in pending:
            if fut.set_running_or_notify_cancel():
                fut.set_exception(
                    ServerClosed("stub engine closed before completion"))
        self._thread.join(timeout=timeout)


def _resolve_profile(spec):
    """Activate the deployment profile for this replica's model, if one
    exists: explicit `spec["profile"]` path > ``MXNET_TUNE_PROFILE`` >
    lookup by (model, hardware) fingerprint under the profile dir.
    Returns the applied profile hash (reported in the hello so the Fleet
    can detect divergent tunings) or None — a mismatched or corrupt
    profile falls back loudly inside tune.profile and the replica boots
    on env/defaults; tuning must never keep a replica down."""
    if spec.get("stub"):
        # jax-free protocol stub: pass a declared hash through verbatim
        # (lets fleet-level divergence plumbing be tested without a model)
        return spec.get("profile_hash")
    try:
        from ..tune import profile as _tprof
        model_fp = _tprof.model_fingerprint(spec.get("config", {}))
        path = spec.get("profile") or os.environ.get("MXNET_TUNE_PROFILE")
        if path:
            prof = _tprof.DeploymentProfile.load(path)
        else:
            prof = _tprof.lookup(model_fp)
        if prof is not None and _tprof.activate(prof, model_fp=model_fp,
                                                source="replica"):
            return prof.profile_hash
    except Exception as e:  # noqa: BLE001 — boot anyway, on defaults
        logger.warning("deployment profile unavailable (%s); replica "
                       "starts on env/defaults", e)
    return None


def _build_engine(spec):
    """Engine from a version-pinned spec manifest. `stub: true` selects
    the jax-free protocol stub (test plumbing); otherwise a
    CachedDecoder + ContinuousEngine (warm via MXNET_COMPILE_CACHE_DIR,
    tuned via the activated deployment profile)."""
    if spec.get("stub"):
        return _StubEngine(spec)
    from .continuous import (CachedDecoder, ContinuousEngine,
                             DecoderConfig)
    cfg = DecoderConfig(**spec.get("config", {}))
    model = CachedDecoder(cfg, seed=int(spec.get("seed", 0)))
    eng = ContinuousEngine(model, **spec.get("engine", {}))
    eng.start()
    return eng


def main(argv=None):
    ap = argparse.ArgumentParser(prog="serve.replica")
    ap.add_argument("--connect", type=int, required=True,
                    help="router listen port on 127.0.0.1")
    ap.add_argument("--replica", type=int, required=True)
    ap.add_argument("--spec", required=True,
                    help="version-pinned model spec JSON path")
    args = ap.parse_args(argv)
    logging.basicConfig(
        stream=sys.stderr, level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    with open(args.spec) as f:
        spec = json.load(f)
    version = str(spec.get("version", "v0"))

    metrics_port = _start_metrics(args.replica)
    profile_hash = _resolve_profile(spec)    # before any program builds
    eng = _build_engine(spec)

    sock = socket.create_connection(("127.0.0.1", args.connect),
                                    timeout=60)
    sock.settimeout(None)
    rfile = sock.makefile("r", encoding="utf-8", newline="\n")
    wlock = threading.Lock()

    def send(msg):
        data = (json.dumps(msg) + "\n").encode("utf-8")
        try:
            with wlock:
                sock.sendall(data)
        except OSError:
            pass            # router gone; the reader loop will see EOF

    send({"type": "hello", "replica": args.replica, "pid": os.getpid(),
          "version": version, "metrics_port": metrics_port,
          "warmup_s": eng.warmup_s,
          "compile_cache_size": eng.compile_cache_size(),
          "profile_hash": profile_hash})

    drain_started = threading.Event()
    done = threading.Event()

    def _finish_drain(timeout_s):
        t0 = time.perf_counter()
        eng.close(drain=True, timeout=timeout_s)
        send({"type": "drained", "replica": args.replica,
              "version": version,
              "drain_ms": round((time.perf_counter() - t0) * 1e3, 3)})
        done.set()
        # orderly exit: close the socket so the router's reader sees EOF
        # AFTER `drained`; the supervisor respawns us (maybe on a new spec)
        try:
            sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass

    def _on_done(rid, fut):
        try:
            toks = fut.result()
        except ReplicaDraining as e:
            send({"type": "error", "id": rid, "kind": "ReplicaDraining",
                  "message": str(e)})
        except Exception as e:  # typed serve errors and unexpected alike
            send({"type": "error", "id": rid,
                  "kind": type(e).__name__, "message": str(e)})
        else:
            send({"type": "reply", "id": rid, "version": version,
                  "tokens": [int(t) for t in toks]})

    for line in rfile:
        try:
            msg = json.loads(line)
        except ValueError:
            continue
        t = msg.get("type")
        if t == "request":
            rid = msg.get("id")
            # re-join the router's trace: the serve.request root minted
            # inside submit() becomes a CHILD of the router's
            # fleet.request span (TraceContext.to_dict/from_dict hop), so
            # one trace survives a failover re-dispatch
            ctx = _trace.TraceContext.from_dict(msg.get("trace") or {})
            token = _trace.attach(ctx) if ctx is not None else None
            # sampling params (temperature/top_k/top_p/seed) ride the
            # request message; absent keys keep the engine's greedy
            # defaults so old routers speak the same protocol
            sampling = {k: msg[k]
                        for k in ("temperature", "top_k", "top_p", "seed")
                        if k in msg}
            try:
                fut = eng.submit(msg.get("prompt"),
                                 msg.get("max_new", 16),
                                 deadline_ms=msg.get("deadline_ms"),
                                 **sampling)
            except ReplicaDraining as e:
                send({"type": "error", "id": rid,
                      "kind": "ReplicaDraining", "message": str(e)})
            except Exception as e:
                send({"type": "error", "id": rid,
                      "kind": type(e).__name__, "message": str(e)})
            else:
                fut.add_done_callback(
                    lambda f, rid=rid: _on_done(rid, f))
            finally:
                if token is not None:
                    _trace.detach(token)
        elif t == "ping":
            waiting, running = eng.queue_depth()
            send({"type": "pong", "seq": msg.get("seq"),
                  "replica": args.replica, "version": version,
                  "waiting": waiting, "running": running,
                  "draining": bool(getattr(eng, "draining", False)),
                  "retraces": eng.retraces_after_warmup(),
                  "compile_cache_size": eng.compile_cache_size(),
                  # prefix-cache effectiveness, surfaced so the router's
                  # affinity decisions are observable fleet-wide
                  "prefix_hits": eng.prefix_hit_count()})
        elif t == "drain":
            if not drain_started.is_set():
                drain_started.set()
                eng.begin_drain()
                timeout_s = float(msg.get("timeout_ms", 30000.0)) / 1e3
                threading.Thread(target=_finish_drain,
                                 args=(timeout_s,), daemon=True,
                                 name="replica-drain").start()
        elif t == "stop":
            break

    if drain_started.is_set():
        done.wait(timeout=5.0)
        return 0
    try:
        eng.close(drain=False, timeout=5.0)
    except Exception:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
