"""mx.telemetry — unified metrics registry + step-timeline attribution.

One observability surface for the whole process (≙ the reference's
profiler/metrics layer: `MXNET_PROFILER_MODE`, per-op profiling hooks,
KVStore server profiling):

  telemetry.counter/gauge/histogram   typed metrics with labels on the
                                      process-global REGISTRY
  telemetry.snapshot(reset=False)     flat dict over EVERY counter in the
                                      process — dispatch, serve, feed,
                                      kvstore, spans — one call
  telemetry.prometheus_text()         Prometheus text exposition (0.0.4)
  telemetry.span("train.step", n=1)   nesting-aware tracer: Chrome-trace
                                      lane + duration histogram
  telemetry.StepTimeline              per-step data-stall / compute / H2D /
                                      allreduce breakdown from live counters
  telemetry.model_flops(...)          XLA-counted MFU numerator
  telemetry.start_metrics_server(port)  /metrics HTTP endpoint
  telemetry.trace                     end-to-end request tracing
                                      (TraceContext / attach) + the crash
                                      flight recorder (flightrec_*)

The legacy surfaces keep working: `profiler.dispatch_stats()`,
`profiler.serve_stats()` and `profiler.feed_stats()` are shims over
registry-adopted `StatsGroup`s with identical keys and reset semantics.

Knobs: `MXNET_TELEMETRY` (default on; `0` makes spans and step timelines
no-ops — counters stay live, they are free), `MXNET_METRICS_PORT` (when
set, `mx.serve.Server.start()` also starts the /metrics endpoint).
"""
from __future__ import annotations

import threading as _threading

from ..base import _register_env
from .registry import (Counter, Gauge, Histogram, StatsGroup, Registry,
                       REGISTRY, counter, gauge, histogram, stats_group,
                       snapshot, snapshot_json, prometheus_text,
                       DEFAULT_BUCKETS)
from . import trace
from .trace import (TraceContext, current_context, attach, detach,
                    attached, new_context, child_context,
                    flightrec_record, flightrec_dump, flightrec_maybe_dump,
                    flightrec_events, install_crash_hooks, FLIGHTREC)
from .steptrace import (span, NO_SPAN, current_span, record_span,
                        StepTimeline,
                        model_flops, block_fwd_flops, cost_flops,
                        device_peak_flops)

__all__ = [
    "Counter", "Gauge", "Histogram", "StatsGroup", "Registry", "REGISTRY",
    "counter", "gauge", "histogram", "stats_group", "snapshot",
    "snapshot_json", "prometheus_text", "DEFAULT_BUCKETS",
    "span", "NO_SPAN", "current_span", "record_span", "StepTimeline",
    "model_flops",
    "block_fwd_flops", "cost_flops", "device_peak_flops",
    "metrics_text", "start_metrics_server",
    "ensure_metrics_server", "mem_on_oom", "mem_install_oom_hook",
    "trace", "TraceContext", "current_context", "attach", "detach",
    "attached", "new_context", "child_context", "flightrec_record",
    "flightrec_dump", "flightrec_maybe_dump", "flightrec_events",
    "install_crash_hooks", "FLIGHTREC",
]

_register_env("MXNET_TELEMETRY", bool, True,
              "0 disables span recording and step-timeline collection "
              "(counters stay live — plain increments are free)")
_register_env("MXNET_METRICS_PORT", int, None,
              "When set, serve.Server.start() also serves the telemetry "
              "/metrics endpoint on this port (0 = ephemeral)")

def mem_on_oom(error, where=""):
    """Crash-path-safe proxy to `inspect.memory.on_oom`: the ONE shared
    wrapper every driver (run_resilient / run_elastic / serve batcher /
    continuous engine) calls from its exception path. Guards the IMPORT
    too — a failure to load the inspect package (interpreter teardown,
    broken install) must never replace the original error on a crash
    path. Returns the dump path or None; never raises."""
    try:
        from ..inspect.memory import on_oom
        return on_oom(error, where=where)
    except Exception:
        return None


def mem_install_oom_hook():
    """Crash-path-safe proxy to `inspect.memory.install_oom_hook` (the
    sys.excepthook chain for uncaught OOMs), armed next to
    `install_crash_hooks` by the same drivers. Never raises."""
    try:
        from ..inspect.memory import install_oom_hook
        install_oom_hook()
    except Exception:
        pass


def metrics_text():
    """The full registry in Prometheus text format — what /metrics serves."""
    return prometheus_text()


def start_metrics_server(port=0, host="127.0.0.1"):
    """Serve `/metrics` (Prometheus text) and `/metrics.json` (snapshot)
    on a daemon thread. Returns the HTTPServer; `server.server_address`
    carries the bound (host, port) — pass port=0 for an ephemeral port —
    and `server.shutdown()` stops it."""
    import json as _json
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class _Handler(BaseHTTPRequestHandler):
        def do_GET(self):
            path = self.path.split("?")[0]
            if path == "/metrics":
                body = prometheus_text().encode()
                ctype = "text/plain; version=0.0.4; charset=utf-8"
            elif path == "/metrics.json":
                body = snapshot_json().encode()
                ctype = "application/json"
            else:
                self.send_response(404)
                self.end_headers()
                return
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):      # no stderr chatter per scrape
            pass

    server = ThreadingHTTPServer((host, int(port)), _Handler)
    t = threading.Thread(target=server.serve_forever,
                         name="mx-metrics", daemon=True)
    t.start()
    return server


# process-wide /metrics endpoint (the MXNET_METRICS_PORT integration):
# one per process no matter how many Servers start, and no Server's close()
# tears it down under the others — it lives until process exit
_shared_metrics = {"server": None}
_shared_metrics_lock = _threading.Lock()


def ensure_metrics_server(port=0, host="127.0.0.1"):
    """Start (once) and return the process-wide /metrics endpoint. Repeat
    calls return the existing server regardless of port — the registry is
    process-global, so one endpoint serves every subsystem."""
    with _shared_metrics_lock:
        if _shared_metrics["server"] is None:
            _shared_metrics["server"] = start_metrics_server(port, host)
        return _shared_metrics["server"]
