"""mx.serve — dynamic-batching inference server over exported artifacts.

The ROADMAP north star serves "heavy traffic from millions of users"; the
deploy layer (deploy.py, ≙ the reference's c_predict_api.h predictor) stops
at single-shot `ExportedModel.run`. This subsystem adds the request-level
layer above it:

  serve.Server          thread-safe bounded queue + dynamic batcher:
                        concurrent requests coalesce into padded
                        power-of-two batch buckets, execute through one
                        compiled program per bucket, and split back to
                        per-request futures
  serve.BucketedModel   bucket -> ExportedModel map (+ `export_block` to
                        produce the per-bucket artifact set from one block)
  serve.CallableModel   the same contract over an in-process jax callable
  serve.stats()         process-wide serving counters (also
                        `profiler.serve_stats()`); per-server metrics —
                        requests/s, p50/p95/p99 latency, batch-occupancy
                        histogram, queue depth, request-timeline
                        queue-wait vs execute split — via `Server.stats()`
  serve.metrics_text()  Prometheus text of the telemetry registry;
                        `Server.metrics_text()` appends per-server gauges
                        and `serve.start_metrics_server(port)` (or
                        MXNET_METRICS_PORT at `Server.start()`) serves it
                        at `/metrics`

Autoregressive (stateful) serving — continuous batching (ISSUE 14):

  serve.ContinuousEngine  iteration-level batching decode engine:
                        requests admit/retire PER MODEL ITERATION over a
                        slotted KV-cache pool; two fixed-shape compiled
                        programs (prefill + multi-step decode) serve
                        every mixed batch — zero retraces after warmup;
                        deadline-aware slot grants (SLO-aware admission)
  serve.KVCachePool     preallocated `(max_slots+1, layers, max_len,
                        heads, head_dim)` KV slab + claim/free slots;
                        typed `SlotsFullError` on exhaustion
  serve.CachedDecoder   the bundled cached-KV transformer decoder model
                        (greedy, deterministic) the engine drives; see
                        docs/SERVING.md "Continuous batching"

Overload behavior is explicit, not emergent: admission control bounds the
queue (`MXNET_SERVE_MAX_QUEUE`), the overload policy picks reject-newest
or shed-oldest (`MXNET_SERVE_OVERLOAD_POLICY`), per-request deadlines fail
fast with typed errors (`MXNET_SERVE_DEADLINE_MS`), and the
`serve.enqueue` / `serve.execute` / `serve.reply` fault points make every
degraded path deterministically testable via `MXNET_FAULT_SPEC`. See
docs/SERVING.md.
"""
from __future__ import annotations

from ..base import _register_env
from ..telemetry import metrics_text, start_metrics_server
from .batcher import (ServeError, QueueFullError, RequestTimeout,
                      ServerClosed, ReplicaDraining, BucketedModel,
                      CallableModel, Server, pick_bucket)
from .metrics import SERVE_STATS, ServeMetrics, serve_stats as stats
from .kv_pool import (KVCachePool, SlotsFullError, KVPOOL_STATS,
                      kvpool_stats)
from .prefix_cache import (PrefixCache, PrefixCacheError, PREFIX_STATS,
                           prefix_stats)
from .continuous import (ContinuousEngine, CachedDecoder, DecoderConfig,
                         RequestTiming, init_decoder_params)
from .fleet import (Fleet, FleetError, ReplicaDied, FLEET_STATS,
                    fleet_stats)

__all__ = [
    "Server", "BucketedModel", "CallableModel", "pick_bucket",
    "ServeError", "QueueFullError", "RequestTimeout", "ServerClosed",
    "ServeMetrics", "SERVE_STATS", "stats",
    "metrics_text", "start_metrics_server",
    # continuous (iteration-level) batching
    "ContinuousEngine", "CachedDecoder", "DecoderConfig",
    "RequestTiming", "init_decoder_params", "KVCachePool",
    "SlotsFullError",
    "KVPOOL_STATS", "kvpool_stats",
    # shared-prefix KV cache
    "PrefixCache", "PrefixCacheError", "PREFIX_STATS", "prefix_stats",
    # multi-replica serving fleet
    "Fleet", "FleetError", "ReplicaDied", "ReplicaDraining",
    "FLEET_STATS", "fleet_stats",
]

_register_env("MXNET_SERVE_MAX_QUEUE", int, 256,
              "Bound on queued inference requests (admission control)")
_register_env("MXNET_SERVE_BATCH_TIMEOUT_MS", float, 2.0,
              "Max wait to fill a batch after its first request")
_register_env("MXNET_SERVE_DEADLINE_MS", float, None,
              "Default per-request queue deadline (unset = none)")
_register_env("MXNET_SERVE_OVERLOAD_POLICY", str, "reject",
              "Queue-full behavior: 'reject' (newest) or 'shed' (oldest)")
_register_env("MXNET_SERVE_MAX_SLOTS", int, 8,
              "KV-cache slots in the continuous-batching engine = max "
              "concurrently-decoding requests (serve.KVCachePool)")
_register_env("MXNET_SERVE_PREFILL_BUDGET", int, 256,
              "Max prompt tokens prefilled per engine iteration "
              "(bounds prefill's added latency on in-flight decode)")
_register_env("MXNET_SERVE_DECODE_STEPS", int, 4,
              "Decode micro-iterations per compiled dispatch in the "
              "continuous engine (host round-trip amortization)")
_register_env("MXNET_SERVE_PREFILL_LANES", int, None,
              "Fixed lane count of the prefill program (unset = "
              "min(max_slots, 8)); sized to the admission rate")
_register_env("MXNET_SERVE_KV_DTYPE", str, None,
              "KV pool storage dtype ('int8' = quantized codes + "
              "scales; unset = model dtype)")
_register_env("MXNET_SERVE_PREFIX_BLOCK", int, 16,
              "Shared-prefix cache granularity in tokens (prefixes "
              "cache and match on whole blocks only)")
_register_env("MXNET_SERVE_PREFIX_CACHE_SLOTS", int, 0,
              "Dedicated KV-pool rows holding shared-prefix KV for "
              "reuse across requests (0 = prefix cache off)")
_register_env("MXNET_SERVE_PREFIX_CACHE_INSERT", int, 1,
              "Publish a retiring request's own prompt prefix back "
              "into the shared-prefix cache (0 = read-only cache)")
_register_env("MXNET_FLEET_REPLICAS", int, 2,
              "Replica worker processes a serve.Fleet spawns")
_register_env("MXNET_FLEET_HEARTBEAT_MS", float, 500.0,
              "Fleet heartbeat interval; a replica missing "
              "`heartbeat_misses` consecutive beats is declared hung")
_register_env("MXNET_FLEET_RETRY_BUDGET", int, 2,
              "Failover retries per request before the original replica "
              "error surfaces to the client")
_register_env("MXNET_FLEET_DRAIN_TIMEOUT_MS", float, 30000.0,
              "Max wait for a draining replica to finish its resident "
              "requests before the swap hard-stops it (survivors absorb "
              "its in-flight via failover)")
