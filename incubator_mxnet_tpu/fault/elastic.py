"""mx.fault.elastic — ZeRO-sharded data-parallel training that SURVIVES
worker loss: elastic, fault-injected resume across mesh-size changes.

The reference's parameter-server design split optimizer-update work across
server shards and sketched (but never shipped) elasticity on top (PAPER.md
layer 0, ps-lite). This module is the SPMD-era composition of the pieces
the repo already has:

  * optimizer states sharded over the dp mesh axis
    (`optimizer.sharded.ShardedOptimizer`) — each replica owns ``1/dp`` of
    the moments plus a master copy of its parameter slice, so
    optimizer-state memory per replica drops ~linearly with dp;
  * gradient buckets reduce-scattered over dp through the kvstore bucket
    timeline (`kvstore.reduce_scatter_buckets`), dispatched while the
    backward program is still in flight (the PR-3 overlap mechanism);
  * fresh parameters reassembled per step with a bucketed all-gather
    (`kvstore.allgather_buckets`);
  * checkpoints committed PER-SHARD through the MANIFEST.json protocol
    (`checkpoint.save_sharded(extra=...)`) so a SIGKILL mid-epoch resumes
    bit-exact — including onto a DIFFERENT dp size via
    `checkpoint.Repartition`, which re-partitions the optimizer shards,
    not just the params;
  * every collective wrapped in typed timeout/retry/backoff
    (`fault.retrying` semantics; fault points `kvstore.reduce_scatter`,
    `kvstore.allgather`, `elastic.resume`, `elastic.step`,
    `elastic.loss`), with a straggler watchdog that probes each dp rank's
    device and names the one that stalled;
  * graceful degradation: on unrecoverable worker loss `run_elastic`
    SHRINKS the dp mesh, repartitions the intact state (or the last
    committed checkpoint), and continues instead of dying.

Retry safety: unlike the cross-process collectives in `kvstore`'s dist
path (deliberately fail-fast — RESILIENCE.md), the dp axis here is an
in-process SPMD mesh: one host thread drives EVERY rank, so a retry
re-enters the collective for all ranks together and cannot desynchronize
peers. That is why `fault.retrying` wraps these collectives and only
these.

Determinism contract: `batch_fn(step)` must be a pure function of the step
index (draw from a step-seeded RNG) — that is what lets a resumed run, on
the same or a smaller mesh, replay the exact batch sequence the
uninterrupted run saw. `tools/crashtest.py --elastic` proves the resulting
bit-exactness under a real SIGKILL on the 8-way CPU mesh.
"""
from __future__ import annotations

import threading
import time

import numpy as _np

from ..base import MXNetError, get_env
from . import (InjectedFault, WatchdogTimeout, inject,
               loss_is_finite, retrying as _retrying,
               watchdog as _watchdog, _log_event)
from ..telemetry.registry import REGISTRY

__all__ = [
    "ElasticError", "CollectiveTimeout", "StragglerTimeout", "WorkerLost",
    "ElasticTrainer", "ElasticRun", "run_elastic", "straggler_report",
]

from ..base import _register_env

_register_env("MXNET_ELASTIC_COLLECTIVE_TIMEOUT", float, None,
              "Seconds before an elastic trainer collective "
              "(reduce-scatter / all-gather bucket set) aborts with "
              "StragglerTimeout naming the stalled rank (default: no "
              "timeout)")
_register_env("MXNET_ELASTIC_COLLECTIVE_RETRIES", int, 2,
              "Bounded retries for transient elastic-collective errors "
              "(IOError/OSError/TimeoutError); safe in-process because one "
              "host thread drives every dp rank")

ELASTIC_STEPS = REGISTRY.counter(
    "elastic.steps", help="elastic trainer optimizer steps applied")
ELASTIC_RESUMES = REGISTRY.counter(
    "elastic.resumes", help="elastic checkpoint resumes (any mesh size)")
ELASTIC_SHRINKS = REGISTRY.counter(
    "elastic.mesh_shrinks", help="graceful-degradation dp-mesh shrinks")
ELASTIC_SKIPPED = REGISTRY.counter(
    "elastic.skipped_nonfinite", help="steps skipped on non-finite loss")
ELASTIC_RETRIES = REGISTRY.counter(
    "elastic.collective_retries",
    help="transient elastic-collective retries")
ELASTIC_RESUME_US = REGISTRY.gauge(
    "elastic.resume_latency_us",
    help="wall time of the most recent elastic resume (restore + "
         "repartition + first allgather)")
ELASTIC_MEM_BYTES = REGISTRY.gauge(
    "elastic.mem_per_replica_bytes",
    help="optimizer-state bytes (master shards + moments) per replica")
ELASTIC_DP = REGISTRY.gauge(
    "elastic.dp", help="current dp size of the elastic trainer's mesh")

# pre-seed every metric's slot: registry snapshots only emit touched
# metrics, and "elastic.resumes absent because no resume happened yet"
# reads as a registration bug to dashboards (and made tests order-dependent)
for _m in (ELASTIC_STEPS, ELASTIC_RESUMES, ELASTIC_SHRINKS,
           ELASTIC_SKIPPED, ELASTIC_RETRIES):
    _m.inc(0)
for _g in (ELASTIC_RESUME_US, ELASTIC_MEM_BYTES, ELASTIC_DP):
    _g.set(0)
del _m, _g


class ElasticError(MXNetError):
    """Base class for elastic-trainer failures."""


class CollectiveTimeout(ElasticError):
    """A bucketed collective exceeded its configured timeout."""


class StragglerTimeout(CollectiveTimeout):
    """A collective stalled and the per-rank probe attributed (or failed
    to attribute) the straggler. `report` is the full per-rank probe
    result; `stalled_ranks` the ranks whose probe never completed."""

    def __init__(self, message, report=None, stalled_ranks=None):
        super().__init__(message)
        self.report = report or []
        self.stalled_ranks = list(stalled_ranks or [])


class WorkerLost(ElasticError):
    """A dp worker is unrecoverably gone; `run_elastic` shrinks the mesh
    and continues when allowed."""


# errors run_elastic treats as unrecoverable worker loss (InjectedFault is
# the test-harness simulation hook: `kvstore.allgather:3:error` plays a
# rank dying mid-gather)
WORKER_LOSS_ERRORS = (WorkerLost, StragglerTimeout, CollectiveTimeout,
                      InjectedFault)


def _default_probe(rank, device):
    import jax
    x = jax.device_put(_np.float32(rank), device)
    jax.block_until_ready(x + 1.0)


def straggler_report(mesh, axis="dp", probe_timeout=5.0, probe_fn=None):
    """Probe each dp rank's device with a tiny computation under its own
    deadline and report who answered: the attribution half of the
    straggler watchdog. A rank whose probe does not complete within
    `probe_timeout` seconds is reported ``ok: False`` — on a stalled
    barrier that is the rank holding everyone up.

    Returns ``[{"rank", "device", "ok", "ms"}, ...]`` in rank order.
    `probe_fn(rank, device)` overrides the default device probe (tests
    inject a blocking probe to simulate a wedged rank)."""
    jmesh = getattr(mesh, "jax_mesh", mesh)
    names = list(jmesh.axis_names)
    if axis not in names:
        raise MXNetError(f"mesh axes {names} have no {axis!r}")
    devarr = _np.asarray(jmesh.devices)
    moved = _np.moveaxis(devarr, names.index(axis), 0)
    dp = moved.shape[0]
    flat = moved.reshape(dp, -1)
    fn = probe_fn or _default_probe
    probes = []
    for rank in range(dp):
        dev = flat[rank, 0]
        result = {}

        def _go(rank=rank, dev=dev, result=result):
            t0 = time.perf_counter()
            try:
                fn(rank, dev)
                result["ok"] = True
            except Exception as e:
                result["ok"] = False
                result["error"] = repr(e)
            result["ms"] = (time.perf_counter() - t0) * 1e3
        th = threading.Thread(target=_go, daemon=True,
                              name=f"mx-elastic-probe-{rank}")
        th.start()
        probes.append((rank, dev, result, th))
    # ONE shared deadline: all probes started above run concurrently, so
    # a mesh with several wedged ranks still reports in ~probe_timeout,
    # not dp x probe_timeout
    deadline = time.perf_counter() + probe_timeout
    report = []
    for rank, dev, result, th in probes:
        th.join(max(0.0, deadline - time.perf_counter()))
        row = {"rank": rank, "device": str(dev),
               "ok": bool(result.get("ok", False)),
               "ms": round(result.get("ms", probe_timeout * 1e3), 2)}
        if "error" in result:
            row["error"] = result["error"]
        report.append(row)
    return report


def _entry_for_step(directory, step):
    from .. import checkpoint as ckpt
    if step is None:
        return ckpt.latest_entry(directory)
    manifest = ckpt._read_manifest(directory) or {}
    for e in manifest.get("committed", []):
        if e["step"] == step:
            return e
    return None


class ElasticTrainer:
    """ZeRO-1/2-style data-parallel trainer over an in-process dp mesh.

    ``loss_fn(params_dict, batch) -> scalar`` must be pure jax, averaging
    over its (local) batch. Per step:

      1. per-replica forward+backward under `shard_map` (batch sharded on
         dp, params replicated) — grads come out per-replica STACKED;
      2. bucketed `kvstore.reduce_scatter_buckets` (scaled ``1/dp`` =
         gradient mean), dispatched while backward is still in flight;
      3. shard-local optimizer update (`ShardedOptimizer.update` — one
         donated program over every (dp, L) master/moment shard);
      4. bucketed `kvstore.allgather_buckets` reassembles fresh params.

    Construct with `params` for a cold start or via `ElasticTrainer.resume`
    to restore from a manifest-committed sharded checkpoint (any dp size).
    """

    def __init__(self, loss_fn, params=None, optimizer="sgd", dp=None,
                 mesh=None, axis="dp", bucket_bytes=None,
                 collective_timeout=None, collective_retries=None,
                 probe_fn=None, **opt_kwargs):
        import jax
        from ..optimizer.sharded import ShardedOptimizer
        from .. import kvstore as _kv

        self.loss_fn = loss_fn
        self.axis = axis
        if mesh is None:
            from ..parallel import dp_mesh
            mesh = dp_mesh(dp, axis=axis)
        self.jax_mesh = getattr(mesh, "jax_mesh", mesh)
        self.dp = int(self.jax_mesh.shape[axis])
        self.sopt = ShardedOptimizer(optimizer, self.jax_mesh, axis=axis,
                                     **opt_kwargs)
        self._opt_kwargs = dict(opt_kwargs)
        self._optimizer_arg = optimizer
        self.bucket_bytes = int(bucket_bytes or _kv.KVStore._BUCKET_BYTES)
        self.collective_timeout = (
            collective_timeout if collective_timeout is not None
            else get_env("MXNET_ELASTIC_COLLECTIVE_TIMEOUT", typ=float))
        self.collective_retries = int(
            collective_retries if collective_retries is not None
            else get_env("MXNET_ELASTIC_COLLECTIVE_RETRIES", 2, typ=int))
        self._probe_fn = probe_fn
        self._grad_fns = {}
        self._sanitize_armed = False
        self._pending_gather = False
        self._step_idx = 0
        self._overlap_hits = 0
        self._overlap_total = 0
        if params is not None:
            self.wshard, self.meta = self.sopt.shard_params(params)
            self.states = self.sopt.init_states(self.wshard)
            self._names = tuple(sorted(self.wshard))
            self.params = self._allgather_params()
            self._note_shape_metrics()
        else:   # shell for resume()/shrunk() to adopt state into
            self.wshard, self.states, self.meta = {}, {}, {}
            self._names = ()
            self.params = {}

    # ------------------------------------------------------------------
    def _note_shape_metrics(self):
        ELASTIC_DP.set(self.dp)
        ELASTIC_MEM_BYTES.set(self.mem_per_replica_bytes())

    def mem_per_replica_bytes(self):
        """Optimizer-state bytes (master shards + moments) ONE replica
        holds — the ZeRO denominator; measured from real device buffers."""
        return self.sopt.mem_per_replica_bytes(self.wshard, self.states)

    def overlap_fraction(self):
        """Event-based overlap: the fraction of steps whose reduce-scatter
        bucket dispatch completed while the backward program was provably
        still in flight (`Array.is_ready()` on the last gradient). None
        before any step."""
        if not self._overlap_total:
            return None
        return self._overlap_hits / self._overlap_total

    # ------------------------------------------------------------------
    def _collective(self, point, fn):
        """Typed timeout/retry/backoff around one bucketed collective.

        Transient IOError/OSError/TimeoutError retries up to
        `collective_retries` times (safe in-process — one host thread
        drives every rank). A watchdog stall triggers the straggler probe
        and raises StragglerTimeout naming the unresponsive rank(s)."""
        timeout = self.collective_timeout

        def guarded():
            try:
                with _watchdog(timeout,
                               f"elastic {point} exceeded {timeout}s"):
                    return fn()
            except WatchdogTimeout:
                if timeout is None:
                    # OUR watchdog is unarmed: this is an enclosing guard
                    # (run_elastic's watchdog_seconds) firing mid-call —
                    # not a collective stall; let the owner handle it
                    raise
                report = straggler_report(self.jax_mesh, axis=self.axis,
                                          probe_timeout=min(timeout, 5.0),
                                          probe_fn=self._probe_fn)
                stalled = [r["rank"] for r in report if not r["ok"]]
                who = (f"rank(s) {stalled} unresponsive" if stalled
                       else "every rank answered the probe "
                            "(transient stall)")
                # black-box the attribution before the raise unwinds:
                # the flight recorder names the stalled rank(s) even if
                # the driver's recovery path swallows this exception
                try:
                    from ..telemetry.trace import (flightrec_record,
                                                   flightrec_maybe_dump)
                    flightrec_record("collective_timeout", point,
                                     stalled_ranks=stalled, dp=self.dp,
                                     timeout_s=timeout)
                    flightrec_maybe_dump("straggler")
                except Exception:
                    pass
                raise StragglerTimeout(
                    f"collective {point!r} stalled past {timeout:.3g}s; "
                    f"{who}", report=report, stalled_ranks=stalled)

        def _count(attempt, error):
            ELASTIC_RETRIES.inc()

        return _retrying(max_attempts=self.collective_retries + 1,
                         backoff=0.05,
                         retry_on=(IOError, OSError, TimeoutError),
                         name=f"elastic.{point}", on_retry=_count)(guarded)()

    def _allgather_params(self):
        from .. import kvstore as _kv
        names = self._names
        shards = [self.wshard[n] for n in names]
        metas = [(self.meta[n]["numel"], tuple(self.meta[n]["shape"]))
                 for n in names]
        outs = self._collective(
            "allgather",
            lambda: _kv.allgather_buckets(shards, metas, self.jax_mesh,
                                          axis=self.axis,
                                          bucket_bytes=self.bucket_bytes))
        params = dict(zip(names, outs))
        # census attribution (mx.inspect.memory): the replicated working
        # params are the third leg of the elastic resident set next to
        # the optimizer_shards the ShardedOptimizer registers
        try:
            from ..inspect import memory as _mem
            _mem.register(params, owner="elastic_params")
        except Exception:
            pass
        return params

    def memory_plans(self):
        """Memory plans of the cached bucketed reduce-scatter/all-gather
        programs this trainer's steps dispatch
        (`mx.inspect.memory.collective_memory_plans`): run at least one
        step first so the programs exist."""
        from ..inspect.memory import collective_memory_plans
        return collective_memory_plans()

    # ------------------------------------------------------------------
    def _stage_batch(self, batch):
        import jax
        import jax.tree_util as jtu
        from jax.sharding import NamedSharding, PartitionSpec as P

        def stage(leaf):
            a = _np.asarray(leaf) if not hasattr(leaf, "ndim") else leaf
            if getattr(a, "ndim", 0) < 1 or a.shape[0] % self.dp:
                raise MXNetError(
                    f"batch leaves need a leading axis divisible by "
                    f"dp={self.dp}, got {getattr(a, 'shape', None)}")
            sh = NamedSharding(self.jax_mesh,
                               P(self.axis, *([None] * (a.ndim - 1))))
            return jax.device_put(a, sh)
        return jtu.tree_map(stage, batch)

    def _grad_fn_for(self, staged):
        import jax
        import jax.tree_util as jtu
        from jax.sharding import PartitionSpec as P
        from ..parallel import shard_map as _shard_map

        leaves, treedef = jtu.tree_flatten(staged)
        key = (treedef, tuple((tuple(l.shape), str(l.dtype))
                              for l in leaves))
        fn = self._grad_fns.get(key)
        if fn is not None:
            return fn
        names = self._names
        loss_fn = self.loss_fn
        axis = self.axis
        pshapes = [tuple(self.meta[n]["shape"]) for n in names]

        def body(plist, batch_local):
            def f(pl):
                return loss_fn(dict(zip(names, pl)), batch_local)
            loss, grads = jax.value_and_grad(f)(list(plist))
            # stack per-replica results along a fresh dp-sharded axis
            return ((loss.reshape(1),)
                    + tuple(g.reshape((1,) + tuple(g.shape))
                            for g in grads))

        in_specs = ([P()] * len(names),
                    jtu.tree_unflatten(treedef, [
                        P(axis, *([None] * (l.ndim - 1))) for l in leaves]))
        out_specs = ((P(axis),)
                     + tuple(P(axis, *([None] * len(s)))
                             for s in pshapes))
        fn = jax.jit(_shard_map(body, self.jax_mesh, in_specs, out_specs))
        self._grad_fns[key] = fn
        return fn

    def forward_backward(self, batch):
        """Per-replica backward + bucketed reduce-scatter; returns
        (loss, gshards) with gshards in the (dp, L) shard layout the
        update consumes. The loss read is the step's only sync point —
        reduce-scatter buckets dispatch while backward is in flight."""
        from .. import kvstore as _kv
        from ..telemetry import span as _span
        with _span("elastic.step", step=self._step_idx):
            staged = self._stage_batch(batch)
            fn = self._grad_fn_for(staged)
            outs = fn([self.params[n] for n in self._names], staged)
            losses, grads = outs[0], list(outs[1:])
            sentinel = grads[-1] if grads else losses
            gshards = self._collective(
                "reduce_scatter",
                lambda: _kv.reduce_scatter_buckets(
                    grads, self.jax_mesh, axis=self.axis,
                    scale=1.0 / self.dp, bucket_bytes=self.bucket_bytes))
            # event-based overlap sample: backward still in flight when
            # the reduce-scatter buckets finished dispatching?
            self._overlap_total += 1
            try:
                if not sentinel.is_ready():
                    self._overlap_hits += 1
            except Exception:
                pass
            loss = float(_np.mean(_np.asarray(losses)))
            return loss, dict(zip(self._names, gshards))

    def apply(self, gshards=None):
        """Shard update + parameter all-gather. Two-phase on purpose: if a
        worker is lost DURING the gather (post-update), the pending flag
        lets the shrunk trainer finish with a re-gather only — replaying
        the whole step would double-apply the donated update."""
        if self._pending_gather:
            if gshards is not None:
                # completing a pending gather consumes NO gradients: a
                # caller handing fresh ones in expects an update — losing
                # them silently would drop an optimizer step
                raise MXNetError(
                    "a previous apply() was interrupted mid-gather: call "
                    "apply(None) to complete it before stepping again")
        else:
            if gshards is None:
                raise MXNetError("apply(None) is only valid to complete a "
                                 "pending gather after worker loss")
            self.wshard, self.states = self.sopt.update(
                self.wshard, gshards, self.states)
            self._pending_gather = True
        self.params = self._allgather_params()
        self._pending_gather = False
        self._step_idx += 1
        ELASTIC_STEPS.inc()

    def step(self, batch):
        """One full elastic step; returns the (host) mean loss."""
        from .. import sanitize as _sanitize
        loss, gshards = self.forward_backward(batch)
        self.apply(gshards)
        if _sanitize.enabled("retrace"):
            # the first step compiles the grad + sharded-update programs;
            # from the second on, any growth is a retrace-hazard breach.
            # A shrunk() trainer is a NEW instance, so it re-arms over its
            # own fresh programs after its own first step.
            if not self._sanitize_armed:
                _sanitize.arm()
                self._sanitize_armed = True
            else:
                _sanitize.poll(where="elastic.step")
        return loss

    # ------------------------------------------------------------------
    # checkpoint / resume / shrink
    # ------------------------------------------------------------------
    def state_arrays(self):
        """{name: full np param} reassembled from the master shards."""
        from ..optimizer.sharded import from_shards
        return {n: from_shards(_np.asarray(self.wshard[n]),
                               self.meta[n]["numel"],
                               tuple(self.meta[n]["shape"]))
                for n in self._names}

    def opt_arrays(self):
        """{name: state tree of np arrays} param-shaped, unpadded — the
        checkpoint-parity view of the sharded moments."""
        from ..optimizer.sharded import from_shards

        def conv(st, n):
            if st is None:
                return None
            if isinstance(st, tuple):
                return tuple(conv(s, n) for s in st)
            return from_shards(_np.asarray(st), self.meta[n]["numel"],
                               tuple(self.meta[n]["shape"]))
        return {n: conv(self.states[n], n) for n in self._names}

    def save(self, directory, step=None, keep_last=None, extra=None):
        """Commit the sharded state through the MANIFEST.json protocol:
        shard data via orbax (each host writes its shards), layout +
        counters in the manifest entry, atomically with the step."""
        from .. import checkpoint as ckpt
        from ..optimizer.sharded import state_layout
        step = self._step_idx if step is None else step
        tree = {"wshard": dict(self.wshard)}
        opt = {n: self.states[n] for n in self._names
               if self.states[n] is not None}
        if opt:
            tree["opt"] = opt
        manifest_extra = {
            "elastic": {
                "version": 1,
                "dp": self.dp,
                "axis": self.axis,
                "optimizer": type(self.sopt.base).__name__,
                "trainer_step": self._step_idx,
                "meta": self.meta,
                "layout": {n: state_layout(self.states[n])
                           for n in self._names},
                # Adam-family bias correction: per-param update counts
                # must survive a resume or t restarts at 1
                "update_counts": {str(k): int(v) for k, v in
                                  self.sopt.base._index_update_count
                                  .items()},
                "num_update": int(self.sopt.base.num_update),
            }}
        if extra:
            manifest_extra.update(extra)
        return ckpt.save_sharded(directory, tree, step=step,
                                 keep_last=keep_last, extra=manifest_extra)

    @classmethod
    def resume(cls, directory, loss_fn, optimizer="sgd", dp=None,
               mesh=None, step=None, **kw):
        """Restore from the newest committed checkpoint onto a mesh of
        `dp` devices — the SAME size for a plain restart, a DIFFERENT
        size for elastic restart (`checkpoint.Repartition` re-slices every
        master/moment shard onto the new dp). Returns
        (trainer, completed_step, manifest_extra)."""
        from .. import checkpoint as ckpt
        from ..optimizer.sharded import layout_spec_tree
        from jax.sharding import PartitionSpec as P

        t0 = time.perf_counter()
        inject("elastic.resume")
        entry = _entry_for_step(directory, step)
        if entry is None:
            raise MXNetError(f"no committed checkpoint under {directory!r}"
                             + (f" at step {step}" if step else ""))
        em = (entry.get("extra") or {}).get("elastic")
        if not em:
            raise MXNetError(
                f"checkpoint step {entry['step']} in {directory!r} has no "
                "elastic metadata; was it written by ElasticTrainer.save?")
        opt_name = (optimizer if isinstance(optimizer, str)
                    else type(optimizer).__name__).lower()
        if opt_name != str(em["optimizer"]).lower():
            raise MXNetError(
                f"checkpoint was written by {em['optimizer']}, resume "
                f"requested {opt_name}: pass the matching optimizer")
        self = cls(loss_fn, params=None, optimizer=optimizer, dp=dp,
                   mesh=mesh, axis=em["axis"], **kw)
        self.meta = {n: dict(m) for n, m in em["meta"].items()}
        self._names = tuple(sorted(self.meta))
        old_dp = int(em["dp"])

        def leaf_spec(name):
            if self.dp == old_dp:
                return P(self.axis, None)
            return ckpt.Repartition(self.meta[name]["numel"],
                                    axis=self.axis)

        specs = {"wshard": {n: leaf_spec(n) for n in self._names}}
        layout = em.get("layout") or {}
        opt_specs = {n: layout_spec_tree(layout[n],
                                         lambda n=n: leaf_spec(n))
                     for n in layout if layout.get(n) is not None}
        if opt_specs:
            specs["opt"] = opt_specs
        tree, got_step = ckpt.rescale_sharded(directory, self.jax_mesh,
                                              specs, step=entry["step"])
        self.wshard = {n: tree["wshard"][n] for n in self._names}
        restored_opt = tree.get("opt") or {}
        self.states = {n: self.sopt._tuplify(restored_opt[n])
                       if n in restored_opt else None
                       for n in self._names}
        self._step_idx = int(em.get("trainer_step", got_step))
        self.sopt.base._index_update_count.update(
            {k: int(v) for k, v in (em.get("update_counts") or {}).items()})
        self.sopt.base.num_update = int(em.get("num_update", 0))
        self.params = self._allgather_params()
        dur_us = (time.perf_counter() - t0) * 1e6
        ELASTIC_RESUMES.inc()
        ELASTIC_RESUME_US.set(dur_us)
        self._note_shape_metrics()
        _log_event("elastic.resumed", dir=directory, step=got_step,
                   dp=self.dp, old_dp=old_dp, latency_us=round(dur_us, 1))
        return self, got_step, entry.get("extra") or {}

    def shrunk(self, new_dp):
        """Graceful degradation: repartition the INTACT in-memory state
        onto a `new_dp`-device mesh and return the new trainer (the old
        one's buffers are host-copied first, so a half-donated update can
        never be torn). Works for growth too."""
        from ..optimizer.sharded import repartition

        if new_dp == self.dp:
            return self
        host_w = {n: _np.asarray(self.wshard[n]) for n in self._names}
        host_s = {n: self._host_state(self.states[n])
                  for n in self._names}
        new = type(self)(self.loss_fn, params=None,
                         optimizer=self._optimizer_arg, dp=new_dp,
                         axis=self.axis, bucket_bytes=self.bucket_bytes,
                         collective_timeout=self.collective_timeout,
                         collective_retries=self.collective_retries,
                         probe_fn=self._probe_fn, **self._opt_kwargs)
        new.meta = {n: dict(m) for n, m in self.meta.items()}
        new._names = self._names
        new.wshard = {
            n: new.sopt.place(repartition(host_w[n],
                                          self.meta[n]["numel"], new_dp))
            for n in self._names}

        def place_state(st, numel):
            if st is None:
                return None
            if isinstance(st, tuple):
                return tuple(place_state(s, numel) for s in st)
            return new.sopt.place(repartition(st, numel, new_dp))
        new.states = {n: place_state(host_s[n], self.meta[n]["numel"])
                      for n in self._names}
        # the base optimizer's per-param step counts ride along so Adam
        # bias correction stays continuous across the shrink
        new.sopt.base._index_update_count.update(
            self.sopt.base._index_update_count)
        new.sopt.base.num_update = self.sopt.base.num_update
        new._step_idx = self._step_idx
        new._pending_gather = self._pending_gather
        if self._pending_gather:
            # the caller's next apply(None) gathers anyway — doing it
            # here too would run the most expensive collective twice on
            # the degraded path; carry the (pre-update) params as a
            # placeholder until then
            new.params = dict(self.params)
        else:
            new.params = new._allgather_params()
        ELASTIC_SHRINKS.inc()
        new._note_shape_metrics()
        _log_event("elastic.shrunk", old_dp=self.dp, new_dp=new_dp,
                   step=self._step_idx)
        return new

    @staticmethod
    def _host_state(st):
        if st is None:
            return None
        if isinstance(st, tuple):
            return tuple(ElasticTrainer._host_state(s) for s in st)
        return _np.asarray(st)


class ElasticRun:
    """Result of run_elastic: final trainer + elasticity accounting."""

    def __init__(self):
        self.trainer = None
        self.step = 0
        self.resumed_from = None
        self.resumed_dp = None
        self.saved_steps = []
        self.skipped_nonfinite = 0
        self.shrinks = 0
        self.dp_history = []
        self.losses = []

    def params(self):
        return self.trainer.state_arrays()

    def opt_state(self):
        return self.trainer.opt_arrays()

    def __repr__(self):
        return (f"ElasticRun(step={self.step}, "
                f"resumed_from={self.resumed_from}, dp_history="
                f"{self.dp_history}, shrinks={self.shrinks}, "
                f"skipped_nonfinite={self.skipped_nonfinite})")


def run_elastic(loss_fn, params, batch_fn, ckpt_dir, num_steps, *,
                optimizer="sgd", dp=None, axis="dp", ckpt_every=10,
                keep_last=3, skip_nonfinite=True, min_dp=1,
                shrink_on_worker_loss=True, shrink_to=None,
                worker_loss_errors=WORKER_LOSS_ERRORS,
                collective_timeout=None, collective_retries=None,
                watchdog_seconds=None, probe_fn=None, **opt_kwargs):
    """The elastic training driver: `run_resilient`'s recovery contract on
    top of the ZeRO-sharded `ElasticTrainer`.

      - on entry, a committed checkpoint in `ckpt_dir` resumes the run —
        onto `dp` devices, whatever dp it was SAVED under (shard
        repartition included); the passed `params` are only the
        cold-start value;
      - `batch_fn(step) -> batch` must be deterministic in `step` (the
        replay-parity contract; see the module docstring);
      - non-finite losses skip the update but advance the step index,
        crash-consistently (the count is persisted in the manifest);
      - unrecoverable worker loss (`worker_loss_errors`) SHRINKS the dp
        mesh — default HALVING (keeps any even global batch divisible;
        `shrink_to=lambda dp: dp - 1` for one-rank-at-a-time when the
        batch allows) — repartitions state, and RETRIES the same step,
        until `min_dp` would be violated;
      - checkpoints commit every `ckpt_every` steps through the manifest
        protocol. Returns an ElasticRun.
    """
    from .. import checkpoint as ckpt
    from ..telemetry import (install_crash_hooks, mem_install_oom_hook,
                             mem_on_oom, span as _span)

    # an elastic run should always leave a black box (hooks are no-ops
    # unless MXNET_FLIGHTREC_DIR is set) — the memory one included: an
    # uncaught RESOURCE_EXHAUSTED dumps census + plans on the way down
    install_crash_hooks()
    mem_install_oom_hook()
    run = ElasticRun()
    shrink_to = shrink_to or (lambda d: d // 2)
    kw = dict(collective_timeout=collective_timeout,
              collective_retries=collective_retries, probe_fn=probe_fn)

    if ckpt.latest_step(ckpt_dir) is not None:
        resume = _retrying(max_attempts=2, backoff=0.05,
                           name="elastic.resume")(ElasticTrainer.resume)
        trainer, completed, extra = resume(ckpt_dir, loss_fn,
                                           optimizer=optimizer, dp=dp,
                                           **kw, **opt_kwargs)
        saved = extra.get("elastic_run") or {}
        run.skipped_nonfinite = int(saved.get("skipped_nonfinite", 0))
        run.shrinks = int(saved.get("shrinks", 0))
        run.resumed_from = completed
        run.resumed_dp = trainer.dp
    else:
        trainer = ElasticTrainer(loss_fn, params, optimizer=optimizer,
                                 dp=dp, axis=axis, **kw, **opt_kwargs)
        completed = 0
    run.dp_history.append(trainer.dp)

    def _save(step_no):
        extra = {"elastic_run": {"skipped_nonfinite": run.skipped_nonfinite,
                                 "shrinks": run.shrinks}}
        trainer.save(ckpt_dir, step=step_no, keep_last=keep_last,
                     extra=extra)
        run.saved_steps.append(step_no)
        _log_event("elastic.saved", dir=ckpt_dir, step=step_no,
                   dp=trainer.dp)

    save_retrying = _retrying(max_attempts=3, backoff=0.05,
                              name="elastic.checkpoint")(_save)

    step = completed
    while step < num_steps:
        try:
            # span OUTSIDE the watchdog: the span_open flight-recorder
            # event (step + dp) hits the spool before the step body runs,
            # so a SIGKILL mid-step leaves a black box naming the
            # in-flight step and mesh (crashtest --flightrec asserts it)
            with _span("elastic.step", step=step, dp=trainer.dp), \
                    _watchdog(watchdog_seconds):
                if trainer._pending_gather:
                    # worker lost mid-gather last attempt: the donated
                    # update already happened — finish the gather only
                    trainer.apply(None)
                else:
                    inject("elastic.step")
                    loss, gshards = trainer.forward_backward(
                        batch_fn(step))
                    loss = inject("elastic.loss", loss)
                    if skip_nonfinite and not loss_is_finite(loss):
                        run.skipped_nonfinite += 1
                        ELASTIC_SKIPPED.inc()
                        _log_event("elastic.skipped_nonfinite", step=step)
                    else:
                        trainer.apply(gshards)
                        run.losses.append(loss)
        except worker_loss_errors as e:
            # keep shrinking toward min_dp: the shrink itself runs a
            # collective (the repartitioned state's first allgather), so
            # a worker that stays dead fails it too — that must degrade
            # further, not abort the recovery
            err, target = e, trainer.dp
            while True:
                target = shrink_to(target)
                if not shrink_on_worker_loss or target < min_dp:
                    raise err
                _log_event("elastic.worker_loss", step=step,
                           error=repr(err), old_dp=trainer.dp,
                           new_dp=target)
                try:
                    trainer = trainer.shrunk(target)
                except worker_loss_errors as again:
                    err = again
                    continue
                break
            run.shrinks += 1
            run.dp_history.append(target)
            continue    # retry the SAME step on the smaller mesh
        except BaseException as e:
            # not a worker loss: before the error unwinds, an OOM-shaped
            # failure (RESOURCE_EXHAUSTED mid-step) leaves the memory
            # black box naming the top owners (no-op, and
            # exception-proof, for every other error)
            mem_on_oom(e, where="elastic.step")
            raise
        step += 1
        if step % ckpt_every == 0 or step == num_steps:
            save_retrying(step)

    run.trainer = trainer
    run.step = num_steps
    return run
