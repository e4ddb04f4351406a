"""FusedTrainStep — one XLA program for fwd + loss + bwd + clip + update.

TPU-native counterpart of the reference's fused-RNN training capability
(src/operator/rnn.cc: the whole BPTT step as one kernel) generalized to ANY
HybridBlock: the forward, the loss, the backward, global-norm clipping and
the optimizer update all compile into a single jitted computation with
donated parameter/state buffers. No per-op dispatch, no per-step tape, no
host round-trips inside the step.

    step = FusedTrainStep(net, fn, optimizer)        # fn(net, *inputs)
    loss, *extras = step(x, y, ...)                  # one XLA execution

`fn` receives the live net and the step inputs and returns a scalar loss
NDArray (or a tuple (loss, *extras) — extras pass through untouched, e.g.
recurrent states). Optimizers whose `step_one` kernels are pure traceable
functions work (the same eligibility as the multi-tensor fused update
path); host-stateful rules (SGLD, Nadam) and multi_precision are
rejected at construction — use gluon.Trainer for those.

Input staging: host-array inputs start an ASYNC device transfer before the
dispatch; inputs that are already committed device arrays with the right
placement (an `io.DeviceFeed`-staged batch) skip the transfer — feed the
step through `io.prefetch_to_device(loader)` and batch N+1's host prep +
H2D overlaps batch N's compute (`max(data, step)` instead of their sum).
"""
from __future__ import annotations

import numpy as _np

from ...base import MXNetError
from ...ops import fused as _fused_mod
from ...telemetry import span as _span, NO_SPAN, trace as _trace

# "argument not given" marker for knobs whose default is a real value
# (None = XLA-default remat, True = donate) — lets the mx.tune profile
# tier slot in UNDER an explicit argument but OVER the built-in default
_TUNE_UNSET = object()

__all__ = ["FusedTrainStep", "FusedInferStep"]

_staging = None   # (jax.Array, maybe_device_put), resolved on first step


def _stage_raw(r):
    """Per-input staging for the step hot path: async H2D for host arrays,
    skip for committed device arrays (io.DeviceFeed batches), raw scalars
    untouched. Imports resolve ONCE — this runs per input per step."""
    global _staging
    if _staging is None:
        import jax
        from ...io.device_feed import maybe_device_put
        _staging = (jax.Array, maybe_device_put)
    arr_t, put = _staging
    return put(r) if isinstance(r, (arr_t, _np.ndarray)) else r


class FusedInferStep:
    """One jitted XLA program per inference step, chained elision-proof.

    The compiled step maps ``x -> (logits, x_next)`` where ``x_next`` is the
    donated input perturbed by a scalar derived from the logits. The data
    dependence means step N+1 cannot begin before step N produced its
    output and no step can be elided, while the host
    never blocks between dispatches — per-dispatch latency overlaps with
    device compute exactly like the fused training chain.

        step = FusedInferStep(net)
        out = step(x0)          # seed the chain
        for _ in range(n - 1):
            out = step()        # continue the chain, one dispatch each
        out.asnumpy()           # sync: forces the whole chain

    Reference counterpart: scoring-mode CachedOp dispatch
    (src/imperative/cached_op.cc Forward) — here the entire net is one XLA
    executable and consecutive calls pipeline through donated buffers.
    """

    def __init__(self, net, perturb=1e-6, steps_per_call=1,
                 use_fusion=None):
        params = [p for _, p in sorted(net.collect_params().items())]
        for p in params:
            if p._data is None:
                raise MXNetError(
                    "FusedInferStep needs a fully initialized net: run one "
                    "forward pass first")
        self._net = net
        self._params = params
        self._perturb = perturb
        self._K = int(steps_per_call)   # K chained forwards per dispatch
        # fused kernel tier (ops/fused.py): default on for the fused steps
        # per MXNET_USE_FUSION; the scope engages at trace time
        self._use_fusion = _fused_mod._env_use_fusion() \
            if use_fusion is None else bool(use_fusion)
        self._jit = None
        self._x = None
        self._pnds = None

    def _build(self):
        import jax
        import jax.numpy as jnp
        from ... import autograd, random as _random
        from ...ndarray import _wrap

        net, params, eps, n_steps = (self._net, self._params, self._perturb,
                                     self._K)
        use_fusion = self._use_fusion

        def one(pbufs, x):
            saved = []
            for p, buf in zip(params, pbufs):
                nd = p.data()
                saved.append(nd._data)
                nd._data = buf
                nd._version += 1
            try:
                key = jax.random.PRNGKey(0)  # inference: dropout inactive
                with autograd._Scope(recording=False, training=False), \
                        _random.trace_key_scope(key), \
                        _fused_mod.fusion_scope(use_fusion):
                    out = net(_wrap(x))
                logits = out._arr
            finally:
                for p, old in zip(params, saved):
                    # deliberate trace-time buffer swap: params point at the
                    # jit args during net(x), restored before tracing ends
                    p.data()._data = old  # mxlint: disable=trace-closure-mutation
            x_next = x + (eps * jnp.mean(logits)).astype(x.dtype)
            return logits, x_next

        def step(pbufs, x):
            if n_steps == 1:
                return one(pbufs, x)

            def body(carry, _):
                logits, x_next = one(pbufs, carry)
                return x_next, logits

            x_final, logits_all = jax.lax.scan(body, x, None,
                                               length=n_steps)
            return logits_all[-1], x_final

        from ... import sanitize as _sanitize
        return _sanitize.maybe_wrap_donated(
            jax.jit(step, donate_argnums=(1,)), (1,),
            "fused.chain_step")

    def lowered(self, x=None):
        """The chained-inference program lowered for inspection
        (`mx.inspect.inspect_step(step, x0)`) without executing or
        consuming the chain state. `x` may be omitted once the chain is
        seeded."""
        from ...ndarray import NDArray
        if self._jit is None:
            self._jit = self._build()
            self._pnds = [p.data() for p in self._params]
        if x is not None:
            raw = x._arr if isinstance(x, NDArray) else x
        elif self._x is not None:
            raw = self._x
        else:
            raise MXNetError("FusedInferStep.lowered needs an input: "
                             "pass x or seed the chain with step(x0)")
        pbufs = [nd._arr for nd in self._pnds]
        return self._jit.lower(pbufs, raw)

    def __call__(self, x=None):
        import jax.numpy as jnp
        from ...ndarray import NDArray, _wrap
        if self._jit is None:
            self._jit = self._build()
            self._pnds = [p.data() for p in self._params]
        if x is not None:
            raw = x._arr if isinstance(x, NDArray) else x
            # the chain buffer is donated every step — seed with a COPY so
            # the caller's array stays valid (and re-seeding works)
            self._x = jnp.array(raw, copy=True)
        if self._x is None:
            raise MXNetError("seed the chain: step(x0) before step()")
        pbufs = [nd._arr for nd in self._pnds]
        logits, self._x = self._jit(pbufs, self._x)
        return _wrap(logits)


class FusedTrainStep:
    """One XLA program per call; with ``steps_per_call=K`` the program runs K
    full train steps via ``lax.scan`` (weights/optimizer-state/BN-stats carry
    on device) — the standard TPU host-loop-elimination pattern: per-dispatch
    host latency amortizes K-fold, which is what bounds small-batch
    throughput. Inputs then take a leading (K, ...)
    axis. The learning rate is resolved once per call (per-step schedules
    advance by optimizer update count as usual; within one call the lr is a
    trace constant, like the reference's update_on_kvstore batching)."""

    def __init__(self, net, fn, optimizer, clip_global_norm=None,
                 steps_per_call=1, remat=_TUNE_UNSET, donate=_TUNE_UNSET,
                 use_fusion=None):
        from ... import optimizer as opt_mod
        from ...tune.profile import resolve as _tune_resolve
        # knob precedence: explicit arg > deployment profile > default.
        # `None` is a meaningful remat policy (XLA default), so "caller
        # said nothing" needs its own sentinel for the profile tier.
        if remat is _TUNE_UNSET:
            remat = _tune_resolve("train.remat")
        if donate is _TUNE_UNSET:
            donate = _tune_resolve("train.donate", True)
        optimizer = opt_mod.create(optimizer)
        # same eligibility rules as the multi-tensor fused path
        # (optimizer/__init__.py fused_update_all): host-stateful rules
        # (SGLD's per-step noise key, Nadam's m_schedule) would be baked
        # in as trace-time constants, multi-precision needs the
        # update_multi_precision flow, and subclasses overriding update()
        # expect to be called per-param on the host.
        if not getattr(optimizer, "_fused_safe", True):
            raise MXNetError(
                f"{type(optimizer).__name__} keeps per-step host state and "
                "cannot be traced into one program; use gluon.Trainer")
        if optimizer.multi_precision:
            raise MXNetError(
                "multi_precision optimizers are not supported by "
                "FusedTrainStep yet; use gluon.Trainer")
        if (type(optimizer).update is not opt_mod.Optimizer.update
                or type(optimizer).update_multi_precision
                is not opt_mod.Optimizer.update_multi_precision):
            raise MXNetError(
                f"{type(optimizer).__name__} overrides update(); the "
                "extension point runs per-param on the host — use "
                "gluon.Trainer")
        self._net = net
        self._fn = fn
        self._opt = optimizer
        self._clip = clip_global_norm
        # remat: trade FLOPs for HBM traffic on the backward's saved
        # residuals — None (XLA default), "full" (recompute the whole
        # forward; near-zero residual traffic), "dots" (save matmul
        # outputs, recompute elementwise/conv chains). Which wins is
        # hardware-bound; not measured on the chip (no cell sets it).
        if remat not in (None, "full", "dots"):
            raise MXNetError(f"unknown remat policy {remat!r}")
        self._remat = remat
        # donate: hand the trainable weight + optimizer-state buffers to
        # XLA (in-place update, halves the peak weight footprint). The
        # off switch is for program shapes that schedule better without
        # donation aliasing.
        self._donate = bool(donate)
        # fused kernel tier (ops/fused.py) — default ON for the fused
        # step per MXNET_USE_FUSION; the scope engages around the
        # forward trace so gluon blocks route through the fused ops
        self._use_fusion = _fused_mod._env_use_fusion() \
            if use_fusion is None else bool(use_fusion)
        self._K = int(steps_per_call)
        if self._K < 1:
            raise MXNetError("steps_per_call must be >= 1")
        params = [p for _, p in sorted(net.collect_params().items())]
        for p in params:
            if p._data is None:
                raise MXNetError(
                    "FusedTrainStep needs a fully initialized net: run one "
                    "forward pass first (deferred shapes must be resolved)")
        self._params = params
        # same per-parameter lr_mult/wd_mult plumbing as gluon.Trainer
        # (trainer.py:48-57): _get_lr/_get_wd resolve multipliers through
        # optimizer.param_dict
        optimizer.param_dict = {i: p for i, p in enumerate(params)}
        self._train_idx = [i for i, p in enumerate(params)
                           if p.grad_req != "null"]
        self._frozen_idx = [i for i, p in enumerate(params)
                            if p.grad_req == "null"]
        self._states = None
        self._jit = None
        self._registered = False        # `_run`'s own: `lowered` may come first
        self._calls = 0                 # the live `train.step` span's number
        self._meta = {"aux_idx": None}  # frozen params mutated in forward

    # ------------------------------------------------------------------
    def _ensure_states(self):
        if self._states is None:
            self._states = [
                self._opt.create_state_multi_precision(
                    i, self._params[i].data())
                for i in self._train_idx]

    def _build(self):
        import jax
        import jax.numpy as jnp
        from ... import autograd, random as _random
        from ...ndarray import _wrap
        from ...optimizer import _state_bufs, _wrap_state

        params = self._params
        train_idx, frozen_idx = self._train_idx, self._frozen_idx
        net, fn, opt, clip = self._net, self._fn, self._opt, self._clip
        takes_t = type(opt)._step_takes_t()
        meta = self._meta

        n_steps = self._K
        frozen_pos = {i: k for k, i in enumerate(frozen_idx)}
        use_fusion = self._use_fusion

        def one_step(train_bufs, sbufs, frozen_bufs, key, lrs, wds, rescale,
                     ts, in_raw):
            def loss_of(tbufs):
                full = [None] * len(params)
                for k, i in enumerate(train_idx):
                    full[i] = tbufs[k]
                for k, i in enumerate(frozen_idx):
                    full[i] = frozen_bufs[k]
                saved = []
                for p, buf in zip(params, full):
                    nd = p.data()
                    saved.append(nd._data)
                    nd._data = buf
                    nd._version += 1
                try:
                    with autograd._Scope(recording=False, training=True), \
                            _random.trace_key_scope(key), \
                            _fused_mod.fusion_scope(use_fusion), \
                            jax.named_scope("forward"):
                        out = fn(net, *[_wrap(r) for r in in_raw])
                    if isinstance(out, (tuple, list)):
                        loss, extras = out[0], tuple(out[1:])
                    else:
                        loss, extras = out, ()
                    loss_raw = loss._arr
                    extras_raw = tuple(e._arr for e in extras)
                    # aux state written during forward (BN running stats
                    # live on grad_req='null' params); which indices mutate
                    # is a trace-time constant, recorded once in meta
                    mutated = {}
                    for i, (p, buf) in enumerate(zip(params, full)):
                        cur = p.data()._data
                        if cur is not buf:
                            mutated[i] = cur
                    if meta["aux_idx"] is None:
                        # trace-time memo by design (see comment above)
                        meta["aux_idx"] = tuple(sorted(mutated))  # mxlint: disable=trace-closure-mutation
                    aux_bufs = tuple(mutated[i] for i in sorted(mutated))
                finally:
                    for p, old in zip(params, saved):
                        # deliberate trace-time buffer swap (see ChainStep)
                        p.data()._data = old  # mxlint: disable=trace-closure-mutation
                return loss_raw, (extras_raw, aux_bufs)

            # prevent_cse=False: we are always under jit (and under scan
            # for K>1), where the CSE-prevention barriers are unnecessary
            # and would slow the remat'd program (jax.checkpoint docs)
            if self._remat == "full":
                loss_of = jax.checkpoint(
                    loss_of, policy=jax.checkpoint_policies.nothing_saveable,
                    prevent_cse=False)
            elif self._remat == "dots":
                loss_of = jax.checkpoint(
                    loss_of, policy=jax.checkpoint_policies.dots_saveable,
                    prevent_cse=False)
            # the backward pass carries the forward's scope under jax's
            # own transform names: `transpose(jvp(forward))`
            (loss, (extras, aux_bufs)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(list(train_bufs))

            if clip is not None:
                total = jnp.zeros((), jnp.float32)
                for g in grads:
                    total = total + jnp.sum(jnp.square(g.astype(jnp.float32)))
                norm = jnp.sqrt(total)
                scale = jnp.minimum(
                    1.0, clip / jnp.maximum(norm, 1e-12))
                grads = [g * scale.astype(g.dtype) for g in grads]

            prev = opt.rescale_grad
            # deliberate trace-time swap (inner kernels key on the traced
            # rescale), restored in finally below
            opt.rescale_grad = rescale  # mxlint: disable=trace-closure-mutation
            try:
                new_w, new_s = [], []
                with jax.named_scope("update"):
                    for k, i in enumerate(train_idx):
                        w = _wrap(train_bufs[k])
                        g = _wrap(grads[k])
                        st = _wrap_state(sbufs[k])
                        if takes_t:
                            opt.step_one(i, w, g, st, lrs[k], wds[k],
                                         t=ts[k])
                        else:
                            opt.step_one(i, w, g, st, lrs[k], wds[k])
                        new_w.append(w._arr)
                        new_s.append(_state_bufs(st))
            finally:
                opt.rescale_grad = prev  # mxlint: disable=trace-closure-mutation -- restore of the trace-time swap
            # fold BN-stat updates back into the frozen set so a scanned
            # call carries them step to step
            new_frozen = list(frozen_bufs)
            for pos, i in enumerate(meta["aux_idx"]):
                new_frozen[frozen_pos[i]] = aux_bufs[pos]
            return new_w, new_s, new_frozen, loss, extras

        def step(train_bufs, sbufs, frozen_bufs, key, lrs, wds, rescale, ts,
                 *in_raw):
            if n_steps == 1:
                new_w, new_s, new_f, loss, extras = one_step(
                    train_bufs, sbufs, frozen_bufs, key, lrs, wds, rescale,
                    ts, in_raw)
                aux = tuple(new_f[frozen_pos[i]] for i in meta["aux_idx"])
                return new_w, new_s, loss, extras, aux

            # K train steps in ONE XLA program: weights/opt-state/BN-stats
            # carry on device, inputs have a leading (K, ...) axis
            keys = jax.random.split(key, n_steps)

            def body(carry, per):
                tb, sb, fb, t_off = carry
                key_k = per[0]
                in_k = per[1:]
                ts_k = None if ts is None else [t + t_off for t in ts]
                nw, ns, nf, loss, extras = one_step(
                    tuple(tb), tuple(sb), tuple(fb), key_k, lrs, wds,
                    rescale, ts_k, in_k)
                return ((tuple(nw), tuple(ns), tuple(nf), t_off + 1.0),
                        (loss, extras))

            carry0 = (tuple(train_bufs), tuple(sbufs), tuple(frozen_bufs),
                      jnp.float32(0.0))
            (new_w, new_s, new_f, _), (losses, extras) = jax.lax.scan(
                body, carry0, (keys,) + tuple(in_raw))
            aux = tuple(new_f[frozen_pos[i]] for i in meta["aux_idx"])
            return list(new_w), list(new_s), losses, extras, aux

        # donate only the trainable weight + optimizer-state buffers; frozen
        # params keep their buffers live across calls.
        from ... import sanitize as _sanitize
        donate = (0, 1) if self._donate else ()
        return _sanitize.maybe_wrap_donated(
            jax.jit(step, donate_argnums=donate), donate,
            "fused.train_step")

    # ------------------------------------------------------------------
    def _call_args(self, inputs, key, t_of):
        """What `_jit` takes for these inputs, the one place that lays it
        out: the step's real call and `lowered` pass the same list.
        `t_of(i)` is parameter i's update count at the call's first inner
        step (asked only for rules that take `t`)."""
        from ...ndarray import NDArray
        from ...optimizer import _state_bufs

        opt = self._opt
        lrs = _np.asarray([opt._get_lr(i) for i in self._train_idx],
                          _np.float32)
        wds = _np.asarray([opt._get_wd(i) for i in self._train_idx],
                          _np.float32)
        ts = (_np.asarray([t_of(i) for i in self._train_idx], _np.float32)
              if type(opt)._step_takes_t() else None)
        train_bufs = [self._params[i].data()._arr for i in self._train_idx]
        frozen_bufs = [self._params[i].data()._arr
                       for i in self._frozen_idx]
        sbufs = [_state_bufs(s) for s in self._states]
        # stage inputs asynchronously: host arrays start their H2D transfer
        # now (overlapping the caller's prologue), while batches that are
        # already committed device arrays with the right placement — e.g.
        # from io.DeviceFeed — skip the redundant transfer entirely (counted
        # in profiler.feed_stats()["device_put_skipped"]). Raw python
        # scalars pass through untouched to keep weak-typed promotion
        # semantics.
        in_raw = tuple(
            _stage_raw(a._arr if isinstance(a, NDArray) else a)
            for a in inputs)
        return (train_bufs, sbufs, frozen_bufs, key, lrs, wds,
                _np.float32(opt.rescale_grad), ts, *in_raw)

    def lowered(self, *inputs):
        """The fused step lowered for these input shapes WITHOUT running
        it: a `jax.stages.Lowered` whose `.compile()` yields the exact
        program `step(*inputs)` would execute. This is the inspection
        surface — `mx.inspect.inspect_step(step, x, y)` walks its
        compiled HLO for fusion-level offender attribution, and
        `flops_per_call` cost-counts it. The lowering lands in jax's jit
        cache, so a subsequent real `step(...)` with the same shapes does
        not re-pay compilation. It is the registered program's own
        (`profiler.program_scopes()` finds `jit_step` from here on)."""
        import jax
        from ... import profiler as _profiler

        self._ensure_states()
        if self._jit is None:
            self._jit = self._build()
        # fixed key: only shapes matter for lowering, and consuming the
        # global RNG stream here would silently change training
        # reproducibility for callers that cost-count before training
        args = self._call_args(inputs, jax.random.PRNGKey(0),
                               lambda i: 1.0)
        return _profiler.register_program(self._jit, args).lower()

    def flops_per_call(self, *inputs):
        """XLA-counted FLOPs of ONE compiled step call (cost analysis of
        the lowered fwd+loss+bwd+update program, MAC=2 — the same
        convention as chip peak specs). With `steps_per_call=K` this is
        the K-step program's total; divide by K for per-step. This is the
        MFU numerator `telemetry.StepTimeline(flops_per_step=...)` wants —
        live-counter MFU instead of hand-math."""
        from ...telemetry import cost_flops
        return cost_flops(self.lowered(*inputs), what="the fused step")

    def __call__(self, *inputs):
        # live spans on the profiler's clock while a collector is armed
        # (docs/OBSERVABILITY.md "Hot-path spans"); one gate a call
        on = _trace.armed()
        self._calls += 1
        with (_span("train.step", step_num=self._calls) if on
              else NO_SPAN):
            return self._run(inputs, on)

    def _run(self, inputs, on):
        from ... import random as _random
        from ...ndarray import _wrap
        from ...optimizer import _state_restore

        self._ensure_states()
        if self._jit is None:
            self._jit = self._build()
        opt = self._opt
        for _ in range(self._K):
            for i in self._train_idx:
                opt._update_count(i)
        # takes_t rules see t = count at that inner step: base + scan offset
        args = self._call_args(
            inputs, _random.next_key(),
            lambda i: opt._index_update_count[i] - self._K + 1)
        if not self._registered:
            # the step's first call notes the program with its arguments'
            # shapes (no buffer) where `profiler.program_scopes()` finds
            # it: `jit_step` in a device trace
            from ... import profiler as _profiler
            _profiler.register_program(self._jit, args)
            self._registered = True
        with (_span("train.step.dispatch") if on else NO_SPAN):
            new_w, new_s, loss, extras, aux_bufs = self._jit(*args)

        for k, i in enumerate(self._train_idx):
            self._params[i].data()._set_arr(new_w[k])
            _state_restore(self._states[k], new_s[k])
        for i, buf in zip(self._meta["aux_idx"], aux_bufs):
            self._params[i].data()._set_arr(buf)
        # census attribution (mx.inspect.memory): the donated update
        # produced FRESH weight/state buffers — re-attribute them so a
        # live-buffer census names the training state (a weakref-dict
        # write per buffer; must never break the step)
        try:
            from ...inspect import memory as _mem
            _mem.register((new_w, new_s, list(aux_bufs)),
                          owner="train_step")
        except Exception:
            pass
        out = (_wrap(loss),) + tuple(_wrap(e) for e in extras)
        return out if len(out) > 1 else out[0]
