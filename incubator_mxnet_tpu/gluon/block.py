"""gluon.Block / HybridBlock — the module system.

Reference: python/mxnet/gluon/block.py (Block:202 — child/param registration,
hooks:518, save/load; HybridBlock:997 — deferred-compute trace `_get_graph`,
`_build_cache`:1095 → CachedOp:1211, `hybridize(static_alloc,static_shape)`
:1379, `infer_shape`, `export`:1471).

TPU-native design — `hybridize()` ≙ `jax.jit` (SURVEY §7 table):
the reference traces forward with deferred compute into an nnvm graph and
executes it through CachedOp (memory planning + fusion passes). Here the
forward is traced by XLA itself: `_build_cache` constructs a *pure* function
  pure_fn(param_buffers, rng_key, *input_buffers) -> (outputs, aux_updates)
by temporarily binding traced buffers into the parameters' NDArrays, running
the user's `forward`, and collecting any parameter whose buffer was replaced
during the trace (BatchNorm running stats etc.) as explicit aux outputs —
functionalizing the reference's mutable-state ops. XLA then does what
MXPlanMemory + pointwise fusion + NVRTC did (memory planning, fusion) during
compilation. Autograd through a hybridized call tapes the whole cached op as
ONE node (≙ the _CachedOp node in the reference tape).
"""
from __future__ import annotations

import re
from collections import OrderedDict

import numpy as _np

from ..base import MXNetError
from .. import autograd
from .. import random as _random
from .parameter import Parameter, DeferredInitializationError

__all__ = ["Block", "HybridBlock", "SymbolBlock"]


def _amp_fingerprint():
    """The active autocast target dtype, or None (part of the cached-op
    cache key: amp-on and amp-off traces are different XLA programs)."""
    import sys
    amp_mod = sys.modules.get("incubator_mxnet_tpu.amp")
    if amp_mod is None or not amp_mod.is_active():
        return None
    return amp_mod._state["target_dtype"]


def _fusion_fingerprint():
    """Whether the fused kernel tier is active (part of the cached-op
    cache key: a fusion-on trace bakes fused ops into the XLA program,
    a fusion-off trace must not reuse it)."""
    from ..ops import fused as _fused
    return _fused.fusion_enabled()


from contextlib import contextmanager as _contextmanager


@_contextmanager
def _amp_scope(amp_fp):
    """Re-enter (or force off) the autocast state a forward trace was built
    under — including the fingerprinted target dtype, which may have been
    re-inited globally since — so the backward's recompute trace bakes
    identical casts."""
    import sys
    amp_mod = sys.modules.get("incubator_mxnet_tpu.amp")
    if amp_fp is None:
        if amp_mod is None:
            yield
            return
        with amp_mod.autocast(False):
            yield
        return
    from .. import amp as amp_mod
    prev_dtype = amp_mod._state["target_dtype"]
    amp_mod._state["target_dtype"] = amp_fp
    try:
        with amp_mod.autocast(True):
            yield
    finally:
        amp_mod._state["target_dtype"] = prev_dtype


class _BlockScope:
    """Naming helper for programmatically-created children."""
    _count = {}

    @classmethod
    def create_name(cls, prefix):
        n = cls._count.get(prefix, 0)
        cls._count[prefix] = n + 1
        return f"{prefix}{n}"


class Block:
    """Base class for all layers and models (≙ gluon.Block, block.py:202)."""

    def __init__(self):
        self._children = OrderedDict()
        self._reg_params = OrderedDict()
        self._forward_hooks = OrderedDict()
        self._forward_pre_hooks = OrderedDict()
        self._hook_id = 0

    # ------------------------------------------------------------------
    # registration
    # ------------------------------------------------------------------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
                value.__dict__["_scope_name"] = name
        elif isinstance(value, Parameter):
            params = self.__dict__.get("_reg_params")
            if params is not None:
                params[name] = value
                if value._name in (None, "param", "const"):
                    value._name = name
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block
        # the name it runs under in a compiled program (`__call__`)
        block.__dict__["_scope_name"] = name
        super().__setattr__(f"_child_{name}", block)

    def register_block(self, *a, **kw):
        return self.register_child(*a, **kw)

    # ------------------------------------------------------------------
    # parameter collection
    # ------------------------------------------------------------------
    def collect_params(self, select=None):
        """Dict of structural-name → Parameter (≙ Block.collect_params)."""
        out = OrderedDict()
        pat = re.compile(select) if select else None
        for name, p in self._iter_params(""):
            p._structural_name = name
            if pat is None or pat.match(name):
                out[name] = p
        return out

    @property
    def params(self):
        return dict(self._reg_params)

    def _iter_params(self, prefix):
        for name, p in self._reg_params.items():
            yield (prefix + name if prefix else name), p
        for cname, child in self._children.items():
            yield from child._iter_params(
                (prefix + cname + "." if prefix else cname + "."))

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def initialize(self, init=None, device=None, verbose=False,
                   force_reinit=False, ctx=None):
        """Initialize all parameters (≙ Block.initialize)."""
        for _, p in self.collect_params().items():
            p.initialize(init=None, device=device or ctx,
                         default_init=init, force_reinit=force_reinit)
        return self

    def hybridize(self, active=True, **kwargs):
        """Recursively activate hybrid (compiled) execution."""
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for p in self._reg_params.values():
            p.cast(dtype)
        return self

    def reset_ctx(self, device):
        for _, p in self.collect_params().items():
            p.reset_ctx(device)

    reset_device = reset_ctx

    def zero_grad(self):
        for _, p in self.collect_params().items():
            p.zero_grad()

    def setattr(self, name, value):
        """Set an attribute on all parameters (≙ Block.setattr), e.g.
        net.setattr('grad_req', 'null')."""
        for _, p in self.collect_params().items():
            setattr(p, name, value)

    def share_parameters(self, shared):
        """Adopt parameters from `shared` dict by structural name
        (≙ Block.share_parameters)."""
        own = self.collect_params()
        for name, p in shared.items():
            if name in own:
                self._replace_param(name, p)
        return self

    def _replace_param(self, structural_name, new_param):
        parts = structural_name.split(".")
        blk = self
        for part in parts[:-1]:
            blk = blk._children[part]
        blk._reg_params[parts[-1]] = new_param
        object.__setattr__(blk, parts[-1], new_param)

    # ------------------------------------------------------------------
    # hooks (≙ block.py:518 register_forward_hook etc.)
    # ------------------------------------------------------------------
    def register_forward_hook(self, hook):
        self._hook_id += 1
        self._forward_hooks[self._hook_id] = hook
        return _HookHandle(self._forward_hooks, self._hook_id)

    def register_forward_pre_hook(self, hook):
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return _HookHandle(self._forward_pre_hooks, self._hook_id)

    def apply(self, fn):
        """Apply fn to self and all children recursively (≙ Block.apply)."""
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    # ------------------------------------------------------------------
    # save / load (≙ Block.save_parameters / load_parameters)
    # ------------------------------------------------------------------
    def save_parameters(self, filename, deduplicate=False):
        params = self.collect_params()
        seen = {}
        payload = {}
        for name, p in params.items():
            if p._data is None:
                continue
            arr = p.data().asnumpy()
            if deduplicate and id(p) in seen:
                continue
            seen[id(p)] = name
            payload[name] = arr
        # temp write + atomic rename: a crash mid-save never truncates a
        # previously-good params file (see mx.fault)
        from .. import fault as _fault
        with _fault.atomic_output(filename) as f:
            _np.savez(f, **payload)

    def load_parameters(self, filename, device=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False, ctx=None):
        loaded = dict(_np.load(filename, allow_pickle=False))
        params = self.collect_params()
        for name, p in params.items():
            if name not in loaded:
                if not allow_missing:
                    raise MXNetError(
                        f"parameter {name} missing in file {filename}")
                continue
            arr = loaded[name]
            if cast_dtype:
                arr = arr.astype(p.dtype)
            p.shape = arr.shape
            from ..ndarray import array
            p.set_data(array(arr, device=device or ctx, dtype=str(arr.dtype)))
        if not ignore_extra:
            extra = set(loaded) - set(params)
            if extra:
                raise MXNetError(
                    f"file {filename} contains extra parameters {sorted(extra)}")

    load_params = load_parameters
    save_params = save_parameters

    def load_dict(self, param_dict, device=None, allow_missing=False,
                  ignore_extra=False):
        params = self.collect_params()
        for name, p in params.items():
            if name not in param_dict:
                if not allow_missing:
                    raise MXNetError(f"parameter {name} missing in dict")
                continue
            p.set_data(param_dict[name])

    # ------------------------------------------------------------------
    # call path
    # ------------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        if _in_trace(args):
            return self._call_traced(args, kwargs)
        return self._forward_with_hooks(*args, **kwargs)

    def _call_traced(self, args, kwargs):
        # while it is traced into a compiled program (hybridize, a fused
        # step), a child runs under its registered name as a
        # `jax.named_scope`: the program's instructions then carry
        # `features/4/0/...` in their `op_name`
        # (`profiler.program_scopes`). An eager call never comes here
        name = self.__dict__.get("_scope_name")
        if name is None:
            return self._forward_with_hooks(*args, **kwargs)
        import jax
        with jax.named_scope(name):
            return self._forward_with_hooks(*args, **kwargs)

    def _forward_with_hooks(self, *args, **kwargs):
        if not self.__dict__.get("_params_ready", False):
            self._resolve_own_deferred(*args)
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def _resolve_own_deferred(self, *args):
        """Just-in-time shape inference for this block's own parameters
        (≙ HybridBlock._deferred_infer_shape): leaf layers override
        infer_shape; it runs on the first call when input shapes are known."""
        pending = [p for p in self._reg_params.values()
                   if p._deferred_init is not None]
        if pending:
            self.infer_shape(*args)
            for p in pending:
                p._finish_deferred_init()
        self._params_ready = True

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def infer_shape(self, *args):
        """Infer deferred parameter shapes from inputs. Layers that own
        deferred params override this (≙ HybridBlock.infer_shape)."""
        raise MXNetError(
            f"{type(self).__name__} has parameters with unknown shape but "
            "does not implement infer_shape(*inputs)")

    def summary(self, *inputs):
        """Print a per-layer summary (≙ Block.summary)."""
        rows = []

        def _hook(block, ins, outs):
            o = outs[0] if isinstance(outs, (list, tuple)) else outs
            n_params = sum(int(_np.prod(p.shape or ()))
                           for p in block._reg_params.values()
                           if p.shape is not None)
            rows.append((type(block).__name__, tuple(getattr(o, "shape", ())),
                         n_params))

        handles = []
        for block in _walk(self):
            handles.append(block.register_forward_hook(_hook))
        try:
            self(*inputs)
        finally:
            for h in handles:
                h.detach()
        total = sum(r[2] for r in rows)
        lines = [f"{'Layer':<28}{'Output shape':<24}{'Params':>12}",
                 "-" * 64]
        lines += [f"{n:<28}{str(s):<24}{p:>12}" for n, s, p in rows]
        lines += ["-" * 64, f"{'Total params':<52}{total:>12}"]
        print("\n".join(lines))
        return "\n".join(lines)

    def __repr__(self):
        lines = [type(self).__name__ + "("]
        for name, child in self._children.items():
            child_repr = repr(child).replace("\n", "\n  ")
            lines.append(f"  ({name}): {child_repr}")
        lines.append(")")
        return "\n".join(lines)


def _walk(block):
    yield block
    for c in block._children.values():
        yield from _walk(c)


_TRACE_TYPES = []       # (NDArray, jax.core.Tracer), resolved at first use


def _in_trace(args):
    """True when any input is a jax tracer (we're under an enclosing jit).
    Every block's call asks: two type checks an argument, no import."""
    if not _TRACE_TYPES:
        import jax
        from ..ndarray import NDArray
        _TRACE_TYPES[:] = [NDArray, jax.core.Tracer]
    nd, tracer = _TRACE_TYPES
    for a in args:
        if isinstance(a._arr if isinstance(a, nd) else a, tracer):
            return True
    return False


class _HookHandle:
    def __init__(self, hooks, hid):
        self._hooks, self._hid = hooks, hid

    def detach(self):
        self._hooks.pop(self._hid, None)


# ---------------------------------------------------------------------------
# HybridBlock
# ---------------------------------------------------------------------------
class HybridBlock(Block):
    """A Block whose forward can be compiled as one XLA computation
    (≙ gluon.HybridBlock, block.py:997; hybridize ≙ CachedOp ≙ jax.jit)."""

    def __init__(self):
        super().__init__()
        self._active = False
        self._cached_graph = {}  # (training, amp_fp) -> (jit fn, meta, bwd)
        self._cached_params = None  # stable param order for the cache
        self._shapes_ready = False
        self._jit_kwargs = {}
        self._subgraph_backend = None  # optimize_for rewriter (subgraph.py)

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  **kwargs):
        """Activate compiled execution. static_alloc maps to XLA buffer
        donation (handled by jit automatically); static_shape means "don't
        re-specialize per shape" — jax.jit already caches per shape, so both
        flags are accepted for API compatibility (reference block.py:1379)."""
        self._active = active
        self._cached_graph = {}
        self._cached_params = None
        self._shapes_ready = False
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)

    def __call__(self, *args, **kwargs):
        if _in_trace(args):
            # inside an enclosing trace the parent cache already captures this
            # block's ops (≙ child CachedOps fold into the parent graph)
            return self._call_traced(args, kwargs)
        if not self._active or kwargs:
            return self._forward_with_hooks(*args, **kwargs)
        if not self._shapes_ready:
            # deferred params anywhere in the tree: run ONE eager pass so each
            # leaf resolves its shapes just in time, then cache from next call
            if any(p._deferred_init is not None
                   for _, p in self.collect_params().items()):
                return self._forward_with_hooks(*args, **kwargs)
            self._shapes_ready = True
        return self._call_cached(*args)

    # ------------------------------------------------------------------
    # the CachedOp equivalent
    # ------------------------------------------------------------------
    def _call_cached(self, *args):
        import jax
        from ..ndarray import NDArray, _wrap

        for hook in self._forward_pre_hooks.values():
            hook(self, args)

        if self._cached_params is None:
            self._cached_params = [p for _, p in
                                   sorted(self.collect_params().items())]
        params = self._cached_params
        training = autograd.is_training()
        # cache key includes the autocast state (an amp-on trace bakes
        # bf16 casts into the XLA program) and the fused-tier state (a
        # fusion-on trace bakes fused ops) — neither may serve the other
        amp_fp = (_amp_fingerprint(), _fusion_fingerprint())
        cached = self._cached_graph.get((training, amp_fp))
        if cached is None:
            cached = self._build_cache(training, amp_fp)
            self._cached_graph[(training, amp_fp)] = cached
        jit_fn, meta = cached[0], cached[1]

        n_in = len(args)
        key = _random.next_key()

        from ..ops.registry import invoke

        def runner(key, *flat):
            inputs, pbufs = flat[:n_in], flat[n_in:]
            outs, aux, _ = jit_fn(pbufs, key, *inputs)
            return tuple(outs) + tuple(aux)

        get_bwd = cached[2]
        cached_vjp = lambda raw, cts: get_bwd(n_in)(raw[0], raw[1:], cts)
        results = invoke(runner,
                         (key,) + tuple(args)
                         + tuple(p.data() for p in params),
                         name=type(self).__name__, multi_out=True,
                         cached_vjp=cached_vjp)
        n_out = meta["n_out"]
        outs = results[:n_out]
        aux_new = results[n_out:]
        # write functionalized aux-state updates back into their parameters
        for p_idx, new_val in zip(meta["aux_indices"], aux_new):
            arr = params[p_idx].data()
            with autograd.pause():
                arr._set_arr(new_val._data)  # adopt without materializing
        out = meta["treedef"](outs)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def _build_cache(self, training, fp=None):
        """Construct + jit the pure function for this block (≙ _build_cache
        block.py:1095 building the CachedOp). `fp` is the
        (amp fingerprint, fusion fingerprint) pair from _call_cached."""
        import jax
        from ..ops import fused as _fused
        amp_fp, fusion_fp = fp if isinstance(fp, tuple) else (fp, False)
        params = self._cached_params
        block = self
        meta = {"n_out": None, "aux_indices": None, "treedef": None}

        def pure_fn(pbufs, rng_key, *inputs):
            from ..ndarray import NDArray, _wrap
            saved = []
            for p, buf in zip(params, pbufs):
                nd = p.data()
                saved.append(nd._data)
                nd._data = buf
                nd._version += 1
            mutated = {}
            try:
                # pin the fingerprinted fused-tier state: jit may retrace
                # this fn later (new shapes) under a different ambient
                # scope, and the cache entry's routing must not flip
                with autograd._Scope(recording=False, training=training), \
                        _random.trace_key_scope(rng_key), \
                        _fused.fusion_scope(fusion_fp):
                    wrapped = tuple(_wrap(x) for x in inputs)
                    out = block.forward(*wrapped)
                single = not isinstance(out, (list, tuple))
                outs = (out,) if single else tuple(out)
                out_raw = tuple(o._arr for o in outs)
                for i, (p, buf) in enumerate(zip(params, pbufs)):
                    cur = p.data()._data
                    if cur is not buf:
                        mutated[i] = cur
            finally:
                for p, old in zip(params, saved):
                    p.data()._data = old
            if meta["n_out"] is None:
                meta["n_out"] = len(out_raw)
                meta["aux_indices"] = sorted(mutated)
                meta["single"] = single
                if single:
                    meta["treedef"] = lambda outs: outs[0]
                else:
                    meta["treedef"] = lambda outs: tuple(outs)
            aux = tuple(mutated[i] for i in sorted(mutated))
            return out_raw, aux, None

        fwd = pure_fn
        if self._subgraph_backend is not None:
            # optimize_for plug-in point: the backend rewrites the traced
            # equations; the result still compiles as one XLA program.
            # The backward below recomputes through THIS wrapped forward,
            # so gradients flow through the rewritten math, not the
            # original equations.
            fwd = self._subgraph_backend.transform_callable(pure_fn)

        bwd_cache = {}

        def get_bwd(n_in):
            """Jitted recompute-based VJP, compiled ONCE per input arity.

            Per-call jax.vjp over the cached graph re-traces + transposes in
            Python every step (50ms-class overhead on a ResNet) and runs the
            backward through the eager transpose interpreter. Instead:
            recompute the forward inside ONE jitted backward — XLA fuses
            fwd-recompute + transpose into a single program, no residual
            storage, no per-step tracing. The rng key rides through as a
            jit argument so dropout masks replay identically.
            """
            bwd = bwd_cache.get(n_in)
            if bwd is not None:
                return bwd

            def bwd_fn(key, flat_args, cts):
                def flat_fn(*a):
                    inputs, pbufs = a[:n_in], a[n_in:]
                    outs, aux, _ = fwd(pbufs, key, *inputs)
                    return tuple(outs) + tuple(aux)

                # replay the forward's autocast AND fused-tier state:
                # backward runs with amp suspended, but the recompute must
                # bake the SAME bf16 casts and the SAME fused-op routing
                # the forward trace did or cotangent dtypes/graphs mismatch
                with _amp_scope(amp_fp), _fused.fusion_scope(fusion_fp):
                    _, vjp = jax.vjp(flat_fn, *flat_args)
                grads = vjp(tuple(cts))
                # None for the (integer) rng key slot + float0 -> None so
                # jit never returns float0 buffers
                clean = tuple(
                    None if (hasattr(g, "dtype")
                             and g.dtype == jax.dtypes.float0) else g
                    for g in grads)
                return (None,) + clean

            bwd = jax.jit(bwd_fn)
            bwd_cache[n_in] = bwd
            return bwd

        return jax.jit(fwd), meta, get_bwd

    # ------------------------------------------------------------------
    def optimize_for(self, x, *args, backend=None, **kwargs):
        """≙ HybridBlock.optimize_for (block.py:1272).

        On TPU the baseline graph optimization happens in XLA, so
        backend=None/'xla' hybridizes and warms the cache. Registered
        SUBGRAPH BACKENDS (gluon.subgraph.register_subgraph_backend, the
        plug-in point ≙ subgraph_property.h) additionally rewrite the
        traced equations before jit — third-party rewrites compose into
        the same compiled program. Unknown backends raise (reference
        semantics: partitioning for an unregistered backend is an error,
        not a silent no-op)."""
        _KNOWN = (None, "xla", "XLA", "tpu", "TPU")
        if backend not in _KNOWN:
            from .subgraph import get_subgraph_backend
            self._subgraph_backend = get_subgraph_backend(backend)
            self._cached_graph.clear()   # rebuild with the rewriter applied
        elif self._subgraph_backend is not None:
            # explicit revert to the baseline stack
            self._subgraph_backend = None
            self._cached_graph.clear()
        self.hybridize(True)
        self(x, *args)

    def export(self, path, epoch=0, remove_amp_cast=True,
               example_inputs=None):
        """Serialize the compiled graph + params (≙ HybridBlock.export,
        block.py:1471: model-symbol.json + model-0000.params).

        Always saves `<path>-<epoch>.params.npz` (weights by structural
        name); when `example_inputs` are given, additionally saves

          * `<path>-<epoch>.stablehlo.mlir` — StableHLO text of the
            inference forward (the portable artifact replacing the nnvm
            symbol JSON),
          * `<path>-<epoch>.jaxport` — the versioned `jax.export`
            serialization of the same module, lowered for cpu AND tpu
            (the *executable* artifact: `deploy.ExportedModel`, the C ABI
            in native/c_api.cc, and the C++ frontend all consume it),
          * `<path>-<epoch>.deploy.json` — manifest (param order, input
            avals, output arity) so a loader needs no Python model class.

        Returns the tuple of file paths written."""
        import jax
        from ..ndarray import NDArray
        params_file = f"{path}-{epoch:04d}.params.npz"
        self.save_parameters(params_file)
        outputs = [params_file]
        if example_inputs is not None:
            if not isinstance(example_inputs, (list, tuple)):
                example_inputs = (example_inputs,)
            if self._cached_params is None:
                self._cached_params = [p for _, p in
                                       sorted(self.collect_params().items())]
            params = self._cached_params
            cached = self._cached_graph.get((False, None))
            if cached is None:
                cached = self._build_cache(False)
                self._cached_graph[(False, None)] = cached
            jit_fn, meta = cached[0], cached[1]
            pbufs = tuple(p.data()._arr for p in params)
            in_raw = tuple(a._arr if isinstance(a, NDArray) else a
                           for a in example_inputs)
            # constant key: export must not advance the global RNG stream
            dummy_key = jax.random.PRNGKey(0)
            lowered = jit_fn.lower(pbufs, dummy_key, *in_raw)
            hlo_file = f"{path}-{epoch:04d}.stablehlo.mlir"
            with open(hlo_file, "w") as f:
                f.write(lowered.as_text(dialect="stablehlo"))
            outputs.append(hlo_file)

            import json
            import jax.export as _jexp
            exported = _jexp.export(jit_fn, platforms=("cpu", "tpu"))(
                pbufs, dummy_key, *in_raw)
            port_file = f"{path}-{epoch:04d}.jaxport"
            with open(port_file, "wb") as f:
                f.write(exported.serialize())
            outputs.append(port_file)

            manifest = {
                "format_version": 1,
                "params": [name for name, _ in
                           sorted(self.collect_params().items())],
                "n_out": meta["n_out"],
                "single_output": bool(meta.get("single", meta["n_out"] == 1)),
                "aux_indices": list(meta["aux_indices"]),
                "inputs": [{"shape": list(a.shape), "dtype": str(a.dtype)}
                           for a in in_raw],
            }
            man_file = f"{path}-{epoch:04d}.deploy.json"
            with open(man_file, "w") as f:
                json.dump(manifest, f, indent=1)
            outputs.append(man_file)
        return tuple(outputs)

    def forward(self, *args):
        raise NotImplementedError

    def reset_cache(self):
        self._cached_graph = {}
        self._cached_params = None
        self._shapes_ready = False


class SymbolBlock(HybridBlock):
    """≙ gluon.SymbolBlock (block.py:1638). The reference wraps a saved
    symbol graph; here a saved exported module (`HybridBlock.export`
    artifact triple) served by `deploy.ExportedModel`. `forward` executes
    the compiled program — no Python model class required."""

    def __init__(self, model):
        super().__init__()
        self._model = model

    def forward(self, *args):
        # Traceable path (call_arrays): works eagerly AND under a parent
        # hybridize trace — the exported program composes into the outer
        # XLA computation instead of forcing a host round-trip.
        from ..ndarray import NDArray, _wrap
        arrs = [a._arr if isinstance(a, NDArray) else a for a in args]
        out_raw = self._model.call_arrays(*arrs)
        outs = tuple(_wrap(o) for o in out_raw)
        return outs[0] if self._model.single_output else outs

    @staticmethod
    def imports(symbol_file, input_names=None, param_file=None, device=None):
        """Load a saved model artifact.

        Two formats are accepted (≙ gluon.SymbolBlock.imports):
        - a reference `*-symbol.json` legacy graph (+ `.params` checkpoint):
          parsed by mx.symbol and executed as a pure jax function
          (gluon/_legacy_symbol_block.py);
        - this framework's own export triple (`net-0000` prefix or the
          `.jaxport` path): served by deploy.ExportedModel.
        """
        if symbol_file.endswith(".json"):
            from ._legacy_symbol_block import build_legacy_block
            return build_legacy_block(symbol_file, input_names, param_file)
        from ..deploy import ExportedModel
        if symbol_file.endswith(".jaxport"):
            prefix = symbol_file[:-len(".jaxport")]
        elif symbol_file.endswith(".stablehlo.mlir"):
            prefix = symbol_file[:-len(".stablehlo.mlir")]
        else:
            prefix = symbol_file
        model = ExportedModel(
            prefix,
            params=param_file if param_file else None)
        return SymbolBlock(model)
