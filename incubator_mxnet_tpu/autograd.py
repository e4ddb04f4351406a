"""Autograd: tape-based reverse-mode AD over eager ops.

Reference equivalents: python/mxnet/autograd.py:121-272 (record/pause scopes,
backward, grad, mark_variables, custom Function) and the C++ taping runtime
Imperative::RecordOp / Imperative::Backward (src/imperative/imperative.cc:210,413)
with AGInfo bookkeeping (include/mxnet/imperative.h:54-92).

TPU-native design: instead of nnvm backward-graph construction with per-op
FGradient registrations, every recorded op captures a `jax.vjp` closure at call
time (one forward execution, residuals held by XLA buffers). backward() walks the
tape in reverse topological order calling the closures; `create_graph=True`
re-records the closure calls themselves, giving higher-order gradients for free
(vjp-of-vjp). grad_req write/add/null semantics match the reference
(kWriteTo/kAddTo/kNullOp, include/mxnet/op_attr_types.h).
"""
from __future__ import annotations

import threading
from collections import defaultdict

import numpy as _np

from .base import MXNetError

__all__ = [
    "record", "pause", "train_mode", "predict_mode",
    "is_recording", "is_training", "set_recording", "set_training",
    "mark_variables", "backward", "grad", "Function",
]

_state = threading.local()


def _get(attr, default):
    return getattr(_state, attr, default)


def is_recording():
    """Whether autograd is taping ops (≙ mx.autograd.is_recording)."""
    return _get("recording", False)


def is_training():
    """Whether ops run in train mode (dropout active, BN uses batch stats)."""
    return _get("training", False)


def set_recording(is_record):
    prev = is_recording()
    _state.recording = bool(is_record)
    return prev


def set_training(train_mode_):
    prev = is_training()
    _state.training = bool(train_mode_)
    return prev


class _Scope:
    def __init__(self, recording=None, training=None):
        self._recording = recording
        self._training = training

    def __enter__(self):
        if self._recording is not None:
            self._prev_rec = set_recording(self._recording)
        if self._training is not None:
            self._prev_train = set_training(self._training)
        return self

    def __exit__(self, *exc):
        if self._recording is not None:
            set_recording(self._prev_rec)
        if self._training is not None:
            set_training(self._prev_train)


def record(train_mode=True):
    """Scope in which executed ops are taped for backward (autograd.py:121)."""
    return _Scope(recording=True, training=train_mode)


def pause(train_mode=False):
    """Scope in which taping is suspended (autograd.py:145)."""
    return _Scope(recording=False, training=train_mode)


def train_mode():
    return _Scope(training=True)


def predict_mode():
    return _Scope(training=False)


# ---------------------------------------------------------------------------
# Tape structure
# ---------------------------------------------------------------------------
class Variable:
    """Grad slot attached to a leaf NDArray (≙ AGInfo on a variable node)."""

    __slots__ = ("grad_req", "grad", "fresh")

    def __init__(self, grad_req="write", grad=None):
        if grad_req not in ("write", "add", "null"):
            raise MXNetError(f"invalid grad_req {grad_req!r}")
        self.grad_req = grad_req
        self.grad = grad       # NDArray or None
        self.fresh = False     # whether .grad holds grads from the last backward


class Node:
    """One taped op: a vjp closure + links to producer entries of its inputs.

    parents[i] is one of:
      ("node", Node, out_idx)  input i produced by another taped op
      ("var", NDArray)         input i is a marked variable (leaf)
      None                     input i untracked (constant)

    `fn`/`inputs`/`single_out` are kept so create_graph can re-linearize the
    op as a function of its primals (vjp closures capture residuals as
    constants, so higher-order grads need a fresh jax.vjp through the tape).

    Keyed ops — bulked (deferred) AND the immediate fast path (PR2) — tape
    with `vjp_fn=None` plus the forward's stable `key`: backward
    re-linearizes from the primal inputs via invoke under a derived
    ("vjp", key, ...) identity, so the vjp lands in the same bulked segment
    (recompute-based, XLA CSEs the duplicated forward — one compiled program
    for the whole fwd+bwd chain) or, immediate, in a cached compiled VJP
    kernel: repeat (key, avals) backwards never retrace in Python.
    """

    __slots__ = ("vjp_fn", "parents", "out_avals", "name", "fn", "inputs",
                 "single_out", "key", "cached_vjp", "inputs_raw")

    def __init__(self, vjp_fn, parents, out_avals, name="", fn=None,
                 inputs=None, single_out=False, key=None, cached_vjp=None,
                 inputs_raw=None):
        self.vjp_fn = vjp_fn
        self.parents = parents
        self.out_avals = out_avals  # [(shape, dtype), ...] per output
        self.name = name
        self.fn = fn
        self.inputs = inputs
        self.single_out = single_out
        self.key = key
        self.cached_vjp = cached_vjp
        # snapshot of the raw input buffers at record time: backward
        # re-linearization must see the values the forward saw, even if the
        # user mutates the NDArrays in between (buffers are immutable, so
        # holding them is the faithful residual-capture equivalent)
        self.inputs_raw = inputs_raw

    def _primals(self, create_graph):
        if self.inputs_raw is not None and not create_graph:
            return tuple(self.inputs_raw)
        return tuple(self.inputs)

    def apply_vjp(self, cts, create_graph=False):
        """Compute input cotangents given output cotangents (NDArray list)."""
        from .ops.registry import invoke
        if self.cached_vjp is not None and self.vjp_fn is None \
                and not create_graph:
            # bulked cached-op (HybridBlock): the jitted recompute-VJP runs
            # over the real primal args so it defers like any other op
            n_in = len(self.inputs)
            cv = self.cached_vjp

            def cvjp(*a):
                return cv(tuple(a[:n_in]), tuple(a[n_in:]))

            kk = ("cvjp", self.key) if self.key is not None else None
            with _Scope(recording=False):
                return invoke(cvjp, self._primals(False) + tuple(cts),
                              name=f"backward_{self.name}", multi_out=True,
                              key=kk)
        if self.fn is not None and (create_graph or self.vjp_fn is None):
            import jax
            from .ops.segment import DISPATCH_STATS
            fn, n_in, single = self.fn, len(self.inputs), self.single_out

            def relinearized(*args):
                # body runs when python actually (re)traces: once per
                # (key, avals) through the compiled-kernel/replay caches,
                # every call on the unkeyed fallback — the counter the
                # no-retrace test watches
                DISPATCH_STATS["vjp_trace"] += 1
                primals, cs = args[:n_in], args[n_in:]
                _, vjp = jax.vjp(fn, *primals)
                return vjp(cs[0] if single else tuple(cs))

            kk = ("vjp", self.key, single, n_in) if self.key is not None \
                else None
            with _Scope(recording=create_graph):
                return invoke(relinearized,
                              self._primals(create_graph) + tuple(cts),
                              name=f"backward_{self.name}", multi_out=True,
                              key=kk)
        if self.vjp_fn is None:
            # fn AND vjp_fn gone: this node was severed by a previous
            # backward (_sever_nodes). Surface the cause instead of a
            # cryptic NoneType crash deep in the engine.
            raise MXNetError(
                f"array produced by {self.name!r} belongs to a computation "
                "graph already consumed by an earlier backward(); recompute "
                "it inside the current record block or detach() it before "
                "reuse")
        with _Scope(recording=False):
            # residual-capturing vjp closures are one-shot: keep them out of
            # the bulking caches (key=False) — identity-keying them would
            # recompile per call and pin residual buffers
            return invoke(self.vjp_fn, tuple(cts),
                          name=f"backward_{self.name}", multi_out=True,
                          _vjp_tuple=True, key=False)


def mark_variables(variables, gradients=None, grad_reqs="write"):
    """Attach grad buffers to arrays so backward accumulates into them
    (≙ autograd.mark_variables, autograd.py:196)."""
    if not isinstance(variables, (list, tuple)):
        variables = [variables]
        gradients = [gradients]
    if gradients is None:
        gradients = [None] * len(variables)
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for arr, g, req in zip(variables, gradients, grad_reqs):
        arr._var = Variable(req, g)


# ---------------------------------------------------------------------------
# Backward execution
# ---------------------------------------------------------------------------
def _toposort(root_nodes):
    order, seen = [], set()
    stack = [(n, False) for n in root_nodes]
    while stack:
        node, done = stack.pop()
        if done:
            order.append(node)
            continue
        if id(node) in seen:
            continue
        seen.add(id(node))
        stack.append((node, True))
        for p in node.parents:
            if p is not None and p[0] == "node" and id(p[1]) not in seen:
                stack.append((p[1], False))
    return order  # parents before children


def _is_float0(x):
    import jax
    return getattr(x, "dtype", None) == jax.dtypes.float0


def _amp_suspended():
    """Suspend AMP autocast during backward: gradient math (vjp application
    and cotangent accumulation) must run in the recorded dtypes, not get
    re-cast by the forward autocast lists."""
    import sys
    from contextlib import nullcontext
    amp_mod = sys.modules.get("incubator_mxnet_tpu.amp")
    if amp_mod is not None and amp_mod._state["active"]:
        return amp_mod.autocast(False)
    return nullcontext()


def backward(heads, head_grads=None, retain_graph=False, train_mode=True,
             create_graph=False, variables=None):
    """Run the tape backward from `heads` (≙ autograd.backward / MXAutogradBackwardEx).

    If `variables` is given, returns their gradients instead of writing into
    marked .grad buffers (≙ autograd.grad, autograd.py:272).
    """
    with _amp_suspended():
        return _backward_impl(heads, head_grads, retain_graph, train_mode,
                              create_graph, variables)


def _backward_impl(heads, head_grads, retain_graph, train_mode,
                   create_graph, variables):
    import jax.numpy as jnp
    from .ndarray import NDArray, _wrap
    from .ops.registry import invoke

    if isinstance(heads, NDArray):
        heads = [heads]
        if head_grads is not None and not isinstance(head_grads, (list, tuple)):
            head_grads = [head_grads]
    if head_grads is None:
        head_grads = [None] * len(heads)

    # Seed cotangents per (node, out_idx); NDArray cotangents so create_graph
    # can re-record the vjp applications.
    cts = defaultdict(dict)  # id(node) -> {out_idx: NDArray}
    node_by_id = {}
    roots = []
    var_grads = {}  # id(var array) -> NDArray cotangent (for grad() mode)
    var_arrays = {}
    # grad() w.r.t. tape-connected intermediates: capture the cotangent of
    # their producing (node, out_idx) entry right before that node's vjp runs
    entry_targets = {}  # id(node) -> {out_idx: array}
    if variables is not None:
        for v in variables:
            entry = getattr(v, "_entry", None)
            if entry is not None and getattr(v, "_var", None) is None:
                node, idx = entry
                entry_targets.setdefault(id(node), {})[idx] = v
                roots.append(node)

    def _acc_var(arr, ct):
        key = id(arr)
        var_arrays[key] = arr
        if key in var_grads:
            var_grads[key] = var_grads[key] + ct
        else:
            var_grads[key] = ct

    for h, hg in zip(heads, head_grads):
        if hg is None:
            hg = _wrap(jnp.ones(h.shape, h.dtype))
        entry = getattr(h, "_entry", None)
        if entry is not None:
            node, idx = entry
            node_by_id[id(node)] = node
            roots.append(node)
            if idx in cts[id(node)]:
                cts[id(node)][idx] = cts[id(node)][idx] + hg
            else:
                cts[id(node)][idx] = hg
        elif getattr(h, "_var", None) is not None:
            _acc_var(h, hg)
        else:
            raise MXNetError(
                "cannot differentiate: output is not connected to the tape "
                "(was it computed outside autograd.record()?)")

    order = _toposort(roots)
    for n in order:
        node_by_id[id(n)] = n

    # Reverse topological: children (late ops) first.
    for node in reversed(order):
        node_cts = cts.pop(id(node), {})
        for idx, target in entry_targets.get(id(node), {}).items():
            if idx in node_cts:
                _acc_var(target, node_cts[idx])
        if not node_cts:
            continue
        full = []
        for i, (shape, dtype) in enumerate(node.out_avals):
            if i in node_cts:
                ct = node_cts[i]
                # cross-dtype edges (AMP bf16<->f32 casts) need the cotangent
                # in the producing output's dtype for jax.vjp
                if ct.dtype != dtype:
                    with _Scope(recording=False):
                        ct = ct.astype(dtype)
                full.append(ct)
            elif _np.issubdtype(_np.dtype(dtype), _np.floating) or str(dtype) == "bfloat16":
                full.append(_wrap(jnp.zeros(shape, dtype)))
            else:
                # Non-float outputs carry symbolic-zero (float0) cotangents;
                # they stay raw numpy (jax cannot device-put float0).
                import jax
                full.append(_np.zeros(shape, jax.dtypes.float0))
        # Apply the vjp. Under create_graph the op is re-linearized from its
        # primal inputs and the application recorded → higher-order grads.
        with _Scope(training=train_mode):
            in_cts = node.apply_vjp(full, create_graph=create_graph)
        for parent, ct in zip(node.parents, in_cts):
            if parent is None or ct is None or _is_float0(ct):
                continue
            kind = parent[0]
            if kind == "node":
                _, pnode, pidx = parent
                d = cts[id(pnode)]
                if pidx in d:
                    d[pidx] = d[pidx] + ct
                else:
                    d[pidx] = ct
            else:  # variable leaf
                _acc_var(parent[1], ct)

    if variables is not None:
        out = []
        for v in variables:
            g = var_grads.get(id(v))
            if g is None:
                g = _wrap(jnp.zeros(v.shape, v.dtype))
            out.append(g)
        if not retain_graph:
            _free_tape(heads)
            _sever_nodes(order)
        return out

    # Write into marked variables per grad_req (kWriteTo/kAddTo/kNullOp).
    for key, ct in var_grads.items():
        arr = var_arrays[key]
        var = arr._var
        if var.grad_req == "null":
            continue
        if var.grad is None:
            var.grad = ct.copy()
        elif var.grad_req == "add" and var.fresh:
            var.grad[:] = var.grad + ct
        else:
            var.grad[:] = ct
        var.fresh = True
    if not retain_graph:
        _free_tape(heads)
        _sever_nodes(order)
    # backward() bounds an iteration for hand-rolled loops (no Trainer):
    # flush oversized segments here so each compile stays loop-shaped
    # instead of accumulating to the hard op cap
    from .ops import segment as _segment
    if _segment.current_size() > 256:
        _segment.flush_all()
    return None


def _free_tape(heads):
    """Drop tape entries reachable from heads so residual buffers free eagerly
    (≙ the reference clearing AGInfo after backward unless retain_graph)."""
    for h in heads:
        entry = getattr(h, "_entry", None)
        if entry is not None:
            h._entry = None


def _sever_nodes(order):
    """Break the NDArray._entry <-> Node.inputs reference cycle once the
    backward pass has consumed the tape. Without this, every recorded
    intermediate survives until a *cyclic* GC run — residual buffers free
    late AND (under op bulking) segment liveness becomes GC-timing-dependent,
    destabilizing the replay-cache keys into per-iteration recompiles."""
    for n in order:
        n.inputs = None
        n.inputs_raw = None
        n.parents = ()
        n.vjp_fn = None
        n.fn = None
        n.cached_vjp = None


def grad(heads, variables, head_grads=None, retain_graph=None, create_graph=False,
         train_mode=True):
    """Return gradients of heads w.r.t. variables (≙ autograd.grad:272)."""
    if retain_graph is None:
        retain_graph = create_graph
    if not isinstance(variables, (list, tuple)):
        variables = [variables]
        single = True
    else:
        single = False
    for v in variables:
        if getattr(v, "_var", None) is None and getattr(v, "_entry", None) is None:
            raise MXNetError("grad target must be a marked variable "
                             "(call attach_grad()) or tape-connected")
    out = backward(heads, head_grads, retain_graph=retain_graph,
                   train_mode=train_mode, create_graph=create_graph,
                   variables=variables)
    return out[0] if single else out


# ---------------------------------------------------------------------------
# Custom differentiable function (≙ autograd.Function, autograd.py:389-519)
# ---------------------------------------------------------------------------
class Function:
    """User-defined op with custom backward.

    class Sigmoid(Function):
        def forward(self, x): ...   # runs with autograd paused
        def backward(self, dy): ... # returns grads w.r.t. forward inputs
    """

    def __init__(self):
        self._saved = None

    def save_for_backward(self, *arrays):
        self._saved = arrays

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *output_grads):
        raise NotImplementedError

    def __call__(self, *inputs):
        from .ndarray import NDArray, _wrap
        with pause():
            outputs = self.forward(*inputs)
        single = not isinstance(outputs, (list, tuple))
        outs = [outputs] if single else list(outputs)
        if is_recording():
            parents = []
            for a in inputs:
                if isinstance(a, NDArray):
                    if getattr(a, "_var", None) is not None:
                        parents.append(("var", a))
                        continue
                    e = getattr(a, "_entry", None)
                    if e is not None:
                        parents.append(("node", e[0], e[1]))
                        continue
                parents.append(None)

            fn = self

            def vjp_fn(cts):
                with pause():
                    gs = fn.backward(*[_wrap(c) for c in cts])
                if not isinstance(gs, (list, tuple)):
                    gs = [gs]
                return tuple(g._arr if isinstance(g, NDArray) else g for g in gs)

            node = Node(vjp_fn, parents,
                        [(o.shape, o.dtype) for o in outs],
                        name=type(self).__name__)
            for i, o in enumerate(outs):
                o._entry = (node, i)
        return outs[0] if single else tuple(outs)
