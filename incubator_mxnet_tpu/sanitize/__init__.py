"""mx.sanitize — opt-in runtime twin of the mxlint compiled-contract
passes (ISSUE 20).

The static analyzer (`mx.analysis.donation_safety` / `retrace_hazard`)
proves what the LITERALS promise; this package checks what the PROCESS
actually does. Three independent modes, armed via ``MXNET_SANITIZE``
(comma list, e.g. ``MXNET_SANITIZE=donation,retrace`` or ``all``), all
off by default with ZERO overhead when off (`maybe_wrap_donated`
returns the jitted program unchanged):

``donation``
    Wraps every donated compiled program. After each call the donated
    argument leaves are **explicitly deleted** — on CPU donation is a
    no-op, so the notorious "works in CI, dies on TPU" class ships
    silently; deletion makes CPU fail exactly where TPU would. Each
    consumed array is also recorded in a poison registry (weakref +
    provenance), so re-passing a dead array to any wrapped program
    raises a typed :class:`DonationViolation` naming the argument, the
    program that consumed it, and the call that tripped — instead of a
    delayed, anonymous "Array has been deleted".

``retrace``
    A compile-counter sentinel over the zero-retrace contract. Every
    wrapped program is tracked; :func:`arm` snapshots each program's
    compile-cache size (the engine arms automatically after warmup, a
    fleet replica therefore arms in its own process since the spawn env
    carries MXNET_SANITIZE); :func:`poll` raises
    :class:`RetraceViolation` naming the program that grew and the
    argument-signature drift between the armed call and the offending
    one. The engine polls once per decode wave; `steady_state()` wraps
    any other region (the elastic trainer arms after its first step).

``slot``
    Generalizes the PR-14 poison-fill test hook into an always-on
    canary: :class:`SlotCanary` claims ONE pool slot, poisons its KV
    row with a sentinel, and `check()` reads a small probe slice every
    decode wave — any program write that escapes the slot masks shows
    up immediately as :class:`SlotCanaryError` naming the wave, rather
    than as silent cross-request KV corruption. Costs one pool slot and
    one tiny device->host read per wave.

Every violation also lands in the flight recorder
(`telemetry.flightrec_record`), so the crash black box names the
contract breach. The modes' cost on the chip is not measured: no cell
runs with `MXNET_SANITIZE` set.
"""
from __future__ import annotations

import contextlib
import os
import threading
import weakref

from ..base import MXNetError

__all__ = [
    "DonationViolation", "RetraceViolation", "SlotCanaryError",
    "modes", "enabled", "scope", "maybe_wrap_donated", "arm", "poll",
    "steady_state", "tracked_programs", "SlotCanary", "clear",
]

_VALID_MODES = ("donation", "retrace", "slot")


class DonationViolation(MXNetError):
    """A host alias of a donated (consumed) buffer re-entered a compiled
    program."""


class RetraceViolation(MXNetError):
    """A compiled program grew its compile cache inside an armed
    steady-state region."""


class SlotCanaryError(MXNetError):
    """The poisoned canary KV row was overwritten — slot isolation is
    broken."""


# ---------------------------------------------------------------------------
# mode handling
# ---------------------------------------------------------------------------
_override = None          # scope() test hook; beats the env when not None
_olock = threading.Lock()


def modes():
    """The active mode set (frozenset of {'donation','retrace','slot'})."""
    if _override is not None:
        return _override
    raw = os.environ.get("MXNET_SANITIZE", "")
    if not raw:
        return frozenset()
    if raw.strip() == "all":
        return frozenset(_VALID_MODES)
    out = set()
    for piece in raw.split(","):
        piece = piece.strip()
        if not piece:
            continue
        if piece not in _VALID_MODES:
            raise MXNetError(
                f"MXNET_SANITIZE: unknown mode {piece!r} "
                f"(valid: {', '.join(_VALID_MODES)}, or 'all')")
        out.add(piece)
    return frozenset(out)


def enabled(mode):
    return mode in modes()


@contextlib.contextmanager
def scope(spec):
    """Arm modes for a block regardless of the env (test hook):
    ``with sanitize.scope("donation,retrace"): ...``"""
    global _override
    new = (frozenset(_VALID_MODES) if spec.strip() == "all"
           else frozenset(p.strip() for p in spec.split(",") if p.strip()))
    for m in new:
        if m not in _VALID_MODES:
            raise MXNetError(f"sanitize.scope: unknown mode {m!r}")
    with _olock:
        prev, _override = _override, new
    try:
        yield
    finally:
        with _olock:
            _override = prev


def _flightrec(kind, name, **fields):
    """Record the violation in the flight recorder and (when
    MXNET_FLIGHTREC_DIR is set) leave a black-box dump, so a contract
    breach names itself on disk even if the raising process dies."""
    try:
        from ..telemetry import flightrec_maybe_dump, flightrec_record
        flightrec_record(kind, name, **fields)
        flightrec_maybe_dump(reason=f"{kind}:{name}")
    except Exception:
        pass                     # diagnostics must never mask the error


# ---------------------------------------------------------------------------
# donation mode: poison registry
# ---------------------------------------------------------------------------
_plock = threading.Lock()
_poisoned = {}            # id(arr) -> (weakref, provenance string)
_MAX_POISONED = 4096


def _arr_leaves(tree):
    import jax
    return [x for x in jax.tree_util.tree_leaves(tree)
            if isinstance(x, jax.Array)]


def _register_consumed(leaves, provenance):
    with _plock:
        if len(_poisoned) > _MAX_POISONED:
            dead = [k for k, (r, _) in _poisoned.items() if r() is None]
            for k in dead:
                del _poisoned[k]
        for a in leaves:
            try:
                _poisoned[id(a)] = (weakref.ref(a), provenance)
            except TypeError:
                pass             # non-weakrefable leaf: skip tracking


def _check_alive(args, kwargs, prog_name):
    """Raise DonationViolation when any argument leaf was consumed by an
    earlier donated call (the poison registry names the consumer)."""
    for i, a in enumerate(args):
        for leaf in _arr_leaves(a):
            hit = None
            with _plock:
                rec = _poisoned.get(id(leaf))
                if rec is not None and rec[0]() is leaf:
                    hit = rec[1]
            dead = False
            try:
                dead = leaf.is_deleted()
            except Exception:
                pass
            if hit is not None or dead:
                why = hit or "an earlier donated call"
                _flightrec("sanitize.donation", prog_name, arg=i,
                           consumed_by=why)
                raise DonationViolation(
                    f"argument {i} of `{prog_name}` is a host alias of a "
                    f"buffer already consumed by {why} — rebind it from "
                    f"that program's output (donated buffers die with "
                    f"the call; on TPU this read would be a delayed "
                    f"'Array has been deleted')")


def _consume_donated(args, donated, prog_name):
    """Post-call: register + delete the donated argument leaves so CPU
    fails exactly where TPU would."""
    for pos in donated:
        if pos >= len(args):
            continue
        leaves = _arr_leaves(args[pos])
        _register_consumed(
            leaves, f"`{prog_name}` (donated argument {pos})")
        for leaf in leaves:
            try:
                if not leaf.is_deleted():
                    leaf.delete()
            except Exception:
                pass             # committed/global arrays refuse: fine


# ---------------------------------------------------------------------------
# retrace mode: compile-counter sentinel
# ---------------------------------------------------------------------------
_tracked = weakref.WeakSet()     # every _SanitizedProgram ever built
_tracked_version = 0             # bumped per new program (cheap "did a
_arm_epoch = 0                   # new variant appear" check in poll)
_arm_version = -1
_armed_snapshot = []             # [(weakref(prog), size, sig)] at arm
_alock = threading.Lock()


def _signature(args):
    """Cheap aval signature of a call: (shape, dtype) per array leaf,
    type name per other leaf — the drift shown by RetraceViolation."""
    import jax
    sig = []
    for leaf in jax.tree_util.tree_leaves(args):
        if isinstance(leaf, jax.Array):
            sig.append((tuple(leaf.shape), str(leaf.dtype)))
        else:
            sig.append(type(leaf).__name__)
    return tuple(sig)


def tracked_programs():
    return list(_tracked)


def arm():
    """Snapshot every tracked program's compile-cache size; later growth
    — or a NEW program compiling — is a RetraceViolation. The engine
    calls this after warmup (re-arming re-baselines everything, so a
    second engine's warmup in the same process is not a false trip)."""
    global _arm_epoch, _arm_version, _armed_snapshot
    with _alock:
        _arm_epoch += 1
        _arm_version = _tracked_version
        _armed_snapshot = [
            (weakref.ref(prog), prog._cache_size(), prog._last_sig)
            for prog in _tracked]


def _retrace_error(prog, size, sig, cur, where, is_new):
    _flightrec("sanitize.retrace", prog.name, armed=size, now=cur,
               where=where)
    grew = (f"is a NEW program variant compiled after arm "
            f"({cur} program(s))" if is_new else
            f"compiled {cur - size} new program(s) after arm "
            f"({size} -> {cur})")
    last = prog._last_sig
    raise RetraceViolation(
        f"`{prog.name}` {grew}"
        + (f" in {where}" if where else "")
        + (f"; armed-call signature {sig} vs last call {last}"
           if sig != last else
           "; argument signatures are identical — look for "
           "weak-type or sharding drift"))


def poll(where=""):
    """Raise RetraceViolation if any tracked program compiled since the
    last `arm()` — cache growth of an armed program, or a brand-new
    program variant materializing after warmup. No-op until armed.
    Steady-path cost is one `_cache_size()` int compare per armed
    program; the new-variant scan only runs when a program was actually
    built since arm (version counter)."""
    if _arm_epoch == 0:
        return
    for ref, size, sig in _armed_snapshot:
        prog = ref()
        if prog is None or size < 0:
            continue
        cur = prog._cache_size()
        if cur > size:
            _retrace_error(prog, size, sig, cur, where, is_new=False)
    if _tracked_version != _arm_version:
        with _alock:
            armed = {ref() for ref, _, _ in _armed_snapshot}
            progs = [p for p in _tracked if p not in armed]
        for prog in progs:
            cur = prog._cache_size()
            if cur > 0:
                _retrace_error(prog, 0, None, cur, where, is_new=True)


@contextlib.contextmanager
def steady_state(where="steady-state"):
    """Arm on entry, poll on exit: any recompile inside the region
    raises. Wrap an engine/elastic steady loop body or a whole run."""
    arm()
    yield
    poll(where=where)


def clear():
    """Drop all sanitizer state (poison registry, armed snapshots) —
    test isolation hook."""
    global _arm_epoch, _arm_version, _armed_snapshot
    with _plock:
        _poisoned.clear()
    with _alock:
        _armed_snapshot = []
        _arm_epoch = 0
        _arm_version = -1


# ---------------------------------------------------------------------------
# the wrapper
# ---------------------------------------------------------------------------
class _SanitizedProgram:
    """Transparent shim over one donated jitted program. Forwards every
    attribute (`lower`, `_cache_size`, ...) so warmup lowering and the
    zero-retrace observable see the real jit."""

    def __init__(self, fn, donate_argnums, name):
        global _tracked_version
        self._fn = fn
        self._donated = tuple(int(p) for p in donate_argnums)
        self.name = name
        self._last_args = None
        with _alock:
            _tracked.add(self)
            _tracked_version += 1

    def __call__(self, *args, **kwargs):
        active = modes()
        if "donation" in active:
            _check_alive(args, kwargs, self.name)
        if "retrace" in active:
            # keep only a REFERENCE — the (shape, dtype) signature is
            # computed lazily at arm/violation time (~150us per call
            # saved on the steady path; the donated leaves held here are
            # dead husks, so no live device memory is pinned)
            self._last_args = args
        out = self._fn(*args, **kwargs)
        if "donation" in active:
            _consume_donated(args, self._donated, self.name)
        return out

    @property
    def _last_sig(self):
        return (None if self._last_args is None
                else _signature(self._last_args))

    def _cache_size(self):
        f = getattr(self._fn, "_cache_size", None)
        try:
            return int(f()) if f is not None else -1
        except Exception:
            return -1

    def __getattr__(self, item):
        return getattr(self._fn, item)

    def __repr__(self):
        return f"<sanitized {self.name} donate={self._donated}>"


def maybe_wrap_donated(fn, donate_argnums, name):
    """Wrap a donated jitted program when any sanitizer mode is armed at
    build time; otherwise return `fn` unchanged (zero overhead off).

    The wrapper checks the LIVE mode set on every call, so a program
    built inside `sanitize.scope(...)` (or with MXNET_SANITIZE set)
    reacts to mode changes without rebuilding."""
    if not modes():
        return fn
    return _SanitizedProgram(fn, donate_argnums, name)


# ---------------------------------------------------------------------------
# slot mode: the canary row
# ---------------------------------------------------------------------------
class SlotCanary:
    """One claimed-and-poisoned pool slot, checked every decode wave.

    The decode program runs over ALL pool rows as lanes; the canary slot
    is never handed to a request, so its lane is permanently inactive
    and must scatter into the garbage row and keep whatever state it
    holds — if the sentinel row of ANY cache leaf ever changes (K/V
    slabs, rings, recurrent state alike), a program wrote through the
    slot masks. `rearm()` after `pool.reallocate()` (the leaves were
    replaced wholesale)."""

    def __init__(self, pool, value=1e9):
        import jax
        self.pool = pool
        self.value = float(value)
        self.slot = pool.claim()
        self.waves = 0
        self._arm()
        slot = self.slot
        self._expect = pool._sentinels(self.value)
        # probe elements of the canary row of every leaf — its first, its
        # middle and its last catch both scatter-offset and full-row
        # overwrites
        self._probes = {
            leaf.name: sorted({tuple(0 for _ in leaf.shape),
                               tuple(d // 2 for d in leaf.shape),
                               tuple(d - 1 for d in leaf.shape)})
            for leaf in pool.spec}

        # ONE compiled fused probe per wave (every leaf -> a scalar):
        # a per-leaf fancy-index gather + np.asarray would be a host
        # round trip for every leaf
        def _ok(leaves):
            ok = True
            for name, at in self._probes.items():
                for idx in at:
                    ok &= leaves[name][(slot,) + idx] == self._expect[name]
            return ok

        self._probe_ok = jax.jit(_ok)
        self._pending = None

    def _arm(self):
        self.pool.poison_slot(self.slot, self.value)

    def rearm(self):
        """Re-poison after the leaves were replaced (pool.reallocate())."""
        self._arm()
        self._pending = None        # drop a probe of the dead buffers

    def check(self, where="decode wave"):
        """Probe the canary row; raise SlotCanaryError when it lost its
        sentinel. The probe is PIPELINED one wave deep: each call
        dispatches this wave's fused probe and reads the PREVIOUS
        wave's result, so the device->host sync lands after the overlap
        window instead of stalling the wave that issued it (detection
        still runs every wave, surfacing at most one wave late)."""
        import numpy as _np
        self.waves += 1
        pending, self._pending = (self._pending,
                                  self._probe_ok(dict(self.pool.leaves)))
        if pending is None or bool(pending):
            return
        self._pending = None
        # slow path (violation only): name the leaf and what we found
        for nm, at in self._probes.items():
            got = _np.asarray([self.pool.leaves[nm][(self.slot,) + idx]
                               for idx in at])
            if not _np.all(got == _np.asarray(self._expect[nm],
                                              dtype=got.dtype)):
                _flightrec("sanitize.slot", nm, slot=self.slot,
                           where=where, waves=self.waves)
                raise SlotCanaryError(
                    f"canary KV slot {self.slot} ({nm} slab) was "
                    f"overwritten at {where} (wave {self.waves}): "
                    f"expected sentinel {self._expect[nm]}, found "
                    f"{got.ravel()[:4].tolist()} — a compiled program "
                    f"wrote outside its slot masks")
        raise SlotCanaryError(
            f"canary KV slot {self.slot} failed its probe at {where} "
            f"(wave {self.waves})")

    def release(self):
        self.pool.free(self.slot)
