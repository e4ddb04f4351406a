"""mx.inspect.memory — device-memory observability.

The time side of the observability story is rich (StepTimeline, the HLO
roofline in `inspect.roofline`, request tracing, the flight recorder); the
MEMORY side was an opaque `RESOURCE_EXHAUSTED` with no record of which
subsystem owned the bytes. The reference answered this with its storage
profiler and pooled `StorageManager` accounting (`MXNET_PROFILER_MODE`
memory lanes — PAPER.md layers 2 and 8); the XLA-era equivalent here is
four connected pieces:

  * **Memory plans** — `memory_plan(obj, *args)` extracts the compiled
    program's buffer-assignment totals (`argument_size` / `output_size` /
    `temp_size` / `alias_size` / `generated_code_size`, via
    `Compiled.memory_analysis()`) from every surface that already exposes
    `.lowered()` — `FusedTrainStep` / `FusedInferStep`,
    `deploy.ExportedModel` bucket programs, the continuous engine's
    prefill + decode programs (`ContinuousEngine.memory_plans()`), and
    the elastic bucketed collectives (`collective_memory_plans()`).
    `peak_bytes = argument + output + temp - alias` is the predicted peak
    HBM of one execution. Degradation contract (the PR-7 rule): a jax/
    backend without `memory_analysis()` falls back to an HLO-shape lower
    bound (`source: "hlo_shapes"`, `complete: false`) and an unparseable
    program degrades to zeros (`source: "unavailable"`) — never a crash.
    `assert_donation(plan, params_bytes)` proves buffer donation actually
    aliased: with donation on, `alias_size` covers the donated buffers;
    with it off the assertion raises — a remat×donate regression that
    doubles peak HBM is a failing number, not a vibe.

  * **Attributed census** — a lightweight ownership registry:
    subsystems `register(array_or_tree, owner="kv_pool")` their long-lived
    device buffers (KVCachePool slabs, ShardedOptimizer shards,
    DeviceFeed/ImageRecordIter staging, FusedTrainStep weights), or wrap a
    region in `with tag("my_subsystem"):` so inner `register(tree)` calls
    inherit the owner. `census()` then groups `jax.live_arrays()` into
    owner -> {count, bytes, shapes} with an honest `untagged` bucket —
    attribution is by registration, never inference. `census_diff(a, b)`
    is the leak detector's primitive and `leakcheck(fn, rounds=N)` fails
    when untagged live bytes grow monotonically across rounds.

  * **OOM forensics** — `on_oom(error)` recognizes
    RESOURCE_EXHAUSTED/out-of-memory errors and dumps census + the active
    memory plans + the flight-recorder ring as one JSON black box before
    the error re-raises, wired into `run_resilient` / `run_elastic` /
    the serve engines next to the existing flightrec arm hooks
    (`install_oom_hook()` additionally chains `sys.excepthook` so an
    UNCAUGHT OOM still leaves the dump). `StepTimeline` gains a
    `peak_hbm_bytes` lane from the same `profiler.read_memory_sample()`
    the MemoryMonitor uses (honest `device` vs `host_rss` source stamp).

  * **Operator CLI** — `tools/memscope.py`. The chip's peak is the
    benchmark's `memory_peak_bytes` (PERF.md §4, §7).

Owner names are flat `[a-z0-9_]+` tokens ON PURPOSE: dotted names would
collide with the telemetry metric namespace in the docs tables, and
mxlint's `mem-owner-*` rules hold the code <-> OBSERVABILITY.md owner
table consistent both directions.

Census accounting note: `bytes` is `Array.nbytes` — the GLOBAL logical
size of a sharded array (on the in-process CPU mesh that equals the
host bytes actually held; on a multi-host mesh divide by the process
count for the per-host share).

Knobs: `MXNET_MEM_SAMPLE_INTERVAL`, `MXNET_MEM_OOM_DUMP`,
`MXNET_MEM_CENSUS_DEPTH` (docs/ENV_VARS.md). Metric catalog (`mem.*`):
docs/OBSERVABILITY.md "Device memory".
"""
from __future__ import annotations

import contextvars
import json
import os
import re
import sys
import threading
import weakref
from collections import OrderedDict

from ..base import MXNetError, get_env, _register_env
from ..telemetry import REGISTRY
from ..telemetry import trace as _trace

__all__ = [
    "memory_plan", "plan_from_compiled", "assert_donation",
    "collective_memory_plans", "active_plans", "note_plan",
    "tag", "register", "current_tag", "census", "census_diff",
    "leakcheck", "MemoryLeakError",
    "is_oom_error", "on_oom", "oom_report", "dump_oom",
    "install_oom_hook",
]

_register_env("MXNET_MEM_SAMPLE_INTERVAL", float, 0.05,
              "Default sampling interval (seconds) of "
              "profiler.MemoryMonitor — the device-memory timeline lane")
_register_env("MXNET_MEM_OOM_DUMP", str, None,
              "OOM black-box dumps: unset/1 = enabled (files land in "
              "MXNET_FLIGHTREC_DIR, else the cwd), 0 = disabled, any "
              "other value = the dump directory")
_register_env("MXNET_MEM_CENSUS_DEPTH", int, 5,
              "Distinct shapes listed per owner in census() reports "
              "(counts/bytes always cover everything)")

# -- metrics (docs/OBSERVABILITY.md "Device memory" catalog) ----------------
MEM_PLANS = REGISTRY.counter(
    "mem.plans", help="compiled-program memory plans computed")
MEM_CENSUS_RUNS = REGISTRY.counter(
    "mem.census_runs", help="live-buffer census passes")
MEM_TAGGED = REGISTRY.gauge(
    "mem.tagged_bytes", help="live device bytes attributed to a named "
    "owner in the most recent census")
MEM_UNTAGGED = REGISTRY.gauge(
    "mem.untagged_bytes", help="live device bytes with no registered "
    "owner in the most recent census")
MEM_OOM_DUMPS = REGISTRY.counter(
    "mem.oom_dumps", help="OOM black-box dump files written")


# ---------------------------------------------------------------------------
# memory plans
# ---------------------------------------------------------------------------
_PLAN_FIELDS = (
    ("argument_size", "argument_size_in_bytes"),
    ("output_size", "output_size_in_bytes"),
    ("temp_size", "temp_size_in_bytes"),
    ("alias_size", "alias_size_in_bytes"),
    ("generated_code_size", "generated_code_size_in_bytes"),
)

# name -> plan of the most recent plans computed in this process: what an
# OOM dump reports as "what was supposed to fit". Bounded (a sweep over
# many bucket programs must not grow without limit).
_plans_lock = threading.Lock()
_ACTIVE_PLANS = OrderedDict()
_ACTIVE_PLANS_CAP = 32


def note_plan(name, plan):
    """Record `plan` in the active-plan table the OOM dump reports."""
    with _plans_lock:
        _ACTIVE_PLANS.pop(name, None)
        _ACTIVE_PLANS[name] = plan
        while len(_ACTIVE_PLANS) > _ACTIVE_PLANS_CAP:
            _ACTIVE_PLANS.popitem(last=False)


def active_plans():
    """{name: plan} snapshot of the plans computed in this process."""
    with _plans_lock:
        return dict(_ACTIVE_PLANS)


def _shape_fallback(compiled, plan):
    """HLO-shape lower bound when memory_analysis() is unavailable: sum
    the entry computation's parameter and root-output shapes. `temp_size`
    is honestly unknown (0) — the plan says so via `complete: false`."""
    from . import hlo as _hlo
    try:
        module = _hlo.parse_module(compiled.as_text())
        entry = module.entry or next(iter(module.computations.values()))
        arg = out = 0
        for ins in entry.instructions:
            if ins.opcode == "parameter":
                arg += _hlo.shape_bytes(ins.shape)
        root = entry.root
        if root is not None:
            out = _hlo.shape_bytes(root.shape)
        plan.update(argument_size=int(arg), output_size=int(out),
                    temp_size=0, alias_size=0, generated_code_size=0,
                    peak_bytes=int(arg + out),
                    source="hlo_shapes", complete=False)
    except Exception as e:
        # last resort: an unparseable program still yields a plan object,
        # flagged unusable — never a crash (the PR-7 degradation contract)
        plan.update(argument_size=0, output_size=0, temp_size=0,
                    alias_size=0, generated_code_size=0, peak_bytes=0,
                    source="unavailable", complete=False,
                    error=f"{type(e).__name__}: {e}")
    return plan


def plan_from_compiled(compiled, name="program"):
    """Memory plan of an already-compiled stage (json.dumps-safe dict).

    `source` says where the numbers came from: `memory_analysis` (XLA's
    buffer assignment — authoritative, includes temporaries and donation
    aliasing), `hlo_shapes` (argument/output lower bound only), or
    `unavailable`. `peak_bytes = argument + output + temp - alias` is the
    predicted device high-water of one execution (aliased argument bytes
    are reused for outputs, so they never exist twice)."""
    plan = {"name": name}
    ma = None
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is not None and hasattr(ma, "argument_size_in_bytes"):
        try:
            for key, attr in _PLAN_FIELDS:
                plan[key] = int(getattr(ma, attr, 0) or 0)
            plan["peak_bytes"] = max(0, plan["argument_size"]
                                     + plan["output_size"]
                                     + plan["temp_size"]
                                     - plan["alias_size"])
            plan["source"] = "memory_analysis"
            plan["complete"] = True
        except Exception:
            plan = _shape_fallback(compiled, {"name": name})
    else:
        plan = _shape_fallback(compiled, plan)
    MEM_PLANS.inc()
    note_plan(name, plan)
    return plan


def memory_plan(obj, *args, name=None):
    """Memory plan for any inspectable surface: FusedTrainStep /
    FusedInferStep (`memory_plan(step, x, y)`), `deploy.ExportedModel`
    (per bucket program), jitted callables, `jax.stages.Lowered` /
    `Compiled` stages — the same `lower_any` resolution the roofline
    profiler uses, so everything `inspect_step` can rank, this can
    size."""
    from .report import lower_any, _name_of
    compiled = lower_any(obj, *args)
    return plan_from_compiled(compiled, name=name or _name_of(obj))


def assert_donation(plan, params_bytes, slack=0.02):
    """Prove the plan actually aliased (donated) at least `params_bytes`
    of its arguments. Raises MXNetError when it did not — the guard that
    turns a donate=off (or remat-policy-broke-donation) regression into a
    failing number. `slack` tolerates sub-percent layout padding."""
    params_bytes = int(params_bytes)
    if plan.get("source") != "memory_analysis":
        raise MXNetError(
            f"cannot prove donation for plan {plan.get('name')!r}: "
            f"buffer-assignment stats unavailable "
            f"(source={plan.get('source')!r})")
    aliased = int(plan.get("alias_size", 0))
    if aliased + slack * params_bytes < params_bytes:
        raise MXNetError(
            f"donation check failed for {plan.get('name')!r}: "
            f"{aliased} bytes aliased < {params_bytes} bytes of donated "
            f"buffers — donation did not take (peak HBM pays the "
            f"buffers twice)")
    return aliased


def collective_memory_plans():
    """Memory plans of every cached elastic bucketed-collective program
    (`kvstore.reduce_scatter_buckets` / `allgather_buckets`): run a
    trainer step first so the programs exist, then call this. Returns
    {name: plan}; a program whose lowering fails (dead mesh) degrades to
    a `source: "unavailable"` entry, never a crash."""
    from ..kvstore import collective_compiled_surfaces
    plans = {}
    for i, s in enumerate(collective_compiled_surfaces()):
        name = f"kvstore.{s['kind']}[{i}]"
        try:
            lowered = s["fn"].lower(*s["avals"])
            plans[name] = plan_from_compiled(lowered.compile(), name=name)
        except Exception as e:
            plans[name] = {"name": name, "source": "unavailable",
                           "complete": False, "peak_bytes": 0,
                           "error": f"{type(e).__name__}: {e}"}
    return plans


# ---------------------------------------------------------------------------
# ownership registry + census
# ---------------------------------------------------------------------------
_OWNER_RE = re.compile(r"^[a-z0-9_]+$")
_reg_lock = threading.Lock()
_owned = {}          # id(raw array) -> (weakref, owner)
_tag_ctx = contextvars.ContextVar("mx_mem_tag", default=None)


class MemoryLeakError(MXNetError):
    """leakcheck() observed monotonically growing untagged live bytes."""


def _check_owner(owner):
    if not isinstance(owner, str) or not _OWNER_RE.match(owner):
        raise MXNetError(
            f"memory owner must be a flat [a-z0-9_]+ token (dots would "
            f"collide with the metric namespace), got {owner!r}")
    return owner


class tag:
    """`with mem.tag("my_subsystem"):` — ambient owner for `register`
    calls in the block (thread/context-local; nesting shadows)."""

    __slots__ = ("owner", "_token")

    def __init__(self, owner):
        self.owner = _check_owner(owner)
        self._token = None

    def __enter__(self):
        self._token = _tag_ctx.set(self.owner)
        return self

    def __exit__(self, *exc):
        _tag_ctx.reset(self._token)
        return False


def current_tag():
    """The ambient owner set by an enclosing `tag(...)`, or None."""
    return _tag_ctx.get()


def _register_leaf(raw, owner):
    key = id(raw)

    def _gone(ref, key=key):
        # only delete OUR entry: a recycled id may already belong to a
        # newer registration by the time this callback fires
        with _reg_lock:
            ent = _owned.get(key)
            if ent is not None and ent[0] is ref:
                del _owned[key]

    try:
        ref = weakref.ref(raw, _gone)
    except TypeError:
        return                       # unweakrefable leaf: skip silently
    with _reg_lock:
        _owned[key] = (ref, owner)


def register(tree, owner=None):
    """Attribute `tree`'s array leaves to `owner` (or the ambient
    `tag(...)` owner). Idempotent and cheap — a weakref per leaf; dead
    arrays drop their entries automatically, and re-registering under a
    new owner overwrites (the donated-buffer-swap idiom re-registers the
    fresh buffers each step). Returns `tree` so call sites can wrap
    in-line. Never raises for odd leaves — attribution must not be able
    to break the subsystem it observes."""
    owner = _check_owner(owner if owner is not None
                         else (_tag_ctx.get() or _no_owner()))
    _walk_register(tree, owner)
    return tree


def _no_owner():
    raise MXNetError("register() needs owner= (or an enclosing "
                     "`with mem.tag(...):` block)")


def _walk_register(node, owner):
    if node is None:
        return
    if isinstance(node, dict):
        for v in node.values():
            _walk_register(v, owner)
        return
    if isinstance(node, (list, tuple)):
        for v in node:
            _walk_register(v, owner)
        return
    raw = getattr(node, "_arr", node)    # NDArray unwraps to its buffer
    if hasattr(raw, "nbytes") and hasattr(raw, "shape"):
        _register_leaf(raw, owner)


def registered_count():
    """Live registry entries (test/diagnostic aid)."""
    with _reg_lock:
        return len(_owned)


def census(depth=None):
    """Group `jax.live_arrays()` by registered owner.

    Returns a json-safe report::

        {"owners": {name: {"count", "bytes", "shapes": {repr: count}}},
         "total_bytes", "tagged_bytes", "untagged_bytes",
         "tagged_fraction", "n_arrays"}

    Attribution is honest: only explicitly registered buffers get a
    name; everything else lands in `untagged` (jit caches, constants,
    user arrays). `depth` bounds the distinct shapes listed per owner
    (`MXNET_MEM_CENSUS_DEPTH`; counts and bytes always cover all)."""
    import jax
    if depth is None:
        depth = get_env("MXNET_MEM_CENSUS_DEPTH", 5, typ=int)
    with _reg_lock:
        snapshot = dict(_owned)
    owners = {}
    total = tagged = n = 0
    for arr in jax.live_arrays():
        try:
            nb = int(arr.nbytes)
        except Exception:
            continue
        n += 1
        total += nb
        ent = snapshot.get(id(arr))
        name = "untagged"
        if ent is not None and ent[0]() is arr:
            name = ent[1]
            tagged += nb
        g = owners.get(name)
        if g is None:
            g = owners[name] = {"count": 0, "bytes": 0, "shapes": {}}
        g["count"] += 1
        g["bytes"] += nb
        srep = f"{arr.dtype}{list(arr.shape)}"
        if srep in g["shapes"] or len(g["shapes"]) < depth:
            g["shapes"][srep] = g["shapes"].get(srep, 0) + 1
    ordered = OrderedDict(sorted(owners.items(),
                                 key=lambda kv: -kv[1]["bytes"]))
    untagged = total - tagged
    MEM_CENSUS_RUNS.inc()
    MEM_TAGGED.set(tagged)
    MEM_UNTAGGED.set(untagged)
    return {"owners": ordered, "total_bytes": total,
            "tagged_bytes": tagged, "untagged_bytes": untagged,
            "tagged_fraction": round(tagged / total, 6) if total else 0.0,
            "n_arrays": n}


def census_diff(before, after):
    """Per-owner growth between two census() reports: the leak
    detector's primitive. Positive `bytes` = grew."""
    owners = {}
    names = set(before["owners"]) | set(after["owners"])
    for name in sorted(names):
        a = before["owners"].get(name, {"count": 0, "bytes": 0})
        b = after["owners"].get(name, {"count": 0, "bytes": 0})
        db, dc = b["bytes"] - a["bytes"], b["count"] - a["count"]
        if db or dc:
            owners[name] = {"bytes": db, "count": dc}
    return {"owners": owners,
            "total_bytes": after["total_bytes"] - before["total_bytes"],
            "untagged_bytes": (after["untagged_bytes"]
                               - before["untagged_bytes"])}


def leakcheck(fn, rounds=4, raise_on_leak=True, min_growth_bytes=4096):
    """Run `fn()` `rounds` times and fail when untagged live bytes grow
    MONOTONICALLY across every round — the signature of a per-round leak
    (a dropped reference cycle, an accumulating cache, a buffer pinned
    per call). One extra warmup execution runs first and is NOT counted:
    first-call allocation (jit compile caches, pool carves) is expected
    growth, not a leak.

    Returns the report; with `raise_on_leak` (default) a detected leak
    raises `MemoryLeakError` carrying it. `min_growth_bytes` filters
    allocator jitter: total growth below it never fails."""
    if rounds < 2:
        raise MXNetError("leakcheck needs rounds >= 2")
    fn()                                     # warmup: first-call allocs
    series_untagged, series_total = [], []
    baseline = census()
    for _ in range(rounds):
        fn()
        c = census()
        series_untagged.append(c["untagged_bytes"])
        series_total.append(c["total_bytes"])
    growth = series_untagged[-1] - baseline["untagged_bytes"]
    monotone = all(b > a for a, b in zip(series_untagged,
                                         series_untagged[1:]))
    leak = bool(monotone and growth >= min_growth_bytes)
    report = {"rounds": rounds, "leak": leak,
              "untagged_bytes": series_untagged,
              "total_bytes": series_total,
              "baseline_untagged_bytes": baseline["untagged_bytes"],
              "growth_bytes": int(growth),
              "growth_mb": round(growth / 2**20, 3),
              "per_round_bytes": int(growth / rounds)}
    if leak and raise_on_leak:
        err = MemoryLeakError(
            f"untagged live bytes grew monotonically across {rounds} "
            f"rounds (+{growth} bytes, ~{report['per_round_bytes']} "
            f"bytes/round) — something allocates per call and never "
            f"frees")
        err.report = report
        raise err
    return report


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------
_OOM_MARKERS = ("resource_exhausted", "resource exhausted",
                "out of memory", "allocation failure")


def is_oom_error(error):
    """Does this exception look like a device/host OOM? Matches the XLA
    RESOURCE_EXHAUSTED family (`XlaRuntimeError`, RuntimeError text) and
    plain MemoryError — by message, because jaxlib's exception types vary
    across versions."""
    if error is None:
        return False
    if isinstance(error, MemoryError):
        return True
    msg = f"{type(error).__name__}: {error}".lower()
    return any(m in msg for m in _OOM_MARKERS)


def _oom_dump_dir():
    v = get_env("MXNET_MEM_OOM_DUMP", typ=str)
    if v and v not in ("0", "1"):
        return v
    d = _trace.FLIGHTREC._spool_dir()
    return d or "."


def _oom_dump_enabled():
    return get_env("MXNET_MEM_OOM_DUMP", typ=str) != "0"


def oom_report(error=None):
    """The black-box payload: census + active memory plans + the
    flight-recorder ring + device memory info. Every piece degrades
    independently (a dump on the crash path must never raise)."""
    from .. import profiler as _profiler
    rep = {"pid": os.getpid(),
           "error": None if error is None else
           f"{type(error).__name__}: {error}"}
    try:
        rep["census"] = census()
    except Exception as e:
        rep["census_error"] = f"{type(e).__name__}: {e}"
    rep["plans"] = active_plans()
    try:
        sample, source = _profiler.read_memory_sample()
        rep["bytes_in_use"] = sample
        rep["memory_source"] = source
    except Exception:
        pass
    try:
        from ..device import device_memory_info
        info = device_memory_info()
        rep["device_memory"] = {"free": info.free, "total": info.total,
                                "known": info.known}
    except Exception:
        pass
    try:
        rep["flightrec"] = _trace.flightrec_events()
    except Exception:
        pass
    return rep


def dump_oom(error=None, path=None, reason="oom"):
    """Write the OOM black box as one JSON file; returns the path or
    None (crash-path code: never raises). Default location:
    `<dir>/oomdump-<pid>.json` under MXNET_MEM_OOM_DUMP / the flightrec
    dir / the cwd — newest dump wins (atomic replace)."""
    try:
        rep = oom_report(error)
        rep["reason"] = reason
        if path is None:
            d = _oom_dump_dir()
            os.makedirs(d, exist_ok=True)
            path = os.path.join(d, f"oomdump-{os.getpid()}.json")
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(rep, f, default=str)
        os.replace(tmp, path)
        MEM_OOM_DUMPS.inc()
        return path
    except Exception:
        return None


def on_oom(error, where=""):
    """The OOM handler the drivers call before re-raising: if `error` is
    OOM-shaped (and dumps are enabled), record it in the flight recorder
    and write the black box. Returns the dump path, or None when the
    error is not an OOM / dumping is off. Never raises."""
    try:
        if not is_oom_error(error) or not _oom_dump_enabled():
            return None
        _trace.flightrec_record("oom", where or "oom",
                                error=str(error)[:400])
        _trace.flightrec_maybe_dump("oom")
        return dump_oom(error=error, reason=where or "oom")
    except Exception:
        return None


_hook_lock = threading.Lock()
_hook_installed = [False]


def install_oom_hook():
    """Idempotent: chain `sys.excepthook` so an UNCAUGHT OOM writes the
    black box on the way down. Armed by `run_resilient` / `run_elastic`
    / `Server.start` / `ContinuousEngine.start` next to the flight
    recorder's crash hooks; a no-op beyond the first call."""
    with _hook_lock:
        if _hook_installed[0]:
            return
        _hook_installed[0] = True
    prev = sys.excepthook

    def _hook(tp, val, tb):
        try:
            on_oom(val, where="uncaught")
        except Exception:
            pass
        prev(tp, val, tb)

    sys.excepthook = _hook
