"""Retrace-hazard pass: Python values that vary at run time must not
reach trace-time positions inside steady-state loops.

The zero-retrace contract (`ContinuousEngine.retraces_after_warmup()`,
`programs_compiled`) holds only if every trace-time input — array SHAPES,
`static_argnums` values, pytree STRUCTURE — is constant across steady-
state iterations. The classic leaks are all Python-side: `len(batch)` of
a runtime collection used as an array dim (recompiles per batch size),
a static arg recomputed per iteration (recompiles per value; unhashable
literals fail outright), and dicts built from unordered sets (pytree
structure varies per process, silently doubling the program cache).

A function is STEADY-STATE when it sits on the engine's replay path: it
contains a loop that (directly, or through same-module helpers such as
`ContinuousEngine._iterate` -> `_dispatch_wave` / the batcher `_execute`)
invokes a compiled program resolved by the donation-safety program table.

Rules:

  retrace-shape-from-data     `len(...)` / `.shape` of data assembled
                              inside the steady loop flowing into an
                              array-constructor dim or a compiled-program
                              argument — each distinct value is a new
                              trace
  retrace-unstable-static-arg a `static_argnums` position fed an
                              unhashable literal (list/set/dict —
                              TypeError at call time), or, inside a
                              steady loop, a value derived from runtime
                              data (a new compile per distinct value)
  retrace-unordered-pytree    a dict built by iterating a `set(...)` /
                              `frozenset(...)` inside a steady region:
                              pytree key order varies across processes,
                              so "the same" call compiles twice (sort the
                              keys first)
"""
from __future__ import annotations

import ast

from .core import Finding, call_name, dotted
from .donation_safety import (_bind_targets, _own_walk, _scopes,
                              resolve_programs)

__all__ = ["run"]

RULES = ("retrace-shape-from-data", "retrace-unstable-static-arg",
         "retrace-unordered-pytree")

_SHAPED_CTORS = {"zeros", "ones", "full", "empty", "arange"}
_SET_CTORS = {"set", "frozenset"}


def _program_calls(fn, table, qual):
    """[(Call, ProgInfo)] of direct compiled-program calls in fn's own
    body."""
    out = []
    for n in _own_walk(fn):
        if isinstance(n, ast.Call):
            info = table.lookup_call(n, qual)
            if info is not None:
                out.append((n, info))
    return out


def _loops(fn):
    for n in _own_walk(fn):
        if isinstance(n, (ast.For, ast.While)):
            yield n


def _called(node, names):
    """The simple/attr names of `names` that `node`'s subtree calls."""
    out = set()
    for n in _own_walk(node):
        if isinstance(n, ast.Call):
            cname = call_name(n)
            if cname and cname.split(".")[-1] in names:
                out.add(cname.split(".")[-1])
    return out


def _steady_regions(mod, table, scopes):
    """[(qual, fn, region_node)] — regions executed once per steady-state
    iteration. A loop body that calls a compiled program (or a same-module
    program-calling helper) is a region; so is the WHOLE body of a helper
    that a loop invokes each iteration, directly or through other
    helpers."""
    prog_callers = {fn.name for qual, fn in scopes
                    if _program_calls(fn, table, qual)}
    by_name = {}
    for qual, fn in scopes:
        by_name.setdefault(fn.name, []).append(fn)
    # a function that calls a program-calling helper is one itself
    grew = True
    while grew:
        grew = False
        for name, fns in by_name.items():
            if name not in prog_callers and any(
                    _called(fn, prog_callers) for fn in fns):
                prog_callers.add(name)
                grew = True
    regions = []
    helpers_in_loops = set()
    for qual, fn in scopes:
        for loop in _loops(fn):
            direct = any(True for n in _own_walk(loop)
                         if isinstance(n, ast.Call)
                         and table.lookup_call(n, qual) is not None)
            via_helper = _called(loop, prog_callers)
            if direct or via_helper:
                regions.append((qual, fn, loop))
            helpers_in_loops |= via_helper
    # ... and so are the helpers that those helpers call
    todo = list(helpers_in_loops)
    while todo:
        for fn in by_name.get(todo.pop(), ()):
            new = _called(fn, prog_callers) - helpers_in_loops
            helpers_in_loops |= new
            todo.extend(new)
    for qual, fn in scopes:
        if fn.name in helpers_in_loops:
            regions.append((qual, fn, fn))
    return regions


def _region_bound_names(region):
    """Names assigned inside the region — per-iteration runtime data."""
    bound = set()
    for n in _own_walk(region):
        if isinstance(n, ast.Assign):
            for t in n.targets:
                _bind_targets(t, bound)
        elif isinstance(n, (ast.AugAssign, ast.AnnAssign)):
            _bind_targets(n.target, bound)
        elif isinstance(n, (ast.For, ast.AsyncFor)):
            _bind_targets(n.target, bound)
    return bound


def _data_derived(expr, bound):
    """A subexpression showing `expr` is derived from runtime data:
    `len(...)` of anything, or `.shape`/`.size` of a region-bound name.
    Returns (node, description) or None."""
    for n in ast.walk(expr):
        if isinstance(n, ast.Call):
            cname = call_name(n)
            if cname == "len":
                return n, "len(...)"
        elif isinstance(n, ast.Attribute) and n.attr in ("shape", "size"):
            base = dotted(n.value)
            if base and base.split(".")[0] in bound:
                return n, f"{base}.{n.attr}"
    return None


def _shape_from_data(mod, qual, region, bound, table, findings, seen):
    for n in _own_walk(region):
        if not isinstance(n, ast.Call):
            continue
        cname = call_name(n)
        last = cname.split(".")[-1] if cname else None
        if last in _SHAPED_CTORS and n.args:
            hit = _data_derived(n.args[0], bound)
            if hit is not None:
                key = ("shape", n.lineno)
                if key not in seen:
                    seen.add(key)
                    findings.append(Finding(
                        "retrace-shape-from-data", mod.relpath, n.lineno,
                        f"array dim from `{hit[1]}` inside a steady-state "
                        f"loop: every distinct value is a new trace of "
                        f"every consumer — pad to a fixed shape instead",
                        scope=qual, symbol=f"{last}:{hit[1]}"))
        info = table.lookup_call(n, qual)
        if info is not None:
            for i, a in enumerate(n.args):
                hit = _data_derived(a, bound)
                if hit is not None:
                    key = ("arg", n.lineno, i)
                    if key not in seen:
                        seen.add(key)
                        findings.append(Finding(
                            "retrace-shape-from-data", mod.relpath,
                            a.lineno,
                            f"compiled-program argument {i} derives from "
                            f"`{hit[1]}` inside a steady-state loop — a "
                            f"Python scalar is a trace CONSTANT, so each "
                            f"distinct value recompiles the program",
                            scope=qual, symbol=f"arg{i}:{hit[1]}"))


def _static_args(mod, qual, fn, table, findings, steady_nodes, bound,
                 seen):
    for n, info in _program_calls(fn, table, qual):
        if not info.static:
            continue
        for pos in sorted(info.static):
            if pos >= len(n.args):
                continue
            a = n.args[pos]
            if isinstance(a, (ast.List, ast.Set, ast.Dict)):
                key = ("unhashable", n.lineno, pos)
                if key not in seen:
                    seen.add(key)
                    findings.append(Finding(
                        "retrace-unstable-static-arg", mod.relpath,
                        a.lineno,
                        f"static_argnums position {pos} is fed an "
                        f"unhashable {type(a).__name__.lower()} literal — "
                        f"jit static args must be hashable (TypeError at "
                        f"call time); pass a tuple",
                        scope=qual, symbol=f"static{pos}"))
                continue
            if n in steady_nodes:
                hit = _data_derived(a, bound)
                name_hit = any(
                    isinstance(x, ast.Name) and x.id in bound
                    for x in ast.walk(a))
                if hit is not None or name_hit:
                    why = hit[1] if hit else "a per-iteration local"
                    key = ("varying", n.lineno, pos)
                    if key not in seen:
                        seen.add(key)
                        findings.append(Finding(
                            "retrace-unstable-static-arg", mod.relpath,
                            a.lineno,
                            f"static_argnums position {pos} derives from "
                            f"{why} inside a steady-state loop — every "
                            f"distinct value compiles a new program",
                            scope=qual, symbol=f"static{pos}"))


def _unordered_pytree(mod, qual, region, findings, seen):
    for n in _own_walk(region):
        iters = []
        if isinstance(n, ast.DictComp):
            iters = [g.iter for g in n.generators]
        elif isinstance(n, ast.Call):
            cname = call_name(n)
            if cname and cname.split(".")[-1] == "dict":
                for a in n.args:
                    if isinstance(a, (ast.GeneratorExp, ast.ListComp)):
                        iters.extend(g.iter for g in a.generators)
        for it in iters:
            if isinstance(it, ast.Call):
                iname = call_name(it)
                if iname and iname.split(".")[-1] in _SET_CTORS:
                    key = ("pytree", n.lineno)
                    if key not in seen:
                        seen.add(key)
                        findings.append(Finding(
                            "retrace-unordered-pytree", mod.relpath,
                            n.lineno,
                            f"dict built by iterating `{iname}(...)` in a "
                            f"steady-state region: set order varies per "
                            f"process, so the pytree structure (and the "
                            f"compiled program) differs run to run — "
                            f"iterate `sorted({iname}(...))`",
                            scope=qual, symbol=iname))


def run(modules):
    findings = []
    for mod in modules:
        table = resolve_programs(mod)
        if not (table.attr_progs or table.local_progs or table.factories):
            continue
        scopes = _scopes(mod.tree)
        regions = _steady_regions(mod, table, scopes)
        seen = set()
        # per-function steady call nodes + bound names for the static rule
        steady_by_fn = {}
        for qual, fn, region in regions:
            bound = _region_bound_names(region)
            _shape_from_data(mod, qual, region, bound, table, findings,
                             seen)
            _unordered_pytree(mod, qual, region, findings, seen)
            nodes, prev_bound = steady_by_fn.setdefault(
                (qual, fn), (set(), set()))
            nodes.update(n for n in _own_walk(region)
                         if isinstance(n, ast.Call))
            prev_bound.update(bound)
        # unhashable static-literal check runs everywhere; the run-varying
        # check only applies to a function's steady call nodes
        for qual, fn in scopes:
            nodes, bound = steady_by_fn.get((qual, fn), (set(), set()))
            _static_args(mod, qual, fn, table, findings, nodes, bound,
                         seen)
    return findings
