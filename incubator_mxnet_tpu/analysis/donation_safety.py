"""Donation-safety pass: buffer-donation dataflow over compiled programs.

`jax.jit(fn, donate_argnums=...)` consumes the donated inputs: after the
call the caller-side arrays are dead and any use raises the notoriously
delayed "Array has been deleted" — on TPU. On CPU donation is a no-op, so
the bug class ships silently through CI and detonates on hardware. Both
confirmed PR-14 review bugs were in this class. This pass models the
package's donation idioms statically:

  * programs bound directly: `prog = jax.jit(fn, donate_argnums=(1, 2))`
    (locals resolve within their function; `self.X` / module attributes
    resolve module-wide by their last segment);
  * program FACTORIES: a function whose body builds and returns a donated
    jit (`prefill_program`, `decode_program`, `fused_update_all`, ...) —
    any `y = obj.factory(...)` bind, and the direct `obj.factory(w)(...)`
    call form, inherit the factory's donated positions.

Two rules:

  donation-use-after-donate    a name passed in a donated position is
                               read / returned / re-captured / re-donated
                               before being rebound from program output.
                               Loop bodies are analyzed for two
                               iterations, so the "buffers fetched once
                               outside the steady loop" variant (donate,
                               loop around, donate the same dead array
                               again) is caught too.
  donation-unrestored-on-error an `except` handler that swallows errors
                               raised around a donated call without
                               restoring the consumed buffers (no
                               re-raise, no `*.reallocate()` call): the
                               program may have consumed its inputs
                               before dying, leaving the pool/slab dead —
                               the PR-14 `pool.reallocate()` class.

Comparisons are per-module and literal, like every mxlint pass: donation
that only happens behind computed indirection is unauditable and should
be rewritten, not special-cased.
"""
from __future__ import annotations

import ast

from .core import Finding, call_name, dotted

__all__ = ["run", "resolve_programs", "ProgramTable"]

RULES = ("donation-use-after-donate", "donation-unrestored-on-error")

_JIT_NAMES = {"jit"}


class ProgInfo:
    """One compiled program's trace-time contract, as far as the module's
    literals declare it."""

    __slots__ = ("donated", "static", "line")

    def __init__(self, donated=frozenset(), static=frozenset(), line=0):
        self.donated = donated      # frozenset of positions, or None=unknown
        self.static = static        # frozenset of static_argnums positions
        self.line = line

    @property
    def is_donating(self):
        return self.donated is None or bool(self.donated)


class ProgramTable:
    """Per-module resolution of names that are compiled programs.

    `attr_progs` — names usable module-wide (self.X / CLS.X / module
    globals), keyed by the LAST dotted segment; `local_progs` — plain-name
    binds keyed by enclosing scope qualname; `factories` — functions that
    build and return a jit, keyed by simple name.
    """

    def __init__(self):
        self.attr_progs = {}
        self.local_progs = {}
        self.factories = {}

    def lookup_call(self, node, scope):
        """ProgInfo for a Call node if its callee is a known program (or a
        direct factory call `obj.factory(w)(...)`), else None."""
        cname = call_name(node)
        if cname:
            last = cname.split(".")[-1]
            info = self.local_progs.get(scope, {}).get(cname)
            if info is None and "." not in cname:
                info = self.local_progs.get(scope, {}).get(last)
            if info is None:
                info = self.attr_progs.get(last)
            if info is not None:
                return info
        # obj.factory(w)(args...): the callee is itself a factory call
        if isinstance(node.func, ast.Call):
            inner = call_name(node.func)
            if inner:
                fac = self.factories.get(inner.split(".")[-1])
                if fac is not None:
                    return fac
        return None


def _int_positions(node):
    """frozenset of int positions from a donate_argnums/static_argnums
    literal (int, tuple/list of ints); None when the value is computed."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return frozenset((node.value,))
    if isinstance(node, (ast.Tuple, ast.List)):
        out = set()
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, int):
                out.add(el.value)
            else:
                return None
        return frozenset(out)
    return None


def _jit_call_info(node):
    """ProgInfo when `node` is a `jax.jit(...)`-shaped Call, else None."""
    if not isinstance(node, ast.Call):
        return None
    cname = call_name(node)
    if not cname or cname.split(".")[-1] not in _JIT_NAMES:
        return None
    donated = frozenset()
    static = frozenset()
    for kw in node.keywords:
        if kw.arg == "donate_argnums":
            donated = _int_positions(kw.value)
        elif kw.arg in ("static_argnums", "static_argnames"):
            static = _int_positions(kw.value) or frozenset()
    return ProgInfo(donated=donated, static=static, line=node.lineno)


def _find_jit_in_expr(value):
    """The first jit-call ProgInfo anywhere inside an assigned expression
    (handles `maybe_wrap_donated(jax.jit(...), ...)` wrapping)."""
    for node in ast.walk(value):
        info = _jit_call_info(node)
        if info is not None:
            return info
    return None


def _scopes(tree):
    """[(qualname, funcdef)] for every function, nested included."""
    out = []

    def visit(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = f"{prefix}.{child.name}" if prefix else child.name
                out.append((q, child))
                visit(child, q)
            elif isinstance(child, ast.ClassDef):
                q = f"{prefix}.{child.name}" if prefix else child.name
                visit(child, q)
            else:
                visit(child, prefix)

    visit(tree, "")
    return out


def resolve_programs(mod):
    """Build the module's ProgramTable (shared with retrace_hazard)."""
    table = ProgramTable()
    scopes = _scopes(mod.tree)

    # 1. direct binds + factory discovery
    for qual, fn in scopes:
        returned_names = set()
        jit_locals = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                info = _find_jit_in_expr(node.value)
                if info is None:
                    continue
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        jit_locals[t.id] = info
                        table.local_progs.setdefault(qual, {})[t.id] = info
                    else:
                        d = dotted(t)
                        if d:
                            table.attr_progs[d.split(".")[-1]] = info
            elif isinstance(node, ast.Return) and node.value is not None:
                info = _jit_call_info(node.value)
                if info is not None:
                    table.factories[fn.name] = info
                elif isinstance(node.value, ast.Name):
                    returned_names.add(node.value.id)
        for name in returned_names:
            if name in jit_locals:
                table.factories.setdefault(fn.name, jit_locals[name])

    # module-level binds (outside any function)
    for node in mod.tree.body:
        if isinstance(node, ast.Assign):
            info = _find_jit_in_expr(node.value)
            if info is None:
                continue
            for t in node.targets:
                d = dotted(t)
                if d:
                    table.attr_progs[d.split(".")[-1]] = info

    # 2. binds from factory calls: `self._prog = model.decode_program(...)`
    for qual, fn in scopes:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign) \
                    or not isinstance(node.value, ast.Call):
                continue
            cname = call_name(node.value)
            if not cname:
                continue
            fac = table.factories.get(cname.split(".")[-1])
            if fac is None:
                continue
            for t in node.targets:
                if isinstance(t, ast.Name):
                    table.local_progs.setdefault(qual, {})[t.id] = fac
                else:
                    d = dotted(t)
                    if d:
                        table.attr_progs.setdefault(d.split(".")[-1], fac)
    return table


# ---------------------------------------------------------------------------
# rule 1: donation-use-after-donate — linear event-stream dataflow
# ---------------------------------------------------------------------------
def _arg_name(node):
    """Trackable donated-argument name: a plain Name or a dotted attr."""
    if isinstance(node, ast.Name):
        return node.id
    return dotted(node)


def _bind_targets(target, out):
    """All names a (possibly tuple) assignment target rebinds."""
    if isinstance(target, ast.Name):
        out.add(target.id)
    elif isinstance(target, (ast.Tuple, ast.List)):
        for el in target.elts:
            _bind_targets(el, out)
    elif isinstance(target, ast.Starred):
        _bind_targets(target.value, out)
    else:
        d = dotted(target)
        if d:
            out.add(d)


class _Flow:
    """Branch-aware abstract interpreter for one function body.

    State is `poisoned: {name -> (prog label, donate line)}`. `If`
    branches run on copies and merge by union of the NON-terminated
    branches (a branch ending in return/raise/break/continue contributes
    no out-state, so `if x: return prog(p, k, v)` / `return prog(p, k,
    v)` pairs don't cross-poison). Loop bodies run twice so poison from
    iteration N reaches iteration N+1's reads — the "buffers fetched once
    outside the loop" bug."""

    def __init__(self, mod, table, scope, findings):
        self.mod = mod
        self.table = table
        self.scope = scope
        self.findings = findings
        self.poisoned = {}
        self.reported = set()     # (name, line): loops replay bodies

    # -- events -----------------------------------------------------------
    def read(self, name, line):
        hit = self.poisoned.get(name)
        if hit and (name, line) not in self.reported:
            self.reported.add((name, line))
            self.findings.append(Finding(
                "donation-use-after-donate", self.mod.relpath, line,
                f"`{name}` is read here but was donated to `{hit[0]}` at "
                f"line {hit[1]} — rebind it from the program's output "
                f"(donated buffers die with the call)",
                scope=self.scope, symbol=name))
            del self.poisoned[name]

    def donated_call(self, node, info):
        names = _donated_call_args(node, info)
        label = _prog_label(node)
        # re-donating / re-passing a dead name IS a use
        for name, _pos in names:
            hit = self.poisoned.get(name)
            if hit and (name, node.lineno) not in self.reported:
                self.reported.add((name, node.lineno))
                self.findings.append(Finding(
                    "donation-use-after-donate", self.mod.relpath,
                    node.lineno,
                    f"`{name}` was donated to `{hit[0]}` at line "
                    f"{hit[1]} and is passed to `{label}` again without "
                    f"being rebound from program output — on TPU this "
                    f"is a use of a deleted array",
                    scope=self.scope, symbol=name))
        for name, _pos in names:
            self.poisoned[name] = (label, node.lineno)

    def bind(self, target):
        names = set()
        _bind_targets(target, names)
        for name in names:
            self.poisoned.pop(name, None)

    # -- expressions (evaluation order) ------------------------------------
    def expr(self, node):
        """A donated call's argument reads happen BEFORE the call consumes
        them (legitimate pre-call uses); its donated-position args are
        checked by donated_call itself (re-donation of a dead name)."""
        if node is None or isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            return
        if isinstance(node, ast.Call):
            info = self.table.lookup_call(node, self.scope)
            if info is not None and info.is_donating:
                donated = info.donated or frozenset()
                for i, a in enumerate(node.args):
                    if i not in donated:
                        self.expr(a)
                for kw in node.keywords:
                    self.expr(kw.value)
                self.donated_call(node, info)
                return
            if isinstance(node.func, (ast.Call, ast.Subscript)):
                self.expr(node.func)
            for a in node.args:
                self.expr(a)
            for kw in node.keywords:
                self.expr(kw.value)
            return
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            self.read(node.id, node.lineno)
            return
        if isinstance(node, ast.Attribute) \
                and isinstance(node.ctx, ast.Load):
            d = dotted(node)
            if d:
                self.read(d, node.lineno)
                return
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.expr):
                self.expr(child)
            elif isinstance(child, ast.keyword):
                self.expr(child.value)
            elif isinstance(child, ast.comprehension):
                self.expr(child.iter)
                for c in child.ifs:
                    self.expr(c)

    # -- statements --------------------------------------------------------
    def stmts(self, body):
        """Run a statement list; True when the path terminated early."""
        for s in body:
            if self.stmt(s):
                return True
        return False

    def _branches(self, arms):
        """Run each arm from the current state on a copy; merge the
        non-terminated out-states by union."""
        entry = dict(self.poisoned)
        outs = []
        for arm in arms:
            self.poisoned = dict(entry)
            if not self.stmts(arm):
                outs.append(self.poisoned)
        if not outs:
            self.poisoned = dict(entry)
            return True
        merged = {}
        for out in outs:
            merged.update(out)
        self.poisoned = merged
        return False

    def stmt(self, s):
        if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.ClassDef, ast.Import, ast.ImportFrom,
                          ast.Global, ast.Nonlocal, ast.Pass)):
            return False
        if isinstance(s, ast.Assign):
            self.expr(s.value)
            for t in s.targets:
                self.bind(t)
        elif isinstance(s, ast.AugAssign):
            self.expr(s.value)
            # aug-assign READS its target before writing it back
            self.expr(s.target)
            self.bind(s.target)
        elif isinstance(s, ast.AnnAssign):
            self.expr(s.value)
            if s.value is not None:
                self.bind(s.target)
        elif isinstance(s, (ast.For, ast.AsyncFor)):
            self.expr(s.iter)
            for _ in range(2):
                self.bind(s.target)
                self.stmts(s.body)
            self.stmts(s.orelse)
        elif isinstance(s, ast.While):
            for _ in range(2):
                self.expr(s.test)
                self.stmts(s.body)
            self.stmts(s.orelse)
        elif isinstance(s, ast.If):
            self.expr(s.test)
            return self._branches([s.body, s.orelse])
        elif isinstance(s, (ast.With, ast.AsyncWith)):
            for item in s.items:
                self.expr(item.context_expr)
                if item.optional_vars is not None:
                    self.bind(item.optional_vars)
            return self.stmts(s.body)
        elif isinstance(s, ast.Try):
            arms = [s.body + s.orelse] + [h.body for h in s.handlers]
            term = self._branches(arms)
            if s.finalbody:
                term = self.stmts(s.finalbody) or term
            return term
        elif isinstance(s, ast.Return):
            self.expr(s.value)
            return True
        elif isinstance(s, ast.Raise):
            for child in ast.iter_child_nodes(s):
                self.expr(child)
            return True
        elif isinstance(s, (ast.Break, ast.Continue)):
            return True
        elif isinstance(s, (ast.Expr, ast.Assert, ast.Delete)):
            for child in ast.iter_child_nodes(s):
                if isinstance(child, ast.expr):
                    self.expr(child)
        else:
            for child in ast.iter_child_nodes(s):
                if isinstance(child, ast.expr):
                    self.expr(child)
        return False


def _donated_call_args(node, info):
    """[(name, position)] of trackable names at donated positions."""
    if info.donated is None:
        return []
    out = []
    for pos in sorted(info.donated):
        if pos < len(node.args):
            name = _arg_name(node.args[pos])
            if name:
                out.append((name, pos))
    return out


def _prog_label(node):
    cname = call_name(node)
    if cname:
        return cname
    if isinstance(node.func, ast.Call):
        return (call_name(node.func) or "<program>") + "(...)"
    return "<program>"


def _use_after_donate(mod, table, qual, fn, findings):
    flow = _Flow(mod, table, qual, findings)
    flow.stmts(fn.body)


# ---------------------------------------------------------------------------
# rule 2: donation-unrestored-on-error
# ---------------------------------------------------------------------------
_RESTORE_CALLEES = {"reallocate"}


def _own_walk(node):
    """ast.walk that does NOT descend into nested function/class defs."""
    todo = list(ast.iter_child_nodes(node))
    while todo:
        n = todo.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda, ast.ClassDef)):
            continue
        yield n
        todo.extend(ast.iter_child_nodes(n))


def _donating_functions(mod, table, scopes):
    """Simple names of functions whose body (transitively, via same-module
    simple-name calls) performs a donated-program call — so a try/except
    around `self._iterate()` is recognized as guarding the donated
    decode call two levels down."""
    direct = set()
    calls = {}                      # fn simple name -> {callee last segs}
    for qual, fn in scopes:
        callees = set()
        for n in _own_walk(fn):
            if not isinstance(n, ast.Call):
                continue
            info = table.lookup_call(n, qual)
            if info is not None and info.is_donating:
                direct.add(fn.name)
            cname = call_name(n)
            if cname:
                callees.add(cname.split(".")[-1])
        calls.setdefault(fn.name, set()).update(callees)
    # fixpoint: callers of donating functions donate too
    changed = True
    while changed:
        changed = False
        for name, callees in calls.items():
            if name not in direct and callees & direct:
                direct.add(name)
                changed = True
    return direct


def _contains_donated_call(body, table, scope, donating):
    """(node, label) of the first donated-program call lexically inside
    `body` (not descending into nested defs) — directly, or via a call to
    a same-module function that donates transitively. Else None."""
    via = None
    for s in body:
        nodes = [s] if isinstance(s, ast.Call) else []
        nodes += list(_own_walk(s))
        for n in nodes:
            if not isinstance(n, ast.Call):
                continue
            info = table.lookup_call(n, scope)
            if info is not None and info.is_donating:
                return n, _prog_label(n)
            cname = call_name(n)
            if via is None and cname \
                    and cname.split(".")[-1] in donating:
                via = (n, f"{cname}()")
    return via


_BROAD_EXC = {"Exception", "BaseException", "RuntimeError"}


def _handler_is_broad(handler):
    """True when the handler can swallow a compiled program's runtime
    failure: bare `except:`, or a type (or tuple member) named Exception /
    BaseException / RuntimeError. Narrow custom-exception handlers
    (`except Reject:`) are control flow, not donation swallowing."""
    if handler.type is None:
        return True
    types = handler.type.elts if isinstance(handler.type, ast.Tuple) \
        else [handler.type]
    for t in types:
        d = dotted(t)
        if d and d.split(".")[-1] in _BROAD_EXC:
            return True
    return False


def _handler_restores(handler):
    """True when the except handler re-raises or restores donated state
    (a `*.reallocate()` call)."""
    for n in _own_walk(handler):
        if isinstance(n, ast.Raise):
            return True
        if isinstance(n, ast.Call):
            cname = call_name(n)
            if cname and cname.split(".")[-1] in _RESTORE_CALLEES:
                return True
    return False


def _unrestored_on_error(mod, table, qual, fn, findings, donating):
    for node in _own_walk(fn):
        if not isinstance(node, ast.Try):
            continue
        hit = _contains_donated_call(node.body, table, qual, donating)
        if hit is None:
            continue
        _call, label = hit
        for handler in node.handlers:
            if not _handler_is_broad(handler) or _handler_restores(handler):
                continue
            findings.append(Finding(
                "donation-unrestored-on-error", mod.relpath,
                handler.lineno,
                f"except handler swallows errors around donated call "
                f"`{label}` without restoring the consumed buffers — "
                f"re-raise or call `.reallocate()` on the owning pool "
                f"(a program that dies mid-execution may already have "
                f"consumed its donated inputs)",
                scope=qual, symbol=label))


def run(modules):
    findings = []
    for mod in modules:
        table = resolve_programs(mod)
        if not (table.attr_progs or table.local_progs or table.factories):
            continue
        scopes = _scopes(mod.tree)
        donating = _donating_functions(mod, table, scopes)
        for qual, fn in scopes:
            _use_after_donate(mod, table, qual, fn, findings)
            _unrestored_on_error(mod, table, qual, fn, findings, donating)
    return findings
