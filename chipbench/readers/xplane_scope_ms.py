"""Device time, per execution of a compiled program, of the operations
under one of the PROGRAM's own scopes (`jax.named_scope`), in ms.

The trace names an operation by its HLO text, which holds no scope; the
compiled program's text does (`metadata={op_name=...}`). The program
publishes that table, instruction name -> (scope path, pass), through
`incubator_mxnet_tpu.profiler.program_scopes(module)`, and this reader
joins it with the trace's `XLA Ops` and `XLA Modules` lines:

- an operation belongs to the module execution whose interval holds its
  start (instruction names repeat across programs);
- its time is its SELF time: its duration less what the operations
  nested in it on the same line cover (a `while` holds its body's events);
- the metric is the summed self time of the operations of the executions
  of `module` whose scope path matches `scope` (and whose pass is `pass`,
  where given), over the executions.

Params: `module` (a regular expression on the `XLA Modules` line),
`scope` (one on the scope path; `^$` is "no scope of the program's"),
optional `pass` (`fwd` / `bwd`).
None where the program has no such accessor (the parent of the PR that
added this reader), where no execution matched, or where more than
`UNKNOWN_SHARE` of the executions' device time lies in instruction names
the table does not hold: a stale table must not read as a small number."""
import re

UNKNOWN_SHARE = 0.02
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def tables(pattern):
    """{module name: {instruction: (scope path, pass)}} from the program,
    or None where it has no such accessor."""
    try:
        from incubator_mxnet_tpu import profiler
        return profiler.program_scopes(pattern)
    except (ImportError, AttributeError):
        return None


def self_times(starts, durs):
    """Each event's duration less the part of it that the events nested
    directly in it cover (events of one line; an event that starts inside
    another is nested in it)."""
    import numpy as np
    order = np.lexsort((-durs, starts))        # by start, the longer first
    ends = starts + durs
    own = durs.copy()
    stack = []
    for i in order:
        while stack and ends[stack[-1]] <= starts[i]:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(ends[i], ends[stack[-1]]) - starts[i]
        stack.append(i)
    return np.maximum(own, 0.0)


def executions(ops, modules):
    """For each event of the line `ops`, the index of the event of
    `modules` whose interval holds its start, or -1."""
    import numpy as np
    order = np.argsort(modules.starts, kind="stable")
    m_starts = modules.starts[order]
    m_ends = m_starts + modules.durs[order]
    at = np.searchsorted(m_starts, ops.starts, side="right") - 1
    inside = (at >= 0) & (ops.starts < m_ends[np.maximum(at, 0)])
    return np.where(inside, order[np.maximum(at, 0)], -1)


def instruction(name):
    """`fusion.17` from an operation's whole HLO text."""
    return name.partition(" = ")[0].strip().lstrip("%")


_memo = [None, None]      # the last trace joined, and its lines


def joined(trace):
    """[(ops line, modules line, self ns by op, module event by op)] per
    device; kept for the trace's other metrics."""
    if _memo[0] is not trace:
        lines = []
        for dev in trace.devices.values():
            ops, modules = dev.get(OPS_LINE), dev.get(MODULES_LINE)
            if ops is None or modules is None or not len(ops.names) \
                    or not len(modules.names):
                continue
            lines.append((ops, modules, self_times(ops.starts, ops.durs),
                          executions(ops, modules)))
        _memo[:] = [trace, lines]
    return _memo[1]


def reduce(lines, params, scopes):
    """The metric from `joined`'s lines and the program's tables."""
    module = re.compile(params["module"])
    scope = re.compile(params["scope"])
    which = params.get("pass")
    runs = 0
    total = unknown = hit = 0.0
    for ops, modules, own, run_of in lines:
        table_of = {}                    # module event -> its table
        for i, name in enumerate(modules.names):
            if module.search(name):
                table_of[i] = scopes.get(name.partition("(")[0], {})
        runs += len(table_of)
        verdict = {}                     # (table id, op name) -> 0 / 1 / None
        for name, ns, run in zip(ops.names, own, run_of):
            table = table_of.get(int(run))
            if table is None:
                continue
            total += ns
            key = (id(table), name)
            if key not in verdict:
                found = table.get(instruction(name))
                verdict[key] = None if found is None else int(
                    bool(scope.search(found[0]))
                    and which in (None, found[1]))
            if verdict[key] is None:
                unknown += ns
            else:
                hit += ns * verdict[key]
    if not runs or not total or unknown > UNKNOWN_SHARE * total:
        return None
    return 1e-6 * hit / runs


def read(params, ctx):
    if ctx["trace"] is None:
        return None
    scopes = tables(params["module"])
    if scopes is None:
        return None
    return reduce(joined(ctx["trace"]), params, scopes)
