"""A scope's share of its roofline: the least time the chip could take for
the work done in the traced window, max(FLOPs / peak FLOP/s, bytes / peak
bytes/s), over the device self time of the operations that run under the
PROGRAM's scope `scope` in the executions of `module`, in percent.

The time is `xplane_scope_ms`'s join of the trace with the program's own
instruction -> scope table (its reader's lines, tables and rules: an
operation's self time, the execution whose interval holds its start, None
where more than `UNKNOWN_SHARE` of the time lies in instructions the table
does not hold), summed over the executions rather than averaged. The work
comes from the path's counters `<work>_flops` and `<work>_bytes`, computed
from shapes and lengths for the traced part of the window, so that
whatever implements the scope (XLA's fusions, a kernel) is judged on the
same work.

Params: `module`, `scope` (as `xplane_scope_ms`), `work`. None where the
program has no scope accessor, the path no such counters, or the trace no
matching execution."""
import re

from .xplane_scope_ms import UNKNOWN_SHARE, instruction, joined, tables


def scope_seconds(lines, params, scopes):
    """Summed self time (s) of the operations of every execution of
    `module` under `scope`; None as `xplane_scope_ms.reduce` has it."""
    module = re.compile(params["module"])
    scope = re.compile(params["scope"])
    runs = 0
    total = unknown = hit = 0.0
    for ops, modules, own, run_of in lines:
        table_of = {i: scopes.get(name.partition("(")[0], {})
                    for i, name in enumerate(modules.names)
                    if module.search(name)}
        runs += len(table_of)
        for name, ns, run in zip(ops.names, own, run_of):
            table = table_of.get(int(run))
            if table is None:
                continue
            total += ns
            found = table.get(instruction(name))
            if found is None:
                unknown += ns
            elif scope.search(found[0]):
                hit += ns
    if not runs or not total or unknown > UNKNOWN_SHARE * total:
        return None
    return 1e-9 * hit


def read(params, ctx):
    if ctx["trace"] is None:
        return None
    c = ctx["counters"]
    flops = c.get(params["work"] + "_flops")
    byts = c.get(params["work"] + "_bytes")
    if flops is None or byts is None:
        return None
    scopes = tables(params["module"])
    if scopes is None:
        return None
    seconds = scope_seconds(joined(ctx["trace"]), params, scopes)
    if not seconds:
        return None
    peaks = ctx["peaks"]
    least = max(flops / peaks["flops_per_s"], byts / peaks["bytes_per_s"])
    return 100.0 * least / seconds
