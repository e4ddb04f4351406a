"""Device time, per micro-step of a compiled program, of the operations
whose name matches `pattern`: their summed time over (executions of the
modules that match `module`) x the counter `steps` (how many micro-steps
one execution holds), in ms. The trace's operation names are HLO text
without the program's scopes, so a pattern names the operations by the
shapes they produce or read (`layer_metrics/*.json` say which)."""


def read(params, ctx):
    if ctx["trace"] is None:
        return None
    seconds, count = ctx["trace"].op_time_s(params["pattern"])
    _, runs = ctx["trace"].module_time_s(params["module"])
    steps = ctx["counters"].get(params["steps"])
    if not count or not runs or not steps:
        return None
    return 1e3 * seconds / (runs * steps)
