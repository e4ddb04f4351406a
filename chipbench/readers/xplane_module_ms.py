"""Device time per execution of the compiled programs whose module name
matches `pattern` (the `XLA Modules` line of the trace), in ms."""


def read(params, ctx):
    if ctx["trace"] is None:
        return None
    seconds, count = ctx["trace"].module_time_s(params["pattern"])
    if not count:
        return None
    return 1e3 * seconds / count
