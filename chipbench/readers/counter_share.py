"""One counter of the path over another, in percent: `params["part"]` over
`params["whole"]`, both differences of two `ContinuousEngine.stats()`
snapshots that the path put among its counters. A program whose `stats()`
lacks them (the parent of the PR that added them), or a window in which
the whole is 0, reads as None."""


def read(params, ctx):
    c = ctx["counters"]
    part, whole = c.get(params["part"]), c.get(params["whole"])
    if part is None or not whole:
        return None
    return 100.0 * part / whole
