"""Share of the window in which the train loop waited on an empty feed:
`profiler.feed_stats()['stall_data_us']` over the window, in percent."""


def read(params, ctx):
    stall = ctx["counters"].get("feed_stall_data_s")
    if stall is None:
        return None
    return 100.0 * stall / ctx["window_s"]
