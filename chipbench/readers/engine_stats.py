"""Slot occupancy of the engine over the window: `mean_active_slots`
(the difference of two `ContinuousEngine.stats()` snapshots: `active_sum`
over `decode_iterations`) over `max_slots`, in percent."""


def read(params, ctx):
    c = ctx["counters"]
    if not c.get("decode_iterations"):
        return None
    return 100.0 * c["active_sum"] / c["decode_iterations"] / c["max_slots"]
