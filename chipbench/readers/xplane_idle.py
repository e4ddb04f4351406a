"""Share of the traced window in which no operation ran on the device:
1 - union of the device-op intervals over the window, in percent."""


def read(params, ctx):
    if ctx["trace"] is None:
        return None
    busy_s, window_s = ctx["trace"].busy_and_window_s()
    return 100.0 * (1.0 - busy_s / window_s)
