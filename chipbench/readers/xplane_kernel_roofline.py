"""A kernel's share of its roofline: the least time the chip could take
for the work done in the traced window, max(FLOPs / peak FLOP/s, bytes /
peak bytes/s), over the device time of the operations whose name matches
`pattern`, in percent. The work comes from the path's counters
`<work>_flops` and `<work>_bytes` (computed by `chipbench/work.py` from
shapes and lengths, for the traced part of the window)."""


def read(params, ctx):
    if ctx["trace"] is None:
        return None
    seconds, count = ctx["trace"].op_time_s(params["pattern"])
    c = ctx["counters"]
    flops = c.get(params["work"] + "_flops")
    byts = c.get(params["work"] + "_bytes")
    if not count or not seconds or flops is None or byts is None:
        return None
    peaks = ctx["peaks"]
    least = max(flops / peaks["flops_per_s"], byts / peaks["bytes_per_s"])
    return 100.0 * least / seconds
