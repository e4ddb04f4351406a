"""The whole step's share of the chip's peak: useful FLOPs of the work
done in the window (counter `useful_flops`, from `chipbench/work.py`)
over window x peak FLOP/s x chips, in percent."""


def read(params, ctx):
    flops = ctx["counters"].get("useful_flops")
    if flops is None:
        return None
    chips = ctx["counters"].get("chips", 1)
    return 100.0 * flops / (ctx["window_s"] * chips
                            * ctx["peaks"]["flops_per_s"])
