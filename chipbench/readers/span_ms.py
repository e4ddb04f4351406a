"""Host time per unit of work, from the program's own spans: the self
time of the spans whose name matches `sum` (a span's duration less what
the matching spans inside it cover, so nothing counts twice) over the
number of spans whose name matches `per`, in ms.

The spans come through the program's public accessor
(`incubator_mxnet_tpu.profiler.events()`: the in-memory buffer that
`telemetry.span` fills while the benchmark's `jax.profiler` session is
open), not from the `.xplane.pb`. Kept are the spans that start in the
traced interval: from `skip_head_s` after the buffer's first span (the
session's start; the configurations' `settle_s`) for `ctx["window_s"]`.
A program that records no such spans (the parent of the PR that added
this reader) reads as None."""
import re


def spans():
    """[(name, thread, start_us, dur_us)] of the program's buffer, or []
    where the program has no such accessor."""
    try:
        from incubator_mxnet_tpu import profiler
        events = profiler.events()
    except (ImportError, AttributeError):
        return []
    return [(e["name"], e["tid"], e["ts"], e["dur"]) for e in events
            if e.get("ph") == "X"]


def self_time_us(intervals):
    """Length of the union of [(start, end)]: the summed self time of
    properly nested spans of one thread."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def reduce(all_spans, params, window_s):
    """The metric from [(name, thread, start_us, dur_us)]."""
    if not all_spans:
        return None
    lo = min(s[2] for s in all_spans) + 1e6 * params.get("skip_head_s", 0.0)
    hi = lo + 1e6 * window_s
    kept = [s for s in all_spans if lo <= s[2] <= hi]
    per = re.compile(params["per"])
    count = sum(1 for s in kept if per.search(s[0]))
    if not count:
        return None
    summed = re.compile(params["sum"])
    by_thread = {}
    for name, thread, start, dur in kept:
        if summed.search(name):
            by_thread.setdefault(thread, []).append((start, start + dur))
    total_us = sum(self_time_us(v) for v in by_thread.values())
    return 1e-3 * total_us / count


def read(params, ctx):
    return reduce(spans(), params, ctx["window_s"])
